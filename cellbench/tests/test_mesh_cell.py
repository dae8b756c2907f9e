"""The cell ``jlt_apply_mesh4`` (the dense sketch of an [MC,MR] operand on a
2 × 2 mesh) at tiny sizes on forced host devices of the CPU: the manifest's
entries, the configuration's statements, the chip's share of the counts, the
two readers of the layer ``mesh`` on a hand-made ``Reduction`` with four
device planes, and the driver's check — a sound run, the lower-precision
control and a replicated result. Nothing here is a device metric.

Needs four CPU devices: alone, this file asks jax for them before the
backend starts; where the backend is up with fewer
(``XLA_FLAGS=--xla_force_host_platform_device_count=4`` not set), the tests
that run the driver skip.
"""

import dataclasses
import importlib
import json
import time

import jax
import pytest

try:        # before the backend starts
    jax.config.update("jax_num_cpu_devices", 4)
except RuntimeError:    # the backend is up: the fixture decides
    pass

from cellbench import collectives, harness, roofline  # noqa: E402
from cellbench import trace as trace_mod  # noqa: E402
from cellbench.counts import dense_sketch_mesh as counts  # noqa: E402

CELL = "jlt_apply_mesh4"
CONFIG = "jlt_mcmr_262144x16384_s1024"
MS, RATE = "collective_ms.apply", "collective_rate.apply"
# the cell's shape at a size a CPU run holds; norm_dev is statistical, about
# |z|·√(2/(s·n))/2, restated at the same ten sigmas as conftest's TINY
TINY = {"m": 256, "n": 1024, "s": 128, "check_rows": 32,
        "limits": {"rel_max": 1e-4, "norm_dev": 2e-2, "operator_mean_z": 6.0,
                   "operator_var_z": 6.0, "layout_defect": 0}}


@pytest.fixture
def cell():
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices: XLA_FLAGS="
                    "--xla_force_host_platform_device_count=4")
    whole = harness.load_cell(CELL)
    return dataclasses.replace(whole, config={**whole.config, **TINY})


def run(cell, seed=7, step_wrapper=None, seconds=0.3):
    return harness.run_cell(cell, seed, seconds, False,
                            t_start=time.perf_counter(),
                            step_wrapper=step_wrapper)


def driver_of(cell):
    return importlib.import_module(f"cellbench.drivers.{cell.traffic['driver']}")


# -- the manifest and the configuration --------------------------------------


def test_the_manifest_entries_resolve():
    whole = harness.load_cell(CELL)
    assert whole.chips == 4 and whole.config_name == CONFIG
    assert whole.traffic_name == "apply_grid2d"
    assert whole.traffic == {**whole.traffic, "driver": "sketch_apply_mesh",
                             "loop": "closed", "callers": 1,
                             "latency_metric": "apply_ms", "warm_steps": 4,
                             "trace_seconds": 4}
    assert {m["name"] for m in whole.end_to_end} == {"apply_ms", "setup_s"}
    assert {m["name"] for m in whole.per_layer} == {
        "sketch_device_ms.apply", "sketch_roofline.apply", "device_idle.apply",
        "sketch_host_ms.apply", "stream_key_ms.apply", "sketch_plan_ms.apply",
        "sketch_dispatch_ms.apply", "setup_import_s", "setup_lower_s",
        "setup_compile_s", "idle_before_enqueue_ms.apply",
        "idle_after_enqueue_ms.apply", MS, RATE}
    manifest = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    for name, unit, better in ((MS, "ms", "lower"), (RATE, "GB/s", "higher")):
        assert next(m for m in manifest["per_layer"] if m["name"] == name) == {
            "name": name, "unit": unit, "better": better,
            "source": "device_trace", "layer": "mesh", "moves": "apply_ms",
            "workloads": [CELL]}
    # the one four-chip cell, and no other cell reads the mesh layer
    assert [w["name"] for w in manifest["workloads"] if w["chips"] == 4] == [CELL]
    # every file the entries name is there, and every reader loads
    for m in whole.per_layer:
        assert callable(harness._reader(m["name"]))
    for module in ("drivers.sketch_apply_mesh", "counts.dense_sketch_mesh",
                   "references.dense_sketch", "loops.closed"):
        importlib.import_module(f"cellbench.{module}")


def test_the_configuration_states_what_the_contract_asks():
    cfg = harness.load_cell(CELL).config
    assert (cfg["family"], cfg["dtype"], cfg["dimension"]) == (
        "JLT", "float32", "rowwise")
    assert (cfg["m"], cfg["n"], cfg["s"], cfg["grid"], cfg["panels"]) == (
        262144, 16384, 1024, [2, 2], 2)
    assert set(cfg["reduced"]) == {"rows"}
    for key in ("source", "layout", "guarantees", "deployment", "assumed",
                "memory", "precision"):
        assert cfg[key]
    assert (cfg["counts"], cfg["reference"]) == ("dense_sketch_mesh", "dense_sketch")
    for key in ("m", "n", "k", "panels", "data"):
        assert cfg["assumed"][key]
    manifest = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert entry["source"] == cfg["source"] and len(cfg["source"]) <= 200
    assert entry["reduced"] == ["rows"]
    # the one-chip dense cell's four limits, and the layout held exactly
    one_chip = harness.load_cell("jlt_apply").config["limits"]
    assert cfg["limits"] == {**one_chip, "layout_defect": 0}
    assert cfg["precision"] == harness.load_cell("jlt_apply").config["precision"]


# -- the counts: a chip's share ----------------------------------------------


def test_counts_are_one_chips_share():
    cfg = harness.load_cell(CELL).config
    work = counts.work(cfg)
    assert work == {"flops": 2 * 131072 * 8192 * 1024,
                    "bytes": (131072 * 8192 + 131072 * 512) * 4}
    # two jlt_apply panels' operations a chip, and a quarter of the whole
    one_chip = importlib.import_module("cellbench.counts.dense_sketch").work(
        harness.load_cell("jlt_apply").config)
    assert work["flops"] == 2 * one_chip["flops"]
    assert 4 * work["flops"] == 2 * cfg["m"] * cfg["n"] * cfg["s"]
    least, bound = roofline.least_time(work, roofline.peaks("TPU v5 lite"))
    assert bound == "flops" and 11.0e-3 < least < 11.4e-3


# -- the readers of the layer "mesh" on a hand-made reduction ----------------

OPERATIONS, PLANES = 20, 4
KERNEL_S, SCATTER_S = 36.0e-3, 4.0e-3       # an apply's, on one chip
SENT = 131072 * 1024 * 4 // 2               # a reduce-scatter over a pair


def run_of(op_seconds, operations=OPERATIONS, traced=True):
    busy = {f"/device:TPU:{d}": operations * (KERNEL_S + SCATTER_S) + 1e-3 * d
            for d in range(PLANES)}
    reduction = trace_mod.Reduction(
        window_s=operations * 45e-3, busy_s=sum(busy.values()) / PLANES,
        busy_s_by_device=busy, n_ops=3 * operations * PLANES,
        op_seconds=op_seconds, gap_seconds={})
    return harness.Run(cell=None, device_kind="cpu", operations=operations,
                       trace=reduction if traced else None)


def summed(seconds):            # op_seconds are sums over the planes
    return OPERATIONS * PLANES * seconds


@pytest.fixture
def ring():
    from libskylark_tpu.telemetry import metrics, trace

    before = metrics._ENABLED
    trace.clear_finished()
    yield trace
    metrics._ENABLED = before
    trace.clear_finished()


def dispatches(ring, count, **attrs):
    for k in range(count):
        span = ring.Span("sketch.dispatch", f"apply-{k}", None, None,
                         dict(attrs))
        span.t_start_ns, span.t_end_ns = k * 1000, k * 1000 + 10
        ring._FINISHED.append(span)


@pytest.mark.parametrize("name", ["reduce_scatter.7", "reduce-scatter.7",
                                  "all-reduce-start.1", "all-gather-done.2",
                                  "collective-permute.3", "all_to_all.4"])
def test_collective_ms_reads_the_mean_over_the_chips(name, capsys):
    ops = {"_fused_call.3": summed(KERNEL_S), name: summed(SCATTER_S)}
    value = harness._reader(MS)(run_of(ops))
    assert value == pytest.approx(1e3 * SCATTER_S)
    log = capsys.readouterr().out
    assert "[cellbench] mesh operations=20" in log
    assert "busy_ms_device_0=40.0000" in log and "busy_ms_device_3=40.1500" in log
    assert "busy_spread_ms=0.1500" in log and f"collective_ops={name}" in log


def test_collective_rate_reads_bytes_sent_over_one_chips_time(ring):
    ops = {"_fused_call.3": summed(KERNEL_S), "reduce_scatter.7": summed(SCATTER_S)}
    dispatches(ring, OPERATIONS + 5, path="mesh", collective_bytes=SENT)
    value = harness._reader(RATE)(run_of(ops))
    assert value == pytest.approx(SENT / SCATTER_S / 1e9)       # 67.1 GB/s


def test_no_collective_in_the_window_gives_no_number(ring, capsys):
    dispatches(ring, OPERATIONS, path="mesh", collective_bytes=SENT)
    ops = {"_fused_call.3": summed(KERNEL_S), "fusion.12": summed(1e-3),
           "reduce.5": summed(1e-4), "all.9": 1.0}
    assert not any(collectives.is_collective(n) for n in ops)
    for name in (MS, RATE):
        assert harness._reader(name)(run_of(ops)) is None
        assert harness._reader(name)(run_of({}, traced=False)) is None
        assert harness._reader(name)(run_of(ops, operations=0)) is None
    assert "[cellbench] mesh" not in capsys.readouterr().out


def test_collective_rate_needs_the_programs_spans(ring):
    ops = {"all-reduce.3": summed(SCATTER_S)}
    # a program without the mesh route: an all-reduce in the trace, no span
    assert harness._reader(RATE)(run_of(ops)) is None
    assert harness._reader(MS)(run_of(ops)) == pytest.approx(1e3 * SCATTER_S)
    # spans of another path, fewer spans than operations, a route with no
    # collective: nothing whole to read
    dispatches(ring, OPERATIONS, path="features", features=1)
    assert harness._reader(RATE)(run_of(ops)) is None
    dispatches(ring, OPERATIONS - 1, path="mesh", collective_bytes=SENT)
    assert harness._reader(RATE)(run_of(ops)) is None
    ring.clear_finished()
    dispatches(ring, OPERATIONS, path="mesh", collective_bytes=0)
    assert harness._reader(RATE)(run_of(ops)) is None


# -- the driver on four host devices -----------------------------------------


def test_a_sound_run_is_correct_and_says_its_route(cell, capsys):
    result = run(cell, seed=2**32 + 5)
    assert set(result) == {"correct", "attempted", "failed", "metrics", "device"}
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0 and result["device"]["count"] == 4
    assert set(result["metrics"]) == {"apply_ms", "setup_s"}
    json.loads(json.dumps(result))
    log = capsys.readouterr().out
    for name in cell.config["limits"]:          # each number beside its limit
        assert f"check name={name} value=" in log and "limit=" in log
    assert "compile in_window=0" in log
    assert ("dispatch route=program grid=2x2 spec=PartitionSpec('rows','cols') "
            "local_shape=(128,512) kernel=xla_blocks") in log
    assert "collective=psum_scatter reduce_over=('cols',)" in log
    assert f"collective_bytes={128 * 128 * 4 // 2}" in log


def test_same_seed_same_operands_on_the_mesh(cell):
    import numpy as np

    driver = driver_of(cell)
    a = driver.setup(cell.config, cell.traffic, 2**32 + 5)
    b = driver.setup(cell.config, cell.traffic, 2**32 + 5)
    other = driver.setup(cell.config, cell.traffic, 5)
    assert all(np.array_equal(x, y) for x, y in zip(a.panels, b.panels))
    assert not np.array_equal(a.panels[0], a.panels[1])
    assert not np.array_equal(a.panels[0], other.panels[0])
    assert a.context_seed == b.context_seed != other.context_seed
    for panel in a.panels:
        assert panel.shape == (cell.config["m"], cell.config["n"])
        assert panel.sharding.is_equivalent_to(a.layout, 2)
        assert {s.data.shape for s in panel.addressable_shards} == {(128, 512)}
    # no two devices hold the same block, and the entries are N(0, 1)
    blocks = [np.asarray(s.data) for s in a.panels[0].addressable_shards]
    assert not np.array_equal(blocks[0], blocks[1])
    whole = np.asarray(a.panels[0])
    assert abs(whole.mean()) < 0.02 and abs(whole.std() - 1.0) < 0.02
    with pytest.raises(ValueError):
        driver.setup({**cell.config, "family": "CT"}, cell.traffic, 1)


def test_the_check_reads_rows_out_of_the_distributed_arrays(cell):
    import jax.numpy as jnp
    import numpy as np

    driver = driver_of(cell)
    state = driver.setup(cell.config, cell.traffic, 3)
    idx = jnp.asarray([0, 5, 127, 128, 255])
    got = np.asarray(driver._rows(state.panels[0], idx, mesh=state.mesh))
    assert np.array_equal(got, np.asarray(state.panels[0])[np.asarray(idx)])


def test_the_bf16_reference_is_not_correct_by_rel_max_alone(cell, capsys):
    driver = driver_of(cell)

    def stand_in(state, _step):
        return driver.controls(state)["reference_bf16"]

    assert run(cell, step_wrapper=stand_in)["correct"] is False
    log = capsys.readouterr().out
    failed = [line for line in log.splitlines()
              if line.startswith("[cellbench] check name=") and "ok=False" in line]
    assert len(failed) == 1 and "name=rel_max" in failed[0]


@pytest.mark.parametrize("layout", ["replicated", "rows_only"])
def test_a_result_not_laid_as_the_operand_is_refused(cell, layout, capsys):
    """The numbers right, the layout wrong: the sketch axis left whole on
    every device (what an all-reduce in the exchange's place gives)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    def relaid(state, step):
        spec = P() if layout == "replicated" else P("rows", None)

        def wrapped(i):
            return jax.device_put(step(i), NamedSharding(state.mesh, spec))
        return wrapped

    assert run(cell, step_wrapper=relaid)["correct"] is False
    log = capsys.readouterr().out
    failed = [line for line in log.splitlines()
              if line.startswith("[cellbench] check name=") and "ok=False" in line]
    assert len(failed) == 1 and "name=layout_defect value=1.0" in failed[0]
