"""The cell ``jlt_sparse_apply`` (the dense sketch of sparse rows) at a tiny
size on the CPU: the manifest's entries resolve, the contract's keys, all
three controls and a broken timed path come out not correct, the counts
against a hand count, the reference's forms against each other, and the
``lane_fill.apply`` reader on a canned span list. Nothing here is a device
metric."""

import dataclasses
import importlib
import importlib.util
import json
import math
import pathlib
import time
import types

import numpy as np
import pytest

from cellbench import harness, roofline
from cellbench.counts import sparse_dense_sketch as counts
from cellbench.references import sparse_dense_sketch as reference

WORKLOAD = "jlt_sparse_apply"
PACKAGE = pathlib.Path(__file__).resolve().parents[1]
# the cell's shapes' ratios at a size a CPU run holds: n no multiple of 128,
# ~2 % dense, s ≪ n. norm_dev and colsum_dev are statistical or depend on
# the block's size, so their limits are restated for 2048 rows: over the
# seeds tried here norm_dev reads 4e-3 at most, and colsum_dev 2e-6 sound
# against 3e-3 without the last 1024 stored nonzeros.
TINY = {"n": 1181, "s": 128, "rows_per_panel": 2048, "panels": 4,
        "nnz_per_row_mean": 24, "check_rows": 64, "hot_columns": 32}
TINY_LIMITS = {"norm_dev": 3e-2, "colsum_dev": 1e-4}
DRIVER = "cellbench.drivers.sparse_dense_apply"


@pytest.fixture
def cell():
    whole = harness.load_cell(WORKLOAD)
    limits = {**whole.config["limits"], **TINY_LIMITS}
    return dataclasses.replace(whole, config={**whole.config, **TINY, "limits": limits})


def run(cell, seed=7, trace=False, step_wrapper=None, seconds=0.3):
    return harness.run_cell(cell, seed, seconds, trace, t_start=time.perf_counter(),
                            step_wrapper=step_wrapper)


def test_manifest_entries_resolve():
    manifest = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    cell = harness.load_cell(WORKLOAD)
    assert cell.chips == 1 and cell.config_name == "jlt_rcv1_d47236_s1024"
    assert cell.traffic_name == "apply_sparse_rows_dense"
    assert cell.traffic["driver"] == "sparse_dense_apply"
    assert cell.traffic["latency_metric"] == "apply_ms"
    config = cell.config
    assert (config["n"], config["s"], config["nnz_per_row_mean"]) == (47236, 1024, 74)
    assert (config["rows_per_panel"], config["panels"]) == (262144, 4)
    assert config["architecture"] is None and config["dimension"] == "rowwise"
    entry = next(c for c in manifest["configs"] if c["name"] == cell.config_name)
    assert sorted(entry["reduced"]) == sorted(config["reduced"]) == [
        "panels", "rows_per_panel"]
    assert len(entry["source"]) <= 200 and "svd.hpp:259-261" in entry["source"]
    assert set(config["limits"]) == set(config["limit_reasons"])
    # the data laws are the accepted sparse cell's, key for key
    other = harness.load_cell("cwt_sparse_apply").config
    for key in ("n", "row_length", "column_skew", "values", "nnz_per_row_mean",
                "rows_per_panel", "panels"):
        assert config[key] == other[key], key
    # every file the harness finds by name is there
    for module in (f"cellbench.counts.{config['counts']}",
                   f"cellbench.references.{config['reference']}", DRIVER):
        importlib.import_module(module)
    reported = {m["name"] for m in cell.end_to_end}
    assert reported == {"apply_ms", "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    assert {"lane_fill.apply", "sparse_nnz_rate.apply", "sketch_roofline.apply",
            "sketch_device_ms.apply", "device_idle.apply"} <= names
    for name in names:
        assert (PACKAGE / "layer_metrics" / f"{name}.py").is_file(), name


def test_result_has_exactly_the_contract_keys(cell, capsys):
    result = run(cell, seed=2**32 + 5)
    assert set(result) == {"correct", "attempted", "failed", "metrics", "device"}
    assert result["device"]["platform"] == "cpu"
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(result["metrics"]) == {"apply_ms", "setup_s"}
    json.loads(json.dumps(result))
    log = capsys.readouterr().out
    for name in cell.config["limits"]:      # each number beside its limit
        assert f"check name={name} value=" in log and "limit=" in log
    assert "compile in_window=0" in log and "dispatch path=sparse" in log
    assert "kernel=xla:_backend_cpu" in log
    assert "counter name=sketch.sparse_nnz" in log


def test_same_seed_same_operands_as_the_hash_cell(cell):
    """The generator is the accepted cell's, imported: one seed, one corpus."""
    driver = importlib.import_module(DRIVER)
    theirs = importlib.import_module("cellbench.drivers.sparse_hash_apply")
    assert driver._panel is theirs._panel and driver._zipf_cdf is theirs._zipf_cdf
    a = driver.setup(cell.config, cell.traffic, 2**32 + 5)
    b = driver.setup(cell.config, cell.traffic, 2**32 + 5)
    c = driver.setup(cell.config, cell.traffic, 5)
    assert all((x != y).nnz == 0 for x, y in zip(a.host, b.host))
    assert a.context_seed == b.context_seed != c.context_seed
    assert any((x != y).nnz for x, y in zip(a.host, c.host))
    hash_config = {**harness.load_cell("cwt_sparse_apply").config, **TINY}
    d = theirs.setup(hash_config, cell.traffic, 2**32 + 5)
    assert all((x != y).nnz == 0 for x, y in zip(a.host, d.host))


@pytest.mark.parametrize("control", ["reference_bf16", "program_drops_last_chunk",
                                     "program_other_counter"])
def test_control_is_not_correct(cell, control):
    driver = importlib.import_module(DRIVER)
    assert run(cell)["correct"] is True
    stand_in = lambda state, _step: driver.controls(state)[control]  # noqa: E731
    assert run(cell, step_wrapper=stand_in)["correct"] is False


def test_each_control_fails_by_its_own_number(cell):
    """bfloat16 moves the sampled rows, a dropped chunk the whole block's
    row sums, another counter the sampled rows; none moves the law."""
    driver = importlib.import_module(DRIVER)
    state = driver.setup(cell.config, cell.traffic, 7)
    limits = cell.config["limits"]
    blocks = range(driver.keep(state))
    sound = driver.check(state, [(i, driver.step(state, i)) for i in blocks])
    assert all(sound[name] <= limits[name] for name in sound), sound
    controls = driver.controls(state)
    failed = {}
    for name, stand_in in controls.items():
        got = driver.check(state, [(i, stand_in(i)) for i in blocks])
        failed[name] = {k for k in got if not got[k] <= limits[k]}
    assert "rel_max" in failed["reference_bf16"]
    assert "rowsum_dev" in failed["program_drops_last_chunk"]
    assert "rel_max" in failed["program_other_counter"]
    for names in failed.values():
        assert not names & {"operator_mean_z", "operator_var_z"}


def _lose_an_eighth_of_the_rows(state, step):
    def broken(i):
        out = step(i)
        return out.at[: out.shape[0] // 8].set(0.0)
    return broken


def _double_the_answer(state, step):
    return lambda i: 2.0 * step(i)


@pytest.mark.parametrize("breaker", [_lose_an_eighth_of_the_rows, _double_the_answer])
def test_broken_timed_path_is_not_correct(cell, breaker):
    result = run(cell, step_wrapper=breaker)
    assert result["correct"] is False
    assert result["attempted"] > 0 and math.isfinite(result["metrics"]["setup_s"]["value"])


def test_traced_run_off_the_tpu_reports_spans_but_no_device_metric(cell):
    result = run(cell, trace=True, seconds=2.0)
    assert "busy_s" not in result["device"] and "breakdown" not in result
    # no device plane: the span readers alone print, the device readers nothing
    assert {"lane_fill.apply", "sketch_host_ms.apply",
            "sketch_dispatch_ms.apply"} <= set(result["metrics"])
    assert "sketch_device_ms.apply" not in result["metrics"]
    # off the kernel the layout is the lane class: under a sixteenth is padding
    assert 93.0 < result["metrics"]["lane_fill.apply"]["value"] <= 100.0
    assert result["correct"] is True


def test_counts_against_a_hand_count():
    config = json.loads(
        (PACKAGE / "configs" / "jlt_rcv1_d47236_s1024.json").read_text())
    # 262144 rows × 74 stored = 19,398,656 nonzeros, a multiply and an add
    # for each of 1024 results; 8 B a nonzero + 262145 row pointers × 4 B +
    # Sᵀ 47236 × 1024 × 4 B read once + 262144 × 1024 × 4 B written once
    assert counts.stored_nonzeros(config) == 19_398_656
    work = counts.work(config)
    assert work["flops"] == 2 * 19_398_656 * 1024 == 39_728_447_488
    assert work["bytes"] == (19_398_656 * 8 + 262145 * 4 + 47236 * 1024 * 4
                             + 262144 * 1024 * 4) == 1_423_458_308
    least, bound = roofline.least_time(work, {"flops_per_s": 197e12, "bytes_per_s": 819e9})
    assert bound == "hbm" and least == pytest.approx(1.7380e-3, rel=1e-3)
    # at a small shape, by hand: 4 rows × 3 stored, s = 2, n = 5
    small = {"rows_per_panel": 4, "nnz_per_row_mean": 3, "n": 5, "s": 2}
    assert counts.work(small) == {"flops": 2 * 12 * 2,
                                  "bytes": 12 * 8 + 5 * 4 + 5 * 2 * 4 + 4 * 2 * 4}


def test_the_reference_forms_agree_and_bf16_moves_them():
    import scipy.sparse as sp

    rng = np.random.default_rng(3)
    n, s, rows = 301, 24, 2 * reference.ROW_BLOCK + 40
    X = sp.random(rows, n, density=0.03, format="csr", random_state=5,
                  dtype=np.float32)
    S = reference.operator(99, 0, s, n)
    assert S.shape == (s, n)
    by_rows = np.asarray(reference.apply_rows(X, S))
    whole = np.asarray(reference.apply_block(X, S))
    want = X.astype(np.float64) @ np.asarray(S, np.float64).T
    scale = np.abs(want).max()
    assert np.abs(by_rows - want).max() <= 1e-6 * scale
    assert np.abs(whole - want).max() <= 1e-6 * scale
    low = np.asarray(reference.apply_block(X, S, "bf16"))
    assert 1e-4 * scale < np.abs(low - want).max() < 3e-2 * scale
    # the ragged operator is the leading columns of the dense cells' one
    from cellbench.references import dense_sketch

    assert np.array_equal(np.asarray(S),
                          np.asarray(dense_sketch.operator(99, 0, s, 512))[:, :n])
    del rng


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "lane_fill_reader", PACKAGE / "layer_metrics" / f"{name}.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def test_lane_fill_reader_on_a_canned_span_list(monkeypatch):
    from libskylark_tpu.telemetry import trace

    def span(name, **attrs):
        return types.SimpleNamespace(name=name, attrs=attrs)

    canned = [
        span("sketch.dispatch", path="sparse", nnz=5, lane_slots=5),   # warm-up
        span("sketch.apply"),
        span("sketch.dispatch", path="features", features=9),
        span("sketch.dispatch", path="sparse", nnz=300, lane_slots=400),
        span("sketch.dispatch", path="sparse", nnz=100, lane_slots=400),
    ]
    monkeypatch.setattr(trace, "finished_spans", lambda: canned)
    read = _reader("lane_fill.apply")
    run_of = lambda n: types.SimpleNamespace(operations=n, trace=None)  # noqa: E731
    assert read(run_of(2)) == pytest.approx(50.0)
    assert read(run_of(3)) == pytest.approx(100.0 * 405 / 805)
    assert read(run_of(4)) is None          # an operation left no such span
    assert read(run_of(0)) is None
    # a program whose spans carry no lane_slots (the parent's): no number
    monkeypatch.setattr(trace, "finished_spans", lambda: [
        span("sketch.dispatch", path="sparse", nnz=300)])
    assert read(run_of(1)) is None
