"""The cell ``tensorsketch_features_apply`` (TensorSketch features for the
polynomial kernel at MNIST widths) at tiny sizes on the CPU: the manifest
entries, the contract's keys, the controls coming out not correct, the
counts against the hand reckoning, the reference against the tensor-power
statement of the definition, and the reader this PR brought on a span ring
the program filled. Nothing here is a device metric. (Broken maps against
the check, and the program against the reference: tests/
test_tensorsketch_program.py, tier-1.)"""

import dataclasses
import importlib
import json
import time
import types

import jax.numpy as jnp
import numpy as np
import pytest

from cellbench import harness, roofline
from cellbench.counts import tensorsketch as counts
from cellbench.references import tensorsketch_features as reference

CELL = "tensorsketch_features_apply"
CONFIG = "ppt_mnist_d784_s16384_q3"
# the cell's shape at a size a CPU run holds (n ≪ s, as 784 ≪ 16384). The
# statistics are restated for these widths: one row's squared norm has sd
# √(11.5/s)·k = 0.21·k at s = 256 (2.6e-2·k at 16384), and a map's deviation
# is common to its rows, so norm_dev's limit is eight times the cell's.
TINY = {"n": 33, "s": 256, "gamma": 1.0 / 33, "rows_per_panel": 301,
        "check_rows": 64,
        "limits": {"rel_max": 3e-5, "norm_dev": 0.32, "kernel_z": 6.0,
                   "bucket_chi2_z": 6.0, "sign_mean_z": 6.0}}


@pytest.fixture
def cell():
    whole = harness.load_cell(CELL)
    return dataclasses.replace(whole, config={**whole.config, **TINY})


def run(cell, seed=7, trace=False, step_wrapper=None, seconds=0.3):
    return harness.run_cell(cell, seed, seconds, trace, t_start=time.perf_counter(),
                            step_wrapper=step_wrapper)


def driver_of(cell):
    return importlib.import_module(f"cellbench.drivers.{cell.traffic['driver']}")


def test_result_has_exactly_the_contract_keys(cell, capsys):
    result = run(cell, seed=2**32 + 5)
    assert set(result) == {"correct", "attempted", "failed", "metrics", "device"}
    assert set(result["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(result["metrics"]) == {"apply_ms", "setup_s"}
    json.loads(json.dumps(result))
    log = capsys.readouterr().out
    for name in cell.config["limits"]:          # each number beside its limit
        assert f"check name={name} value=" in log and "limit=" in log
    assert "compile in_window=0" in log
    assert ("dispatch route=program row_block=301 sketch=spectral_operator "
            "fft=mxu_two_stage grade=float32") in log


def test_the_manifest_entries_resolve():
    whole = harness.load_cell(CELL)
    assert whole.chips == 1 and whole.config_name == CONFIG
    assert whole.traffic_name == "apply_features_poly"
    assert whole.traffic == {**whole.traffic, "driver": "tensorsketch_apply",
                             "loop": "closed", "callers": 1,
                             "latency_metric": "apply_ms", "warm_steps": 4,
                             "trace_seconds": 4}
    assert {m["name"] for m in whole.end_to_end} == {"apply_ms", "setup_s"}
    assert {m["name"] for m in whole.per_layer} == {
        "sketch_device_ms.apply", "sketch_roofline.apply", "device_idle.apply",
        "sketch_host_ms.apply", "stream_key_ms.apply", "sketch_plan_ms.apply",
        "sketch_dispatch_ms.apply", "feature_rate.apply", "setup_import_s",
        "setup_lower_s", "setup_compile_s", "conv_rate.apply"}
    manifest = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    metric = next(m for m in manifest["per_layer"] if m["name"] == "conv_rate.apply")
    assert metric == {
        "name": "conv_rate.apply", "unit": "Gelem/s", "better": "higher",
        "source": "device_trace", "layer": "sketch kernel", "moves": "apply_ms",
        "workloads": [CELL]}
    # every file the entries name is there, and every reader loads
    for m in whole.per_layer:
        assert callable(harness._reader(m["name"]))
    for module in ("drivers.tensorsketch_apply", "counts.tensorsketch",
                   "references.tensorsketch_features", "loops.closed"):
        importlib.import_module(f"cellbench.{module}")
    # the sibling feature cells keep their own sets
    for other in ("rft_features_apply", "fastfood_features_apply"):
        assert "conv_rate.apply" not in {
            m["name"] for m in harness.load_cell(other).per_layer}


def test_the_configuration_states_what_the_contract_asks():
    cfg = harness.load_cell(CELL).config
    assert (cfg["family"], cfg["kernel"], cfg["dtype"]) == ("PPT", "polynomial", "float32")
    assert (cfg["n"], cfg["s"], cfg["q"], cfg["c"]) == (784, 16384, 3, 1.0)
    assert cfg["gamma"] == 1.0 / 784
    assert (cfg["rows_per_panel"], cfg["panels"], cfg["dimension"]) == (
        60000, 1, "rowwise")
    assert cfg["reduced"] == {}                 # nothing of the source is cut
    for key in ("source", "guarantees", "assumed", "deployment", "limits",
                "memory", "precision", "limits_set_from"):
        assert cfg[key]
    for key in ("s", "gamma", "q", "c", "data", "panels", "paper"):
        assert cfg["assumed"][key]
    assert len(cfg["source"]) <= 200
    manifest = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert entry["source"] == cfg["source"] and entry["reduced"] == []
    assert set(cfg["limits"]) == {"rel_max", "norm_dev", "kernel_z",
                                  "bucket_chi2_z", "sign_mean_z"}


def test_same_seed_same_operands_and_large_seeds_differ(cell):
    driver = driver_of(cell)
    a = driver.setup(cell.config, cell.traffic, 2**32 + 5)
    b = driver.setup(cell.config, cell.traffic, 2**32 + 5)
    other = driver.setup(cell.config, cell.traffic, 5)
    assert all(np.array_equal(x, y) for x, y in zip(a.panels, b.panels))
    assert a.context_seed == b.context_seed != other.context_seed
    assert not np.array_equal(a.panels[0], other.panels[0])
    assert a.panels[0].shape == (cell.config["rows_per_panel"], cell.config["n"])
    assert type(a.transform).__name__ == "PPT"
    # the data law: γ‖x‖² ≈ 2, so k(x, x) ≈ 27
    sq = np.asarray(jnp.sum(a.panels[0] ** 2, axis=1)) * cell.config["gamma"]
    assert 1.6 < sq.mean() < 2.4


def test_the_driver_refuses_another_family(cell):
    driver = driver_of(cell)
    with pytest.raises(ValueError):
        driver.setup({**cell.config, "family": "GaussianRFT"}, cell.traffic, 1)
    with pytest.raises(ValueError):
        driver.setup({**cell.config, "kernel": "gaussian"}, cell.traffic, 1)


@pytest.mark.parametrize("control", ["program_bf16", "reference_bf16"])
def test_a_control_is_not_correct(cell, control):
    driver = driver_of(cell)

    def stand_in(state, _step):
        return driver.controls(state)[control]

    assert run(cell)["correct"] is True
    assert run(cell, step_wrapper=stand_in)["correct"] is False


# -- the reference ----------------------------------------------------------


def test_reference_against_the_tensor_power_statement():
    d, s, q, gamma, c = 6, 64, 3, 0.3, 1.5
    X = jnp.asarray(np.random.default_rng(4).standard_normal((7, d)), jnp.float32)
    parts = reference.streams(9, 2, d, s, q)
    assert parts["h"].shape == parts["v"].shape == (q, d)
    assert parts["hh"].shape == parts["hv"].shape == (q,)
    power = reference.tensor_power_sketch(X, parts, gamma, c)
    got = np.asarray(reference.features(X, parts, gamma, c), np.float64)
    assert np.abs(got - power).max() / np.abs(power).max() < 2e-6
    low = np.asarray(reference.features(X, parts, gamma, c, "bf16"), np.float64)
    assert 1e-4 < np.abs(low - power).max() / np.abs(power).max() < 1e-1


def test_reference_features_estimate_the_exact_kernel():
    d, s, q, gamma, c = 64, 4096, 3, 1.0 / 64, 1.0
    X = jnp.asarray(np.random.default_rng(2).standard_normal((48, d)), jnp.float32)
    parts = reference.streams(3, 0, d, s, q)
    Z = np.asarray(reference.features(X, parts, gamma, c), np.float64)
    K = np.asarray(reference.polynomial_kernel(X, gamma, c, q), np.float64)
    diag = np.diag(K)
    inflation = harness.load_cell(CELL).config["kernel_var_inflation"]
    z = np.abs(Z @ Z.T - K) / np.sqrt(
        inflation * (np.outer(diag, diag) + K * K) / s)
    assert z.max() < 6.0
    laws = reference.law_z_scores(parts, 16)
    assert all(laws[k] < 6.0 for k in laws)


def test_reference_imports_nothing_of_the_program():
    import ast
    import pathlib

    tree = ast.parse(pathlib.Path(reference.__file__).read_text())
    names = {a.name for node in ast.walk(tree) if isinstance(node, ast.Import)
             for a in node.names} | {node.module for node in ast.walk(tree)
                                     if isinstance(node, ast.ImportFrom)}
    assert not any(name.startswith("libskylark_tpu") for name in names)


# -- the counts -------------------------------------------------------------


def test_tensorsketch_counts_and_least_time():
    """The issue's reckoning, to the digit: q·n multiply-adds, q + 1 real
    FFTs at (5/2)·S·log₂S, q − 1 products of S/2 + 1 complex bins an
    example; X read once, Z written once."""
    cfg = harness.load_cell(CELL).config
    work = counts.work(cfg)
    m, n, s, q = 60000, 784, 16384, 3
    a_row = 2 * q * n + (q + 1) * 5 * s * 14 // 2 + 6 * (q - 1) * (s // 2 + 1)
    assert a_row == 4704 + 2_293_760 + 98_316 == 2_396_780
    assert work == {"flops": m * a_row, "bytes": (m * n + m * s) * 4}
    assert work["flops"] == 143_806_800_000 and work["bytes"] == 4_120_320_000
    least, bound = roofline.least_time(work, roofline.peaks("TPU v5 lite"))
    assert bound == "hbm"                       # its result binds it
    assert least == pytest.approx(4.12032e9 / 819e9)    # 5.03 ms
    assert work["flops"] / 197e12 < least / 6


def test_tensorsketch_counts_follow_the_configuration():
    small = counts.work({"rows_per_panel": 8, "n": 3, "s": 16, "q": 2})
    assert small == {"flops": 8 * (2 * 2 * 3 + 3 * 5 * 16 * 4 // 2 + 6 * 9),
                     "bytes": (24 + 128) * 4}


# -- the reader this PR brought ----------------------------------------------


@pytest.fixture
def ring():
    from libskylark_tpu import telemetry
    from libskylark_tpu.telemetry import metrics, trace

    before = metrics._ENABLED
    trace.clear_finished()
    yield telemetry
    metrics._ENABLED = before
    trace.clear_finished()


def _applies(count, rows=16, n=33, s=256, q=3):
    from libskylark_tpu import Context
    from libskylark_tpu import sketch as sk

    X = jnp.asarray(np.random.default_rng(1).standard_normal((rows, n)), jnp.float32)
    T = sk.PPT(n, s, Context(5), q=q, gamma=1.0 / n)
    for _ in range(count):
        T.apply(X, sk.ROWWISE).block_until_ready()


def _read(name, operations, busy_s=0.5):
    run_ = harness.Run(cell=None, device_kind="cpu", operations=operations,
                       trace=types.SimpleNamespace(busy_s=busy_s) if busy_s else None)
    return harness._reader(name)(run_)


def test_conv_rate_finds_nothing_without_spans(ring):
    assert _read("conv_rate.apply", 12) is None
    assert _read("conv_rate.apply", 0) is None


def test_the_readers_on_a_ring_the_program_filled(ring):
    ring.set_enabled(True)
    _applies(12)
    # (q + 1) transforms × 256 entries × 16 examples an apply; 16 × 256 features
    assert _read("conv_rate.apply", 12) == pytest.approx(
        12 * 4 * 256 * 16 / 0.5 / 1e9)
    assert _read("feature_rate.apply", 12) == pytest.approx(12 * 16 * 256 / 0.5 / 1e9)
    # fewer spans than operations, no trace, no operations: nothing to read
    assert _read("conv_rate.apply", 13) is None
    assert _read("conv_rate.apply", 12, busy_s=0) is None
    assert _read("conv_rate.apply", 0) is None


def test_conv_rate_finds_nothing_on_another_feature_map(ring):
    """The other maps' spans are of other families: the parent's program,
    and the sibling cells', leave the metric out."""
    from libskylark_tpu import Context
    from libskylark_tpu import sketch as sk

    ring.set_enabled(True)
    X = jnp.asarray(np.random.default_rng(1).standard_normal((16, 48)), jnp.float32)
    for _ in range(12):
        sk.FastGaussianRFT(48, 160, Context(5), sigma=9.8).apply(
            X, sk.ROWWISE).block_until_ready()
    assert _read("conv_rate.apply", 12) is None
    assert _read("feature_rate.apply", 12) is not None
