"""The cells ``rft_features_apply`` (Gaussian random Fourier features at
speech widths) and ``jlt_apply_cw`` (the dense sketch, columnwise) at tiny
sizes on the CPU: the contract's keys, every control and a broken timed path
come out not correct, the counts against a hand count, the reference against
the exact kernel, and the reader this PR brought on a span ring the program
filled and on a recorded excerpt of one. Nothing here is a device metric."""

import dataclasses
import importlib
import json
import math
import time
import types

import jax.numpy as jnp
import numpy as np
import pytest

from cellbench import harness, roofline
from cellbench.counts import dense_features as counts
from cellbench.references import rft_features as reference

# the cells' shapes' ratios at a size a CPU run holds: n = 440 stays (ragged:
# not a multiple of 256), s ≫ n. norm_dev is statistical, about
# 0.376·|z|/√(2s) (the mean of cos(2b) over the features): 2e-3·|z| at the
# cell's s = 16384, 1.2e-2·|z| here, so its limit is restated.
TINY = {
    "rft_features_apply": {"s": 512, "rows_per_panel": 512, "check_rows": 64,
                           "limits": {"rel_max": 1e-4, "norm_dev": 6e-2,
                                      "kernel_z": 6.0, "operator_mean_z": 6.0,
                                      "operator_var_z": 6.0, "shift_chi2_z": 6.0}},
    "jlt_apply_cw": {"n": 512, "s": 256, "rows_per_panel": 512, "check_rows": 64,
                     "limits": {"rel_max": 1e-4, "norm_dev": 2e-2,
                                "operator_mean_z": 6.0, "operator_var_z": 6.0}},
}
CELLS = sorted(TINY)


@pytest.fixture
def cell():
    def make(workload):
        whole = harness.load_cell(workload)
        return dataclasses.replace(whole, config={**whole.config, **TINY[workload]})
    return make


def run(cell, seed=7, trace=False, step_wrapper=None, seconds=0.3):
    return harness.run_cell(cell, seed, seconds, trace, t_start=time.perf_counter(),
                            step_wrapper=step_wrapper)


@pytest.mark.parametrize("workload", CELLS)
def test_result_has_exactly_the_contract_keys(cell, workload, capsys):
    result = run(cell(workload), seed=2**32 + 5)
    assert set(result) == {"correct", "attempted", "failed", "metrics", "device"}
    assert set(result["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(result["metrics"]) == {"apply_ms", "setup_s"}
    json.loads(json.dumps(result))
    log = capsys.readouterr().out
    for name in cell(workload).config["limits"]:    # each number beside its limit
        assert f"check name={name} value=" in log and "limit=" in log
    assert "compile in_window=0" in log


@pytest.mark.parametrize("workload", CELLS)
def test_the_manifest_entries(workload):
    whole = harness.load_cell(workload)
    assert whole.chips == 1 and whole.traffic["loop"] == "closed"
    assert {m["name"] for m in whole.end_to_end} == {"apply_ms", "setup_s"}
    listed = {m["name"] for m in whole.per_layer}
    assert {"sketch_device_ms.apply", "sketch_roofline.apply", "device_idle.apply",
            "sketch_host_ms.apply", "stream_key_ms.apply", "sketch_plan_ms.apply",
            "sketch_dispatch_ms.apply"} <= listed
    assert ("feature_rate.apply" in listed) == (workload == "rft_features_apply")
    assert "sparse_nnz_rate.apply" not in listed
    # the accepted cell keeps its own set
    assert "feature_rate.apply" not in {
        m["name"] for m in harness.load_cell("jlt_apply").per_layer}


def test_the_configuration_states_what_the_contract_asks():
    cfg = harness.load_cell("rft_features_apply").config
    assert (cfg["n"], cfg["s"], cfg["family"], cfg["tag"]) == (
        440, 16384, "GaussianRFT", "regular")
    assert set(cfg["reduced"]) == {"rows_per_panel", "panels"}
    for key in ("source", "guarantees", "assumed", "deployment", "limits"):
        assert cfg[key]
    assert len(cfg["source"]) <= 200
    # the columnwise cell reads the admitted configuration, unedited
    assert harness.load_cell("jlt_apply_cw").config == harness.load_cell("jlt_apply").config


@pytest.mark.parametrize("workload", CELLS)
def test_same_seed_same_operands_and_large_seeds_differ(cell, workload):
    c = cell(workload)
    driver = importlib.import_module(f"cellbench.drivers.{c.traffic['driver']}")
    a = driver.setup(c.config, c.traffic, 2**32 + 5)
    b = driver.setup(c.config, c.traffic, 2**32 + 5)
    other = driver.setup(c.config, c.traffic, 5)
    assert all(np.array_equal(x, y) for x, y in zip(a.panels, b.panels))
    assert a.context_seed == b.context_seed != other.context_seed
    assert not np.array_equal(a.panels[0], other.panels[0])
    rows, n = c.config["rows_per_panel"], c.config["n"]
    assert a.panels[0].shape == ((rows, n) if workload == "rft_features_apply"
                                 else (n, rows))


@pytest.mark.parametrize("workload,control_name", [
    ("rft_features_apply", "reference_bf16"), ("jlt_apply_cw", "reference_bf16")])
def test_lower_precision_control_is_not_correct(cell, workload, control_name):
    c = cell(workload)
    driver = importlib.import_module(f"cellbench.drivers.{c.traffic['driver']}")

    def control(state, _step):
        return driver.controls(state)[control_name]

    assert run(c)["correct"] is True
    assert run(c, step_wrapper=control)["correct"] is False


def _lose_an_s_tile(state, step):
    def broken(i):
        out = step(i)
        return out.at[:, : out.shape[1] // 8].set(0.0)
    return broken


def _lose_the_shifts(state, step):
    """cos(XWᵀ/σ) without b: each value is a sound feature value of some
    other map; the norm and the kernel estimate are what notice."""
    def broken(i):
        cfg = state.config
        W = reference.frequencies(state.context_seed, 0, cfg["s"], cfg["n"])
        return reference.features(state.panels[i % len(state.panels)], W,
                                  jnp.zeros((cfg["s"],), jnp.float32), cfg["sigma"])
    return broken


def _lose_a_column_block(state, step):
    def broken(i):
        out = step(i)
        return out.at[:, : out.shape[1] // 8].set(0.0)   # an eighth of the examples
    return broken


@pytest.mark.parametrize("workload,breaker", [
    ("rft_features_apply", _lose_an_s_tile), ("rft_features_apply", _lose_the_shifts),
    ("jlt_apply_cw", _lose_a_column_block)])
def test_broken_timed_path_is_not_correct(cell, workload, breaker):
    result = run(cell(workload), step_wrapper=breaker)
    assert result["correct"] is False
    assert result["attempted"] > 0 and math.isfinite(result["metrics"]["setup_s"]["value"])


def test_the_check_holds_the_served_rows_to_the_kernel(cell):
    """kernel_z is computed from what the timed path produced: features of
    another bandwidth are sound cos values and fail it (and rel_max)."""
    c = cell("rft_features_apply")
    driver = importlib.import_module("cellbench.drivers.feature_apply")
    state = driver.setup(c.config, c.traffic, 11)
    kept = [(i, driver.step(state, i)) for i in range(2)]
    sound = driver.check(state, kept)
    assert all(sound[k] <= c.config["limits"][k] for k in sound)
    W = reference.frequencies(state.context_seed, 0, c.config["s"], c.config["n"])
    b = reference.shifts(state.context_seed, 0, c.config["s"])
    narrow = [(i, reference.features(state.panels[i], W, b, c.config["sigma"] / 2))
              for i in range(2)]
    got = driver.check(state, narrow)
    assert got["kernel_z"] > 6.0 and got["rel_max"] > 1e-2
    assert got["norm_dev"] <= c.config["limits"]["norm_dev"]


# -- the reference ---------------------------------------------------------


def test_reference_features_estimate_the_exact_kernel():
    s, n, sigma = 4096, 440, 30.0
    X = jnp.asarray(np.random.default_rng(2).standard_normal((48, n)), jnp.float32)
    W, b = reference.frequencies(3, 0, s, n), reference.shifts(3, 0, s)
    assert W.shape == (s, n) and b.shape == (s,)
    assert 0.0 <= float(b.min()) and float(b.max()) < reference.TWO_PI
    Z = np.asarray(reference.features(X, W, b, sigma), np.float64)
    K = np.asarray(reference.gaussian_kernel(X, sigma), np.float64)
    D = ((np.asarray(X, np.float64)[:, None] - np.asarray(X, np.float64)[None]) ** 2).sum(-1)
    np.testing.assert_allclose(K, np.exp(-D / (2 * sigma ** 2)), rtol=1e-5)
    z = np.abs(Z @ Z.T - K) / np.sqrt((1 + 0.5 * K ** 4 - K * K) / s)
    assert z.max() < 5.0
    # the ragged width is the first 440 columns of the two whole blocks
    np.testing.assert_array_equal(
        np.asarray(reference.frequencies(3, 0, s, 512))[:, :n], np.asarray(W))
    # and the control is one precision below, visibly
    low = np.asarray(reference.features(X, W, b, sigma, "bf16"), np.float64)
    assert 1e-4 < np.abs(low - Z).max() / math.sqrt(2 / s) < 1e-1


def test_reference_imports_nothing_of_the_program():
    import ast
    import pathlib

    tree = ast.parse(pathlib.Path(reference.__file__).read_text())
    names = {a.name for node in ast.walk(tree) if isinstance(node, ast.Import)
             for a in node.names} | {node.module for node in ast.walk(tree)
                                     if isinstance(node, ast.ImportFrom)}
    assert not any(name.startswith("libskylark_tpu") for name in names)


# -- the counts ------------------------------------------------------------


def test_dense_features_counts_and_least_time():
    cfg = harness.load_cell("rft_features_apply").config
    work = counts.work(cfg)
    m, n, s = 32768, 440, 16384
    assert work == {"flops": 2 * m * n * s, "bytes": (m * n + m * s) * 4,
                    "transcendentals": m * s}
    assert work["flops"] == 472_446_402_560 and work["bytes"] == 2_205_155_328
    least, bound = roofline.least_time(work, roofline.peaks("TPU v5 lite"))
    assert bound == "hbm"                       # its own result binds it
    assert least == pytest.approx(2.205155328e9 / 819e9)
    assert work["flops"] / 197e12 == pytest.approx(2.398e-3, rel=1e-3)


def test_dense_features_counts_follow_the_configuration():
    small = counts.work({"rows_per_panel": 8, "n": 3, "s": 5})
    assert small == {"flops": 240, "bytes": (24 + 40) * 4, "transcendentals": 40}


# -- the reader this PR brought --------------------------------------------


@pytest.fixture
def ring():
    from libskylark_tpu import telemetry
    from libskylark_tpu.telemetry import metrics, trace

    before = metrics._ENABLED
    trace.clear_finished()
    yield telemetry
    metrics._ENABLED = before
    trace.clear_finished()


def _applies(count, rows=16, s=128):
    from libskylark_tpu import Context
    from libskylark_tpu import sketch as sk

    X = jnp.asarray(np.random.default_rng(1).standard_normal((rows, 440)), jnp.float32)
    for _ in range(count):      # a map each: none pins its operator
        sk.GaussianRFT(440, s, Context(5), sigma=30.0).apply(
            X, sk.ROWWISE).block_until_ready()


def _read(operations, busy_s=0.5):
    run_ = harness.Run(cell=None, device_kind="cpu", operations=operations,
                       trace=types.SimpleNamespace(busy_s=busy_s) if busy_s else None)
    return harness._reader("feature_rate.apply")(run_)


def test_feature_rate_reads_the_last_operations_spans(ring):
    ring.set_enabled(True)
    _applies(3, rows=8)         # warm-up: other shapes, left out
    _applies(12)
    assert _read(12) == pytest.approx(12 * 16 * 128 / 0.5 / 1e9)


def test_feature_rate_needs_ten_whole_spans_and_a_trace(ring):
    assert _read(12) is None                    # gate shut: no span
    ring.set_enabled(True)
    _applies(9)
    assert _read(9) is None                     # under ten
    _applies(3)
    assert _read(12) is not None
    assert _read(14) is None                    # fewer spans than operations
    assert _read(12, busy_s=0) is None          # nothing traced
    assert _read(0) is None


def test_feature_rate_on_a_recorded_span_excerpt(monkeypatch):
    """Spans as the chip's traced run left them (rft_features_apply, PR 32:
    twelve applies of 32768 × 16384, busy 0.2402 s): 26.8 G feature values/s;
    a sparse dispatch among them is not counted."""
    from libskylark_tpu.telemetry import trace

    recorded = {"path": "features", "family": "GaussianRFT", "epilogue": "cos",
                "kernel": "pallas_planes", "features": 536870912, "m_tile": 512,
                "s_tile": 1024, "operator_residency": "hbm"}
    spans = [types.SimpleNamespace(name="sketch.dispatch", attrs=dict(recorded))
             for _ in range(12)]
    spans.insert(5, types.SimpleNamespace(
        name="sketch.dispatch", attrs={"path": "sparse", "nnz": 7}))
    spans.insert(0, types.SimpleNamespace(name="sketch.apply", attrs={}))
    monkeypatch.setattr(trace, "finished_spans", lambda: spans)
    assert _read(12, busy_s=0.2402) == pytest.approx(26.82, rel=1e-3)
    # a program older than the feature route's spans gives nothing
    monkeypatch.setattr(trace, "finished_spans", lambda: [])
    assert _read(12, busy_s=0.2402) is None


def test_traced_run_off_the_tpu_reports_only_span_metrics(cell, ring):
    result = run(cell("rft_features_apply"), trace=True, seconds=0.5)
    assert set(result["metrics"]) == {
        "sketch_host_ms.apply", "stream_key_ms.apply", "sketch_plan_ms.apply",
        "sketch_dispatch_ms.apply"}
    assert "breakdown" not in result
