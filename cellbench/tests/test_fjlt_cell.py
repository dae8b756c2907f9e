"""The cell ``fjlt_apply_cw`` (the Blendenpik sketch: FJLT with the
Walsh-Hadamard mixer, columnwise, of a tall dense operand) at a tiny size on
the CPU: the contract's keys, the control and broken timed paths come out not
correct, the reference against the dense Hadamard matrix, the counts against a
hand count, and the reader this PR brought on a span ring the program filled
and on recorded spans. Nothing here is a device metric."""

import ast
import dataclasses
import importlib
import json
import math
import pathlib
import time
import types

import jax.numpy as jnp
import numpy as np
import pytest

from cellbench import harness, roofline
from cellbench.counts import fut_mix_sample as counts
from cellbench.references import srht as reference

WORKLOAD = "fjlt_apply_cw"
# the cell's ratios at a size a CPU run holds: m ≫ s = 4n. norm_dev is
# statistical, about |z|·√(2/n)/(2√s) (each sampled row's squared norm is a
# chi-square of n terms): 3.5e-4·|z| at the cell's n = 1024, s = 4096,
# 5.5e-3·|z| here, so its limit is restated at the same seven sigmas.
TINY = {"m": 4096, "n": 64, "s": 256, "check_cols": 32,
        "limits": {"rel_max": 1e-6, "norm_dev": 4e-2,
                   "sign_mean_z": 6.0, "sample_chi2_z": 6.0}}


@pytest.fixture
def cell():
    whole = harness.load_cell(WORKLOAD)
    return dataclasses.replace(whole, config={**whole.config, **TINY})


@pytest.fixture
def driver(cell):
    return importlib.import_module(f"cellbench.drivers.{cell.traffic['driver']}")


def run(cell, seed=7, trace=False, step_wrapper=None, seconds=0.3):
    return harness.run_cell(cell, seed, seconds, trace, t_start=time.perf_counter(),
                            step_wrapper=step_wrapper)


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
    assert "dispatch route=fut kernel=xla_f32" in log


def test_the_manifest_entries():
    whole = harness.load_cell(WORKLOAD)
    assert whole.chips == 1 and whole.traffic["loop"] == "closed"
    assert whole.traffic["driver"] == "fjlt_apply_cw"
    assert {m["name"] for m in whole.end_to_end} == {"apply_ms", "setup_s"}
    listed = {m["name"] for m in whole.per_layer}
    assert listed == {
        "sketch_device_ms.apply", "sketch_roofline.apply", "device_idle.apply",
        "sketch_host_ms.apply", "stream_key_ms.apply", "sketch_dispatch_ms.apply",
        "mix_rate.apply", "setup_import_s", "setup_lower_s", "setup_compile_s"}
    # the accepted cells keep their own sets
    for other in ("jlt_apply", "jlt_apply_cw", "cwt_sparse_apply", "rft_features_apply"):
        assert "mix_rate.apply" not in {
            m["name"] for m in harness.load_cell(other).per_layer}
    manifest = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    assert [w["name"] for w in manifest["workloads"]][-1] == WORKLOAD
    assert all(w["chips"] == 1 for w in manifest["workloads"])


def test_the_configuration_states_what_the_contract_asks():
    cfg = harness.load_cell(WORKLOAD).config
    assert (cfg["family"], cfg["fut"], cfg["m"], cfg["n"], cfg["s"]) == (
        "FJLT", "wht", 1 << 20, 1024, 4096)
    assert cfg["s"] == cfg["gamma"] * cfg["n"] and cfg["dimension"] == "columnwise"
    assert set(cfg["reduced"]) == {"rows", "panels"}
    for key in ("source", "guarantees", "assumed", "deployment", "limits", "memory"):
        assert cfg[key]
    assert len(cfg["source"]) <= 200
    manifest = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in manifest["configs"]
                 if c["name"] == "fjlt_blendenpik_m1048576_n1024")
    assert entry["source"] == cfg["source"] and entry["reduced"] == ["rows", "panels"]


def test_same_seed_same_operands_and_large_seeds_differ(cell, driver):
    a = driver.setup(cell.config, cell.traffic, 2**32 + 5)
    b = driver.setup(cell.config, cell.traffic, 2**32 + 5)
    other = driver.setup(cell.config, cell.traffic, 5)
    assert all(np.array_equal(x, y) for x, y in zip(a.panels, b.panels))
    assert a.context_seed == b.context_seed != other.context_seed
    assert not np.array_equal(a.panels[0], other.panels[0])
    assert len(a.panels) == 2 and a.panels[0].shape == (TINY["m"], TINY["n"])
    # every 64th row 32 times as heavy: 1/64 of the rows, most of the energy
    energy = np.square(np.asarray(a.panels[0], np.float64)).sum(axis=1)
    assert energy[::64].sum() / energy.sum() > 0.9


@pytest.mark.parametrize("name", ["reference_bf16x2", "reference_bf16",
                                  "unmixed_sample"])
def test_every_control_is_not_correct(cell, driver, name):
    def control(state, _step):
        return driver.controls(state)[name]

    assert set(driver.controls(driver.setup(cell.config, cell.traffic, 3))) == {
        "reference_bf16x2", "reference_bf16", "unmixed_sample"}
    assert run(cell)["correct"] is True
    assert run(cell, step_wrapper=control)["correct"] is False


def _lose_a_column_block(state, step):
    def broken(i):
        out = step(i)
        return out.at[:, : out.shape[1] // 8].set(0.0)
    return broken


def _lose_the_signs(state, step):
    def broken(i):
        cfg = state.config
        D, idx = reference.streams(state.context_seed, 0, cfg["m"], cfg["s"])
        return reference.apply_cols(state.panels[i % len(state.panels)],
                                    jnp.ones_like(D), idx)
    return broken


@pytest.mark.parametrize("breaker", [_lose_a_column_block, _lose_the_signs])
def test_broken_timed_path_is_not_correct(cell, breaker):
    result = run(cell, step_wrapper=breaker)
    assert result["correct"] is False
    assert result["attempted"] > 0 and math.isfinite(result["metrics"]["setup_s"]["value"])


def test_the_unmixed_sample_is_refused_by_the_norm(cell, driver):
    """√(N/s)·A[idx]: sound rows of the operand, never mixed — the norm is
    what notices, because a sixty-fourth of the rows hold the energy."""
    state = driver.setup(cell.config, cell.traffic, 11)
    unmixed = driver.controls(state)["unmixed_sample"]
    got = driver.check(state, [(i, unmixed(i)) for i in range(2)])
    assert got["norm_dev"] > cell.config["limits"]["norm_dev"]


def test_the_laws_are_read_from_the_transforms_own_streams(cell, driver):
    """A program whose signs or samples broke their law is refused, whatever
    the reference's are: the check reads ``diagonal()`` / ``sample_indices()``."""
    state = driver.setup(cell.config, cell.traffic, 13)
    kept = [(i, driver.step(state, i)) for i in range(2)]
    sound = driver.check(state, kept)
    assert sound["sign_mean_z"] < 6.0 and sound["sample_chi2_z"] < 6.0
    m, s = cell.config["m"], cell.config["s"]
    state.transform.diagonal = lambda *a, **k: jnp.ones((m,), jnp.float32)
    state.transform.sample_indices = lambda: jnp.arange(s, dtype=jnp.int32)
    broken = driver.check(state, kept)
    assert broken["sign_mean_z"] > 6.0 and broken["sample_chi2_z"] > 6.0
    assert broken["rel_max"] == sound["rel_max"]


def test_the_two_part_control_lies_between_the_reference_and_one_part():
    n, s = 4096, 256
    D, idx = reference.streams(5, 0, n, s)
    A = jnp.asarray(np.random.default_rng(6).standard_normal((n, 16)), jnp.float32)
    ref = reference.apply_cols(A, D, idx)
    err = {p: float(jnp.max(jnp.abs(reference.apply_cols(A, D, idx, p) - ref))
                    / jnp.max(jnp.abs(ref))) for p in ("bf16x2", "bf16")}
    assert 5e-7 < err["bf16x2"] < 5e-5 < err["bf16"] < 1e-2
    with pytest.raises(ValueError):
        reference.apply_cols(A, D, idx, "fp8")


# -- the reference ---------------------------------------------------------


def test_reference_is_the_dense_sampled_hadamard_operator():
    n, s = 512, 96
    D, idx = reference.streams(3, 2, n, s)
    assert D.shape == (n,) and idx.shape == (s,)
    assert set(np.unique(np.asarray(D))) == {-1.0, 1.0}
    assert 0 <= int(idx.min()) and int(idx.max()) < n
    i, j = np.meshgrid(np.asarray(idx), np.arange(n), indexing="ij")
    H = 1.0 - 2.0 * (np.bitwise_count((i & j).astype(np.uint64)) & 1)
    S = H * np.asarray(D, np.float64)[None, :] / math.sqrt(s)
    A = np.random.default_rng(4).standard_normal((n, 7))
    got = np.asarray(reference.apply_cols(jnp.asarray(A, jnp.float32), D, idx), np.float64)
    np.testing.assert_allclose(got, S @ A, atol=2e-6 * np.abs(S @ A).max())
    # a prefix of a longer stream, and the control one precision below, visibly
    D2, idx2 = reference.streams(3, 2, n, 2 * s)
    assert np.array_equal(np.asarray(idx2)[:s], np.asarray(idx))
    low = np.asarray(reference.apply_cols(jnp.asarray(A, jnp.float32), D, idx, "bf16"))
    assert 1e-4 < np.abs(low - got).max() / np.abs(got).max() < 1e-1


def test_reference_streams_cross_a_chunk_and_samples_repeat():
    n, s = 1 << 13, 1 << 13               # two chunks of each stream
    D, idx = reference.streams(9, 0, n, s)
    sign_z, chi2_z = reference.law_z_scores(D, idx, n, 64)
    assert sign_z < 6.0 and chi2_z < 6.0
    assert len(np.unique(np.asarray(idx))) < s      # with replacement
    stuck = jnp.zeros_like(idx)
    assert reference.law_z_scores(jnp.ones_like(D), stuck, n, 64) > (6.0, 6.0)


def test_reference_imports_nothing_of_the_program():
    tree = ast.parse(pathlib.Path(reference.__file__).read_text())
    names = {a.name for node in ast.walk(tree) if isinstance(node, ast.Import)
             for a in node.names} | {node.module for node in ast.walk(tree)
                                     if isinstance(node, ast.ImportFrom)}
    assert not any(name.startswith("libskylark_tpu") for name in names)


# -- the counts ------------------------------------------------------------


def test_fut_mix_sample_counts_and_least_time():
    work = counts.work(harness.load_cell(WORKLOAD).config)
    # the butterfly's adds: m·log2(m)·n = 2^20·20·1024; (m·n + s·n)·4 B
    assert work == {"flops": (1 << 20) * 20 * 1024,
                    "bytes": ((1 << 20) * 1024 + 4096 * 1024) * 4}
    assert work["flops"] == 21_474_836_480 and work["bytes"] == 4_311_744_512
    least, bound = roofline.least_time(work, roofline.peaks("TPU v5 lite"))
    assert bound == "hbm"                   # reading A once binds it
    assert least == pytest.approx(4.311744512e9 / 819e9) == pytest.approx(5.2646e-3, rel=1e-4)


def test_fut_mix_sample_counts_follow_the_configuration():
    assert counts.work({"m": 8, "n": 3, "s": 2}) == {
        "flops": 8 * 3 * 3, "bytes": (24 + 6) * 4}


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


def _applies(count, n=1024, cols=16):
    from libskylark_tpu import Context
    from libskylark_tpu import sketch as sk

    X = jnp.asarray(np.random.default_rng(1).standard_normal((n, cols)), jnp.float32)
    T = sk.FJLT(n, 64, Context(5), fut="wht")
    for _ in range(count):
        T.apply(X, sk.COLUMNWISE).block_until_ready()


def _read(operations, busy_s=0.5):
    run_ = harness.Run(cell=None, device_kind="cpu", operations=operations,
                       trace=types.SimpleNamespace(busy_s=busy_s) if busy_s else None)
    return harness._reader("mix_rate.apply")(run_)


def test_mix_rate_reads_the_last_operations_spans(ring):
    ring.set_enabled(True)
    _applies(3, n=512)          # warm-up: another shape, left out
    _applies(12)
    assert _read(12) == pytest.approx(12 * 1024 * 16 / 0.5 / 1e9)


def test_mix_rate_needs_ten_whole_spans_and_a_trace(ring):
    assert _read(12) is None                    # gate shut: no span
    ring.set_enabled(True)
    _applies(9)
    assert _read(9) is None                     # under ten
    _applies(3)
    assert _read(12) is not None
    assert _read(14) is None                    # fewer spans than operations
    assert _read(12, busy_s=0) is None          # nothing traced
    assert _read(0) is None


def test_mix_rate_on_recorded_spans(monkeypatch):
    """Spans as a traced run of the cell leaves them (twelve applies of
    2^20 × 1024 entries, busy 0.27 s): 47.7 G entries/s; a feature-map
    dispatch among them is not counted."""
    from libskylark_tpu.telemetry import trace

    recorded = {"path": "fut", "family": "FJLT", "fut": "wht",
                "kernel": "pallas_blocks", "factors": (64, 128, 128),
                "elements": 1 << 30, "sampled": 4096 * 1024}
    spans = [types.SimpleNamespace(name="sketch.dispatch", attrs=dict(recorded))
             for _ in range(12)]
    spans.insert(5, types.SimpleNamespace(
        name="sketch.dispatch", attrs={"path": "features", "features": 7}))
    spans.insert(0, types.SimpleNamespace(name="sketch.apply", attrs={}))
    monkeypatch.setattr(trace, "finished_spans", lambda: spans)
    assert _read(12, busy_s=0.27) == pytest.approx(12 * 2**30 / 0.27 / 1e9)
    # a program older than the route's spans (the parent) gives nothing
    monkeypatch.setattr(trace, "finished_spans", lambda: [])
    assert _read(12, busy_s=0.27) is None


def test_traced_run_off_the_tpu_reports_only_span_metrics(cell, ring):
    result = run(cell, trace=True, seconds=0.5)
    assert set(result["metrics"]) == {
        "sketch_host_ms.apply", "stream_key_ms.apply", "sketch_dispatch_ms.apply"}
    assert "breakdown" not in result
