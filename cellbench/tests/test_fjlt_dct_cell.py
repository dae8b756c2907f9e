"""The cell ``fjlt_dct_apply_cw`` (the Blendenpik sketch with upstream's own
mixer: FJLT with the DCT, columnwise, of a tall dense operand whose height
is no power of two) at a tiny size on the CPU: the contract's keys, the
controls and broken timed paths come out not correct, the reference against
the cosine sum of the definition, the counts against a hand count, and the
accepted reader ``mix_rate.apply`` on the new route's spans. Nothing here is
a device metric."""

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
from cellbench.counts import fut_dct_mix_sample as counts
from cellbench.references import dct_fjlt as reference

WORKLOAD = "fjlt_dct_apply_cw"
CONFIG = "fjlt_blendenpik_dct_m1000000_n1024"
# the cell's ratios at a size a CPU run holds: m ≫ s = 4n, m a multiple of
# 1000 and no power of two. norm_dev is statistical, about
# |z|·√(2/n)/(2√s): 3.5e-4·|z| at the cell's n = 1024, s = 4096, 5.5e-3·|z|
# here, so its limit is restated at the same seven sigmas.
TINY = {"m": 4000, "n": 64, "s": 256, "check_cols": 32,
        "limits": {"rel_max": 1e-6, "norm_dev": 4e-2,
                   "sign_mean_z": 6.0, "sample_chi2_z": 6.0}}
CONTROLS = {"reference_bf16x2", "reference_bf16", "reference_bf16_table",
            "unmixed_sample"}


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
    assert "dispatch route=fut kernel=xla_dft factors=" in log


def test_the_manifest_entries():
    whole = harness.load_cell(WORKLOAD)
    assert whole.chips == 1 and whole.traffic["loop"] == "closed"
    assert whole.traffic["driver"] == "fjlt_dct_apply_cw"
    assert whole.traffic["warm_steps"] == 4 and whole.traffic["trace_seconds"] == 4
    assert {m["name"] for m in whole.end_to_end} == {"apply_ms", "setup_s"}
    listed = {m["name"] for m in whole.per_layer}
    # the sibling cell's set: the route opens no sketch.plan
    assert listed == {m["name"] for m in harness.load_cell("fjlt_apply_cw").per_layer}
    assert "mix_rate.apply" in listed and "sketch_plan_ms.apply" not in listed
    manifest = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    assert WORKLOAD in [w["name"] for w in manifest["workloads"]]
    assert all(w["chips"] == 1 for w in manifest["workloads"])
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    assert len(set(pairs)) == len(pairs)


def test_the_configuration_states_what_the_contract_asks():
    cfg = harness.load_cell(WORKLOAD).config
    assert (cfg["family"], cfg["fut"], cfg["m"], cfg["n"], cfg["s"]) == (
        "FJLT", "dct", 1_000_000, 1024, 4096)
    assert cfg["m"] & (cfg["m"] - 1) and cfg["m"] % 1000 == 0
    assert cfg["s"] == cfg["gamma"] * cfg["n"] and cfg["dimension"] == "columnwise"
    assert set(cfg["reduced"]) == {"rows", "panels"}
    assert "fut" not in cfg["assumed"]          # the mixer is the source's own
    for key in ("source", "guarantees", "assumed", "deployment", "limits", "memory"):
        assert cfg[key]
    assert len(cfg["source"]) <= 200
    sibling = harness.load_cell("fjlt_apply_cw").config
    assert set(cfg) == set(sibling)             # the keys of its sibling
    manifest = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert entry["source"] == cfg["source"] and entry["reduced"] == ["rows", "panels"]
    assert entry["source"] != next(
        c for c in manifest["configs"]
        if c["name"] == "fjlt_blendenpik_m1048576_n1024")["source"]


def test_same_seed_same_operands_and_large_seeds_differ(cell, driver):
    a = driver.setup(cell.config, cell.traffic, 2**32 + 5)
    b = driver.setup(cell.config, cell.traffic, 2**32 + 5)
    other = driver.setup(cell.config, cell.traffic, 5)
    assert all(np.array_equal(x, y) for x, y in zip(a.panels, b.panels))
    assert a.context_seed == b.context_seed != other.context_seed
    assert not np.array_equal(a.panels[0], other.panels[0])
    assert len(a.panels) == 2 and a.panels[0].shape == (TINY["m"], TINY["n"])
    assert a.transform._fut_name == "dct"
    energy = np.square(np.asarray(a.panels[0], np.float64)).sum(axis=1)
    assert energy[::64].sum() / energy.sum() > 0.9


@pytest.mark.parametrize("name", sorted(CONTROLS))
def test_every_control_is_not_correct(cell, driver, name):
    def control(state, _step):
        return driver.controls(state)[name]

    assert set(driver.controls(driver.setup(cell.config, cell.traffic, 3))) == CONTROLS
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
        return jnp.asarray(reference.apply_cols(
            state.panels[i % len(state.panels)], jnp.ones_like(D), idx), jnp.float32)
    return broken


def _pad_the_axis(state, step):
    """The DCT of the axis padded to the next power of two: another operator."""
    def broken(i):
        from libskylark_tpu import sketch as sk
        from libskylark_tpu.base.context import Context

        cfg = state.config
        panel = state.panels[i % len(state.panels)]
        padded = 1 << (cfg["m"] - 1).bit_length()
        T = sk.FJLT(padded, cfg["s"], Context(state.context_seed))
        return T.apply(jnp.pad(panel, ((0, padded - cfg["m"]), (0, 0))), sk.COLUMNWISE)
    return broken


@pytest.mark.parametrize("breaker", [_lose_a_column_block, _lose_the_signs,
                                     _pad_the_axis])
def test_broken_timed_path_is_not_correct(cell, breaker):
    result = run(cell, step_wrapper=breaker)
    assert result["correct"] is False
    assert result["attempted"] > 0 and math.isfinite(result["metrics"]["setup_s"]["value"])


def test_the_unmixed_sample_is_refused_by_the_norm(cell, driver):
    state = driver.setup(cell.config, cell.traffic, 11)
    unmixed = driver.controls(state)["unmixed_sample"]
    got = driver.check(state, [(i, unmixed(i)) for i in range(2)])
    assert got["norm_dev"] > cell.config["limits"]["norm_dev"]


def test_the_laws_are_read_from_the_transforms_own_streams(cell, driver):
    state = driver.setup(cell.config, cell.traffic, 13)
    kept = [(i, driver.step(state, i)) for i in range(2)]
    sound = driver.check(state, kept)
    assert sound["sign_mean_z"] < 6.0 and sound["sample_chi2_z"] < 6.0
    assert sound["rel_max"] < cell.config["limits"]["rel_max"]
    m, s = cell.config["m"], cell.config["s"]
    state.transform.diagonal = lambda *a, **k: jnp.ones((m,), jnp.float32)
    state.transform.sample_indices = lambda: jnp.arange(s, dtype=jnp.int32)
    broken = driver.check(state, kept)
    assert broken["sign_mean_z"] > 6.0 and broken["sample_chi2_z"] > 6.0
    assert broken["rel_max"] == sound["rel_max"]


def test_the_controls_order_by_what_they_cut():
    """Two bfloat16 parts of the operand lie between the reference and one
    part; a bfloat16 cosine table reads as one part does."""
    n, s = 3000, 256
    D, idx = reference.streams(5, 0, n, s)
    A = jnp.asarray(np.random.default_rng(6).standard_normal((n, 16)), jnp.float32)
    ref = reference.apply_cols(A, D, idx)

    def err(got):
        return float(np.max(np.abs(np.asarray(got, np.float64) - ref)) / np.max(np.abs(ref)))

    full = err(reference.cosine_sum_cols(A, D, idx))
    two = err(reference.cosine_sum_cols(A, D, idx, "bf16x2"))
    one = err(reference.cosine_sum_cols(A, D, idx, "bf16"))
    table = err(reference.cosine_sum_cols(A, D, idx, "highest", "bf16"))
    assert full < 8e-7 < two < 5e-5 < one < 1e-2
    assert 2e-4 < table < 1e-2
    assert 8e-7 < err(reference.apply_cols(A, D, idx, "bf16x2")) < 5e-5
    with pytest.raises(ValueError):
        reference.apply_cols(A, D, idx, "fp8")
    with pytest.raises(ValueError):
        reference.cosine_sum_cols(A[:2999], D[:2999], idx)


# -- the reference ---------------------------------------------------------


@pytest.mark.parametrize("n", [1000, 999, 1 << 9])
def test_reference_is_the_cosine_sum_of_the_definition(n):
    s = 96
    D, idx = reference.streams(3, 2, n, s)
    assert D.shape == (n,) and idx.shape == (s,)
    assert set(np.unique(np.asarray(D))) == {-1.0, 1.0}
    assert 0 <= int(idx.min()) and int(idx.max()) < n
    k = np.asarray(idx, np.int64)[:, None]
    j = np.arange(n, dtype=np.int64)[None, :]
    C = 2.0 * np.cos(np.pi * ((k * (2 * j + 1)) % (4 * n)) / (2.0 * n))
    S = math.sqrt(n / s) / math.sqrt(2.0 * n) * C * np.asarray(D, np.float64)[None, :]
    A = np.random.default_rng(4).standard_normal((n, 7)).astype(np.float32)
    got = reference.apply_cols(jnp.asarray(A), D, idx)
    assert got.dtype == np.float64
    np.testing.assert_allclose(got, S @ A.astype(np.float64),
                               atol=1e-12 * np.abs(S @ A).max())
    # every row of S but k = 0 has unit squared norm times N/s... the k = 0
    # row of upstream's scale counts twice
    norms = (S * S).sum(axis=1) * s / n
    zero = np.asarray(idx) == 0
    np.testing.assert_allclose(norms[~zero], 1.0, atol=1e-9)
    np.testing.assert_allclose(norms[zero], 2.0, atol=1e-9)
    D2, idx2 = reference.streams(3, 2, n, 2 * s)
    assert np.array_equal(np.asarray(idx2)[:s], np.asarray(idx))


def test_reference_streams_on_a_span_that_is_no_power_of_two():
    n, s = 1_000_000, 1 << 13             # two chunks of the sample stream
    D, idx = reference.streams(9, 0, n, s)
    sign_z, chi2_z = reference.law_z_scores(D, idx, n, 64)
    assert sign_z < 6.0 and chi2_z < 6.0
    assert int(idx.max()) < n and int(idx.max()) > n - n // 64
    stuck = jnp.zeros_like(idx)
    assert reference.law_z_scores(jnp.ones_like(D), stuck, n, 64) > (6.0, 6.0)


def test_reference_imports_nothing_of_the_program():
    tree = ast.parse(pathlib.Path(reference.__file__).read_text())
    names = {a.name for node in ast.walk(tree) if isinstance(node, ast.Import)
             for a in node.names} | {node.module for node in ast.walk(tree)
                                     if isinstance(node, ast.ImportFrom)}
    assert not any(name.startswith("libskylark_tpu") for name in names)


# -- the counts ------------------------------------------------------------


def test_fut_dct_mix_sample_counts_and_least_time():
    work = counts.work(harness.load_cell(WORKLOAD).config)
    # a fast DCT's (5/2)·m·log2(m) a column; (m·n + s·n)·4 B
    assert work == {"flops": int(2.5 * 1e6 * math.log2(1e6) * 1024),
                    "bytes": (1_000_000 * 1024 + 4096 * 1024) * 4}
    assert work["flops"] == 51_024_815_537 and work["bytes"] == 4_112_777_216
    least, bound = roofline.least_time(work, roofline.peaks("TPU v5 lite"))
    assert bound == "hbm"                   # reading A once binds it
    assert least == pytest.approx(4.112777216e9 / 819e9) == pytest.approx(5.0217e-3, rel=1e-4)


def test_fut_dct_mix_sample_counts_follow_the_configuration():
    assert counts.work({"m": 8, "n": 3, "s": 2}) == {
        "flops": int(2.5 * 8 * 3 * 3), "bytes": (24 + 6) * 4}


# -- the accepted reader on the new route's spans ----------------------------


@pytest.fixture
def ring():
    from libskylark_tpu import telemetry
    from libskylark_tpu.telemetry import metrics, trace

    before = metrics._ENABLED
    trace.clear_finished()
    yield telemetry
    metrics._ENABLED = before
    trace.clear_finished()


def _applies(count, n=1000, cols=16):
    from libskylark_tpu import Context
    from libskylark_tpu import sketch as sk

    X = jnp.asarray(np.random.default_rng(1).standard_normal((n, cols)), jnp.float32)
    T = sk.FJLT(n, 64, Context(5))
    for _ in range(count):
        T.apply(X, sk.COLUMNWISE).block_until_ready()


def _read(operations, busy_s=0.5):
    run_ = harness.Run(cell=None, device_kind="cpu", operations=operations,
                       trace=types.SimpleNamespace(busy_s=busy_s) if busy_s else None)
    return harness._reader("mix_rate.apply")(run_)


def test_mix_rate_reads_the_dct_routes_spans(ring):
    ring.set_enabled(True)
    _applies(3, n=600)          # warm-up: another shape, left out
    _applies(12)
    assert _read(12) == pytest.approx(12 * 1000 * 16 / 0.5 / 1e9)


def test_mix_rate_on_recorded_spans_of_the_cell(monkeypatch):
    """Spans as a traced run of the cell leaves them; a program that has no
    such route (the parent fails before it opens one) gives nothing."""
    from libskylark_tpu.telemetry import trace

    recorded = {"path": "fut", "family": "FJLT", "fut": "dct",
                "kernel": "xla_dft", "factors": (100, 125, 80),
                "elements": 1_000_000 * 1024, "sampled": 4096 * 1024}
    spans = [types.SimpleNamespace(name="sketch.dispatch", attrs=dict(recorded))
             for _ in range(12)]
    monkeypatch.setattr(trace, "finished_spans", lambda: spans)
    assert _read(12, busy_s=1.2) == pytest.approx(12 * 1.024e9 / 1.2 / 1e9)
    monkeypatch.setattr(trace, "finished_spans", lambda: [])
    assert _read(12, busy_s=1.2) is None


def test_traced_run_off_the_tpu_reports_only_span_metrics(cell, ring):
    result = run(cell, trace=True, seconds=0.5)
    assert set(result["metrics"]) == {
        "sketch_host_ms.apply", "stream_key_ms.apply", "sketch_dispatch_ms.apply"}
    assert "breakdown" not in result
