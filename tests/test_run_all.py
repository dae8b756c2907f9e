"""Persistence/resume semantics of benchmarks/run_all.py.

The bench driver must survive being killed mid-suite (a chip call has a
time limit): it persists after every config, a --resume pass re-measures
only what's missing, and no code path may destroy previously captured
evidence (ref: the run-on-target measurement discipline of
tests/unit/CMakeLists.txt:10-46 — capture must be incremental and
idempotent).

Bench bodies are stubbed — these tests exercise the orchestration, not
the measurements. Stubs return the table's REAL metric names (records
are keyed by the bench table's metric, and the gate direction table only
knows those names).
"""

from __future__ import annotations

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "benchmarks"))

import run_all  # noqa: E402

M_A = "jlt_sketch_apply_GBps"            # slot: bench_jlt
M_B = "cwt_sparse_apply_Mnnz_per_s"      # slot: bench_cwt_sparse
SEL = "bench_jlt,bench_cwt_sparse"


def _stub(metric, value):
    def fn(scale):
        return {"metric": metric, "value": value, "unit": "u"}
    return fn


def _crash(metric):
    def fn(scale):
        raise RuntimeError("boom")
    return fn


@pytest.fixture
def harness(monkeypatch, tmp_path):
    """run_all with stubbed benches saving into tmp_path. Returns
    (runner, saved, tmp_path); runner(argv, [jlt_stub, cwt_stub]) -> exit
    code. Tests must select stubbed slots via --only so the real (slow)
    bench bodies never run."""
    monkeypatch.setattr(run_all, "HERE", str(tmp_path))

    def runner(argv, benches):
        slots = ["bench_jlt", "bench_cwt_sparse"]
        for name, fn in zip(slots, benches):
            fn.__name__ = name            # --only matches fn.__name__
            monkeypatch.setattr(run_all, name, fn)
        monkeypatch.setattr(sys, "argv", ["run_all.py"] + argv)
        try:
            run_all.main()
        except SystemExit as e:
            return e.code if isinstance(e.code, int) else 1
        return 0

    def saved(round_no):
        import jax

        path = tmp_path / (
            f"results_r{round_no:02d}_{jax.default_backend()}.json")
        return json.loads(path.read_text()) if path.exists() else None

    return runner, saved, tmp_path


def _rows(doc):
    return {r["metric"]: r for r in doc["results"]}


def test_persists_after_each_config_and_null_on_crash(harness):
    runner, saved, _ = harness
    code = runner(["--scale", "small", "--save", "90", "--only", SEL],
                  [_stub(M_A, 1.5), _crash(M_B)])
    assert code == 0
    rows = _rows(saved(90))
    assert rows[M_A]["value"] == 1.5
    assert rows[M_B]["value"] is None and "boom" in rows[M_B]["error"]


def test_resume_skips_captured_and_remeasures_null(harness):
    runner, saved, _ = harness
    runner(["--scale", "small", "--save", "90", "--only", SEL],
           [_stub(M_A, 1.5), _crash(M_B)])
    # second pass: M_A must NOT re-run (a re-run would record 9.9);
    # M_B (null) must re-measure and succeed now
    code = runner(["--scale", "small", "--save", "90", "--resume",
                   "--only", SEL],
                  [_stub(M_A, 9.9), _stub(M_B, 2.0)])
    assert code == 0
    rows = _rows(saved(90))
    assert rows[M_A]["value"] == 1.5 and rows[M_A]["resumed"] is True
    assert rows[M_B]["value"] == 2.0 and "resumed" not in rows[M_B]


def test_failed_remeasure_keeps_good_record(harness):
    runner, saved, _ = harness
    runner(["--scale", "small", "--save", "90", "--only", "bench_jlt"],
           [_stub(M_A, 1.5)])
    # NO --resume: M_A re-runs and crashes — the captured value survives
    code = runner(["--scale", "small", "--save", "90",
                   "--only", "bench_jlt"], [_crash(M_A)])
    assert code == 0
    rec = _rows(saved(90))[M_A]
    assert rec["value"] == 1.5 and "boom" in rec["remeasure_error"]


def test_only_selection_carries_other_rows(harness):
    runner, saved, _ = harness
    runner(["--scale", "small", "--save", "90", "--only", SEL],
           [_stub(M_A, 1.5), _stub(M_B, 2.5)])
    runner(["--scale", "small", "--save", "90", "--only", "bench_jlt"],
           [_stub(M_A, 3.5)])
    rows = _rows(saved(90))
    assert rows[M_A]["value"] == 3.5      # re-measured
    assert rows[M_B]["value"] == 2.5      # carried through


def test_scale_mismatch_refuses_overwrite(harness):
    runner, saved, _ = harness
    runner(["--scale", "small", "--save", "90", "--only", "bench_jlt"],
           [_stub(M_A, 1.5)])
    code = runner(["--scale", "full", "--save", "90",
                   "--only", "bench_jlt"], [_stub(M_A, 9.9)])
    assert code != 0
    assert _rows(saved(90))[M_A]["value"] == 1.5  # file untouched


def test_resume_requires_save(harness):
    runner, _, _ = harness
    code = runner(["--scale", "small", "--resume", "--only", "bench_jlt"],
                  [_stub(M_A, 1.5)])
    assert code != 0


def _write_prior(tmp, value):
    import jax

    backend = jax.default_backend()
    (tmp / f"results_r89_{backend}.json").write_text(json.dumps(
        {"round": 89, "scale": "small", "backend": backend,
         "results": [{"metric": M_A, "value": value}]}))


def test_vs_prior_excludes_own_file(harness):
    runner, saved, tmp = harness
    _write_prior(tmp, 1.0)                # a genuine prior round
    runner(["--scale", "small", "--save", "90", "--only", "bench_jlt"],
           [_stub(M_A, 2.0)])
    # a --resume pass must keep the 2.0x cross-round ratio, not
    # recompute a self-comparison of 1.0 against its own save file
    runner(["--scale", "small", "--save", "90", "--resume",
            "--only", "bench_jlt"], [_stub(M_A, 9.9)])
    rec = _rows(saved(90))[M_A]
    assert rec["value"] == 2.0 and rec["vs_best_prior"] == 2.0


def test_gate_fails_on_resumed_regression(harness):
    runner, saved, tmp = harness
    _write_prior(tmp, 10.0)
    runner(["--scale", "small", "--save", "90", "--only", "bench_jlt"],
           [_stub(M_A, 1.0)])  # 0.1x — a regression, captured pre-kill
    code = runner(["--scale", "small", "--save", "90", "--resume",
                   "--gate", "--only", "bench_jlt"], [_stub(M_A, 9.9)])
    assert code == 1  # the resumed regression still fails the gate


def _write_prior_with_canary(tmp, value, canary_s):
    import jax

    backend = jax.default_backend()
    (tmp / f"results_r89_{backend}.json").write_text(json.dumps(
        {"round": 89, "scale": "small", "backend": backend,
         "canary_s": canary_s,
         "results": [{"metric": M_A, "value": value}]}))


def test_gate_normalizes_host_speed_drift(harness, monkeypatch):
    """r4 verdict #2: on the CPU backend a uniform host-speed change
    must NOT trip the gate (the canary cancels it), while a genuine
    same-host regression still must."""
    runner, saved, tmp = harness
    _write_prior_with_canary(tmp, 10.0, canary_s=0.1)

    # today's host is 2x slower: canary doubles, throughput halves.
    # Raw ratio 0.55 would trip the 0.9 gate; normalized is 1.1.
    monkeypatch.setattr(run_all, "canary_seconds", lambda: 0.2)
    code = runner(["--scale", "small", "--save", "90", "--gate",
                   "--only", "bench_jlt"], [_stub(M_A, 5.5)])
    assert code == 0
    rec = _rows(saved(90))[M_A]
    assert rec["vs_best_prior"] == 0.55          # raw ratio still shown
    assert rec["vs_best_prior_canary_norm"] == 1.1
    assert rec["canary_normalized"] == 1.1

    # same host speed as the prior, value genuinely down 50%: trips
    monkeypatch.setattr(run_all, "canary_seconds", lambda: 0.1)
    code = runner(["--scale", "small", "--save", "91", "--gate",
                   "--only", "bench_jlt"], [_stub(M_A, 5.0)])
    assert code == 1


def test_prior_without_canary_still_gates_raw(harness):
    """Pre-r5 rounds have no canary_s: the raw ratchet must keep
    working against them."""
    runner, saved, tmp = harness
    _write_prior(tmp, 10.0)
    code = runner(["--scale", "small", "--save", "90", "--gate",
                   "--only", "bench_jlt"], [_stub(M_A, 5.0)])
    assert code == 1
    rec = _rows(saved(90))[M_A]
    assert rec["vs_best_prior"] == 0.5
    assert "vs_best_prior_canary_norm" not in rec
