"""The two per-layer metrics that split the device's idle time an apply at
the program's handover to the runtime (``cellbench/periods.py``): on a
synthetic ring and a synthetic ``Reduction`` the identity before + after +
device = period holds, and the metric is left out wherever there is nothing
whole to read. Host-clock numbers of a CPU; none is a device metric."""

import dataclasses
import json
import time

import pytest

from cellbench import harness, periods
from cellbench import trace as trace_mod

BEFORE, AFTER = "idle_before_enqueue_ms.apply", "idle_after_enqueue_ms.apply"
CELLS = ["jlt_apply", "cwt_sparse_apply", "rft_features_apply", "jlt_apply_cw",
         "fjlt_apply_cw", "fjlt_dct_apply_cw", "fastfood_features_apply",
         "tensorsketch_features_apply"]
PERIOD_NS, BEFORE_NS, DEVICE_S = 20_000_000, 310_000, 18.2e-3


@pytest.fixture
def ring():
    from libskylark_tpu.telemetry import metrics, trace

    before = metrics._ENABLED
    trace.clear_finished()
    yield trace
    metrics._ENABLED = before
    trace.clear_finished()


def put(ring, name, start, end, trace_id):
    s = ring.Span(name, trace_id, None, None, None)
    s.t_start_ns, s.t_end_ns = start, end
    ring._FINISHED.append(s)


def applies(ring, count, *, twice_in=None, jitter=0):
    """``count`` applies a period apart, each a plan, then a dispatch whose
    body starts ``BEFORE_NS`` after the apply; apply ``twice_in`` dispatches
    twice. ``jitter`` lengthens every other apply's Python."""
    for k in range(count):
        t0, trace_id = k * PERIOD_NS, f"apply-{k}"
        before = BEFORE_NS + (jitter if k % 2 else 0)
        put(ring, "sketch.plan", t0 + 100_000, t0 + 150_000, trace_id)
        put(ring, "sketch.dispatch", t0 + before, t0 + before + 400_000, trace_id)
        if k == twice_in:
            put(ring, "sketch.dispatch", t0 + 900_000, t0 + 950_000, trace_id)
        put(ring, "sketch.apply", t0, t0 + 1_000_000, trace_id)


def run_of(operations, device_s=DEVICE_S, traced=True):
    reduction = trace_mod.Reduction(
        window_s=operations * PERIOD_NS * 1e-9, busy_s=operations * device_s,
        busy_s_by_device={}, n_ops=2 * operations, op_seconds={}, gap_seconds={})
    return harness.Run(cell=None, device_kind="cpu", operations=operations,
                       trace=reduction if traced else None)


def read(name, run):
    return harness._reader(name)(run)


@pytest.mark.parametrize("name,source,layer", [
    (BEFORE, "host_clock", "sketch kernel"), (AFTER, "device_trace", "device")])
def test_the_manifest_lists_both_in_all_eight_cells(name, source, layer):
    manifest = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    (entry,) = [m for m in manifest["per_layer"] if m["name"] == name]
    assert entry == {"name": name, "unit": "ms", "better": "lower",
                     "source": source, "layer": layer, "moves": "apply_ms",
                     "workloads": CELLS}
    assert [m["name"] for m in manifest["per_layer"]][-2:] == [BEFORE, AFTER]
    for cell in CELLS:
        assert name in {m["name"] for m in harness.load_cell(cell).per_layer}


def test_before_plus_after_plus_device_is_the_period(ring, capsys):
    applies(ring, 14)
    run = run_of(14)
    before, after = read(BEFORE, run), read(AFTER, run)
    assert before == pytest.approx(BEFORE_NS * 1e-6, rel=1e-12)
    assert before + after + DEVICE_S * 1e3 == pytest.approx(
        PERIOD_NS * 1e-6, rel=1e-12)
    assert after > before > 0
    # and the two accounts of the idle share agree where nothing stalls
    assert (before + after) / (PERIOD_NS * 1e-6) == pytest.approx(
        run.trace.idle_share, rel=1e-9)
    lines = [ln for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("[cellbench] idle_")]
    assert [ln.split()[1] for ln in lines] == ["idle_before", "idle_after"]
    for field in ("periods=13", "handovers=1", "before_ms=0.3100",
                  "self_ms=0.2600", "sketch.plan=0.0500", "call_ms=0.4000",
                  "reader_s="):
        assert field in lines[0], field
    for field in ("periods=13", "period_ms=20.0000", "before_ms=0.3100",
                  "device_ms=18.2000", "after_ms=1.4900", "idle_share=0.09000",
                  "idle_share_traced=0.09000", "reader_s="):
        assert field in lines[1], field


def test_medians_are_taken_apart(ring):
    applies(ring, 15, jitter=200_000)       # 14 periods: seven with more Python
    got = periods.split(run_of(15), "idle_before")
    assert got["periods"] == 14
    assert got["before_s"] == pytest.approx((BEFORE_NS + 100_000) * 1e-9)
    assert got["period_s"] == pytest.approx(PERIOD_NS * 1e-9)


@pytest.mark.parametrize("name", [BEFORE, AFTER])
def test_nothing_whole_to_read_leaves_the_metric_out(ring, monkeypatch, name):
    applies(ring, 14)
    assert read(name, run_of(14)) is not None
    assert read(name, run_of(14, traced=False)) is None   # no device plane
    assert read(name, run_of(0)) is None
    ring.clear_finished()
    applies(ring, 10)                                     # nine periods
    assert read(name, run_of(10)) is None
    ring.clear_finished()
    applies(ring, 11)                                     # ten
    assert read(name, run_of(11)) is not None
    monkeypatch.delattr(ring, "apply_periods")            # an older program
    assert read(name, run_of(11)) is None


@pytest.mark.parametrize("name", [BEFORE, AFTER])
def test_an_apply_that_hands_over_twice_leaves_the_metric_out(ring, capsys, name):
    applies(ring, 14, twice_in=5)
    assert read(name, run_of(14)) is None
    (line,) = [ln for ln in capsys.readouterr().out.splitlines()
               if ln.startswith("[cellbench] idle_")]
    assert "left_out=handovers" in line and "with_1=12" in line and "with_2=1" in line
    ring.clear_finished()
    applies(ring, 14, twice_in=13)      # the newest apply: it has no period
    assert read(name, run_of(14)) is not None


@pytest.mark.parametrize("name", [BEFORE, AFTER])
def test_a_wrapped_ring_leaves_the_metric_out(ring, capsys, name):
    applies(ring, 14)
    for k in range(ring._FINISHED.maxlen):      # the ring wraps past them
        put(ring, "stream.key", 10**12 + k, 10**12 + k + 1, "later")
    assert read(name, run_of(14)) is None
    ring.clear_finished()
    for k in range(ring._FINISHED.maxlen):      # a window that lost its start
        put(ring, "stream.key", k, k + 1, "earlier")
    applies(ring, 14)
    assert read(name, run_of(14)) is None
    assert "left_out=wrapped_ring" in capsys.readouterr().out


def test_a_cpu_rehearsal_prints_neither(tiny_cell, ring):
    """A traced CPU run has no device plane: the line carries the span
    metrics it carried before and neither idle metric."""
    from libskylark_tpu import telemetry

    telemetry.set_enabled(False)
    cell = dataclasses.replace(
        tiny_cell("jlt_apply"), per_layer=harness.load_cell("jlt_apply").per_layer)
    assert {BEFORE, AFTER} <= {m["name"] for m in cell.per_layer}
    result = harness.run_cell(cell, 7, 1.0, True, t_start=time.perf_counter())
    assert set(result["metrics"]) == {
        "sketch_host_ms.apply", "stream_key_ms.apply", "sketch_plan_ms.apply"}
    # the program kept the periods all the same: they are the operator's too
    assert len(telemetry.apply_periods("sketch.apply")) >= 9
