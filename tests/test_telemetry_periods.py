"""``telemetry.apply_periods``: the periods of a closed blocking loop split
at the program's handover to the runtime (``telemetry/names.py``
``HANDOVER``) — on hand-built rings, where every nanosecond is known, and
on the applies of the eight benchmark cells' transforms at tiny CPU
shapes, each of which must hand over exactly once."""

from __future__ import annotations

import numpy as np
import pytest

from libskylark_tpu import Context, telemetry
from libskylark_tpu import sketch as sk
from libskylark_tpu.telemetry import metrics as mmod
from libskylark_tpu.telemetry import trace as tmod
from libskylark_tpu.telemetry.names import HANDOVER, SPANS

ROOT = "sketch.apply"
NS = 1e-9


@pytest.fixture(autouse=True)
def _telemetry_state():
    prev = mmod._ENABLED
    tmod.clear_finished()
    yield
    mmod._ENABLED = prev
    tmod.clear_finished()


def put(name, start, end, trace, thread="MainThread"):
    """One finished span of the ring, its stamps in nanoseconds."""
    s = tmod.Span(name, trace, None, None, None)
    s.t_start_ns, s.t_end_ns, s.thread = start, end, thread
    tmod._FINISHED.append(s)
    return s


def compiled_apply(t0, trace, *, execute=(500, 800)):
    """An apply through ``engine.compiled``: sketch.apply → sketch.dispatch
    → engine.call → engine.lookup, engine.execute (children finish first,
    as in the ring)."""
    put("stream.key", t0 + 50, t0 + 60, trace)
    put("sketch.plan", t0 + 100, t0 + 200, trace)
    put("engine.lookup", t0 + 350, t0 + 400, trace)
    put("engine.execute", t0 + execute[0], t0 + execute[1], trace)
    put("engine.call", t0 + 320, t0 + 880, trace)
    put("sketch.dispatch", t0 + 300, t0 + 900, trace)
    return put(ROOT, t0, t0 + 1000, trace)


def test_handover_is_declared_in_order_of_preference():
    assert HANDOVER == ("engine.execute", "sketch.dispatch")
    assert all(name in SPANS for name in HANDOVER)
    assert "apply_periods" in telemetry.__all__


def test_descendants_through_two_levels_and_parts_to_the_nanosecond():
    compiled_apply(0, "a")
    compiled_apply(2000, "b")
    compiled_apply(4500, "c")           # the newest: it has no successor
    first, second = telemetry.apply_periods(ROOT)
    assert first["period_s"] == pytest.approx(2000 * NS, abs=1e-15)
    assert second["period_s"] == pytest.approx(2500 * NS, abs=1e-15)
    for p in (first, second):
        assert p["handover"] == "engine.execute" and p["handovers"] == 1
        assert p["before_s"] == pytest.approx(500 * NS, abs=1e-15)
        assert p["call_s"] == pytest.approx(300 * NS, abs=1e-15)
        # by the innermost span open at each instant: the ancestors of the
        # handover give their own time ahead of the executable's call
        assert {k: round(v / NS) for k, v in p["before_by_name"].items()} == {
            "stream.key": 10, "sketch.plan": 100, "sketch.dispatch": 20,
            "engine.call": 30 + 100, "engine.lookup": 50}
        assert round(p["before_self_s"] / NS) == 50 + 40 + 100
        assert abs(sum(p["before_by_name"].values()) + p["before_self_s"]
                   - p["before_s"]) < 0.5 * NS


def test_the_parts_add_up_whatever_the_stamps():
    rng = np.random.default_rng(53)
    t0 = 0
    for k in range(40):
        a, b, c, d, e, f = np.sort(rng.integers(1, 10**6, 6)).tolist()
        put("stream.key", t0 + a, t0 + b, f"t{k}")
        put("engine.lookup", t0 + c + 1, t0 + d, f"t{k}")
        put("engine.execute", t0 + d, t0 + e, f"t{k}")
        put("engine.call", t0 + c, t0 + e, f"t{k}")
        put(ROOT, t0, t0 + f, f"t{k}")
        t0 += f + int(rng.integers(0, 10**6))
    periods = telemetry.apply_periods(ROOT)
    assert len(periods) == 39
    for p in periods:
        parts = sum(round(v / NS) for v in p["before_by_name"].values())
        assert parts + round(p["before_self_s"] / NS) == round(p["before_s"] / NS)


def test_preference_first_call_and_count():
    # both names inside: engine.execute wins over the sketch.dispatch around it
    compiled_apply(0, "a")
    # a jit called directly under sketch.dispatch: the second preference
    put("sketch.plan", 2100, 2200, "b")
    put("sketch.dispatch", 2300, 2900, "b")
    put(ROOT, 2000, 3000, "b")
    # two dispatches an apply: the first one's start, both counted
    put("sketch.dispatch", 4200, 4300, "c")
    put("sketch.dispatch", 4400, 4900, "c")
    put(ROOT, 4000, 5000, "c")
    # two executables under one dispatch
    put("engine.execute", 6300, 6400, "d")
    put("engine.execute", 6500, 6600, "d")
    put("sketch.dispatch", 6100, 6900, "d")
    put(ROOT, 6000, 7000, "d")
    put(ROOT, 8000, 9000, "e")
    got = telemetry.apply_periods(ROOT)
    assert [(p["handover"], p["handovers"], round(p["before_s"] / NS))
            for p in got] == [
        ("engine.execute", 1, 500), ("sketch.dispatch", 1, 300),
        ("sketch.dispatch", 2, 200), ("engine.execute", 2, 300)]
    assert got[1]["before_by_name"] == {"sketch.plan": pytest.approx(100 * NS)}
    assert got[3]["before_by_name"] == {
        "sketch.dispatch": pytest.approx(200 * NS)}


def test_the_newest_root_is_left_out_and_last_counts_roots():
    for k in range(5):
        compiled_apply(2000 * k, f"t{k}")
    assert len(telemetry.apply_periods(ROOT)) == 4
    assert len(telemetry.apply_periods(ROOT, last=3)) == 2
    assert telemetry.apply_periods(ROOT, last=1) == []
    assert telemetry.apply_periods(ROOT, last=0) == []
    assert telemetry.apply_periods("no.such.span") == []
    (only,) = telemetry.apply_periods(ROOT, last=2)
    assert only["period_s"] == pytest.approx(2000 * NS, abs=1e-15)


def test_a_root_without_a_handover_span_gives_none_numbers():
    put("stream.key", 10, 20, "a")
    put(ROOT, 0, 1000, "a")             # an eager composition: no handover
    compiled_apply(3000, "b")
    compiled_apply(5000, "c")
    bare, whole = telemetry.apply_periods(ROOT)
    assert bare == {"period_s": pytest.approx(3000 * NS), "before_s": None,
                    "before_by_name": None, "before_self_s": None,
                    "call_s": None, "handover": None, "handovers": 0}
    assert whole["handovers"] == 1 and whole["before_s"] is not None


def test_a_span_of_another_thread_is_ignored():
    # a flush worker's executable inside the interval, on the same trace
    put("engine.execute", 100, 200, "a", thread="flush-0")
    compiled_apply(0, "a")
    # another thread's applies between this thread's: not its periods
    put("engine.execute", 1500, 1600, "x", thread="worker-1")
    put(ROOT, 1400, 1700, "x", thread="worker-1")
    compiled_apply(2000, "b")
    compiled_apply(4000, "c")
    got = telemetry.apply_periods(ROOT)
    assert [round(p["period_s"] / NS) for p in got] == [2000, 2000]
    assert [p["handovers"] for p in got] == [1, 1]
    assert round(got[0]["before_s"] / NS) == 500
    # the newest root's thread is the one read
    put(ROOT, 6000, 6100, "y", thread="worker-1")
    (other,) = telemetry.apply_periods(ROOT)
    assert round(other["period_s"] / NS) == 6000 - 1400
    assert other["handover"] == "engine.execute"


def test_a_span_outside_the_roots_interval_is_no_descendant():
    put("engine.execute", 1200, 1300, "a")      # same trace, after the root
    put("sketch.dispatch", 300, 900, "a")
    put(ROOT, 0, 1000, "a")
    put(ROOT, 2000, 3000, "b")
    (p,) = telemetry.apply_periods(ROOT)
    assert p["handover"] == "sketch.dispatch" and p["handovers"] == 1


def test_a_wrapped_ring_gives_none():
    telemetry.set_enabled(True)
    ring = tmod._FINISHED.maxlen
    with telemetry.span(ROOT):
        for _ in range(ring + 8):       # the first children fall out
            with telemetry.span("stream.key"):
                pass
    assert len(telemetry.finished_spans()) == ring
    assert telemetry.apply_periods(ROOT) is None
    assert telemetry.stage_seconds(ROOT) is None
    for _ in range(3):                  # a window the ring holds whole
        with telemetry.span(ROOT):
            with telemetry.span("sketch.dispatch"):
                pass
    assert len(telemetry.apply_periods(ROOT, last=3)) == 2
    assert telemetry.apply_periods(ROOT, last=4) is None


# ---------------------------------------------------------------------------
# the eight cells' transforms: one handover an apply, by the expected route
# ---------------------------------------------------------------------------


def _dense(shape):
    import jax.numpy as jnp

    return jnp.asarray(
        np.random.default_rng(3).standard_normal(shape), jnp.float32)


def _jlt(dimension):
    def make():
        A = _dense((24, 512) if dimension == sk.ROWWISE else (512, 24))
        return (lambda: sk.JLT(512, 64, Context(5))), A, dimension
    return make


def _cwt_sparse():
    import scipy.sparse as sp

    from libskylark_tpu.base.sparse import SparseMatrix

    X = sp.random(64, 300, density=0.05, format="csr", dtype=np.float32,
                  random_state=7)
    return ((lambda: sk.CWT(300, 128, Context(5))),
            SparseMatrix.from_scipy(X), sk.ROWWISE)


def _features(tag):
    def make():
        from libskylark_tpu.ml import kernels

        return ((lambda: kernels.Gaussian(48, 3.0).create_rft(
            128, Context(5), tag)), _dense((16, 48)), sk.ROWWISE)
    return make


def _fjlt(n, fut):
    def make():
        return ((lambda: sk.FJLT(n, 32, Context(5), fut=fut)),
                _dense((n, 8)), sk.COLUMNWISE)
    return make


def _tensorsketch():
    from libskylark_tpu.ml import kernels

    return ((lambda: kernels.Polynomial(20, 3, 1.0, 0.05).create_rft(
        64, Context(5))), _dense((16, 20)), sk.ROWWISE)


# off the TPU the dense kernels decline and the JLT's XLA contraction is
# the jit called under sketch.dispatch: the same handover name as on the
# chip. Last: names the split of ``before_s`` carries on that route in every
# apply (off the TPU a reused dense transform pins its operator after a few
# applies and asks for no plan and no key from then on).
DENSE = {"sketch.operand", "stream.key"}
COMPILED = {"engine.call", "engine.lookup", "sketch.dispatch", "stream.key"}
CELLS = [
    ("jlt_apply", _jlt(sk.ROWWISE), "sketch.dispatch",
     {"sketch.operand", "sketch.materialize"}),
    ("jlt_apply_cw", _jlt(sk.COLUMNWISE), "sketch.dispatch",
     {"sketch.operand", "sketch.materialize"}),
    ("cwt_sparse_apply", _cwt_sparse, "engine.execute", COMPILED),
    ("rft_features_apply", _features("regular"), "engine.execute",
     DENSE | COMPILED | {"sketch.materialize"}),
    ("fjlt_apply_cw", _fjlt(1024, "wht"), "engine.execute", DENSE | COMPILED),
    ("fjlt_dct_apply_cw", _fjlt(1000, "dct"), "engine.execute",
     DENSE | COMPILED),
    ("fastfood_features_apply", _features("fast"), "engine.execute",
     DENSE | COMPILED | {"sketch.plan"}),
    ("tensorsketch_features_apply", _tensorsketch, "engine.execute",
     DENSE | COMPILED | {"sketch.plan"}),
]


@pytest.mark.parametrize("cell,make,handover,named", CELLS,
                         ids=[c[0] for c in CELLS])
def test_every_cells_apply_hands_over_once(cell, make, handover, named):
    build, A, dimension = make()
    transform = build()
    transform.apply(A, dimension).block_until_ready()    # compiled, gate shut
    telemetry.set_enabled(True)
    tmod.clear_finished()
    for _ in range(4):
        transform.apply(A, dimension).block_until_ready()
    periods = telemetry.apply_periods(ROOT)
    assert len(periods) == 3
    for p in periods:
        assert p["handovers"] == 1, (cell, p)
        assert p["handover"] == handover
        assert 0 < p["before_s"] < p["period_s"]
        assert p["call_s"] > 0
        assert set(p["before_by_name"]) >= named, (cell, p["before_by_name"])
        assert all(name in SPANS for name in p["before_by_name"])
        assert abs(sum(p["before_by_name"].values()) + p["before_self_s"]
                   - p["before_s"]) < 0.5 * NS
    roots = [s for s in telemetry.finished_spans() if s.name == ROOT]
    assert all(s.attrs["family"] == transform.sketch_type for s in roots)
