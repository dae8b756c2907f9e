"""Set-up accounted from inside the program (telemetry/setup.py): import
records that add up to wall time, one trace / lower / backend_compile
record a fresh jit, union not sum, the ``until_ns`` cut, always on with no
span opened, bounded. Host-clock numbers of a CPU run; none is a device
metric."""

import json
import os
import subprocess
import sys
import time
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from libskylark_tpu import telemetry
from libskylark_tpu.telemetry import setup

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_IMPORTS = """
import json, sys, time
import jax                      # the caller's own, before the package
t0 = time.perf_counter_ns()
import libskylark_tpu
from libskylark_tpu import sketch
t1 = time.perf_counter_ns()
from libskylark_tpu.telemetry import setup
print(json.dumps({
    "wall_s": (t1 - t0) * 1e-9,
    "records": [r._asdict() for r in setup.records(until_ns=t1)],
    "union_s": setup.seconds(("import",), until_ns=t1),
    "specs": sorted({type(m.__spec__).__name__
                     for m in list(sys.modules.values())
                     if getattr(m, "__spec__", None) is not None}),
    "first_on_meta_path": type(sys.meta_path[0]).__name__,
}))
"""


@pytest.fixture(scope="module")
def imported():
    """What a process of its own recorded around ``import libskylark_tpu;
    from libskylark_tpu import sketch``."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    done = subprocess.run([sys.executable, "-c", _IMPORTS], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=240)
    assert done.returncode == 0, done.stderr[-2000:]
    got = json.loads(done.stdout.strip().splitlines()[-1])
    assert {r["phase"] for r in got["records"]} == {"import"}
    return got


@pytest.fixture
def records(monkeypatch):
    """An empty list of the module's own size in place of the process's: a
    test worker that has compiled thousands of programs has wrapped its
    own, and counts over a wrapped list mean nothing."""
    monkeypatch.setattr(setup, "_RECORDS", deque(maxlen=setup._RECORDS.maxlen))
    monkeypatch.setattr(setup, "_dropped", 0)
    return setup


def test_import_records_add_up_to_wall_time(imported):
    own = sum(r["self_ns"] for r in imported["records"]) * 1e-9
    wall = imported["wall_s"]
    assert abs(own - wall) <= max(0.05 * wall, 0.020), (own, wall)
    # nested intervals: their union is the same wall time
    assert imported["union_s"] == pytest.approx(own, abs=1e-6)
    assert all(0 <= r["self_ns"] <= r["t_end_ns"] - r["t_start_ns"]
               for r in imported["records"])


def test_import_records_name_the_modules(imported):
    names = [r["name"] for r in imported["records"]]
    assert len(names) == len(set(names))            # a module is imported once
    assert "libskylark_tpu" in names                # from the stamp on its line 1
    assert "libskylark_tpu.sketch" in names
    assert "libskylark_tpu.sketch.qrft" in names
    assert any(n.split(".")[0] == "scipy" for n in names)   # what qrft pulls
    # the package's record leaves its modules out: they have their own
    by_name = {r["name"]: r for r in imported["records"]}
    package = by_name["libskylark_tpu"]
    assert package["self_ns"] < package["t_end_ns"] - package["t_start_ns"]


def test_the_callers_jax_has_no_record(imported):
    assert not [r["name"] for r in imported["records"]
                if r["name"].split(".")[0] in ("jax", "jaxlib", "numpy")]


def test_a_timed_spec_is_a_plain_one_again(imported):
    assert imported["first_on_meta_path"] == "_ImportWatch"
    assert "_TimedSpec" not in imported["specs"]


def test_no_frame_of_the_watch_stands_under_an_import(records, tmp_path, monkeypatch):
    """The clock is read from calls made before and after a module's code
    runs, never from a frame around it: the depth of the stack under an
    import is the parent commit's (CPython's frame chunks made a deeper
    one cost 0.4 s on the chip host)."""
    name = f"setup_probe_module_{time.time_ns()}"
    (tmp_path / f"{name}.py").write_text(
        "import sys\n"
        "FILES = []\n"
        "f = sys._getframe()\n"
        "while f is not None:\n"
        "    FILES.append(f.f_code.co_filename)\n"
        "    f = f.f_back\n")
    monkeypatch.syspath_prepend(str(tmp_path))
    around = setup._enter("libskylark_tpu.setup_probe_importer")
    try:
        probe = __import__(name)        # first imported inside one of ours
    finally:
        setup._leave(around)
        sys.modules.pop(name, None)
    assert not [f for f in probe.FILES if f.endswith("telemetry/setup.py")]
    assert type(probe.__spec__).__name__ == "ModuleSpec"
    inner, outer = [r for r in setup.records() if r.phase == "import"]
    assert (inner.name, outer.name) == (name, "libskylark_tpu.setup_probe_importer")
    assert outer.self_ns == (outer.t_end_ns - outer.t_start_ns
                             - (inner.t_end_ns - inner.t_start_ns))


def _fresh(tag):
    def program(x):
        return x * 2.0 + 1.0
    program.__name__ = program.__qualname__ = f"setup_probe_{tag}_{time.time_ns()}"
    return program


def _named(name):
    return [r for r in setup.records() if r.name == name]


def test_a_fresh_jit_leaves_one_record_a_phase_and_a_second_call_none(records):
    program = _fresh("once")
    jitted = jax.jit(program)
    x = jnp.ones((7, 3), jnp.float32)
    jitted(x).block_until_ready()
    first = _named(program.__name__)
    assert sorted(r.phase for r in first) == ["backend_compile", "lower", "trace"]
    assert all(r.t_end_ns > r.t_start_ns and r.self_ns > 0 for r in first)
    order = {r.phase: r for r in first}
    assert (order["trace"].t_end_ns <= order["lower"].t_end_ns
            <= order["backend_compile"].t_end_ns)
    jitted(x).block_until_ready()
    assert _named(program.__name__) == first


def test_a_jit_traced_inside_anothers_trace_is_not_counted_twice(records):
    inner = _fresh("inner")

    def slow_inner(x):
        time.sleep(0.05)        # runs while tracing, and only then
        return inner(x)
    slow_inner.__name__ = slow_inner.__qualname__ = inner.__name__
    jitted_inner = jax.jit(slow_inner)
    outer = _fresh("outer")

    def traced_outer(x):
        return jitted_inner(x) + 1.0
    traced_outer.__name__ = traced_outer.__qualname__ = outer.__name__

    before = setup.seconds(("trace",))
    jax.jit(traced_outer)(jnp.ones((5,), jnp.float32)).block_until_ready()
    grew = setup.seconds(("trace",)) - before
    (inside,) = [r for r in _named(inner.__name__) if r.phase == "trace"]
    (around,) = [r for r in _named(outer.__name__) if r.phase == "trace"]
    assert around.t_start_ns <= inside.t_start_ns and inside.t_end_ns <= around.t_end_ns
    both = (inside.t_end_ns - inside.t_start_ns
            + around.t_end_ns - around.t_start_ns) * 1e-9
    assert both >= 0.1 and grew <= both - 0.04      # the union, not the sum
    assert grew == pytest.approx((around.t_end_ns - around.t_start_ns) * 1e-9,
                                 abs=5e-3)
    # and the outer record's own seconds leave the inner one out
    assert around.self_ns <= (around.t_end_ns - around.t_start_ns
                              - (inside.t_end_ns - inside.t_start_ns))


def test_until_leaves_out_a_compile_that_ended_after_it(records):
    early, late = _fresh("early"), _fresh("late")
    x = jnp.ones((3, 5), jnp.float32)
    jax.jit(early)(x).block_until_ready()
    cut = time.perf_counter_ns()
    jax.jit(late)(x).block_until_ready()
    until = {r.name for r in setup.records(until_ns=cut)}
    assert early.__name__ in until and late.__name__ not in until
    whole, part = setup.summary(), setup.summary(until_ns=cut)
    for phase in ("trace", "lower", "backend_compile"):
        assert part["events"][phase] < whole["events"][phase]
        assert part["seconds"][phase] < whole["seconds"][phase]
    for phase in ("lower", "backend_compile"):   # one program, no op inside
        assert part["events"][phase] == whole["events"][phase] - 1
    assert late.__name__ not in {r["name"] for r in part["largest"]}


def test_summary_has_every_phase_and_the_largest_records(records):
    x = jnp.ones((6, 2), jnp.float32)
    for tag in ("a", "b"):
        jax.jit(_fresh(tag))(x).block_until_ready()
    got = setup.summary(top=3)
    assert set(got) == {"seconds", "events", "total_s", "largest", "dropped"}
    assert got["dropped"] == 0
    assert set(got["seconds"]) == set(got["events"]) == set(setup.PHASES)
    assert len(got["largest"]) == 3
    own = [r["self_s"] for r in got["largest"]]
    assert own == sorted(own, reverse=True)
    # the parts overlap at most (an import made while tracing)
    assert got["total_s"] <= sum(got["seconds"][p] for p in setup.PHASES[:-1]) + 1e-9
    json.dumps(got)


def test_always_on_and_no_span_opened(records):
    """With telemetry disabled and no profiler session an apply of a new
    shape still leaves its records, and the span ring stays empty."""
    from libskylark_tpu import Context
    from libskylark_tpu import sketch as sk
    from libskylark_tpu.telemetry import metrics, trace

    gate = metrics._ENABLED
    telemetry.set_enabled(False)
    trace.clear_finished()
    try:
        A = jnp.asarray(np.random.default_rng(3).standard_normal((24, 517)),
                        jnp.float32)
        sk.JLT(517, 61, Context(11)).apply(A, sk.ROWWISE).block_until_ready()
        after = setup.summary()["events"]
        assert telemetry.finished_spans() == []
    finally:
        metrics._ENABLED = gate
    for phase in ("trace", "lower", "backend_compile"):
        assert after[phase] > 0


def test_the_list_is_bounded(monkeypatch):
    assert setup._RECORDS.maxlen is not None and setup._RECORDS.maxlen <= 8192
    monkeypatch.setattr(setup, "_RECORDS", deque(maxlen=8))
    monkeypatch.setattr(setup, "_dropped", 0)
    monkeypatch.setattr(setup, "_counters", None)
    for i in range(20):
        setup._record("trace", f"r{i}", i, i + 1, 1)
        assert setup.dropped() == max(0, i - 7)     # and says what it let go
    assert [r.name for r in setup.records()] == [f"r{i}" for i in range(12, 20)]
    assert setup.summary()["dropped"] == 12


def test_a_cache_load_is_named_after_its_compile_and_never_added(monkeypatch):
    monkeypatch.setattr(setup, "_RECORDS", deque(maxlen=64))
    monkeypatch.setattr(setup, "_counters", None)
    setup._on_duration("/jax/compilation_cache/cache_retrieval_time_sec", 0.010)
    assert setup.records() == []                    # waits for its compile
    setup._on_duration("/jax/core/compile/backend_compile_duration", 0.012,
                       fun_name="jit(loaded)")
    setup._on_duration("/jax/core/unrelated_duration", 5.0, fun_name="x")
    load, compile_ = setup.records()
    assert (load.phase, load.name) == ("cache_load", "loaded")
    assert (compile_.phase, compile_.name) == ("backend_compile", "loaded")
    got = setup.summary()
    assert got["seconds"]["cache_load"] == pytest.approx(0.010, abs=1e-6)
    assert got["seconds"]["backend_compile"] == pytest.approx(0.012, abs=1e-6)
    assert got["total_s"] == pytest.approx(0.012, abs=1e-6)


def test_the_two_counters_ride_the_one_registry():
    from libskylark_tpu.telemetry.names import METRICS, SETUP_PHASES

    assert METRICS["setup.seconds"] == METRICS["setup.events"] == "counter"
    assert tuple(SETUP_PHASES) == setup.PHASES
    program = _fresh("counted")
    assert setup.records()          # the process's own list, never emptied
    seconds, events = setup._counters
    x = jnp.ones((2, 9), jnp.float32)       # its own program first
    before = (seconds.value(phase="lower"), events.value(phase="lower"))
    jax.jit(program)(x).block_until_ready()
    (lower,) = [r for r in _named(program.__name__) if r.phase == "lower"]
    assert events.value(phase="lower") == before[1] + 1
    assert seconds.value(phase="lower") == pytest.approx(
        before[0] + lower.self_ns * 1e-9)
    snap = telemetry.snapshot()["metrics"]
    assert {"phase": "import"} in [v["labels"] for v in snap["setup.seconds"]["values"]]
    text = telemetry.prometheus_text()
    assert 'skylark_setup_seconds_total{phase="import"}' in text
    assert 'skylark_setup_events_total{phase="backend_compile"}' in text
