"""The three per-layer metrics that read the program's set-up records
(``setup_import_s``, ``setup_lower_s``, ``setup_compile_s``): each gives the
seconds that ended before the window's first ``sketch.apply`` and leaves out
what was compiled after it (the check's reference); ``None`` off the chip,
with no whole window in the ring, and on a program that keeps no such
records. Host-clock numbers of a CPU rehearsal; none is a device metric."""

import json
import time
import types
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cellbench import harness

READERS = {"setup_import_s": ("import",), "setup_lower_s": ("trace", "lower"),
           "setup_compile_s": ("backend_compile",)}
CELLS = ["jlt_apply", "cwt_sparse_apply", "rft_features_apply", "jlt_apply_cw"]


@pytest.fixture
def ring(monkeypatch):
    """The gate open, an empty span ring and an empty list of set-up records
    (this process's own may have dropped its imports by now) holding one
    stand-in import."""
    from libskylark_tpu import telemetry
    from libskylark_tpu.telemetry import metrics, setup, trace

    monkeypatch.setattr(setup, "_RECORDS", deque(maxlen=setup._RECORDS.maxlen))
    monkeypatch.setattr(setup, "_dropped", 0)
    now = time.perf_counter_ns()
    setup._record("import", "libskylark_tpu.stand_in", now - 7_000_000, now, 7_000_000)
    before = metrics._ENABLED
    trace.clear_finished()
    telemetry.set_enabled(True)
    yield telemetry
    metrics._ENABLED = before
    trace.clear_finished()


def applies(count):
    from libskylark_tpu import Context
    from libskylark_tpu import sketch as sk

    A = jnp.asarray(np.random.default_rng(1).standard_normal((16, 512)), jnp.float32)
    for _ in range(count):
        sk.JLT(512, 64, Context(5)).apply(A, sk.ROWWISE).block_until_ready()


def read_all(operations, traced=True):
    """As ``test_feature_cells.py``'s ``_read``: a stand-in ``Reduction``."""
    run = harness.Run(cell=None, device_kind="cpu", operations=operations,
                      trace=types.SimpleNamespace(busy_s=0.5) if traced else None)
    return {name: harness._reader(name)(run) for name in READERS}


def compile_something():
    def reference(x):
        return jnp.tanh(x) @ x.T
    reference.__name__ = f"check_reference_{time.time_ns()}"
    jax.jit(reference)(jnp.ones((4, 4), jnp.float32)).block_until_ready()
    return reference.__name__


@pytest.mark.parametrize("name", sorted(READERS))
def test_reads_what_ended_before_the_windows_first_apply(ring, name):
    from libskylark_tpu.telemetry import setup

    applies(2)                                  # warm-up: its compiles count
    compile_something()
    cut = time.perf_counter_ns()
    applies(12)                                 # the window
    want = setup.seconds(READERS[name], until_ns=cut)
    assert want > 0
    got = read_all(12)[name]
    assert got == pytest.approx(want, abs=1e-9)
    # the check compiles its reference after the window, before the readers:
    # a run whose check is skipped reads the same number
    late = compile_something()
    assert late in {r.name for r in setup.records()}
    assert setup.seconds(READERS[name]) > want or name == "setup_import_s"
    assert read_all(12)[name] == got
    # a window of fewer operations starts later, and still before the check
    assert got <= read_all(5)[name] <= setup.seconds(READERS[name], until_ns=cut + 10**12)


@pytest.mark.parametrize("name", sorted(READERS))
def test_nothing_whole_to_read_gives_none(ring, monkeypatch, name):
    from libskylark_tpu import telemetry
    from libskylark_tpu.telemetry import trace

    assert read_all(12)[name] is None                   # an empty ring
    applies(12)
    assert read_all(12)[name] is not None
    assert read_all(12, traced=False)[name] is None     # no device plane
    assert read_all(0)[name] is None
    assert read_all(14)[name] is None                   # fewer spans than operations
    for _ in range(trace._FINISHED.maxlen):             # the ring wraps past them
        with ring.span("stream.key"):
            pass
    assert read_all(12)[name] is None
    trace.clear_finished()
    applies(12)
    assert read_all(12)[name] is not None
    monkeypatch.setattr(telemetry.setup, "_dropped", 1)  # the list let a record go
    assert read_all(12)[name] is None
    monkeypatch.setattr(telemetry.setup, "_dropped", 0)
    monkeypatch.delattr(telemetry, "setup")             # a program older than the records
    assert read_all(12)[name] is None


@pytest.mark.parametrize("name", sorted(READERS))
def test_the_manifest_lists_all_four_cells(name):
    manifest = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    (entry,) = [m for m in manifest["per_layer"] if m["name"] == name]
    assert entry == {"name": name, "unit": "s", "better": "lower",
                     "source": "host_clock", "layer": "set-up",
                     "moves": "setup_s", "workloads": CELLS}
    for workload in CELLS:
        assert name in {m["name"] for m in harness.load_cell(workload).per_layer}


def test_the_phases_are_the_programs():
    from libskylark_tpu.telemetry import setup
    from libskylark_tpu.telemetry.names import SETUP_PHASES

    assert set(SETUP_PHASES) == set(setup.PHASES)
    for name, phases in READERS.items():
        assert all(SETUP_PHASES[p] == name for p in phases)
    assert {v for v in SETUP_PHASES.values()} == set(READERS) | {"operator"}
