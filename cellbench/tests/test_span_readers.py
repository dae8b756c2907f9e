"""The per-layer metrics that read the program's own spans: each reader
gives a number from a ring the program filled, leaves the metric out where
there is nothing whole to read, and the traced run of the harness opens the
program's gate by itself. Host-clock numbers of a CPU rehearsal; none is a
device metric."""

import dataclasses
import time

import jax.numpy as jnp
import numpy as np
import pytest

from cellbench import harness

READERS = ("sketch_host_ms.apply", "stream_key_ms.apply", "sketch_plan_ms.apply")


@pytest.fixture
def ring():
    from libskylark_tpu import telemetry
    from libskylark_tpu.telemetry import metrics, trace

    before = metrics._ENABLED
    trace.clear_finished()
    yield telemetry
    metrics._ENABLED = before
    trace.clear_finished()


def applies(count):
    from libskylark_tpu import Context
    from libskylark_tpu import sketch as sk

    A = jnp.asarray(np.random.default_rng(1).standard_normal((16, 512)), jnp.float32)
    for _ in range(count):      # a transform each: none pins its operator
        sk.JLT(512, 64, Context(5)).apply(A, sk.ROWWISE).block_until_ready()


def read_all(operations):
    run = harness.Run(cell=None, device_kind="cpu", operations=operations, trace=None)
    return {name: harness._reader(name)(run) for name in READERS}


def test_the_manifest_lists_the_readers_for_jlt_apply():
    listed = {m["name"]: m for m in harness.load_cell("jlt_apply").per_layer}
    for name in READERS:
        assert listed[name]["moves"] == "apply_ms" and listed[name]["unit"] == "ms"


@pytest.mark.parametrize("name", READERS)
def test_no_span_no_number(ring, name):
    ring.set_enabled(False)
    applies(12)                                   # gate shut: the ring stays empty
    assert read_all(12)[name] is None
    assert read_all(0)[name] is None


def test_readers_give_the_stages_of_the_last_operations(ring):
    ring.set_enabled(True)
    applies(14)
    got = read_all(12)
    assert all(isinstance(v, float) and v > 0 for v in got.values()), got
    # every apply's stages lie inside it (medians of each taken apart: the
    # order of the sums holds because it holds in every apply)
    stages = ring.stage_seconds("sketch.apply", last=12)
    assert all(s["children"]["stream.key"] + s["children"]["sketch.plan"]
               <= s["total_s"] for s in stages)
    assert got["stream_key_ms.apply"] < got["sketch_host_ms.apply"]
    assert got["sketch_plan_ms.apply"] < got["sketch_host_ms.apply"]


@pytest.mark.parametrize("name", READERS)
def test_fewer_than_ten_or_a_wrapped_ring_is_left_out(ring, name):
    from libskylark_tpu.telemetry import trace

    ring.set_enabled(True)
    applies(9)
    assert read_all(9)[name] is None               # too few for a median
    applies(3)
    assert read_all(12)[name] is not None
    for _ in range(trace._FINISHED.maxlen):        # the ring wraps past them
        with ring.span("stream.key"):
            pass
    assert read_all(12)[name] is None


def test_traced_run_opens_the_gate_by_itself(tiny_cell, ring):
    """With telemetry off, the profiler session of a traced run is enough:
    the line carries the three span metrics (here beside no device metric,
    since a CPU run has no device plane)."""
    ring.set_enabled(False)
    cell = dataclasses.replace(
        tiny_cell("jlt_apply"), per_layer=harness.load_cell("jlt_apply").per_layer)
    result = harness.run_cell(cell, 7, 1.0, True, t_start=time.perf_counter())
    assert set(result["metrics"]) == set(READERS)
    assert all(m["unit"] == "ms" for m in result["metrics"].values())
    # off the TPU the reused transform pins its operator after a few applies,
    # and an apply then derives no key and resolves no plan: those two read 0
    assert result["metrics"]["sketch_host_ms.apply"]["value"] > 0
    assert result["attempted"] >= 10 and result["correct"] is True


@pytest.mark.parametrize("workload,outermost,inner", [
    ("jlt_apply", "sketch.apply", "sketch.dispatch"),
    ("randsvd", "nla.approximate_svd", "engine.call")])
def test_span_split_prints_every_parent_span(tiny_cell, ring, monkeypatch, capsys,
                                             workload, outermost, inner):
    from cellbench.tools import span_split

    cell = tiny_cell(workload)
    monkeypatch.setattr(harness, "load_cell", lambda name, *manifest: cell)
    assert span_split.main(["--workload", workload, "--seed", "5",
                            "--seconds", "0.3", "--allow-cpu"]) == 0
    lines = [ln for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("[span_split]")]
    assert lines[0].startswith("[span_split] samples") and "median_ms=" in lines[0]
    stage = next(ln for ln in lines if f"stage name={outermost} " in ln)
    assert "total_ms=" in stage and "self_ms=" in stage and f"{inner}=" in stage
    assert lines[-1].startswith("[span_split] outside ms=")
    assert ring.enabled() is False                  # the tool shuts its gate again
