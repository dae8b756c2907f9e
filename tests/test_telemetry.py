"""Telemetry subsystem tests (libskylark_tpu/telemetry/).

Covers the registry (counters/gauges/histograms, labels, the
near-free-when-disabled contract, collector adapters), the span API
(contextvar nesting, error status, the ``jax.profiler.TraceAnnotation``
mirror, explicit cross-thread handoff), the exporters (JSONL schema,
Prometheus text), and the serve-pipeline integration the issue's
acceptance criteria name: a request id set at ``submit()`` must appear
on the flush span and on every bisection-isolation child span —
across the thread hop into the flush worker, including under an
injected ``serve.flush`` fault plan.
"""

from __future__ import annotations

import functools
import json
import threading

import numpy as np
import pytest

from libskylark_tpu import Context, engine, telemetry
from libskylark_tpu import sketch as sk
from libskylark_tpu.resilience import faults
from libskylark_tpu.telemetry import export as export_mod
from libskylark_tpu.telemetry import metrics as mmod
from libskylark_tpu.telemetry import trace as tmod


@pytest.fixture(autouse=True)
def _telemetry_state():
    prev = mmod._ENABLED
    tmod.clear_finished()
    yield
    mmod._ENABLED = prev
    tmod.clear_finished()


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


class TestMetrics:
    def test_disabled_record_is_noop(self):
        telemetry.set_enabled(False)
        c = telemetry.counter("t.disabled_counter")
        g = telemetry.gauge("t.disabled_gauge")
        h = telemetry.histogram("t.disabled_hist")
        c.inc()
        g.set(5.0)
        h.observe(0.1)
        assert c.to_dict()["values"] == []
        assert g.to_dict()["values"] == []
        assert h.to_dict()["values"] == []

    def test_counter_labels_and_values(self):
        telemetry.set_enabled(True)
        c = telemetry.counter("t.counter", "help")
        c.inc()
        c.inc(2, site="a")
        c.inc(3, site="a")
        assert c.value() == 1
        assert c.value(site="a") == 5
        doc = c.to_dict()
        assert doc["type"] == "counter" and doc["help"] == "help"

    def test_inc_always_bypasses_gate(self):
        telemetry.set_enabled(False)
        c = telemetry.counter("t.always_counter")
        c.inc_always(outcome="hit")
        assert c.value(outcome="hit") == 1

    def test_gauge_set_and_add(self):
        telemetry.set_enabled(True)
        g = telemetry.gauge("t.gauge")
        g.set(2.5)
        g.add(1.0)
        assert g.value() == 3.5

    def test_histogram_buckets(self):
        telemetry.set_enabled(True)
        h = telemetry.histogram("t.hist", buckets=(0.1, 1.0))
        for v in (0.05, 0.5, 5.0):
            h.observe(v)
        cell = h.to_dict()["values"][0]
        assert cell["counts"] == [1, 1, 1]       # <=0.1, <=1.0, +Inf
        assert cell["count"] == 3
        assert cell["sum"] == pytest.approx(5.55)

    def test_get_or_create_idempotent_and_typed(self):
        assert telemetry.counter("t.same") is telemetry.counter("t.same")
        with pytest.raises(ValueError):
            telemetry.gauge("t.same")

    def test_registry_reset_keeps_handles(self):
        telemetry.set_enabled(True)
        c = telemetry.counter("t.reset_me")
        c.inc(7)
        telemetry.registry().reset()
        assert c.value() == 0
        c.inc(1)
        assert c.value() == 1

    def test_snapshot_structure_and_collectors(self):
        telemetry.register_collector("t.block", lambda: {"x": 1})
        snap = telemetry.snapshot()
        assert set(snap) == {"enabled", "metrics", "collectors"}
        assert snap["collectors"]["t.block"] == {"x": 1}
        # the wired adapters: engine + serve re-homed under one schema
        assert "lifetime" in snap["collectors"]["engine"]
        assert "queued" in snap["collectors"]["serve"]
        json.dumps(snap)  # JSON-able end to end

    def test_broken_collector_never_fails_snapshot(self):
        def boom():
            raise RuntimeError("collector died")

        telemetry.register_collector("t.broken", boom)
        try:
            snap = telemetry.snapshot()
            assert "error" in snap["collectors"]["t.broken"]
        finally:
            telemetry.registry().unregister_collector("t.broken")


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------


class TestSpans:
    def test_disabled_span_yields_none(self):
        telemetry.set_enabled(False)
        with telemetry.span("nope") as sp:
            assert sp is None
        assert telemetry.finished_spans() == []

    def test_force_opens_span_while_disabled(self):
        telemetry.set_enabled(False)
        with telemetry.span("forced", force=True) as sp:
            assert sp is not None
        assert sp.duration_s is not None

    def test_parent_child_nesting_and_restore(self):
        telemetry.set_enabled(True)
        with telemetry.span("root") as root:
            assert telemetry.current_span() is root
            with telemetry.span("child") as child:
                assert child.parent_id == root.span_id
                assert child.trace_id == root.trace_id
            assert telemetry.current_span() is root
        assert telemetry.current_span() is None
        names = [s.name for s in telemetry.finished_spans()]
        assert names == ["child", "root"]      # children finish first

    def test_error_status(self):
        telemetry.set_enabled(True)
        with pytest.raises(ValueError):
            with telemetry.span("boom"):
                raise ValueError("x")
        sp = telemetry.finished_spans()[-1]
        assert sp.status == "error" and "ValueError" in sp.error

    def test_request_id_inheritance(self):
        telemetry.set_enabled(True)
        with telemetry.span("root", request_id="req-7"):
            with telemetry.span("child") as child:
                assert child.request_id == "req-7"

    def test_cross_thread_handoff(self):
        telemetry.set_enabled(True)
        out = {}
        with telemetry.span("origin", request_id="req-x") as origin:
            ctx = telemetry.get_context()

        def work():
            # a fresh thread has NO ambient context...
            with telemetry.span("orphan") as o:
                out["orphan_parent"] = o.parent_id
            # ...until the handoff context is attached explicitly
            with telemetry.attach(ctx):
                with telemetry.span("adopted") as a:
                    out["parent"] = a.parent_id
                    out["trace"] = a.trace_id
                    out["rid"] = a.request_id

        t = threading.Thread(target=work)
        t.start()
        t.join()
        assert out["orphan_parent"] is None
        assert out["parent"] == origin.span_id
        assert out["trace"] == origin.trace_id
        assert out["rid"] == "req-x"

    def test_trace_annotation_mirror(self, monkeypatch):
        import jax.profiler

        entered = []

        class FakeAnnotation:
            def __init__(self, name):
                self.name = name

            def __enter__(self):
                entered.append(self.name)
                return self

            def __exit__(self, *exc):
                return False

        monkeypatch.setattr(jax.profiler, "TraceAnnotation",
                            FakeAnnotation)
        telemetry.set_enabled(True)
        with telemetry.span("mirror.me"):
            pass
        assert entered == ["mirror.me"]

    def test_add_event_lands_on_current_span(self):
        telemetry.set_enabled(True)
        with telemetry.span("evented") as sp:
            telemetry.add_event("retry", {"attempt": 1})
        assert sp.events[0]["name"] == "retry"
        assert sp.events[0]["attrs"]["attempt"] == 1
        telemetry.add_event("dropped")  # outside any span: no-op


# ---------------------------------------------------------------------------
# timer shim: PhaseTimer phases ARE spans now
# ---------------------------------------------------------------------------


class TestTimerShim:
    def test_phase_emits_span_with_own_gate(self):
        from libskylark_tpu.utility import timer as timer_mod

        prev = timer_mod._ENABLED
        telemetry.set_enabled(False)   # global switch OFF...
        try:
            timer_mod.set_enabled(True)  # ...phase gate ON wins (force)
            t = timer_mod.PhaseTimer("shim")
            with t.phase("PHASE_A"):
                pass
            assert t.counts["PHASE_A"] == 1
            sp = telemetry.finished_spans()[-1]
            assert sp.name == "PHASE_A"
            assert sp.attrs["phase_timer"] == "shim"
            assert t.totals["PHASE_A"] == pytest.approx(sp.duration_s)
        finally:
            timer_mod._ENABLED = prev


# ---------------------------------------------------------------------------
# serve pipeline propagation (the acceptance-criteria trace)
# ---------------------------------------------------------------------------


def _ragged_reqs(n, seed=0):
    rng = np.random.default_rng(seed)
    ctx = Context(seed=seed)
    return [(sk.JLT(48, 16, ctx),
             rng.standard_normal((48, 3 + i % 4)).astype(np.float32))
            for i in range(n)]


class TestServePropagation:
    def test_request_id_survives_into_flush_thread(self):
        telemetry.set_enabled(True)
        tmod.clear_finished()
        (T, A), = _ragged_reqs(1)
        with engine.MicrobatchExecutor(max_batch=4, linger_us=500) as ex:
            fut = ex.submit_sketch(T, A, dimension=sk.COLUMNWISE,
                                   request_id="req-hop")
            fut.result(timeout=120)   # flusher pops after linger
        spans = {s.span_id: s for s in telemetry.finished_spans()}
        submits = [s for s in spans.values() if s.name == "serve.submit"]
        flushes = [s for s in spans.values() if s.name == "serve.flush"
                   and "req-hop" in s.attrs.get("request_ids", [])]
        assert len(submits) == 1 and len(flushes) == 1
        fl = flushes[0]
        # the flush ran on the worker thread, not the submitting one,
        # yet parents under the submit span and carries its request id
        assert fl.thread != submits[0].thread
        assert fl.thread.startswith("skylark-serve-worker")
        assert fl.parent_id == submits[0].span_id
        assert fl.request_id == "req-hop"

    def test_request_id_on_flush_and_every_isolation_span(self):
        """The issue's satellite: a request id set at submit() appears
        on the flush span and on every bisection-isolation child span,
        under an injected ``serve.flush`` fault plan."""
        telemetry.set_enabled(True)
        tmod.clear_finished()
        reqs = _ragged_reqs(4)
        rids = [f"req-iso-{i}" for i in range(3)] + ["req-iso-poison"]
        plan = {"seed": 1, "faults": [
            {"site": "serve.flush", "error": "SketchError",
             "tag": "poison"}]}
        with engine.MicrobatchExecutor(max_batch=4,
                                       linger_us=50_000) as ex:
            with faults.fault_plan(plan):
                futs = [ex.submit_sketch(T, A, dimension=sk.COLUMNWISE,
                                         request_id=rid)
                        for (T, A), rid in zip(reqs[:3], rids[:3])]
                with faults.tag("poison"):
                    pT, pA = reqs[3]
                    pf = ex.submit_sketch(pT, pA,
                                          dimension=sk.COLUMNWISE,
                                          request_id=rids[3])
                ex.flush()
                for f in futs:
                    f.result(timeout=120)   # cohort-mates succeed
                with pytest.raises(Exception) as ei:
                    pf.result(timeout=120)
                assert type(ei.value).__name__ == "SketchError"

        spans = telemetry.finished_spans()
        by_id = {s.span_id: s for s in spans}
        flushes = [s for s in spans if s.name == "serve.flush"
                   and set(rids) <= set(s.attrs.get("request_ids", []))]
        assert len(flushes) == 1, "cohort flush span with all ids"
        fl = flushes[0]
        assert fl.status == "error"
        assert by_id[fl.parent_id].name == "serve.submit"

        isolations = [s for s in spans if s.name == "serve.isolation"]
        # cohort of 4: two halves, then the poison half splits again
        assert len(isolations) == 4
        for iso in isolations:
            iso_rids = iso.attrs.get("request_ids", [])
            assert iso_rids, "every isolation span carries request ids"
            assert set(iso_rids) <= set(rids)
            # rooted under THE flush span
            anc = iso
            while anc is not None and anc.name != "serve.flush":
                anc = by_id.get(anc.parent_id)
            assert anc is fl
        poison_leaves = [s for s in isolations
                         if s.attrs.get("request_ids") == [rids[3]]
                         and s.status == "error"]
        assert len(poison_leaves) == 1, "poison pinned at capacity 1"

    def test_no_spans_and_no_ids_when_disabled(self):
        telemetry.set_enabled(False)
        tmod.clear_finished()
        (T, A), = _ragged_reqs(1)
        with engine.MicrobatchExecutor(max_batch=2, linger_us=500) as ex:
            fut = ex.submit_sketch(T, A, dimension=sk.COLUMNWISE)
            fut.result(timeout=120)
        assert [s for s in telemetry.finished_spans()
                if s.name.startswith("serve.")] == []


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------


class TestJsonlExport:
    def test_span_and_metric_lines(self, tmp_path):
        telemetry.set_enabled(True)
        ex = export_mod.JsonlExporter(str(tmp_path))
        try:
            with telemetry.span("outer", request_id="req-j"):
                with telemetry.span("inner"):
                    pass
            ex.flush_sync()
            span_docs = [json.loads(line)
                         for line in open(ex.span_path)]
            names = {d["name"]: d for d in span_docs}
            assert {"outer", "inner"} <= set(names)
            assert (names["inner"]["parent_id"]
                    == names["outer"]["span_id"])
            assert names["inner"]["request_id"] == "req-j"
            for d in span_docs:
                for field in ("kind", "name", "trace_id", "span_id",
                              "t_wall", "duration_s", "status",
                              "thread"):
                    assert field in d
            metric_docs = [json.loads(line)
                           for line in open(ex.metrics_path)]
            assert metric_docs[-1]["kind"] == "metrics"
            assert "collectors" in metric_docs[-1]["snapshot"]
        finally:
            ex.close()

    def test_preemption_hook_runs_final_flush(self, tmp_path):
        from libskylark_tpu.resilience import preemption

        telemetry.set_enabled(True)
        ex = export_mod.JsonlExporter(str(tmp_path))
        try:
            with preemption._LOCK:
                hooks = list(preemption._HOOKS)
            assert ex.flush_sync in hooks
            with telemetry.span("pre-teardown"):
                pass
            ex.flush_sync()
            assert any(json.loads(line)["name"] == "pre-teardown"
                       for line in open(ex.span_path))
        finally:
            ex.close()
        with preemption._LOCK:
            assert ex.flush_sync not in preemption._HOOKS

    def test_install_from_env_is_idempotent(self, tmp_path, monkeypatch):
        monkeypatch.setenv("SKYLARK_TELEMETRY_DIR", str(tmp_path))
        first = export_mod.install_exporter()
        try:
            assert first is not None
            assert export_mod.install_exporter() is first
        finally:
            export_mod.shutdown_exporter()
        assert export_mod.get_exporter() is None


class TestPrometheus:
    def test_counter_gauge_histogram_rendering(self):
        telemetry.set_enabled(True)
        telemetry.counter("t.prom_count").inc(2, site="s")
        telemetry.gauge("t.prom_gauge").set(1.5)
        telemetry.histogram("t.prom_hist", buckets=(1.0,)).observe(0.5)
        text = telemetry.prometheus_text()
        assert 'skylark_t_prom_count_total{site="s"} 2' in text
        assert "skylark_t_prom_gauge 1.5" in text
        assert 'skylark_t_prom_hist_bucket{le="1"} 1' in text
        assert 'skylark_t_prom_hist_bucket{le="+Inf"} 1' in text
        assert "skylark_t_prom_hist_count 1" in text

    def test_unified_counters_exposed(self):
        """The acceptance criterion: prometheus_text() carries the
        re-homed engine/serve/resilience numbers."""
        text = telemetry.prometheus_text()
        assert "skylark_engine_lifetime_misses" in text
        assert "skylark_serve_submitted" in text
        assert "skylark_serve_queued" in text
        assert "skylark_resilience_faults" in text

    def test_label_escaping(self):
        telemetry.set_enabled(True)
        telemetry.counter("t.escape").inc(1, v='a"b\nc')
        text = telemetry.prometheus_text()
        assert 'v="a\\"b\\nc"' in text


# ---------------------------------------------------------------------------
# engine integration
# ---------------------------------------------------------------------------


class TestEngineIntegration:
    def test_dump_stats_embeds_snapshot_atomically(self, tmp_path):
        path = tmp_path / "stats.json"
        engine.dump_stats(str(path))
        doc = json.loads(path.read_text())
        assert "telemetry" in doc
        assert "engine" in doc["telemetry"]["collectors"]
        assert "serve" in doc["telemetry"]["collectors"]
        # atomicity: no orphan temp file left beside the artifact
        assert list(tmp_path.iterdir()) == [path]

    def test_cold_compile_emits_span(self):
        telemetry.set_enabled(True)
        tmod.clear_finished()
        import jax.numpy as jnp

        def f(x):
            return x * 2.0

        cf = engine.compiled(f, name="telemetry.test_compile",
                             key_fn=lambda *a: ("telemetry-span-test",))
        cf(jnp.ones((3,), jnp.float32))
        compiles = [s for s in telemetry.finished_spans()
                    if s.name == "engine.compile"
                    and s.attrs.get("name") == "telemetry.test_compile"]
        assert len(compiles) == 1
        cf(jnp.ones((3,), jnp.float32))   # warm hit: no second span
        compiles = [s for s in telemetry.finished_spans()
                    if s.name == "engine.compile"
                    and s.attrs.get("name") == "telemetry.test_compile"]
        assert len(compiles) == 1


# ---------------------------------------------------------------------------
# spans on the two measured host paths: the apply and the solve
# ---------------------------------------------------------------------------


def _children(spans, parent):
    return [s for s in spans if s.parent_id == parent.span_id]


def _one(spans, name):
    found = [s for s in spans if s.name == name]
    assert len(found) == 1, f"{name}: {[s.name for s in spans]}"
    return found[0]


def _apply_operand(m=24, n=512):
    import jax.numpy as jnp

    return jnp.asarray(
        np.random.default_rng(3).standard_normal((m, n)), jnp.float32)


def _interpreted_kernel(monkeypatch):
    """Route the dense dispatch into the fused kernel in interpret mode
    (off the TPU the dispatch declines it; steered here, in the test)."""
    from libskylark_tpu.sketch import dense as dense_mod
    from libskylark_tpu.sketch import pallas_dense

    def interpreted(key, dist, A, s_dim, scale, which):
        return getattr(pallas_dense, which)(key, dist, A, s_dim, scale,
                                            interpret=True)

    monkeypatch.setattr(dense_mod, "try_pallas_apply", interpreted)


class TestHotPathSpans:
    APPLY_STAGES = {"sketch.operand", "sketch.materialize", "stream.key",
                    "sketch.plan", "sketch.dispatch"}

    def test_gate_shut_leaves_the_ring_empty(self):
        from libskylark_tpu import nla

        telemetry.set_enabled(False)
        sk.JLT(512, 64, Context(5)).apply(_apply_operand(), sk.ROWWISE)
        nla.approximate_svd(_apply_operand(96, 40), 3, Context(6))
        assert telemetry.finished_spans() == []
        assert telemetry.stage_seconds("sketch.apply") == []

    @pytest.mark.parametrize("path", ["pallas", "xla_full"])
    def test_apply_is_one_root_with_its_stages(self, path, monkeypatch):
        if path == "pallas":
            _interpreted_kernel(monkeypatch)
        T = sk.JLT(512, 64, Context(5))
        A = _apply_operand()
        telemetry.set_enabled(True)
        T.apply(A, sk.ROWWISE).block_until_ready()
        spans = telemetry.finished_spans()
        root = _one(spans, "sketch.apply")
        assert root.parent_id is None
        kids = _children(spans, root)
        assert {s.name for s in kids} == self.APPLY_STAGES
        assert len(kids) == len(spans) - 1          # nothing deeper, no stray
        for kid in kids:
            assert root.t_start_ns <= kid.t_start_ns <= kid.t_end_ns \
                <= root.t_end_ns
        assert root.duration_s == pytest.approx(
            (root.t_end_ns - root.t_start_ns) * 1e-9)
        assert root.attrs["path"] == path
        assert root.attrs["family"] == "JLT"
        assert root.attrs["dimension"] == "rowwise"
        assert root.attrs["shape"] == (24, 512)
        assert _one(spans, "sketch.plan").attrs["plan_source"] in (
            "cache", "heuristic")
        assert _one(spans, "sketch.dispatch").attrs["padded"] is False
        whats = sorted(s.attrs["what"] for s in kids
                       if s.name == "stream.key")
        assert all(isinstance(s.attrs["cached"], bool) for s in kids
                   if s.name == "stream.key")
        if path == "pallas":
            # the block-key table is derived inside the apply's program
            assert whats == ["allocation"]
            assert root.attrs["plan_source"] in ("cache", "heuristic")
            assert root.attrs["m_tile"] >= 8 and root.attrs["precision"]
            # one m-tile at this shape: nothing to keep between tiles
            assert root.attrs["operator_residency"] == "per_tile"
        else:
            assert whats == ["allocation", "allocation"]
            assert "operator_residency" not in root.attrs

    @pytest.mark.parametrize("cap,residency", [(0, "hbm"), (None, "vmem")])
    def test_apply_span_names_the_operator_residency(self, cap, residency,
                                                     monkeypatch):
        """``sketch.apply`` says where the kernel kept the generated
        operator between m-tiles — what the effective plan reports."""
        from libskylark_tpu.sketch import pallas_dense
        from libskylark_tpu.sketch import params as sketch_params

        _interpreted_kernel(monkeypatch)
        if cap is not None:
            monkeypatch.setattr(pallas_dense, "_SCRATCH_CAP_BYTES", cap)
        monkeypatch.setattr(sketch_params, "_pallas_m_tile", 8)
        T = sk.JLT(512, 64, Context(5))
        A = _apply_operand()
        telemetry.set_enabled(True)
        T.apply(A, sk.ROWWISE).block_until_ready()
        root = _one(telemetry.finished_spans(), "sketch.apply")
        assert root.attrs["m_tile"] == 8
        assert root.attrs["operator_residency"] == residency
        assert pallas_dense.effective_plan(
            T.dist, A.shape, A.dtype, 64, 1,
            interpret=True)["operator_residency"] == residency

    def test_apply_under_jit_opens_no_span(self):
        import jax

        T = sk.JLT(512, 64, Context(5))
        telemetry.set_enabled(True)
        jax.jit(lambda a: T.apply(a, sk.ROWWISE))(
            _apply_operand()).block_until_ready()
        assert telemetry.finished_spans() == []

    def test_profiler_session_opens_the_gate(self, tmp_path):
        """Telemetry off: a ``jax.profiler`` session alone records the
        spans, in the ring and as events of the profile's host line,
        inside the caller's annotation; the gate shuts with the session."""
        import jax
        from jax.profiler import ProfileData

        T = sk.JLT(512, 64, Context(5))
        A = _apply_operand()
        T.apply(A, sk.ROWWISE).block_until_ready()      # warm, gate shut
        telemetry.set_enabled(False)
        assert telemetry.finished_spans() == []
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 1
        options.host_tracer_level = 2
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            with jax.profiler.TraceAnnotation("test.window"):
                T.apply(A, sk.ROWWISE).block_until_ready()
        finally:
            jax.profiler.stop_trace()
        spans = telemetry.finished_spans()
        root = _one(spans, "sketch.apply")
        assert {s.name for s in _children(spans, root)} == self.APPLY_STAGES
        T.apply(A, sk.ROWWISE).block_until_ready()
        assert len(telemetry.finished_spans()) == len(spans)   # shut again

        (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
        (host,) = [p for p in ProfileData.from_file(str(path)).planes
                   if p.name == "/host:CPU"]
        lines = [[(e.name, e.start_ns, e.start_ns + e.duration_ns)
                  for e in line.events] for line in host.lines]
        (main,) = [evs for evs in lines
                   if any(n == "test.window" for n, _, _ in evs)]
        (_, w0, w1), = [e for e in main if e[0] == "test.window"]
        for name in self.APPLY_STAGES | {"sketch.apply"}:
            events = [e for e in main if e[0] == name]
            assert len(events) == (2 if name == "stream.key" else 1)
            assert all(w0 <= a <= b <= w1 for _, a, b in events)

    def test_solve_is_one_tree_down_to_the_executable(self):
        import jax.numpy as jnp

        from libskylark_tpu import nla

        A = jnp.asarray(np.random.default_rng(9).standard_normal((136, 40)),
                        jnp.float32)
        telemetry.set_enabled(True)
        engine.reset()

        def solve():
            tmod.clear_finished()
            nla.approximate_svd(A, 3, Context(7))
            spans = telemetry.finished_spans()
            root = _one(spans, "nla.approximate_svd")
            assert root.parent_id is None
            assert root.attrs["shape"] == (136, 40) and root.attrs["k"] == 3
            assert sorted(s.name for s in _children(spans, root)) == [
                "engine.call", "stream.key"]
            call = _one(spans, "engine.call")
            assert call.attrs["name"] == "approximate_svd"
            return spans, call

        spans, call = solve()                       # cold
        assert call.attrs["hit"] is False
        assert [s.name for s in _children(spans, call)] == [
            "engine.lookup", "engine.compile", "engine.execute"]
        assert [s.name for s in
                _children(spans, _one(spans, "engine.compile"))] == [
            "engine.lower", "engine.backend_compile"]
        spans, call = solve()                       # warm
        assert call.attrs["hit"] is True
        assert [s.name for s in _children(spans, call)] == [
            "engine.lookup", "engine.execute"]

    def test_stage_seconds_sums_children_and_takes_the_last(self):
        A = _apply_operand()
        telemetry.set_enabled(True)
        for _ in range(3):      # a transform each: none pins its operator
            sk.JLT(512, 64, Context(5)).apply(
                A, sk.ROWWISE).block_until_ready()
        spans = telemetry.finished_spans()
        stages = telemetry.stage_seconds("sketch.apply", last=2)
        assert len(stages) == 2
        root = [s for s in spans if s.name == "sketch.apply"][-1]
        kids = _children(spans, root)
        keys = [s.duration_s for s in kids if s.name == "stream.key"]
        assert len(keys) == 2
        last = stages[-1]
        assert last["total_s"] == root.duration_s
        assert last["children"]["stream.key"] == pytest.approx(sum(keys))
        assert set(last["children"]) == self.APPLY_STAGES
        assert last["self_s"] == pytest.approx(
            root.duration_s - sum(s.duration_s for s in kids))
        assert 0.0 < last["self_s"] < last["total_s"]
        assert len(telemetry.stage_seconds("sketch.apply")) == 3
        assert telemetry.stage_seconds("no.such.span") == []

    def test_stage_seconds_is_none_on_a_wrapped_ring(self):
        telemetry.set_enabled(True)
        ring = tmod._FINISHED.maxlen
        with telemetry.span("sketch.apply"):
            for _ in range(ring + 8):       # the first children fall out
                with telemetry.span("stream.key"):
                    pass
        assert len(telemetry.finished_spans()) == ring
        assert telemetry.stage_seconds("sketch.apply") is None
        # a window the ring still holds whole reads again
        for _ in range(2):
            with telemetry.span("sketch.apply"):
                with telemetry.span("stream.key"):
                    pass
        assert telemetry.stage_seconds("sketch.apply", last=2) is not None
        assert len(telemetry.stage_seconds("sketch.apply", last=2)) == 2
        assert telemetry.stage_seconds("sketch.apply", last=3) is None


# ---------------------------------------------------------------------------
# declared span names (telemetry/names.py SPANS)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _span_literals() -> dict:
    """{string literal: [file:line, ...]} over the first argument of every
    ``span(...)`` call in the package."""
    import ast
    import pathlib

    import libskylark_tpu

    root = pathlib.Path(libskylark_tpu.__file__).parent
    found: dict = {}
    for path in sorted(root.rglob("*.py")):
        for node in ast.walk(ast.parse(path.read_text())):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            if (f.attr if isinstance(f, ast.Attribute)
                    else getattr(f, "id", None)) != "span":
                continue
            first = (node.args[0] if node.args else next(
                (k.value for k in node.keywords if k.arg == "name"), None))
            if first is None:
                continue
            for leaf in ast.walk(first):
                if isinstance(leaf, ast.Constant) and isinstance(
                        leaf.value, str):
                    found.setdefault(leaf.value, []).append(
                        f"{path.relative_to(root)}:{node.lineno}")
    return found


class TestSpanNames:
    from libskylark_tpu.telemetry.names import SPANS

    def test_every_span_call_site_is_declared(self):
        undeclared = {name: sites for name, sites in _span_literals().items()
                      if name not in self.SPANS}
        assert undeclared == {}

    def test_stream_key_says_whether_it_hit(self):
        """The ``stream.key`` span of an allocation sets ``cached`` and
        the access is counted in ``stream.key_cache{result}``, declared
        and created once: what reads the share of hits keys on both."""
        import ast
        import pathlib

        import libskylark_tpu
        from libskylark_tpu.telemetry.names import METRICS

        assert METRICS["stream.key_cache"] == "counter"
        source = (pathlib.Path(libskylark_tpu.__file__).parent
                  / "base" / "context.py").read_text()
        (block,) = [
            node for node in ast.walk(ast.parse(source))
            if isinstance(node, ast.With) and any(
                isinstance(leaf, ast.Constant) and leaf.value == "stream.key"
                for item in node.items for leaf in ast.walk(item))]
        attrs = [call.args[0].value for call in ast.walk(block)
                 if isinstance(call, ast.Call)
                 and getattr(call.func, "attr", None) == "set_attr"]
        assert attrs == ["cached"]
        created = [call for call in ast.walk(ast.parse(source))
                   if isinstance(call, ast.Call)
                   and getattr(call.func, "attr", None) == "counter"]
        assert [c.args[0].value for c in created] == ["stream.key_cache"]
        labels = {kw.arg for call in ast.walk(ast.parse(source))
                  if isinstance(call, ast.Call)
                  and getattr(call.func, "attr", None) == "inc_always"
                  for kw in call.keywords}
        assert labels == {"result"}

    def test_every_handover_name_is_a_declared_span_with_a_site(self):
        """``HANDOVER`` (what ``apply_periods`` splits an apply at) names
        spans the package opens: a rename fails here, not as a metric
        that turns into ``None``."""
        from libskylark_tpu.telemetry.names import HANDOVER

        assert len(HANDOVER) == len(set(HANDOVER)) >= 1
        for name in HANDOVER:
            assert name in self.SPANS, f"undeclared handover {name!r}"
            assert name in _span_literals(), f"no call site for {name!r}"

    @pytest.mark.parametrize("name", sorted(SPANS))
    def test_declared_span_has_a_call_site(self, name):
        assert name in _span_literals(), f"stale declaration {name!r}"
        layer, feeds = self.SPANS[name]
        assert layer and feeds
