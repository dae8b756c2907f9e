"""Scatter-free CWT/CountSketch Pallas kernel (sketch/pallas_hash.py)
and the serve-bucket kernel-selection seam it feeds.

Oracles, strongest first:

- *stream bit-equality*: the kernel's in-VMEM (h, v) generation
  (``_gen_hv`` over the ``chunk_key_table`` keys) reproduces
  ``randgen.stream_slice`` bit-for-bit — jax.random's own
  fold_in/split/randint/rademacher pipeline replayed through the shared
  integer-op Threefry, across chunk boundaries.
- *exact-accumulation bit-equality* (interpret mode): ``accum="exact"``
  equals ``HashTransform.apply`` AND ``cwt_serve_apply`` bitwise,
  including zero-padded serve lanes and across capacity classes (the
  serve layer's lane-invariance contract).
- *MXU-mode dataflow bit-equality on lattice data*: integer-valued
  inputs make every bucket sum exact, so the one-hot contraction is
  bit-equal to the scatter no matter the accumulation order — this pins
  the whole MXU dataflow bitwise; float data is then 1e-5-close (order
  differs, values don't).
- serve integration: a forced-pallas flush is bit-equal to the
  capacity-1 XLA dispatch, the kernel choice is a static of the
  executable key, declines are counted by reason, and on a CPU host the
  tuner correctly certifies XLA for every serve bucket (the interpret
  penalty) while a TPU device kind ranks the kernel where the model
  says it wins.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import jax.random as jr

from libskylark_tpu import Context, engine, tune
from libskylark_tpu import sketch as sk
from libskylark_tpu.base import randgen
from libskylark_tpu.base import threefry as tf
from libskylark_tpu.sketch import pallas_hash as ph
from libskylark_tpu.sketch.hash import cwt_serve_apply


@pytest.fixture()
def fresh_engine():
    engine.reset()
    yield
    engine.reset()


@pytest.fixture()
def mem_plan_cache():
    """In-memory plan cache (no disk, empty): tests that edit plans must
    not touch the committed benchmarks/plan_cache.json."""
    prev = tune.set_cache(tune.PlanCache(path=None))
    yield tune.get_cache()
    tune.set_cache(prev)


def _cwt_and_ref(n, s, m, seed=7, rowwise=False):
    rng = np.random.default_rng(seed)
    T = sk.CWT(n, s, Context(seed=seed))
    kd = np.asarray(jr.key_data(T.allocation.key), np.uint32)
    shape = (m, n) if rowwise else (n, m)
    A = rng.standard_normal(shape).astype(np.float32)
    dim = sk.ROWWISE if rowwise else sk.COLUMNWISE
    ref = np.asarray(T.apply(jnp.asarray(A), dim))
    return T, kd, A, ref


class TestStreamReplication:
    @pytest.mark.parametrize("s_dim", [16, 100, 128])
    @pytest.mark.parametrize("n", [8, 40, 2048, 5000])
    def test_gen_hv_bit_equals_stream_slice(self, s_dim, n):
        """The in-kernel generation path (plain jnp ops here — the same
        ops Mosaic lowers) replays randgen.stream_slice exactly:
        UniformInt bucket stream, Rademacher value stream, across the
        CHUNK boundary (n=5000 spans two chunks)."""
        key = jr.key(42)
        n_pad = ph._padded_n(n)
        n_tile = min(n_pad, ph.CHUNK)
        n_chunks = n_pad // n_tile
        cols = min(n_tile, ph._GEN_COLS)
        tbl = ph.chunk_key_table(key, n_chunks)
        hs, vs = [], []
        for c in range(n_chunks):
            h, v = ph._gen_hv(tbl, c, s_dim, n_tile, cols)
            hs.append(np.asarray(h).reshape(-1))
            vs.append(np.asarray(v).reshape(-1))
        h_ref = np.asarray(randgen.stream_slice(
            jr.fold_in(key, 0), randgen.UniformInt(0, s_dim - 1), 0, n,
            dtype=jnp.int32))
        v_ref = np.asarray(randgen.stream_slice(
            jr.fold_in(key, 1), randgen.Rademacher(), 0, n,
            dtype=jnp.float32))
        assert np.array_equal(np.concatenate(hs)[:n], h_ref)
        assert np.array_equal(np.concatenate(vs)[:n], v_ref)

    def test_randint_multiplier_matches_jax(self):
        # pow2 spans ≤ 2^16 cancel the high draw entirely
        assert tf.randint_multiplier(16) == 0
        assert tf.randint_multiplier(1 << 16) == 0
        # general spans keep jax's double-draw mix
        assert tf.randint_multiplier(100) == ((65536 % 100) ** 2) % 100


class TestBitEquality:
    @pytest.mark.parametrize("rowwise", [False, True])
    @pytest.mark.parametrize("n,s,m", [(40, 16, 3), (100, 24, 5),
                                       (513, 32, 4)])
    def test_exact_accum_bit_equals_apply(self, n, s, m, rowwise):
        _T, kd, A, ref = _cwt_and_ref(n, s, m, rowwise=rowwise)
        out = np.asarray(ph.cwt_apply(kd, A, s_dim=s, rowwise=rowwise,
                                      accum="exact", interpret=True))
        assert np.array_equal(out, ref)

    @pytest.mark.parametrize("rowwise", [False, True])
    def test_padded_serve_lanes_bit_equal(self, rowwise):
        """Zero-padding the stream axis past the transform's true N —
        exactly what the serve bucket's pow2 class does — leaves the
        kernel bit-equal to cwt_serve_apply over the SAME padded
        operand and to the unpadded transform.apply."""
        n, s, m = 40, 16, 3
        _T, kd, A, ref = _cwt_and_ref(n, s, m, rowwise=rowwise)
        pad = [(0, 13), (0, 0)] if not rowwise else [(0, 0), (0, 13)]
        Ap = np.pad(A, pad)
        sv = np.asarray(cwt_serve_apply(kd, jnp.asarray(Ap), s_dim=s,
                                        rowwise=rowwise))
        out = np.asarray(ph.cwt_apply(kd, Ap, s_dim=s, rowwise=rowwise,
                                      accum="exact", interpret=True))
        assert np.array_equal(out, sv)
        assert np.array_equal(out, ref)

    def test_capacity_invariance_batched(self):
        """Per-lane bits are invariant to the cohort's capacity class:
        the same lane at B=1 and inside a B=3 stack (mixed seeds)
        produces identical bits — the serve lane-invariance contract."""
        lanes = [_cwt_and_ref(40, 16, 3, seed=i) for i in range(3)]
        kds = np.stack([kd for (_, kd, _, _) in lanes])
        As = np.stack([A for (_, _, A, _) in lanes])
        out = np.asarray(ph.cwt_apply_batched(
            kds, As, s_dim=16, rowwise=False, accum="exact",
            interpret=True))
        for i, (_, kd, A, ref) in enumerate(lanes):
            solo = np.asarray(ph.cwt_apply(
                kd, A, s_dim=16, rowwise=False, accum="exact",
                interpret=True))
            assert np.array_equal(out[i], solo)
            assert np.array_equal(out[i], ref)

    def test_mxu_mode_bit_equal_on_lattice_data(self):
        """Integer-valued data makes every bucket sum exact in f32, so
        the MXU one-hot contraction — different accumulation ORDER,
        identical values — is bit-equal to the scatter. This pins the
        entire mxu dataflow bitwise."""
        rng = np.random.default_rng(3)
        T = sk.CWT(200, 24, Context(seed=11))
        kd = np.asarray(jr.key_data(T.allocation.key), np.uint32)
        A = rng.integers(-8, 9, (200, 4)).astype(np.float32)
        ref = np.asarray(T.apply(jnp.asarray(A), sk.COLUMNWISE))
        out = np.asarray(ph.cwt_apply(kd, A, s_dim=24, rowwise=False,
                                      accum="mxu", interpret=True))
        assert np.array_equal(out, ref)

    def test_mxu_mode_close_on_float_data(self):
        _T, kd, A, ref = _cwt_and_ref(1000, 32, 5)
        out = np.asarray(ph.cwt_apply(kd, A, s_dim=32, rowwise=False,
                                      accum="mxu", interpret=True))
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


class TestQualifyAndDispatch:
    def test_qualify_reasons(self):
        ok, why = ph.qualify(16, 40, 3, np.float32, interpret=True)
        assert ok and why == "ok"
        ok, why = ph.qualify(16, 40, 3, np.float64, interpret=True)
        assert not ok and "float64" in why
        ok, why = ph.qualify(16, 0, 3, np.float32, interpret=True)
        assert not ok and "degenerate" in why
        ok, why = ph.qualify(16, 40, 3, np.float32, accum="nope")
        assert not ok and "accum" in why
        if not ph.available():
            ok, why = ph.qualify(16, 40, 3, np.float32)
            assert not ok and "TPU" in why

    def test_plan_tiles_shrink_dont_fail(self):
        plan = ph.plan_tiles(40, 3, 16)
        assert plan is not None
        n_pad, n_tile, m_pad, mt = plan
        assert n_pad == 64 and n_tile == 64
        assert m_pad % mt == 0
        # absurd s_dim: no tile fits — decline, never a Mosaic abort
        assert ph.plan_tiles(4096, 8, 50_000_000) is None

    @pytest.mark.skipif(ph.available(), reason="CPU-host dispatch test")
    def test_try_apply_declines_off_tpu(self, monkeypatch,
                                        mem_plan_cache):
        """The direct-apply hook: off-TPU the kernel always declines —
        env override and even a (mis-)certified plan entry cannot route
        an eager apply into uncompileable Mosaic."""
        T = sk.CWT(40, 16, Context(seed=0))
        A = jnp.asarray(np.ones((40, 3), np.float32))
        assert ph.try_apply(T, A, rowwise=False) is None
        monkeypatch.setenv("SKYLARK_HASH_KERNEL", "pallas")
        assert ph.try_apply(T, A, rowwise=False) is None
        monkeypatch.delenv("SKYLARK_HASH_KERNEL")
        w = tune.hash_workload("CWT", A.shape, A.dtype, 16, seq_axis=0)
        mem_plan_cache.put(w, tune.Plan("pallas"), source="measured",
                           value=1.0)
        assert ph.try_apply(T, A, rowwise=False) is None
        # and the public apply still serves (the scatter)
        out = T.apply(A, sk.COLUMNWISE)
        assert np.isfinite(np.asarray(out)).all()

    def test_try_apply_takes_only_the_env_pin(self, monkeypatch,
                                              mem_plan_cache):
        """Where the kernel qualifies (a TPU, played here), a cached
        "pallas" plan for the workload does not steer an eager apply:
        only ``SKYLARK_HASH_KERNEL`` routes it to the kernel."""
        T = sk.CWT(40, 16, Context(seed=0))
        A = jnp.asarray(np.ones((40, 3), np.float32))
        mem_plan_cache.put(
            tune.hash_workload("CWT", A.shape, A.dtype, 16, seq_axis=0),
            tune.Plan("pallas"), source="measured", value=1.0)
        monkeypatch.setattr(ph, "qualify", lambda *a, **k: (True, "ok"))
        calls = []
        monkeypatch.setattr(
            ph, "cwt_apply",
            lambda kd, A, **kw: calls.append(kw["accum"]) or "served")
        monkeypatch.delenv("SKYLARK_HASH_KERNEL", raising=False)
        assert ph.try_apply(T, A, rowwise=False) is None
        monkeypatch.setenv("SKYLARK_HASH_KERNEL", "xla")
        assert ph.try_apply(T, A, rowwise=False) is None
        monkeypatch.setenv("SKYLARK_HASH_KERNEL", "pallas_exact")
        assert ph.try_apply(T, A, rowwise=False) == "served"
        monkeypatch.setenv("SKYLARK_HASH_KERNEL", "pallas")
        assert ph.try_apply(T, A, rowwise=False) == "served"
        assert calls == ["exact", "mxu"]


class TestTuneServeBuckets:
    def test_hash_candidates_and_cpu_ranking(self):
        w = tune.hash_workload("CWT", (1000, 8), "float32", 32,
                               seq_axis=0)
        plans = tune.enumerate_candidates(w)
        assert {p.backend for p in plans} == {"pallas", "xla"}
        # on a CPU host the pallas plan means the interpreter: the
        # penalty must rank XLA first, always
        best, cost = tune.rank_candidates(w)[0]
        assert best.backend == "xla"

    def test_tpu_ranking_prefers_kernel_in_its_regime(self):
        # long stream, narrow sketch: the scatter serializes n rows
        # while the one-hot contraction is cheap — kernel wins
        w = tune.serve_workload(
            "sketch_apply", "CWT", "float32", (1024, 64), 32, 16,
            rowwise=False, device_kind="tpu_v5_lite")
        assert tune.rank_candidates(w)[0][0].backend == "pallas"
        # fastfood: fused chain ~9x less HBM traffic than the XLA chain
        wf = tune.serve_workload(
            "fastfood_features", "FastGaussianRFT", "float32",
            (512, 512), 512, 8, device_kind="tpu_v5_lite")
        assert tune.rank_candidates(wf)[0][0].backend == "pallas"

    def test_serve_key_carries_batch_class_legacy_keys_unchanged(self):
        w = tune.serve_workload("sketch_apply", "JLT", "float32",
                                (64, 128), 32, 8, rowwise=True)
        assert w.key().endswith("|b8")
        legacy = tune.dense_workload("normal", (64, 128), "float32", 32,
                                     seq_axis=1)
        assert "|b" not in legacy.key()

    def test_record_ranked_persists_and_yields_to_measured(
            self, mem_plan_cache):
        w = tune.serve_workload("sketch_apply", "CWT", "float32",
                                (64, 8), 16, 4, rowwise=False)
        plan, cost = tune.record_ranked(w)
        ent = mem_plan_cache.entry(w)
        assert ent["source"] == "ranked"
        assert ent["plan"]["backend"] == plan.backend == "xla"
        # a measured certification is never displaced by a re-ranking
        mem_plan_cache.put(w, tune.Plan("pallas"), source="measured",
                           value=2.0)
        tune.record_ranked(w)
        assert mem_plan_cache.entry(w)["source"] == "measured"

    def test_dense_serve_candidates_cross_m_tiles(self):
        w = tune.serve_workload("sketch_apply", "JLT", "float32",
                                (512, 1024), 64, 8, rowwise=True)
        plans = tune.enumerate_candidates(w)
        mts = {p.m_tile for p in plans if p.backend == "pallas"}
        assert mts == {128, 256, 512}
        assert any(p.backend == "xla" for p in plans)


class TestServeKernelSelection:
    def _cwt_reqs(self, k=8, seed=21):
        rng = np.random.default_rng(seed)
        T = sk.CWT(40, 16, Context(seed=seed))
        ops = [rng.standard_normal((40, 3)).astype(np.float32)
               for _ in range(k)]
        return T, ops

    def test_forced_pallas_flush_bit_equal_to_capacity1_xla(
            self, fresh_engine, mem_plan_cache):
        """The CI gate's bit-equality leg: a coalesced kernel-path
        flush equals the capacity-1 forced-XLA dispatch bitwise (exact
        accumulation under the interpreter)."""
        T, ops = self._cwt_reqs()
        with engine.MicrobatchExecutor(max_batch=8, linger_us=1000,
                                       kernel="pallas") as exp:
            futs = [exp.submit_sketch(T, A, dimension=sk.COLUMNWISE)
                    for A in ops]
            pall = [np.asarray(f.result(timeout=60)) for f in futs]
            st = exp.stats()
        assert st["kernel"]["by_backend"]["pallas"]["flushes"] >= 1
        with engine.MicrobatchExecutor(max_batch=1, linger_us=100,
                                       kernel="xla") as ex1:
            for A, p in zip(ops, pall):
                s = np.asarray(ex1.submit_sketch(
                    T, A, dimension=sk.COLUMNWISE).result(timeout=60))
                assert np.array_equal(p, s)

    def test_kernel_choice_is_executable_key_static(self, fresh_engine,
                                                    mem_plan_cache):
        """Forcing the other backend on an identical bucket compiles a
        DIFFERENT executable — the choice token is in the key, so a
        selection flip can never silently reuse the wrong program."""
        T, ops = self._cwt_reqs()
        with engine.MicrobatchExecutor(max_batch=8, linger_us=1000,
                                       kernel="xla") as ex:
            futs = [ex.submit_sketch(T, A, dimension=sk.COLUMNWISE)
                    for A in ops]
            [f.result(timeout=60) for f in futs]
        m0 = engine.stats().misses
        with engine.MicrobatchExecutor(max_batch=8, linger_us=1000,
                                       kernel="pallas") as ex:
            futs = [ex.submit_sketch(T, A, dimension=sk.COLUMNWISE)
                    for A in ops]
            [f.result(timeout=60) for f in futs]
        assert engine.stats().misses > m0
        assert engine.stats().recompiles == 0

    def test_env_override_beats_plan_cache(self, fresh_engine,
                                           mem_plan_cache, monkeypatch):
        T, ops = self._cwt_reqs(k=4)
        w = tune.serve_workload("sketch_apply", "CWT", "float32",
                                (64, 8), 16, 4, rowwise=False)
        mem_plan_cache.put(w, tune.Plan("pallas"), source="measured",
                           value=1.0)
        monkeypatch.setenv("SKYLARK_SERVE_KERNEL", "xla")
        with engine.MicrobatchExecutor(max_batch=4,
                                       linger_us=1000) as ex:
            futs = [ex.submit_sketch(T, A, dimension=sk.COLUMNWISE)
                    for A in ops]
            [f.result(timeout=60) for f in futs]
            st = ex.stats()
        assert st["kernel"]["by_backend"] == {"xla": {"flushes": 1}}

    def test_plan_cache_routes_flush_and_default_is_xla(
            self, fresh_engine, mem_plan_cache):
        """arg > override > cache > default precedence, cache leg: a
        certified pallas entry for EXACTLY this (bucket, capacity)
        routes the flush through the kernel; without one the default
        stays the vmapped XLA path."""
        T, ops = self._cwt_reqs(k=4)
        with engine.MicrobatchExecutor(max_batch=4,
                                       linger_us=1000) as ex:
            futs = [ex.submit_sketch(T, A, dimension=sk.COLUMNWISE)
                    for A in ops]
            xla_out = [np.asarray(f.result(timeout=60)) for f in futs]
            assert (ex.stats()["kernel"]["by_backend"]
                    == {"xla": {"flushes": 1}})
        w = tune.serve_workload("sketch_apply", "CWT", "float32",
                                (64, 8), 16, 4, rowwise=False)
        mem_plan_cache.put(w, tune.Plan("pallas"), source="measured",
                           value=1.0)
        with engine.MicrobatchExecutor(max_batch=4,
                                       linger_us=1000) as ex:
            futs = [ex.submit_sketch(T, A, dimension=sk.COLUMNWISE)
                    for A in ops]
            pal_out = [np.asarray(f.result(timeout=60)) for f in futs]
            assert (ex.stats()["kernel"]["by_backend"]
                    == {"pallas": {"flushes": 1}})
        for a, b in zip(xla_out, pal_out):
            assert np.array_equal(a, b)   # exact accum: bit-equal

    def test_decline_reason_counted(self, fresh_engine, mem_plan_cache):
        """A pallas intent the kernel can't serve (f64) falls back to
        XLA and the reason lands in the by_reason label set."""
        rng = np.random.default_rng(5)
        T = sk.CWT(40, 16, Context(seed=5))
        ops = [rng.standard_normal((40, 3)) for _ in range(2)]  # f64
        with engine.MicrobatchExecutor(max_batch=2, linger_us=500,
                                       kernel="pallas") as ex:
            futs = [ex.submit_sketch(T, A, dimension=sk.COLUMNWISE)
                    for A in ops]
            [f.result(timeout=60) for f in futs]
            st = ex.stats()
        assert st["kernel"]["by_backend"]["xla"]["flushes"] >= 1
        assert any("float64" in r for r in st["kernel"]["by_reason"])
        agg = engine.serve_stats()
        assert agg["kernel"]["by_reason"]

    def test_prometheus_rendering_of_kernel_counters(
            self, fresh_engine, mem_plan_cache):
        """The fleet-operator surface: kernel selection and decline
        reasons render through the by_<label> convention as Prometheus
        label sets — skylark_serve_kernel_flushes{backend="..."} and
        ..._declined_flushes{reason="..."} — so which replicas are on
        the fast path (and why the others are not) is one scrape
        away."""
        from libskylark_tpu.telemetry import export as texp

        rng = np.random.default_rng(29)
        T = sk.CWT(40, 16, Context(seed=29))
        good = [rng.standard_normal((40, 3)).astype(np.float32)
                for _ in range(2)]
        bad = [rng.standard_normal((40, 3)) for _ in range(2)]  # f64
        with engine.MicrobatchExecutor(max_batch=2, linger_us=500,
                                       kernel="pallas") as ex:
            for ops in (good, bad):
                futs = [ex.submit_sketch(T, A, dimension=sk.COLUMNWISE)
                        for A in ops]
                [f.result(timeout=60) for f in futs]
        txt = texp.prometheus_text()
        assert 'skylark_serve_kernel_flushes{backend="pallas"}' in txt
        assert 'skylark_serve_kernel_flushes{backend="xla"}' in txt
        declined = [ln for ln in txt.splitlines()
                    if ln.startswith(
                        "skylark_serve_kernel_declined_flushes{reason=")]
        assert declined and any("float64" in ln for ln in declined)

    def test_zero_recompiles_after_warmup_with_selection(
            self, fresh_engine, mem_plan_cache):
        """The acceptance criterion: selection enabled, every capacity
        class warmed once, then a storm — zero misses, zero
        recompiles."""
        T, ops = self._cwt_reqs(k=16)
        with engine.MicrobatchExecutor(max_batch=8, linger_us=5000,
                                       kernel="pallas") as ex:
            for cap in (1, 2, 4, 8):
                futs = [ex.submit_sketch(T, ops[i],
                                         dimension=sk.COLUMNWISE)
                        for i in range(cap)]
                ex.flush()
                [f.result(timeout=60) for f in futs]
            m0, r0 = engine.stats().misses, engine.stats().recompiles
            for _ in range(3):
                futs = [ex.submit_sketch(T, A, dimension=sk.COLUMNWISE)
                        for A in ops]
                [f.result(timeout=60) for f in futs]
            assert engine.stats().misses == m0
            assert engine.stats().recompiles == r0


class TestPlanEditInvalidation:
    def test_plan_edit_recompiles_measurement_rerecord_does_not(
            self, fresh_engine, mem_plan_cache):
        """The r7 fingerprint contract extended to serve buckets:
        editing a bucket's PLAN re-keys (and recompiles) its flush
        executable exactly once; re-recording a better measurement of
        the SAME plan recompiles nothing."""
        rng = np.random.default_rng(31)
        T = sk.CWT(40, 16, Context(seed=31))
        ops = [rng.standard_normal((40, 3)).astype(np.float32)
               for _ in range(4)]
        w = tune.serve_workload("sketch_apply", "CWT", "float32",
                                (64, 8), 16, 4, rowwise=False)
        mem_plan_cache.put(w, tune.Plan("xla"), source="ranked")
        with engine.MicrobatchExecutor(max_batch=4,
                                       linger_us=1000) as ex:
            def storm():
                futs = [ex.submit_sketch(T, A, dimension=sk.COLUMNWISE)
                        for A in ops]
                return [np.asarray(f.result(timeout=60)) for f in futs]

            first = storm()
            m0 = engine.stats().misses
            # measurement re-record, same plan: fingerprint unchanged
            mem_plan_cache.record_measurement(w, tune.Plan("xla"), 5.0)
            storm()
            assert engine.stats().misses == m0
            # plan EDIT: xla -> pallas — exactly one fresh compile for
            # this bucket's capacity class, results still bit-equal
            mem_plan_cache.put(w, tune.Plan("pallas"),
                               source="measured", value=9.0)
            edited = storm()
            assert engine.stats().misses == m0 + 1
            assert ex.stats()["kernel"]["by_backend"]["pallas"][
                "flushes"] >= 1
            for a, b in zip(first, edited):
                assert np.array_equal(a, b)
            assert engine.stats().recompiles == 0
