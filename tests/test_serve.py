"""Microbatch serving layer (libskylark_tpu/engine/serve.py).

Oracles, per endpoint:

- *lane invariance* (bitwise): a request's result out of a coalesced
  padded flush equals the SAME request dispatched sequentially through
  the serve layer at capacity 1 — the batched program's lanes are
  independent, so cohort composition and capacity class can never
  change a request's bits.
- *stream exactness* (bitwise, CWT): zero-padded coordinates scatter
  exact zeros, so the batched CWT result is bit-equal to the plain
  ``transform.apply`` — the strongest form of the pad-and-mask claim.
- *numerical agreement*: against the sequential public APIs
  (``transform.apply``, ``solve_l2_sketched``, ``krr_predict``) at
  tight tolerance — XLA's batched contraction may legitimately reorder
  f32 accumulation, so dense matmuls are allclose, not bitwise.

Plus the runtime properties: one executable per (bucket, capacity)
reused across cohorts, donation of the executor-owned stacked buffers,
backpressure, thread-safety of concurrent submission, counters, and a
sharded (8-virtual-device mesh) run.
"""

from __future__ import annotations

import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import libskylark_tpu.parallel as par
from libskylark_tpu import Context, engine, ml
from libskylark_tpu import sketch as sk
from libskylark_tpu.algorithms import regression as reg
from libskylark_tpu.base import errors as sk_errors
from libskylark_tpu.engine import bucket as bucketing
from libskylark_tpu.engine import serve as serve_mod
from libskylark_tpu.resilience import faults


@pytest.fixture()
def fresh_engine():
    engine.reset()
    yield
    engine.reset()


def _executor(**kw):
    kw.setdefault("max_batch", 8)
    kw.setdefault("linger_us", 1000)
    return engine.MicrobatchExecutor(**kw)


def _ragged_sketch_reqs(n_reqs=12, cls=sk.JLT, seed=0, s_dim=16):
    rng = np.random.default_rng(seed)
    ctx = Context(seed=seed)
    reqs = []
    for i in range(n_reqs):
        n = 40 + (i % 3) * 9          # ragged stream dim, one pow2 class
        m = 3 + (i % 4)               # ragged free dim
        T = cls(n, s_dim, ctx)
        A = rng.standard_normal((n, m)).astype(np.float32)
        reqs.append((T, A))
    return reqs


def _capacity1_results(reqs, submit):
    """Sequential dispatch through the serve layer itself: a fresh
    capacity-1 executor, one request per flush."""
    ex1 = _executor(max_batch=1, linger_us=100)
    outs = [np.asarray(submit(ex1, T, A).result(timeout=60))
            for (T, A) in reqs]
    ex1.shutdown()
    return outs


class TestBitEquality:
    def test_cwt_batched_bit_equal_to_transform_apply(self, fresh_engine):
        """Scatter-add padding is exact: coalesced CWT == apply, bitwise,
        across a ragged cohort sharing one bucket."""
        reqs = _ragged_sketch_reqs(12, cls=sk.CWT)
        with _executor() as ex:
            futs = [ex.submit_sketch(T, A, dimension=sk.COLUMNWISE)
                    for (T, A) in reqs]
            for (T, A), f in zip(reqs, futs):
                ref = np.asarray(T.apply(jnp.asarray(A), sk.COLUMNWISE))
                assert np.array_equal(np.asarray(f.result(timeout=60)),
                                      ref)

    def test_dense_batched_lane_invariant_and_close(self, fresh_engine):
        """Dense (JLT) batched results: bit-equal to the capacity-1
        sequential dispatch, allclose to transform.apply."""
        reqs = _ragged_sketch_reqs(12, cls=sk.JLT)
        with _executor() as ex:
            futs = [ex.submit_sketch(T, A, dimension=sk.COLUMNWISE)
                    for (T, A) in reqs]
            batched = [np.asarray(f.result(timeout=60)) for f in futs]
        seq = _capacity1_results(
            reqs, lambda e, T, A: e.submit_sketch(T, A,
                                                  dimension=sk.COLUMNWISE))
        for b, s in zip(batched, seq):
            assert np.array_equal(b, s)
        for (T, A), b in zip(reqs, batched):
            ref = np.asarray(T.apply(jnp.asarray(A), sk.COLUMNWISE))
            np.testing.assert_allclose(b, ref, rtol=1e-5, atol=1e-6)

    def test_rowwise_dense(self, fresh_engine):
        rng = np.random.default_rng(3)
        ctx = Context(seed=3)
        reqs = [(sk.JLT(48, 16, ctx),
                 rng.standard_normal((5 + i % 3, 48)).astype(np.float32))
                for i in range(6)]
        with _executor() as ex:
            futs = [ex.submit_sketch(T, A, dimension=sk.ROWWISE)
                    for (T, A) in reqs]
            batched = [np.asarray(f.result(timeout=60)) for f in futs]
        for (T, A), b in zip(reqs, batched):
            assert b.shape == (A.shape[0], 16)
            ref = np.asarray(T.apply(jnp.asarray(A), sk.ROWWISE))
            np.testing.assert_allclose(b, ref, rtol=1e-5, atol=1e-6)

    def test_solve_batched_vs_sequential(self, fresh_engine):
        rng = np.random.default_rng(1)
        ctx = Context(seed=1)
        reqs = []
        for i in range(9):
            n = 30 + (i % 3) * 2
            T = sk.JLT(n, 12, ctx)
            A = rng.standard_normal((n, 4)).astype(np.float32)
            B = rng.standard_normal((n, 2)).astype(np.float32)
            reqs.append((T, A, B))
        with _executor() as ex:
            futs = [ex.submit_solve(A, B, transform=T)
                    for (T, A, B) in reqs]
            batched = [np.asarray(f.result(timeout=60)) for f in futs]
        # lane invariance: capacity-1 dispatch is bit-equal
        ex1 = _executor(max_batch=1, linger_us=100)
        for (T, A, B), b in zip(reqs, batched):
            s = np.asarray(ex1.submit_solve(A, B, transform=T)
                           .result(timeout=60))
            assert np.array_equal(b, s)
        ex1.shutdown()
        # and the public sequential API agrees numerically
        for (T, A, B), b in zip(reqs, batched):
            ref = np.asarray(reg.solve_l2_sketched(
                jnp.asarray(A), jnp.asarray(B), T))
            np.testing.assert_allclose(b, ref, rtol=1e-4, atol=1e-5)

    def test_solve_cwt_and_1d_rhs(self, fresh_engine):
        rng = np.random.default_rng(2)
        ctx = Context(seed=2)
        reqs = []
        for i in range(5):
            n = 40 + i
            T = sk.CWT(n, 16, ctx)
            A = rng.standard_normal((n, 3)).astype(np.float32)
            b = rng.standard_normal((n,)).astype(np.float32)
            reqs.append((T, A, b))
        with _executor() as ex:
            futs = [ex.submit_solve(A, b, transform=T)
                    for (T, A, b) in reqs]
            outs = [np.asarray(f.result(timeout=60)) for f in futs]
        for (T, A, b), x in zip(reqs, outs):
            assert x.shape == (3,)        # 1-D rhs squeezes, like the API
            ref = np.asarray(reg.solve_l2_sketched(
                jnp.asarray(A), jnp.asarray(b), T))
            np.testing.assert_allclose(x, ref, rtol=1e-4, atol=1e-5)

    def test_krr_predict_batched(self, fresh_engine):
        rng = np.random.default_rng(4)
        X = jnp.asarray(rng.standard_normal((40, 5)).astype(np.float32))
        Y = jnp.asarray(rng.standard_normal((40, 1)).astype(np.float32))
        k = ml.Gaussian(5, sigma=2.0)
        coef = ml.kernel_ridge(k, X, Y, 0.1)
        queries = [rng.standard_normal((2 + i % 5, 5)).astype(np.float32)
                   for i in range(10)]
        with _executor() as ex:
            futs = [ex.submit_krr_predict(k, q, X, coef)
                    for q in queries]
            batched = [np.asarray(f.result(timeout=60)) for f in futs]
        ex1 = _executor(max_batch=1, linger_us=100)
        for q, b in zip(queries, batched):
            s = np.asarray(ex1.submit_krr_predict(k, q, X, coef)
                           .result(timeout=60))
            assert np.array_equal(b, s)
        ex1.shutdown()
        for q, b in zip(queries, batched):
            ref = np.asarray(ml.krr_predict(k, jnp.asarray(q), X, coef))
            np.testing.assert_allclose(b, ref, rtol=1e-4, atol=1e-5)


class TestFastfoodEndpoint:
    """The Fastfood/RFT feature-map serve endpoint (r12): vmap-safe
    pure apply + bucket statics, so the fused-chain kernel has real
    serve traffic. Oracles mirror the sketch_apply ones: lane
    invariance bitwise, numerical agreement with ``transform.apply``
    (the vmapped chain may reorder f32 contractions)."""

    def _reqs(self, n_reqs=8, seed=13, n=100, s=64):
        rng = np.random.default_rng(seed)
        ctx = Context(seed=seed)
        T = sk.FastGaussianRFT(n, s, ctx, sigma=2.0)
        return [(T, rng.standard_normal((2 + i % 4, n))
                 .astype(np.float32)) for i in range(n_reqs)]

    def test_batched_matches_apply_and_capacity1(self, fresh_engine):
        reqs = self._reqs()
        with _executor() as ex:
            futs = [ex.submit_fastfood(T, A) for (T, A) in reqs]
            batched = [np.asarray(f.result(timeout=60)) for f in futs]
        seq = _capacity1_results(
            reqs, lambda e, T, A: e.submit_fastfood(T, A))
        for b, s in zip(batched, seq):
            assert np.array_equal(b, s)       # lane invariance
        for (T, A), b in zip(reqs, batched):
            # the lane keeps the eager chain, apply() is the one compiled
            # program (PR 48): equal to float32 tolerance of the out-scale
            ref = np.asarray(T.apply(jnp.asarray(A), sk.ROWWISE))
            assert b.shape == ref.shape
            np.testing.assert_allclose(b, ref, rtol=1e-5,
                                       atol=2e-5 * T.scale)

    def test_matern_and_1d_input(self, fresh_engine):
        rng = np.random.default_rng(17)
        ctx = Context(seed=17)
        T = sk.FastMaternRFT(60, 32, ctx, nu=1.5, l=0.8)
        x = rng.standard_normal((60,)).astype(np.float32)
        with _executor(linger_us=500) as ex:
            out = np.asarray(ex.submit_fastfood(T, x).result(timeout=60))
        X = jnp.asarray(x)[None, :]
        assert out.shape == (32,)
        # against the apply as one compiled program (what the serve
        # executable is): the original band
        ref = np.asarray(jax.jit(lambda X: T.apply(X, sk.ROWWISE))(X))
        np.testing.assert_allclose(out, ref[0], rtol=1e-5, atol=1e-6)
        # against the op-by-op apply, which sums the two WHT
        # contractions in another order: features are scale*cos(arg), so
        # the two may differ by the rounding of arg — held to ONE f32
        # ulp of the largest argument (Matern's heavy-tailed Sm makes
        # it ~178 here; arg from the explicit host chain)
        import scipy.linalg

        H = scipy.linalg.hadamard(T._NB).astype(np.float64)
        xp = np.zeros(T._NB)
        xp[:60] = x
        f32 = jnp.float32
        arg = (((xp * np.asarray(T._B(f32), np.float64)[0]) @ H)[
            np.asarray(T._perms())[0]]
            * np.asarray(T._G(f32), np.float64)[0]) @ H \
            * np.asarray(T._Sm(f32), np.float64)
        ulp = float(np.spacing(np.float32(np.abs(arg[:32]).max())))
        eager = np.asarray(T.apply(X, sk.ROWWISE))
        assert np.abs(out - eager[0]).max() <= T.scale * ulp

    def test_seed_sharing_one_bucket(self, fresh_engine):
        """Transforms differing only by seed coalesce into ONE bucket
        (streams rebuild from the stacked raw keys): the second cohort
        is pure cache hits."""
        rng = np.random.default_rng(19)
        ctx = Context(seed=19)
        Ts = [sk.FastGaussianRFT(80, 32, ctx, sigma=1.5)
              for _ in range(8)]
        ops = [rng.standard_normal((3, 80)).astype(np.float32)
               for _ in range(8)]
        with _executor(max_batch=4, linger_us=10_000_000) as ex:
            futs = [ex.submit_fastfood(T, A)
                    for T, A in zip(Ts[:4], ops[:4])]
            [f.result(timeout=60) for f in futs]
            m0 = engine.stats().misses
            futs = [ex.submit_fastfood(T, A)
                    for T, A in zip(Ts[4:], ops[4:])]
            outs = [np.asarray(f.result(timeout=60)) for f in futs]
        assert engine.stats().misses == m0
        assert engine.stats().recompiles == 0
        for T, A, o in zip(Ts[4:], ops[4:], outs):
            ref = np.asarray(T.apply(jnp.asarray(A), sk.ROWWISE))
            np.testing.assert_allclose(o, ref, rtol=1e-5, atol=1e-6)

    def test_sigma_separates_buckets(self, fresh_engine):
        """The Sm spec is a bucket static: transforms with different
        sigma must not share a cohort (their streams differ by more
        than the key)."""
        rng = np.random.default_rng(23)
        ctx = Context(seed=23)
        Ta = sk.FastGaussianRFT(40, 16, ctx, sigma=1.0)
        Tb = sk.FastGaussianRFT(40, 16, ctx, sigma=3.0)
        A = rng.standard_normal((3, 40)).astype(np.float32)
        with _executor(linger_us=500) as ex:
            oa = np.asarray(ex.submit_fastfood(Ta, A).result(timeout=60))
            ob = np.asarray(ex.submit_fastfood(Tb, A).result(timeout=60))
        np.testing.assert_allclose(
            oa, np.asarray(Ta.apply(jnp.asarray(A), sk.ROWWISE)),
            rtol=1e-5, atol=2e-5 * Ta.scale)
        np.testing.assert_allclose(
            ob, np.asarray(Tb.apply(jnp.asarray(A), sk.ROWWISE)),
            rtol=1e-5, atol=2e-5 * Tb.scale)
        assert not np.allclose(oa, ob)

    def test_rejects_non_fastfood_and_bad_dim(self, fresh_engine):
        with _executor() as ex:
            with pytest.raises(TypeError, match="FastRFT"):
                ex.submit_fastfood(sk.JLT(32, 8, Context(seed=0)),
                                   np.zeros((2, 32), np.float32))
            T = sk.FastGaussianRFT(40, 16, Context(seed=1))
            with pytest.raises(ValueError, match="input dim"):
                ex.submit_fastfood(T, np.zeros((2, 39), np.float32))


class TestBucketingAndCache:
    def test_one_bucket_for_ragged_class_zero_recompiles(self,
                                                         fresh_engine):
        """Two cohorts sharing a bucket reuse ONE executable: the second
        flush is all cache hits, and the recompile counter never
        moves."""
        reqs = _ragged_sketch_reqs(16, cls=sk.JLT)
        # max_batch == cohort size + huge linger: each group of 8
        # flushes as one deterministic capacity-8 cohort
        with _executor(max_batch=8, linger_us=10_000_000) as ex:
            futs = [ex.submit_sketch(T, A) for (T, A) in reqs[:8]]
            [f.result(timeout=60) for f in futs]
            m0 = engine.stats().misses
            futs = [ex.submit_sketch(T, A) for (T, A) in reqs[8:]]
            [f.result(timeout=60) for f in futs]
            st = engine.stats()
            assert st.misses == m0       # second cohort: pure hits
            assert st.recompiles == 0
            assert ex.stats()["flushes"] >= 2

    def test_capacity_classes_are_pow2(self, fresh_engine):
        reqs = _ragged_sketch_reqs(5, cls=sk.JLT)
        with _executor(linger_us=500) as ex:
            futs = [ex.submit_sketch(T, A) for (T, A) in reqs]
            [f.result(timeout=60) for f in futs]
            hist = ex.stats()["batch_capacity_hist"]
        for cap in hist:
            assert cap & (cap - 1) == 0 and cap <= 8

    def test_pow2_pad_policy(self):
        assert bucketing.pow2_pad(3) == 8      # floor
        assert bucketing.pow2_pad(48) == 64
        assert bucketing.pow2_pad(64) == 64
        assert bucketing.pow2_pad(65) == 128
        assert bucketing.capacity_class(3, 8) == 4
        assert bucketing.capacity_class(9, 8) == 8     # clamped
        assert bucketing.capacity_class(3, 8, multiple=8) == 8

    def test_stats_counters(self, fresh_engine):
        reqs = _ragged_sketch_reqs(10, cls=sk.CWT)
        with _executor() as ex:
            futs = [ex.submit_sketch(T, A) for (T, A) in reqs]
            [f.result(timeout=60) for f in futs]
            st = ex.stats()
        assert st["submitted"] == 10 and st["completed"] == 10
        assert st["failed"] == 0 and st["flushes"] >= 1
        assert 0.0 <= st["padding_waste_ratio"] < 1.0
        assert st["latency_s"]["p50"] is not None
        assert st["latency_s"]["p99"] >= st["latency_s"]["p50"]
        agg = engine.serve_stats()
        assert agg["completed"] >= 10 and agg["executors"] >= 1

    def test_dump_stats_includes_serve(self, fresh_engine, tmp_path):
        reqs = _ragged_sketch_reqs(3, cls=sk.CWT)
        with _executor() as ex:
            [f.result(timeout=60)
             for f in [ex.submit_sketch(T, A) for (T, A) in reqs]]
            path = tmp_path / "stats.json"
            engine.dump_stats(str(path))
        import json

        doc = json.loads(path.read_text())
        assert doc["serve"]["completed"] >= 3

    def test_unknown_endpoint_and_bad_shapes(self, fresh_engine):
        with _executor() as ex:
            with pytest.raises(ValueError, match="unknown serve"):
                ex.submit("nope")
            T = sk.JLT(32, 8, Context(seed=0))
            with pytest.raises(ValueError, match="input dim"):
                ex.submit_sketch(T, np.zeros((31, 2), np.float32))
            # FJLT serves panel-free since the SRHT tier, but only the
            # Sylvester-Hadamard mixer has the closed form
            with pytest.raises(sk_errors.UnsupportedError, match="wht"):
                ex.submit_sketch(
                    sk.FJLT(32, 8, Context(seed=1), fut="dct"),
                    np.zeros((32, 2), np.float32))
            with pytest.raises(TypeError, match="dense"):
                ex.submit_sketch(sk.UST(32, 8, Context(seed=2)),
                                 np.zeros((32, 2), np.float32))


class TestDonationUnderBucketReuse:
    def test_flush_buffers_consumed_and_executable_reused(
            self, fresh_engine, monkeypatch):
        """The donated padded batch buffer is DEAD after its flush (a
        re-read would raise jax's deleted-buffer error), and donation
        does not fragment the cache: the next cohort in the bucket
        reuses the same executable."""
        recorded = []
        real_stack = bucketing.stack_pad

        def tracking_stack(arrays, padded_shape, capacity, dtype):
            out = jnp.asarray(real_stack(arrays, padded_shape, capacity,
                                         dtype))
            recorded.append(out)
            return out

        monkeypatch.setattr(serve_mod.bucketing, "stack_pad",
                            tracking_stack)
        # n = s_dim = 64 makes the batched input and output lanes the
        # same shape, so XLA can ALIAS the donated batch buffer (jax
        # deletes a donated buffer only when the aliasing was usable)
        ctx = Context(seed=5)
        rng = np.random.default_rng(5)
        reqs = [(sk.JLT(64, 64, ctx),
                 rng.standard_normal((64, 8)).astype(np.float32))
                for _ in range(8)]
        # max_batch == cohort size + an effectively-infinite linger:
        # each group of 4 flushes as exactly one capacity-4 cohort, so
        # the second cohort deterministically re-uses the first's
        # executable
        with _executor(max_batch=4, linger_us=10_000_000) as ex:
            futs = [ex.submit_sketch(T, A) for (T, A) in reqs[:4]]
            r1 = [np.asarray(f.result(timeout=60)) for f in futs]
            m0 = engine.stats().misses
            futs = [ex.submit_sketch(T, A) for (T, A) in reqs[4:]]
            r2 = [np.asarray(f.result(timeout=60)) for f in futs]
        stacked = [b for b in recorded if b.ndim == 3]
        assert stacked, "tracking stack_pad never saw a batch buffer"
        # every aliasable stacked batch buffer was consumed by its
        # flush — the executor must never re-read one
        consumed = [b for b in stacked if b.shape[1:] == (64, 8)
                    and b.dtype == jnp.float32]
        assert consumed and all(b.is_deleted() for b in consumed)
        # donation did not fragment the cache: cohorts at an already-
        # warmed capacity reuse the first flush's executable
        assert engine.stats().misses == m0
        assert engine.stats().recompiles == 0
        # results were sliced to host BEFORE the donation killed the
        # device buffers, and both cohorts produced valid output
        assert all(np.isfinite(x).all() for x in r1 + r2)

    def test_krr_model_operands_not_donated(self, fresh_engine):
        """Bucket-lived model arrays are re-read by every flush — they
        must survive (only the per-flush query batch is donated)."""
        rng = np.random.default_rng(0)
        X = jnp.asarray(rng.standard_normal((20, 3)).astype(np.float32))
        Y = jnp.asarray(rng.standard_normal((20, 1)).astype(np.float32))
        k = ml.Gaussian(3, sigma=1.0)
        coef = ml.kernel_ridge(k, X, Y, 0.1)
        q = rng.standard_normal((4, 3)).astype(np.float32)
        with _executor(linger_us=500) as ex:
            a = np.asarray(ex.submit_krr_predict(k, q, X, coef)
                           .result(timeout=60))
            b = np.asarray(ex.submit_krr_predict(k, q, X, coef)
                           .result(timeout=60))
        assert not coef.is_deleted() and not X.is_deleted()
        assert np.array_equal(a, b)


class TestBackpressureAndLifecycle:
    def test_backpressure_raises_past_bound(self, fresh_engine):
        reqs = _ragged_sketch_reqs(6, cls=sk.CWT)
        ex = _executor(max_batch=8, linger_us=10_000_000, max_queue=4)
        try:
            futs = [ex.submit_sketch(T, A, timeout=10.0)
                    for (T, A) in reqs[:4]]
            with pytest.raises(engine.ServeOverloadedError):
                ex.submit_sketch(*reqs[4], timeout=0.2)
            assert ex.stats()["rejected"] == 1
            ex.flush()
            [f.result(timeout=60) for f in futs]
        finally:
            ex.shutdown()

    def test_shutdown_drains_pending(self, fresh_engine):
        reqs = _ragged_sketch_reqs(5, cls=sk.CWT)
        ex = _executor(max_batch=8, linger_us=10_000_000)
        futs = [ex.submit_sketch(T, A) for (T, A) in reqs]
        ex.shutdown()                      # must flush, not strand
        assert all(np.isfinite(np.asarray(f.result(timeout=5))).all()
                   for f in futs)
        with pytest.raises(RuntimeError, match="shut down"):
            ex.submit_sketch(*reqs[0])

    def test_submit_error_does_not_poison_cohort(self, fresh_engine):
        """A request whose endpoint raises inside the flush fans the
        exception to ITS cohort only; the executor keeps serving."""
        ctx = Context(seed=0)
        T = sk.JLT(32, 8, ctx)
        A = np.full((32, 3), np.nan, np.float32)   # NaN is fine math-wise
        with _executor() as ex:
            out = np.asarray(ex.submit_sketch(T, A).result(timeout=60))
            assert out.shape == (8, 3)
            good = np.zeros((32, 3), np.float32)
            out2 = np.asarray(ex.submit_sketch(T, good).result(timeout=60))
            assert np.isfinite(out2).all()


class TestDeadlineVsFlushFailure:
    """Satellite: submit-timeout vs flush-failure interleavings. A
    request whose deadline expires while queued must resolve to
    ServeOverloadedError — never the flush's injected error, and never
    by riding a poison-isolation retry (the broader chaos battery lives
    in tests/test_resilience.py)."""

    def test_expired_while_queued_gets_overloaded_not_retry(
            self, fresh_engine):
        ctx = Context(seed=21)
        rng = np.random.default_rng(21)
        T = sk.CWT(40, 16, ctx)
        ops = [rng.standard_normal((40, 3)).astype(np.float32)
               for _ in range(8)]
        refs = [np.asarray(T.apply(jnp.asarray(A), sk.COLUMNWISE))
                for A in ops]
        plan = {"seed": 0, "faults": [
            {"site": "serve.flush", "error": "SketchError",
             "tag": "poison"}]}
        ex = _executor(max_batch=8, linger_us=10_000_000)
        try:
            with faults.fault_plan(plan):
                futs = {}
                for i, A in enumerate(ops):
                    if i == 2:
                        # expires in the queue: the flush (poisoned, so
                        # it retries bisection-style) happens after
                        with faults.tag("expired-leg"):
                            futs[i] = ex.submit_sketch(T, A, deadline=0.0)
                    elif i == 5:
                        with faults.tag("poison"):
                            futs[i] = ex.submit_sketch(T, A)
                    else:
                        futs[i] = ex.submit_sketch(T, A)
                ex.flush()
            # the expired request: ServeOverloadedError, NOT the
            # injected SketchError a retry pass would have fanned to it
            exc = futs[2].exception(timeout=60)
            assert isinstance(exc, engine.ServeOverloadedError)
            assert "deadline expired" in str(exc)
            # the poison request alone got the injected class
            assert isinstance(futs[5].exception(timeout=60),
                              sk_errors.SketchError)
            # every other cohort-mate re-coalesced and matches the
            # sequential oracle bitwise
            for i in (0, 1, 3, 4, 6, 7):
                assert np.array_equal(
                    np.asarray(futs[i].result(timeout=60)), refs[i]), i
            st = ex.stats()
            assert st["expired"] == 1
            assert st["poisoned"] == 1
            assert st["completed"] == 6
        finally:
            ex.shutdown()

    def test_deadline_satisfied_in_time_resolves_normally(
            self, fresh_engine):
        ctx = Context(seed=22)
        T = sk.CWT(32, 8, ctx)
        A = np.ones((32, 2), np.float32)
        with _executor(linger_us=500) as ex:
            out = ex.submit_sketch(T, A, deadline=60.0).result(timeout=60)
            ref = np.asarray(T.apply(jnp.asarray(A), sk.COLUMNWISE))
            assert np.array_equal(np.asarray(out), ref)
            assert ex.stats()["expired"] == 0


class TestConcurrentSubmission:
    def test_many_threads_one_bucket(self, fresh_engine):
        """The satellite thread-safety battery at the serve level: many
        submitter threads, multiple worker threads, one bucket — every
        result correct, engine counters consistent, no lost updates."""
        ctx = Context(seed=9)
        rng = np.random.default_rng(9)
        T = sk.CWT(40, 16, ctx)
        ref_in = [rng.standard_normal((40, 4)).astype(np.float32)
                  for _ in range(64)]
        refs = [np.asarray(T.apply(jnp.asarray(A), sk.COLUMNWISE))
                for A in ref_in]
        engine.reset()
        results: dict = {}
        errors: list = []
        with _executor(max_batch=8, workers=4, linger_us=2000) as ex:
            def client(tid):
                try:
                    futs = [(i, ex.submit_sketch(T, ref_in[i]))
                            for i in range(tid, 64, 8)]
                    for i, f in futs:
                        results[i] = np.asarray(f.result(timeout=120))
                except Exception as e:  # noqa: BLE001
                    errors.append(e)

            threads = [threading.Thread(target=client, args=(t,))
                       for t in range(8)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        assert not errors
        assert len(results) == 64
        for i in range(64):
            assert np.array_equal(results[i], refs[i])
        st = engine.stats()
        # counter integrity under concurrency: every executable call is
        # accounted, and single-flight kept compiles at one per
        # (bucket, capacity class)
        assert st.hits + st.misses == st.executions
        assert st.misses <= 4              # pow2 classes ≤ {1,2,4,8}
        assert st.recompiles == 0


class TestShardedServe:
    def test_mesh_sharded_flush_matches_unsharded(self, fresh_engine,
                                                  mesh1d):
        """The forced 8-virtual-device run: the executor shards each
        flush's batch dimension across the mesh; results agree with the
        unsharded sequential API and the engine never thrashes."""
        reqs = _ragged_sketch_reqs(16, cls=sk.JLT, seed=11)
        with _executor(mesh=mesh1d, linger_us=2000) as ex:
            futs = [ex.submit_sketch(T, A) for (T, A) in reqs]
            outs = [np.asarray(f.result(timeout=120)) for f in futs]
            hist = ex.stats()["batch_capacity_hist"]
        for (T, A), b in zip(reqs, outs):
            ref = np.asarray(T.apply(jnp.asarray(A), sk.COLUMNWISE))
            np.testing.assert_allclose(b, ref, rtol=1e-5, atol=1e-6)
        # capacity classes round to the device count: every flush ran
        # with a batch divisible across the 8 devices
        assert all(cap % 8 == 0 for cap in hist)
        assert engine.stats().recompiles == 0

    def test_mesh_sharded_krr(self, fresh_engine, mesh1d):
        rng = np.random.default_rng(12)
        X = jnp.asarray(rng.standard_normal((32, 4)).astype(np.float32))
        Y = jnp.asarray(rng.standard_normal((32, 1)).astype(np.float32))
        k = ml.Gaussian(4, sigma=1.5)
        coef = ml.kernel_ridge(k, X, Y, 0.1)
        queries = [rng.standard_normal((3 + i % 4, 4)).astype(np.float32)
                   for i in range(12)]
        with _executor(mesh=mesh1d, linger_us=2000) as ex:
            futs = [ex.submit_krr_predict(k, q, X, coef)
                    for q in queries]
            outs = [np.asarray(f.result(timeout=120)) for f in futs]
        for q, b in zip(queries, outs):
            ref = np.asarray(ml.krr_predict(k, jnp.asarray(q), X, coef))
            np.testing.assert_allclose(b, ref, rtol=1e-4, atol=1e-5)
