"""FWHT-native tier (docs/performance, "In-kernel FWHT and compressed
matmul"): the panel-free SRHT lowering and the compressed
approximate-matmul endpoint.

Oracles:

- *Sylvester reference*: ``fut.fwht`` equals the dense
  ``_hadamard_np`` matmul bit for bit on integer-valued f32 lattices
  (exact adds both ways), allclose on general floats.
- *dyadic bit-equality*: the fused ``fwht_sketch`` / serve /
  ``fold_rows`` programs are bit-equal to the
  ``operator_panel`` matmul whenever every intermediate is exactly
  representable — integer-valued operands with ``n`` and ``s`` EVEN
  powers of two (``1/sqrt(n)`` dyadic). Odd powers (n = 2^13, ...)
  are allclose only: the scales are irrational and summation orders
  legitimately differ in the last ulp.
- *one flush program* for the SRHT family: the vmapped lane function
  (``fjlt.srht_serve_apply``; tests/test_fjlt_program.py holds it and
  the block kernel of the direct apply). A pallas intent — executor
  ``kernel=`` argument or ``SKYLARK_SERVE_KERNEL`` — declines to it,
  counted; the family pins older trees read are not read.
- *compressed matmul*: ``(A Sᵀ)(S B)`` is within the returned
  ``‖A‖_F·‖B‖_F·√(2/s)`` scale on well-conditioned data; the sparse-A
  CWT lane is bit-equal to its densified twin.
"""

from __future__ import annotations

import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import jax.random as jr
import scipy.sparse as sp

from libskylark_tpu import Context, engine
from libskylark_tpu import sketch as sk
from libskylark_tpu.base.context import Allocation
from libskylark_tpu.base.errors import UnsupportedError
from libskylark_tpu.sketch import fjlt as _fjlt
from libskylark_tpu.sketch import fut as _fut
from libskylark_tpu.sketch.fjlt import FJLT
from libskylark_tpu.sketch.hash import CWT


@pytest.fixture()
def fresh_engine():
    engine.reset()
    yield
    engine.reset()


def _executor(**kw):
    kw.setdefault("max_batch", 8)
    kw.setdefault("linger_us", 1000)
    return engine.MicrobatchExecutor(**kw)


def _kd(transform):
    return engine.serve.MicrobatchExecutor._key_data(transform)


def _lattice(rng, shape):
    """Integer-valued f32: every butterfly intermediate is exact."""
    return rng.integers(-4, 5, size=shape).astype(np.float32)


# ---------------------------------------------------------------------------
# fut.fwht vs the dense Sylvester reference
# ---------------------------------------------------------------------------


class TestFWHT:
    @pytest.mark.parametrize("n", [2, 8, 64, 256, 1024])
    def test_matches_hadamard_matmul(self, n):
        rng = np.random.default_rng(n)
        A = _lattice(rng, (n, 5))
        H = _fut._hadamard_np(n).astype(np.float32)
        out = np.asarray(_fut.fwht(jnp.asarray(A), axis=0))
        assert np.array_equal(out, H @ A)

    def test_general_floats_allclose(self):
        rng = np.random.default_rng(0)
        A = rng.standard_normal((512, 7)).astype(np.float32)
        H = _fut._hadamard_np(512).astype(np.float32)
        out = np.asarray(_fut.fwht(jnp.asarray(A), axis=0))
        np.testing.assert_allclose(out, H @ A, rtol=2e-4, atol=2e-3)

    def test_axis1(self):
        rng = np.random.default_rng(1)
        A = _lattice(rng, (3, 128))
        H = _fut._hadamard_np(128).astype(np.float32)
        out = np.asarray(_fut.fwht(jnp.asarray(A), axis=1))
        assert np.array_equal(out, A @ H)

    def test_nonpow2_rejected(self):
        with pytest.raises(ValueError, match="power-of-2"):
            _fut.fwht(jnp.zeros((12, 3)), axis=0)

    def test_fused_sketch_equals_composed(self):
        """fwht_sketch is the literal diag→FWHT→gather composition."""
        rng = np.random.default_rng(2)
        n, s, m = 1024, 64, 9
        A = rng.standard_normal((n, m)).astype(np.float32)
        D = (1.0 - 2.0 * rng.integers(0, 2, n)).astype(np.float32)
        idx = rng.integers(0, n, s).astype(np.int32)
        fs, ss = 1.0 / math.sqrt(n), math.sqrt(n / s)
        fused = np.asarray(_fut.fwht_sketch(
            jnp.asarray(A), jnp.asarray(D), jnp.asarray(idx), fs, ss,
            axis=0))
        mixed = _fut.fwht(fs * jnp.asarray(D)[:, None] * A, axis=0)
        composed = np.asarray(ss * mixed[jnp.asarray(idx), :])
        assert np.array_equal(fused, composed)


# ---------------------------------------------------------------------------
# the panel-free SRHT programs vs the operator-panel oracle
# ---------------------------------------------------------------------------


class TestPanelFree:
    @pytest.mark.parametrize("n,s", [(256, 16), (4096, 64)])
    def test_serve_apply_bit_equal_dyadic(self, n, s):
        """n, s even powers of two + lattice data: bit-equal to both
        the transform's own apply and the materialized panel."""
        rng = np.random.default_rng(s)
        t = FJLT(n, s, Context(seed=5), fut="wht")
        A = _lattice(rng, (7, n))
        out = np.asarray(_fjlt.srht_serve_apply(
            _kd(t), jnp.asarray(A), s_dim=s, rowwise=True))
        ref = np.asarray(t.apply(A, sk.ROWWISE))
        assert np.array_equal(out, ref)
        panel = t.operator_panel(0, n)
        assert np.array_equal(out, A @ np.asarray(panel).T)

    def test_serve_apply_columnwise(self):
        n, s = 1024, 64
        rng = np.random.default_rng(3)
        t = FJLT(n, s, Context(seed=9), fut="wht")
        A = _lattice(rng, (n, 5))
        out = np.asarray(_fjlt.srht_serve_apply(
            _kd(t), jnp.asarray(A), s_dim=s, rowwise=False))
        assert np.array_equal(out, np.asarray(t.apply(A, sk.COLUMNWISE)))

    def test_serve_apply_floats_allclose(self):
        n, s = 2048, 128
        rng = np.random.default_rng(4)
        t = FJLT(n, s, Context(seed=2), fut="wht")
        A = rng.standard_normal((6, n)).astype(np.float32)
        out = np.asarray(_fjlt.srht_serve_apply(
            _kd(t), jnp.asarray(A), s_dim=s, rowwise=True))
        np.testing.assert_allclose(
            out, np.asarray(t.apply(A, sk.ROWWISE)), rtol=1e-4,
            atol=1e-4)

    @pytest.mark.parametrize("lo,hi", [(0, 256), (0, 1), (17, 18),
                                       (13, 200), (128, 256)])
    def test_fold_rows_vs_panel(self, lo, hi):
        """Partial folds over aligned-block decompositions equal the
        panel contraction (dyadic regime: bitwise)."""
        n, s, m = 256, 16, 6
        rng = np.random.default_rng(hi)
        t = FJLT(n, s, Context(seed=13), fut="wht")
        X = _lattice(rng, (hi - lo, m))
        out = np.asarray(t.fold_rows(X, lo, hi))
        panel = np.asarray(t.operator_panel(lo, hi))
        assert np.array_equal(out, panel @ X)

    def test_fold_rows_split_sums_to_full(self):
        n, s, m = 1024, 64, 4
        rng = np.random.default_rng(8)
        t = FJLT(n, s, Context(seed=21), fut="wht")
        X = _lattice(rng, (n, m))
        full = np.asarray(t.fold_rows(X, 0, n))
        split = (np.asarray(t.fold_rows(X[:300], 0, 300))
                 + np.asarray(t.fold_rows(X[300:], 300, n)))
        np.testing.assert_allclose(full, split, rtol=1e-5, atol=1e-5)
        assert np.array_equal(
            full, np.asarray(t.apply(X, sk.COLUMNWISE)))

    def test_fold_rows_non_wht_rejected(self):
        t = FJLT(256, 16, Context(seed=1), fut="dct")
        with pytest.raises(UnsupportedError):
            t.fold_rows(np.zeros((4, 2), np.float32), 0, 4)


# ---------------------------------------------------------------------------
# serve integration: the SRHT sketch_apply family
# ---------------------------------------------------------------------------


class TestServeSRHT:
    def test_capacity1_bit_equality_both_orientations(
            self, fresh_engine):
        rng = np.random.default_rng(11)
        n, s = 1024, 256
        t = FJLT(n, s, Context(seed=7), fut="wht")
        with _executor() as ex:
            A = _lattice(rng, (37, n))
            out = np.asarray(ex.submit_sketch(
                t, A, dimension=sk.ROWWISE).result(timeout=60))
            assert np.array_equal(
                out, np.asarray(t.apply(A, sk.ROWWISE)))
            Ac = _lattice(rng, (n, 9))
            outc = np.asarray(ex.submit_sketch(
                t, Ac, dimension=sk.COLUMNWISE).result(timeout=60))
            assert np.array_equal(
                outc, np.asarray(t.apply(Ac, sk.COLUMNWISE)))
            st = ex.stats()["fwht"]
            assert st["by_backend"]["xla"]["flushes"] == 2

    def test_cohort_lane_matches_capacity1(self, fresh_engine):
        rng = np.random.default_rng(12)
        n, s = 512, 64
        ts = [FJLT(n, s, Context(seed=50 + i), fut="wht")
              for i in range(4)]
        ops = [_lattice(rng, (6, n)) for _ in range(4)]
        with _executor(max_batch=4, linger_us=50000) as ex:
            futs = [ex.submit_sketch(t, A, dimension=sk.ROWWISE)
                    for t, A in zip(ts, ops)]
            ex.flush()
            batched = [np.asarray(f.result(timeout=60)) for f in futs]
        with _executor(max_batch=1, linger_us=100) as ex1:
            for t, A, got in zip(ts, ops, batched):
                solo = np.asarray(ex1.submit_sketch(
                    t, A, dimension=sk.ROWWISE).result(timeout=60))
                assert np.array_equal(got, solo)

    def test_nonpow2_rejected(self, fresh_engine):
        t = FJLT(1000, 64, Context(seed=3), fut="wht")
        with _executor() as ex:
            with pytest.raises(ValueError, match="power-of-2"):
                ex.submit_sketch(
                    t, np.zeros((4, 1000), np.float32),
                    dimension=sk.ROWWISE)

    def test_non_wht_mixer_rejected(self, fresh_engine):
        t = FJLT(1024, 64, Context(seed=3), fut="dct")
        with _executor() as ex:
            with pytest.raises(UnsupportedError):
                ex.submit_sketch(t, np.zeros((4, 1024), np.float32),
                                 dimension=sk.ROWWISE)

    def test_zero_recompiles_after_warmup(self, fresh_engine):
        rng = np.random.default_rng(13)
        n, s = 512, 64
        t = FJLT(n, s, Context(seed=19), fut="wht")
        reqs = [_lattice(rng, (5, n)) for _ in range(8)]
        with _executor(max_batch=8, linger_us=4000) as ex:
            for cap in (1, 2, 4, 8):
                futs = [ex.submit_sketch(t, A, dimension=sk.ROWWISE)
                        for A in reqs[:cap]]
                ex.flush()
                [f.result(timeout=60) for f in futs]
            m0, r0 = engine.stats().misses, engine.stats().recompiles
            for _ in range(2):
                futs = [ex.submit_sketch(t, A, dimension=sk.ROWWISE)
                        for A in reqs]
                ex.flush()
                [f.result(timeout=60) for f in futs]
            assert engine.stats().misses - m0 == 0
            assert engine.stats().recompiles - r0 == 0

    @pytest.mark.parametrize("dimension,intent", [
        (sk.ROWWISE, "arg"), (sk.COLUMNWISE, "env")],
        ids=["rowwise", "columnwise"])
    def test_pallas_intent_declines_to_the_lane_program(
            self, fresh_engine, monkeypatch, dimension, intent):
        """The SRHT flush has one program, the vmapped lane function: a
        pallas intent (executor argument or SKYLARK_SERVE_KERNEL) declines
        to it with one counted reason, and a warm-up pack cannot seed one."""
        rng = np.random.default_rng(14)
        n, s = 4096, 256
        t = FJLT(n, s, Context(seed=23), fut="wht")
        A = _lattice(rng, (16, n) if dimension == sk.ROWWISE else (n, 16))
        with _executor() as ex:
            want = np.asarray(ex.submit_sketch(
                t, A, dimension=dimension).result(timeout=120))
            (memo_key,) = ex._kernel_memo
            assert not ex.restore_kernel_choice(memo_key[0], 4, "pallas")
            assert ex.restore_kernel_choice(memo_key[0], 4, "xla")
        if intent == "env":
            monkeypatch.setenv("SKYLARK_SERVE_KERNEL", "pallas")
        with _executor(**({"kernel": "pallas"} if intent == "arg"
                          else {})) as ex:
            got = np.asarray(ex.submit_sketch(
                t, A, dimension=dimension).result(timeout=120))
            st = ex.stats()
            (choice,) = ex._kernel_memo.values()
        assert np.array_equal(got, want)
        assert np.array_equal(got, np.asarray(t.apply(A, dimension)))
        slug = "no-batched-kernel-the-lane-program-serves"
        assert choice == ("xla", intent, slug)
        assert st["kernel"]["by_reason"] == {slug: {"declined_flushes": 1}}
        assert st["fwht"]["by_backend"] == {"xla": {"flushes": 1}}


@pytest.mark.parametrize("name,value", [
    ("SKYLARK_SPARSE_KERNEL", "pallas"), ("SKYLARK_FWHT_KERNEL", "pallas"),
    ("SKYLARK_FWHT_MIN_N", "1")])
def test_removed_pins_are_not_read(fresh_engine, monkeypatch, name, value):
    """The family pins of older trees: set, they move neither the memoised
    choice nor the executable keys nor the compile count of a warmed
    executor, and the registry declares none of them."""
    from libskylark_tpu.base import env as _env
    from libskylark_tpu.base.sparse import SparseMatrix

    assert name not in _env.REGISTRY
    rng = np.random.default_rng(15)
    t = FJLT(4096, 64, Context(seed=29), fut="wht")
    A = _lattice(rng, (4, 4096))
    c = CWT(256, 16, Context(seed=31))
    S = SparseMatrix.from_scipy(sp.random(
        256, 6, density=0.02, random_state=7, dtype=np.float32))

    def storm(ex):
        futs = [ex.submit_sketch(t, A, dimension=sk.ROWWISE),
                ex.submit_sparse(c, S, dimension=sk.COLUMNWISE)]
        ex.flush()
        return [np.asarray(f.result(timeout=120)) for f in futs]

    with _executor() as ex:
        before = storm(ex)
        memo = dict(ex._kernel_memo)
        keys = set(engine.cache().keys())
        compiles = engine.stats().compiles
        monkeypatch.setenv(name, value)
        ex._kernel_memo.clear()             # resolve again, the name set
        after = storm(ex)
        assert dict(ex._kernel_memo) == memo
        assert set(memo.values()) == {("xla", "default", None)}
    with _executor() as fresh:              # and an executor born under it
        again = storm(fresh)
        assert dict(fresh._kernel_memo) == memo
    assert set(engine.cache().keys()) == keys
    assert engine.stats().compiles == compiles
    for x, y, z in zip(before, after, again):
        assert np.array_equal(x, y) and np.array_equal(x, z)


# ---------------------------------------------------------------------------
# compressed approximate matmul
# ---------------------------------------------------------------------------


class TestCompressedMatmul:
    def test_srht_dense_within_bound(self, fresh_engine):
        rng = np.random.default_rng(18)
        n, m, p = 2048, 40, 17
        t = FJLT(n, 512, Context(seed=11), fut="wht")
        A = rng.standard_normal((m, n)).astype(np.float32)
        B = rng.standard_normal((n, p)).astype(np.float32)
        with _executor() as ex:
            est, bound = ex.submit_compressed_matmul(
                A, B, t).result(timeout=120)
        est = np.asarray(est)
        assert est.shape == (m, p)
        err = np.linalg.norm(est - A @ B)
        assert err <= bound
        assert bound == pytest.approx(
            np.linalg.norm(A) * np.linalg.norm(B)
            * math.sqrt(2.0 / 512))

    def test_cwt_dense_within_bound(self, fresh_engine):
        rng = np.random.default_rng(19)
        n, m, p = 1500, 30, 9            # non-pow2 contraction
        t = CWT(n, 512, Context(seed=13))
        A = rng.standard_normal((m, n)).astype(np.float32)
        B = rng.standard_normal((n, p)).astype(np.float32)
        with _executor() as ex:
            est, bound = ex.submit_compressed_matmul(
                A, B, t).result(timeout=120)
        assert np.linalg.norm(np.asarray(est) - A @ B) <= bound

    def test_sparse_cwt_bit_equal_to_densified(self, fresh_engine):
        rng = np.random.default_rng(20)
        n, m, p = 1500, 30, 9
        t = CWT(n, 256, Context(seed=17))
        A = sp.random(m, n, density=0.05, random_state=5,
                      dtype=np.float32, format="csr")
        B = rng.standard_normal((n, p)).astype(np.float32)
        with _executor() as ex:
            es, bs = ex.submit_compressed_matmul(
                A, B, t).result(timeout=120)
            ed, bd = ex.submit_compressed_matmul(
                A.toarray(), B, t).result(timeout=120)
        assert np.array_equal(np.asarray(es), np.asarray(ed))
        assert bs == pytest.approx(bd)

    def test_sparse_srht_matches_densified(self, fresh_engine):
        rng = np.random.default_rng(21)
        n, m, p = 2048, 30, 9
        t = FJLT(n, 256, Context(seed=19), fut="wht")
        A = sp.random(m, n, density=0.05, random_state=6,
                      dtype=np.float32, format="csr")
        B = rng.standard_normal((n, p)).astype(np.float32)
        with _executor() as ex:
            es, _ = ex.submit_compressed_matmul(
                A, B, t).result(timeout=120)
            ed, _ = ex.submit_compressed_matmul(
                A.toarray(), B, t).result(timeout=120)
        np.testing.assert_allclose(np.asarray(es), np.asarray(ed),
                                   rtol=1e-4, atol=1e-4)

    def test_default_transform_family_split(self, fresh_engine):
        """No caller transform: SRHT on pow2 contraction, CWT
        otherwise; the two front doors build bit-identical defaults."""
        rng = np.random.default_rng(22)
        with _executor() as ex:
            A = rng.standard_normal((8, 1024)).astype(np.float32)
            B = rng.standard_normal((1024, 3)).astype(np.float32)
            est, _ = ex.submit_compressed_matmul(
                A, B, s_dim=256, seed=4).result(timeout=120)
            t = engine.serve.default_cmm_transform(A, s_dim=256,
                                                   seed=4)
            assert isinstance(t, FJLT)
            est2, _ = ex.submit_compressed_matmul(
                A, B, t).result(timeout=120)
            assert np.array_equal(np.asarray(est), np.asarray(est2))
            A2 = rng.standard_normal((8, 1000)).astype(np.float32)
            assert isinstance(
                engine.serve.default_cmm_transform(A2), CWT)

    def test_unsupported_family_rejected(self, fresh_engine):
        rng = np.random.default_rng(23)
        t = sk.JLT(256, 32, Context(seed=3))
        A = rng.standard_normal((4, 256)).astype(np.float32)
        B = rng.standard_normal((256, 3)).astype(np.float32)
        with _executor() as ex:
            with pytest.raises(TypeError):
                ex.submit_compressed_matmul(A, B, t)

    def test_contraction_mismatch_rejected(self, fresh_engine):
        t = CWT(256, 32, Context(seed=3))
        with _executor() as ex:
            with pytest.raises(ValueError):
                ex.submit_compressed_matmul(
                    np.zeros((4, 256), np.float32),
                    np.zeros((128, 3), np.float32), t)

    def test_submits_counted(self, fresh_engine):
        rng = np.random.default_rng(24)
        t = CWT(512, 64, Context(seed=31))
        A = rng.standard_normal((4, 512)).astype(np.float32)
        B = rng.standard_normal((512, 3)).astype(np.float32)
        with _executor() as ex:
            ex.submit_compressed_matmul(A, B, t).result(timeout=60)
            st = ex.stats()["fwht"]
        assert st["cm_submits"] == 1
        assert engine.serve_stats()["fwht"]["cm_submits"] >= 1


# ---------------------------------------------------------------------------
# cross-subsystem dyadic regression: the dist shard fold and the
# session appender ride the SAME panel-free fold_rows — both must stay
# on the operator-panel oracle's bit pattern in the dyadic regime
# ---------------------------------------------------------------------------


class TestPanelFreeDistSessions:
    def test_dist_srht_shards_bit_equal_dyadic(self):
        """Ragged shard folds summed across shards equal the one-shot
        apply bit for bit (n, s even powers of two + lattice data:
        every partial is an exact dyadic rational, so the shard-order
        summation is exact)."""
        from libskylark_tpu.dist import plan as dp

        n, s, d = 256, 16, 6
        rng = np.random.default_rng(26)
        A = _lattice(rng, (n, d))
        plan = dp.ShardPlan(kind="srht", n=n, s_dim=s, d=d, seed=5,
                            targets=0, shard_rows=48).validate()
        sx = np.zeros((s, d), np.float32)
        for i, _, _ in plan.shards():
            sx = sx + dp.compute_shard(
                plan, i, dp.ArraySource(A))["SX"]
        t = FJLT(n, s, Context(seed=5), fut="wht")
        assert np.array_equal(
            sx, np.asarray(t.apply(jnp.asarray(A), sk.COLUMNWISE)))

    def test_session_fold_bit_equal_to_dist_fold_dyadic(self, tmp_path):
        """The sessions appender (cached full diagonal) and the dist
        folder (per-slice streams) are twins — same bits at the same
        offsets (the both-or-neither rule in sessions/state.py)."""
        from libskylark_tpu import sessions
        from libskylark_tpu.io.chunked import iter_array_batches

        n, s, d = 256, 16, 6
        rng = np.random.default_rng(27)
        A = _lattice(rng, (n, d))
        reg = sessions.SessionRegistry(directory=str(tmp_path))
        sid = reg.open(sessions.SessionSpec(
            kind="srht", n=n, s_dim=s, d=d, seed=5))
        seq = 0
        for Xb, _ in iter_array_batches(A, 40):
            seq += 1
            reg.append(sid, Xb, seq=seq)
        out = reg.finalize(sid)
        t = FJLT(n, s, Context(seed=5), fut="wht")
        assert np.array_equal(
            out["SX"],
            np.asarray(t.apply(jnp.asarray(A), sk.COLUMNWISE)))


# ---------------------------------------------------------------------------
# telemetry surface
# ---------------------------------------------------------------------------


class TestTelemetry:
    def test_prometheus_names(self, fresh_engine):
        from libskylark_tpu import telemetry

        rng = np.random.default_rng(25)
        t = FJLT(1024, 64, Context(seed=3), fut="wht")
        with _executor() as ex:
            ex.submit_sketch(t, _lattice(rng, (4, 1024)),
                             dimension=sk.ROWWISE).result(timeout=60)
            tc = CWT(512, 64, Context(seed=5))
            ex.submit_compressed_matmul(
                rng.standard_normal((4, 512)).astype(np.float32),
                rng.standard_normal((512, 3)).astype(np.float32),
                tc).result(timeout=60)
        text = telemetry.prometheus_text()
        assert "skylark_serve_fwht_flushes_total" in text
        assert "skylark_serve_compressed_matmul_submits_total" in text
