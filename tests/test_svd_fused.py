"""Fused randomized-SVD pipeline tests (r7 tentpole).

Oracles: (a) the engine's compile counters plus jax's lowering counter
— the recompile guard; (b) parity between the fused single-executable
pipeline and the unfused phase-profiling path (both run the same
algorithm on the same (seed, counter) sketch, so they must agree to the
f32 CholeskyQR2 grade on well- AND ill-conditioned operands); (c) dtype
threading through the wide-matrix recursion.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import jax._src.test_util as jtu

from libskylark_tpu import Context, engine, nla
from libskylark_tpu.utility import timer as phase_timer


@pytest.fixture()
def fresh_engine():
    engine.reset()
    yield
    engine.reset()


@pytest.fixture()
def profiling():
    """Select the unfused per-phase variant for the duration."""
    phase_timer.set_enabled(True)
    yield
    phase_timer.set_enabled(False)


def _lowrank(m, n, r, seed=0, noise=0.0):
    rng = np.random.default_rng(seed)
    A = rng.standard_normal((m, r)) @ rng.standard_normal((r, n))
    if noise:
        A = A + noise * rng.standard_normal((m, n))
    return A.astype(np.float32)


def _ill_conditioned(m=512, n=64, decades=4.5, seed=2):
    """Spectrum spanning ~10× past the f32 CholeskyQR textbook bound
    (cond ≈ 3e4 ≈ 10/√ε_f32) — the regime the CholeskyQR2 second pass
    exists for."""
    rng = np.random.default_rng(seed)
    Uq, _ = np.linalg.qr(rng.standard_normal((m, n)))
    Vq, _ = np.linalg.qr(rng.standard_normal((n, n)))
    s = np.logspace(0, -decades, n)
    return ((Uq * s) @ Vq.T).astype(np.float32)


def _both_paths(A, rank, seed, params):
    """(fused, unfused) factorizations of the same problem with the
    same sketch allocation."""
    fused = nla.approximate_svd(jnp.asarray(A), rank, Context(seed=seed),
                                params)
    phase_timer.set_enabled(True)
    try:
        eager = nla.approximate_svd(jnp.asarray(A), rank,
                                    Context(seed=seed), params)
    finally:
        phase_timer.set_enabled(False)
    return fused, eager


class TestFusedEagerParity:
    def test_well_conditioned(self):
        A = _lowrank(200, 80, 6, seed=1, noise=0.01)
        p = nla.ApproximateSVDParams(num_iterations=2)
        (Uf, Sf, Vf), (Ue, Se, Ve) = _both_paths(A, 6, 3, p)
        np.testing.assert_allclose(np.asarray(Sf), np.asarray(Se),
                                   rtol=1e-4)
        rf = np.asarray(Uf) * np.asarray(Sf) @ np.asarray(Vf).T
        re = np.asarray(Ue) * np.asarray(Se) @ np.asarray(Ve).T
        # same algorithm, same sketch bits: the two programs differ only
        # in op scheduling/fusion, so the reconstructions agree at f32
        np.testing.assert_allclose(rf, re, atol=1e-4 * np.abs(re).max())

    def test_ill_conditioned(self):
        A = _ill_conditioned()
        p = nla.ApproximateSVDParams(num_iterations=2)
        (Uf, Sf, Vf), (Ue, Se, Ve) = _both_paths(A, 8, 13, p)
        np.testing.assert_allclose(np.asarray(Sf), np.asarray(Se),
                                   rtol=1e-4)
        # both paths keep the factors orthonormal at the CholeskyQR2
        # grade through the ill-conditioned panels
        for F in (Uf, Vf):
            np.testing.assert_allclose(np.asarray(F.T @ F), np.eye(8),
                                       atol=1e-4)

    @pytest.mark.parametrize("rr", ["cqr2", "svd"])
    def test_rr_variants_fused(self, rr):
        A = _ill_conditioned()
        ref = np.linalg.svd(A, compute_uv=False)[:8]
        _, S, _ = nla.approximate_svd(
            jnp.asarray(A), 8, Context(seed=13),
            nla.ApproximateSVDParams(num_iterations=2, rr=rr))
        np.testing.assert_allclose(np.asarray(S), ref, rtol=1e-4)

    def test_symmetric_parity(self):
        rng = np.random.default_rng(8)
        Q, _ = np.linalg.qr(rng.standard_normal((80, 80)))
        w = np.zeros(80)
        w[:6] = [10, -8, 6, 4, -2, 1]
        A = ((Q * w) @ Q.T).astype(np.float32)
        p = nla.ApproximateSVDParams(num_iterations=3)
        Vf, Sf = nla.approximate_symmetric_svd(jnp.asarray(A), 6,
                                               Context(seed=23), p)
        phase_timer.set_enabled(True)
        try:
            Ve, Se = nla.approximate_symmetric_svd(jnp.asarray(A), 6,
                                                   Context(seed=23), p)
        finally:
            phase_timer.set_enabled(False)
        np.testing.assert_allclose(np.asarray(Sf), np.asarray(Se),
                                   rtol=1e-4, atol=1e-5)
        rf = np.asarray(Vf) * np.asarray(Sf) @ np.asarray(Vf).T
        re = np.asarray(Ve) * np.asarray(Se) @ np.asarray(Ve).T
        np.testing.assert_allclose(rf, re, atol=1e-4 * np.abs(re).max())


class TestRecompileGuard:
    def test_identical_shapes_compile_once(self, fresh_engine):
        """r7 acceptance: the second identical-shape call compiles 0
        new executables — by the engine's counters AND jax's lowering
        counter."""
        A = jnp.asarray(_lowrank(96, 48, 4, seed=5))
        p = nla.ApproximateSVDParams(num_iterations=1)
        nla.approximate_svd(A, 4, Context(seed=7), p)
        assert engine.stats().misses == 1
        with jtu.count_jit_and_pmap_lowerings() as lowerings:
            nla.approximate_svd(A, 4, Context(seed=7), p)
        assert lowerings() == 0
        s = engine.stats()
        assert (s.misses, s.hits, s.recompiles) == (1, 1, 0)

    def test_new_seed_hits_same_executable(self, fresh_engine):
        """The sketch key is a *dynamic* argument: a different Context
        seed reuses the executable (serve-many), it does not recompile."""
        A = jnp.asarray(_lowrank(96, 48, 4, seed=5))
        p = nla.ApproximateSVDParams(num_iterations=1)
        nla.approximate_svd(A, 4, Context(seed=1), p)
        nla.approximate_svd(A, 4, Context(seed=2), p)
        s = engine.stats()
        assert (s.misses, s.hits) == (1, 1)

    def test_param_change_compiles_fresh(self, fresh_engine):
        A = jnp.asarray(_lowrank(96, 48, 4, seed=5))
        nla.approximate_svd(A, 4, Context(seed=1),
                            nla.ApproximateSVDParams(num_iterations=1))
        nla.approximate_svd(A, 4, Context(seed=1),
                            nla.ApproximateSVDParams(num_iterations=2))
        s = engine.stats()
        assert s.misses == 2 and s.recompiles == 0

    def test_profiling_path_bypasses_engine(self, fresh_engine, profiling):
        A = jnp.asarray(_lowrank(64, 32, 4, seed=6))
        nla.approximate_svd(A, 4, Context(seed=3),
                            nla.ApproximateSVDParams(num_iterations=1))
        assert engine.stats().executions == 0


class TestDtypeThreading:
    @pytest.fixture()
    def x64(self):
        with jax.enable_x64(True):
            yield

    def test_wide_matrix_keeps_dtype_override(self, x64):
        """Satellite regression: the wide-matrix (m < n) recursion must
        carry the caller's dtype override through the transpose."""
        rng = np.random.default_rng(11)
        A = rng.standard_normal((24, 80))           # float64 under x64
        U, S, V = nla.approximate_svd(
            jnp.asarray(A), 4, Context(seed=5),
            nla.ApproximateSVDParams(num_iterations=1),
            dtype=jnp.float32)
        assert U.dtype == jnp.float32
        assert S.dtype == jnp.float32
        assert V.dtype == jnp.float32
        assert U.shape == (24, 4) and V.shape == (80, 4)

    def test_tall_dtype_override(self, x64):
        rng = np.random.default_rng(12)
        A = rng.standard_normal((80, 24))
        U, S, V = nla.approximate_svd(
            jnp.asarray(A), 4, Context(seed=5),
            nla.ApproximateSVDParams(num_iterations=1),
            dtype=jnp.float64)
        assert U.dtype == jnp.float64

    def test_sparse_dtype_override_rejected(self):
        import scipy.sparse as sp

        from libskylark_tpu.base.sparse import SparseMatrix

        dense = np.eye(8, dtype=np.float32)
        A = SparseMatrix.from_scipy(sp.csc_matrix(dense))
        with pytest.raises(Exception, match="dtype"):
            nla.approximate_svd(A, 2, Context(0), dtype=jnp.float32)
