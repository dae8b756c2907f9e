"""NLA-layer tests: approximate SVD (reconstruction oracle), least squares,
condition estimation, spectral helpers.

Mirrors the reference's SVD reconstruction checks
(ref: tests/unit/test_utils.hpp:61-148, SVDElementalTest.cpp) and the
regression-test spectral bounds.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from libskylark_tpu import Context, nla
from libskylark_tpu import parallel as par


def _lowrank(m, n, r, seed=0, noise=0.0, dtype=np.float32):
    rng = np.random.default_rng(seed)
    A = rng.standard_normal((m, r)) @ rng.standard_normal((r, n))
    if noise:
        A = A + noise * rng.standard_normal((m, n))
    return A.astype(dtype)


class TestApproximateSVD:
    def test_exact_rank_reconstruction(self):
        """Rank-r matrix recovered to the reference's 1e-4-style tolerance."""
        A = _lowrank(200, 80, 6, seed=1)
        U, S, V = nla.approximate_svd(jnp.asarray(A), 6, Context(seed=3),
                                      nla.ApproximateSVDParams(num_iterations=2))
        recon = np.asarray(U) * np.asarray(S) @ np.asarray(V).T
        err = np.linalg.norm(recon - A) / np.linalg.norm(A)
        assert err < 1e-4

    @pytest.mark.slow
    def test_wide_matrix_branch(self):
        A = _lowrank(60, 300, 5, seed=2)
        U, S, V = nla.approximate_svd(jnp.asarray(A), 5, Context(seed=5),
                                      nla.ApproximateSVDParams(num_iterations=2))
        assert U.shape == (60, 5) and V.shape == (300, 5)
        recon = np.asarray(U) * np.asarray(S) @ np.asarray(V).T
        assert np.linalg.norm(recon - A) / np.linalg.norm(A) < 1e-4

    def test_singular_values_match_exact(self):
        A = _lowrank(150, 100, 20, seed=3, noise=0.01)
        sv_exact = np.linalg.svd(A, compute_uv=False)[:5]
        _, S, _ = nla.approximate_svd(jnp.asarray(A), 5, Context(seed=7),
                                      nla.ApproximateSVDParams(num_iterations=3))
        np.testing.assert_allclose(np.asarray(S), sv_exact, rtol=0.05)

    def test_orthonormal_factors(self):
        A = _lowrank(100, 60, 8, seed=4, noise=0.05)
        U, S, V = nla.approximate_svd(jnp.asarray(A), 8, Context(seed=11),
                                      nla.ApproximateSVDParams(num_iterations=2))
        np.testing.assert_allclose(np.asarray(U.T @ U), np.eye(8), atol=1e-4)
        np.testing.assert_allclose(np.asarray(V.T @ V), np.eye(8), atol=1e-4)
        assert (np.diff(np.asarray(S)) <= 1e-6).all()  # descending

    @pytest.mark.slow
    def test_power_iteration_improves_noisy(self):
        A = _lowrank(300, 200, 10, seed=5, noise=0.5)
        best = np.linalg.svd(A, compute_uv=False)
        tail = np.sqrt((best[10:] ** 2).sum())

        def err(q):
            U, S, V = nla.approximate_svd(
                jnp.asarray(A), 10, Context(seed=13),
                nla.ApproximateSVDParams(num_iterations=q))
            recon = np.asarray(U) * np.asarray(S) @ np.asarray(V).T
            return np.linalg.norm(recon - A)

        e0, e3 = err(0), err(3)
        assert e3 <= e0 + 1e-5
        assert e3 <= 1.05 * tail  # near-optimal with power iterations

    def test_sharded_input(self, mesh1d):
        A = _lowrank(256, 64, 4, seed=6)
        A_sh = par.distribute(A, par.row_sharded(mesh1d))
        U, S, V = nla.approximate_svd(A_sh, 4, Context(seed=17),
                                      nla.ApproximateSVDParams(num_iterations=2))
        recon = np.asarray(U) * np.asarray(S) @ np.asarray(V).T
        assert np.linalg.norm(recon - A) / np.linalg.norm(A) < 1e-3

    def test_jittable(self):
        A = jnp.asarray(_lowrank(80, 40, 4, seed=7))
        ctx = Context(seed=19)
        # pre-allocate so the jitted fn closes over a fixed transform
        f = jax.jit(lambda M: nla.approximate_svd(
            M, 4, Context(seed=19), nla.ApproximateSVDParams(num_iterations=1)))
        U, S, V = f(A)
        recon = np.asarray(U) * np.asarray(S) @ np.asarray(V).T
        assert np.linalg.norm(recon - np.asarray(A)) / np.linalg.norm(A) < 1e-3

    def test_invalid_rank(self):
        with pytest.raises(Exception, match="rank"):
            nla.approximate_svd(jnp.eye(4), 0, Context(0))

    @pytest.mark.parametrize("symmetric", [False, True])
    def test_fused_pipeline_carries_the_phase_names(self, symmetric):
        """The fused pipelines name their phases as the unfused variant's
        timers do: each scope is in the op_name metadata of the compiled
        program (it does not rename the instructions)."""
        from libskylark_tpu.nla import svd

        statics = dict(k=4, kp=8, num_iterations=1, skip_qr=False,
                       ortho="cqr2")
        if symmetric:
            fn, shape = svd._symmetric_svd_pipeline, (48, 48)
        else:
            fn, shape, statics["rr"] = svd._svd_pipeline, (64, 48), "cqr2"
        A = jax.ShapeDtypeStruct(shape, jnp.float32)
        text = jax.jit(fn, static_argnames=tuple(statics)).lower(
            A, jax.random.key(0), **statics).compile().as_text()
        for phase in ("SKETCH", "POWER_ITERATION", "RR_PROJECT", "RR_SMALL"):
            assert f"/{phase}/" in text, phase

    def test_rr_reductions_agree(self):
        """The CQR2-reduced Rayleigh-Ritz (r5 default — the r4 mesh
        hotspot fix) and the reference-algebra direct SVD of the k'×n
        panel (ref: nla/svd.hpp:286-290) must produce the same
        factorization on the same sketch, including on an
        ill-conditioned spectrum (decay past 1/√ε in f32)."""
        rng = np.random.default_rng(21)
        r0 = 48
        decay = 0.82 ** np.arange(r0)
        A = ((rng.standard_normal((300, r0)) * decay)
             @ rng.standard_normal((r0, 160))).astype(np.float32)
        out = {}
        for rr in ("cqr2", "svd"):
            U, S, V = nla.approximate_svd(
                jnp.asarray(A), 8, Context(seed=23),
                nla.ApproximateSVDParams(num_iterations=1, rr=rr))
            np.testing.assert_allclose(np.asarray(U.T @ U), np.eye(8),
                                       atol=1e-4)
            np.testing.assert_allclose(np.asarray(V.T @ V), np.eye(8),
                                       atol=1e-4)
            out[rr] = np.asarray(S)
        np.testing.assert_allclose(out["cqr2"], out["svd"], rtol=1e-4)

    @pytest.mark.parametrize("rr,ortho", [("cqr2", "cqr2"),
                                          ("svd", "qr")])
    def test_ill_conditioned_parity_near_f32_cqr_bound(self, rr, ortho):
        """Parity at a spectrum spanning ~10× past the f32 CholeskyQR
        textbook bound (cond ≲ 1/√ε ≈ 3e3): the top-k singular values
        must match reference algebra (np.linalg.svd) at f32 grade for
        BOTH the mesh-native default (cqr2/cqr2 — accurate far past the
        textbook bound for the truncated spectra randomized SVD meets)
        and the reference-algebra combination rr='svd', ortho='qr'
        (Householder + direct panel SVD — the configuration to reach
        for on EXTREME spectra; docs/nla.rst). ADVICE r5."""
        rng = np.random.default_rng(2)
        m, n, k = 512, 64, 8
        Uq, _ = np.linalg.qr(rng.standard_normal((m, n)))
        Vq, _ = np.linalg.qr(rng.standard_normal((n, n)))
        s = np.logspace(0, -4.5, n)        # cond ≈ 3e4 ≈ 10/√ε_f32
        A = (Uq * s) @ Vq.T
        ref = np.linalg.svd(A, compute_uv=False)[:k]
        U, S, V = nla.approximate_svd(
            jnp.asarray(A, jnp.float32), k, Context(seed=13),
            nla.ApproximateSVDParams(num_iterations=2, rr=rr,
                                     ortho=ortho))
        np.testing.assert_allclose(np.asarray(S), ref, rtol=1e-4)
        # factors stay orthonormal through the ill-conditioned panels
        np.testing.assert_allclose(np.asarray(U.T @ U), np.eye(k),
                                   atol=1e-4)
        np.testing.assert_allclose(np.asarray(V.T @ V), np.eye(k),
                                   atol=1e-4)

    def test_rr_invalid_value_raises(self):
        with pytest.raises(Exception, match="rr"):
            nla.approximate_svd(
                jnp.asarray(_lowrank(40, 20, 4, seed=9)), 4,
                Context(seed=2), nla.ApproximateSVDParams(rr="bogus"))


class TestSymmetricSVD:
    def test_symmetric_reconstruction(self):
        rng = np.random.default_rng(8)
        Q, _ = np.linalg.qr(rng.standard_normal((80, 80)))
        w = np.zeros(80)
        w[:6] = [10, -8, 6, 4, -2, 1]
        A = ((Q * w) @ Q.T).astype(np.float32)
        V, S = nla.approximate_symmetric_svd(jnp.asarray(A), 6, Context(seed=23),
                                             nla.ApproximateSVDParams(num_iterations=3))
        recon = np.asarray(V) * np.asarray(S) @ np.asarray(V).T
        assert np.linalg.norm(recon - A) / np.linalg.norm(A) < 1e-3
        # eigenvalues with signs, sorted by magnitude
        np.testing.assert_allclose(np.asarray(S), w[:6], rtol=1e-3, atol=1e-3)

    def test_rejects_nonsquare(self):
        with pytest.raises(Exception, match="square"):
            nla.approximate_symmetric_svd(jnp.zeros((3, 4)), 2, Context(0))


class TestLeastSquares:
    def _problem(self, m=2000, n=12, seed=9):
        rng = np.random.default_rng(seed)
        A = rng.standard_normal((m, n)).astype(np.float32)
        x = rng.standard_normal((n,)).astype(np.float32)
        b = A @ x + 0.1 * rng.standard_normal(m).astype(np.float32)
        return A, b

    def test_approximate_ls_residual(self):
        A, b = self._problem()
        x = nla.approximate_least_squares(jnp.asarray(A), jnp.asarray(b),
                                          Context(seed=29))
        res_opt = np.linalg.norm(A @ np.linalg.lstsq(A, b, rcond=None)[0] - b)
        res = np.linalg.norm(A @ np.asarray(x) - b)
        assert res <= 1.5 * res_opt

    def test_fast_ls_high_accuracy(self):
        A, b = self._problem(seed=10)
        x, it = nla.fast_least_squares(jnp.asarray(A), jnp.asarray(b),
                                       Context(seed=31))
        assert int(it) > 0
        x_np = np.linalg.lstsq(A, b, rcond=None)[0]
        res_opt = np.linalg.norm(A @ x_np - b)
        res = np.linalg.norm(A @ np.asarray(x) - b)
        assert res <= 1.0001 * res_opt


class TestCondEst:
    def test_estimates_condition(self):
        rng = np.random.default_rng(11)
        m, n, cond = 300, 40, 50.0
        U, _ = np.linalg.qr(rng.standard_normal((m, n)))
        V, _ = np.linalg.qr(rng.standard_normal((n, n)))
        s = np.logspace(0, -np.log10(cond), n)
        A = ((U * s) @ V.T).astype(np.float32)
        est, smax, smin = nla.estimate_condition(jnp.asarray(A), Context(seed=37),
                                                 max_iter=150)
        assert smax == pytest.approx(1.0, rel=0.05)
        assert est == pytest.approx(cond, rel=0.35)

    def test_deterministic(self):
        A = jnp.asarray(np.random.default_rng(12).standard_normal((50, 10)),
                        dtype=jnp.float32)
        e1 = nla.estimate_condition(A, Context(seed=41))
        e2 = nla.estimate_condition(A, Context(seed=41))
        assert e1 == e2

    def test_sparse_operand_matches_dense(self, mesh1d):
        """Sparse and distributed-sparse operands drive the same
        Golub-Kahan loop through scipy matvecs. Tolerance is loose on
        purpose: the dense path runs BLAS gemv, the sparse path scipy CSC
        matvecs — different accumulation orders can flip the discrete
        convergence checks on some BLAS builds, shifting the stop
        iteration by one tol=1e-3 window."""
        import scipy.sparse as sp

        from libskylark_tpu.base.dist_sparse import distribute_sparse
        from libskylark_tpu.base.sparse import SparseMatrix

        rng = np.random.default_rng(13)
        dense = (rng.standard_normal((120, 20)) *
                 (rng.uniform(size=(120, 20)) < 0.3)).astype(np.float32)
        A = SparseMatrix.from_scipy(sp.csc_matrix(dense))
        e_dense = nla.estimate_condition(jnp.asarray(dense),
                                         Context(seed=43))
        e_sparse = nla.estimate_condition(A, Context(seed=43))
        np.testing.assert_allclose(e_sparse, e_dense, rtol=5e-3)

    @pytest.mark.slow
    def test_dist_sparse_operand_never_materializes(self, mesh2d,
                                                    monkeypatch):
        """DistSparseMatrix operands drive the Golub-Kahan recurrence ON
        DEVICE through spmm/spmm_t (ref: nla/CondEst.hpp:67-305 drives the
        distributed operand) — gathering the operand to one host would cap
        the operand size at one host's memory, so to_local is forbidden
        for the whole run. The f32 device recurrence (with full
        reorthogonalization) must agree with the f64 host path."""
        import scipy.sparse as sp

        from libskylark_tpu.base.dist_sparse import (DistSparseMatrix,
                                                     distribute_sparse)
        from libskylark_tpu.base.sparse import SparseMatrix

        rng = np.random.default_rng(13)
        dense = (rng.standard_normal((120, 20)) *
                 (rng.uniform(size=(120, 20)) < 0.3)).astype(np.float32)
        A = SparseMatrix.from_scipy(sp.csc_matrix(dense))
        e_sparse = nla.estimate_condition(A, Context(seed=43))
        D = distribute_sparse(A, mesh2d, row_axis="rows", col_axis="cols")
        monkeypatch.setattr(
            DistSparseMatrix, "to_local",
            lambda self: (_ for _ in ()).throw(
                AssertionError("condest gathered the operand to host")),
        )
        e_dist = nla.estimate_condition(D, Context(seed=43))
        np.testing.assert_allclose(e_dist, e_sparse, rtol=5e-2)


class TestSpectral:
    def test_chebyshev_points(self):
        x = nla.chebyshev_points(5)
        np.testing.assert_allclose(x, [1.0, np.sqrt(2) / 2, 0.0,
                                       -np.sqrt(2) / 2, -1.0], atol=1e-12)

    def test_chebyshev_points_general_interval(self):
        x = nla.chebyshev_points(5, a=2.0, b=3.0)
        assert x.max() == pytest.approx(3.0) and x.min() == pytest.approx(2.0)
        assert x[2] == pytest.approx(2.5)  # midpoint snapped to center

    def test_diff_matrix_differentiates_polynomials(self):
        """D applied to values of p(x)=x³ must give 3x² exactly (degree < N)."""
        D, x = nla.chebyshev_diff_matrix(8)
        p = x**3
        dp = D @ p
        np.testing.assert_allclose(dp, 3 * x**2, atol=1e-10)

    def test_diff_matrix_rescaled_interval(self):
        D, x = nla.chebyshev_diff_matrix(10, a=0.0, b=2.0)
        assert x.min() == pytest.approx(0.0) and x.max() == pytest.approx(2.0)
        p = x**2
        np.testing.assert_allclose(D @ p, 2 * x, atol=1e-9)
