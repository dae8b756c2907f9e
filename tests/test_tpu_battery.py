"""Cross-layer ON-CHIP battery (@pytest.mark.tpu, run with
SKYLARK_TEST_TPU=1 on a real TPU backend).

The kernel's own on-chip tier (tests/test_pallas_dense.py) covers only
the Pallas kernel; a Mosaic/XLA-on-TPU regression in any
non-Pallas path — the hash scatter, FJLT's DCT, while_loop Krylov,
rand-SVD, the jitted ADMM consensus step — would have passed every test
the repo could run. This battery executes one small correctness oracle
per layer ON the TPU backend, the run-on-target discipline of the
reference's unit suite (ref: tests/unit/CMakeLists.txt:10-46) with the
reference's 1e-4-grade oracles (ref: tests/unit/test_utils.hpp:48).

Every oracle is HOST-side numpy/scipy — nothing on the reference side
of an assert touches the device, so an XLA-on-TPU lowering bug cannot
cancel itself out of the comparison. Shapes are toy: the point is
lowering coverage, not perf.
"""

import hashlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import scipy.fft as sfft

from libskylark_tpu.base.context import Context
from libskylark_tpu.sketch import pallas_dense as pd

# SKYLARK_BATTERY_FORCE=1 runs the battery on the CPU backend — a dry
# validation of the test logic itself (APIs, oracle math) before chip
# time is spent on it. The goldens and oracles are backend-independent
# by construction.
ON_TPU = (pd.available()
          or os.environ.get("SKYLARK_BATTERY_FORCE") == "1")

pytestmark = [
    pytest.mark.tpu,
    pytest.mark.skipif(not ON_TPU, reason="needs a real TPU backend"),
]


def _rand(*shape, seed=0):
    return np.random.default_rng(seed).standard_normal(shape).astype(
        np.float32)


# ---------------------------------------------------------------------------
# base: counter-based RNG bit-exactness across backends (P9)
# ---------------------------------------------------------------------------


class TestBaseLayer:
    # goldens captured on the CPU backend (stream format 3); equality on
    # TPU proves the threefry uint32 pipeline and the exact draw→sample
    # maps (sign bit, 24-bit unit, modular randint) lower bit-exactly
    # across backends — the P9 stream-format claim. The Normal map goes
    # through erf_inv, which is backend-dependent at the ~1e-5 level
    # (observed on a v5e, PR 21: the panel's sha differs from the
    # CPU's): float families are bit-pinned per backend, and across
    # backends the WHOLE panel is held to the 1e-4 oracle against the
    # CPU's values.
    GOLDEN_RADEMACHER_PANEL = ("47bf328e7ef4012bef5196b18dc7a414"
                               "dddd647561cd5f034e33ce1ed3deb4e9")
    GOLDEN_UNIFORM_SLICE = ("8854a2a0bace75bda3bd6dbc5cd7b045"
                            "30d0fd9a170ea0f44671d02cfc978597")
    GOLDEN_INT_SLICE = ("dbb1f8269899a2fbe8d4933de37972e8"
                        "3dee29285397763ed155845cc262e50f")
    # the (8, 16) Normal panel as the CPU backend generates it, float32
    # little-endian bytes; sha256 = GOLDEN_PANEL (unchanged since
    # stream format 2: dense_block was always written in explicit ops)
    GOLDEN_PANEL = ("0c2b80f7b592cbac127aa4dc1d3e3231"
                    "e7146d68d455dc5d166a7830092311b3")
    CPU_NORMAL_PANEL = (
        "0090f63f5a6ca43f3501aabde0c9613fca8a8b3fe328a63fe4f489bf5453733f"
        "508132bfc94c3dbffd8797bfa64a853fcf5490bdfee41c3fbcebb1bf496eb63f"
        "8b5b0b3e200b6bbf77f935bf7bb47ebe6cb15d3f154fc4be955a25bfb9ce993f"
        "136f123f4bddb43dcac48d3f7adc0dbf711d313fd6f9ca3e1b753fbfbd62ec3e"
        "2d7c8e3f28cbe3bed38b90bc8aaa163fff84afbef6c6893ff21ce8be562ead3f"
        "725690be600baa3f9bc1a33ee170c3be01f9e5bfc778003f2a0d2e3e86c549c0"
        "33c8bbbe73d7a03e7966203e9fcaadbef903bfbc5045f33e4ec4e33ee84e9b3f"
        "11a0603e14a41abfb9abb23ebe64403fbed4c6be648b59bccc48f3be31edf4bf"
        "633af63f44b1473fb4d4fdbede92423f342d8dbff71a653fb4948dbf1a74683d"
        "2387c1bff784f83e3fa6d63d1ed962bfc376ce3e28e6a63fbd553b3fa36e7bbf"
        "f590ccbfd8e7673f4af2dd3e53d4a23f4549f3bfa1f5113fe482ed3e7a281b3f"
        "66625abf8efd86be69aaff3e47a768bfac13363e6221ddbf743b103f52b07cbf"
        "10221440829fb73d1d7486bf6360cabe751c3b3f6fe3b63e68b5a0bd2019aabe"
        "ed1521bd64c60ebf3ab9b4bfa424a83e471ade3fc2a17abd9500cdbd4570a63f"
        "b9a9153fda6182bf2da64d3f1776c93f3bdd96bfd2f083bd0215a93de1b080bf"
        "d59922bfc6110c40f7e1cbbe9f5cc1be84347a3d3ae5503c6b8c87be6436433f")

    @staticmethod
    def _sha(x, dtype) -> str:
        return hashlib.sha256(np.ascontiguousarray(
            np.asarray(x, dtype)).tobytes()).hexdigest()

    def test_threefry_streams_bit_exact_vs_cpu_golden(self):
        from libskylark_tpu.base import randgen

        key = Context(seed=42).allocate().key
        P = randgen.dense_panel(key, randgen.Rademacher(), 8, 0, 16,
                                256, "float32")
        assert self._sha(P, np.float32) == self.GOLDEN_RADEMACHER_PANEL
        U = randgen.stream_slice(key, randgen.Uniform(0.0, 1.0),
                                 0, 16, dtype="float32")
        assert self._sha(U, np.float32) == self.GOLDEN_UNIFORM_SLICE
        I = randgen.stream_slice(key, randgen.UniformInt(0, 99),
                                 0, 16, dtype="int32")
        assert self._sha(I, np.int32) == self.GOLDEN_INT_SLICE

    def test_normal_panel_vs_cpu_values(self):
        import jax

        from libskylark_tpu.base import randgen

        key = Context(seed=42).allocate().key
        N = randgen.dense_panel(key, randgen.Normal(), 8, 0, 16, 256,
                                "float32")
        want = np.frombuffer(bytes.fromhex(self.CPU_NORMAL_PANEL),
                             "<f4").reshape(8, 16)
        assert self._sha(want, np.float32) == self.GOLDEN_PANEL
        if jax.default_backend() == "cpu":
            assert self._sha(N, np.float32) == self.GOLDEN_PANEL
        np.testing.assert_allclose(np.asarray(N), want,
                                   atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# sketch: dense (XLA path), hash scatter (dense + local sparse), FJLT DCT
# ---------------------------------------------------------------------------


class TestSketchLayer:
    def test_jlt_xla_path_vs_host_gemm(self):
        """The NON-Pallas dense path (the sharded-apply workhorse): the
        on-device generation + gemm vs a host f64 gemm over the
        host-pulled operator panel."""
        from libskylark_tpu.sketch import JLT, ROWWISE
        from libskylark_tpu.sketch import params as sketch_params

        n, s, m = 1024, 64, 32
        T = JLT(n, s, Context(seed=3))
        A = _rand(m, n, seed=1)
        prev = sketch_params.get_use_pallas()
        sketch_params.set_use_pallas(False)
        try:
            got = np.asarray(T.apply(jnp.asarray(A), ROWWISE))
        finally:
            sketch_params.set_use_pallas(prev)
        S_host = np.asarray(T.s_panel(0, n), np.float64)
        np.testing.assert_allclose(
            got, A.astype(np.float64) @ S_host.T, atol=1e-4, rtol=1e-4)

    def test_cwt_scatter_dense_and_sparse_vs_host(self):
        """The hash-sketch segment-sum/scatter lowering, dense input and
        local-CSC sparse input, vs a host scatter loop."""
        import scipy.sparse as sp

        from libskylark_tpu.base.sparse import SparseMatrix
        from libskylark_tpu.sketch import COLUMNWISE, CWT

        n, s, m = 512, 32, 16
        T = CWT(n, s, Context(seed=4))
        h = np.asarray(T.bucket_indices())
        v = np.asarray(T.values(jnp.float32), np.float64)

        A = _rand(n, m, seed=2)
        want = np.zeros((s, m), np.float64)
        for i in range(n):
            want[h[i]] += v[i] * A[i]
        got = np.asarray(T.apply(jnp.asarray(A), COLUMNWISE))
        np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)

        Asp = sp.random(n, m, density=0.05, random_state=0,
                        dtype=np.float64)
        got_sp = np.asarray(T.apply(SparseMatrix.from_scipy(Asp),
                                    COLUMNWISE))
        want_sp = np.zeros((s, m), np.float64)
        dense = Asp.toarray()
        for i in range(n):
            want_sp[h[i]] += v[i] * dense[i]
        np.testing.assert_allclose(got_sp, want_sp, atol=1e-4, rtol=1e-4)

    def test_fjlt_dct_path_vs_scipy(self):
        """FJLT = sqrt(N/S)·R·F·D with F the FFTW-convention DCT-II
        (sketch/fut.py): on-chip apply vs the explicit host operator
        assembled from scipy.fft.dct."""
        import libskylark_tpu.sketch as sk

        N, S, m = 256, 32, 8
        T = sk.FJLT(N, S, Context(seed=7))
        D = np.asarray(T.diagonal(), np.float64)
        R = np.asarray(T.sample_indices())
        F = sfft.dct(np.eye(N), type=2, axis=0)
        S_explicit = (np.sqrt(N / S) * (1.0 / np.sqrt(2 * N))
                      * F[R, :] @ np.diag(D))
        A = _rand(N, m, seed=3)
        got = np.asarray(T.apply(jnp.asarray(A), sk.COLUMNWISE))
        np.testing.assert_allclose(got, S_explicit @ A, atol=1e-3,
                                   rtol=1e-3)

    def test_frft_fastfood_kernel_approximation(self):
        """Fastfood features on chip approximate the Gaussian kernel
        (the SHGΠHB chain end-to-end: WHT matmuls, gather permutation,
        cos featurization)."""
        from libskylark_tpu.sketch import ROWWISE
        from libskylark_tpu.sketch.frft import FastGaussianRFT

        d, s, m, sigma = 64, 2048, 12, 3.0
        X = _rand(m, d, seed=4) * 0.3
        T = FastGaussianRFT(d, s, Context(seed=8), sigma=sigma)
        F = np.asarray(T.apply(jnp.asarray(X), ROWWISE), np.float64)
        got = F @ F.T
        d2 = ((X[:, None, :] - X[None, :, :]) ** 2).sum(-1)
        want = np.exp(-d2 / (2 * sigma * sigma))
        assert np.max(np.abs(got - want)) < 0.15  # MC-rate oracle


# ---------------------------------------------------------------------------
# algorithms: while_loop Krylov on chip
# ---------------------------------------------------------------------------


class TestAlgorithmsLayer:
    def test_lsqr_while_loop_vs_numpy_lstsq(self):
        from libskylark_tpu.algorithms.krylov import KrylovParams, lsqr

        m, n = 256, 24
        A = _rand(m, n, seed=5)
        x_true = _rand(n, seed=6)
        b = A @ x_true
        x, _ = lsqr(jnp.asarray(A), jnp.asarray(b),
                    KrylovParams(tolerance=1e-8, iter_lim=200))
        want = np.linalg.lstsq(A.astype(np.float64),
                               b.astype(np.float64), rcond=None)[0]
        np.testing.assert_allclose(np.asarray(x), want, atol=1e-3,
                                   rtol=1e-3)


# ---------------------------------------------------------------------------
# nla: randomized SVD on chip
# ---------------------------------------------------------------------------


class TestNlaLayer:
    def test_rand_svd_vs_numpy(self):
        from libskylark_tpu.nla.svd import approximate_svd

        m, n, k = 384, 128, 6
        rng = np.random.default_rng(9)
        # low-rank + small tail so the top-k are well separated
        B = (rng.standard_normal((m, k)) * (10.0 ** -np.arange(k))
             ) @ rng.standard_normal((k, n))
        A = (B + 1e-6 * rng.standard_normal((m, n))).astype(np.float32)
        U, S, V = approximate_svd(jnp.asarray(A), k, Context(seed=10))
        sv_true = np.linalg.svd(A.astype(np.float64),
                                compute_uv=False)[:k]
        np.testing.assert_allclose(np.asarray(S), sv_true, rtol=1e-2)
        # factorization consistency: A·V ≈ U·S, all factors host-side
        Un, Sn, Vn = (np.asarray(U, np.float64), np.asarray(S, np.float64),
                      np.asarray(V, np.float64))
        res = np.linalg.norm(A.astype(np.float64) @ Vn - Un * Sn[None, :])
        assert res / np.linalg.norm(Sn) < 1e-3


# ---------------------------------------------------------------------------
# ml: one jitted ADMM consensus solve on chip
# ---------------------------------------------------------------------------


class TestMlLayer:
    def test_admm_trains_and_is_deterministic(self):
        from libskylark_tpu.algorithms.prox import (HingeLoss,
                                                    L2Regularizer)
        from libskylark_tpu.ml.admm import BlockADMMSolver
        from libskylark_tpu.ml.kernels import Gaussian

        n, d, s = 256, 16, 128
        rng = np.random.default_rng(11)
        X = rng.standard_normal((n, d)).astype(np.float32)
        y = (X[:, 0] > 0).astype(np.int64)

        def run():
            solver = BlockADMMSolver.from_kernel(
                Context(seed=12), HingeLoss(), L2Regularizer(), 0.01, s,
                Gaussian(d, sigma=3.0), num_partitions=2)
            solver.maxiter = 6
            solver.tol = 0.0
            return solver.train(X, y)

        m1 = run()
        labels, _ = m1.predict(X)
        acc = float(np.mean(np.asarray(labels).reshape(-1) == y))
        assert acc > 0.9  # separable toy problem must fit

        m2 = run()  # counter-based streams: same seed → bit-identical
        np.testing.assert_array_equal(np.asarray(m1.coef),
                                      np.asarray(m2.coef))
