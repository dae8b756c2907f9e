"""Sketch-layer tests: dense (JLT/CT), hash (CWT/MMT/WZT), UST, RFT/RLT.

Test strategy mirrors the reference (SURVEY.md §4):
- Oracle = redundant computation: sharded apply vs single-device apply with
  the same (seed, counter) must agree elementwise ≤ 1e-4
  (ref: tests/unit/DenseSketchApplyElementalTest.cpp:44-101, test_utils.hpp:48).
- Property tests: σᵢ(SA) ∈ (1±0.5)·σᵢ(A) for subspace-embedding transforms
  (ref: tests/regression/svd_test.py:35-65).
- Round-trip: serialize → deserialize → identical apply
  (ref: tests/unit/SerializationTest.cpp).
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from libskylark_tpu import Context
from libskylark_tpu import parallel as par
from libskylark_tpu import sketch as sk

ATOL = 1e-4  # the reference's oracle tolerance (test_utils.hpp:48)


def _rand(m, n, seed=0):
    return np.random.default_rng(seed).standard_normal((m, n)).astype(np.float32)


# (transform factory, oracle atol). The reference's 1e-4 oracle threshold
# (tests/unit/test_utils.hpp:48) is an f64 bound; heavy-tailed frequency
# draws (LaplacianRFT's Cauchy W can land |W|~1e3+) legitimately amplify
# f32 partial-sum reorder to a few 1e-4, so those entries carry a
# conditioning-scaled tolerance. ExpSemigroupRLT is the other amplifier:
# its features are e^w with w up to ~30, so an f32 reorder wobble δ in w
# lands as relative output error ≈ δ·|w|.
ALL_TRANSFORMS = [
    (lambda N, S, ctx: sk.JLT(N, S, ctx), 1e-4),
    (lambda N, S, ctx: sk.CT(N, S, ctx, C=2.0), 1e-4),
    (lambda N, S, ctx: sk.CWT(N, S, ctx), 1e-4),
    (lambda N, S, ctx: sk.MMT(N, S, ctx), 1e-4),
    (lambda N, S, ctx: sk.WZT(N, S, ctx, p=1.5), 1e-4),
    (lambda N, S, ctx: sk.UST(N, S, ctx, replace=True), 1e-4),
    (lambda N, S, ctx: sk.UST(N, S, ctx, replace=False), 1e-4),
    (lambda N, S, ctx: sk.GaussianRFT(N, S, ctx, sigma=2.0), 1e-4),
    (lambda N, S, ctx: sk.LaplacianRFT(N, S, ctx, sigma=2.0), 1e-3),
    (lambda N, S, ctx: sk.MaternRFT(N, S, ctx, nu=1.5, l=2.0), 1e-4),
    (lambda N, S, ctx: sk.ExpSemigroupRLT(N, S, ctx, beta=0.5), 1e-3),
]


class TestApplyShapes:
    @pytest.mark.parametrize("make,atol", ALL_TRANSFORMS)
    def test_shapes_both_dims(self, make, atol):
        N, S, m = 64, 16, 8
        T = make(N, S, Context(seed=3))
        A_col = jnp.asarray(_rand(N, m))
        out = T.apply(A_col, sk.COLUMNWISE)
        assert out.shape == (S, m)
        A_row = jnp.asarray(_rand(m, N))
        out = T.apply(A_row, sk.ROWWISE)
        assert out.shape == (m, S)

    def test_dimension_mismatch_raises(self):
        T = sk.JLT(64, 16, Context(0))
        with pytest.raises(Exception):
            T.apply(jnp.zeros((32, 4)), sk.COLUMNWISE)


class TestShardedOracle:
    """Sharded apply == local apply at the same (seed, counter)."""

    @pytest.mark.parametrize("make,atol", ALL_TRANSFORMS)
    def test_rowsharded_columnwise(self, make, atol, mesh1d):
        N, S, m = 128, 32, 16
        A = _rand(N, m, seed=1)
        T = make(N, S, Context(seed=7))
        local = np.asarray(T.apply(jnp.asarray(A), sk.COLUMNWISE))
        A_sharded = par.distribute(A, par.row_sharded(mesh1d))
        sharded = np.asarray(T.apply(A_sharded, sk.COLUMNWISE))
        # the per-transform tolerance scales rtol too: the amplifying
        # transforms' error is relative to huge feature values, where
        # any atol is a no-op
        np.testing.assert_allclose(sharded, local, atol=max(ATOL, atol),
                                   rtol=max(1e-4, atol))

    @pytest.mark.parametrize("make,atol", ALL_TRANSFORMS[:6])
    def test_grid2d_rowwise(self, make, atol, mesh2d):
        N, S, m = 128, 32, 16
        A = _rand(m, N, seed=2)
        T = make(N, S, Context(seed=7))
        local = np.asarray(T.apply(jnp.asarray(A), sk.ROWWISE))
        A_sharded = par.distribute(A, par.grid2d(mesh2d))
        sharded = np.asarray(T.apply(A_sharded, sk.ROWWISE))
        np.testing.assert_allclose(sharded, local, atol=max(ATOL, atol),
                                   rtol=1e-4)

    def test_jit_apply(self):
        """apply() is jittable end-to-end (generation traced into XLA)."""
        T = sk.JLT(64, 16, Context(5))
        A = jnp.asarray(_rand(64, 8))
        eager = T.apply(A, sk.COLUMNWISE)
        jitted = jax.jit(lambda x: T.apply(x, sk.COLUMNWISE))(A)
        np.testing.assert_allclose(np.asarray(jitted), np.asarray(eager), atol=1e-5)


class TestBlockedApply:
    def test_blocked_matches_unblocked(self):
        """The memory-bounded scan path (traced block ids) equals the fused
        path — analog of the reference's 3-regime equivalence."""
        N, S, m = 1024, 32, 8
        A_col = jnp.asarray(_rand(N, m, seed=3))
        A_row = jnp.asarray(_rand(m, N, seed=4))
        T = sk.JLT(N, S, Context(seed=11))
        plain_c = np.asarray(T.apply(A_col, sk.COLUMNWISE))
        plain_r = np.asarray(T.apply(A_row, sk.ROWWISE))
        sk.params.set_blocksize(512)
        try:
            blocked_c = np.asarray(T.apply(A_col, sk.COLUMNWISE))
            blocked_r = np.asarray(T.apply(A_row, sk.ROWWISE))
        finally:
            sk.params.set_blocksize(0)
        np.testing.assert_allclose(blocked_c, plain_c, atol=ATOL)
        np.testing.assert_allclose(blocked_r, plain_r, atol=ATOL)

    def test_blocked_with_remainder(self):
        N, S, m = 700, 16, 4  # 700 not divisible by panel size
        A = jnp.asarray(_rand(N, m, seed=5))
        T = sk.CT(N, S, Context(seed=13))
        plain = np.asarray(T.apply(A, sk.COLUMNWISE))
        sk.params.set_blocksize(256)
        try:
            blocked = np.asarray(T.apply(A, sk.COLUMNWISE))
        finally:
            sk.params.set_blocksize(0)
        # Cauchy entries are heavy-tailed; allow relative slack for the
        # different reduction order of the scan path.
        np.testing.assert_allclose(blocked, plain, atol=1e-3, rtol=1e-4)


class TestHashAgainstExplicit:
    """Hash sketches equal the explicit sparse S built from their streams."""

    @pytest.mark.parametrize(
        "cls,kw", [(sk.CWT, {}), (sk.MMT, {}), (sk.WZT, {"p": 1.2})]
    )
    def test_explicit_matrix(self, cls, kw):
        N, S, m = 96, 24, 8
        T = cls(N, S, Context(seed=17), **kw)
        h = np.asarray(T.bucket_indices())
        v = np.asarray(T.values())
        S_mat = np.zeros((S, N), np.float32)
        S_mat[h, np.arange(N)] = v
        A = _rand(N, m, seed=6)
        got = np.asarray(T.apply(jnp.asarray(A), sk.COLUMNWISE))
        np.testing.assert_allclose(got, S_mat @ A, atol=ATOL, rtol=1e-4)
        B = _rand(m, N, seed=7)
        got_r = np.asarray(T.apply(jnp.asarray(B), sk.ROWWISE))
        np.testing.assert_allclose(got_r, B @ S_mat.T, atol=ATOL, rtol=1e-4)

    def test_cwt_values_are_signs(self):
        T = sk.CWT(50, 10, Context(19))
        v = np.asarray(T.values())
        assert set(np.unique(v)) <= {-1.0, 1.0}


class TestUST:
    def test_rows_are_samples(self):
        N, S, m = 40, 10, 5
        A = _rand(N, m, seed=8)
        T = sk.UST(N, S, Context(23), replace=True)
        idx = np.asarray(T.sample_indices())
        got = np.asarray(T.apply(jnp.asarray(A), sk.COLUMNWISE))
        np.testing.assert_array_equal(got, A[idx, :])

    def test_without_replacement_unique(self):
        T = sk.UST(40, 30, Context(29), replace=False)
        idx = np.asarray(T.sample_indices())
        assert len(np.unique(idx)) == 30
        assert idx.min() >= 0 and idx.max() < 40


class TestSpectralProperty:
    """σᵢ(SA) ∈ (1±0.5)·σᵢ(A) with sketch size R = N_cols/ε², averaged over
    repeats (ref: tests/regression/svd_test.py:35-65, ε=0.5)."""

    @pytest.mark.parametrize("cls", [sk.JLT, sk.CWT])
    def test_subspace_embedding(self, cls):
        eps = 0.5
        n, d = 400, 10
        R = int(d / (eps * eps) * 4)  # comfortably above d/eps^2
        A = _rand(n, d, seed=9)
        sv_a = np.linalg.svd(A, compute_uv=False)
        ok = 0
        reps = 5
        for rep in range(reps):
            T = cls(n, R, Context(seed=100 + rep))
            SA = np.asarray(T.apply(jnp.asarray(A), sk.COLUMNWISE))
            sv = np.linalg.svd(SA, compute_uv=False)
            if ((sv >= (1 - eps) * sv_a) & (sv <= (1 + eps) * sv_a)).all():
                ok += 1
        assert ok >= 4, f"embedding bound failed in {reps-ok}/{reps} repeats"


class TestKernelApproximation:
    def test_gaussian_rft_approximates_kernel(self):
        """z(x)ᵀz(y) ≈ exp(-‖x-y‖²/(2σ²)) — the defining property of
        Rahimi-Recht features (ref: ml/kernels.hpp gaussian_t)."""
        d, S, sigma = 8, 4096, 2.0
        rng = np.random.default_rng(10)
        X = rng.standard_normal((d, 6)).astype(np.float32)
        T = sk.GaussianRFT(d, S, Context(31), sigma=sigma)
        Z = np.asarray(T.apply(jnp.asarray(X), sk.COLUMNWISE))
        approx = Z.T @ Z
        d2 = ((X[:, :, None] - X[:, None, :]) ** 2).sum(axis=0)
        exact = np.exp(-d2 / (2 * sigma * sigma))
        np.testing.assert_allclose(approx, exact, atol=0.08)

    def test_rlt_positive(self):
        T = sk.ExpSemigroupRLT(8, 64, Context(37), beta=0.5)
        X = np.abs(_rand(8, 5, seed=11))  # semigroup kernels live on R+
        Z = np.asarray(T.apply(jnp.asarray(X), sk.COLUMNWISE))
        # exp(-Wx) with heavy-tailed Levy W underflows to 0 for large draws
        assert (Z >= 0).all() and np.isfinite(Z).all() and (Z > 0).any()


class TestSerialization:
    @pytest.mark.parametrize("make,atol", ALL_TRANSFORMS)
    def test_roundtrip_identical_apply(self, make, atol):
        N, S, m = 64, 16, 4
        T = make(N, S, Context(seed=41))
        T2 = sk.deserialize_sketch(json.loads(T.to_json()))
        assert T2.sketch_type == T.sketch_type
        A = jnp.asarray(_rand(N, m, seed=12))
        a1 = np.asarray(T.apply(A, sk.COLUMNWISE))
        a2 = np.asarray(T2.apply(A, sk.COLUMNWISE))
        np.testing.assert_array_equal(a1, a2)

    def test_schema_fields(self):
        T = sk.JLT(10, 5, Context(seed=43))
        d = T.to_dict()
        assert d["skylark_object_type"] == "sketch"
        assert d["sketch_type"] == "JLT"
        assert d["N"] == 10 and d["S"] == 5
        assert "seed" in d["creation_context"]

    def test_unknown_type_raises(self):
        with pytest.raises(Exception, match="unknown sketch type"):
            sk.deserialize_sketch({"sketch_type": "NOPE", "N": 1, "S": 1,
                                   "creation_context": {"seed": 0, "counter": 0}})

    def test_context_advances_distinct_transforms(self):
        ctx = Context(seed=47)
        T1 = sk.JLT(32, 8, ctx)
        T2 = sk.JLT(32, 8, ctx)
        A = jnp.asarray(_rand(32, 4, seed=13))
        a1 = np.asarray(T1.apply(A, sk.COLUMNWISE))
        a2 = np.asarray(T2.apply(A, sk.COLUMNWISE))
        assert not np.allclose(a1, a2)


class TestStreamFormatGate:
    def test_missing_format_field_rejected(self):
        """Pre-versioning serializations carry the legacy stream layout and
        must be rejected (review regression)."""
        import json as _json

        T = sk.JLT(64, 8, Context(seed=1))
        d = _json.loads(T.to_json())
        del d["stream_format"]
        with pytest.raises(Exception, match="stream format"):
            sk.deserialize_sketch(d)

    @pytest.mark.parametrize("fmt", [1, 2])
    def test_stale_format_rejected(self, fmt):
        """Format 2 drew its chunk streams through jax.random's samplers;
        format 3 (explicit threefry ops) must refuse it."""
        import json as _json

        T = sk.JLT(64, 8, Context(seed=1))
        d = _json.loads(T.to_json())
        assert d["stream_format"] == 3
        d["stream_format"] = fmt
        with pytest.raises(Exception, match="stream format"):
            sk.deserialize_sketch(d)


class TestMaterialize:
    def test_materialized_apply_matches_virtual(self):
        """materialize() pins S and takes the one-gemm path; results must
        equal the virtual-operator apply to the oracle (identical entries
        by construction; only contraction scheduling differs)."""
        import numpy as np

        from libskylark_tpu.sketch import JLT, ROWWISE, COLUMNWISE

        n, s, m = 512, 64, 40
        T = JLT(n, s, Context(seed=61))
        rng = np.random.default_rng(6)
        A_r = jnp.asarray(rng.standard_normal((m, n)), jnp.float32)
        A_c = jnp.asarray(rng.standard_normal((n, m)), jnp.float32)
        want_r = np.asarray(T.apply(A_r, ROWWISE))
        want_c = np.asarray(T.apply(A_c, COLUMNWISE))
        T.materialize()
        assert T._op_cache is not None
        np.testing.assert_allclose(np.asarray(T.apply(A_r, ROWWISE)),
                                   want_r, atol=1e-4, rtol=1e-4)
        np.testing.assert_allclose(np.asarray(T.apply(A_c, COLUMNWISE)),
                                   want_c, atol=1e-4, rtol=1e-4)
        T.dematerialize()
        assert T._op_cache is None

    def test_materialized_sparse_apply_matches_virtual(self):
        """Sparse operands take the cached-gemm path too."""
        import numpy as np
        import scipy.sparse as sp

        from libskylark_tpu.base.sparse import SparseMatrix
        from libskylark_tpu.sketch import JLT, ROWWISE

        n, s, m = 512, 48, 30
        T = JLT(n, s, Context(seed=63))
        A = SparseMatrix.from_scipy(sp.random(
            m, n, density=0.1, random_state=np.random.default_rng(7),
            format="csc", dtype=np.float32))
        want = np.asarray(T.apply(A, ROWWISE))
        T.materialize()
        np.testing.assert_allclose(np.asarray(T.apply(A, ROWWISE)), want,
                                   atol=1e-4, rtol=1e-4)

    def test_wider_dtype_bypasses_cache(self):
        """An apply in a dtype WIDER than the cache must regenerate, not
        upcast the truncated cache (f64 parity under jax x64 — QRFT's W
        is host-f64; upcasting an f32 cache would silently degrade it)."""
        from libskylark_tpu.sketch import JLT

        T = JLT(128, 16, Context(seed=65)).materialize()  # f32 cache
        assert T._cached_op(jnp.float32) is not None
        assert T._cached_op(jnp.float64) is None
        assert T._cached_op(jnp.bfloat16) is not None  # narrower: cast ok

    def test_rft_materialize_matches_virtual(self):
        """RFT pins its frequency matrix W through the same OperatorCache
        protocol; featurized outputs must match the virtual path."""
        import numpy as np

        from libskylark_tpu.sketch import ROWWISE, COLUMNWISE
        from libskylark_tpu.sketch.rft import GaussianRFT

        n, s, m = 512, 64, 24
        T = GaussianRFT(n, s, Context(seed=64), sigma=2.0)
        rng = np.random.default_rng(8)
        A_r = jnp.asarray(rng.standard_normal((m, n)), jnp.float32)
        A_c = jnp.asarray(rng.standard_normal((n, m)), jnp.float32)
        want_r = np.asarray(T.apply(A_r, ROWWISE))
        want_c = np.asarray(T.apply(A_c, COLUMNWISE))
        T.materialize()
        np.testing.assert_allclose(np.asarray(T.apply(A_r, ROWWISE)),
                                   want_r, atol=1e-4, rtol=1e-4)
        np.testing.assert_allclose(np.asarray(T.apply(A_c, COLUMNWISE)),
                                   want_c, atol=1e-4, rtol=1e-4)
        # sparse operands take the cached-W path too
        import scipy.sparse as sp

        from libskylark_tpu.base.sparse import SparseMatrix

        As = SparseMatrix.from_scipy(sp.random(
            16, n, density=0.1, random_state=np.random.default_rng(9),
            format="csc", dtype=np.float32))
        T.dematerialize()
        want_s = np.asarray(T.apply(As, ROWWISE))
        T.materialize()
        np.testing.assert_allclose(np.asarray(T.apply(As, ROWWISE)),
                                   want_s, atol=1e-4, rtol=1e-4)
        Asc = SparseMatrix.from_scipy(sp.random(
            n, 16, density=0.1, random_state=np.random.default_rng(10),
            format="csc", dtype=np.float32))
        T.dematerialize()
        want_sc = np.asarray(T.apply(Asc, COLUMNWISE))
        T.materialize()
        np.testing.assert_allclose(np.asarray(T.apply(Asc, COLUMNWISE)),
                                   want_sc, atol=1e-4, rtol=1e-4)

    def test_cache_not_serialized(self):
        """The cache is runtime state: serialize/deserialize round-trips
        the (seed, counter) definition only."""
        import json as _json

        from libskylark_tpu import sketch as sk
        from libskylark_tpu.sketch import JLT

        T = JLT(256, 32, Context(seed=62)).materialize()
        payload = T.to_dict()
        assert "cache" not in _json.dumps(payload).lower()
        T2 = sk.deserialize_sketch(payload)
        assert T2._op_cache is None
