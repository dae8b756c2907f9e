"""Fused Fastfood kernel oracles.

Interpret-mode tests pin the kernel's EXACT semantics against the XLA
chain (`FastRFT._features_rows`) on CPU — same diagonals, permutations,
block order, truncation, cos featurization — so chip time goes to
Mosaic compilation, not semantics. The @tpu test compiles each variant
on the chip."""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from libskylark_tpu.base.context import Context
from libskylark_tpu.sketch import pallas_fastfood as pf
from libskylark_tpu.sketch.frft import FastGaussianRFT, FastMaternRFT


def _X(m, d, seed=0, scale=0.3):
    return jnp.asarray(
        np.random.default_rng(seed).standard_normal((m, d)) * scale,
        jnp.float32)


def _oracle(T, X):
    """The XLA chain is the semantic definition (its own correctness is
    pinned by the explicit-operator oracle in test_sketch_fast.py)."""
    return np.asarray(T._features_rows(X), np.float64)


class TestInterpretOracle:
    @pytest.mark.parametrize("m,d,s", [
        (32, 512, 512),     # single block, no padding
        (32, 512, 1536),    # THREE blocks (block-major order + perms)
        (24, 300, 512),     # d < NB: column padding
        (19, 512, 700),     # ragged rows (row padding) + truncation
    ])
    def test_matches_xla_chain(self, m, d, s):
        T = FastGaussianRFT(d, s, Context(seed=8), sigma=2.5)
        X = _X(m, d, seed=m)
        got = pf.features_rows(T, X, interpret=True, precision="f32")
        assert got is not None and got.shape == (m, s)
        np.testing.assert_allclose(np.asarray(got), _oracle(T, X),
                                   atol=1e-4, rtol=1e-4)

    def test_matern_sm_diagonal(self):
        T = FastMaternRFT(512, 1024, Context(seed=9), nu=1.5, l=2.0)
        X = _X(16, 512, seed=3)
        got = pf.features_rows(T, X, interpret=True, precision="f32")
        np.testing.assert_allclose(np.asarray(got), _oracle(T, X),
                                   atol=1e-4, rtol=1e-4)

    def test_bf16x3_regime_stays_in_oracle(self):
        """The shipping contraction regime: ±1 Hadamard factors are
        bf16-exact, so the 3-pass split must stay f32-grade through the
        DOUBLE WHT (error compounds across the two transforms)."""
        T = FastGaussianRFT(512, 512, Context(seed=11), sigma=2.0)
        X = _X(32, 512, seed=5)
        got = pf.features_rows(T, X, interpret=True, precision="bf16x3")
        np.testing.assert_allclose(np.asarray(got), _oracle(T, X),
                                   atol=1e-4, rtol=1e-4)

    @pytest.mark.parametrize("m,d,s", [
        (32, 512, 512),
        (24, 300, 1536),    # padding + multi-block through the split
    ])
    def test_split_variant_matches_xla_chain(self, m, d, s):
        """The two-kernel variant (XLA gather between VMEM stages — for
        where Mosaic rejects the fused kernel's in-kernel gather) must
        satisfy the same oracle."""
        T = FastGaussianRFT(d, s, Context(seed=8), sigma=2.5)
        X = _X(m, d, seed=m + 1)
        got = pf.features_rows(T, X, interpret=True, precision="f32",
                               variant="split")
        assert got is not None and pf.last_served_variant == "split"
        np.testing.assert_allclose(np.asarray(got), _oracle(T, X),
                                   atol=1e-4, rtol=1e-4)

    def test_variants_agree_bitwise_class(self):
        """Fused and split compute the same chain; at f32 regime the
        two must agree to float-roundoff (the gather position is the
        only structural difference and it is exact)."""
        T = FastGaussianRFT(512, 1024, Context(seed=14))
        X = _X(16, 512, seed=2)
        a = pf.features_rows(T, X, interpret=True, precision="f32",
                             variant="fused")
        b = pf.features_rows(T, X, interpret=True, precision="f32",
                             variant="split")
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-6, rtol=1e-6)

    def test_wht2_bf16x3_remap_is_bit_identical(self):
        """_wht2 remaps bf16x3 → bf16gen2 (2 passes) on the claim that
        the ±1 Hadamard operand's bf16 lo-term is identically zero, so
        bf16x3's middle pass contributes exact zeros. Pin it: force the
        un-remapped 3-pass split through _dot directly and require BIT
        equality with _wht2's remapped result (review finding — the
        claim held only in a docstring)."""
        from libskylark_tpu.sketch.fut import _hadamard_np
        from libskylark_tpu.sketch.pallas_dense import _dot
        from libskylark_tpu.sketch.pallas_fastfood import (_wht2,
                                                           _wht_split)

        mt, NB = 8, 1024
        a, b = _wht_split(NB)
        Ha = jnp.asarray(_hadamard_np(a), jnp.float32)
        Hb = jnp.asarray(_hadamard_np(b), jnp.float32)
        W = jnp.asarray(
            np.random.default_rng(6).standard_normal((mt, NB)),
            jnp.float32)
        got = _wht2(W, Ha, Hb, mt, a, b, "bf16x3")  # remapped to gen2
        dims = (((1,), (0,)), ((), ()))
        Z = _dot(W.reshape(mt * a, b), Hb, dims,
                 "bf16x3").reshape(mt, a, b)
        Zt = jnp.swapaxes(Z, 1, 2)
        Y = _dot(Zt.reshape(mt * b, a), Ha, dims,
                 "bf16x3").reshape(mt, b, a)
        want = jnp.swapaxes(Y, 1, 2).reshape(mt, NB)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @pytest.mark.parametrize("variant", ["Split", "auto", "planned"])
    def test_invalid_variant_raises_valueerror(self, variant):
        """"fused" or "split", given by the caller: nothing else — the
        cache-steered "auto" and "planned" went with the cache."""
        T = FastGaussianRFT(512, 512, Context(seed=15))
        with pytest.raises(ValueError, match="variant"):
            pf.features_rows(T, _X(8, 512), interpret=True,
                             variant=variant)

    def test_deterministic_across_calls(self):
        T = FastGaussianRFT(512, 512, Context(seed=12))
        X = _X(16, 512, seed=7)
        a = pf.features_rows(T, X, interpret=True)
        b = pf.features_rows(T, X, interpret=True)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_kernel_approximates_gaussian_kernel(self):
        """End-to-end sanity at MC rate — same oracle class as the
        on-chip battery's Fastfood test."""
        d, s, m, sigma = 64, 2048, 12, 3.0
        X = _X(m, d, seed=4)
        T = FastGaussianRFT(d, s, Context(seed=8), sigma=sigma)
        F = np.asarray(
            pf.features_rows(T, X, interpret=True), np.float64)
        got = F @ F.T
        Xn = np.asarray(X, np.float64)
        d2 = ((Xn[:, None, :] - Xn[None, :, :]) ** 2).sum(-1)
        want = np.exp(-d2 / (2 * sigma * sigma))
        assert np.max(np.abs(got - want)) < 0.15


class TestDispatch:
    def test_declines_off_tpu_and_falls_back(self):
        """On the CPU backend supported() is False: the public apply
        must transparently take the XLA chain (and the kernel path must
        return None rather than raise)."""
        from libskylark_tpu.sketch import ROWWISE

        T = FastGaussianRFT(512, 512, Context(seed=13))
        X = _X(8, 512, seed=9)
        assert pf.features_rows(T, X) is None
        out = T.apply(X, ROWWISE)  # dispatch falls through, no error
        np.testing.assert_allclose(np.asarray(out), _oracle(T, X),
                                   atol=1e-4, rtol=1e-4)

    def test_declines_dct_core_and_small_nb(self):
        X = _X(8, 300, seed=1)
        assert not pf.supported(
            FastGaussianRFT(300, 512, Context(seed=2), fut="dct"), X)
        assert not pf.supported(
            FastGaussianRFT(64, 128, Context(seed=3)), _X(8, 64))

    def test_plan_m_tile_respects_budget(self):
        mt = pf.plan_m_tile(4096, 1 << 20)
        assert mt is not None and mt % 8 == 0
        assert mt * 4096 * 4 * 8 <= pf._VMEM_BUDGET_BYTES
        assert pf.plan_m_tile(1 << 22, 128) is None  # absurd NB declines


ON_TPU = (pf.available()
          or os.environ.get("SKYLARK_BATTERY_FORCE") == "1")


@pytest.mark.tpu
@pytest.mark.skipif(not ON_TPU, reason="needs a real TPU backend")
class TestOnChip:
    # strict: the day a JAX upgrade lowers a variant, the unexpected
    # pass fails the tier and someone puts the kernel back on a dispatch
    @pytest.mark.xfail(strict=True, reason=(
        "Mosaic rejects both variants on a TPU v5e, jax 0.9.0 (PR 21): "
        "fused — 'Shape mismatch in input, indices and output' (the "
        "lane gather); split — 'infer-vector-layout: unsupported shape "
        "cast' (tpu.reshape 64x2048 -> 4096x32 in _wht2)"))
    @pytest.mark.parametrize("variant", ["fused", "split"])
    def test_mosaic_compiles_and_matches_host_oracle(self, variant):
        """Real Mosaic lowering of each variant, compared to the
        HOST-side explicit chain. A rejection raises with Mosaic's
        message."""
        d, s, m = 2048, 2048, 64
        T = FastGaussianRFT(d, s, Context(seed=21), sigma=2.0)
        X = _X(m, d, seed=17)
        got = pf.features_rows(T, X, precision="bf16x3", variant=variant)
        if got is None and not pf.available():
            pytest.skip("kernel declined: no TPU pallas backend")
        assert got is not None and pf.last_served_variant == variant
        np.testing.assert_allclose(np.asarray(got), _oracle(T, X),
                                   atol=1e-4, rtol=1e-4)


class TestFastfoodExplicitCall:
    def _transform(self):
        return FastGaussianRFT(512, 512, Context(seed=9), sigma=2.0)

    def _input(self):
        return _X(32, 512, seed=3, scale=1.0)

    def test_explicit_call_reaches_kernel(self):
        """The kernel is reached by an explicit call and by nothing
        else — otherwise a precision sweep silently measures the XLA
        chain under a kernel label."""
        T, A = self._transform(), self._input()
        pf.last_served_variant = None
        out = pf.features_rows(T, A, interpret=True, precision="f32",
                               variant="split")
        assert out is not None and pf.last_served_variant == "split"
        ref = T._features_rows(A)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-4)

    @pytest.mark.parametrize("variant,launcher",
                             [("fused", "_launch"),
                              ("split", "_launch_split")])
    def test_requested_variant_rejection_is_loud(self, variant, launcher,
                                                 monkeypatch):
        """An explicitly requested variant that Mosaic rejects must
        raise — never turn into the other variant or the XLA chain
        silently (on the chip a silent fallback serves a different
        program than the record and the caller believe)."""
        T, A = self._transform(), self._input()
        monkeypatch.setattr(pf, "supported", lambda *a: True)
        monkeypatch.setattr(
            pf, launcher,
            lambda *a, **k: (_ for _ in ()).throw(
                RuntimeError("simulated Mosaic rejection")))
        with pytest.raises(RuntimeError, match="simulated Mosaic"):
            pf.features_rows(T, A, precision="f32", variant=variant)

    def test_transform_apply_takes_the_xla_chain(self, monkeypatch):
        """``FastRFT.apply`` does not reach the fused kernel: Mosaic
        rejects both its variants on a v5e, so the transform's own path
        is the one compiled program of ``frft.fastfood_features`` (PR 48),
        equal to the XLA chain to float32 tolerance (another summation
        order, ``cos_turns`` for the cosine)."""
        from libskylark_tpu.sketch import COLUMNWISE, ROWWISE

        def no_kernel(*a, **k):
            raise AssertionError("FastRFT.apply reached the kernel")

        monkeypatch.setattr(pf, "features_rows", no_kernel)
        T, A = self._transform(), self._input()
        ref = np.asarray(T._features_rows(A))
        tol = 2e-5 * T.scale
        np.testing.assert_allclose(np.asarray(T.apply(A, ROWWISE)), ref,
                                   rtol=0, atol=tol)
        np.testing.assert_allclose(np.asarray(T.apply(A.T, COLUMNWISE)),
                                   ref.T, rtol=0, atol=tol)
