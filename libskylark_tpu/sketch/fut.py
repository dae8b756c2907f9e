"""Fast unitary transforms: DCT, DHT, WHT.

TPU-native analog of the reference's FFTW/SpiralWHT plan wrappers
(ref: sketch/FUT.hpp:21-347). The reference wraps FFTW r2r plans (REDFT10 =
unnormalized DCT-II, REDFT01 = DCT-III, FFTW_DHT) and SpiralWHT; here the
transforms are XLA ops — ``jax.scipy.fft.dct`` matches FFTW's unnormalized
convention exactly, DHT is Re(FFT) − Im(FFT), and WHT is a log2(N) reshape
butterfly that XLA maps onto the VPU.

Scale convention matches the reference (ref: sketch/FUT.hpp:55-56): each FUT
exposes ``scale() = 1/sqrt(ScaleVal·N)`` with ScaleVal 2 for DCT, 1 for
DHT/WHT, making scale·F approximately orthonormal.
"""

from __future__ import annotations

import functools
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def _dct2_last(x: jnp.ndarray) -> jnp.ndarray:
    """Unnormalized DCT-II along the last axis, via Makhoul's single-FFT
    decomposition: v = [x_even, reverse(x_odd)], y_k = 2·Re(FFT(v)_k·W_k),
    W_k = exp(−iπk/2N). Written out by hand because this backend supports
    lax.fft but not jax.scipy.fft.dct's lowering."""
    n = x.shape[-1]
    v = jnp.concatenate([x[..., ::2], jnp.flip(x[..., 1::2], -1)], -1)
    V = jnp.fft.fft(v, axis=-1)
    k = jnp.arange(n, dtype=jnp.float32)
    W = jnp.exp((-1j * math.pi / (2.0 * n)) * k).astype(V.dtype)
    return (2.0 * (V * W).real).astype(x.dtype)


def _dct3_last(y: jnp.ndarray) -> jnp.ndarray:
    """Unnormalized DCT-III (FFTW REDFT01) along the last axis — the exact
    inverse of :func:`_dct2_last` up to the FFTW 2N factor."""
    n = y.shape[-1]
    yr = jnp.concatenate(
        [jnp.zeros_like(y[..., :1]), jnp.flip(y[..., 1:], -1)], -1)
    k = jnp.arange(n, dtype=jnp.float32)
    W = jnp.exp((1j * math.pi / (2.0 * n)) * k)
    V = 0.5 * (y - 1j * yr).astype(W.dtype) * W
    v = jnp.fft.ifft(V, axis=-1).real.astype(y.dtype)
    m = (n + 1) // 2
    x = jnp.zeros_like(y)
    x = x.at[..., ::2].set(v[..., :m])
    x = x.at[..., 1::2].set(jnp.flip(v[..., m:], -1))
    return 2.0 * n * x


@partial(jax.jit, static_argnames="axis")
def dct(A: jnp.ndarray, axis: int = 0) -> jnp.ndarray:
    """Unnormalized DCT-II (FFTW REDFT10 analog).

    Jitted: the whole transform is one program per shape, with the
    complex twiddle factors baked in as constants."""
    return jnp.moveaxis(_dct2_last(jnp.moveaxis(A, axis, -1)), -1, axis)


@partial(jax.jit, static_argnames="axis")
def idct(A: jnp.ndarray, axis: int = 0) -> jnp.ndarray:
    """Unnormalized DCT-III = FFTW REDFT01 (inverse of REDFT10 up to 2N)."""
    return jnp.moveaxis(_dct3_last(jnp.moveaxis(A, axis, -1)), -1, axis)


def dht(A: jnp.ndarray, axis: int = 0) -> jnp.ndarray:
    """Discrete Hartley transform (FFTW_DHT analog): cas-kernel, self-inverse
    up to N."""
    F = jnp.fft.fft(A, axis=axis)
    return jnp.real(F) - jnp.imag(F)


# Transform length at which the WHT switches from the VPU butterfly to
# the kron-factored matmul formulation (H_N = H_a ⊗ H_b, a·b = N): two
# dense contractions against small ±1 Hadamard factors that run on the
# MXU. N(√N+√N) MXU FLOPs beat N·log2(N) VPU passes (each a strided
# reshape across the whole array) well before N = 512 on TPU; the two
# paths are exact-arithmetic-identical (±1 entries, f32 adds).
_MATMUL_MIN_N = 512


@functools.lru_cache(maxsize=None)
def _hadamard_np(n: int):
    """Dense Sylvester Hadamard H_n (±1, natural ordering), n = 2^k."""
    H = np.ones((1, 1), np.float32)
    while H.shape[0] < n:
        H = np.block([[H, H], [H, -H]])
    return H


@functools.partial(jax.jit, static_argnames=("axis", "precision"))
def _wht_matmul(A: jnp.ndarray, axis: int, precision=None) -> jnp.ndarray:
    """WHT along ``axis`` as H_a · X · H_b over the (a, b)-folded axis.

    Sylvester ordering is kron-associative (H_{2^k} = H_2^{⊗k}), so for
    any split a·b = N, row-major folding x[p·b+q] = X[p, q] gives
    (H_a ⊗ H_b)x = vec(H_a X H_bᵀ); H is symmetric, hence H_a X H_b.
    Jitted so the Hadamard factors are baked into the program as
    constants.

    ``precision`` threads to the contractions; None inherits the
    ambient policy (the library-wide HIGHEST default / the
    SKYLARK_MATMUL_PRECISION knob / any ``default_matmul_precision``
    context). ``Precision.HIGH`` (TPU: 3-pass bf16) is a near-lossless
    speed regime HERE because every Hadamard entry is ±1 — exactly
    representable in bfloat16 — so the only term the 3-pass split drops
    is the X-residual×H product at ~2⁻¹⁶ relative; FastRFT opts in by
    default (see frft.py)."""
    x = jnp.moveaxis(A, axis, -1)
    n = x.shape[-1]
    k = n.bit_length() - 1
    a = 1 << (k - k // 2)
    b = 1 << (k // 2)
    Ha = jnp.asarray(_hadamard_np(a), x.dtype)
    Hb = jnp.asarray(_hadamard_np(b), x.dtype)
    X = x.reshape(x.shape[:-1] + (a, b))
    Y = jnp.einsum("ia,...ab,bj->...ij", Ha, X, Hb, precision=precision)
    return jnp.moveaxis(Y.reshape(x.shape), -1, axis)


def _wht_butterfly(A: jnp.ndarray, axis: int = 0) -> jnp.ndarray:
    """log2(N) in-register butterfly passes (the SpiralWHT shape)."""
    if axis != 0:
        return jnp.moveaxis(_wht_butterfly(jnp.moveaxis(A, axis, 0)), 0, axis)
    n = A.shape[0]
    orig_shape = A.shape
    x = A.reshape(n, -1)
    h = 1
    while h < n:
        x = x.reshape(n // (2 * h), 2, h, -1)
        a, b = x[:, 0], x[:, 1]
        x = jnp.stack([a + b, a - b], axis=1).reshape(n, -1)
        h *= 2
    return x.reshape(orig_shape)


def wht(A: jnp.ndarray, axis: int = 0, precision=None) -> jnp.ndarray:
    """Fast Walsh-Hadamard transform (natural/Hadamard ordering), N = 2^k
    (SpiralWHT analog, ref: sketch/FUT.hpp:225-347). Unnormalized,
    self-inverse up to N. Large lengths take the MXU matmul formulation
    (:func:`_wht_matmul`, ``precision`` threads to its contractions);
    small ones the VPU butterfly (exact adds; precision n/a)."""
    n = A.shape[axis]
    if n & (n - 1):
        raise ValueError(f"WHT requires power-of-2 length, got {n}")
    if n >= _MATMUL_MIN_N:
        return _wht_matmul(A, axis, precision)
    return _wht_butterfly(A, axis)


#: The promoted serve-program name for the panel-free Hadamard lowering
#: (docs/performance, "In-kernel FWHT and compressed matmul"): the SRHT
#: serve/dist/session paths contract through ``fwht`` instead of
#: materializing ``FJLT.operator_panel`` columns. Same function as
#: :func:`wht` — the alias marks the serve-surface contract: its
#: lowering (butterfly or kron matmul) must stay exact-arithmetic-
#: identical to the dense Sylvester reference ``_hadamard_np``.
fwht = wht


def sign_mix_sample(apply, A: jnp.ndarray, diag: jnp.ndarray,
                    idx: jnp.ndarray, fut_scale: float, samp_scale: float,
                    axis: int = 0) -> jnp.ndarray:
    """``samp_scale · gather(apply(fut_scale · diag ⊙ A, axis), idx)``: the
    FJLT as the plain composition sign → transform → sample, for any
    mixer ``apply(X, axis)``, the whole mixed operand in memory. ``axis``
    is the transform axis: 0 for columnwise operands ``(n, m)``, 1 for
    rowwise ``(m, n)``. What ``FJLT.apply`` runs where the compiled
    mix-and-sample program (sketch/fjlt.py) declines."""
    if axis == 0:
        return samp_scale * apply(fut_scale * diag[:, None] * A, 0)[idx, :]
    return samp_scale * apply(fut_scale * diag[None, :] * A, 1)[:, idx]


def fwht_sketch(A: jnp.ndarray, diag: jnp.ndarray, idx: jnp.ndarray,
                fut_scale: float, samp_scale: float, axis: int = 0,
                precision=None) -> jnp.ndarray:
    """:func:`sign_mix_sample` with the Walsh-Hadamard mixer: the oracle
    of the dyadic battery (tests/test_fwht.py). It is *bit-equal* to the
    ``operator_panel`` matmul reference whenever every intermediate is
    exactly representable (integer-valued operands with ``n`` and ``s``
    even powers of two), and there the compiled program
    (``fjlt.fjlt_mix_sample``, which the serve tier and ``FJLT.apply``
    run) is bit-equal to it."""
    return sign_mix_sample(partial(wht, precision=precision), A, diag, idx,
                           fut_scale, samp_scale, axis)


# ---------------------------------------------------------------------------
# the mix-and-sample contraction of a long transform axis (FJLT / SRHT)
# ---------------------------------------------------------------------------
#
# H_N = H_a ⊗ H_R over the row-major fold i = p·R + r (Sylvester ordering
# is kron-associative). A long axis is transformed in full inside each
# block of R consecutive rows (:func:`wht_blocks`: factors the MXU holds,
# the operand never transposed — the transform axis is already the fold's
# leading axes) and only the sampled rows of the last, outer factor are
# computed (:func:`sample_outer`): s rows of a·R, a signed sum of a rows
# each, instead of one more whole stage.

#: Widest Hadamard factor a stage contracts against (log2): the MXU's side.
_FACTOR_LOG2 = 7


def block_factors(block: int) -> tuple:
    """The Kronecker split of a block of ``block`` (a power of two) rows,
    outer factor first: the fewest factors of at most 2⁷, as even as they
    come (2¹⁴ → (128, 128), 2¹¹ → (64, 32))."""
    k = block.bit_length() - 1
    if block <= 0 or block != 1 << k:
        raise ValueError(f"WHT block must be a power of two, got {block}")
    stages = max(1, -(-k // _FACTOR_LOG2))
    base, extra = divmod(k, stages)
    return tuple(1 << (base + (i < extra)) for i in range(stages))


def _hadamard_stage(H: np.ndarray, X: jnp.ndarray, bf16_split: bool):
    """``einsum("ij,gjrw->girw", H, X)``, H a dense ±1 factor. Every
    entry of H is exact in bfloat16, so under ``bf16_split`` only the
    operand carries the float32: X = hi + mid + lo in bfloat16 (3 × 8
    significant bits, the whole float32 significand) and three
    single-pass products with float32 accumulation — half the MXU passes
    of a float32 ``highest`` contraction, which splits both sides. Off
    the MXU (the CPU) one float32 product."""
    if not bf16_split:
        return jnp.einsum("ij,gjrw->girw", jnp.asarray(H, X.dtype), X,
                          precision=jax.lax.Precision.HIGHEST)
    Hb = jnp.asarray(H, jnp.bfloat16)

    def bf16_part(x):
        # reduce_precision, not a cast and back: XLA may keep the excess
        # precision of a convert pair, and the split would be X + 0 + 0
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)

    hi = bf16_part(X)
    mid = bf16_part(X - hi)
    lo = X - hi - mid

    def one(part):
        return jnp.einsum("ij,gjrw->girw", Hb, part.astype(jnp.bfloat16),
                          precision=jax.lax.Precision.DEFAULT,
                          preferred_element_type=jnp.float32)

    return one(hi) + (one(mid) + one(lo))


def wht_blocks(X: jnp.ndarray, block: int, bf16_split: bool = False):
    """Unnormalized WHT of X (N, w) along axis 0 inside each block of
    ``block`` consecutive rows (``block`` divides N): one contraction a
    factor of :func:`block_factors`, each against the fold's own leading
    axes — no ``moveaxis``, no transposed copy."""
    n, w = X.shape
    inner = block
    for f in block_factors(block):
        inner //= f
        X = _hadamard_stage(_hadamard_np(f), X.reshape(-1, f, inner, w),
                            bf16_split).reshape(n, w)
    return X


#: Bytes of gathered rows :func:`sample_outer` holds at a time (on a v5e,
#: 2²⁰ × 1024 by 4096 samples: 64 MiB 5.3 ms, 256 MiB 8.3).
_SAMPLE_CHUNK_BYTES = 1 << 26


def sample_outer(Y: jnp.ndarray, idx: jnp.ndarray, block: int) -> jnp.ndarray:
    """Rows ``idx`` of ``(H_a ⊗ I) · Y`` for Y (a·block, w) already
    transformed inside its blocks: the last Kronecker factor at the
    sampled rows only. Row p·block + r of the full transform is
    Σ_q (−1)^popcount(p & q) · Y[q·block + r], so a sample costs a gather
    of a rows and their signed sum. The gathered rows are held ``chunk``
    samples at a time (≤ ``_SAMPLE_CHUNK_BYTES``)."""
    n, w = Y.shape
    a = n // block
    if a == 1:
        return Y[idx]
    s = idx.shape[0]
    shift = block.bit_length() - 1
    q = jnp.arange(a, dtype=jnp.int32)[:, None]

    def rows(ix):
        par = jax.lax.population_count((ix >> shift)[None, :] & q)
        sign = (1 - 2 * (par & 1)).astype(Y.dtype)
        # whole rows of Y as it lies (a gather along the block axis of a
        # 3-D view has XLA re-lay the operand out: one more copy of it)
        at = (q * block + (ix & (block - 1))[None, :]).reshape(-1)
        return jnp.sum(sign[:, :, None] * Y[at].reshape(a, -1, w), axis=0)

    chunk = max(8, _SAMPLE_CHUNK_BYTES // (a * w * Y.dtype.itemsize))
    if chunk >= s:
        return rows(idx)
    pad = -s % chunk
    out = jax.lax.map(rows, jnp.pad(idx, (0, pad)).reshape(-1, chunk))
    return out.reshape(-1, w)[:s]


class FUT:
    """A fast unitary transform with the reference's scale convention."""

    def __init__(self, n: int):
        self.n = int(n)

    def scale(self) -> float:
        raise NotImplementedError

    def apply(self, A: jnp.ndarray, axis: int = 0) -> jnp.ndarray:
        raise NotImplementedError

    def apply_inverse(self, A: jnp.ndarray, axis: int = 0) -> jnp.ndarray:
        raise NotImplementedError


class DCT(FUT):
    """ScaleVal=2 (ref: sketch/FUT.hpp:138-140)."""

    name = "dct"

    def scale(self) -> float:
        return 1.0 / math.sqrt(2.0 * self.n)

    def apply(self, A, axis=0):
        return dct(A, axis)

    def apply_inverse(self, A, axis=0):
        return idct(A, axis)


class DHT(FUT):
    """ScaleVal=1 (ref: sketch/FUT.hpp:142-143)."""

    name = "dht"

    def scale(self) -> float:
        return 1.0 / math.sqrt(self.n)

    def apply(self, A, axis=0):
        return dht(A, axis)

    apply_inverse = apply


class WHT(FUT):
    """Walsh-Hadamard; requires power-of-2 n (ref: sketch/FUT.hpp:225-347)."""

    name = "wht"

    def scale(self) -> float:
        return 1.0 / math.sqrt(self.n)

    def apply(self, A, axis=0, precision=None):
        return wht(A, axis, precision)

    apply_inverse = apply


_FUTS = {"dct": DCT, "dht": DHT, "wht": WHT}


def make_fut(name: str, n: int) -> FUT:
    return _FUTS[name](n)
