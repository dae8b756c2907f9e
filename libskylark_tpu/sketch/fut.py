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


#: Bytes of gathered rows :func:`sample_outer` holds at a time. On a v5e,
#: 2²⁰ × 1024 by 4096 samples, device ms of the gather and its sum an apply:
#: 4.5 at 16, 32 and 64 MiB, 7.5 at 128 (PR 39 read 5.3 and, at 256 MiB, 8.3
#: by the host's clock) where the compiler gathers 128 rows a step (whole
#: index tiles: every power of two of samples × 64 rows); 256 rows a step
#: (PR 50, :func:`_sample_chunk`) 2.95 / 3.10 / 3.04 at 16 / 32 / 64 MiB — by
#: the padded samples, 11.2 ns a row at each — and 5.4 at 128: to 64 MiB the
#: compiler keeps a chunk's gathered rows on the chip (memory space ``S(1)``
#: of the compiled text; the sum reads them at 7 TB/s), past it in HBM.
_SAMPLE_CHUNK_BYTES = 1 << 26


def _sampled_in_chunks(rows, idx, chunk: int, w: int):
    """``rows(idx)`` (s, w), the samples taken ``chunk`` at a time
    (:func:`_sample_chunk`: what ``rows`` gathers for them stays about
    ``_SAMPLE_CHUNK_BYTES``), the last chunk filled up with sample 0."""
    s = idx.shape[0]
    if chunk >= s:
        return rows(idx)
    pad = -s % chunk
    out = jax.lax.map(rows, jnp.pad(idx, (0, pad)).reshape(-1, chunk))
    return out.reshape(-1, w)[:s]


def sample_outer(Y: jnp.ndarray, idx: jnp.ndarray, block: int) -> jnp.ndarray:
    """Rows ``idx`` of ``(H_a ⊗ I) · Y`` for Y (a·block, w) already
    transformed inside its blocks: the last Kronecker factor at the
    sampled rows only. Row p·block + r of the full transform is
    Σ_q (−1)^popcount(p & q) · Y[q·block + r], so a sample costs a gather
    of a rows and their signed sum. The gathered rows are held ``chunk``
    samples at a time (:func:`sample_outer_chunk`). The order of a sample's
    sum is the compiler's: on a v5e its ``reduce`` adds the a rows in runs
    whose number follows the chunk's shape (a = 64 × 1024 columns: 2 runs of
    32 at 256 samples a chunk, 16 of 4 at 264, 4 of 16 at 280, one of 64 at
    296), so another chunk is another last bit — the sum written out term by
    term keeps one order for 0.23 ms an apply and 1 s of tracing (PR 50)."""
    n, w = Y.shape
    a = n // block
    if a == 1:
        return Y[idx]
    shift = block.bit_length() - 1
    q = jnp.arange(a, dtype=jnp.int32)[:, None]

    def rows(ix):
        par = jax.lax.population_count((ix >> shift)[None, :] & q)
        sign = (1 - 2 * (par & 1)).astype(Y.dtype)
        # whole rows of Y as it lies (a gather along the block axis of a
        # 3-D view has XLA re-lay the operand out: one more copy of it)
        at = (q * block + (ix & (block - 1))[None, :]).reshape(-1)
        return jnp.sum(sign[:, :, None] * Y[at].reshape(a, -1, w), axis=0)

    chunk = sample_outer_chunk(a, w, idx.shape[0], Y.dtype.itemsize)
    return _sampled_in_chunks(rows, idx, chunk, w)


def sample_outer_chunk(a: int, w: int, s: int, itemsize: int = 4) -> int:
    """Samples, of ``s``, whose rows :func:`sample_outer` gathers at a time
    from a Y of ``a`` blocks and ``w`` columns: :func:`_sample_chunk` of the
    a rows a sample gathers (2²⁰ = 64 × 16384 rows × 1024 float32: 264, where
    the byte rule alone says 256 = sixteen whole index tiles), all of them
    where one block holds the axis or one chunk the samples."""
    return s if a == 1 else min(s, _sample_chunk(a * w * itemsize, a))


# ---------------------------------------------------------------------------
# the blocked DFT behind the DCT / DHT of a long axis (FJLT at any height)
# ---------------------------------------------------------------------------
#
# DCT-II by Makhoul: with v = [x_even, reverse(x_odd)] and V = DFT_N(v),
# y_k = 2·Re(e^{−iπk/2N}·V_k); the DHT is Re(V) − Im(V) of V = DFT_N(x).
# Cooley–Tukey over the fold j = (a·f2 + b)·R + r of the axis, N = f1·f2·R:
# with k1 = k mod f1·f2 = κ1 + f1·κ2,
#
#   V_k = Σ_r ω_N^{r·k} · Z[k1, r],
#   Z[k1, r] = Σ_b ω_{f2}^{b·κ2} ω_{f1·f2}^{b·κ1} · Σ_a ω_{f1}^{a·κ1} v[a, b, r].
#
# The stages do not mix the sampled digit r: stage one contracts a, stage two
# b, and the outer factor is a sum over r that is wanted at the s sampled
# outputs only — a sum that can be added up a few r at a time. So a pass
# takes ρ of the R slabs of r (the caller's choice, by memory) and, of the
# free axis, everything the operand's rows hold: its rows come into the
# stages in ONE gather of whole rows of the operand itself
# (:func:`dft_source_rows`: Makhoul's order and the digit a brought next to
# the free axis, which is where the MXU contracts; no slice of the operand
# is taken first: a gather of rows of 2 KB and more moves its bytes as fast
# as a copy would), their signs by the same fold of the sign vector
# (:func:`dft_source_signs`), the two inner stages (:func:`dft_blocks`, which
# see the pass as the split (ρ, f1, f2) of a shorter axis) are one dense
# contraction each and, v being real, only for κ1 ≤ f1/2 (Z[N1 − k1] =
# conj Z[k1]): ρ·f1·f2 real numbers in, as many out. The outer factor, its
# twiddles and Makhoul's are one dot of 2ρ real terms a sampled output and
# pass (:func:`sample_outer_dft`), added up over the passes: s outputs,
# never the N.
#
# Every array the stages pass between them lies on whole (8, 128) tiles of
# the chip, 8 rows by 128 free-axis entries: a digit that stands next to the
# free axis is padded to a multiple of 8 (:func:`dft_pads` — zero columns and
# rows of the factors, so zeros are added up and nothing is dropped), and
# re|im stands above such a digit, never under it. Then each fold between
# the gather, the stages and the sample is a bitcast. Where a digit of 125
# or a row (κ1, re|im) of 126 meets the 8 sublanes, or a trailing 2 the tile,
# XLA re-lays the whole tile out in a pass of its own (10⁶ × 1024 on a v5e:
# two such passes, 25 of 121 ms an apply).

#: Longest inner DFT factor (a dense f × f factor on the MXU) and longest
#: outer factor (rows gathered and summed a sampled output).
_DFT_FACTOR_MAX = 128
_DFT_OUTER_MAX = 256


@functools.lru_cache(maxsize=256)
def dft_factors(n: int):
    """The split ``(R, f1, f2)`` of a DCT / DHT axis of ``n`` that
    :func:`dft_blocks` and :func:`sample_outer_dft` serve — the sampled
    outer factor first, ``f2`` = 1 where one inner stage does — or None:
    ``n = R·f1·f2`` with 2 ≤ f1 ≤ 128, f2 ≤ 128 and R ≤ 256 (so n ≤ 2²²,
    and no prime factor past 256). Among the splits the cheapest by a count
    of what a column costs — stage one f1, stage two 2·f2 (complex on both
    sides), 2·R for the rows gathered a sample —, the smaller f2 on a tie.
    The count does not see the padding (:func:`dft_pads`: at 10⁶ the choice
    (100, 125, 80) gathers 2.4 % more rows and contracts 1.6 % more
    batches)."""
    best = None
    for f1 in range(2, min(n, _DFT_FACTOR_MAX) + 1):
        if n % f1:
            continue
        rest = n // f1
        for f2 in range(1, min(rest, _DFT_FACTOR_MAX) + 1):
            if rest % f2 or rest // f2 > _DFT_OUTER_MAX:
                continue
            r = rest // f2
            cost = (f1 + (2 * f2 if f2 > 1 else 0) + 2 * r, f2)
            if best is None or cost < best[0]:
                best = (cost, (r, f1, f2))
    return best and best[1]


def _pad_to(x: int, mult: int = 8) -> int:
    return -(-x // mult) * mult


#: The v5e compiler lays a gather's indices in tiles of 1024 and, where
#: their count ends within a step or two of a tile's end, gathers 128 rows a
#: step with a quarter of the buffers it takes otherwise (256 rows a step):
#: 12–15 ns a row against 6–8 at rows of 1 KB (10⁶ × 256 from 1,024,000 rows
#: 50 ms, from 1,000,000 24). Compiled here, the slack up to the tile's end
#: at which it still does: 0 (every whole count), 84 (16,300), 127 (16,257),
#: 192 (33,600), 255 (33,537); and no longer at 168 (32,600), 200 (16,184),
#: 256 (33,536). No rule was found for the reach; 256 is the widest seen.
_GATHER_INDEX_TILE = 1024


def _gathers_fast(count: int) -> bool:
    """Whether a gather of ``count`` whole rows surely runs 256 rows a
    step: its indices end more than 256 short of an index tile's end."""
    return -count % _GATHER_INDEX_TILE > 256


def _sample_chunk(sample_bytes: int, each: int) -> int:
    """Samples :func:`_sampled_in_chunks` takes at a time, ``each`` rows
    gathered a sample: what fits ``_SAMPLE_CHUNK_BYTES`` at ``sample_bytes``
    a sample, then the next multiple of 8 past that (at most 64 samples
    past) whose rows the compiler gathers fast (:func:`_gathers_fast`;
    10⁶ × 1024 on ρ = 50: 168 samples of 100 rows, 69 MB, not 163; 2²⁰ × 1024
    on blocks of 16384: 264 samples of 64 rows, not 256). Where ``each`` is a
    multiple of 128 every such count fills whole tiles and the byte rule's
    chunk stands."""
    chunk = _pad_to(max(8, _SAMPLE_CHUNK_BYTES // sample_bytes))
    return next((c for c in range(chunk, chunk + 64, 8)
                 if _gathers_fast(c * each)), chunk)


def dft_pads(factors: tuple) -> tuple:
    """``(f1p, hp, f2p, blocks)``: the extents the stages' arrays carry, for
    the split ``factors`` = (R, f1, f2) of an axis or (ρ, f1, f2) of a pass
    over ρ of its R slabs (only ``blocks`` follows the first factor).
    For the digits that stand next to the free axis — a of stage one's input
    (f1) and κ1 of its result (h = f1//2 + 1 values), each up to a multiple
    of 8, the rows of a tile; κ2 of stage two's result (f2) up to a multiple
    of 4, so that its rows (re | im, κ2) are whole tiles (1 where f2 = 1: no
    stage two). And ``blocks`` of slabs of stage one's input, the digit b:
    f2, or one more where the f2·ρ·f1p rows a pass gathers would fill whole
    index tiles (``_GATHER_INDEX_TILE``) and f2 + 1 blocks do not (never
    where f2 = 1). The pads are zero columns and rows of :func:`dft_tables`."""
    r, f1, f2 = factors
    f1p = _pad_to(f1)
    slab = r * f1p
    more = (f2 > 1 and not _gathers_fast(f2 * slab)
            and _gathers_fast((f2 + 1) * slab))
    return (f1p, _pad_to(f1 // 2 + 1), _pad_to(f2, 4) if f2 > 1 else 1,
            f2 + more)


@functools.lru_cache(maxsize=8)
def dft_tables(factors: tuple) -> tuple:
    """The inner stages' factors as float32 host arrays, from float64
    phases reduced in integers, zero wherever an index is a pad
    (:func:`dft_pads`): ``F1`` (2·hp, f1p), rows (re | im, κ1) of
    ω_{f1}^{a·κ1} for κ1 ≤ f1/2; and, where f2 > 1, ``T2`` (hp, 2·f2p,
    2·blocks): for each κ1 the complex factor ω_{f2}^{b·κ2}·ω_{f1·f2}^{b·κ1}
    (stage two with the twiddle between the stages folded in) as the real
    matrix [[re, −im], [im, re]] over (re | im, κ2) × (re | im, b)."""
    _, f1, f2 = factors
    f1p, hp, f2p, blocks = dft_pads(factors)
    h = f1 // 2 + 1

    def cis(phase, period):                 # e^{−2πi·phase/period}
        t = 2.0 * np.pi * (phase % period).astype(np.float64) / period
        return np.cos(t), -np.sin(t)

    k1, a = np.arange(h)[:, None], np.arange(f1)[None, :]
    F1 = np.zeros((2, hp, f1p), np.float32)
    F1[:, :h, :f1] = cis(k1 * a, f1)
    F1 = F1.reshape(2 * hp, f1p)
    if f2 == 1:
        return (F1,)
    k1 = np.arange(h)[:, None, None]
    k2, b = np.arange(f2)[None, :, None], np.arange(f2)[None, None, :]
    re, im = cis(b * k2 * f1 + b * k1, f1 * f2)
    T2 = np.zeros((hp, 2, f2p, 2, blocks), np.float32)
    T2[:h, 0, :f2, 0, :f2], T2[:h, 0, :f2, 1, :f2] = re, -im
    T2[:h, 1, :f2, 0, :f2], T2[:h, 1, :f2, 1, :f2] = im, re
    return F1, T2.reshape(hp, 2 * f2p, 2 * blocks)


def dft_source_rows(n: int, factors: tuple, mixer: str, slabs: int = 0,
                    first=0) -> jnp.ndarray:
    """The row of the operand that stands at row (b·ρ + r − first)·f1p + a
    of the stages' input for the pass over the ρ = ``slabs`` slabs r ∈
    [first, first + ρ) of the sampled digit (all R from 0 by default;
    ``first`` may be traced): int32, length blocks·ρ·f1p of
    :func:`dft_pads` (ρ, f1, f2), v[(a·f2 + b)·R + r] — the digit stage one
    contracts brought next to the free axis, in whole tiles of rows — with v
    Makhoul's order for the DCT (the even rows, then the odd ones from the
    last back) and the operand's own for the DHT. A pad names a row of the
    operand too (a ≥ f1 the slab's last again, b ≥ f2 the first block's): its
    column of the factor is zero, and every output of the transform depends
    on every row already."""
    r, f1, f2 = factors
    slabs = slabs or r
    f1p, _, _, blocks = dft_pads((slabs, f1, f2))
    i = jnp.arange(blocks * slabs * f1p, dtype=jnp.int32)
    slab = i // f1p                                     # b·ρ + r − first
    j = (jnp.minimum(i % f1p, f1 - 1) * (f2 * r) + slab // slabs % f2 * r
         + slab % slabs + first)
    if mixer != "dct":
        return j
    return jnp.where(j < (n + 1) // 2, 2 * j, 2 * (n - 1 - j) + 1)


def _makhoul_order(D: jnp.ndarray) -> jnp.ndarray:
    """A vector's even entries, then its odd ones from the last back — each
    256 entries parted (evens | odds) by an exact 0 / 1 permutation on the
    MXU: a strided slice of a vector compiles to a gather of scalars on a
    v5e, and that costs a scalar what a row gather costs a row (10⁶: 11 ms)."""
    n = D.shape[0]
    lane = np.arange(256)
    P = np.zeros((256, 256), np.float32)
    P[lane, lane // 2 + 128 * (lane % 2)] = 1.0
    E = jnp.dot(jnp.pad(D, (0, -n % 256)).reshape(-1, 256),
                jnp.asarray(P, D.dtype), precision=jax.lax.Precision.HIGHEST)
    even, odd = E[:, :128].reshape(-1), E[:, 128:].reshape(-1)
    return jnp.concatenate([even[:(n + 1) // 2], odd[:n // 2][::-1]])


def dft_source_signs(D: jnp.ndarray, factors: tuple, mixer: str,
                     slabs: int = 0) -> jnp.ndarray:
    """The entries of a vector D over the axis at the rows
    :func:`dft_source_rows` names, for every pass of ``slabs`` slabs at
    once, as (blocks, R, f1p): the slabs [first, first + ρ) of axis 1,
    flattened, are ``D[dft_source_rows(n, factors, mixer, slabs, first)]``
    with zeros at the pads. No gather (its price is above): the rows' own
    fold (a, b, r) → (b, r, a) of D itself, which a vector can afford."""
    r, f1, f2 = factors
    f1p, _, _, blocks = dft_pads((slabs or r, f1, f2))
    if mixer == "dct":
        D = _makhoul_order(D)
    return jnp.pad(D.reshape(f1, f2, r).transpose(1, 2, 0),
                   ((0, blocks - f2), (0, 0), (0, f1p - f1)))


def dft_blocks(U: jnp.ndarray, factors: tuple, tables) -> jnp.ndarray:
    """The inner stages of the DFT of a real v along axis 0, for U
    (blocks·R·f1p, w) = v in the row order of :func:`dft_source_rows` — or,
    with ``factors`` = (ρ, f1, f2) and their tables, the ρ slabs of one pass:
    the stages leave the sampled digit alone. Z for κ1 ≤ f1/2 only, as a
    (hp·R·2·f2p, w) array whose row (κ1, r, re|im, κ2) is
    ((κ1·R + r)·2 + re|im)·f2p + κ2 — with f2 = 1 a (R·2·hp, w) one, row
    (r·2 + re|im)·hp + κ1 — zero at the pads (:func:`dft_pads`).
    Both sides of each contraction carry float32 (``highest``: a DFT factor
    is not exact in bfloat16); each result is written in the order the
    contraction leaves it, the next reader's indices follow it, and every
    fold here splits or joins whole tiles of rows."""
    r, f1, f2 = factors
    f1p, hp, f2p, blocks = dft_pads(factors)
    w = U.shape[1]
    highest = jax.lax.Precision.HIGHEST
    Z = jnp.einsum("ka,xaw->xkw", jnp.asarray(tables[0]),
                   U.reshape(blocks * r, f1p, w), precision=highest)
    if f2 > 1:
        T2 = jnp.asarray(tables[1]).reshape(hp, 2 * f2p, 2, blocks)
        Z = jnp.einsum("kcpb,brpkw->krcw", T2, Z.reshape(blocks, r, 2, hp, w),
                       precision=highest)
    return Z.reshape(-1, w)


def _cis_turns(p: jnp.ndarray, period: int):
    """(cos, sin) of 2π·p/period for int32 p in [0, period), the octant
    taken in integers so that the float32 angle lies in [0, π/4]: an entry
    is right to an ulp or two of float32 whatever the period (a float32
    quotient p/period carries its rounding into the whole turn: 5e-7 of
    angle at p ≈ 10⁶·4)."""
    q = 8 * p
    octant = q // period
    rest = q - octant * period
    odd = (octant & 1) == 1
    rest = jnp.where(odd, period - rest, rest)
    t = rest.astype(jnp.float32) * jnp.float32(math.pi / (4.0 * period))
    c, s = jnp.cos(t), jnp.sin(t)
    swap = ((octant + 1) & 2) == 2                   # octants 1, 2, 5, 6
    cos = jnp.where(swap, s, c)
    sin = jnp.where(swap, c, s)
    cos = jnp.where(((octant + 2) & 4) == 4, -cos, cos)   # octants 2..5
    sin = jnp.where(octant >= 4, -sin, sin)
    return cos, sin


def sample_outer_dft(Z: jnp.ndarray, idx: jnp.ndarray, n: int,
                     factors: tuple, mixer: str, scale: float,
                     first=0) -> jnp.ndarray:
    """``scale`` · rows ``idx`` of the unnormalized DCT-II (``mixer``
    ``"dct"``; FFTW REDFT10: y_k = 2·Σ_j x_j·cos(πk(2j+1)/2N)) or DHT
    (``"dht"``) of an axis of ``n`` = R·f1·f2, from Z = :func:`dft_blocks`
    of it: the outer factor at the sampled outputs only — or, from the Z of
    the pass over the slabs r ∈ [first, first + ρ) (ρ read off Z's rows,
    ``first`` may be traced), those slabs' part of it, the passes' parts to
    be added up. Output k reads the rows r of Z[k mod f1·f2] (re and im; the
    conjugate's where κ1 > f1/2) against e^{−2πi·(r·k mod n)/n}, for the DCT
    times e^{−iπk/2n}: phases reduced in int32 (R·n < 2³¹ by the rule of
    :func:`dft_factors`), the gathered rows held ``chunk`` samples at a time
    (≤ ``_SAMPLE_CHUNK_BYTES``)."""
    _, f1, f2 = factors
    _, hp, f2p, _ = dft_pads(factors)
    under = f2p if f2 > 1 else hp
    r, w = Z.shape[0] // (2 * hp * f2p), Z.shape[1]     # the pass's slabs
    j = jnp.arange(r, dtype=jnp.int32)[:, None]

    def rows(ix):
        k1 = ix % (f1 * f2)
        ka, kb = (k1 % f1)[None, :], (k1 // f1)[None, :]
        mirrored = ka > f1 // 2              # Z[k1] = conj Z[f1·f2 − k1]
        ka = jnp.where(mirrored, f1 - ka, ka)
        kb = jnp.where(mirrored, f2 - 1 - kb, kb)
        phase = (ix[None, :] * (j + first)) % n
        if mixer == "dct":
            cos, sin = _cis_turns((4 * phase + ix[None, :]) % (4 * n), 4 * n)
            on_re, on_im = 2.0 * cos, 2.0 * sin
        else:                                # Re V − Im V
            cos, sin = _cis_turns(phase, n)
            on_re, on_im = cos + sin, sin - cos
        on_im = jnp.where(mirrored, -on_im, on_im)
        # the re rows (:func:`dft_blocks`' order), the im rows the padded
        # extent of the digit under re|im after them; the samples stand
        # next to the free axis (whole tiles of them: the fold below is a
        # bitcast whatever 2ρ is) and the sum runs over the leading axis
        at = ((ka * r + j) * 2 * under + kb) if f2 > 1 else j * 2 * under + ka
        at = jnp.concatenate([at, at + under], axis=0).reshape(-1)
        weight = jnp.float32(scale) * jnp.concatenate([on_re, on_im], axis=0)
        # whole rows of Z as it lies, multiplied and added up in float32
        return jnp.sum(weight[:, :, None] * Z[at].reshape(2 * r, -1, w),
                       axis=0)

    chunk = _sample_chunk(2 * r * w * Z.dtype.itemsize, 2 * r)
    return _sampled_in_chunks(rows, idx, chunk, w)


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
