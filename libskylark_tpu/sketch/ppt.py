"""PPT (TensorSketch) — Pham-Pagh polynomial kernel sketch.

TPU-native analog of ref: sketch/PPT_data.hpp:24-120, sketch/PPT_Elemental.hpp:16-870.
Approximates the polynomial kernel (γ·xᵀy + c)^q: q independent CountSketches
of x, each lifted by the homogeneity term √c·e_{h_i}·s_i, FFT'd, multiplied
elementwise across q, and inverse-FFT'd. The reference loops columns with
per-column FFTW plans.

An apply of a float32 operand on one device is ONE compiled program
(``sketch.tensorsketch_features``, :func:`tensorsketch_features`): a pure
function of the allocation's key words and the operand that walks the
examples in blocks of rows, so that its temporaries are a few (block × S)
arrays whatever the operand's height. In it no CountSketch is ever formed:
the spectrum of a CountSketch is a dense product,
FFT(C_k x)[κ] = Σ_j s_j x_j ω^{h_j κ} = x · (C_k F), and at N ≪ S the N × S
operator C_k F (row j is s_j times row h_j of the DFT matrix, the half
spectrum of S/2 + 1 bins as S real columns) is generated once a program
from the buckets and signs and contracted with a block on the MXU — no
scatter, no forward transform. The q spectra are multiplied in float32
complex arithmetic and the one inverse transform, half spectrum → S reals
along the feature axis, is a two-stage blocked DFT on the MXU (S = N1·N2,
the twiddles applied between the stages). Every product carries float32 on
both sides: the six bfloat16 partial products of its operands' three parts
that ``Precision.HIGHEST`` forms on a TPU, accumulated in float32. The two
stages (K = N1, 2·N2) say ``highest`` and leave the passes to the compiler.
The spectral products lay the six out themselves, side by side along K
(:func:`packed`): one bfloat16 product over 6N columns, because
the compiler's six passes each round K up to whole 128-deep MXU tiles by
themselves — at N = 784 = 6·128 + 16 that is 6 × 7 = 42 tile passes of which
36.75 carry data, where 6·784 = 4704 columns round up once, to 37.

And the product is formed by bucket class (:func:`radix`, R a power of two
up to 8, a function of N and S alone). Write a bucket h = R·h′ + p: for every
κ and every q < R, F[κ + q·S/R] = Σ_{p<R} ω_R^{pq}·T_p[κ] with the class sums
T_p[κ] = Σ_{j: h_j ≡ p (mod R)} v_j x_j ω_S^{h_jκ} — one decimation-in-time
step of the FFT, exact in exact arithmetic. So the MXU forms the R class sums
on the S/2R bins κ < S/2R alone — class p's inputs (N/R of them: K falls by
R) against the rows of C_k F whose bucket lies in class p, their columns
κ < S/2R — and the S/2 bins of the R groups [q·S/R, q·S/R + S/2R), one of
every conjugate pair, follow by R multiply-adds a bin on the VPU inside the
spectra's product: a quarter of the MXU's multiply-adds at R = 4, every bin
still the sum of all N (+ 1) terms and every partial product still one of the
six bfloat16 pairs accumulated in float32. A class is given
:func:`class_cols` operator rows, N/R and four standard deviations; the
block's examples are brought into each sketch's class order by an exact 0/1
product (:func:`class_ordered`); a group's first bin stands beside its
conjugate (or is its own: bins 0 and S/2) and takes half weight; the R
midpoint bins, in no group, are R/2 conjugate pairs whose class sums are one
real number each and join the inverse transform between its stages as the
Nyquist bin does at R = 1. A transform one of whose classes overflows its
rows takes R = 1 (:meth:`PPT.radix`), the whole product, never a dropped
input.

What the v5e compiler makes of a block (``tests/test_v5e_compile.py`` holds
it): the block's examples sliced out of the operand, ordered and packed once;
the 2R·q class sums, a class's real and imaginary columns an array each; the
spectra's product in two fusions, each storing its half of stage one's
operand in place (the first hands the pair product on to the second) — the
groups an axis of these arrays between the examples' two digits,
(B/8, R, 8, S/2R), so that either fusion reads every class sum once; stage
one, which reads that operand as the tiles of a (B, S) array lie; the
twiddles; stage two; and one fusion that turns the two digits
of t and stores the block into its rows of the result — six passes over
block-sized arrays at q = 3 (nine where R = 1: the q whole products are three
of them), every array written once and read once but the class sums and the
pair product, and no pass that only lays an array out again. That holds where
rows and row block are multiples of 8 and N1 of 128; other shapes turn a
block in a pass of its own and copy it in.

Everything else — another dtype, an operand on several devices, an S with
no such split — keeps the eager chain (:meth:`PPT._sketch_columns`: q
``cwt.apply``, ``jnp.fft`` over whole arrays), which stays the tests'
oracle.

Sub-allocations: child(i) = i-th internal CWT; sub-streams 100/101 = the
homogeneity hash (idx, val) (ref: PPT_data.hpp:100-106).
"""

from __future__ import annotations

import functools
import math
from typing import Any

import jax
import jax.numpy as jnp

from libskylark_tpu.base import randgen
from libskylark_tpu.sketch import fut as _fut
from libskylark_tpu.sketch.hash import CWT
from libskylark_tpu.sketch.rft import _ProgramAllocation
from libskylark_tpu.sketch.transform import (_REGISTRY, SketchTransform,
                                             register)
from libskylark_tpu.telemetry import metrics as _metrics
from libskylark_tpu.telemetry import trace as _trace

_ROWS = _metrics.counter(
    "sketch.tensorsketch_rows",
    "examples featurized by TensorSketch applies, by family and route")

#: Longest factor of the inverse transform's split (a dense factor on the
#: MXU), and with it the longest S the program serves (2¹⁶: the phases
#: h·κ < S²/2 stay inside int32).
_FACTOR_MAX = 256
#: Entries (rows × S) of a block of the walk: 2²⁶ is 4096 examples at
#: S = 16384, 256 MiB a stage array.
_BLOCK_ENTRIES = 1 << 26
#: Most bucket classes a spectral product is formed by (:func:`radix`).
_RADIX_MAX = 4

_HIGHEST = jax.lax.Precision.HIGHEST
#: The partial products (part of x, part of w) of a float32-grade product —
#: the six of ``Precision.HIGHEST``, every pair of parts whose orders add up
#: to at most two — in the order they lie along the packed K, the smallest
#: first: the MXU adds K up in order, and 5N small terms added to a sum that
#: already holds hi·hi round at its size, not at theirs (on a v5e 3.6e-7 of
#: the largest entry against float64 at N = 784 where this order reads
#: 1.5e-7, ``highest`` itself 1.5e-7; the time is the same).
_TERMS = ((2, 0), (0, 2), (1, 1), (1, 0), (0, 1), (0, 0))


@functools.lru_cache(maxsize=256)
def split(s: int):
    """The split ``(N1, N2)`` of the feature axis, S = N1·N2, that the
    inverse transform's two stages contract — N1 even (the half spectrum
    fills N1/2 rows of N2 bins), both at most 256, the smallest N1 + N2
    (the stages cost 4·S·(N1 + N2) multiply-adds an example), the shorter
    first — or None where S has none (an odd S, a prime factor past 256,
    S > 2¹⁶)."""
    best = None
    for n1 in range(2, min(s, _FACTOR_MAX) + 1, 2):
        if s % n1 or s // n1 > _FACTOR_MAX:
            continue
        cost = (n1 + s // n1, n1)
        if best is None or cost < best[0]:
            best = (cost, (n1, s // n1))
    return best and best[1]


def block_rows(m: int, s: int) -> int:
    """Examples a step of the walk takes: what :data:`_BLOCK_ENTRIES`
    holds at S features, a multiple of 8, at most the m there are."""
    return min(m, max(8, _BLOCK_ENTRIES // s // 8 * 8))


def _row_major(x):
    """``x`` held row-major through the compiler's layout assignment (the
    layout API imported at the first trace: importing the sketch layer pulls
    nothing of ``jax.experimental``)."""
    from jax.experimental.layout import Layout, with_layout_constraint

    return with_layout_constraint(
        x, Layout(major_to_minor=tuple(range(x.ndim))))


def _grade(x, grade: str):
    """An MXU product's operand at the program's grade: as it is
    (``"float32"``), or rounded to one bfloat16 part (``"bf16"``: the
    regime of sketch/params.py a benchmark's control runs)."""
    if grade == "float32":
        return x
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def bf16_parts(a, count: int = 3):
    """The leading ``count`` bfloat16 parts of a float32 array: hi = bf16(a),
    mid = bf16(a − hi), lo = bf16(a − hi − mid) — each difference exact in
    float32, so that the three add back up to ``a`` bit for bit (8 + 8 + 8
    bits of significand). Rounded by :func:`_grade`'s ``reduce_precision``,
    which no compiler flag folds away as it may a convert there and back."""
    parts = []
    for _ in range(count):
        part = _grade(a, "bf16")
        parts.append(part.astype(jnp.bfloat16))
        a = a - part
    return parts


def _terms(grade: str):
    """The partial products a spectral product at ``grade`` is made of: all
    of :data:`_TERMS`, or for ``"bf16"`` the one term x_hi·w_hi."""
    return _TERMS if grade == "float32" else _TERMS[-1:]


def k_tiles(n: int, grade: str = "float32", radix: int = 1) -> int:
    """128-deep MXU tile passes a spectral product of N input columns takes
    over its packed K: ⌈6N/128⌉ — never more than the 6·⌈N/128⌉ of six
    products each padded by itself, fewer wherever N's last tile is under
    five sixths full, six times fewer at N ≤ 21. Formed by bucket class
    (``radix`` R > 1) it is R products of ⌈6c/128⌉ tiles, c =
    :func:`class_cols`, each over S/R columns where the one is over S: the
    columns a tile pass feeds fall by R, the tiles do not grow by R."""
    return radix * -(-len(_terms(grade)) * class_cols(n, radix) // 128)


def class_cols(n: int, radix: int) -> int:
    """Operator rows a bucket class h ≡ p (mod R) is given, a static
    capacity: N/R and four standard deviations of a class's size (the
    buckets are uniform: binomial(N, 1/R)) in whole sublanes of 8, then as
    many more as the last 128-deep MXU tile of the six-fold packed K holds
    anyway — 784 inputs: 448 at R = 2 (21 tiles), 256 at R = 4 (196 + 4·12.1
    → 248, whose 12th tile holds 256: five standard deviations for nothing),
    144 at R = 8 (7 tiles). A transform with a fuller class takes radix 1
    (:meth:`PPT.radix`); N itself at R = 1."""
    if radix == 1:
        return n
    mean = n / radix
    cols = -(-(mean + 4.0 * math.sqrt(mean * (1.0 - 1.0 / radix))) // 8) * 8
    return int(-(-len(_TERMS) * cols // 128) * 128 // len(_TERMS) // 8 * 8)


@functools.lru_cache(maxsize=256)
def radix(n: int, s: int) -> int:
    """The number of bucket classes R the spectral products of N inputs into
    S features are formed by — a function of the shapes alone: the power of
    two up to :data:`_RADIX_MAX` whose groups are whole high digits of the
    inverse transform (2R divides N1) that takes the fewest MXU tile passes
    × columns a sketch, the products' R·⌈6c/128⌉·S/R and the class ordering's
    3·⌈N/128⌉·R·c; 1 where the classes would be mostly padding (small N) or S
    has no split."""
    if split(s) is None:
        return 1

    def passes(r):
        order = 0 if r == 1 else 3 * -(-n // 128) * r * class_cols(n, r)
        return k_tiles(n, radix=r) * (s // r) + order

    allowed = [r for r in (1, 2, 4, 8)
               if r == 1 or (r <= _RADIX_MAX and split(s)[0] % (2 * r) == 0
                             and r * class_cols(n, r) <= 2 * n)]
    return min(allowed, key=lambda r: (passes(r), r))


def packed(a, side: int, grade: str = "float32"):
    """One side of a float32-grade product x·w as ONE bfloat16 product at
    default precision, accumulated in float32: the bfloat16 parts of the
    examples x (B, N) (``side`` 0) side by side, or of the operator w (N, S)
    (``side`` 1) one above the other, as :data:`_TERMS` pairs them —
    [x_lo | x_hi | x_mid | x_mid | x_hi | x_hi] (B, 6N) against
    [w_hi ; w_lo ; w_mid ; w_hi ; w_mid ; w_hi] (6N, S). ``grade`` ``"bf16"``
    keeps the one term x_hi·w_hi: K = N."""
    terms = _terms(grade)
    parts = bf16_parts(a, 1 + max(t[side] for t in terms))
    return jnp.concatenate([parts[t[side]] for t in terms], axis=-1 - side)


def spectral_operator(h, v, s: int):
    """Rows of the half-spectrum DFT operator: for buckets ``h`` (r,) int32
    and signed scales ``v`` (r,) float32 the (r, S) real array whose row j is
    the spectrum of v_j·e_{h_j} — columns κ < S/2 its real parts
    v_j·cos(2πh_jκ/S), columns S/2 + κ its imaginary parts −v_j·sin(2πh_jκ/S),
    and in column S/2 (bin 0 has no imaginary part) the Nyquist bin
    v_j·(−1)^{h_j}. Phases reduced in int32, each entry right to an ulp or
    two (``fut._cis_turns``)."""
    half = s // 2
    kappa = jnp.arange(half, dtype=jnp.int32)[None, :]
    cos, sin = _fut._cis_turns((h[:, None] * kappa) % s, s)
    nyquist = (1 - 2 * (h & 1)).astype(jnp.float32)[:, None]
    return v[:, None] * jnp.concatenate(
        [cos, jnp.where(kappa == 0, nyquist, -sin)], axis=1)


def class_slots(h, radix: int):
    """Where each input lies once the inputs are ordered by bucket class:
    for buckets ``h`` (N,) int32 the (N,) int32 column p·c + rank of input j
    in the (R, c) table of classes — p = h_j mod R, rank its place among its
    class's inputs, c = :func:`class_cols` — and −1 for an input past its
    class's capacity (the caller has counted: :meth:`PPT.radix`)."""
    cols, p = class_cols(h.shape[0], radix), h & (radix - 1)
    member = p[:, None] == jnp.arange(radix, dtype=jnp.int32)[None, :]
    rank = jnp.sum(jnp.where(member, jnp.cumsum(member, axis=0) - 1, 0), axis=1)
    return jnp.where(rank < cols, p * cols + rank, -1)


def class_operator(h, v, s: int, radix: int, grade: str = "float32",
                   first_weight: float = 1.0):
    """One CountSketch's half of a spectral product formed by bucket class:
    ``W[p, re | im]``, the packed operators (6c, S/2R) bfloat16 — class p's
    rows of :func:`spectral_operator` (its inputs in rank order, a pad row
    v = 0), their columns κ < S/2R, real and imaginary parts an array each
    (:func:`packed` along K; a class sum sliced out of a wider product is
    copied before the spectra's product reads it) —; the exact 0/1 matrix
    (N, R·c) bfloat16 that brings a block's examples into the same order on
    the MXU (a gathered minor axis is a scalar gather on a v5e); and ``mid``
    (N, R) float32, the midpoints' operator: column p is v_j·(−1)^{h′_j} on
    class p's inputs — T_p at κ = S/2R is e^{−iπp/R} times one real number.
    ``first_weight`` scales column κ = 0: a group's first bin stands beside
    its conjugate (or is its own) and takes half weight, which ONE sketch of
    the product carries."""
    cols, half = class_cols(h.shape[0], radix), s // (2 * radix)
    slot = class_slots(h, radix)
    order = slot[:, None] == jnp.arange(radix * cols, dtype=jnp.int32)[None, :]
    hc = jnp.zeros((radix * cols,), jnp.int32).at[slot].set(h, mode="drop")
    vc = jnp.zeros((radix * cols,), jnp.float32).at[slot].set(v, mode="drop")
    W = packed(_class_rows(hc, vc, s, radix, first_weight).reshape(
        radix, cols, 2, half).transpose(0, 2, 1, 3), 1, grade)    # (R, 2, 6c, ·)
    return W, order.astype(jnp.bfloat16), _midpoint_rows(h, v, radix)


def _midpoint_rows(h, v, radix: int):
    """(r, R): row j is v_j·(−1)^{h′_j} in the column of its bucket's class."""
    member = (h & (radix - 1))[:, None] == jnp.arange(radix, dtype=jnp.int32)
    odd = (h >> (radix.bit_length() - 1)) & 1
    return jnp.where(member, (v * (1 - 2 * odd).astype(v.dtype))[:, None], 0.0)


def _class_rows(h, v, s: int, radix: int, first_weight: float):
    """Rows of the class sums' operator (r, S/R): :func:`spectral_operator`'s
    columns κ < S/2R, real parts then imaginary parts — column κ = 0 of the
    imaginary parts the zero it is (the midpoints have an operator of their
    own), of both at ``first_weight``."""
    half = s // (2 * radix)
    kappa = jnp.arange(half, dtype=jnp.int32)[None, :]
    cos, sin = _fut._cis_turns((h[:, None] * kappa) % s, s)
    weight = jnp.where(kappa == 0, jnp.asarray(first_weight, jnp.float32), 1.0)
    scale = v[:, None] * weight
    return jnp.concatenate([scale * cos, scale * -sin], axis=1)


def _inverse_factors(n1: int, n2: int, radix: int = 1):
    """The inverse transform's factors, generated in the program: ``M1``
    (2·N1, N1), stage one over the high digit κ1 of κ = N2·κ1 + κ2 — the N1/2
    digits the spectra's product stores, group q < R the N1/2R from q·N1/R
    (0 … N1/2 − 1 at R = 1) —, rows (re | im, t1), columns (re | im, κ1),
    e^{+2πiκ1t1/N1} as a real matrix, times 2/S (each bin stands for its
    conjugate too: a group's first bin, beside its conjugate's group or its
    own conjugate, at half weight); the twiddles between the stages ``Tc``,
    ``Ts`` (N1, N2), cos and sin of 2πκ2t1/S; ``M2`` (2·N2, N2), stage two
    over the low digit — rows (re | im, κ2), the real part of
    e^{2πiκ2t2/N2}·(re + i·im); and ``mid`` (2·max(R/2, 1), N1), what the
    midpoint bins f = (2q + 1)·S/2R, which lie in no group, add to column
    κ2 = 0 between the stages: rows (re | im, q), cos and −sin of
    π(2q + 1)t1/R times 2/S (1/S at R = 1: the Nyquist bin is its own
    conjugate)."""
    s = n1 * n2
    t1 = jnp.arange(n1, dtype=jnp.int32)[:, None]
    k1 = jnp.arange(n1 // 2, dtype=jnp.int32)[None, :]
    per = n1 // (2 * radix)
    k1 = k1 // per * (2 * per) + k1 % per
    cos, sin = _fut._cis_turns((t1 * k1) % n1, n1)
    M1 = jnp.float32(2.0 / s) * jnp.concatenate(
        [jnp.concatenate([cos, -sin], axis=1),
         jnp.concatenate([sin, cos], axis=1)], axis=0)
    k2 = jnp.arange(n2, dtype=jnp.int32)
    Tc, Ts = _fut._cis_turns((t1 * k2[None, :]) % s, s)
    cos, sin = _fut._cis_turns((k2[:, None] * k2[None, :]) % n2, n2)
    M2 = jnp.concatenate([cos, -sin], axis=0)
    odd = 2 * jnp.arange(max(radix // 2, 1), dtype=jnp.int32)[:, None] + 1
    cos, sin = _fut._cis_turns((odd * t1.T) % (2 * radix), 2 * radix)
    mid = (jnp.float32(1.0 / s) * cos if radix == 1 else
           jnp.float32(2.0 / s) * jnp.concatenate([cos, -sin], axis=0))
    return M1, Tc, Ts, M2, mid


def _times(a, b):
    return a[0] * b[0] - a[1] * b[1], a[0] * b[1] + a[1] * b[0]


def _turns(rows, cols, period: float):
    """cos and −sin of 2π·rows·cols/period, (rows, cols) float32 from the
    host: the quarter turns exact."""
    import numpy as np

    angle = 2.0 * np.pi * np.outer(rows, cols) / period
    return (np.round(np.cos(angle), 15).astype(np.float32),
            np.round(-np.sin(angle), 15).astype(np.float32))


def class_ordered(Xb, orders, radix: int, grade: str):
    """A block's examples (B, N) in each sketch's class order, packed:
    ``x[k][p]`` (6c, B) bfloat16 — class p's columns of the examples'
    bfloat16 parts as :func:`packed` lays them along K, brought there by an
    exact 0/1 product on the MXU (one term a column, each part by itself:
    ordering all six terms in the product reads 3.6 ms a sketch and apply on
    a v5e where three parts and the slabs' copies read 1; the examples stay
    next to the lanes, where the compiler keeps the packed examples of
    radix 1 too, so that a class's six terms are whole slabs along K) —
    behind a barrier, made once a block."""
    terms = [t[0] for t in _terms(grade)]
    parts = jnp.stack(bf16_parts(Xb, 1 + max(terms)))              # (3, B, N)
    # the terms' parts as runs of consecutive parts: (2, 0, 1, 1, 0, 0) is
    # [2:3], [0:2], [1:2], [0:1], [0:1] — a slice a run, not two a term
    runs = [[terms[0], terms[0] + 1]]
    for t in terms[1:]:
        if t == runs[-1][1]:
            runs[-1][1] += 1
        else:
            runs.append([t, t + 1])
    ordered = []
    for order in orders:
        Y = jnp.einsum("nrc,tbn->rtcb", order.reshape(order.shape[0], radix, -1),
                       parts, precision=jax.lax.Precision.DEFAULT,
                       preferred_element_type=jnp.bfloat16)
        ordered.append([jnp.concatenate([Y[p, a:b] for a, b in runs]).reshape(
            -1, Xb.shape[0]) for p in range(radix)])
    return jax.lax.optimization_barrier(ordered)


def class_sums(x, W):
    """One sketch's class sums T_p on the bins κ < S/2R, ``T[p][re | im]``
    (B, S/2R) float32: 2R bfloat16 products at default precision (the parts
    are split already) of class p's ordered examples (6c, B) and a packed
    operator (6c, S/2R)."""
    # row-major said outright: the compiler writes these products examples-
    # minor where their reader broadcasts them, and copies each
    return [[_row_major(jnp.einsum("kb,ks->bs", x[p], W[p][part],
                                   precision=jax.lax.Precision.DEFAULT,
                                   preferred_element_type=jnp.float32))
             for part in range(2)] for p in range(len(x))]


def class_bias(h, v, s: int, radix: int, weights):
    """The homogeneity terms' spectra on the groups' bins, ``(re, im)`` each
    (q, 1, R, 1, S/2R): term k (bucket h_k, value v_k) lies in class
    p = h_k mod R, so its part of group g's bins is ω_R^{pg} times its rows
    of :func:`_class_rows` — added to a sketch's spectrum once the classes
    are joined."""
    cos, sin = (jnp.asarray(c.T)[h & (radix - 1)][:, :, None]       # (q, R, 1)
                for c in _turns(range(radix), range(radix), radix))
    rows = jax.vmap(lambda h, v, w: _class_rows(h[None], v[None], s, radix, w))(
        h, v, weights).reshape(h.shape[0], 1, 2, -1)
    re, im = rows[:, :, 0], rows[:, :, 1]                          # (q, 1, ·)
    return ((cos * re - sin * im)[:, None, :, None],
            (cos * im + sin * re)[:, None, :, None])


def _class_spectra(Xb, operators, s: int, grade: str, radix: int, lo: int):
    """The product of the q spectra, formed by bucket class: for bucket
    h = R·h′ + p every bin F[κ + q·S/R], q < R, is Σ_p ω_R^{pq}·T_p[κ] of the
    R class sums T_p[κ] = Σ_{h_j ≡ p} v_j x_j ω_S^{h_jκ} on the S/2R bins
    κ < S/2R — one decimation-in-time step, exact in exact arithmetic —, so a
    sketch is 2R bfloat16 products (B, 6c)·(6c, S/2R) of a quarter (R = 4)
    of the multiply-adds and R multiply-adds a bin on the VPU. The groups
    [q·S/R, q·S/R + S/2R) hold one of every conjugate pair but their first
    bins, which stand beside their conjugates (or are their own) and take
    half weight (sketch 0's operator carries it), and the R midpoints
    f = (2q + 1)·S/2R, R/2 pairs, which have a small product of their own.

    The groups are an axis of the arrays, between the examples' two digits:
    a spectrum is (B/lo, R, lo, S/2R) — (h, q, l, κ), example lo·h + l —, made
    of the class sums (h, 1, l, κ) by broadcast against the R × R factors, so
    that the compiler's two fusions (the real half stored and the pair
    product handed on, then the imaginary half: the whole product's) read
    every class sum where they read the spectra at radix 1, and each half of
    stage one's operand (B/lo, 2, R, lo, S/2R) is stored in one piece: where
    N2 is a lane's 128 that is (h, κ1, l, κ2) as stage one reads it. Returns
    the two halves and the midpoints' products (R, B), (re | im, q)."""
    W, bias, order, wmid, bmid = operators
    B, half = Xb.shape[0], s // (2 * radix)
    ordered = class_ordered(Xb, order, radix, grade)
    cos, sin = _turns(range(radix), range(radix), radix)           # [q, p]
    mcos, msin = _turns(range(radix), [2 * q + 1 for q in range(radix // 2)],
                        2 * radix)

    def factor(c):
        """A factor a group against (h, 1, l, κ): None where every one is 0,
        1.0 where every one is 1."""
        if not c.any() or (c == 1.0).all():
            return 1.0 if c.any() else None
        return jnp.asarray(c)[None, :, None, None]

    factors = [(factor(cos[:, p]), factor(sin[:, p])) for p in range(radix)]

    def turned(c, t):
        return None if c is None else t if isinstance(c, float) else c * t

    def joined(a, b, sign=1):
        if b is None:
            return a
        if a is None:
            return -b if sign < 0 else b
        return a + b if sign > 0 else a - b

    spectra = mids = None
    for k in range(len(W)):
        T = class_sums(ordered[k], W[k])
        re, im = bias[0][k], bias[1][k]
        for p in range(radix):
            tre, tim = (t.reshape(B // lo, 1, lo, half) for t in T[p])
            # ω_R^{pq}·T_p, ω = cos − i·sin (``sin`` holds −sin)
            c, s_ = factors[p]
            re = joined(re, joined(turned(c, tre), turned(s_, tim), -1))
            im = joined(im, joined(turned(c, tim), turned(s_, tre)))
        m = jnp.dot(_grade(Xb, grade), _grade(wmid[k], grade),
                    precision=_HIGHEST) + bmid[k]                 # (B, R)
        mid = (jnp.dot(m, mcos, precision=_HIGHEST),
               jnp.dot(m, msin, precision=_HIGHEST))              # (B, R/2)
        if spectra is None:
            spectra, mids = (re, im), mid
            continue
        spectra, mids = _times(spectra, (re, im)), _times(mids, mid)
    shape = (B // lo, radix, lo, half)
    return ([jnp.broadcast_to(part, shape) for part in spectra],
            jnp.concatenate(mids, axis=1).T)


def _whole_spectra(Xb, operators, s: int, grade: str):
    """The product of the q spectra where the products are not formed by
    class (radix 1): a sketch's half spectrum is one product x·(C_k F) over
    all S real columns. Returns the two halves (B, S/2) of stage one's
    operand and the Nyquist bins' product (1, B)."""
    W, bias = operators
    first = jnp.arange(s // 2, dtype=jnp.int32)[None, :] == 0
    # the block's examples, packed, as an array of their own, made once: a
    # product that reads them through the walk's dynamic slice of the whole
    # operand is a tenth slower on a v5e (4.16 against 3.77 ms), three times
    # a block
    x = jax.lax.optimization_barrier(packed(Xb, 0, grade))
    re = im = nyquist = None
    for k in range(len(W)):
        # default precision, said outright: the parts are bfloat16 already,
        # and the package's ambient ``highest`` has nothing to split
        F = jnp.dot(x, W[k], precision=jax.lax.Precision.DEFAULT,
                    preferred_element_type=jnp.float32) + bias[k]
        fre, fim = F[:, :s // 2], F[:, s // 2:]
        if re is None:
            re, im, nyquist = fre, fim, fim[:, 0]
            continue
        # bins 0 and S/2 are real and share column 0: re holds bin 0's
        # product, im the Nyquist bin's — which the twiddles' pass takes as a
        # vector, so it is multiplied up on its own (B,) column, to the same
        # bits: a second reader of im would cost the product a whole array
        nyquist = nyquist * fim[:, 0]
        both = im * fim
        re, im = (re * fre - jnp.where(first, 0.0, both),
                  jnp.where(first, both, re * fim + im * fre))
    return [jnp.where(first, 0.5 * re, re), jnp.where(first, 0.0, im)], nyquist[None, :]


def _block_features(Xb, operators, factors, grade: str, radix: int = 1):
    """The features of the examples ``Xb`` (B, N) as (B/8, N2, 8, N1) —
    (h, t2, l, t1): example 8·h + l, feature t = t1 + N1·t2, which is how
    the (8, 128) tiles of a row-major (B, S) array lie in memory where N1 is
    a lane's 128; where B is no multiple of 8, (B, N2, 1, N1), by class
    (1, N2, B, N1): the caller turns either alike. The q spectra
    x·(C_k F) + the homogeneity term's, their product, the inverse transform:
    stage one makes t1 of κ1, the twiddle couples (κ2, t1), stage two makes
    t2 of κ2 and leaves the two digits of t to the caller's store.
    ``operators`` at ``radix`` 1 are the q packed operators, (6N, S) bfloat16
    each (:func:`packed`: a spectral product is one bfloat16 product over
    the packed K, :func:`k_tiles`), and the homogeneity term's spectra
    (q, S); by bucket class (R > 1) what :func:`_class_spectra` reads —
    the packed operators of :func:`class_operator` an array a class and part,
    :func:`class_bias`, the class orders, the midpoints' operators and the
    homogeneity term's midpoints."""
    M1, Tc, Ts, M2, mid = factors
    n1, n2 = Tc.shape
    B, s = Xb.shape[0], n1 * n2
    # the stages see a block as (B/8, ·, 8, ·): eight examples next to the
    # lanes, as the tiles of (B, S) lie, so that stage one reads U as it is
    lo = 8 if B % 8 == 0 else 1
    # stage one's operand (re | im, κ1, κ2), each half stored where it lies:
    # a concatenate is a pass of its own on a v5e (two pads and a maximum,
    # which no producer is fused into), an update in place is its producer's
    if radix == 1:
        halves, mids = _whole_spectra(Xb, operators, s, grade)
        U = jax.lax.empty((B, s), jnp.float32)
        for g, piece in enumerate(halves):
            U = jax.lax.dynamic_update_slice(U, piece, (0, g * (s // 2)))
        X = U.reshape(B // lo, lo, n1, n2).transpose(0, 2, 1, 3)  # (h, κ1, l, κ2)
    else:
        lo = lo if lo == 8 else B            # no sublane padded: (1, ·, B, ·)
        halves, mids = _class_spectra(Xb, operators, s, grade, radix, lo)
        U = jax.lax.empty((B // lo, 2, radix, lo, s // (2 * radix)), jnp.float32)
        for g, piece in enumerate(halves):
            U = jax.lax.dynamic_update_slice(U, piece[:, None], (0, g, 0, 0, 0))
        # row-major said outright: left to itself the v5e compiler lays the
        # spectra's product examples-minor, copies every class sum into that
        # layout and the operand back out of it
        U = _row_major(U)
        # (h, re | im · q, l, κ1 of the group, κ2) → (h, κ1, l, κ2): where N2
        # is a lane's 128 the tiles of U lie in that order already
        X = U.reshape(B // lo, 2 * radix, lo, -1, n2).transpose(
            0, 1, 3, 2, 4).reshape(B // lo, n1, lo, n2)
    R = jnp.einsum("uk,hklc->hulc", _grade(M1, grade), _grade(X, grade),
                   precision=_HIGHEST)
    Rre, Rim = R[:, :n1], R[:, n1:]                          # (h, t1, l, κ2)
    # the bins outside the groups (the Nyquist bin's (−1)^t = (−1)^{t1} at
    # radix 1) depend on t1 alone and join bin κ2 = 0, whose factor in stage
    # two is 1 for every t2
    low = jnp.arange(n2, dtype=jnp.int32)[None, None, None, :] == 0
    tc, ts = Tc[None, :, None, :], Ts[None, :, None, :]
    ny = jnp.dot(mids.T, mid, precision=_HIGHEST)            # (B, t1)
    ny = ny.reshape(B // lo, lo, n1).transpose(0, 2, 1)[..., None]
    V = jnp.concatenate([Rre * tc - Rim * ts + jnp.where(low, ny, 0.0),
                         Rre * ts + Rim * tc], axis=3)       # (h, t1, l, re|im κ2)
    return jnp.einsum("hulk,kt->htlu", _grade(V, grade), _grade(M2, grade),
                      precision=_HIGHEST)                    # (h, t2, l, t1)


def tensorsketch_features(key_data, A, *, spec, rowwise: bool,
                          row_block: int = 0, grade: str = "float32",
                          radix: int = 1):
    """One TensorSketch apply as a pure function of the transform's raw key
    data ((2,) uint32) and a float32 operand: for every example x,
    ``z = IFFT(∏_{k<q} FFT(√γ·C_k x + √c·s'_k·e_{h'_k}))``, real — what
    :meth:`PPT._sketch_columns` computes. ``spec`` = (sketch_type, N, S,
    sorted hyper-parameters) rebuilds the transform around the traced key, so
    the buckets, the signs and the homogeneity hash are the transform's own
    methods on the same sub-streams: the same bits as the eager chain's.

    The examples are walked ``row_block`` at a time (0: :func:`block_rows`'s
    own), the last block drawn back so that it ends with the operand — its
    first rows are computed twice, to the same bits, and no row is padded —
    and each block's features go into their rows of the result in place.
    Where rows and block are whole (8, 128) tiles of a result whose split has
    N1 a multiple of the 128 lanes, the result is carried through the walk as
    (m/8, N2, 8, N1) — the bytes of the row-major (m, S) — and ONE fusion
    turns the digits of t and stores the block at its offset on the untiled
    leading dimension; any other shape turns a block's features first and
    copies them in. A columnwise operand (N, m) is walked by column blocks, a
    block transposed on its way in and out."""
    sketch_type, n, s, extra = spec
    T = _REGISTRY[sketch_type]._from_parts(
        n, s, _ProgramAllocation(key_data), dict(extra))
    hidx, hval = T._hash_idx(), T._hash_val(jnp.float32)
    sg, sc = jnp.float32(math.sqrt(T._gamma)), jnp.float32(math.sqrt(T._c))
    # the packed operators one array each: stacked, they are copied once more
    if radix == 1:
        operators = (
            [packed(spectral_operator(cwt.bucket_indices(),
                                      sg * cwt.values(jnp.float32), s), 1, grade)
             for cwt in T._cwts], spectral_operator(hidx, sc * hval, s))
    else:
        # a group's first bin at half weight: sketch 0's to carry; the q
        # sketches' operators traced once (a third of the program's text)
        weights = jnp.asarray([0.5] + [1.0] * (len(T._cwts) - 1), jnp.float32)
        W, order, wmid = jax.vmap(functools.partial(
            class_operator, s=s, radix=radix, grade=grade))(
            jnp.stack([cwt.bucket_indices() for cwt in T._cwts]),
            sg * jnp.stack([cwt.values(jnp.float32) for cwt in T._cwts]),
            first_weight=weights)
        # an operator an array: one sliced inside the walk is copied a block
        W = [[[W[k, p, part] for part in range(2)] for p in range(radix)]
             for k in range(len(T._cwts))]
        bias = class_bias(hidx, sc * hval, s, radix, weights)
        bmid = _midpoint_rows(hidx, sc * hval, radix)
        operators = (W, bias, list(order), list(wmid), bmid)
    n1, n2 = split(s)
    factors = _inverse_factors(n1, n2, radix)

    turned = functools.partial(_block_features, operators=operators,
                               factors=factors, grade=grade, radix=radix)

    def features(Xb):
        # row-major, said outright: left to itself the v5e compiler carries
        # the result through the walk column-major (stage two's product
        # leaves the examples next to the lanes) and transposes all of it
        # at the end — 3.9 GB of temporaries more at 60,000 × 16384
        return _row_major(
            turned(Xb).transpose(0, 2, 1, 3).reshape(Xb.shape[0], s))

    m = A.shape[0] if rowwise else A.shape[1]
    B = min(m, row_block) if row_block else block_rows(m, s)
    if B == m:
        return features(A) if rowwise else features(A.T).T
    tiled = rowwise and m % 8 == 0 and B % 8 == 0 and n1 % 128 == 0

    def step(i, Z):
        lo = jnp.minimum(i * B, m - B)
        if not rowwise:
            Xb = jax.lax.dynamic_slice(A, (0, lo), (n, B)).T
            return jax.lax.dynamic_update_slice(Z, features(Xb).T, (0, lo))
        Xb = jax.lax.dynamic_slice(A, (lo, 0), (B, n))
        if not tiled:
            return jax.lax.dynamic_update_slice(Z, features(Xb), (lo, 0))
        return _row_major(
            jax.lax.dynamic_update_slice(Z, turned(Xb), (lo // 8, 0, 0, 0)))

    shape = (m, s) if rowwise else (s, m)
    Z = jax.lax.fori_loop(
        0, -(-m // B), step,
        jax.lax.empty((m // 8, n2, 8, n1) if tiled else shape, A.dtype))
    return Z.transpose(0, 2, 1, 3).reshape(shape) if tiled else Z


@functools.lru_cache(maxsize=None)
def _features_program():
    """The compiled apply, built at the first dense operand so that
    importing the sketch layer never pulls the engine."""
    from libskylark_tpu.engine.compiled import compiled

    return compiled(tensorsketch_features, name="sketch.tensorsketch_features",
                    static_argnames=("spec", "rowwise", "row_block", "grade",
                                     "radix"))


@register
class PPT(SketchTransform):
    sketch_type = "PPT"

    def __init__(self, N, S, context, q: int = 3, c: float = 1.0,
                 gamma: float = 1.0):
        from libskylark_tpu.base import errors

        if q < 1:
            raise errors.InvalidParametersError(f"PPT degree q must be >= 1, got {q}")
        if c < 0 or gamma < 0:
            raise errors.InvalidParametersError(
                f"PPT parameters c and gamma must be nonnegative, got c={c}, gamma={gamma}"
            )
        self._q = int(q)
        self._c = float(c)
        self._gamma = float(gamma)
        super().__init__(N, S, context)

    def _build(self):
        self._cwts = [
            CWT(self._N, self._S, self._alloc.child(i)) for i in range(self._q)
        ]
        self._classes = None

    def radix(self) -> int:
        """The bucket classes this transform's spectral products are formed
        by: :func:`radix` of its shapes where every class of every one of
        its CountSketches fits :func:`class_cols` — counted once a transform,
        one small read of the device — else 1, the whole product: never a
        dropped input. Four standard deviations of room: under one seed in a
        thousand takes 1 at 784 inputs."""
        if self._classes is None:
            r = radix(self._N, self._S)
            if r > 1:
                h = [cwt.bucket_indices() for cwt in self._cwts]
                if any(isinstance(b, jax.core.Tracer) for b in h):
                    return 1                 # buckets nobody can count here
                import numpy as np

                fullest = max(np.bincount(np.asarray(b) & (r - 1)).max() for b in h)
                r = r if fullest <= class_cols(self._N, r) else 1
            self._classes = r
        return self._classes

    def _hash_idx(self) -> jnp.ndarray:
        return randgen.stream_slice(
            self.subkey(100), randgen.UniformInt(0, self._S - 1), 0, self._q,
            dtype=jnp.int32,
        )

    def _hash_val(self, dtype) -> jnp.ndarray:
        return randgen.stream_slice(
            self.subkey(101), randgen.Rademacher(), 0, self._q, dtype=dtype
        )

    def _sketch_columns(self, A: jnp.ndarray) -> jnp.ndarray:
        """Columnwise TensorSketch of A (N, m) -> (S, m)
        (ref: PPT_Elemental.hpp:155-185)."""
        dt = A.dtype
        hidx = self._hash_idx()
        hval = self._hash_val(dt)
        sqrt_gamma = math.sqrt(self._gamma)
        sqrt_c = math.sqrt(self._c)
        P = None
        for i, cwt in enumerate(self._cwts):
            W = sqrt_gamma * cwt.apply(A)                     # (S, m)
            W = W.at[hidx[i], :].add(sqrt_c * hval[i])
            FW = jnp.fft.fft(W, axis=0)
            P = FW if P is None else P * FW
        return jnp.real(jnp.fft.ifft(P, axis=0)).astype(dt)

    def features_plan(self, A, rowwise: bool) -> dict:
        """What an apply does with this operand, as the attributes its
        ``sketch.dispatch`` span carries: ``route`` ``"program"``
        (:func:`tensorsketch_features`, with the ``row_block`` of its walk,
        its ``grade``, the form of its spectral ``product`` — ``"packed_k"``,
        :func:`packed` —, the ``radix`` R of bucket classes a product is
        formed by (:meth:`radix`; 1: the whole product), the ``class_cols``
        a class is given and the MXU ``k_tiles`` a product takes,
        R·⌈6c/128⌉ over S/R columns each, :func:`k_tiles`)
        or ``"chain"`` with the ``reason`` the eager chain
        keeps it — another dtype than float32, an S the inverse transform
        cannot split (:func:`split`), an operand that lies on more than one
        device (the program's walk would gather it) — and the forms of the
        CountSketch and of the transform on that route."""
        from libskylark_tpu.sketch import params as sketch_params

        m = A.shape[0] if rowwise else A.shape[1]
        reason = None
        if A.dtype != jnp.float32:
            reason = f"dtype={A.dtype}"
        elif split(self._S) is None:
            reason = f"s={self._S}"
        elif not isinstance(A, jax.core.Tracer) and len(A.devices()) > 1:
            reason = f"devices={len(A.devices())}"
        if reason is not None:
            return {"route": "chain", "reason": reason, "row_block": m,
                    "sketch": "segment_sum", "fft": "jnp.fft"}
        bf16 = sketch_params.get_pallas_precision() == "bf16"
        grade = "bf16" if bf16 else "float32"
        r = self.radix()
        return {"route": "program", "row_block": block_rows(m, self._S),
                "sketch": "spectral_operator", "fft": "mxu_two_stage",
                "grade": grade, "product": "packed_k", "radix": r,
                "class_cols": class_cols(self._N, r),
                "k_tiles": k_tiles(self._N, grade, r)}

    def _features(self, A: jnp.ndarray, rowwise: bool) -> jnp.ndarray:
        """The dense apply: the one ``sketch.tensorsketch_features`` program
        (under a caller's trace: part of the caller's program), or the eager
        chain where :meth:`features_plan` names a reason."""
        with _trace.span("sketch.plan"):
            plan = self.features_plan(A, rowwise)
        m = A.shape[0] if rowwise else A.shape[1]
        traced = isinstance(A, jax.core.Tracer)
        # path "features": the feature maps' own (rft.py, frft.py), which the
        # benchmark's feature_rate reads; ``elements`` the entries transformed
        attrs = {"path": "features", "family": self.sketch_type,
                 "q": self._q, "s": self._S, "rows": m,
                 "features": m * self._S,
                 "elements": m * self._S * (self._q + 1), **plan}
        if plan["route"] == "chain":
            if traced:
                return self._chain(A, rowwise)
            with _trace.span("sketch.dispatch", attrs):
                out = self._chain(A, rowwise)
        else:
            statics = dict(
                spec=(self.sketch_type, self._N, self._S,
                      tuple(sorted(self._extra_params().items()))),
                rowwise=rowwise, grade=plan["grade"], radix=plan["radix"])
            key_data = self._alloc.key_data
            if traced:
                return tensorsketch_features(key_data, A, **statics)
            with _trace.span("sketch.dispatch", attrs):
                out = _features_program()(key_data, A, **statics)
        # the radix says how often the products were formed by class ("1":
        # a transform with an overfull class, or shapes with no classes)
        labels = {"radix": str(plan["radix"])} if "radix" in plan else {}
        _ROWS.inc_always(m, family=self.sketch_type, route=plan["route"],
                         **labels)
        return out

    def _chain(self, A: jnp.ndarray, rowwise: bool) -> jnp.ndarray:
        return self._sketch_columns(A.T).T if rowwise else self._sketch_columns(A)

    def _apply_columnwise(self, A: jnp.ndarray) -> jnp.ndarray:
        return self._features(A, rowwise=False)

    def _apply_rowwise(self, A: jnp.ndarray) -> jnp.ndarray:
        return self._features(A, rowwise=True)

    def _extra_params(self) -> dict[str, Any]:
        return {"q": self._q, "c": self._c, "gamma": self._gamma}

    @classmethod
    def _from_parts(cls, N, S, alloc, d):
        return cls(N, S, alloc, q=int(d.get("q", 3)), c=float(d.get("c", 1.0)),
                   gamma=float(d.get("gamma", 1.0)))
