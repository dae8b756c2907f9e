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

What the v5e compiler makes of a block (``tests/test_v5e_compile.py`` holds
it): the block's examples sliced out of the operand and packed once; the q
products of those; the spectra's product in two fusions, each storing its
half of stage one's operand in place (the first hands the pair product on to
the second); stage one, which reads that operand as the tiles of a (B, S)
array lie; the twiddles; stage two; and one fusion that turns the two digits
of t and stores the block into its rows of the result — nine passes over
block-sized arrays at q = 3, every array written once and read once but the
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


def k_tiles(n: int, grade: str = "float32") -> int:
    """128-deep MXU tile passes a spectral product of N input columns takes
    over its packed K: ⌈6N/128⌉ — never more than the 6·⌈N/128⌉ of six
    products each padded by itself, fewer wherever N's last tile is under
    five sixths full, six times fewer at N ≤ 21."""
    return -(-len(_terms(grade)) * n // 128)


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
    return jnp.concatenate([parts[t[side]] for t in terms], axis=1 - side)


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


def _inverse_factors(n1: int, n2: int):
    """The inverse transform's factors, generated in the program: ``M1``
    (2·N1, N1), stage one over the high digit κ1 < N1/2 of κ = N2·κ1 + κ2 —
    rows (re | im, t1), columns (re | im, κ1), e^{+2πiκ1t1/N1} as a real
    matrix, times 2/S (each bin but 0 and S/2 stands for its conjugate
    too); the twiddles between the stages ``Tc``, ``Ts`` (N1, N2), cos and
    sin of 2πκ2t1/S; and ``M2`` (2·N2, N2), stage two over the low digit —
    rows (re | im, κ2), the real part of e^{2πiκ2t2/N2}·(re + i·im)."""
    s = n1 * n2
    t1 = jnp.arange(n1, dtype=jnp.int32)[:, None]
    k1 = jnp.arange(n1 // 2, dtype=jnp.int32)[None, :]
    cos, sin = _fut._cis_turns((t1 * k1) % n1, n1)
    M1 = jnp.float32(2.0 / s) * jnp.concatenate(
        [jnp.concatenate([cos, -sin], axis=1),
         jnp.concatenate([sin, cos], axis=1)], axis=0)
    k2 = jnp.arange(n2, dtype=jnp.int32)
    Tc, Ts = _fut._cis_turns((t1 * k2[None, :]) % s, s)
    cos, sin = _fut._cis_turns((k2[:, None] * k2[None, :]) % n2, n2)
    return M1, Tc, Ts, jnp.concatenate([cos, -sin], axis=0)


def _block_features(Xb, operators, factors, grade: str):
    """The features of the examples ``Xb`` (B, N) as (B/8, N2, 8, N1) —
    (h, t2, l, t1): example 8·h + l, feature t = t1 + N1·t2, which is how
    the (8, 128) tiles of a row-major (B, S) array lie in memory where N1 is
    a lane's 128; (B, N2, 1, N1) where B is no multiple of 8. The q spectra
    x·(C_k F) + the homogeneity term's, their product, the inverse transform:
    stage one makes t1 of κ1, the twiddle couples (κ2, t1), stage two makes
    t2 of κ2 and leaves the two digits of t to the caller's store.
    ``operators`` are the q packed operators, (6N, S) bfloat16 each
    (:func:`packed`), and the homogeneity term's spectra (q, S): a spectral
    product is one bfloat16 product over the packed K, ⌈6N/128⌉ MXU tile
    passes (:func:`k_tiles`) where ``highest`` on float32 operands pads each
    of its six passes to ⌈N/128⌉ by itself (37 against 42 at N = 784)."""
    W, bias = operators
    M1, Tc, Ts, M2 = factors
    n1, n2 = Tc.shape
    B, s = Xb.shape[0], n1 * n2
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
    # stage one's operand (re | im, κ1, κ2), each half stored where it lies:
    # a concatenate is a pass of its own on a v5e (two pads and a maximum,
    # which no producer is fused into), an update in place is its producer's
    U = jax.lax.dynamic_update_slice(
        jax.lax.empty((B, s), jnp.float32), jnp.where(first, 0.5 * re, re),
        (0, 0))
    U = jax.lax.dynamic_update_slice(U, jnp.where(first, 0.0, im), (0, s // 2))
    # the stages see a block as (B/8, ·, 8, ·): eight examples next to the
    # lanes, as the tiles of (B, S) lie, so that stage one reads U as it is
    lo = 8 if B % 8 == 0 else 1
    X = U.reshape(B // lo, lo, n1, n2).transpose(0, 2, 1, 3)  # (h, κ1, l, κ2)
    R = jnp.einsum("uk,hklc->hulc", _grade(M1, grade), _grade(X, grade),
                   precision=_HIGHEST)
    Rre, Rim = R[:, :n1], R[:, n1:]                          # (h, t1, l, κ2)
    # the Nyquist bin's (−1)^t = (−1)^{t1} joins bin κ2 = 0, whose factor
    # in stage two is 1 for every t2
    sign = (1 - 2 * (jnp.arange(n1, dtype=jnp.int32) & 1)).astype(jnp.float32)
    low = jnp.arange(n2, dtype=jnp.int32)[None, None, None, :] == 0
    tc, ts = Tc[None, :, None, :], Ts[None, :, None, :]
    ny = ((nyquist * jnp.float32(1.0 / s)).reshape(B // lo, 1, lo, 1)
          * sign[None, :, None, None])
    V = jnp.concatenate([Rre * tc - Rim * ts + jnp.where(low, ny, 0.0),
                         Rre * ts + Rim * tc], axis=3)       # (h, t1, l, re|im κ2)
    return jnp.einsum("hulk,kt->htlu", _grade(V, grade), _grade(M2, grade),
                      precision=_HIGHEST)                    # (h, t2, l, t1)


def tensorsketch_features(key_data, A, *, spec, rowwise: bool,
                          row_block: int = 0, grade: str = "float32"):
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
    from jax.experimental.layout import Layout, with_layout_constraint

    hidx, hval = T._hash_idx(), T._hash_val(jnp.float32)
    sg, sc = jnp.float32(math.sqrt(T._gamma)), jnp.float32(math.sqrt(T._c))
    # the packed operators one array each: stacked, they are copied once more
    operators = (
        [packed(spectral_operator(cwt.bucket_indices(),
                                  sg * cwt.values(jnp.float32), s), 1, grade)
         for cwt in T._cwts],
        spectral_operator(hidx, sc * hval, s))
    n1, n2 = split(s)
    factors = _inverse_factors(n1, n2)

    turned = functools.partial(_block_features, operators=operators,
                               factors=factors, grade=grade)

    def features(Xb):
        # row-major, said outright: left to itself the v5e compiler carries
        # the result through the walk column-major (stage two's product
        # leaves the examples next to the lanes) and transposes all of it
        # at the end — 3.9 GB of temporaries more at 60,000 × 16384
        return with_layout_constraint(
            turned(Xb).transpose(0, 2, 1, 3).reshape(Xb.shape[0], s),
            Layout(major_to_minor=(0, 1)))

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
        return with_layout_constraint(
            jax.lax.dynamic_update_slice(Z, turned(Xb), (lo // 8, 0, 0, 0)),
            Layout(major_to_minor=(0, 1, 2, 3)))

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
                    static_argnames=("spec", "rowwise", "row_block", "grade"))


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
        :func:`packed` — and the MXU ``k_tiles`` one takes, :func:`k_tiles`)
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
        return {"route": "program", "row_block": block_rows(m, self._S),
                "sketch": "spectral_operator", "fft": "mxu_two_stage",
                "grade": grade, "product": "packed_k",
                "k_tiles": k_tiles(self._N, grade)}

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
                rowwise=rowwise, grade=plan["grade"])
            key_data = self._alloc.key_data
            if traced:
                return tensorsketch_features(key_data, A, **statics)
            with _trace.span("sketch.dispatch", attrs):
                out = _features_program()(key_data, A, **statics)
        _ROWS.inc_always(m, family=self.sketch_type, route=plan["route"])
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
