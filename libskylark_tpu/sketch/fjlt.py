"""RFUT (randomized fast unitary transform) and FJLT.

TPU-native analogs of ref: sketch/RFUT_data.hpp:20-55, sketch/RFUT_Elemental.hpp:15-310,
sketch/FJLT_data.hpp:25-98, sketch/FJLT_Elemental.hpp:13-555.

RFUT: X → F·D·X with D a random (Rademacher) diagonal and F a fast unitary
transform scaled to near-orthonormality.

FJLT (subsampled randomized DCT/DHT): S = sqrt(N/S_dim) · R · F · D — mix with
RFUT, then uniformly sample S_dim coordinates
(ref: FJLT_Elemental.hpp:144-174: per-rank local FUT, then sample with scale
sqrt(N/S)). Under a sharded input the FUT runs independently per column shard
(the transform acts along the N axis, which is materialized locally when the
input is column-sharded; for row-sharded inputs XLA re-lays out, the analog of
the reference's [VC,*] → [*,VR] redistribution).

An apply of a float32 operand on one device is ONE compiled program
(``sketch.fjlt_mix_sample``, :func:`fjlt_mix_sample`): D and the sampled
coordinates generated inside from the allocation's key words, the inner
factors of the transform contracted in full a tile of the free axis at a time
and the last, outer factor taken at the sampled rows only.

* ``fut="wht"`` (the SRHT), an axis that is a power of two: the axis mixed in
  full inside blocks of rows and the Kronecker factor above the blocks sampled
  — on a TPU, columnwise, the blocks in one Pallas pass over the operand
  (sketch/pallas_wht.py), elsewhere on XLA without a transposed copy
  (sketch/fut.py ``wht_blocks``); its workspace is at most one operand-sized
  array, and the serve tier's lanes (:func:`srht_serve_apply`) are the same
  function on XLA;
* ``fut="dct"`` (the default, upstream's FFTW mixer) and ``"dht"``, an axis
  ``fut.dft_factors`` splits — N = R·f1·f2 with f1, f2 ≤ 128 and R ≤ 256, so
  every N ≤ 2²² with such a split (10⁶ = 100·125·80), no prime factor past
  256: the walk runs over the sampled digit r, ρ of its R slabs a pass
  (:func:`dft_slabs`, by memory) — whole rows of the operand gathered once
  into the order the stages contract (``fut.dft_source_rows``; columnwise no
  slice of the operand is taken first), the signs taken at the gathered rows,
  the DFT behind the transform in two dense stages of float32 factors on the
  MXU (``fut.dft_blocks``, half of its outputs: the input is real) and the
  pass's 2ρ rows against their twiddles a sampled output, added up over the
  passes (``fut.sample_outer_dft``); every array between the stages lies on
  whole (8, 128) tiles (``fut.dft_pads``), so that no pass only moves data.

Every other height and dtype, and an operand that lies on several devices,
keeps the eager composition (``fut.sign_mix_sample``: for the DCT ``lax.fft``
along a moved axis over complex copies of the whole operand).
"""

from __future__ import annotations

import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from libskylark_tpu.base import errors, randgen
from libskylark_tpu.base import threefry as tf
from libskylark_tpu.sketch import fut as _fut
from libskylark_tpu.sketch.fut import make_fut
from libskylark_tpu.sketch.transform import SketchTransform, register
from libskylark_tpu.telemetry import metrics as _metrics
from libskylark_tpu.telemetry import trace as _trace

_MIXED = _metrics.counter(
    "sketch.mixed_elements",
    "operand entries sign-and-transform mixed by the compiled FJLT apply "
    "(transform axis × free axis), by family and kernel")

#: Free-axis entries the XLA route mixes at a time: its workspace is a few
#: (N × tile) float32 temporaries, whatever the operand's other extent. No
#: wider tile read faster on a v5e, and one whose temporary reaches 64 MiB
#: (a serve flush's lanes counted) takes twice as long (PR 39, ms at 128 /
#: 256 / 1024 / 2048: rowwise 8192 × 8192 3.8 / — / 3.7 / 7.1, rowwise 4096 ×
#: 65536 13.7 / 26.2 / 40.7 / —, columnwise 65536 × 1000 3.7 / 6.7 / 10.8 / —).
MIX_TILE = 128
#: Rows of the transform axis mixed in full; the factor above them is
#: computed at the sampled rows only (``fut.sample_outer``).
MIX_BLOCK = 16384
#: The widest cut tile of the DCT / DHT route (:func:`dft_tile`: a rowwise
#: operand's): none wider was read on the chip where the tile is a copy.
_DFT_TILE_MAX = 512


def fjlt_mix_sample(key_data, A, *tables, s_dim: int, rowwise: bool,
                    kernel: str, tile: int, block: int = 0,
                    fut: str = "wht", factors: tuple = ()):
    """One FJLT apply as a pure function of the allocation's raw key words
    ((2,) uint32): ``√(N/s) · (scale·F_N · (D ⊙ A))[idx]`` along the
    transform axis (rows columnwise, columns rowwise) of a float32 A, for
    the mixer ``fut``: ``H_N/√N`` (``"wht"``), the unnormalized DCT-II
    ``/√(2N)`` (``"dct"``) or the DHT ``/√N`` (``"dht"``).

    D (sub-stream 0) and the sampled coordinates (sub-stream 1) are the
    positional streams :meth:`FJLT.diagonal` / :meth:`FJLT.sample_indices`
    read, generated here from the key. ``kernel``:

    * ``"pallas_blocks"`` — ``wht`` columnwise on a TPU: H_N = H_a ⊗
      H_block, one pass of :func:`pallas_wht.mix_blocks` over the operand
      writes the block-mixed matrix, the one operand-sized workspace of an
      apply, and the outer factor is taken at the s sampled rows only;
    * ``"xla_bf16x3"`` | ``"xla_f32"`` — ``wht`` everywhere else: the free
      axis walked ``tile`` entries at a time (a rowwise tile transposed by
      itself), so the workspace is a few (N × tile) temporaries; the
      Hadamard factors contract as exact bfloat16 against a three-way split
      operand on a TPU, in float32 off it;
    * ``"xla_dft"`` — ``dct`` | ``dht`` over ``factors`` = (R, f1, f2) of
      ``fut.dft_factors``: whole rows of the operand (``tile`` = its free
      axis; a rowwise one's tile, transposed) gathered in the stages' order
      with their signs, :func:`dft_slabs` of the R slabs a pass, their DFT in
      two dense ``highest`` stages, their part of the outer factor at the
      sampled outputs (sketch/fut.py). ``tables``: a pass's ``fut.dft_tables``
      on the operand's device; left out (a trace), constants of the program.
    """
    n = A.shape[1] if rowwise else A.shape[0]
    m = A.shape[0] if rowwise else A.shape[1]

    def stream(tag, dist, stop, dtype):
        key = jax.random.wrap_key_data(tf.fold_in(key_data, tag))
        return randgen.stream_slice(key, dist, 0, stop, dtype=dtype)

    D = stream(0, randgen.Rademacher(), n, A.dtype)
    idx = stream(1, randgen.UniformInt(0, n - 1), s_dim, jnp.int32)
    scale = 1.0 / math.sqrt(s_dim)

    if kernel == "pallas_blocks":
        from libskylark_tpu.sketch import pallas_wht

        Y = pallas_wht.mix_blocks(A, D, block=block, tile=tile)
        return scale * _fut.sample_outer(Y, idx, block)

    if kernel == "xla_dft":
        slabs = dft_slabs(n, factors, tile, m, rowwise)
        part = (slabs,) + factors[1:]               # a pass, to the stages
        tables = tables or _fut.dft_tables(part)
        scale /= math.sqrt(2.0) if fut == "dct" else 1.0

        def mixed(X):                               # X (N, w) → (s, w)
            # the walk over the sampled digit, ``slabs`` of its R slabs a
            # pass: whole rows of X gathered once, with their signs, the two
            # stages, and the passes' parts of the outer factor added up —
            # columnwise X is the operand itself, no slice of it taken
            return _dft_passes(X, D, idx, tables, fut=fut, factors=factors,
                               slabs=slabs, scale=scale)
    else:
        def mixed(X):
            Y = _fut.wht_blocks(D[:, None] * X, block, kernel == "xla_bf16x3")
            return scale * _fut.sample_outer(Y, idx, block)

    def columns(lo, w):
        """The sketch of free-axis entries [lo, lo + w), in A's layout."""
        if rowwise:
            return mixed(jax.lax.dynamic_slice(A, (lo, 0), (w, n)).T).T
        return mixed(jax.lax.dynamic_slice(A, (0, lo), (n, w)))

    if m <= tile:
        return columns(0, m)
    full, rest = divmod(m, tile)
    out = jnp.zeros((m, s_dim) if rowwise else (s_dim, m), A.dtype)

    def place(out, lo, part):
        return jax.lax.dynamic_update_slice(
            out, part, (lo, 0) if rowwise else (0, lo))

    out = jax.lax.fori_loop(
        0, full, lambda j, o: place(o, j * tile, columns(j * tile, tile)), out)
    if rest:
        out = place(out, full * tile, columns(full * tile, rest))
    return out


def _dft_passes(X, D, idx, tables, *, fut: str, factors: tuple, slabs: int,
                scale: float):
    """``scale`` · rows ``idx`` of the DCT / DHT of ``D ⊙ X`` along axis 0 of
    X (N, w), N = R·f1·f2 = ``factors``: R ÷ ``slabs`` passes, each the
    blocked DFT of ``slabs`` slabs of the sampled digit (``tables`` are the
    pass's, ``fut.dft_tables``) and their part of the outer factor's sum."""
    n, part = X.shape[0], (slabs,) + factors[1:]
    signs = _fut.dft_source_signs(D, factors, fut, slabs)

    def passed(first):                              # slabs [first, first + ρ)
        # the signs follow the gathered rows by a fold of their own and are
        # multiplied where stage one reads its input: no pass over the tile
        source = _fut.dft_source_rows(n, factors, fut, slabs, first)
        of_rows = jax.lax.dynamic_slice_in_dim(signs, first, slabs, 1)
        Z = _fut.dft_blocks(of_rows.reshape(-1, 1) * X[source], part, tables)
        return _fut.sample_outer_dft(Z, idx, n, factors, fut, scale, first)

    if slabs == factors[0]:
        return passed(0)
    return jax.lax.fori_loop(
        0, factors[0] // slabs, lambda t, out: out + passed(t * slabs),
        jnp.zeros((idx.shape[0], X.shape[1]), X.dtype))


@functools.lru_cache(maxsize=None)
def _mix_program():
    """The compiled apply, built at the first operand so that importing
    the sketch layer never pulls the engine."""
    from libskylark_tpu.engine.compiled import compiled

    return compiled(fjlt_mix_sample, name="sketch.fjlt_mix_sample",
                    static_argnames=("s_dim", "rowwise", "kernel", "block",
                                     "tile", "fut", "factors"))


@functools.lru_cache(maxsize=8)
def _dft_tables_on(factors: tuple, device) -> tuple:
    """``fut.dft_tables(factors)`` — of a pass, (ρ, f1, f2) — placed on
    ``device`` once: built at the first operand of that split and walk, an
    argument of every apply after it."""
    return tuple(jax.device_put(t, device) for t in _fut.dft_tables(factors))


def solver_fut(n: int) -> str:
    """The mixer the least-squares solvers give their FJLT over an axis of
    ``n`` (Blendenpik's choices are WHT, DCT and DHT): the Hadamard one
    where ``n`` is a power of two (±1 factors, the Pallas pass), else the
    DCT, upstream's own, which takes any ``n``. Either is the compiled,
    memory-bounded program above wherever ``fut.dft_factors(n)`` splits
    ``n`` (every power of two does; so does a multiple of 1000 up to 2²²
    with no prime factor past 256); a height it declines is mixed by the
    eager composition, ``lax.fft`` over complex copies of the operand."""
    return "wht" if n > 0 and not n & (n - 1) else "dct"


#: What the DCT / DHT route's temporaries may take of the device beside the
#: operand and the result (:func:`_dft_pass_bytes`; v5e compile,
#: ``memory_analysis``: 4.26 GB at 10⁶ = 100·125·80 × 1024 on ρ = 50).
_DFT_TEMP_BYTES = 5.0e9
#: The most an entry of a cut (N × tile) tile takes where one pass has all
#: R slabs: two arrays of N × tile float32 with their pads (8.3 bytes at
#: 10⁶, 9.1 at 2²¹ = 128³, where h = 65 is padded to 72).
_DFT_ENTRY_BYTES = 9.1


def _dft_pass_bytes(n: int, factors: tuple, slabs: int, w: int,
                    copied: bool) -> int:
    """What a pass of the DCT / DHT route over ``slabs`` slabs of the
    sampled digit and ``w`` free-axis entries holds, two float32 arrays at
    a time — the gathered rows and stage one's result, then the two stages'
    results (``fut.dft_pads`` of the pass) — and, where it reads a cut tile
    (``copied``), the (n × w) copy: beside them while another pass will
    read it, beside the gathered rows alone where one pass takes all R."""
    r, f1, f2 = factors
    f1p, hp, f2p, blocks = _fut.dft_pads((slabs, f1, f2))
    gathered, one = blocks * slabs * f1p, blocks * slabs * 2 * hp
    two = hp * slabs * 2 * f2p if f2 > 1 else 0
    rows = max(gathered + one, one + two)
    if copied:
        rows = rows + n if slabs < r else max(rows, n + gathered)
    return 4 * w * rows


def dft_slabs(n: int, factors: tuple, tile: int, m: int, rowwise: bool) -> int:
    """ρ: the slabs of the sampled digit r (of the R = ``factors[0]``) a
    pass of the DCT / DHT route takes, over ``tile`` of the ``m`` free-axis
    entries of an operand with a transform axis of ``n``: the largest
    divisor of R whose temporaries (:func:`_dft_pass_bytes`; the tile is a
    copy where it is cut or a rowwise operand's, transposed) stay under
    ``_DFT_TEMP_BYTES``. What an apply gathers does not depend on ρ: it is
    memory's alone. 10⁶ × 1024 columnwise: 50 (4.25 GB; all 100 to 602
    columns, one slab to ≈ 60,000)."""
    w, copied = min(tile, m), rowwise or m > tile
    return next((d for d in range(factors[0], 1, -1) if factors[0] % d == 0
                 and _dft_pass_bytes(n, factors, d, w, copied)
                 <= _DFT_TEMP_BYTES), 1)


def dft_tile(n: int) -> int:
    """Free-axis entries of a cut tile of the DCT / DHT route over a
    transform axis of ``n`` — a rowwise operand's, which is transposed
    first, and a columnwise one's past the width whose whole rows one slab
    fits at: the widest multiple of 128, to ``_DFT_TILE_MAX``, that
    ``_DFT_ENTRY_BYTES`` an entry keep under ``_DFT_TEMP_BYTES`` — 512 to
    n = 10⁶, 256 at 2²¹, 128 at 2²². The wider the better, to rows of
    2 KB: the two row gathers cost by the row there (``PERF.md`` §5)."""
    lanes = int(_DFT_TEMP_BYTES / (_DFT_ENTRY_BYTES * n)) // 128
    return 128 * max(1, min(lanes, _DFT_TILE_MAX // 128))


def _xla_plan(n: int, dtype) -> tuple:
    """(kernel, block, tile) of :func:`fjlt_mix_sample` on XLA for a
    transform axis of ``n``: the bfloat16 split is the MXU's and float32's."""
    split = jax.default_backend() == "tpu" and dtype == jnp.float32
    return "xla_bf16x3" if split else "xla_f32", min(n, MIX_BLOCK), MIX_TILE


def srht_serve_apply(key_data, A, *, s_dim: int, rowwise: bool):
    """Panel-free SRHT serve program (the ``sketch_apply`` executable
    body for the FJLT/``wht`` family, docs/serving):
    :func:`fjlt_mix_sample` on XLA — vmap-batchable, a pure function of
    the raw key data, the same streams and the same contraction as the
    transform's own apply. The transform axis is the exact (never padded)
    extent: the FWHT length defines the operator, so ``_sketch_statics``
    pads only the free axis for this family."""
    n = A.shape[1] if rowwise else A.shape[0]
    if n & (n - 1):
        raise ValueError(f"SRHT serve requires power-of-2 n, got {n}")
    kernel, block, tile = _xla_plan(n, A.dtype)
    return fjlt_mix_sample(jnp.asarray(key_data), A, s_dim=s_dim,
                           rowwise=rowwise, kernel=kernel, block=block,
                           tile=tile)


def _popcount_parity(a: np.ndarray) -> np.ndarray:
    """Elementwise popcount parity of a uint64 array. ``np.bitwise_count``
    when this numpy has it (>= 2.0); otherwise the xor-fold parity
    trick (six shifts — parity is all the Hadamard sign needs)."""
    if hasattr(np, "bitwise_count"):
        return np.bitwise_count(a) & np.uint64(1)
    for shift in (32, 16, 8, 4, 2, 1):
        a = a ^ (a >> np.uint64(shift))
    return a & np.uint64(1)


@register
class RFUT(SketchTransform):
    """X → F·D·X (output dim == input dim). ``dist`` fixed to Rademacher, the
    only use in the reference (FJLT's underlying mixer)."""

    sketch_type = "RFUT"

    def __init__(self, N, S=None, context=None, fut: str = "dct"):
        # RFUT preserves dimension; accept (N, context) calling style too.
        if context is None:
            context = S
            S = N
        self._fut_name = fut
        super().__init__(N, N, context)

    def _build(self):
        self._fut = make_fut(self._fut_name, self._N)

    def diagonal(self, dtype=jnp.float32) -> jnp.ndarray:
        return randgen.stream_slice(
            self.subkey(0), randgen.Rademacher(), 0, self._N, dtype=dtype
        )

    def _apply_columnwise(self, A: jnp.ndarray) -> jnp.ndarray:
        D = self.diagonal(A.dtype)
        return self._fut.apply(self._fut.scale() * D[:, None] * A, axis=0)

    def _apply_rowwise(self, A: jnp.ndarray) -> jnp.ndarray:
        D = self.diagonal(A.dtype)
        return self._fut.apply(self._fut.scale() * D[None, :] * A, axis=1)

    def _extra_params(self) -> dict[str, Any]:
        return {"fut": self._fut_name}

    @classmethod
    def _from_parts(cls, N, S, alloc, d):
        return cls(N, alloc, fut=d.get("fut", "dct"))


@register
class FJLT(SketchTransform):
    """Fast Johnson-Lindenstrauss transform (ref: sketch/FJLT_data.hpp):
    ``fut`` = ``"dct"`` (the default, as upstream's FFTW build), ``"dht"``
    or ``"wht"``. A float32 operand on one device is sketched by one
    compiled, memory-bounded program at every height N the mixer's rule
    takes (:meth:`mix_plan`: ``wht`` the powers of two; ``dct`` / ``dht``
    N = R·f1·f2 with f1, f2 ≤ 128 and R ≤ 256), by the eager composition
    elsewhere."""

    sketch_type = "FJLT"

    def __init__(self, N, S, context, fut: str = "dct"):
        self._fut_name = fut
        super().__init__(N, S, context)

    def _build(self):
        self._fut = make_fut(self._fut_name, self._N)

    def diagonal(self, dtype=jnp.float32) -> jnp.ndarray:
        """Rademacher mixing diagonal (sub-stream 0; the underlying RFUT's D)."""
        return randgen.stream_slice(
            self.subkey(0), randgen.Rademacher(), 0, self._N, dtype=dtype
        )

    def sample_indices(self) -> jnp.ndarray:
        """Uniform coordinate samples (sub-stream 1; ref: FJLT_data.hpp:83-86)."""
        return randgen.stream_slice(
            self.subkey(1),
            randgen.UniformInt(0, self._N - 1),
            0,
            self._S,
            dtype=jnp.int32,
        )

    def operator_panel(self, col_start: int, col_stop: int,
                       dtype=jnp.float32,
                       diagonal=None) -> np.ndarray:
        """Columns ``[col_start, col_stop)`` of the sampled-WHT operator
        in closed form, as a host array:
        ``S[k, j] = D[j] · (−1)^popcount(idx_k & j) / sqrt(s)`` — the
        Sylvester Hadamard entry at (sampled row ``idx_k``, position
        ``j``) times the Rademacher diagonal, scaled to ``1/sqrt(s)``
        (the FJLT's ``sqrt(n/s)`` times the WHT's ``1/sqrt(n)``).

        This is the positional column-panel stream the streaming SRHT
        appenders (:mod:`libskylark_tpu.sessions`) and the row-sharded
        partial sketches (:mod:`libskylark_tpu.dist`) fold against: a
        pure function of ``(seed, col_start, col_stop)``, so any
        process recomputes a shard's panel bit-identically. Only the
        ``wht`` mixer has this closed form (``n`` a power of two).

        ``diagonal`` lets a long-lived caller amortize the Rademacher
        stream: pass the FULL host :meth:`diagonal` (length ``n``,
        panel dtype) and only its slice is used — the sessions
        appender generates it once at open (thousands of small
        appends), while shard tasks omit it and materialize just their
        own O(shard) slice (``n`` may dwarf any one task). Both paths
        are bit-identical (positional streams)."""
        if self._fut_name != "wht":
            raise errors.UnsupportedError(
                "operator_panel is closed-form only for the 'wht' "
                f"(Sylvester-Hadamard) mixer, not {self._fut_name!r}")
        dt = np.dtype(dtype)
        # the s sampled rows never change for this instance: memoize
        # the host copy so a long panel stream pays that PRNG
        # generation and device->host transfer once, not per panel.
        # Runtime state only — never serialized (the OperatorCache
        # discipline).
        idx = self._host_sample_indices()
        cols = np.arange(col_start, col_stop, dtype=np.uint64)
        par = _popcount_parity(idx[:, None] & cols[None, :])
        signs = (1.0 - 2.0 * par).astype(dt)
        if diagonal is not None:
            diag = np.asarray(diagonal, dtype=dt)[col_start:col_stop]
        else:
            diag = np.asarray(randgen.stream_slice(
                self.subkey(0), randgen.Rademacher(), col_start,
                col_stop, dtype=dt))
        return (signs * diag) / np.asarray(math.sqrt(self._S), dt)

    def _host_sample_indices(self) -> np.ndarray:
        """Host uint64 copy of :meth:`sample_indices`, memoized (the
        ``operator_panel`` cache — shared so the panel oracle and the
        panel-free fold gather from literally the same host array)."""
        idx = getattr(self, "_panel_idx_cache", None)
        if idx is None:
            idx = np.asarray(self.sample_indices()).astype(np.uint64)
            self._panel_idx_cache = idx
        return idx

    def fold_rows(self, X, row_start: int, row_stop: int,
                  dtype=jnp.float32, diagonal=None) -> jnp.ndarray:
        """Panel-free partial fold: ``operator_panel(row_start,
        row_stop) @ X`` without materializing the O(rows·s) panel.

        The row range decomposes greedily into ≤ 2·log2(n) aligned
        power-of-two blocks ``[b, b+L)`` (``b % L == 0``); within one,
        ``popcount(idx_k & (b+j)) = popcount(idx_k & b) +
        popcount((idx_k mod L) & j)``, so the block's contribution is
        ``(−1)^popcount(idx_k & b) · FWHT_L(D_blk ⊙ X_blk)[idx_k mod
        L]`` — an O(L·log L·m) transform instead of an O(L·s) panel
        generation plus an O(L·s·m) contraction. Per-block signs and
        gather coordinates come host-side from the memoized sample
        indices (the same array the panel oracle uses), so the fold is
        the panel's bit pattern whenever every intermediate is exactly
        representable (integer-valued data, ``n``/``s`` even powers of
        two — the regression battery in tests/test_fwht.py), and
        allclose otherwise. ``diagonal`` follows the
        :meth:`operator_panel` contract: the FULL host diagonal, of
        which only ``[row_start:row_stop)`` is read."""
        if self._fut_name != "wht":
            raise errors.UnsupportedError(
                "fold_rows is closed-form only for the 'wht' "
                f"(Sylvester-Hadamard) mixer, not {self._fut_name!r}")
        dt = np.dtype(dtype)
        lo, hi = int(row_start), int(row_stop)
        X = jnp.asarray(X)
        if X.dtype != dt:
            X = X.astype(dt)
        if X.shape[0] != hi - lo:
            raise ValueError(
                f"operand rows {X.shape[0]} != range extent {hi - lo}")
        idx = self._host_sample_indices()
        out = jnp.zeros((self._S,) + X.shape[1:], dt)
        off = lo
        while off < hi:
            rem = hi - off
            block = 1 << (rem.bit_length() - 1)
            if off:
                block = min(block, off & -off)
            par = _popcount_parity(idx & np.uint64(off))
            signs = jnp.asarray((1.0 - 2.0 * par).astype(dt))
            gidx = jnp.asarray((idx & np.uint64(block - 1))
                               .astype(np.int32))
            if diagonal is not None:
                d = np.asarray(diagonal, dtype=dt)[off:off + block]
            else:
                d = randgen.stream_slice(
                    self.subkey(0), randgen.Rademacher(), off,
                    off + block, dtype=dt)
            w = d[:, None] * X[off - lo:off - lo + block]
            if block > 1:
                w = _fut.fwht(w, axis=0)
            out = out + signs[:, None] * w[gidx]
            off += block
        return (1.0 / math.sqrt(self._S)) * out

    def mix_plan(self, A, rowwise: bool):
        """(kernel, split, tile) of :func:`fjlt_mix_sample` for this
        operand, from the shapes and the device alone — ``split`` the rows
        mixed in full (``wht``) or the factors (R, f1, f2) of the blocked
        DFT (``dct`` / ``dht``), ``tile`` the free-axis entries a pass takes
        (``dct`` / ``dht``: a columnwise operand's whole rows wherever one
        slab of the sampled digit fits the budget, else :func:`dft_tile`)
        — or None where the eager composition below
        serves: another dtype than float32; an axis the mixer's rule
        declines — ``wht`` takes the powers of two, ``dct`` and ``dht``
        every N = R·f1·f2 with 2 ≤ f1 ≤ 128, f2 ≤ 128, R ≤ 256
        (``fut.dft_factors``: to 2²², no prime factor past 256; 1,000,000 =
        100·125·80) —; an operand that lies on more than one device (the
        composition's FUT runs a column shard at a time under XLA's
        partitioner; the program's tile walk would slice the sharded axis).
        A traced operand shows no placement and is taken as the dense
        sketches take it (``dense.pallas_ambient_ok``): the kernel on a
        process of one device, else the XLA route, which is right under any
        sharding — but where the caller's operand is in fact sharded along
        its free axis, the partitioner gathers it whole for the tile walk."""
        if A.dtype != jnp.float32:
            return None
        hadamard = self._fut_name == "wht"
        factors = None if hadamard else _fut.dft_factors(self._N)
        if (self._N & (self._N - 1)) if hadamard else (factors is None):
            return None                 # a height the mixer's rule declines
        traced = isinstance(A, jax.core.Tracer)
        if not traced and len(A.devices()) > 1:
            return None
        if not hadamard:
            # whole rows of a columnwise operand wherever a slab of them fits
            m = A.shape[0] if rowwise else A.shape[1]
            whole = not rowwise and _dft_pass_bytes(
                self._N, factors, 1, m, False) <= _DFT_TEMP_BYTES
            return "xla_dft", factors, m if whole else dft_tile(self._N)
        on_tpu = jax.default_backend() == "tpu"
        if on_tpu and not rowwise and (not traced or jax.device_count() == 1):
            from libskylark_tpu.sketch import pallas_wht    # pulls pallas

            served = pallas_wht.plan(A.shape, A.dtype)
            if served is not None:
                return ("pallas_blocks",) + served
        return _xla_plan(self._N, A.dtype)

    def _mix_sample(self, A, rowwise: bool):
        """The apply as the one ``sketch.fjlt_mix_sample`` program (under a
        caller's trace: part of the caller's program); None where
        :meth:`mix_plan` declines."""
        plan = self.mix_plan(A, rowwise)
        if plan is None:
            return None
        kernel, split, tile = plan
        statics = dict(s_dim=self._S, rowwise=rowwise, kernel=kernel,
                       tile=tile)
        if kernel == "xla_dft":
            statics.update(fut=self._fut_name, factors=split)
            factors = split
        else:
            statics.update(block=split)
            factors = (self._N // split,) + _fut.block_factors(split)
        key_data = self._alloc.key_data
        if isinstance(A, jax.core.Tracer):
            return fjlt_mix_sample(key_data, A, **statics)
        columns = A.shape[0] if rowwise else A.shape[1]
        attrs = {"path": "fut", "family": self.sketch_type,
                 "fut": self._fut_name, "kernel": kernel,
                 "factors": factors, "tile": tile,
                 "elements": self._N * columns, "sampled": self._S * columns}
        tables = ()
        if kernel == "xla_dft":
            attrs["slabs"] = dft_slabs(self._N, split, tile, columns, rowwise)
            tables = _dft_tables_on((attrs["slabs"],) + split[1:],
                                    next(iter(A.devices())))
        else:
            # the Pallas pass leaves all the columns to the sampled factor,
            # the XLA walk a tile of them
            width = columns if kernel == "pallas_blocks" else min(tile, columns)
            attrs["sample_chunk"] = _fut.sample_outer_chunk(
                factors[0], width, self._S, A.dtype.itemsize)
        with _trace.span("sketch.dispatch", attrs):
            out = _mix_program()(key_data, A, *tables, **statics)
        _MIXED.inc_always(attrs["elements"], family=self.sketch_type,
                          kernel=kernel)
        return out

    def _apply_axis(self, A: jnp.ndarray, axis: int) -> jnp.ndarray:
        out = self._mix_sample(A, rowwise=axis == 1)
        if out is not None:
            return out
        return _fut.sign_mix_sample(
            self._fut.apply, A, self.diagonal(A.dtype), self.sample_indices(),
            self._fut.scale(), math.sqrt(self._N / self._S), axis)

    def _apply_columnwise(self, A: jnp.ndarray) -> jnp.ndarray:
        return self._apply_axis(A, 0)

    def _apply_rowwise(self, A: jnp.ndarray) -> jnp.ndarray:
        return self._apply_axis(A, 1)

    def _extra_params(self) -> dict[str, Any]:
        return {"fut": self._fut_name}

    @classmethod
    def _from_parts(cls, N, S, alloc, d):
        return cls(N, S, alloc, fut=d.get("fut", "dct"))
