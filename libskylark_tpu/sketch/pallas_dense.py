"""Pallas TPU kernel: fused on-the-fly sketch generation + matmul.

The hot primitive of the framework (ref: SURVEY.md §3.1 — the reference's
blocked panel algorithm in sketch/dense_transform_Elemental_mc_mr.hpp with
``realize_matrix_view`` generating S panels on demand). Each
(S_dim × BLOCK_COLS) panel of S is generated on the VPU — exact same bits
as :func:`randgen.dense_block`, via the shared integer-op Threefry in
base/threefry.py — and contracted on the MXU. Inside one grid step the two
do NOT overlap (PERF.md §6, PR 27: a step costs generation plus matmul, at
≈ 46 G entries/s on a v5e), so what matters is how often a panel is made.
:func:`operator_residency` decides, from shapes only, where the operator
lives between the m-tiles of one apply: ``"vmem"`` (small S: generated in
the first m-tile sweep into VMEM scratch), ``"hbm"`` (big S, either
orientation: a generation kernel writes S once an apply to HBM, scale
folded in, as the bf16 hi/lo planes the contraction needs, and a
contraction kernel streams them beside the A tiles) or ``"per_tile"`` (a
single m-tile: generated in the grid step that contracts it). Nothing is
kept across applies.

Rowwise (out = A·Sᵀ, the regime of BASELINE config 1) and columnwise
(out = S·A) applies; inputs the kernel declines (wrong backend,
distribution, dtype, no tile inside the VMEM plan) take the XLA path in
sketch/dense.py. A kernel the dispatch selected and Mosaic then rejects is
a bug and raises — it never turns into the XLA path silently.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import jax.random as jr
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from libskylark_tpu.base import randgen, threefry as tf
from libskylark_tpu.sketch import params as sketch_params
from libskylark_tpu.sketch.cos_turns import TURN, cos_turns
from libskylark_tpu.sketch.dense import BLOCK_COLS  # the stream format's
# panel width — single source of truth (dense.py imports this module only
# lazily, so no cycle)
from libskylark_tpu.sketch.transform import note_apply
from libskylark_tpu.telemetry import trace as _trace

_HALF = BLOCK_COLS // 2


def available() -> bool:
    """True when the default backend compiles Mosaic kernels."""
    return jax.default_backend() == "tpu"


def compiler_params(*dimension_semantics: str):
    """Mosaic compiler params of every ``pallas_call`` in
    sketch/pallas_*.py whose tile plan targets Mosaic's default scoped
    VMEM (``_VMEM_BUDGET_BYTES``, 16 MiB of a v5e core's 128): no
    ``vmem_limit_bytes``. At the headline widths every such kernel that
    compiles on a v5e compiles inside it (PERF.md, PR 21); the one
    rejection seen since (m_tile 1024 at s_dim 1024, PR 27) was a plan
    that left the matmul's result tile out (:func:`_vmem_estimate`).
    Two families plan past the scope and pass what they fitted: the
    "hbm" contraction's grown row tile (:func:`_contraction_params`,
    PR 49) and pallas_wht.py's mixers (PR 39)."""
    return pltpu.CompilerParams(dimension_semantics=dimension_semantics)


def _gen_block(dist_kind, s_dim, keys_ref, k, row0=0, rows=None):
    """Generate rows [row0, row0 + rows) of operator column block k
    (default: all s_dim) in VMEM — bit-identical to randgen.dense_block's
    threefry-pair layout. Rows of S are independent in the stream
    (c[r, j] = r·128 + j), so an s-tile is the same counters offset by
    its first row; ``row0`` may be traced."""
    rows = s_dim if rows is None else rows
    k0 = keys_ref[k, 0]
    k1 = keys_ref[k, 1]
    r = jax.lax.broadcasted_iota(jnp.uint32, (rows, _HALF), 0)
    if not (isinstance(row0, int) and row0 == 0):
        r = r + jnp.asarray(row0).astype(jnp.uint32)
    c = r * _HALF + jax.lax.broadcasted_iota(jnp.uint32, (rows, _HALF), 1)
    b0, b1 = tf.threefry2x32(k0, k1, c, c + s_dim * _HALF)
    if dist_kind == "normal":
        s0, s1 = tf.bits_to_normal(b0), tf.bits_to_normal(b1)
    elif dist_kind == "cauchy":
        s0, s1 = tf.bits_to_cauchy(b0), tf.bits_to_cauchy(b1)
    elif dist_kind == "rademacher":
        s0, s1 = tf.bits_to_rademacher(b0), tf.bits_to_rademacher(b1)
    else:
        raise NotImplementedError(dist_kind)
    return jnp.concatenate([s0, s1], axis=1)  # (rows, BLOCK_COLS)


def _accumulate(out_ref, acc, k):
    @pl.when(k == 0)
    def _init():
        out_ref[:] = acc

    @pl.when(k != 0)
    def _acc():
        out_ref[:] += acc


def _bf16_dot(a, b, dims):
    """One bf16 MXU pass with f32 accumulation. Precision pinned
    explicitly: the package-level default matmul precision is "highest",
    which on bf16 operands asks Mosaic for an fp32 contraction it can't
    lower ("Bad lhs type")."""
    return jax.lax.dot_general(
        a.astype(jnp.bfloat16),
        b.astype(jnp.bfloat16),
        dims,
        precision=jax.lax.Precision.DEFAULT,
        preferred_element_type=jnp.float32,
    )


def _dot(lhs, rhs, dims, precision, gen_side=1):
    """MXU contraction at the requested precision regime.

    ``gen_side`` names the operand (0=lhs, 1=rhs) that is the GENERATED
    operator block — only the "bf16gen2" regime uses it: the operator
    is rounded to bf16 (by that regime's definition the rounded values
    ARE the operator — exact in every later bf16 pass), so only the
    data side needs the error-compensated hi/lo split: 2 MXU passes
    for f32-grade accuracy w.r.t. the rounded operator, vs bf16x3's 3
    passes for the f32 operator.

    ``"bf16x3"`` (the default, set in sketch/params.py): 3-pass
    error-compensated bf16 split (spelled out below; Mosaic has no
    ``Precision.HIGH`` lowering) — f32-grade rounding at roughly twice
    the MXU rate of HIGHEST, checked on chip against the XLA path at
    1e-4 (chip_smoke.py). The explicit hi/lo split
    performs real bf16 rounding in interpret mode too, so both the
    interpreter and the on-chip test exercise the same arithmetic.
    ``"f32"``: full-f32 passes (``Precision.HIGHEST``) — the conservative
    regime; keeps the fused apply inside the framework's 1e-4
    determinism oracle vs the XLA/CPU path on deep contractions.
    ``"bf16"``: single-pass bf16 inputs + f32 accumulation — the fastest
    MXU regime; contraction rounds at ~2⁻⁸ relative, which EXCEEDS the
    1e-4 oracle for large N (quantified in tests/test_pallas_dense.py), so
    callers opt in explicitly for throughput-only work."""

    bf16_dot = functools.partial(_bf16_dot, dims=dims)
    if precision == "bf16":
        return bf16_dot(lhs, rhs)
    if precision == "bf16gen2":
        if gen_side == 0:
            rhs_hi = rhs.astype(jnp.bfloat16).astype(jnp.float32)
            return bf16_dot(lhs, rhs_hi) + bf16_dot(lhs, rhs - rhs_hi)
        lhs_hi = lhs.astype(jnp.bfloat16).astype(jnp.float32)
        return bf16_dot(lhs_hi, rhs) + bf16_dot(lhs - lhs_hi, rhs)
    if precision == "bf16x3":
        # Error-compensated 3-pass split. Mosaic has no lowering for
        # Precision.HIGH (verified on v5e: "Unsupported dot precision:
        # HIGH"), so the split is spelled out: x = hi + lo with hi the
        # bf16 rounding of x; hi·hi + hi·lo + lo·hi recovers all but the
        # lo·lo term (~2⁻¹⁶ relative) — f32-grade for the 1e-4 oracle.
        lhs_hi = lhs.astype(jnp.bfloat16).astype(jnp.float32)
        rhs_hi = rhs.astype(jnp.bfloat16).astype(jnp.float32)
        lhs_lo = lhs - lhs_hi
        rhs_lo = rhs - rhs_hi
        return bf16_dot(lhs_hi, rhs_hi) + (
            bf16_dot(lhs_hi, rhs_lo) + bf16_dot(lhs_lo, rhs_hi)
        )
    return jax.lax.dot_general(
        lhs,
        rhs,
        dims,
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


# Per-core VMEM budget the tile plans target: Mosaic's default SCOPED
# limit (16 MiB — not the core's VMEM, which is 128 MiB on a v5e by
# pltpu.get_tpu_info(); PERF.md §6, PR 27). A plan past the scope is a
# Mosaic rejection, which raises — but for the "hbm" contraction's row
# tile, grown under :func:`_vmem_cap` with the limit passed (PR 49).
_VMEM_BUDGET_BYTES = 16 * 1024 * 1024

# Cap on the "vmem" residency (:func:`operator_residency`): when the
# full virtual S fits, each block is generated ONCE (first m-tile sweep)
# into VMEM scratch and every later tile contracts against the cached
# copy. A larger operator is kept in HBM instead (the "hbm" residency).
# Must leave room for Mosaic's double-buffered
# A/out tiles inside _VMEM_BUDGET_BYTES (advisor r2 medium finding: the
# old 48 MiB default exceeded the scoped limit and could fail Mosaic
# compilation outright on the shard_map path).
_SCRATCH_CAP_BYTES = 8 * 1024 * 1024


def _vmem_estimate(m_tile: int, s_tile: int, scratch_bytes: int) -> int:
    """Per-core scoped-VMEM plan for one grid step: double-buffered A tile
    (m_tile × BLOCK_COLS) and out tile (m_tile × s_tile), ONE more
    m_tile × s_tile for the matmul result before it is accumulated, the
    generated operator block + generation temporaries
    (~4 × s_tile × BLOCK_COLS), plus the optional operator-cache scratch.
    ``s_tile`` is the width of the result tile: the whole s_dim unless
    :func:`_tile_plan` had to tile it.
    Held against what Mosaic itself asks for at s_tile = 1024 (least
    ``vmem_limit_bytes`` that compiles for a v5e, PR 27): m_tile 512
    plans 11.0 MiB here and needs 9.4 (9.9 under the "hbm" residency);
    m_tile 1024 plans 18 and needs 17.0 either way — over the 16 MiB
    scope, which Mosaic refused on the chip when the plan left the
    result tile out. The "hbm" residency's contraction kernel swaps the
    generation term for its double-buffered plane tiles and the A-tile
    split (2.9 MiB against 2.4 at s_tile = 1024), inside the same term,
    so a tile planned here fits there too."""
    return 4 * (
        2 * m_tile * BLOCK_COLS
        + 3 * m_tile * s_tile
        + 4 * s_tile * BLOCK_COLS
    ) + scratch_bytes


def operator_residency(s_dim: int, n: int, m: int, m_tile: int,
                       s_tile: Optional[int] = None) -> str:
    """Where the generated operator lives between the m-tiles of ONE
    apply, from the padded shapes alone, whichever the orientation (``m``
    is the extent that is tiled: rows of a rowwise operand, columns of a
    columnwise one) — the single rule the ``pallas_call`` sites,
    :func:`effective_plan` and the ``sketch.apply`` span
    all read:

    ``"per_tile"``  a single m-tile: nothing to reuse, each block is
                    generated in the grid step that contracts it.
    ``"vmem"``      S fits the scratch cap and the VMEM plan: generated
                    during the first m-tile sweep into VMEM scratch.
    ``"hbm"``       S too big for VMEM: generated once an apply into HBM
                    by its own kernel, streamed by the contraction
                    kernel (:func:`_planes_call`).

    ``s_tile`` (default: s_dim) is the result tile's width under an
    s-tiled plan; the operator kept is always the whole (s_dim × n).

    Reading an entry back costs 4 B ÷ 819 GB/s ≈ 5 ps against ≈ 22 ps to
    regenerate it (≈ 46 G entries/s on a v5e, PERF.md §6 PR 27), so
    keeping S pays from the second m-tile on. Nothing is kept ACROSS
    applies: every apply regenerates, once."""
    if m // m_tile <= 1:
        return "per_tile"
    scratch_bytes = s_dim * n * 4
    if (scratch_bytes <= _SCRATCH_CAP_BYTES
            and _vmem_estimate(m_tile, s_tile or s_dim, scratch_bytes)
            <= _VMEM_BUDGET_BYTES):
        return "vmem"
    return "hbm"


def _resolve_block(dist_kind, s_dim, keys_ref, k, s_scr, row0=0, rows=None):
    """Rows [row0, row0 + rows) of operator block k (default: all): from
    the VMEM cache when present (filled during the first m-tile sweep),
    else regenerated in place."""
    if s_scr is None:
        return _gen_block(dist_kind, s_dim, keys_ref, k, row0, rows)
    at = (slice(None) if rows is None else pl.ds(row0, rows),
          pl.ds(k * BLOCK_COLS, BLOCK_COLS))

    @pl.when(pl.program_id(0) == 0)
    def _gen():
        s_scr[at] = _gen_block(dist_kind, s_dim, keys_ref, k, row0, rows)

    return s_scr[at]


def _finisher(scale_ref, epilogue, operand_refs):
    """What finishes a result tile in VMEM once its last k step is in
    (shared by every rowwise kernel), or None: the tile scale of
    :func:`_tile_scaled`, then the fused epilogue of
    :func:`_cos_epilogue` — ``epilogue = ("cos_turns", outscale)`` with
    ``operand_refs = (sc_ref, sh_ref)``, both in turns →
    outscale·cos(2π·(acc·sc + sh)) by :func:`cos_turns` (ref:
    RFT_Elemental.hpp:83-156, the reference's fused elementwise loops):
    the output never makes the extra HBM round-trip a separate
    elementwise op would cost, and the cosine is an exact reduction and
    one polynomial, not the stock lowering (11 of the 20 ms of an
    ``rft_features_apply`` apply on a v5e, PERF.md §6 PR 38)."""
    if scale_ref is None and epilogue is None:
        return None

    def finish(acc):
        if scale_ref is not None:
            acc = scale_ref[0] * acc
        if epilogue is not None:
            kind, outscale = epilogue
            assert kind == "cos_turns"
            sc_ref, sh_ref = operand_refs
            acc = cos_turns(acc * sc_ref[:] + sh_ref[:], outscale)
        return acc

    return finish


def _cos_epilogue(sc, sh, inscale: float, outscale: float):
    """``(extra_operands, epilogue)`` of a rowwise call that finishes its
    tiles with outscale·cos(acc·inscale·sc + sh), ``sc`` / ``sh`` the
    (s_dim,) per-feature scales and shifts in radians: the two
    (1, s_dim) vectors are handed over in turns, ``inscale`` folded in —
    s-length work inside the caller's executable, once an apply, which
    also takes a multiply a value out of the epilogue."""
    sc = sc.astype(jnp.float32).reshape(1, -1) * (inscale / TURN)
    sh = sh.astype(jnp.float32).reshape(1, -1) / TURN
    return (sc, sh), ("cos_turns", float(outscale))


# Entries of a result tile the finisher takes at a time: 64 rows of a
# 1024-wide tile, 256 KiB an intermediate.
_FINISH_SLAB = 64 * 1024


def _finish_in_place(out_ref, finish):
    """``finish`` over the finished tile, a slab of whole rows
    (``_FINISH_SLAB`` entries, at least a sublane group) at a time, the
    slabs unrolled. Applied to the whole tile at once every intermediate
    of the elementwise chain is a tile-sized VMEM temporary: the
    polynomial cosine then asks Mosaic for 19.5 MiB of its 16 where a
    512 × 1024 tile is accumulated over k steps; in slabs the temporaries
    are a slab each. Unrolled, because straight-line code is what
    Mosaic's scheduler lays under the MXU's passes — the cosine then
    costs 0.1 ms of a 9.9 ms apply on a v5e, against 2.8 ms as a
    ``fori_loop`` over slabs (a block of its own: nothing overlaps it).
    Few slabs, because each is traced and lowered again: 64 of 8 rows
    put 0.37 s on every process's set-up (PERF.md §6, PR 38)."""
    m_tile, s_tile = out_ref.shape
    step = max(8, _FINISH_SLAB // s_tile // 8 * 8)
    for row in range(0, m_tile, step):
        rows = slice(row, min(row + step, m_tile))
        out_ref[rows, :] = finish(out_ref[rows, :])


def _store(out_ref, acc, k, n_blocks, finish):
    """out_tile (+)= acc over the k steps of one result tile, ``finish``
    (:func:`_finisher`) applied in place after the last
    (:func:`_finish_in_place`)."""
    if n_blocks == 1:
        out_ref[:] = acc
        if finish is not None:
            _finish_in_place(out_ref, finish)
        return
    _accumulate(out_ref, acc, k)
    if finish is not None:
        @pl.when(k == n_blocks - 1)
        def _finish():
            _finish_in_place(out_ref, finish)


def _row0(s_dim, s_tile, j):
    """First operator row of s-tile ``j`` (a plain 0 when S is not tiled:
    the kernel is then the untiled one, to the instruction)."""
    return 0 if s_tile == s_dim else j * s_tile


def _kernel(dist_kind, s_dim, s_tile, n_blocks, precision, epilogue,
            keys_ref, a_ref, *refs):
    """Rowwise, operator generated in the kernel ("vmem" / "per_tile"):
    out_tile += A_tile @ S_blkᵀ (S entries are bit-exact; only the
    contraction rounds, per the ``precision`` regime), S_blk the
    ``s_tile`` rows of block k this result tile takes. ``refs`` =
    (*epilogue operands, out[, operator-cache scratch]); the optional
    epilogue finishes the tile in VMEM (:func:`_finisher`)."""
    n_operands = 0 if epilogue is None else 2
    operand_refs, out_ref = refs[:n_operands], refs[n_operands]
    s_scr = refs[n_operands + 1] if len(refs) > n_operands + 1 else None
    k = pl.program_id(2)
    S_blk = _resolve_block(dist_kind, s_dim, keys_ref, k, s_scr,
                           _row0(s_dim, s_tile, pl.program_id(1)), s_tile)
    acc = _dot(a_ref[:], S_blk, (((1,), (1,)), ((), ())), precision,
               gen_side=1)
    _store(out_ref, acc, k, n_blocks,
           _finisher(None, epilogue, operand_refs))


def _kernel_cw(dist_kind, s_dim, m_tile, precision, keys_ref, a_ref, out_ref,
               s_scr=None):
    """Columnwise: out_tile += S_blk @ A_blk (same precision regime)."""
    k = pl.program_id(1)
    S_blk = _resolve_block(dist_kind, s_dim, keys_ref, k, s_scr)
    acc = _dot(S_blk, a_ref[:], (((1,), (0,)), ((), ())), precision,
               gen_side=0)
    _accumulate(out_ref, acc, k)


def _operator_scratch(residency: str, s_dim: int, n: int) -> list:
    """Scratch shapes of an in-kernel-generation call: the whole
    operator under "vmem" (filled by the first m-tile sweep), else
    none."""
    if residency == "vmem":
        return [pltpu.VMEM((s_dim, n), jnp.float32)]
    return []


def _grid_params(residency: str, *inner: str):
    """dimension_semantics for pallas_call: the VMEM operator cache needs
    strictly sequential grid order (the i==0 sweep fills it) — no
    megacore splitting over the m-tile dimension (nor, rowwise, over the
    s-tiles under it: ``inner``)."""
    if residency == "vmem":
        return compiler_params(*["arbitrary"] * (len(inner) + 2))
    return compiler_params("parallel", *inner, "arbitrary")


# ---------------------------------------------------------------------------
# the "hbm" residency: generate once an apply, contract against the planes
# ---------------------------------------------------------------------------


def _plane_dtypes(precision: str) -> tuple:
    """The stored form of the operator per contraction regime — what the
    MXU passes of :func:`_dot` consume: "bf16x3" the bf16 hi/lo pair
    (2 × 2 B an entry, what one f32 plane takes), "bf16"/"bf16gen2" one
    bf16 plane, "f32" (and anything else, as in ``_dot``) one f32 plane."""
    if precision == "bf16x3":
        return (jnp.bfloat16, jnp.bfloat16)
    if precision in ("bf16", "bf16gen2"):
        return (jnp.bfloat16,)
    return (jnp.float32,)


def _tile_scaled(precision: str, scale) -> bool:
    """"bf16gen2" keeps its definition under "hbm" (sketch/params.py:
    the operator is scale × the bf16 rounding of the UNIT stream): its
    plane holds bf16(S) and the scale finishes the tile. Every other
    regime folds the scale into the planes."""
    return scale is not None and precision == "bf16gen2"


def _kernel_gen(dist_kind, s_dim, s_tile, scaled, keys_ref, *refs):
    """Generation kernel: the ``s_tile`` rows of column block k of the
    operator that grid step (j, k) owns — the same Threefry counters and
    bits as :func:`_gen_block` everywhere else — times ``scale``, written
    as the planes of :func:`_plane_dtypes`."""
    if scaled:
        scale_ref, *plane_refs = refs
    else:
        scale_ref, plane_refs = None, refs
    S = _gen_block(dist_kind, s_dim, keys_ref, pl.program_id(1),
                   _row0(s_dim, s_tile, pl.program_id(0)), s_tile)
    if scaled:
        S = S * scale_ref[0]
    hi = S.astype(plane_refs[0].dtype)
    plane_refs[0][:] = hi
    if len(plane_refs) == 2:
        plane_refs[1][:] = (S - hi.astype(jnp.float32)).astype(
            plane_refs[1].dtype)


def _operator_planes(keys, scale, *, s_dim, dist_kind, precision,
                     s_tile=None, interpret=False):
    """S (s_dim × n_blocks·BLOCK_COLS), generated once into HBM as the
    planes ``precision`` contracts with: ``hi = bf16(scale·S)`` and, for
    "bf16x3", ``lo = bf16(scale·S − hi)``. ``scale`` None (or the
    "bf16gen2" regime, :func:`_tile_scaled`) stores the unit stream. A
    grid step makes ``s_tile`` rows (default: all) of one column block."""
    s_tile = s_tile or s_dim
    n_blocks = keys.shape[0]
    scaled = scale is not None and not _tile_scaled(precision, scale)
    operands, in_specs = [keys], [pl.BlockSpec(memory_space=pltpu.SMEM)]
    if scaled:
        operands.append(jnp.asarray(scale, jnp.float32).reshape(1))
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
    dtypes = _plane_dtypes(precision)
    return pl.pallas_call(
        functools.partial(_kernel_gen, dist_kind, s_dim, s_tile, scaled),
        grid=(s_dim // s_tile, n_blocks),
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((s_tile, BLOCK_COLS), lambda j, k: (j, k),
                                memory_space=pltpu.VMEM) for _ in dtypes],
        out_shape=[jax.ShapeDtypeStruct((s_dim, n_blocks * BLOCK_COLS), dt)
                   for dt in dtypes],
        compiler_params=compiler_params("parallel", "parallel"),
        interpret=interpret,
    )(*operands)


def _dot_planes(a, planes, precision, gen_side=1):
    """The data tile ``a`` against the stored planes — A_tile · S_blkᵀ
    (``gen_side`` 1: the operator is the rhs) or S_blk · A_blk (0: the
    lhs): the products :func:`_dot` issues for that side, in its order,
    with the operator's split already made — only the data tile is split
    here, on the VPU."""
    dims = (((1,), (1 if gen_side else 0,)), ((), ()))

    def sides(x, s):
        return (x, s) if gen_side else (s, x)

    if planes[0].dtype == jnp.float32:
        return jax.lax.dot_general(
            *sides(a, planes[0]), dims, precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
    if precision == "bf16":
        return _bf16_dot(*sides(a, planes[0]), dims)
    a_hi = a.astype(jnp.bfloat16)
    a_lo = a - a_hi.astype(jnp.float32)
    if precision == "bf16gen2":
        return _bf16_dot(*sides(a_hi, planes[0]), dims) + _bf16_dot(
            *sides(a_lo, planes[0]), dims)
    lhs, rhs = sides((a_hi, a_lo), planes)
    return _bf16_dot(lhs[0], rhs[0], dims) + (
        _bf16_dot(lhs[0], rhs[1], dims) + _bf16_dot(lhs[1], rhs[0], dims))


def _kernel_planes(n_blocks, precision, n_planes, tile_scaled, epilogue,
                   gen_side, *refs):
    """Contraction kernel of the "hbm" residency: out_tile += A_tile @
    S_blkᵀ with the (s_tile × k) plane tiles streamed in beside the
    (m_tile × k) A tile (k: :func:`_plane_step_cols`), or — ``gen_side``
    0, columnwise — out_tile += S_blk @ A_blk with the (s_dim × k) plane
    tiles beside the (k × m_tile) A tile. No generation, no operator
    split, no iota inside the m × s × k loop; k is the last grid axis.
    ``refs`` = ([scale], a, *planes, *epilogue operands, out)."""
    refs = list(refs)
    scale_ref = refs.pop(0) if tile_scaled else None
    a_ref, plane_refs = refs[0], refs[1:1 + n_planes]
    *operand_refs, out_ref = refs[1 + n_planes:]
    acc = _dot_planes(a_ref[:], [p[:] for p in plane_refs], precision,
                      gen_side)
    _store(out_ref, acc, pl.program_id(2 if gen_side else 1), n_blocks,
           _finisher(scale_ref, epilogue, operand_refs))


def _plane_step_cols(n: int, m_tile: int, s_tile: int,
                     lhs_f32: bool = False) -> int:
    """Columns of A and of the planes one contraction step takes: two
    BLOCK_COLS blocks where n divides and the plan fits, else one. The
    wider step halves the grid steps and the out tile's
    read-modify-writes — 23.1 → 22.1 ms an apply at 65536 × 8192 → 1024
    on a v5e; four blocks bought nothing more (PERF.md §6, PR 27). At
    n ≤ 512 (the feature maps' input widths) the wide step is the whole
    contraction: the tile is written once, finished (:func:`_store`).

    The plan is the contraction kernel's own, fitted to what Mosaic asks
    for (least ``vmem_limit_bytes`` that compiles for a v5e, eleven
    shapes, PR 27): 4·(2.9·m_tile·k + 3·m_tile·s_tile) + 9.8·s_tile·k
    bytes at "bf16x3" — A tile double-buffered plus its hi/lo split, out
    tile double-buffered plus the matmul result, plane tiles
    double-buffered plus what is loaded of them; "f32" needs up to
    8·m_tile·k more, hence the 5. A columnwise step holds the same three
    tiles transposed (A k × m_tile, out s_dim × m_tile, planes
    s_dim × k) and asks for less (14.0 MiB of the 16.0 planned at
    512 × 1024 × 512, "bf16x3") — but for ``lhs_f32``: the "f32" regime's
    plane tile is there the LEFT operand of the HIGHEST contraction,
    which Mosaic splits whole, 20 B an entry and not 10 (sixteen shapes,
    PR 36). That regime alone steps down to half a block where one does
    not fit (s_dim 1536 at m_tile 512 asks 18.5 MiB at 256 columns).
    The plan is :func:`_contraction_vmem`; a row tile grown past the
    scope keeps the step of the tile inside it (:func:`_contraction`)."""
    for cols in (2 * BLOCK_COLS, BLOCK_COLS):
        if n % cols == 0 and _contraction_vmem(
                m_tile, s_tile, cols, lhs_f32) <= _VMEM_BUDGET_BYTES:
            return cols
    return BLOCK_COLS // 2 if lhs_f32 else BLOCK_COLS


def _planes_call(A, keys, scale, extra_operands, *, s_dim, s_tile, dist_kind,
                 m_tile, precision, interpret, epilogue, rowwise=True):
    """The "hbm" residency: two ``pallas_call``s in one executable — the
    planes of S (s_dim × n), then ``scale``·A·Sᵀ of the padded A (m, n)
    over the grid (row tile, s-tile, k) or, ``rowwise`` False,
    ``scale``·S·A of A (n, m) over (column tile, k), the result tile all
    s_dim rows. With no scratch every axis but k stays "parallel"."""
    m, n = A.shape if rowwise else A.shape[::-1]
    k_cols, vmem_limit = _contraction(
        n, m_tile, s_tile, _lhs_f32(precision, rowwise),
        epilogue is not None)
    n_blocks = n // k_cols
    planes = _operator_planes(keys, scale, s_dim=s_dim, dist_kind=dist_kind,
                              precision=precision, s_tile=s_tile,
                              interpret=interpret)
    tile_scaled = _tile_scaled(precision, scale)
    vmem = functools.partial(pl.BlockSpec, memory_space=pltpu.VMEM)
    if rowwise:
        grid = (m // m_tile, s_dim // s_tile, n_blocks)
        a_spec = vmem((m_tile, k_cols), lambda i, j, k: (i, k))
        plane_spec = vmem((s_tile, k_cols), lambda i, j, k: (j, k))
        out_spec = vmem((m_tile, s_tile), lambda i, j, k: (i, j))
        out_shape = (m, s_dim)
    else:
        grid = (m // m_tile, n_blocks)
        a_spec = vmem((k_cols, m_tile), lambda j, k: (k, j))
        plane_spec = vmem((s_dim, k_cols), lambda j, k: (0, k))
        out_spec = vmem((s_dim, m_tile), lambda j, k: (0, j))
        out_shape = (s_dim, m)
    operands, in_specs = [], []
    if tile_scaled:
        operands.append(jnp.asarray(scale, jnp.float32).reshape(1))
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
    operands += [A, *planes, *extra_operands]
    in_specs += [a_spec] + [plane_spec for _ in planes] + [
        vmem((1, s_tile), lambda i, j, k: (0, j)) for _ in extra_operands]
    return pl.pallas_call(
        functools.partial(_kernel_planes, n_blocks, precision, len(planes),
                          tile_scaled, epilogue, int(rowwise)),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct(out_shape, jnp.float32),
        # a row tile past the default scope asks for the plan it fitted
        compiler_params=_contraction_params(len(grid), vmem_limit),
        interpret=interpret,
    )(*operands)


def _rowwise_pallas_call(A, keys, scale, extra_operands, *, s_dim, dist_kind,
                         m_tile, precision, interpret, s_tile=None,
                         epilogue=None):
    """``scale``·A·Sᵀ (``scale`` None: unscaled) of the UNPADDED A by the
    kernel(s) of the operand's :func:`operator_residency`, the zero
    padding and the slice back inside the caller's one executable
    (:func:`_padded`: exact). ``extra_operands`` are the (1, s_dim) VMEM
    vectors of ``epilogue`` (:func:`_finisher`); ``s_tile`` (default:
    s_dim) is the result tile's width (:func:`_tile_plan`).

    "hbm": :func:`_planes_call`, scale folded into the planes. "vmem" /
    "per_tile": one call that generates in the kernel — grid, key-table
    SMEM spec, A-tile spec, accumulator out spec, operator scratch — and
    the scale as a pass over its result."""
    s_tile = s_tile or s_dim
    rows = A.shape[0]
    A = _padded(A, seq_axis=1, mt=m_tile)
    m, n = A.shape
    n_blocks = n // BLOCK_COLS
    residency = operator_residency(s_dim, n, m, m_tile, s_tile)
    if residency == "hbm":
        out = _planes_call(A, keys, scale, extra_operands, s_dim=s_dim,
                           s_tile=s_tile, dist_kind=dist_kind, m_tile=m_tile,
                           precision=precision, interpret=interpret,
                           epilogue=epilogue)
        return out if m == rows else out[:rows]
    out = pl.pallas_call(
        functools.partial(_kernel, dist_kind, s_dim, s_tile, n_blocks,
                          precision, epilogue),
        grid=(m // m_tile, s_dim // s_tile, n_blocks),
        in_specs=[
            # whole key table in SMEM every step (tiny); indexed by k
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(
                (m_tile, BLOCK_COLS), lambda i, j, k: (i, k),
                memory_space=pltpu.VMEM,
            ),
        ] + [
            pl.BlockSpec((1, s_tile), lambda i, j, k: (0, j),
                         memory_space=pltpu.VMEM)
            for _ in extra_operands
        ],
        out_specs=pl.BlockSpec(
            (m_tile, s_tile), lambda i, j, k: (i, j), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct((m, s_dim), jnp.float32),
        scratch_shapes=_operator_scratch(residency, s_dim, n),
        compiler_params=_grid_params(residency, "parallel"),
        interpret=interpret,
    )(keys, A, *extra_operands)
    if m != rows:
        out = out[:rows]
    return out if scale is None else scale * out


def _block_table(keys, n: int) -> jnp.ndarray:
    """The (n_blocks, 2) block-key table of a fused call, traceable:
    ``keys`` itself where the caller holds a table (a shard's slice of
    the global one, parallel/shard_apply.py), derived from the
    allocation's (2,) key words otherwise — a dozen cipher calls inside
    the apply's own program, not a dispatch before it."""
    return keys if keys.ndim == 2 else _block_key_table(keys, n)


@functools.partial(
    jax.jit,
    static_argnames=("s_dim", "dist_kind", "m_tile", "precision",
                     "interpret", "s_tile"),
)
def _fused_call(A, keys, scale=None, *, s_dim, dist_kind, m_tile,
                precision="f32", interpret=False, s_tile=None):
    return _rowwise_pallas_call(A, _block_table(keys, A.shape[1]), scale, (),
                                s_dim=s_dim, dist_kind=dist_kind,
                                m_tile=m_tile, precision=precision,
                                interpret=interpret, s_tile=s_tile)


@functools.partial(
    jax.jit,
    static_argnames=("s_dim", "dist_kind", "m_tile", "precision",
                     "inscale", "outscale", "interpret", "s_tile"),
)
def _fused_call_cos(A, keys, sc, sh, *, s_dim, dist_kind, m_tile,
                    precision="f32", inscale=1.0, outscale=1.0,
                    interpret=False, s_tile=None):
    operands, epilogue = _cos_epilogue(sc, sh, inscale, outscale)
    return _rowwise_pallas_call(A, _block_table(keys, A.shape[1]), None,
                                operands, s_dim=s_dim, dist_kind=dist_kind,
                                m_tile=m_tile, precision=precision,
                                interpret=interpret, s_tile=s_tile,
                                epilogue=epilogue)


@functools.partial(
    jax.jit,
    static_argnames=("s_dim", "dist_kind", "m_tile", "precision",
                     "interpret"),
)
def _fused_call_cw(A, keys, scale=None, *, s_dim, dist_kind, m_tile,
                   precision="f32", interpret=False):
    """``scale``·S·A (``scale`` None: unscaled) of the UNPADDED A (n, m),
    one executable — the columnwise twin of :func:`_rowwise_pallas_call`:
    the block-key table, the zero padding and the slice back where the
    operand is ragged (:func:`_padded`: exact), and the kernel(s) of the
    operand's :func:`operator_residency`. "hbm": :func:`_planes_call`,
    the scale folded into the planes. "vmem" / "per_tile": one call that
    generates in the kernel, the scale a pass over its result. The result
    tile is always all s_dim rows (no s-tile columnwise)."""
    cols = A.shape[1]
    A = _padded(A, seq_axis=0, mt=m_tile)
    n, m = A.shape
    keys = _block_table(keys, n)
    residency = operator_residency(s_dim, n, m, m_tile)
    if residency == "hbm":
        out = _planes_call(A, keys, scale, (), s_dim=s_dim, s_tile=s_dim,
                           dist_kind=dist_kind, m_tile=m_tile,
                           precision=precision, interpret=interpret,
                           epilogue=None, rowwise=False)
        scale = None        # applied: in the planes, or to each tile
    else:
        out = pl.pallas_call(
            functools.partial(_kernel_cw, dist_kind, s_dim, m_tile,
                              precision),
            grid=(m // m_tile, n // BLOCK_COLS),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec((BLOCK_COLS, m_tile), lambda j, k: (k, j),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((s_dim, m_tile), lambda j, k: (0, j),
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((s_dim, m), jnp.float32),
            scratch_shapes=_operator_scratch(residency, s_dim, n),
            compiler_params=_grid_params(residency),
            interpret=interpret,
        )(keys, A)
    if m != cols:
        out = out[:, :cols]
    return out if scale is None else scale * out


_DIST_KINDS = {
    randgen.Normal: "normal",
    randgen.Cauchy: "cauchy",
    randgen.Rademacher: "rademacher",
}


def _resolve_knobs(m_tile, precision):
    """The two tuning knobs of an apply: the call-site argument, else the
    sketch.params setter (whose default is the heuristic). Returns
    ``(m_tile, precision, source)`` — ``source`` is "arg" when the call
    site gave either knob, "heuristic" otherwise. The tile is a request
    that is only ever shrunk; None is the planner's (:func:`_planned`)."""
    source = "heuristic" if m_tile is None and precision is None else "arg"
    if m_tile is None:
        m_tile = sketch_params.get_pallas_m_tile()
    if precision is None:
        precision = sketch_params.get_pallas_precision()
    return m_tile, precision, source


def supported(dist, dtype) -> bool:
    kind = _DIST_KINDS.get(type(dist))
    if kind is None:
        return False
    # only the standard forms share the plain bit transforms
    if kind == "normal" and (dist.mean != 0.0 or dist.std != 1.0):
        return False
    if kind == "cauchy" and (dist.loc != 0.0 or dist.scale != 1.0):
        return False
    return jnp.dtype(dtype) == jnp.float32


def _pad_to(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def _tile_fits(m_tile: int, s_tile: int, epilogue: bool) -> bool:
    """The VMEM plan of one (m_tile × s_tile) result tile
    (:func:`_vmem_estimate`, scratch excluded — operator_residency checks
    it) against the scope. A fused ``epilogue`` counts one more result
    tile, its temporaries: for the stock cos over the whole tile Mosaic
    asked 17.2 MiB at 512 × 1536 where the plan without them says 16.0
    (compiled for a v5e, PR 32). Since the finisher works in slabs
    (:func:`_finish_in_place`) it asks 14.0 there, 13.8 at 512 × 1024
    over 16 k steps (15.8 before) and 9.6 at the feature cell's plan
    (least limit that compiles, PR 38): the term is room now, kept —
    by PR 49 too — because it decides the feature cell's 512 × 1024."""
    return _vmem_estimate(
        m_tile, s_tile,
        4 * m_tile * s_tile if epilogue else 0) <= _VMEM_BUDGET_BYTES


def _fit_rows(m: int, m_tile: int, s_tile: int,
              epilogue: bool = False) -> Optional[int]:
    """The requested row tile fitted to ``m`` rows and to the VMEM plan
    of a result tile ``s_tile`` wide (:func:`_tile_fits`), or None when
    no tile fits."""
    m = _pad_to(max(m, 8), 8)
    # power-of-two tile ≥ 8: the halving search below then always
    # terminates at a divisor of the 8-aligned m (a non-pow2 request,
    # e.g. the argument m_tile=100, would otherwise collapse to 1)
    m_tile = max(8, 1 << (max(m_tile, 8).bit_length() - 1))
    m_tile = min(m_tile, m)
    while m % m_tile:
        m_tile //= 2
    if not _tile_fits(m_tile, s_tile, epilogue):
        # scan smaller valid tiles — ≥ 8, multiples of 8 (sublane
        # tiling), divisors of the padded m — largest first. (m_tile may
        # be the non-power-of-2 m itself via min(m_tile, m), so blind
        # halving could skip valid tiles or land misaligned.)
        for t in range(min(m_tile - 8, _pad_to(m_tile // 2, 8)), 7, -8):
            if m % t == 0 and _tile_fits(t, s_tile, epilogue):
                return t
        # no valid tile fits (the generation term scales with s_tile
        # alone)
        return None
    return m_tile


def _admits(dist, dtype, interpret: bool) -> bool:
    """Backend + distribution + dtype: what no tile plan can cure."""
    return (interpret or available()) and supported(dist, dtype)


def _qualify(dist, A, seq_axis: int, m_tile: int, interpret: bool,
             s_dim: int = 0, epilogue: bool = False):
    """Common qualification: backend + distribution. Returns the m-tile
    size for the (possibly padded) m extent under a FULL-width result
    tile, or None for fallback (the rowwise applies go on to tile s:
    :func:`_tile_plan`).

    The returned tile is pre-shrunk so the kernel's VMEM plan
    (:func:`_fit_rows`) fits ``_VMEM_BUDGET_BYTES``: a Mosaic
    VMEM-exhaustion failure inside a
    jitted shard_map program has no catchable fallback seam, so the
    pre-flight must make compilation succeed, not try/except it (advisor
    r2 medium finding).

    Ragged shapes are handled by the callers via zero-padding (exact for
    these contractions: padded A columns multiply virtual S columns by
    zero; padded A rows produce output rows that are sliced away) — the
    parity requirement the reference exercises at np∈{5,7}
    (ref: tests/unit/CMakeLists.txt:31-33)."""
    if not _admits(dist, A.dtype, interpret):
        return None
    return _fit_rows(A.shape[1 - seq_axis], m_tile, s_dim, epilogue)


# Lane width of the result: an s-tile is a multiple of it.
_LANES = 128


def _tile_plan(dist, A, seq_axis: int, m_tile: int, interpret: bool,
               s_dim: int, epilogue: bool = False):
    """``(m_tile, s_tile)`` of an unbatched apply, from the shapes alone,
    or None (XLA serves). Where a row tile takes the full-width result
    (:func:`_qualify`) that is the plan, ``s_tile == s_dim``, as it
    always was. Where none does — s_dim ≥ 3999: the result tile and the
    generated block both grow with s_dim — a ROWWISE apply tiles s
    instead: the requested row tile unshrunk, and the widest s-tile the
    same VMEM plan admits among the divisors of s_dim that are multiples
    of 128 lanes (s_dim = 16384 at m_tile 512: 1024, the untiled plan of
    s_dim = 1024). Rows of S are independent in the stream, so each
    s-tile generates, or streams from the planes, just its own rows."""
    if not _admits(dist, A.dtype, interpret):
        return None
    m = A.shape[1 - seq_axis]
    mt = _fit_rows(m, m_tile, s_dim, epilogue)
    if mt is not None:
        return mt, s_dim
    if seq_axis != 1 or s_dim % _LANES:
        return None
    mt = _fit_rows(m, m_tile, _LANES, epilogue)
    if mt is None:
        return None
    # no full-width tile fits at 8 rows, so none past 4096 columns does;
    # the narrowest, 128, fits by the line above
    widths = range(min(s_dim // 2, 4096) // _LANES * _LANES, 0, -_LANES)
    return mt, next(st for st in widths
                    if s_dim % st == 0 and _tile_fits(mt, st, epilogue))


class Plan(NamedTuple):
    """What a fused apply will run (:func:`_plan`); hashable, so a
    compiled program takes it as a static."""
    m_tile: int
    s_tile: int
    precision: str
    operator_residency: str
    interpret: bool = False


def _plan(dist, A, s_dim: int, seq_axis: int, m_tile, precision,
          interpret: bool, epilogue: bool = False) -> Optional[Plan]:
    """The prelude of every fused apply: the knobs, the tiles and the
    residency by :func:`_planned` (``epilogue``: the kernel finishes its
    tiles with the cos), under the ``sketch.plan`` span; what was chosen
    is noted on the enclosing ``sketch.apply`` under the report's own
    keys (:func:`effective_plan`), the "hbm" contraction's k step and
    the ``vmem_limit_bytes`` it passes (0: none) among them. Returns the
    :class:`Plan` with the effective tiles, or None when the kernel
    declines and the caller takes the XLA path."""
    with _trace.span("sketch.plan") as sp:
        plan = _planned(dist, A, s_dim, seq_axis, m_tile, precision,
                        interpret, epilogue)
        if sp is not None:
            sp.set_attr("plan_source", plan["plan_source"])
    if not plan["kernel"]:
        return None
    note_apply(path="pallas", **{key: plan[key] for key in (
        "m_tile", "s_tile", "precision", "plan_source", "operator_residency",
        "k_cols", "vmem_limit_bytes")})
    return Plan(plan["m_tile"], plan["s_tile"], plan["precision"],
                plan["operator_residency"], interpret)


@functools.partial(jax.jit, static_argnames="n")
def _block_key_table(kd, n: int) -> jnp.ndarray:
    """uint32 (n_blocks, 2) Threefry key table for column blocks 0..n/BC
    of the stream under raw key words ``kd`` ((2,) uint32): block b's
    words are those of ``randgen.chunk_key(key, b)``."""
    n_blocks = -(-n // BLOCK_COLS)
    return jax.vmap(lambda b: randgen.chunk_key_data(kd, b))(
        jnp.arange(n_blocks, dtype=jnp.int32))


def _key_data(key):
    """The raw (2,) uint32 words of ``key``. The entry points below take
    the allocation's typed key or its ``Allocation.key_data``; a typed key
    is unwrapped here, one eager op where no trace is active — the hot
    caller (sketch/dense.py) hands the words."""
    if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        return jr.key_data(key)
    return key


def _block_keys(key, n: int) -> jnp.ndarray:
    """:func:`_block_key_table` as a dispatch of its own, for a caller
    that holds the table and slices it (:func:`fused_partial`'s tests)."""
    with _trace.span("stream.key", {"what": "block_table"}):
        return _block_key_table(_key_data(key), n)


def _padded_extents(n: int, m: int, mt: int) -> tuple[int, int]:
    """Padded (seq, other) extents of an apply: seq to a BLOCK_COLS
    multiple, the other to an mt multiple — shared by :func:`_padded`
    and :func:`effective_plan` so the plan sees the kernel's real
    shapes."""
    return _pad_to(n, BLOCK_COLS), _pad_to(max(m, 8), mt)


def _padded(A, seq_axis: int, mt: int):
    """Zero-pad A so seq axis % BLOCK_COLS == 0 and the other % mt == 0."""
    n, m = A.shape[seq_axis], A.shape[1 - seq_axis]
    n_p, m_p = _padded_extents(n, m, mt)
    pn, pm = n_p - n, m_p - m
    if pn == 0 and pm == 0:
        return A
    pads = [(0, pn), (0, pm)] if seq_axis == 0 else [(0, pm), (0, pn)]
    return jnp.pad(A, pads)


def _is_padded(A, seq_axis: int, mt: int) -> bool:
    return _padded_extents(A.shape[seq_axis], A.shape[1 - seq_axis],
                           mt) != (A.shape[seq_axis], A.shape[1 - seq_axis])


def rowwise_apply(
    key: jax.Array,
    dist,
    A: jnp.ndarray,
    s_dim: int,
    scale: float,
    m_tile: int | None = None,
    precision: str | None = None,
    interpret: bool = False,
) -> Optional[jnp.ndarray]:
    """out = scale · A @ Sᵀ with S the virtual (s_dim × N) matrix of
    :func:`randgen.dense_block`. Returns None when the kernel declines
    (caller takes the XLA path). A Mosaic rejection of a planned kernel
    raises."""
    plan = _plan(dist, A, s_dim, 1, m_tile, precision, interpret)
    if plan is None:
        return None
    kd = _key_data(key)
    with _trace.span("sketch.dispatch") as sp:
        # the handover (telemetry/names.py HANDOVER): the body starts
        # with the call of the one executable — the block-key table, the
        # padding (where the operand is ragged), the kernel(s) and the
        # scale, folded into the planes under the "hbm" residency, a pass
        # over the result otherwise; the attribute waits until the
        # runtime has the work
        out = _fused_call(A, kd, scale, s_dim=s_dim,
                          dist_kind=_DIST_KINDS[type(dist)],
                          m_tile=plan.m_tile, s_tile=plan.s_tile,
                          precision=plan.precision, interpret=interpret)
        if sp is not None:
            sp.set_attr("padded", _is_padded(A, 1, plan.m_tile))
        return out


def columnwise_apply(
    key: jax.Array,
    dist,
    A: jnp.ndarray,
    s_dim: int,
    scale: float,
    m_tile: int | None = None,
    precision: str | None = None,
    interpret: bool = False,
) -> Optional[jnp.ndarray]:
    """out = scale · S @ A for A (N, m): the same kernels as
    :func:`rowwise_apply`, the contraction transposed — under the "hbm"
    residency S is generated once an apply, not once a column tile.
    Returns None when the kernel declines (caller takes the XLA path)."""
    plan = _plan(dist, A, s_dim, 0, m_tile, precision, interpret)
    if plan is None:
        return None
    kd = _key_data(key)
    with _trace.span("sketch.dispatch") as sp:
        # the handover, as rowwise: one executable (table, padding,
        # kernel(s), scale) called first, the attribute after it
        out = _fused_call_cw(A, kd, scale, s_dim=s_dim,
                             dist_kind=_DIST_KINDS[type(dist)],
                             m_tile=plan.m_tile, precision=plan.precision,
                             interpret=interpret)
        if sp is not None:
            sp.set_attr("padded", _is_padded(A, 0, plan.m_tile))
        return out


def rft_rowwise_apply(
    key: jax.Array,
    dist,
    A: jnp.ndarray,
    s_dim: int,
    inscale: float,
    outscale: float,
    sc: jnp.ndarray,
    sh: jnp.ndarray,
    m_tile: int | None = None,
    precision: str | None = None,
    interpret: bool = False,
) -> Optional[jnp.ndarray]:
    """Fused random-Fourier-feature rowwise apply:
    ``outscale · cos((A @ (inscale·S)ᵀ) ⊙ sc + sh)`` with the cos
    epilogue applied in VMEM (no extra HBM round-trip of the feature
    matrix). ``sc``/``sh`` are (s_dim,) per-feature scales/shifts.
    Returns None when not applicable. The kernel alone, on given
    vectors; ``RFT.apply`` runs the same kernel inside its one program
    (:func:`features_rows`), the vectors generated there."""
    plan = _plan(dist, A, s_dim, 1, m_tile, precision, interpret,
                 epilogue=True)
    if plan is None:
        return None
    kd = _key_data(key)
    # the vectors' conversions are dispatches of their own: ahead of the
    # handover span, whose body starts with the executable's call
    sc = jnp.asarray(sc, jnp.float32).reshape(1, s_dim)
    sh = jnp.asarray(sh, jnp.float32).reshape(1, s_dim)
    with _trace.span("sketch.dispatch") as sp:
        out = _fused_call_cos(
            A, kd, sc, sh,
            s_dim=s_dim, dist_kind=_DIST_KINDS[type(dist)],
            m_tile=plan.m_tile, s_tile=plan.s_tile,
            precision=plan.precision, inscale=float(inscale),
            outscale=float(outscale), interpret=interpret)
        if sp is not None:
            sp.set_attr("padded", _is_padded(A, 1, plan.m_tile))
        return out


def features_rows(key, dist, A, s_dim: int, inscale: float, outscale: float,
                  sc, sh, plan: Plan) -> jnp.ndarray:
    """The body of :func:`rft_rowwise_apply` under a plan already made
    (:func:`_plan`), traceable: the block-key table, the padding, the
    kernel(s) and the slice back, for the caller's one executable
    (sketch/rft.py ``sketch.rft_features``)."""
    operands, epilogue = _cos_epilogue(sc, sh, inscale, outscale)
    return _rowwise_pallas_call(
        A, _block_key_table(_key_data(key), A.shape[1]), None, operands,
        s_dim=s_dim, dist_kind=_DIST_KINDS[type(dist)], m_tile=plan.m_tile,
        s_tile=plan.s_tile, precision=plan.precision,
        interpret=plan.interpret, epilogue=epilogue)


def fused_partial(
    keys: jax.Array,
    dist,
    A_loc: jnp.ndarray,
    s_dim: int,
    seq_axis: int,
    m_tile: int | None = None,
    precision: str | None = None,
    interpret: bool = False,
    plan: Optional[Plan] = None,
    scale=None,
) -> Optional[jnp.ndarray]:
    """Contraction of a local shard against the operator blocks keyed by
    ``keys`` (n_blocks_local, 2), unscaled unless ``scale`` is given (then
    folded into the planes under the "hbm" residency, as the one-chip
    apply's) — the building block that lets the ``shard_map`` program
    (parallel/shard_apply.py ``dense_mesh``) run the fused kernel per
    device: each device passes its own slice of the global key table,
    contracts its shard, and the caller reduces.

    ``seq_axis`` is the contracted axis of ``A_loc`` (1 → A·Sᵀ partial,
    0 → S·A partial). The shard's sequence extent must equal
    ``keys.shape[0] * BLOCK_COLS`` (callers pre-pad to block multiples).
    ``plan``: the :func:`_plan` of the shard made ahead of the trace (a
    compiled program's static); None plans here. Returns None when the
    kernel isn't applicable (caller falls back; backend/distribution
    qualification is _qualify's)."""
    if A_loc.shape[seq_axis] != keys.shape[0] * BLOCK_COLS:
        return None
    if plan is None:
        plan = _plan(dist, A_loc, s_dim, seq_axis, m_tile, precision,
                     interpret)
        if plan is None:
            return None
    kw = dict(s_dim=s_dim, dist_kind=_DIST_KINDS[type(dist)],
              m_tile=plan.m_tile, precision=plan.precision,
              interpret=plan.interpret or interpret)
    if seq_axis == 1:
        return _fused_call(A_loc, keys, scale, s_tile=plan.s_tile, **kw)
    return _fused_call_cw(A_loc, keys, scale, **kw)


# ---------------------------------------------------------------------------
# the plan of an apply, and the "hbm" contraction's tile past the default scope
# ---------------------------------------------------------------------------
#
# (Below the kernels and their call sites on purpose: a compiled program
# carries the line of every frame that traced it, so what stands above
# keeps its lines and a program this section does not change — the
# feature cell's — stays the same bytes.)

# The row tile the planner asks for where nobody requested one
# (sketch/params.py, m-tile note): 512 is the largest power of two whose
# plan fits Mosaic's default scope at s_dim = 1024, and the ceiling is
# where the measured gain of a larger contraction tile flattens (− 0.7 ms
# an apply at 1024, − 0.2 more at 2048; PERF.md §6, PR 27 and PR 49).
_M_TILE = 512
_M_TILE_CEILING = 2048

# What a grown contraction asks of Mosaic over its fitted plan. The plan
# is a least-limit fit on twenty-seven shapes of at most 512 rows, not a
# bound: at 2048 × 1024 × 512 Mosaic asks 51.1 MiB rowwise at "f32"
# where it says 49.0 (40.0 at "bf16x3"; compiled for a v5e, PR 49).
_VMEM_SLACK_BYTES = 8 * 1024 * 1024


def _contraction_vmem(m_tile: int, s_tile: int, k_cols: int,
                      lhs_f32: bool = False) -> int:
    """The contraction kernel's own VMEM plan for a step of ``k_cols``
    columns, in bytes — the fit :func:`_plane_step_cols` describes."""
    return (4 * (5 * m_tile * k_cols + 3 * m_tile * s_tile)
            + (20 if lhs_f32 else 10) * s_tile * k_cols)


def _lhs_f32(precision: str, rowwise: bool) -> bool:
    """Whether the plane tile is the split LEFT operand of a HIGHEST
    contraction (:func:`_plane_step_cols`): columnwise "f32"."""
    return not rowwise and _plane_dtypes(precision)[0] == jnp.float32


def _contraction(n: int, m_tile: int, s_tile: int, lhs_f32: bool,
                 epilogue: bool) -> tuple[int, int]:
    """``(k_cols, vmem_limit)`` of the "hbm" contraction at ``m_tile``,
    from the shapes alone — one rule, read by the call
    (:func:`_planes_call`) and by the plan that reports it
    (:func:`_planned`), as :func:`operator_residency` is. A tile of at
    most ``_M_TILE`` rows that the default scope admits
    (:func:`_tile_fits`: every tile the planner's own request can end
    at) takes its own :func:`_plane_step_cols` and passes no limit, 0:
    the program it always was. A larger tile — grown, or an explicit
    request the scope let through — takes the k step of the
    ``_M_TILE`` plan (halved until the scope admits it), so that each
    result row keeps its k order, its products and its bits whatever the
    tile, and passes its fitted plan plus the slack, never less than the
    default scope."""
    base = min(m_tile, _M_TILE)
    while base > 8 and not _tile_fits(base, s_tile, epilogue):
        base //= 2
    k_cols = _plane_step_cols(n, base, s_tile, lhs_f32)
    if base == m_tile:
        return k_cols, 0
    return k_cols, max(_contraction_vmem(m_tile, s_tile, k_cols, lhs_f32)
                       + _VMEM_SLACK_BYTES, _VMEM_BUDGET_BYTES)


def _contraction_params(grid_rank: int, vmem_limit: int):
    """:func:`compiler_params` of the contraction's grid — every axis but
    the last, k, "parallel" — with ``vmem_limit`` where there is one."""
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",) * (grid_rank - 1) + ("arbitrary",),
        vmem_limit_bytes=vmem_limit or None)


@functools.cache    # a process has one default device; the reading is 30 µs
def _vmem_cap() -> int:
    """The scoped VMEM a contraction may ask Mosaic for: half of the
    core's VMEM as ``pltpu.get_tpu_info()`` reports it for the device
    (64 MiB on a v5e; the other half stays the compiler's), never under
    the default scope — and the default scope itself where there is no
    TPU to ask (interpret mode on a CPU) or the core has no more (v2–v4:
    16 MiB), so that every plan there is the one it always was."""
    try:
        core = pltpu.get_tpu_info().vmem_capacity_bytes
    except ValueError:          # "Unsupported TPU device kind: cpu"
        return _VMEM_BUDGET_BYTES
    return max(core // 2, _VMEM_BUDGET_BYTES)


def _grown_rows(n: int, m: int, m_tile: int, s_tile: int, precision: str,
                rowwise: bool, epilogue: bool, vmem_cap: int) -> int:
    """The row tile of an "hbm" contraction nobody requested a tile for:
    the largest power of two ≤ ``_M_TILE_CEILING`` that divides the ``m``
    rows as ``m_tile`` padded them (no shape pads further, or
    differently, than it did), leaves more than one tile (the residency
    stays "hbm") and whose limit (:func:`_contraction`) fits
    ``vmem_cap``; ``m_tile`` itself where none does, where the cap
    grants nothing over the default scope, where the contraction is one
    k step (n ≤ 512, the feature maps: each tile is written once, there
    is no read-modify-write to amortize, and the kernel stands at 98 %
    of its MXU floor) and for the rowwise "f32" regime, whose A tile is
    the left operand of a HIGHEST contraction that Mosaic splits whole:
    on a v5e 2048 rows took 41.0 ms an apply against 37.0 at 512, where
    every other regime and the columnwise "f32" gained 0.4–0.9 ms
    (PERF.md §6, PR 49). A larger tile changes which rows share a grid
    step — fewer steps, fewer reads of the planes, fewer
    read-modify-writes of the out tile — and nothing of a row's
    arithmetic."""
    f32 = _plane_dtypes(precision)[0] == jnp.float32
    lhs_f32 = _lhs_f32(precision, rowwise)
    if (vmem_cap <= _VMEM_BUDGET_BYTES or (f32 and rowwise)
            or n <= _contraction(n, m_tile, s_tile, lhs_f32, epilogue)[0]):
        return m_tile
    rows = _M_TILE_CEILING
    while rows > m_tile:
        if m % rows == 0 and m // rows > 1 and _contraction(
                n, rows, s_tile, lhs_f32, epilogue)[1] <= vmem_cap:
            return rows
        rows //= 2
    return m_tile


def _planned(dist, A, s_dim: int, seq_axis: int, m_tile, precision,
             interpret: bool, epilogue: bool = False,
             vmem_cap: Optional[int] = None) -> dict:
    """What a fused apply of ``A`` (anything with a shape and a dtype)
    will run, from the shapes alone — the one resolution behind the
    dispatch (:func:`_plan`) and the report (:func:`effective_plan`,
    which documents the keys): the knobs (:func:`_resolve_knobs`), the
    tiles the default scope admits (:func:`_tile_plan`) and the
    residency at those tiles, so that a shape that is "vmem" or
    "per_tile" there stays so. An "hbm" contraction whose tile nobody
    requested then takes the row tile of :func:`_grown_rows` under
    ``vmem_cap`` (default: :func:`_vmem_cap`, the device's)."""
    m_tile, precision, source = _resolve_knobs(m_tile, precision)
    tiles = _tile_plan(dist, A, seq_axis=seq_axis, m_tile=m_tile or _M_TILE,
                       interpret=interpret, s_dim=s_dim, epilogue=epilogue)
    if tiles is None:
        return {"kernel": False, "plan_id": "xla", "plan_source": source}
    mt, st = tiles
    # the same padding/residency helpers the pallas_call sites use
    n_p, m_p = _padded_extents(A.shape[seq_axis], A.shape[1 - seq_axis], mt)
    residency = operator_residency(s_dim, n_p, m_p, mt, st)
    k_cols, vmem_limit = BLOCK_COLS, 0      # the generating kernels' step
    if residency == "hbm":
        if m_tile is None:
            mt = _grown_rows(n_p, m_p, mt, st, precision, seq_axis == 1,
                             epilogue,
                             _vmem_cap() if vmem_cap is None else vmem_cap)
        k_cols, vmem_limit = _contraction(
            n_p, mt, st, _lhs_f32(precision, seq_axis == 1), epilogue)
    tile_id = f"mt{mt}" if st == s_dim else f"mt{mt}/st{st}"
    return {"kernel": True, "m_tile": mt, "s_tile": st, "k_cols": k_cols,
            "vmem_limit_bytes": vmem_limit,
            "operator_residency": residency,
            "operator_cache": residency == "vmem",
            "precision": precision,
            # the label bench records carry: backend, tiles and
            # regime, one string for one plan
            "plan_id": f"pallas/{tile_id}/{precision}",
            "plan_source": source}


def effective_plan(dist, shape, dtype, s_dim: int, seq_axis: int,
                   m_tile: int | None = None,
                   interpret: bool = False,
                   precision: str | None = None,
                   epilogue: bool = False,
                   vmem_cap: int | None = None) -> dict:
    """The plan a fused apply with these arguments would actually run —
    WITHOUT running it. The requested tile can be adjusted downstream
    (:func:`_tile_plan` shrinks an over-budget m-tile, or tiles s; a tile
    nobody requested is grown for the "hbm" contraction,
    :func:`_grown_rows`), so anything recording a measurement labeled
    with the REQUESTED knobs must ask for the EFFECTIVE ones or the
    record lies about what was measured (e.g. the m-tile sweep rows in
    benchmarks/). It IS the dispatch's resolution (:func:`_planned`).

    Returns ``{"kernel": False, "plan_id": "xla"}`` when the apply would
    take the XLA fallback, else ``kernel/m_tile/s_tile/k_cols/
    vmem_limit_bytes/operator_residency/operator_cache/precision/plan_id/
    plan_source`` (``operator_cache`` is ``operator_residency == "vmem"``;
    ``s_tile`` is ``s_dim`` unless the plan tiles s; ``k_cols`` is the
    contraction's step and ``vmem_limit_bytes`` what the "hbm"
    contraction passes Mosaic, 0 where it passes none). ``epilogue``: the
    plan of a feature map's apply, whose kernel finishes its tiles with
    the cos. ``vmem_cap`` (internal; default the device's,
    :func:`_vmem_cap`) is the scope a grown tile may ask for: a compile
    for a described chip hands over that chip's."""
    A = jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype))
    return _planned(dist, A, s_dim, seq_axis, m_tile, precision, interpret,
                    epilogue, vmem_cap)


# ---------------------------------------------------------------------------
# a row window of the "hbm" contraction: the mesh program's panels
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("dist", "s_dim", "plan"))
def partial_planes(keys, scale=None, *, dist, s_dim: int, plan: Plan) -> list:
    """The planes of S an "hbm" rowwise apply under ``plan`` contracts
    with (:func:`_operator_planes`, ``scale`` folded in as there), for a
    caller that makes them once and contracts row windows against them
    (:func:`window_partial`)."""
    return _operator_planes(keys, scale, s_dim=s_dim,
                            dist_kind=_DIST_KINDS[type(dist)],
                            precision=plan.precision, s_tile=plan.s_tile,
                            interpret=plan.interpret)


def unwritten(shape: tuple, dtype, interpret: bool = False) -> jnp.ndarray:
    """An array of ``shape`` that nothing has written (a call with no
    operand and an empty body: the buffer as the allocator hands it), for a
    caller that stores every entry itself (``dynamic_update_slice`` by
    panels) and has no use for a fill pass."""
    return pl.pallas_call(
        lambda out_ref: None, out_shape=jax.ShapeDtypeStruct(shape, dtype),
        out_specs=pl.BlockSpec(memory_space=pl.ANY), interpret=interpret,
        name="unwritten")()


@functools.partial(jax.jit, static_argnames=("count", "chunks", "plan"))
def window_partial(A, planes, first, scale=None, *, count: int,
                   chunks: int = 1, plan: Plan) -> list:
    """Row tiles [first, first + count) of A·Sᵀ — the rowwise
    contraction of :func:`_planes_call` on a window of its row grid: the
    WHOLE padded A (m, n) is the operand and the A tile's index map reads
    ``(i + first, k)``, so no slice of A stands in front of the call; the
    result is the window's (count · m_tile, s_dim), handed back as
    ``chunks`` arrays of s_dim / chunks columns each, in order (the plan
    must not tile s). ``first`` is a prefetched scalar and no static:
    every window of an apply is one traced function and one kernel body.
    Tile for tile the steps, their order and their bits are the whole
    call's."""
    m_tile, precision = plan.m_tile, plan.precision
    n = A.shape[1]
    s_dim = planes[0].shape[0]
    if plan.s_tile != s_dim or s_dim % chunks:
        raise ValueError(f"a window in {chunks} chunks needs the sketch axis "
                         f"in one tile that they divide: s_dim {s_dim}, "
                         f"s_tile {plan.s_tile}")
    width = s_dim // chunks
    k_cols, vmem_limit = _contraction(n, m_tile, s_dim, False, False)
    n_blocks = n // k_cols
    tile_scaled = _tile_scaled(precision, scale)
    vmem = functools.partial(pl.BlockSpec, memory_space=pltpu.VMEM)
    operands, in_specs = [], []
    if tile_scaled:
        operands.append(jnp.asarray(scale, jnp.float32).reshape(1))
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
    operands += [A, *planes]
    in_specs += [vmem((m_tile, k_cols), lambda i, k, f: (i + f[0], k))] + [
        vmem((s_dim, k_cols), lambda i, k, f: (0, k)) for _ in planes]

    def kernel(first_ref, *refs):
        del first_ref       # read by the A tile's index map alone
        refs = list(refs)
        finish = _finisher(refs.pop(0) if tile_scaled else None, None, ())
        a_ref, plane_refs = refs[0], refs[1:1 + len(planes)]
        acc = _dot_planes(a_ref[:], [p[:] for p in plane_refs], precision)
        for c, out_ref in enumerate(refs[1 + len(planes):]):
            _store(out_ref, acc[:, c * width:(c + 1) * width],
                   pl.program_id(1), n_blocks, finish)

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(count, n_blocks), in_specs=in_specs,
            out_specs=[vmem((m_tile, width), lambda i, k, f: (i, 0))
                       for _ in range(chunks)]),
        out_shape=[jax.ShapeDtypeStruct((count * m_tile, width), jnp.float32)
                   for _ in range(chunks)],
        compiler_params=_contraction_params(2, vmem_limit),
        interpret=plan.interpret,
    )(jnp.asarray(first, jnp.int32).reshape(1), *operands)


# ---------------------------------------------------------------------------
# batched (microbatch-flush) launchers: one kernel over a stacked cohort
# ---------------------------------------------------------------------------
#
# The serve layer (engine/serve.py) flushes a cohort as ONE executable.
# These launchers give that executable a single pallas_call whose grid
# carries the batch as its leading (parallel) axis — batch lanes tile
# innermost against the same VMEM budget as the unbatched kernel (one
# lane's working set per grid step; _qualify's shrink-don't-fail plan
# applies unchanged), and every lane contracts against its OWN virtual
# operator (per-lane key table, per-lane scale) so transforms differing
# only by seed coexist in one flush. Per-lane bits are capacity-
# invariant: lanes run the same fixed-tile program independently.


def _kernel_batched_rw(dist_kind, s_dim, n_blocks, precision, keys_ref,
                       scale_ref, a_ref, out_ref):
    """Batched rowwise: out[b] += A[b]_tile @ (scale[b]·S_blk[b])ᵀ.
    Grid (batch, m_tiles, n_blocks); key table flattened (B·nb, 2)."""
    b = pl.program_id(0)
    k = pl.program_id(2)
    S_blk = _gen_block(dist_kind, s_dim, keys_ref, b * n_blocks + k)
    S_blk = S_blk * scale_ref[b]
    acc = _dot(a_ref[0], S_blk, (((1,), (1,)), ((), ())), precision,
               gen_side=1)
    _accumulate(out_ref, acc[None], k)


def _kernel_batched_cw(dist_kind, s_dim, n_blocks, precision, keys_ref,
                       scale_ref, a_ref, out_ref):
    """Batched columnwise: out[b] += (scale[b]·S_blk[b]) @ A[b]_blk."""
    b = pl.program_id(0)
    k = pl.program_id(2)
    S_blk = _gen_block(dist_kind, s_dim, keys_ref, b * n_blocks + k)
    S_blk = S_blk * scale_ref[b]
    acc = _dot(S_blk, a_ref[0], (((1,), (0,)), ((), ())), precision,
               gen_side=0)
    _accumulate(out_ref, acc[None], k)


@functools.partial(
    jax.jit,
    static_argnames=("s_dim", "dist_kind", "m_tile", "precision",
                     "rowwise", "interpret"),
)
def _batched_call(A, keys, scale, *, s_dim, dist_kind, m_tile,
                  precision, rowwise, interpret):
    B = A.shape[0]
    n = A.shape[2] if rowwise else A.shape[1]
    m = A.shape[1] if rowwise else A.shape[2]
    n_blocks = n // BLOCK_COLS
    grid = (B, m // m_tile, n_blocks)
    params = compiler_params("parallel", "parallel", "arbitrary")
    if rowwise:
        kern = functools.partial(_kernel_batched_rw, dist_kind, s_dim,
                                 n_blocks, precision)
        a_spec = pl.BlockSpec((1, m_tile, BLOCK_COLS),
                              lambda b, i, k: (b, i, k),
                              memory_space=pltpu.VMEM)
        out_spec = pl.BlockSpec((1, m_tile, s_dim),
                                lambda b, i, k: (b, i, 0),
                                memory_space=pltpu.VMEM)
        out_shape = jax.ShapeDtypeStruct((B, m, s_dim), jnp.float32)
    else:
        kern = functools.partial(_kernel_batched_cw, dist_kind, s_dim,
                                 n_blocks, precision)
        a_spec = pl.BlockSpec((1, BLOCK_COLS, m_tile),
                              lambda b, i, k: (b, k, i),
                              memory_space=pltpu.VMEM)
        out_spec = pl.BlockSpec((1, s_dim, m_tile),
                                lambda b, i, k: (b, 0, i),
                                memory_space=pltpu.VMEM)
        out_shape = jax.ShapeDtypeStruct((B, s_dim, m), jnp.float32)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # keys (B·nb, 2)
            pl.BlockSpec(memory_space=pltpu.SMEM),  # scale (B,)
            a_spec,
        ],
        out_specs=out_spec,
        out_shape=out_shape,
        compiler_params=params,
        interpret=interpret,
    )(keys, scale, A)


def serve_qualify(dist, s_dim: int, n: int, m: int, dtype,
                  interpret: bool = False,
                  m_tile: Optional[int] = None) -> tuple[bool, str]:
    """Host-side qualification for the batched serve launcher:
    (ok, reason) — the serve layer's decline counter wants the why."""
    if not interpret and not available():
        return False, "backend is not a TPU (interpret-mode only here)"
    if not supported(dist, dtype):
        return False, f"distribution/dtype unsupported ({dtype})"
    lane = jax.ShapeDtypeStruct((m, n), jnp.dtype(dtype))
    mt = _qualify(dist, lane, seq_axis=1,
                  m_tile=(m_tile or sketch_params.get_pallas_m_tile()
                          or _M_TILE),
                  interpret=interpret, s_dim=s_dim)
    if mt is None:
        return False, "no m-tile fits the VMEM budget"
    return True, "ok"


def serve_batched_apply(key_data, scale, A, *, dist, s_dim: int,
                        rowwise: bool, m_tile: Optional[int] = None,
                        precision: Optional[str] = None,
                        interpret: bool = False) -> jnp.ndarray:
    """Batched fused generate+matmul for a microbatch flush: the
    stacked-cohort analog of :func:`rowwise_apply`/:func:`columnwise_
    apply`, fully traceable (the serve builder compiles it into the
    bucket's batched executable). ``key_data`` (B, 2) uint32,
    ``scale`` (B,), ``A`` (B, m, n) rowwise / (B, n, m) columnwise.
    The scale multiplies the generated operator entries — the same
    elementwise order as ``serve_apply``'s scaled virtual panel.
    Raises on unqualified input: callers gate on
    :func:`serve_qualify` first."""
    A = jnp.asarray(A)
    n_axis = 2 if rowwise else 1
    n, m = A.shape[n_axis], A.shape[3 - n_axis]
    lane = jax.ShapeDtypeStruct(
        (m, n) if rowwise else (n, m), A.dtype)
    mt = _qualify(dist, lane, seq_axis=1 if rowwise else 0,
                  m_tile=(m_tile or sketch_params.get_pallas_m_tile()
                          or _M_TILE),
                  interpret=interpret, s_dim=s_dim)
    if mt is None:
        raise ValueError(
            f"batched dense kernel unqualified for s_dim={s_dim} "
            f"shape {A.shape}")
    if precision is None:
        precision = sketch_params.get_pallas_precision()
    n_p, m_p = _padded_extents(n, m, mt)
    pads = [(0, 0), (0, 0), (0, 0)]
    pads[n_axis] = (0, n_p - n)
    pads[3 - n_axis] = (0, m_p - m)
    Ap = jnp.pad(A, pads) if (n_p != n or m_p != m) else A
    B = A.shape[0]
    keys = jax.vmap(lambda k: _block_key_table(k, n))(
        jnp.asarray(key_data, jnp.uint32))
    out = _batched_call(
        Ap, keys.reshape(B * keys.shape[1], 2),
        jnp.asarray(scale, jnp.float32).reshape(B),
        s_dim=s_dim, dist_kind=_DIST_KINDS[type(dist)], m_tile=mt,
        precision=precision, rowwise=rowwise, interpret=interpret)
    return out[:, :m, :] if rowwise else out[:, :, :m]
