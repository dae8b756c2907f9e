"""Pallas TPU kernel: sparse × dense, ``Y = X·B`` for a sparse row block X
(rows × n) and a dense right factor B (n × k), the result built in VMEM
one block of rows at a time from lanes regrouped at placement.

What is here: :func:`tiles_plan` (do these extents fit, and with which
blocks), ``_kernel_tiles`` / ``_tiles_call`` (the kernel and its launch) and
:func:`tiles_apply` (the traceable entry). Who chooses it:
``sparse_serve.product_kernel``, from the backend and the shapes alone, for
``base.sparse.spmm`` and for ``DenseTransform.apply`` on a ``SparseMatrix``
rowwise (the dense sketch of a sparse operand, whose right factor is Sᵀ
generated inside the same program) — and, since PR 61, for the transposed
side: ``base.sparse.spmm_t`` and the columnwise apply are this walk with Xᵀ
in X's place, over a placement of their own (*The runs layout* below).
Where it does not apply: a backend that compiles no Mosaic kernel, values
that are not float32, a width k that is no multiple of 128 or past 2048, a
chunk table past SMEM. Off the TPU the kernel runs only interpreted, for
the tests.

The layout (``SparseMatrix.tiled_device``, placed once). The row axis is
cut into blocks of ``row_block`` rows, the column axis into tiles of
``col_tile`` columns; a *segment* is the stored nonzeros of one (row block,
column tile) pair. Inside a segment a lane's *rank* is its place among the
lanes of its own row (0 for the row's first column of the tile, 1 for its
second, …; ranks past 14 share the last class), and the lanes lie by
**(rank, row)**: every row's lane 0 by rising row, then every row's lane 1,
and so on — a row's lanes keep their rising-column order, and consecutive
slots address different rows. The rows that hold a lane of rank r + 1 are
among those that hold one of rank r, so any ``group`` consecutive lanes
that lie in rank classes of at least ``group`` rows each address ``group``
different rows (inside a class rows are distinct; a window across two
classes takes the largest rows of the one and the smallest of the other,
which has as many rows above those, all of them in the first). A segment's
sequence therefore splits at its first class with fewer than ``group``
rows into a *grouped prefix*, repeat-free in every window of ``group``
slots wherever a chunk cuts it, and a *serial tail* (the high ranks of a
few long rows: 0.43 % of the lanes at 74 nonzeros a row over 24 tiles).
Segments lie in (row block, column tile) order, each in ⌈stored ÷
``chunk``⌉ *chunks* of ``chunk`` lane slots, all full but the segment's
first, which holds the remainder (the walk of a segment's last chunk is
what hides the copy of the next segment's tile of B and the write of a
finished block: a nearly empty last chunk would hide nothing, and a full
one hides the copy only if it walks for as long as the copy takes —
``TilesPlan.cover`` lanes, which ``chunk`` is sized to hold and the
placement counts the segments by, ``covered_segments``), and the
column tiles are of one width, to 8 columns, so that no row block ends on a
narrow tile's short segment; a slot holds the lane's value and one packed
word, its row in the block · 2¹⁶ + its column in the tile, both times
``TilesPlan.stride``. Two tables, one entry a chunk, say which segment the
chunk belongs to and, packed, how many of its slots are stored lanes
(0 for an empty chunk) + 2¹⁶ · how many of those, from its first slot on,
lie in the grouped prefix. Every row block owns at least one chunk, so that
its block of the result is written.

The walk. The v5e has no vector gather, so a lane's row of B is not
gathered: it is *addressed*. B and the result are viewed so that one row of
either is whole vector registers — (n, k/128, 128), a row behind a leading
index, or, where k is a multiple of 1024, flat (n · k/128, 128) with the
packed words counting in sublanes, which saves the two index
multiplications a lane —; a grid step is one chunk, its slots in SMEM, the
column tile of B (``col_tile`` rows) and the row block of the result
(``row_block`` rows) in VMEM, both fetched by the pipeline only when the
chunk's segment changes tile or block; and the lane at slot j does

    Y[row_j] += value_j · B[column_j]

on the VPU in float32: a load of each row, a multiply-add, a store. The
row is a run-time value, so the compiler orders every load of the block
after the store before it, and the hardware holds a load of the row just
stored: walked one by one, row-major, a lane waited on the lane before
(11.5 cycles a lane on a v5e, PERF.md PR 57–58). The grouped slots of a
chunk are walked ``group`` at a time instead — the ``group`` rows of the
block and of B loaded, the sums formed, then the ``group`` rows stored:
loads first and stores last in program order, which the placement makes
legal — and 128 slots an iteration, straight-line code over a 128-aligned
window of the slots whose offsets are static, so that a group's address
arithmetic (ten scalar operations a lane on two scalar slots a bundle: the
walk's bound) is laid under the group before's vector work. The slots past
the grouped count (under ``group`` left over, and the serial tail) are
walked one by one. The products and sums are float32 arithmetic (no
bfloat16 piece anywhere), each stored nonzero contributes exactly once, and
the terms of one result row are added in column-tile order and by rising
column inside a tile — the order of the row-major walk, so the result is
that walk's to the bit whatever the group. The loops stop at the chunk's
counts: padding slots are moved, never multiplied, so a non-finite entry of
B poisons only the rows whose lanes address it.

The runs layout (``TilesPlan.runs``; ``base.sparse._tile_runs``, the
transposed side). With Xᵀ in X's place a result row is a *feature* of a
corpus: a frequent one stores a lane in most of a tile's 2048 examples,
most hold one or none — the (rank, row) order puts such a row's lanes into
high rank classes of few rows, the serial tail (15 % of the lanes at
rcv1's skew). There a row with at least ``_RUN_ROW`` lanes in a segment is
kept whole, row-major, and its lanes are padded to a multiple of ``group``
slots (zero-valued copies of its last lane); the short rows lie by (rank,
row) as above and their grouped prefix, cut to whole groups, is the chunk's
grouped slots; what that leaves joins the runs. The kernel takes the slots
past the grouped count ``group`` at a time as a *run*: ``group`` loads of
B, their terms summed in registers (pairwise), ONE load and ONE store of
the result row — a run's loads of B wait on no store, and a hot row costs
a lane less than a grouped one (five scalar operations a lane, not ten).
The sums of a row's terms are pairwise inside a run and by rising column
across runs: float32 arithmetic, each stored nonzero once, not the
row-major walk's bits. Every block of result rows streams all of B once,
so the transposed plan's blocks are twice as tall (4096 rows at k ≤ 1024).

The door. An array that crosses a Mosaic call in another tiling than its
other owner's is a silent copy by XLA, so each crosses in the layout of the
side that is NOT the kernel. B comes in the kernel's view where its maker
can write it so (``sparse_serve.operator_rows_panels(..., lanes=128)``: Sᵀ
of both dense sketches; a supplied (n, k) factor is relaid, a copy of B).
The result, under the flat views (k a multiple of 1024), leaves as (rows, k)
rows: the walk accumulates in a VMEM scratch block of the flat view, the
call's output block is the plain (row_block, k) one, and the last chunk of
a row block hands the block over, one sublane-strided load and one store a
result register (``_hand_over``; 262,144 pairs at 262144 × 1024) — the
call's output is the product, ragged last block clipped, and nothing relays
it. At the other widths a row is part of a register behind a leading index
and such a load would gather single sublanes: the result keeps the
kernel's view and :func:`tiles_apply` reshapes it (at k = 128 the two
layouts are the same bytes; from 256 to 896 XLA copies the result).

Workspace. VMEM: 2 · (row_block + col_tile) rows of max(k, 1024) floats, and
row_block more for the scratch block under the flat views (39.4 MiB at
k = 1024; 64 MiB under the transposed plan's 4096-row blocks). HBM, beside
the operands and the result: none under the flat views; at the other
widths :func:`tiles_apply` adds the (rows, k) relayout of the result's
(rows, k/128, 128) view. Nothing grows with nnz · k.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
_BLOCK_ROWS = 2048      # rows of a result block and of a tile of B at k ≤ 1024
# lane slots a grid step, the first whose tables fit. The rule: the walk of
# a FULL chunk has to outlast the copy of the next segment's tile of B — the
# pipeline fetches one step ahead, and a segment's last chunk is a full one.
# ``TilesPlan.cover`` says how many lanes that takes: 3952 at the
# jlt_sparse_apply cell's tile (1976 × 1024 floats, ≈ 10 µs), 4096 at the
# largest a plan makes (8 MiB), so the first entry holds them whatever k is.
# The readings, ms a block of that cell (PERF.md §6,
# benchmarks/spmm_walk_steps.log). The lane-by-lane walk, 9.1 ns a lane,
# 1200 lanes cover the copy: 512 slots 184.3, 1024 171.0, 2048 157.0, 4096
# 156.6 (PR 57: 2048). The grouped walk, 2.5–3.7 ns a lane: 512 95.0, 1024
# 86.6, 2048 76.1, 4096 59.0 (PR 58 kept 2048: every segment exposed
# 2.5–5 µs of the copy). PR 60, three seeds: 2048 76.1–76.3,
# 4096 59.1–59.9, 8192 58.5–59.6 — 0.35–0.6 ms under 4096, inside the cell's
# bound of 0.9, for 43 % of the slots filled against 60 %: 4096. It is the
# best chunk at the other widths too: k = 128 (1 MiB tiles) 1024 55.3, 2048
# 53.4, 4096 53.0, 8192 53.6; k = 2048 (1024-row blocks, where the empty
# walk reads what the full one does: B's stream alone) 1024 175.5, 2048
# 151.1, 4096 148.2, 8192 148.3. A 16 MiB tile (4096-row blocks) wants the
# second entry, as the rule says: 64.8 under 4096 slots, 59.5 under 8192
_CHUNKS = (4096, 8192)
_MAX_CHUNKS = 1 << 15   # entries of each scalar-prefetched table: two tables
                        # take a quarter of the v5e's 1 MiB of SMEM
_UNROLL = 8             # lanes an iteration of the serial walk
_GROUP = 8              # rows whose loads go ahead of their stores (grouped walk)
_SPAN = 128             # slots the grouped walk unrolls: an SMEM window's alignment
_MAX_K = 2048
_RUN_ROW = 16           # lanes of one result row in one segment from which
                        # the runs layout keeps the row whole, as a run
_RUN_UNROLL = 8         # runs an iteration of the run walk (ms a block of
                        # the jlt_sparse_apply_cw cell: 1 124.6, 2 113.6,
                        # 4 110.6, 8 108.8 — benchmarks/spmm_t_walk_steps.log)
_COPY_BYTES_A_LANE = 2048   # of a tile of B, arriving while a grouped lane
                            # walks: the v5e's HBM rate, 819 B/ns, times the
                            # lane as scheduled, 2.5 ns (3.70 bundles, 1.5 GHz)


class TilesPlan(NamedTuple):
    """Blocks of one product: ``row_block`` result rows and ``col_tile``
    rows of B in VMEM, ``chunk`` lane slots a grid step, ``k_tiles`` =
    k / 128, the grid's blocks and tiles, ``n_chunks``, the static bound on
    the chunks of any operand of these extents, ``group``, the rows a
    step of the grouped walk loads before it stores any, and ``runs``: the
    slots past a chunk's grouped ones are runs of ``group`` slots on ONE
    result row each, summed in registers (the transposed side's layout),
    not single lanes."""
    row_block: int
    col_tile: int
    chunk: int
    k_tiles: int
    row_blocks: int
    col_tiles: int
    n_chunks: int
    group: int
    runs: bool = False

    @property
    def stride(self) -> int:
        """What a packed word counts rows and columns in. Where a row of
        the blocks is whole (8, 128) registers (k a multiple of 1024) the
        kernel's views are flat, (rows · k/128, 128), and a word holds
        row · k/128 and column · k/128, the sublane a row starts at: no
        lane multiplies an index. Else 1: a row is part of a register
        behind a leading row index."""
        return self.k_tiles if self.k_tiles % 8 == 0 else 1

    @property
    def cover(self) -> int:
        """The lanes a segment's last chunk has to hold for its walk to
        outlast the copy of the next segment's tile of B (``col_tile`` rows
        of k floats at the HBM's rate, against the grouped walk's fastest
        lane): 3952 at 1976 × 1024, 4096 at the largest tile a plan makes
        (8 MiB). The placement counts the segments that hold them
        (``covered_segments``)."""
        tile_bytes = self.col_tile * self.k_tiles * LANES * 4
        return -(-tile_bytes // _COPY_BYTES_A_LANE)

    @property
    def layout(self) -> tuple:
        """What the placement depends on (``SparseMatrix.tiled_device``;
        the side is the caller's word: ``runs`` plans lay A's columns)."""
        return (self.row_block, self.col_tile, self.chunk, self.n_chunks,
                self.group, self.stride, self.cover)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def tiles_plan(shape: tuple, k: int, lanes: int, dtype,
               transposed: bool = False) -> tuple:
    """``(plan, None)`` when the kernel fits ``X (shape) · B (n × k)`` with
    ``lanes`` lane positions placed, else ``(None, why)``. Shapes only:
    whether the backend compiles Mosaic kernels is the caller's question.

    ``transposed``: the plan of ``Xᵀ · B`` (B is rows × k) — the result's
    rows are X's columns, the short and skewed side of a corpus, and B the
    long streamed one. Every row block of the result streams all of B once,
    so the blocks are twice as tall (4096 rows at k ≤ 1024: twelve passes
    over B at 47236 columns, not twenty-four; a packed word holds
    row · stride in 15 bits, which 4096 · 8 fills); the layout is the runs
    one (``TilesPlan.runs``), whose padding is bounded by 7/16 of the lanes
    and 896 slots a segment (``base.sparse._tile_runs``), and the chunk
    bound counts it."""
    rows, n = int(shape[0]), int(shape[1])
    if transposed:
        rows, n = n, rows
    if jnp.dtype(dtype) != jnp.float32:
        return None, f"dtype {jnp.dtype(dtype).name}"
    if k % LANES or not LANES <= k <= _MAX_K:
        return None, f"k {k} not a multiple of 128 up to {_MAX_K}"
    if rows < 1 or n < 1:
        return None, "empty operand"
    k_tiles = k // LANES
    cap = _BLOCK_ROWS if k_tiles <= 8 else _BLOCK_ROWS // 2
    row_block = min(2 * cap if transposed else cap, _round_up(rows, 8))
    # column tiles of one width (to 8 columns), not whole ones and a rest: a
    # narrow last tile is a short last segment of every row block, which
    # hides nothing of the block's write and the next tile's copy
    col_tile = _round_up(-(-n // -(-n // cap)), 8)
    row_blocks, col_tiles = -(-rows // row_block), -(-n // col_tile)
    # a run's slot walks in ≈ 1 ns, under half a grouped lane's time: the
    # runs layout takes the longer chunk first (110.6 → 105.1 ms a block of
    # the jlt_sparse_apply_cw cell, a third fewer steps)
    for chunk in (_CHUNKS[::-1] if transposed else _CHUNKS):
        if transposed:
            # a run's padding is under _GROUP slots: 7/16 of the lanes of
            # the rows kept whole, and a segment's other run lanes (under
            # _GROUP a rank class, and the grouped prefix's remainder) are
            # at most _RUN_ROW · (_GROUP − 1), each a run of its own at worst
            n_chunks = (-(-lanes * (_RUN_ROW + _GROUP - 1)
                          // (_RUN_ROW * chunk))
                        + row_blocks * col_tiles * (1 + -(
                            -_RUN_ROW * (_GROUP - 1) * _GROUP // chunk)))
        else:
            n_chunks = -(-lanes // chunk) + row_blocks * col_tiles
        if n_chunks <= _MAX_CHUNKS:
            return TilesPlan(row_block, col_tile, chunk, k_tiles, row_blocks,
                             col_tiles, n_chunks, _GROUP, transposed), None
    return None, f"chunk table {n_chunks} past {_MAX_CHUNKS} entries"


def vmem_bytes(plan: TilesPlan) -> int:
    """The VMEM the call holds: two buffers each of the result block and of
    B's tile (a row under 8 sublanes is padded to 8) and, under the flat
    views, the scratch block the walk accumulates in."""
    row = _round_up(plan.k_tiles, 8) * LANES * 4
    return (2 * (plan.row_block + plan.col_tile)
            + plan.row_block * (plan.stride > 1)) * row


def _kernel_tiles(col_tiles, group, stride, runs, segment, count, packed_ref,
                  vals_ref, b_ref, rows_ref, *scratch):
    """One grid step: the stored lanes of one chunk into their row block,
    the chunk's grouped slots ``group`` at a time, the rest one by one —
    or, under ``runs``, ``group`` slots of one row at a time. Under the
    flat views (``stride`` > 1) the block is a VMEM scratch, and the row
    block's last chunk hands it over to ``rows_ref`` as (row_block, k)
    rows (:func:`_hand_over`); else ``rows_ref`` is the block itself."""
    t = pl.program_id(0)
    block = segment[t] // col_tiles
    first = (t == 0) | (block != segment[jnp.maximum(t - 1, 0)] // col_tiles)
    out_ref = scratch[0] if scratch else rows_ref

    @pl.when(first)
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    _walk(group, stride, runs, count[t], packed_ref, vals_ref, b_ref, out_ref)
    if scratch:
        # the tables' segments never fall, so the next chunk is another
        # block's as soon as it is past this block's tiles: no divide a step
        end = pl.num_programs(0) - 1
        last = (t == end) | (segment[jnp.minimum(t + 1, end)]
                             >= (block + 1) * col_tiles)
        pl.when(last)(lambda: _hand_over(stride, out_ref, rows_ref))


def _hand_over(stride, acc_ref, rows_ref):
    """A finished block out of the walk's flat view (row_block · stride,
    128) into the call's own (row_block, k) block: register h of rows
    8g … 8g + 7 is the sublanes 8g · stride + h, + stride, … of the flat
    view — one strided load and one store a result register. Movement
    only; what leaves the call is laid as XLA lays a (rows, k) array, so
    nothing relays it."""
    def eight_rows(g, carry):
        at = pl.multiple_of(g * 8, 8)
        for h in range(stride):
            rows_ref[pl.ds(at, 8), h * LANES:(h + 1) * LANES] = acc_ref[
                pl.ds(at * stride + h, 8, stride=stride), :]
        return carry

    jax.lax.fori_loop(0, rows_ref.shape[0] // 8, eight_rows, 0)


def _walk(group, stride, runs, counts, packed_ref, vals_ref, b_ref, out_ref):
    """A chunk's stored lanes (``counts``: its entry of the count table)
    into the block ``out_ref``, in the walk's view."""

    def rows(word):
        """A lane's row of the result block and its row of B's tile."""
        row, col = word >> 16, word & 0xFFFF
        if stride == 1:
            return row, col
        return (pl.ds(pl.multiple_of(row, 8), stride),
                pl.ds(pl.multiple_of(col, 8), stride))

    def lane(j):
        row, col = rows(packed_ref[0, 0, j])
        out_ref[row] = out_ref[row] + vals_ref[0, 0, j] * b_ref[col]

    def ahead(words, values, at, groups):
        """``groups`` groups of slots from ``at`` on: a group's rows differ
        (the placement's word), so all its loads go before its stores."""
        for g in range(groups):
            into, sums = [], []
            for u in range(group):
                row, col = rows(words[0, 0, at + g * group + u])
                into.append(row)
                sums.append(out_ref[row]
                            + values[0, 0, at + g * group + u] * b_ref[col])
            for row, total in zip(into, sums):
                out_ref[row] = total

    def span(q, carry):
        # a 128-aligned window of the slots: the offsets inside it are
        # static, and straight-line code lets the scheduler lay a group's
        # scalar work under the one before's vector work
        window = pl.ds(pl.multiple_of(q * _SPAN, _SPAN), _SPAN)
        ahead(packed_ref.at[:, :, window], vals_ref.at[:, :, window], 0,
              _SPAN // group)
        return carry

    def one(g, carry):
        ahead(packed_ref, vals_ref, g * group, 1)
        return carry

    n, grouped = counts & 0xFFFF, counts >> 16
    spans = 0
    if packed_ref.shape[-1] % _SPAN == 0 and _SPAN % group == 0:
        spans = grouped // _SPAN
        jax.lax.fori_loop(0, spans, span, 0)
    groups = grouped // group
    jax.lax.fori_loop(spans * (_SPAN // group), groups, one, 0)
    done = groups * group

    if runs:
        def run(at):
            """``group`` slots on ONE result row (the placement's word; a
            short run is padded with zero-valued copies of its last lane):
            their terms summed in registers, the row loaded and stored
            once. A run's loads of B wait on no store."""
            row, col = rows(packed_ref[0, 0, at])
            terms = [vals_ref[0, 0, at] * b_ref[col]]
            for u in range(1, group):
                _, col = rows(packed_ref[0, 0, at + u])
                terms.append(vals_ref[0, 0, at + u] * b_ref[col])
            while len(terms) > 1:       # pairwise: a short chain of adds
                terms = ([a + b for a, b in zip(terms[::2], terms[1::2])]
                         + terms[len(terms) & ~1:])
            out_ref[row] = out_ref[row] + terms[0]

        def some(g, carry):
            for u in range(_RUN_UNROLL):
                run(done + (g * _RUN_UNROLL + u) * group)
            return carry

        def one_run(q, carry):
            run(done + q * group)
            return carry

        units = (n - done) // group
        whole = units // _RUN_UNROLL
        jax.lax.fori_loop(0, whole, some, 0)
        jax.lax.fori_loop(whole * _RUN_UNROLL, units, one_run, 0)
        return

    def serial(g, carry):
        for u in range(_UNROLL):
            lane(done + g * _UNROLL + u)
        return carry

    def single(j, carry):
        lane(j)
        return carry

    whole = (n - done) // _UNROLL
    jax.lax.fori_loop(0, whole, serial, 0)
    jax.lax.fori_loop(done + whole * _UNROLL, n, single, 0)


@functools.partial(jax.jit, static_argnames=("rows", "plan", "interpret"))
def _tiles_call(segment, count, packed, vals, B, *, rows: int,
                plan: TilesPlan, interpret: bool):
    """The walk over B (tiles · col_tile, k). Where ``plan.stride`` says
    flat, the result is (rows, k) as it stands — the blocks handed over as
    rows, the last one clipped to ``rows`` —; else it is in the kernel's
    view, (blocks · row_block, k/128, 128)."""
    slots = pl.BlockSpec((1, 1, plan.chunk), lambda t, seg, cnt: (t, 0, 0),
                         memory_space=pltpu.SMEM)
    flat = plan.stride > 1
    k = plan.k_tiles * LANES

    def view(rows):
        return ((rows * plan.k_tiles, LANES) if flat
                else (rows, plan.k_tiles, LANES))

    def at(i):
        return (i, 0) if flat else (i, 0, 0)

    return pl.pallas_call(
        functools.partial(_kernel_tiles, plan.col_tiles, plan.group,
                          plan.stride, plan.runs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(plan.n_chunks,),
            in_specs=[
                slots, slots,
                pl.BlockSpec(view(plan.col_tile), lambda t, seg, cnt:
                             at(seg[t] % plan.col_tiles))],
            out_specs=pl.BlockSpec(
                (plan.row_block, k) if flat else view(plan.row_block),
                lambda t, seg, cnt: at(seg[t] // plan.col_tiles)),
            scratch_shapes=([pltpu.VMEM(view(plan.row_block), jnp.float32)]
                            if flat else [])),
        out_shape=jax.ShapeDtypeStruct(
            (rows, k) if flat else view(plan.row_blocks * plan.row_block),
            jnp.float32),
        # sequential: a row block's chunks follow each other and add up
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem_bytes(plan) + (16 << 20)),
        interpret=interpret,
    )(segment, count, packed, vals, B.reshape(view(B.shape[0])))


def tiles_apply(segment, count, packed, vals, B, *, shape: tuple,
                plan: TilesPlan, interpret: bool = False) -> jnp.ndarray:
    """``X·B`` (rows × k, float32) for X placed as
    ``SparseMatrix.tiled_device(plan.layout)`` says and B (≥ n rows, k)
    float32: rows of B past n are never addressed (a B shorter than the
    column tiles reach is padded, a longer one handed over as it is: a
    slice would be a copy). B may come in the kernel's view, (≥ n, k/128,
    128): on a TPU the reshape of a (n, k) array to it is a copy of B.
    Where k is a multiple of 1024 (``plan.stride`` > 1) the result is the
    Mosaic call's own output, rows as XLA lays them: nothing relays it.
    At the other widths it leaves the call in the kernel's view and the
    reshape to (rows, k) is a copy of it. Traceable."""
    rows = int(shape[0])
    k = plan.k_tiles * LANES
    n_pad = plan.col_tiles * plan.col_tile
    if B.shape[0] < n_pad:
        B = jnp.pad(B, ((0, n_pad - B.shape[0]),) + ((0, 0),) * (B.ndim - 1))
    out = _tiles_call(segment, count, packed, vals, B, rows=rows, plan=plan,
                      interpret=interpret)
    return out if plan.stride > 1 else out.reshape(-1, k)[:rows]
