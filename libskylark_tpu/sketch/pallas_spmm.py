"""Pallas TPU kernel: sparse × dense, ``Y = X·B`` for a sparse row block X
(rows × n) and a dense right factor B (n × k), the result built in VMEM
one block of rows at a time from lanes regrouped at placement.

What is here: :func:`tiles_plan` (do these extents fit, and with which
blocks), ``_kernel_tiles`` / ``_tiles_call`` (the kernel and its launch) and
:func:`tiles_apply` (the traceable entry). Who chooses it:
``sparse_serve.product_kernel``, from the backend and the shapes alone, for
``base.sparse.spmm`` and for ``DenseTransform.apply`` on a ``SparseMatrix``
rowwise (the dense sketch of a sparse operand, whose right factor is Sᵀ
generated inside the same program). Where it does not apply: a backend that
compiles no Mosaic kernel, values that are not float32, a width k that is
no multiple of 128 or past 2048, a chunk table past SMEM; ``spmm_t`` and the
columnwise sparse apply (their contraction runs down the rows: lanes
regrouped by row block feed no resident block of the result). Off the TPU
the kernel runs only interpreted, for the tests.

The layout (``SparseMatrix.tiled_device``, placed once). The row axis is
cut into blocks of ``row_block`` rows, the column axis into tiles of
``col_tile`` columns; a *segment* is the stored nonzeros of one (row block,
column tile) pair, row-major inside it. Segments lie in (row block, column
tile) order, each in ⌈stored ÷ ``chunk``⌉ *chunks* of ``chunk`` lane slots,
all full but the segment's first, which holds the remainder (the walk of a
segment's last chunk is what hides the copy of the next segment's tile of B
and the write of a finished block: a nearly empty last chunk would hide
nothing), and the column tiles are of one width, to 8 columns, so that no
row block ends on a narrow tile's short segment; a slot holds the lane's
value and one packed word, its row in the block · 2¹⁶ + its column in the
tile. Two tables, one entry a chunk, say which segment the
chunk belongs to and how many of its slots are stored lanes. Every row
block owns at least one chunk, so that its block of the result is written.

The walk. The v5e has no vector gather, so a lane's row of B is not
gathered: it is *addressed*. B is viewed (n, k/128, 128) and the result
(rows, k/128, 128), so that one row of either is whole vector registers
behind a leading index; a grid step is one chunk, its slots in SMEM, the
column tile of B (``col_tile`` rows) and the row block of the result
(``row_block`` rows) in VMEM, both fetched by the pipeline only when the
chunk's segment changes tile or block; and the lane at slot j does

    Y[row_j] += value_j · B[column_j]

on the VPU in float32: a load of each row, a multiply-add, a store. The
products and sums are float32 arithmetic (no bfloat16 piece anywhere),
each stored nonzero contributes exactly once, and the terms of one result
row are added in column-tile order, row-major inside a tile. The loop stops
at the chunk's count: padding slots are moved, never multiplied, so a
non-finite entry of B poisons only the rows whose lanes address it.

Workspace. VMEM: 2 · (row_block + col_tile) rows of max(k, 1024) floats
(at most 32 MiB at k = 1024). HBM, beside the operands and the result: none
in the kernel; :func:`tiles_apply` adds the (rows, k) relayout of the
result's (rows, k/128, 128) view. Nothing grows with nnz · k.
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
# lane slots a grid step, the first that fits. 2048: a segment's last chunk
# has to outlast the copy of the next segment's tile of B (8 MiB, ≈ 10 µs ≈
# 1200 lanes of walk); at the jlt_sparse_apply cell 1024 slots read 171.0 ms
# a block, 2048 157.0, 4096 156.6, 512 184.3 (PERF.md PR 57)
_CHUNKS = (2048, 4096)
_MAX_CHUNKS = 1 << 15   # entries of each scalar-prefetched table: two tables
                        # take a quarter of the v5e's 1 MiB of SMEM
_UNROLL = 8             # lanes an iteration of the walk
_MAX_K = 2048


class TilesPlan(NamedTuple):
    """Blocks of one product: ``row_block`` result rows and ``col_tile``
    rows of B in VMEM, ``chunk`` lane slots a grid step, ``k_tiles`` =
    k / 128, the grid's blocks and tiles, and ``n_chunks``, the static
    bound on the chunks of any operand of these extents."""
    row_block: int
    col_tile: int
    chunk: int
    k_tiles: int
    row_blocks: int
    col_tiles: int
    n_chunks: int

    @property
    def layout(self) -> tuple:
        """What the placement depends on (``SparseMatrix.tiled_device``)."""
        return (self.row_block, self.col_tile, self.chunk, self.n_chunks)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def tiles_plan(shape: tuple, k: int, lanes: int, dtype) -> tuple:
    """``(plan, None)`` when the kernel fits ``X (shape) · B (n × k)`` with
    ``lanes`` lane positions placed, else ``(None, why)``. Shapes only:
    whether the backend compiles Mosaic kernels is the caller's question."""
    rows, n = int(shape[0]), int(shape[1])
    if jnp.dtype(dtype) != jnp.float32:
        return None, f"dtype {jnp.dtype(dtype).name}"
    if k % LANES or not LANES <= k <= _MAX_K:
        return None, f"k {k} not a multiple of 128 up to {_MAX_K}"
    if rows < 1 or n < 1:
        return None, "empty operand"
    k_tiles = k // LANES
    cap = _BLOCK_ROWS if k_tiles <= 8 else _BLOCK_ROWS // 2
    row_block = min(cap, _round_up(rows, 8))
    # column tiles of one width (to 8 columns), not whole ones and a rest: a
    # narrow last tile is a short last segment of every row block, which
    # hides nothing of the block's write and the next tile's copy
    col_tile = _round_up(-(-n // -(-n // cap)), 8)
    row_blocks, col_tiles = -(-rows // row_block), -(-n // col_tile)
    for chunk in _CHUNKS:
        n_chunks = -(-lanes // chunk) + row_blocks * col_tiles
        if n_chunks <= _MAX_CHUNKS:
            return TilesPlan(row_block, col_tile, chunk, k_tiles, row_blocks,
                             col_tiles, n_chunks), None
    return None, f"chunk table {n_chunks} past {_MAX_CHUNKS} entries"


def vmem_bytes(plan: TilesPlan) -> int:
    """The VMEM the pipeline holds: two buffers each of the result block
    and of B's tile (a row under 8 sublanes is padded to 8)."""
    row = _round_up(plan.k_tiles, 8) * LANES * 4
    return 2 * (plan.row_block + plan.col_tile) * row


def _kernel_tiles(col_tiles, segment, count, packed_ref, vals_ref, b_ref,
                  out_ref):
    """One grid step: the stored lanes of one chunk into their row block."""
    t = pl.program_id(0)
    block = segment[t] // col_tiles
    first = (t == 0) | (block != segment[jnp.maximum(t - 1, 0)] // col_tiles)

    @pl.when(first)
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    def lane(j):
        word = packed_ref[0, 0, j]
        row, col = word >> 16, word & 0xFFFF
        out_ref[row] = out_ref[row] + vals_ref[0, 0, j] * b_ref[col]

    def group(g, carry):
        for u in range(_UNROLL):
            lane(g * _UNROLL + u)
        return carry

    def single(j, carry):
        lane(j)
        return carry

    n = count[t]
    whole = n // _UNROLL
    jax.lax.fori_loop(0, whole, group, 0)
    jax.lax.fori_loop(whole * _UNROLL, n, single, 0)


@functools.partial(jax.jit, static_argnames=("plan", "interpret"))
def _tiles_call(segment, count, packed, vals, b3, *, plan: TilesPlan,
                interpret: bool):
    slots = pl.BlockSpec((1, 1, plan.chunk), lambda t, seg, cnt: (t, 0, 0),
                         memory_space=pltpu.SMEM)
    return pl.pallas_call(
        functools.partial(_kernel_tiles, plan.col_tiles),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(plan.n_chunks,),
            in_specs=[
                slots, slots,
                pl.BlockSpec((plan.col_tile, plan.k_tiles, LANES),
                             lambda t, seg, cnt:
                             (seg[t] % plan.col_tiles, 0, 0))],
            out_specs=pl.BlockSpec(
                (plan.row_block, plan.k_tiles, LANES),
                lambda t, seg, cnt: (seg[t] // plan.col_tiles, 0, 0))),
        out_shape=jax.ShapeDtypeStruct(
            (plan.row_blocks * plan.row_block, plan.k_tiles, LANES),
            jnp.float32),
        # sequential: a row block's chunks follow each other and add up
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem_bytes(plan) + (16 << 20)),
        interpret=interpret,
    )(segment, count, packed, vals, b3)


def tiles_apply(segment, count, packed, vals, B, *, shape: tuple,
                plan: TilesPlan, interpret: bool = False) -> jnp.ndarray:
    """``X·B`` (rows × k, float32) for X placed as
    ``SparseMatrix.tiled_device(plan.layout)`` says and B (≥ n rows, k)
    float32: rows of B past n are never addressed. Traceable."""
    rows = int(shape[0])
    k = plan.k_tiles * LANES
    n_pad = plan.col_tiles * plan.col_tile
    if B.shape[0] < n_pad:
        B = jnp.pad(B, ((0, n_pad - B.shape[0]), (0, 0)))
    out = _tiles_call(segment, count, packed, vals,
                      B[:n_pad].reshape(n_pad, plan.k_tiles, LANES),
                      plan=plan, interpret=interpret)
    return out.reshape(-1, k)[:rows]
