"""Pallas TPU kernel: the rowwise hash sketch of CSR lanes, every result
tile built in VMEM.

What is here: :func:`rows_plan` (does the kernel fit these extents),
:func:`rows_visits` (host arithmetic for the records), ``_kernel_rows`` /
``_rows_call`` (the kernel and its launch) and :func:`hash_rows_apply`
(the traceable entry). Who chooses it: ``sparse_serve.sparse_kernel``, from
the backend and the shapes alone, for ``HashTransform.apply`` on a
``SparseMatrix`` — on a TPU, rowwise, where :func:`rows_plan` fits. Where
it does not apply: the columnwise apply (lanes in row order feed no
contiguous run of result rows), any backend that compiles no Mosaic
kernel, extents :func:`rows_plan` declines, and the serve flush, which is
``jax.vmap`` of the lane program with the XLA scatter at every shape
(``engine/serve.py`` asks no kernel rule for a sparse bucket). Off the TPU
the kernel runs only interpreted, for the tests.

The direct sparse apply's program (``sketch.hash_sparse``) takes this
kernel in place of ``out.at[rows, bucket].add(term)`` — XLA's sort plus
element scatter into HBM, 222 of 235 ms at the cwt_sparse_apply cell —
wherever :func:`rows_plan` fits. It needs no table and no gather: bucket
and term are computed at the lane by the XLA prologue (``randgen
.stream_at``), and CSR lanes are already in row order, so a tile of R
result rows is fed by one contiguous run of lanes,
``indptr[R·t] … indptr[R·(t+1)]``, and no row id is ever stored: a
lane lies in the row whose start is at or before its position and whose
end is past it.

The v5e has no vector scatter, so a tile is accumulated by compares and a
contraction. With s_dim = H·128, split the bucket as b = hi·128 + lo. For
each chunk of 128 lanes build two factors from lane-oriented vectors
broadcast along sublanes,

    A[j, l]  = term_l · [hi_l·R + row_l − R·t = j]     (R·H × 128)
    B[lo, l] = [lo_l = lo]                             (128 × 128)

(A by masks: sublane r of ``[start_r ≤ position_l < end_r]``, the row
edges read from a table the step keeps with one row a sublane, ANDed with
``[hi_l = h]`` for each h)
and add A·Bᵀ (the contraction over the lane axis) to an (R·H × 128)
float32 accumulator: ``acc[hi·R : hi·R + R, :]`` is the tile's
``Z[R·t : R·t + R, hi·128 : hi·128 + 128]``. The term goes through the
MXU as three bfloat16 pieces that sum to the float32 value exactly, B is
0/1, so every product is exact and only the ORDER in which the terms of
one cell are added differs from the scatter's (last ulp); a cell with a
single term holds it to the bit. Lanes of a chunk that belong to a
neighbouring tile, and the lane padding, are masked by position and add
nothing. A non-finite value poisons the 128 buckets of its group in its
row (NaN·0 in the contraction), the caveat ``pallas_hash``'s "mxu" mode
documents; values under 2⁻¹⁰⁰ may lose low bits (a bfloat16 piece goes
subnormal).

One grid step builds ``_ROWS_A_STEP`` result rows: it streams its run of
lanes from HBM through a double-buffered ring of ``_ROWS_BLOCK``-chunk
blocks (the lane arrays are a (lanes/128, 128) view, read 8-chunk
aligned), fetches its row starts a step ahead, and writes its block of
the result once, in natural layout.

The walk. A step visits ONE sequence of (tile, chunk) pairs: for each of
its tiles that owns a lane, in order, the chunks ``p0 >> 7 … (p1 − 1) >>
7`` of the tile's lanes ``[p0, p1)`` — a chunk that two tiles share once
for each, an empty tile not at all (:func:`rows_visits` counts them). The
state of the walk is two scalars, the live tile and the chunk
(:func:`_next_visit`); a visit adds its product to its tile's slot of the
step's accumulator, and nothing in the loop body depends on which tile
that is, so a tile's end costs nothing. The body is unrolled
``_ROWS_UNROLL`` visits an iteration; only the last iteration of a ring
block can run past the block's (or the step's) last visit, and what
overruns is masked. Before PR 41 the loop was nested — ring block, tile,
chunks in sixes — and every tile was rounded up to six visits in every
block it touched: 239 k visit slots for the cell's 184 k pairs.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from libskylark_tpu.sketch.pallas_dense import available, compiler_params

LANES = 128
_ROWS_A_STEP = 256      # result rows a grid step: 1024 steps at 262144 rows
_ROWS_BLOCK = 128       # chunks a DMA (64 KiB an array)
# visits an iteration of the walk, so that the MXU results of one overlap
# the factor building of the next: at the cell 14.8 ms a call unrolled by
# 6, 13.7 by 8, 12.6 by 12, 12.0 by 16, 11.7 by 20 (the nested walk: 20.0
# by 6) — the lowering and Mosaic's compile grow with it, the gain past 16
# does not (PERF.md PR 41)
_ROWS_UNROLL = 16
# entries of the scalar-prefetched table of tile starts: a quarter of the
# v5e's 1 MiB of SMEM (the whole ``indptr`` of the cell is 4 B too many)
_ROWS_MAX_TABLE = 1 << 16
_STARTS_WINDOW = 1024   # row starts a DMA: 8 rows of the (rows/128, 128)
                        # view, so every read is tile-aligned


def rows_plan(n_rows: int, s_dim: int, lanes: int, dtype) -> Optional[tuple]:
    """``(R, H, G)`` — rows a tile, 128-wide bucket groups, tiles a grid
    step — when the kernel fits a rowwise apply of these extents, else
    None: float32 values, ``s_dim`` = H·128 with H ≤ 16 (R = 8 rows a tile,
    16 where H is odd: the bfloat16 factor A wants R·H a multiple of 16),
    the row count a multiple of R, the lane extent a multiple of 1024
    (every ``lane_class`` ≥ 2¹⁵ is) and at least a block, the table of tile
    starts inside SMEM. Shapes only: whether the backend compiles Mosaic
    kernels is the caller's question."""
    h = s_dim // LANES
    if (jnp.dtype(dtype) != jnp.float32
            or s_dim != h * LANES or not 1 <= h <= 16
            or lanes < _ROWS_BLOCK * LANES or lanes % 1024):
        return None
    r = 16 if h % 2 else 8
    n_tiles = n_rows // r
    if n_rows < r or n_rows % r or n_tiles >= _ROWS_MAX_TABLE:
        return None
    g = _ROWS_A_STEP // r
    while n_tiles % g:
        g //= 2
    return r, h, g


def _fitted_plan(n_rows: int, s_dim: int, lanes: int, dtype) -> tuple:
    plan = rows_plan(n_rows, s_dim, lanes, dtype)
    if plan is None:
        raise ValueError(
            f"rowwise sparse kernel does not fit rows={n_rows} "
            f"s_dim={s_dim} lanes={lanes} dtype={jnp.dtype(dtype).name}")
    return plan


def rows_visits(indptr, n_rows: int, s_dim: int, lanes: int) -> tuple:
    """``(visits, chunks)`` of one apply: the (tile, chunk) pairs the
    kernel's walk visits — for each tile with lanes ``[p0, p1)`` the chunks
    ``p0 >> 7 … (p1 − 1) >> 7``, a border chunk once for each tile that owns
    lanes of it, an empty tile not at all — and the chunks that hold a
    stored lane. Host arithmetic on ``indptr`` for the records and the
    tests; no apply calls it."""
    r = _fitted_plan(n_rows, s_dim, lanes, jnp.float32)[0]

    def chunks(p0, p1):     # of the runs [p0, p1) that hold a lane
        live = p1 > p0
        return int((((p1[live] - 1) >> 7) - (p0[live] >> 7) + 1).sum())

    indptr = np.asarray(indptr, np.int64)
    return chunks(indptr[:-1:r], indptr[r::r]), chunks(indptr[:1], indptr[-1:])


def _next_visit(it, c, p1, valid):
    """The walk's advance from a visit of chunk ``c`` by live tile ``it``,
    whose lanes end at ``p1``: the next chunk of the tile, or — the tile
    done — the next live tile at the chunk its first lane lies in (``p1``
    is that lane: empty tiles hold none), the same chunk again where the
    border falls inside it. A masked visit stays where it is."""
    done = c >= (p1 - 1) >> 7
    return (jnp.where(valid & done, it + 1, it),
            jnp.where(valid, jnp.where(done, p1 >> 7, c + 1), c))


def _kernel_rows(R, H, G, n_chunks, tptr, term_hbm, bucket_hbm, starts_hbm,
                 out_ref, tbuf, bbuf, acc_ref, sem, sbuf, ssem, edges,
                 tile_of, end_of):
    """One grid step: G row tiles from their contiguous run of lanes."""
    NB, U, J = _ROWS_BLOCK, _ROWS_UNROLL, R * H
    step = pl.program_id(0)
    t0 = step * G
    run_lo, run_hi = tptr[t0], tptr[t0 + G]
    first = (run_lo >> 10) << 3         # the run's first chunk, 8-aligned
    n_blocks = jnp.where(
        run_hi > run_lo, (((run_hi + LANES - 1) >> 7) - first + NB - 1) // NB,
        0)

    def starts_copy(of_step, slot):
        window = (of_step * (G * R)) // _STARTS_WINDOW
        return pltpu.make_async_copy(
            starts_hbm.at[pl.ds(pl.multiple_of(window * 8, 8), 8)],
            sbuf.at[slot], ssem.at[slot])

    def block_copies(k, slot):
        # the last block is read from where it still ends inside the lanes
        src = pl.ds(pl.multiple_of(
            jnp.minimum(first + k * NB, n_chunks - NB), 8), NB)
        return (pltpu.make_async_copy(term_hbm.at[src], tbuf.at[slot],
                                      sem.at[0, slot]),
                pltpu.make_async_copy(bucket_hbm.at[src], bbuf.at[slot],
                                      sem.at[1, slot]))

    @pl.when(step == 0)
    def _own_starts():
        starts_copy(0, 0).start()

    @pl.when(step + 1 < pl.num_programs(0))
    def _next_starts():
        starts_copy(step + 1, (step + 1) % 2).start()

    @pl.when(n_blocks > 0)
    def _first_block():
        for c in block_copies(0, 0):
            c.start()

    acc_ref[...] = jnp.zeros_like(acc_ref)
    starts_copy(step, step % 2).wait()

    # where each row of the step starts, one row a sublane and the same in
    # every lane — what a visit compares its lanes' positions with: the
    # step's lane rows of the window turned on their side, and the end of
    # the last row behind them
    row0 = (step * (G * R)) % _STARTS_WINDOW
    for q in range(max(G * R // LANES, 1)):
        across = jnp.broadcast_to(
            sbuf[step % 2, pl.ds((row0 >> 7) + q, 1), :], (8, LANES))
        edges[q * LANES:(q + 1) * LANES, :] = jnp.broadcast_to(
            across.T[:, :1], (LANES, LANES))
    edge0 = pl.multiple_of(row0 & (LANES - 1), 8)
    edges[pl.ds(edge0 + G * R, 8), :] = jnp.full((8, LANES), run_hi)

    def note(g, n):
        # the step's tiles that own a lane, in order: live tile n is tile g
        p1 = tptr[t0 + g + 1]
        tile_of[n] = g
        end_of[n] = p1
        return n + (p1 > tptr[t0 + g]).astype(jnp.int32)

    n_live = jax.lax.fori_loop(0, G, note, 0)

    lane = jax.lax.broadcasted_iota(jnp.int32, (R, LANES), 1)
    lo_of = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)
    contract_lanes = (((1,), (1,)), ((), ()))

    def visit(slot, base, block_hi, state):
        """Chunk ``c`` for live tile ``it``; masked (and the state left as
        it is) past the block's or the step's end."""
        it, c = state
        valid = (it < n_live) & (c < block_hi)
        at = jnp.minimum(it, n_live - 1)
        g = tile_of[at]
        i = jnp.where(valid, c - base, 0)
        # a lane's row in the tile by its position; a masked visit's lanes
        # stand before every row
        pos = jnp.where(valid, c * LANES, -LANES) + lane
        edge = edge0 + g * R
        in_row = ((pos >= edges[pl.ds(pl.multiple_of(edge, 8), R), :])
                  & (pos < edges[pl.ds(edge + 1, R), :]))
        x = jnp.broadcast_to(tbuf[slot, pl.ds(i, 1), :], (R, LANES))
        bucket = bbuf[slot, pl.ds(i, 1), :]
        # x = x0 + x1 + x2 exactly, each piece a bfloat16
        x0 = x.astype(jnp.bfloat16).astype(jnp.float32)
        rest = x - x0
        x1 = rest.astype(jnp.bfloat16).astype(jnp.float32)
        x2 = rest - x1
        hi = jnp.broadcast_to(bucket >> 7, (R, LANES))
        groups = [in_row & (hi == h) for h in range(H)]
        a3 = jnp.concatenate(
            [jnp.where(at_h, piece, 0.0)
             for piece in (x0, x1, x2) for at_h in groups],
            axis=0).astype(jnp.bfloat16)
        b = (lo_of == (bucket & (LANES - 1))
             ).astype(jnp.float32).astype(jnp.bfloat16)
        # one bfloat16 pass, said aloud: the package's default matmul
        # precision is float32, which Mosaic refuses for bfloat16
        # operands; the products are exact either way
        part = jax.lax.dot_general(
            a3, b, contract_lanes, precision=jax.lax.Precision.DEFAULT,
            preferred_element_type=jnp.float32)
        acc_ref[g] += (part[:J] + part[J:2 * J]) + part[2 * J:]
        return _next_visit(it, c, end_of[at], valid)

    def block(k, state):
        slot = k % 2

        @pl.when(k + 1 < n_blocks)
        def _next_block():
            for c in block_copies(k + 1, 1 - slot):
                c.start()

        for c in block_copies(k, slot):
            c.wait()
        block_lo = first + k * NB
        base = jnp.minimum(block_lo, n_chunks - NB)     # chunk of ring row 0

        def visits(state):
            # traced once, lowered U times
            return jax.lax.fori_loop(
                0, U, lambda _, s: visit(slot, base, block_lo + NB, s),
                state, unroll=True)

        return jax.lax.while_loop(
            lambda s: (s[0] < n_live) & (s[1] < block_lo + NB), visits, state)

    jax.lax.fori_loop(0, n_blocks, block, (jnp.int32(0), run_lo >> 7))

    def write(g, carry):
        # a loop, not G·H static copies: Mosaic's lowering takes 5 ms a
        # ref access on the host, 2.6 s of a first apply for 512 of them
        rows = pl.ds(pl.multiple_of(g * R, R), R)
        for h in range(H):
            out_ref[rows, h * LANES:(h + 1) * LANES] = (
                acc_ref[g, h * R:(h + 1) * R, :])
        return carry

    jax.lax.fori_loop(0, G, write, 0)


@functools.partial(jax.jit, static_argnames=("n_rows", "s_dim", "plan",
                                             "interpret"))
def _rows_call(tile_ptr, term, bucket, starts, *, n_rows, s_dim, plan,
               interpret):
    R, H, G = plan
    NB = _ROWS_BLOCK
    lane_array = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        functools.partial(_kernel_rows, R, H, G, term.shape[0]),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_rows // (G * R),),
            in_specs=[lane_array, lane_array, lane_array],
            out_specs=pl.BlockSpec((G * R, s_dim), lambda s, tptr: (s, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, NB, LANES), jnp.float32),   # the ring
                pltpu.VMEM((2, NB, LANES), jnp.int32),
                pltpu.VMEM((G, R * H, LANES), jnp.float32),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((2, 8, LANES), jnp.int32),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((max(G * R, LANES) + 8, LANES), jnp.int32),
                pltpu.SMEM((G,), jnp.int32),
                pltpu.SMEM((G,), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((n_rows, s_dim), jnp.float32),
        # sequential: a step starts the next step's copy of its row starts
        compiler_params=compiler_params("arbitrary"),
        interpret=interpret,
    )(tile_ptr, term, bucket, starts)


def hash_rows_apply(term, bucket, indptr, *, n_rows: int, s_dim: int,
                    interpret: bool = False) -> jnp.ndarray:
    """``zeros((n_rows, s_dim)).at[rows, bucket].add(term)`` for CSR lanes
    in row order — ``term``/``bucket`` (lanes,), ``indptr`` (n_rows + 1,);
    the row of a lane is what ``indptr`` says — by the kernel above.
    Traceable: the sparse program calls it after its lane prologue.
    :func:`rows_plan` must fit. Differs from the scatter only in the order
    the terms of one cell are added."""
    plan = _fitted_plan(n_rows, s_dim, term.shape[0], term.dtype)
    starts = jnp.pad(indptr[:-1], (0, -n_rows % _STARTS_WINDOW))
    return _rows_call(
        indptr[::plan[0]], term.reshape(-1, LANES),
        bucket.reshape(-1, LANES), starts.reshape(-1, LANES),
        n_rows=n_rows, s_dim=s_dim, plan=plan, interpret=interpret)
