"""What the transposed sparse × dense walk costs and where: one block of the
``jlt_sparse_apply_cw`` cell, ``Xᵀ·B`` with the operator supplied in the
kernel's view, timed under the plan as shipped and under variants — every
count zeroed (the empty walk: B's stream and the steps alone), every segment
sent to B's first tile (the whole walk with B's stream taken away: wrong
sums, right time — shipped less this one is the part of the stream that no
walk hides), the runs left out (the grouped region alone), the runs' unroll,
8192-slot chunks, 2048-row result blocks (24 passes over B, not 12), and the
rowwise side's layout on the same lanes (PR 58's (rank, row) order, ranks
past 14 walked lane by lane: what the transposed side read before it had a
layout of its own) — with the host placement's seconds beside them. Run on
the chip; one line a variant (``PERF.md`` §6 says what they mean).

    python3 benchmarks/spmm_t_walk_steps.py [seed [start of a variant's name ...]]

The rowwise side's sibling is ``benchmarks/spmm_walk_steps.py``.
"""
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.getcwd())
from cellbench import seeds                                     # noqa: E402
from cellbench.drivers import sparse_hash_apply as gen          # noqa: E402
from libskylark_tpu import sketch as sk                         # noqa: E402
from libskylark_tpu.base import randgen                         # noqa: E402
from libskylark_tpu.base import sparse as sparse_mod            # noqa: E402
from libskylark_tpu.base.context import Context                 # noqa: E402
from libskylark_tpu.base.sparse import SparseMatrix             # noqa: E402
from libskylark_tpu.engine.bucket import lane_class             # noqa: E402
from libskylark_tpu.sketch import pallas_spmm, sparse_serve     # noqa: E402

cfg = json.load(open("cellbench/configs/jlt_rcv1_m524288_s1024_cw.json"))
seed = int(sys.argv[1]) if len(sys.argv) > 1 else 2271560481
only = sys.argv[2:]
m, n, k = cfg["rows_per_panel"], cfg["n"], cfg["s"]
cdf = gen._zipf_cdf(n, 1.0)
ids = seeds.rng(seed, "feature_ids").permutation(n).astype(np.int32)
X = gen._panel(cfg, seed, 0, cdf, ids)
A = SparseMatrix.from_scipy(X)
lanes = lane_class(X.nnz)
print("device", jax.devices()[0].device_kind, "seed", seed, "nnz", X.nnz,
      flush=True)

T = sk.JLT(m, k, Context(7))
B = jax.jit(lambda kd: sparse_serve.operator_rows_panels(
    kd, T.scale, dist=randgen.Normal(), s_dim=k, n=m, dtype=jnp.float32,
    lanes=pallas_spmm.LANES))(T.allocation.key_data).block_until_ready()
ref = {}


def timed(plan, placed, name, extra=""):
    call = jax.jit(lambda *a: pallas_spmm.tiles_apply(
        *a, shape=(n, m), plan=plan))
    out = call(*placed, B).block_until_ready()
    samples = []
    for _ in range(5):
        t0 = time.perf_counter()
        call(*placed, B).block_until_ready()
        samples.append(time.perf_counter() - t0)
    digest = float(jnp.sum(jnp.abs(out)))
    ref.setdefault("sum", digest)
    print(f"{name}: row_block={plan.row_block} col_tile={plan.col_tile} "
          f"chunk={plan.chunk} n_chunks={plan.n_chunks} "
          f"ms={1e3 * float(np.median(samples)):.2f} "
          f"abs_sum_vs_shipped={digest / ref['sum']:.7f} {extra}", flush=True)


def wanted(name):
    return not only or any(name.startswith(o) for o in only)


def place(plan, runs=True):
    t0 = time.perf_counter()
    tile = sparse_mod._tile_runs if runs else sparse_mod._tile_lanes
    arrays, counts = tile(
        *A.csc_parts(), shape=(n, m), row_block=plan.row_block,
        col_tile=plan.col_tile, chunk=plan.chunk, n_chunks=plan.n_chunks,
        group=plan.group, stride=plan.stride, cover=plan.cover)
    host_s = time.perf_counter() - t0
    placed = jax.block_until_ready(tuple(jnp.asarray(a) for a in arrays))
    return placed, f"{counts} host_s={host_s:.2f} total_s={time.perf_counter() - t0:.2f}"


plan, why = pallas_spmm.tiles_plan((m, n), k, lanes, jnp.float32,
                                   transposed=True)
assert plan is not None, why
placed, said = place(plan)
timed(plan, placed, "shipped", said)
segment, count, packed, vals = placed
if wanted("empty"):
    timed(plan, (segment, jnp.zeros_like(count), packed, vals), "empty_walk")
if wanted("b_pinned"):
    # a segment's tile of B is segment % col_tiles, its block of the result
    # segment // col_tiles: with the remainder struck every chunk walks its
    # own lanes into its own block over ONE resident tile, fetched once —
    # the steps, the lanes and the result's writes of the shipped walk,
    # none of B's 12 × 2 GiB (31.5 ms at the HBM's rate, if nothing hid it)
    timed(plan, (segment - segment % plan.col_tiles, count, packed, vals),
          "b_pinned")
if wanted("grouped_only"):
    grouped = count >> 16
    timed(plan, (segment, grouped | grouped << 16, packed, vals),
          "grouped_only")
for unroll in (1, 2, 8):
    if wanted("unroll"):
        pallas_spmm._RUN_UNROLL = unroll
        jax.clear_caches()
        timed(plan, placed, f"unroll_{unroll}")
pallas_spmm._RUN_UNROLL = 4
jax.clear_caches()
if wanted("chunk_8192"):
    pallas_spmm._CHUNKS = (8192,)
    wide, why = pallas_spmm.tiles_plan((m, n), k, lanes, jnp.float32,
                                       transposed=True)
    pallas_spmm._CHUNKS = (4096, 8192)
    assert wide is not None, why
    placed_wide, said = place(wide)
    timed(wide, placed_wide, "chunk_8192", said)
    del placed_wide
if wanted("rows_2048"):
    low = plan._replace(row_block=2048, row_blocks=-(-n // 2048),
                        n_chunks=plan.n_chunks + 6144)
    placed_low, said = place(low)
    timed(low, placed_low, "rows_2048", said)
    del placed_low
if wanted("rowwise_layout"):
    # the accepted side's layout on the transposed lanes
    old = plan._replace(runs=False, n_chunks=-(-lanes // plan.chunk)
                        + plan.row_blocks * plan.col_tiles)
    placed_old, said = place(old, runs=False)
    timed(old, placed_old, "rowwise_layout", said)
for run_row, chunks, unroll in ((8, (8192,), 8), (4, (8192,), 8), (2, (8192,), 8),
                                (16, (8192,), 8)):
    name = f"run_row_{run_row}_chunk_{chunks[0]}_unroll_{unroll}"
    if wanted("run_row"):
        pallas_spmm._RUN_ROW = run_row
        pallas_spmm._CHUNKS, pallas_spmm._RUN_UNROLL = chunks, unroll
        jax.clear_caches()
        other, why = pallas_spmm.tiles_plan((m, n), k, lanes, jnp.float32,
                                            transposed=True)
        assert other is not None, why
        placed_other, said = place(other)
        timed(other, placed_other, name, said)
        del placed_other
