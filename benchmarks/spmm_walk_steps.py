"""What a step of the sparse × dense kernel's walk costs: one block of the
``jlt_sparse_apply`` cell, ``base.sparse.spmm``'s product with the operator
supplied, timed under the kernel's constants as shipped and patched — the
group, the unrolled span, the grouped counts zeroed (the serial walk over the
new order), the chunk (PR 60: 2048 the parent's, 8192, and what k = 128 and
2048 want), 4096-row blocks (B's tile 16 MiB), every count zeroed (the empty
walk: the step's floor) — and the host placement timed beside them. Run on
the chip; prints one line a variant (``benchmarks/spmm_walk_steps.log`` holds
PR 58's and PR 60's readings, ``PERF.md`` §6 what they mean).

    python3 benchmarks/spmm_walk_steps.py [seed [start of a variant's name ...]]

The sibling that sized PR 57's chunks is ``cellbench/tools/spmm_steps.py``.
"""
import functools
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
from libskylark_tpu.base.context import Context                 # noqa: E402
from libskylark_tpu.base.sparse import SparseMatrix             # noqa: E402
from libskylark_tpu.engine.bucket import lane_class             # noqa: E402
from libskylark_tpu.sketch import pallas_spmm, sparse_serve     # noqa: E402

cfg = json.load(open("cellbench/configs/jlt_rcv1_d47236_s1024.json"))
seed = int(sys.argv[1]) if len(sys.argv) > 1 else 2271560481
cdf = gen._zipf_cdf(cfg["n"], 1.0)
ids = seeds.rng(seed, "feature_ids").permutation(cfg["n"]).astype(np.int32)
X = gen._panel(cfg, seed, 0, cdf, ids)
only = sys.argv[2:]
print("device", jax.devices()[0].device_kind, "seed", seed, "nnz", X.nnz,
      flush=True)
shipped = (pallas_spmm._CHUNKS, pallas_spmm._GROUP, pallas_spmm._SPAN,
           pallas_spmm._BLOCK_ROWS)
ref = {}


@functools.lru_cache(maxsize=1)
def right_factor(k):
    """Sᵀ of a JLT(n, k), rows past n generated as the cell's program does."""
    T = sk.JLT(cfg["n"], k, Context(7))
    return jax.jit(lambda kd: sparse_serve.operator_rows(
        kd, T.scale, dist=randgen.Normal(), s_dim=k, n=49152,
        dtype=jnp.float32))(T.allocation.key_data).block_until_ready()


def variant(name, chunks=shipped[0], group=shipped[1], span=shipped[2],
            block_rows=shipped[3], k=1024, counts=lambda c: c):
    """One line: the plan, the lanes walked in groups, the segments whose
    last chunk covers the next tile's copy, the placement's seconds, five
    timings, and the largest difference from the first result of this k."""
    if only and not name.startswith(tuple(only)):
        return
    jax.clear_caches()
    (pallas_spmm._CHUNKS, pallas_spmm._GROUP, pallas_spmm._SPAN,
     pallas_spmm._BLOCK_ROWS) = chunks, group, span, block_rows
    plan, why = pallas_spmm.tiles_plan(X.shape, k, lane_class(X.nnz),
                                       jnp.float32)
    A = SparseMatrix.from_scipy(X)
    t0 = time.perf_counter()
    lanes = list(A.tiled_device(plan.layout))       # the host sort + upload
    place_s = time.perf_counter() - t0
    grouped = A.grouped_lanes(plan.layout)
    covered = A.covered_segments(plan.layout)
    lanes[1] = counts(lanes[1])
    f = jax.jit(functools.partial(sparse_serve.product_lanes,
                                  kernel="pallas_tiles", shape=A.shape,
                                  plan=plan))
    B = right_factor(k)
    out = f(*lanes, B).block_until_ready()
    ts = []
    for _ in range(5):
        t0 = time.perf_counter()
        out = f(*lanes, B).block_until_ready()
        ts.append(round((time.perf_counter() - t0) * 1e3, 2))
    err = None
    if bool(jnp.any(lanes[1])):
        err = float(jnp.abs(out - ref.setdefault(k, out)).max())
    print(f"{name:28s} k {k} blocks {plan.row_block}x{plan.col_tile} chunk "
          f"{plan.chunk} n_chunks {plan.n_chunks} group {plan.group} "
          f"grouped_lanes {grouped} ({grouped / X.nnz:.4f}) covered_segments "
          f"{covered} of {plan.row_blocks * plan.col_tiles} (cover "
          f"{plan.cover}) place_s {place_s:.2f} ms {ts} vs_first_maxabs {err}",
          flush=True)


empty = jnp.zeros_like      # every count zeroed: the walk's floor


variant("shipped")
variant("shipped empty", counts=empty)
variant("chunk 2048", chunks=(2048,))
variant("chunk 2048 empty", chunks=(2048,), counts=empty)
variant("chunk 8192", chunks=(8192,))
variant("chunk 8192 empty", chunks=(8192,), counts=empty)
variant("rows 4096 chunk 8192", chunks=(8192,), block_rows=4096)
variant("rows 4096 chunk 8192 empty", chunks=(8192,), block_rows=4096,
        counts=empty)
variant("rows 4096 chunk 4096", chunks=(4096,), block_rows=4096)
variant("serial on the new order", counts=lambda c: c & 0xFFFF)
variant("no span (a loop a group)", span=1 << 30)
variant("group 4", group=4)
variant("group 16", group=16)
for k in (128, 2048):
    for chunk in (1024, 2048, 4096, 8192):
        variant(f"k{k} chunk {chunk}", chunks=(chunk,), k=k)
    variant(f"k{k} chunk 4096 empty", chunks=(4096,), k=k, counts=empty)
