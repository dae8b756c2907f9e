"""What a grid step of the sparse × dense kernel costs: one block of the
``jlt_sparse_apply`` cell, ``base.sparse.spmm``'s product with the operator
supplied, timed under other chunk sizes and unrolls and with every chunk's
count zeroed (the walk empty: what is left is the step's floor). The
benchmark's own runs do not run this; it patches the kernel's constants in
its own process and prints one line a variant.

    python3 -m cellbench.tools.spmm_steps

PR 57 found 8.55 µs a live chunk whatever the chunk held (``PERF.md`` §6) and
wrote this to find why: its readings (``records/jlt_sparse_apply_steps.log``)
set the kernel's chunk to 2048 slots.
"""
import functools, json, time
import numpy as np
import jax, jax.numpy as jnp
from cellbench import seeds
from cellbench.drivers import sparse_hash_apply as gen
from libskylark_tpu import sketch as sk
from libskylark_tpu.base.context import Context
from libskylark_tpu.base.sparse import SparseMatrix
from libskylark_tpu.base import randgen
from libskylark_tpu.sketch import pallas_spmm, sparse_serve

cfg = json.load(open("cellbench/configs/jlt_rcv1_d47236_s1024.json"))
seed = 2271560481
cdf = gen._zipf_cdf(cfg["n"], 1.0)
ids = seeds.rng(seed, "feature_ids").permutation(cfg["n"]).astype(np.int32)
X = gen._panel(cfg, seed, 0, cdf, ids)
A = SparseMatrix.from_scipy(X)
T = sk.JLT(cfg["n"], 1024, Context(7))
B = jax.jit(lambda kd: sparse_serve.operator_rows(kd, T.scale, dist=randgen.Normal(), s_dim=1024, n=49152, dtype=jnp.float32))(T.allocation.key_data).block_until_ready()
ref = None

def variant(name, chunks, unroll, block_rows=2048, empty=False):
    global ref
    jax.clear_caches()
    pallas_spmm._CHUNKS, pallas_spmm._UNROLL, pallas_spmm._BLOCK_ROWS = chunks, unroll, block_rows
    plan, why = pallas_spmm.tiles_plan(A.shape, 1024, 19922944, jnp.float32)
    if plan is None:
        print(name, "declined", why, flush=True); return
    lanes = list(A.tiled_device(plan.layout))
    live = int((np.asarray(lanes[1]) > 0).sum())
    if empty:
        lanes[1] = jnp.zeros_like(lanes[1])
    f = jax.jit(functools.partial(sparse_serve.product_lanes, kernel="pallas_tiles", shape=A.shape, plan=plan))
    out = f(*lanes, B).block_until_ready()
    ts = []
    for _ in range(5):
        t0 = time.perf_counter(); out = f(*lanes, B).block_until_ready(); ts.append((time.perf_counter() - t0) * 1e3)
    err = None
    if not empty:
        if ref is None: ref = out
        err = float(jnp.abs(out - ref).max())
    print(name, "plan", plan, "live_chunks", live, "ms", [round(t, 2) for t in ts], "vs_baseline_maxabs", err, flush=True)

variant("V0 chunk1024 U8", (1024,), 8)     # the first row is the others' reference
variant("F0 chunk1024 empty", (1024,), 8, empty=True)
pallas_spmm._MAX_CHUNKS = 1 << 16
variant("V7 chunk512 U8", (512,), 8)
variant("V1 chunk2048 U8", (2048,), 8)
variant("V2 chunk4096 U8", (4096,), 8)
variant("F2 chunk4096 empty", (4096,), 8, empty=True)
variant("V3 chunk1024 U16", (1024,), 16)
variant("V4 chunk4096 U16", (4096,), 16)
variant("V5 chunk4096 U4", (4096,), 4)
variant("V6 chunk4096 U8 blocks1024", (4096,), 8, block_rows=1024)
