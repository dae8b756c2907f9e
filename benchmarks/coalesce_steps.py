"""One block of the sparse → sparse hash sketch, stage by stage, on the chip:

    python3 benchmarks/coalesce_steps.py [--rows 524288] [--mean 116]

prints the milliseconds of the lane streams, the row ranks, the windowed
sort, the sum-and-compact tail, the whole windowed and the whole global
``sparse_coalesce.coalesce`` and the whole ``apply_sparse`` program's body
at the cell's shape (``cwt_sparse_out_apply``). ``--describe`` compiles the
same programs for a described v5e on a box without one (no times: compile
seconds and ``memory_analysis`` only).
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import numpy as np


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=524288)
    ap.add_argument("--mean", type=float, default=116.0)
    ap.add_argument("--n", type=int, default=3231961)
    ap.add_argument("--s", type=int, default=262144)
    ap.add_argument("--cap", type=int, default=1024)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--describe", action="store_true")
    ap.add_argument("--skip-global", action="store_true")
    args = ap.parse_args()

    from libskylark_tpu.engine.bucket import lane_class
    from libskylark_tpu.sketch import sparse_coalesce as sc
    from libskylark_tpu.sketch.sparse_serve import lane_terms

    rng = np.random.default_rng(5)
    sigma = 0.6
    lens = np.clip(np.rint(rng.lognormal(np.log(args.mean) - sigma ** 2 / 2,
                                         sigma, args.rows)), 1, args.cap
                   ).astype(np.int64)
    indptr = np.concatenate([[0], np.cumsum(lens)]).astype(np.int32)
    nnz = int(indptr[-1])
    lanes = lane_class(nnz)
    print(f"rows={args.rows} nnz={nnz} lanes={lanes} s={args.s} cap={args.cap}",
          flush=True)
    rows, s, cap, bits = args.rows, args.s, args.cap, (args.s - 1).bit_length()
    key = jax.random.key(3)

    def streams(indices, data):
        return lane_terms(jax.random.key_data(key), data, indices, s_dim=s)

    def ranks(ip):
        return sc._row_ranks(ip, lanes)

    def wsort(rank, minor, term):
        return sc._window_sorted(rank, minor, term, cap=cap, bits=bits)

    def tail(rank, minor, term, ip):
        lane = jnp.arange(lanes, dtype=jnp.int32)
        valid = lane < ip[-1]
        same = ((rank[1:] == rank[:-1]) & (minor[1:] == minor[:-1])
                & valid[1:])
        no = jnp.zeros((1,), bool)
        term = sc._segment_sums(term, jnp.concatenate([no, same]))
        keep = valid & ~jnp.concatenate([same, no])
        stored, before, (d, i) = sc._compact(keep, (term, minor))
        return d, i, jnp.concatenate([before, stored[None]])[ip]

    def window(minor, term, ip):
        return sc.coalesce(None, minor, term, ip[-1], n_major=rows,
                           n_minor=s, form="window", cap=cap, starts=ip)

    def glob(major, minor, term, ip):
        return sc.coalesce(major, minor, term, ip[-1], n_major=rows,
                           n_minor=s, form="global")

    def whole(indices, data, ip):
        return window(*streams(indices, data), ip)

    i32 = jax.ShapeDtypeStruct((lanes,), jnp.int32)
    f32 = jax.ShapeDtypeStruct((lanes,), jnp.float32)
    ipt = jax.ShapeDtypeStruct((rows + 1,), jnp.int32)
    programs = [("streams", streams, (i32, f32)), ("ranks", ranks, (ipt,)),
                ("window_sort", wsort, (i32, i32, f32)),
                ("tail", tail, (i32, i32, f32, ipt)),
                ("coalesce_window", window, (i32, f32, ipt)),
                ("whole_window", whole, (i32, f32, ipt))]
    if not args.skip_global:
        programs.append(("coalesce_global", glob, (i32, i32, f32, ipt)))

    if args.describe:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        one = SingleDeviceSharding(topo.devices[0])
        for name, fn, shapes in programs:
            t0 = time.perf_counter()
            compiled = jax.jit(fn).lower(*(
                jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one)
                for x in shapes)).compile()
            m = compiled.memory_analysis()
            print(f"{name}: compile_s={time.perf_counter() - t0:.1f} "
                  f"temp_GB={m.temp_size_in_bytes / 1e9:.3f} "
                  f"args_GB={m.argument_size_in_bytes / 1e9:.3f} "
                  f"out_GB={m.output_size_in_bytes / 1e9:.3f}", flush=True)
        return 0

    indices = jnp.asarray(np.pad(rng.integers(0, args.n, nnz, dtype=np.int32),
                                 (0, lanes - nnz)))
    data = jnp.asarray(np.pad(np.abs(rng.standard_normal(nnz, np.float32)),
                              (0, lanes - nnz)))
    ip = jnp.asarray(indptr)
    major = jnp.asarray(np.pad(np.repeat(np.arange(rows, dtype=np.int32), lens),
                               (0, lanes - nnz)))
    bucket, term = jax.jit(streams)(indices, data)
    rank = jax.jit(ranks)(ip)
    minor_s, term_s = jax.jit(wsort)(rank, bucket, term)
    inputs = {"streams": (indices, data), "ranks": (ip,),
              "window_sort": (rank, bucket, term),
              "tail": (rank, minor_s, term_s, ip),
              "coalesce_window": (bucket, term, ip),
              "whole_window": (indices, data, ip),
              "coalesce_global": (major, bucket, term, ip)}
    outs = {}
    for name, fn, _ in programs:
        jitted = jax.jit(fn)
        t0 = time.perf_counter()
        out = jax.block_until_ready(jitted(*inputs[name]))
        first = time.perf_counter() - t0
        times = []
        for _ in range(args.reps):
            t0 = time.perf_counter()
            out = jax.block_until_ready(jitted(*inputs[name]))
            times.append(time.perf_counter() - t0)
        outs[name] = out
        print(f"{name}: first_s={first:.2f} ms=" + ",".join(
            f"{1e3 * t:.2f}" for t in times), flush=True)
    w = outs["coalesce_window"]
    print(f"stored={int(w[2][-1])} merged={int(w[3])}", flush=True)
    if "coalesce_global" in outs:
        g = outs["coalesce_global"]
        same = all(bool(jnp.array_equal(a, b)) for a, b in zip(w[1:3], g[1:3]))
        worst = float(jnp.max(jnp.abs(w[0] - g[0])))
        print(f"window == global: structure {same}, data within {worst:.2e}",
              flush=True)
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use", 0)
    print(f"peak_GB={peak / 1e9:.3f}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
