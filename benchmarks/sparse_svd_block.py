"""``approximate_svd`` of one libsvm-shaped sparse row block, end to end on
the chip: the solve that, before PR 61, sketched its range and stopped at
the first ``Aᵀ·Q`` (``base.sparse.spmm_t``'s nnz × k temporary).

    python3 benchmarks/sparse_svd_block.py [--rows 262144] [--rank 512]

Two parts, each printed as one ``[sparse_svd]`` line:

* the block of ``rows`` examples × 47236 features (the generator of the
  ``jlt_sparse_apply`` cells, imported), rank 512, one power iteration:
  seconds of the whole solve, warm (the second of two calls), and the
  Ritz residual ‖A·V − U·Σ‖_F ÷ ‖Σ‖ of what it returned;
* a block small enough that its dense SVD fits the host (``--small-rows``
  × ``--small-n``), the same call: the singular values against numpy's of
  the densified operand.

Not a benchmark cell (no end-to-end metric times a solve yet: PERF.md §7).
"""

from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.getcwd())     # run from the root of the checkout


def block(rows: int, n: int, seed: int, nnz_mean: int = 74):
    from cellbench import seeds
    from cellbench.drivers.sparse_hash_apply import _panel, _zipf_cdf

    config = {"rows_per_panel": rows, "n": n, "nnz_per_row_mean": nnz_mean,
              "row_length": {"sigma": 0.6, "min": 1, "max": min(1024, n)}}
    cdf = _zipf_cdf(n, 1.0)
    ids = seeds.rng(seed, "feature_ids").permutation(n).astype(np.int32)
    return _panel(config, seed, 0, cdf, ids)


def solve(X, rank: int, iterations: int, seed: int):
    import jax

    from libskylark_tpu.base.context import Context
    from libskylark_tpu.base.sparse import SparseMatrix
    from libskylark_tpu.nla.svd import ApproximateSVDParams, approximate_svd

    A = SparseMatrix.from_scipy(X)
    params = ApproximateSVDParams(num_iterations=iterations)
    times = []
    for _ in range(2):      # the first call places and compiles
        t0 = time.perf_counter()
        U, S, V = jax.block_until_ready(
            approximate_svd(A, rank, Context(seed), params))
        times.append(time.perf_counter() - t0)
    return (U, S, V), times


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--rows", type=int, default=262144)
    parser.add_argument("--n", type=int, default=47236)
    parser.add_argument("--rank", type=int, default=512)
    parser.add_argument("--iterations", type=int, default=1)
    parser.add_argument("--small-rows", type=int, default=8192)
    parser.add_argument("--small-n", type=int, default=2048)
    parser.add_argument("--small-rank", type=int, default=64)
    parser.add_argument("--seed", type=int, default=61)
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp

    from libskylark_tpu.base.sparse import SparseMatrix, spmm

    print(f"[sparse_svd] device={jax.devices()[0].device_kind!r}", flush=True)
    X = block(args.rows, args.n, args.seed)
    (U, S, V), times = solve(X, args.rank, args.iterations, args.seed)
    A = SparseMatrix.from_scipy(X)
    resid = float(jnp.linalg.norm(spmm(A, V) - U * S[None, :])
                  / jnp.linalg.norm(S))
    ortho = float(jnp.max(jnp.abs(U.T @ U - jnp.eye(U.shape[1]))))
    print(f"[sparse_svd] block rows={args.rows} n={args.n} nnz={X.nnz} "
          f"rank={args.rank} iterations={args.iterations} "
          f"first_s={times[0]:.3f} warm_s={times[1]:.3f} "
          f"sigma_max={float(S[0]):.5f} sigma_min={float(S[-1]):.5f} "
          f"resid={resid:.3e} orth_err={ortho:.3e}", flush=True)

    Xs = block(args.small_rows, args.small_n, args.seed, nnz_mean=24)
    (_, Ss, _), small_times = solve(Xs, args.small_rank, 2, args.seed)
    exact = np.linalg.svd(Xs.toarray().astype(np.float64),
                          compute_uv=False)[:args.small_rank]
    rel = np.abs(np.asarray(Ss, np.float64) - exact) / exact
    print(f"[sparse_svd] small rows={args.small_rows} n={args.small_n} "
          f"rank={args.small_rank} iterations=2 warm_s={small_times[1]:.3f} "
          f"sigma_rel_err_top8={rel[:8].max():.3e} "
          f"sigma_rel_err_all={rel.max():.3e}", flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
