"""skylark_svd: approximate SVD of a matrix read from file.

TPU-native analog of ref: nla/skylark_svd.cpp:225-345 — reads libsvm
(file or directory) or an arc-list graph, runs ApproximateSVD (or the
symmetric variant), writes prefix.U.txt / prefix.S.txt / prefix.V.txt.
"""

from __future__ import annotations

import argparse
import sys
import time


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="skylark_svd",
        description="Sketch-accelerated approximate SVD "
        "(ref: nla/skylark_svd.cpp)",
    )
    p.add_argument("inputfile", nargs="?", help="input file (libsvm format)")
    p.add_argument("--filetype", choices=["LIBSVM", "ARC_LIST"],
                   default="LIBSVM")
    p.add_argument("-d", "--directory", action="store_true",
                   help="inputfile is a directory of libsvm shards")
    p.add_argument("-s", "--seed", type=int, default=38734)
    p.add_argument("-k", "--rank", type=int, default=6)
    p.add_argument("-i", "--powerits", type=int, default=2)
    p.add_argument("--skipqr", action="store_true")
    p.add_argument("-r", "--ratio", type=int, default=2,
                   help="oversampling ratio")
    p.add_argument("-a", "--additive", type=int, default=0,
                   help="oversampling additive")
    p.add_argument("--symmetric", action="store_true")
    p.add_argument("--sparse", action="store_true",
                   help="load the matrix as sparse")
    p.add_argument("--single", action="store_true",
                   help="single precision (f32 is the TPU-native default; "
                   "flag kept for command-line parity)")
    from libskylark_tpu.cli import add_streaming_args

    add_streaming_args(p)
    p.add_argument("--profile", nargs=2, type=int, metavar=("H", "W"),
                   help="generate a random HxW matrix and run on it")
    p.add_argument("--prefix", default="out")
    return p


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    import jax.numpy as jnp
    import numpy as np

    import libskylark_tpu.io as skio
    from libskylark_tpu.base.context import Context
    from libskylark_tpu.cli import write_ascii_matrix
    from libskylark_tpu.nla.svd import (
        ApproximateSVDParams,
        approximate_svd,
        approximate_symmetric_svd,
    )

    if args.streaming and (args.directory or args.filetype == "ARC_LIST"
                           or args.sparse or args.profile):
        print("error: --streaming applies only to a single dense libsvm "
              "file", file=sys.stderr)
        return 2

    context = Context(seed=args.seed)
    t0 = time.time()
    if args.profile:
        h, w = args.profile
        rng = np.random.default_rng(args.seed)
        A = jnp.asarray(rng.standard_normal((h, w)).astype(np.float32))
    elif args.inputfile is None:
        print("error: inputfile required (or --profile)", file=sys.stderr)
        return 2
    elif args.filetype == "ARC_LIST":
        # sparse adjacency operand — never densified (ref: the sparse
        # branch of nla/skylark_svd.cpp:129-215)
        A = skio.read_arc_list(args.inputfile, symmetrize=True)
    elif args.directory:
        X, _ = skio.read_dir_libsvm(args.inputfile, sparse=args.sparse)
        A = X if args.sparse else jnp.asarray(X)
    elif args.streaming:
        from libskylark_tpu.cli import read_streaming

        A, _ = read_streaming(args.inputfile, args.batch_rows)
    else:
        X, _ = skio.read_libsvm(args.inputfile, sparse=args.sparse)
        A = X if args.sparse else jnp.asarray(X)
    print(f"Reading the matrix... took {time.time() - t0:.2e} sec")

    params = ApproximateSVDParams(
        num_iterations=args.powerits,
        oversampling_ratio=args.ratio,
        oversampling_additive=args.additive,
        skip_qr=args.skipqr,
    )
    t0 = time.time()
    if args.symmetric or args.filetype == "ARC_LIST":
        V, S = approximate_symmetric_svd(A, args.rank, context, params)
        U = V
    else:
        U, S, V = approximate_svd(A, args.rank, context, params)
    print(f"Computing approximate SVD... took {time.time() - t0:.2e} sec")

    write_ascii_matrix(args.prefix + ".U.txt", U)
    write_ascii_matrix(args.prefix + ".S.txt", S)
    write_ascii_matrix(args.prefix + ".V.txt", V)
    return 0


if __name__ == "__main__":
    sys.exit(main())
