"""skylark_linear: sketch-accelerated least-squares solve from file.

TPU-native analog of ref: nla/skylark_linear.cpp:97-201 — reads a libsvm
regression problem, solves min ‖Ax − b‖₂ with FastLeastSquares (Blendenpik)
or sketch-and-solve, writes the solution vector.
"""

from __future__ import annotations

import argparse
import sys
import time


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="skylark_linear",
        description="Sketched least squares (ref: nla/skylark_linear.cpp)",
    )
    p.add_argument("inputfile", help="input file (libsvm format)")
    p.add_argument("-d", "--directory", action="store_true")
    p.add_argument("-s", "--seed", type=int, default=38734)
    p.add_argument("-p", "--highprecision", action="store_true",
                   help="accurate sketch-preconditioned solve (Blendenpik); "
                   "default is sketch-and-solve")
    p.add_argument("-f", "--single", action="store_true",
                   help="kept for command-line parity (f32 is the default)")
    p.add_argument("--prefix", default="out",
                   help="solution written to prefix.x.txt")
    from libskylark_tpu.cli import add_streaming_args

    add_streaming_args(p)
    return p


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    import jax.numpy as jnp

    import libskylark_tpu.io as skio
    from libskylark_tpu.base.context import Context
    from libskylark_tpu.cli import write_ascii_matrix
    from libskylark_tpu.nla.least_squares import (
        approximate_least_squares,
        fast_least_squares,
    )

    t0 = time.time()
    if args.streaming:
        if args.directory:
            print("error: --streaming reads a single libsvm file",
                  file=sys.stderr)
            return 2
        from libskylark_tpu.cli import read_streaming

        X, Y = read_streaming(args.inputfile, args.batch_rows)
    else:
        reader = skio.read_dir_libsvm if args.directory else skio.read_libsvm
        X, Y = reader(args.inputfile)
    print(f"Reading the matrix... took {time.time() - t0:.2e} sec")

    context = Context(seed=args.seed)
    t0 = time.time()
    if args.highprecision:
        x = fast_least_squares(jnp.asarray(X), jnp.asarray(Y), context)
        if isinstance(x, tuple):
            x = x[0]
    else:
        x = approximate_least_squares(jnp.asarray(X), jnp.asarray(Y), context)
    print(f"Solving the least squares... took {time.time() - t0:.2e} sec")

    write_ascii_matrix(args.prefix + ".x.txt", x)
    return 0


if __name__ == "__main__":
    sys.exit(main())
