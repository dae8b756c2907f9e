"""skylark_convert2hdf5: libsvm → HDF5 dataset conversion.

TPU-native analog of ref: ml/skylark_convert2hdf5.cpp:30-60 — mode 0
converts to the dense layout ("X"/"Y" datasets), mode 1 to the sparse
layout ("dimensions"/"indptr"/"indices"/"values"/"Y").
"""

from __future__ import annotations

import argparse
import sys


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="skylark_convert2hdf5",
        description="libsvm → HDF5 converter "
        "(ref: ml/skylark_convert2hdf5.cpp)",
    )
    p.add_argument("inputfile", help="libsvm input file")
    p.add_argument("hdf5file", help="HDF5 output file")
    p.add_argument("--mode", type=int, default=0, choices=[0, 1],
                   help="0: dense layout, 1: sparse layout")
    p.add_argument("--min-d", type=int, default=0)
    return p


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    import libskylark_tpu.io as skio

    X, Y = skio.read_libsvm(args.inputfile, sparse=args.mode == 1,
                            min_d=args.min_d)
    skio.write_hdf5(args.hdf5file, X, Y)
    print(f"input: {args.inputfile} hdf5file: {args.hdf5file} "
          f"mode: {args.mode} min_d: {args.min_d}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
