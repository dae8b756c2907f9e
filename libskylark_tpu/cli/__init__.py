"""Command-line drivers mirroring the reference executables.

TPU-native analogs of the compiled CLIs (ref: nla/skylark_svd.cpp,
nla/skylark_linear.cpp, ml/skylark_ml.cpp, ml/skylark_graph_se.cpp,
ml/skylark_community.cpp, ml/skylark_convert2hdf5.cpp). Run as
``python -m libskylark_tpu.cli.skylark_svd [...]`` etc.; each module
exposes ``main(argv) -> int`` for programmatic use and testing.

Flag names and defaults track the reference's boost::program_options
tables so command lines port over mechanically.
"""

from __future__ import annotations

import numpy as np

# fileformat enum (ref: ml/options.hpp:46-52)
LIBSVM_DENSE, LIBSVM_SPARSE, HDF5_DENSE, HDF5_SPARSE = 0, 1, 2, 3


def read_dataset(path: str, fileformat: int, min_d: int = 0):
    """ml/io.hpp:871-890 ``read()`` dispatch equivalent."""
    import libskylark_tpu.io as skio

    if fileformat == LIBSVM_DENSE:
        return skio.read_libsvm(path, min_d=min_d)
    if fileformat == LIBSVM_SPARSE:
        return skio.read_libsvm(path, min_d=min_d, sparse=True)
    if fileformat == HDF5_DENSE:
        return skio.read_hdf5(path, min_d=min_d)
    if fileformat == HDF5_SPARSE:
        return skio.read_hdf5(path, min_d=min_d, sparse=True)
    raise SystemExit(f"unknown fileformat {fileformat}")


def write_ascii_matrix(path: str, M, digits: int = 8) -> None:
    """El::Write(..., El::ASCII) equivalent (ref: nla/skylark_svd.cpp:110)."""
    np.savetxt(path, np.asarray(M), fmt=f"%.{digits}g")


def add_streaming_args(p) -> None:
    """Shared --streaming/--batch-rows flags (bounded-memory sharded
    ingestion; the HDFS-reader analog) for the libsvm-reading CLIs."""
    p.add_argument("--streaming", action="store_true",
                   help="stream the (dense libsvm) file into sharded "
                   "device memory in bounded host memory")
    p.add_argument("--batch-rows", type=int, default=65536,
                   help="rows per streamed batch with --streaming")


def read_streaming(path: str, batch_rows: int):
    """Stream ``path`` into a row-sharded device array over the default
    1D mesh (see io.read_libsvm_sharded)."""
    import libskylark_tpu.io as skio
    from libskylark_tpu.parallel import make_mesh

    return skio.read_libsvm_sharded(path, make_mesh(),
                                    batch_rows=batch_rows)
