"""libskylark_tpu — a TPU-native randomized numerical linear algebra framework.

A from-scratch JAX/XLA/Pallas re-design of the capabilities of libSkylark
(/root/reference): sketching transforms, sketch-accelerated NLA (randomized
SVD, sketched least squares, condition estimation) and ML on top of sketching
(kernel ridge regression, RLSC, block-ADMM kernel machines, graph spectral
embedding, local community detection).

Design stance (see SURVEY.md §7): sharding specs over a `jax.sharding.Mesh`
replace Elemental's distribution template parameters; XLA collectives over
ICI/DCN replace Boost.MPI; `jax.random`'s counter-based Threefry replaces
Random123 — preserving the reference's core determinism property that a
sketch's entries are a pure function of (seed, counter), independent of the
data layout (ref: base/randgen.hpp:98-115, base/context.hpp:19-194).
"""

import time as _time

# set-up accounting (telemetry/setup.py): the package's own import record
# starts here, and the telemetry package goes first so that its import
# watch is on sys.meta_path ahead of every other import below
_T0_NS = _time.perf_counter_ns()

__version__ = "0.1.0"

from libskylark_tpu import telemetry
from libskylark_tpu.base.precision import install_default_matmul_precision

# f32 matmuls must actually be f32 on TPU (default lowering is one bf16
# MXU pass — outside the 1e-4 oracle; see base/precision.py for the
# measurement). Env opt-out: SKYLARK_MATMUL_PRECISION=default.
install_default_matmul_precision()

from libskylark_tpu.base.context import Context
from libskylark_tpu.base import errors
from libskylark_tpu.base.sparse import SparseMatrix
from libskylark_tpu.base.dist_sparse import DistSparseMatrix, distribute_sparse

telemetry.setup.package_imported()

__all__ = [
    "Context", "errors", "telemetry", "__version__",
    "SparseMatrix", "DistSparseMatrix", "distribute_sparse",
]
