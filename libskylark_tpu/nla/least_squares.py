"""High-level least squares: ApproximateLeastSquares and FastLeastSquares.

TPU-native analog of ref: nla/least_squares.hpp:41-241.
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from libskylark_tpu.algorithms import regression
from libskylark_tpu.base import errors
from libskylark_tpu.base.context import Context


def approximate_least_squares(
    A: jnp.ndarray,
    B: jnp.ndarray,
    context: Context,
    sketch_size: Optional[int] = None,
    sketch: str = "fjlt",
):
    """Sketch-and-solve least squares (Drineas et al.); default sketch size
    4×Width(A) with an FJLT (ref: nla/least_squares.hpp:41-83; the Hadamard
    mixer where Height(A) is a power of two, else the DCT). Sparse
    operands (``SparseMatrix``/``DistSparseMatrix``) default to a CWT
    sketch (the FJLT needs a dense fast transform)."""
    from libskylark_tpu import sketch as sk
    from libskylark_tpu.base.sparse import is_sparse_operand
    from libskylark_tpu.sketch.fjlt import solver_fut

    if is_sparse_operand(A):
        if sketch == "fjlt":
            sketch = "cwt"
    else:
        A = jnp.asarray(A)
    m, n = A.shape
    s = int(sketch_size) if sketch_size else 4 * n
    s = min(max(s, n + 1), m)
    if sketch == "fjlt":
        T = sk.FJLT(m, s, context, fut=solver_fut(m))
    elif sketch == "cwt":
        T = sk.CWT(m, s, context)
    elif sketch == "jlt":
        T = sk.JLT(m, s, context)
    else:
        raise errors.InvalidParametersError(
            f"unknown sketch {sketch!r}; expected 'fjlt', 'cwt', or 'jlt'"
        )
    return regression.solve_l2_sketched(A, B, T)


def fast_least_squares(
    A: jnp.ndarray,
    B: jnp.ndarray,
    context: Context,
    params: Optional[regression.AcceleratedParams] = None,
):
    """Accurate sketch-preconditioned solve — Blendenpik with condition
    fallback (ref: nla/least_squares.hpp:216-236). Returns (X, lsqr_iters).

    Dense operands dispatch as two engine-compiled executables (precond
    build + the LSQR while_loop) with a single host sync for the
    condition-fallback branch — see
    :func:`libskylark_tpu.algorithms.regression.solve_l2_accelerated`."""
    return regression.solve_l2_accelerated(
        A, B, context, method="blendenpik", params=params
    )
