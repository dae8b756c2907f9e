"""Regression framework: exact, sketched, and sketch-accelerated solvers.

TPU-native analog of the reference's tag-dispatched regression framework
(ref: algorithms/regression/regression_problem.hpp:10-84,
linearl2_regression_solver_Elemental.hpp:23-163,
sketched_regression_solver.hpp:12-28,
accelerated_linearl2_regression_solver_Elemental.hpp:10-276).

The compile-time tag algebra (problem type × penalty × regularization ×
algorithm tag) becomes plain runtime parameters — Python already dispatches
dynamically, and XLA specializes per shape at trace time, which is where the
reference's template instantiation actually paid off.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import jax.scipy.linalg as jsl

from libskylark_tpu import engine
from libskylark_tpu.algorithms import krylov
from libskylark_tpu.algorithms.precond import MatPrecond, Precond, TriInversePrecond
from libskylark_tpu.base import errors
from libskylark_tpu.base.context import Context
from libskylark_tpu.base.params import Params
from libskylark_tpu.base.precision import with_solver_precision


@dataclasses.dataclass
class RegressionProblem:
    """min ‖A·x − b‖ with the reference's problem algebra
    (ref: regression_problem.hpp:10-58)."""

    A: jnp.ndarray
    kind: str = "linear"  # linear | polynomial | kernel
    penalty: str = "l2"  # l2 | l1 | lp
    regularization: Optional[str] = None


# -- exact L2 solvers (ref: linearl2_regression_solver_Elemental.hpp) --


@with_solver_precision
def solve_l2_exact(A: jnp.ndarray, B: jnp.ndarray, method: str = "qr") -> jnp.ndarray:
    """Exact least squares min ‖A·X − B‖ by the requested algorithm tag
    (ref: linearl2_regression_solver.hpp:11-37 — qr/sne/ne/svd)."""
    A = jnp.asarray(A)
    B = jnp.asarray(B)
    squeeze = B.ndim == 1
    if squeeze:
        B = B[:, None]
    if method == "qr":
        Q, R = jnp.linalg.qr(A)
        X = jsl.solve_triangular(R, Q.T @ B, lower=False)
    elif method == "sne":
        # Semi-normal equations: R from QR(A), solve RᵀR X = AᵀB.
        _, R = jnp.linalg.qr(A)
        Y = jsl.solve_triangular(R, A.T @ B, lower=False, trans="T")
        X = jsl.solve_triangular(R, Y, lower=False)
    elif method == "ne":
        G = A.T @ A
        L = jnp.linalg.cholesky(G)
        Y = jsl.solve_triangular(L, A.T @ B, lower=True)
        X = jsl.solve_triangular(L, Y, lower=True, trans="T")
    elif method == "svd":
        U, s, Vt = jnp.linalg.svd(A, full_matrices=False)
        s_inv = jnp.where(s > s[0] * jnp.finfo(A.dtype).eps * max(A.shape), 1.0 / s, 0.0)
        X = Vt.T @ (s_inv[:, None] * (U.T @ B))
    else:
        raise errors.InvalidParametersError(f"unknown exact l2 method {method!r}")
    return X[:, 0] if squeeze else X


# -- sketch-and-solve (ref: sketched_regression_solver.hpp:12-28) --


@with_solver_precision
def solve_l2_sketched(
    A: jnp.ndarray,
    B: jnp.ndarray,
    transform,
    method: str = "qr",
) -> jnp.ndarray:
    """Sketch-and-solve: compress rows of [A | B] with any columnwise sketch
    transform, then solve the small problem exactly
    (ref: sketched_regression_solver_Elemental.hpp — sketch to [STAR,STAR]
    and solve locally; here the small problem is replicated by construction).

    Dense operands run sketch + solve as one engine-compiled executable
    (keyed on the transform's serialization digest); sparse operands and
    calls inside a user jit take the direct path."""
    from libskylark_tpu.base.sparse import is_sparse_operand
    from libskylark_tpu.sketch import COLUMNWISE

    B = jnp.asarray(B)
    squeeze = B.ndim == 1  # sketch apply promotes vectors to (N, 1)

    def solve(A, B):
        SA = transform.apply(A, COLUMNWISE)
        SB = transform.apply(B, COLUMNWISE)
        return solve_l2_exact(SA, SB, method=method)

    if is_sparse_operand(A) or isinstance(A, jax.core.Tracer) \
            or isinstance(B, jax.core.Tracer):
        X = solve(A, B)
    else:
        cf = engine.compiled(
            solve, name="solve_l2_sketched", donate_argnums=(0, 1),
            donate="auto",
            key_fn=lambda *a: (engine.digest(transform), method))
        X = cf(jnp.asarray(A), B)
    return X[:, 0] if squeeze else X


def sketched_solve_serve(key_data, scale, A, B, *, sketch_type: str,
                         s_dim: int, method: str = "qr") -> jnp.ndarray:
    """Pure, vmap-batchable sketch-and-solve for the microbatch serving
    layer (:mod:`libskylark_tpu.engine.serve`): rebuilds the row sketch
    from the transform's raw key data and solves the compressed problem
    — the whole request is one traceable function of
    ``(key_data, scale, A, B)`` with the sketch family and method
    static. Zero-padding the row dimension of A/B is exact (padded rows
    contribute nothing through either sketch family); the feature and
    target dimensions are NOT paddable (a zero feature column makes the
    small problem singular), so the serving bucket keys them exactly."""
    from libskylark_tpu.base import randgen
    from libskylark_tpu.sketch import dense, hash as sketch_hash

    if sketch_type == "CWT":
        SA = sketch_hash.cwt_serve_apply(key_data, A, s_dim=s_dim,
                                         rowwise=False)
        SB = sketch_hash.cwt_serve_apply(key_data, B, s_dim=s_dim,
                                         rowwise=False)
    elif sketch_type == "JLT":
        SA = dense.serve_apply(key_data, scale, A,
                               dist=randgen.Normal(), s_dim=s_dim,
                               rowwise=False)
        SB = dense.serve_apply(key_data, scale, B,
                               dist=randgen.Normal(), s_dim=s_dim,
                               rowwise=False)
    else:
        raise errors.InvalidParametersError(
            f"serve path supports JLT/CWT sketches, got {sketch_type!r}")
    return solve_l2_exact(SA, SB, method=method)


# -- accelerated solvers (ref: accelerated_linearl2_regression_solver_*) --


@dataclasses.dataclass
class AcceleratedParams(Params):
    """Knobs of the Blendenpik/LSRN family."""

    sketch_size_factor: float = 4.0  # s = factor × n
    tolerance: float = 1e-10
    iter_lim: int = -1
    cond_threshold: float = 1e7  # fallback to exact SVD if precond this bad
    sketch: str = "fjlt"  # fjlt | jlt | cwt


def _accel_transform(m: int, n: int, context: Context,
                     params: AcceleratedParams, *, gaussian: bool = False):
    """The row-compressing sketch of the accelerated family; allocated
    eagerly (advances the Context counter) so the compiled solve phases
    can be keyed on its serialization digest."""
    from libskylark_tpu import sketch as sk
    from libskylark_tpu.sketch.fjlt import solver_fut

    s = int(params.sketch_size_factor * n)
    s = min(max(s, n + 1), m)
    if gaussian:
        return sk.JLT(m, s, context)
    if params.sketch == "fjlt":
        return sk.FJLT(m, s, context, fut=solver_fut(m))
    if params.sketch == "jlt":
        return sk.JLT(m, s, context)
    if params.sketch == "cwt":
        return sk.CWT(m, max(s, 4 * n), context)
    raise errors.InvalidParametersError(f"unknown sketch {params.sketch!r}")


def _blendenpik_r(A, T) -> jnp.ndarray:
    """R factor of the sketched operand — the right preconditioner
    (ref: accelerated_linearl2_regression_solver_Elemental.hpp:68-77)."""
    from libskylark_tpu import sketch as sk

    SA = T.apply(A, sk.COLUMNWISE)
    return jnp.linalg.qr(SA, mode="r")


def _lsrn_parts(A, T) -> tuple[jnp.ndarray, jnp.ndarray]:
    """LSRN preconditioner N = V·Σ⁻¹ from the SVD of the sketch
    (ref: accelerated_linearl2_regression_solver.hpp lsrn_tag)."""
    from libskylark_tpu import sketch as sk

    SA = T.apply(A, sk.COLUMNWISE)
    _, sv, Vt = jnp.linalg.svd(SA, full_matrices=False)
    Ninv = Vt.T * (1.0 / jnp.maximum(sv, sv[0] * jnp.finfo(SA.dtype).eps))[None, :]
    return Ninv, sv


@with_solver_precision
def build_blendenpik_precond(
    A: jnp.ndarray, context: Context, params: AcceleratedParams
) -> tuple[Precond, jnp.ndarray]:
    """Sketch A and QR the sketch; R is the right preconditioner
    (ref: accelerated_linearl2_regression_solver_Elemental.hpp:68-77)."""
    T = _accel_transform(*A.shape, context, params)
    R = _blendenpik_r(A, T)
    return TriInversePrecond(R), R


@with_solver_precision
def build_lsrn_precond(
    A: jnp.ndarray, context: Context, params: AcceleratedParams
) -> tuple[Precond, jnp.ndarray]:
    """LSRN: Gaussian sketch, SVD of the sketch, precond N = V·Σ⁻¹
    (ref: accelerated_linearl2_regression_solver.hpp lsrn_tag)."""
    T = _accel_transform(*A.shape, context, params, gaussian=True)
    Ninv, sv = _lsrn_parts(A, T)
    return MatPrecond(Ninv), sv


@with_solver_precision
def solve_l2_accelerated(
    A: jnp.ndarray,
    B: jnp.ndarray,
    context: Context,
    method: str = "blendenpik",
    params: Optional[AcceleratedParams] = None,
):
    """Sketch-preconditioned LSQR (Blendenpik / LSRN / simplified variant)
    with an ill-conditioning fallback to the exact SVD solver
    (ref: accelerated_linearl2_regression_solver_Elemental.hpp:208-276).

    Returns (X, iterations); iterations == 0 signals the exact fallback.

    ``A`` may be dense, a :class:`SparseMatrix`, or a
    :class:`DistSparseMatrix` — sparse operands default the sketch to CWT
    (the reference's sparse-input path; the FJLT needs a dense fast
    transform) and run LSQR through the sparse matvecs.

    Dense operands run as TWO engine-compiled executables — the
    precond-build phase (sketch → factor → condition estimate) and the
    LSQR ``lax.while_loop`` phase — with exactly one scalar host sync
    between them: the reference's CondEst fallback decision
    (ref: :241-253), which is a genuine host branch (the fallback
    traces a completely different program)."""
    from libskylark_tpu.base.sparse import is_sparse_operand

    params = params or AcceleratedParams()
    is_sparse = is_sparse_operand(A)
    if is_sparse:
        if params.sketch == "fjlt":
            params = dataclasses.replace(params, sketch="cwt")
    else:
        A = jnp.asarray(A)
    B = jnp.asarray(B)
    use_engine = (not is_sparse
                  and not isinstance(A, jax.core.Tracer)
                  and not isinstance(B, jax.core.Tracer))

    if method in ("blendenpik", "simplified_blendenpik"):
        p2 = (dataclasses.replace(params, sketch="cwt")
              if method == "simplified_blendenpik" else params)
        if use_engine:
            T = _accel_transform(*A.shape, context, p2)

            def build(A):
                R = _blendenpik_r(A, T)
                # Condition of the small R factor — the reference runs
                # CondEst and falls back to exact SVD (ref: :241-253).
                return R, jnp.linalg.cond(R)

            P, cond = engine.compiled(
                build, name="ls_accel_precond",
                key_fn=lambda *a: (engine.digest(T), method))(A)
            make_precond = TriInversePrecond
        else:
            precond, R = build_blendenpik_precond(A, context, p2)
            cond = jnp.linalg.cond(R)
    elif method == "lsrn":
        if use_engine:
            T = _accel_transform(*A.shape, context, params, gaussian=True)

            def build(A):
                Ninv, sv = _lsrn_parts(A, T)
                return Ninv, sv[0] / jnp.maximum(sv[-1],
                                                 jnp.finfo(sv.dtype).tiny)

            P, cond = engine.compiled(
                build, name="ls_accel_precond",
                key_fn=lambda *a: (engine.digest(T), method))(A)
            make_precond = MatPrecond
        else:
            precond, sv = build_lsrn_precond(A, context, params)
            cond = sv[0] / jnp.maximum(sv[-1], jnp.finfo(A.dtype).tiny)
    else:
        raise errors.InvalidParametersError(f"unknown accelerated method {method!r}")

    if not bool(jnp.isfinite(cond)) or float(cond) > params.cond_threshold:
        # exact fallback is a dense factorization (as in the reference)
        Ad = A.todense() if is_sparse else A
        return solve_l2_exact(Ad, B, method="svd"), jnp.int32(0)

    kp = krylov.KrylovParams(tolerance=params.tolerance, iter_lim=params.iter_lim)
    if use_engine:
        def run_lsqr(A, B, P):
            return krylov.lsqr(A, B, params=kp, precond=make_precond(P))

        return engine.compiled(
            run_lsqr, name="ls_accel_lsqr", donate_argnums=(1,),
            donate="auto",
            key_fn=lambda *a: (method, kp.tolerance, kp.iter_lim))(A, B, P)
    return krylov.lsqr(A, B, params=kp, precond=precond)
