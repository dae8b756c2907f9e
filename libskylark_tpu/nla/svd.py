"""Randomized SVD: PowerIteration, ApproximateSVD, ApproximateSymmetricSVD.

TPU-native analog of ref: nla/svd.hpp:24-447 (Halko-Martinsson-Tropp):
sketch → power iteration with QR re-orthogonalization → small factorization →
rank truncation. The reference's four orientation combos and m≥n / m<n
branches collapse: everything is jnp, XLA handles layout, and the wide case
is the tall case on Aᵀ.

Dense operands run as ONE compiled program: sketch, power iteration
(``lax.fori_loop``) and the CholeskyQR2 Rayleigh-Ritz fuse into a single
executable served by :mod:`libskylark_tpu.engine` — compile once per
(shape, dtype, plan, params) class, then every subsequent solve is one
device dispatch. Two paths intentionally stay op-by-op: the phase-
profiling variant (``SKYLARK_TPU_PROFILE=1``), which must sync between
phases to attribute device time, and sparse/distributed-sparse operands,
whose containers are not jit inputs.

On a sharded A the sketch apply and the A·(Aᵀ·Q) products carry the
collectives while the (m × k') panel stays replicated — the TPU form of
the reference's [MC,MR] × [STAR,STAR] pattern.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from libskylark_tpu.base import errors
from libskylark_tpu.base.context import Context
from libskylark_tpu.base.params import Params
from libskylark_tpu.base.precision import with_solver_precision
from libskylark_tpu import engine, telemetry


@dataclasses.dataclass
class ApproximateSVDParams(Params):
    """ref: nla/svd.hpp:24-52 (defaults oversampling_ratio=2, additive=0,
    num_iterations=0, skip_qr=False; JSON-loadable).

    ``ortho`` selects the panel orthogonalization: "cqr2" (CholeskyQR2,
    nla/tsqr.py — the mesh-native default: local gemm + one psum +
    triangular solve, all MXU work; the diagonal lift plus second pass
    keep it accurate far past the textbook cond ≲ 1/√ε bound for the
    truncated spectra randomized SVD meets) or "qr" (Householder — the
    reference's El::qr algebra, replicated LAPACK/XLA work on a mesh).

    ``rr`` selects the Rayleigh-Ritz reduction: "cqr2" (default —
    tall-QR-reduce Bᵀ = (Aᵀ·Q) with CholeskyQR2, then SVD only the
    (k'×k') triangular factor; every O(n·k'²) flop is a shardable gemm)
    or "svd" (the reference's direct SVD of the k'×n panel,
    nla/svd.hpp:286-290 — on a mesh XLA replicates that LAPACK/QR-
    iteration work on every device, measured 5× slower at 8192²/k'=128,
    and on TPU the wide-matrix SVD lowering is iterative and slow)."""

    oversampling_ratio: float = 2.0
    oversampling_additive: int = 0
    num_iterations: int = 0
    skip_qr: bool = False
    ortho: str = "cqr2"
    rr: str = "cqr2"


def _orthonormalize(Q: jnp.ndarray, method: str) -> jnp.ndarray:
    if method == "cqr2":
        from libskylark_tpu.nla.tsqr import cholesky_qr2

        return cholesky_qr2(Q)[0]
    if method != "qr":
        raise errors.InvalidParametersError(
            f"ortho must be 'qr' or 'cqr2', got {method!r}"
        )
    return jnp.linalg.qr(Q)[0]


def _validate_params(params: ApproximateSVDParams) -> None:
    """Eager parameter validation — the fused pipelines must reject bad
    params before tracing, with the same errors the eager path raises."""
    if params.ortho not in ("qr", "cqr2"):
        raise errors.InvalidParametersError(
            f"ortho must be 'qr' or 'cqr2', got {params.ortho!r}")
    if params.rr not in ("cqr2", "svd"):
        raise errors.InvalidParametersError(
            f"rr must be 'cqr2' or 'svd', got {params.rr!r}")


def _as_linear_ops(A):
    """(mv, rmv, shape): X ↦ A·X and X ↦ Aᵀ·X over any operand kind —
    dense array, local :class:`SparseMatrix`, or mesh-distributed
    :class:`DistSparseMatrix` (the analog of the reference's
    matrix-type-templated NLA, e.g. the sparse branch of
    nla/skylark_svd.cpp:129-215, which never densifies)."""
    from libskylark_tpu.base.dist_sparse import DistSparseMatrix
    from libskylark_tpu.base.sparse import SparseMatrix, spmm, spmm_t

    if isinstance(A, SparseMatrix):
        return (lambda X: spmm(A, X)), (lambda X: spmm_t(A, X)), A.shape
    if isinstance(A, DistSparseMatrix):
        return A.spmm, A.spmm_t, A.shape
    A = jnp.asarray(A)
    # rmv as (Xᵀ·A)ᵀ, not Aᵀ·X. These call sites serve the UNFUSED paths
    # (the SKYLARK_TPU_PROFILE phase-profiling variant and the sparse
    # containers), which dispatch op-by-op: an eager Aᵀ materializes a
    # transposed copy of the WHOLE operand per call (268 MB at 8192²
    # f32, with a resharding shuffle when A is mesh-sharded) where the
    # result transpose is a k'-panel. The fused pipelines write the
    # natural Aᵀ·Q — under jit XLA folds either form into the same gemm.
    return (lambda X: A @ X), (lambda X: (X.T @ A).T), A.shape


def _transposed(A):
    from libskylark_tpu.base.dist_sparse import DistSparseMatrix
    from libskylark_tpu.base.sparse import SparseMatrix

    if isinstance(A, (SparseMatrix, DistSparseMatrix)):
        return A.T
    return jnp.asarray(A).T


@with_solver_precision
def power_iteration(
    A,
    Q: jnp.ndarray,
    num_iterations: int,
    orthogonalize: bool = True,
    adjoint: bool = False,
    ortho: str = "qr",
) -> jnp.ndarray:
    """(A·Aᵀ)^q · Q (or (Aᵀ·A)^q · Q when ``adjoint``) with
    re-orthogonalization between products unless disabled
    (ref: nla/svd.hpp:76-153 — the four orientation combos). ``A`` may be
    dense, sparse, or distributed sparse; ``ortho`` as in
    :class:`ApproximateSVDParams`."""
    mv, rmv, _ = _as_linear_ops(A)
    for _ in range(num_iterations):
        if adjoint:
            Q = rmv(mv(Q))
        else:
            Q = mv(rmv(Q))
        if orthogonalize:
            Q = _orthonormalize(Q, ortho)
    return Q


# ---------------------------------------------------------------------------
# fused dense pipelines (one executable per solve; libskylark_tpu/engine)
# ---------------------------------------------------------------------------


def _jlt_panel(key, n: int, kp: int, dtype) -> jnp.ndarray:
    """The (k' × n) JLT operator, bit-identical to ``JLT.s_panel(0, n)``
    for the same allocation key — the stream format, distribution, and
    scale convention all come from the ONE definition in sketch/dense.py,
    so the fused pipeline sketches with exactly the bits the unfused
    ``JLT.apply`` path would generate."""
    from libskylark_tpu.sketch.dense import JLT, virtual_panel

    return virtual_panel(key, JLT.dist, kp, 0, n, JLT.scale_for(kp), dtype)


def _svd_pipeline(A, key, *, k: int, kp: int, num_iterations: int,
                  skip_qr: bool, ortho: str, rr: str):
    """The whole tall-dense randomized SVD as one traceable program:
    sketch → fori_loop power iteration → Rayleigh-Ritz
    (ref: nla/svd.hpp:227-324 collapsed into a single trace)."""
    # the scopes carry the unfused variant's phase names into the HLO's
    # op_name metadata (trace time only; the instructions do not change)
    n = A.shape[1]
    with jax.named_scope("SKETCH"):
        S = _jlt_panel(key, n, kp, A.dtype)
        Q = A @ S.T                                 # range sketch (m, kp)

    def body(_, Q):
        Q = A @ (A.T @ Q)
        if not skip_qr:
            Q = _orthonormalize(Q, ortho)
        return Q

    with jax.named_scope("POWER_ITERATION"):
        if not skip_qr:
            Q = _orthonormalize(Q, ortho)
        Q = lax.fori_loop(0, num_iterations, body, Q)
        if skip_qr:
            # one final orthogonalization is always required before
            # projection
            Q = _orthonormalize(Q, ortho)

    with jax.named_scope("RR_PROJECT"):
        Bt = A.T @ Q                                # (n, kp); B = Btᵀ
    with jax.named_scope("RR_SMALL"):
        if rr == "svd":
            Ub, S_, Vt = jnp.linalg.svd(Bt.T, full_matrices=False)
            return Q @ Ub[:, :k], S_[:k], Vt[:k, :].T
        # rr == "cqr2": Bᵀ = Qb·Rb (all-gemm tall QR) ⇒ B = Rbᵀ·Qbᵀ; SVD
        # only the k'×k' factor: Rbᵀ = Ur·S·Vrᵀ ⇒ B = Ur·S·(Qb·Vr)ᵀ. The
        # expensive n-dimension work is gemms that shard along n.
        from libskylark_tpu.nla.tsqr import cholesky_qr2

        Qb, Rb = cholesky_qr2(Bt)
        Ur, S_, Vrt = jnp.linalg.svd(Rb.T, full_matrices=False)
        return Q @ Ur[:, :k], S_[:k], Qb @ Vrt.T[:, :k]


def _symmetric_svd_pipeline(A, key, *, k: int, kp: int,
                            num_iterations: int, skip_qr: bool,
                            ortho: str):
    """Symmetric variant as one program: Gaussian sketch → fori_loop
    power iteration → Rayleigh-Ritz via eigh (ref: nla/svd.hpp:326-396)."""
    n = A.shape[0]
    with jax.named_scope("SKETCH"):
        S = _jlt_panel(key, n, kp, A.dtype)
        Q = A @ S.T                                 # (n, kp) range sketch

    def body(_, Q):
        Q = A @ Q
        if not skip_qr:
            Q = _orthonormalize(Q, ortho)
        return Q

    with jax.named_scope("POWER_ITERATION"):
        Q = _orthonormalize(Q, ortho)
        Q = lax.fori_loop(0, num_iterations, body, Q)
        if skip_qr:
            Q = _orthonormalize(Q, ortho)

    # Rayleigh-Ritz: eigendecomposition of QᵀAQ (ref: nla/svd.hpp:175-225)
    with jax.named_scope("RR_PROJECT"):
        G = Q.T @ (A @ Q)
    with jax.named_scope("RR_SMALL"):
        G = 0.5 * (G + G.T)
        w, Z = jnp.linalg.eigh(G)
        # take the k largest-magnitude eigenpairs, descending
        order = jnp.argsort(-jnp.abs(w))[:k]
        return Q @ Z[:, order], w[order]


# donate="auto": the operand is consumed only when the user opted in
# (SKYLARK_ENGINE_DONATE=1) — public solvers must not invalidate caller
# arrays by default (docs/performance.rst, donation caveats).
_STATIC_SVD = ("k", "kp", "num_iterations", "skip_qr", "ortho", "rr")
_svd_compiled = engine.compiled(
    _svd_pipeline, static_argnames=_STATIC_SVD, donate_argnums=(0,),
    donate="auto", name="approximate_svd")
_symmetric_svd_compiled = engine.compiled(
    _symmetric_svd_pipeline, static_argnames=_STATIC_SVD[:-1],
    donate_argnums=(0,), donate="auto", name="approximate_symmetric_svd")


def _profiling_enabled() -> bool:
    from libskylark_tpu.utility.timer import timers_enabled

    return timers_enabled()


def _is_dense(A) -> bool:
    return not hasattr(A, "coo") and not hasattr(A, "spmm")


def _oversampled(params: ApproximateSVDParams, k: int, limit: int) -> int:
    kp = min(int(params.oversampling_ratio * k)
             + int(params.oversampling_additive), limit)
    return max(kp, k)


@with_solver_precision
def approximate_svd(
    A: jnp.ndarray,
    rank: int,
    context: Context,
    params: Optional[ApproximateSVDParams] = None,
    dtype=None,
):
    """Rank-``rank`` approximate SVD: returns (U, S, V) with A ≈ U·diag(S)·Vᵀ
    (ref: nla/svd.hpp:227-324).

    Sketch size k' = ratio·k + additive; JLT range sketch; power iteration;
    small exact SVD; truncation. Wide matrices (m < n) are handled by
    factoring Aᵀ and swapping U/V (the reference's second branch).

    Dense operands run as a single compiled executable served by the
    engine cache (see module docstring); ``SKYLARK_TPU_PROFILE=1``
    selects the unfused per-phase variant instead. ``A`` may also be a
    local :class:`SparseMatrix` or a :class:`DistSparseMatrix` — the
    sparse kinds are never densified (the reference's sparse branch,
    nla/skylark_svd.cpp:129-215) and always run unfused."""
    params = params or ApproximateSVDParams()
    _validate_params(params)
    if _is_dense(A):
        A = jnp.asarray(A)
        if dtype is not None:
            A = A.astype(dtype)
    elif dtype is not None:
        raise errors.InvalidParametersError(
            "dtype override is only supported for dense operands; sparse "
            "operands compute at their device dtype"
        )
    m, n = A.shape
    k = int(rank)
    if k <= 0:
        raise errors.InvalidParametersError(f"rank must be positive, got {rank}")
    kp = _oversampled(params, k, min(m, n))

    if m < n:
        # the caller's dtype override must survive the recursion — A was
        # already cast above, and threading it keeps the (no-op) cast on
        # the transposed operand explicit
        V, S, U = approximate_svd(_transposed(A), rank, context, params,
                                  dtype=dtype)
        return U, S, V

    from libskylark_tpu import sketch as sk

    T = sk.JLT(n, kp, context)

    if _is_dense(A) and not _profiling_enabled():
        statics = dict(k=k, kp=kp, num_iterations=int(params.num_iterations),
                       skip_qr=bool(params.skip_qr), ortho=params.ortho,
                       rr=params.rr)
        if isinstance(A, jax.core.Tracer):
            # already inside an outer trace (a user jit): inline the same
            # pipeline — the outer jit owns compilation and caching
            return _svd_pipeline(A, T._alloc.key, **statics)
        with telemetry.span("nla.approximate_svd") as sp:
            if sp is not None:
                sp.attrs.update(shape=(m, n), k=k, kp=kp,
                                num_iterations=statics["num_iterations"])
            return _svd_compiled(A, T._alloc.key, **statics)
    return _approximate_svd_unfused(A, T, k, params)


def _approximate_svd_unfused(A, T, k: int, params: ApproximateSVDParams):
    """The op-by-op variant: phase-profiled (SKYLARK_TPU_PROFILE=1) and
    the only path sparse operands take. Each phase syncs its outputs so
    device time attributes to the right phase — which is exactly why it
    cannot be the serving path: the reference profiles its solvers per
    phase (ref: ml/BlockADMM.hpp:357-365) and the north-star
    extrapolation (BASELINE.md) needs sketch / power-iteration /
    Rayleigh-Ritz wall-clock splits."""
    from libskylark_tpu import sketch as sk
    from libskylark_tpu.utility.timer import get_timer, timers_enabled

    mv, rmv, _ = _as_linear_ops(A)
    timer = get_timer("svd")
    _sync = jax.block_until_ready if timers_enabled() else (lambda x: x)

    # Range sketch: Y = A·Sᵀ via a rowwise JLT on the n-dimension
    # (ref: nla/svd.hpp:259-261).
    with timer.phase("SKETCH"):
        Q = _sync(T.apply(A, sk.ROWWISE))  # (m, kp)
    with timer.phase("POWER_ITERATION"):
        if not params.skip_qr:
            Q = _orthonormalize(Q, params.ortho)
        Q = power_iteration(A, Q, params.num_iterations,
                            orthogonalize=not params.skip_qr,
                            ortho=params.ortho)
        if params.skip_qr:
            # One final orthogonalization is always required before
            # projection.
            Q = _orthonormalize(Q, params.ortho)
        Q = _sync(Q)

    # Rayleigh-Ritz on the range: B = Qᵀ·A = (Aᵀ·Q)ᵀ, small
    # factorization, rotate back (ref: nla/svd.hpp:283-290). Profiled as
    # two phases: RR_PROJECT is the O(m·n·k') gemm over A — the same
    # cost class as SKETCH, irreducible — while RR_SMALL is the
    # factorization/rotation work the r4 verdict flagged at 43% of wall
    # (an eager whole-operand transpose + replicated wide SVD; now
    # sharded gemms + a k'×k' SVD).
    with timer.phase("RR_PROJECT"):
        Bt = _sync(rmv(Q))  # (n, kp) — tall; B = Btᵀ
    with timer.phase("RR_SMALL"):
        if params.rr == "svd":
            Ub, S, Vt = jnp.linalg.svd(Bt.T, full_matrices=False)
            U, S, V = _sync((Q @ Ub[:, :k], S[:k], Vt[:k, :].T))
        else:
            # Bᵀ = Qb·Rb (all-gemm tall QR) ⇒ B = Rbᵀ·Qbᵀ; SVD only the
            # k'×k' factor: Rbᵀ = Ur·S·Vrᵀ ⇒ B = Ur·S·(Qb·Vr)ᵀ. The
            # expensive n-dimension work is gemms that shard along n.
            from libskylark_tpu.nla.tsqr import cholesky_qr2

            Qb, Rb = cholesky_qr2(Bt)
            Ur, S, Vrt = jnp.linalg.svd(Rb.T, full_matrices=False)
            U, S, V = _sync((Q @ Ur[:, :k], S[:k], Qb @ Vrt.T[:, :k]))
    return U, S, V


@with_solver_precision
def approximate_symmetric_svd(
    A: jnp.ndarray,
    rank: int,
    context: Context,
    params: Optional[ApproximateSVDParams] = None,
):
    """Approximate eigendecomposition of symmetric A: returns (V, S) with
    A ≈ V·diag(S)·Vᵀ (ref: nla/svd.hpp:326-396 — Gaussian sketch +
    SymmetricPowerIteration + Rayleigh-Ritz via HermitianEig). ``A`` may
    be dense, sparse, or distributed sparse; dense operands run fused
    (one executable, engine-cached) with the power loop a
    ``lax.fori_loop``."""
    params = params or ApproximateSVDParams()
    _validate_params(params)
    if _is_dense(A):
        A = jnp.asarray(A)
    n, n2 = A.shape
    if n != n2:
        raise errors.InvalidParametersError("symmetric SVD expects a square matrix")
    if int(rank) <= 0:
        raise errors.InvalidParametersError(f"rank must be positive, got {rank}")
    k = int(rank)
    kp = _oversampled(params, k, n)

    from libskylark_tpu import sketch as sk

    T = sk.JLT(n, kp, context)

    if _is_dense(A) and not _profiling_enabled():
        statics = dict(k=k, kp=kp, num_iterations=int(params.num_iterations),
                       skip_qr=bool(params.skip_qr), ortho=params.ortho)
        if isinstance(A, jax.core.Tracer):
            return _symmetric_svd_pipeline(A, T._alloc.key, **statics)
        return _symmetric_svd_compiled(A, T._alloc.key, **statics)

    mv, _rmv, _ = _as_linear_ops(A)
    Q = T.apply(A, sk.ROWWISE)  # (n, kp) Gaussian range sketch
    Q = _orthonormalize(Q, params.ortho)
    for _ in range(params.num_iterations):
        Q = mv(Q)
        if not params.skip_qr:
            Q = _orthonormalize(Q, params.ortho)
    if params.skip_qr:
        Q = _orthonormalize(Q, params.ortho)

    # Rayleigh-Ritz: eigendecomposition of QᵀAQ (ref: nla/svd.hpp:175-225).
    G = Q.T @ mv(Q)
    G = 0.5 * (G + G.T)
    w, Z = jnp.linalg.eigh(G)
    # take the k largest-magnitude eigenpairs, descending
    order = jnp.argsort(-jnp.abs(w))[:k]
    return Q @ Z[:, order], w[order]
