"""Shape-bucketed microbatch serving: coalesce concurrent requests into
one vmapped executable per flush.

The r7 engine made a *single* request run as one compiled program; this
layer makes *N concurrent small requests* run as ``N / batch`` compiled
programs. Requests enter through a future-returning :meth:`submit` on
the hot endpoints — dense/CWT sketch-apply, Fastfood/RFT feature maps,
sketched least squares, KRR predict — and are grouped by **bucket**:
(endpoint statics, dtype, pow2 shape class, sharding) as defined in
:mod:`libskylark_tpu.engine.bucket`.

Sparse operands are first-class (docs/serving, "Sparse operands on
the serve path"): :meth:`~MicrobatchExecutor.submit_sparse` /
:meth:`~MicrobatchExecutor.submit_sparse_solve` pack a
:class:`~libskylark_tpu.base.sparse.SparseMatrix` (or scipy sparse)
operand as padded (data, indices, indptr) CSR lanes whose bucket keys
carry a pow2 **nnz class** next to the dims/dtype — ragged-nnz
cohorts coalesce into one flush executable, bit-equal to the dense
reference (``todense()`` → ``transform.apply``), with operands past
``SKYLARK_SPARSE_MIN_DENSITY`` auto-densified onto the dense path
(counted). A sparse flush is ``jax.vmap`` of the lane program (the XLA
scatter) at every shape; a pallas intent on a sparse bucket declines to
it, counted.

Flush kernels: the sketch-apply and fastfood buckets can flush through
the endpoint's **batched Pallas kernel** (one ``pallas_call`` over the
stacked cohort — ``sketch/pallas_hash.py`` scatter-free CountSketch,
``sketch/pallas_dense.py`` fused generate+matmul, ``sketch/
pallas_fastfood.py`` fused SHGΠHB chain) instead of the vmapped XLA
path. Which program serves a (bucket, capacity) flush is resolved by
:meth:`MicrobatchExecutor._resolve_flush_kernel` with the precedence
``kernel=`` argument > ``SKYLARK_SERVE_KERNEL`` env > default (xla);
the resolved choice is a **static of the executable cache key**, so
selection can never retrace a warm bucket (docs/performance,
"Serve-bucket kernel selection").
A cohort flushes as ONE ``jax.vmap``-batched executable when it reaches
``max_batch`` or its oldest request has lingered ``linger_us``; past
``max_queue`` pending requests, ``submit`` blocks (backpressure) and
eventually raises :class:`ServeOverloadedError`.

Batched executables route through the same process-global executable
cache as the r7 solver pipelines (:mod:`libskylark_tpu.engine.compiled`)
— the bucket statics ride the ``key_fn`` extras and the padded batch
shape rides the avals, so steady-state traffic is zero-recompile after
one warmup per (bucket, capacity class). The stacked per-flush operand
buffers are **donated**: the executor owns them (freshly allocated each
flush, never re-read), so XLA may reuse their memory for the batch
output regardless of the global ``SKYLARK_ENGINE_DONATE`` opt-in, which
continues to govern only user-owned operands.

Exactness: padding is bit-exact, not approximate. The sketch operators
are positional virtual streams, so zero-padded coordinates contribute
exact zeros (``sketch.dense.serve_apply`` / ``sketch.hash
.cwt_serve_apply``); batch lanes are invariant to the capacity class
(a cohort of 3 padded to capacity 4 returns the same bits per lane as
capacity 8). Filler lanes replicate the last real request rather than
feeding zeros into factorizations.

Counters (``MicrobatchExecutor.stats()`` / ``engine.serve_stats()``):
submitted / completed / failed / rejected, queued gauge, coalesced
(requests that shared a flush), flushes, batch-capacity and cohort-size
histograms, padding-waste ratio, and p50/p99/mean request latency.

Stateful sessions (:mod:`libskylark_tpu.sessions`, docs/sessions): the
executor also hosts *bucket-lived* sketch sessions —
:meth:`~MicrobatchExecutor.open_sketch_session` /
:meth:`~MicrobatchExecutor.session_append` /
:meth:`~MicrobatchExecutor.session_finalize` — a registry keyed
alongside the bucket statics (session id → maintained sketch state +
append journal sequence number). Every accepted append is journaled
under ``SKYLARK_SESSION_DIR`` *before* its future resolves; a drain
checkpoints live session state (the r9 drain hook discipline), so a
peer executor resumes a drained — or ``kill -9``'d — replica's
sessions from checkpoint + journal tail, bit-equal, with idempotent
sequence numbers making duplicate replay a no-op. Under DEGRADED
health, session appends (the best-effort streaming class) shed
*before* interactive one-shot traffic; expired deadlines and TTL
evictions resolve append futures to :class:`ServeOverloadedError` /
:class:`~libskylark_tpu.base.errors.SessionEvictedError` instead of
hanging.

Multi-tenant QoS (:mod:`libskylark_tpu.qos`, docs/qos): every request
carries a **priority class** (interactive / standard / best_effort)
resolved from its ``tenant=`` argument by a
:class:`~libskylark_tpu.qos.TenantRegistry` — with token-bucket rate
limits refusing over-quota tenants at admission
(:class:`~libskylark_tpu.base.errors.TenantQuotaError`). The class
rides the bucket *key* (classes queue separately, share executables)
and the flusher drains the per-class queues with **weighted-fair
deficit round robin** (8:4:1); shedding — DEGRADED and queue-pressure
— is class-ordered: best_effort before standard before interactive,
session appends below interactive. An optional per-executor
**adaptive batching controller** (``adaptive=True``,
:mod:`libskylark_tpu.qos.controller`) retunes per-bucket
linger/batch targets against the class p99 SLOs, moving batch
targets only along already-warm capacity rungs — zero recompiles by
construction. Heterogeneous library endpoints ride the same
machinery: :meth:`~MicrobatchExecutor.submit_graph_ase` /
:meth:`~MicrobatchExecutor.submit_graph_ppr` (adjacency over the
sparse CSR lanes), :meth:`~MicrobatchExecutor.submit_condest`,
:meth:`~MicrobatchExecutor.submit_lowrank`,
:meth:`~MicrobatchExecutor.submit_rlsc_predict` — each a distinct
bucket family, each bit-equal to its capacity-1 dispatch and to its
eager twin.

Content-addressed caching (:mod:`libskylark_tpu.engine.resultcache`,
docs/caching; opt-in via ``cache=True`` / ``SKYLARK_CACHE``): every
endpoint is a pure function of (operand bytes, key data, statics), so
requests carry a blake2b **digest** — computed once, at the fleet
front door when one exists (``_digest=``) — behind three fast paths
at intake: results pinned by :meth:`~MicrobatchExecutor
.register_operand` (the sketch-once residency API), a byte-bounded
QoS-partitioned digest→result cache, and **single-flight** coalescing
of concurrent identical requests onto one flush (one leader, N
futures, bit-equal fan-out; a poisoned flush fails all coalesced
waiters with the leader's exception). All three are bypassed while
DEGRADED — a shedding executor never blocks intake on cache locks.

Resilience (r9, :mod:`libskylark_tpu.resilience`): a failed flush no
longer fans its exception to the whole cohort — the executor retries
**bisection-style**, splitting the cohort in half and re-executing each
half, so a single poison request converges to its own capacity-1 flush
in ≤ log2(max_batch) retries and receives the exception *alone* while
every cohort-mate re-coalesces and succeeds (lane invariance makes the
re-coalesced results bit-equal). The executor carries health states —
``SERVING`` → ``DEGRADED`` (recent-flush failure ratio past
``degraded_threshold``; submits load-shed at a reduced queue bound) →
``DRAINING`` (:meth:`drain`: intake refused, queue flushed, in-flight
futures resolved — what the preemption handler calls on SIGTERM) →
``STOPPED``. Requests accept a ``deadline``; one that expires while
queued resolves to :class:`ServeOverloadedError` and never consumes an
isolation retry. The flush worker hosts the ``serve.flush`` fault-
injection site (:mod:`libskylark_tpu.resilience.faults`), so all of the
above is deterministically chaos-testable (``benchmarks/
chaos_battery.py``, the CI chaos gate).
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import itertools
import math
import sys
import threading
import time
import warnings
import weakref
from concurrent.futures import Future
from typing import Optional

import numpy as np

from libskylark_tpu import telemetry as _telemetry
from libskylark_tpu.base import env as _env
from libskylark_tpu.base import errors as _errors
from libskylark_tpu.base import locks as _locks
from libskylark_tpu.qos import scheduler as _qsched
from libskylark_tpu.qos import tenants as _qtenants
from libskylark_tpu.telemetry import metrics as _metrics
from libskylark_tpu.engine import bucket as bucketing
from libskylark_tpu.engine import resultcache as _rcache
from libskylark_tpu.engine.compiled import compiled as engine_compile
from libskylark_tpu.engine.compiled import digest as engine_digest
from libskylark_tpu.resilience import faults
from libskylark_tpu.resilience import health as _health
from libskylark_tpu.resilience.policy import Deadline
from libskylark_tpu.telemetry import trace as _trace

ENDPOINTS = ("sketch_apply", "fastfood_features", "solve_l2_sketched",
             "krr_predict", "sparse_sketch_apply",
             "sparse_solve_l2_sketched", "graph_ase", "graph_ppr",
             "condest", "lowrank", "rlsc_predict",
             "compressed_matmul")

# endpoints behind the selection seam (arg > env > default), whose
# flush runs at the ambient matmul precision and is counted by backend;
# the others always flush through the vmapped XLA path under
# solver_precision(). The sparse sketch endpoint is here for the
# precision and the counters: it has no batched kernel, so a pallas
# intent on it resolves to a counted decline
_KERNEL_ENDPOINTS = ("sketch_apply", "fastfood_features",
                     "sparse_sketch_apply")

# sparse-operand intake telemetry (docs/serving, "Sparse operands on
# the serve path") — registry metrics created HERE once (the
# metric-names rule's one-creation-site contract); the per-executor
# disaggregation lives in ``stats()["sparse"]`` and rides the serve
# collector.
_SPARSE_SUBMITS = _metrics.counter(
    "serve.sparse_submits",
    "Sparse (CSR) serve submissions accepted by submit_sparse / "
    "submit_sparse_solve, before the densify decision")
_SPARSE_DENSIFIED = _metrics.counter(
    "serve.sparse_densified",
    "Sparse submissions auto-densified onto the dense serve path "
    "(operand density >= SKYLARK_SPARSE_MIN_DENSITY)")
_SPARSE_KERNEL_FLUSHES = _metrics.counter(
    "serve.sparse_kernel_flushes",
    "Sparse-bucket flushes by resolved flush backend (xla = the "
    "O(nnz) scatter, the one program a sparse flush has)")
_SPARSE_NNZ_HIST = _metrics.histogram(
    "serve.sparse_nnz_class",
    "pow2 nnz class of accepted sparse submissions — the sparse "
    "bucket-population signal (one bucket per (shape class, nnz "
    "class, dtype))",
    buckets=tuple(float(1 << p) for p in range(6, 21)))

# panel-free FWHT tier telemetry (docs/performance, "In-kernel FWHT
# and compressed matmul") — created HERE once; the per-executor
# disaggregation lives in ``stats()["fwht"]`` and rides the serve
# collector.
_FWHT_FLUSHES = _metrics.counter(
    "serve.fwht_flushes",
    "SRHT-family sketch_apply flushes by resolved flush backend "
    "(xla = the panel-free mix-and-sample program, "
    "fjlt.srht_serve_apply, the one program an SRHT flush has)")
_CM_SUBMITS = _metrics.counter(
    "serve.compressed_matmul_submits",
    "Compressed approximate-matmul submissions reaching the flush "
    "path (cache hits bypass prep and are not counted here)")

_KERNEL_BACKENDS = _env.SERVE_KERNEL_BACKENDS

# multi-tenant QoS instruments (docs/qos) — created HERE once (the
# metric-names one-creation-site contract); the always-on per-class
# accounting lives in ``stats()["qos"]`` and rides the ``qos``
# collector registered at the bottom of this module. The controller
# gauges (qos.linger_target / qos.batch_target) are created in
# ``qos/controller.py``.
_QOS_ADMITTED = _metrics.counter(
    "qos.admitted",
    "Requests admitted past QoS admission, by priority class and "
    "tenant")
_QOS_SHED = _metrics.counter(
    "qos.shed",
    "Requests shed by the class-ordered shed policy (DEGRADED or "
    "queue pressure), by priority class and tenant")
_QOS_RATE_LIMITED = _metrics.counter(
    "qos.rate_limited",
    "Requests refused at admission by a tenant token bucket "
    "(TenantQuotaError), by priority class and tenant")
_QOS_QUEUE_DEPTH = _metrics.gauge(
    "qos.queue_depth",
    "Queued (not yet dispatched) requests, by priority class and "
    "replica (per-executor series — N executors must not clobber one "
    "label key)")
_QOS_LATENCY = _metrics.histogram(
    "qos.request_latency",
    "Request latency (submit to resolve, seconds), by priority class")

# auto-assigned replica identity labels ("ex-0", "ex-1", ...) for
# executors constructed without an explicit ``name`` — every executor
# has an identity so per-replica telemetry disaggregation never falls
# back to "some anonymous executor"
_EX_SEQ = itertools.count()

# Executor health states (see the module docstring / docs/resilience).
SERVING = "SERVING"
DEGRADED = "DEGRADED"
DRAINING = "DRAINING"
STOPPED = "STOPPED"


class ServeOverloadedError(RuntimeError):
    """Backpressure bound hit (the queue stayed at ``max_queue`` past
    the submit timeout), load shed in a DEGRADED/DRAINING executor, or
    a request deadline that expired while queued."""


@dataclasses.dataclass
class _Request:
    endpoint: str
    arrays: dict            # per-request operands (host np, stack-padded)
    true_shapes: dict       # name -> original shape (for unpad/waste)
    meta: dict              # endpoint bits: squeeze flags, true extents
    future: Future = dataclasses.field(default_factory=Future)
    t_submit: float = dataclasses.field(default_factory=time.monotonic)
    deadline: Optional[Deadline] = None   # expires-while-queued bound
    tags: frozenset = frozenset()         # fault-injection tags (chaos)
    request_id: Optional[str] = None      # telemetry request identity
    tctx: Optional[object] = None         # telemetry SpanContext handoff
    qos_class: str = "standard"           # resolved priority class
    tenant: str = ""                      # resolved tenant name


@dataclasses.dataclass
class _Bucket:
    key: tuple              # full bucket identity (statics + model ids)
    statics: tuple          # engine key_fn extras (no object ids)
    ctx: dict               # closure objects: dist/kernel/model arrays
    reqs: list = dataclasses.field(default_factory=list)
    qos_class: str = "standard"   # the per-class queue this bucket
    #                               belongs to (class is part of the
    #                               bucket KEY, never of the statics:
    #                               classes share executables)

    @property
    def oldest(self) -> float:
        return self.reqs[0].t_submit if self.reqs else float("inf")


def dispatch_loop(workq) -> None:
    """Flush-worker loop over a dispatch queue of ``(executor,
    (bucket, cohort))`` items (``None`` poisons one worker). Run by
    each executor's own worker threads, and by a
    :class:`~libskylark_tpu.fleet.ReplicaPool`'s shared worker pool
    when replicas are constructed with ``dispatch_queue=`` — cohorts
    from many executors then drain through one host-sized pool."""
    while True:
        item = workq.get()
        if item is None:
            return
        ex, work = item
        ex._dispatch_cohort(*work)


#: sentinel "bucket" for a training slice in the dispatch plumbing
#: (docs/training): the flusher offers it to the deficit scheduler as
#: best-effort backlog only when no higher class has pending work, and
#: ``_dispatch_cohort`` routes it to the train manager instead of the
#: cohort runner. One sentinel per flusher pass — at most one slice
#: dispatches per scheduler decision, so training yields the moment
#: real traffic arrives (preemption at slice boundaries, structurally).
_TRAIN_KEY = object()


def _percentile(sorted_vals: list, q: float) -> Optional[float]:
    if not sorted_vals:
        return None
    i = min(int(q * (len(sorted_vals) - 1) + 0.5), len(sorted_vals) - 1)
    return sorted_vals[i]


# ---------------------------------------------------------------------------
# bucket statics derivation — shared by the executor's per-endpoint prep
# and the fleet router's affinity key (libskylark_tpu/fleet/router.py):
# both MUST hash the same tuple or sticky routing would send a request
# to a replica whose executable cache is warm for a DIFFERENT class.
# ---------------------------------------------------------------------------


def _serve_kernel_env():
    """``SKYLARK_SERVE_KERNEL`` — the one-shot override between the
    executor argument and the XLA default in the flush-kernel
    precedence (``pallas`` | ``xla``; anything else is ignored so a
    typo degrades to the default, the repo's env-parse convention —
    the registry parser encodes exactly that)."""
    return _env.SERVE_KERNEL.get()


def _pallas_native() -> bool:
    """Whether this backend compiles Mosaic kernels natively; off-TPU a
    pallas flush runs the interpreter (a correctness surface the tests
    and the CI bit-equality leg use, reached only by an explicit
    pin)."""
    from libskylark_tpu.sketch.pallas_dense import available

    return available()


def _lane_program_only(statics) -> bool:
    """Sparse and SRHT sketch buckets have no batched kernel: their
    flush is ``jax.vmap`` of the lane program (``sparse_serve`` /
    ``fjlt.srht_serve_apply``), so a pallas intent on one declines."""
    return (statics[0] == "sparse_sketch_apply"
            or statics[:2] == ("sketch_apply", "SRHT"))


def _decline_slug(msg: str) -> str:
    """Compact label-value form of a kernel decline reason (the
    ``by_reason`` Prometheus label set must not carry free prose)."""
    import re

    return re.sub(r"[^a-z0-9]+", "-", str(msg).lower()).strip("-")[:48]


def _sketch_family(transform):
    """(family tag, dist instance) for a serve-able transform."""
    from libskylark_tpu.sketch.dense import DenseTransform
    from libskylark_tpu.sketch.fjlt import FJLT
    from libskylark_tpu.sketch.hash import CWT

    if isinstance(transform, CWT):
        return "CWT", None
    if isinstance(transform, FJLT):
        # the serve family is the SRHT: the panel-free mix-and-sample
        # program is closed-form only for the Sylvester-Hadamard mixer
        # — the same restriction operator_panel/fold_rows carry
        if transform._fut_name != "wht":
            raise _errors.UnsupportedError(
                "FJLT serves panel-free only with the 'wht' "
                f"(Sylvester-Hadamard) mixer, not "
                f"{transform._fut_name!r}")
        return "SRHT", None
    if isinstance(transform, DenseTransform):
        return transform.sketch_type, transform.dist
    raise TypeError(
        "serve endpoints batch dense (JLT/CT), CWT and FJLT/SRHT "
        "transforms (Fastfood/RFT feature maps go through "
        f"submit_fastfood); got {type(transform).__name__}")


def _sketch_statics(transform, A, dimension, pad_floor):
    """(statics, info) for a sketch_apply request. ``info`` carries the
    derivation intermediates the executor's prep reuses (reshaped
    operand, family, dist, rowwise flag, padded class shape)."""
    from libskylark_tpu.sketch import COLUMNWISE, Dimension

    dimension = dimension or COLUMNWISE
    rowwise = Dimension(dimension) == Dimension.ROWWISE
    A = np.asarray(A)
    if A.ndim == 1:
        A = A[None, :] if rowwise else A[:, None]
    n = A.shape[1] if rowwise else A.shape[0]
    if n != transform.input_dim:
        raise ValueError(
            f"operand dim {n} != transform input dim "
            f"{transform.input_dim}")
    family, dist = _sketch_family(transform)
    if family == "SRHT":
        # the FWHT length IS the operator: padding the transform axis
        # would change what the sketch computes, so only the free axis
        # buckets (the panel path padded both — the operator panel was
        # stream-exact at any extent; the panel-free program is not)
        if n & (n - 1):
            raise ValueError(
                f"SRHT serve requires a power-of-2 transform dim, "
                f"got {n}")
        pad_axes = (0,) if rowwise else (1,)
    else:
        pad_axes = (0, 1)  # both extents paddable: N is stream-exact,
        #                    the other axis is sliced off the output
    padded = bucketing.pad_shape(A.shape, pad_axes, pad_floor)
    statics = ("sketch_apply", family, repr(dist),
               transform.sketch_dim, rowwise, str(A.dtype), padded)
    return statics, {"A": A, "family": family, "dist": dist,
                     "rowwise": rowwise, "padded": padded}


def _is_sparse_operand(A) -> bool:
    from libskylark_tpu.base.sparse import SparseMatrix

    if isinstance(A, SparseMatrix):
        return True
    try:
        import scipy.sparse as sp

        return sp.issparse(A)
    except ImportError:  # pragma: no cover - scipy is a hard dep here
        return False


def default_cmm_transform(A, *, s_dim: Optional[int] = None,
                          seed: int = 0):
    """The transform ``submit_compressed_matmul`` builds when the
    caller holds none: SRHT (FJLT/``wht``) when A's contraction dim is
    a power of two, CWT otherwise, at ``s_dim`` (default
    ``SKYLARK_FWHT_CM_SDIM``) buckets seeded from ``seed``. Shared by
    the executor and fleet-router conveniences so the two front doors
    build bit-identical operators — a fleet submit and a local submit
    of the same (A, B, s_dim, seed) coalesce in the result cache."""
    from libskylark_tpu.base.context import Allocation

    n = int(A.shape[1] if hasattr(A, "shape")
            else np.asarray(A).shape[1])
    s = int(s_dim or _env.FWHT_CM_SDIM.get())
    alloc = Allocation(int(seed), 0)
    if n & (n - 1):
        from libskylark_tpu.sketch.hash import CWT

        return CWT(n, s, alloc)
    from libskylark_tpu.sketch.fjlt import FJLT

    return FJLT(n, s, alloc, fut="wht")


def _cmm_statics(transform, A, B, pad_floor):
    """(statics, info) for a compressed_matmul request: estimate A·B
    (A: (m, n) dense or CSR, B: (n, p) dense) from one shared sketch —
    ``(A Sᵀ)(S B)`` with the SAME operator S both sides, family CWT or
    SRHT. The contraction extent n is an exact bucket component (both
    family programs are stream-exact only at the true extent, and the
    error bound is a function of the true contraction); m and p bucket
    to their pow2 classes. The expected-error scale
    ``‖A‖_F·‖B‖_F·√(2/s)`` is computed host-side here and rides the
    request meta — the future resolves to ``(estimate, bound)``."""
    family, _dist = _sketch_family(transform)
    if family not in ("CWT", "SRHT"):
        raise TypeError(
            f"compressed_matmul serves CWT/SRHT sketches, got "
            f"{family} (a dense virtual panel would cost more than "
            "the product it estimates)")
    B = np.asarray(B)
    if B.ndim != 2:
        raise ValueError(f"compressed_matmul expects a (n, p) B, got "
                         f"{B.shape}")
    sparse = _is_sparse_operand(A)
    if sparse:
        A = _coerce_sparse(A)
        m, n = A.shape
        dtype = str(np.dtype(A.device_dtype))
        norm_a = float(np.linalg.norm(A.csr_parts(
            np.dtype(dtype))[0]))
    else:
        A = np.asarray(A)
        if A.ndim != 2:
            raise ValueError(
                f"compressed_matmul expects a (m, n) A, got {A.shape}")
        m, n = A.shape
        dtype = str(A.dtype)
        norm_a = float(np.linalg.norm(A))
    if B.shape[0] != n:
        raise ValueError(
            f"contraction mismatch: A is {(m, n)}, B is {B.shape}")
    if n != transform.input_dim:
        raise ValueError(
            f"contraction dim {n} != transform input dim "
            f"{transform.input_dim}")
    if family == "SRHT" and n & (n - 1):
        raise ValueError(
            f"SRHT compressed_matmul requires a power-of-2 "
            f"contraction dim, got {n}")
    s_dim = transform.sketch_dim
    bound = (norm_a * float(np.linalg.norm(B))
             * math.sqrt(2.0 / s_dim))
    m_pad = bucketing.pow2_pad(m, pad_floor)
    p_pad = bucketing.pow2_pad(B.shape[1], pad_floor)
    nnz_cls = (bucketing.nnz_class(A.nnz,
                                   _env.SPARSE_NNZ_FLOOR.get())
               if sparse else 0)
    statics = ("compressed_matmul", family, s_dim, sparse, n, dtype,
               m_pad, p_pad, nnz_cls)
    return statics, {"A": A, "B": B, "family": family,
                     "sparse": sparse, "s_dim": s_dim, "n": n,
                     "m": m, "p": B.shape[1], "bound": bound,
                     "padded_A": (m_pad, n), "padded_B": (n, p_pad),
                     "nnz_class": nnz_cls, "dtype": dtype}


def _fastfood_statics(transform, A, pad_floor):
    """(statics, info) for a fastfood_features request: the Fastfood /
    RFT feature-map serve endpoint. The row extent is the one paddable
    class dimension (rows are independent lanes of the chain); the
    column extent must equal the transform's input dim exactly — the
    chain's own NB-padding is part of the feature definition. The Sm
    spec (kind, param) is a bucket static: transforms differing only by
    seed share one executable (streams rebuild from the stacked raw
    keys), transforms differing by sigma/nu do not."""
    from libskylark_tpu.sketch.frft import FastRFT

    if not isinstance(transform, FastRFT):
        raise TypeError(
            "fastfood_features serves FastRFT-family transforms "
            f"(FastGaussianRFT/FastMaternRFT); got "
            f"{type(transform).__name__}")
    A = np.asarray(A)
    squeeze = A.ndim == 1
    if squeeze:
        A = A[None, :]
    if A.shape[1] != transform.input_dim:
        raise ValueError(
            f"operand dim {A.shape[1]} != transform input dim "
            f"{transform.input_dim}")
    sm_kind, sm_param = transform._sm_spec()
    m_pad = bucketing.pow2_pad(A.shape[0], pad_floor)
    statics = ("fastfood_features", transform._fut_name, sm_kind,
               repr(sm_param), transform.sketch_dim, A.shape[1],
               str(A.dtype), m_pad)
    return statics, {"A": A, "squeeze": squeeze, "m_pad": m_pad,
                     "fut": transform._fut_name, "sm_kind": sm_kind,
                     "sm_param": sm_param,
                     "family": type(transform).sketch_type}


def _coerce_sparse(A):
    """The framework's :class:`~libskylark_tpu.base.sparse
    .SparseMatrix` view of a sparse serve operand (scipy sparse
    accepted and attached zero-copy where possible). Dense operands
    are a type error — they belong on ``submit_sketch``."""
    from libskylark_tpu.base.sparse import SparseMatrix

    if isinstance(A, SparseMatrix):
        return A
    try:
        import scipy.sparse as sp

        if sp.issparse(A):
            return SparseMatrix.from_scipy(A)
    except ImportError:  # pragma: no cover - scipy is a hard dep here
        pass
    raise TypeError(
        "sparse serve endpoints take a SparseMatrix or scipy.sparse "
        f"operand; got {type(A).__name__} (dense operands go through "
        "submit_sketch)")


def _sparse_sketch_statics(transform, A, dimension, pad_floor):
    """(statics, info) for a sparse_sketch_apply request: the CSR twin
    of :func:`_sketch_statics`, with the pow2 **nnz class**
    (``engine.bucket.nnz_class`` at the ``SKYLARK_SPARSE_NNZ_FLOOR``
    granularity) riding the statics next to the padded dims/dtype —
    two ragged-nnz requests in one class share one flush executable,
    their (data, indices) lanes zero-padded to the class extent."""
    from libskylark_tpu.sketch import COLUMNWISE, Dimension

    dimension = dimension or COLUMNWISE
    rowwise = Dimension(dimension) == Dimension.ROWWISE
    A = _coerce_sparse(A)
    n = A.width if rowwise else A.height
    if n != transform.input_dim:
        raise ValueError(
            f"operand dim {n} != transform input dim "
            f"{transform.input_dim}")
    family, dist = _sketch_family(transform)
    padded = bucketing.pad_shape(A.shape, (0, 1), pad_floor)
    nnz_cls = bucketing.nnz_class(A.nnz, _env.SPARSE_NNZ_FLOOR.get())
    dtype = str(np.dtype(A.device_dtype))
    statics = ("sparse_sketch_apply", family, repr(dist),
               transform.sketch_dim, rowwise, dtype, padded, nnz_cls)
    return statics, {"A": A, "family": family, "dist": dist,
                     "rowwise": rowwise, "padded": padded,
                     "nnz_class": nnz_cls, "dtype": dtype}


def _sparse_solve_statics(transform, A, B, method, pad_floor):
    """(statics, info) for a sparse_solve_l2_sketched request: CSR
    design matrix, dense target block."""
    A = _coerce_sparse(A)
    B = np.asarray(B)
    squeeze = B.ndim == 1
    if squeeze:
        B = B[:, None]
    if B.shape[0] != A.height:
        raise ValueError(f"solve expects (n,d) A and (n,t) B, got "
                         f"{A.shape} / {B.shape}")
    if A.height != transform.input_dim:
        raise ValueError(
            f"operand rows {A.height} != transform input dim "
            f"{transform.input_dim}")
    family, dist = _sketch_family(transform)
    if family not in ("JLT", "CWT"):
        raise TypeError(f"sparse solve serve path supports JLT/CWT, "
                        f"got {family}")
    n_pad = bucketing.pow2_pad(A.height, pad_floor)
    nnz_cls = bucketing.nnz_class(A.nnz, _env.SPARSE_NNZ_FLOOR.get())
    dtype = str(np.dtype(A.device_dtype))
    # d and t are exact bucket components (zero feature/target columns
    # would make the compressed problem singular) — same rule as the
    # dense solve bucket
    statics = ("sparse_solve_l2_sketched", family,
               transform.sketch_dim, method, A.width, B.shape[1],
               dtype, n_pad, nnz_cls)
    return statics, {"A": A, "B": B, "squeeze": squeeze,
                     "family": family, "n_pad": n_pad,
                     "nnz_class": nnz_cls, "dtype": dtype}


def _seed_key_data(seed: int) -> np.ndarray:
    """Raw key data of the root key of ``seed`` (``jax.random.key(seed)``'s
    words, by base/threefry.py's rule) as a host array — the key material
    of the seed-addressed endpoints (graph_ase, condest)."""
    from libskylark_tpu.base import threefry

    return np.array(threefry.seed_words(seed), dtype=np.uint32)


def _coerce_adjacency(A):
    from libskylark_tpu.ml.graph import coerce_adjacency

    return coerce_adjacency(A)[0]


def _graph_ase_statics(A, k, iters, pad_floor):
    """(statics, info) for a graph_ase request: adjacency spectral
    embedding over the r18 sparse CSR lanes — adjacency matrices are
    exactly the sparse regime those lanes optimize. ``k`` (embedding
    dim) and ``iters`` (subspace iterations) are statics; the seed
    rides as key-data operand bits so seeds share one executable."""
    S = _coerce_adjacency(A)
    padded = bucketing.pad_shape(S.shape, (0, 1), pad_floor)
    nnz_cls = bucketing.nnz_class(S.nnz, _env.SPARSE_NNZ_FLOOR.get())
    dtype = str(np.dtype(S.device_dtype))
    k = int(k)
    iters = max(int(iters), 1)
    if not 0 < k <= S.height:
        raise ValueError(f"embedding dim k={k} must be in (0, "
                         f"{S.height}]")
    statics = ("graph_ase", k, iters, dtype, padded, nnz_cls)
    return statics, {"A": S, "padded": padded, "nnz_class": nnz_cls,
                     "dtype": dtype, "k": k, "iters": iters}


def _graph_ppr_statics(A, s, alpha, iters, pad_floor):
    """(statics, info) for a graph_ppr request: fixed-iteration
    personalized PageRank over the CSR adjacency. ``alpha``/``iters``
    are statics; the personalization vector is an operand."""
    S = _coerce_adjacency(A)
    padded = bucketing.pad_shape(S.shape, (0, 1), pad_floor)
    nnz_cls = bucketing.nnz_class(S.nnz, _env.SPARSE_NNZ_FLOOR.get())
    dtype = str(np.dtype(S.device_dtype))
    s = np.asarray(s, dtype=np.dtype(dtype))
    if s.shape != (S.height,):
        raise ValueError(f"personalization vector shape {s.shape} != "
                         f"({S.height},)")
    alpha = float(alpha)
    if not 0.0 < alpha < 1.0:
        raise ValueError(f"alpha must be in (0, 1), got {alpha}")
    statics = ("graph_ppr", alpha, max(int(iters), 1), dtype, padded,
               nnz_cls)
    return statics, {"A": S, "s": s, "padded": padded,
                     "nnz_class": nnz_cls, "dtype": dtype,
                     "alpha": alpha, "iters": max(int(iters), 1)}


def _condest_statics(A, steps, pad_floor):
    """(statics, info) for a condest request: fixed-step Golub-Kahan
    condition estimation (``nla.condest.condest_serve_apply``)."""
    A = np.asarray(A)
    if A.ndim != 2:
        raise ValueError(f"condest expects a matrix, got {A.shape}")
    steps = max(int(steps), 1)
    if steps >= min(A.shape):
        raise ValueError(
            f"steps={steps} must be < min(shape)={min(A.shape)} "
            "(the Krylov space is exhausted past that)")
    padded = bucketing.pad_shape(A.shape, (0, 1), pad_floor)
    statics = ("condest", steps, str(A.dtype), padded)
    return statics, {"A": A, "padded": padded, "steps": steps}


def _lowrank_statics(transform_s, transform_t, A, k, pad_floor):
    """(statics, info) for a lowrank request: two-level-sketch
    dominant-subspace basis (``nla.lowrank.lowrank_serve_apply``)
    from two caller-held dense-family transforms. The row extent is
    the paddable class dimension (rows sketch independently); the
    feature extent is exact."""
    fam_s, dist_s = _sketch_family(transform_s)
    fam_t, dist_t = _sketch_family(transform_t)
    if fam_s != fam_t or repr(dist_s) != repr(dist_t):
        raise TypeError(
            f"lowrank serves a matched dense transform pair, got "
            f"{fam_s}/{fam_t}")
    if dist_s is None:
        raise TypeError("lowrank serves dense families (JLT/CT); CWT "
                        "has no dense virtual panel here")
    A = np.asarray(A)
    if A.ndim != 2 or A.shape[1] != transform_s.input_dim \
            or A.shape[1] != transform_t.input_dim:
        raise ValueError(
            f"operand {A.shape} does not match transform input dims "
            f"{transform_s.input_dim}/{transform_t.input_dim}")
    k = int(k)
    if not 0 < k <= transform_s.sketch_dim:
        raise ValueError(f"k={k} must be in (0, "
                         f"{transform_s.sketch_dim}]")
    m_pad = bucketing.pow2_pad(A.shape[0], pad_floor)
    statics = ("lowrank", fam_s, repr(dist_s),
               transform_s.sketch_dim, transform_t.sketch_dim, k,
               A.shape[1], str(A.dtype), m_pad)
    return statics, {"A": A, "dist": dist_s,
                     "padded": (m_pad, A.shape[1]), "k": k}


def _lowrank_key_data(transform, dtype):
    """(key data, scale) operand pair of one lowrank transform —
    shared with the eager twin (``nla.lowrank.lowrank_serve``) so
    both sides feed the pure endpoint identical bits."""
    kd = MicrobatchExecutor._key_data(transform)
    scale = np.asarray(getattr(transform, "scale", 1.0),
                       dtype=np.dtype(dtype))
    return kd, scale


def _solve_statics(transform, A, B, method, pad_floor):
    """(statics, info) for a solve_l2_sketched request."""
    A = np.asarray(A)
    B = np.asarray(B)
    squeeze = B.ndim == 1
    if squeeze:
        B = B[:, None]
    if A.ndim != 2 or B.shape[0] != A.shape[0]:
        raise ValueError(f"solve expects (n,d) A and (n,t) B, got "
                         f"{A.shape} / {B.shape}")
    if A.shape[0] != transform.input_dim:
        raise ValueError(
            f"operand rows {A.shape[0]} != transform input dim "
            f"{transform.input_dim}")
    family, dist = _sketch_family(transform)
    if family not in ("JLT", "CWT"):
        raise TypeError(f"solve serve path supports JLT/CWT, "
                        f"got {family}")
    n_pad = bucketing.pow2_pad(A.shape[0], pad_floor)
    # d and t are exact bucket components: zero feature/target
    # columns would make the compressed problem singular
    statics = ("solve_l2_sketched", family, transform.sketch_dim,
               method, A.shape[1], B.shape[1], str(A.dtype), n_pad)
    return statics, {"A": A, "B": B, "squeeze": squeeze,
                     "family": family, "n_pad": n_pad}


def _krr_statics(kernel, X_new, X_train, coef, pad_floor,
                 endpoint: str = "krr_predict"):
    """(statics, info) for a krr_predict request — and, with
    ``endpoint="rlsc_predict"``, for its classification twin (same
    bucket anatomy, distinct bucket family: the endpoints trace
    different programs). Shape-only on the model operands — the
    router must not pay a device conversion to compute an affinity
    key, so this reads ``np.shape`` where the executor's prep later
    converts."""
    X_new = np.asarray(X_new)
    squeeze_q = X_new.ndim == 1
    if squeeze_q:
        X_new = X_new[None, :]
    train_shape = tuple(np.shape(X_train))
    coef_shape = tuple(np.shape(coef))
    if len(coef_shape) == 1:
        coef_shape = coef_shape + (1,)
    if X_new.shape[1] != train_shape[1]:
        raise ValueError(
            f"query dim {X_new.shape[1]} != train dim "
            f"{train_shape[1]}")
    q_pad = bucketing.pow2_pad(X_new.shape[0], pad_floor)
    statics = (endpoint, engine_digest(kernel),
               train_shape, coef_shape, str(X_new.dtype), q_pad)
    return statics, {"X_new": X_new, "squeeze_q": squeeze_q,
                     "q_pad": q_pad}


def request_statics(endpoint: str, *,
                    pad_floor: int = bucketing.PAD_FLOOR,
                    **kwargs) -> tuple:
    """The engine-level bucket statics a request of ``endpoint`` with
    these operands lands in: (endpoint, family/digest, dtype, shape
    class, ...) — exactly the tuple the executor keys its batched
    executables on. This is the fleet router's affinity key (one
    executable class == one consistent-hash bucket), exposed as a
    module function so routing never has to build a request to know
    where it belongs. Transport kwargs (``timeout`` / ``deadline`` /
    ``request_id``) are ignored; ``pad_floor`` must match the target
    executors' (a :class:`~libskylark_tpu.fleet.ReplicaPool` keeps it
    uniform)."""
    return derive_request(endpoint, pad_floor=pad_floor, **kwargs)[0]


def derive_request(endpoint: str, *,
                   pad_floor: int = bucketing.PAD_FLOOR,
                   **kwargs) -> tuple:
    """``(statics, info)`` — the full derivation behind
    :func:`request_statics`. The fleet router uses this form and hands
    the result back to the chosen replica's ``submit`` (internal
    ``_derived=`` kwarg) so the derivation runs once per routed
    request, not once in the router and again in the executor."""
    for transport in ("timeout", "deadline", "request_id", "tenant",
                      "qos_class", "_digest"):
        kwargs.pop(transport, None)
    if endpoint == "sketch_apply":
        kwargs.setdefault("dimension", None)
        return _sketch_statics(kwargs["transform"], kwargs["A"],
                               kwargs["dimension"], pad_floor)
    if endpoint == "fastfood_features":
        return _fastfood_statics(kwargs["transform"], kwargs["A"],
                                 pad_floor)
    if endpoint == "solve_l2_sketched":
        kwargs.setdefault("method", "qr")
        return _solve_statics(kwargs["transform"], kwargs["A"],
                              kwargs["B"], kwargs["method"], pad_floor)
    if endpoint == "krr_predict":
        return _krr_statics(kwargs["kernel"], kwargs["X_new"],
                            kwargs["X_train"], kwargs["coef"],
                            pad_floor)
    if endpoint == "sparse_sketch_apply":
        kwargs.setdefault("dimension", None)
        return _sparse_sketch_statics(kwargs["transform"], kwargs["A"],
                                      kwargs["dimension"], pad_floor)
    if endpoint == "sparse_solve_l2_sketched":
        kwargs.setdefault("method", "qr")
        return _sparse_solve_statics(kwargs["transform"], kwargs["A"],
                                     kwargs["B"], kwargs["method"],
                                     pad_floor)
    if endpoint == "graph_ase":
        return _graph_ase_statics(kwargs["A"], kwargs["k"],
                                  kwargs.get("iters", 2), pad_floor)
    if endpoint == "graph_ppr":
        return _graph_ppr_statics(kwargs["A"], kwargs["s"],
                                  kwargs.get("alpha", 0.85),
                                  kwargs.get("iters", 16), pad_floor)
    if endpoint == "condest":
        return _condest_statics(kwargs["A"], kwargs.get("steps", 8),
                                pad_floor)
    if endpoint == "lowrank":
        return _lowrank_statics(kwargs["transform_s"],
                                kwargs["transform_t"], kwargs["A"],
                                kwargs["k"], pad_floor)
    if endpoint == "rlsc_predict":
        return _krr_statics(kwargs["kernel"], kwargs["X_new"],
                            kwargs["X_train"], kwargs["coef"],
                            pad_floor, endpoint="rlsc_predict")
    if endpoint == "compressed_matmul":
        return _cmm_statics(kwargs["transform"], kwargs["A"],
                            kwargs["B"], pad_floor)
    raise ValueError(f"unknown serve endpoint {endpoint!r}; "
                     f"expected one of {ENDPOINTS}")


def request_digest(endpoint: str, derived: tuple, kwargs: dict) -> str:
    """The request's content address (docs/caching, "Digest anatomy"):
    blake2b-256 over the bucket statics plus everything else that
    reaches the executable — the transform's raw key data (the seed;
    same operand bytes under a different seed MUST digest differently,
    the miscoalesce regression), any scale, the operand bytes (CSR
    operands hash their (data, indices, indptr) parts — never
    densified), and model/seed material per endpoint family.

    ``derived`` is :func:`derive_request`'s ``(statics, info)`` and
    ``kwargs`` the endpoint kwargs it was derived from, so the fleet
    router — which has both in hand — computes the digest ONCE per
    request and forwards it (``_digest=``); a standalone executor
    derives it itself. The digest deliberately contains no object ids
    and no transport state: two replicas handed the same request
    bytes compute the same address, which is what makes the cache
    deterministic across a fleet."""
    statics, info = derived
    kd = MicrobatchExecutor._key_data

    def scale_of(t):
        return np.float64(getattr(t, "scale", 1.0))

    def csr(A, dtype):
        data, indices, indptr = A.csr_parts(np.dtype(dtype))
        return [("shape", repr(tuple(A.shape))), ("data", data),
                ("indices", indices), ("indptr", indptr)]

    if endpoint in ("sketch_apply", "fastfood_features"):
        t = kwargs["transform"]
        parts = [("kd", kd(t)), ("scale", scale_of(t)),
                 ("A", info["A"])]
    elif endpoint == "solve_l2_sketched":
        t = kwargs["transform"]
        parts = [("kd", kd(t)), ("scale", scale_of(t)),
                 ("A", info["A"]), ("B", info["B"])]
    elif endpoint in ("krr_predict", "rlsc_predict"):
        # the model CONTENT is part of the address (the bucket key's
        # id()-identity is a queueing concern — content addressing
        # must survive a model round-trip through a new object)
        parts = [("Xq", info["X_new"]),
                 ("X_train", np.asarray(kwargs["X_train"])),
                 ("coef", np.asarray(kwargs["coef"])),
                 ("coding", repr(kwargs.get("coding")))]
    elif endpoint in ("sparse_sketch_apply", "sparse_solve_l2_sketched"):
        t = kwargs["transform"]
        parts = [("kd", kd(t)), ("scale", scale_of(t))]
        parts += csr(info["A"], info["dtype"])
        if endpoint == "sparse_solve_l2_sketched":
            parts.append(("B", info["B"]))
    elif endpoint == "graph_ase":
        parts = [("seed", repr(int(kwargs.get("seed", 0))))]
        parts += csr(info["A"], info["dtype"])
    elif endpoint == "graph_ppr":
        parts = csr(info["A"], info["dtype"]) + [("s", info["s"])]
    elif endpoint == "condest":
        parts = [("seed", repr(int(kwargs.get("seed", 0)))),
                 ("A", info["A"])]
    elif endpoint == "lowrank":
        ts, tt = kwargs["transform_s"], kwargs["transform_t"]
        parts = [("kd_s", kd(ts)), ("scale_s", scale_of(ts)),
                 ("kd_t", kd(tt)), ("scale_t", scale_of(tt)),
                 ("A", info["A"])]
    elif endpoint == "compressed_matmul":
        t = kwargs["transform"]
        parts = [("kd", kd(t)), ("scale", scale_of(t))]
        if info["sparse"]:
            parts += csr(info["A"], info["dtype"])
        else:
            parts.append(("A", info["A"]))
        parts.append(("B", info["B"]))
    else:
        raise ValueError(f"unknown serve endpoint {endpoint!r}; "
                         f"expected one of {ENDPOINTS}")
    return _rcache.operand_digest(parts, statics=statics)


class MicrobatchExecutor:
    """Thread-safe microbatching executor over the serve endpoints.

    ::

        ex = engine.MicrobatchExecutor(max_batch=8, linger_us=2000)
        fut = ex.submit_sketch(transform, A, dimension=sk.ROWWISE)
        fut2 = ex.submit_solve(A, b, transform=T, method="qr")
        fut3 = ex.submit_krr_predict(kernel, Xq, X_train, coef)
        SA = fut.result()
        ex.shutdown()

    ``mesh`` (optional ``jax.sharding.Mesh``) shards every flush's batch
    dimension across the mesh — capacity classes round up to the device
    count so each device gets equal lanes; model operands (KRR's
    training set and coefficients) are replicated.

    ``workers`` flush cohorts concurrently; the executable cache is
    single-flight, so concurrent cold flushes of one bucket compile
    once. Submission itself is cheap (a host-side pack + queue append)
    and safe from any thread.

    ``dispatch_queue`` (advanced; a ``queue.Queue``) makes this
    executor enqueue its cohorts there instead of spawning its own
    workers — the seam a :class:`~libskylark_tpu.fleet.ReplicaPool`
    uses to size flush concurrency to the HOST rather than to N
    replicas (N replicas × own workers oversubscribes a small host;
    see docs/fleet "Tuning N"). The queue's owner runs the worker
    threads (:func:`dispatch_loop`) and must outlive the executor.
    """

    def __init__(self, max_batch: int = 8, linger_us: int = 2000,
                 max_queue: int = 1024, workers: int = 1,
                 mesh=None, pad_floor: int = bucketing.PAD_FLOOR,
                 degraded_threshold: float = 0.5,
                 failure_window: int = 32,
                 shed_fraction: float = 0.25,
                 name: Optional[str] = None,
                 dispatch_queue=None,
                 kernel: Optional[str] = None,
                 tenants=None,
                 adaptive: bool = False,
                 cache: Optional[bool] = None,
                 cache_bytes: Optional[int] = None):
        if max_batch < 1:
            raise ValueError("max_batch must be >= 1")
        if kernel is not None and kernel not in _KERNEL_BACKENDS:
            raise ValueError(
                f"kernel must be one of {_KERNEL_BACKENDS} or None "
                f"(SKYLARK_SERVE_KERNEL, else xla), got {kernel!r}")
        if not 0.0 < degraded_threshold <= 1.0:
            raise ValueError("degraded_threshold must be in (0, 1]")
        if not 0.0 < shed_fraction <= 1.0:
            raise ValueError("shed_fraction must be in (0, 1]")
        # replica identity: the label under which this executor's
        # counters disaggregate in telemetry.snapshot() / Prometheus,
        # and the name a ReplicaPool/Router address it by
        self.name = str(name) if name else f"ex-{next(_EX_SEQ)}"
        self.max_batch = int(max_batch)
        self.linger = float(linger_us) * 1e-6
        self.max_queue = int(max_queue)
        self.pad_floor = int(pad_floor)
        self.degraded_threshold = float(degraded_threshold)
        self.shed_fraction = float(shed_fraction)
        self._mesh = mesh
        self._batch_axis = None
        self._ndev = 1
        # fewest distinct devices that held a flush's output so far
        # (stats()["mesh"]); None until a sharded flush ran
        self._flush_devices_min: Optional[int] = None
        if mesh is not None:
            self._batch_axis = tuple(mesh.shape.keys())[0]
            self._ndev = int(mesh.shape[self._batch_axis])

        self._lock = _locks.make_lock("serve.state")
        self._work_cv = threading.Condition(self._lock)   # flusher wakeups
        self._space_cv = threading.Condition(self._lock)  # backpressure
        self._idle_cv = threading.Condition(self._lock)   # drain quiescence
        self._buckets: "dict[tuple, _Bucket]" = {}
        self._pending = 0
        # multi-tenant QoS (docs/qos): the tenant registry resolves
        # tenant= submits to priority classes and charges token
        # buckets; the deficit scheduler replaces the FIFO drain order
        # across the per-class queues; per-bucket linger/batch targets
        # start at the static config and move only when the adaptive
        # controller is on
        self._tenants = (tenants if tenants is not None
                         else _qtenants.get_registry())
        self._sched = _qsched.DeficitScheduler(quantum=self.max_batch)
        self._class_pending = collections.Counter()  # under _lock
        self._qos_targets: "dict[tuple, list]" = {}  # under _lock
        self._inflight = 0                # popped cohorts being executed
        self._stop = False
        self._draining = False

        self._compiled: dict = {}          # bucket key -> CompiledFn
        self._compiled_lock = _locks.make_lock("serve.compiled")
        # flush-kernel selection (docs/performance "Serve-bucket kernel
        # selection"): the explicit argument tops the precedence; the
        # memo makes key_fn's per-call re-resolution a dict hit, keyed
        # on (bucket statics, capacity) for the executor's life
        self.kernel = kernel
        self._kernel_memo: dict = {}

        self._stats_lock = _locks.make_lock("serve.stats")
        self._counts = collections.Counter()
        # flush-kernel selection counters (per flush): backend ->
        # flushes served, decline-reason -> flushes that fell back
        self._kernel_sel: "collections.Counter" = collections.Counter()
        self._kernel_dec: "collections.Counter" = collections.Counter()
        self._batch_hist: "collections.Counter" = collections.Counter()
        self._cohort_hist: "collections.Counter" = collections.Counter()
        # sparse-operand intake/flush disaggregation (docs/serving,
        # "Sparse operands on the serve path")
        self._sparse_kernel_sel: "collections.Counter" = \
            collections.Counter()
        self._sparse_nnz_hist: "collections.Counter" = \
            collections.Counter()
        # SRHT/FWHT flush disaggregation (docs/performance, "In-kernel
        # FWHT and compressed matmul")
        self._fwht_sel: "collections.Counter" = collections.Counter()
        # QoS accounting (under _stats_lock): (kind, class, tenant)
        # counters, per-class latency windows, per-bucket adaptive-
        # controller observations (latency window, warm capacity set,
        # padding-waste raw counts, classes seen)
        self._qos_counts: "collections.Counter" = collections.Counter()
        self._latency_by_class: dict = {
            c: collections.deque(maxlen=4096) for c in _qtenants.CLASSES}
        self._bucket_obs: dict = {}
        self._pad_real = 0
        self._pad_total = 0
        self._latency = collections.deque(maxlen=8192)
        # sliding window of flush-attempt outcomes (1.0 = failed): the
        # DEGRADED detector's evidence
        self._health = collections.deque(maxlen=max(int(failure_window), 4))
        # push-side of the health states: the last state published to
        # the resilience hub (fleet routers subscribe); guarded by its
        # own lock so a flush worker and a drain can race a transition
        # without serializing on the executor lock
        self._pub_lock = _locks.make_lock("serve.pub")
        self._published_state = SERVING
        # stateful sketch sessions (docs/sessions): the registry is
        # built lazily on the first session verb — one-shot serving
        # never pays the directory setup
        self._session_registry = None
        # training jobs (docs/training): lazy like the registry — the
        # flusher consults it only once a job has been submitted
        self._train_mgr = None
        # content-addressed result cache + single-flight dedupe
        # (docs/caching): opt-in — the ctor argument wins, else the
        # SKYLARK_CACHE flag. The residency table exists regardless:
        # register_operand must pin on a cache-off replica too (the
        # fleet broadcasts registrations to every replica, and an
        # OperandRef must resolve wherever the request lands).
        if cache is None:
            cache = bool(_env.CACHE.get())
        self._cache = (_rcache.ResultCache(name=self.name,
                                           max_bytes=cache_bytes)
                       if cache else None)
        self._residency = _rcache.ResidencyTable(name=self.name)
        # pipelined dist-serve endpoints (docs/distributed): the local
        # no-fleet coordinator is built lazily; by-replica shard-task
        # counts feed the serve_stats() dist block
        self._dist_local_co = None
        self._dist_by_replica: "collections.Counter" = \
            collections.Counter()

        import queue as _queue

        if dispatch_queue is not None:
            self._workq = dispatch_queue
            self._workers = []        # the queue's owner runs them
        else:
            self._workq = _queue.Queue()
            self._workers = [
                threading.Thread(
                    target=dispatch_loop, args=(self._workq,),
                    name=f"skylark-serve-worker-{i}", daemon=True)
                for i in range(max(int(workers), 1))
            ]
            for t in self._workers:
                t.start()
        self._flusher = threading.Thread(
            target=self._flusher_loop, name="skylark-serve-flusher",
            daemon=True)
        self._flusher.start()
        # the adaptive batching controller (docs/qos): opt-in per
        # executor; SKYLARK_QOS_ADAPT=0 freezes even opted-in ones
        self._controller = None
        if adaptive:
            from libskylark_tpu.qos.controller import AdaptiveController

            self._controller = AdaptiveController(self)
        _EXECUTORS.add(self)

    # ------------------------------------------------------------------
    # submit: request intake
    # ------------------------------------------------------------------

    def submit(self, endpoint: str, /, **kwargs) -> Future:
        """Queue one request; returns a future resolving to exactly what
        the endpoint's sequential API returns. ``timeout`` (seconds,
        default 30) bounds the backpressure wait. ``deadline`` (seconds
        or a :class:`~libskylark_tpu.resilience.Deadline`) bounds the
        request's whole queued life: one that expires before its flush
        executes resolves to :class:`ServeOverloadedError` instead of
        occupying a batch lane (or an isolation retry). ``request_id``
        names the request in the telemetry trace (docs/observability;
        minted automatically when telemetry is on) — it survives the
        cross-thread hop into the flush worker and appears on the flush
        span and every bisection-isolation child span."""
        timeout = kwargs.pop("timeout", 30.0)
        deadline = Deadline.coerce(kwargs.pop("deadline", None))
        rid = kwargs.pop("request_id", None)
        # QoS admission (docs/qos): resolve the tenant to its priority
        # class and charge its token bucket. ``qos_class=`` marks a
        # request the front door (a fleet Router, whose registry holds
        # the token buckets) already admitted — re-charging here would
        # double-bill every routed request.
        tenant = kwargs.pop("tenant", None)
        qos_class = kwargs.pop("qos_class", None)
        if qos_class is None:
            try:
                tenant, qos_class = self._tenants.admit(tenant)
            except _errors.TenantQuotaError as e:
                _cls = self._tenants.resolve(tenant)[1]
                with self._stats_lock:
                    self._qos_counts[
                        ("rate_limited", _cls, e.tenant)] += 1
                _QOS_RATE_LIMITED.inc(
                    **{"class": _cls, "tenant": e.tenant})
                raise
            # cardinality bound: unregistered tenant names account
            # under the anonymous bucket — label sets and per-tenant
            # stats must not grow with arbitrary caller strings. Only
            # applied where the tenant is RESOLVED: a pre-resolved
            # request (qos_class= from a fleet front door) carries a
            # label its router already vetted against ITS registry —
            # a process replica's own registry doesn't know it
            tenant = self._tenants.accounting_name(tenant)
        else:
            qos_class = _qtenants.coerce_class(qos_class)
            tenant = str(tenant) if tenant else ""
        # chaos seam: a plan can deterministically fail admission
        faults.check("qos.admit", tags=faults.current_tags(),
                     detail=f"{endpoint} {tenant or '-'} {qos_class}")
        # internal fast path: the fleet router already derived the
        # bucket statics to pick this replica — reuse them instead of
        # re-deriving (the derivation is the submit hot path's single
        # biggest cost; doing it twice per routed request would tax
        # every fleet submit)
        derived = kwargs.pop("_derived", None)
        digest = kwargs.pop("_digest", None)
        # operand residency (docs/caching): an ``A=`` that is an
        # OperandRef resolves to the pinned bytes before derivation —
        # the ref IS the content hash, so resolution cannot change
        # what the request means, only skip re-shipping it
        if _rcache.is_ref(kwargs.get("A")):
            kwargs["A"] = self._residency.resolve(
                _rcache.as_ref(kwargs["A"]).digest)
        # content-addressed fast paths (docs/caching): pinned results
        # → digest→result cache → single-flight coalescing. All three
        # are skipped wholesale while DEGRADED: a shedding executor
        # must never block intake on cache locks, and a degraded
        # flush path must not populate the cache either (the settle
        # callback re-checks). A front-door digest (``_digest=`` from
        # a fleet router) is reused; otherwise it is derived here —
        # at most once per request, with the derivation shared with
        # the per-endpoint prep below.
        flight = None
        cache_key = None
        if self._cache is not None and not self._is_degraded():
            if digest is None:
                if derived is None:
                    derived = derive_request(
                        endpoint, pad_floor=self.pad_floor, **kwargs)
                digest = request_digest(endpoint, derived, kwargs)
            cache_key = digest
            pinned = self._residency.result(cache_key)
            if pinned is not None:
                self._cache.note_hit(qos_class, pinned)
                return self._bypass_future(qos_class, pinned)
            hit = self._cache.lookup(cache_key, qos_class)
            if hit is not _rcache.MISS:
                return self._bypass_future(qos_class, hit)
            follower = self._cache.join_flight(cache_key, qos_class)
            if follower is not None:
                with self._lock:
                    self._sched.note_bypass(qos_class)
                return follower
        if rid is None and _telemetry.enabled():
            rid = _trace.new_request_id()
        # the submit span covers pack + enqueue; its context (trace id,
        # span id, request id) rides the request into the flush thread
        with _trace.span("serve.submit", attrs={"endpoint": endpoint},
                         request_id=rid) as sp:
            if endpoint == "sketch_apply":
                key, statics, ctx, req = self._prep_sketch(
                    _derived=derived, **kwargs)
            elif endpoint == "fastfood_features":
                key, statics, ctx, req = self._prep_fastfood(
                    _derived=derived, **kwargs)
            elif endpoint == "solve_l2_sketched":
                key, statics, ctx, req = self._prep_solve(
                    _derived=derived, **kwargs)
            elif endpoint == "krr_predict":
                key, statics, ctx, req = self._prep_krr(
                    _derived=derived, **kwargs)
            elif endpoint == "sparse_sketch_apply":
                key, statics, ctx, req = self._prep_sparse_sketch(
                    _derived=derived, **kwargs)
            elif endpoint == "sparse_solve_l2_sketched":
                key, statics, ctx, req = self._prep_sparse_solve(
                    _derived=derived, **kwargs)
            elif endpoint == "graph_ase":
                key, statics, ctx, req = self._prep_graph_ase(
                    _derived=derived, **kwargs)
            elif endpoint == "graph_ppr":
                key, statics, ctx, req = self._prep_graph_ppr(
                    _derived=derived, **kwargs)
            elif endpoint == "condest":
                key, statics, ctx, req = self._prep_condest(
                    _derived=derived, **kwargs)
            elif endpoint == "lowrank":
                key, statics, ctx, req = self._prep_lowrank(
                    _derived=derived, **kwargs)
            elif endpoint == "rlsc_predict":
                key, statics, ctx, req = self._prep_rlsc(
                    _derived=derived, **kwargs)
            elif endpoint == "compressed_matmul":
                key, statics, ctx, req = self._prep_cmm(
                    _derived=derived, **kwargs)
            else:
                raise ValueError(f"unknown serve endpoint {endpoint!r}; "
                                 f"expected one of {ENDPOINTS}")
            req.deadline = deadline
            req.request_id = rid
            req.qos_class = qos_class
            req.tenant = tenant or ""
            if sp is not None:
                req.tctx = sp.context()
            # capture the submitting thread's fault tags so chaos plans
            # can pin a fault to THIS request wherever its cohort
            # executes
            req.tags = faults.current_tags()
            # single-flight leadership spans the whole enqueue: the
            # flight must be joinable BEFORE the request is queued
            # (identical concurrent submits coalesce while the leader
            # lingers), and a synchronous refusal — shed, drain,
            # backpressure timeout — must fan its exception to every
            # follower already attached (no orphaned futures)
            if cache_key is not None:
                flight = self._cache.lead_flight(
                    cache_key, qos_class, req.future)
            try:
                self._enqueue(key, statics, ctx, req, timeout)
            except BaseException as e:
                if flight is not None:
                    self._cache.abort_flight(flight, e)
                raise
            if flight is not None:
                req.future.add_done_callback(
                    lambda f, _fl=flight: self._cache.settle_flight(
                        _fl, f, insert=not self._is_degraded()))
        return req.future

    def submit_sketch(self, transform, A, dimension=None, **kw) -> Future:
        return self.submit("sketch_apply", transform=transform, A=A,
                           dimension=dimension, **kw)

    def submit_fastfood(self, transform, A, **kw) -> Future:
        """Fastfood/RFT feature-map endpoint: resolves to exactly what
        ``transform.apply(A, ROWWISE)`` returns (the (m, S) feature
        map; 1-D input returns (S,))."""
        return self.submit("fastfood_features", transform=transform,
                           A=A, **kw)

    def submit_solve(self, A, B, transform, method: str = "qr",
                     **kw) -> Future:
        return self.submit("solve_l2_sketched", A=A, B=B,
                           transform=transform, method=method, **kw)

    def _note_sparse_intake(self, A) -> bool:
        """Count one sparse submission and decide the densify fallback
        (docs/serving, "Sparse operands on the serve path"): an operand
        at or above ``SKYLARK_SPARSE_MIN_DENSITY`` routes to the dense
        endpoint — at high density the padded CSR lanes carry more
        bytes than the dense operand and the O(nnz) scatter loses to
        the dense contraction. Returns whether to densify."""
        nnz_cls = bucketing.nnz_class(A.nnz,
                                      _env.SPARSE_NNZ_FLOOR.get())
        _SPARSE_SUBMITS.inc_always()
        _SPARSE_NNZ_HIST.observe_always(float(nnz_cls))
        densify = A.density >= _env.SPARSE_MIN_DENSITY.get()
        if densify:
            _SPARSE_DENSIFIED.inc_always()
        with self._stats_lock:
            self._counts["sparse_submits"] += 1
            self._sparse_nnz_hist[nnz_cls] += 1
            if densify:
                self._counts["sparse_densified"] += 1
        return densify

    def submit_sparse(self, transform, A, dimension=None, **kw) -> Future:
        """Sparse (CSR-packed) sketch-apply endpoint: ``A`` is a
        :class:`~libskylark_tpu.base.sparse.SparseMatrix` or
        scipy.sparse operand; resolves to what
        ``transform.apply(A.todense(), dimension)`` returns, as a host
        array — bit-equal to the densified request through the serve
        layer (for CWT that extends to the eager dense apply at any
        shape: the CSR lanes accumulate in the dense scatter's
        row-major order; dense families carry the dense serve
        endpoint's own epsilon band off pow2 stream classes — docs/
        serving, "Sparse operands on the serve path"). Operands at or
        above the auto-densify threshold route through the dense
        serve path (counted as ``sparse_densified``)."""
        A = _coerce_sparse(A)
        if self._note_sparse_intake(A):
            Ad = np.asarray(A.to_scipy().toarray(),
                            dtype=np.dtype(A.device_dtype))
            return self.submit("sketch_apply", transform=transform,
                               A=Ad, dimension=dimension, **kw)
        return self.submit("sparse_sketch_apply", transform=transform,
                           A=A, dimension=dimension, **kw)

    def submit_sparse_solve(self, A, B, transform, method: str = "qr",
                            **kw) -> Future:
        """Sparse sketched least-squares: CSR design matrix ``A``,
        dense target block ``B``; resolves to what
        ``solve_l2_sketched(A.todense(), B, transform)`` returns. Same
        densify fallback rule as :meth:`submit_sparse`."""
        A = _coerce_sparse(A)
        if self._note_sparse_intake(A):
            Ad = np.asarray(A.to_scipy().toarray(),
                            dtype=np.dtype(A.device_dtype))
            return self.submit("solve_l2_sketched", A=Ad, B=B,
                               transform=transform, method=method,
                               **kw)
        return self.submit("sparse_solve_l2_sketched", A=A, B=B,
                           transform=transform, method=method, **kw)

    def submit_krr_predict(self, kernel, X_new, X_train, coef,
                           **kw) -> Future:
        return self.submit("krr_predict", kernel=kernel, X_new=X_new,
                           X_train=X_train, coef=coef, **kw)

    # -- heterogeneous library endpoints (docs/qos) --------------------

    def submit_graph_ase(self, A, k: int, *, seed: int = 0,
                         iters: int = 2, **kw) -> Future:
        """Adjacency spectral embedding endpoint: ``A`` is a
        :class:`~libskylark_tpu.ml.graph.Graph`, SparseMatrix, scipy
        sparse, or dense square adjacency (packed as r18 CSR lanes);
        resolves to the (n, k) embedding host array — bit-equal to
        :func:`~libskylark_tpu.ml.graph.graph_ase_serve` with the
        same seed."""
        return self.submit("graph_ase", A=A, k=k, seed=seed,
                           iters=iters, **kw)

    def submit_graph_ppr(self, A, s, *, alpha: float = 0.85,
                         iters: int = 16, **kw) -> Future:
        """Personalized-PageRank endpoint: ``s`` is the (n,)
        personalization vector in adjacency row order; resolves to
        the (n,) diffusion vector — bit-equal to
        :func:`~libskylark_tpu.ml.graph.graph_ppr_serve`."""
        return self.submit("graph_ppr", A=A, s=s, alpha=alpha,
                           iters=iters, **kw)

    def submit_condest(self, A, *, steps: int = 8, seed: int = 0,
                       **kw) -> Future:
        """Condition-estimation endpoint: fixed-step Golub-Kahan;
        resolves to the ``(cond, sigma_max, sigma_min)`` host (3,)
        array — bit-equal to
        :func:`~libskylark_tpu.nla.condest.condest_serve`."""
        return self.submit("condest", A=A, steps=steps, seed=seed,
                           **kw)

    def submit_lowrank(self, transform_s, transform_t, A, k: int,
                       **kw) -> Future:
        """Dominant-subspace endpoint: two-level sketch basis from a
        matched dense transform pair; resolves to the (n, k) basis —
        bit-equal to
        :func:`~libskylark_tpu.nla.lowrank.lowrank_serve` at pow2
        row classes."""
        return self.submit("lowrank", transform_s=transform_s,
                           transform_t=transform_t, A=A, k=k, **kw)

    def submit_compressed_matmul(self, A, B, transform=None, *,
                                 s_dim: Optional[int] = None,
                                 seed: int = 0, **kw) -> Future:
        """Compressed approximate matmul (docs/performance,
        "In-kernel FWHT and compressed matmul"): estimate ``A @ B``
        from one shared sketch — ``(A Sᵀ)(S B)`` with the SAME
        operator S on both sides, so the estimate is unbiased
        (``E[SᵀS] = I`` for both families). Resolves to
        ``(estimate, bound)``: the (m, p) host estimate and the
        expected-error scale ``‖A‖_F·‖B‖_F·√(2/s)`` (the standard
        sketched-AMM Frobenius bound — an expectation-level scale,
        not a tail guarantee). ``A`` may be dense or CSR (the sparse
        lane sketches straight off the r18 CSR packing for CWT, and
        densifies in-executable for SRHT). Pass a caller-held CWT or
        FJLT/``wht`` transform for seed control, or let ``s_dim``
        (default ``SKYLARK_FWHT_CM_SDIM``) and ``seed`` build one:
        SRHT when the contraction dim is a power of two, CWT
        otherwise."""
        if transform is None:
            transform = default_cmm_transform(A, s_dim=s_dim, seed=seed)
        return self.submit("compressed_matmul", transform=transform,
                           A=A, B=B, **kw)

    def submit_rlsc_predict(self, kernel, X_new, X_train, coef,
                            coding=None, **kw) -> Future:
        """RLSC classification endpoint: argmax over the one-vs-all
        KRR scores; resolves to int32 class indices (decoded to
        labels when ``coding`` is given) — bit-equal to
        :func:`~libskylark_tpu.ml.rlsc.rlsc_predict`."""
        return self.submit("rlsc_predict", kernel=kernel, X_new=X_new,
                           X_train=X_train, coef=coef, coding=coding,
                           **kw)

    # ------------------------------------------------------------------
    # pipelined distributed serve endpoints (docs/distributed)
    # ------------------------------------------------------------------

    def _submit_dist(self, endpoint: str, plan, source, *,
                     tenant=None, qos_class=None, min_coverage=None,
                     deadline=None, timeout=None, request_id=None,
                     pool=None, replicas=None, coordinator=None,
                     pipeline=None, _digest=None, solve=None,
                     digest_extra=()) -> Future:
        """Common path of the dist endpoints: QoS admission, the
        content-addressed fast paths (a dist result is a pure function
        of (source digest, plan fingerprint, seed) — same digest, same
        bits), then a :class:`~libskylark_tpu.dist.serve.DistServeJob`
        driven on a daemon thread under a ``serve.submit`` span whose
        request id parents every ``dist.shard_task`` span."""
        from libskylark_tpu.dist import serve as _dserve
        from libskylark_tpu.dist.coordinator import (
            DistSketchCoordinator)

        plan.validate()
        if source.n < plan.n:
            raise _errors.InvalidParametersError(
                f"source holds {source.n} rows < plan.n={plan.n}")
        rid = request_id
        # QoS admission: same double-billing discipline as submit() —
        # ``qos_class=`` marks a front-door-admitted request
        if qos_class is None:
            try:
                tenant, qos_class = self._tenants.admit(tenant)
            except _errors.TenantQuotaError as e:
                _cls = self._tenants.resolve(tenant)[1]
                with self._stats_lock:
                    self._qos_counts[
                        ("rate_limited", _cls, e.tenant)] += 1
                _QOS_RATE_LIMITED.inc(
                    **{"class": _cls, "tenant": e.tenant})
                raise
            tenant = self._tenants.accounting_name(tenant)
        else:
            qos_class = _qtenants.coerce_class(qos_class)
            tenant = str(tenant) if tenant else ""
        faults.check("qos.admit", tags=faults.current_tags(),
                     detail=f"{endpoint} {tenant or '-'} {qos_class}")
        with self._stats_lock:
            self._counts["dist_jobs"] += 1
        # the effective coverage gate is part of the request's identity:
        # an interactive caller gating at 0.9 and a batch caller gating
        # at 1.0 must never share a cache or single-flight key, or the
        # batch caller could be handed a degraded answer its SLO forbids
        gate = (_dserve.class_min_coverage(qos_class)
                if min_coverage is None else float(min_coverage))
        flight = None
        cache_key = None
        if self._cache is not None and not self._is_degraded():
            cache_key = _digest or _dserve.dist_request_digest(
                endpoint, plan, source,
                extra=(*tuple(digest_extra), ("gate", gate)))
            pinned = self._residency.result(cache_key)
            if pinned is not None:
                self._cache.note_hit(qos_class, pinned)
                return self._bypass_future(qos_class, pinned)
            hit = self._cache.lookup(cache_key, qos_class)
            if hit is not _rcache.MISS:
                return self._bypass_future(qos_class, hit)
            follower = self._cache.join_flight(cache_key, qos_class)
            if follower is not None:
                with self._lock:
                    self._sched.note_bypass(qos_class)
                return follower
        if rid is None and _telemetry.enabled():
            rid = _trace.new_request_id()
        fut: Future = Future()
        with _trace.span("serve.submit", attrs={"endpoint": endpoint},
                         request_id=rid) as sp:
            co = coordinator
            if co is None and (pool is not None
                               or replicas is not None):
                co = DistSketchCoordinator(pool=pool, replicas=replicas)
            if co is None:
                co = self._dist_local_co
                if co is None:
                    with self._lock:
                        if self._dist_local_co is None:
                            self._dist_local_co = \
                                DistSketchCoordinator()
                        co = self._dist_local_co
            job = _dserve.DistServeJob(
                plan, source, coordinator=co, qos_class=qos_class,
                tenant=tenant, registry=self._tenants,
                min_coverage=min_coverage,
                deadline=deadline if deadline is not None else timeout,
                pipeline=pipeline, request_id=rid,
                parent_ctx=sp.context() if sp is not None else None)

            def _settle(j, exc):
                with self._stats_lock:
                    self._counts["dist_completed" if exc is None
                                 else "dist_failed"] += 1
                    if j.stats.get("early_resolved"):
                        self._counts["dist_early_resolves"] += 1
                    for name, k in j.stats.get("by_replica",
                                               {}).items():
                        self._dist_by_replica[name] += k

            if cache_key is not None:
                flight = self._cache.lead_flight(cache_key, qos_class,
                                                 fut)
            try:
                _dserve.run_job_into(job, fut, solve=solve,
                                     on_done=_settle)
            except BaseException as e:
                if flight is not None:
                    self._cache.abort_flight(flight, e)
                raise
            if flight is not None:
                def _insert_ok(f) -> bool:
                    # a degraded result is circumstance (which replicas
                    # died this time), not content — never cache it;
                    # settle_flight still shares it with in-flight
                    # followers of the same gate+digest
                    if self._is_degraded() or f.exception() is not None:
                        return False
                    v = f.result()
                    if isinstance(v, dict):
                        return not v.get("degraded")
                    return not getattr(v, "degraded", False)

                fut.add_done_callback(
                    lambda f, _fl=flight: self._cache.settle_flight(
                        _fl, f, insert=_insert_ok(f)))
        return fut

    def submit_dist_sketch(self, plan, source, **kw) -> Future:
        """Pipelined distributed sketch: shard tasks of ``plan`` fan
        across the coordinator's fleet (``pool=`` / ``replicas=`` /
        ``coordinator=``; with none, a private thread pool pipelines
        shard compute locally) and partials merge incrementally as
        they land. Resolves to the
        :class:`~libskylark_tpu.dist.plan.DistSketchResult` —
        full-coverage bits equal to
        :func:`~libskylark_tpu.dist.plan.sketch_local`. Per-class
        ``min_coverage`` gates apply (docs/qos): interactive requests
        may resolve early with a quantified
        :class:`~libskylark_tpu.dist.plan.DegradedSketchResult`."""
        return self._submit_dist("dist_sketch", plan, source, **kw)

    def submit_dist_lstsq(self, source, *, s_dim: int, seed: int = 0,
                          kind: str = "cwt", shard_rows: int = 0,
                          **kw) -> Future:
        """Distributed sketch-and-solve least squares
        (:func:`~libskylark_tpu.dist.algorithms.sketched_lstsq` as a
        serve endpoint): the joint ``[X | Y]`` sketch streams through
        the fleet, only the local ``s_dim`` system solves here.
        Resolves to the same ``{"coef", "coverage", "missing",
        "degraded"}`` dict."""
        from libskylark_tpu.dist import serve as _dserve
        from libskylark_tpu.dist.algorithms import lstsq_plan

        plan = lstsq_plan(source, s_dim=s_dim, seed=seed, kind=kind,
                          shard_rows=shard_rows)
        return self._submit_dist("dist_lstsq", plan, source,
                                 solve=_dserve.solve_lstsq, **kw)

    def submit_dist_svd(self, source, rank: int, *, s_dim=None,
                        seed: int = 0, kind: str = "jlt",
                        shard_rows: int = 0, **kw) -> Future:
        """Distributed randomized SVD
        (:func:`~libskylark_tpu.dist.algorithms.randomized_svd` as a
        serve endpoint): resolves to the same ``{"singular_values",
        "Vt", "coverage", "missing", "degraded"}`` dict."""
        from libskylark_tpu.dist import serve as _dserve
        from libskylark_tpu.dist.algorithms import svd_plan

        plan = svd_plan(source, rank, s_dim=s_dim, seed=seed,
                        kind=kind, shard_rows=shard_rows)
        return self._submit_dist(
            "dist_svd", plan, source,
            solve=lambda r: _dserve.solve_svd(r, rank),
            digest_extra=(("rank", int(rank)),), **kw)

    # ------------------------------------------------------------------
    # stateful sketch sessions (docs/sessions)
    # ------------------------------------------------------------------

    @property
    def sessions(self):
        """This executor's :class:`~libskylark_tpu.sessions.registry
        .SessionRegistry` (built on first use; every executor in a
        host shares the ``SKYLARK_SESSION_DIR`` root, which is what
        makes drain handoff and crash replay possible)."""
        if self._session_registry is None:
            from libskylark_tpu.sessions import SessionRegistry

            with self._lock:
                if self._session_registry is None:
                    self._session_registry = SessionRegistry(
                        name=self.name)
        return self._session_registry

    def open_sketch_session(self, kind: str, *, n: int, s_dim: int,
                            d: int, seed: int = 0,
                            dtype: str = "float32", targets: int = 0,
                            k: int = 0, lam: float = 1e-3,
                            sigma: float = 1.0,
                            ttl_s: Optional[float] = None,
                            session_id: Optional[str] = None) -> str:
        """Open a stateful sketch session and return its id. ``kind``
        is one of :data:`libskylark_tpu.sessions.KINDS` (``cwt`` /
        ``jlt`` / ``srht`` row-batch appenders, ``isvd`` incremental
        randomized SVD, ``krr`` online KRR); the remaining arguments
        are the :class:`~libskylark_tpu.sessions.SessionSpec` fields.
        Refused (like any intake) on a draining/stopped executor."""
        from libskylark_tpu.sessions import SessionSpec

        with self._lock:
            self._refuse_if_unavailable_locked()
        spec = SessionSpec(kind=kind, n=int(n), s_dim=int(s_dim),
                           d=int(d), seed=int(seed), dtype=str(dtype),
                           targets=int(targets), k=int(k),
                           lam=float(lam), sigma=float(sigma),
                           ttl_s=ttl_s)
        return self.sessions.open(spec, session_id=session_id)

    def session_append(self, session_id: str, X, Y=None,
                       seq: Optional[int] = None,
                       deadline=None) -> Future:
        """Fold one row batch into a session; the returned future
        resolves to ``(seq, rows)`` only after the append is journaled
        (durable) AND folded. Duplicate sequence numbers resolve to
        the current position as a no-op (crash-retry idempotency).
        Shedding (all resolved on the future, never raised here):
        DRAINING refuses; DEGRADED sheds session appends *before*
        interactive traffic (streaming is the best-effort class — the
        client owns the journal replay story, an interactive caller
        does not); an expired ``deadline`` resolves to
        :class:`ServeOverloadedError` without journaling; an evicted
        or unknown session resolves to :class:`~libskylark_tpu.base
        .errors.SessionEvictedError`."""
        fut: Future = Future()
        try:
            with self._lock:
                self._refuse_if_unavailable_locked()
            if self._is_degraded():
                with self._stats_lock:
                    self._counts["session_shed"] += 1
                raise ServeOverloadedError(
                    "executor DEGRADED: session appends shed before "
                    "interactive traffic")
            dl = Deadline.coerce(deadline)
            if dl is not None and dl.expired:
                with self._stats_lock:
                    self._counts["expired"] += 1
                raise ServeOverloadedError(
                    "session append deadline expired before execution")
            out = self.sessions.append(
                session_id, X, Y=Y, seq=seq,
                tags=faults.current_tags())
        except (KeyboardInterrupt, SystemExit):
            raise
        except BaseException as e:  # noqa: BLE001 — resolve, don't leak
            fut.set_exception(e)
            return fut
        fut.set_result(out)
        return fut

    def session_finalize(self, session_id: str) -> Future:
        """Terminal result of a session (the maintained sketch / the
        iSVD factors / the KRR coefficients); the session's artifacts
        are removed and its id tombstoned. Resolves to
        :class:`~libskylark_tpu.base.errors.SessionEvictedError` for
        an evicted/unknown id — never hangs."""
        fut: Future = Future()
        try:
            with self._lock:
                if self._stop:
                    raise RuntimeError(
                        "MicrobatchExecutor is shut down")
            out = self.sessions.finalize(session_id)
        except (KeyboardInterrupt, SystemExit):
            raise
        except BaseException as e:  # noqa: BLE001
            fut.set_exception(e)
            return fut
        fut.set_result(out)
        return fut

    def _checkpoint_sessions(self) -> None:
        """Drain-path hook: checkpoint every live session synchronously
        (journal fsync + accumulator snapshot) so a peer resumes from
        state instead of a full journal replay. No-op when this
        executor never opened a session. Training sessions are
        sessions — a drain checkpoints them here, and the flusher has
        already stopped offering their slices (the draining guard), so
        a resuming peer continues bit-equal from this snapshot."""
        reg = self._session_registry
        if reg is not None:
            reg.checkpoint_all()

    # -- training jobs (docs/training) ----------------------------------

    @property
    def train_jobs(self):
        """This executor's :class:`~libskylark_tpu.train.jobs
        .TrainManager` (built on first use, like :attr:`sessions`)."""
        if self._train_mgr is None:
            from libskylark_tpu.train.jobs import TrainManager

            with self._lock:
                if self._train_mgr is None:
                    self._train_mgr = TrainManager(self)
        return self._train_mgr

    def _wake_flusher(self) -> None:
        """Nudge the flusher: training work became runnable (submit,
        resume, or a requeued slice) and the fast-path submit routes
        never signal ``_work_cv`` for it."""
        with self._lock:
            self._work_cv.notify_all()

    def submit_train_job(self, spec, operands: Optional[dict] = None,
                         *, session_id: Optional[str] = None):
        """Submit a training job (docs/training): the job's operands
        and session open durably here, then its slices run as
        best-effort work in idle scheduler slots. Returns a
        :class:`~libskylark_tpu.train.jobs.TrainJobHandle` whose
        future resolves to the trained model — or raises
        :class:`~libskylark_tpu.base.errors.TrainBudgetExhaustedError`
        with exact progress when the iteration/deadline budget runs
        out first. Refused on a draining/stopped executor; shed (like
        session appends) on a DEGRADED one — training is the
        definitionally-preemptible class."""
        with self._lock:
            self._refuse_if_unavailable_locked()
        if self._is_degraded():
            with self._stats_lock:
                self._counts["train_shed"] += 1
            raise ServeOverloadedError(
                "executor DEGRADED: train submits shed before "
                "interactive traffic")
        return self.train_jobs.submit(spec, operands=operands,
                                      session_id=session_id)

    def resume_train_job(self, session_id: str):
        """Adopt a training job from its on-disk session (drain
        handoff / crash replay) and continue running its slices here.
        Same availability gates as submit."""
        with self._lock:
            self._refuse_if_unavailable_locked()
        return self.train_jobs.resume(session_id)

    def train_job_status(self, session_id: str) -> dict:
        """Progress snapshot of a job live on this executor (raises
        :class:`~libskylark_tpu.base.errors.SessionEvictedError` when
        it is not)."""
        mgr = self._train_mgr
        if mgr is None:
            raise _errors.SessionEvictedError(
                f"train job {session_id!r} is not live on this "
                "replica (no jobs were ever submitted here)")
        return mgr.status(session_id)

    # -- result cache + operand residency (docs/caching) ---------------

    def _bypass_future(self, cls: str, value) -> Future:
        """A request satisfied without a dispatch (pinned result or
        cache hit): an already-resolved future holding the shared
        read-only value, noted in the scheduler's fairness ledger so
        a hot cached class never LOOKS starved next to its goodput."""
        with self._lock:
            self._sched.note_bypass(cls)
        f: Future = Future()
        f.set_result(value)
        return f

    def register_operand(self, A, transform=None, dimension=None,
                         **kw) -> "_rcache.OperandRef":
        """Content-hash ``A``, pin it resident, and return its
        :class:`~libskylark_tpu.engine.resultcache.OperandRef`. Later
        submits may pass the ref as the ``A=`` operand of any dense
        endpoint and the executor substitutes the pinned bytes — no
        re-shipping, and (with the cache on) the request digest is
        identical to submitting the raw bytes, so ref and raw callers
        share one cache line. A fleet Router broadcasts registrations
        so every replica resolves the ref locally (docs/fleet).

        With ``transform=`` the operand is sketched ONCE — an
        ordinary submit: admission, QoS and chaos all apply — and the
        result pinned under the request digest, outside the byte
        quotas: every later ``submit_sketch(transform, ref)`` (or the
        same raw bytes) skips the sketch stage entirely, cache
        evictions notwithstanding. Pins live until
        :meth:`unregister_operand`; re-registering identical bytes is
        a no-op (the digest IS the bytes)."""
        A = np.asarray(A)
        d = _rcache.operand_digest([("A", A)])
        self._residency.pin(d, A)
        ref = _rcache.OperandRef(d)
        if transform is not None:
            value = self.submit(
                "sketch_apply", transform=transform, A=A,
                dimension=dimension, **kw).result()
            derived = derive_request(
                "sketch_apply", pad_floor=self.pad_floor,
                transform=transform, A=A, dimension=dimension)
            rd = request_digest(
                "sketch_apply", derived,
                {"transform": transform, "A": A,
                 "dimension": dimension})
            self._residency.pin_result(rd, value, owner=d)
        return ref

    def unregister_operand(self, ref) -> bool:
        """Unpin a registered operand — and every result pinned with
        it. Returns whether it was resident. In-cache entries for the
        operand's requests survive (they are ordinary quota-bounded
        entries); only the pins go."""
        return self._residency.unpin(_rcache.as_ref(ref).digest)

    def resident_operands(self) -> list:
        """Digests of the operands currently pinned here, sorted."""
        return self._residency.digests()

    def _cache_stats_block(self) -> Optional[dict]:
        """The ``stats()["cache"]`` block: the cache's own counters
        plus the residency sub-block; ``None`` on a cache-off executor
        with nothing pinned (the common case must not grow every
        stats dump)."""
        res = self._residency.stats()
        if self._cache is None:
            if not res["resident_operands"] and not res["pinned_results"]:
                return None
            return {"residency": res}
        blk = self._cache.stats()
        blk["residency"] = res
        return blk

    # -- per-endpoint packing -----------------------------------------

    @staticmethod
    def _key_data(transform) -> np.ndarray:
        """Raw key data of the transform's allocation as a host array —
        submit is on the request hot path; the allocation keeps its
        words (base/context.py), no device involved."""
        return transform.allocation.key_words

    def _prep_sketch(self, transform, A, dimension=None, _derived=None):
        statics, info = _derived or _sketch_statics(
            transform, A, dimension, self.pad_floor)
        A = info["A"]
        ctx = {"dist": info["dist"], "family": info["family"],
               "s_dim": transform.sketch_dim, "rowwise": info["rowwise"],
               "padded": info["padded"], "dtype": str(A.dtype)}
        req = _Request(
            endpoint="sketch_apply",
            arrays={"kd": self._key_data(transform),
                    "scale": np.asarray(getattr(transform, "scale", 1.0),
                                        dtype=A.dtype),
                    "A": A},
            true_shapes={"A": A.shape},
            meta={"padded": info["padded"], "rowwise": info["rowwise"],
                  "s_dim": transform.sketch_dim},
        )
        return statics, statics, ctx, req

    def _prep_cmm(self, transform, A, B, _derived=None):
        statics, info = _derived or _cmm_statics(
            transform, A, B, self.pad_floor)
        A, B = info["A"], info["B"]
        dtype = np.dtype(info["dtype"])
        ctx = {"family": info["family"], "s_dim": info["s_dim"],
               "sparse": info["sparse"], "padded_A": info["padded_A"],
               "padded_B": info["padded_B"],
               "nnz_class": info["nnz_class"], "dtype": info["dtype"]}
        arrays = {"kd": self._key_data(transform),
                  "B": B.astype(dtype, copy=False)}
        if info["sparse"]:
            data, idx, ptr = self._pack_csr(
                A, info["padded_A"][0], info["nnz_class"], dtype)
            arrays.update(data=data, indices=idx, indptr=ptr)
            true_shapes = {"data": (A.nnz,), "B": B.shape}
        else:
            arrays["A"] = A.astype(dtype, copy=False)
            true_shapes = {"A": A.shape, "B": B.shape}
        _CM_SUBMITS.inc_always()
        with self._stats_lock:
            self._counts["cm_submits"] += 1
        req = _Request(
            endpoint="compressed_matmul",
            arrays=arrays,
            true_shapes=true_shapes,
            meta={"m": info["m"], "p": info["p"],
                  "bound": info["bound"],
                  "padded_A": info["padded_A"]},
        )
        return statics, statics, ctx, req

    def _prep_fastfood(self, transform, A, _derived=None):
        statics, info = _derived or _fastfood_statics(
            transform, A, self.pad_floor)
        A = info["A"]
        ctx = {"family": info["family"], "fut": info["fut"],
               "sm_kind": info["sm_kind"], "sm_param": info["sm_param"],
               "n_dim": A.shape[1], "s_dim": transform.sketch_dim,
               "padded": (info["m_pad"], A.shape[1]),
               "dtype": str(A.dtype)}
        req = _Request(
            endpoint="fastfood_features",
            arrays={"kd": self._key_data(transform), "A": A},
            true_shapes={"A": A.shape},
            meta={"padded": (info["m_pad"], A.shape[1]),
                  "m": A.shape[0], "squeeze": info["squeeze"]},
        )
        return statics, statics, ctx, req

    def _prep_solve(self, A, B, transform, method: str = "qr",
                    _derived=None):
        statics, info = _derived or _solve_statics(
            transform, A, B, method, self.pad_floor)
        A, B, n_pad = info["A"], info["B"], info["n_pad"]
        ctx = {"family": info["family"], "s_dim": transform.sketch_dim,
               "method": method}
        req = _Request(
            endpoint="solve_l2_sketched",
            arrays={"kd": self._key_data(transform),
                    "scale": np.asarray(getattr(transform, "scale", 1.0),
                                        dtype=A.dtype),
                    "A": A, "B": B.astype(A.dtype, copy=False)},
            true_shapes={"A": A.shape, "B": B.shape},
            meta={"padded_A": (n_pad, A.shape[1]),
                  "padded_B": (n_pad, B.shape[1]),
                  "squeeze": info["squeeze"]},
        )
        return statics, statics, ctx, req

    @staticmethod
    def _pack_csr(A, rows_pad: int, nnz_class: int, dtype):
        """One request's padded (data, indices, indptr) CSR lanes:
        data/indices zero-padded to the nnz class (value 0.0 at a
        clamped coordinate — exact no-ops through every sparse
        endpoint), indptr monotone-padded with the true nnz to the
        padded row extent (so the in-executable row-id expansion stays
        a valid binary search; docs/serving)."""
        data, indices, indptr = A.csr_parts(dtype)
        nnz = len(data)
        d = np.zeros(int(nnz_class), dtype=dtype)
        d[:nnz] = data
        idx = np.zeros(int(nnz_class), dtype=np.int32)
        idx[:nnz] = indices
        ptr = np.full(int(rows_pad) + 1, nnz, dtype=np.int32)
        ptr[: len(indptr)] = indptr
        return d, idx, ptr

    def _prep_sparse_sketch(self, transform, A, dimension=None,
                            _derived=None):
        statics, info = _derived or _sparse_sketch_statics(
            transform, A, dimension, self.pad_floor)
        A = info["A"]
        dtype = np.dtype(info["dtype"])
        data, idx, ptr = self._pack_csr(
            A, info["padded"][0], info["nnz_class"], dtype)
        ctx = {"dist": info["dist"], "family": info["family"],
               "s_dim": transform.sketch_dim,
               "rowwise": info["rowwise"], "padded": info["padded"],
               "nnz_class": info["nnz_class"], "dtype": info["dtype"]}
        req = _Request(
            endpoint="sparse_sketch_apply",
            arrays={"kd": self._key_data(transform),
                    "scale": np.asarray(
                        getattr(transform, "scale", 1.0), dtype=dtype),
                    "data": data, "indices": idx, "indptr": ptr},
            true_shapes={"data": (A.nnz,)},
            meta={"padded": info["padded"],
                  "rowwise": info["rowwise"],
                  "s_dim": transform.sketch_dim,
                  "shape": A.shape, "nnz": A.nnz},
        )
        return statics, statics, ctx, req

    def _prep_sparse_solve(self, A, B, transform, method: str = "qr",
                           _derived=None):
        statics, info = _derived or _sparse_solve_statics(
            transform, A, B, method, self.pad_floor)
        A, B, n_pad = info["A"], info["B"], info["n_pad"]
        dtype = np.dtype(info["dtype"])
        data, idx, ptr = self._pack_csr(A, n_pad, info["nnz_class"],
                                        dtype)
        ctx = {"family": info["family"],
               "s_dim": transform.sketch_dim, "method": method,
               "padded_A": (n_pad, A.width),
               "nnz_class": info["nnz_class"], "dtype": info["dtype"]}
        req = _Request(
            endpoint="sparse_solve_l2_sketched",
            arrays={"kd": self._key_data(transform),
                    "scale": np.asarray(
                        getattr(transform, "scale", 1.0), dtype=dtype),
                    "data": data, "indices": idx, "indptr": ptr,
                    "B": B.astype(dtype, copy=False)},
            true_shapes={"data": (A.nnz,), "B": B.shape},
            meta={"padded_B": (n_pad, B.shape[1]),
                  "nnz": A.nnz, "squeeze": info["squeeze"]},
        )
        return statics, statics, ctx, req

    def _prep_krr(self, kernel, X_new, X_train, coef, _derived=None):
        import jax.numpy as jnp

        statics, info = _derived or _krr_statics(
            kernel, X_new, X_train, coef, self.pad_floor)
        X_new, squeeze_q, q_pad = (info["X_new"], info["squeeze_q"],
                                   info["q_pad"])
        # model identity is taken from the objects the CALLER holds,
        # before any conversion: a server submitting the same numpy
        # model on every request must keep coalescing into one bucket
        # (the converted arrays would have a fresh id per submit)
        model_ids = (id(X_train), id(coef))
        model_refs = (X_train, coef)
        X_train = jnp.asarray(X_train)
        coef = jnp.asarray(coef)
        squeeze_t = coef.ndim == 1
        if squeeze_t:
            coef = coef[:, None]
        # model identity separates buckets (cohorts must not mix
        # models) but stays OUT of the engine key: two models with the
        # same shapes share one executable. The bucket ctx pins the
        # caller's original objects so their ids stay valid for the
        # bucket's lifetime.
        key = statics + model_ids
        ctx = {"kernel": kernel, "X_train": X_train, "coef": coef,
               "model_refs": model_refs}
        req = _Request(
            endpoint="krr_predict",
            arrays={"Xq": X_new},
            true_shapes={"Xq": X_new.shape},
            meta={"padded": (q_pad, X_new.shape[1]),
                  "q": X_new.shape[0],
                  "squeeze_q": squeeze_q, "squeeze_t": squeeze_t},
        )
        return key, statics, ctx, req

    def _prep_graph_ase(self, A, k, seed=0, iters=2, _derived=None):
        statics, info = _derived or _graph_ase_statics(
            A, k, iters, self.pad_floor)
        S = info["A"]
        dtype = np.dtype(info["dtype"])
        data, idx, ptr = self._pack_csr(
            S, info["padded"][0], info["nnz_class"], dtype)
        ctx = {"k": info["k"], "iters": info["iters"],
               "padded": info["padded"],
               "nnz_class": info["nnz_class"], "dtype": info["dtype"]}
        req = _Request(
            endpoint="graph_ase",
            arrays={"kd": _seed_key_data(int(seed)),
                    "data": data, "indices": idx, "indptr": ptr},
            true_shapes={"data": (S.nnz,)},
            meta={"n": S.height, "k": info["k"]},
        )
        return statics, statics, ctx, req

    def _prep_graph_ppr(self, A, s, alpha=0.85, iters=16,
                        _derived=None):
        statics, info = _derived or _graph_ppr_statics(
            A, s, alpha, iters, self.pad_floor)
        S, s = info["A"], info["s"]
        dtype = np.dtype(info["dtype"])
        data, idx, ptr = self._pack_csr(
            S, info["padded"][0], info["nnz_class"], dtype)
        ctx = {"alpha": info["alpha"], "iters": info["iters"],
               "padded": info["padded"],
               "nnz_class": info["nnz_class"], "dtype": info["dtype"]}
        req = _Request(
            endpoint="graph_ppr",
            arrays={"data": data, "indices": idx, "indptr": ptr,
                    "s": s},
            true_shapes={"data": (S.nnz,)},
            meta={"n": S.height},
        )
        return statics, statics, ctx, req

    def _prep_condest(self, A, steps=8, seed=0, _derived=None):
        statics, info = _derived or _condest_statics(
            A, steps, self.pad_floor)
        A = info["A"]
        ctx = {"steps": info["steps"], "padded": info["padded"],
               "dtype": str(A.dtype)}
        req = _Request(
            endpoint="condest",
            arrays={"kd": _seed_key_data(int(seed)), "A": A},
            true_shapes={"A": A.shape},
            meta={"padded": info["padded"]},
        )
        return statics, statics, ctx, req

    def _prep_lowrank(self, transform_s, transform_t, A, k,
                      _derived=None):
        statics, info = _derived or _lowrank_statics(
            transform_s, transform_t, A, k, self.pad_floor)
        A = info["A"]
        kd_s, sc_s = _lowrank_key_data(transform_s, A.dtype)
        kd_t, sc_t = _lowrank_key_data(transform_t, A.dtype)
        ctx = {"dist": info["dist"], "k": info["k"],
               "s_dim": transform_s.sketch_dim,
               "t_dim": transform_t.sketch_dim,
               "padded": info["padded"]}
        req = _Request(
            endpoint="lowrank",
            arrays={"kd_s": kd_s, "scale_s": sc_s,
                    "kd_t": kd_t, "scale_t": sc_t, "A": A},
            true_shapes={"A": A.shape},
            meta={"padded": info["padded"], "m": A.shape[0],
                  "k": info["k"]},
        )
        return statics, statics, ctx, req

    def _prep_rlsc(self, kernel, X_new, X_train, coef, coding=None,
                   _derived=None):
        import jax.numpy as jnp

        statics, info = _derived or _krr_statics(
            kernel, X_new, X_train, coef, self.pad_floor,
            endpoint="rlsc_predict")
        X_new, squeeze_q, q_pad = (info["X_new"], info["squeeze_q"],
                                   info["q_pad"])
        # same model-identity rule as krr_predict: ids of the CALLER's
        # objects separate buckets, converted arrays live in the ctx
        model_ids = (id(X_train), id(coef))
        model_refs = (X_train, coef)
        X_train = jnp.asarray(X_train)
        coef = jnp.asarray(coef)
        if coef.ndim == 1:
            coef = coef[:, None]
        key = statics + model_ids
        ctx = {"kernel": kernel, "X_train": X_train, "coef": coef,
               "model_refs": model_refs}
        req = _Request(
            endpoint="rlsc_predict",
            arrays={"Xq": X_new},
            true_shapes={"Xq": X_new.shape},
            meta={"padded": (q_pad, X_new.shape[1]),
                  "q": X_new.shape[0], "squeeze_q": squeeze_q,
                  "coding": (list(coding)
                             if coding is not None else None)},
        )
        return key, statics, ctx, req

    # ------------------------------------------------------------------
    # queueing + flushing
    # ------------------------------------------------------------------

    def _refuse_if_unavailable_locked(self) -> None:
        """Reject intake into a draining/stopped executor (caller holds
        ``_lock``). Draining is a load-shed (the caller should
        re-resolve to a healthy replica); a plain shutdown is a
        programming error."""
        if self._draining:
            with self._stats_lock:
                self._counts["shed"] += 1
            raise ServeOverloadedError(
                "executor is draining (preemption) — request refused")
        if self._stop:
            raise RuntimeError("MicrobatchExecutor is shut down")

    def _class_shed_bound(self, cls: str) -> int:
        """DEGRADED shed bound (queued + in-flight requests) of one
        priority class: ``max_queue x the class's shed fraction``,
        scaled by the executor's ``shed_fraction`` argument relative
        to the standard class's *declared default* (0.25) — so the
        pre-QoS ctor knob still moves all three bounds together while
        each ``SKYLARK_QOS_SHED_*`` env knob moves exactly its own
        class (scaling by the LIVE standard value would make the
        standard knob a no-op and inversely rescale the others)."""
        scale = self.shed_fraction / float(
            _env.QOS_SHED_STANDARD.default)
        return max(1, int(self.max_queue
                          * _qtenants.shed_fraction(cls) * scale))

    def _note_shed(self, req: _Request) -> None:
        with self._stats_lock:
            self._counts["shed"] += 1
            self._qos_counts[("shed", req.qos_class, req.tenant)] += 1
        _QOS_SHED.inc(**{"class": req.qos_class,
                         "tenant": req.tenant})

    def _enqueue(self, key, statics, ctx, req, timeout) -> None:
        deadline = time.monotonic() + (timeout if timeout else 0)
        degraded = self._is_degraded()
        cls = req.qos_class
        # the per-class queue is the bucket itself: class rides the
        # bucket KEY (same statics = same executable, the class only
        # separates queues so the deficit scheduler can order them)
        key = tuple(key) + (cls,)
        shed_bound = self._class_shed_bound(cls)
        pressure = _qtenants.PRESSURE_FRACTIONS.get(cls, 1.0)
        with self._lock:
            self._refuse_if_unavailable_locked()
            exposure = self._pending + self._inflight
            if degraded and exposure >= shed_bound:
                # DEGRADED load shed, class-ordered (docs/qos): reject
                # immediately at the class's reduced bound instead of
                # letting callers linger behind a failing flush path —
                # best_effort's bound is the smallest, so it sheds
                # FIRST; interactive's is the largest, so it sheds
                # LAST. The bound counts queued AND in-flight requests
                # — the full-cohort fast path moves work straight to
                # the workers, so a queued-only count would let a
                # max_batch-sized burst bypass the shed
                self._note_shed(req)
                raise ServeOverloadedError(
                    f"load shed: executor DEGRADED and exposure at "
                    f"{exposure} >= {cls} shed bound {shed_bound}")
            if pressure < 1.0 and exposure >= max(
                    1, int(self.max_queue * pressure)):
                # queue-pressure shed: a best_effort storm stops
                # admitting at its fractional bound even on a HEALTHY
                # executor, so it can never fill the queue against
                # standard/interactive traffic (the global-shed
                # unfairness fix — the regression test pins that one
                # best_effort storm never sheds a concurrent
                # interactive request)
                self._note_shed(req)
                raise ServeOverloadedError(
                    f"load shed: {cls} exposure at {exposure} >= "
                    f"pressure bound {int(self.max_queue * pressure)}")
            while self._pending >= self.max_queue:
                wait = deadline - time.monotonic() if timeout else None
                if timeout and wait <= 0:
                    with self._stats_lock:
                        self._counts["rejected"] += 1
                    raise ServeOverloadedError(
                        f"serve queue at bound ({self.max_queue}) for "
                        f"{timeout}s")
                if not self._space_cv.wait(timeout=wait):
                    with self._stats_lock:
                        self._counts["rejected"] += 1
                    raise ServeOverloadedError(
                        f"serve queue at bound ({self.max_queue}) for "
                        f"{timeout}s")
                self._refuse_if_unavailable_locked()
            # a waiter woken by the queue draining may reacquire the
            # lock only AFTER a drain/shutdown completed — appending
            # then would strand the future in a bucket no flusher will
            # ever pop, so the availability check repeats at loop exit
            self._refuse_if_unavailable_locked()
            b = self._buckets.get(key)
            if b is None:
                b = self._buckets[key] = _Bucket(key=key, statics=statics,
                                                ctx=ctx, qos_class=cls)
            b.reqs.append(req)
            self._pending += 1
            self._class_pending[cls] += 1
            _QOS_QUEUE_DEPTH.set(float(self._class_pending[cls]),
                                 **{"class": cls,
                                    "replica": self.name})
            with self._stats_lock:
                self._counts["submitted"] += 1
                self._counts["queued_peak"] = max(
                    self._counts["queued_peak"], self._pending)
                self._qos_counts[("admitted", cls, req.tenant)] += 1
            _QOS_ADMITTED.inc(**{"class": cls, "tenant": req.tenant})
            # full-cohort fast path: hand the cohort straight to the
            # worker queue instead of waking the flusher thread to
            # rediscover it — one less wakeup/context switch on the
            # max_batch steady state (the flusher still owns linger
            # expiry, drain, and partial flushes). The put must stay
            # under the lock: popped outside it, a racing shutdown()
            # could post the worker-poisoning sentinels between our
            # pop and put (the cohort is no longer in _buckets, so
            # the flusher sees nothing left), stranding every future
            # in the cohort behind workers that already exited —
            # under the lock, FIFO orders the work ahead of the
            # sentinels. The queue is unbounded, so put cannot block.
            # ... and it is QoS-gated: a full best_effort cohort must
            # not jump the workers ahead of queued interactive work —
            # the fast path only fires when no strictly-higher class
            # has pending requests (then the scheduler's order is
            # trivially respected); otherwise the flusher's deficit
            # round-robin decides
            ci = _qtenants.CLASSES.index(cls)
            higher_pending = any(
                self._class_pending.get(c, 0) > 0
                for c in _qtenants.CLASSES[:ci])
            work = (self._pop_cohort_locked(key)
                    if (len(b.reqs) >= self._bucket_cap_locked(statics)
                        and not higher_pending)
                    else None)
            if work is None:
                self._work_cv.notify_all()
            else:
                self._sched.charge(cls, len(work[1]))
                self._workq.put((self, work))

    def _bucket_targets_locked(self, statics: tuple) -> tuple:
        """(linger seconds, cohort cap) of one bucket — the static
        config unless the adaptive controller retuned it (caller
        holds ``_lock``)."""
        t = self._qos_targets.get(statics)
        if t is None:
            return self.linger, self.max_batch
        return float(t[0]), int(t[1])

    def _bucket_cap_locked(self, statics: tuple) -> int:
        t = self._qos_targets.get(statics)
        return self.max_batch if t is None else int(t[1])

    def bucket_targets(self, statics) -> tuple:
        """Public (linger_s, batch_cap) view of one bucket's live
        targets (the adaptive controller's read side)."""
        with self._lock:
            return self._bucket_targets_locked(tuple(statics))

    def set_bucket_targets(self, statics, *, linger_s=None,
                           batch_cap=None) -> None:
        """Retune one bucket (the adaptive controller's write side).
        ``batch_cap`` clamps to [1, max_batch] — the compiled
        capacity ladder's roof — and the flusher re-evaluates
        immediately (a shortened linger must fire now, not at the old
        expiry)."""
        statics = tuple(statics)
        with self._lock:
            cur = list(self._bucket_targets_locked(statics))
            if linger_s is not None:
                cur[0] = max(float(linger_s), 0.0)
            if batch_cap is not None:
                cur[1] = max(1, min(int(batch_cap), self.max_batch))
            self._qos_targets[statics] = cur
            self._work_cv.notify_all()

    def _pop_cohort_locked(self, key) -> Optional[tuple]:
        b = self._buckets.get(key)
        if b is None or not b.reqs:
            return None
        cap = self._bucket_cap_locked(b.statics)
        cohort = b.reqs[:cap]
        b.reqs = b.reqs[cap:]
        if not b.reqs:
            del self._buckets[key]
        self._pending -= len(cohort)
        self._class_pending[b.qos_class] -= len(cohort)
        _QOS_QUEUE_DEPTH.set(
            float(max(self._class_pending[b.qos_class], 0)),
            **{"class": b.qos_class, "replica": self.name})
        self._inflight += 1
        self._space_cv.notify_all()
        return (b, cohort)

    def _cohort_done_locked(self) -> None:
        self._inflight -= 1
        if self._pending == 0 and self._inflight == 0:
            self._idle_cv.notify_all()

    def _flusher_loop(self) -> None:
        """Linger expiry + weighted-fair dispatch (docs/qos): ready
        cohorts (full, lingered out, or flushed by drain/stop) are
        grouped by priority class and the deficit scheduler picks
        which class dispatches next — the replacement for the pre-QoS
        dict-order drain. Within a class, the oldest bucket goes
        first (FIFO per class). Linger and cohort caps are
        per-bucket: the adaptive controller's targets, falling back
        to the static config."""
        while True:
            work = None
            with self._lock:
                if self._stop and not self._buckets:
                    break
                now = time.monotonic()
                wait = None
                ready: dict = {}          # class -> oldest ready key
                for key in list(self._buckets):
                    b = self._buckets[key]
                    linger, cap = self._bucket_targets_locked(b.statics)
                    full = len(b.reqs) >= cap
                    expired = now - b.oldest >= linger
                    if full or expired or self._stop or self._draining:
                        prev = ready.get(b.qos_class)
                        if (prev is None or b.oldest
                                < self._buckets[prev].oldest):
                            ready[b.qos_class] = key
                    else:
                        w = b.oldest + linger - now
                        wait = w if wait is None else min(wait, w)
                # training slices ride the same scheduler pass as
                # best-effort backlog (docs/training) — but only when
                # no higher class has pending work: idle slots feed
                # training, a single interactive request displaces it
                # at the next slice boundary
                train_mgr = self._train_mgr
                if (train_mgr is not None and not self._stop
                        and not self._draining
                        and train_mgr.has_runnable()):
                    higher = any(
                        self._class_pending.get(c, 0) > 0
                        for c in _qtenants.CLASSES
                        if c != _qtenants.BEST_EFFORT)
                    if higher:
                        train_mgr.note_deferred()
                    elif _qtenants.BEST_EFFORT not in ready:
                        ready[_qtenants.BEST_EFFORT] = _TRAIN_KEY
                    if _TRAIN_KEY not in ready.values():
                        # displaced (or a real best-effort bucket won
                        # the slot): the fast-path submit does not
                        # signal _work_cv, so poll for the idle window
                        # instead of lingering indefinitely
                        w = 0.05
                        wait = w if wait is None else min(wait, w)
                if ready:
                    backlog = {
                        c: (self._class_pending.get(c, 0)
                            + (1 if ready[c] is _TRAIN_KEY else 0))
                        for c in ready}

                    def cost(c):
                        if ready[c] is _TRAIN_KEY:
                            return 1
                        b0 = self._buckets[ready[c]]
                        return min(len(b0.reqs),
                                   self._bucket_cap_locked(b0.statics))

                    cls = self._sched.next_class(backlog, cost)
                    if cls is not None and ready[cls] is _TRAIN_KEY:
                        job = train_mgr.claim_next()
                        if job is not None:
                            self._inflight += 1
                            self._sched.charge(cls, 1)
                            work = (_TRAIN_KEY, job)
                    elif cls is not None:
                        work = self._pop_cohort_locked(ready[cls])
                        if work is not None:
                            self._sched.charge(cls, len(work[1]))
                if work is None:
                    if self._stop:
                        continue
                    self._work_cv.wait(timeout=wait)
                    continue
            self._workq.put((self, work))
        for _ in self._workers:
            self._workq.put(None)

    def _dispatch_cohort(self, bucket_obj, cohort) -> None:
        """Run one popped cohort through the isolation-retrying
        executor, with the last-resort exception fan and the in-flight
        bookkeeping — the single dispatch path shared by the worker
        threads and the synchronous :meth:`flush`."""
        if bucket_obj is _TRAIN_KEY:
            # a training slice: ``cohort`` is the claimed job. The
            # manager resolves every outcome on the job future or
            # requeues — no client futures to fan an exception to.
            try:
                mgr = self._train_mgr
                if mgr is not None:
                    mgr.run_slice(cohort)
            finally:
                with self._lock:
                    self._cohort_done_locked()
            return
        try:
            self._run_cohort(bucket_obj, cohort)
        except (KeyboardInterrupt, SystemExit):
            raise       # a synchronous flush() on the main thread must
            #             let Ctrl-C stop the process
        except BaseException as e:  # noqa: BLE001 — last-resort fan
            for r in cohort:
                if not r.future.done():
                    r.future.set_exception(e)
            with self._stats_lock:
                self._counts["failed"] += len(cohort)
        finally:
            with self._lock:
                self._cohort_done_locked()


    def flush(self) -> None:
        """Synchronously flush every pending cohort from the calling
        thread (tests/bench warmup; normal traffic never needs it).
        Returns only after every in-flight cohort has resolved too —
        the full-cohort fast path hands work to the worker threads at
        submit time, and "synchronous" must cover those (a chaos test
        activates a fault plan around submit+flush and the flush
        attempts must execute inside the plan's extent)."""
        while True:
            with self._lock:
                work = None
                for key in list(self._buckets):
                    work = self._pop_cohort_locked(key)
                    if work:
                        break
            if not work:
                break
            self._dispatch_cohort(*work)
        with self._lock:
            while self._inflight:
                self._idle_cv.wait(timeout=0.1)

    # ------------------------------------------------------------------
    # failure isolation: bisection converges on the poison request
    # ------------------------------------------------------------------

    def _drop_expired(self, cohort: list) -> list:
        """Resolve deadline-expired requests to ServeOverloadedError and
        return the survivors. Runs before EVERY execution attempt, so an
        expired request never occupies a lane or an isolation retry."""
        live = []
        expired = 0
        for r in cohort:
            if r.deadline is not None and r.deadline.expired:
                expired += 1
                if not r.future.done():
                    r.future.set_exception(ServeOverloadedError(
                        f"request deadline expired after "
                        f"{time.monotonic() - r.t_submit:.3f}s in queue"))
            else:
                live.append(r)
        if expired:
            with self._stats_lock:
                self._counts["expired"] += expired
        return live

    def _run_cohort(self, b: _Bucket, cohort: list, depth: int = 0) -> None:
        """Execute a cohort; on failure, bisect to isolate the poison.

        A failed flush splits the cohort in half and re-executes each
        half (lane invariance keeps the re-coalesced results bit-equal
        to what the full flush would have produced), recursing until the
        failure pins to a single request — only THAT future gets the
        exception; every cohort-mate resolves successfully. Worst case
        per request: ``ceil(log2(cohort))`` ≤ ``log2(max_batch)`` retry
        levels, ~2× the flush work of the clean path for the one
        afflicted cohort. Transient faults (that pass on re-execution)
        cost one split and poison nobody.
        """
        cohort = self._drop_expired(cohort)
        if not cohort:
            return
        # Telemetry (docs/observability): the root attempt is the
        # "serve.flush" span, parented — across the thread hop — under
        # the first request's submit span, so the request id minted at
        # submit() is on this span; bisection halves recurse INSIDE the
        # span's extent, so every "serve.isolation" retry nests under
        # it (and inherits the request id) with its own half's ids in
        # ``request_ids``. Disabled telemetry: one no-op branch.
        span_cm = _trace.span(
            "serve.flush" if depth == 0 else "serve.isolation",
            parent=cohort[0].tctx if depth == 0 else None)
        with span_cm as sp:
            if sp is not None:
                sp.set_attr("endpoint", b.statics[0])
                sp.set_attr("cohort", len(cohort))
                sp.set_attr("depth", depth)
                sp.set_attr("request_ids",
                            [r.request_id for r in cohort
                             if r.request_id is not None])
            try:
                self._execute(b, cohort)
            except (KeyboardInterrupt, SystemExit):
                raise   # cancellation stops the process — it must not
                #         be "isolated" into some request's future
            except BaseException as e:  # noqa: BLE001 — taxonomy-agnostic
                if sp is not None:
                    sp.status = "error"
                    sp.error = repr(e)
                with self._stats_lock:
                    self._counts["flush_failures"] += 1
                    if depth == 0:
                        # health evidence is per INCIDENT (root attempts
                        # only): a bisection records log2(B)+1 correlated
                        # failures, which would let ONE poison request in
                        # a quiet executor flip the state to DEGRADED and
                        # shed healthy traffic — contradicting "fails
                        # alone"
                        self._health.append(1.0)
                if depth == 0:
                    self._maybe_publish_state()
                if len(cohort) == 1:
                    r = cohort[0]
                    if not r.future.done():
                        r.future.set_exception(e)
                    with self._stats_lock:
                        self._counts["failed"] += 1
                        self._counts["poisoned"] += 1
                    return
                mid = len(cohort) // 2
                with self._stats_lock:
                    self._counts["isolation_retries"] += 2
                    self._counts["isolation_depth_peak"] = max(
                        self._counts["isolation_depth_peak"], depth + 1)
                self._run_cohort(b, cohort[:mid], depth + 1)
                self._run_cohort(b, cohort[mid:], depth + 1)
            else:
                if depth == 0:
                    with self._stats_lock:
                        self._health.append(0.0)
                    self._maybe_publish_state()

    def _is_degraded(self) -> bool:
        with self._stats_lock:
            n = len(self._health)
            if n < 4:
                return False
            return sum(self._health) / n >= self.degraded_threshold

    # ------------------------------------------------------------------
    # cohort execution: pad → stack → one vmapped executable → unpad
    # ------------------------------------------------------------------

    def _compiled_for(self, b: _Bucket):
        # keyed on the engine statics, NOT the full bucket key: model
        # ids separate buckets only to keep cohorts unmixed, but every
        # same-shaped model shares one wrapper (and one executable) —
        # and the dict stays bounded by the shape-class space instead of
        # growing with model churn
        with self._compiled_lock:
            cf = self._compiled.get(b.statics)
            if cf is None:
                cf = self._build_batched(b)
                self._compiled[b.statics] = cf
            return cf

    # ------------------------------------------------------------------
    # flush-kernel selection (docs/performance, "Serve-bucket kernel
    # selection"): which program serves a (bucket, capacity) flush —
    # the endpoint's batched Pallas kernel or the vmapped XLA path.
    # Precedence: executor ``kernel=`` argument > SKYLARK_SERVE_KERNEL
    # env > default (xla). A pallas intent that fails host-side
    # qualification declines (reason counted) back to xla.
    # ------------------------------------------------------------------

    def _qualify_serve_kernel(self, b: _Bucket):
        """Host-side (ok, why) qualification of the bucket's batched
        kernel at the padded lane class — run BEFORE a pallas choice is
        committed to the executable key, so an unqualified bucket keys
        (and compiles) the XLA program it will actually run."""
        ctx = b.ctx
        endpoint = b.statics[0]
        interpret = not _pallas_native()
        if _lane_program_only(b.statics):
            return False, "no batched kernel: the lane program serves"
        if endpoint == "fastfood_features":
            from libskylark_tpu.sketch import pallas_fastfood

            return pallas_fastfood.serve_qualify(
                ctx["n_dim"], ctx["s_dim"], ctx["padded"][0],
                ctx["dtype"], ctx["fut"], interpret=interpret)
        padded, rowwise = ctx["padded"], ctx["rowwise"]
        n = padded[1] if rowwise else padded[0]
        m = padded[0] if rowwise else padded[1]
        if ctx["family"] == "CWT":
            from libskylark_tpu.sketch import pallas_hash

            return pallas_hash.qualify(ctx["s_dim"], n, m,
                                       ctx["dtype"],
                                       interpret=interpret)
        from libskylark_tpu.sketch import pallas_dense

        return pallas_dense.serve_qualify(
            ctx["dist"], ctx["s_dim"], n, m, ctx["dtype"],
            interpret=interpret)

    def _resolve_flush_kernel(self, b: _Bucket, capacity: int) -> tuple:
        """``(backend, source, declined)`` for one (bucket, capacity)
        flush. Memoized for the executor's life: the engine key_fn
        re-resolves on every call (the kernel choice is a STATIC of the
        executable key — the r7 jit-leak gate's zero-recompile contract
        holds because this is a dict hit with a stable answer).
        ``declined`` is the reason slug when a pallas intent fell back
        to xla (the ``by_reason`` counter), else None."""
        if b.statics[0] not in _KERNEL_ENDPOINTS:
            return ("xla", "endpoint", None)
        memo_key = (b.statics, int(capacity))
        got = self._kernel_memo.get(memo_key)
        if got is not None:
            return got
        if self.kernel is not None:
            choice, source = self.kernel, "arg"
        elif (pin := _serve_kernel_env()) is not None:
            choice, source = pin, "env"
        else:
            choice, source = "xla", "default"
        out = (choice, source, None)
        if choice == "pallas":
            ok, why = self._qualify_serve_kernel(b)
            if not ok:
                out = ("xla", source, _decline_slug(why))
        self._kernel_memo[memo_key] = out
        return out

    def _kernel_key_token(self, b: _Bucket, capacity: int) -> str:
        """The kernel-choice static the flush executable is keyed on:
        the resolved backend's name."""
        return self._resolve_flush_kernel(b, capacity)[0]

    def _poison_kernel(self, b: _Bucket, capacity: int,
                       reason: str) -> None:
        """Force (bucket, capacity) onto the XLA path for the rest of
        the executor's life — the compile-time Mosaic-rejection
        fallback (a rejection is a decline, not an outage)."""
        self._kernel_memo[(b.statics, int(capacity))] = (
            "xla", "fallback", reason)

    def restore_kernel_choice(self, statics, capacity: int,
                              token: str) -> bool:
        """Seed the flush-kernel memo for one (bucket statics,
        capacity) with a warmup-pack-recorded decision — the kernel
        choice ships *with* the compiled artifact instead of being
        re-resolved (host qualification) per process (docs/performance,
        "Persistent AOT artifacts & warmup packs"). ``token`` is a
        backend's name; returns whether the decision was restored (any
        other token, a pre-schema-2 ``pallas/mt128/f32`` among them,
        falls back to live resolution — a decline, not an error). An
        explicit pin — executor ``kernel=`` argument or
        ``SKYLARK_SERVE_KERNEL`` — outranks the pack: the memo is
        consulted before either, so seeding it would silently override
        the operator's pin; decline instead and let live resolution
        honor the precedence."""
        if self.kernel is not None or _serve_kernel_env() is not None:
            return False
        statics = tuple(statics)
        if token not in _KERNEL_BACKENDS or (
                token != "xla" and _lane_program_only(statics)):
            return False
        self._kernel_memo[(statics, int(capacity))] = (token, "pack", None)
        return True

    def load_warmup_pack(self, pack_dir: str, *,
                         strict: bool = False) -> dict:
        """Boot this executor from a warmup pack: load every packed
        executable into the process executable cache and restore the
        packed per-bucket kernel decisions into this executor's memo
        (:func:`libskylark_tpu.engine.warmup.load_pack`). Call before
        accepting traffic; returns the loader's report."""
        from libskylark_tpu.engine import warmup as _warmup

        return _warmup.load_pack(pack_dir, executors=(self,),
                                 strict=strict)

    def _build_batched(self, b: _Bucket):
        import jax

        statics = b.statics
        ctx = b.ctx
        endpoint = statics[0]
        # kernel-selecting endpoints key their executables on the
        # resolved kernel-choice token too: the choice is derived from
        # the SAME (bucket, capacity) pair at key time and at trace
        # time, so the key can never disagree with the program it names
        def serve_key(*a):
            return statics + (
                "kernel", self._kernel_key_token(b, int(a[0].shape[0])))

        if endpoint == "sketch_apply":
            s_dim, rowwise = ctx["s_dim"], ctx["rowwise"]
            if ctx["family"] == "CWT":
                from libskylark_tpu.sketch.hash import cwt_serve_apply

                def one(kd, scale, A):
                    return cwt_serve_apply(kd, A, s_dim=s_dim,
                                           rowwise=rowwise)
            elif ctx["family"] == "SRHT":
                from libskylark_tpu.sketch.fjlt import srht_serve_apply

                # the SRHT's scaling is fully determined by (n, s_dim)
                # inside the program; the scale lane rides unread for
                # arity uniformity with the other sketch families
                def one(kd, scale, A):
                    return srht_serve_apply(kd, A, s_dim=s_dim,
                                            rowwise=rowwise)
            else:
                from libskylark_tpu.sketch.dense import serve_apply

                dist = ctx["dist"]

                def one(kd, scale, A):
                    return serve_apply(kd, scale, A, dist=dist,
                                       s_dim=s_dim, rowwise=rowwise)

            inner = jax.vmap(one)

            def batched_sketch(kd, scale, A):
                backend, _src, _why = self._resolve_flush_kernel(
                    b, int(A.shape[0]))
                if backend == "pallas":
                    interpret = not _pallas_native()
                    if ctx["family"] == "CWT":
                        from libskylark_tpu.sketch import pallas_hash

                        # exact accumulation under the interpreter:
                        # bit-equal to the scatter (the CI bit-equality
                        # leg); the MXU mode serves on real silicon
                        return pallas_hash.cwt_apply_batched(
                            kd, A, s_dim=s_dim, rowwise=rowwise,
                            accum="exact" if interpret else "mxu",
                            interpret=interpret)
                    from libskylark_tpu.sketch import pallas_dense

                    return pallas_dense.serve_batched_apply(
                        kd, scale, A, dist=ctx["dist"], s_dim=s_dim,
                        rowwise=rowwise, interpret=interpret)
                return inner(kd, scale, A)

            return engine_compile(
                batched_sketch, name="serve.sketch_apply",
                donate_argnums=(0, 1, 2),
                key_fn=serve_key)
        if endpoint == "fastfood_features":
            from libskylark_tpu.sketch.frft import fastfood_serve_apply

            n_dim, s_dim = ctx["n_dim"], ctx["s_dim"]
            fut, sm_kind, sm_param = (ctx["fut"], ctx["sm_kind"],
                                      ctx["sm_param"])

            def one_ff(kd, A):
                return fastfood_serve_apply(
                    kd, A, n_dim=n_dim, s_dim=s_dim, fut=fut,
                    sm_kind=sm_kind, sm_param=sm_param)

            inner_ff = jax.vmap(one_ff)

            def batched_fastfood(kd, A):
                backend, _src, _why = self._resolve_flush_kernel(
                    b, int(A.shape[0]))
                if backend == "pallas":
                    from libskylark_tpu.sketch import pallas_fastfood

                    return pallas_fastfood.serve_features_batched(
                        kd, A, n_dim=n_dim, s_dim=s_dim, fut=fut,
                        sm_kind=sm_kind, sm_param=sm_param,
                        interpret=not _pallas_native())
                return inner_ff(kd, A)

            return engine_compile(
                batched_fastfood, name="serve.fastfood_features",
                donate_argnums=(0, 1),
                key_fn=serve_key)
        if endpoint == "sparse_sketch_apply":
            from libskylark_tpu.sketch import sparse_serve as _ssrv

            s_dim, rowwise = ctx["s_dim"], ctx["rowwise"]
            padded = ctx["padded"]
            if ctx["family"] == "CWT":
                def one_sp(kd, scale, data, indices, indptr):
                    return _ssrv.cwt_sparse_serve_apply(
                        kd, data, indices, indptr, s_dim=s_dim,
                        rowwise=rowwise, shape=padded)
            else:
                dist = ctx["dist"]

                def one_sp(kd, scale, data, indices, indptr):
                    return _ssrv.dense_sparse_serve_apply(
                        kd, scale, data, indices, indptr, dist=dist,
                        s_dim=s_dim, rowwise=rowwise, shape=padded)

            # no batched kernel: the flush is the vmapped lane program
            inner_sp = jax.vmap(one_sp)

            def batched_sparse(kd, scale, data, indices, indptr):
                return inner_sp(kd, scale, data, indices, indptr)

            return engine_compile(
                batched_sparse, name="serve.sparse_sketch_apply",
                donate_argnums=(0, 1, 2, 3, 4),
                key_fn=serve_key)
        if endpoint == "compressed_matmul":
            # always-xla flush (like the solve endpoints): the two
            # family sketch programs each run panel-free already, and
            # the closing (m, s)x(s, p) gemm is XLA's bread and butter
            family, s_dim = ctx["family"], ctx["s_dim"]
            padded_a = ctx["padded_A"]
            if family == "SRHT":
                from libskylark_tpu.sketch.fjlt import srht_serve_apply

                def skA_dense(kd, A):
                    return srht_serve_apply(kd, A, s_dim=s_dim,
                                            rowwise=True)

                def skB(kd, B):
                    return srht_serve_apply(kd, B, s_dim=s_dim,
                                            rowwise=False)
            else:
                from libskylark_tpu.sketch.hash import cwt_serve_apply

                def skA_dense(kd, A):
                    return cwt_serve_apply(kd, A, s_dim=s_dim,
                                           rowwise=True)

                def skB(kd, B):
                    return cwt_serve_apply(kd, B, s_dim=s_dim,
                                           rowwise=False)

            if ctx["sparse"]:
                from libskylark_tpu.sketch import sparse_serve as _ssrv

                if family == "CWT":
                    # sketch straight off the padded CSR lanes (the
                    # r18 packing) — no densify
                    def one_cm(kd, data, indices, indptr, B):
                        SA = _ssrv.cwt_sparse_serve_apply(
                            kd, data, indices, indptr, s_dim=s_dim,
                            rowwise=True, shape=padded_a)
                        return SA @ skB(kd, B)
                else:
                    # the SRHT has no CSR program (the FWHT mixes
                    # every coordinate); densify in-executable, the
                    # same policy the dense-family sparse flush uses
                    def one_cm(kd, data, indices, indptr, B):
                        Ad = _ssrv.scatter_dense(
                            data, indices, indptr, shape=padded_a)
                        return skA_dense(kd, Ad) @ skB(kd, B)

                inner_cm = jax.vmap(one_cm)

                def batched_cmm(kd, data, indices, indptr, B):
                    return inner_cm(kd, data, indices, indptr, B)

                return engine_compile(
                    batched_cmm, name="serve.compressed_matmul",
                    donate_argnums=(0, 1, 2, 3, 4),
                    key_fn=lambda *a: statics)

            def one_cm(kd, A, B):
                return skA_dense(kd, A) @ skB(kd, B)

            inner_cm = jax.vmap(one_cm)

            def batched_cmm(kd, A, B):
                return inner_cm(kd, A, B)

            return engine_compile(
                batched_cmm, name="serve.compressed_matmul",
                donate_argnums=(0, 1, 2),
                key_fn=lambda *a: statics)
        if endpoint == "sparse_solve_l2_sketched":
            from libskylark_tpu.sketch import sparse_serve as _ssrv

            family, s_dim, method = (ctx["family"], ctx["s_dim"],
                                     ctx["method"])
            padded_a = ctx["padded_A"]

            def one_sps(kd, scale, data, indices, indptr, B):
                return _ssrv.sparse_solve_serve(
                    kd, scale, data, indices, indptr, B,
                    sketch_type=family, s_dim=s_dim, method=method,
                    shape=padded_a)

            inner_sps = jax.vmap(one_sps)

            def batched_sparse_solve(kd, scale, data, indices, indptr,
                                     B):
                return inner_sps(kd, scale, data, indices, indptr, B)

            return engine_compile(
                batched_sparse_solve,
                name="serve.sparse_solve_l2_sketched",
                donate_argnums=(0, 1, 2, 3, 4, 5),
                key_fn=lambda *a: statics)
        if endpoint == "solve_l2_sketched":
            from libskylark_tpu.algorithms.regression import (
                sketched_solve_serve,
            )

            family, s_dim, method = (ctx["family"], ctx["s_dim"],
                                     ctx["method"])

            def one(kd, scale, A, B):
                return sketched_solve_serve(
                    kd, scale, A, B, sketch_type=family, s_dim=s_dim,
                    method=method)

            inner = jax.vmap(one)

            def batched_solve(kd, scale, A, B):
                return inner(kd, scale, A, B)

            return engine_compile(
                batched_solve, name="serve.solve_l2_sketched",
                donate_argnums=(0, 1, 2, 3),
                key_fn=lambda *a: statics)
        if endpoint == "graph_ase":
            from libskylark_tpu.ml.graph import ase_serve_apply

            k_dim, g_iters = ctx["k"], ctx["iters"]
            g_padded = ctx["padded"]

            def one_ga(kd, data, indices, indptr):
                return ase_serve_apply(kd, data, indices, indptr,
                                       k=k_dim, iters=g_iters,
                                       shape=g_padded)

            inner_ga = jax.vmap(one_ga)

            # capacity-1 flushes run the PLAIN single-lane program
            # (shape is static at trace time): the vmapped batch-1
            # lowering of a deep linalg chain can differ from the
            # unbatched program by an f32 ulp, and the capacity-1
            # dispatch is the bit-equality reference the other
            # capacities (whose lanes XLA lowers like the plain
            # program) are pinned against
            def batched_graph_ase(kd, data, indices, indptr):
                if kd.shape[0] == 1:
                    return one_ga(kd[0], data[0], indices[0],
                                  indptr[0])[None]
                return inner_ga(kd, data, indices, indptr)

            return engine_compile(
                batched_graph_ase, name="serve.graph_ase",
                donate_argnums=(0, 1, 2, 3),
                key_fn=lambda *a: statics)
        if endpoint == "graph_ppr":
            from libskylark_tpu.ml.graph import ppr_serve_apply

            p_alpha, p_iters = ctx["alpha"], ctx["iters"]
            p_padded = ctx["padded"]

            def one_pp(data, indices, indptr, s):
                return ppr_serve_apply(data, indices, indptr, s,
                                       alpha=p_alpha, iters=p_iters,
                                       shape=p_padded)

            inner_pp = jax.vmap(one_pp)

            def batched_graph_ppr(data, indices, indptr, s):
                if data.shape[0] == 1:   # see batched_graph_ase
                    return one_pp(data[0], indices[0], indptr[0],
                                  s[0])[None]
                return inner_pp(data, indices, indptr, s)

            return engine_compile(
                batched_graph_ppr, name="serve.graph_ppr",
                donate_argnums=(0, 1, 2, 3),
                key_fn=lambda *a: statics)
        if endpoint == "condest":
            from libskylark_tpu.nla.condest import condest_serve_apply

            c_steps = ctx["steps"]

            def one_ce(kd, A):
                return condest_serve_apply(kd, A, steps=c_steps)

            # statically unrolled lanes, NOT vmap: the deep Golub-
            # Kahan recurrence (dot-reorthogonalization chain) is not
            # lane-bitwise under XLA's batched lowering, and the
            # capacity-1 bit-equality contract outranks trace size
            # for this tiny program (k+1 short vectors per lane)
            def batched_condest(kd, A):
                return jax.numpy.stack(
                    [one_ce(kd[i], A[i]) for i in range(A.shape[0])])

            return engine_compile(
                batched_condest, name="serve.condest",
                donate_argnums=(0, 1),
                key_fn=lambda *a: statics)
        if endpoint == "lowrank":
            from libskylark_tpu.nla.lowrank import lowrank_serve_apply

            lr_dist, lr_k = ctx["dist"], ctx["k"]
            lr_s, lr_t = ctx["s_dim"], ctx["t_dim"]

            def one_lr(kd_s, sc_s, kd_t, sc_t, A):
                return lowrank_serve_apply(kd_s, sc_s, kd_t, sc_t, A,
                                           dist=lr_dist, s=lr_s,
                                           t=lr_t, k=lr_k)

            inner_lr = jax.vmap(one_lr)

            def batched_lowrank(kd_s, sc_s, kd_t, sc_t, A):
                if A.shape[0] == 1:      # see batched_graph_ase
                    return one_lr(kd_s[0], sc_s[0], kd_t[0],
                                  sc_t[0], A[0])[None]
                return inner_lr(kd_s, sc_s, kd_t, sc_t, A)

            return engine_compile(
                batched_lowrank, name="serve.lowrank",
                donate_argnums=(0, 1, 2, 3, 4),
                key_fn=lambda *a: statics)
        if endpoint == "rlsc_predict":
            # classification twin of krr_predict: model operands
            # broadcast, never donated
            from libskylark_tpu.ml.rlsc import rlsc_predict_kernel

            r_kernel = ctx["kernel"]

            def one_rl(Xq, X_train, coef):
                return rlsc_predict_kernel(r_kernel, Xq, X_train, coef)

            inner_rl = jax.vmap(one_rl, in_axes=(0, None, None))

            def batched_rlsc(Xq, X_train, coef):
                return inner_rl(Xq, X_train, coef)

            return engine_compile(
                batched_rlsc, name="serve.rlsc_predict",
                donate_argnums=(0,),
                key_fn=lambda *a: statics)
        # krr_predict: model operands broadcast, never donated (they
        # are bucket-lived and re-read by every flush)
        from libskylark_tpu.ml.krr import krr_predict_kernel

        kernel = ctx["kernel"]

        def one(Xq, X_train, coef):
            return krr_predict_kernel(kernel, Xq, X_train, coef)

        inner = jax.vmap(one, in_axes=(0, None, None))

        def batched_krr(Xq, X_train, coef):
            return inner(Xq, X_train, coef)

        return engine_compile(
            batched_krr, name="serve.krr_predict", donate_argnums=(0,),
            key_fn=lambda *a: statics)

    def _device_put_batch(self, arr):
        """Shard a stacked (capacity, ...) host buffer's batch dimension
        across the executor mesh (no-op without one)."""
        if self._mesh is None:
            return arr
        import jax
        from jax.sharding import NamedSharding, PartitionSpec

        spec = PartitionSpec(self._batch_axis,
                             *([None] * (arr.ndim - 1)))
        return jax.device_put(arr, NamedSharding(self._mesh, spec))

    def _device_put_replicated(self, arr):
        if self._mesh is None:
            return arr
        import jax
        from jax.sharding import NamedSharding, PartitionSpec

        return jax.device_put(
            arr, NamedSharding(self._mesh, PartitionSpec()))

    def _execute(self, b: _Bucket, cohort: list) -> None:
        k = len(cohort)
        capacity = bucketing.capacity_class(k, self.max_batch,
                                            multiple=self._ndev)
        endpoint = b.statics[0]
        # chaos seam: fires per execution ATTEMPT with the cohort's tag
        # union, so a tag-pinned plan fails exactly the attempts that
        # contain the poison request — which is what bisection needs
        faults.check("serve.flush",
                     tags=frozenset().union(*(r.tags for r in cohort)),
                     detail=f"{endpoint} k={k} cap={capacity}")
        # kernel selection: resolved once per flush (memo hit after the
        # first), counted per flush so operators see live which buckets
        # are on the fast path and WHY the others are not
        kernel_backend, kdeclined = "xla", None
        if endpoint in _KERNEL_ENDPOINTS:
            kernel_backend, _ks, kdeclined = \
                self._resolve_flush_kernel(b, capacity)
        if endpoint == "sketch_apply":
            padded = cohort[0].meta["padded"]
            args = self._stack_common(cohort, padded, capacity,
                                      with_b=False)
            primary = "A"
        elif endpoint == "fastfood_features":
            padded = cohort[0].meta["padded"]
            dtype = cohort[0].arrays["A"].dtype
            kd = bucketing.stack_pad([r.arrays["kd"] for r in cohort],
                                     (2,), capacity, np.uint32)
            Astk = bucketing.stack_pad([r.arrays["A"] for r in cohort],
                                       padded, capacity, dtype)
            args = (self._device_put_batch(kd),
                    self._device_put_batch(Astk))
            primary = "A"
        elif endpoint == "solve_l2_sketched":
            padded = cohort[0].meta["padded_A"]
            args = self._stack_common(
                cohort, padded, capacity, with_b=True,
                padded_b=cohort[0].meta["padded_B"])
            primary = "A"
        elif endpoint in ("sparse_sketch_apply",
                          "sparse_solve_l2_sketched"):
            # CSR lanes: every request in the bucket shares the nnz
            # class (a bucket static), so the (data, indices, indptr)
            # arrays are uniform; the nnz lane extent is the waste
            # accounting's "padded shape"
            nnz_pad = cohort[0].arrays["data"].shape[0]
            padded = (nnz_pad,)
            dtype = cohort[0].arrays["data"].dtype
            ptr_len = cohort[0].arrays["indptr"].shape[0]
            args = [
                self._device_put_batch(bucketing.stack_pad(
                    [r.arrays["kd"] for r in cohort], (2,), capacity,
                    np.uint32)),
                self._device_put_batch(bucketing.stack_pad(
                    [np.asarray(r.arrays["scale"]).reshape(())
                     for r in cohort], (), capacity, dtype)),
                self._device_put_batch(bucketing.stack_pad(
                    [r.arrays["data"] for r in cohort], (nnz_pad,),
                    capacity, dtype)),
                self._device_put_batch(bucketing.stack_pad(
                    [r.arrays["indices"] for r in cohort], (nnz_pad,),
                    capacity, np.int32)),
                self._device_put_batch(bucketing.stack_pad(
                    [r.arrays["indptr"] for r in cohort], (ptr_len,),
                    capacity, np.int32)),
            ]
            if endpoint == "sparse_solve_l2_sketched":
                args.append(self._device_put_batch(bucketing.stack_pad(
                    [r.arrays["B"] for r in cohort],
                    cohort[0].meta["padded_B"], capacity, dtype)))
            args = tuple(args)
            primary = "data"
        elif endpoint == "compressed_matmul":
            dtype = cohort[0].arrays["B"].dtype
            kd = bucketing.stack_pad([r.arrays["kd"] for r in cohort],
                                     (2,), capacity, np.uint32)
            args = [self._device_put_batch(kd)]
            if b.ctx["sparse"]:
                nnz_pad = cohort[0].arrays["data"].shape[0]
                padded = (nnz_pad,)
                ptr_len = cohort[0].arrays["indptr"].shape[0]
                args += [
                    self._device_put_batch(bucketing.stack_pad(
                        [r.arrays["data"] for r in cohort],
                        (nnz_pad,), capacity, dtype)),
                    self._device_put_batch(bucketing.stack_pad(
                        [r.arrays["indices"] for r in cohort],
                        (nnz_pad,), capacity, np.int32)),
                    self._device_put_batch(bucketing.stack_pad(
                        [r.arrays["indptr"] for r in cohort],
                        (ptr_len,), capacity, np.int32)),
                ]
                primary = "data"
            else:
                padded = b.ctx["padded_A"]
                args.append(self._device_put_batch(bucketing.stack_pad(
                    [r.arrays["A"] for r in cohort], padded, capacity,
                    dtype)))
                primary = "A"
            args.append(self._device_put_batch(bucketing.stack_pad(
                [r.arrays["B"] for r in cohort], b.ctx["padded_B"],
                capacity, dtype)))
            args = tuple(args)
        elif endpoint in ("graph_ase", "graph_ppr"):
            # CSR adjacency lanes (the r18 packing): uniform within
            # the bucket (nnz class is a static); graph_ase leads
            # with the key lanes, graph_ppr trails with the
            # personalization vectors
            nnz_pad = cohort[0].arrays["data"].shape[0]
            padded = (nnz_pad,)
            dtype = cohort[0].arrays["data"].dtype
            ptr_len = cohort[0].arrays["indptr"].shape[0]
            args = []
            if endpoint == "graph_ase":
                args.append(self._device_put_batch(bucketing.stack_pad(
                    [r.arrays["kd"] for r in cohort], (2,), capacity,
                    np.uint32)))
            args += [
                self._device_put_batch(bucketing.stack_pad(
                    [r.arrays["data"] for r in cohort], (nnz_pad,),
                    capacity, dtype)),
                self._device_put_batch(bucketing.stack_pad(
                    [r.arrays["indices"] for r in cohort], (nnz_pad,),
                    capacity, np.int32)),
                self._device_put_batch(bucketing.stack_pad(
                    [r.arrays["indptr"] for r in cohort], (ptr_len,),
                    capacity, np.int32)),
            ]
            if endpoint == "graph_ppr":
                args.append(self._device_put_batch(bucketing.stack_pad(
                    [r.arrays["s"] for r in cohort],
                    (b.ctx["padded"][0],), capacity, dtype)))
            args = tuple(args)
            primary = "data"
        elif endpoint == "condest":
            padded = cohort[0].meta["padded"]
            dtype = cohort[0].arrays["A"].dtype
            kd = bucketing.stack_pad([r.arrays["kd"] for r in cohort],
                                     (2,), capacity, np.uint32)
            Astk = bucketing.stack_pad([r.arrays["A"] for r in cohort],
                                       padded, capacity, dtype)
            args = (self._device_put_batch(kd),
                    self._device_put_batch(Astk))
            primary = "A"
        elif endpoint == "lowrank":
            padded = cohort[0].meta["padded"]
            dtype = cohort[0].arrays["A"].dtype
            args = tuple(
                self._device_put_batch(bucketing.stack_pad(
                    [r.arrays[nm] for r in cohort], shp, capacity, dt))
                for nm, shp, dt in (("kd_s", (2,), np.uint32),
                                    ("scale_s", (), dtype),
                                    ("kd_t", (2,), np.uint32),
                                    ("scale_t", (), dtype),
                                    ("A", padded, dtype)))
            primary = "A"
        else:
            padded = cohort[0].meta["padded"]
            Xq = bucketing.stack_pad(
                [r.arrays["Xq"] for r in cohort], padded, capacity,
                cohort[0].arrays["Xq"].dtype)
            args = (self._device_put_batch(Xq),
                    self._device_put_replicated(b.ctx["X_train"]),
                    self._device_put_replicated(b.ctx["coef"]))
            primary = "Xq"

        cf = self._compiled_for(b)
        from libskylark_tpu.base.precision import solver_precision

        # the sequential solve/KRR endpoints trace under
        # solver_precision() (full-f32 matmuls on TPU); the batched
        # program must bake in the SAME regime or a served result would
        # silently diverge from its sequential twin on MXU backends.
        # Sketch-apply stays at the fast ambient default, also matching
        # its sequential path (base/precision.py policy).
        def dispatch():
            prec = (contextlib.nullcontext()
                    if endpoint in _KERNEL_ENDPOINTS
                    else solver_precision())
            with prec, warnings.catch_warnings():
                # the donated stacked buffers rarely alias the batch
                # output — jax's unusable-donation warning is this
                # layer's expected steady state, silenced ONLY around
                # the serve dispatch so user donation sites keep their
                # diagnostic
                warnings.filterwarnings(
                    "ignore",
                    message="Some donated buffers were not usable")
                return cf(*args)

        if kernel_backend == "pallas" and _pallas_native():
            # compile-time Mosaic rejection is a DECLINE, not an
            # outage: poison this (bucket, capacity) onto the XLA path
            # and re-dispatch — the key_fn re-resolves to the xla
            # token, so the retry compiles (and caches) the fallback
            # program. Rejections surface as JaxRuntimeError from
            # Mosaic proper but as trace-time NotImplementedError /
            # LoweringError from the Pallas lowering rules, so the net
            # is Exception-wide; the serve.flush fault seam fires
            # BEFORE this block, so an injected chaos fault can never
            # be misread as a rejection. A rejected attempt never
            # EXECUTED, so the donated buffers are intact and the
            # re-dispatch is safe; a post-compile runtime failure may
            # have consumed them — detected below — in which case the
            # original error propagates into bisection isolation
            # (future flushes of this bucket still take the XLA path).
            try:
                out = dispatch()
            except Exception:  # noqa: BLE001 — decline seam, see above
                self._poison_kernel(b, capacity, "mosaic-reject")
                kernel_backend, kdeclined = "xla", "mosaic-reject"
                if any(getattr(a, "is_deleted", lambda: False)()
                       for a in args):
                    raise
                out = dispatch()
        else:
            out = dispatch()
        # resolve futures from ONE host view of the batch output: a
        # per-request eager device slice would cost a dispatched XLA op
        # per lane — at microbatch request sizes that's comparable to
        # the whole flush. Serving results terminate at the client, so
        # they come back as host arrays (near zero-copy on CPU), and
        # each future resolves to a VIEW into this one buffer (_unpad
        # slices, never copies) — the handoff a process replica's
        # shared-memory transport writes straight out of (fleet/shm:
        # np.copyto from the strided view into the slot, no
        # ascontiguousarray staging copy in between).
        if self._mesh is not None:
            spread = len({s.device for s in out.addressable_shards})
            with self._stats_lock:
                self._flush_devices_min = min(
                    self._flush_devices_min or spread, spread)
        out = np.asarray(out)

        now = time.monotonic()
        for i, r in enumerate(cohort):
            try:
                r.future.set_result(self._unpad(endpoint, out, i, r))
            except BaseException as e:  # noqa: BLE001
                if not r.future.done():
                    r.future.set_exception(e)
        with self._stats_lock:
            self._counts["flushes"] += 1
            self._counts["completed"] += k
            if k > 1:
                self._counts["coalesced"] += k
            if endpoint in _KERNEL_ENDPOINTS:
                self._kernel_sel[kernel_backend] += 1
                if kdeclined:
                    self._kernel_dec[kdeclined] += 1
                if endpoint == "sparse_sketch_apply":
                    self._sparse_kernel_sel[kernel_backend] += 1
                    _SPARSE_KERNEL_FLUSHES.inc_always(
                        backend=kernel_backend)
                if (endpoint == "sketch_apply"
                        and b.statics[1] == "SRHT"):
                    self._fwht_sel[kernel_backend] += 1
                    _FWHT_FLUSHES.inc_always(backend=kernel_backend)
            self._batch_hist[capacity] += 1
            self._cohort_hist[k] += 1
            pad_total = bucketing.padded_elements(padded, capacity)
            pad_real = bucketing.real_elements(
                [r.true_shapes[primary] for r in cohort])
            self._pad_total += pad_total
            self._pad_real += pad_real
            # per-bucket adaptive-controller observations (docs/qos):
            # the latency window, the warm capacity set (the rungs the
            # controller may move the batch target along — already
            # compiled, so moving there can never compile), padding
            # waste and the classes whose traffic this bucket carried
            obs = self._bucket_obs.get(b.statics)
            if obs is None:
                obs = self._bucket_obs[b.statics] = {
                    "lat": collections.deque(maxlen=512),
                    "caps": set(), "classes": set(),
                    "pad_real": 0, "pad_total": 0, "n": 0}
            obs["caps"].add(int(capacity))
            obs["classes"].add(b.qos_class)
            obs["pad_total"] += pad_total
            obs["pad_real"] += pad_real
            obs["n"] += k
            for r in cohort:
                lat = now - r.t_submit
                self._latency.append(lat)
                self._latency_by_class[r.qos_class].append(lat)
                obs["lat"].append(lat)
        for r in cohort:
            _QOS_LATENCY.observe(now - r.t_submit,
                                 **{"class": r.qos_class})

    def _stack_common(self, cohort, padded, capacity, *, with_b,
                      padded_b=None) -> tuple:
        dtype = cohort[0].arrays["A"].dtype
        kd = bucketing.stack_pad([r.arrays["kd"] for r in cohort], (2,),
                                 capacity, np.uint32)
        scale = bucketing.stack_pad(
            [np.asarray(r.arrays["scale"]).reshape(()) for r in cohort],
            (), capacity, dtype)
        A = bucketing.stack_pad([r.arrays["A"] for r in cohort], padded,
                                capacity, dtype)
        args = [self._device_put_batch(kd), self._device_put_batch(scale),
                self._device_put_batch(A)]
        if with_b:
            B = bucketing.stack_pad([r.arrays["B"] for r in cohort],
                                    padded_b, capacity, dtype)
            args.append(self._device_put_batch(B))
        return tuple(args)

    @staticmethod
    def _unpad(endpoint: str, out, lane: int, r: _Request):
        if endpoint == "sketch_apply":
            if r.meta["rowwise"]:
                return out[lane, : r.true_shapes["A"][0], :]
            return out[lane, :, : r.true_shapes["A"][1]]
        if endpoint == "fastfood_features":
            p = out[lane, : r.meta["m"], :]
            return p[0] if r.meta["squeeze"] else p
        if endpoint == "solve_l2_sketched":
            x = out[lane]
            return x[:, 0] if r.meta["squeeze"] else x
        if endpoint == "sparse_sketch_apply":
            h, w = r.meta["shape"]
            if r.meta["rowwise"]:
                return out[lane, :h, :]
            return out[lane, :, :w]
        if endpoint == "sparse_solve_l2_sketched":
            x = out[lane]
            return x[:, 0] if r.meta["squeeze"] else x
        if endpoint == "compressed_matmul":
            # (estimate, bound): the view discipline holds for the
            # estimate; the bound is a host float computed at submit
            return (out[lane, : r.meta["m"], : r.meta["p"]],
                    r.meta["bound"])
        if endpoint == "graph_ase":
            return out[lane, : r.meta["n"], :]
        if endpoint == "graph_ppr":
            return out[lane, : r.meta["n"]]
        if endpoint == "condest":
            return out[lane]
        if endpoint == "lowrank":
            return out[lane, : r.meta["m"], :]
        if endpoint == "rlsc_predict":
            p = out[lane, : r.meta["q"]]
            coding = r.meta.get("coding")
            if coding is not None:
                p = np.asarray([coding[int(i)] for i in p])
            return p[0] if r.meta["squeeze_q"] else p
        p = out[lane, : r.meta["q"], :]
        if r.meta["squeeze_t"]:
            p = p[:, 0]
        if r.meta["squeeze_q"]:
            p = p[0]
        return p

    # ------------------------------------------------------------------
    # health + drain
    # ------------------------------------------------------------------

    @property
    def state(self) -> str:
        """``SERVING`` | ``DEGRADED`` | ``DRAINING`` | ``STOPPED``.

        DEGRADED = the recent flush-attempt failure ratio (over the
        ``failure_window`` sliding window) is at or past
        ``degraded_threshold``; submits load-shed at ``max_queue *
        shed_fraction`` instead of queueing behind a failing flush
        path. The state self-heals: successful flushes push the ratio
        back down."""
        with self._lock:
            if self._stop:
                return STOPPED
            if self._draining:
                return DRAINING
        return DEGRADED if self._is_degraded() else SERVING

    def queue_depth(self) -> int:
        """Pending + in-flight request count — the live load signal the
        fleet router's spill heuristic reads. Note this is a superset
        of the telemetry ``queued`` gauge, which reports only the
        pending (not-yet-dispatched) count: under high in-flight load
        the router sees a larger number than a scraped dashboard, so
        tune ``Router.spill_threshold`` against this method, not the
        gauge."""
        with self._lock:
            return self._pending + self._inflight

    def latency_quantile(self, q: float = 0.99) -> Optional[float]:
        """One quantile of the r10 request-latency histogram (seconds;
        ``None`` before any completion). Cheaper than :meth:`stats`
        (no counter snapshot) — the fleet router derives its hedge
        delay from this, and the autoscaler reads it at tick cadence,
        so it must not contend with the flush path for more than the
        stats lock."""
        with self._stats_lock:
            lat = sorted(self._latency)
        return _percentile(lat, q)

    def qos_bucket_obs(self) -> dict:
        """Per-bucket adaptive-controller observations: ``statics ->
        {p99, padding_waste, caps, classes, n}`` (docs/qos). The
        controller's read side — cheap (one stats-lock snapshot), no
        contention with the flush path beyond that lock."""
        with self._stats_lock:
            snap = {
                statics: {
                    "lat": sorted(o["lat"]),
                    "caps": frozenset(o["caps"]),
                    "classes": frozenset(o["classes"]),
                    "pad_real": o["pad_real"],
                    "pad_total": o["pad_total"],
                    "n": o["n"],
                }
                for statics, o in self._bucket_obs.items()
            }
        return {
            statics: {
                "p99": _percentile(o["lat"], 0.99),
                "padding_waste": (
                    round(1.0 - o["pad_real"] / o["pad_total"], 4)
                    if o["pad_total"] else None),
                "caps": o["caps"],
                "classes": o["classes"],
                "n": o["n"],
            }
            for statics, o in snap.items()
        }

    def qos_reset_bucket_obs(self, statics) -> None:
        """Drop one bucket's latency window and padding-waste counts
        (the warm capacity set and class set persist — the
        zero-recompile rungs must survive a reset). The adaptive
        controller calls this after acting on a bucket so the next
        decision scores post-change evidence: without it, the burst
        that triggered a step keeps dominating the rolling window and
        drives repeated same-direction steps long after the live
        latency recovered."""
        with self._stats_lock:
            o = self._bucket_obs.get(tuple(statics))
            if o is not None:
                o["lat"].clear()
                o["pad_real"] = 0
                o["pad_total"] = 0

    def _qos_stats_block(self) -> dict:
        """The ``stats()["qos"]`` block: per-class admission/shed/
        rate-limit counters, queue depths, latency percentiles, the
        scheduler's deficit state, the live adaptive targets and the
        controller rollup — rendered on the Prometheus surface by
        the ``qos`` collector (``skylark_qos_*``)."""
        with self._stats_lock:
            qc = dict(self._qos_counts)
            lat_cls = {c: sorted(d)
                       for c, d in self._latency_by_class.items()}
        with self._lock:
            depth = {c: int(self._class_pending.get(c, 0))
                     for c in _qtenants.CLASSES}
            targets = {
                str(statics[0]): {"linger_s": round(float(t[0]), 6),
                                  "batch": int(t[1])}
                for statics, t in self._qos_targets.items()}
        by_class: dict = {
            c: {"admitted": 0, "shed": 0, "rate_limited": 0,
                "queue_depth": depth[c]}
            for c in _qtenants.CLASSES}
        by_tenant: dict = {}
        for (kind, cls, tenant), n in qc.items():
            by_class[cls][kind] += n
            if tenant:
                t = by_tenant.setdefault(
                    tenant, {"admitted": 0, "shed": 0,
                             "rate_limited": 0})
                t[kind] += n
        for c, lat in lat_cls.items():
            by_class[c]["latency_s"] = {
                "p50": _percentile(lat, 0.50),
                "p99": _percentile(lat, 0.99),
                "n": len(lat),
            }
        return {
            "by_class": by_class,
            "by_tenant": dict(sorted(by_tenant.items())),
            "scheduler": self._sched.stats(),
            "targets": targets,
            "controller": (self._controller.stats()
                           if self._controller is not None else None),
        }

    def _maybe_publish_state(self) -> None:
        """Publish a health-state transition to the resilience hub
        (:mod:`libskylark_tpu.resilience.health`) if one happened —
        the push-side a fleet router subscribes to. Called from every
        root flush outcome (DEGRADED flips, both directions), from
        :meth:`drain` (DRAINING) and :meth:`shutdown` (STOPPED).
        Callbacks run outside the executor lock; the publish lock only
        serializes the compare-and-set so two racing workers can't
        both announce the same transition. The state read must happen
        INSIDE the publish lock: read outside, a worker descheduled
        between read and acquire would publish its stale snapshot
        after a peer already announced a newer one."""
        with self._pub_lock:
            new = self.state
            old = self._published_state
            if new == old:
                return
            self._published_state = new
            # publish under the (executor-independent) publish lock so
            # racing transitions reach subscribers in order — a
            # DEGRADED announcement landing after the recovery to
            # SERVING would wedge a router's view of a healthy replica
            _health.publish(self, old, new)

    def drain(self, timeout: Optional[float] = 30.0) -> bool:
        """Preemption-safe drain: stop intake (new submits raise
        :class:`ServeOverloadedError`), flush every queued cohort, and
        wait until every in-flight future has resolved, then stop the
        threads. Returns whether quiescence was reached inside
        ``timeout`` (the executor is stopped either way — a SIGTERM
        handler cannot wait forever). Idempotent; called by
        :func:`libskylark_tpu.resilience.install_preemption_handler`
        on SIGTERM for every live executor."""
        dl = Deadline.after(timeout)
        with self._lock:
            if self._stop:
                return True
            self._draining = True
            self._work_cv.notify_all()
            self._space_cv.notify_all()
        # announce DRAINING before waiting for quiescence: a subscribed
        # router must shed new traffic to peers WHILE the drain flushes
        # the queue, not after
        self._maybe_publish_state()
        with self._lock:
            drained = True
            while self._pending or self._inflight or self._buckets:
                rem = dl.remaining()
                if rem <= 0:
                    drained = False
                    break
                self._idle_cv.wait(
                    timeout=0.1 if rem == float("inf") else min(rem, 0.1))
        # live session state is checkpointed HERE — the r9 drain hook
        # discipline (docs/sessions "Graceful handoff"): journal
        # fsync'd + accumulator snapshot durable before the executor
        # stops, so a peer resumes the stream from state. Runs even on
        # a drain timeout (the journal already holds every accepted
        # append; the checkpoint just bounds the peer's replay).
        try:
            self._checkpoint_sessions()
        except Exception as e:  # noqa: BLE001 — the drain must finish
            warnings.warn(f"session checkpoint during drain failed: "
                          f"{e}", RuntimeWarning, stacklevel=2)
        # on timeout a cohort is wedged in execution — joining the
        # threads would block past the deadline the caller (a SIGTERM
        # grace window) budgeted, starving the checkpoint hooks that
        # run after the drain; stop without waiting instead
        self.shutdown(wait=drained)
        return drained

    # ------------------------------------------------------------------
    # stats + lifecycle
    # ------------------------------------------------------------------

    def stats(self) -> dict:
        """Snapshot of the serving counters (see module docstring)."""
        with self._stats_lock:
            lat = sorted(self._latency)
            c = dict(self._counts)
            batch_hist = dict(sorted(self._batch_hist.items()))
            cohort_hist = dict(sorted(self._cohort_hist.items()))
            pad_real, pad_total = self._pad_real, self._pad_total
            ksel = dict(sorted(self._kernel_sel.items()))
            kdec = dict(sorted(self._kernel_dec.items()))
            sp_sel = dict(sorted(self._sparse_kernel_sel.items()))
            sp_nnz = dict(sorted(self._sparse_nnz_hist.items()))
            fw_sel = dict(sorted(self._fwht_sel.items()))
            dist_by = dict(self._dist_by_replica)
            flush_devices_min = self._flush_devices_min
        with self._lock:
            queued = self._pending
        return {
            "state": self.state,
            # sharded serving: the mesh's batch-axis extent and the
            # fewest distinct devices that held any flush's output (a
            # mesh executor that computes on one device reads 1 here)
            "mesh": ({"devices": self._ndev,
                      "flush_devices_min": flush_devices_min}
                     if self._mesh is not None else None),
            "submitted": c.get("submitted", 0),
            "completed": c.get("completed", 0),
            "failed": c.get("failed", 0),
            "rejected": c.get("rejected", 0),
            "shed": c.get("shed", 0),
            "session_shed": c.get("session_shed", 0),
            "expired": c.get("expired", 0),
            "poisoned": c.get("poisoned", 0),
            "flush_failures": c.get("flush_failures", 0),
            "isolation_retries": c.get("isolation_retries", 0),
            "isolation_depth_peak": c.get("isolation_depth_peak", 0),
            "queued": queued,
            "queued_peak": c.get("queued_peak", 0),
            "coalesced": c.get("coalesced", 0),
            "flushes": c.get("flushes", 0),
            # by_<label> convention (docs/observability): renders on
            # the Prometheus surface as skylark_serve_kernel_flushes
            # {backend="pallas"} / ..._declined_flushes{reason="..."}
            "kernel": {
                "by_backend": {k: {"flushes": int(v)}
                               for k, v in ksel.items()},
                "by_reason": {k: {"declined_flushes": int(v)}
                              for k, v in kdec.items()},
            },
            # sparse-operand intake/flush disaggregation (docs/serving,
            # "Sparse operands on the serve path"); by_backend renders
            # as skylark_serve_sparse_kernel_flushes{backend="..."}
            "sparse": {
                "submits": c.get("sparse_submits", 0),
                "densified": c.get("sparse_densified", 0),
                "by_backend": {k: {"kernel_flushes": int(v)}
                               for k, v in sp_sel.items()},
                "nnz_class_hist": sp_nnz,
            },
            # panel-free FWHT tier (docs/performance, "In-kernel FWHT
            # and compressed matmul"); by_backend renders as
            # skylark_serve_fwht_flushes{backend="..."}
            "fwht": {
                "by_backend": {k: {"flushes": int(v)}
                               for k, v in fw_sel.items()},
                "cm_submits": c.get("cm_submits", 0),
            },
            # pipelined dist-serve jobs (docs/distributed): by_replica
            # renders as skylark_dist_shard_tasks{replica="..."} — the
            # shard placement skew surface
            "dist": {
                "jobs": c.get("dist_jobs", 0),
                "completed": c.get("dist_completed", 0),
                "failed": c.get("dist_failed", 0),
                "early_resolves": c.get("dist_early_resolves", 0),
                "by_replica": {k: {"shard_tasks": int(v)}
                               for k, v in sorted(dist_by.items())},
            },
            "batch_capacity_hist": batch_hist,
            "cohort_size_hist": cohort_hist,
            "padding_waste_ratio": (
                round(1.0 - pad_real / pad_total, 4) if pad_total else None
            ),
            "latency_s": {
                "p50": _percentile(lat, 0.50),
                "p99": _percentile(lat, 0.99),
                "mean": (sum(lat) / len(lat)) if lat else None,
                "n": len(lat),
            },
            # the multi-tenant QoS block (docs/qos): per-class
            # admission/shed/latency, scheduler deficits, adaptive
            # targets — the "qos" telemetry collector aggregates it
            # across executors
            "qos": self._qos_stats_block(),
            # the stateful-session block (None until the first session
            # verb; the cross-registry rollup is the "sessions"
            # telemetry collector)
            "sessions": (self._session_registry.stats()
                         if self._session_registry is not None
                         else None),
            # the training-job block (docs/training; None until the
            # first submit — the cross-executor rollup is the "train"
            # telemetry collector) plus the shed counter, which lives
            # on the executor because shedding happens before the
            # manager is consulted
            "train": (dict(self._train_mgr.stats(),
                           shed=c.get("train_shed", 0))
                      if self._train_mgr is not None
                      else None),
            # the result-cache block (docs/caching): None until the
            # cache is enabled or an operand is pinned; the "cache"
            # telemetry collector aggregates it across executors
            "cache": self._cache_stats_block(),
        }

    def shutdown(self, wait: bool = True) -> None:
        """Stop intake, flush everything pending, join the threads."""
        with self._lock:
            if self._stop:
                return
            self._stop = True
            self._work_cv.notify_all()
            self._space_cv.notify_all()
        self._maybe_publish_state()
        if self._controller is not None:
            self._controller.close()
        if wait:
            self._flusher.join()
            for t in self._workers:
                t.join()
        # live training jobs are released, not failed: their sessions
        # stay on disk (the drain hook checkpointed them) and each
        # unresolved job future breaks retryably so a router's resume
        # chain re-homes the job on a surviving replica
        mgr = self._train_mgr
        if mgr is not None:
            try:
                mgr.release_jobs(
                    f"executor {self.name!r} stopped mid-job; the "
                    "session remains on disk for a peer to resume")
            except Exception:  # noqa: BLE001 — shutdown must finish
                pass
        # sync the session journals WITHOUT deleting artifacts — a
        # peer (or a restarted process) resumes them from disk
        reg = self._session_registry
        if reg is not None:
            try:
                reg.close()
            except Exception:  # noqa: BLE001 — shutdown must finish
                pass

    def __enter__(self) -> "MicrobatchExecutor":
        return self

    def __exit__(self, *exc) -> None:
        self.shutdown()


_EXECUTORS: "weakref.WeakSet[MicrobatchExecutor]" = weakref.WeakSet()


def _merge_qos_blocks(blocks) -> dict:
    """Cross-executor merge of per-executor ``stats()["qos"]`` blocks
    — shared by :func:`serve_stats` and the ``qos`` collector so the
    aggregation semantics (counters sum, queue depths sum, served
    counts sum, tenants union) cannot drift apart."""
    qos_class: dict = {
        c: collections.Counter() for c in _qtenants.CLASSES}
    qos_tenant: dict = {}
    qos_served: "collections.Counter" = collections.Counter()
    for q in blocks:
        for cc, blk in q["by_class"].items():
            for kk in ("admitted", "shed", "rate_limited",
                       "queue_depth"):
                qos_class[cc][kk] += blk.get(kk, 0)
        for tname, blk in q["by_tenant"].items():
            t = qos_tenant.setdefault(tname, collections.Counter())
            t.update(blk)
        qos_served.update(q["scheduler"]["served"])
    return {
        "by_class": {c: dict(qos_class[c]) for c in _qtenants.CLASSES},
        "by_tenant": {t: dict(v)
                      for t, v in sorted(qos_tenant.items())},
        "served": dict(qos_served),
    }


def serve_stats() -> dict:
    """Aggregate counters across every live executor in the process
    (the serve analog of ``engine.stats()``; folded into
    ``engine.dump_stats`` under ``"serve"``), disaggregated per
    replica under ``by_replica``.

    Aggregation semantics over N executors (the r11 fix — the
    single-executor-era version summed what it knew and silently
    dropped the rest): monotone counters SUM; the peak diagnostics
    (``queued_peak``, ``isolation_depth_peak``) take the MAX — summing
    a per-replica high-water mark across replicas would report a queue
    depth no single executor ever saw; the capacity/cohort histograms
    merge bin-wise; padding waste re-derives from the pooled raw
    element counts (a mean of per-replica ratios would weight an idle
    replica equally with a loaded one); latency percentiles come from
    the pooled samples; ``states`` counts executors per health state.
    ``by_replica`` keys each executor's own :meth:`stats()` block by
    its ``name`` — the replica label telemetry and the Prometheus
    renderer use (``docs/observability``)."""
    agg: dict = {"executors": 0}
    _SUM_KEYS = ("submitted", "completed", "failed", "rejected", "shed",
                 "session_shed", "expired", "poisoned",
                 "flush_failures", "isolation_retries", "queued",
                 "coalesced", "flushes")
    _MAX_KEYS = ("queued_peak", "isolation_depth_peak")
    sums = collections.Counter({k: 0 for k in _SUM_KEYS})
    maxes = {k: 0 for k in _MAX_KEYS}
    batch_hist: "collections.Counter" = collections.Counter()
    cohort_hist: "collections.Counter" = collections.Counter()
    states: "collections.Counter" = collections.Counter()
    ksel: "collections.Counter" = collections.Counter()
    kdec: "collections.Counter" = collections.Counter()
    sparse_sums: "collections.Counter" = collections.Counter(
        {"submits": 0, "densified": 0})
    sparse_sel: "collections.Counter" = collections.Counter()
    sparse_nnz: "collections.Counter" = collections.Counter()
    fwht_sel: "collections.Counter" = collections.Counter()
    cm_submits = 0
    dist_sums: "collections.Counter" = collections.Counter(
        {"jobs": 0, "completed": 0, "failed": 0, "early_resolves": 0})
    dist_by: "collections.Counter" = collections.Counter()
    _TRAIN_SUM = ("jobs_submitted", "slices_run", "preemptions",
                  "resumes", "budget_exhausted", "completed", "failed",
                  "retries", "active", "queued", "shed")
    train_sums: "collections.Counter" = collections.Counter(
        {k: 0 for k in _TRAIN_SUM})
    train_seen = False
    qos_blocks: list = []
    cache_blocks: list = []
    by_replica: dict = {}
    lat_all: list = []
    waste_real = waste_total = 0
    for ex in list(_EXECUTORS):
        s = ex.stats()
        agg["executors"] += 1
        for k in _SUM_KEYS:
            sums[k] += s[k]
        for k in _MAX_KEYS:
            maxes[k] = max(maxes[k], s.get(k, 0))
        batch_hist.update(s["batch_capacity_hist"])
        cohort_hist.update(s["cohort_size_hist"])
        for kk, vv in s["kernel"]["by_backend"].items():
            ksel[kk] += vv["flushes"]
        for kk, vv in s["kernel"]["by_reason"].items():
            kdec[kk] += vv["declined_flushes"]
        sparse_sums["submits"] += s["sparse"]["submits"]
        sparse_sums["densified"] += s["sparse"]["densified"]
        for kk, vv in s["sparse"]["by_backend"].items():
            sparse_sel[kk] += vv["kernel_flushes"]
        sparse_nnz.update(s["sparse"]["nnz_class_hist"])
        for kk, vv in s["fwht"]["by_backend"].items():
            fwht_sel[kk] += vv["flushes"]
        cm_submits += s["fwht"]["cm_submits"]
        for kk in ("jobs", "completed", "failed", "early_resolves"):
            dist_sums[kk] += s["dist"][kk]
        for kk, vv in s["dist"]["by_replica"].items():
            dist_by[kk] += vv["shard_tasks"]
        if s.get("train") is not None:
            train_seen = True
            for kk in _TRAIN_SUM:
                train_sums[kk] += int(s["train"].get(kk, 0))
        qos_blocks.append(s["qos"])
        cache_blocks.append(s.get("cache"))
        states[s["state"]] += 1
        if s["padding_waste_ratio"] is not None:
            with ex._stats_lock:
                waste_real += ex._pad_real
                waste_total += ex._pad_total
        with ex._stats_lock:
            lat_all.extend(ex._latency)
        name = ex.name
        while name in by_replica:     # defensive: caller reused a name
            name += "+"
        by_replica[name] = s
    agg.update(sums)
    agg.update(maxes)
    agg["batch_capacity_hist"] = dict(sorted(batch_hist.items()))
    agg["cohort_size_hist"] = dict(sorted(cohort_hist.items()))
    agg["kernel"] = {
        "by_backend": {k: {"flushes": int(v)}
                       for k, v in sorted(ksel.items())},
        "by_reason": {k: {"declined_flushes": int(v)}
                      for k, v in sorted(kdec.items())},
    }
    agg["sparse"] = {
        "submits": sparse_sums["submits"],
        "densified": sparse_sums["densified"],
        "by_backend": {k: {"kernel_flushes": int(v)}
                       for k, v in sorted(sparse_sel.items())},
        "nnz_class_hist": dict(sorted(sparse_nnz.items())),
    }
    agg["fwht"] = {
        "by_backend": {k: {"flushes": int(v)}
                       for k, v in sorted(fwht_sel.items())},
        "cm_submits": int(cm_submits),
    }
    # dist-serve rollup (docs/distributed): executor job counters,
    # fleet-wide shard placement, plus the process-lifetime rollups of
    # the coordinator and the dist-serve driver (imported lazily —
    # dist pulls the engine package, not the other way around)
    agg["dist"] = {
        **{k: int(dist_sums[k]) for k in
           ("jobs", "completed", "failed", "early_resolves")},
        "by_replica": {k: {"shard_tasks": int(v)}
                       for k, v in sorted(dist_by.items())},
    }
    try:
        from libskylark_tpu.dist.coordinator import dist_stats
        from libskylark_tpu.dist.serve import dist_serve_stats
        agg["dist"]["lifetime"] = {"coordinator": dist_stats(),
                                   "serve": dist_serve_stats()}
    except Exception:  # noqa: BLE001 — stats must never fail serving
        pass
    # training-job rollup (docs/training): monotone counters and live
    # occupancy SUM across replicas; None when no replica ever ran one
    agg["train"] = ({k: int(train_sums[k]) for k in _TRAIN_SUM}
                    if train_seen else None)
    agg["qos"] = _merge_qos_blocks(qos_blocks)
    agg["cache"] = _rcache.merge_cache_blocks(cache_blocks)
    agg["states"] = dict(sorted(states.items()))
    agg["padding_waste_ratio"] = (
        round(1.0 - waste_real / waste_total, 4) if waste_total else None)
    lat_all.sort()
    agg["latency_s"] = {"p50": _percentile(lat_all, 0.50),
                        "p99": _percentile(lat_all, 0.99),
                        "n": len(lat_all)}
    agg["by_replica"] = dict(sorted(by_replica.items()))
    # network front-door rollup (docs/networking) — only when the net
    # tier is actually loaded (the sys.modules guard keeps a pure
    # in-process deployment from importing the socket layer just to
    # report stats about it)
    if "libskylark_tpu.net.server" in sys.modules:
        try:
            from libskylark_tpu.net.server import net_stats
            agg["net"] = net_stats()
        except Exception:  # noqa: BLE001 — stats must never fail serving
            pass
    return agg


# telemetry re-homing (docs/observability): the executor's counters are
# authoritative — the collector snapshots the cross-executor aggregate
# (including the live ``queued`` queue-depth gauge) instead of double-
# counting on the submit/flush hot paths.
_telemetry.register_collector("serve", serve_stats)


def qos_stats() -> dict:
    """Cross-executor multi-tenant QoS aggregate (the ``qos``
    collector block in ``telemetry.snapshot()``; renders as
    ``skylark_qos_*`` on the Prometheus surface — the ``by_class`` /
    ``by_tenant`` sub-blocks become label sets). Aggregates the
    per-executor qos blocks DIRECTLY (not via :func:`serve_stats` —
    a snapshot already runs the ``serve`` collector, and re-running
    the full cross-executor aggregation would double every scrape's
    latency-sort cost). Folds in the process-global tenant registry
    so a scrape shows the registered tenants and their live token
    balances."""
    agg = _merge_qos_blocks(
        [ex._qos_stats_block() for ex in list(_EXECUTORS)])
    agg["registry"] = _qtenants.get_registry().stats()
    return agg


_telemetry.register_collector("qos", qos_stats)


def cache_stats() -> dict:
    """Cross-executor result-cache aggregate (the ``cache`` collector
    block in ``telemetry.snapshot()``; renders as ``skylark_cache_*``
    on the Prometheus surface — ``by_class`` becomes the class label
    set). Aggregates the per-executor cache blocks DIRECTLY, not via
    :func:`serve_stats` — same double-scrape rationale as
    :func:`qos_stats`; cache-off executors contribute nothing."""
    return _rcache.merge_cache_blocks(
        [ex._cache_stats_block() for ex in list(_EXECUTORS)])


_telemetry.register_collector("cache", cache_stats)
