"""Sketch-apply autotuner: candidate plans, offline cost ranking, and a
persistent plan cache the serve tier consults before its default.

The flow (designed for scarce TPU access — see ISSUE/ROADMAP):

1. **Offline** (any host, no TPU): :func:`enumerate_candidates` lists
   every plan for a workload; :func:`rank_candidates` orders them with
   the hardware-free cost model (:mod:`tune.cost`); :func:`autotune_topk`
   returns the short list a live window should actually measure.
2. **Live window**: measure the top-k and :func:`record_measurement`
   the winner — the cache persists to disk
   (``benchmarks/plan_cache.json`` by default).
3. **Dispatch**: the serve tier (engine/serve.py, engine/warmup.py)
   calls :func:`plan_for` when it picks a bucket's flush kernel, after
   an explicit ``kernel=`` argument and the env pins and before its XLA
   default. The eager applies in sketch/ do not read the cache: their
   kernel, tile and regime are the call-site argument, else the
   ``sketch.params`` setter, else one rule from the device and shapes.

``SKYLARK_PLAN_CACHE`` points the cache elsewhere (or ``0`` disables
persistence); :func:`libskylark_tpu.sketch.params.set_use_plan_cache`
gates dispatch-time consultation at runtime.
"""

from __future__ import annotations

from typing import Optional

from libskylark_tpu.tune.cache import (PlanCache, default_cache_path,
                                       get_cache, set_cache)
from libskylark_tpu.tune.cost import (RATES, analyze_jitted,
                                      effective_rates, plan_cost,
                                      rank_plans, rate_provenance)
from libskylark_tpu.tune.plans import (Plan, Workload, bucket_dim,
                                       current_device_kind,
                                       enumerate_candidates,
                                       normalize_device_kind)

__all__ = [
    "Plan", "PlanCache", "Workload", "analyze_jitted", "autotune_topk",
    "bucket_dim", "current_device_kind", "default_cache_path",
    "dense_workload", "effective_rates", "enumerate_candidates",
    "fastfood_workload", "get_cache", "hash_workload",
    "normalize_device_kind", "plan_cost", "plan_for",
    "plan_fingerprint", "rank_candidates", "rank_plans",
    "rate_provenance", "record_measurement", "record_ranked",
    "serve_workload", "set_cache", "RATES",
]


def plan_fingerprint() -> str:
    """Content fingerprint of the global plan cache's *plans* — the
    component the solver engine folds into its executable cache keys
    (see :meth:`PlanCache.fingerprint`). Never raises."""
    try:
        return get_cache().fingerprint()
    except Exception:
        return "no-plan-cache"


# -- workload constructors (the dispatchers' vocabulary) --

def dense_workload(dist_kind: str, shape, dtype, s_dim: int,
                   seq_axis: int, *, rft: bool = False,
                   device_kind: Optional[str] = None) -> Workload:
    """Workload for a dense virtual-operator apply. ``shape`` is the
    2-D input's shape; ``seq_axis`` its contracted axis (1 → rowwise
    A·Sᵀ, 0 → columnwise S·A); ``rft`` marks the cos-epilogue variant."""
    m = int(shape[1 - seq_axis])
    n = int(shape[seq_axis])
    op = ("rft_rowwise" if rft
          else ("dense_rowwise" if seq_axis == 1 else "dense_columnwise"))
    return Workload(
        device_kind=device_kind or current_device_kind(),
        op=op, transform=str(dist_kind), dtype=str(dtype),
        shape=(m, n, int(s_dim)))


def fastfood_workload(transform_type: str, shape, dtype, s_dim: int, *,
                      device_kind: Optional[str] = None) -> Workload:
    """Workload for a Fastfood feature map on row-major (m, d) input."""
    return Workload(
        device_kind=device_kind or current_device_kind(),
        op="fastfood_rows", transform=str(transform_type),
        dtype=str(dtype), shape=(int(shape[0]), int(shape[1]),
                                 int(s_dim)))


def hash_workload(sketch_type: str, shape, dtype, s_dim: int,
                  seq_axis: int, *,
                  device_kind: Optional[str] = None) -> Workload:
    """Workload for a hash-sketch (CWT/CountSketch) direct apply —
    the scatter-free kernel (sketch/pallas_hash.py) vs the XLA
    ``segment_sum`` scatter. ``shape`` is the 2-D input's shape;
    ``seq_axis`` its contracted (hashed) axis."""
    m = int(shape[1 - seq_axis])
    n = int(shape[seq_axis])
    op = "hash_rowwise" if seq_axis == 1 else "hash_columnwise"
    return Workload(
        device_kind=device_kind or current_device_kind(),
        op=op, transform=str(sketch_type), dtype=str(dtype),
        shape=(m, n, int(s_dim)))


def serve_workload(endpoint: str, family: str, dtype, lane_shape,
                   s_dim: int, capacity: int, *, rowwise: bool = True,
                   nnz: int = 0,
                   device_kind: Optional[str] = None) -> Workload:
    """Workload for one microbatch serve bucket (engine/serve.py flush
    builders): a batched-kernel-vs-vmapped-XLA decision per (endpoint /
    orientation, transform family, dtype, pow2 lane shape class, batch
    capacity class). ``lane_shape`` is ONE lane's padded class shape
    ((m, n) rowwise / (n, m) columnwise for sketch_apply and
    sparse_sketch_apply; (m, n_dim) for fastfood_features);
    ``capacity`` the pow2 batch class. Sparse buckets additionally
    carry their pow2 ``nnz`` class — the sparse ladder's costs are
    nnz-proportional, so two density regimes of one dense shape class
    tune independently."""
    if endpoint == "sketch_apply":
        op = "serve_sketch_rw" if rowwise else "serve_sketch_cw"
        m = int(lane_shape[0]) if rowwise else int(lane_shape[1])
        n = int(lane_shape[1]) if rowwise else int(lane_shape[0])
    elif endpoint == "sparse_sketch_apply":
        op = "serve_sparse_rw" if rowwise else "serve_sparse_cw"
        m = int(lane_shape[0]) if rowwise else int(lane_shape[1])
        n = int(lane_shape[1]) if rowwise else int(lane_shape[0])
    elif endpoint == "fastfood_features":
        op = "serve_fastfood"
        m, n = int(lane_shape[0]), int(lane_shape[1])
    elif endpoint == "compressed_matmul":
        # lane_shape is (m_pad, n); the kept extent of B (p_pad) rides
        # the nnz slot — the shape triple only has room for (m, n, s).
        op = "serve_cmm"
        m, n = int(lane_shape[0]), int(lane_shape[1])
    else:
        raise ValueError(
            f"endpoint {endpoint!r} has no serve-bucket workload")
    return Workload(
        device_kind=device_kind or current_device_kind(),
        op=op, transform=str(family), dtype=str(dtype),
        shape=(m, n, int(s_dim)), batch=int(capacity), nnz=int(nnz))


# -- the three public verbs --

def plan_for(w: Workload) -> Optional[Plan]:
    """Cached plan for ``w``, or None (dispatcher keeps its heuristic).
    Never raises: a broken cache must not take down a sketch apply."""
    try:
        return get_cache().lookup(w)
    except Exception:
        return None


def rank_candidates(w: Workload, allow_fast: bool = False,
                    rates: Optional[dict] = None):
    """(plan, cost-record) pairs, best modeled plan first."""
    return rank_plans(w, enumerate_candidates(w, allow_fast=allow_fast),
                      rates)


def autotune_topk(w: Workload, k: int = 3,
                  allow_fast: bool = False) -> list[Plan]:
    """The k plans a live TPU window should measure for ``w``, best
    modeled first — the offline half of the tuner."""
    return [p for p, _ in rank_candidates(w, allow_fast=allow_fast)[:k]]


def record_ranked(w: Workload, allow_fast: bool = False):
    """Offline half of the serve tuner: rank ``w``'s candidates with
    the hardware-free model and persist the winner as a ``"ranked"``
    cache entry — never displacing a measured one (a live window's
    certification always outranks the model). Returns the ``(plan,
    cost-record)`` winner either way."""
    plan, cost = rank_candidates(w, allow_fast=allow_fast)[0]
    cache = get_cache()
    cur = cache.entry(w)
    if cur is None or cur.get("source") != "measured":
        cache.put(w, plan, source="ranked",
                  extra={"modeled_s": cost["modeled_s"]})
        cache.save()
    return plan, cost


def record_measurement(w: Workload, plan: Plan, value: float,
                       unit: str = "GB/s",
                       extra: Optional[dict] = None) -> bool:
    """Feed a measured result into the global cache and persist it.
    Returns whether the cache changed (see
    :meth:`PlanCache.record_measurement` for the better-only rule)."""
    cache = get_cache()
    changed = cache.record_measurement(w, plan, value, unit=unit,
                                       extra=extra)
    if changed:
        cache.save()
    return changed
