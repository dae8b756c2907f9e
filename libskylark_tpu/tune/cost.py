"""Hardware-free cost models for plan ranking.

Two complementary models live here:

1. :func:`analyze_jitted` — XLA's own compiled-HLO cost analysis
   (flops / bytes-accessed / memory split), promoted into the package
   from ``benchmarks/hlo_cost.py`` (which now imports it from here).
   For a fixed jitted computation at fixed shapes these numbers are
   deterministic properties of the lowered HLO — the drift-proof perf
   signal the CI ratchet gates on, and the cost oracle for XLA-path
   plans.

2. :func:`plan_cost` / :func:`rank_plans` — an **analytic** roofline
   model for Pallas kernel plans, which XLA cannot cost (the Mosaic
   kernel only compiles on TPU). It prices the exact quantities the
   kernel's own documentation identifies as the cost structure
   (sketch/pallas_dense.py, sketch/params.py): MXU passes per
   contraction regime, operator generation on the VPU (~50 ops/entry;
   once an apply when the operator is resident in VMEM or HBM, one full
   regeneration per m-tile sweep otherwise —
   ``pallas_dense.operator_residency``) and HBM traffic (the resident
   planes' included).

Absolute times from the analytic model are NOT predictions — only the
ORDERING is consumed (rank the candidates, measure the top-k in a live
window). The rate constants are v5e headline figures; override via the
``RATES`` mapping for other parts, or let measured ``cost_calib_*``
records from ``benchmarks/ledger.json`` recalibrate them per host
class (:func:`effective_rates` / ``SKYLARK_COST_CALIB`` — provenance
per rate via :func:`rate_provenance`, analytic fallback whenever no
measurement exists). Ranking is deterministic: stable sort on
(modeled seconds, plan_id).
"""

from __future__ import annotations

import json
import math
import os
from typing import Optional, Sequence

from libskylark_tpu.base import env as _env
from libskylark_tpu.base import locks as _locks
from libskylark_tpu.tune.plans import (FASTFOOD_OPS, HASH_OPS,
                                       SERVE_DENSE_FAMILIES, SERVE_OPS,
                                       SPARSE_SERVE_OPS, Plan, Workload,
                                       normalize_device_kind, xla_only)

# --------------------------------------------------------------------------
# compiled-HLO analysis (promoted from benchmarks/hlo_cost.py)
# --------------------------------------------------------------------------


def analyze_jitted(name: str, jitted, *avals) -> dict:
    """Lower+compile ``jitted`` at ``avals`` and return its XLA cost /
    memory analysis as a flat record. Deterministic for fixed shapes and
    toolchain — zero hardware, zero timing noise."""
    compiled = jitted.lower(*avals).compile()
    ca = compiled.cost_analysis()
    mem = compiled.memory_analysis()
    return {
        "config": name,
        "flops": float(ca.get("flops", 0.0)),
        "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
        "argument_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
        "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
        "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
    }


# --------------------------------------------------------------------------
# analytic kernel-plan roofline
# --------------------------------------------------------------------------

# v5e headline rates. Ranking consumes ratios, not absolutes, so these
# only need to be right RELATIVE to each other at the order-of-magnitude
# level the plan axes move (MXU pass count, generation sweeps, HBM).
RATES = {
    "mxu_flops_per_s": 197e12,   # one bf16 MXU pass
    "vpu_ops_per_s": 5e12,       # effective generation issue rate: an
    # estimate, not measured on today's kernel
    "hbm_bytes_per_s": 820e9,    # HBM bandwidth
    # XLA scatter-add update retire rate: the TPU scatter unit is
    # row-serial (~1 update row/cycle at ~1 GHz-ish issue) — the cost
    # structure that makes segment_sum the hash sketch's bottleneck.
    # Only the ORDER vs the kernel's MXU one-hot contraction matters.
    "scatter_rows_per_s": 1.2e9,
}

# VPU ops per generated operator entry: Threefry + inverse-CDF ≈ 50
# (sketch/params.py m-tile analysis; SURVEY §3.1).
GEN_OPS_PER_ENTRY = 50

# Fixed cost of one Pallas grid step (DMA issue, semaphores, the out
# tile's read-modify-write). On a v5e the resident-operator kernel took
# 24.8 ms at m_tile 256 against 23.4 at 512, 4096 steps apart: 0.37 µs a
# step (PERF.md §6, PR 27).
GRID_STEP_S = 0.35e-6

# MXU passes per logical f32 contraction at each kernel regime
# (sketch/pallas_dense._dot): bf16 single pass; bf16gen2 two;
# bf16x3 three; "f32" lowers to Precision.HIGHEST ≈ 6 bf16 passes.
MXU_PASSES = {"bf16": 1, "bf16gen2": 2, "bf16x3": 3, "f32": 6}

# The XLA paths' measured-regime factors, relative to the fused kernel's
# single-gemm traffic at the same shapes (BASELINE.md / hlo_cost_r05:
# the XLA Fastfood chain re-touches the (rows, NB) intermediate ~9x;
# the split variant ~3x).
_FASTFOOD_TRAFFIC_X = {"fused": 1.0, "split": 3.0, "xla_chain": 9.0}


# --------------------------------------------------------------------------
# measured calibration: ledger records -> per-rate constants
# --------------------------------------------------------------------------
#
# ``bench.py`` modes append ``cost_calib_<rate>`` records to
# ``benchmarks/ledger.json`` (e.g. ``cost_calib_scatter_rows_per_s``
# from the timed scatter microbench in ``--dist-serve``). When
# ``SKYLARK_COST_CALIB`` points at such a ledger (``auto`` = the repo
# copy), :func:`effective_rates` overlays those measurements on the
# analytic ``RATES`` — but ONLY records whose ``host_class`` matches
# this host (same platform + core-count formula as the ledger writer):
# a rate measured on a 16-core TPU runner must never recalibrate a
# 1-core CPU ranking. Latest matching record wins. Every rate carries
# provenance (:func:`rate_provenance`): ``analytic`` until a
# measurement says otherwise, so rankings only move when a measured
# number moved them — the property the tune tests pin.

# sentinel: "resolve the path from the env knob" (distinct from None,
# which callers may pass to mean "no calibration, pure RATES")
_CALIB_AUTO = object()

_calib_lock = _locks.make_lock("tune.cost.calib")
_calib_cache: dict = {}  # abspath -> (stat_sig, overlay, provenance)


def _host_class() -> str:
    """This host's comparability class — the exact formula
    ``bench.py._ledger_append`` stamps on every record."""
    try:
        import jax

        plat = jax.default_backend()
    except Exception:  # noqa: BLE001 — classification, not a gate
        plat = "unknown"
    return f"{plat}-{os.cpu_count()}c"


def _repo_ledger_path() -> str:
    root = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    return os.path.join(root, "benchmarks", "ledger.json")


def _resolve_calib_path(path) -> Optional[str]:
    if path is _CALIB_AUTO:
        path = _env.COST_CALIB.get()
    if path is None:
        return None
    if str(path).strip().lower() == "auto":
        return _repo_ledger_path()
    return str(path)


def _read_calibration(path: str, host_class: str) -> tuple[dict, dict]:
    """Parse one ledger file into ``(overlay, provenance)``. Tolerant
    of junk lines (the ledger is telemetry); only ``cost_calib_<rate>``
    records for a known rate, with a finite positive value and a
    matching host class, participate. Later records shadow earlier
    ones (latest measurement wins)."""
    overlay: dict = {}
    prov: dict = {}
    try:
        with open(path, encoding="utf-8") as f:
            lines = f.readlines()
    except OSError:
        return overlay, prov
    for lineno, line in enumerate(lines, start=1):
        line = line.strip()
        if not line:
            continue
        try:
            rec = json.loads(line)
        except ValueError:
            continue
        if not isinstance(rec, dict):
            continue
        metric = str(rec.get("metric", ""))
        if not metric.startswith("cost_calib_"):
            continue
        rate_name = metric[len("cost_calib_"):]
        if rate_name not in RATES:
            continue
        if rec.get("host_class") != host_class:
            continue
        try:
            value = float(rec.get("value"))
        except (TypeError, ValueError):
            continue
        if not math.isfinite(value) or value <= 0.0:
            continue
        overlay[rate_name] = value
        prov[rate_name] = {"source": "measured", "metric": metric,
                           "value": value, "host_class": host_class,
                           "path": path, "line": lineno}
    return overlay, prov


def _calibration(path) -> tuple[dict, dict]:
    """(overlay, measured-provenance) for ``path`` (env-resolved when
    the ``_CALIB_AUTO`` sentinel), memoized on the file's stat
    signature so repeated rankings don't re-read the ledger but a
    fresh bench append is picked up immediately."""
    resolved = _resolve_calib_path(path)
    if resolved is None:
        return {}, {}
    resolved = os.path.abspath(resolved)
    try:
        st = os.stat(resolved)
        sig = (st.st_mtime_ns, st.st_size)
    except OSError:
        sig = None
    with _calib_lock:
        hit = _calib_cache.get(resolved)
        if hit is not None and hit[0] == sig:
            return hit[1], hit[2]
    if sig is None:
        overlay, prov = {}, {}
    else:
        overlay, prov = _read_calibration(resolved, _host_class())
    with _calib_lock:
        _calib_cache[resolved] = (sig, overlay, prov)
    return overlay, prov


def effective_rates(path=_CALIB_AUTO) -> dict:
    """The rate table rankings actually consume: analytic ``RATES``
    overlaid with any matching measured ``cost_calib_*`` ledger
    records. Default resolves the ledger from ``SKYLARK_COST_CALIB``
    (unset → no overlay → exactly ``RATES``, so the analytic model is
    the fallback whenever no measurement exists); pass an explicit
    ledger path to calibrate from a specific file, or ``None`` for the
    pure analytic table."""
    overlay, _prov = _calibration(path)
    rates = dict(RATES)
    rates.update(overlay)
    return rates


def rate_provenance(path=_CALIB_AUTO) -> dict:
    """Per-rate provenance for :func:`effective_rates` at the same
    ``path``: ``{"source": "analytic"}`` for hand-set roofline
    constants, else ``{"source": "measured", "metric", "value",
    "host_class", "path", "line"}`` naming the ledger record that set
    it."""
    _overlay, prov = _calibration(path)
    return {name: dict(prov.get(name, {"source": "analytic"}))
            for name in RATES}


def _dense_operator_residency(w: Workload, m_tile: int) -> str:
    """Where the kernel would keep the generated operator between
    m-tiles — the kernel's OWN rule and budgets
    (pallas_dense.operator_residency) on the padded extents the kernel
    sees, so ranking can't drift from dispatch. sketch/ never imports
    tune/, so the import has no cycle to hide; it stays lazy to keep
    jax.experimental.pallas off ``import libskylark_tpu.tune``."""
    from libskylark_tpu.sketch.pallas_dense import (_padded_extents,
                                                    operator_residency)

    m, n, s = w.shape
    n_p, m_p = _padded_extents(n, m, m_tile)
    return operator_residency(s, n_p, m_p, m_tile)


def _dense_grid_steps(w: Workload, m_tile: int) -> int:
    """Grid steps of the dense kernel: m-tiles × operator column blocks
    (the stream format's panel width, sketch/dense.BLOCK_COLS)."""
    from libskylark_tpu.sketch.dense import BLOCK_COLS

    m, n, _s = w.shape
    return max(1, -(-m // m_tile)) * -(-n // BLOCK_COLS)


def plan_cost(w: Workload, p: Plan, rates: Optional[dict] = None) -> dict:
    """Modeled cost record for serving ``w`` with ``p``:
    ``{flops, bytes, gen_entries, modeled_s}``. See module doc — only
    the ordering of ``modeled_s`` across plans is meaningful. When
    ``rates`` is None the table comes from :func:`effective_rates`
    (analytic ``RATES`` unless ``SKYLARK_COST_CALIB`` names a ledger
    with matching measured records)."""
    rates = effective_rates() if rates is None else rates
    m, n, s = w.shape
    if w.op in FASTFOOD_OPS:
        return _fastfood_cost(w, p, rates)
    if w.op in HASH_OPS or w.op in SERVE_OPS:
        return _hash_or_serve_cost(w, p, rates)

    bytes_moved = 4.0 * (m * n + m * s)
    hbm_s = bytes_moved / rates["hbm_bytes_per_s"]
    if p.backend == "xla":
        # materialize S (one more operator-sized HBM round trip) + one
        # HIGHEST-precision gemm; generation runs once, fused by XLA
        flops = 2.0 * m * n * s * MXU_PASSES["f32"]
        gen_entries = float(n * s)
        xla_bytes = bytes_moved + 2.0 * 4.0 * n * s
        compute_s = (flops / rates["mxu_flops_per_s"]
                     + gen_entries * GEN_OPS_PER_ENTRY
                     / rates["vpu_ops_per_s"])
        modeled = max(xla_bytes / rates["hbm_bytes_per_s"], compute_s)
        return {"flops": flops, "bytes": xla_bytes,
                "gen_entries": gen_entries, "modeled_s": modeled}

    if p.backend != "pallas":
        raise ValueError(f"unknown dense backend {p.backend!r}")
    m_tile = p.m_tile or 512
    precision = p.precision or "bf16x3"
    flops = 2.0 * m * n * s * MXU_PASSES[precision]
    m_tiles = max(1, -(-m // m_tile))
    residency = _dense_operator_residency(w, m_tile)
    # generated once an apply when resident ("vmem"/"hbm"), once per
    # m-tile sweep otherwise
    gen_entries = float(n * s * (m_tiles if residency == "per_tile" else 1))
    if residency == "hbm":
        # the planes: written once, streamed back once per m-tile (bf16
        # hi/lo or one f32 plane 4 B an entry, one bf16 plane 2 B)
        plane_bytes = n * s * (2.0 if precision in ("bf16", "bf16gen2")
                               else 4.0)
        bytes_moved += plane_bytes * (1 + m_tiles)
        hbm_s = bytes_moved / rates["hbm_bytes_per_s"]
    mxu_s = flops / rates["mxu_flops_per_s"]
    gen_s = gen_entries * GEN_OPS_PER_ENTRY / rates["vpu_ops_per_s"]
    # the kernel serializes them (measured: a step costs generation
    # plus matmul, sketch/params.py m-tile note)
    compute_s = mxu_s + gen_s
    compute_s += _dense_grid_steps(w, m_tile) * GRID_STEP_S
    modeled = max(hbm_s, compute_s)
    return {"flops": flops, "bytes": bytes_moved,
            "gen_entries": gen_entries, "modeled_s": modeled}


def _device_runs_mosaic(device_kind: str) -> bool:
    """Whether ``device_kind`` compiles Mosaic kernels natively. Off-TPU
    a "pallas" plan means the pallas *interpreter* — a correctness
    surface, not a speed surface — so the model must never rank it
    above any XLA lowering there."""
    return normalize_device_kind(device_kind).startswith("tpu")


# Interpret-mode multiplier for pallas plans costed on a non-Mosaic
# host. The exact value is irrelevant (only ordering is consumed); it
# just has to dwarf every real kernel-vs-XLA ratio, so the serve tuner
# on a CPU host ALWAYS certifies the XLA path — the honest outcome the
# bench record and the CI pallas-serve gate pin.
INTERPRET_PENALTY = 1e4


def _hash_lane_cost(m: int, n: int, s: int, p: Plan,
                    rates: dict) -> dict:
    """One CWT/CountSketch lane (m non-contracted, n coordinates,
    s buckets). XLA: the ``segment_sum`` scatter — n update rows
    retired serially by the scatter unit, stream generation on the
    VPU. Pallas: the scatter-free one-hot contraction — 2·m·n·s MXU
    flops at HIGHEST (~6 bf16 passes), same generation bill, gen and
    matmul serialized (the hash kernel has no pipelined variant)."""
    bytes_moved = 4.0 * (m * n + m * s)
    hbm_s = bytes_moved / rates["hbm_bytes_per_s"]
    gen_entries = 2.0 * n          # h (bucket) + v (value) streams
    gen_s = gen_entries * GEN_OPS_PER_ENTRY / rates["vpu_ops_per_s"]
    if p.backend == "xla":
        scatter_s = n / rates["scatter_rows_per_s"]
        return {"flops": 2.0 * n * m, "bytes": bytes_moved,
                "gen_entries": gen_entries,
                "modeled_s": max(hbm_s, scatter_s + gen_s)}
    flops = 2.0 * m * n * s * MXU_PASSES["f32"]
    mxu_s = flops / rates["mxu_flops_per_s"]
    return {"flops": flops, "bytes": bytes_moved,
            "gen_entries": gen_entries,
            "modeled_s": max(hbm_s, mxu_s + gen_s)}


def _serve_dense_lane_cost(m: int, n: int, s: int, p: Plan,
                           rates: dict) -> dict:
    """One dense-family serve lane. XLA: materialize the operator +
    HIGHEST gemm (the vmapped ``serve_apply``). Pallas: the batched
    fused kernel at bf16x3 — no operator-cache scratch in the batched
    launcher, so generation is paid once per m-tile sweep and
    serialized against the MXU."""
    bytes_moved = 4.0 * (m * n + m * s)
    if p.backend == "xla":
        flops = 2.0 * m * n * s * MXU_PASSES["f32"]
        gen_entries = float(n * s)
        xla_bytes = bytes_moved + 2.0 * 4.0 * n * s
        compute_s = (flops / rates["mxu_flops_per_s"]
                     + gen_entries * GEN_OPS_PER_ENTRY
                     / rates["vpu_ops_per_s"])
        return {"flops": flops, "bytes": xla_bytes,
                "gen_entries": gen_entries,
                "modeled_s": max(xla_bytes / rates["hbm_bytes_per_s"],
                                 compute_s)}
    m_tile = p.m_tile or 256
    flops = 2.0 * m * n * s * MXU_PASSES["bf16x3"]
    sweeps = max(1, -(-m // m_tile))
    gen_entries = float(n * s * sweeps)
    compute_s = (flops / rates["mxu_flops_per_s"]
                 + gen_entries * GEN_OPS_PER_ENTRY
                 / rates["vpu_ops_per_s"])
    return {"flops": flops, "bytes": bytes_moved,
            "gen_entries": gen_entries,
            "modeled_s": max(bytes_moved / rates["hbm_bytes_per_s"],
                             compute_s)}


def _sparse_lane_cost(m: int, n: int, s: int, nnz: int,
                      rates: dict) -> dict:
    """One sparse-CSR serve lane (m kept extent, n sketched extent, s
    buckets, nnz the pow2 nonzero class — the quantity every term here
    scales with, which is the whole point of the sparse path): the
    O(nnz) ``scatter-add`` — nnz update rows retired serially by the
    scatter unit — plus the 2·n stream generation."""
    bytes_moved = 4.0 * (3 * nnz + m * s)  # CSR lanes in, dense out
    hbm_s = bytes_moved / rates["hbm_bytes_per_s"]
    gen_entries = 2.0 * n                  # h + v streams (full extent)
    gen_s = gen_entries * GEN_OPS_PER_ENTRY / rates["vpu_ops_per_s"]
    scatter_s = nnz / rates["scatter_rows_per_s"]
    return {"flops": 2.0 * nnz, "bytes": bytes_moved,
            "gen_entries": gen_entries,
            "modeled_s": max(hbm_s, scatter_s + gen_s)}


def _srht_lane_cost(m: int, n: int, s: int, rates: dict) -> dict:
    """One SRHT serve lane (m kept extent, n pow2 transform extent, s
    sampled rows): the panel-free lowering, priced as a kron-factored
    WHT of two HIGHEST matmuls against factors of size ~sqrt(n) each
    (4·m·n·sqrt(n) flops; ``fjlt.srht_serve_apply`` contracts factors
    of at most 128, fewer flops past n = 16384), the sign diagonal and
    sample gather ride the VPU."""
    bytes_moved = 4.0 * (m * n + m * s)
    hbm_s = bytes_moved / rates["hbm_bytes_per_s"]
    root = math.sqrt(float(n))
    flops = 4.0 * m * n * root * MXU_PASSES["f32"]
    gen_entries = float(n + s)     # sign diagonal + sample indices
    compute_s = (flops / rates["mxu_flops_per_s"]
                 + gen_entries * GEN_OPS_PER_ENTRY
                 / rates["vpu_ops_per_s"])
    return {"flops": flops, "bytes": bytes_moved,
            "gen_entries": gen_entries,
            "modeled_s": max(hbm_s, compute_s)}


def _cmm_cost(w: Workload, p: Plan, rates: dict) -> dict:
    """One compressed-approximate-matmul lane: sketch both operands
    down the shared contraction (A·Sᵀ and S·B) and multiply the
    (m×s)·(s×p) estimates. Always-XLA (the flush composes two existing
    sketch programs plus a small GEMM — there is no fused kernel), so
    a pallas plan is a caller bug, not a rankable candidate
    (:func:`_hash_or_serve_cost` refuses it). The workload's ``nnz``
    slot carries the kept extent of B (p) — the shape triple only has
    room for (m, n, s)."""
    m, n, s = w.bucket()
    pk = max(int(w.nnz), 1)            # kept extent of B, pow2 class
    if w.transform == "SRHT":
        ska, skb = (_srht_lane_cost(e, n, s, rates) for e in (m, pk))
    else:
        ska, skb = (_hash_lane_cost(e, n, s, p, rates) for e in (m, pk))
    gemm_flops = 2.0 * m * s * pk * MXU_PASSES["f32"]
    gemm_bytes = 4.0 * (m * s + s * pk + m * pk)
    gemm_s = max(gemm_flops / rates["mxu_flops_per_s"],
                 gemm_bytes / rates["hbm_bytes_per_s"])
    return {"flops": ska["flops"] + skb["flops"] + gemm_flops,
            "bytes": ska["bytes"] + skb["bytes"] + gemm_bytes,
            "gen_entries": ska["gen_entries"] + skb["gen_entries"],
            "modeled_s": ska["modeled_s"] + skb["modeled_s"] + gemm_s}


def _hash_or_serve_cost(w: Workload, p: Plan, rates: dict) -> dict:
    """Cost record for the hash direct-apply sites and the serve-bucket
    sites. Serve workloads scale one lane's cost by the batch capacity
    class (``w.batch``); pallas plans costed for a non-Mosaic device
    kind carry the interpret-mode penalty, so an offline ranking run on
    a CPU host correctly certifies XLA for every serve bucket."""
    if p.backend not in ("pallas", "xla"):
        raise ValueError(
            f"unknown {w.op} backend {p.backend!r} (pallas|xla)")
    if p.backend != "xla" and xla_only(w):
        raise ValueError(
            f"{w.op} ({w.transform}) has no pallas kernel; only the "
            "XLA flush exists")
    m, n, s = w.bucket()
    if w.op == "serve_cmm":
        rec = _cmm_cost(w, p, rates)
    elif w.op == "serve_fastfood":
        ff = Plan("fused" if p.backend == "pallas" else "xla_chain",
                  precision=p.precision)
        rec = _fastfood_cost(w, ff, rates)
    elif w.op in SPARSE_SERVE_OPS:
        rec = _sparse_lane_cost(m, n, s, max(int(w.nnz), 1), rates)
    elif w.op in HASH_OPS or w.transform == "CWT":
        rec = _hash_lane_cost(m, n, s, p, rates)
    elif w.transform == "SRHT":
        rec = _srht_lane_cost(m, n, s, rates)
    elif w.transform in SERVE_DENSE_FAMILIES:
        rec = _serve_dense_lane_cost(m, n, s, p, rates)
    else:
        raise ValueError(
            f"serve workload family {w.transform!r} has no cost model")
    lanes = max(int(w.batch), 1) if w.op in SERVE_OPS else 1
    if lanes > 1:
        rec = {k: v * lanes for k, v in rec.items()}
    if p.backend == "pallas" and not _device_runs_mosaic(w.device_kind):
        rec["modeled_s"] *= INTERPRET_PENALTY
        rec["interpret"] = True
    return rec


def _fastfood_cost(w: Workload, p: Plan, rates: dict) -> dict:
    m, _d, s = w.shape
    # block length NB ≥ s for the single-block case; the chain computes
    # nb·NB ≥ s features. Use s rounded to the bucket as the effective
    # feature extent — exact block math is the kernel's business.
    nb_feats = max(s, 512)
    base_bytes = 4.0 * m * nb_feats  # one intermediate-sized touch
    traffic_x = _FASTFOOD_TRAFFIC_X.get(p.backend)
    if traffic_x is None:
        raise ValueError(f"unknown fastfood backend {p.backend!r}")
    bytes_moved = base_bytes * (1.0 + traffic_x)
    # two WHTs as kron-factored dots: 2 · 2·m·NB·(√NB+√NB) ≈
    # 4·m·NB^1.5 flops per pass
    passes = MXU_PASSES[p.precision or "bf16x3"] if p.backend != \
        "xla_chain" else MXU_PASSES["f32"]
    flops = 4.0 * m * nb_feats * (nb_feats ** 0.5) * passes
    modeled = max(bytes_moved / rates["hbm_bytes_per_s"],
                  flops / rates["mxu_flops_per_s"])
    return {"flops": flops, "bytes": bytes_moved, "gen_entries": 0.0,
            "modeled_s": modeled}


def rank_plans(w: Workload, plans: Sequence[Plan],
               rates: Optional[dict] = None
               ) -> list[tuple[Plan, dict]]:
    """Deterministically rank ``plans`` for ``w``: ascending modeled
    seconds, ties broken by plan_id. The offline pre-ranking a live TPU
    window's top-k measurement starts from. ``rates=None`` resolves
    through :func:`effective_rates`, so a measured ``cost_calib_*``
    ledger record can flip a ranking — and nothing else can."""
    rates = effective_rates() if rates is None else rates
    scored = [(p, plan_cost(w, p, rates)) for p in plans]
    scored.sort(key=lambda pc: (pc[1]["modeled_s"], pc[0].plan_id()))
    return scored
