"""Kernel-plan and workload descriptors for the sketch-apply autotuner.

A **workload** names a sketch-apply hot-path invocation abstractly enough
to be cached across processes: ``(device_kind, op, transform, dtype,
shape bucket)``. A **plan** names every tuning decision the dispatchers
can make for it: which backend serves the apply (fused Pallas kernel vs
the XLA path; fused vs split Fastfood variant), the Pallas ``m_tile``
and the contraction-precision regime.

Shapes are bucketed to the next power of two so one certified plan
serves a neighborhood of shapes — the kernels' own qualification
(``pallas_dense._qualify``) re-validates the concrete shape at dispatch
and shrinks/declines as needed, so a bucket can never force an invalid
configuration, only a suboptimal one.

Candidate enumeration is the offline half of the tuner: it lists every
plan worth considering for a workload so :mod:`tune.cost` can pre-rank
them without hardware and a live TPU window measures only the top-k
(TPU windows have been scarce for four straight rounds — a window must
certify the best config, not probe for it). Accuracy-opt-in regimes
("bf16", "bf16gen2" on data contractions) are enumerated only with
``allow_fast=True``: the autotuner must never auto-select a regime the
1e-4 determinism oracle doesn't cover.
"""

from __future__ import annotations

import dataclasses
from typing import Iterator, Optional, Sequence

# Dense-kernel m-tile candidates: powers of two spanning the regimes the
# r2/r3 on-chip sweeps explored. _qualify pre-shrinks over-budget tiles,
# so enumeration may include tiles a given s_dim can't hold.
DENSE_M_TILES = (128, 256, 512, 1024)

# Oracle-grade contraction regimes (auto-selectable) vs throughput
# regimes (opt-in via allow_fast; see sketch/params.py regime docs).
ORACLE_PRECISIONS = ("bf16x3", "f32")
FAST_PRECISIONS = ("bf16gen2", "bf16")

# ops the dense kernel can serve
DENSE_OPS = ("dense_rowwise", "dense_columnwise", "rft_rowwise")
FASTFOOD_OPS = ("fastfood_rows",)

# hash (CWT/CountSketch) direct-apply dispatch sites — the scatter-free
# kernel (sketch/pallas_hash.py) vs the XLA segment_sum scatter
HASH_OPS = ("hash_rowwise", "hash_columnwise")

# serve-bucket dispatch sites (engine/serve.py flush builders): one
# workload per (endpoint/orientation, transform family, dtype, pow2
# shape class, batch capacity class). The ``batch`` field carries the
# capacity class; backends are "pallas" (the endpoint's batched kernel
# — hash, dense or fused-fastfood) vs "xla" (the vmapped XLA flush).
# The sparse ops additionally carry the pow2 **nnz class**
# (``Workload.nnz``) — the scatter's cost is a function of the nonzero
# count, not the dense extents.
SERVE_OPS = ("serve_sketch_cw", "serve_sketch_rw", "serve_fastfood",
             "serve_sparse_cw", "serve_sparse_rw", "serve_cmm")

# the sparse-CSR serve sites (subset of SERVE_OPS): the XLA O(nnz)
# scatter, the one program they have
SPARSE_SERVE_OPS = ("serve_sparse_cw", "serve_sparse_rw")

# dense-family serve buckets enumerate a small m-tile ladder (the
# batched kernel's only knob); CWT/fastfood serve kernels are knobless.
SERVE_DENSE_M_TILES = (128, 256, 512)

# serve families whose sketch operator is a dense virtual stream, and
# the dense-kernel distribution each maps onto (the serve workload's
# ``transform`` field carries the FAMILY tag, the cost model prices the
# underlying stream)
SERVE_DENSE_FAMILIES = {"JLT": "normal", "CT": "cauchy"}


def bucket_dim(x: int) -> int:
    """Next power of two ≥ x (min 8): one cache entry serves the whole
    bucket; concrete-shape feasibility stays the dispatcher's job."""
    x = max(int(x), 8)
    return 1 << (x - 1).bit_length()


def normalize_device_kind(kind: str) -> str:
    """Canonical cache-key form of ``jax.Device.device_kind`` (or
    "cpu"): lowercased, runs of non-alphanumerics collapsed to one
    underscore, so "TPU v5 lite" and "tpu-v5-lite" key identically."""
    import re

    return re.sub(r"[^a-z0-9]+", "_", str(kind).lower()).strip("_")


def current_device_kind() -> str:
    import jax

    return normalize_device_kind(jax.devices()[0].device_kind)


@dataclasses.dataclass(frozen=True)
class Workload:
    """One cacheable hot-path invocation class.

    ``op``: dispatch site — one of DENSE_OPS / FASTFOOD_OPS.
    ``transform``: the operator stream kind — a distribution kind
    ("normal"/"cauchy"/"rademacher") for dense ops, the transform's
    ``sketch_type`` for Fastfood.
    ``shape``: (m, n, s) — m the non-contracted input extent, n the
    contracted (sketched) extent, s the sketch/feature dimension.
    """

    device_kind: str
    op: str
    transform: str
    dtype: str
    shape: tuple[int, int, int]
    # batch capacity class (serve workloads only; 0 = not batched).
    # Appended to the key only when set, so every pre-serve cache key —
    # including the committed benchmarks/plan_cache.json entries —
    # is unchanged.
    batch: int = 0
    # pow2 nnz class (sparse serve workloads only; 0 = dense). Same
    # append-only key rule as ``batch``: pre-sparse keys are unchanged.
    nnz: int = 0

    def bucket(self) -> tuple[int, int, int]:
        return tuple(bucket_dim(d) for d in self.shape)

    def key(self) -> str:
        b = "x".join(str(d) for d in self.bucket())
        base = "|".join((normalize_device_kind(self.device_kind),
                         self.op, self.transform, str(self.dtype), b))
        if self.batch:
            base = f"{base}|b{self.batch}"
        if self.nnz:
            base = f"{base}|z{self.nnz}"
        return base


@dataclasses.dataclass(frozen=True)
class Plan:
    """One complete tuning decision for a workload.

    ``backend``: "pallas" | "xla" for dense ops; "fused" | "split" |
    "xla_chain" for Fastfood. The XLA backends carry no knobs — they
    mean "take the existing non-kernel path".
    """

    backend: str
    m_tile: Optional[int] = None
    precision: Optional[str] = None

    def plan_id(self) -> str:
        """Deterministic short id — the label bench records carry and
        tie-break ranking sorts by."""
        parts = [self.backend]
        if self.m_tile is not None:
            parts.append(f"mt{self.m_tile}")
        if self.precision is not None:
            parts.append(self.precision)
        return "/".join(parts)

    @classmethod
    def from_plan_id(cls, token: str,
                     known_backends=("pallas", "xla")) -> "Plan | None":
        """Invert :meth:`plan_id` (the warmup-pack manifests persist
        plan ids as the per-bucket kernel decision). Kept next to the
        encoder so the two formats cannot drift apart silently.
        Returns None for a token this build does not understand — an
        unknown backend, an empty part, or (since every known
        component is matched explicitly) more than one free-form
        precision part. A ``pipe`` part (the pipelined-generation
        kernel, deleted in PR 30) is skipped: packs and cache files
        written by older trees are input from outside the program."""
        parts = str(token).split("/")
        if not parts or parts[0] not in known_backends:
            return None
        m_tile = None
        precision = None
        for p in parts[1:]:
            if p.startswith("mt") and p[2:].isdigit():
                m_tile = int(p[2:])
            elif p == "pipe":
                continue
            elif p and precision is None:
                precision = p
            else:
                return None
        return cls(backend=parts[0], m_tile=m_tile, precision=precision)

    def to_dict(self) -> dict:
        d = {"backend": self.backend}
        if self.m_tile is not None:
            d["m_tile"] = int(self.m_tile)
        if self.precision is not None:
            d["precision"] = self.precision
        return d

    @classmethod
    def from_dict(cls, d: dict) -> "Plan":
        # a stored "pipeline" key (older trees) is ignored
        return cls(
            backend=str(d["backend"]),
            m_tile=(int(d["m_tile"]) if d.get("m_tile") is not None
                    else None),
            precision=d.get("precision"),
        )


def _dense_candidates(w: Workload, precisions: Sequence[str]
                      ) -> Iterator[Plan]:
    m, _n, _s = w.bucket()
    for prec in precisions:
        for mt in DENSE_M_TILES:
            if mt > m:
                continue
            yield Plan("pallas", m_tile=mt, precision=prec)
    yield Plan("xla")


def _fastfood_candidates(precisions: Sequence[str]) -> Iterator[Plan]:
    for prec in precisions:
        yield Plan("fused", precision=prec)
        yield Plan("split", precision=prec)
    yield Plan("xla_chain")


def xla_only(w: Workload) -> bool:
    """Serve buckets whose flush is the vmapped lane program alone: the
    sparse ones, the SRHT ones and the compressed-matmul endpoint."""
    return (w.op == "serve_cmm" or w.op in SPARSE_SERVE_OPS
            or w.transform == "SRHT")


def _serve_candidates(w: Workload) -> Iterator[Plan]:
    """Kernel-vs-XLA candidates for one serve bucket. The dense
    families enumerate the batched kernel's m-tile ladder; the hash
    and fastfood serve kernels are knobless — precision stays the
    serve layer's own policy (oracle regimes only), so a committed
    cache entry can never opt a flush into bf16. The sparse buckets,
    the SRHT buckets and the compressed-matmul endpoint have no
    batched kernel (their flush is the vmapped lane program) and
    enumerate exactly one plan."""
    if xla_only(w):
        yield Plan("xla")
        return
    if w.transform in SERVE_DENSE_FAMILIES:
        m, _n, _s = w.bucket()
        for mt in SERVE_DENSE_M_TILES:
            if mt <= max(m, SERVE_DENSE_M_TILES[0]):
                yield Plan("pallas", m_tile=mt)
    else:
        yield Plan("pallas")
    yield Plan("xla")


def enumerate_candidates(w: Workload,
                         allow_fast: bool = False) -> list[Plan]:
    """Every plan worth ranking for ``w``. The dense list crosses
    m-tiles × precision regimes, plus the XLA fallback; Fastfood crosses variant × precision plus the XLA chain;
    hash and serve buckets cross the scatter-free kernel vs the XLA
    path. ``allow_fast`` adds the accuracy-opt-in regimes (never
    auto-selected by default — see module doc)."""
    precisions = ORACLE_PRECISIONS + (FAST_PRECISIONS if allow_fast
                                      else ())
    if w.op in DENSE_OPS:
        return list(_dense_candidates(w, precisions))
    if w.op in FASTFOOD_OPS:
        return list(_fastfood_candidates(precisions))
    if w.op in HASH_OPS:
        return [Plan("pallas"), Plan("xla")]
    if w.op in SERVE_OPS:
        return list(_serve_candidates(w))
    raise ValueError(f"unknown workload op {w.op!r}")
