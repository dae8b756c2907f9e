"""Shape classes for microbatch serving: pow2 pad-and-mask policy.

The serving executor (:mod:`libskylark_tpu.engine.serve`) coalesces
concurrent requests into one vmapped executable per *bucket*. A bucket
is the set of requests that can share a compiled program: same endpoint
statics (sketch family, sketch dim, solve method, kernel digest, ...),
same dtype, and the same **shape class** — every paddable dimension
rounded up to the next power of two (with a floor, so tiny requests
don't fragment into one-off buckets). Two ragged requests in one class
are padded to the class shape with zeros; the endpoints' virtual random
streams are positional, so zero-padding is *bit-exact*, not just
masked-approximate (see ``sketch.dense.serve_apply``).

The batch dimension gets the same treatment: a cohort of k requests
runs at the pow2 **capacity class** ≥ k (clamped to ``max_batch``,
rounded to the mesh's device count when the batch is sharded), with
filler lanes replicating the last real request. Steady-state traffic
therefore compiles one executable per (bucket, capacity class) and
never again — the zero-recompile property the CI serve gate asserts.

The cost of padding is wasted MXU work, tracked by the executor as
``padding_waste`` (1 − real elements / padded elements over the primary
operand). Halving the pow2 growth (``geometric=√2``-style classes)
would halve worst-case waste at the price of ~2× more buckets; the
pow2 default keeps the executable population small, which is what
bounds compile time and cache pressure in a serve-many process.
"""

from __future__ import annotations

from typing import Sequence

import numpy as np

# Smallest padded extent: dimensions below this share one class, so a
# flood of tiny requests (the microbatching sweet spot) lands in a
# single bucket instead of one per exact shape.
PAD_FLOOR = 8


def pow2_pad(n: int, floor: int = PAD_FLOOR) -> int:
    """The shape class of extent ``n``: next power of two ≥ max(n, floor)."""
    n = max(int(n), int(floor))
    return 1 << (n - 1).bit_length()


def pad_shape(shape: Sequence[int], pad_axes: Sequence[int],
              floor: int = PAD_FLOOR) -> tuple[int, ...]:
    """Round the extents named by ``pad_axes`` up to their pow2 class;
    other extents are exact-match bucket components (e.g. the feature
    dimension of a solve, which cannot be zero-padded without making
    the compressed problem singular)."""
    pad_axes = set(int(a) for a in pad_axes)
    return tuple(
        pow2_pad(e, floor) if i in pad_axes else int(e)
        for i, e in enumerate(shape)
    )


def nnz_class(nnz: int, floor: int = 64) -> int:
    """The **nnz class** of a sparse operand: next power of two ≥
    max(nnz, floor). Sparse serve buckets key on this alongside the
    padded dims/dtype (docs/serving, "Sparse operands on the serve
    path"): two ragged-nnz requests in one class pad their (data,
    indices) lanes to the class extent and coalesce into one flush
    executable — padding entries carry value 0.0 at position 0, which
    contributes exact zeros through every sparse endpoint. The floor
    (``SKYLARK_SPARSE_NNZ_FLOOR``) keeps a flood of tiny sparse
    requests in a single bucket, the same anti-fragmentation role
    ``PAD_FLOOR`` plays for dense extents."""
    return pow2_pad(nnz, max(int(floor), 1))


def lane_class(nnz: int) -> int:
    """The lane extent of a **device-resident** sparse operand
    (``SparseMatrix.csr_device``): max(nnz, 64) rounded up to a
    thirty-second of its power of two, so under a sixteenth of the lanes
    are padding and an octave holds sixteen classes. Row blocks of one
    corpus (nnz within a few percent of each other) share one compiled
    program, as they would under :func:`nnz_class`; but a resident
    operand pays for every lane on every apply, and pow2 padding is up to
    half of them: 19.4 M nonzeros → 2²⁵ lanes, 674 ms an apply on a v5e
    against 379 ms at this class and 369 ms unpadded, which compiles anew
    for every operand (PERF.md PR 28). The serve tier keeps
    :func:`nnz_class`: there a class is a bucket requests coalesce in,
    and fewer, wider classes are the point."""
    n = max(int(nnz), 64)
    granule = pow2_pad(n) >> 5
    return -(-n // granule) * granule


def result_lanes(lanes_in: int) -> int:
    """The lane extent of a sparse → sparse hash sketch's **result**
    (``HashTransform.apply_sparse``): its operand's. A hash sketch relabels
    each stored nonzero 1:1 and sums the collisions, so ``nnz_out ≤ nnz_in``
    and the operand's :func:`lane_class` always holds the result, whose own
    count is data the program cannot size an array by; the blocks of one
    corpus then share one executable on the way in and on the way out, and
    a result fed to a second sketch presents the class it was born with."""
    return int(lanes_in)


def capacity_class(k: int, max_batch: int, multiple: int = 1) -> int:
    """Batch capacity for a cohort of ``k`` requests: pow2 ≥ k, clamped
    to ``max_batch``, then rounded up to ``multiple`` (the mesh device
    count when the batch dimension is sharded — every shard must get
    the same lane count)."""
    cap = min(1 << (max(int(k), 1) - 1).bit_length(), int(max_batch))
    m = max(int(multiple), 1)
    cap = ((cap + m - 1) // m) * m
    return max(cap, 1)


def capacity_ladder(max_batch: int, multiple: int = 1) -> tuple:
    """Every capacity class reachable below ``max_batch`` — the pow2
    rungs (rounded to ``multiple``), ascending. Any bucket's *warm*
    capacity set — the rungs it has actually flushed at, which is
    what the adaptive batching controller
    (:mod:`libskylark_tpu.qos.controller`) restricts its batch-target
    moves to — is a subset of this ladder; warmup drivers and
    capacity planning enumerate it to pre-compile the whole set."""
    rungs = []
    k = 1
    while k <= int(max_batch):
        cap = capacity_class(k, max_batch, multiple)
        if not rungs or cap != rungs[-1]:
            rungs.append(cap)
        k <<= 1
    # a non-pow2 max_batch clamps full cohorts to a rung the pow2
    # sweep never visits (capacity_class(12, 12) = 12) — the most
    # common capacity under load must be on the ladder
    top = capacity_class(int(max_batch), max_batch, multiple)
    if top != rungs[-1]:
        rungs.append(top)
    return tuple(rungs)


def stack_pad(arrays: Sequence[np.ndarray], padded_shape: Sequence[int],
              capacity: int, dtype) -> np.ndarray:
    """One host-side (capacity, *padded_shape) buffer holding every
    request's operand zero-padded into its top-left corner, filler
    lanes replicating the last real request (replication, not zeros:
    a zero operand can hit degenerate branches — a singular QR, a NaN
    cond — and a filler lane must cost exactly one real lane, never
    poison the flush). The buffer is freshly allocated per flush: the
    executor donates it to the executable, so reuse across flushes
    would re-read a deleted buffer."""
    padded_shape = tuple(int(e) for e in padded_shape)
    out = np.zeros((int(capacity),) + padded_shape, dtype=dtype)
    for i, a in enumerate(arrays):
        a = np.asarray(a)
        out[(i,) + tuple(slice(0, e) for e in a.shape)] = a
    for i in range(len(arrays), int(capacity)):
        out[i] = out[len(arrays) - 1]
    return out


def padded_elements(padded_shape: Sequence[int], capacity: int) -> int:
    return int(capacity) * int(np.prod([int(e) for e in padded_shape]))


def real_elements(shapes: Sequence[Sequence[int]]) -> int:
    return int(sum(int(np.prod([int(e) for e in s])) for s in shapes))


def result_nbytes(value) -> int:
    """Byte accounting of one serve result for the cache/residency
    quotas (docs/caching): host arrays count their buffer, containers
    sum their array members, anything else counts a conservative
    64-byte overhead. This is the same element-accounting layer the
    padding-waste counters use — quota arithmetic must agree across
    every executor, so it lives here rather than per call site."""
    if isinstance(value, np.ndarray):
        return int(value.nbytes)
    if isinstance(value, (tuple, list)):
        return 64 + sum(result_nbytes(v) for v in value)
    if isinstance(value, dict):
        return 64 + sum(result_nbytes(v) for v in value.values())
    if isinstance(value, (bytes, bytearray)):
        return len(value)
    return 64
