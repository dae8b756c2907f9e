"""Hash-based sparse-embedding sketches: CWT (CountSketch), MMT, WZT.

TPU-native analog of the reference's hash_transform family
(ref: sketch/hash_transform_data.hpp:21-104, sketch/CWT_data.hpp:23-70,
sketch/MMT_data.hpp:22-60, sketch/WZT_data.hpp:27-124).

The transform is defined by two virtual streams over the allocation key:
``row_idx`` — a uniform bucket in [0, S) per input coordinate — and
``row_value`` — a per-coordinate scaling (Rademacher for CWT, Cauchy for MMT,
signed reciprocal-exponential for WZT). Where the reference applies these with
O(nnz) CSC scatter loops (ref: sketch/hash_transform_Elemental.hpp:83-124),
the TPU-native formulation of a dense operand's apply is a ``segment_sum`` —
a dataflow scatter-add XLA maps onto the VPU, and which under a sharded input
becomes a local segment-sum + psum exactly like the reference's
local-accumulate + all_reduce pattern
(ref: sketch/hash_transform_Elemental.hpp:427-607).

A :class:`~libskylark_tpu.base.sparse.SparseMatrix` operand runs one compiled
program an apply (``engine.compiled``, name ``sketch.hash_sparse``): the pure
function the sparse serve flush vmaps
(:func:`libskylark_tpu.sketch.sparse_serve.cwt_sparse_serve_apply`) on the
operand's device-resident row-major lanes — each stored nonzero's bucket
computed at its lane from the allocation's key data and the nonzero's
coordinate (``randgen.stream_at``: the counter cipher, no table of the
stream and no gather; the CountSketch's sign likewise, MMT's and WZT's
values still gathered from their stream's table), and one O(nnz)
scatter-add in row-major order, which is the order the dense
``segment_sum`` retires the same terms in: the result is bit-equal to
``apply(A.todense())`` (ref: sketch/hash_transform_local_sparse.hpp:12-152).
On a TPU the rowwise apply adds the terms up in a Pallas kernel instead,
each tile of result rows in VMEM from its own run of lanes
(``sparse_serve.sparse_kernel`` says where; ``sketch/pallas_sparse.py``
``hash_rows_apply``): the same exact products, the terms of one cell added
in another order — last ulp against the scatter, a cell with one term to
the bit.

``apply_sparse`` keeps the result sparse (upstream's CSC → CSC engine, the
hashing trick at its own width: a (rows × 2¹⁸) dense result cannot exist):
one compiled program again (``sketch.hash_sparse_out``,
:func:`libskylark_tpu.sketch.sparse_serve.cwt_sparse_out_serve_apply`) —
the same lane streams, then the coalescing of
:mod:`libskylark_tpu.sketch.sparse_coalesce` (sort each row's relabelled
lanes by bucket, sum the collisions, compact, rebuild the row pointers) —
whose result is a canonical ``SparseMatrix`` born on the device, its host
side lazy and its stored count a device scalar until asked for.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from libskylark_tpu.base import errors, randgen
from libskylark_tpu.sketch.transform import SketchTransform, register
from libskylark_tpu.telemetry import metrics as _metrics
from libskylark_tpu.telemetry import trace as _trace

_SPARSE_NNZ = _metrics.counter(
    "sketch.sparse_nnz",
    "Stored nonzeros sketched through the compiled sparse hash apply, "
    "by family and kernel")
_SPARSE_MERGED = _metrics.counter(
    "sketch.sparse_merged",
    "Lanes a sparse -> sparse hash apply's collisions merged away (stored "
    "nonzeros in less stored nonzeros out), counted when the result's count "
    "is first read, by family and kernel")


def value_stream(kind: tuple, key, n: int, dtype) -> jnp.ndarray:
    """v[0:n] of the hash family ``kind`` — ``("CWT",)``, ``("MMT",)`` or
    ``("WZT", p)`` — under the allocation key ``key``: a pure function,
    shared by the transforms' own ``values()`` and the compiled sparse
    program (where ``kind`` is a static argument)."""
    def stream(tag, dist):
        return randgen.stream_slice(jax.random.fold_in(key, tag), dist, 0, n,
                                    dtype=dtype)

    if kind[0] == "CWT":
        return stream(1, randgen.Rademacher())
    if kind[0] == "MMT":
        return stream(1, randgen.Cauchy())
    if kind[0] == "WZT":
        e, pm = stream(1, randgen.Exponential()), stream(2, randgen.Rademacher())
        return pm * jnp.power(1.0 / e, 1.0 / kind[1])
    raise errors.InvalidParametersError(f"no hash value stream {kind!r}")


@functools.lru_cache(maxsize=None)
def _sparse_program():
    """The compiled sparse apply, built at the first sparse operand so that
    importing the sketch layer never pulls the engine."""
    from libskylark_tpu.engine.compiled import compiled
    from libskylark_tpu.sketch.sparse_serve import cwt_sparse_serve_apply

    return compiled(
        cwt_sparse_serve_apply, name="sketch.hash_sparse",
        static_argnames=("s_dim", "rowwise", "shape", "values", "kernel"))


@functools.lru_cache(maxsize=None)
def _sparse_out_program():
    """The compiled sparse → sparse apply (``apply_sparse``), built like
    :func:`_sparse_program` at the first call."""
    from libskylark_tpu.engine.compiled import compiled
    from libskylark_tpu.sketch.sparse_serve import cwt_sparse_out_serve_apply

    return compiled(
        cwt_sparse_out_serve_apply, name="sketch.hash_sparse_out",
        static_argnames=("s_dim", "rowwise", "shape", "values", "form",
                         "cap"))


def cwt_serve_apply(key_data, A, *, s_dim: int, rowwise: bool) -> jnp.ndarray:
    """Pure, vmap-batchable CWT apply for the microbatch serving layer
    (:mod:`libskylark_tpu.engine.serve`): one request's CountSketch as a
    function of the transform's raw key data ((2,) uint32 from
    ``jax.random.key_data``). The bucket/value streams are positional —
    identical to :meth:`HashTransform.bucket_indices` /
    :meth:`CWT.values` over the first N coordinates — so zero-padding
    the operand past the transform's true N leaves the result bit-equal:
    padded coordinates scatter-add exact zeros."""
    import jax.random as jr

    key = jr.wrap_key_data(jnp.asarray(key_data))
    n = A.shape[1] if rowwise else A.shape[0]
    h = randgen.stream_slice(
        jax.random.fold_in(key, 0), randgen.UniformInt(0, s_dim - 1),
        0, n, dtype=jnp.int32)
    v = randgen.stream_slice(
        jax.random.fold_in(key, 1), randgen.Rademacher(), 0, n,
        dtype=A.dtype)
    if rowwise:
        return jax.ops.segment_sum(v[:, None] * A.T, h,
                                   num_segments=s_dim).T
    return jax.ops.segment_sum(v[:, None] * A, h, num_segments=s_dim)


class HashTransform(SketchTransform):
    """Base: SA[h[j], :] += v[j] * A[j, :] (columnwise)."""

    sketch_type = "HashTransform"

    def bucket_indices(self) -> jnp.ndarray:
        """h[0:N] — bucket of each input coordinate (sub-stream 0)."""
        return randgen.stream_slice(
            self.subkey(0), randgen.UniformInt(0, self._S - 1), 0, self._N,
            dtype=jnp.int32,
        )

    def _value_kind(self) -> tuple:
        """Static description of the value stream (``value_stream``)."""
        return (self.sketch_type,)

    def values(self, dtype=jnp.float32) -> jnp.ndarray:
        """v[0:N] — per-coordinate scaling (sub-streams 1, 2)."""
        return value_stream(self._value_kind(), self._alloc.key, self._N,
                            dtype)

    def _apply_columnwise(self, A: jnp.ndarray) -> jnp.ndarray:
        out = self._try_kernel(A, rowwise=False)
        if out is not None:
            return out
        h = self.bucket_indices()
        v = self.values(A.dtype)
        return jax.ops.segment_sum(v[:, None] * A, h, num_segments=self._S)

    def _apply_rowwise(self, A: jnp.ndarray) -> jnp.ndarray:
        out = self._try_kernel(A, rowwise=True)
        if out is not None:
            return out
        h = self.bucket_indices()
        v = self.values(A.dtype)
        return jax.ops.segment_sum(v[:, None] * A.T, h, num_segments=self._S).T

    def _try_kernel(self, A, *, rowwise: bool):
        """Scatter-free Pallas dispatch (sketch/pallas_hash.py) — CWT on
        a qualifying TPU operand, routed only by an explicit override
        (``SKYLARK_HASH_KERNEL``); None declines and the
        ``segment_sum`` scatter below serves (see
        ``pallas_hash.try_apply``)."""
        from libskylark_tpu.sketch import pallas_hash

        return pallas_hash.try_apply(self, A, rowwise=rowwise)

    # -- sparse input: one compiled O(nnz) scatter program an apply (the
    # dataflow form of ref: sketch/hash_transform_local_sparse.hpp:12-152) --

    def _apply_sparse(self, A, *, rowwise: bool) -> jnp.ndarray:
        from libskylark_tpu.sketch.sparse_serve import lookup, sparse_kernel

        data, indices, indptr = A.csr_device()
        key_data = self._alloc.key_data
        values = self._value_kind()
        kernel = sparse_kernel(A.shape, self._S, int(data.shape[0]),
                               data.dtype, rowwise)
        with _trace.span("sketch.dispatch",
                         {"path": "sparse", "family": self.sketch_type,
                          "nnz": A.nnz, "nnz_class": int(data.shape[0]),
                          "lookup": lookup(values), "kernel": kernel,
                          "walk": "flat"}):
            out = _sparse_program()(
                key_data, data, indices, indptr, s_dim=self._S,
                rowwise=rowwise, shape=A.shape, values=values, kernel=kernel)
        _SPARSE_NNZ.inc_always(A.nnz, family=self.sketch_type, kernel=kernel)
        return out

    def _apply_columnwise_sparse(self, A) -> jnp.ndarray:
        return self._apply_sparse(A, rowwise=False)

    def _apply_rowwise_sparse(self, A) -> jnp.ndarray:
        return self._apply_sparse(A, rowwise=True)

    # -- distributed sparse input (P4/P5): local scatter + psum (ref:
    # sketch/hash_transform_CombBLAS.hpp:16-632) --

    def _apply_columnwise_dist_sparse(self, A) -> jnp.ndarray:
        from libskylark_tpu.sketch import dist_sparse_apply as dsa

        return dsa.hash_columnwise(self, A)

    def _apply_rowwise_dist_sparse(self, A) -> jnp.ndarray:
        from libskylark_tpu.sketch import dist_sparse_apply as dsa

        return dsa.hash_rowwise(self, A)

    def apply_sparse(self, A, dimension=None):
        """Sparse → sparse apply (ref: sketch/hash_transform_local_sparse
        .hpp:12-152, CSC → CSC with duplicates summed): returns a
        :class:`SparseMatrix` **born on the device**
        (``SparseMatrix.from_device_csr``), its lanes made by ONE compiled
        program (``engine.compiled``, ``sketch.hash_sparse_out``:
        :func:`sparse_serve.cwt_sparse_out_serve_apply`) on the operand's
        device-resident lanes.

        Every stored nonzero (r, c, x) contributes v(c)·x to (r, h(c))
        rowwise — v(r)·x to (h(r), c) columnwise — exactly once, h and v
        the very streams :meth:`apply` uses, so ``apply_sparse(A)
        .todense()`` equals ``apply(A)`` wherever the dense result can be
        held. The result is canonical: each row's columns ascending and
        distinct, collisions summed in float32, ``indptr`` exact, the lanes
        past the stored count 0.0 at column 0, the lane extent the
        operand's (``engine.bucket.result_lanes``: ``nnz_out ≤ nnz_in``, so
        the blocks of one corpus share one executable). Nothing crosses to
        the host inside the call — not the operand, not the streams, not
        the result: the stored count stays a device scalar until someone
        asks for ``.nnz``, the result's host CSC until someone asks for it
        — and ``A`` is neither modified nor placed any differently.
        ``sparse_serve.coalesce_kernel`` says which sort coalesces (the
        span's ``kernel`` / ``why``). CWT, MMT and WZT, both dimensions,
        take the same program.

        Workspace: ``sparse_coalesce._WORKSPACE_WORDS`` (10) 4-byte words a
        lane beside operand and result, whatever the shapes — 1.7 GB of
        temporaries at 60.8 M lanes (524288 × 3231961 → 262144 buckets).

        A :class:`DistSparseMatrix` input returns a distributed sparse
        result (the SpParMat→SpParMat analog, all device-side; collisions
        there stay separate COO entries)."""
        from libskylark_tpu.base.dist_sparse import DistSparseMatrix
        from libskylark_tpu.sketch.transform import (COLUMNWISE, Dimension,
                                                     note_apply)

        if isinstance(A, DistSparseMatrix):
            from libskylark_tpu.sketch import dist_sparse_apply as dsa

            cw = (dimension or COLUMNWISE) == Dimension.COLUMNWISE
            return dsa.hash_apply_sparse(self, A, columnwise=cw)

        dimension = dimension or COLUMNWISE
        # the root span of one apply, as SketchTransform.apply opens it: the
        # benchmark's host-side readers key on it
        with _trace.span("sketch.apply") as sp:
            if sp is not None:
                sp.attrs.update(
                    family=self.sketch_type,
                    dimension=getattr(dimension, "value", dimension),
                    shape=tuple(A.shape), dtype=str(A.dtype), result="sparse")
            note_apply(path="sparse")
            return self._apply_sparse_out(
                A, rowwise=dimension != Dimension.COLUMNWISE)

    def _apply_sparse_out(self, A, *, rowwise: bool):
        """:meth:`apply_sparse` of a local ``SparseMatrix``, inside its
        ``sketch.apply`` span."""
        from libskylark_tpu.base.sparse import SparseMatrix
        from libskylark_tpu.engine.bucket import result_lanes
        from libskylark_tpu.sketch.sparse_serve import coalesce_kernel, lookup

        if not rowwise:
            if A.height != self._N:
                raise errors.SketchError(
                    f"columnwise apply expects {self._N} rows, got {A.shape}"
                )
        elif A.width != self._N:
            raise errors.SketchError(
                f"rowwise apply expects {self._N} cols, got {A.shape}"
            )
        data, indices, indptr = A.csr_device()
        lanes = int(data.shape[0])
        values = self._value_kind()
        row_cap = A.row_cap if rowwise else None
        kernel, form, cap, why = coalesce_kernel(A.shape, self._S, rowwise,
                                                 row_cap)
        family = self.sketch_type
        with _trace.span("sketch.dispatch",
                         {"path": "sparse", "family": family,
                          "nnz_class": lanes, "lookup": lookup(values),
                          "result": "sparse", "kernel": kernel, "why": why,
                          "lanes_out": result_lanes(lanes)}) as sp:
            d, i, p, merged = _sparse_out_program()(
                self._alloc.key_data, data, indices, indptr, s_dim=self._S,
                rowwise=rowwise, shape=A.shape, values=values, form=form,
                cap=cap)
        out = SparseMatrix.from_device_csr(
            d, i, p, (A.height, self._S) if rowwise else (self._S, A.width),
            row_cap=row_cap)

        # the counts are told, never read: the operand's where it is known
        # (a host-born operand's always is), the result's when someone asks
        def counted_in(nnz):
            if sp is not None:
                sp.attrs["nnz"] = nnz
            _SPARSE_NNZ.inc_always(nnz, family=family, kernel=kernel)
            out.when_counted(lambda nnz_out: counted_out(nnz, nnz_out))

        def counted_out(nnz, nnz_out):
            if sp is not None:
                sp.attrs["nnz_out"] = nnz_out
            _SPARSE_MERGED.inc_always(nnz - nnz_out, family=family,
                                      kernel=kernel)

        A.when_counted(counted_in)
        return out


@register
class CWT(HashTransform):
    """Clarkson-Woodruff CountSketch: ±1 values (OSNAP s=1)
    (ref: sketch/CWT_data.hpp:23-70)."""

    sketch_type = "CWT"


@register
class MMT(HashTransform):
    """Meng-Mahoney transform: CountSketch with Cauchy values for l1 embedding
    (ref: sketch/MMT_data.hpp:22-60)."""

    sketch_type = "MMT"


@register
class WZT(HashTransform):
    """Woodruff-Zhang transform for lp (p in [1,2]): values are
    ±(1/Exp(1))^(1/p) (ref: sketch/WZT_data.hpp:106-124 — base exponential
    stream reshaped to the target distribution, signed by a Rademacher
    stream)."""

    sketch_type = "WZT"

    def __init__(self, N, S, context, p: float = 2.0):
        if p < 1 or p > 2:
            from libskylark_tpu.base import errors

            raise errors.InvalidParametersError(
                "WZT parameter p has to be in [1, 2]"
            )
        self._p = float(p)
        super().__init__(N, S, context)

    def _value_kind(self) -> tuple:
        return ("WZT", self._p)

    def _extra_params(self) -> dict[str, Any]:
        return {"P": self._p}

    @classmethod
    def _from_parts(cls, N, S, alloc, d):
        return cls(N, S, alloc, p=float(d.get("P", 2.0)))
