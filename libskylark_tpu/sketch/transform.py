"""Sketch transform protocol, dimension tags, and serialization registry.

TPU-native analog of the reference's sketch architecture
(ref: sketch/sketch_transform.hpp:60-92, sketch/sketch_transform_data.hpp:28-87,
sketch/transforms.hpp:12-18, sketch/sketch_add.hpp:15-55).

Where the reference pairs a matrix-type-agnostic ``X_data_t`` with per-layout
``X_t<In,Out>`` apply engines, here a single transform object covers all
layouts: the apply methods are pure jnp functions, so input sharding flows
through and XLA inserts the collectives that Elemental's per-distribution
specializations hand-coded. The type-erased ``boost::any`` dispatch layer
(ref: sketch/sketch_transform.hpp:187-221) has no analog — Python is already
dynamically typed.

Dimension convention (ref: sketch/transforms.hpp:12-18):
- ``COLUMNWISE``: sketch_of_A = S · A   (compresses the column dimension: A is N×m)
- ``ROWWISE``:    sketch_of_A = A · Sᵀ  (compresses the row dimension: A is m×N)
"""

from __future__ import annotations

import enum
import json
from typing import Any, Union

import jax
import jax.numpy as jnp

from libskylark_tpu import __version__
from libskylark_tpu.base import errors
from libskylark_tpu.base.context import Allocation, Context
from libskylark_tpu.telemetry import trace as _trace


class Dimension(enum.Enum):
    COLUMNWISE = "columnwise"
    ROWWISE = "rowwise"


COLUMNWISE = Dimension.COLUMNWISE
ROWWISE = Dimension.ROWWISE

_REGISTRY: dict[str, type["SketchTransform"]] = {}


def note_apply(**attrs) -> None:
    """Record on the enclosing ``sketch.apply`` span what the dispatch
    below it chose (``path``, the kernel's plan). No-op outside one."""
    sp = _trace.current_span()
    if sp is not None and sp.name == "sketch.apply":
        sp.attrs.update(attrs)


def register(cls: type["SketchTransform"]) -> type["SketchTransform"]:
    """Register a transform class for deserialization
    (ref: sketch/sketch_add.hpp:15-55 from_ptree registry)."""
    _REGISTRY[cls.sketch_type] = cls
    return cls


class OperatorCache:
    """Opt-in materialize-and-reuse for transforms whose operator is a
    lazily generated dense matrix (DenseTransform's S, RFT's frequency
    matrix W).

    The virtual-operator design pays generation on EVERY apply — the
    right trade for one-shot sketches of huge operands. Workloads that
    apply the same transform repeatedly (feature maps inside solver
    iterations, ref: ml/BlockADMM.hpp:434 cached transforms; serving
    predict paths) call ``materialize()`` to pin the operator in device
    memory and amortize generation to zero, at rows×N×itemsize bytes.
    The cache is runtime state — never serialized (serialization stays
    (seed, counter)-based)."""

    _op_cache = None
    _eager_applies = 0

    def _full_operator(self, dtype) -> jnp.ndarray:
        raise NotImplementedError

    def materialize(self, dtype=jnp.float32):
        """Pin the full operator; later applies contract against the
        cached array instead of regenerating. Returns ``self``."""
        self._op_cache = self._full_operator(dtype)
        return self

    def dematerialize(self):
        """Drop the pinned operator (and the auto-dispatch apply count —
        an explicit drop means 'stop amortizing', not 'repin at once')."""
        self._op_cache = None
        self._eager_applies = 0
        return self

    def _op_bytes(self, dtype) -> int:
        """Pinned-operator size for the auto-materialize budget; the
        cached operator is (sketch_dim × N) for every current user."""
        return int(self._S) * int(self._N) * jnp.dtype(dtype).itemsize

    def _note_eager_apply(self, A, seq_axis: int | None = None) -> None:
        """Auto-materialize dispatch (see sketch/params.py): the Nth
        EAGER dense apply of this instance pins the operator when it
        fits the budget. Applies under a jit trace never count — the
        trace runs once, and materializing inside it would pin a tracer.
        Steady-state reuse (a serving predict path, a feature map inside
        an eager solver loop) thus amortizes generation to zero without
        anyone calling :meth:`materialize`. The decision — on the kernel
        route a second resolution of the apply's plan — and, the Nth time,
        the pin itself run under the ``sketch.materialize`` span."""
        with _trace.span("sketch.materialize"):
            self._auto_materialize(A, seq_axis)

    def _auto_materialize(self, A, seq_axis: int | None) -> None:
        dtype = A.dtype
        if self._op_cache is not None and \
                jnp.dtype(dtype).itemsize <= self._op_cache.dtype.itemsize:
            return
        # (a cache NARROWER than this request doesn't serve it —
        # _cached_op refuses to upcast — so wide applies keep counting
        # and re-pin at the wider dtype rather than regenerate forever)
        if isinstance(A, jax.core.Tracer):
            return
        from libskylark_tpu.sketch import params as sketch_params

        if not sketch_params.get_auto_materialize():
            return
        if self._materialize_changes_numerics(A, seq_axis):
            # never auto-switch a path whose numerics differ from the
            # cached gemm (the fused TPU kernel's bf16x3/accumulation
            # order): two identical eager applies must not differ by
            # prior call count. Explicit materialize() remains available
            # — an explicit call is a visible regime choice.
            return
        self._eager_applies += 1
        if self._eager_applies < sketch_params.get_auto_materialize_after():
            return
        if self._op_bytes(dtype) > sketch_params.get_auto_materialize_bytes():
            return
        self.materialize(dtype)

    def _materialize_changes_numerics(self, A, seq_axis=None) -> bool:
        """True when auto-pinning would CHANGE the numerics of later
        eager applies (e.g. the apply currently routes through the fused
        Pallas kernel, whose contraction regime differs from the
        materialized XLA gemm). ``seq_axis`` is the apply orientation
        (0 columnwise, 1 rowwise, None unknown) so overrides can ask the
        kernel dispatch for its real decision. Default False: on the
        plain XLA path the materialized contraction is the same
        computation."""
        return False

    def _cached_op(self, dtype):
        """The pinned operator, cast to the apply dtype if needed (the
        cast is O(elements) — noise next to the gemm; silently skipping
        the cache on a narrower dtype would defeat the explicitly
        requested amortization). A request WIDER than the cache returns
        None — upcasting a truncated cache would silently degrade e.g.
        f64 applies (QRFT builds W in host f64; under jax x64 the
        virtual path is full-precision), so wide applies regenerate."""
        c = self._op_cache
        if c is None:
            return None
        want = jnp.dtype(dtype)
        if want.itemsize > c.dtype.itemsize:
            return None
        return c if c.dtype == want else c.astype(want)

    def _op_or(self, dtype, build):
        """The cached operator for ``dtype``, else ``build(dtype)``."""
        c = self._cached_op(dtype)
        return c if c is not None else build(dtype)


class SketchTransform:
    """A sketching transform S: R^N -> R^S_dim.

    Mathematical definition lives in the (seed, counter) allocation plus the
    hyper-params — matrix-free and serializable, like the reference's
    ``_data_t`` classes. Construction advances the context's counter
    (ref: sketch/sketch_transform_data.hpp ``build``).
    """

    sketch_type = "SketchTransform"

    def __init__(self, N: int, S: int, context: Union[Context, Allocation]):
        if N <= 0 or S <= 0:
            raise errors.InvalidParametersError(
                f"sketch dims must be positive, got N={N}, S={S}"
            )
        self._N = int(N)
        self._S = int(S)
        if isinstance(context, Context):
            self._alloc = context.allocate()
        else:
            self._alloc = context
        self._build()

    def _build(self) -> None:
        """Derive any host-side sample arrays. Default: nothing."""

    # -- structural queries (ref: sketch_transform.hpp getindim/getsketchdim) --

    @property
    def input_dim(self) -> int:
        return self._N

    @property
    def sketch_dim(self) -> int:
        return self._S

    @property
    def allocation(self) -> Allocation:
        return self._alloc

    def subkey(self, tag: int) -> jax.Array:
        """Sub-stream key ``tag`` of this transform's allocation; the analog
        of the reference's sequential counter advancement during build."""
        return self._alloc.child(tag).key

    # -- apply --

    def apply(self, A, dimension: Dimension = COLUMNWISE) -> jnp.ndarray:
        """Apply the sketch (ref: sketch/sketch_transform.hpp:60-92).

        COLUMNWISE: A is (N, m) -> (S, m).  ROWWISE: A is (m, N) -> (m, S).
        Works on any jax.Array regardless of sharding; XLA handles the
        distributed contraction. A :class:`~libskylark_tpu.base.sparse.SparseMatrix`
        input routes to the transform's sparse kernel (ref: the reference's
        per-(input,output)-type specializations, e.g.
        sketch/hash_transform_local_sparse.hpp) and produces a dense result.
        """
        with _trace.span("sketch.apply") as sp:
            if sp is not None:
                sp.attrs.update(
                    family=self.sketch_type,
                    dimension=getattr(dimension, "value", dimension),
                    shape=tuple(getattr(A, "shape", ())),
                    dtype=str(getattr(A, "dtype", type(A).__name__)))
            return self._apply(A, dimension)

    def _apply(self, A, dimension: Dimension) -> jnp.ndarray:
        """Validate the operand and dispatch to the ``_apply_*`` of its
        kind and orientation."""
        from libskylark_tpu.base.dist_sparse import DistSparseMatrix
        from libskylark_tpu.base.sparse import SparseMatrix

        if isinstance(A, DistSparseMatrix):
            note_apply(path="sparse")
            # dimension validation lives in dist_sparse_apply._check_dim
            if dimension == Dimension.COLUMNWISE:
                return self._apply_columnwise_dist_sparse(A)
            return self._apply_rowwise_dist_sparse(A)
        if isinstance(A, SparseMatrix):
            note_apply(path="sparse")
            if dimension == Dimension.COLUMNWISE:
                if A.height != self._N:
                    raise errors.SketchError(
                        f"columnwise apply expects {self._N} rows, got {A.shape}"
                    )
                return self._apply_columnwise_sparse(A)
            if A.width != self._N:
                raise errors.SketchError(
                    f"rowwise apply expects {self._N} cols, got {A.shape}"
                )
            return self._apply_rowwise_sparse(A)
        columnwise = dimension == COLUMNWISE
        axis, name, extent = ((0, "columnwise", "rows") if columnwise
                              else (1, "rowwise", "cols"))
        # the largest piece of an apply's own time ahead of its handover
        # (telemetry/names.py HANDOVER), under a name of its own
        with _trace.span("sketch.operand"):
            A = jnp.asarray(A)
            if A.ndim == 1:
                A = A[:, None] if columnwise else A[None, :]
            if A.shape[axis] != self._N:
                raise errors.SketchError(
                    f"{name} apply expects A with {self._N} {extent}, "
                    f"got {A.shape}")
        if columnwise:
            return self._apply_columnwise(A)
        return self._apply_rowwise(A)

    def _apply_columnwise(self, A: jnp.ndarray) -> jnp.ndarray:
        raise errors.NotImplementedYetError(
            f"{self.sketch_type}: columnwise apply not implemented"
        )

    def _apply_rowwise(self, A: jnp.ndarray) -> jnp.ndarray:
        raise errors.NotImplementedYetError(
            f"{self.sketch_type}: rowwise apply not implemented"
        )

    def _apply_columnwise_sparse(self, A) -> jnp.ndarray:
        raise errors.NotImplementedYetError(
            f"{self.sketch_type}: columnwise sparse apply not implemented"
        )

    def _apply_rowwise_sparse(self, A) -> jnp.ndarray:
        raise errors.NotImplementedYetError(
            f"{self.sketch_type}: rowwise sparse apply not implemented"
        )

    def _apply_columnwise_dist_sparse(self, A) -> jnp.ndarray:
        raise errors.NotImplementedYetError(
            f"{self.sketch_type}: columnwise distributed-sparse apply "
            "not implemented"
        )

    def _apply_rowwise_dist_sparse(self, A) -> jnp.ndarray:
        raise errors.NotImplementedYetError(
            f"{self.sketch_type}: rowwise distributed-sparse apply "
            "not implemented"
        )

    # -- serialization (ref: sketch_transform_data.hpp:64-71 add_common) --

    def _extra_params(self) -> dict[str, Any]:
        """Transform-specific hyper-params to serialize."""
        return {}

    # Stream-format generation: bumped whenever the bit-level definition of
    # the virtual random streams changes (chunk size, threefry pair
    # layout, a distribution's draw→sample map — see base/randgen.py).
    # Deserialization rejects a mismatch rather than silently producing a
    # different operator. Format 3: chunk streams moved from jax.random's
    # samplers to the explicit ops of base/threefry.py.
    STREAM_FORMAT = 3

    def to_dict(self) -> dict[str, Any]:
        d = {
            "skylark_object_type": "sketch",
            "sketch_type": self.sketch_type,
            "skylark_version": __version__,
            "stream_format": self.STREAM_FORMAT,
            "N": self._N,
            "S": self._S,
            "creation_context": self._alloc.to_dict(),
        }
        d.update(self._extra_params())
        return d

    def to_json(self) -> str:
        return json.dumps(self.to_dict())

    @classmethod
    def _from_parts(
        cls, N: int, S: int, alloc: Allocation, d: dict[str, Any]
    ) -> "SketchTransform":
        return cls(N, S, alloc)

    def __repr__(self) -> str:
        return f"{self.sketch_type}(N={self._N}, S={self._S})"


def deserialize_sketch(obj: Union[str, dict[str, Any]]) -> SketchTransform:
    """Reconstruct a transform from its JSON form
    (ref: sketch/sketch_add.hpp from_ptree; python sketch.py deserialize_sketch:118)."""
    d = json.loads(obj) if isinstance(obj, str) else obj
    stype = d.get("sketch_type")
    cls = _REGISTRY.get(stype)
    if cls is None:
        raise errors.SketchError(f"unknown sketch type {stype!r}")
    # A missing field means a pre-versioning serialization — those were
    # written under the original (format-1) stream layout, so they must be
    # rejected too, not defaulted to the current format.
    fmt = int(d.get("stream_format", 1))
    if fmt != SketchTransform.STREAM_FORMAT:
        raise errors.SketchError(
            f"sketch was serialized with stream format {fmt}; this build "
            f"implements format {SketchTransform.STREAM_FORMAT} — the "
            "operator would not reproduce"
        )
    alloc = Allocation.from_dict(d["creation_context"])
    return cls._from_parts(int(d["N"]), int(d["S"]), alloc, d)
