"""Lazy dense sketch transforms: JLT, CT.

TPU-native analog of the reference's dense_transform family
(ref: sketch/dense_transform.hpp, sketch/dense_transform_data.hpp:22-174,
sketch/JLT_data.hpp:17-78, sketch/CT_data.hpp:21-60).

The sketch matrix S (S_dim × N) is *virtual*: entries are a pure function of
(allocation key, column block), so any column panel can be materialized
on-demand on whichever device needs it — the reference's
``realize_matrix_view`` trick (ref: sketch/dense_transform_data.hpp:79-152)
that lets distributed apply proceed without ever storing S. Column blocks are
``BLOCK_COLS`` wide; the block width is part of the transform's definition
(changing it changes the entries).

Three apply regimes (the analog of the reference's 3-regime panel algorithm,
ref: sketch/dense_transform_Elemental_mc_mr.hpp:617-658, tuned by
sketch_params blocksize/factor):
- small N: materialize S once, single fused matmul (XLA fuses generation
  into the pipeline; MXU does the work).
- large N (``apply_blocked``): lax.scan over column panels of S / row panels
  of A, materializing one (S_dim × blocksize) panel per step — bounded memory,
  traced block ids.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from libskylark_tpu.base import randgen
from libskylark_tpu.sketch import params as sketch_params
from libskylark_tpu.sketch.transform import (OperatorCache,
                                             SketchTransform, note_apply,
                                             register)
from libskylark_tpu.telemetry import trace as _trace

# Width of a virtual-S column block; part of the stream format.
BLOCK_COLS = 256


def virtual_panel(key, dist, s_dim: int, col_start: int, col_stop: int,
                  scale: float, dtype=jnp.float32) -> jnp.ndarray:
    """Columns [col_start, col_stop) of the scaled virtual (s_dim × N)
    operator in the dense-block stream format. THE one definition of
    the stream (BLOCK_COLS included): ``DenseTransform.s_panel`` and
    the engine-fused solver pipelines (nla/svd.py) both call this, so
    their operator bits cannot drift apart."""
    return scale * randgen.dense_panel(
        key, dist, s_dim, col_start, col_stop, BLOCK_COLS, dtype)


def serve_apply(key_data, scale, A, *, dist, s_dim: int,
                rowwise: bool) -> jnp.ndarray:
    """Pure, vmap-batchable dense sketch apply for the microbatch
    serving layer (:mod:`libskylark_tpu.engine.serve`): one request's
    S·A (or A·Sᵀ) as a function of the transform's raw key data, with
    every knob static. The operator bits come from :func:`virtual_panel`
    — the same positional stream ``DenseTransform`` applies — so a
    request whose operand is zero-padded past the transform's true N
    produces the exact bits of the unpadded apply: padded coordinates
    multiply zero rows/columns, and the stream's first N positions are
    invariant to the padded width.

    ``key_data`` is ``jax.random.key_data(transform.allocation.key)``
    ((2,) uint32), which the executor can stack host-side; ``scale`` is
    traced so transforms differing only by scale (CT's C) share one
    executable."""
    import jax.random as jr

    key = jr.wrap_key_data(jnp.asarray(key_data))
    n = A.shape[1] if rowwise else A.shape[0]
    S = virtual_panel(key, dist, s_dim, 0, n,
                      jnp.asarray(scale, A.dtype), A.dtype)
    return (A @ S.T) if rowwise else (S @ A)


def pallas_ambient_ok(A) -> bool:
    """True when the ONE-CHIP fused kernel may run on ``A`` in the ambient
    context: use_pallas is on AND the array is single-device. A concrete
    operand on more than one device is :func:`on_mesh`'s: its apply is the
    ``sketch.dense_mesh`` program (parallel/shard_apply.py), the same
    kernel a device under ``shard_map``. On a tracer the sharding is
    unreadable, so traced applies qualify only when the backend has a
    single device and sharding is impossible; a traced apply of a sharded
    operand stays XLA's to partition."""
    if not sketch_params.get_use_pallas():
        return False
    import jax

    if isinstance(A, jax.core.Tracer):
        return len(jax.devices()) == 1
    if isinstance(A, jax.Array):
        try:
            return len(A.sharding.device_set) == 1
        except Exception:
            return False
    return False


def on_mesh(A) -> bool:
    """True for a concrete 2-D ``jax.Array`` that lies on more than one
    device — what ``DenseTransform._apply_dense`` hands to the mesh
    program. A tracer's placement is unreadable (``pallas_ambient_ok``'s
    convention): never."""
    if isinstance(A, jax.core.Tracer) or not isinstance(A, jax.Array):
        return False
    return A.ndim == 2 and len(A.sharding.device_set) > 1


def pallas_serves_eager(A, dist, s_dim: int,
                        seq_axis: int | None) -> bool:
    """True when an eager dense apply of ``A`` would route through the
    fused Mosaic kernel — whose contraction numerics (bf16x3 split,
    accumulation order) differ from a materialized XLA gemm. Used to
    veto auto-materialize on that path: the Nth eager apply must not
    silently change numerics vs the first (cross-call reproducibility).
    Mirrors the dispatch's FULL qualification via ``effective_plan``
    (distribution/dtype support, pallas importability, VMEM/tile
    budget): any apply the kernel would decline runs the plain XLA
    contraction and must keep auto-amortizing."""
    if not pallas_ambient_ok(A):
        return False
    from libskylark_tpu.sketch import pallas_dense

    if not pallas_dense.available():
        return False
    if getattr(A, "ndim", 0) != 2:
        # non-2D never reaches the kernel (dispatch is 2-D only): the
        # XLA path serves it, auto-materialize may amortize freely
        return False
    if seq_axis is None:
        # orientation unknown: veto only if EITHER orientation would
        # take the kernel (r4 advisor — a bare supported() check vetoed
        # applies whose over-budget s_dim the VMEM/tile qualification
        # would decline, permanently disabling auto-materialize on an
        # apply that actually runs the XLA path)
        return any(
            bool(pallas_dense.effective_plan(
                dist, A.shape, A.dtype, s_dim, ax).get("kernel"))
            for ax in (0, 1))
    return bool(pallas_dense.effective_plan(
        dist, A.shape, A.dtype, s_dim, seq_axis).get("kernel"))


def try_pallas_apply(key, dist, A, s_dim: int, scale: float, which: str):
    """Fused generation+matmul TPU kernel (sketch/pallas_dense.py) for a
    virtual operator in the dense-block stream format (the feature maps
    of sketch/rft.py plan the same kernels inside their own program).
    Returns None when the backend/input don't qualify; the kernel-side
    resolution fills m_tile / precision from the sketch.params setters."""
    if not pallas_ambient_ok(A):
        return None
    from libskylark_tpu.sketch import pallas_dense

    return getattr(pallas_dense, which)(key, dist, A, s_dim, scale)


class DenseTransform(OperatorCache, SketchTransform):
    """Base: S = scale × i.i.d. matrix from ``dist``
    (ref: sketch/random_dense_transform_data.hpp:15-76)."""

    sketch_type = "DenseTransform"
    dist: randgen.Distribution = randgen.Normal()

    @property
    def scale(self) -> float:
        raise NotImplementedError

    # -- virtual S materialization --

    def s_panel(self, col_start: int, col_stop: int, dtype=jnp.float32) -> jnp.ndarray:
        """Materialize S[:, col_start:col_stop] (static bounds)."""
        return virtual_panel(self._alloc.key, self.dist, self._S,
                             col_start, col_stop, self.scale, dtype)

    def s_block(self, block_id, dtype=jnp.float32) -> jnp.ndarray:
        """Materialize column block ``block_id`` (traced id ok; for scan loops)."""
        return self.scale * randgen.dense_block(
            self._alloc.key, self.dist, self._S, block_id, BLOCK_COLS, dtype
        )

    # -- materialize-and-reuse (OperatorCache; entries identical to the
    # virtual stream's by construction — same s_panel) --

    def _full_operator(self, dtype) -> jnp.ndarray:
        return self.s_panel(0, self._N, dtype)

    def _materialize_changes_numerics(self, A, seq_axis=None) -> bool:
        return (pallas_serves_eager(A, self.dist, self._S, seq_axis)
                or self._mesh_serves(A))

    # -- apply --

    def _effective_blocksize(self, dtype) -> int:
        """The panel width to apply at: the global ``blocksize`` knob, or
        — when unset (0) but the full operator would exceed the
        auto-blocking threshold — an automatic panel width. The reference
        defaults to blocked apply (blocksize=1000,
        ref: sketch/sketch_params.hpp:15-19) precisely so S never
        materializes; unbounded materialization of an (S_dim × N)
        operator at huge N would OOM where the reference works."""
        blocksize = sketch_params.get_blocksize()
        if blocksize:
            return blocksize if self._N > blocksize else 0
        itemsize = jnp.dtype(dtype).itemsize
        if self._S * self._N * itemsize > sketch_params.get_auto_block_bytes():
            # raw width; _panel_schedule rounds to BLOCK_COLS multiples
            return max(
                BLOCK_COLS,
                sketch_params.get_auto_block_bytes()
                // max(self._S * itemsize, 1),
            )
        return 0

    def _apply_columnwise(self, A: jnp.ndarray) -> jnp.ndarray:
        return self._apply_dense(A, seq_axis=0)

    def _apply_rowwise(self, A: jnp.ndarray) -> jnp.ndarray:
        return self._apply_dense(A, seq_axis=1)

    def _apply_dense(self, A: jnp.ndarray, seq_axis: int) -> jnp.ndarray:
        """S·A (``seq_axis`` 0) or A·Sᵀ (1) by the first path that
        serves: the pinned operator, the fused kernel, the mesh program
        (an operand on more than one device), the blocked scan, the
        materialized gemm."""
        rowwise = seq_axis == 1
        self._note_eager_apply(A, seq_axis=seq_axis)
        S = self._cached_op(A.dtype)
        path = "cached_op"
        attrs = {"padded": False}
        if S is None:
            out = self._try_pallas(
                A, "rowwise_apply" if rowwise else "columnwise_apply")
            if out is not None:
                return out
            if on_mesh(A):
                out, declined = self._mesh_apply(A, seq_axis)
                if out is not None:
                    return out
                # XLA partitions what follows; the span says why
                attrs["route"] = f"xla: {declined}"
            blocksize = self._effective_blocksize(A.dtype)
            if blocksize:
                note_apply(path="xla_blocked")
                blocked = (self._apply_rowwise_blocked if rowwise
                           else self._apply_columnwise_blocked)
                with _trace.span("sketch.dispatch", attrs):
                    return blocked(A, blocksize)
            path = "xla_full"
            S = self.s_panel(0, self._N, A.dtype)
        note_apply(path=path)
        # the transpose is a dispatch of its own: ahead of the handover
        # span (telemetry/names.py HANDOVER), which holds the one matmul
        left, right = (A, S.T) if rowwise else (S, A)
        with _trace.span("sketch.dispatch", attrs):
            return left @ right

    def _try_pallas(self, A, which: str):
        return try_pallas_apply(
            self._alloc.key_data, self.dist, A, self._S, self.scale, which
        )

    # -- an operand on more than one device: parallel/shard_apply.py,
    # imported (with jax.shard_map) at the first such operand --

    def _mesh_serves(self, A) -> bool:
        if not on_mesh(A):
            return False
        from libskylark_tpu.parallel import shard_apply

        return shard_apply.serves(A)

    def _mesh_apply(self, A, seq_axis: int):
        from libskylark_tpu.parallel import shard_apply

        return shard_apply.apply_on_mesh(self, A, seq_axis)

    # -- sparse input (ref: sketch/dense_transform_Mixed.hpp:19) --

    def _apply_columnwise_sparse(self, A) -> jnp.ndarray:
        """S·A of a ``SparseMatrix`` = (Aᵀ·Sᵀ)ᵀ: ONE compiled program an
        apply (``sketch.dense_sparse_cw``), as :meth:`_apply_rowwise_sparse`
        is — the operator generated inside it from the allocation's key
        words (``sparse_serve.dense_sparse_apply_cw`` states the
        workspace), the transposed sparse product ``base.sparse.spmm_t``
        runs, over the placement of A's transposed side. A pinned operator
        is ``spmm_t``'s right factor; one past ``auto_block_bytes`` keeps
        the host loop over panels of Aᵀ's columns."""
        from libskylark_tpu.base.sparse import spmm_t

        S = self._cached_op(A.device_dtype)
        if S is not None:
            return spmm_t(A, S.T).T      # S·A = (Aᵀ·Sᵀ)ᵀ
        if self._op_bytes(A.device_dtype) > sketch_params.get_auto_block_bytes():
            # Aᵀ's columns are A's rows = the sketched dim, so the panel
            # loop runs over Aᵀ (host CSC transpose, O(nnz))
            return self._sparse_panel_loop(
                A.transpose(), self._effective_blocksize(A.device_dtype)).T
        return self._sparse_program_apply(A, "transposed")

    def _apply_rowwise_sparse(self, A) -> jnp.ndarray:
        """A·Sᵀ of a ``SparseMatrix``: ONE compiled program an apply
        (``sketch.dense_sparse``, ``engine.compiled``) — the operator
        generated inside it from the allocation's key words, the same S the
        dense apply of ``A.todense()`` contracts with, and the sparse ×
        dense product ``base.sparse.spmm`` runs
        (``sparse_serve.dense_sparse_apply`` states the workspace: it does
        not grow with nnz × S). A pinned operator is ``spmm``'s right
        factor. The program holds the whole operator, so one past
        ``auto_block_bytes`` (2 GiB unless set) keeps the host loop over
        column panels; the ``blocksize`` knob alone chooses nothing here."""
        from libskylark_tpu.base.sparse import spmm

        S = self._cached_op(A.device_dtype)
        if S is not None:
            return spmm(A, S.T)          # A·Sᵀ
        if self._op_bytes(A.device_dtype) > sketch_params.get_auto_block_bytes():
            return self._sparse_panel_loop(
                A, self._effective_blocksize(A.device_dtype))
        return self._sparse_program_apply(A, "rows")

    def _sparse_program_apply(self, A, side: str) -> jnp.ndarray:
        """The compiled dense sketch of ``A`` on ``side`` (``"rows"``: A·Sᵀ,
        ``"transposed"``: S·A): the side's placement, the ``sketch.dispatch``
        span, the one program, the ``sketch.sparse_nnz`` count."""
        from libskylark_tpu.base.sparse import product_operands

        lanes, kernel, plan, attrs = product_operands(
            A, self._S, A.device_dtype, side)
        key_data = self._alloc.key_data
        scale = self._device_scale()
        with _trace.span("sketch.dispatch", {
                "path": "sparse", "family": self.sketch_type, "s": self._S,
                **attrs, "operator_view": "kernel" if plan else "rows"}):
            out = _sparse_program(side)(
                key_data, scale, *lanes, dist=self.dist,
                s_dim=self._S, shape=A.shape, kernel=kernel, plan=plan)
        from libskylark_tpu.sketch.hash import _SPARSE_NNZ

        _SPARSE_NNZ.inc_always(A.nnz, family=self.sketch_type, kernel=kernel)
        return out

    def _device_scale(self) -> jnp.ndarray:
        """``self.scale`` as a scalar on the device, placed once a
        transform (as ``Allocation.key_data`` is once a process): handed
        over as a Python float it is one host-to-device transfer an apply,
        which the program's launch waits for — a call into the runtime and
        a completion more on every apply's critical path. Weakly typed,
        as ``jnp.asarray(float)`` is: the same executable either way."""
        held = self.__dict__.get("_scale_on_device")
        if held is None:
            held = self.__dict__["_scale_on_device"] = jnp.asarray(self.scale)
        return held

    def _sparse_panel_loop(self, A, blocksize: int) -> jnp.ndarray:
        """A·Sᵀ for sparse (m, N) A without ever materializing S beyond an
        (S_dim × blocksize) panel — the sparse analog of the blocked dense
        apply (honors the reference's blocksize memory bound,
        ref: sketch/sketch_params.hpp:15-19). Host loop over column panels
        (CSC column views are O(1)); per-panel nonzeros are zero-padded to
        one uniform size so XLA compiles at most two program shapes."""
        import numpy as np

        dt = A.device_dtype
        bs, n_full, rem = self._panel_schedule(blocksize)
        bounds = [(p * bs, (p + 1) * bs) for p in range(n_full)]
        if rem:
            bounds.append((n_full * bs, self._N))
        views = [A.column_view(p0, p1) for p0, p1 in bounds]
        pad = max((v.nnz for v in views), default=1) or 1
        acc = jnp.zeros((A.height, self._S), dt)
        for (p0, p1), V in zip(bounds, views):
            sp = V.to_scipy().tocoo()
            r = np.zeros(pad, np.int32)
            c = np.zeros(pad, np.int32)
            vals = np.zeros(pad, np.dtype(dt))
            r[: V.nnz] = sp.row
            c[: V.nnz] = sp.col
            vals[: V.nnz] = sp.data  # padding rows add v=0 at (0, 0)
            Sp = self.s_panel(p0, p1, dt)        # (S_dim, p1-p0)
            G = Sp.T[jnp.asarray(c)] * jnp.asarray(vals, dt)[:, None]
            acc = acc + jax.ops.segment_sum(
                G, jnp.asarray(r), num_segments=A.height
            )
        return acc

    # -- distributed sparse input (P4/P5): per-cell virtual panels + psum
    # (ref: sketch/dense_transform_Mixed.hpp:19) --

    def _apply_columnwise_dist_sparse(self, A) -> jnp.ndarray:
        from libskylark_tpu.sketch import dist_sparse_apply as dsa

        return dsa.dense_columnwise(self, A)

    def _apply_rowwise_dist_sparse(self, A) -> jnp.ndarray:
        from libskylark_tpu.sketch import dist_sparse_apply as dsa

        return dsa.dense_rowwise(self, A)

    # -- blocked (memory-bounded) apply: scan over column panels of S --

    def _panel_schedule(self, blocksize: int):
        """Round blocksize down to a BLOCK_COLS multiple; compute panel count."""
        bs = max(BLOCK_COLS, (blocksize // BLOCK_COLS) * BLOCK_COLS)
        n_full = self._N // bs
        rem = self._N - n_full * bs
        return bs, n_full, rem

    def _apply_columnwise_blocked(self, A: jnp.ndarray, blocksize: int) -> jnp.ndarray:
        """SA = Σ_p S[:, p] @ A[p, :], one virtual panel at a time."""
        bs, n_full, rem = self._panel_schedule(blocksize)
        blocks_per_panel = bs // BLOCK_COLS
        m = A.shape[1]
        acc0 = jnp.zeros((self._S, m), A.dtype)

        def body(acc, p):
            first = p * blocks_per_panel
            panel = jnp.concatenate(
                [self.s_block(first + b, A.dtype) for b in range(blocks_per_panel)],
                axis=1,
            )
            a_rows = lax.dynamic_slice_in_dim(A, p * bs, bs, axis=0)
            return acc + panel @ a_rows, None

        acc, _ = lax.scan(body, acc0, jnp.arange(n_full, dtype=jnp.int32))
        if rem:
            tail = self.s_panel(n_full * bs, self._N, A.dtype)
            acc = acc + tail @ A[n_full * bs :, :]
        return acc

    def _apply_rowwise_blocked(self, A: jnp.ndarray, blocksize: int) -> jnp.ndarray:
        """A·Sᵀ = Σ_p A[:, p] @ S[:, p]ᵀ, one virtual panel at a time."""
        bs, n_full, rem = self._panel_schedule(blocksize)
        blocks_per_panel = bs // BLOCK_COLS
        m = A.shape[0]
        acc0 = jnp.zeros((m, self._S), A.dtype)

        def body(acc, p):
            first = p * blocks_per_panel
            panel = jnp.concatenate(
                [self.s_block(first + b, A.dtype) for b in range(blocks_per_panel)],
                axis=1,
            )
            a_cols = lax.dynamic_slice_in_dim(A, p * bs, bs, axis=1)
            return acc + a_cols @ panel.T, None

        acc, _ = lax.scan(body, acc0, jnp.arange(n_full, dtype=jnp.int32))
        if rem:
            tail = self.s_panel(n_full * bs, self._N, A.dtype)
            acc = acc + A[:, n_full * bs :] @ tail.T
        return acc


@register
class JLT(DenseTransform):
    """Johnson-Lindenstrauss transform: S ~ N(0, 1/S_dim)
    (ref: sketch/JLT_data.hpp:27-38 — scale sqrt(1/S))."""

    sketch_type = "JLT"
    dist = randgen.Normal()

    @staticmethod
    def scale_for(s_dim: int) -> float:
        """The JLT scale convention, callable without an instance (the
        fused solver pipelines rebuild the operator from a bare key)."""
        return math.sqrt(1.0 / s_dim)

    @property
    def scale(self) -> float:
        return self.scale_for(self._S)


@register
class CT(DenseTransform):
    """Cauchy transform for l1 embedding: Cauchy entries scaled C/S
    (ref: sketch/CT_data.hpp:35-47)."""

    sketch_type = "CT"
    dist = randgen.Cauchy()

    def __init__(self, N, S, context, C: float = 1.0):
        self._C = float(C)
        super().__init__(N, S, context)

    @property
    def scale(self) -> float:
        return self._C / self._S

    def _extra_params(self) -> dict[str, Any]:
        return {"C": self._C}

    @classmethod
    def _from_parts(cls, N, S, alloc, d):
        return cls(N, S, alloc, C=float(d.get("C", 1.0)))


# -- the compiled dense sketch of a sparse operand (below everything the
# dense cells' programs trace through, whose lines keep their numbers) --

_SPARSE_PROGRAMS: dict = {}


def _sparse_program(side: str):
    """``sketch.dense_sparse`` (``side`` ``"rows"``) or
    ``sketch.dense_sparse_cw`` (``"transposed"``), built at the side's first
    sparse operand so that importing the sketch layer pulls neither the
    engine nor a Pallas module."""
    program = _SPARSE_PROGRAMS.get(side)
    if program is None:
        from libskylark_tpu.engine.compiled import compiled
        from libskylark_tpu.sketch import sparse_serve

        body, name = ((sparse_serve.dense_sparse_apply, "sketch.dense_sparse")
                      if side == "rows" else
                      (sparse_serve.dense_sparse_apply_cw,
                       "sketch.dense_sparse_cw"))
        program = _SPARSE_PROGRAMS[side] = compiled(
            body, name=name,
            static_argnames=("dist", "s_dim", "shape", "kernel", "plan"))
    return program
