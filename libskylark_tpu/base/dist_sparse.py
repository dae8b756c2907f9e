"""Mesh-distributed sparse matrix: the P4/P5 parallelism strategies.

TPU-native analog of the reference's distributed sparse containers and
their sketch/gemm code paths:

- ``sparse_dist_matrix_t`` + VC★/★VR — a 1D-distributed sparse matrix with
  owner/local-index arithmetic (ref: base/sparse_dist_matrix.hpp:46-389,
  base/sparse_vc_star_matrix.hpp:19-52),
- the CombBLAS 2D SUMMA grid (SpParMat on a √p×√p grid) and the mixed
  CombBLAS×Elemental gemm bridges (ref: sketch/hash_transform_CombBLAS.hpp:
  16-632, base/detail/combblas_mixed_gemm.hpp:14-376).

Design (TPU-first, not a port): the nonzeros are partitioned by
(row-block × col-block) grid cell over a 1D or 2D mesh. Each cell stores
its triplets in *local* coordinates, zero-padded to one uniform nnz so the
whole matrix is three stacked device arrays of static shape
``(pr, pc, pad)`` — ``lr`` (local row), ``lc`` (local col), ``v`` (value;
0.0 for padding at local (0, 0)) — sharded
``NamedSharding(mesh, P(row_axis, col_axis, None))``. Row/col blocks are
``ceil(h/pr)`` / ``ceil(w/pc)`` wide; ragged edges are handled by the
uniform padded block size (the np∈{5,7} layouts the reference tests,
ref: tests/unit/CMakeLists.txt:31-33).

Products are ``shard_map`` local segment-sums + one ``psum`` over the
contracted mesh axis — the reference's local-gemm + all_reduce pattern
(ref: base/Gemm.hpp:84-103) with the SUMMA reduction riding ICI. Dense
operands enter sharded on the matching axis and zero-padded to the block
grid; outputs come back sharded on the kept axis.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from libskylark_tpu.base import errors
from libskylark_tpu.base.sparse import SparseMatrix


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def _pad_rows(B: jnp.ndarray, to: int) -> jnp.ndarray:
    return B if B.shape[0] == to else jnp.pad(B, ((0, to - B.shape[0]), (0, 0)))


class DistSparseMatrix:
    """Sparse (h × w) matrix distributed over a mesh grid (see module doc).

    Construct with :func:`distribute_sparse`; ``row_axis``/``col_axis`` are
    mesh axis names (either may be None for a 1D distribution — the VC★ /
    ★VR analogs; both set is the 2D SUMMA-grid analog, P4).
    """

    def __init__(
        self,
        mesh: Mesh,
        row_axis: Optional[str],
        col_axis: Optional[str],
        shape: Tuple[int, int],
        lr: jax.Array,
        lc: jax.Array,
        v: jax.Array,
    ):
        self.mesh = mesh
        self.row_axis = row_axis
        self.col_axis = col_axis
        self._shape = shape
        self.pr = mesh.shape[row_axis] if row_axis else 1
        self.pc = mesh.shape[col_axis] if col_axis else 1
        self.bs_r = _ceil_div(shape[0], self.pr)
        self.bs_c = _ceil_div(shape[1], self.pc)
        self.lr, self.lc, self.v = lr, lc, v

    # -- queries --

    @property
    def shape(self) -> Tuple[int, int]:
        return self._shape

    @property
    def height(self) -> int:
        return self._shape[0]

    @property
    def width(self) -> int:
        return self._shape[1]

    @property
    def dtype(self):
        return self.v.dtype

    def _spec(self, *dims) -> P:
        return P(*dims)

    def _triplet_spec(self) -> P:
        return P(self.row_axis, self.col_axis, None)

    def _axes(self):
        """(row axes present, col axes present) as psum-able names."""
        return self.row_axis, self.col_axis

    # -- conversions (tests / host interop) --

    def to_local(self) -> SparseMatrix:
        """Gather to a host-side local :class:`SparseMatrix` (the
        CIRC_CIRC analog)."""
        lr = np.asarray(jax.device_get(self.lr))
        lc = np.asarray(jax.device_get(self.lc))
        v = np.asarray(jax.device_get(self.v))
        rows = lr + (np.arange(self.pr) * self.bs_r)[:, None, None]
        cols = lc + (np.arange(self.pc) * self.bs_c)[None, :, None]
        rows = np.broadcast_to(rows, v.shape).reshape(-1)
        cols = np.broadcast_to(cols, v.shape).reshape(-1)
        vals = v.reshape(-1)
        keep = vals != 0
        return SparseMatrix.from_coo(
            rows[keep], cols[keep], vals[keep], self._shape
        )

    # -- products --

    def spmm(self, B) -> jax.Array:
        """A @ B, B dense (w, k) → (h, k) sharded on ``row_axis``.

        SUMMA over the col axis: each cell contracts its nonzeros against
        its B row-block locally (segment-sum over local rows), then one
        psum over ``col_axis`` (ref: base/Gemm.hpp:84-103 local+all_reduce;
        combblas_mixed_gemm.hpp SUMMA bridge)."""
        B = jnp.asarray(B)
        squeeze = B.ndim == 1
        if squeeze:
            B = B[:, None]
        if B.shape[0] != self.width:
            raise errors.InvalidParametersError(
                f"spmm: A is {self._shape}, B is {B.shape}"
            )
        B = _pad_rows(B, self.pc * self.bs_c).astype(self.v.dtype)
        k = B.shape[1]
        bs_r, bs_c = self.bs_r, self.bs_c
        col_axis, row_axis = self.col_axis, self.row_axis

        def local(lr, lc, v, B_loc):
            lr, lc, v = lr[0, 0], lc[0, 0], v[0, 0]
            part = jax.ops.segment_sum(
                v[:, None] * B_loc[lc], lr, num_segments=bs_r
            )
            if col_axis:
                part = lax.psum(part, col_axis)
            return part[None]

        out = shard_map(
            local,
            mesh=self.mesh,
            in_specs=(
                self._triplet_spec(),
                self._triplet_spec(),
                self._triplet_spec(),
                P(col_axis, None),
            ),
            out_specs=P(row_axis, None, None),
        )(self.lr, self.lc, self.v, B)
        out = out.reshape(self.pr * bs_r, k)[: self.height]
        return out[:, 0] if squeeze else out

    def spmm_t(self, B) -> jax.Array:
        """Aᵀ @ B, B dense (h, k) → (w, k) sharded on ``col_axis``
        (the Gram-type product; psum over ``row_axis``)."""
        B = jnp.asarray(B)
        squeeze = B.ndim == 1
        if squeeze:
            B = B[:, None]
        if B.shape[0] != self.height:
            raise errors.InvalidParametersError(
                f"spmm_t: A is {self._shape}, B is {B.shape}"
            )
        B = _pad_rows(B, self.pr * self.bs_r).astype(self.v.dtype)
        k = B.shape[1]
        bs_c = self.bs_c
        col_axis, row_axis = self.col_axis, self.row_axis

        def local(lr, lc, v, B_loc):
            lr, lc, v = lr[0, 0], lc[0, 0], v[0, 0]
            part = jax.ops.segment_sum(
                v[:, None] * B_loc[lr], lc, num_segments=bs_c
            )
            if row_axis:
                part = lax.psum(part, row_axis)
            return part[None]

        out = shard_map(
            local,
            mesh=self.mesh,
            in_specs=(
                self._triplet_spec(),
                self._triplet_spec(),
                self._triplet_spec(),
                P(row_axis, None),
            ),
            out_specs=P(col_axis, None, None),
        )(self.lr, self.lc, self.v, B)
        out = out.reshape(self.pc * bs_c, k)[: self.width]
        return out[:, 0] if squeeze else out

    def compact(self, utilization_threshold: float = 0.5
                ) -> "DistSparseMatrix":
        """Shrink the per-cell padding to the true max cell nnz when slot
        utilization has dropped below ``utilization_threshold``.

        Cell-merging operations (e.g. the sparse→sparse hash apply,
        sketch/dist_sparse_apply.py) multiply the padded slot count by the
        merged mesh-axis extent while the real nnz stays fixed, so chained
        applies compound mostly-zero slots that every downstream
        spmm/todense then segment-sums over. Compaction is device-side
        with a static output shape: one global-nnz readback picks the new
        pad, a per-cell stable argsort on the padding flag moves real
        entries first, and the slot axis is sliced. Entries with v == 0
        are semantically padding for every consumer (they contribute
        nothing to any product, the CSC duplicate-sum convention of
        ref: base/sparse_matrix.hpp:136), so dropping them is exact."""
        pad = self.v.shape[-1]
        true_pad = max(int(jnp.max(jnp.count_nonzero(self.v, axis=-1))), 1)
        if true_pad > pad * utilization_threshold:
            return self
        order = jnp.argsort((self.v == 0).astype(jnp.int32), axis=-1,
                            stable=True)[..., :true_pad]
        spec = NamedSharding(self.mesh, self._triplet_spec())
        take = lambda a: jax.device_put(
            jnp.take_along_axis(a, order, axis=-1), spec)
        return DistSparseMatrix(
            self.mesh, self.row_axis, self.col_axis, self._shape,
            take(self.lr), take(self.lc), take(self.v),
        )

    def transpose(self) -> "DistSparseMatrix":
        """Aᵀ — pure relabeling: swap the grid axes and the local
        coordinates (no data movement beyond the stacked-array transpose;
        ref: base/sparse_matrix.hpp Transpose:303)."""
        perm = (1, 0, 2)
        return DistSparseMatrix(
            self.mesh, self.col_axis, self.row_axis,
            (self.width, self.height),
            self.lc.transpose(perm), self.lr.transpose(perm),
            self.v.transpose(perm),
        )

    @property
    def T(self) -> "DistSparseMatrix":
        return self.transpose()

    def todense(self) -> jax.Array:
        """Dense (h, w) array sharded P(row_axis, col_axis)."""
        bs_r, bs_c = self.bs_r, self.bs_c

        def local(lr, lc, v):
            lr, lc, v = lr[0, 0], lc[0, 0], v[0, 0]
            out = jnp.zeros((bs_r, bs_c), v.dtype).at[lr, lc].add(v)
            return out[None, None]

        out = shard_map(
            local,
            mesh=self.mesh,
            in_specs=(self._triplet_spec(),) * 3,
            out_specs=P(self.row_axis, self.col_axis, None, None),
        )(self.lr, self.lc, self.v)
        out = out.transpose(0, 2, 1, 3).reshape(
            self.pr * bs_r, self.pc * bs_c
        )
        return out[: self.height, : self.width]

    def __repr__(self) -> str:
        return (
            f"DistSparseMatrix({self.height}x{self.width}, "
            f"grid={self.pr}x{self.pc}, pad_nnz={self.v.shape[-1]}, "
            f"axes=({self.row_axis}, {self.col_axis}))"
        )


def distribute_sparse(
    A: SparseMatrix,
    mesh: Mesh,
    row_axis: Optional[str] = None,
    col_axis: Optional[str] = None,
) -> DistSparseMatrix:
    """Partition a local :class:`SparseMatrix` onto the mesh grid.

    The analog of the reference's queue_update/finalize bulk construction
    (ref: base/sparse_dist_matrix.hpp:106-182): triplets are bucketed to
    their owner cell by index arithmetic, padded to a uniform per-cell nnz
    (pad entries: value 0 at local (0,0) — exact under every product), and
    shipped to devices as three stacked arrays.
    """
    if row_axis is None and col_axis is None:
        raise errors.InvalidParametersError(
            "distribute_sparse needs at least one mesh axis"
        )
    pr = mesh.shape[row_axis] if row_axis else 1
    pc = mesh.shape[col_axis] if col_axis else 1
    h, w = A.shape
    bs_r, bs_c = _ceil_div(h, pr), _ceil_div(w, pc)

    sp = A.to_scipy().tocoo()
    rows = np.asarray(sp.row, dtype=np.int64)
    cols = np.asarray(sp.col, dtype=np.int64)
    # device values follow the framework's precision policy (f64 host
    # buffers land as f32 — same as SparseMatrix.coo / the local oracle)
    vals = np.asarray(sp.data, dtype=np.dtype(A.device_dtype))
    rb, cb = rows // bs_r, cols // bs_c
    cell = rb * pc + cb
    order = np.argsort(cell, kind="stable")
    rows, cols, vals, cell = rows[order], cols[order], vals[order], cell[order]
    counts = np.bincount(cell, minlength=pr * pc)
    pad = max(int(counts.max()) if len(counts) else 0, 1)

    lr = np.zeros((pr, pc, pad), np.int32)
    lc = np.zeros((pr, pc, pad), np.int32)
    v = np.zeros((pr, pc, pad), vals.dtype)
    starts = np.concatenate([[0], np.cumsum(counts)])
    for cidx in range(pr * pc):
        s, e = starts[cidx], starts[cidx + 1]
        i, j = cidx // pc, cidx % pc
        lr[i, j, : e - s] = rows[s:e] - i * bs_r
        lc[i, j, : e - s] = cols[s:e] - j * bs_c
        v[i, j, : e - s] = vals[s:e]

    spec = NamedSharding(mesh, P(row_axis, col_axis, None))
    return DistSparseMatrix(
        mesh, row_axis, col_axis, (h, w),
        jax.device_put(jnp.asarray(lr), spec),
        jax.device_put(jnp.asarray(lc), spec),
        jax.device_put(jnp.asarray(v), spec),
    )
