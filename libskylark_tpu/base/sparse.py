"""Local sparse matrix (CSC) and sparse×dense products.

TPU-native analog of ref: base/sparse_matrix.hpp:23-346 (``sparse_matrix_t``):
a CSC container with zero-copy attach from scipy buffers, duplicate-summing
COO construction (ref: set():136), transpose (ref: Transpose:303) and
read-only column views (ref: view:256).

On the device a matrix is placed once per value dtype, as row-major CSR
lanes (:meth:`SparseMatrix.csr_device`: data, column ids, row pointers,
the nnz extent zero-padded to its ``engine.bucket.lane_class``) — what the
compiled sparse hash sketch and :func:`spmm` walk —, as those lanes
regrouped by (row block, column tile) for the sparse × dense kernel
(:meth:`SparseMatrix.tiled_device`, under a ``sparse.place`` span), or as
the row-major COO triplets (:meth:`SparseMatrix.coo`), the row ids expanded
on the device. The transposed product has a placement of its own, made at
the first ``Aᵀ·B`` and never at an ``A·B``: A's column-major lanes
(:meth:`SparseMatrix.csc_device`) or those regrouped by (block of A's
columns, tile of A's rows) (``tiled_device(..., side="transposed")``).

The products (ref: base/Gemm.hpp:335-519). :func:`spmm` (A·B) is one
compiled program (``sparse.spmm``) whose workspace does not grow with
nnz × k: on a TPU, where ``sketch.sparse_serve.product_kernel`` finds the
shapes, the Pallas walk of ``sketch/pallas_spmm.py`` over the regrouped
lanes (VMEM blocks only); anywhere else a loop over spans of
``_SPAN_LANES`` CSR lanes, each span one gather of its rows of B, one
multiply and one scatter-add into the rows the span touches
(``_SPAN_LANES`` × k values at a time; before PR 57 the whole nnz × k
gather was one array, 79 GB at 19.4 M nonzeros × 1024).
``DenseTransform.apply`` on a sparse operand, rowwise, is the same program
body behind an operator generated in the program. :func:`spmm_t` (Aᵀ·B) is
the same body over the lanes of Aᵀ (``sparse.spmm_t``; before PR 61 a
``segment_sum(v[:, None] * B[r], c)`` over all nonzeros at once, which no
operand of the size :func:`spmm` serves fitted), and the columnwise dense
sketch of a sparse operand runs it behind its generated operator. All
nnz-shaped arrays have static shapes, so the products are jittable.
"""

from __future__ import annotations

import functools
import time
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from libskylark_tpu.base import errors


class SparseMatrix:
    """Immutable local sparse matrix, CSC on host, row-major on device.

    Construction never copies the supplied numpy buffers (the reference's
    external-ownership ``attach`` semantics, ref: base/sparse_matrix.hpp:82);
    device placement happens lazily, once per value dtype, at the first
    ``csr_device()`` or ``coo()``.

    A matrix may also be **born on the device**
    (:meth:`from_device_csr`: the result of ``HashTransform.apply_sparse``):
    its row-major lanes are device arrays in the :meth:`csr_device` format,
    its stored count a device scalar until someone asks for :attr:`nnz`, and
    :meth:`csr_device`, :meth:`coo` and :meth:`todense` serve from those
    lanes with no host round trip. Its host side — the CSC buffers behind
    :attr:`indptr` / :attr:`indices` / :attr:`data`, :meth:`to_scipy`,
    :meth:`csc_parts` and the tiled placements — materializes lazily, once,
    at the first of those (:attr:`host_materialized` says whether it has).
    """

    def __init__(
        self,
        colptr: np.ndarray,
        rowind: np.ndarray,
        values: np.ndarray,
        shape: Tuple[int, int],
    ):
        self._colptr = np.asarray(colptr, dtype=np.int64)
        self._rowind = np.asarray(rowind, dtype=np.int32)
        self._values = np.asarray(values)
        self._shape = (int(shape[0]), int(shape[1]))
        if len(self._colptr) != self._shape[1] + 1:
            raise errors.InvalidParametersError(
                f"colptr length {len(self._colptr)} != width+1 "
                f"{self._shape[1] + 1}"
            )
        if len(self._rowind) != len(self._values):
            raise errors.InvalidParametersError("rowind/values length mismatch")
        self._init_state(born=None, nnz=len(self._values), row_cap=None)

    def _init_state(self, born, nnz, row_cap) -> None:
        """What every matrix keeps beside its host buffers."""
        # device-resident layouts by value dtype: {"csr": the placed
        # lanes, "csc": the column-major ones, "coo": the triplets derived
        # from the row-major lanes on first coo(), ("tiled", side, layout):
        # the lanes regrouped for the product kernel and the counts of how
        # the walk takes them}
        self._device: dict = {}
        # the canonical scipy CSR this was attached from, when it was one
        self._row_major = None
        # the lanes a device-born matrix was born as (from_device_csr), the
        # stored count (None until such a matrix is asked for it), a bound
        # on a row's lanes, what to tell when the count is read
        self._born = born
        self._nnz = nnz
        self._row_cap = row_cap
        self._count_hooks: list = []

    # -- constructors --

    @classmethod
    def from_device_csr(cls, data, indices, indptr, shape: Tuple[int, int],
                        row_cap=None) -> "SparseMatrix":
        """A matrix born on the device: canonical row-major lanes ``(data,
        indices, indptr)`` in the :meth:`csr_device` format — a row's
        columns ascending and distinct, ``indptr`` exact ((height + 1,)
        int32, ``indptr[-1]`` the stored count), the lanes past it 0.0 at
        column 0, the lane extent whatever the producer chose (for a hash
        sketch's result its operand's, ``engine.bucket.result_lanes``).
        Nothing is read back: the count stays on the device until
        :attr:`nnz` is asked for, the host buffers until they are.
        ``row_cap`` is a bound on the lanes of one row where the producer
        knows one (:attr:`row_cap`)."""
        out = cls.__new__(cls)
        out._shape = (int(shape[0]), int(shape[1]))
        out._colptr = out._rowind = out._values = None
        out._init_state(born=(data, indices, indptr), nnz=None,
                        row_cap=None if row_cap is None else int(row_cap))
        out._device[jnp.dtype(data.dtype)] = {"csr": out._born}
        return out

    @classmethod
    def from_scipy(cls, A) -> "SparseMatrix":
        """Attach a ``scipy.sparse`` matrix (converted to CSC if needed;
        zero-copy when already CSC — ref: python sketch.py _ScipyAdapter)."""
        import scipy.sparse as sp

        csc = A.tocsc()
        out = cls(csc.indptr, csc.indices, csc.data, csc.shape)
        if A.format == "csr" and A.has_canonical_format:
            # attached by reference, like a CSC's buffers: the row-major
            # lanes are this matrix's own (1.9 s of conversions a 19 M-nnz
            # row block otherwise, on every first placement — PERF.md PR 31)
            out._row_major = A
        return out

    @classmethod
    def from_coo(
        cls,
        rows,
        cols,
        values,
        shape: Tuple[int, int],
    ) -> "SparseMatrix":
        """Duplicate-summing COO→CSC build (ref: sparse_matrix.hpp set():136)."""
        import scipy.sparse as sp

        A = sp.coo_matrix(
            (np.asarray(values), (np.asarray(rows), np.asarray(cols))),
            shape=shape,
        ).tocsc()
        A.sum_duplicates()
        return cls(A.indptr, A.indices, A.data, A.shape)

    @classmethod
    def from_csr(
        cls,
        data,
        indices,
        indptr,
        shape: Tuple[int, int],
    ) -> "SparseMatrix":
        """Build from CSR parts (the serve wire format — the inverse of
        :meth:`csr_parts`). Converted to the canonical CSC host layout;
        duplicates are summed (ref: sparse_matrix.hpp set():136)."""
        import scipy.sparse as sp

        A = sp.csr_matrix(
            (np.asarray(data), np.asarray(indices), np.asarray(indptr)),
            shape=shape,
        ).tocsc()
        A.sum_duplicates()
        return cls(A.indptr, A.indices, A.data, A.shape)

    @classmethod
    def from_dense(cls, A, threshold: float = 0.0) -> "SparseMatrix":
        import scipy.sparse as sp

        A = np.asarray(A)
        if threshold > 0.0:
            A = np.where(np.abs(A) > threshold, A, 0.0)
        return cls.from_scipy(sp.csc_matrix(A))

    # -- queries (ref: base/query.hpp Height/Width) --

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
    def nnz(self) -> int:
        """The stored count. A device-born matrix reads it off the device
        at the first ask (one scalar; it waits for the program that makes
        the lanes) and tells whoever asked to be told (:meth:`when_counted`)."""
        if self._nnz is None:
            self._nnz = int(self._born[2][-1])
            hooks, self._count_hooks = self._count_hooks, []
            for hook in hooks:
                hook(self._nnz)
        return self._nnz

    @property
    def nnz_known(self) -> bool:
        """Whether :attr:`nnz` answers without reading the device."""
        return self._nnz is not None

    def when_counted(self, hook) -> None:
        """``hook(nnz)`` now, where the count is known, else when it is
        first read — never by a read of its own (the sparse → sparse
        apply's span and counter learn the result's count this way)."""
        if self._nnz is not None:
            hook(self._nnz)
        else:
            self._count_hooks.append(hook)

    @property
    def host_materialized(self) -> bool:
        """Whether the host CSC buffers exist (always, for a host-born
        matrix)."""
        return self._values is not None

    @property
    def lanes(self) -> int:
        """The lane extent of :meth:`csr_device`: a device-born matrix's
        own, else the ``lane_class`` of the stored count."""
        if self._born is not None:
            return int(self._born[0].shape[0])
        from libskylark_tpu.engine.bucket import lane_class

        return lane_class(self.nnz)

    @property
    def row_cap(self):
        """A bound on the stored lanes of one row, or ``None`` where none
        is known without reading the device: a host-born matrix's longest
        row (counted once), a device-born one's as its producer gave it."""
        if self._row_cap is None and self._values is not None:
            if self._row_major is not None:
                lengths = np.diff(self._row_major.indptr)
            else:
                lengths = np.bincount(self._rowind, minlength=self._shape[0])
            self._row_cap = int(lengths.max()) if lengths.size else 0
        return self._row_cap

    @property
    def density(self) -> float:
        """nnz / (height·width) — the serve layer's auto-densify signal
        (``SKYLARK_SPARSE_MIN_DENSITY``, docs/serving)."""
        cells = self._shape[0] * self._shape[1]
        return (self.nnz / cells) if cells else 0.0

    @property
    def dtype(self):
        if self._values is None:
            return np.dtype(self._born[0].dtype)
        return self._values.dtype

    def _host(self) -> "SparseMatrix":
        """The host CSC buffers of a device-born matrix, made once: the
        lanes read back (the one device → host crossing of such a matrix),
        attached as the canonical scipy CSR they are, converted to CSC."""
        if self._values is None:
            import scipy.sparse as sp

            data, indices, indptr = (np.asarray(x) for x in self._born)
            nnz = self.nnz
            A = sp.csr_matrix((data[:nnz], indices[:nnz], indptr),
                              shape=self._shape)
            csc = A.tocsc()
            self._colptr = np.asarray(csc.indptr, dtype=np.int64)
            self._rowind = np.asarray(csc.indices, dtype=np.int32)
            self._values = np.asarray(csc.data)
            self._row_major = A
        return self

    @property
    def device_dtype(self):
        """dtype of the device-side values (f64 host buffers land as f32 —
        the TPU-native precision policy; pass an explicit dtype to ``coo``
        to override)."""
        return jnp.float32 if self.dtype == np.float64 else jnp.dtype(
            self.dtype
        )

    @property
    def indptr(self) -> np.ndarray:
        return self._host()._colptr

    @property
    def indices(self) -> np.ndarray:
        return self._host()._rowind

    @property
    def data(self) -> np.ndarray:
        return self._host()._values

    # -- conversions --

    def _device_dtype_of(self, dtype):
        return jax.dtypes.canonicalize_dtype(
            np.dtype(dtype) if dtype is not None else self.device_dtype)

    def _place_lanes(self, eff, extent: int, transposed: bool = False):
        """:meth:`csr_parts` (:meth:`csc_parts`: Aᵀ's) on the device, the
        nnz lanes zero-padded to ``extent``."""
        data, indices, indptr = (self.csc_parts(eff) if transposed
                                 else self.csr_parts(eff))
        pad = extent - self.nnz
        return (_place(np.pad(data, (0, pad))),
                _place(np.pad(indices, (0, pad))), _place(indptr))

    def csr_device(self, dtype=None) -> Tuple[jax.Array, jax.Array,
                                              jax.Array]:
        """Device-resident row-major lanes ``(data, indices, indptr)``:
        :meth:`csr_parts` with the nnz extent of ``data``/``indices``
        zero-padded to its ``engine.bucket.lane_class`` (value 0.0 at
        column 0: exact zeros through every sparse endpoint; under a
        sixteenth of the lanes), ``indptr`` exact, (height + 1,) int32.
        Placed on the first call for a value dtype and kept: later calls
        move nothing from the host. Row blocks whose nnz fall in one class
        present one shape to a compiled program."""
        eff = self._device_dtype_of(dtype)
        layouts = self._device.setdefault(eff, {})
        if "csr" not in layouts:
            if self._born is not None:      # another dtype: cast where it is
                data, indices, indptr = self._born
                layouts["csr"] = (data.astype(eff), indices, indptr)
                return layouts["csr"]
            from libskylark_tpu.engine.bucket import lane_class

            layouts["csr"] = self._place_lanes(eff, lane_class(self.nnz))
        return layouts["csr"]

    def tiled_device(self, layout: tuple, dtype=None,
                     side: str = "rows") -> Tuple[jax.Array, ...]:
        """The lanes of :meth:`csr_device` regrouped for the sparse × dense
        kernel (``sketch/pallas_spmm.py``, which says what the layout
        means): ``layout`` = (row_block, col_tile, chunk, n_chunks, group,
        stride, cover) → ``(segment, count, packed, vals)``, the two chunk
        tables (n_chunks,) int32 — ``count`` a chunk's stored lanes, 0 for
        an empty one, and · 2¹⁶ its leading slots that lie in the segment's
        grouped prefix — and the slots (n_chunks, 1, chunk) int32 / values.
        Regrouped on the host (:func:`_tile_lanes`: one stable sort of the
        stored lanes by (segment, rank); a device sort of 19.9 M lanes
        compiles for 40–86 s) and placed on the first call for a
        (dtype, side, layout), under a ``sparse.place`` span that carries
        the side, the bytes placed, the lanes the walk takes ``group`` at a
        time (``grouped_lanes``), the segments whose last chunk outlasts
        the copy of B's next tile (``covered_segments``) and the seconds it
        took; kept like the lanes: later calls move nothing.

        ``side="transposed"`` is the second placement, for ``Aᵀ·B``: the
        lanes of Aᵀ — this matrix's own CSC buffers — laid by
        :func:`_tile_runs` (result rows are A's columns, ``layout`` a
        ``runs`` plan's), made at the first transposed product and never at
        a rowwise one; its span also counts ``run_lanes`` and
        ``run_slots``."""
        return self._tiled(layout, dtype, side)[0]

    def grouped_lanes(self, layout: tuple, dtype=None,
                      side: str = "rows") -> int:
        """Of the stored lanes of :meth:`tiled_device` under ``layout``,
        those the kernel walks ``group`` rows at a time: the whole groups
        of every chunk's grouped slots. The rest is walked lane by lane
        (as runs of one row on the transposed side)."""
        return self._tiled(layout, dtype, side)[1]["grouped_lanes"]

    def covered_segments(self, layout: tuple, dtype=None,
                         side: str = "rows") -> int:
        """Of the live segments of :meth:`tiled_device` under ``layout``,
        those whose last chunk holds at least ``cover`` stored lanes: its
        walk outlasts the copy of the next segment's tile of B."""
        return self._tiled(layout, dtype, side)[1]["covered_segments"]

    def _tiled(self, layout: tuple, dtype, side: str = "rows") -> tuple:
        """``(placed arrays, counts)`` of one (dtype, side, layout)."""
        eff = self._device_dtype_of(dtype)
        layouts = self._device.setdefault(eff, {})
        key = ("tiled", side) + tuple(layout)
        if key not in layouts:
            from libskylark_tpu.telemetry import trace as _trace

            (row_block, col_tile, chunk, n_chunks, group, stride,
             cover) = layout
            transposed = side == "transposed"
            # set-up work, once a layout: timed whoever listens (the
            # benchmark's setup_place_s reads these spans), like
            # telemetry/setup.py's records
            with _trace.span("sparse.place", {"layout": "tiled", "side": side,
                                              "nnz": self.nnz},
                             force=True) as sp:
                t0 = time.perf_counter()
                lanes, counts = (_tile_runs if transposed else _tile_lanes)(
                    *(self.csc_parts(eff) if transposed
                      else self.csr_parts(eff)),
                    shape=self._shape[::-1] if transposed else self._shape,
                    row_block=row_block, col_tile=col_tile, chunk=chunk,
                    n_chunks=n_chunks, group=group, stride=stride,
                    cover=cover)
                placed = jax.block_until_ready(
                    tuple(_place(a) for a in lanes))
                sp.attrs.update(
                    bytes=sum(int(a.nbytes) for a in placed),
                    lane_slots=n_chunks * chunk, **counts,
                    seconds=time.perf_counter() - t0)
            layouts[key] = placed, counts
        return layouts[key]

    def csc_device(self, dtype=None) -> Tuple[jax.Array, jax.Array,
                                              jax.Array]:
        """:meth:`csr_device` of Aᵀ: this matrix's column-major lanes
        ``(data, row indices, column pointers)`` on the device, the nnz
        extent zero-padded to its lane class — what the transposed
        product's span loop walks where the kernel does not serve. Placed
        on the first call for a value dtype and kept."""
        eff = self._device_dtype_of(dtype)
        layouts = self._device.setdefault(eff, {})
        if "csc" not in layouts:
            from libskylark_tpu.engine.bucket import lane_class

            layouts["csc"] = self._place_lanes(eff, lane_class(self.nnz),
                                               transposed=True)
        return layouts["csc"]

    def coo(self, dtype=None) -> Tuple[jax.Array, jax.Array, jax.Array]:
        """Device COO triplets (rows, cols, vals) in row-major order;
        cached per resolved dtype. The row ids are expanded on the device
        from the row pointers (no host ``repeat``). Where
        :meth:`csr_device`'s lanes are resident the columns and values are
        their leading ``nnz``; else the exact lanes are placed for this,
        and the triplets *are* those arrays — a product's operand holds
        12 B a nonzero on the device and no second layout.

        ``dtype=None`` always resolves to :meth:`device_dtype` (the f32
        precision-policy default) — a cache left behind by an explicit-dtype
        call is never returned for a default-dtype request."""
        eff = self._device_dtype_of(dtype)
        layouts = self._device.setdefault(eff, {})
        if "coo" not in layouts and self._born is not None:
            # a device-born matrix's triplets are its lanes, padding and all
            # (value 0.0 at column 0 of the last row: exact zeros wherever
            # triplets are summed) — cutting them to nnz would read the count
            data, indices, indptr = self.csr_device(eff)
            layouts["coo"] = (_row_ids(indptr, nnz=int(data.shape[0])),
                              indices, data)
        if "coo" not in layouts:
            data, indices, indptr = (layouts.get("csr")
                                     or self._place_lanes(eff, self.nnz))
            if data.shape[0] != self.nnz:
                data, indices = data[:self.nnz], indices[:self.nnz]
            layouts["coo"] = (_row_ids(indptr, nnz=self.nnz), indices, data)
        return layouts["coo"]

    def csr_parts(self, dtype=None) -> Tuple[np.ndarray, np.ndarray,
                                             np.ndarray]:
        """Canonical CSR parts ``(data, indices, indptr)`` as host numpy
        arrays — row-major, sorted column indices, duplicates summed —
        the lane layout the sparse serve endpoints pack
        (:mod:`libskylark_tpu.engine.serve`, ``submit_sparse``). The
        row-major nonzero order is load-bearing: the serve scatter
        accumulates in exactly this order, which is what makes the CSR
        flush bit-equal to the dense reference's row-order
        ``segment_sum`` (docs/serving, "Sparse operands on the serve
        path"). ``dtype=None`` resolves to :attr:`device_dtype` (the
        f32 precision-policy default)."""
        eff = np.dtype(dtype) if dtype is not None else np.dtype(
            jax.dtypes.canonicalize_dtype(self.device_dtype))
        A = self._host()._row_major
        if A is None:
            A = self.to_scipy().tocsr()
            A.sum_duplicates()
            A.sort_indices()
        return (np.asarray(A.data, dtype=eff),
                np.asarray(A.indices, dtype=np.int32),
                np.asarray(A.indptr, dtype=np.int32))

    def csc_parts(self, dtype=None) -> Tuple[np.ndarray, np.ndarray,
                                             np.ndarray]:
        """:meth:`csr_parts` of Aᵀ: ``(data, row indices, column
        pointers)`` column-major, sorted row indices, duplicates summed —
        this matrix's own buffers where they are canonical (those of a
        scipy CSC, or converted from a canonical CSR, are)."""
        eff = np.dtype(dtype) if dtype is not None else np.dtype(
            jax.dtypes.canonicalize_dtype(self.device_dtype))
        A = self.to_scipy()
        if not A.has_canonical_format:
            A = A.copy()
            A.sum_duplicates()
        return (np.asarray(A.data, dtype=eff),
                np.asarray(A.indices, dtype=np.int32),
                np.asarray(A.indptr, dtype=np.int32))

    def todense(self, dtype=None) -> jax.Array:
        r, c, v = self.coo(dtype)
        return jnp.zeros(self._shape, v.dtype).at[r, c].add(v)

    def to_scipy(self):
        import scipy.sparse as sp

        self._host()
        return sp.csc_matrix(
            (self._values, self._rowind, self._colptr), shape=self._shape
        )

    # -- structural ops --

    def transpose(self) -> "SparseMatrix":
        """(ref: base/sparse_matrix.hpp Transpose:303)"""
        return SparseMatrix.from_scipy(self.to_scipy().T)

    @property
    def T(self) -> "SparseMatrix":
        return self.transpose()

    def column_view(self, j0: int, j1: int) -> "SparseMatrix":
        """Read-only view of columns [j0, j1) (ref: view:256) — shares the
        rowind/values buffers."""
        self._host()
        lo, hi = self._colptr[j0], self._colptr[j1]
        return SparseMatrix(
            self._colptr[j0 : j1 + 1] - lo,
            self._rowind[lo:hi],
            self._values[lo:hi],
            (self.height, j1 - j0),
        )

    def __repr__(self) -> str:
        nnz = self._nnz if self._nnz is not None else "on device"
        return (
            f"SparseMatrix({self.height}x{self.width}, nnz={nnz}, "
            f"dtype={self.dtype})"
        )


def _place(x: np.ndarray) -> jax.Array:
    """Host buffer → device array: the one place a ``SparseMatrix`` crosses
    to the device."""
    return jnp.asarray(x)


def csr_row_ids(indptr, nnz_pad: int) -> jnp.ndarray:
    """Expand a (rows+1,) CSR ``indptr`` into per-nonzero row ids for
    the leading ``nnz_pad`` lane positions (int32): the row of position
    j is the number of interior row ends at or before j. Positions past
    the true nnz (the lane padding; ``indptr`` is monotone-padded with
    nnz) come out as the last row — their data is 0.0, so that target
    accumulates exact zeros. Jittable: one scatter of the row ends and a
    running sum over the static lane extent (a ``searchsorted`` over the
    lanes gives the same ids and takes 2.8 s for 16.8 M lanes on a v5e
    against 7 ms, PERF.md PR 28)."""
    ends = jnp.zeros((nnz_pad,), jnp.int32).at[indptr[1:-1]].add(
        1, indices_are_sorted=True)     # monotone row pointers: XLA adds no
                                        # sort (12 s of compile at 19 M lanes)
    return jnp.cumsum(ends, dtype=jnp.int32)


_row_ids = jax.jit(lambda indptr, *, nnz: csr_row_ids(indptr, nnz),
                   static_argnames=("nnz",))


_RANK_CLASSES = 16      # a lane's rank is kept to 15: later ones share a class


def _tile_lanes(data: np.ndarray, indices: np.ndarray, indptr: np.ndarray, *,
                shape: Tuple[int, int], row_block: int, col_tile: int,
                chunk: int, n_chunks: int, group: int, stride: int,
                cover: int) -> tuple:
    """Host CSR parts → the (segment, count, packed, vals) of
    :meth:`SparseMatrix.tiled_device`, numpy arrays, and the counts
    ``grouped_lanes``, the stored lanes the walk takes ``group`` at a time,
    and ``covered_segments``: the live segments whose last chunk — a full
    one, or the only one — holds at
    least ``cover`` stored lanes (``TilesPlan.cover``: enough for its walk
    to outlast the copy of the next segment's tile of B).

    A lane's *rank* is its place among the lanes of its own row inside its
    segment (CSR order, sorted columns: its index less the index of the
    first lane of its (row, column tile) run), kept to ``_RANK_CLASSES``
    − 1. ONE stable sort by segment · ``_RANK_CLASSES`` + rank lays a
    segment's lanes by (rank, row): every row's first lane of the tile by
    rising row, then every row's second, …, the lanes of the last class
    row-major. The key fits 16 bits, and numpy sorts it by radix, up to
    4096 segments (3072 at 262144 × 47236); past that it is an int32 and
    numpy's slower merge sort serves (no cell does that). The rows of
    rank class r + 1 are among those of class r, so any ``group``
    consecutive lanes that lie in classes of at least ``group`` rows
    address ``group`` different rows: a segment's *grouped prefix* ends at
    its first class with fewer rows, the rest is its *serial tail*. A
    segment's ⌈stored ÷ chunk⌉ chunks are full but its first, which holds
    the remainder; ``count`` holds, a chunk, its stored lanes and · 2¹⁶
    how many of them, from its first slot on, lie in the grouped prefix. A
    packed word counts its row and its column in units of ``stride``.
    ``n_chunks`` bounds the chunks of any operand of these extents; chunks
    past the last live one repeat its segment with count 0, so the
    kernel's pipeline fetches nothing for them."""
    rows, n = int(shape[0]), int(shape[1])
    col_tiles = -(-n // col_tile)
    n_seg = -(-rows // row_block) * col_tiles
    nnz = int(indices.shape[0])
    if chunk >= 1 << 16:
        raise errors.InvalidParametersError(
            f"a chunk of {chunk} slots does not pack into a count")
    ranks = _RANK_CLASSES
    key_t = np.uint16 if n_seg * ranks <= 1 << 16 else np.int32
    lengths = np.diff(indptr)
    tile, _, at, rank = _lane_ranks(indices, indptr, col_tile, key_t)
    row = np.arange(rows, dtype=np.int32)
    key = np.repeat((row // row_block * (col_tiles * ranks)).astype(key_t),
                    lengths)
    key += tile * key_t(ranks)
    key += np.minimum(rank, ranks - 1).astype(key_t)
    order = np.argsort(key, kind="stable")      # (segment, rank, row) order
    ends = np.searchsorted(key[order],
                           np.arange(1, n_seg * ranks).astype(key_t))
    sizes = np.diff(ends, prepend=0, append=nnz).reshape(n_seg, ranks)
    stored = sizes.sum(axis=1)
    wide = np.minimum.accumulate(sizes[:, :-1] >= group, axis=1)
    grouped = (sizes[:, :-1] * wide).sum(axis=1)
    segment, count, holds, ahead, _, _, covered = _deal_chunks(
        stored, grouped, chunk=chunk, n_chunks=n_chunks, col_tiles=col_tiles,
        cover=cover)
    live = holds.shape[0]
    # sorted lanes lie segment after segment and, dealt in order, chunk
    # after chunk: a lane's slot is its chunk's first slot plus its place
    first_lane = np.cumsum(holds) - holds
    slot = at + np.repeat(
        (np.arange(live) * chunk - first_lane).astype(np.int32), holds)
    word = _packed_words(indices, lengths, tile, row_block, col_tile, stride)
    packed = np.zeros(n_chunks * chunk, np.int32)
    vals = np.zeros(n_chunks * chunk, data.dtype)
    packed[slot] = word[order]
    vals[slot] = data[order]
    return ((segment, count, packed.reshape(n_chunks, 1, chunk),
             vals.reshape(n_chunks, 1, chunk)),
            {"grouped_lanes": int((ahead // group * group).sum()),
             "covered_segments": covered})


def _lane_ranks(indices: np.ndarray, indptr: np.ndarray, col_tile: int,
                key_t) -> tuple:
    """``(tile, starts, at, rank)`` of row-major lanes: a lane's column
    tile, whether it starts a (row, tile) run — where the tile changes or a
    row does —, its index and its place in its run."""
    nnz = int(indices.shape[0])
    lengths = np.diff(indptr)
    tile = (indices.view(np.uint32) // np.uint32(col_tile)).astype(key_t)
    starts = np.empty(nnz, bool)
    starts[:1] = True
    np.not_equal(tile[1:], tile[:-1], out=starts[1:])
    starts[indptr[:-1][lengths > 0]] = True
    at = np.arange(nnz, dtype=np.int32)
    rank = at - np.maximum.accumulate(np.where(starts, at, 0))
    return tile, starts, at, rank


def _packed_words(indices: np.ndarray, lengths: np.ndarray, tile: np.ndarray,
                  row_block: int, col_tile: int, stride: int) -> np.ndarray:
    """A lane's packed word: row · stride in the block · 2¹⁶ + column ·
    stride in the tile."""
    row = np.arange(lengths.shape[0], dtype=np.int32)
    word = np.repeat((row % row_block * stride) << 16, lengths)
    word += indices * stride if stride != 1 else indices
    word -= tile.astype(np.int32) * (col_tile * stride)
    return word


def _tile_runs(data: np.ndarray, indices: np.ndarray, indptr: np.ndarray, *,
               shape: Tuple[int, int], row_block: int, col_tile: int,
               chunk: int, n_chunks: int, group: int, stride: int,
               cover: int) -> tuple:
    """:func:`_tile_lanes` for a product whose result rows are long and
    skewed — the transposed side, the lanes those of Aᵀ (A's CSC), a result
    row a *feature*: a frequent one holds most of a tile's columns, most
    rows hold a lane or none. The same tables and slots, another order
    inside a segment (``TilesPlan.runs``):

    * a row with at least ``pallas_spmm._RUN_ROW`` (≤ ``_RANK_CLASSES``)
      lanes in the segment is kept whole, row-major, as a *run*; the
      others' lanes lie by (rank, row) as :func:`_tile_lanes` lays them,
      and the *grouped region* is the grouped prefix of those cut to whole
      groups of ``group``;
    * every other lane (the whole rows, the classes past the prefix, the
      prefix's last few) joins the *run region* behind it, by (row,
      column), each row's lanes padded to a multiple of ``group`` slots
      with zero-valued copies of its last lane — so that ``group`` slots
      address ONE row, and the kernel sums them in registers and loads and
      stores the row once. A padding slot multiplies a row of B that its
      own row multiplies anyway.

    The padding is under ``group`` slots a run: (``group`` − 1) /
    ``_RUN_ROW`` of the lanes of the whole rows, and at most ``_RUN_ROW`` ·
    (``group`` − 1) other run lanes a segment — what ``tiles_plan``'s chunk
    bound counts. ``count`` holds a chunk's slots in use (padding included)
    and · 2¹⁶ those of the grouped region. Two radix sorts (16-bit keys up
    to 4096 segments).
    Returns the arrays and the counts ``grouped_lanes``, ``run_lanes``
    (stored lanes in runs), ``run_slots`` (with their padding) and
    ``covered_segments``."""
    rows, n = int(shape[0]), int(shape[1])
    col_tiles = -(-n // col_tile)
    n_seg = -(-rows // row_block) * col_tiles
    nnz = int(indices.shape[0])
    if chunk >= 1 << 16 or chunk % group:
        raise errors.InvalidParametersError(
            f"a chunk of {chunk} slots does not pack into a count of runs "
            f"of {group}")
    # the plan's own number: its chunk bound counts the padding by it
    from libskylark_tpu.sketch.pallas_spmm import _RUN_ROW

    ranks = _RANK_CLASSES
    key_t = np.uint16 if n_seg * ranks <= 1 << 16 else np.int32
    lengths = np.diff(indptr)
    tile, starts, at, rank = _lane_ranks(indices, indptr, col_tile, key_t)
    begins = np.flatnonzero(starts)
    held = np.diff(begins, append=nnz)          # lanes of a (row, tile) run
    # a whole row goes to the last class
    rank[np.repeat(held >= _RUN_ROW, held)] = ranks - 1
    row = np.arange(rows, dtype=np.int32)
    seg = np.repeat((row // row_block * col_tiles).astype(key_t), lengths)
    seg += tile
    key = seg * key_t(ranks) + rank.astype(key_t)
    order = np.argsort(key, kind="stable")      # (segment, class, row) order
    sizes = np.bincount(key, minlength=n_seg * ranks).reshape(n_seg, ranks)
    wide = np.minimum.accumulate(sizes[:, :-1] >= group, axis=1)
    grouped = (sizes[:, :-1] * wide).sum(axis=1) // group * group
    lanes_of = sizes.sum(axis=1)
    place = at - np.repeat(np.cumsum(lanes_of) - lanes_of,
                           lanes_of).astype(np.int32)
    ahead = place < np.repeat(grouped, lanes_of)    # sorted lanes, grouped
    g_lane, g_place = order[ahead], place[ahead]
    # the run region: what is left, in (segment, row, column) order — the
    # lanes are row-major already, so one stable sort by segment
    left = np.ones(nnz, bool)
    left[g_lane] = False
    r_lane = np.flatnonzero(left).astype(np.int32)
    r_seg = seg[r_lane]
    by_seg = np.argsort(r_seg, kind="stable")
    r_lane, r_seg = r_lane[by_seg], r_seg[by_seg].astype(np.int32)
    r_row = np.repeat(row, lengths)[r_lane]
    new = np.ones(r_lane.shape[0], bool)
    np.logical_or(r_row[1:] != r_row[:-1], r_seg[1:] != r_seg[:-1],
                  out=new[1:])
    r_begin = np.flatnonzero(new)
    r_len = np.diff(r_begin, append=r_lane.shape[0])
    r_slots = -(-r_len // group) * group
    run_seg = r_seg[r_begin]
    seg_slots = np.bincount(run_seg, weights=r_slots,
                            minlength=n_seg).astype(np.int64)
    stored = grouped + seg_slots
    segment, count, _, _, first_chunk, rest, covered = _deal_chunks(
        stored, grouped, chunk=chunk, n_chunks=n_chunks, col_tiles=col_tiles,
        cover=cover)
    # a run's first place in its segment: behind the grouped region and the
    # runs before it
    run_place = (np.cumsum(r_slots) - r_slots
                 - (np.cumsum(seg_slots) - seg_slots)[run_seg]
                 + grouped[run_seg])
    r_place = (np.repeat(run_place - r_begin, r_len)
               + np.arange(r_lane.shape[0]))
    pads = r_slots - r_len
    p_place = (np.repeat(run_place + r_len - (np.cumsum(pads) - pads), pads)
               + np.arange(int(pads.sum())))

    def slot(of, at_place):
        """A segment's places dealt into its chunks: the first holds
        ``rest`` of them, the others are full."""
        return (first_chunk[of] * chunk + at_place
                + (at_place >= rest[of]) * (chunk - rest[of]))

    word = _packed_words(indices, lengths, tile, row_block, col_tile, stride)
    packed = np.zeros(n_chunks * chunk, np.int32)
    vals = np.zeros(n_chunks * chunk, data.dtype)
    g_slot = slot(np.repeat(np.arange(n_seg), grouped), g_place)
    packed[g_slot], vals[g_slot] = word[g_lane], data[g_lane]
    r_slot = slot(r_seg, r_place)
    packed[r_slot], vals[r_slot] = word[r_lane], data[r_lane]
    # a padding slot repeats its run's last lane, value 0.0
    packed[slot(np.repeat(run_seg, pads), p_place)] = np.repeat(
        word[r_lane[r_begin + r_len - 1]], pads)
    return ((segment, count, packed.reshape(n_chunks, 1, chunk),
             vals.reshape(n_chunks, 1, chunk)),
            {"grouped_lanes": int(grouped.sum()),
             "run_lanes": int(r_lane.shape[0]),
             "run_slots": int(r_slots.sum()), "covered_segments": covered})


def _deal_chunks(stored: np.ndarray, grouped: np.ndarray, *, chunk: int,
                 n_chunks: int, col_tiles: int, cover: int) -> tuple:
    """Each segment's ``stored`` slots dealt into ⌈stored ÷ chunk⌉ chunks,
    a row block's first segment into at least one: ``(segment, count, holds,
    ahead, first_chunk, rest, covered)`` — the two chunk tables
    (``n_chunks`` entries), a live chunk's slots held and how many of them
    lie in its segment's first ``grouped`` slots, a segment's first chunk
    and the slots that one holds, and the live segments whose last chunk
    holds at least ``cover`` slots."""
    chunks = -(-stored // chunk)
    chunks[::col_tiles] = np.maximum(chunks[::col_tiles], 1)   # a row block
    chunk_end = np.cumsum(chunks)                               # owns a chunk
    live = int(chunk_end[-1])
    if live > n_chunks:
        raise errors.InvalidParametersError(
            f"tiled layout needs {live} chunks, the plan holds {n_chunks}")
    of = np.repeat(np.arange(stored.shape[0], dtype=np.int32), chunks)
    # a segment's remainder goes into its FIRST chunk and the others are
    # full: the walk of a segment's last chunk is what hides the copy of the
    # next segment's tile of B (and, at a row block's end, the write of the
    # result block), so the last chunk must be the long one — a short first
    # chunk exposes nothing, its successor reuses both blocks (PERF.md
    # PR 57: a nearly empty last chunk cost what a full one did, and a
    # block whose last segment was short read 2.4 ms longer)
    first_chunk = chunk_end - chunks
    nth = np.arange(live) - first_chunk[of]                 # chunk of its segment
    rest = stored - (np.maximum(chunks, 1) - 1) * chunk
    holds = np.where(nth == 0, rest[of], chunk)
    before = np.where(nth == 0, 0, rest[of] + (nth - 1) * chunk)
    ahead = np.clip(grouped[of] - before, 0, holds)
    segment = np.full(n_chunks, of[-1], np.int32)
    segment[:live] = of
    count = np.zeros(n_chunks, np.int32)
    count[:live] = holds | ahead << 16
    # a segment's last chunk is full, or the one chunk that holds it all
    covered = (stored > 0) & (np.where(chunks > 1, chunk, stored) >= cover)
    return segment, count, holds, ahead, first_chunk, rest, int(covered.sum())


def is_sparse_operand(A) -> bool:
    """True for the framework's sparse matrix kinds (local
    :class:`SparseMatrix` or mesh-distributed ``DistSparseMatrix``) —
    the shared predicate for operand dispatch in the solver layers."""
    from libskylark_tpu.base.dist_sparse import DistSparseMatrix

    return isinstance(A, (SparseMatrix, DistSparseMatrix))


# The sparse products route through the engine's executable cache
# (:mod:`libskylark_tpu.engine.compiled`): one cached executable per op
# name, kernel, avals and shapes, built lazily (first product) so that
# importing ``base.sparse`` never pulls the engine or the sketch layer, and
# covered by the jit-leak gate's zero-recompile contract.
_COMPILED_PRODUCTS: dict = {}

# lanes one step of the XLA product gathers rows of B for: its workspace is
# this many rows of k values (128 MiB at k = 1024), whatever nnz is
_SPAN_LANES = 1 << 15


def spans_product(data, indices, indptr, B, *, n_rows: int) -> jnp.ndarray:
    """``A·B`` from CSR lanes by XLA, in spans of ``_SPAN_LANES`` lanes: a
    span gathers its lanes' rows of B, scales them and adds them to the
    rows of the result its lanes lie in (sorted row ids: CSR order). The
    temporaries are one span's (``_SPAN_LANES`` × k) and the row ids (one
    int32 a lane); the lane padding carries value 0.0 and adds exact zeros
    to the last row. Traceable; the product every backend can run."""
    lanes = data.shape[0]
    span = min(_SPAN_LANES, lanes)
    row = csr_row_ids(indptr, lanes)
    pad = -lanes % span
    if pad:
        data, indices = jnp.pad(data, (0, pad)), jnp.pad(indices, (0, pad))
        row = jnp.pad(row, (0, pad), mode="edge")

    def add_span(q, out):
        at = q * span
        v, c, r = (jax.lax.dynamic_slice_in_dim(x, at, span)
                   for x in (data, indices, row))
        return out.at[r].add(v[:, None] * B[c], indices_are_sorted=True)

    out = jnp.zeros((n_rows, B.shape[1]), B.dtype)
    if lanes + pad == span:
        return add_span(0, out)
    return jax.lax.fori_loop(0, (lanes + pad) // span, add_span, out)


def _product(op: str, *operands, **statics) -> jax.Array:
    """``spmm`` (``op`` ``"spmm"``, the program ``sparse.spmm``) or
    ``spmm_t`` (``"spmm_t"``, ``sparse.spmm_t``): one body,
    ``sparse_serve.product_lanes``, over the lanes of the side it serves
    (A's, or Aᵀ's), as one compiled program. Under a caller's trace (a
    Krylov loop's body: B a tracer) the body is traced into the caller's
    program instead, the placed lanes its constants."""
    from libskylark_tpu.sketch.sparse_serve import product_lanes

    if isinstance(operands[-1], jax.core.Tracer):
        return product_lanes(*operands, **statics)
    cf = _COMPILED_PRODUCTS.get(op)
    if cf is None:
        from libskylark_tpu.engine.compiled import compiled as _compiled

        cf = _COMPILED_PRODUCTS[op] = _compiled(
            product_lanes, name=f"sparse.{op}",
            static_argnames=("kernel", "shape", "plan"))
    return cf(*operands, **statics)


def product_operands(A: SparseMatrix, k: int, dtype,
                     side: str = "rows") -> tuple:
    """What the ``sparse.spmm`` program body takes for ``A`` against a
    right factor of ``k`` columns: ``(lanes, kernel, plan, attrs)`` — the
    device arrays (regrouped for the kernel, else the CSR lanes), the
    kernel's name as ``sparse_serve.product_kernel`` decides it, its plan
    (None off the kernel) and the counts a ``sketch.dispatch`` span
    carries. ``side="transposed"``: the same for ``Aᵀ`` against a factor of
    ``A.height`` rows — Aᵀ's lanes (A's CSC), its own placement. Placement
    happens here, once per side and layout."""
    from libskylark_tpu.engine.bucket import lane_class
    from libskylark_tpu.sketch.sparse_serve import product_kernel

    nnz_class = lane_class(A.nnz)
    transposed = side == "transposed"
    eff = A._device_dtype_of(dtype)
    kernel, plan = product_kernel(A.shape, k, nnz_class, eff,
                                  rowwise=not transposed)
    # "kernel_view": the product leaves the Mosaic call in the kernel's view
    # and XLA relays it into rows (pallas_spmm.tiles_apply)
    attrs = {"kernel": kernel, "nnz": A.nnz, "nnz_class": nnz_class,
             "result_layout": ("rows" if plan is None or plan.stride > 1
                               else "kernel_view")}
    if transposed:
        attrs["side"] = side
    if plan is None:
        attrs.update(lane_slots=nnz_class, segments=1)
        lanes = A.csc_device(dtype) if transposed else A.csr_device(dtype)
        return lanes, kernel, None, attrs
    lanes, counts = A._tiled(plan.layout, dtype, side)
    attrs.update(lane_slots=plan.n_chunks * plan.chunk, **counts,
                 segments=plan.row_blocks * plan.col_tiles,
                 row_block=plan.row_block, col_tile=plan.col_tile,
                 chunk=plan.chunk)
    return lanes, kernel, plan, attrs


def spmm(A: SparseMatrix, B) -> jax.Array:
    """A @ B with A sparse (h×w), B dense (w×k) → dense (h×k), the
    reference's local sparse × dense kernels (ref: base/Gemm.hpp:335-519)
    as ONE compiled program (``sparse.spmm``, ``engine.compiled``) whose
    workspace is bounded independently of nnz × k: the Pallas walk of
    ``sketch/pallas_spmm.py`` where ``sparse_serve.product_kernel`` finds
    the shapes (a TPU, float32, k a multiple of 128 up to 2048), else
    :func:`spans_product`. Counts the stored nonzeros under
    ``sparse.spmm_nnz``."""
    B = jnp.asarray(B)
    squeeze = B.ndim == 1
    if squeeze:
        B = B[:, None]
    if B.shape[0] != A.width:
        raise errors.InvalidParametersError(
            f"spmm: A is {A.shape}, B is {B.shape}"
        )
    lanes, kernel, plan, _ = product_operands(A, int(B.shape[1]), B.dtype)
    out = _product("spmm", *lanes, B, kernel=kernel, shape=A.shape, plan=plan)
    _spmm_nnz().inc_always(A.nnz, kernel=kernel)
    return out[:, 0] if squeeze else out


@functools.lru_cache(maxsize=None)
def _spmm_nnz():
    from libskylark_tpu.telemetry import metrics as _metrics

    return _metrics.counter(
        "sparse.spmm_nnz",
        "Stored nonzeros multiplied through base.sparse.spmm and spmm_t "
        '(side="transposed"), by kernel')


def spmm_t(A: SparseMatrix, B) -> jax.Array:
    """Aᵀ @ B with A sparse (h×w), B dense (h×k) → dense (w×k): ONE
    compiled program (``sparse.spmm_t``), :func:`spmm`'s body over the
    lanes of Aᵀ — A's own column-major buffers, placed at the first
    transposed product: regrouped by (block of A's columns, tile of A's
    rows) for the Pallas walk where ``sparse_serve.product_kernel(...,
    rowwise=False)`` finds the shapes (a TPU, float32, k a multiple of 128
    up to 2048), else the span loop. Workspace, whatever nnz is: the
    kernel's VMEM blocks (and, at a k that is no multiple of 1024, the
    (w × k) relayout of its result), or the span loop's ``_SPAN_LANES`` × k
    rows and one int32 a lane. Counts the stored nonzeros under
    ``sparse.spmm_nnz`` with ``side="transposed"``."""
    B = jnp.asarray(B)
    squeeze = B.ndim == 1
    if squeeze:
        B = B[:, None]
    if B.shape[0] != A.height:
        raise errors.InvalidParametersError(
            f"spmm_t: A is {A.shape}, B is {B.shape}"
        )
    lanes, kernel, plan, _ = product_operands(A, int(B.shape[1]), B.dtype,
                                              side="transposed")
    out = _product("spmm_t", *lanes, B, kernel=kernel, shape=A.shape[::-1],
                   plan=plan)
    _spmm_nnz().inc_always(A.nnz, kernel=kernel, side="transposed")
    return out[:, 0] if squeeze else out


def gemm(A, B, transpose_a: bool = False) -> jax.Array:
    """Unified dense/sparse matmul (ref: base/Gemm.hpp's overload set).

    Sparse operands use the segment-sum kernels; dense×dense is a plain
    jnp matmul (sharded inputs flow through, XLA inserts collectives)."""
    a_sp = isinstance(A, SparseMatrix)
    b_sp = isinstance(B, SparseMatrix)
    if a_sp and b_sp:
        # sparse×sparse stays on host (ref: CombBLAS path — out of TPU scope)
        out = (A.to_scipy().T if transpose_a else A.to_scipy()) @ B.to_scipy()
        return SparseMatrix.from_scipy(out)
    if a_sp:
        return spmm_t(A, B) if transpose_a else spmm(A, B)
    if b_sp:
        A = jnp.asarray(A)
        if transpose_a:
            A = A.T
        # A @ B = (Bᵀ @ Aᵀ)ᵀ
        return spmm_t(B, A.T).T
    A = jnp.asarray(A)
    return (A.T if transpose_a else A) @ jnp.asarray(B)
