"""Sketch application over mesh-distributed sparse matrices (P4/P5).

TPU-native analog of the reference's distributed-sparse sketch engines:
the CombBLAS hash-transform specializations
(ref: sketch/hash_transform_CombBLAS.hpp:16-632) and the mixed
sparse-input dense transform (ref: sketch/dense_transform_Mixed.hpp:19).

Pattern shared by all four applies: a ``shard_map`` in which each grid
cell contracts its *local* nonzeros — hash transforms via an O(nnz)
scatter-add into the bucket dimension, dense transforms via a segment-sum
against an on-device-generated panel of the virtual operator S (the
``realize_matrix_view`` trick, ref: sketch/dense_transform_data.hpp:79-152,
here with traced block ids so each device builds exactly its own panel) —
followed by one ``psum`` over the mesh axis that carries the sketched
dimension (the reference's local-accumulate + all_reduce,
ref: sketch/hash_transform_Elemental.hpp:427-607).

Outputs are dense, sharded on the kept axis; the sketched dimension is
replicated (the [★,★]-output convention of the reference's dist applies).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from libskylark_tpu.base import errors
from libskylark_tpu.base.dist_sparse import DistSparseMatrix


def _check_dim(T, D: DistSparseMatrix, columnwise: bool) -> None:
    n = D.height if columnwise else D.width
    if n != T.input_dim:
        raise errors.SketchError(
            f"{'columnwise' if columnwise else 'rowwise'} apply expects "
            f"{T.input_dim} on the sketched dimension, got {D.shape}"
        )


# ---------------------------------------------------------------------------
# hash transforms (CWT / MMT / WZT)
# ---------------------------------------------------------------------------


def hash_columnwise(T, D: DistSparseMatrix) -> jax.Array:
    """S·A for A (N, w) distributed sparse → (S_dim, w) sharded on
    ``col_axis`` (bucket dimension replicated)."""
    _check_dim(T, D, columnwise=True)
    h = T.bucket_indices()
    vs = T.values(D.dtype)
    s_dim, bs_r, bs_c = T.sketch_dim, D.bs_r, D.bs_c
    row_axis, col_axis = D.row_axis, D.col_axis

    def local(lr, lc, v, h, vs):
        lr, lc, v = lr[0, 0], lc[0, 0], v[0, 0]
        rb = lax.axis_index(row_axis) if row_axis else 0
        g = rb * bs_r + lr                     # global input coordinate
        part = jnp.zeros((s_dim, bs_c), v.dtype).at[h[g], lc].add(vs[g] * v)
        if row_axis:
            part = lax.psum(part, row_axis)
        return part[None]

    out = shard_map(
        local,
        mesh=D.mesh,
        in_specs=(D._triplet_spec(),) * 3 + (P(), P()),
        out_specs=P(col_axis, None, None),
    )(D.lr, D.lc, D.v, h, vs)
    return out.transpose(1, 0, 2).reshape(s_dim, D.pc * bs_c)[:, : D.width]


def hash_rowwise(T, D: DistSparseMatrix) -> jax.Array:
    """A·Sᵀ for A (m, N) distributed sparse → (m, S_dim) sharded on
    ``row_axis``."""
    _check_dim(T, D, columnwise=False)
    h = T.bucket_indices()
    vs = T.values(D.dtype)
    s_dim, bs_r, bs_c = T.sketch_dim, D.bs_r, D.bs_c
    row_axis, col_axis = D.row_axis, D.col_axis

    def local(lr, lc, v, h, vs):
        lr, lc, v = lr[0, 0], lc[0, 0], v[0, 0]
        cb = lax.axis_index(col_axis) if col_axis else 0
        g = cb * bs_c + lc
        part = jnp.zeros((bs_r, s_dim), v.dtype).at[lr, h[g]].add(vs[g] * v)
        if col_axis:
            part = lax.psum(part, col_axis)
        return part[None]

    out = shard_map(
        local,
        mesh=D.mesh,
        in_specs=(D._triplet_spec(),) * 3 + (P(), P()),
        out_specs=P(row_axis, None, None),
    )(D.lr, D.lc, D.v, h, vs)
    return out.reshape(D.pr * bs_r, s_dim)[: D.height]


def hash_apply_sparse(T, D: DistSparseMatrix, columnwise: bool = True
                      ) -> DistSparseMatrix:
    """Sparse→sparse distributed hash apply: the analog of the reference's
    SpParMat → SpParMat CombBLAS path (ref:
    sketch/hash_transform_CombBLAS.hpp:141-632 — sketching a distributed
    sparse matrix without densifying it).

    A hash sketch maps each nonzero 1:1 — columnwise, (r, c, v) →
    (h[r], c, vs[r]·v) — so the triplets are rewritten cell-locally with
    NO arithmetic collective; the cells along the sketched axis then merge
    into one bucket-extent block (a reshape across that mesh axis — data
    movement proportional to nnz), leaving a :class:`DistSparseMatrix`
    distributed on the kept axis only. Padding entries stay padding (v=0
    at local (0,0)). Duplicate bucket collisions remain separate COO
    entries — every consumer (spmm/todense/to_local) sums duplicates, the
    CSC ``set()`` convention of ref: base/sparse_matrix.hpp:136.
    """
    import jax as _jax
    from jax.sharding import NamedSharding

    _check_dim(T, D, columnwise=columnwise)
    h = T.bucket_indices()
    vs = T.values(D.dtype)
    bs_r, bs_c = D.bs_r, D.bs_c
    row_axis, col_axis = D.row_axis, D.col_axis
    mesh = D.mesh

    def local(lr, lc, v, h, vs):
        lr_, lc_, v_ = lr[0, 0], lc[0, 0], v[0, 0]
        keep = v_ != 0
        if columnwise:
            rb = lax.axis_index(row_axis) if row_axis else 0
            g = rb * bs_r + lr_
            new_lr = jnp.where(keep, h[g], 0)
            new_lc = lc_
        else:
            cb = lax.axis_index(col_axis) if col_axis else 0
            g = cb * bs_c + lc_
            new_lr = lr_
            new_lc = jnp.where(keep, h[g], 0)
        new_v = jnp.where(keep, vs[g] * v_, jnp.zeros((), v_.dtype))
        return (new_lr[None, None], new_lc[None, None], new_v[None, None])

    nlr, nlc, nv = shard_map(
        local, mesh=mesh,
        in_specs=(D._triplet_spec(),) * 3 + (P(), P()),
        out_specs=(D._triplet_spec(),) * 3,
    )(D.lr, D.lc, D.v, h, vs)

    pr, pc, pad = D.pr, D.pc, D.v.shape[-1]
    if columnwise:
        # merge the pr row-cells into the single bucket row block
        spec = NamedSharding(mesh, P(None, col_axis, None))
        merge = lambda a: _jax.device_put(
            a.transpose(1, 0, 2).reshape(1, pc, pr * pad), spec)
        out = DistSparseMatrix(
            mesh, None, col_axis, (T.sketch_dim, D.width),
            merge(nlr), merge(nlc), merge(nv),
        )
    else:
        spec = NamedSharding(mesh, P(row_axis, None, None))
        merge = lambda a: _jax.device_put(
            a.reshape(pr, 1, pc * pad), spec)
        out = DistSparseMatrix(
            mesh, row_axis, None, (D.height, T.sketch_dim),
            merge(nlr), merge(nlc), merge(nv),
        )
    # the merge multiplied the slot count by the merged axis extent while
    # real nnz stayed fixed; re-compact so chained sparse applies don't
    # compound mostly-zero slots (advisor r2 finding). Skipped when the
    # merged axis had extent 1 (no growth): compact()'s nnz readback is a
    # blocking device sync not worth paying on the no-op case.
    merged_extent = pr if columnwise else pc
    return out.compact() if merged_extent > 1 else out


# ---------------------------------------------------------------------------
# UST (row/column sampling) — per-cell one-hot selection + psum
# ---------------------------------------------------------------------------


def ust_columnwise(T, D: DistSparseMatrix) -> jax.Array:
    """S·A = A[idx, :] for A (N, w) distributed sparse → (S_dim, w)
    dense, sharded on ``col_axis``. Each cell scatters the nonzeros whose
    global row is sampled into the output slots (handles
    with-replacement duplicates: every slot t with idx[t] == r receives
    row r)."""
    _check_dim(T, D, columnwise=True)
    idx = T.sample_indices()                      # (S_dim,) global rows
    s_dim, bs_r, bs_c = T.sketch_dim, D.bs_r, D.bs_c
    row_axis, col_axis = D.row_axis, D.col_axis

    # out[t, c] = Σ_j sel[t, j] · v[j] · [lc[j] == c]
    def local(lr, lc, v, idx):
        lr_, lc_, v_ = lr[0, 0], lc[0, 0], v[0, 0]
        rb = lax.axis_index(row_axis) if row_axis else 0
        g = rb * bs_r + lr_
        sel = (idx[:, None] == g[None, :]).astype(v_.dtype)  # (s, pad)
        weighted = sel * v_[None, :]
        part = jax.ops.segment_sum(
            weighted.T, lc_, num_segments=bs_c
        ).T                                        # (s, bs_c)
        if row_axis:
            part = lax.psum(part, row_axis)
        return part[None]

    out = shard_map(
        local,
        mesh=D.mesh,
        in_specs=(D._triplet_spec(),) * 3 + (P(),),
        out_specs=P(col_axis, None, None),
    )(D.lr, D.lc, D.v, idx)
    return out.transpose(1, 0, 2).reshape(s_dim, D.pc * bs_c)[:, : D.width]


def ust_rowwise(T, D: DistSparseMatrix) -> jax.Array:
    """A·Sᵀ = A[:, idx] for A (m, N) distributed sparse → (m, S_dim)
    dense, sharded on ``row_axis``."""
    _check_dim(T, D, columnwise=False)
    idx = T.sample_indices()
    s_dim, bs_r, bs_c = T.sketch_dim, D.bs_r, D.bs_c
    row_axis, col_axis = D.row_axis, D.col_axis

    def local(lr, lc, v, idx):
        lr_, lc_, v_ = lr[0, 0], lc[0, 0], v[0, 0]
        cb = lax.axis_index(col_axis) if col_axis else 0
        g = cb * bs_c + lc_
        sel = (g[:, None] == idx[None, :]).astype(v_.dtype)  # (pad, s)
        weighted = sel * v_[:, None]
        part = jax.ops.segment_sum(
            weighted, lr_, num_segments=bs_r
        )                                          # (bs_r, s)
        if col_axis:
            part = lax.psum(part, col_axis)
        return part[None]

    out = shard_map(
        local,
        mesh=D.mesh,
        in_specs=(D._triplet_spec(),) * 3 + (P(),),
        out_specs=P(row_axis, None, None),
    )(D.lr, D.lc, D.v, idx)
    return out.reshape(D.pr * bs_r, s_dim)[: D.height]


# ---------------------------------------------------------------------------
# dense transforms (JLT / CT) — virtual-operator panels per cell
# ---------------------------------------------------------------------------


def _cell_panel(T, block_start, width: int, dtype):
    """S[:, block_start .. +width) with a *traced* start column.

    Generates the static number of BLOCK_COLS blocks covering any
    alignment (one vmapped generator call — a single traced kernel, not
    nb unrolled ones), then dynamic-slices — each device materializes
    only its own (S_dim × width(+BC)) window of the virtual operator."""
    from libskylark_tpu.sketch.dense import BLOCK_COLS

    nb = -(-width // BLOCK_COLS) + 1
    first = block_start // BLOCK_COLS
    off = block_start % BLOCK_COLS
    blocks = jax.vmap(
        lambda b: T.s_block(b, dtype)
    )(first + jnp.arange(nb, dtype=jnp.int32))        # (nb, s_dim, BC)
    panel = blocks.transpose(1, 0, 2).reshape(T.sketch_dim, nb * BLOCK_COLS)
    return lax.dynamic_slice(
        panel, (0, off), (T.sketch_dim, width)
    )


def dense_rowwise(T, D: DistSparseMatrix) -> jax.Array:
    """A·Sᵀ for A (m, N) distributed sparse → (m, S_dim) sharded on
    ``row_axis``; contraction over the col axis rides one psum."""
    _check_dim(T, D, columnwise=False)
    s_dim, bs_r, bs_c = T.sketch_dim, D.bs_r, D.bs_c
    row_axis, col_axis = D.row_axis, D.col_axis

    def local(lr, lc, v):
        lr, lc, v = lr[0, 0], lc[0, 0], v[0, 0]
        cb = lax.axis_index(col_axis) if col_axis else 0
        panelT = _cell_panel(T, cb * bs_c, bs_c, v.dtype).T   # (bs_c, s_dim)
        part = jax.ops.segment_sum(
            v[:, None] * panelT[lc], lr, num_segments=bs_r
        )
        if col_axis:
            part = lax.psum(part, col_axis)
        return part[None]

    out = shard_map(
        local,
        mesh=D.mesh,
        in_specs=(D._triplet_spec(),) * 3,
        out_specs=P(row_axis, None, None),
    )(D.lr, D.lc, D.v)
    return out.reshape(D.pr * bs_r, s_dim)[: D.height]


def dense_columnwise(T, D: DistSparseMatrix) -> jax.Array:
    """S·A for A (N, w) distributed sparse → (S_dim, w) sharded on
    ``col_axis``."""
    _check_dim(T, D, columnwise=True)
    s_dim, bs_r, bs_c = T.sketch_dim, D.bs_r, D.bs_c
    row_axis, col_axis = D.row_axis, D.col_axis

    def local(lr, lc, v):
        lr, lc, v = lr[0, 0], lc[0, 0], v[0, 0]
        rb = lax.axis_index(row_axis) if row_axis else 0
        panelT = _cell_panel(T, rb * bs_r, bs_r, v.dtype).T   # (bs_r, s_dim)
        part = jax.ops.segment_sum(
            v[:, None] * panelT[lr], lc, num_segments=bs_c
        )
        if row_axis:
            part = lax.psum(part, row_axis)
        return part.T[None]

    out = shard_map(
        local,
        mesh=D.mesh,
        in_specs=(D._triplet_spec(),) * 3,
        out_specs=P(col_axis, None, None),
    )(D.lr, D.lc, D.v)
    return out.transpose(1, 0, 2).reshape(s_dim, D.pc * bs_c)[:, : D.width]
