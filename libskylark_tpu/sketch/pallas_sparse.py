"""Pallas TPU kernel: scatter-free sparse (CSR-lane) CountSketch.

The sparse serve path's XLA program (:mod:`libskylark_tpu.sketch
.sparse_serve`) is an O(nnz) ``scatter-add`` — on TPU the scatter unit
retires one update row at a time, so even at 0.1% density the MXU
idles through the whole flush. Per the FlashSketch sketch-kernel
co-design line (PAPERS.md), this kernel restates the sparse CountSketch
as MXU work over the nonzeros only:

1. **In-kernel stream regeneration** — the (h, v) bucket/value streams
   are rebuilt from the transform's raw Threefry key with the exact
   r12 discipline (:mod:`libskylark_tpu.sketch.pallas_hash`'s
   ``chunk_key_table`` + ``_gen_hv``: per-chunk fold_in/split key table
   in SMEM, 2048-wide Threefry sweeps + ``randint`` modular math in
   VMEM), bit-identical to ``randgen.stream_slice``.

2. **Gather-on-coordinates** — the generated streams are gathered at
   the lane's nonzero coordinates (``h[rows]``/``v[rows]`` columnwise,
   ``h[cols]``/``v[cols]`` rowwise): O(nnz) stream reads instead of the
   dense kernel's O(N) sweep.

3. **Bucket-tiled one-hot MXU contraction** (``accum="mxu"``) — each
   128-nonzero tile becomes two one-hot factors: a signed bucket
   one-hot ``Hv`` (s_dim × 128, carrying v·val) and a coordinate
   one-hot (128 × m), contracted on the MXU at ``Precision.HIGHEST``.
   The one-hots are exact, so only the contraction ORDER differs from
   the scatter — last-ulp on float data, bit-equal on lattice data
   (the test battery pins the dataflow this way).

4. **Exact sequential accumulation** (``accum="exact"``) — a fori_loop
   masked outer-product add reproducing the scatter's CSR row-major
   accumulation order term by term: **bit-equal to
   ``sparse_serve.cwt_sparse_serve_apply``** (and therefore to the
   dense reference — docs/serving) including padded lane entries,
   whose 0.0 values contribute exact ±0.0.

Dispatch: :func:`qualify` **declines on CPU** — unlike the dense-lane
``pallas_hash`` exact mode, interpret-mode execution of this kernel has
no role on the serve hot path (the XLA scatter IS already the exact
reference there), so off-TPU the serve layer's qualification keeps the
scatter and the tune ladder's interpret penalty certifies XLA. Tests
exercise the kernel directly with ``interpret=True``. On TPU, routing
is autotuned per (bucket, capacity, nnz class) through the serve ladder
(``tune._serve_candidates`` / ``cost._sparse_lane_cost``) and certified
by ``bench.py --certify-kernels``; Mosaic compile-time rejection
declines back to XLA (the serve layer's poison-for-the-fingerprint-era
rule, counted as ``mosaic-reject``), never fails a request. On a TPU
v5e (jax 0.9.0) that rejection is what happens: the Pallas TPU lowering
refuses the (1, nnz) lane blocks of the (B, nnz) operands — the last
two block dims must divide (8, 128) or span the array (PERF.md).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from libskylark_tpu.sketch.pallas_dense import (_VMEM_BUDGET_BYTES,
                                                available,
                                                compiler_params)
from libskylark_tpu.sketch.pallas_hash import (CHUNK, _GEN_COLS,
                                               _MODES, _gen_hv,
                                               _padded_n,
                                               chunk_key_table)

# nonzeros contracted per one-hot MXU tile (the lane width of the
# bucket-tiled contraction)
NNZ_TILE = 128


# ---------------------------------------------------------------------------
# planning + qualification
# ---------------------------------------------------------------------------


def _vmem_estimate(s_dim: int, n_stream: int, m: int,
                   nnz_pad: int) -> int:
    """Per-lane VMEM plan: the three CSR lane arrays, the regenerated
    h/v streams (plus ~6 chunk-sized cipher temporaries), the output
    accumulator, and the two one-hot tile factors."""
    n_tile = min(n_stream, CHUNK)
    return 4 * (
        3 * nnz_pad
        + 2 * n_stream
        + 6 * n_tile
        + s_dim * m
        + s_dim * NNZ_TILE
        + NNZ_TILE * m
    )


def qualify(s_dim: int, n: int, m: int, nnz: int, dtype,
            interpret: bool = False,
            accum: str = "mxu") -> tuple[bool, str]:
    """Host-side qualification: (ok, reason). Declines on CPU even in
    interpret mode (module doc — the XLA scatter already serves the
    exact surface there); the serve layer counts the reasons in its
    ``by_reason`` decline labels."""
    if accum not in _MODES:
        return False, f"unknown accum mode {accum!r}"
    if interpret or not available():
        return False, ("backend is not a TPU (sparse kernel has no "
                       "interpret-mode serve surface — xla scatter "
                       "serves)")
    if jnp.dtype(dtype) != jnp.float32:
        return False, f"dtype {jnp.dtype(dtype).name} != float32"
    if s_dim < 1 or n < 1 or m < 1 or nnz < 1:
        return False, "degenerate shape"
    if _vmem_estimate(s_dim, _padded_n(n), m,
                      _pad_nnz(nnz)) > _VMEM_BUDGET_BYTES:
        return False, "lane does not fit the VMEM budget"
    return True, "ok"


def _pad_nnz(nnz: int) -> int:
    return -(-max(int(nnz), 1) // NNZ_TILE) * NNZ_TILE


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------


def _gen_streams(keys_ref, b, s_dim: int, n_stream: int):
    """Flat (n_stream,) h/v streams for lane ``b`` — the Python loop
    over the (static) chunk count concatenates the per-chunk 2-D
    generation grids; bit-identical to ``randgen.stream_slice`` via
    the shared ``_gen_hv`` cipher."""
    n_tile = min(n_stream, CHUNK)
    n_chunks = n_stream // n_tile
    cols = min(n_tile, _GEN_COLS)
    hs, vs = [], []
    for c in range(n_chunks):
        h, v = _gen_hv(keys_ref, b * n_chunks + c, s_dim, n_tile, cols)
        hs.append(h.reshape(-1))
        vs.append(v.reshape(-1))
    if n_chunks == 1:
        return hs[0], vs[0]
    return jnp.concatenate(hs), jnp.concatenate(vs)


def _kernel_sparse(s_dim, n_stream, m, nnz_pad, rowwise, accum,
                   keys_ref, data_ref, rows_ref, cols_ref, out_ref):
    """One lane's sparse CountSketch. Columnwise: out (s_dim, m) with
    buckets gathered at the row coordinate; rowwise: out (m, s_dim)
    with buckets gathered at the column coordinate."""
    b = pl.program_id(0)
    h, v = _gen_streams(keys_ref, b, s_dim, n_stream)
    data = data_ref[0]
    rows = rows_ref[0]
    cols = cols_ref[0]
    hashed = cols if rowwise else rows
    kept = rows if rowwise else cols
    hj = h[hashed]
    vj = v[hashed] * data
    if accum == "mxu":
        acc = None
        for t in range(nnz_pad // NNZ_TILE):
            sl = slice(t * NNZ_TILE, (t + 1) * NNZ_TILE)
            ht, vt, kt = hj[sl], vj[sl], kept[sl]
            onehot_b = (jax.lax.broadcasted_iota(
                jnp.int32, (s_dim, NNZ_TILE), 0) == ht[None, :])
            hv = onehot_b.astype(jnp.float32) * vt[None, :]
            onehot_k = (kt[:, None] == jax.lax.broadcasted_iota(
                jnp.int32, (NNZ_TILE, m), 1)).astype(jnp.float32)
            part = jax.lax.dot_general(
                hv, onehot_k, (((1,), (0,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)
            if rowwise:
                part = part.T
            acc = part if acc is None else acc + part
        out_ref[:] = acc[None]
    else:
        # exact scatter order: one nonzero at a time in CSR row-major
        # order — the masked lanes contribute ±0.0, which never
        # perturbs a sum
        out_ref[:] = jnp.zeros_like(out_ref)
        iota_s = jax.lax.broadcasted_iota(jnp.int32, (s_dim, 1), 0)
        iota_m = jax.lax.broadcasted_iota(jnp.int32, (1, m), 1)

        def body(j, _):
            hjj = jax.lax.dynamic_slice(hj, (j,), (1,))[0]
            vjj = jax.lax.dynamic_slice(vj, (j,), (1,))[0]
            kjj = jax.lax.dynamic_slice(kept, (j,), (1,))[0]
            mask_s = (iota_s == hjj).astype(jnp.float32)
            mask_m = (iota_m == kjj).astype(jnp.float32)
            upd = mask_s * (vjj * mask_m)
            out_ref[:] += (upd.T if rowwise else upd)[None]
            return 0

        jax.lax.fori_loop(0, nnz_pad, body, 0)


# ---------------------------------------------------------------------------
# launch
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit,
    static_argnames=("s_dim", "n_stream", "m", "rowwise", "accum",
                     "interpret"),
)
def _sparse_call(keys, data, rows, cols, *, s_dim, n_stream, m,
                 rowwise, accum, interpret):
    B, nnz_pad = data.shape
    out_shape = ((B, m, s_dim) if rowwise else (B, s_dim, m))
    kern = functools.partial(_kernel_sparse, s_dim, n_stream, m,
                             nnz_pad, rowwise, accum)
    lane = pl.BlockSpec((1, nnz_pad), lambda b: (b, 0),
                        memory_space=pltpu.VMEM)
    return pl.pallas_call(
        kern,
        grid=(B,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # whole key table
            lane, lane, lane,
        ],
        out_specs=pl.BlockSpec(
            (1,) + out_shape[1:], lambda b: (b, 0, 0),
            memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct(out_shape, jnp.float32),
        compiler_params=compiler_params("parallel"),
        interpret=interpret,
    )(keys, data, rows, cols)


def cwt_sparse_apply_batched(key_data, data, rows, cols, *, s_dim: int,
                             rowwise: bool, shape: tuple,
                             accum: str = "mxu",
                             interpret: bool = False) -> jnp.ndarray:
    """Batched scatter-free sparse CountSketch: one kernel over a
    stacked CSR-lane cohort. ``key_data`` (B, 2) uint32 raw keys,
    ``data``/``rows``/``cols`` (B, nnz_pad) value / row-id / column-id
    lanes (row ids pre-expanded from the indptr lanes —
    ``sparse_serve.csr_row_ids``), ``shape`` the padded (rows, cols)
    lane class. Fully traceable — the serve flush builder calls this
    inside its engine-compiled batched executable. Per-lane bits are
    capacity-invariant: every lane runs the same fixed-tile program."""
    import jax.random as jr

    if accum not in _MODES:
        raise ValueError(f"accum must be one of {_MODES}, got {accum!r}")
    data = jnp.asarray(data, jnp.float32)
    rows = jnp.asarray(rows, jnp.int32)
    cols = jnp.asarray(cols, jnp.int32)
    kd = jnp.asarray(key_data, jnp.uint32)
    B, nnz = data.shape
    n_rows, n_cols = int(shape[0]), int(shape[1])
    n = n_cols if rowwise else n_rows
    m = n_rows if rowwise else n_cols
    n_stream = _padded_n(n)
    nnz_pad = _pad_nnz(nnz)
    if nnz_pad != nnz:
        padw = ((0, 0), (0, nnz_pad - nnz))
        data = jnp.pad(data, padw)      # 0.0 values: exact no-ops
        rows = jnp.pad(rows, padw)
        cols = jnp.pad(cols, padw)
    n_tile = min(n_stream, CHUNK)
    n_chunks = n_stream // n_tile
    keys = jax.vmap(
        lambda k: chunk_key_table(jr.wrap_key_data(k), n_chunks))(kd)
    return _sparse_call(keys.reshape(B * n_chunks, 6), data, rows, cols,
                        s_dim=s_dim, n_stream=n_stream, m=m,
                        rowwise=rowwise, accum=accum,
                        interpret=interpret)


def cwt_sparse_apply(key_data, data, rows, cols, *, s_dim: int,
                     rowwise: bool, shape: tuple, accum: str = "mxu",
                     interpret: bool = False) -> jnp.ndarray:
    """Single-request form: the batched kernel at B == 1 (bit-identical
    lanes either way). Same contract as
    ``sparse_serve.cwt_sparse_serve_apply`` under ``accum="exact"``."""
    kd = jnp.asarray(key_data, jnp.uint32).reshape(1, 2)
    out = cwt_sparse_apply_batched(
        kd, jnp.asarray(data)[None], jnp.asarray(rows)[None],
        jnp.asarray(cols)[None], s_dim=s_dim, rowwise=rowwise,
        shape=shape, accum=accum, interpret=interpret)
    return out[0]
