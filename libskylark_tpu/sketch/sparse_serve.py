"""Pure, vmap-batchable sparse (CSR-lane) serve endpoints.

The microbatch serving layer (:mod:`libskylark_tpu.engine.serve`)
accepts sparse operands as padded **(data, indices, indptr) CSR lanes**:
``data``/``indices`` zero-padded to the bucket's pow2 nnz class,
``indptr`` monotone-padded with the true nnz to the padded row extent
(so ragged-nnz cohorts coalesce into one flush executable — docs/
serving, "Sparse operands on the serve path"). The functions here are
the per-lane programs those flushes vmap over; each is a pure function
of the transform's raw key data plus the CSR lanes, with every shape
static, mirroring ``sketch.hash.cwt_serve_apply`` / ``sketch.dense
.serve_apply`` for dense operands.

Exactness contract (the CI sparse-serve gate pins it):

- **CWT** (:func:`cwt_sparse_serve_apply`): each nonzero's bucket and
  sign are computed at its lane from the key data and the nonzero's
  coordinate (``randgen.stream_at`` — the integers the dense path's
  ``stream_slice`` tabulates, with no table and no gather), and the
  scatter-add runs over the CSR nonzeros in row-major order — exactly
  the order in which the dense reference's ``segment_sum`` retires the
  same nonzero terms (dense zero entries contribute exact ±0.0, which
  never perturbs an accumulator) — so the sparse flush is **bit-equal** to
  ``transform.apply(A.todense())`` at any shape and to the densified
  request through the dense serve path. Padded lane entries carry
  value 0.0 at clamped position 0: exact zeros, any capacity class.
- **dense families** (:func:`dense_sparse_serve_apply`, JLT/CT): the
  lanes are scattered to the padded dense class shape *inside the
  executable* (the integer scatter reproduces ``todense()`` exactly)
  and the request then runs the literal dense serve program
  (``dense.serve_apply``) on it — bit-equal to the densified request
  by construction, with the client-side densify + dense-operand
  stacking cost (the flush hot path's host bytes) eliminated. Against
  the *eager* ``transform.apply`` this coincides bitwise when the
  stream extent is its own pow2 class and otherwise sits in the dense
  serve endpoint's documented float-epsilon band (padding the
  reduction length re-blocks an f32 dot), exactly like the dense
  buckets themselves.
- **sketched least-squares** (:func:`sparse_solve_serve`): the sketch
  stage is one of the above; equal sketch bits feed the identical
  ``solve_l2_exact``, so the solve inherits the sketch's contract.

The CWT path is where sparsity pays: O(nnz) scatter work instead of the
dense path's O(N·m) segment-sum (``benchmarks/results_sparse_cpu.json``).
On a TPU the direct rowwise apply (``HashTransform.apply``) replaces the
scatter by the kernel that builds each result tile in VMEM
(``pallas_sparse.hash_rows_apply``) wherever :func:`sparse_kernel` finds
its shapes. Where the result stays sparse (``HashTransform.apply_sparse``),
:func:`cwt_sparse_out_serve_apply` at the end of this file is the sibling:
lanes → canonical lanes, :func:`coalesce_kernel` saying which sort serves.

The CSR lane format (and :func:`scatter_dense`) is also the intake of
the **graph serve endpoints** (docs/qos): ``submit_graph_ase`` /
``submit_graph_ppr`` pack adjacency matrices — the sparse regime this
module optimizes for — as the same padded (data, indices, indptr)
lanes with a pow2 nnz class, densifying in-executable through the
identical integer scatter (:mod:`libskylark_tpu.ml.graph`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from libskylark_tpu.base import randgen
from libskylark_tpu.base.sparse import csr_row_ids


def lookup(values: tuple) -> str:
    """How the program of the value stream ``values`` finds a stored
    nonzero's bucket and value: ``"lane"`` — both computed at the lane
    (``randgen.stream_at``; the CountSketch) — or ``"lane+table"`` — the
    bucket computed, the value gathered from its stream's table (MMT, WZT:
    transcendental maps). The sparse ``sketch.dispatch`` span carries it."""
    return "lane" if values == ("CWT",) else "lane+table"


def sparse_kernel(shape: tuple, s_dim: int, lanes: int, dtype,
                  rowwise: bool) -> str:
    """Which program accumulates a direct sparse apply's terms:
    ``"pallas_rows"`` — the kernel that builds each result tile in VMEM
    (:func:`libskylark_tpu.sketch.pallas_sparse.hash_rows_apply`) — on a
    TPU, rowwise, where its plan fits (float32, ``s_dim`` a multiple of 128
    up to 2048, rows a multiple of 8 — 16 where ``s_dim`` is an odd
    multiple — lanes a multiple of 1024 and at least 16384), else
    ``"xla_scatter"``. Decided from what the apply can observe, by
    ``HashTransform._apply_sparse``; the sparse ``sketch.dispatch`` span
    and the ``sketch.sparse_nnz`` counter carry it."""
    from libskylark_tpu.sketch import pallas_sparse

    fits = rowwise and pallas_sparse.available() and pallas_sparse.rows_plan(
        int(shape[0]), s_dim, lanes, dtype) is not None
    return "pallas_rows" if fits else "xla_scatter"


def cwt_sparse_serve_apply(key_data, data, indices, indptr, *,
                           s_dim: int, rowwise: bool, shape: tuple,
                           values: tuple = ("CWT",),
                           kernel: str = "xla_scatter") -> jnp.ndarray:
    """One request's CountSketch of a CSR operand: O(nnz) scatter-add,
    bit-equal to ``cwt_serve_apply`` on the densified operand (module
    doc). ``shape`` is the padded (rows, cols) class shape the lanes
    describe; the sketched extent (rows columnwise, cols rowwise) is
    stream-exact under zero-padding, the kept extent is sliced by the
    caller. Returns (s_dim, cols) columnwise / (rows, s_dim) rowwise.

    ``values`` names the value stream (``sketch.hash.value_stream``): the
    default is the CountSketch's signs; ``("MMT",)`` and ``("WZT", p)``
    make this the program of those families' direct sparse apply too
    (``HashTransform.apply`` on a ``SparseMatrix`` compiles exactly this
    function, one lane, through ``engine.compiled``); :func:`lookup` says
    which of bucket and value each of them computes at the lane.

    ``kernel`` (:func:`sparse_kernel`) names what adds the terms up: the
    default scatter-add, bit-equal as above, or ``"pallas_rows"``, which
    differs from it in the order the terms of one cell are added (a cell
    with one term holds it to the bit) and is interpreted off the TPU.
    """
    import jax.random as jr

    from libskylark_tpu.sketch.hash import value_stream

    key = jr.wrap_key_data(jnp.asarray(key_data))
    n_rows, n_cols = int(shape[0]), int(shape[1])
    # a lane's row: stored for the scatter and for the columnwise lookup;
    # the rowwise kernel reads it off ``indptr`` tile by tile
    rows = (None if kernel == "pallas_rows"
            else csr_row_ids(indptr, data.shape[0]))
    # the coordinate each nonzero is hashed by; its bucket h(by) is
    # computed at the lane from the counter cipher, not gathered from a
    # table of the stream (an element gather is 7 ns a lane on a v5e)
    by = indices if rowwise else rows
    bucket = randgen.stream_at(
        jax.random.fold_in(key, 0), randgen.UniformInt(0, s_dim - 1), by,
        dtype=jnp.int32)
    if lookup(values) == "lane":
        # v = ±1 computed at the lane too, and −x is exactly (−1)·x
        sign = randgen.stream_at(jax.random.fold_in(key, 1),
                                 randgen.Rademacher(), by)
        term = jnp.where(sign < 0, -data, data)
    else:
        # MMT/WZT: transcendental maps, still one table and one gather
        n = n_cols if rowwise else n_rows
        term = value_stream(values, key, n, data.dtype)[by] * data
    if kernel == "pallas_rows":
        from libskylark_tpu.sketch import pallas_sparse

        return pallas_sparse.hash_rows_apply(
            term, bucket, indptr, n_rows=n_rows, s_dim=s_dim,
            interpret=not pallas_sparse.available())
    if rowwise:
        # out[r, h[c]] += v[c]·val — CSR row-major order IS the dense
        # segment-sum's coordinate order per output cell
        out = jnp.zeros((n_rows, s_dim), data.dtype)
        return out.at[rows, bucket].add(term)
    out = jnp.zeros((s_dim, n_cols), data.dtype)
    return out.at[bucket, indices].add(term)


def scatter_dense(data, indices, indptr, *, shape: tuple) -> jnp.ndarray:
    """Densify CSR lanes to the padded class shape on device — the
    integer scatter reproduces ``SparseMatrix.todense()`` exactly
    (canonical CSR has no duplicate coordinates, so accumulation order
    is irrelevant; padded entries add 0.0 at a clamped coordinate)."""
    rows = csr_row_ids(indptr, data.shape[0])
    return jnp.zeros(tuple(int(e) for e in shape),
                     data.dtype).at[rows, indices].add(data)


def dense_sparse_serve_apply(key_data, scale, data, indices, indptr, *,
                             dist, s_dim: int, rowwise: bool,
                             shape: tuple) -> jnp.ndarray:
    """One request's dense-family (JLT/CT) sketch of a CSR operand:
    in-executable densify + the literal dense serve program — bit-equal
    to the densified request (module doc)."""
    from libskylark_tpu.sketch.dense import serve_apply

    A = scatter_dense(data, indices, indptr, shape=shape)
    return serve_apply(key_data, scale, A, dist=dist, s_dim=s_dim,
                       rowwise=rowwise)


def sparse_solve_serve(key_data, scale, data, indices, indptr, B, *,
                       sketch_type: str, s_dim: int, method: str,
                       shape: tuple) -> jnp.ndarray:
    """Sketch-and-solve with a CSR design matrix: SA from the sparse
    columnwise sketch above, SB from the dense serve sketch of the
    (dense) target block, then the identical ``solve_l2_exact`` the
    dense serve endpoint runs — so equal sketch bits mean equal
    solutions. Zero-padded rows contribute nothing through either
    family; the feature/target extents are exact bucket components
    (a zero feature column would make the compressed problem
    singular)."""
    from libskylark_tpu.algorithms.regression import solve_l2_exact
    from libskylark_tpu.base import errors
    from libskylark_tpu.sketch import dense, hash as sketch_hash

    if sketch_type == "CWT":
        SA = cwt_sparse_serve_apply(key_data, data, indices, indptr,
                                    s_dim=s_dim, rowwise=False,
                                    shape=shape)
        SB = sketch_hash.cwt_serve_apply(key_data, B, s_dim=s_dim,
                                         rowwise=False)
    elif sketch_type == "JLT":
        SA = dense_sparse_serve_apply(
            key_data, scale, data, indices, indptr,
            dist=randgen.Normal(), s_dim=s_dim, rowwise=False,
            shape=shape)
        SB = dense.serve_apply(key_data, scale, B,
                               dist=randgen.Normal(), s_dim=s_dim,
                               rowwise=False)
    else:
        raise errors.InvalidParametersError(
            f"sparse solve serve path supports JLT/CWT sketches, got "
            f"{sketch_type!r}")
    return solve_l2_exact(SA, SB, method=method)


# -- sparse × dense: the product of base.sparse.spmm / spmm_t and the dense
# sketches of a sparse operand, rowwise and (at the end of the file)
# columnwise (ref: base/Gemm.hpp:335-519 through
# sketch/dense_transform_Mixed.hpp:19). Below the hash endpoints, whose
# traced lines keep their numbers. --


def _compiles_mosaic() -> bool:
    """True when the default backend compiles Mosaic kernels (a TPU); asked
    before a Pallas module is imported, so that a CPU process never pays
    for one."""
    return jax.default_backend() == "tpu"


def product_kernel(shape: tuple, k: int, lanes: int, dtype,
                   rowwise: bool = True) -> tuple:
    """Which program multiplies a sparse operand A of ``shape`` (``lanes``
    lane positions placed) by a dense right factor of ``k`` columns —
    ``A·B`` (``rowwise``: a rowwise sketch, ``spmm``) or ``Aᵀ·B`` (not
    ``rowwise``: the transposed side — a columnwise sketch, ``spmm_t``):
    ``("pallas_tiles", plan)`` / ``("pallas_runs", plan)`` — the walk of
    :mod:`libskylark_tpu.sketch.pallas_spmm` over the side's lanes regrouped
    by (block of result rows, tile of B's rows), the transposed side's
    under the runs layout — on a TPU where the side's plan fits (float32,
    k a multiple of 128 up to 2048, a chunk table inside SMEM), else
    ``("xla: <why>", None)``: the span loop of
    ``base.sparse.spans_product`` over the side's lanes. Decided from what
    the caller can observe — the backend and the shapes — by
    ``base.sparse.product_operands`` for ``spmm``, ``spmm_t`` and the dense
    sketches of a ``SparseMatrix``; the ``sketch.dispatch`` span and the
    ``sketch.sparse_nnz`` / ``sparse.spmm_nnz`` counters carry the name."""
    if not _compiles_mosaic():
        return f"xla: backend {jax.default_backend()}", None
    from libskylark_tpu.sketch import pallas_spmm

    plan, why = pallas_spmm.tiles_plan(shape, k, lanes, dtype,
                                       transposed=not rowwise)
    if plan is None:
        return f"xla: {why}", None
    return ("pallas_tiles" if rowwise else "pallas_runs"), plan


def product_lanes(*operands, kernel: str, shape: tuple,
                  plan=None) -> jnp.ndarray:
    """``A·B`` — the body of the ``sparse.spmm`` program, and of
    ``sparse.spmm_t`` with Aᵀ in A's place (``shape`` Aᵀ's, the lanes those
    of A's transposed side). ``operands`` are the device arrays as
    ``base.sparse.product_operands`` placed them for ``kernel`` (the
    regrouped lanes of ``SparseMatrix.tiled_device``, else the CSR lanes)
    followed by B (at least n rows; float32 for the kernel). A kernel that
    does not compile raises: nothing falls back."""
    *lanes, B = operands
    if plan is not None:
        from libskylark_tpu.sketch import pallas_spmm

        return pallas_spmm.tiles_apply(
            *lanes, B, shape=shape, plan=plan,
            interpret=not _compiles_mosaic())
    from libskylark_tpu.base.sparse import spans_product

    data, indices, indptr = lanes
    return spans_product(data, indices, indptr, B[:shape[1]],
                         n_rows=int(shape[0]))


def operator_rows(key_data, scale, *, dist, s_dim: int, n: int,
                  dtype) -> jnp.ndarray:
    """Sᵀ, rows [0, n) — row c is column c of the scaled virtual operator in
    the dense-block stream format (``sketch.dense.virtual_panel``: the same
    S the dense apply contracts with, entry for entry), generated block by
    block under one ``vmap`` instead of one traced copy of the cipher a
    block. ``n`` may run past the transform's N: a position's entry does
    not depend on the width generated."""
    import jax.random as jr

    from libskylark_tpu.sketch.dense import BLOCK_COLS

    key = jr.wrap_key_data(jnp.asarray(key_data))
    blocks = jax.vmap(lambda b: randgen.dense_block(
        key, dist, s_dim, b, BLOCK_COLS, dtype))(
            jnp.arange(-(-n // BLOCK_COLS), dtype=jnp.int32))
    rows = jnp.swapaxes(blocks, 1, 2).reshape(-1, s_dim)[:n]
    return jnp.asarray(scale, dtype) * rows


def dense_sparse_apply(key_data, scale, *lanes, dist, s_dim: int,
                       shape: tuple, kernel: str, plan=None) -> jnp.ndarray:
    """One rowwise dense-family (JLT/CT) sketch of a sparse operand,
    ``X·Sᵀ`` (rows × s_dim): the program ``sketch.dense_sparse``, a pure
    function of the allocation's key words, the scale and the operand's
    lanes. The operator is generated here — for the kernel in the kernel's
    view and in one panel (:func:`operator_rows_panels`: to the stream's
    block past the width the column tiles pad N to, handed over unsliced),
    else :func:`operator_rows` — and the product is :func:`product_lanes`,
    the body ``base.sparse.spmm`` runs.

    Workspace, whatever nnz is: under the kernel the operator (N × s_dim
    values, once: 0.18 GiB at 47236 × 1024 — and, at an s_dim that is no
    multiple of 1024, the relayout of the result, rows × s_dim); else the
    operator twice while it is laid out, the span loop's ``_SPAN_LANES`` ×
    s_dim rows and one int32 a lane."""
    if plan is None:
        Bt = operator_rows(key_data, scale, dist=dist, s_dim=s_dim,
                           n=int(shape[1]), dtype=lanes[0].dtype)
    else:
        from libskylark_tpu.sketch.pallas_spmm import LANES

        Bt = operator_rows_panels(
            key_data, scale, dist=dist, s_dim=s_dim,
            n=plan.col_tiles * plan.col_tile, dtype=lanes[-1].dtype,
            lanes=LANES, panel_blocks=0)
    return product_lanes(*lanes, Bt, kernel=kernel, shape=shape, plan=plan)


# -- the transposed side: S·X of a columnwise dense sketch, Aᵀ·B --


def operator_rows_panels(key_data, scale, *, dist, s_dim: int, n: int,
                         dtype, lanes: int | None = None,
                         panel_blocks: int = 64) -> jnp.ndarray:
    """:func:`operator_rows` for a long sketched axis (n the examples of a
    corpus, not its features), at least ``n`` rows (to the stream's block:
    the rows past ``n`` are the stream's next entries, which no lane
    addresses): the same entries, each block generated already transposed
    and scaled (``randgen.dense_block_rows``: no (s_dim × n) array, no
    relayout and no second copy of one) and ``panel_blocks`` blocks at a
    time under one loop, so that beside the result the program holds one
    panel's cipher words (64 blocks: 16384 rows, 64 MiB a word array at
    s_dim 1024), not the operator's; ``panel_blocks`` = 0 is one panel of
    them all, for a short axis (the features'). ``lanes`` = 128 gives the
    rows in the kernel's view, (rows, s_dim / 128, 128) — on a TPU a (rows,
    s_dim) array is tiled otherwise, and handing one to the kernel is a
    copy of it."""
    import jax.random as jr

    from libskylark_tpu.sketch.dense import BLOCK_COLS

    key = jr.wrap_key_data(jnp.asarray(key_data))
    scale = jnp.asarray(scale, dtype)
    blocks = jax.lax.map(
        lambda b: scale * randgen.dense_block_rows(key, dist, s_dim, b,
                                                   BLOCK_COLS, dtype, lanes),
        jnp.arange(-(-n // BLOCK_COLS), dtype=jnp.int32),
        batch_size=panel_blocks)
    return blocks.reshape((-1,) + blocks.shape[2:])


def dense_sparse_apply_cw(key_data, scale, *lanes, dist, s_dim: int,
                          shape: tuple, kernel: str, plan=None) -> jnp.ndarray:
    """One columnwise dense-family (JLT/CT) sketch of a sparse operand X of
    ``shape`` (m × n), ``S·X = (Xᵀ·Sᵀ)ᵀ`` (s_dim × n): the program
    ``sketch.dense_sparse_cw``, a pure function of the allocation's key
    words, the scale and the lanes of X's transposed side
    (``base.sparse.product_operands(..., side="transposed")``). Sᵀ (m ×
    s_dim: column c of S is the row of example c) is generated here
    (:func:`operator_rows_panels`: the S the dense columnwise apply
    contracts with, entry for entry; for the kernel in the kernel's view)
    and stands where ``spmm_t``'s B stands: the product is
    :func:`product_lanes` over Xᵀ.

    Workspace, whatever nnz is: Sᵀ once (m × s_dim values, 2 GiB at 524288
    × 1024), one generation panel, the product's (n × s_dim) result — the
    sketch's own bytes where s_dim is a multiple of 1024, else relaid —, or
    the span loop's ``_SPAN_LANES`` × s_dim rows and one int32 a lane."""
    from libskylark_tpu.sketch.pallas_spmm import LANES

    m, n = int(shape[0]), int(shape[1])
    if plan is None:
        St = operator_rows_panels(key_data, scale, dist=dist, s_dim=s_dim,
                                  n=m, dtype=lanes[0].dtype)
    else:
        St = operator_rows_panels(
            key_data, scale, dist=dist, s_dim=s_dim,
            n=plan.col_tiles * plan.col_tile, dtype=lanes[-1].dtype,
            lanes=LANES)
    return product_lanes(*lanes, St, kernel=kernel, shape=(n, m),
                         plan=plan).T


# -- sparse → sparse: the hash sketch whose result stays sparse
# (HashTransform.apply_sparse; ref: sketch/hash_transform_local_sparse.hpp:
# 12-152). At the end of the file: the traced lines above keep their
# numbers. --


def coalesce_kernel(shape: tuple, s_dim: int, rowwise: bool,
                    row_cap) -> tuple:
    """Which program coalesces a sparse → sparse hash sketch of an operand
    of ``shape`` whose longest row holds ``row_cap`` lanes (``None``:
    unknown), ``(kernel, form, cap, why)``: ``"xla_window_sort"`` — rowwise,
    the rows at most ``sparse_coalesce._WINDOW_CAP`` lanes and a window's row
    rank and the bucket inside one 32-bit key: each row sorted inside a
    window of 2·cap lanes, a batched minor-axis sort in VMEM — else
    ``"xla_global_sort"``: one two-key sort of every lane by (row, column),
    log²(lanes)/2 stages through HBM (a columnwise sketch regroups every
    lane; a row bound nobody knows). The sibling of :func:`sparse_kernel`
    for the sparse result, decided from what ``HashTransform.apply_sparse``
    can observe; its ``sketch.dispatch`` span and the ``sketch.sparse_nnz``
    counter carry the name, the span the reason too."""
    from libskylark_tpu.sketch import sparse_coalesce

    form, cap, why = sparse_coalesce.sort_form(s_dim, rowwise, row_cap)
    return f"xla_{form}_sort", form, cap, why


def cwt_sparse_out_serve_apply(key_data, data, indices, indptr, *,
                               s_dim: int, rowwise: bool, shape: tuple,
                               values: tuple = ("CWT",), form: str = "global",
                               cap=None) -> tuple:
    """One hash sketch of a CSR operand to a CSR result, lanes → lanes: the
    sibling of :func:`cwt_sparse_serve_apply` whose result stays sparse, a
    pure function of the allocation's key words and the operand's lanes
    (``SparseMatrix.csr_device()``: ``shape`` the operand's, the lane
    padding 0.0 at column 0) with every shape static — the program
    ``sketch.hash_sparse_out``.

    Every stored nonzero (r, c, x) contributes v(c)·x to (r, h(c)) rowwise,
    v(r)·x to (h(r), c) columnwise, exactly once: bucket and sign computed
    at the lane by ``randgen.stream_at`` as in the dense-result program (no
    table, no gather; MMT's and WZT's value still gathered from its table,
    :func:`lookup`), then :func:`sparse_coalesce.coalesce` under ``form`` /
    ``cap`` (:func:`coalesce_kernel`). Returns ``(data, indices, indptr,
    merged)``: canonical CSR lanes of the (rows × s_dim) / (s_dim × cols)
    result in the operand's lane extent — a row's buckets ascending and
    distinct, collisions summed in float32, ``indptr`` exact, lanes past
    the stored count 0.0 at column 0 — and the lanes the collisions merged
    away, a device scalar. Workspace: ``sparse_coalesce._WORKSPACE_WORDS``
    4-byte words a lane."""
    from libskylark_tpu.sketch import sparse_coalesce

    n_rows, n_cols = int(shape[0]), int(shape[1])
    count = indptr[-1]
    # a lane's row is needed by the global sort and by the columnwise
    # lookup; the windowed sort reads the row starts off ``indptr``
    rows = (None if form == "window"
            else csr_row_ids(indptr, data.shape[0]))
    bucket, term = lane_terms(
        key_data, data, indices if rowwise else rows, s_dim=s_dim,
        values=values, n=n_cols if rowwise else n_rows)
    if rowwise:
        return sparse_coalesce.coalesce(
            rows, bucket, term, count, n_major=n_rows, n_minor=s_dim,
            form=form, cap=cap, starts=indptr if form == "window" else None)
    return sparse_coalesce.coalesce(
        bucket, indices, term, count, n_major=s_dim, n_minor=n_cols,
        form=form)


def lane_terms(key_data, data, by, *, s_dim: int, values: tuple = ("CWT",),
               n: int | None = None) -> tuple:
    """``(bucket, term)`` of each lane: h(by) in [0, s_dim) and v(by)·data,
    ``by`` the coordinate a lane is hashed by (its column rowwise, its row
    columnwise). Bucket — and for the CountSketch the sign — computed at
    the lane by ``randgen.stream_at`` (−x is exactly (−1)·x); MMT's and
    WZT's value gathered from its stream's table of ``n`` entries
    (:func:`lookup`)."""
    import jax.random as jr

    from libskylark_tpu.sketch.hash import value_stream

    key = jr.wrap_key_data(jnp.asarray(key_data))
    bucket = randgen.stream_at(
        jax.random.fold_in(key, 0), randgen.UniformInt(0, s_dim - 1), by,
        dtype=jnp.int32)
    if lookup(values) == "lane":
        sign = randgen.stream_at(jax.random.fold_in(key, 1),
                                 randgen.Rademacher(), by)
        return bucket, jnp.where(sign < 0, -data, data)
    return bucket, value_stream(values, key, n, data.dtype)[by] * data
