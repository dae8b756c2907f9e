"""The transposed sparse product: ``JLT|CT.apply(SparseMatrix, COLUMNWISE)``
(S·X) is one ``engine.compiled`` program an apply
(``sketch.dense_sparse_cw``), its operator generated inside it, and its
product the body ``base.sparse.spmm_t`` runs (``sparse.spmm_t``): the walk of
``sketch/pallas_spmm.py`` over the lanes of Xᵀ under the runs layout
(interpreted here, off the TPU), else the span loop over X's column-major
lanes.

Oracles:

- *plain reference*: ``cellbench/references/sparse_dense_sketch_cw.py``
  (imports nothing of the program): S from (seed, counter) by the stream
  definition, and Σ_panels S[:, panel]·X[panel].toarray() at the highest
  matmul precision;
- ``T.apply(X.todense(), COLUMNWISE)``: the same S, entry for entry;
- ``A.todense().T @ B`` for ``spmm_t`` with a supplied right factor;
- the identities a lost lane moves: Σ_i Y[i, j] = (1ᵀS)·X and
  Σ_j Y[i, j] = S·(X·1);
- a single bfloat16 pass of the reference fails the tolerance the program
  holds;
- one ``sketch.dispatch`` span with ``side="transposed"`` an apply, the
  transposed placement made once, and never at a rowwise product;
- ``approximate_svd`` and ``lsqr`` on a ``SparseMatrix`` against the dense
  operand.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import scipy.sparse as sp

from cellbench.references import sparse_dense_sketch_cw as reference
from libskylark_tpu import Context, engine
from libskylark_tpu import sketch as sk
from libskylark_tpu.base import sparse as sparse_mod
from libskylark_tpu.base.sparse import SparseMatrix, spmm, spmm_t
from libskylark_tpu.sketch import pallas_spmm, sparse_serve
from libskylark_tpu.telemetry import metrics, trace

M = 301             # the sketched extent: no multiple of a tile or a block
N = 211             # features: the result's rows, no multiple of a row block
S = 128
SEED, COUNTER = 11, 0
TOL = 1e-4          # of the largest entry: the cell's rel_max limit
ROUTES = ["xla", "pallas_runs"]


def operand(m: int = M, n: int = N, seed: int = 4) -> sp.csr_matrix:
    """The configuration's skew at a small n, features scattered over ids:
    one feature in every example (a row of Xᵀ that is fully dense), a few
    in half to a tenth of them, a Zipf tail, features with ONE lane, two
    features with none (one of them the last id: the last block of result
    rows is short and ends empty), example 3 empty."""
    rng = np.random.default_rng(seed)
    ids = rng.permutation(n - 1)        # id n − 1 stays empty
    X = np.zeros((m, n), np.float32)
    share = [1.0, 0.6, 0.45, 0.3, 0.2, 0.1] + [
        min(0.1, 2.0 / r) for r in range(7, n - 40)]
    for rank, p in enumerate(share):
        rows = np.flatnonzero(rng.random(m) < p)
        X[rows, ids[rank]] = np.abs(rng.standard_normal(rows.size)) + 0.1
    for rank in range(len(share), n - 2):       # one lane each
        X[rng.integers(m), ids[rank]] = 1.0
    X[3] = 0.0
    X = sp.csr_matrix(X)
    X.sort_indices()
    assert X[:, ids[0]].nnz == m - 1 and X[:, n - 1].nnz == 0
    return X


@pytest.fixture()
def fresh():
    engine.reset()
    before = metrics._ENABLED
    trace.clear_finished()
    yield
    metrics._ENABLED = before
    trace.clear_finished()
    engine.reset()


@pytest.fixture(params=ROUTES)
def route(request, monkeypatch):
    """The program under each of its products. Off the TPU the rule picks
    the span loop; the kernel (interpreted) is put in its place with small
    blocks, so that the result spans several blocks of features and the
    streamed factor several tiles of examples, and a chunk ends inside a
    run."""
    if request.param == "pallas_runs":
        monkeypatch.setattr(pallas_spmm, "_BLOCK_ROWS", 32)
        monkeypatch.setattr(pallas_spmm, "_CHUNKS", (64,))

        def rule(shape, k, lanes, dtype, rowwise=True):
            plan, why = pallas_spmm.tiles_plan(shape, k, lanes, dtype,
                                               transposed=not rowwise)
            if plan is None:
                return f"xla: {why}", None
            return ("pallas_tiles" if rowwise else "pallas_runs"), plan

        monkeypatch.setattr(sparse_serve, "product_kernel", rule)
    return request.param


FAMILIES = [(sk.JLT, {}), (sk.CT, {"C": 2.0})]


@pytest.mark.parametrize("family,kwargs", FAMILIES)
class TestAgainstTheDensifiedApply:
    def test_matches_the_densified_apply(self, fresh, route, family, kwargs):
        T = family(M, S, Context(SEED), **kwargs)
        X = operand()
        A = SparseMatrix.from_scipy(X)
        got = np.asarray(T.apply(A, sk.COLUMNWISE))
        want = np.asarray(T.apply(jnp.asarray(X.toarray()), sk.COLUMNWISE))
        assert got.shape == want.shape == (S, N)
        assert got.dtype == np.float32
        assert np.abs(got - want).max() <= TOL * np.abs(want).max()
        assert not got[:, N - 1].any()      # the empty feature stays empty

    def test_operator_is_the_dense_applys(self, fresh, family, kwargs):
        T = family(M, S, Context(SEED), **kwargs)
        for lanes in (None, 128):
            rows = sparse_serve.operator_rows_panels(
                T.allocation.key_data, T.scale, dist=T.dist, s_dim=S, n=M,
                dtype=jnp.float32, lanes=lanes, panel_blocks=1)
            assert rows.shape[0] == 512     # to the stream's block of 256
            flat = np.asarray(rows).reshape(512, S)
            assert np.array_equal(flat[:M], np.asarray(T.s_panel(0, M)).T)


class TestJLTAgainstThePlainReference:
    def test_matches_the_reference(self, fresh, route):
        X = operand()
        key_data = reference.allocation_key_data(SEED, COUNTER)
        got = np.asarray(sk.JLT(M, S, Context(SEED)).apply(
            SparseMatrix.from_scipy(X), sk.COLUMNWISE))
        want = np.asarray(reference.apply_block(X, key_data, S))
        assert np.abs(got - want).max() <= TOL * np.abs(want).max()
        cols = np.array([0, 5, 100, N - 1])
        by_cols = np.asarray(reference.apply_cols(X[:, cols], key_data, S))
        assert np.abs(got[:, cols] - by_cols).max() <= TOL * np.abs(want).max()

    def test_a_bfloat16_pass_is_another_result(self, fresh, route):
        X = operand()
        key_data = reference.allocation_key_data(SEED, COUNTER)
        got = np.asarray(sk.JLT(M, S, Context(SEED)).apply(
            SparseMatrix.from_scipy(X), sk.COLUMNWISE))
        low = np.asarray(reference.apply_block(X, key_data, S, "bf16"))
        assert np.abs(got - low).max() > 10 * TOL * np.abs(got).max()

    def test_another_counter_is_another_operator(self, fresh, route):
        X = operand()
        A = SparseMatrix.from_scipy(X)
        context = Context(SEED)
        first = np.asarray(sk.JLT(M, S, context).apply(A, sk.COLUMNWISE))
        second = np.asarray(sk.JLT(M, S, context).apply(A, sk.COLUMNWISE))
        assert np.abs(first - second).max() > 0.1 * np.abs(first).max()

    def test_every_stored_nonzero_counts_once(self, fresh, route):
        """Σ_i Y[i, j] = (1ᵀS)·X and Σ_j Y[i, j] = S·(X·1): a lane left out
        or taken twice anywhere moves both."""
        X = operand()
        Sref = np.asarray(reference.operator(SEED, COUNTER, S, M), np.float64)
        got = np.asarray(sk.JLT(M, S, Context(SEED)).apply(
            SparseMatrix.from_scipy(X), sk.COLUMNWISE), np.float64)
        X64 = X.astype(np.float64)
        colsum = X64.T @ Sref.sum(axis=0)
        rowsum = Sref @ np.asarray(X64.sum(axis=1)).ravel()
        assert np.abs(got.sum(axis=0) - colsum).max() \
            <= 1e-5 * np.abs(colsum).max()
        assert np.abs(got.sum(axis=1) - rowsum).max() \
            <= 1e-5 * np.abs(rowsum).max()
        # and the identities do see one lane: drop the last stored nonzero
        cut = X.copy()
        cut.data[-1] = 0.0
        cut.eliminate_zeros()
        less = np.asarray(sk.JLT(M, S, Context(SEED)).apply(
            SparseMatrix.from_scipy(cut), sk.COLUMNWISE), np.float64)
        assert np.abs(less.sum(axis=1) - rowsum).max() \
            > 1e-4 * np.abs(rowsum).max()


class TestSpmmT:
    @pytest.mark.parametrize("k", [128, 256])
    def test_matches_the_dense_product(self, fresh, route, k):
        X = operand()
        A = SparseMatrix.from_scipy(X)
        B = np.random.default_rng(1).standard_normal((M, k)).astype(np.float32)
        want = X.toarray().astype(np.float64).T @ B
        got = np.asarray(spmm_t(A, B))
        assert got.shape == (N, k) and got.dtype == np.float32
        assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max()
        assert engine.stats().compiles == 1     # one program

    def test_a_vector_and_a_narrow_factor_take_the_span_loop(self, fresh,
                                                             route):
        """LSQR's Aᵀ·u and condest's: k = 1 is no width of the kernel's."""
        X = operand()
        A = SparseMatrix.from_scipy(X)
        u = np.random.default_rng(2).standard_normal(M).astype(np.float32)
        want = X.toarray().astype(np.float64).T @ u
        got = np.asarray(spmm_t(A, u))
        assert got.shape == (N,)
        assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max()
        B = np.stack([u, 2 * u], axis=1)
        assert np.abs(np.asarray(spmm_t(A, B))[:, 1] - 2 * want).max() \
            <= 2e-5 * np.abs(want).max()

    def test_gemm_routes_through_it(self, fresh, route):
        X = operand()
        A = SparseMatrix.from_scipy(X)
        B = np.random.default_rng(3).standard_normal((M, S)).astype(np.float32)
        want = X.toarray().astype(np.float64).T @ B
        via_a = np.asarray(sparse_mod.gemm(A, B, transpose_a=True))
        via_b = np.asarray(sparse_mod.gemm(B.T, A))      # Bᵀ·A = (Aᵀ·B)ᵀ
        assert np.abs(via_a - want).max() <= 1e-5 * np.abs(want).max()
        assert np.abs(via_b - want.T).max() <= 1e-5 * np.abs(want).max()

    def test_float64_operands_are_the_span_loops(self, fresh):
        with jax.enable_x64():
            X = operand().astype(np.float64)
            A = SparseMatrix.from_scipy(X)
            B = np.random.default_rng(5).standard_normal((M, 128))
            got = np.asarray(spmm_t(A, jnp.asarray(B)))
        assert np.abs(got - X.toarray().T @ B).max() <= 1e-5

    def test_non_canonical_column_buffers_are_summed_first(self, fresh):
        """A CSC with unsorted rows and a duplicate: the column-major lanes
        are the canonical ones."""
        rows = np.array([4, 1, 1, 0, 2], np.int32)
        cols_ptr = np.array([0, 3, 3, 5], np.int64)
        vals = np.array([1.0, 2.0, 3.0, 4.0, 5.0], np.float32)
        A = SparseMatrix(cols_ptr, rows, vals, (5, 3))
        data, indices, indptr = A.csc_parts()
        assert indptr.tolist() == [0, 2, 2, 4]
        assert indices.tolist() == [1, 4, 0, 2] and data.tolist() == [5, 1, 4, 5]
        B = np.eye(5, dtype=np.float32)
        assert np.array_equal(np.asarray(spmm_t(A, B)),
                              A.to_scipy().toarray().T)


class TestThePlacement:
    def plan(self, X, col_tile=32, chunk=16, group=4, k_tiles=1, row_block=16):
        n, m = X.shape[1], X.shape[0]
        segs = -(-n // row_block) * -(-m // col_tile)
        return pallas_spmm.TilesPlan(
            row_block, col_tile, chunk, k_tiles, -(-n // row_block),
            -(-m // col_tile), 4 * -(-X.nnz // chunk) + 8 * segs, group, True)

    def walk(self, placed, plan, n):
        """What the kernel does with the tables, in numpy: B = the identity
        of the streamed axis, so the result is Xᵀ itself."""
        segment, count, packed, vals = map(np.asarray, placed)
        out = np.zeros((plan.row_blocks * plan.row_block,
                        plan.col_tiles * plan.col_tile))
        for t in range(plan.n_chunks):
            used, grouped = count[t] & 0xFFFF, count[t] >> 16
            block, tile = divmod(segment[t], plan.col_tiles)
            assert grouped % plan.group == 0 and (used - grouped) % plan.group == 0
            words, values = packed[t, 0], vals[t, 0]
            row = (words >> 16) // plan.stride + block * plan.row_block
            col = (words & 0xFFFF) // plan.stride + tile * plan.col_tile
            for g in range(0, grouped, plan.group):     # a group: rows differ
                assert np.unique(row[g:g + plan.group]).size == plan.group
            for g in range(grouped, used, plan.group):  # a run: one row
                assert np.unique(row[g:g + plan.group]).size == 1
            np.add.at(out, (row[:used], col[:used]), values[:used])
            assert not values[used:].any()
        return out

    @pytest.mark.parametrize("group,chunk,k_tiles", [(4, 16, 1), (8, 64, 1),
                                                     (8, 24, 8)])
    def test_every_lane_once_groups_of_rows_runs_of_one(self, group, chunk,
                                                        k_tiles):
        X = operand()
        A = SparseMatrix.from_scipy(X)
        plan = self.plan(X, group=group, chunk=chunk, k_tiles=k_tiles)
        placed = A.tiled_device(plan.layout, side="transposed")
        out = self.walk(placed, plan, N)
        assert np.array_equal(out[:N, :M], X.toarray().T.astype(np.float64))
        counts = A._tiled(plan.layout, None, "transposed")[1]
        assert counts["grouped_lanes"] + counts["run_lanes"] == X.nnz
        assert counts["run_slots"] >= counts["run_lanes"] > 0
        assert counts["grouped_lanes"] > 0
        # a run's padding is under a group: whole rows hold ≥ 16 lanes
        assert counts["run_slots"] - counts["run_lanes"] \
            <= (group - 1) * X.nnz / 16 + 16 * (group - 1) * group * (
                plan.row_blocks * plan.col_tiles)
        assert A.grouped_lanes(plan.layout, side="transposed") \
            == counts["grouped_lanes"]

    def test_a_dense_row_of_the_transpose_is_one_run_a_tile(self):
        X = operand()
        A = SparseMatrix.from_scipy(X)
        plan = self.plan(X)
        segment, count, packed, _ = map(
            np.asarray, A.tiled_device(plan.layout, side="transposed"))
        hot = int(np.argmax(np.bincount(X.indices, minlength=N)))
        block, local = divmod(hot, plan.row_block)
        # (the last tile holds 13 examples: under 16 lanes the row is short)
        whole = segment % plan.col_tiles < plan.col_tiles - 1
        seen = 0
        for t in np.flatnonzero((segment // plan.col_tiles == block) & whole):
            used, grouped = count[t] & 0xFFFF, count[t] >> 16
            rows_ = (packed[t, 0, :used] >> 16) // plan.stride
            assert local not in rows_[:grouped]     # never a grouped lane
            seen += np.count_nonzero(rows_[grouped:] == local)
        assert seen >= M - 13 - 1           # its lanes and their padding

    def test_the_bound_on_the_chunks_holds_the_worst_padding(self):
        """``tiles_plan``'s static bound: 23/16 of the lanes and 896 slots a
        segment, whatever the operand."""
        assert pallas_spmm._RUN_ROW <= sparse_mod._RANK_CLASSES
        plan, why = pallas_spmm.tiles_plan((524288, 47236), 1024, 39845888,
                                           jnp.float32, transposed=True)
        assert why is None and plan.runs and plan.row_block == 4096
        assert (plan.row_blocks, plan.col_tiles) == (12, 256)
        assert plan.n_chunks * plan.chunk >= 39845888 * 23 // 16 + 896 * 3072
        assert plan.n_chunks <= pallas_spmm._MAX_CHUNKS
        assert plan.row_block * plan.stride <= 1 << 15      # a word's 15 bits
        # every lane a run of its own in every segment fits the plan
        X = sp.csr_matrix(np.eye(64, 48, dtype=np.float32))
        small = self.plan(X, group=8, chunk=16)
        SparseMatrix.from_scipy(X).tiled_device(small.layout,
                                                side="transposed")

    def test_placed_once_and_never_at_a_rowwise_product(self, fresh, route):
        metrics._ENABLED = True
        X = operand()
        A = SparseMatrix.from_scipy(X)
        T = sk.JLT(M, S, Context(SEED))
        spmm(A, np.ones((N, S), np.float32))        # a rowwise product
        Tr = sk.JLT(N, S, Context(SEED))
        Tr.apply(A, sk.ROWWISE)
        placed = [s for s in trace.finished_spans() if s.name == "sparse.place"]
        assert not [s for s in placed if s.attrs["side"] == "transposed"]
        assert not any(key == "csc" or key[:2] == ("tiled", "transposed")
                       for held in A._device.values() for key in held)
        first = np.asarray(T.apply(A, sk.COLUMNWISE))
        spmm_t(A, np.ones((M, S), np.float32))
        second = np.asarray(T.apply(A, sk.COLUMNWISE))
        assert np.array_equal(first, second)
        placed = [s for s in trace.finished_spans() if s.name == "sparse.place"
                  and s.attrs["side"] == "transposed"]
        if route == "pallas_runs":
            (span,) = placed
            assert span.attrs["nnz"] == X.nnz and span.attrs["seconds"] > 0
            assert span.attrs["run_lanes"] + span.attrs["grouped_lanes"] == X.nnz
            assert span.attrs["bytes"] > 8 * X.nnz
        else:
            assert not placed       # the span loop walks the lanes as they are

    def test_the_place_span_opens_with_telemetry_off(self, fresh, route):
        """Set-up work, once a layout: the benchmark's ``setup_place_s``
        reads it from a run nobody traces."""
        assert not metrics._ENABLED
        X = operand()
        sk.JLT(M, S, Context(SEED)).apply(SparseMatrix.from_scipy(X),
                                          sk.COLUMNWISE)
        placed = [s for s in trace.finished_spans() if s.name == "sparse.place"]
        assert len(placed) == (1 if route == "pallas_runs" else 0)
        assert not [s for s in trace.finished_spans()
                    if s.name == "sketch.dispatch"]

    def test_no_transfer_after_the_first_apply(self, fresh, route):
        X = operand()
        A = SparseMatrix.from_scipy(X)
        T = sk.JLT(M, S, Context(SEED))
        first = np.asarray(T.apply(A, sk.COLUMNWISE))
        with jax.transfer_guard_host_to_device("disallow"):
            second = T.apply(A, sk.COLUMNWISE)
        assert np.array_equal(first, np.asarray(second))


class TestDeclines:
    def test_every_decline_carries_its_reason(self):
        shape = (524288, 47236)
        for k, dtype, lanes, why in [
                (1000, jnp.float32, 1 << 20, "multiple of 128"),
                (4096, jnp.float32, 1 << 20, "multiple of 128"),
                (1, jnp.float32, 1 << 20, "multiple of 128"),
                (1024, jnp.bfloat16, 1 << 20, "dtype bfloat16"),
                (1024, jnp.float64, 1 << 20, "dtype float64"),
                (1024, jnp.float32, 1 << 28, "chunk table")]:
            plan, said = pallas_spmm.tiles_plan(shape, k, lanes, dtype,
                                                transposed=True)
            assert plan is None and why in said
        assert sparse_serve.product_kernel(
            shape, 1024, 1 << 20, jnp.float32, rowwise=False) == (
                f"xla: backend {jax.default_backend()}", None)

    def test_the_reason_is_on_the_span_and_the_counters(self, fresh, route):
        metrics._ENABLED = True
        X = operand()
        A = SparseMatrix.from_scipy(X)
        T = sk.JLT(M, S, Context(SEED))
        T.apply(A, sk.COLUMNWISE)
        trace.clear_finished()
        before = _counter("sketch.sparse_nnz")
        T.apply(A, sk.COLUMNWISE).block_until_ready()
        spans = trace.finished_spans()
        (dispatch,) = [s for s in spans if s.name == "sketch.dispatch"]
        attrs = dispatch.attrs
        assert attrs["path"] == "sparse" and attrs["side"] == "transposed"
        assert attrs["family"] == "JLT" and attrs["s"] == S
        assert attrs["nnz"] == X.nnz <= attrs["nnz_class"]
        assert attrs["lane_slots"] >= attrs["nnz"]
        if route == "pallas_runs":
            assert attrs["kernel"] == "pallas_runs"
            # s = 128: a row is part of a register, the result is relaid
            assert attrs["result_layout"] == "kernel_view"
            assert attrs["operator_view"] == "kernel"
            assert attrs["grouped_lanes"] + attrs["run_lanes"] == X.nnz
            assert attrs["run_slots"] >= attrs["run_lanes"]
            assert attrs["segments"] == -(-N // 64) * -(-M // 32)
        else:
            assert attrs["kernel"] == f"xla: backend {jax.default_backend()}"
            assert attrs["result_layout"] == attrs["operator_view"] == "rows"
            assert attrs["segments"] == 1 and "run_lanes" not in attrs
        assert not [s for s in spans if s.name == "sparse.place"]
        assert [p["handovers"] for p in trace.apply_periods("sketch.apply")] \
            in ([], [1])
        after = _counter("sketch.sparse_nnz")
        key = (("family", "JLT"), ("kernel", attrs["kernel"]))
        assert after.get(key, 0) - before.get(key, 0) == X.nnz
        # called directly, spmm_t counts under its own name, with the side
        before = _counter("sparse.spmm_nnz")
        spmm_t(A, np.ones((M, S), np.float32))
        after = _counter("sparse.spmm_nnz")
        (key,) = [k for k in after if after[k] != before.get(k, 0)]
        assert dict(key) == {"kernel": attrs["kernel"], "side": "transposed"}
        assert after[key] - before.get(key, 0) == X.nnz


class TestTheHandedOverWidths:
    """s a multiple of 1024: the runs walk accumulates in a scratch block
    and hands each finished block of 64 features over as rows — 211
    features, so the last block is clipped —; the result is the Mosaic
    call's own output (``tests/test_sparse_dense_program.py``
    ``TestTheHandOver`` holds it to the kernel's view bit by bit)."""

    @pytest.mark.parametrize("s_dim", [1024, 2048])
    def test_the_sketch_and_its_span(self, fresh, route, s_dim):
        metrics._ENABLED = True
        X = operand()
        T = sk.JLT(M, s_dim, Context(SEED))
        got = np.asarray(T.apply(SparseMatrix.from_scipy(X), sk.COLUMNWISE))
        want = np.asarray(T.apply(jnp.asarray(X.toarray()), sk.COLUMNWISE))
        assert got.shape == want.shape == (s_dim, N)
        assert np.abs(got - want).max() <= TOL * np.abs(want).max()
        assert not got[:, N - 1].any()          # the feature nobody holds
        (dispatch,) = [s for s in trace.finished_spans()
                       if s.name == "sketch.dispatch"
                       and s.attrs.get("path") == "sparse"]
        assert dispatch.attrs["result_layout"] == "rows"
        assert dispatch.attrs["operator_view"] == (
            "kernel" if route == "pallas_runs" else "rows")

    def test_spmm_t_with_a_supplied_factor(self, fresh, route):
        X = operand()
        B = np.random.default_rng(3).standard_normal(
            (M, 1024)).astype(np.float32)
        got = np.asarray(spmm_t(SparseMatrix.from_scipy(X), B))
        want = X.toarray().T.astype(np.float64) @ B.astype(np.float64)
        assert got.shape == (N, 1024)
        assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max()


def _counter(name: str) -> dict:
    entry = metrics.snapshot()["metrics"].get(name)
    if entry is None:
        return {}
    return {tuple(sorted(v["labels"].items())): int(v["value"])
            for v in entry["values"]}


def test_pinned_operator_is_spmm_ts_right_factor(fresh, route):
    X = operand()
    A = SparseMatrix.from_scipy(X)
    T = sk.JLT(M, S, Context(SEED))
    virtual = np.asarray(T.apply(A, sk.COLUMNWISE))
    T.materialize()
    pinned = np.asarray(T.apply(A, sk.COLUMNWISE))
    assert np.abs(pinned - virtual).max() <= 1e-6 * np.abs(virtual).max()


def test_operator_past_auto_block_bytes_keeps_the_panel_loop(fresh):
    from libskylark_tpu.sketch import params as sketch_params

    X = operand()
    A = SparseMatrix.from_scipy(X)
    T = sk.JLT(M, S, Context(SEED))
    want = np.asarray(T.apply(A, sk.COLUMNWISE))
    old = sketch_params.get_auto_block_bytes()
    sketch_params.set_auto_block_bytes(M * S * 4 - 1)
    try:
        got = np.asarray(T.apply(A, sk.COLUMNWISE))
    finally:
        sketch_params.set_auto_block_bytes(old)
    assert engine.stats().compiles == 1     # the loop is eager: no program
    assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max()


class TestTheSolversOnASparseOperand:
    """The callers that stopped at the transposed product: the dense
    operand is the oracle."""

    def low_rank(self, m=240, n=96, rank=6, seed=8):
        rng = np.random.default_rng(seed)
        U = rng.standard_normal((m, rank)) * (rng.random((m, rank)) < 0.3)
        V = rng.standard_normal((rank, n)) * (rng.random((rank, n)) < 0.5)
        D = (U * np.linspace(10, 1, rank)) @ V
        D += 1e-3 * rng.standard_normal((m, n)) * (rng.random((m, n)) < 0.05)
        return sp.csr_matrix(D.astype(np.float32))

    def test_approximate_svd_agrees_with_the_dense_operand(self, fresh, route):
        from libskylark_tpu.nla.svd import ApproximateSVDParams, approximate_svd

        X = self.low_rank()
        params = ApproximateSVDParams(num_iterations=2)
        _, s_sparse, _ = approximate_svd(SparseMatrix.from_scipy(X), 6,
                                         Context(3), params)
        _, s_dense, _ = approximate_svd(jnp.asarray(X.toarray()), 6,
                                        Context(3), params)
        exact = np.linalg.svd(X.toarray(), compute_uv=False)[:6]
        assert np.allclose(np.asarray(s_sparse), np.asarray(s_dense),
                           rtol=1e-3)
        assert np.allclose(np.asarray(s_sparse), exact, rtol=1e-2)

    def test_lsqr_agrees_with_the_dense_operand(self, fresh, route):
        from libskylark_tpu.algorithms.krylov import KrylovParams, lsqr

        rng = np.random.default_rng(9)
        X = sp.random(160, 24, density=0.3, format="csr", random_state=9,
                      dtype=np.float32)
        X = (X + sp.vstack([sp.identity(24, dtype=np.float32),
                            sp.csr_matrix((136, 24), dtype=np.float32)])).tocsr()
        b = rng.standard_normal((160, 1)).astype(np.float32)
        params = KrylovParams(iter_lim=200, tolerance=1e-7)
        x_sparse, _ = lsqr(SparseMatrix.from_scipy(X), jnp.asarray(b), params)
        x_dense, _ = lsqr(jnp.asarray(X.toarray()), jnp.asarray(b), params)
        best = np.linalg.lstsq(X.toarray(), b, rcond=None)[0]
        assert np.allclose(np.asarray(x_sparse), np.asarray(x_dense),
                           atol=1e-3 * np.abs(best).max())
        assert np.allclose(np.asarray(x_sparse), best,
                           atol=1e-2 * np.abs(best).max())
