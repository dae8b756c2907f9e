"""The sparse → sparse hash sketch as one compiled program
(``HashTransform.apply_sparse`` → ``sketch.hash_sparse_out``) and the
device-born ``SparseMatrix`` it returns, on the CPU at small sizes.

Oracles: the plain reference of the cell (``cellbench/references/
sparse_hash_sparse.py``: h and v from the stream definition in numpy, the
relabelled triplets summed per cell in float64, canonical CSR — it imports
nothing of the program), and the same-seed dense-result apply
(``T.apply(A)``, the reference repo's redundant-computation oracle).
"""

import numpy as np
import pytest
import scipy.sparse as sp

import jax
import jax.numpy as jnp

from cellbench.references import sparse_hash_sparse as reference
from libskylark_tpu import Context, engine
from libskylark_tpu import sketch as sk
from libskylark_tpu.base.sparse import SparseMatrix, spmm, spmm_t
from libskylark_tpu.engine.bucket import lane_class, result_lanes
from libskylark_tpu.sketch import sparse_coalesce, sparse_serve
from libskylark_tpu.sketch.hash import HashTransform
from libskylark_tpu.telemetry import metrics, trace

SEED = 11
N = 1181                # no multiple of 8, 128 or 4096: the stream's last chunk ragged
ROWS = 61


def operand(rows: int = ROWS, n: int = N, seed: int = 4, longest: int = 300,
            typical: int = 40) -> sp.csr_matrix:
    """Ragged rows of distinct features: row 0 and row 3 empty, row 1 of one
    feature, row 5 of ``longest``, the last row empty, the others 1 ..
    ``typical``; values N(0, 1)."""
    rng = np.random.default_rng(seed)
    lengths = rng.integers(1, typical + 1, rows)
    lengths[[0, 3, rows - 1]] = 0
    lengths[1], lengths[5] = 1, min(longest, n)
    cols = np.concatenate([np.sort(rng.choice(n, size=k, replace=False))
                           for k in lengths])
    vals = rng.standard_normal(cols.shape[0]).astype(np.float32)
    return sp.csr_matrix((vals, cols, np.concatenate([[0], np.cumsum(lengths)])),
                         shape=(rows, n))


@pytest.fixture()
def fresh():
    engine.reset()
    before = metrics._ENABLED
    trace.clear_finished()
    yield
    metrics._ENABLED = before
    trace.clear_finished()
    engine.reset()


def lanes_of(Z: SparseMatrix) -> tuple:
    data, indices, indptr = (np.asarray(x) for x in Z.csr_device())
    return data, indices, indptr


def assert_canonical_and_equal(Z: SparseMatrix, ref: sp.csr_matrix,
                               terms: sp.csr_matrix | None = None) -> None:
    """Structure exactly the reference's, lanes past the count 0.0 at column
    0, data to 1e-6 of the largest entry (a float32 rounding a term where a
    cell sums more than eight) — and to the bit where a cell has ≤ 2 terms
    (``terms``: the count of terms a stored cell sums, same structure)."""
    data, indices, indptr = lanes_of(Z)
    assert Z.shape == ref.shape
    np.testing.assert_array_equal(indptr, ref.indptr)
    nnz = int(indptr[-1])
    np.testing.assert_array_equal(indices[:nnz], ref.indices)
    assert not data[nnz:].any() and not indices[nnz:].any()
    most = 8 if terms is None or not terms.nnz else max(8, terms.data.max())
    np.testing.assert_allclose(
        data[:nnz], ref.data, rtol=0,
        atol=1.2e-7 * most * np.abs(ref.data).max() if nnz else 0)
    if terms is not None:
        few = terms.data <= 2
        np.testing.assert_array_equal(data[:nnz][few],
                                      ref.data[few].astype(np.float32))


def close(got, want) -> None:
    """To 1e-6 of the largest entry: float32 sums against float64 ones."""
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=1e-6 * np.abs(want).max())


def term_counts(X: sp.csr_matrix, h, s: int, rowwise: bool) -> sp.csr_matrix:
    ones = sp.csr_matrix((np.ones_like(X.data), X.indices, X.indptr), X.shape)
    return reference.apply_csr(ones.indptr, ones.indices, ones.data, h,
                               np.ones_like(h, np.float32), s, X.shape,
                               rowwise=rowwise)


class TestTheReference:
    @pytest.mark.parametrize("n,s", [(N, 64), (5000, 1000), (9000, 1 << 18),
                                     (4096, 70001)])
    def test_streams_are_the_transforms(self, n, s):
        T = sk.CWT(n, s, Context(SEED))
        h, v = reference.streams(SEED, 0, n, s)
        np.testing.assert_array_equal(h, np.asarray(T.bucket_indices()))
        np.testing.assert_array_equal(v, np.asarray(T.values()))

    def test_second_allocation_is_counter_one(self):
        ctx = Context(SEED)
        sk.CWT(N, 64, ctx)
        T = sk.CWT(N, 64, ctx)
        h, _ = reference.streams(SEED, 1, N, 64)
        np.testing.assert_array_equal(h, np.asarray(T.bucket_indices()))

    def test_apply_csr_leaves_its_operand_alone(self):
        X = operand()
        indptr = X.indptr.copy()
        h, v = reference.streams(SEED, 0, N, 8)
        Z = reference.apply_csr(X.indptr, X.indices, X.data, h, v, 8, X.shape)
        np.testing.assert_array_equal(X.indptr, indptr)
        assert Z.has_canonical_format and Z.dtype == np.float64
        dense = np.zeros((ROWS, 8))
        np.add.at(dense, (np.repeat(np.arange(ROWS), np.diff(X.indptr)),
                          h[X.indices]), v[X.indices] * X.data.astype(np.float64))
        np.testing.assert_allclose(Z.toarray(), dense, atol=1e-12)


class TestStructureAgainstTheReference:
    @pytest.mark.parametrize("s", [8, 64, 4096, 1 << 18])
    @pytest.mark.parametrize("rowwise", [True, False])
    def test_cwt_is_the_reference_lane_for_lane(self, fresh, s, rowwise):
        X = operand() if rowwise else operand().T.tocsr()
        X.sort_indices()
        T = sk.CWT(N, s, Context(SEED))
        Z = T.apply_sparse(SparseMatrix.from_scipy(X),
                           sk.ROWWISE if rowwise else sk.COLUMNWISE)
        h, v = reference.streams(SEED, 0, N, s)
        ref = reference.apply_csr(X.indptr, X.indices, X.data, h, v, s,
                                  X.shape, rowwise=rowwise)
        assert_canonical_and_equal(Z, ref, term_counts(X, h, s, rowwise))
        assert Z.nnz == ref.nnz

    def test_a_corpus_built_to_collide_merges_most_rows(self, fresh):
        X = operand(rows=200, typical=60)
        T = sk.CWT(N, 8, Context(SEED))
        Z = T.apply_sparse(SparseMatrix.from_scipy(X), sk.ROWWISE)
        h, v = reference.streams(SEED, 0, N, 8)
        ref = reference.apply_csr(X.indptr, X.indices, X.data, h, v, 8, X.shape)
        assert_canonical_and_equal(Z, ref)
        merged_rows = np.diff(ref.indptr) < np.diff(X.indptr)
        assert merged_rows.mean() > 0.5 and Z.nnz < X.nnz / 2

    @pytest.mark.parametrize("longest,form,cap", [
        (1, "window", 128), (128, "window", 128), (129, "window", 256),
        (1024, "window", 1024), (1025, "window", 2048),
        (4096, "window", 4096), (4097, "global", None)])
    def test_rows_of_length_0_1_and_the_clip(self, fresh, longest, form, cap):
        n = 6000
        X = operand(n=n, longest=longest, typical=min(longest, 40))
        A = SparseMatrix.from_scipy(X)
        assert A.row_cap == max(longest, 1 if longest == 1 else
                                int(np.diff(X.indptr).max()))
        kernel, got_form, got_cap, _ = sparse_serve.coalesce_kernel(
            A.shape, 512, True, A.row_cap)
        assert (kernel, got_form, got_cap) == (f"xla_{form}_sort", form, cap)
        T = sk.CWT(n, 512, Context(SEED))
        Z = T.apply_sparse(A, sk.ROWWISE)
        h, v = reference.streams(SEED, 0, n, 512)
        ref = reference.apply_csr(X.indptr, X.indices, X.data, h, v, 512,
                                  X.shape)
        assert_canonical_and_equal(Z, ref)
        assert Z.row_cap == A.row_cap       # a result row holds no more lanes

    def test_an_empty_operand(self, fresh):
        X = sp.csr_matrix((5, N), dtype=np.float32)
        Z = sk.CWT(N, 16, Context(SEED)).apply_sparse(
            SparseMatrix.from_scipy(X), sk.ROWWISE)
        data, indices, indptr = lanes_of(Z)
        assert Z.nnz == 0 and not indptr.any() and not data.any()
        assert np.asarray(Z.todense()).shape == (5, 16)


@pytest.mark.parametrize("cls,kwargs", [(sk.CWT, {}), (sk.MMT, {}),
                                        (sk.WZT, {"p": 1.5})])
@pytest.mark.parametrize("rowwise", [True, False])
@pytest.mark.parametrize("s", [8, 300])
def test_todense_is_the_dense_result_apply(fresh, cls, kwargs, rowwise, s):
    """Every family through the same program, both dimensions: the streams
    are ``T.apply``'s own, and a cell's terms are summed in float32."""
    X = operand() if rowwise else operand().T.tocsr()
    A = SparseMatrix.from_scipy(X)
    T = cls(N, s, Context(SEED), **kwargs)
    dim = sk.ROWWISE if rowwise else sk.COLUMNWISE
    Z = T.apply_sparse(A, dim)
    want = np.asarray(T.apply(A, dim))
    got = np.asarray(Z.todense())
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=2e-6,
                               atol=2e-6 * np.abs(want).max())
    # canonical whatever the family: ascending and distinct inside a row
    data, indices, indptr = lanes_of(Z)
    for r in range(Z.height):
        row = indices[indptr[r]:indptr[r + 1]]
        assert np.all(np.diff(row) > 0)
    assert sparse_serve.lookup(T._value_kind()) == (
        "lane" if cls is sk.CWT else "lane+table")


class TestTheTwoSortsAgree:
    @pytest.mark.parametrize("seed", [0, 1, 2])
    @pytest.mark.parametrize("pad", [0, 37])
    def test_window_equals_global(self, seed, pad):
        rng = np.random.default_rng(seed)
        lengths = rng.integers(0, 130, 90)
        indptr = np.concatenate([[0], np.cumsum(lengths)]).astype(np.int32)
        nnz = int(indptr[-1])
        minor = np.pad(rng.integers(0, 50, nnz).astype(np.int32), (0, pad))
        term = np.pad(rng.standard_normal(nnz).astype(np.float32), (0, pad))
        major = np.pad(np.repeat(np.arange(90, dtype=np.int32), lengths),
                       (0, pad))
        w = sparse_coalesce.coalesce(
            None, jnp.asarray(minor), jnp.asarray(term), jnp.int32(nnz),
            n_major=90, n_minor=50, form="window", cap=256,
            starts=jnp.asarray(indptr))
        g = sparse_coalesce.coalesce(
            jnp.asarray(major), jnp.asarray(minor), jnp.asarray(term),
            jnp.int32(nnz), n_major=90, n_minor=50, form="global")
        # one structure; a cell of many terms is summed in another order
        for a, b in zip(w[1:3], g[1:3]):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        close(np.asarray(w[0]), np.asarray(g[0]))
        assert int(w[3]) == int(g[3]) == nnz - int(w[2][-1]) > 0

    @pytest.mark.parametrize("n_minor,grouped,row_cap,form", [
        (1 << 18, True, 1024, "window"), (1 << 18, True, None, "global"),
        (1 << 18, False, 1024, "global"), (1 << 18, True, 5000, "global"),
        (1 << 21, True, 1024, "global"), (1 << 20, True, 1024, "window"),
        (8, True, 0, "window")])
    def test_the_rule(self, n_minor, grouped, row_cap, form):
        got, cap, why = sparse_coalesce.sort_form(n_minor, grouped, row_cap)
        assert got == form and isinstance(why, str) and why
        assert (cap is None) == (form == "global")
        if cap is not None:
            assert cap >= max(row_cap, 128) and cap & (cap - 1) == 0

    def test_the_result_keeps_its_operands_lane_class(self):
        assert result_lanes(lane_class(60_800_000)) == lane_class(60_800_000)


class TestOneExecutableALaneClass:
    def test_two_blocks_of_one_class_compile_once(self, fresh):
        T = sk.CWT(N, 64, Context(SEED))
        X = operand()
        blocks = [X]
        for cut in (1, 2):      # the same block short of a few stored lanes
            Y = X.tolil()
            Y[7, X[7].indices[:cut]] = 0
            blocks.append(Y.tocsr().astype(np.float32))
        classes = {lane_class(X.nnz) for X in blocks}
        caps = {sparse_coalesce.window_cap(int(np.diff(X.indptr).max()))
                for X in blocks}
        assert len(classes) == 1 and len(caps) == 1 \
            and len({X.nnz for X in blocks}) == 3
        T.apply_sparse(SparseMatrix.from_scipy(blocks[0]), sk.ROWWISE)
        compiles = engine.stats().compiles
        for X in blocks[1:]:
            Z = T.apply_sparse(SparseMatrix.from_scipy(X), sk.ROWWISE)
            assert Z.lanes == lane_class(X.nnz)
        assert engine.stats().compiles == compiles

    def test_a_result_fed_back_shares_its_class(self, fresh):
        """The result is born in its operand's lane extent, so a second
        sketch of the same width compiles nothing new."""
        T = sk.CWT(N, N, Context(SEED))
        A = SparseMatrix.from_scipy(operand())
        Z1 = T.apply_sparse(A, sk.ROWWISE)
        compiles = engine.stats().compiles
        Z2 = T.apply_sparse(Z1, sk.ROWWISE)
        assert engine.stats().compiles == compiles and Z2.lanes == A.lanes


class TestNothingCrossesToTheHost:
    def test_inside_an_apply(self, fresh, monkeypatch):
        """The CPU backend enforces no transfer guard, so the calls are
        watched: the operand's host side, the transform's tables and the
        result's host side are never asked for."""
        A = SparseMatrix.from_scipy(operand())
        T = sk.CWT(N, 64, Context(SEED))
        A.csr_device()                      # placed, as a resident block is
        asked = []
        monkeypatch.setattr(SparseMatrix, "to_scipy",
                            lambda self: asked.append("to_scipy"))
        monkeypatch.setattr(SparseMatrix, "_host",
                            lambda self: asked.append("_host"))
        monkeypatch.setattr(HashTransform, "bucket_indices",
                            lambda self: asked.append("bucket_indices"))
        monkeypatch.setattr(HashTransform, "values",
                            lambda self, dtype=None: asked.append("values"))
        real_asarray = np.asarray
        monkeypatch.setattr(np, "asarray", lambda x, *a, **k: (
            asked.append("np.asarray of a device array")
            if isinstance(x, jax.Array) else None) or real_asarray(x, *a, **k))
        Z = T.apply_sparse(A, sk.ROWWISE)
        jax.block_until_ready(Z.csr_device())
        assert asked == []
        assert not Z.host_materialized and not Z.nnz_known
        assert "on device" in repr(Z)

    def test_the_operand_is_not_modified_nor_placed_anew(self, fresh):
        X = operand()
        A = SparseMatrix.from_scipy(X)
        lanes = A.csr_device()
        before = [np.array(x) for x in lanes]
        sk.CWT(N, 64, Context(SEED)).apply_sparse(A, sk.ROWWISE)
        sk.MMT(N, 8, Context(SEED)).apply_sparse(A, sk.ROWWISE)
        assert all(a is b for a, b in zip(A.csr_device(), lanes))
        for a, b in zip(A.csr_device(), before):
            np.testing.assert_array_equal(np.asarray(a), b)
        assert set(A._device[jnp.dtype(jnp.float32)]) == {"csr"}

    def test_a_result_feeds_a_second_apply_unmaterialized(self, fresh):
        X = operand()
        T1 = sk.CWT(N, 500, Context(SEED))
        T2 = sk.CWT(500, 16, Context(SEED + 1))
        Z1 = T1.apply_sparse(SparseMatrix.from_scipy(X), sk.ROWWISE)
        Z2 = T2.apply_sparse(Z1, sk.ROWWISE)
        assert not Z1.host_materialized and not Z1.nnz_known
        assert not Z2.host_materialized
        want = np.asarray(T2.apply(T1.apply(jnp.asarray(X.toarray()),
                                            sk.ROWWISE), sk.ROWWISE))
        np.testing.assert_allclose(np.asarray(Z2.todense()), want,
                                   rtol=1e-5, atol=1e-5)
        # and columnwise, which regroups every lane (no row bound left)
        Z3 = sk.CWT(ROWS, 7, Context(3)).apply_sparse(Z1, sk.COLUMNWISE)
        assert Z3.shape == (7, 500) and Z3.row_cap is None
        h, v = reference.streams(3, 0, ROWS, 7)
        Z1h = Z1.to_scipy().tocsr()
        ref = reference.apply_csr(Z1h.indptr, Z1h.indices, Z1h.data, h, v, 7,
                                  Z1h.shape, rowwise=False)
        assert_canonical_and_equal(Z3, ref)


class TestTheDeviceBornMatrix:
    @pytest.fixture()
    def born(self, fresh):
        X = operand()
        T = sk.CWT(N, 96, Context(SEED))
        Z = T.apply_sparse(SparseMatrix.from_scipy(X), sk.ROWWISE)
        h, v = reference.streams(SEED, 0, N, 96)
        return Z, reference.apply_csr(X.indptr, X.indices, X.data, h, v, 96,
                                      X.shape)

    def test_nnz_is_read_once_and_tells_who_asked(self, born):
        Z, ref = born
        told = []
        Z.when_counted(told.append)
        assert told == [] and not Z.nnz_known
        assert Z.nnz == ref.nnz and told == [ref.nnz] and Z.nnz_known
        Z.when_counted(told.append)
        assert told == [ref.nnz, ref.nnz]
        assert Z.density == pytest.approx(ref.nnz / (ROWS * 96))

    def test_to_scipy_round_trip(self, born):
        Z, ref = born
        got = Z.to_scipy()
        assert Z.host_materialized and got.format == "csc"
        close(got.toarray(), ref.toarray())
        assert Z.indptr.shape == (97,) and Z.indices.dtype == np.int32
        assert Z.data.shape == (ref.nnz,) and Z.dtype == np.float32
        again = SparseMatrix.from_scipy(got)
        np.testing.assert_array_equal(np.asarray(again.todense()),
                                      np.asarray(Z.todense()))

    def test_csr_parts_and_csc_parts_are_canonical(self, born):
        Z, ref = born
        data, indices, indptr = Z.csr_parts()
        np.testing.assert_array_equal(indptr, ref.indptr)
        np.testing.assert_array_equal(indices, ref.indices)
        csc = ref.tocsc()
        data_t, rows_t, colptr = Z.csc_parts()
        np.testing.assert_array_equal(colptr, csc.indptr)
        np.testing.assert_array_equal(rows_t, csc.indices)

    def test_coo_and_todense_serve_from_the_lanes(self, born, monkeypatch):
        Z, ref = born
        monkeypatch.setattr(SparseMatrix, "_host", lambda self: 1 / 0)
        r, c, v = Z.coo()
        assert r.shape == c.shape == v.shape == (Z.lanes,)
        close(np.asarray(Z.todense()), ref.toarray())
        assert not Z.nnz_known
        half = Z.csr_device(jnp.bfloat16)       # cast where it is
        assert half[0].dtype == jnp.bfloat16 and half[1] is Z.csr_device()[1]

    @pytest.mark.parametrize("k", [1, 5])
    def test_spmm_and_spmm_t(self, born, k):
        Z, ref = born
        B = np.random.default_rng(2).standard_normal((96, k)).astype(np.float32)
        np.testing.assert_allclose(np.asarray(spmm(Z, B)), ref @ B,
                                   rtol=1e-4, atol=1e-5)
        C = np.random.default_rng(3).standard_normal((ROWS, k)).astype(np.float32)
        np.testing.assert_allclose(np.asarray(spmm_t(Z, C)), ref.T @ C,
                                   rtol=1e-4, atol=1e-5)

    def test_transpose_and_column_view(self, born):
        Z, ref = born
        close(np.asarray(Z.T.todense()), ref.toarray().T)
        close(np.asarray(Z.column_view(3, 40).todense()),
              ref.toarray()[:, 3:40])

    def test_host_born_matrices_are_what_they_were(self, fresh):
        X = operand()
        A = SparseMatrix.from_scipy(X)
        assert A.nnz_known and A.host_materialized and A.nnz == X.nnz
        assert A.lanes == lane_class(X.nnz) == A.csr_device()[0].shape[0]
        assert A.row_cap == int(np.diff(X.indptr).max())
        B = SparseMatrix.from_scipy(X.tocsc())      # no row-major side
        assert B.row_cap == A.row_cap
        r, c, v = A.coo()
        assert r.shape == (X.nnz,)      # a host-born matrix's triplets: exact


class TestSpansAndCounters:
    def _counter(self, name):
        got = metrics.snapshot()["metrics"].get(name)
        return sum(v["value"] for v in got["values"]) if got else 0

    def test_the_span_and_the_lazy_counts(self, fresh):
        metrics._ENABLED = True
        X = operand()
        A = SparseMatrix.from_scipy(X)
        T = sk.CWT(N, 8, Context(SEED))
        T.apply_sparse(A, sk.ROWWISE)
        trace.clear_finished()
        nnz0 = self._counter("sketch.sparse_nnz")
        merged0 = self._counter("sketch.sparse_merged")
        Z = T.apply_sparse(A, sk.ROWWISE)
        dispatch, = [s for s in trace.finished_spans()
                     if s.name == "sketch.dispatch"]
        attrs = dispatch.attrs
        assert attrs["path"] == "sparse" and attrs["result"] == "sparse"
        assert attrs["family"] == "CWT" and attrs["lookup"] == "lane"
        assert attrs["kernel"] == "xla_window_sort" and attrs["why"]
        assert attrs["nnz"] == X.nnz
        assert attrs["nnz_class"] == attrs["lanes_out"] == A.lanes
        assert "nnz_out" not in attrs           # nobody asked yet
        assert self._counter("sketch.sparse_nnz") - nnz0 == X.nnz
        assert self._counter("sketch.sparse_merged") == merged0
        stored = Z.nnz                          # the first ask fills both
        assert attrs["nnz_out"] == stored < X.nnz
        assert self._counter("sketch.sparse_merged") - merged0 \
            == X.nnz - stored
        # exactly one handover an apply: the engine.execute inside the span
        assert len([s for s in trace.finished_spans()
                    if s.name == "engine.execute"]) == 1

    def test_columnwise_says_global_and_why(self, fresh):
        metrics._ENABLED = True
        A = SparseMatrix.from_scipy(operand())
        sk.CWT(ROWS, 8, Context(SEED)).apply_sparse(A, sk.COLUMNWISE)
        dispatch = [s for s in trace.finished_spans()
                    if s.name == "sketch.dispatch"][-1]
        assert dispatch.attrs["kernel"] == "xla_global_sort"
        assert "regrouped" in dispatch.attrs["why"]

    def test_the_coalescing_stage_is_named_in_the_module(self, fresh):
        """The device trace names the coalescing operations by their scope:
        the compiled module's instructions carry it."""
        A = SparseMatrix.from_scipy(operand())
        sk.CWT(N, 8, Context(SEED)).apply_sparse(A, sk.ROWWISE)
        cache = engine.cache()
        key, = [k for k in cache.keys() if k[0] == "sketch.hash_sparse_out"]
        text = cache.lookup(key).executable.as_text()
        assert f"/{sparse_coalesce.SCOPE}/" in text
        assert "sort" in text
