"""Sparse-operand serve hot path (docs/serving, "Sparse operands on
the serve path").

Oracles:

- *dense-reference bit-equality*: a CSR request through
  ``submit_sparse`` equals ``transform.apply(A.todense())`` **bit for
  bit** — CWT because the CSR lanes accumulate in the dense scatter's
  row-major order (zero entries contribute exact ±0.0), the dense
  families (JLT) because the flush densifies in-executable and runs
  the literal dense serve program.
- *lane invariance* (bitwise): a ragged-nnz cohort member's result out
  of a coalesced flush equals its own capacity-1 dispatch.
- *bucket discipline*: the pow2 nnz class rides the statics — ragged
  nnz inside one class coalesces into one bucket (zero recompiles
  after warmup), across classes it keys separate buckets.
- *one flush program* for the sparse family: the vmapped lane function
  with the XLA scatter, whatever the shape and whatever
  ``sparse_serve.sparse_kernel`` says of a direct apply there; a pallas
  intent (executor ``kernel=`` argument or ``SKYLARK_SERVE_KERNEL``)
  declines to it, counted.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax.numpy as jnp
import scipy.sparse as sp

from libskylark_tpu import Context, engine
from libskylark_tpu import sketch as sk
from libskylark_tpu.base.sparse import SparseMatrix, spmm, spmm_t
from libskylark_tpu.engine import bucket as bucketing
from libskylark_tpu.engine.serve import request_statics
from libskylark_tpu.sketch import pallas_sparse, sparse_serve


@pytest.fixture()
def fresh_engine():
    engine.reset()
    yield
    engine.reset()


def _executor(**kw):
    kw.setdefault("max_batch", 8)
    kw.setdefault("linger_us", 1000)
    return engine.MicrobatchExecutor(**kw)


def _rand_sparse(rng, h, w, nnz, dtype=np.float32):
    r = rng.integers(0, h, nnz)
    c = rng.integers(0, w, nnz)
    v = rng.standard_normal(nnz).astype(dtype)
    return SparseMatrix.from_scipy(
        sp.coo_matrix((v, (r, c)), shape=(h, w)))


def _lattice_sparse(rng, h, w, nnz):
    """Integer-valued data: every bucket sum is exact, so even the MXU
    contraction (which only reorders additions) is bitwise."""
    r = rng.integers(0, h, nnz)
    c = rng.integers(0, w, nnz)
    v = rng.integers(-4, 5, nnz).astype(np.float32)
    return SparseMatrix.from_scipy(
        sp.coo_matrix((v, (r, c)), shape=(h, w)))


# ---------------------------------------------------------------------------
# bit-equality battery: CSR serve path vs the dense reference
# ---------------------------------------------------------------------------


class TestBitEquality:
    @pytest.mark.parametrize("family", [sk.CWT, sk.JLT])
    @pytest.mark.parametrize("dimension", [sk.COLUMNWISE, sk.ROWWISE])
    @pytest.mark.parametrize("dtype", [np.float32, np.float64])
    def test_sparse_vs_dense_reference(self, fresh_engine, family,
                                       dimension, dtype):
        """submit_sparse == transform.apply(todense()) bit for bit,
        both orientations, f32 and f64-host (device f32 policy).
        CWT holds at ANY shape (the scatter-order argument); the
        dense families hold when the stream extent is its own pow2
        class (padding changes the matmul's reduction length, which
        legitimately re-blocks an f32 dot — the dense serve
        endpoint's own documented epsilon band covers non-pow2
        classes, asserted below)."""
        rng = np.random.default_rng(3)
        ctx = Context(seed=1)
        N = 100 if family is sk.CWT else 128   # pow2 for dense fams
        m, s_dim = 9, 16
        T = family(N, s_dim, ctx)
        shape = (m, N) if dimension == sk.ROWWISE else (N, m)
        A = _rand_sparse(rng, *shape, nnz=37, dtype=dtype)
        with _executor() as ex:
            out = np.asarray(ex.submit_sparse(
                T, A, dimension=dimension).result(timeout=60))
        ref = np.asarray(T.apply(A.todense(), dimension))
        assert np.array_equal(out, ref)

    def test_jlt_nonpow2_class_epsilon_band(self, fresh_engine):
        """Off the pow2 stream class, the JLT sparse flush stays
        bit-equal to the densified serve request (same padded-class
        program) and allclose to the eager apply — the dense serve
        endpoint's own oracle band, inherited unchanged."""
        rng = np.random.default_rng(30)
        ctx = Context(seed=30)
        T = sk.JLT(300, 24, ctx)
        A = _rand_sparse(rng, 300, 11, nnz=60)
        with _executor() as ex:
            o_sp = np.asarray(ex.submit_sparse(
                T, A, dimension=sk.COLUMNWISE).result(timeout=60))
            o_de = np.asarray(ex.submit_sketch(
                T, np.asarray(A.todense()),
                dimension=sk.COLUMNWISE).result(timeout=60))
        assert np.array_equal(o_sp, o_de)
        assert np.allclose(
            o_sp, np.asarray(T.apply(A.todense(), sk.COLUMNWISE)),
            rtol=1e-4, atol=1e-5)

    @pytest.mark.parametrize("family", [sk.CWT, sk.JLT])
    def test_sparse_vs_densified_serve_submit(self, fresh_engine,
                                              family):
        """The sparse flush also equals the densified operand through
        the DENSE serve endpoint (a different executable at the same
        class) — the cross-executable half of the densify contract."""
        rng = np.random.default_rng(4)
        ctx = Context(seed=2)
        T = family(120, 16, ctx)
        A = _rand_sparse(rng, 120, 7, nnz=55)
        with _executor() as ex:
            o_sp = np.asarray(ex.submit_sparse(
                T, A, dimension=sk.COLUMNWISE).result(timeout=60))
            o_de = np.asarray(ex.submit_sketch(
                T, np.asarray(A.todense()),
                dimension=sk.COLUMNWISE).result(timeout=60))
        assert np.array_equal(o_sp, o_de)

    def test_scipy_input_accepted(self, fresh_engine):
        rng = np.random.default_rng(5)
        ctx = Context(seed=3)
        T = sk.CWT(64, 8, ctx)
        A = sp.random(64, 5, density=0.05, random_state=1,
                      dtype=np.float32)
        with _executor() as ex:
            out = np.asarray(ex.submit_sparse(
                T, A, dimension=sk.COLUMNWISE).result(timeout=60))
        ref = np.asarray(T.apply(
            SparseMatrix.from_scipy(A).todense(), sk.COLUMNWISE))
        assert np.array_equal(out, ref)
        with _executor() as ex, pytest.raises(TypeError):
            ex.submit_sparse(T, rng.standard_normal((64, 5)))

    def test_explicit_zero_and_empty_operands(self, fresh_engine):
        """nnz = 0 and explicit stored zeros are exact through the
        padded lanes."""
        ctx = Context(seed=4)
        T = sk.CWT(32, 8, ctx)
        empty = SparseMatrix.from_coo([], [], [], (32, 4))
        with _executor() as ex:
            out = np.asarray(ex.submit_sparse(
                T, empty, dimension=sk.COLUMNWISE).result(timeout=60))
        assert np.array_equal(out, np.zeros((8, 4), np.float32))


# ---------------------------------------------------------------------------
# ragged-nnz cohorts, lane invariance, bucket keys
# ---------------------------------------------------------------------------


class TestBuckets:
    def test_ragged_nnz_coalesces_and_matches_capacity1(
            self, fresh_engine):
        rng = np.random.default_rng(0)
        ctx = Context(seed=0)
        T = sk.CWT(256, 16, ctx)
        reqs = [_rand_sparse(rng, 256, 6, nnz=10 + 6 * i)
                for i in range(8)]
        with _executor(max_batch=8, linger_us=5000) as ex:
            futs = [ex.submit_sparse(T, A, dimension=sk.COLUMNWISE)
                    for A in reqs]
            ex.flush()
            outs = [np.asarray(f.result(timeout=60)) for f in futs]
            st = ex.stats()
        assert st["flushes"] == 1          # one bucket, one flush
        assert st["coalesced"] == 8
        with _executor(max_batch=1, linger_us=100) as ex1:
            for A, o in zip(reqs, outs):
                one = np.asarray(ex1.submit_sparse(
                    T, A, dimension=sk.COLUMNWISE).result(timeout=60))
                assert np.array_equal(o, one)

    def test_nnz_class_key_stability(self, fresh_engine):
        rng = np.random.default_rng(1)
        ctx = Context(seed=1)
        T = sk.CWT(256, 16, ctx)

        def exact_nnz(nnz):
            # distinct coordinates: the class boundary assertions need
            # the EXACT nonzero count (random COO duplicates collapse)
            flat = rng.choice(256 * 6, nnz, replace=False)
            v = rng.standard_normal(nnz).astype(np.float32)
            return SparseMatrix.from_scipy(sp.coo_matrix(
                (v, (flat // 6, flat % 6)), shape=(256, 6)))

        k = [request_statics("sparse_sketch_apply", transform=T,
                             A=exact_nnz(nnz),
                             dimension=sk.COLUMNWISE)
             for nnz in (10, 40, 63, 64, 65, 200)]
        assert k[0] == k[1] == k[2] == k[3]   # class 64 (floor)
        assert k[3] != k[4]                   # 65 -> class 128
        assert k[5] != k[4]                   # 200 -> class 256
        # derivation is stable call to call
        again = request_statics(
            "sparse_sketch_apply", transform=T,
            A=exact_nnz(10),
            dimension=sk.COLUMNWISE)
        assert again == k[0]

    def test_nnz_floor_env_knob(self, fresh_engine, monkeypatch):
        assert bucketing.nnz_class(1) == 64
        assert bucketing.nnz_class(65) == 128
        monkeypatch.setenv("SKYLARK_SPARSE_NNZ_FLOOR", "256")
        rng = np.random.default_rng(2)
        ctx = Context(seed=2)
        T = sk.CWT(64, 8, ctx)
        k1 = request_statics("sparse_sketch_apply", transform=T,
                             A=_rand_sparse(rng, 64, 4, nnz=5),
                             dimension=sk.COLUMNWISE)
        k2 = request_statics("sparse_sketch_apply", transform=T,
                             A=_rand_sparse(rng, 64, 4, nnz=200),
                             dimension=sk.COLUMNWISE)
        assert k1 == k2                       # both under the 256 floor

    def test_zero_recompiles_after_warmup(self, fresh_engine):
        rng = np.random.default_rng(3)
        ctx = Context(seed=3)
        T = sk.CWT(256, 16, ctx)
        reqs = [_rand_sparse(rng, 256, 6, nnz=10 + 6 * i)
                for i in range(8)]
        with _executor(max_batch=8, linger_us=4000) as ex:
            for cap in (1, 2, 4, 8):
                futs = [ex.submit_sparse(T, A,
                                         dimension=sk.COLUMNWISE)
                        for A in reqs[:cap]]
                ex.flush()
                [f.result(timeout=60) for f in futs]
            m0, r0 = engine.stats().misses, engine.stats().recompiles
            for _ in range(2):
                futs = [ex.submit_sparse(T, A,
                                         dimension=sk.COLUMNWISE)
                        for A in reqs]
                ex.flush()
                [f.result(timeout=60) for f in futs]
            assert engine.stats().misses - m0 == 0
            assert engine.stats().recompiles - r0 == 0


# ---------------------------------------------------------------------------
# densify fallback + counters
# ---------------------------------------------------------------------------


class TestDensifyAndCounters:
    def test_densify_fallback_threshold(self, fresh_engine,
                                        monkeypatch):
        rng = np.random.default_rng(4)
        ctx = Context(seed=4)
        T = sk.CWT(64, 8, ctx)
        A = _rand_sparse(rng, 64, 8, nnz=200)   # ~39% dense
        with _executor() as ex:
            out = np.asarray(ex.submit_sparse(
                T, A, dimension=sk.COLUMNWISE).result(timeout=60))
            st = ex.stats()["sparse"]
            assert st["submits"] == 1
            assert st["densified"] == 1
            # the densified request never reached the sparse bucket
            assert st["by_backend"] == {}
        assert np.array_equal(
            out, np.asarray(T.apply(A.todense(), sk.COLUMNWISE)))
        # raising the threshold keeps the same operand on the CSR path
        monkeypatch.setenv("SKYLARK_SPARSE_MIN_DENSITY", "0.9")
        with _executor() as ex:
            out2 = np.asarray(ex.submit_sparse(
                T, A, dimension=sk.COLUMNWISE).result(timeout=60))
            st = ex.stats()["sparse"]
            assert st["densified"] == 0
            assert sum(v["kernel_flushes"]
                       for v in st["by_backend"].values()) == 1
        assert np.array_equal(out, out2)

    def test_stats_block_and_hist(self, fresh_engine):
        rng = np.random.default_rng(5)
        ctx = Context(seed=5)
        T = sk.CWT(256, 8, ctx)
        with _executor() as ex:
            for nnz in (10, 10, 100):
                ex.submit_sparse(T, _rand_sparse(rng, 256, 4, nnz),
                                 dimension=sk.COLUMNWISE)
            ex.flush()
            st = ex.stats()["sparse"]
        assert st["submits"] == 3
        assert st["nnz_class_hist"] == {64: 2, 128: 1}
        agg = engine.serve_stats()["sparse"]
        assert agg["submits"] >= 3

    def test_prometheus_surface(self, fresh_engine):
        from libskylark_tpu import telemetry

        rng = np.random.default_rng(6)
        ctx = Context(seed=6)
        T = sk.CWT(64, 8, ctx)
        with _executor() as ex:
            ex.submit_sparse(T, _rand_sparse(rng, 64, 4, 10),
                             dimension=sk.COLUMNWISE)
            ex.flush()
        text = telemetry.prometheus_text()
        assert "skylark_serve_sparse_submits_total" in text
        assert "skylark_serve_sparse_kernel_flushes_total" in text
        assert "skylark_serve_sparse_nnz_class_bucket" in text


# ---------------------------------------------------------------------------
# the sparse flush has one program: the vmapped lane function
# ---------------------------------------------------------------------------


class TestSparseFlush:
    @pytest.mark.parametrize("family", [sk.CWT, sk.JLT])
    @pytest.mark.parametrize("dimension,intent", [
        (sk.COLUMNWISE, "arg"), (sk.ROWWISE, "env")],
        ids=["columnwise", "rowwise"])
    def test_pallas_intent_declines(self, fresh_engine, monkeypatch,
                                    family, dimension, intent):
        """A pallas intent (executor argument or SKYLARK_SERVE_KERNEL)
        on a sparse bucket: the lane program serves, bit-equal to the
        default executor, with one counted reason; a warm-up pack
        cannot seed one either."""
        rng = np.random.default_rng(7)
        N, m, s_dim = 256, 6, 16
        T = family(N, s_dim, Context(seed=7))
        shape = (m, N) if dimension == sk.ROWWISE else (N, m)
        A = _rand_sparse(rng, *shape, nnz=20)
        with _executor() as ex:
            want = np.asarray(ex.submit_sparse(
                T, A, dimension=dimension).result(timeout=60))
            (memo_key,) = ex._kernel_memo
            assert ex._kernel_memo[memo_key] == (
                "xla", "default", None)
            assert not ex.restore_kernel_choice(memo_key[0], 4, "pallas")
            assert ex.restore_kernel_choice(memo_key[0], 4, "xla")
        if intent == "env":
            monkeypatch.setenv("SKYLARK_SERVE_KERNEL", "pallas")
        with _executor(**({"kernel": "pallas"} if intent == "arg"
                          else {})) as ex:
            got = np.asarray(ex.submit_sparse(
                T, A, dimension=dimension).result(timeout=60))
            st = ex.stats()
            (choice,) = ex._kernel_memo.values()
        assert np.array_equal(got, want)
        slug = "no-batched-kernel-the-lane-program-serves"
        assert choice == ("xla", intent, slug)
        assert st["kernel"]["by_reason"] == {slug: {"declined_flushes": 1}}
        assert st["sparse"]["by_backend"] == {"xla": {"kernel_flushes": 1}}

    @pytest.mark.parametrize("shape,s_dim,nnz", [
        ((64, 3000), 128, 9000), ((256, 1000), 384, 20000)])
    def test_the_flush_takes_the_scatter_whatever_the_shape(
            self, fresh_engine, monkeypatch, shape, s_dim, nnz):
        """Shapes at which a direct rowwise apply on a TPU takes the rows
        kernel (``sparse_kernel``): the flush asks no such rule, it is
        the scatter lane — bit-equal to the dense reference, the kernel
        never traced, whatever ``SKYLARK_SERVE_KERNEL`` says."""
        monkeypatch.setattr(pallas_sparse, "available", lambda: True)

        def never(*a, **kw):
            raise AssertionError("the serve flush traced the rows kernel")

        monkeypatch.setattr(pallas_sparse, "hash_rows_apply", never)
        rng = np.random.default_rng(nnz)
        T = sk.CWT(shape[1], s_dim, Context(seed=5))
        A = _rand_sparse(rng, *shape, nnz=nnz)
        padded = bucketing.pad_shape(A.shape, (0, 1))
        lanes = bucketing.nnz_class(A.nnz)
        assert sparse_serve.sparse_kernel(
            padded, s_dim, lanes, jnp.float32, True) == "pallas_rows"
        monkeypatch.setenv("SKYLARK_SERVE_KERNEL", "pallas")
        with _executor(max_batch=1, linger_us=100) as ex:
            out = np.asarray(ex.submit_sparse(
                T, A, dimension=sk.ROWWISE).result(timeout=120))
            st = ex.stats()
            (choice,) = ex._kernel_memo.values()
        assert np.array_equal(
            out, np.asarray(T.apply(A.todense(), sk.ROWWISE)))
        slug = "no-batched-kernel-the-lane-program-serves"
        assert choice == ("xla", "env", slug)
        assert st["sparse"]["by_backend"] == {"xla": {"kernel_flushes": 1}}
        assert st["kernel"]["by_reason"] == {slug: {"declined_flushes": 1}}


# ---------------------------------------------------------------------------
# sparse solve endpoint
# ---------------------------------------------------------------------------


class TestSparseSolve:
    @pytest.mark.parametrize("family", [sk.CWT, sk.JLT])
    def test_bit_equal_to_dense_serve_solve(self, fresh_engine,
                                            family):
        rng = np.random.default_rng(9)
        ctx = Context(seed=9)
        T = family(96, 48, ctx)
        A = _rand_sparse(rng, 96, 5, nnz=40)
        B = rng.standard_normal((96, 2)).astype(np.float32)
        with _executor() as ex:
            xs = np.asarray(ex.submit_sparse_solve(
                A, B, T).result(timeout=60))
            xd = np.asarray(ex.submit_solve(
                np.asarray(A.todense()), B, T).result(timeout=60))
        assert np.array_equal(xs, xd)

    def test_vector_target_squeezes(self, fresh_engine):
        rng = np.random.default_rng(10)
        ctx = Context(seed=10)
        T = sk.CWT(96, 48, ctx)
        A = _rand_sparse(rng, 96, 5, nnz=40)
        b = rng.standard_normal(96).astype(np.float32)
        with _executor() as ex:
            x = np.asarray(ex.submit_sparse_solve(
                A, b, T).result(timeout=60))
        assert x.shape == (5,)


# ---------------------------------------------------------------------------
# the lane's row ids (base.sparse.csr_row_ids, what the scatter reads)
# ---------------------------------------------------------------------------


class TestRowIds:
    def test_row_id_expansion(self):
        ptr = jnp.asarray(np.array([0, 2, 2, 5, 5], np.int32))
        rows = np.asarray(sparse_serve.csr_row_ids(ptr, 8))
        # 5 real nonzeros over rows [0,0,2,2,2]; padding clamps to 3
        assert rows.tolist() == [0, 0, 2, 2, 2, 3, 3, 3]


# ---------------------------------------------------------------------------
# spmm via the executable cache (jit-leak regression)
# ---------------------------------------------------------------------------


class TestSpmmEngineRouting:
    def test_spmm_caches_one_executable(self, fresh_engine):
        rng = np.random.default_rng(14)
        A = _rand_sparse(rng, 64, 32, nnz=100)
        B = rng.standard_normal((32, 4)).astype(np.float32)
        ref = np.asarray(A.to_scipy() @ B)
        out0 = np.asarray(spmm(A, B))
        assert np.allclose(out0, ref, rtol=1e-5, atol=1e-5)
        m0, r0 = engine.stats().misses, engine.stats().recompiles
        for _ in range(5):
            np.asarray(spmm(A, B))
        assert engine.stats().misses == m0       # warm: pure hits
        assert engine.stats().recompiles == r0

    def test_spmm_t_caches_one_executable(self, fresh_engine):
        rng = np.random.default_rng(15)
        A = _rand_sparse(rng, 64, 32, nnz=100)
        B = rng.standard_normal((64, 3)).astype(np.float32)
        ref = np.asarray(A.to_scipy().T @ B)
        out0 = np.asarray(spmm_t(A, B))
        assert np.allclose(out0, ref, rtol=1e-5, atol=1e-5)
        m0 = engine.stats().misses
        for _ in range(5):
            np.asarray(spmm_t(A, B))
        assert engine.stats().misses == m0

    def test_vector_rhs_squeezes(self, fresh_engine):
        rng = np.random.default_rng(16)
        A = _rand_sparse(rng, 20, 10, nnz=30)
        b = rng.standard_normal(10).astype(np.float32)
        out = np.asarray(spmm(A, b))
        assert out.shape == (20,)
        assert np.allclose(out, np.asarray(A.to_scipy() @ b),
                           rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# csr_parts / from_csr round trip
# ---------------------------------------------------------------------------


class TestCsrParts:
    def test_round_trip_and_order(self):
        rng = np.random.default_rng(17)
        A = _rand_sparse(rng, 30, 7, nnz=25)
        data, indices, indptr = A.csr_parts()
        assert data.dtype == np.float32
        assert indptr.shape == (31,)
        assert indptr[-1] == len(data) == A.nnz
        # row-major, sorted columns inside each row
        for r in range(30):
            seg = indices[indptr[r]:indptr[r + 1]]
            assert np.all(np.diff(seg) > 0) or len(seg) <= 1
        B = SparseMatrix.from_csr(data, indices, indptr, (30, 7))
        assert np.array_equal(np.asarray(B.todense()),
                              np.asarray(A.todense()))

    @pytest.mark.parametrize("source", ["canonical_csr", "unsorted_csr",
                                        "csc", "coo"])
    def test_lanes_do_not_depend_on_the_source_format(self, source):
        """A canonical CSR is attached as the row-major lanes themselves
        (no CSC round trip); every other source converts — to the same
        parts, bit for bit."""
        import scipy.sparse as sp

        rng = np.random.default_rng(19)
        r, c = rng.integers(0, 40, 300), rng.integers(0, 9, 300)
        v = rng.standard_normal(300).astype(np.float32)
        _, first = np.unique(r * 9 + c, return_index=True)  # one a coordinate
        r, c, v = r[first], c[first], v[first]
        coo = sp.coo_matrix((v, (r, c)), shape=(40, 9))
        if source == "unsorted_csr":
            # CSR parts by hand: rows in order, their columns descending
            by_row = np.argsort(r[::-1], kind="stable")
            r, c, v = r[::-1], c[::-1], v[::-1]
            indptr = np.concatenate([[0], np.cumsum(np.bincount(
                r, minlength=40))])
            X = sp.csr_matrix((v[by_row], c[by_row], indptr), shape=(40, 9))
            assert not X.has_canonical_format
        else:
            X = {"canonical_csr": coo.tocsr(), "csc": coo.tocsc(),
                 "coo": coo}[source]
        want = SparseMatrix.from_scipy(X.tocsc()).csr_parts()
        A = SparseMatrix.from_scipy(X)
        assert (A._row_major is not None) == (source == "canonical_csr")
        got = A.csr_parts()
        assert all(np.array_equal(g, w) and g.dtype == w.dtype
                   for g, w in zip(got, want))
        assert np.array_equal(np.asarray(A.todense()), X.toarray())

    def test_density(self):
        rng = np.random.default_rng(18)
        A = _rand_sparse(rng, 100, 10, nnz=10)
        assert A.density == pytest.approx(0.01)
