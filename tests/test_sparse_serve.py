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
- *selection precedence* for the sparse family: executor ``kernel=``
  argument > ``SKYLARK_SPARSE_KERNEL`` > plan cache > xla default,
  with the sparse Pallas kernel declining off-TPU (counted reason).
- *kernel exactness* (interpret mode, direct): ``accum="exact"`` is
  bit-equal to the serve scatter; ``"mxu"`` is allclose (and bit-equal
  on lattice data).
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import scipy.sparse as sp

from libskylark_tpu import Context, engine, tune
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
# autotuner precedence for the sparse family
# ---------------------------------------------------------------------------


class TestSelectionPrecedence:
    def _flush_one(self, ex):
        rng = np.random.default_rng(7)
        ctx = Context(seed=7)
        T = sk.CWT(256, 16, ctx)
        A = _rand_sparse(rng, 256, 6, nnz=20)
        fut = ex.submit_sparse(T, A, dimension=sk.COLUMNWISE)
        ex.flush()
        fut.result(timeout=60)
        (choice,) = ex._kernel_memo.values()
        return choice

    def test_arg_beats_env(self, fresh_engine, monkeypatch):
        monkeypatch.setenv("SKYLARK_SPARSE_KERNEL", "pallas")
        with _executor(kernel="xla") as ex:
            backend, _plan, source, declined = self._flush_one(ex)
        assert (backend, source, declined) == ("xla", "arg", None)

    def test_env_beats_plan_cache(self, fresh_engine, monkeypatch):
        monkeypatch.setenv("SKYLARK_SPARSE_KERNEL", "pallas")
        prev = tune.set_cache(tune.PlanCache(path=None))
        try:
            with _executor() as ex:
                backend, _plan, source, declined = self._flush_one(ex)
        finally:
            tune.set_cache(prev)
        # the pin resolved from env; off-TPU the sparse kernel
        # DECLINES (counted) and the flush falls back to xla
        assert source == "env"
        assert backend == "xla"
        assert declined is not None
        assert "not-a-tpu" in declined or "tpu" in declined

    def test_sparse_pin_does_not_touch_dense_buckets(
            self, fresh_engine, monkeypatch):
        monkeypatch.setenv("SKYLARK_SPARSE_KERNEL", "pallas")
        rng = np.random.default_rng(8)
        ctx = Context(seed=8)
        T = sk.CWT(64, 16, ctx)
        A = rng.standard_normal((64, 6)).astype(np.float32)
        prev = tune.set_cache(tune.PlanCache(path=None))
        try:
            with _executor() as ex:
                fut = ex.submit_sketch(T, A, dimension=sk.COLUMNWISE)
                ex.flush()
                fut.result(timeout=60)
                (choice,) = ex._kernel_memo.values()
        finally:
            tune.set_cache(prev)
        # dense bucket: the sparse pin is invisible; default xla
        assert choice[2] == "default"

    def test_plan_cache_beats_default(self, fresh_engine):
        prev = tune.set_cache(tune.PlanCache(path=None))
        try:
            w = tune.serve_workload(
                "sparse_sketch_apply", "CWT", "float32", (256, 8),
                16, 1, rowwise=False, nnz=64)
            tune.get_cache().put(w, tune.Plan("pallas"),
                                 source="measured")
            with _executor(max_batch=1, linger_us=100) as ex:
                backend, _plan, source, declined = self._flush_one(ex)
        finally:
            tune.set_cache(prev)
        assert source == "plan"
        assert backend == "xla" and declined is not None  # CPU decline

    def test_sparse_pin_outranks_pack_restore(self, fresh_engine,
                                              monkeypatch):
        """A warmup-pack-recorded decision must NOT seed the memo when
        the operator pinned the sparse family — the memo is consulted
        before the pin, so seeding would silently override it."""
        statics = ("sparse_sketch_apply", "CWT", "None", 16, False,
                   "float32", (256, 8), 64)
        with _executor() as ex:
            monkeypatch.setenv("SKYLARK_SPARSE_KERNEL", "xla")
            assert not ex.restore_kernel_choice(statics, 4, "pallas")
            monkeypatch.delenv("SKYLARK_SPARSE_KERNEL")
            assert ex.restore_kernel_choice(statics, 4, "pallas")
            # dense statics are unaffected by the sparse pin
            monkeypatch.setenv("SKYLARK_SPARSE_KERNEL", "xla")
            dense = ("sketch_apply", "CWT", "None", 16, False,
                     "float32", (64, 8))
            assert ex.restore_kernel_choice(dense, 4, "xla")

    def test_default_is_xla(self, fresh_engine):
        prev = tune.set_cache(tune.PlanCache(path=None))
        try:
            with _executor() as ex:
                backend, _plan, source, declined = self._flush_one(ex)
        finally:
            tune.set_cache(prev)
        assert (backend, source, declined) == ("xla", "default", None)

    def test_ranked_certifies_xla_off_tpu(self, fresh_engine):
        w = tune.serve_workload(
            "sparse_sketch_apply", "CWT", "float32", (4096, 16), 32,
            8, rowwise=False, nnz=1024)
        assert "z1024" in w.key()
        ranked = tune.rank_candidates(w)
        assert ranked[0][0].backend == "xla"
        assert any(p.backend == "pallas" for p, _ in ranked)
        pallas_rec = next(c for p, c in ranked
                          if p.backend == "pallas")
        assert pallas_rec.get("interpret")  # penalty applied off-TPU


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
# the Pallas sparse kernel (direct, interpret mode)
# ---------------------------------------------------------------------------


class TestPallasSparseKernel:
    def _lanes(self, A, rng_dtype=np.float32):
        padded = bucketing.pad_shape(A.shape, (0, 1))
        nnz_cls = bucketing.nnz_class(A.nnz)
        data, idx, ptr = A.csr_parts(rng_dtype)
        d = np.zeros(nnz_cls, rng_dtype)
        d[: len(data)] = data
        ix = np.zeros(nnz_cls, np.int32)
        ix[: len(idx)] = idx
        pt = np.full(padded[0] + 1, len(data), np.int32)
        pt[: len(ptr)] = ptr
        rows = np.asarray(sparse_serve.csr_row_ids(
            jnp.asarray(pt), nnz_cls))
        return padded, d, ix, pt, rows

    @pytest.mark.parametrize("rowwise", [False, True])
    def test_exact_accum_bit_equal_to_serve_scatter(self, rowwise):
        rng = np.random.default_rng(11)
        ctx = Context(seed=11)
        N, m, s_dim = 200, 11, 16
        shape = (m, N) if rowwise else (N, m)
        A = _rand_sparse(rng, *shape, nnz=70)
        T = sk.CWT(N, s_dim, ctx)
        kd = np.asarray(jax.random.key_data(T.allocation.key),
                        dtype=np.uint32)
        padded, d, ix, pt, rows = self._lanes(A)
        ref = np.asarray(sparse_serve.cwt_sparse_serve_apply(
            kd, jnp.asarray(d), jnp.asarray(ix), jnp.asarray(pt),
            s_dim=s_dim, rowwise=rowwise, shape=padded))
        out = np.asarray(pallas_sparse.cwt_sparse_apply(
            kd, d, rows, ix, s_dim=s_dim, rowwise=rowwise,
            shape=padded, accum="exact", interpret=True))
        assert np.array_equal(out, ref)

    @pytest.mark.parametrize("rowwise", [False, True])
    def test_mxu_accum_allclose_and_lattice_bitwise(self, rowwise):
        rng = np.random.default_rng(12)
        ctx = Context(seed=12)
        N, m, s_dim = 128, 9, 16
        shape = (m, N) if rowwise else (N, m)
        T = sk.CWT(N, s_dim, ctx)
        kd = np.asarray(jax.random.key_data(T.allocation.key),
                        dtype=np.uint32)
        A = _rand_sparse(rng, *shape, nnz=50)
        padded, d, ix, pt, rows = self._lanes(A)
        ref = np.asarray(sparse_serve.cwt_sparse_serve_apply(
            kd, jnp.asarray(d), jnp.asarray(ix), jnp.asarray(pt),
            s_dim=s_dim, rowwise=rowwise, shape=padded))
        out = np.asarray(pallas_sparse.cwt_sparse_apply(
            kd, d, rows, ix, s_dim=s_dim, rowwise=rowwise,
            shape=padded, accum="mxu", interpret=True))
        assert np.allclose(out, ref, rtol=1e-5, atol=1e-6)
        L = _lattice_sparse(rng, *shape, nnz=50)
        padded, d, ix, pt, rows = self._lanes(L)
        ref = np.asarray(sparse_serve.cwt_sparse_serve_apply(
            kd, jnp.asarray(d), jnp.asarray(ix), jnp.asarray(pt),
            s_dim=s_dim, rowwise=rowwise, shape=padded))
        out = np.asarray(pallas_sparse.cwt_sparse_apply(
            kd, d, rows, ix, s_dim=s_dim, rowwise=rowwise,
            shape=padded, accum="mxu", interpret=True))
        assert np.array_equal(out, ref)

    def test_batched_lanes_capacity_invariant(self):
        rng = np.random.default_rng(13)
        ctx = Context(seed=13)
        N, m, s_dim = 128, 8, 16
        ops = [_rand_sparse(rng, N, m, nnz=30 + i) for i in range(4)]
        Ts = [sk.CWT(N, s_dim, ctx) for _ in ops]
        kds, ds, rs, cs = [], [], [], []
        padded = bucketing.pad_shape((N, m), (0, 1))
        for T, A in zip(Ts, ops):
            _, d, ix, pt, rows = self._lanes(A)
            kds.append(np.asarray(
                jax.random.key_data(T.allocation.key), np.uint32))
            ds.append(d)
            rs.append(rows)
            cs.append(ix)
        full = np.asarray(pallas_sparse.cwt_sparse_apply_batched(
            np.stack(kds), np.stack(ds), np.stack(rs), np.stack(cs),
            s_dim=s_dim, rowwise=False, shape=padded, accum="exact",
            interpret=True))
        for i in range(4):
            one = np.asarray(pallas_sparse.cwt_sparse_apply(
                kds[i], ds[i], rs[i], cs[i], s_dim=s_dim,
                rowwise=False, shape=padded, accum="exact",
                interpret=True))
            assert np.array_equal(full[i], one)

    def test_qualify_declines_off_tpu(self):
        ok, why = pallas_sparse.qualify(16, 128, 8, 64, "float32",
                                        interpret=True)
        assert not ok and "TPU" in why
        ok, why = pallas_sparse.qualify(16, 128, 8, 64, "float32",
                                        interpret=False)
        assert not ok  # CPU backend: available() is False

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
