"""Scatter-free CWT/CountSketch Pallas kernel (sketch/pallas_hash.py)
and the serve-bucket kernel-selection seam it feeds.

Oracles, strongest first:

- *stream bit-equality*: the kernel's in-VMEM (h, v) generation
  (``_gen_hv`` over the ``chunk_key_table`` keys) reproduces
  ``randgen.stream_slice`` bit-for-bit — jax.random's own
  fold_in/split/randint/rademacher pipeline replayed through the shared
  integer-op Threefry, across chunk boundaries.
- *exact-accumulation bit-equality* (interpret mode): ``accum="exact"``
  equals ``HashTransform.apply`` AND ``cwt_serve_apply`` bitwise,
  including zero-padded serve lanes and across capacity classes (the
  serve layer's lane-invariance contract).
- *MXU-mode dataflow bit-equality on lattice data*: integer-valued
  inputs make every bucket sum exact, so the one-hot contraction is
  bit-equal to the scatter no matter the accumulation order — this pins
  the whole MXU dataflow bitwise; float data is then 1e-5-close (order
  differs, values don't).
- serve integration: a forced-pallas flush is bit-equal to the
  capacity-1 XLA dispatch, the kernel choice is a static of the
  executable key, declines are counted by reason, and the flush rule
  (``kernel=`` argument > ``SKYLARK_SERVE_KERNEL`` > XLA) is held as a
  table over every bucket family.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import jax.random as jr

from libskylark_tpu import Context, engine
from libskylark_tpu import sketch as sk
from libskylark_tpu.base import randgen
from libskylark_tpu.base import threefry as tf
from libskylark_tpu.sketch import pallas_hash as ph
from libskylark_tpu.sketch.hash import cwt_serve_apply


@pytest.fixture()
def fresh_engine():
    engine.reset()
    yield
    engine.reset()


def _cwt_and_ref(n, s, m, seed=7, rowwise=False):
    rng = np.random.default_rng(seed)
    T = sk.CWT(n, s, Context(seed=seed))
    kd = np.asarray(jr.key_data(T.allocation.key), np.uint32)
    shape = (m, n) if rowwise else (n, m)
    A = rng.standard_normal(shape).astype(np.float32)
    dim = sk.ROWWISE if rowwise else sk.COLUMNWISE
    ref = np.asarray(T.apply(jnp.asarray(A), dim))
    return T, kd, A, ref


class TestStreamReplication:
    @pytest.mark.parametrize("s_dim", [16, 100, 128])
    @pytest.mark.parametrize("n", [8, 40, 2048, 5000])
    def test_gen_hv_bit_equals_stream_slice(self, s_dim, n):
        """The in-kernel generation path (plain jnp ops here — the same
        ops Mosaic lowers) replays randgen.stream_slice exactly:
        UniformInt bucket stream, Rademacher value stream, across the
        CHUNK boundary (n=5000 spans two chunks)."""
        key = jr.key(42)
        n_pad = ph._padded_n(n)
        n_tile = min(n_pad, ph.CHUNK)
        n_chunks = n_pad // n_tile
        cols = min(n_tile, ph._GEN_COLS)
        tbl = ph.chunk_key_table(key, n_chunks)
        hs, vs = [], []
        for c in range(n_chunks):
            h, v = ph._gen_hv(tbl, c, s_dim, n_tile, cols)
            hs.append(np.asarray(h).reshape(-1))
            vs.append(np.asarray(v).reshape(-1))
        h_ref = np.asarray(randgen.stream_slice(
            jr.fold_in(key, 0), randgen.UniformInt(0, s_dim - 1), 0, n,
            dtype=jnp.int32))
        v_ref = np.asarray(randgen.stream_slice(
            jr.fold_in(key, 1), randgen.Rademacher(), 0, n,
            dtype=jnp.float32))
        assert np.array_equal(np.concatenate(hs)[:n], h_ref)
        assert np.array_equal(np.concatenate(vs)[:n], v_ref)

    def test_randint_multiplier_matches_jax(self):
        # pow2 spans ≤ 2^16 cancel the high draw entirely
        assert tf.randint_multiplier(16) == 0
        assert tf.randint_multiplier(1 << 16) == 0
        # general spans keep jax's double-draw mix
        assert tf.randint_multiplier(100) == ((65536 % 100) ** 2) % 100


class TestBitEquality:
    @pytest.mark.parametrize("rowwise", [False, True])
    @pytest.mark.parametrize("n,s,m", [(40, 16, 3), (100, 24, 5),
                                       (513, 32, 4)])
    def test_exact_accum_bit_equals_apply(self, n, s, m, rowwise):
        _T, kd, A, ref = _cwt_and_ref(n, s, m, rowwise=rowwise)
        out = np.asarray(ph.cwt_apply(kd, A, s_dim=s, rowwise=rowwise,
                                      accum="exact", interpret=True))
        assert np.array_equal(out, ref)

    @pytest.mark.parametrize("rowwise", [False, True])
    def test_padded_serve_lanes_bit_equal(self, rowwise):
        """Zero-padding the stream axis past the transform's true N —
        exactly what the serve bucket's pow2 class does — leaves the
        kernel bit-equal to cwt_serve_apply over the SAME padded
        operand and to the unpadded transform.apply."""
        n, s, m = 40, 16, 3
        _T, kd, A, ref = _cwt_and_ref(n, s, m, rowwise=rowwise)
        pad = [(0, 13), (0, 0)] if not rowwise else [(0, 0), (0, 13)]
        Ap = np.pad(A, pad)
        sv = np.asarray(cwt_serve_apply(kd, jnp.asarray(Ap), s_dim=s,
                                        rowwise=rowwise))
        out = np.asarray(ph.cwt_apply(kd, Ap, s_dim=s, rowwise=rowwise,
                                      accum="exact", interpret=True))
        assert np.array_equal(out, sv)
        assert np.array_equal(out, ref)

    def test_capacity_invariance_batched(self):
        """Per-lane bits are invariant to the cohort's capacity class:
        the same lane at B=1 and inside a B=3 stack (mixed seeds)
        produces identical bits — the serve lane-invariance contract."""
        lanes = [_cwt_and_ref(40, 16, 3, seed=i) for i in range(3)]
        kds = np.stack([kd for (_, kd, _, _) in lanes])
        As = np.stack([A for (_, _, A, _) in lanes])
        out = np.asarray(ph.cwt_apply_batched(
            kds, As, s_dim=16, rowwise=False, accum="exact",
            interpret=True))
        for i, (_, kd, A, ref) in enumerate(lanes):
            solo = np.asarray(ph.cwt_apply(
                kd, A, s_dim=16, rowwise=False, accum="exact",
                interpret=True))
            assert np.array_equal(out[i], solo)
            assert np.array_equal(out[i], ref)

    def test_mxu_mode_bit_equal_on_lattice_data(self):
        """Integer-valued data makes every bucket sum exact in f32, so
        the MXU one-hot contraction — different accumulation ORDER,
        identical values — is bit-equal to the scatter. This pins the
        entire mxu dataflow bitwise."""
        rng = np.random.default_rng(3)
        T = sk.CWT(200, 24, Context(seed=11))
        kd = np.asarray(jr.key_data(T.allocation.key), np.uint32)
        A = rng.integers(-8, 9, (200, 4)).astype(np.float32)
        ref = np.asarray(T.apply(jnp.asarray(A), sk.COLUMNWISE))
        out = np.asarray(ph.cwt_apply(kd, A, s_dim=24, rowwise=False,
                                      accum="mxu", interpret=True))
        assert np.array_equal(out, ref)

    def test_mxu_mode_close_on_float_data(self):
        _T, kd, A, ref = _cwt_and_ref(1000, 32, 5)
        out = np.asarray(ph.cwt_apply(kd, A, s_dim=32, rowwise=False,
                                      accum="mxu", interpret=True))
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


class TestQualifyAndDispatch:
    def test_qualify_reasons(self):
        ok, why = ph.qualify(16, 40, 3, np.float32, interpret=True)
        assert ok and why == "ok"
        ok, why = ph.qualify(16, 40, 3, np.float64, interpret=True)
        assert not ok and "float64" in why
        ok, why = ph.qualify(16, 0, 3, np.float32, interpret=True)
        assert not ok and "degenerate" in why
        ok, why = ph.qualify(16, 40, 3, np.float32, accum="nope")
        assert not ok and "accum" in why
        if not ph.available():
            ok, why = ph.qualify(16, 40, 3, np.float32)
            assert not ok and "TPU" in why

    def test_plan_tiles_shrink_dont_fail(self):
        plan = ph.plan_tiles(40, 3, 16)
        assert plan is not None
        n_pad, n_tile, m_pad, mt = plan
        assert n_pad == 64 and n_tile == 64
        assert m_pad % mt == 0
        # absurd s_dim: no tile fits — decline, never a Mosaic abort
        assert ph.plan_tiles(4096, 8, 50_000_000) is None

    @pytest.mark.skipif(ph.available(), reason="CPU-host dispatch test")
    def test_try_apply_declines_off_tpu(self, monkeypatch):
        """The direct-apply hook: off-TPU the kernel always declines —
        the env override cannot route an eager apply into uncompileable
        Mosaic."""
        T = sk.CWT(40, 16, Context(seed=0))
        A = jnp.asarray(np.ones((40, 3), np.float32))
        assert ph.try_apply(T, A, rowwise=False) is None
        monkeypatch.setenv("SKYLARK_HASH_KERNEL", "pallas")
        assert ph.try_apply(T, A, rowwise=False) is None
        monkeypatch.delenv("SKYLARK_HASH_KERNEL")
        # and the public apply still serves (the scatter)
        out = T.apply(A, sk.COLUMNWISE)
        assert np.isfinite(np.asarray(out)).all()

    def test_try_apply_takes_only_the_env_pin(self, monkeypatch):
        """Where the kernel qualifies (a TPU, played here), only
        ``SKYLARK_HASH_KERNEL`` routes an eager apply to the kernel."""
        T = sk.CWT(40, 16, Context(seed=0))
        A = jnp.asarray(np.ones((40, 3), np.float32))
        monkeypatch.setattr(ph, "qualify", lambda *a, **k: (True, "ok"))
        calls = []
        monkeypatch.setattr(
            ph, "cwt_apply",
            lambda kd, A, **kw: calls.append(kw["accum"]) or "served")
        monkeypatch.delenv("SKYLARK_HASH_KERNEL", raising=False)
        assert ph.try_apply(T, A, rowwise=False) is None
        monkeypatch.setenv("SKYLARK_HASH_KERNEL", "xla")
        assert ph.try_apply(T, A, rowwise=False) is None
        monkeypatch.setenv("SKYLARK_HASH_KERNEL", "pallas_exact")
        assert ph.try_apply(T, A, rowwise=False) == "served"
        monkeypatch.setenv("SKYLARK_HASH_KERNEL", "pallas")
        assert ph.try_apply(T, A, rowwise=False) == "served"
        assert calls == ["exact", "mxu"]


class TestServeKernelSelection:
    def _cwt_reqs(self, k=8, seed=21):
        rng = np.random.default_rng(seed)
        T = sk.CWT(40, 16, Context(seed=seed))
        ops = [rng.standard_normal((40, 3)).astype(np.float32)
               for _ in range(k)]
        return T, ops

    def test_forced_pallas_flush_bit_equal_to_capacity1_xla(
            self, fresh_engine):
        """The CI gate's bit-equality leg: a coalesced kernel-path
        flush equals the capacity-1 forced-XLA dispatch bitwise (exact
        accumulation under the interpreter)."""
        T, ops = self._cwt_reqs()
        with engine.MicrobatchExecutor(max_batch=8, linger_us=1000,
                                       kernel="pallas") as exp:
            futs = [exp.submit_sketch(T, A, dimension=sk.COLUMNWISE)
                    for A in ops]
            pall = [np.asarray(f.result(timeout=60)) for f in futs]
            st = exp.stats()
        assert st["kernel"]["by_backend"]["pallas"]["flushes"] >= 1
        with engine.MicrobatchExecutor(max_batch=1, linger_us=100,
                                       kernel="xla") as ex1:
            for A, p in zip(ops, pall):
                s = np.asarray(ex1.submit_sketch(
                    T, A, dimension=sk.COLUMNWISE).result(timeout=60))
                assert np.array_equal(p, s)

    def test_kernel_choice_is_executable_key_static(self, fresh_engine):
        """Forcing the other backend on an identical bucket compiles a
        DIFFERENT executable — the choice token is in the key, so a
        selection flip can never silently reuse the wrong program."""
        T, ops = self._cwt_reqs()
        with engine.MicrobatchExecutor(max_batch=8, linger_us=1000,
                                       kernel="xla") as ex:
            futs = [ex.submit_sketch(T, A, dimension=sk.COLUMNWISE)
                    for A in ops]
            [f.result(timeout=60) for f in futs]
        m0 = engine.stats().misses
        with engine.MicrobatchExecutor(max_batch=8, linger_us=1000,
                                       kernel="pallas") as ex:
            futs = [ex.submit_sketch(T, A, dimension=sk.COLUMNWISE)
                    for A in ops]
            [f.result(timeout=60) for f in futs]
        assert engine.stats().misses > m0
        assert engine.stats().recompiles == 0

    def test_env_pin_beats_the_default(self, fresh_engine, monkeypatch):
        """arg > env > default precedence, env leg: the pin routes the
        flush through the kernel, bit-equal (exact accumulation) to the
        default's."""
        T, ops = self._cwt_reqs(k=4)
        outs = {}
        for pin in (None, "pallas"):
            if pin:
                monkeypatch.setenv("SKYLARK_SERVE_KERNEL", pin)
            with engine.MicrobatchExecutor(max_batch=4,
                                           linger_us=1000) as ex:
                futs = [ex.submit_sketch(T, A, dimension=sk.COLUMNWISE)
                        for A in ops]
                outs[pin] = [np.asarray(f.result(timeout=60))
                             for f in futs]
                assert (ex.stats()["kernel"]["by_backend"]
                        == {pin or "xla": {"flushes": 1}})
        for a, b in zip(outs[None], outs["pallas"]):
            assert np.array_equal(a, b)

    def test_default_is_xla(self, fresh_engine, monkeypatch):
        monkeypatch.delenv("SKYLARK_SERVE_KERNEL", raising=False)
        T, ops = self._cwt_reqs(k=4)
        with engine.MicrobatchExecutor(max_batch=4,
                                       linger_us=1000) as ex:
            futs = [ex.submit_sketch(T, A, dimension=sk.COLUMNWISE)
                    for A in ops]
            [f.result(timeout=60) for f in futs]
            assert (ex.stats()["kernel"]["by_backend"]
                    == {"xla": {"flushes": 1}})
            assert list(ex._kernel_memo.values()) == [
                ("xla", "default", None)]

    def test_decline_reason_counted(self, fresh_engine):
        """A pallas intent the kernel can't serve (f64) falls back to
        XLA and the reason lands in the by_reason label set."""
        rng = np.random.default_rng(5)
        T = sk.CWT(40, 16, Context(seed=5))
        ops = [rng.standard_normal((40, 3)) for _ in range(2)]  # f64
        with engine.MicrobatchExecutor(max_batch=2, linger_us=500,
                                       kernel="pallas") as ex:
            futs = [ex.submit_sketch(T, A, dimension=sk.COLUMNWISE)
                    for A in ops]
            [f.result(timeout=60) for f in futs]
            st = ex.stats()
        assert st["kernel"]["by_backend"]["xla"]["flushes"] >= 1
        assert any("float64" in r for r in st["kernel"]["by_reason"])
        agg = engine.serve_stats()
        assert agg["kernel"]["by_reason"]

    def test_prometheus_rendering_of_kernel_counters(
            self, fresh_engine):
        """The fleet-operator surface: kernel selection and decline
        reasons render through the by_<label> convention as Prometheus
        label sets — skylark_serve_kernel_flushes{backend="..."} and
        ..._declined_flushes{reason="..."} — so which replicas are on
        the fast path (and why the others are not) is one scrape
        away."""
        from libskylark_tpu.telemetry import export as texp

        rng = np.random.default_rng(29)
        T = sk.CWT(40, 16, Context(seed=29))
        good = [rng.standard_normal((40, 3)).astype(np.float32)
                for _ in range(2)]
        bad = [rng.standard_normal((40, 3)) for _ in range(2)]  # f64
        with engine.MicrobatchExecutor(max_batch=2, linger_us=500,
                                       kernel="pallas") as ex:
            for ops in (good, bad):
                futs = [ex.submit_sketch(T, A, dimension=sk.COLUMNWISE)
                        for A in ops]
                [f.result(timeout=60) for f in futs]
        txt = texp.prometheus_text()
        assert 'skylark_serve_kernel_flushes{backend="pallas"}' in txt
        assert 'skylark_serve_kernel_flushes{backend="xla"}' in txt
        declined = [ln for ln in txt.splitlines()
                    if ln.startswith(
                        "skylark_serve_kernel_declined_flushes{reason=")]
        assert declined and any("float64" in ln for ln in declined)

    def test_zero_recompiles_after_warmup_with_selection(
            self, fresh_engine):
        """The acceptance criterion: selection enabled, every capacity
        class warmed once, then a storm — zero misses, zero
        recompiles."""
        T, ops = self._cwt_reqs(k=16)
        with engine.MicrobatchExecutor(max_batch=8, linger_us=5000,
                                       kernel="pallas") as ex:
            for cap in (1, 2, 4, 8):
                futs = [ex.submit_sketch(T, ops[i],
                                         dimension=sk.COLUMNWISE)
                        for i in range(cap)]
                ex.flush()
                [f.result(timeout=60) for f in futs]
            m0, r0 = engine.stats().misses, engine.stats().recompiles
            for _ in range(3):
                futs = [ex.submit_sketch(T, A, dimension=sk.COLUMNWISE)
                        for A in ops]
                [f.result(timeout=60) for f in futs]
            assert engine.stats().misses == m0
            assert engine.stats().recompiles == r0


# The flush rule as a table: which program serves a flush is the
# executor's ``kernel=`` argument, else ``SKYLARK_SERVE_KERNEL``, else
# XLA; a pallas intent on a family without a batched kernel declines.

_NO_KERNEL = "no-batched-kernel-the-lane-program-serves"


def _family_request(family):
    """(submit, transform, operand) of one tiny request of a bucket
    family."""
    import scipy.sparse as sp

    from libskylark_tpu.base.sparse import SparseMatrix
    from libskylark_tpu.sketch.fjlt import FJLT
    from libskylark_tpu.sketch.frft import FastGaussianRFT

    rng = np.random.default_rng(3)
    ctx = Context(seed=3)

    def dense(shape):
        return rng.standard_normal(shape).astype(np.float32)

    def sketch(dimension):
        return lambda ex, T, A: ex.submit_sketch(T, A, dimension=dimension)

    if family == "jlt_rowwise":
        return sketch(sk.ROWWISE), sk.JLT(128, 16, ctx), dense((8, 128))
    if family == "jlt_columnwise":
        return sketch(sk.COLUMNWISE), sk.JLT(128, 16, ctx), dense((128, 8))
    if family == "cwt":
        return sketch(sk.COLUMNWISE), sk.CWT(40, 16, ctx), dense((40, 3))
    if family == "fastfood":
        return (lambda ex, T, A: ex.submit_fastfood(T, A),
                FastGaussianRFT(512, 512, ctx, sigma=2.0), dense((8, 512)))
    if family == "srht":
        return (sketch(sk.ROWWISE), FJLT(256, 16, ctx, fut="wht"),
                dense((5, 256)))
    assert family == "sparse"
    A = SparseMatrix.from_scipy(sp.random(
        256, 6, density=0.02, random_state=3, dtype=np.float32,
        format="coo"))
    return (lambda ex, T, A: ex.submit_sparse(T, A, dimension=sk.COLUMNWISE),
            sk.CWT(256, 16, ctx), A)


_BATCHED = ("jlt_rowwise", "jlt_columnwise", "cwt", "fastfood")
_LANE_ONLY = ("sparse", "srht")


@pytest.mark.parametrize("family", _BATCHED + _LANE_ONLY)
@pytest.mark.parametrize("pin", ["none", "arg_xla", "arg_pallas",
                                 "env_pallas"])
def test_flush_rule(fresh_engine, monkeypatch, family, pin):
    monkeypatch.delenv("SKYLARK_SERVE_KERNEL", raising=False)
    if pin == "env_pallas":
        monkeypatch.setenv("SKYLARK_SERVE_KERNEL", "pallas")
    kernel = {"arg_xla": "xla", "arg_pallas": "pallas"}.get(pin)
    source = {"none": "default", "env_pallas": "env"}.get(pin, "arg")
    intent = "pallas" if pin.endswith("pallas") else "xla"
    if intent == "pallas" and family in _LANE_ONLY:
        want = ("xla", source, _NO_KERNEL)
    else:
        want = (intent, source, None)
    submit, T, A = _family_request(family)
    with engine.MicrobatchExecutor(max_batch=1, linger_us=100,
                                   kernel=kernel) as ex:
        out = np.asarray(submit(ex, T, A).result(timeout=120))
        st = ex.stats()["kernel"]
        assert list(ex._kernel_memo.values()) == [want]
    assert np.isfinite(out).all()
    assert st["by_backend"] == {want[0]: {"flushes": 1}}
    assert st["by_reason"] == ({want[2]: {"declined_flushes": 1}}
                               if want[2] else {})


def test_the_argument_beats_the_env(fresh_engine, monkeypatch):
    monkeypatch.setenv("SKYLARK_SERVE_KERNEL", "pallas")
    submit, T, A = _family_request("cwt")
    with engine.MicrobatchExecutor(max_batch=1, linger_us=100,
                                   kernel="xla") as ex:
        submit(ex, T, A).result(timeout=60)
        assert list(ex._kernel_memo.values()) == [("xla", "arg", None)]
