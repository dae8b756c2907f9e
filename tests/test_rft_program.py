"""A dense random-feature apply is ONE compiled program
(``sketch.rft_features``): frequencies, scales and shifts from the key, the
projection and the featurization — on the XLA route (the CPU, Cauchy
frequencies, a pinned W) and, interpreted here, on the fused kernels'. Held
to the benchmark's plain reference, to the exact Gaussian gram, to the eager
composition, and to its spans and counter."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cellbench.references import rft_features as reference
from libskylark_tpu import engine, telemetry
from libskylark_tpu import sketch as sk
from libskylark_tpu.base.context import Context
from libskylark_tpu.ml import kernels
from libskylark_tpu.sketch import pallas_dense, rft
from libskylark_tpu.telemetry import metrics, trace

N, S, SIGMA, SEED = 440, 512, 30.0, 21
FAMILIES = [(sk.GaussianRFT, {"sigma": 3.0}), (sk.LaplacianRFT, {"sigma": 40.0}),
            (sk.MaternRFT, {"nu": 1.5, "l": 4.0}),
            (sk.ExpSemigroupRLT, {"beta": 0.5})]


def examples(rows=48, n=N, seed=3):
    return jnp.asarray(
        np.random.default_rng(seed).standard_normal((rows, n)), jnp.float32)


@pytest.fixture
def fresh():
    engine.reset()
    before = metrics._ENABLED
    trace.clear_finished()
    yield
    metrics._ENABLED = before
    trace.clear_finished()
    engine.reset()


@pytest.fixture
def interpreted(monkeypatch):
    """The fused kernels serve, interpreted: what a TPU's dispatch plans."""
    plan = rft.RFT._kernel_plan
    monkeypatch.setattr(rft.RFT, "_kernel_plan",
                        lambda self, A, interpret=False: plan(self, A, True))


def eager(T, A, rowwise=True):
    """The op-by-op composition the program replaced."""
    W = T.w_panel(0, T.input_dim, A.dtype)
    return T._featurize(A @ W.T if rowwise else W @ A,
                        feature_axis=1 if rowwise else 0)


# ---------------------------------------------------------------------------
# the values
# ---------------------------------------------------------------------------


def test_gaussian_features_equal_the_plain_reference():
    """``Gaussian(440, σ).create_rft(s, ctx, "regular").apply(X, ROWWISE)``
    — the call Block-ADMM and KRR make — against W, b and the map rebuilt
    from the stream's published definition (ragged n = 440 included)."""
    T = kernels.Gaussian(N, SIGMA).create_rft(S, Context(SEED), "regular")
    X = examples()
    W = reference.frequencies(SEED, 0, S, N)
    b = reference.shifts(SEED, 0, S)
    np.testing.assert_array_equal(np.asarray(T.shifts()), np.asarray(b))
    np.testing.assert_allclose(np.asarray(T.w_panel(0, N)) * SIGMA,
                               np.asarray(W), rtol=1e-6, atol=1e-7)
    got = np.asarray(T.apply(X, sk.ROWWISE))
    want = np.asarray(reference.features(X, W, b, SIGMA))
    assert np.abs(got - want).max() / T.outscale < 1e-5


def test_inner_products_estimate_the_exact_gaussian_gram():
    s = 8192
    T = sk.GaussianRFT(N, s, Context(SEED), sigma=SIGMA)
    X = examples(rows=64)
    Z = np.asarray(T.apply(X, sk.ROWWISE), np.float64)
    K = np.asarray(reference.gaussian_kernel(X, SIGMA), np.float64)
    np.testing.assert_allclose(np.diag(K), 1.0, atol=1e-6)
    assert 0.3 < np.median(K) < 0.8                     # nothing degenerate
    z = np.abs(Z @ Z.T - K) / np.sqrt((1.0 + 0.5 * K ** 4 - K * K) / s)
    assert z.max() < 5.0
    np.testing.assert_allclose(
        K, np.asarray(kernels.Gaussian(N, SIGMA).gram(X)), atol=2e-5)


@pytest.mark.parametrize("family,kw", FAMILIES)
@pytest.mark.parametrize("dimension", [sk.ROWWISE, sk.COLUMNWISE])
def test_the_program_holds_the_eager_composition(fresh, family, kw, dimension):
    T = family(N, S, Context(SEED), **kw)
    rowwise = dimension == sk.ROWWISE
    A = jnp.abs(examples()) if family is sk.ExpSemigroupRLT else examples()
    A = A if rowwise else A.T
    got = np.asarray(T.apply(A, dimension))
    want = np.asarray(eager(T, A, rowwise))
    if family is sk.ExpSemigroupRLT:        # no scale to move: bit for bit
        np.testing.assert_array_equal(got, want)
    else:
        # Cauchy phases are heavy-tailed: a last-ulp change of the
        # projection is a visible change of a cos (sketch/rft.py)
        tol = 1e-4 if family is not sk.LaplacianRFT else 1e-2
        np.testing.assert_allclose(got, want, atol=tol * T.outscale)
    under_jit = jax.jit(lambda a: T.apply(a, dimension))(A)
    np.testing.assert_allclose(np.asarray(under_jit), got,
                               atol=1e-5 * T.outscale)


# ---------------------------------------------------------------------------
# one program: one compile, one dispatch
# ---------------------------------------------------------------------------


def test_off_the_kernel_one_compile_one_dispatch(fresh):
    T = sk.GaussianRFT(N, S, Context(SEED), sigma=SIGMA)
    X = examples()
    program = rft._features_program()
    ran = program.stats.executions
    first = np.asarray(T.apply(X, sk.ROWWISE))
    assert engine.stats().compiles == 1
    # a second map of the family shares the executable: the key is an
    # argument of the program, not a constant of it
    T2 = sk.GaussianRFT(N, S, Context(SEED + 1), sigma=SIGMA)
    other = np.asarray(T2.apply(X, sk.ROWWISE))
    second = np.asarray(T.apply(X, sk.ROWWISE))
    assert engine.stats().compiles == 1
    assert np.array_equal(first, second) and not np.array_equal(first, other)
    assert program.name == "sketch.rft_features"
    assert program.stats.executions - ran == 3

    telemetry.set_enabled(True)
    sk.GaussianRFT(N, S, Context(SEED + 2), sigma=SIGMA).apply(
        X, sk.ROWWISE).block_until_ready()
    spans = trace.finished_spans()
    root = next(s for s in spans if s.name == "sketch.apply")
    kids = [s for s in spans if s.parent_id == root.span_id]
    # the operand is made an array and checked, the auto-materialize
    # dispatch decides, the plan is asked for (and declines off the TPU),
    # the key derived, and the one enqueue is the engine's call under the
    # dispatch span
    assert [s.name for s in kids] == [
        "sketch.operand", "sketch.materialize", "sketch.plan", "stream.key",
        "sketch.dispatch"]
    assert kids[3].attrs["what"] == "allocation"
    assert isinstance(kids[3].attrs["cached"], bool)
    dispatch = kids[4]
    calls = [s for s in spans if s.name == "engine.call"]
    assert len(calls) == 1 and calls[0].parent_id == dispatch.span_id
    assert calls[0].attrs["name"] == "sketch.rft_features"
    assert calls[0].attrs["hit"] is True
    assert root.attrs["path"] == "xla_full"


def test_a_pinned_operator_is_an_argument_of_the_same_program(fresh):
    T = sk.GaussianRFT(N, S, Context(SEED), sigma=SIGMA)
    X = examples()
    ran = rft._features_program().stats.executions
    virtual = np.asarray(T.apply(X, sk.ROWWISE))
    T.materialize()
    pinned = np.asarray(T.apply(X, sk.ROWWISE))
    np.testing.assert_allclose(pinned, virtual, atol=1e-5 * T.outscale)
    assert rft._features_program().stats.executions - ran == 2
    assert engine.stats().compiles == 2     # (key, X) and (key, X, W)


def test_ragged_width_is_padded_inside_the_program(interpreted):
    """n = 440 reaches the program unpadded: the pad to 512 columns is an
    op of the one executable, not a dispatch of its own an apply."""
    T = sk.GaussianRFT(N, S, Context(SEED), sigma=SIGMA)
    X = examples(rows=16)
    plan = T._kernel_plan(X)
    jaxpr = jax.make_jaxpr(
        lambda k, a: rft.rft_features(
            k, a, spec=("GaussianRFT", N, S, (("sigma", SIGMA),)),
            rowwise=True, plan=plan))(
        jax.random.key_data(T.allocation.key), X)
    assert jaxpr.in_avals[1].shape == (16, N)
    assert _count(jaxpr.jaxpr, "pad") == 1
    assert _count(jaxpr.jaxpr, "pallas_call") >= 1


@pytest.mark.parametrize("m_tile,calls", [(64, 1), (8, 2)],
                         ids=["per_tile", "hbm"])
def test_only_the_xla_route_holds_the_stock_cosine(interpreted, monkeypatch,
                                                   m_tile, calls):
    """The kernel route's whole program — kernels included — has no
    ``cos`` primitive: its epilogue is sketch/cos_turns.py. The XLA route
    of the same program keeps ``jnp.cos``."""
    from libskylark_tpu.sketch import params as sketch_params

    monkeypatch.setattr(pallas_dense, "_SCRATCH_CAP_BYTES", 0)
    monkeypatch.setattr(sketch_params, "_pallas_m_tile", m_tile)
    T = sk.GaussianRFT(N, S, Context(SEED), sigma=SIGMA)
    X = examples()
    plan = T._kernel_plan(X)
    assert plan.operator_residency == ("hbm" if calls == 2 else "per_tile")

    def program(plan):
        return jax.make_jaxpr(
            lambda k, a: rft.rft_features(
                k, a, spec=("GaussianRFT", N, S, (("sigma", SIGMA),)),
                rowwise=True, plan=plan))(T.allocation.key_data, X).jaxpr

    kernels, xla = program(plan), program(None)
    assert _count(kernels, "pallas_call") == calls
    assert _count(kernels, "cos") == 0 and _count(kernels, "round") >= 1
    assert _count(xla, "cos") == 1 and _count(xla, "pallas_call") == 0


def _count(jaxpr, primitive: str) -> int:
    found = 0
    for eqn in jaxpr.eqns:
        found += eqn.primitive.name == primitive
        for inner in jax.core.jaxprs_in_params(eqn.params):
            found += _count(inner, primitive)
    return found


# ---------------------------------------------------------------------------
# the kernel route through apply, its spans and its counter
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("scope,residency,kernel", [
    (None, "per_tile", "pallas_generate"),
    (1200 * 1024, "hbm", "pallas_planes")])
def test_kernel_route_spans_and_counter(fresh, interpreted, monkeypatch, scope,
                                        residency, kernel):
    from libskylark_tpu.sketch import params as sketch_params

    s = 1024
    if scope is not None:       # the cell's plan at a stand-in's size
        monkeypatch.setattr(pallas_dense, "_VMEM_BUDGET_BYTES", scope)
        monkeypatch.setattr(pallas_dense, "_SCRATCH_CAP_BYTES", 0)
        monkeypatch.setattr(sketch_params, "_pallas_m_tile", 8)
    T = sk.GaussianRFT(N, s, Context(SEED), sigma=SIGMA)
    X = examples(rows=40)
    counter = metrics.registry().counter("sketch.features")
    before = counter.value(family="GaussianRFT", kernel=kernel)
    telemetry.set_enabled(True)
    got = np.asarray(T.apply(X, sk.ROWWISE))
    spans = trace.finished_spans()
    telemetry.set_enabled(False)
    np.testing.assert_allclose(got, np.asarray(eager(T, X)),
                               atol=1e-4 * T.outscale)
    root = next(s_ for s_ in spans if s_.name == "sketch.apply")
    kids = [s_ for s_ in spans if s_.parent_id == root.span_id]
    assert [s_.name for s_ in kids] == [
        "sketch.operand", "sketch.materialize", "sketch.plan", "stream.key",
        "sketch.dispatch"]
    # one key access an apply: the block-key table is the program's
    assert kids[3].attrs["what"] == "allocation"
    dispatch = kids[4].attrs
    assert dispatch["path"] == "features" and dispatch["epilogue"] == "cos"
    assert dispatch["finisher"] == "cos_turns"
    assert dispatch["family"] == "GaussianRFT" and dispatch["kernel"] == kernel
    assert dispatch["features"] == 40 * s
    assert dispatch["operator_residency"] == residency
    assert dispatch["m_tile"] == (8 if scope else 40)
    assert dispatch["s_tile"] == (256 if scope else s)
    assert root.attrs["path"] == "pallas"
    assert root.attrs["s_tile"] == dispatch["s_tile"]
    assert counter.value(family="GaussianRFT", kernel=kernel) - before == 40 * s
    # the plan is the one effective_plan reports
    plan = pallas_dense.effective_plan(T.dist, X.shape, X.dtype, s, 1,
                                       interpret=True)
    assert (plan["m_tile"], plan["s_tile"], plan["operator_residency"]) == (
        dispatch["m_tile"], dispatch["s_tile"], residency)


@pytest.mark.parametrize("family,kw,epilogue", [
    (sk.LaplacianRFT, {"sigma": 40.0}, "cos"),
    (sk.ExpSemigroupRLT, {"beta": 0.5}, "exp")])
def test_other_frequencies_keep_the_xla_route(fresh, interpreted, family, kw,
                                              epilogue):
    """Cauchy and Lévy frequencies never plan a kernel, whatever the
    backend; the span and the counter say ``xla``."""
    T = family(N, S, Context(SEED), **kw)
    X = jnp.abs(examples())
    counter = metrics.registry().counter("sketch.features")
    before = counter.value(family=T.sketch_type, kernel="xla")
    telemetry.set_enabled(True)
    T.apply(X, sk.ROWWISE).block_until_ready()
    dispatch = next(s for s in trace.finished_spans()
                    if s.name == "sketch.dispatch").attrs
    assert dispatch == {"path": "features", "family": T.sketch_type,
                        "epilogue": epilogue, "finisher": epilogue,
                        "kernel": "xla",
                        "features": X.shape[0] * S}
    assert counter.value(family=T.sketch_type,
                         kernel="xla") - before == X.shape[0] * S


def test_columnwise_and_pinned_applies_do_not_plan_a_kernel(interpreted):
    T = sk.GaussianRFT(N, S, Context(SEED), sigma=SIGMA)
    X = examples()
    assert T._kernel_plan(X) is not None
    assert not T._materialize_changes_numerics(X.T, seq_axis=0)
    telemetry.set_enabled(True)
    try:
        T.apply(X.T, sk.COLUMNWISE).block_until_ready()
        T.materialize()
        T.apply(X, sk.ROWWISE).block_until_ready()
        kernels_seen = [(s.attrs["kernel"], s.attrs["finisher"])
                        for s in trace.finished_spans()
                        if s.name == "sketch.dispatch"]
    finally:
        telemetry.set_enabled(False)
        trace.clear_finished()
    assert kernels_seen == [("xla", "cos"), ("xla", "cos")]
