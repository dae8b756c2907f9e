"""Automatic materialize-and-reuse dispatch (OperatorCache +
sketch/params auto_materialize knobs).

The virtual-operator default pays generation per apply — right for
one-shot sketches; steady-state reuse (serving predict paths, eager
solver loops) should amortize it to zero WITHOUT a manual
``materialize()`` call. The dispatch must never fire under a jit trace
(it would pin a tracer), never exceed its byte budget, and — on the XLA
path — change nothing numerically (the materialized apply is the same
contraction as the unblocked virtual one)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from libskylark_tpu.base.context import Context
from libskylark_tpu.sketch import JLT, ROWWISE
from libskylark_tpu.sketch import params as sketch_params
from libskylark_tpu.sketch.qrft import GaussianQRFT
from libskylark_tpu.sketch.rft import GaussianRFT


@pytest.fixture(autouse=True)
def _restore_params():
    prev = (sketch_params.get_auto_materialize(),
            sketch_params.get_auto_materialize_after(),
            sketch_params.get_auto_materialize_bytes())
    yield
    sketch_params.set_auto_materialize(prev[0])
    sketch_params.set_auto_materialize_after(prev[1])
    sketch_params.set_auto_materialize_bytes(prev[2])


@pytest.fixture
def A():
    rng = np.random.default_rng(0)
    return jnp.asarray(rng.standard_normal((32, 256)), jnp.float32)


def test_nth_eager_apply_pins_and_preserves_results(A):
    sketch_params.set_auto_materialize_after(3)
    T = JLT(256, 16, Context(seed=1))
    fresh = np.asarray(JLT(256, 16, Context(seed=1)).apply(A, ROWWISE))
    outs = [np.asarray(T.apply(A, ROWWISE)) for _ in range(4)]
    assert T._op_cache is not None          # pinned on the 3rd apply
    for o in outs:
        # XLA path: materialized apply is the SAME contraction — exact
        np.testing.assert_array_equal(o, fresh)


def test_jit_traced_applies_never_count(A):
    sketch_params.set_auto_materialize_after(1)
    T = JLT(256, 16, Context(seed=1))
    f = jax.jit(lambda X: T.apply(X, ROWWISE))
    for _ in range(4):
        f(A).block_until_ready()
    assert T._op_cache is None


def test_budget_respected(A):
    sketch_params.set_auto_materialize_after(1)
    sketch_params.set_auto_materialize_bytes(16 * 256 * 4 - 1)  # 1 short
    T = JLT(256, 16, Context(seed=1))
    T.apply(A, ROWWISE)
    T.apply(A, ROWWISE)
    assert T._op_cache is None


def test_disable_flag(A):
    sketch_params.set_auto_materialize(False)
    sketch_params.set_auto_materialize_after(1)
    T = JLT(256, 16, Context(seed=1))
    for _ in range(3):
        T.apply(A, ROWWISE)
    assert T._op_cache is None


def test_dematerialize_resets_dispatch(A):
    sketch_params.set_auto_materialize_after(2)
    T = JLT(256, 16, Context(seed=1))
    T.apply(A, ROWWISE)
    T.apply(A, ROWWISE)
    assert T._op_cache is not None
    T.dematerialize()
    assert T._op_cache is None
    T.apply(A, ROWWISE)                      # count restarted: 1 < 2
    assert T._op_cache is None


@pytest.mark.parametrize("make", [
    lambda: GaussianRFT(256, 24, Context(seed=2), sigma=2.0),
    lambda: GaussianQRFT(256, 24, Context(seed=2), sigma=2.0),
])
def test_feature_maps_auto_pin_within_oracle(A, make):
    sketch_params.set_auto_materialize_after(2)
    T = make()
    fresh = np.asarray(make().apply(A, ROWWISE))
    for _ in range(3):
        out = np.asarray(T.apply(A, ROWWISE))
    assert T._op_cache is not None
    np.testing.assert_allclose(out, fresh, atol=1e-4, rtol=1e-4)


def test_no_auto_pin_on_fused_kernel_path(A, monkeypatch):
    """When the eager apply routes through the fused Pallas kernel,
    auto-materialize must NOT fire: pinning would silently switch the
    Nth apply from bf16x3 kernel numerics to a full-precision cached
    gemm — a cross-call reproducibility break (r3 advisor, medium).
    Simulated off-chip by forcing the veto predicate on (the real
    kernel cannot compile on CPU); what's under test is the dispatch
    wiring: would-serve -> never auto-pin."""
    from libskylark_tpu.sketch import dense as dense_mod

    monkeypatch.setattr(dense_mod, "pallas_serves_eager",
                        lambda *a: True)
    sketch_params.set_auto_materialize_after(1)
    T = JLT(256, 16, Context(seed=1))
    for _ in range(3):
        T.apply(A, ROWWISE)
    assert T._op_cache is None  # veto: no silent regime switch
    # explicit materialize() remains the visible opt-in
    T.materialize()
    assert T._op_cache is not None

    # RFT shares the veto through the same dispatch
    R = GaussianRFT(256, 24, Context(seed=2), sigma=2.0)
    for _ in range(3):
        R.apply(A, ROWWISE)
    assert R._op_cache is None


def test_unsupported_kernel_inputs_still_auto_pin(A, monkeypatch):
    """pallas_serves_eager mirrors the kernel's own qualification: an
    apply the kernel would DECLINE (f64 input — supported() is
    f32-only) runs the plain XLA contraction, so auto-materialize must
    keep amortizing it even in a pallas-ambient context (review
    finding: the veto must not permanently disable amortization for
    XLA-path applies on TPU)."""
    from libskylark_tpu.sketch import dense as dense_mod
    from libskylark_tpu.sketch import pallas_dense

    monkeypatch.setattr(pallas_dense, "available", lambda: True)
    monkeypatch.setattr(dense_mod, "pallas_ambient_ok", lambda A: True)
    sketch_params.set_auto_materialize_after(2)
    T = JLT(256, 16, Context(seed=1))
    Ab = A.astype(jnp.bfloat16)  # supported() is f32-only -> XLA path
    assert not dense_mod.pallas_serves_eager(Ab, T.dist, 16, 1)
    T.apply(Ab, ROWWISE)
    T.apply(Ab, ROWWISE)
    assert T._op_cache is not None  # amortization kept

    # VMEM/tile decline (review finding): an f32 apply whose s_dim
    # exceeds every valid tile's VMEM budget falls back to XLA too —
    # the veto must mirror that via effective_plan, not just supported().
    # Columnwise, where the result tile keeps its full height; a rowwise
    # apply tiles s instead, unless s_dim is no multiple of 128 lanes
    assert not dense_mod.pallas_serves_eager(A.T, T.dist, 1 << 16, 0)
    assert not dense_mod.pallas_serves_eager(A, T.dist, (1 << 16) + 8, 1)
    assert dense_mod.pallas_serves_eager(A, T.dist, 1 << 16, 1)
    # while a plannable config (small s_dim) IS vetoed
    assert dense_mod.pallas_serves_eager(A, T.dist, 16, 1)


def test_wider_dtype_request_repins(A):
    """A narrow pin must not permanently block amortization for wider
    dtypes: _cached_op refuses to upcast, so wide applies keep counting
    and re-pin at the wider dtype."""
    sketch_params.set_auto_materialize_after(2)
    T = JLT(256, 16, Context(seed=1))
    Ab = A.astype(jnp.bfloat16)
    T.apply(Ab, ROWWISE)
    T.apply(Ab, ROWWISE)
    assert T._op_cache is not None and T._op_cache.dtype == jnp.bfloat16
    T.apply(A, ROWWISE)                      # f32: wider, counts anew
    assert T._op_cache.dtype == jnp.float32  # re-pinned wider


def test_expsemigroup_qrlt_auto_pins(A):
    from libskylark_tpu.sketch.qrft import ExpSemigroupQRLT

    sketch_params.set_auto_materialize_after(2)
    Apos = jnp.abs(A)  # semigroup kernels take nonnegative inputs
    T = ExpSemigroupQRLT(256, 24, Context(seed=2), beta=0.5)
    fresh = np.asarray(
        ExpSemigroupQRLT(256, 24, Context(seed=2), beta=0.5).apply(
            Apos, ROWWISE))
    for _ in range(3):
        out = np.asarray(T.apply(Apos, ROWWISE))
    assert T._op_cache is not None
    np.testing.assert_allclose(out, fresh, atol=1e-4, rtol=1e-4)
