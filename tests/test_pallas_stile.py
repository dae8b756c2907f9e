"""The s-tiled rowwise kernels of sketch/pallas_dense.py (interpret mode on
the CPU): where no row tile takes the full-width result the plan tiles s,
each s-tile generating — or streaming from the planes — just its own rows
of the operator. Against the XLA route at every residency, at ragged
widths and rows, and the plan at the benchmark's shapes."""

import jax.numpy as jnp
import numpy as np
import pytest

from libskylark_tpu.base import randgen
from libskylark_tpu.base.context import Context
from libskylark_tpu.sketch import ROWWISE, JLT
from libskylark_tpu.sketch import pallas_dense as pd
from libskylark_tpu.sketch.dense import BLOCK_COLS
from libskylark_tpu.sketch.rft import GaussianRFT, MaternRFT

NORMAL = randgen.Normal()


def _operand(m, n, seed=8):
    return jnp.asarray(
        np.random.default_rng(seed).standard_normal((m, n)), jnp.float32)


def _plan(shape, s, **kw):
    return pd.effective_plan(NORMAL, shape, jnp.float32, s, 1,
                             interpret=True, **kw)


# ---------------------------------------------------------------------------
# the plan, from the shapes alone
# ---------------------------------------------------------------------------


def test_plan_at_the_jlt_apply_shape_is_the_untiled_one():
    """65536 × 8192 → 1024: s fits as it always did — m_tile 512, the
    operator in HBM, one s-tile as wide as the result."""
    p = _plan((65536, 8192), 1024)
    assert (p["m_tile"], p["s_tile"], p["operator_residency"]) == (
        512, 1024, "hbm")
    assert p["plan_id"] == "pallas/mt512/bf16x3"


def test_plan_at_the_feature_cell_shape_tiles_s():
    """32768 × 440 → 16384: no row tile takes a 16384-wide result tile
    (the parent declined); the plan keeps the row tile and tiles s."""
    assert pd._qualify(NORMAL, jnp.zeros((8, 440), jnp.float32), 1, 512,
                       True, 16384) is None
    p = _plan((32768, 440), 16384)
    assert p["kernel"] is True
    assert (p["m_tile"], p["s_tile"], p["operator_residency"]) == (
        512, 1024, "hbm")
    assert p["plan_id"] == "pallas/mt512/st1024/bf16x3"
    # n = 440 pads to 512: the whole contraction is one wide step
    assert pd._plane_step_cols(512, 512, 1024) == 2 * BLOCK_COLS


@pytest.mark.parametrize("s", [4096, 16384, 12288, 5120, 399872])
def test_s_tile_is_the_widest_divisor_the_row_tile_admits(s):
    """The requested row tile stays; the s-tile is the widest multiple of
    128 lanes that divides s_dim inside the same VMEM plan (399,872 is
    the 400,000 features of the TIMIT run, rounded to the lanes)."""
    p = _plan((4096, 512), s)
    fits = [st for st in range(128, s, 128) if s % st == 0
            and pd._vmem_estimate(512, st, 0) <= pd._VMEM_BUDGET_BYTES]
    assert (p["m_tile"], p["s_tile"]) == (512, max(fits))
    assert p["s_tile"] == {4096: 1024, 16384: 1024, 12288: 1536,
                           5120: 1280, 399872: 1408}[s]


def test_the_last_width_a_full_width_tile_takes():
    """s_dim = 3968 still fits untiled (at 8 rows, as in the parent)."""
    p = _plan((4096, 512), 3968)
    assert (p["m_tile"], p["s_tile"]) == (8, 3968)


def test_no_plan_where_s_is_no_multiple_of_the_lanes_or_columnwise():
    assert _plan((4096, 512), 5000)["kernel"] is False
    assert pd.effective_plan(NORMAL, (512, 4096), jnp.float32, 4096, 0,
                             interpret=True)["kernel"] is False
    # the batched serve launcher keeps its full-height block
    ok, why = pd.serve_qualify(NORMAL, 4096, 512, 64, jnp.float32,
                               interpret=True)
    assert not ok and "VMEM" in why


# ---------------------------------------------------------------------------
# the kernels, s-tiled, against the XLA route
# ---------------------------------------------------------------------------


@pytest.fixture
def small_scope(monkeypatch):
    """A VMEM scope so small that stand-in widths tile s as the cell's
    does (s = 1024 at 8 rows: four s-tiles of 256)."""
    monkeypatch.setattr(pd, "_VMEM_BUDGET_BYTES", 1200 * 1024)


def _residency(monkeypatch, residency):
    if residency == "hbm":
        monkeypatch.setattr(pd, "_SCRATCH_CAP_BYTES", 0)
    elif residency == "vmem":
        monkeypatch.setattr(pd, "_SCRATCH_CAP_BYTES", 1 << 30)
        monkeypatch.setattr(
            pd, "_vmem_estimate",
            lambda m_tile, s_tile, scratch: 4 * (
                2 * m_tile * BLOCK_COLS + 3 * m_tile * s_tile
                + 4 * s_tile * BLOCK_COLS))


@pytest.mark.parametrize("residency,m_tile", [
    ("hbm", 8), ("per_tile", 64), ("vmem", 8)])
@pytest.mark.parametrize("shape,s", [
    ((24, 512), 512), ((13, 440), 1024), ((40, 300), 768)],
    ids=["aligned", "ragged_440", "ragged_300"])
def test_s_tiled_cos_kernel_equals_the_xla_program(shape, s, residency,
                                                   m_tile, small_scope,
                                                   monkeypatch):
    _residency(monkeypatch, residency)
    m, n = shape
    T = GaussianRFT(n, s, Context(seed=14), sigma=3.0)
    A = _operand(m, n)
    want = np.asarray(T.apply(A, ROWWISE))      # the XLA program
    p = _plan(shape, s, m_tile=m_tile)
    assert p["s_tile"] < s and p["operator_residency"] == residency
    for precision in ("bf16x3", "f32"):
        got = pd.rft_rowwise_apply(
            T.subkey(0), T.dist, A, s, T.inscale, T.outscale,
            np.asarray(T.row_scales()), np.asarray(T.shifts()),
            m_tile=m_tile, precision=precision, interpret=True)
        assert got.shape == (m, s)
        np.testing.assert_allclose(np.asarray(got), want, atol=1e-4,
                                   rtol=1e-4)


@pytest.mark.parametrize("residency,m_tile", [
    ("hbm", 8), ("per_tile", 64), ("vmem", 8)])
def test_fused_cosine_against_a_float64_map(residency, m_tile, small_scope,
                                            monkeypatch):
    """The epilogue where the contraction's 1e-4 cannot hide it: the
    "f32" regime against the whole map in float64 — W, b and the scales
    as the stream defines them — on the feature cell's kind of phases
    (σ = √(2n): a projection of deviation 0.7 rad, shifts in [0, 2π)).
    What is left is the rounding of the phase itself, ≈ |t|·2⁻²⁴ turns."""
    _residency(monkeypatch, residency)
    m, n, s = 40, 440, 1024
    T = GaussianRFT(n, s, Context(seed=38), sigma=(2.0 * n) ** 0.5)
    A = _operand(m, n, seed=6)
    p = _plan((m, n), s, m_tile=m_tile)
    assert p["s_tile"] < s and p["operator_residency"] == residency
    got = pd.rft_rowwise_apply(
        T.subkey(0), T.dist, A, s, T.inscale, T.outscale,
        np.asarray(T.row_scales()), np.asarray(T.shifts()),
        m_tile=m_tile, precision="f32", interpret=True)
    W = np.asarray(T.w_panel(0, n), np.float64)         # inscale folded in
    projection = np.asarray(A, np.float64) @ W.T
    assert 0.6 < projection.std() < 0.8
    want = np.cos(projection + np.asarray(T.shifts(), np.float64))
    err = np.abs(np.asarray(got, np.float64) / T.outscale - want)
    assert err.max() <= 2e-6


@pytest.mark.parametrize("residency,m_tile", [("hbm", 8), ("per_tile", 64)])
def test_s_tiled_projection_is_bit_equal_to_the_untiled(residency, m_tile,
                                                        monkeypatch):
    """The same apply, s tiled and not: an s-tile holds the same operator
    rows and every result cell the same products in the same order."""
    _residency(monkeypatch, residency)
    # one contraction step width under both scopes: the same sums
    monkeypatch.setattr(pd, "_plane_step_cols", lambda *a: BLOCK_COLS)
    m, n, s = 24, 440, 512
    jlt = JLT(n, s, Context(seed=10))
    A = _operand(m, n, seed=5)
    args = (jlt._alloc.key, jlt.dist, A, s, jlt.scale)
    kw = dict(m_tile=m_tile, interpret=True)
    untiled = np.asarray(pd.rowwise_apply(*args, **kw))
    assert _plan((m, n), s, m_tile=m_tile)["s_tile"] == s
    monkeypatch.setattr(pd, "_VMEM_BUDGET_BYTES", 1200 * 1024)
    assert _plan((m, n), s, m_tile=m_tile)["s_tile"] == 256
    tiled = np.asarray(pd.rowwise_apply(*args, **kw))
    np.testing.assert_array_equal(tiled, untiled)


@pytest.mark.parametrize("precision", ["bf16x3", "f32", "bf16gen2"])
def test_planes_generated_by_s_tiles_are_the_untiled_planes(precision):
    s, n_blocks = 384, 2
    keys = pd._block_keys(Context(seed=32).allocate().key,
                          n_blocks * BLOCK_COLS)
    kw = dict(s_dim=s, dist_kind="normal", precision=precision,
              interpret=True)
    whole = pd._operator_planes(keys, 0.25, **kw)
    tiled = pd._operator_planes(keys, 0.25, s_tile=128, **kw)
    for a, b in zip(whole, tiled):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a.astype(jnp.float32)),
                                      np.asarray(b.astype(jnp.float32)))


@pytest.mark.parametrize("s", [1024, 4096, 16384])
def test_real_widths_at_a_few_rows(s, monkeypatch):
    """S ∈ {1024 (untiled), 4096, 16384} under the real VMEM scope, 440
    columns, ragged rows, Matern's per-feature scales in the epilogue."""
    monkeypatch.setattr(pd, "_SCRATCH_CAP_BYTES", 0)
    m, n = 20, 440
    T = MaternRFT(n, s, Context(seed=3), nu=1.5, l=20.0)
    A = _operand(m, n, seed=2)
    p = _plan((m, n), s, m_tile=8)
    assert p["s_tile"] == (s if s == 1024 else 2048)
    assert p["operator_residency"] == "hbm"
    got = pd.rft_rowwise_apply(
        T.subkey(0), T.dist, A, s, T.inscale, T.outscale,
        np.asarray(T.row_scales()), np.asarray(T.shifts()),
        m_tile=8, precision="f32", interpret=True)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(T.apply(A, ROWWISE)),
                               atol=1e-4, rtol=1e-4)
