"""``sketch/cos_turns.py`` against float64 ``cos(2πt)``: the error contract
(3e-7 for every finite float32 phase in turns), the bound, the special
values — for the function as XLA runs it here and for a numpy float32
model of the same operations with every product and sum rounded on its
own, which is the arithmetic of a chip whose vector unit has no fused
multiply-add (XLA's CPU backend contracts ``p·u + c``)."""

import importlib.util
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from libskylark_tpu.sketch.cos_turns import _SIN_TURNS, TURN, cos_turns

CONTRACT = 3e-7
ROOT = pathlib.Path(__file__).resolve().parents[1]


def model(t, outscale=1.0):
    """:func:`cos_turns` in numpy float32, operation by operation."""
    t = np.asarray(t, np.float32)
    with np.errstate(invalid="ignore"):
        r = t - np.rint(t)
    w = np.float32(0.25) - np.abs(r)
    u = w * w
    coef = [np.float32(outscale * c) for c in _SIN_TURNS]
    p = np.full_like(u, coef[-1])
    for c in coef[-2::-1]:
        p = p * u + c
    return np.clip(w * p, np.float32(-outscale), np.float32(outscale))


def compiled(t, outscale=1.0):
    return np.asarray(jax.jit(lambda x: cos_turns(x, outscale))(
        jnp.asarray(t, jnp.float32)))


def _rng():
    return np.random.default_rng(38)


def _dense_grid():
    return np.linspace(-2.0, 2.0, 2_000_001)


def _feature_phases():
    """The cell's kind: a normal projection of deviation 0.7 rad plus a
    shift in [0, 2π), in turns."""
    rng = _rng()
    return (0.7 * rng.standard_normal(1_000_000)
            + rng.uniform(0, TURN, 1_000_000)) / TURN


def _to_2_23():
    return _rng().uniform(-2.0 ** 23, 2.0 ** 23, 1_000_000)


def _log_uniform_to_1e30():
    rng = _rng()
    return rng.choice([-1.0, 1.0], 500_000) * np.exp(
        rng.uniform(np.log(1e-30), np.log(1e30), 500_000))


def _near_quarter_points():
    """Every float32 within 2048 ulps of a multiple of a quarter turn up
    to ±4 turns: the zero crossings, the peaks and the fold's seams."""
    centres = np.arange(-16, 17, dtype=np.float32) * np.float32(0.25)
    steps = np.arange(-2048, 2049)
    bits = centres.view(np.int32)[:, None] + steps[None, :]
    near_zero = np.concatenate([np.arange(0, 4096, dtype=np.int32),
                                np.arange(0, 4096, dtype=np.int32)
                                | np.int32(-2 ** 31)])
    return np.concatenate([bits[centres != 0].ravel().astype(np.int32),
                           near_zero]).view(np.float32)


RANGES = {"dense_grid_pm2": _dense_grid, "feature_phases": _feature_phases,
          "uniform_to_2_23": _to_2_23, "log_uniform_to_1e30":
          _log_uniform_to_1e30, "near_quarter_points": _near_quarter_points}


@pytest.mark.parametrize("evaluate", [compiled, model],
                         ids=["xla", "no_fma_model"])
@pytest.mark.parametrize("name", list(RANGES))
def test_error_contract_against_float64(name, evaluate):
    t = RANGES[name]().astype(np.float32)
    want = np.cos(TURN * (t.astype(np.float64) % 1.0))
    got = evaluate(t).astype(np.float64)
    assert np.abs(got - want).max() <= CONTRACT
    assert np.abs(got).max() <= 1.0


@pytest.mark.parametrize("evaluate", [compiled, model],
                         ids=["xla", "no_fma_model"])
@pytest.mark.parametrize("outscale", [
    1.0, 0.25, (2.0 / 16384) ** 0.5, (2.0 / 1000) ** 0.5, 0.3, 1.7])
def test_outscale_is_folded_in_and_bounds_the_result(outscale, evaluate):
    """|result| ≤ float32(outscale) on every float32 phase whose fold
    lands within 2e-3 of a peak (where Horner's rule can overshoot), and
    the error, in units of outscale, on the cell's kind of phases."""
    lo, hi = (np.float32(x).view(np.int32) for x in (0.25 - 2e-3, 0.25))
    w = np.arange(lo, hi + 1, dtype=np.int32).view(np.float32)
    quarter = np.float32(0.25)
    # ¼ − |r| = w exactly: towards the +peak (r → 0) and the −peak (|r| → ½)
    peaks = np.concatenate([quarter - w, w - quarter, w + quarter,
                            -(w + quarter), 3.0 + (quarter - w)])
    got = evaluate(peaks, outscale)
    assert np.abs(got).max() <= np.float32(outscale)
    # a whole turn: outscale itself, to the rounding of the coefficients
    assert outscale - got[np.flatnonzero(peaks == 0.0)[0]] <= 3e-7 * outscale
    t = _feature_phases().astype(np.float32)
    want = outscale * np.cos(TURN * (t.astype(np.float64) % 1.0))
    err = np.abs(evaluate(t, outscale).astype(np.float64) - want).max()
    assert err <= (CONTRACT if outscale in (1.0, 0.25) else 4e-7) * outscale


@pytest.mark.parametrize("evaluate", [compiled, model],
                         ids=["xla", "no_fma_model"])
def test_special_values(evaluate):
    ints = np.array([0.0, -0.0, 1.0, -3.0, 7.0, 2.0 ** 23, -(2.0 ** 23) - 1,
                     2.0 ** 24 + 2, 1e30, -3.4e38], np.float32)
    np.testing.assert_array_equal(evaluate(ints), np.ones_like(ints))
    halves = np.array([0.5, -0.5, 1.5, -2.5, 2.0 ** 22 + 0.5], np.float32)
    np.testing.assert_array_equal(evaluate(halves), -np.ones_like(halves))
    quarters = np.array([0.25, -0.25, 0.75, 1.25, -5.75], np.float32)
    np.testing.assert_array_equal(evaluate(quarters), np.zeros_like(quarters))
    with np.errstate(invalid="ignore"):
        assert np.isnan(evaluate(
            np.array([np.inf, -np.inf, np.nan], np.float32))).all()
    tiny = np.array([1e-30, -1e-38, 1e-45], np.float32)    # a denormal too
    np.testing.assert_array_equal(evaluate(tiny), np.ones_like(tiny))


def test_the_two_arithmetics_agree_to_rounding():
    t = _feature_phases().astype(np.float32)
    assert np.abs(compiled(t).astype(np.float64) - model(t)).max() <= 2.5e-7


def test_coefficients_are_the_recipes():
    spec = importlib.util.spec_from_file_location(
        "fit_cos_turns", ROOT / "script" / "fit_cos_turns.py")
    recipe = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(recipe)
    coefficients, minimax = recipe.fit()
    assert tuple(coefficients) == _SIN_TURNS
    assert all(np.float32(c) == c for c in _SIN_TURNS)
    assert minimax < 5e-9


def test_no_cos_primitive_and_traceable_in_a_kernel_dtype():
    jaxpr = jax.make_jaxpr(lambda x: cos_turns(x, 0.5))(
        jnp.zeros((8, 128), jnp.float32))
    names = {eqn.primitive.name for eqn in jaxpr.eqns}
    assert "cos" not in names and "sin" not in names
    assert jaxpr.out_avals[0].dtype == jnp.float32
