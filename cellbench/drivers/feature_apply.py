"""Driver for one kind of operation: a rowwise dense random-feature apply,
``kernel.create_rft(s, context, "regular").apply(X, ROWWISE)`` — the call
Block-ADMM and KRR make for every feature block — on device-resident panels
of examples.

Set-up builds the kernel's feature map from the seed and the panels on the
device; a step is one blocking apply on the next panel; the check holds
sampled rows of the last result of every panel to the plain reference, the
whole result to its norm, and the sampled rows' inner products to the
kernel the map approximates.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from cellbench import seeds
from cellbench.drivers import lower_precision
from cellbench.references import rft_features as reference

SHIFT_BINS = 64


@dataclasses.dataclass
class State:
    config: dict
    seed: int
    context_seed: int
    transform: object
    panels: list
    rowwise: object


@functools.partial(jax.jit, static_argnames=("rows", "n"))
def _panel(key, i, *, rows: int, n: int):
    return jax.random.normal(jax.random.fold_in(key, i), (rows, n), jnp.float32)


def setup(config: dict, traffic: dict, seed: int) -> State:
    from libskylark_tpu import sketch as sk
    from libskylark_tpu.base.context import Context
    from libskylark_tpu.ml import kernels

    if config["family"] != "GaussianRFT":
        raise ValueError(f"feature_apply drives GaussianRFT, got {config['family']!r}")
    context_seed = seeds.context_seed(seed)
    transform = kernels.Gaussian(config["n"], config["sigma"]).create_rft(
        config["s"], Context(context_seed), config["tag"])
    key = seeds.data_key(seed, "examples")
    panels = [_panel(key, i, rows=config["rows_per_panel"], n=config["n"])
              for i in range(config["panels"])]
    jax.block_until_ready(panels)
    return State(config, seed, context_seed, transform, panels, sk.ROWWISE)


def describe(state: State) -> dict:
    """What the dispatch will do with this operand (for the log)."""
    from libskylark_tpu.sketch import pallas_dense

    A = state.panels[0]
    plan = pallas_dense.effective_plan(
        state.transform.dist, A.shape, A.dtype, state.config["s"], 1)
    return {k: plan.get(k) for k in ("kernel", "precision", "m_tile", "s_tile",
                                     "operator_residency", "plan_source")}


def keep(state: State) -> int:
    return len(state.panels)


def step(state: State, i: int):
    return state.transform.apply(
        state.panels[i % len(state.panels)], state.rowwise).block_until_ready()


def _finite(x: float) -> float:
    return x if np.isfinite(x) else np.inf


def check(state: State, kept: list) -> dict:
    """The numbers compared, each the worst over the kept results."""
    cfg = state.config
    s, n, sigma = cfg["s"], cfg["n"], cfg["sigma"]
    W = reference.frequencies(state.context_seed, 0, s, n)
    b = reference.shifts(state.context_seed, 0, s)
    outscale = math.sqrt(2.0 / s)
    got = {"rel_max": 0.0, "norm_dev": 0.0, "kernel_z": 0.0}
    for i, out in kept:
        panel = state.panels[i % len(state.panels)]
        if out.shape != (panel.shape[0], s):
            raise AssertionError(f"served shape {out.shape}")
        idx = jnp.asarray(np.sort(seeds.rng(state.seed, f"rows.{i % len(state.panels)}")
                                  .choice(panel.shape[0], cfg["check_rows"], replace=False)))
        rows, served = panel[idx], out[idx]
        ref = reference.features(rows, W, b, sigma)
        rel = float(jnp.max(jnp.abs(served - ref))) / outscale
        # ‖z(x)‖² ≈ 1 for every example: the whole result
        dev = abs(float(jnp.sum(out * out)) / panel.shape[0] - 1.0)
        # the guarantee: z_i·z_j estimates k(x_i, x_j); each of the s terms
        # 2cos(φ_i)cos(φ_j) has mean k and variance 1 + k⁴/2 − k²
        gram = jnp.dot(served, served.T, precision=jax.lax.Precision.HIGHEST)
        k = reference.gaussian_kernel(rows, sigma)
        z = jnp.abs(gram - k) / jnp.sqrt((1.0 + 0.5 * k ** 4 - k * k) / s)
        got["rel_max"] = max(got["rel_max"], _finite(rel))
        got["norm_dev"] = max(got["norm_dev"], _finite(dev))
        got["kernel_z"] = max(got["kernel_z"], _finite(float(jnp.max(z))))
    # the laws the configuration states: W i.i.d. N(0, 1), b i.i.d. U[0, 2π)
    count = W.size
    got["operator_mean_z"] = abs(float(jnp.mean(W))) * count ** 0.5
    got["operator_var_z"] = abs(float(jnp.var(W)) - 1.0) * (count / 2.0) ** 0.5
    hist = np.bincount(
        np.minimum((np.asarray(b, np.float64) * (SHIFT_BINS / reference.TWO_PI))
                   .astype(np.int64), SHIFT_BINS - 1), minlength=SHIFT_BINS)
    expected = s / SHIFT_BINS
    chi2 = float(((hist - expected) ** 2).sum() / expected)
    got["shift_chi2_z"] = abs(chi2 - (SHIFT_BINS - 1)) / (2.0 * (SHIFT_BINS - 1)) ** 0.5
    return got


def controls(state: State) -> dict:
    """Lower-precision stand-ins for ``step``: the program's own kernel
    regimes below the shipping one (they exist on the TPU only), and the
    reference computed in bfloat16 in the program's place."""
    cfg = state.config
    W = reference.frequencies(state.context_seed, 0, cfg["s"], cfg["n"])
    b = reference.shifts(state.context_seed, 0, cfg["s"])

    def reference_bf16(i):
        panel = state.panels[i % len(state.panels)]
        return reference.features(panel, W, b, cfg["sigma"],
                                  "bf16").block_until_ready()

    return {"program_bf16": lower_precision.program_at(step, state, "bf16"),
            "program_bf16gen2": lower_precision.program_at(step, state, "bf16gen2"),
            "reference_bf16": reference_bf16}
