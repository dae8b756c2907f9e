"""Driver for one kind of operation: a rowwise Fastfood feature-map apply,
``kernel.create_rft(s, context, "fast").apply(X, ROWWISE)`` — the call KRR
(``use_fast``) and Block-ADMM make for every feature block of a
Gaussian-kernel model — on the device-resident training set.

Set-up builds the kernel's Fastfood map from the seed and the examples on
the device; a step is one blocking apply on the next panel; the check holds
sampled rows of the last result of every panel to the plain reference
``references/fastfood_features.py``, the whole result to its norm, the
sampled rows' inner products to the kernel the map approximates (with the
variance Fastfood's dependent rows have: the configuration's
``kernel_var_inflation``), and the streams the program reads to the laws the
configuration states — each Π_k, exactly, to being a permutation.
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
from cellbench.references import fastfood_features as reference


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

    if config["family"] != "FastGaussianRFT" or config["fut"] != "wht":
        raise ValueError("fastfood_apply drives FastGaussianRFT under the wht "
                         f"core, got {config['family']!r} / {config['fut']!r}")
    context_seed = seeds.context_seed(seed)
    transform = kernels.Gaussian(config["n"], config["sigma"]).create_rft(
        config["s"], Context(context_seed), config["tag"])
    if type(transform).__name__ != config["family"]:
        raise AssertionError(f"the tag built a {type(transform).__name__}")
    key = seeds.data_key(seed, "examples")
    panels = [_panel(key, i, rows=config["rows_per_panel"], n=config["n"])
              for i in range(config["panels"])]
    jax.block_until_ready(panels)
    return State(config, seed, context_seed, transform, panels, sk.ROWWISE)


def describe(state: State) -> dict:
    """What the dispatch will do with this operand (for the log); a program
    that has no one-program route for the chain says so."""
    plan = getattr(state.transform, "features_plan", None)
    if plan is None:
        return {"route": "chain", "reason": "no_program"}
    served = plan(state.panels[0], True)
    if isinstance(served, str):
        return {"route": "chain", "reason": served}
    return {"route": "fastfood_blocks", "kernel": served[0], "tile": served[1]}


def keep(state: State) -> int:
    return len(state.panels)


def step(state: State, i: int):
    return state.transform.apply(
        state.panels[i % len(state.panels)], state.rowwise).block_until_ready()


def _finite(x: float) -> float:
    return x if np.isfinite(x) else np.inf


def check_rows(state: State, panel, out, tag: str, parts: dict) -> dict:
    """``rel_max``, ``norm_dev``, ``kernel_z`` of one result of one panel."""
    cfg = state.config
    s, sigma = cfg["s"], cfg["sigma"]
    if out.shape != (panel.shape[0], s):
        raise AssertionError(f"served shape {out.shape}")
    idx = jnp.asarray(np.sort(seeds.rng(state.seed, f"rows.{tag}").choice(
        panel.shape[0], min(cfg["check_rows"], panel.shape[0]), replace=False)))
    rows, served = panel[idx], out[idx]
    ref = reference.features(rows, parts, sigma)
    rel = float(jnp.max(jnp.abs(served - ref))) / math.sqrt(2.0 / s)
    # ‖z(x)‖² ≈ 1 for every example: the whole result
    dev = abs(float(jnp.sum(out * out)) / panel.shape[0] - 1.0)
    # the guarantee: z_i·z_j estimates k(x_i, x_j). Each of the s terms
    # 2cos(φ_i)cos(φ_j) has mean k and variance 1 + k⁴/2 − k²; the terms of a
    # Fastfood block are dependent (one g and one Π behind all NB of them),
    # which the calibrated inflation of the variance accounts for
    gram = jnp.dot(served, served.T, precision=jax.lax.Precision.HIGHEST)
    k = reference.gaussian_kernel(rows, sigma)
    var = cfg["kernel_var_inflation"] * (1.0 + 0.5 * k ** 4 - k * k) / s
    z = float(jnp.max(jnp.abs(gram - k) / jnp.sqrt(var)))
    return {"rel_max": _finite(rel), "norm_dev": _finite(dev),
            "kernel_z": _finite(z)}


def check(state: State, kept: list) -> dict:
    """The numbers compared, each the worst over the kept results."""
    cfg = state.config
    parts = reference.streams(state.context_seed, 0, cfg["n"], cfg["s"])
    got = {"rel_max": 0.0, "norm_dev": 0.0, "kernel_z": 0.0}
    for i, out in kept:
        which = i % len(state.panels)
        one = check_rows(state, state.panels[which], out, str(which), parts)
        got = {name: max(got[name], one[name]) for name in got}
    # the laws the configuration states, of the streams the program reads
    # (rel_max above holds what it made of them to the reference's own)
    T = state.transform
    got.update(reference.law_z_scores(
        T._B(jnp.float32), T._G(jnp.float32), T.shifts(jnp.float32),
        T._perms(), cfg["law_bins"]))
    return got


def controls(state: State) -> dict:
    """Stand-ins for ``step`` that must come out not correct: the program's
    own ``"bf16"`` regime (one bfloat16 part of the operand a Hadamard
    product; on the TPU's kernel route only — elsewhere the regime changes
    nothing and the control is sound, as the dense cells' is off the chip),
    and the reference computed in bfloat16 in the program's place."""
    cfg = state.config
    parts = reference.streams(state.context_seed, 0, cfg["n"], cfg["s"])

    def reference_bf16(i):
        panel = state.panels[i % len(state.panels)]
        # in slabs of rows: the reference's dense products are not the
        # program's walk, and a whole panel of them would not fit
        slab = 2048
        out = [reference.features(panel[lo:lo + slab], parts, cfg["sigma"], "bf16")
               for lo in range(0, panel.shape[0], slab)]
        return jnp.concatenate(out).block_until_ready()

    return {"program_bf16": lower_precision.program_at(step, state, "bf16"),
            "reference_bf16": reference_bf16}
