"""Driver for one kind of operation: a rowwise TensorSketch feature-map
apply, ``kernels.Polynomial(n, q, c, γ).create_rft(s, context).apply(X,
ROWWISE)`` — the call KRR and Block-ADMM make for every feature block of a
polynomial-kernel model — on the device-resident training set.

Set-up builds the kernel's map (a ``sketch.PPT``) from the seed and the
examples on the device; a step is one blocking apply; the check holds
sampled rows of the last result to the plain reference
``references/tensorsketch_features.py``, the whole result's squared norms to
(γ‖x‖² + c)^q, the sampled rows' inner products to the kernel the map
approximates (with the variance TensorSketch has: the configuration's
``kernel_var_inflation``), and the streams the program reads — each factor's
buckets and signs, the homogeneity hash with them — to the laws the
configuration states.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from cellbench import seeds
from cellbench.drivers import lower_precision
from cellbench.references import tensorsketch_features as reference

CLASSES = 10


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
    """Examples of ten classes: x = μ_class + N(0, I), the class uniform,
    the ten means μ ~ N(0, I) the same for every panel of a seed."""
    means = jax.random.normal(jax.random.fold_in(key, 0x6d65616e), (CLASSES, n),
                              jnp.float32)
    kc, kx = jax.random.split(jax.random.fold_in(key, i))
    labels = jax.random.randint(kc, (rows,), 0, CLASSES)
    return means[labels] + jax.random.normal(kx, (rows, n), jnp.float32)


def setup(config: dict, traffic: dict, seed: int) -> State:
    from libskylark_tpu import sketch as sk
    from libskylark_tpu.base.context import Context
    from libskylark_tpu.ml import kernels

    if config["family"] != "PPT" or config["kernel"] != "polynomial":
        raise ValueError("tensorsketch_apply drives PPT under the polynomial "
                         f"kernel, got {config['family']!r} / {config['kernel']!r}")
    context_seed = seeds.context_seed(seed)
    transform = kernels.Polynomial(
        config["n"], q=config["q"], c=config["c"], gamma=config["gamma"]
    ).create_rft(config["s"], Context(context_seed))
    if type(transform).__name__ != config["family"]:
        raise AssertionError(f"the kernel built a {type(transform).__name__}")
    key = seeds.data_key(seed, "examples")
    panels = [_panel(key, i, rows=config["rows_per_panel"], n=config["n"])
              for i in range(config["panels"])]
    jax.block_until_ready(panels)
    return State(config, seed, context_seed, transform, panels, sk.ROWWISE)


def describe(state: State) -> dict:
    """What the dispatch does with this operand — the attributes its
    ``sketch.dispatch`` span carries (for the log); a program that has no
    one-program route for the map says so."""
    plan = getattr(state.transform, "features_plan", None)
    if plan is None:
        return {"route": "chain", "reason": "no_program"}
    return plan(state.panels[0], True)


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
    s, q, c, gamma = cfg["s"], cfg["q"], cfg["c"], cfg["gamma"]
    if out.shape != (panel.shape[0], s):
        raise AssertionError(f"served shape {out.shape}")
    idx = jnp.asarray(np.sort(seeds.rng(state.seed, f"rows.{tag}").choice(
        panel.shape[0], min(cfg["check_rows"], panel.shape[0]), replace=False)))
    rows, served = panel[idx], out[idx]
    ref = reference.features(rows, parts, gamma, c)
    # against the rows' largest entry (the homogeneity term alone puts
    # ±c^{q/2} into one bucket of every row)
    rel = float(jnp.max(jnp.abs(served - ref)) / jnp.max(jnp.abs(ref)))
    # E‖z(x)‖² = (γ‖x‖² + c)^q for every example: the whole result, the two
    # sums over all examples (one map's deviation is common to its rows)
    want = (gamma * jnp.sum(panel * panel, axis=1) + c) ** q
    dev = abs(float(jnp.sum(out * out) / jnp.sum(want)) - 1.0)
    # the guarantee: ⟨z(x), z(y)⟩ estimates k(x, y) with TensorSketch's own
    # variance (reference.kernel_variance: exact to order 1/S); the estimate
    # is a polynomial of degree 2q in the signs, heavier-tailed than a
    # normal, which the calibrated inflation of the variance accounts for
    gram = jnp.dot(served, served.T, precision=jax.lax.Precision.HIGHEST)
    k = reference.polynomial_kernel(rows, gamma, c, q)
    var = cfg["kernel_var_inflation"] * reference.kernel_variance(rows, gamma, c, q, s)
    z = float(jnp.max(jnp.abs(gram - k) / jnp.sqrt(var)))
    return {"rel_max": _finite(rel), "norm_dev": _finite(dev),
            "kernel_z": _finite(z)}


def transform_streams(transform) -> dict:
    """The buckets and signs the transform itself reports, in the
    reference's form."""
    return {"s": transform.sketch_dim,
            "h": jnp.stack([cwt.bucket_indices() for cwt in transform._cwts]),
            "v": jnp.stack([cwt.values(jnp.float32) for cwt in transform._cwts]),
            "hh": transform._hash_idx(),
            "hv": transform._hash_val(jnp.float32)}


def check(state: State, kept: list) -> dict:
    """The numbers compared, each the worst over the kept results."""
    cfg = state.config
    parts = reference.streams(state.context_seed, 0, cfg["n"], cfg["s"], cfg["q"])
    got = {"rel_max": 0.0, "norm_dev": 0.0, "kernel_z": 0.0}
    for i, out in kept:
        which = i % len(state.panels)
        one = check_rows(state, state.panels[which], out, str(which), parts)
        got = {name: max(got[name], one[name]) for name in got}
    # the laws the configuration states, of the streams the program reads
    # (rel_max above holds what it made of them to the reference's own)
    got.update(reference.law_z_scores(transform_streams(state.transform),
                                      cfg["law_bins"]))
    return got


def controls(state: State) -> dict:
    """Stand-ins for ``step`` that must come out not correct: the program's
    own ``"bf16"`` regime (every MXU product's operands in one bfloat16
    part), and the reference computed in bfloat16 in the program's place."""
    cfg = state.config
    parts = reference.streams(state.context_seed, 0, cfg["n"], cfg["s"], cfg["q"])

    def reference_bf16(i):
        panel = state.panels[i % len(state.panels)]
        # in slabs of rows: the reference's whole-length complex transforms
        # are not the program's walk, and a whole panel of them would not fit
        slab = 2048
        out = [reference.features(panel[lo:lo + slab], parts, cfg["gamma"],
                                  cfg["c"], "bf16")
               for lo in range(0, panel.shape[0], slab)]
        return jnp.concatenate(out).block_until_ready()

    return {"program_bf16": lower_precision.program_at(step, state, "bf16"),
            "reference_bf16": reference_bf16}
