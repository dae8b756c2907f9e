"""Driver for one kind of operation: a rowwise dense sketch apply,
``SketchTransform.apply(A, ROWWISE)``, on device-resident panels.

Set-up builds one transform from the seed and the panels on the device; a
step is one blocking apply on the next panel; the check compares sampled
rows of the last output of every panel with the plain reference.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from cellbench import seeds
from cellbench.references import dense_sketch as reference


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

    if config["family"] != "JLT":
        raise ValueError(f"sketch_apply drives JLT, got {config['family']!r}")
    context_seed = seeds.context_seed(seed)
    transform = sk.JLT(config["n"], config["s"], Context(context_seed))
    key = seeds.data_key(seed, "operand")
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
    return {k: plan.get(k) for k in ("kernel", "precision", "m_tile", "plan_source")}


def keep(state: State) -> int:
    return len(state.panels)


def step(state: State, i: int):
    return state.transform.apply(
        state.panels[i % len(state.panels)], state.rowwise).block_until_ready()


def check(state: State, kept: list) -> dict:
    """The numbers compared, each the worst over the kept outputs."""
    cfg = state.config
    S = reference.operator(state.context_seed, 0, cfg["s"], cfg["n"])
    got = {"rel_max": 0.0, "norm_dev": 0.0}
    for i, out in kept:
        panel = state.panels[i % len(state.panels)]
        if out.shape != (panel.shape[0], cfg["s"]):
            raise AssertionError(f"served shape {out.shape}")
        idx = jnp.asarray(np.sort(seeds.rng(state.seed, f"rows.{i % len(state.panels)}")
                                  .choice(panel.shape[0], cfg["check_rows"], replace=False)))
        ref = reference.apply_rows(panel[idx], S)
        rel = float(jnp.max(jnp.abs(out[idx] - ref)) / jnp.max(jnp.abs(ref)))
        dev = float(jnp.abs(jnp.sqrt(jnp.sum(out * out) / jnp.sum(panel * panel)) - 1.0))
        got["rel_max"] = max(got["rel_max"], rel if np.isfinite(rel) else np.inf)
        got["norm_dev"] = max(got["norm_dev"], dev if np.isfinite(dev) else np.inf)
    # the guarantee the configuration states: entries i.i.d. N(0, 1/s)
    count = S.size
    got["operator_mean_z"] = abs(float(jnp.mean(S))) * (count * cfg["s"]) ** 0.5
    got["operator_var_z"] = abs(float(jnp.var(S)) * cfg["s"] - 1.0) * (count / 2.0) ** 0.5
    return got


def controls(state: State) -> dict:
    """Lower-precision stand-ins for ``step``: the program's own kernel
    regimes below the shipping one (they exist on the TPU only), and the
    reference computed in bfloat16 in the program's place."""
    from libskylark_tpu.sketch import params as sketch_params

    def program_at(precision):
        def run(i):
            before = sketch_params.get_pallas_precision()
            sketch_params.set_pallas_precision(precision)
            try:
                return step(state, i)
            finally:
                sketch_params.set_pallas_precision(before)
        return run

    cfg = state.config
    S = reference.operator(state.context_seed, 0, cfg["s"], cfg["n"])

    def reference_bf16(i):
        panel = state.panels[i % len(state.panels)]
        return reference.apply_rows(panel, S, "bf16").block_until_ready()

    return {"program_bf16": program_at("bf16"),
            "program_bf16gen2": program_at("bf16gen2"),
            "reference_bf16": reference_bf16}
