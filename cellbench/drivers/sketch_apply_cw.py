"""Driver for one kind of operation: a columnwise dense sketch apply,
``SketchTransform.apply(A, COLUMNWISE)`` = S·A, on device-resident operands
that hold the configuration's panels with examples as columns (n × rows,
upstream's own layout: ``ml/io.hpp`` reads d × n).

Set-up builds one transform from the seed and the operands on the device; a
step is one blocking apply on the next operand; the check compares sampled
columns of the last output of every operand with the plain reference.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from cellbench import seeds
from cellbench.drivers import lower_precision
from cellbench.references import dense_sketch as reference


@dataclasses.dataclass
class State:
    config: dict
    seed: int
    context_seed: int
    transform: object
    panels: list
    columnwise: object


@functools.partial(jax.jit, static_argnames=("n", "cols"))
def _panel(key, i, *, n: int, cols: int):
    return jax.random.normal(jax.random.fold_in(key, i), (n, cols), jnp.float32)


def setup(config: dict, traffic: dict, seed: int) -> State:
    from libskylark_tpu import sketch as sk
    from libskylark_tpu.base.context import Context

    if config["family"] != "JLT":
        raise ValueError(f"sketch_apply_cw drives JLT, got {config['family']!r}")
    context_seed = seeds.context_seed(seed)
    transform = sk.JLT(config["n"], config["s"], Context(context_seed))
    key = seeds.data_key(seed, "operand_cw")
    panels = [_panel(key, i, n=config["n"], cols=config["rows_per_panel"])
              for i in range(config["panels"])]
    jax.block_until_ready(panels)
    return State(config, seed, context_seed, transform, panels, sk.COLUMNWISE)


def describe(state: State) -> dict:
    """What the dispatch will do with this operand (for the log)."""
    from libskylark_tpu.sketch import pallas_dense

    A = state.panels[0]
    plan = pallas_dense.effective_plan(
        state.transform.dist, A.shape, A.dtype, state.config["s"], 0)
    return {k: plan.get(k) for k in ("kernel", "precision", "m_tile",
                                     "operator_residency", "plan_source")}


def keep(state: State) -> int:
    return len(state.panels)


def step(state: State, i: int):
    return state.transform.apply(
        state.panels[i % len(state.panels)], state.columnwise).block_until_ready()


def check(state: State, kept: list) -> dict:
    """The numbers compared, each the worst over the kept outputs."""
    cfg = state.config
    S = reference.operator(state.context_seed, 0, cfg["s"], cfg["n"])
    got = {"rel_max": 0.0, "norm_dev": 0.0}
    for i, out in kept:
        panel = state.panels[i % len(state.panels)]
        if out.shape != (cfg["s"], panel.shape[1]):
            raise AssertionError(f"served shape {out.shape}")
        idx = jnp.asarray(np.sort(seeds.rng(state.seed, f"cols.{i % len(state.panels)}")
                                  .choice(panel.shape[1], cfg["check_rows"], replace=False)))
        ref = reference.apply_rows(panel[:, idx].T, S).T
        rel = float(jnp.max(jnp.abs(out[:, idx] - ref)) / jnp.max(jnp.abs(ref)))
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
    cfg = state.config
    S = reference.operator(state.context_seed, 0, cfg["s"], cfg["n"])

    def reference_bf16(i):
        panel = state.panels[i % len(state.panels)]
        return jnp.dot(S.astype(jnp.bfloat16), panel.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32).block_until_ready()

    return {"program_bf16": lower_precision.program_at(step, state, "bf16"),
            "program_bf16gen2": lower_precision.program_at(step, state, "bf16gen2"),
            "reference_bf16": reference_bf16}
