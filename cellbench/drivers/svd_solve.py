"""Driver for one kind of operation: a whole rank-k randomized SVD,
``nla.approximate_svd(A, rank, context, params)``, of a device-resident
planted-spectrum operand.

Set-up plants the operand from the seed; a step is one blocking solve (the
library draws a fresh sketch from the run's ``Context`` each time); the check
holds the last solve's factors to the planted ones.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from cellbench import seeds
from cellbench.counts import randsvd_passes
from cellbench.references import randsvd as reference


@dataclasses.dataclass
class State:
    config: dict
    seed: int
    A: jax.Array
    U0: jax.Array
    sigma: jax.Array
    V0: jax.Array
    context: object
    params: object
    solve: object


def setup(config: dict, traffic: dict, seed: int) -> State:
    from libskylark_tpu import nla
    from libskylark_tpu.base.context import Context

    A, U0, sigma, V0 = jax.jit(lambda key: reference.planted(key, config))(
        seeds.data_key(seed, "operand"))
    jax.block_until_ready(A)
    params = nla.ApproximateSVDParams(
        oversampling_ratio=config["oversampling_ratio"],
        oversampling_additive=config.get("oversampling_additive", 0),
        num_iterations=config["num_iterations"])
    return State(config, seed, A, U0, sigma, V0,
                 Context(seeds.context_seed(seed)), params, nla.approximate_svd)


def describe(state: State) -> dict:
    from libskylark_tpu.base import precision

    return {"solver_precision": precision.get_solver_precision(),
            "sketch_width": randsvd_passes.sketch_width(state.config)}


def keep(state: State) -> int:
    return 1


def step(state: State, i: int):
    return jax.block_until_ready(state.solve(
        state.A, state.config["rank"], state.context, state.params))


def check(state: State, kept: list) -> dict:
    cfg = state.config
    _, (U, s, V) = kept[-1]
    k = cfg["rank"]
    if U.shape != (cfg["m"], k) or s.shape != (k,) or V.shape != (cfg["n"], k):
        raise AssertionError(f"served shapes {U.shape} {s.shape} {V.shape}")
    rng = seeds.rng(state.seed, "entries")
    rows = jnp.asarray(np.sort(rng.choice(cfg["m"], cfg["check_rows"], replace=False)))
    cols = jnp.asarray(np.sort(rng.choice(cfg["n"], cfg["check_cols"], replace=False)))
    got = reference.errors(state.A, U, s, V, state.U0, state.sigma, state.V0, rows, cols)
    return {name: (v if np.isfinite(v) else np.inf) for name, v in got.items()}


def controls(state: State) -> dict:
    """Lower-precision stand-ins for ``step``: the program's own solver
    precision switch (it bites on the TPU only), and the plain reference
    algorithm computed below float32 in the program's place."""
    from libskylark_tpu.base import precision

    def program_at(value):
        def run(i):
            before = precision.get_solver_precision()
            precision.set_solver_precision(value)
            try:
                return step(state, i)
            finally:
                precision.set_solver_precision(before)
        return run

    cfg = state.config

    def reference_at(value):
        def run(i):
            return jax.block_until_ready(reference.randomized_svd(
                state.A, cfg["rank"], randsvd_passes.sketch_width(cfg),
                cfg["num_iterations"], seeds.data_key(state.seed, f"sketch.{i}"),
                value))
        return run

    return {"program_high": program_at("high"),
            "program_bf16": program_at("bfloat16"),
            "reference_high": reference_at("high"),
            "reference_bf16": reference_at("bf16")}
