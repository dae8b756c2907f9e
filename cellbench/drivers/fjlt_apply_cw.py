"""Driver for one kind of operation: the Blendenpik sketch,
``FJLT(m, s, context, fut="wht").apply(A, COLUMNWISE)`` = S·A of a tall dense
operand A (m × n) held on the device. For a height that is a power of two it
is the sketch ``_blendenpik_r`` (``fast_least_squares``) and
``approximate_least_squares`` build; they apply it inside their own compiled
programs, this cell times the apply by itself.

Set-up builds the transform from the seed and the operands on the device; a
step is one blocking apply on the next operand; the check holds sampled
columns of the last result of every operand to the plain reference, the whole
result to the operand's norm, and the transform's own signs and samples —
the streams the program reads — to the laws the configuration states.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from cellbench import seeds
from cellbench.references import srht as reference

HEAVY_EVERY, HEAVY_SCALE = 64, 32.0     # the configuration's leverage spread


@dataclasses.dataclass
class State:
    config: dict
    seed: int
    context_seed: int
    transform: object
    panels: list
    columnwise: object


@functools.partial(jax.jit, static_argnames=("m", "n"))
def _panel(key, i, *, m: int, n: int):
    """N(0, 1) entries, every 64th row 32 times as heavy: 1/64 of the rows
    hold 94 % of the energy, which an unmixed sample would not see."""
    x = jax.random.normal(jax.random.fold_in(key, i), (m, n), jnp.float32)
    heavy = (jnp.arange(m, dtype=jnp.int32) % HEAVY_EVERY) == 0
    return x * jnp.where(heavy, HEAVY_SCALE, 1.0).astype(jnp.float32)[:, None]


def setup(config: dict, traffic: dict, seed: int) -> State:
    from libskylark_tpu import sketch as sk
    from libskylark_tpu.base.context import Context

    if config["family"] != "FJLT":
        raise ValueError(f"fjlt_apply_cw drives FJLT, got {config['family']!r}")
    context_seed = seeds.context_seed(seed)
    transform = sk.FJLT(config["m"], config["s"], Context(context_seed),
                        fut=config["fut"])
    key = seeds.data_key(seed, "operand_tall")
    panels = [_panel(key, i, m=config["m"], n=config["n"])
              for i in range(config["panels"])]
    jax.block_until_ready(panels)
    return State(config, seed, context_seed, transform, panels, sk.COLUMNWISE)


def describe(state: State) -> dict:
    """What the dispatch will do with this operand (for the log); a program
    older than the one-program route says so."""
    plan = getattr(state.transform, "mix_plan", None)
    served = plan and plan(state.panels[0], False)
    if not served:
        return {"route": "eager"}
    kernel, block, tile = served
    return {"route": "fut", "kernel": kernel, "block": block, "tile": tile}


def keep(state: State) -> int:
    return len(state.panels)


def step(state: State, i: int):
    return state.transform.apply(
        state.panels[i % len(state.panels)], state.columnwise).block_until_ready()


def _finite(x: float) -> float:
    return x if np.isfinite(x) else np.inf


def check(state: State, kept: list) -> dict:
    """The numbers compared, each the worst over the kept results."""
    cfg = state.config
    m, n, s = cfg["m"], cfg["n"], cfg["s"]
    D, idx = reference.streams(state.context_seed, 0, m, s)
    got = {"rel_max": 0.0, "norm_dev": 0.0}
    for i, out in kept:
        panel = state.panels[i % len(state.panels)]
        if out.shape != (s, n):
            raise AssertionError(f"served shape {out.shape}")
        cols = jnp.asarray(np.sort(
            seeds.rng(state.seed, f"cols.{i % len(state.panels)}")
            .choice(n, min(cfg["check_cols"], n), replace=False)))
        ref = reference.apply_cols(panel[:, cols], D, idx)
        rel = float(jnp.max(jnp.abs(out[:, cols] - ref)) / jnp.max(jnp.abs(ref)))
        # E‖S·A‖²_F = ‖A‖²_F: the whole result against the whole operand
        dev = float(jnp.abs(jnp.sqrt(jnp.sum(out * out) / jnp.sum(panel * panel)) - 1.0))
        got["rel_max"] = max(got["rel_max"], _finite(rel))
        got["norm_dev"] = max(got["norm_dev"], _finite(dev))
        del ref
    # the laws the configuration states, D fair ±1 and idx uniform on
    # [0, m), of the streams the program reads (rel_max above holds what it
    # made of them to the reference's own D and idx)
    got["sign_mean_z"], got["sample_chi2_z"] = reference.law_z_scores(
        state.transform.diagonal(), state.transform.sample_indices(), m,
        cfg["law_bins"])
    return got


def controls(state: State) -> dict:
    """Stand-ins for ``step`` that must come out not correct. The program
    has no regime below the shipping one, so the reference runs in its
    place on an operand cut to the first two of its three bfloat16 parts
    (``reference_bf16x2``, the nearest precision below the stated one: what
    a split that dropped its last part would serve) and to the first alone
    (``reference_bf16``), a block of columns at a time; and the sample
    without the mixing, √(N/s)·A[idx] — sound rows of the operand that miss
    where its energy lies."""
    cfg = state.config
    D, idx = reference.streams(state.context_seed, 0, cfg["m"], cfg["s"])
    block = min(cfg["check_cols"], cfg["n"])

    def reference_in(precision):
        def control(i):
            panel = state.panels[i % len(state.panels)]
            return jnp.concatenate(
                [reference.apply_cols(panel[:, lo:lo + block], D, idx, precision)
                 for lo in range(0, cfg["n"], block)], axis=1).block_until_ready()
        return control

    def unmixed_sample(i):
        panel = state.panels[i % len(state.panels)]
        return (panel[idx] * jnp.float32((cfg["m"] / cfg["s"]) ** 0.5)
                ).block_until_ready()

    return {"reference_bf16x2": reference_in("bf16x2"),
            "reference_bf16": reference_in("bf16"),
            "unmixed_sample": unmixed_sample}
