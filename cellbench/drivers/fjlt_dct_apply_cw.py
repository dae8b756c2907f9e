"""Driver for one kind of operation: the Blendenpik sketch with upstream's
own mixer, ``FJLT(m, s, context).apply(A, COLUMNWISE)`` — ``fut="dct"``, the
transform's default — = S·A of a tall dense operand A (m × n) held on the
device, m no power of two. It is the sketch ``_blendenpik_r``
(``fast_least_squares``) and ``approximate_least_squares`` build by default
at such a height; they apply it inside their own compiled programs, this
cell times the apply by itself.

Set-up and step are ``drivers/fjlt_apply_cw.py``'s (the transform from the
seed, the operands on the device, one blocking apply on the next operand);
the check holds sampled columns of the last result of every operand to the
plain reference ``references/dct_fjlt.py`` (a float64 DCT on the host), the
whole result to the operand's norm, and the transform's own signs and
samples — the streams the program reads — to the laws the configuration
states.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from cellbench import seeds
from cellbench.drivers.fjlt_apply_cw import State, _finite, keep, setup, step  # noqa: F401
from cellbench.references import dct_fjlt as reference


def describe(state: State) -> dict:
    """What the dispatch will do with this operand (for the log); a program
    that has no one-program route for this mixer and height says so."""
    plan = getattr(state.transform, "mix_plan", None)
    served = plan and plan(state.panels[0], False)
    if not served:
        return {"route": "eager"}
    kernel, factors, tile = served
    return {"route": "fut", "kernel": kernel,
            "factors": "x".join(str(f) for f in factors), "tile": tile}


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
        cols = np.sort(seeds.rng(state.seed, f"cols.{i % len(state.panels)}")
                       .choice(n, min(cfg["check_cols"], n), replace=False))
        ref = reference.apply_cols(panel[:, jnp.asarray(cols)], D, idx)
        served = np.asarray(out[:, jnp.asarray(cols)], np.float64)
        rel = float(np.max(np.abs(served - ref)) / np.max(np.abs(ref)))
        # E‖S·A‖²_F = ‖A‖²_F·(1 + O(1/m)): the whole result against the whole operand
        dev = float(jnp.abs(jnp.sqrt(jnp.sum(out * out) / jnp.sum(panel * panel)) - 1.0))
        got["rel_max"] = max(got["rel_max"], _finite(rel))
        got["norm_dev"] = max(got["norm_dev"], _finite(dev))
        del ref, served
    # the laws the configuration states, D fair ±1 and idx uniform on
    # [0, m), of the streams the program reads (rel_max above holds what it
    # made of them to the reference's own D and idx)
    got["sign_mean_z"], got["sample_chi2_z"] = reference.law_z_scores(
        state.transform.diagonal(), state.transform.sample_indices(), m,
        cfg["law_bins"])
    return got


def controls(state: State) -> dict:
    """Stand-ins for ``step`` that must come out not correct. The program
    has no regime below the shipping one, so the definition's cosine sum
    runs in its place (``reference.cosine_sum_cols``, float32 on the
    device, every column against one table): on an operand cut to the first
    two of its three bfloat16 parts (``reference_bf16x2``, the nearest
    precision below the stated one: what a split that dropped its last part
    would serve) and to the first alone (``reference_bf16``); with its
    cosine table rounded to bfloat16 (``reference_bf16_table``: what a DFT
    factor contracted in a single bfloat16 pass would serve); and the sample
    without the mixing, √(N/s)·A[idx] — sound rows of the operand that miss
    where its energy lies."""
    cfg = state.config
    D, idx = reference.streams(state.context_seed, 0, cfg["m"], cfg["s"])

    def cosine_sum(precision, table):
        def control(i):
            return reference.cosine_sum_cols(
                state.panels[i % len(state.panels)], D, idx, precision, table
            ).block_until_ready()
        return control

    def unmixed_sample(i):
        panel = state.panels[i % len(state.panels)]
        return (panel[idx] * jnp.float32((cfg["m"] / cfg["s"]) ** 0.5)
                ).block_until_ready()

    return {"reference_bf16x2": cosine_sum("bf16x2", "float32"),
            "reference_bf16": cosine_sum("bf16", "float32"),
            "reference_bf16_table": cosine_sum("highest", "bf16"),
            "unmixed_sample": unmixed_sample}
