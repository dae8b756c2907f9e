"""Driver for one kind of operation: a columnwise dense sketch (JLT) of a
sparse operand, ``JLT(rows, s, Context(seed)).apply(SparseMatrix,
COLUMNWISE)`` = S·X, on device-resident sparse row blocks (S·A of the
sketched solvers on a libsvm-shaped corpus; with Q in the operator's place,
the Aᵀ·Q of a sparse power iteration).

Set-up builds the row blocks on the host from the seed with the generator
of ``sparse_hash_apply`` (the same corpus model, imported, not copied), one
transform over the blocks' rows, and applies it to every block once, which
compiles the program (each block's transposed side is placed on the device
as soon as the block is made, all blocks at once: a solver's set-up lays its
operand out before its first product). A step is one blocking apply on the
next block; the check holds the last output of every block to
the plain reference: sampled result columns (half of them hot features), the
column sums and the row sums of the WHOLE result, its norm, and the law of
the operator as the served columns show it.
"""

from __future__ import annotations

import concurrent.futures
import dataclasses

import jax.numpy as jnp
import numpy as np
import scipy.sparse as sp

from cellbench import seeds
from cellbench.drivers.sparse_dense_apply import LAST_LANES, _log_counter
from cellbench.drivers.sparse_hash_apply import _block_sums, _panel, _zipf_cdf
from cellbench.references import sparse_dense_sketch_cw as reference


@dataclasses.dataclass
class State:
    config: dict
    seed: int
    context_seed: int
    transform: object
    panels: list            # the program's SparseMatrix row blocks
    host: list              # scipy CSR of each block (canonical), for the check
    columnwise: object


def setup(config: dict, traffic: dict, seed: int) -> State:
    from libskylark_tpu import sketch as sk
    from libskylark_tpu.base.context import Context
    from libskylark_tpu.base.sparse import SparseMatrix

    if config["family"] != "JLT" or config["dimension"] != "columnwise":
        raise ValueError("sparse_dense_apply_cw drives a columnwise JLT, got "
                         f"{config['family']!r} {config['dimension']!r}")
    cdf = _zipf_cdf(config["n"], config["column_skew"]["exponent"])
    ids = seeds.rng(seed, "feature_ids").permutation(config["n"]).astype(np.int32)

    def block(i):
        X = _panel(config, seed, i, cdf, ids)
        A = SparseMatrix.from_scipy(X)
        _placed(A, config["s"])     # the host's regrouping, block beside block
        return X, A

    with concurrent.futures.ThreadPoolExecutor(config["panels"]) as pool:
        host, panels = zip(*pool.map(block, range(config["panels"])))
    context_seed = seeds.context_seed(seed)
    transform = sk.JLT(config["rows_per_panel"], config["s"],
                       Context(context_seed))
    state = State(config, seed, context_seed, transform, list(panels),
                  list(host), sk.COLUMNWISE)
    for i in range(len(panels)):    # every block applied once, here
        step(state, i)
    return state


def _placed(A, s: int) -> dict:
    """Block ``A``'s transposed side placed for a right factor of ``s``
    columns (once: later calls find it), and what the program would do with
    it (its ``sketch.dispatch`` attributes); empty for a program that knows
    no transposed side."""
    try:
        from libskylark_tpu.base.sparse import product_operands

        return dict(product_operands(A, s, A.device_dtype,
                                     side="transposed")[3])
    except (ImportError, TypeError):
        return {}


def _plan(state: State) -> dict:
    return _placed(state.panels[0], state.config["s"])


def describe(state: State) -> dict:
    nnz = [int(X.nnz) for X in state.host]
    plan = _plan(state)
    return {"path": "sparse", "rows": state.host[0].shape[0], "nnz": sum(nnz),
            "nnz_min": min(nnz), "nnz_max": max(nnz),
            **{k: str(plan[k]).replace(" ", "_") for k in
               ("side", "kernel", "nnz_class", "lane_slots", "segments",
                "row_block", "col_tile", "chunk", "grouped_lanes", "run_lanes",
                "run_slots") if k in plan}}


def keep(state: State) -> int:
    return len(state.panels)


def step(state: State, i: int):
    return state.transform.apply(
        state.panels[i % len(state.panels)], state.columnwise).block_until_ready()


def check_columns(state: State, p: int) -> np.ndarray:
    """The result columns block ``p``'s check reads: ``check_cols`` seeded
    features that store a lane, half of them among the block's
    ``hot_features`` most frequent (a hot result row, nearly dense in every
    tile) and half among the rest (a lane or a few)."""
    cfg = state.config
    counts = np.bincount(state.host[p].indices, minlength=cfg["n"])
    by_count = np.argsort(-counts, kind="stable")
    stored = int(np.count_nonzero(counts))
    hot = by_count[:min(cfg["hot_features"], stored // 2)]
    rest = by_count[hot.shape[0]:stored]
    rng = seeds.rng(state.seed, f"cols.{p}")
    half = min(cfg["check_cols"] // 2, hot.shape[0], rest.shape[0])
    return np.sort(np.concatenate([rng.choice(hot, half, replace=False),
                                   rng.choice(rest, half, replace=False)]))


def check(state: State, kept: list) -> dict:
    """The numbers compared, each the worst over the kept outputs."""
    cfg = state.config
    n, s, m = cfg["n"], cfg["s"], cfg["rows_per_panel"]
    key_data = reference.allocation_key_data(state.context_seed, 0)
    blocks = sorted({i % len(state.panels) for i, _ in kept})
    # one pass over S for every block: 1ᵀS and S·(X_p·1)
    ones = np.stack([np.asarray(state.host[p].sum(axis=1)).ravel()
                     for p in blocks], axis=1)
    ones_S, S_ones = reference.operator_sums(key_data, s, m, ones)
    got = {"rel_max": 0.0, "norm_dev": 0.0, "colsum_dev": 0.0,
           "rowsum_dev": 0.0, "operator_mean_z": 0.0, "operator_var_z": 0.0}

    def worst(name, value):
        got[name] = max(got[name], value if np.isfinite(value) else np.inf)

    for i, out in kept:
        p = i % len(state.panels)
        X = state.host[p]
        if out.shape != (s, n):
            raise AssertionError(f"served shape {out.shape}")
        idx = check_columns(state, p)
        X_cols = X[:, idx]
        ref = reference.apply_cols(X_cols, key_data, s)
        cols_out = out[:, jnp.asarray(idx)]
        worst("rel_max", float(jnp.max(jnp.abs(cols_out - ref))
                               / jnp.max(jnp.abs(ref))))
        # E‖S·X‖²_F = ‖X‖²_F: statistical (a hot column's ‖S·x‖² is a
        # chi-square of s terms, and the hot columns hold most of the energy)
        sq = float(np.sum(_block_sums(out * out)))
        energy = float(np.sum(X.data.astype(np.float64) ** 2))
        worst("norm_dev", abs((sq / energy) ** 0.5 - 1.0))
        # every stored nonzero moves its column's sum by value·(1ᵀS)[row]
        # and every row sum by value·S[:, row]: one left out anywhere shows
        want = X.T.astype(np.float64) @ ones_S
        worst("colsum_dev", float(np.max(np.abs(_block_sums(out) - want))
                                  / np.max(np.abs(want))))
        want = S_ones[:, blocks.index(p)]
        sums = _block_sums(out.T)
        worst("rowsum_dev", float(np.max(np.abs(sums - want))
                                  / np.max(np.abs(want))))
        # the guarantee the configuration states, held to what was served:
        # S's entries i.i.d. N(0, 1/s)
        mean_z, var_z = reference.law_z_scores(X_cols, np.asarray(cols_out), s)
        worst("operator_mean_z", mean_z)
        worst("operator_var_z", var_z)
    _log_counter(state)
    return got


def without_last_chunk(X: sp.csr_matrix, plan: dict) -> sp.csr_matrix:
    """``X`` less the lanes of the program's last chunk: the last ``chunk``
    stored nonzeros, in (feature, example) order, of the last block of
    features' last tile of examples that holds any — the transposed side's
    blocks, as the dispatch names them; the last ``LAST_LANES`` stored
    nonzeros where it names none."""
    keep_mask = np.ones(X.nnz, bool)
    row_of = np.repeat(np.arange(X.shape[0]), np.diff(X.indptr))
    if "chunk" in plan:
        in_block = X.indices >= (X.shape[1] - 1) // plan["row_block"] * plan["row_block"]
        tile = row_of // plan["col_tile"]
        last_tile = tile[in_block].max()
        lanes = np.flatnonzero(in_block & (tile == last_tile))
        lanes = lanes[np.argsort(X.indices[lanes], kind="stable")]
        keep_mask[lanes[-plan["chunk"]:]] = False
    else:
        keep_mask[-LAST_LANES:] = False
    lengths = np.bincount(row_of[keep_mask], minlength=X.shape[0])
    return sp.csr_matrix(
        (X.data[keep_mask], X.indices[keep_mask],
         np.concatenate([[0], np.cumsum(lengths)])), shape=X.shape)


def controls(state: State) -> dict:
    """Stand-ins for ``step`` that must come out not correct: the plain
    reference at one bfloat16 pass in the program's place, the program on
    operands that lack the lanes of their last chunk
    (:func:`without_last_chunk`), and the program under another allocation
    counter (a second transform of the same context: another S of the same
    law)."""
    from libskylark_tpu import sketch as sk
    from libskylark_tpu.base.context import Context
    from libskylark_tpu.base.sparse import SparseMatrix

    cfg = state.config
    key_data = reference.allocation_key_data(state.context_seed, 0)
    dropped: dict = {}

    def reference_bf16(i):
        X = state.host[i % len(state.panels)]
        return reference.apply_block(X, key_data, cfg["s"],
                                     "bf16").block_until_ready()

    def program_drops_last_chunk(i):
        p = i % len(state.panels)
        if p not in dropped:
            dropped[p] = SparseMatrix.from_scipy(
                without_last_chunk(state.host[p], _plan(state)))
        return state.transform.apply(dropped[p],
                                     state.columnwise).block_until_ready()

    context = Context(state.context_seed)
    context.allocate()                      # counter 0 is the cell's transform
    other = sk.JLT(cfg["rows_per_panel"], cfg["s"], context)

    def program_other_counter(i):
        return other.apply(state.panels[i % len(state.panels)],
                           state.columnwise).block_until_ready()

    return {"reference_bf16": reference_bf16,
            "program_drops_last_chunk": program_drops_last_chunk,
            "program_other_counter": program_other_counter}
