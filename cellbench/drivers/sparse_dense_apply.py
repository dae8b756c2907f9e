"""Driver for one kind of operation: a rowwise dense sketch (JLT) of a sparse
operand, ``JLT(n, s, Context(seed)).apply(SparseMatrix, ROWWISE)``, on
device-resident sparse row blocks (the range sketch of ``skylark_svd``'s
sparse branch on libsvm-shaped data).

Set-up builds the row blocks on the host from the seed with the generator
of ``sparse_hash_apply`` (the same corpus model, imported, not copied), one
transform, and applies it to every block once, which places each block on
the device. A step is one blocking apply on the next block; the check holds
the last output of every block to the plain reference: sampled rows, the
row sums, the column sums and the norm of the WHOLE block, and the law of
the operator as the served rows show it.
"""

from __future__ import annotations

import concurrent.futures
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import scipy.sparse as sp

from cellbench import seeds
from cellbench.drivers.sparse_hash_apply import _block_sums, _panel, _zipf_cdf
from cellbench.references import sparse_dense_sketch as reference

LAST_LANES = 1024   # what the control drops where the program names no chunk


@dataclasses.dataclass
class State:
    config: dict
    seed: int
    context_seed: int
    transform: object
    panels: list            # the program's SparseMatrix row blocks
    host: list              # scipy CSR of each block (canonical), for the check
    rowwise: object
    facts: dict = dataclasses.field(default_factory=dict)   # per-block sums, cached


def setup(config: dict, traffic: dict, seed: int) -> State:
    from libskylark_tpu import sketch as sk
    from libskylark_tpu.base.context import Context
    from libskylark_tpu.base.sparse import SparseMatrix

    if config["family"] != "JLT" or config["dimension"] != "rowwise":
        raise ValueError("sparse_dense_apply drives a rowwise JLT, got "
                         f"{config['family']!r} {config['dimension']!r}")
    cdf = _zipf_cdf(config["n"], config["column_skew"]["exponent"])
    ids = seeds.rng(seed, "feature_ids").permutation(config["n"]).astype(np.int32)
    with concurrent.futures.ThreadPoolExecutor(config["panels"]) as pool:
        host = list(pool.map(lambda i: _panel(config, seed, i, cdf, ids),
                             range(config["panels"])))
    context_seed = seeds.context_seed(seed)
    transform = sk.JLT(config["n"], config["s"], Context(context_seed))
    panels = [SparseMatrix.from_scipy(X) for X in host]
    state = State(config, seed, context_seed, transform, panels, host, sk.ROWWISE)
    for i in range(len(panels)):    # each block placed on the device, here
        step(state, i)
    return state


def _plan(state: State) -> dict:
    """What the program would do with block 0 (its ``sketch.dispatch``
    attributes); empty for a program that cannot say."""
    try:
        from libskylark_tpu.base.sparse import product_operands
    except ImportError:
        return {}
    A = state.panels[0]
    return dict(product_operands(A, state.config["s"], A.device_dtype)[3])


def describe(state: State) -> dict:
    nnz = [int(X.nnz) for X in state.host]
    plan = _plan(state)
    return {"path": "sparse", "rows": state.host[0].shape[0], "nnz": sum(nnz),
            "nnz_min": min(nnz), "nnz_max": max(nnz),
            **{k: str(plan[k]).replace(" ", "_") for k in
               ("kernel", "nnz_class", "lane_slots", "segments", "row_block",
                "col_tile", "chunk") if k in plan}}


def keep(state: State) -> int:
    return len(state.panels)


def step(state: State, i: int):
    return state.transform.apply(
        state.panels[i % len(state.panels)], state.rowwise).block_until_ready()


def _facts(state: State, p: int) -> dict:
    """What the check needs of block ``p``'s operand, summed once: each
    column's energy and sum, and the Gram matrix of its hottest columns."""
    if p not in state.facts:
        X, cfg = state.host[p], state.config
        data64 = X.data.astype(np.float64)
        energy = np.bincount(X.indices, weights=data64 ** 2, minlength=cfg["n"])
        hot = np.sort(np.argsort(energy)[-cfg["hot_columns"]:])
        dense_hot = jnp.asarray(X[:, hot].toarray())
        gram = jnp.dot(dense_hot.T, dense_hot, precision=jax.lax.Precision.HIGHEST)
        state.facts[p] = {
            "energy": energy,
            "column_sums": np.bincount(X.indices, weights=data64,
                                       minlength=cfg["n"]),
            "hot": hot, "gram": gram}
    return state.facts[p]


def check(state: State, kept: list) -> dict:
    """The numbers compared, each the worst over the kept outputs."""
    cfg = state.config
    n, s = cfg["n"], cfg["s"]
    S = reference.operator(state.context_seed, 0, s, n)
    S_sums = None
    got = {"rel_max": 0.0, "norm_dev": 0.0, "colsum_dev": 0.0,
           "rowsum_dev": 0.0, "operator_mean_z": 0.0, "operator_var_z": 0.0}

    def worst(name, value):
        got[name] = max(got[name], value if np.isfinite(value) else np.inf)

    for i, out in kept:
        p = i % len(state.panels)
        X, facts = state.host[p], _facts(state, p)
        if out.shape != (X.shape[0], s):
            raise AssertionError(f"served shape {out.shape}")
        idx = np.sort(seeds.rng(state.seed, f"rows.{p}").choice(
            X.shape[0], cfg["check_rows"], replace=False))
        ref = reference.apply_rows(X[idx], S)
        rows_out = out[jnp.asarray(idx)]
        worst("rel_max", float(jnp.max(jnp.abs(rows_out - ref))
                               / jnp.max(jnp.abs(ref))))
        expected = reference.expected_sq_norm(
            facts["energy"], facts["gram"], facts["hot"], S)
        sq = float(np.sum(_block_sums(out * out)))
        worst("norm_dev", abs((sq / expected) ** 0.5 - 1.0))
        want = np.asarray(reference.column_sums_sketch(facts["column_sums"], S),
                          np.float64)
        worst("colsum_dev", float(np.max(np.abs(_block_sums(out) - want))
                                  / np.max(np.abs(want))))
        # every row of the block: a stored nonzero left out anywhere moves
        # its row's sum by its value times a standard normal
        if "row_sums" not in facts:     # one S a state: summed once
            if S_sums is None:
                S_sums = reference.operator_column_sums(S)
            facts["row_sums"] = reference.row_sums_sketch(X, S_sums)
        want = facts["row_sums"]
        sums = np.asarray(jnp.sum(out, axis=1), np.float64)
        worst("rowsum_dev", float(np.max(np.abs(sums - want))
                                  / np.max(np.abs(want))))
        # the guarantee the configuration states, held to what was served:
        # S's entries i.i.d. N(0, 1/s)
        mean_z, var_z = reference.law_z_scores(X[idx], np.asarray(rows_out), s)
        worst("operator_mean_z", mean_z)
        worst("operator_var_z", var_z)
    _log_counter(state)
    return got


def _log_counter(state: State) -> None:
    """The program's own count of the nonzeros it sketched, beside the
    operands' (a program without the counter prints nothing)."""
    from libskylark_tpu.telemetry import metrics

    counter = metrics.snapshot()["metrics"].get("sketch.sparse_nnz")
    if counter is not None:
        total = sum(int(v["value"]) for v in counter["values"])
        print(f"[cellbench] counter name=sketch.sparse_nnz value={total} "
              f"nnz_of_one_round={sum(int(X.nnz) for X in state.host)}",
              flush=True)


def controls(state: State) -> dict:
    """Stand-ins for ``step`` that must come out not correct: the plain
    reference at one bfloat16 pass in the program's place, the program on
    operands that lack the lanes of their last chunk (the last ``chunk``
    stored nonzeros, in row order, of the last row block's last column
    tile that holds any — the program's own blocks, as its dispatch names
    them; the last ``LAST_LANES`` stored nonzeros where it names none), and
    the program under another allocation counter (a second transform of
    the same context: another S of the same law)."""
    from libskylark_tpu import sketch as sk
    from libskylark_tpu.base.context import Context
    from libskylark_tpu.base.sparse import SparseMatrix

    cfg = state.config
    S = reference.operator(state.context_seed, 0, cfg["s"], cfg["n"])
    dropped: dict = {}

    def reference_bf16(i):
        X = state.host[i % len(state.panels)]
        return reference.apply_block(X, S, "bf16").block_until_ready()

    def without_last_chunk(X: sp.csr_matrix) -> sp.csr_matrix:
        plan = _plan(state)
        keep_mask = np.ones(X.nnz, bool)
        row_of = np.repeat(np.arange(X.shape[0]), np.diff(X.indptr))
        if "chunk" in plan:
            in_block = row_of >= (X.shape[0] - 1) // plan["row_block"] * plan["row_block"]
            tile = X.indices // plan["col_tile"]
            last_tile = tile[in_block].max()
            lanes = np.flatnonzero(in_block & (tile == last_tile))
            keep_mask[lanes[-plan["chunk"]:]] = False
        else:
            keep_mask[-LAST_LANES:] = False
        lengths = np.bincount(row_of[keep_mask], minlength=X.shape[0])
        return sp.csr_matrix(
            (X.data[keep_mask], X.indices[keep_mask],
             np.concatenate([[0], np.cumsum(lengths)])), shape=X.shape)

    def program_drops_last_chunk(i):
        p = i % len(state.panels)
        if p not in dropped:
            dropped[p] = SparseMatrix.from_scipy(without_last_chunk(state.host[p]))
        return state.transform.apply(dropped[p], state.rowwise).block_until_ready()

    context = Context(state.context_seed)
    context.allocate()                      # counter 0 is the cell's transform
    other = sk.JLT(cfg["n"], cfg["s"], context)

    def program_other_counter(i):
        return other.apply(state.panels[i % len(state.panels)],
                           state.rowwise).block_until_ready()

    return {"reference_bf16": reference_bf16,
            "program_drops_last_chunk": program_drops_last_chunk,
            "program_other_counter": program_other_counter}
