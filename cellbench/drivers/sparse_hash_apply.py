"""Driver for one kind of operation: a rowwise hash sketch (CWT) of a sparse
operand, ``CWT(n, s, Context(seed)).apply(SparseMatrix, ROWWISE)``, on
device-resident sparse row blocks (feature hashing of libsvm-shaped data).

Set-up builds the row blocks on the host from the seed (row lengths
log-normal, features Zipf over ranks scattered over ids and distinct within a
row — a draw that repeats one of its row is redrawn — values |N(0, 1)|, each
row scaled to unit norm), one transform, and applies it to every block once,
which places each block on the device. A step is one blocking apply on the
next block; the check holds the last output of every block to the plain
reference, and reads the buckets and signs it tests against their laws out of
those outputs.
"""

from __future__ import annotations

import concurrent.futures
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import scipy.sparse as sp

from cellbench import seeds
from cellbench.references import sparse_hash as reference

SUM_BLOCK = 4096   # rows summed at a time in float32; blocks are added in float64
COO_GRANULE = 1 << 20   # the reference control's triplets are zero-padded to a
                        # multiple of this, so that blocks and seeds share its compiles


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


def _zipf_cdf(n: int, exponent: float) -> np.ndarray:
    weights = np.arange(1, n + 1, dtype=np.float64) ** -exponent
    return np.cumsum(weights / weights.sum())


def _distinct_ranks(rng, cdf: np.ndarray, lengths: np.ndarray) -> np.ndarray:
    """``lengths.sum()`` Zipf ranks, row after row, distinct within each row:
    a draw that repeats one of its row is redrawn until it is new (successive
    sampling without replacement, so a row stores as many features as its
    length says)."""
    n = cdf.shape[0]

    def draw(count):
        return np.minimum(np.searchsorted(cdf, rng.random(count)), n - 1)

    base = np.repeat(np.arange(lengths.shape[0], dtype=np.int64) * n, lengths)
    keys = base + draw(base.shape[0])           # row · n + rank
    order = np.argsort(keys, kind="stable")
    in_order = keys[order]
    first = np.ones(order.shape[0], bool)
    first[1:] = in_order[1:] != in_order[:-1]
    taken = [in_order[first]]       # sorted: the first draws, then the redraws
    todo = np.sort(order[~first])
    while todo.size:
        cand = base[todo] + draw(todo.size)
        new = np.ones(cand.shape[0], bool)
        for level in taken:
            at = np.minimum(np.searchsorted(level, cand), level.shape[0] - 1)
            new &= level[at] != cand
        settled, which = np.unique(cand[new], return_index=True)
        accepted = np.flatnonzero(new)[which]
        keys[todo[accepted]] = settled
        taken[1:] = [np.sort(np.concatenate(taken[1:] + [settled]))]
        todo = np.delete(todo, accepted)
    return keys - base


def _panel(config: dict, seed: int, i: int, cdf: np.ndarray,
           ids: np.ndarray) -> sp.csr_matrix:
    """Row block ``i`` as canonical CSR (sorted, distinct columns), float32,
    rows of unit 2-norm."""
    rng = seeds.rng(seed, f"panel.{i}")
    rows, n = config["rows_per_panel"], config["n"]
    law = config["row_length"]
    mu = np.log(config["nnz_per_row_mean"]) - law["sigma"] ** 2 / 2.0
    lengths = np.clip(np.rint(rng.lognormal(mu, law["sigma"], rows)),
                      law["min"], law["max"]).astype(np.int64)
    indptr = np.concatenate([[0], np.cumsum(lengths)])
    cols = ids[_distinct_ranks(rng, cdf, lengths)]
    vals = np.abs(rng.standard_normal(indptr[-1], dtype=np.float32))
    X = sp.csr_matrix((vals, cols, indptr), shape=(rows, n))
    X.sort_indices()
    norms = np.sqrt(np.add.reduceat(
        X.data.astype(np.float64) ** 2, X.indptr[:-1]))     # every row has ≥ 1
    X.data /= np.repeat(norms, lengths).astype(np.float32)
    return X


def setup(config: dict, traffic: dict, seed: int) -> State:
    from libskylark_tpu import sketch as sk
    from libskylark_tpu.base.context import Context
    from libskylark_tpu.base.sparse import SparseMatrix

    if config["family"] != "CWT" or config["dimension"] != "rowwise":
        raise ValueError("sparse_hash_apply drives a rowwise CWT, got "
                         f"{config['family']!r} {config['dimension']!r}")
    skew = config["column_skew"]
    cdf = _zipf_cdf(config["n"], skew["exponent"])
    ids = seeds.rng(seed, "feature_ids").permutation(config["n"]).astype(np.int32)
    with concurrent.futures.ThreadPoolExecutor(config["panels"]) as pool:
        host = list(pool.map(lambda i: _panel(config, seed, i, cdf, ids),
                             range(config["panels"])))
    context_seed = seeds.context_seed(seed)
    transform = sk.CWT(config["n"], config["s"], Context(context_seed))
    panels = [SparseMatrix.from_scipy(X) for X in host]
    state = State(config, seed, context_seed, transform, panels, host, sk.ROWWISE)
    for i in range(len(panels)):    # each block placed on the device, here
        step(state, i)
    return state


def describe(state: State) -> dict:
    nnz = [int(X.nnz) for X in state.host]
    return {"path": "sparse", "rows": state.host[0].shape[0], "nnz": sum(nnz),
            "nnz_min": min(nnz), "nnz_max": max(nnz)}


def keep(state: State) -> int:
    return len(state.panels)


def step(state: State, i: int):
    return state.transform.apply(
        state.panels[i % len(state.panels)], state.rowwise).block_until_ready()


def _block_sums(Z) -> np.ndarray:
    """Σ_r Z[r, :], float32 within blocks of rows, float64 across them."""
    pad = -Z.shape[0] % SUM_BLOCK
    blocks = jnp.pad(Z, ((0, pad), (0, 0))).reshape(-1, SUM_BLOCK, Z.shape[1])
    return np.asarray(jnp.sum(blocks, axis=1), np.float64).sum(axis=0)


def _facts(state: State, p: int) -> dict:
    """What the check needs of block ``p``'s operand, summed once: ‖X‖²_F,
    the column sums, and the Gram matrix of its hottest columns."""
    if p not in state.facts:
        X, cfg = state.host[p], state.config
        data64 = X.data.astype(np.float64)
        energy = np.bincount(X.indices, weights=data64 ** 2, minlength=cfg["n"])
        hot = np.sort(np.argsort(energy)[-cfg["hot_columns"]:])
        dense_hot = jnp.asarray(X[:, hot].toarray())
        gram = jnp.dot(dense_hot.T, dense_hot, precision=jax.lax.Precision.HIGHEST)
        state.facts[p] = {
            "sq_norm": float(energy.sum()),
            "column_sums": np.bincount(X.indices, weights=data64,
                                       minlength=cfg["n"]),
            "hot": hot, "gram": gram}
    return state.facts[p]


def check(state: State, kept: list) -> dict:
    """The numbers compared, each the worst over the kept outputs."""
    cfg = state.config
    n, s = cfg["n"], cfg["s"]
    h, v = reference.streams(state.context_seed, 0, n, s)
    got = {"rel_max": 0.0, "norm_dev": 0.0, "colsum_dev": 0.0}
    served = _Served(n)     # the buckets and signs the outputs themselves show

    def worst(name, value):
        got[name] = max(got[name], value if np.isfinite(value) else np.inf)

    for i, out in kept:
        p = i % len(state.panels)
        X, facts = state.host[p], _facts(state, p)
        if out.shape != (X.shape[0], s):
            raise AssertionError(f"served shape {out.shape}")
        idx = np.sort(seeds.rng(state.seed, f"rows.{p}").choice(
            X.shape[0], cfg["check_rows"], replace=False))
        ref = reference.apply_rows(X[idx].toarray(), h, v, s)
        rows_out = out[jnp.asarray(idx)]
        worst("rel_max", float(jnp.max(jnp.abs(rows_out - ref))
                               / jnp.max(jnp.abs(ref))))
        served.read(X[idx], np.asarray(rows_out))
        expected = reference.expected_sq_norm(
            facts["sq_norm"], facts["gram"], h[facts["hot"]], v[facts["hot"]])
        sq = float(np.sum(_block_sums(out * out)))
        worst("norm_dev", abs((sq / expected) ** 0.5 - 1.0))
        want = np.asarray(reference.bucket_sums(
            jnp.asarray(facts["column_sums"], jnp.float32), h, v, s), np.float64)
        worst("colsum_dev", float(np.max(np.abs(_block_sums(out) - want))
                                  / np.max(np.abs(want))))
    # the guarantees the configuration states, held to what was served:
    # h uniform on [0, s), v = ±1
    got["bucket_chi2_z"], got["sign_mean_z"] = served.law_z_scores(s)
    _log_counter(state)
    return got


class _Served:
    """The bucket and the sign of each feature as the served rows show them.
    A feature that shares its bucket with no other feature of its row leaves
    its value there to the bit (0 + x = x, and −x is exact), so a result
    entry that equals ±one stored value of its row tells that feature's
    bucket and sign. Entries that sum several features, or equal none or two
    of the row's values, tell nothing and are passed over."""

    def __init__(self, n: int):
        self.bucket = np.full(n, -1, np.int64)
        self.sign = np.zeros(n, np.float32)
        self.conflicts = 0      # a feature shown in two buckets, or both signs

    def read(self, X_rows: sp.csr_matrix, Z_rows: np.ndarray) -> None:
        for r in range(X_rows.shape[0]):
            lo, hi = X_rows.indptr[r], X_rows.indptr[r + 1]
            values, features = X_rows.data[lo:hi], X_rows.indices[lo:hi]
            by_value = np.argsort(values)
            sorted_values = values[by_value]
            buckets = np.flatnonzero(Z_rows[r])
            entries = Z_rows[r, buckets]
            at = np.searchsorted(sorted_values, np.abs(entries))
            near = np.minimum(at, hi - lo - 1)
            # one stored value equals |entry|, and no second one does
            hit = (at < hi - lo) & (sorted_values[near] == np.abs(entries))
            hit &= np.append(sorted_values[1:] != sorted_values[:-1], True)[near]
            c = features[by_value[near[hit]]]
            b, sign = buckets[hit], np.sign(entries[hit])
            seen = self.bucket[c] >= 0
            self.conflicts += int(np.sum(
                seen & ((self.bucket[c] != b) | (self.sign[c] != sign)))
                + c.size - np.unique(c).size)
            self.bucket[c], self.sign[c] = b, sign

    def law_z_scores(self, s: int) -> tuple:
        """The z-scores of the features read, against uniform buckets and
        fair signs; infinite where the outputs show a feature two ways or
        too few features for the test (under five a bucket)."""
        known = self.bucket >= 0
        if self.conflicts or known.sum() < 5 * s:
            return float("inf"), float("inf")
        return reference.law_z_scores(jnp.asarray(self.bucket[known], jnp.int32),
                                      jnp.asarray(self.sign[known]), s)


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
    reference computed with bfloat16 values in the program's place, the
    program on operands that lack the last stored nonzero of each row, and
    the plain reference under streams that break their laws (odd buckets
    folded onto even ones, three signs in four positive)."""
    from libskylark_tpu.base.sparse import SparseMatrix

    cfg = state.config
    h, v = reference.streams(state.context_seed, 0, cfg["n"], cfg["s"])
    coo: dict = {}
    dropped: dict = {}

    def reference_in_place(i, h, v, precision):
        p = i % len(state.panels)
        if p not in coo:
            X = state.host[p].tocoo()
            pad = -X.nnz % COO_GRANULE      # value 0.0 at (0, 0): exact zeros
            coo[p] = tuple(jnp.asarray(np.pad(a, (0, pad)))
                           for a in (X.row, X.col, X.data))
        return reference.apply_coo(*coo[p], h, v, (state.host[p].shape[0], cfg["s"]),
                                   precision).block_until_ready()

    def reference_bf16(i):
        return reference_in_place(i, h, v, "bf16")

    h_folded = h & ~1
    v_skewed = jnp.where((jnp.arange(cfg["n"]) % 2 == 0) | (v > 0), 1.0, -1.0
                         ).astype(v.dtype)

    def reference_breaks_laws(i):
        return reference_in_place(i, h_folded, v_skewed, "highest")

    def program_drops_last(i):
        p = i % len(state.panels)
        if p not in dropped:
            X = state.host[p]
            keep_mask = np.ones(X.nnz, bool)
            keep_mask[X.indptr[1:] - 1] = False
            lengths = np.diff(X.indptr) - 1
            dropped[p] = SparseMatrix.from_scipy(sp.csr_matrix(
                (X.data[keep_mask], X.indices[keep_mask],
                 np.concatenate([[0], np.cumsum(lengths)])), shape=X.shape))
        return state.transform.apply(dropped[p], state.rowwise).block_until_ready()

    return {"reference_bf16": reference_bf16,
            "program_drops_last": program_drops_last,
            "reference_breaks_laws": reference_breaks_laws}
