"""Driver for one kind of operation: a rowwise hash sketch (CWT) of a sparse
operand whose result stays sparse, ``CWT(n, s, Context(seed)).apply_sparse(
SparseMatrix, ROWWISE)``, on device-resident sparse row blocks (feature
hashing of a wide libsvm-shaped corpus down to a learner's table: a sparse
row in, a sparse row out).

Set-up builds the row blocks on the host from the seed with the sibling
driver's generator (``sparse_hash_apply``: row lengths log-normal, features
Zipf over ranks scattered over ids and distinct within a row, values
|N(0, 1)|, each row scaled to unit norm), one transform, and applies it to
every block once, which places each block on the device. A step is one
``apply_sparse`` on the next block ending in ``block_until_ready`` on the
result's three device arrays; the check holds the last result of every
block, whole, to the plain reference, and reads the buckets and signs it
tests against their laws directly off the stored (row, bucket, value)
against the operand's (row, feature, value).
"""

from __future__ import annotations

import concurrent.futures
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import scipy.sparse as sp

from cellbench import seeds
from cellbench.drivers.sparse_hash_apply import _panel, _zipf_cdf
from cellbench.references import sparse_hash_sparse as reference


@dataclasses.dataclass
class State:
    config: dict
    seed: int
    context_seed: int
    context: object
    transform: object
    panels: list            # the program's SparseMatrix row blocks
    host: list              # scipy CSR of each block (canonical), for the check
    rowwise: object


def setup(config: dict, traffic: dict, seed: int) -> State:
    from libskylark_tpu import sketch as sk
    from libskylark_tpu.base.context import Context
    from libskylark_tpu.base.sparse import SparseMatrix

    if (config["family"] != "CWT" or config["dimension"] != "rowwise"
            or config["result"] != "sparse"):
        raise ValueError("sparse_hash_sparse_apply drives a rowwise CWT to a "
                         f"sparse result, got {config['family']!r} "
                         f"{config['dimension']!r} {config.get('result')!r}")
    if not hasattr(sk.CWT, "apply_sparse") or not hasattr(
            SparseMatrix, "from_device_csr"):
        raise SystemExit("this program has no device sparse -> sparse apply")
    skew = config["column_skew"]
    cdf = _zipf_cdf(config["n"], skew["exponent"])
    ids = seeds.rng(seed, "feature_ids").permutation(config["n"]).astype(np.int32)
    with concurrent.futures.ThreadPoolExecutor(config["panels"]) as pool:
        host = list(pool.map(lambda i: _panel(config, seed, i, cdf, ids),
                             range(config["panels"])))
    context_seed = seeds.context_seed(seed)
    context = Context(context_seed)
    transform = sk.CWT(config["n"], config["s"], context)
    panels = [SparseMatrix.from_scipy(X) for X in host]
    state = State(config, seed, context_seed, context, transform, panels, host,
                  sk.ROWWISE)
    for i in range(len(panels)):    # each block placed on the device, here
        step(state, i)
    return state


def describe(state: State) -> dict:
    from libskylark_tpu.sketch.sparse_serve import coalesce_kernel

    nnz = [int(X.nnz) for X in state.host]
    A = state.panels[0]
    kernel, _, cap, _ = coalesce_kernel(A.shape, state.config["s"], True,
                                        A.row_cap)
    return {"path": "sparse", "result": "sparse", "kernel": kernel, "cap": cap,
            "rows": state.host[0].shape[0], "nnz": sum(nnz),
            "nnz_min": min(nnz), "nnz_max": max(nnz),
            "lanes": ",".join(str(P.lanes) for P in state.panels)}


def keep(state: State) -> int:
    return len(state.panels)


def _finished(Z):
    """The sample's end: the result's three device arrays are there."""
    jax.block_until_ready(Z.csr_device())
    return Z


def step(state: State, i: int):
    return _finished(state.transform.apply_sparse(
        state.panels[i % len(state.panels)], state.rowwise))


def _structure(Z, ref: sp.csr_matrix) -> tuple:
    """``(defects, host lanes)`` of a served result against the reference:
    the row pointers and column ids that differ, the lanes past the stored
    count that are not 0.0 at column 0, adjacent equal and descending
    columns inside a row."""
    data, indices, indptr = (np.asarray(x) for x in Z.csr_device())
    if indptr.shape != ref.indptr.shape or Z.shape != ref.shape:
        raise AssertionError(f"served shape {Z.shape}, lanes {data.shape}")
    nnz, want = int(indptr[-1]), int(ref.nnz)
    both = min(nnz, want)
    struct = (int(np.count_nonzero(indptr != ref.indptr)) + abs(nnz - want)
              + int(np.count_nonzero(indices[:both] != ref.indices[:both]))
              + int(np.count_nonzero(data[nnz:]))
              + int(np.count_nonzero(indices[nnz:])))
    step_ = np.diff(indices[:nnz].astype(np.int64))
    inside = np.ones(max(nnz - 1, 0), bool)     # lanes j, j + 1 share a row
    ends = indptr[1:-1]
    inside[ends[(ends > 0) & (ends < nnz)] - 1] = False
    return ({"struct_defect": struct,
             "dup_defect": int(np.count_nonzero(inside & (step_ == 0))),
             "order_defect": int(np.count_nonzero(inside & (step_ < 0)))},
            (data, indices, indptr))


class _Served:
    """The bucket and the sign of each feature, read directly off stored
    entries: in a row that lost no lane to a collision the result's lanes are
    the operand's, relabelled — x and ±x are the same float32 up to the sign
    — so ordering both by (row, |value|) lays each feature beside its bucket
    and its sign. Rows whose |values| tie, or that merged lanes, and stored
    zeros tell nothing and are passed over."""

    def __init__(self, n: int):
        self.bucket = np.full(n, -1, np.int64)
        self.sign = np.zeros(n, np.float32)
        self.conflicts = 0      # a feature shown in two buckets, or both signs

    def read(self, X_rows: sp.csr_matrix, lanes: tuple, idx: np.ndarray) -> None:
        data, indices, indptr = lanes
        lo, hi = indptr[idx], indptr[idx + 1]
        whole = (hi - lo) == np.diff(X_rows.indptr)     # no lane merged
        take = np.repeat(whole, np.diff(X_rows.indptr))
        row = np.repeat(np.arange(idx.shape[0]), np.diff(X_rows.indptr))[take]
        x, feature = X_rows.data[take], X_rows.indices[take]
        at = np.concatenate([np.arange(a, b) for a, b in
                             zip(lo[whole], hi[whole])] or [np.zeros(0, int)])
        z, bucket = data[at], indices[at]
        by_x = np.lexsort((x, row))
        by_z = np.lexsort((np.abs(z), row))
        x, feature, row = x[by_x], feature[by_x], row[by_x]
        z, bucket = z[by_z], bucket[by_z]
        # a row with a tie in |value| (or any mismatch) is left out whole
        bad = x != np.abs(z)
        bad[1:] |= (x[1:] == x[:-1]) & (row[1:] == row[:-1])
        # and a stored 0.0 shows no sign
        good = ~np.isin(row, row[bad]) & (z != 0)
        by_c = np.argsort(feature[good], kind="stable")
        c, b, sign = (a[good][by_c] for a in (feature, bucket, np.sign(z)))
        twice = c[1:] == c[:-1]     # a feature met in two rows of this read
        seen = self.bucket[c] >= 0
        self.conflicts += int(
            np.sum(twice & ((b[1:] != b[:-1]) | (sign[1:] != sign[:-1])))
            + np.sum(seen & ((self.bucket[c] != b) | (self.sign[c] != sign))))
        self.bucket[c], self.sign[c] = b, sign

    def law_z_scores(self, s: int, bins: int) -> tuple:
        """The z-scores of the features read, against uniform buckets and
        fair signs; infinite where the results show a feature two ways or
        too few features for the test (under five a class)."""
        known = self.bucket >= 0
        if self.conflicts or known.sum() < 5 * bins:
            return float("inf"), float("inf")
        return reference.law_z_scores(self.bucket[known], self.sign[known], s,
                                      bins)


def check(state: State, kept: list) -> dict:
    """The numbers compared, each the worst over the kept results."""
    cfg = state.config
    n, s = cfg["n"], cfg["s"]
    h, v = reference.streams(state.context_seed, 0, n, s)
    got = {"struct_defect": 0, "dup_defect": 0, "order_defect": 0,
           "rel_max": 0.0, "norm_dev": 0.0}
    served = _Served(n)

    def worst(name, value):
        got[name] = max(got[name], value if np.isfinite(value) else np.inf)

    for i, Z in kept:
        p = i % len(state.panels)
        X = state.host[p]
        ref = reference.apply_csr(X.indptr, X.indices, X.data, h, v, s, X.shape)
        defects, lanes = _structure(Z, ref)
        for name, value in defects.items():
            worst(name, value)
        data, nnz = lanes[0], int(lanes[2][-1])
        if nnz == ref.nnz:
            worst("rel_max", float(np.max(np.abs(data[:nnz] - ref.data))
                                   / np.max(np.abs(ref.data))))
        else:
            worst("rel_max", np.inf)
        sq = float(np.sum(data[:nnz].astype(np.float64) ** 2))
        worst("norm_dev", abs(sq / float(
            np.sum(X.data.astype(np.float64) ** 2)) - 1.0))
        idx = np.sort(seeds.rng(state.seed, f"rows.{p}").choice(
            X.shape[0], min(cfg["check_rows"], X.shape[0]), replace=False))
        served.read(X[idx], lanes, idx)
    # the guarantees the configuration states, held to what was served:
    # h uniform on [0, s), v = ±1
    got["bucket_chi2_z"], got["sign_mean_z"] = served.law_z_scores(
        s, cfg["law_bins"])
    _log_counters(state, kept)
    return got


def _log_counters(state: State, kept: list) -> None:
    """The program's own counts beside the operands': the nonzeros it
    sketched, and — read now, from the kept results' counts — the lanes its
    collisions merged (a program without the counters prints nothing)."""
    from libskylark_tpu.telemetry import metrics

    stored = sum(int(Z.nnz) for _, Z in kept)   # tells the spans and counter
    snapshot = metrics.snapshot()["metrics"]
    for name in ("sketch.sparse_nnz", "sketch.sparse_merged"):
        counter = snapshot.get(name)
        if counter is not None:
            total = sum(int(v["value"]) for v in counter["values"])
            print(f"[cellbench] counter name={name} value={total} "
                  f"nnz_of_one_round={sum(int(X.nnz) for X in state.host)} "
                  f"stored_of_kept={stored}", flush=True)


def controls(state: State) -> dict:
    """Stand-ins for ``step`` that must come out not correct, each by the
    number it should: the program's stages without the sum and the
    compaction (sorted rows, collisions left as adjacent duplicates:
    ``dup_defect``), without the sort (the relabelled lanes in the operand's
    order: ``order_defect``), the program on bfloat16-rounded values
    (``rel_max``), on operands that lack their last lane-class granule
    (``struct_defect``, ``norm_dev``), and under the context's next
    allocation counter (``struct_defect``: other buckets)."""
    from libskylark_tpu import sketch as sk
    from libskylark_tpu.base.sparse import SparseMatrix
    from libskylark_tpu.engine.bucket import pow2_pad
    from libskylark_tpu.sketch import sparse_coalesce, sparse_serve

    cfg = state.config
    s, T = cfg["s"], state.transform
    made: dict = {}

    def operand(kind, p):
        if (kind, p) not in made:
            X = state.host[p]
            if kind == "bf16":
                Y = sp.csr_matrix((reference._values(X.data, "bf16"), X.indices,
                                   X.indptr), shape=X.shape)
            else:   # the last granule of the lane class cut off
                left = X.nnz - (pow2_pad(X.nnz) >> 5)
                indptr = np.minimum(X.indptr, left)
                Y = sp.csr_matrix((X.data[:left], X.indices[:left], indptr),
                                  shape=X.shape)
            made[kind, p] = SparseMatrix.from_scipy(Y)
        return made[kind, p]

    relabel = jax.jit(lambda key_data, data, indices: sparse_serve.lane_terms(
        key_data, data, indices, s_dim=s))

    def sort_rows(bucket, term, indptr, cap):
        _, bucket, term = sparse_coalesce._rows_sorted(
            indptr, bucket, term, cap=cap, n_minor=s)
        live = jnp.arange(term.shape[0]) < indptr[-1]
        return jnp.where(live, bucket, 0), jnp.where(live, term, 0.0)

    sort_rows = jax.jit(sort_rows, static_argnames=("cap",))

    def stages(i, sort):
        A = state.panels[i % len(state.panels)]
        data, indices, indptr = A.csr_device()
        bucket, term = relabel(T._alloc.key_data, data, indices)
        if sort:
            bucket, term = sort_rows(bucket, term, indptr,
                                     cap=sparse_coalesce.window_cap(A.row_cap))
        return _finished(SparseMatrix.from_device_csr(
            term, bucket, indptr, (A.height, s)))

    other = []

    def other_counter(i):
        if not other:   # the context's next allocation: counter 1
            other.append(sk.CWT(cfg["n"], s, state.context))
        return _finished(other[0].apply_sparse(
            state.panels[i % len(state.panels)], state.rowwise))

    def on(kind):
        return lambda i: _finished(T.apply_sparse(
            operand(kind, i % len(state.panels)), state.rowwise))

    return {"program_without_coalescing": lambda i: stages(i, sort=True),
            "program_without_sort": lambda i: stages(i, sort=False),
            "program_bf16_values": on("bf16"),
            "program_drops_granule": on("granule"),
            "other_allocation_counter": other_counter}
