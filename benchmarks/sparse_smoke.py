"""Sparse-serve smoke — the CI sparse-serve gate's driver.

A CSR serve mix asserting the sparse-operand hot-path contract
(docs/serving, "Sparse operands on the serve path") end to end, fast
enough for the per-commit gate:

- **one program**: the executor is pinned ``kernel="pallas"`` and
  every sparse flush still counts as "xla" — a sparse flush has one
  program, the vmapped lane function with the scatter, and a pallas
  intent on it is a counted decline;
- **ragged-nnz coalescing**: requests whose nnz differ inside one
  class land in ONE bucket and flush as one executable — asserted via
  ``request_statics`` identity, the coalesced counter, and ZERO engine
  misses/recompiles across two measured storms after the capacity-
  ladder warmup;
- **bit-equality**: every sparse flush (CWT and JLT, coalesced) is
  bit-equal to the densified reference — ``transform.apply(
  A.todense())`` — and to its own capacity-1 dispatch (lane
  invariance);
- **densify fallback**: an operand at or above
  ``SKYLARK_SPARSE_MIN_DENSITY`` routes through the dense endpoint and
  is counted (``sparse_densified``), still bit-equal;
- **sparse solve**: the CSR sketched-least-squares endpoint matches
  the dense serve solve on the densified operand bit for bit.

Usage: ``python benchmarks/sparse_smoke.py`` (script/ci wires
``JAX_PLATFORMS=cpu``). Prints one JSON record; exits nonzero on any
violation.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402

N_REQUESTS = 16
MAX_BATCH = 8
CAPACITIES = (1, 2, 4, 8)
N_DIM, M_DIM, S_DIM = 512, 12, 16
NNZ_BASE = 40                    # class 64 at the default floor


def main() -> int:
    import jax
    import scipy.sparse as sp

    from libskylark_tpu import Context, engine
    from libskylark_tpu import sketch as sk
    from libskylark_tpu.base.sparse import SparseMatrix
    from libskylark_tpu.engine.serve import request_statics

    rng = np.random.default_rng(0)
    ctx = Context(seed=0)
    violations = []

    def rand_sparse(nnz, h=N_DIM, w=M_DIM):
        r = rng.integers(0, h, nnz)
        c = rng.integers(0, w, nnz)
        v = rng.standard_normal(nnz).astype(np.float32)
        return SparseMatrix.from_scipy(
            sp.coo_matrix((v, (r, c)), shape=(h, w)))

    # ragged nnz inside one class (floor 64): 33..56
    T_cwt = sk.CWT(N_DIM, S_DIM, ctx)
    cwt_reqs = [rand_sparse(33 + (i % 8) * 3) for i in range(N_REQUESTS)]
    T_jlt = sk.JLT(N_DIM, S_DIM, ctx)
    jlt_reqs = [rand_sparse(33 + (i % 8) * 3) for i in range(N_REQUESTS)]

    # -- bucket-key stability: one statics tuple across the ragged mix --
    keys = {request_statics("sparse_sketch_apply", transform=T_cwt,
                            A=A, dimension=sk.COLUMNWISE)
            for A in cwt_reqs}
    if len(keys) != 1:
        violations.append(
            f"ragged-nnz requests split into {len(keys)} buckets — the "
            "nnz class must coalesce one class into one bucket")
    k_small = request_statics("sparse_sketch_apply", transform=T_cwt,
                              A=rand_sparse(40),
                              dimension=sk.COLUMNWISE)
    k_large = request_statics("sparse_sketch_apply", transform=T_cwt,
                              A=rand_sparse(400),
                              dimension=sk.COLUMNWISE)
    if k_small == k_large:
        violations.append(
            "nnz classes 64 and 512 keyed identically — the nnz class "
            "is not in the bucket statics")

    engine.reset()
    # -- warm ladder, then zero-compile storms ---------------------
    ex = engine.MicrobatchExecutor(max_batch=MAX_BATCH,
                                   linger_us=5000,
                                   max_queue=8 * N_REQUESTS,
                                   kernel="pallas")

    def storm():
        futs = ([ex.submit_sparse(T_cwt, A, dimension=sk.COLUMNWISE)
                 for A in cwt_reqs]
                + [ex.submit_sparse(T_jlt, A,
                                    dimension=sk.COLUMNWISE)
                   for A in jlt_reqs])
        outs = [f.result(timeout=120) for f in futs]
        jax.block_until_ready(outs)
        return outs

    for T, reqs in ((T_cwt, cwt_reqs), (T_jlt, jlt_reqs)):
        for cap in CAPACITIES:
            futs = [ex.submit_sparse(T, A, dimension=sk.COLUMNWISE)
                    for A in reqs[:cap]]
            ex.flush()
            [f.result(timeout=120) for f in futs]
    storm()
    misses_before = engine.stats().misses
    recompiles_before = engine.stats().recompiles
    outs = storm()
    storm()
    misses = engine.stats().misses - misses_before
    recompiles = engine.stats().recompiles - recompiles_before
    st = ex.stats()
    if misses:
        violations.append(
            f"{misses} engine cache miss(es) after per-bucket "
            "warmup on the sparse path")
    if recompiles:
        violations.append(
            f"{recompiles} executable recompile(s) on the warm "
            "sparse path")
    if not st["coalesced"]:
        violations.append("no coalesced sparse requests — the "
                          "ragged-nnz cohort never shared a flush")
    if not st["sparse"]["submits"]:
        violations.append("sparse submit counter inert")
    if set(st["kernel"]["by_backend"]) != {"xla"}:
        violations.append(
            "a pallas pin on a sparse bucket flushed through "
            f"{sorted(st['kernel']['by_backend'])} — the sparse "
            "flush has no batched kernel")

    # -- bit-equality: densified reference + capacity-1 ------------
    refs = ([np.asarray(T_cwt.apply(A.todense(), sk.COLUMNWISE))
             for A in cwt_reqs]
            + [np.asarray(T_jlt.apply(A.todense(), sk.COLUMNWISE))
               for A in jlt_reqs])
    for i, (o, r) in enumerate(zip(outs, refs)):
        if not np.array_equal(np.asarray(o), r):
            violations.append(
                f"request {i}: sparse flush not bit-equal to the "
                "densified reference (todense -> transform.apply)")
            break
    with engine.MicrobatchExecutor(max_batch=1,
                                   linger_us=100) as ex1:
        for i, (T, A) in enumerate(
                [(T_cwt, A) for A in cwt_reqs]
                + [(T_jlt, A) for A in jlt_reqs]):
            one = np.asarray(ex1.submit_sparse(
                T, A, dimension=sk.COLUMNWISE).result(timeout=120))
            if not np.array_equal(np.asarray(outs[i]), one):
                violations.append(
                    f"request {i}: coalesced sparse flush not "
                    "bit-equal to capacity-1 dispatch")
                break

    # -- densify fallback ------------------------------------------
    dense_ish = rand_sparse(int(N_DIM * M_DIM * 0.5))
    d0 = ex.stats()["sparse"]["densified"]
    fut = ex.submit_sparse(T_cwt, dense_ish,
                           dimension=sk.COLUMNWISE)
    got = np.asarray(fut.result(timeout=120))
    if ex.stats()["sparse"]["densified"] != d0 + 1:
        violations.append(
            "densify fallback not counted for a 50%-dense operand")
    if not np.array_equal(
            got, np.asarray(T_cwt.apply(dense_ish.todense(),
                                        sk.COLUMNWISE))):
        violations.append("densified fallback result diverged")

    # -- sparse solve ----------------------------------------------
    T_s = sk.CWT(64, 32, ctx)
    A_s = rand_sparse(30, h=64, w=6)
    B_s = rng.standard_normal((64, 2)).astype(np.float32)
    xs = np.asarray(ex.submit_sparse_solve(
        A_s, B_s, T_s).result(timeout=120))
    xd = np.asarray(ex.submit_solve(
        np.asarray(A_s.todense()), B_s, T_s).result(timeout=120))
    if not np.array_equal(xs, xd):
        violations.append(
            "sparse solve not bit-equal to the dense serve solve "
            "on the densified operand")
    ex.shutdown()

    rec = {
        "metric": "sparse_serve_smoke",
        "n_requests": 2 * N_REQUESTS,
        "max_batch": MAX_BATCH,
        "misses_after_warmup": misses,
        "recompiles_after_warmup": recompiles,
        "sparse_stats": st["sparse"],
        "violations": violations,
    }
    print(json.dumps(rec), flush=True)
    if violations:
        print("sparse-serve smoke FAILED:", file=sys.stderr)
        for v in violations:
            print(f"  - {v}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
