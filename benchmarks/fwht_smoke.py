"""FWHT-serve smoke — the CI gate for the panel-free SRHT tier.

A fast battery asserting the panel-free SRHT contract end to end:

- **offline tuning**: every SRHT (bucket, capacity class) workload is
  ranked by the hardware-free cost model into an in-memory plan cache
  (the committed ``benchmarks/plan_cache.json`` is never touched); the
  decision is "xla" for every bucket — the SRHT flush has one program,
  the vmapped lane function. The ``serve_cmm`` workload must enumerate
  exactly its one XLA candidate;
- **zero recompiles with selection enabled**: warm the capacity
  ladder, then two measured SRHT + compressed-matmul storms run with
  ZERO engine cache misses and ZERO recompiles, each request bit-equal
  to its own capacity-1 dispatch on integer-lattice operands;
- **compressed matmul**: the ``(estimate, bound)`` future resolves
  with the estimate inside the bound on well-conditioned data, and the
  sparse-A CWT lane is bit-equal to its densified twin.

Usage: ``python benchmarks/fwht_smoke.py`` (script/ci wires
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

N_REQUESTS = 8
MAX_BATCH = 4
CAPACITIES = (1, 2, 4)
N_DIM, S_DIM = 4096, 256          # 4^6 / 4^4: the dyadic regime


def main() -> int:
    import jax
    import scipy.sparse as sp

    from libskylark_tpu import Context, engine, tune
    from libskylark_tpu import sketch as sk
    from libskylark_tpu.sketch.fjlt import FJLT

    rng = np.random.default_rng(0)
    violations = []

    ts = [FJLT(N_DIM, S_DIM, Context(seed=i), fut="wht")
          for i in range(N_REQUESTS)]
    ops = [rng.integers(-4, 5, size=(5 + i % 4, N_DIM))
           .astype(np.float32) for i in range(N_REQUESTS)]
    t_cm = sk.CWT(1500, 256, Context(seed=77))
    cm_a = rng.standard_normal((30, 1500)).astype(np.float32)
    cm_b = rng.standard_normal((1500, 9)).astype(np.float32)

    engine.reset()
    prev_cache = tune.set_cache(tune.PlanCache(path=None))
    try:
        # -- offline tuning: SRHT ladder + the serve_cmm single lane ----
        decisions = {}
        for cap in CAPACITIES:
            w = tune.serve_workload(
                "sketch_apply", "SRHT", "float32", (8, N_DIM), S_DIM,
                cap, rowwise=True)
            plan, _cost = tune.record_ranked(w)
            ent = tune.get_cache().entry(w)
            decisions[f"srht_rw_8x{N_DIM}_s{S_DIM}/b{cap}"] = {
                "backend": plan.backend,
                "source": ent["source"] if ent else None,
            }
            if ent is None or ent.get("source") != "ranked":
                violations.append(
                    f"srht/b{cap}: no ranked plan-cache entry")
            if plan.backend != "xla":
                violations.append(
                    f"srht/b{cap}: tuner picked {plan.backend!r} — "
                    "the SRHT flush has no batched kernel")
        w_cm = tune.serve_workload(
            "compressed_matmul", "CWT", "float32", (32, 1500), 256, 1,
            nnz=16)
        cm_cands = tune.enumerate_candidates(w_cm)
        if [p.backend for p in cm_cands] != ["xla"]:
            violations.append(
                "serve_cmm enumerated candidates beyond its one XLA "
                f"lane: {[p.backend for p in cm_cands]}")

        # -- selection enabled: warm ladder, then zero-compile storms ---
        ex = engine.MicrobatchExecutor(max_batch=MAX_BATCH,
                                       linger_us=5000,
                                       max_queue=8 * N_REQUESTS)

        def storm():
            futs = [ex.submit_sketch(t, A, dimension=sk.ROWWISE)
                    for t, A in zip(ts, ops)]
            futs.append(ex.submit_compressed_matmul(cm_a, cm_b, t_cm))
            outs = [f.result(timeout=300) for f in futs]
            jax.block_until_ready(outs[:-1])
            return outs

        for cap in CAPACITIES:
            futs = [ex.submit_sketch(t, A, dimension=sk.ROWWISE)
                    for t, A in zip(ts[:cap], ops[:cap])]
            ex.flush()
            [f.result(timeout=300) for f in futs]
        storm()
        misses_before = engine.stats().misses
        recompiles_before = engine.stats().recompiles
        sel_outs = storm()
        storm()
        misses = engine.stats().misses - misses_before
        recompiles = engine.stats().recompiles - recompiles_before
        fwht_flushes = ex.stats()["fwht"]
        ex.shutdown()
        if misses:
            violations.append(
                f"{misses} engine cache miss(es) after per-bucket "
                "warmup with selection enabled")
        if recompiles:
            violations.append(
                f"{recompiles} executable recompile(s) with selection "
                "enabled")
        if not fwht_flushes["by_backend"]:
            violations.append(
                "no SRHT flushes attributed — serve.fwht_flushes went "
                "inert")

        # -- lane invariance: a storm's request vs its capacity-1 run ---
        with engine.MicrobatchExecutor(max_batch=1,
                                       linger_us=100) as ex1:
            xouts = [np.asarray(ex1.submit_sketch(
                t, A, dimension=sk.ROWWISE).result(timeout=300))
                for t, A in zip(ts, ops)]
        for i, (s_out, x) in enumerate(zip(sel_outs, xouts)):
            if not np.array_equal(np.asarray(s_out), x):
                violations.append(
                    f"SRHT request {i}: selection-enabled flush not "
                    "bit-equal to capacity-1 XLA dispatch")
                break

        # -- compressed matmul: bound + sparse/dense twin ---------------
        with engine.MicrobatchExecutor(max_batch=1,
                                       linger_us=100) as exc:
            est, bound = exc.submit_compressed_matmul(
                cm_a, cm_b, t_cm).result(timeout=300)
            err = float(np.linalg.norm(np.asarray(est) - cm_a @ cm_b))
            if err > bound:
                violations.append(
                    f"compressed matmul error {err:.3f} exceeded its "
                    f"bound {bound:.3f} on well-conditioned data")
            a_sp = sp.random(30, 1500, density=0.05, random_state=3,
                             dtype=np.float32, format="csr")
            es, _ = exc.submit_compressed_matmul(
                a_sp, cm_b, t_cm).result(timeout=300)
            ed, _ = exc.submit_compressed_matmul(
                a_sp.toarray(), cm_b, t_cm).result(timeout=300)
            if not np.array_equal(np.asarray(es), np.asarray(ed)):
                violations.append(
                    "sparse-A CWT compressed-matmul lane not bit-equal "
                    "to its densified twin")
            cm_count = exc.stats()["fwht"]["cm_submits"]
            if cm_count != 3:
                violations.append(
                    f"cm_submits counted {cm_count}, expected 3")
    finally:
        tune.set_cache(prev_cache)

    rec = {
        "metric": "fwht_smoke",
        "n_requests": N_REQUESTS,
        "n_dim": N_DIM,
        "s_dim": S_DIM,
        "decisions": decisions,
        "selection_flushes_by_backend": {
            k: v["flushes"]
            for k, v in fwht_flushes["by_backend"].items()},
        "misses_after_warmup": misses,
        "recompiles_after_warmup": recompiles,
        "cm_error": err,
        "cm_bound": float(bound),
        "violations": violations,
    }
    print(json.dumps(rec), flush=True)
    if violations:
        print("fwht smoke FAILED:", file=sys.stderr)
        for v in violations:
            print(f"  - {v}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
