"""FWHT-serve smoke — the CI gate for the panel-free SRHT tier.

A fast battery asserting the panel-free SRHT contract end to end:

- **one program**: the executor is pinned ``kernel="pallas"`` and
  every SRHT flush still counts as "xla" — the SRHT flush has one
  program, the vmapped lane function;
- **zero recompiles with the pin declined**: warm the capacity
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

    from libskylark_tpu import Context, engine
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
    # -- pinned and declined: warm ladder, then zero-compile storms ---
    ex = engine.MicrobatchExecutor(max_batch=MAX_BATCH,
                                   linger_us=5000,
                                   max_queue=8 * N_REQUESTS,
                                   kernel="pallas")

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
    if set(fwht_flushes["by_backend"]) != {"xla"}:
        violations.append(
            "a pallas pin on an SRHT bucket flushed through "
            f"{sorted(fwht_flushes['by_backend'])} — the SRHT "
            "flush has one program and it must be counted")

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

    rec = {
        "metric": "fwht_smoke",
        "n_requests": N_REQUESTS,
        "n_dim": N_DIM,
        "s_dim": S_DIM,
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
