"""Pallas-serve smoke — the CI kernel-selection gate's driver.

A 2-bucket serve mix asserting the r12 flush-kernel selection contract
end to end, fast enough for the per-commit gate:

- **the default is XLA**: an executor built with no pin
  (``kernel=None`` — arg > env > default) flushes every bucket of the
  mix through the vmapped XLA program, counted by backend;
- **zero recompiles with selection enabled**: that executor warms
  the capacity ladder of both buckets, then two measured storms run
  with ZERO engine cache misses and ZERO recompiles — the kernel
  choice is a static of the executable key resolved from a memoized
  (bucket, capacity) pair, so steady-state selection can never
  retrace a warm bucket;
- **bit-equality of the kernel path**: a forced-pallas coalesced CWT
  flush (exact-accumulation under the interpreter) is bit-equal to
  the capacity-1 forced-XLA dispatch, request by request — the
  scatter-free kernel IS the scatter, bit for bit; the dense (JLT)
  kernel path is held to the serve layer's numerical oracle
  (allclose — its bf16x3 regime legitimately reorders f32 sums).

Usage: ``python benchmarks/pallas_serve_smoke.py`` (script/ci wires
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

N_REQUESTS = 16          # per bucket
MAX_BATCH = 8
CAPACITIES = (1, 2, 4, 8)


def main() -> int:
    import jax

    from libskylark_tpu import Context, engine
    from libskylark_tpu import sketch as sk

    rng = np.random.default_rng(0)
    ctx = Context(seed=0)
    violations = []

    # -- the 2-bucket mix: CWT columnwise + JLT rowwise ------------------
    T_cwt = sk.CWT(40, 16, ctx)
    cwt_reqs = [(T_cwt,
                 rng.standard_normal((40, 3 + i % 4)).astype(np.float32))
                for i in range(N_REQUESTS)]
    jlt_reqs = []
    for i in range(N_REQUESTS):
        n = 112 + (i % 3) * 8
        T = sk.JLT(n, 32, ctx)
        A = rng.standard_normal((48 + (i % 4) * 4, n)).astype(np.float32)
        jlt_reqs.append((T, A))

    engine.reset()
    # -- selection enabled: warm ladder, then zero-compile storms ----
    ex = engine.MicrobatchExecutor(max_batch=MAX_BATCH,
                                   linger_us=5000,
                                   max_queue=8 * N_REQUESTS)

    def storm():
        futs = ([ex.submit_sketch(T, A, dimension=sk.COLUMNWISE)
                 for (T, A) in cwt_reqs]
                + [ex.submit_sketch(T, A, dimension=sk.ROWWISE)
                   for (T, A) in jlt_reqs])
        outs = [f.result(timeout=120) for f in futs]
        jax.block_until_ready(outs)
        return outs

    for reqs, dim in ((cwt_reqs, sk.COLUMNWISE),
                      (jlt_reqs, sk.ROWWISE)):
        for cap in CAPACITIES:
            futs = [ex.submit_sketch(T, A, dimension=dim)
                    for (T, A) in reqs[:cap]]
            ex.flush()
            [f.result(timeout=120) for f in futs]
    storm()
    misses_before = engine.stats().misses
    recompiles_before = engine.stats().recompiles
    sel_outs = storm()
    storm()
    misses = engine.stats().misses - misses_before
    recompiles = engine.stats().recompiles - recompiles_before
    sel_flushes = ex.stats()["kernel"]["by_backend"]
    ex.shutdown()
    if misses:
        violations.append(
            f"{misses} engine cache miss(es) after per-bucket "
            "warmup with selection enabled")
    if recompiles:
        violations.append(
            f"{recompiles} executable recompile(s) with selection "
            "enabled")
    if not sel_flushes:
        violations.append(
            "selection-enabled executor counted no kernel flushes "
            "— the by_backend counter went inert")
    if set(sel_flushes) - {"xla"}:
        violations.append(
            "an executor with no pin flushed through "
            f"{sorted(sel_flushes)} — the default is XLA")

    # -- bit-equality: forced kernel path vs capacity-1 XLA ----------
    with engine.MicrobatchExecutor(max_batch=MAX_BATCH,
                                   linger_us=5000,
                                   kernel="pallas") as exp:
        pfuts = ([exp.submit_sketch(T, A, dimension=sk.COLUMNWISE)
                  for (T, A) in cwt_reqs]
                 + [exp.submit_sketch(T, A, dimension=sk.ROWWISE)
                    for (T, A) in jlt_reqs])
        pouts = [np.asarray(f.result(timeout=120)) for f in pfuts]
        pstats = exp.stats()["kernel"]["by_backend"]
    if not pstats.get("pallas", {}).get("flushes"):
        violations.append(
            "forced-pallas executor served no pallas flushes "
            f"(by_backend={pstats})")
    with engine.MicrobatchExecutor(max_batch=1, linger_us=100,
                                   kernel="xla") as ex1:
        xouts = []
        for (T, A) in cwt_reqs:
            xouts.append(np.asarray(ex1.submit_sketch(
                T, A, dimension=sk.COLUMNWISE).result(timeout=120)))
        for (T, A) in jlt_reqs:
            xouts.append(np.asarray(ex1.submit_sketch(
                T, A, dimension=sk.ROWWISE).result(timeout=120)))
    n_cwt = len(cwt_reqs)
    for i in range(n_cwt):
        if not np.array_equal(pouts[i], xouts[i]):
            violations.append(
                f"CWT request {i}: kernel-path flush not bit-equal "
                "to capacity-1 XLA dispatch")
            break
    # the dense-kernel oracle band (test_pallas_dense): the batched
    # kernel's bf16x3 regime reorders f32 sums the XLA vmapped path
    # accumulates exactly
    for i in range(n_cwt, len(pouts)):
        if not np.allclose(pouts[i], xouts[i], rtol=1e-4,
                           atol=1e-4):
            violations.append(
                f"JLT request {i - n_cwt}: kernel-path flush "
                "diverged from capacity-1 XLA dispatch")
            break
    for i in range(n_cwt):
        if not np.array_equal(np.asarray(sel_outs[i]), xouts[i]):
            violations.append(
                f"CWT request {i}: selection-enabled flush not "
                "bit-equal to capacity-1 XLA dispatch")
            break

    rec = {
        "metric": "pallas_serve_smoke",
        "n_requests": 2 * N_REQUESTS,
        "max_batch": MAX_BATCH,
        "selection_flushes_by_backend": {
            k: v["flushes"] for k, v in sel_flushes.items()},
        "forced_pallas_flushes_by_backend": {
            k: v["flushes"] for k, v in pstats.items()},
        "misses_after_warmup": misses,
        "recompiles_after_warmup": recompiles,
        "violations": violations,
    }
    print(json.dumps(rec), flush=True)
    if violations:
        print("pallas-serve smoke FAILED:", file=sys.stderr)
        for v in violations:
            print(f"  - {v}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
