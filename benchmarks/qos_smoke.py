"""Multi-tenant QoS smoke — the CI qos gate's driver (docs/qos).

A mixed 4-family traffic storm (CWT sketch + graph ASE + condest +
RLSC predict) across the three priority classes, asserting the QoS
contract end to end, fast enough for the per-commit gate:

- **priority isolation**: a best_effort storm past its pressure bound
  sheds (counted, ``>0``) while every interactive request in the same
  window completes with ZERO failures — the class-ordered shed policy
  that replaced the global shed;
- **zero recompiles after warmup**: the second storm runs with zero
  engine misses/recompiles — class separation rides the bucket key,
  never the executable key, so mixed-tenant traffic compiles nothing
  new;
- **adaptive retuning without a compile**: a manually-ticked
  controller (tight interactive SLO) changes linger/batch targets
  between the storms, and the target change itself introduces zero
  compiles — the targets only move along warm capacity rungs;
- **bit-equality per endpoint**: each family's storm results are
  bit-equal to capacity-1 dispatch through a fresh max_batch=1
  executor;
- **weighted fairness evidence**: the scheduler's served counters
  show every class drained (starvation freedom).

Usage: ``python benchmarks/qos_smoke.py`` (script/ci wires
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

MAX_BATCH = 4
MAX_QUEUE = 32
N_DIM, S_DIM = 48, 16
GRAPH_N = 20
BE_STORM = 3 * MAX_QUEUE         # well past the 0.5 pressure bound


def _fail(rec, msg):
    rec["violation"] = msg
    print(json.dumps(rec), flush=True)
    return 1


def main() -> int:
    from libskylark_tpu import Context, engine, qos
    from libskylark_tpu import sketch as sk
    from libskylark_tpu.ml import graph as mgraph
    from libskylark_tpu.ml.kernels import Gaussian
    from libskylark_tpu.qos.controller import AdaptiveController

    rng = np.random.default_rng(7)
    ctx = Context(seed=7)

    # the four traffic families
    T = sk.CWT(N_DIM, S_DIM, ctx)
    sketch_ops = [rng.standard_normal((N_DIM, 3 + i % 3))
                  .astype(np.float32) for i in range(8)]
    G = mgraph.Graph()
    for _ in range(4 * GRAPH_N):
        u, v = rng.integers(0, GRAPH_N, 2)
        G.add_edge(int(u), int(v))
    cond_ops = [rng.standard_normal((24, 10)).astype(np.float32)
                for _ in range(4)]
    Xtr = rng.standard_normal((12, 4)).astype(np.float32)
    coef = rng.standard_normal((12, 3)).astype(np.float32)
    rlsc_queries = [rng.standard_normal((5, 4)).astype(np.float32)
                    for _ in range(4)]
    gk = Gaussian(4, 1.0)

    reg = qos.TenantRegistry()
    reg.register("ui", qos.INTERACTIVE)
    reg.register("svc", qos.STANDARD)
    reg.register("etl", qos.BEST_EFFORT)

    ex = engine.MicrobatchExecutor(
        max_batch=MAX_BATCH, linger_us=1000, max_queue=MAX_QUEUE,
        workers=1, tenants=reg)
    ctrl = AdaptiveController(ex, start=False)

    def storm(count_sheds: bool):
        """One mixed storm: interactive sketch+condest+rlsc, standard
        graph, plus (optionally) a best_effort sketch burst past the
        pressure bound. Returns (futures-by-family, be_sheds,
        interactive_failures)."""
        futs = {"sketch": [], "graph_ase": [], "condest": [],
                "rlsc": []}
        be_sheds = 0
        interactive = []
        for i in range(8):
            f = ex.submit_sketch(T, sketch_ops[i % 8], tenant="ui")
            futs["sketch"].append(f)
            interactive.append(f)
        for s in range(3):
            futs["graph_ase"].append(
                ex.submit_graph_ase(G, 3, seed=s, tenant="svc"))
        for A in cond_ops:
            f = ex.submit_condest(A, steps=6, seed=1, tenant="ui")
            futs["condest"].append(f)
            interactive.append(f)
        for Xq in rlsc_queries:
            f = ex.submit_rlsc_predict(gk, Xq, Xtr, coef,
                                       tenant="ui")
            futs["rlsc"].append(f)
            interactive.append(f)
        if count_sheds:
            for i in range(BE_STORM):
                try:
                    futs["sketch"].append(ex.submit_sketch(
                        T, sketch_ops[i % 8], tenant="etl",
                        timeout=0.0))
                except engine.ServeOverloadedError:
                    be_sheds += 1
        ex.flush()
        failures = 0
        results = {}
        for fam, fs in futs.items():
            out = []
            for f in fs:
                try:
                    out.append(np.asarray(f.result(timeout=120)))
                except Exception:  # noqa: BLE001 — counted below
                    out.append(None)
                    if f in interactive:
                        failures += 1
            results[fam] = out
        return results, be_sheds, failures

    rec: dict = {"bench": "QOS_SMOKE", "max_batch": MAX_BATCH,
                 "max_queue": MAX_QUEUE}

    # ---- phase 0: deterministic capacity-ladder warmup per family —
    # the storm's cohort sizes are timing-dependent, so every rung a
    # cohort COULD land on must be compiled before the measured window
    for cap in (1, 2, 4):
        fs = [ex.submit_sketch(T, sketch_ops[i % 8], tenant="ui")
              for i in range(cap)]
        fs += [ex.submit_graph_ase(G, 3, seed=s, tenant="svc")
               for s in range(min(cap, 3))]
        fs += [ex.submit_condest(cond_ops[i % 4], steps=6, seed=1,
                                 tenant="ui") for i in range(cap)]
        fs += [ex.submit_rlsc_predict(gk, rlsc_queries[i % 4], Xtr,
                                      coef, tenant="ui")
               for i in range(cap)]
        ex.flush()
        [f.result(timeout=120) for f in fs]

    # ---- phase 1: warmup storm (exercises the mixed-flow paths)
    warm_results, _, warm_failures = storm(count_sheds=False)
    if warm_failures:
        return _fail(rec, f"{warm_failures} interactive failure(s) "
                     "during warmup")
    base = engine.stats().to_dict()

    # ---- adaptive retuning between the storms: tight interactive SLO
    os.environ["SKYLARK_QOS_SLO_INTERACTIVE_MS"] = "0.0001"
    os.environ["SKYLARK_QOS_SLO_STANDARD_MS"] = "0.0001"
    try:
        changes = 0
        for _ in range(4):
            changes += ctrl.tick()
            # fresh completions between ticks so hysteresis can act
            fs = [ex.submit_sketch(T, A, tenant="ui")
                  for A in sketch_ops]
            ex.flush()
            [f.result(timeout=120) for f in fs]
    finally:
        os.environ.pop("SKYLARK_QOS_SLO_INTERACTIVE_MS", None)
        os.environ.pop("SKYLARK_QOS_SLO_STANDARD_MS", None)
    rec["controller_changes"] = changes
    rec["targets"] = ex.stats()["qos"]["targets"]
    if changes < 1:
        return _fail(rec, "adaptive controller made no target change")

    # ---- phase 2: measured storm with the best_effort burst
    results, be_sheds, failures = storm(count_sheds=True)
    after = engine.stats().to_dict()
    rec["interactive_failures"] = failures
    rec["best_effort_sheds"] = be_sheds
    rec["misses_after_warmup"] = after["misses"] - base["misses"]
    rec["recompiles_after_warmup"] = (after["recompiles"]
                                      - base["recompiles"])
    if failures:
        return _fail(rec, f"{failures} interactive failure(s) during "
                     "the best_effort storm")
    if be_sheds < 1:
        return _fail(rec, "best_effort storm shed nothing — the "
                     "pressure bound is not engaging")
    if rec["misses_after_warmup"] or rec["recompiles_after_warmup"]:
        return _fail(rec, "engine compiled inside the measured storm "
                     "(adaptation or class separation leaked into "
                     "the executable key)")

    qstats = ex.stats()["qos"]
    rec["by_class"] = {
        c: {k: qstats["by_class"][c][k]
            for k in ("admitted", "shed", "rate_limited")}
        for c in qos.CLASSES}
    rec["served"] = qstats["scheduler"]["served"]
    if qstats["by_class"]["interactive"]["shed"]:
        return _fail(rec, "interactive requests were shed")
    if rec["served"]["interactive"] < 1:
        return _fail(rec, "scheduler served no interactive cohorts")

    # ---- bit-equality vs capacity-1 dispatch, per family
    ex1 = engine.MicrobatchExecutor(max_batch=1, linger_us=100,
                                    tenants=reg)
    try:
        cap1 = {
            "sketch": [np.asarray(ex1.submit_sketch(
                T, sketch_ops[i % 8]).result(timeout=120))
                for i in range(8)],
            "graph_ase": [np.asarray(ex1.submit_graph_ase(
                G, 3, seed=s).result(timeout=120)) for s in range(3)],
            "condest": [np.asarray(ex1.submit_condest(
                A, steps=6, seed=1).result(timeout=120))
                for A in cond_ops],
            "rlsc": [np.asarray(ex1.submit_rlsc_predict(
                gk, Xq, Xtr, coef).result(timeout=120))
                for Xq in rlsc_queries],
        }
    finally:
        ex1.shutdown()
    bit_equal = {}
    for fam, refs in cap1.items():
        got = [r for r in results[fam][: len(refs)] if r is not None]
        bit_equal[fam] = (len(got) == len(refs)
                          and all(np.array_equal(a, b)
                                  for a, b in zip(got, refs)))
    rec["bit_equal_to_capacity1"] = bit_equal
    ex.shutdown()
    if not all(bit_equal.values()):
        bad = [f for f, ok in bit_equal.items() if not ok]
        return _fail(rec, f"bit-equality vs capacity-1 broke: {bad}")

    rec["ok"] = True
    print(json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
