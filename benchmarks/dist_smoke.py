"""Dist smoke — the CI fault-tolerant-distributed-sketching gate
(docs/distributed).

Proves the shard-task contract over REAL process replicas, the
resilience tier the chaos battery's in-process dist leg cannot:

- **Leg A — SIGKILL mid-storm**: a 2-process-replica fleet where the
  victim child boots with a seeded ``SKYLARK_FAULT_PLAN`` carrying a
  ``crash`` spec at the ``dist.shard`` site (hard ``os._exit(137)``
  inside a shard task — the deterministic ``kill -9``, riding the
  pool's ``replica_env`` seat into ONE child, the r16 crash-fault
  discipline). The coordinator must reassign every in-flight and
  remaining shard of the corpse to the surviving peer and finish:
  full coverage, zero abandoned shards, final sketch **bit-equal** to
  the one-shot ``sketch_local`` reference (whose ingest is the
  ``io/chunked`` absolute batch grid), zero client-visible failures
  (``sketch()`` returns normally), the pool reaps the victim
  (``crashed_names()``), and zero engine compiles (shard tasks never
  touch the executable cache — chaos must not start compiles).

- **Leg B — forced abandonment**: an in-process coordinator under a
  fault plan that fails every shard-task attempt after the second hit
  with a one-retry budget: the ``min_coverage=1.0`` default must
  raise ``SketchCoverageError`` (never a silently-partial answer),
  and an explicit ``min_coverage=0.25`` must return a
  ``DegradedSketchResult`` whose coverage arithmetic is EXACT —
  rows merged, coverage fraction, coalesced missing row ranges.

Prints one JSON record; exits nonzero on any violation.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402

N_ROWS = 96
D = 8
S_DIM = 16
SHARD_ROWS = 12          # 8 shard tasks
SEED = 31

CRASH_PLAN = json.dumps({"seed": 7, "faults": [
    {"site": "dist.shard", "crash": True, "on_hit": 2}]})


def _rows():
    return np.random.default_rng(SEED).standard_normal(
        (N_ROWS, D)).astype(np.float32)


def _leg_crash(plan, src, ref) -> dict:
    from libskylark_tpu import dist, fleet

    def victim_env(name):
        # the crash spec rides into ONE child only — the surviving
        # peer must not inherit the chaos plan
        return ({"SKYLARK_FAULT_PLAN": CRASH_PLAN}
                if name == "r0" else None)

    pool = fleet.ReplicaPool(2, backend="process", max_batch=4,
                             replica_env=victim_env)
    try:
        co = dist.DistSketchCoordinator(pool, retries=3)
        failed = None
        result = None
        try:
            result = co.sketch(plan, src)
        except Exception as e:  # noqa: BLE001 — a raise IS the failure
            failed = repr(e)
        return {
            "failed": failed,
            "bit_equal": (result is not None
                          and bool(np.array_equal(result.SX, ref.SX))),
            "coverage": (None if result is None else result.coverage),
            "crashed": pool.crashed_names(),
            "stats": co.stats(),
        }
    finally:
        pool.shutdown()


def _leg_abandon(plan, src) -> dict:
    from libskylark_tpu import dist
    from libskylark_tpu.base import errors as sk_errors
    from libskylark_tpu.resilience import faults

    kill_plan = {"seed": 7, "faults": [
        {"site": "dist.shard", "error": "IOError_", "after": 2}]}
    co = dist.DistSketchCoordinator(retries=1, max_inflight=1)
    gate_raised = False
    with faults.fault_plan(kill_plan):
        try:
            co.sketch(plan, src)              # min_coverage default 1.0
        except sk_errors.SketchCoverageError:
            gate_raised = True
    co2 = dist.DistSketchCoordinator(retries=1, max_inflight=1)
    with faults.fault_plan(kill_plan):
        res = co2.sketch(plan, src, min_coverage=0.25)
    return {
        "gate_raised": gate_raised,
        "degraded_type": type(res).__name__,
        "coverage": res.coverage,
        "rows_merged": res.rows_merged,
        "missing": [list(r) for r in res.missing],
        "abandoned": co2.stats()["abandoned"],
    }


def main() -> int:
    from libskylark_tpu import dist, engine

    A = _rows()
    plan = dist.ShardPlan(kind="cwt", n=N_ROWS, s_dim=S_DIM, d=D,
                          seed=SEED, shard_rows=SHARD_ROWS)
    src = dist.ArraySource(A)
    engine.reset()
    # the one-shot reference: the same plan executed sequentially in
    # THIS process (io/chunked grid ingest, canonical merge tree)
    ref = dist.sketch_local(plan, src)
    violations = []

    crash_rec = _leg_crash(plan, src, ref)
    if crash_rec["failed"]:
        violations.append(
            f"crash leg: client-visible failure: {crash_rec['failed']}")
    if not crash_rec["bit_equal"]:
        violations.append(
            "crash leg: merged sketch not bit-equal to the one-shot "
            "sketch_local reference")
    if crash_rec["coverage"] != 1.0:
        violations.append(
            f"crash leg: coverage {crash_rec['coverage']} != 1.0 — "
            "shards were lost instead of reassigned")
    if crash_rec["crashed"] != ["r0"]:
        violations.append(
            f"crash leg: pool reaped {crash_rec['crashed']}, expected "
            "['r0'] (the crash-fault victim)")
    st = crash_rec["stats"]
    if st["reassigned"] < 1:
        violations.append(
            "crash leg: the SIGKILL produced no shard reassignment")
    if st["abandoned"]:
        violations.append(
            f"crash leg: {st['abandoned']} shard(s) abandoned — the "
            "retry budget should have absorbed the crash")

    abandon_rec = _leg_abandon(plan, src)
    if not abandon_rec["gate_raised"]:
        violations.append(
            "abandon leg: min_coverage=1.0 did not raise "
            "SketchCoverageError on a degraded merge")
    if abandon_rec["degraded_type"] != "DegradedSketchResult":
        violations.append(
            f"abandon leg: got {abandon_rec['degraded_type']}, "
            "expected DegradedSketchResult")
    # shards 0,1 complete (hits 1,2); shards 2..7 fail both attempts:
    # 24 rows merged of 96, missing = rows [24, 96)
    if (abandon_rec["rows_merged"] != 24
            or abandon_rec["coverage"] != 24 / 96
            or abandon_rec["missing"] != [[24, 96]]
            or abandon_rec["abandoned"] != 6):
        violations.append(
            f"abandon leg: coverage arithmetic wrong: {abandon_rec}")

    est = engine.stats()
    if est.compiles:
        violations.append(
            f"{est.compiles} engine compile(s) during the dist legs — "
            "shard tasks must not touch the executable cache")

    rec = {
        "metric": "dist_smoke",
        "n_rows": N_ROWS,
        "shards": plan.num_shards,
        "crash": crash_rec,
        "abandon": abandon_rec,
        "engine_compiles": est.compiles,
        "violations": violations,
    }
    print(json.dumps(rec), flush=True)
    if violations:
        print("dist smoke FAILED:", file=sys.stderr)
        for v in violations:
            print(f"  - {v}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
