"""Dist-serve smoke — the CI pipelined-serve chaos gate
(docs/distributed).

Proves the serve-endpoint contract (``Router.submit_dist_sketch``)
over REAL process replicas under a deterministic kill, the tier the
in-process chaos battery cannot reach:

- a **3-process-replica fleet** where ONE child (``r0``) boots with a
  seeded ``SKYLARK_FAULT_PLAN`` carrying a ``crash`` spec at the
  ``dist.shard`` site — a hard ``os._exit(137)`` inside its second
  shard task, the deterministic mid-storm ``kill -9``;
- the client **future resolves normally**: zero client-visible
  failures, coverage **1.0** after reassignment, merged sketch
  **bit-equal** to the one-shot ``sketch_local`` reference (the
  incremental merge tree is associativity-exact, not approximately
  equal), and the pool reaps the victim;
- the run repeats with the same seeds and the dispatch/retry/
  reassignment counts must be **identical** — ``pipeline=1``
  serializes shard dispatch, so the crash point and every recovery
  decision are replayable, not merely survivable;
- **zero engine compiles** in the measured window (shard tasks never
  touch the parent's executable cache) and **no ``/dev/shm`` leaks**
  once the fleets are down (shard operands ride the zero-copy SHM
  rings at these sizes — every segment must be unlinked at shutdown).

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

N_ROWS = 4096
D = 64
S_DIM = 32
SHARD_ROWS = 512         # 8 shard tasks of ~128 KiB — over the SHM
#                          threshold, so operands ride the rings
# SEED pins the ring placement as well as the data: at this plan
# fingerprint the 3-member ring owns shards [r1 r1 r0 r1 r2 r0 r2 r2],
# so the victim's SECOND task (shard 5) is the deterministic crash
# point mid-storm.
SEED = 42

CRASH_PLAN = json.dumps({"seed": 7, "faults": [
    {"site": "dist.shard", "crash": True, "on_hit": 2}]})


def _rows():
    return np.random.default_rng(SEED).standard_normal(
        (N_ROWS, D)).astype(np.float32)


def run_once(plan, src, ref) -> dict:
    """One fixed-seed storm: fresh 3-replica process fleet, victim
    ``r0`` armed with the crash plan, one ``submit_dist_sketch``
    through the router at ``pipeline=1`` (serialized dispatch — the
    chaos-determinism lever)."""
    from libskylark_tpu import fleet

    def victim_env(name):
        return ({"SKYLARK_FAULT_PLAN": CRASH_PLAN}
                if name == "r0" else None)

    pool = fleet.ReplicaPool(3, backend="process", max_batch=4,
                             replica_env=victim_env)
    router = fleet.Router(pool)
    try:
        failed = None
        result = None
        try:
            fut = router.submit_dist_sketch(plan, src, pipeline=1)
            result = fut.result(timeout=300)
        except Exception as e:  # noqa: BLE001 — a raise IS the failure
            failed = repr(e)
        co_stats = router.stats()["dist_coordinator"] or {}
        return {
            "failed": failed,
            "bit_equal": (result is not None
                          and bool(np.array_equal(result.SX, ref.SX))),
            "coverage": (None if result is None else result.coverage),
            "crashed": pool.crashed_names(),
            "dispatched": co_stats.get("dispatched"),
            "retried": co_stats.get("retried"),
            "reassigned": co_stats.get("reassigned"),
            "abandoned": co_stats.get("abandoned"),
        }
    finally:
        router.close()
        pool.shutdown()


def main() -> int:
    from libskylark_tpu import dist, engine
    from libskylark_tpu.fleet.shm import shm_entries

    A = _rows()
    plan = dist.ShardPlan(kind="cwt", n=N_ROWS, s_dim=S_DIM, d=D,
                          seed=SEED, shard_rows=SHARD_ROWS)
    src = dist.ArraySource(A)
    engine.reset()
    ref = dist.sketch_local(plan, src)
    shm_before = shm_entries()
    c0 = engine.stats().compiles
    violations = []

    runs = [run_once(plan, src, ref), run_once(plan, src, ref)]
    for i, r in enumerate(runs):
        if r["failed"]:
            violations.append(
                f"run {i}: client-visible failure: {r['failed']}")
        if not r["bit_equal"]:
            violations.append(
                f"run {i}: merged sketch not bit-equal to the one-shot "
                "sketch_local reference")
        if r["coverage"] != 1.0:
            violations.append(
                f"run {i}: coverage {r['coverage']} != 1.0 — shards "
                "were lost instead of reassigned")
        if r["crashed"] != ["r0"]:
            violations.append(
                f"run {i}: pool reaped {r['crashed']}, expected "
                "['r0'] (the crash-fault victim)")
        if not r["reassigned"]:
            violations.append(
                f"run {i}: the SIGKILL produced no shard reassignment")
        if r["abandoned"]:
            violations.append(
                f"run {i}: {r['abandoned']} shard(s) abandoned — the "
                "retry budget should have absorbed the crash")
    replay = {k: (runs[0][k], runs[1][k])
              for k in ("dispatched", "retried", "reassigned",
                        "abandoned")}
    if any(a != b for a, b in replay.values()):
        violations.append(
            f"recovery not replayable: fixed-seed runs disagree on "
            f"{replay}")

    compiles = engine.stats().compiles - c0
    if compiles:
        violations.append(
            f"{compiles} engine compile(s) in the measured window — "
            "dist-serve jobs must not touch the executable cache")
    leaked = [n for n in shm_entries() if n not in shm_before]
    if leaked:
        violations.append(
            f"/dev/shm leak: {leaked} outlived the fleets")

    rec = {
        "metric": "dist_serve_smoke",
        "n_rows": N_ROWS,
        "shards": plan.num_shards,
        "runs": runs,
        "replay": replay,
        "engine_compiles": compiles,
        "shm_leaked": leaked,
        "violations": violations,
    }
    print(json.dumps(rec), flush=True)
    if violations:
        print("dist-serve smoke FAILED:", file=sys.stderr)
        for v in violations:
            print(f"  - {v}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
