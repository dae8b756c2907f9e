"""Deterministic chaos battery — the CI chaos gate's driver.

Runs a fixed serve storm under a seeded ``SKYLARK_FAULT_PLAN`` and
asserts the resilience subsystem's contract end to end:

- **zero orphaned futures**: every submitted request resolves (result
  or exception) — a failure path that strands a future deadlocks a
  real client;
- **poison isolation**: the single tagged poison request in a *full*
  cohort fails alone with the injected error class; every cohort-mate
  re-coalesces and succeeds **bit-equal to the fault-free run**
  (transform.apply is the clean oracle — the CWT serve path is
  bit-exact against it);
- **bounded convergence**: bisection pins the poison in
  ≤ log2(max_batch) retry levels (the executor's
  ``isolation_depth_peak`` counter);
- **determinism**: two runs under the same plan seed produce the
  identical injected-fault sequence (``faults.fired()``) and identical
  surviving-request bits;
- **zero leaked executables**: the engine's jit-leak counter
  (``recompiles``) stays 0 and every miss is accounted
  (``hits + misses == executions``) — chaos must not thrash the
  executable cache;
- **clean drain**: ``drain()`` after the storm reaches quiescence;
- **deterministic router failover** (the fleet leg): a fixed-seed
  ``fleet.route`` fault storm through a 3-replica
  :class:`~libskylark_tpu.fleet.Router` — every injected route fault
  fails over to the next ring candidate, every request still resolves
  bit-equal to the fault-free oracle, the failover counter equals the
  fired-fault count, and two same-seed runs replay the identical
  fired sequence. Route checks run on the submitting thread, so the
  hit order — unlike flush-side hits under concurrent workers — is
  deterministic by construction;
- **hedged-straggler rescue** (the hedge leg): a tag-pinned
  ``stall_s`` fault makes one request's primary flush a straggler; a
  hedging router must mirror it after its fixed delay, take the
  mirror's result (``hedge_wins``), let the stalled loser complete
  (verify mode), and prove the determinism guard: both executions
  bit-equal, zero mismatches, zero orphans, and the identical fired
  sequence across two same-seed runs;
- **survivable sessions** (the session leg, docs/sessions): a CWT
  session streamed through a 2-replica router with the owner
  preempted mid-stream AND a seeded ``session.append`` fault — the
  drain handoff resumes on the peer, the same-seq retry absorbs the
  fault (idempotent replay), finalize is bit-equal to the one-shot
  sketch, zero client-visible failures, and two same-seed runs replay
  the identical fired sequence;
- **fault-tolerant distributed sketching** (the dist leg,
  docs/distributed): a fixed-seed ``dist.shard`` crash/retry storm
  through a 2-replica :class:`~libskylark_tpu.dist.
  DistSketchCoordinator` (``max_inflight=1`` serializes dispatch so
  the hit order is deterministic by construction) — every fired fault
  is absorbed by a reassigned re-execution, the full-coverage merge
  is **bit-equal to the one-shot** ``sketch_local`` reference, two
  same-seed runs replay the identical fired sequence AND identical
  bits; a second, budget-exhausting plan forces abandonment and the
  leg asserts the degraded path's exact coverage arithmetic, missing
  row ranges, and the ``min_coverage`` raise;
- **preemptible training jobs** (the train leg, docs/training): a
  sliced Block-ADMM KRR job through a 2-replica router with a seeded
  ``train.slice`` fault fired BEFORE the slice's journaled append —
  the manager's retry budget re-runs the exact same slice, the job
  completes **bit-equal to the fault-free engine run** with zero
  client-visible failures, the manager's retry counter equals the
  fired-fault count, and two same-seed runs replay the identical
  fired sequence.

Usage: ``python benchmarks/chaos_battery.py --gate`` (script/ci wires
``JAX_PLATFORMS=cpu`` and the canned ``SKYLARK_FAULT_PLAN``). Prints
one JSON record; exits nonzero on any violation. The storm uses forced
flushes and an effectively-infinite linger, so cohort composition —
and therefore the fault-hit sequence — is deterministic by
construction, which is what makes the replay comparison meaningful.
"""

from __future__ import annotations

import json
import math
import os
import sys

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Chaos runs are hardware-independent; default to CPU unless the
# caller pinned a platform (the conftest discipline).
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402

N_REQUESTS = 48
MAX_BATCH = 8
POISON_INDEX = 11       # second cohort, middle lane — a FULL cohort
S_DIM = 16
N_FEAT = 40

# The canned plan: a request-pinned poison plus a one-shot transient
# flush fault landing on a known full-cohort attempt — bisection must
# absorb it with zero client-visible failures (both halves re-execute
# clean), in contrast to the poison, which must fail exactly one
# future. The battery asserts the transient actually fired (an inert
# plan is a gate bug, not a pass).
DEFAULT_PLAN = {
    "seed": 7,
    "faults": [
        {"site": "serve.flush", "error": "SketchError", "tag": "poison"},
        {"site": "serve.flush", "error": "IOError_", "on_hit": 5},
    ],
}


def _requests():
    from libskylark_tpu import Context
    from libskylark_tpu import sketch as sk

    rng = np.random.default_rng(0)
    ctx = Context(seed=0)
    T = sk.CWT(N_FEAT, S_DIM, ctx)
    ops = [rng.standard_normal((N_FEAT, 3 + i % 4)).astype(np.float32)
           for i in range(N_REQUESTS)]
    return T, ops


def _clean_refs(T, ops):
    import jax.numpy as jnp

    from libskylark_tpu import sketch as sk

    return [np.asarray(T.apply(jnp.asarray(A), sk.COLUMNWISE))
            for A in ops]


def _storm(T, ops):
    """One deterministic storm: submit in cohort-sized groups (forced
    flush each), poison one request, drain. Returns outcomes + logs."""
    from libskylark_tpu import engine
    from libskylark_tpu.resilience import faults

    ex = engine.MicrobatchExecutor(max_batch=MAX_BATCH,
                                   linger_us=10_000_000)
    futs = []
    for i, A in enumerate(ops):
        if i == POISON_INDEX:
            with faults.tag("poison"):
                futs.append(ex.submit_sketch(T, A))
        else:
            futs.append(ex.submit_sketch(T, A))
        if (i + 1) % MAX_BATCH == 0:
            ex.flush()
    ex.flush()
    drained = ex.drain(timeout=60.0)
    outcomes = []
    for f in futs:
        if not f.done():
            outcomes.append(("ORPHANED", None))
        elif f.exception() is not None:
            outcomes.append(("ERROR", type(f.exception()).__name__))
        else:
            outcomes.append(("OK", np.asarray(f.result())))
    return outcomes, faults.fired(), ex.stats(), drained


# The fleet leg's canned plan: fleet.route-only, because route checks
# happen on the (single) submitting thread — their hit order is
# deterministic, which is what makes the replay comparison exact. A
# serve.flush spec here would race across the replicas' worker threads.
FLEET_PLAN = {
    "seed": 13,
    "faults": [
        {"site": "fleet.route", "error": "IOError_", "every": 5},
    ],
}
FLEET_REPLICAS = 3


def _fleet_storm(T, ops):
    """One deterministic routed storm over a 3-replica fleet: submit
    in cohort groups (pool-flushed each), drain. Returns outcomes,
    the fired log, and the router's counters."""
    from libskylark_tpu import fleet
    from libskylark_tpu.resilience import faults

    pool = fleet.ReplicaPool(FLEET_REPLICAS, max_batch=MAX_BATCH,
                             linger_us=10_000_000)
    router = fleet.Router(pool)
    futs = []
    for i, A in enumerate(ops):
        futs.append(router.submit_sketch(T, A))
        if (i + 1) % MAX_BATCH == 0:
            pool.flush()
    pool.flush()
    outcomes = []
    for f in futs:
        if not f.done():
            outcomes.append(("ORPHANED", None))
        elif f.exception() is not None:
            outcomes.append(("ERROR", type(f.exception()).__name__))
        else:
            outcomes.append(("OK", np.asarray(f.result())))
    stats = router.stats()
    fired = faults.fired()
    router.close()
    pool.shutdown()
    return outcomes, fired, stats


def _fleet_leg(T, ops, refs, violations):
    from libskylark_tpu.resilience import faults

    with faults.fault_plan(dict(FLEET_PLAN)):
        out1, fired1, stats1 = _fleet_storm(T, ops)
    with faults.fault_plan(dict(FLEET_PLAN)):
        out2, fired2, stats2 = _fleet_storm(T, ops)

    orphans = sum(1 for s, _ in out1 + out2 if s == "ORPHANED")
    if orphans:
        violations.append(f"fleet leg: {orphans} orphaned future(s)")
    for run, out in (("run1", out1), ("run2", out2)):
        for i, (status, val) in enumerate(out):
            if status != "OK":
                violations.append(
                    f"fleet leg {run}: request {i} got {status}/{val} "
                    "— a route fault leaked to a client")
                break
            if not np.array_equal(val, refs[i]):
                violations.append(
                    f"fleet leg {run}: request {i} not bit-equal to "
                    "the fault-free oracle")
                break
    if fired1 != fired2:
        violations.append(
            f"fleet leg: fired sequences differ across same-seed "
            f"runs: {fired1} vs {fired2}")
    if not fired1:
        violations.append("fleet leg: plan injected nothing — inert")
    if any(site != "fleet.route" for site, _, _ in fired1):
        violations.append("fleet leg: unexpected site in fired log")
    for run, st in (("run1", stats1), ("run2", stats2)):
        if st["failover"] != len(fired1):
            violations.append(
                f"fleet leg {run}: failover count {st['failover']} != "
                f"fired route faults {len(fired1)}")
        if st["routed"] != len(ops):
            violations.append(
                f"fleet leg {run}: routed {st['routed']} != "
                f"{len(ops)} submitted")
    return {
        "replicas": FLEET_REPLICAS,
        "fired": [list(f) for f in fired1],
        "failover": stats1["failover"],
        "affinity_hit_rate": stats1["affinity_hit_rate"],
        "deterministic": fired1 == fired2,
    }


# The hedge leg's canned plan: a tag-pinned STALL on the primary's
# flush (a straggler, not an error — stall_s sleeps and proceeds).
# The router's watchdog must mirror the request to the second ring-
# preference replica after its fixed hedge delay and take the mirror's
# result; verify mode lets the stalled loser complete and compares
# both bitwise — the determinism guard (the endpoints are pure, so the
# two executions must agree to the bit).
HEDGE_PLAN = {
    "seed": 17,
    "faults": [
        {"site": "serve.flush", "stall_s": 0.35, "tag": "hedge-stall"},
    ],
}
HEDGE_DELAY_MS = 50


def _hedge_storm(T, ops):
    import time as _time

    from concurrent.futures import wait as cf_wait

    from libskylark_tpu import fleet
    from libskylark_tpu.resilience import faults

    pool = fleet.ReplicaPool(2, max_batch=MAX_BATCH, linger_us=1000)
    router = fleet.Router(pool, hedge=True,
                          hedge_delay_ms=HEDGE_DELAY_MS,
                          hedge_verify=True)
    # warm BOTH replicas for the class: the mirror must answer from a
    # warm cache so the race is about queueing, not compiles
    for name in pool.names():
        pool.get(name).submit("sketch_apply", transform=T, A=ops[0],
                              dimension=None).result(timeout=120)
    # ONE tagged request: the leg isolates the straggler-rescue
    # mechanism (storm semantics are the fleet leg's job) — on a
    # loaded 1-core host a full storm would hedge on ordinary backlog
    # too, making "exactly one hedge" unassertable
    with faults.tag("hedge-stall"):
        futs = [router.submit_sketch(T, ops[0])]
    cf_wait(futs, timeout=120)
    # both-attempts-complete: wait until every executor quiesces (the
    # stalled loser's flush finishes and resolves its future)
    deadline = _time.monotonic() + 30
    while _time.monotonic() < deadline and any(
            pool.get(n).queue_depth() for n in pool.names()):
        _time.sleep(0.02)
    inflight = sum(pool.get(n).queue_depth() for n in pool.names())
    outcomes = []
    for f in futs:
        if not f.done():
            outcomes.append(("ORPHANED", None))
        elif f.exception() is not None:
            outcomes.append(("ERROR", type(f.exception()).__name__))
        else:
            outcomes.append(("OK", np.asarray(f.result())))
    stats = router.stats()
    fired = faults.fired()
    router.close()
    pool.shutdown()
    return outcomes, fired, stats, inflight


def _hedge_leg(T, ops, refs, violations):
    from libskylark_tpu.resilience import faults

    runs = []
    for _ in range(2):
        with faults.fault_plan(dict(HEDGE_PLAN)):
            runs.append(_hedge_storm(T, ops))
    (out1, fired1, stats1, in1), (out2, fired2, stats2, in2) = runs

    orphans = sum(1 for s, _ in out1 + out2 if s == "ORPHANED")
    if orphans or in1 or in2:
        violations.append(
            f"hedge leg: {orphans} orphaned future(s), "
            f"{in1 + in2} stuck in-flight")
    for run, out in (("run1", out1), ("run2", out2)):
        status, val = out[0]
        if status != "OK":
            violations.append(
                f"hedge leg {run}: request got {status}/{val}")
        elif not np.array_equal(val, refs[0]):
            violations.append(
                f"hedge leg {run}: result not bit-equal to the "
                "unhedged oracle")
    for run, st in (("run1", stats1), ("run2", stats2)):
        if st["hedged"] != 1:
            violations.append(
                f"hedge leg {run}: hedged {st['hedged']} != 1 — the "
                "injected stall did not trigger exactly one hedge")
        if st["hedge_wins"] != 1:
            violations.append(
                f"hedge leg {run}: the mirror did not win against a "
                f"{HEDGE_PLAN['faults'][0]['stall_s']}s straggler")
        if st["hedge_mismatches"]:
            violations.append(
                f"hedge leg {run}: {st['hedge_mismatches']} hedge "
                "result mismatch(es) — an endpoint is no longer "
                "deterministic")
    if fired1 != fired2:
        violations.append(
            f"hedge leg: fired sequences differ across same-seed "
            f"runs: {fired1} vs {fired2}")
    if not fired1 or any(e[2] != "stall" for e in fired1):
        violations.append(
            f"hedge leg: expected only stall firings, got {fired1}")
    return {
        "fired": [list(f) for f in fired1],
        "hedged": stats1["hedged"],
        "hedge_wins": stats1["hedge_wins"],
        "hedge_mismatches": stats1["hedge_mismatches"],
        "deterministic": fired1 == fired2 and [s for s, _ in out1]
        == [s for s, _ in out2],
    }


def _session_run(A, ref, plan_doc):
    """One fixed-seed stateful-session episode (docs/sessions): a CWT
    session streamed through a 2-replica router, the owner preempted
    mid-stream (drain handoff), an injected ``session.append`` fault
    absorbed by a same-seq retry (idempotent replay), finalize
    compared bit-equal to the one-shot sketch."""
    import shutil
    import tempfile

    from libskylark_tpu import fleet
    from libskylark_tpu.resilience import faults

    prev_dir = os.environ.get("SKYLARK_SESSION_DIR")
    scratch = tempfile.mkdtemp(prefix="skylark_chaos_sessions_")
    os.environ["SKYLARK_SESSION_DIR"] = scratch
    pool = fleet.ReplicaPool(2, max_batch=4)
    router = fleet.Router(pool)
    client_failures = 0
    retries = 0
    try:
        with faults.fault_plan(plan_doc) as plan:
            sid = router.open_sketch_session(
                "cwt", n=64, s_dim=16, d=8, seed=21, owner="r0")
            for i in range(4):
                if i == 2:
                    # SIGTERM-semantics preemption of the session
                    # owner mid-stream: checkpoint + peer resume
                    pool.preempt_replica(router.session_owner(sid))
                for attempt in range(3):
                    try:
                        router.session_append(
                            sid, A[i * 16:(i + 1) * 16],
                            seq=i + 1).result(timeout=30.0)
                        break
                    except Exception:  # noqa: BLE001 — retry same seq
                        retries += 1
                else:
                    client_failures += 1
            out = router.session_finalize(sid).result(timeout=30.0)
            fired = list(plan.fired)
        stats = router.stats()
        return {
            "bits_equal": bool(np.array_equal(out["SX"], ref)),
            "fired": fired,
            "retries": retries,
            "client_visible_failures": client_failures,
            "session_handoffs": stats["session_handoffs"],
        }
    finally:
        router.close()
        pool.shutdown()
        if prev_dir is None:
            os.environ.pop("SKYLARK_SESSION_DIR", None)
        else:
            os.environ["SKYLARK_SESSION_DIR"] = prev_dir
        shutil.rmtree(scratch, ignore_errors=True)


def _session_leg(violations):
    """Sessions under chaos, twice with the same seed: the injected
    fault sequence and the finalize bits must replay identically, with
    zero client-visible failures and at least one real handoff."""
    import jax.numpy as jnp

    from libskylark_tpu import Context
    from libskylark_tpu import sketch as sk

    A = np.random.default_rng(21).standard_normal(
        (64, 8)).astype(np.float32)
    ref = np.asarray(sk.CWT(64, 16, Context(seed=21)).apply(
        jnp.asarray(A), sk.COLUMNWISE))
    plan_doc = {"seed": 7, "faults": [
        {"site": "session.append", "error": "IOError_", "on_hit": 3}]}
    rec1 = _session_run(A, ref, plan_doc)
    rec2 = _session_run(A, ref, plan_doc)
    for run, rec in (("run1", rec1), ("run2", rec2)):
        if not rec["bits_equal"]:
            violations.append(
                f"session leg {run}: finalize not bit-equal to the "
                "one-shot sketch through drain + injected fault")
        if rec["client_visible_failures"]:
            violations.append(
                f"session leg {run}: "
                f"{rec['client_visible_failures']} client-visible "
                "failure(s)")
        if rec["session_handoffs"] < 1:
            violations.append(
                f"session leg {run}: owner preemption produced no "
                "session handoff")
    if not rec1["fired"]:
        violations.append("session leg: plan injected nothing — inert")
    if rec1["fired"] != rec2["fired"]:
        violations.append(
            f"session leg: fired sequences differ across same-seed "
            f"runs: {rec1['fired']} vs {rec2['fired']}")
    return {
        "fired": [list(f) for f in rec1["fired"]],
        "retries": rec1["retries"],
        "session_handoffs": rec1["session_handoffs"],
        "client_visible_failures": rec1["client_visible_failures"],
        "deterministic": rec1["fired"] == rec2["fired"],
    }


def _dist_run(A, plan_doc, *, retries, min_coverage):
    """One fixed-seed distributed-sketch storm (docs/distributed): a
    7-shard CWT plan over a 2-replica fleet, dispatch serialized
    (``max_inflight=1``) so the ``dist.shard`` hit order — and
    therefore the fired sequence — is deterministic by construction."""
    from libskylark_tpu import dist, fleet
    from libskylark_tpu.base import errors as sk_errors
    from libskylark_tpu.resilience import faults

    plan = dist.ShardPlan(kind="cwt", n=64, s_dim=S_DIM, d=8, seed=23,
                          shard_rows=10)
    src = dist.ArraySource(A)
    pool = fleet.ReplicaPool(2, max_batch=4)
    try:
        co = dist.DistSketchCoordinator(pool, retries=retries,
                                        max_inflight=1)
        with faults.fault_plan(plan_doc) as p:
            gate_raised = False
            result = None
            try:
                result = co.sketch(plan, src,
                                   min_coverage=min_coverage)
            except sk_errors.SketchCoverageError:
                gate_raised = True
            fired = list(p.fired)
        return {"result": result, "fired": fired,
                "gate_raised": gate_raised, "stats": co.stats(),
                "plan": plan, "source": src}
    finally:
        pool.shutdown()


def _dist_leg(violations):
    """Distributed sketching under chaos, twice per plan seed."""
    from libskylark_tpu import dist

    A = np.random.default_rng(23).standard_normal(
        (64, 8)).astype(np.float32)

    # -- retry storm: every third shard-task execution fails ------------
    storm_plan = {"seed": 7, "faults": [
        {"site": "dist.shard", "error": "IOError_", "every": 3}]}
    rec1 = _dist_run(A, storm_plan, retries=3, min_coverage=1.0)
    rec2 = _dist_run(A, storm_plan, retries=3, min_coverage=1.0)
    ref = dist.sketch_local(rec1["plan"], rec1["source"])
    for run, rec in (("run1", rec1), ("run2", rec2)):
        r = rec["result"]
        if r is None:
            violations.append(
                f"dist leg {run}: storm raised instead of absorbing "
                "the injected shard faults")
            continue
        if r.coverage != 1.0 or rec["stats"]["abandoned"]:
            violations.append(
                f"dist leg {run}: coverage {r.coverage} with "
                f"{rec['stats']['abandoned']} abandoned — the retry "
                "budget should have absorbed every fault")
        if not np.array_equal(r.SX, ref.SX):
            violations.append(
                f"dist leg {run}: merged sketch not bit-equal to the "
                "one-shot sketch_local reference")
        if rec["stats"]["retried"] < 1:
            violations.append(
                f"dist leg {run}: plan fired but nothing retried")
    if not rec1["fired"]:
        violations.append("dist leg: plan injected nothing — inert")
    if rec1["fired"] != rec2["fired"]:
        violations.append(
            f"dist leg: fired sequences differ across same-seed runs: "
            f"{rec1['fired']} vs {rec2['fired']}")
    if (rec1["result"] is not None and rec2["result"] is not None
            and not np.array_equal(rec1["result"].SX,
                                   rec2["result"].SX)):
        violations.append(
            "dist leg: merged bits differ across same-seed runs")

    # -- forced abandonment: everything after hit 2 fails ---------------
    kill_plan = {"seed": 7, "faults": [
        {"site": "dist.shard", "error": "IOError_", "after": 2}]}
    gated = _dist_run(A, kill_plan, retries=1, min_coverage=1.0)
    if not gated["gate_raised"]:
        violations.append(
            "dist leg: degraded merge below min_coverage=1.0 did not "
            "raise SketchCoverageError")
    deg = _dist_run(A, kill_plan, retries=1, min_coverage=0.25)
    r = deg["result"]
    if r is None:
        violations.append(
            "dist leg: degraded run raised despite min_coverage=0.25")
    else:
        # shards 0,1 complete (hits 1,2); shards 2..6 fail every
        # attempt: coverage = 20/64, missing = rows [20, 64)
        if (r.coverage != 20 / 64 or r.missing != ((20, 64),)
                or r.rows_merged != 20):
            violations.append(
                f"dist leg: degraded accounting wrong — coverage "
                f"{r.coverage} missing {r.missing} rows "
                f"{r.rows_merged}, expected 20/64, ((20, 64),), 20")
        if deg["stats"]["abandoned"] != 5:
            violations.append(
                f"dist leg: {deg['stats']['abandoned']} abandoned "
                "shards, expected 5")
    return {
        "fired": [list(f) for f in rec1["fired"]],
        "retried": rec1["stats"]["retried"],
        "reassigned": rec1["stats"]["reassigned"],
        "degraded_coverage": (None if r is None else r.coverage),
        "degraded_missing": (None if r is None else list(r.missing)),
        "deterministic": rec1["fired"] == rec2["fired"],
    }


def _train_run(ops, plan_doc):
    """One fixed-seed training-job episode (docs/training): a sliced
    Block-ADMM KRR job through a 2-replica router with a seeded
    ``train.slice`` fault — the fault fires BEFORE the slice's
    journaled append, the manager's retry budget re-runs the exact
    same slice, and the job completes. A single job means a single
    flusher drains its slices sequentially, so the hit order — and
    therefore the fired sequence — is deterministic by construction."""
    import shutil
    import tempfile

    from libskylark_tpu import fleet
    from libskylark_tpu.resilience import faults
    from libskylark_tpu.train import TrainJobSpec

    prev_dir = os.environ.get("SKYLARK_SESSION_DIR")
    scratch = tempfile.mkdtemp(prefix="skylark_chaos_train_")
    os.environ["SKYLARK_SESSION_DIR"] = scratch
    pool = fleet.ReplicaPool(2, max_batch=4)
    router = fleet.Router(pool)
    try:
        with faults.fault_plan(plan_doc) as plan:
            fut = router.submit_train_job(
                TrainJobSpec(solver="admm_krr", budget_iters=200,
                             slice_iters=2,
                             hyper={"num_features": 16,
                                    "num_partitions": 2, "lam": 1e-2,
                                    "seed": 3, "tol": 1e-3}).to_dict(),
                operands=ops, session_id="train-chaos")
            out, err = None, None
            try:
                out = fut.result(timeout=120.0)
            except Exception as e:  # noqa: BLE001 — leg accounting
                err = repr(e)
            fired = list(plan.fired)
        retries = sum((r.stats().get("train") or {}).get("retries", 0)
                      for r in pool.replicas())
        return {"out": out, "error": err, "fired": fired,
                "retries": retries}
    finally:
        router.close()
        pool.shutdown()
        if prev_dir is None:
            os.environ.pop("SKYLARK_SESSION_DIR", None)
        else:
            os.environ["SKYLARK_SESSION_DIR"] = prev_dir
        shutil.rmtree(scratch, ignore_errors=True)


def _train_leg(violations):
    """Training jobs under chaos, twice with the same seed: the
    injected slice fault must be absorbed by the retry budget (zero
    client-visible failures), the trained coefficients must be
    bit-equal to the uninterrupted no-chaos engine run, and two
    same-seed runs must replay the identical fired sequence."""
    from libskylark_tpu.train import make_engine

    rng = np.random.default_rng(13)
    X = rng.standard_normal((48, 6))
    ops = {"X": X, "Y": (X[:, :1] > 0).astype(np.float64) * 2 - 1}
    hyper = {"num_features": 16, "num_partitions": 2, "lam": 1e-2,
             "seed": 3, "tol": 1e-3}
    eng = make_engine("admm_krr", hyper, ops)
    st, it = eng.init(), 0
    while it < 200:
        st = eng.step(st, 2)
        it += 2
        if eng.info(st)["converged"]:
            break
    ref = eng.result(st)

    plan_doc = {"seed": 7, "faults": [
        {"site": "train.slice", "error": "IOError_", "on_hit": 2}]}
    rec1 = _train_run(ops, plan_doc)
    rec2 = _train_run(ops, plan_doc)
    for run, rec in (("run1", rec1), ("run2", rec2)):
        if rec["error"] is not None:
            violations.append(
                f"train leg {run}: job failed instead of absorbing "
                f"the injected slice fault: {rec['error']}")
            continue
        out = rec["out"]
        if not out.get("converged"):
            violations.append(f"train leg {run}: job did not converge")
        if not np.array_equal(out["coef"], ref["coef"]):
            violations.append(
                f"train leg {run}: coefficients not bit-equal to the "
                "fault-free engine run")
        if rec["retries"] != len(rec["fired"]):
            violations.append(
                f"train leg {run}: {rec['retries']} manager retries "
                f"for {len(rec['fired'])} fired fault(s) — the retry "
                "budget and the plan disagree")
    if not rec1["fired"]:
        violations.append("train leg: plan injected nothing — inert")
    if any(site != "train.slice" for site, _, _ in rec1["fired"]):
        violations.append("train leg: unexpected site in fired log")
    if rec1["fired"] != rec2["fired"]:
        violations.append(
            f"train leg: fired sequences differ across same-seed "
            f"runs: {rec1['fired']} vs {rec2['fired']}")
    return {
        "fired": [list(f) for f in rec1["fired"]],
        "retries": rec1["retries"],
        "iterations": (None if rec1["out"] is None
                       else rec1["out"]["iterations"]),
        "deterministic": rec1["fired"] == rec2["fired"],
    }


def main() -> int:
    from libskylark_tpu import engine
    from libskylark_tpu.base import errors  # noqa: F401 — class names
    from libskylark_tpu.resilience import faults

    env = os.environ.get("SKYLARK_FAULT_PLAN")

    def make_plan():
        # fresh plan per run (counters/RNG at zero) — FaultPlan.parse
        # owns the inline-JSON-or-path env convention
        return (faults.FaultPlan.parse(env) if env
                else faults.FaultPlan(DEFAULT_PLAN))

    T, ops = _requests()
    refs = _clean_refs(T, ops)

    engine.reset()
    violations = []
    plan1 = make_plan()
    with faults.fault_plan(plan1):
        out1, fired1, stats1, drained1 = _storm(T, ops)
    with faults.fault_plan(make_plan()):
        out2, fired2, stats2, drained2 = _storm(T, ops)

    # -- zero orphaned futures ------------------------------------------
    orphans = sum(1 for s, _ in out1 + out2 if s == "ORPHANED")
    if orphans:
        violations.append(f"{orphans} orphaned future(s)")
    if not (drained1 and drained2):
        violations.append("drain did not reach quiescence")

    # -- poison isolation + bit-equality of survivors -------------------
    for run, out in (("run1", out1), ("run2", out2)):
        for i, (status, val) in enumerate(out):
            if i == POISON_INDEX:
                if status != "ERROR" or val != "SketchError":
                    violations.append(
                        f"{run}: poison request got {status}/{val}, "
                        f"expected the injected SketchError")
            elif status != "OK":
                violations.append(
                    f"{run}: non-poison request {i} got {status}/{val}")
            elif not np.array_equal(val, refs[i]):
                violations.append(
                    f"{run}: request {i} not bit-equal to fault-free run")

    # -- determinism: identical fault sequence + identical bits ---------
    if fired1 != fired2:
        violations.append(
            f"fired-fault sequences differ across same-seed runs: "
            f"{fired1} vs {fired2}")
    for i, ((s1, v1), (s2, v2)) in enumerate(zip(out1, out2)):
        if s1 != s2 or (s1 == "OK" and not np.array_equal(v1, v2)):
            violations.append(f"request {i} outcome differs across runs")
    if not fired1:
        violations.append("plan injected nothing — the battery is inert")
    elif len({e[2] for e in fired1}) < 2 and env is None:
        violations.append(
            "canned plan fired only one error class — the transient-"
            "absorption leg went inert (retune the on_hit)")

    # -- bounded convergence --------------------------------------------
    depth_bound = int(math.ceil(math.log2(MAX_BATCH)))
    for run, st in (("run1", stats1), ("run2", stats2)):
        if st["isolation_depth_peak"] > depth_bound:
            violations.append(
                f"{run}: isolation depth {st['isolation_depth_peak']} > "
                f"log2(max_batch) = {depth_bound}")

    # -- fleet leg: deterministic router failover -----------------------
    fleet_rec = _fleet_leg(T, ops, refs, violations)

    # -- hedge leg: injected stall -> mirrored request ------------------
    hedge_rec = _hedge_leg(T, ops, refs, violations)

    # -- session leg: drain handoff + injected append fault -------------
    session_rec = _session_leg(violations)

    # -- dist leg: shard-crash storm + degraded-merge arithmetic --------
    dist_rec = _dist_leg(violations)

    # -- train leg: injected slice fault -> retry-budget replay ---------
    train_rec = _train_leg(violations)

    # -- lock-order witness (instrumented-lock mode) --------------------
    # With SKYLARK_LOCK_WITNESS=1 (the CI chaos gate sets it) every
    # lock the storm touched — executor state/stats/pub, engine cache,
    # health hub, fault plan, router/pool/ring — was constructed
    # instrumented, and the recorded acquisition-order graph must be
    # acyclic: the runtime half of the lock-discipline story, validated
    # against `script/lint --graph`'s static half on the same battery.
    from libskylark_tpu.base import locks as _locks

    witness_rec = None
    if _locks.witness_enabled():
        witness_rec = _locks.witness_report()
        if not witness_rec["acquisitions"]:
            violations.append(
                "lock witness enabled but recorded nothing — the "
                "instrumented-lock leg went inert")
        for v in witness_rec["violations"]:
            violations.append(
                f"lock-order cycle closed at runtime: "
                f"{v['edge'][0]} -> {v['edge'][1]} "
                f"(held {v['held']}, thread {v['thread']})")

    # -- zero leaked executables (the jit-leak counter) -----------------
    est = engine.stats()
    if est.recompiles:
        violations.append(f"{est.recompiles} executable recompile(s) "
                          "under chaos — cache thrash")
    if est.hits + est.misses != est.executions:
        violations.append(
            f"engine counters unbalanced: hits {est.hits} + misses "
            f"{est.misses} != executions {est.executions}")

    rec = {
        "metric": "chaos_battery",
        "plan_seed": plan1.seed,
        "n_requests": N_REQUESTS,
        "max_batch": MAX_BATCH,
        "faults_fired": len(fired1),
        "fired": [list(f) for f in fired1],
        "poisoned": stats1["poisoned"],
        "isolation_retries": stats1["isolation_retries"],
        "isolation_depth_peak": stats1["isolation_depth_peak"],
        "depth_bound": depth_bound,
        "engine_recompiles": est.recompiles,
        "deterministic": fired1 == fired2,
        "fleet": fleet_rec,
        "hedge": hedge_rec,
        "sessions": session_rec,
        "dist": dist_rec,
        "train": train_rec,
        "lock_witness": witness_rec,
        "violations": violations,
    }
    print(json.dumps(rec), flush=True)
    if violations:
        print("chaos battery FAILED:", file=sys.stderr)
        for v in violations:
            print(f"  - {v}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
