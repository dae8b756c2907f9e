"""Train smoke — the CI training-jobs chaos gate (docs/training).

Proves the training-as-a-service contract over REAL process replicas:
two tenants each train a kernel-ridge model via sliced Block-ADMM on a
2-replica fleet while an interactive sketch storm runs through the
same front door. One replica — the owner of tenant A's job, pinned by
session-ring probing — boots with a seeded ``SKYLARK_FAULT_PLAN``
carrying a ``train.slice`` **crash** spec: a hard ``os._exit`` fired
on its third slice attempt, BEFORE that slice's journaled append (the
deterministic ``kill -9`` mid-slice). The pool reaps the corpse, the
router's resume chain adopts the on-disk session on the surviving
peer — fencing the dead owner's lease — and the job replays exactly
the acked two-slice prefix and continues.

Asserts:

- **bit-equal resume**: both tenants' trained coefficients are
  bit-equal to an uninterrupted single-process reference run of the
  same engine with the same slice boundaries — the SIGKILL is
  invisible in the bits;
- **zero client-visible failures**: both job futures resolve with
  results (no error), and every interactive request in the storm
  succeeds within its bounded retries;
- the pool reaped exactly the victim (``crashed_names()``) and the
  router counted at least one train resume dispatch;
- **interactive p99 within gate**: best_effort training slices drain
  only in idle scheduler slots, so the storm's p99 stays under
  ``P99_GATE_S`` even with two jobs training and a replica dying.

Prints one JSON record; exits nonzero on any violation.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import threading
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402

HYPER = {"num_features": 16, "num_partitions": 2, "lam": 1e-2,
         "seed": 3, "tol": 1e-3}
BUDGET_ITERS = 200
SLICE_ITERS = 2
P99_GATE_S = 1.0
STORM_ROWS, STORM_D, STORM_S = 32, 8, 8

# fires on the victim's THIRD slice attempt, before that slice's
# append is journaled — the acked prefix the peer must replay is
# exactly two slices
CRASH_PLAN = json.dumps({"seed": 7, "faults": [
    {"site": "train.slice", "crash": True, "on_hit": 3}]})


def _krr_ops(seed, m=48, d=6):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((m, d))
    Y = (X[:, :1] > 0).astype(np.float64) * 2 - 1
    return {"X": X, "Y": Y}


def _reference(ops):
    """The uninterrupted run: the same engine, the same slice
    boundaries, one process, no chaos. The sliced job is bit-equal to
    this by the tentpole invariant (tests/test_train.py proves it at
    every boundary); the smoke proves it survives a SIGKILL."""
    from libskylark_tpu.train import make_engine

    eng = make_engine("admm_krr", dict(HYPER), ops)
    st = eng.init()
    it = 0
    while it < BUDGET_ITERS:
        st = eng.step(st, min(SLICE_ITERS, BUDGET_ITERS - it))
        it += SLICE_ITERS
        if eng.info(st)["converged"]:
            break
    return eng.result(st)


def _pick_sid(router, prefix, owner):
    """A session id whose ring preference puts ``owner`` first — the
    same deterministic construction ``submit_train_job`` dispatches
    by, probed without recording an assignment."""
    for i in range(256):
        sid = f"{prefix}{i}"
        if router._session_candidates(sid)[0] == owner:
            return sid
    raise RuntimeError(f"no session id maps to {owner!r}")


def _storm(router, stop, rec):
    """The interactive foreground: one sketch stream at
    ``qos_class="interactive"`` with bounded same-request retries;
    latency is client-perceived (retries included)."""
    from libskylark_tpu import Context
    from libskylark_tpu import sketch as sk

    T = sk.JLT(STORM_ROWS, STORM_S, Context(seed=1))
    rng = np.random.default_rng(5)
    ops = [rng.standard_normal((STORM_ROWS, STORM_D)).astype(np.float32)
           for _ in range(4)]
    # warm both replicas' executable caches before the clock starts
    for A in ops + ops:
        router.submit_sketch(T, A, qos_class="interactive").result(
            timeout=60.0)
    lat, retries, failures, i = [], 0, 0, 0
    while not stop.is_set():
        A = ops[i % len(ops)]
        t0 = time.perf_counter()
        for _attempt in range(4):
            try:
                router.submit_sketch(
                    T, A, qos_class="interactive").result(timeout=30.0)
                lat.append(time.perf_counter() - t0)
                break
            except Exception:  # noqa: BLE001 — retry through the kill
                retries += 1
                time.sleep(0.05)
        else:
            failures += 1
        i += 1
        time.sleep(0.005)
    rec["latencies"] = lat
    rec["retries"] = retries
    rec["client_visible_failures"] = failures


def main() -> int:
    import atexit
    import shutil

    from libskylark_tpu import fleet
    from libskylark_tpu.train import TrainJobSpec

    scratch = tempfile.mkdtemp(prefix="skylark_train_smoke_")
    os.environ["SKYLARK_SESSION_DIR"] = scratch
    atexit.register(shutil.rmtree, scratch, ignore_errors=True)

    ops_a, ops_b = _krr_ops(13), _krr_ops(29)
    ref_a, ref_b = _reference(ops_a), _reference(ops_b)
    violations = []

    def victim_env(name):
        # the crash spec rides into ONE child only — the chaos plan
        # must not leak into the surviving peer
        return ({"SKYLARK_FAULT_PLAN": CRASH_PLAN}
                if name == "r0" else None)

    pool = fleet.ReplicaPool(2, backend="process", max_batch=4,
                             replica_env=victim_env)
    router = fleet.Router(pool)
    storm_rec: dict = {}
    stop = threading.Event()
    try:
        # pin tenant A's job onto the victim and tenant B's onto the
        # peer, so the crash deterministically lands in A's third
        # slice while B trains undisturbed
        sid_a = _pick_sid(router, "train-krr-a", "r0")
        sid_b = _pick_sid(router, "train-krr-b", "r1")
        storm = threading.Thread(
            target=_storm, args=(router, stop, storm_rec), daemon=True)
        storm.start()
        fut_a = router.submit_train_job(
            TrainJobSpec(solver="admm_krr", hyper=dict(HYPER),
                         budget_iters=BUDGET_ITERS,
                         slice_iters=SLICE_ITERS,
                         tenant="tenant-a").to_dict(),
            operands=ops_a, session_id=sid_a)
        fut_b = router.submit_train_job(
            TrainJobSpec(solver="admm_krr", hyper=dict(HYPER),
                         budget_iters=BUDGET_ITERS,
                         slice_iters=SLICE_ITERS,
                         tenant="tenant-b").to_dict(),
            operands=ops_b, session_id=sid_b)
        job_failures = 0
        outs = {}
        for tenant, fut in (("a", fut_a), ("b", fut_b)):
            try:
                outs[tenant] = fut.result(timeout=240.0)
            except Exception as e:  # noqa: BLE001 — gate accounting
                job_failures += 1
                violations.append(
                    f"tenant {tenant}: job future failed: {e!r}")
        stop.set()
        storm.join(timeout=120.0)
        rstats = router.stats()
        crashed = pool.crashed_names()
        survivor = pool.get("r1").stats().get("train") or {}
    finally:
        stop.set()
        router.close()
        pool.shutdown()

    for tenant, ref in (("a", ref_a), ("b", ref_b)):
        out = outs.get(tenant)
        if out is None:
            continue
        if not out.get("converged"):
            violations.append(f"tenant {tenant}: job did not converge")
        if not np.array_equal(out["coef"], ref["coef"]):
            violations.append(
                f"tenant {tenant}: coefficients not bit-equal to the "
                "uninterrupted reference run")
        if out["iterations"] != ref["iterations"]:
            violations.append(
                f"tenant {tenant}: {out['iterations']} iterations, "
                f"reference ran {ref['iterations']}")
    if crashed != ["r0"]:
        violations.append(
            f"pool reaped {crashed}, expected ['r0'] (the "
            "train.slice crash-fault victim)")
    if rstats["train_resumes"] < 1:
        violations.append(
            "router counted no train resume — the kill never forced "
            "a handoff")
    if survivor.get("resumes", 0) < 1:
        violations.append(
            "surviving replica reports no manager resume — the "
            "session was not adopted from disk")
    storm_failures = storm_rec.get("client_visible_failures", 0)
    if storm_failures or job_failures:
        violations.append(
            f"client-visible failures: {storm_failures} storm, "
            f"{job_failures} job")
    lat = storm_rec.get("latencies") or []
    p99 = float(np.percentile(lat, 99)) if lat else None
    if not lat:
        violations.append("storm recorded no latencies — inert")
    elif p99 > P99_GATE_S:
        violations.append(
            f"interactive p99 {p99 * 1e3:.1f} ms over the "
            f"{P99_GATE_S * 1e3:.0f} ms gate — training slices "
            "starved the interactive class")

    rec = {
        "metric": "train_smoke",
        "budget_iters": BUDGET_ITERS,
        "slice_iters": SLICE_ITERS,
        "iterations": {t: outs[t]["iterations"] for t in outs},
        "crashed": crashed,
        "train_jobs": rstats["train_jobs"],
        "train_resumes": rstats["train_resumes"],
        "survivor_train": survivor,
        "storm_requests": len(lat),
        "storm_retries": storm_rec.get("retries", 0),
        "interactive_p99_ms": None if p99 is None else p99 * 1e3,
        "p99_gate_ms": P99_GATE_S * 1e3,
        "violations": violations,
    }
    print(json.dumps(rec), flush=True)
    if violations:
        print("train smoke FAILED:", file=sys.stderr)
        for v in violations:
            print(f"  - {v}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
