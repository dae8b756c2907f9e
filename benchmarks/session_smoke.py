"""Session smoke — the CI survivable-sessions gate (docs/sessions).

Proves the stateful-session contract over REAL process replicas, the
two resilience tiers the chaos battery's in-process leg cannot:

- **Leg A — SIGTERM drain handoff**: a CWT session owned by one
  process replica of a 2-replica fleet; mid-stream the owner gets a
  real SIGTERM (``ReplicaPool.preempt_replica`` — the child's r9
  preemption handler drains its executor, which checkpoints the live
  session), the owner leaves the router's ring so the next verb
  re-resolves ownership to the peer (fencing the drained owner's
  lease), the peer resumes from the checkpoint, and the stream
  continues. Asserts: the peer resumed from a *checkpoint* (not a
  full journal replay), at least one counted handoff, zero
  client-visible failures, finalize **bit-equal** to the one-shot
  sketch of the same row stream (the ``io.chunked.iter_array_batches``
  batching of it).

- **Leg B — crash-fault replay**: the owner child boots with a seeded
  ``SKYLARK_FAULT_PLAN`` carrying the ``crash`` spec (hard
  ``os._exit`` at the ``session.append`` site — the deterministic
  ``kill -9``, riding the pool's ``replica_env`` seat into ONE
  victim). The kill lands before the append is journaled; the
  client's same-seq retry replays onto the peer from the journal.
  Asserts: the pool reaped the crashed member
  (``crashed_names()``), an attached autoscaler replaced it back to
  the floor (the pack-boot replacement path), zero client-visible
  failures, finalize bit-equal.

Both legs also assert zero engine recompiles (sessions never touch
the executable cache — chaos must not start). Prints one JSON record;
exits nonzero on any violation.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402

N_ROWS = 96
D = 8
S_DIM = 16
BATCH = 16
SEED = 29

CRASH_PLAN = json.dumps({"seed": 7, "faults": [
    {"site": "session.append", "crash": True, "on_hit": 3}]})


def _rows():
    return np.random.default_rng(SEED).standard_normal(
        (N_ROWS, D)).astype(np.float32)


def _reference(A):
    """The one-shot sketch of the same row stream: the session's
    io.chunked batching concatenates back to A, and the CWT session is
    bit-equal to the one-shot apply by construction."""
    import jax.numpy as jnp

    from libskylark_tpu import Context
    from libskylark_tpu import sketch as sk
    from libskylark_tpu.io.chunked import iter_array_batches

    seen = [Xb for Xb, _ in iter_array_batches(A, BATCH)]
    assert np.array_equal(np.concatenate(seen), A)
    return np.asarray(sk.CWT(N_ROWS, S_DIM, Context(seed=SEED)).apply(
        jnp.asarray(A), sk.COLUMNWISE))


def _stream(router, pool, sid, A, *, preempt_after=None):
    """Drive the append stream with bounded same-seq retries; returns
    (client_visible_failures, retries)."""
    failures = retries = 0
    n_batches = N_ROWS // BATCH
    for i in range(n_batches):
        if preempt_after is not None and i == preempt_after:
            pool.preempt_replica(router.session_owner(sid))
        for _attempt in range(4):
            try:
                seq, rows = router.session_append(
                    sid, A[i * BATCH:(i + 1) * BATCH],
                    seq=i + 1).result(timeout=60.0)
                assert (seq, rows) == (i + 1, (i + 1) * BATCH)
                break
            except Exception:  # noqa: BLE001 — retry the same seq
                retries += 1
                time.sleep(0.2)
        else:
            failures += 1
    return failures, retries


def _leg_drain(A, ref) -> dict:
    from libskylark_tpu import fleet

    pool = fleet.ReplicaPool(2, backend="process", max_batch=4)
    router = fleet.Router(pool)
    try:
        sid = router.open_sketch_session(
            "cwt", n=N_ROWS, s_dim=S_DIM, d=D, seed=SEED, owner="r0")
        failures, retries = _stream(router, pool, sid, A,
                                    preempt_after=3)
        new_owner = router.session_owner(sid)
        peer_sessions = pool.get(new_owner).stats().get("sessions") or {}
        out = router.session_finalize(sid).result(timeout=60.0)
        return {
            "bit_equal": bool(np.array_equal(out["SX"], ref)),
            "client_visible_failures": failures,
            "retries": retries,
            "handoffs": router.stats()["session_handoffs"],
            "new_owner": new_owner,
            "peer_resumed": peer_sessions.get("resumed", 0),
            "peer_replayed_records":
                peer_sessions.get("replayed_records", 0),
        }
    finally:
        router.close()
        pool.shutdown()


def _leg_crash(A, ref) -> dict:
    from libskylark_tpu import fleet

    def victim_env(name):
        # the crash spec rides into ONE child only — the chaos plan
        # must not leak into the surviving peer
        return ({"SKYLARK_FAULT_PLAN": CRASH_PLAN}
                if name == "r0" else None)

    pool = fleet.ReplicaPool(2, backend="process", max_batch=4,
                             replica_env=victim_env)
    router = fleet.Router(pool)
    scaler = fleet.Autoscaler(pool, router, min_replicas=2,
                              max_replicas=3, interval_s=0.2,
                              cooldown_s=0.5)
    try:
        sid = router.open_sketch_session(
            "cwt", n=N_ROWS, s_dim=S_DIM, d=D, seed=SEED, owner="r0")
        failures, retries = _stream(router, pool, sid, A)
        out = router.session_finalize(sid).result(timeout=60.0)
        # the autoscaler must replace the reaped member back to the
        # floor (the pack-boot path — here pack-less, same verb)
        deadline = time.monotonic() + 120.0
        while (len(pool.names()) < 2
               and time.monotonic() < deadline):
            time.sleep(0.2)
        return {
            "bit_equal": bool(np.array_equal(out["SX"], ref)),
            "client_visible_failures": failures,
            "retries": retries,
            "handoffs": router.stats()["session_handoffs"],
            "crashed": pool.crashed_names(),
            "replicas_after": pool.names(),
            "scale_ups": scaler.stats()["scale_ups"],
        }
    finally:
        scaler.close()
        router.close()
        pool.shutdown()


def main() -> int:
    import atexit
    import shutil

    from libskylark_tpu import engine

    scratch = tempfile.mkdtemp(prefix="skylark_session_smoke_")
    os.environ["SKYLARK_SESSION_DIR"] = scratch
    atexit.register(shutil.rmtree, scratch, ignore_errors=True)
    A = _rows()
    ref = _reference(A)
    engine.reset()
    violations = []

    drain_rec = _leg_drain(A, ref)
    if not drain_rec["bit_equal"]:
        violations.append(
            "drain leg: finalize not bit-equal to the one-shot sketch")
    if drain_rec["client_visible_failures"]:
        violations.append(
            f"drain leg: {drain_rec['client_visible_failures']} "
            "client-visible failure(s)")
    if drain_rec["handoffs"] < 1:
        violations.append("drain leg: no session handoff counted")
    if drain_rec["peer_resumed"] < 1:
        violations.append("drain leg: peer never resumed the session")
    if drain_rec["peer_replayed_records"]:
        violations.append(
            f"drain leg: peer replayed "
            f"{drain_rec['peer_replayed_records']} journal record(s) — "
            "the drain checkpoint did not cover the stream")

    crash_rec = _leg_crash(A, ref)
    if not crash_rec["bit_equal"]:
        violations.append(
            "crash leg: finalize not bit-equal to the one-shot sketch")
    if crash_rec["client_visible_failures"]:
        violations.append(
            f"crash leg: {crash_rec['client_visible_failures']} "
            "client-visible failure(s)")
    if crash_rec["crashed"] != ["r0"]:
        violations.append(
            f"crash leg: pool reaped {crash_rec['crashed']}, "
            "expected ['r0'] (the crash-fault victim)")
    if crash_rec["retries"] < 1:
        violations.append(
            "crash leg: the crash fault never fired (zero retries)")
    if len(crash_rec["replicas_after"]) < 2:
        violations.append(
            f"crash leg: autoscaler did not replace the dead member "
            f"(replicas: {crash_rec['replicas_after']})")
    if crash_rec["scale_ups"] < 1:
        violations.append("crash leg: no autoscaler replacement event")

    est = engine.stats()
    if est.recompiles:
        violations.append(
            f"{est.recompiles} engine recompile(s) during the "
            "session legs")

    rec = {
        "metric": "session_smoke",
        "n_rows": N_ROWS,
        "batch_rows": BATCH,
        "drain": drain_rec,
        "crash": crash_rec,
        "engine_recompiles": est.recompiles,
        "violations": violations,
    }
    print(json.dumps(rec), flush=True)
    if violations:
        print("session smoke FAILED:", file=sys.stderr)
        for v in violations:
            print(f"  - {v}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
