"""Fleet smoke — the CI fleet gate's driver.

A 2-replica router run asserting the fleet subsystem's contract end
to end, fast enough for the per-commit gate:

- **warm-cache affinity**: after a capacity-ladder warmup, a measured
  storm routes with affinity hit-rate > 0.9 (sticky bounded-load
  ownership — in practice 1.0) and ZERO engine cache misses or
  recompiles. The affinity counter is what proves sticky routing:
  thread replicas share the one process-global executable cache, so
  the zero-miss check guards against compile thrash across routing,
  not against misrouting (only process replicas have per-replica
  caches where a misroute would surface as a miss);
- **correctness through the router**: every routed CWT result is
  bit-equal to the sequential ``transform.apply`` oracle (stream
  exactness survives routing);
- **clean drain-failover under an injected flush fault**: one replica
  drains mid-traffic (the per-replica preemption story) while a
  seeded ``serve.flush`` fault fires — bisection absorbs the fault,
  the router sheds the drained replica's traffic to its peer, and the
  gate asserts zero client-visible failures, zero orphaned futures,
  the drained replica off the ring, and its final drain hook fired;
- **autoscale round-trip from a warmup pack**: a 1-replica pool booted
  from a freshly built pack (cache reset in between, so the pack —
  not the builder's warm cache — supplies every executable) rides a
  throttled queue storm: the queue-depth controller scales up to 2
  (the new replica joins the router's ring via the SERVING publish),
  every storm future resolves bit-equal with zero client-visible
  failures and **zero backend compiles**, then sustained idleness
  drains the grown replica back away (the r11 SIGTERM-drain path) —
  also with zero failures. Leaked ``/dev/shm`` transport segments are
  asserted zero at exit.

Usage: ``python benchmarks/fleet_smoke.py`` (script/ci wires
``JAX_PLATFORMS=cpu``). Prints one JSON record; exits nonzero on any
violation.
"""

from __future__ import annotations

import json
import os
import sys
from concurrent.futures import wait as cf_wait

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402

N_REQUESTS = 32
MAX_BATCH = 8
CLASSES = (40, 96)          # two pow2 stream classes (pad 64 / 128)
S_DIM = 16

DRAIN_FAULT_PLAN = {
    "seed": 11,
    "faults": [
        # one transient flush fault during the drain-failover leg,
        # pinned to a tagged request the leg plants inside a
        # full-by-construction cohort: bisection must absorb it (both
        # halves re-execute clean), so it costs isolation retries but
        # zero client-visible failures. An unpinned on_hit=N spec
        # would make the gate timing-flaky: which flush attempt is
        # hit N depends on worker scheduling, and a singleton cohort
        # taking the hit cannot bisect — the client would see the
        # injected error with no code defect.
        {"site": "serve.flush", "error": "IOError_",
         "tag": "drain-poison", "times": 1},
    ],
}


def _autoscale_leg(violations) -> dict:
    """Queue storm -> scale-up observed -> idle -> scale-down drain,
    zero client-visible failures, zero compiles via the warmup pack
    (see module doc)."""
    import shutil
    import tempfile
    import time

    import jax.numpy as jnp

    from libskylark_tpu import Context, engine, fleet
    from libskylark_tpu import sketch as sk
    from libskylark_tpu.engine import warmup
    from libskylark_tpu.resilience import faults

    rng = np.random.default_rng(1)
    ctx = Context(seed=0)
    T = sk.CWT(CLASSES[0], S_DIM, ctx)
    ops = [rng.standard_normal((CLASSES[0], 3 + i % 4))
           .astype(np.float32) for i in range(24)]
    refs = [np.asarray(T.apply(jnp.asarray(A), sk.COLUMNWISE))
            for A in ops]

    pack_dir = tempfile.mkdtemp(prefix="skylark_fleet_pack_")
    rec: dict = {"pack_entries": None}
    try:
        spec = warmup.BucketSpec(
            endpoint="sketch_apply", family="CWT", n=CLASSES[0], m=6,
            s_dim=S_DIM, rowwise=False, capacities=(1, 2, 4, 8))
        manifest = warmup.build_pack(pack_dir, [spec])
        rec["pack_entries"] = len(manifest.get("entries", []))
        # reset: the pack, not the builder's warm cache, must supply
        # every executable the leg runs
        engine.reset()
        compiles0 = engine.stats().compiles
        pool = fleet.ReplicaPool(1, max_batch=MAX_BATCH,
                                 linger_us=2000, warmup_pack=pack_dir)
        router = fleet.Router(pool)
        scaler = fleet.Autoscaler(
            pool, router, min_replicas=1, max_replicas=2, up_depth=2,
            down_depth=1, up_ticks=1, down_ticks=4, cooldown_s=0.3,
            interval_s=0.05)
        try:
            # throttled storm: +10 ms per flush so the controller's
            # ticks deterministically observe the backlog
            plan = {"seed": 2, "faults": [
                {"site": "serve.flush", "stall_s": 0.01, "every": 1}]}
            failures = 0
            with faults.fault_plan(plan):
                futs = [router.submit_sketch(T, A)
                        for A in ops for _ in range(4)]
                deadline = time.monotonic() + 20
                while (time.monotonic() < deadline
                       and len(pool.names()) < 2):
                    time.sleep(0.05)
                scaled_up = len(pool.names()) == 2
                grown = [n for n in pool.names() if n != "r0"]
                if not scaled_up:
                    violations.append(
                        "autoscale leg: queue storm never scaled up")
                elif grown[0] not in router.routable():
                    violations.append(
                        "autoscale leg: grown replica never joined "
                        "the router ring")
                for i, f in enumerate(futs):
                    try:
                        out = f.result(timeout=120)
                    except Exception:  # noqa: BLE001 — counted
                        failures += 1
                        continue
                    if not np.array_equal(np.asarray(out),
                                          refs[i // 4]):
                        violations.append(
                            f"autoscale leg: request {i} diverged")
                        break
            if failures:
                violations.append(
                    f"autoscale leg: {failures} client-visible "
                    "failure(s) during scale-up storm")
            # idle: the controller must drain back to the floor
            deadline = time.monotonic() + 30
            while (time.monotonic() < deadline
                   and len(pool.names()) > 1):
                time.sleep(0.1)
            if len(pool.names()) != 1:
                violations.append(
                    "autoscale leg: idle fleet never scaled down")
            # post-shrink traffic still lands, still compile-free
            out = router.submit_sketch(T, ops[0]).result(timeout=60)
            if not np.array_equal(np.asarray(out), refs[0]):
                violations.append(
                    "autoscale leg: post-scale-down request diverged")
            compiles = engine.stats().compiles - compiles0
            if compiles:
                violations.append(
                    f"autoscale leg: {compiles} backend compile(s) — "
                    "the warmup pack did not cover the leg")
            st = scaler.stats()
            rec.update({
                "scaled_up": scaled_up,
                "scale_ups": st["scale_ups"],
                "scale_downs": st["scale_downs"],
                "client_visible_failures": failures,
                "compiles": compiles,
                "aot_loads": engine.stats().aot_loads,
                "replicas_final": len(pool.names()),
            })
        finally:
            scaler.close()
            router.close()
            pool.shutdown()
    finally:
        shutil.rmtree(pack_dir, ignore_errors=True)
    leaked = fleet.shm_entries()
    if leaked:
        violations.append(
            f"autoscale leg: leaked /dev/shm entries: {leaked}")
    return rec


def main() -> int:
    import jax.numpy as jnp

    from libskylark_tpu import Context, engine, fleet
    from libskylark_tpu import sketch as sk
    from libskylark_tpu.resilience import faults

    rng = np.random.default_rng(0)
    ctx = Context(seed=0)
    transforms = {n: sk.CWT(n, S_DIM, ctx) for n in CLASSES}
    reqs = []
    for i in range(N_REQUESTS):
        n = CLASSES[i % len(CLASSES)]
        A = rng.standard_normal((n, 3 + i % 4)).astype(np.float32)
        reqs.append((transforms[n], A))
    refs = [np.asarray(T.apply(jnp.asarray(A), sk.COLUMNWISE))
            for (T, A) in reqs]

    engine.reset()
    violations = []
    # linger long enough that a mid-burst flusher expiry (which could
    # strand the drain leg's tagged request in an undersized cohort)
    # needs a >0.2 s stall between two adjacent submits — full cohorts
    # still dispatch immediately, so the storm legs never wait on it
    pool = fleet.ReplicaPool(2, max_batch=MAX_BATCH, linger_us=200_000)
    router = fleet.Router(pool)

    def storm():
        futs = [router.submit_sketch(T, A) for (T, A) in reqs]
        return [f.result(timeout=120) for f in futs]

    # -- warmup: the capacity ladder of both classes ---------------------
    for c_idx in range(len(CLASSES)):
        idxs = [i for i in range(N_REQUESTS)
                if i % len(CLASSES) == c_idx]
        cap = 1
        while cap <= MAX_BATCH:
            futs = [router.submit_sketch(*reqs[i]) for i in idxs[:cap]]
            [f.result(timeout=120) for f in futs]
            cap *= 2
    storm()

    # -- measured storm: warm affinity, zero compiles --------------------
    # engine.stats() returns the LIVE mutable counter object, so the
    # before-snapshot must capture the int, not the object
    misses_before = engine.stats().misses
    r0 = router.stats()
    outs = storm()
    st1 = engine.stats()
    r1 = router.stats()
    routed = r1["routed"] - r0["routed"]
    hits = r1["affinity_hit"] - r0["affinity_hit"]
    hit_rate = hits / routed if routed else 0.0
    misses = st1.misses - misses_before
    if hit_rate <= 0.9:
        violations.append(
            f"affinity hit-rate {hit_rate:.3f} <= 0.9 after warmup")
    if misses:
        violations.append(
            f"{misses} engine cache miss(es) on the warm fleet")
    if st1.recompiles:
        violations.append(
            f"{st1.recompiles} executable recompile(s) on the warm "
            "replica")
    for i, (o, ref) in enumerate(zip(outs, refs)):
        if not np.array_equal(np.asarray(o), ref):
            violations.append(
                f"request {i} not bit-equal to transform.apply "
                "through the router")
            break

    # -- drain-failover under an injected flush fault --------------------
    victim = router.owner_of("sketch_apply", transform=reqs[0][0],
                             A=reqs[0][1], dimension=None)
    by_replica_before = dict(r1["by_replica"])
    hooks = []
    pool.on_replica_drain(victim, lambda: hooks.append(victim))
    drain_failures = orphans = 0
    with faults.fault_plan(DRAIN_FAULT_PLAN):
        futs, exp = [], []
        # plant the tagged request inside a full-by-construction
        # cohort on the victim: MAX_BATCH same-class submits
        # back-to-back reach the fast path at capacity, and with the
        # tag at position 1 no realistic flusher-expiry fragmentation
        # can leave it in a singleton cohort (see DRAIN_FAULT_PLAN)
        burst = [reqs[2 * j] for j in range(MAX_BATCH)]
        for j, (T, A) in enumerate(burst):
            if j == 1:
                with faults.tag("drain-poison"):
                    futs.append(router.submit_sketch(T, A))
            else:
                futs.append(router.submit_sketch(T, A))
            exp.append(refs[2 * j])
        for i, (T, A) in enumerate(reqs):
            futs.append(router.submit_sketch(T, A))
            exp.append(refs[i])
            if i == N_REQUESTS // 4:
                drained = pool.preempt_replica(victim, timeout=60)
        fired = faults.fired()
        # bounded wait, THEN done-check: calling result() first would
        # make the orphan check unreachable (it either returns or
        # raises) — chaos_battery's _fleet_storm sets the idiom
        cf_wait(futs, timeout=120)
        for i, f in enumerate(futs):
            if not f.done():
                orphans += 1
            elif f.exception() is not None:
                drain_failures += 1
            elif not np.array_equal(np.asarray(f.result()), exp[i]):
                violations.append(
                    f"drain leg: request {i} diverged from oracle")
    if not drained:
        violations.append("victim replica did not drain to quiescence")
    if hooks != [victim]:
        violations.append(
            f"final drain hook fired {hooks!r}, expected [{victim!r}]")
    if drain_failures:
        violations.append(
            f"{drain_failures} client-visible failure(s) during the "
            "one-replica drain")
    if orphans:
        violations.append(f"{orphans} orphaned future(s)")
    if victim in router.routable():
        violations.append("drained replica still on the routing ring")
    if not fired:
        violations.append(
            "injected flush fault never fired — the drain-failover "
            "leg went inert (retune on_hit)")
    surviving = [n for n in pool.names() if n != victim]
    # delta across the drain leg only — the warmup ladder already
    # spread traffic over both replicas, so a whole-run count could
    # never catch a failover bug that black-holes post-drain traffic
    by_replica_after = router.stats()["by_replica"]
    absorbed = sum(
        by_replica_after.get(n, 0) - by_replica_before.get(n, 0)
        for n in surviving)
    if absorbed <= 0:
        violations.append(
            "no drain-leg traffic reached the surviving replica")

    router_stats = router.stats()
    replica_names = pool.names()
    router.close()
    pool.shutdown()

    # -- autoscale leg: pack-booted elastic pool -------------------------
    autoscale_rec = _autoscale_leg(violations)

    rec = {
        "metric": "fleet_smoke",
        "n_requests": N_REQUESTS,
        "replicas": replica_names,
        "router": router_stats,
        "affinity_hit_rate": round(hit_rate, 4),
        "misses_measured_window": misses,
        "recompiles": st1.recompiles,
        "drain_victim": victim,
        "drain_fault_fired": [list(f) for f in fired],
        "client_visible_failures": drain_failures,
        "autoscale": autoscale_rec,
        "violations": violations,
    }
    print(json.dumps(rec), flush=True)
    if violations:
        print("fleet smoke FAILED:", file=sys.stderr)
        for v in violations:
            print(f"  - {v}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
