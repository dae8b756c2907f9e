"""Cache smoke — the CI cache gate's driver (docs/caching).

A 2-replica fleet hot-operand storm asserting the content-addressed
caching tier's contract end to end, fast enough for the per-commit
gate:

- **hit rate**: after a one-pass warmup the storm's repeat requests
  are served from the replicas' digest→result caches with aggregate
  hit-rate > 0.9;
- **one flush per unique request**: across the whole warmup + storm,
  the fleet runs EXACTLY one flush per unique (digest, statics, seed)
  — a duplicate never recomputes, and the same operand bytes under a
  different Context seed never share a flush (the miscoalesce
  regression);
- **front-door single-flight**: a concurrent storm of one fresh
  digest coalesces at the router — every follower fans bit-equal off
  ONE added flush;
- **bit-equality**: every cached result is bit-equal to the uncached
  control (the sequential ``transform.apply`` oracle — stream
  exactness survives the cache);
- **zero recompiles** across the measured storm (the cache serves
  hits without touching the executable cache);
- **residency round-trip over the process transport**: a
  ``register_operand`` broadcast to a process replica rides the SHM
  rings, a ref submit resolves bit-equal, unregister drops the pin,
  and **no /dev/shm transport segments leak** at exit.

Usage: ``python benchmarks/cache_smoke.py`` (script/ci wires
``JAX_PLATFORMS=cpu``). Prints one JSON record; exits nonzero on any
violation.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402

N_STORM = 80
N_UNIQUE = 4
MAX_BATCH = 8
CLASSES = (40, 96)          # two pow2 stream classes (pad 64 / 128)
S_DIM = 16


def _fleet_cache_stats(pool) -> dict:
    from libskylark_tpu.engine import resultcache as rc

    blocks = [pool.get(n).executor.stats().get("cache")
              for n in pool.names()]
    merged = rc.merge_cache_blocks([b for b in blocks if b])
    merged["flushes"] = sum(
        pool.get(n).executor.stats()["flushes"] for n in pool.names())
    return merged


def main() -> int:
    import jax
    import jax.numpy as jnp

    from libskylark_tpu import Context, engine, fleet
    from libskylark_tpu import sketch as sk

    engine.reset()
    violations: list = []
    rng = np.random.default_rng(0)

    # N_UNIQUE unique requests over two bucket classes, each under its
    # own Context seed — unique CONTENT, shared buckets
    uniq = []
    for i in range(N_UNIQUE):
        n = CLASSES[i % len(CLASSES)]
        T = sk.CWT(n, S_DIM, Context(seed=i))
        A = rng.standard_normal((n, 3 + i)).astype(np.float32)
        uniq.append((T, A))
    oracle = [np.asarray(T.apply(jnp.asarray(A), sk.COLUMNWISE))
              for (T, A) in uniq]

    pool = fleet.ReplicaPool(2, max_batch=MAX_BATCH, linger_us=2000,
                             cache=True)
    router = fleet.Router(pool, cache=True)
    rec: dict = {"n_storm": N_STORM, "n_unique": N_UNIQUE}
    try:
        # -- warmup: each unique computes exactly once ----------------
        for (T, A) in uniq:
            router.submit_sketch(T, A).result(timeout=120)
        # the settle callback inserts AFTER the future resolves —
        # barrier on the fleet-wide entry count before the storm
        deadline = time.monotonic() + 30
        while (_fleet_cache_stats(pool)["entries"] < N_UNIQUE
               and time.monotonic() < deadline):
            time.sleep(0.005)
        st0 = _fleet_cache_stats(pool)
        if st0["flushes"] != N_UNIQUE:
            violations.append(
                f"warmup ran {st0['flushes']} flushes for "
                f"{N_UNIQUE} unique requests")
        eng0 = engine.stats()
        compiles0 = (eng0.misses, eng0.recompiles)

        # -- hot storm: every request is a repeat ---------------------
        outs = []
        for i in range(N_STORM):
            T, A = uniq[i % N_UNIQUE]
            outs.append(np.asarray(
                router.submit_sketch(T, A).result(timeout=120)))
        st1 = _fleet_cache_stats(pool)
        eng1 = engine.stats()
        rec["hit_rate"] = st1["hit_rate"]
        rec["hits"] = st1["hits"]
        rec["misses"] = st1["misses"]
        rec["bytes_saved"] = st1["bytes_saved"]
        rec["flushes_total"] = st1["flushes"]
        rec["recompiles_storm"] = (
            eng1.misses - compiles0[0], eng1.recompiles - compiles0[1])
        if st1["hit_rate"] is None or st1["hit_rate"] <= 0.9:
            violations.append(
                f"storm hit-rate {st1['hit_rate']} <= 0.9")
        if st1["flushes"] != N_UNIQUE:
            violations.append(
                f"{st1['flushes']} flushes for {N_UNIQUE} unique "
                "requests — a duplicate recomputed or a unique "
                "coalesced")
        if rec["recompiles_storm"] != (0, 0):
            violations.append(
                f"storm compiled: misses/recompiles "
                f"{rec['recompiles_storm']}")
        for i, out in enumerate(outs):
            if not np.array_equal(out, oracle[i % N_UNIQUE]):
                violations.append(
                    f"storm request {i} diverged from the uncached "
                    "oracle")
                break

        # -- miscoalesce regression: same bytes, different seed -------
        T0, A0 = uniq[0]
        T_alt = sk.CWT(CLASSES[0], S_DIM, Context(seed=77))
        alt = np.asarray(
            router.submit_sketch(T_alt, A0).result(timeout=120))
        if np.array_equal(alt, oracle[0]):
            violations.append(
                "different-seed request returned the cached seed-0 "
                "result (miscoalesce)")
        if not np.array_equal(
                alt, np.asarray(T_alt.apply(jnp.asarray(A0),
                                            sk.COLUMNWISE))):
            violations.append(
                "different-seed request diverged from its own oracle")

        # -- front-door single-flight: one fresh digest, stormed ------
        T_sf = sk.CWT(CLASSES[0], S_DIM, Context(seed=88))
        A_sf = rng.standard_normal((CLASSES[0], 5)).astype(np.float32)
        flushes_before = _fleet_cache_stats(pool)["flushes"]
        futs = [router.submit_sketch(T_sf, A_sf) for _ in range(16)]
        sf_outs = [np.asarray(f.result(timeout=120)) for f in futs]
        rs = router.stats()
        sf_flushes = _fleet_cache_stats(pool)["flushes"] - flushes_before
        rec["single_flight"] = {
            "coalesced": rs["coalesced"],
            "routed_total": rs["routed"],
            "flushes_added": sf_flushes,
        }
        want = np.asarray(T_sf.apply(jnp.asarray(A_sf), sk.COLUMNWISE))
        if any(not np.array_equal(o, want) for o in sf_outs):
            violations.append("single-flight fan diverged")
        if sf_flushes != 1:
            violations.append(
                f"single-flight storm added {sf_flushes} flushes, "
                "expected exactly 1")
    finally:
        router.close()
        pool.shutdown()

    # -- residency over the process transport + /dev/shm hygiene ------
    pool2 = fleet.ReplicaPool(1, backend="process", max_batch=MAX_BATCH,
                              cache=True)
    try:
        router2 = fleet.Router(pool2, cache=True)
        try:
            T0, A0 = uniq[0]
            ref = router2.register_operand(A0)
            via = np.asarray(router2.submit_sketch(T0, ref)
                             .result(timeout=180))
            if not np.array_equal(via, oracle[0]):
                violations.append(
                    "process-replica ref submit diverged from oracle")
            held = router2.unregister_operand(ref)
            if held != 1:
                violations.append(
                    f"unregister dropped {held} pins, expected 1")
            rec["residency_process_leg"] = {
                "ref": str(ref)[:12], "unregistered_from": held}
        finally:
            router2.close()
    finally:
        pool2.shutdown()
    leaked = fleet.shm_entries()
    if leaked:
        violations.append(f"leaked /dev/shm entries: {leaked}")
    rec["shm_leaks"] = len(leaked)

    rec["violations"] = violations
    rec["ok"] = not violations
    print(json.dumps(rec), flush=True)
    if violations:
        for v in violations:
            print(f"CACHE GATE VIOLATION: {v}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
