"""Net smoke — the CI network front-door gate's driver
(docs/networking).

A loopback TCP storm against a 2-replica fleet asserting the net
subsystem's contract end to end, fast enough for the per-commit gate:

- **wire transparency**: a 3-client loopback storm over cached
  digests returns every result bit-equal to the in-process
  ``Router.submit_sketch`` oracle with ZERO executable compiles in
  the measured window — the socket hop adds no numerics and no
  compilation;
- **retry idempotency**: a torn connection followed by the client's
  transparent reconnect-resend of the identical frame bytes lands on
  the router's single-flight/result-cache tier — the engine flushes
  EXACTLY once for the digest no matter how many times the wire tore;
- **chaos absorption**: an injected ``net.read`` fault (the fault
  table's socket site) tears a live server connection mid-stream and
  the client's bounded retry absorbs it with no caller-visible error;
- **SIGTERM drain**: the process preemption handler GOAWAYs every
  connection and flushes inflight responses — a burst submitted just
  before the signal resolves with ZERO client-visible failures.

Usage: ``python benchmarks/net_smoke.py`` (script/ci wires
``JAX_PLATFORMS=cpu``). Prints one JSON record; exits nonzero on any
violation.
"""

from __future__ import annotations

import json
import os
import signal
import sys
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402

N_STORM = 60
N_CLIENTS = 3
N_UNIQUE = 4
MAX_BATCH = 8
CLASSES = (40, 96)          # two pow2 stream classes (pad 64 / 128)
S_DIM = 16
DRAIN_BURST = 12


def _fleet_cache_stats(pool) -> dict:
    from libskylark_tpu.engine import resultcache as rc

    blocks = [pool.get(n).executor.stats().get("cache")
              for n in pool.names()]
    merged = rc.merge_cache_blocks([b for b in blocks if b])
    merged["flushes"] = sum(
        pool.get(n).executor.stats()["flushes"] for n in pool.names())
    return merged


def main() -> int:
    import jax.numpy as jnp

    from libskylark_tpu import Context, engine, fleet, net
    from libskylark_tpu import sketch as sk
    from libskylark_tpu.resilience import faults, preemption

    engine.reset()
    violations: list = []
    rng = np.random.default_rng(0)

    uniq = []
    for i in range(N_UNIQUE):
        n = CLASSES[i % len(CLASSES)]
        T = sk.CWT(n, S_DIM, Context(seed=i))
        A = rng.standard_normal((n, 3 + i)).astype(np.float32)
        uniq.append((T, A))

    pool = fleet.ReplicaPool(2, max_batch=MAX_BATCH, linger_us=2000,
                             cache=True)
    router = fleet.Router(pool, cache=True)
    srv = net.NetServer(router)
    clients = [net.NetClient(srv.address, retry_backoff_s=0.02, seed=i)
               for i in range(N_CLIENTS)]
    rec: dict = {"metric": "net_smoke", "n_storm": N_STORM,
                 "n_clients": N_CLIENTS, "n_unique": N_UNIQUE}
    try:
        # -- warmup + oracle: the IN-PROCESS path computes each unique
        # exactly once; the loopback storm must reproduce these bytes
        oracle = [np.asarray(
            router.submit_sketch(T, A).result(timeout=120))
            for (T, A) in uniq]
        deadline = time.monotonic() + 30
        while (_fleet_cache_stats(pool)["entries"] < N_UNIQUE
               and time.monotonic() < deadline):
            time.sleep(0.005)
        eng0 = engine.stats()
        compiles0 = (eng0.misses, eng0.recompiles)

        # -- leg 1: loopback storm, bit-equal + zero recompiles -------
        futs = []
        for i in range(N_STORM):
            T, A = uniq[i % N_UNIQUE]
            c = clients[i % N_CLIENTS]
            futs.append(c.submit("sketch_apply", transform=T, A=A,
                                 dimension=sk.COLUMNWISE))
        outs = [np.asarray(f.result(timeout=120)) for f in futs]
        eng1 = engine.stats()
        rec["recompiles_storm"] = (
            eng1.misses - compiles0[0], eng1.recompiles - compiles0[1])
        for i, out in enumerate(outs):
            if not np.array_equal(out, oracle[i % N_UNIQUE]):
                violations.append(
                    f"loopback request {i} diverged from the "
                    "in-process oracle")
                break
        if rec["recompiles_storm"] != (0, 0):
            violations.append(
                f"loopback storm compiled: misses/recompiles "
                f"{rec['recompiles_storm']}")
        ns = srv.stats()
        rec["requests_served"] = ns["requests"]
        if ns["requests"] < N_STORM:
            violations.append(
                f"server counted {ns['requests']} requests for a "
                f"{N_STORM}-request storm")

        # -- leg 2: torn connection + identical re-send -> one flush --
        c0 = clients[0]
        T2 = sk.CWT(CLASSES[0], S_DIM, Context(seed=41))
        A2 = rng.standard_normal((CLASSES[0], 5)).astype(np.float32)
        first = np.asarray(c0.submit(
            "sketch_apply", transform=T2, A=A2,
            dimension=sk.COLUMNWISE).result(timeout=120))
        deadline = time.monotonic() + 30
        while (_fleet_cache_stats(pool)["entries"] < N_UNIQUE + 1
               and time.monotonic() < deadline):
            time.sleep(0.005)
        flushes_before = _fleet_cache_stats(pool)["flushes"]
        with c0._lock:                       # tear the live socket
            sock = c0._sock
        sock.close()
        again = np.asarray(c0.submit(
            "sketch_apply", transform=T2, A=A2,
            dimension=sk.COLUMNWISE).result(timeout=120))
        st = _fleet_cache_stats(pool)
        rec["disconnect_retry"] = {
            "flushes_added": st["flushes"] - flushes_before,
            "bit_equal": bool(np.array_equal(first, again)),
        }
        if st["flushes"] != flushes_before:
            violations.append(
                f"disconnect+resend added "
                f"{st['flushes'] - flushes_before} flush(es) — the "
                "retry recomputed instead of hitting the cache")
        if not np.array_equal(first, again):
            violations.append("retried result diverged from original")

        # -- leg 3: chaos net.read fault absorbed by client retry -----
        # a FRESH client: the only frame read anywhere during the
        # plan is this request, so the fault (checked on frame
        # arrival, before dispatch) deterministically tears THIS
        # connection down pre-dispatch and the retry must happen
        T3 = sk.CWT(CLASSES[1], S_DIM, Context(seed=42))
        A3 = rng.standard_normal((CLASSES[1], 4)).astype(np.float32)
        cx = net.NetClient(srv.address, retry_budget=3,
                           retry_backoff_s=0.02, seed=7)
        plan = {"seed": 1, "faults": [
            {"site": "net.read", "error": "IOError_", "times": 1}]}
        try:
            with faults.fault_plan(plan):
                chaos_out = np.asarray(cx.submit(
                    "sketch_apply", transform=T3, A=A3,
                    dimension=sk.COLUMNWISE).result(timeout=120))
                fired = [f[0] for f in faults.fired()]
            retries = cx.client_stats()["transport_retries"]
        finally:
            cx.close()
        want = np.asarray(T3.apply(jnp.asarray(A3), sk.COLUMNWISE))
        rec["chaos"] = {"fired": fired, "transport_retries": retries,
                        "bit_equal": bool(np.array_equal(chaos_out,
                                                         want))}
        if fired != ["net.read"]:
            violations.append(f"chaos plan fired {fired}, expected "
                              "exactly one net.read hit")
        if not np.array_equal(chaos_out, want):
            violations.append("chaos-leg result diverged from oracle")
        if retries < 1:
            violations.append(
                "net.read fault did not exercise the transport retry")

        # -- leg 4: SIGTERM drain with zero client-visible failures ---
        preemption.install_preemption_handler()
        try:
            resp_before = srv.stats()["responses_sent"]
            # half the burst repeats cached digests, half is FRESH
            # work that must actually flush — so the drain has real
            # inflight computation to settle, not just queued hits
            work = []
            for i in range(DRAIN_BURST):
                if i % 2 == 0:
                    T, A = uniq[i % N_UNIQUE]
                else:
                    n = CLASSES[i % len(CLASSES)]
                    T = sk.CWT(n, S_DIM, Context(seed=50 + i))
                    A = rng.standard_normal((n, 4)).astype(np.float32)
                work.append((T, A))
            wants = [np.asarray(T.apply(jnp.asarray(A), sk.COLUMNWISE))
                     for (T, A) in work]
            burst = [clients[i % N_CLIENTS].submit(
                "sketch_apply", transform=T, A=A,
                dimension=sk.COLUMNWISE) for i, (T, A) in
                enumerate(work)]
            # The drain contract flushes INFLIGHT requests; a frame
            # not yet handed to the router when drain_serving empties
            # the replica ring is legitimately refused with a
            # structured overload error. Pin determinism by waiting
            # until every burst request is inside the router —
            # pending (registered future) or already answered — which
            # only counts requests whose Router.submit has returned.
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                st = srv.stats()
                inside = (st["pending"]
                          + st["responses_sent"] - resp_before)
                if inside >= DRAIN_BURST:
                    break
                time.sleep(0.002)
            os.kill(os.getpid(), signal.SIGTERM)
            if not preemption.wait_for_preemption_teardown(60):
                violations.append("preemption teardown did not finish")
            failures = 0
            for i, fut in enumerate(burst):
                try:
                    out = np.asarray(fut.result(timeout=60))
                    if not np.array_equal(out, wants[i]):
                        failures += 1
                except Exception:  # noqa: BLE001 — any failure counts
                    failures += 1
            ns = srv.stats()
            rec["drain"] = {
                "burst": DRAIN_BURST,
                "client_visible_failures": failures,
                "drains": ns["drains"],
                "goaways_sent": ns["goaways_sent"],
                "draining": ns["draining"],
            }
            if failures:
                violations.append(
                    f"{failures} client-visible failure(s) across a "
                    "SIGTERM drain")
            if ns["drains"] < 1 or not ns["draining"]:
                violations.append("SIGTERM did not drain the server")
            if ns["goaways_sent"] < 1:
                violations.append("drain sent no GOAWAY frames")
        finally:
            preemption.uninstall_preemption_handler()
            preemption.reset_preemption()
    finally:
        for c in clients:
            c.close()
        srv.close()
        router.close()
        pool.shutdown()

    rec["violations"] = violations
    rec["ok"] = not violations
    print(json.dumps(rec), flush=True)
    if violations:
        for v in violations:
            print(f"NET GATE VIOLATION: {v}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
