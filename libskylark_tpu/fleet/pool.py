"""ReplicaPool: owns N named replicas and their shared lifecycle.

The pool is the fleet's capacity layer: it constructs N replicas with
*uniform* executor configuration (one ``pad_floor``, one ``max_batch``
— the router derives affinity keys and spill bounds from the pool, so
a heterogeneous fleet would break sticky routing), names them
``r0..r{N-1}``, and gives the router one place to resolve health-hub
event sources back to replica names. Membership is elastic:
:meth:`~ReplicaPool.add_replica` grows the pool by one pack-booted
replica (announced to subscribed routers via the health hub's SERVING
publish) and :meth:`~ReplicaPool.remove_replica` shrinks it through
the r11 preemption drain — the two verbs the autoscaler
(:mod:`libskylark_tpu.fleet.autoscale`) drives.

Preemption composition (the tentpole contract): the pool registers one
:func:`~libskylark_tpu.resilience.on_preemption` hook, so a
process-wide SIGTERM — which drains every in-process executor via the
r9 handler — also runs every replica's registered drain hooks (final
per-replica checkpoints) exactly once. A *single* replica can be
preempted without touching the rest via :meth:`preempt_replica`:
thread replicas drain in place (there is no thread-scoped SIGTERM);
process replicas get a real SIGTERM. Either way the replica's drain
hooks fire, its in-flight futures resolve, and the health hub
announces DRAINING → STOPPED so a subscribed router sheds its traffic
to peers mid-drain.

Crash reap: a ``ProcessReplica`` child that exits *unexpectedly* (a
``kill -9``, an OOM, the chaos ``crash`` fault) publishes STOPPED from
the parent-side reader thread — the pool subscribes to the hub and
**reaps** the dead member: it leaves the membership immediately (no
drain hooks — there was no grace window), its name lands in
:meth:`crashed_names`, and an attached autoscaler's next tick sees the
pool below its floor and replaces the member via the r13 pack boot.
Without the reap the dead replica stayed a member forever: routers
dropped it from the ring (STOPPED) but the pool's count never shrank,
so the autoscaler never replaced it — the crash-then-shrink hole the
session replay chaos leg exercises (docs/sessions).
"""

from __future__ import annotations

import os
import threading
import warnings
from typing import Callable, Dict, List, Optional

from libskylark_tpu.base import env as _env
from libskylark_tpu.base import errors
from libskylark_tpu.base import locks as _locks
from libskylark_tpu.engine import bucket as bucketing
from libskylark_tpu.engine import serve as _serve
from libskylark_tpu.fleet.replica import (ProcessReplica, Replica,
                                          ThreadReplica)
from libskylark_tpu.resilience import health as _health
from libskylark_tpu.resilience import preemption as _preemption

_UNSET = object()


def resolve_backend(backend: Optional[str]) -> str:
    """The effective replica backend: an explicit argument wins, else
    ``SKYLARK_FLEET_BACKEND``; ``auto`` resolves to process replicas
    on hosts with >= 4 cores (where per-replica cores exist for them
    to use) and thread replicas below (where a spawned interpreter
    per replica buys nothing but boot time)."""
    if backend is None:
        backend = str(_env.FLEET_BACKEND.get())
    backend = str(backend)
    if backend == "auto":
        return "process" if (os.cpu_count() or 1) >= 4 else "thread"
    return backend


def _refuse_chip_parent() -> None:
    """A TPU chip belongs to one process: a parent whose backend is the
    TPU holds the chips, and process replicas that need them would then
    fail or hang at boot. Refuse up front. The supported layout keeps
    the parent off the chip (``JAX_PLATFORMS=cpu``) and seats each
    replica on its own chip through ``replica_env`` (README "On the
    chip")."""
    import jax

    if jax.default_backend() == "tpu":
        raise errors.UnsupportedError(
            "process replicas on a TPU backend: this process holds the "
            "chips its children would need. Start the parent with "
            "JAX_PLATFORMS=cpu and give each replica its own chip through "
            "replica_env (e.g. JAX_PLATFORMS=tpu, TPU_VISIBLE_CHIPS=<i>), "
            "or use backend='thread'")


class ReplicaPool:
    """N uniform replicas behind names (``r0``..``r{N-1}``).

    ::

        pool = fleet.ReplicaPool(4, max_batch=16, linger_us=2000)
        router = fleet.Router(pool)
        ...
        pool.shutdown()

    ``backend`` is ``"thread"`` (default) or ``"process"``; remaining
    keyword arguments are passed to every replica's
    ``MicrobatchExecutor`` (process replicas additionally accept
    ``coordinator=`` — multi-host kwargs forwarded to
    ``parallel.multihost.initialize_distributed`` in the child).

    ``warmup_pack`` boots every replica from a warmup pack
    (docs/performance, "Persistent AOT artifacts & warmup packs"):
    thread replicas load it into the shared process executable cache
    (deserialized once — later replicas find the keys resident and
    only seed their own flush-kernel memos);
    process replicas each load it in their own interpreter BEFORE the
    liveness probe resolves, and inherit the parent's AOT store / plan
    cache / telemetry environment explicitly
    (:data:`~libskylark_tpu.fleet.replica.PROPAGATED_ENV`), so a
    process fleet of N cold children boots serving every packed bucket
    with zero backend compiles.

    ``shared_workers`` (thread backend only) sizes flush concurrency
    to the HOST instead of to N: the pool owns one dispatch queue and
    that many flush worker threads, and every replica enqueues its
    cohorts there (``MicrobatchExecutor(dispatch_queue=...)``). N
    replicas each running their own workers oversubscribe a small
    host — N concurrent flushes thrash more cores than exist — while
    a host-sized shared pool keeps the fleet's flush concurrency
    equal to a well-tuned single executor's (docs/fleet, "Tuning N").
    """

    def __init__(self, n: int = 2, *, backend: Optional[str] = None,
                 names: Optional[List[str]] = None, coordinator=None,
                 shared_workers: Optional[int] = None,
                 warmup_pack: Optional[str] = None,
                 replica_env=None,
                 **executor_kwargs):
        if n < 1:
            raise ValueError("a fleet needs at least one replica")
        backend = resolve_backend(backend)
        if backend not in ("thread", "process"):
            raise ValueError(
                f"backend must be 'thread', 'process' or 'auto', "
                f"got {backend!r}")
        if backend == "process":
            _refuse_chip_parent()
        names = list(names) if names else [f"r{i}" for i in range(n)]
        if len(names) != n or len(set(names)) != n:
            raise ValueError(f"need {n} distinct replica names, "
                             f"got {names!r}")
        self.backend = backend
        self.executor_kwargs = dict(executor_kwargs)
        self.pad_floor = int(executor_kwargs.get(
            "pad_floor", bucketing.PAD_FLOOR))
        self.max_batch = int(executor_kwargs.get("max_batch", 8))
        # per-replica seats: ``coordinator`` / ``replica_env`` may be a
        # dict applied to every process replica, or a callable
        # ``name -> dict`` pinning each replica to its own seat in the
        # multihost pool / its own device subset (env overrides like
        # TPU_VISIBLE_CHIPS applied at child entry; README "On the
        # chip") — the "one replica, one device subset" knob
        self._coordinator = coordinator
        self._replica_env = replica_env
        self.warmup_pack = warmup_pack
        self._lock = _locks.make_lock("fleet.pool")
        self._drain_hooks: Dict[str, list] = {name: [] for name in names}
        self._drained: set = set()
        self._replicas: Dict[str, Replica] = {}
        self._booting: set = set()
        # names under a pool-initiated preemption/drain: their STOPPED
        # events are expected and must not be misread as crashes
        self._removing: set = set()
        self._crashed: List[str] = []
        self._shutdown = False
        self._next_idx = n
        self._dispatchq = None
        self._dispatchers: list = []
        if shared_workers is not None:
            if backend != "thread":
                raise ValueError(
                    "shared_workers applies to thread replicas only "
                    "(process replicas have their own interpreters)")
            import queue as _queue

            from libskylark_tpu.engine.serve import dispatch_loop

            self._dispatchq = _queue.Queue()
            self._dispatchers = [
                threading.Thread(target=dispatch_loop,
                                 args=(self._dispatchq,),
                                 name=f"skylark-fleet-dispatch-{i}",
                                 daemon=True)
                for i in range(max(int(shared_workers), 1))
            ]
            for t in self._dispatchers:
                t.start()
            executor_kwargs = dict(executor_kwargs,
                                   dispatch_queue=self._dispatchq)
        # the FULL construction kwargs (including the shared dispatch
        # queue) — add_replica must build future replicas exactly like
        # the initial ones
        self._replica_kwargs = dict(executor_kwargs)
        try:
            for name in names:
                self._replicas[name] = self._build_replica(name)
        except Exception:
            for r in self._replicas.values():
                r.shutdown()
            self._stop_dispatchers()
            raise
        # process-wide preemption (SIGTERM to THIS process): the r9
        # handler drains the executors; this hook runs after the drain
        # (hook order: drain_serving first) so the per-replica final
        # checkpoints see quiesced replicas
        self._unhook = _preemption.on_preemption(self._run_all_drain_hooks)
        # crash reap (module doc): react to STOPPED events the pool
        # did not initiate
        self._health_unsub = _health.subscribe(self._on_health_event)

    def _per_replica(self, seat, name: str):
        return seat(name) if callable(seat) else seat

    def _build_replica(self, name: str,
                       warmup_pack=_UNSET) -> Replica:
        pack = (self.warmup_pack if warmup_pack is _UNSET
                else warmup_pack)
        if self.backend == "thread":
            return ThreadReplica(name, warmup_pack=pack,
                                 **self._replica_kwargs)
        return ProcessReplica(
            name, coordinator=self._per_replica(self._coordinator, name),
            env_overrides=self._per_replica(self._replica_env, name),
            warmup_pack=pack, **self._replica_kwargs)

    # -- addressing ----------------------------------------------------

    def names(self) -> List[str]:
        return sorted(self._replicas)

    def replicas(self) -> List[Replica]:
        return [self._replicas[n] for n in self.names()]

    def get(self, name: str) -> Replica:
        return self._replicas[name]

    def resolve_source(self, source: object) -> Optional[str]:
        """Map a health-hub event source (an executor for thread
        replicas, the replica object for process replicas) to its
        replica name; ``None`` for sources outside this pool."""
        for name, r in list(self._replicas.items()):
            if r.owns_source(source):
                return name
        return None

    # -- crash reap (module doc) ---------------------------------------

    def _on_health_event(self, source: object, old: str,
                         new: str) -> None:
        if new != _serve.STOPPED:
            return
        with self._lock:
            if self._shutdown:
                return
        name = self.resolve_source(source)
        if name is None:
            return
        dead = None
        with self._lock:
            if (name in self._removing or name in self._drained
                    or self._shutdown):
                return                 # pool-initiated: not a crash
            replica = self._replicas.get(name)
            if not isinstance(replica, ProcessReplica):
                return                 # threads have no crash mode
            if not replica.unexpected_exit:
                return                 # drain-flow STOPPED, not a crash
            # unexpected child exit: reap the membership NOW so the
            # autoscaler's next tick replaces the dead member (the
            # pack boot) instead of counting a corpse as capacity
            dead = self._replicas.pop(name)
            self._drain_hooks.pop(name, None)
            self._crashed.append(name)
        if dead is not None:
            warnings.warn(
                f"replica {name!r} exited unexpectedly — reaped from "
                "the pool (an attached autoscaler will replace it)",
                RuntimeWarning, stacklevel=2)
            try:
                dead.shutdown()        # reap pipe/threads; idempotent
            except Exception:  # noqa: BLE001 — the corpse is gone
                pass

    def crashed_names(self) -> List[str]:
        """Names of replicas reaped after an unexpected exit (crash
        forensics; the session chaos leg asserts on this)."""
        with self._lock:
            return list(self._crashed)

    # -- elastic membership (the autoscaler's seam) --------------------

    def add_replica(self, name: Optional[str] = None, *,
                    warmup_pack=_UNSET) -> str:
        """Grow the pool by one replica (same backend, same uniform
        executor configuration; process replicas get their own
        ``coordinator``/``replica_env`` seat from the per-replica
        callables). Boots from the pool's warmup pack by default — the
        scale-up path is the r13 pack boot, so a grown fleet serves
        its packed buckets with zero compiles. Publishes ``SERVING``
        to the health hub once the replica is live, which is how a
        subscribed router learns to add it to the ring. Returns the
        new replica's name."""
        with self._lock:
            if self._shutdown:
                raise RuntimeError("ReplicaPool is shut down")
            if name is None:
                while (f"r{self._next_idx}" in self._replicas
                       or f"r{self._next_idx}" in self._booting):
                    self._next_idx += 1
                name = f"r{self._next_idx}"
                self._next_idx += 1
            name = str(name)
            if name in self._replicas or name in self._booting:
                raise ValueError(f"replica {name!r} already exists")
            # reserve the name so two concurrent add_replica calls
            # cannot race one name (construction happens unlocked —
            # a process replica boot takes seconds)
            self._booting.add(name)
        try:
            replica = self._build_replica(name, warmup_pack=warmup_pack)
        except Exception:
            with self._lock:
                self._booting.discard(name)
            raise
        with self._lock:
            self._booting.discard(name)
            if self._shutdown:
                # the pool died while we were booting (a slow spawn
                # outliving an autoscaler close + pool.shutdown):
                # registering now would hand a subscribed router a
                # replica nothing will ever stop
                late = replica
            else:
                late = None
                self._replicas[name] = replica
                self._drain_hooks.setdefault(name, [])
                self._drained.discard(name)
        if late is not None:
            late.shutdown()
            raise RuntimeError(
                "ReplicaPool was shut down during the replica boot")
        _health.publish(replica, "NEW", _serve.SERVING)
        return name

    def remove_replica(self, name: str,
                       timeout: Optional[float] = 30.0) -> bool:
        """Shrink the pool by one replica: preempt it (the r11 SIGTERM
        drain for process replicas — DRAINING/STOPPED reach the hub,
        a subscribed router sheds its traffic, in-flight futures
        resolve, its final drain hooks fire), then forget it. Returns
        whether the drain reached quiescence inside ``timeout``."""
        with self._lock:
            if name not in self._replicas:
                raise KeyError(f"no replica named {name!r}")
        drained = self.preempt_replica(name, timeout=timeout)
        replica = None
        with self._lock:
            replica = self._replicas.pop(name, None)
            self._drain_hooks.pop(name, None)
            self._drained.discard(name)
        if replica is not None:
            replica.shutdown()
        return drained

    # -- traffic helpers -----------------------------------------------

    def flush(self) -> None:
        """Synchronously flush every replica, in name order (tests and
        deterministic chaos storms; normal traffic never needs it)."""
        for name in self.names():
            self._replicas[name].flush()

    def stats(self) -> dict:
        return {name: self._replicas[name].stats()
                for name in self.names()}

    # -- per-replica preemption ----------------------------------------

    def on_replica_drain(self, name: str,
                         hook: Callable[[], None]) -> Callable[[], None]:
        """Register a final-drain hook for one replica (its "final
        checkpoint"); runs exactly once, whether the replica is
        preempted alone (:meth:`preempt_replica`) or the whole process
        is SIGTERM'd. Returns the unregister callable."""
        with self._lock:
            self._drain_hooks[name].append(hook)

        def unregister() -> None:
            with self._lock:
                try:
                    self._drain_hooks[name].remove(hook)
                except (KeyError, ValueError):
                    pass

        return unregister

    def _run_drain_hooks(self, name: str) -> None:
        with self._lock:
            if name in self._drained:
                return
            self._drained.add(name)
            hooks = list(self._drain_hooks.get(name, ()))
        for hook in hooks:
            try:
                hook()
            except Exception as e:  # noqa: BLE001 — contain, like r9
                warnings.warn(
                    f"replica {name!r} drain hook {hook!r} failed: {e}",
                    RuntimeWarning, stacklevel=2)

    def _run_all_drain_hooks(self) -> None:
        for name in self.names():
            self._run_drain_hooks(name)

    def preempt_replica(self, name: str,
                        timeout: Optional[float] = 30.0) -> bool:
        """Preempt ONE replica: drain it (intake refused — the health
        hub announces DRAINING, a subscribed router sheds its traffic
        to peers — queued cohorts flush, in-flight futures resolve),
        then fire its drain hooks. Process replicas get a real SIGTERM
        (the child's own preemption handler does the draining);
        thread replicas drain in place. Returns whether quiescence was
        reached inside ``timeout``."""
        replica = self._replicas[name]
        # expected STOPPED ahead: the crash reap must not misread a
        # pool-initiated preemption as an unexpected exit
        with self._lock:
            self._removing.add(name)
        try:
            if isinstance(replica, ProcessReplica):
                replica.preempt()
                # the child's handler drains asynchronously; wait for
                # its STOPPED announcement by polling the cached state
                import time as _time

                deadline = _time.monotonic() + (timeout or 30.0)
                while (replica.state() != "STOPPED"
                       and _time.monotonic() < deadline):
                    _time.sleep(0.05)
                drained = replica.state() == "STOPPED"
            else:
                drained = replica.drain(timeout=timeout)
            self._run_drain_hooks(name)
        finally:
            with self._lock:
                self._removing.discard(name)
        return drained

    def drain_replica(self, name: str,
                      timeout: Optional[float] = 30.0) -> bool:
        """Drain one replica without the preemption framing (no drain
        hooks) — administrative removal, e.g. before a resize."""
        with self._lock:
            self._removing.add(name)
        try:
            return self._replicas[name].drain(timeout=timeout)
        finally:
            with self._lock:
                self._removing.discard(name)

    # -- lifecycle -----------------------------------------------------

    def _stop_dispatchers(self) -> None:
        for _ in self._dispatchers:
            self._dispatchq.put(None)     # FIFO: queued cohorts first
        for t in self._dispatchers:
            t.join(timeout=30.0)
        self._dispatchers = []

    def shutdown(self) -> None:
        with self._lock:
            self._shutdown = True
        self._unhook()
        self._health_unsub()
        for r in self.replicas():
            try:
                r.shutdown()
            except Exception as e:  # noqa: BLE001 — stop the rest too
                warnings.warn(f"replica {r.name!r} shutdown failed: {e}",
                              RuntimeWarning, stacklevel=2)
        if self._dispatchers:
            self._stop_dispatchers()

    def __enter__(self) -> "ReplicaPool":
        return self

    def __exit__(self, *exc) -> None:
        self.shutdown()


__all__ = ["ReplicaPool"]
