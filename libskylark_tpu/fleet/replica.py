"""Fleet replicas: one submit surface over thread- or process-backed
microbatch executors.

A *replica* is one unit of serving capacity the router can address:
it has a name (its ring identity and telemetry label), a
future-returning ``submit`` mirroring
:meth:`~libskylark_tpu.engine.serve.MicrobatchExecutor.submit`, a live
queue-depth signal, the r9 health states, and the drain lifecycle.

Two backings:

- :class:`ThreadReplica` — an in-process
  :class:`~libskylark_tpu.engine.serve.MicrobatchExecutor` (the
  default). Cheapest possible hop (the router calls straight into the
  executor), shares the process executable cache, and health
  transitions reach the resilience hub directly.
- :class:`ProcessReplica` — a spawned child process hosting its own
  executor behind a pickle pipe. The child is a real OS-level
  preemption domain: it installs
  :func:`~libskylark_tpu.resilience.install_preemption_handler`, so a
  SIGTERM *to the child alone* drains its executor (in-flight futures
  resolve and their results still flow back over the pipe) while the
  parent-side router sheds new traffic to peers — the per-replica
  preemption story a thread cannot give. Multi-host placement rides
  the existing :mod:`libskylark_tpu.parallel.multihost` plumbing: pass
  ``coordinator`` kwargs and the child joins the distributed pool via
  ``initialize_distributed`` before serving. Spawn (not fork): a
  forked child would inherit jax's initialized backend and the parent's
  locked thread state.

Process-replica protocol (one duplex pipe, length-tagged tuples):
parent → child: ``("submit", rid, endpoint, kwargs)`` /
``("stats"|"env"|"depth"|"flush", rid)`` / ``("drain", rid, timeout)``
/ ``("shutdown", rid)``; child → parent: ``("result", rid, value)`` /
``("error", rid, exception)`` / ``("rpc", rid, value)`` /
``("state", None, new_state)`` — the last forwarded from the child's
health hub so the parent's hub (and any subscribed router) sees the
child's transitions with the :class:`ProcessReplica` as the source.
Both directions additionally carry ``("shmfree", None, [slots])``
acks for the shared-memory transport below.

**QoS propagation** (docs/qos): ``submit`` kwargs carry the router-
resolved ``tenant=``/``qos_class=`` pair verbatim — over the pickle
pipe for process replicas — so every replica's executor schedules a
request under the same priority class the front door admitted it in;
a replica never re-charges the tenant's token bucket (the buckets
live with the router's registry).

**Shared-memory transport** (:mod:`libskylark_tpu.fleet.shm`, default
on — ``SKYLARK_FLEET_SHM=0`` disables): large ndarrays inside
``submit`` kwargs and results do NOT ride the pickle pipe. The sender
copies them into a slot of the replica pair's shared-memory ring and
the pipe carries a tiny header; the receiver gets a zero-copy view
over the slot, released back to the writer when the view is
garbage-collected. Small values, oversize arrays and ring exhaustion
fall back to pickle — transport choice never changes a result.
"""

from __future__ import annotations

import itertools
import os
import signal
import threading
import warnings
from concurrent.futures import Future
from typing import Optional

import numpy as np

from libskylark_tpu.base import env as _env
from libskylark_tpu.base import locks as _locks
from libskylark_tpu.engine.serve import ServeOverloadedError

# Environment a replica child must agree with its parent on — the AOT
# artifact store, the serve-kernel pin (it is in every flush
# executable's key: a child on a different pin would never hit the
# parent's warmup pack), and the telemetry switches. Propagated
# EXPLICITLY through the spawn args and applied at child entry, not
# left to the accident of what ``os.environ`` held when
# ``Process.start()`` happened to run (a parent that configures its
# store after constructing the pool — or a test that monkeypatches
# around replica construction — must still produce children that
# agree with it). The tuple is DERIVED from the typed registry
# (``base/env.py``: every declaration with ``propagate=True``), so a
# newly declared variable can never again silently miss propagation —
# the registry declaration is the single place that decides.
PROPAGATED_ENV = _env.propagated_names()


def propagated_env() -> dict:
    """Snapshot of :data:`PROPAGATED_ENV` in this process (``None``
    marks a variable to *unset* in the child)."""
    return _env.snapshot_propagated()


def _apply_env(env: Optional[dict]) -> None:
    """Apply a parent's snapshot in the child — set present values,
    delete absent ones — then re-arm the lazy readers that already ran
    at import time (telemetry's enable gate and JSONL exporter)."""
    if env is None:
        return
    for k, v in env.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v
    try:
        from libskylark_tpu import telemetry

        telemetry.set_enabled(bool(_env.TELEMETRY.get())
                              or bool(_env.TELEMETRY_DIR.get()))
        if _env.TELEMETRY_DIR.get():
            telemetry.install_exporter()
    except Exception:  # noqa: BLE001 — telemetry must not block boot
        pass


class Replica:
    """The surface the router programs against (see module doc)."""

    name: str

    def submit(self, endpoint: str, /, **kwargs) -> Future:
        raise NotImplementedError

    def session(self, op: str, /, **kwargs) -> Future:
        """Stateful-session verb (docs/sessions): ``op`` is ``open`` /
        ``append`` / ``finalize`` with the corresponding
        ``MicrobatchExecutor`` session method's kwargs. Returns a
        future (``open`` resolves to the session id, ``append`` to
        ``(seq, rows)``, ``finalize`` to the result dict)."""
        raise NotImplementedError

    def train(self, op: str, /, **kwargs) -> Future:
        """Training-job verb (docs/training): ``op`` is ``submit`` /
        ``resume`` / ``status`` with the corresponding
        ``MicrobatchExecutor`` train method's kwargs. ``submit`` and
        ``resume`` resolve to the job's TERMINAL result (the trained
        model dict, or the terminal error — slices run in the
        replica's idle slots in between); ``status`` resolves to a
        progress snapshot."""
        raise NotImplementedError

    def shard(self, task: dict) -> Future:
        """Distributed-sketch shard-task verb (docs/distributed): the
        payload is :func:`libskylark_tpu.dist.plan.execute_task`'s —
        a serialized :class:`~libskylark_tpu.dist.plan.ShardPlan`, the
        shard index, and a range-readable source. Resolves to the
        task's ``{"index", "rows", "partial"}`` dict. Idempotent by
        construction (the partial is a pure function of the plan), so
        the coordinator retries a failed/crashed future by simply
        re-invoking this on the next ring-preference replica."""
        raise NotImplementedError

    def register_operand(self, A, transform=None, dimension=None,
                         **kwargs) -> Future:
        """Operand-residency verb (docs/caching): content-hash ``A``
        and pin it resident on this replica — precomputing and
        pinning its sketch when ``transform`` is given — so later
        submits can reference the operand by digest instead of
        re-shipping (and re-sketching) it. Resolves to the operand's
        ref string (``ref:<digest>``)."""
        raise NotImplementedError

    def unregister_operand(self, ref) -> Future:
        """Drop a resident operand (and any sketches pinned with it);
        resolves to whether this replica held it."""
        raise NotImplementedError

    def queue_depth(self) -> int:
        raise NotImplementedError

    def state(self) -> str:
        raise NotImplementedError

    def stats(self) -> dict:
        raise NotImplementedError

    def flush(self) -> None:
        raise NotImplementedError

    def drain(self, timeout: Optional[float] = 30.0) -> bool:
        raise NotImplementedError

    def shutdown(self) -> None:
        raise NotImplementedError

    def latency_quantile(self, q: float = 0.99) -> Optional[float]:
        """One quantile of the replica's r10 request-latency histogram
        (seconds; ``None`` when unknown). The router's hedge-delay
        seed — cheap for thread replicas; a process replica returns
        ``None`` rather than pay a pipe RPC on the submit path."""
        return None


class ThreadReplica(Replica):
    """In-process replica: a named ``MicrobatchExecutor`` plus the
    thin identity layer the router needs."""

    backend = "thread"

    def __init__(self, name: str, warmup_pack: Optional[str] = None,
                 **executor_kwargs):
        from libskylark_tpu import engine

        self.name = str(name)
        self.executor = engine.MicrobatchExecutor(name=self.name,
                                                  **executor_kwargs)
        self.warmup_report: Optional[dict] = None
        if warmup_pack:
            # pack loading precedes any traffic by construction (the
            # pool builds replicas before the router exists); a
            # degraded/partial load serves via the compile path
            self.warmup_report = self.executor.load_warmup_pack(
                warmup_pack)

    def submit(self, endpoint: str, /, **kwargs) -> Future:
        return self.executor.submit(endpoint, **kwargs)

    def session(self, op: str, /, **kwargs) -> Future:
        if op == "append":
            return self.executor.session_append(**kwargs)
        if op == "finalize":
            return self.executor.session_finalize(**kwargs)
        fut: Future = Future()
        try:
            if op != "open":
                raise ValueError(f"unknown session op {op!r}")
            fut.set_result(self.executor.open_sketch_session(**kwargs))
        except (KeyboardInterrupt, SystemExit):
            raise
        except BaseException as e:  # noqa: BLE001 — resolve, don't leak
            fut.set_exception(e)
        return fut

    def train(self, op: str, /, **kwargs) -> Future:
        if op in ("submit", "resume"):
            try:
                if op == "submit":
                    handle = self.executor.submit_train_job(
                        kwargs.pop("spec"),
                        operands=kwargs.pop("operands", None),
                        **kwargs)
                else:
                    handle = self.executor.resume_train_job(**kwargs)
            except (KeyboardInterrupt, SystemExit):
                raise
            except BaseException as e:  # noqa: BLE001 — resolve
                fut: Future = Future()
                fut.set_exception(e)
                return fut
            return handle.future
        fut = Future()
        try:
            if op != "status":
                raise ValueError(f"unknown train op {op!r}")
            fut.set_result(self.executor.train_job_status(**kwargs))
        except (KeyboardInterrupt, SystemExit):
            raise
        except BaseException as e:  # noqa: BLE001 — resolve
            fut.set_exception(e)
        return fut

    def shard(self, task: dict) -> Future:
        # a one-shot thread, not the executor queue: shard compute is
        # host-side ingest + eager folds — queueing it behind flush
        # cohorts would stall serve traffic, and a thread per task
        # keeps the coordinator's dispatch loop non-blocking
        from libskylark_tpu.dist.plan import execute_task

        fut: Future = Future()

        def _run():
            try:
                fut.set_result(execute_task(task))
            except (KeyboardInterrupt, SystemExit):
                raise
            except BaseException as e:  # noqa: BLE001 — resolve
                fut.set_exception(e)

        threading.Thread(target=_run, name=f"{self.name}-shard",
                         daemon=True).start()
        return fut

    def register_operand(self, A, transform=None, dimension=None,
                         **kwargs) -> Future:
        # a one-shot thread, not inline: with a transform the pin
        # waits for the precompute flush, and the router broadcasts a
        # registration to every replica — serial waits would make the
        # broadcast O(replicas × flush) instead of one flush deep
        fut: Future = Future()

        def _run():
            try:
                fut.set_result(str(self.executor.register_operand(
                    A, transform=transform, dimension=dimension,
                    **kwargs)))
            except (KeyboardInterrupt, SystemExit):
                raise
            except BaseException as e:  # noqa: BLE001 — resolve
                fut.set_exception(e)

        threading.Thread(target=_run, name=f"{self.name}-register",
                         daemon=True).start()
        return fut

    def unregister_operand(self, ref) -> Future:
        fut: Future = Future()
        try:
            fut.set_result(self.executor.unregister_operand(ref))
        except (KeyboardInterrupt, SystemExit):
            raise
        except BaseException as e:  # noqa: BLE001 — resolve
            fut.set_exception(e)
        return fut

    def queue_depth(self) -> int:
        return self.executor.queue_depth()

    def state(self) -> str:
        return self.executor.state

    def stats(self) -> dict:
        return self.executor.stats()

    def flush(self) -> None:
        self.executor.flush()

    def drain(self, timeout: Optional[float] = 30.0) -> bool:
        return self.executor.drain(timeout=timeout)

    def shutdown(self) -> None:
        self.executor.shutdown()

    def latency_quantile(self, q: float = 0.99) -> Optional[float]:
        return self.executor.latency_quantile(q)

    def owns_source(self, source: object) -> bool:
        """Whether a health-hub event source is this replica (the
        executor publishes for thread replicas)."""
        return source is self.executor or source is self


# ---------------------------------------------------------------------------
# process-backed replica
# ---------------------------------------------------------------------------


def _send_exception(send, rid, e: BaseException) -> None:
    try:
        send(("error", rid, e))
    except Exception:  # unpicklable exception: degrade to its repr
        send(("error", rid, RuntimeError(repr(e))))


def _resolve(fut: Future, result=None, exception=None) -> None:
    """Resolve a parent-side future, tolerating one already resolved —
    a hedge winner cancels the loser, and the loser's pipe result may
    still arrive afterwards (InvalidStateError is the race's benign
    face, not an error)."""
    try:
        if exception is not None:
            fut.set_exception(exception)
        else:
            fut.set_result(result)
    except Exception:  # noqa: BLE001 — already done/cancelled
        pass


def _worker_main(conn, name: str, executor_kwargs: dict,
                 coordinator: Optional[dict],
                 env: Optional[dict] = None,
                 warmup_pack: Optional[str] = None,
                 shm_spec: Optional[dict] = None) -> None:
    """Child entry point (module-level: spawn pickles it by name)."""
    # the parent's engine/telemetry environment first — everything
    # below (jax config, engine import, executor construction, pack
    # load) must see the parent's explicit snapshot, not whatever
    # os.environ happened to hold at Process.start()
    _apply_env(env)
    if env and env.get("JAX_PLATFORMS"):
        # jax was imported — and read the PARENT's JAX_PLATFORMS — when
        # spawn unpickled this module, before the seat above applied: a
        # chip replica of a parent kept off the chip must tell jax itself
        import jax

        jax.config.update("jax_platforms", env["JAX_PLATFORMS"])
    # attach the shared-memory rings BEFORE the heavy imports: the
    # parent unlinks the names the moment our liveness RPC resolves,
    # and the attach is what keeps the mapping alive past that
    transport = None
    if shm_spec is not None:
        from libskylark_tpu.fleet.shm import ShmTransport

        transport = ShmTransport.attach(shm_spec)
    from libskylark_tpu import engine, resilience
    from libskylark_tpu.resilience import health as _health

    if coordinator:
        # multi-host placement: the replica process joins the jax
        # distributed pool through the same multihost plumbing every
        # sharded code path uses (docs/distributed)
        from libskylark_tpu.parallel import multihost

        multihost.initialize_distributed(**coordinator)

    # SIGTERM → drain this executor + final checkpoint hooks, exactly
    # the in-process preemption contract, scoped to this replica
    resilience.install_preemption_handler()
    ex = engine.MicrobatchExecutor(name=name, **executor_kwargs)
    warmup_report = None
    if warmup_pack:
        # BEFORE the message loop: the parent's liveness RPC (its
        # first "stats") only resolves after this, so a packed child
        # is warm before it can ever accept traffic
        try:
            warmup_report = ex.load_warmup_pack(warmup_pack)
        except Exception as e:  # noqa: BLE001 — boot must not die on
            #                     a bad pack; the compile path serves
            warmup_report = {"skipped": f"load failed: {e!r}"}

    send_lock = _locks.make_lock("fleet.replica_send")

    def send(msg) -> None:
        with send_lock:
            conn.send(msg)

    def flush_acks() -> None:
        """Ship slots whose operand views have been collected back to
        the parent (the p2c ring's writer). Best-effort: a dead pipe
        means the whole pair is going down anyway."""
        if transport is None:
            return
        acks = transport.drain_acks()
        if acks:
            try:
                send(("shmfree", None, acks))
            except Exception:  # noqa: BLE001 — parent gone
                pass

    def forward_state(source, old, new) -> None:
        if source is ex:
            try:
                send(("state", None, new))
            except Exception:  # parent gone mid-teardown
                pass

    _health.subscribe(forward_state)

    def reply(rid, fut: Future) -> None:
        try:
            value = fut.result()
        except BaseException as e:  # noqa: BLE001 — future's exception
            _send_exception(send, rid, e)
            return
        try:
            if transport is None:
                send(("result", rid, value))
            else:
                # result handoff without a serialization copy: the
                # future's value is a view into the flush's one host
                # batch (engine/serve._execute); encode copies those
                # bytes straight into a ring slot and the parent maps
                # them zero-copy
                payload, claimed = transport.encode(value)
                try:
                    send(("result", rid, payload))
                except BaseException:
                    transport.unclaim(claimed)
                    raise
        except BaseException as e:  # noqa: BLE001 — containment
            _send_exception(send, rid, e)

    import functools

    while True:
        flush_acks()
        try:
            if not conn.poll(0.1):
                if (resilience.preemption_requested()
                        and resilience.wait_for_preemption_teardown(0.0)):
                    break            # drained by SIGTERM; parent's
                #                      reader sees our STOPPED event
                continue
            msg = conn.recv()
        except (EOFError, OSError):
            break
        kind, rid = msg[0], msg[1]
        if kind == "shmfree":
            if transport is not None:
                transport.release(msg[2])
            continue
        try:
            if kind == "submit":
                endpoint, kwargs = msg[2], msg[3]
                if transport is not None:
                    try:
                        kwargs = transport.decode(kwargs)
                    except Exception:
                        # request lost, ring capacity recovered; the
                        # outer handler errors the parent's future
                        transport.recover(kwargs)
                        flush_acks()
                        raise
                fut = ex.submit(endpoint, **kwargs)
                fut.add_done_callback(functools.partial(reply, rid))
            elif kind == "session":
                # stateful-session verbs (docs/sessions). Append
                # operands arrive over the pickle pipe (not the shm
                # rings — the batch is about to be journaled to disk
                # anyway, so a zero-copy view buys nothing); results
                # go back through the standard reply path
                op, kwargs = msg[2], msg[3]
                if op == "open":
                    # NOT inline: an open builds the O(n) positional
                    # streams and runs three fsyncs — serviced on the
                    # loop thread it would stall every queued submit
                    # and stats/depth probe on this replica for the
                    # duration. ``send`` is lock-protected (replies
                    # already cross threads), so a one-shot thread
                    # keeps the loop responsive.
                    def _open_reply(rid=rid, kwargs=kwargs):
                        try:
                            send(("rpc", rid,
                                  ex.open_sketch_session(**kwargs)))
                        except Exception as e:  # noqa: BLE001
                            _send_exception(send, rid, e)

                    threading.Thread(target=_open_reply,
                                     name=f"{name}-session-open",
                                     daemon=True).start()
                elif op == "append":
                    fut = ex.session_append(**kwargs)
                    fut.add_done_callback(functools.partial(reply, rid))
                elif op == "finalize":
                    fut = ex.session_finalize(**kwargs)
                    fut.add_done_callback(functools.partial(reply, rid))
                else:
                    raise ValueError(f"unknown session op {op!r}")
            elif kind == "train":
                # training-job verbs (docs/training). submit/resume
                # start on a one-shot thread — the operand persist +
                # session open run fsyncs that must not stall the
                # message loop (same reasoning as session opens) —
                # and the reply fires only at the job's TERMINAL
                # future (slices run in idle scheduler slots in
                # between; a SIGKILL before then leaves the session
                # on disk for a peer to resume)
                op, kwargs = msg[2], msg[3]
                if op == "status":
                    send(("rpc", rid, ex.train_job_status(**kwargs)))
                elif op in ("submit", "resume"):
                    def _train_start(rid=rid, op=op, kwargs=kwargs):
                        try:
                            if op == "submit":
                                h = ex.submit_train_job(
                                    kwargs.pop("spec"),
                                    operands=kwargs.pop(
                                        "operands", None),
                                    **kwargs)
                            else:
                                h = ex.resume_train_job(**kwargs)
                            h.future.add_done_callback(
                                functools.partial(reply, rid))
                        except Exception as e:  # noqa: BLE001
                            _send_exception(send, rid, e)

                    threading.Thread(target=_train_start,
                                     name=f"{name}-train",
                                     daemon=True).start()
                else:
                    raise ValueError(f"unknown train op {op!r}")
            elif kind == "shard":
                # distributed-sketch shard task (docs/distributed):
                # computed on a one-shot thread — ingest + eager folds
                # must not stall the message loop (the same reasoning
                # as session opens above). In-memory shard rows ride
                # the shm rings like submit operands (wire-flattened by
                # dist.plan.source_to_wire); descriptor sources pickle.
                # The ``dist.shard`` fault site fires INSIDE
                # execute_task, in this process — which is how a
                # ``crash`` spec in a victim child's SKYLARK_FAULT_PLAN
                # delivers the deterministic kill -9 mid-storm.
                task = msg[2]
                if transport is not None:
                    try:
                        task = transport.decode(task)
                    except Exception:
                        transport.recover(task)
                        flush_acks()
                        raise

                def _shard_reply(rid=rid, task=task):
                    from libskylark_tpu.dist.plan import execute_task

                    try:
                        send(("rpc", rid, execute_task(task)))
                    except Exception as e:  # noqa: BLE001
                        _send_exception(send, rid, e)

                threading.Thread(target=_shard_reply,
                                 name=f"{name}-shard",
                                 daemon=True).start()
            elif kind == "register":
                # operand-residency verb (docs/caching): the operand
                # rides the shm rings exactly like submit kwargs
                # (pickle-pipe fallback when the transport is off).
                # The executor's pin freezes a private COPY, so the
                # ring slot releases as soon as the decoded view
                # drops — a resident operand never holds shm capacity
                kwargs = msg[2]
                if transport is not None:
                    try:
                        kwargs = transport.decode(kwargs)
                    except Exception:
                        transport.recover(kwargs)
                        flush_acks()
                        raise

                # one-shot thread (the session-open reasoning): with
                # a transform the pin waits for its precompute flush,
                # which must not stall the message loop
                def _register_reply(rid=rid, kwargs=kwargs):
                    try:
                        send(("rpc", rid,
                              str(ex.register_operand(**kwargs))))
                    except Exception as e:  # noqa: BLE001
                        _send_exception(send, rid, e)

                threading.Thread(target=_register_reply,
                                 name=f"{name}-register",
                                 daemon=True).start()
            elif kind == "unregister":
                send(("rpc", rid, ex.unregister_operand(msg[2])))
            elif kind == "stats":
                send(("rpc", rid, ex.stats()))
            elif kind == "env":
                # boot introspection: the applied engine environment +
                # the pack-load report (the env-propagation regression
                # test and fleet debugging read this)
                import jax

                send(("rpc", rid, {
                    "env": _env.snapshot_propagated(),
                    # which devices this replica holds (the chip its
                    # seat assigned it: README "On the chip")
                    "devices": [f"{d.platform}:{d.id}"
                                for d in jax.local_devices()],
                    "warmup": warmup_report,
                    "engine": engine.stats().to_dict(),
                    "shm": (transport.stats()
                            if transport is not None else None),
                }))
            elif kind == "depth":
                send(("rpc", rid, ex.queue_depth()))
            elif kind == "flush":
                ex.flush()
                send(("rpc", rid, True))
            elif kind == "drain":
                send(("rpc", rid, ex.drain(timeout=msg[2])))
            elif kind == "shutdown":
                ex.shutdown()
                send(("rpc", rid, True))
                break
        except Exception as e:  # noqa: BLE001 — per-message containment
            _send_exception(send, rid, e)
    try:
        ex.shutdown()
    except Exception:
        pass
    conn.close()


class ProcessReplica(Replica):
    """A replica in its own spawned process (see module doc). Slow to
    boot (a fresh jax import per child) but a true preemption domain:
    :meth:`preempt` delivers a real SIGTERM."""

    backend = "process"

    def __init__(self, name: str, coordinator: Optional[dict] = None,
                 start_timeout: float = 120.0,
                 warmup_pack: Optional[str] = None,
                 env: Optional[dict] = None,
                 env_overrides: Optional[dict] = None,
                 shm: Optional[bool] = None, **executor_kwargs):
        import multiprocessing as mp

        self.name = str(name)
        ctx = mp.get_context("spawn")
        self._conn, child_conn = ctx.Pipe(duplex=True)
        # the engine environment rides the spawn args, not os.environ
        # timing (PROPAGATED_ENV): snapshot now, apply at child entry.
        # ``env_overrides`` layers on top — the device-pinning seat (a
        # pool can give each replica its own accelerator subset via
        # e.g. CUDA_VISIBLE_DEVICES/TPU flags without mutating the
        # parent's environment)
        self._env = dict(env) if env is not None else propagated_env()
        if env_overrides:
            self._env.update({str(k): (None if v is None else str(v))
                              for k, v in env_overrides.items()})
        # shared-memory operand/result transport (fleet/shm): created
        # before spawn so the names ride the args; unlinked the moment
        # the liveness probe proves the child attached
        if shm is None:
            shm = bool(_env.FLEET_SHM.get())
        self._transport = None
        shm_spec = None
        if shm:
            from libskylark_tpu.fleet.shm import ShmTransport

            self._transport = ShmTransport.create(self.name)
            shm_spec = self._transport.child_spec()
        self._proc = ctx.Process(
            target=_worker_main,
            args=(child_conn, self.name, dict(executor_kwargs),
                  coordinator, self._env, warmup_pack, shm_spec),
            name=f"skylark-replica-{self.name}", daemon=True)
        self._proc.start()
        child_conn.close()
        self._lock = _locks.make_lock("fleet.replica")  # send + bookkeeping
        self._rids = itertools.count()
        self._futures: "dict[int, Future]" = {}
        self._state = "SERVING"
        self._closed = False
        # set by the reader tail when the child died WITHOUT ever
        # announcing STOPPED through the drain flow — the pool's crash
        # reap keys off this, not off is_alive() (which can still read
        # True in the microseconds between pipe EOF and process reap)
        self.unexpected_exit = False
        self._reader = threading.Thread(
            target=self._reader_loop,
            name=f"skylark-replica-{self.name}-reader", daemon=True)
        self._reader.start()
        # prove liveness before the router ever trusts this replica: a
        # stats roundtrip forces the child through import + executor
        # construction (or surfaces its crash now, not mid-traffic)
        if self._rpc("stats", timeout=start_timeout) is None:
            self.shutdown()
            raise ServeOverloadedError(
                f"process replica {self.name!r} failed to come up "
                f"within {start_timeout}s")
        if self._transport is not None:
            # the child is alive, so it holds its own mapping: drop
            # the /dev/shm names NOW — from here on there is nothing a
            # SIGKILL on either side could leak
            self._transport.unlink()

    # -- child → parent ------------------------------------------------

    def _reader_loop(self) -> None:
        from libskylark_tpu.resilience import health as _health

        while True:
            try:
                msg = self._conn.recv()
            except (EOFError, OSError):
                break
            kind, rid, payload = msg[0], msg[1], msg[2]
            if kind == "state":
                old, self._state = self._state, payload
                _health.publish(self, old, payload)
                continue
            if kind == "shmfree":
                if self._transport is not None:
                    self._transport.release(payload)
                continue
            with self._lock:
                fut = self._futures.pop(rid, None)
            if fut is None:
                continue
            if kind == "error":
                _resolve(fut, exception=payload)
            else:                      # "result" / "rpc"
                if kind == "result" and self._transport is not None:
                    try:
                        payload = self._transport.decode(payload)
                    except Exception as e:  # noqa: BLE001 — torn slot
                        # the request is lost; the slots must not be —
                        # ack whatever the payload referenced
                        self._transport.recover(payload)
                        _resolve(fut, exception=ServeOverloadedError(
                            f"replica {self.name!r} shm decode failed: "
                            f"{e!r}"))
                        self._flush_shm_acks()
                        continue
                _resolve(fut, result=payload)
            # result views released since the last turnaround free
            # their slots on the child (the c2p ring's writer)
            self._flush_shm_acks()
        # child gone: nothing pending can ever resolve — and nothing
        # can arrive over the rings either, so tear the transport down
        # (unlink is long done; this drops the parent-side mapping)
        with self._lock:
            dead = list(self._futures.values())
            self._futures.clear()
        for fut in dead:
            if not fut.done():
                fut.set_exception(ServeOverloadedError(
                    f"replica process {self.name!r} exited with "
                    f"requests in flight"))
        if self._state not in ("STOPPED",):
            # the child never announced STOPPED itself: a graceful
            # drain forwards DRAINING -> STOPPED over the pipe BEFORE
            # the EOF, so landing here with a live state means the
            # process died out from under us (kill -9, OOM, the chaos
            # ``crash`` fault) — unless the parent itself tore the
            # pipe down (shutdown of a wedged child)
            old, self._state = self._state, "STOPPED"
            self.unexpected_exit = not self._closed
            _health.publish(self, old, "STOPPED")
        if self._transport is not None:
            self._transport.destroy()

    def _flush_shm_acks(self) -> None:
        """Best-effort ``shmfree`` turnaround for released result
        views (parent side). A dead pipe is fine — the pair is going
        down and the mappings die with the processes."""
        if self._transport is None:
            return
        acks = self._transport.drain_acks()
        if not acks:
            return
        try:
            with self._lock:
                self._conn.send(("shmfree", None, acks))
        except Exception:  # noqa: BLE001 — child gone
            pass

    # -- parent → child ------------------------------------------------

    def _send(self, kind: str, *payload) -> Future:
        fut: Future = Future()
        with self._lock:
            if self._closed or not self._proc.is_alive():
                raise ServeOverloadedError(
                    f"replica process {self.name!r} is not serving")
            rid = next(self._rids)
            self._futures[rid] = fut
            try:
                self._conn.send((kind, rid) + payload)
            except (OSError, ValueError) as e:
                self._futures.pop(rid, None)
                raise ServeOverloadedError(
                    f"replica process {self.name!r} pipe closed") from e
            except BaseException:
                # e.g. an unpicklable payload (PicklingError /
                # AttributeError from a local callable): the message
                # never left, so the rid must not sit in _futures
                # waiting for a reply that cannot come
                self._futures.pop(rid, None)
                raise
        return fut

    def _rpc(self, kind: str, *payload, timeout: float = 30.0):
        try:
            return self._send(kind, *payload).result(timeout=timeout)
        except Exception:  # noqa: BLE001 — callers treat None as down
            return None

    def submit(self, endpoint: str, /, **kwargs) -> Future:
        # the router's predigested derivation is an in-process
        # optimization; over the pipe it would pickle the operands
        # twice — the child re-derives instead
        kwargs.pop("_derived", None)
        if self._transport is None:
            return self._send("submit", endpoint, kwargs)
        self._flush_shm_acks()
        payload, claimed = self._transport.encode(kwargs)
        try:
            return self._send("submit", endpoint, payload)
        except BaseException:
            # the header never left: the child will never ack these
            self._transport.unclaim(claimed)
            raise

    def session(self, op: str, /, **kwargs) -> Future:
        # session operands ride the pickle pipe (see _worker_main's
        # "session" branch); the child re-validates against its spec
        return self._send("session", op, kwargs)

    def train(self, op: str, /, **kwargs) -> Future:
        # train operands ride the pickle pipe like session appends —
        # the child persists them to disk at submit anyway, so a
        # zero-copy shm view buys nothing (docs/training)
        return self._send("train", op, kwargs)

    def register_operand(self, A, transform=None, dimension=None,
                         **kwargs) -> Future:
        # the operand crosses like submit kwargs: shm rings when the
        # transport is up, pickle pipe otherwise (docs/caching)
        kwargs = dict(kwargs, A=np.asarray(A), transform=transform,
                      dimension=dimension)
        if self._transport is None:
            return self._send("register", kwargs)
        self._flush_shm_acks()
        payload, claimed = self._transport.encode(kwargs)
        try:
            return self._send("register", payload)
        except BaseException:
            # the header never left: the child will never ack these
            self._transport.unclaim(claimed)
            raise

    def unregister_operand(self, ref) -> Future:
        return self._send("unregister", str(ref))

    def shard(self, task: dict) -> Future:
        # a task is a plan + source descriptor (or one shard's rows)
        # and the reply an s_dim × d partial — both sketch-sized, not
        # data-sized. In-memory rows (wire-flattened ArraySources)
        # ride the shm rings like submit operands; descriptors and the
        # reply take the pickle pipe
        if self._transport is None:
            return self._send("shard", task)
        self._flush_shm_acks()
        payload, claimed = self._transport.encode(task)
        try:
            return self._send("shard", payload)
        except BaseException:
            # the header never left: the child will never ack these
            self._transport.unclaim(claimed)
            raise

    def queue_depth(self) -> int:
        # outstanding submits the parent knows about — no pipe
        # roundtrip on the routing hot path
        with self._lock:
            return len(self._futures)

    def state(self) -> str:
        return self._state

    def stats(self) -> dict:
        return self._rpc("stats") or {}

    def boot_info(self) -> dict:
        """The child's applied engine environment, its devices,
        warmup-pack report, engine counters and shm-transport stats —
        proof of what the replica booted with (and of what its payloads
        rode on)."""
        return self._rpc("env") or {}

    def transport_stats(self) -> Optional[dict]:
        """Parent-side shared-memory transport counters (``None`` when
        the transport is off)."""
        if self._transport is None:
            return None
        return self._transport.stats()

    def flush(self) -> None:
        self._rpc("flush")

    def drain(self, timeout: Optional[float] = 30.0) -> bool:
        ok = self._rpc("drain", timeout,
                       timeout=(timeout or 30.0) + 10.0)
        return bool(ok)

    def preempt(self) -> None:
        """Deliver a real SIGTERM to the replica process — the child's
        preemption handler drains its executor (in-flight results
        still come back) and runs its checkpoint hooks."""
        if self._proc.is_alive():
            os.kill(self._proc.pid, signal.SIGTERM)

    def shutdown(self) -> None:
        with self._lock:
            if self._closed:
                return
            self._closed = True
        try:
            if self._proc.is_alive():
                try:
                    self._conn.send(("shutdown", next(self._rids)))
                except (OSError, ValueError):
                    pass
            self._proc.join(timeout=30.0)
            if self._proc.is_alive():  # wedged child: don't leak it
                warnings.warn(
                    f"replica process {self.name!r} did not exit; "
                    "terminating", RuntimeWarning, stacklevel=2)
                self._proc.terminate()
                self._proc.join(timeout=5.0)
        finally:
            try:
                self._conn.close()
            except OSError:
                pass
            if self._transport is not None:
                self._transport.destroy()

    def owns_source(self, source: object) -> bool:
        return source is self


__all__ = ["PROPAGATED_ENV", "ProcessReplica", "Replica",
           "ThreadReplica", "propagated_env"]
