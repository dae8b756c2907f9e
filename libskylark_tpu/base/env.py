"""Typed registry of every ``SKYLARK_*`` environment variable.

Before this module existed, ~45 scattered ``os.environ`` reads each
re-implemented the repo's env conventions (off-words, typo-degrades-to-
default) and — worse — a newly added variable had to be *remembered*
into :data:`libskylark_tpu.fleet.replica.PROPAGATED_ENV` or process
replicas silently booted with a different engine environment than their
parent (the r13 poisoned-``os.environ``-child class of bug). Declaring
every variable here once, with its parser, default, doc string and
propagate-to-children flag, makes both problems structural:

- the ``env-registry`` lint rule (:mod:`libskylark_tpu.analysis`)
  rejects any raw ``os.environ`` read of a ``SKYLARK_*`` name outside
  this module, and any reference to an undeclared variable;
- :func:`propagated_names` / :func:`snapshot_propagated` mechanically
  feed the replica spawn path, so a declared-propagating variable can
  never again miss process-replica propagation;
- ``script/lint --env-table`` renders the registry as the generated
  reference table in ``docs/env_vars.rst`` — the docs cannot drift
  from the code because they are emitted from it.

Reads are **never cached here**: ``EnvVar.get()`` consults
``os.environ`` on every call, so tests monkeypatching variables keep
working exactly as before. Modules that deliberately latch a value at
import time (``telemetry.metrics.enabled``, ``utility.timer``) keep
their own latch and read through the registry when they do read.

Parse conventions (the repo's, now in one place):

- *flag*: set-and-not-``"0"``/empty is on (``SKYLARK_TELEMETRY``);
- *off-words*: ``0/off/no/false/""`` disable a path-valued variable
  (``SKYLARK_AOT_DIR=off``);
- *typo degrades to default*: a malformed int/float never crashes a
  sketch apply — it falls back to the declared default.
"""

from __future__ import annotations

import os
from typing import Callable, Dict, Optional, Tuple

_UNSET = object()

#: Values that disable a path-valued variable when set explicitly.
OFF_WORDS = ("", "0", "off", "no", "false")


def parse_flag(raw: str) -> bool:
    """On unless empty/``"0"`` (the telemetry/profiler convention)."""
    return raw not in ("", "0")


def parse_bool_default_on(raw: str) -> bool:
    """Off only for an explicit off-word (``SKYLARK_FLEET_SHM``)."""
    return raw.strip().lower() not in OFF_WORDS


def parse_path_or_off(raw: str) -> Optional[str]:
    """A path, or ``None`` when the value is an off-word."""
    return None if raw.strip().lower() in OFF_WORDS else raw


def parse_int(raw: str) -> int:
    return int(raw)


def parse_positive_int(raw: str) -> int:
    n = int(raw)
    if n <= 0:
        raise ValueError(f"expected a positive integer, got {n}")
    return n


def parse_float(raw: str) -> float:
    return float(raw)


def parse_one(raw: str) -> bool:
    """Strict opt-in: only the literal ``"1"`` enables."""
    return raw == "1"


class EnvVar:
    """One declared variable. ``get()`` parses the live environment
    value (typos degrade to the default); ``raw()``/``is_set()`` serve
    the call sites whose semantics the common parsers can't express —
    both still count as going "through the registry" because the
    *declaration* is what the lint rule, the propagation snapshot and
    the doc table key off."""

    __slots__ = ("name", "default", "parser", "doc", "propagate", "kind")

    def __init__(self, name: str, *, default=None,
                 parser: Optional[Callable[[str], object]] = None,
                 doc: str = "", propagate: bool = False,
                 kind: str = "str"):
        self.name = name
        self.default = default
        self.parser = parser
        self.doc = doc
        self.propagate = propagate
        self.kind = kind

    def raw(self) -> Optional[str]:
        """The unparsed environment value (``None`` when unset)."""
        return os.environ.get(self.name)

    def is_set(self) -> bool:
        return self.name in os.environ

    def get(self, default=_UNSET):
        """Parsed value; the declared default (or ``default=``) when
        unset or malformed — a typo degrades, it never raises."""
        fallback = self.default if default is _UNSET else default
        raw = os.environ.get(self.name)
        if raw is None:
            return fallback
        if self.parser is None:
            return raw
        try:
            return self.parser(raw)
        except (ValueError, TypeError):
            return fallback

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return (f"EnvVar({self.name!r}, default={self.default!r}, "
                f"propagate={self.propagate})")


REGISTRY: Dict[str, EnvVar] = {}


def declare(name: str, *, default=None,
            parser: Optional[Callable[[str], object]] = None,
            doc: str = "", propagate: bool = False,
            kind: str = "str") -> EnvVar:
    """Register one variable (module-definition time only). Raises on a
    duplicate declaration — "declared once" is the whole point."""
    if name in REGISTRY:
        raise ValueError(f"environment variable {name!r} declared twice")
    v = REGISTRY[name] = EnvVar(name, default=default, parser=parser,
                                doc=doc, propagate=propagate, kind=kind)
    return v


def lookup(name: str) -> EnvVar:
    """The declared variable, for dynamic access (the lint rule checks
    literal arguments here against the registry)."""
    try:
        return REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"{name!r} is not a declared SKYLARK environment variable; "
            f"declare it in libskylark_tpu/base/env.py") from None


def propagated_names() -> Tuple[str, ...]:
    """Names a process replica must agree with its parent on — every
    declaration with ``propagate=True``, in declaration order. Feeds
    ``fleet.replica.PROPAGATED_ENV`` mechanically."""
    return tuple(v.name for v in REGISTRY.values() if v.propagate)


def snapshot_propagated() -> Dict[str, Optional[str]]:
    """Raw snapshot of every propagating variable in this process
    (``None`` marks a variable the child must *unset*)."""
    return {name: os.environ.get(name) for name in propagated_names()}


# ---------------------------------------------------------------------------
# declarations — one per SKYLARK_* variable, grouped by subsystem
# ---------------------------------------------------------------------------

# -- telemetry --------------------------------------------------------------

TELEMETRY = declare(
    "SKYLARK_TELEMETRY", default=False, parser=parse_flag, kind="flag",
    propagate=True,
    doc="Enable telemetry recording (any value but empty/``0``). "
        "``SKYLARK_TELEMETRY_DIR`` also enables it implicitly.")

TELEMETRY_DIR = declare(
    "SKYLARK_TELEMETRY_DIR", default=None, kind="path", propagate=True,
    doc="Directory for the JSONL telemetry exporter; setting it both "
        "enables telemetry and auto-installs the exporter at first "
        "import (docs/observability).")

TPU_PROFILE = declare(
    "SKYLARK_TPU_PROFILE", default=False, parser=parse_flag, kind="flag",
    doc="Enable the phase timers (``utility.timer``); latched at first "
        "use, ``timer.set_enabled`` overrides programmatically.")

# -- engine / executable cache ---------------------------------------------

EXEC_CACHE_SIZE = declare(
    "SKYLARK_EXEC_CACHE_SIZE", default=128, parser=parse_positive_int,
    kind="int",
    doc="Capacity of the in-process executable LRU "
        "(``engine.compiled``); read once at engine import.")

ENGINE_DONATE = declare(
    "SKYLARK_ENGINE_DONATE", default=False, parser=parse_one, kind="flag",
    doc="``1`` lets the public solver entry points donate user operands "
        "(invalidates the caller's arrays on every backend; "
        "docs/performance \"donation caveats\").")

EXEC_CACHE_DIR = declare(
    "SKYLARK_EXEC_CACHE_DIR", default=None, parser=parse_path_or_off,
    kind="path", propagate=True,
    doc="jax persistent *compilation* cache directory (HLO-keyed, "
        "tracing still paid). Deprecated as an AOT artifact-store "
        "alias — set ``SKYLARK_AOT_DIR`` for artifacts.")

ENGINE_STATS_DUMP = declare(
    "SKYLARK_ENGINE_STATS_DUMP", default=None, kind="path",
    doc="Write the engine's reset-proof stats rollup to this path at "
        "process exit (the CI jit-leak gate's artifact).")

AOT_DIR = declare(
    "SKYLARK_AOT_DIR", default=None, parser=parse_path_or_off,
    kind="path", propagate=True,
    doc="Persistent AOT executable artifact store "
        "(``engine.aot``); an off-word disables even when the "
        "deprecated ``SKYLARK_EXEC_CACHE_DIR`` alias is present.")

AOT_LOCK_STALE = declare(
    "SKYLARK_AOT_LOCK_STALE", default=600.0, parser=parse_float,
    kind="float",
    doc="Age in seconds past which a peer's AOT file lock is presumed "
        "dead and broken.")

AOT_LOCK_TIMEOUT = declare(
    "SKYLARK_AOT_LOCK_TIMEOUT", default=600.0, parser=parse_float,
    kind="float",
    doc="Seconds a cold process waits on the cross-process AOT compile "
        "lock before compiling anyway (liveness over single-flight).")

# -- serving / fleet --------------------------------------------------------

#: The flush-kernel backends (the authority — ``engine.serve`` imports
#: this as its ``_KERNEL_BACKENDS``, so the env parser and the
#: executor's ``kernel=`` validation can never accept different sets).
SERVE_KERNEL_BACKENDS = ("pallas", "xla")

SERVE_KERNEL = declare(
    "SKYLARK_SERVE_KERNEL", default=None, kind="choice", propagate=True,
    parser=lambda raw: (raw.strip().lower()
                        if raw.strip().lower() in SERVE_KERNEL_BACKENDS
                        else None),
    doc="One-shot flush-kernel override between the executor argument "
        "and the XLA default (``pallas`` | ``xla``; anything else "
        "degrades to the default).")

BOOT_T0 = declare(
    "SKYLARK_BOOT_T0", default=None, parser=parse_float, kind="float",
    doc="Parent's ``time.time()`` at replica spawn; the boot probe "
        "reports honest wall-from-spawn time-to-first-result.")

#: The fleet replica backends (``fleet.ReplicaPool`` imports this so
#: the env parser and the pool's ``backend=`` validation agree).
FLEET_BACKENDS = ("thread", "process", "auto")

FLEET_BACKEND = declare(
    "SKYLARK_FLEET_BACKEND", default="thread", kind="choice",
    parser=lambda raw: (raw.strip().lower()
                        if raw.strip().lower() in FLEET_BACKENDS
                        else "thread"),
    doc="Default ``ReplicaPool`` backend when the constructor does not "
        "pin one: ``thread`` | ``process`` | ``auto`` (process on "
        "hosts with >= 4 cores, thread below — the production "
        "many-core default; docs/fleet \"Process replicas\").")

FLEET_SHM = declare(
    "SKYLARK_FLEET_SHM", default=True, parser=parse_bool_default_on,
    kind="flag",
    doc="Shared-memory operand/result transport for process replicas "
        "(default on; ``0`` forces every payload onto the pickle "
        "pipe — docs/fleet \"Shared-memory transport\").")

FLEET_SHM_MIN_BYTES = declare(
    "SKYLARK_FLEET_SHM_MIN_BYTES", default=16 * 1024,
    parser=parse_int, kind="bytes",
    doc="Arrays at or above this size ride the shared-memory ring; "
        "smaller ones (and non-array values) stay on the pickle pipe "
        "where serialization is cheaper than slot bookkeeping.")

FLEET_SHM_SLOTS = declare(
    "SKYLARK_FLEET_SHM_SLOTS", default=8, parser=parse_positive_int,
    kind="int",
    doc="Slots per shared-memory ring direction (parent->child and "
        "child->parent each get this many); an exhausted ring degrades "
        "to the pickle pipe, never blocks.")

FLEET_SHM_SLOT_BYTES = declare(
    "SKYLARK_FLEET_SHM_SLOT_BYTES", default=1 << 20,
    parser=parse_positive_int, kind="bytes",
    doc="Bytes per shared-memory slot; an operand larger than one slot "
        "falls back to the pickle pipe (counted, not an error).")

FLEET_AUTOSCALE_MIN = declare(
    "SKYLARK_FLEET_AUTOSCALE_MIN", default=1, parser=parse_positive_int,
    kind="int",
    doc="Default ``Autoscaler`` floor: the pool never drains below "
        "this many replicas.")

FLEET_AUTOSCALE_MAX = declare(
    "SKYLARK_FLEET_AUTOSCALE_MAX", default=8, parser=parse_positive_int,
    kind="int",
    doc="Default ``Autoscaler`` ceiling: the pool never grows past "
        "this many replicas.")

FLEET_AUTOSCALE_INTERVAL = declare(
    "SKYLARK_FLEET_AUTOSCALE_INTERVAL", default=0.25, parser=parse_float,
    kind="float",
    doc="Seconds between autoscaler control-loop ticks (the cadence "
        "of the queue-depth evaluation).")

FLEET_AUTOSCALE_UP_DEPTH = declare(
    "SKYLARK_FLEET_AUTOSCALE_UP_DEPTH", default=8, parser=parse_int,
    kind="int",
    doc="Mean queued+in-flight requests per replica at or above which "
        "sustained ticks trigger a scale-up (pack boot).")

FLEET_AUTOSCALE_DOWN_DEPTH = declare(
    "SKYLARK_FLEET_AUTOSCALE_DOWN_DEPTH", default=1, parser=parse_int,
    kind="int",
    doc="Mean queued+in-flight requests per replica below which "
        "sustained ticks trigger a scale-down (SIGTERM drain).")

FLEET_AUTOSCALE_COOLDOWN = declare(
    "SKYLARK_FLEET_AUTOSCALE_COOLDOWN", default=5.0, parser=parse_float,
    kind="float",
    doc="Seconds after any scale event before the controller may act "
        "again (hysteresis against flapping).")

FLEET_HEDGE = declare(
    "SKYLARK_FLEET_HEDGE", default=False, parser=parse_flag, kind="flag",
    propagate=False,
    doc="Router-level hedged requests: mirror a straggling in-flight "
        "request to the second ring-preference replica after a "
        "p99-derived delay and take the first result "
        "(docs/fleet \"Hedged requests\").")

FLEET_HEDGE_DELAY_MS = declare(
    "SKYLARK_FLEET_HEDGE_DELAY_MS", default=None, parser=parse_float,
    kind="float",
    doc="Fixed hedge delay in milliseconds; unset derives the delay "
        "from the live p99 request latency (the r10 histograms).")

FLEET_HEDGE_VERIFY = declare(
    "SKYLARK_FLEET_HEDGE_VERIFY", default=False, parser=parse_flag,
    kind="flag",
    doc="Determinism guard: let the hedge loser complete (instead of "
        "cancelling it) and compare both results bitwise, counting "
        "``fleet.hedge_mismatches`` on divergence (chaos battery).")

# -- stateful serve sessions (libskylark_tpu/sessions) ----------------------

SESSION_DIR = declare(
    "SKYLARK_SESSION_DIR", default=None, parser=parse_path_or_off,
    kind="path", propagate=True,
    doc="Durability root of the stateful serve sessions "
        "(``libskylark_tpu.sessions``): per-session append journals, "
        "checkpoints and meta files live here, and a peer replica "
        "resumes a drained/crashed session from it. Unset: a "
        "process-stable directory under the system temp dir (single-"
        "host handoff still works; set it to shared storage for "
        "cross-host resume). Propagated so process replicas journal "
        "to the same root as their parent.")

SESSION_TTL = declare(
    "SKYLARK_SESSION_TTL", default=600.0, parser=parse_float,
    kind="float",
    doc="Default idle TTL in seconds for stateful serve sessions: a "
        "session untouched this long is evicted (journal and "
        "checkpoint removed; later appends/finalize raise "
        "``SessionEvictedError``). Per-session ``ttl_s`` overrides.")

SESSION_FSYNC_EVERY = declare(
    "SKYLARK_SESSION_FSYNC_EVERY", default=8, parser=parse_positive_int,
    kind="int",
    doc="Journal fsync batching: every Nth append also fsyncs the "
        "session journal. Appends always flush to the OS page cache "
        "(process-crash durable); the fsync cadence bounds what a "
        "whole-machine crash can lose. 1 = fsync every append.")

# -- training jobs (libskylark_tpu/train) -----------------------------------

TRAIN_SLICE_ITERS = declare(
    "SKYLARK_TRAIN_SLICE_ITERS", default=8, parser=parse_positive_int,
    kind="int", propagate=True,
    doc="Default solver iterations per training slice — the unit of "
        "preemption and checkpointing of a train job "
        "(``libskylark_tpu.train``): a slice is never interrupted "
        "mid-step, so this bounds both how long a job can occupy an "
        "idle scheduler slot and how much work a crash can lose past "
        "the last checkpoint. Per-job ``slice_iters`` overrides. "
        "Propagated so process replicas slice identically.")

TRAIN_RETRY_BUDGET = declare(
    "SKYLARK_TRAIN_RETRY_BUDGET", default=3, parser=parse_int,
    kind="int", propagate=True,
    doc="How many failed slices a training job absorbs (requeue and "
        "re-run from the journaled state) before the job fails "
        "terminally. Crash-resume via a peer replica does not consume "
        "this budget — it covers in-process slice errors.")

TRAIN_CKPT_EVERY = declare(
    "SKYLARK_TRAIN_CKPT_EVERY", default=4, parser=parse_positive_int,
    kind="int", propagate=True,
    doc="Checkpoint cadence of training jobs: every Nth slice "
        "boundary writes the solver state through the session "
        "checkpoint path, bounding a crashed replica's journal-replay "
        "cost to at most N slices. 1 = checkpoint every slice.")

TRAIN_DEADLINE_S = declare(
    "SKYLARK_TRAIN_DEADLINE_S", default=600.0, parser=parse_float,
    kind="float", propagate=True,
    doc="Default wall-clock deadline in seconds for a training job "
        "(QoS vocabulary: the job-level budget). A job past its "
        "deadline fails with ``TrainBudgetExhaustedError`` at the "
        "next slice boundary, reporting exact iterations completed. "
        "Per-job ``deadline_s`` overrides.")

# -- distributed sketching (libskylark_tpu/dist) ----------------------------

DIST_SHARD_ROWS = declare(
    "SKYLARK_DIST_SHARD_ROWS", default=8192, parser=parse_positive_int,
    kind="int",
    doc="Default rows per shard task when a ``ShardPlan`` does not pin "
        "``shard_rows`` (``libskylark_tpu.dist``): the unit of "
        "re-executable work in distributed sketching "
        "(docs/distributed).")

DIST_RETRIES = declare(
    "SKYLARK_DIST_RETRIES", default=3, parser=parse_int, kind="int",
    doc="Per-shard retry budget of the distributed-sketch coordinator: "
        "how many times a failed shard task is re-executed (with "
        "reassignment to the next ring-preference replica) before it "
        "is abandoned into the degraded-merge accounting.")

DIST_MIN_COVERAGE = declare(
    "SKYLARK_DIST_MIN_COVERAGE", default=1.0, parser=parse_float,
    kind="float",
    doc="Default ``min_coverage`` gate of a distributed sketch merge: "
        "a merged coverage (fraction of declared rows folded in) below "
        "this raises ``SketchCoverageError`` instead of returning a "
        "degraded result. 1.0 = any abandoned shard raises.")

DIST_HEDGE = declare(
    "SKYLARK_DIST_HEDGE", default=False, parser=parse_flag, kind="flag",
    doc="Mirror straggler shard tasks to the next ring-preference "
        "replica after ``SKYLARK_DIST_HEDGE_DELAY_MS`` and take the "
        "first result (the r15 hedging discipline applied to shard "
        "tasks; bit-equal by construction — shard partials are pure "
        "functions of the plan).")

DIST_HEDGE_DELAY_MS = declare(
    "SKYLARK_DIST_HEDGE_DELAY_MS", default=1000.0, parser=parse_float,
    kind="float",
    doc="Straggler threshold for shard-task hedging: an unresolved "
        "shard task older than this is mirrored when "
        "``SKYLARK_DIST_HEDGE`` is on.")

DIST_SERVE_PIPELINE = declare(
    "SKYLARK_DIST_SERVE_PIPELINE", default=0, parser=parse_int,
    kind="int", propagate=True,
    doc="Pipeline depth of a dist-serve job (``submit_dist_sketch`` "
        "and friends): the maximum concurrently outstanding shard "
        "tasks per job. 0 (default) sizes the window automatically to "
        "2x the fleet — deep enough that ingest, shard compute and "
        "incremental merging overlap, while memory stays bounded at "
        "``depth x`` one sketch-sized partial (docs/distributed).")

DIST_SERVE_MERGE_FANIN = declare(
    "SKYLARK_DIST_SERVE_MERGE_FANIN", default=8,
    parser=parse_positive_int, kind="int", propagate=True,
    doc="Merge fan-in of the incremental dist-serve merger: how many "
        "ready pairwise-tree combines are folded per shard-completion "
        "event. A scheduling knob only — the merge tree itself stays "
        "the canonical pairwise reduction, so the merged bits never "
        "depend on this value (docs/distributed).")

DIST_SERVE_MIN_COVERAGE_INTERACTIVE = declare(
    "SKYLARK_DIST_SERVE_MIN_COVERAGE_INTERACTIVE", default=1.0,
    parser=parse_float, kind="float", propagate=True,
    doc="Default ``min_coverage`` of interactive-class dist-serve "
        "requests. Below 1.0 an interactive request may resolve "
        "EARLY with a quantified ``DegradedSketchResult`` once "
        "coverage reaches the gate and every unresolved shard has "
        "already failed at least once — the latency-SLO trade "
        "(docs/distributed, docs/qos). Per-call ``min_coverage=`` "
        "overrides.")

DIST_SERVE_MIN_COVERAGE_STANDARD = declare(
    "SKYLARK_DIST_SERVE_MIN_COVERAGE_STANDARD", default=1.0,
    parser=parse_float, kind="float", propagate=True,
    doc="Default ``min_coverage`` of standard-class dist-serve "
        "requests. Standard (batch) jobs never resolve early: the "
        "storm runs to completion and the gate applies to the final "
        "merge. Per-call ``min_coverage=`` overrides.")

DIST_SERVE_MIN_COVERAGE_BEST_EFFORT = declare(
    "SKYLARK_DIST_SERVE_MIN_COVERAGE_BEST_EFFORT", default=1.0,
    parser=parse_float, kind="float", propagate=True,
    doc="Default ``min_coverage`` of best_effort-class dist-serve "
        "requests (gate applied to the final merge, no early "
        "resolve). Per-call ``min_coverage=`` overrides.")

FAULT_PLAN = declare(
    "SKYLARK_FAULT_PLAN", default=None, kind="json",
    doc="Deterministic fault-injection plan (inline JSON or a path); "
        "activates the chaos sites process-wide "
        "(docs/resilience).")

LOCK_WITNESS = declare(
    "SKYLARK_LOCK_WITNESS", default=False, parser=parse_flag, kind="flag",
    doc="Instrumented-lock mode: locks built by ``base.locks`` record "
        "their runtime acquisition order and the witness fails on "
        "cycles (enabled in the CI chaos battery; docs/analysis).")

# -- sparse serve operands (engine/serve.py, docs/serving) ------------------

SPARSE_MIN_DENSITY = declare(
    "SKYLARK_SPARSE_MIN_DENSITY", default=0.25, parser=parse_float,
    kind="float",
    doc="Density (nnz / height·width) at or above which ``submit_"
        "sparse`` auto-densifies the operand onto the dense serve "
        "path instead of the CSR lanes (counted as "
        "``serve.sparse_densified``). At high density the padded "
        "CSR lanes carry more bytes than the dense operand and the "
        "O(nnz) scatter loses to the dense contraction.")

SPARSE_NNZ_FLOOR = declare(
    "SKYLARK_SPARSE_NNZ_FLOOR", default=64, parser=parse_positive_int,
    kind="int",
    doc="Granularity floor of the serve layer's pow2 **nnz class**: "
        "requests below this many nonzeros share one class, so a "
        "flood of tiny sparse requests coalesces into a single "
        "bucket instead of one per exact nnz.")

# -- compressed matmul (engine/serve.py, docs/performance) ------------------

FWHT_CM_SDIM = declare(
    "SKYLARK_FWHT_CM_SDIM", default=256, parser=parse_positive_int,
    kind="int", propagate=True,
    doc="Default sketch dimension for ``submit_compressed_matmul`` "
        "when the caller passes a contraction length instead of a "
        "transform. Propagated so process replicas estimate with the "
        "same compression (the error bound scales as 1/sqrt(s)).")

# -- multi-tenant QoS (libskylark_tpu/qos, docs/qos) ------------------------

#: The QoS priority classes, most- to least-protected (the authority —
#: ``qos.tenants`` imports this so the env parser, the scheduler's
#: shed ordering and the tenant registry can never disagree).
QOS_CLASSES = ("interactive", "standard", "best_effort")

QOS_ADAPT = declare(
    "SKYLARK_QOS_ADAPT", default=True, parser=parse_bool_default_on,
    kind="flag", propagate=True,
    doc="Freeze switch for the adaptive batching controller "
        "(``libskylark_tpu.qos.controller``): ``0`` freezes every "
        "executor's per-bucket linger/batch targets at their static "
        "config even when the executor was built with "
        "``adaptive=True``. Default on (controllers run where "
        "requested).")

QOS_DEFAULT_CLASS = declare(
    "SKYLARK_QOS_DEFAULT_CLASS", default="standard", kind="choice",
    propagate=True,
    parser=lambda raw: (raw.strip().lower()
                        if raw.strip().lower() in QOS_CLASSES
                        else "standard"),
    doc="Priority class of requests with no ``tenant=`` (and of "
        "tenants the registry does not know): ``interactive`` | "
        "``standard`` | ``best_effort``. Anything else degrades to "
        "``standard``.")

QOS_SHED_INTERACTIVE = declare(
    "SKYLARK_QOS_SHED_INTERACTIVE", default=0.5, parser=parse_float,
    kind="float",
    doc="DEGRADED-shed fraction of ``max_queue`` for the interactive "
        "class: interactive intake sheds only past this exposure — "
        "the LAST class to shed (docs/qos, \"Shed ordering\").")

QOS_SHED_STANDARD = declare(
    "SKYLARK_QOS_SHED_STANDARD", default=0.25, parser=parse_float,
    kind="float",
    doc="DEGRADED-shed fraction of ``max_queue`` for the standard "
        "class (the pre-QoS ``shed_fraction`` behavior — the executor "
        "argument scales all three class fractions together).")

QOS_SHED_BEST_EFFORT = declare(
    "SKYLARK_QOS_SHED_BEST_EFFORT", default=0.1, parser=parse_float,
    kind="float",
    doc="DEGRADED-shed fraction of ``max_queue`` for the best_effort "
        "class — the FIRST class to shed. Best-effort intake "
        "additionally sheds at half the queue bound even when "
        "healthy, so a best-effort storm can never fill the queue "
        "against higher classes.")

QOS_RATE_DEFAULT = declare(
    "SKYLARK_QOS_RATE_DEFAULT", default=None, parser=parse_float,
    kind="float",
    doc="Default per-tenant admission rate (requests/second) for "
        "tenants registered without an explicit ``rate=``. Unset: "
        "registered tenants are unlimited unless they pin a rate.")

QOS_BURST_DEFAULT = declare(
    "SKYLARK_QOS_BURST_DEFAULT", default=None, parser=parse_float,
    kind="float",
    doc="Default token-bucket burst capacity for rate-limited tenants "
        "without an explicit ``burst=``. Unset: 2x the tenant's rate "
        "(one second of headroom above steady state).")

QOS_ADAPT_INTERVAL = declare(
    "SKYLARK_QOS_ADAPT_INTERVAL", default=0.25, parser=parse_float,
    kind="float",
    doc="Seconds between adaptive-controller ticks (the cadence at "
        "which per-bucket linger/batch targets are re-evaluated "
        "against the class SLOs).")

QOS_SLO_INTERACTIVE_MS = declare(
    "SKYLARK_QOS_SLO_INTERACTIVE_MS", default=25.0, parser=parse_float,
    kind="float",
    doc="p99 request-latency SLO (milliseconds) of the interactive "
        "class — the adaptive controller's target for buckets "
        "carrying interactive traffic.")

QOS_SLO_STANDARD_MS = declare(
    "SKYLARK_QOS_SLO_STANDARD_MS", default=250.0, parser=parse_float,
    kind="float",
    doc="p99 request-latency SLO (milliseconds) of the standard "
        "class.")

QOS_SLO_BEST_EFFORT_MS = declare(
    "SKYLARK_QOS_SLO_BEST_EFFORT_MS", default=5000.0,
    parser=parse_float, kind="float",
    doc="p99 request-latency SLO (milliseconds) of the best_effort "
        "class (throughput-oriented: the controller optimizes padding "
        "waste, not latency, while this holds).")

# -- content-addressed result cache (docs/caching) --------------------------

CACHE = declare(
    "SKYLARK_CACHE", default=False, parser=parse_flag, kind="flag",
    propagate=True,
    doc="Content-addressed result cache + single-flight dedupe on the "
        "serve path (docs/caching). Opt-in (``1``): executors "
        "constructed without an explicit ``cache=`` argument consult "
        "this flag. Propagated so process replicas inherit the "
        "fleet's caching decision.")

CACHE_MAX_BYTES = declare(
    "SKYLARK_CACHE_MAX_BYTES", default=256 * 1024 * 1024,
    parser=parse_positive_int, kind="bytes", propagate=True,
    doc="Per-executor byte budget of the digest->result cache; the "
        "per-class quota fractions partition it. 0-or-invalid "
        "degrades to the default.")

CACHE_QUOTA_INTERACTIVE = declare(
    "SKYLARK_CACHE_QUOTA_INTERACTIVE", default=0.5, parser=parse_float,
    kind="float", propagate=True,
    doc="Fraction of ``SKYLARK_CACHE_MAX_BYTES`` reserved for the "
        "interactive class's cached results. Quotas are hard class "
        "partitions: insertion into one class can only evict that "
        "class's own entries, so a best_effort storm can never evict "
        "an interactive working set (docs/caching, \"Tenant "
        "admission\").")

CACHE_QUOTA_STANDARD = declare(
    "SKYLARK_CACHE_QUOTA_STANDARD", default=0.35, parser=parse_float,
    kind="float", propagate=True,
    doc="Fraction of the cache byte budget reserved for the standard "
        "class (see SKYLARK_CACHE_QUOTA_INTERACTIVE).")

CACHE_QUOTA_BEST_EFFORT = declare(
    "SKYLARK_CACHE_QUOTA_BEST_EFFORT", default=0.15,
    parser=parse_float, kind="float", propagate=True,
    doc="Fraction of the cache byte budget reserved for the "
        "best_effort class (see SKYLARK_CACHE_QUOTA_INTERACTIVE).")

CACHE_SINGLE_FLIGHT_TIMEOUT = declare(
    "SKYLARK_CACHE_SINGLE_FLIGHT_TIMEOUT", default=30.0,
    parser=parse_float, kind="float", propagate=True,
    doc="Seconds an in-flight request stays coalescable: identical "
        "requests arriving later than this behind a still-unresolved "
        "leader start their own flight instead of waiting on a "
        "possibly wedged one (docs/caching, \"Single-flight\").")

# -- network serve front door (docs/networking) -----------------------------

NET_HOST = declare(
    "SKYLARK_NET_HOST", default="127.0.0.1", kind="str",
    doc="Bind address of the TCP serve front door "
        "(:class:`libskylark_tpu.net.server.NetServer`). Loopback by "
        "default — exposing the listener beyond the host is a "
        "deliberate deployment decision, not a default.")

NET_PORT = declare(
    "SKYLARK_NET_PORT", default=0, parser=parse_int, kind="int",
    doc="Bind port of the TCP serve front door. ``0`` (the default) "
        "binds an ephemeral port — read ``NetServer.address`` after "
        "construction (tests, smokes).")

NET_MAX_CONNECTIONS = declare(
    "SKYLARK_NET_MAX_CONNECTIONS", default=256,
    parser=parse_positive_int, kind="int",
    doc="Live-connection ceiling on the front door. A connection past "
        "the ceiling is refused with a structured overload error frame "
        "(code 118, docs/networking) rather than a silent reset.")

NET_INFLIGHT_WINDOW = declare(
    "SKYLARK_NET_INFLIGHT_WINDOW", default=32,
    parser=parse_positive_int, kind="int",
    doc="Per-connection inflight-request window. The reader thread "
        "stops reading once this many responses are unflushed, so a "
        "slow reader backpressures through TCP instead of buffering "
        "responses without bound (docs/networking).")

NET_DRAIN_TIMEOUT_S = declare(
    "SKYLARK_NET_DRAIN_TIMEOUT_S", default=10.0, parser=parse_float,
    kind="float",
    doc="Socket-layer drain budget: how long ``NetServer.drain()`` "
        "(and the SIGTERM preemption hook) waits after GOAWAY for "
        "inflight responses to flush before closing connections.")

NET_RETRY_BUDGET = declare(
    "SKYLARK_NET_RETRY_BUDGET", default=3, parser=parse_int,
    kind="int",
    doc="Transport reconnect-resend attempts per request in "
        ":class:`libskylark_tpu.net.client.NetClient`. Safe by "
        "construction — a re-sent frame is byte-identical, so the "
        "server's single-flight table coalesces it onto the original "
        "flight (docs/networking, \"Retry & idempotency\"). 0 "
        "disables transport retry.")

NET_RETRY_BACKOFF_S = declare(
    "SKYLARK_NET_RETRY_BACKOFF_S", default=0.05, parser=parse_float,
    kind="float",
    doc="Base backoff of the client's reconnect retry loop; actual "
        "sleeps are decorrelated-jittered multiples, capped at 2 s.")

# -- sketch kernels ---------------------------------------------------------

MATMUL_PRECISION = declare(
    "SKYLARK_MATMUL_PRECISION", default=None, kind="choice",
    doc="Ambient jax matmul precision installed at package import "
        "(default ``highest``; ``default`` opts out of installation).")

FASTFOOD_PRECISION = declare(
    "SKYLARK_FASTFOOD_PRECISION", default=None, kind="choice",
    doc="Contraction regime inside the fused fastfood kernel "
        "(``f32`` | ``bf16x3`` | ``bf16``); a ``precision=`` argument "
        "beats it.")

HASH_KERNEL = declare(
    "SKYLARK_HASH_KERNEL", default=None, kind="choice",
    doc="CWT/CountSketch flush kernel override: ``pallas``/``mxu``/"
        "``1``, ``pallas_exact``/``exact``, else the XLA scatter.")

AUTO_MATERIALIZE = declare(
    "SKYLARK_AUTO_MATERIALIZE", default=True,
    parser=parse_bool_default_on, kind="flag",
    doc="Automatic materialize-and-reuse dispatch for OperatorCache "
        "transforms (default on; ``0`` disables — "
        "``sketch/params.py``).")

# -- io ---------------------------------------------------------------------

STREAM_PREFETCH = declare(
    "SKYLARK_STREAM_PREFETCH", default=2, parser=parse_int, kind="int",
    doc="Prefetch depth of the double-buffered streaming overlap "
        "(``io.chunked``); 0 disables the overlap.")

WEBHDFS_RETRIES = declare(
    "SKYLARK_WEBHDFS_RETRIES", default=4, parser=parse_int, kind="int",
    doc="Attempt bound of the WebHDFS transport's default retry "
        "policy.")


__all__ = [
    "EnvVar", "OFF_WORDS", "REGISTRY", "declare", "lookup",
    "parse_flag", "parse_bool_default_on", "parse_path_or_off",
    "parse_int", "parse_positive_int", "parse_float", "parse_one",
    "propagated_names", "snapshot_propagated",
]
