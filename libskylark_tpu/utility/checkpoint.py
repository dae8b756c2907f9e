"""Training-state checkpoint/resume for host-loop solvers.

The reference has no training-state persistence (its aux-subsystem
survey row "failure detection / checkpoint-resume" is empty — SURVEY.md
§5); models and sketches serialize, but a killed 1000-iteration ADMM run
restarts from zero. On TPU this matters operationally: long solves on
preemptible capacity are the norm, so the solver state (the ADMM
consensus carry, a restarted-Krylov basis, a streaming-sketch
accumulator) must outlive the process.

Design: a thin wrapper over orbax (the JAX-ecosystem checkpointer) —
async by default so the save streams out of HBM while the next
iterations compute, atomic + versioned on disk, with a JSON metadata
sidecar validated on restore. Anything shaped like a pytree of arrays
checkpoints; solvers opt in by taking a ``checkpoint=`` argument (see
``BlockADMMSolver.train``).
"""

from __future__ import annotations

import os
from typing import Any, Optional

import jax
import jax.numpy as jnp

from libskylark_tpu.base import errors

try:  # pragma: no cover - exercised via the public API below
    import orbax.checkpoint as ocp

    _HAVE_ORBAX = True
except Exception:  # pragma: no cover
    _HAVE_ORBAX = False


class TrainCheckpointer:
    """Versioned training-state store under one directory.

    ``save(step, state, metadata)`` persists a pytree of arrays plus a
    small JSON dict; ``restore()`` returns the newest ``(step, state,
    metadata)``. Saves are asynchronous (compute overlaps the HBM→disk
    stream) unless ``async_save=False``; in-flight writes are finalized
    on ``close()`` / context-manager exit / before a dependent
    ``restore``.

    ``keep`` bounds disk usage to the newest N steps.
    """

    def __init__(self, directory: str, *, keep: int = 3,
                 async_save: bool = True):
        if not _HAVE_ORBAX:  # pragma: no cover
            raise errors.UnsupportedError(
                "orbax-checkpoint is required for TrainCheckpointer")
        self._dir = os.path.abspath(str(directory))
        os.makedirs(self._dir, exist_ok=True)
        self._mngr = ocp.CheckpointManager(
            self._dir,
            options=ocp.CheckpointManagerOptions(
                max_to_keep=int(keep),
                enable_async_checkpointing=bool(async_save),
            ),
        )

    # -- write side --

    def save(self, step: int, state: Any,
             metadata: Optional[dict] = None) -> None:
        """Persist ``state`` (pytree of arrays) at ``step``. Returns
        immediately in async mode; the write is crash-consistent (orbax
        commits atomically per step directory)."""
        from libskylark_tpu.resilience import faults

        faults.check("checkpoint.save", detail=f"step={int(step)}")
        self._mngr.save(
            int(step),
            args=ocp.args.Composite(
                state=ocp.args.StandardSave(state),
                metadata=ocp.args.JsonSave(metadata or {}),
            ),
        )

    def save_sync(self, step: int, state: Any,
                  metadata: Optional[dict] = None, retry=None) -> None:
        """The preemption-handler save: blocks until the step is durable
        on disk, retrying transient failures under ``retry`` (default: 3
        attempts, short backoff — a SIGTERM grace window is seconds, not
        minutes). Used by
        :func:`libskylark_tpu.resilience.register_checkpoint`; a normal
        training loop wants the async :meth:`save` instead."""
        from libskylark_tpu.resilience.policy import RetryPolicy

        retry = retry or RetryPolicy(max_attempts=3, base_delay=0.1,
                                     max_delay=1.0)

        def attempt():
            self.save(step, state, metadata)
            self._mngr.wait_until_finished()

        retry.call(attempt)

    # -- read side --

    def latest_step(self) -> Optional[int]:
        self._mngr.wait_until_finished()
        return self._mngr.latest_step()

    def restore(self, step: Optional[int] = None, target: Any = None):
        """(step, state, metadata) for ``step`` (default: newest).

        ``target`` — a pytree of like-structured arrays (e.g. the
        freshly-initialized solver state) — restores directly into that
        structure/dtype/sharding; without it, arrays come back as numpy
        and orbax warns that the topology is unverified."""
        self._mngr.wait_until_finished()
        step = self._mngr.latest_step() if step is None else int(step)
        if step is None:
            raise errors.InvalidParametersError(
                f"no checkpoint found under {self._dir}")
        out = self._mngr.restore(
            step,
            args=ocp.args.Composite(
                state=ocp.args.StandardRestore(target),
                metadata=ocp.args.JsonRestore(),
            ),
        )
        return step, out["state"], dict(out["metadata"] or {})

    def metadata(self, step: Optional[int] = None):
        """(step, metadata) WITHOUT touching the state arrays — callers
        validate identity/compatibility first, then ``restore`` with a
        ``target`` (a mismatched state would fail inside orbax with a
        shape error before any friendly validation could run)."""
        self._mngr.wait_until_finished()
        step = self._mngr.latest_step() if step is None else int(step)
        if step is None:
            raise errors.InvalidParametersError(
                f"no checkpoint found under {self._dir}")
        out = self._mngr.restore(
            step,
            args=ocp.args.Composite(metadata=ocp.args.JsonRestore()),
        )
        return step, dict(out["metadata"] or {})

    def all_steps(self) -> list[int]:
        self._mngr.wait_until_finished()
        return sorted(self._mngr.all_steps())

    # -- lifecycle --

    def close(self) -> None:
        self._mngr.wait_until_finished()
        self._mngr.close()

    def __enter__(self) -> "TrainCheckpointer":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


# ---------------------------------------------------------------------------
# synchronous state snapshots: the serve-session twin of save_sync
# ---------------------------------------------------------------------------
#
# ``TrainCheckpointer.save_sync`` is the right tool for long training
# loops (async by default, orbax-managed step history). The stateful
# serve sessions (:mod:`libskylark_tpu.sessions`) need something much
# smaller inside a SIGTERM drain hook: one atomic, durable, dependency-
# light snapshot of a dict of host arrays plus a JSON sidecar — written
# in milliseconds, readable by a peer process with nothing but numpy.
# These module-level twins provide exactly that (npz + json, tmp-file +
# rename atomicity, fsync before rename) and are what
# ``SessionRegistry.checkpoint`` calls from the r9 drain path.


def save_sync(path: str, arrays: dict, metadata: Optional[dict] = None
              ) -> None:
    """Atomically persist ``arrays`` (name -> host ndarray) at ``path``
    (``<path>.npz`` + ``<path>.json``), durable before return — the
    drain-hook discipline of :meth:`TrainCheckpointer.save_sync`
    without the orbax machinery. Byte-exact round trip: ``np.save``
    stores raw array bytes, so a restored accumulator continues
    bit-equal. The ``checkpoint.save`` fault site fires here too, so
    chaos plans cover session checkpoints and training saves alike."""
    import json

    import numpy as np

    from libskylark_tpu.resilience import faults

    faults.check("checkpoint.save", detail=f"sync:{os.path.basename(path)}")
    # the metadata rides INSIDE the npz (a reserved key), so one
    # os.replace commits arrays and metadata together — a two-file
    # scheme can crash between renames and pair a new-generation npz
    # with the previous generation's sidecar, which a resume would
    # read as "replay from the OLD seq" and double-fold the journal
    # tail (review finding). The .json twin below is human forensics
    # only; load_sync never trusts it.
    payload = {k: np.asarray(v) for k, v in arrays.items()}
    if "__meta__" in payload:
        raise ValueError("'__meta__' is a reserved checkpoint key")
    payload["__meta__"] = np.frombuffer(
        json.dumps(metadata or {}).encode("utf-8"), dtype=np.uint8)
    npz_tmp = path + ".npz.tmp"
    with open(npz_tmp, "wb") as fh:
        np.savez(fh, **payload)
        fh.flush()
        os.fsync(fh.fileno())
    json_tmp = path + ".json.tmp"
    with open(json_tmp, "w") as fh:
        json.dump(metadata or {}, fh)
        fh.flush()
        os.fsync(fh.fileno())
    os.replace(npz_tmp, path + ".npz")
    os.replace(json_tmp, path + ".json")


def load_sync(path: str):
    """``(arrays, metadata)`` written by :func:`save_sync`, or ``None``
    when no committed snapshot exists at ``path``. The npz is the one
    unit of atomicity — metadata comes from its embedded ``__meta__``
    record, never from the forensics sidecar, so arrays and metadata
    can never be read from different checkpoint generations."""
    import json

    import numpy as np

    if not os.path.exists(path + ".npz"):
        return None
    with np.load(path + ".npz") as z:
        arrays = {k: z[k] for k in z.files if k != "__meta__"}
        metadata = json.loads(bytes(z["__meta__"]).decode("utf-8"))
    return arrays, metadata


def as_checkpointer(obj) -> TrainCheckpointer:
    """Coerce a path-or-checkpointer argument (solver ``checkpoint=``
    convenience: pass a directory string and get defaults)."""
    if isinstance(obj, TrainCheckpointer):
        return obj
    return TrainCheckpointer(str(obj))


@jax.jit
def _spanning_stat(a):
    """Position-weighted f32 reduction, jit-compiled so host-spanning
    operands are legal; the scalar result is replicated everywhere."""
    w = jnp.cos(jnp.arange(a.shape[0], dtype=jnp.float32) * 0.73 + 0.2)
    if a.ndim == 2:
        w = w[:, None] * jnp.cos(
            jnp.arange(a.shape[1], dtype=jnp.float32) * 1.37 + 0.4
        )[None, :]
    return jnp.sum(a * w, dtype=jnp.float32)


def _fully_addressable(a) -> bool:
    """Whether every shard of ``a`` is host-readable (host arrays: yes;
    jax.Arrays spanning other processes' devices: no). Seam for tests —
    multi-host topologies can't be constructed in a unit process."""
    if isinstance(a, jax.Array):
        return a.is_fully_addressable
    return True


def sample_digest(a, rows: int | None = None,
                  byte_budget: int = 64 << 20) -> str:
    """Exact, platform-independent data identity for resume checks
    (ADMM data, streaming batch 0): sha256 over the f32 BYTES of a
    deterministic sample of leading-axis slices plus the full shape.

    Sampling policy (r4 advisor — a fixed 16-row sample let a one-row
    edit in a 1e6-row operand pass the resume check ~99.998% of the
    time): hash ALL bytes whenever the f32 view fits ``byte_budget``
    (64 MiB default — an (n, d) float32 design matrix up to ~16M
    elements is fully covered); above the budget, sample as many evenly
    strided leading-axis slices as the budget buys — the budget bounds
    SAMPLED BYTES, so wide-row operands gather few rows (never fewer
    than 16) rather than blowing past it. ``rows`` overrides the
    computed sample size when given (bounded callers). Byte equality is
    exact and identical across TPU/CPU and JAX versions. Coverage limit
    above the budget (documented trade): content changes confined to
    unsampled rows are not caught; shape changes and any change
    touching a sampled row (including permutations that move sampled
    rows) are."""
    import hashlib

    import numpy as np

    if not _fully_addressable(a):
        # Multi-host-sharded operand: a host gather of even a few rows
        # would raise (spans non-addressable devices), and so would any
        # EAGER op — multi-process arrays compute only under jit. Fall
        # back to a jitted device-side global f32 reduction whose scalar
        # output is fully replicated (hence host-readable on every
        # process, and identical across them). Position-weighted along
        # both axes so a row/column permutation — which would misalign
        # restored state — changes it. Pinned to the platform/JAX
        # version (reduction order): multi-host checkpoints resume only
        # on the topology they were saved under. Single-host keeps the
        # portable byte digest below.
        stat = float(_spanning_stat(a))
        return hashlib.sha256(
            repr((tuple(a.shape), "device_stat", stat)).encode()
        ).hexdigest()

    n = int(a.shape[0]) if getattr(a, "ndim", 0) else 1
    if rows is None:
        row_bytes = 4 * int(np.prod(
            [int(d) for d in getattr(a, "shape", ())[1:]], dtype=np.int64)
            or 1)
        # byte-bounded, never fewer than 16 rows: a (4k, 4M) operand
        # must not be forced to gather 1024 × 16 MB rows (review
        # finding — a row-count floor inverts the byte budget)
        rows = max(16, byte_budget // max(row_bytes, 1))
    idx = sorted(set(
        int(i) for i in np.linspace(0, max(n - 1, 0), num=min(rows, n))))
    idx_arr = np.asarray(idx, dtype=np.intp)  # empty axis: valid no-op
    sample = np.ascontiguousarray(
        np.asarray(a[idx_arr] if getattr(a, "ndim", 0) else a,
                   np.float32))
    h = hashlib.sha256()
    h.update(repr((tuple(getattr(a, "shape", ())), idx)).encode())
    h.update(sample.tobytes())
    return h.hexdigest()


def device_state(state, dtype=None):
    """Restore helper: a pytree of host arrays → device arrays,
    floating-point leaves cast to ``dtype`` when given. Integer/bool
    leaves keep their stored dtype — a step counter or index array in a
    general training state must not be silently cast to the float
    compute dtype (r3 advisor)."""
    def put(x):
        if not hasattr(x, "shape"):
            return x
        if dtype is not None and jnp.issubdtype(
                getattr(x, "dtype", jnp.float32), jnp.floating):
            return jnp.asarray(x, dtype)
        return jnp.asarray(x)
    return jax.tree_util.tree_map(put, state)
