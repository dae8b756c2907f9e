"""``engine.aot`` — persistent AOT executable artifacts.

The r7 executable cache made every solver/serve program compile once
*per process*; this layer makes it compile once *per fleet*. Every AOT
compile that goes through :mod:`libskylark_tpu.engine.compiled` is
serialized (``jax.experimental.serialize_executable``) into an artifact
store under ``SKYLARK_AOT_DIR``, addressed by a digest of the exact
executable-cache key — (solver name, code-version hash, statics,
key_fn extras incl. the serve flush's kernel token, avals, sharding,
donation, precision regime, backend) — so a fresh
process (or a :class:`~libskylark_tpu.fleet.ProcessReplica` child)
**loads instead of compiling** and serves the same bits from its first
request (docs/performance, "Persistent AOT artifacts & warmup packs").

Safety model:

- **The key is the contract.** Anything that would change the traced
  program changes a key component and therefore the digest — a stale
  artifact can never be *served*, only *ignored*. Invalidation is
  automatic: a code change in the wrapped solver or the engine
  itself, a precision flip, a sharding change each land on a fresh
  digest.
- **Compatibility probing.** The key does not capture the runtime, so
  every artifact carries a compat stamp (schema, jax/jaxlib version,
  backend, device kind, device count) checked before deserialization;
  any mismatch — and any deserialize failure at all — falls back to a
  fresh compile, counted (``aot_load_failures``) and warned once per
  reason, never raised into the caller.
- **Cross-process single-flight.** A cold key takes a per-digest file
  lock before compiling; N racing cold processes elect one compiler
  while the rest block on the lock and then *load* the winner's
  artifact — exactly one backend compile fleet-wide. A lock whose
  holder died (same-host pid probe) or that outlived
  ``SKYLARK_AOT_LOCK_STALE`` seconds is taken over; a lock wait past
  ``SKYLARK_AOT_LOCK_TIMEOUT`` gives up and compiles anyway
  (liveness beats strict exactly-once).

``SKYLARK_AOT_DIR`` names the store (``0``/``off`` disables). The
pre-r13 ``SKYLARK_EXEC_CACHE_DIR`` — which wires jax's persistent
*compilation* cache (tracing still paid, HLO-keyed) — doubles as a
deprecated alias: when only it is set, artifacts go to
``$SKYLARK_EXEC_CACHE_DIR/aot`` with a one-time ``DeprecationWarning``.
"""

from __future__ import annotations

import contextlib
import hashlib
import json
import os
import pickle
import socket
import struct
import time
import warnings
from typing import Any, Optional

from libskylark_tpu.base import env as _env

AOT_SCHEMA = 2  # 2: artifacts record the device ids they load onto

_MAGIC = b"SKYAOT1\n"
_SUFFIX = ".skyaot"
# builder-scoped dir override (engine.warmup writes a pack's artifacts
# without touching the process environment)
_DIR_OVERRIDE: Optional[str] = None
_alias_warned = False


class AotLoadError(Exception):
    """An artifact exists but cannot be used (compat mismatch, torn
    file, deserialize failure). ``reason`` is a stable slug the
    failure counters/warnings carry; the caller falls back to a fresh
    compile."""

    def __init__(self, reason: str, detail: str = ""):
        super().__init__(f"{reason}: {detail}" if detail else reason)
        self.reason = reason


# ---------------------------------------------------------------------------
# store location + policy
# ---------------------------------------------------------------------------


def aot_dir() -> Optional[str]:
    """The artifact store directory, or None when disabled.
    ``SKYLARK_AOT_DIR`` wins; a set-but-off value disables even when
    the deprecated ``SKYLARK_EXEC_CACHE_DIR`` alias is present."""
    global _alias_warned
    if _DIR_OVERRIDE is not None:
        return _DIR_OVERRIDE
    if _env.AOT_DIR.is_set():
        # set: the parsed value (an off-word parses to None — disabled,
        # and the legacy alias below must NOT resurrect the store)
        return _env.AOT_DIR.get()
    legacy = _env.EXEC_CACHE_DIR.get()
    if legacy:
        if not _alias_warned:
            _alias_warned = True
            warnings.warn(
                "SKYLARK_EXEC_CACHE_DIR without SKYLARK_AOT_DIR: using "
                f"{legacy}/aot for AOT executable artifacts. The "
                "variable is deprecated for this purpose — it keeps "
                "wiring jax's persistent compilation cache; set "
                "SKYLARK_AOT_DIR for the artifact store "
                "(docs/performance).",
                DeprecationWarning, stacklevel=2)
        return os.path.join(legacy, "aot")
    return None


def enabled() -> bool:
    return aot_dir() is not None


@contextlib.contextmanager
def override_dir(path: Optional[str]):
    """Scoped store override (the warmup-pack builder). Not re-entrant
    across threads — builders are offline, single-threaded tools."""
    global _DIR_OVERRIDE
    prev = _DIR_OVERRIDE
    _DIR_OVERRIDE = path
    try:
        yield
    finally:
        _DIR_OVERRIDE = prev


def lock_stale_seconds() -> float:
    return _env.AOT_LOCK_STALE.get()


def lock_timeout() -> float:
    return _env.AOT_LOCK_TIMEOUT.get()


# ---------------------------------------------------------------------------
# addressing + compatibility
# ---------------------------------------------------------------------------


def key_digest(key: Any) -> str:
    """Content address of one executable-cache key. The key tuple is
    built from primitives with stable ``repr`` (strings, ints, bools,
    nested tuples), so its repr is a faithful serialization."""
    return hashlib.sha256(repr(key).encode()).hexdigest()[:32]


def compat_stamp() -> dict:
    """The runtime properties an artifact is only valid under — the
    parts of the world the cache key does NOT capture."""
    import jax

    try:
        import jaxlib

        jaxlib_v = getattr(jaxlib, "__version__", "unknown")
    except Exception:
        jaxlib_v = "unknown"
    devs = jax.devices()
    return {
        "schema": AOT_SCHEMA,
        "jax": jax.__version__,
        "jaxlib": jaxlib_v,
        "backend": jax.default_backend(),
        "device_kind": devs[0].device_kind if devs else "unknown",
        "device_count": len(devs),
    }


_compat_tag_cache: Optional[str] = None


def compat_tag() -> str:
    """Short content hash of this runtime's compat stamp — part of the
    artifact *filename*, so runtimes whose cache keys coincide (same
    backend, different jax/jaxlib/device kind/count) address different
    files in a shared store instead of overwriting each other's
    artifacts on every fallback compile."""
    global _compat_tag_cache
    if _compat_tag_cache is None:
        doc = json.dumps(compat_stamp(), sort_keys=True).encode()
        _compat_tag_cache = hashlib.sha256(doc).hexdigest()[:8]
    return _compat_tag_cache


def compat_probe(stamp: Optional[dict]) -> tuple[bool, Optional[str]]:
    """(ok, why-not) of an artifact/pack stamp against this process."""
    if not isinstance(stamp, dict):
        return False, "no-compat-stamp"
    here = compat_stamp()
    for field in ("schema", "jax", "jaxlib", "backend", "device_kind",
                  "device_count"):
        if stamp.get(field) != here[field]:
            return False, (f"{field}-mismatch "
                           f"({stamp.get(field)!r} != {here[field]!r})")
    return True, None


def artifact_path(digest: str, dirpath: Optional[str] = None) -> str:
    """Where THIS runtime's artifact for ``digest`` lives — the name
    carries the compat tag, so heterogeneous runtimes sharing one
    store coexist instead of thrashing one path."""
    d = dirpath or aot_dir()
    if d is None:
        raise RuntimeError("AOT artifact store is not enabled")
    return os.path.join(d, f"{digest}.{compat_tag()}{_SUFFIX}")


# ---------------------------------------------------------------------------
# artifact file format: MAGIC | u64 header length | JSON header | pickle
# (the header is readable without unpickling — compat probing and pack
# inspection never execute artifact bytes they might reject)
# ---------------------------------------------------------------------------


def save(key: Any, executable: Any, *, name: str,
         compile_seconds: float = 0.0, meta: Optional[dict] = None,
         dirpath: Optional[str] = None) -> Optional[str]:
    """Serialize one compiled executable under its key digest. Never
    raises — persistence is an optimization, not a failure mode; a
    failed save returns None (counted by the caller's store stats).
    The write is atomic (temp + ``os.replace``): a racing reader sees
    the old artifact or the new one, never a torn file."""
    from jax.experimental import serialize_executable as _se

    d = dirpath or aot_dir()
    if d is None:
        return None
    tmp = None
    try:
        os.makedirs(d, exist_ok=True)
        payload, in_tree, out_tree = _se.serialize(executable)
        digest = key_digest(key)
        header = {
            "schema": AOT_SCHEMA,
            "digest": digest,
            "name": name,
            "compat": compat_stamp(),
            "created": time.time(),
            "compile_seconds": round(float(compile_seconds), 4),
            "key_repr": repr(key),
        }
        if meta:
            header.update(meta)
        hdr = json.dumps(header, sort_keys=True).encode()
        path = artifact_path(digest, d)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "wb") as fh:
            fh.write(_MAGIC)
            fh.write(struct.pack(">Q", len(hdr)))
            fh.write(hdr)
            # the devices the program was compiled for, by id: a load
            # defaults to every device of the backend, which no
            # single-device executable survives on a multi-device host
            device_ids = [dev.id for dev in
                          executable.runtime_executable().local_devices()]
            pickle.dump({"key": key, "payload": payload,
                         "in_tree": in_tree, "out_tree": out_tree,
                         "device_ids": device_ids},
                        fh, protocol=pickle.HIGHEST_PROTOCOL)
        os.replace(tmp, path)
        return path
    except Exception as e:  # noqa: BLE001 — never fail the compile path
        if tmp is not None:
            with contextlib.suppress(OSError):
                os.unlink(tmp)    # no orphan .tmp litter in the store
        warnings.warn(f"AOT artifact save failed for {name!r}: {e!r}",
                      RuntimeWarning, stacklevel=2)
        return None


def read_header(path: str) -> dict:
    """The artifact's JSON header (no unpickling). Raises
    :class:`AotLoadError` on a torn/foreign file."""
    try:
        with open(path, "rb") as fh:
            magic = fh.read(len(_MAGIC))
            if magic != _MAGIC:
                raise AotLoadError("bad-magic", path)
            (hlen,) = struct.unpack(">Q", fh.read(8))
            if hlen > 1 << 20:
                raise AotLoadError("oversized-header", path)
            return json.loads(fh.read(hlen))
    except AotLoadError:
        raise
    except FileNotFoundError:
        raise
    except Exception as e:  # noqa: BLE001 — torn file, bad json, ...
        raise AotLoadError("unreadable-header", repr(e)) from e


def load_file(path: str) -> tuple[Any, Any, dict]:
    """``(key, executable, header)`` from one artifact file. Raises
    :class:`AotLoadError` on any compat or deserialize problem and
    ``FileNotFoundError`` on a plain miss."""
    import jax
    from jax.experimental import serialize_executable as _se

    header = read_header(path)
    ok, why = compat_probe(header.get("compat"))
    if not ok:
        raise AotLoadError("compat", why or "")
    try:
        with open(path, "rb") as fh:
            fh.seek(len(_MAGIC))
            (hlen,) = struct.unpack(">Q", fh.read(8))
            fh.seek(len(_MAGIC) + 8 + hlen)
            doc = pickle.load(fh)
        by_id = {dev.id: dev for dev in jax.devices()}
        executable = _se.deserialize_and_load(
            doc["payload"], doc["in_tree"], doc["out_tree"],
            execution_devices=[by_id[i] for i in doc["device_ids"]])
    except FileNotFoundError:
        raise                 # a plain miss — the caller compiles
    except Exception as e:  # noqa: BLE001 — deserialize is best-effort;
        # I/O errors (stale NFS handle, permissions) take the same
        # fail-open fallback-to-compile route as a bad pickle — the
        # module contract is that a load failure is never raised into
        # the serve path
        raise AotLoadError("deserialize", repr(e)) from e
    return doc["key"], executable, header


def load(key: Any, dirpath: Optional[str] = None
         ) -> Optional[tuple[Any, dict, float]]:
    """``(executable, header, load_seconds)`` for ``key``, or None when
    no artifact exists. Raises :class:`AotLoadError` when one exists
    but is unusable — the caller counts the failure and compiles."""
    d = dirpath or aot_dir()
    if d is None:
        return None
    path = artifact_path(key_digest(key), d)
    t0 = time.perf_counter()
    try:
        stored_key, executable, header = load_file(path)
        if stored_key != key:
            # a digest collision, or an artifact store shared across
            # incompatible code versions whose digests happened to
            # match — either way the stored program is another key's
            raise AotLoadError("key-mismatch", path)
    except FileNotFoundError:
        return None
    except AotLoadError as e:
        # quarantine genuinely broken files so the store self-heals
        # (every later process would otherwise re-fail on the same
        # bytes); compat mismatches stay — the artifact is valid for
        # the runtime that wrote it (a cpu/tpu- or device-count-
        # heterogeneous fleet sharing one store)
        if e.reason != "compat":
            with contextlib.suppress(OSError):
                os.replace(path, path + ".bad")
        raise
    return executable, header, time.perf_counter() - t0


# ---------------------------------------------------------------------------
# cross-process single-flight: a per-digest advisory file lock
# ---------------------------------------------------------------------------


class FileLock:
    """O_EXCL-based advisory lock with stale-holder takeover.

    The holder writes ``{pid, host, t}`` into the lock file. A waiter
    declares the lock stale — and takes it over — when the recorded
    pid is dead (same host only; a pid means nothing remotely) or the
    file is older than ``stale_seconds`` (the cross-host fallback: a
    compile that outlives it has lost its claim either way). A
    takeover unlink is gated on the judged file's inode identity
    (:meth:`_reap`) so racing reapers cannot remove each other's
    re-created locks, and re-creation resolves at
    ``O_CREAT|O_EXCL``: one contender wins, the rest go back to
    waiting."""

    def __init__(self, path: str, *, stale_seconds: Optional[float] = None,
                 poll: float = 0.05):
        self.path = path
        self.stale_seconds = (lock_stale_seconds()
                              if stale_seconds is None else stale_seconds)
        self.poll = poll
        self.held = False

    def _stale_ident(self) -> Optional[tuple]:
        """The (inode, mtime_ns) of the lock file iff it is stale, else
        None. The identity gates the takeover unlink: a contender may
        only remove the exact file it judged stale, never a lock a
        faster peer re-created at the same path in between."""
        try:
            st = os.stat(self.path)
        except OSError:
            return None           # vanished — the create loop retries
        ident = (st.st_ino, st.st_mtime_ns)
        age = time.time() - st.st_mtime
        if age > self.stale_seconds:
            return ident
        try:
            with open(self.path) as fh:
                doc = json.load(fh)
        except Exception:  # noqa: BLE001 — holder died mid-write
            return ident if age > 1.0 else None  # a live writer's instant
        pid, host = doc.get("pid"), doc.get("host")
        if host == socket.gethostname() and isinstance(pid, int):
            try:
                os.kill(pid, 0)
            except ProcessLookupError:
                return ident      # holder is gone
            except PermissionError:
                return None       # alive, different uid
        return None

    def _reap(self, ident: tuple) -> None:
        """Unlink the stale lock only if it is still the judged file —
        two waiters that both judged the old lock stale must not
        unlink each other's freshly re-created locks. (The stat/unlink
        pair is not atomic; the residual window needs the same-path
        inode to be recycled within microseconds, and the worst case
        is one duplicate compile, never a wrong result.)"""
        with contextlib.suppress(OSError):
            st = os.stat(self.path)
            if (st.st_ino, st.st_mtime_ns) == ident:
                os.unlink(self.path)

    def acquire(self, timeout: Optional[float] = None) -> bool:
        """Block until held (True) or ``timeout`` elapses (False — the
        caller proceeds without the lock rather than hanging boot)."""
        deadline = (time.monotonic() + timeout
                    if timeout is not None else None)
        while True:
            try:
                fd = os.open(self.path,
                             os.O_CREAT | os.O_EXCL | os.O_WRONLY)
            except FileExistsError:
                ident = self._stale_ident()
                if ident is not None:
                    self._reap(ident)
                    continue
                if deadline is not None and time.monotonic() >= deadline:
                    return False
                time.sleep(self.poll)
                continue
            except OSError:
                return False      # store dir unwritable: degrade
            with os.fdopen(fd, "w") as fh:
                json.dump({"pid": os.getpid(),
                           "host": socket.gethostname(),
                           "t": time.time()}, fh)
            self.held = True
            return True

    def release(self) -> None:
        """Unlink only a lock we still own: a holder whose compile
        outlived ``stale_seconds`` may have been age-reaped and the
        path re-created by the takeover peer — deleting *that* lock
        would cascade a third holder in while the peer still works."""
        if not self.held:
            return
        self.held = False
        try:
            with open(self.path) as fh:
                doc = json.load(fh)
        except Exception:  # noqa: BLE001 — gone or torn: nothing to free
            return
        if (doc.get("pid") == os.getpid()
                and doc.get("host") == socket.gethostname()):
            with contextlib.suppress(OSError):
                os.unlink(self.path)

    def __enter__(self) -> "FileLock":
        self.acquire()
        return self

    def __exit__(self, *exc) -> None:
        self.release()


def lock_for(key: Any, dirpath: Optional[str] = None) -> FileLock:
    d = dirpath or aot_dir()
    if d is None:
        raise RuntimeError("AOT artifact store is not enabled")
    # an uncreatable store must not fail the compile path (the same
    # fail-open discipline as save()): acquire() on the impossible
    # path returns False and the caller compiles without the lock
    with contextlib.suppress(OSError):
        os.makedirs(d, exist_ok=True)
    return FileLock(os.path.join(d, key_digest(key) + ".lock"))


def list_artifacts(dirpath: Optional[str] = None) -> list[dict]:
    """Headers of every readable artifact in the store (inspection /
    the warmup CLI); unreadable files are skipped, not raised."""
    d = dirpath or aot_dir()
    if d is None or not os.path.isdir(d):
        return []
    out = []
    for fn in sorted(os.listdir(d)):
        if not fn.endswith(_SUFFIX):
            continue
        try:
            out.append(read_header(os.path.join(d, fn)))
        except Exception:  # noqa: BLE001 — inspection is best-effort
            continue
    return out


__all__ = [
    "AOT_SCHEMA", "AotLoadError", "FileLock", "aot_dir", "artifact_path",
    "compat_probe", "compat_stamp", "enabled", "key_digest",
    "list_artifacts", "load", "load_file", "lock_for", "lock_timeout",
    "override_dir", "read_header", "save",
]
