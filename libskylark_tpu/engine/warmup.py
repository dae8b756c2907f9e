"""``engine.warmup`` — warmup packs: precompiled serve-bucket bundles
for zero-recompile fleet boot.

A **warmup pack** is a directory holding (a) one serialized AOT
artifact per hot ``(serve bucket, capacity)`` executable — the exact
programs a :class:`~libskylark_tpu.engine.serve.MicrobatchExecutor`
flushes — and (b) a ``pack.json`` manifest recording, per entry, the
artifact digest, the endpoint/bucket statics, the capacity class, and
the **kernel decision** the builder's executor resolved for that bucket
(the backend's name, a static of the key), plus the pack-wide compat
stamp.

Boot flow (docs/performance, "Persistent AOT artifacts & warmup
packs"): a fresh process — a cold autoscaled replica, a
:class:`~libskylark_tpu.fleet.ProcessReplica` child — calls
:func:`load_pack` (or ``MicrobatchExecutor.load_warmup_pack``) before
accepting traffic. Every packed executable deserializes straight into
the process executable cache under its original key, and the packed
kernel decisions seed the executor's flush-kernel memo, so the first
request of every packed bucket is a cache **hit**: zero tracing, zero
backend compiles, bit-equal results (the executable is byte-identical
to the builder's).

Invalidation is inherited from the key, not re-implemented: a code
change re-keys (artifacts never hit), a jax upgrade / backend / device
change fails the compat probe (pack skipped), and a pack of another
``PACK_SCHEMA`` (schema 1 keyed its entries on a part the key no longer
has) is reported skipped. A skipped or partial pack is never an error
unless ``strict=True`` — boot degrades to the ordinary compile path.

Which buckets go into a pack is the builder's own list, one
:class:`BucketSpec` each (``skylark-warmup build --spec``).
"""

from __future__ import annotations

import dataclasses
import json
import os
import time
from typing import Optional, Sequence

from libskylark_tpu.engine import aot as _aot


def _compiled_module():
    """The :mod:`libskylark_tpu.engine.compiled` module — fetched by
    full name because the package re-exports the same-named decorator,
    shadowing the submodule attribute."""
    import importlib

    return importlib.import_module("libskylark_tpu.engine.compiled")


PACK_SCHEMA = 2
MANIFEST = "pack.json"
_ARTIFACTS = "artifacts"


@dataclasses.dataclass
class BucketSpec:
    """One serve bucket to precompile: the transform class and a
    representative operand shape (padding classes derive exactly as
    they do on the serve path, so a pow2-padded representative *is*
    the class)."""

    endpoint: str             # "sketch_apply" | "fastfood_features"
    family: str               # "JLT" | "CWT" | "FastGaussianRFT" | ...
    n: int                    # transform input dim (contracted extent)
    m: int                    # free extent (rows rowwise / cols columnwise)
    s_dim: int
    dtype: str = "float32"
    rowwise: bool = False
    capacities: tuple = (1,)
    sigma: float = 1.0        # fastfood kernel bandwidth (bucket static)
    seed: int = 0

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["capacities"] = list(self.capacities)
        return d

    @classmethod
    def from_dict(cls, d: dict) -> "BucketSpec":
        d = dict(d)
        d["capacities"] = tuple(int(c) for c in d.get("capacities", (1,)))
        return cls(**d)


def _make_transform(spec: BucketSpec):
    from libskylark_tpu import Context
    from libskylark_tpu import sketch as sk

    ctx = Context(seed=int(spec.seed))
    if spec.family == "CWT":
        return sk.CWT(spec.n, spec.s_dim, ctx)
    if spec.family == "JLT":
        return sk.JLT(spec.n, spec.s_dim, ctx)
    if spec.family == "CT":
        return sk.CT(spec.n, spec.s_dim, ctx)
    if spec.family == "FastGaussianRFT":
        return sk.FastGaussianRFT(spec.n, spec.s_dim, ctx,
                                  sigma=spec.sigma)
    if spec.family == "FastMaternRFT":
        # the spec's sigma rides as the length scale l
        return sk.FastMaternRFT(spec.n, spec.s_dim, ctx, nu=1.5,
                                l=spec.sigma)
    raise ValueError(f"warmup pack cannot build family {spec.family!r}")


def _spec_requests(spec: BucketSpec, capacity: int):
    """``capacity`` distinct (transform, operand) pairs for one flush
    of the spec's bucket — ragged free extents inside one padding
    class, like real traffic."""
    import numpy as np

    rng = np.random.default_rng(spec.seed + capacity)
    out = []
    for i in range(capacity):
        T = _make_transform(dataclasses.replace(spec, seed=spec.seed + i))
        m = max(1, spec.m - (i % min(4, spec.m)))
        if spec.endpoint == "fastfood_features":
            shape = (m, spec.n)
        else:
            shape = (m, spec.n) if spec.rowwise else (spec.n, m)
        A = rng.standard_normal(shape).astype(spec.dtype)
        out.append((T, A))
    return out


def _submit(ex, spec: BucketSpec, T, A):
    from libskylark_tpu.sketch import COLUMNWISE, ROWWISE

    if spec.endpoint == "fastfood_features":
        return ex.submit_fastfood(T, A)
    return ex.submit_sketch(T, A,
                            dimension=ROWWISE if spec.rowwise
                            else COLUMNWISE)


def result_digest(arrays) -> str:
    """Content hash of a cohort's results (shape + dtype + bytes per
    lane) — the bit-equality witness the boot probe compares against
    the builder's recorded value."""
    import hashlib

    import numpy as np

    h = hashlib.sha256()
    for a in arrays:
        a = np.asarray(a)
        h.update(repr((a.shape, str(a.dtype))).encode())
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()[:32]


def _entry_from_key(key: tuple) -> dict:
    """Manifest entry metadata recovered from one executable-cache key
    (see engine/compiled docstring for the tuple anatomy)."""
    statics, kernel = _statics_and_kernel(key)
    capacity = None
    if key[4]:
        lead = key[4][0][0]
        capacity = int(lead[0]) if lead else None
    return {
        "digest": _aot.key_digest(key),
        "name": key[0],
        "endpoint": statics[0] if statics else None,
        "kernel": kernel,
        "capacity": capacity,
        "statics": repr(statics),
    }


def build_pack(pack_dir: str, specs: Sequence, *,
               pad_floor: Optional[int] = None, workers: int = 1,
               reset_engine: bool = True) -> dict:
    """Precompile every (spec, capacity) serve executable and serialize
    it into ``pack_dir`` (artifacts under ``artifacts/``, manifest at
    ``pack.json``). Returns the manifest.

    The builder drives a real :class:`MicrobatchExecutor` — the packed
    executables are the genuine serve programs (same statics, same
    avals, same kernel resolution), not reconstructions. By default the
    process executable cache is reset first so every packed key
    demonstrably produces an artifact (an offline builder has no warm
    cache worth keeping); pass ``reset_engine=False`` to ride an
    existing warm cache when you know the artifacts already exist.
    """
    from libskylark_tpu.engine import bucket as bucketing

    _compiled = _compiled_module()

    specs = [s if isinstance(s, BucketSpec) else BucketSpec.from_dict(s)
             for s in specs]
    if not specs:
        raise ValueError("a warmup pack needs at least one bucket spec")
    max_cap = max(max(s.capacities) for s in specs)
    artifacts = os.path.join(pack_dir, _ARTIFACTS)
    os.makedirs(artifacts, exist_ok=True)
    if reset_engine:
        _compiled.reset()

    from libskylark_tpu.engine.serve import MicrobatchExecutor

    entries: list[dict] = []
    with _aot.override_dir(artifacts):
        ex = MicrobatchExecutor(
            max_batch=max_cap, linger_us=50_000, workers=workers,
            pad_floor=pad_floor if pad_floor is not None
            else bucketing.PAD_FLOOR)
        try:
            for spec in specs:
                for cap in sorted(set(int(c) for c in spec.capacities)):
                    before = set(_compiled.cache().keys())
                    futs = [_submit(ex, spec, T, A)
                            for (T, A) in _spec_requests(spec, cap)]
                    ex.flush()
                    outs = [f.result(timeout=600) for f in futs]
                    # the canonical cohort is deterministic (seeded
                    # from the spec), so this digest is the value ANY
                    # process serving the packed executable must
                    # reproduce, bit for bit
                    rdigest = result_digest(outs)
                    for k in _compiled.cache().keys():
                        if k not in before:
                            ent = _entry_from_key(k)
                            ent["spec"] = spec.to_dict()
                            ent["results_digest"] = rdigest
                            if not os.path.exists(_aot.artifact_path(
                                    ent["digest"], artifacts)):
                                ent["artifact_missing"] = True
                            entries.append(ent)
        finally:
            ex.shutdown()

    manifest = {
        "schema": PACK_SCHEMA,
        "created": time.time(),
        "compat": _aot.compat_stamp(),
        "pad_floor": int(pad_floor) if pad_floor is not None
        else bucketing.PAD_FLOOR,
        "max_batch": max_cap,
        "entries": entries,
    }
    path = os.path.join(pack_dir, MANIFEST)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as fh:
        json.dump(manifest, fh, indent=1, sort_keys=True)
        fh.write("\n")
    os.replace(tmp, path)
    return manifest


def read_manifest(pack_dir: str) -> dict:
    path = (pack_dir if pack_dir.endswith(".json")
            else os.path.join(pack_dir, MANIFEST))
    with open(path) as fh:
        return json.load(fh)


def _statics_and_kernel(key: tuple) -> tuple[tuple, Optional[str]]:
    extra = key[3]
    if len(extra) >= 2 and extra[-2] == "kernel":
        return extra[:-2], extra[-1]
    return extra, None


def load_pack(pack_dir: str, executors: Sequence = (), *,
              strict: bool = False) -> dict:
    """Load a pack's executables into the process executable cache and
    seed each executor's flush-kernel memo from the manifest's
    recorded decisions. Returns a report::

        {"entries": N, "loaded": n, "resident": n, "failed": n,
         "kernel_restored": n, "skipped": why-or-None}

    Skips (another schema, compat mismatch) are reported, not
    raised — boot falls back to the compile path — unless ``strict``.
    Loads count as engine ``aot_loads`` (``load_seconds`` split), never
    as misses or compiles: a packed bucket's first request is a HIT.
    An entry whose key is already in the process executable cache (a
    second thread replica booting from the same pack) is counted
    ``resident`` and not deserialized again — only its kernel
    decisions are (re)seeded into the given executors.
    """
    _compiled = _compiled_module()

    report = {"entries": 0, "loaded": 0, "resident": 0, "failed": 0,
              "kernel_restored": 0, "skipped": None}

    def _bail(why: str) -> dict:
        if strict:
            raise RuntimeError(f"warmup pack {pack_dir!r}: {why}")
        report["skipped"] = why
        return report

    try:
        manifest = read_manifest(pack_dir)
    except Exception as e:  # noqa: BLE001 — a missing pack degrades
        return _bail(f"unreadable manifest ({e!r})")
    if manifest.get("schema") != PACK_SCHEMA:
        return _bail(f"schema {manifest.get('schema')!r} != {PACK_SCHEMA}")
    report["entries"] = len(manifest.get("entries", ()))
    ok, why = _aot.compat_probe(manifest.get("compat"))
    if not ok:
        return _bail(f"compat: {why}")

    root = (os.path.dirname(pack_dir) if pack_dir.endswith(".json")
            else pack_dir)
    from libskylark_tpu.engine.cache import CacheEntry

    resident = {repr(k): k for k in _compiled.cache().keys()}
    for ent in manifest.get("entries", ()):
        path = _aot.artifact_path(ent["digest"],
                                  os.path.join(root, _ARTIFACTS))
        t0 = time.perf_counter()
        key = None
        if resident:
            # header-only probe: a key already in the process cache
            # (another thread replica loaded this pack) needs no
            # second deserialize, just its kernel seeding below. Only
            # worth the extra read when anything IS resident — the
            # common fresh-process boot goes straight to load_file
            try:
                key = resident.get(
                    _aot.read_header(path).get("key_repr"))
            except Exception:  # noqa: BLE001 — load_file reports it
                key = None
        if key is not None:
            report["resident"] += 1
        else:
            try:
                key, executable, header = _aot.load_file(path)
            except Exception as e:  # noqa: BLE001 — per-entry containment
                report["failed"] += 1
                _compiled.cache().note_aot_load_failure()
                if strict:
                    raise RuntimeError(
                        f"warmup pack entry {ent.get('digest')}: {e!r}"
                    ) from e
                continue
            dt = time.perf_counter() - t0
            _compiled.cache().insert(key, CacheEntry(
                executable=executable, name=header.get("name", "packed"),
                compile_seconds=0.0, loaded=True))
            _compiled.cache().note_aot_load(dt)
            report["loaded"] += 1
        token = ent.get("kernel")
        if token:
            statics, _tok = _statics_and_kernel(key)
            capacity = ent.get("capacity")
            for ex in executors:
                if capacity and ex.restore_kernel_choice(
                        statics, capacity, token):
                    report["kernel_restored"] += 1
    return report


def serve_probe(pack_dir: str, *, load: bool = True,
                strict: bool = False) -> dict:
    """Boot-and-serve probe: regenerate every manifest entry's
    canonical cohort from its recorded spec, serve it through a fresh
    executor — after loading the pack when ``load`` (the warm side of
    the boot A/B) or straight onto the compile path when not (the cold
    side) — and compare result digests against the builder's. The
    ``bench.py --boot`` children and the CI boot gate
    (``benchmarks/boot_smoke.py``) both run exactly this, so the
    "zero backend compiles + bit-equal" claim has one implementation.

    Returns ``{"entries", "served", "bit_equal", "mismatches",
    "warmup": load-report-or-None, "engine": stats dict,
    "t_first_result_s", "t_total_s"}``. Engine counters are read as a
    delta from function entry, so an in-process caller (tests) sees
    only the probe's own traffic."""
    import time as _time

    _compiled = _compiled_module()
    manifest = read_manifest(pack_dir)
    from libskylark_tpu.engine.serve import MicrobatchExecutor

    s0 = dataclasses.replace(_compiled.stats())
    t_start = _time.perf_counter()
    ex = MicrobatchExecutor(max_batch=int(manifest.get("max_batch", 8)),
                            linger_us=50_000, workers=1,
                            pad_floor=int(manifest.get("pad_floor", 8)))
    report: dict = {"entries": len(manifest.get("entries", ())),
                    "served": 0, "bit_equal": True, "mismatches": [],
                    "warmup": None}
    try:
        if load:
            report["warmup"] = load_pack(pack_dir, executors=(ex,),
                                         strict=strict)
        t_first = None
        for ent in manifest.get("entries", ()):
            spec = BucketSpec.from_dict(ent["spec"])
            cap = int(ent.get("capacity") or 1)
            futs = [_submit(ex, spec, T, A)
                    for (T, A) in _spec_requests(spec, cap)]
            ex.flush()
            outs = [f.result(timeout=600) for f in futs]
            if t_first is None:
                t_first = _time.perf_counter() - t_start
            report["served"] += cap
            got = result_digest(outs)
            want = ent.get("results_digest")
            if want is not None and got != want:
                report["bit_equal"] = False
                report["mismatches"].append(
                    {"digest": ent["digest"], "got": got, "want": want})
        report["t_first_result_s"] = round(t_first, 4) if t_first else None
        report["t_total_s"] = round(_time.perf_counter() - t_start, 4)
    finally:
        ex.shutdown()
    s1 = _compiled.stats()
    delta = {f.name: getattr(s1, f.name) - getattr(s0, f.name)
             for f in dataclasses.fields(s0)}
    delta["compile_seconds"] = round(delta["compile_seconds"], 4)
    delta["load_seconds"] = round(delta["load_seconds"], 4)
    delta["execute_seconds"] = round(delta["execute_seconds"], 4)
    report["engine"] = delta
    return report


def spawn_boot_probe(pack_dir: str, *, load: bool = True,
                     timeout: float = 600.0) -> dict:
    """Run :func:`serve_probe` in a FRESH python process (the
    ``skylark_warmup boot-probe`` CLI) and return its parsed record —
    the one implementation behind ``bench.py --boot``'s children and
    the CI boot gate (``benchmarks/boot_smoke.py``), so the two always
    measure the same thing. The child gets ``SKYLARK_BOOT_T0`` (the
    spawn instant) and reports honest wall-from-spawn
    time-to-first-result.

    The child environment is scrubbed hermetic: an ambient
    ``SKYLARK_AOT_DIR``/``SKYLARK_EXEC_CACHE_DIR`` would let the
    *cold* control load artifacts persisted by an earlier run (zero
    compiles, gate fails spuriously), and an ambient
    ``SKYLARK_SERVE_KERNEL`` pin would make the executor decline every
    packed kernel decision (``kernel_restored == 0``)."""
    import re
    import subprocess
    import sys

    env = dict(os.environ)
    for k in ("SKYLARK_AOT_DIR", "SKYLARK_EXEC_CACHE_DIR",
              "SKYLARK_SERVE_KERNEL"):
        env.pop(k, None)
    env["SKYLARK_BOOT_T0"] = repr(time.time())
    cmd = [sys.executable, "-m",
           "libskylark_tpu.cli.skylark_warmup", "boot-probe",
           "--pack", pack_dir]
    if not load:
        cmd.append("--no-load")
    repo_root = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    proc = subprocess.run(cmd, capture_output=True, text=True,
                          timeout=timeout, cwd=repo_root, env=env)
    m = re.search(r"BOOT_PROBE (\{.*\})", proc.stdout + proc.stderr)
    if not m:
        raise RuntimeError(
            f"boot probe (load={load}) produced no record "
            f"rc={proc.returncode}: "
            f"{(proc.stdout + proc.stderr)[-800:]}")
    return json.loads(m.group(1))


__all__ = [
    "BucketSpec", "MANIFEST", "PACK_SCHEMA", "build_pack", "load_pack",
    "read_manifest", "result_digest", "serve_probe", "spawn_boot_probe",
]
