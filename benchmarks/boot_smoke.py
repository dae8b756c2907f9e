"""CI boot gate: zero-recompile fleet boot from a warmup pack.

The r13 contract (docs/performance, "Persistent AOT artifacts & warmup
packs"), proven end to end:

1. Build a 2-bucket warmup pack in this process (a JLT rowwise bucket
   and a CWT columnwise bucket, two capacity classes each) — every
   packed (bucket, capacity) executable serialized, the manifest
   recording the kernel decision and the builder's result digests.
2. Boot a FRESH python process (``skylark_warmup boot-probe``) that
   loads the pack and serves every packed bucket's canonical cohort.
   Assert, from the child's own engine counters:
   - **zero backend compiles** (``compiles == 0``): every executable
     arrived as an AOT artifact load (``aot_loads == entries``), and
     every first request was a cache HIT (``misses == 0``);
   - **bit-equality**: the child's results hash to exactly the
     builder's in-process digests — the deserialized executable is
     the builder's program, bit for bit;
   - the pack loaded cleanly (nothing skipped, nothing failed, the
     kernel decisions restored from the manifest).
3. Boot a second fresh process WITHOUT the pack on the same cohorts
   and assert it did compile (> 0) — proving the zero above is the
   pack's doing, not an accident of the workload.

Prints one JSON record; exits nonzero on any violation (the CI boot
gate). Runs anywhere (JAX_PLATFORMS=cpu); ~4 bucket-capacity compiles
in the builder plus two child boots.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def _fail(msg: str) -> None:
    print(f"BOOT SMOKE FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def main() -> None:
    import shutil

    from libskylark_tpu.engine import warmup

    pack = tempfile.mkdtemp(prefix="skylark_boot_smoke_")
    # the pack (serialized executables included) is per-run scratch;
    # _fail exits via sys.exit, so atexit-style cleanup must not be
    # conditional on reaching the end of main
    import atexit

    atexit.register(shutil.rmtree, pack, ignore_errors=True)
    specs = [
        warmup.BucketSpec(endpoint="sketch_apply", family="JLT",
                          n=120, m=28, s_dim=32, rowwise=True,
                          capacities=(1, 2)),
        warmup.BucketSpec(endpoint="sketch_apply", family="CWT",
                          n=48, m=6, s_dim=16, rowwise=False,
                          capacities=(2,)),
    ]
    manifest = warmup.build_pack(pack, specs)
    n_entries = len(manifest["entries"])
    if n_entries < 3:
        _fail(f"builder packed {n_entries} entries, expected 3 "
              f"(2 JLT capacities + 1 CWT)")
    missing = [e["digest"] for e in manifest["entries"]
               if e.get("artifact_missing")]
    if missing:
        _fail(f"builder produced no artifact for {missing}")
    if any(not e.get("kernel") for e in manifest["entries"]):
        _fail("manifest entries missing the kernel decision token")

    # fresh children via the one shared launcher (hermetic env scrub
    # included — engine.warmup.spawn_boot_probe)
    try:
        warm = warmup.spawn_boot_probe(pack, load=True)
        cold = warmup.spawn_boot_probe(pack, load=False)
    except RuntimeError as e:
        _fail(str(e))

    eng = warm["engine"]
    wrep = warm.get("warmup") or {}
    if wrep.get("skipped") is not None:
        _fail(f"fresh process skipped the pack: {wrep['skipped']}")
    if wrep.get("failed"):
        _fail(f"{wrep['failed']} pack entries failed to load")
    if wrep.get("loaded") != n_entries:
        _fail(f"loaded {wrep.get('loaded')} of {n_entries} entries")
    if wrep.get("kernel_restored") != n_entries:
        _fail(f"kernel decisions restored for "
              f"{wrep.get('kernel_restored')} of {n_entries} entries "
              f"(manifest-restored selection broke)")
    if eng["compiles"] != 0:
        _fail(f"fresh process performed {eng['compiles']} backend "
              f"compile(s) despite the warmup pack")
    if eng["misses"] != 0:
        _fail(f"fresh process MISSED {eng['misses']} time(s) — packed "
              f"keys did not match the serve path's keys")
    if eng["aot_loads"] != n_entries:
        _fail(f"aot_loads {eng['aot_loads']} != entries {n_entries}")
    if not warm["bit_equal"]:
        _fail(f"pack-booted results diverged from the in-process "
              f"builder's: {warm['mismatches']}")
    if not cold["bit_equal"]:
        _fail("cold-booted results diverged from the in-process "
              "builder's (determinism of the serve path itself broke)")
    if cold["engine"]["compiles"] == 0:
        _fail("cold probe compiled nothing — the zero-compile claim "
              "above proved nothing")

    print(json.dumps({
        "entries": n_entries,
        "warm": {"compiles": eng["compiles"], "misses": eng["misses"],
                 "aot_loads": eng["aot_loads"],
                 "load_seconds": eng["load_seconds"],
                 "bit_equal": warm["bit_equal"],
                 "wall_since_spawn_s": warm.get("wall_since_spawn_s")},
        "cold": {"compiles": cold["engine"]["compiles"],
                 "compile_seconds": cold["engine"]["compile_seconds"],
                 "bit_equal": cold["bit_equal"],
                 "wall_since_spawn_s": cold.get("wall_since_spawn_s")},
        "kernel_restored": wrep.get("kernel_restored"),
        "ok": True,
    }))


if __name__ == "__main__":
    main()
