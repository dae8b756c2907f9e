"""skylark_warmup: build / inspect / verify warmup packs.

The deployment half of the zero-recompile fleet boot
(docs/performance, "Persistent AOT artifacts & warmup packs"):

``build``
    Take ``--spec`` JSON bucket specs, precompile every (bucket,
    capacity) executable, and serialize the pack (artifacts +
    ``pack.json`` manifest) into ``--pack``.
``inspect``
    Print the manifest summary and whether THIS host/runtime would
    accept the pack (schema + compat probe).
``verify``
    Actually load the pack into this process and report the loader's
    counts — a booted replica should see ``loaded == entries`` and
    zero backend compiles.

Examples::

    skylark_warmup build --pack pack --spec '{"endpoint": \\
        "sketch_apply", "family": "JLT", "n": 128, "m": 64, \\
        "s_dim": 32, "rowwise": true, "capacities": [1, 8, 16]}'
    skylark_warmup inspect --pack /var/skylark/pack
    skylark_warmup verify --pack /var/skylark/pack
"""

from __future__ import annotations

import argparse
import json
import sys


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="skylark_warmup",
        description="Warmup packs: precompiled serve-bucket bundles "
                    "for zero-recompile fleet boot (docs/performance)")
    sub = p.add_subparsers(dest="cmd", required=True)

    b = sub.add_parser("build", help="precompile + serialize a pack")
    b.add_argument("--pack", required=True,
                   help="pack directory (created if missing)")
    b.add_argument("--spec", action="append", required=True,
                   help="bucket spec as JSON (repeatable); see "
                        "engine.warmup.BucketSpec")
    b.add_argument("--pad-floor", type=int, default=None)

    for name, hlp in (("inspect", "manifest summary + compat probe"),
                      ("verify", "load the pack into this process")):
        s = sub.add_parser(name, help=hlp)
        s.add_argument("--pack", required=True)

    bp = sub.add_parser(
        "boot-probe",
        help="boot a fresh serving process from the pack (or cold with "
             "--no-load), serve every packed bucket's canonical cohort, "
             "and report compiles/loads/bit-equality/time-to-first-"
             "result — the bench --boot child and the CI boot gate")
    bp.add_argument("--pack", required=True)
    bp.add_argument("--no-load", action="store_true",
                    help="cold side of the A/B: serve the same cohorts "
                         "without loading the pack")
    return p


def _cmd_build(args) -> int:
    from libskylark_tpu.engine import warmup

    specs = [warmup.BucketSpec.from_dict(json.loads(s))
             for s in args.spec]
    manifest = warmup.build_pack(args.pack, specs,
                                 pad_floor=args.pad_floor)
    missing = [e["digest"] for e in manifest["entries"]
               if e.get("artifact_missing")]
    print(json.dumps({
        "pack": args.pack,
        "entries": len(manifest["entries"]),
        "compat": manifest["compat"],
        "artifact_missing": missing,
    }, indent=1))
    return 1 if missing else 0


def _cmd_inspect(args) -> int:
    from libskylark_tpu.engine import aot, warmup

    try:
        manifest = warmup.read_manifest(args.pack)
    except Exception as e:  # noqa: BLE001 — CLI reports, not raises
        print(f"error: unreadable manifest: {e!r}", file=sys.stderr)
        return 2
    ok, why = aot.compat_probe(manifest.get("compat"))
    if manifest.get("schema") != warmup.PACK_SCHEMA:
        ok, why = False, (f"schema {manifest.get('schema')!r} != "
                          f"{warmup.PACK_SCHEMA}")
    print(json.dumps({
        "schema": manifest.get("schema"),
        "entries": [
            {k: e.get(k) for k in ("name", "endpoint", "capacity",
                                   "kernel", "digest")}
            for e in manifest.get("entries", ())
        ],
        "compat_ok_here": ok,
        "compat_reason": why,
    }, indent=1))
    return 0 if ok else 1


def _cmd_verify(args) -> int:
    from libskylark_tpu import engine
    from libskylark_tpu.engine import warmup

    report = warmup.load_pack(args.pack)
    s = engine.stats()
    report["aot_loads"] = s.aot_loads
    report["load_seconds"] = round(s.load_seconds, 4)
    report["backend_compiles"] = s.compiles
    print(json.dumps(report, indent=1))
    ok = (report["skipped"] is None and report["failed"] == 0
          and report["loaded"] == report["entries"])
    return 0 if ok else 1


def _cmd_boot_probe(args) -> int:
    import time

    from libskylark_tpu.base import env as _env
    from libskylark_tpu.engine import warmup

    report = warmup.serve_probe(args.pack, load=not args.no_load)
    # wall time since the parent spawned us (SKYLARK_BOOT_T0 = parent's
    # time.time() at spawn): the honest time-to-first-result including
    # interpreter + jax import — what a cold autoscaled replica pays
    t0 = _env.BOOT_T0.get()
    if t0 is not None:
        report["wall_since_spawn_s"] = round(time.time() - t0, 4)
    print("BOOT_PROBE " + json.dumps(report))
    ok = report["bit_equal"]
    if not args.no_load:
        # a pack that loaded partially (or not at all) still serves —
        # via the compile path — but the probe must not certify it:
        # `boot-probe && deploy` would ship a pack that recompiles on
        # every replica
        w = report["warmup"] or {}
        ok = (ok and w.get("skipped") is None and not w.get("failed")
              and (w.get("loaded", 0) + w.get("resident", 0)
                   == w.get("entries", -1)))
    return 0 if ok else 1


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    if args.cmd == "build":
        return _cmd_build(args)
    if args.cmd == "inspect":
        return _cmd_inspect(args)
    if args.cmd == "boot-probe":
        return _cmd_boot_probe(args)
    return _cmd_verify(args)


if __name__ == "__main__":
    sys.exit(main())
