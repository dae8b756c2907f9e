"""Splits a cell's period — one apply's start to the next one's — at the
program's handover to the runtime, untraced: the window runs as in the
benchmark but with the program's telemetry switched on in place of the
profiler, so the host's side carries no Python-tracer inflation. A builder's
aid: the benchmark's command never reads it.

    python3 -m cellbench.tools.idle_split --workload rft_features_apply \
        --seed 11 --seconds 10 --device-ms 9.19

Every line it prints starts with ``[idle_split]``: the samples' median, then
the medians over the window's periods of ``telemetry.apply_periods`` — the
period, what lies before the handover (by span name, and the root's own
part), the handover's call — and, given the device's time an apply from a
traced run (``--device-ms``: ``sketch_device_ms.apply``), what is left after
the handover. ``--telemetry 0`` runs the same window with the gate shut and
prints the samples' median alone: the two medians side by side are what the
spans cost.
"""

from __future__ import annotations

import argparse
import importlib
import os
import statistics
import sys

def say(kind: str, **fields) -> None:
    body = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"[idle_split] {kind} {body}", flush=True)


def main(argv: list) -> int:
    parser = argparse.ArgumentParser(prog="python3 -m cellbench.tools.idle_split")
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, default=10.0)
    parser.add_argument("--telemetry", type=int, choices=(0, 1), default=1)
    parser.add_argument("--device-ms", type=float, default=None,
                        help="sketch_device_ms.apply of a traced run of the cell")
    parser.add_argument("--allow-cpu", action="store_true",
                        help="rehearsal only: the readings are not the chip's")
    args = parser.parse_args(argv)

    from cellbench import harness, periods

    # the benchmark's compile cache, so that a run of the cell before this
    # one has compiled what the window needs
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(harness.CACHE_DIR))
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    from libskylark_tpu import telemetry

    cell = harness.load_cell(args.workload)
    device = jax.devices()[0]
    if device.platform != "tpu" and not args.allow_cpu:
        print("idle_split: needs a TPU", file=sys.stderr)
        return harness.EXIT_NO_CHIP
    driver = importlib.import_module(f"cellbench.drivers.{cell.traffic['driver']}")
    loop = importlib.import_module(f"cellbench.loops.{cell.traffic['loop']}")
    state = driver.setup(cell.config, cell.traffic, args.seed)
    for i in range(cell.traffic["warm_steps"]):
        driver.step(state, i)

    telemetry.set_enabled(bool(args.telemetry))
    telemetry.clear_finished()
    window = loop.run(lambda i: driver.step(state, i), args.seconds, driver.keep(state))
    telemetry.set_enabled(False)
    sample_ms = 1e3 * statistics.median(window["samples"])
    say("samples", workload=cell.name, platform=device.platform, seed=args.seed,
        telemetry=args.telemetry, n=len(window["samples"]),
        median_ms=f"{sample_ms:.4f}", spans=len(telemetry.finished_spans()))
    if not args.telemetry:
        return 0

    found = telemetry.apply_periods(periods.ROOT_SPAN, last=window["attempted"])
    if not found:
        say("periods", whole=False)
        return 0

    def med(pick, of=found):
        return 1e3 * statistics.median(map(pick, of))

    handed = [p for p in found if p["handovers"]]
    say("periods", n=len(found), period_ms=f"{med(lambda p: p['period_s']):.4f}",
        **{f"handovers_{n}": sum(p["handovers"] == n for p in found)
           for n in sorted({p["handovers"] for p in found})})
    if not handed:
        return 0
    before = med(lambda p: p["before_s"], handed)
    names = sorted({n for p in handed for n in p["before_by_name"]})
    say("before", ms=f"{before:.4f}",
        self_ms=f"{med(lambda p: p['before_self_s'], handed):.4f}",
        **{n: f"{med(lambda p, n=n: p['before_by_name'].get(n, 0.0), handed):.4f}"
           for n in names})
    say("call", ms=f"{med(lambda p: p['call_s'], handed):.4f}")
    if args.device_ms is not None:
        say("after", device_ms=f"{args.device_ms:.4f}",
            ms=f"{med(lambda p: p['period_s']) - before - args.device_ms:.4f}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
