"""Splits a cell's operation into the program's own spans, untraced: the
window runs as in the benchmark but with the program's telemetry switched on
in place of the profiler, so the split carries no Python-tracer inflation.
A builder's aid: the benchmark's command never reads it.

    python3 -m cellbench.tools.span_split --workload jlt_apply --seed 11 --seconds 10

Every line it prints starts with ``[span_split]``: the samples' median, then
for every span name that has children its median total, self time and
children by name (ms, medians over the window's spans taken apart), and
``outside``: the sample's median minus the outermost span's — the caller
around the program, mostly the wait in ``block_until_ready``.
"""

from __future__ import annotations

import argparse
import importlib
import statistics
import sys


def say(kind: str, **fields) -> None:
    body = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"[span_split] {kind} {body}", flush=True)


def main(argv: list) -> int:
    parser = argparse.ArgumentParser(prog="python3 -m cellbench.tools.span_split")
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, default=10.0)
    parser.add_argument("--manifest", default=None,
                        help="a manifest other than /BENCHMARK.json (a staged cell's)")
    parser.add_argument("--allow-cpu", action="store_true",
                        help="rehearsal only: the readings are not the chip's")
    args = parser.parse_args(argv)

    import jax

    from cellbench import harness
    from libskylark_tpu import telemetry

    cell = (harness.load_cell(args.workload, args.manifest) if args.manifest
            else harness.load_cell(args.workload))
    device = jax.devices()[0]
    if device.platform != "tpu" and not args.allow_cpu:
        print("span_split: needs a TPU", file=sys.stderr)
        return harness.EXIT_NO_CHIP
    driver = importlib.import_module(f"cellbench.drivers.{cell.traffic['driver']}")
    loop = importlib.import_module(f"cellbench.loops.{cell.traffic['loop']}")
    state = driver.setup(cell.config, cell.traffic, args.seed)
    for i in range(cell.traffic["warm_steps"]):
        driver.step(state, i)

    telemetry.set_enabled(True)
    telemetry.clear_finished()
    window = loop.run(lambda i: driver.step(state, i), args.seconds, driver.keep(state))
    telemetry.set_enabled(False)
    spans = telemetry.finished_spans()
    sample_ms = 1e3 * statistics.median(window["samples"])
    say("samples", workload=cell.name, platform=device.platform, seed=args.seed,
        n=len(window["samples"]), median_ms=f"{sample_ms:.4f}", spans=len(spans))

    parents = {s.parent_id for s in spans}
    outermost = None
    for name in dict.fromkeys(s.name for s in spans if s.span_id in parents):
        stages = telemetry.stage_seconds(name)
        if not stages:
            say("stage", name=name, whole=False)
            continue

        def med(pick):
            return f"{1e3 * statistics.median(map(pick, stages)):.4f}"

        children = sorted({c for s in stages for c in s["children"]})
        say("stage", name=name, n=len(stages), total_ms=med(lambda s: s["total_s"]),
            self_ms=med(lambda s: s["self_s"]),
            **{c: med(lambda s, c=c: s["children"].get(c, 0.0)) for c in children})
        if any(s.name == name and s.parent_id is None for s in spans):
            outermost = 1e3 * statistics.median(s["total_s"] for s in stages)
    if outermost is not None:
        say("outside", ms=f"{sample_ms - outermost:.4f}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
