"""Reads, in one process, what the limits of a cell's check are set from:
for each seed the numbers a sound run of the program gives at the cell's own
size and load, and the numbers each lower-precision control gives in its
place. The benchmark's own runs do not run this.

    python3 -m cellbench.tools.calibrate --workload jlt_apply \
        --seeds 11,12,13 --control-seeds 3 --seconds 2 --controls program_bf16

Every line it prints starts with ``[calibrate]``; the last lines give, per
number, the largest sound reading, the smallest reading of each control, and
whether the configuration's limits pass every sound seed and fail every
control.
"""

from __future__ import annotations

import argparse
import gc
import importlib
import sys


def say(kind: str, **fields) -> None:
    body = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"[calibrate] {kind} {body}", flush=True)


def main(argv: list) -> int:
    parser = argparse.ArgumentParser(prog="python3 -m cellbench.tools.calibrate")
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True, help="comma-separated")
    parser.add_argument("--control-seeds", type=int, default=3,
                        help="how many of the seeds also run the controls")
    parser.add_argument("--seconds", type=float, default=2.0)
    parser.add_argument("--controls", default="", help="comma-separated; default all")
    parser.add_argument("--manifest", default=None,
                        help="a manifest other than /BENCHMARK.json (a staged cell's)")
    parser.add_argument("--allow-cpu", action="store_true",
                        help="rehearsal only: the readings are not the chip's")
    args = parser.parse_args(argv)

    import jax

    from cellbench import harness

    cell = (harness.load_cell(args.workload, args.manifest) if args.manifest
            else harness.load_cell(args.workload))
    device = jax.devices()[0]
    if device.platform != "tpu" and not args.allow_cpu:
        print("calibrate: needs a TPU", file=sys.stderr)
        return harness.EXIT_NO_CHIP
    driver = importlib.import_module(f"cellbench.drivers.{cell.traffic['driver']}")
    loop = importlib.import_module(f"cellbench.loops.{cell.traffic['loop']}")
    limits = cell.config["limits"]
    say("start", workload=cell.name, platform=device.platform,
        device_kind=repr(device.device_kind), limits=limits)

    sound: dict = {}      # number -> readings
    control: dict = {}    # control -> number -> readings
    verdicts = []
    seeds = [int(s) for s in args.seeds.split(",")]
    for n_seed, seed in enumerate(seeds):
        state = driver.setup(cell.config, cell.traffic, seed)
        keep = driver.keep(state)

        def read(step, label):
            for i in range(cell.traffic["warm_steps"]):
                step(i)
            window = loop.run(step, args.seconds, keep)
            got = driver.check(state, window["kept"])
            ok = all(got[name] <= limits[name] for name in got)
            say(label, seed=seed, operations=window["attempted"], correct=ok,
                **{k: f"{v:.4e}" for k, v in got.items()})
            return got, ok

        got, ok = read(lambda i: driver.step(state, i), "sound")
        verdicts.append(("sound", seed, ok))
        for name, value in got.items():
            sound.setdefault(name, []).append(value)
        if n_seed < args.control_seeds:
            available = driver.controls(state)
            wanted = [c for c in args.controls.split(",") if c] or list(available)
            for cname in wanted:
                got, ok = read(available[cname], f"control.{cname}")
                verdicts.append((cname, seed, ok))
                for name, value in got.items():
                    control.setdefault(cname, {}).setdefault(name, []).append(value)
            del available
        del state, read
        gc.collect()

    for name, values in sound.items():
        row = {"sound_min": f"{min(values):.4e}", "sound_max": f"{max(values):.4e}",
               "limit": f"{limits[name]:.1e}"}
        for cname, numbers in control.items():
            row[f"{cname}_min"] = f"{min(numbers[name]):.4e}"
            row[f"{cname}_over_sound"] = f"{min(numbers[name]) / max(values):.1f}x"
        say("number", name=name, **row)
    sound_pass = all(ok for kind, _, ok in verdicts if kind == "sound")
    controls_fail = all(not ok for kind, _, ok in verdicts if kind != "sound")
    say("verdict", seeds=len(seeds), every_sound_seed_correct=sound_pass,
        every_control_not_correct=controls_fail)
    return 0 if sound_pass and controls_fail else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
