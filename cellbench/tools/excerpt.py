"""Cuts a recorded trace down to a small excerpt that the tests can hold.

    CELLBENCH_KEEP_TRACE=<dir> python3 -m cellbench --workload <w> ... --trace 1
    python3 -m cellbench.tools.excerpt <dir>/<w>.events.json <out>.pbtxt --operations 3

keeps the device ops and the host frames of the first ``--operations``
operations of the traced window (an operation starts with each call of the
loop's ``step``), shifts the clock to start at 0, shrinks the window span to
what is kept, and writes an XSpace text proto that
``jax.profiler.ProfileData.from_text_proto`` reads back.
"""

from __future__ import annotations

import argparse
import json
import sys

from cellbench import trace


def main(argv: list) -> int:
    parser = argparse.ArgumentParser(prog="python3 -m cellbench.tools.excerpt")
    parser.add_argument("events_json")
    parser.add_argument("out")
    parser.add_argument("--operations", type=int, default=3)
    parser.add_argument("--step-frame", default=" step",
                        help="ending of the host frame that issues one operation")
    parser.add_argument("--min-frame-us", type=float, default=20.0,
                        help="host frames shorter than this are dropped")
    args = parser.parse_args(argv)

    raw = json.load(open(args.events_json))
    events = {tuple(k.split("|")): [tuple(e) for e in v] for k, v in raw.items()}
    w0, w1 = trace.window_of(events)
    frames = events.get((trace.HOST_PLANE, trace.HOST_LINE), [])
    steps = sorted(s for n, s, d in frames
                   if n.endswith(args.step_frame) and w0 <= s < w1)
    if len(steps) <= args.operations:
        raise SystemExit(f"only {len(steps)} operations found in the window")
    cut = steps[args.operations]
    out = {}
    for key, evs in events.items():
        device = key[0].startswith(trace.DEVICE_PLANE)
        if not device and key != (trace.HOST_PLANE, trace.HOST_LINE):
            continue
        kept = []
        for name, start, dur in evs:
            if name == trace.WINDOW_SPAN:
                kept.append((name, 0.0, cut - w0))
            elif start >= w0 and start + dur <= cut and (
                    device or dur >= args.min_frame_us * 1e3):
                kept.append((name, start - w0, dur))
        out[key] = kept
    with open(args.out, "w") as fh:
        fh.write(trace.to_text_proto(out))
    print({"|".join(k): len(v) for k, v in out.items()}, "window_ns", cut - w0)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
