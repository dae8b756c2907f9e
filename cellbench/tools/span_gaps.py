"""Puts the device's idle time down to the program's own spans. A builder's
aid: the benchmark's command never reads it.

    CELLBENCH_KEEP_TRACE=<dir> python3 -m cellbench --workload <w> ... --trace 1
    python3 -m cellbench.tools.span_gaps <dir>/<w>.events.json

(or an excerpt written by ``tools/excerpt.py``). Every interval of the traced
window in which no operation ran on the device goes, by overlap and not by
its midpoint, to the innermost *program span* open during it — a name
declared in ``libskylark_tpu/telemetry/names.py`` ``SPANS``, which the program
enters as a ``TraceAnnotation`` on the profile's host line — and what no
program span covers goes to ``caller``: the code around the program inside
``cellbench.window`` (the loop, the driver's ``step``, ``block_until_ready``).
Prints seconds and share per name, most first.

The device plane's clock is not the host plane's: on the v5e of PR 26 the
device's events read about 2 ms *earlier* than the host's (the kernel "starts"
before the host enqueues it). ``device_clock_lead`` brackets that lead from
two facts of a closed blocking loop — the main device work of an operation
cannot start before the program opened the span that enqueues it, nor end
after the next operation began — and the device's events are moved by the
middle of the bracket before anything is overlapped.
"""

from __future__ import annotations

import bisect
import json
import sys

from cellbench import trace

CALLER = "caller"
ENQUEUES = ("sketch.dispatch", "engine.execute")    # return when the work is enqueued
BLOCK_JOIN_NS = 50e3    # device ops closer than this are one block of work


def load(path: str) -> dict:
    """The events of a kept trace (``.json``) or of an excerpt (text proto)."""
    if path.endswith(".json"):
        with open(path) as fh:
            raw = json.load(fh)
        return {tuple(k.split("|")): [tuple(e) for e in v] for k, v in raw.items()}
    from jax.profiler import ProfileData

    with open(path) as fh:
        return trace.read_events(ProfileData.from_text_proto(fh.read()))


def device_clock_lead(events: dict, names) -> tuple:
    """(low_ns, high_ns): by how much the device plane's clock reads ahead
    of (earlier than) the host plane's, or ``None`` where the trace does not
    say. One operation = one outermost program span; its main device work =
    one of the longest blocks of back-to-back device ops, in order."""
    spans = sorted((s, s + d, n) for n, s, d in
                   events.get((trace.HOST_PLANE, trace.HOST_LINE), []) if n in names)
    roots, open_until = [], -1.0
    for start, end, _ in spans:
        if start >= open_until:
            roots.append((start, end))
            open_until = end
    device = sorted(k for k in events if k[0].startswith(trace.DEVICE_PLANE))
    if len(device) != 1 or len(roots) < 2:
        return None
    blocks = [(a, b - BLOCK_JOIN_NS) for a, b in trace.union(
        [(s, s + d + BLOCK_JOIN_NS) for _, s, d in events[device[0]]])]
    blocks = sorted(sorted(blocks, key=lambda ab: ab[0] - ab[1])[:len(roots)])
    if len(blocks) != len(roots):
        return None
    low, high = [], []
    for k, ((r0, r1), (b0, b1)) in enumerate(zip(roots, blocks)):
        enqueue = [s for s, e, n in spans if n in ENQUEUES and r0 <= s and e <= r1]
        if enqueue:
            low.append(enqueue[-1] - b0)
        if k + 1 < len(roots):
            high.append(roots[k + 1][0] - b1)
    if not low or not high or max(low) > min(high):
        return None
    return max(low), min(high)


def idle_intervals(events: dict, lead_ns: float = 0.0) -> dict:
    """{device plane: [(start_ns, end_ns), ...]}: the gaps between the
    device's operations inside the traced window, the device's events read
    ``lead_ns`` later."""
    w0, w1 = trace.window_of(events)
    out = {}
    for key in sorted(k for k in events if k[0].startswith(trace.DEVICE_PLANE)):
        moved = [(s + lead_ns, s + lead_ns + d) for _, s, d in events[key]]
        busy = trace.union([(max(a, w0), min(b, w1))
                            for a, b in moved if a < w1 and b > w0])
        edges = [w0] + [t for pair in busy for t in pair] + [w1]
        out[key[0]] = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                       if edges[i + 1] > edges[i]]
    if not out:
        raise ValueError("the trace holds no device plane")
    return out


def span_segments(frames: list, names) -> list:
    """Disjoint, sorted (start, end, name): each stretch of the host line
    under the innermost open span whose name is in ``names``. The spans of
    one thread nest."""
    spans = sorted((s, -(s + d), n) for n, s, d in frames if n in names)
    segments, stack, at = [], [], 0.0   # stack of (end, name); at = last edge

    def close_until(t):
        nonlocal at
        while stack and stack[-1][0] <= t:
            end, name = stack.pop()
            if end > at:
                segments.append((at, end, name))
                at = end

    for start, neg_end, name in spans:
        close_until(start)
        if stack and start > at:
            segments.append((at, start, stack[-1][1]))
        at = start
        stack.append((-neg_end, name))
    close_until(float("inf"))
    return segments


def attribute(events: dict, names, lead_ns: float = 0.0) -> dict:
    """{span name or ``caller``: idle seconds}, the mean over the devices."""
    segments = span_segments(events.get((trace.HOST_PLANE, trace.HOST_LINE), []), names)
    starts = [a for a, _, _ in segments]
    gaps_by_device = idle_intervals(events, lead_ns)
    sums: dict = {}
    for gaps in gaps_by_device.values():
        for a, b in gaps:
            covered = 0.0
            i = max(bisect.bisect_right(starts, a) - 1, 0)
            while i < len(segments) and segments[i][0] < b:
                lo, hi, name = segments[i]
                over = min(hi, b) - max(lo, a)
                if over > 0:
                    sums[name] = sums.get(name, 0.0) + over * 1e-9
                    covered += over
                i += 1
            sums[CALLER] = sums.get(CALLER, 0.0) + (b - a - covered) * 1e-9
    return {k: v / len(gaps_by_device) for k, v in sums.items()}


def main(argv: list) -> int:
    if len(argv) != 1:
        print(__doc__, file=sys.stderr)
        return 2
    from libskylark_tpu.telemetry.names import SPANS

    events = load(argv[0])
    w0, w1 = trace.window_of(events)
    bracket = device_clock_lead(events, SPANS)
    lead_ns = sum(bracket) / 2 if bracket else 0.0
    print("[span_gaps] device_clock_lead " + (
        f"low_us={bracket[0] * 1e-3:.1f} high_us={bracket[1] * 1e-3:.1f} "
        f"applied_us={lead_ns * 1e-3:.1f}" if bracket else "unknown applied_us=0"))
    sums = attribute(events, SPANS, lead_ns)
    idle = sum(sums.values())
    frames = events.get((trace.HOST_PLANE, trace.HOST_LINE), [])
    print(f"[span_gaps] window_s={(w1 - w0) * 1e-9:.6f} idle_s={idle:.6f} "
          f"idle_share={idle / ((w1 - w0) * 1e-9):.4f}")
    for name, seconds in sorted(sums.items(), key=lambda kv: -kv[1]):
        count = sum(1 for n, s, d in frames if n == name and w0 <= s < w1)
        print(f"[span_gaps] {name:<24} idle_s={seconds:.6f} "
              f"share={seconds / idle:.4f} spans={count}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
