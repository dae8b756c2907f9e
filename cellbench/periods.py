"""What the two per-layer metrics that split the device's idle time share.

A closed blocking loop is its own clock: nothing is in flight when an apply
begins, so the device is idle from the apply's first line until the program
hands its executable to the runtime, and every other idle nanosecond of the
period (this apply's start to the next one's) comes after that instant. The
program marks the handover (``libskylark_tpu/telemetry/names.py``
``HANDOVER``) and ``telemetry.trace.apply_periods`` reads, for each
``sketch.apply`` of the traced window but the last, the period and what lies
before the handover, all on the host's ``perf_counter_ns`` — no number here is
a difference across the profile's planes, whose clocks differ by ≈ 1.9 ms.
The device's share of a period is the trace's busy time ÷ operations, a
duration on the device's own clock.

``split`` gives ``None`` — both metrics left out — with no device plane
("idle" has no meaning off the chip), on a program without ``apply_periods``,
on a wrapped ring, under ``MIN_PERIODS`` periods, or where a period of the
window holds another number of handovers than one (an apply that hands over
twice has no single "before"); the log line then says which.
"""

from __future__ import annotations

import statistics
import time

ROOT_SPAN = "sketch.apply"      # one an operation, in every admitted cell
MIN_PERIODS = 10                # fewer give no median worth the name


def ms(seconds: float) -> str:
    return f"{1e3 * seconds:.4f}"


def split(run, say_as: str):
    """The traced window's split, or ``None`` (a ``[cellbench] <say_as>
    left_out=…`` line then says why, where the program could have been
    read): ``periods``, their count; the medians, each taken apart, of
    ``period_s``, ``before_s``, ``before_self_s``, ``call_s`` and of each
    name of ``before_by_name`` (``by_name``); ``device_s``, the device's
    busy seconds an operation; ``reader_s``, what reading the ring took."""
    from cellbench import harness
    from libskylark_tpu.telemetry import trace

    apply_periods = getattr(trace, "apply_periods", None)
    device_s = run.device_seconds_per_operation()
    if apply_periods is None or run.trace is None or device_s is None:
        return None
    t0 = time.perf_counter()
    periods = apply_periods(ROOT_SPAN, last=run.operations)
    if periods is None or len(periods) < MIN_PERIODS:
        harness.say(say_as, left_out="wrapped_ring" if periods is None
                    else f"periods={len(periods)}")
        return None
    handovers = sorted({p["handovers"] for p in periods})
    if handovers != [1]:
        harness.say(say_as, left_out="handovers", periods=len(periods),
                    **{f"with_{n}": sum(p["handovers"] == n for p in periods)
                       for n in handovers})
        return None

    def med(pick):
        return statistics.median(pick(p) for p in periods)

    names = sorted({n for p in periods for n in p["before_by_name"]})
    out = {key: med(lambda p, key=key: p[key])
           for key in ("period_s", "before_s", "before_self_s", "call_s")}
    out["by_name"] = {n: med(lambda p, n=n: p["before_by_name"].get(n, 0.0))
                      for n in names}
    return dict(out, periods=len(periods), device_s=device_s,
                reader_s=time.perf_counter() - t0)
