"""Idle time by program span: on a hand-made trace whose answers are worked
out in the comments, and on an excerpt of a real one (the first three applies
of ``jlt_apply``'s traced window on a TPU v5e, recorded in PR 26 with
``tools/excerpt.py`` from a run of the program with its spans)."""

import pathlib

import pytest

from cellbench import trace
from cellbench.tools import span_gaps

NAMES = {"sketch.apply", "stream.key", "sketch.plan", "sketch.dispatch"}

HAND_MADE = {
    ("/device:TPU:0", "XLA Ops"): [
        ("%fusion.1 = f32[8]{0} fusion(f32[8] %p), kind=kLoop", 2000.0, 1000.0),
        ("%cc.2 = f32[8] custom-call(f32[8] %x)", 6000.0, 3000.0),
    ],
    ("/host:CPU", "python3"): [
        (trace.WINDOW_SPAN, 1000.0, 9000.0),            # window 1000..10000
        ("$closed.py:20 run", 900.0, 9500.0),           # a frame, not a span
        ("sketch.apply", 1500.0, 4000.0),               # 1500..5500
        ("stream.key", 1600.0, 600.0),                  # 1600..2200
        ("sketch.plan", 3200.0, 300.0),                 # 3200..3500
        ("sketch.dispatch", 4000.0, 1400.0),            # 4000..5400
        ("stream.key", 9200.0, 300.0),                  # outside any apply
    ],
}


def events_of(text):
    from jax.profiler import ProfileData

    return trace.read_events(ProfileData.from_text_proto(text))


def test_segments_are_the_innermost_span():
    frames = HAND_MADE[("/host:CPU", "python3")]
    assert span_gaps.span_segments(frames, NAMES) == [
        (1500.0, 1600.0, "sketch.apply"), (1600.0, 2200.0, "stream.key"),
        (2200.0, 3200.0, "sketch.apply"), (3200.0, 3500.0, "sketch.plan"),
        (3500.0, 4000.0, "sketch.apply"), (4000.0, 5400.0, "sketch.dispatch"),
        (5400.0, 5500.0, "sketch.apply"), (9200.0, 9500.0, "stream.key")]


def test_hand_made_gaps_by_overlap():
    # idle: 1000..2000, 3000..6000, 9000..10000 (5000 ns of a 9000 ns window)
    got = span_gaps.attribute(events_of(trace.to_text_proto(HAND_MADE)), NAMES)
    assert got == pytest.approx({
        # 1000..1500 and 5500..6000 and 9000..9200 and 9500..10000
        "caller": 1.7e-6,
        # 1500..1600, then 3000..3200, 3500..4000, 5400..5500 of the second gap
        "sketch.apply": 0.9e-6,
        # 1600..2000 of the first gap (a midpoint rule would give it none),
        # and the stray one, 9200..9500
        "stream.key": 0.7e-6,
        "sketch.plan": 0.3e-6,
        "sketch.dispatch": 1.4e-6})
    assert sum(got.values()) == pytest.approx(5e-6)


EXCERPT = pathlib.Path(__file__).with_name("data") / "jlt_apply_3ops_spans.xspace.pbtxt"


def test_real_excerpt_holds_one_set_of_spans_an_apply():
    events = events_of(EXCERPT.read_text())
    w0, w1 = trace.window_of(events)
    frames = events[(trace.HOST_PLANE, trace.HOST_LINE)]
    inside = lambda name: [(s, s + d) for n, s, d in frames  # noqa: E731
                           if n == name and w0 <= s and s + d <= w1]
    applies = inside("sketch.apply")
    assert len(applies) == 3                        # the three operations kept
    assert len(inside("stream.key")) == 6           # Allocation.key + the block table
    assert len(inside("sketch.plan")) == len(inside("sketch.dispatch")) == 3
    for name in ("stream.key", "sketch.plan", "sketch.dispatch"):
        assert all(any(a <= s and e <= b for a, b in applies) for s, e in inside(name))


def test_real_excerpt_clock_lead_and_gaps():
    events = events_of(EXCERPT.read_text())
    # read at face value, the kernel starts 1.7 ms before the host opens the
    # span that enqueues it: the device plane's clock runs ahead
    kernel = min(s for n, s, d in events[("/device:TPU:0", "XLA Ops")]
                 if "tpu_custom_call" in n)
    dispatch = min(s for n, s, d in events[(trace.HOST_PLANE, trace.HOST_LINE)]
                   if n == "sketch.dispatch")
    assert dispatch - kernel > 1.5e6
    low, high = span_gaps.device_clock_lead(events, NAMES)
    assert (low, high) == pytest.approx((1707124.0, 2428149.0))
    assert low >= dispatch - kernel

    red = trace.reduce(events)
    idle = red.window_s - red.busy_s
    plain = span_gaps.attribute(events, NAMES)
    assert sum(plain.values()) == pytest.approx(idle, rel=1e-9)
    assert max(plain, key=plain.get) == "caller"    # what the lead makes of it
    moved = span_gaps.attribute(events, NAMES, (low + high) / 2)
    assert sum(moved.values()) == pytest.approx(idle, rel=2e-2)   # the window's ends move
    assert moved == pytest.approx({
        "stream.key": 0.006502357, "sketch.apply": 0.001776778,
        "caller": 0.0012385945, "sketch.dispatch": 0.0011644805,
        "sketch.plan": 0.000530274}, rel=1e-6)
    # the keys are derived with the device idle from first to last: all of
    # their 6.5 ms (six spans) is idle time
    keys = sum(d for n, s, d in events[(trace.HOST_PLANE, trace.HOST_LINE)]
               if n == "stream.key") * 1e-9
    assert moved["stream.key"] == pytest.approx(keys, rel=1e-3)
