"""The trace reduction: on an excerpt of a real trace (the first three
applies of ``jlt_apply``'s traced window on a TPU v5e, recorded in PR 25 with
``tools/excerpt.py``), and on a hand-made one whose answers are worked out in
the comments."""

import pathlib

import pytest

from cellbench import trace

EXCERPT = pathlib.Path(__file__).with_name("data") / "jlt_apply_3ops.xspace.pbtxt"


def read(text):
    from jax.profiler import ProfileData

    return trace.read_events(ProfileData.from_text_proto(text))


def test_real_excerpt():
    events = read(EXCERPT.read_text())
    ops = events[("/device:TPU:0", "XLA Ops")]
    assert len(ops) == 36                                   # 12 device ops an apply
    red = trace.reduce(events)
    assert red.window_s == pytest.approx(0.13392084, rel=1e-9)
    # no two ops of this excerpt overlap, so the union is the plain sum
    assert red.busy_s == pytest.approx(sum(d for _, _, d in ops) * 1e-9, rel=1e-12)
    assert red.busy_s == pytest.approx(0.121564303, rel=1e-9)
    assert red.n_ops == 36
    assert 100 * red.idle_share == pytest.approx(9.2267, abs=1e-3)
    # per-op sums under short stable names; the kernel is three calls of 39.70 ms
    top = dict(red.device_ops)
    assert list(top)[0] == "_fused_call.1__tpu_custom_call_"
    assert top["_fused_call.1__tpu_custom_call_"] == pytest.approx(0.119100998, rel=1e-9)
    assert top["broadcast_multiply_fusion"] == pytest.approx(0.002454944, rel=1e-9)
    assert all(len(name) <= 80 and " " not in name for name in top)
    # every idle second is attributed to some host frame, most to the frame
    # that sits in block_until_ready (the driver's step)
    gaps = red.gap_seconds
    assert sum(gaps.values()) == pytest.approx(red.window_s - red.busy_s, rel=1e-9)
    assert max(gaps, key=gaps.get) == "sketch_apply.py:66_step"
    assert trace.WINDOW_SPAN not in gaps and len(red.idle_gaps) <= 10


HAND_MADE = {
    ("/device:TPU:0", "XLA Ops"): [
        ("%fusion.1 = f32[8]{0} fusion(f32[8] %p), kind=kLoop", 1000.0, 500.0),
        ('%cc.2 = f32[8] custom-call(f32[8] %x), custom_call_target="tpu_custom_call"',
         2000.0, 3000.0),
        ("%while.3 = (s32[]) while(s32[] %i)", 6000.0, 2000.0),   # encloses fusion.4
        ("%fusion.4 = f32[] fusion()", 6100.0, 500.0),
        ("%late.5 = f32[] fusion()", 9000.0, 1000.0),               # half outside
    ],
    ("/host:CPU", "python3"): [
        (trace.WINDOW_SPAN, 500.0, 9000.0),                          # window 500..9500
        ("$closed.py:20 run", 400.0, 9500.0),
        ("$api.py:3097 block_until_ready", 1400.0, 700.0),          # covers gap 1500..2000
        ("$x.py:1 f", 5100.0, 800.0),                               # covers gap 5000..6000
    ],
}


def test_hand_made_union_nesting_clipping_and_gaps():
    red = trace.reduce(read(trace.to_text_proto(HAND_MADE)))
    assert red.window_s == pytest.approx(9e-6)
    # busy: 500 + 3000 + 2000 (the while, once) + 500 (late.5 clipped at 9500)
    assert red.busy_s == pytest.approx(6e-6)
    assert red.idle_share == pytest.approx(1 - 6 / 9)
    # the enclosing while is left out of the per-op sums; its body is in
    assert red.op_seconds == pytest.approx({
        "fusion.1": 0.5e-6, "cc.2__tpu_custom_call_": 3e-6,
        "fusion.4": 0.5e-6, "late.5": 0.5e-6})
    assert red.n_ops == 4
    # gaps: 500..1000 and 8000..9000 under run, 1500..2000 under
    # block_until_ready, 5000..6000 under f
    assert red.gap_seconds == pytest.approx({
        "closed.py:20_run": 1.5e-6, "api.py:3097_block_until_ready": 0.5e-6,
        "x.py:1_f": 1e-6})


def test_no_device_op_is_an_error():
    events = {("/device:TPU:0", "XLA Ops"): [],
              ("/host:CPU", "python3"): [(trace.WINDOW_SPAN, 0.0, 10.0)]}
    with pytest.raises(ValueError):
        trace.reduce(events)
