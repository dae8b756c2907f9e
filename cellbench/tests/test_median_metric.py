"""The end-to-end latency is the median of the samples: one injected stall
of 3× does not move it, while it moves the mean."""

from cellbench import harness


def test_median_unmoved_by_one_stall():
    steady = [0.0433 + 1e-5 * (i % 7) for i in range(231)]
    stalled = list(steady)
    stalled[100] = 3 * 0.0433
    a, b = harness.sample_stats(steady), harness.sample_stats(stalled)
    assert abs(b["median"] - a["median"]) <= 1e-5       # within one sample step
    assert b["mean"] - a["mean"] > 3e-4                  # the mean moved by 0.8 %
    assert b["above_1.5x_median"] == 1 and a["above_1.5x_median"] == 0
    assert b["max"] == 3 * 0.0433


def test_closed_loop_samples_every_operation():
    from cellbench.loops import closed

    calls = []
    window = closed.run(lambda i: calls.append(i) or i, 0.05, keep=2)
    assert window["attempted"] == len(calls) == len(window["samples"])
    assert [i for i, _ in window["kept"]] == calls[-2:]
    assert window["failed"] == 0
