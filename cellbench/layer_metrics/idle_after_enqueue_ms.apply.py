"""Device idle time an apply from the handover on: the median period (one
``sketch.apply``'s start to the next one's) − the median time before the
handover − the device's busy time an apply: jax's dispatch of the executable,
the launch, gaps between the program's own device ops, the completion, the
caller's wake-up and loop (``cellbench/periods.py``). The two host-clock
medians are durations of one process's clock and the device's is a duration
of its own: nothing is subtracted across the profile's planes. The log line
sets the idle share by this account beside the trace's (``device_idle.apply``
÷ 100): they differ by what the window holds beyond its median periods."""

from cellbench import harness, periods


def read(run):
    split = periods.split(run, "idle_after")
    if split is None:
        return None
    after_s = split["period_s"] - split["before_s"] - split["device_s"]
    harness.say("idle_after", periods=split["periods"],
                period_ms=periods.ms(split["period_s"]),
                before_ms=periods.ms(split["before_s"]),
                device_ms=periods.ms(split["device_s"]),
                after_ms=periods.ms(after_s),
                idle_share=f"{1 - split['device_s'] / split['period_s']:.5f}",
                idle_share_traced=f"{run.trace.idle_share:.5f}",
                reader_s=f"{split['reader_s']:.4f}")
    return 1e3 * after_s
