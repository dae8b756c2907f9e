"""Device time inside collective operations an apply, mean over the chips:
the traced window's time in ops that ``cellbench/collectives.py`` names a
collective (summed over the device planes) ÷ the planes ÷ the applies
completed in it. No such op in the window gives no number.

Beside the metric, as a log line and not as one: each device plane's busy
time an apply and their spread (max − min) — a chip that waits in the
exchange for a slower neighbour shows there."""

from cellbench import collectives, harness


def read(run):
    seconds = collectives.chip_seconds(run)
    if seconds is None:
        return None
    busy = {plane.rsplit(":", 1)[-1]: 1e3 * s / run.operations
            for plane, s in sorted(run.trace.busy_s_by_device.items())}
    harness.say("mesh", operations=run.operations,
                **{f"busy_ms_device_{d}": f"{ms:.4f}" for d, ms in busy.items()},
                busy_spread_ms=f"{max(busy.values()) - min(busy.values()):.4f}",
                collective_ops=",".join(sorted(
                    name for name in run.trace.op_seconds
                    if collectives.is_collective(name))))
    return 1e3 * seconds / run.operations
