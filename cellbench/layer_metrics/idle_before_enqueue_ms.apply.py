"""Device idle time an apply before the program hands its executable to the
runtime: the median, over the traced window's periods, of the time from the
``sketch.apply`` span's start to the start of its handover span
(``engine.execute``, else ``sketch.dispatch``) — the program's own Python,
with the device provably idle (``cellbench/periods.py``). Host clock only.
The log line gives its parts: by span name, ``sketch.apply``'s own, and the
handover's call beside them."""

from cellbench import harness, periods


def read(run):
    split = periods.split(run, "idle_before")
    if split is None:
        return None
    harness.say("idle_before", periods=split["periods"], handovers=1,
                before_ms=periods.ms(split["before_s"]),
                self_ms=periods.ms(split["before_self_s"]),
                **{name: periods.ms(s) for name, s in split["by_name"].items()},
                call_ms=periods.ms(split["call_s"]),
                reader_s=f"{split['reader_s']:.4f}")
    return 1e3 * split["before_s"]
