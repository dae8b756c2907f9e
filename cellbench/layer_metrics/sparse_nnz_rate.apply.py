"""Stored nonzeros sketched per second of device time: the nonzeros of the
operations completed in the traced window, as the program recorded them (the
``nnz`` attribute of each operation's ``sketch.dispatch`` span with
``path="sparse"``), ÷ the device's busy time in that window, in millions."""


def read(run):
    from libskylark_tpu.telemetry import trace

    finished = getattr(trace, "finished_spans", None)
    if finished is None or run.trace is None or not run.operations:
        return None
    nnz = [s.attrs["nnz"] for s in finished()
           if s.name == "sketch.dispatch" and s.attrs.get("path") == "sparse"
           and "nnz" in s.attrs][-run.operations:]
    if len(nnz) < run.operations or not run.trace.busy_s:
        return None     # an operation left no such span: nothing whole to read
    return sum(nnz) / run.trace.busy_s / 1e6
