"""Entries transformed per second of device time by TensorSketch: the entries
of the q forward transforms and the one inverse of every example (rows × S ×
(q + 1)) of the applies completed in the traced window, as the program
recorded them (the ``elements`` attribute of each apply's ``sketch.dispatch``
span of the ``family`` ``"PPT"``), ÷ the device's busy time in that window,
in 10⁹ — beside ``mix_rate.apply`` and ``chain_mix_rate.apply`` of the
Hadamard cells. A program whose applies open no such span, or fewer than ten
of them, gives no number."""

MIN_OPERATIONS = 10


def read(run):
    from libskylark_tpu.telemetry import trace

    finished = getattr(trace, "finished_spans", None)
    if finished is None or run.trace is None or not run.operations:
        return None
    elements = [s.attrs["elements"] for s in finished()
                if s.name == "sketch.dispatch"
                and s.attrs.get("family") == "PPT"
                and "elements" in s.attrs][-run.operations:]
    if (len(elements) < max(run.operations, MIN_OPERATIONS)
            or not run.trace.busy_s):
        return None     # an operation left no such span: nothing whole to read
    return sum(elements) / run.trace.busy_s / 1e9
