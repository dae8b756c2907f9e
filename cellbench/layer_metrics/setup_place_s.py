"""Seconds of set-up the program spent laying sparse operands out for the
product kernel and placing them on the device: the ``seconds`` of the
process's ``sparse.place`` spans (one a (matrix, side, layout), opened
whoever listens), summed. A program whose placement leaves no such span
outside a profiler session gives no number."""


def read(run):
    from libskylark_tpu.telemetry import trace

    finished = getattr(trace, "finished_spans", None)
    if finished is None or not run.operations:
        return None
    seconds = [s.attrs["seconds"] for s in finished()
               if s.name == "sparse.place" and "seconds" in s.attrs]
    return sum(seconds) if seconds else None
