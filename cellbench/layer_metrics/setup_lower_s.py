"""Seconds of set-up the program spent tracing its jits and lowering them to
MLIR (the ``trace`` and ``lower`` records, their union): paid by every
process, whether the persistent compilation cache hits or not."""

from cellbench import setup_stages


def read(run):
    return setup_stages.seconds_before_window(run, ("trace", "lower"))
