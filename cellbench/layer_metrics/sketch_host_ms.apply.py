"""Host time inside the program for one apply: the duration of its
``sketch.apply`` span (``SketchTransform.apply``, entry to enqueue)."""

from cellbench import stages


def read(run):
    return stages.median_ms(run, "sketch.apply", lambda s: s["total_s"])
