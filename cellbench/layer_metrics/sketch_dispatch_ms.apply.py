"""Host time one apply spends handing its work to the device: the
``sketch.dispatch`` span directly under its ``sketch.apply`` (padding or
lookup, and the enqueue of the compiled call; it returns when the work is
enqueued). A program whose applies open no such span gives no number."""

import math

from cellbench import stages


def read(run):
    value = stages.median_ms(
        run, "sketch.apply",
        lambda s: s["children"].get("sketch.dispatch", math.nan))
    return None if value is None or math.isnan(value) else value
