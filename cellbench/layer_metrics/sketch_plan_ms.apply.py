"""Host time one apply spends choosing its kernel plan: the ``sketch.plan``
span directly under its ``sketch.apply`` (knob resolution, the plan cache,
qualification)."""

from cellbench import stages


def read(run):
    return stages.median_ms(
        run, "sketch.apply", lambda s: s["children"].get("sketch.plan", 0.0))
