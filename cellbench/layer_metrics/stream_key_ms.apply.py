"""Host time one apply spends deriving keys: the sum of the ``stream.key``
spans directly under its ``sketch.apply`` (``Allocation.key`` and the
kernel's block-key table)."""

from cellbench import stages


def read(run):
    return stages.median_ms(
        run, "sketch.apply", lambda s: s["children"].get("stream.key", 0.0))
