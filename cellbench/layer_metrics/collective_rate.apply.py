"""Bytes a chip sends through the mesh per second of its collective device
time: the ``collective_bytes`` of the traced window's ``sketch.dispatch``
spans with ``path="mesh"`` (what ONE device sends in its apply's collective,
as the program reckons it from the shapes) ÷ the window's device time inside
collective ops on one chip (``cellbench/collectives.py``), in 10⁹. A rate, as
``mix_rate.apply`` is, and no share: ``peaks.json`` holds no interconnect
peak. A program whose applies open no such span, or a window with no
collective op, gives no number."""

from cellbench import collectives


def read(run):
    seconds = collectives.chip_seconds(run)
    spans = collectives.mesh_dispatches(run)
    if seconds is None or not spans:
        return None
    sent = sum(s.attrs.get("collective_bytes", 0) for s in spans)
    return sent / seconds / 1e9 if sent else None
