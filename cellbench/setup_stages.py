"""What the per-layer metrics that read the program's set-up records share.

The program keeps a record of every import, jit trace, lowering and backend
compile (or cache load) of the process on its spans' clock
(``libskylark_tpu/telemetry/setup.py``, always on). ``seconds_before_window``
reads what ended before the traced window's first operation began, so the
compiles of the check's reference — made after the window and before the
readers run — are out, and so is anything a window compiled (there is
nothing: ``compiles_in_window`` = 0 is part of ``correct``). A program older
than those records has no ``telemetry.setup``, and the metric is left out.
"""

from __future__ import annotations

ROOT_SPAN = "sketch.apply"      # one an operation, in every admitted cell


def seconds_before_window(run, phases):
    """Seconds covered by the union of the set-up records of ``phases``
    that ended before the oldest of the window's ``run.operations``
    ``ROOT_SPAN`` spans began. ``None`` with no device plane (a rehearsal
    off the chip times another compiler), with no such spans or a ring
    that has wrapped past them (the window's start is then not known), on
    a program that keeps no set-up records, or once its bounded list has
    dropped any (the imports go first: the sums would be of a tail)."""
    from libskylark_tpu import telemetry

    setup = getattr(telemetry, "setup", None)
    if (setup is None or run.trace is None or not run.operations
            or setup.dropped()):
        return None
    stages = telemetry.stage_seconds(ROOT_SPAN, last=run.operations)
    if not stages or len(stages) < run.operations:
        return None     # wrapped (None), or an operation left no span
    window = [s for s in telemetry.finished_spans()
              if s.name == ROOT_SPAN][-run.operations:]
    return setup.seconds(phases, until_ns=window[0].t_start_ns)
