"""What the per-layer metrics that read the program's own spans share.

The program opens its spans by itself while a ``jax.profiler`` session
records (``libskylark_tpu/telemetry/trace.py``), so the traced window leaves
them in the program's ring of finished spans; ``stage_seconds`` reads a
parent span's total, self time and children by name from it. A program older
than those spans has no such reader, and the metric is left out.
"""

from __future__ import annotations

import statistics

MIN_OPERATIONS = 10     # fewer spans than this give no median worth the name


def median_ms(run, root: str, pick):
    """Median, in ms, of ``pick(stage)`` over the ``root`` spans of the
    operations completed in the traced window; ``None`` when the program
    recorded fewer than ``MIN_OPERATIONS`` of them or its ring has wrapped
    since (the window is then not whole)."""
    from libskylark_tpu.telemetry import trace

    stage_seconds = getattr(trace, "stage_seconds", None)
    if stage_seconds is None or not run.operations:
        return None
    stages = stage_seconds(root, last=run.operations)
    if stages is None or len(stages) < MIN_OPERATIONS:
        return None
    return 1e3 * statistics.median(pick(stage) for stage in stages)
