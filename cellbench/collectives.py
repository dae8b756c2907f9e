"""What the two per-layer metrics of the layer ``mesh`` share: the device
time a traced window spent inside collective operations, on one chip.

``trace.Reduction.op_seconds`` sums each op's time over the device planes, so
the sum over the collective ops ÷ the number of planes is the mean over the
chips. An op is a collective by its name in the trace (``trace.short_op_name``:
the HLO instruction's name, which jax derives from the primitive —
``reduce_scatter.7`` — or XLA from the opcode — ``all-reduce.3``,
``all-gather-start.1``); both spellings are held to the one tuple below.
"""

from __future__ import annotations

# HLO collective opcodes; an instruction's name starts with one of them, with
# "-" or "_" between the words, and may go on with "-start" / "-done" (the
# halves of an asynchronous collective) and a number. On the v5e the
# sketch.dense_mesh program's traced window prints one of them: see
# cellbench/records/jlt_apply_mesh4.md for the names as the trace had them.
COLLECTIVES = ("reduce-scatter", "all-reduce", "all-gather",
               "collective-permute", "all-to-all")


def is_collective(op_name: str) -> bool:
    return op_name.replace("_", "-").startswith(COLLECTIVES)


def chip_seconds(run):
    """Seconds of the traced window one chip spent inside collective ops
    (mean over the device planes), or ``None``: nothing traced, or no such
    op in the window."""
    if run.trace is None or not run.operations:
        return None
    total = sum(seconds for name, seconds in run.trace.op_seconds.items()
                if is_collective(name))
    if not total:
        return None
    return total / len(run.trace.busy_s_by_device)


def mesh_dispatches(run) -> list:
    """The ``sketch.dispatch`` spans with ``path="mesh"`` of the operations
    completed in the traced window, oldest first; empty where the program
    opens none (a program without the mesh route) or not one an operation."""
    from libskylark_tpu.telemetry import trace

    finished = getattr(trace, "finished_spans", None)
    if finished is None or not run.operations:
        return []
    spans = [s for s in finished() if s.name == "sketch.dispatch"
             and s.attrs.get("path") == "mesh"][-run.operations:]
    return spans if len(spans) == run.operations else []
