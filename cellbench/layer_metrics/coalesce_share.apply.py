"""Share of an apply's device time spent coalescing: of the traced window's
time in device operations, the part NOT spent in operations that the compiled
sparse → sparse program (``sketch.hash_sparse_out`` in the engine's cache)
made outside its coalescing scope (``jax.named_scope``: each HLO
instruction's ``op_name`` says which scope made it), in percent. Outside the
scope are the lane streams (the cipher calls of ``randgen.stream_at``);
inside it the sorts, the segmented sum and the compaction — and with them the
copies the compiler adds for them (the relayouts around the sorts, the
loops' carries), which carry no ``op_name`` at all. A program with no such
executable, or a trace with no operation of its scope, gives no number."""

import re

PROGRAM = "sketch.hash_sparse_out"
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def instructions(hlo_text: str, scope: str) -> tuple:
    """``(inside, outside)``: the names — as ``trace.short_op_name`` spells
    them in a reduction's ``op_seconds`` — of the HLO instructions of
    ``hlo_text`` whose ``op_name`` lies under ``scope``, and of those that
    have an ``op_name`` and none under it."""
    from cellbench import trace

    inside, outside = set(), set()
    for line in hlo_text.splitlines():
        names = _OP_NAME.findall(line)
        if " = " in line and names:
            under = any(f"/{scope}/" in name + "/" for name in names)
            (inside if under else outside).add(
                trace.short_op_name(line.replace("ROOT ", "", 1)))
    return inside, outside


def read(run):
    if run.trace is None or not run.operations:
        return None
    try:
        from libskylark_tpu import engine
        from libskylark_tpu.sketch.sparse_coalesce import SCOPE
    except ImportError:
        return None     # a program older than the sparse → sparse route
    inside, outside = set(), set()
    cache = engine.cache()
    for key in cache.keys():
        if key[0] == PROGRAM:
            a, b = instructions(cache.lookup(key).executable.as_text(), SCOPE)
            inside |= a
            outside |= b - a
    seconds = run.trace.op_seconds
    total = sum(seconds.values())
    if not total or not any(name in inside for name in seconds):
        return None
    return 100.0 * (total - sum(t for name, t in seconds.items()
                                if name in outside)) / total
