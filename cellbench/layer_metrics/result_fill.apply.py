"""Share of the sparse result's lanes that hold a stored entry: Σ ``nnz_out``
÷ Σ ``lanes_out`` over the ``sketch.dispatch`` spans with
``result="sparse"`` of the operations completed in the window, in percent
(the rest is the operand's lane-class padding and the lanes the collisions
merged away). ``nnz_out`` is filled when a result's count is first read — the
check reads the kept results' — never by the apply; a program whose spans
carry no ``nnz_out`` gives no number."""


def read(run):
    from libskylark_tpu.telemetry import trace

    finished = getattr(trace, "finished_spans", None)
    if finished is None or not run.operations:
        return None
    spans = [s.attrs for s in finished()
             if s.name == "sketch.dispatch"
             and s.attrs.get("result") == "sparse"][-run.operations:]
    read_ = [a for a in spans if "nnz_out" in a and a.get("lanes_out")]
    if not read_:
        return None     # no result's count was read: nothing to divide
    return (100.0 * sum(a["nnz_out"] for a in read_)
            / sum(a["lanes_out"] for a in read_))
