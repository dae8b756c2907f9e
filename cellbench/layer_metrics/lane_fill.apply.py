"""Share of the lane slots the sparse walk moves that hold a stored nonzero:
Σ ``nnz`` ÷ Σ ``lane_slots`` over the ``sketch.dispatch`` spans with
``path="sparse"`` of the operations completed in the window, in percent
(the rest is the padding of the placement's chunks and lane class). A
program whose spans carry no ``lane_slots`` gives no number."""


def read(run):
    from libskylark_tpu.telemetry import trace

    finished = getattr(trace, "finished_spans", None)
    if finished is None or not run.operations:
        return None
    spans = [s.attrs for s in finished()
             if s.name == "sketch.dispatch" and s.attrs.get("path") == "sparse"
             and "nnz" in s.attrs and "lane_slots" in s.attrs][-run.operations:]
    slots = sum(a["lane_slots"] for a in spans)
    if len(spans) < run.operations or not slots:
        return None     # an operation left no such span: nothing whole to read
    return 100.0 * sum(a["nnz"] for a in spans) / slots
