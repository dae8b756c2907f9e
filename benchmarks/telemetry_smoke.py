"""Telemetry smoke battery: the CI gate for the observability contract.

Runs a small serve workload with telemetry enabled (JSONL export into a
temp dir), including a tag-pinned poison request under a deterministic
``serve.flush`` fault plan so the bisection-isolation path traces too,
then asserts the exported artifacts:

1. **JSONL schema**: every line in every ``spans-*.jsonl`` /
   ``metrics-*.jsonl`` parses and carries the documented required
   fields (docs/observability.rst).
2. **Span-tree well-formedness**: every non-null ``parent_id`` resolves
   to an exported span (no orphan parents), and no span is its own
   ancestor.
3. **End-to-end request trace**: the request id attached at
   ``submit()`` appears on that request's ``serve.submit`` span, on the
   ``serve.flush`` span of its cohort (which parents under the submit
   span — the cross-thread handoff), and on every
   ``serve.isolation`` retry span whose half contained it.
4. **Unified Prometheus surface**: ``telemetry.prometheus_text()``
   exposes the engine, serve, and resilience counters under the
   ``skylark_`` naming scheme.

Prints one JSON summary line; exits nonzero on any violation. Run by
``script/ci`` (the disabled-mode overhead check lives in the serve
gate, which compares a telemetry-off ``bench.py --serve`` against the
committed r8 record).
"""

from __future__ import annotations

import glob
import json
import os
import sys
import tempfile

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_TDIR = tempfile.mkdtemp(prefix="skylark_telemetry_smoke_")
os.environ["SKYLARK_TELEMETRY_DIR"] = _TDIR  # before libskylark import

# Hardware-independent; default to CPU unless the caller pinned a
# platform (the conftest discipline).
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402

from libskylark_tpu import Context, engine, telemetry  # noqa: E402
from libskylark_tpu import sketch as sk  # noqa: E402
from libskylark_tpu.resilience import faults  # noqa: E402

REQUIRED_SPAN_FIELDS = ("kind", "name", "trace_id", "span_id",
                        "t_wall", "duration_s", "status", "thread")


def fail(msg: str) -> None:
    print(json.dumps({"metric": "telemetry_smoke", "ok": False,
                      "violation": msg}))
    sys.exit(1)


def run_workload() -> tuple:
    """A coalesced cohort with one tag-pinned poison request; returns
    (poison request id, clean request ids)."""
    rng = np.random.default_rng(0)
    ctx = Context(seed=0)
    reqs = [(sk.JLT(48, 16, ctx),
             rng.standard_normal((48, 3 + i)).astype(np.float32))
            for i in range(4)]
    plan = {"seed": 1, "faults": [
        {"site": "serve.flush", "error": "SketchError", "tag": "poison"}]}
    clean_ids = [f"req-smoke-clean-{i}" for i in range(3)]
    poison_id = "req-smoke-poison"
    with engine.MicrobatchExecutor(max_batch=4, linger_us=50_000) as ex:
        with faults.fault_plan(plan):
            futs = [ex.submit_sketch(T, A, dimension=sk.COLUMNWISE,
                                     request_id=rid)
                    for (T, A), rid in zip(reqs[:3], clean_ids)]
            with faults.tag("poison"):
                pT, pA = reqs[3]
                pf = ex.submit_sketch(pT, pA, dimension=sk.COLUMNWISE,
                                      request_id=poison_id)
            ex.flush()
            for f in futs:
                f.result(timeout=120)  # cohort-mates must succeed
            try:
                pf.result(timeout=120)
                fail("poison request unexpectedly succeeded")
            except Exception as e:  # noqa: BLE001 — the expected poison
                if type(e).__name__ != "SketchError":
                    fail(f"poison failed with {type(e).__name__}, "
                         f"expected SketchError")
    exporter = telemetry.get_exporter()
    if exporter is None:
        fail("SKYLARK_TELEMETRY_DIR set but no exporter installed")
    exporter.flush_sync()
    return poison_id, clean_ids


def load_lines(pattern: str) -> list:
    docs = []
    for path in sorted(glob.glob(os.path.join(_TDIR, pattern))):
        with open(path) as fh:
            for i, line in enumerate(fh):
                try:
                    docs.append(json.loads(line))
                except json.JSONDecodeError:
                    fail(f"{os.path.basename(path)}:{i + 1} is not "
                         f"valid JSON")
    return docs


def validate_schema(spans: list, metric_lines: list) -> None:
    for doc in spans:
        missing = [f for f in REQUIRED_SPAN_FIELDS if f not in doc]
        if missing:
            fail(f"span line missing fields {missing}: "
                 f"{json.dumps(doc)[:200]}")
        if doc["kind"] != "span":
            fail(f"spans file carries kind={doc['kind']!r}")
        if doc["status"] not in ("ok", "error"):
            fail(f"span status {doc['status']!r} not ok|error")
    if not metric_lines:
        fail("no metrics lines exported")
    for doc in metric_lines:
        if doc.get("kind") != "metrics" or "snapshot" not in doc:
            fail("metrics line missing kind/snapshot")
        collectors = doc["snapshot"].get("collectors", {})
        for want in ("engine", "serve"):
            if want not in collectors:
                fail(f"metrics snapshot missing collector {want!r}")


def validate_tree(spans: list) -> None:
    by_id = {}
    for doc in spans:
        if doc["span_id"] in by_id:
            fail(f"duplicate span_id {doc['span_id']}")
        by_id[doc["span_id"]] = doc
    for doc in spans:
        parent = doc.get("parent_id")
        if parent is not None and parent not in by_id:
            fail(f"orphan parent: span {doc['name']}/{doc['span_id']} "
                 f"references missing parent {parent}")
        # cycle check: walk to the root (bounded by span count)
        seen = set()
        cur = doc
        while cur is not None:
            if cur["span_id"] in seen:
                fail(f"span ancestry cycle at {cur['span_id']}")
            seen.add(cur["span_id"])
            cur = by_id.get(cur.get("parent_id"))


def validate_request_trace(spans: list, poison_id: str,
                           clean_ids: list) -> dict:
    by_id = {d["span_id"]: d for d in spans}
    submits = [d for d in spans if d["name"] == "serve.submit"]
    flushes = [d for d in spans if d["name"] == "serve.flush"]
    isolations = [d for d in spans if d["name"] == "serve.isolation"]
    all_ids = set(clean_ids) | {poison_id}

    submit_ids = {d.get("request_id") for d in submits}
    if not all_ids <= submit_ids:
        fail(f"submit spans missing request ids: {all_ids - submit_ids}")

    # the cohort's flush span must carry every member's id and parent
    # under a submit span (the cross-thread handoff)
    cohort_flushes = [d for d in flushes
                      if all_ids <= set(d.get("attrs", {})
                                        .get("request_ids", []))]
    if not cohort_flushes:
        fail("no serve.flush span carries the full cohort's request ids")
    fl = cohort_flushes[0]
    parent = by_id.get(fl.get("parent_id"))
    if parent is None or parent["name"] != "serve.submit":
        fail("flush span does not parent under a serve.submit span")
    if fl["status"] != "error":
        fail("poisoned cohort's flush span not marked error")

    # every isolation retry span: nests under the flush tree and its
    # request_ids are a subset of the cohort — and the poison id appears
    # on the capacity-1 isolation span that failed
    poison_leaf = None
    for iso in isolations:
        rids = set(iso.get("attrs", {}).get("request_ids", []))
        if not rids <= all_ids:
            fail(f"isolation span carries foreign request ids: {rids}")
        anc = iso
        while anc is not None and anc["name"] != "serve.flush":
            anc = by_id.get(anc.get("parent_id"))
        if anc is None:
            fail("isolation span not rooted under a serve.flush span")
        if rids == {poison_id} and iso["status"] == "error":
            poison_leaf = iso
    if not isolations:
        fail("no serve.isolation spans under an injected flush fault")
    if poison_leaf is None:
        fail("no failed capacity-1 isolation span pinned to the poison "
             "request id")
    return {"submits": len(submits), "flushes": len(flushes),
            "isolations": len(isolations)}


def validate_prometheus() -> None:
    text = telemetry.prometheus_text()
    for needle in ("skylark_engine_lifetime_misses",
                   "skylark_serve_submitted",
                   "skylark_serve_flush_failures",
                   "skylark_resilience_faults_fired_total",
                   "skylark_telemetry_spans_total"):
        if needle not in text:
            fail(f"prometheus_text missing {needle}")


def main() -> None:
    poison_id, clean_ids = run_workload()
    spans = load_lines("spans-*.jsonl")
    metric_lines = load_lines("metrics-*.jsonl")
    if not spans:
        fail("no spans exported")
    validate_schema(spans, metric_lines)
    validate_tree(spans)
    counts = validate_request_trace(spans, poison_id, clean_ids)
    validate_prometheus()
    print(json.dumps({
        "metric": "telemetry_smoke", "ok": True, "spans": len(spans),
        "metric_lines": len(metric_lines), **counts,
        "poison_request": poison_id,
    }))


if __name__ == "__main__":
    main()
