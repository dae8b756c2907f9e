"""Declared metric and span names: the single source of truth the
``metric-names`` lint rule checks instrument call sites against, and
``tests/test_telemetry.py`` checks ``span(...)`` call sites against.

Every counter/gauge/histogram recorded anywhere in
``libskylark_tpu`` must be declared here once — (name, kind, one-line
role) — and created at exactly one call site. The rule
(:mod:`libskylark_tpu.analysis.rules.metric_names`) flags:

- a creation call whose name is not declared here (typo'd or
  undocumented metric);
- a name created at more than one site (two sites would silently share
  one instrument — or worse, disagree on its kind and raise at import);
- a declaration with no remaining call site (stale — delete it);
- a name that cannot render as a valid Prometheus metric (the exporter
  maps ``.`` to ``_``; everything else must already conform).

Naming convention: ``<subsystem>.<noun>`` (dots become underscores on
the Prometheus surface, and counters grow ``_total`` there —
``engine.compile_seconds`` scrapes as
``skylark_engine_compile_seconds``).
"""

from __future__ import annotations

from typing import Dict, Tuple

#: name -> kind ("counter" | "gauge" | "histogram")
METRICS: Dict[str, str] = {
    # engine (engine/compiled.py)
    "engine.compile_seconds": "histogram",
    "engine.load_seconds": "histogram",
    "engine.persistent_cache_failures": "counter",
    # telemetry's own bookkeeping (telemetry/trace.py)
    "telemetry.spans": "counter",
    # set-up accounting (telemetry/setup.py), by phase (SETUP_PHASES
    # below), always on: each record's own seconds summed, and the count of
    # records — what summary() gives, for whoever scrapes instead of calls
    "setup.seconds": "counter",
    "setup.events": "counter",
    # ml (ml/admm.py)
    "ml.admm.iterations": "counter",
    "ml.admm.objective": "gauge",
    "ml.admm.reldel": "gauge",
    # io (io/chunked.py, io/webhdfs.py)
    "io.chunked.batches": "counter",
    "io.webhdfs.reconnects": "counter",
    # resilience (resilience/faults.py, policy.py, health.py)
    "resilience.faults_fired": "counter",
    "resilience.retries": "counter",
    "resilience.health_transitions": "counter",
    # the compiled sparse hash apply (sketch/hash.py): stored nonzeros
    # sketched, by family and kernel (sparse_serve.sparse_kernel) — the
    # benchmark's cross-check of the nnz that sparse_nnz_rate.apply reads
    # from the sketch.dispatch spans
    "sketch.sparse_nnz": "counter",
    # the sparse -> sparse hash apply (sketch/hash.py apply_sparse, the
    # sketch.hash_sparse_out program, since PR 64): lanes its collisions
    # merged away (nnz in - nnz out), by family and kernel
    # (sparse_serve.coalesce_kernel: "xla_window_sort" | "xla_global_sort");
    # counted when the result's stored count is first read (SparseMatrix.nnz
    # of a device-born matrix), never by a sync inside the apply — the
    # cross-check of the nnz_out that result_fill.apply reads from the
    # sketch.dispatch spans; the apply's stored nonzeros count under
    # sketch.sparse_nnz like the dense-result route's
    "sketch.sparse_merged": "counter",
    # base.sparse.spmm called directly (the sparse power iterations of
    # nla/svd.py, Krylov solvers, kernels on sparse inputs): stored
    # nonzeros multiplied, by kernel (sparse_serve.product_kernel:
    # "pallas_tiles" | "xla: <why>"); a dense sketch of a sparse operand
    # counts under sketch.sparse_nnz instead. Since PR 61 base.sparse.spmm_t
    # counts here too, with side="transposed" (kernel "pallas_runs" |
    # "xla: <why>")
    "sparse.spmm_nnz": "counter",
    # the compiled dense feature-map apply (sketch/rft.py): feature values
    # produced (rows × s), by family and kernel ("pallas_planes" |
    # "pallas_generate" | "xla") — the cross-check of the features that
    # feature_rate.apply reads from the sketch.dispatch spans
    "sketch.features": "counter",
    # the Fastfood apply (sketch/frft.py): feature values produced (rows ×
    # s), by family and route ("fastfood_blocks" = the one compiled
    # program of frft.fastfood_features | "chain" = the eager chain of a
    # declined route) — the cross-check of the features that
    # feature_rate.apply reads from its sketch.dispatch spans
    "sketch.fastfood_features": "counter",
    # the TensorSketch apply (sketch/ppt.py): examples featurized, by
    # family and route ("program" = the one compiled program of
    # ppt.tensorsketch_features, with the label radix = the bucket classes
    # its spectral products were formed by: "1" the whole product — shapes
    # with no classes, or a transform one of whose classes overflowed its
    # rows — | "chain" = the eager chain of a declined operand, no radix)
    # — rows × S × (q + 1) of them are the elements that conv_rate.apply
    # reads from its sketch.dispatch spans
    "sketch.tensorsketch_rows": "counter",
    # the compiled FJLT/wht apply (sketch/fjlt.py): operand entries sign-
    # and Hadamard-mixed (transform axis × free axis), by family and kernel
    # ("pallas_blocks" | "xla_bf16x3" | "xla_f32") — the cross-check of
    # the elements that mix_rate.apply reads from the sketch.dispatch spans
    "sketch.mixed_elements": "counter",
    # the dense apply of an operand on a mesh (parallel/shard_apply.py, the
    # sketch.dense_mesh program): bytes ONE device sends in the apply's
    # collective, reckoned from the shapes, by family and collective
    # ("psum_scatter" | "ppermute_ring" | "psum" | "none") — the cross-check
    # of the collective_bytes that collective_rate.apply reads from the
    # sketch.dispatch spans with path="mesh"
    "sketch.mesh_collective_bytes": "counter",
    # accesses of an allocation's key material (base/context.py), by
    # result ("hit": kept from an earlier access | "miss": derived now),
    # always on — over a benchmark window every access is a hit (the
    # misses are in warm-up); the count beside the stream.key spans'
    # attribute cached
    "stream.key_cache": "counter",
    # sparse serve operands (engine/serve.py, docs/serving)
    "serve.sparse_submits": "counter",
    "serve.sparse_densified": "counter",
    "serve.sparse_kernel_flushes": "counter",
    "serve.sparse_nnz_class": "histogram",
    # FWHT serve tier (engine/serve.py, docs/performance)
    "serve.fwht_flushes": "counter",
    "serve.compressed_matmul_submits": "counter",
    # stateful serve sessions (sessions/registry.py)
    "sessions.opened": "counter",
    "sessions.appends": "counter",
    "sessions.finalized": "counter",
    "sessions.evicted": "counter",
    "sessions.resumed": "counter",
    "sessions.replayed_records": "counter",
    "sessions.checkpoints": "counter",
    "sessions.fenced": "counter",
    "sessions.live": "gauge",
    # distributed sketching (dist/coordinator.py)
    "dist.shards_dispatched": "counter",
    "dist.shards_retried": "counter",
    "dist.shards_reassigned": "counter",
    "dist.shards_abandoned": "counter",
    "dist.merges": "counter",
    "dist.coverage": "gauge",
    # pipelined dist-serve jobs (dist/serve.py, docs/distributed)
    "dist.shard_tasks": "counter",
    "dist.merge_depth": "gauge",
    "dist.jobs": "counter",
    "dist.early_resolves": "counter",
    # multi-tenant QoS (qos/tenants.py, qos/controller.py,
    # engine/serve.py — docs/qos)
    "qos.admitted": "counter",
    "qos.shed": "counter",
    "qos.rate_limited": "counter",
    "qos.queue_depth": "gauge",
    "qos.request_latency": "histogram",
    "qos.linger_target": "gauge",
    "qos.batch_target": "gauge",
    # content-addressed result cache (engine/resultcache.py,
    # docs/caching) — rendered as skylark_cache_* on Prometheus
    "cache.hits": "counter",
    "cache.misses": "counter",
    "cache.bytes_saved": "counter",
    "cache.evicted": "counter",
    "cache.single_flight_coalesced": "counter",
    "cache.resident_operands": "gauge",
    # fleet (fleet/router.py)
    "fleet.session_handoffs": "counter",
    "fleet.routed": "counter",
    "fleet.affinity_hit": "counter",
    "fleet.failover": "counter",
    "fleet.spilled": "counter",
    "fleet.hedged": "counter",
    "fleet.hedge_wins": "counter",
    "fleet.hedge_mismatches": "counter",
    # fleet shared-memory transport (fleet/shm.py)
    "fleet.shm_sends": "counter",
    "fleet.shm_fallbacks": "counter",
    # fleet autoscaler (fleet/autoscale.py)
    "fleet.autoscale_up": "counter",
    "fleet.autoscale_down": "counter",
    "fleet.replicas": "gauge",
    # training jobs (train/jobs.py, docs/training)
    "train.jobs_submitted": "counter",
    "train.slices_run": "counter",
    "train.preemptions": "counter",
    "train.resumes": "counter",
    "train.budget_exhausted": "counter",
    "train.progress": "gauge",
    "train.residual": "gauge",
    # network serve front door (net/server.py, docs/networking) —
    # rendered as skylark_net_* on Prometheus via the net collector
    "net.connections": "gauge",
    "net.requests": "counter",
    "net.wire_errors": "counter",
    "net.bytes_in": "counter",
    "net.bytes_out": "counter",
    "net.drains": "counter",
}

#: span name -> (layer of PERF.md §3, the per-layer metric of
#: BENCHMARK.json it feeds, or "operator" for one that is read by people).
#: Every string literal in the first argument of a ``span(`` call in the
#: package is declared here, and every name here has a call site: the
#: benchmark's readers and ``cellbench/tools/span_gaps.py`` key on these
#: strings, so a rename fails a test instead of turning a metric into
#: ``None``. (``PhaseTimer``'s labels are variables under their own gate.)
SPANS: Dict[str, Tuple[str, str]] = {
    # the measured apply (sketch/transform.py, dense.py, pallas_dense.py,
    # hash.py); the sparse hash apply's sketch.dispatch carries path, family,
    # nnz and nnz_class, which sparse_nnz_rate.apply reads, and for the
    # operator lookup (sparse_serve.lookup: what is computed at the lane)
    # and kernel (sparse_serve.sparse_kernel: "pallas_rows" | "xla_scatter",
    # what adds the terms up) and walk ("flat": the rows kernel's body that
    # visits each (tile, chunk) pair once, pallas_sparse._kernel_rows — a
    # program older than PR 41 has no such key); the feature maps'
    # (sketch/rft.py) carries
    # path="features", family, epilogue, kernel, features (= rows × s, which
    # feature_rate.apply reads), finisher (what computes the elementwise
    # map: "cos_turns" = sketch/cos_turns.py on the kernel route, "cos" |
    # "exp" = the stock jnp function on the XLA route; for the operator)
    # and, on the kernel route, m_tile, s_tile and operator_residency; the
    # FJLT/wht apply's (sketch/fjlt.py) carries path="fut", family, fut,
    # kernel ("pallas_blocks" = pallas_wht.mix_blocks then the gather |
    # "xla_bf16x3" | "xla_f32" for fut="wht"; "xla_dft" = the blocked DFT of
    # fut.dft_blocks / sample_outer_dft for fut="dct" | "dht"), factors (the
    # Kronecker split of the axis, for "xla_dft" the split (R, f1, f2) of
    # fut.dft_factors as the axis has it — the stages' arrays carry f1, its
    # half and f2 padded to whole tiles of rows, fut.dft_pads, and the span
    # does not say so; the sampled outer factor first), tile (the free-axis
    # entries a pass of the walk takes: the Pallas pass's, MIX_TILE, or for
    # "xla_dft" a columnwise operand's whole free axis, else fjlt.dft_tile
    # of the axis; for the operator), slabs ("xla_dft" only: fjlt.dft_slabs,
    # the ρ of the R slabs of the sampled digit a pass takes — R ÷ slabs
    # passes an apply or a tile; for the operator), sample_chunk (fut="wht"
    # only, since PR 50: fut.sample_outer_chunk, the samples whose rows the
    # sampled outer factor gathers at a time — all s_dim where one block
    # holds the axis or one chunk the samples; for the operator), elements
    # (= axis × columns mixed, which mix_rate.apply reads: the operand's, no
    # pad counted) and sampled (= s × columns kept)
    # the sparse -> sparse hash apply (sketch/hash.py apply_sparse, the
    # sketch.hash_sparse_out program, since PR 64) opens its own sketch.apply
    # root (result="sparse") and a sketch.dispatch with path="sparse", family,
    # nnz, nnz_class, lookup, result="sparse", kernel
    # (sparse_serve.coalesce_kernel: "xla_window_sort" | "xla_global_sort",
    # which sort coalesces) and why, lanes_out (the result's lane extent, the
    # operand's) and nnz_out — filled when the result's count is first read,
    # never by a sync inside the apply (result_fill.apply reads nnz_out ÷
    # lanes_out; coalesce_share.apply reads the device ops under the
    # program's "sparse_coalesce" named scope); its handover is the
    # engine.execute inside it
    # the dense sketch of a sparse operand (sketch/dense.py
    # _apply_rowwise_sparse, the sketch.dense_sparse program, since PR 57)
    # carries path="sparse", family, s, kernel (sparse_serve.product_kernel:
    # "pallas_tiles" = the walk of sketch/pallas_spmm.py | "xla: <why>" =
    # base.sparse.spans_product), nnz and nnz_class (sparse_nnz_rate.apply
    # reads nnz), lane_slots (the lane positions the walk's layout holds and
    # its copies move, chunk padding included — the lane class on the XLA
    # route; lane_fill.apply reads nnz ÷ lane_slots), segments (the (row
    # block, column tile) pairs the lanes are regrouped by; 1 on the XLA
    # route) and, on the kernel, row_block, col_tile, chunk and, since PR 58,
    # grouped_lanes (the stored lanes the walk takes a group of rows at a
    # time, loads ahead of stores: ÷ nnz, the share of the walk off the
    # serial read-modify-write chain) and, since PR 60, covered_segments
    # (the live segments whose last chunk — a full one, or the only one —
    # holds at least TilesPlan.cover stored lanes, so that its walk outlasts
    # the copy of the next segment's tile of B: ÷ segments, how often the
    # chunk's size hides that copy; counted once at placement, an apply
    # reads the stored integer) and, since PR 62, how the arrays crossed the
    # Mosaic call's door: result_layout ("rows": the call's own (rows, s)
    # output, s a multiple of 1024 — and the XLA route — | "kernel_view":
    # relaid by XLA) and operator_view ("kernel": Sᵀ generated in the
    # kernel's view | "rows": the XLA route); since PR 61 the columnwise apply
    # (_apply_columnwise_sparse, the sketch.dense_sparse_cw program) opens
    # the same span with side="transposed", kernel "pallas_runs" | "xla:
    # <why>", the blocks those of the transposed side (row_block A's
    # columns, col_tile A's rows) and how the skew was taken: grouped_lanes
    # (short result rows, by (rank, row)), run_lanes (the stored lanes in
    # runs of ONE result row, summed in registers: whole rows of ≥ 16 lanes
    # a segment, and what the grouped prefix leaves) and run_slots (those
    # with their padding to whole groups); its handover is the engine.execute inside
    # it
    # the dense apply of an operand on more than one device
    # (parallel/shard_apply.py apply_on_mesh, since PR 55) carries path="mesh",
    # route ("program"; on the XLA route of sketch/dense.py that a declined
    # sharding keeps, the span has no path and route="xla: <why>"), family,
    # grid ("2x2": the mesh's shape), spec (the operand's PartitionSpec),
    # orientation, local_shape (a device's shard, the contracted axis padded),
    # kernel ("pallas_planes" | "pallas_generate" = the one-chip kernels of
    # the device's plan | "xla_blocks" = the fori_loop over generated blocks)
    # and, on a kernel, operator_residency, m_tile and precision, collective
    # ("psum_scatter" | "ppermute_ring" | "psum" | "none"), reduce_over (the
    # mesh axes that shard the contracted axis) and collective_bytes (what one
    # device sends in that collective, from the shapes: collective_rate.apply
    # reads it); since PR 56 exchange ("pipelined": the reduce-scatter as a
    # ring of ppermutes a row panel, in flight behind the next panel's
    # contraction — collective then reads "ppermute_ring" and the same bytes
    # travel | "single": one collective after the contraction) and panels
    # (the row panels; 1 where single);
    # its handover is the engine.execute inside it
    # a fused dense apply notes its plan on sketch.apply
    # (pallas_dense._plan): path="pallas", m_tile, s_tile, precision,
    # plan_source, operator_residency and, since PR 49, k_cols (the
    # contraction's step) and vmem_limit_bytes (what the "hbm" contraction
    # passes Mosaic for a grown row tile; 0: none passed) — for the operator
    "sketch.apply": ("sketch kernel", "sketch_host_ms.apply"),
    # a dense operand made a jax array and held to the transform's N
    # (SketchTransform._apply: jnp.asarray, the 1-D lift, the shape check) —
    # since PR 53, the largest piece of sketch.apply's own time ahead of the
    # handover (HANDOVER below), a part of apply_periods' before_by_name
    "sketch.operand": ("sketch kernel", "idle_before_enqueue_ms.apply"),
    # the auto-materialize dispatch of a dense transform or feature map
    # (OperatorCache._note_eager_apply): whether this eager apply pins the
    # operator — on the kernel route the apply's plan resolved a second
    # time (pallas_serves_eager -> effective_plan), and the Nth time off it
    # the pin itself; since PR 53 the other large piece of sketch.apply's
    # own time in the dense and feature cells
    "sketch.materialize": ("sketch kernel", "idle_before_enqueue_ms.apply"),
    "sketch.plan": ("sketch kernel", "sketch_plan_ms.apply"),
    "sketch.dispatch": ("sketch kernel", "sketch_dispatch_ms.apply"),
    # every access of an allocation's key material (base/context.py
    # Allocation.key / key_data / key_words), attribute cached (True: kept
    # from an earlier access, False: derived now), and the block-key
    # table where it is a dispatch of its own (pallas_dense._block_keys: a
    # caller that holds the table; every apply, the mesh program's since
    # PR 55 among them, derives it inside its program)
    "stream.key": ("streams", "stream_key_ms.apply"),
    # a sparse operand's lanes regrouped on the device for the sparse ×
    # dense kernel (base/sparse.py SparseMatrix.tiled_device), once per
    # (dtype, side, layout): attributes layout, side ("rows" | "transposed",
    # since PR 61: the second placement, made at the first transposed
    # product), nnz, lane_slots, grouped_lanes, covered_segments (as the
    # dispatch's; run_lanes and run_slots on the transposed side), bytes
    # (placed) and seconds (the host regrouping and the upload, waited for)
    # — set-up, never inside a measured apply; opened whoever listens
    # (force=True, since PR 61: the benchmark's setup_place_s sums the
    # seconds of a run nobody traces)
    "sparse.place": ("set-up", "setup_place_s"),
    # the measured solve (nla/svd.py, engine/compiled.py); under a
    # sketch.apply the same spans are the compiled applies' way to the
    # runtime: engine.lookup is a part of what idle_before_enqueue_ms.apply
    # reads (apply_periods' before_by_name), and engine.execute — exactly
    # entry.executable(*args) — is the handover (HANDOVER below) that ends it
    "nla.approximate_svd": ("solver phases", "operator"),
    "engine.call": ("solver phases", "operator"),
    "engine.lookup": ("solver phases", "idle_before_enqueue_ms.apply"),
    "engine.execute": ("solver phases", "idle_before_enqueue_ms.apply"),
    "engine.compile": ("solver phases", "operator"),
    "engine.lower": ("solver phases", "operator"),
    "engine.backend_compile": ("solver phases", "operator"),
    # the serve tier (engine/serve.py)
    "serve.submit": ("serve flush", "operator"),
    "serve.flush": ("serve flush", "operator"),
    "serve.isolation": ("serve flush", "operator"),
    # fleet/router.py, dist/serve.py
    "fleet.route": ("fleet", "operator"),
    "dist.shard_task": ("fleet", "operator"),
    # net/server.py
    "net.serve": ("wire", "operator"),
    # io/chunked.py, io/webhdfs.py
    "io.chunked.read": ("ingest", "operator"),
    "io.webhdfs.open": ("ingest", "operator"),
}

#: The handover of an apply: the span that wraps the ONE call that hands its
#: compiled program to jax and the runtime, in order of preference — the
#: first ``engine.execute`` among an apply's descendants (the routes through
#: ``engine.compiled``), else its first ``sketch.dispatch`` (a ``jax.jit``
#: called directly: the dense kernels of sketch/pallas_dense.py, the XLA
#: contractions of sketch/dense.py). In a closed blocking loop the device is
#: idle from an apply's first line to this span's start, and every other
#: idle nanosecond of the period comes after it: ``trace.apply_periods``
#: splits there, ``idle_before_enqueue_ms.apply`` and
#: ``idle_after_enqueue_ms.apply`` read the two sides. The body of such a
#: span holds the executable's call and nothing the program could do
#: earlier. Every name here is declared in ``SPANS``.
HANDOVER: Tuple[str, ...] = ("engine.execute", "sketch.dispatch")

#: phase of a set-up record (telemetry/setup.py ``PHASES``) -> the
#: per-layer metric of BENCHMARK.json that reads it (layer "set-up" of
#: PERF.md §3; each moves ``setup_s``), or "operator". The readers
#: (``cellbench/setup_stages.py``) key on these strings.
SETUP_PHASES: Dict[str, str] = {
    # exec_module of the package's modules and of what they import first
    "import": "setup_import_s",
    # jax's jaxpr_trace_duration and jaxpr_to_mlir_module_duration: paid
    # by every process, persistent cache or not
    "trace": "setup_lower_s",
    "lower": "setup_lower_s",
    # jax's backend_compile_duration: the compile on a checkout's first
    # run, the cache load (inside it) on every later one
    "backend_compile": "setup_compile_s",
    # cache_retrieval_time_sec: a detail of the backend_compile record it
    # lies inside, never added to it
    "cache_load": "operator",
}

__all__ = ["HANDOVER", "METRICS", "SETUP_PHASES", "SPANS"]
