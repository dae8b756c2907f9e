"""Solver-pipeline compilation engine: compile once, serve many.

The NLA/ML layers' headline algorithms (randomized SVD, sketch-
preconditioned least squares, random-features KRR) are whole-solver
``jax.jit`` programs served from a donation-aware executable cache
whose key is a function of the program and its arguments alone.

Public surface::

    compiled(fn, static_argnames=..., donate_argnums=..., key_fn=...)
    stats() / reset()          # hit/miss/recompile/compile-time counters
    cache()                    # the LRU itself (snapshot, keys)
    donation_enabled() / maybe_donate(argnums)
    enable_persistent_cache()  # jax.experimental.compilation_cache wiring
    dump_stats(path)           # the CI jit-leak gate's exit artifact
    MicrobatchExecutor(...)    # shape-bucketed microbatch serving
    serve_stats()              # aggregate serving counters (docs/serving)
    SERVING/DEGRADED/DRAINING/STOPPED   # executor health states; the
                               # poison-isolation + drain story is
                               # docs/resilience (r9)

Environment: ``SKYLARK_EXEC_CACHE_SIZE`` (LRU capacity, default 128),
``SKYLARK_AOT_DIR`` (persistent AOT executable-artifact store —
load-instead-of-compile plus cross-process single-flight; see
:mod:`libskylark_tpu.engine.aot` and docs/performance),
``SKYLARK_EXEC_CACHE_DIR`` (jax persistent compilation cache; also a
deprecated alias for the artifact store at ``<dir>/aot``),
``SKYLARK_ENGINE_DONATE=1`` (solver entry points donate operands),
``SKYLARK_ENGINE_STATS_DUMP`` (write counters JSON at process exit).
"""

from libskylark_tpu.engine import aot, bucket, warmup
from libskylark_tpu.engine.cache import (CacheEntry, EngineStats,
                                         ExecutableCache)
from libskylark_tpu.engine.compiled import (CompiledFn, cache, code_version,
                                            compiled, digest,
                                            donation_enabled, dump_stats,
                                            enable_persistent_cache,
                                            maybe_donate, reset, stats)
from libskylark_tpu.engine.serve import (DEGRADED, DRAINING, SERVING,
                                         STOPPED, MicrobatchExecutor,
                                         ServeOverloadedError,
                                         request_statics, serve_stats)

__all__ = [
    "CacheEntry", "CompiledFn", "DEGRADED", "DRAINING", "EngineStats",
    "ExecutableCache", "MicrobatchExecutor", "SERVING", "STOPPED",
    "ServeOverloadedError", "aot", "bucket", "cache",
    "code_version", "compiled", "digest", "donation_enabled", "dump_stats",
    "enable_persistent_cache", "maybe_donate", "request_statics", "reset",
    "serve_stats", "stats", "warmup",
]
