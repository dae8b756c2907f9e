"""Engine tests: the donation-aware executable cache under the solver
pipelines (libskylark_tpu/engine).

Oracles: (a) the cache's own counters — the AOT discipline makes the
miss counter exactly the solver-compile counter; (b) jax's lowering
counter (jax._src.test_util.count_jit_and_pmap_lowerings) as the
framework-level witness that a cache hit really compiles nothing; (c)
donation observable through jax's deleted-buffer error.
"""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import jax._src.test_util as jtu

from libskylark_tpu import Context, engine, nla, tune
from libskylark_tpu.engine.cache import CacheEntry, ExecutableCache


@pytest.fixture()
def fresh_engine():
    engine.reset()
    yield
    engine.reset()


@pytest.fixture()
def scratch_plan_cache():
    """Swap in an empty in-memory plan cache so plan-fingerprint tests
    neither see nor touch the repo's certified benchmarks/plan_cache.json."""
    prev = tune.set_cache(tune.PlanCache(path=None))
    yield tune.get_cache()
    tune.set_cache(prev)


class TestCompiledWrapper:
    def test_hit_miss_counters(self, fresh_engine):
        calls = []

        @engine.compiled(static_argnames=("k",))
        def f(A, *, k):
            calls.append(1)
            return jnp.sum(A) * k

        A = jnp.ones((8, 8))
        assert float(f(A, k=3)) == 192.0
        assert float(f(A, k=3)) == 192.0
        s = engine.stats()
        assert (s.misses, s.hits, s.recompiles) == (1, 1, 0)
        # tracing happened exactly once — the hit served the executable
        assert len(calls) == 1

    def test_static_and_shape_changes_key_separately(self, fresh_engine):
        @engine.compiled(static_argnames=("k",))
        def f(A, *, k):
            return A * k

        f(jnp.ones((4,)), k=1)
        f(jnp.ones((4,)), k=2)       # static change: new executable
        f(jnp.ones((8,)), k=1)       # shape change: new executable
        f(jnp.ones((4,), jnp.bfloat16), k=1)  # dtype change too
        s = engine.stats()
        assert s.misses == 4 and s.hits == 0 and s.recompiles == 0

    def test_dynamic_kwargs_rejected(self, fresh_engine):
        @engine.compiled(static_argnames=("k",))
        def f(A, *, k):
            return A * k

        with pytest.raises(TypeError, match="positional"):
            f(A=jnp.ones((4,)), k=1)

    def test_identical_second_call_compiles_nothing(self, fresh_engine):
        """Framework-level recompile guard: the cache hit must not
        lower/compile anything in jax either."""

        @engine.compiled
        def f(A):
            return A @ A.T

        A = jnp.ones((16, 16))
        f(A)
        with jtu.count_jit_and_pmap_lowerings() as lowerings:
            f(A)
        assert lowerings() == 0
        assert engine.stats().hits == 1

    def test_key_fn_extras_distinguish_closures(self, fresh_engine):
        """Two closures with the same code but different collaborators
        must key separately via key_fn — and identical collaborators
        must share one executable even across wrapper objects."""

        def make(scale):
            def f(A):
                return A * scale

            return engine.compiled(f, name="scaled",
                                   key_fn=lambda *a: (scale,))

        A = jnp.ones((4,))
        assert float(make(2.0)(A)[0]) == 2.0
        assert float(make(3.0)(A)[0]) == 3.0   # different extra: miss
        assert float(make(2.0)(A)[0]) == 2.0   # same extra, new wrapper: hit
        s = engine.stats()
        assert s.misses == 2 and s.hits == 1

    def test_donation_explicit_consumes_operand(self, fresh_engine):
        @engine.compiled(donate_argnums=(0,))
        def f(A):
            return A + 1

        A = jnp.ones((32,))
        f(A)
        with pytest.raises(RuntimeError, match="deleted"):
            _ = A + 1

    def test_auto_donation_off_by_default(self, fresh_engine, monkeypatch):
        monkeypatch.delenv("SKYLARK_ENGINE_DONATE", raising=False)

        @engine.compiled(donate_argnums=(0,), donate="auto")
        def f(A):
            return A + 1

        A = jnp.ones((32,))
        f(A)
        _ = A + 1  # still alive: auto-donation requires the opt-in

    def test_auto_donation_opt_in(self, fresh_engine, monkeypatch):
        @engine.compiled(donate_argnums=(0,), donate="auto")
        def f(A):
            return A + 1

        f(jnp.ones((32,)))  # compiled without donation
        monkeypatch.setenv("SKYLARK_ENGINE_DONATE", "1")
        A = jnp.ones((32,))
        f(A)  # donation flag is part of the key: fresh executable, no thrash
        with pytest.raises(RuntimeError, match="deleted"):
            _ = A + 1
        s = engine.stats()
        assert s.misses == 2 and s.recompiles == 0

    def test_digest_tracks_serialization(self):
        ctx = Context(seed=9)
        from libskylark_tpu import sketch as sk

        t1 = sk.JLT(64, 8, Context(seed=9))
        t2 = sk.JLT(64, 8, Context(seed=9))   # same (seed, counter=0)
        t3 = sk.JLT(64, 8, ctx)
        t4 = sk.JLT(64, 8, ctx)               # counter advanced: differs
        assert engine.digest(t1) == engine.digest(t2)
        assert engine.digest(t3) != engine.digest(t4)

    def test_stats_dump(self, fresh_engine, tmp_path):
        @engine.compiled
        def f(A):
            return A + 1

        f(jnp.ones((4,)))
        path = tmp_path / "engine_stats.json"
        engine.dump_stats(str(path))
        import json

        doc = json.loads(path.read_text())
        assert doc["stats"]["misses"] == 1
        assert doc["cache_size"] == 1
        assert doc["entries"][0]["calls"] == 1


class TestPlanFingerprintKey:
    def test_plan_edit_recompiles_exactly_once(self, fresh_engine,
                                               scratch_plan_cache):
        """Tentpole acceptance: a cached-plan change triggers exactly
        one recompile of an engine-served solver; a no-op write (same
        plan re-recorded with a better measurement) triggers none."""
        A = jnp.asarray(
            np.random.default_rng(0).standard_normal((96, 48)),
            jnp.float32)
        p = nla.ApproximateSVDParams(num_iterations=1)

        def solve():
            return nla.approximate_svd(A, 4, Context(seed=7), p)

        solve()
        solve()
        s = engine.stats()
        assert (s.misses, s.hits) == (1, 1)

        w = tune.dense_workload("normal", (96, 48), "float32", 8,
                                seq_axis=1)
        scratch_plan_cache.put(w, tune.Plan("pallas", m_tile=128,
                                            precision="f32"))
        solve()                       # plan changed: exactly one compile
        solve()                       # and it sticks
        s = engine.stats()
        assert (s.misses, s.hits) == (2, 2)

        # re-recording the SAME plan with a measurement value is not a
        # plan change — the fingerprint hashes plans, not metadata
        scratch_plan_cache.record_measurement(
            w, tune.Plan("pallas", m_tile=128, precision="f32"), 42.0)
        solve()
        s = engine.stats()
        assert (s.misses, s.hits) == (2, 3)
        assert s.recompiles == 0

    def test_fingerprint_stable_and_content_keyed(self, scratch_plan_cache):
        fp0 = scratch_plan_cache.fingerprint()
        assert fp0 == scratch_plan_cache.fingerprint()
        w = tune.dense_workload("normal", (64, 64), "float32", 16,
                                seq_axis=1)
        scratch_plan_cache.put(w, tune.Plan("xla"))
        assert scratch_plan_cache.fingerprint() != fp0


class TestExecutableCacheLRU:
    def _entry(self, name="e"):
        return CacheEntry(executable=None, name=name, compile_seconds=0.0)

    def test_eviction_and_thrash_counter(self):
        c = ExecutableCache(maxsize=2)
        for k in ("a", "b"):
            assert c.lookup(k) is None
            c.insert(k, self._entry(k))
        assert c.lookup("a") is not None        # refresh a; b is now LRU
        assert c.lookup("c") is None
        c.insert("c", self._entry("c"))         # evicts b
        assert c.stats.evictions == 1
        assert c.lookup("b") is None            # thrash: seen before
        assert c.stats.recompiles == 1
        assert len(c) == 2

    def test_reset_clears_seen(self):
        c = ExecutableCache(maxsize=4)
        c.lookup("a")
        c.insert("a", self._entry())
        c.reset()
        assert c.lookup("a") is None
        assert c.stats.recompiles == 0          # fresh slate, not thrash


class TestCacheThreadSafety:
    """The serve executor made the cache multi-threaded for the first
    time: misses must be single-flight (N racing threads on one cold
    key = ONE compile), counter increments must never be lost, and the
    LRU order must survive concurrent mutation."""

    def test_concurrent_calls_single_flight(self, fresh_engine):
        @engine.compiled
        def f(A):
            return A * 2.0 + 1.0

        A = jnp.ones((32, 32))
        n_threads, per = 8, 25
        barrier = threading.Barrier(n_threads)
        errs = []

        def worker():
            try:
                barrier.wait()
                for _ in range(per):
                    f(A)
            except Exception as e:  # noqa: BLE001
                errs.append(e)

        threads = [threading.Thread(target=worker)
                   for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        assert not errs and not any(t.is_alive() for t in threads)
        s = engine.stats()
        total = n_threads * per
        # single-flight: exactly one compile; no increment was lost
        assert s.misses == 1
        assert s.hits == total - 1
        assert s.executions == total
        assert s.recompiles == 0
        assert len(engine.cache()) == 1

    def test_concurrent_distinct_keys_lru_integrity(self):
        c = ExecutableCache(maxsize=4)
        n_threads, per, n_keys = 8, 200, 16
        barrier = threading.Barrier(n_threads)

        def worker(tid):
            barrier.wait()
            for i in range(per):
                k = (tid * per + i) % n_keys
                entry = c.acquire(k)
                if entry is None:
                    c.insert(k, CacheEntry(executable=None, name=str(k),
                                           compile_seconds=0.0))

        threads = [threading.Thread(target=worker, args=(t,))
                   for t in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        assert not any(t.is_alive() for t in threads)
        assert len(c) <= 4
        s = c.stats
        # every lookup resolved to a hit or an owned miss, none dropped
        assert s.hits + s.misses == n_threads * per
        # every miss became exactly one insert; evictions account for
        # all inserts beyond capacity — a corrupted OrderedDict would
        # break this identity
        assert s.evictions == s.misses - len(c)

    def test_compile_failure_releases_waiters(self, fresh_engine):
        @engine.compiled
        def bad(A):
            raise ValueError("boom at trace time")

        A = jnp.ones((8,))
        n_threads = 6
        barrier = threading.Barrier(n_threads)
        outcomes = []

        def worker():
            barrier.wait()
            try:
                bad(A)
                outcomes.append("ok")
            except ValueError:
                outcomes.append("raised")

        threads = [threading.Thread(target=worker)
                   for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        # an aborted compile must release its waiters (no deadlock) and
        # every caller sees the failure
        assert not any(t.is_alive() for t in threads)
        assert outcomes == ["raised"] * n_threads
        # a failed compile never enters `seen`: retries are plain
        # misses, not thrash
        assert engine.stats().recompiles == 0

        @engine.compiled
        def good(A):
            return A + 1

        assert float(good(A)[0]) == 2.0   # cache still serviceable


class TestPersistentCacheWiring:
    def test_enable_persistent_cache(self, tmp_path, monkeypatch):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        prev = jax.config.jax_compilation_cache_dir
        try:
            assert engine.enable_persistent_cache(str(tmp_path))
            assert jax.config.jax_compilation_cache_dir == str(tmp_path)
        finally:
            jax.config.update("jax_compilation_cache_dir", prev)

    def test_cache_placed_from_outside_is_left_alone(self, tmp_path,
                                                     monkeypatch):
        """JAX_COMPILATION_CACHE_DIR set: jax's own reading stands; no
        code path may point the cache anywhere else."""
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/outside")
        prev = jax.config.jax_compilation_cache_dir
        assert engine.enable_persistent_cache(str(tmp_path))
        assert jax.config.jax_compilation_cache_dir == prev

    def test_disabled_values(self, monkeypatch):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert not engine.enable_persistent_cache("0")
        assert not engine.enable_persistent_cache("")
