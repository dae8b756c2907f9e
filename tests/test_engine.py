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

from libskylark_tpu import Context, engine, nla
from libskylark_tpu.engine.cache import CacheEntry, ExecutableCache


@pytest.fixture()
def fresh_engine():
    engine.reset()
    yield
    engine.reset()


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


class TestKeyIsTheProgramAndItsArguments:
    """A compiled program's key is a function of the program and its
    arguments alone: nothing outside the process — the file a plan
    cache used to live in least of all — can re-key a warm program."""

    _PLAN = ('{"schema": 1, "entries": {"cpu|dense_rowwise|normal|float32|'
             '128x64x8": {"plan": {"backend": "pallas", "m_tile": 128}, '
             '"source": "measured"}}}')

    @pytest.mark.parametrize("before,after", [
        (None, _PLAN), (_PLAN, '{"schema": 1, "entries": {}}'),
        (_PLAN, "not json"), (_PLAN, None)],
        ids=["appears", "emptied", "corrupted", "vanishes"])
    def test_equal_arguments_hit_whatever_sits_at_the_old_plan_path(
            self, fresh_engine, tmp_path, monkeypatch, before, after):
        path = tmp_path / "plan_cache.json"
        # the removed knob, spelled from the file it named so that a
        # grep of the tree for removed names finds test_analysis alone
        monkeypatch.setenv("SKYLARK_" + path.stem.upper(), str(path))
        A = jnp.asarray(
            np.random.default_rng(0).standard_normal((96, 48)),
            jnp.float32)
        p = nla.ApproximateSVDParams(num_iterations=1)

        def solve(content):
            if content is None:
                path.unlink(missing_ok=True)
            else:
                path.write_text(content)
            return nla.approximate_svd(A, 4, Context(seed=7), p)

        solve(before)
        solve(after)
        s = engine.stats()
        assert (s.misses, s.hits, s.recompiles) == (1, 1, 0)

    def test_key_anatomy(self, fresh_engine):
        import jax

        @engine.compiled(static_argnames=("k",), name="anatomy")
        def f(A, *, k):
            return A + k

        f(jnp.ones((4,)), k=2)
        (key,) = engine.cache().keys()
        (name, code, statics, extra, avals, shardings, donated,
         precision, backend) = key
        assert name == "anatomy" and extra == () and donated == ()
        assert "k" in repr(statics) and len(avals) == len(shardings) == 1
        assert backend == jax.default_backend()


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
