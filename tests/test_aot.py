"""Persistent AOT executable artifacts, warmup packs, and cross-process
single-flight (libskylark_tpu/engine/aot.py + engine/warmup.py).

Oracles:

- *load-instead-of-compile*: a key compiled once under
  ``SKYLARK_AOT_DIR`` resolves in a later "process" (simulated by
  ``engine.reset()`` in-process, and by real subprocesses in the race
  test) as an ``aot_load`` with ZERO backend compiles, bit-equal.
- *fail-open*: a corrupted / compat-mismatched / foreign artifact is
  counted (``aot_load_failures``), warned once, and falls back to a
  fresh compile — never an exception on the serve path.
- *cross-process single-flight*: N racing cold processes on one key
  perform exactly ONE backend compile fleet-wide (file lock, with
  stale-lock takeover when the holder died).
- *warmup packs*: a pack built in one engine era boots a fresh era
  serving every packed bucket with zero compiles, zero misses, results
  bit-equal to the builder's; plan-fingerprint drift and compat
  mismatches skip the pack instead of mis-serving it.
"""

from __future__ import annotations

import json
import os
import re
import struct
import subprocess
import sys
import textwrap
import threading
import time

import numpy as np
import pytest

import jax.numpy as jnp

from libskylark_tpu import engine
from libskylark_tpu.engine import aot
from libskylark_tpu.engine import warmup


@pytest.fixture()
def fresh_engine():
    engine.reset()
    yield
    engine.reset()


@pytest.fixture()
def aot_store(tmp_path, monkeypatch):
    d = str(tmp_path / "store")
    monkeypatch.setenv("SKYLARK_AOT_DIR", d)
    return d


def _double(x):
    return x * 2.0 + 1.0


def _wrapped(tag: str):
    return engine.compiled(_double, name=f"aot.test.{tag}",
                           key_fn=lambda *a: (tag,))


def _artifacts(store):
    if not os.path.isdir(store):
        return []
    return sorted(f for f in os.listdir(store) if f.endswith(".skyaot"))


class TestArtifactStore:
    def test_load_instead_of_compile_bit_equal(self, fresh_engine,
                                               aot_store):
        cf = _wrapped("roundtrip")
        x = jnp.arange(12, dtype=jnp.float32)
        r1 = np.asarray(cf(x))
        s = engine.stats()
        assert (s.misses, s.compiles, s.aot_loads) == (1, 1, 0)
        assert len(_artifacts(aot_store)) == 1
        engine.reset()                      # "a fresh process"
        r2 = np.asarray(cf(x))
        s = engine.stats()
        assert (s.misses, s.compiles, s.aot_loads) == (1, 0, 1)
        assert s.load_seconds > 0.0 and s.compile_seconds == 0.0
        assert np.array_equal(r1, r2)

    def test_disabled_without_env(self, fresh_engine, tmp_path,
                                  monkeypatch):
        monkeypatch.delenv("SKYLARK_AOT_DIR", raising=False)
        monkeypatch.delenv("SKYLARK_EXEC_CACHE_DIR", raising=False)
        assert not aot.enabled()
        _wrapped("disabled")(jnp.ones(4))
        assert engine.stats().compiles == 1

    def test_off_value_disables_even_with_alias(self, monkeypatch,
                                                tmp_path):
        monkeypatch.setenv("SKYLARK_AOT_DIR", "0")
        monkeypatch.setenv("SKYLARK_EXEC_CACHE_DIR", str(tmp_path))
        assert aot.aot_dir() is None

    def test_legacy_alias_warns_once_and_subdirs(self, monkeypatch,
                                                 tmp_path):
        monkeypatch.delenv("SKYLARK_AOT_DIR", raising=False)
        monkeypatch.setenv("SKYLARK_EXEC_CACHE_DIR", str(tmp_path))
        monkeypatch.setattr(aot, "_alias_warned", False)
        with pytest.warns(DeprecationWarning, match="SKYLARK_AOT_DIR"):
            assert aot.aot_dir() == os.path.join(str(tmp_path), "aot")
        # second resolution is silent (one deprecation note per process)
        import warnings as _w

        with _w.catch_warnings():
            _w.simplefilter("error")
            assert aot.aot_dir() == os.path.join(str(tmp_path), "aot")

    def test_corrupted_artifact_falls_back_and_quarantines(
            self, fresh_engine, aot_store):
        cf = _wrapped("corrupt")
        x = jnp.ones(8, dtype=jnp.float32)
        r1 = np.asarray(cf(x))
        (name,) = _artifacts(aot_store)
        with open(os.path.join(aot_store, name), "wb") as fh:
            fh.write(b"not an artifact")
        engine.reset()
        with pytest.warns(RuntimeWarning, match="unusable"):
            r2 = np.asarray(cf(x))
        s = engine.stats()
        assert s.compiles == 1 and s.aot_loads == 0
        assert s.aot_load_failures == 1
        assert np.array_equal(r1, r2)
        # the broken file was quarantined and the fresh compile
        # re-persisted a good artifact under the canonical name
        assert _artifacts(aot_store) == [name]
        assert os.path.exists(os.path.join(aot_store, name + ".bad"))

    def test_compat_mismatch_falls_back_keeps_artifact(
            self, fresh_engine, aot_store):
        cf = _wrapped("compat")
        x = jnp.ones(6, dtype=jnp.float32)
        r1 = np.asarray(cf(x))
        (name,) = _artifacts(aot_store)
        path = os.path.join(aot_store, name)
        # rewrite the header with a foreign jax version, keeping the
        # pickle payload byte-identical
        with open(path, "rb") as fh:
            raw = fh.read()
        hlen = struct.unpack(">Q", raw[8:16])[0]
        header = json.loads(raw[16:16 + hlen])
        header["compat"]["jax"] = "0.0.0"
        hdr = json.dumps(header, sort_keys=True).encode()
        with open(path, "wb") as fh:
            fh.write(raw[:8] + struct.pack(">Q", len(hdr)) + hdr
                     + raw[16 + hlen:])
        engine.reset()
        r2 = np.asarray(cf(x))
        s = engine.stats()
        assert s.compiles == 1 and s.aot_load_failures == 1
        assert np.array_equal(r1, r2)
        # compat-mismatched artifacts are NOT quarantined: they are
        # valid for the runtime that wrote them... until the fresh
        # compile re-persists over the same digest (same runtime key)
        assert not os.path.exists(path + ".bad")

    def test_key_digest_and_compat_probe(self):
        k1 = ("a", ("b", 1), (2, "c"))
        assert aot.key_digest(k1) == aot.key_digest(("a", ("b", 1),
                                                     (2, "c")))
        assert aot.key_digest(k1) != aot.key_digest(("a", ("b", 2),
                                                     (2, "c")))
        ok, why = aot.compat_probe(aot.compat_stamp())
        assert ok and why is None
        bad = dict(aot.compat_stamp(), backend="tpu-imaginary")
        ok, why = aot.compat_probe(bad)
        assert not ok and "backend" in why
        assert aot.compat_probe(None) == (False, "no-compat-stamp")

    def test_persistent_cache_failure_observable(self, monkeypatch):
        import jax as _jax

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)

        # the package re-exports the same-named decorator, shadowing
        # the submodule attribute even for `import a.b.c as x`
        _c = sys.modules["libskylark_tpu.engine.compiled"]
        from libskylark_tpu import telemetry

        calls = telemetry.counter("engine.persistent_cache_failures")
        before = calls.value(reason="RuntimeError")

        def boom(*a, **kw):
            raise RuntimeError("no config for you")

        monkeypatch.setattr(_jax.config, "update", boom)
        with pytest.warns(RuntimeWarning, match="persistent compilation"):
            assert _c.enable_persistent_cache("/tmp/nowhere") is False
        assert calls.value(reason="RuntimeError") == before + 1


class TestFileLock:
    def test_exclusive_then_release(self, tmp_path):
        path = str(tmp_path / "k.lock")
        a = aot.FileLock(path)
        b = aot.FileLock(path, poll=0.01)
        assert a.acquire(timeout=1.0)
        assert not b.acquire(timeout=0.2)
        a.release()
        assert b.acquire(timeout=1.0)
        b.release()
        assert not os.path.exists(path)

    def test_dead_holder_takeover(self, tmp_path):
        path = str(tmp_path / "k.lock")
        import socket

        # a pid that is certainly not alive: a just-reaped child's
        child = subprocess.Popen(["sleep", "0"])  # noqa: S603,S607
        child.wait()
        with open(path, "w") as fh:
            json.dump({"pid": child.pid, "host": socket.gethostname(),
                       "t": time.time()}, fh)
        lk = aot.FileLock(path, stale_seconds=600.0, poll=0.01)
        t0 = time.monotonic()
        assert lk.acquire(timeout=5.0)
        assert time.monotonic() - t0 < 2.0   # takeover, not timeout
        lk.release()

    def test_age_takeover(self, tmp_path):
        path = str(tmp_path / "k.lock")
        import socket

        with open(path, "w") as fh:
            json.dump({"pid": os.getpid(),       # alive holder...
                       "host": socket.gethostname(),
                       "t": time.time()}, fh)
        old = time.time() - 60.0
        os.utime(path, (old, old))               # ...but long past stale
        lk = aot.FileLock(path, stale_seconds=5.0, poll=0.01)
        assert lk.acquire(timeout=5.0)
        lk.release()

    def test_thread_mutual_exclusion(self, tmp_path):
        path = str(tmp_path / "k.lock")
        inside = []
        overlaps = []

        def worker():
            lk = aot.FileLock(path, poll=0.005)
            for _ in range(5):
                assert lk.acquire(timeout=10.0)
                inside.append(1)
                if len(inside) > 1:
                    overlaps.append(True)
                time.sleep(0.002)
                inside.pop()
                lk.release()

        ts = [threading.Thread(target=worker) for _ in range(3)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert not overlaps


_RACE_CHILD = textwrap.dedent("""
    import json, os, sys, time
    sys.path.insert(0, {repo!r})
    sys.path.insert(0, {moddir!r})
    go = sys.argv[1]
    while not os.path.exists(go):
        time.sleep(0.005)
    from libskylark_tpu import engine
    import aot_race_fn, jax.numpy as jnp, numpy as np
    cf = engine.compiled(aot_race_fn.fn, name="aot.race",
                         key_fn=lambda *a: ("race",))
    out = np.asarray(cf(jnp.ones((32, 32), jnp.float32)))
    s = engine.stats()
    print(json.dumps({{"compiles": s.compiles, "aot_loads": s.aot_loads,
                       "failures": s.aot_load_failures,
                       "sum": float(out.sum())}}))
""")


class TestCrossProcessSingleFlight:
    def test_racing_cold_processes_compile_exactly_once(self, tmp_path):
        """The acceptance criterion: N cold replicas racing on one key
        perform exactly one backend compile fleet-wide — the winner
        compiles under the file lock and serializes; the waiters block
        on the lock, then LOAD the winner's artifact."""
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        (tmp_path / "aot_race_fn.py").write_text(
            "import jax.numpy as jnp\n"
            "def fn(x):\n"
            "    return (x @ x.T).sum(axis=0) * 3.0\n")
        child_py = tmp_path / "child.py"
        child_py.write_text(_RACE_CHILD.format(repo=repo,
                                               moddir=str(tmp_path)))
        store = tmp_path / "store"
        go = tmp_path / "go.flag"
        env = dict(os.environ, SKYLARK_AOT_DIR=str(store),
                   JAX_PLATFORMS="cpu")
        procs = [subprocess.Popen(
            [sys.executable, str(child_py), str(go)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env) for _ in range(3)]
        time.sleep(0.5)       # let all three reach the barrier
        go.touch()
        outs = []
        for p in procs:
            stdout, stderr = p.communicate(timeout=240)
            assert p.returncode == 0, stderr[-800:]
            outs.append(json.loads(stdout.strip().splitlines()[-1]))
        assert sum(o["compiles"] for o in outs) == 1
        assert sum(o["aot_loads"] for o in outs) == 2
        assert all(o["failures"] == 0 for o in outs)
        assert len({o["sum"] for o in outs}) == 1
        # the lock is gone, the artifact remains
        files = os.listdir(store)
        assert [f for f in files if f.endswith(".skyaot")]
        assert not [f for f in files if f.endswith(".lock")]


def _pack_specs():
    return [
        warmup.BucketSpec(endpoint="sketch_apply", family="JLT",
                          n=120, m=28, s_dim=32, rowwise=True,
                          capacities=(1, 2)),
        warmup.BucketSpec(endpoint="sketch_apply", family="CWT",
                          n=48, m=6, s_dim=16, rowwise=False,
                          capacities=(2,)),
    ]


class TestWarmupPack:
    def test_build_then_boot_zero_compiles_bit_equal(self, fresh_engine,
                                                     tmp_path):
        pack = str(tmp_path / "pack")
        manifest = warmup.build_pack(pack, _pack_specs())
        assert len(manifest["entries"]) == 3
        assert all(e["kernel"] for e in manifest["entries"])
        assert all(e.get("results_digest") for e in manifest["entries"])
        assert not any(e.get("artifact_missing")
                       for e in manifest["entries"])
        # cold control first: same cohorts, no pack -> compiles
        engine.reset()
        cold = warmup.serve_probe(pack, load=False)
        assert cold["engine"]["compiles"] == 3
        assert cold["bit_equal"], cold["mismatches"]
        # the boot under test: fresh era + pack -> zero compiles,
        # zero misses (every first request a HIT), all loads
        engine.reset()
        warm = warmup.serve_probe(pack, load=True)
        assert warm["warmup"]["loaded"] == 3
        assert warm["warmup"]["kernel_restored"] == 3
        assert warm["engine"]["compiles"] == 0
        assert warm["engine"]["misses"] == 0
        assert warm["engine"]["aot_loads"] == 3
        assert warm["bit_equal"], warm["mismatches"]

    def test_old_schema_pack_is_reported_skipped(self, fresh_engine,
                                                 tmp_path, capsys):
        """A pack built before schema 2 keyed its entries on a part the
        key no longer has: none could ever hit, so the loader says so
        instead of loading artifacts nothing will ask for."""
        from libskylark_tpu.cli import skylark_warmup

        pack = str(tmp_path / "pack")
        warmup.build_pack(pack, _pack_specs()[:1])
        manifest = warmup.read_manifest(pack)
        assert manifest["schema"] == warmup.PACK_SCHEMA == 2
        assert sorted(manifest) == ["compat", "created", "entries",
                                    "max_batch", "pad_floor", "schema"]
        manifest["schema"] = 1
        with open(os.path.join(pack, warmup.MANIFEST), "w") as fh:
            json.dump(manifest, fh)
        engine.reset()
        report = warmup.load_pack(pack)
        assert report["loaded"] == 0
        assert report["skipped"] == "schema 1 != 2"
        assert sorted(report) == ["entries", "failed", "kernel_restored",
                                  "loaded", "resident", "skipped"]
        with pytest.raises(RuntimeError, match="schema 1 != 2"):
            warmup.load_pack(pack, strict=True)
        assert skylark_warmup.main(["inspect", "--pack", pack]) == 1
        said = json.loads(capsys.readouterr().out)
        assert said["compat_reason"] == "schema 1 != 2"
        assert not said["compat_ok_here"]

    def test_compat_mismatch_skips_pack(self, fresh_engine, tmp_path):
        pack = str(tmp_path / "pack")
        warmup.build_pack(pack, _pack_specs()[:1])
        manifest = warmup.read_manifest(pack)
        manifest["compat"]["device_count"] = 4096
        with open(os.path.join(pack, warmup.MANIFEST), "w") as fh:
            json.dump(manifest, fh)
        engine.reset()
        report = warmup.load_pack(pack)
        assert report["loaded"] == 0
        assert report["skipped"].startswith("compat:")

    def test_missing_pack_degrades(self, tmp_path):
        report = warmup.load_pack(str(tmp_path / "nope"))
        assert report["loaded"] == 0 and report["skipped"]

    def test_kernel_token_parse_and_restore(self, fresh_engine):
        """A token is a backend's name; anything else — an older
        tree's ``pallas/mt128/f32`` among them — declines to live
        resolution, which then answers by the rule."""
        ex = engine.MicrobatchExecutor(max_batch=2, linger_us=500)
        try:
            statics = ("sketch_apply", "CWT", "None", 16, False,
                       "float32", (64, 8))
            assert ex.restore_kernel_choice(statics, 2, "xla")
            assert ex._kernel_memo[(statics, 2)] == ("xla", "pack", None)
            assert ex.restore_kernel_choice(statics, 4, "pallas")
            assert ex._kernel_memo[(statics, 4)] == (
                "pallas", "pack", None)
            for old in ("pallas/mt128/f32", "pallas/mt128/pipe",
                        "garbage!"):
                assert not ex.restore_kernel_choice(statics, 8, old)
            assert (statics, 8) not in ex._kernel_memo
        finally:
            ex.shutdown()

    def test_explicit_kernel_pin_outranks_pack(self, fresh_engine,
                                               monkeypatch):
        """An operator pin (executor ``kernel=`` arg or
        SKYLARK_SERVE_KERNEL) must not be overridden by a pack's
        recorded decision — restore declines, live resolution rules."""
        statics = ("sketch_apply", "CWT", "None", 16, False,
                   "float32", (64, 8))
        ex = engine.MicrobatchExecutor(max_batch=2, linger_us=500,
                                       kernel="xla")
        try:
            assert not ex.restore_kernel_choice(statics, 2, "pallas")
            assert not ex._kernel_memo
        finally:
            ex.shutdown()
        monkeypatch.setenv("SKYLARK_SERVE_KERNEL", "xla")
        ex = engine.MicrobatchExecutor(max_batch=2, linger_us=500)
        try:
            assert not ex.restore_kernel_choice(statics, 2, "xla")
            assert not ex._kernel_memo
        finally:
            ex.shutdown()

    def test_second_load_skips_resident_keys(self, fresh_engine,
                                             tmp_path):
        """A second thread replica booting from the same pack finds
        every key resident: no second deserialize, no aot_loads
        inflation — only its own kernel memo gets seeded."""
        pack = str(tmp_path / "pack")
        warmup.build_pack(pack, _pack_specs()[:1])
        engine.reset()
        r1 = warmup.load_pack(pack)
        assert r1["loaded"] >= 1 and r1["resident"] == 0
        loads_after_first = engine.stats().aot_loads
        ex = engine.MicrobatchExecutor(max_batch=2, linger_us=500)
        try:
            r2 = warmup.load_pack(pack, executors=(ex,))
            assert r2["loaded"] == 0
            assert r2["resident"] == r1["loaded"]
            assert r2["failed"] == 0
            assert r2["kernel_restored"] >= 1
            assert engine.stats().aot_loads == loads_after_first
        finally:
            ex.shutdown()

    def test_cli_builds_from_specs_alone(self, fresh_engine, tmp_path,
                                         capsys):
        from libskylark_tpu.cli import skylark_warmup

        pack = str(tmp_path / "pack")
        with pytest.raises(SystemExit):       # --spec is required
            skylark_warmup.main(["build", "--pack", pack])
        capsys.readouterr()
        spec = json.dumps(_pack_specs()[0].to_dict())
        assert skylark_warmup.main(
            ["build", "--pack", pack, "--spec", spec]) == 0
        built = json.loads(capsys.readouterr().out)
        assert built["entries"] == 2 and not built["artifact_missing"]
        assert sorted(built) == ["artifact_missing", "compat", "entries",
                                 "pack"]
        assert skylark_warmup.main(["inspect", "--pack", pack]) == 0
        said = json.loads(capsys.readouterr().out)
        assert said["schema"] == 2 and said["compat_ok_here"]
        assert {e["kernel"] for e in said["entries"]} == {"xla"}

    def test_artifact_headers_readable_without_unpickle(
            self, fresh_engine, tmp_path):
        pack = str(tmp_path / "pack")
        warmup.build_pack(pack, _pack_specs()[:1])
        arts = aot.list_artifacts(os.path.join(pack, "artifacts"))
        assert len(arts) == 2
        for h in arts:
            assert h["name"] == "serve.sketch_apply"
            assert h["compat"]["backend"] == "cpu"
            # the pickled key never executed: list_artifacts reads
            # headers only (pickle.loads would need jax state)
            assert "key_repr" in h


class TestEnvPropagation:
    def test_snapshot_and_apply(self, monkeypatch):
        from libskylark_tpu.fleet import replica as replica_mod

        monkeypatch.setenv("SKYLARK_AOT_DIR", "/tmp/a")
        monkeypatch.setenv("SKYLARK_SERVE_KERNEL", "pallas")
        monkeypatch.delenv("SKYLARK_TELEMETRY_DIR", raising=False)
        snap = replica_mod.propagated_env()
        assert snap["SKYLARK_AOT_DIR"] == "/tmp/a"
        assert snap["SKYLARK_SERVE_KERNEL"] == "pallas"
        assert snap["SKYLARK_TELEMETRY_DIR"] is None
        # the parent moves on; the child still applies the snapshot
        monkeypatch.setenv("SKYLARK_AOT_DIR", "/tmp/CHANGED")
        monkeypatch.setenv("SKYLARK_TELEMETRY_DIR", "/tmp/t")
        replica_mod._apply_env(snap)
        assert os.environ["SKYLARK_AOT_DIR"] == "/tmp/a"
        assert "SKYLARK_TELEMETRY_DIR" not in os.environ

    def test_apply_none_is_noop(self):
        from libskylark_tpu.fleet import replica as replica_mod

        replica_mod._apply_env(None)


class TestTelemetryRendering:
    def test_aot_counters_prometheus_rendered(self, fresh_engine,
                                              aot_store):
        """Satellite: the ``aot_loads`` / ``aot_load_failures`` /
        ``load_seconds`` split shows up on the unified Prometheus
        surface (engine collector block flattened to gauges)."""
        from libskylark_tpu import telemetry

        @engine.compiled(name="aot.test.prom")
        def f(x):
            return x * 3.0

        x = jnp.arange(6.0, dtype=jnp.float32)
        f(x)                      # compile + persist
        engine.reset()
        f(x)                      # fresh era: artifact load
        s = engine.stats()
        assert s.aot_loads == 1 and s.compiles == 0
        text = telemetry.prometheus_text()
        assert "skylark_engine_stats_aot_loads 1" in text
        assert "skylark_engine_stats_aot_load_failures 0" in text
        assert "skylark_engine_stats_load_seconds" in text
        assert "skylark_engine_stats_compiles 0" in text
        # lifetime rollup carries the pre-reset compile (>= because
        # the rollup is reset-proof across the whole test session)
        m = re.search(r"skylark_engine_lifetime_compiles (\d+)", text)
        assert m and int(m.group(1)) >= 1


@pytest.mark.slow
class TestProcessReplicaPackBoot:
    def test_child_env_explicit_and_zero_compile_boot(
            self, fresh_engine, tmp_path, monkeypatch):
        """Satellite regression: a spawn child applies the parent's
        EXPLICIT engine-environment snapshot (not whatever os.environ
        held at Process.start), loads the warmup pack before accepting
        traffic, and serves the packed bucket bit-equal with ZERO
        backend compiles — the acceptance criterion's ProcessReplica
        leg."""
        from libskylark_tpu import fleet
        from libskylark_tpu import sketch as sk

        spec = warmup.BucketSpec(endpoint="sketch_apply", family="CWT",
                                 n=48, m=6, s_dim=16, rowwise=False,
                                 capacities=(1,))
        pack = str(tmp_path / "pack")
        manifest = warmup.build_pack(pack, [spec])
        assert manifest["entries"]

        store_a = str(tmp_path / "store_a")
        monkeypatch.setenv("SKYLARK_AOT_DIR", store_a)
        env = fleet.propagated_env()
        assert env["SKYLARK_AOT_DIR"] == store_a
        # poison os.environ AFTER the snapshot: without explicit
        # propagation the child would inherit this by spawn accident
        monkeypatch.setenv("SKYLARK_AOT_DIR", str(tmp_path / "WRONG"))

        r = fleet.ProcessReplica(
            "packed", warmup_pack=pack, env=env,
            max_batch=int(manifest["max_batch"]), linger_us=1000)
        try:
            info = r.boot_info()
            assert info["env"]["SKYLARK_AOT_DIR"] == store_a
            wrep = info["warmup"]
            assert wrep["skipped"] is None and wrep["failed"] == 0
            assert wrep["loaded"] == len(manifest["entries"])
            eng0 = info["engine"]
            assert eng0["compiles"] == 0
            assert eng0["aot_loads"] == len(manifest["entries"])

            # the canonical cohort, through the pipe: bit-equal to the
            # parent's sequential reference, still zero compiles
            (T, A) = warmup._spec_requests(spec, 1)[0]
            ref = np.asarray(T.apply(jnp.asarray(A), sk.COLUMNWISE))
            fut = r.submit("sketch_apply", transform=T, A=A,
                           dimension=sk.COLUMNWISE)
            r.flush()
            got = np.asarray(fut.result(timeout=120))
            assert np.array_equal(got, ref)
            eng1 = r.boot_info()["engine"]
            assert eng1["compiles"] == 0 and eng1["misses"] == 0
            assert eng1["hits"] >= 1
        finally:
            r.shutdown()
