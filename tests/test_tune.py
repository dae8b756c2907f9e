"""Autotuner subsystem tests (libskylark_tpu/tune/): plan-cache disk
round-trip, deterministic offline cost ranking (including the r03
m-tile ordering reproduced with zero TPU access), and what the eager
dispatch in sketch/ does beside it — the cache never steers it: the
argument beats the setter beats the default."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from libskylark_tpu import tune
from libskylark_tpu.base import randgen
from libskylark_tpu.base.context import Context
from libskylark_tpu.sketch import params as sketch_params
from libskylark_tpu.sketch import pallas_dense as pd

FLAGSHIP = (8192, 8192)     # the headline config's input shape
FLAGSHIP_S = 1024


@pytest.fixture
def injected_cache():
    """A fresh in-memory cache installed as the process-global one;
    restores the previous cache (and plan-cache gating) afterwards."""
    cache = tune.PlanCache(path=None)
    prev = tune.set_cache(cache)
    prev_gate = sketch_params.get_use_plan_cache()
    sketch_params.set_use_plan_cache(True)
    yield cache
    sketch_params.set_use_plan_cache(prev_gate)
    tune.set_cache(prev)


def _flagship_workload(device_kind="tpu_v5_lite"):
    return tune.dense_workload("normal", FLAGSHIP, "float32",
                               FLAGSHIP_S, seq_axis=1,
                               device_kind=device_kind)


class TestWorkloadAndPlans:
    def test_bucketing_is_pow2_and_key_stable(self):
        w1 = tune.dense_workload("normal", (100, 1000), "float32", 96, 1,
                                 device_kind="TPU v5 lite")
        w2 = tune.dense_workload("normal", (128, 1024), "float32", 128, 1,
                                 device_kind="tpu-v5-lite")
        # different concrete shapes in the same bucket, differently
        # spelled device kinds: one cache key
        assert w1.key() == w2.key()
        assert w1.bucket() == (128, 1024, 128)

    def test_plan_id_and_dict_roundtrip(self):
        p = tune.Plan("pallas", m_tile=512, precision="bf16x3")
        assert p.plan_id() == "pallas/mt512/bf16x3"
        assert tune.Plan.from_dict(p.to_dict()) == p
        assert tune.Plan.from_plan_id(p.plan_id()) == p
        # what an older tree stored for its pipelined-generation kernel
        # still parses, to the plan without it
        assert tune.Plan.from_dict(dict(p.to_dict(), pipeline=True)) == p
        assert tune.Plan.from_plan_id("pallas/mt512/bf16x3/pipe") == p
        assert tune.Plan.from_dict(tune.Plan("xla").to_dict()) == \
            tune.Plan("xla")

    def test_candidates_exclude_fast_regimes_by_default(self):
        w = _flagship_workload()
        precs = {p.precision for p in tune.enumerate_candidates(w)
                 if p.backend == "pallas"}
        assert precs == {"bf16x3", "f32"}
        fast = {p.precision
                for p in tune.enumerate_candidates(w, allow_fast=True)
                if p.backend == "pallas"}
        assert {"bf16", "bf16gen2"} <= fast


class TestCostRanking:
    def test_ranking_deterministic(self):
        w = _flagship_workload()
        first = [p.plan_id() for p, _ in tune.rank_candidates(w)]
        for _ in range(3):
            assert [p.plan_id()
                    for p, _ in tune.rank_candidates(w)] == first
        # order-independence of the candidate list
        cands = tune.enumerate_candidates(w)
        shuffled = list(reversed(cands))
        assert [p.plan_id()
                for p, _ in tune.rank_plans(w, shuffled)] == first

    def test_mtile_ordering(self):
        """With zero TPU access, the offline ranking orders the m-tiles
        (256, 512 at the bf16x3 regime) the way the chip
        does (sketch/params.py m-tile note; 23.4 against 24.8 ms at the
        cell's shape, PR 27): 512 over 256 — with the operator resident
        by the grid steps and the plane re-reads alone."""
        w = _flagship_workload()
        ranked = [p.plan_id() for p, _ in tune.rank_candidates(w)]
        i512 = ranked.index("pallas/mt512/bf16x3")
        i256 = ranked.index("pallas/mt256/bf16x3")
        assert i512 < i256

    def test_model_orders_headline_regimes(self):
        """The analytic model orders the regimes by MXU passes: bf16x3
        (3) ahead of f32 (6) at the flagship config."""
        w = _flagship_workload()
        c3 = tune.plan_cost(w, tune.Plan("pallas", 512, "bf16x3"))
        cf = tune.plan_cost(w, tune.Plan("pallas", 512, "f32"))
        assert c3["modeled_s"] < cf["modeled_s"]

    def test_autotune_topk(self):
        w = _flagship_workload()
        top = tune.autotune_topk(w, k=3)
        assert len(top) == 3
        assert all(p.backend == "pallas" for p in top)

    def test_fastfood_candidates_rank(self):
        w = tune.fastfood_workload("FastGaussianRFT", (16384, 4096),
                                   "float32", 4096,
                                   device_kind="tpu_v5_lite")
        ranked = [p.plan_id() for p, _ in tune.rank_candidates(w)]
        # the fused kernel's ~9x HBM-traffic advantage over the XLA
        # chain (BASELINE.md crossover) must order the backends
        assert ranked.index("fused/bf16x3") \
            < ranked.index("split/bf16x3") < ranked.index("xla_chain")


class TestOperatorResidencyOnePredicate:
    """Where the generated operator lives between m-tiles is decided in
    ONE place (``pallas_dense.operator_residency``); the cost model's
    generation count, the reported plan and the kernel call that is
    traced all read it, so they cannot disagree."""

    @pytest.mark.parametrize(
        "shape,s,m_tile,seq_axis,want",
        [((65536, 8192), 1024, 512, 1, "hbm"),       # the benchmark cell
         ((1024, 1024), 128, 256, 1, "vmem"),        # small S: VMEM cache
         ((512, 8192), 1024, 512, 1, "per_tile"),    # one m-tile
         ((8192, 65536), 1024, 512, 0, "hbm"),       # columnwise big S
         # nobody's request, under a v5e's cap: the planner's 2048 (PR 49)
         ((65536, 8192), 1024, None, 1, "hbm"),
         ((8192, 65536), 1024, None, 0, "hbm")],
        ids=["headline_hbm", "small_vmem", "single_tile", "columnwise",
             "headline_grown", "columnwise_grown"])
    def test_cost_plan_and_kernel_agree(self, shape, s, m_tile, seq_axis,
                                        want):
        import functools

        n, m = shape[seq_axis], shape[1 - seq_axis]
        # reader 1: the reported plan
        plan = pd.effective_plan(randgen.Normal(), shape, jnp.float32, s,
                                 seq_axis, m_tile=m_tile, interpret=True,
                                 vmem_cap=64 << 20)
        m_tile = m_tile or 2048
        m_tiles = m // m_tile
        assert pd.operator_residency(s, n, m, m_tile) == want
        assert plan["m_tile"] == m_tile
        assert (plan["vmem_limit_bytes"] > 0) == (m_tile == 2048)
        assert plan["operator_residency"] == want
        assert plan["operator_cache"] is (want == "vmem")
        # reader 2: the cost model generates once when resident
        w = tune.dense_workload("normal", shape, "float32", s, seq_axis,
                                device_kind="tpu_v5_lite")
        cost = tune.plan_cost(w, tune.Plan("pallas", m_tile, "bf16x3"))
        assert cost["gen_entries"] == float(
            n * s * (m_tiles if want == "per_tile" else 1))
        a_and_out = 4.0 * (m * n + m * s)
        assert cost["bytes"] == a_and_out + (
            4.0 * n * s * (1 + m_tiles) if want == "hbm" else 0.0)
        # reader 3: the call that is traced — a generation call plus a
        # contraction call under "hbm", one fused call otherwise
        call = pd._fused_call if seq_axis == 1 else pd._fused_call_cw
        traced = jax.make_jaxpr(functools.partial(
            call, s_dim=s, dist_kind="normal", m_tile=m_tile,
            precision="bf16x3", interpret=True))(
                jax.ShapeDtypeStruct(shape, jnp.float32),
                jax.ShapeDtypeStruct((n // 256, 2), jnp.uint32))
        assert str(traced).count("pallas_call") == (2 if want == "hbm"
                                                    else 1)


class TestPlanCacheDisk:
    def test_roundtrip_identical_dispatch_decisions(self, tmp_path):
        path = str(tmp_path / "plans.json")
        cache = tune.PlanCache(path)
        w1 = _flagship_workload()
        w2 = tune.fastfood_workload("FastGaussianRFT", (16384, 4096),
                                    "float32", 4096,
                                    device_kind="tpu_v5_lite")
        cache.put(w1, tune.Plan("pallas", 512, "bf16x3"),
                  source="measured", value=86.269)
        cache.put(w2, tune.Plan("fused", precision="bf16x3"),
                  source="ranked")
        assert cache.save()

        loaded = tune.PlanCache.load(path)
        for w in (w1, w2):
            assert loaded.lookup(w) == cache.lookup(w)
        assert loaded.entry(w1)["value"] == 86.269
        assert loaded.entry(w1)["source"] == "measured"

    def test_schema_mismatch_loads_empty_and_never_clobbers(
            self, tmp_path):
        path = tmp_path / "plans.json"
        path.write_text(json.dumps({"schema": 999, "entries": {
            "k": {"plan": {"backend": "pallas"}}}}))
        loaded = tune.PlanCache.load(str(path))
        assert loaded.entries == {}
        assert "schema" in (loaded.load_error or "")
        loaded.put(_flagship_workload(), tune.Plan("pallas", 256))
        assert loaded.save() is False  # never overwrite a newer schema
        assert json.loads(path.read_text())["schema"] == 999

    def test_corrupt_file_loads_empty(self, tmp_path):
        path = tmp_path / "plans.json"
        path.write_text("{not json")
        assert tune.PlanCache.load(str(path)).entries == {}

    def test_measured_only_replaced_by_better(self, tmp_path):
        cache = tune.PlanCache(str(tmp_path / "p.json"))
        w = _flagship_workload()
        p1 = tune.Plan("pallas", 512, "bf16x3")
        assert cache.record_measurement(w, p1, 80.0)
        # worse measurement: rejected
        assert not cache.record_measurement(
            w, tune.Plan("pallas", 256, "bf16x3"), 70.0)
        assert cache.lookup(w) == p1
        # better: accepted
        p2 = tune.Plan("pallas", 1024, "bf16x3")
        assert cache.record_measurement(w, p2, 90.0)
        assert cache.lookup(w) == p2

    def test_concurrent_writers_merge_instead_of_losing_updates(
            self, tmp_path):
        """Two processes certifying different workloads in one window:
        each loads before the other saves; the second save must MERGE
        the first writer's entry, not erase it with its stale
        snapshot — and a better measured value on disk must survive a
        worse in-memory one."""
        path = str(tmp_path / "p.json")
        w1, w2 = _flagship_workload(), tune.dense_workload(
            "normal", (1024, 1024), "float32", 128, 1,
            device_kind="tpu_v5_lite")

        a = tune.PlanCache.load(path)   # both load the empty file
        b = tune.PlanCache.load(path)
        a.path = b.path = path
        a.record_measurement(w1, tune.Plan("pallas", 512, "bf16x3"),
                             86.0)
        assert a.save()
        b.record_measurement(w2, tune.Plan("pallas", 256, "bf16x3"),
                             40.0)
        assert b.save()                  # must not drop a's w1 entry
        merged = tune.PlanCache.load(path)
        assert merged.lookup(w1) is not None
        assert merged.lookup(w2) is not None

        # stale worse measurement for the SAME key: disk's better wins
        c = tune.PlanCache.load(path)
        c.path = path
        c.entries[w1.key()] = {"plan": tune.Plan(
            "pallas", 128, "bf16x3").to_dict(), "source": "measured",
            "value": 10.0, "unit": "GB/s"}
        assert c.save()
        assert tune.PlanCache.load(path).entry(w1)["value"] == 86.0

    def test_disabled_persistence_path(self, monkeypatch):
        monkeypatch.setenv("SKYLARK_PLAN_CACHE", "0")
        assert tune.default_cache_path() is None
        monkeypatch.setenv("SKYLARK_PLAN_CACHE", "/tmp/custom.json")
        assert tune.default_cache_path() == "/tmp/custom.json"


class TestEagerDispatchIgnoresCache:
    """The eager applies in sketch/ never read the plan cache: the knobs
    are the call-site argument, else the sketch.params setter, else the
    default, whatever entry the cache holds for the workload."""

    SHAPE = (64, 1024)
    S = 96

    def _inject(self, cache):
        cache.put(tune.dense_workload("normal", self.SHAPE,
                                      jnp.dtype("float32"), self.S, 1),
                  tune.Plan("pallas", 16, "f32"),
                  source="measured", value=1.0)

    def test_effective_plan_heuristic_whatever_is_cached(
            self, injected_cache):
        self._inject(injected_cache)
        plan = pd.effective_plan(randgen.Normal(), self.SHAPE,
                                 jnp.float32, self.S, 1, interpret=True)
        assert plan["kernel"] and plan["plan_source"] == "heuristic"
        assert plan["m_tile"] == 64  # default 512 clamped to m
        assert plan["precision"] == "bf16x3"

    def test_explicit_arg_beats_setter(self, injected_cache):
        self._inject(injected_cache)
        sketch_params.set_pallas_m_tile(8)
        try:
            plan = pd.effective_plan(randgen.Normal(), self.SHAPE,
                                     jnp.float32, self.S, 1, m_tile=32,
                                     interpret=True)
        finally:
            sketch_params.set_pallas_m_tile(None)
        assert plan["m_tile"] == 32          # arg wins
        assert plan["plan_source"] == "arg"
        assert plan["precision"] == "bf16x3"  # open knob: the setter's

    def test_runtime_setter_beats_default(self, injected_cache):
        self._inject(injected_cache)
        sketch_params.set_pallas_m_tile(32)
        try:
            plan = pd.effective_plan(randgen.Normal(), self.SHAPE,
                                     jnp.float32, self.S, 1,
                                     interpret=True)
        finally:
            sketch_params.set_pallas_m_tile(None)
        assert plan["m_tile"] == 32
        assert plan["plan_source"] == "heuristic"


class TestFastfoodExplicitCall:
    def _transform(self):
        from libskylark_tpu.sketch.frft import FastGaussianRFT

        return FastGaussianRFT(512, 512, Context(seed=9), sigma=2.0)

    def _input(self):
        return jnp.asarray(np.random.default_rng(3).standard_normal(
            (32, 512)), jnp.float32)

    def test_explicit_call_reaches_kernel_whatever_is_cached(
            self, injected_cache):
        """The kernel is reached by an explicit call and by nothing
        else: a cached xla_chain entry for the workload does not decline
        it — otherwise a precision sweep silently measures the XLA chain
        under a kernel label."""
        from libskylark_tpu.sketch import pallas_fastfood as pf

        T, A = self._transform(), self._input()
        w = tune.fastfood_workload("FastGaussianRFT", A.shape, A.dtype,
                                   T._S)
        injected_cache.put(w, tune.Plan("xla_chain"), source="measured")
        pf.last_served_variant = None
        out = pf.features_rows(T, A, interpret=True, precision="f32",
                               variant="split")
        assert out is not None and pf.last_served_variant == "split"
        ref = T._features_rows(A)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-4)

    @pytest.mark.parametrize("variant,launcher",
                             [("fused", "_launch"),
                              ("split", "_launch_split")])
    def test_requested_variant_rejection_is_loud(self, variant, launcher,
                                                 monkeypatch):
        """An explicitly requested variant that Mosaic rejects must
        raise — never turn into the other variant or the XLA chain
        silently (on the chip a silent fallback serves a different
        program than the record and the caller believe)."""
        from libskylark_tpu.sketch import pallas_fastfood as pf

        T, A = self._transform(), self._input()
        monkeypatch.setattr(pf, "supported", lambda *a: True)
        monkeypatch.setattr(
            pf, launcher,
            lambda *a, **k: (_ for _ in ()).throw(
                RuntimeError("simulated Mosaic rejection")))
        with pytest.raises(RuntimeError, match="simulated Mosaic"):
            pf.features_rows(T, A, precision="f32", variant=variant)

    def test_transform_apply_takes_the_xla_chain(self, monkeypatch):
        """``FastRFT.apply`` does not reach the fused kernel: Mosaic
        rejects both its variants on a v5e, so the transform's own path
        is the one compiled program of ``frft.fastfood_features`` (PR 48),
        equal to the XLA chain to float32 tolerance (another summation
        order, ``cos_turns`` for the cosine)."""
        from libskylark_tpu.sketch import ROWWISE, COLUMNWISE
        from libskylark_tpu.sketch import pallas_fastfood as pf

        def no_kernel(*a, **k):
            raise AssertionError("FastRFT.apply reached the kernel")

        monkeypatch.setattr(pf, "features_rows", no_kernel)
        T, A = self._transform(), self._input()
        ref = np.asarray(T._features_rows(A))
        tol = 2e-5 * T.scale
        np.testing.assert_allclose(np.asarray(T.apply(A, ROWWISE)), ref,
                                   rtol=0, atol=tol)
        np.testing.assert_allclose(np.asarray(T.apply(A.T, COLUMNWISE)),
                                   ref.T, rtol=0, atol=tol)


class TestCostCalibration:
    """Measured calibration of the analytic cost model (tune/cost.py):
    ``cost_calib_<rate>`` ledger records overlay RATES for the matching
    host class, with provenance; the analytic model is the fallback;
    and calibration changes plan RANKING only when a measurement says
    so."""

    @pytest.fixture(autouse=True)
    def _fresh(self, monkeypatch):
        from libskylark_tpu.tune import cost

        monkeypatch.delenv("SKYLARK_COST_CALIB", raising=False)
        cost._calib_cache.clear()
        yield
        cost._calib_cache.clear()

    @staticmethod
    def _ledger(tmp_path, records, name="ledger.json"):
        p = tmp_path / name
        p.write_text("\n".join(
            r if isinstance(r, str) else json.dumps(r)
            for r in records) + "\n")
        return str(p)

    def test_unset_knob_is_pure_analytic(self):
        from libskylark_tpu.tune import cost

        assert cost.effective_rates() == cost.RATES
        prov = cost.rate_provenance()
        assert set(prov) == set(cost.RATES)
        assert all(v == {"source": "analytic"} for v in prov.values())

    def test_overlay_latest_wins_host_filter_junk_tolerance(
            self, tmp_path):
        from libskylark_tpu.tune import cost

        hc = cost._host_class()
        path = self._ledger(tmp_path, [
            "not json {",                                      # junk
            {"metric": "cost_calib_scatter_rows_per_s",
             "value": 1.0e9, "host_class": hc},                # older
            {"metric": "cost_calib_scatter_rows_per_s",
             "value": 7.7e8, "host_class": "tpu-v9-999c"},     # other host
            {"metric": "cost_calib_scatter_rows_per_s",
             "value": -5.0, "host_class": hc},                 # invalid
            {"metric": "cost_calib_no_such_rate",
             "value": 3.0, "host_class": hc},                  # unknown
            {"metric": "dist_serve_fanout_speedup",
             "value": 0.9, "host_class": hc},                  # not calib
            {"metric": "cost_calib_scatter_rows_per_s",
             "value": 2.5e9, "host_class": hc},                # winner
        ])
        rates = cost.effective_rates(path)
        assert rates["scatter_rows_per_s"] == 2.5e9
        # untouched rates stay analytic
        assert rates["mxu_flops_per_s"] == cost.RATES["mxu_flops_per_s"]
        prov = cost.rate_provenance(path)
        m = prov["scatter_rows_per_s"]
        assert m["source"] == "measured" and m["value"] == 2.5e9
        assert m["host_class"] == hc and m["line"] == 7
        assert prov["mxu_flops_per_s"] == {"source": "analytic"}

    def test_ranking_flips_only_under_a_measurement(self, tmp_path,
                                                    monkeypatch):
        from libskylark_tpu.tune import cost

        # the pinned workload: a huge-n hash sketch on tpu-v5e, where
        # the analytic scatter rate (1.2e9 rows/s) makes the scatter-
        # free pallas kernel win; a MEASURED scatter rate of 5e9 rows/s
        # says this host scatters fast enough that XLA wins instead
        w = tune.Workload(device_kind="tpu-v5e", op="hash_rowwise",
                          transform="CWT", dtype="float32",
                          shape=(32, 1 << 20, 256))
        plans = [tune.Plan("xla"), tune.Plan("pallas")]
        analytic = [p.backend for p, _ in cost.rank_plans(w, plans)]
        assert analytic == ["pallas", "xla"]

        # a measurement that AGREES with the analytic constant must
        # not change the ranking — calibration is not a reshuffle
        agree = self._ledger(tmp_path, [
            {"metric": "cost_calib_scatter_rows_per_s",
             "value": cost.RATES["scatter_rows_per_s"],
             "host_class": cost._host_class()}], name="agree.json")
        monkeypatch.setenv("SKYLARK_COST_CALIB", agree)
        assert [p.backend
                for p, _ in cost.rank_plans(w, plans)] == analytic

        flip = self._ledger(tmp_path, [
            {"metric": "cost_calib_scatter_rows_per_s",
             "value": 5.0e9,
             "host_class": cost._host_class()}], name="flip.json")
        monkeypatch.setenv("SKYLARK_COST_CALIB", flip)
        assert [p.backend for p, _ in cost.rank_plans(w, plans)] \
            == ["xla", "pallas"]

    def test_memo_invalidates_when_the_ledger_grows(self, tmp_path):
        from libskylark_tpu.tune import cost

        hc = cost._host_class()
        path = self._ledger(tmp_path, [
            {"metric": "cost_calib_scatter_rows_per_s",
             "value": 2.0e9, "host_class": hc}])
        assert cost.effective_rates(path)["scatter_rows_per_s"] == 2.0e9
        with open(path, "a") as fh:
            fh.write(json.dumps(
                {"metric": "cost_calib_scatter_rows_per_s",
                 "value": 3.0e9, "host_class": hc}) + "\n")
        assert cost.effective_rates(path)["scatter_rows_per_s"] == 3.0e9

    def test_missing_file_degrades_to_analytic(self, tmp_path,
                                               monkeypatch):
        from libskylark_tpu.tune import cost

        monkeypatch.setenv("SKYLARK_COST_CALIB",
                           str(tmp_path / "nope.json"))
        assert cost.effective_rates() == cost.RATES
        assert cost.rate_provenance()["scatter_rows_per_s"] \
            == {"source": "analytic"}
