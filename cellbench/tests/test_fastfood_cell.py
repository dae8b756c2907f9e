"""The cell ``fastfood_features_apply`` (Fastfood features for the Gaussian
kernel at CIFAR-10 widths) at tiny sizes on the CPU: the manifest entries,
the contract's keys, the controls and broken timed paths coming out not
correct (a bfloat16 program, a swapped Π, a dropped G), the counts against a
hand count, the reference against a dense explicit V, and the reader this PR
brought on a span ring the program filled. Nothing here is a device metric."""

import dataclasses
import importlib
import json
import math
import time
import types

import jax.numpy as jnp
import numpy as np
import pytest

from cellbench import harness, roofline
from cellbench.counts import fastfood_chain as counts
from cellbench.references import fastfood_features as reference

CELL = "fastfood_features_apply"
# the cell's shape at a size a CPU run holds: n = 48 pads to NB = 64 (ragged,
# as 3072 → 4096), s = 224 is three whole blocks and half a fourth. The
# statistics are restated for these widths: norm_dev ≈ 0.376·|z|/√(2s) =
# 1.8e-2·|z|; 64 dependent rows a block widen the kernel estimate's variance
# far more than 4096 do (about 4 ×, measured here).
TINY = {"n": 48, "s": 224, "sigma": 9.8, "rows_per_panel": 384, "check_rows": 64,
        "kernel_var_inflation": 4.0,
        "limits": {"rel_max": 1e-4, "norm_dev": 0.1, "kernel_z": 6.0,
                   "sign_mean_z": 6.0, "gauss_mean_z": 6.0, "gauss_var_z": 6.0,
                   "shift_chi2_z": 6.0, "perm_defect": 0.0}}


@pytest.fixture
def cell():
    whole = harness.load_cell(CELL)
    return dataclasses.replace(whole, config={**whole.config, **TINY})


def run(cell, seed=7, trace=False, step_wrapper=None, seconds=0.3):
    return harness.run_cell(cell, seed, seconds, trace, t_start=time.perf_counter(),
                            step_wrapper=step_wrapper)


def driver_of(cell):
    return importlib.import_module(f"cellbench.drivers.{cell.traffic['driver']}")


def test_result_has_exactly_the_contract_keys(cell, capsys):
    result = run(cell, seed=2**32 + 5)
    assert set(result) == {"correct", "attempted", "failed", "metrics", "device"}
    assert set(result["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(result["metrics"]) == {"apply_ms", "setup_s"}
    json.loads(json.dumps(result))
    log = capsys.readouterr().out
    for name in cell.config["limits"]:          # each number beside its limit
        assert f"check name={name} value=" in log and "limit=" in log
    assert "compile in_window=0" in log
    assert "dispatch route=fastfood_blocks kernel=xla_f32" in log


def test_the_manifest_entries():
    whole = harness.load_cell(CELL)
    assert whole.chips == 1 and whole.traffic["loop"] == "closed"
    assert whole.traffic["driver"] == "fastfood_apply"
    assert (whole.traffic["warm_steps"], whole.traffic["trace_seconds"]) == (4, 4)
    assert {m["name"] for m in whole.end_to_end} == {"apply_ms", "setup_s"}
    assert {m["name"] for m in whole.per_layer} == {
        "sketch_device_ms.apply", "sketch_roofline.apply", "device_idle.apply",
        "sketch_host_ms.apply", "stream_key_ms.apply", "sketch_dispatch_ms.apply",
        "feature_rate.apply", "setup_import_s", "setup_lower_s", "setup_compile_s",
        "chain_mix_rate.apply"}
    manifest = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    # new entries stand last in their lists; the new metric is this cell's
    assert manifest["workloads"][-1]["name"] == CELL
    assert manifest["configs"][-1]["name"] == whole.config_name
    assert manifest["per_layer"][-1] == {
        "name": "chain_mix_rate.apply", "unit": "Gelem/s", "better": "higher",
        "source": "device_trace", "layer": "sketch kernel", "moves": "apply_ms",
        "workloads": [CELL]}
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        if CELL in metric.get("workloads", []):
            assert metric["workloads"][-1] == CELL
    # the accepted feature cell keeps its own set
    assert "chain_mix_rate.apply" not in {
        m["name"] for m in harness.load_cell("rft_features_apply").per_layer}


def test_the_configuration_states_what_the_contract_asks():
    cfg = harness.load_cell(CELL).config
    assert (cfg["family"], cfg["tag"], cfg["fut"]) == ("FastGaussianRFT", "fast", "wht")
    assert (cfg["n"], cfg["s"], cfg["sigma"]) == (3072, 16384, 78.0)
    assert (cfg["rows_per_panel"], cfg["panels"], cfg["dimension"]) == (
        50000, 1, "rowwise")
    assert reference.geometry(cfg["n"], cfg["s"]) == (cfg["block_len"], cfg["blocks"])
    assert cfg["reduced"] == {}                 # nothing of the source is cut
    for key in ("source", "guarantees", "assumed", "deployment", "limits", "memory"):
        assert cfg[key]
    assert len(cfg["source"]) <= 200
    manifest = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in manifest["configs"] if c["name"] == "ffgrft_cifar10_d3072_s16384")
    assert entry["source"] == cfg["source"] and entry["reduced"] == []
    assert cfg["limits"]["rel_max"] == 1e-4 and cfg["limits"]["perm_defect"] == 0.0


def test_same_seed_same_operands_and_large_seeds_differ(cell):
    driver = driver_of(cell)
    a = driver.setup(cell.config, cell.traffic, 2**32 + 5)
    b = driver.setup(cell.config, cell.traffic, 2**32 + 5)
    other = driver.setup(cell.config, cell.traffic, 5)
    assert all(np.array_equal(x, y) for x, y in zip(a.panels, b.panels))
    assert a.context_seed == b.context_seed != other.context_seed
    assert not np.array_equal(a.panels[0], other.panels[0])
    assert a.panels[0].shape == (cell.config["rows_per_panel"], cell.config["n"])
    assert type(a.transform).__name__ == "FastGaussianRFT"


def test_the_driver_refuses_another_family(cell):
    driver = driver_of(cell)
    with pytest.raises(ValueError):
        driver.setup({**cell.config, "family": "GaussianRFT"}, cell.traffic, 1)
    with pytest.raises(ValueError):
        driver.setup({**cell.config, "fut": "dct"}, cell.traffic, 1)


# -- controls and broken timed paths ----------------------------------------


def test_the_bfloat16_reference_is_not_correct(cell):
    driver = driver_of(cell)

    def control(state, _step):
        return driver.controls(state)["reference_bf16"]

    assert run(cell)["correct"] is True
    assert run(cell, step_wrapper=control)["correct"] is False


def _program_with(state, **broken):
    """The program's own function on streams of a transform whose one part
    is broken: what a program with that fault would serve."""
    from libskylark_tpu.sketch import frft

    T = state.transform
    spec = (T.sketch_type, T._N, T._S, tuple(sorted(T._extra_params().items())))
    cls = type(T)
    saved = {name: getattr(cls, name) for name in broken}
    for name, method in broken.items():
        setattr(cls, name, method)
    try:
        def served(i):
            return frft.fastfood_features(
                T._alloc.key_data, state.panels[i % len(state.panels)],
                spec=spec, rowwise=True, kernel="xla_f32")
        outs = {i: served(i) for i in range(len(state.panels))}
    finally:
        for name, method in saved.items():
            setattr(cls, name, method)
    return lambda i: outs[i % len(state.panels)]


def _a_bfloat16_program(state, step):
    """Every Hadamard product of the program on an operand rounded to
    bfloat16: a single pass where the configuration states three."""
    from libskylark_tpu.sketch import fut

    real = fut.wht_blocks

    def one_pass(X, block, bf16_split=False):
        return real(X.astype(jnp.bfloat16).astype(jnp.float32), block, bf16_split)

    fut.wht_blocks = one_pass
    try:
        return _program_with(state)
    finally:
        fut.wht_blocks = real


def _a_swapped_permutation(state, step):
    """Π_k of another block in every block's place (still permutations)."""
    real = type(state.transform)._perms
    return _program_with(state, _perms=lambda self: jnp.roll(real(self), 1, axis=0))


def _a_dropped_g(state, step):
    real = type(state.transform)._G
    return _program_with(state, _G=lambda self, dt: jnp.ones_like(real(self, dt)))


@pytest.mark.parametrize("breaker", [
    _a_bfloat16_program, _a_swapped_permutation, _a_dropped_g])
def test_broken_timed_path_is_not_correct(cell, breaker):
    result = run(cell, step_wrapper=breaker)
    assert result["correct"] is False
    assert result["attempted"] > 0 and math.isfinite(result["metrics"]["setup_s"]["value"])


def test_the_check_reads_the_programs_own_streams(cell):
    """The laws are held on what the transform generates: a Π with a repeated
    entry is counted exactly, a G of the wrong scale fails its variance."""
    driver = driver_of(cell)
    state = driver.setup(cell.config, cell.traffic, 11)
    kept = [(0, driver.step(state, 0))]
    sound = driver.check(state, kept)
    assert all(sound[k] <= cell.config["limits"][k] for k in sound)
    cls = type(state.transform)
    perms, G = cls._perms, cls._G
    try:
        cls._perms = lambda self: perms(self).at[1, 5].set(perms(self)[1, 6])
        assert driver.check(state, kept)["perm_defect"] == 1.0
        cls._perms = perms
        cls._G = lambda self, dt: 1.5 * G(self, dt)
        assert driver.check(state, kept)["gauss_var_z"] > 6.0
    finally:
        cls._perms, cls._G = perms, G
    # features of half the bandwidth are sound cos values and fail the kernel
    parts = reference.streams(state.context_seed, 0, cell.config["n"], cell.config["s"])
    narrow = [(0, reference.features(state.panels[0], parts, cell.config["sigma"] / 2))]
    got = driver.check(state, narrow)
    assert got["kernel_z"] > 6.0 and got["rel_max"] > 1e-2


# -- the reference ----------------------------------------------------------


@pytest.mark.parametrize("n,s", [(48, 160), (48, 256), (64, 64), (100, 300)])
def test_reference_against_a_dense_explicit_v(n, s):
    sigma = math.sqrt(2.0 * n)
    X = jnp.asarray(np.random.default_rng(n + s).standard_normal((40, n)), jnp.float32)
    parts = reference.streams(9, 2, n, s)
    NB, nb = reference.geometry(n, s)
    assert parts["B"].shape == parts["G"].shape == parts["perms"].shape == (nb, NB)
    assert parts["shifts"].shape == (s,)
    assert all(sorted(np.asarray(p).tolist()) == list(range(NB)) for p in parts["perms"])
    V = reference.explicit_v(parts, sigma, n)
    assert V.shape == (s, n)
    dense = math.sqrt(2.0 / s) * np.cos(
        np.asarray(X, np.float64) @ V.T + np.asarray(parts["shifts"], np.float64))
    got = np.asarray(reference.features(X, parts, sigma), np.float64)
    assert np.abs(got - dense).max() / math.sqrt(2.0 / s) < 2e-5
    # and the control is one precision below, visibly
    low = np.asarray(reference.features(X, parts, sigma, "bf16"), np.float64)
    assert 1e-4 < np.abs(low - dense).max() / math.sqrt(2.0 / s) < 1e-1


def test_reference_features_estimate_the_exact_kernel():
    n, s, sigma = 256, 4096, math.sqrt(2.0 * 256)
    X = jnp.asarray(np.random.default_rng(2).standard_normal((48, n)), jnp.float32)
    parts = reference.streams(3, 0, n, s)
    Z = np.asarray(reference.features(X, parts, sigma), np.float64)
    K = np.asarray(reference.gaussian_kernel(X, sigma), np.float64)
    z = np.abs(Z @ Z.T - K) / np.sqrt(2.0 * (1 + 0.5 * K ** 4 - K * K) / s)
    assert z.max() < 6.0
    assert abs((Z * Z).sum() / 48 - 1.0) < 0.05
    laws = reference.law_z_scores(parts["B"], parts["G"], parts["shifts"],
                                  parts["perms"], 64)
    assert all(laws[k] < 6.0 for k in laws) and laws["perm_defect"] == 0.0


def test_the_hadamard_matrix_is_sylvesters():
    H = reference.hadamard(8)
    assert H[0].tolist() == [1.0] * 8 and H[1].tolist() == [1, -1] * 4
    np.testing.assert_array_equal(H @ H.T, 8 * np.eye(8))
    i, j = 5, 3                                  # popcount(5 & 3) = 1
    assert H[i, j] == -1.0
    with pytest.raises(ValueError):
        reference.hadamard(12)


def test_reference_imports_nothing_of_the_program():
    import ast
    import pathlib

    tree = ast.parse(pathlib.Path(reference.__file__).read_text())
    names = {a.name for node in ast.walk(tree) if isinstance(node, ast.Import)
             for a in node.names} | {node.module for node in ast.walk(tree)
                                     if isinstance(node, ast.ImportFrom)}
    assert not any(name.startswith("libskylark_tpu") for name in names)


# -- the counts -------------------------------------------------------------


def test_fastfood_counts_and_least_time():
    cfg = harness.load_cell(CELL).config
    work = counts.work(cfg)
    m, n, s, NB, nb = 50000, 3072, 16384, 4096, 4
    assert work == {"flops": m * (2 * nb * NB * 12 + 4 * nb * NB),
                    "bytes": (m * n + m * s) * 4, "transcendentals": m * s}
    assert work["flops"] == 22_937_600_000 and work["bytes"] == 3_891_200_000
    least, bound = roofline.least_time(work, roofline.peaks("TPU v5 lite"))
    assert bound == "hbm"                       # its operand and its result bind it
    assert least == pytest.approx(3.8912e9 / 819e9)
    assert work["flops"] / 197e12 < least / 30


def test_fastfood_counts_follow_the_configuration():
    small = counts.work({"rows_per_panel": 8, "n": 3, "s": 5})    # NB 4, 2 blocks
    assert small == {"flops": 8 * (2 * 2 * 4 * 2 + 4 * 2 * 4),
                     "bytes": (24 + 40) * 4, "transcendentals": 40}


# -- the reader this PR brought ----------------------------------------------


@pytest.fixture
def ring():
    from libskylark_tpu import telemetry
    from libskylark_tpu.telemetry import metrics, trace

    before = metrics._ENABLED
    trace.clear_finished()
    yield telemetry
    metrics._ENABLED = before
    trace.clear_finished()


def _applies(count, rows=16, n=48, s=160):
    from libskylark_tpu import Context
    from libskylark_tpu import sketch as sk

    X = jnp.asarray(np.random.default_rng(1).standard_normal((rows, n)), jnp.float32)
    T = sk.FastGaussianRFT(n, s, Context(5), sigma=9.8)
    for _ in range(count):
        T.apply(X, sk.ROWWISE).block_until_ready()


def _read(name, operations, busy_s=0.5):
    run_ = harness.Run(cell=None, device_kind="cpu", operations=operations,
                       trace=types.SimpleNamespace(busy_s=busy_s) if busy_s else None)
    return harness._reader(name)(run_)


def test_the_readers_on_a_ring_the_program_filled(ring):
    ring.set_enabled(True)
    _applies(12)
    # 2 stages × 3 blocks × 64 × 16 examples an apply; 16 × 160 features
    assert _read("chain_mix_rate.apply", 12) == pytest.approx(
        12 * 2 * 3 * 64 * 16 / 0.5 / 1e9)
    assert _read("feature_rate.apply", 12) == pytest.approx(12 * 16 * 160 / 0.5 / 1e9)
    # fewer spans than operations, no trace, no operations: nothing to read
    assert _read("chain_mix_rate.apply", 13) is None
    assert _read("chain_mix_rate.apply", 12, busy_s=0) is None
    assert _read("chain_mix_rate.apply", 0) is None


def test_the_new_reader_finds_nothing_on_a_dense_feature_map(ring):
    """The dense maps' spans carry ``features`` and no ``elements``: the
    parent's program, and the sibling cell's, leave the metric out."""
    from libskylark_tpu import Context
    from libskylark_tpu import sketch as sk

    ring.set_enabled(True)
    X = jnp.asarray(np.random.default_rng(1).standard_normal((16, 48)), jnp.float32)
    for _ in range(12):
        sk.GaussianRFT(48, 64, Context(5), sigma=9.8).apply(
            X, sk.ROWWISE).block_until_ready()
    assert _read("chain_mix_rate.apply", 12) is None
    assert _read("feature_rate.apply", 12) is not None
