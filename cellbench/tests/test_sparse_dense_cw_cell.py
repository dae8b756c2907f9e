"""The cell ``jlt_sparse_apply_cw`` (the columnwise dense sketch of sparse
rows, S·X) at a tiny size on the CPU: the manifest's entries resolve, the
contract's keys, all three controls and a broken timed path come out not
correct, the counts against a hand count, the reference's forms against each
other, and the ``setup_place_s`` reader on a canned span list. Nothing here
is a device metric."""

import dataclasses
import importlib
import importlib.util
import json
import math
import pathlib
import time
import types

import numpy as np
import pytest

from cellbench import harness, roofline
from cellbench.counts import sparse_dense_sketch_cw as counts
from cellbench.references import sparse_dense_sketch_cw as reference

WORKLOAD = "jlt_sparse_apply_cw"
PACKAGE = pathlib.Path(__file__).resolve().parents[1]
# the cell's shapes' ratios at a size a CPU run holds: 256 sketched rows a
# block, n no multiple of 128, s ≪ n. The whole-result numbers depend on the
# block's size, so their limits are restated for 256 rows: over the seeds
# tried here norm_dev reads 8e-2 at most (a chi-square of 32 terms a
# column), colsum_dev and rowsum_dev 1e-6 sound against 1e-2 and more
# without the last stored nonzeros.
TINY = {"n": 1181, "s": 32, "rows_per_panel": 256, "panels": 4,
        "nnz_per_row_mean": 24, "check_cols": 64, "hot_features": 64}
TINY_LIMITS = {"norm_dev": 0.25, "colsum_dev": 1e-4, "rowsum_dev": 1e-4}
DRIVER = "cellbench.drivers.sparse_dense_apply_cw"


@pytest.fixture
def cell():
    whole = harness.load_cell(WORKLOAD)
    limits = {**whole.config["limits"], **TINY_LIMITS}
    return dataclasses.replace(whole, config={**whole.config, **TINY, "limits": limits})


def run(cell, seed=7, trace=False, step_wrapper=None, seconds=0.3):
    return harness.run_cell(cell, seed, seconds, trace, t_start=time.perf_counter(),
                            step_wrapper=step_wrapper)


def test_manifest_entries_resolve():
    manifest = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    cell = harness.load_cell(WORKLOAD)
    assert cell.chips == 1 and cell.config_name == "jlt_rcv1_m524288_s1024_cw"
    assert cell.traffic_name == "apply_sparse_rows_dense_cw"
    assert cell.traffic["driver"] == "sparse_dense_apply_cw"
    assert cell.traffic["latency_metric"] == "apply_ms"
    assert (cell.traffic["warm_steps"], cell.traffic["trace_seconds"]) == (4, 10)
    config = cell.config
    assert (config["n"], config["s"], config["nnz_per_row_mean"]) == (47236, 1024, 74)
    assert (config["rows_per_panel"], config["panels"]) == (524288, 4)
    assert config["architecture"] is None and config["dimension"] == "columnwise"
    entry = next(c for c in manifest["configs"] if c["name"] == cell.config_name)
    assert sorted(entry["reduced"]) == sorted(config["reduced"]) == [
        "panels", "rows_per_panel"]
    assert len(entry["source"]) <= 200 and entry["source"] == config["source"]
    for cited in ("dense_transform_Mixed.hpp:19", "lsrn_tag", ":68-90",
                  "svd.hpp:113-119"):
        assert cited in entry["source"], cited
    assert set(config["limits"]) == set(config["limit_reasons"])
    # the data laws are the accepted sparse cells', key for key
    other = harness.load_cell("cwt_sparse_apply").config
    for key in ("n", "row_length", "column_skew", "values", "nnz_per_row_mean"):
        assert config[key] == other[key], key
    # every file the harness finds by name is there
    for module in (f"cellbench.counts.{config['counts']}",
                   f"cellbench.references.{config['reference']}", DRIVER):
        importlib.import_module(module)
    reported = {m["name"] for m in cell.end_to_end}
    assert reported == {"apply_ms", "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    assert {"lane_fill.apply", "sparse_nnz_rate.apply", "sketch_roofline.apply",
            "sketch_device_ms.apply", "device_idle.apply",
            "setup_place_s"} <= names
    for name in names:
        assert (PACKAGE / "layer_metrics" / f"{name}.py").is_file(), name
    # the one metric this cell's PR adds reads both sparse × dense cells
    place = next(m for m in manifest["per_layer"] if m["name"] == "setup_place_s")
    assert place["workloads"] == ["jlt_sparse_apply", WORKLOAD]
    assert place["moves"] == "setup_s" and place["layer"] == "set-up"


def test_result_has_exactly_the_contract_keys(cell, capsys):
    result = run(cell, seed=2**32 + 5)
    assert set(result) == {"correct", "attempted", "failed", "metrics", "device"}
    assert result["device"]["platform"] == "cpu"
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(result["metrics"]) == {"apply_ms", "setup_s"}
    json.loads(json.dumps(result))
    log = capsys.readouterr().out
    for name in cell.config["limits"]:      # each number beside its limit
        assert f"check name={name} value=" in log and "limit=" in log
    assert "compile in_window=0" in log and "dispatch path=sparse" in log
    assert "side=transposed" in log and "kernel=xla:_backend_cpu" in log
    assert "counter name=sketch.sparse_nnz" in log


def test_same_seed_same_corpus_as_the_hash_cell(cell):
    """The generator is the accepted cell's, imported: one seed, one corpus."""
    driver = importlib.import_module(DRIVER)
    theirs = importlib.import_module("cellbench.drivers.sparse_hash_apply")
    assert driver._panel is theirs._panel and driver._zipf_cdf is theirs._zipf_cdf
    a = driver.setup(cell.config, cell.traffic, 2**32 + 5)
    b = driver.setup(cell.config, cell.traffic, 2**32 + 5)
    c = driver.setup(cell.config, cell.traffic, 5)
    assert all((x != y).nnz == 0 for x, y in zip(a.host, b.host))
    assert a.context_seed == b.context_seed != c.context_seed
    assert any((x != y).nnz for x, y in zip(a.host, c.host))
    assert a.transform._N == TINY["rows_per_panel"]     # the sketched extent
    hash_config = {**harness.load_cell("cwt_sparse_apply").config, **TINY}
    d = theirs.setup(hash_config, cell.traffic, 2**32 + 5)
    assert all((x != y).nnz == 0 for x, y in zip(a.host, d.host))


def test_the_sampled_columns_read_hot_and_cold_features(cell):
    driver = importlib.import_module(DRIVER)
    state = driver.setup(cell.config, cell.traffic, 7)
    idx = driver.check_columns(state, 0)
    counts_ = np.bincount(state.host[0].indices, minlength=TINY["n"])
    assert idx.shape == (TINY["check_cols"],) == np.unique(idx).shape
    assert counts_[idx].min() >= 1          # every one stores a lane
    assert counts_[idx].max() >= 0.5 * TINY["rows_per_panel"]   # a hot row
    assert counts_[idx].min() <= 2          # a row with a lane or two
    cut = np.sort(counts_)[-TINY["hot_features"]]
    assert np.count_nonzero(counts_[idx] >= cut) >= TINY["check_cols"] // 2


@pytest.mark.parametrize("control", ["reference_bf16", "program_drops_last_chunk",
                                     "program_other_counter"])
def test_control_is_not_correct(cell, control):
    driver = importlib.import_module(DRIVER)
    assert run(cell)["correct"] is True
    stand_in = lambda state, _step: driver.controls(state)[control]  # noqa: E731
    assert run(cell, step_wrapper=stand_in)["correct"] is False


def test_each_control_fails_by_its_own_number(cell):
    """bfloat16 moves the sampled columns, a dropped chunk the whole
    result's sums, another counter the sampled columns; neither precision
    nor the counter moves the law."""
    driver = importlib.import_module(DRIVER)
    state = driver.setup(cell.config, cell.traffic, 7)
    limits = cell.config["limits"]
    blocks = range(driver.keep(state))
    sound = driver.check(state, [(i, driver.step(state, i)) for i in blocks])
    assert all(sound[name] <= limits[name] for name in sound), sound
    failed = {}
    for name, stand_in in driver.controls(state).items():
        got = driver.check(state, [(i, stand_in(i)) for i in blocks])
        failed[name] = {k for k in got if not got[k] <= limits[k]}
    assert "rel_max" in failed["reference_bf16"]
    assert {"rowsum_dev", "colsum_dev"} <= failed["program_drops_last_chunk"]
    assert "rel_max" in failed["program_other_counter"]
    # (at this size the dropped lanes are a sixth of a block: its sampled
    # columns no longer follow from the operand's, and the law may move)
    for control in ("reference_bf16", "program_other_counter"):
        assert not failed[control] & {"operator_mean_z", "operator_var_z"}


def test_the_dropped_chunk_is_the_transposed_sides_last(cell):
    """With the program's blocks named: the last block of features' last
    tile of examples that holds any loses its last ``chunk`` lanes in
    (feature, example) order; with none named, the last stored nonzeros."""
    import scipy.sparse as sp

    driver = importlib.import_module(DRIVER)
    X = sp.random(96, 50, density=0.3, format="csr", random_state=3,
                  dtype=np.float32)
    plan = {"row_block": 16, "col_tile": 32, "chunk": 5}
    cut = driver.without_last_chunk(X, plan)
    lost = (X - cut).tocoo()
    assert lost.nnz == 5 == X.nnz - cut.nnz
    assert lost.col.min() >= 48 and lost.row.min() >= 64    # block 3, tile 2
    kept_there = cut[64:, 48:].tocoo()
    # what stays of that segment comes before what went, feature-major
    assert (kept_there.col.max(), 0) <= (lost.col.min(), 0)
    assert (X - driver.without_last_chunk(X, {})).nnz == min(
        driver.LAST_LANES, X.nnz)


def _lose_a_twelfth_of_the_features(state, step):
    def broken(i):
        out = step(i)
        return out.at[:, : out.shape[1] // 12].set(0.0)
    return broken


def _double_the_answer(state, step):
    return lambda i: 2.0 * step(i)


@pytest.mark.parametrize("breaker", [_lose_a_twelfth_of_the_features,
                                     _double_the_answer])
def test_broken_timed_path_is_not_correct(cell, breaker):
    result = run(cell, step_wrapper=breaker)
    assert result["correct"] is False
    assert result["attempted"] > 0 and math.isfinite(result["metrics"]["setup_s"]["value"])


def test_traced_run_off_the_tpu_reports_spans_but_no_device_metric(cell):
    result = run(cell, trace=True, seconds=2.0)
    assert "busy_s" not in result["device"] and "breakdown" not in result
    # no device plane: the span readers alone print, the device readers nothing
    assert {"lane_fill.apply", "sketch_host_ms.apply",
            "sketch_dispatch_ms.apply"} <= set(result["metrics"])
    assert "sketch_device_ms.apply" not in result["metrics"]
    assert 93.0 < result["metrics"]["lane_fill.apply"]["value"] <= 100.0
    # off the kernel nothing is regrouped: no sparse.place span, no number
    assert "setup_place_s" not in result["metrics"]
    assert result["correct"] is True


def test_counts_against_a_hand_count():
    config = json.loads(
        (PACKAGE / "configs" / "jlt_rcv1_m524288_s1024_cw.json").read_text())
    # 524288 rows × 74 stored = 38,797,312 nonzeros, a multiply and an add
    # for each of 1024 results; 8 B a nonzero + 47237 column pointers × 4 B +
    # Sᵀ 524288 × 1024 × 4 B read once + 1024 × 47236 × 4 B written once
    assert counts.stored_nonzeros(config) == 38_797_312
    work = counts.work(config)
    assert work["flops"] == 2 * 38_797_312 * 1024 == 79_456_894_976
    assert work["bytes"] == (38_797_312 * 8 + 47237 * 4 + 524288 * 1024 * 4
                             + 1024 * 47236 * 4) == 2_651_529_748
    least, bound = roofline.least_time(work, {"flops_per_s": 197e12, "bytes_per_s": 819e9})
    assert bound == "hbm" and least == pytest.approx(3.2375e-3, rel=1e-3)
    # at a small shape, by hand: 4 rows × 3 stored, s = 2, n = 5
    small = {"rows_per_panel": 4, "nnz_per_row_mean": 3, "n": 5, "s": 2}
    assert counts.work(small) == {"flops": 2 * 12 * 2,
                                  "bytes": 12 * 8 + 6 * 4 + 4 * 2 * 4 + 5 * 2 * 4}


def test_the_reference_forms_agree_and_bf16_moves_them():
    import scipy.sparse as sp

    n, s, rows = 301, 24, 2 * reference.PANEL_ROWS + 40
    X = sp.random(rows, n, density=0.03, format="csr", random_state=5,
                  dtype=np.float32)
    key_data = reference.allocation_key_data(99, 0)
    S = reference.operator(99, 0, s, rows)
    assert S.shape == (s, rows)
    want = np.asarray(S, np.float64) @ X.astype(np.float64).toarray()
    scale = np.abs(want).max()
    idx = np.array([0, 7, 150, 300])
    by_cols = np.asarray(reference.apply_cols(X[:, idx], key_data, s))
    whole = np.asarray(reference.apply_block(X, key_data, s))
    assert np.abs(by_cols - want[:, idx]).max() <= 1e-6 * scale
    assert np.abs(whole - want).max() <= 1e-6 * scale
    low = np.asarray(reference.apply_block(X, key_data, s, "bf16"))
    assert 1e-4 * scale < np.abs(low - want).max() < 3e-2 * scale
    ones_S, S_ones = reference.operator_sums(
        key_data, s, rows, np.asarray(X.sum(axis=1)))
    assert np.allclose(ones_S, np.asarray(S, np.float64).sum(axis=0), atol=1e-5)
    assert np.allclose(S_ones[:, 0], want.sum(axis=1), atol=1e-4 * scale)
    # the operator is the rowwise reference's and the dense cells' one
    from cellbench.references import dense_sketch, sparse_dense_sketch

    assert np.array_equal(np.asarray(S),
                          np.asarray(sparse_dense_sketch.operator(99, 0, s, rows)))
    assert np.array_equal(
        np.asarray(S)[:, :512], np.asarray(dense_sketch.operator(99, 0, s, 512)))
    # the law read out of sound columns, and out of columns of another law
    mean_z, var_z = reference.law_z_scores(X[:, :64], want[:, :64], s)
    assert mean_z < 6 and var_z < 6
    assert reference.law_z_scores(X[:, :64], 1.5 * want[:, :64], s)[1] > 6


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "setup_place_reader", PACKAGE / "layer_metrics" / f"{name}.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def test_setup_place_reader_on_a_canned_span_list(monkeypatch):
    from libskylark_tpu.telemetry import trace

    def span(name, **attrs):
        return types.SimpleNamespace(name=name, attrs=attrs)

    canned = [
        span("sparse.place", side="transposed", seconds=2.5, bytes=10),
        span("sketch.apply"),
        span("sparse.place", side="rows", seconds=1.25, bytes=10),
        span("sketch.dispatch", path="sparse", nnz=300, lane_slots=400),
    ]
    monkeypatch.setattr(trace, "finished_spans", lambda: canned)
    read = _reader("setup_place_s")
    run_of = lambda n: types.SimpleNamespace(operations=n, trace=None)  # noqa: E731
    assert read(run_of(3)) == pytest.approx(3.75)
    assert read(run_of(0)) is None
    # a program whose placement leaves no span outside a session (the
    # parent's), or one that regroups nothing: no number
    monkeypatch.setattr(trace, "finished_spans", lambda: [span("sketch.apply")])
    assert read(run_of(1)) is None
