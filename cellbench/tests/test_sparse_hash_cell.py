"""The cell ``cwt_sparse_apply`` (feature hashing of sparse rows) at a tiny
size on the CPU: the contract's keys, both controls and a broken timed path
come out not correct, the counts against a hand count, the plain reference's
two forms against each other, and the two readers this cell brought on a span
ring the program filled. Nothing here is a device metric."""

import dataclasses
import importlib
import json
import math
import pathlib
import time
import types

import jax.numpy as jnp
import numpy as np
import pytest

from cellbench import harness, roofline
from cellbench.counts import sparse_hash as counts
from cellbench.references import sparse_hash as reference

WORKLOAD = "cwt_sparse_apply"
CONFIGS = pathlib.Path(__file__).resolve().parents[1] / "configs"
# the cell's shapes' ratios at a size a CPU run holds: n not a multiple of
# 128, ~2 % dense, s ≪ n. norm_dev is statistical (collisions among the
# columns outside the hot set, fewer buckets and fewer rows than the cell's),
# so its limit is restated: 6e-3 at most over the seeds tried here, against
# 4e-2 when each row loses its last nonzero.
TINY = {"n": 1181, "s": 64, "rows_per_panel": 2048, "panels": 4,
        "nnz_per_row_mean": 24, "check_rows": 64, "hot_columns": 32}
TINY_LIMITS = {"norm_dev": 2e-2}
READERS = ("sparse_nnz_rate.apply", "sketch_dispatch_ms.apply")


@pytest.fixture
def cell():
    whole = harness.load_cell(WORKLOAD)
    limits = {**whole.config["limits"], **TINY_LIMITS}
    return dataclasses.replace(whole, config={**whole.config, **TINY, "limits": limits})


def run(cell, seed=7, trace=False, step_wrapper=None, seconds=0.3):
    return harness.run_cell(cell, seed, seconds, trace, t_start=time.perf_counter(),
                            step_wrapper=step_wrapper)


def test_result_has_exactly_the_contract_keys(cell, capsys):
    result = run(cell, seed=2**32 + 5)
    assert set(result) == {"correct", "attempted", "failed", "metrics", "device"}
    assert set(result["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert result["device"]["platform"] == "cpu"
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(result["metrics"]) == {"apply_ms", "setup_s"}
    json.loads(json.dumps(result))
    log = capsys.readouterr().out
    for name in cell.config["limits"]:      # each number beside its limit
        assert f"check name={name} value=" in log and "limit=" in log
    assert "compile in_window=0" in log and "dispatch path=sparse" in log
    # the program's own count of what it sketched is logged beside the operands'
    assert "counter name=sketch.sparse_nnz" in log


def test_same_seed_same_operands_and_large_seeds_differ(cell):
    driver = importlib.import_module("cellbench.drivers.sparse_hash_apply")
    a = driver.setup(cell.config, cell.traffic, 2**32 + 5)
    b = driver.setup(cell.config, cell.traffic, 2**32 + 5)
    c = driver.setup(cell.config, cell.traffic, 5)
    assert all((x != y).nnz == 0 for x, y in zip(a.host, b.host))
    assert a.context_seed == b.context_seed != c.context_seed
    assert any((x != y).nnz for x, y in zip(a.host, c.host))
    X = a.host[0]
    assert X.has_canonical_format and X.dtype == np.float32
    norms = np.sqrt(np.asarray(X.multiply(X).sum(axis=1))).ravel()
    np.testing.assert_allclose(norms, 1.0, rtol=1e-5)       # rows of unit norm
    assert np.diff(X.indptr).min() >= 1


def test_rows_store_as_many_distinct_features_as_the_law_draws(cell):
    """A repeated draw is redrawn, not summed away: the stored row lengths
    are the clipped log-normal's own, with the configuration's mean, and the
    features keep their Zipf order of frequency."""
    driver = importlib.import_module("cellbench.drivers.sparse_hash_apply")
    config = {**cell.config, "panels": 1, "rows_per_panel": 4096}
    X = driver.setup(config, cell.traffic, 11).host[0]
    lengths = np.diff(X.indptr)
    law = config["row_length"]
    assert law["min"] <= lengths.min() and lengths.max() <= law["max"]
    assert lengths.mean() == pytest.approx(config["nnz_per_row_mean"], rel=0.03)
    assert np.std(np.log(lengths)) == pytest.approx(law["sigma"], rel=0.1)
    rows_with = np.bincount(X.indices, minlength=config["n"])
    assert rows_with.max() <= X.shape[0]                    # distinct in a row
    by_rank = np.sort(rows_with)[::-1]
    assert by_rank[0] > 2 * by_rank[9] > 4 * by_rank[99] > 0


def test_distinct_ranks_redraws_until_every_row_is_distinct():
    driver = importlib.import_module("cellbench.drivers.sparse_hash_apply")
    cdf = driver._zipf_cdf(50, 1.0)
    lengths = np.array([50, 1, 49, 7, 50], np.int64)   # whole rows: many redraws
    ranks = driver._distinct_ranks(np.random.default_rng(5), cdf, lengths)
    again = driver._distinct_ranks(np.random.default_rng(5), cdf, lengths)
    assert np.array_equal(ranks, again) and ranks.shape == (lengths.sum(),)
    for row in np.split(ranks, np.cumsum(lengths)[:-1]):
        assert np.unique(row).size == row.size and 0 <= row.min() <= row.max() < 50


def test_law_numbers_are_read_from_what_was_served(cell):
    """The buckets and signs under the law tests come out of the outputs: a
    stand-in whose streams break the laws moves both numbers past their
    limit, and a sound run's match the streams the reference rebuilds."""
    driver = importlib.import_module("cellbench.drivers.sparse_hash_apply")
    state = driver.setup(cell.config, cell.traffic, 7)
    kept = [(i, driver.step(state, i)) for i in range(driver.keep(state))]
    sound = driver.check(state, kept)
    limits = cell.config["limits"]
    assert sound["bucket_chi2_z"] < limits["bucket_chi2_z"]
    assert sound["sign_mean_z"] < limits["sign_mean_z"]
    # what the outputs show of h and v is the reference's, feature by feature
    served = driver._Served(cell.config["n"])
    X = state.host[0]
    served.read(X[:64], np.asarray(kept[0][1][:64]))
    h, v = reference.streams(state.context_seed, 0, cell.config["n"], cell.config["s"])
    known = served.bucket >= 0
    assert known.sum() >= 5 * cell.config["s"] and served.conflicts == 0
    assert np.array_equal(served.bucket[known], np.asarray(h)[known])
    assert np.array_equal(served.sign[known], np.asarray(v)[known])
    broken = driver.controls(state)["reference_breaks_laws"]
    got = driver.check(state, [(i, broken(i)) for i in range(driver.keep(state))])
    assert got["bucket_chi2_z"] > limits["bucket_chi2_z"]
    assert got["sign_mean_z"] > limits["sign_mean_z"]
    # values rounded to bfloat16 show no feature's bucket: no law number
    low = driver.controls(state)["reference_bf16"]
    got = driver.check(state, [(i, low(i)) for i in range(driver.keep(state))])
    assert got["bucket_chi2_z"] == got["sign_mean_z"] == float("inf")


@pytest.mark.parametrize("control", ["reference_bf16", "program_drops_last",
                                     "reference_breaks_laws"])
def test_control_is_not_correct(cell, control):
    driver = importlib.import_module("cellbench.drivers.sparse_hash_apply")
    assert run(cell)["correct"] is True
    stand_in = lambda state, _step: driver.controls(state)[control]  # noqa: E731
    assert run(cell, step_wrapper=stand_in)["correct"] is False


def _lose_an_eighth_of_the_rows(state, step):
    def broken(i):
        out = step(i)
        return out.at[: out.shape[0] // 8].set(0.0)
    return broken


def _double_the_answer(state, step):
    return lambda i: 2.0 * step(i)


@pytest.mark.parametrize("breaker", [_lose_an_eighth_of_the_rows, _double_the_answer])
def test_broken_timed_path_is_not_correct(cell, breaker):
    result = run(cell, step_wrapper=breaker)
    assert result["correct"] is False
    assert result["attempted"] > 0 and math.isfinite(result["metrics"]["setup_s"]["value"])


def test_traced_run_off_the_tpu_reports_spans_but_no_device_metric(cell):
    result = run(cell, trace=True, seconds=0.4)    # six spans an apply: inside the ring
    assert "busy_s" not in result["device"] and "breakdown" not in result
    # no device plane: the span readers alone print, the device readers nothing
    assert set(result["metrics"]) == {"sketch_host_ms.apply", "stream_key_ms.apply",
                                      "sketch_dispatch_ms.apply"}
    assert result["correct"] is True


def test_counts_against_a_hand_count():
    config = json.loads((CONFIGS / "cwt_rcv1_d47236_s1024.json").read_text())
    # 262144 rows × 74 stored = 19,398,656 nonzeros: one add each; 8 B each
    # + 262145 row pointers × 4 B + 262144 × 1024 × 4 B written once
    assert counts.stored_nonzeros(config) == 19_398_656
    work = counts.work(config)
    assert work["flops"] == 19_398_656
    assert work["bytes"] == 19_398_656 * 8 + 262145 * 4 + 262144 * 1024 * 4 \
        == 1_229_979_652
    least, bound = roofline.least_time(work, {"flops_per_s": 197e12, "bytes_per_s": 819e9})
    assert bound == "hbm" and least == pytest.approx(1.5018e-3, rel=1e-3)


def test_the_reference_forms_agree_and_bf16_moves_them():
    rng = np.random.default_rng(3)
    n, s, rows = 301, 24, 40
    X = np.where(rng.random((rows, n)) < 0.1, np.abs(rng.standard_normal((rows, n))), 0.0)
    X = X.astype(np.float32)
    h, v = reference.streams(99, 0, n, s)
    r, c = np.nonzero(X)
    dense = np.asarray(reference.apply_rows(X, h, v, s, block=16))
    flat = np.asarray(reference.apply_coo(jnp.asarray(r), jnp.asarray(c),
                                          jnp.asarray(X[r, c]), h, v, (rows, s)))
    assert np.array_equal(dense, flat)
    want = np.zeros((rows, s), np.float64)
    np.add.at(want, (r, np.asarray(h)[c]), np.asarray(v, np.float64)[c] * X[r, c])
    np.testing.assert_allclose(dense, want, rtol=1e-6, atol=1e-6)
    low = np.asarray(reference.apply_rows(X, h, v, s, "bf16"))
    rel = np.abs(low - dense).max() / np.abs(dense).max()
    assert 1e-4 < rel < 2e-2
    sums = np.asarray(reference.bucket_sums(jnp.asarray(X.sum(axis=0)), h, v, s))
    np.testing.assert_allclose(sums, dense.sum(axis=0), rtol=1e-4, atol=1e-4)
    # E‖Z‖² given every column's bucket and sign is ‖Z‖² itself
    gram = jnp.asarray(X.T @ X)
    assert reference.expected_sq_norm(float((X * X).sum()), gram, h, v) \
        == pytest.approx(float((dense * dense).sum()), rel=1e-5)


def test_the_manifest_lists_the_cells_readers():
    listed = {m["name"]: m for m in harness.load_cell(WORKLOAD).per_layer}
    assert set(READERS) <= set(listed) and "sketch_plan_ms.apply" not in listed
    assert listed["sparse_nnz_rate.apply"]["unit"] == "Mnnz/s"
    assert all(listed[name]["moves"] == "apply_ms" for name in READERS)
    # jlt_apply keeps the six it had: test_span_readers.py pins the set of a
    # traced rehearsal, and that file is not this cell's to edit
    dense = {m["name"] for m in harness.load_cell("jlt_apply").per_layer}
    assert not set(READERS) & dense


@pytest.fixture
def ring():
    from libskylark_tpu import telemetry
    from libskylark_tpu.telemetry import metrics, trace

    before = metrics._ENABLED
    trace.clear_finished()
    yield telemetry
    metrics._ENABLED = before
    trace.clear_finished()


def _sparse_applies(count, nnz_seed=1):
    import scipy.sparse as sp

    from libskylark_tpu import Context
    from libskylark_tpu import sketch as sk
    from libskylark_tpu.base.sparse import SparseMatrix

    A = SparseMatrix.from_scipy(sp.random(
        40, 301, density=0.1, format="csr", dtype=np.float32,
        random_state=np.random.default_rng(nnz_seed)))
    T = sk.CWT(301, 24, Context(5))
    for _ in range(count):
        T.apply(A, sk.ROWWISE).block_until_ready()
    return A.nnz


def _read(name, operations, busy_s=2.0):
    trace = types.SimpleNamespace(busy_s=busy_s)
    run = harness.Run(cell=None, device_kind="cpu", operations=operations, trace=trace)
    return harness._reader(name)(run)


def test_readers_on_a_ring_the_program_filled(ring):
    ring.set_enabled(True)
    nnz = _sparse_applies(12)
    # 12 operations of nnz stored nonzeros each over 2 s of device time
    assert _read("sparse_nnz_rate.apply", 12) == pytest.approx(12 * nnz / 2.0 / 1e6)
    dispatch = _read("sketch_dispatch_ms.apply", 12)
    stages = ring.stage_seconds("sketch.apply", last=12)
    assert dispatch == pytest.approx(
        1e3 * float(np.median([s["children"]["sketch.dispatch"] for s in stages])))
    assert 0 < dispatch < 1e3 * float(np.median([s["total_s"] for s in stages]))


@pytest.mark.parametrize("name", READERS)
def test_nothing_whole_to_read_no_number(ring, name):
    ring.set_enabled(False)
    _sparse_applies(12)                         # gate shut: the ring stays empty
    assert _read(name, 12) is None
    ring.set_enabled(True)
    _sparse_applies(3)
    assert _read(name, 12) is None              # fewer spans than operations
    assert _read(name, 0) is None


def test_nnz_rate_reads_no_dense_apply(ring):
    """A dense apply's ``sketch.dispatch`` carries no nnz: the reader of the
    sparse rate finds nothing there (the metric is not listed for that cell)."""
    from libskylark_tpu import Context
    from libskylark_tpu import sketch as sk

    ring.set_enabled(True)
    A = jnp.ones((16, 512), jnp.float32)
    for _ in range(12):
        sk.JLT(512, 64, Context(5)).apply(A, sk.ROWWISE).block_until_ready()
    assert _read("sparse_nnz_rate.apply", 12) is None
    assert _read("sketch_dispatch_ms.apply", 12) > 0
    run = harness.Run(cell=None, device_kind="cpu", operations=12, trace=None)
    assert harness._reader("sparse_nnz_rate.apply")(run) is None
