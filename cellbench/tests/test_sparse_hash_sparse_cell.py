"""The cell ``cwt_sparse_out_apply`` (feature hashing to a sparse result) at a
tiny size on the CPU: the manifest's entries resolve, the contract's keys,
every control and a broken timed path come out not correct — each control by
the number it should —, the counts against a hand count, the plain reference
against a loop, and the two readers this cell brought. Nothing here is a
device metric."""

import dataclasses
import importlib
import json
import pathlib
import time
import types

import numpy as np
import pytest

from cellbench import harness, roofline
from cellbench.counts import sparse_hash_coalesce as counts
from cellbench.references import sparse_hash_sparse as reference

WORKLOAD = "cwt_sparse_out_apply"
ROOT = pathlib.Path(__file__).resolve().parents[2]
CONFIG = ROOT / "cellbench" / "configs" / "cwt_url_d3231961_s262144.json"
# the cell's shapes' ratios at a size a CPU run holds: n no multiple of 128,
# s ≪ n a power of two, rows of ~24 lanes under a cap of 128. norm_dev is
# statistical — collisions are 500 times likelier at 512 buckets than at 2¹⁸
# — so its limit is restated (sound seeds here read under 1.5e-2, an operand
# short of a lane granule 0.1).
TINY = {"n": 12011, "s": 512, "rows_per_panel": 256, "panels": 4,
        "nnz_per_row_mean": 24, "check_rows": 256, "law_bins": 16}
TINY_LIMITS = {"norm_dev": 5e-2}
READERS = ("coalesce_share.apply", "result_fill.apply")
DRIVER = "cellbench.drivers.sparse_hash_sparse_apply"
# control -> the number it must fail by
CONTROLS = {"program_without_coalescing": "dup_defect",
            "program_without_sort": "order_defect",
            "program_bf16_values": "rel_max",
            "program_drops_granule": "struct_defect",
            "other_allocation_counter": "struct_defect"}


@pytest.fixture
def cell():
    whole = harness.load_cell(WORKLOAD)
    limits = {**whole.config["limits"], **TINY_LIMITS}
    return dataclasses.replace(whole, config={**whole.config, **TINY,
                                              "limits": limits})


def run(cell, seed=7, trace=False, step_wrapper=None, seconds=0.3):
    return harness.run_cell(cell, seed, seconds, trace,
                            t_start=time.perf_counter(),
                            step_wrapper=step_wrapper)


def test_the_manifest_entries_resolve():
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = harness.load_cell(WORKLOAD)
    assert cell.chips == 1 and cell.config_name == "cwt_url_d3231961_s262144"
    assert cell.traffic_name == "apply_sparse_rows_sparse_out"
    assert cell.traffic["driver"] == "sparse_hash_sparse_apply"
    assert cell.traffic["loop"] == "closed" and cell.traffic["warm_steps"] == 4
    entry = next(c for c in manifest["configs"]
                 if c["name"] == cell.config_name)
    assert entry["reduced"] == sorted(cell.config["reduced"], reverse=True) \
        == ["rows_per_panel", "panels"]
    assert entry["source"] == cell.config["source"] and len(entry["source"]) <= 200
    assert {m["name"] for m in cell.end_to_end} == {"apply_ms", "setup_s"}
    listed = {m["name"]: m for m in cell.per_layer}
    assert set(listed) == {
        "sketch_device_ms.apply", "sketch_roofline.apply", "device_idle.apply",
        "sketch_host_ms.apply", "stream_key_ms.apply",
        "sketch_dispatch_ms.apply", "sparse_nnz_rate.apply",
        "idle_before_enqueue_ms.apply", "idle_after_enqueue_ms.apply",
        "setup_import_s", "setup_lower_s", "setup_compile_s", *READERS}
    for name in READERS:
        assert listed[name]["workloads"] == [WORKLOAD]
        assert listed[name]["moves"] == "apply_ms" and listed[name]["unit"] == "%"
        harness._reader(name)       # layer_metrics/<name>.py loads
    for module in ("drivers.sparse_hash_sparse_apply",
                   f"counts.{cell.config['counts']}",
                   f"references.{cell.config['reference']}", "loops.closed"):
        importlib.import_module(f"cellbench.{module}")
    # the new entries stand last in their lists, the old ones as they were
    assert manifest["workloads"][-1]["name"] == WORKLOAD
    assert manifest["configs"][-1]["name"] == cell.config_name
    assert [m["name"] for m in manifest["per_layer"][-2:]] == list(READERS)
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if WORKLOAD in m.get("workloads", []):
            assert m["workloads"][-1] == WORKLOAD


def test_the_configuration_states_its_widths_and_its_cuts():
    config = json.loads(CONFIG.read_text())
    assert config["n"] == 3_231_961 and config["s"] == 262_144 == 1 << 18
    assert config["dimension"] == "rowwise" and config["result"] == "sparse"
    assert config["rows_per_panel"] == 524_288 and config["panels"] == 4
    assert set(config["reduced"]) == {"rows_per_panel", "panels"}
    assert {"s", "nnz_per_row_mean", "row_length", "column_skew",
            "values"} <= set(config["assumed"])
    assert config["architecture"] is None
    assert set(config["limits"]) == set(config["limit_reasons"])
    assert all(config["limits"][k] == 0 for k in
               ("struct_defect", "dup_defect", "order_defect"))
    for key in ("source", "guarantees", "deployment"):
        assert config[key]
    siblings = json.loads((CONFIG.parent / "cwt_rcv1_d47236_s1024.json").read_text())
    for key in ("row_length", "column_skew", "values"):
        assert config[key] == siblings[key]


def test_result_has_exactly_the_contract_keys(cell, capsys):
    result = run(cell, seed=2**32 + 5)
    assert set(result) == {"correct", "attempted", "failed", "metrics", "device"}
    assert set(result["device"]) == {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(result["metrics"]) == {"apply_ms", "setup_s"}
    json.loads(json.dumps(result))
    log = capsys.readouterr().out
    for name in cell.config["limits"]:      # each number beside its limit
        assert f"check name={name} value=" in log and "limit=" in log
    assert "compile in_window=0" in log
    assert "dispatch path=sparse result=sparse kernel=xla_window_sort cap=" in log
    assert "counter name=sketch.sparse_nnz" in log
    assert "counter name=sketch.sparse_merged" in log


def test_same_seed_same_operands_and_a_step_keeps_the_host_out(cell):
    driver = importlib.import_module(DRIVER)
    a = driver.setup(cell.config, cell.traffic, 2**32 + 5)
    b = driver.setup(cell.config, cell.traffic, 2**32 + 5)
    c = driver.setup(cell.config, cell.traffic, 5)
    assert all((x != y).nnz == 0 for x, y in zip(a.host, b.host))
    assert a.context_seed == b.context_seed != c.context_seed
    Z = driver.step(a, 1)
    assert Z.shape == (256, 512) and not Z.host_materialized and not Z.nnz_known
    assert Z.lanes == a.panels[1].lanes     # born in its operand's lane class


def test_a_sound_run_reads_what_the_reference_says(cell):
    """The law numbers are read off the stored entries: the buckets and signs
    they show are the reference's, feature by feature."""
    driver = importlib.import_module(DRIVER)
    state = driver.setup(cell.config, cell.traffic, 7)
    kept = [(i, driver.step(state, i)) for i in range(driver.keep(state))]
    got = driver.check(state, kept)
    limits = cell.config["limits"]
    assert all(got[name] <= limits[name] for name in got), got
    assert got["struct_defect"] == got["dup_defect"] == got["order_defect"] == 0
    assert got["rel_max"] < 1e-7
    served = driver._Served(cell.config["n"])
    X = state.host[0]
    idx = np.arange(X.shape[0])
    lanes = tuple(np.asarray(x) for x in kept[0][1].csr_device())
    served.read(X, lanes, idx)
    h, v = reference.streams(state.context_seed, 0, cell.config["n"],
                             cell.config["s"])
    known = served.bucket >= 0
    assert known.sum() > 1000 and served.conflicts == 0
    assert np.array_equal(served.bucket[known], h[known])
    assert np.array_equal(served.sign[known], v[known])


def test_a_stored_zero_and_a_tie_tell_nothing_and_no_lie():
    """A stored 0.0 shows no sign and two equal |values| of one row no
    pairing: neither becomes a conflict (the first chip run of PR 64 read
    infinite z-scores from one stored zero among 243 M values)."""
    import scipy.sparse as sp

    driver = importlib.import_module(DRIVER)
    n, s = 50, 1 << 10
    h, v = reference.streams(3, 0, n, s)
    rows = [[(1, 0.5), (4, 0.0), (7, 0.25)],        # a stored zero
            [(2, 0.5), (4, 0.75), (9, 0.5)],        # a tie: the row is passed over
            [(4, 0.125), (7, 0.375)]]
    X = sp.csr_matrix(
        (np.array([x for r in rows for _, x in r], np.float32),
         np.array([c for r in rows for c, _ in r], np.int32),
         np.cumsum([0] + [len(r) for r in rows])), shape=(3, n))
    Z = reference.apply_csr(X.indptr, X.indices, X.data, h, v, s, X.shape)
    assert Z.nnz == X.nnz                           # nothing merged
    lanes = (Z.data.astype(np.float32), Z.indices, Z.indptr)
    served = driver._Served(n)
    served.read(X, lanes, np.arange(3))
    served.read(X, lanes, np.arange(3))             # met again: still no conflict
    assert served.conflicts == 0
    known = np.flatnonzero(served.bucket >= 0)
    assert list(known) == [1, 4, 7]     # 4 by row 2, not by its zero; 2 and 9 never
    assert np.array_equal(served.bucket[known], h[known])
    assert np.array_equal(served.sign[known], v[known])


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_control_fails_by_the_number_it_should(cell, control):
    driver = importlib.import_module(DRIVER)
    state = driver.setup(cell.config, cell.traffic, 11)
    stand_in = driver.controls(state)[control]
    got = driver.check(state, [(i, stand_in(i))
                               for i in range(driver.keep(state))])
    limits = cell.config["limits"]
    number = CONTROLS[control]
    assert got[number] > limits[number], got
    if control == "program_bf16_values":
        assert got["rel_max"] > 10 * limits["rel_max"]
        assert got["struct_defect"] == 0    # the structure is the values' own
    if control == "program_without_sort":
        assert got["order_defect"] > state.host[0].nnz // 4
    if control == "program_drops_granule":
        # a thirty-second to a sixteenth of the lanes is gone, and its energy
        assert got["norm_dev"] > 0.02


def test_control_is_not_correct_through_the_harness(cell):
    driver = importlib.import_module(DRIVER)
    assert run(cell)["correct"] is True
    stand_in = lambda state, _step: driver.controls(state)[  # noqa: E731
        "program_without_coalescing"]
    assert run(cell, step_wrapper=stand_in)["correct"] is False


def _lose_the_last_row(state, step):
    from libskylark_tpu.base.sparse import SparseMatrix

    def broken(i):
        Z = step(i)
        data, indices, indptr = Z.csr_device()
        return SparseMatrix.from_device_csr(
            data, indices, indptr.at[-1].set(indptr[-2]), Z.shape)
    return broken


def _double_the_answer(state, step):
    from libskylark_tpu.base.sparse import SparseMatrix

    def broken(i):
        Z = step(i)
        data, indices, indptr = Z.csr_device()
        return SparseMatrix.from_device_csr(2.0 * data, indices, indptr, Z.shape)
    return broken


@pytest.mark.parametrize("breaker", [_lose_the_last_row, _double_the_answer])
def test_broken_timed_path_is_not_correct(cell, breaker):
    assert run(cell, step_wrapper=breaker)["correct"] is False


def test_traced_run_off_the_tpu_reports_spans_but_no_device_metric(cell):
    result = run(cell, trace=True, seconds=0.4)
    assert "busy_s" not in result["device"] and "breakdown" not in result
    # no device plane: the span readers alone print — result_fill among them,
    # since the check read the kept results' counts
    assert set(result["metrics"]) == {
        "sketch_host_ms.apply", "stream_key_ms.apply",
        "sketch_dispatch_ms.apply", "result_fill.apply"}
    assert 50.0 < result["metrics"]["result_fill.apply"]["value"] <= 100.0
    assert result["correct"] is True


def test_counts_against_a_hand_count():
    config = json.loads(CONFIG.read_text())
    # 524288 rows × 115.6 stored = 60,607,693 nonzeros: one add each; 8 B a
    # lane read once and written once + two row pointers of 524289 × 4 B
    assert counts.stored_nonzeros(config) == 60_607_693
    work = counts.work(config)
    assert work["flops"] == 60_607_693
    assert work["bytes"] == 2 * 60_607_693 * 8 + 2 * 524_289 * 4 == 973_917_400
    least, bound = roofline.least_time(
        work, {"flops_per_s": 197e12, "bytes_per_s": 819e9})
    assert bound == "hbm" and least == pytest.approx(1.1892e-3, rel=1e-3)


def test_the_reference_against_a_loop_and_bf16_moves_it():
    rng = np.random.default_rng(3)
    n, s, rows = 301, 24, 40
    dense = np.where(rng.random((rows, n)) < 0.1,
                     rng.standard_normal((rows, n)), 0.0).astype(np.float32)
    import scipy.sparse as sp

    X = sp.csr_matrix(dense)
    h, v = reference.streams(99, 0, n, s)
    assert h.min() >= 0 and h.max() < s and set(np.unique(v)) == {-1.0, 1.0}
    want = np.zeros((rows, s))
    for r, c in zip(*np.nonzero(dense)):
        want[r, h[c]] += float(v[c]) * float(dense[r, c])
    Z = reference.apply_csr(X.indptr, X.indices, X.data, h, v, s, X.shape)
    assert Z.has_canonical_format
    np.testing.assert_allclose(Z.toarray(), want, atol=1e-12)
    want_cw = np.zeros((s, n))
    h_r, v_r = reference.streams(99, 0, rows, s)
    for r, c in zip(*np.nonzero(dense)):
        want_cw[h_r[r], c] += float(v_r[r]) * float(dense[r, c])
    Zc = reference.apply_csr(X.indptr, X.indices, X.data, h_r, v_r, s,
                             X.shape, rowwise=False)
    np.testing.assert_allclose(Zc.toarray(), want_cw, atol=1e-12)
    low = reference.apply_csr(X.indptr, X.indices, X.data, h, v, s, X.shape,
                              precision="bf16")
    rel = np.abs(low.toarray() - want).max() / np.abs(want).max()
    assert 1e-4 < rel < 2e-2
    # the laws: uniform buckets and fair signs pass, folded buckets do not
    hz, vz = reference.law_z_scores(*reference.streams(5, 0, 40000, 1 << 18),
                                    1 << 18, 64)
    assert hz < 6 and vz < 6
    folded, _ = reference.streams(5, 0, 40000, 1 << 18)
    assert reference.law_z_scores(folded & ~1, np.ones(40000), 1 << 18, 64)[0] > 6
    assert reference.law_z_scores(folded, np.ones(40000), 1 << 18, 64)[1] > 6


# -- the two readers this cell brought --

HLO = '''
HloModule jit_f
ENTRY %main {
  %p0 = f32[8]{0} parameter(0), metadata={op_name="data"}
  %fusion.3 = f32[8]{0} fusion(%p0), kind=kLoop, metadata={op_name="jit(f)/jit(main)/sparse_coalesce/add" source_file="x.py"}
  %sort.5 = (u32[4,8]{1,0}, f32[4,8]{1,0}) sort(%a, %b), dimensions={1}, metadata={op_name="jit(f)/jit(main)/sparse_coalesce/sort[dimension=1]"}
  %fusion.9 = u32[8]{0} fusion(%p0), kind=kLoop, metadata={op_name="jit(f)/jit(main)/threefry/xor"}
  ROOT %while.2 = (s32[], f32[8]{0}) while(%t), metadata={op_name="jit(f)/jit(main)/sparse_coalesce/while"}
}
'''


def _run(op_seconds, operations=12):
    trace = types.SimpleNamespace(op_seconds=op_seconds, busy_s=1.0)
    return harness.Run(cell=None, device_kind="cpu", operations=operations,
                       trace=trace)


def test_instructions_reads_the_scope_off_the_module():
    module = harness._reader("coalesce_share.apply").__globals__
    inside, outside = module["instructions"](HLO, "sparse_coalesce")
    assert inside == {"fusion.3", "sort.5", "while.2"}
    assert outside == {"p0", "fusion.9"}
    assert module["instructions"](HLO, "coalesce")[0] == set()


@pytest.fixture
def ring():
    from libskylark_tpu import engine, telemetry
    from libskylark_tpu.telemetry import metrics, trace

    before = metrics._ENABLED
    trace.clear_finished()
    engine.reset()
    yield telemetry
    metrics._ENABLED = before
    trace.clear_finished()
    engine.reset()


def _sparse_out_applies(count, read_counts=True):
    import scipy.sparse as sp

    from libskylark_tpu import Context
    from libskylark_tpu import sketch as sk
    from libskylark_tpu.base.sparse import SparseMatrix

    A = SparseMatrix.from_scipy(sp.random(
        40, 301, density=0.2, format="csr", dtype=np.float32,
        random_state=np.random.default_rng(1)))
    T = sk.CWT(301, 8, Context(5))
    out = [T.apply_sparse(A, sk.ROWWISE) for _ in range(count)]
    stored = [Z.nnz for Z in out] if read_counts else []
    return A, stored


def test_coalesce_share_leaves_out_what_lies_outside_the_scope(ring):
    from libskylark_tpu import engine

    _sparse_out_applies(1)
    key, = [k for k in engine.cache().keys()
            if k[0] == "sketch.hash_sparse_out"]
    text = engine.cache().lookup(key).executable.as_text()
    read = harness._reader("coalesce_share.apply")
    inside, outside = read.__globals__["instructions"](text, "sparse_coalesce")
    assert inside and any(name.startswith("sort") for name in inside)
    assert outside - inside         # the lane streams
    a, b = sorted(inside)[0], sorted(outside - inside)[0]
    # 1 s of 4 outside the scope; a copy without a name counts inside
    assert read(_run({a: 2.0, b: 1.0, "copy.99": 1.0})) == pytest.approx(75.0)
    assert read(_run({b: 1.0, "copy.99": 1.0})) is None     # none of the scope
    assert read(harness.Run(cell=None, device_kind="cpu", operations=12,
                            trace=None)) is None


def test_coalesce_share_without_the_program_gives_no_number(ring):
    read = harness._reader("coalesce_share.apply")
    assert read(_run({"sort.5": 3.0})) is None      # nothing compiled yet


def test_result_fill_divides_what_was_read(ring):
    ring.set_enabled(True)
    A, stored = _sparse_out_applies(12)
    read = harness._reader("result_fill.apply")
    assert read(_run({})) == pytest.approx(100.0 * sum(stored) / (12 * A.lanes))
    assert 0 < read(_run({})) < 100.0


def test_result_fill_without_a_count_read_gives_no_number(ring):
    ring.set_enabled(True)
    _sparse_out_applies(12, read_counts=False)
    read = harness._reader("result_fill.apply")
    assert read(_run({})) is None           # nobody asked a result its count
    assert read(_run({}, operations=0)) is None
    ring.set_enabled(False)
    from libskylark_tpu.telemetry import trace

    trace.clear_finished()
    _sparse_out_applies(3)                  # gate shut: the ring stays empty
    assert read(_run({})) is None
