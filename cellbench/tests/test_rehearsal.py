"""A CPU rehearsal of each driver at tiny sizes: the result object has the
contract's keys, the command refuses to run off the TPU, the lower-precision
control comes out not correct, and so does a run whose timed path is broken
underneath. Nothing here is a device metric."""

import json
import math
import os
import subprocess
import sys
import time

import pytest

from cellbench import harness

WORKLOADS = ("jlt_apply", "randsvd")
CONTROLS = [("jlt_apply", "reference_bf16"), ("randsvd", "reference_high"),
            ("randsvd", "reference_bf16")]
CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}


def run(cell, seed=7, trace=False, step_wrapper=None):
    return harness.run_cell(cell, seed, 0.3, trace, t_start=time.perf_counter(),
                            step_wrapper=step_wrapper)


@pytest.mark.parametrize("workload", WORKLOADS)
def test_result_has_exactly_the_contract_keys(tiny_cell, workload, capsys):
    cell = tiny_cell(workload)
    result = run(cell, seed=2**32 + 5)
    assert set(result) == CONTRACT_KEYS
    assert set(result["device"]) == DEVICE_KEYS
    assert result["device"]["platform"] == "cpu"        # labelled for what it is
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    names = {m["name"] for m in cell.end_to_end}
    assert set(result["metrics"]) == names and "setup_s" in names
    for metric in result["metrics"].values():
        assert set(metric) == {"value", "unit"} and metric["value"] > 0
    json.loads(json.dumps(result))
    log = capsys.readouterr().out
    # each number compared is printed beside its limit
    for name in cell.config["limits"]:
        assert f"check name={name} value=" in log and "limit=" in log
    assert "compile in_window=0" in log


@pytest.mark.parametrize("workload", WORKLOADS)
def test_traced_run_off_the_tpu_reports_no_device_metric(tiny_cell, workload):
    result = run(tiny_cell(workload), trace=True)
    assert result["metrics"] == {}                       # no device plane, no number
    assert "busy_s" not in result["device"] and "breakdown" not in result


def test_command_refuses_without_a_tpu():
    proc = subprocess.run(
        [sys.executable, "-m", "cellbench", "--workload", "jlt_apply", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=harness.ROOT, capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""                     # no result, no metric
    assert "needs 1 TPU" in proc.stderr


@pytest.mark.parametrize("workload,control_name", CONTROLS)
def test_lower_precision_control_is_not_correct(tiny_cell, workload, control_name):
    """The reference, one precision below the configuration's, put in the
    program's place through the whole of a run."""
    import importlib

    cell = tiny_cell(workload)
    driver = importlib.import_module(f"cellbench.drivers.{cell.traffic['driver']}")

    def control(state, _step):
        return driver.controls(state)[control_name]

    assert run(cell)["correct"] is True
    assert run(cell, step_wrapper=control)["correct"] is False


def _break_sketch(state, step):
    def broken(i):
        out = step(i)
        return out.at[:, : out.shape[1] // 8].set(0.0)   # an eighth of each answer lost
    return broken


def _break_svd(state, step):
    def broken(i):
        U, s, V = step(i)
        return U, s * (1.0 + 1e-2), V                    # the spectrum altered
    return broken


@pytest.mark.parametrize("workload,breaker", [("jlt_apply", _break_sketch),
                                              ("randsvd", _break_svd)])
def test_broken_timed_path_is_not_correct(tiny_cell, workload, breaker):
    result = run(tiny_cell(workload), step_wrapper=breaker)
    assert result["correct"] is False
    assert result["attempted"] > 0 and math.isfinite(result["metrics"]["setup_s"]["value"])
