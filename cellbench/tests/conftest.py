"""Tests of the benchmark's own yardstick. Run by hand:

    JAX_PLATFORMS=cpu python3 -m pytest cellbench/tests -q

They run on the CPU at tiny sizes; no number they produce is a device metric.
"""

import dataclasses
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
STAGED_MANIFEST = ROOT / "cellbench" / "staged" / "BENCHMARK.with_randsvd.json"
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

# Sizes a CPU test run can hold; the shapes' ratios (s ≪ n, k' = planted rank)
# follow the cells'. norm_dev is statistical, about |z|·√(2/(s·n))/2 (the
# Frobenius norm of S itself fluctuates): 2.4e-4·|z| at the cell's shape,
# 2e-3·|z| here, so its limit is restated at the same ten sigmas.
# Rounding errors grow with the contraction length, so the
# rand-SVD limits that separate float32 from three-pass bfloat16 are restated
# for the tiny shape by the same rule as the cell's (above three times the
# largest sound reading, under the smallest control reading; CPU, 3 seeds:
# sound 2.2e-7 / 5.9e-7, `high` 3.8e-6 / 4.7e-6 for subspace_err / ritz_resid).
TINY = {
    "jlt_apply": {"n": 512, "s": 256, "rows_per_panel": 512, "check_rows": 64,
                  "limits": {"rel_max": 1e-4, "norm_dev": 2e-2,
                             "operator_mean_z": 6.0, "operator_var_z": 6.0}},
    "randsvd": {"m": 1024, "n": 512, "rank": 8, "planted_rank": 16,
                "check_rows": 32, "check_cols": 32,
                "limits": {"subspace_err": 1e-6, "elem_err": 1.9e-5,
                           "ritz_resid": 2e-6, "sigma_err": 1e-3, "orth_err": 1e-4}},
}


@pytest.fixture
def tiny_cell():
    from cellbench import harness

    def make(workload: str):
        # the staged manifest holds the accepted cells and the staged ones
        cell = harness.load_cell(workload, STAGED_MANIFEST)
        return dataclasses.replace(cell, config={**cell.config, **TINY[workload]})

    return make
