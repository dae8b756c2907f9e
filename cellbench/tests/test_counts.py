"""The count functions and the table of peaks against hand-worked values."""

import json
import pathlib

import pytest

from cellbench import roofline
from cellbench.counts import dense_sketch, randsvd_passes

CONFIGS = pathlib.Path(__file__).resolve().parents[1] / "configs"
V5E = {"flops_per_s": 197e12, "bytes_per_s": 819e9}


def config(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def test_dense_sketch_counts_and_least_time():
    work = dense_sketch.work(config("jlt_n8192_s1024"))
    # 2·m·n·s = 2·65536·8192·1024; (m·n + m·s)·4 B
    assert work["flops"] == 2 * 65536 * 8192 * 1024 == 1_099_511_627_776
    assert work["bytes"] == (65536 * 8192 + 65536 * 1024) * 4 == 2_415_919_104
    least, bound = roofline.least_time(work, V5E)
    # 1.0995e12 / 197e12 = 5.581 ms (flops) against 2.416e9 / 819e9 = 2.950 ms
    assert bound == "flops"
    assert least == pytest.approx(5.581e-3, rel=1e-3)


def test_randsvd_counts_and_least_time():
    cfg = config("randsvd_65536x16384_k64")
    assert randsvd_passes.sketch_width(cfg) == 128
    work = randsvd_passes.work(cfg)
    # six passes: 6·2·65536·16384·128 flops, 6·65536·16384·4 B
    assert work["flops"] == 6 * 2 * 65536 * 16384 * 128 == 1_649_267_441_664
    assert work["bytes"] == 6 * 65536 * 16384 * 4 == 25_769_803_776
    least, bound = roofline.least_time(work, V5E)
    # 2.577e10 / 819e9 = 31.465 ms (HBM) against 1.649e12 / 197e12 = 8.372 ms
    assert bound == "hbm"
    assert least == pytest.approx(31.465e-3, rel=1e-3)


def test_peaks_table():
    peak = roofline.peaks("TPU v5 lite")
    assert peak["flops_per_s"] == 197e12 and peak["bytes_per_s"] == 819e9
    assert "source" in peak
    with pytest.raises(KeyError):
        roofline.peaks("cpu")
