"""The table of peaks and the least time the chip could take for a count."""

from __future__ import annotations

import json
import pathlib

_PEAKS = pathlib.Path(__file__).with_name("peaks.json")


def peaks(device_kind: str) -> dict:
    """Peak rates of ``device_kind``; a device not in the table is an error,
    not a default."""
    table = json.loads(_PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in {_PEAKS.name}; "
            f"known: {sorted(table)}")
    return table[device_kind]


def least_time(work: dict, peak: dict) -> tuple[float, str]:
    """(seconds, which peak bounds it) for ``work`` = {"flops", "bytes"}."""
    t_flops = work["flops"] / peak["flops_per_s"]
    t_bytes = work["bytes"] / peak["bytes_per_s"]
    return (t_flops, "flops") if t_flops >= t_bytes else (t_bytes, "hbm")
