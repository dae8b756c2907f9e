"""Closed loop, one caller: the next operation starts when the last one has
returned. A sample is the host-clock time of one ``step`` (which ends in
``block_until_ready``); the window holds the call and the clock, nothing
else of the benchmark's own."""

from __future__ import annotations

import collections
import time


def run(step, seconds: float, keep: int) -> dict:
    """Drive ``step(i)`` for ``seconds``. Returns the per-operation samples
    (seconds) and the last ``keep`` results as (i, result), for the check."""
    clock = time.perf_counter
    samples: list[float] = []
    kept: collections.deque = collections.deque(maxlen=keep)
    i = 0
    begin = clock()
    end = begin + seconds
    while True:
        t0 = clock()
        if t0 >= end:
            break
        out = step(i)
        samples.append(clock() - t0)
        kept.append((i, out))
        i += 1
    return {"samples": samples, "kept": list(kept), "attempted": i,
            "failed": 0, "window_s": clock() - begin}
