"""Reduction of a profiler trace (``.xplane.pb``) to what the per-layer
metrics read, with ``jax.profiler.ProfileData`` and nothing else.

Planes ``/device:TPU:<i>``, line ``XLA Ops``: one event per device
operation. Plane ``/host:CPU``, line ``python3``: the Python frames of the
main thread (the profiler's Python tracer), used to say what the host was
doing during each gap in which the device ran nothing. All planes share one
clock (nanoseconds since the start of the profiling session).

Busy time is the union of the device-op intervals inside the window; the
idle share is 1 − busy ÷ window. An op that encloses other ops of the same
line (a ``while`` around its body) counts towards the union once and is
left out of the per-op sums.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

DEVICE_PLANE = "/device:TPU:"
DEVICE_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
HOST_LINE = "python3"
WINDOW_SPAN = "cellbench.window"


@dataclasses.dataclass
class Reduction:
    window_s: float                 # the traced window
    busy_s: float                   # union of device-op intervals, mean over devices
    busy_s_by_device: dict          # plane name -> seconds
    n_ops: int                      # device ops inside the window (leaves), all devices
    op_seconds: dict                # short op name -> seconds, summed over devices
    gap_seconds: dict               # host frame -> idle seconds, mean over devices

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    @property
    def device_ops(self) -> list:
        """[[short name, seconds], ...], the ten that took most time."""
        return _ranked(self.op_seconds)

    @property
    def idle_gaps(self) -> list:
        """[[host frame, seconds], ...], the ten that covered most idle time."""
        return _ranked(self.gap_seconds)


def _ranked(sums: dict, top: int = 10) -> list:
    return [[k, v] for k, v in sorted(sums.items(), key=lambda kv: -kv[1])[:top]]


def find_xplane(directory: str) -> str:
    found = sorted(glob.glob(os.path.join(
        directory, "plugins", "profile", "*", "*.xplane.pb")))
    if len(found) != 1:
        raise FileNotFoundError(
            f"expected one .xplane.pb under {directory}, found {found}")
    return found[0]


def read_events(profile) -> dict:
    """{(plane name, line name): [(name, start_ns, duration_ns), ...]} of a
    ``ProfileData``, for the device-op lines and every host line."""
    out = {}
    for plane in profile.planes:
        device = plane.name.startswith(DEVICE_PLANE)
        if not device and plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            if device and line.name != DEVICE_LINE:
                continue
            out[(plane.name, line.name)] = [
                (e.name, float(e.start_ns), float(e.duration_ns))
                for e in line.events]
    return out


def load(path: str) -> dict:
    from jax.profiler import ProfileData

    return read_events(ProfileData.from_file(path))


def short_op_name(name: str) -> str:
    """``%fusion.2031 = f32[...] fusion(...)`` -> ``fusion.2031``; a custom
    call keeps its target: ``custom-call.25__EighTpu_``."""
    m = re.match(r"\s*%?([^\s=]+)\s*=", name)
    short = m.group(1) if m else name.strip().lstrip("%")
    target = re.search(r'custom_call_target="([^"]+)"', name)
    if target:
        short = f"{short}__{target.group(1)}_"
    return _clean(short)


def short_frame_name(name: str) -> str:
    """``$svd.py:257 approximate_svd`` -> ``svd.py:257_approximate_svd``."""
    return _clean(name.strip().lstrip("$"))


def _clean(text: str) -> str:
    return re.sub(r"[^A-Za-z0-9_.:-]", "_", text)[:80]


def union(intervals: list) -> list:
    """Merged, sorted [start, end) intervals."""
    merged: list = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1] = end
        else:
            merged.append([start, end])
    return merged


def leaves(events: list) -> list:
    """The events that enclose no other event of their line."""
    ordered = sorted(events, key=lambda e: (e[1], -e[2]))
    parents = set()
    stack: list = []  # indices of open events
    for idx, (_, start, dur) in enumerate(ordered):
        while stack and (ordered[stack[-1]][1] + ordered[stack[-1]][2]) <= start:
            stack.pop()
        if stack and start + dur <= ordered[stack[-1]][1] + ordered[stack[-1]][2]:
            parents.add(stack[-1])
        stack.append(idx)
    return [e for idx, e in enumerate(ordered) if idx not in parents]


def innermost_frames(frames: list, times: list) -> list:
    """For each time (ascending), the name of the innermost frame open at it,
    or ``None``. ``frames`` nest (one thread's call stack)."""
    ordered = sorted(frames, key=lambda e: (e[1], -e[2]))
    out, stack, nxt = [], [], 0
    for t in times:
        while nxt < len(ordered) and ordered[nxt][1] <= t:
            stack.append(ordered[nxt])
            nxt += 1
        stack = [f for f in stack if f[1] + f[2] > t]  # drop the closed frames
        out.append(stack[-1][0] if stack else None)
    return out


def window_of(events: dict) -> tuple:
    """(start_ns, end_ns) of the traced window: the ``cellbench.window`` span
    on a host line, else the span of the device ops."""
    for (plane, _), evs in events.items():
        if plane != HOST_PLANE:
            continue
        for name, start, dur in evs:
            if name == WINDOW_SPAN:
                return start, start + dur
    ops = [e for (plane, _), evs in events.items()
           if plane.startswith(DEVICE_PLANE) for e in evs]
    if not ops:
        raise ValueError("the trace holds no device operation")
    return min(e[1] for e in ops), max(e[1] + e[2] for e in ops)


def reduce(events: dict) -> Reduction:
    """Busy time, per-op sums and attributed idle gaps of one trace."""
    w0, w1 = window_of(events)
    frames = [f for f in events.get((HOST_PLANE, HOST_LINE), [])
              if f[0] != WINDOW_SPAN]
    busy_by_device, op_sums, gap_sums, n_ops = {}, {}, {}, 0
    device_lines = sorted(k for k in events if k[0].startswith(DEVICE_PLANE))
    if not device_lines:
        raise ValueError("the trace holds no device plane")
    for key in device_lines:
        inside = [(n, max(s, w0), min(s + d, w1) - max(s, w0))
                  for n, s, d in events[key] if s < w1 and s + d > w0]
        busy = union([(s, s + d) for _, s, d in inside])
        busy_by_device[key[0]] = sum(e - s for s, e in busy) * 1e-9
        for name, _, dur in leaves(inside):
            short = short_op_name(name)
            op_sums[short] = op_sums.get(short, 0.0) + dur * 1e-9
            n_ops += 1
        edges = [w0] + [t for pair in busy for t in pair] + [w1]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        names = innermost_frames(frames, [(a + b) / 2 for a, b in gaps])
        for (a, b), name in zip(gaps, names):
            label = short_frame_name(name) if name else "host_untraced"
            gap_sums[label] = gap_sums.get(label, 0.0) + (b - a) * 1e-9
    if not n_ops:
        raise ValueError("no device operation ran inside the traced window")

    n_dev = len(busy_by_device)
    return Reduction(
        window_s=(w1 - w0) * 1e-9,
        busy_s=sum(busy_by_device.values()) / n_dev,
        busy_s_by_device=busy_by_device,
        n_ops=n_ops,
        op_seconds=op_sums,
        gap_seconds={k: v / n_dev for k, v in gap_sums.items()},
    )


def to_text_proto(events: dict) -> str:
    """An XSpace text proto holding ``events`` (for recording an excerpt of a
    real trace that ``ProfileData.from_text_proto`` reads back)."""
    planes: dict = {}
    for (plane, line), evs in events.items():
        planes.setdefault(plane, []).append((line, evs))
    out = []
    for pid, (plane, lines) in enumerate(sorted(planes.items()), start=1):
        meta: dict = {}
        out.append(f"planes {{\n  id: {pid}\n  name: {_quote(plane)}")
        for lid, (line, evs) in enumerate(lines, start=1):
            out.append(f"  lines {{\n    id: {lid}\n    name: {_quote(line)}\n"
                       "    timestamp_ns: 0")
            for name, start, dur in evs:
                mid = meta.setdefault(name, len(meta) + 1)
                out.append(f"    events {{ metadata_id: {mid} "
                           f"offset_ps: {int(start * 1000)} "
                           f"duration_ps: {int(dur * 1000)} }}")
            out.append("  }")
        for name, mid in meta.items():
            out.append(f"  event_metadata {{ key: {mid} value {{ id: {mid} "
                       f"name: {_quote(name)} }} }}")
        out.append("}")
    return "\n".join(out) + "\n"


def _quote(text: str) -> str:
    return '"' + text.replace("\\", "\\\\").replace('"', '\\"') + '"'
