"""The harness: runs one cell once. Driven by data: it reads the cell from
``BENCHMARK.json`` and finds the configuration, the traffic mix, the driver,
the loop, the count function and each per-layer metric's reader by name. It
names no workload itself.

A run: start the runtime (timed apart), set up (operands on the device from
the seed, warm every shape), measure for ``--seconds`` (or trace a few
seconds of it), read the peak memory, check what the window produced against
the plain reference, print the log lines and last the one JSON line.
"""

from __future__ import annotations

import argparse
import dataclasses
import importlib
import importlib.util
import json
import os
import pathlib
import shutil
import statistics
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent
PACKAGE = pathlib.Path(__file__).resolve().parent
CACHE_DIR = PACKAGE / ".jax_cache"   # used unless JAX_COMPILATION_CACHE_DIR is set
UNIT_SCALE = {"s": 1.0, "ms": 1e3, "us": 1e6}
SLOW_FACTOR = 1.5   # a sample above this many medians is counted as a stall

EXIT_NO_CHIP = 3


def say(event: str, **fields) -> None:
    body = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"[cellbench] {event} {body}".rstrip(), flush=True)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: list    # the manifest's metric entries that this cell reports
    per_layer: list


def load_cell(workload: str, manifest_path: pathlib.Path = ROOT / "BENCHMARK.json") -> Cell:
    """The cell ``workload`` of the manifest, with its configuration's and its
    traffic mix's files read."""
    manifest = json.loads(pathlib.Path(manifest_path).read_text())
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    config_entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    end_to_end = [m for m in manifest["end_to_end"]
                  if workload in m.get("workloads", [workload])]
    reported = {m["name"] for m in end_to_end}
    per_layer = [m for m in manifest["per_layer"]
                 if (workload in m["workloads"] if "workloads" in m
                     else m["moves"] in reported)]
    return Cell(
        name=workload, chips=cell["chips"], config_name=cell["config"],
        config=json.loads((ROOT / config_entry["file"]).read_text()),
        traffic_name=cell["traffic"],
        traffic=json.loads(
            (PACKAGE / "traffic" / f"{cell['traffic']}.json").read_text()),
        end_to_end=end_to_end, per_layer=per_layer)


def _reader(metric_name: str):
    """The per-layer metric's reader: ``layer_metrics/<name>.py``, ``read(run)``."""
    path = PACKAGE / "layer_metrics" / f"{metric_name}.py"
    spec = importlib.util.spec_from_file_location(
        "cellbench_layer_metric_" + metric_name.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


@dataclasses.dataclass
class Run:
    """What a per-layer metric's reader may read."""
    cell: Cell
    device_kind: str
    operations: int         # operations completed in the traced window
    trace: object           # trace.Reduction, or None when nothing was traced

    def device_seconds_per_operation(self):
        """Device busy time of the traced window ÷ operations completed in it,
        or ``None`` when there is nothing to read."""
        if self.trace is None or not self.operations:
            return None
        return self.trace.busy_s / self.operations

    def work(self) -> dict:
        counts = importlib.import_module(
            f"cellbench.counts.{self.cell.config['counts']}")
        return counts.work(self.cell.config)

    def least_time(self) -> tuple:
        from cellbench import roofline

        return roofline.least_time(self.work(), roofline.peaks(self.device_kind))


class CompileCounter:
    """Counts backend compiles and persistent-cache traffic through
    ``jax.monitoring`` (every jit in the process, not only the engine's)."""

    def __init__(self):
        self.compiles = self.cache_hits = self.cache_misses = 0

    def on_duration(self, name: str, *_args, **_kw) -> None:
        if name == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def on_event(self, name: str, **_kw) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1


def sample_stats(samples: list) -> dict:
    ordered = sorted(samples)
    median = statistics.median(ordered)
    return {
        "n": len(ordered), "median": median, "mean": statistics.fmean(ordered),
        "p95": ordered[min(len(ordered) - 1, int(0.95 * len(ordered)))],
        "max": ordered[-1],
        "above_1.5x_median": sum(1 for s in ordered if s > SLOW_FACTOR * median),
    }


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, runtime_start_s: float = 0.0,
             step_wrapper=None) -> dict:
    """Drive one run of ``cell`` on the devices JAX has and return the result
    object. ``step_wrapper`` (tests, the calibration tool) may replace the
    driver's step: ``step_wrapper(state, step) -> step``."""
    import jax

    from cellbench import trace as trace_mod

    devices = jax.devices()[:cell.chips]
    driver = importlib.import_module(f"cellbench.drivers.{cell.traffic['driver']}")
    loop = importlib.import_module(f"cellbench.loops.{cell.traffic['loop']}")
    counter = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(counter.on_duration)
    jax.monitoring.register_event_listener(counter.on_event)

    # -- set-up: operands from the seed, then every shape of the window warm --
    t_data = time.perf_counter()
    state = driver.setup(cell.config, cell.traffic, seed)
    step = (lambda i: driver.step(state, i))
    if step_wrapper is not None:
        step = step_wrapper(state, step)
    t_warm = time.perf_counter()
    for i in range(cell.traffic["warm_steps"]):
        step(i)
    t_ready = time.perf_counter()
    setup_s = (t_ready - t_start) - runtime_start_s
    say("dispatch", **driver.describe(state))
    say("setup", setup_s=f"{setup_s:.3f}",
        import_s=f"{t_data - t_start - runtime_start_s:.3f}",
        data_s=f"{t_warm - t_data:.3f}", warm_s=f"{t_ready - t_warm:.3f}",
        runtime_start_s_left_out=f"{runtime_start_s:.3f}",
        cache_hits=counter.cache_hits, cache_misses=counter.cache_misses)

    # -- the window --
    compiles_before = counter.compiles
    engine_before = _engine_compiles()
    keep = driver.keep(state)
    reduction = None
    if trace:
        length = min(seconds, cell.traffic["trace_seconds"])
        tdir = tempfile.mkdtemp(prefix="cellbench_trace_")
        try:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 1
            options.host_tracer_level = 2
            jax.profiler.start_trace(tdir, profiler_options=options)
            try:
                with jax.profiler.TraceAnnotation(trace_mod.WINDOW_SPAN):
                    window = loop.run(step, length, keep)
            finally:
                jax.profiler.stop_trace()
            events = trace_mod.load(trace_mod.find_xplane(tdir))
            if any(k[0].startswith(trace_mod.DEVICE_PLANE) for k in events):
                reduction = trace_mod.reduce(events)
            keep_dir = os.environ.get("CELLBENCH_KEEP_TRACE")
            if keep_dir:  # builder's aid: the raw events, for recording an excerpt
                pathlib.Path(keep_dir).mkdir(parents=True, exist_ok=True)
                (pathlib.Path(keep_dir) / f"{cell.name}.events.json").write_text(
                    json.dumps({"|".join(k): v for k, v in events.items()}))
        finally:
            shutil.rmtree(tdir, ignore_errors=True)
    else:
        window = loop.run(step, seconds, keep)
    in_window = counter.compiles - compiles_before
    engine_in_window = _engine_compiles() - engine_before
    memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for d in devices)

    stats = sample_stats(window["samples"])
    say("compile", in_window=in_window, engine_in_window=engine_in_window)
    say("samples", **{k: (f"{v:.6f}" if isinstance(v, float) else v)
                      for k, v in stats.items()}, window_s=f"{window['window_s']:.3f}")

    # -- the check: what the window produced, against the plain reference --
    t_check = time.perf_counter()
    got = driver.check(state, window["kept"])
    got["compiles_in_window"] = in_window + engine_in_window
    limits = dict(cell.config["limits"], compiles_in_window=0)
    correct = True
    for name, value in got.items():
        ok = bool(value <= limits[name])
        correct = correct and ok
        say("check", name=name, value=f"{value:.4e}", limit=f"{limits[name]:.1e}", ok=ok)
    say("check_time", seconds=f"{time.perf_counter() - t_check:.3f}")

    # -- the result --
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": int(memory_peak)}
    result = {"correct": correct, "attempted": window["attempted"],
              "failed": window["failed"], "metrics": {}, "device": device}
    if trace:
        run = Run(cell, devices[0].device_kind, window["attempted"], reduction)
        if reduction is not None:
            least, bound = run.least_time()
            say("roofline", counts=cell.config["counts"],
                least_ms=f"{least * 1e3:.3f}", bound=bound)
            say("trace", window_s=f"{reduction.window_s:.6f}",
                busy_s=f"{reduction.busy_s:.6f}", device_ops=reduction.n_ops,
                operations=window["attempted"])
            device["busy_s"] = reduction.busy_s
            device["window_s"] = reduction.window_s
            result["breakdown"] = {"device_ops": reduction.device_ops,
                                   "idle_gaps": reduction.idle_gaps}
        for metric in cell.per_layer:
            value = _reader(metric["name"])(run)
            if value is not None:
                result["metrics"][metric["name"]] = {
                    "value": value, "unit": metric["unit"]}
    else:
        for metric in cell.end_to_end:
            if metric["name"] == "setup_s":
                value = setup_s
            elif metric["name"] == cell.traffic["latency_metric"]:
                value = stats["median"] * UNIT_SCALE[metric["unit"]]
            else:
                raise KeyError(f"nothing computes end-to-end metric {metric['name']!r}")
            result["metrics"][metric["name"]] = {"value": value, "unit": metric["unit"]}
    return result


def _engine_compiles() -> int:
    from libskylark_tpu import engine

    return int(engine.stats().compiles)


def main(argv: list, t_start: float) -> int:
    parser = argparse.ArgumentParser(prog="python3 -m cellbench")
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = parser.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        parser.error("--seed must be non-negative and --seconds positive")
    cell = load_cell(args.workload)

    # the compile cache: where the environment says, else one fixed path
    # inside the checkout (the path is part of the cache's key)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(CACHE_DIR))
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    t0 = time.perf_counter()
    devices = jax.devices()
    runtime_start_s = time.perf_counter() - t0
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"cellbench: workload {cell.name!r} needs {cell.chips} TPU chip(s); "
              f"JAX found {len(devices)} {devices[0].platform!r} device(s). "
              "Nothing run, no metric printed.", file=sys.stderr)
        return EXIT_NO_CHIP
    import libskylark_tpu  # noqa: F401  the system under test

    say("start", workload=cell.name, config=cell.config_name,
        traffic=cell.traffic_name, driver=cell.traffic["driver"], seed=args.seed,
        seconds=args.seconds, trace=args.trace, platform=devices[0].platform,
        device_kind=repr(devices[0].device_kind), devices=len(devices),
        compile_cache=os.environ["JAX_COMPILATION_CACHE_DIR"])
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      t_start=t_start, runtime_start_s=runtime_start_s)
    print(json.dumps(result), flush=True)
    return 0
