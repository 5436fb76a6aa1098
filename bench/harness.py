"""What every cell shares: finding its files by name, the chip check, the
compilation cache, set-up and window timing, the traced run, the
comparison that decides ``correct``, and the result line.

A cell's traffic mix names its ``entry`` (``fit``, ``evaluate`` or
``serve``): the module ``bench/entries/<entry>.py`` that builds the cell
from its configuration and mix, warms it, drives the measured window and
compares what the window produced with the plain reference.  Per-layer
metrics are read by ``bench/metrics/<metric>.py``, one file each, and a
configuration's scoring model by ``bench/models/<model>.py`` (its tables,
reference energies and operation counts).
"""
from __future__ import annotations

import argparse
import functools
import gc
import importlib
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class NoChip(RuntimeError):
    pass


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def find(kind: str, name: str, bench: Path = BENCH) -> Path:
    """``bench/<kind>/<name>.json`` (configs, traffic) or ``.py``
    (metrics, entries, models): each configuration, mix, metric, entry and
    scoring model lives in a file named after it."""
    for ext in (".json", ".py"):
        path = bench / kind / f"{name}{ext}"
        if path.exists():
            return path
    raise FileNotFoundError(f"no {kind} file named {name!r} under {bench}")


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@functools.cache
def model(name: str):
    """The scoring model ``bench/models/<name>.py``, loaded once: its
    ``roles``, ``tables``, ``constrain``, ``energy``, ``candidates``,
    ``relations``, ``answer_scale`` and operation counts."""
    return load_module(find("models", name))


class CompileClock:
    """Seconds XLA spent compiling in this process, the programs compiled
    and how many the persistent cache held (JAX's own monitoring events;
    the seconds include reads from the cache)."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax

        self.seconds, self.programs, self.hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_hit)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.seconds += duration
            self.programs += 1

    def _on_hit(self, event: str, **_) -> None:
        self.hits += event == self.HIT


def check_devices(chips: int):
    """The devices of a run: at least ``chips`` TPUs, or ``NoChip``."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found "
                     f"{len(devices)}")
    return devices[:chips]


def enable_cache() -> str:
    """JAX's persistent compilation cache at ``$JAX_COMPILATION_CACHE_DIR``
    or at a fixed path inside the checkout; every program is written to
    it, however short its compile."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache" / "bench")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class Cell:
    """One cell of one run: its configuration, mix, seed and devices, plus
    what the run learns (the work the window or the trace covered, the
    program's own counters, the limits of ``correct``)."""

    def __init__(self, name: str, config: dict, mix: dict, chips: int,
                 seed: int, devices):
        self.name, self.config, self.mix = name, config, mix
        self.chips, self.seed, self.devices = chips, seed, devices
        self.work: dict = {}          # what the window or the trace covered
        self.counters: dict = {}      # the program's own counters
        self.limits: dict = {}


def finite(x):
    """A number JSON can hold: an infinite or NaN reading becomes 1e300
    (``correct`` is decided before, on the reading itself)."""
    return x if math.isfinite(x) else 1e300


def peak_memory(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def traced(fn, trace_dir: str):
    """Run ``fn`` under the profiler, inside one ``bench.window`` span."""
    import jax

    with jax.profiler.trace(trace_dir):
        with jax.profiler.TraceAnnotation("bench.window"):
            return fn()


def read_per_layer(cell: Cell, spec: dict, summary, peaks: dict,
                   bench: Path = BENCH) -> dict:
    from bench import flops

    ctx = {"cell": cell, "summary": summary, "peak": peaks,
           "flops": flops}
    out = {}
    for metric in spec["per_layer"]:
        cells = metric.get("workloads")
        if cells is not None and cell.name not in cells:
            continue
        value = load_module(find("metrics", metric["name"], bench)).read(ctx)
        if value is not None:
            out[metric["name"]] = {"value": value, "unit": metric["unit"]}
    return out


def device_peaks(kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")
    if kind not in table["devices"]:
        raise KeyError(f"device kind {kind!r} has no entry in "
                       "bench/peaks.json")
    return table["devices"][kind]


def verdict(numbers: list, *, failed: int = 0,
            window_compiles: int = 0) -> bool:
    """``correct``: nothing failed or compiled in the window, and every
    number compared is within its limit."""
    return (failed == 0 and window_compiles == 0
            and all(n["value"] <= n["limit"] for n in numbers))


def run_cell(spec: dict, cell_spec: dict, config: dict, mix: dict, *,
             seed: int, seconds: float, trace: bool, started_s: float,
             devices, clock: CompileClock, limits: dict | None = None,
             bench: Path = BENCH) -> dict:
    """Set up, warm, measure (or trace) and check one cell; returns the
    result object the last line prints.  ``limits`` (default: the cell's
    ``bench/limits/<cell>.json``) bound the numbers compared."""
    t_entry = time.perf_counter()
    cell = Cell(cell_spec["name"], config, mix, cell_spec["chips"], seed,
                devices)
    cell.limits = (limits if limits is not None
                   else load_json(find("limits", cell.name, bench)))
    entry = load_module(find("entries", mix["entry"], bench)).Entry(cell)
    entry.setup()
    gc.collect()        # set-up's garbage, in set-up and not in the window
    setup_s = started_s + time.perf_counter() - t_entry
    cell.counters["setup_compile_s"] = clock.seconds
    compiled_before = clock.programs
    print(f"[{cell.name}] setup_s={setup_s!r} "
          f"xla_compile_s={clock.seconds!r} programs={clock.programs} "
          f"cache_hits={clock.hits}", file=sys.stderr, flush=True)

    summary = None
    if trace:
        from bench import trace as trace_lib

        tmp = tempfile.mkdtemp(prefix="bench_trace_")
        try:
            window = traced(entry.trace_window, tmp)
            summary = trace_lib.summarize(trace_lib.find_xplane(tmp),
                                          chips=cell.chips)
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
    else:
        window = entry.window(seconds)
    window_compiles = clock.programs - compiled_before
    memory_peak = peak_memory(devices)
    t_check = time.perf_counter()
    numbers = entry.check()
    gc.collect()
    print(f"[{cell.name}] check_s = {time.perf_counter() - t_check!r}",
          file=sys.stderr)

    correct = verdict(numbers, failed=window["failed"],
                      window_compiles=window_compiles)
    numbers.append({"name": "window_compiles", "value": window_compiles,
                    "limit": 0})
    for key, value in window.get("log", {}).items():
        print(f"[{cell.name}] {key} = {value!r}", file=sys.stderr)
    for n in numbers:
        print(f"[{cell.name}] {n['name']} = {n['value']!r} "
              f"(limit {n['limit']!r})", file=sys.stderr)

    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    if trace:
        metrics = read_per_layer(cell, spec, summary,
                                 device_peaks(d0.device_kind), bench)
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
    else:
        metrics = {}
        for m in spec["end_to_end"]:
            cells = m.get("workloads")
            if cells is not None and cell.name not in cells:
                continue
            value = setup_s if m["name"] == "setup_s" else window[
                "metrics"].get(m["name"])
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result = {"correct": bool(correct), "attempted": window["attempted"],
              "failed": window["failed"], "metrics": metrics,
              "device": device}
    if summary is not None:
        result["breakdown"] = summary.breakdown()
    result["compared"] = {n["name"]: [finite(n["value"]), n["limit"]]
                          for n in numbers}
    return result


def parse(argv):
    ap = argparse.ArgumentParser(
        prog="bench/run.py", description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True,
                    help="a cell named in BENCHMARK.json")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True,
                    help="length of the measured window")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0,
                    help="1: read the per-layer metrics from a profiler "
                         "trace of part of the window")
    return ap.parse_args(argv)


def main(argv, started_s: float = 0.0) -> int:
    args = parse(argv)
    spec = load_json(ROOT / "BENCHMARK.json")
    cells = {c["name"]: c for c in spec["workloads"]}
    if args.workload not in cells:
        print(f"unknown workload {args.workload!r}; BENCHMARK.json has "
              f"{sorted(cells)}", file=sys.stderr)
        return 2
    cell_spec = cells[args.workload]
    config = load_json(find("configs", cell_spec["config"]))
    mix = load_json(find("traffic", cell_spec["traffic"]))

    import jax

    try:
        devices = check_devices(cell_spec["chips"])
    except NoChip as exc:
        print(f"bench: {exc} - nothing was run", file=sys.stderr)
        return 3
    cache = enable_cache()
    if config.get("matmul_precision"):
        jax.config.update("jax_default_matmul_precision",
                          config["matmul_precision"])
    clock = CompileClock()
    print(f"bench: {args.workload} seed={args.seed} on {len(devices)} x "
          f"{devices[0].device_kind}, compile cache {cache}",
          file=sys.stderr, flush=True)
    result = run_cell(spec, cell_spec, config, mix, seed=args.seed,
                      seconds=args.seconds, trace=bool(args.trace),
                      started_s=started_s, devices=devices, clock=clock)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
