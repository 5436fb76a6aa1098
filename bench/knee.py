"""The sweep that finds a serving cell's knee: the highest offered rate at
which completions keep pace with arrivals and the backlog does not grow.

    python bench/knee.py --workload <cell> --seed <n> --seconds 10 \
        --rates 400 800 1600 3200

One process sets the cell up once, then offers each rate in turn for
``--seconds`` with the cell's own mix and prints one JSON line per rate:
the completed rate, the latency quantiles, and the 95th percentile of the
window's first and last thirds (a backlog that grows shows as a last third
far above the first).  The knee is recorded in the mix's file by hand; the
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import harness

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    spec = harness.load_json(ROOT / "BENCHMARK.json")
    c = {w["name"]: w for w in spec["workloads"]}[args.workload]
    config = harness.load_json(harness.find("configs", c["config"]))
    mix = harness.load_json(harness.find("traffic", c["traffic"]))
    devices = harness.check_devices(c["chips"])
    harness.enable_cache()
    import jax

    if config.get("matmul_precision"):
        jax.config.update("jax_default_matmul_precision",
                          config["matmul_precision"])
    cell = harness.Cell(c["name"], config, mix, c["chips"], args.seed,
                        devices)
    entry = harness.load_module(harness.find("entries", mix["entry"])).Entry(
        cell)
    t = time.perf_counter()
    entry.setup()
    print(json.dumps({"setup_s": time.perf_counter() - t}), flush=True)
    for rate in args.rates:
        mix["rate_per_s"] = rate
        entry.server.clear_cache()      # each rate starts as a run does
        out = entry.window(args.seconds)
        due = entry.plan["due"]
        ok = entry.ok
        lat = entry.latency
        third = args.seconds / 3
        first = lat[ok & (due < third)]
        last = lat[ok & (due >= 2 * third)]
        finished = entry.finished_s
        print(json.dumps({
            "rate_per_s": rate, "requests": len(due),
            "failed": out["failed"],
            "completed_per_s": int(ok.sum()) / finished,
            "drain_s": finished - args.seconds,
            "p50_ms": float(np.percentile(lat[ok], 50) * 1e3),
            "p95_ms": float(np.percentile(lat[ok], 95) * 1e3),
            "p99_ms": float(np.percentile(lat[ok], 99) * 1e3),
            "p95_first_third_ms": float(np.percentile(first, 95) * 1e3),
            "p95_last_third_ms": float(np.percentile(last, 95) * 1e3),
            "mean_wave": out["log"]["mean_wave"],
            "cache_hit_share": out["log"]["cache_hit_share"],
            "client_late_p95_ms": out["log"]["client_late_p95_ms"],
            "steady_recompiles": out["log"]["steady_recompiles"]}),
            flush=True)
    entry.server.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
