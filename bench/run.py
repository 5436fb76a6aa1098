"""Run one benchmark cell on the accelerator this process finds.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``; its model
configuration and traffic mix are read from ``bench/configs/<config>.json``
and ``bench/traffic/<traffic>.json``.  With ``--trace 0`` the last line of
standard output is a JSON object holding the cell's end-to-end metrics;
with ``--trace 1`` it holds its per-layer metrics, read from a profiler
trace of part of the window.  It exits non-zero, and prints no result,
where JAX finds no TPU or fewer chips than the cell asks for.
"""
from __future__ import annotations

import os
import sys
import time
from pathlib import Path

T_IMPORT = time.perf_counter()
ROOT = Path(__file__).resolve().parent.parent


def process_age_s() -> float:
    """Seconds since this process started (Linux), else since import."""
    try:
        ticks = os.sysconf("SC_CLK_TCK")
        with open("/proc/self/stat") as f:
            start = int(f.read().rsplit(")", 1)[1].split()[19]) / ticks
        with open("/proc/uptime") as f:
            return float(f.read().split()[0]) - start
    except (OSError, ValueError, IndexError):
        return time.perf_counter() - T_IMPORT


if __name__ == "__main__":
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import harness

    sys.exit(harness.main(sys.argv[1:], started_s=process_age_s()))
