"""Tiny cells for the CPU rehearsal: the real configurations and mixes
with the graph, width and load cut down, and limits set for the CPU."""
from __future__ import annotations

import copy
import json
from pathlib import Path

from bench import harness

BENCH = Path(harness.__file__).resolve().parent
GRAPH = {"n_entities": 120, "n_relations": 7, "n_train": 1500,
         "n_valid": 100, "n_test": 100}
LIMITS = {
    "fit": {"loss_gap": 1e-4, "step1_change_gap": 1e-4,
            "step3_change_gap": 1e-4},
    "evaluate": {"rank_gap": 1, "classify_accuracy_gap": 1e-6},
    "serve": {"answer_gap": 1e-5},
}


def spec() -> dict:
    return harness.load_json(BENCH.parent / "BENCHMARK.json")


# cells whose files are under bench/ but which BENCHMARK.json does not
# name yet (not measured on the chip); rehearsed here all the same
PENDING = {
    "transe-fb15k.train-4chip": {"name": "transe-fb15k.train-4chip",
                                 "config": "transe-fb15k",
                                 "traffic": "train-4chip", "chips": 4},
    "distmult-fb15k.serve": {"name": "distmult-fb15k.serve",
                             "config": "distmult-fb15k",
                             "traffic": "serve", "chips": 1},
}


def cell(name: str):
    """(cell spec, config, mix, limits) of a tiny copy of cell ``name``."""
    cells = {**PENDING, **{c["name"]: c for c in spec()["workloads"]}}
    c = copy.deepcopy(cells[name])
    config = harness.load_json(harness.find("configs", c["config"]))
    mix = harness.load_json(harness.find("traffic", c["traffic"]))
    config["graph"].update(GRAPH)
    config["dim"] = 16
    if mix["entry"] == "fit":
        mix["batch_size"] = 32
        mix["trace_blocks"] = 2
    if mix["entry"] == "serve":
        mix.update(rate_per_s=200, trace_seconds=1, check_sample=64)
    if mix["entry"] == "evaluate":
        mix["trace_passes"] = 1
    return c, config, mix, dict(LIMITS[mix["entry"]])


def run(name: str, *, seed: int = 7, seconds: float = 1.0,
        trace: bool = False, bench: Path = BENCH, **over):
    import jax

    c, config, mix, limits = cell(name)
    for key, value in over.items():
        {"config": config, "mix": mix, "limits": limits}[key].update(value)
    if config.get("matmul_precision"):
        jax.config.update("jax_default_matmul_precision",
                          config["matmul_precision"])
    clock = harness.CompileClock()
    devices = jax.devices()[:c["chips"]]
    out = harness.run_cell(spec(), c, config, mix, seed=seed,
                           seconds=seconds, trace=trace, started_s=0.0,
                           devices=devices, clock=clock, limits=limits,
                           bench=bench)
    json.dumps(out)
    return out
