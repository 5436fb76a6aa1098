"""The controls and planted faults that the limits of ``correct`` are set
against, read at a cell's own size.

    python bench/control.py --workload <cell> --seeds 11 12 13

For each seed it builds the cell's graph and tables as a run does, puts
the reference computed one precision lower in the program's place
(bfloat16 for the float32 TransE cells, three-pass matmuls for the
DistMult cell at ``highest``), and prints, as one JSON line, every number
the cell compares, read against the float32 reference, and under
``correct`` what a run's own predicate (``harness.verdict``) makes of
each against the cell's limits.  Training cells
also read two faults planted in the reference: half of each batch left
out (the mean over the rest), and the Reduce's exchange left out (worker
0's tables kept).  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent.parent


def fit_readings(config, mix, seed):
    from bench import reference, weights
    from bench.entries.fit import CHECK_STEPS, inputs, leaf_gap

    tr = config["train"]
    g, fit_seed = inputs(config, mix)
    tables = weights.make(config, seed)
    t0 = {k: np.asarray(v, np.float32) for k, v in tables.items()}

    def run(prec="f32", fault=None):
        losses, states = reference.train(
            config["model"], tables, g.train, fit_seed,
            n_workers=mix["n_workers"], batch=mix["batch_size"],
            margin=tr["margin"], lr=tr["learning_rate"], epochs=CHECK_STEPS,
            prec=prec, fault=fault)
        change = {s: {k: float(np.linalg.norm(states[s - 1][k] - t0[k]))
                      for k in t0} for s in (1, CHECK_STEPS)}
        return losses, change

    ref = run()
    out = {}
    for name, kw in (("control_bf16", {"prec": "bf16"}),
                     ("fault_half_batch", {"fault": "half_batch"}),
                     ("fault_no_exchange", {"fault": "no_exchange"})):
        losses, change = run(**kw)
        out[name] = {
            "loss_gap": max(abs(a - b) / abs(b)
                            for a, b in zip(losses, ref[0])),
            "step1_change_gap": leaf_gap(change[1], ref[1][1]),
            "step3_change_gap": leaf_gap(change[CHECK_STEPS],
                                         ref[1][CHECK_STEPS])}
    return out


def evaluate_readings(config, mix, seed):
    from bench import graph as graph_lib
    from bench import reference, weights

    g = graph_lib.generate(config["graph"], seed)
    tables = weights.make(config, seed)
    known = reference.Known(g.all_triples, g.n_entities, g.n_relations)
    ref = reference.ranks(config["model"], tables, g.test, known)
    low = reference.ranks(config["model"], tables, g.test, known,
                          prec="bf16")
    acc = [reference.classify_accuracy(config["model"], tables, g.valid,
                                       g.test, g.n_entities, g.n_relations,
                                       prec) for prec in ("f32", "bf16")]
    return {"control_bf16": {
        "rank_gap": max(int(np.max(np.abs(low[k].astype(np.int64)
                                          - ref[k].astype(np.int64))))
                        for k in ref),
        "classify_accuracy_gap": abs(acc[1] - acc[0])}}


def serve_readings(config, mix, seed):
    from bench import graph as graph_lib
    from bench import reference, weights
    from bench.entries import serve

    g = graph_lib.generate(config["graph"], seed)
    tables = weights.make(config, seed)
    known = reference.Known(g.all_triples, g.n_entities, g.n_relations)
    plan = serve.schedule(mix, serve.distinct_keys(g), seed, 1.0)
    rng = np.random.default_rng([seed, 0xC4EC])
    pick = np.sort(rng.choice(len(plan["due"]),
                              min(mix["check_sample"], len(plan["due"])),
                              replace=False))
    gap = 0.0
    for i, kind in enumerate(serve.KINDS):
        sel = pick[plan["kind"][pick] == i]
        if not len(sel):
            continue
        filt = known if mix["filtered"] and kind != "relations" else None
        args = (config["model"], tables, kind, plan["a"][sel],
                plan["b"][sel], mix["k"], filt)
        _, ref_e, scale = reference.top_k(*args)
        ids, low_e, _ = reference.top_k(*args, prec="high")
        for j in range(len(sel)):
            gap = max(gap, serve.answer_gap(ids[j], low_e[j, ids[j]],
                                            ref_e[j], scale[j]))
    return {"control_high": {"answer_gap": gap}}


READINGS = {"fit": fit_readings, "evaluate": evaluate_readings,
            "serve": serve_readings}


def readings(config: dict, mix: dict, seed: int) -> dict:
    import jax

    if config.get("matmul_precision"):
        jax.config.update("jax_default_matmul_precision",
                          config["matmul_precision"])
    return READINGS[mix["entry"]](config, mix, seed)


def verdicts(got: dict, limits: dict) -> dict:
    """Each control's or fault's ``correct``, by the predicate a run of
    the cell applies to its own numbers."""
    from bench import harness

    return {name: harness.verdict([
        {"name": k, "value": v, "limit": limits[k]}
        for k, v in reading.items() if k in limits])
        for name, reading in got.items()}


def main(argv=None) -> int:
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import harness

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cells = {c["name"]: c for c in
             harness.load_json(ROOT / "BENCHMARK.json")["workloads"]}
    c = cells[args.workload]
    config = harness.load_json(harness.find("configs", c["config"]))
    mix = harness.load_json(harness.find("traffic", c["traffic"]))
    limits = harness.load_json(harness.find("limits", args.workload))
    harness.check_devices(1)
    harness.enable_cache()
    for seed in args.seeds:
        t = time.perf_counter()
        out = readings(config, mix, seed)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "seconds": time.perf_counter() - t, **out,
                          "correct": verdicts(out, limits)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
