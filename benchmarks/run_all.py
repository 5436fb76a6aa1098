"""Recorded benchmark runner: executes the perf-trajectory benches and
writes JSON artifacts at the repo root so the numbers accumulate across PRs.

    PYTHONPATH=src python -m benchmarks.run_all [--model transe] [--full]
        [--quick] [--out-dir DIR]

Always runs the pipeline bench (host vs device epochs/sec, W in {1,2,4,8},
both paradigms -> ``BENCH_pipeline.json``), the eval bench (host vs device
eval-engine queries/sec on filtered entity inference, W in {1,2,4,8}
-> ``BENCH_eval.json``), the trace bench (quality-vs-epoch curves per
merge strategy + in-loop eval overhead -> ``BENCH_trace.json``), the
serve bench (batched KnowledgeBase top-k queries/sec vs a per-query host
loop, W in {1,2,4} -> ``BENCH_serve.json``), and the latency bench
(open-loop Poisson traffic through the continuous-batching ``KGServer``:
p50/p99 latency, sustained QPS, capacity, steady-state recompiles per
batching config -> ``BENCH_latency.json``), and the scale bench (sparse
vs dense Reduce transport epochs/sec + merge wire bytes vs graph size up
to 1e6 entities, sharded-table per-device residency + sharded-Reduce
rate at W in {2,4,8}, TSV ingest throughput, large-graph fit->evaluate
round trip -> ``BENCH_scale.json``; ``--quick`` keeps the 50k-entity
train + shard_table cells + ingest row), and the async bench
(time-to-reference-quality of the bounded-staleness / joint-negative
/ partitioner training variants vs the synchronous baseline at W=4
-> ``BENCH_async.json``; ``--quick`` keeps the sync + joint-48 cells),
and the online bench (held-out-entity ``kb.update(scope="cold")`` parity
vs full retrain + serve-while-refresh swap consistency
-> ``BENCH_online.json``; ``--quick`` reruns the parity cell with
shrunken epoch counts on the same graph).

``--quick`` is the CI bench-regression profile: the W in {1, 4}
cross-section of the grids (and single-repeat trace overhead) — the
per-cell measurement discipline is unchanged, so the steady-state rates
stay comparable to the committed full-grid baselines
(``benchmarks/check_regression.py`` compares only the rows both files
share).  ``--out-dir`` redirects the JSONs (CI writes to a scratch
dir and uploads it as an artifact instead of touching the baselines).
``--full`` additionally runs the printed-only suites (strategies /
speedup / kernels / convergence) via ``benchmarks.run``.
"""
from __future__ import annotations

import argparse
import json
import os
import platform
import time


def _write(payload: dict, out: str) -> None:
    with open(out, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    print(f"wrote {out}", flush=True)


def _env() -> dict:
    import jax

    return {
        "date": time.strftime("%Y-%m-%d %H:%M:%S"),
        "jax": jax.__version__,
        "devices": [str(d) for d in jax.devices()],
        "platform": platform.platform(),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="transe")
    ap.add_argument("--out", default="BENCH_pipeline.json")
    ap.add_argument("--eval-out", default="BENCH_eval.json")
    ap.add_argument("--trace-out", default="BENCH_trace.json")
    ap.add_argument("--serve-out", default="BENCH_serve.json")
    ap.add_argument("--latency-out", default="BENCH_latency.json")
    ap.add_argument("--scale-out", default="BENCH_scale.json")
    ap.add_argument("--async-out", default="BENCH_async.json")
    ap.add_argument("--online-out", default="BENCH_online.json")
    ap.add_argument("--out-dir", default=".",
                    help="directory the BENCH_*.json files are written to")
    ap.add_argument("--quick", action="store_true",
                    help="CI profile: W in {1,4} grid cross-section "
                         "(single-repeat trace overhead) — rates stay "
                         "comparable to the committed baselines")
    ap.add_argument("--full", action="store_true",
                    help="also run the printed-only benchmark suites")
    args = ap.parse_args()

    from repro import compile_cache

    compile_cache.enable()

    from benchmarks import (bench_async, bench_eval, bench_latency,
                            bench_online, bench_pipeline, bench_scale,
                            bench_serve, bench_trace)

    os.makedirs(args.out_dir, exist_ok=True)

    def path(name: str) -> str:
        return os.path.join(args.out_dir, name)

    print("== bench:pipeline ==", flush=True)
    t0 = time.time()
    rows = bench_pipeline.run(verbose=True, model=args.model,
                              quick=args.quick)
    print(f"== bench:pipeline done ({time.time() - t0:.0f}s) ==", flush=True)
    _write({
        "bench": "pipeline",
        **_env(),
        "config": {
            "epochs_per_cell": bench_pipeline.EPOCHS,
            "dim": bench_pipeline.DIM,
            "batch_size": bench_pipeline.BATCH,
            "graph": "synthetic_kg(1, n_entities=1000, n_relations=10, "
                     "n_triplets=4000)",
        },
        "rows": rows,
    }, path(args.out))

    print("== bench:eval ==", flush=True)
    t0 = time.time()
    eval_rows = bench_eval.run(verbose=True, model=args.model,
                               quick=args.quick)
    print(f"== bench:eval done ({time.time() - t0:.0f}s) ==", flush=True)
    _write({
        "bench": "eval",
        **_env(),
        "config": {
            "repeats": bench_eval.REPEATS,
            "iters": bench_eval.ITERS,
            "dim": bench_eval.DIM,
            "chunk": bench_eval.CHUNK,
            "graph": "synthetic_kg(1, n_entities=1000, n_relations=10, "
                     "n_triplets=4000)",
        },
        "rows": eval_rows,
    }, path(args.eval_out))

    print("== bench:trace ==", flush=True)
    t0 = time.time()
    trace_out = bench_trace.run(verbose=True, model=args.model,
                                quick=args.quick)
    print(f"== bench:trace done ({time.time() - t0:.0f}s) ==", flush=True)
    _write({
        "bench": "trace",
        **_env(),
        "config": {
            "eval_every": bench_trace.EVAL_EVERY,
            "dim": bench_trace.DIM,
            "batch_size": bench_trace.BATCH,
            "workers": bench_trace.WORKERS,
            "graph": "synthetic_kg(1, n_entities=1000, n_relations=10, "
                     "n_triplets=4000)",
        },
        **trace_out,
    }, path(args.trace_out))

    print("== bench:serve ==", flush=True)
    t0 = time.time()
    serve_rows = bench_serve.run(verbose=True, model=args.model,
                                 quick=args.quick)
    print(f"== bench:serve done ({time.time() - t0:.0f}s) ==", flush=True)
    _write({
        "bench": "serve",
        **_env(),
        "config": {
            "repeats": bench_serve.REPEATS,
            "host_iters": bench_serve.HOST_ITERS,
            "engine_iters": bench_serve.ENGINE_ITERS,
            "dim": bench_serve.DIM,
            "k": bench_serve.K,
            "tile": bench_serve.TILE,
            "graph": "synthetic_kg(1, n_entities=1000, n_relations=10, "
                     "n_triplets=4000)",
        },
        "rows": serve_rows,
    }, path(args.serve_out))

    print("== bench:latency ==", flush=True)
    t0 = time.time()
    latency_rows = bench_latency.run(verbose=True, model=args.model,
                                     quick=args.quick)
    print(f"== bench:latency done ({time.time() - t0:.0f}s) ==", flush=True)
    _write({
        "bench": "latency",
        **_env(),
        "config": {
            "n_requests": bench_latency.N_REQUESTS,
            "n_burst": bench_latency.N_BURST,
            "unique_queries": bench_latency.UNIQUE,
            "dim": bench_latency.DIM,
            "k": bench_latency.K,
            "rates_qps": list(bench_latency.RATES),
            "graph": "synthetic_kg(1, n_entities=1000, n_relations=10, "
                     "n_triplets=4000)",
        },
        "rows": latency_rows,
    }, path(args.latency_out))

    print("== bench:scale ==", flush=True)
    t0 = time.time()
    scale_rows = bench_scale.run(verbose=True, model=args.model,
                                 quick=args.quick)
    print(f"== bench:scale done ({time.time() - t0:.0f}s) ==", flush=True)
    _write({
        "bench": "scale",
        **_env(),
        "config": {
            "dim": bench_scale.DIM,
            "workers": bench_scale.WORKERS,
            "strategy": bench_scale.STRATEGY,
            "sizes": {str(n): list(v)
                      for n, v in bench_scale.SIZES.items()},
            "shard_workers": list(bench_scale.SHARD_WORKERS),
            "repeats": bench_scale.REPEATS,
            "ingest_lines": bench_scale.INGEST_LINES,
            "graph": "random_kg (uniform int32 triples)",
        },
        "rows": scale_rows,
    }, path(args.scale_out))

    print("== bench:async ==", flush=True)
    t0 = time.time()
    async_rows = bench_async.run(verbose=True, model=args.model,
                                 quick=args.quick)
    print(f"== bench:async done ({time.time() - t0:.0f}s) ==", flush=True)
    _write({
        "bench": "async",
        **_env(),
        "config": {
            "epochs": bench_async.EPOCHS,
            "eval_every": bench_async.EVAL_EVERY,
            "dim": bench_async.DIM,
            "batch_size": bench_async.BATCH,
            "workers": bench_async.WORKERS,
            "norm": bench_async.NORM,
            "ref_band": bench_async.REF_BAND,
            "graph": "synthetic_kg(1, n_entities=300, n_relations=10, "
                     "n_triplets=6000)",
        },
        "rows": async_rows,
    }, path(args.async_out))

    print("== bench:online ==", flush=True)
    t0 = time.time()
    online_rows = bench_online.run(verbose=True, model=args.model,
                                   quick=args.quick)
    print(f"== bench:online done ({time.time() - t0:.0f}s) ==", flush=True)
    _write({
        "bench": "online",
        **_env(),
        "config": {
            "epochs_retrain": bench_online.EPOCHS_RETRAIN,
            "epochs_update": bench_online.EPOCHS_UPDATE,
            "delta_frac": bench_online.DELTA_FRAC,
            "dim": bench_online.DIM,
            "workers": bench_online.WORKERS,
            "learning_rate": bench_online.LR,
            "serve_queries": bench_online.SERVE_QUERIES,
            "serve_delta": bench_online.SERVE_DELTA,
            "graph": "synthetic_kg(2, n_entities=1000, n_relations=12, "
                     "n_triplets=100000)",
        },
        "rows": online_rows,
    }, path(args.online_out))

    if args.full:
        from benchmarks import run as run_mod

        for name, fn in run_mod.suites().items():
            if name not in ("pipeline", "eval", "trace"):  # already recorded
                run_mod.run_suite(name, fn)


if __name__ == "__main__":
    main()
