"""Chip smoke test: the knowledge-graph path end to end on a TPU.

    python chip_smoke.py [--seed 0] [--out chiprun_out/chip_smoke]
    python chip_smoke.py --chips 4     # the sharded multi-chip path only

With no option it drives, on one chip and through the entry points a user
calls, one TransE deployment at FB15k's shape and DGL-KE's published
FB15k width (dim 400, L1), generated from ``--seed``:

  1. ``repro.kg.fit``: 2 epochs, SGD, device pipeline, 4 vmap workers of
     256 triples per batch — loss per epoch must be finite and fall;
  2. ``repro.kg.evaluate`` on 2,048 test queries with the default device
     engine (the ``rank_topk`` Pallas kernel on TPU) and the exact
     ``fused=False`` path, and the host reference on 256 of them — filtered
     mean rank within 0.5% and hits@10 within 0.5 points between engines;
  3. ``KnowledgeBase`` save/load, then a warmed ``KGServer`` answers 64
     requests — each with the engine's direct answer's ids and its
     energies to float32 rounding, with no steady-state recompile;
  4. ``kb.update`` with 300 delta triples — finite tables, new fingerprint.

``--chips 4`` instead fits with ``backend="shard_map"`` over a 4-chip mesh
(sparse transport, sharded entity tables) and evaluates with the sharded
device engine, against the same fit and eval at ``backend="vmap"`` on chip
0.

Every phase prints its wall time and, on its own line, the seconds XLA
spent compiling (``xla_compile_s``; small when the persistent compilation
cache already holds the programs), the programs it compiled and how many
of those the cache held (``cache_hits``).  Phase wall times are bring-up
observations, not benchmark numbers.  Any failed check raises and the
script exits non-zero.  It exits non-zero before any work when JAX finds
no TPU.  The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time
from pathlib import Path

import jax
import numpy as np

from bench.harness import CompileClock

ROOT = Path(__file__).resolve().parent

# FB15k (Bordes et al. 2013, Table 1)
N_ENTITIES, N_RELATIONS = 14_951, 1_345
N_TRAIN, N_VALID, N_TEST = 483_142, 50_000, 59_071
# DGL-KE's FB15k TransE width; the repo's default norm
FIT = dict(model="transe", paradigm="sgd", dim=400, norm="l1", n_workers=4,
           batch_size=256, pipeline="device", block_epochs=1)
EPOCHS = 2
N_EVAL, N_HOST = 2_048, 256
N_REQUESTS, N_DELTA, N_NEW_ENTITIES = 64, 300, 10
# engines must agree this closely (a last-ulp tie can move a rank by one)
MR_REL_TOL, HITS_ABS_TOL = 0.005, 0.005
# served energies must match direct ones to float32 rounding of a 400-term sum
ENERGY_RTOL = 1e-5


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


class Phase:
    """Times one phase and prints its wall and compile seconds."""

    def __init__(self, name: str, clock: CompileClock):
        self.name, self.clock = name, clock

    def __enter__(self) -> "Phase":
        c = self.clock
        self.t0 = time.perf_counter()
        self.c0 = (c.seconds, c.programs, c.hits)
        return self

    def __exit__(self, exc_type, *_) -> None:
        if exc_type is None:
            c, (s0, p0, h0) = self.clock, self.c0
            print(f"[{self.name}] wall_s={time.perf_counter() - self.t0:.3f}",
                  flush=True)
            print(f"[{self.name}] xla_compile_s={c.seconds - s0:.3f} "
                  f"programs={c.programs - p0} cache_hits={c.hits - h0}",
                  flush=True)


def fb15k_shaped_graph(seed: int):
    """A planted-translation graph with FB15k's entity, relation and split
    counts (``data/kg.synthetic_kg``; nothing is downloaded)."""
    from repro.data import kg as kg_lib

    total = N_TRAIN + N_VALID + N_TEST
    graph = kg_lib.synthetic_kg(
        seed, n_entities=N_ENTITIES, n_relations=N_RELATIONS,
        n_triplets=total, valid_frac=(N_VALID + 0.5) / total,
        test_frac=(N_TEST + 0.5) / total)
    sizes = (len(graph.train), len(graph.valid), len(graph.test))
    check(sizes == (N_TRAIN, N_VALID, N_TEST),
          f"graph splits {sizes} != FB15k's {(N_TRAIN, N_VALID, N_TEST)}")
    return graph


def eval_slice(graph, n: int):
    """The graph with only its first ``n`` test triples as queries.  The
    rest of the test split moves to train, so the known set that filtered
    ranking subtracts is the full graph's."""
    from repro.data import kg as kg_lib

    return kg_lib.KG(graph.n_entities, graph.n_relations,
                     np.concatenate([graph.train, graph.test[n:]]),
                     graph.valid, graph.test[:n])


def fit(graph, clock: CompileClock, seed: int, **kw):
    from repro import kg

    marks = []
    with Phase(f"fit {kw.get('backend', 'vmap')}", clock) as ph:
        res = kg.fit(graph, epochs=EPOCHS, seed=seed,
                     callback=lambda e, loss: marks.append(
                         (e, loss, time.perf_counter())),
                     **FIT, **kw)
        jax.block_until_ready(res.params)
    last = ph.t0
    for e, loss, t in marks:
        print(f"  epoch {e + 1}: loss={loss:.6f} wall_s={t - last:.3f}")
        last = t
    losses = [loss for _, loss, _ in marks]
    check(len(losses) == EPOCHS and all(np.isfinite(losses)),
          f"non-finite or missing epoch losses {losses}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    check(all(bool(np.isfinite(np.asarray(v)).all())
              for v in res.params.values()), "non-finite trained tables")
    return res


def filtered_metrics(ranks: dict) -> tuple[float, float]:
    r = np.concatenate([ranks["tail"], ranks["head"]]).astype(np.float64)
    return float(r.mean()), float((r <= 10).mean())


def compare(name: str, a: dict, b: dict, n: int | None = None) -> None:
    """Print how many ranks differ between two engines' rank vectors (the
    first ``n`` queries) and fail if their filtered metrics disagree."""
    for grp in ("raw_ranks", "filtered_ranks"):
        diff = sum(int(np.sum(a[grp][s][:n] != b[grp][s][:n]))
                   for s in ("tail", "head"))
        total = 2 * len(a[grp]["tail"][:n])
        print(f"  {name}: {grp} differing {diff}/{total}")
    (mr_a, h_a), (mr_b, h_b) = (
        filtered_metrics({s: v[:n] for s, v in x["filtered_ranks"].items()})
        for x in (a, b))
    print(f"  {name}: filtered MR {mr_a:.4f} vs {mr_b:.4f}, "
          f"hits@10 {h_a:.6f} vs {h_b:.6f}")
    check(abs(mr_a - mr_b) <= MR_REL_TOL * mr_b,
          f"{name}: filtered mean rank {mr_a} vs {mr_b}")
    check(abs(h_a - h_b) <= HITS_ABS_TOL, f"{name}: hits@10 {h_a} vs {h_b}")


def eval_program_has_kernel(params, graph) -> bool:
    """Whether the compiled program of the default device eval holds the
    Mosaic kernel (``tpu_custom_call``): lowered with the query layout
    ``entity_ranks_device`` builds for one worker."""
    from repro.core import eval_device
    from repro.core.models import get_model

    tails, heads = graph.eval_filter_candidates()
    S, C, Qp = eval_device._layout(len(graph.test), eval_device.DEFAULT_CHUNK, 1)
    q, tc, hc = (eval_device._shard(eval_device._pad_rows(a, Qp), 1, S, C)
                 for a in (graph.test, tails, heads))
    compiled = eval_device._entity_ranks_device.lower(
        get_model("transe"), params, q, tc, hc, norm="l1", backend="vmap",
        mesh=None, axis_name="workers", fused=True, relations=True).compile()
    return "tpu_custom_call" in compiled.as_text()


def evaluate(params, graph, clock: CompileClock) -> None:
    from repro import kg
    from repro.core import eval as host_eval
    from repro.core import eval_device
    from repro.kernels import ops

    g = eval_slice(graph, N_EVAL)
    masks = g.eval_filter_candidates()
    ranks = {}
    for name, kw in (("default", {}), ("exact", {"fused": False})):
        with Phase(f"eval device {name}", clock):
            m = kg.evaluate(params, "transe", g, engine="device", **kw)
        print(f"  entity_filtered {m['entity_filtered']}")
        print(f"  relation_prediction {m['relation_prediction']}")
        print(f"  triplet_classification_acc "
              f"{m['triplet_classification_acc']:.6f}")
        # the same call evaluate made: a jit cache hit, per-query ranks
        ranks[name] = eval_device.entity_ranks_device(
            params, g.test, "l1", masks, relations=True, **kw)
    with Phase("eval host", clock):
        ranks["host"] = host_eval.entity_inference(
            params, g.test[:N_HOST], "l1", g.known_set(),
            known_index=g.known_index(), return_ranks=True)
    check(ops.fused_eval_available("transe"),
          "the default device engine does not pick the rank_topk kernel")
    has_kernel = eval_program_has_kernel(params, g)
    print(f"  default eval program holds tpu_custom_call: {has_kernel}")
    check(has_kernel, "rank_topk kernel missing from the eval program")
    compare("default vs exact", ranks["default"], ranks["exact"])
    compare("default vs host", ranks["default"], ranks["host"], N_HOST)
    compare("exact vs host", ranks["exact"], ranks["host"], N_HOST)


def serve(kb, graph, out: Path, clock: CompileClock):
    from repro.kb import KnowledgeBase
    from repro.serve import KGServer

    with Phase("kb save/load", clock):
        path = out / "kb"
        shutil.rmtree(path, ignore_errors=True)
        kb.save(str(path))
        kb = KnowledgeBase.load(str(path))
    rows = graph.test[:N_REQUESTS]
    kinds = ("tails", "heads", "relations")
    with KGServer(kb, max_batch=16, default_k=10) as server:
        with Phase("serve warmup", clock):
            warm = server.warmup()
        print(f"  warm_compiles={warm}")
        with Phase("serve", clock):
            futures = []
            for i, (h, r, t) in enumerate(rows):
                kind = kinds[i % 3]
                if kind == "tails":
                    futures.append(server.submit(kind, h, r, filtered=True))
                elif kind == "heads":
                    futures.append(server.submit(kind, t, r, filtered=True))
                else:
                    futures.append(server.submit(kind, h, t))
            answers = [f.result(timeout=600) for f in futures]
        st = server.stats()
    print(f"  requests={st.requests} waves={st.waves} "
          f"p50_ms={st.p50_ms:.3f} p99_ms={st.p99_ms:.3f} "
          f"steady_recompiles={st.steady_recompiles}")
    # the engine's direct answers, one batch per kind.  The server pads a
    # wave to its bucket and the direct call pads to the engine's chunk:
    # on the chip the two programs may sum an energy in different orders,
    # so the answers must hold the same ids and energies equal to float32
    # rounding; how many are also bitwise equal is printed
    h, r, t = rows.T
    picks = {kind: np.arange(i, len(rows), 3) for i, kind in enumerate(kinds)}
    direct = {
        "tails": kb.query_tails(h[picks["tails"]], r[picks["tails"]],
                                k=10, filtered=True),
        "heads": kb.query_heads(t[picks["heads"]], r[picks["heads"]],
                                k=10, filtered=True),
        "relations": kb.query_relations(h[picks["relations"]],
                                        t[picks["relations"]], k=10),
    }
    mismatched, bitwise, worst = 0, 0, 0.0
    for kind, idx in picks.items():
        for j, i in enumerate(idx):
            ids, e = direct[kind].ids[j], direct[kind].energies[j]
            got = answers[i]
            rel = float(np.max(np.abs(got.energies - e) / np.abs(e)))
            worst = max(worst, rel)
            same_ids = np.array_equal(got.ids, ids)
            bitwise += same_ids and np.array_equal(got.energies, e)
            mismatched += not (same_ids and rel <= ENERGY_RTOL)
    print(f"  answers bitwise equal to the engine's direct answer: "
          f"{bitwise}/{len(answers)}; max relative energy difference "
          f"{worst!r}")
    print(f"  answers differing from the engine's direct answer: "
          f"{mismatched}/{len(answers)}")
    check(len(answers) == N_REQUESTS and mismatched == 0,
          "served answers differ from the engine's direct answers")
    check(st.steady_recompiles == 0,
          f"{st.steady_recompiles} steady-state recompiles")
    return kb


def update(kb, seed: int, clock: CompileClock) -> None:
    rng = np.random.default_rng(seed)
    delta = np.stack([rng.integers(0, N_ENTITIES, N_DELTA),
                      rng.integers(0, N_RELATIONS, N_DELTA),
                      rng.integers(0, N_ENTITIES, N_DELTA)], axis=1)
    # a few unseen entities, so the tables grow
    delta[:N_NEW_ENTITIES, 2] = N_ENTITIES + np.arange(N_NEW_ENTITIES)
    with Phase("update", clock):
        new = kb.update(delta.astype(np.int32), seed=seed)
        jax.block_until_ready(new.params)
    print(f"  {kb.n_entities} -> {new.n_entities} entities, "
          f"fingerprint {kb.fingerprint()} -> {new.fingerprint()}")
    check(all(bool(np.isfinite(np.asarray(v)).all())
              for v in new.params.values()), "non-finite updated tables")
    check(new.n_entities == N_ENTITIES + N_NEW_ENTITIES,
          f"tables did not grow to {N_ENTITIES + N_NEW_ENTITIES} rows")
    check(new.fingerprint() != kb.fingerprint(),
          "the update left the fingerprint unchanged")


def report_peak_bytes() -> None:
    for d in jax.devices():
        stats = d.memory_stats() or {}
        print(f"  {d}: peak_bytes_in_use={stats.get('peak_bytes_in_use')}")


def one_chip(graph, out: Path, seed: int, clock: CompileClock) -> None:
    res = fit(graph, clock, seed)
    evaluate(res.params, graph, clock)
    kb = serve(res.kb, graph, out, clock)
    update(kb, seed, clock)
    report_peak_bytes()


def four_chips(graph, seed: int, clock: CompileClock) -> None:
    """Sharded fit + sharded device eval over a 4-chip mesh, against the
    same fit and eval on chip 0 with the vmap backend."""
    from repro.core import eval_device

    check(len(jax.devices()) == 4, f"--chips 4 needs 4 devices, "
          f"found {len(jax.devices())}")
    mesh = jax.make_mesh((4,), ("workers",))
    sharded = dict(merge_transport="sparse", table_sharding="sharded")
    res = {"shard_map": fit(graph, clock, seed, backend="shard_map",
                            mesh=mesh, **sharded)}
    ent = res["shard_map"].params["ent"]
    table = ent.size * ent.dtype.itemsize
    for shard in ent.addressable_shards:
        print(f"  {shard.device}: entity table bytes at rest "
              f"{shard.data.nbytes} of {table} "
              f"({shard.data.nbytes / table:.4f})")
    report_peak_bytes()
    res["vmap"] = fit(graph, clock, seed, backend="vmap", **sharded)
    for name in ("ent", "rel"):
        a = np.asarray(res["shard_map"].params[name])
        b = np.asarray(res["vmap"].params[name])
        print(f"  params[{name!r}] max |shard_map - vmap| = "
              f"{float(np.max(np.abs(a - b)))!r}, "
              f"rows differing {int(np.sum(np.any(a != b, axis=1)))}"
              f"/{len(a)}")
    g = eval_slice(graph, N_EVAL)
    masks = g.eval_filter_candidates()
    ranks = {}
    for backend, kw in (("shard_map", {"mesh": mesh}), ("vmap", {})):
        with Phase(f"eval sharded {backend}", clock):
            ranks[backend] = eval_device.entity_ranks_device(
                res[backend].params, g.test, "l1", masks, n_workers=4,
                backend=backend, table_sharding="sharded", **kw)
    compare("shard_map vs vmap", ranks["shard_map"], ranks["vmap"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded multi-chip path and the "
                         "one-chip run it is compared with")
    ap.add_argument("--out", default=str(ROOT / "chiprun_out" / "chip_smoke"),
                    help="the only directory the smoke writes to")
    args = ap.parse_args(argv)

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    print(f"device: {device}", flush=True)
    if device["platform"] != "tpu":
        print("chip_smoke: no TPU found — nothing was run", file=sys.stderr)
        return 2

    sys.path.insert(0, str(ROOT / "src"))
    from repro import compile_cache

    print(f"compile cache: {compile_cache.enable()}", flush=True)
    clock = CompileClock()
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    with Phase("graph", clock):
        graph = fb15k_shaped_graph(args.seed)
    if args.chips == 4:
        four_chips(graph, args.seed, clock)
    else:
        one_chip(graph, out, args.seed, clock)
    print(f"total xla_compile_s={clock.seconds:.3f} "
          f"programs={clock.programs} cache_hits={clock.hits}")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
