"""What the benchmark's tables, reference and operation counts give, as
digests: a change that must leave them as they are is held to the file
this writes.

    PYTHONPATH=src:. JAX_PLATFORMS=cpu python bench/tests/record_invariants.py

It writes ``bench/tests/data/invariants.json``: the sha256 of
``weights.make``'s tables for each configuration (at the rehearsal size
and at the configuration's own, two seeds each); of the reference's ranks,
classification accuracy, serve ``top_k`` answers, and training losses and
states, at the rehearsal size; and the operation counts at the
configuration's own shape.  Only public calls are used, so the same script
reads the tree before a change and after it.
"""
from __future__ import annotations

import copy
import hashlib
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[2]
OUT = Path(__file__).resolve().parent / "data" / "invariants.json"
CONFIGS = ("transe-fb15k", "distmult-fb15k")
SEEDS = (7, 2**33 + 4)
TINY = {"n_entities": 120, "n_relations": 7, "n_train": 1500,
        "n_valid": 100, "n_test": 100}
TINY_DIM = 16
TRAIN = {"n_workers": 4, "batch": 32, "epochs": 3}


def digest(x) -> str:
    """sha256 of arrays, dicts of arrays (by sorted key), lists of them,
    with each array's dtype and shape."""
    h = hashlib.sha256()

    def feed(v):
        if isinstance(v, dict):
            for k in sorted(v):
                h.update(k.encode())
                feed(v[k])
        elif isinstance(v, (list, tuple)):
            for item in v:
                feed(item)
        else:
            a = np.ascontiguousarray(np.asarray(v))
            h.update(f"{a.dtype}{a.shape}".encode())
            h.update(a.tobytes())

    feed(x)
    return h.hexdigest()


def _config(name: str, tiny: bool) -> dict:
    from bench import harness

    config = copy.deepcopy(harness.load_json(harness.find("configs", name)))
    if tiny:
        config["graph"].update(TINY)
        config["dim"] = TINY_DIM
    return config


def tables(config_name: str, seed: int, tiny: bool) -> dict:
    from bench import weights

    return weights.make(_config(config_name, tiny), seed)


def reference_readings(config_name: str, seed: int) -> dict:
    """Every reference output a cell or control compares, at the rehearsal
    size, as digests (and the accuracy and losses as numbers)."""
    import jax

    from bench import graph as graph_lib
    from bench import reference

    config = _config(config_name, tiny=True)
    if config.get("matmul_precision"):
        jax.config.update("jax_default_matmul_precision",
                          config["matmul_precision"])
    model = config["model"]
    t = tables(config_name, seed, tiny=True)
    g = graph_lib.generate(config["graph"], seed)
    known = reference.Known(g.all_triples, g.n_entities, g.n_relations)
    out = {}
    for prec in ("f32", "bf16", "high"):
        out[f"ranks.{prec}"] = digest(
            reference.ranks(model, t, g.test, known, prec=prec))
        out[f"accuracy.{prec}"] = reference.classify_accuracy(
            model, t, g.valid, g.test, g.n_entities, g.n_relations, prec)
    trip = reference.classification_triples(g.valid, g.test, g.n_entities)
    out["energies.f32"] = digest(reference.energies(model, t, trip))
    rng = np.random.default_rng(seed & 0xFFFF)
    a = rng.integers(0, g.n_entities, 24)
    b_rel = rng.integers(0, g.n_relations, 24)
    b_ent = rng.integers(0, g.n_entities, 24)
    for prec in ("f32", "high"):
        for kind in ("tails", "heads", "relations"):
            b = b_ent if kind == "relations" else b_rel
            for filt in (None, known) if kind != "relations" else (None,):
                got = reference.top_k(model, t, kind, a, b, 5, filt, prec)
                key = f"top_k.{kind}.{prec}.{'known' if filt else 'all'}"
                out[key] = digest(list(got))
    g_fit = graph_lib.structure(config["graph"])
    tr = config["train"]
    for prec, fault in (("f32", None), ("bf16", None), ("f32", "half_batch"),
                        ("f32", "no_exchange")):
        losses, states = reference.train(
            model, t, g_fit.train, 0, n_workers=TRAIN["n_workers"],
            batch=TRAIN["batch"], margin=tr["margin"],
            lr=tr["learning_rate"], epochs=TRAIN["epochs"], prec=prec,
            fault=fault)
        key = f"train.{prec}.{fault or 'sound'}"
        out[key + ".losses"] = losses
        out[key + ".states"] = digest(states)
    return out


def op_counts(config_name: str) -> dict:
    from bench import flops

    config = _config(config_name, tiny=False)
    model, dim, g = config["model"], config["dim"], config["graph"]
    ops, nbytes = flops.rank_topk_work(dim, 2 * g["n_test"],
                                       g["n_entities"])
    return {
        "energy_ops": flops.energy_ops(model, dim),
        "train_ops_per_triple": flops.train_ops_per_triple(model, dim),
        "scan_ops": flops.scan_ops(model, dim, g["n_test"],
                                   g["n_entities"]),
        "eval_ops_per_test_triple": flops.eval_ops_per_test_triple(
            model, dim, g["n_entities"], g["n_relations"], g["n_valid"],
            g["n_test"]),
        "rank_topk_work": [ops, nbytes],
    }


def part(kind: str, name: str) -> dict:
    """One configuration's ``tables``, ``reference`` or ``flops`` entries,
    under the keys the file holds them by."""
    out = {}
    if kind == "tables":
        for seed in SEEDS:
            for tiny in (True, False):
                size = "tiny" if tiny else "full"
                out[f"{name}.tables.{size}.{seed}"] = digest(
                    tables(name, seed, tiny))
    elif kind == "reference":
        for seed in SEEDS:
            for key, value in reference_readings(name, seed).items():
                out[f"{name}.reference.{seed}.{key}"] = value
    else:
        for key, value in op_counts(name).items():
            out[f"{name}.flops.{key}"] = value
    return out


PARTS = ("tables", "reference", "flops")


def record() -> dict:
    return {k: v for name in CONFIGS for kind in PARTS
            for k, v in part(kind, name).items()}


if __name__ == "__main__":
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    OUT.write_text(json.dumps(record(), indent=1, sort_keys=True) + "\n")
    print(f"wrote {OUT}")
