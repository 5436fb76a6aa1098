"""A scoring model comes into the benchmark as one file,
``bench/models/<model>.py``.

* What the two TransE and DistMult cells compare is held to the digests
  that ``record_invariants.py`` wrote before the models moved into their
  files: the tables from a seed, the reference's outputs at the rehearsal
  size and the operation counts at FB15k's shape, all exactly.
* Every model file rehearses through the unchanged ``fit`` and
  ``evaluate`` entries with ``correct`` true, and with ``correct`` false
  where any one of its tables is left unmoved by the program's step, or
  where the Reduce's exchange is left out.
* The reference's Reduce averages a table with trailing axes row by row.
"""
from __future__ import annotations

import json
import re
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, reference
from bench.tests import record_invariants, tiny

BENCH = Path(harness.__file__).resolve().parent
MODELS = sorted(p.stem for p in (BENCH / "models").glob("*.py"))
RECORDED = json.loads(record_invariants.OUT.read_text())


@pytest.fixture
def fresh():
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("kind", record_invariants.PARTS)
@pytest.mark.parametrize("name", record_invariants.CONFIGS)
def test_what_the_cells_compare_is_unchanged(name, kind):
    got = record_invariants.part(kind, name)
    want = {k: v for k, v in RECORDED.items()
            if k.startswith(f"{name}.{kind}.")}
    assert got.keys() == want.keys()
    assert {k for k in got if got[k] != want[k]} == set()


def test_the_shared_files_name_no_model():
    """Weights, reference, op counts, the harness, the entries, the
    metrics and the controls dispatch through the model's file."""
    shared = [BENCH / f for f in ("weights.py", "reference.py", "flops.py",
                                  "harness.py", "control.py")]
    shared += sorted((BENCH / "entries").glob("*.py"))
    shared += sorted((BENCH / "metrics").glob("*.py"))
    quoted = re.compile("[\"'](" + "|".join(MODELS) + ")[\"']")
    named = {p.name: quoted.findall(p.read_text()) for p in shared}
    assert {k: v for k, v in named.items() if v} == {}


@pytest.mark.parametrize("model", MODELS)
def test_model_file_provides_everything(model):
    m = harness.model(model)
    for attr in ("tables", "constrain", "energy", "candidates", "relations",
                 "answer_scale", "energy_ops", "candidate_ops",
                 "relation_ops"):
        assert callable(getattr(m, attr)), attr
    assert set(m.roles.values()) == {"ent", "rel"}
    t = m.tables(jax.random.PRNGKey(0), 11, 5, 8)
    assert set(t) == set(m.roles)
    for k, v in t.items():
        assert v.shape[0] == {"ent": 11, "rel": 5}[m.roles[k]]
        assert v.dtype == jnp.float32


@pytest.mark.parametrize("entry", ["transe-fb15k.train",
                                   "transe-fb15k.eval"])
@pytest.mark.parametrize("model", MODELS)
def test_model_rehearses_correct(model, entry, fresh):
    out = tiny.run(entry, config={"model": model}, seconds=0.3)
    assert out["correct"], out["compared"]


def _frozen(table):
    """``make_block_fn`` whose block hands ``table`` back as it came in."""
    from repro.core import mapreduce

    make = mapreduce.make_block_fn

    def made(*a, **kw):
        block = make(*a, **kw)

        def run(state, ids):
            before = jnp.copy(state[table])
            out = block(state, ids)
            return (dict(out[0], **{table: before}),) + tuple(out[1:])
        return run
    return made


@pytest.mark.parametrize("model,table", [
    (m, k) for m in MODELS for k in sorted(harness.model(m).roles)])
def test_table_left_unmoved_reads_false(model, table, monkeypatch, fresh):
    from repro.core import mapreduce

    monkeypatch.setattr(mapreduce, "make_block_fn", _frozen(table))
    out = tiny.run("transe-fb15k.train", config={"model": model},
                   seconds=0.2)
    assert not out["correct"], out["compared"]


@pytest.mark.parametrize("model", MODELS)
def test_exchange_left_out_reads_false(model, monkeypatch, fresh):
    """The Reduce keeps worker 0's tables instead of merging the four."""
    from repro.core import mapreduce

    def no_exchange(model, strategy, stacked, stats, merge_key):
        return {k: v[0] for k, v in stacked.items()}

    monkeypatch.setattr(mapreduce, "_merge_tables_stacked", no_exchange)
    out = tiny.run("transe-fb15k.train", config={"model": model},
                   seconds=0.2)
    assert not out["correct"], out["compared"]


def test_reduce_over_trailing_axes():
    """A ``(R, 2, 3)`` table: each row the touch-weighted mean of the
    workers' rows, an untouched row the plain mean; by hand in numpy."""
    rng = np.random.default_rng(0)
    W, R = 3, 5
    stacked = rng.normal(size=(W, R, 2, 3)).astype(np.float32)
    count = rng.integers(0, 3, size=(W, R)).astype(np.float32)
    count[:, 2] = 0.0
    want = np.empty((R, 2, 3), np.float32)
    for r in range(R):
        n = count[:, r].sum()
        if n:
            want[r] = sum(count[w, r] * stacked[w, r] for w in range(W)) / n
        else:
            want[r] = stacked[:, r].mean(axis=0)
    got = np.asarray(reference.merge(jnp.asarray(stacked),
                                     jnp.asarray(count)))
    assert got.shape == (R, 2, 3)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-7)


def test_training_reference_keeps_a_table_with_trailing_axes(monkeypatch):
    """A model with a relation-indexed ``(R, 2, 3)`` table trains in the
    reference: the table stays in every state, moves where the batches
    touch it, and under the exchange left out differs from the merge."""
    transe = harness.model("transe")

    def tables(key, n_entities, n_relations, dim):
        k, k_pair = jax.random.split(key)
        return dict(transe.tables(k, n_entities, n_relations, dim),
                    pair=jax.random.normal(k_pair, (n_relations, 2, 3)))

    def energy(t, trip, prec="f32"):
        h, tail = t["ent"][trip[..., 0], :2], t["ent"][trip[..., 2], :3]
        bilinear = jnp.einsum("...i,...ij,...j->...", h,
                              t["pair"][trip[..., 1]], tail)
        return transe.energy(t, trip, prec) + bilinear

    fake = types.SimpleNamespace(
        roles={"ent": "ent", "rel": "rel", "pair": "rel"}, tables=tables,
        constrain=transe.constrain, energy=energy)
    real = harness.model
    monkeypatch.setattr(harness, "model", lambda name: (
        fake if name == "trailing-axes" else real(name)))
    g = tiny.cell("transe-fb15k.train")[1]["graph"]
    from bench import graph as graph_lib

    train = graph_lib.structure(g).train
    t0 = tables(jax.random.PRNGKey(3), g["n_entities"], g["n_relations"], 8)
    kw = dict(n_workers=2, batch=32, margin=1.0, lr=0.01, epochs=2)
    losses, states = reference.train("trailing-axes", t0, train, 0, **kw)
    _, alone = reference.train("trailing-axes", t0, train, 0,
                               fault="no_exchange", **kw)
    assert len(losses) == 2
    for s in states:
        assert s["pair"].shape == (g["n_relations"], 2, 3)
    moved = np.abs(states[0]["pair"] - np.asarray(t0["pair"])).max()
    assert moved > 1e-4
    assert np.abs(states[0]["pair"] - alone[0]["pair"]).max() > 1e-6
