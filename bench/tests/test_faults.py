"""Each fault a cell can have, planted in the program underneath a tiny
run on the CPU, must turn ``correct`` false: a training step that returns
its state unchanged, half of each batch left out (the mean over the rest),
the Reduce's exchange between chips left out, and an answer altered where
it is produced."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from bench.tests import tiny


@pytest.fixture
def fresh():
    jax.clear_caches()
    yield
    jax.clear_caches()


def test_step_returning_its_state_unchanged(monkeypatch, fresh):
    from repro.core import mapreduce

    make = mapreduce.make_block_fn

    def frozen(*a, **kw):
        block = make(*a, **kw)

        def run(state, ids):
            out = block(jax.tree.map(jnp.copy, state), ids)
            return (state,) + tuple(out[1:])
        return run

    monkeypatch.setattr(mapreduce, "make_block_fn", frozen)
    out = tiny.run("transe-fb15k.train", seconds=0.2)
    assert not out["correct"]
    assert out["compared"]["step1_change_gap"][0] == pytest.approx(1.0)


def test_half_of_each_batch_left_out(monkeypatch, fresh):
    from repro.core.models import base

    full = base.KGModel.margin_loss

    def half(self, params, pos, neg, **kw):
        n = pos.shape[0] // 2
        return full(self, params, pos[:n], neg[:n], **kw)

    monkeypatch.setattr(base.KGModel, "margin_loss", half)
    out = tiny.run("transe-fb15k.train", seconds=0.2)
    assert not out["correct"], out["compared"]


def test_exchange_between_chips_left_out():
    code = """
import json
from repro.core import mapreduce
from bench.tests import tiny

def no_exchange(model, cfg, local, stats, loss, key, base, *a):
    import jax.numpy as jnp
    return local, jnp.zeros((), jnp.int32)

mapreduce._merge_tables_sparse_collective = no_exchange
print(json.dumps(tiny.run("transe-fb15k.train-4chip", seconds=0.2)))
"""
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=f"{root}/src:{root}")
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=root,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert not out["correct"], out["compared"]


def test_ranks_altered_where_produced(monkeypatch, fresh):
    from repro.core import eval_device

    chunk = eval_device._entity_chunk

    def altered(model, params, q, cands, side, norm, fused):
        raw, filt = chunk(model, params, q, cands, side, norm, fused)
        return raw.at[0].add(50), filt.at[0].add(50)

    monkeypatch.setattr(eval_device, "_entity_chunk", altered)
    out = tiny.run("transe-fb15k.eval", seconds=0.2)
    assert not out["correct"], out["compared"]


def test_classification_altered_where_produced(monkeypatch, fresh):
    from repro.core import eval as host_eval

    accuracy = host_eval._threshold_accuracy

    def altered(*a, **kw):
        test = a[6]
        return accuracy(*a, **kw) + 1.0 / (2 * len(test))

    monkeypatch.setattr(host_eval, "_threshold_accuracy", altered)
    out = tiny.run("transe-fb15k.eval", seconds=0.2)
    assert not out["correct"], out["compared"]
    assert out["compared"]["rank_gap"][0] == 0


def test_served_answer_altered_where_produced(monkeypatch):
    from repro.serve import kg_engine

    topk = kg_engine.KGQueryEngine._entity_topk

    def altered(self, *a, **kw):
        res = topk(self, *a, **kw)
        ids = res.ids.copy()
        ids[:, 0] = (ids[:, 0] + 1) % self.n_entities
        return kg_engine.QueryResult(ids, res.energies)

    monkeypatch.setattr(kg_engine.KGQueryEngine, "_entity_topk", altered)
    out = tiny.run("distmult-fb15k.serve", seconds=0.5)
    assert not out["correct"], out["compared"]
