"""The cells this benchmark gained with RotatE, rehearsed on the CPU.

* ``rotate-fb15k.eval`` at a tiny size reads ``correct`` true through the
  plain and the kernel path (interpret mode), false with a rank or the
  accuracy altered where they are produced, and its bfloat16 control
  fails the rehearsal's limits (at the cell's size on the chip,
  ``python bench/control.py`` reads it against the committed ones);
* ``bench/models/rotate.py``'s counts match its own traced arithmetic,
  and the kernel's work is the scan's per candidate plus the compare and
  count.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from bench import control, flops, harness
from bench.tests import tiny
from bench.tests.test_flops import _count, _traced

CELL = "rotate-fb15k.eval"


@pytest.fixture
def fresh():
    jax.clear_caches()
    yield
    jax.clear_caches()


def test_cell_is_named_with_its_metrics():
    spec = tiny.spec()
    cells = {c["name"]: c for c in spec["workloads"]}
    assert cells[CELL]["config"] == "rotate-fb15k"
    assert cells[CELL]["chips"] == 1
    reading = {m["name"] for m in spec["end_to_end"] + spec["per_layer"]
               if CELL in m.get("workloads", [CELL])}
    assert {"eval_triples_per_s", "setup_s", "rotate_scan_roofline",
            "scan_useful_share", "filter_useful_share"} <= reading
    config = harness.load_json(harness.find("configs", "rotate-fb15k"))
    assert config["model"] == "rotate" and config["dim"] == 1000
    assert config["reduced"] == []


def test_cell_runs_and_is_correct(fresh):
    out = tiny.run(CELL)
    assert out["correct"], out["compared"]
    assert set(out["metrics"]) == {"eval_triples_per_s", "setup_s"}


def test_through_the_kernel_in_interpret_mode(fresh):
    """The fused path on the CPU: the modulus kernel's ranks read
    ``correct``, and the scan counters give ``scan_useful_share``."""
    from jax.experimental.pallas import tpu as pltpu

    from repro import obs

    obs.reset()
    with pltpu.force_tpu_interpret_mode():
        out = tiny.run(CELL, seconds=0.2, mix={"fused": True})
    assert out["correct"], out["compared"]
    c = obs.counters()
    assert 0 < c["eval.scan_useful_cells"] < c["eval.scan_cells"]
    share = harness.load_module(
        harness.find("metrics", "scan_useful_share")).read(
            {"cell": harness.Cell(CELL, {}, {"entry": "evaluate"}, 1, 0,
                                  None)})
    assert 0.0 < share < 100.0


def test_rank_altered_reads_false(monkeypatch, fresh):
    from repro.core import eval_device

    chunk = eval_device._entity_chunk

    def altered(model, params, q, cands, side, norm, fused):
        raw, filt = chunk(model, params, q, cands, side, norm, fused)
        return raw.at[0].add(50), filt.at[0].add(50)

    monkeypatch.setattr(eval_device, "_entity_chunk", altered)
    out = tiny.run(CELL, seconds=0.2)
    assert not out["correct"], out["compared"]


def test_accuracy_altered_reads_false(monkeypatch, fresh):
    from repro.core import eval as host_eval

    accuracy = host_eval._threshold_accuracy

    def altered(*a, **kw):
        return accuracy(*a, **kw) + 1.0 / (2 * len(a[6]))

    monkeypatch.setattr(host_eval, "_threshold_accuracy", altered)
    out = tiny.run(CELL, seconds=0.2)
    assert not out["correct"], out["compared"]
    assert out["compared"]["rank_gap"][0] <= 1


def test_bf16_control_fails_the_limits():
    _, config, mix, limits = tiny.cell(CELL)
    for seed in (3, 2**33 + 4):
        got = control.readings(config, mix, seed)
        assert control.verdicts(got, limits)["control_bf16"] is False, got


@pytest.mark.parametrize("dim", [2, 5])
def test_energy_counts_its_arithmetic_and_no_transcendental(dim):
    """14 counted operations a column; the phase's cosine and sine are
    the only other operations on ``dim`` elements."""
    jaxpr = _traced("rotate", "energy", jnp.array([1, 2, 3]), dim=dim)
    assert flops.energy_ops("rotate", dim) == _count(jaxpr, dim)
    names = sorted(e.primitive.name for e in jaxpr.eqns
                   if e.primitive.name in ("cos", "sin"))
    assert names == ["cos", "sin"]


def test_scan_work_is_the_scan_plus_compare_and_count():
    m = harness.model("rotate")
    rows, E, dim = 3, 10, 4
    q = jnp.array([[1, 2, 3], [4, 0, 5], [7, 1, 2]])
    traced = _count(_traced("rotate", "candidates", q, side="tail",
                            dim=dim), rows * E * dim)
    ops, nbytes = m.scan_work(dim, rows, E)
    assert ops == traced + 2 * rows * E
    assert nbytes == 4 * (E * 2 * dim + rows * 2 * dim + rows + rows)
    ops, _ = m.scan_work(1000, 2 * 59071, 14951)
    assert ops == 2 * 59071 * 14951 * (7 * 1000 + 2)

