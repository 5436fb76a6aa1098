"""The operation and byte counts, checked at small shapes against a count
of the reference's own arithmetic: each model file's energy and scans are
traced, and every elementwise operation and every term of a sum that runs
once per column (per row and column in a scan) is counted, element by
element."""
from __future__ import annotations

from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import flops, harness

MODELS = sorted(p.stem for p in
                (Path(harness.__file__).parent / "models").glob("*.py"))
ARITHMETIC = {"add", "sub", "mul", "div", "abs", "neg", "max", "min",
              "sqrt", "rsqrt", "exp", "log", "square", "integer_pow", "pow"}


def _count(jaxpr, at_least: int) -> int:
    """Operations of ``jaxpr`` on at least ``at_least`` elements: an
    elementwise operation by its output's size, a sum by its input's, a
    matrix product at a multiply and an add a term."""
    ops = 0
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        for sub in eqn.params.values():     # a nested (jitted) call
            sub = getattr(sub, "jaxpr", sub)
            if hasattr(sub, "eqns"):
                ops += _count(sub, at_least)
        if name in ARITHMETIC:
            n = int(np.prod(eqn.outvars[0].aval.shape))
        elif name == "reduce_sum":
            n = int(np.prod(eqn.invars[0].aval.shape))
        elif name == "dot_general":
            (contract, _), _ = eqn.params["dimension_numbers"]
            k = int(np.prod([eqn.invars[0].aval.shape[d] for d in contract]))
            n = 2 * k * int(np.prod(eqn.outvars[0].aval.shape))
        else:
            continue
        if n >= at_least:
            ops += n
    return ops


def _traced(model: str, fn: str, *args, **kw):
    m = harness.model(model)
    t = m.tables(jax.random.PRNGKey(0), 10, 3, kw.pop("dim"))
    return jax.make_jaxpr(
        lambda t, *a: getattr(m, fn)(t, *a, **kw))(t, *args).jaxpr


def _energy_by_hand(model: str, dim: int) -> int:
    """One triple's energy: everything it computes on ``dim`` columns."""
    trip = jnp.array([1, 2, 3])
    return _count(_traced(model, "energy", trip, dim=dim), dim)


@pytest.mark.parametrize("model", MODELS)
@pytest.mark.parametrize("dim", [2, 3, 8])
def test_energy_ops(model, dim):
    assert flops.energy_ops(model, dim) == _energy_by_hand(model, dim)


@pytest.mark.parametrize("model", MODELS)
def test_train_ops_per_triple(model):
    dim = 5
    forward = 2 * _energy_by_hand(model, dim)     # positive and negative
    assert flops.train_ops_per_triple(model, dim) == 3 * forward


@pytest.mark.parametrize("model", MODELS)
def test_scan_ops(model):
    """Both sides of the entity scan: the work done for every (row,
    candidate) pair, not once a row or once a candidate."""
    rows, cands, dim = 2, 10, 4
    q = jnp.array([[1, 2, 3], [4, 0, 5]])
    for side in ("tail", "head"):
        by_hand = _count(_traced(model, "candidates", q, side=side,
                                 dim=dim),
                         rows * cands * dim)
        assert flops.scan_ops(model, dim, rows, cands) == by_hand


@pytest.mark.parametrize("model", MODELS)
def test_relation_scan_ops(model):
    rows, rels, dim = 2, 3, 4
    q = jnp.array([[1, 0, 3], [4, 0, 5]])
    by_hand = _count(_traced(model, "relations", q, dim=dim),
                     rows * rels * dim)
    assert flops.relation_scan_ops(model, dim, rows, rels) == by_hand


@pytest.mark.parametrize("model", MODELS)
def test_eval_ops_per_test_triple(model):
    dim, E, R, V, T = 4, 10, 3, 6, 5
    per_query = (flops.scan_ops(model, dim, 1, E)              # tail side
                 + flops.scan_ops(model, dim, 1, E)            # head side
                 + flops.relation_scan_ops(model, dim, 1, R))  # relation
    classify = (2 * V + 2 * T) * flops.energy_ops(model, dim)
    got = flops.eval_ops_per_test_triple(model, dim, E, R, V, T)
    assert got == pytest.approx(per_query + classify / T)


def test_rank_topk_work():
    rows, E, dim = 2, 3, 4
    ops = sum(3 * dim + 2 for _ in range(rows) for _ in range(E))
    nbytes = 4 * (E * dim + rows * dim + rows + rows)
    assert flops.rank_topk_work(dim, rows, E) == (ops, nbytes)


def test_rank_topk_work_ignores_padding():
    """The count is of the problem, so the kernel's 512-wide padded table
    and its tiles do not enter it."""
    ops, nbytes = flops.rank_topk_work(400, 59071, 14951)
    assert ops == 59071 * 14951 * (3 * 400 + 2)
    assert nbytes == 4 * (14951 * 400 + 59071 * 400 + 2 * 59071)


def test_roofline_share():
    share, bound = flops.roofline_share(1e12, 1e9, 1.0, 1e14, 1e12)
    assert bound == "compute" and share == pytest.approx(1.0)
    share, bound = flops.roofline_share(1e9, 1e12, 2.0, 1e14, 1e12)
    assert bound == "memory" and share == pytest.approx(50.0)
