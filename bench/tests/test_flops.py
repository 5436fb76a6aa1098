"""The operation and byte counts, checked at small shapes against a count
made by hand, element by element, from the same definitions."""
from __future__ import annotations

import pytest

from bench import flops


def _energy_by_hand(model: str, dim: int) -> int:
    ops = 0
    for _ in range(dim):
        ops += 2 if model == "transe" else 2      # h + r, - t | h*r, *t
        ops += 1 if model == "transe" else 0      # |.|
        ops += 1                                  # sum term
    return ops


@pytest.mark.parametrize("model", ["transe", "distmult"])
@pytest.mark.parametrize("dim", [1, 3, 8])
def test_energy_ops(model, dim):
    assert flops.energy_ops(model, dim) == _energy_by_hand(model, dim)


@pytest.mark.parametrize("model", ["transe", "distmult"])
def test_train_ops_per_triple(model):
    dim = 5
    forward = 2 * _energy_by_hand(model, dim)     # positive and negative
    assert flops.train_ops_per_triple(model, dim) == 3 * forward


@pytest.mark.parametrize("model,per", [("transe", 3), ("distmult", 2)])
def test_scan_ops(model, per):
    rows, cands, dim = 3, 7, 4
    by_hand = sum(per * dim for _ in range(rows) for _ in range(cands))
    assert flops.scan_ops(model, dim, rows, cands) == by_hand


def test_eval_ops_per_test_triple():
    dim, E, R, V, T = 4, 10, 3, 6, 5
    per_query = (flops.scan_ops("transe", dim, 1, E)        # tail side
                 + flops.scan_ops("transe", dim, 1, E)      # head side
                 + flops.scan_ops("transe", dim, 1, R))     # relation
    classify = (2 * V + 2 * T) * flops.energy_ops("transe", dim)
    got = flops.eval_ops_per_test_triple("transe", dim, E, R, V, T)
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
