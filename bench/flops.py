"""Operations and bytes the work requires, counted from the problem's
shapes (rows, entities, relations, unpadded width) and never from a
kernel's tiles, padding or chunking, so a retiled kernel is read against
the same work.

An elementwise add, subtract, multiply, divide, absolute value or
comparison is one operation; a sum over ``k`` terms is ``k`` operations.
What one energy, one scanned candidate and one scanned relation cost is
the scoring model's own count (``bench/models/<model>.py``).
"""
from __future__ import annotations

from bench import harness


def energy_ops(model: str, dim: int) -> int:
    """One triple's energy, by the model's file (TransE-L1 ``sum |h + r -
    t|``: 4 per column; DistMult ``-sum h r t``: 3 per column)."""
    return harness.model(model).energy_ops(dim)


def train_ops_per_triple(model: str, dim: int) -> int:
    """One trained triple with its one negative: the forward pass scores
    both (two energies), and the backward pass with the update is counted,
    as is usual, at twice the forward pass."""
    return 3 * 2 * energy_ops(model, dim)


def scan_ops(model: str, dim: int, rows: int, candidates: int) -> int:
    """Scoring ``candidates`` entities against ``rows`` prepared queries,
    at the model's cost of one (query, candidate) pair (TransE-L1
    subtract, abs and sum: 3 per column; DistMult a multiply-add: 2)."""
    return harness.model(model).candidate_ops(dim) * rows * candidates


def relation_scan_ops(model: str, dim: int, rows: int,
                      relations: int) -> int:
    """Scoring every one of ``relations`` relations between the head and
    tail of ``rows`` queries, at the model's cost of one (query, relation)
    pair."""
    return harness.model(model).relation_ops(dim) * rows * relations


def eval_ops_per_test_triple(model: str, dim: int, n_entities: int,
                             n_relations: int, n_valid: int,
                             n_test: int) -> float:
    """The paper's three tasks per test triple: both sides of the entity
    scan, the relation scan, and triple classification's four energies
    (valid and test, true and corrupted) spread over the test triples."""
    scans = (scan_ops(model, dim, 1, 2 * n_entities)
             + relation_scan_ops(model, dim, 1, n_relations))
    classify = 2 * (n_valid + n_test) * energy_ops(model, dim) / n_test
    return scans + classify


def rank_topk_work(dim: int, rows: int, n_entities: int) -> tuple:
    """(operations, bytes) of ``rank_topk`` ranking ``rows`` L1 queries
    against an ``n_entities`` table: subtract, abs and sum per column, plus
    the compare and count per entity; the table and the queries are read
    once, the gold distances read and the counts written once (float32)."""
    ops = rows * n_entities * (3 * dim + 2)
    nbytes = 4 * (n_entities * dim + rows * dim + 2 * rows)
    return ops, nbytes


def roofline_share(ops: float, nbytes: float, seconds: float,
                   peak_flops: float, peak_bw: float) -> tuple:
    """(share in %, bound) for work measured at ``seconds``: the least
    time the chip could take over the time taken; ``bound`` names which
    of the two peaks sets that least time."""
    t_ops, t_bytes = ops / peak_flops, nbytes / peak_bw
    bound = "compute" if t_ops >= t_bytes else "memory"
    return 100.0 * max(t_ops, t_bytes) / seconds, bound
