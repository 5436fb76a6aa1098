"""TransE (Bordes et al. 2013, NeurIPS): a relation translates the head
to the tail, energy ``||h + r - t||_1``.

Tables, by the program's names: ``ent`` ``(E, k)`` and ``rel`` ``(R, k)``,
drawn uniform with unit relation rows; training projects the entity rows
to unit length at the start of each epoch.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference, weights

roles = {"ent": "ent", "rel": "rel"}


def tables(key, n_entities: int, n_relations: int, dim: int) -> dict:
    k_ent, k_rel = jax.random.split(key)
    ent = weights.uniform(k_ent, n_entities, dim)
    rel = weights.unit_rows(weights.uniform(k_rel, n_relations, dim))
    return {"ent": ent, "rel": rel}


def constrain(t: dict) -> dict:
    return dict(t, ent=reference.unit_rows(t["ent"]))


def energy(t: dict, trip, prec: str = "f32"):
    h, r = t["ent"][trip[..., 0]], t["rel"][trip[..., 1]]
    return jnp.sum(jnp.abs(h + r - t["ent"][trip[..., 2]]), axis=-1)


def candidates(t: dict, q, side: str, prec: str = "f32"):
    """Energy of every entity put in ``side`` of each row of ``q``:
    ``(B, E)``."""
    ent, r = t["ent"], t["rel"][q[:, 1]]
    if side == "tail":
        x = ent[q[:, 0]] + r
        return jnp.sum(jnp.abs(x[:, None, :] - ent[None]), axis=-1)
    x = ent[q[:, 2]] - r
    return jnp.sum(jnp.abs(ent[None] - x[:, None, :]), axis=-1)


def relations(t: dict, q, prec: str = "f32"):
    """Energy of every relation between the head and tail of each row:
    ``(B, R)``."""
    h, tail = t["ent"][q[:, 0]], t["ent"][q[:, 2]]
    return jnp.sum(jnp.abs((h - tail)[:, None, :] + t["rel"][None]),
                   axis=-1)


def answer_scale(t: dict, kind: str, a, b) -> np.ndarray:
    """A distance needs no scale: 1 for every query."""
    return np.ones(len(a))


def energy_ops(dim: int) -> int:
    """Add, subtract, abs and the sum: 4 per column."""
    return 4 * dim


def candidate_ops(dim: int) -> int:
    """One candidate of one query in the scan: subtract, abs and sum, 3
    per column."""
    return 3 * dim


def relation_ops(dim: int) -> int:
    """One relation of one query in the relation scan: add, abs and sum,
    3 per column."""
    return 3 * dim
