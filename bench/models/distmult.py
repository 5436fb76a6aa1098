"""DistMult (Yang et al. 2015, ICLR): a diagonal bilinear score, energy
``-sum(h * r * t)``.

Tables, by the program's names: ``ent`` ``(E, k)`` and ``rel`` ``(R, k)``,
drawn uniform with unit entity rows; training projects the entity rows to
unit length at the start of each epoch.  The scans are matrix multiplies,
at the precision ``prec`` names (``reference.dot``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference, weights

roles = {"ent": "ent", "rel": "rel"}


def tables(key, n_entities: int, n_relations: int, dim: int) -> dict:
    k_ent, k_rel = jax.random.split(key)
    ent = weights.unit_rows(weights.uniform(k_ent, n_entities, dim))
    rel = weights.uniform(k_rel, n_relations, dim)
    return {"ent": ent, "rel": rel}


def constrain(t: dict) -> dict:
    return dict(t, ent=reference.unit_rows(t["ent"]))


def energy(t: dict, trip, prec: str = "f32"):
    h, r = t["ent"][trip[..., 0]], t["rel"][trip[..., 1]]
    return -jnp.sum(h * r * t["ent"][trip[..., 2]], axis=-1)


def candidates(t: dict, q, side: str, prec: str = "f32"):
    """Energy of every entity put in ``side`` of each row of ``q``:
    ``(B, E)``."""
    ent, r = t["ent"], t["rel"][q[:, 1]]
    fixed = ent[q[:, 0]] if side == "tail" else ent[q[:, 2]]
    return -reference.dot(fixed * r, ent.T, prec)


def relations(t: dict, q, prec: str = "f32"):
    """Energy of every relation between the head and tail of each row:
    ``(B, R)``."""
    h, tail = t["ent"][q[:, 0]], t["ent"][q[:, 2]]
    return -reference.dot(h * tail, t["rel"].T, prec)


def answer_scale(t: dict, kind: str, a, b) -> np.ndarray:
    """``||q||_2 * max ||c||_2``: the query vector's length (``h * r`` or
    ``t * r``; ``h * t`` for relations) times the longest candidate row,
    which bounds every energy of the query."""
    if kind == "relations":
        qv, other = t["ent"][a] * t["ent"][b], t["rel"]
    else:                       # (h, r) for tails, (t, r) for heads
        qv, other = t["ent"][a] * t["rel"][b], t["ent"]
    rows = jnp.linalg.norm(jnp.asarray(other, jnp.float32), axis=1)
    return np.asarray(jnp.linalg.norm(qv, axis=1) * jnp.max(rows))


def energy_ops(dim: int) -> int:
    """Two multiplies and the sum: 3 per column."""
    return 3 * dim


def candidate_ops(dim: int) -> int:
    """One candidate of one query in the scan: a multiply-add, 2 per
    column."""
    return 2 * dim


def relation_ops(dim: int) -> int:
    """One relation of one query in the relation scan: a multiply-add, 2
    per column."""
    return 2 * dim
