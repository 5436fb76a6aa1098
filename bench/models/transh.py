"""TransH (Wang et al. 2014, AAAI): each relation has a hyperplane, with
unit normal ``w_r``, and a translation ``d_r`` on it; the head and tail
are projected onto the hyperplane before the translation, energy
``||h_p + d_r - t_p||_1`` with ``x_p = x - (w_r . x) w_r``.

Tables, by the program's names: ``ent`` ``(E, k)``, ``rel`` ``(R, k)``
(the translations) and ``norm`` ``(R, k)`` (the normals, indexed by
relation), drawn uniform with unit entity and normal rows; training
projects both back to unit length at the start of each epoch.  The energy
scales each normal to unit length itself, so the score is defined between
projections too.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference, weights

roles = {"ent": "ent", "rel": "rel", "norm": "rel"}


def tables(key, n_entities: int, n_relations: int, dim: int) -> dict:
    k_ent, k_rel, k_norm = jax.random.split(key, 3)
    return {"ent": weights.unit_rows(weights.uniform(k_ent, n_entities, dim)),
            "rel": weights.uniform(k_rel, n_relations, dim),
            "norm": weights.unit_rows(
                weights.uniform(k_norm, n_relations, dim))}


def constrain(t: dict) -> dict:
    return dict(t, ent=reference.unit_rows(t["ent"]),
                norm=reference.unit_rows(t["norm"]))


def _project(x, w):
    """``x`` less its component along the unit normal ``w``."""
    return x - jnp.sum(x * w, axis=-1, keepdims=True) * w


def energy(t: dict, trip, prec: str = "f32"):
    w = reference.unit_rows(t["norm"][trip[..., 1]])
    h = _project(t["ent"][trip[..., 0]], w)
    tail = _project(t["ent"][trip[..., 2]], w)
    return jnp.sum(jnp.abs(h + t["rel"][trip[..., 1]] - tail), axis=-1)


def candidates(t: dict, q, side: str, prec: str = "f32"):
    """Energy of every entity put in ``side`` of each row of ``q``:
    ``(B, E)``, every entity projected onto each row's hyperplane."""
    ent, r = t["ent"], t["rel"][q[:, 1]]
    w = reference.unit_rows(t["norm"][q[:, 1]])
    every = _project(ent[None], w[:, None, :])               # (B, E, k)
    if side == "tail":
        x = _project(ent[q[:, 0]], w) + r
        return jnp.sum(jnp.abs(x[:, None, :] - every), axis=-1)
    x = _project(ent[q[:, 2]], w) - r
    return jnp.sum(jnp.abs(every - x[:, None, :]), axis=-1)


def relations(t: dict, q, prec: str = "f32"):
    """Energy of every relation between the head and tail of each row:
    ``(B, R)``, the pair projected onto every relation's hyperplane."""
    w = reference.unit_rows(t["norm"])[None]                # (1, R, k)
    h = _project(t["ent"][q[:, 0]][:, None, :], w)          # (B, R, k)
    tail = _project(t["ent"][q[:, 2]][:, None, :], w)
    return jnp.sum(jnp.abs(h + t["rel"][None] - tail), axis=-1)


def answer_scale(t: dict, kind: str, a, b) -> np.ndarray:
    """A distance needs no scale: 1 for every query."""
    return np.ones(len(a))


def energy_ops(dim: int) -> int:
    """The unit normal (square, sum, divide: 3 per column), both
    projections (multiply, sum, scale, subtract: 8), then add, subtract,
    abs and sum (4): 15 per column."""
    return 15 * dim


def candidate_ops(dim: int) -> int:
    """One candidate of one query in the scan: its projection onto the
    query's hyperplane (4 per column), then subtract, abs and sum (3)."""
    return 7 * dim


def relation_ops(dim: int) -> int:
    """One relation of one query in the relation scan: the head's and the
    tail's projections (8 per column), then add, subtract, abs and sum
    (4).  The unit normals are made once a scan, not once a query."""
    return 12 * dim
