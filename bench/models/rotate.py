"""RotatE (Sun et al. 2019, ICLR, arXiv:1902.10197): each relation rotates
the head in complex space, energy ``sum_j |h_j r_j - t_j|`` with
``r_j = exp(i theta_j)``.

Tables, by the program's names: ``ent`` ``(E, 2k)``, each row ``k``
complex numbers as ``[re | im]``, and ``rel`` ``(R, k)``, phase values;
both drawn uniform in ``+-(gamma + eps) / k`` with the authors' FB15k
settings gamma 24 and eps 2, and a value ``v`` is the phase ``v pi / ((gamma
+ eps) / k)``.  The modulus of a rotation is 1 by construction, so there is
no constraint to apply.

Scans measure each candidate from the rotated query, ``h r`` for tails
and ``t conj(r)`` for heads (``|h r - t| = |h - t conj(r)|`` as ``|r| =
1``; the authors' code scores a head batch so), ``BLOCK`` candidate rows
at a time so that the reference's query blocks never hold a ``(B, E, k)``
array.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

roles = {"ent": "ent", "rel": "rel"}
GAMMA, EPSILON = 24.0, 2.0
BLOCK = 2048          # candidate entity rows scored at a time


def _range(k: int) -> float:
    return (GAMMA + EPSILON) / k


def tables(key, n_entities: int, n_relations: int, dim: int) -> dict:
    k_ent, k_rel = jax.random.split(key)
    b = _range(dim)
    return {"ent": jax.random.uniform(k_ent, (n_entities, 2 * dim),
                                      jnp.float32, -b, b),
            "rel": jax.random.uniform(k_rel, (n_relations, dim),
                                      jnp.float32, -b, b)}


def constrain(t: dict) -> dict:
    return t


def _split(x):
    k = x.shape[-1] // 2
    return x[..., :k], x[..., k:]


def _rotation(rel):
    phase = rel * (math.pi / _range(rel.shape[-1]))
    return jnp.cos(phase), jnp.sin(phase)


def _moduli_sum(re, im):
    return jnp.sum(jnp.sqrt(re * re + im * im), axis=-1)


def energy(t: dict, trip, prec: str = "f32"):
    h_re, h_im = _split(t["ent"][trip[..., 0]])
    t_re, t_im = _split(t["ent"][trip[..., 2]])
    c, s = _rotation(t["rel"][trip[..., 1]])
    return _moduli_sum(h_re * c - h_im * s - t_re, h_re * s + h_im * c - t_im)


def candidates(t: dict, q, side: str, prec: str = "f32"):
    """Energy of every entity put in ``side`` of each row of ``q``:
    ``(B, E)``, ``BLOCK`` entities at a time."""
    c, s = _rotation(t["rel"][q[:, 1]])
    if side == "tail":
        re, im = _split(t["ent"][q[:, 0]])
        x_re, x_im = re * c - im * s, re * s + im * c
    else:
        re, im = _split(t["ent"][q[:, 2]])
        x_re, x_im = re * c + im * s, im * c - re * s
    e_re, e_im = _split(t["ent"])
    return jnp.concatenate([
        _moduli_sum(x_re[:, None, :] - e_re[None, lo:lo + BLOCK],
                    x_im[:, None, :] - e_im[None, lo:lo + BLOCK])
        for lo in range(0, e_re.shape[0], BLOCK)], axis=1)


def relations(t: dict, q, prec: str = "f32"):
    """Energy of every relation between the head and tail of each row:
    ``(B, R)``."""
    h_re, h_im = _split(t["ent"][q[:, 0]])
    t_re, t_im = _split(t["ent"][q[:, 2]])
    c, s = _rotation(t["rel"])
    re = h_re[:, None, :] * c[None] - h_im[:, None, :] * s[None]
    im = h_re[:, None, :] * s[None] + h_im[:, None, :] * c[None]
    return _moduli_sum(re - t_re[:, None, :], im - t_im[:, None, :])


def answer_scale(t: dict, kind: str, a, b) -> np.ndarray:
    """A distance needs no scale: 1 for every query."""
    return np.ones(len(a))


def energy_ops(dim: int) -> int:
    """Per complex column: the phase (a multiply), the rotation (four
    multiplies, a subtract and an add), the two differences, the modulus
    (two multiplies, an add and a square root) and the sum: 14.  Cosine
    and sine are not among the operations counted (``bench/flops.py``)."""
    return 14 * dim


def candidate_ops(dim: int) -> int:
    """One candidate of one query in the scan: two differences, two
    squares, an add, a square root and the sum, 7 per complex column."""
    return 7 * dim


def relation_ops(dim: int) -> int:
    """One relation of one query in the relation scan: the rotation (6 per
    complex column), the two differences, the modulus (4) and the sum: 13.
    The relations' cosines and sines are made once a scan, not once a
    query."""
    return 13 * dim


def scan_work(dim: int, rows: int, n_entities: int) -> tuple:
    """(operations, bytes) of the modulus rank kernel scanning ``rows``
    rotated queries against ``n_entities`` entity rows of ``dim`` complex
    columns: 7 per column plus the compare and count per entity; the
    table and the queries read once, the gold distances read and the
    counts written once (float32)."""
    ops = rows * n_entities * (7 * dim + 2)
    nbytes = 4 * (2 * n_entities * dim + 2 * rows * dim + 2 * rows)
    return ops, nbytes
