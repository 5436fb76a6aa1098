"""Embedding tables made on the device from a seed, in one jitted call.

Uniform(-6/sqrt(k), 6/sqrt(k)) rows (TransE's Algorithm 1, lines 1-4),
with the constraint each model trains under already applied: unit-L2
relation rows for TransE, unit-L2 entity rows for DistMult.  The tables
are the benchmark's, not the program's: the reference regenerates them
from the same seed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A key from every bit of ``seed`` (``PRNGKey`` keeps 32)."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def unit_rows(x: jax.Array) -> jax.Array:
    return x / (jnp.linalg.norm(x, axis=-1, keepdims=True) + 1e-12)


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _tables(key, model: str, n_entities: int, n_relations: int, dim: int):
    bound = 6.0 / jnp.sqrt(float(dim))
    k_ent, k_rel = jax.random.split(key)
    ent = jax.random.uniform(k_ent, (n_entities, dim), jnp.float32,
                             -bound, bound)
    rel = jax.random.uniform(k_rel, (n_relations, dim), jnp.float32,
                             -bound, bound)
    if model == "transe":
        rel = unit_rows(rel)
    elif model == "distmult":
        ent = unit_rows(ent)
    else:
        raise ValueError(f"no tables for model {model!r}")
    return {"ent": ent, "rel": rel}


def make(config: dict, seed: int, device=None) -> dict:
    g = config["graph"]
    key = seed_key(seed)
    if device is not None:
        key = jax.device_put(key, device)
    return _tables(key, config["model"], g["n_entities"],
                   g["n_relations"], config["dim"])
