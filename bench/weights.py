"""Embedding tables made on the device from a seed, in one jitted call.

The scoring model's file (``bench/models/<model>.py``) draws them, each
with the constraint the model trains under already applied.  The draws
are uniform(-6/sqrt(k), 6/sqrt(k)) rows (TransE's Algorithm 1, lines
1-4).  The tables are the benchmark's, not the program's: the reference
regenerates them from the same seed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench import harness


def seed_key(seed: int) -> jax.Array:
    """A key from every bit of ``seed`` (``PRNGKey`` keeps 32)."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def uniform(key, rows: int, dim: int) -> jax.Array:
    """``(rows, dim)`` float32, uniform in ``[-6/sqrt(dim), 6/sqrt(dim))``."""
    bound = 6.0 / jnp.sqrt(float(dim))
    return jax.random.uniform(key, (rows, dim), jnp.float32, -bound, bound)


def unit_rows(x: jax.Array) -> jax.Array:
    return x / (jnp.linalg.norm(x, axis=-1, keepdims=True) + 1e-12)


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _tables(key, model: str, n_entities: int, n_relations: int, dim: int):
    return harness.model(model).tables(key, n_entities, n_relations, dim)


def make(config: dict, seed: int, device=None) -> dict:
    g = config["graph"]
    key = seed_key(seed)
    if device is not None:
        key = jax.device_put(key, device)
    return _tables(key, config["model"], g["n_entities"],
                   g["n_relations"], config["dim"])
