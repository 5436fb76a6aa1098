"""TransE (Bordes et al., 2013) — the scoring model the paper parallelizes.

Entities and relations are ``k``-dim vectors; a true triplet ``<h, r, t>``
should satisfy ``h + r ≈ t``.  Energy (Eq. 1 of the paper):

    d(h, r, t) = || h + r - t ||_{1 or 2}

Registered as ``"transe"``; it is the reference model for the fused Pallas
scoring kernel (``kernels/transe_score.py``), and the engine reproduces the
pre-refactor single-model code path bit-for-bit (tests/test_kg_api.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.models import base
from repro.core.models.base import KGConfig, Params, dissimilarity


class TransE(base.KGModel):
    name = "transe"
    roles = {"ent": "ent", "rel": "rel"}
    supports_fused_kernel = True

    def init_params(self, key: jax.Array, cfg: KGConfig) -> Params:
        """Uniform(-6/sqrt(k), 6/sqrt(k)) init; relations L2-normalized once
        (TransE Algorithm 1, lines 1-4 of the paper)."""
        k_ent, k_rel = jax.random.split(key)
        ent = base.uniform_table(k_ent, cfg.n_entities, cfg.dim, cfg.dtype)
        rel = base.uniform_table(k_rel, cfg.n_relations, cfg.dim, cfg.dtype)
        rel = rel / (jnp.linalg.norm(rel, axis=-1, keepdims=True) + 1e-12)
        return {"ent": ent, "rel": rel}

    def energy(
        self, params: Params, triplets: jax.Array, norm: str = "l1"
    ) -> jax.Array:
        h = params["ent"][triplets[..., 0]]
        r = params["rel"][triplets[..., 1]]
        t = params["ent"][triplets[..., 2]]
        return dissimilarity(h + r - t, norm)

    def normalize(self, params: Params) -> Params:
        """e <- e / ||e||_2 for every entity (per-epoch constraint)."""
        ent = params["ent"]
        ent = ent / (jnp.linalg.norm(ent, axis=-1, keepdims=True) + 1e-12)
        return {"ent": ent, "rel": params["rel"]}

    def candidate_energies(
        self, params: Params, triplets: jax.Array, side: str, norm: str = "l1"
    ) -> jax.Array:
        """Closed form: one (B, E, k) broadcast instead of E substitutions."""
        ent, rel = params["ent"], params["rel"]
        h, r, t = triplets[:, 0], triplets[:, 1], triplets[:, 2]
        if side == "tail":
            q = ent[h] + rel[r]                            # (B, k)
            diff = q[:, None, :] - ent[None, :, :]         # (B, E, k)
        elif side == "head":
            q = ent[t] - rel[r]                            # t - r
            diff = ent[None, :, :] - q[:, None, :]
        else:
            raise ValueError(f"bad side {side!r}")
        return dissimilarity(diff, norm)

    def candidate_slice_energies(
        self, params: Params, triplets: jax.Array, side: str,
        norm: str = "l1", *, lo, n: int
    ) -> jax.Array:
        """Shard-local scan: only candidate rows ``[lo, lo + n)`` of the
        entity table are touched, the query-side lookups stay full-table.
        Elementwise ops + a per-element norm reduction, so each column is
        bitwise the corresponding column of :meth:`candidate_energies`."""
        ent, rel = params["ent"], params["rel"]
        cent = jax.lax.dynamic_slice_in_dim(ent, lo, n, axis=0)
        h, r, t = triplets[:, 0], triplets[:, 1], triplets[:, 2]
        if side == "tail":
            q = ent[h] + rel[r]                            # (B, k)
            diff = q[:, None, :] - cent[None, :, :]        # (B, n, k)
        elif side == "head":
            q = ent[t] - rel[r]
            diff = cent[None, :, :] - q[:, None, :]
        else:
            raise ValueError(f"bad side {side!r}")
        return dissimilarity(diff, norm)

    def relation_energies(
        self, params: Params, triplets: jax.Array, norm: str = "l1"
    ) -> jax.Array:
        ent, rel = params["ent"], params["rel"]
        h = ent[triplets[:, 0]]
        t = ent[triplets[:, 2]]
        diff = (h - t)[:, None, :] + rel[None, :, :]       # (B, R, k)
        return dissimilarity(diff, norm)

    def joint_energies(
        self, params: Params, pos: jax.Array, cand: jax.Array,
        side_head: jax.Array, norm: str = "l1"
    ) -> jax.Array:
        """Closed form: one (B, C, k) broadcast.  A corrupted head scores
        ``||c + r - t||`` and a corrupted tail ``||h + r - c||``; both norms
        are sign-invariant, so each is ``||c - q||`` with the per-row query
        ``q = t - r`` (head side) or ``h + r`` (tail side) — C gathers of
        the candidate pool instead of B·C per-triplet gathers.

        Under ``l2`` the (B, C) distance matrix is computed through the
        ``|c - q|^2 = |c|^2 - 2 c.q + |q|^2`` expansion: one (B, C)
        matmul, no (B, C, k) difference tensor on either the forward or
        the backward pass — the DGL-KE "one corruption batch scored as a
        matmul" form, and what keeps the joint step near per-triplet
        cost.  ``l1`` has no matmul form and keeps the broadcast."""
        ent, rel = params["ent"], params["rel"]
        h, r, t = pos[:, 0], pos[:, 1], pos[:, 2]
        q = jnp.where(
            side_head[:, None], ent[t] - rel[r], ent[h] + rel[r])
        cm = ent[cand]
        if norm == "l2":
            d2 = (jnp.sum(q * q, axis=-1)[:, None]
                  - 2.0 * (q @ cm.T)
                  + jnp.sum(cm * cm, axis=-1)[None, :])
            return jnp.sqrt(jnp.maximum(d2, 0.0) + 1e-12)
        return dissimilarity(cm[None, :, :] - q[:, None, :], norm)

    # -- fused Pallas kernels (late imports: kernels/ops imports this pkg) --

    def fused_margin_loss(
        self, params, pos, neg, *, margin, norm, interpret=False
    ):
        from repro.kernels import ops

        return ops.transe_margin_loss(
            params, pos, neg, margin=margin, norm=norm, interpret=interpret
        )

    def fused_rank_counts(
        self, params, triplets, side, *, norm, interpret=False
    ):
        """Streaming rank-count kernel: q = h + r (tail) / t - r (head),
        count entities strictly closer than the gold."""
        from repro.kernels import rank_topk

        ent, rel = params["ent"], params["rel"]
        h = ent[triplets[:, 0]]
        r = rel[triplets[:, 1]]
        t = ent[triplets[:, 2]]
        if side == "tail":
            q = h + r
            gold = t
        elif side == "head":
            q = t - r
            gold = h
        else:
            raise ValueError(f"bad side {side!r}")
        gold_d = dissimilarity(q - gold, norm)
        return rank_topk.rank_counts(
            q, ent, gold_d, norm=norm, interpret=interpret
        )

    def fused_scan_cells(self, params, rows, norm):
        from repro.kernels import rank_topk

        E, k = params["ent"].shape
        return rank_topk.scan_cells(rows, E, k, norm=norm)
