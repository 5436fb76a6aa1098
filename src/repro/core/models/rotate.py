"""RotatE (Sun et al., 2019, ICLR, arXiv:1902.10197) — relations as
rotations in complex space.

An entity is ``k`` complex numbers, stored as one real row ``[re | im]``
of ``2k`` floats; a relation is ``k`` phases ``θ``, so each relation
coordinate ``r_j = exp(i θ_j)`` has modulus 1 by construction.  A true
triplet should satisfy ``h ∘ r ≈ t`` (element-wise complex product):

    d(h, r, t) = sum_j | h_j r_j - t_j |

the sum of complex moduli, RotatE's one energy: any ``norm`` but "l1" is
refused.

Phases follow the authors' parametrization: a relation value ``v`` is the
phase ``v · π / ((γ + ε) / k)``, and both tables start uniform in
``±(γ + ε) / k`` (γ = 24, ε = 2 are their FB15k settings), so initial
phases are uniform in ``[-π, π]``.  The unit modulus needs no projection:
``normalize`` is the identity, which keeps the sparse-transport row
contract trivially.

Since ``|r_j| = 1``, ``|h_j r_j - t_j| = |h_j - t_j conj(r_j)|``: every
entity scan measures candidates from one rotated query per row — ``h ∘ r``
for tails, ``t ∘ conj(r)`` for heads, as the authors' code scores a head
batch — and the rank kernel scans raw entity rows
(``kernels/rank_topk.modulus_rank_counts``).  Scans over candidate rows
run ``BLOCK`` rows at a time, so no ``(B, E, 2k)`` difference is live.

Training uses the engine's margin loss and SGD like every registered
model; the paper's self-adversarial negative-sampling loss is not
implemented here.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.core.models import base
from repro.core.models.base import KGConfig, Params

# candidate entity (or relation) rows one scan step scores
BLOCK = 512
# the authors' FB15k margin and range slack: tables start uniform in
# +-(GAMMA + EPSILON) / k, and that value is the phase pi
GAMMA, EPSILON = 24.0, 2.0


def embedding_range(k: int) -> float:
    """``(γ + ε) / k``: the init bound, and the value of phase π."""
    return (GAMMA + EPSILON) / k


def _halves(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """``(..., 2k)`` ``[re | im]`` rows -> their real and imaginary parts."""
    k = x.shape[-1] // 2
    return x[..., :k], x[..., k:]


def _modulus(re: jax.Array, im: jax.Array) -> jax.Array:
    """``|re + i im|``, with a zero gradient where the modulus is 0 (the
    plain ``sqrt`` has an infinite one there)."""
    sq = re * re + im * im
    nonzero = sq > 0
    return jnp.where(nonzero, jnp.sqrt(jnp.where(nonzero, sq, 1.0)), 0.0)


def _check_norm(norm: str) -> None:
    if norm != "l1":
        raise ValueError(
            f"RotatE's energy is the sum of complex moduli (norm='l1'), "
            f"not norm={norm!r}")


def _distance(re: jax.Array, im: jax.Array, norm: str) -> jax.Array:
    """The energy of a complex difference ``re + i im`` over its last
    axis: the sum of moduli."""
    _check_norm(norm)
    return jnp.sum(_modulus(re, im), axis=-1)


def _blocked(score, rows: jax.Array) -> jax.Array:
    """``score(rows)`` -> ``(B, n)`` for ``n`` candidate rows, computed
    ``BLOCK`` rows at a time where ``n`` exceeds it (zero-padded rows
    score harmlessly and are sliced off)."""
    n = rows.shape[0]
    if n <= BLOCK:
        return score(rows)
    nb = -(-n // BLOCK)
    padded = jnp.concatenate(
        [rows, jnp.zeros((nb * BLOCK - n,) + rows.shape[1:], rows.dtype)])
    out = jax.lax.map(score, padded.reshape((nb, BLOCK) + rows.shape[1:]))
    return jnp.moveaxis(out, 0, 1).reshape(out.shape[1], -1)[:, :n]


class RotatE(base.KGModel):
    name = "rotate"
    roles = {"ent": "ent", "rel": "rel"}
    supports_fused_kernel = True

    def width(self, params: Params) -> int:
        return int(params["rel"].shape[1])

    def init_params(self, key: jax.Array, cfg: KGConfig) -> Params:
        """Both tables uniform in ``±(γ + ε)/k``: ``ent`` ``(E, 2k)``
        ``[re | im]``, ``rel`` ``(R, k)`` phase values."""
        k_ent, k_rel = jax.random.split(key)
        bound = embedding_range(cfg.dim)
        ent = jax.random.uniform(k_ent, (cfg.n_entities, 2 * cfg.dim),
                                 cfg.dtype, -bound, bound)
        rel = jax.random.uniform(k_rel, (cfg.n_relations, cfg.dim),
                                 cfg.dtype, -bound, bound)
        return {"ent": ent, "rel": rel}

    def rotation(self, rel_rows: jax.Array) -> tuple[jax.Array, jax.Array]:
        """``(cos θ, sin θ)`` of relation rows ``(..., k)``."""
        k = rel_rows.shape[-1]
        phase = rel_rows * (math.pi / embedding_range(k))
        return jnp.cos(phase), jnp.sin(phase)

    def query(
        self, params: Params, triplets: jax.Array, side: str
    ) -> tuple[jax.Array, jax.Array]:
        """The rotated query every ``side`` candidate is measured from:
        ``h ∘ r`` (tail) or ``t ∘ conj(r)`` (head), as ``(re, im)``."""
        c, s = self.rotation(params["rel"][triplets[..., 1]])
        if side == "tail":
            re, im = _halves(params["ent"][triplets[..., 0]])
            return re * c - im * s, re * s + im * c
        if side == "head":
            re, im = _halves(params["ent"][triplets[..., 2]])
            return re * c + im * s, im * c - re * s
        raise ValueError(f"bad side {side!r}")

    def energy(
        self, params: Params, triplets: jax.Array, norm: str = "l1"
    ) -> jax.Array:
        q_re, q_im = self.query(params, triplets, "tail")
        t_re, t_im = _halves(params["ent"][triplets[..., 2]])
        return _distance(q_re - t_re, q_im - t_im, norm)

    def normalize(self, params: Params) -> Params:
        """Identity: the phase parametrization keeps ``|r_j| = 1``."""
        return dict(params)

    def normalize_rows(self, name: str, rows: jax.Array) -> jax.Array:
        return rows

    # -- closed-form scans --------------------------------------------------

    @staticmethod
    def _scan(q_re, q_im, rows, norm):
        """``(B, n)`` distances from ``(B, k)`` queries to ``(n, 2k)``
        candidate rows, ``BLOCK`` rows at a time."""
        def score(block):
            e_re, e_im = _halves(block)
            return _distance(q_re[:, None, :] - e_re[None],
                             q_im[:, None, :] - e_im[None], norm)
        return _blocked(score, rows)

    def candidate_energies(
        self, params: Params, triplets: jax.Array, side: str, norm: str = "l1"
    ) -> jax.Array:
        """Closed form: every entity row against the ``(B, k)`` rotated
        query, one block of candidates at a time."""
        q_re, q_im = self.query(params, triplets, side)
        return self._scan(q_re, q_im, params["ent"], norm)

    def candidate_slice_energies(
        self, params: Params, triplets: jax.Array, side: str,
        norm: str = "l1", *, lo, n: int
    ) -> jax.Array:
        """Shard-local scan: the same per-candidate arithmetic on rows
        ``[lo, lo + n)`` only, so each column is the matching column of
        :meth:`candidate_energies`."""
        q_re, q_im = self.query(params, triplets, side)
        cent = jax.lax.dynamic_slice_in_dim(params["ent"], lo, n, axis=0)
        return self._scan(q_re, q_im, cent, norm)

    def relation_energies(
        self, params: Params, triplets: jax.Array, norm: str = "l1"
    ) -> jax.Array:
        """``(B, R)``: the head rotated by every relation against the
        tail, one block of relations at a time."""
        h_re, h_im = _halves(params["ent"][triplets[:, 0]])
        t_re, t_im = _halves(params["ent"][triplets[:, 2]])

        def score(rel_rows):
            c, s = self.rotation(rel_rows)                 # (n, k)
            re = (h_re[:, None, :] * c[None] - h_im[:, None, :] * s[None]
                  - t_re[:, None, :])
            im = (h_re[:, None, :] * s[None] + h_im[:, None, :] * c[None]
                  - t_im[:, None, :])
            return _distance(re, im, norm)
        return _blocked(score, params["rel"])

    def joint_energies(
        self, params: Params, pos: jax.Array, cand: jax.Array,
        side_head: jax.Array, norm: str = "l1"
    ) -> jax.Array:
        """Closed form: the C pool rows against each positive's rotated
        query (``t ∘ conj(r)`` where the head is corrupted, ``h ∘ r``
        where the tail is), one block of candidates at a time."""
        t_re, t_im = self.query(params, pos, "head")
        h_re, h_im = self.query(params, pos, "tail")
        q_re = jnp.where(side_head[:, None], t_re, h_re)
        q_im = jnp.where(side_head[:, None], t_im, h_im)
        return self._scan(q_re, q_im, params["ent"][cand], norm)

    # -- kernel hooks (late imports: kernels/ops imports this package) ------

    def fused_margin_loss(
        self, params, pos, neg, *, margin, norm, interpret=False
    ):
        """No training kernel: the engine's margin loss on the energy."""
        return self.margin_loss(params, pos, neg, margin=margin, norm=norm)

    def fused_rank_counts(
        self, params, triplets, side, *, norm, interpret=False
    ):
        """Rank counts of the raw entity rows around the rotated query,
        through the complex-modulus kernel; the gold distance is the same
        sum of moduli."""
        from repro.kernels import rank_topk

        q_re, q_im = self.query(params, triplets, side)
        gold = params["ent"][triplets[:, 2 if side == "tail" else 0]]
        g_re, g_im = _halves(gold)
        gold_d = _distance(q_re - g_re, q_im - g_im, norm)
        q = jnp.concatenate([q_re, q_im], axis=1)
        return rank_topk.modulus_rank_counts(
            q, params["ent"], gold_d, interpret=interpret)

    def fused_scan_cells(self, params, rows: int, norm: str):
        from repro.kernels import rank_topk

        _check_norm(norm)
        E, k = params["ent"].shape[0], self.width(params)
        return rank_topk.scan_cells(rows, E, k, modulus=True)
