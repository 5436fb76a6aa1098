"""jit'd public wrappers around the Pallas kernels + model-aware dispatch.

Kernels compile to Mosaic unless the caller passes ``interpret=True``,
which executes the kernel bodies through the Pallas interpreter — what the
CPU tests do.  No backend check picks interpret mode behind the caller's
back: a kernel asked for off TPU without it fails to compile.

``fused_margin_loss`` is differentiable: the Pallas kernel computes the
forward; the backward is closed-form (TransE gradients are ±sign/±unit
vectors scatter-added into the tables) and implemented with segment-sum
scatters — so training can use the fused forward without a hand-written
scatter kernel.

The ``kg_margin_loss`` / ``entity_rank_counts`` entry points dispatch on the
``KGModel``: models with a fused Pallas path (``supports_fused_kernel``,
currently TransE) hit the kernels; every other registered model falls back
to its pure-jnp energy — same semantics, no kernel required to plug in a
new scoring model.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.models import get_model
from repro.kernels import ref, transe_score


# ---------------------------------------------------------------------------
# Fused TransE margin loss (training path)
# ---------------------------------------------------------------------------

def _pack_idx(pos: jax.Array, neg: jax.Array) -> jax.Array:
    """[h, r, t, nh, nt] rows from (B,3) pos/neg triplets (same relation)."""
    return jnp.stack(
        [pos[:, 0], pos[:, 1], pos[:, 2], neg[:, 0], neg[:, 2]], axis=1
    ).astype(jnp.int32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def fused_margin_loss(
    ent: jax.Array,
    rel: jax.Array,
    idx: jax.Array,
    margin: float,
    norm: str,
    interpret: bool,
) -> jax.Array:
    """Mean hinge loss over the batch, forward computed by the Pallas kernel."""
    loss, _, _ = transe_score.transe_score(
        ent, rel, idx, margin=margin, norm=norm, interpret=interpret
    )
    return jnp.mean(loss)


def _fwd(ent, rel, idx, margin, norm, interpret):
    loss, d_pos, d_neg = transe_score.transe_score(
        ent, rel, idx, margin=margin, norm=norm, interpret=interpret
    )
    return jnp.mean(loss), (ent, rel, idx, loss, d_pos, d_neg)


def _bwd(margin, norm, interpret, res, g):
    """Closed-form TransE backward.

    For active pairs (hinge > 0), with u = h + r - t, v = nh + r - nt:
        dL/du =  s(u),  dL/dv = -s(v)
    where s(x) = sign(x) for L1 and x/||x|| for L2.  Then
        grad_h = du, grad_t = -du, grad_nh = -dv_term... (see below)
        grad_r = du + dv_contrib
    scattered into the tables by segment-sum.
    """
    ent, rel, idx, loss, d_pos, d_neg = res
    B = idx.shape[0]
    scale = (g / B) * (loss > 0).astype(jnp.float32)             # (B,)

    h = ent[idx[:, 0]].astype(jnp.float32)
    r = rel[idx[:, 1]].astype(jnp.float32)
    t = ent[idx[:, 2]].astype(jnp.float32)
    nh = ent[idx[:, 3]].astype(jnp.float32)
    nt = ent[idx[:, 4]].astype(jnp.float32)

    u = h + r - t
    v = nh + r - nt
    if norm == "l1":
        su = jnp.sign(u)
        sv = jnp.sign(v)
    else:
        su = u / (d_pos[:, None] + 1e-12)
        sv = v / (d_neg[:, None] + 1e-12)

    gu = su * scale[:, None]          # d loss / d (h + r - t)
    gv = -sv * scale[:, None]         # d loss / d (nh + r - nt)

    E, k = ent.shape
    R = rel.shape[0]
    rows = jnp.concatenate([idx[:, 0], idx[:, 2], idx[:, 3], idx[:, 4]])
    vals = jnp.concatenate([gu, -gu, gv, -gv], axis=0)
    d_ent = jax.ops.segment_sum(vals, rows, num_segments=E)
    d_rel = jax.ops.segment_sum(gu + gv, idx[:, 1], num_segments=R)
    return d_ent.astype(ent.dtype), d_rel.astype(rel.dtype), None


fused_margin_loss.defvjp(_fwd, _bwd)


def transe_margin_loss(
    params,
    pos: jax.Array,
    neg: jax.Array,
    *,
    margin: float = 1.0,
    norm: str = "l1",
    interpret: bool = False,
) -> jax.Array:
    """Drop-in fused replacement for ``core.transe.margin_loss``."""
    idx = _pack_idx(pos, neg)
    return fused_margin_loss(
        params["ent"], params["rel"], idx, margin, norm, interpret
    )


def kg_margin_loss(
    model,
    params,
    pos: jax.Array,
    neg: jax.Array,
    *,
    margin: float = 1.0,
    norm: str = "l1",
    interpret: bool = False,
) -> jax.Array:
    """Model-dispatched margin loss: models declaring
    ``supports_fused_kernel`` provide their own Pallas path via
    ``fused_margin_loss`` (TransE wraps ``transe_margin_loss`` below);
    everything else falls back to the model's pure-jnp energy.  Both paths
    are differentiable."""
    model = get_model(model)
    if model.supports_fused_kernel:
        return model.fused_margin_loss(
            params, pos, neg, margin=margin, norm=norm, interpret=interpret
        )
    return model.margin_loss(params, pos, neg, margin=margin, norm=norm)


# ---------------------------------------------------------------------------
# Entity-inference ranking (evaluation path)
# ---------------------------------------------------------------------------

def fused_eval_available(model) -> bool:
    """True when entity ranking for ``model`` should stream through its
    Pallas kernel on this backend: the model declares
    ``supports_fused_kernel`` AND the backend is TPU, the only one the
    kernels compile for.  Elsewhere the batched jnp path runs, which is
    also the exact eval reference; the device eval engine's ``fused=None``
    auto mode keys off this."""
    model = get_model(model)
    return model.supports_fused_kernel and jax.default_backend() == "tpu"


def entity_rank_counts(
    params,
    triplets: jax.Array,      # (B, 3)
    side: str = "tail",
    *,
    norm: str = "l1",
    interpret: bool = False,
    model="transe",
) -> jax.Array:
    """rank-1 counts (entities strictly closer than gold) per test triplet.
    rank = 1 + returned count.  Fused-kernel models stream entity tiles
    through their own Pallas kernel (``fused_rank_counts``); others score
    candidates with the model's batched pure-jnp path."""
    model = get_model(model)
    if model.supports_fused_kernel:
        return model.fused_rank_counts(
            params, triplets, side, norm=norm, interpret=interpret
        )
    scores = model.candidate_energies(params, triplets, side, norm)
    # gold score read out of the SAME matrix (as core/eval.py does) — a
    # recompute via model.energy can differ in the last ulp and make the
    # gold entity count itself.
    gold = triplets[:, 2] if side == "tail" else triplets[:, 0]
    gold_d = scores[jnp.arange(scores.shape[0]), gold]
    return jnp.sum(scores < gold_d[:, None], axis=1).astype(jnp.int32)


# Re-export oracles for tests/benchmarks
transe_score_ref = ref.transe_score_ref
rank_counts_ref = ref.rank_counts_ref
