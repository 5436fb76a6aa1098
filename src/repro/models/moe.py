"""Mixture-of-Experts FFN: shared + routed experts with top-k routing
(DeepSeek-V2 / Qwen-MoE style).

Dispatch is **scatter-based with fixed capacity** — the TPU/pjit-friendly
middle ground (DESIGN.md §3):
  * no (T, E, C) one-hot dispatch tensor (GShard einsum) — that blows HBM at
    pod batch sizes;
  * no data-dependent ragged shapes (XLA needs static shapes);
  * tokens pick top-k experts; a cumsum over the (T, E) assignment matrix
    gives each (token, expert) pair its slot; pairs beyond capacity C are
    dropped (standard capacity-factor semantics, cf ≥ 1 keeps drop rates
    ~0 at balanced load).
  * per-expert compute is ONE batched einsum (E, C, d) x (E, d, f) — a
    block-diagonal MXU-shaped matmul; with experts sharded over the
    ``model``/EP axis, XLA lowers the scatter/gather to all-to-alls.

FLOPs scale with tokens·top_k·cf — i.e. *active* parameters, which is what
the roofline's MODEL_FLOPS/HLO_FLOPs usefulness ratio checks.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.models import common
from repro.models.common import ModelConfig, dense_init
from repro.parallel.util import constrain as _constrain_axes


def _constrain(x, axes):
    return _constrain_axes(x, axes)


# expert tensors are padded to a multiple of the model-axis size so they
# shard evenly (qwen2-moe's 60 experts -> 64 rows; the 4 dummies are never
# routed to — the router has exactly n_experts outputs).
EXPERT_PAD = 16


def padded_experts(cfg: ModelConfig) -> int:
    return -(-cfg.n_experts // EXPERT_PAD) * EXPERT_PAD


def init_moe(key, cfg: ModelConfig):
    ks = jax.random.split(key, 6)
    E, d, f = padded_experts(cfg), cfg.d_model, cfg.moe_d_ff
    p = {
        "router": dense_init(ks[0], (d, cfg.n_experts), d, jnp.float32),
        "wg": dense_init(ks[1], (E, d, f), d, cfg.param_dtype),
        "wi": dense_init(ks[2], (E, d, f), d, cfg.param_dtype),
        "wo": dense_init(ks[3], (E, f, d), f, cfg.param_dtype),
    }
    if cfg.n_shared_experts:
        p["shared"] = common.init_mlp(
            ks[4], d, cfg.n_shared_experts * f, cfg, gated=True)
    return p


def _route(p, x_flat: jax.Array, cfg: ModelConfig):
    """x_flat (T, d) -> (weights (T, K), experts (T, K) int32, aux_loss)."""
    logits = jnp.einsum("td,de->te", x_flat.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    weights, experts = jax.lax.top_k(probs, cfg.top_k)          # (T, K)
    if cfg.norm_topk_prob:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-9)
    weights = weights * cfg.routed_scaling
    # load-balancing aux loss (Switch-style): E * sum_e f_e * P_e
    T = x_flat.shape[0]
    me = jnp.mean(probs, axis=0)                                # (E,)
    one_hot = jax.nn.one_hot(experts[:, 0], cfg.n_experts)      # top-1 frac
    ce = jnp.mean(one_hot, axis=0)
    aux = cfg.n_experts * jnp.sum(me * ce)
    return weights, experts, aux


def apply_moe(p, x: jax.Array, cfg: ModelConfig):
    """x (B, L, d) -> (out (B, L, d), aux_loss scalar).

    Two dispatch backends:
      * ``shard_map`` (production, used whenever an ambient mesh with a
        'model' axis is present and shapes divide): tokens stay on their
        data shard; each model column dispatches only its expert slice with
        a LOCAL scatter, runs its experts, combines locally, and one psum
        over 'model' sums the per-slice contributions.  No global scatter
        for GSPMD to replicate (which it otherwise does — see §Perf log).
      * ``scatter`` (fallback: single device / unpartitionable shapes):
        plain capacity scatter into a global (E, C, d) buffer.
    """
    from repro.parallel import util as putil

    mesh = putil._ambient_mesh()
    B, L, d = x.shape
    T = B * L
    if mesh is not None and "model" in mesh.axis_names:
        dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        dp_size = 1
        for a in dp:
            dp_size *= mesh.shape[a]
        if dp_size > 1 and T % dp_size == 0 \
                and padded_experts(cfg) % mesh.shape["model"] == 0:
            return _apply_moe_shardmap(p, x, cfg, mesh, dp)
    return _apply_moe_scatter(p, x, cfg)


def _apply_moe_scatter(p, x: jax.Array, cfg: ModelConfig):
    B, L, d = x.shape
    T = B * L
    E, K, f = cfg.n_experts, cfg.top_k, cfg.moe_d_ff
    x_flat = x.reshape(T, d)

    weights, experts, aux = _route(p, x_flat, cfg)

    # ---- slot assignment: position of each (token, k) pair within its expert
    flat_exp = experts.reshape(T * K)                           # (TK,)
    onehot = jax.nn.one_hot(flat_exp, E, dtype=jnp.int32)       # (TK, E)
    pos_in_expert = (jnp.cumsum(onehot, axis=0) - onehot)       # exclusive
    slot = jnp.take_along_axis(
        pos_in_expert, flat_exp[:, None], axis=1)[:, 0]         # (TK,)
    # capacity: cf ≥ E/K is exactly dropless (C = T); floor of 8 keeps
    # tiny decode batches from starving an expert.
    capacity = min(max(int((T * K * cfg.capacity_factor) / E), min(8, T)), T)
    keep = slot < capacity

    # ---- dispatch: scatter token rows into (E, C, d)
    # tok_ids = repeat(arange(T), K) keeps each token's K rows contiguous,
    # so the TK dim inherits T's data sharding exactly — the constraints
    # below stop GSPMD from replicating the scatter operands (observed as
    # ~10 GB/device dispatch buffers on qwen2-moe without them).
    tok_ids = jnp.repeat(jnp.arange(T), K)
    safe_exp = jnp.where(keep, flat_exp, 0)
    safe_slot = jnp.where(keep, slot, capacity - 1)
    buf = jnp.zeros((padded_experts(cfg), capacity, d), cfg.dtype)
    vals = x_flat[tok_ids] * keep[:, None].astype(cfg.dtype)
    vals = _constrain(vals, (("pod", "data"), None))
    buf = buf.at[safe_exp, safe_slot].add(vals, mode="drop")
    # expert-parallel over 'model', slot dim over 'data' (pjit inserts the
    # all-to-alls); no-op without an ambient mesh (CPU tests).
    buf = _constrain(buf, ("model", ("pod", "data"), None))

    # ---- per-expert FFN: block-diagonal batched matmul
    act = common.act_fn(cfg.act)
    up = jnp.einsum("ecd,edf->ecf", buf, p["wi"].astype(cfg.dtype))
    gate = act(jnp.einsum("ecd,edf->ecf", buf, p["wg"].astype(cfg.dtype)))
    out_e = jnp.einsum("ecf,efd->ecd", gate * up, p["wo"].astype(cfg.dtype))

    # ---- combine: gather back and weight
    gathered = out_e[safe_exp, safe_slot]                       # (TK, d)
    gathered = _constrain(gathered, (("pod", "data"), None))
    w_flat = (weights.reshape(T * K) * keep).astype(cfg.dtype)
    contrib = gathered * w_flat[:, None]
    out = jax.ops.segment_sum(contrib, tok_ids, num_segments=T)
    out = _constrain(out, (("pod", "data"), None))

    if cfg.n_shared_experts:
        out = out + common.apply_mlp(p["shared"], x_flat, cfg)

    return out.reshape(B, L, d), aux.astype(jnp.float32)


# ---------------------------------------------------------------------------
# shard_map dispatch (production path)
# ---------------------------------------------------------------------------

def _apply_moe_shardmap(p, x: jax.Array, cfg: ModelConfig, mesh, dp):
    """Expert-parallel dispatch with data-local token scatter.

    Layout inside shard_map over (dp..., 'model'):
      x_loc   (T/dp, d)      — tokens sharded over dp, replicated over model
      wi/wg   (Ep/mp, d/dp?, f) — experts over 'model', fsdp dim over 'data'
                                  (gathered locally per use; the gather's
                                  transpose reduce-scatters the grads)
      out     psum over 'model' of each expert-slice's contribution.
    """
    from jax.sharding import PartitionSpec as P

    B, L, d = x.shape
    T = B * L
    Ep = padded_experts(cfg)
    mp = mesh.shape["model"]
    E, K, f = cfg.n_experts, cfg.top_k, cfg.moe_d_ff
    e_loc = Ep // mp
    fsdp = cfg.sharding_profile == "fsdp_tp" and "data" in mesh.axis_names \
        and d % mesh.shape["data"] == 0

    x_flat = x.reshape(T, d)
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    t_loc = T // dp_size
    # local capacity: worst-case tokens per expert slice with cf headroom
    cap = max(int(t_loc * K * cfg.capacity_factor / E), min(8, t_loc))
    cap = min(cap, t_loc)

    wspec = P("model", "data", None) if fsdp else P("model", None, None)
    wospec = P("model", None, "data") if fsdp else P("model", None, None)

    def worker(x_loc, router, wg, wi, wo):
        # x_loc (t_loc, d); wg/wi (e_loc, d[/dp], f); wo (e_loc, f, d[/dp])
        if fsdp:
            wg = jax.lax.all_gather(wg, "data", axis=1, tiled=True)
            wi = jax.lax.all_gather(wi, "data", axis=1, tiled=True)
            wo = jax.lax.all_gather(wo, "data", axis=2, tiled=True)
        logits = jnp.einsum("td,de->te", x_loc.astype(jnp.float32), router)
        probs = jax.nn.softmax(logits, axis=-1)
        weights, experts = jax.lax.top_k(probs, K)              # (t_loc, K)
        if cfg.norm_topk_prob:
            weights = weights / (jnp.sum(weights, -1, keepdims=True) + 1e-9)
        weights = weights * cfg.routed_scaling

        j = jax.lax.axis_index("model")
        lo = j * e_loc
        mine = (experts >= lo) & (experts < lo + e_loc)         # (t_loc, K)
        local_e = jnp.where(mine, experts - lo, 0)

        flat_e = local_e.reshape(t_loc * K)
        flat_keep = mine.reshape(t_loc * K)
        onehot = jax.nn.one_hot(flat_e, e_loc, dtype=jnp.int32) * \
            flat_keep[:, None].astype(jnp.int32)
        pos = (jnp.cumsum(onehot, axis=0) - onehot)
        slot = jnp.take_along_axis(pos, flat_e[:, None], axis=1)[:, 0]
        keep = flat_keep & (slot < cap)
        safe_e = jnp.where(keep, flat_e, 0)
        safe_s = jnp.where(keep, slot, cap - 1)

        tok = jnp.repeat(jnp.arange(t_loc), K)
        vals = x_loc[tok] * keep[:, None].astype(cfg.dtype)
        buf = jnp.zeros((e_loc, cap, d), cfg.dtype)
        buf = buf.at[safe_e, safe_s].add(vals, mode="drop")

        act = common.act_fn(cfg.act)
        up = jnp.einsum("ecd,edf->ecf", buf, wi.astype(cfg.dtype))
        gate = act(jnp.einsum("ecd,edf->ecf", buf, wg.astype(cfg.dtype)))
        out_e = jnp.einsum("ecf,efd->ecd", gate * up, wo.astype(cfg.dtype))

        gathered = out_e[safe_e, safe_s]                        # (t_loc*K, d)
        w_flat = (weights.reshape(t_loc * K) * keep).astype(cfg.dtype)
        contrib = jax.ops.segment_sum(
            gathered * w_flat[:, None], tok, num_segments=t_loc)
        contrib = jax.lax.psum(contrib, "model")

        # Switch-style aux loss; the factors are averaged over dp BEFORE
        # the product so this equals the global-batch computation exactly
        me = jnp.mean(probs, axis=0)
        ce = jnp.mean(jax.nn.one_hot(experts[:, 0], E), axis=0)
        if dp:
            me = jax.lax.pmean(me, dp)
            ce = jax.lax.pmean(ce, dp)
        aux = E * jnp.sum(me * ce)
        return contrib, aux

    in_specs = (P(dp, None), P(None, None), wspec, wspec, wospec)
    out_specs = (P(dp, None), P())
    out, aux = jax.shard_map(
        worker, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )(x_flat, p["router"], p["wg"], p["wi"], p["wo"])

    if cfg.n_shared_experts:
        out = out + common.apply_mlp(p["shared"], x_flat, cfg)
    return out.reshape(B, L, d), aux.astype(jnp.float32)
