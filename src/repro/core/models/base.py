"""The model-agnostic KG embedding interface the MapReduce engine trains.

The paper parallelizes one scoring function (TransE), but its Map/Reduce
machinery — balanced partitioning, local-SGD epochs, conflict-resolving
merges, BGD gradient reduction — never looks inside the score.  ``KGModel``
is the seam: a scoring model provides

  * ``init_params``      — its embedding tables (a dict of ``(N, k)`` arrays),
  * ``energy``           — d(h, r, t) for a batch of triplets (lower = truer),
  * ``normalize``        — the per-epoch/step constraint projection,
  * ``param_roles``      — which stats table ('ent' | 'rel') covers each
                           param table, the touched-key bookkeeping the
                           Reduce-phase merges need,
  * ``candidate_energies`` / ``relation_energies`` — batched eval scoring
                           (generic fallbacks provided; models override with
                           closed forms),
  * ``make_negatives``   — corrupted-triplet construction (Eq. 2 by default).

Everything else — margin ranking loss, SGD steps, local-SGD epochs with
per-key touch stats, BGD gradients — is shared engine math implemented once
here, so a new scoring model is a ~100-line subclass (see transh.py /
distmult.py), not a fork of the engine.

Params are a plain dict ``{table_name: (N, k) array}``; triplets are int32
``(..., 3)`` arrays of ``(h, r, t)`` ids.  All methods are pure and
jit/vmap/shard_map friendly.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.core import negative

Params = Dict[str, jax.Array]


@dataclasses.dataclass(frozen=True)
class KGConfig:
    """Hyper-parameters shared by every registered scoring model
    (single-thread training is paper Algorithm 1 with the model's energy)."""

    n_entities: int
    n_relations: int
    dim: int = 50
    margin: float = 1.0
    norm: str = "l1"            # 'l1' | 'l2'  (Eq. 1 allows either)
    learning_rate: float = 0.01
    # 'epoch' applies the model's constraint projection at the start of each
    # epoch (TransE); 'step' after every SGD step; 'none' disables.
    normalize: str = "epoch"
    # negative sampling: 'unif' (paper / TransE) or 'bern' (TransH-style)
    sampling: str = "unif"
    # negative *scoring* scheme: 'pertriplet' pairs each positive with its
    # one corrupted counterpart (Eq. 3, the paper); 'joint' scores a shared
    # candidate pool — the batch's first ``neg_candidates`` corrupted
    # entities — against EVERY positive via the model's ``joint_energies``
    # matmul/broadcast closed form (DGL-KE's joint negative sampling:
    # B·C ranking pairs per batch instead of B, amortizing each gather).
    negatives: str = "pertriplet"
    # 'joint' pool size C (clamped to the batch size); 0 = the full batch.
    neg_candidates: int = 0
    dtype: Any = jnp.float32

    def __post_init__(self):
        if self.norm not in ("l1", "l2"):
            raise ValueError(f"norm must be 'l1' or 'l2', got {self.norm!r}")
        if self.normalize not in ("epoch", "step", "none"):
            raise ValueError(f"bad normalize: {self.normalize!r}")
        if self.negatives not in ("pertriplet", "joint"):
            raise ValueError(f"bad negatives: {self.negatives!r}")
        if self.neg_candidates < 0:
            raise ValueError(
                f"neg_candidates must be >= 0 (0 = full batch), got "
                f"{self.neg_candidates}")


def dissimilarity(x: jax.Array, norm: str) -> jax.Array:
    if norm == "l1":
        return jnp.sum(jnp.abs(x), axis=-1)
    return jnp.sqrt(jnp.sum(x * x, axis=-1) + 1e-12)


def unit_rows(x: jax.Array) -> jax.Array:
    """Row-wise L2 normalization (the constraint projection primitive)."""
    return x / (jnp.linalg.norm(x, axis=-1, keepdims=True) + 1e-12)


def uniform_table(key: jax.Array, n: int, dim: int, dtype) -> jax.Array:
    """Uniform(-6/sqrt(k), 6/sqrt(k)) init (TransE Algorithm 1, lines 1-4)."""
    bound = 6.0 / jnp.sqrt(float(dim))
    return jax.random.uniform(key, (n, dim), dtype, -bound, bound)


def pairwise_hinge(
    d_pos: jax.Array, d_neg: jax.Array, margin: float
) -> jax.Array:
    """[gamma + d(pos) - d(neg)]_+  (Eq. 3 summand)."""
    return jnp.maximum(0.0, margin + d_pos - d_neg)


def apply_gradients(params: Params, grads: Params, lr: float) -> Params:
    return jax.tree.map(lambda p, g: p - lr * g, params, grads)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class EpochStats:
    """Bookkeeping one Map worker emits for the Reduce phase."""

    mean_loss: jax.Array        # scalar, mean pair loss over the epoch
    ent_count: jax.Array        # (E,) how many updates touched each entity
    ent_loss: jax.Array         # (E,) summed pair loss attributed to entity
    rel_count: jax.Array        # (R,)
    rel_loss: jax.Array         # (R,)


def _accumulate_touch(
    stats: tuple, pos: jax.Array, neg: jax.Array, pair_loss: jax.Array, E: int, R: int
) -> tuple:
    ent_count, ent_loss, rel_count, rel_loss = stats
    # keys touched by the update: h, t of pos AND the corrupted entity of neg.
    heads = jnp.concatenate([pos[:, 0], neg[:, 0]])
    tails = jnp.concatenate([pos[:, 2], neg[:, 2]])
    l2 = jnp.concatenate([pair_loss, pair_loss])
    ent_count = ent_count.at[heads].add(1.0).at[tails].add(1.0)
    ent_loss = ent_loss.at[heads].add(l2).at[tails].add(l2)
    rel_count = rel_count.at[pos[:, 1]].add(1.0)
    rel_loss = rel_loss.at[pos[:, 1]].add(pair_loss)
    return ent_count, ent_loss, rel_count, rel_loss


class KGModel:
    """Base class: subclass, fill in the model-specific pieces, register."""

    name: str = "base"
    # table name -> which touch-stats table governs its merge ('ent' | 'rel')
    roles: Dict[str, str] = {"ent": "ent", "rel": "rel"}
    # True iff kernels/ops.py has a fused Pallas scoring path for this model
    supports_fused_kernel: bool = False

    # -- model-specific interface ------------------------------------------

    def init_params(self, key: jax.Array, cfg: KGConfig) -> Params:
        raise NotImplementedError

    def energy(
        self, params: Params, triplets: jax.Array, norm: str = "l1"
    ) -> jax.Array:
        """d(h, r, t) for a batch of triplets ``(..., 3)`` -> ``(...,)``.
        Lower = more plausible (similarity models negate their score)."""
        raise NotImplementedError

    def normalize(self, params: Params) -> Params:
        """Constraint projection (default: unit-L2 entity rows)."""
        out = dict(params)
        out["ent"] = unit_rows(params["ent"])
        return out

    def normalize_rows(self, name: str, rows: jax.Array) -> jax.Array:
        """Row-local restriction of :meth:`normalize` for table ``name``:
        the projection applied to a ``(n, k)`` slice of rows.

        Contract (the sparse Reduce transport depends on it): for every
        table, ``normalize(params)[name][ids] == normalize_rows(name,
        params[name][ids])`` **bitwise** — i.e. the constraint projection
        touches each row independently, so a merge that only ships touched
        rows can reconstruct what an *untouched* row evolved into (``m``
        chained projections of its round-input value) without seeing the
        full table.  A model whose projection couples rows (e.g. a
        table-global rescale) must not be trained with
        ``merge_transport="sparse"``; tests/test_sparse_transport.py pins
        the contract per registered model.  Default matches the default
        ``normalize``: unit-L2 rows for ``"ent"``, identity elsewhere."""
        if name == "ent":
            return unit_rows(rows)
        return rows

    def param_roles(self) -> Dict[str, str]:
        return dict(self.roles)

    # -- eval scoring (generic fallbacks; override with closed forms) ------

    def candidate_energies(
        self, params: Params, triplets: jax.Array, side: str, norm: str = "l1"
    ) -> jax.Array:
        """Energies of every entity substituted as ``side`` ('tail'|'head')
        of each triplet: ``(B, 3) -> (B, E)``.  Generic fallback substitutes
        one entity at a time (vmapped); fine for tests, models override."""
        if side not in ("tail", "head"):
            raise ValueError(f"bad side {side!r}")
        col = 2 if side == "tail" else 0
        E = params["ent"].shape[0]

        def one(e):
            return self.energy(params, triplets.at[:, col].set(e), norm)

        return jax.vmap(one)(jnp.arange(E)).T

    def candidate_slice_energies(
        self, params: Params, triplets: jax.Array, side: str,
        norm: str = "l1", *, lo, n: int
    ) -> jax.Array:
        """Columns ``[lo, lo + n)`` of :meth:`candidate_energies`:
        ``(B, 3) -> (B, n)``, the shard-local candidate scan the sharded
        eval / serving paths run per table shard (``lo`` may be traced,
        ``n`` is static).

        Contract (tests/test_sharded_tables.py pins it per registered
        model): **bitwise** equal to slicing the full matrix, so a
        per-shard scan + cross-shard combine reproduces the replicated
        ranking exactly.  The generic fallback materializes the full
        ``(B, E)`` matrix and slices it — always exact, never cheaper;
        models override to touch only the candidate rows (the caller
        guarantees ``lo + n <= E``, padding the entity table if needed)."""
        full = self.candidate_energies(params, triplets, side, norm)
        return jax.lax.dynamic_slice_in_dim(full, lo, n, axis=1)

    def relation_energies(
        self, params: Params, triplets: jax.Array, norm: str = "l1"
    ) -> jax.Array:
        """Energies of every relation substituted into each triplet:
        ``(B, 3) -> (B, R)``."""
        R = params["rel"].shape[0]

        def one(r):
            return self.energy(params, triplets.at[:, 1].set(r), norm)

        return jax.vmap(one)(jnp.arange(R)).T

    # -- fused-kernel hooks (kernels/ops.py dispatch) ------------------------

    def fused_margin_loss(
        self,
        params: Params,
        pos: jax.Array,
        neg: jax.Array,
        *,
        margin: float,
        norm: str,
        interpret: bool = False,
    ) -> jax.Array:
        """Pallas-fused margin loss.  A model declaring
        ``supports_fused_kernel = True`` MUST override this (and
        ``fused_rank_counts``) with its own kernel — the dispatch in
        kernels/ops.py calls it blindly."""
        raise NotImplementedError(
            f"{self.name!r} sets supports_fused_kernel but does not "
            "implement fused_margin_loss")

    def fused_rank_counts(
        self,
        params: Params,
        triplets: jax.Array,
        side: str,
        *,
        norm: str,
        interpret: bool = False,
    ) -> jax.Array:
        """Pallas-fused entity-inference rank counts (see fused_margin_loss)."""
        raise NotImplementedError(
            f"{self.name!r} sets supports_fused_kernel but does not "
            "implement fused_rank_counts")

    # -- negative sampling --------------------------------------------------

    def make_negatives(
        self,
        key: jax.Array,
        pos_batches: jax.Array,
        cfg: KGConfig,
        head_prob_per_rel: jax.Array | None = None,
    ) -> jax.Array:
        """Corrupted counterparts of ``pos_batches`` (Eq. 2).  Models with a
        bespoke corruption scheme override this."""
        return negative.make_negatives(
            key, pos_batches, cfg.n_entities, cfg.sampling, head_prob_per_rel
        )

    # -- joint negative scoring (DGL-KE-style shared candidate pool) --------

    def joint_parts(
        self, pos: jax.Array, neg: jax.Array, n_candidates: int
    ) -> tuple[jax.Array, jax.Array]:
        """Derive the shared corruption pool from the per-triplet negatives:
        ``cand`` is the batch's first C corrupted entities, ``side_head``
        marks which side each positive's corruption replaced.  No new
        randomness — the pool reuses the engine's existing negative stream,
        so the joint scheme inherits the (seed, epoch, worker) determinism
        contract for free."""
        side_head = neg[:, 0] != pos[:, 0]
        corrupted = jnp.where(side_head, neg[:, 0], neg[:, 2])
        C = corrupted.shape[0] if n_candidates == 0 else n_candidates
        cand = corrupted[: min(C, corrupted.shape[0])]
        return cand, side_head

    def joint_energies(
        self,
        params: Params,
        pos: jax.Array,          # (B, 3)
        cand: jax.Array,         # (C,) shared candidate entity ids
        side_head: jax.Array,    # (B,) bool: candidate replaces the head
        norm: str = "l1",
    ) -> jax.Array:
        """Energy of every candidate substituted into every positive's
        corruption side: ``(B, C)``.  Generic fallback substitutes one
        candidate at a time (vmapped) — column ``c`` at row ``b`` is exactly
        ``energy`` of the substituted triplet, so the diagonal with
        per-triplet candidates reproduces ``energy(neg)`` bitwise
        (tests/test_async_schedule.py pins it).  Models override with
        matmul/broadcast closed forms."""

        def one(e):
            h = jnp.where(side_head, e, pos[:, 0])
            t = jnp.where(side_head, pos[:, 2], e)
            trip = jnp.stack([h, pos[:, 1], t], axis=1).astype(pos.dtype)
            return self.energy(params, trip, norm)

        return jax.vmap(one)(cand).T                          # (B, C)

    def joint_hinges(
        self,
        params: Params,
        pos: jax.Array,
        neg: jax.Array,
        *,
        margin: float,
        norm: str,
        n_candidates: int = 0,
    ) -> tuple[jax.Array, jax.Array]:
        """The (B, C) hinge matrix of the joint objective plus its validity
        mask (a candidate equal to a positive's gold entity on the corrupted
        side is a false negative and is masked out, Eq. 2's constraint)."""
        cand, side_head = self.joint_parts(pos, neg, n_candidates)
        d_pos = self.energy(params, pos, norm)                # (B,)
        d_cand = self.joint_energies(params, pos, cand, side_head, norm)
        gold = jnp.where(side_head, pos[:, 0], pos[:, 2])
        valid = (cand[None, :] != gold[:, None]).astype(d_cand.dtype)
        return pairwise_hinge(d_pos[:, None], d_cand, margin) * valid, valid

    def joint_margin_loss(
        self,
        params: Params,
        pos: jax.Array,
        neg: jax.Array,
        *,
        margin: float,
        norm: str,
        n_candidates: int = 0,
    ) -> jax.Array:
        """Mean hinge over the B·C valid (positive, candidate) pairs — the
        joint-sampling analogue of :meth:`margin_loss`."""
        hinges, valid = self.joint_hinges(
            params, pos, neg, margin=margin, norm=norm,
            n_candidates=n_candidates)
        return jnp.sum(hinges) / jnp.maximum(jnp.sum(valid), 1.0)

    def joint_pair_loss(
        self,
        params: Params,
        pos: jax.Array,
        neg: jax.Array,
        *,
        margin: float,
        norm: str,
        n_candidates: int = 0,
    ) -> jax.Array:
        """Per-positive mean hinge over its valid candidates — the joint
        analogue of :meth:`per_pair_loss` for the Reduce touch stats."""
        hinges, valid = self.joint_hinges(
            params, pos, neg, margin=margin, norm=norm,
            n_candidates=n_candidates)
        return jnp.sum(hinges, axis=1) / jnp.maximum(
            jnp.sum(valid, axis=1), 1.0)

    def _loss_fn(self, cfg: KGConfig):
        """The training objective ``(params, pos, neg) -> loss`` the config
        selects: the per-triplet margin loss, or the joint-candidate one."""
        if cfg.negatives == "joint":
            return functools.partial(
                self.joint_margin_loss, margin=cfg.margin, norm=cfg.norm,
                n_candidates=cfg.neg_candidates)
        return functools.partial(
            self.margin_loss, margin=cfg.margin, norm=cfg.norm)

    def _pair_loss_fn(self, cfg: KGConfig):
        """Per-positive loss ``(params, pos, neg) -> (B,)`` matching
        :meth:`_loss_fn` — feeds the per-key Reduce touch stats."""
        if cfg.negatives == "joint":
            return functools.partial(
                self.joint_pair_loss, margin=cfg.margin, norm=cfg.norm,
                n_candidates=cfg.neg_candidates)
        return functools.partial(
            self.per_pair_loss, margin=cfg.margin, norm=cfg.norm)

    # -- shared engine math (identical for every model) ---------------------

    def margin_loss(
        self,
        params: Params,
        pos: jax.Array,
        neg: jax.Array,
        *,
        margin: float,
        norm: str,
    ) -> jax.Array:
        """Mean margin ranking loss over a batch of (pos, neg) triplet pairs.

        The paper sums over the training set; we use the mean so the learning
        rate is batch-size independent (equivalent up to lr rescaling)."""
        d_pos = self.energy(params, pos, norm)
        d_neg = self.energy(params, neg, norm)
        return jnp.mean(pairwise_hinge(d_pos, d_neg, margin))

    def per_pair_loss(
        self,
        params: Params,
        pos: jax.Array,
        neg: jax.Array,
        *,
        margin: float,
        norm: str,
    ) -> jax.Array:
        """Hinge per (pos, neg) pair — per-key loss bookkeeping for the
        mini-loss Reduce strategy."""
        return pairwise_hinge(
            self.energy(params, pos, norm), self.energy(params, neg, norm), margin
        )

    def sgd_step(
        self, params: Params, pos: jax.Array, neg: jax.Array, cfg: KGConfig
    ) -> tuple[Params, jax.Array]:
        """One (mini-batch) SGD step of Algorithm 1's inner loop (the
        objective — per-triplet or joint — comes from ``cfg.negatives``)."""
        loss, grads = jax.value_and_grad(self._loss_fn(cfg))(params, pos, neg)
        params = jax.tree.map(
            lambda p, g: p - cfg.learning_rate * g, params, grads
        )
        if cfg.normalize == "step":
            params = self.normalize(params)
        return params, loss

    def _compact_batch(
        self, params: Params, pos: jax.Array, neg: jax.Array, cfg: KGConfig
    ) -> tuple[dict, Params, jax.Array, jax.Array]:
        """Candidate row sets + compact tables + remapped triplets for one
        batch: every row the batch references, deduplicated, with static
        capacity (4B entity / 2B relation slots, padded with the
        out-of-range id ``n_rows`` so scatters drop them)."""
        ent_ids = jnp.concatenate([pos[:, 0], pos[:, 2], neg[:, 0], neg[:, 2]])
        rel_ids = jnp.concatenate([pos[:, 1], neg[:, 1]])
        E, R = cfg.n_entities, cfg.n_relations
        cand = {
            "ent": jnp.unique(ent_ids, size=int(min(E, ent_ids.shape[0])),
                              fill_value=E),
            "rel": jnp.unique(rel_ids, size=int(min(R, rel_ids.shape[0])),
                              fill_value=R),
        }
        roles = self.param_roles()
        compact = {
            name: jnp.take(params[name], cand[roles[name]], axis=0,
                           mode="fill", fill_value=0.0)
            for name in params
        }

        def remap(t):
            return jnp.stack([
                jnp.searchsorted(cand["ent"], t[:, 0]),
                jnp.searchsorted(cand["rel"], t[:, 1]),
                jnp.searchsorted(cand["ent"], t[:, 2]),
            ], axis=1).astype(t.dtype)

        return cand, compact, remap(pos), remap(neg)

    def sgd_step_sparse(
        self, params: Params, pos: jax.Array, neg: jax.Array, cfg: KGConfig,
        update_mask: Params | None = None,
    ) -> tuple[Params, jax.Array]:
        """:meth:`sgd_step` touching only the rows the batch references —
        the ParaGraphE idiom, and the Map-phase half of the sparse
        transport (``merge_transport="sparse"``): per step the tables see
        one O(batch) gather and one O(batch) scatter instead of a
        table-sized gradient materialization.

        Bitwise-identical to the dense step: the energy evaluated on the
        gathered compact tables computes the same floats (gathers
        compose), its gradient is the same per-row scatter-add of the same
        cotangents in the same update order (just into compact buffers),
        and a row no batch id references has gradient exactly ``+0.0``
        under the dense step (``p - lr*0 == p`` bitwise), so skipping it
        changes nothing.  tests/test_sparse_transport.py pins the
        equivalence across models, strategies, and pipelines.

        ``update_mask`` (the online tier's masked fine-tune) freezes every
        row whose mask bit is False: a frozen candidate row scatters its
        *unchanged* compact value back (a bitwise no-op), while free rows
        step normally against the pristine frozen values."""
        cand, compact, pos_c, neg_c = self._compact_batch(
            params, pos, neg, cfg)
        # the remap preserves id (in)equality — both pos and neg ids appear
        # in the candidate list and searchsorted maps them injectively — so
        # the joint objective's side/candidate/gold-mask derivation computes
        # the same booleans on the compact triplets as on the originals
        loss, grads = jax.value_and_grad(self._loss_fn(cfg))(
            compact, pos_c, neg_c)
        roles = self.param_roles()
        stepped = {
            name: compact[name] - cfg.learning_rate * grads[name]
            for name in params
        }
        if update_mask is not None:
            free = {
                name: jnp.take(update_mask[name], cand[roles[name]],
                               mode="fill", fill_value=False)
                for name in params
            }
            stepped = {
                name: jnp.where(free[name][:, None], stepped[name],
                                compact[name])
                for name in params
            }
        params = {
            name: params[name].at[cand[roles[name]]].set(
                stepped[name], mode="drop")
            for name in params
        }
        if cfg.normalize == "step":
            params = self._masked_normalize(params, update_mask)
        return params, loss

    def _masked_normalize(
        self, params: Params, update_mask: Params | None
    ) -> Params:
        """:meth:`normalize`, with frozen rows clamped back bitwise when an
        ``update_mask`` is in play (re-projection of an already-trained row
        is not always the identity — e.g. 'epoch'-mode artifacts)."""
        normed = self.normalize(params)
        if update_mask is None:
            return normed
        return {
            name: jnp.where(update_mask[name][:, None], normed[name],
                            params[name])
            for name in params
        }

    def run_epoch(
        self,
        params: Params,
        pos_batches: jax.Array,     # (S, B, 3) minibatches of training triplets
        neg_batches: jax.Array,     # (S, B, 3) corrupted counterparts
        cfg: KGConfig,
        sparse_apply: bool = False,
        update_mask: Params | None = None,
    ) -> tuple[Params, EpochStats]:
        """One epoch of Algorithm 1 on one worker: constraint projection, then
        scan SGD over the worker's minibatches, tracking the per-key stats
        Reduce needs.  Pure; used by the vmap backend (vmapped over workers)
        and inside shard_map (per shard).  ``sparse_apply`` swaps the step
        for the bitwise-identical compact-row :meth:`sgd_step_sparse`
        (engaged by ``merge_transport="sparse"``).  ``update_mask`` (one
        bool row-mask per param table) freezes unmasked rows bitwise — the
        online tier's incremental fine-tune; it requires the sparse step."""
        if update_mask is not None and not sparse_apply:
            raise ValueError(
                "update_mask requires sparse_apply=True — the masked "
                "fine-tune rides the compact-row step's candidate gather")
        if update_mask is not None:
            step = functools.partial(
                self.sgd_step_sparse, update_mask=update_mask)
        else:
            step = self.sgd_step_sparse if sparse_apply else self.sgd_step
        pair_fn = self._pair_loss_fn(cfg)
        if cfg.normalize == "epoch":
            params = self._masked_normalize(params, update_mask)
        E, R = cfg.n_entities, cfg.n_relations
        zeros = (
            jnp.zeros((E,), cfg.dtype),
            jnp.zeros((E,), cfg.dtype),
            jnp.zeros((R,), cfg.dtype),
            jnp.zeros((R,), cfg.dtype),
        )

        def body(carry, batch):
            params, stats, loss_sum = carry
            pos, neg = batch
            pair = pair_fn(params, pos, neg)
            params, loss = step(params, pos, neg, cfg)
            stats = _accumulate_touch(stats, pos, neg, pair, E, R)
            return (params, stats, loss_sum + loss), None

        (params, stats, loss_sum), _ = jax.lax.scan(
            body,
            (params, zeros, jnp.zeros((), cfg.dtype)),
            (pos_batches, neg_batches),
        )
        n_steps = pos_batches.shape[0]
        epoch_stats = EpochStats(
            mean_loss=loss_sum / n_steps,
            ent_count=stats[0],
            ent_loss=stats[1],
            rel_count=stats[2],
            rel_loss=stats[3],
        )
        return params, epoch_stats

    def batch_gradients(
        self, params: Params, pos: jax.Array, neg: jax.Array, cfg: KGConfig
    ) -> tuple[jax.Array, Params]:
        """Loss and gradients for the BGD Map phase (§3.2.1): the worker emits
        gradients, never touching its local params.  ``cfg.negatives``
        selects the per-triplet or joint objective, same as the SGD step."""
        return jax.value_and_grad(self._loss_fn(cfg))(params, pos, neg)
