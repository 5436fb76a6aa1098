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


def _distinct(
    ids: jax.Array, n_rows: int, size: int
) -> tuple[jax.Array, jax.Array]:
    """``jnp.unique(ids, size=size, fill_value=n_rows,
    return_inverse=True)`` for ids in ``[0, n_rows)``, by three sorts:
    the distinct ids ascending, padded with ``n_rows``, and each id's slot
    among them.  ``jnp.unique`` builds both with scatters, which the TPU
    runs an element at a time."""
    s, order = jax.lax.sort(
        (ids, jnp.arange(ids.shape[0], dtype=ids.dtype)), num_keys=1)
    first = jnp.concatenate([jnp.ones((1,), bool), s[1:] != s[:-1]])
    slot_sorted = jnp.cumsum(first.astype(ids.dtype)) - 1
    distinct = jax.lax.sort(jnp.where(first, s, n_rows))[:size]
    _, slot = jax.lax.sort((order, slot_sorted), num_keys=1)
    return distinct, slot


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


def _zero_touch(cfg: KGConfig, lead: tuple = ()) -> tuple:
    """Empty touch stats for :func:`_accumulate_touch` (``lead`` workers)."""
    E, R = cfg.n_entities, cfg.n_relations
    return tuple(jnp.zeros(lead + (n,), cfg.dtype) for n in (E, E, R, R))


def _epoch_stats(stats: tuple, loss_sum: jax.Array, n_steps: int) -> EpochStats:
    return EpochStats(mean_loss=loss_sum / n_steps, ent_count=stats[0],
                      ent_loss=stats[1], rel_count=stats[2], rel_loss=stats[3])


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

    def width(self, params: Params) -> int:
        """The configured width ``k`` (``KGConfig.dim``) of ``params``:
        the entity row's, unless the model stores rows of another width."""
        return int(params["ent"].shape[1])

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

    def fused_scan_cells(
        self, params: Params, rows: int, norm: str
    ) -> tuple[int, int]:
        """(scanned, useful) (query, entity, column) cells one
        :meth:`fused_rank_counts` call over ``rows`` queries covers: with
        the kernel's padding, and the problem's own (host arithmetic on
        shapes, ``kernels/rank_topk.scan_cells``)."""
        raise NotImplementedError(
            f"{self.name!r} sets supports_fused_kernel but does not "
            "implement fused_scan_cells")

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

    def _candidates(
        self, pos: jax.Array, neg: jax.Array, cfg: KGConfig
    ) -> tuple[dict, jax.Array, jax.Array]:
        """Every row one batch references, per role — deduplicated, sorted,
        with static capacity (4B entity / 2B relation slots, capped at the
        table) and padded with the out-of-range id ``n_rows`` so scatters
        drop them — and the batch's triplets rewritten as slots of those
        row sets."""
        B = pos.shape[0]
        E, R = cfg.n_entities, cfg.n_relations
        ent, ent_slot = _distinct(
            jnp.concatenate([pos[:, 0], pos[:, 2], neg[:, 0], neg[:, 2]]),
            E, int(min(E, 4 * B)))
        rel, rel_slot = _distinct(
            jnp.concatenate([pos[:, 1], neg[:, 1]]), R, int(min(R, 2 * B)))
        ent_slot = ent_slot.reshape(4, B).astype(pos.dtype)
        rel_slot = rel_slot.reshape(2, B).astype(pos.dtype)
        pos_c = jnp.stack([ent_slot[0], rel_slot[0], ent_slot[1]], axis=1)
        neg_c = jnp.stack([ent_slot[2], rel_slot[1], ent_slot[3]], axis=1)
        return {"ent": ent, "rel": rel}, pos_c, neg_c

    def _gather_rows(self, params: Params, cand: dict) -> Params:
        """The compact tables: each table's candidate rows (zeros for
        padding slots)."""
        roles = self.param_roles()
        return {
            name: jnp.take(params[name], cand[roles[name]], axis=0,
                           mode="fill", fill_value=0.0)
            for name in params
        }

    def _compact_update(
        self, compact: Params, cand: dict, pos_c: jax.Array,
        neg_c: jax.Array, cfg: KGConfig, update_mask: Params | None = None,
    ) -> tuple[Params, jax.Array]:
        """The SGD step on one batch's compact tables (rows ``cand``,
        triplets remapped to slots): the stepped rows and the loss.  Frozen
        rows of ``update_mask`` keep their compact value."""
        # the remap preserves id (in)equality — every pos and neg id has
        # its own slot in the candidate list — so the joint objective's
        # side/candidate/gold-mask derivation computes the same booleans on
        # the compact triplets as on the originals
        loss, grads = jax.value_and_grad(self._loss_fn(cfg))(
            compact, pos_c, neg_c)
        stepped = {
            name: compact[name] - cfg.learning_rate * grads[name]
            for name in compact
        }
        if update_mask is not None:
            roles = self.param_roles()
            stepped = {
                name: jnp.where(
                    jnp.take(update_mask[name], cand[roles[name]],
                             mode="fill", fill_value=False)[:, None],
                    stepped[name], compact[name])
                for name in compact
            }
        return stepped, loss

    def sgd_step_sparse(
        self, params: Params, pos: jax.Array, neg: jax.Array, cfg: KGConfig,
        update_mask: Params | None = None,
    ) -> tuple[Params, jax.Array]:
        """:meth:`sgd_step` touching only the rows the batch references —
        the ParaGraphE idiom: per step the tables see one O(batch) gather
        and one O(batch) scatter instead of a table-sized gradient
        materialization.

        The dense step's arithmetic: the energy evaluated on the gathered
        compact tables computes the same floats (gathers compose), its
        gradient sums the same per-row cotangents (just into compact
        buffers), and a row no batch id references has gradient exactly
        ``+0.0`` under the dense step (``p - lr*0 == p`` bitwise), so
        skipping it changes nothing.  Only the order in which XLA sums a
        repeated row's contributions may differ between the two programs,
        in the last bit of that row.  tests/test_sparse_transport.py pins
        the two bitwise where the order agrees, and within a few ulps where
        the candidate set is smaller than the table.

        ``update_mask`` (the online tier's masked fine-tune) freezes every
        row whose mask bit is False: a frozen candidate row scatters its
        *unchanged* compact value back (a bitwise no-op), while free rows
        step normally against the pristine frozen values."""
        cand, pos_c, neg_c = self._candidates(pos, neg, cfg)
        roles = self.param_roles()
        compact = self._gather_rows(params, cand)
        stepped, loss = self._compact_update(
            compact, cand, pos_c, neg_c, cfg, update_mask)
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
        for the compact-row one (:meth:`run_epoch_flat` with one worker;
        ``mapreduce.compact_map`` decides it on the device pipeline, the
        host pipeline engages it with ``merge_transport="sparse"``).
        ``update_mask`` (one bool row-mask per param table) freezes
        unmasked rows bitwise — the online tier's incremental fine-tune; it
        requires the sparse step."""
        if update_mask is not None and not sparse_apply:
            raise ValueError(
                "update_mask requires sparse_apply=True — the masked "
                "fine-tune rides the compact-row step's candidate gather")
        if sparse_apply:
            params, stats = self.run_epoch_flat(
                params, pos_batches[None], neg_batches[None], cfg,
                update_mask=update_mask)
            return params, jax.tree.map(lambda x: x[0], stats)
        pair_fn = self._pair_loss_fn(cfg)
        if cfg.normalize == "epoch":
            params = self.normalize(params)

        def body(carry, batch):
            params, stats, loss_sum = carry
            pos, neg = batch
            pair = pair_fn(params, pos, neg)
            params, loss = self.sgd_step(params, pos, neg, cfg)
            stats = _accumulate_touch(stats, pos, neg, pair,
                                      cfg.n_entities, cfg.n_relations)
            return (params, stats, loss_sum + loss), None

        (params, stats, loss_sum), _ = jax.lax.scan(
            body, (params, _zero_touch(cfg), jnp.zeros((), cfg.dtype)),
            (pos_batches, neg_batches))
        return params, _epoch_stats(stats, loss_sum, pos_batches.shape[0])

    def run_epoch_flat(
        self,
        flat: Params,               # (W * N, k) per table: W workers' rows
        pos_batches: jax.Array,     # (W, S, B, 3)
        neg_batches: jax.Array,     # (W, S, B, 3)
        cfg: KGConfig,
        update_mask: Params | None = None,
    ) -> tuple[Params, EpochStats]:
        """:meth:`run_epoch` with ``sparse_apply`` for W workers at once,
        on their tables laid end to end: worker ``w``'s row ``i`` is flat
        row ``w * N + i``.  Each step takes every worker's candidate rows
        in one gather (ids offset by ``w * N``), steps the compact
        ``(W, 4B, k)`` / ``(W, 2B, k)`` buffers per worker, and writes them
        back with one scatter; padding slots point past the flat table and
        drop.  No step touches a row outside its batch, and the flat carry
        keeps XLA from flattening a ``(W, N, k)`` table around each batched
        scatter.  Per worker the arithmetic is :meth:`sgd_step_sparse`'s;
        the pair losses for the touch stats come from the same compact
        tables (gathers compose, so they are the dense step's floats).
        Returns the flat tables and ``(W,)``-stacked stats; with ``W = 1``
        the flat table is the worker's own, which is how :meth:`run_epoch`
        runs its compact step."""
        W = pos_batches.shape[0]
        roles = self.param_roles()
        E, R = cfg.n_entities, cfg.n_relations
        n_rows = {"ent": E, "rel": R}
        pair_fn = self._pair_loss_fn(cfg)

        def project(flat):
            # the constraint projection per worker, on the (W, N, k) view
            view = {name: x.reshape((W, -1) + x.shape[1:])
                    for name, x in flat.items()}
            view = jax.vmap(
                lambda p: self._masked_normalize(p, update_mask))(view)
            return {name: x.reshape(flat[name].shape)
                    for name, x in view.items()}

        def worker(compact, cand, pos_c, neg_c):
            pair = pair_fn(compact, pos_c, neg_c)
            stepped, loss = self._compact_update(
                compact, cand, pos_c, neg_c, cfg, update_mask)
            return stepped, loss, pair

        def body(carry, batch):
            flat, stats, loss_sum = carry
            pos, neg = batch                                 # (W, B, 3)
            cand, pos_c, neg_c = jax.vmap(
                lambda p, q: self._candidates(p, q, cfg))(pos, neg)
            rows = {
                role: jnp.where(
                    c < n_rows[role],
                    c + n_rows[role] * jnp.arange(W, dtype=c.dtype)[:, None],
                    W * n_rows[role]).reshape(-1)
                for role, c in cand.items()
            }
            compact = {
                name: jnp.take(x, rows[roles[name]], axis=0, mode="fill",
                               fill_value=0.0).reshape(
                                   (W, -1) + x.shape[1:])
                for name, x in flat.items()
            }
            stepped, loss, pair = jax.vmap(worker)(compact, cand, pos_c,
                                                   neg_c)
            flat = {
                name: x.at[rows[roles[name]]].set(
                    stepped[name].reshape((-1,) + x.shape[1:]),
                    mode="drop")
                for name, x in flat.items()
            }
            if cfg.normalize == "step":
                flat = project(flat)
            stats = jax.vmap(
                lambda s, p, q, l: _accumulate_touch(s, p, q, l, E, R))(
                    stats, pos, neg, pair)
            return (flat, stats, loss_sum + loss), None

        if cfg.normalize == "epoch":
            flat = project(flat)
        (flat, stats, loss_sum), _ = jax.lax.scan(
            body, (flat, _zero_touch(cfg, (W,)), jnp.zeros((W,), cfg.dtype)),
            (jnp.swapaxes(pos_batches, 0, 1),
             jnp.swapaxes(neg_batches, 0, 1)))
        return flat, _epoch_stats(stats, loss_sum, pos_batches.shape[1])

    def batch_gradients(
        self, params: Params, pos: jax.Array, neg: jax.Array, cfg: KGConfig
    ) -> tuple[jax.Array, Params]:
        """Loss and gradients for the BGD Map phase (§3.2.1): the worker emits
        gradients, never touching its local params.  ``cfg.negatives``
        selects the per-triplet or joint objective, same as the SGD step."""
        return jax.value_and_grad(self._loss_fn(cfg))(params, pos, neg)
