"""The model-agnostic MapReduce KG-embedding engine (paper §3).

The paper parallelizes TransE; this engine parallelizes any registered
``KGModel`` (``repro.core.models``: transe / transh / distmult / yours) —
the Map/Reduce machinery never looks inside the scoring function.  Most
callers should use the top-level facade instead of this module:

    from repro import kg
    result = kg.fit(my_kg, model="distmult", paradigm="bgd", epochs=50)

Two paradigms, exactly as the paper structures them:

  * **SGD-based** (§3.1): Map = each worker runs a full local-SGD epoch on its
    balanced subset with a private copy of the embeddings; Reduce = merge the
    W inconsistent copies per key (``core/merge.py`` strategies).  The merges
    are applied per embedding table, routed by the model's ``param_roles()``
    (entity- vs relation-indexed touch stats) — extra tables like TransH's
    hyperplane normals ride through with zero engine changes.
  * **BGD-based** (§3.2): Map = each worker computes the *gradient* of its
    subset batch; Reduce = sum gradients; one global update.  Conflict-free
    by construction — this is synchronous data-parallel training.

Two execution backends with identical math:

  * ``vmap``      — simulated workers on a single device (leading worker axis
                    via ``jax.vmap``).  Exact semantics, used for quality
                    benchmarks and tests on this CPU-only container.
  * ``shard_map`` — real devices along a mesh axis; Reduce runs as
                    ``jax.lax`` collectives.  ``reduce_impl`` picks the
                    paper-literal ``allgather`` Reduce or the optimized
                    ``psum`` winner-select Reduce (see merge.py).

Two **data pipelines**, selected by ``MapReduceConfig.pipeline``:

  * ``host``   — the original per-epoch loop: numpy batch permutations
                 (``data/kg.epoch_batches``), one H2D transfer, one jit
                 dispatch, and one blocking ``float(loss)`` sync per epoch.
                 Kept as the reference path (the ``repro.core.transe`` shim
                 reproduces it bit-for-bit) — but dispatch overhead, not the
                 Map/Reduce math, dominates small-to-medium graphs.
  * ``device`` — the **scanned driver** (``make_block_fn``): the partitioned
                 triplets are placed on device once at ``train()`` start, and
                 a whole block of epochs runs as ONE compiled
                 ``jax.lax.scan``.  Per-epoch batching (permutations from
                 ``fold_in(seed, epoch)`` keys), negative sampling, and the
                 Reduce merge keys are all folded into the scanned epoch
                 body, so no per-epoch host work remains; the loss history
                 comes back as a device array per block and callbacks fire at
                 block boundaries only.

Epoch scheduling (``EpochSchedule``, device pipeline only):

  * ``block_epochs``  — epochs per compiled scan block (one jit dispatch per
                        block; results are bit-identical for any block size).
  * ``merge_every=K`` — SGD workers run K local epochs between Reduces
                        (touch stats accumulate across the K epochs); a
                        beyond-paper schedule the scanned driver makes nearly
                        free, trading merge traffic for local drift.
  * ``repartition_every=M`` — re-split the triplets across workers on
                        device every M epochs (round r = e // M indexes a
                        fresh global permutation; round 0 is the original
                        partition), killing the residual split bias of a
                        partition frozen at start.
  * ``donate_params``  — (MapReduceConfig; device pipeline, default on)
                        donate the params buffer to each block call so the
                        accelerator never holds two copies of the tables.

Beyond the paper's barrier (the scheduling lab; all composable):

  * ``staleness=S``     — bounded-staleness Reduce (SGD + device pipeline):
                          worker ``w`` re-reads the merged global view only
                          at rounds ``r`` with ``(r + o_w) % (S+1) == 0``
                          (plus round 0), training against a view up to S
                          rounds stale in between; every worker's deltas
                          still merge every round (participation-masked
                          stale Reduce, ``merge.merge_*_stale``).  The
                          refresh schedule is ``fold_in``-pure in
                          (seed, round, worker) — see ``make_block_fn`` —
                          so S=0 is bit-identical to the synchronous path
                          and vmap == shard_map bitwise.  Checkpoint/resume
                          is refused under S>0 (worker locals are scratch
                          state the manifest cannot capture).
  * ``partition=...``   — ``data/kg.PARTITIONERS``: 'balanced' (the paper's
                          random equal split), 'stratified', 'degree'
                          (degree-stratified mix per worker), 'overlap'
                          (greedy minimal cross-worker entity overlap);
                          all thread through on-device re-partitioning.
  * ``negatives='joint'`` — DGL-KE-style joint sampling (both paradigms):
                          one shared corruption batch of ``neg_candidates``
                          scored against every positive as a (B, C) matrix
                          (a matmul for TransE l2) instead of per-triplet
                          gathers; gold-colliding candidates are masked.
                          See ``core/negative.py`` + ``models/base.joint_*``.

In-training evaluation: ``train(..., eval_loop=EvalLoopConfig(...))`` (or
``kg.fit(eval_every=K)``) runs the evaluation protocol at Reduce
boundaries — the host pipeline evaluates between epochs, the device driver
slices its compiled blocks at eval boundaries (free in results by block
invariance) — and returns a ``core/trace.TrainingTrace`` of
quality-vs-epoch curves with optional early stopping and best-params
checkpointing.

The module-level ``train()`` drives blocks (device) or epochs (host)
host-side and is what ``repro.kg.fit`` calls.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import obs
from repro.core import merge as merge_lib
from repro.core import negative
from repro.core import models as kg_models
from repro.core import trace as trace_lib
from repro.core.models.base import EpochStats, KGConfig, KGModel, Params, apply_gradients
from repro.data import kg as kg_lib
from repro.parallel.sharding import kg_partitions, kg_table_shardings
from repro.parallel.util import all_gather_deltas
from repro.util import warn_fresh


@dataclasses.dataclass(frozen=True)
class EpochSchedule:
    """How the device pipeline groups epochs (see the module docstring).

    ``block_epochs`` epochs run as one compiled ``lax.scan`` (one jit
    dispatch per block — any block size gives bit-identical results);
    every ``merge_every`` epochs the SGD Reduce runs, so K > 1 lets each
    Map worker take K local epochs between merges.  ``block_epochs`` must
    be a multiple of ``merge_every`` (blocks end on a merge boundary).

    ``repartition_every=M`` re-splits the triplets across workers on
    device every M epochs (``data/kg.device_repartition``) — the epoch
    batching already redraws within-worker permutations per epoch, but the
    worker membership of each triplet is otherwise frozen at ``train()``
    start; M kills that residual split bias.  The effective partition of
    epoch ``e`` is a pure function of (seed, ``e // M``) — round 0 is the
    original partition — so block-size invariance is untouched and
    ``M >= epochs`` is bit-identical to ``M=None`` (off).  M must be a
    multiple of ``merge_every``: workers hold their subset for whole
    Reduce rounds (the paper's Map contract), and the driver slices
    compiled blocks at re-partition boundaries so the permutation +
    gather runs once per round, not once per epoch."""

    block_epochs: int = 1
    merge_every: int = 1
    repartition_every: Optional[int] = None

    def __post_init__(self):
        if self.block_epochs < 1:
            raise ValueError(f"block_epochs must be >= 1, got {self.block_epochs}")
        if self.merge_every < 1:
            raise ValueError(f"merge_every must be >= 1, got {self.merge_every}")
        if self.block_epochs % self.merge_every != 0:
            raise ValueError(
                f"block_epochs={self.block_epochs} must be a multiple of "
                f"merge_every={self.merge_every} so every block ends on a "
                "Reduce boundary")
        if self.repartition_every is not None and (
            self.repartition_every < 1
            or self.repartition_every % self.merge_every != 0
        ):
            raise ValueError(
                f"repartition_every must be >= 1 (or None to disable) and "
                f"a multiple of merge_every={self.merge_every} — workers "
                "hold their subset for whole Reduce rounds; got "
                f"{self.repartition_every}")


@dataclasses.dataclass(frozen=True)
class CheckpointConfig:
    """Periodic training checkpoints at Reduce boundaries (``train()`` /
    ``kg.fit(checkpoint_every=K, ckpt_dir=...)``).

    ``every`` counts epochs between snapshots (a multiple of
    ``merge_every`` on the device pipeline — checkpoints are shared-model
    states, which only exist at Reduce boundaries); ``None`` saves only
    the final state.  The run's last epoch (including an early stop) is
    always checkpointed, so ``resume=True`` can always continue.  Saves go
    through ``train/checkpoint.AsyncSaver`` by default — the loop pays
    the device->host snapshot, a daemon thread pays the disk I/O;
    ``synchronous=True`` forces in-line writes (tests, tiny runs).

    The manifest records model name, seed, graph fingerprint, epoch, and
    the loss history so far — everything ``kg.fit(resume=True)`` needs to
    continue **bit-identically** (the device pipeline's randomness is a
    pure function of (seed, epoch); the host pipeline's split-chain is
    replayed from the manifest's epoch)."""

    ckpt_dir: str
    every: Optional[int] = None
    keep: int = 3
    synchronous: bool = False

    def __post_init__(self):
        if self.every is not None and self.every < 1:
            raise ValueError(f"every must be >= 1 (or None), got {self.every}")


def resume_config(tcfg: KGConfig, cfg: MapReduceConfig) -> dict:
    """The manifest fields a resume must match for bit-identity: every
    knob that shapes the training trajectory — partitioning, batching,
    schedule, paradigm/pipeline/strategy, and the scalar hyperparameters.
    ``backend`` is deliberately absent (vmap and shard_map are proved
    equivalent, so resuming a vmap checkpoint on a real mesh is fine), as
    are ``block_epochs`` (block-size invariance), ``merge_transport``
    (the sparse transport is bit-identical to dense, so a dense-trained
    checkpoint resumes under sparse transport and vice versa),
    ``table_sharding`` (the shard-routed merge is bit-identical to the
    replicated one, so checkpoints move freely between layouts), and
    ``touched_capacity`` (any validated capacity packs the same rows)."""
    return {
        "paradigm": cfg.paradigm,
        "pipeline": cfg.pipeline,
        "n_workers": cfg.n_workers,
        "batch_size": cfg.batch_size,
        "partition": cfg.partition,
        "staleness": cfg.staleness,
        "strategy": cfg.strategy if cfg.paradigm == "sgd" else None,
        "merge_every": cfg.schedule.merge_every,
        "repartition_every": cfg.schedule.repartition_every,
        "margin": tcfg.margin,
        "norm": tcfg.norm,
        "learning_rate": tcfg.learning_rate,
        "normalize": tcfg.normalize,
        "sampling": tcfg.sampling,
    }


class _CheckpointWriter:
    """Driver-side checkpoint hook: owns the AsyncSaver and the shared
    manifest fields; both pipeline loops call ``due`` / ``save``."""

    def __init__(self, cfg: CheckpointConfig, base_extra: dict):
        from repro.train import checkpoint as checkpoint_lib

        self._lib = checkpoint_lib
        self.cfg = cfg
        self.base = base_extra
        self.saver = None if cfg.synchronous else checkpoint_lib.AsyncSaver()
        self.last_saved: Optional[int] = None

    def due(self, done: int, epochs: int, stopping: bool = False) -> bool:
        if done == self.last_saved:
            return False
        return (
            done == epochs
            or stopping
            or (self.cfg.every is not None and done % self.cfg.every == 0)
        )

    def save(self, done: int, params, history) -> None:
        extra = dict(self.base, epoch=done, loss_history=list(history))
        self.last_saved = done
        if self.saver is None:
            self._lib.save(self.cfg.ckpt_dir, done, params, extra=extra,
                           keep=self.cfg.keep)
        else:
            self.saver.save_async(self.cfg.ckpt_dir, done, params,
                                  extra=extra, keep=self.cfg.keep)

    def finish(self) -> None:
        if self.saver is not None:
            self.saver.wait()


@dataclasses.dataclass(frozen=True)
class MapReduceConfig:
    n_workers: int = 4
    paradigm: str = "sgd"           # 'sgd' | 'bgd'
    strategy: str = "average"       # merge_lib.STRATEGIES (sgd paradigm only)
    reduce_impl: str = "psum"       # 'psum' | 'allgather' (shard_map backend)
    # Reduce wire format: 'dense' exchanges whole tables (the reference);
    # 'sparse' exchanges only rows the round's touch stats mark updated, as
    # statically-sized padded delta buffers — bit-identical results (see
    # the transport contract in core/merge.py).  Under shard_map, sparse
    # transport supersedes reduce_impl (the packed buffers are all-gathered;
    # there is nothing to psum).
    merge_transport: str = "dense"  # 'dense' | 'sparse'
    backend: str = "vmap"           # 'vmap' | 'shard_map'
    batch_size: int = 256
    # host partitioner (data/kg.PARTITIONERS): 'balanced' | 'stratified' |
    # 'degree' (degree-stratified) | 'overlap' (greedy overlap-minimizing).
    # The `partitioner` property is the public alias.
    partition: str = "balanced"
    axis_name: str = "workers"
    model: str = "transe"           # kg_models registry name
    pipeline: str = "host"          # 'host' | 'device' (see module docstring)
    schedule: EpochSchedule = EpochSchedule()
    # raise instead of warn when batch_size doesn't divide the worker split
    strict_batching: bool = False
    # device pipeline: donate the params buffer to each block call (halves
    # peak accelerator memory — the old params are dead the moment the
    # block's first update lands).  None = auto (on); the driver copies
    # caller-provided resume params first, so user buffers are never
    # invalidated.
    donate_params: Optional[bool] = None
    # 'replicated' keeps every worker's full (N, k) tables — the reference.
    # 'sharded' gives each of the n_workers shards ownership of a contiguous
    # row block of every table: the Reduce routes each worker's sparse delta
    # buffers to the owning shard (per-shard candidate union + local merge,
    # no full-table all_gather — see the "Sharded tables" section of
    # core/merge.py) and, on the shard_map backend's device pipeline, the
    # tables rest sharded over the mesh axis between blocks (~1/W per-device
    # table bytes).  Bit-identical to replicated for every strategy x
    # paradigm x pipeline x backend.  Requires merge_transport='sparse'.
    table_sharding: str = "replicated"
    # sparse transport: static per-round delta-buffer capacity override
    # (touched rows per worker per table).  None = the analytic
    # merge_lib.touched_capacity bound.  An override below the bound would
    # make pack_delta silently drop rows, so train() validates it against
    # the bound and raises before any epoch runs; the runtime overflow
    # check (delta_overflow) is the second seatbelt.
    touched_capacity: Optional[int] = None
    # Bounded-staleness scheduling (SGD paradigm, device pipeline): S > 0
    # lets each worker keep training against a global view up to S Reduce
    # rounds stale — worker w refreshes its local copy from the global view
    # only at rounds r with (r + o_w) % (S+1) == 0 (o_w a fold_in-derived
    # per-worker phase offset, so refreshes stagger instead of re-creating
    # the barrier), while EVERY worker's this-round deltas still merge into
    # the global view each round via the participation-masked stale Reduce
    # (core/merge.py "stale" functions).  S=0 dispatches to the synchronous
    # path verbatim — bit-identical by construction.  The whole staleness
    # schedule is a pure function of (seed, worker, round): same seed =>
    # same result, on either backend (the determinism contract,
    # docs/architecture.md; tested in tests/test_async_schedule.py).
    staleness: int = 0

    @property
    def partitioner(self) -> str:
        """Public alias of ``partition`` (the ISSUE-9 partitioner knob)."""
        return self.partition

    def __post_init__(self):
        if self.paradigm not in ("sgd", "bgd"):
            raise ValueError(f"bad paradigm {self.paradigm!r}")
        if self.paradigm == "sgd" and self.strategy not in merge_lib.STRATEGIES:
            raise ValueError(f"bad strategy {self.strategy!r}")
        if self.merge_transport not in ("dense", "sparse"):
            raise ValueError(f"bad merge_transport {self.merge_transport!r}")
        if self.table_sharding not in ("replicated", "sharded"):
            raise ValueError(f"bad table_sharding {self.table_sharding!r}")
        if self.table_sharding == "sharded" and self.merge_transport != "sparse":
            raise ValueError(
                "table_sharding='sharded' routes sparse delta buffers to "
                "their owning shards — it needs merge_transport='sparse' "
                "(the dense transport exchanges whole tables, which is the "
                "replicated layout by definition)")
        if self.touched_capacity is not None:
            if self.merge_transport != "sparse":
                raise ValueError(
                    "touched_capacity sizes the sparse transport's delta "
                    "buffers — set merge_transport='sparse' or drop it")
            if self.paradigm != "sgd":
                raise ValueError(
                    "touched_capacity is an SGD-paradigm knob (the BGD "
                    "sparse update sizes its buffers exactly from the "
                    "batch shape)")
            if self.touched_capacity < 1:
                raise ValueError(
                    f"touched_capacity must be >= 1 (or None for the "
                    f"analytic bound), got {self.touched_capacity}")
        if self.backend not in ("vmap", "shard_map"):
            raise ValueError(f"bad backend {self.backend!r}")
        if self.pipeline not in ("host", "device"):
            raise ValueError(f"bad pipeline {self.pipeline!r}")
        if self.partition not in kg_lib.PARTITIONERS:
            raise ValueError(
                f"bad partition {self.partition!r}; want one of "
                f"{tuple(kg_lib.PARTITIONERS)}")
        if (self.partition == "overlap"
                and self.schedule.repartition_every is not None):
            raise ValueError(
                "partition='overlap' cannot re-partition on device: the "
                "overlap-minimizing split is a host-side greedy stream, "
                "not a permutation the compiled pipeline can redraw — "
                "drop repartition_every or pick 'balanced'/'stratified'/"
                "'degree'")
        if self.staleness < 0:
            raise ValueError(
                f"staleness must be >= 0, got {self.staleness}")
        if self.staleness > 0 and (
            self.paradigm != "sgd" or self.pipeline != "device"
        ):
            raise ValueError(
                "staleness > 0 is the bounded-staleness SGD Reduce on the "
                "device pipeline (BGD's gradient Reduce has no local copies "
                "to go stale; the host loop Reduces synchronously every "
                "epoch) — set paradigm='sgd', pipeline='device'")
        if self.pipeline == "host" and (
            self.schedule.block_epochs != 1
            or self.schedule.merge_every != 1
            or self.schedule.repartition_every is not None
        ):
            raise ValueError(
                "EpochSchedule with block_epochs/merge_every != 1 or "
                "repartition_every set needs pipeline='device' — the host "
                "loop drives one epoch at a time with a Reduce per epoch "
                "on the partition it built at start")
        if self.schedule.merge_every > 1 and self.paradigm != "sgd":
            raise ValueError(
                "merge_every > 1 is an SGD-paradigm schedule (BGD has no "
                "Reduce merge to defer)")
        kg_models.get_model(self.model)      # raises on unknown name


def _resolve(cfg: MapReduceConfig, model: Optional[KGModel]) -> KGModel:
    return kg_models.get_model(model if model is not None else cfg.model)


# ---------------------------------------------------------------------------
# SGD paradigm
# ---------------------------------------------------------------------------

def _stats_for_role(stats: EpochStats, role: str):
    if role == "ent":
        return stats.ent_count, stats.ent_loss
    return stats.rel_count, stats.rel_loss


@obs.scope("reduce")
def _merge_tables_stacked(
    model: KGModel, strategy: str, stacked: Params, stats, merge_key: jax.Array
) -> Params:
    """Reduce every table of the stacked (leading worker axis) params dict,
    routed by the model's entity/relation roles.  Tables are merged in sorted
    name order with per-table fold-out keys ('ent' then 'rel' for TransE —
    the pre-refactor key-split order, kept bit-for-bit)."""
    roles = model.param_roles()
    names = sorted(stacked.keys())
    keys = jax.random.split(merge_key, len(names))
    out = {}
    for name, key in zip(names, keys):
        count, loss = _stats_for_role(stats, roles[name])
        out[name] = merge_lib.merge_stacked(
            strategy, stacked[name], count, loss, stats.mean_loss, key
        )
    return out


def _delta_capacity(
    cfg: MapReduceConfig, n_rows: int, n_steps: int, k_epochs: int, role: str
) -> int:
    """The static delta-buffer capacity for one table: the analytic
    :func:`merge_lib.touched_capacity` bound, or the user override
    (validated >= the bound by :func:`_check_touched_capacity` before any
    epoch runs; clamped to the table like the bound itself)."""
    if cfg.touched_capacity is not None:
        return int(min(n_rows, cfg.touched_capacity))
    return merge_lib.touched_capacity(
        n_rows, cfg.batch_size, n_steps, k_epochs, role)


def _check_touched_capacity(
    cfg: MapReduceConfig, tcfg: KGConfig, model: KGModel, n_steps: int
) -> None:
    """Fail fast at train() time when a user capacity override is below the
    analytic touched-rows bound for any table role — pack_delta's
    drop-scatter would silently discard the overflow rows otherwise."""
    if cfg.touched_capacity is None or cfg.merge_transport != "sparse":
        return
    if cfg.paradigm != "sgd":
        return
    rows = {"ent": tcfg.n_entities, "rel": tcfg.n_relations}
    K = cfg.schedule.merge_every
    for role in sorted(set(model.param_roles().values())):
        n_rows = rows[role]
        bound = merge_lib.touched_capacity(
            n_rows, cfg.batch_size, n_steps, K, role)
        if min(n_rows, cfg.touched_capacity) < bound:
            raise ValueError(
                f"touched_capacity={cfg.touched_capacity} is below the "
                f"analytic bound {bound} for {role!r}-role tables "
                f"({n_steps} steps x batch_size {cfg.batch_size} x "
                f"merge_every {K}): pack_delta would silently drop touched "
                "rows and corrupt the merge.  Raise the override or pass "
                "None to use the bound.")


def _virgin_repeats(tcfg: KGConfig, n_steps: int, k_epochs: int) -> int:
    """How many times a row *no* step touched has been through the model's
    constraint projection by Reduce time: once per epoch start
    (``normalize='epoch'``), once per step (``'step'``), never
    (``'none'``)."""
    if tcfg.normalize == "epoch":
        return k_epochs
    if tcfg.normalize == "step":
        return k_epochs * n_steps
    return 0


@obs.scope("reduce")
def _merge_tables_sparse_stacked(
    model: KGModel,
    cfg: MapReduceConfig,
    stacked: Params,
    stats,
    merge_key: jax.Array,
    base: Params,                # the shared round-input params
    tcfg: KGConfig,
    n_steps: int,
    k_epochs: int,
) -> tuple[Params, jax.Array]:
    """Sparse-transport Reduce of the stacked params: pack each worker's
    touched rows into static-capacity delta buffers, merge only the union
    candidate rows, scatter into the evolved base table — bit-identical to
    :func:`_merge_tables_stacked` (same sorted-name order and per-table
    fold-out keys).  With ``cfg.table_sharding='sharded'`` the merge is
    routed per owning shard (still bit-identical).

    Returns ``(params, overflow)`` — ``overflow`` is the worst per-table
    touched-capacity excess this round (int32 scalar, 0 under the analytic
    bound); the train drivers raise on a positive value because
    ``pack_delta`` would have silently dropped that many rows' updates."""
    roles = model.param_roles()
    names = sorted(stacked.keys())
    keys = jax.random.split(merge_key, len(names))
    m = _virgin_repeats(tcfg, n_steps, k_epochs)
    out = {}
    overflow = jnp.zeros((), jnp.int32)
    for name, key in zip(names, keys):
        count, loss = _stats_for_role(stats, roles[name])
        n_rows = stacked[name].shape[1]
        cap = _delta_capacity(cfg, n_rows, n_steps, k_epochs, roles[name])
        overflow = jnp.maximum(overflow, merge_lib.delta_overflow(count, cap))
        pack = functools.partial(
            merge_lib.pack_delta, capacity=cap, n_rows=n_rows)
        idx, vals, cnt, lss = jax.vmap(pack)(stacked[name], count, loss)
        if cfg.table_sharding == "sharded":
            out[name] = merge_lib.merge_sparse_sharded_stacked(
                cfg.strategy, idx, vals, cnt, lss, stats.mean_loss,
                stacked[name][0], base[name],
                functools.partial(model.normalize_rows, name), m, key,
                n_shards=cfg.n_workers)
        else:
            out[name] = merge_lib.merge_sparse_stacked(
                cfg.strategy, idx, vals, cnt, lss, stats.mean_loss,
                stacked[name][0], base[name],
                functools.partial(model.normalize_rows, name), m, key)
    return out, overflow


@obs.scope("reduce")
def _merge_tables_sparse_collective(
    model: KGModel,
    cfg: MapReduceConfig,
    local: Params,
    stats,
    worker_loss: jax.Array,      # scalar, this worker's round loss
    merge_key: jax.Array,
    base: Params,                # the shared round-input params
    tcfg: KGConfig,
    n_steps: int,
    k_epochs: int,
) -> tuple[Params, jax.Array]:
    """Sparse-transport Reduce inside shard_map: all-gather each table's
    packed delta buffers — the transport's only cross-worker traffic,
    O(W·C·k) wire bytes instead of whole tables — then replay the stacked
    sparse merge on every worker, or, with
    ``cfg.table_sharding='sharded'``, merge only this shard's owned
    candidate block and all-gather the merged blocks
    (:func:`merge_lib.merge_sparse_sharded_collective`).  The replayed
    math is *identical* to the vmap backend's, so the two backends agree
    bitwise under sparse transport (the dense psum path agrees only to
    tolerance).  ``cfg.reduce_impl`` is ignored: there is nothing to
    psum.  Must run inside shard_map over ``cfg.axis_name``.

    Returns ``(params, overflow)`` with ``overflow`` pmax-ed over workers
    (replicated) — see :func:`_merge_tables_sparse_stacked`."""
    roles = model.param_roles()
    names = sorted(local.keys())
    keys = jax.random.split(merge_key, len(names))
    m = _virgin_repeats(tcfg, n_steps, k_epochs)
    wl = merge_lib.all_gather(worker_loss, cfg.axis_name)        # (W,)
    out = {}
    overflow = jnp.zeros((), jnp.int32)
    for name, key in zip(names, keys):
        count, loss = _stats_for_role(stats, roles[name])
        n_rows = local[name].shape[0]
        cap = _delta_capacity(cfg, n_rows, n_steps, k_epochs, roles[name])
        overflow = jnp.maximum(overflow, merge_lib.delta_overflow(count, cap))
        packed = merge_lib.pack_delta(local[name], count, loss, cap, n_rows)
        idx, vals, cnt, lss = all_gather_deltas(packed, cfg.axis_name)
        if cfg.table_sharding == "sharded":
            out[name] = merge_lib.merge_sparse_sharded_collective(
                cfg.strategy, idx, vals, cnt, lss, wl,
                local[name], base[name],
                functools.partial(model.normalize_rows, name), m,
                cfg.axis_name, key)
        else:
            out[name] = merge_lib.merge_sparse_stacked(
                cfg.strategy, idx, vals, cnt, lss, wl,
                local[name], base[name],
                functools.partial(model.normalize_rows, name), m, key)
    return out, merge_lib.pmax(overflow, cfg.axis_name)


@obs.scope("reduce")
def _merge_tables_stale_stacked(
    model: KGModel, strategy: str, stacked: Params, stats, merge_key: jax.Array,
    base: Params,
) -> Params:
    """Bounded-staleness Reduce of the stacked worker copies into the
    global view ``base`` — same sorted-name order and per-table fold-out
    keys as :func:`_merge_tables_stacked`, but participation-masked
    (:func:`merge_lib.merge_stacked_stale`): only this-round touchers
    contribute per row, rows nobody touched keep the global view."""
    roles = model.param_roles()
    names = sorted(stacked.keys())
    keys = jax.random.split(merge_key, len(names))
    out = {}
    for name, key in zip(names, keys):
        count, loss = _stats_for_role(stats, roles[name])
        out[name] = merge_lib.merge_stacked_stale(
            strategy, stacked[name], count, loss, stats.mean_loss,
            base[name], key)
    return out


@obs.scope("reduce")
def _merge_tables_stale_sparse(
    model: KGModel,
    cfg: MapReduceConfig,
    stacked: Params,
    stats,
    merge_key: jax.Array,
    base: Params,                # the global view being merged into
    n_steps: int,
    k_epochs: int,
) -> tuple[Params, jax.Array]:
    """Sparse-transport bounded-staleness Reduce (vmap backend): pack each
    worker's touched rows, stale-merge the candidate union into the global
    view — bit-identical to :func:`_merge_tables_stale_stacked`.  No virgin
    reconstruction: non-touchers are excluded per row, so the transport
    needs no shared round input (workers started from different views).
    Returns ``(params, overflow)`` like the synchronous sparse merge."""
    roles = model.param_roles()
    names = sorted(stacked.keys())
    keys = jax.random.split(merge_key, len(names))
    out = {}
    overflow = jnp.zeros((), jnp.int32)
    for name, key in zip(names, keys):
        count, loss = _stats_for_role(stats, roles[name])
        n_rows = stacked[name].shape[1]
        cap = _delta_capacity(cfg, n_rows, n_steps, k_epochs, roles[name])
        overflow = jnp.maximum(overflow, merge_lib.delta_overflow(count, cap))
        pack = functools.partial(
            merge_lib.pack_delta, capacity=cap, n_rows=n_rows)
        idx, vals, cnt, lss = jax.vmap(pack)(stacked[name], count, loss)
        if cfg.table_sharding == "sharded":
            out[name] = merge_lib.merge_sparse_stale_sharded_stacked(
                cfg.strategy, idx, vals, cnt, lss, stats.mean_loss,
                base[name], key, n_shards=cfg.n_workers)
        else:
            out[name] = merge_lib.merge_sparse_stale(
                cfg.strategy, idx, vals, cnt, lss, stats.mean_loss,
                base[name], key)
    return out, overflow


@obs.scope("reduce")
def _merge_tables_stale_collective(
    model: KGModel,
    cfg: MapReduceConfig,
    local: Params,
    stats,
    worker_loss: jax.Array,
    merge_key: jax.Array,
    base: Params,                # the replicated global view
    n_steps: int,
    k_epochs: int,
) -> tuple[Params, jax.Array]:
    """Bounded-staleness Reduce inside shard_map.  Sparse transport:
    all-gather the packed buffers and replay the stacked stale merge
    (shard-routed under ``table_sharding='sharded'``) — bitwise the vmap
    backend.  Dense transport: all-gather tables + stats and replay
    :func:`merge_lib.merge_stacked_stale` (the stale mode has no psum
    winner-select — participation masks need every toucher's row, so the
    all-gather replay IS the collective path, keeping both backends
    bitwise-equal).  Must run inside shard_map over ``cfg.axis_name``."""
    roles = model.param_roles()
    names = sorted(local.keys())
    keys = jax.random.split(merge_key, len(names))
    ax = cfg.axis_name
    wl = merge_lib.all_gather(worker_loss, ax)                    # (W,)
    out = {}
    overflow = jnp.zeros((), jnp.int32)
    for name, key in zip(names, keys):
        count, loss = _stats_for_role(stats, roles[name])
        if cfg.merge_transport == "sparse":
            n_rows = local[name].shape[0]
            cap = _delta_capacity(cfg, n_rows, n_steps, k_epochs, roles[name])
            overflow = jnp.maximum(
                overflow, merge_lib.delta_overflow(count, cap))
            packed = merge_lib.pack_delta(local[name], count, loss, cap,
                                          n_rows)
            idx, vals, cnt, lss = all_gather_deltas(packed, ax)
            out[name] = merge_lib.merge_sparse_stale_collective(
                cfg.strategy, idx, vals, cnt, lss, wl, base[name], ax, key,
                sharded=cfg.table_sharding == "sharded")
        else:
            stacked = merge_lib.all_gather(local[name], ax)
            counts = merge_lib.all_gather(count, ax)
            losses = merge_lib.all_gather(loss, ax)
            out[name] = merge_lib.merge_stacked_stale(
                cfg.strategy, stacked, counts, losses, wl, base[name], key)
    return out, merge_lib.pmax(overflow, ax)


def sgd_epoch_vmap(
    params: Params,
    pos: jax.Array,              # (W, S, B, 3)
    neg: jax.Array,              # (W, S, B, 3)
    cfg: MapReduceConfig,
    tcfg: KGConfig,
    merge_key: jax.Array,
    model: Optional[KGModel] = None,
    *,
    with_overflow: bool = False,
) -> tuple[Params, jax.Array]:
    """Map (vmapped local epochs from shared params) + Reduce (stacked).

    ``with_overflow=True`` (the train drivers' contract) appends the
    round's sparse-transport capacity-overflow scalar to the return —
    ``(params, loss, overflow)`` — so the host loop can raise before the
    silently-truncated merge is ever consumed."""
    model = _resolve(cfg, model)
    run = functools.partial(
        model.run_epoch, cfg=tcfg,
        sparse_apply=cfg.merge_transport == "sparse")
    with obs.scope("map"):
        stacked, stats = jax.vmap(run, in_axes=(None, 0, 0))(params, pos, neg)
    overflow = jnp.zeros((), jnp.int32)
    if cfg.merge_transport == "sparse":
        merged, overflow = _merge_tables_sparse_stacked(
            model, cfg, stacked, stats, merge_key, params, tcfg,
            pos.shape[1], 1)
    else:
        merged = _merge_tables_stacked(
            model, cfg.strategy, stacked, stats, merge_key)
    loss = jnp.mean(stats.mean_loss)
    if with_overflow:
        return merged, loss, overflow
    return merged, loss


@obs.scope("reduce")
def _merge_tables_collective(
    model: KGModel,
    cfg: MapReduceConfig,
    local: Params,
    stats,
    worker_loss: jax.Array,
    merge_key: jax.Array,
) -> Params:
    """The shard_map analogue of ``_merge_tables_stacked``: Reduce every
    table of this shard's params via collectives, routed by the model's
    roles — same sorted-name order and per-table fold-out keys, so the two
    paths make bit-identical choices given the same key.  Must run inside
    shard_map over ``cfg.axis_name``."""
    roles = model.param_roles()
    names = sorted(local.keys())
    keys = jax.random.split(merge_key, len(names))
    mfn = (
        merge_lib.merge_collective
        if cfg.reduce_impl == "psum"
        else merge_lib.merge_allgather
    )
    out = {}
    for name, key in zip(names, keys):
        count, loss = _stats_for_role(stats, roles[name])
        out[name] = mfn(cfg.strategy, local[name], count, loss,
                        worker_loss, cfg.axis_name, key)
    return out


def sgd_epoch_shard(
    params: Params,
    pos: jax.Array,              # (W, S, B, 3), sharded on axis 0
    neg: jax.Array,
    cfg: MapReduceConfig,
    tcfg: KGConfig,
    merge_key: jax.Array,
    mesh: Mesh,
    model: Optional[KGModel] = None,
    *,
    with_overflow: bool = False,
) -> tuple[Params, jax.Array]:
    """Map/Reduce over a real mesh axis via shard_map.  ``with_overflow``
    appends the sparse-transport overflow scalar (replicated, pmax-ed over
    workers) — see :func:`sgd_epoch_vmap`."""
    model = _resolve(cfg, model)
    ax = cfg.axis_name

    def worker(params, pos_w, neg_w):
        # pos_w: (1, S, B, 3) — this shard's subset
        with obs.scope("map"):
            local, stats = model.run_epoch(
                params, pos_w[0], neg_w[0], tcfg,
                sparse_apply=cfg.merge_transport == "sparse")
        overflow = jnp.zeros((), jnp.int32)
        if cfg.merge_transport == "sparse":
            out, overflow = _merge_tables_sparse_collective(
                model, cfg, local, stats, stats.mean_loss, merge_key,
                params, tcfg, pos_w.shape[1], 1)
        else:
            out = _merge_tables_collective(
                model, cfg, local, stats, stats.mean_loss, merge_key)
        loss = jax.lax.pmean(stats.mean_loss, ax)
        if with_overflow:
            return out, loss, overflow
        return out, loss

    fn = jax.shard_map(
        worker,
        mesh=mesh,
        in_specs=(P(), P(ax), P(ax)),
        out_specs=(P(), P(), P()) if with_overflow else (P(), P()),
        check_vma=False,
    )
    return fn(params, pos, neg)


# ---------------------------------------------------------------------------
# BGD paradigm
# ---------------------------------------------------------------------------

def _bgd_candidate_ids(pos_b: jax.Array, neg_b: jax.Array, role: str,
                       n_rows: int) -> jax.Array:
    """Static-size sorted union of the rows one BGD step can reference:
    positive + corrupted heads and tails (entity-role tables) or the batch
    relations (relation-role tables), padded with ``n_rows``.  Works on a
    stacked ``(W, B, 3)`` batch (vmap) or one shard's ``(B, 3)``."""
    if role == "ent":
        ids = jnp.concatenate(
            [pos_b[..., 0], pos_b[..., 2], neg_b[..., 0], neg_b[..., 2]],
            axis=-1)
    else:
        ids = jnp.concatenate([pos_b[..., 1], neg_b[..., 1]], axis=-1)
    flat = ids.reshape(-1)
    size = int(min(n_rows, flat.shape[0])) + 1
    return jnp.unique(flat, size=size, fill_value=n_rows)


def _bgd_sparse_update_stacked(
    model: KGModel, cfg: MapReduceConfig, tcfg: KGConfig, params: Params,
    grads: Params, pos_b: jax.Array, neg_b: jax.Array,
) -> Params:
    """Sparse BGD Reduce (vmap backend): autodiff gradients are *exactly*
    zero at rows a batch never references, so restricting the gradient
    mean + update to the batches' candidate rows is bit-identical to the
    dense update (``p - lr·0 == p``, sign of zero included — scatter-add
    grads are ``+0.0`` at unreferenced rows).  With
    ``cfg.table_sharding='sharded'`` the candidate set is additionally
    partitioned into owning row blocks and updated block-by-block — the
    mean + update never mix rows, so the decomposition is bit-identical
    (the vmap simulation of the collective routing below)."""
    roles = model.param_roles()
    out = {}
    for name in params:
        n_rows = params[name].shape[0]
        cand = _bgd_candidate_ids(pos_b, neg_b, roles[name], n_rows)
        if cfg.table_sharding == "sharded":
            R = merge_lib.shard_rows(n_rows, cfg.n_workers)
            table, grad = params[name], grads[name]

            def shard_update(lo, table=table, grad=grad, cand=cand,
                             n_rows=n_rows, R=R):
                own = merge_lib.own_candidates(cand, lo, R, n_rows)
                gc = jnp.mean(
                    jnp.take(grad, own, axis=1, mode="fill", fill_value=0.0),
                    axis=0)
                pc = jnp.take(table, own, axis=0, mode="fill", fill_value=0.0)
                return own, pc - tcfg.learning_rate * gc

            los = jnp.arange(cfg.n_workers, dtype=cand.dtype) * R
            owns, rows = jax.lax.map(shard_update, los)
            out[name] = params[name].at[owns.reshape(-1)].set(
                rows.reshape(-1, rows.shape[-1]), mode="drop")
        else:
            gc = jnp.mean(
                jnp.take(grads[name], cand, axis=1, mode="fill",
                         fill_value=0.0),
                axis=0)
            pc = jnp.take(params[name], cand, axis=0, mode="fill",
                          fill_value=0.0)
            out[name] = params[name].at[cand].set(
                pc - tcfg.learning_rate * gc, mode="drop")
    return out


def _bgd_sparse_update_collective(
    model: KGModel, cfg: MapReduceConfig, tcfg: KGConfig, params: Params,
    grads: Params, pos_b: jax.Array, neg_b: jax.Array,
) -> Params:
    """Sparse BGD Reduce (shard_map): each worker packs its gradient rows
    at its own batch's candidate ids, all-gathers the packed buffers
    (O(W·C·k) wire bytes instead of a whole-table pmean), and replays the
    stacked mean + update — bitwise equal to the vmap backend (the dense
    pmean path agrees only to tolerance).  With
    ``cfg.table_sharding='sharded'`` each worker updates only the candidate
    block it owns and the updated blocks are all-gathered — same wire
    class, per-worker update compute cut to its block.  Must run inside
    shard_map."""
    roles = model.param_roles()
    ax = cfg.axis_name
    out = {}
    for name in params:
        n_rows = params[name].shape[0]
        mine = _bgd_candidate_ids(pos_b, neg_b, roles[name], n_rows)
        gvals = jnp.take(grads[name], mine, axis=0, mode="fill",
                         fill_value=0.0)
        idx, vals = all_gather_deltas((mine, gvals), ax)
        cand = merge_lib.sparse_candidates(idx, n_rows)
        if cfg.table_sharding == "sharded":
            R = merge_lib.shard_rows(n_rows, idx.shape[0])
            lo = (jax.lax.axis_index(ax) * R).astype(cand.dtype)
            cand = merge_lib.own_candidates(cand, lo, R, n_rows)
        zero = jnp.zeros((cand.shape[0], vals.shape[-1]), vals.dtype)
        svals = jax.vmap(
            merge_lib.lookup_rows, in_axes=(0, 0, None, None, None)
        )(idx, vals, cand, zero, n_rows)
        gc = jnp.mean(svals, axis=0)
        pc = jnp.take(params[name], cand, axis=0, mode="fill", fill_value=0.0)
        new = pc - tcfg.learning_rate * gc
        if cfg.table_sharding == "sharded":
            cand = jax.lax.all_gather(cand, ax).reshape(-1)
            new = jax.lax.all_gather(new, ax).reshape(-1, new.shape[-1])
        out[name] = params[name].at[cand].set(new, mode="drop")
    return out


def bgd_epoch_vmap(
    params: Params,
    pos: jax.Array,              # (W, S, B, 3)
    neg: jax.Array,
    cfg: MapReduceConfig,
    tcfg: KGConfig,
    model: Optional[KGModel] = None,
) -> tuple[Params, jax.Array]:
    """Per step: Map = per-worker gradients, Reduce = mean, global update.
    Mathematically identical to single-thread minibatch SGD on the W·B-sized
    union batch (tested in tests/test_kg_api.py for every model)."""
    model = _resolve(cfg, model)
    if tcfg.normalize == "epoch":
        params = model.normalize(params)

    pos_s = jnp.swapaxes(pos, 0, 1)   # (S, W, B, 3)
    neg_s = jnp.swapaxes(neg, 0, 1)

    def step(carry, batch):
        params, loss_sum = carry
        pos_b, neg_b = batch          # (W, B, 3)
        losses, grads = jax.vmap(
            lambda p, n: model.batch_gradients(params, p, n, tcfg)
        )(pos_b, neg_b)
        if cfg.merge_transport == "sparse":
            params = _bgd_sparse_update_stacked(
                model, cfg, tcfg, params, grads, pos_b, neg_b)
        else:
            grads = jax.tree.map(lambda g: jnp.mean(g, axis=0), grads)
            params = apply_gradients(params, grads, tcfg.learning_rate)
        if tcfg.normalize == "step":
            params = model.normalize(params)
        return (params, loss_sum + jnp.mean(losses)), None

    (params, loss_sum), _ = jax.lax.scan(
        step, (params, jnp.zeros((), tcfg.dtype)), (pos_s, neg_s)
    )
    return params, loss_sum / pos_s.shape[0]


def _bgd_epoch_collective(
    model: KGModel,
    cfg: MapReduceConfig,
    tcfg: KGConfig,
    params: Params,
    pos: jax.Array,              # (S, B, 3) this shard's epoch batches
    neg: jax.Array,
) -> tuple[Params, jax.Array]:
    """One BGD epoch on this shard: per-step pmean-Reduced gradients and a
    global update.  The single definition of the shard-side BGD update rule
    — used by the per-epoch driver and the scanned block driver.  Must run
    inside shard_map over ``cfg.axis_name``."""
    ax = cfg.axis_name
    if tcfg.normalize == "epoch":
        params = model.normalize(params)

    def step(carry, batch):
        params, loss_sum = carry
        pos_b, neg_b = batch
        loss, grads = model.batch_gradients(params, pos_b, neg_b, tcfg)
        if cfg.merge_transport == "sparse":
            params = _bgd_sparse_update_collective(
                model, cfg, tcfg, params, grads, pos_b, neg_b)
            # mean of all-gathered losses: bitwise the vmap backend's loss
            # (pmean agrees only to tolerance)
            loss_red = jnp.mean(jax.lax.all_gather(loss, ax))
        else:
            grads = jax.lax.pmean(grads, ax)          # the BGD Reduce
            params = apply_gradients(params, grads, tcfg.learning_rate)
            loss_red = jax.lax.pmean(loss, ax)
        if tcfg.normalize == "step":
            params = model.normalize(params)
        return (params, loss_sum + loss_red), None

    (params, loss_sum), _ = jax.lax.scan(
        step, (params, jnp.zeros((), tcfg.dtype)), (pos, neg)
    )
    return params, loss_sum / pos.shape[0]


def bgd_epoch_shard(
    params: Params,
    pos: jax.Array,
    neg: jax.Array,
    cfg: MapReduceConfig,
    tcfg: KGConfig,
    mesh: Mesh,
    model: Optional[KGModel] = None,
) -> tuple[Params, jax.Array]:
    model = _resolve(cfg, model)
    ax = cfg.axis_name

    def worker(params, pos_w, neg_w):
        return _bgd_epoch_collective(
            model, cfg, tcfg, params, pos_w[0], neg_w[0])

    fn = jax.shard_map(
        worker, mesh=mesh,
        in_specs=(P(), P(ax), P(ax)), out_specs=(P(), P()),
        check_vma=False,
    )
    return fn(params, pos, neg)


# ---------------------------------------------------------------------------
# Scanned block driver (the 'device' pipeline)
# ---------------------------------------------------------------------------

# fold_in tag separating the device pipeline's (data, negative, merge) key
# streams from the init key derived from the same seed.
_DEVICE_STREAM_TAG = 0xD417A
# fold_in tag for the re-partition permutation stream — folded (not split)
# off the same root so the original three streams keep their pre-existing
# values and repartition_every=None runs are unchanged bit-for-bit.
_REPARTITION_TAG = 0x5917
# fold_in tag for the bounded-staleness refresh-phase stream — folded off
# the same root (same idiom as _REPARTITION_TAG) so staleness=0 runs keep
# every pre-existing stream bit-for-bit.
_STALENESS_TAG = 0x57A1E


def _device_keys(seed: int) -> tuple[jax.Array, ...]:
    """Per-purpose base keys for the device pipeline; every per-epoch key is
    ``fold_in(base, epoch)`` (and per-worker keys fold the worker index on
    top), so all randomness is a pure function of (seed, epoch, worker) —
    which is exactly what makes block size irrelevant to the results."""
    root = jax.random.fold_in(jax.random.PRNGKey(seed), _DEVICE_STREAM_TAG)
    k_data, k_neg, k_merge = jax.random.split(root, 3)
    k_part = jax.random.fold_in(root, _REPARTITION_TAG)
    k_stale = jax.random.fold_in(root, _STALENESS_TAG)
    return k_data, k_neg, k_merge, k_part, k_stale


def _zero_stats(tcfg: KGConfig, lead: tuple = ()) -> EpochStats:
    E, R = tcfg.n_entities, tcfg.n_relations
    return EpochStats(
        mean_loss=jnp.zeros(lead, tcfg.dtype),
        ent_count=jnp.zeros(lead + (E,), tcfg.dtype),
        ent_loss=jnp.zeros(lead + (E,), tcfg.dtype),
        rel_count=jnp.zeros(lead + (R,), tcfg.dtype),
        rel_loss=jnp.zeros(lead + (R,), tcfg.dtype),
    )


def compact_map(cfg: MapReduceConfig, tcfg: KGConfig,
                masked: bool = False) -> bool:
    """Whether the device pipeline's SGD Map steps only the batch's rows
    (``KGModel.sgd_step_sparse`` / ``run_epoch_flat``) instead of the dense
    ``sgd_step``: when a batch's ``4B`` entity candidate slots are fewer
    than 4/9 of the table's rows, and no per-step projection rewrites
    every row anyway.  A candidate slot (gathered, stepped, written back)
    costs about 2¼ rows of the dense step's passes over the table: on one
    TPU v5e at E=14,951, k=400, W=4 the compact step wins at 4B = 0.41 E
    and loses at 0.48 E.  The masked fine-tune (``masked``) always takes
    it — it rides the candidate gather.  The two steps agree to the last
    bit but where XLA sums a repeated row's contributions in another order
    (``KGModel.sgd_step_sparse``)."""
    return masked or (9 * cfg.batch_size < tcfg.n_entities
                      and tcfg.normalize != "step")


def make_block_fn(
    cfg: MapReduceConfig,
    tcfg: KGConfig,
    partitioned: jax.Array,      # (W, N_w, 3) on device (sharded for shard_map)
    *,
    mesh: Optional[Mesh] = None,
    model: Optional[KGModel] = None,
    head_prob: Optional[jax.Array] = None,
    seed: int = 0,
    donate: bool = False,
    with_overflow: bool = False,
    strata: Optional[jax.Array] = None,
    update_mask: Optional[Params] = None,
) -> Callable:
    """Returns jitted ``block_fn(params, epoch_ids) -> (params, losses)``
    — or ``(params, losses, overflow)`` with ``with_overflow=True``, where
    ``overflow`` is the block's worst sparse-transport capacity excess
    (int32 scalar, 0 outside the SGD sparse transport); the device driver
    opts in and raises on a positive value at block boundaries.

    ``epoch_ids`` is a ``(L,)`` int32 array of absolute epoch indices with
    ``L % schedule.merge_every == 0``; the whole block runs as one compiled
    scan with on-device batching, negative sampling, and (SGD) Reduce merges
    every ``merge_every`` epochs — zero per-epoch host work.  ``losses`` is
    the ``(L,)`` per-epoch mean loss, returned as a device array (callers
    decide when to sync).  Epoch results are bit-identical for any block
    split because every key is ``fold_in``-derived from (seed, epoch).

    ``schedule.repartition_every=M`` re-splits the triplets across
    workers: the effective partition of every epoch in the block is the
    global permutation of round ``epoch_ids[0] // M``
    (``data/kg.repartition_perm``), computed ONCE per block call — the
    permutation + whole-set gather (and, on shard_map, the cross-worker
    all_gather) costs one dispatch per round, not one per epoch.  Callers
    must therefore keep every ``epoch_ids`` block inside a single
    re-partition round (``train()`` slices blocks at round boundaries);
    round indexing stays a pure function of (seed, ``e // M``), so block
    invariance holds and the two backends stay in lockstep (the shard_map
    path all-gathers the shards and takes its own slice of the same
    permutation).

    ``donate=True`` donates the params buffer of every call
    (``jit(donate_argnums=0)``) — peak accelerator memory drops by one full
    copy of the embedding tables; callers must treat the passed params as
    consumed (``_train_device`` does).

    ``cfg.staleness=S > 0`` switches the SGD paradigm to the bounded-
    staleness block functions: the state threaded through ``block_fn`` (and
    between blocks) becomes the tuple ``(global_view, worker_locals)``
    instead of a bare params dict — worker locals persist across rounds
    (that's the whole point), so they must persist across *block* calls too
    or block slicing would change results.  Worker ``w`` re-reads the
    global view only at rounds ``r`` with ``(r + o_w) % (S + 1) == 0``
    (plus round 0), where ``o_w`` is a per-worker phase offset drawn from
    the dedicated ``_STALENESS_TAG`` stream; every worker's this-round
    deltas still merge into the global view each round via the
    participation-masked stale Reduce (``merge.merge_*_stale``).  All of it
    is ``fold_in``-pure in (seed, round, worker), so block invariance and
    the vmap/shard_map bitwise agreement carry over.

    ``strata`` (host-computed per-triplet stratum ids over the flattened
    partition, in partition order) makes the re-partition rounds stratified:
    each round re-shuffles *within* strata (``data/kg``'s
    ``repartition_perm_stratified``), preserving the degree-stratified
    partitioner's mix per worker.  ``None`` keeps the original unstratified
    permutation byte-for-byte.

    ``update_mask`` (one bool row-mask per param table; the online tier's
    masked fine-tune) freezes every row whose bit is False **bitwise**:
    the sparse SGD step skips frozen candidate rows, epoch-start/step
    constraint projections are clamped on frozen rows, and each merge
    round's output is clamped back to the round input on frozen rows —
    so frozen rows are inductively byte-identical to the initial params
    while free rows see exactly the gradients a from-scratch run
    restricted to the same mask would compute.  Requires the SGD
    paradigm's sparse transport with ``staleness == 0``.

    The SGD Map's step follows :func:`compact_map`, not the transport:
    the compact row step where a batch references fewer rows than the
    table holds, else the dense ``sgd_step``; the vmap backend runs the
    compact step on the W workers' tables laid end to end
    (``KGModel.run_epoch_flat``), reshaped once per merge round.

    The vmap and shard_map backends derive identical per-worker keys (vmapped
    ``fold_in(·, w)`` vs ``fold_in(·, axis_index)``), so the two backends see
    the same batches and negatives."""
    model = _resolve(cfg, model)
    W, B, K = cfg.n_workers, cfg.batch_size, cfg.schedule.merge_every
    M = cfg.schedule.repartition_every
    S = cfg.staleness
    n_w = partitioned.shape[1]
    ax = cfg.axis_name
    k_data, k_neg, k_merge, k_part, k_stale = _device_keys(seed)
    strata = None if strata is None else jnp.asarray(strata)
    if update_mask is not None:
        if (cfg.paradigm != "sgd" or cfg.merge_transport != "sparse"
                or S > 0):
            raise ValueError(
                "update_mask (the masked fine-tune) requires the SGD "
                "paradigm with merge_transport='sparse' and staleness=0 — "
                f"got paradigm={cfg.paradigm!r}, "
                f"merge_transport={cfg.merge_transport!r}, staleness={S}")
        update_mask = {name: jnp.asarray(m, dtype=bool)
                       for name, m in update_mask.items()}
    compact = compact_map(cfg, tcfg, update_mask is not None)
    run_epoch = functools.partial(
        model.run_epoch, cfg=tcfg, sparse_apply=compact,
        update_mask=update_mask)

    @obs.scope("map")
    def run(params: Params, pos: jax.Array, neg: jax.Array):
        """One worker's local epoch of SGD steps from ``params``: the Map
        (shard_map backend, and the vmap backend's dense step;
        ``update_mask`` is None whenever staleness > 0)."""
        return run_epoch(params, pos, neg)

    @obs.scope("reduce")
    def clamp_frozen(merged: Params, base: Params) -> Params:
        """Clamp frozen rows of a merge round's output back to the round
        input (merge arithmetic — non-pow2 averaging, virgin-row
        reconstruction — is not guaranteed bitwise-identity on rows no
        worker moved); ``base`` frozen rows are inductively original."""
        if update_mask is None:
            return merged
        return {
            name: jnp.where(update_mask[name][:, None], merged[name],
                            base[name])
            for name in merged
        }

    @obs.scope("negatives")
    def block_part(epoch_ids: jax.Array) -> jax.Array:
        """The (W, N_w, 3) partition in effect for this whole block (vmap
        backend): the static split, or re-partition round
        ``epoch_ids[0] // M`` — constant across the block because the
        driver slices blocks at round boundaries."""
        if M is None:
            return partitioned
        r = epoch_ids[0] // M
        return kg_lib.device_repartition(
            jax.random.fold_in(k_part, r), partitioned, r, strata)

    @obs.scope("negatives")
    def worker_block_part(epoch_ids: jax.Array, w: jax.Array,
                          part_w: jax.Array) -> jax.Array:
        """Worker ``w``'s (N_w, 3) slice of ``block_part`` inside
        shard_map: all-gather the shards once per block, then take this
        worker's rows of the same global permutation — identical triplets
        to the vmap backend's worker ``w``."""
        if M is None:
            return part_w
        r = epoch_ids[0] // M
        flat = jax.lax.all_gather(part_w, ax, axis=0, tiled=True)
        if strata is None:
            perm = kg_lib.repartition_perm(
                jax.random.fold_in(k_part, r), W * n_w, r)
        else:
            perm = kg_lib.repartition_perm_stratified(
                jax.random.fold_in(k_part, r), strata, W, r)
        rows = jax.lax.dynamic_slice_in_dim(perm, w * n_w, n_w)
        return jnp.take(flat, rows, axis=0)

    @obs.scope("negatives")
    def worker_epoch_data(e: jax.Array, w: jax.Array, part_w: jax.Array):
        """(pos, neg) for worker ``w`` at epoch ``e`` (the shard_map per-
        worker path).  Key contract shared with ``epoch_data`` below — both
        fold (epoch, then worker) — so the backends match bit-for-bit."""
        kb = jax.random.fold_in(jax.random.fold_in(k_data, e), w)
        pos = kg_lib.device_worker_batches(kb, part_w, B)
        kn = jax.random.fold_in(jax.random.fold_in(k_neg, e), w)
        neg = model.make_negatives(kn, pos, tcfg, head_prob)
        return pos, neg

    @obs.scope("negatives")
    def epoch_data(e: jax.Array, part: jax.Array):
        """Stacked (W, S, B, 3) pos/neg for the vmap backend, batched via
        the data layer's ``device_epoch_batches`` (which folds the worker
        index exactly like ``worker_epoch_data``)."""
        pos = kg_lib.device_epoch_batches(
            jax.random.fold_in(k_data, e), part, B)
        kn = jax.random.fold_in(k_neg, e)
        neg = jax.vmap(
            lambda pos_w, w: model.make_negatives(
                jax.random.fold_in(kn, w), pos_w, tcfg, head_prob)
        )(pos, jnp.arange(W))
        return pos, neg

    # -- vmap backend -------------------------------------------------------

    @obs.scope("reduce")
    def _broadcast(params: Params) -> Params:
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x, (W,) + x.shape), params)

    @obs.scope("reduce")
    def _shared(stacked: Params) -> Params:
        """Worker 0's copy of every table: after a Reduce all W are equal."""
        return jax.tree.map(lambda x: x[0], stacked)

    # The W workers' local tables during a merge round's epochs: the
    # (W, N, k) stack for the dense step, or, for the compact step, the
    # same rows laid end to end as (W * N, k) — reshaped once per round,
    # outside the step loop (see ``KGModel.run_epoch_flat``).
    if compact:
        @obs.scope("map")
        def map_epoch(local: Params, pos: jax.Array, neg: jax.Array):
            return model.run_epoch_flat(local, pos, neg, tcfg,
                                        update_mask=update_mask)

        @obs.scope("map")
        def to_local(stacked: Params) -> Params:
            return jax.tree.map(
                lambda x: x.reshape((-1,) + x.shape[2:]), stacked)

        @obs.scope("map")
        def to_stacked(local: Params) -> Params:
            return jax.tree.map(
                lambda x: x.reshape((W, -1) + x.shape[1:]), local)
    else:
        map_epoch = jax.vmap(run)

        def to_local(stacked: Params) -> Params:
            return stacked

        to_stacked = to_local

    def sgd_block_vmap(params: Params, epoch_ids: jax.Array):
        part = block_part(epoch_ids)

        def round_body(carry, eids):             # eids: (K,) one merge round
            stacked, ovf = carry
            base = _shared(stacked)              # shared round input

            def local_epoch(carry, e):
                local, acc = carry
                pos, neg = epoch_data(e, part)
                local, stats = map_epoch(local, pos, neg)
                acc = jax.tree.map(jnp.add, acc, stats)
                return (local, acc), jnp.mean(stats.mean_loss)

            (local, acc), losses = jax.lax.scan(
                local_epoch, (to_local(stacked), _zero_stats(tcfg, (W,))),
                eids)
            stacked = to_stacked(local)
            acc = dataclasses.replace(acc, mean_loss=acc.mean_loss / K)
            mk = jax.random.fold_in(k_merge, eids[-1])
            if cfg.merge_transport == "sparse":
                merged, o = _merge_tables_sparse_stacked(
                    model, cfg, stacked, acc, mk, base, tcfg,
                    n_w // B, K)
                ovf = jnp.maximum(ovf, o)
            else:
                merged = _merge_tables_stacked(
                    model, cfg.strategy, stacked, acc, mk)
            merged = clamp_frozen(merged, base)
            return (_broadcast(merged), ovf), losses

        (stacked, ovf), losses = jax.lax.scan(
            round_body, (_broadcast(params), jnp.zeros((), jnp.int32)),
            epoch_ids.reshape(-1, K))
        out = _shared(stacked)
        if with_overflow:
            return out, losses.reshape(-1), ovf
        return out, losses.reshape(-1)

    def _stale_offsets() -> jax.Array:
        """Per-worker refresh-phase offsets o_w ~ U{0..S}: workers refresh
        at different rounds instead of in lockstep, which is what makes the
        schedule 'asynchronous' while staying a pure function of (seed, w).
        """
        return jax.vmap(
            lambda w: jax.random.randint(
                jax.random.fold_in(k_stale, w), (), 0, S + 1)
        )(jnp.arange(W))

    def sgd_block_stale_vmap(state, epoch_ids: jax.Array):
        """Bounded-staleness SGD block (vmap backend).  ``state`` is
        ``(global_view, worker_locals)`` — see the staleness paragraph in
        the factory docstring.  The round index is absolute
        (``eids[0] // K``), so refresh decisions are block-split invariant.
        """
        part = block_part(epoch_ids)
        offs = _stale_offsets()

        def round_body(carry, eids):             # eids: (K,) one merge round
            (g, local), ovf = carry
            r = eids[0] // K
            gate = (r == 0) | ((r + offs) % (S + 1) == 0)     # (W,) refresh?

            def adopt(gx, lx):
                return jnp.where(
                    gate.reshape((W,) + (1,) * gx.ndim),
                    jnp.broadcast_to(gx, (W,) + gx.shape), lx)

            stacked = jax.tree.map(adopt, g, local)

            def local_epoch(carry, e):
                local, acc = carry
                pos, neg = epoch_data(e, part)
                local, stats = map_epoch(local, pos, neg)
                acc = jax.tree.map(jnp.add, acc, stats)
                return (local, acc), jnp.mean(stats.mean_loss)

            (local, acc), losses = jax.lax.scan(
                local_epoch, (to_local(stacked), _zero_stats(tcfg, (W,))),
                eids)
            stacked = to_stacked(local)
            acc = dataclasses.replace(acc, mean_loss=acc.mean_loss / K)
            mk = jax.random.fold_in(k_merge, eids[-1])
            if cfg.merge_transport == "sparse":
                g, o = _merge_tables_stale_sparse(
                    model, cfg, stacked, acc, mk, g, n_w // B, K)
                ovf = jnp.maximum(ovf, o)
            else:
                g = _merge_tables_stale_stacked(
                    model, cfg.strategy, stacked, acc, mk, g)
            return ((g, stacked), ovf), losses

        ((g, local), ovf), losses = jax.lax.scan(
            round_body, (state, jnp.zeros((), jnp.int32)),
            epoch_ids.reshape(-1, K))
        if with_overflow:
            return (g, local), losses.reshape(-1), ovf
        return (g, local), losses.reshape(-1)

    def bgd_block_vmap(params: Params, epoch_ids: jax.Array):
        part = block_part(epoch_ids)

        def epoch_body(params, e):
            pos, neg = epoch_data(e, part)
            return bgd_epoch_vmap(params, pos, neg, cfg, tcfg, model)

        return jax.lax.scan(epoch_body, params, epoch_ids)

    # -- shard_map backend (whole block inside one shard_map) ---------------

    def sgd_block_shard(params: Params, epoch_ids: jax.Array):
        def worker(params, part_w, epoch_ids):
            w = jax.lax.axis_index(ax)
            part_w = worker_block_part(epoch_ids, w, part_w[0])

            def round_body(carry, eids):
                # the params carry is the shared merged round input
                base, ovf = carry

                def local_epoch(carry, e):
                    local, acc = carry
                    pos, neg = worker_epoch_data(e, w, part_w)
                    local, stats = run(local, pos, neg)
                    acc = jax.tree.map(jnp.add, acc, stats)
                    return (local, acc), jax.lax.pmean(stats.mean_loss, ax)

                (local, acc), losses = jax.lax.scan(
                    local_epoch, (base, _zero_stats(tcfg)), eids)
                mk = jax.random.fold_in(k_merge, eids[-1])
                if cfg.merge_transport == "sparse":
                    out, o = _merge_tables_sparse_collective(
                        model, cfg, local, acc, acc.mean_loss / K, mk,
                        base, tcfg, n_w // B, K)
                    ovf = jnp.maximum(ovf, o)
                else:
                    out = _merge_tables_collective(
                        model, cfg, local, acc, acc.mean_loss / K, mk)
                out = clamp_frozen(out, base)
                return (out, ovf), losses

            (params, ovf), losses = jax.lax.scan(
                round_body, (params, jnp.zeros((), jnp.int32)),
                epoch_ids.reshape(-1, K))
            if with_overflow:
                return params, losses.reshape(-1), ovf
            return params, losses.reshape(-1)

        fn = jax.shard_map(
            worker, mesh=mesh,
            in_specs=(P(), P(ax), P()),
            out_specs=(P(), P(), P()) if with_overflow else (P(), P()),
            check_vma=False,
        )
        return fn(params, partitioned, epoch_ids)

    def sgd_block_stale_shard(state, epoch_ids: jax.Array):
        """Bounded-staleness SGD block (shard_map backend).  The global
        view stays replicated (P()); each worker's local tables live in the
        ``(W, ...)``-stacked ``state[1]``, row-sharded over the mesh axis
        (P(ax)) so every device holds exactly its own copy.  The per-worker
        refresh gate folds ``axis_index`` into the same ``_STALENESS_TAG``
        stream the vmap backend vmaps over, and the stale Reduce replays
        identical stacked math after an all-gather — both backends agree
        bitwise (pinned by tests)."""

        def worker(state, part_w, epoch_ids):
            g, local = state
            w = jax.lax.axis_index(ax)
            part_w = worker_block_part(epoch_ids, w, part_w[0])
            local = jax.tree.map(lambda x: x[0], local)
            off = jax.random.randint(
                jax.random.fold_in(k_stale, w), (), 0, S + 1)

            def round_body(carry, eids):
                g, local, ovf = carry
                r = eids[0] // K
                gate = (r == 0) | ((r + off) % (S + 1) == 0)
                local = jax.tree.map(
                    lambda gx, lx: jnp.where(gate, gx, lx), g, local)

                def local_epoch(carry, e):
                    local, acc = carry
                    pos, neg = worker_epoch_data(e, w, part_w)
                    local, stats = run(local, pos, neg)
                    acc = jax.tree.map(jnp.add, acc, stats)
                    return (local, acc), jax.lax.pmean(stats.mean_loss, ax)

                (local, acc), losses = jax.lax.scan(
                    local_epoch, (local, _zero_stats(tcfg)), eids)
                mk = jax.random.fold_in(k_merge, eids[-1])
                g, o = _merge_tables_stale_collective(
                    model, cfg, local, acc, acc.mean_loss / K, mk, g,
                    n_w // B, K)
                ovf = jnp.maximum(ovf, o)
                return (g, local, ovf), losses

            (g, local, ovf), losses = jax.lax.scan(
                round_body, (g, local, jnp.zeros((), jnp.int32)),
                epoch_ids.reshape(-1, K))
            local = jax.tree.map(lambda x: x[None], local)
            if with_overflow:
                return (g, local), losses.reshape(-1), ovf
            return (g, local), losses.reshape(-1)

        state_specs = (P(), P(ax))
        fn = jax.shard_map(
            worker, mesh=mesh,
            in_specs=(state_specs, P(ax), P()),
            out_specs=(
                (state_specs, P(), P()) if with_overflow
                else (state_specs, P())),
            check_vma=False,
        )
        return fn(state, partitioned, epoch_ids)

    def bgd_block_shard(params: Params, epoch_ids: jax.Array):
        def worker(params, part_w, epoch_ids):
            w = jax.lax.axis_index(ax)
            part_w = worker_block_part(epoch_ids, w, part_w[0])

            def epoch_body(params, e):
                pos, neg = worker_epoch_data(e, w, part_w)
                return _bgd_epoch_collective(
                    model, cfg, tcfg, params, pos, neg)

            return jax.lax.scan(epoch_body, params, epoch_ids)

        fn = jax.shard_map(
            worker, mesh=mesh,
            in_specs=(P(), P(ax), P()), out_specs=(P(), P()),
            check_vma=False,
        )
        return fn(params, partitioned, epoch_ids)

    if cfg.backend == "shard_map":
        if mesh is None:
            raise ValueError("shard_map backend needs a mesh")
        if cfg.paradigm == "sgd":
            fn = sgd_block_stale_shard if S > 0 else sgd_block_shard
        else:
            fn = bgd_block_shard
    else:
        if cfg.paradigm == "sgd":
            fn = sgd_block_stale_vmap if S > 0 else sgd_block_vmap
        else:
            fn = bgd_block_vmap

    if with_overflow and cfg.paradigm == "bgd":
        # BGD sizes its sparse buffers exactly from the batch shape, so
        # overflow is impossible — append the constant to keep the driver
        # contract uniform
        inner_bgd = fn

        def fn(params, epoch_ids):
            out, losses = inner_bgd(params, epoch_ids)
            return out, losses, jnp.zeros((), jnp.int32)

    out_shardings = None
    if cfg.table_sharding == "sharded" and cfg.backend == "shard_map":
        # rest the tables row-sharded over the mesh axis between blocks:
        # _train_device places the input params P(axis) and these output
        # shardings keep the donated in/out layouts matched, so
        # per-device table residency stays ~1/W across the run (inside a
        # block the Map still gathers full tables — see ROADMAP's
        # sharded-tables item for the fully shard-resident follow-on).
        # jit's out_shardings (unlike with_sharding_constraint) take both
        # Auto and Explicit mesh axes — jax.make_mesh defaults to Explicit
        tables = kg_table_shardings(
            model.param_roles(),
            jax.eval_shape(lambda k: model.init_params(k, tcfg),
                           jax.random.PRNGKey(0)),
            mesh, "sharded", axis_name=ax)
        # staleness>0 threads (global_view, worker_locals): only the
        # global view rests row-sharded (locals are already P(ax)-stacked)
        state = (tables, None) if S > 0 else tables
        out_shardings = (state, None) + ((None,) if with_overflow else ())

    return jax.jit(fn, donate_argnums=(0,) if donate else (),
                   out_shardings=out_shardings)


# ---------------------------------------------------------------------------
# Epoch dispatcher + host-side training driver
# ---------------------------------------------------------------------------

def make_epoch_fn(
    cfg: MapReduceConfig,
    tcfg: KGConfig,
    mesh: Optional[Mesh] = None,
    model: Optional[KGModel] = None,
    *,
    with_overflow: bool = False,
) -> Callable:
    """Returns jitted ``epoch_fn(params, pos, neg, merge_key) -> (params,
    loss)`` — or ``(params, loss, overflow)`` with ``with_overflow=True``
    (the train driver's contract; BGD appends a constant 0 since its
    sparse buffers cannot overflow)."""
    model = _resolve(cfg, model)
    if cfg.backend == "shard_map":
        if mesh is None:
            raise ValueError("shard_map backend needs a mesh")
        if cfg.paradigm == "sgd":
            fn = lambda p, pos, neg, k: sgd_epoch_shard(
                p, pos, neg, cfg, tcfg, k, mesh, model,
                with_overflow=with_overflow)
        else:
            fn = lambda p, pos, neg, k: bgd_epoch_shard(
                p, pos, neg, cfg, tcfg, mesh, model)
    else:
        if cfg.paradigm == "sgd":
            fn = lambda p, pos, neg, k: sgd_epoch_vmap(
                p, pos, neg, cfg, tcfg, k, model,
                with_overflow=with_overflow)
        else:
            fn = lambda p, pos, neg, k: bgd_epoch_vmap(
                p, pos, neg, cfg, tcfg, model)
    if with_overflow and cfg.paradigm == "bgd":
        inner = fn
        fn = lambda p, pos, neg, k: inner(p, pos, neg, k) + (
            jnp.zeros((), jnp.int32),)
    return jax.jit(fn)


def _raise_on_overflow(overflow, last_epoch: int) -> None:
    """Host-side seatbelt at Reduce boundaries: a positive sparse-transport
    overflow means :func:`merge_lib.pack_delta` silently dropped that many
    touched rows' updates this round — the merged tables are corrupt, so
    stop instead of training on."""
    n = int(overflow)
    if n > 0:
        raise RuntimeError(
            f"sparse-transport delta overflow at epoch {last_epoch}: a "
            f"Reduce round touched {n} more rows than the packed buffer "
            "capacity, so pack_delta dropped their updates and the merge "
            "is corrupt.  The analytic touched_capacity bound makes this "
            "impossible — an undersized MapReduceConfig.touched_capacity "
            "override (or a bound regression) is the cause; raise the "
            "override or pass None.")


@dataclasses.dataclass
class TrainResult:
    params: Params
    loss_history: list
    epochs_run: int
    model: str = "transe"
    # in-training evaluation (eval_loop / kg.fit(eval_every=...)): the
    # quality-vs-epoch trace, and — when keep_best — the params snapshot of
    # the best-metric boundary (paper-style model selection)
    trace: "Optional[trace_lib.TrainingTrace]" = None
    best_params: Optional[Params] = None
    best_epoch: Optional[int] = None
    # the persistent/serveable artifact view of this result — a
    # repro.kb.KnowledgeBase assembled by kg.fit (None when train() is
    # driven directly below the facade)
    kb: Optional[object] = None


def _make_recorder(
    kg, tcfg, cfg, model, eval_loop
) -> "Optional[trace_lib.TraceRecorder]":
    if eval_loop is None:
        return None
    if cfg.pipeline == "device" and (
        eval_loop.eval_every % cfg.schedule.merge_every != 0
    ):
        raise ValueError(
            f"eval_every={eval_loop.eval_every} is not a multiple of "
            f"merge_every={cfg.schedule.merge_every} — in-loop evals run at "
            "Reduce boundaries (between Reduces the workers hold W "
            "divergent local copies, not a shared model); pick a multiple")
    return trace_lib.TraceRecorder(
        eval_loop, trace_lib.make_eval_fn(kg, model, tcfg.norm, eval_loop))


def _finish_result(
    params, history, epochs_run, model, recorder
) -> TrainResult:
    if recorder is None:
        return TrainResult(
            params=params, loss_history=history, epochs_run=epochs_run,
            model=model.name)
    return TrainResult(
        params=params, loss_history=history, epochs_run=epochs_run,
        model=model.name, trace=recorder.finalize(),
        best_params=recorder.best_params, best_epoch=recorder.best_epoch)


def train(
    kg: kg_lib.KG,
    tcfg: KGConfig,
    cfg: MapReduceConfig,
    *,
    epochs: int = 50,
    seed: int = 0,
    mesh: Optional[Mesh] = None,
    params: Optional[Params] = None,
    callback: Optional[Callable[[int, float], None]] = None,
    model: Optional[KGModel] = None,
    eval_loop: "Optional[trace_lib.EvalLoopConfig]" = None,
    checkpoint: Optional[CheckpointConfig] = None,
    start_epoch: int = 0,
    resume_fresh_init: bool = True,
    prior_history: Optional[list] = None,
    update_mask: Optional[Params] = None,
) -> TrainResult:
    """Training driver: balanced partitioning, deterministic batches,
    negative sampling, Map/Reduce epochs, loss history.  With
    ``cfg.pipeline == 'device'`` the epochs run in compiled scan blocks
    (``make_block_fn``); with ``'host'`` one epoch is dispatched at a time
    (the original, bit-for-bit-preserved loop).

    Balance rule: the partitioner gives every worker exactly
    ``N // n_workers`` triplets (dropping the ``N % n_workers`` tail so all
    workers take identical step counts — the paper's balance requirement),
    and each epoch runs ``N_w // batch_size`` steps per worker.  A
    ``batch_size`` that does not divide ``N_w`` leaves the trailing
    ``N_w % batch_size`` triplets of each worker's per-epoch permutation out
    of that epoch (the reshuffle rotates which ones); the dropped count is
    surfaced once per run as a warning, or as a ``ValueError`` when
    ``cfg.strict_batching`` is set.

    Callbacks: with the host pipeline ``callback(epoch, loss)`` fires every
    epoch; with the device pipeline it fires at block boundaries only (with
    the block's last epoch index and loss) — per-epoch host sync is exactly
    what the scanned driver exists to remove.

    In-training evaluation: ``eval_loop`` (a ``trace.EvalLoopConfig``, see
    ``kg.fit(eval_every=...)``) runs the evaluation protocol every
    ``eval_every`` epochs — a Reduce boundary by construction (the host
    pipeline Reduces every epoch; the device driver slices its compiled
    blocks at eval boundaries, which the block-size invariance makes free
    in results and cheap in dispatches) — records a ``TrainingTrace`` on
    the result, snapshots best-metric params, and early-stops on
    ``patience``.

    Checkpoint/resume: ``checkpoint`` (a :class:`CheckpointConfig`)
    snapshots params + manifest at Reduce boundaries; ``start_epoch=N``
    (with the checkpointed ``params``) resumes a run **bit-identically** —
    the device pipeline's batching/negatives/merges are pure functions of
    (seed, epoch) so absolute epoch ids are all it needs, and the host
    pipeline fast-forwards its split-chain (``resume_fresh_init`` replays
    the original run's init split when that run fresh-initialized).
    ``prior_history`` (the manifest's loss history) is prepended so a
    resumed ``TrainResult`` matches the unbroken run's.

    Masked fine-tune: ``update_mask`` (one bool row-mask per param table,
    shaped to the table's role) freezes unmasked rows bitwise while free
    rows train exactly as a from-scratch run restricted to the same mask
    would — the online tier's incremental ``update()``.  Requires the SGD
    paradigm's device pipeline with ``merge_transport='sparse'``,
    ``staleness=0``, caller-provided ``params``, and no checkpointing
    (delta checkpoints live in ``repro.online``, not here).

    ``cfg.n_workers == 1`` with any backend reproduces single-thread
    Algorithm 1 (the paper's baseline) for the chosen model."""
    model = _resolve(cfg, model)
    if update_mask is not None:
        if cfg.paradigm != "sgd" or cfg.merge_transport != "sparse":
            raise ValueError(
                "update_mask requires paradigm='sgd' with "
                "merge_transport='sparse' — the masked fine-tune rides the "
                "sparse transport's touched-row machinery")
        if cfg.pipeline != "device":
            raise ValueError(
                "update_mask requires pipeline='device' — the host "
                "pipeline's per-epoch dispatch has no masked step")
        if cfg.staleness > 0:
            raise ValueError(
                f"update_mask with staleness={cfg.staleness}: stale worker "
                "locals would carry frozen-row drift across rounds; masked "
                "fine-tunes are synchronous")
        if checkpoint is not None:
            raise ValueError(
                "update_mask with checkpoint: masked fine-tunes persist "
                "through the online tier's delta checkpoints "
                "(repro.online), not base kg_train snapshots")
        if params is None:
            raise ValueError(
                "update_mask without params: a masked fine-tune refines an "
                "existing artifact's tables — pass them")
        roles = model.param_roles()
        if set(update_mask) != set(roles):
            raise ValueError(
                f"update_mask tables {sorted(update_mask)} do not match "
                f"model {model.name!r} tables {sorted(roles)}")
        for name, m in update_mask.items():
            rows = (tcfg.n_entities if roles[name] == "ent"
                    else tcfg.n_relations)
            if tuple(np.shape(m)) != (rows,):
                raise ValueError(
                    f"update_mask[{name!r}] has shape {np.shape(m)}, "
                    f"expected ({rows},) — one bool per row of the "
                    f"{roles[name]!r}-role table")
    if start_epoch < 0 or (start_epoch and start_epoch >= epochs):
        raise ValueError(
            f"start_epoch={start_epoch} must be in [0, epochs={epochs}) — "
            "resuming a checkpoint at or past the requested epoch count "
            "has nothing left to train; raise epochs")
    if cfg.pipeline == "device" and start_epoch % cfg.schedule.merge_every:
        raise ValueError(
            f"start_epoch={start_epoch} is not a multiple of "
            f"merge_every={cfg.schedule.merge_every} — device-pipeline "
            "checkpoints live at Reduce boundaries")
    if (checkpoint is not None and checkpoint.every is not None
            and cfg.pipeline == "device"
            and checkpoint.every % cfg.schedule.merge_every):
        raise ValueError(
            f"checkpoint every={checkpoint.every} is not a multiple of "
            f"merge_every={cfg.schedule.merge_every} — checkpoints are "
            "shared-model states, which only exist at Reduce boundaries")
    if cfg.staleness > 0 and (checkpoint is not None or start_epoch > 0):
        raise ValueError(
            f"staleness={cfg.staleness} cannot checkpoint or resume — the "
            "run state includes every worker's stale local tables, which "
            "the Reduce-boundary manifest does not capture; bounded-"
            "staleness runs reproduce by full rerun instead (all their "
            "randomness is a fold_in-pure function of (seed, round, "
            "worker))")
    part_fn = kg_lib.PARTITIONERS[cfg.partition]
    partitioned = part_fn(seed, kg.train, cfg.n_workers)
    # strata for the degree partitioner's re-partition rounds: labels over
    # the flattened (partition-order) triplets — each round permutes the
    # ORIGINAL partition (device_repartition), so the flat labels stay
    # valid every round
    strata = None
    if (cfg.partition == "degree" and cfg.pipeline == "device"
            and cfg.schedule.repartition_every is not None):
        strata = kg_lib.triplet_strata(
            partitioned.reshape(-1, 3), tcfg.n_entities)
    n_w = partitioned.shape[1]
    if n_w < cfg.batch_size:
        raise ValueError(
            f"batch_size={cfg.batch_size} exceeds the "
            f"{partitioned.shape[1]} triplets each of the {cfg.n_workers} "
            "workers holds — zero steps per epoch; shrink batch_size or "
            "n_workers")
    remainder = n_w % cfg.batch_size
    if remainder:
        msg = (
            f"batch_size={cfg.batch_size} does not divide the per-worker "
            f"split of {n_w} triplets — each epoch leaves out the trailing "
            f"{remainder} triplets of every worker's permutation "
            f"({remainder * cfg.n_workers} of {n_w * cfg.n_workers} total); "
            "the per-epoch reshuffle rotates which triplets sit out, so all "
            "of them still train over time.  Pick a batch_size dividing "
            f"{n_w} to use every triplet every epoch.")
        if cfg.strict_batching:
            raise ValueError(msg)
        # warn_fresh, not warnings.warn: the process-wide warning registry
        # would swallow the report for every later fit() in this process,
        # even though each run drops its own counts
        warn_fresh(msg, stacklevel=2)

    _check_touched_capacity(cfg, tcfg, model, n_w // cfg.batch_size)

    head_prob = None
    if tcfg.sampling == "bern":
        head_prob = jnp.asarray(
            negative.bernoulli_stats(kg.train, kg.n_relations)
        )

    key = jax.random.PRNGKey(seed)
    caller_params = params is not None
    if params is None:
        key, k_init = jax.random.split(key)
        params = model.init_params(k_init, tcfg)
    elif set(params) != set(model.param_roles()):
        raise ValueError(
            f"resume params have tables {sorted(params)} but model "
            f"{model.name!r} expects {sorted(model.param_roles())} — "
            "params from a different model?")
    elif start_epoch > 0 and resume_fresh_init:
        # replay the resumed run's init split so the host pipeline's
        # per-epoch key chain continues exactly where it left off
        key, _ = jax.random.split(key)

    recorder = _make_recorder(kg, tcfg, cfg, model, eval_loop)
    writer = None
    if checkpoint is not None:
        # fresh_init records whether the ORIGINAL epoch-0 run initialized
        # its own params — what a future resume must replay
        fresh_init = (
            not caller_params if start_epoch == 0 else resume_fresh_init)
        writer = _CheckpointWriter(checkpoint, {
            "kind": "kg_train",
            "model": model.name,
            "seed": seed,
            "paradigm": cfg.paradigm,
            "pipeline": cfg.pipeline,
            "dim": tcfg.dim,
            "n_entities": tcfg.n_entities,
            "n_relations": tcfg.n_relations,
            "fresh_init": fresh_init,
            "graph": kg.fingerprint(),
            "config": resume_config(tcfg, cfg),
        })

    if cfg.pipeline == "device":
        return _train_device(
            tcfg, cfg, model, partitioned, head_prob, params,
            epochs=epochs, seed=seed, mesh=mesh, callback=callback,
            recorder=recorder, eval_loop=eval_loop,
            caller_params=caller_params, writer=writer,
            start_epoch=start_epoch, prior_history=prior_history,
            strata=strata, update_mask=update_mask)

    # surface sparse-transport capacity overflow at every Reduce (the
    # loop already syncs float(loss) per epoch, so this costs nothing)
    with_overflow = cfg.paradigm == "sgd" and cfg.merge_transport == "sparse"
    epoch_fn = make_epoch_fn(
        cfg, tcfg, mesh, model, with_overflow=with_overflow)

    if cfg.backend == "shard_map":
        assert mesh is not None
        rep = NamedSharding(mesh, P())
        shard = NamedSharding(mesh, P(cfg.axis_name))
        params = jax.device_put(params, rep)

    # fast-forward the split chain over the epochs the checkpoint covers:
    # batches are a pure function of (seed, epoch) already, and this makes
    # the negative/merge keys match the unbroken run's too
    for _ in range(start_epoch):
        key, _, _ = jax.random.split(key, 3)

    history = list(prior_history or [])
    epochs_run = epochs
    for epoch in range(start_epoch, epochs):
        pos = kg_lib.epoch_batches(seed, epoch, partitioned, cfg.batch_size)
        key, k_neg, k_merge = jax.random.split(key, 3)
        pos = jnp.asarray(pos)
        neg = model.make_negatives(k_neg, pos, tcfg, head_prob)
        if cfg.backend == "shard_map":
            pos = jax.device_put(pos, shard)
            neg = jax.device_put(neg, shard)
        if with_overflow:
            params, loss, overflow = epoch_fn(params, pos, neg, k_merge)
            _raise_on_overflow(overflow, epoch)
        else:
            params, loss = epoch_fn(params, pos, neg, k_merge)
        loss = float(loss)
        history.append(loss)
        if callback is not None:
            callback(epoch, loss)
        # the host pipeline Reduces every epoch, so any eval_every lands on
        # a Reduce boundary; the final epoch is always evaluated
        done = epoch + 1
        stop = False
        if recorder is not None and (
            done % eval_loop.eval_every == 0 or done == epochs
        ):
            stop = recorder.record(epoch, done, loss, params)
        if writer is not None and writer.due(done, epochs, stopping=stop):
            writer.save(done, params, history)
        if stop:
            epochs_run = done
            break
    if writer is not None:
        writer.finish()
    return _finish_result(params, history, epochs_run, model, recorder)


def _train_device(
    tcfg: KGConfig,
    cfg: MapReduceConfig,
    model: KGModel,
    partitioned: np.ndarray,     # (W, N_w, 3) host array from the partitioner
    head_prob: Optional[jax.Array],
    params: Params,
    *,
    epochs: int,
    seed: int,
    mesh: Optional[Mesh],
    callback: Optional[Callable[[int, float], None]],
    recorder: "Optional[trace_lib.TraceRecorder]" = None,
    eval_loop: "Optional[trace_lib.EvalLoopConfig]" = None,
    caller_params: bool = False,
    writer: "Optional[_CheckpointWriter]" = None,
    start_epoch: int = 0,
    prior_history: Optional[list] = None,
    strata: Optional[np.ndarray] = None,
    update_mask: Optional[Params] = None,
) -> TrainResult:
    """Device-pipeline driver: put the partitioned triplets on device once,
    then run epochs in compiled scan blocks (``make_block_fn``).  The only
    per-block host work is the jit dispatch and the optional callback.

    In-loop evals (``eval_loop``) slice the blocks at eval boundaries —
    ``eval_every`` is a multiple of ``merge_every`` (validated by the
    caller), so every eval lands on a Reduce boundary and the block-size
    invariance keeps the sliced run bit-identical to the unsliced one.
    Checkpoints (``writer``) slice the blocks the same way; resuming from
    ``start_epoch`` just starts the epoch-id stream there — every key is
    ``fold_in(seed, epoch)``-derived, so the resumed run is bit-identical
    to the unbroken one.

    Params-buffer donation (``cfg.donate_params``, default on): each block
    call donates its params input, so the accelerator never holds two full
    copies of the embedding tables; caller-provided resume params are
    copied first so the user's buffers stay valid."""
    sched = cfg.schedule
    if epochs % sched.merge_every != 0:
        raise ValueError(
            f"epochs={epochs} is not a multiple of "
            f"merge_every={sched.merge_every} — the trailing local epochs "
            "would never be Reduced into the shared params; pick a multiple")

    part = jnp.asarray(partitioned)
    if cfg.backend == "shard_map":
        if mesh is None:
            raise ValueError("shard_map backend needs a mesh")
        parts = kg_partitions(cfg.table_sharding, axis_name=cfg.axis_name)
        part = jax.device_put(part, NamedSharding(mesh, parts.batch))
        # replicated: every device holds full tables; sharded: each
        # entity-role table rests row-sharded (~1/W per device) and the
        # block fn constrains its output to the same layout, keeping
        # donation in/out matched.  Relation-role (and non-dividing)
        # tables replicate — see kg_table_shardings.
        params = jax.device_put(params, kg_table_shardings(
            model.param_roles(), params, mesh, cfg.table_sharding,
            axis_name=cfg.axis_name))

    donate = cfg.donate_params if cfg.donate_params is not None else True
    if donate and caller_params:
        # never donate the caller's buffers (resume params / shared refs);
        # freshly initialized params have no outside owner and skip the copy
        params = jax.tree.map(lambda x: jnp.array(x), params)

    with_overflow = cfg.paradigm == "sgd" and cfg.merge_transport == "sparse"
    block_fn = make_block_fn(
        cfg, tcfg, part, mesh=mesh, model=model, head_prob=head_prob,
        seed=seed, donate=donate, with_overflow=with_overflow,
        strata=strata, update_mask=update_mask)

    # which SGD step the Map runs, counted once per block on the host
    map_counter = None
    if cfg.paradigm == "sgd":
        map_counter = ("map.compact_steps"
                       if compact_map(cfg, tcfg, update_mask is not None)
                       else "map.dense_steps")
        steps_per_epoch = cfg.n_workers * (
            partitioned.shape[1] // cfg.batch_size)

    # bounded staleness threads (global_view, worker_locals) through the
    # blocks — locals must survive block boundaries or slicing at eval/
    # checkpoint points would change results.  Locals start as W copies of
    # the global view (round 0 force-refreshes every worker anyway).
    stale = cfg.staleness > 0
    if stale:
        locals0 = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (cfg.n_workers,) + x.shape),
            params)
        if cfg.backend == "shard_map":
            locals0 = jax.device_put(
                locals0, NamedSharding(mesh, P(cfg.axis_name)))
        state = (params, locals0)
    else:
        state = params

    eval_every = eval_loop.eval_every if eval_loop is not None else None
    ckpt_every = writer.cfg.every if writer is not None else None
    repart = sched.repartition_every
    loss_blocks = []
    history = list(prior_history or [])    # host floats converted so far

    def snapshot_history() -> list:
        # sync the per-block device losses only when a checkpoint (or the
        # final result) actually needs them on the host; blocks are
        # append-only, so each call converts just the new ones
        while loss_blocks:
            history.extend(float(x) for x in np.asarray(loss_blocks.pop(0)))
        return history

    start = start_epoch
    epochs_run = epochs
    while start < epochs:
        # every block is a multiple of merge_every (epochs, block_epochs,
        # eval_every, checkpoint every, and repartition_every all are), so
        # every block — including the remainder and boundary slices —
        # still ends on a Reduce.  Blocks are additionally sliced at
        # re-partition boundaries so block_fn computes each round's
        # partition exactly once (see make_block_fn).
        length = min(sched.block_epochs, epochs - start)
        if eval_every is not None:
            length = min(length, eval_every - start % eval_every)
        if ckpt_every is not None:
            length = min(length, ckpt_every - start % ckpt_every)
        if repart is not None:
            length = min(length, repart - start % repart)
        epoch_ids = jnp.arange(start, start + length, dtype=jnp.int32)
        if with_overflow:
            with obs.span("fit.block"):
                state, losses, overflow = block_fn(state, epoch_ids)
            _raise_on_overflow(overflow, start + length - 1)
        else:
            with obs.span("fit.block"):
                state, losses = block_fn(state, epoch_ids)
        if map_counter is not None:
            obs.count(map_counter, steps_per_epoch * length)
        # evals/checkpoints/results read the *global view* — under
        # staleness the worker locals are divergent scratch state
        params = state[0] if stale else state
        loss_blocks.append(losses)               # device array per block
        start += length
        if callback is not None:
            with obs.span("fit.sync"):          # waits for the block
                last = float(losses[-1])
            callback(start - 1, last)
        stop = False
        with obs.span("fit.boundary"):
            if recorder is not None and (
                start % eval_every == 0 or start == epochs
            ):
                stop = recorder.record(
                    start - 1, start // sched.merge_every,
                    float(losses[-1]), params)
            if writer is not None and writer.due(start, epochs,
                                                 stopping=stop):
                writer.save(start, params, snapshot_history())
        if stop:
            epochs_run = start
            break
    if writer is not None:
        writer.finish()
    return _finish_result(params, snapshot_history(), epochs_run, model,
                          recorder)
