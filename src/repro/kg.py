"""Top-level model-agnostic KG embedding API, built around the
:class:`~repro.kb.KnowledgeBase` artifact.

Training produces — and every downstream surface consumes — a
``KnowledgeBase``: model + embedding tables + graph metadata as one
persistent, serveable object.  ``fit`` and ``evaluate`` are thin wrappers
around it:

    from repro import kg
    from repro.data import kg as kg_lib

    graph = kg_lib.synthetic_kg(0)
    result = kg.fit(graph, model="distmult", paradigm="bgd", epochs=50)

    kb = result.kb                       # the trained artifact
    kb.save("my_kb")                     # persist (atomic, manifest'd)
    kb = kg.KnowledgeBase.load("my_kb")  # ... in another process

    top = kb.query_tails(h, r, k=10)     # device-resident batched top-k
    metrics = kg.evaluate(kb)            # == kb.evaluate()
    metrics = kg.evaluate(result.params, "distmult", graph)   # still works

Long runs checkpoint and resume **bit-identically** from inside ``fit``:

    kg.fit(graph, epochs=100, ckpt_dir="ckpt", checkpoint_every=10)
    # ... crash / preemption ...
    kg.fit(graph, epochs=100, ckpt_dir="ckpt", resume=True)
    # == the unbroken 100-epoch run, parameter-for-parameter

``model`` is any name in ``kg.models()`` (transe / transh / distmult /
rotate / your plugin — see ``repro.core.models``); ``paradigm`` is the
paper's 'sgd' (local epochs + conflict-resolving Reduce) or 'bgd'
(gradient Reduce);
``backend`` is 'vmap' (simulated workers, single device) or 'shard_map'
(real mesh axis, pass ``mesh=``).
"""
from __future__ import annotations

from typing import Callable, Optional

import jax

from repro import kb as kb_lib
from repro.core import eval as kg_eval
from repro.core import mapreduce
from repro.core import trace as trace_lib
from repro.core.models import KGConfig, KGModel, available, get_model
from repro.train import checkpoint as checkpoint_lib

TrainResult = mapreduce.TrainResult
EpochSchedule = mapreduce.EpochSchedule
TrainingTrace = trace_lib.TrainingTrace
KnowledgeBase = kb_lib.KnowledgeBase


def models() -> tuple:
    """Names of all registered scoring models."""
    return available()


def make_configs(
    kg,
    model: "str | KGModel" = "transe",
    paradigm: str = "sgd",
    *,
    dim: int = 50,
    margin: float = 1.0,
    norm: str = "l1",
    learning_rate: float = 0.01,
    normalize: str = "epoch",
    sampling: str = "unif",
    n_workers: int = 4,
    strategy: str = "average",
    reduce_impl: str = "psum",
    merge_transport: str = "dense",
    backend: str = "vmap",
    batch_size: int = 256,
    partition: str = "balanced",
    partitioner: Optional[str] = None,
    pipeline: str = "host",
    block_epochs: int = 1,
    merge_every: int = 1,
    repartition_every: Optional[int] = None,
    strict_batching: bool = False,
    donate_params: Optional[bool] = None,
    table_sharding: str = "replicated",
    touched_capacity: Optional[int] = None,
    staleness: int = 0,
    negatives: str = "pertriplet",
    neg_candidates: int = 0,
) -> tuple[KGConfig, mapreduce.MapReduceConfig]:
    """Build the (model hyperparams, engine) config pair ``fit`` uses —
    exposed separately for benchmarks that drive epochs by hand.

    ``pipeline='device'`` runs epochs in compiled scan blocks of
    ``block_epochs`` with on-device batching and negative sampling (results
    are bit-identical for any block size); ``merge_every=K`` lets SGD
    workers take K local epochs between Reduces; ``repartition_every=M``
    re-splits the triplets across workers on device every M epochs
    (killing residual split bias); ``donate_params`` (default on) donates
    the params buffer through each compiled block so the accelerator holds
    one copy of the tables.  ``pipeline='host'`` (the default) is the
    original per-epoch loop, preserved bit-for-bit.

    ``merge_transport='sparse'`` makes every Reduce exchange only the rows
    the round's touch stats mark updated (static-capacity padded delta
    buffers) instead of whole tables — bit-identical results on every
    strategy, paradigm, pipeline, and backend (see the transport contract
    in ``core/merge.py``); 'dense' (the default) is the reference.

    ``table_sharding='sharded'`` (requires the sparse transport) routes
    every Reduce to the shard owning each touched row — per-shard
    candidate unions, local merges, no full-table all-gather — and keeps
    results bit-identical to 'replicated' on every strategy, paradigm,
    pipeline, and backend.  ``touched_capacity`` overrides the analytic
    per-round touched-row bound of the sparse delta buffers (rows per
    worker per Reduce); an undersized override is rejected at config time
    and an overflow at run time raises instead of silently dropping
    updates.

    ``partitioner`` (alias of ``partition``; either spelling works) picks
    the host-side triplet split: 'balanced' (uniform shuffle-split, the
    reference), 'stratified' (relation-stratified), 'degree'
    (degree-stratified — every worker gets the same head+tail degree mix,
    so no worker trains only on cold entities), or 'overlap' (greedy
    streaming split minimizing cross-worker entity overlap, which shrinks
    the Reduce's conflict surface; incompatible with
    ``repartition_every``).

    ``staleness=S`` (SGD paradigm, ``pipeline='device'``) bounds how stale
    each worker's view of the merged model may get: workers re-read the
    global tables only every 1..S+1 Reduce rounds (staggered,
    fold_in-derived phases) while their deltas still merge into the global
    view each round.  S=0 (default) is the synchronous engine, verbatim;
    S>0 trades Reduce-barrier adoption for extra local progress and stays
    deterministically reproducible (same seed, same result — see
    docs/architecture.md).

    ``negatives='joint'`` scores every positive in a batch against one
    shared corruption pool (the DGL-KE joint negative sampling) instead of
    its own corrupted triplet — one (B, C) matmul-style scoring pass per
    batch; ``neg_candidates=C`` caps the pool (0 = the whole batch's
    corruptions).  Works under both paradigms and every
    pipeline/backend/transport."""
    model = get_model(model)
    if partitioner is not None:
        partition = partitioner
    kcfg = KGConfig(
        n_entities=kg.n_entities,
        n_relations=kg.n_relations,
        dim=dim,
        margin=margin,
        norm=norm,
        learning_rate=learning_rate,
        normalize=normalize,
        sampling=sampling,
        negatives=negatives,
        neg_candidates=neg_candidates,
    )
    mcfg = mapreduce.MapReduceConfig(
        n_workers=n_workers,
        paradigm=paradigm,
        strategy=strategy,
        reduce_impl=reduce_impl,
        merge_transport=merge_transport,
        backend=backend,
        batch_size=batch_size,
        partition=partition,
        model=model.name,
        pipeline=pipeline,
        schedule=mapreduce.EpochSchedule(
            block_epochs=block_epochs, merge_every=merge_every,
            repartition_every=repartition_every),
        strict_batching=strict_batching,
        donate_params=donate_params,
        table_sharding=table_sharding,
        touched_capacity=touched_capacity,
        staleness=staleness,
    )
    return kcfg, mcfg


def fit(
    kg,
    model: "str | KGModel" = "transe",
    paradigm: str = "sgd",
    *,
    epochs: int = 50,
    seed: int = 0,
    mesh=None,
    params=None,
    callback: Optional[Callable[[int, float], None]] = None,
    eval_every: Optional[int] = None,
    eval_metric: str = "entity_filtered.mean_rank",
    patience: Optional[int] = None,
    eval_engine: str = "device",
    eval_filtered: bool = True,
    eval_kw: Optional[dict] = None,
    keep_best: bool = True,
    ckpt_dir: Optional[str] = None,
    checkpoint_every: Optional[int] = None,
    resume: bool = False,
    keep_checkpoints: int = 3,
    sync_checkpoints: bool = False,
    **config_kw,
) -> TrainResult:
    """Train ``model`` on ``kg`` with the MapReduce engine.

    ``config_kw`` forwards to :func:`make_configs` (dim, margin, norm,
    learning_rate, n_workers, strategy, backend, batch_size, pipeline,
    block_epochs, merge_every, repartition_every, partitioner=,
    staleness=, negatives=, ...).  Returns a
    :class:`TrainResult` with params, loss_history, and the resolved model
    name.

    With ``pipeline="device"`` whole blocks of epochs run as one compiled
    scan on device and ``callback`` fires at block boundaries only (the
    host pipeline calls it every epoch).

    In-training evaluation (``core/trace.py``): ``eval_every=K`` runs the
    full evaluation protocol every K epochs *from inside the loop* — at
    Reduce boundaries, so K must be a multiple of ``merge_every`` on the
    device pipeline — and attaches a :class:`TrainingTrace` of
    quality-vs-epoch curves to the result.  Each entry's metrics are
    exactly what a post-hoc :func:`evaluate` of the same params returns.
    ``eval_metric`` (a dotted spec, default the paper-style filtered mean
    rank) drives ``patience`` early stopping (stop after that many
    consecutive non-improving evals) and — with ``keep_best`` — the
    ``best_params`` / ``best_epoch`` snapshot on the result.
    ``eval_engine`` defaults to the device engine (identical numbers,
    benchmarked multiples faster; ``eval_kw`` forwards engine options —
    ``n_workers`` defaults to the training worker count).

    Checkpoint/resume: ``ckpt_dir`` + ``checkpoint_every=K`` snapshot
    params and manifest every K epochs (a Reduce boundary — a multiple of
    ``merge_every`` on the device pipeline; ``checkpoint_every=None``
    saves the final state only; saves are async unless
    ``sync_checkpoints``).  ``resume=True`` restores the latest
    checkpoint in ``ckpt_dir`` — after validating model name, seed, and
    graph fingerprint against this call — and continues to ``epochs``
    total, **bit-identically** to the unbroken run (batching, negative
    sampling, and merge keys are pure functions of (seed, epoch);
    tests/test_kb.py pins this per pipeline x paradigm).

    ``model`` may be a registry name or a ``KGModel`` instance; an instance
    is used as-is (it shadows any registry entry sharing its name — custom
    subclasses train with their own overrides).  Instances with a name the
    registry doesn't know must be ``register()``-ed first.

    The returned ``TrainResult`` carries the trained artifact as ``.kb``
    (a :class:`KnowledgeBase`) — save it, serve it, or evaluate it."""
    model = get_model(model)
    kcfg, mcfg = make_configs(kg, model, paradigm, **config_kw)

    ckpt_cfg = None
    resume_kw: dict = {}
    if ckpt_dir is not None:
        ckpt_cfg = mapreduce.CheckpointConfig(
            ckpt_dir=ckpt_dir, every=checkpoint_every,
            keep=keep_checkpoints, synchronous=sync_checkpoints)
    else:
        ckpt_only = {
            "checkpoint_every": checkpoint_every is not None,
            "resume": resume,
            "keep_checkpoints": keep_checkpoints != 3,
            "sync_checkpoints": sync_checkpoints,
        }
        passed = sorted(k for k, hit in ckpt_only.items() if hit)
        if passed:
            raise ValueError(
                f"{passed} configure checkpointing and need ckpt_dir= "
                "to say where the checkpoints live")
    if resume:
        if params is not None:
            raise ValueError(
                "pass either resume=True (params come from the latest "
                "checkpoint) or params=, not both")
        template = jax.eval_shape(
            lambda k: model.init_params(k, kcfg), jax.random.PRNGKey(0))
        _, params, _, extra = checkpoint_lib.restore(
            ckpt_dir, params_template=template,
            expect={"kind": "kg_train", "model": model.name,
                    "seed": seed, "graph": kg.fingerprint(),
                    "config": mapreduce.resume_config(kcfg, mcfg)})
        resume_kw = dict(
            start_epoch=int(extra["epoch"]),
            resume_fresh_init=bool(extra.get("fresh_init", True)),
            prior_history=list(extra.get("loss_history") or []),
        )
    eval_loop = None
    if eval_every is not None:
        engine_kw = dict(eval_kw or {})
        if eval_engine == "device":
            engine_kw.setdefault("n_workers", mcfg.n_workers)
        eval_loop = trace_lib.EvalLoopConfig(
            eval_every=eval_every, metric=eval_metric, patience=patience,
            engine=eval_engine, filtered=eval_filtered,
            engine_kw=engine_kw, keep_best=keep_best)
    else:
        non_defaults = {
            "eval_metric": eval_metric != "entity_filtered.mean_rank",
            "patience": patience is not None,
            "eval_engine": eval_engine != "device",
            "eval_filtered": eval_filtered is not True,
            "eval_kw": eval_kw is not None,
            "keep_best": keep_best is not True,
        }
        passed = sorted(k for k, hit in non_defaults.items() if hit)
        if passed:
            raise ValueError(
                f"{passed} configure the in-training evaluation loop and "
                "would be silently ignored — pass eval_every=K to enable "
                "it")
    res = mapreduce.train(
        kg, kcfg, mcfg,
        epochs=epochs, seed=seed, mesh=mesh, params=params, callback=callback,
        model=model, eval_loop=eval_loop, checkpoint=ckpt_cfg, **resume_kw,
    )
    res.kb = kb_lib.KnowledgeBase(
        model=model, params=res.params, graph=kg, norm=kcfg.norm,
        meta={"paradigm": paradigm, "epochs": res.epochs_run, "seed": seed,
              "dim": kcfg.dim})
    return res


def evaluate(
    params,
    model: "str | KGModel | None" = None,
    kg=None,
    *,
    norm: Optional[str] = None,
    filtered: bool = True,
    engine: str = "host",
    **engine_kw,
) -> dict:
    """All three paper tasks (entity inference, relation prediction, triplet
    classification) for any registered model.

    Accepts either a :class:`KnowledgeBase` (``evaluate(kb)`` — model,
    graph, and norm come from the artifact; any explicitly passed value
    overrides) or the raw ``(params, model, kg)`` triple every pre-existing
    call site uses.

    ``engine="host"`` is the frozen reference protocol loop;
    ``engine="device"`` runs each task as one compiled device-resident
    computation with the query axis optionally sharded over workers —
    identical numbers, benchmarked multiples faster (BENCH_eval.json).
    Device-engine options ride in ``engine_kw``: ``n_workers``, ``backend``
    ('vmap' | 'shard_map'), ``mesh``, ``chunk``, ``fused``, ``max_fanout``,
    ``table_sharding`` ('replicated' | 'sharded' — the shard-local
    candidate scan; identical numbers either way) — see
    ``repro.core.eval_device.evaluate_all_device``."""
    if isinstance(params, kb_lib.KnowledgeBase):
        kb = params
        params = kb.params
        model = kb.model if model is None else model
        kg = kb.graph if kg is None else kg
        norm = kb.norm if norm is None else norm
        if kg is None:
            raise ValueError(
                "this KnowledgeBase carries no graph (loaded with "
                "include_graph=False?) — pass kg= explicitly")
    elif model is None or kg is None:
        raise TypeError(
            "evaluate(params, ...) needs model= and kg= when params is a "
            "raw table dict (or pass a KnowledgeBase)")
    return kg_eval.evaluate_all(
        params, kg, norm=norm or "l1", filtered=filtered, model=model,
        engine=engine, **engine_kw
    )


def update(kb, new_triples, **updater_kw) -> "kb_lib.KnowledgeBase":
    """Incrementally fold ``new_triples`` into a trained artifact and
    return a NEW :class:`KnowledgeBase` — ``kg.update(kb, triples)`` is
    ``kb.update(triples)``.  Unseen entities/relations get ids exactly as
    a fresh ``load_tsv_dir`` would intern them, the grown tables warm-init
    from relation neighbors, and a short masked fine-tune moves only the
    rows the delta touches (``repro.online.OnlineUpdater``)."""
    if not isinstance(kb, kb_lib.KnowledgeBase):
        raise TypeError(
            f"update() takes a KnowledgeBase artifact, got {type(kb)!r} — "
            "train one with kg.fit(...).kb or load one with "
            "KnowledgeBase.load")
    return kb.update(new_triples, **updater_kw)
