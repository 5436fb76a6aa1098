"""Device-resident batched evaluation engine.

The host reference (``core/eval.py``) certifies that MapReduce-merged
embeddings retain single-thread quality, but it pays a python loop over
query chunks, one jit dispatch per chunk, and a per-query python walk over
the filtered known candidates — on large graphs the *eval* loop, not
training, becomes the wall.  This module is the eval analogue of the PR 2
scan-over-epochs training pipeline: each task runs as **one compiled
computation** over the whole test split.

How it works, per task:

  * **Entity inference** — test queries are padded and laid out as
    ``(W, S, C, 3)``: ``W`` workers (the same vmap / shard_map backends the
    training engine uses, via ``parallel/util.worker_map``) each scan over
    ``S`` chunks of ``C`` queries.  Every chunk scores all entities through
    the model's ``candidate_energies`` (or, for models with
    ``supports_fused_kernel`` on TPU, streams entity tiles through the
    ``rank_topk`` Pallas kernel), extracts raw ranks on device, and applies
    filtering by gathering candidate columns of the *same* score matrix at
    the ``KG``'s precomputed padded known-candidate masks
    (``KG.eval_filter_candidates`` — built once, placed on device once).
    Only the final ``(Q,)`` rank vectors return to the host.
  * **Relation prediction** — fused into the *same* scan body as entity
    inference (``relations=True``): each chunk also scores all R relations
    through ``relation_energies`` and extracts the gold relation's rank, so
    the full ranking protocol is one pass over the test queries instead of
    two (the ROADMAP "tiny win").  A standalone scan
    (``relation_prediction_device``) remains for callers that only need
    relation ranks.
  * **Triplet classification** — the four score vectors (valid/test,
    pos/neg) are computed in one jitted dispatch; the per-relation
    threshold fit is inherently host-side (tiny sorts) and shared with the
    host engine (``eval._threshold_accuracy``), so both engines agree
    exactly.

Parity contract: with ``fused=False`` (the default off TPU) the device
engine reads gold and candidate scores out of the same
``candidate_energies`` matrix the host reference uses, so ranks — and hence
metrics — are **identical**, not merely close (tests/test_eval_device.py).
The fused kernel path recomputes gold distances in streaming form and may
differ in the last ulp; it is opt-in off TPU and cross-checked with
tolerance like the other kernel tests.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import PartitionSpec as P

from repro import obs
from repro.core import eval as host_eval
from repro.core import merge as merge_lib
from repro.core.models import KGModel, Params, get_model
from repro.parallel.util import worker_map

RankMetrics = host_eval.RankMetrics

DEFAULT_CHUNK = 256
# bytes of entity rows a scan step gathers per filter column: DEFAULT_CHUNK
# queries of a 400-float float32 row (DGL-KE's FB15k width for TransE)
CHUNK_ROW_BYTES = DEFAULT_CHUNK * 400 * 4


def chunk_rows(params: Params) -> int:
    """Queries per scan step for ``params``: ``DEFAULT_CHUNK`` up to
    400-float entity rows, fewer (a multiple of 8) for wider ones, so the
    filter correction's substituted-triple gathers — chunk x widest known
    group x row — stay the size they have at TransE's width (RotatE's
    2,000-float rows: 48 queries)."""
    ent = params["ent"]
    row = int(np.prod(ent.shape[1:])) * np.dtype(ent.dtype).itemsize
    return int(min(DEFAULT_CHUNK, max(8, CHUNK_ROW_BYTES // row // 8 * 8)))


# ---------------------------------------------------------------------------
# Layout: pad the query axis and split it (workers, scan steps, chunk rows)
# ---------------------------------------------------------------------------

def _layout(n: int, chunk: int, n_workers: int) -> Tuple[int, int, int]:
    """(S, C, padded_n) for ``n`` queries: each of ``n_workers`` workers
    scans ``S`` chunks of ``C`` rows; ``S * C * n_workers >= n``."""
    C = max(1, chunk // n_workers)
    step = C * n_workers
    S = max(1, -(-n // step))
    return S, C, S * step


def _pad_rows(arr: np.ndarray, padded_n: int) -> np.ndarray:
    """Pad axis 0 to ``padded_n`` by repeating row 0 (valid ids, scored
    harmlessly, sliced off after the ranks come back)."""
    if len(arr) == padded_n:
        return arr
    reps = np.broadcast_to(arr[:1], (padded_n - len(arr),) + arr.shape[1:])
    return np.concatenate([arr, reps], axis=0)


def _shard(arr: np.ndarray, W: int, S: int, C: int) -> jax.Array:
    """(padded_n, ...) -> (W, S, C, ...), worker-major contiguous rows."""
    return jnp.asarray(arr.reshape((W, S, C) + arr.shape[1:]))


def _unshard(out: jax.Array, n: int) -> np.ndarray:
    """(W, S, C) rank grid -> (n,) host vector in original query order."""
    return np.asarray(out).reshape(-1)[:n]


def _pad_ent_tables(model: KGModel, params: Params, padded_E: int) -> Params:
    """Zero-pad every entity-role table to ``padded_E`` rows so the
    ``n_shards`` equal row blocks of the sharded scan tile it exactly.
    Pad rows are dead weight only: the rank / top-k math masks candidates
    by ``id < n_entities``, so their (finite) scores never count."""
    roles = model.param_roles()
    out = dict(params)
    for name, arr in params.items():
        if roles.get(name) != "ent":
            continue
        arr = jnp.asarray(arr)
        if arr.shape[0] < padded_E:
            pad = jnp.zeros((padded_E - arr.shape[0],) + arr.shape[1:],
                            arr.dtype)
            arr = jnp.concatenate([arr, pad], axis=0)
        out[name] = arr
    return out


def _check_sharded_mesh(backend: str, mesh, n_shards: int,
                        axis_name: str = "workers") -> None:
    """The sharded scan assigns row block ``i`` to mesh position ``i``, so
    under shard_map the mesh axis must be exactly ``n_shards`` wide (vmap
    simulates the shards on one device and needs no mesh)."""
    if backend != "shard_map":
        return
    if mesh is None:
        raise ValueError("backend='shard_map' needs a mesh")
    if mesh.shape[axis_name] != n_shards:
        raise ValueError(
            f"table_sharding='sharded' over shard_map needs mesh axis "
            f"{axis_name!r} of size {n_shards} (= n_workers), got "
            f"{mesh.shape[axis_name]}")


# ---------------------------------------------------------------------------
# Entity inference
# ---------------------------------------------------------------------------

def _entity_chunk(
    model: KGModel,
    params: Params,
    chunk: jax.Array,        # (C, 3)
    cands: jax.Array,        # (C, P) padded candidate ids (pad id = E)
    side: str,
    norm: str,
    fused: bool,
) -> Tuple[jax.Array, jax.Array]:
    """(raw, filtered) ranks for one chunk, fully on device.

    The filtered rank subtracts known candidates (other than the gold
    entity) scoring strictly better than the gold — the same predicate the
    host reference applies per query, evaluated here as one gather over the
    padded mask.  Pad ids point one past the entity table and read +inf, so
    they never count."""
    E = params["ent"].shape[0]
    gold_ids = chunk[:, 2] if side == "tail" else chunk[:, 0]
    if fused:
        with obs.scope("eval.scan"):
            raw_counts = model.fused_rank_counts(
                params, chunk, side, norm=norm)
            raw = 1 + raw_counts.astype(jnp.int32)
        # candidate scores via substituted-triplet energies (the kernel
        # never materializes the (C, E) matrix); gold recomputed the same way
        with obs.scope("eval.filter"):
            col = 2 if side == "tail" else 0
            subst = jnp.broadcast_to(
                chunk[:, None, :], cands.shape + (3,)
            ).at[:, :, col].set(jnp.minimum(cands, E - 1))
            cvals = model.energy(params, subst, norm)
            cvals = jnp.where(cands >= E, jnp.inf, cvals)
        with obs.scope("eval.scan"):
            gold = model.energy(params, chunk, norm)
    else:
        with obs.scope("eval.scan"):
            scores = model.candidate_energies(params, chunk, side, norm)
            gold = scores[jnp.arange(scores.shape[0]), gold_ids]
            raw = 1 + jnp.sum(
                scores < gold[:, None], axis=1).astype(jnp.int32)
        # pad ids (== E) gather a clamped column, then read +inf — no
        # (C, E+1) copy of the score matrix inside the scan body
        with obs.scope("eval.filter"):
            cvals = jnp.take_along_axis(
                scores, jnp.minimum(cands, E - 1), axis=1)
            cvals = jnp.where(cands >= E, jnp.inf, cvals)
    with obs.scope("eval.filter"):
        better = (cvals < gold[:, None]) & (cands != gold_ids[:, None])
        filt = raw - jnp.sum(better, axis=1).astype(jnp.int32)
    # the fused path recomputes distances and can disagree with the raw
    # count in the last ulp; ranks are >= 1 by construction on the exact path
    return raw, jnp.maximum(filt, 1)


@obs.scope("eval.relations")
def _relation_ranks(model: KGModel, params: Params, q: jax.Array,
                    norm: str) -> jax.Array:
    """(C,) rank of each query's gold relation among all relations."""
    scores = model.relation_energies(params, q, norm)
    gold = scores[jnp.arange(scores.shape[0]), q[:, 1]]
    return 1 + jnp.sum(scores < gold[:, None], axis=1).astype(jnp.int32)


@functools.partial(
    jax.jit,
    static_argnames=(
        "model", "norm", "backend", "axis_name", "fused", "mesh",
        "relations"),
)
def _entity_ranks_device(
    model: KGModel,
    params: Params,
    queries: jax.Array,      # (W, S, C, 3)
    tail_cands: jax.Array,   # (W, S, C, Pt)
    head_cands: jax.Array,   # (W, S, C, Ph)
    *,
    norm: str,
    backend: str,
    mesh,
    axis_name: str,
    fused: bool,
    relations: bool = False,
) -> Dict[str, jax.Array]:
    """Both sides' (raw, filtered) rank grids — and, with ``relations``,
    the gold-relation rank grid — in one compiled computation.  Fusing the
    relation task into the same scan body saves a second pass over the
    query layout (one scan, three rank families)."""

    def per_worker(params, q_w, tc_w, hc_w):
        def body(_, inp):
            q, tc, hc = inp
            raw_t, filt_t = _entity_chunk(
                model, params, q, tc, "tail", norm, fused)
            raw_h, filt_h = _entity_chunk(
                model, params, q, hc, "head", norm, fused)
            out = {
                "tail_raw": raw_t, "tail_filtered": filt_t,
                "head_raw": raw_h, "head_filtered": filt_h,
            }
            if relations:
                out["relation"] = _relation_ranks(model, params, q, norm)
            return None, out

        _, outs = jax.lax.scan(body, None, (q_w, tc_w, hc_w))
        return outs          # each (S, C)

    run = worker_map(
        per_worker, backend=backend, mesh=mesh, axis_name=axis_name)
    return run(params, queries, tail_cands, head_cands)


# ---------------------------------------------------------------------------
# Sharded tables: shard-local candidate scan + exact cross-shard combine
# ---------------------------------------------------------------------------

def _shard_slice_parts(model, params, q, side, norm, gold_ids, lo, n):
    """One shard's ``(C, n)`` score slice over candidate rows
    ``[lo, lo + n)`` plus the gold entity's partial score: the owning
    shard reads it out of its slice, every other shard contributes +inf,
    so a min across shards is *bitwise* the gold score the replicated
    scan reads out of the full matrix."""
    s = model.candidate_slice_energies(params, q, side, norm, lo=lo, n=n)
    off = gold_ids - lo
    own = (off >= 0) & (off < n)
    gp = jnp.where(
        own,
        jnp.take_along_axis(s, jnp.clip(off, 0, n - 1)[:, None],
                            axis=1)[:, 0],
        jnp.inf)
    return s, gp


@functools.partial(
    jax.jit,
    static_argnames=(
        "model", "norm", "backend", "axis_name", "mesh", "n_shards",
        "n_entities", "relations"),
)
def _entity_ranks_sharded(
    model: KGModel,
    params: Params,          # entity-role tables padded to n_shards * R
    queries: jax.Array,      # (S, C, 3) — the query axis is NOT split
    tail_cands: jax.Array,   # (S, C, Pt)
    head_cands: jax.Array,   # (S, C, Ph)
    *,
    norm: str,
    backend: str,
    mesh,
    axis_name: str,
    n_shards: int,
    n_entities: int,
    relations: bool = False,
) -> Dict[str, jax.Array]:
    """``_entity_ranks_device`` with the *candidate* axis sharded instead
    of the query axis: each of ``n_shards`` shards scans only its
    contiguous block of ``R = shard_rows(E, W)`` entity rows
    (``candidate_slice_energies``) and the per-shard partials combine
    exactly —

      * gold score: owner's value via min / ``pmin`` (returns an operand
        bit-exactly; every non-owner holds +inf),
      * raw rank:   1 + an **integer** sum of per-shard strictly-better
        counts (padded columns masked by ``id < E``; int addition is
        associative, so the partition can't perturb the total),
      * filtered:   each known candidate is owned by exactly one shard,
        which checks it against the combined gold; counts int-sum.

    Ranks are therefore bitwise the replicated scan's, per strategy and
    backend (tests/test_sharded_tables.py).  ``vmap`` stacks the shard
    axis on one device; ``shard_map`` places block ``i`` on mesh position
    ``i`` (mesh axis width must equal ``n_shards``)."""
    E, W = n_entities, n_shards
    R = merge_lib.shard_rows(E, W)
    cdtype = queries.dtype

    if backend == "vmap":
        los = (jnp.arange(W, dtype=cdtype) * R).astype(cdtype)
        cols = los[:, None] + jnp.arange(R, dtype=cdtype)[None, :]  # (W, R)
        live = cols < E

        def side_ranks(q, cands, side):
            gold_ids = q[:, 2] if side == "tail" else q[:, 0]
            with obs.scope("eval.scan"):
                s_all, gp_all = jax.vmap(
                    lambda lo: _shard_slice_parts(
                        model, params, q, side, norm, gold_ids, lo, R)
                )(los)                           # (W, C, R), (W, C)
                gold = jnp.min(gp_all, axis=0)
                raw = 1 + jnp.sum(
                    (s_all < gold[None, :, None]) & live[:, None, :],
                    axis=(0, 2)).astype(jnp.int32)
            with obs.scope("eval.filter"):
                c_off = cands[None, :, :] - los[:, None, None]
                inr = (c_off >= 0) & (c_off < R) & (cands[None] < E)
                cv = jnp.take_along_axis(
                    s_all, jnp.clip(c_off, 0, R - 1), axis=2)
                better = (inr & (cv < gold[None, :, None])
                          & (cands[None] != gold_ids[None, :, None]))
                filt = raw - jnp.sum(better, axis=(0, 2)).astype(jnp.int32)
            return raw, jnp.maximum(filt, 1)

        def body(_, inp):
            q, tc, hc = inp
            raw_t, filt_t = side_ranks(q, tc, "tail")
            raw_h, filt_h = side_ranks(q, hc, "head")
            out = {
                "tail_raw": raw_t, "tail_filtered": filt_t,
                "head_raw": raw_h, "head_filtered": filt_h,
            }
            if relations:
                out["relation"] = _relation_ranks(model, params, q, norm)
            return None, out

        _, outs = jax.lax.scan(
            body, None, (queries, tail_cands, head_cands))
        return outs

    def per_shard(params, q_all, tc_all, hc_all):
        lo = (jax.lax.axis_index(axis_name) * R).astype(cdtype)
        live = (lo + jnp.arange(R, dtype=cdtype)) < E

        def side_ranks(q, cands, side):
            gold_ids = q[:, 2] if side == "tail" else q[:, 0]
            with obs.scope("eval.scan"):
                s, gp = _shard_slice_parts(
                    model, params, q, side, norm, gold_ids, lo, R)
                gold = jax.lax.pmin(gp, axis_name)
                cnt = jnp.sum((s < gold[:, None]) & live[None, :],
                              axis=1).astype(jnp.int32)
                raw = 1 + jax.lax.psum(cnt, axis_name)
            with obs.scope("eval.filter"):
                c_off = cands - lo
                inr = (c_off >= 0) & (c_off < R) & (cands < E)
                cv = jnp.take_along_axis(
                    s, jnp.clip(c_off, 0, R - 1), axis=1)
                better = (inr & (cv < gold[:, None])
                          & (cands != gold_ids[:, None]))
                filt = raw - jax.lax.psum(
                    jnp.sum(better, axis=1).astype(jnp.int32), axis_name)
            return raw, jnp.maximum(filt, 1)

        def body(_, inp):
            q, tc, hc = inp
            raw_t, filt_t = side_ranks(q, tc, "tail")
            raw_h, filt_h = side_ranks(q, hc, "head")
            out = {
                "tail_raw": raw_t, "tail_filtered": filt_t,
                "head_raw": raw_h, "head_filtered": filt_h,
            }
            if relations:
                # every shard computes the full relation scan identically
                # (the relation table is never sharded)
                out["relation"] = _relation_ranks(model, params, q, norm)
            return None, out

        _, outs = jax.lax.scan(body, None, (q_all, tc_all, hc_all))
        return outs

    fn = jax.shard_map(
        per_shard, mesh=mesh,
        in_specs=(P(), P(), P(), P()), out_specs=P(), check_vma=False)
    return fn(params, queries, tail_cands, head_cands)


def entity_ranks_device(
    params: Params,
    test: np.ndarray,
    norm: str = "l1",
    cand_masks: Optional[Tuple[np.ndarray, np.ndarray]] = None,
    *,
    model: "str | KGModel" = "transe",
    chunk: Optional[int] = None,
    n_workers: int = 1,
    backend: str = "vmap",
    mesh=None,
    fused: Optional[bool] = None,
    relations: bool = False,
    table_sharding: str = "replicated",
) -> Dict[str, np.ndarray]:
    """Per-query entity-inference ranks from the device engine, in test
    order: ``{"raw_ranks": {"tail", "head"}, "filtered_ranks": {...}}`` —
    the exact arrays ``host_eval.entity_inference(return_ranks=True)``
    produces (``filtered_ranks`` only when ``cand_masks`` is given).

    ``relations=True`` additionally returns ``"relation_ranks"`` (the
    gold-relation rank per query), computed in the *same* scan body — the
    fused protocol pass ``evaluate_all_device`` runs.

    ``table_sharding="sharded"`` shards the *candidate* axis instead of
    the query axis: ``n_workers`` shards each scan only their contiguous
    entity-row block and the partial ranks combine exactly
    (``_entity_ranks_sharded``) — ranks stay bitwise identical to the
    replicated scan."""
    model = get_model(model)
    if table_sharding not in ("replicated", "sharded"):
        raise ValueError(
            f"table_sharding must be 'replicated' or 'sharded', got "
            f"{table_sharding!r}")
    sharded = table_sharding == "sharded"
    if sharded:
        if fused:
            raise ValueError(
                "fused=True is incompatible with table_sharding='sharded' "
                "(the Pallas rank kernel streams the full entity table)")
        fused = False
    else:
        fused = _resolve_fused(model, fused)
    test = np.asarray(test, np.int32)
    Q = len(test)
    E = params["ent"].shape[0]
    if chunk is None:
        chunk = chunk_rows(params)
    # sharded mode keeps every query on every shard (W=1 in the layout):
    # the candidate axis, not the query axis, is what splits W ways
    S, C, Qp = _layout(Q, chunk, 1 if sharded else n_workers)
    W = n_workers
    if fused:
        # both sides, one kernel call per worker and scan step
        scanned, _ = model.fused_scan_cells(params, C, norm)
        _, per_query = model.fused_scan_cells(params, 1, norm)
        obs.count("eval.scan_cells", 2 * W * S * scanned)
        obs.count("eval.scan_useful_cells", 2 * Q * per_query)

    if cand_masks is None:
        # pad-only masks: zero filtering work, filtered == raw (dropped
        # from the returned dict below)
        empty = np.full((Q, 1), E, np.int32)
        tails, heads = empty, empty
    else:
        tails, heads = cand_masks
    layout_W = 1 if sharded else W
    with obs.span("eval.layout"):
        q = _shard(_pad_rows(test, Qp), layout_W, S, C)
        tc = _shard(_pad_rows(np.asarray(tails, np.int32), Qp),
                    layout_W, S, C)
        hc = _shard(_pad_rows(np.asarray(heads, np.int32), Qp),
                    layout_W, S, C)
        if sharded:
            _check_sharded_mesh(backend, mesh, W)
            R = merge_lib.shard_rows(E, W)
            params = _pad_ent_tables(model, params, W * R)

    with obs.span("eval.ranks"):
        if sharded:
            outs = _entity_ranks_sharded(
                model, params, q[0], tc[0], hc[0], norm=norm,
                backend=backend, mesh=mesh, axis_name="workers",
                n_shards=W, n_entities=E, relations=relations)
        else:
            outs = _entity_ranks_device(
                model, params, q, tc, hc, norm=norm, backend=backend,
                mesh=mesh, axis_name="workers", fused=fused,
                relations=relations)
        out = {"raw_ranks": {
            "tail": _unshard(outs["tail_raw"], Q),
            "head": _unshard(outs["head_raw"], Q),
        }}
        if cand_masks is not None:
            out["filtered_ranks"] = {
                "tail": _unshard(outs["tail_filtered"], Q),
                "head": _unshard(outs["head_filtered"], Q),
            }
        if relations:
            out["relation_ranks"] = _unshard(outs["relation"], Q)
    return out


def entity_inference_device(
    params: Params,
    test: np.ndarray,
    norm: str = "l1",
    cand_masks: Optional[Tuple[np.ndarray, np.ndarray]] = None,
    *,
    model: "str | KGModel" = "transe",
    chunk: Optional[int] = None,
    n_workers: int = 1,
    backend: str = "vmap",
    mesh=None,
    fused: Optional[bool] = None,
    table_sharding: str = "replicated",
) -> Dict[str, RankMetrics]:
    """Device-engine entity inference: raw (and, with ``cand_masks``,
    filtered) metrics identical to the host reference."""
    ranks = entity_ranks_device(
        params, test, norm, cand_masks, model=model, chunk=chunk,
        n_workers=n_workers, backend=backend, mesh=mesh, fused=fused,
        table_sharding=table_sharding)
    raw = ranks["raw_ranks"]
    out = {"raw": host_eval._metrics_from_ranks(
        np.concatenate([raw["tail"], raw["head"]]))}
    if cand_masks is not None:
        filt = ranks["filtered_ranks"]
        out["filtered"] = host_eval._metrics_from_ranks(
            np.concatenate([filt["tail"], filt["head"]]))
    return out


def _resolve_fused(model: KGModel, fused: Optional[bool]) -> bool:
    """``fused=None`` -> the Pallas ``rank_topk`` path iff the model has one
    and we are on TPU (kernels/ops dispatch rule).  Off TPU the pure-jnp
    path is both faster (no interpret-mode overhead) and exactly
    host-parity.  An explicit ``fused=True`` is a hard request: models
    without a kernel raise instead of silently downgrading."""
    if fused is None:
        from repro.kernels import ops

        return ops.fused_eval_available(model)
    if fused and not model.supports_fused_kernel:
        raise ValueError(
            f"fused=True but model {model.name!r} has no fused Pallas "
            "kernel (supports_fused_kernel is False) — drop fused or "
            "implement fused_rank_counts")
    return bool(fused)


# ---------------------------------------------------------------------------
# Relation prediction
# ---------------------------------------------------------------------------

@functools.partial(
    jax.jit, static_argnames=("model", "norm", "backend", "axis_name", "mesh"))
def _relation_ranks_device(
    model: KGModel,
    params: Params,
    queries: jax.Array,      # (W, S, C, 3)
    *,
    norm: str,
    backend: str,
    mesh,
    axis_name: str,
) -> jax.Array:
    def per_worker(params, q_w):
        def body(_, q):
            return None, _relation_ranks(model, params, q, norm)

        _, ranks = jax.lax.scan(body, None, q_w)
        return ranks

    run = worker_map(
        per_worker, backend=backend, mesh=mesh, axis_name=axis_name)
    return run(params, queries)


def relation_prediction_device(
    params: Params,
    test: np.ndarray,
    norm: str = "l1",
    *,
    model: "str | KGModel" = "transe",
    chunk: int = 512,
    n_workers: int = 1,
    backend: str = "vmap",
    mesh=None,
    return_ranks: bool = False,
):
    """Rank the gold relation among all relations, scanned on device."""
    model = get_model(model)
    test = np.asarray(test, np.int32)
    Q = len(test)
    S, C, Qp = _layout(Q, chunk, n_workers)
    q = _shard(_pad_rows(test, Qp), n_workers, S, C)
    ranks = _unshard(
        _relation_ranks_device(
            model, params, q, norm=norm, backend=backend, mesh=mesh,
            axis_name="workers"),
        Q)
    metrics = host_eval._metrics_from_ranks(ranks)
    return (metrics, ranks) if return_ranks else metrics


# ---------------------------------------------------------------------------
# Triplet classification
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("model", "norm"))
@obs.scope("eval.classify")
def _tc_scores(model: KGModel, params: Params, triplets: jax.Array, norm: str):
    return model.energy(params, triplets, norm)


def triplet_classification_device(
    params: Params,
    valid: np.ndarray,
    test: np.ndarray,
    n_entities: int,
    norm: str = "l1",
    seed: int = 0,
    model: "str | KGModel" = "transe",
    negatives: Optional[tuple] = None,
) -> float:
    """Triplet classification with device-batched scoring: the four score
    vectors come from one jitted dispatch over the concatenated arrays;
    corruption draws and threshold fitting are byte-identical to the host
    engine (shared ``_tc_negatives`` / ``_threshold_accuracy``).
    ``negatives`` is the cached ``KG.tc_negatives(seed)`` pair —
    ``evaluate_all_device`` passes it so the per-Reduce in-loop eval skips
    the corruption dispatches."""
    model = get_model(model)
    with obs.span("eval.classify_host"):
        valid_neg, test_neg = (
            negatives if negatives is not None
            else host_eval._tc_negatives(valid, test, n_entities, seed))
        sections = np.cumsum([len(valid), len(valid_neg), len(test)])
        allt = jnp.asarray(
            np.concatenate([valid, valid_neg, test, test_neg], axis=0))
    scores = np.asarray(_tc_scores(model, params, allt, norm))
    sv_pos, sv_neg, st_pos, st_neg = np.split(scores, sections)
    with obs.span("eval.classify_host"):
        return host_eval._threshold_accuracy(
            sv_pos, sv_neg, st_pos, st_neg, valid, valid_neg, test,
            test_neg, int(params["rel"].shape[0]))


# ---------------------------------------------------------------------------
# The full protocol
# ---------------------------------------------------------------------------

def evaluate_all_device(
    params: Params,
    kg,
    norm: str = "l1",
    filtered: bool = True,
    model: "str | KGModel" = "transe",
    *,
    chunk: Optional[int] = None,
    n_workers: int = 1,
    backend: str = "vmap",
    mesh=None,
    fused: Optional[bool] = None,
    max_fanout: Optional[int] = None,
    table_sharding: str = "replicated",
) -> Dict[str, object]:
    """All three paper tasks on the device engine — same output dict as the
    host ``evaluate_all`` (which dispatches here for ``engine="device"``).

    The two ranking tasks run as ONE fused scan over the test queries
    (``entity_ranks_device(relations=True)``): each chunk scores both
    entity sides *and* all relations, so the protocol makes a single pass
    over the query layout — this is the engine the in-training evaluation
    loop (``core/trace.py``) runs at every Reduce boundary.

    ``chunk`` queries (default :func:`chunk_rows`) are scored per scan
    step, split over ``n_workers``
    along the query axis (``backend="vmap"`` on one device,
    ``"shard_map"`` over a real mesh axis — pass ``mesh``).  ``fused``
    forces the Pallas ``rank_topk`` path on or off (default: auto).
    ``max_fanout`` caps the padded filter-mask width
    (``KG.eval_filter_candidates``); leave ``None`` for exact filtering.
    ``table_sharding="sharded"`` swaps in the shard-local candidate scan
    (exact cross-shard combine — metrics unchanged bitwise)."""
    model = get_model(model)
    masks = None
    if filtered:
        with obs.span("eval.masks"):
            masks = kg.eval_filter_candidates(max_fanout)
        cells, known = kg.eval_filter_counts(max_fanout)
        obs.count("eval.filter_cells", cells)
        obs.count("eval.filter_known", known)
    ranks = entity_ranks_device(
        params, kg.test, norm, masks, model=model, chunk=chunk,
        n_workers=n_workers, backend=backend, mesh=mesh, fused=fused,
        relations=True, table_sharding=table_sharding)
    with obs.span("eval.classify_host"):
        negatives = kg.tc_negatives(0)
    tc = triplet_classification_device(
        params, kg.valid, kg.test, kg.n_entities, norm, model=model,
        negatives=negatives,
    )
    raw = ranks["raw_ranks"]
    with obs.span("eval.metrics"):
        out = {
            "entity_raw": host_eval._metrics_from_ranks(
                np.concatenate([raw["tail"], raw["head"]])).row(),
            "relation_prediction": host_eval._metrics_from_ranks(
                ranks["relation_ranks"]).row(),
            "triplet_classification_acc": tc,
        }
        if filtered:
            filt = ranks["filtered_ranks"]
            out["entity_filtered"] = host_eval._metrics_from_ranks(
                np.concatenate([filt["tail"], filt["head"]])).row()
    return out
