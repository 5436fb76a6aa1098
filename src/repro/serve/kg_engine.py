"""Device-resident KG link-prediction query engine.

The paper *evaluates* entity inference and relation prediction; a deployed
knowledge repository *serves* them — "which tails complete (h, r, ?)?" at
traffic rates, the DGL-KE-style artifact the ROADMAP north star needs.
This module is the serving face of the PR 3 device eval engine: a batch of
queries runs as **one compiled top-k computation** instead of a per-query
host loop.

How a query batch runs (``query_tails`` / ``query_heads``):

  * Queries are padded and laid out ``(W, S, C, 2)`` exactly like the eval
    engine's test split (``core/eval_device._layout``): ``W`` workers —
    the same vmap / shard_map backends, via ``parallel/util.worker_map`` —
    each scan ``S`` chunks of ``C`` queries.
  * Every chunk scores all E entities through the model's
    ``candidate_energies`` (the same closed forms eval uses), masks
    excluded candidates to +inf via the padded-id scatter trick the eval
    filter uses (pad id = E never lands; serve-time exclusion = the KG's
    ``known_candidate_masks``), and extracts ``jax.lax.top_k`` ids +
    energies on device.  Only the final ``(B, k)`` grids return to host.
  * ``query_relations`` is the same scan over ``relation_energies``.

Rank parity: ``rank()`` routes ad-hoc triplet batches through the *eval*
engine's scan (``core/eval_device.entity_ranks_device``), including its
``kernels/rank_topk`` fused dispatch on TPU — so the rank a served
candidate would get is bit-identical to what ``kg.evaluate`` reports for
the same query (tests/test_kb.py proves top-k-derived ranks equal the
eval rank vectors, raw and filtered).

Energies are "lower = truer" throughout (as everywhere in the repo):
result ids come back best-first with their energies; excluded or padded
candidates surface as +inf energies when ``k`` exceeds the live
candidate count.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import eval_device
from repro.core import merge as merge_lib
from repro.core.models import KGModel, Params, get_model
from repro.parallel.util import worker_map


@dataclasses.dataclass(frozen=True)
class QueryResult:
    """One batched top-k answer: ``ids[i, j]`` is the j-th best candidate
    for query ``i`` and ``energies[i, j]`` its model energy (ascending per
    row — best first; +inf marks exhausted/excluded slots)."""

    ids: np.ndarray        # (B, k) int32
    energies: np.ndarray   # (B, k) float32


def _unshard_k(out: jax.Array, n: int) -> np.ndarray:
    """(W, S, C, k) grid -> (n, k) host array in original query order."""
    arr = np.asarray(out)
    return arr.reshape(-1, arr.shape[-1])[:n]


@functools.partial(
    jax.jit,
    static_argnames=(
        "model", "side", "norm", "k", "backend", "mesh", "axis_name"),
)
def _entity_topk_device(
    model: KGModel,
    params: Params,
    queries: jax.Array,      # (W, S, C, 3)
    exclude: jax.Array,      # (W, S, C, P) padded candidate ids (pad id = E)
    *,
    side: str,
    norm: str,
    k: int,
    backend: str,
    mesh,
    axis_name: str,
):
    """Top-k (ids, energies) over all entities for every query — one
    compiled scan, query axis sharded over workers."""

    def per_worker(params, q_w, ex_w):
        def body(_, inp):
            q, ex = inp
            scores = model.candidate_energies(params, q, side, norm)
            E = scores.shape[1]
            # mask excluded ids to +inf: pad entries (>= E) clamp to a real
            # column but scatter -inf, and .max() with -inf is the identity
            rows = jnp.arange(q.shape[0])[:, None]
            cols = jnp.minimum(ex, E - 1)
            upd = jnp.where(ex < E, jnp.inf, -jnp.inf)
            scores = scores.at[rows, cols].max(upd)
            neg, ids = jax.lax.top_k(-scores, k)
            return None, (ids.astype(jnp.int32), -neg)

        _, out = jax.lax.scan(body, None, (q_w, ex_w))
        return out               # each (S, C, k)

    run = worker_map(
        per_worker, backend=backend, mesh=mesh, axis_name=axis_name)
    return run(params, queries, exclude)


@functools.partial(
    jax.jit,
    static_argnames=(
        "model", "side", "norm", "k", "backend", "mesh", "axis_name",
        "n_shards", "n_entities"),
)
def _entity_topk_sharded(
    model: KGModel,
    params: Params,          # entity-role tables padded to n_shards * R
    queries: jax.Array,      # (S, C, 3) — queries replicated, not split
    exclude: jax.Array,      # (S, C, P) padded candidate ids (pad id = E)
    *,
    side: str,
    norm: str,
    k: int,
    backend: str,
    mesh,
    axis_name: str,
    n_shards: int,
    n_entities: int,
):
    """``_entity_topk_device`` with the candidate axis sharded: each shard
    scans only its contiguous block of ``R = shard_rows(E, W)`` entity
    rows (``candidate_slice_energies``), takes a local
    ``top_k(min(k, R))``, and the per-shard lists combine *shard-major*
    into one ``(C, W*kk)`` union re-top_k'd to ``k``.

    The combine is tie-break exact, not just value exact: ``lax.top_k``
    breaks energy ties toward the lowest index, the union's shard-major
    order is globally id-ascending within any tie class (shards hold
    ascending id ranges; local lists are id-ascending within ties), and
    every candidate the full-table top-k would pick survives its local
    cut (at most k-1 candidates precede it anywhere, so certainly within
    its own shard — and ``kk = R`` keeps whole shards when k exceeds R).
    Padded rows (id >= E) read +inf before the local cut and excluded ids
    are masked by the single shard that owns them, exactly as the
    replicated scan does — so ids *and* energies are bitwise the
    replicated answer (tests/test_sharded_tables.py)."""
    E, W = n_entities, n_shards
    R = merge_lib.shard_rows(E, W)
    kk = min(k, R)
    cdtype = queries.dtype

    def local_topk(params, q, ex, lo):
        s = model.candidate_slice_energies(params, q, side, norm, lo=lo, n=R)
        col = lo + jnp.arange(R, dtype=cdtype)
        s = jnp.where(col[None, :] >= E, jnp.inf, s)
        # exclusion scatter, shard-local: ids outside [lo, lo+R) (and pad
        # ids >= E) clamp to a real column but scatter -inf — the identity
        rows = jnp.arange(q.shape[0])[:, None]
        off = ex - lo
        valid = (off >= 0) & (off < R) & (ex < E)
        cols = jnp.clip(off, 0, R - 1)
        upd = jnp.where(valid, jnp.inf, -jnp.inf)
        s = s.at[rows, cols].max(upd)
        neg, idx = jax.lax.top_k(-s, kk)
        return (lo + idx).astype(jnp.int32), -neg      # (C, kk) each

    def combine(ids_all, en_all):
        # (W, C, kk), shard-major union: (C, W * kk)
        C = ids_all.shape[1]
        ids_u = jnp.moveaxis(ids_all, 0, 1).reshape(C, W * kk)
        en_u = jnp.moveaxis(en_all, 0, 1).reshape(C, W * kk)
        neg, j = jax.lax.top_k(-en_u, k)
        return jnp.take_along_axis(ids_u, j, axis=1), -neg

    if backend == "vmap":
        los = (jnp.arange(W, dtype=cdtype) * R).astype(cdtype)

        def body(_, inp):
            q, ex = inp
            ids_all, en_all = jax.vmap(
                lambda lo: local_topk(params, q, ex, lo))(los)
            return None, combine(ids_all, en_all)

        _, out = jax.lax.scan(body, None, (queries, exclude))
        return out                   # each (S, C, k)

    def per_shard(params, q_all, ex_all):
        lo = (jax.lax.axis_index(axis_name) * R).astype(cdtype)

        def body(_, inp):
            q, ex = inp
            ids, en = local_topk(params, q, ex, lo)
            # every shard gathers all local lists (axis order = shard
            # order) and runs the identical combine — outputs replicated
            ids_all = jax.lax.all_gather(ids, axis_name)
            en_all = jax.lax.all_gather(en, axis_name)
            return None, combine(ids_all, en_all)

        _, out = jax.lax.scan(body, None, (q_all, ex_all))
        return out

    fn = jax.shard_map(
        per_shard, mesh=mesh,
        in_specs=(P(), P(), P()), out_specs=P(), check_vma=False)
    return fn(params, queries, exclude)


@functools.partial(
    jax.jit,
    static_argnames=("model", "norm", "k", "backend", "mesh", "axis_name"))
def _relation_topk_device(
    model: KGModel,
    params: Params,
    queries: jax.Array,      # (W, S, C, 3)
    *,
    norm: str,
    k: int,
    backend: str,
    mesh,
    axis_name: str,
):
    def per_worker(params, q_w):
        def body(_, q):
            scores = model.relation_energies(params, q, norm)
            neg, ids = jax.lax.top_k(-scores, k)
            return None, (ids.astype(jnp.int32), -neg)

        _, out = jax.lax.scan(body, None, q_w)
        return out

    run = worker_map(
        per_worker, backend=backend, mesh=mesh, axis_name=axis_name)
    return run(params, queries)


@functools.partial(jax.jit, static_argnames=("model", "norm"))
def _score_device(model: KGModel, params: Params, triplets, norm: str):
    return model.energy(params, triplets, norm)


class KGQueryEngine:
    """Batched link-prediction over one (model, params) pair.

    ``n_workers`` shards the query axis (``backend='vmap'`` on a single
    device, ``'shard_map'`` over a real mesh axis — pass ``mesh``); any
    batch size works, the layout pads to worker x chunk granularity the
    way the eval engine does.  The engine is stateless apart from the
    tables — jit caches key on (model, norm, k, layout statics), so
    repeated traffic with the same shape is one dispatch per batch.

    ``exclude`` masks are padded ``(B, P)`` id arrays (pad id =
    n_entities), the exact layout ``KG.known_candidate_masks`` /
    ``KG.eval_filter_candidates`` build — ``KnowledgeBase`` passes known
    neighbors here so served candidates are *new* links.

    ``table_sharding="sharded"`` swaps the full-table scan for the
    shard-local candidate scan + cross-shard top-k combine
    (``_entity_topk_sharded``): ``n_workers`` becomes the shard count
    over the *entity* axis (queries stay whole), and answers — ids and
    energies — are bitwise the replicated engine's.
    """

    def __init__(
        self,
        model: "str | KGModel",
        params: Params,
        *,
        norm: str = "l1",
        n_workers: int = 1,
        backend: str = "vmap",
        mesh=None,
        chunk: Optional[int] = None,
        table_sharding: str = "replicated",
    ):
        if table_sharding not in ("replicated", "sharded"):
            raise ValueError(
                f"table_sharding must be 'replicated' or 'sharded', got "
                f"{table_sharding!r}")
        self.model = get_model(model)
        self.params = params
        self.norm = norm
        self.n_workers = n_workers
        self.backend = backend
        self.mesh = mesh
        # queries per scan step: the eval engine's row-byte rule unless set
        self.chunk = eval_device.chunk_rows(params) if chunk is None else chunk
        self.table_sharding = table_sharding
        self.n_entities = int(params["ent"].shape[0])
        self.n_relations = int(params["rel"].shape[0])
        if table_sharding == "sharded":
            eval_device._check_sharded_mesh(backend, mesh, n_workers)
            R = merge_lib.shard_rows(self.n_entities, n_workers)
            # pad once at construction; rank()/score() keep the original
            self._padded_params = eval_device._pad_ent_tables(
                self.model, params, n_workers * R)
        else:
            self._padded_params = None

    # -- layout helpers (shared with the eval engine) ----------------------

    def _shard_queries(self, triplets: np.ndarray, exclude,
                       chunk: Optional[int] = None,
                       split_queries: bool = True):
        Q = len(triplets)
        # sharded tables keep every query on every shard (W=1 layout):
        # the entity axis, not the query axis, is what splits W ways
        W = self.n_workers if split_queries else 1
        S, C, Qp = eval_device._layout(
            Q, self.chunk if chunk is None else chunk, W)
        q = eval_device._shard(
            eval_device._pad_rows(np.asarray(triplets, np.int32), Qp),
            W, S, C)
        if exclude is None:
            exclude = np.full((Q, 1), self.n_entities, np.int32)
        ex = eval_device._shard(
            eval_device._pad_rows(np.asarray(exclude, np.int32), Qp),
            W, S, C)
        return q, ex, Q

    @staticmethod
    def _pair_triplets(a, b, side: str) -> np.ndarray:
        a = np.atleast_1d(np.asarray(a, np.int32))
        b = np.atleast_1d(np.asarray(b, np.int32))
        a, b = np.broadcast_arrays(a, b)
        zero = np.zeros_like(a)
        if side == "tail":              # (h, r, ?) — gold slot unused
            cols = (a, b, zero)
        elif side == "head":            # (?, r, t)
            cols = (zero, b, a)
        else:                           # (h, ?, t) for relation queries
            cols = (a, zero, b)
        return np.stack(cols, axis=1)

    # -- queries -----------------------------------------------------------

    def query_tails(self, heads, rels, k: int = 10,
                    exclude: Optional[np.ndarray] = None,
                    chunk: Optional[int] = None) -> QueryResult:
        """Top-k tail completions of ``(h, r, ?)`` for a batch of (heads,
        rels) id arrays.  ``exclude`` drops known candidates (padded id
        rows; see class docstring).  ``chunk`` overrides the engine's
        per-scan-step chunk for this call — ``KGServer`` passes its padded
        bucket size here so every admitted wave lands on a pre-compiled
        ``(W, 1, bucket, ...)`` shape instead of the engine's default
        eval-sized layout."""
        return self._entity_topk(
            self._pair_triplets(heads, rels, "tail"), "tail", k, exclude,
            chunk)

    def query_heads(self, tails, rels, k: int = 10,
                    exclude: Optional[np.ndarray] = None,
                    chunk: Optional[int] = None) -> QueryResult:
        """Top-k head completions of ``(?, r, t)``."""
        return self._entity_topk(
            self._pair_triplets(tails, rels, "head"), "head", k, exclude,
            chunk)

    def _entity_topk(self, triplets, side, k, exclude,
                     chunk: Optional[int] = None) -> QueryResult:
        k = min(int(k), self.n_entities)
        if self.table_sharding == "sharded":
            q, ex, Q = self._shard_queries(
                triplets, exclude, chunk, split_queries=False)
            ids, energies = _entity_topk_sharded(
                self.model, self._padded_params, q[0], ex[0], side=side,
                norm=self.norm, k=k, backend=self.backend, mesh=self.mesh,
                axis_name="workers", n_shards=self.n_workers,
                n_entities=self.n_entities)
        else:
            q, ex, Q = self._shard_queries(triplets, exclude, chunk)
            ids, energies = _entity_topk_device(
                self.model, self.params, q, ex, side=side, norm=self.norm,
                k=k, backend=self.backend, mesh=self.mesh,
                axis_name="workers")
        return QueryResult(_unshard_k(ids, Q), _unshard_k(energies, Q))

    def query_relations(self, heads, tails, k: int = 10,
                        chunk: Optional[int] = None) -> QueryResult:
        """Top-k relations linking ``(h, ?, t)`` pairs."""
        k = min(int(k), self.n_relations)
        triplets = self._pair_triplets(heads, tails, "relation")
        q, _, Q = self._shard_queries(triplets, None, chunk)
        ids, energies = _relation_topk_device(
            self.model, self.params, q, norm=self.norm, k=k,
            backend=self.backend, mesh=self.mesh, axis_name="workers")
        return QueryResult(_unshard_k(ids, Q), _unshard_k(energies, Q))

    def score(self, heads, rels, tails) -> np.ndarray:
        """Model energies of fully-specified ``(h, r, t)`` triplets
        (lower = more plausible), one jitted dispatch per batch."""
        h = np.atleast_1d(np.asarray(heads, np.int32))
        r = np.atleast_1d(np.asarray(rels, np.int32))
        t = np.atleast_1d(np.asarray(tails, np.int32))
        h, r, t = np.broadcast_arrays(h, r, t)
        triplets = jnp.asarray(np.stack([h, r, t], axis=1))
        return np.asarray(
            _score_device(self.model, self.params, triplets, self.norm))

    def rank(
        self,
        triplets: np.ndarray,
        side: str = "tail",
        cand_masks=None,
        fused: Optional[bool] = None,
    ) -> np.ndarray:
        """Rank the gold entity of each ``(h, r, t)`` among all entities —
        the *eval* engine's scan (including its fused ``rank_topk``
        dispatch on TPU), so a served candidate's rank is bit-identical to
        what ``kg.evaluate`` would report.  ``cand_masks`` applies the
        filtered-ranking correction (a padded id array as in
        ``KG.eval_filter_candidates``, one-sided)."""
        # the eval scan computes both sides; feed the one-sided mask to
        # both and read back only the requested side
        masks = None if cand_masks is None else (cand_masks, cand_masks)
        out = eval_device.entity_ranks_device(
            self.params, np.asarray(triplets, np.int32), self.norm, masks,
            model=self.model, chunk=self.chunk, n_workers=self.n_workers,
            backend=self.backend, mesh=self.mesh, fused=fused,
            table_sharding=self.table_sharding)
        group = "filtered_ranks" if cand_masks is not None else "raw_ranks"
        return out[group][side]
