"""Knowledge-graph data pipeline.

The paper trains on Freebase/NELL subsets (WN100K / FB150K); this container
has no network access, so we ship (a) a loader for the standard triplet TSV
format those datasets use (``head\trelation\ttail`` per line, id-mapped) and
(b) a synthetic *planted-translation* generator whose ground truth actually
satisfies the TransE assumption — entities get latent positions, relations
get latent translation vectors, and triplets are generated where
``z_h + g_r ≈ z_t``.  Ranking metrics on it are therefore meaningful: a model
that learns the structure ranks gold entities highly, a broken one does not.

Also here: the paper's *balanced subsets* partitioning for the Map phase and
two epoch-batching pipelines, both deterministic (restart-safe: batches are a
pure function of (seed, epoch)):

  * ``epoch_batches``        — the **host** pipeline: numpy permutations,
    one ``(W, S, B, 3)`` array transferred to device per epoch.  Kept for
    the ``repro.core.transe`` bit-for-bit shim and as the reference.
  * ``device_epoch_batches`` / ``device_worker_batches`` — the **device**
    pipeline: per-worker permutations drawn from ``fold_in`` keys entirely
    on device, so the scanned epoch driver (``core/mapreduce.py``) never
    round-trips to the host between epochs.
  * ``device_repartition`` / ``repartition_perm`` — on-device re-splitting
    of the triplets across workers every M epochs
    (``EpochSchedule.repartition_every``), removing the residual split
    bias of a partition frozen at ``train()`` start.
"""
from __future__ import annotations

import dataclasses
import hashlib
import os
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.util import warn_fresh


@dataclasses.dataclass
class KG:
    """A knowledge graph with a train/valid/test triplet split."""

    n_entities: int
    n_relations: int
    train: np.ndarray           # (N_tr, 3) int32 rows of (h, r, t)
    valid: np.ndarray
    test: np.ndarray

    # lazily built known-triplet structures (see known_set / known_index /
    # eval_filter_candidates); not part of the dataclass comparison/repr
    # surface
    _known: Optional[set] = dataclasses.field(
        default=None, repr=False, compare=False)
    _known_index: Optional[tuple] = dataclasses.field(
        default=None, repr=False, compare=False)
    _filter_cands: Dict = dataclasses.field(
        default_factory=dict, repr=False, compare=False)
    _filter_counts: Dict = dataclasses.field(
        default_factory=dict, repr=False, compare=False)
    _tc_negatives: Dict = dataclasses.field(
        default_factory=dict, repr=False, compare=False)

    @property
    def all_triplets(self) -> np.ndarray:
        return np.concatenate([self.train, self.valid, self.test], axis=0)

    def known_set(self) -> set:
        """Set of all true triplets — used for *filtered* ranking metrics.

        Built once and cached on the instance: ``evaluate_all`` calls this
        per evaluation, and rebuilding a multi-hundred-thousand-entry set of
        tuples each time dominated eval setup.  The splits are treated as
        immutable after construction (as everywhere else in the repo)."""
        if self._known is None:
            self._known = {tuple(t) for t in self.all_triplets.tolist()}
        return self._known

    def known_index(self) -> tuple:
        """``(by_hr, by_rt)`` group indices over :meth:`known_set`.

        ``by_hr[(h, r)]`` is the sorted list of known tails of ``(h, r)``;
        ``by_rt[(r, t)]`` the sorted known heads.  Built once and cached on
        the instance — this is the structure both eval engines filter with
        (the host reference walks the lists per query; the device engine
        flattens them into the padded masks of
        :meth:`eval_filter_candidates`)."""
        if self._known_index is None:
            by_hr: Dict[tuple, list] = {}
            by_rt: Dict[tuple, list] = {}
            for (h, r, t) in self.known_set():
                by_hr.setdefault((h, r), []).append(t)
                by_rt.setdefault((r, t), []).append(h)
            for d in (by_hr, by_rt):
                for k in d:
                    d[k].sort()
            self._known_index = (by_hr, by_rt)
        return self._known_index

    def eval_filter_candidates(
        self, max_fanout: Optional[int] = None
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Padded known-candidate id arrays for filtered ranking of the test
        split: ``(tail_cands, head_cands)``, each ``(n_test, P)`` int32,
        padded with ``n_entities`` (an out-of-table id the device engine maps
        to +inf energy).

        Row ``i`` of ``tail_cands`` holds the known tails of
        ``(h_i, r_i)`` — the entities the filtered metric must not count
        against query ``i`` — and ``head_cands`` likewise the known heads of
        ``(r_i, t_i)``.  ``P`` is the largest group size (so no information
        is lost by default); ``max_fanout`` caps it, trading exactness for a
        smaller device-resident mask — truncated rows keep their first
        ``max_fanout`` (sorted) candidates and the total dropped count is
        surfaced once as a warning (filtered ranks of affected queries
        become upper bounds).  Built once per ``max_fanout`` and cached on
        the instance."""
        if max_fanout not in self._filter_cands:
            by_hr, by_rt = self.known_index()
            tail_groups = [by_hr[(h, r)] for h, r, _ in self.test.tolist()]
            head_groups = [by_rt[(r, t)] for _, r, t in self.test.tolist()]
            tails, dropped_t = _pad_groups(
                tail_groups, self.n_entities, max_fanout)
            heads, dropped_h = _pad_groups(
                head_groups, self.n_entities, max_fanout)
            dropped = dropped_t + dropped_h
            if dropped:
                # warn_fresh, not warnings.warn: the process-wide registry
                # would swallow the report for every later graph/eval in
                # this process, though each drops its own counts
                warn_fresh(
                    f"max_fanout={max_fanout} truncates the filtered-known "
                    f"candidate masks: {dropped} known candidates dropped "
                    f"across {len(self.test)} test queries "
                    f"({dropped_t} tail-side, {dropped_h} head-side) — "
                    "filtered ranks of the affected queries become upper "
                    "bounds.  Raise max_fanout (or leave it None) for exact "
                    "filtering.", stacklevel=2)
            self._filter_cands[max_fanout] = (tails, heads)
            known = sum(map(len, tail_groups)) + sum(map(len, head_groups))
            self._filter_counts[max_fanout] = (
                tails.size + heads.size, known - dropped)
        return self._filter_cands[max_fanout]

    def eval_filter_counts(
        self, max_fanout: Optional[int] = None
    ) -> Tuple[int, int]:
        """``(cells, known)`` of the masks :meth:`eval_filter_candidates`
        builds: the padded cells one filtered pass scores,
        ``n_test × (P_tail + P_head)``, and how many of them hold a real
        known candidate.  Counted once, when the masks are built."""
        self.eval_filter_candidates(max_fanout)
        return self._filter_counts[max_fanout]

    def known_candidate_masks(
        self, pairs: np.ndarray, side: str
    ) -> np.ndarray:
        """Padded known-entity ids for arbitrary serve-time queries.

        ``pairs`` is ``(B, 2)``: ``(h, r)`` rows for ``side="tail"`` (known
        tails of each pair are returned) or ``(r, t)`` rows for
        ``side="head"`` (known heads).  Output is ``(B, P)`` int32 padded
        with ``n_entities`` — the same layout
        :meth:`eval_filter_candidates` builds for the test split, so the
        serving engine masks them out with the identical +inf gather the
        eval engine uses.  Pairs the graph has never seen get an all-pad
        row (nothing to exclude)."""
        if side not in ("tail", "head"):
            raise ValueError(f"bad side {side!r}")
        by_hr, by_rt = self.known_index()
        index = by_hr if side == "tail" else by_rt
        groups = [
            index.get((int(a), int(b)), [])
            for a, b in np.asarray(pairs, np.int64)
        ]
        return _pad_groups(groups, self.n_entities, None)[0]

    def fingerprint(self) -> Dict[str, object]:
        """Content identity of this graph: sizes plus a short sha256 of each
        split's triplet array.  Persisted in ``KnowledgeBase`` / training-
        checkpoint manifests so a resume or load against a *different* graph
        fails loudly instead of silently training on mismatched ids."""

        def digest(a: np.ndarray) -> str:
            a = np.ascontiguousarray(np.asarray(a, np.int32))
            return hashlib.sha256(a.tobytes()).hexdigest()[:16]

        return {
            "n_entities": self.n_entities,
            "n_relations": self.n_relations,
            "train": digest(self.train),
            "valid": digest(self.valid),
            "test": digest(self.test),
        }

    def tc_negatives(self, seed: int = 0) -> Tuple[np.ndarray, np.ndarray]:
        """Corrupted valid/test counterparts for triplet classification,
        built once per seed and cached on the instance.

        The draws are exactly ``core/eval._tc_negatives`` (both engines'
        exact-parity contract depends on them) — a pure function of
        (valid, test, n_entities, seed), so caching cannot change any
        metric.  The in-training evaluation loop calls the full protocol
        every Reduce round; rebuilding these corruption dispatches per call
        dominated triplet-classification cost."""
        if seed not in self._tc_negatives:
            from repro.core import eval as kg_eval

            self._tc_negatives[seed] = kg_eval._tc_negatives(
                self.valid, self.test, self.n_entities, seed)
        return self._tc_negatives[seed]

    def invalidate_caches(self) -> None:
        """Drop every lazily built known-triplet structure.

        The splits are treated as immutable after construction everywhere
        in the repo, so the caches never go stale on the supported paths —
        but anything that *does* mutate a graph in place (don't) must call
        this, or filtered ranks and classification negatives keep using
        pre-mutation candidate sets.  The online tier never needs it: a
        graph update goes through :meth:`extend`, which returns a fresh
        instance with fresh caches."""
        self._known = None
        self._known_index = None
        self._filter_cands = {}
        self._filter_counts = {}
        self._tc_negatives = {}

    def extend(
        self,
        new_train: np.ndarray,
        n_entities: Optional[int] = None,
        n_relations: Optional[int] = None,
    ) -> "KG":
        """A **new** graph with ``new_train`` appended to the train split.

        Entity/relation counts grow to cover every id the delta references
        (or to the explicit ``n_entities``/``n_relations`` the online
        tier's interning already computed).  Returning a fresh instance —
        never mutating — is what keeps the lazy eval caches and the
        :meth:`fingerprint` honest: the extended graph starts with empty
        caches and a different train digest, so filtered ranks, tc
        negatives, and the serving tier's answer cache can never reuse
        pre-update state."""
        new_train = np.asarray(new_train, np.int32).reshape(-1, 3)
        n_ent, n_rel = self.n_entities, self.n_relations
        if len(new_train):
            n_ent = max(n_ent,
                        int(new_train[:, (0, 2)].max()) + 1)
            n_rel = max(n_rel, int(new_train[:, 1].max()) + 1)
        if n_entities is not None:
            if n_entities < n_ent:
                raise ValueError(
                    f"n_entities={n_entities} does not cover the delta's "
                    f"max entity id ({n_ent - 1})")
            n_ent = n_entities
        if n_relations is not None:
            if n_relations < n_rel:
                raise ValueError(
                    f"n_relations={n_relations} does not cover the delta's "
                    f"max relation id ({n_rel - 1})")
            n_rel = n_relations
        return KG(
            n_entities=n_ent,
            n_relations=n_rel,
            train=np.concatenate([self.train, new_train], axis=0),
            valid=self.valid,
            test=self.test,
        )


def _pad_groups(
    groups: list, pad_id: int, max_fanout: Optional[int]
) -> Tuple[np.ndarray, int]:
    """Dense ``(len(groups), P)`` int32 array from ragged id lists, padded
    with ``pad_id``; returns the array and the count of ids dropped by the
    ``max_fanout`` cap."""
    widest = max((len(g) for g in groups), default=0)
    P = widest if max_fanout is None else min(widest, max_fanout)
    P = max(P, 1)
    out = np.full((len(groups), P), pad_id, np.int32)
    dropped = 0
    for i, g in enumerate(groups):
        n = len(g)
        if n > P:
            dropped += n - P
            n = P
        out[i, :n] = g[:n]
    return out, dropped


# ---------------------------------------------------------------------------
# Loading (Freebase/NELL-style TSV)
# ---------------------------------------------------------------------------

def load_tsv_dir(path: str) -> KG:
    """Load ``train.txt``/``valid.txt``/``test.txt`` of ``h\tr\tt`` string
    triplets (the FB15k / WN18 / NELL release layout), building id maps."""
    ent2id: Dict[str, int] = {}
    rel2id: Dict[str, int] = {}

    def get(d: Dict[str, int], k: str) -> int:
        if k not in d:
            d[k] = len(d)
        return d[k]

    def read(fname: str) -> np.ndarray:
        rows = []
        full = os.path.join(path, fname)
        if not os.path.exists(full):
            return np.zeros((0, 3), np.int32)
        with open(full) as f:
            for line in f:
                parts = line.rstrip("\n").split("\t")
                if len(parts) != 3:
                    continue
                h, r, t = parts
                rows.append((get(ent2id, h), get(rel2id, r), get(ent2id, t)))
        return np.asarray(rows, np.int32)

    train = read("train.txt")
    valid = read("valid.txt")
    test = read("test.txt")
    return KG(len(ent2id), len(rel2id), train, valid, test)


# ---------------------------------------------------------------------------
# Synthetic planted-translation KG
# ---------------------------------------------------------------------------

def synthetic_kg(
    seed: int,
    n_entities: int = 2000,
    n_relations: int = 20,
    n_triplets: int = 20000,
    latent_dim: int = 16,
    noise: float = 0.05,
    valid_frac: float = 0.05,
    test_frac: float = 0.05,
) -> KG:
    """Generate a KG whose triplets satisfy ``z_h + g_r ≈ z_t`` by
    construction.

    Entities live on the unit sphere in ``latent_dim``; each relation is a
    random small translation.  For each triplet we sample (h, r), displace,
    add noise, and connect to the nearest entity — so the translation
    structure TransE assumes is genuinely present and recoverable.
    """
    rng = np.random.default_rng(seed)
    z = rng.normal(size=(n_entities, latent_dim)).astype(np.float32)
    z /= np.linalg.norm(z, axis=1, keepdims=True)
    g = rng.normal(scale=0.5, size=(n_relations, latent_dim)).astype(np.float32)

    # over-sample then dedupe to hit the requested count
    n_draw = int(n_triplets * 1.6)
    h = rng.integers(0, n_entities, size=n_draw)
    r = rng.integers(0, n_relations, size=n_draw)
    target = z[h] + g[r] + rng.normal(scale=noise, size=(n_draw, latent_dim))
    # nearest entity by blocked L2 search: |tb|² - 2 tb·z + |z|², built in
    # one (block, E) buffer per block (in place, so the blocks stay in
    # cache — the same float64 operations in the same order as the plain
    # expression, hence the same argmin)
    t = np.empty((n_draw,), np.int64)
    zz = np.sum(z * z, axis=1)[None, :]
    block = 256
    for i in range(0, n_draw, block):
        tb = target[i : i + block]
        d = (2.0 * tb) @ z.T
        np.subtract(np.sum(tb * tb, axis=1, keepdims=True), d, out=d)
        d += zz
        t[i : i + block] = np.argmin(d, axis=1)

    triplets = np.stack([h, r, t], axis=1).astype(np.int32)
    triplets = triplets[triplets[:, 0] != triplets[:, 2]]        # no self loops
    triplets = np.unique(triplets, axis=0)
    rng.shuffle(triplets)
    triplets = triplets[:n_triplets]

    n_valid = int(len(triplets) * valid_frac)
    n_test = int(len(triplets) * test_frac)
    valid, test, train = (
        triplets[:n_valid],
        triplets[n_valid : n_valid + n_test],
        triplets[n_valid + n_test :],
    )
    return KG(n_entities, n_relations, train, valid, test)


# ---------------------------------------------------------------------------
# Balanced partitioning (the paper's "several balanced subsets")
# ---------------------------------------------------------------------------

def partition_balanced(
    seed: int, triplets: np.ndarray, n_workers: int
) -> np.ndarray:
    """Shuffle + round-robin split into ``n_workers`` equal subsets.

    Returns a dense ``(W, N//W, 3)`` array (tail remainder dropped so every
    worker gets identical step counts — the paper's balance requirement;
    at most W-1 triplets are dropped per epoch and the shuffle re-draws them
    across epochs)."""
    rng = np.random.default_rng(seed)
    perm = rng.permutation(len(triplets))
    per = len(triplets) // n_workers
    idx = perm[: per * n_workers].reshape(n_workers, per)
    return triplets[idx]


def partition_stratified(
    seed: int, triplets: np.ndarray, n_workers: int
) -> np.ndarray:
    """Relation-stratified balanced split: each worker sees (approximately)
    the full relation distribution — reduces merge conflict severity for
    relation embeddings (beyond-paper option, benchmarked)."""
    rng = np.random.default_rng(seed)
    order = np.lexsort((rng.random(len(triplets)), triplets[:, 1]))
    per = len(triplets) // n_workers
    chunks = [order[w::n_workers][:per] for w in range(n_workers)]
    return triplets[np.stack(chunks)]


def entity_degrees(triplets: np.ndarray, n_entities: int) -> np.ndarray:
    """Per-entity degree (head + tail occurrences) over a triplet set."""
    t = np.asarray(triplets)
    deg = np.bincount(t[:, 0], minlength=n_entities)
    deg += np.bincount(t[:, 2], minlength=n_entities)
    return deg[:n_entities].astype(np.int64)


def triplet_strata(
    triplets: np.ndarray, n_entities: int, n_buckets: int = 8
) -> np.ndarray:
    """Quantile-bucket each triplet by its degree score ``deg[h] + deg[t]``.

    The strata labels (int32, ``(N,)``) drive the degree-stratified
    partitioner: splitting each bucket evenly across workers gives every
    worker the same hub/tail-entity mix, so no worker's subset is dominated
    by high-conflict hub rows (DGL-KE's motivation for degree-aware
    splits).  Bucket edges are degree-score quantiles of *this* triplet
    set, so the labels are a pure function of the triplets."""
    t = np.asarray(triplets)
    if len(t) == 0:
        return np.zeros((0,), np.int32)
    deg = entity_degrees(t, n_entities)
    score = deg[t[:, 0]] + deg[t[:, 2]]
    edges = np.quantile(score, np.linspace(0, 1, n_buckets + 1)[1:-1])
    return np.searchsorted(edges, score, side="right").astype(np.int32)


def partition_degree_stratified(
    seed: int, triplets: np.ndarray, n_workers: int, n_buckets: int = 8
) -> np.ndarray:
    """Degree-stratified balanced split: bucket triplets by degree score
    (``triplet_strata``) and round-robin each bucket across workers, so
    hub-entity triplets — the rows every worker's merge fights over — are
    spread evenly instead of landing on whichever worker the shuffle chose.
    Same shuffle-within-stratum + ``order[w::W]`` idiom as
    :func:`partition_stratified`, keyed on degree instead of relation."""
    t = np.asarray(triplets)
    n_entities = int(t[:, [0, 2]].max()) + 1 if len(t) else 0
    strata = triplet_strata(t, n_entities, n_buckets)
    rng = np.random.default_rng(seed)
    order = np.lexsort((rng.random(len(t)), strata))
    per = len(t) // n_workers
    chunks = [order[w::n_workers][:per] for w in range(n_workers)]
    return t[np.stack(chunks)]


def partition_overlap_min(
    seed: int, triplets: np.ndarray, n_workers: int
) -> np.ndarray:
    """Overlap-minimizing balanced split (greedy streaming LDG).

    Each triplet goes to the worker that already holds the most triplets
    touching its head/tail entities (affinity), minus a load penalty, under
    a hard per-worker cap of ``N // W`` — fewer entities shared across
    workers means fewer conflicting rows at Reduce time.  Deterministic in
    ``seed`` (stream order is a seeded shuffle; argmax ties break to the
    lowest worker id).  Host-side O(N·W); intended for partition-quality
    experiments at bench scale, not million-triplet ingest."""
    t = np.asarray(triplets)
    rng = np.random.default_rng(seed)
    n_entities = int(t[:, [0, 2]].max()) + 1 if len(t) else 0
    per = len(t) // n_workers
    aff = np.zeros((n_entities, n_workers), np.float64)
    load = np.zeros(n_workers, np.int64)
    chunks: list[list[int]] = [[] for _ in range(n_workers)]
    assigned = 0
    for i in rng.permutation(len(t)):
        if assigned == per * n_workers:
            break
        h, tl = int(t[i, 0]), int(t[i, 2])
        score = aff[h] + aff[tl] - load / max(per, 1)
        score[load >= per] = -np.inf
        w = int(np.argmax(score))
        chunks[w].append(i)
        aff[h, w] += 1.0
        aff[tl, w] += 1.0
        load[w] += 1
        assigned += 1
    return t[np.array(chunks, dtype=np.int64)]


#: Host partitioner registry — ``MapReduceConfig.partition`` values.
PARTITIONERS = {
    "balanced": partition_balanced,
    "stratified": partition_stratified,
    "degree": partition_degree_stratified,
    "overlap": partition_overlap_min,
}


def epoch_batches(
    seed: int,
    epoch: int,
    partitioned: np.ndarray,     # (W, N_w, 3)
    batch_size: int,
) -> np.ndarray:
    """Deterministic minibatches for one epoch: ``(W, S, B, 3)``.

    Pure function of (seed, epoch) — a restarted job regenerates byte-
    identical batches, which is what makes checkpoint-resume exact
    (``train/ft.py``).

    Remainder rule: ``S = N_w // batch_size`` — the trailing
    ``N_w % batch_size`` triplets of each worker's permutation sit out of
    the epoch, but the per-epoch reshuffle rotates *which* triplets those
    are, so every triplet still trains over time.  ``mapreduce.train``
    surfaces the dropped count once per run (warning, or an error under
    ``strict_batching``)."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, epoch]))
    W, N_w, _ = partitioned.shape
    S = N_w // batch_size
    out = np.empty((W, S, batch_size, 3), np.int32)
    for w in range(W):
        perm = rng.permutation(N_w)[: S * batch_size]
        out[w] = partitioned[w][perm].reshape(S, batch_size, 3)
    return out


# ---------------------------------------------------------------------------
# Device pipeline: on-device epoch batching (pure jax, scan/jit friendly)
# ---------------------------------------------------------------------------

def device_worker_batches(
    key: jax.Array,
    triplets: jax.Array,         # (N_w, 3) one worker's split, on device
    batch_size: int,
) -> jax.Array:
    """One worker's epoch batch grid, built on device: ``(S, B, 3)``.

    The jax analogue of one row of :func:`epoch_batches` for the ``device``
    pipeline: the permutation is drawn from ``key`` (callers fold in
    (epoch, worker) — see ``mapreduce.make_block_fn``), so batches stay a
    pure function of (seed, epoch, worker) and checkpoint-resume stays
    exact.  Same remainder rule as the host path: ``N_w % batch_size``
    triplets rotate out of each epoch."""
    n = triplets.shape[0]
    steps = n // batch_size
    perm = jax.random.permutation(key, n)[: steps * batch_size]
    return jnp.take(triplets, perm, axis=0).reshape(steps, batch_size, 3)


def repartition_perm(key: jax.Array, n: int, round_idx: jax.Array) -> jax.Array:
    """The global triplet permutation of re-partition round ``round_idx``.

    Round 0 is the identity — the original host-side partition — so a
    ``repartition_every`` larger than the run is bit-identical to no
    re-partitioning at all.  The single definition of the permutation both
    device-pipeline backends index into: the vmap driver applies it to the
    stacked ``(W, N_w, 3)`` array (:func:`device_repartition`); the
    shard_map driver all-gathers its shards and takes its own
    ``N_w``-row slice of the same permutation — so worker ``w`` holds
    identical triplets on both backends."""
    perm = jax.random.permutation(key, n)
    return jnp.where(round_idx == 0, jnp.arange(n), perm)


def repartition_perm_stratified(
    key: jax.Array,
    strata: jax.Array,           # (n,) int32 per-triplet stratum labels
    n_workers: int,
    round_idx: jax.Array,
) -> jax.Array:
    """Strata-preserving re-partition permutation (degree partitioner).

    The device analogue of the ``order[w::W]`` host idiom: shuffle within
    each stratum (``lexsort`` on a fresh uniform draw keyed by the round),
    then deal the stratified order round-robin so worker ``w`` receives
    rows ``order[w::W]`` — each re-partition round redraws worker
    membership while keeping every worker's degree mix intact.  Round 0 is
    the identity, matching :func:`repartition_perm`.  ``strata`` describes
    the *original* flat triplet order (the array ``device_repartition``
    permutes), so the labels stay valid for every round."""
    n = strata.shape[0]
    n_w = n // n_workers
    u = jax.random.uniform(key, (n,))
    order = jnp.lexsort((u, strata))
    perm = order.reshape(n_w, n_workers).T.reshape(-1)
    return jnp.where(round_idx == 0, jnp.arange(n), perm)


def device_repartition(
    key: jax.Array,
    partitioned: jax.Array,      # (W, N_w, 3) on device
    round_idx: jax.Array,
    strata: jax.Array | None = None,
) -> jax.Array:
    """Re-split the full triplet set across workers on device.

    The device pipeline's epoch batching redraws *within-worker*
    permutations every epoch but the worker *membership* of each triplet is
    frozen at ``train()`` start; re-partitioning every M epochs
    (``EpochSchedule.repartition_every``) kills that residual split bias.
    Pure function of (key, round) — callers fold the round index into the
    key — which is what keeps block-size invariance intact.  With
    ``strata`` (degree partitioner) the permutation is stratum-preserving
    (:func:`repartition_perm_stratified`); without, it is the original
    uniform :func:`repartition_perm` — byte-identical to before strata
    existed."""
    W, n_w, _ = partitioned.shape
    flat = partitioned.reshape(W * n_w, 3)
    if strata is None:
        perm = repartition_perm(key, W * n_w, round_idx)
    else:
        perm = repartition_perm_stratified(key, strata, W, round_idx)
    return jnp.take(flat, perm, axis=0).reshape(W, n_w, 3)


def device_epoch_batches(
    key: jax.Array,
    partitioned: jax.Array,      # (W, N_w, 3) on device
    batch_size: int,
) -> jax.Array:
    """All workers' batch grids on device: ``(W, S, B, 3)``.

    Per-worker permutations come from ``fold_in(key, w)`` — identical keys
    to what the shard_map scanned driver derives from ``axis_index``, so the
    vmap and shard_map device pipelines see the same batches."""
    W = partitioned.shape[0]
    return jax.vmap(
        lambda part_w, w: device_worker_batches(
            jax.random.fold_in(key, w), part_w, batch_size)
    )(partitioned, jnp.arange(W))
