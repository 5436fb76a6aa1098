"""Reduce-phase merge strategies (paper §3.1.2).

After the Map phase, W workers hold W inconsistent copies of each embedding
table.  The paper proposes three ways to Reduce the W vectors per key:

  * ``random``            — pick one worker's vector per key at random,
  * ``average``           — per-key mean,
  * ``miniloss``          — the vector from the worker with the smallest loss.

We implement each in two refinements (DESIGN.md §2 Faithfulness notes):
  * per-key *touch-aware* variants (only workers whose subset actually
    updated the key participate) — ``random``, ``average``,
    ``miniloss_perkey``;
  * the literal global variants — ``average_all`` (plain mean over all
    workers), ``miniloss_global`` (min-mean-loss worker wins every key).

Two execution paths with identical semantics:
  * **stacked**: tables carry a leading worker axis ``(W, N, k)`` — used by
    the vmap simulation backend and by the all_gather Reduce;
  * **collective**: per-shard tables ``(N, k)`` inside ``shard_map`` with an
    ``axis_name`` — the production path.  The priority-select trick (psum of
    ``emb * onehot(winner)``) reduces Reduce traffic from O(W·N·k)
    (all_gather, paper-literal) to O(N·k) (two psums) — see DESIGN.md §4 and
    EXPERIMENTS.md §Perf.

A "table" here is one embedding matrix ``(N, k)`` with its per-key stats
``count (N,)`` / ``loss (N,)``; callers apply the merge per table ('ent',
'rel').

Transport contract (``MapReduceConfig.merge_transport``)
--------------------------------------------------------

Both execution paths above ship *whole tables* per Reduce — O(W·N·k)
(all_gather) or O(N·k) (psum) wire bytes per table regardless of how few
rows the round actually updated.  The **sparse** transport replaces the
exchanged payload with compact per-worker *delta buffers* while producing
bit-identical merged tables:

  * **pack** (:func:`pack_delta`): each worker gathers the rows its touch
    stats mark updated (``count > 0``) into ``(C, k)`` value / ``(C,)``
    count / loss buffers plus a sorted ``(C,)`` row-id vector.
  * **capacity / padding rule** (:func:`touched_capacity`): ``C`` is a
    *static* upper bound on touched rows per round —
    ``min(n_rows, f · batch_size · steps_per_epoch · merge_every)`` with
    ``f = 4`` for entity-role tables (positive + corrupted heads and
    tails) and ``f = 1`` for relation-role tables (corruption preserves
    the relation) — so the device pipeline's ``lax.scan`` block compiles
    once; unused slots are padded with the out-of-range row id ``n_rows``
    (values 0, dropped by every consumer via ``mode="fill"`` gathers and
    ``mode="drop"`` scatters).  The same drop-scatter makes capacity a
    **hard correctness bound**: a round touching more than ``C`` rows
    would silently lose the overflow slots' updates.  The engine
    therefore counts touched rows on device (:func:`delta_overflow`),
    surfaces the worst per-table excess at every Reduce boundary, and
    the train drivers raise on a positive count; a user capacity
    override below the analytic bound
    (``MapReduceConfig.touched_capacity``) is rejected at ``train()``
    time, before any epoch runs.
  * **merge** (:func:`merge_sparse_stacked`): the union of all workers'
    touched ids (:func:`sparse_candidates`) is the only row set merged.
    Per worker, a candidate row it did not touch is reconstructed as the
    *virgin* value — ``m`` chained applications of the model's row-local
    ``normalize_rows`` to the round-input row, ``m`` = merged epochs
    (``normalize="epoch"``), merged steps (``"step"``) or 0 — which is
    exactly what that worker's dense copy holds there.  Every strategy
    then runs the dense per-row math on the ``(W, U, k)`` candidate
    slices (all dense reductions here are per-row, so slicing is
    bit-exact), and the result is scattered into the evolved base table.
    Rows no worker touched keep the base value (selection strategies) or
    the dense plain-mean-of-identical-copies (averaging strategies, which
    only differs from the copy itself when W is not a power of two — see
    :func:`sparse_untouched_base`).

The sparse transport is *bit-identical* to the dense stacked/allgather
numerics for every strategy; under ``shard_map`` it all-gathers the packed
buffers (O(W·C·k) wire bytes) and replays the same stacked math, so vmap
and shard_map agree bitwise (a strengthening of the dense psum path's
tolerance-level agreement).  Dense remains the default and the reference.

Sharded tables (``MapReduceConfig.table_sharding="sharded"``)
-------------------------------------------------------------

The sparse transport doubles as the routing layer for sharded tables:
every table is partitioned into W contiguous row blocks
(:func:`shard_rows`), the candidate union is split per block
(:func:`own_candidates` — sorted and overflow-free by construction), and
each shard merges only the candidates it owns
(:func:`merge_sparse_sharded_stacked`,
:func:`merge_sparse_sharded_collective`).  Every strategy's math is
per-row over the worker axis and the blocks partition the union, so the
shard-routed merge is bit-identical to the monolithic one.  Under
shard_map the Reduce exchanges packed deltas plus each shard's merged
own-block — O(W·C·k) wire bytes, never a full-table all_gather — and the
per-shard merge compute drops to the shard's share of the union.
(Memory note: 'random' still draws its full ``(W, n_rows)`` priority
matrix per shard — RNG output is shape-dependent — so that strategy's
transient footprint does not shrink with sharding.)
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from repro import obs

STRATEGIES = (
    "random",
    "average",
    "average_all",
    "miniloss_perkey",
    "miniloss_global",
)

_BIG = 1e30


# ---------------------------------------------------------------------------
# Stacked path: tables (W, N, k); counts/losses (W, N); worker_loss (W,)
# ---------------------------------------------------------------------------

def _select_by_priority_stacked(
    stacked: jax.Array, priority: jax.Array
) -> jax.Array:
    """Per key, return the row of the worker with the max priority.
    ``stacked``: (W, N, k); ``priority``: (W, N) -> (N, k)."""
    winner = jnp.argmax(priority, axis=0)                       # (N,)
    return jnp.take_along_axis(
        stacked, winner[None, :, None], axis=0
    )[0]


def merge_average_all_stacked(stacked: jax.Array) -> jax.Array:
    return jnp.mean(stacked, axis=0)


def merge_average_stacked(stacked: jax.Array, counts: jax.Array) -> jax.Array:
    """Touch-count-weighted mean; keys untouched everywhere keep the plain
    mean (all copies are identical there, so it is the anchor value)."""
    w = counts[..., None]                                       # (W, N, 1)
    total = jnp.sum(w, axis=0)
    weighted = jnp.sum(stacked * w, axis=0)
    plain = jnp.mean(stacked, axis=0)
    return jnp.where(total > 0, weighted / jnp.maximum(total, 1.0), plain)


def _random_priorities(key: jax.Array, W: int, N: int) -> jax.Array:
    """Per-worker uniform priorities from worker-folded keys — the same
    construction in the stacked and collective paths, so the two backends
    make bit-identical choices given the same key."""
    return jax.vmap(
        lambda w: jax.random.uniform(jax.random.fold_in(key, w), (N,))
    )(jnp.arange(W))


def merge_random_stacked(
    key: jax.Array, stacked: jax.Array, counts: jax.Array
) -> jax.Array:
    """Per-key uniform choice among the workers that touched the key."""
    W, N = counts.shape
    u = _random_priorities(key, W, N)
    priority = jnp.where(counts > 0, u, -_BIG)
    # no toucher anywhere -> all copies identical; worker argmax(u) is fine.
    any_touch = jnp.any(counts > 0, axis=0)
    priority = jnp.where(any_touch[None, :], priority, u)
    return _select_by_priority_stacked(stacked, priority)


def merge_miniloss_perkey_stacked(
    stacked: jax.Array, counts: jax.Array, losses: jax.Array
) -> jax.Array:
    """Per key: the worker with the smallest mean per-touch loss wins."""
    mean_loss = jnp.where(counts > 0, losses / jnp.maximum(counts, 1.0), _BIG)
    priority = -mean_loss                                        # max == min loss
    return _select_by_priority_stacked(stacked, priority)


def merge_miniloss_global_stacked(
    stacked: jax.Array, worker_loss: jax.Array
) -> jax.Array:
    """The single worker with the smallest epoch loss wins every key."""
    winner = jnp.argmin(worker_loss)
    return stacked[winner]


def merge_stacked(
    strategy: str,
    stacked: jax.Array,
    counts: jax.Array,
    losses: jax.Array,
    worker_loss: jax.Array,
    key: jax.Array | None = None,
) -> jax.Array:
    if strategy == "average":
        return merge_average_stacked(stacked, counts)
    if strategy == "average_all":
        return merge_average_all_stacked(stacked)
    if strategy == "random":
        if key is None:
            raise ValueError("'random' strategy needs a PRNG key")
        return merge_random_stacked(key, stacked, counts)
    if strategy == "miniloss_perkey":
        return merge_miniloss_perkey_stacked(stacked, counts, losses)
    if strategy == "miniloss_global":
        return merge_miniloss_global_stacked(stacked, worker_loss)
    raise ValueError(f"unknown strategy {strategy!r}; want one of {STRATEGIES}")


# ---------------------------------------------------------------------------
# Collective path: per-shard (N, k) inside shard_map over `axis`
# ---------------------------------------------------------------------------

# The Reduce's traffic between workers: every collective of the shard_map
# merge goes through one of these, so its ops carry the
# ``repro.reduce.exchange`` scope (op metadata only).

@obs.scope("reduce.exchange")
def all_gather(x: jax.Array, axis: str) -> jax.Array:
    return jax.lax.all_gather(x, axis)


@obs.scope("reduce.exchange")
def psum(x: jax.Array, axis: str) -> jax.Array:
    return jax.lax.psum(x, axis)


@obs.scope("reduce.exchange")
def pmax(x: jax.Array, axis: str) -> jax.Array:
    return jax.lax.pmax(x, axis)


@obs.scope("reduce.exchange")
def pmin(x: jax.Array, axis: str) -> jax.Array:
    return jax.lax.pmin(x, axis)


def _select_by_priority_psum(
    local: jax.Array, priority: jax.Array, axis: str
) -> jax.Array:
    """Collective winner-take-all: O(N) + O(N·k) psums instead of an
    O(W·N·k) all_gather.

    Exact two-phase selection (float-safe): (1) pmax finds the best priority
    — pmax returns one of the operand values bit-exactly, so the equality
    test below is well defined; (2) among workers tying at the best
    priority, the smallest worker index wins (matching the stacked path's
    ``argmax`` first-winner tie-break); (3) one masked psum of the winner's
    rows."""
    idx = jax.lax.axis_index(axis).astype(jnp.float32)
    best = pmax(priority, axis)                                   # (N,)
    am_best = priority == best
    my_claim = jnp.where(am_best, idx, jnp.inf)
    winner = pmin(my_claim, axis)                                 # (N,)
    mine = (am_best & (idx == winner)).astype(local.dtype)        # (N,)
    return psum(local * mine[:, None], axis)


def merge_collective(
    strategy: str,
    local: jax.Array,            # (N, k) this worker's table
    count: jax.Array,            # (N,)
    loss: jax.Array,             # (N,)
    worker_loss: jax.Array,      # scalar, this worker's epoch loss
    axis: str,
    key: jax.Array | None = None,
    liveness: jax.Array | None = None,
) -> jax.Array:
    """psum-based Reduce (production path).  ``liveness`` is an optional
    per-worker 0/1 scalar (this worker's own flag): dead workers are excluded
    from every strategy — the K-of-N fault-tolerant merge of DESIGN.md §4."""
    live = jnp.ones((), local.dtype) if liveness is None else liveness.astype(local.dtype)
    W_live = psum(live, axis)

    if strategy == "average_all":
        return psum(local * live, axis) / jnp.maximum(W_live, 1.0)

    if strategy == "average":
        w = count * live                                          # (N,)
        total = psum(w, axis)
        weighted = psum(local * w[:, None], axis)
        plain = psum(local * live, axis) / jnp.maximum(W_live, 1.0)
        return jnp.where(
            total[:, None] > 0, weighted / jnp.maximum(total, 1.0)[:, None], plain
        )

    if strategy == "random":
        if key is None:
            raise ValueError("'random' strategy needs a PRNG key")
        # fold in the worker id so every shard draws a distinct priority from
        # a shared key (same key across shards => deterministic merge);
        # identical construction to _random_priorities for backend parity.
        idx = jax.lax.axis_index(axis)
        u = jax.random.uniform(jax.random.fold_in(key, idx), count.shape)
        touched = (count > 0) & (live > 0)
        any_touch = psum(touched.astype(jnp.float32), axis) > 0
        pri = jnp.where(touched, u, jnp.where(any_touch, -_BIG, u))
        pri = jnp.where(live > 0, pri, -2 * _BIG)
        return _select_by_priority_psum(local, pri, axis)

    if strategy == "miniloss_perkey":
        mean_loss = jnp.where(count > 0, loss / jnp.maximum(count, 1.0), _BIG)
        pri = jnp.where(live > 0, -mean_loss, -2 * _BIG)
        return _select_by_priority_psum(local, pri, axis)

    if strategy == "miniloss_global":
        pri = jnp.where(live > 0, -worker_loss, -2 * _BIG)
        pri = jnp.broadcast_to(pri, count.shape)
        return _select_by_priority_psum(local, pri, axis)

    raise ValueError(f"unknown strategy {strategy!r}; want one of {STRATEGIES}")


def merge_allgather(
    strategy: str,
    local: jax.Array,
    count: jax.Array,
    loss: jax.Array,
    worker_loss: jax.Array,
    axis: str,
    key: jax.Array | None = None,
) -> jax.Array:
    """Paper-literal Reduce: gather all W copies then run the stacked merge.
    O(W·N·k) collective bytes — kept as the faithful baseline the §Perf
    hillclimb starts from."""
    stacked = all_gather(local, axis)                            # (W, N, k)
    counts = all_gather(count, axis)                             # (W, N)
    losses = all_gather(loss, axis)
    wl = all_gather(worker_loss, axis)                           # (W,)
    return merge_stacked(strategy, stacked, counts, losses, wl, key)


# ---------------------------------------------------------------------------
# Sparse delta transport (merge_transport="sparse") — see module docstring
# ---------------------------------------------------------------------------

def touched_capacity(
    n_rows: int, batch_size: int, steps_per_epoch: int, merge_every: int,
    role: str,
) -> int:
    """Static per-worker delta-buffer capacity for one Reduce round.

    One SGD step touches at most ``4 * batch_size`` entity rows (positive +
    corrupted heads and tails) and ``batch_size`` relation rows (corruption
    keeps the relation), so ``f·B·S·K`` bounds a round of ``K`` local
    epochs of ``S`` steps; never more than the table itself."""
    per_step = (4 if role == "ent" else 1) * batch_size
    return int(min(n_rows, per_step * steps_per_epoch * merge_every))


def pack_delta(
    table: jax.Array, count: jax.Array, loss: jax.Array,
    capacity: int, n_rows: int,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """One worker's padded delta buffer: the rows its touch stats mark
    updated.  Returns ``(idx, vals, cnt, lss)`` with ``idx`` the sorted
    ``(capacity,)`` touched row ids padded with ``n_rows`` and the others
    the corresponding ``(capacity, k)`` / ``(capacity,)`` gathers
    (zero-filled at pads).

    The compaction is a cumsum + scatter rather than ``jnp.nonzero(...,
    size=capacity)``: the batched (vmapped-over-workers) lowering of
    sized nonzero sorts all ``n_rows`` elements per worker, which at 1e6
    rows costs more than the entire dense merge; cumsum + drop-scatter is
    a linear pass and produces the identical sorted-ascending id vector.
    """
    mask = count > 0
    slot = jnp.where(mask, jnp.cumsum(mask) - 1, capacity)
    idx = jnp.full((capacity,), n_rows, slot.dtype).at[slot].set(
        jnp.arange(n_rows, dtype=slot.dtype), mode="drop")
    vals = jnp.take(table, idx, axis=0, mode="fill", fill_value=0.0)
    cnt = jnp.take(count, idx, mode="fill", fill_value=0.0)
    lss = jnp.take(loss, idx, mode="fill", fill_value=0.0)
    return idx, vals, cnt, lss


def delta_overflow(count: jax.Array, capacity: int) -> jax.Array:
    """How many touched rows :func:`pack_delta`'s drop-scatter would
    silently discard for this round: ``max(touched - capacity, 0)``,
    maxed over any leading worker axis.  Zero by construction under the
    analytic :func:`touched_capacity` bound; positive only if the
    capacity was overridden below the real touch count (or the bound is
    wrong) — the merge drivers surface this at every Reduce boundary and
    the train pipelines raise on a positive value."""
    touched = jnp.sum((count > 0).astype(jnp.int32), axis=-1)
    return jnp.max(jnp.maximum(touched - capacity, 0))


def sparse_candidates(idx: jax.Array, n_rows: int) -> jax.Array:
    """Union of every worker's touched row ids: ``idx`` is the stacked
    ``(W, C)`` id vectors; returns a sorted unique id vector of static size
    ``min(n_rows, W·C) + 1`` padded with ``n_rows`` (the +1 slot absorbs
    the pad id itself whenever any buffer is underfull)."""
    W, C = idx.shape
    size = int(min(n_rows, W * C)) + 1
    return jnp.unique(idx.reshape(-1), size=size, fill_value=n_rows)


def lookup_rows(
    idx: jax.Array, vals: jax.Array, cand: jax.Array, virgin: jax.Array,
    n_rows: int,
) -> jax.Array:
    """Reconstruct one worker's rows at the candidate ids: its packed value
    where ``cand`` appears in the (sorted) ``idx``, the shared ``virgin``
    row otherwise."""
    C = idx.shape[0]
    pos = jnp.clip(jnp.searchsorted(idx, cand), 0, C - 1)
    found = (idx[pos] == cand) & (cand < n_rows)
    return jnp.where(found[:, None], vals[pos], virgin)


def lookup_delta(
    idx: jax.Array, vals: jax.Array, cnt: jax.Array, lss: jax.Array,
    cand: jax.Array, virgin: jax.Array, n_rows: int,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Reconstruct one worker's table slice + touch stats at the candidate
    rows: packed values where the worker touched the row, the shared
    ``virgin`` value (and zero count/loss, matching the dense stats) where
    it did not.  ``idx`` must be sorted, as :func:`pack_delta` emits."""
    C = idx.shape[0]
    pos = jnp.clip(jnp.searchsorted(idx, cand), 0, C - 1)
    found = (idx[pos] == cand) & (cand < n_rows)
    val = jnp.where(found[:, None], vals[pos], virgin)
    c = jnp.where(found, cnt[pos], 0.0)
    l = jnp.where(found, lss[pos], 0.0)
    return val, c, l


def virgin_rows(rows, normalize_row_fn, repeats: int):
    """The value every worker's copy of an *untouched* row holds at Reduce
    time: ``repeats`` chained applications of the model's row-local
    constraint projection to the round-input row (repeats = epochs merged
    for ``normalize="epoch"``, steps merged for ``"step"``, 0 for
    ``"none"``).

    Chained applications run through ``fori_loop``, never unrolled: in the
    dense path each projection lives in its own scan iteration, and
    unrolling here lets XLA fuse consecutive projections into one kernel
    whose rounding drifts from the dense path by an ulp — the loop
    boundary pins each application to the standalone rounding."""
    if repeats == 0:
        return rows
    if repeats == 1:
        return normalize_row_fn(rows)
    return jax.lax.fori_loop(0, repeats, lambda _, r: normalize_row_fn(r), rows)


def sparse_untouched_base(strategy: str, local: jax.Array, W: int) -> jax.Array:
    """Merged value of rows *no* worker touched, from one worker's local
    copy (all copies agree there).  Selection strategies return one of the
    identical copies — the copy itself, exactly.  The averaging strategies
    compute the plain mean over W identical copies, which is bit-identical
    to the copy only when W is a power of two; otherwise replay the dense
    reduction on a broadcast so the float rounding matches the dense path
    exactly.  The barrier keeps XLA's algebraic simplifier from collapsing
    the reduce-of-broadcast into ``x * W / W`` inside a fused program —
    that rewrite rounds 1 ulp away from the dense path's genuine W-way
    sum on rare values."""
    if strategy not in ("average", "average_all") or (W & (W - 1)) == 0:
        return local
    stacked = jax.lax.optimization_barrier(
        jnp.broadcast_to(local, (W,) + local.shape))
    return jnp.mean(stacked, axis=0)


def merge_candidates(
    strategy: str,
    cand: jax.Array,          # (U,) sorted candidate row ids, padded n_rows
    svals: jax.Array,         # (W, U, k) reconstructed rows per worker
    scnt: jax.Array,          # (W, U)
    sloss: jax.Array,         # (W, U)
    worker_loss: jax.Array,   # (W,)
    n_rows: int,
    key: jax.Array | None = None,
) -> jax.Array:
    """:func:`merge_stacked` restricted to the candidate rows.  Every dense
    reduction is per-row (sums/argmax over the worker axis), so running it
    on the ``(W, U, k)`` slices is bit-identical to slicing the dense
    output.  'random' still draws its full ``(W, n_rows)`` priority matrix
    (RNG output depends on shape) and gathers the candidate columns."""
    if strategy == "average":
        w = scnt[..., None]
        total = jnp.sum(w, axis=0)
        weighted = jnp.sum(svals * w, axis=0)
        # real candidates always have total > 0; the plain-mean branch is
        # only reachable at pad rows, whose output is dropped.
        return jnp.where(
            total > 0, weighted / jnp.maximum(total, 1.0), jnp.mean(svals, axis=0)
        )
    if strategy == "average_all":
        return jnp.mean(svals, axis=0)
    if strategy == "random":
        if key is None:
            raise ValueError("'random' strategy needs a PRNG key")
        W = svals.shape[0]
        u_full = _random_priorities(key, W, n_rows)              # (W, n_rows)
        u = jnp.take(u_full, cand, axis=1, mode="fill", fill_value=0.0)
        priority = jnp.where(scnt > 0, u, -_BIG)
        any_touch = jnp.any(scnt > 0, axis=0)
        priority = jnp.where(any_touch[None, :], priority, u)
        return _select_by_priority_stacked(svals, priority)
    if strategy == "miniloss_perkey":
        mean_loss = jnp.where(scnt > 0, sloss / jnp.maximum(scnt, 1.0), _BIG)
        return _select_by_priority_stacked(svals, -mean_loss)
    if strategy == "miniloss_global":
        return svals[jnp.argmin(worker_loss)]
    raise ValueError(f"unknown strategy {strategy!r}; want one of {STRATEGIES}")


def apply_delta(base: jax.Array, cand: jax.Array, rows: jax.Array) -> jax.Array:
    """Scatter merged candidate rows into the evolved base table; pad
    candidates (id == n_rows, out of range) drop out."""
    return base.at[cand].set(rows, mode="drop")


def merge_sparse_stacked(
    strategy: str,
    idx: jax.Array,           # (W, C) packed row ids
    vals: jax.Array,          # (W, C, k)
    cnts: jax.Array,          # (W, C)
    losses: jax.Array,        # (W, C)
    worker_loss: jax.Array,   # (W,)
    local: jax.Array,         # (N, k) any one worker's full table
    base: jax.Array,          # (N, k) the shared round-input table
    normalize_row_fn,
    repeats: int,
    key: jax.Array | None = None,
) -> jax.Array:
    """Merge packed delta buffers from W workers into the full table —
    bit-identical to :func:`merge_stacked` on the dense copies.  ``local``
    supplies untouched-row values (any worker's copy: they agree there);
    ``base`` + ``normalize_row_fn``/``repeats`` reconstruct what a
    *partially* untouched candidate row evolved into per worker."""
    W = idx.shape[0]
    n_rows = base.shape[0]
    cand = sparse_candidates(idx, n_rows)
    virgin = virgin_rows(
        jnp.take(base, cand, axis=0, mode="fill", fill_value=0.0),
        normalize_row_fn, repeats,
    )
    svals, scnt, sloss = jax.vmap(
        lookup_delta, in_axes=(0, 0, 0, 0, None, None, None)
    )(idx, vals, cnts, losses, cand, virgin, n_rows)
    rows = merge_candidates(
        strategy, cand, svals, scnt, sloss, worker_loss, n_rows, key
    )
    return apply_delta(sparse_untouched_base(strategy, local, W), cand, rows)


# ---------------------------------------------------------------------------
# Sharded tables: shard-routed merge (table_sharding="sharded")
# ---------------------------------------------------------------------------

def shard_rows(n_rows: int, n_shards: int) -> int:
    """Contiguous row-block size per shard: shard ``s`` owns rows
    ``[s·R, min((s+1)·R, n_rows))`` with ``R = ceil(n_rows / n_shards)``.
    Every table is sharded by the same rule, so a row's owner is a pure
    function of its id."""
    return -(-n_rows // n_shards)


def own_candidates(
    cand: jax.Array, lo: jax.Array, block: int, n_rows: int
) -> jax.Array:
    """One shard's slice of the candidate union: the (still sorted) ids in
    ``[lo, lo + block)``, compacted into a static ``min(block, U-1) + 1``
    buffer padded with ``n_rows``.  A shard owns at most ``block`` real
    rows and ``cand`` carries at most ``U - 1`` real ids, so this buffer
    can never overflow — no drop risk, unlike :func:`pack_delta`."""
    U = cand.shape[0]
    cap = int(min(block, U - 1)) + 1
    mask = (cand >= lo) & (cand < lo + block) & (cand < n_rows)
    slot = jnp.where(mask, jnp.cumsum(mask) - 1, cap)
    return jnp.full((cap,), n_rows, cand.dtype).at[slot].set(cand, mode="drop")


def _merge_own_block(
    strategy, idx, vals, cnts, losses, worker_loss, base,
    normalize_row_fn, repeats, lo, block, cand, key,
):
    """Merge the candidates one shard owns.  Per-candidate math is the
    exact computation :func:`merge_sparse_stacked` runs at that row —
    strategies never mix rows, so restricting to an owned block changes
    nothing bitwise ('random' draws the same full ``(W, n_rows)``
    priority matrix from the same key and gathers disjoint columns)."""
    n_rows = base.shape[0]
    own = own_candidates(cand, lo, block, n_rows)
    virgin = virgin_rows(
        jnp.take(base, own, axis=0, mode="fill", fill_value=0.0),
        normalize_row_fn, repeats,
    )
    svals, scnt, sloss = jax.vmap(
        lookup_delta, in_axes=(0, 0, 0, 0, None, None, None)
    )(idx, vals, cnts, losses, own, virgin, n_rows)
    rows = merge_candidates(
        strategy, own, svals, scnt, sloss, worker_loss, n_rows, key
    )
    return own, rows


def merge_sparse_sharded_stacked(
    strategy: str,
    idx: jax.Array,           # (W, C) packed row ids
    vals: jax.Array,          # (W, C, k)
    cnts: jax.Array,          # (W, C)
    losses: jax.Array,        # (W, C)
    worker_loss: jax.Array,   # (W,)
    local: jax.Array,         # (N, k) any one worker's full table
    base: jax.Array,          # (N, k) the shared round-input table
    normalize_row_fn,
    repeats: int,
    key: jax.Array | None = None,
    *,
    n_shards: int,
) -> jax.Array:
    """Shard-routed :func:`merge_sparse_stacked`: the candidate union is
    partitioned into ``n_shards`` contiguous row blocks and each block is
    merged independently — bit-identical to the monolithic merge because
    the blocks partition the union and strategy math is per-row.  This is
    the vmap-backend simulation of the collective path below; the blocks
    run under ``lax.map`` so transient memory stays one block's worth."""
    W = idx.shape[0]
    n_rows = base.shape[0]
    R = shard_rows(n_rows, n_shards)
    cand = sparse_candidates(idx, n_rows)

    def shard_merge(lo):
        return _merge_own_block(
            strategy, idx, vals, cnts, losses, worker_loss, base,
            normalize_row_fn, repeats, lo, R, cand, key,
        )

    los = jnp.arange(n_shards, dtype=cand.dtype) * R
    owns, rows = jax.lax.map(shard_merge, los)
    out = sparse_untouched_base(strategy, local, W)
    return apply_delta(out, owns.reshape(-1), rows.reshape(-1, rows.shape[-1]))


def merge_candidates_stale(
    strategy: str,
    cand: jax.Array,          # (U,) sorted candidate row ids, padded n_rows
    svals: jax.Array,         # (W, U, k) worker rows at the candidates
    scnt: jax.Array,          # (W, U) this-round touch counts
    sloss: jax.Array,         # (W, U)
    worker_loss: jax.Array,   # (W,)
    bcand: jax.Array,         # (U, k) the global view at the candidates
    n_rows: int,
    key: jax.Array | None = None,
) -> jax.Array:
    """Participation-masked Reduce for the bounded-staleness mode: per row,
    only workers whose round actually touched it contribute — workers that
    did not hold an *arbitrary stale* value there (not the shared round
    input the synchronous strategies assume), so they must be excluded from
    every strategy, and a row nobody touched keeps the global view
    ``bcand`` exactly (the ParaGraphE push-touched-rows semantics;
    untouched global rows are never re-normalized).  The math is per-row
    over the worker axis, so the dense path (``merge_stacked_stale`` passes
    the full table with ``cand = arange``) and the packed sparse path
    compute bit-identical rows."""
    touched = scnt > 0
    any_touch = jnp.any(touched, axis=0)                         # (U,)
    if strategy == "average":
        w = scnt[..., None]
        merged = jnp.sum(svals * w, axis=0) / jnp.maximum(
            jnp.sum(w, axis=0), 1.0)
    elif strategy == "average_all":
        # "all workers" under staleness = all this-round *touchers*: the
        # non-toucher copies are stale garbage, not identical round inputs
        w = touched.astype(svals.dtype)[..., None]
        merged = jnp.sum(svals * w, axis=0) / jnp.maximum(
            jnp.sum(w, axis=0), 1.0)
    elif strategy == "random":
        if key is None:
            raise ValueError("'random' strategy needs a PRNG key")
        W = svals.shape[0]
        u_full = _random_priorities(key, W, n_rows)              # (W, n_rows)
        u = jnp.take(u_full, cand, axis=1, mode="fill", fill_value=0.0)
        merged = _select_by_priority_stacked(
            svals, jnp.where(touched, u, -_BIG))
    elif strategy == "miniloss_perkey":
        mean_loss = jnp.where(
            touched, sloss / jnp.maximum(scnt, 1.0), _BIG)
        merged = _select_by_priority_stacked(svals, -mean_loss)
    elif strategy == "miniloss_global":
        # the best *toucher* per row wins (a global winner that skipped the
        # row would push its stale copy over fresher work)
        pri = jnp.where(touched, -worker_loss[:, None], -_BIG)
        merged = _select_by_priority_stacked(svals, pri)
    else:
        raise ValueError(
            f"unknown strategy {strategy!r}; want one of {STRATEGIES}")
    return jnp.where(any_touch[:, None], merged, bcand)


def merge_stacked_stale(
    strategy: str,
    stacked: jax.Array,       # (W, N, k) worker copies after their round
    counts: jax.Array,        # (W, N) this-round touch counts
    losses: jax.Array,        # (W, N)
    worker_loss: jax.Array,   # (W,)
    base: jax.Array,          # (N, k) the global view being merged into
    key: jax.Array | None = None,
) -> jax.Array:
    """Dense bounded-staleness Reduce: :func:`merge_candidates_stale` over
    every row of the table (the reference the sparse transport must match
    bitwise)."""
    N = counts.shape[1]
    cand = jnp.arange(N, dtype=jnp.int32)
    return merge_candidates_stale(
        strategy, cand, stacked, counts, losses, worker_loss, base, N, key)


def merge_sparse_stale(
    strategy: str,
    idx: jax.Array,           # (W, C) packed row ids
    vals: jax.Array,          # (W, C, k)
    cnts: jax.Array,          # (W, C)
    losses: jax.Array,        # (W, C)
    worker_loss: jax.Array,   # (W,)
    base: jax.Array,          # (N, k) the global view being merged into
    key: jax.Array | None = None,
) -> jax.Array:
    """Sparse-transport bounded-staleness Reduce: merge the union of the
    workers' touched rows into the global view.  No virgin reconstruction:
    a worker that skipped a candidate row is *excluded* from that row's
    merge (zero count via :func:`lookup_delta`), so its placeholder value
    never contributes — which is exactly why the stale Reduce composes with
    the sparse transport without the synchronous path's shared-round-input
    bookkeeping.  Bit-identical to :func:`merge_stacked_stale` on the dense
    copies (per-row math on slices)."""
    n_rows = base.shape[0]
    cand = sparse_candidates(idx, n_rows)
    placeholder = jnp.zeros((cand.shape[0], base.shape[1]), base.dtype)
    svals, scnt, sloss = jax.vmap(
        lookup_delta, in_axes=(0, 0, 0, 0, None, None, None)
    )(idx, vals, cnts, losses, cand, placeholder, n_rows)
    bcand = jnp.take(base, cand, axis=0, mode="fill", fill_value=0.0)
    rows = merge_candidates_stale(
        strategy, cand, svals, scnt, sloss, worker_loss, bcand, n_rows, key)
    return apply_delta(base, cand, rows)


def _merge_own_block_stale(
    strategy, idx, vals, cnts, losses, worker_loss, base, lo, block, cand, key,
):
    """Stale-merge the candidates one shard owns — the bounded-staleness
    analogue of :func:`_merge_own_block` (per-candidate math, restricting
    to an owned block changes nothing bitwise)."""
    n_rows = base.shape[0]
    own = own_candidates(cand, lo, block, n_rows)
    placeholder = jnp.zeros((own.shape[0], base.shape[1]), base.dtype)
    svals, scnt, sloss = jax.vmap(
        lookup_delta, in_axes=(0, 0, 0, 0, None, None, None)
    )(idx, vals, cnts, losses, own, placeholder, n_rows)
    bown = jnp.take(base, own, axis=0, mode="fill", fill_value=0.0)
    rows = merge_candidates_stale(
        strategy, own, svals, scnt, sloss, worker_loss, bown, n_rows, key)
    return own, rows


def merge_sparse_stale_sharded_stacked(
    strategy: str,
    idx: jax.Array,
    vals: jax.Array,
    cnts: jax.Array,
    losses: jax.Array,
    worker_loss: jax.Array,
    base: jax.Array,
    key: jax.Array | None = None,
    *,
    n_shards: int,
) -> jax.Array:
    """Shard-routed :func:`merge_sparse_stale`: the candidate union is
    partitioned into owned row blocks, each stale-merged independently —
    bit-identical to the monolithic stale merge (blocks partition the
    union; the strategy math never mixes rows)."""
    n_rows = base.shape[0]
    R = shard_rows(n_rows, n_shards)
    cand = sparse_candidates(idx, n_rows)

    def shard_merge(lo):
        return _merge_own_block_stale(
            strategy, idx, vals, cnts, losses, worker_loss, base,
            lo, R, cand, key)

    los = jnp.arange(n_shards, dtype=cand.dtype) * R
    owns, rows = jax.lax.map(shard_merge, los)
    return apply_delta(base, owns.reshape(-1), rows.reshape(-1, rows.shape[-1]))


def merge_sparse_stale_collective(
    strategy: str,
    idx: jax.Array,           # (W, C) all-gathered packed row ids
    vals: jax.Array,
    cnts: jax.Array,
    losses: jax.Array,
    worker_loss: jax.Array,
    base: jax.Array,          # (N, k) the replicated global view
    axis: str,
    key: jax.Array | None = None,
    *,
    sharded: bool = False,
) -> jax.Array:
    """Bounded-staleness Reduce inside ``shard_map``: the packed buffers
    are already all-gathered (the transport's only cross-worker traffic),
    so every worker replays the stacked stale merge — or, with
    ``sharded=True``, merges only its owned candidate block and
    all-gathers the merged blocks, mirroring
    :func:`merge_sparse_sharded_collective`.  Bitwise equal to the vmap
    backend either way."""
    if not sharded:
        return merge_sparse_stale(
            strategy, idx, vals, cnts, losses, worker_loss, base, key)
    W = idx.shape[0]
    n_rows = base.shape[0]
    R = shard_rows(n_rows, W)
    cand = sparse_candidates(idx, n_rows)
    lo = (jax.lax.axis_index(axis) * R).astype(cand.dtype)
    own, rows = _merge_own_block_stale(
        strategy, idx, vals, cnts, losses, worker_loss, base,
        lo, R, cand, key)
    owns = all_gather(own, axis)
    rws = all_gather(rows, axis)
    return apply_delta(base, owns.reshape(-1), rws.reshape(-1, rws.shape[-1]))


def merge_sparse_sharded_collective(
    strategy: str,
    idx: jax.Array,           # (W, C) all-gathered packed row ids
    vals: jax.Array,          # (W, C, k)
    cnts: jax.Array,          # (W, C)
    losses: jax.Array,        # (W, C)
    worker_loss: jax.Array,   # (W,)
    local: jax.Array,         # (N, k) this shard's full table copy
    base: jax.Array,          # (N, k) the shared round-input table
    normalize_row_fn,
    repeats: int,
    axis: str,
    key: jax.Array | None = None,
) -> jax.Array:
    """Shard-routed merge inside ``shard_map`` (mesh axis size == number
    of shards): this worker merges only the candidate block it owns
    (``lo = axis_index · R``), then the merged own-blocks are all-gathered
    — O(W·cap·k) wire bytes, never a full-table all_gather — and every
    worker scatters all blocks into its base copy.  all_gather returns
    operands bit-exactly, so the result matches
    :func:`merge_sparse_sharded_stacked` (and hence the monolithic merge)
    bitwise on every shard."""
    W = idx.shape[0]
    n_rows = base.shape[0]
    R = shard_rows(n_rows, W)
    cand = sparse_candidates(idx, n_rows)
    lo = (jax.lax.axis_index(axis) * R).astype(cand.dtype)
    own, rows = _merge_own_block(
        strategy, idx, vals, cnts, losses, worker_loss, base,
        normalize_row_fn, repeats, lo, R, cand, key,
    )
    owns = all_gather(own, axis)                            # (W, cap)
    rws = all_gather(rows, axis)                            # (W, cap, k)
    out = sparse_untouched_base(strategy, local, W)
    return apply_delta(out, owns.reshape(-1), rws.reshape(-1, rws.shape[-1]))
