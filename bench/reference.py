"""The plain reference every cell is compared with.

Straightforward ``jax.numpy``, imports nothing of the program and takes
nothing it made: the tables come from the benchmark's own seed
(``bench/weights.py``), the graph from ``bench/graph.py``.  Every function
takes a ``prec``: ``"f32"`` is the reference (float32, matmuls at
``highest``); the controls are ``"bf16"`` (tables and arithmetic in
bfloat16) and ``"high"`` (float32 with three-pass matmuls).

A scoring model's math is its own file, ``bench/models/<model>.py``
(found by ``harness.model``): its energy, the energies of every candidate
entity or relation of a query, the constraint training projects onto at
the start of an epoch, the roles of its tables and a served answer's
scale.  Tables travel as a dict of any names, each with its leading axis
indexed by entity (role ``"ent"``) or by relation (role ``"rel"``) and
any trailing shape.

What the reference restates of the system's published semantics, so that
its results can be compared one by one:

* energies: the model file's (TransE ``||h + r - t||_1``, DistMult
  ``-sum(h * r * t)``);
* filtered ranking: a known candidate other than the gold entity that
  scores strictly better does not count against the gold;
* training: each of ``W`` workers holds ``N // W`` triples of a seeded
  shuffle, applies the model's constraint at the start of an epoch, then
  takes ``N_w // B`` SGD steps on the mean margin loss of a batch and its
  negatives; the Reduce averages each row of every table over the workers
  that touched it (by the touch count of the table's role), and the epoch
  loss is the mean over workers of the mean step loss.  Batches,
  corruptions and the split are drawn by the documented key scheme: keys
  fold in (epoch, worker) off ``fold_in(PRNGKey(seed), 0xD417A)``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import harness

PRECISIONS = {
    "f32": (jnp.float32, "highest"),
    "bf16": (jnp.bfloat16, "highest"),
    "high": (jnp.float32, "high"),
}


def cast(tables: dict, prec: str) -> dict:
    dtype = PRECISIONS[prec][0]
    return {k: jnp.asarray(v).astype(dtype) for k, v in tables.items()}


def dot(a, b, prec):
    """``a @ b``: at ``highest`` for the reference and the bfloat16
    control, and for ``high`` the three bfloat16 passes spelled out
    (``hi*hi + hi*lo + lo*hi``, products exact in float32), so the
    control is the same on every backend."""
    if PRECISIONS[prec][1] == "highest":
        return jnp.matmul(a, b, precision="highest")

    def split(x):
        hi = x.astype(jnp.bfloat16)
        return hi, (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)

    (ah, al), (bh, bl) = split(a), split(b)

    def mm(x, y):
        return jnp.matmul(x, y, preferred_element_type=jnp.float32)

    return mm(ah, bh) + mm(ah, bl) + mm(al, bh)


def unit_rows(x):
    """Rows scaled to unit L2 length: the constraint projection."""
    return x / (jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True)) + 1e-12)


# -- known groups (filtering) -------------------------------------------------

class Known:
    """The known tails of each (h, r) and heads of each (r, t) of a graph,
    as sorted key arrays with their members."""

    def __init__(self, triples: np.ndarray, n_entities: int,
                 n_relations: int):
        self.E, self.R = n_entities, n_relations
        h, r, t = (triples[:, i].astype(np.int64) for i in range(3))
        self.tail = self._groups(h * n_relations + r, t)
        self.head = self._groups(r * n_entities + t, h)

    @staticmethod
    def _groups(keys, members):
        order = np.lexsort((members, keys))
        keys, members = keys[order], members[order]
        uniq, start = np.unique(keys, return_index=True)
        end = np.append(start[1:], len(keys))
        return uniq, start, end, members

    def members(self, side: str, a: np.ndarray, b: np.ndarray):
        """(query index, member) pairs of the groups of queries ``(a, b)``:
        (h, r) for tails, (r, t) for heads."""
        uniq, start, end, members = self.tail if side == "tail" else self.head
        key = (a.astype(np.int64) * (self.R if side == "tail" else self.E)
               + b.astype(np.int64))
        pos = np.searchsorted(uniq, key)
        pos = np.minimum(pos, len(uniq) - 1)
        hit = uniq[pos] == key
        lo = np.where(hit, start[pos], 0)
        n = np.where(hit, end[pos] - start[pos], 0)
        rows = np.repeat(np.arange(len(key)), n)
        offs = np.arange(n.sum()) - np.repeat(np.cumsum(n) - n, n)
        return rows, members[np.repeat(lo, n) + offs]

    def mask(self, side: str, a, b) -> np.ndarray:
        rows, cols = self.members(side, a, b)
        m = np.zeros((len(a), self.E), bool)
        m[rows, cols] = True
        return m


# -- evaluation ---------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("model", "prec"))
def _rank_block(t, q, known_t, known_h, *, model, prec):
    m = harness.model(model)
    out = {}
    for side, col, known in (("tail", 2, known_t), ("head", 0, known_h)):
        s = m.candidates(t, q, side, prec)
        gold_id = q[:, col]
        gold = jnp.take_along_axis(s, gold_id[:, None], axis=1)
        better = s < gold
        raw = 1 + jnp.sum(better, axis=1)
        own = jnp.arange(s.shape[1])[None, :] == gold_id[:, None]
        out[f"{side}_raw"] = raw
        out[f"{side}_filtered"] = raw - jnp.sum(better & known & ~own, axis=1)
    s = m.relations(t, q, prec)
    gold = jnp.take_along_axis(s, q[:, 1:2], axis=1)
    out["relation"] = 1 + jnp.sum(s < gold, axis=1)
    return out


def ranks(model: str, tables: dict, test: np.ndarray, known: Known,
          prec: str = "f32", block: int = 64) -> dict:
    """Raw and filtered ranks of the gold head and tail, and the gold
    relation's rank, of every test triple."""
    t = cast(tables, prec)
    parts = []
    n = len(test)
    for lo in range(0, n, block):
        q = test[lo:lo + block]
        pad = block - len(q)
        if pad:
            q = np.concatenate([q, np.repeat(q[:1], pad, 0)])
        kt = known.mask("tail", q[:, 0], q[:, 1])
        kh = known.mask("head", q[:, 1], q[:, 2])
        out = _rank_block(t, jnp.asarray(q), kt, kh, model=model,
                          prec=prec)
        parts.append({k: np.asarray(v)[:block - pad] for k, v in out.items()})
    return {k: np.concatenate([p[k] for p in parts]) for k in parts[0]}


def corrupt(key, triples: np.ndarray, n_entities: int) -> np.ndarray:
    """Head or tail, by a fair coin, moved by a uniform offset in
    ``[1, E)`` (so it differs): the corruption the system's triple
    classification draws from ``PRNGKey(0)``."""
    k_side, k_ent = jax.random.split(key)
    n = len(triples)
    head = np.asarray(jax.random.bernoulli(k_side, 0.5, (n,)))
    off = np.asarray(jax.random.randint(k_ent, (n,), 1, n_entities))
    out = triples.copy()
    out[:, 0] = np.where(head, (triples[:, 0] + off) % n_entities,
                         triples[:, 0])
    out[:, 2] = np.where(head, triples[:, 2],
                         (triples[:, 2] + off) % n_entities)
    return out


def classification_triples(valid, test, n_entities: int) -> np.ndarray:
    """Valid, corrupted valid, test and corrupted test triples, in the
    order triple classification scores them."""
    k_v, k_t = jax.random.split(jax.random.PRNGKey(0))
    return np.concatenate([valid, corrupt(k_v, valid, n_entities), test,
                           corrupt(k_t, test, n_entities)])


def energies(model: str, tables: dict, triples: np.ndarray,
             prec: str = "f32") -> np.ndarray:
    t = cast(tables, prec)
    fn = jax.jit(functools.partial(harness.model(model).energy, prec=prec))
    return np.asarray(fn(t, jnp.asarray(triples)), np.float32)


def best_threshold(scores: np.ndarray, labels: np.ndarray) -> float:
    """The threshold (a score under it reads as true) that classifies the
    most of ``scores`` right: the first best cut of the sorted scores, at
    the midpoint of its two neighbours, or just outside at either end."""
    order = np.argsort(scores)
    s, lab = scores[order], labels[order]
    right = (np.concatenate([[0], np.cumsum(lab)])
             + np.concatenate([np.cumsum(1 - lab[::-1])[::-1], [0]]))
    i = int(np.argmax(right))
    if i == 0:
        return float(s[0]) - 1e-6
    if i == len(s):
        return float(s[-1]) + 1e-6
    return float(0.5 * (s[i - 1] + s[i]))


def classify_accuracy(model: str, tables: dict, valid: np.ndarray,
                      test: np.ndarray, n_entities: int, n_relations: int,
                      prec: str = "f32") -> float:
    """Triple classification (Socher et al. 2013): one threshold per
    relation fitted on the valid triples and their corruptions (a
    relation with fewer than 4 of them takes the threshold fitted on all),
    and the share of test triples and their corruptions it classifies
    right."""
    trip = classification_triples(valid, test, n_entities)
    e = energies(model, tables, trip, prec).astype(np.float64)
    nv, nt = len(valid), len(test)
    ev, et = e[:2 * nv], e[2 * nv:]
    rv, rt = trip[:2 * nv, 1], trip[2 * nv:, 1]
    lv = np.repeat([1.0, 0.0], nv)
    thr = np.full(n_relations, best_threshold(ev, lv))
    order = np.argsort(rv, kind="stable")
    bounds = np.searchsorted(rv[order], np.arange(n_relations + 1))
    for r in range(n_relations):
        rows = order[bounds[r]:bounds[r + 1]]
        if len(rows) >= 4:
            thr[r] = best_threshold(ev[rows], lv[rows])
    truth = np.repeat([True, False], nt)
    return float(np.mean((et < thr[rt]) == truth))


# -- serving ------------------------------------------------------------------

def top_k(model: str, tables: dict, kind: str, a: np.ndarray, b: np.ndarray,
          k: int, known: Known | None, prec: str = "f32"):
    """Best-first ids and energies of ``k`` candidates per query, known
    candidates left out where ``known`` is given, with each query's scale
    (the model's ``answer_scale``, from the float32 tables)."""
    m = harness.model(model)
    t = cast(tables, prec)
    zero = np.zeros_like(a)
    if kind == "relations":
        q = np.stack([a, zero, b], 1)
        s = m.relations(t, jnp.asarray(q), prec)
    else:
        side = "tail" if kind == "tails" else "head"
        q = (np.stack([a, b, zero], 1) if side == "tail"
             else np.stack([zero, b, a], 1))
        s = m.candidates(t, jnp.asarray(q), side, prec)
        if known is not None:
            pair = (a, b) if side == "tail" else (b, a)
            s = jnp.where(known.mask(side, *pair), jnp.inf, s)
    s = np.asarray(s, np.float64)
    ids = np.argsort(s, axis=1, kind="stable")[:, :k]
    return ids, s, m.answer_scale(cast(tables, "f32"), kind, a, b)


# -- training -----------------------------------------------------------------

STREAM_TAG = 0xD417A


def split_workers(seed: int, train: np.ndarray, n_workers: int):
    """``(W, N // W, 3)``: a seeded shuffle cut into equal parts."""
    perm = np.random.default_rng(seed).permutation(len(train))
    per = len(train) // n_workers
    return train[perm[:per * n_workers].reshape(n_workers, per)]


def _corrupt_batch(key, pos, n_entities: int):
    k_side, k_ent = jax.random.split(key)
    n = pos.shape[0]
    head = jax.random.bernoulli(k_side, 0.5, (n,))
    off = jax.random.randint(k_ent, (n,), 1, n_entities)
    h = jnp.where(head, (pos[:, 0] + off) % n_entities, pos[:, 0])
    t = jnp.where(head, pos[:, 2], (pos[:, 2] + off) % n_entities)
    return jnp.stack([h, pos[:, 1], t], 1).astype(pos.dtype)


def merge(stacked, count):
    """The Reduce of one table: ``stacked`` is ``(W, N, ...)``, the
    workers' copies; ``count`` is ``(W, N)``, how often each worker
    touched each row.  A row is the touch-weighted mean of the workers'
    rows, broadcast over its trailing axes; a row no worker touched is the
    plain mean."""
    w = count.reshape(count.shape + (1,) * (stacked.ndim - 2))
    total = jnp.sum(w, axis=0)
    weighted = jnp.sum(stacked.astype(jnp.float32) * w, axis=0)
    plain = jnp.mean(stacked.astype(jnp.float32), axis=0)
    return jnp.where(total > 0, weighted / jnp.maximum(total, 1.0),
                     plain).astype(stacked.dtype)


@functools.partial(
    jax.jit, static_argnames=("model", "batch", "margin", "lr", "prec",
                              "fault"))
def _epoch(tables, parts, keys, epoch, *, model, batch, margin, lr, prec,
           fault):
    m = harness.model(model)
    k_data, k_neg = keys
    W, n_w, _ = parts.shape
    steps = n_w // batch
    rows = {m.roles[k]: v.shape[0] for k, v in tables.items()}
    E, R = rows["ent"], rows["rel"]
    start = m.constrain(tables)

    def loss_fn(p, pos, neg):
        if fault == "half_batch":
            pos, neg = pos[: pos.shape[0] // 2], neg[: neg.shape[0] // 2]
        d_pos = m.energy(p, pos, prec)
        d_neg = m.energy(p, neg, prec)
        return jnp.mean(jnp.maximum(0.0, margin + d_pos - d_neg))

    def worker(w):
        kd = jax.random.fold_in(jax.random.fold_in(k_data, epoch), w)
        perm = jax.random.permutation(kd, n_w)[: steps * batch]
        pos = parts[w][perm].reshape(steps, batch, 3)
        kn = jax.random.fold_in(jax.random.fold_in(k_neg, epoch), w)
        neg = jax.vmap(lambda k, p: _corrupt_batch(k, p, E))(
            jax.random.split(kn, steps), pos)

        def step(p, b):
            loss, g = jax.value_and_grad(loss_fn)(p, *b)
            p = jax.tree.map(lambda x, gx: (x - lr * gx).astype(x.dtype),
                             p, g)
            return p, loss

        p, losses = jax.lax.scan(step, start, (pos, neg))
        ents = jnp.concatenate([pos[..., 0], pos[..., 2], neg[..., 0],
                                neg[..., 2]], axis=None)
        e_count = jnp.zeros((E,), jnp.float32).at[ents].add(1.0)
        r_count = jnp.zeros((R,), jnp.float32).at[pos[..., 1].ravel()].add(1.0)
        return p, jnp.mean(losses.astype(jnp.float32)), e_count, r_count

    p, loss, e_count, r_count = jax.vmap(worker)(jnp.arange(W))
    if fault == "no_exchange":
        return {k: v[0] for k, v in p.items()}, jnp.mean(loss)
    count = {"ent": e_count, "rel": r_count}
    merged = {k: merge(v, count[m.roles[k]]) for k, v in p.items()}
    return merged, jnp.mean(loss)


def train(model: str, tables: dict, train_triples: np.ndarray, seed: int,
          *, n_workers: int, batch: int, margin: float, lr: float,
          epochs: int = 3, prec: str = "f32", fault: str | None = None):
    """Per-epoch losses and the tables after each of ``epochs`` epochs
    (float32 numpy), from ``tables`` and the program seed ``seed``."""
    root = jax.random.fold_in(jax.random.PRNGKey(seed), STREAM_TAG)
    k_data, k_neg, _ = jax.random.split(root, 3)
    parts = jnp.asarray(split_workers(seed, train_triples, n_workers))
    p = cast(tables, prec)
    losses, states = [], []
    for e in range(epochs):
        p, loss = _epoch(p, parts, (k_data, k_neg), jnp.int32(e),
                         model=model, batch=batch, margin=margin, lr=lr,
                         prec=prec, fault=fault)
        losses.append(float(loss))
        states.append({k: np.asarray(v, np.float32) for k, v in p.items()})
    return losses, states
