"""Knowledge graphs shaped like a public one, generated from a seed.

The shape comes from a configuration file: the entity, relation and split
counts, and the share of relations in each of Bordes et al.'s four
categories.  A relation is 1-to-1, 1-to-MANY, MANY-to-1 or MANY-to-MANY
by the averaged number of tails per head (``tph``) and heads per tail
(``hpt``) over the whole graph, each side MANY at 1.5 or more.  The
configuration's Zipf exponents give the skew of relation frequency and
entity popularity, and its ``many_fan`` the least mean group size of a
MANY side.

Each relation is drawn by its category: a ONE side holds distinct
entities, picked by popularity without replacement; a MANY side draws
from a pool of ``n / many_fan`` entities (picked by popularity), each
triple's member drawn from the pool by popularity, so a popular member
holds a large group.  Self loops and duplicates never occur.  The
structure is drawn once from the configuration's ``graph_seed``, so every
run of a cell has the same group sizes, filter width and compiled shapes;
``relabel`` gives a seed its own graph of identical shape.
"""
from __future__ import annotations

import dataclasses

import numpy as np

CATEGORIES = ("1-1", "1-N", "N-1", "N-N")


@dataclasses.dataclass
class Graph:
    n_entities: int
    n_relations: int
    train: np.ndarray          # (N, 3) int32 rows of (h, r, t)
    valid: np.ndarray
    test: np.ndarray

    @property
    def all_triples(self) -> np.ndarray:
        return np.concatenate([self.train, self.valid, self.test])


def zipf_probs(n: int, s: float) -> np.ndarray:
    w = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** s
    return w / w.sum()


def split_counts(total: int, shares) -> np.ndarray:
    """``total`` cut in proportion to ``shares`` by largest remainders."""
    exact = total * np.asarray(shares, np.float64) / np.sum(shares)
    out = np.floor(exact).astype(np.int64)
    out[np.argsort(out - exact)[: total - out.sum()]] += 1
    return out


def _pick(rng, logp: np.ndarray, n: int) -> np.ndarray:
    """``n`` distinct entities, picked by popularity without replacement
    (the Gumbel top-``n``), in a random order."""
    keys = logp + rng.gumbel(size=len(logp))
    return rng.permutation(np.argpartition(-keys, n - 1)[:n])


def _many(rng, p: np.ndarray, n: int, fan: float) -> np.ndarray:
    """``n`` draws from a pool of ``ceil(n / fan)`` entities picked by
    popularity, each draw weighted by popularity within the pool."""
    pool = _pick(rng, np.log(p), min(len(p), -(-n // int(fan))))
    w = p[pool] / p[pool].sum()
    return pool[rng.choice(len(pool), n, p=w)]


def _relation(rng, p: np.ndarray, n: int, cat: str, fan: float):
    """(heads, tails) of ``n`` distinct pairs with no self loop, drawn as
    1-to-1, 1-to-MANY or MANY-to-MANY; a MANY-to-1 relation is a
    1-to-MANY one read backwards."""
    E = len(p)
    logp = np.log(p)
    if cat == "N-N":
        hp = _pick(rng, logp, min(E, -(-n // int(fan))))
        tp = _pick(rng, logp, min(E, -(-n // int(fan))))
        wh, wt = p[hp] / p[hp].sum(), p[tp] / p[tp].sum()
        h = t = np.empty(0, np.int64)
        while len(h) < n:
            m = 2 * (n - len(h)) + 64
            h = np.concatenate([h, hp[rng.choice(len(hp), m, p=wh)]])
            t = np.concatenate([t, tp[rng.choice(len(tp), m, p=wt)]])
            keep = h != t
            h, t = h[keep], t[keep]
            _, first = np.unique(h * E + t, return_index=True)
            first = np.sort(first)[:n]
            h, t = h[first], t[first]
        return h, t
    one = _pick(rng, logp, n) if cat == "1-1" else _many(rng, p, n, fan)
    spare = _pick(rng, logp, min(E, n + 64))
    other, extra = spare[:n].copy(), list(spare[n:])
    for i in np.flatnonzero(other == one):
        while extra[-1] == one[i]:
            extra.insert(0, extra.pop())
        other[i] = extra.pop()
    return one, other


def categorize(triples: np.ndarray, n_entities: int,
               n_relations: int) -> np.ndarray:
    """Each relation's category by Bordes et al.'s rule: index into
    ``CATEGORIES`` (-1 for a relation with no triple)."""
    h = triples[:, 0].astype(np.int64)
    r = triples[:, 1].astype(np.int64)
    t = triples[:, 2].astype(np.int64)
    n = np.bincount(r, minlength=n_relations).astype(np.float64)
    heads = np.bincount(np.unique(r * n_entities + h) // n_entities,
                        minlength=n_relations)
    tails = np.bincount(np.unique(r * n_entities + t) // n_entities,
                        minlength=n_relations)
    with np.errstate(divide="ignore", invalid="ignore"):
        tph, hpt = n / heads, n / tails
    cat = 2 * (hpt >= 1.5) + (tph >= 1.5)
    return np.where(n > 0, cat, -1)


def _structure(shape: dict) -> Graph:
    E, R = shape["n_entities"], shape["n_relations"]
    sizes = (shape["n_train"], shape["n_valid"], shape["n_test"])
    total = sum(sizes)
    fan = shape["many_fan"]
    rng = np.random.default_rng(shape["graph_seed"])
    p = zipf_probs(E, shape["entity_zipf"])[rng.permutation(E)]
    n_rel = split_counts(total, zipf_probs(R, shape["relation_zipf"]))
    n_rel = n_rel[rng.permutation(R)]
    shares = [shape["relation_categories"][c] for c in CATEGORIES]
    cats = np.repeat(np.arange(4), split_counts(R, shares))
    cats = cats[rng.permutation(R)]
    # a relation of more triples than half the entities keeps no ONE side
    # distinct: it trades its category with the smallest MANY-to-MANY one
    big = n_rel > E // 2
    for r in np.flatnonzero(big & (cats != 3)):
        swap = np.flatnonzero((cats == 3) & ~big)
        if len(swap):
            cats[swap[np.argmin(n_rel[swap])]] = cats[r]
        cats[r] = 3
    heads, tails, rels = [], [], []
    for r in range(R):
        cat = CATEGORIES[cats[r]]
        h, t = _relation(rng, p, int(n_rel[r]), "1-N" if cat == "N-1"
                         else cat, fan)
        if cat == "N-1":
            h, t = t, h
        heads.append(h)
        tails.append(t)
        rels.append(np.full(len(h), r))
    trip = np.stack([np.concatenate(heads), np.concatenate(rels),
                     np.concatenate(tails)], 1).astype(np.int32)
    trip = trip[rng.permutation(total)]
    a, b = sizes[0], sizes[0] + sizes[1]
    return Graph(E, R, trip[:a], trip[a:b], trip[b:])


_CACHE: dict = {}


def structure(shape: dict) -> Graph:
    """The configuration's graph, as ``graph_seed`` draws it."""
    key = tuple(sorted((k, str(v)) for k, v in shape.items()))
    if key not in _CACHE:
        _CACHE[key] = _structure(shape)
    return _CACHE[key]


def relabel(g: Graph, seed: int) -> Graph:
    """``g`` with its entities and relations renamed by ``seed``."""
    rng = np.random.default_rng(seed)
    ent = rng.permutation(g.n_entities).astype(np.int32)
    rel = rng.permutation(g.n_relations).astype(np.int32)

    def names(t: np.ndarray) -> np.ndarray:
        return np.stack([ent[t[:, 0]], rel[t[:, 1]], ent[t[:, 2]]], 1)

    return Graph(g.n_entities, g.n_relations, names(g.train),
                 names(g.valid), names(g.test))


def generate(shape: dict, seed: int) -> Graph:
    """The configuration's graph, relabelled by ``seed``."""
    return relabel(structure(shape), seed)


def group_sizes(triples: np.ndarray, n_entities: int, n_relations: int):
    """Sizes of the known groups: tails per (h, r) and heads per (r, t)."""
    h = triples[:, 0].astype(np.int64)
    r = triples[:, 1].astype(np.int64)
    t = triples[:, 2].astype(np.int64)
    _, by_hr = np.unique(h * n_relations + r, return_counts=True)
    _, by_rt = np.unique(r * n_entities + t, return_counts=True)
    return by_hr, by_rt
