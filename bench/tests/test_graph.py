"""The benchmark's graph generator: FB15k's exact counts and relation
categories, no duplicate and no self loop, and a relabelling that keeps
every group size."""
from __future__ import annotations

import numpy as np
import pytest

from bench import graph, harness
from bench.tests import tiny


def _fb15k() -> dict:
    return harness.load_json(harness.find("configs", "transe-fb15k"))["graph"]


@pytest.mark.parametrize("shape", ["fb15k", "tiny"])
def test_counts_categories_and_no_repeats(shape):
    s = _fb15k()
    if shape == "tiny":
        s.update(tiny.GRAPH)
    g = graph.structure(s)
    assert (len(g.train), len(g.valid), len(g.test)) == (
        s["n_train"], s["n_valid"], s["n_test"])
    a = g.all_triples.astype(np.int64)
    assert a.min() >= 0 and a[:, [0, 2]].max() < s["n_entities"]
    assert a[:, 1].max() < s["n_relations"]
    assert not np.any(a[:, 0] == a[:, 2])
    key = (a[:, 0] * s["n_relations"] + a[:, 1]) * s["n_entities"] + a[:, 2]
    assert len(np.unique(key)) == len(key)
    if shape == "fb15k":
        got = np.bincount(graph.categorize(a, s["n_entities"],
                                           s["n_relations"]), minlength=4)
        want = graph.split_counts(
            s["n_relations"],
            [s["relation_categories"][c] for c in graph.CATEGORIES])
        assert got.tolist() == want.tolist()
        assert np.bincount(a[:, 1]).min() > 0


def test_relabelling_keeps_the_shape():
    s = _fb15k()
    s.update(tiny.GRAPH)
    a, b = graph.generate(s, 1), graph.generate(s, 2**40 + 3)
    assert not np.array_equal(a.train, b.train)
    for x, y in zip(graph.group_sizes(a.all_triples, 120, 7),
                    graph.group_sizes(b.all_triples, 120, 7)):
        assert sorted(x) == sorted(y)
