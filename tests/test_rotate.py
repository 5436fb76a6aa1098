"""RotatE against a plain reference written here, on a small graph with
seeded random tables (CPU).

The reference is float64 numpy with complex numbers: entity rows ``[re |
im]`` become ``re + 1j * im``, a relation value ``v`` the rotation
``exp(1j * v * pi / ((24 + 2) / k))``, and the energy the sum of
moduli.  The program computes in float32
and, for the entity scans, from the rotated query ``t conj(r)`` on the
head side, so energies agree to float32 rounding (``RTOL``), and a rank
is held to the band of ranks the reference gives within that rounding.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import kg as kg_api
from repro.core import eval_device, mapreduce
from repro.core.models import KGConfig, get_model
from repro.core.models import rotate as rotate_lib
from repro.data import kg as kg_lib
from repro.kb import KnowledgeBase
from repro.kernels import rank_topk
from repro.serve import KGServer

K = 8                       # complex columns
# float32 energies of about K terms against float64: relative rounding
RTOL, ATOL = 2e-5, 1e-5


@pytest.fixture(scope="module")
def graph():
    return kg_lib.synthetic_kg(0, n_entities=300, n_relations=6,
                               n_triplets=2500)


@pytest.fixture(scope="module")
def params(graph):
    cfg = KGConfig(n_entities=graph.n_entities,
                   n_relations=graph.n_relations, dim=K)
    return get_model("rotate").init_params(jax.random.PRNGKey(3), cfg)


# -- the plain reference ------------------------------------------------------

def _complex(params):
    ent = np.asarray(params["ent"], np.float64)
    k = ent.shape[1] // 2
    phase = np.asarray(params["rel"], np.float64) * math.pi / (26.0 / k)
    return ent[:, :k] + 1j * ent[:, k:], np.exp(1j * phase)


def _dist(z):
    return np.abs(z).sum(-1)


def ref_energy(params, trip):
    e, r = _complex(params)
    trip = np.asarray(trip)
    return _dist(e[trip[..., 0]] * r[trip[..., 1]] - e[trip[..., 2]])


def ref_candidates(params, trip, side):
    """(B, E): every entity substituted as ``side``, by the energy itself."""
    e, r = _complex(params)
    trip = np.asarray(trip)
    h, rr, t = e[trip[:, 0]], r[trip[:, 1]], e[trip[:, 2]]
    if side == "tail":
        z = (h * rr)[:, None, :] - e[None]
    else:
        z = e[None] * rr[:, None, :] - t[:, None, :]
    return _dist(z)


def ref_relations(params, trip):
    e, r = _complex(params)
    trip = np.asarray(trip)
    h, t = e[trip[:, 0]], e[trip[:, 2]]
    return _dist(h[:, None, :] * r[None] - t[:, None, :])


def rank_band(scores, gold_ids, tol=RTOL):
    """(low, high) rank of each gold entity: 1 + the candidates surely
    closer, 1 + those closer within the float32 rounding."""
    gold = scores[np.arange(len(scores)), gold_ids][:, None]
    low = 1 + np.sum(scores < gold * (1 - tol), axis=1)
    high = 1 + np.sum(scores < gold * (1 + tol), axis=1)
    return low, high


def _assert_close(got, want):
    np.testing.assert_allclose(np.asarray(got, np.float64), want,
                               rtol=RTOL, atol=ATOL)


# -- energies -----------------------------------------------------------------

def test_energy(graph, params):
    trip = graph.test[:50]
    _assert_close(get_model("rotate").energy(params, jnp.asarray(trip)),
                  ref_energy(params, trip))


@pytest.mark.parametrize("hook", ["energy", "candidate_energies",
                                  "relation_energies", "fused_scan_cells"])
def test_other_norms_are_refused(graph, params, hook):
    """RotatE has one energy, the sum of complex moduli: ``norm="l2"``
    is refused, not silently read as another distance."""
    m = get_model("rotate")
    q = jnp.asarray(graph.test[:4])
    call = {
        "energy": lambda: m.energy(params, q, "l2"),
        "candidate_energies": lambda: m.candidate_energies(
            params, q, "tail", "l2"),
        "relation_energies": lambda: m.relation_energies(params, q, "l2"),
        "fused_scan_cells": lambda: m.fused_scan_cells(params, 4, "l2"),
    }[hook]
    with pytest.raises(ValueError, match="complex moduli"):
        call()


def test_tables_and_init_range(params, graph):
    assert params["ent"].shape == (graph.n_entities, 2 * K)
    assert params["rel"].shape == (graph.n_relations, K)
    bound = 26.0 / K
    for t in params.values():
        assert float(jnp.max(jnp.abs(t))) <= bound
    m = get_model("rotate")
    assert m.normalize(params)["ent"] is params["ent"]
    assert m.width(params) == K


@pytest.mark.parametrize("side", ["tail", "head"])
def test_candidate_and_slice_energies(graph, params, side):
    m = get_model("rotate")
    q = jnp.asarray(graph.test[:20])
    full = m.candidate_energies(params, q, side)
    _assert_close(full, ref_candidates(params, q, side))
    part = m.candidate_slice_energies(params, q, side, lo=37, n=101)
    np.testing.assert_array_equal(np.asarray(part),
                                  np.asarray(full)[:, 37:138])


def test_relation_energies(graph, params):
    q = jnp.asarray(graph.test[:20])
    _assert_close(get_model("rotate").relation_energies(params, q),
                  ref_relations(params, q))


def test_joint_energies(graph, params):
    m = get_model("rotate")
    pos = jnp.asarray(graph.train[:16])
    cand = jnp.asarray(graph.train[16:26, 2])
    side_head = jnp.asarray(np.arange(16) % 3 == 0)
    got = m.joint_energies(params, pos, cand, side_head, "l1")
    p = np.asarray(pos)
    want = np.empty((16, 10))
    for j, c in enumerate(np.asarray(cand)):
        trip = p.copy()
        trip[:, 0] = np.where(np.asarray(side_head), c, p[:, 0])
        trip[:, 2] = np.where(np.asarray(side_head), p[:, 2], c)
        want[:, j] = ref_energy(params, trip)
    _assert_close(got, want)


def test_scans_run_block_by_block(graph, params, monkeypatch):
    """Candidate rows beyond ``BLOCK`` are scored a block at a time (no
    ``(B, E, 2k)`` array); the result is the unblocked one."""
    m = get_model("rotate")
    q = jnp.asarray(graph.test[:9])
    whole = np.asarray(m.candidate_energies(params, q, "tail"))
    rels = np.asarray(m.relation_energies(params, q))
    monkeypatch.setattr(rotate_lib, "BLOCK", 64)
    jaxpr = jax.make_jaxpr(
        lambda p: m.candidate_energies(p, q, "tail"))(params)
    assert "scan" in str(jaxpr)
    _assert_close(m.candidate_energies(params, q, "tail"), whole)
    _assert_close(m.relation_energies(params, q), rels)


# -- the rank kernel ----------------------------------------------------------

@pytest.mark.parametrize("side", ["tail", "head"])
def test_kernel_counts_within_the_exact_band(graph, params, side):
    """The kernel in interpret mode against exact counts: it sums the
    moduli 128 lanes at a time, and the gold distance beside it, in
    another order than numpy, so a candidate within float32 rounding of
    the gold may fall on either side; the count lies in the reference's
    band."""
    m = get_model("rotate")
    q = jnp.asarray(graph.test[:40])
    got = np.asarray(m.fused_rank_counts(params, q, side, norm="l1",
                                         interpret=True))
    gold = graph.test[:40, 2 if side == "tail" else 0]
    low, high = rank_band(ref_candidates(params, q, side), gold)
    assert np.all((got + 1 >= low) & (got + 1 <= high)), (got, low, high)


def test_modulus_kernel_pads_each_half_with_zeros():
    """Columns past k, in either half, add exactly nothing: a 130-column
    problem (two 128-lane slices a half) counts as numpy does."""
    rng = np.random.default_rng(0)
    k, B, E = 130, 5, 70
    q = rng.normal(size=(B, 2 * k)).astype(np.float32)
    tab = rng.normal(size=(E, 2 * k)).astype(np.float32)
    d = np.abs((q[:, None, :k] - tab[None, :, :k])
               + 1j * (q[:, None, k:] - tab[None, :, k:])).sum(-1)
    gold = d[:, 3].astype(np.float32) * np.float32(1.0 + 1e-4)
    got = rank_topk.modulus_rank_counts(
        jnp.asarray(q), jnp.asarray(tab), jnp.asarray(gold), interpret=True)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.sum(d < gold[:, None], axis=1))


def test_scan_cells_count_the_padding():
    scanned, useful = rank_topk.scan_cells(48, 14951, 1000, modulus=True)
    assert useful == 48 * 14951 * 2000
    tb, te = rank_topk._tiles(48, 14951, "l1", 2048, 2)
    assert (tb, te) == (16, 256)
    assert scanned == 48 * (-(-14951 // 256) * 256) * 2048
    scanned, useful = rank_topk.scan_cells(256, 14951, 400)
    assert useful == 256 * 14951 * 400
    assert scanned == 256 * 15360 * 512


# -- training -----------------------------------------------------------------

def _ref_fit(params, train, *, seed, n_workers, batch, margin, lr, epochs):
    """The device pipeline's epochs, plainly: the documented split and key
    scheme, mean-hinge SGD on each worker's batches with one corrupted
    head or tail each, and the touch-weighted average Reduce."""
    E = params["ent"].shape[0]
    R = params["rel"].shape[0]
    k = params["rel"].shape[1]

    def energy(p, trip):
        re, im = p["ent"][:, :k], p["ent"][:, k:]
        ph = p["rel"][trip[:, 1]] * (math.pi / (26.0 / k))
        c, s = jnp.cos(ph), jnp.sin(ph)
        h, t = trip[:, 0], trip[:, 2]
        dre = re[h] * c - im[h] * s - re[t]
        dim = re[h] * s + im[h] * c - im[t]
        return jnp.sum(jnp.sqrt(dre * dre + dim * dim), axis=-1)

    @jax.jit
    @jax.value_and_grad
    def loss_grad(p, pos, neg):
        return jnp.mean(jnp.maximum(0.0, margin + energy(p, pos)
                                    - energy(p, neg)))

    root = jax.random.fold_in(jax.random.PRNGKey(seed), 0xD417A)
    k_data, k_neg, _ = jax.random.split(root, 3)
    perm = np.random.default_rng(seed).permutation(len(train))
    per = len(train) // n_workers
    parts = train[perm[:per * n_workers].reshape(n_workers, per)]
    steps = per // batch
    p = {n: jnp.asarray(v) for n, v in params.items()}
    losses = []
    for epoch in range(epochs):
        outs, counts = [], []
        for w in range(n_workers):
            kd = jax.random.fold_in(jax.random.fold_in(k_data, epoch), w)
            order = jax.random.permutation(kd, per)[:steps * batch]
            pos = jnp.asarray(parts[w])[order].reshape(steps, batch, 3)
            kn = jax.random.fold_in(jax.random.fold_in(k_neg, epoch), w)
            pw, step_losses = dict(p), []
            e_count = np.zeros(E)
            r_count = np.zeros(R)
            for i, key in enumerate(jax.random.split(kn, steps)):
                k_side, k_ent = jax.random.split(key)
                b = pos[i]
                head = jax.random.bernoulli(k_side, 0.5, (batch,))
                off = jax.random.randint(k_ent, (batch,), 1, E)
                neg = jnp.stack([
                    jnp.where(head, (b[:, 0] + off) % E, b[:, 0]), b[:, 1],
                    jnp.where(head, b[:, 2], (b[:, 2] + off) % E)], 1)
                loss, g = loss_grad(pw, b, neg)
                pw = {n: pw[n] - lr * g[n] for n in pw}
                step_losses.append(float(loss))
                for ids in (b[:, 0], b[:, 2], neg[:, 0], neg[:, 2]):
                    np.add.at(e_count, np.asarray(ids), 1.0)
                np.add.at(r_count, np.asarray(b[:, 1]), 1.0)
            outs.append(pw)
            counts.append({"ent": e_count, "rel": r_count})
            losses.append(np.mean(step_losses))
        merged = {}
        for n in p:
            stack = np.stack([np.asarray(o[n], np.float64) for o in outs])
            c = np.stack([cw[n] for cw in counts])[:, :, None]
            tot = c.sum(0)
            merged[n] = np.where(tot > 0, (stack * c).sum(0)
                                 / np.maximum(tot, 1.0), stack.mean(0))
        p = {n: jnp.asarray(v, jnp.float32) for n, v in merged.items()}
    per_epoch = np.asarray(losses).reshape(epochs, n_workers).mean(1)
    return p, per_epoch


@pytest.mark.parametrize("step", ["compact", "dense"])
def test_two_fit_epochs_match_the_reference(graph, params, monkeypatch, step):
    """Two ``kg.fit`` epochs on the device pipeline, on the compact Map
    (16 x 9 < 300 entities) and with it patched off, against the plain
    epochs: losses and tables to float32 rounding of the sums."""
    if step == "dense":
        monkeypatch.setattr(mapreduce, "compact_map", lambda *a, **k: False)
    kw = dict(seed=0, n_workers=2, batch=16, margin=1.0, lr=0.05, epochs=2)
    res = kg_api.fit(
        graph, model="rotate", paradigm="sgd", epochs=2, dim=K,
        learning_rate=kw["lr"], margin=kw["margin"],
        batch_size=kw["batch"], n_workers=kw["n_workers"], backend="vmap",
        pipeline="device", merge_transport="dense", strategy="average",
        block_epochs=1, seed=0, params=params)
    want, losses = _ref_fit(params, np.asarray(graph.train), **kw)
    np.testing.assert_allclose(res.loss_history, losses, rtol=1e-5)
    for n in want:
        np.testing.assert_allclose(np.asarray(res.params[n]),
                                   np.asarray(want[n]), rtol=1e-4,
                                   atol=1e-5, err_msg=n)
        assert not np.allclose(np.asarray(res.params[n]),
                               np.asarray(params[n]))


@pytest.mark.parametrize("negatives", ["pertriplet", "joint"])
@pytest.mark.parametrize("transport", ["dense", "sparse"])
def test_fit_trains_every_way(graph, negatives, transport):
    res = kg_api.fit(graph, model="rotate", paradigm="sgd", epochs=2,
                     dim=K, batch_size=59, n_workers=2, negatives=negatives,
                     merge_transport=transport, learning_rate=0.05)
    assert np.all(np.isfinite(res.loss_history))
    assert res.params["ent"].shape == (graph.n_entities, 2 * K)


# -- evaluation and serving ---------------------------------------------------

def test_device_eval_ranks(graph, params):
    """``kg.evaluate(engine="device")``'s ranks: equal to the host
    engine's, and each raw rank within the reference's band."""
    test = graph.test
    out = eval_device.entity_ranks_device(
        params, test, "l1", graph.eval_filter_candidates(), model="rotate",
        relations=True)
    for side, col in (("tail", 2), ("head", 0)):
        low, high = rank_band(ref_candidates(params, test, side), test[:, col])
        got = out["raw_ranks"][side]
        assert np.all((got >= low) & (got <= high))
        assert np.all(out["filtered_ranks"][side] <= got)
    low, high = rank_band(ref_relations(params, test), test[:, 1])
    got = out["relation_ranks"]
    assert np.all((got >= low) & (got <= high))
    host = kg_api.evaluate(params, "rotate", graph)
    assert kg_api.evaluate(params, "rotate", graph, engine="device") == host


def test_chunk_follows_row_bytes():
    """TransE's chunk at DGL-KE's width stays 256; RotatE's 2,000-float
    rows take 48, so the filter's gathers keep TransE's size."""
    def spec(width):
        return {"ent": jax.ShapeDtypeStruct((14951, width), jnp.float32)}
    assert eval_device.chunk_rows(spec(400)) == eval_device.DEFAULT_CHUNK
    assert eval_device.chunk_rows(spec(50)) == eval_device.DEFAULT_CHUNK
    assert eval_device.chunk_rows(spec(2000)) == 48


def test_served_rank_takes_the_row_byte_chunk(graph, monkeypatch):
    """At the authors' width (2,000-float entity rows) a served rank scans
    48 queries a step, the eval engine's chunk, not TransE's 256."""
    cfg = KGConfig(n_entities=graph.n_entities,
                   n_relations=graph.n_relations, dim=1000)
    wide = get_model("rotate").init_params(jax.random.PRNGKey(5), cfg)
    kb = KnowledgeBase("rotate", wide, graph=graph)
    assert kb.dim == 1000 and kb.engine().chunk == 48
    seen = []
    scan = eval_device.entity_ranks_device

    def spy(*args, **kw):
        seen.append(kw["chunk"])
        return scan(*args, **kw)

    monkeypatch.setattr(eval_device, "entity_ranks_device", spy)
    rows = graph.test[:5]
    got = kb.engine().rank(rows, "tail")
    assert seen == [48]
    low, high = rank_band(ref_candidates(wide, rows, "tail"), rows[:, 2])
    assert np.all((got >= low) & (got <= high)), (got, low, high)


def test_knowledge_base_and_server(graph, params):
    kb = KnowledgeBase("rotate", params, graph=graph, norm="l1")
    assert kb.dim == K
    rows = graph.test[:6]
    h, r, t = rows[:, 0], rows[:, 1], rows[:, 2]
    tails = kb.query_tails(h, r, k=5)
    want = ref_candidates(params, rows, "tail")
    _assert_close(tails.energies,
                  np.take_along_axis(want, tails.ids, axis=1))
    _assert_close(tails.energies, np.sort(want, axis=1)[:, :5])
    heads = kb.query_heads(t, r, k=5, filtered=True)
    assert np.all(np.diff(heads.energies, axis=1) >= 0)
    rels = kb.query_relations(h, t, k=3)
    _assert_close(rels.energies, np.sort(ref_relations(params, rows),
                                         axis=1)[:, :3])
    _assert_close(kb.score(h, r, t), ref_energy(params, rows))
    with KGServer(kb, max_batch=4, max_wait_us=2000, default_k=5) as srv:
        for (hh, rr, tt), i in zip(rows, range(len(rows))):
            ans = srv.query_tails(hh, rr)
            np.testing.assert_array_equal(ans.ids, tails.ids[i])
            np.testing.assert_allclose(ans.energies, tails.energies[i],
                                       rtol=1e-6)
        ans = srv.query_relations(h[0], t[0], k=3)
        np.testing.assert_array_equal(ans.ids, rels.ids[0])
