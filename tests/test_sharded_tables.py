"""Sharded entity tables (``table_sharding="sharded"``): bit-identity
against the replicated layout across training, eval, and serving.

The acceptance bar (ISSUE 8): with the entity table split into contiguous
row blocks over the mesh axis — sparse deltas routed to their owning
shard in the Reduce, eval and serving scanning only shard-local candidate
blocks — every result is **bitwise** identical to the replicated layout:
final params for every merge strategy x paradigm x pipeline x backend,
block-size invariant and checkpoint-compatible across layouts; per-query
raw/filtered/relation ranks; and top-k answers including exclusion masks
and exact tie-breaks.  W=3 over 200 entities keeps the shard blocks
ragged (67/67/66 + one pad row), so every cell also exercises the
padded-tail masking.  The fast cross-sections run in tier-1; the full
model x strategy matrix is marked ``slow``; real W=8 shard_map cells live
in tests/helpers/multiworker_check.py (``check_sharded_tables``).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import kg as kg_api
from repro.core import eval_device, merge as merge_lib
from repro.core.models import get_model
from repro.data import kg as kg_lib
from repro.kb import KnowledgeBase
from repro.serve.kg_engine import KGQueryEngine

MODELS = ["transe", "transh", "distmult"]
# the per-model scan contracts (slice energies, sharded ranks and top-k)
CONTRACT_MODELS = MODELS + ["rotate"]
STRATEGIES = list(merge_lib.STRATEGIES)
W = 3          # does not divide n_entities=200: ragged shard blocks


@pytest.fixture(scope="module")
def graph():
    return kg_lib.synthetic_kg(0, n_entities=200, n_relations=5,
                               n_triplets=1200)


@pytest.fixture(scope="module")
def masks(graph):
    """Filtered-ranking candidate masks for the first N test rows, aligned
    with the ``graph.test[:N]`` slices the eval cells query."""
    tails, heads = graph.eval_filter_candidates()

    def take(n):
        return tails[:n], heads[:n]

    return take


def _fit(graph, **kw):
    defaults = dict(model="transe", paradigm="sgd", backend="vmap",
                    n_workers=W, dim=8, learning_rate=0.05, batch_size=83,
                    seed=0, epochs=3, merge_transport="sparse")
    defaults.update(kw)
    return kg_api.fit(graph, **defaults)


def _assert_identical(r1, r2):
    np.testing.assert_array_equal(
        np.asarray(r1.loss_history, np.float32),
        np.asarray(r2.loss_history, np.float32))
    assert set(r1.params) == set(r2.params)
    for k in r1.params:
        np.testing.assert_array_equal(
            np.asarray(r1.params[k]), np.asarray(r2.params[k]),
            err_msg=f"table {k}")


def _pair(graph, **kw):
    rep = _fit(graph, table_sharding="replicated", **kw)
    sh = _fit(graph, table_sharding="sharded", **kw)
    return rep, sh


def _params(graph, model_name, seed=0, dim=8):
    model = get_model(model_name)
    kcfg, _ = kg_api.make_configs(graph, model=model_name, dim=dim)
    return model, model.init_params(jax.random.PRNGKey(seed), kcfg)


# ---------------------------------------------------------------------------
# Training: shard-routed Reduce == replicated Reduce, bitwise
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("strategy", STRATEGIES)
def test_sharded_matches_replicated_host(graph, strategy):
    """Every merge strategy, host pipeline: the per-shard candidate union
    + local merge reassembles exactly the replicated merge's output."""
    _assert_identical(*_pair(graph, strategy=strategy))


def test_sharded_matches_replicated_device(graph):
    """Device pipeline with deferred Reduces: K local epochs of drift
    between shard-routed merges."""
    _assert_identical(*_pair(
        graph, pipeline="device", epochs=4, block_epochs=2, merge_every=2,
        strategy="average_all"))


@pytest.mark.parametrize("pipeline", ["host", "device"])
def test_sharded_matches_replicated_bgd(graph, pipeline):
    kw = dict(paradigm="bgd", pipeline=pipeline)
    if pipeline == "device":
        kw.update(epochs=4, block_epochs=2)
    _assert_identical(*_pair(graph, **kw))


def test_sharded_matches_replicated_shard_map(graph):
    """In-process single-device mesh; real W=8 shard_map bit-identity is
    covered by tests/helpers/multiworker_check.py."""
    mesh = jax.make_mesh((1,), ("workers",))
    _assert_identical(*_pair(
        graph, backend="shard_map", mesh=mesh, n_workers=1, batch_size=187,
        pipeline="device", epochs=4, block_epochs=2))


def test_sharded_block_size_invariant(graph):
    kw = dict(pipeline="device", table_sharding="sharded", epochs=4,
              merge_every=2)
    _assert_identical(_fit(graph, block_epochs=2, **kw),
                      _fit(graph, block_epochs=4, **kw))


def test_sharded_requires_sparse_transport(graph):
    with pytest.raises(ValueError, match="merge_transport='sparse'"):
        _fit(graph, merge_transport="dense", table_sharding="sharded")


def test_checkpoint_moves_between_layouts(graph, tmp_path):
    """``table_sharding`` is deliberately absent from the resume manifest:
    a replicated-trained checkpoint resumes under the sharded layout (and
    vice versa) and still reproduces the uninterrupted run exactly."""
    kw = dict(pipeline="device", block_epochs=2, checkpoint_every=2)
    ref = _fit(graph, epochs=4, ckpt_dir=str(tmp_path / "ref"), **kw)
    for first, second in (("replicated", "sharded"),
                          ("sharded", "replicated")):
        d = str(tmp_path / f"{first}-to-{second}")
        _fit(graph, epochs=2, table_sharding=first, ckpt_dir=d, **kw)
        res = _fit(graph, epochs=4, table_sharding=second, ckpt_dir=d,
                   resume=True, **kw)
        for k in ref.params:
            np.testing.assert_array_equal(
                np.asarray(ref.params[k]), np.asarray(res.params[k]),
                err_msg=f"{first}->{second} table {k}")


@pytest.mark.slow
@pytest.mark.parametrize("model", MODELS)
@pytest.mark.parametrize("strategy", STRATEGIES)
def test_sharded_matrix(graph, model, strategy):
    _assert_identical(*_pair(
        graph, model=model, strategy=strategy, pipeline="device", epochs=4,
        block_epochs=2, merge_every=2))


# ---------------------------------------------------------------------------
# The per-model slice contract the sharded scan is built on
# ---------------------------------------------------------------------------

# RotatE has one energy (the sum of complex moduli): l1 only
@pytest.mark.parametrize(
    "norm,model_name",
    [(norm, m) for norm in ("l1", "l2") for m in MODELS] + [("l1", "rotate")])
def test_candidate_slice_energies_contract(graph, model_name, norm):
    """``candidate_slice_energies`` == columns [lo, lo+n) of the full
    score matrix, **bitwise**, for ragged block offsets — the per-model
    contract every shard-local scan rests on (models that slice the
    entity table before scoring must reduce in the same order the full
    matrix does)."""
    model, params = _params(graph, model_name, seed=2)
    rng = np.random.default_rng(0)
    q = jnp.asarray(np.stack([
        rng.integers(0, 200, 16), rng.integers(0, 5, 16),
        rng.integers(0, 200, 16)], axis=1).astype(np.int32))
    for side in ("tail", "head"):
        full = np.asarray(model.candidate_energies(params, q, side, norm))
        for lo, n in ((0, 200), (67, 67), (134, 66), (13, 5)):
            sl = model.candidate_slice_energies(
                params, q, side, norm, lo=jnp.int32(lo), n=n)
            np.testing.assert_array_equal(
                full[:, lo:lo + n], np.asarray(sl),
                err_msg=f"{model_name}/{side}/{norm} lo={lo} n={n}")


# ---------------------------------------------------------------------------
# Eval: shard-local candidate scan + exact cross-shard combine
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("model_name", CONTRACT_MODELS)
def test_sharded_eval_ranks_bitwise(graph, masks, model_name):
    """Raw, filtered, and relation ranks from the sharded scan equal the
    replicated scan's exactly — gold via cross-shard min, counts via
    integer sums, pad rows masked by id."""
    model, params = _params(graph, model_name, seed=1)
    test = graph.test[:48]
    kw = dict(model=model, cand_masks=masks(48), n_workers=W,
              relations=True)
    rep = eval_device.entity_ranks_device(params, test, **kw)
    sh = eval_device.entity_ranks_device(
        params, test, table_sharding="sharded", **kw)
    for group in ("raw_ranks", "filtered_ranks"):
        for side in ("tail", "head"):
            np.testing.assert_array_equal(
                rep[group][side], sh[group][side],
                err_msg=f"{model_name} {group}/{side}")
    np.testing.assert_array_equal(rep["relation_ranks"],
                                  sh["relation_ranks"])


def test_sharded_eval_chunk_invariant(graph, masks):
    """The chunked scan layout cannot matter: different chunk sizes give
    identical sharded ranks (queries pad, never split, across shards)."""
    model, params = _params(graph, "transe", seed=1)
    test = graph.test[:32]
    outs = [eval_device.entity_ranks_device(
        params, test, model=model, cand_masks=masks(32), n_workers=W,
        table_sharding="sharded", chunk=c) for c in (8, 64)]
    for side in ("tail", "head"):
        np.testing.assert_array_equal(outs[0]["raw_ranks"][side],
                                      outs[1]["raw_ranks"][side])


def test_sharded_eval_shard_map_single_device(graph, masks):
    model, params = _params(graph, "transe", seed=1)
    test = graph.test[:24]
    rep = eval_device.entity_ranks_device(
        params, test, model=model, cand_masks=masks(24), n_workers=1)
    sh = eval_device.entity_ranks_device(
        params, test, model=model, cand_masks=masks(24), n_workers=1,
        backend="shard_map", mesh=jax.make_mesh((1,), ("workers",)),
        table_sharding="sharded")
    for side in ("tail", "head"):
        np.testing.assert_array_equal(rep["raw_ranks"][side],
                                      sh["raw_ranks"][side])


def test_sharded_eval_rejects_fused_and_bad_value(graph):
    model, params = _params(graph, "transe")
    with pytest.raises(ValueError, match="fused"):
        eval_device.entity_ranks_device(
            params, graph.test[:4], model=model, n_workers=W, fused=True,
            table_sharding="sharded")
    with pytest.raises(ValueError, match="table_sharding"):
        eval_device.entity_ranks_device(
            params, graph.test[:4], model=model, table_sharding="diagonal")


# ---------------------------------------------------------------------------
# Serving: shard-local top-k + cross-shard combine, ties exact
# ---------------------------------------------------------------------------

def _engines(graph, model_name, **kw):
    model, params = _params(graph, model_name, seed=3)
    rep = KGQueryEngine(model, params, n_workers=W, **kw)
    sh = KGQueryEngine(model, params, n_workers=W,
                       table_sharding="sharded", **kw)
    return rep, sh


def _assert_query_equal(a, b, label=""):
    np.testing.assert_array_equal(a.ids, b.ids, err_msg=label)
    np.testing.assert_array_equal(a.energies, b.energies, err_msg=label)


@pytest.mark.parametrize("model_name", CONTRACT_MODELS)
def test_sharded_topk_bitwise(graph, model_name):
    """k < R, k > R (local kk cut), and k = E (full table) — every local
    cut provably keeps each global winner, so the combined top-k matches
    the replicated one bitwise, ids and energies."""
    rep, sh = _engines(graph, model_name)
    rows = graph.test[:12]
    h, r, t = rows[:, 0], rows[:, 1], rows[:, 2]
    for k in (5, 80, 200):
        _assert_query_equal(rep.query_tails(h, r, k=k),
                            sh.query_tails(h, r, k=k),
                            f"{model_name} tails k={k}")
        _assert_query_equal(rep.query_heads(t, r, k=k),
                            sh.query_heads(t, r, k=k),
                            f"{model_name} heads k={k}")
    _assert_query_equal(rep.query_relations(h, t, k=3),
                        sh.query_relations(h, t, k=3))


def test_sharded_topk_with_exclusion(graph):
    """Exclusion ids scatter into their owning shard's slice only; the
    padded exclusion sentinel (id = E) lands in no shard."""
    rep, sh = _engines(graph, "transe")
    rows = graph.test[:6]
    h, r = rows[:, 0], rows[:, 1]
    base = rep.query_tails(h, r, k=8)
    ex = np.sort(base.ids[:, :3].astype(np.int32), axis=1)
    ex = np.concatenate(     # ragged width + explicit pad sentinels
        [ex, np.full((len(ex), 2), 200, np.int32)], axis=1)
    a = rep.query_tails(h, r, k=8, exclude=ex)
    b = sh.query_tails(h, r, k=8, exclude=ex)
    _assert_query_equal(a, b, "excluded tails")
    for i in range(len(ex)):
        assert not set(ex[i, :3].tolist()) & set(
            int(x) for x in b.ids[i][np.isfinite(b.energies[i])])


def test_sharded_topk_tie_break_exact(graph):
    """All-zero tables tie every candidate; lax.top_k breaks ties toward
    the lowest index, and the shard-major combine preserves exactly that
    global order — so even fully degenerate scores pick identical ids."""
    model = get_model("transe")
    params = {"ent": jnp.zeros((200, 8)), "rel": jnp.zeros((5, 8))}
    rep = KGQueryEngine(model, params, n_workers=W)
    sh = KGQueryEngine(model, params, n_workers=W, table_sharding="sharded")
    q = np.zeros(4, np.int32)
    for k in (5, 67, 80):
        _assert_query_equal(rep.query_tails(q, q, k=k),
                            sh.query_tails(q, q, k=k), f"ties k={k}")


def test_sharded_rank_and_score_parity(graph, masks):
    """The engine's rank() threads table_sharding into the eval scan;
    score() never shards (full-row lookups)."""
    rep, sh = _engines(graph, "transe")
    rows = graph.test[:16]
    np.testing.assert_array_equal(rep.rank(rows, "tail"),
                                  sh.rank(rows, "tail"))
    np.testing.assert_array_equal(
        rep.score(rows[:, 0], rows[:, 1], rows[:, 2]),
        sh.score(rows[:, 0], rows[:, 1], rows[:, 2]))


def test_engine_rejects_bad_sharding_config(graph):
    model, params = _params(graph, "transe")
    with pytest.raises(ValueError, match="table_sharding"):
        KGQueryEngine(model, params, n_workers=W, table_sharding="nope")
    with pytest.raises(ValueError, match="mesh"):
        KGQueryEngine(model, params, n_workers=2, backend="shard_map",
                      table_sharding="sharded")


# ---------------------------------------------------------------------------
# End-to-end threading: kg.fit knob, KnowledgeBase engines, evaluate
# ---------------------------------------------------------------------------

def test_kb_engine_cache_keys_on_sharding(graph):
    model, params = _params(graph, "transe")
    kb = KnowledgeBase(model, params, graph=graph)
    sh = kb.engine(n_workers=W, table_sharding="sharded")
    assert kb.engine(n_workers=W, table_sharding="sharded") is sh
    assert kb.engine(n_workers=W) is not sh
    assert sh.table_sharding == "sharded"


def test_kb_evaluate_sharded_parity(graph):
    """The full three-task protocol through the public artifact API:
    metrics from the sharded device engine equal the replicated ones."""
    model, params = _params(graph, "transe", seed=4)
    kb = KnowledgeBase(model, params, graph=graph)
    rep = kb.evaluate(engine="device", n_workers=W)
    sh = kb.evaluate(engine="device", n_workers=W,
                     table_sharding="sharded")
    assert rep == sh


def test_fit_threads_sharding_into_result(graph):
    """kg.fit(table_sharding=...) flows into MapReduceConfig — the pair
    helper above depends on it, pin it explicitly once."""
    _, mcfg = kg_api.make_configs(graph, merge_transport="sparse",
                                  table_sharding="sharded")
    assert mcfg.table_sharding == "sharded"
    with pytest.raises(ValueError, match="merge_transport"):
        kg_api.make_configs(graph, table_sharding="sharded")
