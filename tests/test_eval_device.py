"""Parity, property, and golden-regression tests for the device-resident
evaluation engine (core/eval_device.py) against the frozen host reference
(core/eval.py).

The acceptance bar is *exact* agreement, not closeness: for every model x
task x filtered/raw setting the device engine must produce identical ranks
and identical metric floats, and the worker-sharded run (W=4) must equal
W=1.  The full model matrix is marked ``slow`` (run by the CI slow-suites
job); a transe smoke subset stays in tier-1.

``hypothesis`` is an optional test dep: when absent the property-based test
is skipped and a parametrized fixed-seed fallback covers the same check
path (same pattern as tests/test_kernels_rank_topk.py).
"""
import json
import os
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro.core import eval_device, kg_eval
from repro.core.models import KGConfig, get_model
from repro.data import kg as kg_lib

MODELS = ["transe", "transh", "distmult"]
GOLDEN_PATH = os.path.join(os.path.dirname(__file__), "golden",
                           "eval_golden.json")


@pytest.fixture(scope="module")
def tiny_params(tiny_kg):
    cfg = KGConfig(
        n_entities=tiny_kg.n_entities, n_relations=tiny_kg.n_relations,
        dim=16)
    return {
        name: get_model(name).init_params(jax.random.PRNGKey(2), cfg)
        for name in MODELS
    }


def _assert_entity_parity(tiny_kg, params, model, **device_kw):
    host = kg_eval.entity_inference(
        params, tiny_kg.test, "l1", tiny_kg.known_set(), model=model,
        known_index=tiny_kg.known_index(), return_ranks=True)
    masks = tiny_kg.eval_filter_candidates()
    dev_ranks = eval_device.entity_ranks_device(
        params, tiny_kg.test, "l1", masks, model=model, **device_kw)
    dev = eval_device.entity_inference_device(
        params, tiny_kg.test, "l1", masks, model=model, **device_kw)
    for grp in ("raw_ranks", "filtered_ranks"):
        for side in ("tail", "head"):
            np.testing.assert_array_equal(
                np.asarray(host[grp][side]),
                np.asarray(dev_ranks[grp][side]),
                err_msg=f"{model}/{grp}/{side}")
    assert host["raw"].row() == dev["raw"].row()
    assert host["filtered"].row() == dev["filtered"].row()


# ---------------------------------------------------------------------------
# Exact parity: the full model x task x filter matrix (slow job)
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.parametrize("model", MODELS)
def test_entity_parity_exact(tiny_kg, tiny_params, model):
    _assert_entity_parity(tiny_kg, tiny_params[model], model, n_workers=2)


@pytest.mark.slow
@pytest.mark.parametrize("model", MODELS)
def test_relation_parity_exact(tiny_kg, tiny_params, model):
    host = kg_eval.relation_prediction(
        tiny_params[model], tiny_kg.test, "l1", model=model)
    dev, dev_ranks = eval_device.relation_prediction_device(
        tiny_params[model], tiny_kg.test, "l1", model=model, n_workers=2,
        return_ranks=True)
    # reference ranks rebuilt with the host engine's own scoring function
    scores = np.asarray(kg_eval._relation_scores(
        get_model(model), tiny_params[model], jnp.asarray(tiny_kg.test),
        "l1"))
    gold = scores[np.arange(len(tiny_kg.test)), tiny_kg.test[:, 1]]
    ref_ranks = 1 + (scores < gold[:, None]).sum(axis=1)
    np.testing.assert_array_equal(ref_ranks, np.asarray(dev_ranks))
    assert host.row() == dev.row()


@pytest.mark.slow
@pytest.mark.parametrize("model", MODELS)
def test_triplet_classification_parity_exact(tiny_kg, tiny_params, model):
    host = kg_eval.triplet_classification(
        tiny_params[model], tiny_kg.valid, tiny_kg.test,
        tiny_kg.n_entities, "l1", model=model)
    dev = eval_device.triplet_classification_device(
        tiny_params[model], tiny_kg.valid, tiny_kg.test,
        tiny_kg.n_entities, "l1", model=model)
    assert host == dev


@pytest.mark.slow
@pytest.mark.parametrize("model", MODELS)
@pytest.mark.parametrize("filtered", [True, False])
def test_evaluate_all_parity_exact(tiny_kg, tiny_params, model, filtered):
    host = kg_eval.evaluate_all(
        tiny_params[model], tiny_kg, filtered=filtered, model=model)
    dev = kg_eval.evaluate_all(
        tiny_params[model], tiny_kg, filtered=filtered, model=model,
        engine="device", n_workers=2)
    assert host == dev


@pytest.mark.slow
@pytest.mark.parametrize("model", MODELS)
def test_w4_sharded_equals_w1(tiny_kg, tiny_params, model):
    masks = tiny_kg.eval_filter_candidates()
    r1 = eval_device.entity_ranks_device(
        tiny_params[model], tiny_kg.test, "l1", masks, model=model,
        n_workers=1)
    r4 = eval_device.entity_ranks_device(
        tiny_params[model], tiny_kg.test, "l1", masks, model=model,
        n_workers=4)
    for grp in ("raw_ranks", "filtered_ranks"):
        for side in ("tail", "head"):
            np.testing.assert_array_equal(
                r1[grp][side], r4[grp][side],
                err_msg=f"{model}/{grp}/{side}")


# ---------------------------------------------------------------------------
# Tier-1 smoke subset (transe) — fast cross-section of the matrix above
# ---------------------------------------------------------------------------

def test_parity_smoke_transe(tiny_kg, tiny_params):
    _assert_entity_parity(tiny_kg, tiny_params["transe"], "transe",
                          n_workers=2, chunk=64)
    host = kg_eval.evaluate_all(tiny_params["transe"], tiny_kg,
                                model="transe")
    dev = kg_eval.evaluate_all(tiny_params["transe"], tiny_kg,
                               model="transe", engine="device", n_workers=4)
    assert host == dev


def test_chunk_size_invariance(tiny_kg, tiny_params):
    masks = tiny_kg.eval_filter_candidates()
    a = eval_device.entity_ranks_device(
        tiny_params["transe"], tiny_kg.test, "l1", masks, model="transe",
        chunk=32)
    b = eval_device.entity_ranks_device(
        tiny_params["transe"], tiny_kg.test, "l1", masks, model="transe",
        chunk=256)
    for grp in ("raw_ranks", "filtered_ranks"):
        for side in ("tail", "head"):
            np.testing.assert_array_equal(a[grp][side], b[grp][side])


def test_shard_map_backend_matches_vmap(tiny_kg, tiny_params):
    # in-process single-device mesh, same pattern as the pipeline tests;
    # real multi-device shard_map semantics are covered by tests/helpers.
    # W=2 on the 1-device mesh exercises the multiple-worker-blocks-per-
    # shard path (each shard vmaps over W/M blocks — regression for the
    # bug where only block 0 of each shard was evaluated)
    mesh = jax.make_mesh((1,), ("workers",))
    masks = tiny_kg.eval_filter_candidates()
    v = eval_device.entity_ranks_device(
        tiny_params["transe"], tiny_kg.test, "l1", masks, model="transe",
        n_workers=2)
    s = eval_device.entity_ranks_device(
        tiny_params["transe"], tiny_kg.test, "l1", masks, model="transe",
        backend="shard_map", mesh=mesh, n_workers=2)
    for grp in ("raw_ranks", "filtered_ranks"):
        for side in ("tail", "head"):
            np.testing.assert_array_equal(v[grp][side], s[grp][side])


def test_fused_relation_scan_matches_host_and_standalone(tiny_kg,
                                                         tiny_params):
    """Relation prediction fused into the entity scan body
    (entity_ranks_device(relations=True), what evaluate_all_device runs)
    must equal both the host reference and the standalone relation scan,
    rank for rank."""
    for model in MODELS:
        host_m, host_ranks = kg_eval.relation_prediction(
            tiny_params[model], tiny_kg.test, "l1", model=model,
            return_ranks=True)
        fused = eval_device.entity_ranks_device(
            tiny_params[model], tiny_kg.test, "l1",
            tiny_kg.eval_filter_candidates(), model=model, n_workers=2,
            relations=True)
        np.testing.assert_array_equal(
            host_ranks, fused["relation_ranks"], err_msg=model)
        standalone_m, standalone_ranks = (
            eval_device.relation_prediction_device(
                tiny_params[model], tiny_kg.test, "l1", model=model,
                n_workers=2, return_ranks=True))
        np.testing.assert_array_equal(
            host_ranks, standalone_ranks, err_msg=model)
        assert host_m.row() == standalone_m.row()


def test_tc_negatives_cached_and_identical(tiny_kg, tiny_params):
    """KG.tc_negatives caches the corruption draws (the in-loop eval calls
    the protocol every Reduce round) without changing a single draw."""
    a = tiny_kg.tc_negatives(0)
    b = tiny_kg.tc_negatives(0)
    assert a[0] is b[0] and a[1] is b[1]          # built once, cached
    direct = kg_eval._tc_negatives(
        tiny_kg.valid, tiny_kg.test, tiny_kg.n_entities, 0)
    np.testing.assert_array_equal(a[0], direct[0])
    np.testing.assert_array_equal(a[1], direct[1])
    # and the cached path yields the same accuracy as the self-built one
    tc_cached = eval_device.triplet_classification_device(
        tiny_params["transe"], tiny_kg.valid, tiny_kg.test,
        tiny_kg.n_entities, "l1", model="transe", negatives=a)
    tc_plain = eval_device.triplet_classification_device(
        tiny_params["transe"], tiny_kg.valid, tiny_kg.test,
        tiny_kg.n_entities, "l1", model="transe")
    assert tc_cached == tc_plain


def test_worker_map_validates_backend_and_mesh():
    """worker_map argument validation (the W % mesh-size divisibility check
    needs a multi-device mesh and is exercised by tests/helpers)."""
    from repro.parallel.util import worker_map

    with pytest.raises(ValueError, match="bad backend"):
        worker_map(lambda b, x: x, backend="pmap")
    with pytest.raises(ValueError, match="needs a mesh"):
        worker_map(lambda b, x: x, backend="shard_map")


def test_fused_true_requires_kernel(tiny_kg, tiny_params):
    """Explicit fused=True on a kernel-less model must raise, not silently
    fall back to the jnp path."""
    masks = tiny_kg.eval_filter_candidates()
    with pytest.raises(ValueError, match="no fused Pallas kernel"):
        eval_device.entity_ranks_device(
            tiny_params["distmult"], tiny_kg.test, "l1", masks,
            model="distmult", fused=True)


def test_fused_kernel_path_matches_reference(tiny_kg, tiny_params):
    """The rank_topk Pallas path against the exact jnp path — kernel-test
    tolerance: identical up to last-ulp tie flips.  Off TPU the kernel only
    runs interpreted, which this test asks for explicitly."""
    from jax.experimental.pallas import tpu as pltpu

    masks = tiny_kg.eval_filter_candidates()
    test = tiny_kg.test[:48]
    tmasks = (masks[0][:48], masks[1][:48])
    exact = eval_device.entity_ranks_device(
        tiny_params["transe"], test, "l1", tmasks, model="transe",
        fused=False)
    with pltpu.force_tpu_interpret_mode():
        fused = eval_device.entity_ranks_device(
            tiny_params["transe"], test, "l1", tmasks, model="transe",
            fused=True)
    for grp in ("raw_ranks", "filtered_ranks"):
        for side in ("tail", "head"):
            diff = np.abs(exact[grp][side].astype(np.int64)
                          - fused[grp][side].astype(np.int64))
            assert diff.max() <= 1, (grp, side, diff.max())


def test_fused_auto_resolution_off_tpu(tiny_params):
    """fused=None must resolve to the exact jnp path off TPU (parity by
    default on this container)."""
    from repro.kernels import ops

    model = get_model("transe")
    if jax.default_backend() == "tpu":
        assert ops.fused_eval_available(model)
    else:
        assert not ops.fused_eval_available(model)
    assert not ops.fused_eval_available(get_model("distmult"))


# ---------------------------------------------------------------------------
# Property-based invariants (hypothesis optional, fixed-seed fallback)
# ---------------------------------------------------------------------------

def _check_eval_invariants(seed):
    rng = np.random.default_rng(seed)
    E, R, k, Q, P = 40, 4, 8, 12, 3
    params = {
        "ent": jnp.asarray(rng.normal(size=(E, k)).astype(np.float32)),
        "rel": jnp.asarray(rng.normal(size=(R, k)).astype(np.float32)),
    }
    queries = np.stack([
        rng.integers(0, E, Q), rng.integers(0, R, Q), rng.integers(0, E, Q),
    ], axis=1).astype(np.int32)
    # random known-candidate masks; always include the gold id (as the
    # real masks do — test triplets are known) plus random others, pad = E
    tails = np.full((Q, P), E, np.int32)
    heads = np.full((Q, P), E, np.int32)
    for i in range(Q):
        tails[i, 0] = queries[i, 2]
        heads[i, 0] = queries[i, 0]
        tails[i, 1:] = rng.integers(0, E, P - 1)
        heads[i, 1:] = rng.integers(0, E, P - 1)

    ranks = eval_device.entity_ranks_device(
        params, queries, "l1", (tails, heads), model="transe",
        chunk=8, n_workers=2)
    for side in ("tail", "head"):
        raw = ranks["raw_ranks"][side]
        filt = ranks["filtered_ranks"][side]
        assert np.all(raw >= 1) and np.all(raw <= E), raw
        assert np.all(filt >= 1) and np.all(filt <= E), filt
        assert np.all(filt <= raw), (filt, raw)

    # permutation equivariance of ranks => invariance of every metric
    perm = rng.permutation(Q)
    ranks_p = eval_device.entity_ranks_device(
        params, queries[perm], "l1", (tails[perm], heads[perm]),
        model="transe", chunk=8, n_workers=2)
    for grp in ("raw_ranks", "filtered_ranks"):
        for side in ("tail", "head"):
            np.testing.assert_array_equal(
                ranks_p[grp][side], ranks[grp][side][perm])


@pytest.mark.parametrize("seed", [0, 7, 123, 2**31 - 1])
def test_eval_invariants_fixed_seeds(seed):
    """Non-hypothesis fallback: always runs, fixed corpus of instances."""
    _check_eval_invariants(seed)


if HAVE_HYPOTHESIS:
    @given(seed=st.integers(0, 2**31 - 1))
    @settings(max_examples=10, deadline=None)
    def test_property_eval_invariants(seed):
        _check_eval_invariants(seed)


def test_gold_tie_handling_deterministic():
    """Entities whose score exactly ties the gold never count against the
    rank (strict <), and repeated evaluation is bit-identical."""
    k = 4
    ent = np.zeros((5, k), np.float32)
    ent[0] = 0.0                     # head
    ent[1] = 1.0                     # gold tail: d = ||h + r - t|| = 0
    ent[2] = 1.0                     # exact tie with gold
    ent[3] = 0.5                     # strictly closer? d = 2.0 > 0 -> no
    ent[4] = 9.0                     # far
    rel = np.ones((1, k), np.float32)
    params = {"ent": jnp.asarray(ent), "rel": jnp.asarray(rel)}
    queries = np.array([[0, 0, 1]], np.int32)
    masks = (np.array([[1, 2]], np.int32), np.array([[0, 5]], np.int32))
    a = eval_device.entity_ranks_device(
        params, queries, "l1", masks, model="transe")
    b = eval_device.entity_ranks_device(
        params, queries, "l1", masks, model="transe")
    # gold distance 0; no entity is strictly closer; the tie (ent 2) and the
    # known candidate (also ent 2) are both excluded
    assert a["raw_ranks"]["tail"][0] == 1
    assert a["filtered_ranks"]["tail"][0] == 1
    for grp in ("raw_ranks", "filtered_ranks"):
        for side in ("tail", "head"):
            np.testing.assert_array_equal(a[grp][side], b[grp][side])


# ---------------------------------------------------------------------------
# Data-layer filter structures
# ---------------------------------------------------------------------------

def test_filter_candidates_cached_and_exact(tiny_kg):
    a = tiny_kg.eval_filter_candidates()
    b = tiny_kg.eval_filter_candidates()
    assert a[0] is b[0] and a[1] is b[1]          # built once, cached
    by_hr, by_rt = tiny_kg.known_index()
    pad = tiny_kg.n_entities
    for i, (h, r, t) in enumerate(tiny_kg.test[:20].tolist()):
        row = [e for e in a[0][i].tolist() if e != pad]
        assert row == by_hr[(h, r)]
        row = [e for e in a[1][i].tolist() if e != pad]
        assert row == by_rt[(r, t)]


def test_filter_candidates_truncation_warns_once(tiny_kg):
    g = kg_lib.synthetic_kg(3, n_entities=150, n_relations=4,
                            n_triplets=1500)
    with pytest.warns(UserWarning, match="truncates the filtered-known"):
        t1, h1 = g.eval_filter_candidates(max_fanout=1)
    assert t1.shape[1] == 1 and h1.shape[1] == 1
    with warnings.catch_warnings():
        warnings.simplefilter("error")            # cached: no second warning
        g.eval_filter_candidates(max_fanout=1)


def test_truncated_masks_give_rank_upper_bounds(tiny_kg, tiny_params):
    exact = eval_device.entity_ranks_device(
        tiny_params["transe"], tiny_kg.test, "l1",
        tiny_kg.eval_filter_candidates(), model="transe")
    with pytest.warns(UserWarning):
        trunc_masks = tiny_kg.eval_filter_candidates(max_fanout=1)
    trunc = eval_device.entity_ranks_device(
        tiny_params["transe"], tiny_kg.test, "l1", trunc_masks,
        model="transe")
    for side in ("tail", "head"):
        assert np.all(trunc["filtered_ranks"][side]
                      >= exact["filtered_ranks"][side])


def test_host_engine_rejects_device_options(tiny_kg, tiny_params):
    with pytest.raises(ValueError, match="engine='device'"):
        kg_eval.evaluate_all(
            tiny_params["transe"], tiny_kg, model="transe", n_workers=4)
    with pytest.raises(ValueError, match="bad engine"):
        kg_eval.evaluate_all(
            tiny_params["transe"], tiny_kg, model="transe", engine="gpu")


# ---------------------------------------------------------------------------
# Golden-metrics regression: committed numbers for a fixed-seed graph
# ---------------------------------------------------------------------------

def _golden_setup(spec):
    graph = kg_lib.synthetic_kg(**spec["graph"])
    cfg = KGConfig(
        n_entities=graph.n_entities, n_relations=graph.n_relations,
        dim=spec["dim"])
    params = get_model(spec["model"]).init_params(
        jax.random.PRNGKey(spec["params_seed"]), cfg)
    return graph, params


@pytest.mark.parametrize("engine", ["host", "device"])
def test_golden_metrics(engine):
    """Eval refactors must not silently drift: both engines reproduce the
    committed evaluate_all numbers for a fixed-seed graph + fixed-seed
    params (regenerate with tests/golden/make_eval_golden.py after an
    *intentional* protocol change)."""
    with open(GOLDEN_PATH) as f:
        golden = json.load(f)
    for case in golden["cases"]:
        graph, params = _golden_setup(case)
        kw = {"n_workers": 2} if engine == "device" else {}
        got = kg_eval.evaluate_all(
            params, graph, model=case["model"], engine=engine, **kw)
        for task, row in case["metrics"].items():
            if isinstance(row, dict):
                for metric, want in row.items():
                    assert got[task][metric] == pytest.approx(
                        want, rel=1e-5, abs=1e-7), (
                        case["model"], task, metric)
            else:
                assert got[task] == pytest.approx(row, rel=1e-5), (
                    case["model"], task)
