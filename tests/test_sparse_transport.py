"""Sparse delta-Reduce transport (``merge_transport="sparse"``): bit-identity
against the dense reference across strategies, paradigms, pipelines, and
backends, plus the touch-stat invariants the transport is built on.

The acceptance bar (ISSUE 7): identical final params for every merge
strategy x paradigm (sgd/bgd) x pipeline (host/device) x backend
(vmap/shard_map), block-size invariant, and checkpoint/resume-compatible
across transports.  The fast cross-sections run in tier-1; the full
model x strategy x pipeline matrix is marked ``slow`` (CI slow-suites
job); real W=8 shard_map cells live in tests/helpers/multiworker_check.py.

``hypothesis`` is optional: the property tests fall back to a fixed seed
corpus when it is absent (repo idiom, see tests/test_merge.py).
"""
import functools
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

from repro import kg as kg_api
from repro import obs
from repro.core import mapreduce
from repro.core import merge as merge_lib
from repro.core.models import KGConfig, get_model
from repro.data import kg as kg_lib

MODELS = ["transe", "transh", "distmult"]
# the per-model contracts (transport, compact step, row-local projection)
CONTRACT_MODELS = MODELS + ["rotate"]
STRATEGIES = list(merge_lib.STRATEGIES)


@pytest.fixture(scope="module")
def small_kg():
    # 1200 triples split 748 train / 3 workers = 249 per worker; batch 83
    # gives 3 exact steps (no remainder warning)
    return kg_lib.synthetic_kg(0, n_entities=200, n_relations=5,
                               n_triplets=1200)


def _fit(graph, **kw):
    defaults = dict(model="transe", paradigm="sgd", backend="vmap",
                    n_workers=3, dim=8, learning_rate=0.05, batch_size=83,
                    seed=0, epochs=3)
    defaults.update(kw)
    return kg_api.fit(graph, **defaults)


def _assert_identical(r1, r2, losses="exact"):
    if losses == "exact":
        np.testing.assert_array_equal(
            np.asarray(r1.loss_history, np.float32),
            np.asarray(r2.loss_history, np.float32))
    else:
        np.testing.assert_allclose(
            np.asarray(r1.loss_history, np.float32),
            np.asarray(r2.loss_history, np.float32), rtol=1e-6)
    assert set(r1.params) == set(r2.params)
    for k in r1.params:
        np.testing.assert_array_equal(
            np.asarray(r1.params[k]), np.asarray(r2.params[k]),
            err_msg=f"table {k}")


def _pair(graph, **kw):
    dense = _fit(graph, merge_transport="dense", **kw)
    sparse = _fit(graph, merge_transport="sparse", **kw)
    return dense, sparse


# ---------------------------------------------------------------------------
# Bit-identity: fast cross-sections (tier-1)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("strategy", STRATEGIES)
def test_sparse_matches_dense_host(small_kg, strategy):
    """Every merge strategy, host pipeline, W=3 (non-pow2 exercises the
    broadcast-mean untouched path of average/average_all)."""
    _assert_identical(*_pair(small_kg, strategy=strategy))


@pytest.mark.parametrize("model", CONTRACT_MODELS)
def test_sparse_matches_dense_device(small_kg, model):
    """Device pipeline with deferred Reduces (merge_every=2): K local
    epochs of drift between merges, roles-aware extra tables (TransH's
    ``norm``) included."""
    _assert_identical(*_pair(
        small_kg, model=model, pipeline="device", epochs=4, block_epochs=2,
        merge_every=2, strategy="average_all"))


@pytest.mark.parametrize("normalize", ["step", "none"])
def test_sparse_matches_dense_normalize_modes(small_kg, normalize):
    """The virgin-row reconstruction depends on the projection cadence:
    'step' chains one projection per step, 'none' chains none."""
    _assert_identical(*_pair(
        small_kg, pipeline="device", epochs=4, block_epochs=2,
        merge_every=2, normalize=normalize))


@pytest.mark.parametrize("pipeline", ["host", "device"])
def test_sparse_matches_dense_bgd(small_kg, pipeline):
    kw = dict(paradigm="bgd", pipeline=pipeline)
    if pipeline == "device":
        kw.update(epochs=4, block_epochs=2)
    _assert_identical(*_pair(small_kg, **kw))


def test_sparse_matches_dense_shard_map(small_kg):
    """In-process single-device mesh; real W=8 shard_map bit-identity is
    covered by tests/helpers/multiworker_check.py."""
    mesh = jax.make_mesh((1,), ("workers",))
    _assert_identical(*_pair(
        small_kg, backend="shard_map", mesh=mesh, n_workers=1,
        batch_size=187, pipeline="device", epochs=4, block_epochs=2))


@pytest.mark.parametrize("strategy", ["average", "average_all"])
def test_sparse_matches_dense_batch_remainder(small_kg, strategy):
    """Batch remainder + non-pow2 W: steps drop 49 triples per worker and
    rows untouched by *every* worker go through the broadcast-mean
    fallback of ``sparse_untouched_base`` — the config where an XLA
    reduce-of-broadcast simplification once drifted 1 ulp from the dense
    plain-mean (pinned by the optimization barrier there)."""
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        _assert_identical(*_pair(small_kg, strategy=strategy,
                                 batch_size=100, epochs=6))


def test_sparse_block_size_invariant(small_kg):
    """Grouping epochs into compiled blocks cannot matter under the sparse
    transport either — its capacity and virgin-repeat counts are per
    merge round, not per block."""
    kw = dict(pipeline="device", merge_transport="sparse", epochs=4,
              merge_every=2)
    _assert_identical(_fit(small_kg, block_epochs=2, **kw),
                      _fit(small_kg, block_epochs=4, **kw))


def test_checkpoint_resume_across_transports(small_kg, tmp_path):
    """``merge_transport`` is deliberately absent from the resume manifest:
    a dense-trained checkpoint resumes under sparse transport (and vice
    versa) and still reproduces the uninterrupted run exactly."""
    kw = dict(pipeline="device", block_epochs=2, checkpoint_every=2)
    ref = _fit(small_kg, epochs=4, ckpt_dir=str(tmp_path / "ref"), **kw)
    for first, second in (("dense", "sparse"), ("sparse", "dense")):
        d = str(tmp_path / f"{first}-to-{second}")
        _fit(small_kg, epochs=2, merge_transport=first, ckpt_dir=d, **kw)
        res = _fit(small_kg, epochs=4, merge_transport=second, ckpt_dir=d,
                   resume=True, **kw)
        for k in ref.params:
            np.testing.assert_array_equal(
                np.asarray(ref.params[k]), np.asarray(res.params[k]),
                err_msg=f"{first}->{second} table {k}")


# ---------------------------------------------------------------------------
# Bit-identity: full matrix (slow suite)
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.parametrize("model", MODELS)
@pytest.mark.parametrize("strategy", STRATEGIES)
@pytest.mark.parametrize("pipeline", ["host", "device"])
def test_sparse_matrix(small_kg, model, strategy, pipeline):
    kw = dict(model=model, strategy=strategy, pipeline=pipeline)
    if pipeline == "device":
        kw.update(epochs=4, block_epochs=2, merge_every=2)
    _assert_identical(*_pair(small_kg, **kw))


# ---------------------------------------------------------------------------
# Delta-buffer overflow: fail loudly, never corrupt silently (satellite)
# ---------------------------------------------------------------------------

def test_undersized_touched_capacity_raises_at_config_time(small_kg):
    """An override below the analytic touched-rows bound would make
    pack_delta silently drop rows — train() refuses it before any epoch
    runs (the pre-fix behavior was exactly that silent corruption)."""
    with pytest.raises(ValueError, match="below the analytic bound"):
        _fit(small_kg, merge_transport="sparse", touched_capacity=3)


def test_touched_capacity_must_match_transport(small_kg):
    with pytest.raises(ValueError, match="sparse"):
        _fit(small_kg, merge_transport="dense", touched_capacity=100)


@pytest.mark.parametrize("pipeline", ["host", "device"])
def test_overflow_raises_at_reduce_boundary(small_kg, pipeline, monkeypatch):
    """Runtime seatbelt behind the config check: if the capacity bound
    itself ever regresses (simulated by patching it tiny), the on-device
    overflow count surfaces at the next Reduce boundary as a RuntimeError
    instead of training on over a corrupted merge."""
    monkeypatch.setattr(merge_lib, "touched_capacity",
                        lambda n_rows, batch, steps, k, role: 2)
    kw = dict(merge_transport="sparse", pipeline=pipeline)
    if pipeline == "device":
        kw.update(epochs=4, block_epochs=2)
    with pytest.raises(RuntimeError, match="delta overflow"):
        _fit(small_kg, **kw)


def test_generous_touched_capacity_still_bitwise(small_kg):
    """Capacity padding is inert: an oversized validated override packs
    the same touched rows, so results stay bit-identical to dense."""
    dense = _fit(small_kg, merge_transport="dense")
    sparse = _fit(small_kg, merge_transport="sparse",
                  touched_capacity=small_kg.n_entities)
    _assert_identical(dense, sparse)


# ---------------------------------------------------------------------------
# The compact Map step (sgd_step_sparse) in isolation
# ---------------------------------------------------------------------------

def _random_batch(rng, E, R, B):
    return jnp.asarray(np.stack([
        rng.integers(0, E, B), rng.integers(0, R, B), rng.integers(0, E, B),
    ], axis=1).astype(np.int32))


@pytest.mark.parametrize("model_name", CONTRACT_MODELS)
@pytest.mark.parametrize("normalize", ["epoch", "step"])
def test_compact_step_bitwise(model_name, normalize):
    """``sgd_step_sparse`` == ``sgd_step`` bitwise: same forward floats on
    gathered compact tables, same scatter-add gradient order, and rows no
    batch id references have exactly-zero dense gradient."""
    model = get_model(model_name)
    kcfg, _ = kg_api.make_configs(
        kg_lib.synthetic_kg(0, n_entities=60, n_relations=4,
                            n_triplets=200),
        model=model_name, dim=8, learning_rate=0.05, normalize=normalize)
    rng = np.random.default_rng(7)
    params = model.init_params(jax.random.PRNGKey(0), kcfg)
    pos = _random_batch(rng, 60, 4, 32)
    neg = _random_batch(rng, 60, 4, 32)
    dense_p, dense_l = jax.jit(model.sgd_step, static_argnums=3)(
        params, pos, neg, kcfg)
    sparse_p, sparse_l = jax.jit(model.sgd_step_sparse, static_argnums=3)(
        params, pos, neg, kcfg)
    np.testing.assert_array_equal(np.asarray(dense_l), np.asarray(sparse_l))
    for k in dense_p:
        np.testing.assert_array_equal(
            np.asarray(dense_p[k]), np.asarray(sparse_p[k]),
            err_msg=f"table {k}")


# Where the candidate set is smaller than the table, XLA may sum a repeated
# row's gradient contributions in another order in the compact program than
# in the dense one: the two agree to the last bit but for a few rows, which
# differ by a few ulps of the table's unit scale.
TABLE_RTOL, TABLE_ATOL = 1e-6, 1e-7


def _assert_close_tables(compact, dense):
    """Losses bitwise, tables within ``TABLE_RTOL``/``TABLE_ATOL``."""
    np.testing.assert_array_equal(np.asarray(compact[1]),
                                  np.asarray(dense[1]))
    assert set(compact[0]) == set(dense[0])
    for k in dense[0]:
        np.testing.assert_allclose(
            np.asarray(compact[0][k]), np.asarray(dense[0][k]),
            rtol=TABLE_RTOL, atol=TABLE_ATOL, err_msg=f"table {k}")


@pytest.mark.parametrize("model_name", CONTRACT_MODELS)
def test_compact_step_close_where_candidates_fewer(model_name):
    """``sgd_step_sparse`` against the plain full-table gradient step on
    batches whose 4B candidate slots are fewer than the table's rows (so
    the compact tables are a proper subset, with repeated rows): 200
    steps, each from the dense step's last tables."""
    model = get_model(model_name)
    E, R, B = 600, 12, 30
    kcfg = KGConfig(n_entities=E, n_relations=R, dim=8, learning_rate=0.05)
    rng = np.random.default_rng(11)
    params = model.init_params(jax.random.PRNGKey(0), kcfg)
    dense_step = jax.jit(model.sgd_step, static_argnums=3)
    sparse_step = jax.jit(model.sgd_step_sparse, static_argnums=3)
    for _ in range(200):
        pos = _random_batch(rng, E, R, B)
        neg = _random_batch(rng, E, R, B)
        dense = dense_step(params, pos, neg, kcfg)
        _assert_close_tables(sparse_step(params, pos, neg, kcfg), dense)
        params = dense[0]


# ---------------------------------------------------------------------------
# The device pipeline's compact Map on flat worker tables
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def wide_kg():
    # 3600 train triples, 900 per worker at W=4: batch 30 gives 30 exact
    # steps, and 9 × 30 < 600: the Map takes the compact step
    return kg_lib.synthetic_kg(0, n_entities=600, n_relations=12,
                               n_triplets=4000)


def _wide_fit(graph, **kw):
    # miniloss_perkey merges by the per-row losses, so the touch stats'
    # pair losses count as well as the tables
    return _fit(graph, n_workers=4, batch_size=30, pipeline="device",
                strategy="miniloss_perkey", **kw)


def _assert_fits_close(compact, dense):
    _assert_close_tables((compact.params, compact.loss_history),
                         (dense.params, dense.loss_history))


@pytest.mark.parametrize("merge_every", [1, 2])
@pytest.mark.parametrize("transport", ["dense", "sparse"])
@pytest.mark.parametrize("normalize", ["epoch", "none"])
@pytest.mark.parametrize("model", MODELS)
def test_flat_compact_map_matches_dense_step(wide_kg, monkeypatch, model,
                                             normalize, transport,
                                             merge_every):
    """Where ``9B < E`` the vmap backend's Map steps only the batch's rows
    on the W workers' tables laid end to end
    (``KGModel.run_epoch_flat``); after 3 merge rounds its losses equal,
    bitwise, and its tables within a few ulps, those of the same fit with
    every worker on the plain full-table ``sgd_step`` (the dispatch rule
    patched off)."""
    kw = dict(model=model, normalize=normalize, merge_transport=transport,
              merge_every=merge_every, block_epochs=merge_every,
              epochs=3 * merge_every)
    compact = _wide_fit(wide_kg, **kw)
    with monkeypatch.context() as m:
        m.setattr(mapreduce, "compact_map", lambda *a, **k: False)
        dense = _wide_fit(wide_kg, **kw)
    _assert_fits_close(compact, dense)


@pytest.mark.parametrize("transport", ["dense", "sparse"])
def test_flat_compact_map_matches_dense_step_stale(wide_kg, monkeypatch,
                                                   transport):
    """The same under bounded staleness, whose worker tables persist
    across merge rounds and blocks."""
    kw = dict(merge_transport=transport, staleness=1, epochs=4,
              block_epochs=2)
    compact = _wide_fit(wide_kg, **kw)
    with monkeypatch.context() as m:
        m.setattr(mapreduce, "compact_map", lambda *a, **k: False)
        dense = _wide_fit(wide_kg, **kw)
    _assert_fits_close(compact, dense)


@pytest.mark.parametrize("normalize,batch_size,step", [
    ("epoch", 30, "compact"),
    ("none", 30, "compact"),
    ("step", 30, "dense"),         # every step projects every row anyway
    ("epoch", 100, "dense"),       # 4B < E <= 9B: slots cost more than rows
    ("epoch", 150, "dense"),       # 4B >= E: no row fewer to step
])
def test_map_step_follows_dispatch_rule(wide_kg, normalize, batch_size,
                                        step):
    """The device pipeline picks the Map step from what it can see —
    ``9B < E`` and no per-step projection — whatever the Reduce's wire
    format; the ``map.*_steps`` counters say which ran, W × steps ×
    epochs of it, counted once per block."""
    obs.reset()
    _fit(wide_kg, n_workers=4, batch_size=batch_size, pipeline="device",
         normalize=normalize, merge_transport="sparse", epochs=3)
    steps = 4 * (900 // batch_size) * 3
    assert obs.counters() == {f"map.{step}_steps": steps}
    obs.reset()


@pytest.mark.parametrize("model_name", CONTRACT_MODELS)
def test_normalize_rows_row_local_contract(model_name):
    """The transport contract: ``normalize(params)[name][ids] ==
    normalize_rows(name, params[name][ids])`` bitwise, per table — the
    projection must touch rows independently."""
    model = get_model(model_name)
    kcfg, _ = kg_api.make_configs(
        kg_lib.synthetic_kg(0, n_entities=50, n_relations=4,
                            n_triplets=150),
        model=model_name, dim=8)
    params = model.init_params(jax.random.PRNGKey(3), kcfg)
    full = model.normalize(params)
    ids = np.array([0, 3, 7, 11, 49])
    for name in params:
        n = min(params[name].shape[0] - 1, ids.max())
        sel = np.unique(np.minimum(ids, n))
        np.testing.assert_array_equal(
            np.asarray(full[name][sel]),
            np.asarray(model.normalize_rows(name, params[name][sel])),
            err_msg=f"table {name}")


# ---------------------------------------------------------------------------
# Touch-stat property: touched rows cover changed rows (satellite)
# ---------------------------------------------------------------------------

_E, _R, _W, _S, _B = 80, 5, 3, 4, 16


def _epoch_inputs(seed):
    model = get_model("transe")
    kcfg, _ = kg_api.make_configs(
        kg_lib.synthetic_kg(0, n_entities=_E, n_relations=_R,
                            n_triplets=200),
        dim=6, learning_rate=0.1)
    rng = np.random.default_rng(seed)
    params = model.init_params(jax.random.PRNGKey(seed), kcfg)
    pos = jnp.stack([
        jnp.stack([_random_batch(rng, _E, _R, _B) for _ in range(_S)])
        for _ in range(_W)])
    neg = jnp.stack([
        jnp.stack([_random_batch(rng, _E, _R, _B) for _ in range(_S)])
        for _ in range(_W)])
    return model, kcfg, params, pos, neg


def _check_touched_covers_changed_sgd(strategy, seed):
    """After one worker epoch, every row that differs from its virgin
    evolution (the projection applied to the shared round input) is marked
    touched; after the Reduce, every row the merge moved away from virgin
    is in the union of the workers' touched sets.  This is the invariant
    the sparse transport ships deltas on."""
    model, kcfg, params, pos, neg = _epoch_inputs(seed)
    run = functools.partial(model.run_epoch, cfg=kcfg)
    stacked, stats = jax.vmap(run, in_axes=(None, 0, 0))(params, pos, neg)
    counts = {"ent": stats.ent_count, "rel": stats.rel_count}
    key = jax.random.PRNGKey(seed + 1)
    for name in params:
        role = model.roles[name]
        virgin = np.asarray(merge_lib.virgin_rows(
            params[name], functools.partial(model.normalize_rows, name), 1))
        touched = np.asarray(counts[role]) > 0            # (W, n)
        local = np.asarray(stacked[name])
        for w in range(_W):
            changed = np.any(local[w] != virgin, axis=1)
            stray = changed & ~touched[w]
            assert not stray.any(), (
                f"{name}: worker {w} changed untouched rows "
                f"{np.nonzero(stray)[0][:5]}")
        merged = np.asarray(merge_lib.merge_stacked(
            strategy, stacked[name], counts[role],
            getattr(stats, f"{role}_loss"), stats.mean_loss, key))
        union = touched.any(axis=0)
        merged_w = merged if merged.ndim == 2 else merged[0]
        changed = np.any(merged_w != virgin, axis=1)
        stray = changed & ~union
        assert not stray.any(), (
            f"{name}/{strategy}: merge moved untouched rows "
            f"{np.nonzero(stray)[0][:5]}")


def _check_touched_covers_changed_bgd(seed):
    """BGD: rows with nonzero batch gradient are exactly rows the batch
    references — the candidate-id invariant the sparse BGD update uses."""
    model, kcfg, params, pos, neg = _epoch_inputs(seed)
    pos_b, neg_b = pos[0, 0], neg[0, 0]
    _, grads = model.batch_gradients(params, pos_b, neg_b, kcfg)
    ids = {
        "ent": np.unique(np.concatenate([
            np.asarray(pos_b[:, 0]), np.asarray(pos_b[:, 2]),
            np.asarray(neg_b[:, 0]), np.asarray(neg_b[:, 2])])),
        "rel": np.unique(np.concatenate([
            np.asarray(pos_b[:, 1]), np.asarray(neg_b[:, 1])])),
    }
    for name in params:
        nz = np.nonzero(np.any(np.asarray(grads[name]) != 0, axis=1))[0]
        assert set(nz.tolist()) <= set(ids[model.roles[name]].tolist()), name


class TestTouchPropertiesFallback:
    """Non-hypothesis fallbacks: always run, fixed corpus of instances."""

    @pytest.mark.parametrize("strategy", STRATEGIES)
    @pytest.mark.parametrize("seed", [0, 1])
    def test_sgd_touched_covers_changed(self, strategy, seed):
        _check_touched_covers_changed_sgd(strategy, seed)

    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_bgd_grads_within_batch_ids(self, seed):
        _check_touched_covers_changed_bgd(seed)


if HAVE_HYPOTHESIS:
    class TestTouchProperties:
        @given(strategy=st.sampled_from(STRATEGIES),
               seed=st.integers(0, 2**16))
        @settings(max_examples=10, deadline=None)
        def test_sgd_touched_covers_changed(self, strategy, seed):
            _check_touched_covers_changed_sgd(strategy, seed)

        @given(seed=st.integers(0, 2**16))
        @settings(max_examples=10, deadline=None)
        def test_bgd_grads_within_batch_ids(self, seed):
            _check_touched_covers_changed_bgd(seed)


# ---------------------------------------------------------------------------
# One-time warnings fire once per call, not once per process (satellite)
# ---------------------------------------------------------------------------

def test_batch_remainder_warns_on_every_fit(small_kg):
    """warn_fresh keys the dedupe off the per-process warning registry, so
    back-to-back fits each report their own dropped counts."""
    for _ in range(2):
        with pytest.warns(UserWarning,
                          match="does not divide the per-worker"):
            _fit(small_kg, n_workers=3, batch_size=64, epochs=1)


def test_max_fanout_truncation_warns_on_every_graph():
    graphs = [kg_lib.synthetic_kg(s, n_entities=30, n_relations=2,
                                  n_triplets=300) for s in (0, 1)]
    for g in graphs:
        with pytest.warns(UserWarning, match="max_fanout=1 truncates"):
            g.eval_filter_candidates(max_fanout=1)


def test_no_duplicate_warning_within_one_call(small_kg):
    """Each fit call reports once — warn_fresh defeats the process
    registry without spamming inside a call."""
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        _fit(small_kg, n_workers=3, batch_size=64, epochs=2)
    msgs = [str(w.message) for w in rec
            if "does not divide the per-worker" in str(w.message)]
    assert len(msgs) == 1, msgs
