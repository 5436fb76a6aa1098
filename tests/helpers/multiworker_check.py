"""Subprocess helper: real multi-device semantics checks.

Run with 8 forced host devices (the parent test sets XLA_FLAGS).  Asserts:
  1. shard_map SGD epoch (psum Reduce)      == vmap SGD epoch (stacked Reduce)
  2. shard_map SGD epoch (allgather Reduce) == vmap SGD epoch
  3. shard_map BGD epoch                    == vmap BGD epoch
  4. cross-pod local_sgd outer_merge: average/compressed/liveness semantics
  5. device pipeline (scan-over-epochs blocks): shard_map == vmap for both
     paradigms, incl. merge_every > 1 — the two backends derive identical
     per-worker fold_in keys, so batches/negatives match exactly
  6. device eval engine: shard_map query sharding == vmap (exact ranks) at
     W == mesh size AND W == 2x mesh size (multiple worker blocks per
     shard), and a W that does not divide over the mesh axis raises
  7. on-device re-partitioning (repartition_every): shard_map == vmap —
     the shard path all-gathers and slices the same global permutation the
     vmap path applies directly
  8. in-loop eval trace (kg.fit(eval_every=...)): a shard_map training run
     produces the same trace structure and (to collective-reordering
     tolerance) the same metric curve as the vmap run
  9. checkpoint/resume + serving: a resumed shard_map device-pipeline run
     is bit-identical to its own unbroken run, and the KnowledgeBase
     query engine's shard_map top-k equals the vmap engine exactly
     (ids and energies), raw and filtered
 10. sparse Reduce transport (merge_transport="sparse") at real W=8:
     shard_map sparse == vmap sparse == vmap dense bit-identically, for
     both the every-epoch and merge_every=2 schedules
 11. sharded entity tables (table_sharding="sharded") at real W=8: the
     shard-routed Reduce, the shard-local eval scan, and the shard-local
     serving top-k are each bit-identical to the replicated layout on a
     real 8-device mesh (training params, raw/filtered ranks, and top-k
     ids + energies including exclusion)
 12. bounded-staleness Reduce (staleness=2) at real W=8: shard_map ==
     vmap params bit-for-bit under dense, sparse, and sparse+sharded
     configurations (the stale all-gather replay on a real mesh)
Exit code 0 on success.
"""
import dataclasses
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import local_sgd, mapreduce, negative, transe
from repro.data import kg as kg_lib

W = 8
assert len(jax.devices()) == W, f"expected {W} devices, got {len(jax.devices())}"


def check_engine():
    kg = kg_lib.synthetic_kg(0, n_entities=200, n_relations=5, n_triplets=2000)
    tcfg = transe.TransEConfig(
        n_entities=kg.n_entities, n_relations=kg.n_relations, dim=8,
        learning_rate=0.05,
    )
    mesh = jax.make_mesh((W,), ("workers",))
    part = kg_lib.partition_balanced(0, kg.train, W)
    pos = jnp.asarray(kg_lib.epoch_batches(0, 0, part, 16))
    neg = negative.make_negatives(jax.random.PRNGKey(1), pos, tcfg.n_entities)
    params = transe.init_params(jax.random.PRNGKey(2), tcfg)
    mk = jax.random.PRNGKey(3)

    for strategy in ("average", "miniloss_perkey", "miniloss_global", "random"):
        cfg_v = mapreduce.MapReduceConfig(
            n_workers=W, strategy=strategy, backend="vmap", batch_size=16)
        ref, ref_loss = mapreduce.sgd_epoch_vmap(params, pos, neg, cfg_v, tcfg, mk)
        for impl in ("psum", "allgather"):
            cfg_s = mapreduce.MapReduceConfig(
                n_workers=W, strategy=strategy, reduce_impl=impl,
                backend="shard_map", batch_size=16)
            with mesh:
                got, got_loss = mapreduce.sgd_epoch_shard(
                    params, pos, neg, cfg_s, tcfg, mk, mesh)
            for k in ("ent", "rel"):
                np.testing.assert_allclose(
                    np.asarray(got[k]), np.asarray(ref[k]), rtol=1e-4, atol=1e-5,
                    err_msg=f"SGD {strategy}/{impl} table {k}",
                )
            np.testing.assert_allclose(
                float(got_loss), float(ref_loss), rtol=1e-4,
                err_msg=f"{strategy}/{impl} loss")
        print(f"sgd {strategy}: shard_map(psum & allgather) == vmap  OK")

    cfg_v = mapreduce.MapReduceConfig(
        n_workers=W, paradigm="bgd", backend="vmap", batch_size=16)
    ref, _ = mapreduce.bgd_epoch_vmap(params, pos, neg, cfg_v, tcfg)
    cfg_s = mapreduce.MapReduceConfig(
        n_workers=W, paradigm="bgd", backend="shard_map", batch_size=16)
    got, _ = mapreduce.bgd_epoch_shard(params, pos, neg, cfg_s, tcfg, mesh)
    np.testing.assert_allclose(
        np.asarray(got["ent"]), np.asarray(ref["ent"]), rtol=1e-4, atol=1e-5)
    print("bgd: shard_map == vmap  OK")


def check_outer_merge():
    mesh = jax.make_mesh((4, 2), ("pod", "data"))
    rng = np.random.default_rng(0)
    per_pod = jnp.asarray(rng.normal(size=(4, 6, 3)).astype(np.float32))
    anchor = jnp.asarray(rng.normal(size=(6, 3)).astype(np.float32))
    losses = jnp.asarray(np.array([0.5, 0.2, 0.9, 0.4], np.float32))
    live = jnp.asarray(np.array([1.0, 1.0, 0.0, 1.0], np.float32))

    def run(strategy, compress, use_liveness):
        cfg = local_sgd.OuterConfig(strategy=strategy, compress=compress)

        def f(p, loss, lv):
            st = local_sgd.OuterState(anchor=anchor, momentum=None)
            merged, _ = local_sgd.outer_merge(
                p[0], st, cfg, local_loss=loss[0],
                key=jax.random.PRNGKey(0),
                liveness=lv[0] if use_liveness else None,
            )
            return merged[None]

        out = jax.jit(jax.shard_map(
            f, mesh=mesh,
            in_specs=(P("pod"), P("pod"), P("pod")),
            out_specs=P("pod"), check_vma=False,
        ))(per_pod, losses, live)
        return np.asarray(out)

    # average, uncompressed, all live: anchor + mean(delta)
    out = run("average", "none", False)
    expect = np.asarray(anchor) + np.mean(np.asarray(per_pod) - np.asarray(anchor), 0)
    for pod in range(4):
        np.testing.assert_allclose(out[pod], expect, rtol=1e-5)
    print("outer average OK")

    # average with liveness mask: dead pod 2 excluded
    out = run("average", "none", True)
    deltas = np.asarray(per_pod) - np.asarray(anchor)
    expect = np.asarray(anchor) + deltas[[0, 1, 3]].mean(axis=0)
    np.testing.assert_allclose(out[0], expect, rtol=1e-5)
    print("outer average + liveness OK")

    # int8 compression: close to uncompressed (quantization tolerance)
    out_q = run("average", "int8", False)
    expect = np.asarray(anchor) + deltas.mean(axis=0)
    err = np.abs(out_q[0] - expect).max()
    scale = np.abs(deltas).max() / 127.0
    assert err <= 4 * scale + 1e-6, (err, scale)
    print(f"outer int8 average OK (max err {err:.2e} <= 4*lsb {4*scale:.2e})")

    # miniloss_global: pod 1 (loss .2) wins everywhere
    out = run("miniloss_global", "none", False)
    np.testing.assert_allclose(out[0], np.asarray(per_pod)[1], rtol=1e-5)
    print("outer miniloss_global OK")

    # miniloss_global + liveness: among live pods only
    out = run("miniloss_global", "none", True)
    np.testing.assert_allclose(out[0], np.asarray(per_pod)[1], rtol=1e-5)
    print("outer miniloss_global + liveness OK")

    # random: result equals some pod's params, same on every pod
    out = run("random", "none", False)
    assert any(np.allclose(out[0], np.asarray(per_pod)[w]) for w in range(4))
    for pod in range(1, 4):
        np.testing.assert_allclose(out[pod], out[0])
    print("outer random OK")


def check_device_pipeline():
    kg = kg_lib.synthetic_kg(0, n_entities=200, n_relations=5, n_triplets=2000)
    tcfg = transe.TransEConfig(
        n_entities=kg.n_entities, n_relations=kg.n_relations, dim=8,
        learning_rate=0.05,
    )
    mesh = jax.make_mesh((W,), ("workers",))
    for paradigm, merge_every in (("sgd", 1), ("sgd", 2), ("bgd", 1)):
        cfg_v = mapreduce.MapReduceConfig(
            n_workers=W, paradigm=paradigm, backend="vmap", batch_size=16,
            pipeline="device",
            schedule=mapreduce.EpochSchedule(
                block_epochs=4, merge_every=merge_every))
        res_v = mapreduce.train(kg, tcfg, cfg_v, epochs=4, seed=0)
        cfg_s = dataclasses.replace(cfg_v, backend="shard_map")
        res_s = mapreduce.train(kg, tcfg, cfg_s, epochs=4, seed=0, mesh=mesh)
        np.testing.assert_allclose(
            np.asarray(res_s.loss_history), np.asarray(res_v.loss_history),
            rtol=1e-3, err_msg=f"device {paradigm} K={merge_every} losses")
        for k in ("ent", "rel"):
            np.testing.assert_allclose(
                np.asarray(res_s.params[k]), np.asarray(res_v.params[k]),
                rtol=1e-3, atol=1e-5,
                err_msg=f"device {paradigm} K={merge_every} table {k}")
        print(f"device pipeline {paradigm} K={merge_every}: "
              "shard_map == vmap  OK")


def check_device_eval():
    from repro.core import eval_device
    from repro.core.models import get_model

    kg = kg_lib.synthetic_kg(0, n_entities=200, n_relations=5, n_triplets=2000)
    tcfg = transe.TransEConfig(
        n_entities=kg.n_entities, n_relations=kg.n_relations, dim=8)
    model = get_model("transe")
    params = transe.init_params(jax.random.PRNGKey(2), tcfg)
    masks = kg.eval_filter_candidates()
    mesh = jax.make_mesh((W,), ("workers",))

    ref = eval_device.entity_ranks_device(
        params, kg.test, "l1", masks, model=model, n_workers=W)
    for workers in (W, 2 * W):       # 2W = two worker blocks per shard
        got = eval_device.entity_ranks_device(
            params, kg.test, "l1", masks, model=model, n_workers=workers,
            backend="shard_map", mesh=mesh)
        for grp in ("raw_ranks", "filtered_ranks"):
            for side in ("tail", "head"):
                np.testing.assert_array_equal(
                    got[grp][side], ref[grp][side],
                    err_msg=f"device eval W={workers} {grp}/{side}")
        print(f"device eval W={workers}: shard_map == vmap (exact)  OK")

    try:
        eval_device.entity_ranks_device(
            params, kg.test, "l1", masks, model=model, n_workers=W + 1,
            backend="shard_map", mesh=mesh)
    except ValueError as e:
        assert "does not divide over mesh axis" in str(e), e
        print("device eval W not dividing mesh axis raises  OK")
    else:
        raise AssertionError("indivisible worker count did not raise")


def check_repartition():
    """Re-partitioning across workers on device: the shard_map path
    (all_gather + per-worker slice of the global permutation) must equal
    the vmap path (direct permutation of the stacked partition)."""
    kg = kg_lib.synthetic_kg(0, n_entities=200, n_relations=5, n_triplets=2000)
    tcfg = transe.TransEConfig(
        n_entities=kg.n_entities, n_relations=kg.n_relations, dim=8,
        learning_rate=0.05,
    )
    mesh = jax.make_mesh((W,), ("workers",))
    cfg_v = mapreduce.MapReduceConfig(
        n_workers=W, backend="vmap", batch_size=16, pipeline="device",
        schedule=mapreduce.EpochSchedule(
            block_epochs=2, repartition_every=2))
    res_v = mapreduce.train(kg, tcfg, cfg_v, epochs=6, seed=0)
    cfg_s = dataclasses.replace(cfg_v, backend="shard_map")
    res_s = mapreduce.train(kg, tcfg, cfg_s, epochs=6, seed=0, mesh=mesh)
    np.testing.assert_allclose(
        np.asarray(res_s.loss_history), np.asarray(res_v.loss_history),
        rtol=1e-3, err_msg="repartition losses")
    for k in ("ent", "rel"):
        np.testing.assert_allclose(
            np.asarray(res_s.params[k]), np.asarray(res_v.params[k]),
            rtol=1e-3, atol=1e-5, err_msg=f"repartition table {k}")
    print("device pipeline repartition_every=2: shard_map == vmap  OK")


def check_inloop_eval():
    """The in-loop eval trace from a shard_map training run: identical
    boundary structure to vmap, metric curve equal up to the collective
    reduction-order tolerance of the trained params themselves."""
    from repro import kg as kg_api

    kg = kg_lib.synthetic_kg(0, n_entities=200, n_relations=5, n_triplets=2000)
    mesh = jax.make_mesh((W,), ("workers",))
    kw = dict(model="transe", paradigm="sgd", n_workers=W, dim=8,
              learning_rate=0.05, batch_size=16, epochs=4, seed=0,
              pipeline="device", block_epochs=4, eval_every=2)
    res_v = kg_api.fit(kg, **kw)
    res_s = kg_api.fit(kg, backend="shard_map", mesh=mesh, **kw)
    assert res_v.trace.epochs() == res_s.trace.epochs() == [1, 3]
    assert ([e.merge_round for e in res_v.trace.entries]
            == [e.merge_round for e in res_s.trace.entries])
    np.testing.assert_allclose(
        res_s.trace.values(), res_v.trace.values(), rtol=0.05,
        err_msg="in-loop metric curve")
    # and each backend's trace is exactly its own post-hoc eval
    post = kg_api.evaluate(res_s.params, "transe", kg, engine="device",
                           n_workers=W)
    assert post == res_s.trace.entries[-1].metrics
    print("in-loop eval trace: shard_map == vmap (tolerance) "
          "and == post-hoc (exact)  OK")


def check_kb_resume_serve():
    """Checkpoint/resume and the serving engine under shard_map: resume is
    bit-identical within the backend, and the sharded query engine's
    top-k equals the single-device engine exactly."""
    import tempfile

    from repro import kg as kg_api
    from repro.serve.kg_engine import KGQueryEngine

    kg = kg_lib.synthetic_kg(0, n_entities=200, n_relations=5, n_triplets=2000)
    mesh = jax.make_mesh((W,), ("workers",))
    kw = dict(model="transe", n_workers=W, dim=8, learning_rate=0.05,
              batch_size=16, seed=0, pipeline="device", block_epochs=2,
              backend="shard_map", mesh=mesh)
    full = kg_api.fit(kg, epochs=4, **kw)
    d = tempfile.mkdtemp(prefix="kb_resume_")
    kg_api.fit(kg, epochs=2, ckpt_dir=d, checkpoint_every=2,
               sync_checkpoints=True, **kw)
    resumed = kg_api.fit(kg, epochs=4, ckpt_dir=d, resume=True, **kw)
    for k in ("ent", "rel"):
        np.testing.assert_array_equal(
            np.asarray(resumed.params[k]), np.asarray(full.params[k]),
            err_msg=f"shard_map resume table {k}")
    assert resumed.loss_history == full.loss_history
    print("shard_map checkpoint-resume: bit-identical  OK")

    params = {k: np.asarray(v) for k, v in full.params.items()}
    h, r = kg.test[:32, 0], kg.test[:32, 1]
    exclude = kg.known_candidate_masks(
        np.stack([h, r], axis=1), "tail")
    ref_eng = KGQueryEngine("transe", params)
    shard_eng = KGQueryEngine(
        "transe", params, n_workers=W, backend="shard_map", mesh=mesh)
    for label, q_kw in (("raw", {}), ("filtered", {"exclude": exclude})):
        ref = ref_eng.query_tails(h, r, k=10, **q_kw)
        got = shard_eng.query_tails(h, r, k=10, **q_kw)
        np.testing.assert_array_equal(
            got.ids, ref.ids, err_msg=f"serve {label} ids")
        np.testing.assert_array_equal(
            got.energies, ref.energies, err_msg=f"serve {label} energies")
    ref = ref_eng.query_relations(kg.test[:32, 0], kg.test[:32, 2], k=3)
    got = shard_eng.query_relations(kg.test[:32, 0], kg.test[:32, 2], k=3)
    np.testing.assert_array_equal(got.ids, ref.ids)
    print("serve engine: shard_map == vmap (exact, raw + filtered)  OK")


def check_kg_server():
    """The live serving tier on a sharded backend: a KGServer whose
    tenant engine runs shard_map across W workers forms waves, pads them
    to buckets, and still answers bit-identically to the single-device
    engine — and the warmed buckets never recompile."""
    from repro.core.models import KGConfig, get_model
    from repro.kb import KnowledgeBase
    from repro.serve import KGServer
    from repro.serve.kg_engine import KGQueryEngine

    kg = kg_lib.synthetic_kg(0, n_entities=200, n_relations=5, n_triplets=2000)
    mesh = jax.make_mesh((W,), ("workers",))
    model = get_model("transe")
    params = model.init_params(
        jax.random.PRNGKey(3),
        KGConfig(n_entities=200, n_relations=5, dim=8))
    kb = KnowledgeBase(model, params, graph=kg, norm="l1")
    ref_eng = KGQueryEngine("transe", {k: np.asarray(v)
                                       for k, v in params.items()})
    server = KGServer(kb, max_batch=4, max_wait_us=5000, default_k=10,
                      n_workers=W, backend="shard_map", mesh=mesh)
    server.warmup(kinds=("tails",))
    try:
        for size, filtered in ((1, False), (3, True), (4, False)):
            rows = kg.test[10:10 + size]
            h, r = rows[:, 0], rows[:, 1]
            server.pause()
            futs = [server.submit("tails", hh, rr, filtered=filtered)
                    for hh, rr in zip(h, r)]
            server.resume()
            answers = [f.result(timeout=60) for f in futs]
            if filtered:
                ref = kb.query_tails(h, r, k=10, filtered=True)
            else:
                ref = ref_eng.query_tails(h, r, k=10)
            for i, ans in enumerate(answers):
                np.testing.assert_array_equal(
                    ans.ids, ref.ids[i],
                    err_msg=f"server wave={size} filtered={filtered} ids")
                np.testing.assert_array_equal(
                    ans.energies, ref.energies[i],
                    err_msg=f"server wave={size} energies")
        assert server.stats().steady_recompiles == 0, server.stats()
    finally:
        server.stop()
    print("KGServer: shard_map waves == single-device engine (exact), "
          "0 steady recompiles  OK")


def check_sparse_transport():
    """The delta Reduce at real W=8: every backend x transport combination
    lands on the same bits (the collective sparse path reconstructs the
    same candidate union and merge arithmetic as the stacked paths)."""
    from repro import kg as kg_api

    kg = kg_lib.synthetic_kg(0, n_entities=200, n_relations=5, n_triplets=2000)
    mesh = jax.make_mesh((W,), ("workers",))
    for merge_every in (1, 2):
        kw = dict(model="transe", paradigm="sgd", n_workers=W, dim=8,
                  learning_rate=0.05, batch_size=16, epochs=4, seed=0,
                  pipeline="device", block_epochs=2, merge_every=merge_every)
        ref = kg_api.fit(kg, merge_transport="dense", **kw)
        shard_ref = kg_api.fit(kg, merge_transport="dense",
                               backend="shard_map", mesh=mesh, **kw)
        runs = {
            "vmap/sparse": kg_api.fit(kg, merge_transport="sparse", **kw),
            "shard_map/sparse": kg_api.fit(
                kg, merge_transport="sparse", backend="shard_map",
                mesh=mesh, **kw),
        }
        for label, res in runs.items():
            for k in ("ent", "rel"):
                np.testing.assert_array_equal(
                    np.asarray(res.params[k]), np.asarray(ref.params[k]),
                    err_msg=f"sparse transport K={merge_every} "
                            f"{label} table {k}")
            # the *params* contract is bitwise; the reported loss is a
            # psum-averaged diagnostic whose rounding shifts with the
            # compiled program (same tolerance story as
            # check_device_pipeline), so vmap is exact and shard_map is
            # near-exact
            if "shard_map" in label:
                np.testing.assert_allclose(
                    res.loss_history, shard_ref.loss_history, rtol=1e-6,
                    err_msg=f"K={merge_every} {label} losses")
            else:
                assert res.loss_history == ref.loss_history, (
                    f"K={merge_every} {label} losses")
        print(f"sparse transport K={merge_every}: sparse params == dense "
              "params across backends (exact)  OK")


def check_sharded_tables():
    """Sharded entity tables at real W=8: training, eval, and serving are
    each bit-identical to the replicated layout on a real mesh — the
    tentpole's exactness bar where the collectives actually run."""
    from repro import kg as kg_api
    from repro.core import eval_device
    from repro.core.models import KGConfig, get_model
    from repro.serve.kg_engine import KGQueryEngine

    kg = kg_lib.synthetic_kg(0, n_entities=200, n_relations=5, n_triplets=2000)
    mesh = jax.make_mesh((W,), ("workers",))

    for merge_every in (1, 2):
        kw = dict(model="transe", paradigm="sgd", n_workers=W, dim=8,
                  learning_rate=0.05, batch_size=16, epochs=4, seed=0,
                  pipeline="device", block_epochs=2,
                  merge_every=merge_every, merge_transport="sparse")
        ref = kg_api.fit(kg, backend="shard_map", mesh=mesh, **kw)
        got = kg_api.fit(kg, backend="shard_map", mesh=mesh,
                         table_sharding="sharded", **kw)
        vm = kg_api.fit(kg, table_sharding="sharded", **kw)
        # the residency claim, not just the math: the entity table must
        # *rest* row-sharded (~1/W rows on each device) after the run,
        # while the tiny relation table (5 rows < W) stays replicated
        ent_spec = got.params["ent"].sharding.spec
        assert tuple(ent_spec) == ("workers",), (
            f"entity table rests {ent_spec}, expected row-sharded")
        rows = sorted(s.data.shape[0]
                      for s in got.params["ent"].addressable_shards)
        assert rows == [200 // W] * W, f"per-device ent rows {rows}"
        assert tuple(got.params["rel"].sharding.spec) == (), (
            "relation table should rest replicated")
        for k in ("ent", "rel"):
            np.testing.assert_array_equal(
                np.asarray(got.params[k]), np.asarray(ref.params[k]),
                err_msg=f"sharded train K={merge_every} shard_map table {k}")
            np.testing.assert_array_equal(
                np.asarray(vm.params[k]), np.asarray(ref.params[k]),
                err_msg=f"sharded train K={merge_every} vmap table {k}")
        print(f"sharded tables K={merge_every}: sharded == replicated "
              "params across backends (exact)  OK")

    model = get_model("transe")
    params = model.init_params(
        jax.random.PRNGKey(2),
        KGConfig(n_entities=200, n_relations=5, dim=8))
    masks = kg.eval_filter_candidates()
    ref = eval_device.entity_ranks_device(
        params, kg.test, "l1", masks, model=model, n_workers=W)
    got = eval_device.entity_ranks_device(
        params, kg.test, "l1", masks, model=model, n_workers=W,
        backend="shard_map", mesh=mesh, table_sharding="sharded")
    for grp in ("raw_ranks", "filtered_ranks"):
        for side in ("tail", "head"):
            np.testing.assert_array_equal(
                got[grp][side], ref[grp][side],
                err_msg=f"sharded eval {grp}/{side}")
    print("sharded eval: shard-local scan == replicated (exact)  OK")

    h, r = kg.test[:32, 0], kg.test[:32, 1]
    exclude = kg.known_candidate_masks(np.stack([h, r], axis=1), "tail")
    ref_eng = KGQueryEngine("transe", params, n_workers=W)
    shard_eng = KGQueryEngine(
        "transe", params, n_workers=W, backend="shard_map", mesh=mesh,
        table_sharding="sharded")
    for label, q_kw in (("raw", {}), ("filtered", {"exclude": exclude})):
        for k in (10, 40):           # 40 > R=25: the local-kk cut
            a = ref_eng.query_tails(h, r, k=k, **q_kw)
            b = shard_eng.query_tails(h, r, k=k, **q_kw)
            np.testing.assert_array_equal(
                b.ids, a.ids, err_msg=f"sharded serve {label} k={k} ids")
            np.testing.assert_array_equal(
                b.energies, a.energies,
                err_msg=f"sharded serve {label} k={k} energies")
    print("sharded serve: shard-local top-k == replicated (exact)  OK")


def check_bounded_staleness():
    """Bounded-staleness Reduce (staleness=S) at real W=8: the stale
    schedule runs on a real mesh with the params bitwise-equal to the vmap
    simulation (dense and sparse transports, sharded tables), and the
    reported loss within the usual collective tolerance."""
    from repro import kg as kg_api

    kg = kg_lib.synthetic_kg(0, n_entities=200, n_relations=5, n_triplets=2000)
    mesh = jax.make_mesh((W,), ("workers",))
    for extra in ({}, {"merge_transport": "sparse"},
                  {"merge_transport": "sparse", "table_sharding": "sharded"}):
        kw = dict(model="transe", paradigm="sgd", n_workers=W, dim=8,
                  learning_rate=0.05, batch_size=16, epochs=8, seed=0,
                  pipeline="device", block_epochs=4, merge_every=2,
                  staleness=2, **extra)
        res_v = kg_api.fit(kg, **kw)
        res_s = kg_api.fit(kg, backend="shard_map", mesh=mesh, **kw)
        for k in ("ent", "rel"):
            np.testing.assert_array_equal(
                np.asarray(res_s.params[k]), np.asarray(res_v.params[k]),
                err_msg=f"staleness {extra} table {k}")
        np.testing.assert_allclose(
            np.asarray(res_s.loss_history), np.asarray(res_v.loss_history),
            rtol=1e-6, err_msg=f"staleness {extra} losses")
        label = extra.get("merge_transport", "dense")
        if extra.get("table_sharding") == "sharded":
            label += "/sharded"
        print(f"bounded staleness S=2 ({label}): shard_map == vmap "
              "(params exact)  OK")


if __name__ == "__main__":
    check_engine()
    check_outer_merge()
    check_device_pipeline()
    check_device_eval()
    check_repartition()
    check_inloop_eval()
    check_kb_resume_serve()
    check_kg_server()
    check_sparse_transport()
    check_sharded_tables()
    check_bounded_staleness()
    print("ALL MULTIDEVICE CHECKS PASSED")
