"""``repro.obs``: the scopes change op metadata and nothing else, every
scope reaches the compiled program, the spans reach a profiler trace, the
filter counters equal the mask arithmetic, and the Map-step counters count
each block's steps.

"Nothing else" is checked on compiled HLO text: with the metadata, the
stack-frame tables and the numbers that make instruction names unique
taken out (XLA numbers some constants of the eval scan in another order
when their ops carry another name), the program with the scopes and the
program with ``jax.named_scope`` replaced by a null context are equal.
"""
import contextlib
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import kg as kg_api
from repro import obs
from repro.core import eval_device, mapreduce
from repro.core.models import get_model
from repro.data import kg as kg_lib

HELPER = os.path.join(os.path.dirname(__file__), "helpers",
                      "obs_shardmap_hlo.py")
METADATA = re.compile(r", metadata=\{[^}]*\}")
FRAMES = re.compile(
    r"^(FileNames|FunctionNames|FileLocations|StackFrames)\n(?:.+\n)*",
    re.M)
NUMBERED = re.compile(r"\b([A-Za-z_][\w\-]*)\.(\d+)\b")
SCOPE = re.compile(r"repro\.[a-z_.]*[a-z]")


def program(text: str) -> str:
    """Compiled HLO text without what names the ops: metadata, the
    stack-frame tables, and the numbers of numbered names (renumbered in
    order of first use, per base name)."""
    text = FRAMES.sub("", METADATA.sub("", text))
    names: dict = {}
    per_base: dict = {}

    def renumber(m):
        if m[0] not in names:
            k = per_base.get(m[1], 0)
            per_base[m[1]] = k + 1
            names[m[0]] = f"{m[1]}.#{k}"
        return names[m[0]]

    return NUMBERED.sub(renumber, text)


def scopes_in(text: str) -> set:
    return set(SCOPE.findall(text))


@contextlib.contextmanager
def no_scopes():
    named_scope = jax.named_scope
    jax.named_scope = lambda name: contextlib.nullcontext()
    try:
        yield
    finally:
        jax.named_scope = named_scope


def scoped_and_plain(compile_text):
    jax.clear_caches()
    scoped = compile_text()
    jax.clear_caches()
    with no_scopes():
        plain = compile_text()
    jax.clear_caches()
    assert not scopes_in(plain), "the plain program was read from a cache"
    return scoped, plain


@pytest.fixture(scope="module")
def graph():
    return kg_lib.synthetic_kg(0, n_entities=64, n_relations=4,
                               n_triplets=800)


def _check_train_block_scopes(graph, transport, batch_size):
    kcfg, mcfg = kg_api.make_configs(
        graph, "transe", "sgd", dim=8, n_workers=2, batch_size=batch_size,
        merge_transport=transport)
    model = get_model("transe")
    part = graph.train[:len(graph.train) // 2 * 2].reshape(2, -1, 3)
    params = model.init_params(jax.random.PRNGKey(0), kcfg)

    def compile_text():
        block = mapreduce.make_block_fn(
            mcfg, kcfg, part, model=model,
            with_overflow=transport == "sparse")
        return block.lower(params, jnp.arange(2, dtype=jnp.int32)) \
            .compile().as_text()

    scoped, plain = scoped_and_plain(compile_text)
    assert program(scoped) == program(plain)
    assert scopes_in(scoped) == {"repro.map", "repro.negatives",
                                 "repro.reduce"}


@pytest.mark.parametrize("transport", ["dense", "sparse"])
def test_train_block_scopes_change_only_metadata(graph, transport):
    _check_train_block_scopes(graph, transport, 16)


@pytest.mark.parametrize("transport", ["dense", "sparse"])
def test_compact_map_block_scopes_change_only_metadata(graph, transport):
    """The same at batch 4, where the Map steps the flat worker tables."""
    _check_train_block_scopes(graph, transport, 4)


def test_shard_map_block_scopes_change_only_metadata():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, HELPER], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    for transport, texts in out.items():
        assert program(texts["scoped"]) == program(texts["plain"]), transport
        assert scopes_in(texts["scoped"]) == {
            "repro.map", "repro.negatives", "repro.reduce",
            "repro.reduce.exchange"}, transport
        assert not scopes_in(texts["plain"])


def _eval_layout(graph):
    tails, heads = graph.eval_filter_candidates()
    S, C, Qp = eval_device._layout(len(graph.test), 32, 1)

    def lay(a):
        return eval_device._shard(eval_device._pad_rows(a, Qp), 1, S, C)

    return lay(graph.test), lay(tails), lay(heads)


@pytest.mark.parametrize("sharded", [False, True])
def test_eval_scan_scopes_change_only_metadata(graph, sharded):
    model = get_model("transe")
    kcfg, _ = kg_api.make_configs(graph, "transe", "sgd", dim=8)
    params = model.init_params(jax.random.PRNGKey(0), kcfg)
    q, tc, hc = _eval_layout(graph)

    def compile_text():
        if sharded:
            lowered = eval_device._entity_ranks_sharded.lower(
                model, params, q[0], tc[0], hc[0], norm="l1",
                backend="vmap", mesh=None, axis_name="workers",
                n_shards=2, n_entities=graph.n_entities, relations=True)
        else:
            lowered = eval_device._entity_ranks_device.lower(
                model, params, q, tc, hc, norm="l1", backend="vmap",
                mesh=None, axis_name="workers", fused=False,
                relations=True)
        return lowered.compile().as_text()

    scoped, plain = scoped_and_plain(compile_text)
    assert program(scoped) == program(plain)
    assert scopes_in(scoped) == {"repro.eval.scan", "repro.eval.filter",
                                 "repro.eval.relations"}


def test_classify_scope_changes_only_metadata(graph):
    model = get_model("transe")
    kcfg, _ = kg_api.make_configs(graph, "transe", "sgd", dim=8)
    params = model.init_params(jax.random.PRNGKey(0), kcfg)
    triples = jnp.asarray(graph.valid)

    def compile_text():
        return eval_device._tc_scores.lower(
            model, params, triples, "l1").compile().as_text()

    scoped, plain = scoped_and_plain(compile_text)
    assert program(scoped) == program(plain)
    assert scopes_in(scoped) == {"repro.eval.classify"}


def test_program_normal_form():
    a = ("%broadcast.7 = f32[4]{0} broadcast(%c.2), dimensions={}, "
         'metadata={op_name="jit(f)/repro.map/mul" stack_frame_id=3}\n'
         "ROOT %add.1 = f32[4]{0} add(%broadcast.7, %broadcast.7)\n")
    b = ("%broadcast.9 = f32[4]{0} broadcast(%c.2), dimensions={}\n"
         "ROOT %add.1 = f32[4]{0} add(%broadcast.9, %broadcast.9)\n")
    assert program(a) == program(b)
    c = b.replace("add(%broadcast.9, %broadcast.9)",
                  "multiply(%broadcast.9, %broadcast.9)")
    assert program(a) != program(c)
    assert scopes_in(a) == {"repro.map"}


def test_counters_equal_mask_arithmetic(graph):
    tails, heads = graph.eval_filter_candidates()
    E, Q = graph.n_entities, len(graph.test)
    cells = Q * (tails.shape[1] + heads.shape[1])
    by_hr, by_rt = graph.known_index()
    known = sum(len(by_hr[(h, r)]) + len(by_rt[(r, t)])
                for h, r, t in graph.test.tolist())
    assert int((tails != E).sum() + (heads != E).sum()) == known
    assert graph.eval_filter_counts() == (cells, known)

    model = get_model("transe")
    kcfg, _ = kg_api.make_configs(graph, "transe", "sgd", dim=8)
    params = model.init_params(jax.random.PRNGKey(0), kcfg)
    obs.reset()
    for passes in (1, 2):
        eval_device.evaluate_all_device(params, graph, model="transe")
        assert obs.counters() == {"eval.filter_cells": passes * cells,
                                  "eval.filter_known": passes * known}
    eval_device.evaluate_all_device(params, graph, model="transe",
                                    filtered=False)
    assert obs.counters()["eval.filter_cells"] == 2 * cells
    obs.reset()
    assert obs.counters() == {}


@pytest.mark.parametrize("batch_size,step", [(16, "dense"), (4, "compact")])
def test_map_step_counted_once_per_block(graph, batch_size, step):
    """``map.compact_steps`` / ``map.dense_steps``: W × steps × epochs of
    the Map step the device pipeline ran (compact where 9B < E: 36 < 64
    at batch 4), counted on the host once per block."""
    n_w = len(graph.train) // 2
    obs.reset()
    for fits in (1, 2):
        kg_api.fit(graph, "transe", "sgd", epochs=2, dim=8, n_workers=2,
                   batch_size=batch_size, pipeline="device", block_epochs=1)
        assert obs.counters() == {
            f"map.{step}_steps": fits * 2 * (n_w // batch_size) * 2}
    obs.reset()


def test_counters_follow_max_fanout(graph):
    # every test triple is a known candidate of its own query: with one
    # column per side, every cell holds a real candidate
    with pytest.warns(UserWarning, match="max_fanout"):
        graph.eval_filter_candidates(max_fanout=1)
    assert graph.eval_filter_counts(1) == (2 * len(graph.test),
                                           2 * len(graph.test))
    graph.invalidate_caches()
    assert graph._filter_counts == {}


def test_spans_reach_the_profiler(graph, tmp_path):
    from jax.profiler import ProfileData

    model = get_model("transe")
    kcfg, _ = kg_api.make_configs(graph, "transe", "sgd", dim=8)
    params = model.init_params(jax.random.PRNGKey(0), kcfg)
    kg_api.fit(graph, "transe", "sgd", epochs=2, dim=8, n_workers=2,
               batch_size=35, pipeline="device", block_epochs=1,
               callback=lambda epoch, loss: None)         # warm
    eval_device.evaluate_all_device(params, graph, model="transe")
    with jax.profiler.trace(str(tmp_path)):
        kg_api.fit(graph, "transe", "sgd", epochs=2, dim=8, n_workers=2,
                   batch_size=35, pipeline="device", block_epochs=1,
                   callback=lambda epoch, loss: None)
        eval_device.evaluate_all_device(params, graph, model="transe")
    (path,) = tmp_path.rglob("*.xplane.pb")
    names = {ev.name for plane in ProfileData.from_file(str(path)).planes
             for line in plane.lines for ev in line.events
             if ev.name.startswith(obs.PREFIX)}
    assert names == {
        "repro.fit.block", "repro.fit.sync", "repro.fit.boundary",
        "repro.eval.masks", "repro.eval.layout", "repro.eval.ranks",
        "repro.eval.classify_host", "repro.eval.metrics"}


def test_count_is_thread_safe():
    import threading

    obs.reset()
    threads = [threading.Thread(
        target=lambda: [obs.count("t", 1) for _ in range(2000)])
        for _ in range(8)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert obs.counters() == {"t": 16000}
    obs.reset()


def test_scope_as_decorator_nests():
    @obs.scope("outer")
    def f(x):
        with obs.scope("inner"):
            return jnp.sin(x)

    text = jax.jit(f).lower(np.ones(4, np.float32)).as_text(
        debug_info=True)
    assert "repro.outer/repro.inner/sin" in text
