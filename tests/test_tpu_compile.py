"""Compiles of the main path for a described TPU v5e chip.

Nothing runs here: each test lowers and compiles for the first chip of a
described ``v5e:2x2`` topology, which refuses what the chip's compiler
would refuse — a kernel over its scoped VMEM, an unaligned block, a
program larger than the chip's 16 GB — with no chip attached.  Interpret
mode, which every other kernel test uses, checks none of that.

Shapes are ``chip_smoke.py``'s deployment: FB15k's entity and relation
counts at dim 400.  The topology is described inside a fixture and never
at import time: only one process at a time may load the TPU library.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

import chip_smoke as smoke
from repro import kg
from repro.core import eval_device, mapreduce
from repro.core.models import get_model
from repro.data import kg as kg_lib
from repro.kernels import rank_topk

HBM_BYTES = 16 * 10**9      # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back without one
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _tables(sharding):
    k = smoke.FIT["dim"]
    return {"ent": _spec(sharding, (smoke.N_ENTITIES, k)),
            "rel": _spec(sharding, (smoke.N_RELATIONS, k))}


# query rows = the eval chunk of 256 split over W in {1, 2, 4, 8} workers
@pytest.mark.parametrize("norm", ["l1", "l2"])
@pytest.mark.parametrize("k", [50, 400])
@pytest.mark.parametrize("rows", [32, 64, 128, 256])
def test_rank_counts_compiles(one_chip, rows, k, norm):
    E = smoke.N_ENTITIES
    fn = jax.jit(lambda q, t, g: rank_topk.rank_counts(
        q, t, g, norm=norm, interpret=False))
    compiled = fn.lower(_spec(one_chip, (rows, k)), _spec(one_chip, (E, k)),
                        _spec(one_chip, (rows,))).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_default_eval_program_holds_kernel(one_chip):
    """The device eval the smoke runs by default (one worker, the fused
    TransE path) compiles with the Mosaic kernel inside it."""
    S, C, _ = eval_device._layout(
        smoke.N_EVAL, eval_device.DEFAULT_CHUNK, 1)
    ids = _spec(one_chip, (1, S, C, 3), jnp.int32)
    cands = _spec(one_chip, (1, S, C, 8), jnp.int32)
    compiled = eval_device._entity_ranks_device.lower(
        get_model("transe"), _tables(one_chip), ids, cands, cands,
        norm="l1", backend="vmap", mesh=None, axis_name="workers",
        fused=True, relations=True).compile()
    assert "tpu_custom_call" in compiled.as_text()


# RotatE at the authors' FB15k width: 1,000 complex columns, so entity rows
# of 2,000 floats; its eval chunk (48 rows, ``eval_device.chunk_rows``)
# split over W in {1, 4} workers
ROTATE_K = 1000


@pytest.mark.parametrize("rows", [12, 48])
def test_modulus_rank_counts_compiles(one_chip, rows):
    E = smoke.N_ENTITIES
    fn = jax.jit(lambda q, t, g: rank_topk.modulus_rank_counts(
        q, t, g, interpret=False))
    compiled = fn.lower(_spec(one_chip, (rows, 2 * ROTATE_K)),
                        _spec(one_chip, (E, 2 * ROTATE_K)),
                        _spec(one_chip, (rows,))).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_kernels_carry_their_own_names(one_chip):
    """TransE's kernel is still the custom call ``rank_counts`` and
    RotatE's is ``modulus_rank_counts``: the trace finds each by name."""
    E, k = smoke.N_ENTITIES, smoke.FIT["dim"]
    transe = jax.jit(lambda q, t, g: rank_topk.rank_counts(q, t, g)).lower(
        _spec(one_chip, (64, k)), _spec(one_chip, (E, k)),
        _spec(one_chip, (64,))).compile().as_text()
    rotate = jax.jit(
        lambda q, t, g: rank_topk.modulus_rank_counts(q, t, g)).lower(
        _spec(one_chip, (48, 2 * ROTATE_K)),
        _spec(one_chip, (E, 2 * ROTATE_K)),
        _spec(one_chip, (48,))).compile().as_text()
    assert re.search(r"%rank_counts[.\d]* = .*tpu_custom_call", transe)
    assert not re.search(r"%modulus_rank_counts", transe)
    assert re.search(r"%modulus_rank_counts[.\d]* = .*tpu_custom_call",
                     rotate)


def test_rotate_eval_program_fits_one_chip(one_chip):
    """RotatE's default device eval at FB15k's shape — its 48-query chunk,
    the fused kernel, the filter over known groups 1,001 wide and the
    relation scan — compiles with the kernel and needs less than one
    chip's memory."""
    E, R = smoke.N_ENTITIES, smoke.N_RELATIONS
    tables = {"ent": _spec(one_chip, (E, 2 * ROTATE_K)),
              "rel": _spec(one_chip, (R, ROTATE_K))}
    C = eval_device.chunk_rows(tables)
    assert C == 48
    S, C, _ = eval_device._layout(59_071, C, 1)
    ids = _spec(one_chip, (1, S, C, 3), jnp.int32)
    cands = _spec(one_chip, (1, S, C, 1001), jnp.int32)
    compiled = eval_device._entity_ranks_device.lower(
        get_model("rotate"), tables, ids, cands, cands, norm="l1",
        backend="vmap", mesh=None, axis_name="workers", fused=True,
        relations=True).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert 0 < total < HBM_BYTES, mem


def _training_block(sharding, **fit_kw):
    """The smoke's one-epoch device-pipeline block, compiled, and its
    worker count."""
    empty = np.zeros((0, 3), np.int32)
    graph = kg_lib.KG(smoke.N_ENTITIES, smoke.N_RELATIONS, empty, empty,
                      empty)
    fit_kw = {**{n: v for n, v in smoke.FIT.items()
                 if n not in ("model", "paradigm")}, **fit_kw}
    kcfg, mcfg = kg.make_configs(graph, "transe", "sgd", **fit_kw)
    W = mcfg.n_workers
    partitioned = np.random.default_rng(0).integers(
        0, smoke.N_ENTITIES, size=(W, smoke.N_TRAIN // W, 3)).astype(np.int32)
    block = mapreduce.make_block_fn(
        mcfg, kcfg, partitioned, model=get_model("transe"), donate=True,
        with_overflow=mcfg.merge_transport == "sparse")
    compiled = block.lower(_tables(sharding),
                           _spec(sharding, (1,), jnp.int32)).compile()
    return compiled, W


def test_training_block_fits_one_chip(one_chip):
    """One device-pipeline epoch block at the smoke's configuration
    compiles and needs less than one chip's memory."""
    compiled, _ = _training_block(one_chip)
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert 0 < total < HBM_BYTES, mem


_COMPUTATION = re.compile(r"^(?:ENTRY )?%([\w.\-]+) .*\{$")
_INSTRUCTION = re.compile(
    r"^(?:ROOT )?%([\w.\-]+) = (.*?) ([a-z][\w\-]*)\((.*)$")
_ARRAY = re.compile(r"\b[a-z]\w*\[([\d,]*)\]")
# ops that name or regroup a buffer without reading or writing it
_NO_DATA = {"parameter", "get-tuple-element", "tuple", "bitcast", "while"}


def _array_sizes(shape: str) -> list:
    return [int(np.prod([int(d) for d in dims.split(",") if d]))
            for dims in _ARRAY.findall(shape)]


def _step_loop_table_ops(text: str, n_elems: int) -> tuple[int, list]:
    """In compiled HLO ``text``: the innermost ``while`` loops that carry
    an array of ``n_elems`` elements (the SGD step loop, which carries the
    worker tables), and the ops of their bodies that produce an array of
    that size, other than buffer bookkeeping and an in-place row scatter
    (a fusion whose root scatters into its own parameter)."""
    comps, cur = {}, None
    for line in text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            cur = comps.setdefault(m.group(1), [])
        elif cur is not None and line.startswith("  "):
            m = _INSTRUCTION.match(line.strip())
            if m:
                cur.append(m.groups())
    loops = {re.search(r"body=%([\w.\-]+)", rest).group(1)
             for body in comps.values()
             for _, shape, op, rest in body
             if op == "while" and n_elems in _array_sizes(shape)}

    def calls(comp, seen):
        for _, _, _, rest in comps.get(comp, ()):
            for callee in re.findall(r"(?:calls|body|condition)=%([\w.\-]+)",
                                     rest):
                if callee not in seen:
                    seen.add(callee)
                    calls(callee, seen)
        return seen

    innermost = [b for b in loops if not loops & calls(b, set())]

    def in_place_scatter(fusion_rest):
        callee = re.search(r"calls=%([\w.\-]+)", fusion_rest)
        body = comps.get(callee.group(1), []) if callee else []
        ops = {name: (op, rest) for name, _, op, rest in body}
        root = body[-1] if body else None
        if root is None or root[2] not in ("scatter", "dynamic-update-slice"):
            return False
        operand = re.match(r"\s*%([\w.\-]+)", root[3]).group(1)
        return ops.get(operand, ("",))[0] == "parameter"

    bad = [f"{name} = {shape} {op}"
           for loop in innermost for name, shape, op, rest in comps[loop]
           if n_elems in _array_sizes(shape) and op not in _NO_DATA
           and not (op == "fusion" and in_place_scatter(rest))]
    return len(innermost), bad


@pytest.mark.parametrize("transport", ["dense", "sparse"])
def test_train_step_loop_rewrites_no_table(one_chip, transport):
    """At the smoke's shape a batch references at most 4 × 256 of the
    14,951 entity rows, so the Map steps those rows alone, on the W
    workers' tables carried flat: inside the SGD step loop no op produces
    an array the size of the W worker tables (W·E·k elements) except the
    in-place row scatter — no layout copy, reshape, zeroed gradient or
    update of every row per step.  Either Reduce transport."""
    compiled, W = _training_block(one_chip, merge_transport=transport)
    n_loops, bad = _step_loop_table_ops(
        compiled.as_text(), W * smoke.N_ENTITIES * smoke.FIT["dim"])
    assert n_loops == 1
    assert not bad, bad


@pytest.mark.parametrize("program_name", ["train_block", "eval_scan"])
def test_scopes_change_only_metadata_on_v5e(one_chip, program_name):
    """The chip's compiler, too, makes the same program with the
    ``repro.*`` scopes as without them (``tests/test_obs.py`` checks the
    CPU's): the training block and the fused eval scan with its kernel,
    at a small width."""
    from test_obs import program, scoped_and_plain, scopes_in

    E, R, k = 2048, 64, 128
    tables = {"ent": _spec(one_chip, (E, k)), "rel": _spec(one_chip, (R, k))}
    if program_name == "train_block":
        empty = np.zeros((0, 3), np.int32)
        graph = kg_lib.KG(E, R, empty, empty, empty)
        kcfg, mcfg = kg.make_configs(graph, "transe", "sgd", dim=k,
                                     n_workers=4, batch_size=64)
        partitioned = np.random.default_rng(0).integers(
            0, R, size=(4, 1024, 3)).astype(np.int32)

        def compile_text():
            block = mapreduce.make_block_fn(
                mcfg, kcfg, partitioned, model=get_model("transe"))
            return block.lower(tables, _spec(one_chip, (1,), jnp.int32)) \
                .compile().as_text()
    else:
        ids = _spec(one_chip, (1, 4, 64, 3), jnp.int32)
        cands = _spec(one_chip, (1, 4, 64, 8), jnp.int32)

        def compile_text():
            return eval_device._entity_ranks_device.lower(
                get_model("transe"), tables, ids, cands, cands,
                norm="l1", backend="vmap", mesh=None, axis_name="workers",
                fused=True, relations=True).compile().as_text()

    scoped, plain = scoped_and_plain(compile_text)
    assert program(scoped) == program(plain)
    assert scopes_in(scoped)
