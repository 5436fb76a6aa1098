"""Compiled HLO of the shard_map training block on 4 virtual CPU devices,
with the ``repro.*`` scopes and with ``jax.named_scope`` replaced by a
null context.  Run by ``tests/test_obs.py`` in a process of its own (the
test process keeps one device); prints one JSON object
``{transport: {"scoped": text, "plain": text}}``.
"""
import contextlib
import json
import os
import sys

os.environ.setdefault(
    "XLA_FLAGS", "--xla_force_host_platform_device_count=4")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..",
                                "src"))

import jax                                           # noqa: E402
import jax.numpy as jnp                              # noqa: E402

from repro import kg as kg_api                       # noqa: E402
from repro.core import mapreduce                     # noqa: E402
from repro.core.models import get_model              # noqa: E402
from repro.data import kg as kg_lib                  # noqa: E402

W = 4


def block_text(graph, transport: str, sharding: str) -> str:
    jax.clear_caches()
    kcfg, mcfg = kg_api.make_configs(
        graph, "transe", "sgd", dim=8, n_workers=W, batch_size=16,
        backend="shard_map", merge_transport=transport,
        table_sharding=sharding)
    model = get_model("transe")
    n_w = len(graph.train) // W
    part = graph.train[:W * n_w].reshape(W, n_w, 3)
    mesh = jax.make_mesh((W,), ("workers",))
    block = mapreduce.make_block_fn(
        mcfg, kcfg, part, mesh=mesh, model=model,
        with_overflow=transport == "sparse")
    params = model.init_params(jax.random.PRNGKey(0), kcfg)
    return block.lower(params, jnp.arange(2, dtype=jnp.int32)) \
        .compile().as_text()


def main() -> None:
    assert len(jax.devices()) == W, jax.devices()
    graph = kg_lib.synthetic_kg(0, n_entities=64, n_relations=4,
                                n_triplets=800)
    out = {}
    for transport, sharding in (("dense", "replicated"),
                                ("sparse", "sharded")):
        scoped = block_text(graph, transport, sharding)
        named_scope = jax.named_scope
        jax.named_scope = lambda name: contextlib.nullcontext()
        try:
            plain = block_text(graph, transport, sharding)
        finally:
            jax.named_scope = named_scope
        out[transport] = {"scoped": scoped, "plain": plain}
    print(json.dumps(out))


if __name__ == "__main__":
    main()
