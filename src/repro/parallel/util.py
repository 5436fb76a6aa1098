"""Mesh-aware sharding-constraint helper.

``constrain(x, spec_axes)`` applies ``with_sharding_constraint`` when traced
under an ambient mesh (the dry-run / production path) and is a no-op on
plain CPU traces (smoke tests) — and it silently drops axes the current
mesh doesn't have or that don't divide the dim, so the same model code runs
on (16,16), (2,16,16) and single-device meshes.
"""
from __future__ import annotations

from typing import Sequence, Tuple, Union

import jax
from jax.sharding import PartitionSpec as P

from repro import obs

AxisLike = Union[None, str, Tuple[str, ...]]


def worker_map(fn, *, backend: str, mesh=None, axis_name: str = "workers"):
    """Lift ``fn(broadcast, *per_worker)`` over a leading worker axis.

    The KG engine's two execution backends, as one combinator: ``vmap``
    simulates the workers on a single device; ``shard_map`` places them on a
    real mesh axis.  ``broadcast`` (a pytree, e.g. the embedding tables) is
    replicated to every worker; each remaining argument carries a leading
    ``(W, ...)`` axis that is split across workers.  Outputs regain the
    leading ``W`` axis on both backends, so callers are backend-agnostic —
    this is what the device eval engine shards the query axis with, and the
    same contract ``core/mapreduce.py`` hand-rolls for training."""
    if backend == "vmap":
        def run(broadcast, *sharded):
            return jax.vmap(lambda *xs: fn(broadcast, *xs))(*sharded)
        return run
    if backend != "shard_map":
        raise ValueError(f"bad backend {backend!r}")
    if mesh is None:
        raise ValueError("shard_map backend needs a mesh")

    def run(broadcast, *sharded):
        W = sharded[0].shape[0]
        M = mesh.shape[axis_name]
        if W % M != 0:
            raise ValueError(
                f"worker axis of size {W} does not divide over mesh axis "
                f"{axis_name!r} of size {M}")

        # each shard holds W/M worker blocks; vmap over them so W may be
        # any multiple of the mesh axis size (W == M leaves a 1-wide vmap)
        def worker(broadcast, *xs):
            return jax.vmap(lambda *ys: fn(broadcast, *ys))(*xs)

        f = jax.shard_map(
            worker, mesh=mesh,
            in_specs=(P(),) + (P(axis_name),) * len(sharded),
            out_specs=P(axis_name), check_vma=False,
        )
        return f(broadcast, *sharded)
    return run


@obs.scope("reduce.exchange")
def all_gather_deltas(packed, axis_name: str):
    """All-gather a worker's packed sparse-delta buffers across the named
    shard_map axis: every leaf of the pytree (row ids, values, counts,
    losses — see ``core/merge.pack_delta``) gains a leading ``(W, ...)``
    worker axis, ordered by axis index.  This is the sparse transport's
    only cross-worker traffic: O(W·C·k) wire bytes per table instead of
    the dense paths' O(W·N·k) all_gather / O(N·k)-per-psum, with C the
    static touched-row capacity."""
    return jax.tree.map(lambda x: jax.lax.all_gather(x, axis_name), packed)


def _ambient_mesh():
    try:
        m = jax.sharding.get_abstract_mesh()
        if m is not None and not m.empty:
            return m
    except Exception:
        pass
    try:
        from jax._src import mesh as mesh_lib

        m = mesh_lib.thread_resources.env.physical_mesh
        if m is not None and not m.empty:
            return m
    except Exception:
        pass
    return None


def constrain_batch(x: jax.Array, profile: str) -> jax.Array:
    """Pin dim0 (batch) of an activation to the data-parallel axes.

    Without this, GSPMD may resolve the FSDP contraction (activation
    batch-sharded over 'data' x weight fsdp-sharded over 'data') by
    REPLICATING the activation instead of gathering the weight — observed
    as full-global-batch residual saves and 16x redundant layer compute on
    the gemma2-9b dry-run.  Pinning the batch axis makes weight-gathering
    the only legal resolution (proper FSDP)."""
    axes = ("pod", "data", "model") if profile == "dp" else ("pod", "data")
    return constrain(x, (axes,) + (None,) * (x.ndim - 1))


def constrain(x: jax.Array, axes: Sequence[AxisLike]) -> jax.Array:
    mesh = _ambient_mesh()
    if mesh is None:
        return x
    names = set(mesh.axis_names)
    spec = []
    for dim, ax in zip(x.shape, tuple(axes) + (None,) * (x.ndim - len(axes))):
        if ax is None:
            spec.append(None)
            continue
        group = tuple(a for a in ((ax,) if isinstance(ax, str) else ax)
                      if a in names)
        # longest prefix of the axis group that divides the dim (a batch of
        # 32 on a 256-way dp group still shards 16-way instead of dropping)
        kept = []
        size = 1
        for a in group:
            nxt = size * mesh.shape[a]
            if dim % nxt != 0:
                break
            kept.append(a)
            size = nxt
        spec.append(tuple(kept) if kept and size > 1 else None)
    return jax.lax.with_sharding_constraint(x, P(*spec))
