"""Training through ``repro.kg.fit`` on the device pipeline.

Set-up starts one ``kg.fit`` call, on a thread of its own, with the
benchmark's tables and the mix's settings (one epoch per block).  The fit
builds and compiles its epoch block and runs epochs 0-2, the steps the
reference follows; its callback then holds it until the window opens.
The window is the same fit going on until the callback after the block
that ends past the window's length stops it.  A rate is the triples
trained in the window over its wall time, ending on the synced block.

What the yardstick reads of the program, besides ``kg.fit`` and its
``callback``: ``repro.core.mapreduce.make_block_fn``, wrapped for the
fit's one call of it, so as to read the triples of an epoch and the
tables after epochs 1 and 3.  The epoch block holds the split triples
and the fit's seed as constants, so the graph is the configuration's as
drawn and the fit's seed is the mix's ``fit_seed``: every run compiles
the same program.  ``--seed`` draws the tables.
"""
from __future__ import annotations

import threading
import time

import numpy as np

from bench import graph as graph_lib
from bench import reference, weights

CHECK_STEPS = 3


class Stop(Exception):
    """Raised by the callback to end the fit when the window closes."""


def leaf_gap(prog: dict, ref: dict) -> float:
    """The worst leaf's gap between two norms, over the larger of that
    leaf's reference norm and the median leaf's.  A leaf the reference
    moves by less than a thousandth of the median leaf (round-off alone)
    is left out."""
    med = float(np.median(list(ref.values())))
    return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in ref
               if ref[k] >= 1e-3 * med)


def inputs(config: dict, mix: dict):
    """The graph and the fit's seed: the same for every ``--seed``."""
    return graph_lib.structure(config["graph"]), mix["fit_seed"]


class Entry:
    def __init__(self, cell):
        self.cell = cell
        self.losses: list = []
        self.change: dict = {}
        self.blocks = 0
        self.error = None
        self.until = None
        self.annotate = False
        self.ready, self.go = threading.Event(), threading.Event()

    def setup(self) -> None:
        import jax

        from repro import kg as kg_api
        from repro.core import mapreduce
        from repro.data import kg as kg_lib

        c, cfg, mix = self.cell, self.cell.config, self.cell.mix
        tr = cfg["train"]
        self.graph, self.fit_seed = inputs(cfg, mix)
        g = self.graph
        kg = kg_lib.KG(g.n_entities, g.n_relations, g.train, g.valid, g.test)
        mesh = None
        if mix["backend"] == "shard_map":
            mesh = jax.make_mesh((mix["n_workers"],), ("workers",),
                                 devices=c.devices[:mix["n_workers"]])
        make = mapreduce.make_block_fn
        mapreduce.make_block_fn = self._watch(make)
        self.thread = threading.Thread(target=self._fit, daemon=True, args=(
            kg_api, kg, weights.make(cfg, c.seed), mesh, dict(
                dim=cfg["dim"], margin=tr["margin"], norm=cfg["norm"],
                learning_rate=tr["learning_rate"],
                normalize=tr["normalize"], sampling=tr["sampling"],
                negatives=tr["negatives"], strategy=tr["strategy"],
                n_workers=mix["n_workers"], batch_size=mix["batch_size"],
                backend=mix["backend"],
                merge_transport=mix["merge_transport"],
                table_sharding=mix["table_sharding"], pipeline="device",
                block_epochs=1, merge_every=mix["merge_every"])))
        try:
            self.thread.start()
            self.ready.wait()
        finally:
            mapreduce.make_block_fn = make
        if self.error is not None:
            raise self.error

    def _watch(self, make):
        """``make_block_fn`` that also reads the triples of an epoch and,
        in set-up, the tables' change after the first and third block."""
        import jax
        import jax.numpy as jnp

        def norms(state, start):
            return {k: float(jnp.linalg.norm(state[k] - start[k]))
                    for k in start}

        def made(cfg, tcfg, partitioned, **kw):
            block = make(cfg, tcfg, partitioned, **kw)
            W, n_w = partitioned.shape[:2]
            B = cfg.batch_size
            self.triples_per_epoch = W * (n_w // B) * B
            start = {}

            def run(state, ids):
                if not self.blocks:
                    start.update(jax.tree.map(jnp.copy, state))
                if self.annotate:
                    with jax.profiler.TraceAnnotation("bench.fit.dispatch"):
                        out = block(state, ids)
                else:
                    out = block(state, ids)
                self.blocks += 1
                self.state = out[0]
                if self.blocks in (1, CHECK_STEPS):
                    self.change[self.blocks] = norms(out[0], start)
                if self.blocks == CHECK_STEPS:
                    start.clear()
                return out
            return run
        return made

    def _fit(self, kg_api, kg, tables, mesh, config_kw) -> None:
        try:
            kg_api.fit(kg, self.cell.config["model"], "sgd", epochs=2**30,
                       seed=self.fit_seed, mesh=mesh, params=tables,
                       callback=self._callback, **config_kw)
        except Stop:
            pass
        except Exception as exc:      # raised again in the main thread
            self.error = exc
        finally:
            self.state = None
            self.ready.set()

    def _callback(self, epoch: int, loss: float) -> None:
        import jax

        if len(self.losses) < CHECK_STEPS:
            self.losses.append(loss)
            if len(self.losses) == CHECK_STEPS:
                self.ready.set()
                self.go.wait()
                self.t0, self.n = time.perf_counter(), 0
            return
        self.n += 1
        if self.until(self.n, time.perf_counter() - self.t0):
            jax.block_until_ready(self.state)
            self.t1 = time.perf_counter()
            raise Stop

    def _run(self, until) -> dict:
        self.until = until
        self.go.set()
        self.thread.join()
        if self.error is not None:
            raise self.error
        n, secs = self.n, self.t1 - self.t0
        self.cell.work = {"blocks": n, "triples": n * self.triples_per_epoch,
                          "seconds": secs}
        return {"attempted": n, "failed": 0,
                "metrics": {"train_triples_per_s":
                            n * self.triples_per_epoch / secs},
                "log": {"epochs_in_window": n, "window_s": secs}}

    def window(self, seconds: float) -> dict:
        return self._run(lambda n, t: t >= seconds)

    def trace_window(self) -> dict:
        self.annotate = True
        return self._run(lambda n, t: n >= self.cell.mix["trace_blocks"])

    def check(self) -> list:
        """Each checked epoch's loss, the first epoch's change and the
        third's, against the reference run from the same tables."""
        c, cfg, mix = self.cell, self.cell.config, self.cell.mix
        tr = cfg["train"]
        tables = weights.make(cfg, c.seed, device=c.devices[0])
        losses, states = reference.train(
            cfg["model"], tables, self.graph.train, self.fit_seed,
            n_workers=mix["n_workers"], batch=mix["batch_size"],
            margin=tr["margin"], lr=tr["learning_rate"], epochs=CHECK_STEPS)
        t0 = {k: np.asarray(v, np.float32) for k, v in tables.items()}
        ref = {s: {k: float(np.linalg.norm(states[s - 1][k] - t0[k]))
                   for k in t0} for s in self.change}
        limits = c.limits
        return [
            {"name": "loss_gap", "limit": limits["loss_gap"],
             "value": max(abs(a - b) / abs(b)
                          for a, b in zip(self.losses, losses))},
            {"name": "step1_change_gap", "limit": limits["step1_change_gap"],
             "value": leaf_gap(self.change[1], ref[1])},
            {"name": "step3_change_gap", "limit": limits["step3_change_gap"],
             "value": leaf_gap(self.change[CHECK_STEPS], ref[CHECK_STEPS])},
        ]
