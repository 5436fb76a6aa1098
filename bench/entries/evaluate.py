"""The paper's three-task evaluation through ``repro.kg.evaluate`` with the
device engine, over the whole test split.

Set-up makes the graph and the tables from the seed and runs one full
pass, which compiles the engine and builds the graph's filter caches.
The window repeats full passes; a rate is test triples over the window's
wall time.  Every pass's per-query ranks (the engine's own outputs, seen
on their way to the metrics through ``repro.core.eval_device.
entity_ranks_device``) and its classification accuracy are kept, and the
last pass is compared with the reference query by query.
"""
from __future__ import annotations

import hashlib
import time

import numpy as np

from bench import graph as graph_lib
from bench import reference, weights


def _digest(arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


class Entry:
    def __init__(self, cell):
        self.cell = cell

    def setup(self) -> None:
        from repro import kg as kg_api
        from repro.core import eval_device
        from repro.data import kg as kg_lib

        c, cfg, mix = self.cell, self.cell.config, self.cell.mix
        g = graph_lib.generate(cfg["graph"], c.seed)
        self.graph = g
        self.kg = kg_lib.KG(g.n_entities, g.n_relations, g.train, g.valid,
                            g.test)
        self.params = weights.make(cfg, c.seed)
        self.kw = dict(engine="device", filtered=True,
                       n_workers=mix["n_workers"])
        if mix.get("fused") is not None:
            self.kw["fused"] = mix["fused"]
        self.seen = {}
        ranks_fn = eval_device.entity_ranks_device

        def seen_ranks(*a, **kw):
            out = ranks_fn(*a, **kw)
            self.seen["ranks"] = out
            return out

        # observe the ranks the engine produces on their way to the metrics
        eval_device.entity_ranks_device = seen_ranks
        self._restore = lambda: setattr(eval_device, "entity_ranks_device",
                                        ranks_fn)
        self._kg_api = kg_api
        self.digests = []
        self.one_pass()

    def one_pass(self) -> dict:
        m = self._kg_api.evaluate(self.params, self.cell.config["model"],
                                  self.kg, norm=self.cell.config["norm"],
                                  **self.kw)
        self.metrics = m
        return m

    def _flat(self) -> dict:
        r = self.seen["ranks"]
        return {"tail_raw": r["raw_ranks"]["tail"],
                "head_raw": r["raw_ranks"]["head"],
                "tail_filtered": r["filtered_ranks"]["tail"],
                "head_filtered": r["filtered_ranks"]["head"],
                "relation": r["relation_ranks"],
                "accuracy": np.float64(
                    self.metrics["triplet_classification_acc"])}

    def _run(self, until, annotate: bool = False) -> dict:
        import jax

        t0 = time.perf_counter()
        n = 0
        while not until(n, time.perf_counter() - t0):
            if annotate:
                with jax.profiler.TraceAnnotation("bench.eval.pass"):
                    self.one_pass()
            else:
                self.one_pass()
            self.digests.append(_digest(self._flat().values()))
            n += 1
        secs = time.perf_counter() - t0
        q = len(self.graph.test)
        self.cell.work = {"passes": n, "test_triples": n * q,
                          "seconds": secs}
        return {"attempted": n * q, "failed": 0,
                "metrics": {"eval_triples_per_s": n * q / secs},
                "log": {"passes_in_window": n, "window_s": secs,
                        "entity_filtered": self.metrics["entity_filtered"]}}

    def window(self, seconds: float) -> dict:
        return self._run(lambda n, t: n > 0 and t >= seconds)

    def trace_window(self) -> dict:
        from repro.core import eval_device

        ranks_fn = eval_device.entity_ranks_device
        import jax

        def spans(*a, **kw):
            with jax.profiler.TraceAnnotation("bench.eval.ranks"):
                return ranks_fn(*a, **kw)

        eval_device.entity_ranks_device = spans
        try:
            return self._run(
                lambda n, t: n >= self.cell.mix["trace_passes"],
                annotate=True)
        finally:
            eval_device.entity_ranks_device = ranks_fn

    def check(self) -> list:
        """Every test query's five ranks and the classification accuracy
        of the window's last pass, against the reference; and every pass
        of the window against the last."""
        c, cfg = self.cell, self.cell.config
        got = self._flat()
        self._restore()
        del self.params, self.seen
        g = self.graph
        known = reference.Known(g.all_triples, g.n_entities, g.n_relations)
        tables = weights.make(cfg, c.seed, device=c.devices[0])
        ref = reference.ranks(cfg["model"], tables, g.test, known)
        rank_gap = max(int(np.max(np.abs(got[k].astype(np.int64)
                                         - ref[k].astype(np.int64))))
                       for k in ref)
        acc = reference.classify_accuracy(
            cfg["model"], tables, g.valid, g.test, g.n_entities,
            g.n_relations)
        acc_gap = abs(float(got["accuracy"]) - acc)
        last = self.digests[-1]
        limits = c.limits
        return [
            {"name": "rank_gap", "value": rank_gap,
             "limit": limits["rank_gap"]},
            {"name": "classify_accuracy_gap", "value": acc_gap,
             "limit": limits["classify_accuracy_gap"]},
            {"name": "passes_unlike_last",
             "value": sum(d != last for d in self.digests), "limit": 0},
        ]
