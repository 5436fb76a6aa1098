"""Open-loop traffic through ``repro.serve.KGServer``.

The mix fixes the offered rate, the share of each query kind and the
popularity skew of the keys.  For a window of ``s`` seconds a run sends
``rate * s`` requests at times drawn uniformly over the window (a Poisson
process with its count fixed, so every seed offers the same load), each
kind in its fixed share, each key drawn by a Zipf law over the graph's
distinct keys of that kind in a seeded order.  A request's latency runs
from when it was due to when its answer was set, so a stall counts
against every request behind it.

Set-up warms exactly the shapes the mix uses: a filtered wave of every
bucket for the entity kinds, and the relation kind's buckets.  After the
window a seeded sample of the answered requests is compared with the
reference's top-k.
"""
from __future__ import annotations

import functools
import threading
import time

import numpy as np

from bench import graph as graph_lib
from bench import reference, weights

KINDS = ("tails", "heads", "relations")


def distinct_keys(g) -> dict:
    """Each kind's distinct keys ``(a, b)`` as ``submit`` takes them:
    (h, r) for tails, (t, r) for heads, (h, t) for relations."""
    t = g.all_triples
    return {"tails": np.unique(t[:, [0, 1]], axis=0),
            "heads": np.unique(t[:, [2, 1]], axis=0),
            "relations": np.unique(t[:, [0, 2]], axis=0)}


def schedule(mix: dict, keys: dict, seed: int, seconds: float) -> dict:
    """Due times, kinds and keys of the requests of one window."""
    rng = np.random.default_rng([seed, 0x5E4E])
    n = int(round(mix["rate_per_s"] * seconds))
    due = np.sort(rng.uniform(0.0, seconds, n))
    shares = np.array([mix["kinds"][k] for k in KINDS], np.float64)
    counts = np.floor(shares / shares.sum() * n).astype(int)
    counts[0] += n - counts.sum()
    kind = rng.permutation(np.repeat(np.arange(len(KINDS)), counts))
    a = np.empty(n, np.int64)
    b = np.empty(n, np.int64)
    for i, name in enumerate(KINDS):
        pick = kind == i
        pool = keys[name]
        order = rng.permutation(len(pool))
        p = graph_lib.zipf_probs(len(pool), mix["key_zipf"])
        chosen = pool[order[rng.choice(len(pool), pick.sum(), p=p)]]
        a[pick], b[pick] = chosen[:, 0], chosen[:, 1]
    return {"due": due, "kind": kind, "a": a, "b": b}


def answer_gap(ids, energies, ref_e, scale: float) -> float:
    """How far one served answer lies from the reference, over the query's
    energy scale: the widest gap between a served energy and the reference
    energy of the same candidate, or between the reference energy of the
    candidate served at a position and the reference's own candidate
    there.  A served candidate the filter should have left out reads
    infinite."""
    ref_of_served = ref_e[ids]
    best = np.sort(ref_e)[:len(ids)]
    return float(max(np.max(np.abs(energies - ref_of_served)),
                     np.max(np.abs(ref_of_served - best))) / scale)


class Entry:
    def __init__(self, cell):
        self.cell = cell

    def setup(self) -> None:
        from repro.core.models import get_model
        from repro.data import kg as kg_lib
        from repro.kb import KnowledgeBase
        from repro.serve import KGServer

        c, cfg, mix = self.cell, self.cell.config, self.cell.mix
        g = graph_lib.generate(cfg["graph"], c.seed)
        self.graph = g
        kg = kg_lib.KG(g.n_entities, g.n_relations, g.train, g.valid, g.test)
        kb = KnowledgeBase(model=get_model(cfg["model"]),
                           params=weights.make(cfg, c.seed), graph=kg,
                           norm=cfg["norm"], meta={})
        self.keys = distinct_keys(g)
        self.server = KGServer(
            kb, max_batch=mix["max_batch"], max_wait_us=mix["max_wait_us"],
            cache_size=mix["cache_size"], default_k=mix["k"])
        self.warm()

    def submit(self, kind: str, a, b):
        filtered = self.cell.mix["filtered"] and kind != "relations"
        return self.server.submit(kind, int(a), int(b), filtered=filtered)

    def warm(self) -> None:
        """One wave of each bucket for each entity kind, then the
        server's own warm-up of the relation kind's buckets."""
        srv = self.server
        for kind in ("tails", "heads"):
            if self.cell.mix["kinds"].get(kind, 0) == 0:
                continue
            pool = self.keys[kind]
            start = 0
            for bucket in srv.buckets:
                # keys not asked before, so no answer comes from the cache
                # and the wave fills its bucket
                srv.pause()
                futs = [self.submit(kind, *pool[i])
                        for i in range(start, start + bucket)]
                srv.resume()
                for f in futs:
                    f.result(timeout=1200)
                start += bucket
        if self.cell.mix["kinds"].get("relations", 0):
            srv.warmup(kinds=("relations",), filtered=False)
        else:
            srv.warmup(kinds=())
        srv.clear_cache()

    def _run(self, seconds: float, annotate: bool = False) -> dict:
        """Offer the window's requests as they fall due.  The client keeps
        no future and no answer beyond the seeded sample the check reads:
        objects it kept alive would set off the interpreter's full
        collections inside the server's process, a cost of the client and
        not of the server."""
        import jax

        mix = self.cell.mix
        plan = schedule(mix, self.keys, self.cell.seed, seconds)
        n = len(plan["due"])
        rng = np.random.default_rng([self.cell.seed, 0xC4EC])
        sample = set(rng.choice(n, min(mix["check_sample"], n),
                                replace=False).tolist())
        done = np.full(n, np.nan)
        sent = np.full(n, np.nan)
        kept: dict = {}
        lock = threading.Lock()
        left = [n]
        all_done = threading.Event()

        def finished(i: int, fut) -> None:
            t = time.perf_counter()
            if fut.exception() is None:
                done[i] = t
                if i in sample:
                    kept[i] = fut.result()
            with lock:
                left[0] -= 1
                if left[0] == 0:
                    all_done.set()

        before = self.server.stats()
        t0 = time.perf_counter()
        for i in range(n):
            wait = t0 + plan["due"][i] - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            sent[i] = time.perf_counter()
            kind = KINDS[plan["kind"][i]]
            if annotate:
                with jax.profiler.TraceAnnotation("bench.serve.submit"):
                    f = self.submit(kind, plan["a"][i], plan["b"][i])
            else:
                f = self.submit(kind, plan["a"][i], plan["b"][i])
            f.add_done_callback(functools.partial(finished, i))
            del f
        all_done.wait(timeout=mix["answer_timeout_s"])
        with lock:                # answers after the timeout are failed
            answered_at = done.copy()
        ok = ~np.isnan(answered_at)
        after = self.server.stats()
        self.latency = answered_at - t0 - plan["due"]
        self.finished_s = (float(np.nanmax(answered_at) - t0) if ok.any()
                           else 0.0)
        lat = self.latency[ok]
        late = sent - t0 - plan["due"]
        waves = after.waves - before.waves
        rows = after.mean_wave * after.waves - before.mean_wave * before.waves
        hits = after.cache_hits - before.cache_hits
        self.plan, self.kept, self.ok = plan, kept, ok
        self.cell.counters.update(
            serve_waves=waves, serve_wave_rows=rows,
            serve_cache_hits=hits,
            steady_recompiles=after.steady_recompiles)
        self.cell.work = {"requests": n, "seconds": seconds}
        p95 = float(np.percentile(lat, 95) * 1e3) if len(lat) else None
        return {
            "attempted": n, "failed": int(n - ok.sum()),
            "metrics": {"serve_p95_ms": p95},
            "log": {"requests": n, "answered": int(ok.sum()),
                    "p50_ms": float(np.percentile(lat, 50) * 1e3),
                    "p95_ms": p95,
                    "p99_ms": float(np.percentile(lat, 99) * 1e3),
                    "client_late_p95_ms": float(np.percentile(late, 95)
                                                * 1e3),
                    "mean_wave": rows / max(waves, 1),
                    "cache_hit_share": hits / max(n, 1),
                    "steady_recompiles": after.steady_recompiles}}

    def window(self, seconds: float) -> dict:
        return self._run(seconds)

    def trace_window(self) -> dict:
        import jax

        cls = type(self.server)
        execute, exclusion = cls._execute, cls._wave_exclusion

        def spans(name, fn):
            def run(*a, **kw):
                with jax.profiler.TraceAnnotation(name):
                    return fn(*a, **kw)
            return run

        cls._execute = spans("bench.serve.wave", execute)
        cls._wave_exclusion = spans("bench.serve.masks", exclusion)
        try:
            return self._run(self.cell.mix["trace_seconds"], annotate=True)
        finally:
            cls._execute, cls._wave_exclusion = execute, exclusion

    def check(self) -> list:
        """A seeded sample of the answered requests, each against the
        reference's top-k of the same query (``answer_gap``)."""
        c, cfg, mix = self.cell, self.cell.config, self.cell.mix
        self.server.stop()
        del self.server
        g = self.graph
        pick = np.array(sorted(i for i in self.kept if self.ok[i]),
                        np.int64)
        known = reference.Known(g.all_triples, g.n_entities, g.n_relations)
        tables = weights.make(cfg, c.seed, device=c.devices[0])
        gap = 0.0
        for i, kind in enumerate(KINDS):
            sel = pick[self.plan["kind"][pick] == i]
            if not len(sel):
                continue
            filt = known if mix["filtered"] and kind != "relations" else None
            _, ref_e, scale = reference.top_k(
                cfg["model"], tables, kind, self.plan["a"][sel],
                self.plan["b"][sel], mix["k"], filt)
            for j, idx in enumerate(sel):
                ans = self.kept[idx]
                gap = max(gap, answer_gap(ans.ids, ans.energies, ref_e[j],
                                          scale[j]))
        return [
            {"name": "answer_gap", "value": gap,
             "limit": c.limits["answer_gap"]},
            {"name": "steady_recompiles",
             "value": c.counters["steady_recompiles"], "limit": 0},
        ]
