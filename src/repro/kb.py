"""``KnowledgeBase``: the persistent, serveable KG-embedding artifact.

The paper trains TransE-style embeddings so a knowledge repository can be
*used* — entity inference and relation prediction are its evaluation
tasks — but a trained model that lives only as an in-memory params dict
cannot be saved, resumed, or queried.  ``KnowledgeBase`` unifies
model + params + graph metadata into one artifact, the way DGL-KE serves
a trained embedding table and ParaGraphE exposes the library around the
embedding object:

    from repro import kg
    from repro.data import kg as kg_lib

    graph = kg_lib.synthetic_kg(0)
    result = kg.fit(graph, model="transe", epochs=50)
    kb = result.kb                      # the artifact, assembled by fit

    kb.save("my_kb")                    # persist (atomic, manifest'd)
    kb = kg.KnowledgeBase.load("my_kb")  # ... in the serving process

    top = kb.query_tails(h, r, k=10)           # device-resident top-k
    best = kb.query_relations(h, t, k=3)
    e = kb.score(h, r, t)
    metrics = kb.evaluate(engine="device")     # the paper's protocol

Persistence rides on ``train/checkpoint.py``: ``save`` writes the tables
(and, by default, the graph splits — so a loaded artifact can filter and
evaluate stand-alone) through the atomic ``step_`` layout with a manifest
carrying the model name, table dims, norm, and the graph's content
fingerprint; ``load`` restores self-describing (no shape templates
needed) and cross-checks manifest against tables, so a corrupted or
cross-model artifact fails loudly.

Queries run on ``serve/kg_engine.KGQueryEngine`` — one compiled top-k
computation per batch, query axis sharded over workers — with
``filtered=True`` excluding the graph's known neighbors (serve new links,
the filtered-ranking convention applied to serving).
"""
from __future__ import annotations

import dataclasses
import hashlib
from typing import Dict, Optional

import numpy as np

from repro.core import eval as kg_eval
from repro.core.models import KGModel, Params, get_model
from repro.data.kg import KG
from repro.serve.kg_engine import KGQueryEngine, QueryResult
from repro.train import checkpoint as ckpt_lib

ARTIFACT_KIND = "knowledge_base"


@dataclasses.dataclass
class KnowledgeBase:
    """A trained KG embedding as a first-class artifact (module docstring).

    ``graph`` is optional: without it the artifact still scores and serves
    raw top-k, but filtered queries and ``evaluate`` need the splits
    (``save(include_graph=True)`` keeps them with the tables)."""

    model: KGModel
    params: Params
    graph: Optional[KG] = None
    norm: str = "l1"
    meta: Dict = dataclasses.field(default_factory=dict)
    _engines: Dict = dataclasses.field(
        default_factory=dict, repr=False, compare=False)
    _fingerprint: Optional[str] = dataclasses.field(
        default=None, repr=False, compare=False)

    def __post_init__(self):
        self.model = get_model(self.model)
        missing = set(self.model.param_roles()) - set(self.params)
        if missing:
            raise ValueError(
                f"params are missing tables {sorted(missing)} for model "
                f"{self.model.name!r} (have {sorted(self.params)})")

    # -- identity ----------------------------------------------------------

    @property
    def n_entities(self) -> int:
        return int(self.params["ent"].shape[0])

    @property
    def n_relations(self) -> int:
        return int(self.params["rel"].shape[0])

    @property
    def dim(self) -> int:
        """The configured width ``k``, which is not every table's row
        width (RotatE's entity rows hold 2k floats)."""
        return self.model.width(self.params)

    def fingerprint(self) -> str:
        """Content identity of this artifact: a short sha256 over the model
        name, norm, every parameter table's bytes, and the graph's
        ``KG.fingerprint()`` digests.  Two artifacts answer every query
        identically iff their fingerprints match, which is exactly what an
        answer cache needs as a key — ``serve.KGServer`` keys its LRU on
        this and invalidates on a ``swap()`` that changes it.  Computed
        once and cached (tables and splits are immutable by repo
        convention)."""
        if self._fingerprint is None:
            h = hashlib.sha256()
            h.update(f"{self.model.name}:{self.norm}".encode())
            for name in sorted(self.params):
                arr = np.ascontiguousarray(np.asarray(self.params[name]))
                h.update(f":{name}:{arr.dtype}:{arr.shape}".encode())
                h.update(arr.tobytes())
            if self.graph is not None:
                for key, val in sorted(self.graph.fingerprint().items()):
                    h.update(f":{key}={val}".encode())
            self._fingerprint = h.hexdigest()[:16]
        return self._fingerprint

    # -- persistence -------------------------------------------------------

    def save(self, path: str, *, include_graph: bool = True,
             step: int = 0, keep: int = 3) -> str:
        """Persist atomically under ``path`` (checkpoint ``step_`` layout).
        Returns the committed directory.  The manifest records model name,
        per-table shapes, norm, and the graph fingerprint; the graph
        splits ship with the tables unless ``include_graph=False``."""
        tree = {"params": self.params}
        graph_fp = None
        if include_graph and self.graph is not None:
            tree["graph"] = {
                "train": np.asarray(self.graph.train, np.int32),
                "valid": np.asarray(self.graph.valid, np.int32),
                "test": np.asarray(self.graph.test, np.int32),
            }
        if self.graph is not None:
            graph_fp = self.graph.fingerprint()
        extra = {
            "kind": ARTIFACT_KIND,
            "model": self.model.name,
            "norm": self.norm,
            "dim": self.dim,
            "n_entities": (self.graph.n_entities if self.graph is not None
                           else self.n_entities),
            "n_relations": (self.graph.n_relations if self.graph is not None
                            else self.n_relations),
            "tables": {
                name: list(np.shape(arr))
                for name, arr in sorted(self.params.items())
            },
            "graph": graph_fp,
            "fingerprint": self.fingerprint(),
            "meta": self.meta,
        }
        return ckpt_lib.save(str(path), step, tree, extra=extra, keep=keep)

    @classmethod
    def load(cls, path: str, step: Optional[int] = None) -> "KnowledgeBase":
        """Restore a saved artifact.  Raises a clear error when the
        directory holds something else (e.g. a training checkpoint), the
        manifest names an unregistered model, a stored table's shape
        disagrees with the manifest, or the shipped graph fails its
        fingerprint."""
        _, tree, _, extra = ckpt_lib.restore(
            str(path), step=step, expect={"kind": ARTIFACT_KIND})
        model = get_model(extra["model"])
        params = tree["params"]
        for name, shape in (extra.get("tables") or {}).items():
            if name not in params:
                raise ValueError(
                    f"artifact at {path} is missing table {name!r} named "
                    "in its manifest — truncated or corrupted save?")
            if list(params[name].shape) != list(shape):
                raise ValueError(
                    f"artifact table {name!r} has shape "
                    f"{tuple(params[name].shape)} but the manifest records "
                    f"{tuple(shape)} — corrupted artifact?")
        graph = None
        if "graph" in (tree or {}):
            g = tree["graph"]
            graph = KG(int(extra["n_entities"]), int(extra["n_relations"]),
                       g["train"], g["valid"], g["test"])
            fp = extra.get("graph")
            if fp is not None and graph.fingerprint() != fp:
                raise ValueError(
                    f"graph splits stored at {path} do not match the "
                    "manifest fingerprint — corrupted artifact?")
        return cls(model=model, params=params, graph=graph,
                   norm=extra.get("norm", "l1"),
                   meta=extra.get("meta") or {})

    @classmethod
    def load_chain(cls, path: str) -> "KnowledgeBase":
        """Replay a delta chain: load the base artifact at the chain's
        first step, then apply each delta in order — allocate the grown
        tables, copy the surviving prefix, scatter the stored
        changed/appended rows, extend the graph with the delta triples.
        Every link is validated both ways: the delta's ``base``
        fingerprint must match the artifact built so far, and the rebuilt
        artifact must hash to the delta's ``result`` — a tampered or
        mis-ordered chain refuses instead of answering from wrong rows."""
        steps = ckpt_lib.chain_steps(str(path))
        if not steps:
            raise FileNotFoundError(f"no chain (or artifact) in {path}")
        kb = cls.load(path, step=steps[0])
        for step in steps[1:]:
            tree, extra = ckpt_lib.load_tree(str(path), step)
            if not extra.get("delta"):
                raise ValueError(
                    f"chain step {step} in {path} is not a delta — "
                    "multiple base artifacts in one directory?")
            if extra.get("base") != kb.fingerprint():
                raise ValueError(
                    f"delta step {step} applies to fingerprint "
                    f"{extra.get('base')} but the chain so far builds "
                    f"{kb.fingerprint()} — corrupted or reordered chain")
            params = {}
            for name, shape in (extra.get("tables") or {}).items():
                old = np.asarray(kb.params[name])
                table = np.zeros((int(shape[0]), int(shape[1])), old.dtype)
                table[:old.shape[0]] = old
                rows = (tree.get("rows") or {}).get(name)
                if rows is not None and len(np.atleast_1d(rows["idx"])):
                    table[np.asarray(rows["idx"], np.int64)] = np.asarray(
                        rows["vals"], old.dtype)
                params[name] = table
            graph = kb.graph
            if graph is not None:
                gt = (tree.get("graph") or {}).get("train")
                if gt is None:
                    gt = np.zeros((0, 3), np.int32)
                graph = graph.extend(
                    gt, n_entities=int(extra["n_entities"]),
                    n_relations=int(extra["n_relations"]))
            kb = cls(model=kb.model, params=params, graph=graph,
                     norm=extra.get("norm", kb.norm),
                     meta=extra.get("meta") or dict(kb.meta))
            if kb.fingerprint() != extra.get("result"):
                raise ValueError(
                    f"replaying delta step {step} in {path} produced "
                    f"fingerprint {kb.fingerprint()} but the manifest "
                    f"records {extra.get('result')} — corrupted chain")
        return kb

    # -- online updates ----------------------------------------------------

    def update(self, new_triples, **updater_kw) -> "KnowledgeBase":
        """Incrementally fold ``new_triples`` into this artifact and return
        a NEW KnowledgeBase (this one is immutable by repo convention).
        Grows the tables for unseen ids, warm-inits new rows, fine-tunes
        only the touched rows, and extends the graph — see
        ``repro.online.OnlineUpdater`` for the knobs (epochs, seed,
        delta_dir, vocab, ...)."""
        from repro.online import OnlineUpdater
        return OnlineUpdater(self, **updater_kw).update(new_triples)

    # -- serving -----------------------------------------------------------

    def engine(self, *, n_workers: int = 1, backend: str = "vmap",
               mesh=None, chunk: Optional[int] = None,
               table_sharding: str = "replicated") -> KGQueryEngine:
        """The device query engine over this artifact's tables; instances
        are cached per (n_workers, backend, chunk, mesh, table_sharding)
        so repeated queries reuse compiled computations.
        ``table_sharding="sharded"`` serves from the shard-local candidate
        scan (answers stay bitwise identical — see ``serve/kg_engine``)."""
        key = (n_workers, backend, chunk, id(mesh) if mesh is not None
               else None, table_sharding)
        if key not in self._engines:
            self._engines[key] = KGQueryEngine(
                self.model, self.params, norm=self.norm,
                n_workers=n_workers, backend=backend, mesh=mesh,
                chunk=chunk, table_sharding=table_sharding)
        return self._engines[key]

    def _exclude(self, a, b, side: str) -> np.ndarray:
        if self.graph is None:
            raise ValueError(
                "filtered=True needs the graph (known-neighbor masks); "
                "this KnowledgeBase was loaded without one — re-save with "
                "include_graph=True or pass filtered=False")
        pairs = np.stack(np.broadcast_arrays(
            np.atleast_1d(np.asarray(a, np.int64)),
            np.atleast_1d(np.asarray(b, np.int64))), axis=1)
        return self.graph.known_candidate_masks(pairs, side)

    def query_tails(self, heads, rels, k: int = 10,
                    filtered: bool = False, **engine_kw) -> QueryResult:
        """Top-k tail completions of ``(h, r, ?)``.  ``filtered=True``
        excludes the graph's already-known tails of each pair — serve
        *new* links, the filtered-ranking convention applied to traffic.
        ``engine_kw`` (n_workers / backend / mesh / chunk) picks the
        engine sharding."""
        exclude = self._exclude(heads, rels, "tail") if filtered else None
        return self.engine(**engine_kw).query_tails(
            heads, rels, k=k, exclude=exclude)

    def query_heads(self, tails, rels, k: int = 10,
                    filtered: bool = False, **engine_kw) -> QueryResult:
        """Top-k head completions of ``(?, r, t)`` (see query_tails)."""
        exclude = self._exclude(rels, tails, "head") if filtered else None
        return self.engine(**engine_kw).query_heads(
            tails, rels, k=k, exclude=exclude)

    def query_relations(self, heads, tails, k: int = 10,
                        **engine_kw) -> QueryResult:
        """Top-k relations linking ``(h, ?, t)``."""
        return self.engine(**engine_kw).query_relations(heads, tails, k=k)

    def score(self, heads, rels, tails, **engine_kw) -> np.ndarray:
        """Energies of fully-specified triplets (lower = more plausible)."""
        return self.engine(**engine_kw).score(heads, rels, tails)

    # -- evaluation --------------------------------------------------------

    def evaluate(self, *, filtered: bool = True, engine: str = "host",
                 **engine_kw) -> dict:
        """The paper's three-task protocol on this artifact's graph —
        exactly ``repro.kg.evaluate(kb)``."""
        if self.graph is None:
            raise ValueError(
                "evaluate needs the graph's valid/test splits; this "
                "KnowledgeBase was loaded without a graph")
        return kg_eval.evaluate_all(
            self.params, self.graph, norm=self.norm, filtered=filtered,
            model=self.model, engine=engine, **engine_kw)
