"""String-keyed registry of pluggable KG scoring models.

The MapReduce engine (``core/mapreduce.py``), eval protocol
(``core/eval.py``), kernel dispatch (``kernels/ops.py``) and the
``repro.kg`` facade all resolve models through here:

    from repro.core.models import get_model
    model = get_model("distmult")

Adding a model: subclass ``KGModel`` (see base.py for the interface), give
it a unique ``name``, and ``register()`` an instance — every engine
paradigm, backend, merge strategy, and eval task picks it up for free.
"""
from __future__ import annotations

from typing import Dict, Tuple

from repro.core.models.base import (  # noqa: F401  (re-exported API)
    EpochStats,
    KGConfig,
    KGModel,
    Params,
    apply_gradients,
    dissimilarity,
    pairwise_hinge,
)
from repro.core.models.distmult import DistMult
from repro.core.models.rotate import RotatE
from repro.core.models.transe import TransE
from repro.core.models.transh import TransH

_REGISTRY: Dict[str, KGModel] = {}


def register(model: KGModel) -> KGModel:
    """Register a model instance under its ``name`` (last write wins)."""
    if not isinstance(model, KGModel):
        raise TypeError(f"expected a KGModel instance, got {type(model)!r}")
    _REGISTRY[model.name] = model
    return model


def get_model(name_or_model: "str | KGModel") -> KGModel:
    """Resolve a registry name (or pass a model instance through)."""
    if isinstance(name_or_model, KGModel):
        return name_or_model
    model = _REGISTRY.get(name_or_model)
    if model is None:
        raise ValueError(
            f"unknown KG model {name_or_model!r}; registered: {available()}"
        )
    return model


def available() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


register(TransE())
register(TransH())
register(DistMult())
register(RotatE())
