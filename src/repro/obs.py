"""Names for the profiler, and a few process-wide counters.

Three kinds of instrumentation, all named ``repro.<name>``:

  * ``scope(name)`` — ``jax.named_scope``: the ops traced inside it carry
    ``repro.<name>`` in their ``op_name`` metadata.  Compile-time only: the
    compiled program differs in metadata and nothing else.
  * ``span(name)`` — ``jax.profiler.TraceAnnotation``: a host span on the
    profiler's own clock (about a microsecond when no profiler runs).  Used
    per block, per pass or per call, never inside a per-step loop.
  * ``count(name, n)`` — adds ``n`` to a process-wide integer, read with
    ``counters()`` and cleared with ``reset()``.  Each counter is counted
    where its data is built, never on a hot path.

Read the spans and scopes with the JAX profiler (TensorBoard or xprof), the
counters with ``repro.obs.counters()``; ``docs/architecture.md`` lists them.
There is no exporter, flag or environment variable.
"""
from __future__ import annotations

import contextlib
import threading

import jax

PREFIX = "repro."

_lock = threading.Lock()
_counts: dict = {}


@contextlib.contextmanager
def scope(name: str):
    """``jax.named_scope("repro." + name)``: op metadata only.  Also a
    decorator: each call of the decorated function enters it anew."""
    with jax.named_scope(PREFIX + name):
        yield


def span(name: str):
    """``jax.profiler.TraceAnnotation("repro." + name)``: a host span."""
    return jax.profiler.TraceAnnotation(PREFIX + name)


def count(name: str, n: int) -> None:
    """Add ``n`` to counter ``name``."""
    with _lock:
        _counts[name] = _counts.get(name, 0) + int(n)


def counters() -> dict:
    """A copy of every counter counted since the last ``reset()``."""
    with _lock:
        return dict(_counts)


def reset() -> None:
    with _lock:
        _counts.clear()
