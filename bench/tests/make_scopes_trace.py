"""Record ``data/scopes.xplane.pb``, the trace ``tests/test_bench_scopes.py``
reads: on a TPU, one jitted scan whose body runs a matrix product under
the program's ``repro.map`` scope and a column-mean subtraction under
``repro.reduce`` (two kernels XLA does not fuse), called inside a
``repro.fit.block`` span, then a 5 ms host sleep inside a
``repro.fit.boundary`` span, all inside ``bench.window``.

    python bench/tests/make_scopes_trace.py <out.xplane.pb>
"""
from __future__ import annotations

import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

SHAPE = (8192, 1024)
STEPS = 8
SLEEP_S = 0.005


def step(x, w):
    import jax
    import jax.numpy as jnp

    from repro import obs

    def body(c, _):
        with obs.scope("map"):
            c = jnp.tanh(c @ w)
        with obs.scope("reduce"):
            c = c - jnp.mean(c, axis=0, keepdims=True)
        return c, None

    return jax.lax.scan(body, x, None, length=STEPS)[0]


def main(argv) -> int:
    import jax
    import jax.numpy as jnp

    from bench import trace as trace_lib
    from repro import obs

    if jax.devices()[0].platform != "tpu":
        print("make_scopes_trace: no TPU - nothing recorded",
              file=sys.stderr)
        return 3
    fn = jax.jit(step)
    x = jnp.ones(SHAPE, jnp.float32)
    w = jnp.eye(SHAPE[1], dtype=jnp.float32) * 0.5
    fn(x, w).block_until_ready()                   # compile outside
    tmp = tempfile.mkdtemp(prefix="scopes_trace_")
    try:
        with jax.profiler.trace(tmp):
            with jax.profiler.TraceAnnotation("bench.window"):
                with obs.span("fit.block"):
                    fn(x, w).block_until_ready()
                with obs.span("fit.boundary"):
                    time.sleep(SLEEP_S)
        shutil.copy(trace_lib.find_xplane(tmp), argv[0])
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    print(f"wrote {argv[0]}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
