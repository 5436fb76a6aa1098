"""Seconds XLA spent compiling, or reading compiled programs from the
persistent cache, during set-up (JAX's own monitoring events)."""


def read(ctx):
    return ctx["cell"].counters.get("setup_compile_s")
