"""Requests per wave the server's batcher formed in the traced window
(its own counters, ``KGServer.stats()``)."""


def read(ctx):
    c = ctx["cell"].counters
    if not c.get("serve_waves"):
        return None
    return c["serve_wave_rows"] / c["serve_waves"]
