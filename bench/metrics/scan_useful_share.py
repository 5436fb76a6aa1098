"""Share of the (query, entity, column) cells the fused rank kernel scans
in an evaluation pass that belong to the problem rather than to the
kernel's padding of rows, entities and columns: 100 x
``eval.scan_useful_cells`` / ``eval.scan_cells``, the program's own
counters (``repro.obs.counters()``, counted from shapes once a pass).
None where the program has no such counters."""


def read(ctx):
    if ctx["cell"].mix["entry"] != "evaluate":
        return None
    try:
        from repro import obs
    except ImportError:
        return None
    c = obs.counters()
    if not c.get("eval.scan_cells"):
        return None
    return 100.0 * c["eval.scan_useful_cells"] / c["eval.scan_cells"]
