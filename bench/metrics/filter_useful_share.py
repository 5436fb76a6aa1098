"""Share of the padded filter cells an evaluation pass scores that hold a
real known candidate: 100 x ``eval.filter_known`` / ``eval.filter_cells``,
the program's own counters (``repro.obs.counters()``; each pass adds the
counts of the masks ``KG.eval_filter_candidates`` built once).  None where
the program has no such counters."""


def read(ctx):
    if ctx["cell"].mix["entry"] != "evaluate":
        return None
    try:
        from repro import obs
    except ImportError:
        return None
    c = obs.counters()
    if not c.get("eval.filter_cells"):
        return None
    return 100.0 * c["eval.filter_known"] / c["eval.filter_cells"]
