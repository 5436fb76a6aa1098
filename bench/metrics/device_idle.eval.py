"""Share of the traced window in which no operation ran on the chip (the
mean over the cell's chips), in the cells driven by ``evaluate``."""


def read(ctx):
    cell, s = ctx["cell"], ctx["summary"]
    if cell.mix["entry"] != "evaluate":
        return None
    return 100.0 * (1.0 - s.busy_s / s.window_s)
