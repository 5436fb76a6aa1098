"""Share of the traced window in which a collective (all-reduce,
all-gather, ...) runs on a chip, the mean over the cell's chips: the
Reduce's transport between chips."""


def read(ctx):
    cell, s = ctx["cell"], ctx["summary"]
    if cell.mix["entry"] != "fit" or s.chips < 2 or not s.collective_s:
        return None
    return 100.0 * s.collective_s / s.window_s
