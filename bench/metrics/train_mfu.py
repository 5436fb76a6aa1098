"""Training's share of the chips' peak: the operations the triples trained
in the traced window require (forward and backward, with their
negatives), over the window times chips times the bf16 peak."""


def read(ctx):
    cell, s = ctx["cell"], ctx["summary"]
    if cell.mix["entry"] != "fit" or not cell.work.get("triples"):
        return None
    ops = ctx["flops"].train_ops_per_triple(
        cell.config["model"], cell.config["dim"]) * cell.work["triples"]
    return 100.0 * ops / (s.window_s * s.chips * ctx["peak"]["bf16_flops"])
