"""Evaluation's share of the chip's peak: the operations of the three
tasks over the test triples of the traced passes (both entity scans, the
relation scan, triple classification), over the window times chips times
the bf16 peak."""


def read(ctx):
    cell, s = ctx["cell"], ctx["summary"]
    if cell.mix["entry"] != "evaluate" or not cell.work.get("test_triples"):
        return None
    g = cell.config["graph"]
    per = ctx["flops"].eval_ops_per_test_triple(
        cell.config["model"], cell.config["dim"], g["n_entities"],
        g["n_relations"], g["n_valid"], g["n_test"])
    ops = per * cell.work["test_triples"]
    return 100.0 * ops / (s.window_s * s.chips * ctx["peak"]["bf16_flops"])
