"""``rank_topk``'s share of its roofline: the least time the chip could
take for the ranking work of the traced passes (2 x test triples x
entities x width, from the problem's shapes) over the kernel's summed
device time.  The work is compute-bound at these shapes; the L1 scan runs
on the vector unit, so against the bf16 matrix peak the share is small.

The kernel is found by its name where the trace carries it, else as the
Pallas custom call (``tpu_custom_call``): ``rank_counts`` is the only
Pallas kernel on the evaluation path."""

KERNEL = r"rank_counts|rank_topk|custom_call_target=\"tpu_custom_call\""


def read(ctx):
    cell, s = ctx["cell"], ctx["summary"]
    if cell.mix["entry"] != "evaluate" or not cell.work.get("test_triples"):
        return None
    secs = s.op_seconds(KERNEL)
    if not secs:
        return None
    g = cell.config["graph"]
    ops, nbytes = ctx["flops"].rank_topk_work(
        cell.config["dim"], 2 * cell.work["test_triples"], g["n_entities"])
    share, _ = ctx["flops"].roofline_share(
        ops, nbytes, secs, ctx["peak"]["bf16_flops"],
        ctx["peak"]["hbm_bytes_per_s"])
    return share
