"""The complex-modulus rank kernel's share of its roofline: the least time
the chip could take for the scan work of the traced passes (2 x test
triples x entities x (7k + 2) operations, and the bytes, from the
problem's shapes by the model file's ``scan_work``) over the kernel's
summed device time.

The kernel is found by its name, ``modulus_rank_counts``, where the trace
carries it, else as the Pallas custom call (``tpu_custom_call``): it is
the only Pallas kernel on the path of a model whose file has
``scan_work``.  None for a model file without it."""

KERNEL = r"modulus_rank_counts|custom_call_target=\"tpu_custom_call\""


def read(ctx):
    from bench import harness

    cell, s = ctx["cell"], ctx["summary"]
    if cell.mix["entry"] != "evaluate" or not cell.work.get("test_triples"):
        return None
    work = getattr(harness.model(cell.config["model"]), "scan_work", None)
    secs = s.op_seconds(KERNEL)
    if work is None or not secs:
        return None
    g = cell.config["graph"]
    ops, nbytes = work(cell.config["dim"], 2 * cell.work["test_triples"],
                       g["n_entities"])
    share, _ = ctx["flops"].roofline_share(
        ops, nbytes, secs, ctx["peak"]["bf16_flops"],
        ctx["peak"]["hbm_bytes_per_s"])
    return share
