"""fused_step_roofline: the least time the chip could take for the fused
step's batches, over their device time, in %. A batch's least time is the
larger of its least bytes (``counting.ProductCounts.least_bytes``) at the
HBM peak and two operations per product at the bf16 peak; today the bytes
bound it. The k-th run of the step inside the traced window is the k-th
counted batch (calls dispatch their batches in order, and the warm-up ends
before the window opens). Layer: fused step."""

from bench import counting


def read(ctx):
    if ctx.trace is None or ctx.peaks is None:
        return None
    runs = ctx.trace.module_durations(ctx.fused_step)
    pairs = list(zip(runs, ctx.window.batches, ctx.checks))
    if not pairs:
        return None
    least = 0.0
    for _, batch, checked in pairs:
        cols = batch.columns
        t, _bound = counting.least_time_s(
            ctx.counts.products(cols),
            ctx.counts.least_bytes(cols, checked.c_nnz), ctx.peaks)
        least += t
    return 100.0 * least / sum(d for d, _, _ in pairs)
