"""step_device_s: mean device seconds of one run of the fused batch step's
executable (select, gathers, local multiply, merge) over the batches the
traced window counted: the k-th run of the step inside the window made the
k-th counted batch (calls dispatch their batches in order, and the warm-up
ends before the window opens); runs still going when the window closed are
left out. Layer: fused step."""


def read(ctx):
    if ctx.trace is None:
        return None
    runs = ctx.trace.module_durations(ctx.fused_step)[:len(ctx.window.batches)]
    return sum(runs) / len(runs) if runs else None
