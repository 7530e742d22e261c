"""plan_s: host seconds of one ``plan_batches`` call with the cell's own
arguments (the symbolic pass on the device plus the host plan), timed in the
traced run before the profiler starts. Layer: planner."""


def read(ctx):
    return ctx.plan_s
