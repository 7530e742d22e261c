"""num_batches: the batch count b of the plan timed by ``plan_s``, set by
the chip's free memory. Layer: planner."""


def read(ctx):
    return None if ctx.plan is None else ctx.plan.num_batches
