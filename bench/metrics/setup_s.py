"""setup_s: process start until the window opens (host clock): imports,
operand generation, scatter, the budget, the warm-up call and, in a run
that compiles, compilation."""


def read(ctx):
    return ctx.setup_s
