"""peak_hbm_frac: the device's peak bytes in use over its bytes limit,
read after the window. Layer: device."""


def read(ctx):
    if ctx.memory is None:
        return None
    return ctx.memory["peak_bytes_in_use"] / ctx.memory["bytes_limit"]
