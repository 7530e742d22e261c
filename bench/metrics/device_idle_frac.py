"""device_idle_frac: 1 − (union of the device's busy intervals / traced
window), from the profiler trace. Layer: device."""


def read(ctx):
    return None if ctx.trace is None else ctx.trace.idle_frac
