"""Device-busy milliseconds per executed train-step program in the traced
stretch: union of the device-op intervals over the executions of the program
that took most device time.  Layer: sparse step."""


def read(ctx):
    s = ctx["summary"]
    if s is None or not s.steps:
        return None
    return 1e3 * s.busy_s / s.steps
