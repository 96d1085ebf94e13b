"""fluid solve layer: device self time of fft operations per step.
Source: device_trace.  Moves: step_ms."""
from perfbench.readers import class_ms_per_step


def read(ctx):
    return class_ms_per_step(ctx, "fft")
