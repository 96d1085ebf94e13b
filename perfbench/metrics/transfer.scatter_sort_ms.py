"""transfer engine layer: device self time of scatter, gather and sort
operations per step (bucket prep/refresh, overlap-add, force assembly,
overflow path).  Source: device_trace.  Moves: step_ms."""
from perfbench.readers import class_ms_per_step


def read(ctx):
    return class_ms_per_step(ctx, "scatter_sort")
