"""transfer engine layer: device self time of dot/convolution operations
per step (only the packed transfers contract).  Source: device_trace.
Moves: step_ms."""
from perfbench.readers import class_ms_per_step


def read(ctx):
    return class_ms_per_step(ctx, "dot")
