"""fluid solve layer: device self time per step of the operations under
the ``fluid/transforms`` phase of the compiled step (the forward and inverse
transforms of the fluid solve, whatever implements them).
Source: device_trace.  Moves: step_ms."""
from perfbench.obsread import phase


def read(ctx):
    return phase(ctx, "fluid/transforms")
