"""fluid solve layer: device self time per step of the operations under
the ``fluid`` phase of the compiled step (the whole fluid solve).
Source: device_trace.  Moves: step_ms."""
from perfbench.obsread import phase


def read(ctx):
    return phase(ctx, "fluid")
