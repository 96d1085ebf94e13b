"""transfer engine layer: device self time per step of the operations under
the ``ib/spread`` phase of the compiled step (the force spread to the grid).
Source: device_trace.  Moves: step_ms."""
from perfbench.obsread import phase


def read(ctx):
    return phase(ctx, "ib/spread")
