"""transfer engine layer: device self time per step of the operations under
the ``ib/interp`` phase of the compiled step (both velocity interpolations).
Source: device_trace.  Moves: step_ms."""
from perfbench.obsread import phase


def read(ctx):
    return phase(ctx, "ib/interp")
