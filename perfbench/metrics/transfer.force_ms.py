"""transfer engine layer: device self time per step of the operations under the
``ib/force`` phase of the compiled step (the Lagrangian force at the half
step).
Source: device_trace.  Moves: step_ms."""
from perfbench.obsread import phase


def read(ctx):
    return phase(ctx, "ib/force")
