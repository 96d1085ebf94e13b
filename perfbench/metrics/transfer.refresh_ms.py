"""transfer engine layer: device self time per step of the operations under
the ``ib/refresh`` phase of the compiled step (the half-step refresh of the
buckets, its fallback included).
Source: device_trace.  Moves: step_ms."""
from perfbench.obsread import phase


def read(ctx):
    return phase(ctx, "ib/refresh")
