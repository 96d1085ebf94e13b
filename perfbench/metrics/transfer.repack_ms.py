"""transfer engine layer: device self time per step of the operations under the
``ib/refresh/repack`` phase of the compiled step (the refresh's full re-pack
branch; 0.0, not absent, when the refresh always hit).
Source: device_trace.  Moves: step_ms."""
from perfbench.obsread import phase


def read(ctx):
    return phase(ctx, "ib/refresh/repack")
