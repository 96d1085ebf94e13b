"""transfer engine layer: device self time per step of the operations under the
``ib/prep`` phase of the compiled step (the bucket prep at X_n, and the re-
prep at X_half where an engine has no refresh).
Source: device_trace.  Moves: step_ms."""
from perfbench.obsread import phase


def read(ctx):
    return phase(ctx, "ib/prep")
