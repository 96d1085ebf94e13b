"""transfer engine layer: device self time per step of the operations under the
``ib/prep`` phase of the compiled chunk (since PR 26 the one pack per chunk
before the scan; one prep a step, and the re-prep at X_half, where an engine
has no refresh or the chunk does not carry the layout).
Source: device_trace.  Moves: step_ms."""
from perfbench.obsread import phase


def read(ctx):
    return phase(ctx, "ib/prep")
