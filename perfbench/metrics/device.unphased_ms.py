"""device layer: device self time per step of the operations under the
``unphased`` phase of the compiled step (NO phase: the scan's loop, the
health flag, the marker and midpoint updates, the callbacks' device work).
Source: device_trace.  Moves: step_ms."""
from perfbench.obsread import phase


def read(ctx):
    return phase(ctx, "unphased")
