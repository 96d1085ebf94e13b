"""fluid solve layer: device self time per step of the operations under
the ``fluid/convect`` phase of the compiled step (the convective operator
and its AB2 extrapolation).  Source: device_trace.  Moves: step_ms."""
from perfbench.obsread import phase_ms


def read(ctx):
    got = phase_ms(ctx)
    # None too where the program is from before this phase (the parent)
    return None if got is None else got.get("fluid/convect")
