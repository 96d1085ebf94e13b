"""fluid solve layer: device self time per step under the ``fluid`` phase
and under none of ``fluid/convect``, ``fluid/rhs``, ``fluid/transforms``:
the fused substep's k-space algebra and whatever else the solve does (the
four add up to ``fluid.solve_ms``).  Source: device_trace.  Moves: step_ms."""
from perfbench.obsread import phase_ms


def read(ctx):
    got = phase_ms(ctx)
    if got is None or "fluid/convect" not in got:
        return None         # a program from before the two phases
    return got["fluid"] - got["fluid/convect"] - got["fluid/rhs"] \
        - got["fluid/transforms"]
