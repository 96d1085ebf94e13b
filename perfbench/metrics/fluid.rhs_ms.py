"""fluid solve layer: device self time per step of the operations under
the ``fluid/rhs`` phase of the compiled step (the velocity Laplacian, the
pressure gradient and the assembly of the Helmholtz right-hand side).
Source: device_trace.  Moves: step_ms."""
from perfbench.obsread import phase_ms


def read(ctx):
    got = phase_ms(ctx)
    # None too where the program is from before this phase (the parent)
    return None if got is None else got.get("fluid/rhs")
