"""fluid solve layer: device self time per step of the operations under the
``fluid/krylov/precond`` phase: the saddle solve's preconditioner (red-black
sweeps of the velocity, the Schur proxy's multigrid V-cycle of the pressure).
Source: device_trace.  Moves: step_ms."""
from perfbench.obsread import phase_ms


def read(ctx):
    got = phase_ms(ctx)
    return None if got is None else got.get("fluid/krylov/precond")
