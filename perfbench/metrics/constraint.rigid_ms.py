"""constraint strategy layer: device self time per step of the operations
under the ``constraint/rigid`` phase of the compiled step (the least-squares
rigid projection of the interpolated marker velocities, the excess-inertia
update with gravity, and the rigid marker velocity).
Source: device_trace.  Moves: step_ms."""
from perfbench.obsread import phase_ms


def read(ctx):
    got = phase_ms(ctx)
    # None too where the program has no such phase (a parent of PR 34)
    return None if got is None else got.get("constraint/rigid")
