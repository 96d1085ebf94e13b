"""constraint strategy layer: device self time per step of the operations
under the ``constraint/impose`` phase of the compiled step (the spread
correction's normalisation by the spread indicator on the three face grids,
and its addition to the velocity; the two spreads themselves are
``transfer.spread_ms``).
Source: device_trace.  Moves: step_ms."""
from perfbench.obsread import phase_ms


def read(ctx):
    got = phase_ms(ctx)
    # None too where the program has no such phase (a parent of PR 34)
    return None if got is None else got.get("constraint/impose")
