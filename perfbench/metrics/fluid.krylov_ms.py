"""fluid solve layer: device self time per step of the operations under the
``fluid/krylov`` phase of the compiled step and its three sub-phases
(``operator``, ``precond``, ``arnoldi``): the whole coupled saddle solve by
FGMRES.  Source: device_trace.  Moves: step_ms."""
from perfbench.obsread import phase_ms


def read(ctx):
    got = phase_ms(ctx)
    # None where the program has no such phase (one from before it)
    if got is None or "fluid/krylov" not in got:
        return None
    return sum(v for k, v in got.items()
               if k == "fluid/krylov" or k.startswith("fluid/krylov/"))
