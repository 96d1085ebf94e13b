"""fluid solve layer: device self time per step of the operations under the
``fluid/krylov/arnoldi`` phase: the FGMRES basis' classical Gram-Schmidt
(two rounds of dot products and updates over the basis), the norms and the
basis writes.  Source: device_trace.  Moves: step_ms."""
from perfbench.obsread import phase_ms


def read(ctx):
    got = phase_ms(ctx)
    return None if got is None else got.get("fluid/krylov/arnoldi")
