"""fluid solve layer: device self time per step of the operations under the
``fluid/reproject`` phase of the compiled step: the second projection of a
ConstraintIB step (the imposed velocity's divergence, the Poisson solve's
diagonal divide, the masked gradient and the pinned faces) WITHOUT its axis
transforms, which are ``fluid/transforms`` like every other solve's and are
counted by ``fluid.transform_ms``; it is inside ``fluid.solve_ms`` and
``fluid.algebra_ms``.
Source: device_trace.  Moves: step_ms."""
from perfbench.obsread import phase_ms


def read(ctx):
    got = phase_ms(ctx)
    # None too where the program has no such phase (a parent of PR 34)
    return None if got is None else got.get("fluid/reproject")
