"""fluid solve layer: device self time of dot/convolution operations per
step.  In a fluid-only program every one is an axis product of the
fast-diagonalization solves, so ``fluid.transform_ms`` less this is what the
layout copies and the pad / slice around the products cost.
Source: device_trace.  Moves: step_ms."""
from perfbench.readers import class_ms_per_step


def read(ctx):
    return class_ms_per_step(ctx, "dot")
