"""run-loop callbacks layer: wall time inside the window under the program's
``driver/viz_fn`` spans (the marker dump), per step.
Source: program_span.  Moves: step_ms."""
from perfbench.obsread import span_ms_per_step


def read(ctx):
    return span_ms_per_step(ctx, "driver/viz_fn")
