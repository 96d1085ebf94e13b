"""run-loop callbacks layer: wall time inside the window under the program's
``driver/checkpoint_fn`` spans (the checkpoint), per step.
Source: program_span.  Moves: step_ms."""
from perfbench.obsread import span_ms_per_step


def read(ctx):
    return span_ms_per_step(ctx, "driver/checkpoint_fn")
