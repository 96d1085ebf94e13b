"""run-loop callbacks layer: wall time inside the window under the program's
``driver/metrics_fn`` spans (after every chunk), per step.
Source: program_span.  Moves: step_ms."""
from perfbench.obsread import span_ms_per_step


def read(ctx):
    return span_ms_per_step(ctx, "driver/metrics_fn")
