"""compile layer: seconds of the program's ``compile/backend`` spans (backend
compiles, one per jax.monitoring event) that ended before the window,
summed.
Source: program_span.  Moves: setup_s."""
from perfbench.obsread import span_sum_s


def read(ctx):
    return span_sum_s(ctx, "compile/backend", "setup")
