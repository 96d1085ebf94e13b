"""compile layer: seconds of the program's ``compile/cache_read`` spans
(persistent-cache reads, one per jax.monitoring event) that ended before the
window, summed.
Source: program_span.  Moves: setup_s."""
from perfbench.obsread import span_sum_s


def read(ctx):
    return span_sum_s(ctx, "compile/cache_read", "setup")
