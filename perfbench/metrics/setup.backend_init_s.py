"""compile layer: seconds of the program's ``setup/backend_init`` span, the
first ``jax.devices()`` (the process reaching the chip).
Source: program_span.  Moves: setup_s."""
from perfbench.obsread import span_sum_s


def read(ctx):
    return span_sum_s(ctx, "setup/backend_init", "setup") or None
