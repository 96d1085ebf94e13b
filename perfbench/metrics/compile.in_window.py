"""compile layer: number of backend compiles (``compile/backend`` spans) that
started inside the window; a warmed-up run has none.
Source: program_span.  Moves: step_ms."""
from perfbench.obsread import spans


def read(ctx):
    got = spans(ctx, "compile/backend")
    return None if got is None else len(got)
