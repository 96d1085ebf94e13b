"""compile layer: seconds of the program's ``compile/lower`` spans (a jaxpr
lowered to an MLIR module, one per jax.monitoring event) that ended before
the window, summed.
Source: program_span.  Moves: setup_s."""
from perfbench.obsread import spans


def read(ctx):
    got = spans(ctx, "compile/lower", "setup")
    return sum(s["t1"] - s["t0"] for s in got) if got else None
