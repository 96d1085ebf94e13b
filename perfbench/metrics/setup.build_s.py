"""compile layer: seconds of the program's ``setup/build`` span before the
window: the builder's construction of the integrator and its state (input
to grid, markers, engine, plans).
Source: program_span.  Moves: setup_s."""
from perfbench.obsread import spans


def read(ctx):
    got = spans(ctx, "setup/build", "setup")
    return sum(s["t1"] - s["t0"] for s in got) if got else None
