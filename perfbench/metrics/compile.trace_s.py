"""compile layer: seconds of the program's ``compile/trace`` spans (a
function traced into a jaxpr: the outermost trace of a thread, with every
trace nested in it) that ended before the window, summed.
Source: program_span.  Moves: setup_s."""
from perfbench.obsread import spans


def read(ctx):
    got = spans(ctx, "compile/trace", "setup")
    return sum(s["t1"] - s["t0"] for s in got) if got else None
