"""driver layer: seconds of ``recover_s`` that no span of the program
covers: recovery less the union of every span inside it.
Source: program_span.  Moves: recover_s."""
from perfbench import intervals


def read(ctx):
    spans = intervals.ring()
    iv = spans and intervals.recovery(ctx, spans)
    if not iv:
        return None
    got = intervals.inside(spans, iv)
    if not intervals.inside(got, iv, "compile/trace"):
        return None             # a program without the trace spans
    return ctx["recover"]["recover_s"] - intervals.covered_s(got, iv)
