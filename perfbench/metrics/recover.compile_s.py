"""compile layer: seconds of the recovery under the program's
``compile/trace``, ``compile/lower`` and ``compile/backend`` spans (the new
driver's programs traced, lowered, and read from the cache), their union.
Source: program_span.  Moves: recover_s."""
from perfbench import intervals


def read(ctx):
    spans = intervals.ring()
    iv = spans and intervals.recovery(ctx, spans)
    if not iv:
        return None
    got = intervals.inside(spans, iv, "compile/trace", "compile/lower",
                           "compile/backend")
    if not intervals.inside(got, iv, "compile/trace"):
        return None             # a program without the trace spans
    return intervals.covered_s(got, iv)
