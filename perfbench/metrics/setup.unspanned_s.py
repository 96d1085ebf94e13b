"""compile layer: seconds of ``setup_s`` that no span of the program
covers (imports, ``main.py``'s glue, the benchmark's seeding): set-up less
the union of every span inside it.
Source: program_span.  Moves: setup_s."""
from perfbench import intervals


def read(ctx):
    spans, iv = intervals.ring(), intervals.setup(ctx)
    if spans is None or iv is None:
        return None
    got = intervals.inside(spans, iv)
    if not intervals.inside(got, iv, "setup/build"):
        return None             # a program without the set-up's own spans
    return ctx["setup_s"] - intervals.covered_s(got, iv)
