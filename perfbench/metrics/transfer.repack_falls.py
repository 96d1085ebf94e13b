"""transfer engine layer: refreshes of the carried marker layout that fell
back to a full re-pack inside the window: ``falls`` summed over the program's
``driver/chunk/refresh`` spans (one a chunk, closed after the chunk's sync;
0, not absent, when every refresh hit).
Source: program_span.  Moves: step_ms."""
from perfbench.obsread import spans


def read(ctx):
    got = spans(ctx, "driver/chunk/refresh")
    # a program whose chunks carry no layout closes no such span
    return sum(s["attrs"]["falls"] for s in got) if got else None
