"""fluid solve layer: Arnoldi iterations of the saddle solves per step of
the window: ``iters`` summed over the program's ``driver/chunk/krylov`` spans
(one a chunk, closed after the chunk's sync) over the window's steps.
Source: program_span.  Moves: step_ms."""
from perfbench.obsread import spans


def read(ctx):
    got = spans(ctx, "driver/chunk/krylov")
    # a program whose chunks report no solve closes no such span
    if not got or not ctx.get("steps"):
        return None
    return sum(s["attrs"]["iters"] for s in got) / ctx["steps"]
