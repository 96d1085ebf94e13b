"""checkpoint layer: median seconds of the program's ``checkpoint/fetch`` span
(the device-to-host copy) over the writes inside the window.
Source: program_span.  Moves: step_ms."""
from perfbench.obsread import span_median_s


def read(ctx):
    return span_median_s(ctx, "checkpoint/fetch")
