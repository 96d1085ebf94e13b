"""checkpoint layer: median seconds of one ``save_checkpoint`` inside the
window (the benchmark's spy).  Source: host_clock.  Moves: step_ms."""
import statistics

from perfbench.readers import in_window


def read(ctx):
    vals = in_window(ctx, "save")
    return statistics.median(vals) if vals else None
