"""driver layer: 90th percentile over ALL chunks of the window of (start
of a chunk to the start of the next, callbacks included) per step; None
under ten chunks.  Source: host_clock.  Moves: step_ms."""
import statistics


def read(ctx):
    vals = [1e3 * (c["t_end"] - c["t_start"]) / c["steps"]
            for c in ctx["chunks"]]
    return statistics.quantiles(vals, n=10)[-1] if len(vals) >= 10 else None
