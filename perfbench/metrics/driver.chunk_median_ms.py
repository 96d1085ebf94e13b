"""driver layer: median over the window's chunks of the driver's own
``last_chunk_wall_s`` (dispatch + the one host sync) per step.
Source: program_span.  Moves: step_ms."""
import statistics


def read(ctx):
    vals = [1e3 * c["wall_s"] / c["steps"] for c in ctx["chunks"]
            if c.get("wall_s")]
    return statistics.median(vals) if vals else None
