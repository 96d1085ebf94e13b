"""run-loop callbacks layer: share of the window's wall time spent outside
the driver's chunk spans (metrics_fn, viz_fn, checkpoint_fn and the loop's
own bookkeeping).  Source: program_span.  Moves: step_ms."""


def read(ctx):
    inside = sum(c["wall_s"] for c in ctx["chunks"] if c.get("wall_s"))
    if not ctx["chunks"] or not inside:
        return None
    return 100.0 * (ctx["window_s"] - inside) / ctx["window_s"]
