"""device layer: share of the traced steady chunks in which no operation
ran on the device.  Source: device_trace.  Moves: step_ms."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr or not tr.get("window_s"):
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
