"""What the per-layer metric readers share.  A reader is a module under
``metrics/`` named after its metric with one function ``read(ctx)``; it
returns None where it finds nothing to read, and the harness then leaves the
metric out of the result line."""

from __future__ import annotations


def class_ms_per_step(ctx, op_class: str):
    """Device self time of one operation class in the traced chunks, in
    milliseconds per step; None without a trace or without such operations."""
    tr = ctx.get("trace")
    if not tr or not tr.get("steps"):
        return None
    secs = tr["op_class_s"].get(op_class)
    if not secs:
        return None
    return 1e3 * secs / tr["steps"]


def peaks_of(ctx) -> dict:
    """The chip's published peaks; a device kind that is not in the table is
    an error, not a default."""
    kind = ctx["device"]["kind"]
    table = ctx["peaks"]["device_kinds"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in perfbench/peaks.json")
    return table[kind]


def in_window(ctx, spied_key: str):
    """Durations of the spied calls that started inside the window."""
    if not ctx["chunks"]:
        return []
    t0, t1 = ctx["chunks"][0]["t_start"], ctx["chunks"][-1]["t_end"]
    return [d for t, d in ctx["spied"].get(spied_key, []) if t0 <= t <= t1]
