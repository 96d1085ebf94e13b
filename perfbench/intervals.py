"""What the readers of set-up and recovery share: the two intervals they
account for, found from ``ctx`` and the program's span ring alone, and
the wall time that a set of spans covers inside one of them.

Set-up is [window start - ``setup_s``, window start]; recovery is [start
of the last ``checkpoint/restore`` span, that + ``recover_s``].  A span
counts with the part of it that lies inside; overlapping spans (a child
inside its parent, a compile nested in a trace) count once.
"""

from __future__ import annotations


def ring():
    """The program's closed spans; None where it keeps none."""
    try:
        from ibamr_tpu import obs

        return obs.spans()
    except (ImportError, AttributeError):
        return None


def named(s, path: str) -> bool:
    return s["path"] == path or s["path"].endswith("/" + path)


def setup(ctx):
    ch = ctx.get("chunks")
    if not ch:
        return None
    w0 = ch[0]["t_start"]
    return w0 - ctx["setup_s"], w0


def recovery(ctx, spans):
    rec = ctx.get("recover")
    restores = [s for s in spans if named(s, "checkpoint/restore")]
    if not rec or not restores:
        return None
    t0 = restores[-1]["t0"]
    return t0, t0 + rec["recover_s"]


def inside(spans, iv, *paths):
    """The spans (of ``paths``, or all) that overlap the interval ``iv``."""
    return [s for s in spans if s["t1"] > iv[0] and s["t0"] < iv[1]
            and (not paths or any(named(s, p) for p in paths))]


def covered_s(spans, iv) -> float:
    """Seconds of ``iv`` under at least one of ``spans``."""
    total, end = 0.0, iv[0]
    for t0, t1 in sorted((max(s["t0"], iv[0]), min(s["t1"], iv[1]))
                         for s in spans):
        if t1 > end:
            total += t1 - max(t0, end)
            end = t1
    return total
