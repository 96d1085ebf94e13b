"""What the readers of the program's own tracing share (PR 25): device
time by the phase names inside the compiled step, and the host spans of
the run loop (``ibamr_tpu.obs``).

A program that has no such names or spans (the parent of PR 25: no
``obs.programs`` / ``obs.spans``) reads as None, and the harness then
leaves the metric out.  A program that HAS the registry and whose
compiled text carries no phase at all raises: that is a fault (a stale
compile-cache entry, a scope lost), not an absence.
"""

from __future__ import annotations

import re
import statistics
import time

from perfbench.harness import log

_LABEL = re.compile(r"^(\S+) \[[^:\]]*(?::(.*))?\]$")


def _window(ctx):
    ch = ctx.get("chunks")
    return (ch[0]["t_start"], ch[-1]["t_end"]) if ch else None


# -- device phases -----------------------------------------------------------

def phase_ms(ctx):
    """``{phase: ms/step}`` of the traced chunks: device SELF time under
    each phase of ``deviceprof.PHASES`` (a nested phase also counts in
    the one it is nested in), ``"unphased"`` for operations under none,
    and ``"busy"`` for all of it.  Read once per run, kept on ``ctx``.

    Per-instruction self time is ``ctx["trace"]["device_ops"]`` (labels
    ``"<instruction> [<class>:<primitive>]"``); the instruction -> phase
    map comes from the compiled text of the chunk programs the window's
    driver called (``obs.programs()``, in the driver's order, the later
    one wins where two use one instruction name: the harness's rule for
    its labels).  Data movement the compiler placed takes the phase of
    the value it moves (``deviceprof.names_from_hlo``).  An operation
    that is in no chunk program, or whose last ``op_name`` component is
    not the label's, is unphased."""
    if "_phase_ms" in ctx:
        return ctx["_phase_ms"]
    ctx["_phase_ms"] = out = _phase_ms(ctx)
    return out


def _phase_ms(ctx):
    tr, win = ctx.get("trace"), _window(ctx)
    if not tr or not tr.get("steps") or not tr.get("device_ops") or not win:
        return None
    try:
        from ibamr_tpu import obs
        from ibamr_tpu.obs import deviceprof

        progs = [p for p in obs.programs() if p["t"] <= win[1]]
    except (ImportError, AttributeError):
        return None             # a program without the registry
    t0 = time.perf_counter()
    op_names, phases = deviceprof.programs_names(progs)
    log(f"phases: {len(phases)} of {len(op_names)} instructions of "
        f"{[p['name'] for p in progs]} carry one; text read in "
        f"{time.perf_counter() - t0:.1f} s")
    if not phases:
        raise RuntimeError(
            "device operations were traced and no instruction of "
            f"{[p['name'] for p in progs]} carries a phase: a compile-"
            "cache entry older than the scopes, or the scopes are gone")
    top = {"/".join(seq): seq[0] for seq in deviceprof.PHASES}
    secs = dict.fromkeys(top, 0.0)
    secs["unphased"] = secs["busy"] = 0.0
    for label, s in tr["device_ops"]:
        secs["busy"] += s
        m = _LABEL.match(label)
        inst, prim = (m.group(1), m.group(2) or "") if m else (label, "")
        phase = phases.get(inst)
        # a label with a primitive must be this program's instruction;
        # one without has no metadata, and its phase is inherited
        if phase is None or (prim and prim != op_names.get(
                inst, "").rsplit("/", 1)[-1]):
            secs["unphased"] += s
            continue
        secs[phase] += s
        if top[phase] != phase:
            secs[top[phase]] += s
    return {k: 1e3 * v / tr["steps"] for k, v in secs.items()}


def phase(ctx, name: str):
    got = phase_ms(ctx)
    return None if got is None else got[name]


# -- host spans --------------------------------------------------------------

def spans(ctx, path: str, when: str = "window"):
    """The program's closed spans whose path is, or ends in, ``path``:
    those that started inside the window (``when="window"``) or ended
    before it (``"setup"``).  None where the program keeps no spans."""
    win = _window(ctx)
    try:
        from ibamr_tpu import obs

        ring = obs.spans()
    except (ImportError, AttributeError):
        return None
    if win is None:
        return None
    keep = {"window": lambda s: win[0] <= s["t0"] <= win[1],
            "setup": lambda s: s["t1"] <= win[0]}[when]
    return [s for s in ring if keep(s) and (
        s["path"] == path or s["path"].endswith("/" + path))]


def span_ms_per_step(ctx, path: str):
    """Wall time under ``path`` inside the window, per step of it."""
    got = spans(ctx, path)
    if got is None or not ctx.get("steps"):
        return None
    return 1e3 * sum(s["t1"] - s["t0"] for s in got) / ctx["steps"]


def span_median_s(ctx, path: str):
    got = spans(ctx, path)
    return statistics.median(s["t1"] - s["t0"] for s in got) if got else None


def span_sum_s(ctx, path: str, when: str):
    got = spans(ctx, path, when)
    return None if got is None else sum(s["t1"] - s["t0"] for s in got)
