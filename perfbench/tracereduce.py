"""From a profiler trace to the benchmark's device numbers.

The benchmark's own reduction (a copy in kind of ``obs/deviceprof.py``, not
an import): ``.xplane.pb`` is read through ``jax.profiler.ProfileData`` into
a plain dict (``load_xplane``), and everything else works on that dict, so
the small recorded trace beside the tests is such a dict too.

What it computes, on the busiest device plane:

- ``busy_s``: the union of the intervals in which an operation ran;
  ``window_s``: first operation's start to the last one's end, widened to
  the traced window's host annotations where they are there;
- ``op_class_s``: SELF time by class.  Operations nest (a ``while`` spans its
  body's operations), so an event's self time is its duration less the
  events inside it; the classes' self times add up to ``busy_s`` exactly;
- ``device_ops``: self time by operation name, largest first;
- ``idle_gaps``: the gaps between operations, longest first, each named by
  the benchmark's host annotation (``bench/...``) that covers most of it.
"""

from __future__ import annotations

import glob
import json
import os
import re

# Class of an operation.  The chip's trace names an operation by its HLO
# instruction (``%fusion.3518``), which says nothing of what it computes, so
# the class comes from the instruction's ``op_name`` in the compiled
# program's text (the JAX primitive path, ``.../jit(fft)/fft``) where the
# harness hands that in, and from the instruction's own name otherwise.
# First match wins; what matches nothing is "other" and is reported, never
# dropped.
OP_CLASSES = (
    ("fft", re.compile(r"\bfft\b", re.I)),
    ("scatter_sort", re.compile(
        r"scatter|gather|sort|_take\b|segment_sum|searchsorted|cumsum"
        r"|dynamic[-_]update[-_]slice", re.I)),
    ("dot", re.compile(r"dot_general|conv_general_dilated|(^|[/%])dot\b"
                       r"|convolution", re.I)),
    ("loop", re.compile(r"^%?(while|conditional|call)\b", re.I)),
    ("copy", re.compile(r"^%?(copy|transpose|bitcast|reshape|broadcast|pad"
                        r"|concatenate|slice)(-start|-done)?(\.\d+)*$",
                        re.I)),
)
_HLO_LINE = re.compile(r'^\s*(?:ROOT )?%?([\w.\-]+) = .*?'
                       r'metadata=\{[^}]*?op_name="([^"]*)"')
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
HOST_PREFIX = "bench/"


def short_name(name: str) -> str:
    """``%fusion.3518 = f32[...] fusion(...)`` -> ``fusion.3518``."""
    return name.split(" = ")[0].lstrip("%")


def op_names_from_hlo(text: str) -> dict:
    """``{instruction name: op_name}`` from a compiled program's text."""
    out = {}
    for ln in text.splitlines():
        m = _HLO_LINE.match(ln)
        if m:
            out.setdefault(m.group(1), m.group(2))
    return out


def classify(name: str, op_names: dict | None = None) -> str:
    short = short_name(name)
    for key in ((op_names or {}).get(short), short):
        if key:
            for cls, pat in OP_CLASSES:
                if pat.search(key):
                    return cls
    return "other"


def load_xplane(path: str, keep_host_prefix: str = HOST_PREFIX) -> dict:
    """``{"planes": [{"name", "lines": [{"name", "events": [[name,
    start_ns, dur_ns], ...]}]}]}``: every line of the device planes, and of
    the host planes only the benchmark's own annotations."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    planes = []
    for plane in pd.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        lines = []
        for line in plane.lines:
            evs = [[e.name, int(e.start_ns), int(e.duration_ns)]
                   for e in line.events
                   if device or e.name.startswith(keep_host_prefix)]
            if evs:
                lines.append({"name": line.name, "events": evs})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def load(path: str) -> dict:
    if path.endswith(".json"):
        with open(path) as f:
            return json.load(f)
    return load_xplane(path)


def _union(intervals):
    total, end = 0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def self_times(events):
    """``[(name, self_ns)]`` of one line's events, where an event that
    starts inside another is its child and its time is taken off the
    parent's."""
    out, stack = [], []           # stack of [name, end, self]
    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        e = s + d
        while stack and s >= stack[-1][1]:
            top = stack.pop()
            out.append((top[0], top[2]))
        if stack:
            # a child that runs past its parent's end is cut to it
            e = min(e, stack[-1][1])
            stack[-1][2] -= max(0, e - s)
        stack.append([name, e, max(0, e - s)])
    while stack:
        top = stack.pop()
        out.append((top[0], top[2]))
    return out


def reduce(trace: dict, steps: int | None = None,
           op_names: dict | None = None) -> dict:
    devices = [p for p in trace["planes"] if DEVICE_PLANE.match(p["name"])]
    if not devices:
        raise ValueError("no device plane in the trace: planes "
                         f"{[p['name'] for p in trace['planes']]}")
    host = [ev for p in trace["planes"] if not DEVICE_PLANE.match(p["name"])
            for ln in p["lines"] for ev in ln["events"]
            if ev[0].startswith(HOST_PREFIX)]
    per_device = []
    for p in devices:
        ops = [ev for ln in p["lines"] if ln["name"] == OPS_LINE
               for ev in ln["events"]]
        if not ops:
            continue
        ivs = [(s, s + d) for _, s, d in ops]
        per_device.append((p["name"], ops, _union(ivs),
                           min(s for s, _ in ivs), max(e for _, e in ivs)))
    if not per_device:
        raise ValueError(f"no {OPS_LINE!r} events on any device plane")
    busy_s = sum(d[2] for d in per_device) / len(per_device) / 1e9
    name, ops, busy_ns, t0, t1 = max(per_device, key=lambda d: d[2])
    by_name, by_class = {}, {}
    for nm, ns in self_times(ops):
        cls = classify(nm, op_names)
        short = short_name(nm)
        prim = (op_names or {}).get(short, "").rsplit("/", 1)[-1]
        label = f"{short} [{cls}{':' + prim if prim else ''}]"
        by_name[label] = by_name.get(label, 0) + ns
        by_class[cls] = by_class.get(cls, 0) + ns
    # gaps between operations on the busiest device, named by the host
    merged, end = [], None
    for s, e in sorted((s, s + d) for _, s, d in ops):
        if end is None or s > end:
            merged.append([s, e])
        else:
            merged[-1][1] = max(merged[-1][1], e)
        end = merged[-1][1]
    gaps = {}
    for (_, e0), (s1, _) in zip(merged[:-1], merged[1:]):
        best, cover = "unattributed", 0
        for nm, hs, hd in host:
            ov = min(s1, hs + hd) - max(e0, hs)
            # the innermost (shortest) annotation that covers the most
            if ov > cover:
                best, cover = nm, ov
        gaps[best] = gaps.get(best, 0) + (s1 - e0)
    out = {
        "device_plane": name,
        "devices": len(per_device),
        "busy_s": busy_s,
        "window_s": (t1 - t0) / 1e9,
        "op_class_s": {k: v / 1e9 for k, v in sorted(by_class.items())},
        "self_total_s": sum(by_class.values()) / 1e9,
        "busiest_busy_s": busy_ns / 1e9,
        "device_ops": [[k, v / 1e9] for k, v in
                       sorted(by_name.items(), key=lambda kv: -kv[1])],
        "idle_gaps": [[k, v / 1e9] for k, v in
                      sorted(gaps.items(), key=lambda kv: -kv[1])],
        "steps": steps,
    }
    return out


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def reduce_dir(trace_dir: str, steps: int | None = None,
               sample_to: str | None = None,
               op_names: dict | None = None) -> dict:
    trace = load_xplane(find_xplane(trace_dir))
    if sample_to:
        cut = sample(trace)
        used = {short_name(e[0]) for p in cut["planes"]
                for ln in p["lines"] for e in ln["events"]}
        cut["op_names"] = {k: v for k, v in (op_names or {}).items()
                           if k in used}
        with open(sample_to, "w") as f:
            json.dump(cut, f)
    return reduce(trace, steps, op_names)


def sample(trace: dict, n_events: int = 1500) -> dict:
    """A small cut of a trace for the recorded test file: the first
    ``n_events`` operations of each device line, under their short names,
    and the host annotations that start before the last of them ends."""
    planes, t_end = [], 0
    for p in trace["planes"]:
        if not DEVICE_PLANE.match(p["name"]):
            continue
        lines = []
        for ln in p["lines"]:
            evs = [[short_name(e[0]), e[1], e[2]] for e in
                   sorted(ln["events"], key=lambda e: e[1])[:n_events]]
            if ln["name"] == OPS_LINE:
                t_end = max(t_end, max(s + d for _, s, d in evs))
            lines.append({"name": ln["name"], "events": evs})
        planes.append({"name": p["name"], "lines": lines})
    for p in trace["planes"]:
        if DEVICE_PLANE.match(p["name"]):
            continue
        lines = [{"name": ln["name"],
                  "events": [e for e in ln["events"] if e[1] < t_end]}
                 for ln in p["lines"]]
        lines = [ln for ln in lines if ln["events"]]
        if lines:
            planes.append({"name": p["name"], "lines": lines})
    return {"planes": planes}
