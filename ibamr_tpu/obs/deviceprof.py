"""Device-time attribution: join profiler traces back to spans (PR 10).

PR 9 closed the host half of the observability loop — every phase is a
ledger span, and ``obs.span`` enters ``jax.named_scope`` so device
traces are *annotated* — but nothing ever read a trace back: the
``bench.py --profile-stages`` captures landed as raw
``*.trace.json.gz`` files no tool parsed. This module is the read-back
half. It parses the trace-viewer JSON inside a ``jax.profiler``
capture directory, extracts the device-lane op events, and attributes
each op's time to a span path, producing the per-span
``device_time_s`` table that merges with the host span tree
(``tools/obs.py summary --device``) and the ``prof_summary.json``
artifact ``tools/prof.py diff`` gates perf drift on.

Attribution is LAYERED, because the two backends annotate differently:

1. **scope prefix** — TPU/GPU op events carry the framework op path
   (``tf_op``/``op_name`` args, e.g. ``jit(step)/interp/sin``) whose
   components are exactly the ``jax.named_scope`` names ``obs.span``
   entered; the deepest component matching a known span LEAF wins.
2. **module name** — the CPU (TFRT) backend tags op events only with
   ``{"hlo_module": "jit_chunk", "hlo_op": "fusion.3"}``; the module
   name, normalized (``jit_chunk`` -> ``chunk``), is matched against
   span leaves (so the driver's ``driver/chunk`` span claims every op
   of its compiled chunk), then against an explicit ``module_map``.
3. **module identity** — an op whose module resolves to no span is
   still grouped under its module name (``attributed`` to a named
   home, just not a span) so bench captures with no ledger attached
   remain comparable across revisions.

Anything left — no scope, no module — lands in an EXPLICIT
``unattributed`` breakdown keyed by event name. The invariant
``attributed_s + unattributed_s == total_device_s`` is part of the
summary schema (:func:`validate_summary`), so a parser bug that drops
time fails the schema check instead of silently flattering a capture.

Everything here is offline and host-side: stdlib only, no jax import,
usable on a machine that never saw the accelerator.
"""

from __future__ import annotations

import glob
import gzip
import json
import math
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

PROF_SCHEMA = 1
SUMMARY_NAME = "prof_summary.json"
CENSUS_NAME = "census_counts.json"

# trace-viewer process names that mark an accelerator timeline
_DEVICE_PROC_RE = re.compile(r"/device:|^TPU|^GPU", re.IGNORECASE)
# thread names that are op lanes on TPU/GPU timelines (preferred over
# "XLA Modules"/"Steps" rows, which overlap the op rows and would
# double-count every nanosecond)
_OP_LANE_RE = re.compile(r"XLA Ops|TensorFlow Ops", re.IGNORECASE)
# args keys that can carry a slash-separated framework scope path
_SCOPE_ARG_KEYS = ("tf_op", "op_name", "long_name", "name", "scope")
# op-class buckets for the roofline join: FFT ops, contractions, and
# (PR 15) collectives.  A device op is comm when its HLO opcode is a
# collective (sync or async -start/-done halves) OR its framework scope
# path passes through a ``comm`` component — the named scope the
# parallel layer (fftpar/lagrangian/mesh/norms/krylov) wraps every
# cross-device exchange in — so partitioner-materialized resharding
# that keeps a fused non-collective opcode still lands in ``comm_s``.
_FFT_OP_RE = re.compile(r"(^|[./])i?r?fft", re.IGNORECASE)
_DOT_OP_RE = re.compile(r"(^|[./])(dot|convolution|gemm|matmul)",
                        re.IGNORECASE)
_COMM_OP_RE = re.compile(
    r"(^|[./])(all-reduce|all-gather|all-to-all|collective-permute|"
    r"reduce-scatter|collective-broadcast)(-start|-done)?(\.|$)",
    re.IGNORECASE)
_COMM_SCOPE = "comm"


# ---------------------------------------------------------------------------
# capture-dir / trace-file plumbing
# ---------------------------------------------------------------------------

def find_trace_files(capture_dir: str) -> List[str]:
    """Every trace-viewer JSON in a ``jax.profiler`` capture dir
    (``<dir>/plugins/profile/<ts>/<host>.trace.json.gz`` — one per
    host; plain ``.trace.json`` accepted for hand-built fixtures)."""
    out: List[str] = []
    for pat in ("**/*.trace.json.gz", "**/*.trace.json"):
        out.extend(glob.glob(os.path.join(capture_dir, pat),
                             recursive=True))
    return sorted(set(out))


def load_trace(path: str) -> dict:
    op = gzip.open if path.endswith(".gz") else open
    with op(path, "rb") as f:
        return json.loads(f.read())


def capture_bytes(capture_dir: str) -> int:
    """Total on-disk bytes of a capture directory."""
    total = 0
    for root, _, files in os.walk(capture_dir):
        for name in files:
            try:
                total += os.path.getsize(os.path.join(root, name))
            except OSError:
                pass
    return total


# ---------------------------------------------------------------------------
# device-lane op events
# ---------------------------------------------------------------------------

def _lane_meta(trace: dict) -> Tuple[Dict[int, str], Dict[tuple, str]]:
    """(pid -> process name, (pid, tid) -> thread name) from the
    trace's metadata ('M') events."""
    procs: Dict[int, str] = {}
    threads: Dict[tuple, str] = {}
    for e in trace.get("traceEvents") or []:
        if e.get("ph") != "M":
            continue
        args = e.get("args") or {}
        if e.get("name") == "process_name":
            procs[e.get("pid")] = str(args.get("name", ""))
        elif e.get("name") == "thread_name":
            threads[(e.get("pid"), e.get("tid"))] = \
                str(args.get("name", ""))
    return procs, threads


def device_op_events(trace: dict) -> Tuple[List[dict], List[dict]]:
    """(op events, device-lane descriptions) for one trace.

    TPU/GPU timelines: processes named ``/device:*`` — take the
    ``XLA Ops`` threads (falling back to every thread of the device
    process when no lane is labeled), and count every complete ('X')
    event there as device-op time. CPU (TFRT) timelines: there is no
    device process, and the executor's op events are scattered across
    pool threads — an op event is exactly an X event carrying
    ``hlo_op``/``hlo_module`` args, wherever it sits (the python host
    thread's function-trace events carry neither and are excluded).
    """
    procs, threads = _lane_meta(trace)
    dev_pids = {pid for pid, name in procs.items()
                if _DEVICE_PROC_RE.search(name or "")}
    op_lanes = {key for key, name in threads.items()
                if key[0] in dev_pids and _OP_LANE_RE.search(name or "")}
    labeled_pids = {pid for pid, _ in op_lanes}
    events: List[dict] = []
    lane_busy: Dict[tuple, dict] = {}
    for e in trace.get("traceEvents") or []:
        if e.get("ph") != "X":
            continue
        key = (e.get("pid"), e.get("tid"))
        args = e.get("args") or {}
        if key[0] in dev_pids:
            # device process: only labeled op lanes when any exist FOR
            # THIS pid (module/step rows overlap the op rows)
            if key[0] in labeled_pids and key not in op_lanes:
                continue
        elif "hlo_op" not in args and "hlo_module" not in args:
            continue                      # host-side python/runtime event
        events.append(e)
        lane = lane_busy.setdefault(key, {
            "pid": key[0], "tid": key[1],
            "process": procs.get(key[0], ""),
            "thread": threads.get(key, ""),
            "events": 0, "busy_s": 0.0})
        lane["events"] += 1
        lane["busy_s"] += float(e.get("dur") or 0.0) / 1e6
    lanes = sorted(lane_busy.values(),
                   key=lambda d: -(d["busy_s"]))
    for d in lanes:
        d["busy_s"] = round(d["busy_s"], 9)
    return events, lanes


# ---------------------------------------------------------------------------
# attribution
# ---------------------------------------------------------------------------

def _norm_component(comp: str) -> str:
    """``jit(step)`` -> ``step``; ``transpose[permutation=...]`` ->
    ``transpose``; named-scope components pass through."""
    comp = comp.split("[")[0].strip()
    m = re.match(r"^(?:p?jit|vmap|scan|while|named)\((.*)\)$", comp)
    if m:
        comp = m.group(1)
    return comp


def _norm_module(module: str) -> str:
    """``jit_chunk`` / ``jit__chunk`` / ``jit_step.7`` -> ``chunk`` /
    ``chunk`` / ``step`` — the wrapped function's name, which is what
    a span leaf can plausibly match."""
    m = re.sub(r"\.\d+$", "", str(module))
    m = re.sub(r"^(?:p?jit_+)", "", m)
    return m.strip("_") or str(module)


def _scope_components(event: dict) -> List[str]:
    """The framework scope path of one op event, as components, or []
    when the event carries none (the CPU backend)."""
    args = event.get("args") or {}
    for key in _SCOPE_ARG_KEYS:
        v = args.get(key)
        if isinstance(v, str) and "/" in v:
            return [c for c in v.split("/") if c]
    name = event.get("name")
    if isinstance(name, str) and "/" in name:
        return [c for c in name.split("/") if c]
    return []


def span_leaf_map(span_paths: Iterable[str]) -> Dict[str, str]:
    """leaf name -> full span path. ``obs.span`` enters
    ``jax.named_scope`` with the LEAF of the span name (everything
    after the last ``/`` and ``::``), so the leaf is the token that can
    appear inside a trace. Ambiguous leaves resolve to the SHALLOWEST
    path (deterministic: sorted by depth then name)."""
    leaf_map: Dict[str, str] = {}
    for path in sorted(set(span_paths),
                       key=lambda p: (p.count("/"), p)):
        leaf = path.split("/")[-1].split("::")[-1]
        leaf_map.setdefault(leaf, path)
    return leaf_map


def _resolve(event: dict, leaf_map: Dict[str, str],
             module_map: Dict[str, str]):
    """(key, via) for one op event — ``via`` in {"scope", "module",
    "module-name"} — or (None, None) when nothing identifies it."""
    comps = _scope_components(event)
    for comp in reversed(comps):
        leaf = _norm_component(comp)
        if leaf in leaf_map:
            return leaf_map[leaf], "scope"
    module = (event.get("args") or {}).get("hlo_module")
    if module:
        if module in module_map:
            return module_map[module], "module"
        norm = _norm_module(module)
        if norm in module_map:
            return module_map[norm], "module"
        if norm in leaf_map:
            return leaf_map[norm], "module"
        return norm, "module-name"
    return None, None


def attribute_events(events: List[dict],
                     span_paths: Iterable[str] = (),
                     module_map: Optional[Dict[str, str]] = None,
                     max_ops: int = 16) -> dict:
    """Attribute device-op events to span paths.

    Returns the core of a :data:`SUMMARY_NAME` document; every second
    of device-lane time lands either in ``spans`` (attributed — via
    scope prefix, module match, or module identity) or in the explicit
    ``unattributed`` breakdown. ``op_classes`` tallies
    FFT/contraction/collective op time for the roofline join; the
    classes partition ``total_device_s`` exactly (``other_s`` is the
    remainder), independent of the span accounting identity."""
    leaf_map = span_leaf_map(span_paths)
    module_map = dict(module_map or {})
    spans: Dict[str, dict] = {}
    unattributed: Dict[str, float] = {}
    total = attributed = 0.0
    fft_s = dot_s = comm_s = 0.0
    for e in events:
        dur = float(e.get("dur") or 0.0) / 1e6
        total += dur
        opname = str((e.get("args") or {}).get("hlo_op")
                     or e.get("name") or "?")
        # comm wins over fft/dot: a collective (or an op inside the
        # parallel layer's ``comm`` named scope) is wire time even when
        # its fused opcode also mentions a compute class
        if _COMM_OP_RE.search(opname) or any(
                _norm_component(c) == _COMM_SCOPE
                for c in _scope_components(e)):
            comm_s += dur
        elif _FFT_OP_RE.search(opname):
            fft_s += dur
        elif _DOT_OP_RE.search(opname):
            dot_s += dur
        key, via = _resolve(e, leaf_map, module_map)
        if key is None:
            unattributed[opname] = unattributed.get(opname, 0.0) + dur
            continue
        attributed += dur
        node = spans.setdefault(key, {"device_s": 0.0, "events": 0,
                                      "via": {}, "ops": {}})
        node["device_s"] += dur
        node["events"] += 1
        node["via"][via] = node["via"].get(via, 0) + 1
        node["ops"][opname] = node["ops"].get(opname, 0.0) + dur
    for node in spans.values():
        node["device_s"] = round(node["device_s"], 9)
        top = sorted(node["ops"].items(), key=lambda kv: -kv[1])
        node["ops"] = {k: round(v, 9) for k, v in top[:max_ops]}
    return {
        "total_device_s": round(total, 9),
        "attributed_s": round(attributed, 9),
        "unattributed_s": round(total - attributed, 9),
        "fraction_attributed": round(attributed / total, 6)
        if total > 0 else 1.0,
        "spans": spans,
        "unattributed": {
            k: round(v, 9)
            for k, v in sorted(unattributed.items(),
                               key=lambda kv: -kv[1])[:max_ops]},
        "op_classes": {"fft_s": round(fft_s, 9),
                       "dot_s": round(dot_s, 9),
                       "comm_s": round(comm_s, 9),
                       "other_s": round(total - fft_s - dot_s
                                        - comm_s, 9)},
    }


def spans_from_ledger(ledger_path: str) -> List[str]:
    """Distinct span paths recorded in a run ledger (the PR-9 host
    side of the join)."""
    from ibamr_tpu.obs.bus import read_ledger

    return sorted({r.get("path") or r.get("name")
                   for r in read_ledger(ledger_path)
                   if r.get("kind") == "span"
                   and (r.get("path") or r.get("name"))})


def attribute_capture(capture_dir: str,
                      span_paths: Iterable[str] = (),
                      module_map: Optional[Dict[str, str]] = None,
                      ledger: Optional[str] = None) -> dict:
    """Parse + attribute every trace file in ``capture_dir`` into one
    :data:`SUMMARY_NAME` document. ``ledger`` (a ``ledger.jsonl`` path
    or its directory) contributes its recorded span paths; the
    ``census_counts.json`` sidecar, when present (bench writes it at
    capture time), is joined into a roofline block."""
    paths = list(span_paths)
    if ledger:
        if os.path.isdir(ledger):
            ledger = os.path.join(ledger, "ledger.jsonl")
        paths.extend(spans_from_ledger(ledger))
    files = find_trace_files(capture_dir)
    events: List[dict] = []
    lanes: List[dict] = []
    for f in files:
        ev, ln = device_op_events(load_trace(f))
        events.extend(ev)
        lanes.extend(ln)
    summary = attribute_events(events, paths, module_map)
    summary.update(schema=PROF_SCHEMA,
                   capture_dir=os.path.abspath(capture_dir),
                   trace_files=len(files), lanes=lanes,
                   capture_bytes=capture_bytes(capture_dir))
    census = read_census(capture_dir)
    summary["census"] = census
    if census:
        from ibamr_tpu.obs.roofline import roofline_join

        summary["roofline"] = roofline_join(summary, census)
    else:
        summary["roofline"] = None
    return summary


# ---------------------------------------------------------------------------
# the summary artifact
# ---------------------------------------------------------------------------

def summary_path(path: str) -> str:
    """A directory means its ``prof_summary.json``."""
    if os.path.isdir(path):
        return os.path.join(path, SUMMARY_NAME)
    return path


def write_summary(capture_dir: str, summary: dict) -> str:
    """Atomically land ``prof_summary.json`` next to the capture."""
    path = os.path.join(capture_dir, SUMMARY_NAME)
    tmp = path + f".tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(summary, f, indent=1, sort_keys=True)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)
    return path


def read_summary(path: str) -> dict:
    with open(summary_path(path)) as f:
        return json.load(f)


def read_census(capture_dir_or_path: str) -> Optional[dict]:
    path = capture_dir_or_path
    if os.path.isdir(path):
        path = os.path.join(path, CENSUS_NAME)
    try:
        with open(path) as f:
            data = json.load(f)
        return data if isinstance(data, dict) else None
    except (OSError, ValueError):
        return None


def _num(v) -> bool:
    return isinstance(v, (int, float)) and not isinstance(v, bool) \
        and math.isfinite(v)


def validate_summary(summary) -> List[str]:
    """Schema check; returns problems ([] = valid).

    This is what makes a malformed ``prof_summary.json`` fail LOUDLY
    (``tools/prof.py check`` exits 2) instead of being archived as
    garbage — including the accounting invariant that attributed plus
    unattributed time reconstructs the device total, so time can never
    be silently dropped by a parser bug."""
    probs: List[str] = []
    if not isinstance(summary, dict):
        return ["summary is not an object"]
    if summary.get("schema") != PROF_SCHEMA:
        probs.append(f"schema != {PROF_SCHEMA}: "
                     f"{summary.get('schema')!r}")
    for key in ("total_device_s", "attributed_s", "unattributed_s"):
        v = summary.get(key)
        if not _num(v) or v < 0:
            probs.append(f"{key} not a finite non-negative number: "
                         f"{v!r}")
    frac = summary.get("fraction_attributed")
    if not _num(frac) or not (0.0 <= frac <= 1.0):
        probs.append(f"fraction_attributed outside [0, 1]: {frac!r}")
    spans = summary.get("spans")
    if not isinstance(spans, dict):
        probs.append("spans is not an object")
        spans = {}
    span_sum = 0.0
    for key, node in spans.items():
        dv = node.get("device_s") if isinstance(node, dict) else node
        if not _num(dv) or dv < 0:
            probs.append(f"spans[{key!r}].device_s invalid: {dv!r}")
        else:
            span_sum += dv
    if not isinstance(summary.get("unattributed"), dict):
        probs.append("unattributed breakdown missing")
    if not probs:
        total = summary["total_device_s"]
        tol = max(1e-6, 1e-4 * total)
        if abs(summary["attributed_s"] + summary["unattributed_s"]
               - total) > tol:
            probs.append("attributed_s + unattributed_s != "
                         "total_device_s (time dropped)")
        if abs(span_sum - summary["attributed_s"]) > tol:
            probs.append("sum(spans.device_s) != attributed_s")
    return probs


def compact_summary(summary: dict) -> dict:
    """The embeddable slice (bench JSON ``profiles[*].summary``): the
    tables a diff needs, without per-lane/per-op detail."""
    return {
        "schema": summary.get("schema"),
        "total_device_s": summary.get("total_device_s"),
        "attributed_s": summary.get("attributed_s"),
        "unattributed_s": summary.get("unattributed_s"),
        "fraction_attributed": summary.get("fraction_attributed"),
        "spans": {k: {"device_s": (v.get("device_s")
                                   if isinstance(v, dict) else v)}
                  for k, v in (summary.get("spans") or {}).items()},
        "unattributed": summary.get("unattributed") or {},
        "op_classes": summary.get("op_classes"),
        "census": {k: v for k, v in (summary.get("census") or {}).items()
                   if k in ("label", "n", "executions")} or None,
        "roofline": summary.get("roofline"),
    }


# ---------------------------------------------------------------------------
# pruning (tools/prof.py archive step)
# ---------------------------------------------------------------------------

_RAW_SUFFIXES = (".trace.json.gz", ".trace.json", ".xplane.pb",
                 ".memory_profile.json.gz", ".overview_page.pb",
                 ".input_pipeline.pb", ".tensorflow_stats.pb",
                 ".kernel_stats.pb", ".hlo_proto.pb")


def prune_raw_traces(capture_dir: str) -> int:
    """Delete the raw multi-MB profiler outputs under ``capture_dir``
    (the ``plugins/profile`` tree), keeping the compact
    ``prof_summary.json`` / ``census_counts.json``. Returns bytes
    freed. Callers MUST validate the summary first — ``tools/prof.py
    archive`` refuses to prune when :func:`validate_summary` fails."""
    freed = 0
    for root, dirs, files in os.walk(capture_dir, topdown=False):
        for name in files:
            if not name.endswith(_RAW_SUFFIXES):
                continue
            path = os.path.join(root, name)
            try:
                freed += os.path.getsize(path)
                os.unlink(path)
            except OSError:
                pass
        for d in dirs:
            try:
                os.rmdir(os.path.join(root, d))   # only if now empty
            except OSError:
                pass
    return freed
