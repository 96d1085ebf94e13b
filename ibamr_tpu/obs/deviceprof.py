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

Attribution is LAYERED, because the backends annotate differently:

0. **phase** — this chip's trace names an operation by its HLO
   instruction (``%fusion.3518``) and carries no scope at all. The
   phase comes from the instruction's ``op_name`` in the compiled
   program's text (:func:`names_from_hlo`; the program keeps what
   reading that text again needs, ``obs.programs()``): the deepest
   known phase (:data:`PHASES`, the ``jax.named_scope`` names inside
   the step) on that path; data movement the compiler placed takes
   the phase of the value it moves. Idle gaps between operations are
   named by the program span (``obs.span`` enters ``TraceAnnotation``)
   that covers most of each (:func:`attribute_planes`).
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

Everything here is offline and host-side: stdlib only, no jax import
(but for :func:`program_names`, which lowers a live program, and
:func:`load_planes` on an ``.xplane.pb``), usable on a machine that
never saw the accelerator.
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
OP_NAMES_NAME = "op_names.json"

# The phases of the compiled step, as sequences of ``jax.named_scope``
# names (opened in integrators/ib.py, integrators/constraint_ib.py,
# integrators/ins.py, ops/interaction_packed.py, solvers/fastdiag.py
# and solvers/spectral_plan.py). A phase matches an ``op_name`` when
# its scopes appear on the path in order; the deepest match wins, and
# of two equally deep the one listed first: an axis transform of the
# ConstraintIB re-projection (``fluid/reproject/transforms/...``) is
# ``fluid/transforms`` like every other, and ``fluid/reproject`` is
# what that projection does around its transforms.
PHASES = (("ib/prep",), ("ib/interp",), ("ib/refresh",),
          ("ib/refresh", "repack"), ("ib/force",), ("ib/spread",),
          ("fluid",), ("fluid", "transforms"), ("fluid", "convect"),
          ("fluid", "rhs"), ("fluid", "reproject"),
          ("constraint/rigid",), ("constraint/impose",),
          ("fluid", "krylov"), ("fluid", "krylov", "operator"),
          ("fluid", "krylov", "precond"), ("fluid", "krylov", "arnoldi"))
# host annotations that are the program's own spans (obs.span paths)
PROGRAM_SPAN_RE = re.compile(r"(^|/)(driver|checkpoint|setup|compile)/")
_DEVICE_PLANE_RE = re.compile(r"^/device:(TPU|GPU):\d+$")
_OPS_LINE = "XLA Ops"
# one instruction: name, opcode (lower case, where dtypes are followed
# by ``[`` and layout tiles ``T(``/``S(`` start upper case), operands
_INST_RE = re.compile(r'^\s*(?:ROOT )?%?([\w.\-]+) = .*?'
                      r'\b([a-z][\w\-]*)\(([^)]*)\)')
_OP_NAME_RE = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_OPERAND_RE = re.compile(r'%([\w.\-]+)')
# data movement the compiler places; control flow never inherits a phase
_MOVE_OPCODES = frozenset((
    "copy", "copy-start", "copy-done", "slice", "slice-start",
    "slice-done", "reshape", "transpose", "bitcast", "broadcast", "pad",
    "concatenate"))
_NO_INHERIT = frozenset((
    "while", "conditional", "call", "parameter", "tuple",
    "get-tuple-element", "constant"))

# trace-viewer process names that mark an accelerator timeline
_DEVICE_PROC_RE = re.compile(r"/device:|^TPU|^GPU", re.IGNORECASE)
# thread names that are op lanes on TPU/GPU timelines (preferred over
# "XLA Modules"/"Steps" rows, which overlap the op rows and would
# double-count every nanosecond)
_OP_LANE_RE = re.compile(r"XLA Ops|TensorFlow Ops", re.IGNORECASE)
# args keys that can carry a slash-separated framework scope path
_SCOPE_ARG_KEYS = ("tf_op", "op_name", "long_name", "name", "scope")
# op-class buckets: FFT ops, contractions, and
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
_FFT_PATH_RE = re.compile(r"\bi?r?fft\b", re.IGNORECASE)
_DOT_PATH_RE = re.compile(r"(^|/)(dot_general|dot|convolution)\b",
                          re.IGNORECASE)


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
             module_map: Dict[str, str],
             phases: Optional[Dict[str, str]] = None):
    """(key, via) for one op event — ``via`` in {"phase", "scope",
    "module", "module-name"} — or (None, None) when nothing identifies
    it."""
    if phases:
        phase = phases.get(short_name(str(
            (event.get("args") or {}).get("hlo_op")
            or event.get("name") or "")))
        if phase:
            return phase, "phase"
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
                     max_ops: int = 16,
                     phases: Optional[Dict[str, str]] = None) -> dict:
    """Attribute device-op events to span paths.

    Returns the core of a :data:`SUMMARY_NAME` document; every second
    of device-lane time lands either in ``spans`` (attributed — via
    scope prefix, module match, or module identity) or in the explicit
    ``unattributed`` breakdown. ``op_classes`` tallies
    FFT/contraction/collective op time; the
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
        key, via = _resolve(e, leaf_map, module_map, phases)
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
                      ledger: Optional[str] = None,
                      names: Optional[Tuple[Dict[str, str],
                                            Dict[str, str]]] = None,
                      executions: Optional[int] = None) -> dict:
    """Parse + attribute every trace file in ``capture_dir`` into one
    :data:`SUMMARY_NAME` document. ``ledger`` (a ``ledger.jsonl`` path
    or its directory) contributes its recorded span paths.
    ``names`` (``({instruction: op_name}, {instruction: phase})`` of
    the compiled programs, :func:`programs_names`; default: the
    capture's :data:`OP_NAMES_NAME` sidecar, which
    ``utils.timers.profile_trace`` writes) turns on the phase layer,
    and with an ``.xplane.pb`` in the capture the whole reduction then
    goes through :func:`attribute_planes` (self times, idle gaps).
    ``executions`` (step or chunk launches under the capture) lets
    ``tools/prof.py diff`` compare per execution."""
    paths = list(span_paths)
    if ledger:
        if os.path.isdir(ledger):
            ledger = os.path.join(ledger, "ledger.jsonl")
        paths.extend(spans_from_ledger(ledger))
    op_names, phases = names or read_names(capture_dir) or (None, None)
    xplanes = sorted(glob.glob(os.path.join(capture_dir, "**",
                                            "*.xplane.pb"),
                               recursive=True))
    summary = None
    if phases and xplanes:
        try:
            summary = attribute_planes(load_planes(xplanes[-1]), phases,
                                       op_names)
            files, lanes = xplanes[-1:], summary.pop("lanes")
        except ValueError:
            pass            # a CPU capture has no device plane
    if summary is None:
        files = find_trace_files(capture_dir)
        events: List[dict] = []
        lanes: List[dict] = []
        for f in files:
            ev, ln = device_op_events(load_trace(f))
            events.extend(ev)
            lanes.extend(ln)
        summary = attribute_events(events, paths, module_map,
                                   phases=phases)
    summary.update(schema=PROF_SCHEMA,
                   capture_dir=os.path.abspath(capture_dir),
                   trace_files=len(files), lanes=lanes,
                   capture_bytes=capture_bytes(capture_dir),
                   executions=executions)
    return summary


# ---------------------------------------------------------------------------
# phases: instruction -> op_name -> phase
# ---------------------------------------------------------------------------

def short_name(name: str) -> str:
    """``%fusion.3518 = f32[...] fusion(...)`` -> ``fusion.3518``."""
    return name.split(" = ")[0].lstrip("%")


def phase_of(op_name: str, phases=PHASES) -> Optional[str]:
    """The deepest known phase on an ``op_name`` path, or None."""
    padded = "/" + op_name + "/"
    best = None
    for seq in phases:
        pos = 0
        for scope in seq:
            pos = padded.find("/" + scope + "/", pos)
            if pos < 0:
                break
            pos += len(scope) + 1
        else:
            if best is None or len(seq) > len(best):
                best = seq
    return "/".join(best) if best else None


def phase_map(op_names: Dict[str, str], phases=PHASES) -> Dict[str, str]:
    """``{instruction: phase}`` for the instructions whose own
    ``op_name`` carries one."""
    out = {}
    for inst, op_name in op_names.items():
        ph = phase_of(op_name, phases)
        if ph:
            out[inst] = ph
    return out


def names_from_hlo(text: str, phases=PHASES
                   ) -> Tuple[Dict[str, str], Dict[str, str]]:
    """``({instruction: op_name}, {instruction: phase})`` of a compiled
    program's text. The phase is the one on the instruction's own
    ``op_name``; data movement the compiler placed (an instruction
    with no metadata, or a copy / reshape / transpose / slice ... whose
    path names no phase: the chip's ``copy-done``, ``slice-done`` and
    relayouts, 5% of the busy time at 128^3) takes the phase of the
    value it moves: of its first operand that has one, else of its
    first user that has one. Compute outside every scope (the marker
    and midpoint updates, the health flag) and control flow inherit
    nothing, and nothing is inherited across a loop's boundary."""
    op_names: Dict[str, str] = {}
    order: List[Tuple[str, str, List[str]]] = []
    for ln in text.splitlines():
        m = _INST_RE.match(ln)
        if not m:
            continue
        inst, opcode, operands = m.groups()
        meta = _OP_NAME_RE.search(ln, m.end())
        if meta:
            op_names.setdefault(inst, meta.group(1))
        order.append((inst, opcode, _OPERAND_RE.findall(operands)))
    out = phase_map(op_names, phases)
    moves = [(inst, ops) for inst, opcode, ops in order
             if inst not in out and opcode not in _NO_INHERIT
             and (inst not in op_names or opcode in _MOVE_OPCODES)]
    for inst, ops in moves:                 # from what it moves ...
        for o in ops:
            if o in out:
                out[inst] = out[o]
                break
    first_user: Dict[str, str] = {}
    for inst, _, ops in order:
        for o in ops:
            first_user.setdefault(o, inst)
    for inst, _ in reversed(moves):         # ... or to where it goes
        user = first_user.get(inst)
        if inst not in out and user in out:
            out[inst] = out[user]
    return op_names, out


def program_names(prog: dict) -> Tuple[Dict[str, str], Dict[str, str]]:
    """:func:`names_from_hlo` of one program of ``obs.programs()``:
    lowers it again from its abstract arguments and reads the compiled
    text (the jit's own memo, or a persistent-cache read). Seconds of
    host time at full size, minutes where it has to compile: only for
    someone who asks.

    This jax's cache key leaves op metadata out (``strip-debuginfo``),
    so an executable compiled before a scope existed is served for a
    program that now carries it (measured on the chip, PR 25: the
    parent's 128^3 entry came with the machine). Where the compiled
    text lacks a phase the lowering has, the program is compiled once
    more, past the cache: under a key that takes the metadata in (a
    miss), with nothing written back (the shared cache is small, and
    an entry more evicts a program some run's set-up counts on), and
    with an inert compiler option that gets past the lowering's memo of
    its first executable. The instruction names of the two compiles
    agree: the key pins the computation and the compiler."""
    import jax

    lowered = prog["fn"].lower(*prog["args"])
    names = names_from_hlo(lowered.compile().as_text())
    want = {phase_of(m) for m in re.findall(
        r'loc\("([^"]*)"', lowered.as_text(debug_info=True))} - {None}
    if want - set(names[1].values()):
        past_cache = {
            "jax_compilation_cache_include_metadata_in_key": True,
            "jax_persistent_cache_min_compile_time_secs": float("inf")}
        was = {k: getattr(jax.config, k) for k in past_cache}
        for k, v in past_cache.items():
            jax.config.update(k, v)
        try:
            names = names_from_hlo(lowered.compile(compiler_options={
                "xla_dump_disable_metadata": False}).as_text())
        finally:
            for k, v in was.items():
                jax.config.update(k, v)
    return names


def programs_names(progs: Optional[Iterable[dict]] = None
                   ) -> Tuple[Dict[str, str], Dict[str, str]]:
    """One ``({instruction: op_name}, {instruction: phase})`` over the
    registered programs, in the order they were first called; where two
    programs name one instruction the later one wins."""
    if progs is None:
        from ibamr_tpu.obs.bus import programs

        progs = programs()
    op_names: Dict[str, str] = {}
    phases: Dict[str, str] = {}
    for prog in progs:
        names, ph = program_names(prog)
        op_names.update(names)
        phases.update(ph)
    return op_names, phases


def write_names(capture_dir: str, names=None) -> str:
    """Land the :data:`OP_NAMES_NAME` sidecar beside a capture, so that
    ``tools/prof.py attribute`` can name phases offline."""
    op_names, phases = names or programs_names()
    path = os.path.join(capture_dir, OP_NAMES_NAME)
    with open(path, "w") as f:
        json.dump({"op_names": op_names, "phases": phases}, f)
    return path


def read_names(capture_dir: str):
    try:
        with open(os.path.join(capture_dir, OP_NAMES_NAME)) as f:
            data = json.load(f)
        return data["op_names"], data["phases"]
    except (OSError, ValueError, KeyError, TypeError):
        return None


# ---------------------------------------------------------------------------
# the chip's trace: planes of (name, start, duration) events
# ---------------------------------------------------------------------------

def load_planes(path: str) -> dict:
    """``{"planes": [{"name", "lines": [{"name", "events": [[name,
    start_ns, dur_ns], ...]}]}]}`` from an ``.xplane.pb`` (through
    ``jax.profiler.ProfileData``; of the host planes only the program's
    spans are kept) or from such a dict saved as ``.json``."""
    if path.endswith(".json"):
        with open(path) as f:
            return json.load(f)
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        device = bool(_DEVICE_PLANE_RE.match(plane.name))
        lines = []
        for line in plane.lines:
            evs = [[e.name, int(e.start_ns), int(e.duration_ns)]
                   for e in line.events
                   if device or PROGRAM_SPAN_RE.search(e.name)]
            if evs:
                lines.append({"name": line.name, "events": evs})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def self_times(events) -> List[Tuple[str, int]]:
    """``[(name, self_ns)]`` of one line's events: an event that starts
    inside another is its child (a ``while`` spans its body's
    operations) and its time comes off the parent's, so the self times
    add up to the time in which anything ran."""
    out, stack = [], []           # stack of [name, end, self]
    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        e = s + d
        while stack and s >= stack[-1][1]:
            top = stack.pop()
            out.append((top[0], top[2]))
        if stack:
            e = min(e, stack[-1][1])      # a child is cut to its parent
            stack[-1][2] -= max(0, e - s)
        stack.append([name, e, max(0, e - s)])
    while stack:
        top = stack.pop()
        out.append((top[0], top[2]))
    return out


def attribute_planes(trace: dict, phases: Dict[str, str],
                     op_names: Optional[Dict[str, str]] = None,
                     span_re=PROGRAM_SPAN_RE, max_ops: int = 16) -> dict:
    """Attribute the busiest device plane of a planes dict: each
    operation's SELF time to its instruction's phase (``phases``,
    :func:`phase_map`), the rest to the explicit ``unattributed``
    breakdown, and each idle gap between operations to the host span
    matching ``span_re`` that covers most of it (the innermost of
    those that cover nearly as much). ``total_device_s`` is the time
    in which an operation ran, so ``attributed_s + unattributed_s ==
    total_device_s`` holds."""
    devices = []
    for p in trace["planes"]:
        if not _DEVICE_PLANE_RE.match(p["name"]):
            continue
        ops = [ev for ln in p["lines"] if ln["name"] == _OPS_LINE
               for ev in ln["events"]]
        if ops:
            selfs = self_times(ops)
            devices.append((sum(ns for _, ns in selfs), p["name"], ops,
                            selfs))
    if not devices:
        raise ValueError(f"no {_OPS_LINE!r} events on a device plane: "
                         f"{[p['name'] for p in trace['planes']]}")
    total_ns, plane, ops, selfs = max(devices, key=lambda d: d[0])
    op_names = op_names or {}
    spans: Dict[str, dict] = {}
    unattributed: Dict[str, float] = {}
    classes = {"fft_s": 0.0, "dot_s": 0.0, "comm_s": 0.0}
    attributed = 0
    for name, ns in selfs:
        inst = short_name(name)
        dur = ns / 1e9
        op_name = op_names.get(inst, "")
        # the class by the primitive path where the program's text gave
        # one (the chip fuses a transform into ``fusion.N`` whose path
        # ends ``jit(fft)``), by the instruction's own name otherwise
        if _COMM_OP_RE.search(inst) or f"/{_COMM_SCOPE}/" in op_name:
            classes["comm_s"] += dur
        elif _FFT_PATH_RE.search(op_name) or _FFT_OP_RE.search(inst):
            classes["fft_s"] += dur
        elif _DOT_PATH_RE.search(op_name) or _DOT_OP_RE.search(inst):
            classes["dot_s"] += dur
        phase = phases.get(inst)
        if phase is None:
            unattributed[inst] = unattributed.get(inst, 0.0) + dur
            continue
        attributed += ns
        node = spans.setdefault(phase, {"device_s": 0.0, "events": 0,
                                        "via": {"phase": 0}, "ops": {}})
        node["device_s"] += dur
        node["events"] += 1
        node["via"]["phase"] += 1
        node["ops"][inst] = node["ops"].get(inst, 0.0) + dur
    for node in spans.values():
        node["device_s"] = round(node["device_s"], 9)
        top = sorted(node["ops"].items(), key=lambda kv: -kv[1])
        node["ops"] = {k: round(v, 9) for k, v in top[:max_ops]}
    # idle gaps on that device, each under the host span covering most
    host = sorted((ev for p in trace["planes"]
                   if not _DEVICE_PLANE_RE.match(p["name"])
                   for ln in p["lines"] for ev in ln["events"]
                   if span_re.search(ev[0])), key=lambda ev: ev[1])
    merged: List[List[int]] = []
    for s0, e0 in sorted((s0, s0 + d) for _, s0, d in ops):
        if merged and s0 <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e0)
        else:
            merged.append([s0, e0])
    gaps: Dict[str, float] = {}
    live: List[list] = []         # host spans that can still cover a gap
    nxt = 0
    for (_, e0), (s1, _) in zip(merged[:-1], merged[1:]):
        while nxt < len(host) and host[nxt][1] < s1:
            live.append(host[nxt])
            nxt += 1
        live = [h for h in live if h[1] + h[2] > e0]
        # the span that covers most of the gap; spans nest, and a parent
        # covers whatever its child does, so among those that cover
        # nearly all of it the innermost (shortest) one names it
        cover = [(min(s1, hs + hd) - max(e0, hs), hd, nm)
                 for nm, hs, hd in live]
        cover = [c for c in cover if c[0] > 0]
        best = "unattributed"
        if cover:
            most = max(c[0] for c in cover)
            best = min(c[1:] for c in cover if c[0] >= 0.9 * most)[1]
        gaps[best] = gaps.get(best, 0.0) + (s1 - e0) / 1e9
    total = total_ns / 1e9
    named = classes["fft_s"] + classes["dot_s"] + classes["comm_s"]
    return {
        "total_device_s": round(total, 9),
        "attributed_s": round(attributed / 1e9, 9),
        "unattributed_s": round((total_ns - attributed) / 1e9, 9),
        "fraction_attributed": round(attributed / total_ns, 6)
        if total_ns else 1.0,
        "spans": spans,
        "unattributed": {
            k: round(v, 9)
            for k, v in sorted(unattributed.items(),
                               key=lambda kv: -kv[1])[:max_ops]},
        "op_classes": {**{k: round(v, 9) for k, v in classes.items()},
                       "other_s": round(total - named, 9)},
        "window_s": round((merged[-1][1] - merged[0][0]) / 1e9, 9),
        "idle_gaps": {k: round(v, 9) for k, v in
                      sorted(gaps.items(), key=lambda kv: -kv[1])},
        "lanes": [{"process": plane, "thread": _OPS_LINE,
                   "events": len(ops), "busy_s": round(total, 9)}],
    }


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
        "executions": summary.get("executions"),
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
    ``prof_summary.json`` / ``op_names.json``. Returns bytes
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
