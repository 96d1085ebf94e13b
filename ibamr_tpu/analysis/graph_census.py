"""Census primitives over lowered artifacts (jaxpr + compiled HLO).

These generalize the one-off censuses that grew inside
``tools/hlo_cost_audit.py`` (scatter census, FFT-primitive census,
dot-operand census) into reusable pure functions, and add the three the
contract gate needs that the bench artifact never measured:

- :func:`convert_census` — dtype-promotion census: every
  ``convert_element_type`` by (src -> dst) pair, with the two smells
  flagged explicitly: *f64 widenings* (a narrower float silently
  upcast to f64 — the classic x64-leak that doubles HBM traffic on
  chip) and *round-trip chains* (x -> wider -> x, two converts that
  compute nothing; the deliberate mixed-precision rounding pattern
  f32 -> bf16 -> f32 goes through a NARROWER dtype and is not
  flagged);
- :func:`host_transfer_census` — callback/infeed/outfeed primitives,
  split by whether they sit inside a ``scan``/``while`` body, where
  each one forces a per-iteration device->host round trip that
  serializes the whole chunk;
- :func:`donation_census` — parses the compiled module's
  ``input_output_alias`` table, so ``donate_argnums`` stops being a
  *request* and becomes a *verified* property of the executable.

Everything here is backend-independent and pure: callers hand in a
jaxpr (``jax.make_jaxpr``) or optimized-HLO text
(``compiled.as_text()``); nothing in this module forces a backend,
spawns processes, or touches the registry. ``tools/hlo_cost_audit.py``
(the bench artifact) and ``tools/graph_audit.py`` (the CI gate) both
consume these functions, so the two can never disagree on counting
rules.
"""

from __future__ import annotations

import re
from typing import Iterator, Optional, Tuple

__all__ = [
    "iter_eqns", "fft_census", "dot_census", "convert_census",
    "host_transfer_census", "collective_census", "overlap_census",
    "structural_overlap_census",
    "hlo_op_counts", "op_class_counts",
    "donation_census", "graph_census", "budget_metrics",
]


# ---------------------------------------------------------------------------
# jaxpr walking
# ---------------------------------------------------------------------------

# primitives whose sub-jaxpr executes per loop iteration: anything
# found inside counts as "inside a scan body" for the host-transfer
# budget (a callback there fires every step, not once per chunk)
_LOOP_PRIMS = {"scan", "while"}

# callback-family primitives: each is a host round trip at run time
# (debug_callback covers jax.debug.print too; infeed/outfeed are the
# raw transfer prims some jax versions lower callbacks to)
_HOST_PRIMS = {"debug_callback", "pure_callback", "io_callback",
               "callback", "outside_call", "infeed", "outfeed"}


def _sub_jaxprs(params) -> Iterator[Tuple[str, object]]:
    """(param_name, jaxpr) for every sub-jaxpr in an eqn's params —
    ClosedJaxpr, raw Jaxpr, or tuples/lists of either (cond branches)."""
    for name, v in params.items():
        vs = v if isinstance(v, (tuple, list)) else (v,)
        for w in vs:
            if hasattr(w, "jaxpr"):          # ClosedJaxpr
                yield name, w.jaxpr
            elif hasattr(w, "eqns"):         # raw Jaxpr
                yield name, w


def iter_eqns(jaxpr, in_loop: bool = False):
    """Yield ``(eqn, in_loop)`` for every equation reachable from
    ``jaxpr``, recursing into sub-jaxprs. ``in_loop`` is True once the
    walk has entered the body of a ``scan``/``while`` (the body runs
    per iteration; a ``cond`` branch or inner ``pjit`` inherits its
    enclosing context)."""
    for eqn in jaxpr.eqns:
        yield eqn, in_loop
        child_in_loop = in_loop or eqn.primitive.name in _LOOP_PRIMS
        for _, sub in _sub_jaxprs(eqn.params):
            yield from iter_eqns(sub, child_in_loop)


# ---------------------------------------------------------------------------
# jaxpr censuses
# ---------------------------------------------------------------------------

def fft_census(jaxpr, max_transforms: int = 32) -> dict:
    """Batched-FFT call count + operand bytes at the jaxpr primitive
    level. Primitive-level on purpose: the CPU backend lowers
    ``lax.fft`` to a ducc custom-call an HLO-text census cannot see,
    while the primitive count is exactly the number of batched FFT
    calls the TPU backend will also issue."""
    out = {"fft_ops": 0, "fft_bytes": 0, "fft_transforms": []}
    for eqn, _ in iter_eqns(jaxpr):
        if eqn.primitive.name != "fft":
            continue
        iv, ov = eqn.invars[0].aval, eqn.outvars[0].aval
        ib, ob = (iv.size * iv.dtype.itemsize,
                  ov.size * ov.dtype.itemsize)
        out["fft_ops"] += 1
        out["fft_bytes"] += ib + ob
        if len(out["fft_transforms"]) < max_transforms:
            out["fft_transforms"].append({
                "kind": str(eqn.params.get("fft_type")),
                "in_shape": list(iv.shape),
                "in_bytes": ib, "out_bytes": ob})
    return out


def dot_census(jaxpr) -> dict:
    """Operand/output bytes + FLOPs of every ``dot_general`` — the
    (B,cap,P)/(B,cap,nz) contraction operands are the transfer engines'
    claimed dominant traffic, and their traced dtypes/shapes show
    exactly what occupancy packing and bf16 compression do to them."""
    out = {"dot_lhs_bytes": 0, "dot_rhs_bytes": 0, "dot_out_bytes": 0,
           "dot_count": 0, "dot_flops": 0}
    for eqn, _ in iter_eqns(jaxpr):
        if eqn.primitive.name != "dot_general":
            continue
        lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
        outv = eqn.outvars[0].aval
        out["dot_lhs_bytes"] += lhs.size * lhs.dtype.itemsize
        out["dot_rhs_bytes"] += rhs.size * rhs.dtype.itemsize
        out["dot_out_bytes"] += outv.size * outv.dtype.itemsize
        contracted = 1
        for ax in eqn.params["dimension_numbers"][0][0]:
            contracted *= lhs.shape[ax]
        out["dot_flops"] += 2 * outv.size * contracted
        out["dot_count"] += 1
    return out


def scatter_gather_census(jaxpr) -> dict:
    """Scatter/gather counts at the jaxpr PRIMITIVE level.

    Primitive-level on purpose (like :func:`fft_census`): the XLA CPU
    scatter expander rewrites small scatters into while-loops of
    dynamic-update-slices BEFORE the optimized HLO, so an HLO-text
    scatter budget audited on the CPU backend would be vacuously zero.
    The primitive count is what the TPU backend's serial scatter
    penalty is charged on — the observable the zero-scatter engines
    exist to eliminate."""
    out = {"scatter_prims": 0, "gather_prims": 0}
    for eqn, _ in iter_eqns(jaxpr):
        name = eqn.primitive.name
        if name.startswith("scatter"):
            out["scatter_prims"] += 1
        elif name == "gather":
            out["gather_prims"] += 1
    return out


def _is_float(dtype) -> bool:
    return dtype.kind == "f" or dtype.name == "bfloat16"


def _width(dtype) -> int:
    return int(dtype.itemsize)


def convert_census(jaxpr) -> dict:
    """Dtype-promotion census over every ``convert_element_type``.

    Returns::

        {"convert_ops": total count,
         "convert_pairs": {"f32->f64": n, ...},
         "f64_widenings": count of float converts widening INTO f64,
         "weak_widenings": of those, the weak-typed ones (a Python
                           scalar/np default leaked into the graph),
         "roundtrip_chains": count of x -> wider -> x chains,
         "widening_sites": [up to 16 {src, dst, shape} records]}

    The deliberate mixed-precision *rounding* pattern
    (``x.astype(bf16).astype(f32)`` — through a NARROWER dtype) is not
    flagged; ``bf16 -> f32 -> bf16`` (through a WIDER dtype, two
    converts that compute nothing) is.
    """
    pairs: dict = {}
    f64_widenings = 0
    weak_widenings = 0
    roundtrips = 0
    sites = []
    # var id -> source dtype of the convert that produced it (chain
    # detection: convert(convert(x)) landing back on x's dtype through
    # a wider intermediate)
    produced_from: dict = {}
    for eqn, _ in iter_eqns(jaxpr):
        if eqn.primitive.name != "convert_element_type":
            continue
        src = eqn.invars[0].aval.dtype
        dst = eqn.outvars[0].aval.dtype
        key = f"{src.name}->{dst.name}"
        pairs[key] = pairs.get(key, 0) + 1
        if _is_float(src) and _is_float(dst) and _width(dst) > _width(src):
            if dst.name == "float64":
                f64_widenings += 1
                if bool(eqn.params.get("weak_type", False)):
                    weak_widenings += 1
                if len(sites) < 16:
                    sites.append({"src": src.name, "dst": dst.name,
                                  "shape": list(eqn.invars[0].aval.shape)})
        grand_src = produced_from.get(id(eqn.invars[0]))
        if (grand_src is not None and grand_src == dst
                and _width(src) > _width(dst)):
            # x -> wider -> x: the wider hop computed nothing
            roundtrips += 1
        produced_from[id(eqn.outvars[0])] = src
    return {"convert_ops": sum(pairs.values()),
            "convert_pairs": pairs,
            "f64_widenings": f64_widenings,
            "weak_widenings": weak_widenings,
            "roundtrip_chains": roundtrips,
            "widening_sites": sites}


def host_transfer_census(jaxpr) -> dict:
    """Callback/infeed/outfeed census, split by loop context.

    ``in_scan`` is the budgeted number: a callback inside a
    ``scan``/``while`` body fires once per ITERATION — a per-step
    device->host sync that serializes the chunk the driver exists to
    keep device-resident. Gated debug paths (pad-inertness checks,
    ``record_stats=True`` solve taps) are trace-time gated, so they
    contribute zero here unless someone turns them on in the artifact
    being audited."""
    out = {"host_transfers": 0, "host_transfers_in_scan": 0,
           "host_transfer_prims": {}}
    for eqn, in_loop in iter_eqns(jaxpr):
        if eqn.primitive.name not in _HOST_PRIMS:
            continue
        out["host_transfers"] += 1
        if in_loop:
            out["host_transfers_in_scan"] += 1
        k = eqn.primitive.name + (":scan" if in_loop else "")
        out["host_transfer_prims"][k] = \
            out["host_transfer_prims"].get(k, 0) + 1
    return out


# the explicit cross-device primitives jax traces into a jaxpr.
# psum appears only where the program ASKS for it (shard_map bodies,
# pmapped code); the psums GSPMD inserts to implement a sharded jnp
# reduction materialize at partitioning time and are visible only in
# HLO (the collective_ops op-class and :func:`overlap_census`).
_COLLECTIVE_PRIMS = ("ppermute", "psum", "all_gather", "all_to_all",
                     "pbroadcast")


def collective_census(jaxpr) -> dict:
    """Per-primitive count + bytes census of the explicit collectives.

    Primitive-level on purpose (the :func:`scatter_gather_census`
    argument): backend partitioners rewrite, fuse, and batch
    collectives before optimized HLO — CPU lowers them synchronously,
    TPU splits them into start/done pairs — while the jaxpr primitive
    count is exactly the number of cross-device exchanges the program
    *asked* for, identical on every backend.

    Bytes are the sum of each collective's OUTPUT aval sizes — the
    per-shard payload a device materializes from its peers per
    execution (for ``psum``/``ppermute``/``pbroadcast`` this equals
    the input payload; for ``all_gather`` it is the gathered result,
    ``axis_size`` times the input). Inside a ``shard_map`` body avals
    are per-shard, so the numbers read as per-device traffic.
    """
    out = {"collective_prims": 0, "collective_bytes": 0}
    for p in _COLLECTIVE_PRIMS:
        out[f"{p}_prims"] = 0
        out[f"{p}_bytes"] = 0
    for eqn, _ in iter_eqns(jaxpr):
        name = eqn.primitive.name
        if name not in _COLLECTIVE_PRIMS:
            continue
        nbytes = sum(v.aval.size * v.aval.dtype.itemsize
                     for v in eqn.outvars)
        out[f"{name}_prims"] += 1
        out[f"{name}_bytes"] += nbytes
        out["collective_prims"] += 1
        out["collective_bytes"] += nbytes
    return out


# the data-MOVING collectives for the structural pipeline census.
# pbroadcast is excluded on purpose: it is shard_map's replication
# annotation, lowered to nothing on device — its hundreds of sites
# would swamp the fraction the pipelined exchanges actually move.
_MOVING_COLLECTIVE_PRIMS = ("ppermute", "psum", "all_gather",
                            "all_to_all")

# primitives that are pure layout/bookkeeping, not schedulable
# compute: a window containing only these hides no link latency
_LAYOUT_PRIMS = {"reshape", "broadcast_in_dim", "squeeze", "transpose",
                 "convert_element_type", "copy", "slice",
                 "sharding_constraint", "pbroadcast"}


def structural_overlap_census(jaxpr, max_sites: int = 16) -> dict:
    """Structural hidden/unhidden census at the jaxpr level.

    The HLO :func:`overlap_census` sees only what one backend's
    scheduler DID (the CPU backend lowers every collective
    synchronously, so it reports zero pairs on the CI mesh); this
    census measures what the traced program makes POSSIBLE, identically
    on every backend: a data-moving collective (`ppermute`/`psum`/
    `all_gather`/`all_to_all` — NOT `pbroadcast`, a no-traffic
    replication annotation) counts as **hidden** when at least one
    independent schedulable compute equation sits between its issue
    site and its first consumer in trace order. Such a window is
    exactly what lets a latency-hiding scheduler keep the transfer in
    flight behind real work; an empty (or layout/collective-only)
    window pins the exchange to the critical path on every backend.

    Windows are computed per jaxpr body (trace order within a body is
    the schedulable order; a collective whose result is a body OUTPUT
    gets the remainder of the body as its window). Returns::

        {"structural_collectives": data-moving collectives seen,
         "hidden_collectives": with >=1 compute eqn in the window,
         "unhidden_collectives": with an empty/bookkeeping-only window,
         "hidden_fraction": int percent (100 when no collectives),
         "unhidden_sites": [up to max_sites {prim, window_eqns}]}
    """
    out = {"structural_collectives": 0, "hidden_collectives": 0,
           "unhidden_collectives": 0, "unhidden_sites": []}

    def walk(jx):
        eqns = list(jx.eqns)
        for i, eqn in enumerate(eqns):
            for _, sub in _sub_jaxprs(eqn.params):
                walk(sub)
            name = eqn.primitive.name
            if name not in _MOVING_COLLECTIVE_PRIMS:
                continue
            produced = {id(v) for v in eqn.outvars}
            first_use = len(eqns)
            for j in range(i + 1, len(eqns)):
                if any(id(v) in produced for v in eqns[j].invars):
                    first_use = j
                    break
            compute = 0
            for k in range(i + 1, first_use):
                kn = eqns[k].primitive.name
                if (kn in _LAYOUT_PRIMS
                        or kn in _MOVING_COLLECTIVE_PRIMS):
                    continue
                compute += 1
            out["structural_collectives"] += 1
            if compute:
                out["hidden_collectives"] += 1
            else:
                out["unhidden_collectives"] += 1
                if len(out["unhidden_sites"]) < max_sites:
                    out["unhidden_sites"].append(
                        {"prim": name,
                         "window_eqns": first_use - i - 1})

    walk(jaxpr)
    tot = out["structural_collectives"]
    out["hidden_fraction"] = (
        100 * out["hidden_collectives"] // tot if tot else 100)
    return out


# ---------------------------------------------------------------------------
# HLO-text censuses
# ---------------------------------------------------------------------------

def hlo_op_counts(text: str) -> dict:
    """Opcode census of an optimized-HLO dump (``compiled.as_text()``).

    Quoted metadata (op_name/source strings) can contain anything,
    including op-like tokens — strip quoted spans per line BEFORE
    matching, then take the first ``opcode(`` token on the RHS of each
    ``=`` assignment. Backend-independent: the census runs on whatever
    module the caller compiled. tests/test_forces_hlo.py uses it to pin
    the zero-scatter force-assembly guarantee.
    """
    counts: dict = {}
    for line in text.splitlines():
        if "=" not in line:
            continue
        rhs = re.sub(r'"[^"]*"', '""', line.split("=", 1)[1])
        m = re.search(r"\b([a-z][a-z0-9_.-]*)\s*\(", rhs)
        if m:
            counts[m.group(1)] = counts.get(m.group(1), 0) + 1
    return counts


def indexed_op_counts(text: str, n_index: int, scope: str) -> dict:
    """Of an optimized-HLO dump, the gathers and scatters under the
    named scope ``scope`` and outside every ``cond`` branch whose INDEX
    operand has ``n_index`` rows: ``{"gather": n, "scatter": n}``.
    With ``n_index`` the marker count this counts the moves of
    per-marker values between marker order and slot order
    (tests/test_transfer_marshal.py pins one per velocity transfer of
    the packed engine; the overflow fallbacks live in ``cond``
    branches)."""
    shape_of = dict(re.findall(r"%(\S+) = (\w+\[[\d,]*\])", text))
    found = {"gather": 0, "scatter": 0}
    for kind, operands, op_name in re.findall(
            r" (gather|scatter)\(([^)]*)\).*op_name=\"([^\"]*)\"", text):
        if f"/{scope}/" not in op_name or "/cond/" in op_name:
            continue
        index = operands.split(",")[1].strip().lstrip("%")
        rows = re.search(r"\[(\d+)", shape_of[index])
        if rows and int(rows.group(1)) == n_index:
            found[kind] += 1
    return found


# opcode prefix -> budget class. ``fusion``/arithmetic opcodes are
# deliberately unclassified: their counts are backend fusion decisions,
# not graph contracts.
_OP_CLASSES = (
    ("scatter", "scatter_ops"),
    ("gather", "gather_ops"),
    ("all-gather", "collective_ops"),
    ("all-reduce", "collective_ops"),
    ("all-to-all", "collective_ops"),
    ("collective-permute", "collective_ops"),
    ("custom-call", "custom_calls"),
    ("convert", "convert_hlo_ops"),
    ("fft", "fft_hlo_ops"),
)


def op_class_counts(ops) -> dict:
    """Bucket an opcode census (:func:`hlo_op_counts` output, or raw
    HLO text) into the contract classes. ``gather`` excludes
    ``all-gather`` (a collective, not an addressing op)."""
    if isinstance(ops, str):
        ops = hlo_op_counts(ops)
    out = {cls: 0 for _, cls in _OP_CLASSES}
    for op, n in ops.items():
        # longest-prefix match so "all-gather" never lands in gather_ops
        best = None
        for prefix, cls in _OP_CLASSES:
            if op.startswith(prefix):
                if best is None or len(prefix) > len(best[0]):
                    best = (prefix, cls)
        if best is not None:
            out[best[1]] += n
    return out


# async collective machinery in optimized HLO: `<op>-start` issues the
# transfer, the matching `<op>-done` blocks on it; XLA also wraps some
# collectives in generic `async-start`/`async-done` pairs.
_ASYNC_START_RE = re.compile(
    r"^(all-gather|all-reduce|all-to-all|collective-permute|"
    r"reduce-scatter|collective-broadcast|copy|send|recv|async)-start$")
_SYNC_COLLECTIVE_RE = re.compile(
    r"^(all-gather|all-reduce|all-to-all|collective-permute|"
    r"reduce-scatter|collective-broadcast)(\.|$)")
# opcodes that are bookkeeping, not schedulable compute: having only
# these between a start and its done hides nothing
_STRUCTURAL_OPS = {"parameter", "constant", "tuple", "get-tuple-element",
                   "bitcast", "bitcast-convert", "after-all", "domain"}

_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%([\w.-]+)\s*=")


def overlap_census(hlo_text: str) -> dict:
    """Census of collective/compute overlap in optimized HLO.

    Pairs every ``<collective>-start`` with the ``*-done`` that
    consumes it and counts the schedulable compute ops the scheduler
    placed BETWEEN them — the structural observable for async halo
    exchange: a pair with zero compute in the window (``unhidden``)
    pays its full link latency on the critical path. Synchronous
    collective ops (how the CPU backend — and an unscheduled TPU
    module — emit them) can never overlap and are counted separately
    as ``collective_sync_ops``.

    Returns::

        {"overlap_pairs": start/done pairs found,
         "overlap_hidden": pairs with >=1 compute op in the window,
         "overlap_unhidden": pairs with an empty window,
         "collective_sync_ops": synchronous collective ops,
         "overlap_sites": [up to 16 {op, compute_between}]}
    """
    # (line_idx, def_name, opcode) for every op-defining line, in
    # program order (HLO text lists each computation's ops in order)
    defs = []
    for idx, line in enumerate(hlo_text.splitlines()):
        if "=" not in line:
            continue
        dm = _DEF_RE.match(line)
        rhs = re.sub(r'"[^"]*"', '""', line.split("=", 1)[1])
        om = re.search(r"\b([a-z][a-z0-9_.-]*)\s*\(", rhs)
        if not (dm and om):
            continue
        defs.append((idx, dm.group(1), om.group(1), rhs))

    out = {"overlap_pairs": 0, "overlap_hidden": 0,
           "overlap_unhidden": 0, "collective_sync_ops": 0,
           "overlap_sites": []}
    # strip the .N instance suffix HLO appends to repeated opcodes
    base = lambda op: re.sub(r"\.\d+$", "", op)  # noqa: E731
    starts = {}          # def name -> (position in defs, opcode)
    for pos, (idx, name, op, rhs) in enumerate(defs):
        b = base(op)
        if _ASYNC_START_RE.match(b):
            starts[name] = (pos, op)
        elif b.endswith("-done"):
            # which start does this done consume?
            used = [s for s in starts
                    if re.search(r"%" + re.escape(s) + r"\b", rhs)]
            if not used:
                continue
            sname = used[0]
            spos, sop = starts.pop(sname)
            compute = 0
            for _, _, iop, _ in defs[spos + 1:pos]:
                ib = base(iop)
                if (ib in _STRUCTURAL_OPS or ib.endswith("-start")
                        or ib.endswith("-done")):
                    continue
                compute += 1
            out["overlap_pairs"] += 1
            if compute:
                out["overlap_hidden"] += 1
            else:
                out["overlap_unhidden"] += 1
            if len(out["overlap_sites"]) < 16:
                out["overlap_sites"].append(
                    {"op": sop, "compute_between": compute})
        elif _SYNC_COLLECTIVE_RE.match(b):
            out["collective_sync_ops"] += 1
    return out


_ALIAS_RE = re.compile(r"input_output_alias=\{((?:[^{}]|\{[^{}]*\})*)\}")
_ALIAS_ENTRY_RE = re.compile(
    r"\{[^{}]*\}\s*:\s*\(\s*(\d+)\s*,\s*\{[^{}]*\}\s*,\s*"
    r"(may-alias|must-alias)\s*\)")


def donation_census(hlo_text: str) -> dict:
    """Parse the compiled module's ``input_output_alias`` table.

    ``jax.jit(..., donate_argnums=...)`` is a *request*; whether XLA
    actually aliased each donated buffer to an output is recorded in
    the module header. Returns ``{"donated_args": <distinct aliased
    parameter count>, "donation_entries": <alias-table entries>}`` —
    the verified-donation observable the budgets pin (before this
    census, donation was requested everywhere and verified nowhere)."""
    m = _ALIAS_RE.search(hlo_text)
    if not m:
        return {"donated_args": 0, "donation_entries": 0}
    entries = _ALIAS_ENTRY_RE.findall(m.group(1))
    return {"donated_args": len({int(p) for p, _ in entries}),
            "donation_entries": len(entries)}


# ---------------------------------------------------------------------------
# the one-call composite census
# ---------------------------------------------------------------------------

def graph_census(fn, args, donate_argnums=()) -> dict:
    """Full census of one artifact: trace (jaxpr censuses) + compile on
    the CURRENT backend (HLO censuses + donation audit). Pure apart
    from the compile itself; callers choose the backend (the CI gate
    runs under ``JAX_PLATFORMS=cpu`` child processes — same HLO module
    structure as TPU, per tools/hlo_cost_audit.py)."""
    import jax

    jaxpr = jax.make_jaxpr(fn)(*args)
    jfn = jax.jit(fn, donate_argnums=tuple(donate_argnums)) \
        if donate_argnums else jax.jit(fn)
    compiled = jfn.lower(*args).compile()
    text = compiled.as_text()
    ops = hlo_op_counts(text)
    out = {}
    out.update(op_class_counts(ops))
    out.update(scatter_gather_census(jaxpr.jaxpr))
    out.update(fft_census(jaxpr.jaxpr))
    out.update(dot_census(jaxpr.jaxpr))
    out.update(convert_census(jaxpr.jaxpr))
    out.update(host_transfer_census(jaxpr.jaxpr))
    out.update(collective_census(jaxpr.jaxpr))
    out.update(structural_overlap_census(jaxpr.jaxpr))
    out.update(overlap_census(text))
    out.update(donation_census(text))
    out["hlo_ops_total"] = sum(ops.values())
    return out


# the flat metrics a budget may pin. "max" metrics regress UP;
# "donated_args" is the one "min" metric (regresses DOWN — donation
# silently dropped by a refactor)
BUDGET_MAX_METRICS = (
    "scatter_ops", "scatter_prims", "fft_ops",
    "host_transfers_in_scan", "host_transfers", "f64_widenings",
    "weak_widenings", "roundtrip_chains", "convert_ops", "gather_ops",
    "custom_calls", "collective_ops", "dot_count",
    # PR 15: the comm layer. Per-primitive collective counts + bytes
    # (jaxpr level, backend-independent) and the HLO overlap census —
    # `overlap_unhidden` is the structural pin for async halo
    # exchange: an unhidden start/done pair pays full link latency.
    "collective_prims", "collective_bytes",
    "ppermute_prims", "ppermute_bytes", "psum_prims", "psum_bytes",
    "all_gather_prims", "all_gather_bytes",
    "all_to_all_prims", "all_to_all_bytes",
    "pbroadcast_prims", "pbroadcast_bytes",
    "overlap_pairs", "overlap_unhidden", "collective_sync_ops",
    # PR 16: the structural pipeline census — an unhidden data-moving
    # collective (empty issue->first-consumer window) serializes on
    # every backend, so its count is a ceiling.
    "unhidden_collectives",
)
# "min" metrics regress DOWN: donation silently dropped by a refactor,
# or a double-buffered pipeline collapsing back to a sync chain
# (hidden_fraction is the int percent of data-moving collectives with
# compute in their issue window — see structural_overlap_census).
BUDGET_MIN_METRICS = ("donated_args", "hidden_fraction")


def budget_metrics(census: dict) -> dict:
    """The budget-comparable slice of a :func:`graph_census` result."""
    keys = BUDGET_MAX_METRICS + BUDGET_MIN_METRICS
    return {k: int(census[k]) for k in keys if k in census}
