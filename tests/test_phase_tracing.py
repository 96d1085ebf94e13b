"""Phase names inside the compiled step, host spans in the run loop, and
the read-back that joins both to a chip trace (PR 25).

The device side is ``jax.named_scope`` only (metadata): the tests lower
the ex4 chunk program and read the names from its compiled text, hold
the benchmark's op classes fixed under them, and drive
``obs/deviceprof.py`` and the new ``perfbench/metrics`` readers on a
recorded chip trace and on hand-made inputs.
"""

import importlib.util
import json
import os
import re
import time

import jax
import jax.numpy as jnp
import pytest

from ibamr_tpu import obs
from ibamr_tpu.models.shell3d import build_shell_example
from ibamr_tpu.obs import deviceprof
from ibamr_tpu.utils.hierarchy_driver import HierarchyDriver, RunConfig
from ibamr_tpu.utils import parse_input_string
from perfbench import harness, inputfile, obsread, tracereduce

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RECORDED = os.path.join(ROOT, "perfbench", "tests", "data",
                        "trace_tpu_v5_lite_128.json")
PHASE_NAMES = ["/".join(seq) for seq in deviceprof.PHASES]


def _reader(metric):
    spec = importlib.util.spec_from_file_location(
        "metric_" + metric.replace(".", "_"),
        os.path.join(ROOT, "perfbench", "metrics", metric + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _per_layer():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return {m["name"]: m for m in json.load(f)["per_layer"]}


# ---------------------------------------------------------------------------
# (1) the compiled chunk program carries the phases; classes do not move
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def chunk_op_names():
    """``{instruction: op_name}`` of the ex4 chunk program at 16^3 on
    the packed engine, read the way a metric reader reads it: from the
    program the driver registered, not from a handle on the driver."""
    integ, state = build_shell_example(n_cells=16, n_lat=8, n_lon=8,
                                       use_fast_interaction="packed")
    before = len(obs.programs())
    drv = HierarchyDriver(integ, RunConfig(dt=1e-4, num_steps=2,
                                           health_interval=2))
    drv.run(state)
    progs = obs.programs()[before:]
    assert [p["name"] for p in progs] == ["driver/chunk[2]"]
    # shapes only: no device buffer is kept alive by the registry
    assert not any(isinstance(l, jax.Array) for l in
                   jax.tree_util.tree_leaves(progs[0]["args"]))
    return deviceprof.programs_names(progs)[0]


# the ConstraintIB strategy's own phases are in no shell program
# (tests/test_falling_sphere.py finds them in its chunk), nor are the
# open-boundary solve's (tests/test_dfg3d.py finds them in its step)
SHELL_PHASES = [p for p in PHASE_NAMES
                if not p.startswith(("constraint/", "fluid/krylov"))
                and p != "fluid/reproject"]


@pytest.mark.parametrize("phase", SHELL_PHASES)
def test_chunk_program_carries_phase(chunk_op_names, phase):
    found = set(deviceprof.phase_map(chunk_op_names).values())
    assert phase in found


def test_repack_scope_only_in_the_false_branch(chunk_op_names):
    repack = [v for v in chunk_op_names.values() if "/repack/" in v]
    assert repack
    assert all("ib/refresh/cond/branch_0_fun/repack/" in v
               for v in repack)


def test_scopes_move_no_benchmark_class(chunk_op_names):
    """Every instruction's class by the benchmark's own rule is the one
    it has with the phase components taken out of its ``op_name``."""
    scopes = {s for seq in deviceprof.PHASES for s in seq} | {
        "pack", "overlap_add"}
    strip = re.compile("(?:^|(?<=/))(" + "|".join(
        sorted(map(re.escape, scopes), key=len, reverse=True)) + ")/")
    moved = {}
    for inst, op_name in chunk_op_names.items():
        bare = strip.sub("", strip.sub("", op_name))
        assert not any(f"/{s}/" in f"/{bare}/" for s in scopes), bare
        a = tracereduce.classify(inst, {inst: op_name})
        b = tracereduce.classify(inst, {inst: bare})
        if a != b:
            moved[inst] = (op_name, a, b)
    assert not moved
    for seq in deviceprof.PHASES:
        for s in seq:
            assert tracereduce.classify("x", {"x": s + "/add"}) == "other"


HAND_HLO = """
HloModule jit_chunk

%fused_computation.1 (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0} parameter(0)
  ROOT %add.9 = f32[8]{0} add(%param_0.1, %param_0.1)
}

ENTRY %main (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0:T(1024)} parameter(0), metadata={op_name="state.X"}
  %copy.1 = f32[8]{0:T(1024)S(1)} copy(%p0), metadata={op_name="jit(chunk)/while/body/closed_call"}
  %fusion.1 = f32[8]{0} fusion(%copy.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(chunk)/while/body/closed_call/ib/prep/pack/sort"}
  %copy-start.2 = (f32[8]{0}, f32[8]{0}, u32[]{:S(2)}) copy-start(%fusion.1), metadata={op_name="jit(chunk)/while/body/closed_call/ib/prep/pack/sort"}
  %copy-done.2 = f32[8]{0:T(1024)} copy-done(%copy-start.2)
  %fusion.2 = f32[8]{0} fusion(%copy-done.2), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(chunk)/while/body/closed_call/add"}
  %reshape.3 = f32[8]{0} reshape(%fusion.2), metadata={op_name="jit(chunk)/while/body/closed_call/jit(<lambda>)"}
  %fusion.4 = f32[8]{0} fusion(%reshape.3), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(chunk)/while/body/closed_call/fluid/mul"}
  %while.5 = f32[8]{0} while(%fusion.4), condition=%c, body=%b, metadata={op_name="jit(chunk)/while"}
  ROOT %copy.6 = f32[8]{0} copy(%while.5)
}
"""


def test_compiler_placed_moves_take_the_phase_of_what_they_move():
    op_names, phases = deviceprof.names_from_hlo(HAND_HLO)
    assert op_names == tracereduce.op_names_from_hlo(HAND_HLO)
    assert phases == {
        "fusion.1": "ib/prep", "copy-start.2": "ib/prep",
        "fusion.4": "fluid",
        "copy-done.2": "ib/prep",   # no metadata: from its operand
        "copy.1": "ib/prep",        # a copy of the state: to its user
        "reshape.3": "fluid"}       # a relayout: to its user
    # compute outside every scope, control flow, and what only touches
    # them stay unphased
    assert not {"fusion.2", "while.5", "copy.6", "p0", "add.9"} \
        & set(phases)


def test_phase_of_takes_the_deepest():
    p = deviceprof.phase_of
    assert p("jit(chunk)/while/body/closed_call/ib/refresh/cond/"
             "branch_0_fun/repack/pack/sub") == "ib/refresh/repack"
    assert p("jit(chunk)/while/body/closed_call/ib/refresh/"
             "jit(_take)/gather") == "ib/refresh"
    assert p("jit(chunk)/fluid/jit(step)/transforms/jit(fft)/fft") \
        == "fluid/transforms"
    assert p("jit(chunk)/fluid_solve/transforms/fft") is None
    assert p("jit(chunk)/while/body/closed_call/add") is None


# ---------------------------------------------------------------------------
# (2) spans of a run with cadences
# ---------------------------------------------------------------------------

def test_run_loop_spans_and_ring():
    integ, state = build_shell_example(n_cells=8, n_lat=4, n_lon=4)
    obs.clear_spans()
    drv = HierarchyDriver(
        integ, RunConfig(dt=1e-4, num_steps=6, health_interval=2,
                         viz_dump_interval=4, restart_interval=6),
        metrics_fn=lambda s, k: {"k": k}, viz_fn=lambda s, k: None,
        checkpoint_fn=lambda s, k: None)
    drv.run(state)
    ring = obs.spans()
    by_id = {s["id"]: s for s in ring}
    chunks = [s for s in ring if s["path"] == "driver/chunk"]
    assert [(c["attrs"]["step"], c["attrs"]["chunk"]) for c in chunks] \
        == [(0, 0), (2, 1), (4, 2)]
    for c in chunks:
        kids = [s for s in ring if s["parent"] == c["id"]
                and not s["name"].startswith("compile/")]
        # ``refresh``: the carried chunk's counts, closed after the sync
        assert [k["name"] for k in kids] == ["dispatch", "sync", "refresh"]
        assert [k["path"] for k in kids] == ["driver/chunk/dispatch",
                                             "driver/chunk/sync",
                                             "driver/chunk/refresh"]
        for k in kids:
            assert k["attrs"]["chunk"] == c["attrs"]["chunk"]
            assert c["t0"] <= k["t0"] <= k["t1"] <= c["t1"]
    first = [s["attrs"]["first_call"] for s in ring
             if s["name"] == "dispatch"]
    assert first == [True, False, False]
    # the first call's compiles are children of its dispatch
    comp = [s for s in ring if s["name"] == "compile/backend"]
    assert comp and all(by_id[s["parent"]]["name"] == "dispatch"
                        for s in comp)

    def at(name):
        return [(s["attrs"]["step"], s["attrs"]["chunk"])
                for s in ring if s["name"] == name]
    assert at("driver/metrics_fn") == [(2, 0), (4, 1), (6, 2)]
    # at its cadence, and once more for the final configuration
    assert at("driver/viz_fn") == [(4, 1), (6, 2)]
    assert at("driver/checkpoint_fn") == [(6, 2)]
    assert all(s["parent"] is None for s in ring
               if s["name"].startswith("driver/"))
    # bounded: the oldest drop first
    for i in range(obs.bus.SPAN_RING_SIZE + 5):
        with obs.span("filler", i=i):
            pass
    ring = obs.spans()
    assert len(ring) == obs.bus.SPAN_RING_SIZE
    assert ring[0]["attrs"]["i"] == 5 and ring[-1]["attrs"]["i"] \
        == obs.bus.SPAN_RING_SIZE + 4
    obs.clear_spans()


def test_checkpoint_spans(tmp_path):
    from ibamr_tpu.utils.checkpoint import (restore_checkpoint,
                                            save_checkpoint)

    obs.clear_spans()
    state = {"a": jnp.arange(8.0)}
    save_checkpoint(str(tmp_path), state, 3)
    restore_checkpoint(str(tmp_path), state, step=3)
    assert [(s["path"], s["attrs"]["step"]) for s in obs.spans()
            if s["name"].startswith("checkpoint/")] == [
        ("checkpoint/fetch", 3), ("checkpoint/commit", 3),
        ("checkpoint/restore", 3)]


def test_span_without_ledger_reads_no_program_text(monkeypatch):
    """A plain run never reads a compiled program's text."""
    def boom(*a, **k):
        raise AssertionError("compiled text read in a plain run")
    monkeypatch.setattr(deviceprof, "program_names", boom)
    integ, state = build_shell_example(n_cells=8, n_lat=4, n_lon=4)
    HierarchyDriver(integ, RunConfig(dt=1e-4, num_steps=2,
                                     health_interval=2)).run(state)


# ---------------------------------------------------------------------------
# (3) deviceprof on the recorded chip trace
# ---------------------------------------------------------------------------

def _recorded_phases(op_names):
    """A hand-made instruction -> phase map for the recorded trace (it
    was taken before the scopes existed): by what the op computes."""
    rule = (("fft", "fluid/transforms"), ("gather", "ib/interp"),
            ("scatter", "ib/spread"), ("dot_general", "ib/interp"),
            ("sort", "ib/prep"))
    out = {}
    for inst, op_name in op_names.items():
        for key, phase in rule:
            if key in op_name:
                out[inst] = phase
                break
    return out


def test_deviceprof_on_recorded_chip_trace():
    trace = deviceprof.load_planes(RECORDED)
    phases = _recorded_phases(trace["op_names"])
    s = deviceprof.attribute_planes(trace, phases, trace["op_names"],
                                    span_re=re.compile(r"^bench/"))
    s.update(schema=deviceprof.PROF_SCHEMA)
    assert deviceprof.validate_summary(s) == []
    # nothing dropped: the total is the benchmark's own busy time
    ref = tracereduce.reduce(trace, op_names=trace["op_names"])
    assert s["total_device_s"] == pytest.approx(ref["busiest_busy_s"],
                                                rel=1e-9)
    assert s["attributed_s"] + s["unattributed_s"] == pytest.approx(
        s["total_device_s"], abs=1e-8)
    # the recorded cut holds the first 1500 operations of a chunk whose
    # ``while`` spans all of it: the loop's self time is the cut's own
    loop = s["unattributed"]["while.96"]
    assert s["attributed_s"] > 0.8 * (s["total_device_s"] - loop)
    assert {"ib/interp", "ib/spread"} <= set(s["spans"])
    assert sum(n["device_s"] for n in s["spans"].values()) \
        == pytest.approx(s["attributed_s"], abs=1e-7)
    # the classes agree with the benchmark's where both name one
    assert s["op_classes"]["fft_s"] == pytest.approx(
        ref["op_class_s"].get("fft", 0.0), abs=1e-8)
    # gaps are named by a program span, as the benchmark names them
    assert s["idle_gaps"]
    assert {k: pytest.approx(v, abs=1e-8)
            for k, v in s["idle_gaps"].items()} == dict(ref["idle_gaps"])
    assert any(k.startswith("bench/") for k in s["idle_gaps"])


def test_idle_gap_goes_to_the_innermost_span_that_covers_it():
    """A parent span covers whatever its child does: the child names
    the gap it (nearly) fills, the parent one that its children do
    not."""
    ops = [["%fusion.1", 0, 100], ["%fusion.1", 1100, 100],
           ["%fusion.1", 2200, 100], ["%fusion.1", 9000, 100]]
    host = [["driver/chunk", 90, 2200],             # 90 .. 2290
            ["driver/chunk/dispatch", 120, 1000],   # fills gap 1 to 98%
            ["driver/chunk/sync", 1500, 300],       # a third of gap 2
            ["driver/viz_fn", 2400, 6000]]
    trace = {"planes": [
        {"name": "/device:TPU:0",
         "lines": [{"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU", "lines": [{"name": "python", "events": host}]}]}
    s = deviceprof.attribute_planes(trace, {"fusion.1": "fluid"})
    assert s["idle_gaps"] == {
        "driver/viz_fn": pytest.approx(6.7e-6),
        "driver/chunk/dispatch": pytest.approx(1.0e-6),
        "driver/chunk": pytest.approx(1.0e-6)}
    assert s["total_device_s"] == pytest.approx(4e-7)
    assert s["window_s"] == pytest.approx(9.1e-6)


def test_attribute_capture_uses_the_sidecar(tmp_path):
    """A capture dir with an ``op_names.json`` sidecar and trace-viewer
    events named by instruction attributes by phase."""
    cap = tmp_path / "plugins" / "profile" / "t"
    cap.mkdir(parents=True)
    events = [
        {"ph": "M", "pid": 7, "name": "process_name",
         "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "pid": 7, "tid": 1, "name": "thread_name",
         "args": {"name": "XLA Ops"}},
        {"ph": "X", "pid": 7, "tid": 1, "ts": 0, "dur": 300,
         "name": "%fusion.1 = f32[8] fusion(...)"},
        {"ph": "X", "pid": 7, "tid": 1, "ts": 400, "dur": 100,
         "name": "%fusion.2"},
        {"ph": "X", "pid": 7, "tid": 1, "ts": 600, "dur": 100,
         "name": "%copy.3"}]
    with open(cap / "h.trace.json", "w") as f:
        json.dump({"traceEvents": events}, f)
    op_names = {
        "fusion.1": "jit(chunk)/while/body/ib/spread/scatter-add",
        "fusion.2": "jit(chunk)/while/body/fluid/transforms/jit(fft)/fft",
        "copy.3": "jit(chunk)/while/body/copy"}
    deviceprof.write_names(str(tmp_path),
                           (op_names, deviceprof.phase_map(op_names)))
    s = deviceprof.attribute_capture(str(tmp_path), executions=4)
    assert deviceprof.validate_summary(s) == []
    assert s["spans"]["ib/spread"]["device_s"] == pytest.approx(300e-6)
    assert s["spans"]["fluid/transforms"]["via"] == {"phase": 1}
    assert s["unattributed"] == {"%copy.3": pytest.approx(100e-6)}
    assert s["executions"] == 4


def test_stale_cache_entry_is_kept_out(tmp_path):
    """This jax's cache key leaves op metadata out, so an executable
    compiled before a scope existed is served to the program that has
    it; ``program_names`` sees the lowering's phase missing from the
    compiled text and compiles under a key that takes metadata in."""
    from jax.experimental.compilation_cache import compilation_cache

    from ibamr_tpu.serve.aot_cache import enable_persistent_cache

    prev_dir = jax.config.jax_compilation_cache_dir
    prev_min = jax.config.jax_persistent_cache_min_compile_time_secs
    prev_sz = jax.config.jax_persistent_cache_min_entry_size_bytes
    compilation_cache.reset_cache()
    enable_persistent_cache(directory=str(tmp_path), min_compile_secs=0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    try:
        def body(x):
            return jnp.sin(x) * 2.0 + jnp.cos(x)

        def scoped(x):
            with jax.named_scope("ib/force"):
                return body(x)

        def bare(x):
            return body(x)
        scoped.__name__ = bare.__name__ = "stepfn"
        x = jnp.arange(64.0, dtype=jnp.float32)
        jax.jit(bare)(x).block_until_ready()      # the old entry
        assert os.listdir(tmp_path)
        fn = jax.jit(scoped)
        fn(x).block_until_ready()                 # served the old one
        prog = {"name": "p", "fn": fn,
                "args": (jax.ShapeDtypeStruct(x.shape, x.dtype),)}
        plain, none = deviceprof.names_from_hlo(
            fn.lower(*prog["args"]).compile().as_text())
        assert not none                           # the stale text
        before = sorted(os.listdir(tmp_path))
        names, phases = deviceprof.program_names(prog)
        assert set(phases.values()) == {"ib/force"}
        assert set(names) == set(plain)           # same instructions
        # past the cache: no entry more, and the config as it was
        assert sorted(os.listdir(tmp_path)) == before
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
        assert not jax.config.jax_compilation_cache_include_metadata_in_key
    finally:
        jax.config.update("jax_compilation_cache_dir", prev_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          prev_min)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes",
                          prev_sz)
        compilation_cache.reset_cache()


# ---------------------------------------------------------------------------
# (4) the new readers on a hand-made ctx
# ---------------------------------------------------------------------------

PHASE_METRICS = {
    "transfer.prep_ms": "ib/prep", "transfer.interp_ms": "ib/interp",
    "transfer.refresh_ms": "ib/refresh", "transfer.force_ms": "ib/force",
    "transfer.spread_ms": "ib/spread",
    "transfer.repack_ms": "ib/refresh/repack",
    "fluid.solve_ms": "fluid", "fluid.transform_ms": "fluid/transforms",
    "device.unphased_ms": "unphased",
    # PR 28: the fluid solve's own phases, listed for tg_256.advance only
    "fluid.convect_ms": "fluid/convect", "fluid.rhs_ms": "fluid/rhs"}
FLUID_ONLY = ("fluid.convect_ms", "fluid.rhs_ms", "fluid.algebra_ms",
              "fluid.convect_roofline")
TOP_LEVEL = [m for m, p in PHASE_METRICS.items() if "/" not in p[3:]]
_PRE = "jit(chunk)/while/body/closed_call/"
HAND_OP_NAMES = {
    "fusion.1": _PRE + "ib/prep/pack/sort",
    "fusion.2": _PRE + "ib/interp/jit(_take)/gather",
    "fusion.3": _PRE + "ib/refresh/jit(_take)/gather",
    "fusion.4": _PRE + "ib/force/gather",
    "fusion.5": _PRE + "ib/spread/scatter-add",
    "fusion.6": _PRE + "fluid/mul",
    "fft.7": _PRE + "fluid/transforms/jit(fft)/fft",
    "fusion.8": _PRE + "add",
    "fusion.9": _PRE + "ib/interp/dot_general",
    "fusion.10": _PRE + "fluid/convect/select_n",
    "fusion.11": _PRE + "fluid/rhs/add"}
HAND_OPS = [["fusion.1 [scatter_sort:sort]", 0.010],
            ["fusion.2 [scatter_sort:gather]", 0.020],
            ["fusion.3 [scatter_sort:gather]", 0.004],
            ["fusion.4 [scatter_sort:gather]", 0.006],
            ["fusion.5 [scatter_sort:scatter-add]", 0.028],
            # no metadata of its own: the phase of what it moves
            ["copy-done.5 [copy]", 0.002],
            ["fusion.6 [other:mul]", 0.008],
            ["fft.7 [fft:fft]", 0.002],
            ["fusion.10 [other:select_n]", 0.005],
            ["fusion.11 [other:add]", 0.003],
            ["fusion.8 [other:add]", 0.001],
            # its label's primitive is another program's: unphased
            ["fusion.9 [other:mul]", 0.003],
            # in no chunk program (a callback's device work)
            ["fusion.77 [other]", 0.002],
            ["while.1 [loop]", 0.0005]]
HAND_WANT = {"ib/prep": 10.0, "ib/interp": 20.0, "ib/refresh": 4.0,
             "ib/force": 6.0, "ib/spread": 30.0,
             "ib/refresh/repack": 0.0, "fluid": 18.0,
             "fluid/transforms": 2.0, "fluid/convect": 5.0,
             "fluid/rhs": 3.0, "unphased": 6.5}


def _hand_ctx(monkeypatch, op_names=HAND_OP_NAMES):
    now = time.perf_counter()
    monkeypatch.setattr(obs, "programs", lambda: [
        {"name": "driver/chunk[20]", "t": now - 9.0},
        {"name": "driver/chunk[10]", "t": now - 8.0},
        {"name": "recovery", "t": now + 5.0}])
    seen = []

    def fake(progs):
        seen.append([p["name"] for p in progs])
        phases = deviceprof.phase_map(op_names)
        if phases:
            phases["copy-done.5"] = "ib/spread"     # inherited
        return dict(op_names), phases
    monkeypatch.setattr(deviceprof, "programs_names", fake)
    ctx = {"trace": {"steps": 1, "device_ops": [list(o) for o in HAND_OPS],
                     "busy_s": sum(s for _, s in HAND_OPS)},
           "steps": 40,
           "chunks": [{"t_start": now - 4.0, "t_end": now - 2.0,
                       "steps": 20},
                      {"t_start": now - 2.0, "t_end": now, "steps": 20}]}
    return ctx, seen


@pytest.mark.parametrize("metric", sorted(PHASE_METRICS))
def test_phase_reader(monkeypatch, metric):
    ctx, seen = _hand_ctx(monkeypatch)
    assert _reader(metric)(ctx) == pytest.approx(
        HAND_WANT[PHASE_METRICS[metric]])
    # the window's driver's programs only, in its order; read once
    _reader(metric)(ctx)
    assert seen == [["driver/chunk[20]", "driver/chunk[10]"]]
    entry = _per_layer()[metric]
    assert (entry["source"], entry["moves"]) == ("device_trace", "step_ms")
    if metric in FLUID_ONLY:
        # the fluid-only cells, periodic (PR 28) and wall-bounded (PR 32),
        # and the rigid body over the walled solve (PR 34)
        assert entry["workloads"] == ["tg_256.advance", "cavity_256.advance",
                                      "falling_sphere_e4.advance"]
    else:
        assert {"ex4_shell_256.advance", "ex4_shell_128.advance",
                "ex4_shell_128.production"} <= set(entry["workloads"])


def test_fluid_phases_add_up_to_the_solve(monkeypatch):
    """``fluid.algebra_ms`` is what is under ``fluid`` and under none of
    its three named parts; the convective operator's share of its
    roofline is its least bytes (from shapes) over the HBM peak, over
    the phase's time."""
    ctx, _ = _hand_ctx(monkeypatch)
    ctx.update(grid_n=[256, 256, 256], device={"kind": "TPU v5 lite"},
               peaks={"device_kinds": {"TPU v5 lite": {
                   "hbm_bytes_per_s": 819e9}}})
    parts = [_reader(m)(ctx) for m in ("fluid.convect_ms", "fluid.rhs_ms",
                                       "fluid.algebra_ms",
                                       "fluid.transform_ms")]
    assert parts == pytest.approx([5.0, 3.0, 8.0, 2.0])
    assert sum(parts) == pytest.approx(_reader("fluid.solve_ms")(ctx))
    least_ms = 1e3 * 6 * 256 ** 3 * 4 / 819e9
    assert _reader("fluid.convect_roofline")(ctx) == pytest.approx(
        100.0 * least_ms / 5.0)
    for m in FLUID_ONLY:
        assert _per_layer()[m]["layer"] == "fluid solve"
    # a program from before the two phases (the parent): nothing to read
    old = {k: v for k, v in HAND_OP_NAMES.items()
           if "convect" not in v and "rhs" not in v}
    monkeypatch.setattr(deviceprof, "PHASES", tuple(
        seq for seq in deviceprof.PHASES
        if seq not in (("fluid", "convect"), ("fluid", "rhs"))))
    ctx, _ = _hand_ctx(monkeypatch, old)
    for m in FLUID_ONLY:
        assert _reader(m)(ctx) is None


def test_phase_readers_add_up_to_busy(monkeypatch):
    ctx, _ = _hand_ctx(monkeypatch)
    total = sum(_reader(m)(ctx) for m in TOP_LEVEL)
    assert total == pytest.approx(
        1e3 * ctx["trace"]["busy_s"] / ctx["trace"]["steps"], rel=1e-9)
    assert _reader("transfer.repack_ms")(ctx) == 0.0


def test_phase_reader_raises_on_a_phaseless_program(monkeypatch):
    bare = {k: v.replace("ib/", "").replace("fluid/", "")
            for k, v in HAND_OP_NAMES.items()}
    ctx, _ = _hand_ctx(monkeypatch, bare)
    with pytest.raises(RuntimeError, match="carries a phase"):
        _reader("transfer.prep_ms")(ctx)


def test_phase_reader_without_trace_or_registry(monkeypatch):
    ctx, _ = _hand_ctx(monkeypatch)
    assert _reader("fluid.solve_ms")(dict(ctx, trace=None)) is None
    # a program from before the registry (the parent commit)
    monkeypatch.delattr(obs, "programs")
    ctx.pop("_phase_ms", None)
    assert _reader("fluid.solve_ms")(ctx) is None
    monkeypatch.delattr(obs, "spans")
    assert _reader("driver.dispatch_ms")(ctx) is None
    assert _reader("compile.in_window")(ctx) is None


SPAN_METRICS = {
    # metric: (span path, when, expected from the hand-made ring)
    "driver.dispatch_ms": 1e3 * (0.01 + 0.03) / 40,
    "driver.sync_wait_ms": 1e3 * 0.5 / 40,
    "callbacks.metrics_ms": 1e3 * 0.04 / 40,
    "callbacks.viz_ms": 1e3 * 0.2 / 40,
    "callbacks.checkpoint_ms": 1e3 * 0.3 / 40,
    "checkpoint.fetch_s": 0.05,
    "checkpoint.commit_s": 0.2,
    "setup.backend_init_s": 9.0,
    "compile.cache_read_s": 3.0,
    "compile.backend_s": 1.5,
    "compile.in_window": 1.0,
    # set-up and recovery by stage
    "compile.trace_s": 0.5 + 2.0,
    "compile.lower_s": 0.1 + 0.75,
    "setup.build_s": 0.5,
    # the union of the set-up's spans: backend_init with build (the trace
    # inside it counts once), the restore, two cache reads, the backend,
    # the first dispatch (trace and lowerings inside it), the window's
    # first chunk up to the window's start
    "setup.unspanned_s": 32.0 - (9.5 + 0.2 + 1.0 + 2.0 + 1.5 + 5.0 + 0.25),
    "recover.compile_s": 4.8 - 0.5,
    "recover.first_chunk_s": 0.8,
    "recover.unspanned_s": 6.0 - (0.3 + 5.4)}
NEEDS_NEW_SPANS = ("compile.trace_s", "compile.lower_s", "setup.build_s",
                   "setup.unspanned_s", "recover.compile_s",
                   "recover.unspanned_s")


def _hand_ring(ctx):
    """A set-up, a window and a recovery, by hand: set-up is the 32 s
    before the window, the recovery the 6 s from the last restore on."""
    w0 = ctx["chunks"][0]["t_start"]
    r0 = ctx["chunks"][-1]["t_end"] + 20.0
    ctx.update(setup_s=32.0, recover={"recover_s": 6.0, "restore_s": 0.3})

    def sp(path, t0, dur):
        return {"id": 0, "parent": None, "name": path.rsplit("/", 1)[-1],
                "path": path, "t0": t0, "t1": t0 + dur, "attrs": {}}
    return [
        sp("checkpoint/restore", w0 - 31.0, 0.2),         # not the last
        sp("setup/backend_init", w0 - 30.0, 9.0),
        sp("compile/trace", w0 - 25.0, 0.5),
        sp("setup/build", w0 - 21.0, 0.5),
        sp("driver/chunk/dispatch/compile/cache_read", w0 - 20.0, 1.0),
        sp("compile/cache_read", w0 - 18.0, 2.0),
        sp("driver/chunk/dispatch/compile/backend", w0 - 15.0, 1.5),
        sp("driver/chunk/dispatch", w0 - 10.0, 5.0),      # set-up
        sp("driver/chunk/dispatch/compile/trace", w0 - 10.0, 2.0),
        sp("driver/chunk/dispatch/compile/lower", w0 - 9.5, 0.1),
        sp("driver/chunk/dispatch/compile/lower", w0 - 8.0, 0.75),
        sp("driver/chunk", w0 - 0.25, 2.0),               # opened before
        sp("driver/chunk/dispatch", w0 + 0.1, 0.01),
        sp("driver/chunk/sync", w0 + 0.2, 0.5),
        sp("driver/metrics_fn", w0 + 0.8, 0.04),
        sp("driver/chunk/dispatch", w0 + 2.1, 0.03),
        sp("driver/chunk/dispatch/compile/backend", w0 + 2.1, 0.02),
        sp("driver/viz_fn", w0 + 3.0, 0.2),
        sp("driver/checkpoint_fn", w0 + 3.3, 0.3),
        sp("driver/checkpoint_fn/checkpoint/fetch", w0 + 3.3, 0.05),
        sp("driver/checkpoint_fn/checkpoint/commit", w0 + 3.4, 0.2),
        sp("driver/chunk/sync", w0 + 10.0, 7.0),          # after it
        # the recovery: restore, then a new driver's first chunk
        sp("checkpoint/restore", r0, 0.3),
        sp("driver/chunk", r0 + 0.5, 5.4),
        sp("driver/chunk/dispatch", r0 + 0.5, 4.5),
        sp("driver/chunk/dispatch/compile/trace", r0 + 0.5, 2.0),
        sp("driver/chunk/dispatch/compile/lower", r0 + 2.5, 0.5),
        sp("driver/chunk/dispatch/compile/backend", r0 + 3.0, 1.8),
        sp("driver/chunk/dispatch/compile/cache_read", r0 + 3.1, 1.6),
        sp("driver/chunk/sync", r0 + 5.0, 0.8),
        sp("driver/metrics_fn", r0 + 6.05, 0.05)]          # after it


@pytest.mark.parametrize("metric", sorted(SPAN_METRICS))
def test_span_reader(monkeypatch, metric):
    ctx, _ = _hand_ctx(monkeypatch)
    ring = _hand_ring(ctx)
    monkeypatch.setattr(obs, "spans", lambda: ring)
    assert _reader(metric)(ctx) == pytest.approx(SPAN_METRICS[metric])
    assert _per_layer()[metric]["source"] == "program_span"


@pytest.mark.parametrize("metric", NEEDS_NEW_SPANS)
def test_span_reader_on_a_program_without_the_stage_spans(monkeypatch,
                                                         metric):
    """The parent's ring: no trace, lowering or build span. What needs
    them reads None; the recovery's sync span is the same on both."""
    ctx, _ = _hand_ctx(monkeypatch)
    ring = [s for s in _hand_ring(ctx) if s["name"] not in (
        "trace", "lower", "build")]
    monkeypatch.setattr(obs, "spans", lambda: ring)
    assert _reader(metric)(ctx) is None
    assert _reader("recover.first_chunk_s")(ctx) == pytest.approx(0.8)
    # a cell that does not recover has no recovery to split
    ring[:] = _hand_ring(ctx)
    ctx["recover"] = None
    if metric.startswith("recover."):
        assert _reader(metric)(ctx) is None


def test_set_up_and_recovery_metrics_are_listed():
    cells = [w["name"] for w in json.load(open(
        os.path.join(ROOT, "BENCHMARK.json")))["workloads"]]
    for metric in ("compile.trace_s", "compile.lower_s", "setup.build_s",
                   "setup.unspanned_s", "recover.compile_s",
                   "recover.first_chunk_s", "recover.unspanned_s"):
        entry = _per_layer()[metric]
        assert (entry["unit"], entry["source"]) == ("s", "program_span")
        if metric.startswith("recover."):
            assert entry["moves"] == "recover_s"
            assert entry["workloads"] == [c for c in cells
                                          if c.endswith(".production")]
        else:
            assert (entry["moves"], entry["layer"]) == ("setup_s", "compile")
            assert entry["workloads"] == cells


# ---------------------------------------------------------------------------
# (5) the compile listener
# ---------------------------------------------------------------------------

def test_compile_after_the_first_chunk_shows_with_its_step():
    integ, state = build_shell_example(n_cells=8, n_lat=4, n_lon=4)
    obs.clear_spans()
    c0 = obs.counter("compile_events_total").value
    s0 = obs.counter("compile_seconds_total").value

    def metrics_fn(s, step):
        if step == 4:
            # a program nothing has compiled yet
            jax.jit(lambda x: jnp.tanh(x) * 1.2345 + step)(
                jnp.arange(7.0)).block_until_ready()
        return None
    HierarchyDriver(integ, RunConfig(dt=1e-4, num_steps=4,
                                     health_interval=2),
                    metrics_fn=metrics_fn).run(state)
    ring = obs.spans()
    first_chunk = next(s for s in ring if s["path"] == "driver/chunk")
    late = [s for s in ring if s["name"] == "compile/backend"
            and s["t0"] >= first_chunk["t1"]]
    assert late, [s["path"] for s in ring]
    assert all(s["path"] == "driver/metrics_fn/compile/backend"
               and s["attrs"] == {"step": 4, "chunk": 1, "cached": False,
                                  "fun": s["attrs"]["fun"]} for s in late)
    assert "jit(<lambda>)" in {s["attrs"]["fun"] for s in late}
    assert all(s["t1"] - s["t0"] > 0 for s in late)
    n = len([s for s in ring if s["name"] == "compile/backend"])
    assert obs.counter("compile_events_total").value - c0 == n
    assert obs.counter("compile_seconds_total").value - s0 \
        == pytest.approx(sum(s["t1"] - s["t0"] for s in ring
                             if s["name"] == "compile/backend"))


def test_outermost_traces_only_and_every_stage_names_its_function():
    """A toy shell build and a 2-chunk run: jax reports every nested jit
    and jnp wrapper's trace inside its parent's; the ring keeps one
    ``compile/trace`` per OUTERMOST trace (found here independently, by
    containment of jax's own start/end times), none inside another."""
    import jax.monitoring

    events = []

    def on_span(event, t0, t1, **kw):
        if event.endswith("jaxpr_trace_duration"):
            events.append((t0, t1))
    obs.clear_spans()
    jax.monitoring.register_event_time_span_listener(on_span)
    try:
        integ, state = build_shell_example(n_cells=16, n_lat=8, n_lon=8)
        HierarchyDriver(integ, RunConfig(dt=1e-4, num_steps=4,
                                         health_interval=2)).run(state)
    finally:
        jax.monitoring.unregister_event_time_span_listener(on_span)
    ring = obs.spans()
    outermost = [e for e in events if not any(
        o is not e and o[0] <= e[0] and e[1] <= o[1] for o in events)]
    traces = [s for s in ring if s["name"] == "compile/trace"]
    assert len(events) > 2 * len(traces) > 0       # nested ones folded
    assert len(traces) == len(outermost)
    assert not [(a["path"], b["path"]) for a in traces for b in traces
                if a is not b and b["t0"] <= a["t0"] and a["t1"] <= b["t1"]]
    # the chunk program's own: traced inside its first dispatch
    chunk = [s for s in traces if s["attrs"]["fun"] == "chunk"]
    assert [s["path"] for s in chunk] == ["driver/chunk/dispatch/compile/trace"]
    by_name = {}
    for s in ring:
        by_name.setdefault(s["name"], []).append(s)
    assert by_name["compile/lower"] and by_name["compile/backend"]
    for s in by_name["compile/lower"] + by_name["compile/backend"] + traces:
        assert isinstance(s["attrs"]["fun"], str) and s["attrs"]["fun"]
    assert all(s["attrs"]["cached"] in (True, False)
               for s in by_name["compile/backend"])
    assert "jit(chunk)" in {s["attrs"]["fun"]
                            for s in by_name["compile/lower"]}
    # the first dispatch is its program's trace, lowering and compile
    first = next(s for s in ring if s["name"] == "dispatch"
                 and s["attrs"]["first_call"])
    kids = [s for s in ring if s["parent"] == first["id"]]
    iv = (first["t0"], first["t1"])
    from perfbench import intervals
    assert intervals.covered_s(kids, iv) > 0.9 * (iv[1] - iv[0])
    # set-up's spans are still in the ring after it
    assert [s["path"] for s in ring if s["name"] == "setup/build"] == [
        "setup/build"]
    assert len(ring) < obs.bus.SPAN_RING_SIZE // 8


def test_cache_read_names_the_compile_it_served(tmp_path):
    """A persistent-cache hit: ``compile/cache_read`` closes inside its
    ``compile/backend``, takes that compile's ``fun`` and marks it
    ``cached``."""
    from jax.experimental.compilation_cache import compilation_cache

    from ibamr_tpu.serve.aot_cache import enable_persistent_cache

    prev_dir = jax.config.jax_compilation_cache_dir
    prev_min = jax.config.jax_persistent_cache_min_compile_time_secs
    prev_sz = jax.config.jax_persistent_cache_min_entry_size_bytes
    compilation_cache.reset_cache()
    enable_persistent_cache(directory=str(tmp_path), min_compile_secs=0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    try:
        def cached_fn(x):
            return jnp.sinh(x) * 3.25 + 1.0
        x = jnp.arange(11.0, dtype=jnp.float32)
        jax.jit(cached_fn)(x).block_until_ready()
        jax.clear_caches()
        obs.clear_spans()
        with obs.span("again"):
            jax.jit(cached_fn)(x).block_until_ready()
        ring = obs.spans()
    finally:
        jax.config.update("jax_compilation_cache_dir", prev_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          prev_min)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes",
                          prev_sz)
        compilation_cache.reset_cache()
    back = [s for s in ring if s["name"] == "compile/backend"
            and "cached_fn" in s["attrs"]["fun"]]
    reads = [s for s in ring if s["name"] == "compile/cache_read"]
    assert len(back) == 1 and back[0]["attrs"]["cached"] is True
    assert [r["attrs"]["fun"] for r in reads] == [back[0]["attrs"]["fun"]]
    assert back[0]["t0"] <= reads[0]["t0"] <= reads[0]["t1"] \
        <= back[0]["t1"]
    assert back[0]["path"] == "again/compile/backend"


BUILDERS = {
    # example: (directory, builder, keys that make it a toy)
    "tgv3d": ("examples/navier_stokes/tgv3d", "build_tgv_example",
              {"CartesianGeometry": {"n_cells": [16, 16, 16]}}),
    "cavity3d": ("examples/navier_stokes/cavity3d", "build_cavity_example",
                 {"CartesianGeometry": {"n_cells": [8, 8, 8]}}),
    "falling_sphere": ("examples/ConstraintIB/falling_sphere",
                       "build_falling_sphere_example",
                       {"CartesianGeometry": {"n_cells": [20, 20, 32]}}),
}


@pytest.mark.parametrize("example", ["shell3d"] + sorted(BUILDERS))
def test_each_builder_opens_one_build_span(example):
    if example == "shell3d":
        def build():
            return build_shell_example(n_cells=8, n_lat=4, n_lon=4)
    else:
        where, name, keys = BUILDERS[example]
        mod = harness.load_module(os.path.join(ROOT, where, "main.py"),
                                  example + "_build_span")
        text = inputfile.set_keys(
            open(os.path.join(ROOT, where, "input3d")).read(), keys)

        def build():
            return getattr(mod, name)(parse_input_string(text))
    obs.clear_spans()
    integ, state = build()
    ring = obs.spans()
    assert [s["path"] for s in ring if s["name"] == "setup/build"] == [
        "setup/build"]
    # everything the builder compiled is inside it
    assert all(s["path"].startswith("setup/build/") for s in ring
               if s["name"] != "setup/build")
    assert state is not None and integ is not None


def test_timer_counts_chunks_without_a_span_of_its_own():
    """``HierarchyDriver(timer=...)``: the report keeps one entry per
    chunk; the ring keeps one span per chunk."""
    from ibamr_tpu.utils.timers import TimerManager

    integ, state = build_shell_example(n_cells=8, n_lat=4, n_lon=4)
    tm = TimerManager()
    obs.clear_spans()
    HierarchyDriver(integ, RunConfig(dt=1e-4, num_steps=6,
                                     health_interval=2),
                    timer=tm, timer_name="IB::advanceHierarchy").run(state)
    ring = obs.spans()
    chunks = [s for s in ring if s["name"] == "driver/chunk"]
    assert [s["path"] for s in chunks] == ["driver/chunk"] * 3
    assert not [s for s in ring if "IB::" in s["path"]]
    t = tm.timers["IB::advanceHierarchy"]
    assert t.count == 3
    walls = sum(s["t1"] - s["t0"] for s in chunks)
    assert walls <= t.total < walls + 0.05
    assert "IB::advanceHierarchy" in tm.report()
