"""Pod-scope observability tests (PR 15): the collective/overlap
censuses on tiny hand-built shard_map programs and synthetic HLO, the
``comm_s`` device-op class with its accounting invariants,
per-process ledger shards, and the merge machinery —
deterministic (seq, proc) interleave, torn-tail tolerance, same-run
checking, and the no-double-counted-counters fleet rollup.

Everything runs on the conftest's 8 virtual CPU devices; the async
start/done pairing is exercised on synthetic HLO text because the CPU
backend only ever emits synchronous collectives.
"""

import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.shard_map import shard_map
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

import ibamr_tpu.obs as obs
from ibamr_tpu.analysis.graph_census import (collective_census,
                                             overlap_census)
from ibamr_tpu.obs import deviceprof
from ibamr_tpu.obs.merge import (find_shards, fleet_counters,
                                 fleet_prometheus_text, merge_ledgers)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mesh1d():
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs the conftest's 8 virtual devices")
    return Mesh(np.array(devs[:8]), ("x",))


# ---------------------------------------------------------------------------
# collective census (jaxpr level)
# ---------------------------------------------------------------------------

def test_collective_census_psum():
    mesh = _mesh1d()
    f = shard_map(lambda x: jax.lax.psum(x, "x"), mesh,
                  in_specs=P("x"), out_specs=P(), check_rep=False)
    jaxpr = jax.make_jaxpr(f)(jnp.zeros((64, 4), jnp.float32)).jaxpr
    c = collective_census(jaxpr)
    assert c["psum_prims"] == 1
    # bytes are PER-SHARD avals: (8, 4) f32 = 128 B per device
    assert c["psum_bytes"] == 128
    assert c["collective_prims"] == 1
    assert c["collective_bytes"] == 128
    assert c["ppermute_prims"] == 0


def test_collective_census_ppermute():
    mesh = _mesh1d()
    perm = [(i, (i + 1) % 8) for i in range(8)]
    f = shard_map(lambda x: jax.lax.ppermute(x, "x", perm=perm), mesh,
                  in_specs=P("x"), out_specs=P("x"), check_rep=False)
    jaxpr = jax.make_jaxpr(f)(jnp.zeros((64, 4), jnp.float32)).jaxpr
    c = collective_census(jaxpr)
    assert c["ppermute_prims"] == 1
    assert c["ppermute_bytes"] == 128
    assert c["collective_prims"] == 1


def test_collective_census_all_to_all_and_clean_program():
    mesh = _mesh1d()
    f = shard_map(
        lambda x: jax.lax.all_to_all(x, "x", split_axis=1,
                                     concat_axis=0, tiled=True),
        mesh, in_specs=P("x", None), out_specs=P(None, "x"),
        check_rep=False)
    jaxpr = jax.make_jaxpr(f)(jnp.zeros((64, 8), jnp.float32)).jaxpr
    c = collective_census(jaxpr)
    assert c["all_to_all_prims"] == 1
    # per-shard output: (64, 1) f32 = 256 B per device
    assert c["all_to_all_bytes"] == 256
    # a collective-free program counts zero everywhere
    c2 = collective_census(
        jax.make_jaxpr(lambda a: a * 2.0)(jnp.ones(4)).jaxpr)
    assert c2["collective_prims"] == 0
    assert c2["collective_bytes"] == 0


def test_collective_census_sees_through_scan():
    # collectives inside control flow count (iter_eqns recursion) —
    # the sharded driver chunk is exactly a scan over ppermutes
    mesh = _mesh1d()
    perm = [(i, (i + 1) % 8) for i in range(8)]

    def body(x):
        def step(c, _):
            return jax.lax.ppermute(c, "x", perm=perm), ()
        out, _ = jax.lax.scan(step, x, None, length=3)
        return out

    f = shard_map(body, mesh, in_specs=P("x"), out_specs=P("x"),
                  check_rep=False)
    jaxpr = jax.make_jaxpr(f)(jnp.zeros((64, 4), jnp.float32)).jaxpr
    c = collective_census(jaxpr)
    assert c["ppermute_prims"] == 1          # one eqn inside the scan body


# ---------------------------------------------------------------------------
# overlap census (HLO text level)
# ---------------------------------------------------------------------------

_ASYNC_HLO = """\
HloModule overlap_test
ENTRY main {
  %p0 = f32[8]{0} parameter(0)
  %ag-start = (f32[8]{0}, f32[16]{0}) all-gather-start(f32[8]{0} %p0), dimensions={0}
  %mul = f32[8]{0} multiply(f32[8]{0} %p0, f32[8]{0} %p0)
  %ag-done = f32[16]{0} all-gather-done((f32[8]{0}, f32[16]{0}) %ag-start)
  %cp-start.1 = (f32[8]{0}, f32[8]{0}) collective-permute-start(f32[8]{0} %mul)
  %cp-done.1 = f32[8]{0} collective-permute-done((f32[8]{0}, f32[8]{0}) %cp-start.1)
  %ar = f32[8]{0} all-reduce(f32[8]{0} %mul), to_apply=%add
  ROOT %t = (f32[16]{0}, f32[8]{0}, f32[8]{0}) tuple(%ag-done, %cp-done.1, %ar)
}
"""


def test_overlap_census_pairs_hidden_and_unhidden():
    c = overlap_census(_ASYNC_HLO)
    # all-gather pair has the multiply scheduled inside its window
    # (hidden); the collective-permute pair has an empty window
    assert c["overlap_pairs"] == 2
    assert c["overlap_hidden"] == 1
    assert c["overlap_unhidden"] == 1
    # the synchronous all-reduce can never overlap
    assert c["collective_sync_ops"] == 1
    sites = {s["op"]: s["compute_between"] for s in c["overlap_sites"]}
    assert sites["all-gather-start"] == 1
    assert sites["collective-permute-start"] == 0


def test_overlap_census_structural_window_is_unhidden():
    # only bookkeeping ops between start and done hide nothing
    text = "\n".join([
        "  %s-start = (f32[8]{0}, f32[8]{0}) "
        "collective-permute-start(f32[8]{0} %p)",
        "  %gte = f32[8]{0} get-tuple-element((f32[8]{0}) %other), "
        "index=0",
        "  %tup = (f32[8]{0}) tuple(f32[8]{0} %gte)",
        "  %s-done = f32[8]{0} collective-permute-done("
        "(f32[8]{0}, f32[8]{0}) %s-start)",
    ])
    c = overlap_census(text)
    assert c["overlap_pairs"] == 1
    assert c["overlap_unhidden"] == 1
    assert c["overlap_hidden"] == 0


def test_overlap_census_ignores_quoted_metadata():
    # an opcode name inside quoted metadata must not fake a collective
    text = ('  %f = f32[8]{0} add(f32[8]{0} %a, f32[8]{0} %b), '
            'metadata={op_name="jit(all-reduce)(fake)"}')
    c = overlap_census(text)
    assert c["collective_sync_ops"] == 0
    assert c["overlap_pairs"] == 0


# ---------------------------------------------------------------------------
# deviceprof: the comm_s op class
# ---------------------------------------------------------------------------

def _x(name, dur_us, pid=7, tid=2, args=None):
    return {"ph": "X", "pid": pid, "tid": tid, "ts": 0,
            "dur": dur_us, "name": name, "args": args}


def _comm_trace():
    """TPU-shaped trace: an explicit collective opcode, a fused op
    inside the parallel layer's ``comm`` named scope, plus fft / dot /
    plain compute."""
    events = [
        {"ph": "M", "pid": 7, "name": "process_name",
         "args": {"name": "/device:TPU:0 (chip 0)"}},
        {"ph": "M", "pid": 7, "tid": 2, "name": "thread_name",
         "args": {"name": "XLA Ops"}},
        _x("all-reduce.3", 300,
           args={"tf_op": "jit(step)/step/all-reduce.3"}),
        _x("fusion.9", 200,
           args={"tf_op": "jit(step)/step/comm/fusion.9"}),
        _x("fft.1", 100, args={"tf_op": "jit(step)/step/fft.1"}),
        _x("dot_general.2", 50,
           args={"tf_op": "jit(step)/step/dot_general.2"}),
        _x("fusion.4", 50, args={"tf_op": "jit(step)/step/fusion.4"}),
    ]
    return {"traceEvents": events}


def test_comm_op_class_by_opcode_and_scope():
    events, _ = deviceprof.device_op_events(_comm_trace())
    s = deviceprof.attribute_events(events, ["step"])
    oc = s["op_classes"]
    # collective opcode + comm-scoped fusion both land in comm_s
    assert oc["comm_s"] == pytest.approx(500e-6)
    assert oc["fft_s"] == pytest.approx(100e-6)
    assert oc["dot_s"] == pytest.approx(50e-6)
    assert oc["other_s"] == pytest.approx(50e-6)
    # the classes partition the total exactly
    assert (oc["fft_s"] + oc["dot_s"] + oc["comm_s"] + oc["other_s"]
            == pytest.approx(s["total_device_s"]))
    # and the span accounting identity is untouched
    assert s["attributed_s"] + s["unattributed_s"] == pytest.approx(
        s["total_device_s"])
    assert deviceprof.validate_summary(
        {**s, "schema": deviceprof.PROF_SCHEMA}) == []


def test_real_sharded_capture_reports_comm_class(tmp_path):
    """Acceptance: an 8-device virtual-mesh capture attributes with
    ``comm_s`` present and the accounting identity holding. The CPU
    backend emits synchronous collectives with their opcode names, so
    the class is populated whenever the trace tags collective ops; the
    invariant must hold either way."""
    mesh = _mesh1d()
    perm = [(i, (i + 1) % 8) for i in range(8)]
    f = jax.jit(shard_map(
        lambda x: jax.lax.ppermute(x, "x", perm=perm) * 2.0,
        mesh, in_specs=P("x"), out_specs=P("x"), check_rep=False))
    x = jnp.ones((64, 16), jnp.float32)
    f(x).block_until_ready()            # compile outside the capture
    cap = str(tmp_path / "cap")
    try:
        with jax.profiler.trace(cap):
            for _ in range(3):
                f(x).block_until_ready()
    except Exception as e:              # pragma: no cover
        pytest.skip(f"profiler unavailable: {e}")
    if not deviceprof.find_trace_files(cap):  # pragma: no cover
        pytest.skip("no trace files produced")
    s = deviceprof.attribute_capture(cap)
    assert "comm_s" in s["op_classes"]
    assert s["op_classes"]["comm_s"] >= 0.0
    assert deviceprof.validate_summary(s) == []


# ---------------------------------------------------------------------------
# per-process ledger shards
# ---------------------------------------------------------------------------

def test_ledger_proc_none_is_unchanged(tmp_path):
    path = str(tmp_path / "ledger.jsonl")
    with obs.ledger(path, fingerprint={"c": 1}):
        obs.emit("marker", x=1)
    recs = obs.read_ledger(path)
    assert os.path.exists(path)
    assert all("proc" not in r for r in recs)


def test_ledger_proc_reroutes_and_stamps(tmp_path):
    path = str(tmp_path / "ledger.jsonl")
    with obs.ledger(path, fingerprint={"c": 1}, proc=3) as led:
        obs.emit("marker", x=1)
    assert led.path == str(tmp_path / "ledger-3.jsonl")
    assert not os.path.exists(path)
    recs = obs.read_ledger(led.path)
    assert recs and all(r["proc"] == "3" for r in recs)
    # a directory path works too
    assert obs.shard_path(str(tmp_path), 7) == str(
        tmp_path / "ledger-7.jsonl")
    # hostile proc ids cannot escape the directory
    assert os.sep not in os.path.basename(
        obs.shard_path(str(tmp_path), "../evil"))


def _write_pod(tmp_path, n_procs=2):
    fp = {"cfg": "pod"}
    for proc in range(n_procs):
        obs.reset_metrics()
        with obs.ledger(str(tmp_path / "ledger.jsonl"),
                        fingerprint=fp, proc=proc):
            obs.counter("chunks_total").inc(4 + proc)
            with obs.span("driver"):
                with obs.span("chunk"):
                    pass
            obs.chunk_boundary(step=20)
    obs.reset_metrics()
    return str(tmp_path)


def test_merge_is_deterministic_and_stamped(tmp_path):
    d = _write_pod(tmp_path)
    assert sorted(find_shards(d)) == ["0", "1"]
    m = merge_ledgers(d)
    assert m["procs"] == ["0", "1"]
    # one shared run identity across shards
    assert all(v["run_id"] == m["run_id"]
               for v in m["per_proc"].values())
    # (seq, proc) order: non-decreasing seq, proc breaks ties
    keys = [(r["seq"], r["proc"]) for r in m["records"]]
    assert keys == sorted(keys)
    assert all(r.get("proc") in ("0", "1") for r in m["records"])


def test_merge_tolerates_sigkill_torn_tail(tmp_path):
    d = _write_pod(tmp_path)
    full = merge_ledgers(d)
    shard = os.path.join(d, "ledger-1.jsonl")
    # a SIGKILL mid-write tears at most the final line: truncate the
    # shard mid-record and the merge must lose exactly that record
    raw = open(shard, "rb").read()
    open(shard, "wb").write(raw[:-10])
    torn = merge_ledgers(d)
    assert len(torn["records"]) == len(full["records"]) - 1
    assert torn["run_id"] == full["run_id"]
    assert torn["per_proc"]["1"]["records"] == \
        full["per_proc"]["1"]["records"] - 1


def test_merge_refuses_mixed_runs(tmp_path):
    d = _write_pod(tmp_path)
    with obs.ledger(str(tmp_path / "ledger.jsonl"),
                    fingerprint={"cfg": "OTHER"}, proc=2):
        pass
    with pytest.raises(ValueError, match="run_id"):
        merge_ledgers(d)
    m = merge_ledgers(d, allow_mixed_run_ids=True)
    assert m["procs"] == ["0", "1", "2"]


def test_fleet_counters_namespaced_not_summed(tmp_path):
    d = _write_pod(tmp_path)
    snap = fleet_counters(merge_ledgers(d))
    assert snap["counters"]['chunks_total{proc="0"}'] == 4
    assert snap["counters"]['chunks_total{proc="1"}'] == 5
    # no un-namespaced key survives — a fleet sum must be explicit
    assert "chunks_total" not in snap["counters"]
    text = fleet_prometheus_text(merge_ledgers(d))
    assert 'chunks_total{proc="0"} 4' in text
    assert 'chunks_total{proc="1"} 5' in text


def test_fleet_summary_roundtrip_no_double_count(tmp_path, capsys):
    from tools.obs import main as obs_main

    d = _write_pod(tmp_path)
    # stamp a device_time record with op classes on proc 0's shard
    # (what `prof.py attribute --ledger` appends post-hoc)
    shard = os.path.join(d, "ledger-0.jsonl")
    recs = obs.read_ledger(shard)
    rec = {"seq": max(r["seq"] for r in recs) + 1,
           "run_id": recs[0]["run_id"], "t": recs[-1]["t"] + 1.0,
           "kind": "device_time", "proc": "0", "total_device_s": 0.5,
           "op_classes": {"fft_s": 0.2, "dot_s": 0.1, "comm_s": 0.15,
                          "other_s": 0.05}}
    with open(shard, "a") as f:
        f.write(json.dumps(rec) + "\n")
    assert obs_main(["summary", d, "--fleet"]) == 0
    out = capsys.readouterr().out
    assert "procs: 2" in out
    # each proc's counter renders exactly once — whole-name match, so
    # import-registered siblings like driver_chunks_total don't count
    for proc, val in (("0", 4), ("1", 5)):
        hits = re.findall(
            r'(?m)^\s*chunks_total\{proc="%s"\}\s+(\d+)\s*$' % proc,
            out)
        assert hits == [str(val)], (proc, hits)
    assert "30.0% of capture" in out          # 0.15 / 0.5 comm share
    # per-proc span trees render under per-proc headers
    assert "proc 0:" in out and "proc 1:" in out


def test_fleet_summary_renders_comm_graph_split(tmp_path, capsys):
    """The per-proc hidden/unhidden collective split (PR 16): a
    ``graph_census`` record on a shard (what ``tools/fleet.py`` emits
    per supervised run) renders as the proc's ``comm graph:`` line
    next to the measured comm share."""
    from tools.obs import main as obs_main

    d = _write_pod(tmp_path)
    shard = os.path.join(d, "ledger-1.jsonl")
    recs = obs.read_ledger(shard)
    rec = {"seq": max(r["seq"] for r in recs) + 1,
           "run_id": recs[0]["run_id"], "t": recs[-1]["t"] + 1.0,
           "kind": "graph_census", "proc": "1", "scope": "fleet_chunk",
           "chunk_length": 4, "lanes": 8, "mesh_devices": 8,
           "structural_collectives": 12, "hidden_collectives": 10,
           "unhidden_collectives": 2, "hidden_fraction": 83}
    with open(shard, "a") as f:
        f.write(json.dumps(rec) + "\n")
    assert obs_main(["summary", d, "--fleet"]) == 0
    out = capsys.readouterr().out
    assert ("comm graph: 12 data-moving collectives, 10 hidden / "
            "2 unhidden (83% structurally hidden) [lanes=8 x D=8]"
            in out)
    # proc 0 has no census record -> no comm-graph line in its block
    block0 = out.split("proc 0:")[1].split("proc 1:")[0]
    assert "comm graph" not in block0


def test_run_fleet_emits_chunk_census(tmp_path, capsys):
    """The producing side: a supervised lane-mesh fleet run lands one
    ``graph_census`` record in its ledger, and the lane-mesh chunk is
    fully lane-local (zero data-moving collectives)."""
    from tools.fleet import build_fleet, run_fleet
    from ibamr_tpu.parallel.mesh import make_lane_mesh
    from ibamr_tpu.utils.hierarchy_driver import RunConfig

    _mesh1d()  # skip unless 8 virtual devices
    # x64 session (conftest): the shell must be built in f64 too
    integ, _, stacked = build_fleet(16, 8, 16, 0.05, 8, 0.01,
                                    "float64")
    cfg = RunConfig(dt=1e-3, num_steps=4, health_interval=2)
    summary, _ = run_fleet(integ, stacked, cfg, 8,
                           directory=str(tmp_path),
                           lane_mesh=make_lane_mesh(8))
    recs = obs.read_ledger(os.path.join(str(tmp_path),
                                        "ledger.jsonl"))
    census = [r for r in recs if r.get("kind") == "graph_census"]
    assert len(census) == 1
    c = census[0]
    assert c["scope"] == "fleet_chunk"
    assert c["lanes"] == 8 and c["mesh_devices"] == 8
    assert c["structural_collectives"] == 0
    assert c["hidden_fraction"] == 100
    assert summary["lanes_quarantined"] == 0


# ---------------------------------------------------------------------------
# prof diff: the dedicated comm gate (PR 16)
# ---------------------------------------------------------------------------

def _gate_summaries(comm_a, comm_b, device):
    proc = "/device:TPU:0" if device else "python"
    mk = lambda comm: {  # noqa: E731 - table of two
        "total_device_s": 1.0,
        "spans": {}, "unattributed_s": 0.0,
        "op_classes": {"fft_s": 0.4, "dot_s": 0.3, "comm_s": comm,
                       "other_s": 0.3 - comm},
        "lanes": [{"process": proc, "thread": "XLA Ops",
                   "events": 1, "busy_s": 1.0}]}
    return mk(comm_a), mk(comm_b)


def test_comm_gate_regresses_on_device_capture():
    from tools.prof import diff_summaries

    sa, sb = _gate_summaries(0.010, 0.013, device=True)
    # +30% comm: inside the default 25%+floor general band would not
    # fire for a 3 ms move on a 1 s capture... the op_class judge does
    # fire at 25% — so use a general band ABOVE the move and show the
    # dedicated gate still catches it
    lines, verdict = diff_summaries(sa, sb, tol_pct=50.0,
                                    floor_s=200e-6, comm_tol_pct=10.0)
    assert verdict == "regressed"
    assert any("comm gate" in ln and "REGRESSED" in ln
               for ln in lines)


def test_comm_gate_advisory_on_cpu_capture():
    from tools.prof import diff_summaries

    sa, sb = _gate_summaries(0.010, 0.013, device=False)
    lines, verdict = diff_summaries(sa, sb, tol_pct=50.0,
                                    floor_s=200e-6, comm_tol_pct=10.0)
    assert verdict == "clean"
    assert any("comm gate" in ln and "ADVISORY" in ln
               for ln in lines)


def test_comm_gate_within_band_and_unarmed():
    from tools.prof import diff_summaries

    sa, sb = _gate_summaries(0.010, 0.0101, device=True)
    lines, verdict = diff_summaries(sa, sb, tol_pct=50.0,
                                    floor_s=200e-6, comm_tol_pct=10.0)
    assert verdict == "clean"
    assert any("comm gate" in ln and "within band" in ln
               for ln in lines)
    # unarmed (default): no gate line at all, behavior unchanged
    lines, _ = diff_summaries(sa, sb, tol_pct=50.0, floor_s=200e-6)
    assert not any("comm gate" in ln for ln in lines)


def test_fleet_compare_per_proc_deltas(tmp_path, capsys):
    from tools.obs import main as obs_main

    a = _write_pod(tmp_path / "a")
    b = _write_pod(tmp_path / "b")
    assert obs_main(["compare", a, b]) == 0
    out = capsys.readouterr().out
    assert "proc 0 per-phase wall" in out
    assert "proc 1 per-phase wall" in out
    assert 'chunks_total{proc="1"}' in out
