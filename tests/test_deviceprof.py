"""Device-time attribution tests (PR 10): trace parsing, span
mapping, residual accounting, and the drift gate.

Most of this file drives ``ibamr_tpu/obs/deviceprof.py`` with
HAND-BUILT trace-viewer JSON — the attribution math must be testable
on a machine with no profiler at all, and a synthetic trace pins the
exact event shapes the two backends emit (TPU: ``/device:*``
processes with ``XLA Ops`` lanes and scope paths in ``tf_op`` args;
CPU/TFRT: op events scattered across host pool threads, identified
only by their ``hlo_module``/``hlo_op`` args). The one real capture
(``test_real_capture_attributes_driver_chunk``) closes the acceptance
loop: a CPU-backend ``jax.profiler`` capture of the solo driver chunk
must attribute >= 90% of device-lane time to the ``driver/chunk``
span, with the residual reported explicitly.
"""

import gzip
import json
import os

import pytest

from ibamr_tpu.obs import deviceprof

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# synthetic trace-viewer fixtures
# ---------------------------------------------------------------------------

def _meta(pid, pname, threads):
    out = [{"ph": "M", "pid": pid, "name": "process_name",
            "args": {"name": pname}}]
    for tid, tname in threads.items():
        out.append({"ph": "M", "pid": pid, "tid": tid,
                    "name": "thread_name", "args": {"name": tname}})
    return out


def _x(name, dur_us, pid=1, tid=1, ts=0, args=None):
    return {"ph": "X", "pid": pid, "tid": tid, "ts": ts,
            "dur": dur_us, "name": name, "args": args}


def _cpu_style_trace():
    """The TFRT-CPU shape: one host process, python-tracer events
    (args=None) interleaved with hlo-tagged op events on pool
    threads. 1000us of device-op time total: 900 in jit_chunk, 60 in
    an eager jit_exp, 40 carrying no identity at all."""
    events = _meta(1, "python", {1: "MainThread", 2: "pool-0"})
    events += [
        # python tracer noise — must NOT count as device time
        _x("FuncGraph", 5000, tid=1),
        _x("backend_compile", 2000, tid=1),
        # the chunk's ops, spread across two pool threads
        _x("fusion.1", 500, tid=1,
           args={"hlo_module": "jit_chunk", "hlo_op": "fusion.1"}),
        _x("fft.2", 300, tid=2,
           args={"hlo_module": "jit_chunk", "hlo_op": "fft.2"}),
        _x("dot_general.3", 100, tid=2,
           args={"hlo_module": "jit_chunk", "hlo_op": "dot.3"}),
        # eager constant-folding module (the real residual shape)
        _x("exp.4", 60, tid=2,
           args={"hlo_module": "jit_exp", "hlo_op": "exp.4"}),
        # an op event with NO module identity -> unattributed bucket
        _x("mystery_op", 40, tid=2, args={"hlo_op": "mystery_op"}),
    ]
    return {"displayTimeUnit": "ns", "traceEvents": events}


def _tpu_style_trace():
    """The TPU shape: a /device: process whose ``XLA Ops`` lane
    carries scope paths in ``tf_op``; the ``Steps`` lane overlaps the
    op lane and must be EXCLUDED (else every second double-counts)."""
    events = _meta(7, "/device:TPU:0 (chip 0)",
                   {1: "Steps", 2: "XLA Ops"})
    events += _meta(3, "python", {1: "MainThread"})
    events += [
        _x("step 0", 1000, pid=7, tid=1),          # Steps row: skip
        _x("fusion.9", 700, pid=7, tid=2,
           args={"tf_op": "jit(chunk)/driver/chunk/interp/fusion.9"}),
        _x("fft.1", 200, pid=7, tid=2,
           args={"tf_op": "jit(chunk)/driver/chunk/fft.1"}),
        _x("copy.2", 100, pid=7, tid=2, args={}),  # lane event, no scope
        _x("host python", 4000, pid=3, tid=1),     # host: skip
    ]
    return {"displayTimeUnit": "ns", "traceEvents": events}


def _write_capture(tmp_path, trace, name="host"):
    d = tmp_path / "cap" / "plugins" / "profile" / "2026_08_06"
    d.mkdir(parents=True, exist_ok=True)
    with gzip.open(d / f"{name}.trace.json.gz", "wb") as f:
        f.write(json.dumps(trace).encode())
    return str(tmp_path / "cap")


# ---------------------------------------------------------------------------
# event selection
# ---------------------------------------------------------------------------

def test_cpu_event_selection_ignores_python_tracer():
    events, lanes = deviceprof.device_op_events(_cpu_style_trace())
    # 5 hlo-tagged events; the 7s of python tracer noise excluded
    assert len(events) == 5
    assert sum(e["dur"] for e in events) == 1000
    assert {ln["thread"] for ln in lanes} == {"MainThread", "pool-0"}


def test_tpu_lane_selection_excludes_step_rows():
    events, lanes = deviceprof.device_op_events(_tpu_style_trace())
    # the Steps row (1000us) and host python (4000us) are excluded;
    # the unscoped copy on the op lane IS device time
    assert sum(e["dur"] for e in events) == 1000
    assert len(lanes) == 1 and lanes[0]["thread"] == "XLA Ops"


# ---------------------------------------------------------------------------
# attribution
# ---------------------------------------------------------------------------

def test_module_attribution_maps_jit_chunk_to_span():
    events, _ = deviceprof.device_op_events(_cpu_style_trace())
    s = deviceprof.attribute_events(events, ["driver", "driver/chunk"])
    # jit_chunk -> chunk -> driver/chunk leaf
    assert s["spans"]["driver/chunk"]["device_s"] == pytest.approx(
        900e-6)
    assert s["spans"]["driver/chunk"]["via"] == {"module": 3}
    # jit_exp has no span; grouped under its module name, explicitly
    assert s["spans"]["exp"]["device_s"] == pytest.approx(60e-6)
    assert s["spans"]["exp"]["via"] == {"module-name": 1}
    # the identity-free op is the residual, never dropped
    assert s["unattributed"] == {"mystery_op": pytest.approx(40e-6)}
    assert s["total_device_s"] == pytest.approx(1000e-6)
    assert s["attributed_s"] + s["unattributed_s"] == pytest.approx(
        s["total_device_s"])


def test_scope_prefix_attribution_beats_module():
    events, _ = deviceprof.device_op_events(_tpu_style_trace())
    s = deviceprof.attribute_events(events, ["driver/chunk",
                                             "driver/chunk/interp"])
    # deepest matching scope component wins: interp claims fusion.9
    assert s["spans"]["driver/chunk/interp"]["device_s"] == \
        pytest.approx(700e-6)
    assert s["spans"]["driver/chunk"]["device_s"] == pytest.approx(
        200e-6)
    assert s["unattributed"] == {"copy.2": pytest.approx(100e-6)}


def test_explicit_module_map_override():
    events, _ = deviceprof.device_op_events(_cpu_style_trace())
    s = deviceprof.attribute_events(
        events, [], module_map={"jit_exp": "driver/warmup"})
    assert s["spans"]["driver/warmup"]["device_s"] == pytest.approx(
        60e-6)


def test_span_leaf_map_prefers_shallowest_on_ambiguity():
    m = deviceprof.span_leaf_map(["a/chunk", "chunk", "b/c/chunk"])
    assert m["chunk"] == "chunk"


def test_attribute_capture_roundtrip(tmp_path):
    cap = _write_capture(tmp_path, _cpu_style_trace())
    s = deviceprof.attribute_capture(cap, span_paths=["driver/chunk"])
    assert deviceprof.validate_summary(s) == []
    assert s["trace_files"] == 1
    path = deviceprof.write_summary(cap, s)
    assert deviceprof.read_summary(cap) == json.load(open(path))
    compact = deviceprof.compact_summary(s)
    assert compact["spans"]["driver/chunk"]["device_s"] == \
        s["spans"]["driver/chunk"]["device_s"]
    assert "lanes" not in compact


# ---------------------------------------------------------------------------
# schema validation: malformation is loud
# ---------------------------------------------------------------------------

def test_validate_summary_catches_dropped_time(tmp_path):
    cap = _write_capture(tmp_path, _cpu_style_trace())
    s = deviceprof.attribute_capture(cap)
    assert deviceprof.validate_summary(s) == []
    bad = dict(s, attributed_s=0.0)       # time silently dropped
    assert any("time dropped" in p
               for p in deviceprof.validate_summary(bad))
    assert deviceprof.validate_summary({"schema": 99}) != []
    assert deviceprof.validate_summary("not a dict") != []
    bad2 = dict(s, fraction_attributed=1.5)
    assert any("fraction" in p for p in deviceprof.validate_summary(bad2))


# ---------------------------------------------------------------------------
# executions: the one count the drift gate normalises by
# ---------------------------------------------------------------------------

def test_capture_executions_land_in_the_summary(tmp_path):
    cap = _write_capture(tmp_path, _cpu_style_trace())
    s = deviceprof.attribute_capture(cap, executions=5)
    assert s["executions"] == 5
    assert deviceprof.compact_summary(s)["executions"] == 5
    assert deviceprof.attribute_capture(cap)["executions"] is None
    from tools import prof

    assert prof._per_exec(s, 1.0) == pytest.approx(0.2)
    assert prof._per_exec({"executions": None}, 1.0) == 1.0


# ---------------------------------------------------------------------------
# the drift gate (tools/prof.py)
# ---------------------------------------------------------------------------

def _summarize(tmp_path, name, scale=1.0):
    trace = _cpu_style_trace()
    for e in trace["traceEvents"]:
        if e.get("ph") == "X" and (e.get("args") or {}).get(
                "hlo_module") == "jit_chunk":
            e["dur"] = e["dur"] * scale
    cap = _write_capture(tmp_path / name, trace)
    s = deviceprof.attribute_capture(cap, span_paths=["driver/chunk"])
    deviceprof.write_summary(cap, s)
    return cap


def test_diff_self_is_clean_inflation_regresses(tmp_path):
    from tools.prof import main as prof_main

    a = _summarize(tmp_path, "a")
    assert prof_main(["diff", a, a]) == 0
    b = _summarize(tmp_path, "b", scale=10.0)   # inflated chunk span
    assert prof_main(["diff", a, b]) == 2
    # the reverse direction is an improvement, not a regression
    assert prof_main(["diff", b, a]) == 1


def test_diff_band_tolerates_noise(tmp_path):
    from tools.prof import main as prof_main

    a = _summarize(tmp_path, "a")
    b = _summarize(tmp_path, "b", scale=1.10)   # 10% < 25% band
    assert prof_main(["diff", a, b]) == 0
    # tightening the band makes the same delta a regression... but
    # only past the absolute floor, which 90us of drift is not
    assert prof_main(["diff", a, b, "--tol-pct", "5"]) == 0
    assert prof_main(["diff", a, b, "--tol-pct", "5",
                      "--abs-floor", "10e-6"]) == 2


def test_diff_of_bench_jsons_with_embedded_summaries(tmp_path):
    from tools.prof import main as prof_main

    a = _summarize(tmp_path, "a")
    b = _summarize(tmp_path, "b", scale=10.0)

    def bench_json(cap, path):
        s = deviceprof.read_summary(cap)
        payload = {"stages": [], "profiles": [
            {"dir": cap, "stage": "n16", "rev": "abc", "bytes": 1,
             "attributed": True,
             "summary": deviceprof.compact_summary(s)}]}
        with open(path, "w") as f:
            json.dump(payload, f)
        return str(path)

    ja = bench_json(a, tmp_path / "A.json")
    jb = bench_json(b, tmp_path / "B.json")
    assert prof_main(["diff", ja, ja]) == 0
    assert prof_main(["diff", ja, jb]) == 2


def test_check_and_archive_refuse_malformed(tmp_path):
    from tools.prof import main as prof_main

    cap = _summarize(tmp_path, "a")
    assert prof_main(["check", cap]) == 0
    raw = deviceprof.find_trace_files(cap)
    assert raw
    # corrupt the summary: archive must exit 2 and keep the raw trace
    s = deviceprof.read_summary(cap)
    s["attributed_s"] = -1.0
    with open(os.path.join(cap, deviceprof.SUMMARY_NAME), "w") as f:
        json.dump(s, f)
    assert prof_main(["check", cap]) == 2
    assert prof_main(["archive", cap]) == 2
    assert deviceprof.find_trace_files(cap) == raw
    # restore a valid summary: archive prunes the raw trace, keeps it
    s["attributed_s"] = s["total_device_s"] - s["unattributed_s"]
    deviceprof.write_summary(cap, s)
    assert prof_main(["archive", cap]) == 0
    assert deviceprof.find_trace_files(cap) == []
    assert deviceprof.validate_summary(deviceprof.read_summary(cap)) \
        == []


# ---------------------------------------------------------------------------
# manifest compat + collision fix
# ---------------------------------------------------------------------------

def test_obs_compare_reads_old_and_new_profile_manifests():
    from tools.obs import _profile_entries

    old = _profile_entries({"profiles": ["/tmp/p/n256_ab12cd3"]})
    assert old["n256"]["dir"] == "/tmp/p/n256_ab12cd3"
    assert old["n256"]["attributed"] is False
    new = _profile_entries({"profiles": [
        {"dir": "/tmp/p/n256_ab12cd3", "stage": "n256", "rev": "ab1",
         "bytes": 123, "attributed": True,
         "summary": {"total_device_s": 1.0}}]})
    assert new["n256"]["summary"]["total_device_s"] == 1.0


def test_stage_profile_dir_decollides_repeated_labels():
    import argparse

    from bench import stage_profile_dir

    args = argparse.Namespace(profile="/tmp/prof",
                              profile_stages="n256,packed*")
    used = {}
    d1 = stage_profile_dir(args, "n256", "abc", used=used)
    d2 = stage_profile_dir(args, "n256", "abc", used=used)
    d3 = stage_profile_dir(args, "n256", "abc", used=used)
    assert d1 == "/tmp/prof/n256_abc"
    assert d2 == "/tmp/prof/n256_abc_2"
    assert d3 == "/tmp/prof/n256_abc_3"
    assert stage_profile_dir(args, "nomatch", "abc", used=used) == ""
    # without a tracking dict the legacy single-call behavior holds
    assert stage_profile_dir(args, "n256", "abc") == d1


# ---------------------------------------------------------------------------
# the real thing: a CPU-backend capture of the solo driver chunk
# ---------------------------------------------------------------------------

def test_real_capture_attributes_driver_chunk(tmp_path):
    """Acceptance: capture the driver chunk with jax.profiler on the
    CPU backend, attribute the trace against the run's ledger, and
    account for >= 90% of device-lane time — residual explicit."""
    import jax
    import jax.numpy as jnp

    from ibamr_tpu import obs
    from ibamr_tpu.utils.timers import profile_trace

    cap = str(tmp_path / "cap")
    led = str(tmp_path / "led")

    @jax.jit
    def chunk(x):
        for _ in range(8):
            x = jnp.fft.irfftn(jnp.fft.rfftn(
                jnp.sin(x) * 1.0001), s=x.shape)
        return x

    x = jnp.ones((64, 64), jnp.float32)
    chunk(x).block_until_ready()          # compile outside the capture
    with obs.ledger(os.path.join(led, "ledger.jsonl")):
        with profile_trace(cap, stage="solo"):
            for step in range(40):
                with obs.span("driver/chunk", step=step, block_on=x):
                    x = chunk(x)
            jax.block_until_ready(x)

    # satellite: profile_trace rode the bus — the ledger shows the
    # capture landing as a span plus a `profile` record naming the dir
    records = obs.read_ledger(os.path.join(led, "ledger.jsonl"))
    prof_recs = [r for r in records if r.get("kind") == "profile"]
    assert prof_recs and prof_recs[0]["capture_dir"] == cap
    assert prof_recs[0]["stage"] == "solo"
    assert any(r.get("kind") == "span"
               and r.get("path") == "profile_trace"
               for r in records)

    assert deviceprof.find_trace_files(cap), "profiler wrote no trace"
    summary = deviceprof.attribute_capture(cap, ledger=led)
    assert deviceprof.validate_summary(summary) == []
    total = summary["total_device_s"]
    assert total > 0
    # the chunk span nests under profile_trace's own span (PR 10
    # satellite), so its ledger path is profile_trace/driver/chunk
    chunk_s = sum(v["device_s"] for p, v in summary["spans"].items()
                  if p.endswith("driver/chunk"))
    # the acceptance bar: the solo chunk claims >= 90% of device time
    assert chunk_s >= 0.90 * total, (
        f"driver/chunk={chunk_s} of {total}: "
        f"{json.dumps(deviceprof.compact_summary(summary))[:800]}")
    # and the residual is explicit: every unclaimed second is named
    assert summary["attributed_s"] + summary["unattributed_s"] == \
        pytest.approx(total, rel=1e-6)
