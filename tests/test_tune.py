"""Measured-search autotuner (PR 13 tentpole): ibamr_tpu/tune/.

Space enumeration prunes statically (tile/extent/z-tile geometry, the
wall-BC bf16 refusal, Pallas compile-probe gating) so the runner never
times a candidate that can't ship; trials compile through the AOT
executable cache (the second trial of a family is a HIT — zero
recompiles); winners persist in a schema-v1, provenance-stamped
TUNING_DB.json that models/engine_resolver.py consults with
most-specific-match semantics — and because the resolved name is
fingerprint material, a DB change PRODUCES A NEW SERVE CACHE KEY.
``tools/tune.py check`` is the revalidation gate (exit 0/1/2), and the
committed seed DB itself is tier-1-validated here.
"""

import json
import os
import subprocess
import sys

import pytest

from ibamr_tpu.models.engine_resolver import (DEFAULT_DB_PATH,
                                              RESOLVED_ENGINES)
from ibamr_tpu.tune import db as tdb
from ibamr_tpu.tune.runner import TrialResult, run_trial
from ibamr_tpu.tune.space import Candidate, enumerate_space

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SUPPORT = 4                          # the real IB_4 half-width


# ---------------------------------------------------------------------------
# space: enumeration + static pruning
# ---------------------------------------------------------------------------

def test_space_static_geometry_pruning():
    engines = ("scatter", "packed", "packed_bf16")
    # non-8-divisible xy: every non-scatter candidate pruned
    cands, pruned = enumerate_space((12, 12, 12), 4096, _SUPPORT,
                                    engines=engines,
                                    spectral_dtypes=("f32",),
                                    chunk_lengths=(1,))
    assert {c.engine for c in cands} == {"scatter"}
    assert all("8-tile" in r for c, r in pruned)
    # 8-divisible xy below the make_geometry minimum extent
    cands, pruned = enumerate_space((8, 8, 12), 4096, _SUPPORT,
                                    engines=engines,
                                    spectral_dtypes=("f32",),
                                    chunk_lengths=(1,))
    assert {c.engine for c in cands} == {"scatter"}
    assert all("minimum" in r for c, r in pruned)
    # eligible xy: the z extent is not blocked, so 12 serves
    cands, pruned = enumerate_space((16, 16, 12), 4096, _SUPPORT,
                                    engines=engines,
                                    spectral_dtypes=("f32",),
                                    chunk_lengths=(1,))
    assert {c.engine for c in cands} == set(engines) and not pruned
    # every grid point is accounted for, nothing silently dropped
    total = len(engines) * 1 * 1
    assert len(cands) + len(pruned) == total


def test_space_small_marker_configs_keep_packed():
    # the n_markers >= 4096 promotion heuristic is exactly what the
    # tuner replaces with measurement — it must NOT prune
    cands, _ = enumerate_space((16, 16, 16), 128, _SUPPORT,
                               engines=("scatter", "packed"),
                               spectral_dtypes=("f32",),
                               chunk_lengths=(1,))
    assert {c.engine for c in cands} == {"scatter", "packed"}


def test_space_bf16_wall_bc_refusal():
    cands, pruned = enumerate_space((16, 16, 16), 128, _SUPPORT,
                                    engines=("scatter", "packed"),
                                    spectral_dtypes=("f32", "bf16"),
                                    chunk_lengths=(1,),
                                    bc="dirichlet")
    assert all(c.spectral_dtype == "f32" for c in cands)
    bf16_pruned = [(c, r) for c, r in pruned
                   if c.spectral_dtype == "bf16"]
    assert len(bf16_pruned) == 2
    assert all("periodic-only" in r for _, r in bf16_pruned)


def test_space_probe_gating_memoized():
    calls = []

    def probe(engine):
        calls.append(engine)
        raise RuntimeError("pallas lowering died")

    cands, pruned = enumerate_space(
        (16, 16, 16), 128, _SUPPORT,
        engines=("scatter", "pallas_packed"),
        spectral_dtypes=("f32", "bf16"), chunk_lengths=(1, 4),
        probe_fn=probe)
    # probe called ONCE per probed engine, never for scatter
    assert calls == ["pallas_packed"]
    assert {c.engine for c in cands} == {"scatter"}
    pp = [(c, r) for c, r in pruned if c.engine == "pallas_packed"]
    assert len(pp) == 4                     # 2 dtypes x 2 lengths
    assert all("compile probe failed" in r for _, r in pp)


def test_space_unknown_engine_raises():
    with pytest.raises(ValueError, match="unknown engine"):
        enumerate_space((16, 16, 16), 128, _SUPPORT,
                        engines=("scatterr",))


# ---------------------------------------------------------------------------
# runner: trials through the AOT cache
# ---------------------------------------------------------------------------

def test_trial_through_cache_second_is_hit():
    from ibamr_tpu.serve.aot_cache import ExecutableCache

    cache = ExecutableCache()
    cand = Candidate(engine="scatter", spectral_dtype="f32",
                     chunk_length=2)
    t1 = run_trial(cand, n_cells=8, n_lat=6, n_lon=8, reps=1,
                   cache=cache)
    assert t1.error is None
    assert t1.steps_per_s > 0
    assert not t1.cache_hit and t1.recompiles == 1
    # the second trial of the same candidate family is a cache HIT:
    # zero recompiles — a search re-run (or check's re-race) costs
    # only warm execution
    t2 = run_trial(cand, n_cells=8, n_lat=6, n_lon=8, reps=1,
                   cache=cache)
    assert t2.error is None
    assert t2.cache_hit and t2.recompiles == 0


def test_trial_build_failure_reported_not_raised():
    # the 8-tile does not divide n = 12 and the trial builds with
    # engine_fallback=False — the error must land in the result, the
    # grid must survive
    res = run_trial(Candidate(engine="packed"), n_cells=12, n_lat=6,
                    n_lon=8, reps=1)
    assert res.error is not None
    assert res.steps_per_s == 0.0


# ---------------------------------------------------------------------------
# db: round-trip, schema, merge, shadow lint
# ---------------------------------------------------------------------------

def test_db_roundtrip_and_validation(tmp_path):
    doc = tdb.new_db()
    prov = tdb.make_provenance("cpu", "2026-08-06",
                               device_kind="host", git_rev="abc1234")
    tdb.merge_entry(doc, tdb.make_entry(
        "packed", n=[128, 128, 128], markers_min=100,
        markers_max=1000, spectral_dtype="f32", platform="cpu",
        measured={"steps_per_s": 74.4}, provenance=prov))
    assert tdb.validate_db(doc) == []
    p = tmp_path / "db.json"
    tdb.save_db(doc, str(p))
    back = tdb.load_db(str(p))
    assert back == doc


def test_db_validation_rejects_bad_shapes():
    doc = {"schema": 99, "entries": [
        {"engine": "warp9"},
        {"engine": "packed", "markers_min": 500, "markers_max": 100},
        {"engine": "mxu", "n_cells": "big"},
        {"engine": "scatter", "measured": {"steps_per_s": "fast"}},
        {"engine": "packed_bf16", "provenance": {"timestamp": "x"}},
    ]}
    problems = tdb.validate_db(doc)
    assert any("schema" in p for p in problems)
    assert any("RESOLVED_ENGINES" in p for p in problems)
    assert any("empty marker band" in p for p in problems)
    assert any("n_cells" in p for p in problems)
    assert any("steps_per_s" in p for p in problems)
    assert any("platform" in p for p in problems)


def test_db_provenance_requires_platform():
    with pytest.raises(ValueError, match="platform"):
        tdb.make_provenance("", "2026-08-06")


def test_db_merge_replaces_same_identity():
    doc = tdb.new_db()
    prov = tdb.make_provenance("cpu", "2026-08-06")
    e = dict(n=[16, 16, 16], markers_min=64, markers_max=256,
             spectral_dtype="f32", platform="cpu", provenance=prov)
    tdb.merge_entry(doc, tdb.make_entry(
        "scatter", measured={"steps_per_s": 10.0}, **e))
    tdb.merge_entry(doc, tdb.make_entry(
        "packed", measured={"steps_per_s": 20.0}, **e))
    # re-publication replaced in place, no shadowed duplicate accreted
    assert len(doc["entries"]) == 1
    assert doc["entries"][0]["engine"] == "packed"
    # a different platform's winner for the same key COEXISTS
    prov_tpu = tdb.make_provenance("tpu", "2026-08-06")
    e2 = {**e, "platform": "tpu", "provenance": prov_tpu}
    tdb.merge_entry(doc, tdb.make_entry(
        "packed_bf16", measured={"steps_per_s": 30.0}, **e2))
    assert len(doc["entries"]) == 2
    assert tdb.validate_db(doc) == []


def test_db_shadow_lint_flags_dead_entries():
    entries = [
        # generic band entry, first in file...
        {"engine": "mxu", "markers_min": 50, "markers_max": 500},
        # ...fully covers this equal-specificity narrower band: every
        # query entry[1] matches, entry[0] wins the file-order tie
        {"engine": "packed", "markers_min": 100, "markers_max": 400},
        # NOT shadowed: matches queries outside the band too
        {"engine": "packed_bf16", "n_cells": 64},
    ]
    shadows = tdb.shadowed_entries(entries)
    assert [(j, i) for j, i, _ in shadows] == [(1, 0)]
    problems = tdb.validate_db({"schema": 1, "entries": entries})
    assert any("shadow lint" in p and "entry[1]" in p
               for p in problems)
    # a MORE specific later entry is not shadowed (it wins its overlap)
    entries2 = [
        {"engine": "mxu", "markers_min": 50, "markers_max": 500},
        {"engine": "packed", "n_cells": 64,
         "markers_min": 100, "markers_max": 400},
    ]
    assert tdb.shadowed_entries(entries2) == []


# ---------------------------------------------------------------------------
# resolver -> serve cache key propagation (the ISSUE-pinned contract)
# ---------------------------------------------------------------------------

def test_db_change_produces_new_serve_cache_key(tmp_path,
                                                monkeypatch):
    from ibamr_tpu.models.shell3d import build_shell_example
    from ibamr_tpu.serve.aot_cache import cache_key, step_fingerprint

    def build():
        integ, _ = build_shell_example(
            n_cells=16, n_lat=8, n_lon=16, radius=0.25, aspect=1.2,
            stiffness=1.0, rest_length_factor=0.75, mu=0.05,
            use_fast_interaction=None)
        return integ

    monkeypatch.setenv("IBAMR_TUNING_DB", "none")
    base = build()
    assert base.ib.engine_name == "scatter"     # heuristic at 16^3/128

    db_path = tmp_path / "tuning.json"
    doc = tdb.new_db()
    tdb.merge_entry(doc, tdb.make_entry(
        "packed", n=[16, 16, 16], markers_min=64, markers_max=256,
        spectral_dtype="f32", platform="cpu",
        measured={"steps_per_s": 99.0},
        provenance=tdb.make_provenance("cpu", "2026-08-06")))
    tdb.save_db(doc, str(db_path))
    monkeypatch.setenv("IBAMR_TUNING_DB", str(db_path))
    tuned = build()
    # the DB steered resolution, and the RESOLVED name is fingerprint
    # material: publishing a DB change produces a NEW serve cache key
    # (stale executables can never serve a re-tuned config)
    assert tuned.ib.engine_name == "packed"
    fp_base, fp_tuned = step_fingerprint(base), step_fingerprint(tuned)
    assert fp_base["engine"] == "scatter"
    assert fp_tuned["engine"] == "packed"
    assert cache_key(fp_base) != cache_key(fp_tuned)


def test_committed_seed_db_skipped_on_cpu(monkeypatch):
    # acceptance: the committed tpu-measured seed must never steer a
    # CPU run — resolution falls through to the heuristic
    from ibamr_tpu.models.engine_resolver import resolve_engine

    monkeypatch.delenv("IBAMR_TUNING_DB", raising=False)
    assert os.path.exists(DEFAULT_DB_PATH)
    assert resolve_engine((256, 256, 256), 99856, _SUPPORT,
                          env={}) == "packed"
    assert resolve_engine((16, 16, 16), 128, _SUPPORT,
                          env={}) == "scatter"


# ---------------------------------------------------------------------------
# the committed seed DB is itself tier-1-validated
# ---------------------------------------------------------------------------

def test_committed_tuning_db_valid():
    doc = tdb.load_db(DEFAULT_DB_PATH)
    assert doc.get("schema") == 1
    assert tdb.validate_db(doc) == []
    for e in doc["entries"]:
        assert e["engine"] in RESOLVED_ENGINES
        # every committed number must say where it came from
        prov = e.get("provenance") or {}
        assert prov.get("platform")
        assert prov.get("timestamp")


# ---------------------------------------------------------------------------
# tools/tune.py check: the revalidation gate
# ---------------------------------------------------------------------------

def _cpu_doc(winner="packed", winner_sps=90.0, runner="scatter",
             runner_sps=30.0):
    doc = tdb.new_db()
    tdb.merge_entry(doc, tdb.make_entry(
        winner, n=[16, 16, 16], markers_min=64, markers_max=256,
        spectral_dtype="f32", platform="cpu",
        measured={"steps_per_s": winner_sps, "chunk_length": 1,
                  "reps": 2, "n_lat": 8, "n_lon": 16,
                  "runner_up": runner,
                  "runner_up_steps_per_s": runner_sps,
                  "runner_up_chunk_length": 1,
                  "margin": round(winner_sps / runner_sps, 4)},
        provenance=tdb.make_provenance("cpu", "2026-08-06")))
    return doc


def _fake_retime(rates):
    def retime(cand, **kw):
        return TrialResult(candidate=cand,
                           steps_per_s=rates[cand.engine])
    return retime


def test_check_exit_codes():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import tune as tune_cli

    # winner holds at its recorded rate -> 0
    rc, _ = tune_cli.check_db(
        _cpu_doc(), platform="cpu",
        retime_fn=_fake_retime({"packed": 91.0, "scatter": 31.0}))
    assert rc == 0
    # ranking holds but the winner drifted beyond the band -> STALE 1
    rc, report = tune_cli.check_db(
        _cpu_doc(), platform="cpu",
        retime_fn=_fake_retime({"packed": 50.0, "scatter": 31.0}))
    assert rc == 1
    assert any("stale" in ln for ln in report)
    # the runner-up now WINS beyond the band -> REGRESSED 2
    rc, report = tune_cli.check_db(
        _cpu_doc(), platform="cpu",
        retime_fn=_fake_retime({"packed": 30.0, "scatter": 90.0}))
    assert rc == 2
    assert any("RANKING FLIP" in ln for ln in report)
    # schema/lint problems -> 2 without any re-timing
    rc, report = tune_cli.check_db(
        {"schema": 99, "entries": []}, platform="cpu",
        retime_fn=_fake_retime({}))
    assert rc == 2
    # provenance-mismatched entries are NOT re-timed (schema/lint
    # only) -> the committed tpu seed costs CI nothing
    rc, report = tune_cli.check_db(
        _cpu_doc(), platform="tpu", retime_fn=_fake_retime({}))
    assert rc == 0
    assert any("not re-timed" in ln for ln in report)


def test_check_cli_seed_db_exits_0():
    # acceptance: `tools/tune.py check` exits 0 against the committed
    # seed on the CPU drill (tpu provenance -> schema + lint only)
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "tune.py"),
         "check"],
        capture_output=True, text=True, cwd=REPO, timeout=300)
    assert r.returncode == 0, r.stdout + r.stderr


@pytest.mark.slow
def test_check_cli_flipped_winner_exits_2(tmp_path):
    # acceptance: artificially flip the measured winner (the DB now
    # claims packed beats scatter at 16^3/128 markers on CPU — false)
    # and the gate's real re-race must exit 2
    doc = _cpu_doc(winner="packed", winner_sps=900.0,
                   runner="scatter", runner_sps=30.0)
    p = tmp_path / "flipped.json"
    tdb.save_db(doc, str(p))
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "tune.py"),
         "check", "--db", str(p), "--reps", "1"],
        capture_output=True, text=True, cwd=REPO, timeout=600)
    assert r.returncode == 2, r.stdout + r.stderr
    assert "RANKING FLIP" in r.stdout


# ---------------------------------------------------------------------------
# end-to-end: search -> publish -> resolve -> serve drill
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_search_publish_resolve_serve_roundtrip(tmp_path,
                                                monkeypatch):
    from ibamr_tpu.models.engine_resolver import resolve_engine
    from ibamr_tpu.serve.aot_cache import ExecutableCache
    from ibamr_tpu.tune.runner import db_entry_from_search, search

    cache = ExecutableCache()
    res = search(n_cells=16, n_lat=8, n_lon=16,
                 engines=("scatter", "packed"),
                 spectral_dtypes=("f32", "bf16"), chunk_lengths=(1,),
                 reps=2, probe=False, cache=cache)
    assert len(res.trials) == 4 and not res.pruned
    w = res.winner()
    assert w is not None and w.error is None
    entry = db_entry_from_search(res, platform="cpu",
                                 timestamp="2026-08-06")
    doc = tdb.new_db()
    tdb.merge_entry(doc, entry)
    assert tdb.validate_db(doc) == []
    p = tmp_path / "db.json"
    tdb.save_db(doc, str(p))
    # the resolver returns the MEASURED winner for the matching key
    resolved = resolve_engine(
        (16, 16, 16), 128, _SUPPORT,
        env={"IBAMR_TUNING_DB": str(p)},
        spectral_dtype=w.candidate.spectral_dtype, platform="cpu")
    assert resolved == w.candidate.engine
    # ...and the warm-pool serve drill stays green under the new DB:
    # zero warm compiles, the contract's whole point
    monkeypatch.setenv("IBAMR_TUNING_DB", str(p))
    from ibamr_tpu.serve.router import cold_warm_drill

    drill = cold_warm_drill(n_cells=16, n_lat=8, n_lon=16, lanes=2,
                            steps=2, dt=5e-5,
                            spectral_dtype=w.candidate.spectral_dtype)
    assert drill["warm_compiles"] == 0
    assert drill["cold_ok"] and drill["warm_ok"]
