"""Pluggable auto-engine resolution (PR 12 satellite).

``use_fast_interaction=None`` ("auto") no longer hard-codes the packed
promotion inline: resolution routes through
``ibamr_tpu/models/engine_resolver.py`` (env override -> tuning DB ->
built-in heuristic), and the RESOLVED name — never "auto" — is stamped
on the integrator and lands in the flight-recorder fingerprint, so the
serving cache key always reflects what actually runs.
"""

import json

import pytest

from ibamr_tpu.models.engine_resolver import (ENV_ENGINE, ENV_TUNING_DB,
                                              RESOLVED_ENGINES,
                                              default_rule,
                                              fallback_chain,
                                              load_tuning_db,
                                              resolve_engine)

_SUPPORT = 2                          # ib4 half-width


def test_default_rule_promotion_band():
    # large tile-divisible grid with enough markers -> packed
    assert default_rule((128, 128, 128), 100_000, _SUPPORT) == "packed"
    # too few markers -> scatter
    assert default_rule((128, 128, 128), 100, _SUPPORT) == "scatter"
    # not tile-divisible -> scatter
    assert default_rule((12, 12, 12), 100_000, _SUPPORT) == "scatter"
    # tile-divisible but below the make_geometry minimum extent
    assert default_rule((8, 8, 8), 100_000, _SUPPORT) == "scatter"


def test_env_override_wins_and_validates():
    env = {ENV_ENGINE: "packed"}
    assert resolve_engine((8, 8, 8), 10, _SUPPORT, env=env) == "packed"
    # "auto"/empty defer to the rest of the chain
    assert resolve_engine((8, 8, 8), 10, _SUPPORT,
                          env={ENV_ENGINE: "auto"}) == "scatter"
    assert resolve_engine((8, 8, 8), 10, _SUPPORT,
                          env={ENV_ENGINE: ""}) == "scatter"
    # a typo'd engine dies at build time, never poisons a cache key
    with pytest.raises(ValueError, match="unknown transfer engine"):
        resolve_engine((8, 8, 8), 10, _SUPPORT,
                       env={ENV_ENGINE: "packedd"})
    assert "auto" not in RESOLVED_ENGINES


def test_tuning_db_most_specific_wins(tmp_path):
    db = tmp_path / "tuning.json"
    db.write_text(json.dumps({"schema": 1, "entries": [
        {"engine": "packed", "n_cells": 256},
        # generic marker-band entry FIRST...
        {"engine": "mxu", "markers_min": 50, "markers_max": 500},
        # ...but the later, MORE SPECIFIC entry wins the overlap:
        # file order is not load-bearing for differently-specific
        # entries (the PR-12 first-match order-dependence is gone)
        {"engine": "packed_bf16", "n_cells": 64,
         "markers_min": 50, "markers_max": 500},
    ]}))
    env = {ENV_TUNING_DB: str(db)}
    assert resolve_engine((256, 256, 256), 10_000, _SUPPORT,
                          env=env) == "packed"
    # overlap: both the mxu band and the n_cells=64 entry match;
    # higher specificity (n_cells + band > band alone) wins
    assert resolve_engine((64, 64, 64), 100, _SUPPORT,
                          env=env) == "packed_bf16"
    # off the pinned n_cells, the generic band entry still serves
    assert resolve_engine((32, 32, 32), 100, _SUPPORT,
                          env=env) == "mxu"
    # no entry matches -> heuristic
    assert resolve_engine((64, 64, 64), 10, _SUPPORT,
                          env=env) == "scatter"
    # env override outranks the DB
    assert resolve_engine((256, 256, 256), 10_000, _SUPPORT,
                          env={ENV_TUNING_DB: str(db),
                               ENV_ENGINE: "packed_bf16"}) == "packed_bf16"


def test_tuning_db_equal_specificity_keeps_file_order(tmp_path):
    db = tmp_path / "tuning.json"
    db.write_text(json.dumps({"schema": 1, "entries": [
        {"engine": "mxu", "markers_min": 50, "markers_max": 500},
        {"engine": "packed", "markers_min": 40, "markers_max": 600},
    ]}))
    # both match at score 2 -> the deterministic tiebreak is file
    # order (earlier wins), never dict-iteration accident
    assert resolve_engine((64, 64, 64), 100, _SUPPORT,
                          env={ENV_TUNING_DB: str(db)}) == "mxu"


def test_tuning_db_platform_and_provenance_gates(tmp_path):
    db = tmp_path / "tuning.json"
    db.write_text(json.dumps({"schema": 1, "entries": [
        # platform match-field pin: only serves tpu queries
        {"engine": "packed", "platform": "tpu"},
        # provenance pin: measured on tpu, must not steer cpu runs
        {"engine": "mxu", "markers_min": 50, "markers_max": 500,
         "provenance": {"platform": "tpu", "timestamp": "2026-08-06"}},
    ]}))
    env = {ENV_TUNING_DB: str(db)}
    # under the forced-cpu test backend both entries are skipped
    assert resolve_engine((64, 64, 64), 100, _SUPPORT,
                          env=env) == "scatter"
    # an explicit tpu query reaches them (10 markers: outside the mxu
    # band, so the platform-pinned entry serves)
    assert resolve_engine((64, 64, 64), 10, _SUPPORT, env=env,
                          platform="tpu") == "packed"
    # cpu provenance serves cpu queries
    db.write_text(json.dumps({"schema": 1, "entries": [
        {"engine": "mxu", "markers_min": 50, "markers_max": 500,
         "provenance": {"platform": "cpu",
                        "timestamp": "2026-08-06"}}]}))
    assert resolve_engine((64, 64, 64), 100, _SUPPORT,
                          env=env) == "mxu"


def test_tuning_db_disable_and_spectral_dtype_match(tmp_path):
    db = tmp_path / "tuning.json"
    db.write_text(json.dumps({"schema": 1, "entries": [
        {"engine": "mxu", "markers_min": 50, "markers_max": 500,
         "spectral_dtype": "bf16"}]}))
    env = {ENV_TUNING_DB: str(db)}
    # a bf16-pinned entry does not serve the default-f32 query...
    assert resolve_engine((64, 64, 64), 100, _SUPPORT,
                          env=env) == "scatter"
    # ...but serves the bf16 one
    assert resolve_engine((64, 64, 64), 100, _SUPPORT, env=env,
                          spectral_dtype="bf16") == "mxu"
    # IBAMR_TUNING_DB=none opts out of the committed default DB
    assert resolve_engine((64, 64, 64), 100, _SUPPORT,
                          env={ENV_TUNING_DB: "none"}) == "scatter"


def test_tuning_db_unknown_schema_rejected(tmp_path):
    db = tmp_path / "tuning.json"
    db.write_text(json.dumps({"schema": 99, "entries": []}))
    with pytest.raises(ValueError, match="schema"):
        load_tuning_db(str(db))


def test_malformed_tuning_db_raises(tmp_path):
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"winners": []}))
    with pytest.raises(ValueError, match="entries"):
        load_tuning_db(str(bad))
    # a configured-but-broken DB is an error, not a silent fallback
    with pytest.raises(ValueError):
        resolve_engine((64, 64, 64), 10, _SUPPORT,
                       env={ENV_TUNING_DB: str(bad)})
    with pytest.raises(ValueError, match="unknown transfer engine"):
        ok_shape = tmp_path / "typo.json"
        ok_shape.write_text(json.dumps(
            {"entries": [{"engine": "warp9"}]}))
        resolve_engine((64, 64, 64), 10, _SUPPORT,
                       env={ENV_TUNING_DB: str(ok_shape)})


def test_resolved_engine_stamped_on_integrator_and_fingerprint():
    from ibamr_tpu.models.shell3d import build_shell_example
    from ibamr_tpu.serve.aot_cache import step_fingerprint

    integ, _ = build_shell_example(n_cells=8, n_lat=6, n_lon=8,
                                   radius=0.25, aspect=1.2,
                                   stiffness=1.0,
                                   rest_length_factor=0.75, mu=0.05,
                                   use_fast_interaction=None)
    # tiny grid: the heuristic resolves auto -> scatter, and the
    # RESOLVED name (not "auto") is what the fingerprint carries
    assert integ.ib.engine_name == "scatter"
    fp = step_fingerprint(integ)
    assert fp["engine"] == "scatter"


def test_explicit_engine_stamped_too():
    from ibamr_tpu.models.shell3d import build_shell_example

    integ, _ = build_shell_example(n_cells=8, n_lat=6, n_lon=8,
                                   radius=0.25, aspect=1.2,
                                   stiffness=1.0,
                                   rest_length_factor=0.75, mu=0.05,
                                   use_fast_interaction=False)
    assert integ.ib.engine_name == "scatter"


# ---------------------------------------------------------------------------
# the one table: every row, the pin of what auto means, and its owner
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", RESOLVED_ENGINES)
def test_every_table_row_builds_and_is_accepted_everywhere(name):
    """A row of ENGINES is a whole engine: it builds at 16^3 (Pallas
    rows in interpret mode, as on any CPU), degrades to scatter, and is
    a valid value of the input key, the keyword and the autotuner's
    menu."""
    from ibamr_tpu.models.shell3d import build_shell_example
    from ibamr_tpu.tune.space import DEFAULT_ENGINES, enumerate_space
    from ibamr_tpu.utils.input_db import parse_input_string

    chain = fallback_chain(name)
    assert chain[0] == name and chain[-1] == "scatter"
    assert len(chain) == len(set(chain))
    integ, _ = build_shell_example(n_cells=16, n_lat=8, n_lon=8,
                                   use_fast_interaction=name)
    assert integ.ib.engine_name == name
    assert (integ.ib.fast is None) == (name == "scatter")
    db = parse_input_string(f'''
CartesianGeometry {{ n_cells = 16, 16, 16 }}
Shell {{ n_lat = 8 n_lon = 8 }}
IBMethod {{ transfer_engine = "{name}" }}
''')
    integ2, _ = build_shell_example(input_db=db)
    assert integ2.ib.engine_name == name
    assert type(integ2.ib.fast) is type(integ.ib.fast)
    assert name in DEFAULT_ENGINES
    cands, pruned = enumerate_space((16, 16, 16), 64, 4, engines=(name,),
                                    spectral_dtypes=("f32",),
                                    chunk_lengths=(1,))
    assert [c.engine for c in cands] == [name] and not pruned


@pytest.mark.parametrize("n,platform,want", [
    (128, "tpu", "packed"),
    (256, "tpu", "packed_bf16"),
    (128, "cpu", "packed"),
    (256, "cpu", "packed"),
])
def test_committed_db_resolves_the_benchmark_cells(n, platform, want):
    """What ``auto`` means for the two shell configurations of
    BENCHMARK.json under the committed TUNING_DB.json: the engine each
    cell's ledger lines were measured on."""
    assert resolve_engine((n,) * 3, 99856, 4, env={},
                          spectral_dtype="f32",
                          platform=platform) == want


def test_ops_knows_no_engine_names():
    """ops/ holds the engines; which exist, what each falls back to and
    which get probed is models/engine_resolver.py's alone."""
    import pathlib

    import ibamr_tpu.ops as ops

    for src in pathlib.Path(ops.__path__[0]).glob("*.py"):
        text = src.read_text()
        for word in ("ENGINE_FALLBACKS", "normalize_engine_name",
                     "fallback_chain", '"packed_bf16"', '"pallas_packed"',
                     '"hybrid_bf16"', '"mxu"'):
            assert word not in text, (src.name, word)


@pytest.mark.parametrize("name", ["pallas", "hybrid", "packed_f16", "fast"])
def test_name_outside_the_table_is_refused_everywhere(name):
    """A name that is no row (``pallas`` was one until PR 30;
    docs/MIGRATING.md names the survivor for each that left) is refused
    by the override, the keyword and the input key alike."""
    from ibamr_tpu.models.shell3d import build_shell_example
    from ibamr_tpu.utils.input_db import parse_input_string

    with pytest.raises(ValueError, match="unknown transfer engine"):
        resolve_engine((64, 64, 64), 10, _SUPPORT, env={ENV_ENGINE: name})
    with pytest.raises(ValueError, match="use_fast_interaction"):
        build_shell_example(n_cells=16, n_lat=8, n_lon=8,
                            use_fast_interaction=name)
    with pytest.raises(ValueError, match="transfer_engine"):
        build_shell_example(input_db=parse_input_string(
            f'IBMethod {{ transfer_engine = "{name}" }}'))
