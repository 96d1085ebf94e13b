"""Tier-1 graph-contract gate (PR 8 tentpole satellite): every
artifact in the contract registry must census EXACTLY to its committed
budget in GRAPH_BUDGETS.json — a regression (new scatter, un-fused
FFT, host transfer in the scan body, dropped donation, f64 widening)
fails, and an IMPROVEMENT also fails with instructions to ratchet the
budget (``python tools/graph_audit.py --tighten``), so the committed
file never drifts from reality in either direction.

Measurement is in-process (the suite already isolates per module and
``measure_artifact`` wraps the build in ``disable_x64()``, so the
budgets match the production x64-off posture even though conftest
enables x64). The flagship-scale artifact rides the slow tier.

Also the two repo-wide static gates: the jit-safety linter must be
clean over ``ibamr_tpu/`` (waivers allowed, bare waivers are not),
and the first-wave f64-request fixes stay pinned by asserting the
fixed call sites trace warning-free under x64-off.
"""

import os
import warnings

import jax
import pytest

from ibamr_tpu.analysis.contracts import (
    ARTIFACTS, REPO_ROOT, diff_budget, load_budgets, measure_artifact)
from ibamr_tpu.analysis.jit_lint import lint_paths

BUDGETS = load_budgets()

# Whole-step / chunk lowerings each cost 4-10 s of XLA compile (by
# --durations on the tier-1 box); with the fast tier already within
# ~30 s of the 870 s gate they ride the slow tier per the conftest
# re-tier policy. The fast tier keeps the acceptance-critical
# contracts: the fused substep (zero-scatter / <=2-FFT), verified
# donation via donated_step (same step graph as solo_step), all four
# transfer engines, and the lane fetch path. The slow-tiered
# artifacts stay fully gated by `tools/graph_audit.py` (CI) and the
# full-suite run.
_SLOW_LIGHT = {"solo_step", "solo_step_bf16", "solo_chunk",
               "donated_chunk", "fleet_chunk", "open_channel_step",
               "sharded_chunk", "fleet_mesh_chunk"}

_PARAMS = [
    pytest.param(name, marks=pytest.mark.slow)
    if art.heavy or name in _SLOW_LIGHT else name
    for name, art in ARTIFACTS.items()
]


@pytest.mark.parametrize("name", _PARAMS)
def test_artifact_matches_committed_budget(name):
    assert name in BUDGETS, (
        f"artifact {name!r} has no committed budget — run "
        f"`python tools/graph_audit.py --tighten` and commit "
        f"GRAPH_BUDGETS.json")
    measured = measure_artifact(name)
    d = diff_budget(name, measured, BUDGETS[name])
    assert not d.regressions and not d.missing, (
        f"graph contract REGRESSED for {name!r}: "
        + ", ".join(f"{m}={got} (budget {bound})"
                    for m, (got, bound) in d.regressions.items())
        + (f"; unmeasurable budget metric(s) {d.missing}"
           if d.missing else ""))
    assert not d.improvements, (
        f"graph contract IMPROVED for {name!r}: "
        + ", ".join(f"{m}={got} (budget {bound})"
                    for m, (got, bound) in d.improvements.items())
        + " — ratchet it in with `python tools/graph_audit.py "
          "--tighten` and commit GRAPH_BUDGETS.json")


def test_headline_invariants_are_budgeted():
    """The acceptance-critical invariants must be present in the
    committed file itself, not just implied: the fused spectral substep
    is zero-scatter / <=2-FFT, the donated artifacts actually alias,
    and no artifact tolerates a host transfer inside a scan body."""
    fused = BUDGETS["fused_substep"]
    assert fused["scatter_ops"] == 0 and fused["scatter_prims"] == 0
    assert fused["fft_ops"] <= 2
    assert BUDGETS["donated_step"]["donated_args"] >= 1
    assert BUDGETS["donated_chunk"]["donated_args"] >= 1
    for name, b in BUDGETS.items():
        assert b["host_transfers_in_scan"] == 0, name
    # PR 15: the pod comm-layer pins are in the committed file — the
    # three sharded artifacts budget their collective census and the
    # S2 exchange's halo pushes are ppermutes
    for name in ("sharded_chunk", "fftpar_transpose",
                 "lagrangian_exchange"):
        assert BUDGETS[name]["collective_prims"] > 0, name
    assert BUDGETS["lagrangian_exchange"]["ppermute_prims"] > 0
    assert BUDGETS["sharded_chunk"]["ppermute_prims"] > 0
    assert BUDGETS["sharded_chunk"]["all_to_all_prims"] > 0
    # PR 16: the comm is HIDDEN, and the file pins it. The pipelined
    # pencil transpose splits each of the 4 all_to_alls in 2 tiles
    # (bytes unchanged); the unhidden counts are strictly below the
    # PR-15 baselines (fftpar 4 -> 1, lagrangian 6 -> 2) and the
    # hidden_fraction floors hold every comm-bearing artifact above
    # its measured overlap
    assert BUDGETS["fftpar_transpose"]["all_to_all_prims"] == 8
    assert BUDGETS["fftpar_transpose"]["unhidden_collectives"] <= 1
    assert BUDGETS["fftpar_transpose"]["hidden_fraction"] >= 80
    assert BUDGETS["lagrangian_exchange"]["unhidden_collectives"] <= 2
    assert BUDGETS["lagrangian_exchange"]["hidden_fraction"] >= 80
    for name in ("sharded_chunk", "fftpar_transpose",
                 "lagrangian_exchange", "fleet_mesh_chunk",
                 "krylov_reduce"):
        assert "hidden_fraction" in BUDGETS[name], name
    # the lane-mesh fleet chunk moves no data between lanes
    assert BUDGETS["fleet_mesh_chunk"]["collective_prims"] == 0
    assert BUDGETS["fleet_mesh_chunk"]["unhidden_collectives"] == 0


def test_jit_lint_clean_over_package():
    report = lint_paths([os.path.join(REPO_ROOT, "ibamr_tpu")])
    assert report["files_scanned"] > 20
    active = [f for f in report["findings"] if not f["waived"]]
    assert active == [], (
        "jit-lint findings in ibamr_tpu/ — fix them or add a "
        "justified `# jitlint: ok(<rule>): <reason>` waiver:\n"
        + "\n".join(f"  {f['path']}:{f['line']}: [{f['rule']}] "
                    f"{f['message']}" for f in active))
    # every waiver on the books must carry a reason and be in use
    for w in report["waivers"]:
        assert w["reason"], w
        assert w["used"], f"stale waiver: {w}"


def test_first_wave_f64_fixes_stay_warning_free():
    """Pin the first-wave findings: ins_open's stabilized-PPM boundary
    ramp and the spectral Gaussian filter symbol must trace without
    'Explicitly requested dtype float64' warnings under the production
    x64-off config (the warning means silent truncation)."""
    from ibamr_tpu.solvers.spectral_plan import gaussian_filter_symbol

    with jax.enable_x64(False):
        with warnings.catch_warnings(record=True) as rec:
            warnings.simplefilter("always")
            gaussian_filter_symbol((16, 16), (1.0 / 16, 1.0 / 16),
                                   width=2.0)
            measure_artifact("open_channel_step")
        bad = [w for w in rec
               if "requested dtype" in str(w.message).lower()]
        assert bad == [], [str(w.message) for w in bad]
