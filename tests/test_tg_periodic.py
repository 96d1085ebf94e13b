"""The Taylor-Green configuration (``tg_256``, PR 28) at sizes a CPU runs:
the example ``examples/navier_stokes/tgv3d/main.py`` through
``HierarchyDriver``, against the plain reference
``perfbench/reference/ins_periodic.py`` (numpy float64, its own PPM), through
the benchmark's harness and adapter.
"""

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ibamr_tpu import obs
from ibamr_tpu.obs import deviceprof
from ibamr_tpu.ops import convection
from ibamr_tpu.utils import parse_input_string
from ibamr_tpu.utils.hierarchy_driver import HierarchyDriver, RunConfig
from perfbench import harness, inputfile
from perfbench.reference import ins_periodic as reference

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXAMPLE = os.path.join(ROOT, "examples", "navier_stokes", "tgv3d")
CONFIG = harness.load_json(os.path.join(ROOT, "perfbench", "configs",
                                        "tg_256.json"))


def drive(seed, fault=None, control=None):
    """One rehearsal of the cell: the adapter's ``rehearse_keys`` (16^3 at
    the configuration's own CFL), 40 warm steps, a window, the last 20-step
    chunk against the reference, each reading against ``tg_256.json``'s own
    limits."""
    args = argparse.Namespace(workload="tg_256.advance", seed=seed,
                              seconds=0.5, trace=0, rehearse=True,
                              control=control)
    return harness.run(args, time.perf_counter(), require_chip=False,
                       fault=fault)


# (i), (vii): the program's chunk against the reference, through the harness.
# The limits are the configuration's, each the geometric middle of the chip's
# two readings at 256^3 (PERF.md 6a): du 0.01 between float32's 2.0e-4 and
# the control's 0.52, p 3e-3 between 6.2e-5 and 0.17, div 1e-5 between 1.2e-6
# and 8.1e-5.  At 16^3 here float32 reads du 3e-5, p 2e-5, div 3e-8 to 3e-7
# and the control 0.03, 0.06, 1e-3: the same sides of the same limits.
@pytest.mark.parametrize("seed", [2147483655, 11])
def test_chunk_against_the_reference(seed):
    res = drive(seed)
    assert res["correct"], res["compared"]
    assert set(res["compared"]) == {"window.du", "window.p", "window.div"}
    assert set(res["metrics"]) == {"setup_s", "step_ms"}
    assert res["attempted"] > 0 and res["failed"] == 0
    for c in res["compared"].values():
        assert c["value"] < 0.5 * c["limit"], res["compared"]


# (ii): what must NOT be correct
@pytest.mark.parametrize("fault", ["state_unchanged", "answer_altered"])
def test_a_wrong_chunk_is_not_correct(fault):
    res = drive(11, fault=fault)
    assert not res["correct"], res["compared"]


def test_the_control_is_not_correct():
    """The reference with bfloat16 transform operands, put in the program's
    place, fails every limit; the program in the same run passes."""
    res = drive(11, control=CONFIG["control"])
    assert res["correct"]
    over = {k for k, c in res["control"].items() if c["value"] > c["limit"]}
    assert over == {"control.window.du", "control.window.p",
                    "control.window.div"}, res["control"]


# (iii): the limiter's branches, reference against program
def test_ppm_face_values_on_a_profile_with_an_extremum_and_a_front():
    n, g = 48, convection._G
    i = np.arange(n)
    a = (np.sin(2 * np.pi * i / n)                 # smooth, two extrema
         + 1.5 * (np.abs(i - 12) < 4)              # a plateau: two fronts
         + 0.8 * np.exp(-0.5 * (i - 30.0) ** 2)    # a narrow peak
         + 0.3 * (i == 40))                        # a one-cell spike
    adv = np.where(i % 5 == 0, 0.0, np.cos(2 * np.pi * i / n + 0.3))
    want = reference.ppm_face_values(np.pad(a, g, mode="wrap"),
                                     np.append(adv, adv[0]), 0)[:n]
    got = convection._face_value_padded(
        convection._pad_wrap(jnp.asarray(a), 0, g), jnp.asarray(adv), 0, n,
        g, "ppm", shift=0)
    np.testing.assert_allclose(np.asarray(got), want, rtol=0, atol=1e-14)
    # every branch was taken: flattened extrema, both overshoot repairs,
    # limited and unlimited slopes, all three upwind cases
    centred = 0.5 * (a + np.roll(a, 1))
    assert np.sum(np.abs(want - centred) > 1e-3) > n // 4
    assert {0.0} < set(np.sign(adv))


# (iv), (v): main.py from its own input3d
@pytest.fixture(scope="module")
def example_run(tmp_path_factory):
    """``main.py`` on the example's own ``input3d`` (32^3) for 40 steps with a
    checkpoint at step 20, at Re = 10, where the operator's own dissipation
    is small beside the resolved one, and dt = 0.01 (so that the trapezoid
    of the enstrophy over a chunk is good to a part in a thousand)."""
    out = tmp_path_factory.mktemp("tgv3d")
    text = open(os.path.join(EXAMPLE, "input3d")).read()
    text = inputfile.set_keys(text, {
        "Main": {"log_file": f"{out}/metrics.jsonl",
                 "restart_interval": 20,
                 "restart_dirname": f"{out}/restart"},
        "INSStaggeredHierarchyIntegrator": {"mu": 0.1, "dt": 0.01,
                                            "num_steps": 40}})
    inp = out / "input3d"
    inp.write_text(text)
    mod = harness.load_module(os.path.join(EXAMPLE, "main.py"),
                              "tgv3d_under_test")
    saved = {}
    save = mod.save_checkpoint

    def spy(directory, state, step):
        saved[step] = state
        return save(directory, state, step)
    mod.save_checkpoint = spy
    final = mod.main(["main.py", str(inp)])
    recs = [json.loads(ln) for ln in open(out / "metrics.jsonl")]
    return mod, str(inp), str(out / "restart"), saved, final, recs


def test_example_series_and_energy_balance(example_run):
    recs = example_run[5]
    assert [r["step"] for r in recs] == [0, 20, 40]
    # the source's initial values, to discretisation error: the mean of
    # sin^2 cos^2 cos^2 over a uniform grid is 1/8 exactly; the compact
    # curl reads (sin(h/2)/(h/2))^2 = 0.9968 of 0.375 at h = 2 pi / 32
    assert recs[0]["ke"] == pytest.approx(0.125, rel=1e-4)   # f32 sums
    assert recs[0]["enstrophy"] == pytest.approx(0.375, rel=5e-3)
    assert recs[0]["t"] == 0.0 and recs[2]["t"] == pytest.approx(0.4)
    nu = 0.1
    for r in recs:
        assert r["max_div"] < 2e-5          # float32 rounding of O(1) / h
        assert r["eps_enstrophy"] == pytest.approx(2 * nu * r["enstrophy"])
    # dE_k/dt = -2 nu enstrophy over each chunk (trapezoid).  Tolerance 0.5%:
    # the compact curl makes the viscous part exact for the MAC Laplacian;
    # read here at 0.16%, of which 0.12% is the trapezoid's own over a decay
    # at rate 6 nu ((0.6 * 0.2)^2 / 12); PPM's own dissipation is the rest
    for r0, r1 in zip(recs, recs[1:]):
        rate = (r1["ke"] - r0["ke"]) / (r1["t"] - r0["t"])
        eps = 0.5 * (r0["eps_enstrophy"] + r1["eps_enstrophy"])
        assert rate == pytest.approx(-eps, rel=5e-3), (rate, eps)

def test_example_restart_is_exact(example_run):
    mod, inp, rst, saved, final, _ = example_run
    assert sorted(saved) == [20, 40]
    template = mod.build_tgv_example(mod.parse_input_file(inp))[1]
    restored, step, _ = mod.restore_checkpoint(rst, template, step=20)
    assert step == 20
    for got, want in zip(jax.tree_util.tree_leaves(restored),
                         jax.tree_util.tree_leaves(saved[20])):
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # and ``main.py <input> <restart_dir> 20`` ends where the first run did
    again = mod.main(["main.py", inp, rst, "20"])
    for got, want in zip(jax.tree_util.tree_leaves(again),
                         jax.tree_util.tree_leaves(final)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# (vi): the fluid-only chunk names its own phases
def test_fluid_only_chunk_carries_the_fluid_phases():
    mod = harness.load_module(os.path.join(EXAMPLE, "main.py"),
                              "tgv3d_under_test")
    text = inputfile.set_keys(
        open(os.path.join(EXAMPLE, "input3d")).read(),
        {"CartesianGeometry": {"n_cells": [16, 16, 16]}})
    integ, state = mod.build_tgv_example(parse_input_string(text))
    before = len(obs.programs())
    HierarchyDriver(integ, RunConfig(dt=0.08, num_steps=2,
                                     health_interval=2)).run(state)
    progs = obs.programs()[before:]
    assert [p["name"] for p in progs] == ["driver/chunk[2]"]
    op_names, phases = deviceprof.programs_names(progs)
    assert set(phases.values()) == {"fluid", "fluid/convect", "fluid/rhs",
                                    "fluid/transforms"}
    # one opening of ``fluid``, and nothing named between it and its parts
    for name in op_names.values():
        assert name.count("/fluid/") <= 1, name
        for part in ("convect", "rhs", "transforms"):
            if f"/{part}/" in name:
                assert f"/fluid/{part}/" in name, name

