"""The lid-driven cavity configuration (``cavity_256``, PR 32) at sizes a CPU
runs: the example ``examples/navier_stokes/cavity3d/main.py`` through
``HierarchyDriver``, against the plain reference
``perfbench/reference/ins_walls.py`` (numpy float64, its own wall ghosts, its
solves by sine and cosine transforms), through the benchmark's harness and
adapter.
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
from perfbench.reference import ins_walls as reference

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXAMPLE = os.path.join(ROOT, "examples", "navier_stokes", "cavity3d")
CONFIG = harness.load_json(os.path.join(ROOT, "perfbench", "configs",
                                        "cavity_256.json"))


def drive(seed, fault=None, control=None):
    """One rehearsal of the cell: the adapter's ``rehearse_keys`` (16^3 at
    the configuration's own CFL), 40 warm steps, a window, the last 20-step
    chunk against the reference, each reading against ``cavity_256.json``'s
    own limits."""
    args = argparse.Namespace(workload="cavity_256.advance", seed=seed,
                              seconds=0.5, trace=0, rehearse=True,
                              control=control)
    return harness.run(args, time.perf_counter(), require_chip=False,
                       fault=fault)


def small_db(n=8):
    text = inputfile.set_keys(
        open(os.path.join(EXAMPLE, "input3d")).read(),
        {"CartesianGeometry": {"n_cells": [n, n, n]},
         "INSStaggeredHierarchyIntegrator": {"dt": 0.2 / n}})
    return text, inputfile.parse(text)


# The program's chunk against the reference, through the harness.  The limits
# are the configuration's, each between the chip's two readings at 256^3
# (PERF.md 6a).  At 16^3 here float32 reads du 3e-5, p 5e-6, div 3e-8 and the
# control 0.38, 0.12, 3e-5: the same sides of the same limits.
@pytest.mark.parametrize("seed", [2147483655, 11, 32])
def test_chunk_against_the_reference(seed):
    res = drive(seed)
    assert res["correct"], res["compared"]
    assert set(res["compared"]) == {"window.du", "window.p", "window.div"}
    assert set(res["metrics"]) == {"setup_s", "step_ms"}
    assert res["attempted"] > 0 and res["failed"] == 0
    for c in res["compared"].values():
        assert c["value"] < 0.5 * c["limit"], res["compared"]


@pytest.mark.parametrize("fault", ["state_unchanged", "answer_altered"])
def test_a_wrong_chunk_is_not_correct(fault):
    res = drive(11, fault=fault)
    assert not res["correct"], res["compared"]


def test_the_control_is_not_correct():
    """The reference with bfloat16 operands in every axis transform, put in
    the program's place, fails every limit; the program in the same run
    passes."""
    res = drive(11, control=CONFIG["control"])
    assert res["correct"]
    over = {k for k, c in res["control"].items() if c["value"] > c["limit"]}
    assert over == {"control.window.du", "control.window.p",
                    "control.window.div"}, res["control"]


def test_load_adapter_takes_the_walls_adapter():
    adapter = harness.load_adapter(os.path.join(ROOT, CONFIG["adapter"]))
    assert adapter.BUILDER == "build_cavity_example"
    assert set(adapter.SPIED) == {"save", "restore"}
    assert CONFIG["reduced"] == [] and CONFIG["architecture"] is None


# The reference's solves against a dense solve of the same operator: the
# tridiagonal matrices written out (end rows -3 for cell-centred Dirichlet,
# -1 for Neumann, the (n - 1)-node Dirichlet matrix for the pinned normal
# component), summed over the axes as Kronecker products.
def _matrix(kind, n, h):
    m = n - 1 if kind == "pinned" else n
    a = (np.diag(-2.0 * np.ones(m)) + np.diag(np.ones(m - 1), 1)
         + np.diag(np.ones(m - 1), -1))
    a[0, 0] = a[-1, -1] = {"dirichlet": -3.0, "neumann": -1.0,
                           "pinned": -2.0}[kind]
    return a / (h * h)


@pytest.mark.parametrize("which", [0, 1, 2, 3],
                         ids=["u", "v", "w", "pressure"])
def test_reference_solves_against_a_dense_solve(which):
    n = 8
    ref = reference.WallReference(small_db(n)[1])
    kinds = ref.kinds[which]
    mats = [_matrix(k, n, 1.0 / n) for k in kinds]
    eye = [np.eye(m.shape[0]) for m in mats]
    lap = sum(np.kron(np.kron(*parts[:2]), parts[2]) for parts in (
        [mats[e] if e == d else eye[e] for e in range(3)] for d in range(3)))
    alpha, beta = (100.0, -0.5e-3) if which < 3 else (0.0, 1.0)
    rhs = np.random.default_rng(which).standard_normal((n, n, n))
    if which == 3:
        rhs -= rhs.mean()               # the Neumann problem's compatibility
    got = ref.helmholtz(rhs, which, alpha, beta)
    ref.close()
    inner = tuple(slice(1, None) if k == "pinned" else slice(None)
                  for k in kinds)
    op = alpha * np.eye(lap.shape[0]) + beta * lap
    # the dense operator applied to the answer gives the right-hand side
    # back (for the singular Neumann matrix too), the pinned face reads 0,
    # and the Neumann answer has no mean
    np.testing.assert_allclose(op @ got[inner].ravel(), rhs[inner].ravel(),
                               rtol=0, atol=1e-10 * np.abs(rhs).max()
                               * (1.0 if which < 3 else n * n))
    if which < 3:
        want = np.linalg.solve(op, rhs[inner].ravel())
        np.testing.assert_allclose(got[inner].ravel(), want, rtol=0,
                                   atol=1e-13 * np.abs(want).max())
        lo = [slice(None)] * 3
        lo[which] = 0
        assert np.all(got[tuple(lo)] == 0.0)
    else:
        assert abs(got.mean()) < 1e-14 * np.abs(got).max()


def test_reference_convection_against_the_program_with_a_lid():
    """N(u) with the wall ghosts written out in the reference, against the
    program's ghost-padded operator in float64, on a field that is 0 on
    the pinned faces and has a moving lid."""
    n = 8
    ref = reference.WallReference(small_db(n)[1])
    rng = np.random.default_rng(5)
    u = [rng.standard_normal((n, n, n)) for _ in range(3)]
    for d in range(3):
        u[d][tuple(0 if e == d else slice(None) for e in range(3))] = 0.0
    want = ref.convective_rate(
        [reference.pad_walls(u[d], d, ref.wall_velocity) for d in range(3)])
    ref.close()
    got = convection.convective_rate_bc(
        tuple(jnp.asarray(c) for c in u), ref.dx, "ppm",
        wall_axes=(True, True, True), wall_tangential={(0, 1, 1): ref.u_lid})
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), w, rtol=0,
                                   atol=1e-12 * np.abs(w).max())


# main.py from its own input3d
@pytest.fixture(scope="module")
def example_run(tmp_path_factory):
    """``main.py`` on the example's own keys at 16^3 for 40 steps with a
    checkpoint at step 20."""
    out = tmp_path_factory.mktemp("cavity3d")
    text = open(os.path.join(EXAMPLE, "input3d")).read()
    text = inputfile.set_keys(text, {
        "Main": {"log_file": f"{out}/metrics.jsonl",
                 "restart_interval": 20,
                 "restart_dirname": f"{out}/restart"},
        "CartesianGeometry": {"n_cells": [16, 16, 16]},
        "INSStaggeredHierarchyIntegrator": {"dt": 0.0125, "num_steps": 40}})
    inp = out / "input3d"
    inp.write_text(text)
    mod = harness.load_module(os.path.join(EXAMPLE, "main.py"),
                              "cavity3d_under_test")
    saved = {}
    save = mod.save_checkpoint

    def spy(directory, state, step):
        saved[step] = state
        return save(directory, state, step)
    mod.save_checkpoint = spy
    final = mod.main(["main.py", str(inp)])
    recs = [json.loads(ln) for ln in open(out / "metrics.jsonl")]
    return mod, str(inp), str(out / "restart"), saved, final, recs


def test_example_series_from_rest(example_run):
    final, recs = example_run[4], example_run[5]
    assert [r["step"] for r in recs] == [0, 20, 40]
    assert recs[0]["ke"] == 0.0 and recs[0]["u_min"] == 0.0
    assert recs[2]["t"] == pytest.approx(0.5)
    for r0, r1 in zip(recs, recs[1:]):
        # the lid drags the fluid under it along x: by continuity the return
        # flow below is against it, the fluid goes down at the downstream
        # wall (x = 1) and comes up at the upstream one, and all of it grows
        assert r1["ke"] > r0["ke"]
        assert r1["u_min"] < r0["u_min"] <= 0.0
        assert r1["v_min"] < 0.0 < r1["v_max"]
        assert r1["x_v_min"] > 0.5 > r1["x_v_max"]
        assert r1["y_u_min"] > 0.5           # the layer is still near the lid
        assert r1["max_div"] < 2e-5          # float32 rounding of O(1) / h
    # no flow through any of the six walls, at the bit
    for d, c in enumerate(final.u):
        assert not np.any(np.asarray(jnp.take(c, 0, d)))


def test_example_restart_is_exact(example_run):
    mod, inp, rst, saved, final, _ = example_run
    assert sorted(saved) == [20, 40]
    template = mod.build_cavity_example(mod.parse_input_file(inp))[1]
    restored, step, _ = mod.restore_checkpoint(rst, template, step=20)
    assert step == 20
    # restore_mismatch 0: what the harness's recovery compares, leaf by leaf
    for got, want in zip(jax.tree_util.tree_leaves(restored),
                         jax.tree_util.tree_leaves(saved[20])):
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # and ``main.py <input> <restart_dir> 20`` ends where the first run did
    again = mod.main(["main.py", inp, rst, "20"])
    for got, want in zip(jax.tree_util.tree_leaves(again),
                         jax.tree_util.tree_leaves(final)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_walled_chunk_carries_the_fluid_phases():
    mod = harness.load_module(os.path.join(EXAMPLE, "main.py"),
                              "cavity3d_under_test")
    integ, state = mod.build_cavity_example(parse_input_string(small_db(16)[0]))
    # the lid's lift is a vector along its wall's axis, not a field
    assert [None if c is None else c.shape
            for c in integ.helmholtz_vel_solve.__self__._lift] == \
        [(1, 16, 1), None, None]
    before = len(obs.programs())
    HierarchyDriver(integ, RunConfig(dt=0.0125, num_steps=2,
                                     health_interval=2)).run(state)
    progs = obs.programs()[before:]
    assert [p["name"] for p in progs] == ["driver/chunk[2]"]
    op_names, phases = deviceprof.programs_names(progs)
    assert set(phases.values()) == {"fluid", "fluid/convect", "fluid/rhs",
                                    "fluid/transforms"}
    # one opening of ``fluid``, nothing named between it and its parts, and
    # every axis product under ``transforms``
    for name in op_names.values():
        assert name.count("/fluid/") <= 1, name
        for part in ("convect", "rhs", "transforms"):
            if f"/{part}/" in name:
                assert f"/fluid/{part}/" in name, name
        if "dot_general" in name:
            assert "/fluid/transforms/" in name, name
