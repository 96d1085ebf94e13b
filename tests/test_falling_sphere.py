"""The settling-sphere configuration (``falling_sphere_e4``, PR 34) at sizes a
CPU runs: the example ``examples/ConstraintIB/falling_sphere/main.py``
through ``HierarchyDriver``, the ConstraintIB strategy on a resolver-built
transfer engine, against the plain reference
``perfbench/reference/constraint_walls.py`` (numpy float64, its own
transfers, rigid fit and projections), through the benchmark's harness and
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
from ibamr_tpu.grid import StaggeredGrid
from ibamr_tpu.integrators.cib import RigidBodies
from ibamr_tpu.integrators.constraint_ib import (ConstraintIBMethod,
                                                 fill_sphere, project_rigid,
                                                 rigid_move)
from ibamr_tpu.integrators.ins import INSStaggeredIntegrator
from ibamr_tpu.models.engine_resolver import (construct_transfer_engine,
                                              resolve_engine)
from ibamr_tpu.obs import deviceprof
from ibamr_tpu.ops import interaction, stencils
from ibamr_tpu.utils import parse_input_string
from ibamr_tpu.utils.hierarchy_driver import HierarchyDriver, RunConfig
from perfbench import harness, inputfile
from perfbench.reference import constraint_walls as reference

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXAMPLE = os.path.join(ROOT, "examples", "ConstraintIB", "falling_sphere")
CONFIG = harness.load_json(os.path.join(ROOT, "perfbench", "configs",
                                        "falling_sphere_e4.json"))
F32 = jnp.float32


def drive(seed, fault=None, control=None):
    """One rehearsal of the cell: the adapter's ``rehearse_keys`` (the tank
    at 40 x 40 x 64 with a sphere of 12 cells, 7,153 markers, so the
    resolver names the packed engine), 40 warm steps, a window, the last
    20-step chunk against the reference, each reading against
    ``falling_sphere_e4.json``'s own limits."""
    args = argparse.Namespace(workload="falling_sphere_e4.advance",
                              seed=seed, seconds=0.5, trace=0,
                              rehearse=True, control=control)
    return harness.run(args, time.perf_counter(), require_chip=False,
                       fault=fault)


def small_text(n=(20, 20, 32), dt=0.004, **sphere):
    """The example's input at a tank of ``n`` cells (h = 5 mm at the
    default: the sphere is then 3 cells across unless ``diameter`` says
    otherwise)."""
    return inputfile.set_keys(
        open(os.path.join(EXAMPLE, "input3d")).read(),
        {"CartesianGeometry": {"n_cells": list(n)},
         "INSStaggeredHierarchyIntegrator": {"dt": dt},
         "Sphere": sphere})


def build(text):
    mod = harness.load_module(os.path.join(EXAMPLE, "main.py"),
                              "falling_sphere_under_test")
    return mod, mod.build_falling_sphere_example(parse_input_string(text))


def box_method(engine, n=(16, 16, 32), diameter=0.0375):
    """The strategy in a walled 0.1 x 0.1 x 0.2 box of 6.25 mm cells with a
    sphere of 6 cells (925 markers), on ``engine`` (a row of the
    resolver's table, built for this grid and cloud)."""
    grid = StaggeredGrid(n=n, x_lo=(0.0,) * 3, x_up=(0.1, 0.1, 0.2))
    ins = INSStaggeredIntegrator(grid, rho=960.0, mu=0.058,
                                 convective_op_type="ppm", dtype=F32,
                                 wall_axes=(True,) * 3)
    # off the grid's lattice: a marker exactly on a face or a centre has
    # its four-point stencil decided by the rounding of X / h
    X0 = fill_sphere((0.0512, 0.0487, 0.1213), 0.5 * diameter,
                     0.5 * grid.dx[0], dtype=F32)
    bodies = RigidBodies(body_id=jnp.zeros(X0.shape[0], jnp.int32),
                         n_bodies=1)
    method = ConstraintIBMethod(
        ins, bodies, density_ratio=[1120.0 / 960.0],
        gravity=(0.0, 0.0, -9.81), virtual_mass=0.5,
        fast=construct_transfer_engine(engine, grid, X0, "IB_4"),
        engine_name=engine)
    return method, method.initialize(X0)


def swirling(method, state, seed=3):
    """``state`` with a smooth solenoidal velocity that is 0 on the walls
    (the benchmark's own seeder) and the body already moving and
    spinning."""
    from perfbench.adapters import ins_walls

    ins = ins_walls.seed(method.ins, state.ins, seed, {"velocity_rms": 0.05})
    return state._replace(ins=ins, U_body=jnp.asarray(
        [[0.01, -0.02, -0.1, 3.0, -2.0, 1.0]], F32))


# -- the cell through the harness ------------------------------------------
# The limits are the configuration's, each between the chip's two readings
# at 160 x 160 x 256 (PERF.md 6a).  At 40 x 40 x 64 here float32 reads du
# 1e-4, p 2e-5, div 2e-8, dX 5e-6, dUb 5e-6, rigid 2e-5, body 3e-7 to 1.3e-6:
# the same side of the same limits.
def test_chunk_against_the_reference_on_the_packed_engine():
    res = drive(2147483655)
    assert res["correct"], res["compared"]
    assert set(res["compared"]) == {
        "window." + k for k in ("du", "p", "div", "dX", "dUb", "rigid",
                                "body")}
    assert set(res["metrics"]) == {"setup_s", "step_ms"}
    assert res["attempted"] > 0 and res["failed"] == 0
    for c in res["compared"].values():
        assert c["value"] < 0.5 * c["limit"], res["compared"]


@pytest.mark.parametrize("fault,reading", [
    ("state_unchanged", "du"), ("answer_altered", "du"),
    ("non_rigid", "rigid")])
def test_a_wrong_chunk_is_not_correct(fault, reading):
    res = drive(11, fault=fault)
    assert not res["correct"], res["compared"]
    c = res["compared"]["window." + reading]
    assert c["value"] > c["limit"], res["compared"]


def test_bf16_transfer_operands_alone_are_not_correct(monkeypatch):
    """The configuration guarantees exact float32 transfers.  The PROGRAM
    with nothing lowered but the transfers' operands (the ``packed_bf16``
    row, forced from outside) is not ``correct``, and ``body`` is what says
    so: the whole tank's ``du`` and the body's ``dUb`` average the loss away
    (here they read 2e-3 and 1e-4 to 3e-4, inside their limits), the faces
    inside the body, where each step replaces the velocity by the
    transfers' own output, read 2.3e-4 of the body's speed against 3e-7 to
    1.3e-6 in float32."""
    monkeypatch.setenv("IBAMR_TRANSFER_ENGINE", "packed_bf16")
    res = drive(11)
    assert not res["correct"], res["compared"]
    over = {k for k, c in res["compared"].items() if c["value"] > c["limit"]}
    assert over == {"window.body"}, res["compared"]
    c = res["compared"]["window.body"]
    assert c["value"] > 3.0 * c["limit"], res["compared"]


def test_the_control_is_not_correct():
    """The reference with bfloat16 operands in every axis transform and
    every transfer, put in the program's place, fails the velocity, the
    pressure, the divergence and the body's modes; its body is exactly
    rigid, so ``rigid`` is not a limit it can fail.  The program in the
    same run passes."""
    res = drive(11, control=CONFIG["control"])
    assert res["correct"], res["compared"]
    over = {k for k, c in res["control"].items() if c["value"] > c["limit"]}
    assert over >= {"control.window.du", "control.window.p",
                    "control.window.div", "control.window.dUb",
                    "control.window.body"}, \
        res["control"]
    assert "control.window.rigid" not in over


def test_load_adapter_takes_the_constraint_adapter():
    adapter = harness.load_adapter(os.path.join(ROOT, CONFIG["adapter"]))
    assert adapter.BUILDER == "build_falling_sphere_example"
    assert set(adapter.SPIED) == {"save", "restore"}
    assert set(adapter.faults) == {"non_rigid"}
    assert CONFIG["reduced"] == [] and CONFIG["architecture"] is None
    assert adapter.grid_n(inputfile.parse(open(os.path.join(
        ROOT, "perfbench", "configs", CONFIG["input_file"])).read())) == \
        [160, 160, 256]
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = "falling_sphere_e4.advance"
    listed = {m["name"] for m in bench["per_layer"]
              if cell in m.get("workloads", ())}
    assert {"constraint.rigid_ms", "constraint.impose_ms",
            "fluid.reproject_ms", "constraint.transform_roofline",
            "transfer.spread_ms", "transfer.repack_falls",
            "fluid.transform_ms"} <= listed
    # four solves are counted there and this step makes five
    assert "fluid.dense_roofline" not in listed


# -- the reference's own parts against the program's oracle ------------------
def test_reference_lattice_is_the_programs_sphere():
    text = small_text(diameter=0.03)
    _, (method, state) = build(text)
    ref = reference.ConstraintReference(inputfile.parse(text))
    ref.close()
    assert ref.body.shape == state.X.shape == (925, 3)
    # float32 rounding of a position of 0.1
    np.testing.assert_allclose(np.asarray(state.X, np.float64), ref.body,
                               rtol=0, atol=1e-8)


def test_reference_transfers_and_rigid_fit_against_the_oracle():
    """The reference's IB_4 interpolation, spreading and least-squares rigid
    fit, written out in numpy, against ``ops/interaction`` and
    ``project_rigid`` in float64: the same sums in another order."""
    text = small_text(diameter=0.03)
    db = inputfile.parse(text)
    ref = reference.ConstraintReference(db)
    ref.close()
    grid = StaggeredGrid(n=ref.n, x_lo=ref.x_lo,
                         x_up=tuple(db["CartesianGeometry"]["x_up"]))
    rng = np.random.default_rng(7)
    X = ref.body + rng.uniform(-1e-3, 1e-3, 3)
    u = [rng.standard_normal(ref.n) for _ in range(3)]
    F = rng.standard_normal(X.shape)
    st = ref.stencils(X)
    Xj = jnp.asarray(X)
    np.testing.assert_allclose(
        ref.interp(u, st), np.asarray(interaction.interpolate_vel(
            [jnp.asarray(c) for c in u], grid, Xj)), rtol=0, atol=1e-12)
    for got, want in zip(ref.spread(F, st), interaction.spread_vel(
            jnp.asarray(F), grid, Xj)):
        np.testing.assert_allclose(got, np.asarray(want), rtol=0,
                                   atol=1e-12 * np.abs(got).max())
    bodies = RigidBodies(jnp.zeros(len(X), jnp.int32), 1)
    np.testing.assert_allclose(
        reference.rigid_fit(X, F),
        np.asarray(project_rigid(Xj, bodies, jnp.asarray(F)))[0],
        rtol=0, atol=1e-9)
    modes = np.array([0.1, -0.2, 0.3, 30.0, -20.0, 10.0])
    np.testing.assert_allclose(
        reference.rigid_move(X, modes, 0.01),
        np.asarray(rigid_move(Xj, bodies, jnp.asarray(modes)[None], 0.01)),
        rtol=0, atol=1e-13)


@pytest.mark.parametrize("seed", [5, 2147483659])
def test_steps_against_the_reference_on_the_scatter_oracle(seed):
    """Ten steps of the program (float32, the scatter/gather oracle the
    resolver names at this size) from a seeded state against the reference.
    Float32 rounding through ten steps of a change of a few per cent reads
    du 5e-5, p 3e-5, dX and dUb 1e-6 here; a bfloat16 operand anywhere
    reads a hundred times that."""
    from perfbench.adapters import constraint_walls as adapter

    text = small_text(diameter=0.03)
    _, (method, state) = build(text)
    assert method.engine_name == "scatter" and method.fast is None
    state = adapter.seed(method, state, seed, CONFIG["seed_data"])
    out = state
    step = jax.jit(method.step)
    for _ in range(10):
        out = step(out, 0.004)
    host = lambda s: {k: np.asarray(v)                    # noqa: E731
                      for k, v in adapter.leaves(s).items()}
    ref = reference.ConstraintReference(inputfile.parse(text))
    r_in = reference.state_from_arrays(host(state))
    r_out = ref.advance(r_in, 10)
    ref.close()
    got = adapter.compare(r_out, host(out), r_in)
    for name, tol in (("du", 1e-3), ("p", 1e-3), ("div", 1e-7),
                      ("dX", 1e-4), ("dUb", 1e-4), ("rigid", 1e-4)):
        assert got[name] < tol, got


# -- the strategy itself -----------------------------------------------------
def test_engine_driven_step_equals_the_scatter_oracle():
    """Five steps with every transfer on the packed engine against the same
    steps on the scatter/gather oracle: the same float32 sums in another
    order."""
    fast, st_f = box_method("packed")
    slow, st_s = box_method("scatter")
    assert fast.fast is not None and slow.fast is None
    st_f, st_s = swirling(fast, st_f), swirling(slow, st_s)
    step_f, step_s = jax.jit(fast.step), jax.jit(slow.step)
    for _ in range(5):
        st_f, st_s = step_f(st_f, 2e-3), step_s(st_s, 2e-3)
    scale = max(float(jnp.max(jnp.abs(c))) for c in st_s.ins.u)
    for a, b in zip(st_f.ins.u, st_s.ins.u):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0,
                                   atol=2e-5 * scale)
    np.testing.assert_allclose(np.asarray(st_f.X), np.asarray(st_s.X),
                               rtol=0, atol=5e-8)     # a few ulp of 0.1
    np.testing.assert_allclose(np.asarray(st_f.U_body),
                               np.asarray(st_s.U_body), rtol=2e-5, atol=1e-6)


@pytest.mark.parametrize("engine", ["scatter", "packed"])
def test_driver_chunk_is_the_step_loop(engine):
    """``HierarchyDriver``'s chunk (the marker layout carried through the
    scan where the engine has one) against a loop of the jitted ``step``:
    to float32 rounding (the scan's body and the step fuse otherwise; with
    a layout the packed sums also run in the carried order)."""
    method, state = box_method(engine)
    state = swirling(method, state)
    drv = HierarchyDriver(method, RunConfig(dt=2e-3, num_steps=6,
                                            health_interval=6))
    assert drv._carried
    assert (method.init_carry(state) is not None) == (engine == "packed")
    out = drv.run(state)
    want, step = state, jax.jit(method.step)
    for _ in range(6):
        want = step(want, 2e-3)
    for a, b in zip(jax.tree_util.tree_leaves(out),
                    jax.tree_util.tree_leaves(want)):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        np.testing.assert_allclose(a, b, rtol=0,
                                   atol=2e-5 * max(np.abs(b).max(), 1e-30))


def test_body_stays_rigid_walls_stay_shut_over_40_steps():
    method, state = box_method("packed")
    X0 = np.asarray(state.X, np.float64)
    state = swirling(method, state)
    step = jax.jit(method.step)
    rng = np.random.default_rng(0)
    i, j = rng.integers(0, len(X0), (2, 2000))
    d0 = np.linalg.norm(X0[i] - X0[j], axis=1)
    far = d0 > 0.25 * 0.0375
    h = method.ins.grid.dx[0]
    euler = X0.copy()        # the same motions by the old update X + dt U_b
    for k in range(40):
        state = step(state, 2e-3)
        V, W = np.asarray(state.U_body[0], np.float64).reshape(2, 3)
        euler += 2e-3 * (V + np.cross(W, euler - euler.mean(axis=0)))
        if k % 10 == 9:
            # wall faces exactly 0, the divergence at float32 rounding of
            # speeds of 0.1 over h after the re-projection
            for d, c in enumerate(state.ins.u):
                assert not np.any(np.asarray(jnp.take(c, 0, d)))
            div = stencils.divergence(state.ins.u, method.ins.grid.dx)
            assert float(jnp.max(jnp.abs(div))) * h < 2e-7
    assert abs(float(state.U_body[0, 3])) > 0.1      # it does spin
    X = np.asarray(state.X, np.float64)
    d = np.linalg.norm(X[i] - X[j], axis=1)
    # float32 positions of 0.1 (ulp 7.5e-9) through 40 steps against
    # distances of a centimetre.  The configuration's ``rigid`` limit on
    # the chip (3e-3) guards gross deformation only; THIS pin holds the
    # exact move (it reads 2.7e-5): forward Euler of the same motions, in
    # float64, stretches every distance by (dt |W|)^2 / 2 a step and reads
    # 4.2e-4
    assert np.max(np.abs(d - d0)[far] / d0[far]) < 1e-4
    d_euler = np.linalg.norm(euler[i] - euler[j], axis=1)
    assert np.max(np.abs(d_euler - d0)[far] / d0[far]) > 3e-4


def test_early_free_fall():
    """Released from rest the first step is EXACTLY -a dt g with
    a = (s - 1) / (s + 1/2) (fluid and body at rest, so the projected
    velocity is 0); after 16 steps the fall is bracketed by that slope from
    above and, the fluid's own added-mass reaction and the young boundary
    layer acting from the first steps, by 55% of it from below."""
    method, state = box_method("scatter")
    s, dt, g = 1120.0 / 960.0, 1e-3, 9.81
    a = (s - 1.0) / (s + 0.5)
    step = jax.jit(method.step)
    state = step(state, dt)
    np.testing.assert_allclose(float(state.U_body[0, 2]), -a * dt * g,
                               rtol=1e-5)
    for _ in range(15):
        state = step(state, dt)
    v, v_free = float(state.U_body[0, 2]), -a * g * 16 * dt
    assert 1.02 * v_free <= v <= 0.55 * v_free, (v, v_free)
    # (the box's sphere sits a little off the axis, so it drifts a little)
    np.testing.assert_allclose(np.asarray(state.U_body[0, :2]), 0.0,
                               atol=1e-3)


def test_resolver_names_an_exact_engine_for_the_tank():
    """160 x 160 x 256 with 57,777 markers is no row of the tuning table
    (its rows pin cubic extents), whatever the platform: the built-in rule
    names the exact float32 packed engine, not ``packed_bf16`` by the
    256-wide last axis."""
    for platform in ("tpu", "cpu"):
        assert resolve_engine((160, 160, 256), 57777, 4, env={},
                              platform=platform) == "packed"
    assert resolve_engine((20, 20, 32), 925, 4, env={}) == "scatter"
    assert fill_sphere((0.05, 0.05, 0.1275), 0.0075, 0.1 / 320).shape == \
        (57777, 3)


# -- spans, counters, phases ---------------------------------------------------
@pytest.fixture(scope="module")
def traced_chunk():
    """A 2-step chunk of the strategy on the packed engine through the
    driver: ``(counters before, counters after, the chunk span, op_names,
    phases)``."""
    method, state = box_method("packed")
    before = dict(obs.metrics_snapshot()["counters"])
    n_prog, n_span = len(obs.programs()), len(obs.spans())
    HierarchyDriver(method, RunConfig(dt=2e-3, num_steps=2,
                                      health_interval=2)).run(state)
    progs = obs.programs()[n_prog:]
    assert [p["name"] for p in progs] == ["driver/chunk[2]"]
    chunk = [s for s in obs.spans()[n_span:] if s["path"] == "driver/chunk"]
    return (before, dict(obs.metrics_snapshot()["counters"]), chunk[0],
            *deviceprof.programs_names(progs))


def test_chunk_span_says_what_was_constrained_and_how(traced_chunk):
    before, after, span, _, _ = traced_chunk
    assert span["attrs"]["transfer_engine"] == "packed"
    assert span["attrs"]["constraint_bodies"] == 1
    assert span["attrs"]["constraint_markers"] == 925
    assert span["attrs"]["transform_path"] == "dense"
    # one count per body / marker and traced step (the scan's body is
    # traced once)
    assert after["constraint_bodies"] - before.get("constraint_bodies", 0) \
        == 1
    assert after["constraint_markers"] - before.get("constraint_markers",
                                                    0) == 925
    # five solves a step: 30 dense axis transforms, not the fluid step's 24
    key = "fluid_transform_dense_axes_total"
    assert after[key] - before.get(key, 0) == 30


def test_chunk_says_how_the_marker_values_crossed(traced_chunk):
    """One ``interpolate_vel`` and two ``spread_vel`` a step, each moving
    its rows between marker order and slot order once: the interpolation
    by a gather over ``slot_of_marker``, each spread by a gather through
    ``marker_of_slot``."""
    before, after, span, _, _ = traced_chunk
    assert span["attrs"]["transfer_marshal"] == "rows"
    for key, count in (("transfer_marker_gathers_total", 1),
                       ("transfer_marker_scatters_total", 0),
                       ("transfer_slot_gathers_total", 2)):
        assert after.get(key, 0) - before.get(key, 0) == count


def test_step_marshals_once_per_transfer():
    """The compiled step holds one gather with an index per marker under
    the interpolation's scope (three when every component crossed
    alone), and two gathers with an index per slot and no scatter with
    either under the spreads' scope."""
    from ibamr_tpu.analysis.graph_census import indexed_op_counts

    method, state = box_method("packed")
    ctx = jax.jit(method.init_carry)(state)
    text = jax.jit(lambda s, c: method.step_carried(s, c, 2e-3)).lower(
        state, ctx).compile().as_text()
    n = state.X.shape[0]
    slots = ctx.marker_of_slot.shape[0]
    assert slots != n
    assert indexed_op_counts(text, n, "ib/interp") == \
        {"gather": 1, "scatter": 0}
    assert indexed_op_counts(text, n, "ib/spread") == \
        {"gather": 0, "scatter": 0}
    assert indexed_op_counts(text, slots, "ib/spread") == \
        {"gather": 2, "scatter": 0}


@pytest.mark.parametrize("phase", [
    "ib/prep", "ib/refresh", "ib/refresh/repack", "ib/interp", "ib/spread",
    "constraint/rigid", "constraint/impose", "fluid", "fluid/convect",
    "fluid/rhs", "fluid/transforms", "fluid/reproject"])
def test_chunk_program_carries_phase(traced_chunk, phase):
    assert phase in set(traced_chunk[4].values())


def test_reprojection_transforms_count_as_transforms(traced_chunk):
    op_names, phases = traced_chunk[3], traced_chunk[4]
    inside = [i for i, name in op_names.items()
              if "/fluid/reproject/transforms/" in name]
    assert inside
    assert {phases[i] for i in inside} == {"fluid/transforms"}
    around = [i for i, name in op_names.items() if "/fluid/reproject/" in
              name and "/transforms/" not in name and i in phases]
    assert around and {phases[i] for i in around} == {"fluid/reproject"}
    assert deviceprof.phase_of(
        "jit(chunk)/while/body/fluid/reproject/transforms/dot_general") \
        == "fluid/transforms"
    # every product of the program is a transfer's or a solve's
    for name in op_names.values():
        if "dot_general" in name:
            assert ("/transforms/" in name or "/ib/interp/" in name
                    or "/ib/spread/" in name), name


def test_phase_readers_of_the_new_metrics(monkeypatch):
    """The four new readers on a hand-made phase table: the three times as
    they are, the share from ``work_constraint`` (five solves' least time
    over the time under ``fluid/transforms``), and None where the program
    has no such phase."""
    import importlib.util

    def reader(metric):
        spec = importlib.util.spec_from_file_location(
            "metric_" + metric.replace(".", "_"),
            os.path.join(ROOT, "perfbench", "metrics", metric + ".py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read

    table = {"fluid/transforms": 4.0, "fluid/reproject": 0.5,
             "constraint/rigid": 0.25, "constraint/impose": 0.75}
    ctx = {"_phase_ms": table, "grid_n": [160, 160, 256],
           "device": {"kind": "TPU v5 lite"},
           "peaks": harness.load_json(os.path.join(ROOT, "perfbench",
                                                   "peaks.json"))}
    assert reader("constraint.rigid_ms")(ctx) == 0.25
    assert reader("constraint.impose_ms")(ctx) == 0.75
    assert reader("fluid.reproject_ms")(ctx) == 0.5
    from perfbench import work_constraint, work_walls
    n = (160, 160, 256)
    assert work_constraint.transform_flops_per_step(n) == \
        work_walls.transform_flops_per_step(n) + 2 * 2 * (
            160 * 160 * 256) * (160 + 160 + 256)
    assert work_constraint.transform_bytes_per_step(n) == \
        5 * 4 * 160 * 160 * 256 * 4
    least_ms = 1e3 * max(
        work_constraint.transform_flops_per_step(n) / 197e12,
        work_constraint.transform_bytes_per_step(n) / 819e9)
    assert reader("constraint.transform_roofline")(ctx) == pytest.approx(
        100.0 * least_ms / 4.0)
    assert least_ms == pytest.approx(0.6402, rel=1e-3)
    empty = dict(ctx, _phase_ms={"fluid": 1.0})
    for m in ("constraint.rigid_ms", "constraint.impose_ms",
              "fluid.reproject_ms", "constraint.transform_roofline"):
        assert reader(m)(empty) is None
        assert reader(m)(dict(ctx, _phase_ms=None)) is None


# -- main.py from its own input3d ----------------------------------------------
@pytest.fixture(scope="module")
def example_run(tmp_path_factory):
    """``main.py`` on the example's own keys at 20 x 20 x 32 with a sphere
    of 6 cells for 40 steps with a checkpoint at step 20."""
    out = tmp_path_factory.mktemp("falling_sphere")
    text = inputfile.set_keys(small_text(diameter=0.03), {
        "Main": {"log_file": f"{out}/metrics.jsonl",
                 "restart_interval": 20,
                 "restart_dirname": f"{out}/restart"},
        "INSStaggeredHierarchyIntegrator": {"num_steps": 40}})
    inp = out / "input3d"
    inp.write_text(text)
    mod = harness.load_module(os.path.join(EXAMPLE, "main.py"),
                              "falling_sphere_under_test")
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
    assert recs[0]["ke"] == 0.0 and recs[0]["velocity"] == [0.0, 0.0, 0.0]
    assert recs[0]["height"] == pytest.approx(0.1275, abs=1e-6)
    assert recs[0]["gap"] == pytest.approx(0.1125, abs=1e-6)
    assert recs[2]["t"] == pytest.approx(0.16)
    for r0, r1 in zip(recs, recs[1:]):
        # it falls, faster and faster, along the tank's axis, and drags
        # the oil with it
        assert r1["velocity"][2] < r0["velocity"][2] <= 0.0
        assert r1["height"] < r0["height"] and r1["gap"] < r0["gap"]
        assert abs(r1["velocity"][0]) < 1e-6 > abs(r1["velocity"][1])
        assert r1["ke"] > r0["ke"]
        # the nearest wall is the top one (this sphere is twice the
        # source's), and it recedes
        assert r0["wall_gap"] < r1["wall_gap"] <= r1["gap"]
    # no flow through any of the six walls, at the bit
    for d, c in enumerate(final.ins.u):
        assert not np.any(np.asarray(jnp.take(c, 0, d)))


def test_example_restart_is_exact(example_run):
    mod, inp, rst, saved, final, _ = example_run
    assert sorted(saved) == [20, 40]
    template = mod.build_falling_sphere_example(
        mod.parse_input_file(inp))[1]
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


def test_example_ends_where_the_clearance_does(tmp_path):
    """Released with its lowest point two cells above the bottom the sphere
    is inside the 2.5 cells its delta stencils need: the series' first
    record is written, and the run is over."""
    text = inputfile.set_keys(
        small_text(diameter=0.03, center=[0.05, 0.05, 0.025]),
        {"Main": {"log_file": f"{tmp_path}/metrics.jsonl"}})
    inp = tmp_path / "input3d"
    inp.write_text(text)
    mod = harness.load_module(os.path.join(EXAMPLE, "main.py"),
                              "falling_sphere_under_test")
    with pytest.raises(mod.ClearanceLost, match="2.5 cells"):
        mod.main(["main.py", str(inp)])
    recs = [json.loads(ln) for ln in open(tmp_path / "metrics.jsonl")]
    assert len(recs) == 1 and recs[0]["gap"] == pytest.approx(0.01)
