"""The DFG 3D-2Z cylinder configuration (``dfg3d_2z``) at sizes a CPU runs:
the example ``examples/IB/explicit/dfg3d/main.py`` through
``HierarchyDriver``, the target-point cylinder over the open-boundary coupled
solve (FGMRES), against the plain reference ``perfbench/reference/ib_open.py``
(numpy float64, its own transfers, stencils and Schur-complement solve),
through the benchmark's adapter; the solve's counts on the driver's
``driver/chunk/krylov`` span and counters; the Arnoldi products' stated
precision.
"""

import os
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ibamr_tpu import obs
from ibamr_tpu.utils import parse_input_string
from ibamr_tpu.utils.hierarchy_driver import HierarchyDriver, RunConfig
from perfbench import harness, inputfile
from perfbench.reference import ib_open as reference

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = harness.load_json(os.path.join(ROOT, "perfbench", "configs",
                                        "dfg3d_2z.json"))
ADAPTER = harness.load_adapter(os.path.join(ROOT, CONFIG["adapter"]))
MAIN = harness.load_module(os.path.join(ROOT, CONFIG["entry"]),
                           "dfg3d_main_under_test")


def small_text(n=(12, 12, 64)):
    """The configuration's input at ``n`` cells with the rehearsal's dt,
    stiffness and one-step chunks (h = 34 mm across: the cylinder is three
    cells wide, its markers stop 2.5 cells short of the z walls)."""
    with open(os.path.join(ROOT, "perfbench", "configs",
                           CONFIG["input_file"])) as f:
        text = f.read()
    keys = {s: dict(kv) for s, kv in ADAPTER.rehearse_keys.items()}
    keys["CartesianGeometry"] = {"n_cells": list(n)}
    return inputfile.set_keys(text, keys)


def built(text, seed=2147483700):
    integ, state = MAIN.build_dfg3d_example(parse_input_string(text))
    return integ, ADAPTER.seed(integ, state, seed, CONFIG["seed_data"])


@pytest.fixture(scope="module")
def two_chunks():
    """Two one-step chunks of the seeded state through the driver: the
    states before and after each, and the driver's spans and counters."""
    text = small_text()
    integ, state = built(text)
    seen = [state]
    before = obs.metrics_snapshot()["counters"]
    # by time, not by position: the ring of spans may be full
    t0 = time.perf_counter()
    drv = HierarchyDriver(integ, RunConfig(dt=0.0065, num_steps=2,
                                           health_interval=1),
                          metrics_fn=lambda s, k: seen.append(s) or {})
    drv.run(state)
    after = obs.metrics_snapshot()["counters"]
    spans = [s for s in obs.spans()
             if s["t0"] >= t0 and s["path"] == "driver/chunk/krylov"]
    return text, integ, seen, spans, before, after


def test_two_chunks_match_the_reference(two_chunks):
    text, integ, seen, spans, _, _ = two_chunks
    assert integ.grid.n == (12, 12, 64)
    assert len(seen) == 3 and len(spans) == 2
    ref = ADAPTER.reference(reference, inputfile.parse(text))
    s_in = {k: np.asarray(v) for k, v in ADAPTER.leaves(seen[1]).items()}
    s_out = {k: np.asarray(v) for k, v in ADAPTER.leaves(seen[2]).items()}
    r_in = reference.state_from_arrays(s_in)
    r_out = ref.advance(r_in, 1)
    ref.close()
    # the reference's own solve: far under the program's tolerance
    assert max(ref.residuals) < 1e-9, ref.residuals
    got = ADAPTER.compare(r_out, s_out, r_in)
    assert set(got) == set(CONFIG["limits"])
    for name, limit in CONFIG["limits"].items():
        assert got[name] <= limit, (name, got[name], limit)
    # the inflow faces and the walls' normal faces are held exactly, and
    # the reference's inflow is the program's to float32 rounding
    assert got["inflow"] == 0.0 and got["unconverged"] == 0.0
    np.testing.assert_allclose(s_out["u2"][:, :, 0], r_out.u[2][:, :, 0],
                               rtol=0, atol=3e-7)
    # the step moved the flow: the comparison is not of a frozen state
    assert got["du"] < 1e-3


def test_krylov_span_carries_the_solves_own_counts(two_chunks):
    _, integ, seen, spans, _, _ = two_chunks
    for k, span in enumerate(spans):
        _, stats = jax.jit(integ.step_with_stats)(seen[k], 0.0065)
        want = {key: int(v) for key, v in stats.items()}
        got = {key: span["attrs"][key] for key in want}
        assert got == want, (k, got, want)
        assert got["iters"] == got["restarts"] * integ.ins.solver.m
        assert got["restarts"] >= 1 and got["unconverged"] == 0


def test_counters_add_up_over_two_chunks(two_chunks):
    _, _, _, spans, before, after = two_chunks

    def delta(name):
        return after.get(name, 0) - before.get(name, 0)

    assert delta("stokes_krylov_iterations_total") == sum(
        s["attrs"]["iters"] for s in spans) > 0
    assert delta("stokes_unconverged_solves_total") == 0
    assert delta("driver_chunks_total") == 2
    help_text = {name: obs.help_for(name) for name in
                 ("stokes_krylov_iterations_total",
                  "stokes_unconverged_solves_total")}
    assert all(help_text.values()), help_text


@pytest.fixture(scope="module")
def step_text():
    """The compiled text of one step at 8 x 8 x 32 (the CPU's compile
    keeps every product's stated precision and its op_name)."""
    integ, state = built(small_text(n=(8, 8, 32)))
    return jax.jit(integ.step).lower(state, 0.0065).compile().as_text()


def test_arnoldi_products_state_the_highest_precision(step_text):
    # what a CPU run cannot see: a float32 product with no stated
    # precision is ONE bfloat16 pass on the chip. Every product of the
    # step (the four CGS2 products of the Arnoldi iteration, the update of
    # the iterate, the small least-squares solve's) states the highest
    dots = [ln for ln in step_text.splitlines()
            if re.search(r"= \S+ dot\(", ln)]
    arnoldi = [ln for ln in dots if "/krylov/" in ln and "/arnoldi/" in ln]
    assert len(arnoldi) == 4, len(arnoldi)
    assert len(dots) > len(arnoldi)
    for ln in dots:
        assert "operand_precision={highest,highest}" in ln, ln


def test_step_carries_the_krylov_phases(step_text):
    from ibamr_tpu.obs import deviceprof

    found = set(deviceprof.names_from_hlo(step_text)[1].values())
    for phase in ("fluid/krylov", "fluid/krylov/operator",
                  "fluid/krylov/precond", "fluid/krylov/arnoldi",
                  "fluid/convect", "ib/interp", "ib/spread"):
        assert phase in found, (phase, sorted(found))


def test_targets_are_the_programs_markers():
    text = small_text()
    integ, state = MAIN.build_dfg3d_example(parse_input_string(text))
    want = reference.targets(inputfile.parse(text))
    np.testing.assert_allclose(np.asarray(state.X), want, rtol=0,
                               atol=1e-6)
    assert np.all(np.asarray(state.X) == np.asarray(
        ADAPTER.seed(integ, state, 5, CONFIG["seed_data"]).X))
    # 2.5 cells from each z wall, on the cylinder's surface
    h = integ.grid.dx
    z = want[:, 1]
    np.testing.assert_allclose([z.min(), 0.41 - z.max()], 2.5 * h[1])
    r = np.hypot(want[:, 0] - 0.2, want[:, 2] - 0.5)
    np.testing.assert_allclose(r, 0.05, rtol=1e-12)


@pytest.mark.parametrize("seed", [1, 2147483655])
def test_seed_adds_a_solenoidal_velocity_and_keeps_the_inflow(seed):
    integ, state = MAIN.build_dfg3d_example(parse_input_string(small_text()))
    seeded = ADAPTER.seed(integ, state, seed, CONFIG["seed_data"])
    u = seeded.fluid.u
    div = integ.ins.solver.divergence
    added = div(u) - div(state.fluid.u)
    assert float(jnp.max(jnp.abs(added))) * min(integ.grid.dx) < 1e-6
    # the inflow and the walls' normal faces are the built state's
    assert bool(jnp.all(u[2][:, :, 0] == state.fluid.u[2][:, :, 0]))
    for d in (0, 1):
        for i in (0, -1):
            assert float(jnp.max(jnp.abs(jnp.take(u[d], i, axis=d)))) == 0.0
    moved = float(jnp.sqrt(sum(jnp.mean((a - b) ** 2) for a, b in
                               zip(u, state.fluid.u))))
    # the seeded modes' rms over the lower faces; the appended upper
    # boundary faces are 0
    np.testing.assert_allclose(moved, CONFIG["seed_data"]["velocity_rms"],
                               rtol=0.1)


def test_built_flow_goes_around_the_cylinder():
    text = small_text(n=(16, 16, 96))
    integ, state = MAIN.build_dfg3d_example(parse_input_string(text))
    u = np.asarray(state.fluid.u[2])
    g = integ.grid
    y = (np.arange(g.n[0]) + 0.5) * g.dx[0]
    x = np.arange(g.n[2] + 1) * g.dx[2]
    r = np.hypot(y[:, None] - 0.2, x[None, :] - 0.5)
    inside = np.broadcast_to((r <= 0.05)[:, None, :], u.shape)
    far = np.broadcast_to((r >= 0.05 + MAIN.REST_CELLS * min(g.dx))
                          [:, None, :], u.shape)
    profile = np.broadcast_to(np.asarray(state.fluid.u[2][:, :, :1]),
                              u.shape)
    assert inside.any() and np.all(u[inside] == 0.0)
    assert np.all(u[far] == profile[far])


def test_least_bytes_count_four_reads_of_each_live_basis_row():
    from perfbench.work_open import mg_levels, restart_bytes

    n = (84, 84, 512)
    # the pressure hierarchy the solver builds: 84 -> 42 -> 21
    integ, _ = MAIN.build_dfg3d_example(parse_input_string(small_text(n=n)))
    solver = integ.ins.solver
    assert mg_levels(n) == 3 == len(solver.p_mg.levels)
    # a Krylov vector: the three face-complete components and the pressure
    vec = 4 * (sum(np.prod(sh) for sh in solver.shapes) + np.prod(n))
    levels = mg_levels(n)
    b = [restart_bytes(n, m, levels) for m in (39, 40, 41)]
    # one more Arnoldi iteration reads one more live row in each of CGS2's
    # four passes over the basis, and the update of the iterate one more
    assert b[2] - 2 * b[1] + b[0] == 4 * vec
    # a cycle of 40 at the chip's HBM peak (PERF.md section 5): ~0.35 s
    assert 0.34 < b[1] / 819e9 < 0.36
