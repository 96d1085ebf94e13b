"""Lid-driven cubic cavity at Re = 1000: the standard wall-bounded case of
incompressible codes (S. Albensoeder and H. C. Kuhlmann, "Accurate
three-dimensional lid-driven cavity flow", J. Comput. Phys. 206 (2005)
536-558; the older pseudospectral reference is Ku, Hirsh and Taylor,
J. Comput. Phys. 70 (1987) 439), on one wall-bounded level of
``INSStaggeredIntegrator``: the unit cube, six no-slip walls, the wall
y = 1 moving at U_lid in x, from rest to the steady state, with the
reference's default PPM convection.

Run:  python examples/navier_stokes/cavity3d/main.py [input3d] [restart_dir step]

What a user takes home is the metrics log: the kinetic energy, and the
numbers the source tabulates on the centrelines of the symmetry plane
z = 1/2: the minimum of u along the vertical centreline (x = 1/2) and the
extrema of v along the horizontal centreline (y = 1/2), each with its
position. (From memory, at the steady state: u_min about -0.28 near
y = 0.12, v_max about 0.25 near x = 0.11, v_min about -0.43 near
x = 0.91.)

The advance/restart/health loop is the shared HierarchyDriver skeleton;
this file is config + callbacks only.
"""

import os
import sys

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.join(os.path.dirname(__file__), *[".."] * 3))

# backend guard BEFORE any jax compute: honors JAX_PLATFORMS=cpu,
# otherwise requires a TPU (a run without a chip fails)
from ibamr_tpu.utils.backend_guard import auto_backend  # noqa: E402

auto_backend()

from ibamr_tpu import obs  # noqa: E402
from ibamr_tpu.grid import StaggeredGrid  # noqa: E402
from ibamr_tpu.integrators.ins import INSStaggeredIntegrator  # noqa: E402
from ibamr_tpu.utils import MetricsLogger, parse_input_file  # noqa: E402
from ibamr_tpu.utils.checkpoint import restore_checkpoint, save_checkpoint  # noqa: E402
from ibamr_tpu.utils.hierarchy_driver import HierarchyDriver, RunConfig  # noqa: E402


@obs.span("setup/build")
def build_cavity_example(input_db, dtype=jnp.float32):
    """``(integ, state)`` from ``CartesianGeometry`` and
    ``INSStaggeredHierarchyIntegrator``: walls on all three axes, the
    lid (component 0's tangential velocity on the hi wall of axis 1),
    the fluid at rest."""
    geo = input_db.get_database("CartesianGeometry")
    ins_db = input_db.get_database("INSStaggeredHierarchyIntegrator")
    grid = StaggeredGrid(n=tuple(geo.get_int_array("n_cells")),
                         x_lo=tuple(geo.get_array("x_lo")),
                         x_up=tuple(geo.get_array("x_up")))
    integ = INSStaggeredIntegrator(
        grid, rho=ins_db.get_float("rho"), mu=ins_db.get_float("mu"),
        convective_op_type=ins_db.get_string("convective_op_type"),
        dtype=dtype, wall_axes=(True, True, True),
        wall_tangential={(0, 1, 1): ins_db.get_float("U_lid")})
    return integ, integ.initialize()


def centreline_extrema(integ, state):
    """``(u_min, y, v_max, x, v_min, x)``: the extrema the source tabulates
    on the symmetry plane z = 1/2, sampled where the MAC components live
    (u at x-faces, v at y-faces; a centreline between two rows of
    unknowns is their mean)."""
    g = integ.grid

    def mid(a, axis, nodes):
        # the plane through the middle of ``axis``: a node of the
        # component's own axis, else between two cell centres
        n = a.shape[axis]
        lo, hi = (n // 2, (n + 1) // 2) if nodes else ((n - 1) // 2, n // 2)
        return 0.5 * (jnp.take(a, lo, axis) + jnp.take(a, hi, axis))

    u_line = mid(mid(state.u[0], 2, False), 0, True)        # over y
    v_line = mid(mid(state.u[1], 2, False), 1, True)        # over x
    y = g.x_lo[1] + (jnp.arange(g.n[1]) + 0.5) * g.dx[1]
    x = g.x_lo[0] + (jnp.arange(g.n[0]) + 0.5) * g.dx[0]
    ju, jhi, jlo = (jnp.argmin(u_line), jnp.argmax(v_line),
                    jnp.argmin(v_line))
    return u_line[ju], y[ju], v_line[jhi], x[jhi], v_line[jlo], x[jlo]


def main(argv):
    input_path = argv[1] if len(argv) > 1 else \
        os.path.join(os.path.dirname(__file__), "input3d")
    db = parse_input_file(input_path)
    main_db = db.get_database("Main")
    ins_db = db.get_database("INSStaggeredHierarchyIntegrator")

    integ, state = build_cavity_example(db, dtype=jnp.float32)
    dev = jax.devices()[0]
    u_lid = integ.wall_tangential[(0, 1, 1)]
    print(f"platform={dev.platform} device_kind={dev.device_kind} "
          f"grid={integ.grid.n} convection={integ.convective_op_type} "
          f"Re={integ.rho * u_lid / integ.mu:g}", flush=True)

    start_step = 0
    if len(argv) > 3:
        state, start_step, _ = restore_checkpoint(argv[2], state,
                                                  step=int(argv[3]))
        print(f"restarted from {argv[2]} at step {start_step}")

    rst_dir = main_db.get_string("restart_dirname", "restart_cavity3d")
    cfg = RunConfig(
        dt=ins_db.get_float("dt"),
        num_steps=ins_db.get_int("num_steps"),
        viz_dump_interval=main_db.get_int("viz_dump_interval", 0),
        restart_interval=main_db.get_int("restart_interval", 0),
        health_interval=20)

    vitals = jax.jit(lambda s: (integ.kinetic_energy(s),
                                integ.max_divergence(s),
                                *centreline_extrema(integ, s)))

    with MetricsLogger(main_db.get_string("log_file"), echo=True) as log:

        def metrics_fn(s, step):
            ke, div, u_min, y_u, v_max, x_hi, v_min, x_lo = vitals(s)
            rec = {"step": step, "t": s.t, "ke": ke, "max_div": div,
                   "u_min": u_min, "y_u_min": y_u, "v_max": v_max,
                   "x_v_max": x_hi, "v_min": v_min, "x_v_min": x_lo}
            log.log(rec)
            return rec

        metrics_fn(state, start_step)      # the series starts at its t0
        driver = HierarchyDriver(
            integ, cfg, metrics_fn=metrics_fn,
            checkpoint_fn=lambda s, k: save_checkpoint(rst_dir, s, k))
        return driver.run(state, start_step=start_step)


if __name__ == "__main__":
    main(sys.argv)
