"""Channel-flow driver: inflow -> developed Poiseuille -> open outflow.

Reference parity: the inflow/outflow INS example family (P2/P3 with
INSProjectionBcCoef-style open boundaries). Exercises the coupled
staggered-Stokes saddle solve (solvers.stokes) with explicit upwind
convection each step.

Run:  python examples/navier_stokes/channel2d/main.py [input2d]
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), *[".."] * 3))

# backend guard BEFORE any jax compute: honors JAX_PLATFORMS=cpu,
# otherwise requires a TPU (a run without a chip fails)
from ibamr_tpu.utils.backend_guard import auto_backend  # noqa: E402

auto_backend()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from ibamr_tpu.grid import StaggeredGrid  # noqa: E402
from ibamr_tpu.integrators.ins_open import INSOpenIntegrator  # noqa: E402
from ibamr_tpu.io.vtk import write_vti  # noqa: E402
from ibamr_tpu.solvers.stokes import channel_bc  # noqa: E402
from ibamr_tpu.utils import MetricsLogger, TimerManager, \
    parse_input_file  # noqa: E402


def main(argv):
    input_path = argv[1] if len(argv) > 1 else \
        os.path.join(os.path.dirname(__file__), "input2d")
    db = parse_input_file(input_path)
    main_db = db.get_database("Main")
    geo = db.get_database("CartesianGeometry")
    ins_db = db.get_database("INSOpenIntegrator")

    n = tuple(geo.get_int_array("n"))
    x_lo = tuple(geo.get_float_array("x_lo"))
    x_up = tuple(geo.get_float_array("x_up"))
    grid = StaggeredGrid(n=n, x_lo=x_lo, x_up=x_up)
    H = x_up[1] - x_lo[1]
    dy = H / n[1]
    U = ins_db.get_float("U_max", 1.0)
    y = (np.arange(n[1]) + 0.5) * dy
    profile = 4.0 * U * y * (H - y) / H ** 2

    integ = INSOpenIntegrator(
        n, grid.dx, channel_bc(2),
        mu=ins_db.get_float("mu"), dt=ins_db.get_float("dt"),
        rho=ins_db.get_float("rho", 1.0),
        bdry={(0, 0, 0): jnp.asarray(profile)[None, :], (1, 0, 0): 0.0},
        tol=ins_db.get_float("solver_tol", 1e-8))
    state = integ.initialize()

    viz_dir = main_db.get_string("viz_dirname", "viz_channel2d")
    os.makedirs(viz_dir, exist_ok=True)
    metrics = MetricsLogger(main_db.get_string("log_jsonl",
                                               "channel2d_metrics.jsonl"))
    timers = TimerManager()
    step = jax.jit(integ.step)
    num_steps = ins_db.get_int("num_steps")
    viz_int = main_db.get_int("viz_dump_interval", 0)

    for k in range(num_steps):
        with timers.scope("step"):
            state = step(state)
        if viz_int and (k + 1) % viz_int == 0:
            jax.block_until_ready(state.u[0])
            u_cc = tuple(np.asarray(c) for c in integ._to_cells(state.u))
            write_vti(os.path.join(viz_dir, f"u_{k + 1:05d}.vti"), grid,
                      {"u": u_cc[0], "v": u_cc[1],
                       "p": np.asarray(state.p)})
            flux = float(np.asarray(state.u[0]).sum(axis=1)[-1] * dy)
            metrics.log({"step": k + 1, "t": float(state.t),
                         "outflow_flux": flux,
                         "max_div": float(integ.max_divergence(state))})
            print(f"step {k + 1}: outflow flux {flux:.5f}")

    print(timers.report())
    un = np.asarray(state.u[0])
    err = float(np.max(np.abs(un[3 * n[0] // 4, :] - profile)))
    print(f"developed-profile error vs Poiseuille: {err:.2e}")


if __name__ == "__main__":
    main(sys.argv)
