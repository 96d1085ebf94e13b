"""Taylor-Green vortex at Re = 1600 in the 2 pi-periodic cube: the standard
transition benchmark of incompressible codes (case C3.5 of the 1st
International Workshop on High-Order CFD Methods; reference data of van Rees
et al., J. Comput. Phys. 230 (2011) 2794), on one periodic level of
``INSStaggeredIntegrator`` with the reference's default PPM convection.

Run:  python examples/navier_stokes/tgv3d/main.py [input3d] [restart_dir step]

What a user takes home is the metrics log: the kinetic energy
E_k = <u.u>/2 (0.125 at t = 0), the enstrophy <w.w>/2 (0.375 at t = 0) and
the dissipation it resolves, eps_enstrophy = 2 (mu/rho) enstrophy, to set
against -dE_k/dt (what the limiter dissipates besides is their gap).

The advance/restart/health loop is the shared HierarchyDriver skeleton;
this file is config + callbacks only.
"""

import math
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


def taylor_green(coords, t):
    """The benchmark's initial velocity at ``coords`` (L = V0 = 1)."""
    x, y, z = coords
    return (jnp.sin(x) * jnp.cos(y) * jnp.cos(z),
            -jnp.cos(x) * jnp.sin(y) * jnp.cos(z),
            jnp.zeros(()))


@obs.span("setup/build")
def build_tgv_example(input_db, dtype=jnp.float32):
    """``(integ, state)`` from ``CartesianGeometry`` and
    ``INSStaggeredHierarchyIntegrator``: the analytic field evaluated on
    the device at each component's own faces, and the pressure that goes
    with it, p = (rho V0^2 / 16)(cos 2x + cos 2y)(cos 2z + 2)."""
    geo = input_db.get_database("CartesianGeometry")
    ins_db = input_db.get_database("INSStaggeredHierarchyIntegrator")
    grid = StaggeredGrid(n=tuple(geo.get_int_array("n_cells")),
                         x_lo=tuple(geo.get_array("x_lo")),
                         x_up=tuple(geo.get_array("x_up")))
    integ = INSStaggeredIntegrator(
        grid, rho=ins_db.get_float("rho"), mu=ins_db.get_float("mu"),
        convective_op_type=ins_db.get_string("convective_op_type"),
        dtype=dtype)
    state = integ.initialize(u0=taylor_green)
    x, y, z = grid.cell_centers(dtype)
    p0 = (integ.rho / 16.0) * (jnp.cos(2 * x) + jnp.cos(2 * y)) \
        * (jnp.cos(2 * z) + 2.0)
    return integ, state._replace(p=jnp.broadcast_to(p0, grid.n).astype(dtype))


def main(argv):
    input_path = argv[1] if len(argv) > 1 else \
        os.path.join(os.path.dirname(__file__), "input3d")
    db = parse_input_file(input_path)
    main_db = db.get_database("Main")
    ins_db = db.get_database("INSStaggeredHierarchyIntegrator")

    integ, state = build_tgv_example(db, dtype=jnp.float32)
    dev = jax.devices()[0]
    print(f"platform={dev.platform} device_kind={dev.device_kind} "
          f"grid={integ.grid.n} convection={integ.convective_op_type} "
          f"Re={integ.rho / integ.mu:g}", flush=True)

    start_step = 0
    if len(argv) > 3:
        state, start_step, _ = restore_checkpoint(argv[2], state,
                                                  step=int(argv[3]))
        print(f"restarted from {argv[2]} at step {start_step}")

    rst_dir = main_db.get_string("restart_dirname", "restart_tgv3d")
    cfg = RunConfig(
        dt=ins_db.get_float("dt"),
        num_steps=ins_db.get_int("num_steps"),
        viz_dump_interval=main_db.get_int("viz_dump_interval", 0),
        restart_interval=main_db.get_int("restart_interval", 0),
        health_interval=20)

    # volume means, as the benchmark defines E_k and the enstrophy
    nu = integ.mu / integ.rho
    volume = math.prod(hi - lo
                       for lo, hi in zip(integ.grid.x_lo, integ.grid.x_up))
    vitals = jax.jit(lambda s: (
        integ.kinetic_energy(s) / (integ.rho * volume),
        integ.enstrophy(s) / volume, integ.max_divergence(s)))

    with MetricsLogger(main_db.get_string("log_file"), echo=True) as log:

        def metrics_fn(s, step):
            ke, ens, div = vitals(s)
            rec = {"step": step, "t": s.t, "ke": ke, "enstrophy": ens,
                   "eps_enstrophy": 2.0 * nu * ens, "max_div": div}
            log.log(rec)
            return rec

        metrics_fn(state, start_step)      # the series starts at its t0
        driver = HierarchyDriver(
            integ, cfg, metrics_fn=metrics_fn,
            checkpoint_fn=lambda s, k: save_checkpoint(rst_dir, s, k))
        return driver.run(state, start_step=start_step)


if __name__ == "__main__":
    main(sys.argv)
