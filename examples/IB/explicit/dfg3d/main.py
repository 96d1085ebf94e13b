"""Flow around a cylinder in a duct: the DFG benchmark case 3D-2Z
(M. Schaefer and S. Turek, "Benchmark computations of laminar flow
around a cylinder", Notes on Numerical Fluid Mechanics 52 (1996)
547-566: Re = 100), with a target-point IB cylinder in an
inflow/outflow duct (upstream's external-flow IB examples over the
inflow/outflow-configured ``INSStaggeredHierarchyIntegrator``).

The duct is [0, 2.5] x [0, 0.41] x [0, 0.41] m; a circular cylinder of
diameter 0.1 spans it along z, its axis through (x, y) = (0.5, 0.2),
off the centreline. The inflow at x = 0 is
U = 16 U_m y z (H - y)(H - z) / H^4 (U_m = 2.25, mean speed 1), the
four side walls are no-slip, the outflow at x = 2.5 is traction-free;
rho = 1, mu = 1e-3.

Array axes: 0 is y, 1 is z (the cylinder's axis) and 2 is x, the
streamwise axis, which goes last (the chip tiles the last axis by 128
lanes: 512 cells fill them, 84 would leave a third empty). Input-file
coordinates are in that order.

Run:  python examples/IB/explicit/dfg3d/main.py [input3d] [restart_dir step]

What a user takes home is the metrics log, every chunk: the drag and
lift coefficients c_D and c_L from the net force the markers spread
(``F_net``), and the pressure difference dp between the points just
before and behind the cylinder, as the source reports them.

The clearance contract of ``integrators/ib_open``: the markers'
transfers know no boundary (their delta stencils wrap around), so the
markers stop ``clearance_cells`` cells short of the two z walls that the
cylinder spans. The run is over when a marker strays more than
``TARGET_CELLS`` cells from its target (``metrics_fn`` raises
``TargetsLost``): the body is then no longer the cylinder.

The advance/restart/health loop is the shared HierarchyDriver skeleton;
this file is config + callbacks only.
"""

import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), *[".."] * 4))

# backend guard BEFORE any jax compute: honors JAX_PLATFORMS=cpu,
# otherwise requires a TPU (a run without a chip fails)
from ibamr_tpu.utils.backend_guard import auto_backend  # noqa: E402

auto_backend()

from ibamr_tpu import obs  # noqa: E402
from ibamr_tpu.integrators.ib import IBMethod  # noqa: E402
from ibamr_tpu.integrators.ib_open import IBOpenIntegrator  # noqa: E402
from ibamr_tpu.integrators.ins_open import INSOpenIntegrator  # noqa: E402
from ibamr_tpu.models.engine_resolver import (  # noqa: E402
    build_engine_with_fallback, resolve_engine)
from ibamr_tpu.ops.delta import get_kernel  # noqa: E402
from ibamr_tpu.ops.forces import ForceSpecs  # noqa: E402
from ibamr_tpu.solvers.stokes import channel_bc  # noqa: E402
from ibamr_tpu.utils import MetricsLogger, parse_input_file  # noqa: E402
from ibamr_tpu.utils.checkpoint import restore_checkpoint, save_checkpoint  # noqa: E402
from ibamr_tpu.utils.hierarchy_driver import HierarchyDriver, RunConfig  # noqa: E402

FLOW_AXIS, SPAN_AXIS, LIFT_AXIS = 2, 1, 0
TARGET_CELLS = 1.0
REST_CELLS = 2.0


class TargetsLost(RuntimeError):
    """A marker strayed more than ``TARGET_CELLS`` cells from its
    target."""


def inflow_profile(grid_n, dx, u_m: float, dtype=jnp.float32):
    """The source's inflow on the faces of x = 0:
    16 U_m y z (H - y)(H - z) / H^4 at the face centres, as the face
    slab (n_y, n_z, 1)."""
    yz = [(np.arange(grid_n[e]) + 0.5) * dx[e] for e in (0, 1)]
    h = [grid_n[e] * dx[e] for e in (0, 1)]
    prof = 16.0 * u_m * np.outer(yz[0] * (h[0] - yz[0]),
                                 yz[1] * (h[1] - yz[1])) / (h[0] * h[1]) ** 2
    return jnp.asarray(prof[:, :, None], dtype)


def cylinder_markers(centre, diameter: float, z_lo: float, z_hi: float,
                     spacing: float, dtype=jnp.float32):
    """Target points on the cylinder's surface: ``n_theta`` around it and
    ``n_z`` along it from ``z_lo`` to ``z_hi``, both at about
    ``spacing``; rows (y, z, x)."""
    n_theta = max(3, int(round(math.pi * diameter / spacing)))
    n_z = max(2, int(round((z_hi - z_lo) / spacing)) + 1)
    th = 2.0 * np.pi * np.arange(n_theta) / n_theta
    zs = np.linspace(z_lo, z_hi, n_z)
    T, Z = np.meshgrid(th, zs, indexing="ij")
    r = 0.5 * diameter
    X = np.stack([centre[0] + r * np.sin(T), Z,
                  centre[1] + r * np.cos(T)], axis=-1).reshape(-1, 3)
    return jnp.asarray(X, dtype)


@obs.span("setup/build")
def build_dfg3d_example(input_db, dtype=jnp.float32):
    """``(integ, state)`` from ``CartesianGeometry``,
    ``INSStaggeredHierarchyIntegrator``, ``Inflow``,
    ``IBMethod`` and ``Cylinder``: an ``IBOpenIntegrator`` over the
    open-boundary solve, its transfers on the engine the resolver names
    for this grid and marker count; the state is the inflow profile
    carried down the whole duct around the cylinder (``at_rest_near``),
    p = 0, the markers at their targets."""
    geo = input_db.get_database("CartesianGeometry")
    ins_db = input_db.get_database("INSStaggeredHierarchyIntegrator")
    ib_db = input_db.get_database("IBMethod")
    cyl = input_db.get_database("Cylinder")
    n = tuple(geo.get_int_array("n_cells"))
    x_lo = tuple(geo.get_float_array("x_lo"))
    x_up = tuple(geo.get_float_array("x_up"))
    dx = tuple((hi - lo) / m for lo, hi, m in zip(x_lo, x_up, n))
    profile = inflow_profile(n, dx, input_db.get_database("Inflow")
                             .get_float("U_m"), dtype)
    ins = INSOpenIntegrator(
        n, dx, channel_bc(3, flow_axis=FLOW_AXIS),
        mu=ins_db.get_float("mu"), dt=ins_db.get_float("dt"),
        rho=ins_db.get_float("rho"),
        bdry={(FLOW_AXIS, FLOW_AXIS, 0): profile},
        tol=ins_db.get_float("tol"), dtype=dtype,
        convective_op_type=ins_db.get_string("convective_op_type"),
        stab_band=ins_db.get_int("stab_band"))

    h = min(dx)
    clear = ib_db.get_float("clearance_cells") * dx[SPAN_AXIS]
    X0 = cylinder_markers(cyl.get_float_array("center"),
                          cyl.get_float("diameter"), x_lo[SPAN_AXIS] + clear,
                          x_up[SPAN_AXIS] - clear,
                          ib_db.get_float("marker_spacing_cells") * h, dtype)
    kappa, eta = ib_db.get_float("kappa"), ib_db.get_float("eta")
    kernel = ib_db.get_string("delta_fcn")
    ib = IBMethod(ForceSpecs(), kernel=kernel,
                  force_fn=lambda X, U, t: -kappa * (X - X0) - eta * U)
    integ = IBOpenIntegrator(ins, ib, x_lo=x_lo)
    # the engine bakes in the integrator's own grid (check_fast_grid)
    ib.fast, ib.engine_name = build_engine_with_fallback(
        resolve_engine(n, X0.shape[0], get_kernel(kernel)[0]), integ.grid,
        X0, kernel)
    fluid = ins.initialize()
    u = list(fluid.u)
    u[FLOW_AXIS] = jnp.broadcast_to(profile, u[FLOW_AXIS].shape) \
        * at_rest_near(cyl.get_float_array("center"),
                       0.5 * cyl.get_float("diameter"), n, x_lo, dx, dtype)
    return integ, integ.initialize(X0, fluid=fluid._replace(u=tuple(u)))


def at_rest_near(centre, radius: float, n, x_lo, dx, dtype=jnp.float32):
    """The weight of the streamwise faces: 0 inside the cylinder, rising
    to 1 over the ``REST_CELLS`` cells around it. The flow starts diverted
    around the body instead of through it: no enclosed column of fluid
    whose momentum the targets would have to take up in the first steps.
    The weighted field is not solenoidal; the first step's saddle solve
    projects it."""
    y = x_lo[LIFT_AXIS] + (np.arange(n[LIFT_AXIS]) + 0.5) * dx[LIFT_AXIS]
    x = x_lo[FLOW_AXIS] + np.arange(n[FLOW_AXIS] + 1) * dx[FLOW_AXIS]
    r = np.hypot(y[:, None] - centre[0], x[None, :] - centre[1])
    w = np.clip((r - radius) / (REST_CELLS * min(dx)), 0.0, 1.0)
    return jnp.asarray(w[:, None, :], dtype)


def probe(p, point, x_lo, dx):
    """Trilinear value of the cell-centred ``p`` at ``point``."""
    out = p
    for e in range(3):
        s = (point[e] - x_lo[e]) / dx[e] - 0.5
        i = int(math.floor(s))
        w = s - i
        out = ((1.0 - w) * jax.lax.index_in_dim(out, i, e)
               + w * jax.lax.index_in_dim(out, i + 1, e))
    return out.reshape(())


def main(argv):
    input_path = argv[1] if len(argv) > 1 else \
        os.path.join(os.path.dirname(__file__), "input3d")
    db = parse_input_file(input_path)
    main_db = db.get_database("Main")
    ins_db = db.get_database("INSStaggeredHierarchyIntegrator")

    integ, state = build_dfg3d_example(db, dtype=jnp.float32)
    X0 = state.X
    dev = jax.devices()[0]
    grid = integ.grid
    print(f"platform={dev.platform} device_kind={dev.device_kind} "
          f"grid={grid.n} markers={state.X.shape[0]} "
          f"engine={integ.ib.engine_name}", flush=True)

    start_step = 0
    if len(argv) > 3:
        state, start_step, _ = restore_checkpoint(argv[2], state,
                                                  step=int(argv[3]))
        print(f"restarted from {argv[2]} at step {start_step}")

    rst_dir = main_db.get_string("restart_dirname", "restart_dfg3d")
    cfg = RunConfig(
        dt=ins_db.get_float("dt"),
        num_steps=ins_db.get_int("num_steps"),
        viz_dump_interval=main_db.get_int("viz_dump_interval", 0),
        restart_interval=main_db.get_int("restart_interval", 0),
        health_interval=main_db.get_int("health_interval"))

    probes = db.get_database("PressureProbes")
    front = tuple(probes.get_float_array("front"))
    back = tuple(probes.get_float_array("back"))
    cyl = db.get_database("Cylinder")
    rho = ins_db.get_float("rho")
    h_yz = [grid.x_up[e] - grid.x_lo[e] for e in (0, 1)]
    mean_u = 4.0 * db.get_database("Inflow").get_float("U_m") / 9.0
    # c = 2 F / (rho U_mean^2 D H): the body's frontal area D x H
    scale = 2.0 / (rho * mean_u ** 2 * cyl.get_float("diameter")
                   * h_yz[SPAN_AXIS])

    @jax.jit
    def vitals(s):
        p = s.fluid.p
        dp = probe(p, front, grid.x_lo, grid.dx) \
            - probe(p, back, grid.x_lo, grid.dx)
        offset = jnp.max(jnp.linalg.norm(s.X - X0, axis=1))
        return (s.F_net, dp, offset,
                integ.ins.max_divergence(s.fluid))

    target = TARGET_CELLS * min(grid.dx)

    with MetricsLogger(main_db.get_string("log_file"), echo=True) as log:

        def metrics_fn(s, step):
            F, dp, offset, div = vitals(s)
            # the force on the body is minus what the markers spread
            rec = {"step": step, "t": s.fluid.t,
                   "c_D": -scale * F[FLOW_AXIS],
                   "c_L": -scale * F[LIFT_AXIS], "dp": dp,
                   "target_offset": offset, "max_div": div}
            log.log(rec)
            if float(offset) > target:
                raise TargetsLost(
                    f"step {step}: a marker is {float(offset):.3e} from "
                    f"its target, over the {TARGET_CELLS} cells "
                    f"({target:.3e}) the body may give; the run is over "
                    "(see this file's docstring)")
            return rec

        metrics_fn(state, start_step)      # the series starts at its t0
        driver = HierarchyDriver(
            integ, cfg, metrics_fn=metrics_fn,
            checkpoint_fn=lambda s, k: save_checkpoint(rst_dir, s, k))
        return driver.run(state, start_step=start_step)


if __name__ == "__main__":
    main(sys.argv)
