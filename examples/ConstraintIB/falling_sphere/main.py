"""A rigid sphere settling under gravity in a closed tank: the standard
validation case of rigid-particle IB codes (A. ten Cate, C. H. Nieuwstad,
J. J. Derksen and H. E. A. Van den Akker, "Particle imaging velocimetry
experiments and lattice-Boltzmann simulations on a single sphere settling
under gravity", Phys. Fluids 14 (2002) 4012, case E4: Re = 31.9), by the
ConstraintIB momentum projection (Bhalla, Bale, Griffith and Patankar,
J. Comput. Phys. 250 (2013) 446; upstream's ``ConstraintIBMethod``) over
one wall-bounded level of ``INSStaggeredIntegrator``: a tank with six
no-slip walls, gravity along the LAST array axis, a solid ball of
volumetric markers at spacing h/2 released at rest, PPM convection.

Run:  python examples/ConstraintIB/falling_sphere/main.py [input3d] [restart_dir step]

What a user takes home is the metrics log: the time, the height of the
sphere's centre, its velocity, the gap between its lowest point and the
bottom, and the fluid's kinetic energy. (From memory, case E4 at the
source's size: the settling speed peaks near 0.128 m/s. THIS program's
sphere at 160 x 160 x 256 peaks at 0.171: it does not reproduce the
source yet, and ROADMAP X8 has what was read of why.)

The clearance contract: the markers' transfers know no wall (their delta
stencils wrap around), so every marker has to keep ``CLEARANCE_CELLS``
cells (IB_4's half-width of two cells and the half-cell MAC offset) from
every wall; the run is over when the gap to a wall falls under that, and
``metrics_fn`` raises ``ClearanceLost`` rather than let a marker spread
through a wall. The model has no contact force: the approach to the
bottom below that gap is outside it.

The transfers run on the engine ``models/engine_resolver`` resolves for
the grid and the marker count (``scatter`` on the small example grid,
``packed`` at the benchmark's size); there is no key for it.

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
from ibamr_tpu.integrators.cib import RigidBodies  # noqa: E402
from ibamr_tpu.integrators.constraint_ib import (  # noqa: E402
    ConstraintIBMethod, fill_sphere)
from ibamr_tpu.integrators.ins import INSStaggeredIntegrator  # noqa: E402
from ibamr_tpu.models.engine_resolver import (  # noqa: E402
    build_engine_with_fallback, resolve_engine)
from ibamr_tpu.ops.delta import get_kernel  # noqa: E402
from ibamr_tpu.utils import MetricsLogger, parse_input_file  # noqa: E402
from ibamr_tpu.utils.checkpoint import restore_checkpoint, save_checkpoint  # noqa: E402
from ibamr_tpu.utils.hierarchy_driver import HierarchyDriver, RunConfig  # noqa: E402

CLEARANCE_CELLS = 2.5


class ClearanceLost(RuntimeError):
    """A marker came within ``CLEARANCE_CELLS`` cells of a wall."""


@obs.span("setup/build")
def build_falling_sphere_example(input_db, dtype=jnp.float32):
    """``(method, state)`` from ``CartesianGeometry``,
    ``INSStaggeredHierarchyIntegrator``, ``ConstraintIBMethod`` and
    ``Sphere``: walls on all three axes, one rigid sphere of markers at
    rest in a fluid at rest, its transfers on the engine the resolver
    names for this grid and marker count."""
    geo = input_db.get_database("CartesianGeometry")
    ins_db = input_db.get_database("INSStaggeredHierarchyIntegrator")
    cib_db = input_db.get_database("ConstraintIBMethod")
    sph = input_db.get_database("Sphere")
    grid = StaggeredGrid(n=tuple(geo.get_int_array("n_cells")),
                         x_lo=tuple(geo.get_array("x_lo")),
                         x_up=tuple(geo.get_array("x_up")))
    rho = ins_db.get_float("rho")
    ins = INSStaggeredIntegrator(
        grid, rho=rho, mu=ins_db.get_float("mu"),
        convective_op_type=ins_db.get_string("convective_op_type"),
        dtype=dtype, wall_axes=(True, True, True))
    X0 = fill_sphere(sph.get_float_array("center"),
                     0.5 * sph.get_float("diameter"),
                     sph.get_float("marker_spacing_cells") * min(grid.dx),
                     dtype=dtype)
    bodies = RigidBodies(body_id=jnp.zeros(X0.shape[0], dtype=jnp.int32),
                         n_bodies=1)
    kernel = cib_db.get_string("delta_fcn")
    fast, engine = build_engine_with_fallback(
        resolve_engine(grid.n, X0.shape[0], get_kernel(kernel)[0]),
        grid, X0, kernel)
    method = ConstraintIBMethod(
        ins, bodies, kernel=kernel,
        density_ratio=[sph.get_float("density") / rho],
        gravity=tuple(sph.get_float_array("gravity")),
        virtual_mass=cib_db.get_float("virtual_mass"), fast=fast,
        engine_name=engine)
    return method, method.initialize(X0)


def body_vitals(method, state):
    """``(centre, velocity, wall gap, bottom gap, kinetic energy)``: the
    sphere's centroid and translation, the least distance of a marker
    from any wall and from the bottom (the lo wall of the last axis),
    and the fluid's kinetic energy."""
    g = method.ins.grid
    X = state.X
    lo, hi = jnp.asarray(g.x_lo, X.dtype), jnp.asarray(g.x_up, X.dtype)
    to_wall = jnp.minimum(jnp.min(X - lo, axis=0), jnp.min(hi - X, axis=0))
    return (jnp.mean(X, axis=0), state.U_body[0, :g.dim],
            jnp.min(to_wall), jnp.min(X[:, -1]) - lo[-1],
            method.ins.kinetic_energy(state.ins))


def main(argv):
    input_path = argv[1] if len(argv) > 1 else \
        os.path.join(os.path.dirname(__file__), "input3d")
    db = parse_input_file(input_path)
    main_db = db.get_database("Main")
    ins_db = db.get_database("INSStaggeredHierarchyIntegrator")

    method, state = build_falling_sphere_example(db, dtype=jnp.float32)
    dev = jax.devices()[0]
    grid = method.ins.grid
    print(f"platform={dev.platform} device_kind={dev.device_kind} "
          f"grid={grid.n} markers={state.X.shape[0]} "
          f"engine={method.engine_name} "
          f"density_ratio={float(method.density_ratio[0, 0]):g}",
          flush=True)

    start_step = 0
    if len(argv) > 3:
        state, start_step, _ = restore_checkpoint(argv[2], state,
                                                  step=int(argv[3]))
        print(f"restarted from {argv[2]} at step {start_step}")

    rst_dir = main_db.get_string("restart_dirname", "restart_falling_sphere")
    cfg = RunConfig(
        dt=ins_db.get_float("dt"),
        num_steps=ins_db.get_int("num_steps"),
        viz_dump_interval=main_db.get_int("viz_dump_interval", 0),
        restart_interval=main_db.get_int("restart_interval", 0),
        health_interval=20)

    vitals = jax.jit(lambda s: body_vitals(method, s))
    clearance = CLEARANCE_CELLS * max(grid.dx)

    with MetricsLogger(main_db.get_string("log_file"), echo=True) as log:

        def metrics_fn(s, step):
            centre, vel, wall_gap, gap, ke = vitals(s)
            rec = {"step": step, "t": s.ins.t, "height": centre[-1],
                   "velocity": vel, "gap": gap, "wall_gap": wall_gap,
                   "ke": ke}
            log.log(rec)
            if float(wall_gap) < clearance:
                raise ClearanceLost(
                    f"step {step}: a marker is {float(wall_gap):.3e} from "
                    f"a wall, under the {CLEARANCE_CELLS} cells "
                    f"({clearance:.3e}) its delta stencil needs; the run "
                    "is over (see this file's docstring)")
            return rec

        metrics_fn(state, start_step)      # the series starts at its t0
        driver = HierarchyDriver(
            method, cfg, metrics_fn=metrics_fn,
            checkpoint_fn=lambda s, k: save_checkpoint(rst_dir, s, k))
        return driver.run(state, start_step=start_step)


if __name__ == "__main__":
    main(sys.argv)
