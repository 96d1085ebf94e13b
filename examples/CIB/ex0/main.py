"""CIB ex0-equivalent driver: rigid disc sedimenting in periodic Stokes
flow via the constraint/mobility formulation (reference:
examples/CIB/ex0 main.cpp + input2d — CIBMethod + CIBMobilitySolver).

Run:  python examples/CIB/ex0/main.py [input2d]
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

import numpy as np  # noqa: E402

from ibamr_tpu.grid import StaggeredGrid  # noqa: E402
from ibamr_tpu.integrators import cib  # noqa: E402
from ibamr_tpu.utils import MetricsLogger, TimerManager, parse_input_file  # noqa: E402


def main(argv):
    input_path = argv[1] if len(argv) > 1 else \
        os.path.join(os.path.dirname(__file__), "input2d")
    db = parse_input_file(input_path)
    main_db = db.get_database("Main")
    geom = db.get_database("CartesianGeometry")
    cib_db = db.get_database("CIBMethod")
    body_db = db.get_database("Body")
    ts = db.get_database("TimeStepping")

    grid = StaggeredGrid(n=tuple(geom.get_int_array("n_cells")),
                         x_lo=tuple(geom.get_float_array("x_lo")),
                         x_up=tuple(geom.get_float_array("x_up")))
    nm = body_db.get_int("num_markers")
    X = cib.make_disc(tuple(body_db.get_float_array("center")),
                      body_db.get_float("radius"), nm)
    bodies = cib.RigidBodies(body_id=jnp.zeros(nm, dtype=jnp.int32),
                             n_bodies=1)
    method = cib.CIBMethod(
        grid, bodies, mu=cib_db.get_float("mu", 1.0),
        kernel=cib_db.get_string("delta_fcn", "IB_4"),
        cg_tol=cib_db.get_float("cg_tol", 1e-9),
        cg_maxiter=cib_db.get_int("cg_maxiter", 400))

    F = body_db.get_float_array("force")
    tau = body_db.get_float("torque", 0.0)
    FT = jnp.asarray([[F[0], F[1], tau]], dtype=X.dtype)

    dt = ts.get_float("dt")
    num_steps = ts.get_int("num_steps")
    viz_dir = main_db.get_string("viz_dirname", "viz_cib")
    os.makedirs(viz_dir, exist_ok=True)
    metrics = MetricsLogger(main_db.get_string("log_file", "") or None)
    timers = TimerManager()

    step = jax.jit(lambda x: method.step(x, FT, dt))
    dump = main_db.get_int("viz_dump_interval", 0)
    for k in range(num_steps):
        with timers.scope("CIB::step"):
            X, U, info = step(X)
            jax.block_until_ready(X)
        cent = cib.body_centroids(X, bodies)
        metrics.log({"step": k + 1, "t": (k + 1) * dt,
                     "cg_converged": bool(info.converged),
                     "cg_iters": int(info.max_iters),
                     "centroid": np.asarray(cent[0]).tolist(),
                     "U": np.asarray(U[0]).tolist()})
        if dump and (k + 1) % dump == 0:
            np.save(os.path.join(viz_dir, f"markers_{k + 1:05d}.npy"),
                    np.asarray(X))
    metrics.close()
    print(timers.report())
    cent = cib.body_centroids(X, bodies)
    print(f"final centroid: {np.asarray(cent[0])}")
    return X


if __name__ == "__main__":
    main(sys.argv)
