"""Lid-driven-cavity driver: the classic wall-bounded NS validation
(reference: the navier_stokes lid-cavity examples over the staggered
INS integrator with physical-wall Dirichlet BCs; Ghia, Ghia & Shin
1982 for the benchmark profiles). All four walls are no-slip; the top
lid moves at U_lid. The u(x=0.5, y) centerline profile and the
primary-vortex strength land in the metrics JSONL for comparison
against the Ghia table (pinned at Re=100 by
tests/test_ins_ppm_walls.py::test_lid_driven_cavity_re100_ghia).

Run:  python examples/navier_stokes/cavity2d/main.py [input2d]

This file keeps its own Python loop because the Ghia test pins it. The
cavity on the normal path (``HierarchyDriver``: scan chunks, health vector,
checkpoint and restart) is ``examples/navier_stokes/cavity3d/main.py``, the
cubic cavity at Re = 1000; start a new wall-bounded case from that one.
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), *[".."] * 3))

from ibamr_tpu.utils.backend_guard import auto_backend  # noqa: E402

auto_backend()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from ibamr_tpu.grid import StaggeredGrid  # noqa: E402
from ibamr_tpu.integrators.ins import (INSStaggeredIntegrator,  # noqa: E402
                                       advance)
from ibamr_tpu.io.vtk import write_vti  # noqa: E402
from ibamr_tpu.utils import MetricsLogger, TimerManager, \
    parse_input_file  # noqa: E402


def main(argv):
    input_path = argv[1] if len(argv) > 1 else \
        os.path.join(os.path.dirname(__file__), "input2d")
    db = parse_input_file(input_path)
    main_db = db.get_database("Main")
    geo = db.get_database("CartesianGeometry")
    idb = db.get_database("INSStaggeredHierarchyIntegrator")

    n = tuple(geo.get_int_array("n"))
    grid = StaggeredGrid(n=n, x_lo=tuple(geo.get_float_array("x_lo")),
                         x_up=tuple(geo.get_float_array("x_up")))
    u_lid = idb.get_float("U_lid", 1.0)
    integ = INSStaggeredIntegrator(
        grid, rho=idb.get_float("rho", 1.0), mu=idb.get_float("mu"),
        convective_op_type=idb.get_string("convective_op_type", "ppm"),
        wall_axes=(True, True),
        # component 0's tangential velocity on the hi wall of axis 1:
        # the moving lid
        wall_tangential={(0, 1, 1): u_lid})
    st = integ.initialize()

    viz_dir = main_db.get_string("viz_dirname", "viz_cavity2d")
    os.makedirs(viz_dir, exist_ok=True)
    metrics = MetricsLogger(main_db.get_string("log_jsonl",
                                               "cavity2d_metrics.jsonl"))
    timers = TimerManager()
    dt = idb.get_float("dt")
    num_steps = idb.get_int("num_steps")
    viz_int = main_db.get_int("viz_dump_interval", 0)
    chunk = main_db.get_int("log_interval", viz_int if viz_int else
                            num_steps)

    k = 0
    while k < num_steps:
        m = min(chunk, num_steps - k)
        with timers.scope("advance"):
            st = advance(integ, st, dt, m)
            jax.block_until_ready(st.u[0])
        k += m
        uc = np.asarray(st.u[0][n[0] // 2, :])
        metrics.log({"step": k, "t": float(st.t),
                     "u_center_min": float(uc.min()),
                     "max_div": float(integ.max_divergence(st))})
        print(f"step {k}: primary-vortex u_min {uc.min():.5f} "
              f"(Ghia Re=100: -0.21090), max div "
              f"{float(integ.max_divergence(st)):.1e}")
        if viz_int and k % viz_int == 0:
            write_vti(os.path.join(viz_dir, f"cavity_{k:05d}.vti"),
                      grid, {"p": np.asarray(st.p)})
    # final centerline profile for offline Ghia comparison
    metrics.log({"step": k, "centerline_u":
                 [float(v) for v in np.asarray(st.u[0][n[0] // 2, :])]})
    print(timers.report())


if __name__ == "__main__":
    main(sys.argv)
