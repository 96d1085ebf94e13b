"""A driver that is not a shell, for the benchmark's own tests: the periodic
3D fluid solve alone (``INSStaggeredIntegrator``, no markers) through
``HierarchyDriver`` with a ``metrics_fn`` only.  The ``model_config`` PR that
brings the real fluid-only example replaces this file with it.

Run:  python main.py <input3d>
"""

import os
import sys

import jax.numpy as jnp

sys.path.insert(0, os.path.join(os.path.dirname(__file__), *[".."] * 4))

from ibamr_tpu.utils.backend_guard import auto_backend  # noqa: E402

auto_backend()

from ibamr_tpu.grid import StaggeredGrid  # noqa: E402
from ibamr_tpu.integrators.ins import INSStaggeredIntegrator  # noqa: E402
from ibamr_tpu.utils import MetricsLogger, parse_input_file  # noqa: E402
from ibamr_tpu.utils.hierarchy_driver import HierarchyDriver, RunConfig  # noqa: E402


def build_fluid_example(input_db, dtype=jnp.float32):
    geo = input_db.get_database("CartesianGeometry")
    ins_db = input_db.get_database("INSStaggeredHierarchyIntegrator")
    grid = StaggeredGrid(n=tuple(geo.get_int_array("n_cells")),
                         x_lo=tuple(geo.get_array("x_lo")),
                         x_up=tuple(geo.get_array("x_up")))
    integ = INSStaggeredIntegrator(
        grid, rho=ins_db.get_float("rho"), mu=ins_db.get_float("mu"),
        convective_op_type=ins_db.get_string("convective_op_type"),
        dtype=dtype)
    return integ, integ.initialize()


def main(argv):
    db = parse_input_file(argv[1])
    ins_db = db.get_database("INSStaggeredHierarchyIntegrator")
    integ, state = build_fluid_example(db)
    cfg = RunConfig(dt=ins_db.get_float("dt"),
                    num_steps=ins_db.get_int("num_steps"),
                    health_interval=20)
    with MetricsLogger(db.get_database("Main").get_string("log_file"),
                       echo=True) as log:
        driver = HierarchyDriver(
            integ, cfg, metrics_fn=lambda s, step: log.log(
                {"step": step, "ke": integ.kinetic_energy(s),
                 "max_div": integ.max_divergence(s)}))
        return driver.run(state)


if __name__ == "__main__":
    main(sys.argv)
