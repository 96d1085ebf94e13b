"""ex0-equivalent driver: 2D periodic elastic membrane in incompressible
flow (reference: examples/IB/explicit/ex0 main.cpp + input2d).

Run:  python examples/IB/explicit/ex0/main.py [input2d] [restart_dir step]
"""

import os
import sys

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.join(os.path.dirname(__file__), *[".."] * 4))

# backend guard BEFORE any jax compute: honors JAX_PLATFORMS=cpu,
# otherwise requires a TPU (a run without a chip fails)
from ibamr_tpu.utils.backend_guard import auto_backend  # noqa: E402

auto_backend()

import numpy as np  # noqa: E402

from ibamr_tpu.integrators.ib import advance_ib, polygon_area  # noqa: E402
from ibamr_tpu.models.membrane2d import build_membrane_example  # noqa: E402
from ibamr_tpu.utils import MetricsLogger, TimerManager, parse_input_file  # noqa: E402
from ibamr_tpu.utils.checkpoint import restore_checkpoint, save_checkpoint  # noqa: E402


def main(argv):
    input_path = argv[1] if len(argv) > 1 else \
        os.path.join(os.path.dirname(__file__), "input2d")
    db = parse_input_file(input_path)
    main_db = db.get_database("Main")
    ins_db = db.get_database("INSStaggeredHierarchyIntegrator")

    integ, state = build_membrane_example(input_db=db, dtype=jnp.float32)

    # optional restart: main.py input2d <restart_dir> <step>
    start_step = 0
    if len(argv) > 3:
        state, start_step, _ = restore_checkpoint(argv[2], state,
                                                  step=int(argv[3]))
        print(f"restarted from {argv[2]} at step {start_step}")

    dt = ins_db.get_float("dt")
    num_steps = ins_db.get_int("num_steps")
    viz_int = main_db.get_int("viz_dump_interval", 0)
    rst_int = main_db.get_int("restart_interval", 0)
    viz_dir = main_db.get_string("viz_dirname", "viz_ex0")
    rst_dir = main_db.get_string("restart_dirname", "restart_ex0")
    os.makedirs(viz_dir, exist_ok=True)

    tm = TimerManager.instance()
    with MetricsLogger(main_db.get_string("log_file"), echo=True) as metrics:
        step = start_step
        while step < num_steps:
            chunk = min(viz_int or 50, num_steps - step)
            with tm.scope("IB::advanceHierarchy"):
                state = advance_ib(integ, state, dt, chunk)
                jax.block_until_ready(state.X)
            step += chunk
            metrics.log({
                "step": step,
                "t": state.ins.t,
                "area": polygon_area(state.X),
                "ke": integ.ins.kinetic_energy(state.ins),
                "max_div": integ.ins.max_divergence(state.ins),
                "cfl_dt": integ.ins.cfl_dt(state.ins),
            })
            if viz_int:
                np.savetxt(os.path.join(viz_dir, f"markers.{step:06d}.csv"),
                           np.asarray(state.X), delimiter=",")
            if rst_int and step % rst_int == 0:
                save_checkpoint(rst_dir, state, step)
    print(tm.report())
    return state


if __name__ == "__main__":
    main(sys.argv)
