"""IBFE ex0-equivalent driver: stretched hyperelastic FE disc relaxing in
periodic incompressible flow (reference: examples/IBFE/explicit/ex0
main.cpp + input2d — IBFEMethod with a neo-Hookean solid).

Run:  python examples/IBFE/explicit/ex0/main.py [input2d]
"""

import os
import sys

import jax

sys.path.insert(0, os.path.join(os.path.dirname(__file__), *[".."] * 4))

# backend guard BEFORE any jax compute: honors JAX_PLATFORMS=cpu,
# otherwise requires a TPU (a run without a chip fails)
from ibamr_tpu.utils.backend_guard import auto_backend  # noqa: E402

auto_backend()

import numpy as np  # noqa: E402

from ibamr_tpu.models.fe_disc2d import build_fe_disc_example  # noqa: E402
from ibamr_tpu.utils import MetricsLogger, TimerManager, parse_input_file  # noqa: E402


def main(argv):
    input_path = argv[1] if len(argv) > 1 else \
        os.path.join(os.path.dirname(__file__), "input2d")
    db = parse_input_file(input_path)
    main_db = db.get_database("Main")
    ts = db.get_database("TimeStepping")

    integ, state = build_fe_disc_example(input_db=db)
    fe = integ.ib

    dt = ts.get_float("dt")
    num_steps = ts.get_int("num_steps")
    viz_dir = main_db.get_string("viz_dirname", "viz_ibfe")
    os.makedirs(viz_dir, exist_ok=True)
    metrics = MetricsLogger(main_db.get_string("log_file", "") or None)
    timers = TimerManager()

    step = jax.jit(lambda s: integ.step(s, dt))
    dump = main_db.get_int("viz_dump_interval", 0)
    A0 = float(fe.current_volume(state.X))
    for k in range(num_steps):
        with timers.scope("IBFE::step"):
            state = step(state)
            jax.block_until_ready(state.X)
        if (k + 1) % 10 == 0 or k == 0:
            E = float(fe.energy(state.X))
            A = float(fe.current_volume(state.X))
            metrics.log({"step": k + 1, "t": (k + 1) * dt,
                         "elastic_energy": E,
                         "area": A, "area_drift": (A - A0) / A0})
        if dump and (k + 1) % dump == 0:
            np.save(os.path.join(viz_dir, f"nodes_{k + 1:05d}.npy"),
                    np.asarray(state.X))
    metrics.close()
    print(timers.report())
    print(f"final elastic energy: {float(fe.energy(state.X)):.6g}, "
          f"area drift: {(float(fe.current_volume(state.X)) - A0) / A0:.3e}")
    return state


if __name__ == "__main__":
    main(sys.argv)
