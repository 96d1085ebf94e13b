"""ex4-equivalent driver: 3D elastic shell in incompressible flow
(reference: examples/IB/explicit/ex4 main.cpp + input3d).

Run:  python examples/IB/explicit/ex4/main.py [input3d] [restart_dir step]
Multi-device: the Eulerian grid shards over all visible devices
automatically when more than one device is present (spatial domain
decomposition + the S2 sharded marker transfers).

The advance/viz/restart/health loop is the shared HierarchyDriver
skeleton (T13); this file is config + callbacks only.
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

from ibamr_tpu.models.shell3d import build_shell_example, shell_volume  # noqa: E402
from ibamr_tpu.utils import MetricsLogger, TimerManager, parse_input_file  # noqa: E402
from ibamr_tpu.utils.checkpoint import restore_checkpoint, save_checkpoint  # noqa: E402
from ibamr_tpu.utils.hierarchy_driver import HierarchyDriver, RunConfig  # noqa: E402


def main(argv):
    input_path = argv[1] if len(argv) > 1 else \
        os.path.join(os.path.dirname(__file__), "input3d")
    db = parse_input_file(input_path)
    main_db = db.get_database("Main")
    ins_db = db.get_database("INSStaggeredHierarchyIntegrator")

    integ, state = build_shell_example(input_db=db, dtype=jnp.float32)
    dev = jax.devices()[0]
    print(f"platform={dev.platform} device_kind={dev.device_kind} "
          f"devices={len(jax.devices())} engine={integ.ib.engine_name} "
          f"spectral_dtype={integ.ins.spectral_dtype or 'f32'}", flush=True)

    # shard over all devices when more than one is visible; on one
    # device the driver takes the integrator itself (step_fn=None), so
    # it can carry the packed marker layout through each chunk
    step_fn = None
    if len(jax.devices()) > 1:
        from ibamr_tpu.parallel import make_mesh, make_sharded_ib_step
        from ibamr_tpu.parallel.mesh import place_state

        mesh = make_mesh()
        state = place_state(state, integ.ins.grid, mesh)
        step_fn = make_sharded_ib_step(integ, mesh)
        print(f"sharding over mesh {dict(mesh.shape)}")

    start_step = 0
    if len(argv) > 3:
        state, start_step, _ = restore_checkpoint(argv[2], state,
                                                  step=int(argv[3]))
        print(f"restarted from {argv[2]} at step {start_step}")

    viz_dir = main_db.get_string("viz_dirname", "viz_ex4")
    rst_dir = main_db.get_string("restart_dirname", "restart_ex4")
    os.makedirs(viz_dir, exist_ok=True)
    geo = db.get_database_with_default("CartesianGeometry")
    x_lo = geo.get_array("x_lo", [0.0, 0.0, 0.0])
    x_up = geo.get_array("x_up", [1.0, 1.0, 1.0])
    center = tuple(0.5 * (lo + hi) for lo, hi in zip(x_lo, x_up))

    viz_int = main_db.get_int("viz_dump_interval", 0)
    cfg = RunConfig(
        dt=ins_db.get_float("dt"),
        num_steps=ins_db.get_int("num_steps"),
        viz_dump_interval=viz_int,
        restart_interval=main_db.get_int("restart_interval", 0),
        health_interval=min(20, viz_int) if viz_int else 20)

    tm = TimerManager.instance()
    with MetricsLogger(main_db.get_string("log_file"), echo=True) as log:

        def metrics_fn(s, step):
            rec = {
                "step": step,
                "t": s.ins.t,
                "volume": shell_volume(s.X, center),
                "ke": integ.ins.kinetic_energy(s.ins),
                "max_div": integ.ins.max_divergence(s.ins),
                "cfl_dt": integ.ins.cfl_dt(s.ins),
            }
            log.log(rec)
            return rec

        def viz_fn(s, step):
            np.savetxt(os.path.join(viz_dir, f"markers.{step:06d}.csv"),
                       np.asarray(s.X), delimiter=",")

        driver = HierarchyDriver(
            integ, cfg, step_fn=step_fn, metrics_fn=metrics_fn,
            viz_fn=viz_fn,
            checkpoint_fn=lambda s, k: save_checkpoint(rst_dir, s, k),
            timer=tm, timer_name="IB::advanceHierarchy")
        state = driver.run(state, start_step=start_step)
    print(tm.report())
    return state


if __name__ == "__main__":
    main(sys.argv)
