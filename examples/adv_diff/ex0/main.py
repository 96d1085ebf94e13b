"""IB + adv-diff driver: passive scalar released at the immersed membrane
markers (reference parity: AdvDiffSemiImplicitHierarchyIntegrator P19
registered with the IB/INS integrator, marker sources a la
IBStandardSourceGen P14 — SURVEY.md §2.2).

Run:  python examples/adv_diff/ex0/main.py [input2d]
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

from ibamr_tpu.integrators.adv_diff import (  # noqa: E402
    AdvDiffSemiImplicitIntegrator, TransportedQuantity)
from ibamr_tpu.integrators.ib import polygon_area  # noqa: E402
from ibamr_tpu.models.membrane2d import build_membrane_example  # noqa: E402
from ibamr_tpu.ops import interaction  # noqa: E402
from ibamr_tpu.utils import MetricsLogger, TimerManager, parse_input_file  # noqa: E402


def main(argv):
    input_path = argv[1] if len(argv) > 1 else \
        os.path.join(os.path.dirname(__file__), "input2d")
    db = parse_input_file(input_path)
    main_db = db.get_database("Main")
    ins_db = db.get_database("INSStaggeredHierarchyIntegrator")
    ad_db = db.get_database_with_default(
        "AdvDiffSemiImplicitHierarchyIntegrator")

    integ, state = build_membrane_example(input_db=db, dtype=jnp.float32)
    grid = integ.ins.grid
    kernel = integ.ib.kernel

    adv = AdvDiffSemiImplicitIntegrator(
        grid,
        [TransportedQuantity(
            "C", kappa=ad_db.get_float("kappa", 1e-3),
            convective_op_type=ad_db.get_string("convective_op_type",
                                                "upwind"))],
        dtype=jnp.float32)
    ad_state = adv.initialize()
    strength = ad_db.get_float("source_strength", 1.0)

    def coupled_step(ib_state, ad_state, dt):
        """One IB step, then the scalar advected by the new velocity with
        a source spread from the markers (unit strength per marker)."""
        ib_new = integ.step(ib_state, dt)
        src_markers = jnp.full((ib_new.X.shape[0],), strength,
                               dtype=jnp.float32)
        src = interaction.spread(src_markers, grid, ib_new.X,
                                 centering="cell", kernel=kernel,
                                 weights=ib_new.mask)
        ad_new = adv.step(ad_state, dt, u=ib_new.ins.u, sources=[src])
        return ib_new, ad_new

    step_fn = jax.jit(coupled_step)

    dt = ins_db.get_float("dt")
    num_steps = ins_db.get_int("num_steps")
    viz_int = main_db.get_int("viz_dump_interval", 0)
    viz_dir = main_db.get_string("viz_dirname", "viz_adv_diff")
    os.makedirs(viz_dir, exist_ok=True)

    tm = TimerManager.instance()
    with MetricsLogger(main_db.get_string("log_file"), echo=True) as metrics:
        step = 0
        while step < num_steps:
            chunk = min(viz_int or 25, num_steps - step)
            with tm.scope("IBAdvDiff::advanceHierarchy"):
                for _ in range(chunk):
                    state, ad_state = step_fn(state, ad_state, dt)
                jax.block_until_ready(ad_state.Q)
            step += chunk
            metrics.log({
                "step": step,
                "t": state.ins.t,
                "area": polygon_area(state.X),
                "scalar_total": adv.total(ad_state),
                "scalar_max": jnp.max(ad_state.Q[0]),
                "max_div": integ.ins.max_divergence(state.ins),
            })
            if viz_int:
                np.save(os.path.join(viz_dir, f"scalar.{step:06d}.npy"),
                        np.asarray(ad_state.Q[0]))
    print(tm.report())
    return state, ad_state


if __name__ == "__main__":
    main(sys.argv)
