"""The adapter of the open-boundary IB family (``dfg3d_*``): everything the
harness has to know about ``examples/IB/explicit/dfg3d/main.py``, its
``IBOpenState``, the seeded data and ``perfbench/reference/ib_open.py``.  The
contract is in ``perfbench/harness.py``'s docstring.
"""

from __future__ import annotations

import time
import types
from typing import NamedTuple

import numpy as np

from perfbench.adapters import ins_walls
from perfbench.adapters.ins_periodic import grid_n  # noqa: F401

BUILDER = "build_dfg3d_example"
SPIED = {"save": "save_checkpoint", "restore": "restore_checkpoint"}
faults = {}
FLOW = 2                # array axes (y, z, x)
# the rehearsal's duct at a size a CPU runs: 12 x 12 x 64 cells (h = 34 mm
# across, D = 2.9 h), one step a chunk, dt 6.5 ms (CFL 0.43 at U_m), and
# the targets' stiffness and damping at the configuration's multiples of
# rho h^3 / dt^2 (0.86) and rho h^3 / dt (0.52)
rehearse_keys = {"CartesianGeometry": {"n_cells": [12, 12, 64]},
                 "INSStaggeredHierarchyIntegrator": {"dt": 0.0065},
                 "IBMethod": {"kappa": 0.812, "eta": 3.19e-3},
                 "Main": {"health_interval": 1}}

# when ``seed`` made this run's state: ``_unconverged`` counts the spans
# from then on (a process that ran other solves before keeps their spans)
_SEEDED_AT = [0.0]


class _Grid(NamedTuple):
    u: tuple


def leaves(state) -> dict:
    """The program's IBOpenState as the named leaves the reference takes
    (``F``: the net force the markers spread in the last step)."""
    fl = state.fluid
    return dict(u0=fl.u[0], u1=fl.u[1], u2=fl.u[2], p=fl.p, X=state.X,
                U=state.U, F=state.F_net)


def seed(integ, state, seed: int, seed_data: dict):
    """A state of the run and not of the built one: the inflow profile
    carried down the duct (the built state) plus a smooth solenoidal
    velocity made from ``seed`` that is 0 on every boundary face: the walled
    family's seeded velocity (``ins_walls.seed``: the MAC curl of
    ``seeded.N_MODES`` Fourier modes under the envelope prod sin^2(pi x_d /
    L_d), on the periodic lower-face grid, scaled to ``velocity_rms``), with
    each component's upper boundary face appended as its wrap image, the
    lower face, which the envelope makes 0.  The inflow faces stay exact,
    the markers stay at their targets, and every seed is another state of
    the same cost."""
    import jax.numpy as jnp

    _SEEDED_AT[0] = time.perf_counter()
    g = integ.grid
    zeros = tuple(jnp.zeros(g.n, c.dtype) for c in state.fluid.u)
    w = ins_walls.seed(types.SimpleNamespace(grid=g), _Grid(u=zeros), seed,
                       seed_data).u
    u = tuple(c + jnp.concatenate([a, jnp.take(a, jnp.arange(1), axis=d)],
                                  axis=d)
              for d, (c, a) in enumerate(zip(state.fluid.u, w)))
    return state._replace(fluid=state.fluid._replace(u=u))


def reference(module, db: dict, lowp=None):
    return module.OpenReference(db, lowp=lowp)


def state_from(module, arrays: dict):
    return module.state_from_arrays(arrays)


def arrays_from(ref_state) -> dict:
    """A reference state as the named leaves ``compare`` reads: the
    control's output, put in the program's place."""
    return {**{f"u{d}": ref_state.u[d] for d in range(3)}, "p": ref_state.p,
            "X": ref_state.X, "U": ref_state.U, "F": ref_state.F}


def _unconverged() -> float:
    """Saddle solves that ended above their tolerance, over the program's
    ``driver/chunk/krylov`` spans since the run's state was seeded (the
    whole run: set-up, window and traced chunks); inf where the program
    closes none (its chunks report no solve)."""
    try:
        from ibamr_tpu import obs

        got = [s for s in obs.spans() if s["t0"] >= _SEEDED_AT[0]
               and s["path"].endswith("driver/chunk/krylov")]
    except (ImportError, AttributeError):
        return float("inf")
    return float(sum(s["attrs"]["unconverged"] for s in got)) if got \
        else float("inf")


def compare(ref_out, prog_out: dict, ref_in) -> dict:
    """``du``: the gap of the two final velocities against the reference's
    own change over the chunk (a state returned unchanged reads 1); ``p``:
    the relative L2 gap of the pressures (the outflow fixes the constant);
    ``div``: the largest discrete divergence of the program's own velocity
    times the smallest cell width, over the mean inflow speed 1; ``dX``:
    the gap of the final marker positions against the reference's own
    displacement of them; ``inflow``: the largest change over the chunk of
    a prescribed face of the program's (the inflow faces and the walls'
    normal faces: identity rows of the solve, held exactly);
    ``unconverged``: ``_unconverged``."""
    f64 = lambda a: np.asarray(a, dtype=np.float64)  # noqa: E731
    u = [f64(prog_out[f"u{d}"]) for d in range(3)]
    gap = sum(float(np.sum((u[d] - ref_out.u[d]) ** 2)) for d in range(3))
    chg = sum(float(np.sum((ref_out.u[d] - ref_in.u[d]) ** 2))
              for d in range(3))
    n = [c.shape[d] - 1 for d, c in enumerate(u)]
    div = 0.0
    for d in range(3):
        lo = [slice(None)] * 3
        hi = [slice(None)] * 3
        lo[d], hi[d] = slice(0, n[d]), slice(1, n[d] + 1)
        div = div + (u[d][tuple(hi)] - u[d][tuple(lo)]) / ref_out.dx[d]
    held = 0.0
    for d in range(3):
        for i in ([0] if d == FLOW else [0, n[d]]):
            sl = [slice(None)] * 3
            sl[d] = i
            held = max(held, float(np.max(np.abs(
                u[d][tuple(sl)] - ref_in.u[d][tuple(sl)]))))
    X = f64(prog_out["X"])
    p, p_ref = f64(prog_out["p"]), ref_out.p
    return {"du": (gap / chg) ** 0.5,
            "p": float(np.linalg.norm(p - p_ref) / np.linalg.norm(p_ref)),
            "div": float(np.max(np.abs(div))) * min(ref_out.dx),
            "dX": float(np.linalg.norm(X - ref_out.X)
                        / np.linalg.norm(ref_out.X - ref_in.X)),
            "inflow": held,
            "unconverged": _unconverged()}


def report(integ, db: dict) -> str:
    """What resolved: the transfer engine, the solve's geometry, the CFL
    at the inflow's peak speed."""
    ins = integ.ins
    s = ins.solver
    h = min(ins.dx)
    u_m = float(db["Inflow"]["U_m"])
    dt = float(db["INSStaggeredHierarchyIntegrator"]["dt"])
    return (f"open-boundary IB: grid {ins.n} engine "
            f"{integ.ib.engine_name!r} convection {ins.convective_op_type!r} "
            f"FGMRES m {s.m} restarts {s.restarts} nu {s.nu_sweeps} tol "
            f"{s.tol:g} multigrid levels {len(s.p_mg.levels)} dt {dt} CFL "
            f"at U_m: {dt * u_m / h:.3f} Re "
            f"{ins.rho * 4.0 * u_m / 9.0 * 0.1 / ins.mu:g}")
