"""The adapter of the wall-bounded fluid-only family (``cavity_*``): everything
the harness has to know about ``examples/navier_stokes/cavity3d/main.py``, its
``INSState``, the seeded velocity and ``perfbench/reference/ins_walls.py``.
The contract is in ``perfbench/harness.py``'s docstring.
"""

from __future__ import annotations

import math

import numpy as np

# the state, its leaves and the grid's keys are the periodic fluid-only
# family's: the same INSState behind the same input-file vocabulary
from perfbench.adapters.ins_periodic import (arrays_from, grid_n,  # noqa: F401
                                             leaves, state_from)

BUILDER = "build_cavity_example"
SPIED = {"save": "save_checkpoint", "restore": "restore_checkpoint"}
faults = {}
rehearse_keys = {"CartesianGeometry": {"n_cells": [16, 16, 16]},
                 # CFL 0.20 at U_lid on 16^3, as the configuration's own dt
                 # at its size
                 "INSStaggeredHierarchyIntegrator": {"dt": 0.0125}}


def seed(integ, state, seed: int, seed_data: dict):
    """A state of the run and not of rest: the built state (at rest) plus a
    smooth velocity made from ``seed`` that is discretely solenoidal and 0
    on every wall: the discrete MAC curl of a vector potential of
    ``seeded.N_MODES`` Fourier modes sampled on the cell edges, under the
    envelope prod_d sin^2(pi x_d / L_d), which vanishes with its first
    derivative on the six walls (so the potential's node values on a wall
    are 0, and with them the normal velocity there, and the tangential
    velocity goes to 0 towards a wall), scaled to ``velocity_rms``.  Every
    seed draws the same number of modes from the same shells with the same
    amplitudes (``seeded.mode_table``: only directions and phases differ).
    Velocity only; ``perfbench/seeded.py`` stays as it is bit for bit."""
    import jax
    import jax.numpy as jnp

    from perfbench import seeded

    g = integ.grid
    n, dx = g.n, g.dx
    length = tuple(hi - lo for lo, hi in zip(g.x_lo, g.x_up))
    n_modes = seeded.N_MODES
    rms = float(seed_data["velocity_rms"])

    # the mode table is an ARGUMENT of the jitted call: were it a constant,
    # every seed would be another program (PERF.md, PR 24: 50 s of set-up)
    @jax.jit
    def perturbed(u, ks, phases, amps):
        def potential(c):
            # component c of A lives on the c-edges: cell-centred along c,
            # node-centred along the other two axes (node 0 is the lo wall;
            # the hi wall's node is its wrap image, and 0 like it)
            xs = [((jnp.arange(n[d], dtype=jnp.float32)
                    + (0.5 if d == c else 0.0)) * (dx[d] / length[d])
                   ).reshape([-1 if e == d else 1 for e in range(3)])
                  for d in range(3)]
            out = 0.0
            for m in range(n_modes):
                arg = 2.0 * math.pi * sum(ks[m, d] * xs[d]
                                          for d in range(3)) + phases[m]
                # over |k|: every mode carries the same velocity
                out = out + amps[m, c] * jnp.sin(arg) / jnp.linalg.norm(ks[m])
            return out * math.prod(jnp.sin(math.pi * x) ** 2 for x in xs)

        A = [potential(c) for c in range(3)]

        def dplus(a, axis):
            return (jnp.roll(a, -1, axis) - a) / dx[axis]

        w = [dplus(A[2], 1) - dplus(A[1], 2),
             dplus(A[0], 2) - dplus(A[2], 0),
             dplus(A[1], 0) - dplus(A[0], 1)]
        scale = rms / jnp.sqrt(sum(jnp.mean(c * c) for c in w))
        return tuple((a + scale * c).astype(a.dtype) for a, c in zip(u, w))

    ks, phases, amps = seeded.mode_table(seed)
    f32 = lambda a: jnp.asarray(a[:n_modes], jnp.float32)  # noqa: E731
    return state._replace(u=perturbed(state.u, f32(ks), f32(phases),
                                      f32(amps)))


def reference(module, db: dict, lowp=None):
    ref = module.WallReference(db, lowp=lowp)
    if max(ref.dx) - min(ref.dx) > 1e-12 * max(ref.dx):
        raise ValueError(f"compare's div takes cubic cells, got {ref.dx}")
    return ref


def compare(ref_out, prog_out: dict, ref_in) -> dict:
    """``du``: the gap of the two final velocities against the reference's
    own change over the chunk (a state returned unchanged reads 1); ``p``:
    the relative L2 gap of the pressures, each with its mean removed (the
    Neumann problem fixes the pressure only up to a constant); ``div``: the
    largest discrete divergence of the program's own velocity times h /
    U_lid, U_lid = 1 (the exact projection is the configuration's
    guarantee; the reference's reads 1e-15).  The hi wall face of a
    component is the wrap image of its slot 0, which is 0."""
    f64 = lambda a: np.asarray(a, dtype=np.float64)  # noqa: E731
    u = [f64(prog_out[f"u{d}"]) for d in range(3)]
    gap = sum(float(np.sum((u[d] - ref_out.u[d]) ** 2)) for d in range(3))
    chg = sum(float(np.sum((ref_out.u[d] - ref_in.u[d]) ** 2))
              for d in range(3))
    # cubic cells (``reference`` refuses others): div * h = sum_d delta_d u_d
    div = sum(np.roll(u[d], -1, d) - u[d] for d in range(3))
    p, p_ref = f64(prog_out["p"]), ref_out.p
    p_ref = p_ref - p_ref.mean()
    return {"du": (gap / chg) ** 0.5,
            "p": float(np.linalg.norm(p - p.mean() - p_ref)
                       / np.linalg.norm(p_ref)),
            "div": float(np.max(np.abs(div)))}


def report(integ, db: dict) -> str:
    ins = db["INSStaggeredHierarchyIntegrator"]
    h = min(integ.grid.dx)
    u_lid = float(ins["U_lid"])
    return (f"wall-bounded fluid only: grid {integ.grid.n} walls "
            f"{integ.wall_axes} convection {integ.convective_op_type!r} "
            f"dt {ins['dt']} CFL at U_lid: {float(ins['dt']) * u_lid / h:.3f} "
            f"Re {integ.rho * u_lid / integ.mu:g}")
