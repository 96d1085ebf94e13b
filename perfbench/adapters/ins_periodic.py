"""The adapter of the periodic fluid-only family (``tg_*``): everything the
harness has to know about ``examples/navier_stokes/tgv3d/main.py``, its
``INSState``, the seeded velocity and ``perfbench/reference/ins_periodic.py``.
The contract is in ``perfbench/harness.py``'s docstring.
"""

from __future__ import annotations

import math

import numpy as np

BUILDER = "build_tgv_example"
SPIED = {"save": "save_checkpoint", "restore": "restore_checkpoint"}
faults = {}
rehearse_keys = {"CartesianGeometry": {"n_cells": [16, 16, 16]},
                 # CFL 0.20 at 16^3, as the configuration's own dt at its size
                 "INSStaggeredHierarchyIntegrator": {"dt": 0.08}}


def leaves(state) -> dict:
    """The program's INSState as the named leaves the reference takes."""
    return dict(u0=state.u[0], u1=state.u[1], u2=state.u[2], p=state.p,
                n0=state.n_prev[0], n1=state.n_prev[1], n2=state.n_prev[2],
                k=state.k)


def seed(integ, state, seed: int, seed_data: dict):
    """The built state (the analytic Taylor-Green field) plus a smooth
    solenoidal perturbation made from ``seed``: the discrete MAC curl of a
    vector potential of ``seeded.N_MODES`` Fourier modes sampled on the cell
    edges (its discrete divergence is nought to rounding), scaled to
    ``velocity_rms``.  Every seed draws the same number of modes from the
    same shells with the same amplitudes (``seeded.mode_table``: only
    directions and phases differ), so every seed is another state of the
    same cost.  Velocity only: ``perfbench/seeded.py`` also moves markers,
    and its output must stay as it is bit for bit."""
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
            # node-centred along the other two axes
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
            return out

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
    ref = module.FluidReference(db, lowp=lowp)
    if max(ref.dx) - min(ref.dx) > 1e-12 * max(ref.dx):
        raise ValueError(f"compare's div takes cubic cells, got {ref.dx}")
    return ref


def state_from(module, arrays: dict):
    return module.state_from_arrays(arrays)


def arrays_from(ref_state) -> dict:
    """A reference state as the named leaves ``compare`` reads: the
    control's output, put in the program's place."""
    return {**{f"u{d}": ref_state.u[d] for d in range(3)}, "p": ref_state.p}


def compare(ref_out, prog_out: dict, ref_in) -> dict:
    """``du``: the gap of the two final velocities against the reference's
    own change over the chunk (a state returned unchanged reads 1); ``p``:
    the relative L2 gap of the pressures (with |u| = O(1) the pressure is
    O(0.1)); ``div``: the largest discrete divergence of the program's own
    velocity times h / V0, V0 = 1 (the exact projection is the
    configuration's guarantee; the reference's reads 1e-15)."""
    f64 = lambda a: np.asarray(a, dtype=np.float64)  # noqa: E731
    u = [f64(prog_out[f"u{d}"]) for d in range(3)]
    gap = sum(float(np.sum((u[d] - ref_out.u[d]) ** 2)) for d in range(3))
    chg = sum(float(np.sum((ref_out.u[d] - ref_in.u[d]) ** 2))
              for d in range(3))
    # cubic cells (``reference`` refuses others): div * h = sum_d delta_d u_d
    div = sum(np.roll(u[d], -1, d) - u[d] for d in range(3))
    return {"du": (gap / chg) ** 0.5,
            "p": float(np.linalg.norm(f64(prog_out["p"]) - ref_out.p)
                       / np.linalg.norm(ref_out.p)),
            "div": float(np.max(np.abs(div)))}


def report(integ, db: dict) -> str:
    ins = db["INSStaggeredHierarchyIntegrator"]
    h = min(integ.grid.dx)
    return (f"fluid only: grid {integ.grid.n} convection "
            f"{integ.convective_op_type!r} dt {ins['dt']} "
            f"CFL at |u| = 1: {float(ins['dt']) / h:.3f} "
            f"Re {integ.rho / integ.mu:g}")


def grid_n(db: dict) -> list:
    return [int(v) for v in db["CartesianGeometry"]["n_cells"]]
