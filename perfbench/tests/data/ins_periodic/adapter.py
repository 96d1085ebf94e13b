"""The adapter of the test fixture ``ins_periodic``: a state without
markers, no checkpoint to spy and so no recovery (contract:
``perfbench/harness.py``'s docstring)."""

from __future__ import annotations

from typing import NamedTuple

import numpy as np

BUILDER = "build_fluid_example"
SPIED = {}
faults = {}
rehearse_keys = {"CartesianGeometry": {"n_cells": [16, 16, 16]}}


def leaves(state) -> dict:
    return dict(u0=state.u[0], u1=state.u[1], u2=state.u[2], p=state.p,
                n0=state.n_prev[0], n1=state.n_prev[1], n2=state.n_prev[2],
                k=state.k)


class _WithMarker(NamedTuple):
    ins: object
    X: object


def seed(integ, state, seed: int, seed_data: dict):
    """The velocity half of ``perfbench/seeded.py``: its one jitted call
    also displaces markers, so it is handed a single throwaway one."""
    import jax.numpy as jnp

    from perfbench import seeded

    g = integ.grid
    marker = jnp.full((1, 3), 0.5, state.p.dtype)
    return seeded.seeded_state(_WithMarker(state, marker), g.n, g.x_lo,
                               g.x_up, seed, seed_data["velocity_rms"],
                               0.0).ins


def reference(module, db: dict, lowp=None):
    return module.FluidReference(db, lowp=lowp)


def state_from(module, arrays: dict):
    return module.state_from_arrays(arrays)


def arrays_from(ref_state) -> dict:
    return {**{f"u{d}": ref_state.u[d] for d in range(3)}, "p": ref_state.p}


def compare(ref_out, prog_out: dict, ref_in) -> dict:
    """``du``: the gap of the final velocities against the reference's own
    change over the chunk; ``p``: the relative gap of the pressures."""
    f64 = lambda a: np.asarray(a, dtype=np.float64)  # noqa: E731
    gap = sum(float(np.sum((f64(prog_out[f"u{d}"]) - ref_out.u[d]) ** 2))
              for d in range(3))
    chg = sum(float(np.sum((ref_out.u[d] - ref_in.u[d]) ** 2))
              for d in range(3))
    return {"du": (gap / chg) ** 0.5,
            "p": float(np.linalg.norm(f64(prog_out["p"]) - ref_out.p)
                       / np.linalg.norm(ref_out.p))}


def report(integ, db: dict) -> str:
    return (f"fluid only: grid {integ.grid.n} convection "
            f"{db['INSStaggeredHierarchyIntegrator']['convective_op_type']!r}")


def grid_n(db: dict) -> list:
    return [int(v) for v in db["CartesianGeometry"]["n_cells"]]
