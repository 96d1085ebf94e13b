"""The adapter of the elastic-shell family (``ex4_shell_*``): everything the
harness has to know about ``examples/IB/explicit/ex4/main.py``, its
``IBState``, the shell's seeded data and ``perfbench/reference/ib_shell.py``.
The contract is in ``perfbench/harness.py``'s docstring.
"""

from __future__ import annotations

import numpy as np

BUILDER = "build_shell_example"
SPIED = {"save": "save_checkpoint", "restore": "restore_checkpoint"}

rehearse_keys = {"CartesianGeometry": {"n_cells": [16, 16, 16]},
                 "Shell": {"n_lat": 8, "n_lon": 8}}


def leaves(state) -> dict:
    """The program's IBState as the named leaves the reference takes."""
    ins = state.ins
    return dict(u0=ins.u[0], u1=ins.u[1], u2=ins.u[2], p=ins.p,
                n0=ins.n_prev[0], n1=ins.n_prev[1], n2=ins.n_prev[2],
                k=ins.k, X=state.X, U=state.U)


def seed(integ, state, seed: int, seed_data: dict):
    from perfbench import seeded

    g = integ.ins.grid
    return seeded.seeded_state(state, g.n, g.x_lo, g.x_up, seed,
                               seed_data["velocity_rms"],
                               seed_data["jitter_cells"])


def reference(module, db: dict, lowp=None):
    return module.ShellReference(db, lowp=lowp)


def state_from(module, arrays: dict):
    return module.state_from_arrays(arrays)


def arrays_from(ref_state) -> dict:
    """A reference state as the named leaves ``compare`` reads: the
    control's output, put in the program's place."""
    return {**{f"u{d}": ref_state.u[d] for d in range(3)},
            "p": ref_state.p, "U": ref_state.U, "X": ref_state.X}


def compare(ref_out, prog_out: dict, ref_in) -> dict:
    """The numbers compared, program against reference, for one chunk:
    the change of the velocity over the chunk (the gap between the two
    final fields against the reference's own change), the pressure and the
    marker velocity (gap against the reference's field), by L2 norms."""
    f64 = lambda a: np.asarray(a, dtype=np.float64)  # noqa: E731
    gap = sum(float(np.sum((f64(prog_out[f"u{d}"]) - ref_out.u[d]) ** 2))
              for d in range(3))
    chg = sum(float(np.sum((ref_out.u[d] - ref_in.u[d]) ** 2))
              for d in range(3))

    def rel(a, b):
        return float(np.linalg.norm(f64(a) - b) / np.linalg.norm(b))

    return {"du": (gap / chg) ** 0.5,
            "p": rel(prog_out["p"], ref_out.p),
            "U": rel(prog_out["U"], ref_out.U),
            "dX": float(np.linalg.norm(f64(prog_out["X"]) - ref_out.X)
                        / np.linalg.norm(ref_out.X - ref_in.X))}


def _half_markers(state):
    """Every second marker left out of the transfers."""
    return state._replace(mask=state.mask.at[::2].set(0))


faults = {"half_markers": _half_markers}


def report(integ, db: dict) -> str:
    """What resolved: the engine that ran beside the one the resolver names
    for this size, and the fallbacks counted."""
    from ibamr_tpu import obs
    from ibamr_tpu.models.engine_resolver import resolve_engine
    from ibamr_tpu.ops.delta import get_kernel

    named = resolve_engine(integ.ins.grid.n, int(db["Shell"]["n_lat"])
                           * int(db["Shell"]["n_lon"]),
                           get_kernel(integ.ib.kernel)[0],
                           spectral_dtype=integ.ins.spectral_dtype)
    fallbacks = {k: v for k, v in obs.metrics_snapshot()["counters"].items()
                 if k.startswith("engine_fallbacks_total") and v}
    return (f"engine ran {integ.ib.engine_name!r} resolver names {named!r} "
            f"forced {db.get('IBMethod', {}).get('transfer_engine')!r} "
            f"fallbacks {fallbacks}")


def grid_n(db: dict) -> list:
    return [int(v) for v in db["CartesianGeometry"]["n_cells"]]
