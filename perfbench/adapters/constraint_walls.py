"""The adapter of the rigid-body family (``falling_sphere_*``): everything the
harness has to know about ``examples/ConstraintIB/falling_sphere/main.py``,
its ``ConstraintIBState``, the seeded data and
``perfbench/reference/constraint_walls.py``.  The contract is in
``perfbench/harness.py``'s docstring.
"""

from __future__ import annotations

import math

import numpy as np

from perfbench.adapters import ins_walls
from perfbench.adapters.ins_periodic import grid_n  # noqa: F401

BUILDER = "build_falling_sphere_example"
SPIED = {"save": "save_checkpoint", "restore": "restore_checkpoint"}

# the rehearsal's tank at a size a CPU runs: h = 2.5 mm at the configuration's
# own CFL (0.10 at its settling speed), and a sphere of twice the diameter,
# d = 12 h: 7,153 markers, enough for the resolver to name the packed engine
# on extents the engine's tiles divide
rehearse_keys = {"CartesianGeometry": {"n_cells": [40, 40, 64]},
                 "INSStaggeredHierarchyIntegrator": {"dt": 0.002},
                 "Sphere": {"diameter": 0.03}}
# the pairs of markers whose distances ``rigid`` samples, and how far apart
# (in diameters) a pair has to start to be taken
RIGID_PAIRS, RIGID_MIN_DIAMETERS = 4096, 0.5
# a face is inside the body where the spread indicator S(1) is within a
# tenth of its interior value (8 markers a cell: S(1) h^3 = 8)
BODY_INSIDE = 0.9
# how often the seeder imposes the body's velocity on the fluid under it and
# projects (each round leaves a third of the last one's gap inside a ball),
# and how many cells under the lid the seeded sphere's top stays
SEED_ROUNDS, SEED_TOP_CELLS = 8, 4


def leaves(state) -> dict:
    """The program's ConstraintIBState as the named leaves the reference
    takes (``Ub``: the bodies' rigid modes, (1, 6))."""
    ins = state.ins
    return dict(u0=ins.u[0], u1=ins.u[1], u2=ins.u[2], p=ins.p,
                n0=ins.n_prev[0], n1=ins.n_prev[1], n2=ins.n_prev[2],
                k=ins.k, X=state.X, Ub=state.U_body)


def _carried_fluid(ins, u, centre, radius: float, velocity):
    """``u`` with the fluid inside the ball moving with it, and around it
    the potential flow that goes with that: ``SEED_ROUNDS`` times, the
    velocity within ``radius`` of ``centre`` (blended over one cell across
    the surface) is replaced by ``velocity`` and the whole projected by the
    program's own wall projection, so what is returned is solenoidal and 0
    on the walls.  The centre and the velocity are ARGUMENTS of the jitted
    call: as constants, every seed would be another program."""
    import jax
    import jax.numpy as jnp

    g = ins.grid
    h = min(g.dx)

    @jax.jit
    def carried(u, centre, velocity):
        def inside(comp):
            r2 = 0.0
            for d in range(3):
                x = g.x_lo[d] + (jnp.arange(g.n[d], dtype=jnp.float32)
                                 + (0.0 if d == comp else 0.5)) * g.dx[d]
                r2 = r2 + ((x - centre[d]) ** 2).reshape(
                    [-1 if e == d else 1 for e in range(3)])
            return jnp.clip((radius - jnp.sqrt(r2)) / h + 0.5, 0.0, 1.0)

        chi = [inside(c) for c in range(3)]

        def impose(_, u):
            u = tuple(c + x * (v - c) for c, x, v in zip(u, chi, velocity))
            return ins.project(u, g.dx)[0]

        return jax.lax.fori_loop(0, SEED_ROUNDS, impose, tuple(u))

    return carried(u, jnp.asarray(centre, jnp.float32),
                   jnp.asarray(velocity, jnp.float32))


def seed(method, state, seed: int, seed_data: dict):
    """A state of the fall and not of rest.  The sphere: moved as a whole,
    by the generator's draws, to a height of its centre in ``height`` and a
    lateral offset from the tank's axis of up to ``lateral`` on each axis,
    falling at ``fall_speed`` along the last axis (no rotation, no lateral
    motion).  The fluid: the walled family's seeded velocity
    (``ins_walls.seed``: the MAC curl of ``seeded.N_MODES`` Fourier modes
    under the envelope prod sin^2(pi x_d / L_d), solenoidal and 0 on the six
    walls, scaled to ``velocity_rms``), and in it the fluid under the sphere
    falling with it (``_carried_fluid``): a body handed a speed over a fluid
    that stands still inside it is a second release, not a fall.  The
    translation is a multiple of no cell, so every seed meets the grid
    otherwise; the marker count, the lattice and every array's shape stay
    the built ones."""
    import jax.numpy as jnp

    ins = ins_walls.seed(method.ins, state.ins, seed, seed_data)
    g = method.ins.grid
    rng = np.random.Generator(np.random.PCG64(int(seed) + 1))
    lo, hi = seed_data["height"]
    centre = np.array([0.5 * (a + b) for a, b in zip(g.x_lo, g.x_up)])
    centre[:2] += rng.uniform(-1.0, 1.0, 2) * float(seed_data["lateral"])
    built = np.asarray(state.X, np.float64)
    radius = 0.5 * float(np.ptp(built[:, 0]))
    # the rehearsal's sphere is twice as wide: whatever the size, the top
    # of the sphere starts SEED_TOP_CELLS cells under the lid or lower
    centre[2] = min(g.x_lo[2] + rng.uniform(float(lo), float(hi)),
                    g.x_up[2] - radius - SEED_TOP_CELLS * g.dx[2])
    X = state.X + jnp.asarray(centre - built.mean(axis=0), state.X.dtype)
    fall = [0.0, 0.0, -float(seed_data["fall_speed"])]
    ins = ins._replace(u=_carried_fluid(method.ins, ins.u, centre, radius,
                                        fall))
    U_body = jnp.zeros_like(state.U_body).at[0, :3].set(
        jnp.asarray(fall, state.U_body.dtype))
    return state._replace(ins=ins, X=X, U_body=U_body)


def reference(module, db: dict, lowp=None):
    ref = module.ConstraintReference(db, lowp=lowp)
    if max(ref.dx) - min(ref.dx) > 1e-12 * max(ref.dx):
        raise ValueError(f"compare's div takes cubic cells, got {ref.dx}")
    return ref


def state_from(module, arrays: dict):
    return module.state_from_arrays(arrays)


def arrays_from(ref_state) -> dict:
    """A reference state as the named leaves ``compare`` reads: the
    control's output, put in the program's place."""
    return {**{f"u{d}": ref_state.u[d] for d in range(3)}, "p": ref_state.p,
            "X": ref_state.X, "Ub": ref_state.U_body[None, :]}


def _pair_distances(X: np.ndarray, body: np.ndarray):
    """Distances of a fixed sample of marker pairs in ``X``, and in ``body``
    where they are chosen: pairs at least ``RIGID_MIN_DIAMETERS`` of the
    body's extent apart: a spinning body's float32 positions (ulp 7.5e-9 at
    0.1) take a rounding of their own every step, which reads 3e-4 of such a
    distance after a thousand steps and twice that of one half as long."""
    rng = np.random.Generator(np.random.PCG64(len(body)))
    i, j = rng.integers(0, len(body), (2, 4 * RIGID_PAIRS))
    d_body = np.linalg.norm(body[i] - body[j], axis=1)
    extent = np.max(body.max(axis=0) - body.min(axis=0))
    far = np.flatnonzero(d_body >= RIGID_MIN_DIAMETERS * extent)[:RIGID_PAIRS]
    i, j = i[far], j[far]
    return np.linalg.norm(X[i] - X[j], axis=1), d_body[far]


def _body_gap(ref_out, prog_out: dict, Ub: np.ndarray) -> float:
    """The gap of the velocity INSIDE the body that the gap of its rigid
    modes does not explain: over the faces where the chunk's last step
    imposed nothing but the body's motion (the reference's spread indicator
    at least ``BODY_INSIDE`` of its largest), the root mean square of
    (u_prog - u_ref) - (V_prog - V_ref), over the body's speed |V_ref|.
    There every step REPLACES the velocity by a local mean of the
    transfers' own output, U_b - U_i, so what they lose in ONE step stands
    there undiluted, where the whole tank's ``du`` drowns it in 6.5 million
    faces and ``dUb`` averages it over every marker."""
    gap = faces = 0.0
    for c in range(3):
        s1 = ref_out.indicator[c]
        inside = s1 >= BODY_INSIDE * s1.max()
        g = (np.asarray(prog_out[f"u{c}"], np.float64)[inside]
             - ref_out.u[c][inside]) - (Ub[c] - ref_out.U_body[c])
        gap, faces = gap + float(np.sum(g * g)), faces + int(inside.sum())
    return math.sqrt(gap / faces) / float(np.linalg.norm(ref_out.U_body[:3]))


def compare(ref_out, prog_out: dict, ref_in) -> dict:
    """``du``, ``p``, ``div``: the walled family's (velocity gap against the
    reference's own change over the chunk, pressures with their means
    removed, the program's own largest divergence times h; no velocity scale
    is taken out of ``div``: the speeds here are O(0.1) SI).  ``dX``: the
    gap of the final marker positions against the reference's own
    displacement of them.  ``dUb``: the gap of the body's six rigid modes,
    the rotation weighted by the body's radius of gyration so that both are
    speeds, against the reference's.  ``rigid``: the largest relative
    departure of a sample of pairwise marker distances in the PROGRAM'S
    output from those of the body as the input file builds it
    (``ref_out.body``, the reference's own lattice): whatever the markers
    have been through since the release, they are that body, moved rigidly.
    ``body``: ``_body_gap``, the one number here that bfloat16 operands in
    the TRANSFERS alone fail."""
    f64 = lambda a: np.asarray(a, dtype=np.float64)  # noqa: E731
    out = ins_walls.compare(ref_out, prog_out, ref_in)
    X, Ub = f64(prog_out["X"]), f64(prog_out["Ub"])[0]
    out["dX"] = float(np.linalg.norm(X - ref_out.X)
                      / np.linalg.norm(ref_out.X - ref_in.X))
    r = ref_out.body - ref_out.body.mean(axis=0)
    gyr = np.sqrt(np.mean(np.sum(r * r, axis=1)))
    weigh = np.array([1.0, 1.0, 1.0, gyr, gyr, gyr])
    out["dUb"] = float(np.linalg.norm(weigh * (Ub - ref_out.U_body))
                       / np.linalg.norm(weigh * ref_out.U_body))
    d_out, d_body = _pair_distances(X, ref_out.body)
    out["rigid"] = float(np.max(np.abs(d_out - d_body) / d_body))
    out["body"] = _body_gap(ref_out, prog_out, Ub)
    return out


def _non_rigid(state):
    """A body that is not the input file's: its markers' heights stretched
    by 3% about the centroid, which no rigid motion undoes."""
    z = state.X[:, 2]
    return state._replace(X=state.X.at[:, 2].set(z + 0.03 * (z - z.mean())))


faults = {"non_rigid": _non_rigid}


def report(method, db: dict) -> str:
    """What resolved: the engine that ran beside the one the resolver names
    for this grid and marker count, and the fallbacks counted."""
    from ibamr_tpu import obs
    from ibamr_tpu.models.engine_resolver import resolve_engine
    from ibamr_tpu.ops.delta import get_kernel

    n_markers = int(method.bodies.body_id.shape[0])
    named = resolve_engine(method.ins.grid.n, n_markers,
                           get_kernel(method.kernel)[0])
    counters = obs.metrics_snapshot()["counters"]
    fallbacks = {k: v for k, v in counters.items()
                 if k.startswith("engine_fallbacks_total") and v}
    ins = db["INSStaggeredHierarchyIntegrator"]
    carried = getattr(method.fast, "refresh", None) is not None
    return (f"rigid body in a walled tank: grid {method.ins.grid.n} walls "
            f"{method.ins.wall_axes} markers {n_markers} engine ran "
            f"{method.engine_name!r} resolver names {named!r} fallbacks "
            f"{fallbacks} carried {carried} dt {ins['dt']} convection "
            f"{method.ins.convective_op_type!r}")
