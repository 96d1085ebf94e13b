"""The run's data, made from ``--seed``: a smooth random solenoidal initial
velocity and a smooth sub-cell displacement of the markers.

The seed never touches a size: radius, aspect, lattice and grid stay as the
input file has them (the packed transfer engine sizes a static chunk count
from the concrete lattice at build time, so a seed that moved the geometry
would compile a new program per seed).  Every seed draws the same number of
modes with the same amplitudes; only directions and phases differ, so the
work is the same from seed to seed.

The mode table is drawn on the host (a few dozen numbers); the fields are
evaluated on the device in one jitted call, in the state's own dtype.
"""

from __future__ import annotations

import math

import numpy as np

# wave vectors on the unit box by shell |k|^2, one of each +-pair
_SHELL = {q: [(i, j, k) for i in range(-2, 3) for j in range(-2, 3)
              for k in range(-2, 3)
              if i * i + j * j + k * k == q and (i, j, k) > (0, 0, 0)]
          for q in (1, 2, 3, 4)}
# every seed draws its modes from the SAME shells, so that every seed's
# velocity decays at the same rate and the chunk's change, which the
# comparison measures against, is alike from seed to seed
SHELLS = (1, 2, 2, 3, 3, 4)
N_MODES = len(SHELLS)


def mode_table(seed: int):
    """(wave vectors, phases, unit amplitude vectors) for the vector
    potential / the displacement: ``2 * N_MODES`` modes, the first half for
    the velocity and the second for the markers."""
    rng = np.random.Generator(np.random.PCG64(int(seed)))
    ks = []
    for _ in range(2):
        for q in sorted(set(SHELLS)):
            picks = rng.choice(len(_SHELL[q]), SHELLS.count(q), replace=False)
            ks += [_SHELL[q][i] for i in picks]
    ks = np.array(ks, float)
    phases = rng.uniform(0.0, 2.0 * math.pi, size=(2 * N_MODES,))
    amps = rng.standard_normal((2 * N_MODES, 3))
    # amplitude across the wave vector, so that |k x a| / |k| is 1 and every
    # mode carries the same velocity
    amps -= ks * (np.sum(amps * ks, axis=1) / np.sum(ks * ks, axis=1))[:, None]
    amps /= np.linalg.norm(amps, axis=1, keepdims=True)
    return ks, phases, amps


def seeded_state(state, grid_n, x_lo, x_up, seed: int,
                 velocity_rms: float, jitter_cells: float):
    """``state`` (the program's IBState, markers on the lattice, fluid at
    rest) with the seeded velocity and marker displacement put in.

    The velocity is the discrete MAC curl of a vector potential sampled on
    the cell edges, so its discrete divergence is nought to rounding; it is
    scaled to ``velocity_rms``.  The markers move by at most
    ``jitter_cells`` grid cells, along a smooth field, so that neighbouring
    springs keep their lengths to a few parts in a thousand."""
    import jax
    import jax.numpy as jnp

    ks, phases, amps = mode_table(seed)
    n = tuple(int(v) for v in grid_n)
    dx = tuple((hi - lo) / m for lo, hi, m in zip(x_lo, x_up, n))
    dtype = state.X.dtype

    # the mode table is an ARGUMENT of the jitted call: were it a constant,
    # every seed would be another program, and XLA would fold the whole
    # field at compile time (measured: 50 s of every run's set-up)
    @jax.jit
    def make(X, ks, phases, amps):
        kv, ph, av = ks[:N_MODES], phases[:N_MODES], amps[:N_MODES]
        kx, px, ax = ks[N_MODES:], phases[N_MODES:], amps[N_MODES:]

        def coords(offsets):
            return [((jnp.arange(n[d], dtype=jnp.float32) + offsets[d])
                     * dx[d] + x_lo[d]).reshape(
                         [-1 if e == d else 1 for e in range(3)])
                    for d in range(3)]

        def potential(c):
            # component c of A lives on the c-edges: cell-centred along c,
            # node-centred along the other two axes
            xs = coords([0.5 if d == c else 0.0 for d in range(3)])
            out = 0.0
            for m in range(N_MODES):
                arg = 2.0 * math.pi * sum(kv[m, d] * xs[d]
                                          for d in range(3)) + ph[m]
                out = out + av[m, c] * jnp.sin(arg) / jnp.linalg.norm(kv[m])
            return out

        A = [potential(c) for c in range(3)]

        def dplus(a, axis):
            return (jnp.roll(a, -1, axis) - a) / dx[axis]

        u = [dplus(A[2], 1) - dplus(A[1], 2),
             dplus(A[0], 2) - dplus(A[2], 0),
             dplus(A[1], 0) - dplus(A[0], 1)]
        rms = jnp.sqrt(sum(jnp.mean(c * c) for c in u))
        u = tuple((c * (velocity_rms / rms)).astype(dtype) for c in u)

        disp = 0.0
        for m in range(N_MODES):
            arg = 2.0 * math.pi * sum(X[:, d] * kx[m, d]
                                      for d in range(3)) + px[m]
            disp = disp + jnp.sin(arg)[:, None] * ax[m].astype(X.dtype)
        disp = disp / jnp.max(jnp.abs(disp))
        h = jnp.asarray(dx, X.dtype)
        return u, (X + jitter_cells * h * disp).astype(dtype)

    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    u, X = make(state.X, f32(ks), f32(phases), f32(amps))
    return state._replace(ins=state.ins._replace(u=u), X=X)
