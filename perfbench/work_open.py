"""Work the open-boundary fluid solve needs, computed from the problem (as
in ``work.py``: never from what the implementation happens to move): the
least HBM bytes of the coupled saddle solve by FGMRES(m) with the
projection preconditioner (red-black velocity sweeps, a Schur proxy with one
multigrid V-cycle of the pressure Poisson problem)."""

from __future__ import annotations

import math

# the preconditioner's red-black velocity sweeps (solvers/stokes.
# StaggeredStokesSolver's nu_sweeps); the pressure V-cycle's sweeps
# (solvers/multigrid.PoissonMultigrid's): before and after the coarse
# correction on every level but the coarsest, and on the coarsest; and the
# smallest extent a level keeps
NU_SWEEPS, NU_PRE, NU_POST, NU_COARSE, MIN_CELLS = 4, 2, 2, 40, 4


def mg_levels(n, min_cells: int = MIN_CELLS) -> int:
    """Levels of the pressure hierarchy on ``n`` cells: every extent is
    halved while all are even and half of each is at least ``min_cells``."""
    n, levels = [int(v) for v in n], 1
    while not any(v % 2 or v // 2 < min_cells for v in n):
        n, levels = [v // 2 for v in n], levels + 1
    return levels


def restart_bytes(n, m: int, levels: int, nu_sweeps: int = NU_SWEEPS,
                  itemsize: int = 4) -> int:
    """Least bytes of ONE restart cycle of FGMRES(m) on the face-complete
    MAC layout of ``n`` cells (a vector: three velocity components, each
    one face longer on its own axis, and the pressure).

    Each operand of a stencil is read once and each result written once;
    what the operator or a smoother needs of its neighbours is counted with
    the operand.  Per Arnoldi iteration j (j + 1 live basis rows):

    - the operator: its input read, its output written (2 vectors);
    - the preconditioner: ``nu_sweeps`` sweeps of two colours over the
      velocity, each colour reading the iterate and the right-hand side and
      writing the iterate; the divergence of the result added to the
      pressure residual; one V-cycle (per level but the coarsest,
      ``NU_PRE + NU_POST`` sweeps of two colours and the residual, each
      three fields of the level, the restriction and the prolongation;
      ``NU_COARSE`` sweeps on the coarsest); the Schur proxy's combination;
    - classical Gram-Schmidt twice: each round reads the j + 1 LIVE rows
      for their dot products and again for the update, with the new vector
      read twice and written once; the normalisation reads it and writes
      the next row (the basis rows not yet written are not counted).

    Per cycle besides: the residual of the start (the operator, the
    right-hand side read, the first row written) and of the end, and the
    update of the iterate from the m preconditioned rows."""
    n = [int(v) for v in n]
    cells = math.prod(n)
    vel = sum(math.prod(v + (1 if e == d else 0) for e, v in enumerate(n))
              for d in range(3))
    vec = vel + cells
    smooth = nu_sweeps * 2 * 3 * vel + (vel + 2 * cells)
    vcycle = 0.0
    for lv in range(levels):
        c = cells / 8 ** lv
        if lv == levels - 1:
            vcycle += NU_COARSE * 2 * 3 * c
        else:
            vcycle += (NU_PRE + NU_POST) * 2 * 3 * c + 3 * c \
                + (c + c / 8) + (c / 8 + 2 * c)
    precond = smooth + vcycle + 3 * cells
    per_iter = 2 * vec + precond
    arnoldi = sum(2 * (2 * (j + 1) + 3) + 2 for j in range(m)) * vec
    cycle = m * per_iter + arnoldi + (3 + 2) * vec + (m + 2) * vec \
        + 3 * vec
    return int(cycle * itemsize)
