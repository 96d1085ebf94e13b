"""Plain reference of the fixture ``ins_periodic``: one periodic fluid step
in numpy float64.  It IMPORTS the fluid half of
``perfbench/reference/ib_shell.py`` (``ShellReference.fluid_step``: AB2
convection, Crank-Nicolson diffusion, pressure-increment projection) and
runs it with no body force; nothing of the program."""

from __future__ import annotations

from typing import NamedTuple

import numpy as np

from perfbench.reference import ib_shell


class State(NamedTuple):
    u: tuple
    p: np.ndarray
    n_prev: tuple
    k: int


class FluidReference(ib_shell.ShellReference):
    def __init__(self, db: dict, lowp: str | None = None):
        super().__init__({**db, "Shell": {}}, lowp=lowp)

    def _lattice(self, sh):
        return None, None

    def _step(self, s: State, dt: float) -> State:
        zero = tuple(np.zeros(self.n) for _ in range(3))
        u, p, n_curr = self.fluid_step(s.u, s.p, s.n_prev, s.k, zero, dt)
        return State(u=u, p=p, n_prev=n_curr, k=s.k + 1)


def state_from_arrays(a: dict) -> State:
    f = lambda x: np.asarray(x, dtype=np.float64)  # noqa: E731
    return State(u=(f(a["u0"]), f(a["u1"]), f(a["u2"])), p=f(a["p"]),
                 n_prev=(f(a["n0"]), f(a["n1"]), f(a["n2"])), k=int(a["k"]))
