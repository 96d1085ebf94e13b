"""Plain reference of the wall-bounded fluid-only family (``cavity_*``): one
step of the incompressible Navier-Stokes equations on a MAC grid in a box
with six no-slip walls, one of them a moving lid, in numpy float64 on the
host.

It imports nothing of the program and takes nothing the program made: the
wall ghosts, the stencils and the solves are written here from the
configuration's own input file.  (From the periodic reference it takes
Colella & Woodward's face values ``ppm_face_values``, a function of a 1D
profile that knows no boundary, with its slicing helpers and the state's
type; nothing of its step, which wraps around and solves in Fourier space.
From the shell's reference the bfloat16 rounding and the worker count.)

One step (``INSStaggeredHierarchyIntegrator`` on one wall-bounded level:
pressure-increment projection, AB2 convection, Crank-Nicolson diffusion):

    N*        = 3/2 N(u^n) - 1/2 N(u^{n-1})        (N(u^n) on step 0)
    (rho/dt - mu/2 lap) u* = (rho/dt + mu/2 lap) u^n - rho N* - grad p
    lap(phi0) = div u*;  u^{n+1} = u* - grad(phi0)
    p^{n+1/2} = p^{n-1/2} + (rho/dt) phi0 - (mu/2) lap(phi0)

Storage: component d keeps n values along every axis; along its OWN axis
slot 0 is the lo wall face, where it is 0, and the hi wall face (index n) is
not stored and 0 too.  The wall ghosts, written out:

    cell-centred data along a wall axis (a tangential component; V the
    wall's own velocity, U_lid for u at y = 1, else 0):
        ghost[-1-k] = 2 V_lo - a[k],   ghost[n+k] = 2 V_hi - a[n-1-k]
    the wall-normal component along its own axis (odd about the wall NODE):
        a[-k] = -a[k],   a[n] = 0,   a[n+k] = -a[n-k]
    the pressure: ghost = interior (homogeneous Neumann), so the pressure
        gradient at a wall face is 0 and the normal velocity there stays 0.

N(u)_d = sum_e d/dx_e (u_e u_d) in conservative form at u_d's own faces,
exactly as in the periodic reference but on the ghosts above: the advecting
velocity is the two-point average of u_e onto the flux point, the advected
value the upwinded PPM face value of u_d along e.  The advecting velocity
is 0 on every wall, so no momentum crosses one.

The solves are NOT the program's (dense products with the eigenvectors of
each axis' tridiagonal matrix, found by ``eigh``): here the same matrices
are diagonalised by the sine and cosine transforms whose bases their
eigenvectors are known to be (scipy's FFT-based DST / DCT),

    cell-centred Dirichlet (end rows -3): DST-II, lam_k = -(4/h^2) sin^2((k+1) pi / 2n)
    cell-centred Neumann   (end rows -1): DCT-II, lam_k = -(4/h^2) sin^2(k pi / 2n)
    the n - 1 interior nodes of the pinned normal component:
                                          DST-I,  lam_k = -(4/h^2) sin^2((k+1) pi / 2n)

The moving wall enters the implicit solve as in any textbook: the Dirichlet
ghost's constant part, 2 V / h^2 in the cells next to the lid, goes to the
right-hand side.  The Neumann problem's constant mode is set to 0.

``lowp="bf16"`` computes the same step in the nearest precision below
float32, for the control that ``correct`` has to fail: the operand of every
axis transform is rounded to bfloat16 (what a float32 product on the chip
is without a stated precision).
"""

from __future__ import annotations

import math
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import scipy.fft as sfft

from perfbench.reference.ib_shell import WORKERS, _bf16
from perfbench.reference.ins_periodic import (G, SLAB, State, _cut, _view,
                                              ppm_face_values,
                                              state_from_arrays)

__all__ = ["WallReference", "State", "state_from_arrays", "pad_walls",
           "axis_eigenvalues", "axis_transform"]

# an axis' kind -> (scipy's transform, its type); with norm="ortho" each is
# its own orthogonal eigenvector matrix, and the inverse its transpose
KINDS = {"dirichlet": (sfft.dst, sfft.idst, 2),
         "neumann": (sfft.dct, sfft.idct, 2),
         "pinned": (sfft.dst, sfft.idst, 1)}


def axis_eigenvalues(kind: str, n: int, h: float) -> np.ndarray:
    """Eigenvalues of the axis' second-difference matrix, in the order of
    its transform's output (``n`` is the number of CELLS; ``pinned`` has
    n - 1 unknowns)."""
    k = np.arange(n - 1 if kind == "pinned" else n)
    if kind != "neumann":
        k = k + 1
    return -(4.0 / (h * h)) * np.sin(k * math.pi / (2 * n)) ** 2


def axis_transform(a: np.ndarray, kind: str, axis: int,
                   inverse: bool = False) -> np.ndarray:
    fwd, inv, typ = KINDS[kind]
    return (inv if inverse else fwd)(a, type=typ, axis=axis, norm="ortho",
                                     workers=WORKERS)


def _at(axis: int, sl, ndim: int = 3) -> tuple:
    """The index that is ``sl`` along ``axis`` and everything elsewhere."""
    return tuple(sl if e == axis else slice(None) for e in range(ndim))


def pad_walls(a: np.ndarray, d: int, wall_velocity: dict) -> np.ndarray:
    """Component ``d`` with ``G`` wall ghosts on every side, by the rules
    in this file's docstring.  ``wall_velocity[(d, e, side)]`` is the
    component's own value on the side (0 lo, 1 hi) wall of axis e != d."""
    for e in range(a.ndim):
        n = a.shape[e]
        if e == d:
            lo = -np.flip(a[_at(e, slice(1, G + 1))], e)
            hi = np.concatenate(
                [np.zeros_like(a[_at(e, slice(0, 1))]),
                 -np.flip(a[_at(e, slice(n - G + 1, n))], e)], e)
        else:
            lo = (2.0 * wall_velocity.get((d, e, 0), 0.0)
                  - np.flip(a[_at(e, slice(0, G))], e))
            hi = (2.0 * wall_velocity.get((d, e, 1), 0.0)
                  - np.flip(a[_at(e, slice(n - G, n))], e))
        a = np.concatenate([lo, a, hi], e)
    return a


class WallReference:
    """Built from the parsed input file (``perfbench.inputfile.parse``)."""

    def __init__(self, db: dict, lowp: str | None = None):
        if lowp not in (None, "bf16"):
            raise ValueError(f"unknown lowp {lowp!r}")
        geo, ins = db["CartesianGeometry"], \
            db["INSStaggeredHierarchyIntegrator"]
        self.n = tuple(int(v) for v in geo["n_cells"])
        self.dx = tuple((float(hi) - float(lo)) / n for lo, hi, n
                        in zip(geo["x_lo"], geo["x_up"], self.n))
        self.rho, self.mu = float(ins["rho"]), float(ins["mu"])
        self.dt = float(ins["dt"])
        self.u_lid = float(ins["U_lid"])
        if ins["convective_op_type"].lower() != "ppm":
            raise ValueError("the reference implements PPM convection")
        self.lowp = lowp
        # the lid: u's own value on the hi wall of y
        self.wall_velocity = {(0, 1, 1): self.u_lid}
        # per solve, each axis' kind and the eigenvalues' sum over the
        # unknowns: the three velocity components, then the pressure
        self.kinds = [tuple("pinned" if e == d else "dirichlet"
                            for e in range(3)) for d in range(3)]
        self.kinds.append(("neumann",) * 3)
        self.lam = []
        for kinds in self.kinds:
            lam = 0.0
            for e, kind in enumerate(kinds):
                shape = [1, 1, 1]
                shape[e] = -1
                lam = lam + axis_eigenvalues(
                    kind, self.n[e], self.dx[e]).reshape(shape)
            self.lam.append(lam)
        self._pool = ThreadPoolExecutor(WORKERS)
        self.seconds = {"convect": 0.0, "transforms": 0.0, "total": 0.0}

    def _timed(self, key, fn, *a):
        t0 = time.perf_counter()
        out = fn(*a)
        self.seconds[key] += time.perf_counter() - t0
        return out

    def close(self):
        self._pool.shutdown()

    def _slabs(self, fn, axis: int = 0):
        n = self.n[axis]
        rows = max(1, SLAB * n // math.prod(self.n))
        list(self._pool.map(lambda lo: fn(lo, min(lo + rows, n)),
                            range(0, n, rows)))

    # -- the solves ----------------------------------------------------------
    def _transform(self, a, kinds, inverse=False):
        def run(a):
            for e, kind in enumerate(kinds):
                if self.lowp == "bf16":
                    a = _bf16(a)
                a = axis_transform(a, kind, e, inverse)
            return a
        return self._timed("transforms", run, a)

    def helmholtz(self, rhs: np.ndarray, which: int, alpha: float,
                  beta: float) -> np.ndarray:
        """(alpha + beta lap) q = rhs with homogeneous walls; ``which`` is
        the velocity component, or 3 for the pressure's Neumann problem,
        whose constant mode comes back 0.  A pinned component is solved on
        its interior faces and comes back with slot 0 at 0."""
        kinds = self.kinds[which]
        pinned = [e for e, kind in enumerate(kinds) if kind == "pinned"]
        for e in pinned:
            rhs = rhs[_at(e, slice(1, None))]
        qh = self._transform(rhs, kinds)
        den = alpha + beta * self.lam[which]
        qh /= np.where(den == 0.0, np.inf, den)     # the constant mode: 0
        q = self._transform(qh, kinds, inverse=True)
        for e in pinned:
            q = np.concatenate([np.zeros_like(q[_at(e, slice(0, 1))]), q], e)
        return q

    # -- the convective operator ---------------------------------------------
    def convective_rate(self, up) -> list:
        """N(u) from the three ghost-padded components."""
        n, dx = self.n, self.dx
        out = [np.zeros(n) for _ in range(3)]

        def term(d, e):
            """d/dx_e (u_e u_d) added to ``out[d]``.  The fluxes sit at the
            lower e-faces j = 0 .. n of u_d's own cells (for e = d these are
            the cell centres between u_d's faces j - 1 and j): u_e averaged
            along d onto them, times u_d's PPM value there.  On a wall u_e's
            average is the wall's own normal velocity, 0."""
            s_ax = 1 if e == 0 else 0       # slabs across the stencil's axis

            def slab(lo, hi):
                rng = [(0, m) for m in n]
                rng[s_ax] = (lo, hi)
                faces, cells = list(rng), list(rng)
                faces[e] = (0, n[e] + 1)
                cells[e] = (-G, n[e] + G)
                adv = 0.5 * (_view(up[e], faces, d, -1) + _view(up[e], faces))
                flux = adv * ppm_face_values(_view(up[d], cells), adv, e)
                out[d][tuple(slice(lo, hi) for lo, hi in rng)] += (
                    _cut(flux, e, 1, 0) - _cut(flux, e, 0, -1)) / dx[e]
            self._slabs(slab, s_ax)

        for d in range(3):
            for e in range(3):
                term(d, e)
            out[d][_at(d, 0)] = 0.0     # the wall face does not move
        return out

    # -- the step --------------------------------------------------------------
    def step(self, s: State, dt: float) -> State:
        return self._timed("total", self._step, s, dt)

    def _step(self, s: State, dt: float) -> State:
        n, dx, rho, mu = self.n, self.dx, self.rho, self.mu
        alpha, beta = rho / dt, -0.5 * mu
        up = list(self._pool.map(
            lambda d: pad_walls(s.u[d], d, self.wall_velocity), range(3)))
        pp = np.pad(s.p, G, mode="edge")
        n_curr = self._timed("convect", self.convective_rate, up)
        c1, c2 = (1.0, 0.0) if s.k == 0 else (1.5, -0.5)
        rhs = [np.empty(n) for _ in range(3)]

        def build(lo, hi):
            rows = [(lo, hi), (0, n[1]), (0, n[2])]
            for d in range(3):
                ud = _view(up[d], rows)
                lap = sum((_view(up[d], rows, e, 1) - 2.0 * ud
                           + _view(up[d], rows, e, -1)) / dx[e] ** 2
                          for e in range(3))
                gp = (_view(pp, rows) - _view(pp, rows, d, -1)) / dx[d]
                rhs[d][lo:hi] = ((rho / dt) * ud + 0.5 * mu * lap
                                 - rho * (c1 * n_curr[d][lo:hi]
                                          + c2 * s.n_prev[d][lo:hi]) - gp)

        self._slabs(build)
        del up, pp
        # the moving walls' part of the implicit Laplacian, to the right
        for (d, e, side), v in self.wall_velocity.items():
            rhs[d][_at(e, -1 if side else 0)] -= beta * 2.0 * v / dx[e] ** 2
        u_star = [self.helmholtz(rhs[d], d, alpha, beta) for d in range(3)]
        del rhs
        # projection: the hi wall faces carry 0 like the lo ones, so the
        # divergence needs no other ghost; phi's Neumann ghost (= interior)
        # gives no gradient on a wall face and closes the 7-point
        # Laplacian of the pressure increment
        usp = list(self._pool.map(lambda c: np.pad(c, G), u_star))
        div = np.empty(n)

        def divergence(lo, hi):
            rows = [(lo, hi), (0, n[1]), (0, n[2])]
            div[lo:hi] = sum((_view(usp[d], rows, d, 1) - _view(usp[d], rows))
                             / dx[d] for d in range(3))

        self._slabs(divergence)
        del usp
        phi = self.helmholtz(div, 3, 0.0, 1.0)
        php = np.pad(phi, G, mode="edge")
        u_new, p_new = [np.empty(n) for _ in range(3)], np.empty(n)

        def project(lo, hi):
            rows = [(lo, hi), (0, n[1]), (0, n[2])]
            ph, lap = _view(php, rows), 0.0
            for d in range(3):
                below = _view(php, rows, d, -1)
                u_new[d][lo:hi] = u_star[d][lo:hi] - (ph - below) / dx[d]
                lap = lap + (_view(php, rows, d, 1) - 2.0 * ph
                             + below) / dx[d] ** 2
            p_new[lo:hi] = s.p[lo:hi] + alpha * ph + beta * lap

        self._slabs(project)
        return State(u=tuple(u_new), p=p_new, n_prev=tuple(n_curr), k=s.k + 1)

    def advance(self, s: State, steps: int, dt: float | None = None) -> State:
        dt = self.dt if dt is None else dt
        for _ in range(steps):
            s = self.step(s, dt)
        return s
