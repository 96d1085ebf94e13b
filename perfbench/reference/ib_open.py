"""Plain reference of the open-boundary IB family (``dfg3d_*``): one step of
a target-point cylinder in an inflow/outflow duct, in numpy float64 on the
host.

It imports nothing of the program and takes nothing the program made: the
marker lattice, the inflow profile, the boundary ghosts, the stencils and the
saddle solve are written here from the configuration's own input file.  (From
the periodic reference it takes Colella & Woodward's face values
``ppm_face_values``, a function of a 1D profile that knows no boundary; from
the shell's reference the IB_4 kernel, the bfloat16 rounding and the worker
count.)

Array axes are (y, z, x): axis 2 is the streamwise one, axis 1 the
cylinder's.  Storage is FACE-COMPLETE: component d keeps n + 1 faces along
its own axis, both boundary faces included.  Prescribed faces (the normal
faces of the four walls, 0, and the inflow faces of x = 0, the source's
profile 16 U_m y z (H - y)(H - z) / H^4 at the face centres) are not
unknowns; the outflow faces of x = 2.5 are.

One step (``IBOpenIntegrator`` over ``INSOpenIntegrator``: explicit midpoint
IB, explicit stabilized-PPM convection, backward-Euler viscosity, one coupled
velocity-pressure solve):

    U^n       = J(X^n) u^n                 (IB_4, lower-face layout, wrap)
    X^{n+1/2} = X^n + dt/2 U^n
    F         = -kappa (X^{n+1/2} - X_target) - eta U^n;  f = S(X^{n+1/2}) F
    (rho/dt) u - mu lap u + grad p = (rho/dt) u^n - rho N(u^n) + f
    div u = 0                               (u = data on prescribed faces)
    U^{n+1/2} = J(X^{n+1/2}) (u^n + u^{n+1})/2;  X^{n+1} = X^n + dt U^{n+1/2}

N(u)_d = sum_e a_e d(u_d)/dx_e in advective form: a_e is u_e at u_d's faces
(u_d itself for e = d; for e != d the cell average of u_e, then the mean of
the two cells on either side of the face, the edge cell at a boundary
face).  Along e the derivative is a_e (q_hi - q_lo) / dx_e with q the PPM
face values upwinded by the mean of a_e at the two points either side of the
face, blended into first-order upwind a_e (u_d - u_d[-1]) / dx_e (or the
forward difference where a_e <= 0) by the weight chi = max(1 - i/band,
1 - (n_e - 1 - i)/band, 0) of the i-th point from either end.  The ghosts of
the PPM stencil along e, three a side:

    e != d (u_d cell-centred along e): a prescribed side reflects oddly
        about its datum (0 here: 2 v - u[k]); an open side repeats the
        last value
    e == d (the boundary face is a stored value): a prescribed side
        reflects oddly about it (2 u[0] - u[k]); an open side repeats it

The solve's own Laplacian takes homogeneous ghosts (the data are in the
right-hand side): along a component's own axis the boundary face repeats;
across a prescribed side the ghost is minus the value next to it, across an
open one plus.  The pressure gradient at an outflow face takes p = 0 on the
face (ghost -p), and none acts on a prescribed face.

The solve is NOT the program's (restarted flexible GMRES on the coupled
system, red-black sweeps and a multigrid Schur proxy): here the velocity is
eliminated, u = A^{-1}(r - G p) with A = rho/dt - mu lap on the unknown faces,
which the transforms whose bases are its eigenvectors diagonalise exactly,
axis by axis (with h the axis' width, lam the second difference's
eigenvalues):

    across two walls, cell-centred (ghost -u): DST-II,
        lam_k = -(4/h^2) sin^2((k+1) pi / 2n)
    along x across the inflow and the outflow (ghost -u lo, +u hi): DST-IV,
        lam_k = -(4/h^2) sin^2((k+1/2) pi / 2n)
    along the own axis between two walls (the n - 1 interior faces): DST-I,
        lam_k = -(4/h^2) sin^2((k+1) pi / 2n)
    u_x along x (faces 1 .. n: 0 beyond the inflow face, the outflow face
        repeating itself): no fast transform has this basis; the dense
        eigenvectors of its n x n second difference (numpy's eigh)

and the pressure's Schur complement D A^{-1} G p = D A^{-1} r + D u_b is
solved by restarted GMRES preconditioned by (rho/dt) (D G)^{-1}, the exact
pressure Poisson inverse by cosine transforms (DCT-II along the walled axes,
DCT-IV along x: Neumann at the inflow, p = 0 at the outflow face), to a
residual far below the program's tolerance.

``lowp="bf16"`` computes the same step in the nearest precision below
float32, for the control that ``correct`` has to fail: the state it starts
from, what each stage gives (the interpolated velocities, the markers'
positions and forces, the spread force, the convective rate, the right-hand
side, the solve's velocity and pressure) and the operands of every inner
product and basis combination of the GMRES solve are rounded to bfloat16.
(The products alone would not do: a restarted Krylov solve recomputes its
residual at every restart, and refines through bfloat16 products to any
tolerance.)
"""

from __future__ import annotations

import math
import time
from concurrent.futures import ThreadPoolExecutor
from typing import NamedTuple

import numpy as np
import scipy.fft as sfft

from perfbench.reference.ib_shell import WORKERS, _bf16, _phi_ib4
from perfbench.reference.ins_periodic import SLAB, ppm_face_values

__all__ = ["OpenReference", "State", "state_from_arrays", "targets",
           "inflow_profile"]

FLOW, SPAN = 2, 1       # array axes (y, z, x)
G = 3                   # PPM ghosts a side
# the Schur complement's solve: far below the program's float32 tolerance
GMRES_RTOL, GMRES_M, GMRES_RESTARTS = 1e-12, 30, 10
# scipy's sine transforms by their type (each its own inverse's transpose
# with norm="ortho")
SINES = {"pinned": 1, "dirichlet": 2, "dir_open": 4}


class State(NamedTuple):
    u: tuple            # three face-complete components
    p: np.ndarray       # cell-centred pressure
    X: np.ndarray       # (N, 3) markers
    U: np.ndarray       # (N, 3) marker velocity of the last step
    F: np.ndarray       # (3,) net force the markers spread in the last step
    dx: tuple = None    # the cell widths (a state the reference stepped)


def state_from_arrays(a: dict) -> State:
    f64 = lambda v: np.asarray(v, dtype=np.float64)  # noqa: E731
    return State(u=tuple(f64(a[f"u{d}"]) for d in range(3)), p=f64(a["p"]),
                 X=f64(a["X"]), U=f64(a["U"]), F=f64(a["F"]))


def _at(axis: int, sl, ndim: int = 3) -> tuple:
    return tuple(sl if e == axis else slice(None) for e in range(ndim))


def _take(a, axis: int, lo: int, hi: int):
    return a[_at(axis, slice(lo, hi), a.ndim)]


def targets(db: dict) -> np.ndarray:
    """The cylinder's target points: ``round(pi D / s)`` around it and
    ``round(L / s) + 1`` along it over L = the span less the clearance at
    both walls, both at the spacing s = ``marker_spacing_cells`` times the
    smallest cell width; rows (y, z, x), around-major."""
    geo, ib, cyl = db["CartesianGeometry"], db["IBMethod"], db["Cylinder"]
    n = [int(v) for v in geo["n_cells"]]
    lo = [float(v) for v in geo["x_lo"]]
    hi = [float(v) for v in geo["x_up"]]
    dx = [(b - a) / m for a, b, m in zip(lo, hi, n)]
    s = float(ib["marker_spacing_cells"]) * min(dx)
    clear = float(ib["clearance_cells"]) * dx[SPAN]
    z0, z1 = lo[SPAN] + clear, hi[SPAN] - clear
    D = float(cyl["diameter"])
    cy, cx = (float(v) for v in cyl["center"])
    n_around = max(3, int(round(math.pi * D / s)))
    n_along = max(2, int(round((z1 - z0) / s)) + 1)
    out = np.empty((n_around, n_along, 3))
    for i in range(n_around):
        th = 2.0 * math.pi * i / n_around
        out[i, :, 0] = cy + 0.5 * D * math.sin(th)
        out[i, :, 1] = np.linspace(z0, z1, n_along)
        out[i, :, 2] = cx + 0.5 * D * math.cos(th)
    return out.reshape(-1, 3)


def inflow_profile(db: dict) -> np.ndarray:
    """16 U_m y z (H_y - y)(H_z - z) / (H_y H_z)^2 at the centres of the
    inflow faces, (n_y, n_z)."""
    geo = db["CartesianGeometry"]
    n = [int(v) for v in geo["n_cells"]]
    H = [float(geo["x_up"][e]) - float(geo["x_lo"][e]) for e in range(3)]
    y = (np.arange(n[0]) + 0.5) * H[0] / n[0]
    z = (np.arange(n[1]) + 0.5) * H[1] / n[1]
    u_m = float(db["Inflow"]["U_m"])
    return 16.0 * u_m * np.outer(y * (H[0] - y), z * (H[1] - z)) \
        / (H[0] * H[1]) ** 2


class OpenReference:
    """Built from the parsed input file (``perfbench.inputfile.parse``)."""

    def __init__(self, db: dict, lowp: str | None = None):
        if lowp not in (None, "bf16"):
            raise ValueError(f"unknown lowp {lowp!r}")
        geo = db["CartesianGeometry"]
        ins = db["INSStaggeredHierarchyIntegrator"]
        ib = db["IBMethod"]
        self.n = tuple(int(v) for v in geo["n_cells"])
        self.x_lo = tuple(float(v) for v in geo["x_lo"])
        self.dx = tuple((float(hi) - float(lo)) / n for lo, hi, n
                        in zip(geo["x_lo"], geo["x_up"], self.n))
        self.rho, self.mu = float(ins["rho"]), float(ins["mu"])
        self.dt = float(ins["dt"])
        if ins["convective_op_type"].lower() != "stabilized_ppm":
            raise ValueError("the reference implements stabilized PPM")
        if ib["delta_fcn"] != "IB_4":
            raise ValueError("the reference implements the IB_4 kernel")
        self.band = max(int(ins["stab_band"]), 1)
        self.kappa, self.eta = float(ib["kappa"]), float(ib["eta"])
        self.lowp = lowp
        self.X_target = targets(db)
        self.profile = inflow_profile(db)
        # per component: is each side of each axis prescribed (a wall, or
        # the inflow), and which of its own faces are unknowns
        self.prescribed = [(True, True), (True, True), (True, False)]
        self.unknown = []
        for d in range(3):
            m = np.ones(self._shape(d), bool)
            m[_at(d, 0)] = False
            if self.prescribed[d][1]:
                m[_at(d, -1)] = False
            self.unknown.append(m)
        # the velocity Helmholtz operator, per component: each axis' kind,
        # the slice of unknown faces along its own axis, and the sum of the
        # second differences' eigenvalues
        self.vel_kinds, self.vel_rows, self.vel_lam = [], [], []
        for d in range(3):
            kinds, lam = [], 0.0
            for e in range(3):
                if e != d:
                    kind = "dirichlet" if self.prescribed[e][1] else "dir_open"
                else:
                    kind = "pinned" if self.prescribed[e][1] else "open_face"
                kinds.append(kind)
                shape = [1, 1, 1]
                shape[e] = -1
                lam = lam + self._axis_lam(kind, e).reshape(shape)
            self.vel_kinds.append(kinds)
            self.vel_rows.append(slice(1, self.n[d] + (
                0 if self.prescribed[d][1] else 1)))
            self.vel_lam.append(lam)
        lam = 0.0
        for e in range(3):
            k = np.arange(self.n[e])
            shift = 0.5 if e == FLOW else 0.0
            le = -(4.0 / self.dx[e] ** 2) * np.sin(
                (k + shift) * math.pi / (2 * self.n[e])) ** 2
            shape = [1, 1, 1]
            shape[e] = -1
            lam = lam + le.reshape(shape)
        self.poisson_lam = lam          # never 0: the outflow pins p
        self._pool = ThreadPoolExecutor(WORKERS)
        self.seconds = {"convect": 0.0, "transfers": 0.0, "solve": 0.0,
                        "total": 0.0}
        # the saddle residuals of the solves, over the right-hand side
        self.residuals = []

    def _shape(self, d: int) -> tuple:
        return tuple(m + (1 if e == d else 0) for e, m in enumerate(self.n))

    def _timed(self, key, fn, *a):
        t0 = time.perf_counter()
        out = fn(*a)
        self.seconds[key] += time.perf_counter() - t0
        return out

    def close(self):
        self._pool.shutdown()

    def _slabs(self, fn, shape, axis: int = 0):
        """``fn(lo, hi)`` over slabs of rows of ``axis``, on the pool."""
        n = shape[axis]
        rows = max(1, SLAB * n // math.prod(shape))
        list(self._pool.map(lambda lo: fn(lo, min(lo + rows, n)),
                            range(0, n, rows)))

    def _round(self, a):
        return _bf16(a) if self.lowp == "bf16" else a

    def _dot(self, a, b) -> float:
        return float(np.vdot(self._round(a), self._round(b)))

    # -- the convective operator -------------------------------------------
    def _ghosts(self, c, d: int, e: int):
        """``c`` (component d) with ``G`` data ghosts a side along e."""
        n_e = c.shape[e]
        lo_p, hi_p = self.prescribed[e]
        if e != d:
            lo = -np.flip(_take(c, e, 0, G), e) if lo_p \
                else np.repeat(_take(c, e, 0, 1), G, e)
            hi = -np.flip(_take(c, e, n_e - G, n_e), e) if hi_p \
                else np.repeat(_take(c, e, n_e - 1, n_e), G, e)
        else:
            first, last = _take(c, e, 0, 1), _take(c, e, n_e - 1, n_e)
            lo = 2.0 * first - np.flip(_take(c, e, 1, G + 1), e) if lo_p \
                else np.repeat(first, G, e)
            hi = 2.0 * last - np.flip(_take(c, e, n_e - 1 - G, n_e - 1), e) \
                if hi_p else np.repeat(last, G, e)
        return np.concatenate([lo, c, hi], e)

    def _band(self, n_e: int, e: int) -> np.ndarray:
        i = np.arange(n_e, dtype=np.float64)
        chi = np.maximum(np.clip(1.0 - i / self.band, 0.0, 1.0),
                         np.clip(1.0 - (n_e - 1 - i) / self.band, 0.0, 1.0))
        shape = [1, 1, 1]
        shape[e] = n_e
        return chi.reshape(shape)

    def convective_rate(self, u) -> list:
        dx = self.dx
        # cell averages, then each at the other components' faces
        uc = [0.5 * (_take(u[e], e, 0, self.n[e])
                     + _take(u[e], e, 1, self.n[e] + 1)) for e in range(3)]
        adv = {}
        for d in range(3):
            for e in range(3):
                if e != d:
                    ext = np.concatenate([_take(uc[e], d, 0, 1), uc[e],
                                          _take(uc[e], d, self.n[d] - 1,
                                                self.n[d])], d)
                    adv[d, e] = 0.5 * (_take(ext, d, 0, self.n[d] + 1)
                                       + _take(ext, d, 1, self.n[d] + 2))
                else:
                    adv[d, e] = u[d]
        out = [np.zeros(self._shape(d)) for d in range(3)]

        def term(d, e):
            c, a = u[d], adv[d, e]
            n_e = c.shape[e]
            s_ax = 1 if e == 0 else 0
            chi = self._band(n_e, e)

            def slab(lo, hi):
                rows = _at(s_ax, slice(lo, hi))
                cs, as_ = c[rows], a[rows]
                ext = np.concatenate([_take(as_, e, 0, 1), as_,
                                      _take(as_, e, n_e - 1, n_e)], e)
                mid = 0.5 * (_take(ext, e, 0, n_e + 1)
                             + _take(ext, e, 1, n_e + 2))
                gh = self._ghosts(cs, d, e)
                q = ppm_face_values(gh, mid, e)
                ppm = as_ * (_take(q, e, 1, n_e + 1) - _take(q, e, 0, n_e)) \
                    / dx[e]
                cm, cp = _take(gh, e, G - 1, G - 1 + n_e), \
                    _take(gh, e, G + 1, G + 1 + n_e)
                up = np.where(as_ > 0.0, as_ * (cs - cm) / dx[e],
                              as_ * (cp - cs) / dx[e])
                out[d][rows] += chi * up + (1.0 - chi) * ppm
            self._slabs(slab, c.shape, s_ax)

        for d in range(3):
            for e in range(3):
                term(d, e)
        return out

    # -- the solve's operators ------------------------------------------------
    def _lap(self, c, d: int):
        """The homogeneous-ghost Laplacian of component d (all faces)."""
        out = np.empty_like(c)
        n0 = c.shape[0]
        inv = [1.0 / h ** 2 for h in self.dx]

        def side_ghost(edge, e, side):
            if e == d:
                return edge
            return -edge if self.prescribed[e][side] else edge

        def slab(lo, hi):
            s = c[lo:hi]
            acc = -2.0 * sum(inv) * s
            # along axis 0, from the neighbouring rows
            below = c[lo - 1:hi - 1] if lo > 0 else np.concatenate(
                [side_ghost(c[0:1], 0, 0), c[0:hi - 1]], 0)
            above = c[lo + 1:hi + 1] if hi < n0 else np.concatenate(
                [c[lo + 1:n0], side_ghost(c[n0 - 1:n0], 0, 1)], 0)
            acc += (below + above) * inv[0]
            for e in (1, 2):
                n_e = s.shape[e]
                first, last = _take(s, e, 0, 1), _take(s, e, n_e - 1, n_e)
                m = np.concatenate([side_ghost(first, e, 0),
                                    _take(s, e, 0, n_e - 1)], e)
                p = np.concatenate([_take(s, e, 1, n_e),
                                    side_ghost(last, e, 1)], e)
                acc += (m + p) * inv[e]
            out[lo:hi] = acc
        self._slabs(slab, c.shape)
        return out

    def _grad(self, p, d: int):
        """grad p on component d's faces; 0 on its prescribed faces."""
        h = self.dx[d]
        n_d = self.n[d]
        inner = (_take(p, d, 1, n_d) - _take(p, d, 0, n_d - 1)) / h
        zero = np.zeros_like(_take(p, d, 0, 1))
        last = zero if self.prescribed[d][1] \
            else -2.0 * _take(p, d, n_d - 1, n_d) / h
        return np.concatenate([zero, inner, last], d)

    def _div(self, u):
        return sum((_take(u[d], d, 1, self.n[d] + 1)
                    - _take(u[d], d, 0, self.n[d])) / self.dx[d]
                   for d in range(3))

    def _axis_lam(self, kind: str, e: int) -> np.ndarray:
        """Eigenvalues of the second difference along axis ``e`` of its
        ``kind`` (this file's docstring), in its transform's order."""
        n, h = self.n[e], self.dx[e]
        if kind == "open_face":
            T = (np.diag(np.full(n, -2.0)) + np.diag(np.ones(n - 1), 1)
                 + np.diag(np.ones(n - 1), -1))
            T[-1, -1] = -1.0
            lam, self.open_basis = np.linalg.eigh(T / h ** 2)
            return lam
        k = np.arange(n - 1 if kind == "pinned" else n)
        shift = 0.5 if kind == "dir_open" else 1.0
        return -(4.0 / h ** 2) * np.sin((k + shift) * math.pi / (2 * n)) ** 2

    def _axis_transform(self, a, kind: str, e: int, inverse: bool):
        if kind == "open_face":
            V = self.open_basis
            return np.moveaxis(np.moveaxis(a, e, -1) @ (V if not inverse
                                                         else V.T), -1, e)
        fn = sfft.idst if inverse else sfft.dst
        return fn(a, type=SINES[kind], axis=e, norm="ortho", workers=WORKERS)

    def _helm_solve(self, b, d: int, alpha: float):
        """A x = b on component d's unknown faces (b is 0 elsewhere), by its
        eigenvector transforms."""
        rows = _at(d, self.vel_rows[d])
        x = b[rows]
        for e, kind in enumerate(self.vel_kinds[d]):
            x = self._axis_transform(x, kind, e, False)
        x = x / (alpha - self.mu * self.vel_lam[d])
        for e, kind in enumerate(self.vel_kinds[d]):
            x = self._axis_transform(x, kind, e, True)
        out = np.zeros_like(b)
        out[rows] = x
        return out

    def _solve_velocity(self, r, alpha: float) -> list:
        return list(self._pool.map(
            lambda d: self._helm_solve(r[d], d, alpha), range(3)))

    def _poisson(self, s):
        """(D G)^{-1} s by the cosine transforms."""
        def run(a, inverse):
            for e in range(3):
                typ = 4 if e == FLOW else 2
                fn = sfft.idct if inverse and typ == 2 else sfft.dct
                a = fn(a, type=typ, axis=e, norm="ortho", workers=WORKERS)
            return a
        return run(run(s, False) / self.poisson_lam, True)

    def _gmres(self, apply, precond, b):
        """Right-preconditioned restarted GMRES (modified Gram-Schmidt)."""
        x = np.zeros_like(b)
        bn = math.sqrt(self._dot(b, b))
        for _ in range(GMRES_RESTARTS):
            r = b - apply(x)
            beta = math.sqrt(self._dot(r, r))
            if beta <= GMRES_RTOL * bn:
                break
            V, Z = [r / beta], []
            H = np.zeros((GMRES_M + 1, GMRES_M))
            for j in range(GMRES_M):
                Z.append(precond(V[j]))
                w = apply(Z[j])
                for i in range(j + 1):
                    H[i, j] = self._dot(V[i], w)
                    w -= H[i, j] * self._round(V[i])
                H[j + 1, j] = math.sqrt(self._dot(w, w))
                V.append(w / H[j + 1, j])
                e1 = np.zeros(j + 2)
                e1[0] = beta
                y = np.linalg.lstsq(H[:j + 2, :j + 1], e1, rcond=None)[0]
                if np.linalg.norm(H[:j + 2, :j + 1] @ y - e1) \
                        <= GMRES_RTOL * bn:
                    break
            for i, yi in enumerate(y):
                x += yi * self._round(Z[i])
        return x

    def saddle_solve(self, rhs, alpha: float):
        """The coupled solve: ``rhs`` is the momentum right-hand side on
        every face; the prescribed faces take their data."""
        ub = [np.zeros(self._shape(d)) for d in range(3)]
        ub[FLOW][_at(FLOW, 0)] = self.profile
        # the data's part of the viscous operator, to the right
        r = [np.where(self.unknown[d],
                      rhs[d] + self.mu * self._lap(ub[d], d), 0.0)
             for d in range(3)]

        def schur(q):
            v = self._solve_velocity(
                [np.where(self.unknown[d], self._grad(q, d), 0.0)
                 for d in range(3)], alpha)
            return self._div(v)

        b = self._div(self._solve_velocity(r, alpha)) + self._div(ub)
        p = self._gmres(schur, lambda s: alpha * self._poisson(s), b)
        uh = self._solve_velocity(
            [r[d] - np.where(self.unknown[d], self._grad(p, d), 0.0)
             for d in range(3)], alpha)
        u = [ub[d] + uh[d] for d in range(3)]
        # the program's residual norm: momentum on the unknown faces,
        # identity on the prescribed ones, continuity in every cell
        res = sum(float(np.sum(np.where(
            self.unknown[d], alpha * u[d] - self.mu * self._lap(u[d], d)
            + self._grad(p, d) - rhs[d], 0.0) ** 2)) for d in range(3))
        res += float(np.sum(self._div(u) ** 2))
        big = sum(float(np.sum(np.where(self.unknown[d], rhs[d], ub[d]) ** 2))
                  for d in range(3))
        self.residuals.append(math.sqrt(res / big))
        return u, p

    # -- transfers -------------------------------------------------------------
    def _stencil(self, X, comp: int):
        """Linear indices (N, 64) of component ``comp``'s lower faces around
        each marker on the periodic grid, and the IB_4 weights."""
        idx, wts = [], []
        for d in range(3):
            off = 0.0 if d == comp else 0.5
            xi = (X[:, d] - self.x_lo[d]) / self.dx[d] - off
            j = (np.floor(xi - 2.0).astype(np.int64) + 1)[:, None] \
                + np.arange(4)[None, :]
            wts.append(_phi_ib4(xi[:, None] - j))
            idx.append(np.mod(j, self.n[d]))
        n = self.n
        lin = ((idx[0][:, :, None, None] * n[1] + idx[1][:, None, :, None])
               * n[2] + idx[2][:, None, None, :]).reshape(len(X), 64)
        w = (wts[0][:, :, None, None] * wts[1][:, None, :, None]
             * wts[2][:, None, None, :]).reshape(len(X), 64)
        return lin, w

    def _interp(self, u_low, X):
        def one(c):
            lin, w = self._stencil(X, c)
            return np.sum(u_low[c].reshape(-1)[lin] * w, axis=1)
        return np.stack(list(self._pool.map(one, range(3))), axis=1)

    def _spread(self, F, X):
        """Onto the face-complete layout: the lower faces of the periodic
        grid, and the upper boundary face as the image of face 0 (0 under
        the clearance contract)."""
        inv_vol = 1.0 / math.prod(self.dx)

        def one(c):
            lin, w = self._stencil(X, c)
            f = np.bincount(lin.reshape(-1),
                            weights=((F[:, c] * inv_vol)[:, None] * w
                                     ).reshape(-1),
                            minlength=math.prod(self.n)).reshape(self.n)
            return np.concatenate([f, _take(f, c, 0, 1)], c)
        return list(self._pool.map(one, range(3)))

    def _lower(self, u):
        return [_take(u[d], d, 0, self.n[d]) for d in range(3)]

    # -- the step --------------------------------------------------------------
    def step(self, s: State, dt: float) -> State:
        return self._timed("total", self._step, s, dt)

    def _step(self, s: State, dt: float) -> State:
        alpha = self.rho / dt
        r = self._round_all
        u0, X = r(s.u), self._round(s.X)
        low = self._lower(u0)
        U_n = self._round(self._timed("transfers", self._interp, low, X))
        X_half = self._round(X + 0.5 * dt * U_n)
        F = self._round(-self.kappa * (X_half - self.X_target)
                        - self.eta * U_n)
        f = r(self._timed("transfers", self._spread, F, X_half))
        N = r(self._timed("convect", self.convective_rate, u0))
        rhs = r([alpha * u0[d] - self.rho * N[d] + f[d] for d in range(3)])
        del N, f
        u, p = self._timed("solve", self.saddle_solve, rhs, alpha)
        u, p = r(u), self._round(p)
        mid = [0.5 * (a + b) for a, b in zip(low, self._lower(u))]
        U_half = self._round(self._timed("transfers", self._interp, mid,
                                         X_half))
        return State(u=tuple(u), p=p, X=self._round(X + dt * U_half),
                     U=U_half, F=F.sum(axis=0), dx=self.dx)

    def _round_all(self, arrays) -> list:
        return [self._round(a) for a in arrays]

    def advance(self, s: State, steps: int, dt: float | None = None) -> State:
        dt = self.dt if dt is None else dt
        for _ in range(steps):
            s = self.step(s, dt)
        return s
