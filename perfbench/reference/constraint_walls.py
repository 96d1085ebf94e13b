"""Plain reference of the rigid-body family (``falling_sphere_*``): one step
of the ConstraintIB momentum projection for ONE rigid body of volumetric
markers in a box with six no-slip walls, in numpy float64 on the host.

It imports nothing of the program and takes nothing the program made but the
state it is handed.  (From the walled reference ``ins_walls.py`` it takes the
unconstrained fluid step and the sine/cosine solves, with no wall moving; from
the periodic reference the padded views; from the shell's reference the IB_4
function, the bfloat16 rounding and the worker count.  The transfers, the
rigid projection, the inertia update, the imposition and the re-projection
are written out here.)

One step (Bhalla, Bale, Griffith & Patankar, J. Comput. Phys. 250 (2013)
446, as upstream's ``ConstraintIBMethod`` orders it):

    u*       = the unconstrained walled fluid step of ``ins_walls.py``
    U_i      = J(X^n) u*                      (IB_4, scatter form, each
                                               component at its own faces)
    c        = mean X;  r = X - c
    V_f      = mean U_i;  W_f = I^-1 sum r x (U_i - V_f),
               I = sum (|r|^2 1 - r r^T)      (least-squares rigid motion)
    (V, W)   = (V_f, W_f) + a [(V, W)^n + dt (g, 0) - (V_f, W_f)],
               a = (s - 1) / (s + c_vm),  s = rho_p / rho_f
    U_b      = V + W x r
    u**      = u* + S(U_b - U_i) / S(1)  where S(1) > floor, else u*
    lap(phi) = div u**;  u^{n+1} = u** - grad(phi)      (p is NOT updated)
    X^{n+1}  = c + dt V + exp(dt [W]x) r    (the rigid motion itself: the
                                             body keeps its shape exactly)

Departures from the paper's formulation that the program makes, and this
file with it (each under ``assumed`` in the configuration):

- equal marker weights: the paper weights a marker by its material volume;
  for a body seeded on a uniform lattice the two coincide;
- the virtual-mass form of the inertia update: the paper's explicit update is
  a = (s - 1) / s (c_vm = 0); the program adds the displaced fluid's added
  mass c_vm (1/2 for a sphere) to the denominator, which gives the classical
  early free fall (s - 1) g / (s + 1/2) and a fixed point whose slip against
  the projected fluid velocity is (s - 1) dt g / (1 + c_vm);
- the imposition is a velocity REPLACEMENT normalised by the spread
  indicator S(1) (a partition of unity inside the body), with a floor under
  which a face is outside every body; the spread carries the delta
  function's 1/h^3, and the floor is in those units;
- the second projection's potential is discarded: the pressure is the
  unconstrained step's.

The transfers wrap around like the program's: a marker within 2.5 cells of a
wall would spread through it, which the configuration's clearance contract
excludes.

``lowp="bf16"`` computes the same step in the nearest precision below
float32, for the control that ``correct`` has to fail: the operand of every
axis transform is rounded to bfloat16 (the walled reference's control), and
the delta weights and the transferred values before each spread/interpolate
contraction (what a ``packed_bf16`` engine does).
"""

from __future__ import annotations

import math
import time
from typing import NamedTuple

import numpy as np

from perfbench.reference import ins_walls
from perfbench.reference.ib_shell import _bf16, _phi_ib4
from perfbench.reference.ins_periodic import G, _view

__all__ = ["ConstraintReference", "State", "state_from_arrays"]

# the program's default (``ConstraintIBMethod(indicator_floor=...)``; the
# example passes none), in the spread's units of 1/volume
INDICATOR_FLOOR = 1e-4


class State(NamedTuple):
    u: tuple            # three MAC components, lower-face storage
    p: np.ndarray       # cell-centred pressure at t^{n-1/2}
    n_prev: tuple       # N(u^{n-1})
    k: int              # step counter (AB2 bootstrap)
    X: np.ndarray       # (N, 3) markers of the one body
    U_body: np.ndarray  # (6,) its rigid motion (V, W) about the centroid
    # the body as the input file builds it, on what ``advance`` returns: the
    # shape every later state has to be a rigid motion of
    body: np.ndarray | None = None
    # the spread indicator S(1) of the last step taken (three face fields):
    # where that step imposed the body's velocity
    indicator: tuple | None = None


def lattice_ball(center, radius: float, spacing: float) -> np.ndarray:
    """The points within ``radius`` of ``center`` of the cubic lattice that
    divides the diameter into the least whole number of steps of at most
    ``spacing``, in row-major order of the lattice (a lattice point on the
    sphere itself counts as inside)."""
    n = int(math.ceil(2.0 * radius / spacing - 1e-9)) + 1
    ax = np.linspace(-radius, radius, n)
    x, y, z = np.meshgrid(ax, ax, ax, indexing="ij")
    keep = x * x + y * y + z * z <= radius * radius * (1.0 + 1e-12)
    return np.stack([x[keep] + center[0], y[keep] + center[1],
                     z[keep] + center[2]], axis=1)


def rigid_fit(X: np.ndarray, U: np.ndarray) -> np.ndarray:
    """``(V, W)`` of the least-squares rigid motion of marker velocities
    ``U`` about the markers' centroid, with equal weights."""
    r = X - X.mean(axis=0)
    V = U.mean(axis=0)
    L = np.cross(r, U - V).sum(axis=0)
    inertia = (r * r).sum() * np.eye(3) - r.T @ r
    return np.concatenate([V, np.linalg.solve(inertia, L)])


def rigid_velocity(X: np.ndarray, modes: np.ndarray) -> np.ndarray:
    return modes[:3] + np.cross(modes[3:], X - X.mean(axis=0))


def rigid_move(X: np.ndarray, modes: np.ndarray, dt: float) -> np.ndarray:
    """The markers after the rigid motion ``modes`` has acted for ``dt``:
    the centroid translated, the rest rotated about it by the rotation
    vector dt W (Rodrigues' formula)."""
    c = X.mean(axis=0)
    r, w = X - c, dt * modes[3:]
    theta = np.linalg.norm(w)
    if theta > 0.0:
        k = w / theta
        r = (r * math.cos(theta) + np.cross(k, r) * math.sin(theta)
             + np.outer(r @ k, k) * (1.0 - math.cos(theta)))
    return c + dt * modes[:3] + r


class ConstraintReference:
    """Built from the parsed input file (``perfbench.inputfile.parse``)."""

    def __init__(self, db: dict, lowp: str | None = None):
        ins = db["INSStaggeredHierarchyIntegrator"]
        cib, sph = db["ConstraintIBMethod"], db["Sphere"]
        if cib["delta_fcn"] != "IB_4":
            raise ValueError("the reference implements the IB_4 kernel")
        # the unconstrained step: the walled reference with no wall moving
        self.fluid = ins_walls.WallReference(
            {**db, "INSStaggeredHierarchyIntegrator": {**ins, "U_lid": 0.0}},
            lowp=lowp)
        self.n, self.dx, self.dt = self.fluid.n, self.fluid.dx, self.fluid.dt
        self.x_lo = tuple(float(v) for v in db["CartesianGeometry"]["x_lo"])
        s = float(sph["density"]) / float(ins["rho"])
        self.a = (s - 1.0) / (s + float(cib["virtual_mass"]))
        self.g_modes = np.array([*map(float, sph["gravity"]), 0.0, 0.0, 0.0])
        self.body = lattice_ball(
            [float(v) for v in sph["center"]], 0.5 * float(sph["diameter"]),
            float(sph["marker_spacing_cells"]) * min(self.dx))
        self.lowp = lowp
        self.seconds = {"fluid": 0.0, "transfers": 0.0, "reproject": 0.0}

    def close(self):
        self.fluid.close()

    def _timed(self, key, fn, *a):
        t0 = time.perf_counter()
        out = fn(*a)
        self.seconds[key] += time.perf_counter() - t0
        return out

    # -- transfers, scatter form -------------------------------------------
    def _stencil(self, X: np.ndarray, comp: int):
        """Linear grid indices (N, 64) and tensor-product IB_4 weights of
        component ``comp``'s faces around each marker (indices wrap)."""
        idx, wts = [], []
        for d in range(3):
            xi = (X[:, d] - self.x_lo[d]) / self.dx[d] \
                - (0.0 if d == comp else 0.5)
            j = (np.floor(xi - 2.0).astype(np.int64) + 1)[:, None] \
                + np.arange(4)[None, :]
            wts.append(_phi_ib4(xi[:, None] - j))
            idx.append(np.mod(j, self.n[d]))
        lin = ((idx[0][:, :, None, None] * self.n[1]
                + idx[1][:, None, :, None]) * self.n[2]
               + idx[2][:, None, None, :]).reshape(len(X), 64)
        w = (wts[0][:, :, None, None] * wts[1][:, None, :, None]
             * wts[2][:, None, None, :]).reshape(len(X), 64)
        return lin, _bf16(w) if self.lowp else w

    def stencils(self, X):
        return [self._stencil(X, c) for c in range(3)]

    def interp(self, u, st) -> np.ndarray:
        cols = []
        for c, (lin, w) in enumerate(st):
            vals = u[c].reshape(-1)[lin]
            cols.append(np.sum((_bf16(vals) if self.lowp else vals) * w,
                               axis=1))
        return np.stack(cols, axis=1)

    def spread(self, F, st) -> list:
        """S(F): delta-spread marker values onto each component's faces,
        with the delta function's 1/h^3."""
        inv_vol, size = 1.0 / math.prod(self.dx), math.prod(self.n)
        out = []
        for c, (lin, w) in enumerate(st):
            Fc = F[:, c] * inv_vol
            if self.lowp:
                Fc = _bf16(Fc)
            out.append(np.bincount(
                lin.reshape(-1), weights=(Fc[:, None] * w).reshape(-1),
                minlength=size).reshape(self.n))
        return out

    # -- the second projection ---------------------------------------------
    def project(self, u) -> tuple:
        """u - grad(phi), lap(phi) = div u with phi's normal derivative 0
        on the walls.  Every wall face carries 0 (slot 0 of a component's
        own axis; the hi face is not stored), and the Neumann ghost gives
        no gradient there."""
        n, dx, f = self.n, self.dx, self.fluid
        up = [np.pad(c, G) for c in u]
        div = np.empty(n)

        def divergence(lo, hi):
            rows = [(lo, hi), (0, n[1]), (0, n[2])]
            div[lo:hi] = sum((_view(up[d], rows, d, 1) - _view(up[d], rows))
                             / dx[d] for d in range(3))

        f._slabs(divergence)
        php = np.pad(f.helmholtz(div, 3, 0.0, 1.0), G, mode="edge")
        out = [np.empty(n) for _ in range(3)]

        def correct(lo, hi):
            rows = [(lo, hi), (0, n[1]), (0, n[2])]
            for d in range(3):
                out[d][lo:hi] = u[d][lo:hi] - (
                    _view(php, rows) - _view(php, rows, d, -1)) / dx[d]

        f._slabs(correct)
        return tuple(out)

    # -- the step ------------------------------------------------------------
    def step(self, s: State, dt: float) -> State:
        T = self._timed
        fl = T("fluid", self.fluid.step,
               ins_walls.State(u=s.u, p=s.p, n_prev=s.n_prev, k=s.k), dt)
        st = T("transfers", self.stencils, s.X)
        U_i = T("transfers", self.interp, fl.u, st)
        fit = rigid_fit(s.X, U_i)
        modes = fit + self.a * (s.U_body + dt * self.g_modes - fit)
        U_b = rigid_velocity(s.X, modes)
        num = T("transfers", self.spread, U_b - U_i, st)
        den = T("transfers", self.spread, np.ones_like(U_i), st)
        u_corr = [c + np.where(d > INDICATOR_FLOOR,
                               m / np.maximum(d, INDICATOR_FLOOR), 0.0)
                  for c, m, d in zip(fl.u, num, den)]
        u_new = T("reproject", self.project, u_corr)
        return State(u=u_new, p=fl.p, n_prev=fl.n_prev, k=fl.k,
                     X=rigid_move(s.X, modes, dt), U_body=modes,
                     indicator=tuple(den))

    def advance(self, s: State, steps: int, dt: float | None = None) -> State:
        dt = self.dt if dt is None else dt
        if s.X.shape != self.body.shape:
            raise ValueError(f"the input file builds {self.body.shape[0]} "
                             f"markers, the state has {s.X.shape[0]}")
        for _ in range(steps):
            s = self.step(s, dt)
        return s._replace(body=self.body)


def state_from_arrays(a: dict) -> State:
    """Host float64 state from the named leaves the harness pulls off the
    device (``u0 u1 u2 p n0 n1 n2 k X Ub``; ``Ub`` is (1, 6): one body)."""
    f = lambda x: np.asarray(x, dtype=np.float64)  # noqa: E731
    Ub = f(a["Ub"])
    if Ub.shape != (1, 6):
        raise ValueError(f"the reference takes one 3D body, got {Ub.shape}")
    return State(u=(f(a["u0"]), f(a["u1"]), f(a["u2"])), p=f(a["p"]),
                 n_prev=(f(a["n0"]), f(a["n1"]), f(a["n2"])), k=int(a["k"]),
                 X=f(a["X"]), U_body=Ub[0])
