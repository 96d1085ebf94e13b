"""Plain reference of the periodic fluid-only family (``tg_*``): one step of
the incompressible Navier-Stokes equations on a periodic MAC grid, in numpy
float64 on the host.

It imports nothing of the program and takes nothing the program made: the
stencils, the PPM face values and the Laplacian's symbol are all written here
from the configuration's own input file.  (From the shell's reference it
takes the bfloat16 rounding and the worker count, nothing of its step: that
step has centred convection wired in.)

One step (``INSStaggeredHierarchyIntegrator`` on one periodic level:
pressure-increment projection, AB2 convection, Crank-Nicolson diffusion):

    N*        = 3/2 N(u^n) - 1/2 N(u^{n-1})        (N(u^n) on step 0)
    (rho/dt - mu/2 lap) u* = (rho/dt + mu/2 lap) u^n - rho N* - grad p
    lap(phi0) = div u*;  u^{n+1} = u* - grad(phi0)
    p^{n+1/2} = p^{n-1/2} + (rho/dt) phi0 - (mu/2) lap(phi0)

The Helmholtz solve, the projection and the pressure increment are done in
one pass through Fourier space with the DISCRETE symbols of the MAC
difference operators (forward difference face -> centre, backward
difference centre -> face, the 7-point Laplacian their product): three
transforms forward, four back, and a discrete divergence at rounding.

N(u)_d = sum_e d/dx_e (u_e u_d), in conservative form at u_d's own faces:
the advecting velocity is the two-point average of u_e onto the flux point,
the advected value is the piecewise-parabolic (PPM) face value of u_d along
e, taken from the upwind side of the advecting velocity.  The PPM face
values are Colella & Woodward's (J. Comput. Phys. 54 (1984) 174):

    slope    da_i = MC-limited: 0 at an extremum of (a_{i-1}, a_i, a_{i+1}),
             else sign(a_{i+1} - a_{i-1}) min(|a_{i+1} - a_{i-1}|/2,
             2|a_{i+1} - a_i|, 2|a_i - a_{i-1}|)                  (eq. 1.8)
    face     a_{i+1/2} = (a_i + a_{i+1})/2 - (da_{i+1} - da_i)/6  (eq. 1.6)
    parabola (aL, aR) = (a_{i-1/2}, a_{i+1/2}), flattened to a_i where a_i
             is an extremum of the three, and the far edge pulled in where
             the parabola would overshoot inside the cell         (eq. 1.10)
    upwind   at face i+1/2: aR of cell i where the advecting velocity is
             positive, aL of cell i+1 where negative, their mean at zero.

Departures from upstream's ``INSStaggeredPPMConvectiveOperator`` (written
from memory, ``assumed`` in the configuration): upstream traces the
parabola back along the characteristic over dt/2 (a Godunov predictor);
here, as in the program, the edge state itself is taken (the time
centring comes from AB2), and no contact steepening or flattening is
applied (CW84 sections 3-4 are for shocks).

Elementwise grid work runs in slabs over a thread pool (numpy releases the
GIL), transforms are scipy's.

``lowp="bf16"`` computes the same step in the nearest precision below
float32, for the control that ``correct`` has to fail: the operands of every
transform are rounded to bfloat16 (what the program's opt-in
``spectral_dtype = "bf16"`` does).
"""

from __future__ import annotations

import math
import time
from concurrent.futures import ThreadPoolExecutor
from typing import NamedTuple

import numpy as np
import scipy.fft as sfft

from perfbench.reference.ib_shell import WORKERS, _bf16

G = 3           # ghost cells: a PPM face value reaches three cells out
# float64 numbers in a slab (4 rows at 256^3, 2 MB a temporary).  Twice that
# ran as fast, but the chip's host then lost 30 GiB of free memory beyond the
# process's own 3 while the reference ran (malloc's per-thread heaps mapped
# and unmapped at GB/s), and a command there is ended at 40 GiB (PERF.md, PR 28)
SLAB = 2 ** 18


class State(NamedTuple):
    u: tuple            # three (n, n, n) MAC components, lower-face storage
    p: np.ndarray       # cell-centred pressure at t^{n-1/2}
    n_prev: tuple       # N(u^{n-1})
    k: int              # step counter (AB2 bootstrap)


def _cut(a: np.ndarray, axis: int, lo: int, hi: int) -> np.ndarray:
    """``a[lo:len+hi]`` along ``axis`` (``hi`` <= 0)."""
    idx = [slice(None)] * a.ndim
    idx[axis] = slice(lo, a.shape[axis] + hi)
    return a[tuple(idx)]


def ppm_face_values(a: np.ndarray, adv: np.ndarray, axis: int) -> np.ndarray:
    """Upwinded PPM values at the faces between the cells of ``a`` along
    ``axis``.  A block of L cells gives the L - 5 faces from the one between
    cells 2 and 3 to the one between cells L - 4 and L - 3; ``adv`` is the
    advecting velocity at those faces."""
    dp = _cut(a, axis, 1, 0) - _cut(a, axis, 0, -1)     # a[i+1] - a[i]
    dlo, dhi = _cut(dp, axis, 0, -1), _cut(dp, axis, 1, 0)
    dc = 0.5 * (dlo + dhi)
    slope = np.where(dlo * dhi > 0.0,
                     np.sign(dc) * np.minimum(
                         np.abs(dc), 2.0 * np.minimum(np.abs(dlo),
                                                      np.abs(dhi))),
                     0.0)                               # cells 1 .. L-2
    face = (0.5 * (_cut(a, axis, 1, -2) + _cut(a, axis, 2, -1))
            - (_cut(slope, axis, 1, 0) - _cut(slope, axis, 0, -1)) / 6.0)
    c = _cut(a, axis, 2, -2)                            # cells 2 .. L-3
    aL, aR = _cut(face, axis, 0, -1), _cut(face, axis, 1, 0)
    flat = (aR - c) * (c - aL) <= 0.0
    aL, aR = np.where(flat, c, aL), np.where(flat, c, aR)
    d = aR - aL
    q6 = d * (c - 0.5 * (aL + aR))
    d2 = d * d / 6.0
    aL, aR = (np.where(q6 > d2, 3.0 * c - 2.0 * aR, aL),
              np.where(q6 < -d2, 3.0 * c - 2.0 * aL, aR))
    up, dn = _cut(aR, axis, 0, -1), _cut(aL, axis, 1, 0)
    return np.where(adv > 0.0, up, np.where(adv < 0.0, dn, 0.5 * (up + dn)))


def _pad(u: np.ndarray) -> np.ndarray:
    return np.pad(u, G, mode="wrap")


def _view(a: np.ndarray, rng, axis: int = 0, shift: int = 0) -> np.ndarray:
    """Cells ``rng[d] = (lo, hi)`` of a padded array, in interior
    coordinates (``-G <= lo``, ``hi <= n + G``), moved by ``shift`` along
    ``axis``."""
    idx = [slice(G + lo, G + hi) for lo, hi in rng]
    idx[axis] = slice(idx[axis].start + shift, idx[axis].stop + shift)
    return a[tuple(idx)]


class FluidReference:
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
        if ins["convective_op_type"].lower() != "ppm":
            raise ValueError("the reference implements PPM convection")
        self.lowp = lowp
        # symbols of the MAC differences: fwd takes a lower-face field to
        # cell centres, bwd a cell-centred field to lower faces
        self.fwd, self.bwd, lam = [], [], 0.0
        for d in range(3):
            f = (sfft.rfftfreq(self.n[d]) if d == 2
                 else sfft.fftfreq(self.n[d]))
            shape = [1, 1, 1]
            shape[d] = f.shape[0]
            e = np.exp(2j * math.pi * f).reshape(shape)
            self.fwd.append((e - 1.0) / self.dx[d])
            self.bwd.append((1.0 - 1.0 / e) / self.dx[d])
            lam = lam + (self.fwd[d] * self.bwd[d]).real
        self.lam = lam
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

    def _fft(self, a):
        if self.lowp == "bf16":
            a = _bf16(a)
        return self._timed("transforms",
                           lambda: sfft.rfftn(a, workers=WORKERS))

    def _ifft(self, ah):
        if self.lowp == "bf16":
            ah = _bf16(ah)
        return self._timed(
            "transforms", lambda: sfft.irfftn(ah, s=self.n, workers=WORKERS))

    # -- the convective operator -------------------------------------------
    def convective_rate(self, up) -> list:
        """N(u) from the three ghost-padded components."""
        n, dx = self.n, self.dx
        out = [np.zeros(n) for _ in range(3)]

        def term(d, e):
            """d/dx_e (u_e u_d) added to ``out[d]``.  The fluxes sit at the
            lower e-faces j = 0 .. n of u_d's own cells (for e = d these are
            the cell centres between u_d's faces j - 1 and j): u_e averaged
            along d onto them, times u_d's PPM value there."""
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
        return out

    # -- the step ----------------------------------------------------------
    def step(self, s: State, dt: float) -> State:
        return self._timed("total", self._step, s, dt)

    def _step(self, s: State, dt: float) -> State:
        n, dx, rho, mu = self.n, self.dx, self.rho, self.mu
        *up, pp = self._pool.map(_pad, [*s.u, s.p])
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
        # Helmholtz solve, projection and pressure increment in Fourier
        # space: u*^ = rhs^ / (rho/dt - mu/2 lam); phi0^ = div^ / lam
        uh = [self._fft(r) for r in rhs]
        del rhs
        ph = np.empty_like(uh[0])

        def solve(lo, hi):
            rows = slice(lo, hi)
            lam = self.lam[rows]
            fwd = [self.fwd[0][rows], *self.fwd[1:]]
            bwd = [self.bwd[0][rows], *self.bwd[1:]]
            for d in range(3):
                uh[d][rows] /= rho / dt - 0.5 * mu * lam
            div = sum(fwd[d] * uh[d][rows] for d in range(3))
            phi = np.where(lam == 0, 0.0, div / np.where(lam == 0, 1.0, lam))
            for d in range(3):
                uh[d][rows] -= bwd[d] * phi
            ph[rows] = (rho / dt - 0.5 * mu * lam) * phi

        self._slabs(solve)
        u_new = tuple(self._ifft(uh[d]) for d in range(3))
        p_new = s.p + self._ifft(ph)
        return State(u=u_new, p=p_new, n_prev=tuple(n_curr), k=s.k + 1)

    def advance(self, s: State, steps: int, dt: float | None = None) -> State:
        dt = self.dt if dt is None else dt
        for _ in range(steps):
            s = self.step(s, dt)
        return s


def state_from_arrays(a: dict) -> State:
    """Host float64 state from the named leaves the harness pulls off the
    device (``u0 u1 u2 p n0 n1 n2 k``)."""
    f = lambda x: np.asarray(x, dtype=np.float64)  # noqa: E731
    return State(u=(f(a["u0"]), f(a["u1"]), f(a["u2"])), p=f(a["p"]),
                 n_prev=(f(a["n0"]), f(a["n1"]), f(a["n2"])), k=int(a["k"]))
