"""Plain reference of the ex4 elastic shell: the midpoint immersed-boundary
step on a periodic MAC grid, in numpy float64 on the host.

It imports nothing of the program and takes nothing the program made: the
lattice, the spring list, the delta weights, the Laplacian's symbol and the
stencils are all built here from the configuration's own input file.

One step (``IBExplicitHierarchyIntegrator`` midpoint + pressure-increment
projection with AB2 convection and Crank-Nicolson diffusion):

    U^n       = J(X^n) u^n
    X^{n+1/2} = X^n + dt/2 U^n
    F         = springs(X^{n+1/2});  f = S(X^{n+1/2}) F
    N*        = 3/2 N(u^n) - 1/2 N(u^{n-1})        (N(u^n) on step 0)
    (rho/dt - mu/2 lap) u* = (rho/dt + mu/2 lap) u^n - rho N* - grad p + f
    lap(phi0) = div u*;  u^{n+1} = u* - grad(phi0)
    p^{n+1/2} = p^{n-1/2} + (rho/dt) phi0 - (mu/2) lap(phi0)
    U^{n+1/2} = J(X^{n+1/2}) (u^n + u^{n+1})/2
    X^{n+1}   = X^n + dt U^{n+1/2}

Transforms are scipy's (pocketfft) on the host: on the chip a rank-3
inverse real FFT of a 256^3 field returned wrong values (PERF.md, PR 23),
so the reference does not transform there.  Elementwise grid work is split
into slabs over a thread pool (numpy releases the GIL); nothing else is
clever.

``lowp="bf16"`` computes the same step in the nearest precision below
float32, for the control that ``correct`` has to fail (never used for the
comparison itself): the delta weights and the transferred values are rounded
to bfloat16 before each spread/interpolate contraction (what the program's
``packed_bf16`` engine does) and the operands of every transform are rounded
to bfloat16 (what its ``spectral_dtype = "bf16"`` does).
"""

from __future__ import annotations

import math
import os
import time
from concurrent.futures import ThreadPoolExecutor
from typing import NamedTuple

import numpy as np
import scipy.fft as sfft

WORKERS = max(1, min(16, len(os.sched_getaffinity(0))))


class State(NamedTuple):
    u: tuple            # three (n, n, n) MAC components, lower-face storage
    p: np.ndarray       # cell-centred pressure at t^{n-1/2}
    n_prev: tuple       # N(u^{n-1})
    k: int              # step counter (AB2 bootstrap)
    X: np.ndarray       # (N, 3) markers
    U: np.ndarray       # (N, 3) marker velocity


def _bf16(a: np.ndarray) -> np.ndarray:
    import ml_dtypes

    if np.iscomplexobj(a):
        return _bf16(a.real) + 1j * _bf16(a.imag)
    return a.astype(np.float32).astype(ml_dtypes.bfloat16).astype(np.float64)


def _phi_ib4(r: np.ndarray) -> np.ndarray:
    a = np.abs(r)
    inner = 0.125 * (3.0 - 2.0 * a
                     + np.sqrt(np.maximum(1.0 + 4.0 * a - 4.0 * a * a, 0.0)))
    outer = 0.125 * (5.0 - 2.0 * a
                     - np.sqrt(np.maximum(-7.0 + 12.0 * a - 4.0 * a * a, 0.0)))
    return np.where(a < 1.0, inner, np.where(a < 2.0, outer, 0.0))


def _pad(u: np.ndarray) -> np.ndarray:
    """Periodic ghost layer of one cell on every side."""
    n0, n1, n2 = u.shape
    p = np.empty((n0 + 2, n1 + 2, n2 + 2), u.dtype)
    p[1:-1, 1:-1, 1:-1] = u
    p[0, 1:-1, 1:-1] = u[-1]
    p[-1, 1:-1, 1:-1] = u[0]
    p[:, 0, 1:-1] = p[:, -2, 1:-1]
    p[:, -1, 1:-1] = p[:, 1, 1:-1]
    p[:, :, 0] = p[:, :, -2]
    p[:, :, -1] = p[:, :, 1]
    return p


class ShellReference:
    """Built from the parsed input file (``perfbench.inputfile.parse``)."""

    def __init__(self, db: dict, lowp: str | None = None):
        if lowp not in (None, "bf16"):
            raise ValueError(f"unknown lowp {lowp!r}")
        geo, ins, sh = db["CartesianGeometry"], \
            db["INSStaggeredHierarchyIntegrator"], db["Shell"]
        self.n = tuple(int(v) for v in geo["n_cells"])
        self.x_lo = tuple(float(v) for v in geo["x_lo"])
        self.x_up = tuple(float(v) for v in geo["x_up"])
        self.dx = tuple((hi - lo) / n for lo, hi, n
                        in zip(self.x_lo, self.x_up, self.n))
        self.rho, self.mu = float(ins["rho"]), float(ins["mu"])
        self.dt = float(ins["dt"])
        if ins.get("convective_op_type", "centered").lower() != "centered":
            raise ValueError("the reference implements centered convection")
        if db.get("IBMethod", {}).get("delta_fcn", "IB_4") != "IB_4":
            raise ValueError("the reference implements the IB_4 kernel")
        if float(sh.get("bend_rigidity", 0.0)) != 0.0:
            raise ValueError("the reference implements springs only")
        self.lowp = lowp
        self.X0, self.springs = self._lattice(sh)
        lam = None
        for d in range(3):
            f = (sfft.rfftfreq(self.n[d]) if d == 2
                 else sfft.fftfreq(self.n[d]))
            ld = (2.0 * np.cos(2.0 * math.pi * f) - 2.0) / self.dx[d] ** 2
            shape = [1, 1, 1]
            shape[d] = ld.shape[0]
            lam = ld.reshape(shape) if lam is None else lam + ld.reshape(shape)
        self.lam = lam
        self._pool = ThreadPoolExecutor(WORKERS)
        # where the reference's own time goes (the rest of "total" is the
        # grid stencils)
        self.seconds = {"transfers": 0.0, "transforms": 0.0, "total": 0.0}

    def _timed(self, key, fn, *a):
        t0 = time.perf_counter()
        out = fn(*a)
        self.seconds[key] += time.perf_counter() - t0
        return out

    def close(self):
        self._pool.shutdown()

    # -- structure ---------------------------------------------------------
    def _lattice(self, sh):
        """Latitude-longitude lattice without the poles; springs along the
        rings (closed) and the meridians (open), rest length = the sphere's
        own arc length times ``rest_length_factor``."""
        n_lat, n_lon = int(sh["n_lat"]), int(sh["n_lon"])
        R, aspect = float(sh["radius"]), float(sh["aspect"])
        c = [0.5 * (lo + hi) for lo, hi in zip(self.x_lo, self.x_up)]
        theta = math.pi * (np.arange(n_lat) + 0.5) / n_lat
        phi = 2.0 * math.pi * np.arange(n_lon) / n_lon
        st, ct = np.sin(theta)[:, None], np.cos(theta)[:, None]
        x = c[0] + R * st * np.cos(phi)[None, :]
        y = c[1] + R * st * np.sin(phi)[None, :]
        z = c[2] + R * aspect * ct * np.ones((1, n_lon))
        X0 = np.stack([x.ravel(), y.ravel(), z.ravel()], axis=1)
        i, j = np.meshgrid(np.arange(n_lat), np.arange(n_lon), indexing="ij")
        ring0 = (i * n_lon + j).ravel()
        ring1 = (i * n_lon + (j + 1) % n_lon).ravel()
        ring_rest = np.repeat(2.0 * math.pi * R * np.sin(theta) / n_lon, n_lon)
        im, jm = np.meshgrid(np.arange(n_lat - 1), np.arange(n_lon),
                             indexing="ij")
        mer0 = (im * n_lon + jm).ravel()
        mer1 = ((im + 1) * n_lon + jm).ravel()
        mer_rest = np.full(mer0.shape, math.pi * R / n_lat)
        rest = np.concatenate([ring_rest, mer_rest]) \
            * float(sh["rest_length_factor"])
        return X0, (np.concatenate([ring0, mer0]),
                    np.concatenate([ring1, mer1]),
                    float(sh["stiffness"]), rest)

    def force(self, X: np.ndarray) -> np.ndarray:
        i0, i1, k, rest = self.springs
        d = X[i1] - X[i0]
        length = np.sqrt(np.sum(d * d, axis=1))
        fvec = (k * (length - rest) / np.where(length > 0, length, 1.0)
                )[:, None] * d
        F = np.zeros_like(X)
        for c in range(3):
            F[:, c] = (np.bincount(i0, weights=fvec[:, c], minlength=len(X))
                       - np.bincount(i1, weights=fvec[:, c], minlength=len(X)))
        return F

    # -- transfers ---------------------------------------------------------
    def _stencil(self, X: np.ndarray, comp: int):
        """Linear grid indices (N, 64) and tensor-product IB_4 weights of
        component ``comp``'s faces around each marker."""
        idx, wts = [], []
        for d in range(3):
            off = 0.0 if d == comp else 0.5
            xi = (X[:, d] - self.x_lo[d]) / self.dx[d] - off
            j = (np.floor(xi - 2.0).astype(np.int64) + 1)[:, None] \
                + np.arange(4)[None, :]
            wts.append(_phi_ib4(xi[:, None] - j))
            idx.append(np.mod(j, self.n[d]))
        lin = ((idx[0][:, :, None, None] * self.n[1]
                + idx[1][:, None, :, None]) * self.n[2]
               + idx[2][:, None, None, :]).reshape(len(X), 64)
        w = (wts[0][:, :, None, None] * wts[1][:, None, :, None]
             * wts[2][:, None, None, :]).reshape(len(X), 64)
        if self.lowp == "bf16":
            w = _bf16(w)
        return lin, w

    def stencils(self, X):
        return list(self._pool.map(lambda c: self._stencil(X, c), range(3)))

    def interp(self, u, st) -> np.ndarray:
        def one(c):
            lin, w = st[c]
            vals = u[c].reshape(-1)[lin]
            if self.lowp == "bf16":
                vals = _bf16(vals)
            return np.sum(vals * w, axis=1)
        return np.stack(list(self._pool.map(one, range(3))), axis=1)

    def spread(self, F, st) -> tuple:
        inv_vol = 1.0 / math.prod(self.dx)
        size = math.prod(self.n)

        def one(c):
            lin, w = st[c]
            Fc = F[:, c] * inv_vol
            if self.lowp == "bf16":
                Fc = _bf16(Fc)
            return np.bincount(lin.reshape(-1),
                               weights=(Fc[:, None] * w).reshape(-1),
                               minlength=size).reshape(self.n)
        return tuple(self._pool.map(one, range(3)))

    # -- grid operators, by slabs of the first axis ------------------------
    def _slabs(self, fn):
        n0 = self.n[0]
        # two slabs a worker: 7% faster at 256^3 than one (PERF.md, PR 27)
        edges = np.linspace(0, n0, min(2 * WORKERS, n0) + 1).astype(int)
        list(self._pool.map(lambda ab: fn(int(ab[0]), int(ab[1])),
                            zip(edges[:-1], edges[1:])))

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

    def fluid_step(self, u, p, n_prev, k, f, dt):
        n, dx, rho, mu = self.n, self.dx, self.rho, self.mu
        *up, pp = self._pool.map(_pad, [*u, p])

        def S(a, lo, hi, di=0, dj=0, dk=0):
            """Rows lo:hi of ``a`` shifted: value at (i+di, j+dj, k+dk)."""
            return a[1 + lo + di:1 + hi + di, 1 + dj:1 + n[1] + dj,
                     1 + dk:1 + n[2] + dk]

        def sh(d, s=1):
            v = [0, 0, 0]
            v[d] = s
            return tuple(v)

        n_curr = [np.empty(n) for _ in range(3)]
        rhs = [np.empty(n) for _ in range(3)]
        c1, c2 = (1.0, 0.0) if k == 0 else (1.5, -0.5)

        def build(lo, hi):
            for d in range(3):
                ud = up[d]
                acc = np.zeros((hi - lo,) + n[1:])
                lap = np.zeros_like(acc)
                for e in range(3):
                    if e == d:
                        fp = 0.5 * (S(ud, lo, hi) + S(ud, lo, hi, *sh(d)))
                        fm = 0.5 * (S(ud, lo, hi, *sh(d, -1)) + S(ud, lo, hi))
                        acc += (fp * fp - fm * fm) / dx[d]
                    else:
                        ue = up[e]
                        me = sh(d, -1)
                        pe = sh(e)
                        both = tuple(a + b for a, b in zip(pe, me))
                        flo = (0.5 * (S(ue, lo, hi) + S(ue, lo, hi, *me))
                               * 0.5 * (S(ud, lo, hi)
                                        + S(ud, lo, hi, *sh(e, -1))))
                        fhi = (0.5 * (S(ue, lo, hi, *pe)
                                      + S(ue, lo, hi, *both))
                               * 0.5 * (S(ud, lo, hi, *pe) + S(ud, lo, hi)))
                        acc += (fhi - flo) / dx[e]
                    lap += (S(ud, lo, hi, *sh(e)) - 2.0 * S(ud, lo, hi)
                            + S(ud, lo, hi, *sh(e, -1))) / dx[e] ** 2
                n_curr[d][lo:hi] = acc
                gp = (S(pp, lo, hi) - S(pp, lo, hi, *sh(d, -1))) / dx[d]
                rhs[d][lo:hi] = ((rho / dt) * S(ud, lo, hi) + 0.5 * mu * lap
                                 - rho * (c1 * acc + c2 * n_prev[d][lo:hi])
                                 - gp + f[d][lo:hi])

        self._slabs(build)
        helm = rho / dt - 0.5 * mu * self.lam
        ustar = [self._ifft(self._fft(r) / helm) for r in rhs]
        usp = list(self._pool.map(_pad, ustar))
        div = np.empty(n)

        def divergence(lo, hi):
            acc = np.zeros((hi - lo,) + n[1:])
            for d in range(3):
                acc += (S(usp[d], lo, hi, *sh(d)) - S(usp[d], lo, hi)) / dx[d]
            div[lo:hi] = acc

        self._slabs(divergence)
        lam_safe = np.where(self.lam == 0, 1.0, self.lam)
        phi0 = self._ifft(np.where(self.lam == 0, 0.0,
                                   self._fft(div) / lam_safe))
        php = _pad(phi0)
        u_new = [np.empty(n) for _ in range(3)]
        p_new = np.empty(n)

        def correct(lo, hi):
            lap = np.zeros((hi - lo,) + n[1:])
            for d in range(3):
                u_new[d][lo:hi] = ustar[d][lo:hi] - (
                    S(php, lo, hi) - S(php, lo, hi, *sh(d, -1))) / dx[d]
                lap += (S(php, lo, hi, *sh(d)) - 2.0 * S(php, lo, hi)
                        + S(php, lo, hi, *sh(d, -1))) / dx[d] ** 2
            p_new[lo:hi] = (p[lo:hi] + (rho / dt) * phi0[lo:hi]
                            - 0.5 * mu * lap)

        self._slabs(correct)
        return tuple(u_new), p_new, tuple(n_curr)

    # -- the step ----------------------------------------------------------
    def step(self, s: State, dt: float) -> State:
        return self._timed("total", self._step, s, dt)

    def _step(self, s: State, dt: float) -> State:
        T = self._timed
        st_n = T("transfers", self.stencils, s.X)
        U_n = T("transfers", self.interp, s.u, st_n)
        del st_n
        X_half = s.X + 0.5 * dt * U_n
        st_h = T("transfers", self.stencils, X_half)
        f = T("transfers", self.spread, self.force(X_half), st_h)
        u_new, p_new, n_curr = self.fluid_step(s.u, s.p, s.n_prev, s.k, f, dt)
        u_half = tuple(0.5 * (a + b) for a, b in zip(s.u, u_new))
        U_half = T("transfers", self.interp, u_half, st_h)
        return State(u=u_new, p=p_new, n_prev=n_curr, k=s.k + 1,
                     X=s.X + dt * U_half, U=U_half)

    def advance(self, s: State, steps: int, dt: float | None = None) -> State:
        dt = self.dt if dt is None else dt
        for _ in range(steps):
            s = self.step(s, dt)
        return s


def state_from_arrays(a: dict) -> State:
    """Host float64 state from the named leaves the harness pulls off the
    device (``u0 u1 u2 p n0 n1 n2 k X U``)."""
    f = lambda x: np.asarray(x, dtype=np.float64)  # noqa: E731
    return State(u=(f(a["u0"]), f(a["u1"]), f(a["u2"])), p=f(a["p"]),
                 n_prev=(f(a["n0"]), f(a["n1"]), f(a["n2"])),
                 k=int(a["k"]), X=f(a["X"]), U=f(a["U"]))
