"""Fast-diagonalization Helmholtz/Poisson solver for wall-bounded boxes.

Reference parity: replaces the FAC-multigrid + hypre solves (T8) for
non-periodic uniform levels — the role CCPoissonSolverManager /
SCPoissonSolverManager solvers play under the projection preconditioner
(P3) when walls are present.

Method (classic "fast diagonalization", Lynch-Rice-Thomas): the discrete
Laplacian with BC-modified end rows is a symmetric tridiagonal per axis;
eigendecompose each non-periodic axis ONCE on host (numpy.eigh) and apply
the orthogonal eigenvector matrices as axis transforms. Periodic axes use
FFT. The operator is then diagonal: solve = fwd transforms -> divide ->
inverse transforms.

TPU-first: the eigenvector transforms are dense (n, n) matmuls batched
over all other axes — they run on the MXU at full throughput, which on
TPU routinely beats a same-size FFT. The solve is exact for the discrete
operator (projection stays div-free to roundoff, as in the periodic FFT
path).

Centerings per axis:
- ``cc``        cell-centered unknowns; walls at faces. Dirichlet ghost
                = 2g - Q1 -> end row (-3, 1)/h^2; Neumann ghost = Q1 ->
                end row (-1, 1)/h^2.
- ``fc_pinned`` face-centered normal component; the lo boundary face is
                slot 0 of the array and is PINNED to the BC value (the
                hi boundary face is the same physical DOF in the
                periodic storage convention and is implicit). Unknowns
                are interior faces 1..n-1: standard Dirichlet-node
                tridiagonal of size n-1.
"""

from __future__ import annotations

import functools
import math
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ibamr_tpu import obs
from ibamr_tpu.bc import AxisBC, DomainBC, ghost_reflect_coeff
from ibamr_tpu.grid import StaggeredGrid

# axis transforms a traced solve makes (bumped at trace time, forward
# and inverse counted apart)
_DENSE_AXES_TOTAL = obs.counter("fluid_transform_dense_axes_total")
_FFT_AXES_TOTAL = obs.counter("fluid_transform_fft_axes_total")
obs.describe("fluid_transform_dense_axes_total",
             "axis transforms of traced fast-diagonalization solves made "
             "as dense eigenvector products (forward and inverse apart)")
obs.describe("fluid_transform_fft_axes_total",
             "axis transforms of traced fast-diagonalization solves made "
             "as FFTs (forward and inverse apart)")


def laplacian_1d_cc(n: int, h: float, axbc: AxisBC) -> np.ndarray:
    """BC-modified tridiagonal for a cell-centered axis (homogeneous).

    The boundary row uses the Robin reflection of bc._ghost_layers_cc:
    homogeneous ghost = r * interior with r = -(a/2 - b/h)/(a/2 + b/h),
    so the end diagonal is (-2 + r)/h^2 — which reproduces the classic
    -3 (dirichlet, r=-1) and -1 (neumann, r=+1) rows and covers general
    a*Q + b*dQ/dn = g (T9). The modification is diagonal-only, so the
    matrix stays symmetric and eigh applies."""
    A = np.zeros((n, n))
    inv = 1.0 / (h * h)
    for i in range(n):
        A[i, i] = -2.0 * inv
        if i > 0:
            A[i, i - 1] = inv
        if i < n - 1:
            A[i, i + 1] = inv
    for side, i in ((axbc.lo, 0), (axbc.hi, n - 1)):
        if side.kind == "periodic":
            raise ValueError("periodic axis has no 1D matrix")
        r = ghost_reflect_coeff(side, h)
        A[i, i] = (-2.0 + r) * inv
    return A


def laplacian_1d_fc_pinned(n: int, h: float) -> np.ndarray:
    """Interior-face unknowns (1..n-1) with Dirichlet boundary faces:
    standard (n-1)-point Dirichlet-node tridiagonal."""
    m = n - 1
    A = np.zeros((m, m))
    inv = 1.0 / (h * h)
    for i in range(m):
        A[i, i] = -2.0 * inv
        if i > 0:
            A[i, i - 1] = inv
        if i < m - 1:
            A[i, i + 1] = inv
    return A


def _periodic_symbol(n: int, h: float) -> np.ndarray:
    k = np.fft.fftfreq(n)
    return (2.0 * np.cos(2.0 * math.pi * k) - 2.0) / (h * h)


# plan-cached device-resident periodic axis plans: solver
# re-construction (regrids, level rebuilds) stops recomputing the
# symbol / eigendecomposition and every trace captures the SAME
# constants (the 1-D analog of solvers.spectral_plan.get_plan)
@functools.lru_cache(maxsize=64)
def _periodic_fft_plan_impl(n: int, h: float, x64: bool):
    return ("fft", jnp.asarray(_periodic_symbol(n, h)))


def _periodic_fft_plan(n: int, h: float):
    # keyed on the x64 mode: the cached jnp array's dtype follows the
    # mode at BUILD time, and a stale-mode plan would leak f64 (or f32)
    # constants into every later trace (see spectral_plan.plan_key)
    return _periodic_fft_plan_impl(n, h, bool(jax.config.jax_enable_x64))


@functools.lru_cache(maxsize=64)
def _periodic_eig_plan_impl(n: int, h: float, x64: bool):
    lam, V = np.linalg.eigh(laplacian_1d_periodic(n, h))
    return ("eig", jnp.asarray(V), jnp.asarray(lam))


def _periodic_eig_plan(n: int, h: float):
    return _periodic_eig_plan_impl(n, h, bool(jax.config.jax_enable_x64))


def laplacian_1d_periodic(n: int, h: float) -> np.ndarray:
    """Circulant 1D Laplacian (symmetric; its eigh basis is a real
    orthogonal Fourier basis — the dense-transform alternative to the
    FFT plan)."""
    eye = np.eye(n)
    return (-2.0 * eye + np.roll(eye, 1, axis=1)
            + np.roll(eye, -1, axis=1)) / (h * h)


class FastDiagSolver:
    """Separable Helmholtz solve (alpha + beta lap) Q = rhs on one grid,
    for one combination of per-axis (BC, centering)."""

    def __init__(self, grid: StaggeredGrid, bc: DomainBC,
                 centerings: Sequence[str], dense_periodic: bool = False):
        """``dense_periodic``: apply periodic axes as dense real-Fourier
        eigenbasis MATMULS instead of FFTs. Two reasons to choose it:
        (a) the MXU runs same-size dense transforms at full throughput
        and the SPMD partitioner distributes axis matmuls cleanly, and
        (b) XLA's fft thunk rejects the partitioned layouts a sharded
        composite solve produces (CPU "IsMonotonicWithDim0Major"
        RET_CHECK) — matmul transforms have no such restriction."""
        self.grid = grid
        self.bc = bc
        self.centerings = tuple(centerings)
        self.plans = []            # per axis: ("fft", lam) | ("eig", V, lam)
        for d, (axbc, cent) in enumerate(zip(bc.axes, self.centerings)):
            n, h = grid.n[d], grid.dx[d]
            if axbc.periodic and dense_periodic:
                self.plans.append(_periodic_eig_plan(int(n), float(h)))
            elif axbc.periodic:
                self.plans.append(_periodic_fft_plan(int(n), float(h)))
            elif cent == "cc":
                lam, V = np.linalg.eigh(laplacian_1d_cc(n, h, axbc))
                self.plans.append(("eig", jnp.asarray(V), jnp.asarray(lam)))
            elif cent == "fc_pinned":
                lam, V = np.linalg.eigh(laplacian_1d_fc_pinned(n, h))
                self.plans.append(("eig", jnp.asarray(V), jnp.asarray(lam)))
            else:
                raise ValueError(f"unknown centering {cent!r}")

    # -- helpers -------------------------------------------------------------
    def _axis_matmul(self, x: jnp.ndarray, M: jnp.ndarray,
                     axis: int) -> jnp.ndarray:
        """Apply M (m_out, m_in) along ``axis`` of x, to the accuracy of
        x's own dtype: on a TPU a float32 product without a stated
        precision is ONE bfloat16 pass, and an eigenvector transform
        with bfloat16 operands loses the exact projection (div u = 0
        to rounding) at about 1e-3."""
        moved = jnp.moveaxis(x, axis, -1)
        out = jnp.tensordot(moved, M.astype(moved.dtype), axes=([-1], [1]),
                            precision=jax.lax.Precision.HIGHEST)
        return jnp.moveaxis(out, -1, axis)

    def _interior(self, x: jnp.ndarray) -> Tuple[jnp.ndarray, list]:
        """Slice off pinned boundary faces; remember which axes."""
        pinned = [d for d, c in enumerate(self.centerings)
                  if c == "fc_pinned" and not self.bc.axes[d].periodic]
        idx = [slice(None)] * x.ndim
        for d in pinned:
            idx[d] = slice(1, None)
        return x[tuple(idx)], pinned

    def solve(self, rhs: jnp.ndarray, alpha, beta,
              zero_nullspace: bool = False) -> jnp.ndarray:
        """Solve (alpha + beta lap) Q = rhs. With alpha == 0 and an
        all-Neumann/periodic problem set ``zero_nullspace`` to project
        out the constant mode (periodic-Poisson compatibility analog).

        The axis transforms sit under the phase ``transforms``
        (``jax.named_scope``: metadata only), the diagonal divide
        outside it; which kind a traced solve made is counted
        (``fluid_transform_{dense,fft}_axes_total``) and told to the
        ``driver/chunk`` span whose call traced it
        (``transform_path``)."""
        x, pinned = self._interior(rhs)
        rdt = x.dtype
        cdt = jnp.complex128 if rdt == jnp.float64 else jnp.complex64
        dim = x.ndim
        n_fft = sum(p[0] == "fft" for p in self.plans)
        n_eig = len(self.plans) - n_fft
        any_fft = n_fft > 0
        _DENSE_AXES_TOTAL.inc(2 * n_eig)
        _FFT_AXES_TOTAL.inc(2 * n_fft)
        obs.annotate("driver/chunk", transform_path=(
            "mixed" if n_eig and n_fft else "fft" if n_fft else "dense"))

        # forward eig transforms (real), then FFTs (complex)
        with jax.named_scope("transforms"):
            for d, plan in enumerate(self.plans):
                if plan[0] == "eig":
                    x = self._axis_matmul(x, plan[1].T, d)
            if any_fft:
                x = x.astype(cdt)
                for d, plan in enumerate(self.plans):
                    if plan[0] == "fft":
                        x = jnp.fft.fft(x, axis=d)

        # diagonal solve
        sym = jnp.zeros((), dtype=rdt)
        for d, plan in enumerate(self.plans):
            lam = plan[1] if plan[0] == "fft" else plan[2]
            shape = [1] * dim
            shape[d] = lam.shape[0]
            sym = sym + lam.reshape(shape).astype(rdt)
        denom = alpha + beta * sym
        if zero_nullspace:
            # eigh-computed nullspace eigenvalues are ~1e-13, never an
            # exact 0 — a strict equality test would divide the constant
            # mode by roundoff (observed: f32 pressures of O(1e6)).
            # Threshold relative to the operator's spectral radius.
            tol = 1e-8 * jnp.max(jnp.abs(sym))
            null = jnp.abs(denom) <= tol
            safe = jnp.where(null, 1.0, denom)
            x = jnp.where(null, 0.0, x / safe)
        else:
            x = x / denom

        # inverse transforms
        with jax.named_scope("transforms"):
            if any_fft:
                for d, plan in enumerate(self.plans):
                    if plan[0] == "fft":
                        x = jnp.fft.ifft(x, axis=d)
                x = jnp.real(x).astype(rdt)
            for d, plan in enumerate(self.plans):
                if plan[0] == "eig":
                    x = self._axis_matmul(x, plan[1], d)

        # re-attach pinned faces as zeros (homogeneous walls)
        for d in pinned:
            pad = [(0, 0)] * dim
            pad[d] = (1, 0)
            x = jnp.pad(x, pad)
        return x
