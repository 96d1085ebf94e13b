"""Matrix-free Krylov solvers over pytrees, jit/scan-native.

Reference parity: the IBTK operator/solver framework (T6) — matrix-free
``LinearOperator`` + ``PETScKrylovLinearSolver`` (KSP wrappers) — rebuilt
the TPU way: the operator is any pytree->pytree callable; iteration is a
``lax.while_loop`` so the whole solve compiles into the step function; the
global dot products are ``jnp`` reductions that XLA lowers to ``psum``
collectives under sharding (the analog of the reference's MPI-reduced
VecDot, SURVEY.md §2.4).

Solvers: preconditioned conjugate gradient (SPD systems: Poisson/Helmholtz
with general BCs, CIB mobility) and BiCGStab (mildly nonsymmetric systems).
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from ibamr_tpu.ops.norms import tree_dot, tree_dots  # noqa: E402  (shared)

Pytree = Any
Operator = Callable[[Pytree], Pytree]


def _gnorm(v) -> jnp.ndarray:
    """Global l2 norm under the ``comm`` named scope (the norms.py
    ``_reduce`` discipline): under sharding the reduction lowers to a
    psum, and the scope is what attributes that collective to the comm
    op-class in device profiles instead of ``unattributed``."""
    with jax.named_scope("comm"):
        return jnp.linalg.norm(v)


def tree_axpy(alpha, x: Pytree, y: Pytree) -> Pytree:
    """alpha * x + y"""
    return jax.tree_util.tree_map(lambda xi, yi: alpha * xi + yi, x, y)


def tree_scale(alpha, x: Pytree) -> Pytree:
    return jax.tree_util.tree_map(lambda xi: alpha * xi, x)


def tree_sub(x: Pytree, y: Pytree) -> Pytree:
    return jax.tree_util.tree_map(lambda xi, yi: xi - yi, x, y)


class SolveResult(NamedTuple):
    x: Pytree
    iters: jnp.ndarray      # iterations taken
    resnorm: jnp.ndarray    # final |r|_2 (unweighted l2)
    converged: jnp.ndarray  # bool


def cg(A: Operator, b: Pytree, x0: Optional[Pytree] = None,
       M: Optional[Operator] = None, tol: float = 1e-6,
       atol: float = 0.0, maxiter: int = 100) -> SolveResult:
    """Preconditioned conjugate gradient for SPD A (matrix-free).

    Stops when |r| <= max(tol*|b|, atol). ``M`` applies the preconditioner
    inverse (M ~ A^{-1}). Fully traceable: usable inside jit/scan.
    """
    if x0 is None:
        x0 = jax.tree_util.tree_map(jnp.zeros_like, b)
    if M is None:
        M = lambda r: r  # noqa: E731

    bnorm = jnp.sqrt(tree_dot(b, b))
    stop = jnp.maximum(tol * bnorm, atol)

    r0 = tree_sub(b, A(x0))
    z0 = M(r0)
    p0 = z0
    # one fused reduction for the (r,z)/(r,r) pair — one psum of a
    # (2,) vector under sharding instead of two scalar syncs
    rz0, rn0sq = tree_dots([(r0, z0), (r0, r0)])
    rn0 = jnp.sqrt(rn0sq)

    # Finite-precision divergence guard: when ``tol`` is below the
    # dtype's reachable floor (an f32 solve asked for 1e-9), the
    # recurred residual bottoms out at roundoff and further iterations
    # LOSE conjugacy — the iterate can then wander arbitrarily far
    # (observed: div 1e10 from the VC projection in f32). Track the
    # best iterate seen; stop once the residual has grown far past the
    # best (the run is diverging, not converging); return the BEST
    # iterate when the solve did not converge. Converged solves return
    # the final iterate exactly as before (bitwise-identical path).
    def cond(st):
        x, r, z, p, rz, k, rn, xb, rb = st
        ok = jnp.logical_and(k < maxiter, rn > stop)
        return jnp.logical_and(ok, rn <= 1e4 * rb)

    def body(st):
        x, r, z, p, rz, k, _, xb, rb = st
        Ap = A(p)
        pAp = tree_dot(p, Ap)
        # guard against breakdown (pAp ~ 0 when r ~ 0)
        alpha = jnp.where(pAp > 0, rz / jnp.where(pAp == 0, 1.0, pAp), 0.0)
        x = tree_axpy(alpha, p, x)
        r = tree_axpy(-alpha, Ap, r)
        z = M(r)
        # fused (r,z)/(r,r) reduction: one collective sync per
        # iteration where there were two (values unchanged — each row
        # reduces the same elements in the same order)
        rz_new, rnsq = tree_dots([(r, z), (r, r)])
        beta = jnp.where(rz > 0, rz_new / jnp.where(rz == 0, 1.0, rz), 0.0)
        p = tree_axpy(beta, p, z)
        rn = jnp.sqrt(rnsq)              # carried: cond reuses it
        better = rn < rb
        xb = jax.tree_util.tree_map(
            lambda a_, b_: jnp.where(better, a_, b_), x, xb)
        rb = jnp.minimum(rb, rn)
        return (x, r, z, p, rz_new, k + 1, rn, xb, rb)

    x, r, _, _, _, k, rn, xb, rb = jax.lax.while_loop(
        cond, body, (x0, r0, z0, p0, rz0, jnp.asarray(0), rn0, x0, rn0))
    converged = rn <= stop
    use_best = jnp.logical_and(~converged, rb < rn)
    x = jax.tree_util.tree_map(
        lambda a_, b_: jnp.where(use_best, a_, b_), xb, x)
    rn = jnp.where(use_best, rb, rn)
    return SolveResult(x=x, iters=k, resnorm=rn, converged=converged)


def bicgstab(A: Operator, b: Pytree, x0: Optional[Pytree] = None,
             M: Optional[Operator] = None, tol: float = 1e-6,
             atol: float = 0.0, maxiter: int = 200) -> SolveResult:
    """Right-preconditioned BiCGStab for general (nonsymmetric) A."""
    if x0 is None:
        x0 = jax.tree_util.tree_map(jnp.zeros_like, b)
    if M is None:
        M = lambda r: r  # noqa: E731

    bnorm = jnp.sqrt(tree_dot(b, b))
    stop = jnp.maximum(tol * bnorm, atol)

    r0 = tree_sub(b, A(x0))
    rhat = r0
    one = jnp.asarray(1.0, dtype=jnp.result_type(*jax.tree_util.tree_leaves(b)))
    rn0 = jnp.sqrt(tree_dot(r0, r0))

    # Same finite-precision divergence guard as ``cg`` (round 4), which
    # BiCGStab never received: its recurred residual is even less
    # trustworthy than CG's (the stabilizer omega can all but vanish),
    # so below the dtype floor the iterate wanders while the recurrence
    # reports progress. Carry the best iterate; stop once the residual
    # has grown far past the best; return the BEST iterate only when
    # the solve did not converge — converged solves keep the exact
    # pre-guard path (bitwise-identical result).
    def cond(st):
        x, r, p, v, rho, alpha, omega, k, rn, xb, rb = st
        ok = jnp.logical_and(k < maxiter, rn > stop)
        return jnp.logical_and(ok, rn <= 1e4 * rb)

    def body(st):
        x, r, p, v, rho, alpha, omega, k, _, xb, rb = st
        rho_new = tree_dot(rhat, r)
        denom = jnp.where(rho * omega == 0, 1.0, rho * omega)
        beta = (rho_new / denom) * (alpha / jnp.where(omega == 0, 1.0, omega))
        p = tree_axpy(beta, tree_axpy(-omega, v, p), r)
        phat = M(p)
        v = A(phat)
        rhv = tree_dot(rhat, v)
        alpha = rho_new / jnp.where(rhv == 0, 1.0, rhv)
        s = tree_axpy(-alpha, v, r)
        shat = M(s)
        t = A(shat)
        # fused (t,t)/(t,s) reduction: one collective sync, not two
        tt, ts = tree_dots([(t, t), (t, s)])
        omega = ts / jnp.where(tt == 0, 1.0, tt)
        x = tree_axpy(alpha, phat, tree_axpy(omega, shat, x))
        r = tree_axpy(-omega, t, s)
        rn = jnp.sqrt(tree_dot(r, r))    # carried: cond reuses it
        better = rn < rb
        xb = jax.tree_util.tree_map(
            lambda a_, b_: jnp.where(better, a_, b_), x, xb)
        rb = jnp.minimum(rb, rn)
        return (x, r, p, v, rho_new, alpha, omega, k + 1, rn, xb, rb)

    zeros = jax.tree_util.tree_map(jnp.zeros_like, b)
    x, r, _, _, _, _, _, k, rn, xb, rb = jax.lax.while_loop(
        cond, body, (x0, r0, zeros, zeros, one, one, one,
                     jnp.asarray(0), rn0, x0, rn0))
    converged = rn <= stop
    use_best = jnp.logical_and(~converged, rb < rn)
    x = jax.tree_util.tree_map(
        lambda a_, b_: jnp.where(use_best, a_, b_), xb, x)
    rn = jnp.where(use_best, rb, rn)
    return SolveResult(x=x, iters=k, resnorm=rn, converged=converged)


# ---------------------------------------------------------------------------
# FGMRES + Newton-Krylov (T6 completion: the reference's
# PETScKrylovLinearSolver FGMRES default + PETScNewtonKrylovSolver/SNES
# with matrix-free MFFD Jacobians — SURVEY.md §2.1 T6)
# ---------------------------------------------------------------------------

def _ravel(pytree):
    from jax.flatten_util import ravel_pytree

    flat, unravel = ravel_pytree(pytree)
    return flat, unravel


# the Arnoldi products' stated precision: a float32 product with none is
# ONE bfloat16 pass on the TPU's matrix unit, which rounds the basis and
# its coefficients to an 8-bit mantissa where the solve promises float32;
# on the CPU the statement changes nothing
_HIGHEST = jax.lax.Precision.HIGHEST


def _fgmres_flat(Aop, b, x0, Mop, m, tol, atol, restarts):
    """Flexible right-preconditioned GMRES(m) on flat vectors.

    TPU-first formulation: the Krylov basis is one (m+1, n) matrix, so
    orthogonalization is two matmuls per Arnoldi step (all candidate
    dots at once + rank-1 basis combination) instead of a data-dependent
    inner loop — MXU-friendly and fully lax-traceable. Every restart
    runs all ``m`` Arnoldi iterations, so a solve makes ``m`` times its
    restart count of them.

    Phase names (``jax.named_scope``, metadata only): the whole solve is
    ``krylov``; inside it ``operator`` (the applications of ``Aop``),
    ``precond`` (of ``Mop``) and ``arnoldi`` (CGS2, the norms and the
    basis writes); the small least-squares solve and the update of ``x``
    sit under ``krylov`` alone.
    """
    with jax.named_scope("krylov"):
        return _fgmres_scoped(Aop, b, x0, Mop, m, tol, atol, restarts)


def _fgmres_scoped(Aop, b, x0, Mop, m, tol, atol, restarts):
    """:func:`_fgmres_flat` under its ``krylov`` scope."""
    scope = jax.named_scope
    n = b.shape[0]
    dtype = b.dtype
    bnorm = _gnorm(b)
    stop = jnp.maximum(tol * bnorm, atol)

    def restart_body(carry):
        x, _, it = carry
        with scope("operator"):
            r = b - Aop(x)
        with scope("arnoldi"):
            beta = _gnorm(r)
            beta_safe = jnp.where(beta == 0, 1.0, beta)
            V0 = jnp.zeros((m + 1, n), dtype=dtype).at[0].set(r / beta_safe)
        Z0 = jnp.zeros((m, n), dtype=dtype)
        H0 = jnp.zeros((m + 1, m), dtype=dtype)

        def arnoldi(j, st):
            V, Z, H = st
            v = V[j]
            with scope("precond"):
                z = Mop(v)
            with scope("operator"):
                w = Aop(z)
            # classical Gram-Schmidt with reorthogonalization (CGS2):
            # two batched-dot + rank-k-update rounds keep the basis
            # orthogonal to working precision (important in f32) while
            # staying all-matmul for the MXU
            with scope("arnoldi"):
                mask = (jnp.arange(m + 1) <= j).astype(dtype)
                dots = jnp.dot(V, w, precision=_HIGHEST) * mask
                w = w - jnp.dot(V.T, dots, precision=_HIGHEST)
                dots2 = jnp.dot(V, w, precision=_HIGHEST) * mask
                w = w - jnp.dot(V.T, dots2, precision=_HIGHEST)
                wnorm = _gnorm(w)
                H = H.at[:, j].set(dots + dots2).at[j + 1, j].set(wnorm)
                V = V.at[j + 1].set(w / jnp.where(wnorm == 0, 1.0, wnorm))
                Z = Z.at[j].set(z)
            return V, Z, H

        V, Z, H = jax.lax.fori_loop(0, m, arnoldi, (V0, Z0, H0))
        e1 = jnp.zeros(m + 1, dtype=dtype).at[0].set(beta)
        # rcond = raw machine eps, NOT jax's default eps*max(m,n):
        # a strongly-scaled preconditioner (e.g. the Stokes Schur
        # proxy) inflates sigma_max, and the default cutoff then
        # truncates the small-but-essential singular direction --
        # observed as an f32 FGMRES that makes ZERO progress. True
        # breakdown columns (converged early) still fall below eps.
        y, *_ = jnp.linalg.lstsq(H, e1, rcond=float(jnp.finfo(dtype).eps))
        # an exactly-zero restart residual (projecting an already
        # div-free field, the zero-state initialize) makes H all-zero,
        # and lstsq of an all-zero matrix returns NaN here (0/0 in the
        # SVD-based solve); true breakdown columns can do the same. A
        # non-finite y entry carries no descent information — drop it
        # (keeping x unchanged along that direction is exact).
        y = jnp.where(jnp.isfinite(y), y, jnp.zeros_like(y))
        x = x + jnp.dot(Z.T, y, precision=_HIGHEST)
        with scope("operator"):
            r = b - Aop(x)
        with scope("arnoldi"):
            rn = _gnorm(r)
        return x, rn, it + 1

    def cond(carry):
        _, rn, it = carry
        return jnp.logical_and(it < restarts, rn > stop)

    x, rn, it = jax.lax.while_loop(
        cond, restart_body,
        (x0, jnp.asarray(jnp.inf, dtype=dtype), jnp.asarray(0)))
    return x, rn, it


def fgmres(A: Operator, b: Pytree, x0: Optional[Pytree] = None,
           M: Optional[Operator] = None, m: int = 30,
           tol: float = 1e-6, atol: float = 0.0,
           restarts: int = 10) -> SolveResult:
    """Flexible GMRES(m) over pytrees (general nonsymmetric systems;
    the preconditioner may itself be an inner iteration). ``iters`` of
    the result counts restart cycles, each of ``m`` Arnoldi
    iterations."""
    bflat, unravel = _ravel(b)
    if x0 is None:
        x0flat = jnp.zeros_like(bflat)
    else:
        x0flat, _ = _ravel(x0)

    def Aop(v):
        out, _ = _ravel(A(unravel(v)))
        return out

    if M is None:
        Mop = lambda v: v  # noqa: E731
    else:
        def Mop(v):
            out, _ = _ravel(M(unravel(v)))
            return out

    x, rn, it = _fgmres_flat(Aop, bflat, x0flat, Mop, m, tol, atol,
                             restarts)
    bnorm = _gnorm(bflat)
    stop = jnp.maximum(tol * bnorm, atol)
    return SolveResult(x=unravel(x), iters=it, resnorm=rn,
                       converged=rn <= stop)


class NewtonResult(NamedTuple):
    x: Pytree
    iters: jnp.ndarray
    resnorm: jnp.ndarray
    converged: jnp.ndarray


def newton_krylov(F: Operator, x0: Pytree, tol: float = 1e-8,
                  atol: float = 0.0, maxiter: int = 10,
                  inner_m: int = 20, inner_restarts: int = 2,
                  inner_tol: float = 1e-3) -> NewtonResult:
    """Matrix-free Newton-Krylov: solve F(x) = 0 with exact JVP
    Jacobians (jax.jvp — sharper than the reference's MFFD finite
    differencing) and FGMRES inner solves. Fully lax-traceable, so an
    implicit integrator can run it inside jit/scan.
    """
    x0flat, unravel = _ravel(x0)

    def Fflat(v):
        out, _ = _ravel(F(unravel(v)))
        return out

    f0 = Fflat(x0flat)
    fnorm0 = _gnorm(f0)
    stop = jnp.maximum(tol * jnp.maximum(fnorm0, 1e-30), atol)

    def cond(carry):
        _, fnorm, it = carry
        return jnp.logical_and(it < maxiter, fnorm > stop)

    def body(carry):
        x, fnorm, it = carry
        # one primal evaluation yields both the residual and the
        # tangent-only Jacobian map (cheaper than jax.jvp inside the
        # Arnoldi loop, which would re-trace the primal every iteration)
        fx, Jop = jax.linearize(Fflat, x)

        dx, _, _ = _fgmres_flat(Jop, -fx, jnp.zeros_like(x),
                                lambda v: v, inner_m, inner_tol, 0.0,
                                inner_restarts)

        # backtracking line search (the SNES 'bt' analog): halve the
        # step until the residual norm decreases, tracking the BEST
        # candidate seen — when no scale decreases (inexact Jacobian
        # solve, kinked residual) taking the least-bad step keeps the
        # iteration from wandering. All comparisons are written so a
        # NaN/inf trial norm counts as NOT improved (NaN-safe).
        def ls_cond(c):
            s, fn, bs, bfn, tries = c
            improved = fn < fnorm
            return jnp.logical_and(tries < 6,
                                   jnp.logical_not(improved))

        def ls_body(c):
            s, _, bs, bfn, tries = c
            s = s * 0.5
            fn = _gnorm(Fflat(x + s * dx))
            better = fn < bfn                      # False for NaN fn
            bs = jnp.where(better, s, bs)
            bfn = jnp.where(better, fn, bfn)
            return s, fn, bs, bfn, tries + 1

        fn_full = _gnorm(Fflat(x + dx))
        one = jnp.asarray(1.0, dtype=x.dtype)
        full_ok = jnp.isfinite(fn_full)
        bs0 = jnp.where(full_ok, one, one / 64.0)  # NaN full step: tiny
        bfn0 = jnp.where(full_ok, fn_full,
                         jnp.asarray(jnp.inf, dtype=x.dtype))
        _, _, s, fn, _ = jax.lax.while_loop(
            ls_cond, ls_body, (one, fn_full, bs0, bfn0,
                               jnp.asarray(0)))
        x = x + s * dx
        return x, fn, it + 1

    x, fnorm, it = jax.lax.while_loop(
        cond, body, (x0flat, fnorm0, jnp.asarray(0)))
    return NewtonResult(x=unravel(x), iters=it, resnorm=fnorm,
                        converged=fnorm <= stop)
