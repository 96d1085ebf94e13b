"""Spectral-plan layer: hash-cons-cached symbol tables + the k-space-
resident fused fluid substep.

Round-5 measurement (PERF.md; rev 96498b2, capture file removed) put
``fluid_solve`` at 39.3 ms — the dominant flagship phase once the
transfer-side levers landed. The remaining structural waste was not in
the transforms themselves (the fused substep already runs ONE batched
rfftn and ONE batched irfftn) but around them:

- every spectral solve recomputed its symbol tables (`laplacian_symbol`,
  the staggered divergence symbols) per call/trace — regrids and solver
  re-construction paid the rebuild over and over;
- the transform operands were pinned to f32 with no opt-in cheaper
  precision, even though bf16 operand compression is exactly the trade
  the ``packed_bf16`` transfer engine already sells.

A :class:`SpectralPlan` precomputes the tables ONCE per
``(shape, dx, dtype, bc)`` and hash-conses them in a bounded LRU
(:func:`get_plan`), device-resident, so every spectral solve — the
fused substep, Poisson, Helmholtz, the all-periodic saddle solve —
shares one set of constants. The fused :meth:`SpectralPlan.substep`
performs the viscous Helmholtz solve, the staggered Leray projection,
the pressure-increment assembly AND an optional body-force spectral
filter as ONE batched forward rfftn -> diagonal k-space algebra -> ONE
batched inverse irfftn. ``spectral_dtype`` opts into the mixed-precision
transform path: bf16/split-real transform OPERANDS (the real input
batch and the split-real spectral intermediate are rounded through the
storage dtype) with f32 twiddle factors and f32 accumulation inside the
transform — the accuracy contract is tolerance-pinned against the f64
oracle in tests/test_spectral_plan.py, exactly like ``packed_bf16``.

The default-precision path is BITWISE identical to the pre-plan
implementation (same ops in the same order; the cached tables are built
by the same ``fft.laplacian_symbol`` / ``fft._staggered_div_symbols``
calls), so trajectories and restart files are unchanged.
"""

from __future__ import annotations

import contextlib
import functools
import threading
from collections import OrderedDict
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

Vel = Tuple[jnp.ndarray, ...]

# Reverse-mode policy for the fused substep (PR 19). The default custom
# VJP treats the Helmholtz/pressure coefficients (alpha, beta,
# pinc_coeffs) and filter_sym as non-differentiated constants — design
# variables flow through the RHS fields, and the cotangent pass is the
# SAME plan with conjugated symbols (one batched rfftn + one batched
# irfftn, zero saved spectra). Set this True to fall back to plain
# autodiff when a caller genuinely needs d/d(alpha) or d/d(beta)
# (e.g. differentiating through an adaptive dt); that path re-derives
# the chain rule through the k-space algebra and is NOT covered by the
# ``grad_substep`` graph budget.
DIFFERENTIATE_COEFFS = False


# -- transforms that are right on the chip ------------------------------------
#
# On a TPU v5e (libtpu 0.0.34, PR 23 chip runs) two spellings return
# WRONG values at 256^3 while every piece of them agrees with numpy to
# 4e-7 (64^3 and 128^3 are right, and so is the CPU at every size):
#
# - ONE rank-3 inverse real transform: 30% of max|x| off on 99% of the
#   elements, different from call to call. A rank-1 inverse over the
#   leading axis, then the rank-2 inverse real transform, is right —
#   but only with a barrier between them: without it XLA merges the two
#   back into the rank-3 op (same wrong bits).
# - transforms BATCHED over the stacked fields (3 forward, 4 inverse)
#   whose operand was computed and not a program argument: 35% off,
#   the same wrong values whether spelled rank-3, rank-1+rank-2 or as
#   rank-1 passes with barriers. One field at a time is right.
#
# They left the flagship with max|div u| = 80 after 20 steps (scatter
# engine, one step: 62; through the per-field chained solves: 3e-4).
# So on that backend, rank-3 transforms go one field at a time and the
# inverse is split. Same transforms, same FLOPs, more launches. Other
# backends and ranks keep the single batched call: their graphs, bits
# and FFT budgets do not change.

def _chip_rank3(s: Sequence[int]) -> bool:
    return len(s) == 3 and jax.default_backend() == "tpu"


def rfftn(x: jnp.ndarray, s: Sequence[int],
          axes: Optional[Sequence[int]] = None) -> jnp.ndarray:
    """``jnp.fft.rfftn`` over the trailing ``len(s)`` axes of a field
    or of a stack of fields (see the note above)."""
    if _chip_rank3(s) and x.ndim == 4:
        return jnp.stack([jnp.fft.rfftn(jax.lax.optimization_barrier(c))
                          for c in x])
    return jnp.fft.rfftn(x, axes=axes)


def irfftn(xh: jnp.ndarray, s: Sequence[int],
           axes: Optional[Sequence[int]] = None) -> jnp.ndarray:
    """``jnp.fft.irfftn`` over the trailing ``len(s)`` axes of a
    spectrum or of a stack of spectra (see the note above)."""
    if not _chip_rank3(s):
        return jnp.fft.irfftn(xh, s=s, axes=axes)
    if xh.ndim == 4:
        return jnp.stack([irfftn(c, s) for c in xh])
    barrier = jax.lax.optimization_barrier
    y = barrier(jnp.fft.ifft(barrier(xh), axis=0))
    return jnp.fft.irfftn(y, s=tuple(s[1:]), axes=(1, 2))


@contextlib.contextmanager
def plain_autodiff_substep():
    """Trace-scoped opt-out of the fused substep's custom VJP.

    ``jax.custom_vjp`` refuses forward-mode autodiff (jvp/linearize);
    graphs that linearize through the fluid solve — the implicit
    Newton-Krylov residual folds an INS step into every evaluation —
    must trace inside this context, which routes ``substep`` through
    the raw k-space algebra (both autodiff modes supported natively;
    coefficient gradients become available; the ``grad_substep``
    budget does not apply to graphs traced this way)."""
    global DIFFERENTIATE_COEFFS
    prev = DIFFERENTIATE_COEFFS
    DIFFERENTIATE_COEFFS = True
    try:
        yield
    finally:
        DIFFERENTIATE_COEFFS = prev

# -- spectral_dtype normalization -------------------------------------------

_SPECTRAL_DTYPE_ALIASES = {
    None: None, "none": None, "f32": None, "float32": None,
    "bf16": jnp.bfloat16, "bfloat16": jnp.bfloat16,
    "f64": jnp.float64, "float64": jnp.float64,
}


def canonical_spectral_dtype(spec):
    """Normalize the ``spectral_dtype`` knob: ``None`` (native working
    precision, f32 by convention), ``jnp.bfloat16`` (compressed
    transform operands), or ``jnp.float64`` (escalated: the whole
    substep runs on the f64 twin plan — the precision-escalation chain's
    last link and the shadow audit's reference). Anything else is a
    typo'd input file and raises.

    Note: under a runtime without x64 enabled the f64 request
    canonicalizes to f32 at plan-build time (jax's standard dtype
    demotion) — the knob is then a no-op, not an error."""
    if isinstance(spec, str):
        key = spec.lower()
        if key in _SPECTRAL_DTYPE_ALIASES:
            return _SPECTRAL_DTYPE_ALIASES[key]
        raise ValueError(
            f"spectral_dtype = {spec!r}: expected one of "
            f"{sorted(k for k in _SPECTRAL_DTYPE_ALIASES if k)} or None")
    if spec is None or spec is jnp.bfloat16 or spec is jnp.float64:
        return spec
    if jnp.dtype(spec) == jnp.dtype(jnp.bfloat16):
        return jnp.bfloat16
    if jnp.dtype(spec) == jnp.dtype(jnp.float64):
        return jnp.float64
    raise ValueError(f"spectral_dtype = {spec!r}: only bf16 operand "
                     "compression or f64 escalation is supported "
                     "(None = native precision)")


def _round_real(x: jnp.ndarray, sdtype) -> jnp.ndarray:
    """Round a real transform operand through the storage dtype; the
    transform itself still runs at f32 (f32 twiddle/accumulation)."""
    return x.astype(sdtype).astype(jnp.float32)


def _round_complex(z: jnp.ndarray, sdtype) -> jnp.ndarray:
    """Split-real rounding of a spectral operand: the re/im planes are
    rounded through the storage dtype independently (complex-bf16 does
    not exist as a device type; split-real IS the storage layout)."""
    re = jnp.real(z).astype(sdtype).astype(jnp.float32)
    im = jnp.imag(z).astype(sdtype).astype(jnp.float32)
    return jax.lax.complex(re, im)


# -- the plan ----------------------------------------------------------------

class SpectralPlan:
    """Device-resident spectral symbol tables for one
    ``(shape, dx, dtype, bc)`` and the solves that share them.

    Construct via :func:`get_plan` (the hash-cons cache), not directly —
    direct construction bypasses the LRU and recomputes the tables the
    cache exists to share.
    """

    def __init__(self, shape: Sequence[int], dx: Sequence[float],
                 dtype, bc: str = "periodic"):
        if bc != "periodic":
            raise ValueError(
                f"SpectralPlan bc={bc!r}: only 'periodic' has a "
                "diagonal spectral symbol (walls go through "
                "solvers.fastdiag / solvers.stokes)")
        # table builders live in solvers.fft (the canonical symbol
        # definitions); imported lazily because fft delegates its fused
        # substep back to this module
        from ibamr_tpu.solvers import fft

        self.shape = tuple(int(s) for s in shape)
        self.dx = tuple(float(h) for h in dx)
        self.bc = bc
        self.dim = len(self.shape)
        # batched-transform axes for a leading stack dimension
        self.axes = tuple(range(1, self.dim + 1))
        self.rdtype = jax.dtypes.canonicalize_dtype(dtype)
        self.cdtype = jnp.complex128 if self.rdtype == jnp.float64 \
            else jnp.complex64
        # the tables: discrete-Laplacian symbol on the rfftn grid and
        # the per-axis staggered divergence symbols. Built by the same
        # fft.py code the unplanned solves used, so values are bitwise
        # identical to a per-call rebuild. ensure_compile_time_eval:
        # the first get_plan for a shape often fires INSIDE a jit
        # trace — the tables must come out as concrete device arrays,
        # not tracers, or the hash-cons cache would leak trace-scoped
        # values into every later caller.
        with jax.ensure_compile_time_eval():
            self.sym = fft.laplacian_symbol(self.shape, self.dx,
                                            self.rdtype)
            self.D = fft._staggered_div_symbols(self.shape, self.dx,
                                                self.cdtype)
            if self.rdtype != jnp.float32:
                # pre-materialized f32 views for the bf16 transform
                # path (f32 twiddle/accumulation)
                self._sym_f32 = self.sym.astype(jnp.float32)
                self._D_f32 = tuple(d.astype(jnp.complex64)
                                    for d in self.D)
            else:
                self._sym_f32 = self.sym
                self._D_f32 = self.D

    # -- table views ---------------------------------------------------------
    def _tables(self, f32: bool):
        """(sym, D) at the working precision: the plan's native dtype,
        or the f32 view the bf16 transform path computes in."""
        if not f32:
            return self.sym, self.D
        return self._sym_f32, self._D_f32

    # -- fused substep (the tentpole) ----------------------------------------
    def substep(self, rhs: Vel, alpha, beta,
                pinc_coeffs: Tuple[float, float],
                spectral_dtype=None,
                filter_sym: Optional[jnp.ndarray] = None
                ) -> Tuple[Vel, jnp.ndarray]:
        """K-space-resident fused Stokes substep.

        ONE batched forward rfftn over the stacked MAC rhs, then the
        whole chain as diagonal spectral algebra — Helmholtz inverse
        ``(alpha + beta lap)^{-1}``, optional body-force spectral
        filter ``filter_sym`` (a real symbol multiplied into the rhs
        spectrum: dealiasing masks, Gaussian force smoothing — zero
        extra transforms), staggered Leray projection, and the
        pressure-increment assembly ``p_inc = (a + b lap) phi0`` for
        ``pinc_coeffs = (a, b)`` — then ONE batched inverse irfftn for
        the ``dim + 1`` outputs.

        ``spectral_dtype=jnp.bfloat16`` rounds the transform operands
        (real input batch, split-real spectral intermediate) through
        bf16 while all twiddle factors, k-space tables and accumulation
        stay f32. Returns ``(u_new, p_inc)``; with the default
        precision ``u_new`` is divergence-free to roundoff.
        """
        sdtype = canonical_spectral_dtype(spectral_dtype)
        rdtype = self.rdtype
        if sdtype is jnp.float64:
            # escalated precision: run the WHOLE substep on the f64
            # twin plan (tables, transforms and algebra all at f64) and
            # cast the outputs back to the caller's working dtype. This
            # is the precision-escalation chain's last link and the
            # shadow audit's reference path.
            if rdtype == jnp.float64:
                return self.substep(rhs, alpha, beta, pinc_coeffs,
                                    spectral_dtype=None,
                                    filter_sym=filter_sym)
            plan64 = get_plan(self.shape, self.dx, jnp.float64, self.bc)
            u64, p64 = plan64.substep(
                tuple(c.astype(plan64.rdtype) for c in rhs),
                alpha, beta, pinc_coeffs, spectral_dtype=None,
                filter_sym=filter_sym)
            return (tuple(c.astype(rdtype) for c in u64),
                    p64.astype(rdtype))
        a, b = pinc_coeffs
        sdtype_name = "bf16" if sdtype is jnp.bfloat16 else "none"
        # strongly type concrete coefficients HERE, at trace time: a
        # weak python float crossing the custom_vjp boundary becomes a
        # convert_element_type op per scalar in the compiled graph (the
        # convert budgets pin the substep at its pre-VJP count). Traced
        # coefficients (dt under grad) pass through untouched.
        wdtype = jnp.float32 if sdtype is not None else self.rdtype
        alpha, beta, a, b = (
            v if isinstance(v, jax.core.Tracer) else jnp.asarray(v, wdtype)
            for v in (alpha, beta, a, b))
        if DIFFERENTIATE_COEFFS:
            # opt-out: plain autodiff through the raw math (coefficient
            # cotangents available, gradient cost unbudgeted)
            return _substep_raw(self, sdtype_name, tuple(rhs),
                                alpha, beta, a, b, filter_sym)
        return _substep_core(self, sdtype_name, tuple(rhs),
                             alpha, beta, a, b, filter_sym)

    def kspace_algebra(self, uh: jnp.ndarray, alpha, beta,
                       pinc_coeffs: Tuple[float, float],
                       f32: bool = False,
                       filter_sym: Optional[jnp.ndarray] = None
                       ) -> jnp.ndarray:
        """The diagonal spectral algebra between the substep's two
        transforms: ``uh`` is the stacked forward spectrum of the dim
        MAC components; returns the stacked dim+1 inverse-transform
        operand. Exposed separately so bench.py can time the
        transform-vs-algebra split of the fluid phase."""
        dim = self.dim
        sym, D = self._tables(f32=f32)
        wdtype = jnp.float32 if f32 else self.rdtype
        cdtype = uh.dtype
        if filter_sym is not None:
            uh = uh * filter_sym.astype(wdtype)[None]
        denom = (alpha + beta * sym).astype(wdtype)
        uh = uh / denom[None]
        divh = None
        for d in range(dim):
            t = D[d] * uh[d]
            divh = t if divh is None else divh + t
        sym_safe = jnp.where(sym == 0, 1.0, sym)
        phih = jnp.where(sym == 0, 0.0, divh / sym_safe)
        a, b = pinc_coeffs
        return jnp.stack(
            [uh[d] + jnp.conj(D[d]) * phih for d in range(dim)]
            + [((a + b * sym) * phih).astype(cdtype)])

    def kspace_algebra_adjoint(self, ch: jnp.ndarray, alpha, beta,
                               pinc_coeffs: Tuple[float, float],
                               f32: bool = False,
                               filter_sym: Optional[jnp.ndarray] = None
                               ) -> jnp.ndarray:
        """Conjugate-transpose of :meth:`kspace_algebra`'s block symbol,
        applied to the stacked ``dim + 1`` cotangent spectra ``ch``.

        The substep's spatial map is ``irfftn . diag(M) . rfftn`` for
        the per-mode block symbol ``M(k)``; its real transpose is the
        SAME transform pair around ``M(k)^H``. With ``H = 1/(alpha +
        beta*lam)``, ``P = filter_sym`` and ``D_e`` the staggered
        divergence symbols, the closed form is

            (M^H c)_e = H * P * [ c_e + conj(D_e)/lam *
                                  ( sum_d D_d c_d + (a + b*lam) c_p ) ]

        with the ``1/lam`` term zeroed at k=0 (matching the primal's
        zero-mean pressure convention). Same cached tables, same
        diagonal structure, zero extra transforms — the cotangent pass
        IS the plan."""
        dim = self.dim
        sym, D = self._tables(f32=f32)
        wdtype = jnp.float32 if f32 else self.rdtype
        cdtype = ch.dtype
        a, b = pinc_coeffs
        g = None
        for d in range(dim):
            t = D[d] * ch[d]
            g = t if g is None else g + t
        g = g + ((a + b * sym) * ch[dim]).astype(cdtype)
        sym_safe = jnp.where(sym == 0, 1.0, sym)
        psih = jnp.where(sym == 0, 0.0, g / sym_safe)
        denom = (alpha + beta * sym).astype(wdtype)
        out = jnp.stack([ch[d] + jnp.conj(D[d]) * psih
                         for d in range(dim)]) / denom[None]
        if filter_sym is not None:
            out = out * filter_sym.astype(wdtype)[None]
        return out

    # -- the classic solves, sharing the cached tables -----------------------
    def solve_poisson(self, rhs: jnp.ndarray) -> jnp.ndarray:
        """lap(p) = rhs; zero-mean solution (k=0 mode discarded)."""
        sym = self.sym
        rhat = rfftn(rhs, self.shape)
        sym_safe = jnp.where(sym == 0, 1.0, sym)
        phat = jnp.where(sym == 0, 0.0, rhat / sym_safe)
        p = irfftn(phat, s=self.shape)
        return p.astype(rhs.dtype)

    def solve_helmholtz(self, rhs: jnp.ndarray, alpha, beta) -> jnp.ndarray:
        """(alpha + beta lap) u = rhs (alpha + beta*lam != 0 required)."""
        rhat = rfftn(rhs, self.shape)
        uhat = rhat / (alpha + beta * self.sym)
        u = irfftn(uhat, s=self.shape)
        return u.astype(rhs.dtype)

    def solve_stokes_saddle(self, f_u: Vel, f_p: jnp.ndarray,
                            alpha, mu) -> Tuple[Vel, jnp.ndarray]:
        """Exact periodic saddle-point solve of

            alpha*u - mu*lap(u) + grad(p) = f_u,    -div(u) = f_p

        as one batched spectral pass (the all-periodic collapse of the
        coupled Krylov solve in solvers.stokes): with A = alpha - mu*lam
        and the staggered symbols D_d (gradient -conj(D_d)),

            p_hat = (sum_d D_d f_hat_d + A f_hat_p) / lam     (0 at k=0)
            u_hat_d = (f_hat_d + conj(D_d) p_hat) / A

        Zero modes follow the periodic conventions: p is zero-mean; the
        k=0 velocity mode is f_hat_d(0)/alpha (zeroed when alpha == 0 —
        the steady zero-mean frame). ``alpha`` may be traced.
        """
        dim = self.dim
        rdtype = self.rdtype
        sym, D = self.sym, self.D
        fh = rfftn(jnp.stack(tuple(f_u) + (f_p,)), self.shape,
                   axes=self.axes)
        A = (alpha - mu * sym).astype(rdtype)
        divf = None
        for d in range(dim):
            t = D[d] * fh[d]
            divf = t if divf is None else divf + t
        sym_safe = jnp.where(sym == 0, 1.0, sym)
        ph = jnp.where(sym == 0, 0.0, (divf + A * fh[dim]) / sym_safe)
        A_safe = jnp.where(A == 0, 1.0, A)
        uh = jnp.stack(
            [jnp.where(A == 0, 0.0,
                       (fh[d] + jnp.conj(D[d]) * ph) / A_safe)
             for d in range(dim)] + [ph])
        out = irfftn(uh, s=self.shape, axes=self.axes)
        out = out.astype(rdtype)
        return tuple(out[d] for d in range(dim)), out[dim]


# -- fused-substep reverse mode (PR 19) --------------------------------------
#
# ``_substep_raw`` is the literal substep math (bitwise identical to the
# pre-VJP implementation: same ops, same order). ``_substep_core`` wraps
# it in a ``jax.custom_vjp`` whose backward pass applies the SAME plan
# with conjugated symbols: one batched rfftn over the stacked dim+1
# output cotangents, the diagonal ``kspace_algebra_adjoint``, one
# batched irfftn for the dim RHS cotangents. No spectra are saved from
# the forward pass (residuals are the five scalars + the filter table),
# so a full vjp round trip costs exactly 2x the primal's batched FFT
# calls — the ``grad_substep`` graph budget pins that statically.

def _substep_raw(plan: "SpectralPlan", sdtype_name: str, rhs: Vel,
                 alpha, beta, a, b,
                 filter_sym: Optional[jnp.ndarray]
                 ) -> Tuple[Vel, jnp.ndarray]:
    sdtype = jnp.bfloat16 if sdtype_name == "bf16" else None
    x = jnp.stack(rhs)
    if sdtype is not None:
        # bf16 transform operands, f32 twiddle/accumulation
        x = _round_real(x.astype(jnp.float32), sdtype)
    # ``transforms``: the transform calls and nothing else, whatever
    # implements them (obs/deviceprof reads fluid.transform_ms by it)
    with jax.named_scope("transforms"):
        uh = rfftn(x, plan.shape, axes=plan.axes)
    outh = plan.kspace_algebra(uh, alpha, beta, (a, b),
                               f32=sdtype is not None,
                               filter_sym=filter_sym)
    if sdtype is not None:
        # split-real compression of the inverse-transform operand
        outh = _round_complex(outh, sdtype)
    with jax.named_scope("transforms"):
        out = irfftn(outh, s=plan.shape, axes=plan.axes)
    out = out.astype(plan.rdtype)
    return tuple(out[d] for d in range(plan.dim)), out[plan.dim]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _substep_core(plan: "SpectralPlan", sdtype_name: str, rhs: Vel,
                  alpha, beta, a, b,
                  filter_sym: Optional[jnp.ndarray]
                  ) -> Tuple[Vel, jnp.ndarray]:
    return _substep_raw(plan, sdtype_name, rhs, alpha, beta, a, b,
                        filter_sym)


def _substep_fwd(plan, sdtype_name, rhs, alpha, beta, a, b, filter_sym):
    out = _substep_raw(plan, sdtype_name, rhs, alpha, beta, a, b,
                       filter_sym)
    # residuals: coefficients only — the adjoint needs no forward
    # activations (the whole point of "adjoint at primal cost")
    return out, (alpha, beta, a, b, filter_sym)


def _substep_bwd(plan, sdtype_name, res, ct):
    alpha, beta, a, b, filter_sym = res
    ct_u, ct_p = ct
    sdtype = jnp.bfloat16 if sdtype_name == "bf16" else None
    c = jnp.stack(tuple(ct_u) + (ct_p,)).astype(
        jnp.float32 if sdtype is not None else plan.rdtype)
    if sdtype is not None:
        # mirror the primal's operand compression on the cotangents so
        # the transposed transforms see the same storage precision
        c = _round_real(c, sdtype)
    with jax.named_scope("transforms"):
        ch = rfftn(c, plan.shape, axes=plan.axes)
    gh = plan.kspace_algebra_adjoint(ch, alpha, beta, (a, b),
                                     f32=sdtype is not None,
                                     filter_sym=filter_sym)
    if sdtype is not None:
        gh = _round_complex(gh, sdtype)
    with jax.named_scope("transforms"):
        g = irfftn(gh, s=plan.shape, axes=plan.axes)
    g = g.astype(plan.rdtype)
    rhs_ct = tuple(g[d] for d in range(plan.dim))
    # alpha/beta/pinc are treated as constants (see
    # DIFFERENTIATE_COEFFS); filter_sym is a precomputed table
    zero = lambda v: None if v is None else jnp.zeros_like(v)  # noqa: E731
    return (rhs_ct, zero(alpha), zero(beta), zero(a), zero(b),
            zero(filter_sym))


_substep_core.defvjp(_substep_fwd, _substep_bwd)


# -- the hash-cons LRU cache -------------------------------------------------

_CACHE_MAXSIZE = 16
_cache: "OrderedDict[tuple, SpectralPlan]" = OrderedDict()
_lock = threading.Lock()
_stats = {"hits": 0, "misses": 0, "evictions": 0}

# telemetry twins (PR 9): the same three events published onto the
# process-wide bus, so a run ledger's per-chunk counter snapshots show
# plan-cache behavior alongside every other subsystem
from ibamr_tpu import obs as _obs  # noqa: E402

_OBS_HITS = _obs.counter("spectral_plan_hits_total")
_OBS_MISSES = _obs.counter("spectral_plan_misses_total")
_OBS_EVICTIONS = _obs.counter("spectral_plan_evictions_total")


def plan_key(shape: Sequence[int], dx: Sequence[float], dtype,
             bc: str = "periodic") -> tuple:
    # the x64 flag is part of the key: table BUILDERS run np/jnp math
    # whose intermediate precision follows the mode, so two same-dtype
    # plans built under different modes differ in the last ulp — enough
    # to break tools/replay.py's bitwise pin when it re-executes a
    # capsule under the recorded mode inside a long-lived process
    return (tuple(int(s) for s in shape),
            tuple(float(h) for h in dx),
            jnp.dtype(jax.dtypes.canonicalize_dtype(dtype)).name,
            bc, bool(jax.config.jax_enable_x64))


def get_plan(shape: Sequence[int], dx: Sequence[float], dtype,
             bc: str = "periodic") -> SpectralPlan:
    """Hash-cons a :class:`SpectralPlan`: one table build per distinct
    ``(shape, dx, dtype, bc)``, LRU-bounded so a regrid loop (moving
    fine windows, level rebuilds) cannot grow the cache without bound.
    Device-resident: repeated jit traces capture the SAME arrays, so
    solver re-construction stops recomputing symbol tables."""
    key = plan_key(shape, dx, dtype, bc)
    with _lock:
        plan = _cache.get(key)
        if plan is not None:
            _stats["hits"] += 1
            _OBS_HITS.inc()
            _cache.move_to_end(key)
            return plan
    # build outside the lock (table construction runs device code)
    plan = SpectralPlan(shape, dx, dtype, bc)
    with _lock:
        # double-checked: a racing builder's plan wins LRU placement
        existing = _cache.get(key)
        if existing is not None:
            _stats["hits"] += 1
            _OBS_HITS.inc()
            _cache.move_to_end(key)
            return existing
        _stats["misses"] += 1
        _OBS_MISSES.inc()
        _cache[key] = plan
        while len(_cache) > _CACHE_MAXSIZE:
            _cache.popitem(last=False)
            _stats["evictions"] += 1
            _OBS_EVICTIONS.inc()
    return plan


def plan_cache_stats() -> dict:
    """{hits, misses, evictions, size, maxsize} — the observable the
    cache-boundedness test pins."""
    with _lock:
        return dict(_stats, size=len(_cache), maxsize=_CACHE_MAXSIZE)


def clear_plan_cache() -> None:
    with _lock:
        _cache.clear()
        for k in _stats:
            _stats[k] = 0


# -- module-level conveniences ----------------------------------------------

def spectral_substep(rhs: Vel, dx: Sequence[float], alpha, beta,
                     pinc_coeffs: Tuple[float, float],
                     spectral_dtype=None,
                     filter_sym: Optional[jnp.ndarray] = None
                     ) -> Tuple[Vel, jnp.ndarray]:
    """Plan-cached fused fluid substep (see
    :meth:`SpectralPlan.substep`); fetches/creates the plan for
    ``rhs[0].shape``."""
    plan = get_plan(rhs[0].shape, dx, rhs[0].dtype)
    return plan.substep(rhs, alpha, beta, pinc_coeffs,
                        spectral_dtype=spectral_dtype,
                        filter_sym=filter_sym)


def gaussian_filter_symbol(shape: Sequence[int], dx: Sequence[float],
                           width: float, dtype=jnp.float32) -> jnp.ndarray:
    """Spectral symbol of a discrete Gaussian smoother of standard
    deviation ``width`` (grid units of length): exp(width^2/2 * lam)
    with lam the discrete-Laplacian symbol (lam <= 0, so this is a pure
    low-pass). Intended as ``filter_sym`` for the fused substep's
    body-force smoothing — it rides the substep's existing transforms."""
    from ibamr_tpu.solvers import fft

    # widest AVAILABLE float (f64 only when x64 is enabled): asking for
    # f64 outright warns and truncates under the production x64-off
    # config (graph-audit first-wave finding)
    wide = jax.dtypes.canonicalize_dtype(jnp.float64)
    lam = fft.laplacian_symbol(shape, dx, wide)
    return jnp.exp(0.5 * float(width) ** 2 * lam).astype(dtype)
