"""Spectral-plan layer (round 6): the hash-cons plan cache, the
k-space-resident fused substep (bitwise vs the pre-plan fused
reference in f64), the bf16/split-real mixed-precision transform path
(tolerance-pinned vs the f64 oracle, exactly like packed_bf16), the
all-periodic exact Stokes saddle solve, and the whole-step buffer
donation contracts (no-new-retrace via the driver's trace_counts
observable; ResilientDriver forces donation off)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ibamr_tpu.grid import StaggeredGrid
from ibamr_tpu.integrators.ins import INSStaggeredIntegrator
from ibamr_tpu.solvers import fft, spectral_plan


def _reference_fused(rhs, dx, alpha, beta, pinc_coeffs):
    """The pre-plan fused substep (fft.helmholtz_project_periodic as
    it was before delegation), inlined verbatim: the plan path must be
    BITWISE identical to this in full precision — the refactor moved
    where the symbol tables live, not what the substep computes."""
    shape = rhs[0].shape
    dim = len(shape)
    rdtype = rhs[0].dtype
    axes = tuple(range(1, dim + 1))
    sym = fft.laplacian_symbol(shape, dx, rdtype)
    uh = jnp.fft.rfftn(jnp.stack(rhs), axes=axes)
    cdtype = uh.dtype
    denom = (alpha + beta * sym).astype(rdtype)
    uh = uh / denom[None]
    D = fft._staggered_div_symbols(shape, dx, cdtype)
    divh = None
    for d in range(dim):
        t = D[d] * uh[d]
        divh = t if divh is None else divh + t
    sym_safe = jnp.where(sym == 0, 1.0, sym)
    phih = jnp.where(sym == 0, 0.0, divh / sym_safe)
    a, b = pinc_coeffs
    outh = jnp.stack(
        [uh[d] + jnp.conj(D[d]) * phih for d in range(dim)]
        + [((a + b * sym) * phih).astype(cdtype)])
    out = jnp.fft.irfftn(outh, s=shape, axes=axes).astype(rdtype)
    return tuple(out[d] for d in range(dim)), out[dim]


def _rand_rhs(shape, dtype, seed=0):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.standard_normal(shape), dtype)
                 for _ in range(len(shape)))


def test_plan_substep_bitwise_vs_reference_f64():
    spectral_plan.clear_plan_cache()
    for shape in ((32, 32), (16, 16, 16)):
        g_dx = tuple(1.0 / s for s in shape)
        rhs = _rand_rhs(shape, jnp.float64)
        alpha, beta = 50.0, -0.05
        u_ref, p_ref = _reference_fused(rhs, g_dx, alpha, beta,
                                        (alpha, beta))
        u_pl, p_pl = fft.helmholtz_project_periodic(
            rhs, g_dx, alpha=alpha, beta=beta, pinc_coeffs=(alpha, beta))
        for a, b in zip(u_pl, u_ref):
            assert np.array_equal(np.asarray(a), np.asarray(b))
        assert np.array_equal(np.asarray(p_pl), np.asarray(p_ref))


def test_plan_substep_under_jit_matches_eager():
    # the plan's cached tables are concrete device constants; captured
    # in a jit trace they must NOT leak as tracers (the
    # ensure_compile_time_eval contract) and must reproduce the eager
    # result to f64 roundoff (XLA fusion may reassociate, so this is a
    # tight-tolerance pin, not bitwise)
    spectral_plan.clear_plan_cache()
    shape = (24, 24)
    dx = (1.0 / 24,) * 2
    rhs = _rand_rhs(shape, jnp.float64, seed=3)
    eager = fft.helmholtz_project_periodic(rhs, dx, alpha=10.0,
                                           beta=-0.01,
                                           pinc_coeffs=(10.0, -0.01))
    jitted = jax.jit(lambda r: fft.helmholtz_project_periodic(
        r, dx, alpha=10.0, beta=-0.01, pinc_coeffs=(10.0, -0.01)))(rhs)
    for a, b in zip(jitted[0], eager[0]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=0, atol=1e-13)
    np.testing.assert_allclose(np.asarray(jitted[1]),
                               np.asarray(eager[1]), rtol=0, atol=1e-11)


def test_bf16_substep_tolerance_pinned_vs_f64_oracle():
    """The mixed-precision contract: bf16/split-real transform
    operands keep ~3 decimal digits (the packed_bf16 precision class);
    the f32 path stays at f32 roundoff. Pins both so a silent dtype
    regression in either direction fails loudly."""
    shape = (32, 32, 32)
    dx = tuple(1.0 / s for s in shape)
    alpha, beta = 2.0e4, -0.025   # rho/dt, -mu/2 at flagship-ish dt
    rhs64 = _rand_rhs(shape, jnp.float64, seed=1)
    rhs32 = tuple(c.astype(jnp.float32) for c in rhs64)
    u64, p64 = fft.helmholtz_project_periodic(
        rhs64, dx, alpha=alpha, beta=beta, pinc_coeffs=(alpha, beta))
    u32, p32 = fft.helmholtz_project_periodic(
        rhs32, dx, alpha=alpha, beta=beta, pinc_coeffs=(alpha, beta))
    ubf, pbf = fft.helmholtz_project_periodic(
        rhs32, dx, alpha=alpha, beta=beta, pinc_coeffs=(alpha, beta),
        spectral_dtype="bf16")

    def rel(a, ref):
        a, ref = np.asarray(a, np.float64), np.asarray(ref)
        return np.max(np.abs(a - ref)) / np.max(np.abs(ref))

    for d in range(3):
        assert rel(u32[d], u64[d]) < 1e-5          # f32 roundoff class
        e = rel(ubf[d], u64[d])
        assert e < 2e-2                            # bf16 operand class
        assert e > 1e-6   # and it really IS the compressed path
    assert rel(pbf, p64) < 2e-2


def test_bf16_divergence_stays_bounded():
    # bf16 transforms trade exact discrete div-freedom for operand
    # compression; the residual divergence must stay at the bf16
    # rounding class relative to the velocity scale, not blow up
    from ibamr_tpu.ops import stencils

    shape = (32, 32, 32)
    dx = tuple(1.0 / s for s in shape)
    rhs = _rand_rhs(shape, jnp.float32, seed=2)
    alpha, beta = 2.0e4, -0.025
    u, _ = fft.helmholtz_project_periodic(
        rhs, dx, alpha=alpha, beta=beta, pinc_coeffs=(alpha, beta),
        spectral_dtype="bf16")
    umax = max(float(jnp.max(jnp.abs(c))) for c in u)
    div = stencils.divergence(u, dx)
    # grid-scale divergence: |div| ~ eps_bf16 * |u| / h
    assert float(jnp.max(jnp.abs(div))) < 0.1 * umax / min(dx)


def test_spectral_dtype_knob_validation():
    with pytest.raises(ValueError, match="spectral_dtype"):
        spectral_plan.canonical_spectral_dtype("fp8")
    assert spectral_plan.canonical_spectral_dtype("f32") is None
    assert spectral_plan.canonical_spectral_dtype(None) is None
    assert spectral_plan.canonical_spectral_dtype("bf16") is jnp.bfloat16
    with pytest.raises(ValueError, match="wall_axes"):
        INSStaggeredIntegrator(
            StaggeredGrid(n=(16, 16), x_lo=(0.0,) * 2, x_up=(1.0,) * 2),
            wall_axes=(True, False), spectral_dtype="bf16")


def test_plan_cache_hit_miss_and_bounded_growth():
    """Regrid loops construct solvers over and over; the hash-cons
    cache must serve repeats from memory (hits) and stay LRU-bounded
    when a moving-window regrid walks through many shapes."""
    spectral_plan.clear_plan_cache()
    p1 = spectral_plan.get_plan((16, 16), (0.1, 0.1), jnp.float32)
    p2 = spectral_plan.get_plan((16, 16), (0.1, 0.1), jnp.float32)
    assert p1 is p2                       # hash-cons: the SAME object
    st = spectral_plan.plan_cache_stats()
    assert st["misses"] == 1 and st["hits"] == 1
    # distinct key components are distinct plans
    assert spectral_plan.get_plan((16, 16), (0.1, 0.1),
                                  jnp.float64) is not p1
    assert spectral_plan.get_plan((16, 16), (0.2, 0.1),
                                  jnp.float32) is not p1
    # a regrid-like walk over many shapes cannot grow the cache
    # unboundedly (tiny shapes: this tests the LRU, not the tables)
    for k in range(spectral_plan._CACHE_MAXSIZE + 2):
        spectral_plan.get_plan((4 + 2 * k, 4), (0.1, 0.1), jnp.float32)
    st = spectral_plan.plan_cache_stats()
    assert st["size"] <= st["maxsize"]
    assert st["evictions"] > 0
    spectral_plan.clear_plan_cache()


def test_periodic_saddle_solve_exact_and_matches_fgmres():
    from ibamr_tpu.solvers.stokes import StaggeredStokesSolver, StokesBC

    bc = StokesBC(axes=(None, None))
    n, dx = (24, 24), (1.0 / 24,) * 2
    s = StaggeredStokesSolver(n, dx, bc, alpha=100.0, mu=0.02)
    assert s.spectral is not None       # all-periodic -> spectral path
    rng = np.random.default_rng(5)
    f_u = tuple(jnp.asarray(rng.standard_normal(n)) for _ in range(2))
    f_p = jnp.asarray(rng.standard_normal(n))
    rhs = s.make_rhs(f_u=f_u, f_p=f_p - f_p.mean())
    sol = s.solve(rhs)
    assert bool(sol.converged)
    assert int(sol.iters) == 0          # direct solve, no Krylov sweeps
    assert float(sol.resnorm) < 1e-10
    assert s.last_solve_stats["solver"] == "spectral"
    # cross-validate against the Krylov path on the same rhs
    s.spectral = None
    ref = s.solve(rhs)
    for a, b in zip(sol.u, ref.u):
        assert float(jnp.max(jnp.abs(a - b))) < 1e-9
    assert float(jnp.max(jnp.abs(sol.p - ref.p))) < 1e-9


def test_periodic_saddle_solve_traced_alpha_no_retrace():
    from ibamr_tpu.solvers.stokes import StaggeredStokesSolver, StokesBC

    bc = StokesBC(axes=(None, None, None))
    n, dx = (8, 8, 8), (0.125,) * 3
    s = StaggeredStokesSolver(n, dx, bc, alpha=50.0, mu=0.01)
    rng = np.random.default_rng(6)
    f_u = tuple(jnp.asarray(rng.standard_normal(n)) for _ in range(3))
    rhs = s.make_rhs(f_u=f_u)
    traces = []

    @jax.jit
    def solve_at(a):
        traces.append(1)
        return s.solve(rhs, alpha=a).u[0]

    # velocity (not pressure): with f_p = 0 the pressure is
    # alpha-independent, but u divides by A = alpha - mu*lam
    u1 = solve_at(40.0)
    u2 = solve_at(90.0)     # adaptive-dt contract: one trace, any dt
    assert len(traces) == 1
    assert not np.allclose(np.asarray(u1), np.asarray(u2))


def test_driver_donation_no_retrace_and_buffer_reuse():
    """cfg.donate=True: the chunked driver run keeps ONE trace per
    chunk length (trace_counts observable) and actually donates —
    the pre-chunk state buffers are deleted after the chunk."""
    from ibamr_tpu.utils.hierarchy_driver import HierarchyDriver, RunConfig

    g = StaggeredGrid(n=(16, 16), x_lo=(0.0, 0.0), x_up=(1.0, 1.0))
    integ = INSStaggeredIntegrator(g, mu=0.02, dtype=jnp.float32)
    rng = np.random.default_rng(7)
    u0 = tuple(jnp.asarray(rng.standard_normal(g.n) * 0.1, jnp.float32)
               for _ in range(2))
    state = integ.initialize(u0_arrays=u0)
    first_u = state.u[0]
    cfg = RunConfig(dt=1e-3, num_steps=12, health_interval=4,
                    donate=True)
    drv = HierarchyDriver(integ, cfg)
    out = drv.run(state)
    # one distinct input signature per chunk length — donation must
    # not introduce a retrace
    assert all(v == 1 for v in drv.trace_counts.values())
    assert drv.trace_counts                    # ... and chunks did run
    # the donated input buffer is gone (soft: is_deleted is a jax.Array
    # API detail, but on the CPU backend it is authoritative)
    if hasattr(first_u, "is_deleted"):
        assert first_u.is_deleted()
    assert bool(jnp.all(jnp.isfinite(out.u[0])))


def test_resilient_driver_forces_donation_off(tmp_path):
    from ibamr_tpu.utils.hierarchy_driver import HierarchyDriver, RunConfig
    from ibamr_tpu.utils.supervisor import ResilientDriver

    g = StaggeredGrid(n=(8, 8), x_lo=(0.0, 0.0), x_up=(1.0, 1.0))
    integ = INSStaggeredIntegrator(g, mu=0.02, dtype=jnp.float32)
    cfg = RunConfig(dt=1e-3, num_steps=4, health_interval=2,
                    restart_interval=2, donate=True)
    drv = HierarchyDriver(integ, cfg)
    res = ResilientDriver(drv, str(tmp_path), handle_signals=False)
    # rollback retains pre-chunk state references; donation would
    # invalidate them, so the supervisor must have switched it off
    assert drv.cfg.donate is False
    state = integ.initialize()
    out = res.run(state)                     # and the run still works
    assert bool(jnp.all(jnp.isfinite(out.u[0])))


def test_jitted_step_donation_ib():
    from ibamr_tpu.models.shell3d import build_shell_example

    integ, st = build_shell_example(n_cells=16, n_lat=8, n_lon=8,
                                    mu=0.05)
    step = integ.jitted_step(donate=True, with_stats=False)
    assert step is integ.jitted_step(donate=True, with_stats=False)
    u_before = st.ins.u[0]
    s2 = step(st, 1e-3)
    s3 = step(s2, 1e-3)
    assert bool(jnp.all(jnp.isfinite(s3.X)))
    if hasattr(u_before, "is_deleted"):
        assert u_before.is_deleted()


@pytest.mark.parametrize("lead,axes", [((), None), ((4,), (1, 2, 3))])
def test_chip_transform_spelling_matches_single_call(monkeypatch, lead,
                                                     axes):
    """On the chip, rank-3 transforms go one field at a time and the
    inverse is a rank-1 inverse, a barrier, and a rank-2 inverse real
    transform (the single rank-3 inverse and the field-batched calls
    are wrong at 256^3 on a v5e, PR 23). Same values as the single
    call; off the chip the graph is untouched."""
    from ibamr_tpu.analysis.graph_census import iter_eqns

    s = (8, 6, 10)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal(lead + s))

    def ffts(fn, arg):
        eqns = [e for e, _ in iter_eqns(jax.make_jaxpr(fn)(arg).jaxpr)]
        return ([e for e in eqns if e.primitive.name == "fft"],
                [e.primitive.name for e in eqns])

    def fwd():        # fresh callables per trace: no stale jaxpr
        return lambda a: spectral_plan.rfftn(a, s, axes=axes)

    def inv():
        return lambda z: spectral_plan.irfftn(z, s=s, axes=axes)

    xh = jnp.fft.rfftn(x, axes=axes)
    for fn, arg in ((fwd(), x), (inv(), xh)):
        f, names = ffts(fn, arg)
        assert len(f) == 1 and "optimization_barrier" not in names
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    n_fields = lead[0] if lead else 1
    f, names = ffts(fwd(), x)
    assert len(f) == n_fields
    f_inv, names_inv = ffts(inv(), xh)
    assert len(f_inv) == 2 * n_fields
    assert "optimization_barrier" in names_inv
    for e in f + f_inv:
        assert e.invars[0].aval.ndim == 3          # one field at a time
        assert (len(e.params["fft_lengths"]) < 3
                or "IRFFT" not in str(e.params["fft_type"]))
    np.testing.assert_allclose(np.asarray(fwd()(x)), np.asarray(xh),
                               atol=1e-12)
    np.testing.assert_allclose(np.asarray(inv()(xh)), np.asarray(x),
                               atol=1e-13)
    # rank 2 keeps the single call on every backend
    x2 = jnp.asarray(rng.standard_normal((3, 8, 6)))
    f, _ = ffts(lambda a: spectral_plan.rfftn(a, (8, 6), axes=(1, 2)), x2)
    assert len(f) == 1
