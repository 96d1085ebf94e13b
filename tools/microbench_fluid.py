"""Micro-benchmark: decompose the fluid-solve cost at the flagship size.

Times the spectral substep's internals on the real chip — the batched
forward/inverse transforms, the diagonal k-space algebra between them,
the fused plan substep, the PRE-fusion chain (separate Helmholtz solves
-> projection -> pressure update) it replaced, and the bf16/split-real
mixed-precision transform path — so fluid-phase optimization is driven
by measurement instead of the aggregate ``phases`` table in bench.py
(round 6: PERF.md put fluid_solve at 39.3 ms, the dominant flagship
phase; this names which half of it — transform or algebra — the next
lever must attack).

Usage:  python tools/microbench_fluid.py [--n 256] [--reps 10]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

# importable regardless of caller cwd (run as a script,
# python puts tools/ on sys.path, not the repo root)
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def timeit(fn, reps):
    import jax

    jax.block_until_ready(fn())  # compile + drain the warm-up step
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e3


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=256)
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--dt", type=float, default=5e-5)
    ap.add_argument("--json", action="store_true",
                    help="emit a machine-readable JSON line after the "
                         "table")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ibamr_tpu.serve.aot_cache import enable_persistent_cache
    enable_persistent_cache(jax)

    from ibamr_tpu.grid import StaggeredGrid
    from ibamr_tpu.integrators.ins import INSStaggeredIntegrator
    from ibamr_tpu.solvers import fft, spectral_plan

    n = args.n
    grid = StaggeredGrid(n=(n, n, n), x_lo=(0.0,) * 3, x_up=(1.0,) * 3)
    dt, rho, mu = args.dt, 1.0, 0.05
    alpha, beta = rho / dt, -0.5 * mu
    print(f"n={n} dt={dt} backend={jax.default_backend()}")

    rng = np.random.default_rng(0)
    rhs = tuple(jnp.asarray(rng.standard_normal(grid.n), jnp.float32)
                for _ in range(3))
    plan = spectral_plan.get_plan(grid.n, grid.dx, jnp.float32)
    axes = (1, 2, 3)
    r = args.reps
    out = {"n": n, "backend": jax.default_backend()}

    # transform / algebra split of the fused substep
    x = jnp.stack(rhs)
    fwd = jax.jit(lambda: jnp.fft.rfftn(x, axes=axes))
    out["fwd_transform_ms"] = timeit(fwd, r)
    uh = fwd()
    alg = jax.jit(lambda: plan.kspace_algebra(uh, alpha, beta,
                                              (alpha, beta)))
    out["kspace_algebra_ms"] = timeit(alg, r)
    oh = alg()
    out["inv_transform_ms"] = timeit(
        jax.jit(lambda: jnp.fft.irfftn(oh, s=grid.n, axes=axes)), r)

    # the fused plan substep (2 batched FFT calls total)
    out["fused_substep_ms"] = timeit(jax.jit(
        lambda: plan.substep(rhs, alpha, beta, (alpha, beta))), r)
    # the bf16/split-real mixed-precision transform path
    out["fused_substep_bf16_ms"] = timeit(jax.jit(
        lambda: plan.substep(rhs, alpha, beta, (alpha, beta),
                             spectral_dtype="bf16")), r)

    # the PRE-fusion chain the fused substep replaced (8 single-field
    # transforms + stencil passes)
    def chained():
        from ibamr_tpu.ops import stencils
        u_star = fft.solve_helmholtz_periodic_vel(rhs, grid.dx,
                                                  alpha, beta)
        u_new, phi0 = fft.project_divergence_free(u_star, grid.dx)
        phi = alpha * phi0
        p_inc = phi - (0.5 * mu * dt / rho) * stencils.laplacian(
            phi, grid.dx)
        return u_new, p_inc

    out["chained_substep_ms"] = timeit(jax.jit(chained), r)

    # whole fluid step (convective + rhs assembly + fused substep) and
    # its bf16 twin — what the integrator actually pays per substep
    integ = INSStaggeredIntegrator(grid, rho=rho, mu=mu,
                                   dtype=jnp.float32)
    st = integ.initialize(u0_arrays=rhs)
    out["ins_step_ms"] = timeit(jax.jit(
        lambda: integ.step(st, dt)), r)
    integ_bf = INSStaggeredIntegrator(grid, rho=rho, mu=mu,
                                      dtype=jnp.float32,
                                      spectral_dtype="bf16")
    out["ins_step_bf16_ms"] = timeit(jax.jit(
        lambda: integ_bf.step(st, dt)), r)

    out["plan_cache"] = spectral_plan.plan_cache_stats()

    print(f"fwd transform      {out['fwd_transform_ms']:8.2f} ms")
    print(f"k-space algebra    {out['kspace_algebra_ms']:8.2f} ms")
    print(f"inv transform      {out['inv_transform_ms']:8.2f} ms")
    print(f"fused substep      {out['fused_substep_ms']:8.2f} ms")
    print(f"fused substep bf16 {out['fused_substep_bf16_ms']:8.2f} ms")
    print(f"chained substep    {out['chained_substep_ms']:8.2f} ms")
    print(f"ins step           {out['ins_step_ms']:8.2f} ms")
    print(f"ins step bf16      {out['ins_step_bf16_ms']:8.2f} ms")
    tr = out["fwd_transform_ms"] + out["inv_transform_ms"]
    share = tr / max(out["fused_substep_ms"], 1e-9)
    print(f"transform share of fused substep: {share:.2f} "
          f"({'transform-bound' if share > 0.5 else 'algebra-bound'})")
    if args.json:
        print(json.dumps({k: (round(v, 3) if isinstance(v, float)
                              else v) for k, v in out.items()}),
              flush=True)


if __name__ == "__main__":
    main()
