"""Micro-benchmark: the price of the adjoint, piece by piece (PR 19).

Measures primal-vs-VJP wall time AND the batched-FFT / byte / scatter
census for each differentiable piece — the fused spectral substep, the
packed spread/interp transfers, and the whole coupled IB step — so the
"adjoint at primal cost" claim is a measured ratio, not a budget
assertion alone. The graph numbers come from the same jaxpr-level
censuses the graph budgets pin (``fft_census``, ``convert_census``,
``scatter_gather_census``): the substep VJP must show exactly 2x the
primal's FFT calls, the spread VJP zero scatter primitives beyond the
primal forward it replays (the reverse sweep is pure gathers —
``grad_spread`` pins its isolated backward pass at zero), and every
piece zero f64 widenings.

Usage:  python tools/microbench_grad.py [--n 64] [--reps 5] [--json]
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


def census(fn, *args):
    """fft/convert/scatter slice of the jaxpr census for one callable."""
    import jax

    from ibamr_tpu.analysis.graph_census import (convert_census,
                                                 fft_census,
                                                 scatter_gather_census)

    jaxpr = jax.make_jaxpr(fn)(*args)
    out = {}
    f = fft_census(jaxpr)
    out["fft_ops"] = f["fft_ops"]
    out["fft_bytes"] = f["fft_bytes"]
    out["f64_widenings"] = convert_census(jaxpr)["f64_widenings"]
    out["scatter_prims"] = scatter_gather_census(jaxpr)["scatter_prims"]
    return out


def run(n=64, reps=5, dt=5e-5, quiet=False):
    """Measure every piece at one size; returns the flat metrics dict.

    Callable in-process (bench.py's --grad leg runs it in a guarded
    CPU child) as well as from the CLI below."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ibamr_tpu.serve.aot_cache import enable_persistent_cache
    enable_persistent_cache(jax)

    from ibamr_tpu.grid import StaggeredGrid
    from ibamr_tpu.models.shell3d import build_shell_example
    from ibamr_tpu.solvers import spectral_plan

    r = reps
    rho, mu = 1.0, 0.05
    alpha, beta = rho / dt, -0.5 * mu
    if not quiet:
        print(f"n={n} dt={dt} backend={jax.default_backend()}")
    out = {"n": n, "backend": jax.default_backend()}

    rng = np.random.default_rng(0)
    grid = StaggeredGrid(n=(n, n, n), x_lo=(0.0,) * 3, x_up=(1.0,) * 3)
    rhs = tuple(jnp.asarray(rng.standard_normal(grid.n), jnp.float32)
                for _ in range(3))
    plan = spectral_plan.get_plan(grid.n, grid.dx, jnp.float32)

    # -- fused substep: primal vs full vjp round trip -------------------
    def substep(rr):
        return plan.substep(rr, alpha, beta, (alpha, beta))

    ct = jax.tree_util.tree_map(
        lambda s: jnp.ones(s.shape, s.dtype), jax.eval_shape(substep, rhs))

    def substep_vjp(rr, c):
        val, pull = jax.vjp(substep, rr)
        return val, pull(c)

    out["substep_primal_ms"] = timeit(jax.jit(lambda: substep(rhs)), r)
    out["substep_vjp_ms"] = timeit(
        jax.jit(lambda: substep_vjp(rhs, ct)), r)
    for k, v in census(substep, rhs).items():
        out[f"substep_primal_{k}"] = v
    for k, v in census(substep_vjp, rhs, ct).items():
        out[f"substep_vjp_{k}"] = v

    # -- packed transfers: primal vs vjp through the SAME buckets -------
    nl = max(8, (5 * n) // 4)
    integ, state = build_shell_example(
        n_cells=n, n_lat=nl, n_lon=nl, radius=0.25, aspect=1.2,
        stiffness=1.0, rest_length_factor=0.75, mu=mu,
        use_fast_interaction="packed")
    eng = integ.ib.fast
    X, mask = state.X, state.mask
    b = eng.buckets(X, mask)
    F = jnp.asarray(rng.standard_normal(X.shape), jnp.float32)
    u = state.ins.u

    def spread(Fa, Xa):
        return eng.spread_vel(Fa, Xa, b=b)

    gct = jax.tree_util.tree_map(jnp.ones_like, jax.eval_shape(
        spread, F, X))

    def spread_vjp(Fa, Xa):
        val, pull = jax.vjp(spread, Fa, Xa)
        return val, pull(gct)

    def interp(ua, Xa):
        return eng.interpolate_vel(ua, Xa, b=b)

    uct = jnp.ones_like(jax.eval_shape(interp, u, X))

    def interp_vjp(ua, Xa):
        val, pull = jax.vjp(interp, ua, Xa)
        return val, pull(uct)

    out["spread_primal_ms"] = timeit(jax.jit(lambda: spread(F, X)), r)
    out["spread_vjp_ms"] = timeit(jax.jit(lambda: spread_vjp(F, X)), r)
    out["interp_primal_ms"] = timeit(jax.jit(lambda: interp(u, X)), r)
    out["interp_vjp_ms"] = timeit(jax.jit(lambda: interp_vjp(u, X)), r)
    for k, v in census(spread, F, X).items():
        out[f"spread_primal_{k}"] = v
    for k, v in census(spread_vjp, F, X).items():
        out[f"spread_vjp_{k}"] = v
    for k, v in census(interp_vjp, u, X).items():
        out[f"interp_vjp_{k}"] = v

    # -- whole coupled IB step: primal vs reverse pass ------------------
    def step(st):
        return integ.step(st, dt)

    def step_loss(st):
        leaves = jax.tree_util.tree_leaves(step(st))
        return sum(jnp.sum(l) for l in leaves
                   if jnp.issubdtype(l.dtype, jnp.inexact))

    step_grad = jax.grad(step_loss, allow_int=True)
    out["step_primal_ms"] = timeit(jax.jit(lambda: step(state)), r)
    out["step_vjp_ms"] = timeit(jax.jit(lambda: step_grad(state)), r)
    for k, v in census(step, state).items():
        out[f"step_primal_{k}"] = v
    for k, v in census(step_grad, state).items():
        out[f"step_vjp_{k}"] = v

    for piece in ("substep", "spread", "interp", "step"):
        p, v = out.get(f"{piece}_primal_ms"), out.get(f"{piece}_vjp_ms")
        out[f"{piece}_grad_ratio"] = round(v / max(p, 1e-9), 3)

    if not quiet:
        print(f"{'piece':10s} {'primal ms':>10s} {'vjp ms':>10s} "
              f"{'ratio':>7s} {'ffts p/v':>9s} {'scat v':>7s}")
        for piece in ("substep", "spread", "interp", "step"):
            pf = out.get(f"{piece}_primal_fft_ops", 0)
            vf = out.get(f"{piece}_vjp_fft_ops", 0)
            print(f"{piece:10s} {out[f'{piece}_primal_ms']:10.2f} "
                  f"{out[f'{piece}_vjp_ms']:10.2f} "
                  f"{out[f'{piece}_grad_ratio']:7.2f} "
                  f"{pf:4d}/{vf:<4d} "
                  f"{out.get(f'{piece}_vjp_scatter_prims', 0):7d}")
        wid = sum(v for k, v in out.items()
                  if k.endswith("f64_widenings"))
        print(f"f64 widenings across all graphs: {wid}")
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=64,
                    help="fluid cells per side (3D substep; the coupled "
                         "step scales its shell with it)")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--dt", type=float, default=5e-5)
    ap.add_argument("--json", action="store_true",
                    help="emit a machine-readable JSON line after the "
                         "table")
    args = ap.parse_args()
    out = run(n=args.n, reps=args.reps, dt=args.dt)
    if args.json:
        print(json.dumps({k: (round(v, 3) if isinstance(v, float)
                              else v) for k, v in out.items()}),
              flush=True)


if __name__ == "__main__":
    main()
