"""Micro-benchmark: decompose the spread/interp cost at the flagship size.

Times the SUB-phases of the bucketed MXU transfer engine (bucket build,
weight evaluation, einsum contraction, overlap-add) separately on the
real chip, so transfer-engine optimization is driven by measurement
instead of the aggregate `phases` table in bench.py.

Usage:  python tools/microbench_transfer.py [--n 256] [--cap 0] [--reps 10]
(--cap 0 = use suggest_cap like the flagship model does).
"""

from __future__ import annotations

import argparse
import math
import os
import sys
import time

import numpy as np

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
    ap.add_argument("--cap", type=int, default=0)
    ap.add_argument("--tile", type=int, default=8)
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--no-pallas", action="store_true",
                    help="skip the pallas-packed legs (remote-compile "
                         "stall risk)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from ibamr_tpu.serve.aot_cache import enable_persistent_cache
    enable_persistent_cache(jax)

    from ibamr_tpu.grid import StaggeredGrid
    from ibamr_tpu.models.shell3d import make_spherical_shell
    from ibamr_tpu.ops import interaction_fast as fast

    n = args.n
    grid = StaggeredGrid(n=(n, n, n), x_lo=(0.0, 0.0, 0.0),
                         x_up=(1.0, 1.0, 1.0))
    n_lat = n_lon = 316 if n >= 256 else 180
    s = make_spherical_shell(n_lat, n_lon, 0.25, (0.5, 0.5, 0.5), 1.0,
                             aspect=1.2)
    X = jnp.asarray(s.vertices, dtype=jnp.float32)
    N = X.shape[0]
    F = jnp.ones((N, 3), dtype=jnp.float32)

    cap = args.cap or min(fast.suggest_cap(grid, s.vertices, tile=args.tile),
                          1024)
    eng = fast.FastInteraction(grid, tile=args.tile, cap=cap,
                               overflow_cap=max(2048, N // 4))
    geom = eng.geom
    B = int(np.prod(geom.nblk))
    print(f"n={n} N={N} tile={args.tile} cap={cap} B={B} "
          f"slots={B * cap} util={N / (B * cap):.3f} "
          f"backend={jax.default_backend()}")

    b = jax.jit(eng.buckets)(X)
    occ = np.asarray(jnp.sum(b.wb > 0, axis=1))
    print(f"occupancy: mean={occ.mean():.1f} max={occ.max()} "
          f"active_tiles={np.sum(occ > 0)} "
          f"overflow={int(jnp.sum(b.w_overflow > 0))}")

    r = args.reps
    t_bucket = timeit(jax.jit(lambda: eng.buckets(X)), r)

    wfn = jax.jit(lambda: fast._tile_weights(geom, grid, b, 0, "IB_4"))
    t_weights = timeit(wfn, r)
    A, Wlast = wfn()

    ein = jax.jit(lambda: jnp.einsum(
        "bmp,bmz->bpz", A, Wlast, precision=jax.lax.Precision.HIGHEST))
    t_einsum = timeit(ein, r)
    T = ein()

    ov = jax.jit(lambda: fast._overlap_add(geom, grid, T.reshape(
        (T.shape[0],) + tuple(geom.width) + (n,))))
    t_overlap = timeit(ov, r)

    ex = jax.jit(lambda: fast._extract_tiles(geom, grid, ov()))
    t_extract = timeit(ex, r) - t_overlap

    t_spread3 = timeit(jax.jit(
        lambda: eng.spread_vel(F, X, b=b)), r)
    u = tuple(jnp.zeros(grid.n, dtype=jnp.float32) for _ in range(3))
    t_interp3 = timeit(jax.jit(
        lambda: eng.interpolate_vel(u, X, b=b)), r)

    # packed-chunk engine comparison
    from ibamr_tpu.ops import interaction_packed as packed

    Q = packed.suggest_chunks(grid, s.vertices, tile=args.tile, chunk=128)
    peng = packed.PackedInteraction(grid, tile=args.tile, chunk=128,
                                    nchunks=Q,
                                    overflow_cap=max(2048, N // 4))
    pb = jax.jit(peng.buckets)(X)
    print(f"packed: Q={Q} slots={Q * 128} util={N / (Q * 128):.3f} "
          f"overflow={int(jnp.sum(pb.w_overflow > 0))}")
    t_pbucket = timeit(jax.jit(lambda: peng.buckets(X)), r)

    # slot-preserving refresh vs full re-pack: a half-step-sized drift
    # (well under the footprint slack) re-gathers into the pack-time
    # layout — the integrator pays THIS instead of a second bucket_prep
    dxm = float(min(grid.dx))
    Xh = X + jnp.asarray([[0.3 * dxm, -0.2 * dxm, 0.15 * dxm]],
                         dtype=X.dtype)
    refresh_hit = bool(jax.jit(lambda: peng.refresh(pb, Xh)[1])())
    t_refresh = timeit(jax.jit(lambda: peng.refresh(pb, Xh)[0]), r)

    t_pspread3 = timeit(jax.jit(lambda: peng.spread_vel(F, X, b=pb)), r)
    t_pinterp3 = timeit(jax.jit(
        lambda: peng.interpolate_vel(u, X, b=pb)), r)

    # bf16-compressed twin (operand HBM traffic halved)
    pengb = packed.PackedInteraction(grid, tile=args.tile, chunk=128,
                                     nchunks=Q,
                                     overflow_cap=max(2048, N // 4),
                                     compute_dtype=jnp.bfloat16)
    t_pbspread3 = timeit(jax.jit(lambda: pengb.spread_vel(F, X, b=pb)),
                         r)
    t_pbinterp3 = timeit(jax.jit(
        lambda: pengb.interpolate_vel(u, X, b=pb)), r)

    # pallas-packed: same chunk layout, Pallas tile programs
    t_ppspread3 = t_ppinterp3 = None
    t_hyspread3 = t_hyinterp3 = None
    if not args.no_pallas:
        from ibamr_tpu.ops.pallas_interaction import (
            HybridPackedInteraction, PallasPackedInteraction)

        ppeng = PallasPackedInteraction(grid, tile=args.tile, chunk=128,
                                        nchunks=Q,
                                        overflow_cap=max(2048, N // 4))
        ppb = jax.jit(ppeng.buckets)(X)
        t_ppspread3 = timeit(jax.jit(
            lambda: ppeng.spread_vel(F, X, b=ppb)), r)
        t_ppinterp3 = timeit(jax.jit(
            lambda: ppeng.interpolate_vel(u, X, b=ppb)), r)

        # hybrid: pallas spread + XLA bf16 interp on the SAME context
        hyeng = HybridPackedInteraction(grid, tile=args.tile, chunk=128,
                                        nchunks=Q,
                                        overflow_cap=max(2048, N // 4),
                                        compute_dtype=jnp.bfloat16)
        t_hyspread3 = timeit(jax.jit(
            lambda: hyeng.spread_vel(F, X, b=ppb)), r)
        t_hyinterp3 = timeit(jax.jit(
            lambda: hyeng.interpolate_vel(u, X, b=ppb)), r)

    gb = (A.nbytes + Wlast.nbytes + T.nbytes) / 1e9
    print(f"bucket_build      {t_bucket:8.2f} ms")
    print(f"weights (1 ch)    {t_weights:8.2f} ms   "
          f"A {A.nbytes / 1e6:.0f} MB + Wz {Wlast.nbytes / 1e6:.0f} MB")
    print(f"einsum  (1 ch)    {t_einsum:8.2f} ms   "
          f"{gb:.2f} GB operands -> "
          f"{gb / max(t_einsum, 1e-9) * 1e3:.0f} GB/s")
    print(f"overlap (1 ch)    {t_overlap:8.2f} ms")
    print(f"extract (1 ch)    {t_extract:8.2f} ms")
    print(f"spread_vel (3ch)  {t_spread3:8.2f} ms")
    print(f"interp_vel (3ch)  {t_interp3:8.2f} ms")
    est = 3 * (t_weights + t_einsum + t_overlap)
    print(f"sum est 3ch sprd  {est:8.2f} ms")
    print(f"packed bucket     {t_pbucket:8.2f} ms")
    print(f"packed refresh    {t_refresh:8.2f} ms   "
          f"(vs full re-pack {t_pbucket:.2f} ms, "
          f"hit={refresh_hit})")
    print(f"packed spread 3ch {t_pspread3:8.2f} ms")
    print(f"packed interp 3ch {t_pinterp3:8.2f} ms")
    print(f"pk-bf16 sprd 3ch  {t_pbspread3:8.2f} ms")
    print(f"pk-bf16 intp 3ch  {t_pbinterp3:8.2f} ms")
    if t_ppspread3 is not None:
        print(f"pallas-pk sprd 3c {t_ppspread3:8.2f} ms")
        print(f"pallas-pk intp 3c {t_ppinterp3:8.2f} ms")
    if t_hyspread3 is not None:
        # the hybrid_bf16 registry engine: pallas spread + bf16 interp
        print(f"hybrid_bf16 sprd  {t_hyspread3:8.2f} ms")
        print(f"hybrid_bf16 intp  {t_hyinterp3:8.2f} ms")


if __name__ == "__main__":
    main()
