"""Chip-independent HLO traffic/FLOP audit of the flagship step
(VERDICT round 4, "Next round" item 1b).

``jit(...).lower().compile()`` on the host-CPU backend builds the same
HLO module structure the TPU backend compiles, and XLA's
``cost_analysis()`` / ``memory_analysis()`` report the module's
bytes-accessed and FLOP totals — numbers that do NOT need the chip.
This turns the transfer-engine claims ("occupancy packing lifts slot
utilization so every weight operand shrinks by the same factor; bf16
compression halves what remains") into measured per-engine byte
counts:

- per engine (scatter / mxu / packed / *_bf16): the ISOLATED spread
  and interp contractions at flagship shapes, plus bucket prep;
- the full coupled step and the isolated fluid solve, for the
  phase-share picture that the on-chip ``phases`` table measures in
  wall-clock.

Every leg runs in its own child process (the XLA CPU pipeline has a
rare native-crash flake; an isolated leg loses one data point, not the
artifact). Results land in the file ``--out`` names.
Round 6 adds an FFT census per leg (batched-transform call count +
per-transform bytes at the jaxpr primitive level) and the fluid trio
(``fluid`` fused / ``fluid_chained`` pre-fusion / ``fluid_bf16``
mixed-precision), pinning the spectral fusion by op count.

Caveats (stated in the artifact): CPU-backend fusion/layout decisions
differ from TPU in the details, so treat ratios between engines as the
signal, not absolute byte counts; `bytes accessed` is XLA's HLO-level
estimate (each buffer counted once per producing/consuming op), not an
HBM-transaction trace. The pallas engines cannot be audited this way
(interpret-mode lowering on CPU carries no real cost model) — their
evidence remains the on-chip shootout. The hybrid_bf16 engine is
audited PARTIALLY for the same reason: its interp / bucket-prep /
refresh legs are plain XLA and appear here; its spread leg is the
pallas kernel and does not.
"""

from __future__ import annotations

import argparse
import json
import multiprocessing as mp
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


# The census primitives now live in ibamr_tpu.analysis.graph_census
# (PR 8): ONE set of counting rules shared by this bench artifact, the
# CI drift gate (tools/graph_audit.py) and the tier-1 contract tests.
# Re-exported here because tests/test_forces_hlo.py and
# tests/test_hlo_budgets.py import it from this module.
from ibamr_tpu.analysis.graph_census import hlo_op_counts  # noqa: E402,F401


def _leg_child(q, n, n_lat, n_lon, engine, piece):
    try:
        from ibamr_tpu.utils.backend_guard import force_cpu

        jax = force_cpu()
        import jax.numpy as jnp

        from ibamr_tpu.models.shell3d import build_shell_example

        integ, state = build_shell_example(
            n_cells=n, n_lat=n_lat, n_lon=n_lon, radius=0.25,
            aspect=1.2, stiffness=1.0, rest_length_factor=0.75,
            mu=0.05, use_fast_interaction=engine,
            spectral_dtype="bf16" if piece == "fluid_bf16" else None)
        ib = integ.ib
        grid = integ.ins.grid
        dt = 5e-5
        X, mask = state.X, state.mask
        t0 = time.perf_counter()

        if piece == "step":
            fn = jax.jit(lambda s: integ.step(s, dt))
            lowered = fn.lower(state)
        elif piece in ("fluid", "fluid_bf16", "fluid_chained"):
            # fluid_bf16: the mixed-precision transform path (the
            # integrator was built with spectral_dtype="bf16" above);
            # fluid_chained: the PRE-fusion chain (separate Helmholtz
            # solves -> projection -> pressure update) the fused
            # substep replaced. These legs are the WHOLE ins.step
            # (convective + rhs assembly dilute the substep delta);
            # the substep* trio below isolates the solve itself — the
            # ">= 20% lower fluid-phase bytes-accessed" evidence
            if piece == "fluid_chained":
                integ.ins.fused_stokes = None
            f = tuple(jnp.zeros_like(u) for u in state.ins.u)
            fn = jax.jit(lambda st, ff: integ.ins.step(st, dt, f=ff))
            lowered = fn.lower(state.ins, f)
        elif piece in ("substep", "substep_bf16", "substep_chained"):
            # the spectral solve in ISOLATION: Helmholtz + projection
            # + pressure increment, holding the surrounding step fixed
            from ibamr_tpu.ops import stencils
            from ibamr_tpu.solvers import fft as _fft

            ins = integ.ins
            dx = grid.dx
            alpha, beta = ins.rho / dt, -0.5 * ins.mu
            rhs = state.ins.u
            if piece == "substep_chained":
                def sub(r):
                    u_star = _fft.solve_helmholtz_periodic_vel(
                        r, dx, alpha, beta)
                    u_new, phi0 = _fft.project_divergence_free(
                        u_star, dx)
                    phi = alpha * phi0
                    p_inc = phi + (beta / alpha) * stencils.laplacian(
                        phi, dx)
                    return u_new, p_inc
            else:
                sd = "bf16" if piece == "substep_bf16" else None

                def sub(r):
                    return _fft.helmholtz_project_periodic(
                        r, dx, alpha=alpha, beta=beta,
                        pinc_coeffs=(alpha, beta), spectral_dtype=sd)

            fn = jax.jit(sub)
            lowered = fn.lower(rhs)
        elif piece == "spread":
            F = jnp.zeros_like(X)

            def spread(Xa, Fa, m):
                ctx = ib.prepare(Xa, m)
                return ib.spread_force(Fa, grid, Xa, m, ctx=ctx)

            lowered = jax.jit(spread).lower(X, F, mask)
        elif piece == "interp":
            u = state.ins.u

            def interp(ua, Xa, m):
                ctx = ib.prepare(Xa, m)
                return ib.interpolate_velocity(ua, grid, Xa, m,
                                               ctx=ctx)

            lowered = jax.jit(interp).lower(u, X, mask)
        elif piece == "bucket_prep":
            if ib.fast is None:
                q.put({"skipped": "no fast engine -> no bucket prep"})
                return
            lowered = jax.jit(lambda Xa, m: ib.prepare(Xa, m)).lower(
                X, mask)
        elif piece == "refresh":
            # slot-preserving half-step refresh: the re-gather the
            # midpoint step pays INSTEAD of a second bucket_prep
            if ib.fast is None \
                    or getattr(ib.fast, "refresh", None) is None:
                q.put({"skipped": "engine has no refresh path"})
                return
            ctx0 = jax.jit(lambda Xa, m: ib.prepare(Xa, m))(X, mask)
            lowered = jax.jit(
                lambda c, Xa, m: ib.refresh(c, Xa, m)[0]).lower(
                    ctx0, X, mask)
        elif piece == "transfers_fused":
            # spread + 2x interp sharing ONE bucket prep — the step's
            # actual per-position transfer block, so op-boundary
            # effects (shared canonicalization, fused masks) show up
            F = jnp.zeros_like(X)
            u = state.ins.u

            def block(ua, Xa, Fa, m):
                ctx = ib.prepare(Xa, m)
                U1 = ib.interpolate_velocity(ua, grid, Xa, m, ctx=ctx)
                fv = ib.spread_force(Fa, grid, Xa, m, ctx=ctx)
                U2 = ib.interpolate_velocity(ua, grid, Xa, m, ctx=ctx)
                return U1, fv, U2

            lowered = jax.jit(block).lower(u, X, F, mask)
        else:
            raise ValueError(piece)

        # contraction + FFT censuses: the SHARED counting rules from
        # ibamr_tpu.analysis.graph_census (dot_census: operand bytes of
        # every dot_general — the (B,cap,P)/(B,cap,nz) einsum operands
        # ARE the claimed dominant traffic; fft_census: batched FFT
        # call count + per-transform bytes at the jaxpr PRIMITIVE level
        # — the CPU backend lowers lax.fft to a ducc custom-call, so an
        # HLO-text opcode census cannot see it)
        from ibamr_tpu.analysis.graph_census import dot_census, fft_census

        census = {"dot_lhs_bytes": 0, "dot_rhs_bytes": 0,
                  "dot_out_bytes": 0, "dot_count": 0, "dot_flops": 0,
                  "fft_ops": 0, "fft_bytes": 0, "fft_transforms": []}

        def _walk(jaxpr):
            census.update(fft_census(jaxpr))
            census.update(dot_census(jaxpr))

        try:
            if piece == "spread":
                cj = jax.make_jaxpr(spread)(X, F, mask)
            elif piece == "interp":
                cj = jax.make_jaxpr(interp)(u, X, mask)
            elif piece == "transfers_fused":
                cj = jax.make_jaxpr(block)(u, X, F, mask)
            elif piece == "step":
                cj = jax.make_jaxpr(lambda s: integ.step(s, dt))(state)
            elif piece in ("fluid", "fluid_bf16", "fluid_chained"):
                cj = jax.make_jaxpr(
                    lambda st, ff: integ.ins.step(st, dt, f=ff))(
                        state.ins, f)
            elif piece in ("substep", "substep_bf16",
                           "substep_chained"):
                cj = jax.make_jaxpr(sub)(rhs)
            elif piece == "refresh":
                cj = jax.make_jaxpr(
                    lambda c, Xa, m: ib.refresh(c, Xa, m)[0])(
                        ctx0, X, mask)
            else:
                cj = jax.make_jaxpr(
                    lambda Xa, m: ib.prepare(Xa, m))(X, mask)
            _walk(cj.jaxpr)
        except Exception as ce:  # census is best-effort
            census["census_error"] = f"{type(ce).__name__}: {ce}"

        compiled = lowered.compile()
        ca = compiled.cost_analysis() or {}
        if isinstance(ca, (list, tuple)):
            # older jax returns one properties dict per partition
            ca = ca[0] if ca else {}
        ma = compiled.memory_analysis()
        try:
            # scatter census: the round-5 tax the force-assembly gather
            # table and refresh path exist to eliminate
            ops = hlo_op_counts(compiled.as_text())
            scatter_ops = sum(v for k, v in ops.items()
                              if k.startswith("scatter"))
        except Exception:
            scatter_ops = None
        out = {
            "n": n,
            "markers": int(X.shape[0]),
            "engine": str(engine),
            "piece": piece,
            "flops": float(ca.get("flops", -1.0)),
            "bytes_accessed": float(ca.get("bytes accessed", -1.0)),
            "bytes_out": float(ca.get("bytes accessedout{}", -1.0)),
            "compile_s": round(time.perf_counter() - t0, 1),
            **census,
        }
        if scatter_ops is not None:
            out["scatter_ops"] = scatter_ops
        if ma is not None:
            out.update({
                "arg_bytes": int(ma.argument_size_in_bytes),
                "out_bytes": int(ma.output_size_in_bytes),
                "temp_bytes": int(ma.temp_size_in_bytes),
            })
        q.put(out)
    except Exception as e:  # noqa: BLE001 - report to parent
        q.put({"error": f"{type(e).__name__}: {e}",
               "engine": str(engine), "piece": piece, "n": n})


def run_leg(n, n_lat, n_lon, engine, piece, timeout_s):
    ctx = mp.get_context("spawn")
    q = ctx.Queue()
    p = ctx.Process(target=_leg_child,
                    args=(q, n, n_lat, n_lon, engine, piece))
    p.start()
    p.join(timeout_s)
    if p.is_alive():
        p.terminate()
        p.join(10)
        return {"error": f"timeout > {timeout_s:.0f}s",
                "engine": str(engine), "piece": piece, "n": n}
    try:
        return q.get_nowait()
    except Exception:
        return {"error": f"child died rc={p.exitcode}",
                "engine": str(engine), "piece": piece, "n": n}


ENGINES = {
    "scatter": False,
    "mxu": True,
    "packed": "packed",
    "packed_bf16": "packed_bf16",
    # round 6: pallas-spread + bf16-interp hybrid (XLA legs only — the
    # pallas spread has no CPU cost model; see module docstring)
    "hybrid_bf16": "hybrid_bf16",
}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=256)
    ap.add_argument("--n-lat", type=int, default=316)
    ap.add_argument("--n-lon", type=int, default=316)
    ap.add_argument("--quick-n", type=int, default=64,
                    help="small cross-check size (0 disables)")
    ap.add_argument("--timeout", type=float, default=2400.0)
    ap.add_argument("--out", type=str, required=True)
    ap.add_argument("--engines", type=str, default="",
                    help="comma-separated engine subset (default all)")
    ap.add_argument("--pieces", type=str, default="",
                    help="comma-separated piece subset (default all); "
                         "re-measured legs upsert into --out in place")
    args = ap.parse_args()
    args.pieces = ({s.strip() for s in args.pieces.split(",")}
                   if args.pieces else None)
    global ENGINES
    if args.engines:
        subset = {s.strip() for s in args.engines.split(",")}
        unknown = subset - set(ENGINES)
        if unknown:
            raise SystemExit(f"unknown engines {sorted(unknown)}")
        ENGINES = {k: v for k, v in ENGINES.items() if k in subset}

    legs = []
    sizes = ([(args.quick_n, 100, 100)] if args.quick_n else []) + \
        [(args.n, args.n_lat, args.n_lon)]
    for n, nla, nlo in sizes:
        for label, eng in ENGINES.items():
            if label.startswith("hybrid"):
                # only the XLA legs: spread is the pallas kernel
                pieces = ["interp", "bucket_prep", "refresh"]
            else:
                pieces = ["spread", "interp"]
                if eng is not False:
                    pieces.append("bucket_prep")
            if label in ("packed", "mxu"):
                pieces.append("transfers_fused")
            if label == "packed":
                pieces.append("step")
                # the fluid trio (whole ins.step) plus the isolated
                # substep trio (the solve alone): fused plan path vs
                # the pre-fusion chain vs the bf16 transform path —
                # the round-6 ">= 20% lower fluid-phase bytes" evidence
                pieces.extend(["fluid", "fluid_chained", "fluid_bf16",
                               "substep", "substep_chained",
                               "substep_bf16"])
                pieces.append("refresh")
            for piece in pieces:
                if args.pieces and piece not in args.pieces:
                    continue
                legs.append((n, nla, nlo, label, eng, piece))

    # merge-don't-clobber: an --engines subset run must not destroy
    # the fuller artifact's other legs (re-measured legs replace their
    # own (n, engine, piece) slot only)
    doc = {"note": (
        "XLA HLO cost_analysis on the host-CPU backend "
        "(same HLO structure as TPU; ratios between engines "
        "are the signal, absolute bytes are backend "
        "estimates). pallas engines excluded: interpret-mode "
        "lowering carries no cost model."), "legs": []}
    if os.path.exists(args.out):
        try:
            with open(args.out) as f:
                doc = json.load(f)
        except Exception:
            pass

    def upsert(r):
        key = (r.get("n"), r.get("engine"), r.get("piece"))
        doc["legs"] = [x for x in doc["legs"]
                       if (x.get("n"), x.get("engine"),
                           x.get("piece")) != key]
        doc["legs"].append(r)

    for i, (n, nla, nlo, label, eng, piece) in enumerate(legs):
        print(f"[audit] {i + 1}/{len(legs)}: n={n} engine={label} "
              f"piece={piece}", flush=True)
        r = run_leg(n, nla, nlo, eng, piece, args.timeout)
        r["engine"] = label
        print(f"[audit]   -> {json.dumps(r)}", flush=True)
        upsert(r)
        with open(args.out, "w") as f:
            json.dump(doc, f, indent=1)
    print(f"[audit] wrote {args.out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
