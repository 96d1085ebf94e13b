"""Headline benchmark: IB/explicit/ex4-equivalent 3D elastic shell.

Measures coupled IB timesteps/sec (interp -> force -> spread -> INS
projection solve -> correct) on the BASELINE.json north-star config:
256^3 grid, ~1e5 markers, IB_4 delta. Prints ONE JSON line (last line of
stdout); all progress goes to stderr.

Behaviour:
- the backend comes from ``auto_backend()``: without a chip (and without
  an explicit ``JAX_PLATFORMS=cpu``) the run FAILS;
- sizes are staged (64^3 -> 128^3 -> 256^3) so a late-stage OOM/timeout
  still leaves a real number from the largest completed stage;
- a JSON line is ALWAYS emitted — on total failure it carries an
  ``error`` field;
- the MXU-bucketed and scatter/gather spread-interp paths are compared
  at a mid stage (``mxu_vs_scatter``).

``vs_baseline``: BASELINE.json ``published`` is empty and the reference
mount was empty at survey time (SURVEY.md §6) — no measured reference
denominator exists, so vs_baseline stays null.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def enable_compile_cache(jax) -> None:
    """Persistent XLA compilation cache for the CPU reference children
    (the parent gets it from ``auto_backend()``). One policy for the
    whole repo: ``serve/aot_cache.enable_persistent_cache``."""
    from ibamr_tpu.serve.aot_cache import enable_persistent_cache
    enable_persistent_cache(jax)


def _run_guarded_child(target, child_args, timeout_s: float,
                       hang_msg: str, died_what: str):
    """Run ``target(q, *child_args)`` in a TERMINABLE spawn child and
    return its queued dict, {'error': hang_msg} on timeout, or
    {'error': ...} if the child died without reporting. Shared by every
    CPU reference child (each forces the CPU before importing jax and
    never needs the chip)."""
    import multiprocessing as mp

    ctx = mp.get_context("spawn")
    q = ctx.Queue()
    p = ctx.Process(target=target, args=(q, *child_args))
    p.start()
    p.join(timeout_s)
    if p.is_alive():
        p.terminate()
        p.join(10.0)
        return {"error": hang_msg}
    try:
        return q.get_nowait()
    except Exception:
        return {"error": f"{died_what} child died rc={p.exitcode}"}


def _cpu_sharded_child(q, n, n_lat, n_lon, steps, warmup, dt,
                       n_devices):
    """Child body: time the FLAGSHIP sharded step on an n_devices
    virtual host-CPU mesh (VERDICT round 3 item 8 — the
    chip-independent regression signal)."""
    try:
        from ibamr_tpu.utils.backend_guard import force_cpu

        jax = force_cpu(n_devices)
        enable_compile_cache(jax)
        import time as _t

        from ibamr_tpu.models.shell3d import build_shell_example
        from ibamr_tpu.parallel import make_mesh, make_sharded_ib_step
        from ibamr_tpu.parallel.mesh import place_state

        integ, state0 = build_shell_example(
            n_cells=n, n_lat=n_lat, n_lon=n_lon, radius=0.25,
            aspect=1.2, stiffness=1.0, rest_length_factor=0.75,
            mu=0.05)

        def timed(step_fn, state):
            t0 = _t.perf_counter()
            for _ in range(warmup):
                state = step_fn(state, dt)
            jax.block_until_ready(state)
            compile_s = _t.perf_counter() - t0
            t0 = _t.perf_counter()
            for _ in range(steps):
                state = step_fn(state, dt)
            jax.block_until_ready(state)
            return _t.perf_counter() - t0, compile_s

        mesh = make_mesh(n_devices)
        state = place_state(state0, integ.ins.grid, mesh)
        el_sh, compile_s = timed(make_sharded_ib_step(integ, mesh),
                                 state)
        # single-device leg of the same step: the only scaling signal
        # available without multi-chip hardware (VERDICT round 3 weak
        # #4 — "no scaling measurement exists anywhere"). Virtual CPU
        # devices share the host's cores, so the ratio reads as an
        # SPMD-overhead bound, not real chip scaling; it still catches
        # a sharded-path regression that the single-device number hides
        el_1, _ = timed(jax.jit(lambda s, d: integ.step(s, d)), state0)
        q.put({"n": n, "n_devices": n_devices,
               "markers": n_lat * n_lon,
               "steps_per_sec": round(steps / el_sh, 3),
               "ms_per_step": round(1e3 * el_sh / steps, 3),
               "single_device_steps_per_sec": round(steps / el_1, 3),
               # >1 means the sharded step is FASTER than single-device
               # (a speedup, renamed from 'sharded_over_single' whose
               # name read as the inverse ratio — ADVICE round 4)
               "sharded_speedup": round(el_1 / el_sh, 3),
               "compile_warmup_s": round(compile_s, 2)})
    except Exception as e:  # noqa: BLE001 - report, parent decides
        q.put({"error": f"{type(e).__name__}: {e}"})


def cpu_sharded_reference(timeout_s: float = 300.0, n: int = 32,
                          n_lat: int = 24, n_lon: int = 24,
                          steps: int = 10, warmup: int = 2,
                          dt: float = 5e-5, n_devices: int = 8):
    """chip-independent perf signal (VERDICT round 3 item 8): the
    8-virtual-device sharded flagship step timed on the host CPU in a
    child process, emitted EVERY round regardless of the accelerator's
    health — so a stage regression stays visible across rounds whose
    TPU platform differs. Small fixed shape
    (32^3, ~600 markers) keeps it a bounded smoke-timing, not a
    benchmark of the host."""
    return _run_guarded_child(
        _cpu_sharded_child,
        (n, n_lat, n_lon, steps, warmup, dt, n_devices), timeout_s,
        f"cpu sharded reference hung > {timeout_s:.0f}s", "cpu sharded")


def _fleet_child(q, B, n, n_lat, n_lon, steps, dt):
    """Child body: aggregate throughput of B ensemble lanes through ONE
    vmapped chunk vs the same lanes run one at a time (PR 7 fleet
    mode), on a single virtual CPU device so the signal is
    chip-independent like the sharded reference."""
    try:
        import sys as _sys
        _sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from ibamr_tpu.utils.backend_guard import force_cpu

        jax = force_cpu(1)
        enable_compile_cache(jax)
        from ibamr_tpu.utils.hierarchy_driver import RunConfig
        from tools.fleet import build_fleet, run_fleet, run_sequential

        cfg = RunConfig(dt=dt, num_steps=steps, health_interval=4)
        integ, lane_states, stacked = build_fleet(
            n, n_lat, n_lon, 0.05, B, 0.01, None)
        summary, _ = run_fleet(integ, stacked, cfg, B)
        seq = run_sequential(integ, lane_states, cfg)
        out = {"lanes": B, "n": n, "markers": n_lat * n_lon,
               "steps": steps,
               "aggregate_steps_per_s":
                   summary["aggregate_steps_per_s"],
               "lanes_quarantined": summary["lanes_quarantined"],
               "sequential_steps_per_s":
                   seq["aggregate_steps_per_s"]}
        if seq["aggregate_steps_per_s"] > 0:
            out["fleet_speedup"] = round(
                summary["aggregate_steps_per_s"]
                / seq["aggregate_steps_per_s"], 3)
        q.put(out)
    except Exception as e:  # noqa: BLE001 - report, parent decides
        q.put({"error": f"{type(e).__name__}: {e}"})


def fleet_reference(B: int = 8, timeout_s: float = 600.0, n: int = 32,
                    n_lat: int = 16, n_lon: int = 16, steps: int = 8,
                    dt: float = 1e-3):
    """Vmapped-ensemble throughput signal (PR 7): B lanes of the small
    shell stepped as one lane-batched fleet vs sequentially, in a
    TERMINABLE child. Small fixed shape — a bounded smoke-timing whose
    quarantine count doubles as a fleet-health regression check (a
    healthy run must report 0)."""
    return _run_guarded_child(
        _fleet_child, (B, n, n_lat, n_lon, steps, dt), timeout_s,
        f"fleet leg hung > {timeout_s:.0f}s", "fleet")


def _fleet_mesh_child(q, Bs, n, n_lat, n_lon, steps, dt, n_devices):
    """Child body: the B×D pod-fleet leg (PR 16) — the lane axis of a
    B-lane fleet sharded over ``n_devices`` virtual CPU devices
    (``parallel.mesh.make_lane_mesh``), aggregate lane-steps/s per B.
    chip-independent like the sharded reference; on a real pod the
    same call times ICI-resident lanes."""
    try:
        import sys as _sys
        _sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from ibamr_tpu.utils.backend_guard import force_cpu

        jax = force_cpu(n_devices)
        enable_compile_cache(jax)
        from ibamr_tpu.parallel.mesh import make_lane_mesh
        from ibamr_tpu.utils.hierarchy_driver import RunConfig
        from tools.fleet import build_fleet, run_fleet

        mesh = make_lane_mesh(n_devices)
        cfg = RunConfig(dt=dt, num_steps=steps, health_interval=4)
        legs = []
        for B in Bs:
            integ, _, stacked = build_fleet(
                n, n_lat, n_lon, 0.05, B, 0.01, None)
            summary, _ = run_fleet(integ, stacked, cfg, B,
                                   lane_mesh=mesh)
            legs.append({
                "lanes": B,
                "lanes_per_device": B // n_devices,
                "aggregate_steps_per_s":
                    summary["aggregate_steps_per_s"],
                "lanes_quarantined": summary["lanes_quarantined"],
                "wall_s": summary["wall_s"]})
        q.put({"n": n, "markers": n_lat * n_lon, "steps": steps,
               "mesh_devices": n_devices, "legs": legs})
    except Exception as e:  # noqa: BLE001 - report, parent decides
        q.put({"error": f"{type(e).__name__}: {e}"})


def fleet_mesh_reference(Bs=(8, 64, 256), timeout_s: float = 900.0,
                         n: int = 16, n_lat: int = 8, n_lon: int = 16,
                         steps: int = 4, dt: float = 1e-3,
                         n_devices: int = 8):
    """Pod-fleet throughput signal (PR 16): aggregate lane-steps/s of
    B∈{8,64,256} lanes sharded over the 8-device lane mesh, in a
    TERMINABLE child. Small fixed shape — a bounded smoke-timing on
    CPU whose per-B trend (and 0-quarantine invariant) is tracked
    across rounds."""
    return _run_guarded_child(
        _fleet_mesh_child,
        (tuple(Bs), n, n_lat, n_lon, steps, dt, n_devices), timeout_s,
        f"fleet-mesh leg hung > {timeout_s:.0f}s", "fleet-mesh")


def _serve_child(q, n, n_lat, n_lon, lanes, steps, dt, warm_requests):
    """Child body: the request-to-first-step latency drill — one
    scenario family served cold then warm through a fresh warm-pool
    router (ibamr_tpu/serve/router.py), on a single virtual CPU device
    so the signal is chip-independent like the sharded reference."""
    try:
        import sys as _sys
        _sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from ibamr_tpu.utils.backend_guard import force_cpu

        jax = force_cpu(1)
        enable_compile_cache(jax)
        from ibamr_tpu.serve.router import cold_warm_drill

        q.put(cold_warm_drill(n_cells=n, n_lat=n_lat, n_lon=n_lon,
                              lanes=lanes, steps=steps, dt=dt,
                              warm_requests=warm_requests))
    except Exception as e:  # noqa: BLE001 - report, parent decides
        q.put({"error": f"{type(e).__name__}: {e}"})


def serve_reference(timeout_s: float = 300.0, n: int = 16,
                    n_lat: int = 8, n_lon: int = 16, lanes: int = 2,
                    steps: int = 3, dt: float = 5e-5,
                    warm_requests: int = 8):
    """Cold-vs-warm serving latency signal (PR 12): request-to-first-
    step latency of the warm-pool router, cold (bucket compiles on
    miss) vs warm (AOT cache hit), in a TERMINABLE child. The same
    drill that SERVE_CONTRACT.json pins structurally
    (``tools/serve.py check``); here it rides the bench artifact so the
    cold/warm ratio is trended across rounds. ``warm_requests`` extra
    warm serves (PR 14) give the drill's ``warm_p50_s``/``warm_p99_s``
    histogram percentiles a real sample, and the per-key histogram
    snapshot rides the artifact for ``tools/obs.py compare``."""
    return _run_guarded_child(
        _serve_child, (n, n_lat, n_lon, lanes, steps, dt,
                       warm_requests), timeout_s,
        f"serve leg hung > {timeout_s:.0f}s", "serve")


def _tune_child(q, n, n_lat, n_lon, reps):
    """Child body: a small measured autotuner grid (ibamr_tpu/tune/)
    on a single virtual CPU device — scatter vs packed across both
    spectral dtypes, trials compiled through the AOT cache."""
    try:
        import sys as _sys
        _sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from ibamr_tpu.utils.backend_guard import force_cpu

        jax = force_cpu(1)
        enable_compile_cache(jax)
        from ibamr_tpu.tune.runner import search

        res = search(n_cells=n, n_lat=n_lat, n_lon=n_lon,
                     engines=("scatter", "packed"),
                     spectral_dtypes=("f32", "bf16"),
                     chunk_lengths=(1,), reps=reps, probe=False)
        q.put(res.to_dict())
    except Exception as e:  # noqa: BLE001 - report, parent decides
        q.put({"error": f"{type(e).__name__}: {e}"})


def tune_reference(timeout_s: float = 300.0, n: int = 16,
                   n_lat: int = 8, n_lon: int = 16, reps: int = 2):
    """Measured engine-search signal (PR 13): the autotuner's small
    CPU grid in a TERMINABLE child. Trends the measured ranking and
    margins across rounds next to the serve leg; the full on-chip
    search + DB publication is ``tools/tune.py search --publish``."""
    return _run_guarded_child(
        _tune_child, (n, n_lat, n_lon, reps), timeout_s,
        f"tune leg hung > {timeout_s:.0f}s", "tune")


def _soak_child(q, rates, durations, seed, burst):
    """Child body: the open-loop soak grid — one pre-warmed router
    and executable cache SHARED across the rate x duration cells (the
    grid measures traffic handling, not recompilation), seeded
    Poisson + burst arrivals over the heavy-tailed mix on a single
    virtual CPU device."""
    try:
        import sys as _sys
        _sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from ibamr_tpu.utils.backend_guard import force_cpu

        jax = force_cpu(1)
        enable_compile_cache(jax)
        from ibamr_tpu.serve import aot_cache
        from ibamr_tpu.serve.loadgen import SOAK_POLICIES, soak_drill
        from ibamr_tpu.serve.router import BucketSpec, WarmPoolRouter

        spec = BucketSpec(n_cells=8, n_lat=6, n_lon=8, lanes=2,
                          chunk_steps=2)
        router = WarmPoolRouter([spec],
                                cache=aot_cache.ExecutableCache(),
                                allow_dynamic=True,
                                policies=dict(SOAK_POLICIES))
        router.warm(spec)
        cells = []
        for rate in rates:
            for dur in durations:
                out = soak_drill(seed=seed, duration_s=dur,
                                 rate_rps=rate, burst_factor=burst,
                                 time_scale=0.5, router=router)
                cells.append({
                    "rate_rps": rate, "duration_s": dur,
                    "arrivals": out["arrivals"],
                    "requests_per_s": out["requests_per_s"],
                    "shed_rate": out["shed_rate"],
                    "warm_first_step_p99_s":
                        out["warm_first_step_p99_s"],
                    "queue_wait_p99_s": out["queue_wait_p99_s"],
                    "hung_threads": out["hung_threads"]})
        q.put({"seed": seed, "burst_factor": burst, "grid": cells})
    except Exception as e:  # noqa: BLE001 - report, parent decides
        q.put({"error": f"{type(e).__name__}: {e}"})


def soak_reference(timeout_s: float = 300.0,
                   rates=(4.0, 8.0), durations=(4.0,),
                   seed: int = 0, burst: float = 4.0):
    """Sustained-traffic signal (PR 17): the open-loop Poisson+burst
    soak over an arrival-rate x duration grid in a TERMINABLE child —
    requests/s, shed rate, and warm/queue-wait p99 per cell land in
    the round artifact so traffic capacity is trended across rounds
    next to the single-request serve leg. The chaos-injected variant
    lives in ``tools.fault_injection.run_soak_smoke`` (dryrun path
    21); this leg is the clean-path capacity number."""
    return _run_guarded_child(
        _soak_child, (tuple(rates), tuple(durations), seed, burst),
        timeout_s, f"soak leg hung > {timeout_s:.0f}s", "soak")


def _elastic_child(q, duration_s, rate_rps, shift_frac):
    """Child body: the elastic warm-pool drill (mix shift + memory
    pressure + crash-safe restart) on a single virtual CPU device;
    the drill's own pinned invariants raise inside the child and
    surface as the leg's error string."""
    try:
        import sys as _sys
        _sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from ibamr_tpu.utils.backend_guard import force_cpu

        force_cpu(1)
        from tools.fault_injection import run_elastic_smoke

        out = run_elastic_smoke(duration_s=duration_s,
                                rate_rps=rate_rps,
                                shift_frac=shift_frac)
        q.put({"duration_s": duration_s, "rate_rps": rate_rps,
               "shift_frac": shift_frac,
               "scale_up_s": out["scale_up_s"],
               "restart_warm_s": out["restart_warm_s"],
               "restart_fresh_compiles":
                   out["restart_fresh_compiles"],
               "mode_transitions": out["mode_transitions"],
               "grows": out["grows"], "shrinks": out["shrinks"],
               "shed": out["shed"], "lost": out["lost"],
               "predicted_rps": out["predicted_rps"],
               "measured_rps": out["measured_rps"]})
    except Exception as e:  # noqa: BLE001 - report, parent decides
        q.put({"error": f"{type(e).__name__}: {e}"})


def elastic_reference(timeout_s: float = 300.0,
                      duration_s: float = 5.0, rate_rps: float = 8.0,
                      shift_frac: float = 0.4):
    """Elasticity signal (PR 18): scale-up latency, restart-to-warm
    time, fresh restart compiles (must stay 0), and the capacity
    model's predicted-vs-measured rps from the elastic warm-pool
    drill in a TERMINABLE child — trended across rounds next to the
    soak leg so a scaling or restart regression shows up as a number,
    not an incident."""
    return _run_guarded_child(
        _elastic_child, (duration_s, rate_rps, shift_frac),
        timeout_s, f"elastic leg hung > {timeout_s:.0f}s", "elastic")


def _assim_child(q, fleet_sizes, cycles):
    """Child body: the CLEAN assimilation cadence (no injectors) on a
    single virtual CPU device — one twin-experiment miniature, then
    for each ensemble size B a full supervised observe->analyze->
    advance run with an attached ledger, reporting the analysis wall
    (first cycle pays the AOT compile; steady state is the recurring
    bill) against the chunk cadence and cycles/s. The chaos-injected
    variant lives in ``tools.fault_injection.run_assim_smoke``; this
    leg is the clean-path cost number."""
    try:
        import sys as _sys
        _sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from ibamr_tpu.utils.backend_guard import force_cpu

        force_cpu(1)
        import tempfile as _tempfile

        import jax
        if not jax.config.jax_enable_x64:
            jax.config.update("jax_enable_x64", True)
        import jax.numpy as jnp

        from ibamr_tpu import obs as _obs
        from ibamr_tpu.assim import (AssimConfig, AssimilationCycle,
                                     ObservationOperator,
                                     synthesize_batches)
        from ibamr_tpu.instruments import InstrumentPanel, make_meters
        from ibamr_tpu.models.shell3d import build_shell_example
        from ibamr_tpu.serve.aot_cache import ExecutableCache
        from ibamr_tpu.utils.health import HealthProbe
        from ibamr_tpu.utils.lanes import stack_lanes

        spc, dt0, n_lon = 2, 1e-3, 16
        integ, st0 = build_shell_example(n_cells=16, n_lat=8,
                                         n_lon=n_lon, mu=0.05,
                                         dtype="float64")
        loops = [[2 * n_lon + j for j in range(n_lon)],
                 [5 * n_lon + j for j in range(n_lon)]]
        panel = InstrumentPanel(integ.ins.grid,
                                make_meters(loops, closed=True,
                                            dtype=jnp.float64))
        op = ObservationOperator(panel)
        st, truth = st0, []
        for _ in range(cycles):
            for _ in range(spc):
                st = integ.step(st, dt0)
            truth.append(st)
        batches = synthesize_batches(op, truth, sigma=1e-5, seed=3)

        legs = []
        for B in fleet_sizes:
            fleet0 = stack_lanes([st0._replace(ins=st0.ins._replace(
                u=tuple(c + 2e-3 * (i + 1) for c in st0.ins.u)))
                for i in range(B)])
            cyc = AssimilationCycle(
                integ, op, B,
                AssimConfig(steps_per_cycle=spc, dt=dt0),
                probe=HealthProbe.for_integrator(integ),
                cache=ExecutableCache())
            with _tempfile.TemporaryDirectory(
                    prefix="bench-assim-") as td:
                lp = os.path.join(td, "ledger.jsonl")
                t0 = time.perf_counter()
                with _obs.ledger(lp):
                    cyc.run(fleet0, batches, directory=td,
                            max_retries=1)
                wall = time.perf_counter() - t0
                recs = list(_obs.read_ledger(lp))
            walls = [r["analysis_wall_s"] for r in recs
                     if r.get("kind") == "assim_cycle"
                     and not r.get("skipped")
                     and r.get("analysis_wall_s") is not None]
            steady = walls[1:] or walls
            legs.append({
                "lanes": B, "cycles": len(walls),
                "analysis_wall_first_s": round(walls[0], 4),
                "analysis_wall_steady_s": round(
                    sum(steady) / len(steady), 4),
                "analysis_fraction": round(sum(walls) / wall, 4),
                "cycles_per_s": round(len(walls) / wall, 4),
                "wall_s": round(wall, 3)})
        q.put({"steps_per_cycle": spc, "legs": legs})
    except Exception as e:  # noqa: BLE001 - report, parent decides
        q.put({"error": f"{type(e).__name__}: {e}"})


def assim_reference(timeout_s: float = 420.0,
                    fleet_sizes=(8, 64), cycles: int = 3):
    """Forecasting-cadence signal (PR 20): per-cycle analysis wall
    against the advance cadence and cycles/s for a small and a large
    ensemble from the clean assimilation run in a TERMINABLE child —
    trended across rounds next to the soak/elastic/grad legs so a
    regression in the between-chunk analysis cost (an accidental
    retrace, a host sync creeping into the gain computation) shows up
    as a number, not an incident."""
    return _run_guarded_child(
        _assim_child, (tuple(fleet_sizes), cycles), timeout_s,
        f"assim leg hung > {timeout_s:.0f}s", "assim")


def _grad_child(q, n, reps):
    """Child body: the gradient microbench (PR 19) on a single
    virtual CPU device — primal-vs-VJP wall time and the FFT /
    scatter / f64-widening census for the fused substep, the packed
    transfers, and the whole coupled step."""
    try:
        import sys as _sys
        _sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from ibamr_tpu.utils.backend_guard import force_cpu

        force_cpu(1)
        from tools.microbench_grad import run as grad_run

        out = grad_run(n=n, reps=reps, quiet=True)
        keep = {"n", "backend"}
        for piece in ("substep", "spread", "interp", "step"):
            keep.update({f"{piece}_primal_ms", f"{piece}_vjp_ms",
                         f"{piece}_grad_ratio",
                         f"{piece}_primal_fft_ops",
                         f"{piece}_vjp_fft_ops",
                         f"{piece}_vjp_scatter_prims"})
        slim = {k: (round(v, 3) if isinstance(v, float) else v)
                for k, v in out.items() if k in keep}
        # the VJP graph replays the primal forward (overflow-fallback
        # scatters included); the pinned claim is that the REVERSE
        # sweep adds none on the spread path, so report the delta
        slim["spread_vjp_scatter_added"] = (
            out.get("spread_vjp_scatter_prims", 0)
            - out.get("spread_primal_scatter_prims", 0))
        slim["f64_widenings_total"] = sum(
            v for k, v in out.items() if k.endswith("f64_widenings"))
        q.put(slim)
    except Exception as e:  # noqa: BLE001 - report, parent decides
        q.put({"error": f"{type(e).__name__}: {e}"})


def grad_reference(timeout_s: float = 300.0, n: int = 24,
                   reps: int = 3):
    """Adjoint-cost signal (PR 19): VJP-vs-primal wall ratio plus the
    batched-FFT and scatter counts per differentiable piece from the
    gradient microbench in a TERMINABLE child — trended across rounds
    so a reverse-pass cost regression (an extra transpose FFT, a
    scatter sneaking into the spread adjoint, an f64 widening) shows
    up as a number next to the forward flagship legs."""
    return _run_guarded_child(
        _grad_child, (n, reps), timeout_s,
        f"grad leg hung > {timeout_s:.0f}s", "grad")


def cpu_sharded_reference_with_trend(n_devices: int = 8):
    """The n=32 smoke leg PLUS a larger n=48 leg, with the
    speedup-vs-size trend (round 5, VERDICT round 4 weak #3: the
    sub-1 ratio needed an explanation, not just a number). On ONE
    physical host core, 8 virtual devices add partitioner-inserted
    reshard/collective passes over field-scale data, so the sharded
    step can never beat single-device here; the RISING two-leg trend
    shows the overhead is a CONSTANT-FACTOR cost that amortizes as
    per-step compute grows — a fixed tax, not a scaling defect. (The
    offline three-point sweep in PERF.md measured 0.17 -> 0.33 ->
    0.38 at n = 32, 48, 64; the in-bench artifact carries the 32/48
    pair to stay inside the deadline.) On real multi-chip hardware
    the same pins become ICI collectives and the ratio crosses 1; the
    equality tests pin correctness either way."""
    leg32 = cpu_sharded_reference(timeout_s=420.0, n=32, n_lat=24,
                                  n_lon=24, steps=6,
                                  n_devices=n_devices)
    out = dict(leg32)
    leg48 = cpu_sharded_reference(timeout_s=900.0, n=48, n_lat=32,
                                  n_lon=32, steps=6,
                                  n_devices=n_devices)
    out["legs"] = [leg32, leg48]
    s32 = leg32.get("sharded_speedup")
    s48 = leg48.get("sharded_speedup")
    if s32 is not None and s48 is not None:
        out["speedup_trend_32_to_48"] = round(s48 - s32, 3)
        out["trend_note"] = (
            "virtual devices share one host core: <1 is expected; "
            "the RISING trend with n shows constant-factor SPMD "
            "overhead amortizing, not a scaling defect")
    return out


def git_short_rev() -> str:
    """The repo's short commit hash (``norev`` outside git): profile
    captures are named ``<stage>_<rev>`` so two revisions' traces of
    the same stage sit side by side in one TensorBoard logdir."""
    try:
        import subprocess
        out = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10,
            cwd=os.path.dirname(os.path.abspath(__file__))).stdout
        return out.strip() or "norev"
    except Exception:
        return "norev"


def stage_profile_dir(args, label: str, rev: str,
                      used=None) -> str:
    """Capture dir for one stage under ``--profile-stages``, or ``""``
    (no capture). ``--profile-stages`` is a comma-separated list of
    fnmatch globs over stage labels — ramp stages are ``n<size>``
    (``n256``), flagship legs their engine label (``packed*``).

    ``used`` (a per-run dict the caller owns) de-collides repeated
    labels: two stages sharing a label under the same rev used to get
    the SAME dir, interleaving their traces into one unusable capture
    — now the repeat gets a ``_2``/``_3`` suffix and a warning."""
    import fnmatch
    if not args.profile or not args.profile_stages:
        return ""
    pats = [p.strip() for p in args.profile_stages.split(",")
            if p.strip()]
    if not any(fnmatch.fnmatch(label, p) for p in pats):
        return ""
    d = os.path.join(args.profile, f"{label}_{rev}")
    if used is not None:
        n = used.get(d, 0) + 1
        used[d] = n
        if n > 1:
            log(f"[bench] profile label {label!r} repeats under rev "
                f"{rev}; capturing into {label}_{rev}_{n} instead")
            d = f"{d}_{n}"
    return d


def run_engine_leg(jax, label, engine, n, n_lat, n_lon, args, t_start,
                   platform, profile_dir=None):
    """One transfer-engine leg at size ``n``, in this process (the one
    that holds the chip). Shared by the flagship shootout and the
    mid-size compare. ``profile_dir`` arms the in-stage device
    capture."""
    if label == "fluid_bf16":
        # mixed-precision FLUID leg: the best non-pallas transfer
        # engine (packed_bf16) plus bf16/split-real spectral
        # transforms — the round-6 lever aimed at the fluid_solve
        # floor itself
        return run_stage(jax, n, n_lat, n_lon, args.steps, args.warmup,
                         args.dt, use_fast="packed_bf16",
                         spectral_dtype="bf16", profile_dir=profile_dir,
                         profile_stage=label)
    return run_stage(jax, n, n_lat, n_lon, args.steps, args.warmup,
                     args.dt, use_fast=engine, profile_dir=profile_dir,
                     profile_stage=label)


def phase_breakdown(jax, integ, state, dt: float, iters: int = 10) -> dict:
    """Per-phase ms/step on the current device: bucket prep (+ the
    half-step slot-preserving refresh when the engine has one), interp,
    force, spread, fluid solve — the TimerManager-style table SURVEY §6
    asks for. ``bucket_prep_per_step`` records how many full preps the
    midpoint step actually pays (1 with refresh, 2 without). Each phase is jitted standalone; the sum differs from the
    fused step (XLA fuses across phases there), so the table names the
    dominant phase rather than reconstructing the exact step time."""
    import time as _t

    grid = integ.ins.grid
    ib = integ.ib
    mask = state.mask
    out = {}

    def timeit(name, fn, *args):
        res = fn(*args)
        jax.block_until_ready(res)  # compile + warm
        t0 = _t.perf_counter()
        for _ in range(iters):
            res = fn(*args)
        jax.block_until_ready(res)
        out[name] = round(1e3 * (_t.perf_counter() - t0) / iters, 3)
        return res

    ctx = None
    if getattr(ib, "fast", None) is not None:
        ctx = timeit("bucket_prep",
                     jax.jit(lambda X: ib.prepare(X, mask)), state.X)
        refresh = getattr(ib, "refresh", None)
        refreshes = (refresh is not None
                     and refresh(ctx, state.X, mask)[0] is not None)
        if refreshes:
            # slot-preserving half-step refresh: with it the midpoint
            # step pays bucket_prep ONCE per step (plus this cheaper
            # re-gather); without it, twice
            timeit("bucket_refresh",
                   jax.jit(lambda c, X: refresh(c, X, mask)[0]),
                   ctx, state.X)
        out["bucket_prep_per_step"] = 1 if refreshes else 2
    U = timeit("interp",
               jax.jit(lambda u, X, c: ib.interpolate_velocity(
                   u, grid, X, mask, ctx=c)),
               state.ins.u, state.X, ctx)
    F = timeit("force",
               jax.jit(lambda X, U: ib.compute_force(X, U, 0.0)),
               state.X, U)
    f = timeit("spread",
               jax.jit(lambda F, X, c: ib.spread_force(
                   F, grid, X, mask, ctx=c)),
               F, state.X, ctx)
    timeit("fluid_solve",
           jax.jit(lambda s, f: integ.ins.step(s, dt, f=f)),
           state.ins, f)
    if getattr(integ.ins, "fused_stokes", None) is not None:
        # spectral decomposition of the fluid substep: transform cost
        # (the batched rfftn/irfftn pair) vs the diagonal k-space
        # algebra between them — names WHICH half of the fluid floor
        # the next lever must attack (transform-bound means only
        # precision/sharding moves it; algebra-bound means fusion does)
        from ibamr_tpu.solvers import spectral_plan

        jnp_ = jax.numpy
        dim = len(grid.n)
        axes = tuple(range(1, dim + 1))
        plan = spectral_plan.get_plan(grid.n, grid.dx, integ.ins.dtype)
        alpha = integ.ins.rho / dt
        beta = -0.5 * integ.ins.mu
        spec = {}

        def timeit_s(name, fn, *a):
            res = fn(*a)
            jax.block_until_ready(res)
            t0 = _t.perf_counter()
            for _ in range(iters):
                res = fn(*a)
            jax.block_until_ready(res)
            spec[name] = round(1e3 * (_t.perf_counter() - t0) / iters, 3)
            return res

        x = jnp_.stack(state.ins.u)
        uh = timeit_s("fwd_transform",
                      jax.jit(lambda x: jnp_.fft.rfftn(x, axes=axes)), x)
        outh = timeit_s("kspace_algebra",
                        jax.jit(lambda uh: plan.kspace_algebra(
                            uh, alpha, beta, (alpha, beta))), uh)
        timeit_s("inv_transform",
                 jax.jit(lambda oh: jnp_.fft.irfftn(
                     oh, s=grid.n, axes=axes)), outh)
        spec["transform_ms"] = round(spec["fwd_transform"]
                                     + spec["inv_transform"], 3)
        out["spectral"] = spec
    out["dominant"] = max(
        (k for k in out
         if k not in ("dominant", "bucket_prep_per_step", "spectral")),
        key=lambda k: out[k])
    return out


def run_stage(jax, n: int, n_lat: int, n_lon: int, steps: int,
              warmup: int, dt: float, use_fast=None,
              fast_opts=None, spectral_dtype=None,
              record_dir=None, profile_dir=None,
              profile_stage=None) -> dict:
    """Build the shell config at one grid size and time the jitted step.
    ``fast_opts=(tile, cap)`` overrides the MXU engine geometry (the
    cap/tile sweep); ``spectral_dtype="bf16"`` opts the fluid substep
    into the mixed-precision transform path. ``record_dir`` arms a
    flight recorder on the stage: the pre-run state is snapshotted
    (host-side, before donation can invalidate it) and a non-finite
    finish dumps a ``record_dir/incidents`` replay capsule carrying the
    exact factory spec — ``tools/replay.py`` rebuilds the stage from it
    offline (docs/RESILIENCE.md).

    ``profile_dir`` captures a device profile of the MEASURED loop
    only — the capture starts after compile+warmup, because the
    trace-viewer JSON export caps at 1e6 events and a multi-second
    XLA compile floods it with python-tracer events, truncating the
    device-op events attribution needs (measured: an 8 s in-capture
    compile left 25 op events of a 4-step run)."""
    from ibamr_tpu.models.shell3d import build_shell_example

    integ, state = build_shell_example(
        n_cells=n, n_lat=n_lat, n_lon=n_lon,
        radius=0.25, aspect=1.2, stiffness=1.0, rest_length_factor=0.75,
        mu=0.05, use_fast_interaction=use_fast,
        spectral_dtype=spectral_dtype)
    recorder = None
    if record_dir:
        from ibamr_tpu.utils.flight_recorder import (FlightRecorder,
                                                     factory_spec)
        recorder = FlightRecorder(capacity=1, spec=factory_spec(
            "ibamr_tpu.models.shell3d", "build_shell_example",
            n_cells=n, n_lat=n_lat, n_lon=n_lon, radius=0.25,
            aspect=1.2, stiffness=1.0, rest_length_factor=0.75,
            mu=0.05, use_fast_interaction=use_fast,
            spectral_dtype=spectral_dtype))
        recorder.snapshot(state, step=0, dt=dt, length=warmup + steps,
                          integ=integ)
    if fast_opts is not None:
        from ibamr_tpu.ops.interaction_fast import FastInteraction
        tile, cap = fast_opts
        integ.ib.fast = FastInteraction(
            integ.ins.grid, kernel=integ.ib.kernel, tile=tile, cap=cap,
            overflow_cap=max(2048, state.X.shape[0] // 4))

    # donate the state: the step rewrites every field, so reusing the
    # input buffers saves one full state allocation per step (~0.5 GB
    # of HBM traffic at 256^3). step_with_stats rides the refresh_hit
    # flag out beside the state (None when the engine has no
    # slot-preserving half-step refresh). The executable comes through
    # the AOT cache (one compile per fingerprint+aval family, shared
    # with the warm-pool router); fast_opts changes constants baked
    # into the graph without changing input avals, so it must be in
    # the key.
    from ibamr_tpu.serve import aot_cache

    cache_before = aot_cache.executable_cache_stats()
    t_aot = time.perf_counter()
    step, _entry = aot_cache.cached_step(
        integ, state, dt, donate=True, with_stats=True,
        extra={"fast_opts": list(fast_opts) if fast_opts else None},
        label=f"bench:n{n}")
    aot_s = time.perf_counter() - t_aot
    cache_after = aot_cache.executable_cache_stats()

    from ibamr_tpu.utils.timers import profile_trace

    def timed_run(capture_dir=""):
        nonlocal state
        t_c0 = time.perf_counter()
        for _ in range(max(warmup, 1)):
            state, _ = step(state, dt)
        jax.block_until_ready(state)
        compile_s = time.perf_counter() - t_c0

        # accumulate refresh hits as a device scalar (no per-step sync;
        # a host round-trip per step would poison the timing); the
        # profile capture brackets EXACTLY these `steps` launches —
        # trace start/stop sit outside the timed window
        hit_acc = None
        elapsed = 0.0
        with profile_trace(capture_dir, stage=profile_stage):
            t0 = time.perf_counter()
            for _ in range(steps):
                state, st_stats = step(state, dt)
                rh = st_stats.get("refresh_hit")
                if rh is not None:
                    rh = rh.astype(jax.numpy.int32)
                    hit_acc = rh if hit_acc is None else hit_acc + rh
            jax.block_until_ready(state)
            elapsed = time.perf_counter() - t0
        if hit_acc is not None:
            hit_acc = int(jax.device_get(hit_acc))
        return compile_s, elapsed, hit_acc

    compile_s, elapsed, refresh_hits = timed_run(
        capture_dir=profile_dir or "")
    import numpy as np
    if not bool(np.isfinite(np.asarray(jax.device_get(state.X))).all()):
        err = FloatingPointError(f"non-finite marker state at n={n}")
        if recorder is not None:
            cap = recorder.dump_incident(
                directory=os.path.join(record_dir, "incidents"),
                kind="divergence")
            err.capsule = cap
            log(f"[bench] n={n} diverged; replay capsule: {cap}")
        raise err

    n_markers = int(state.X.shape[0])
    out = {
        "n": n,
        "markers": n_markers,
        "steps_per_sec": round(steps / elapsed, 4),
        "ms_per_step": round(1e3 * elapsed / steps, 3),
        "compile_warmup_s": round(compile_s + aot_s, 2),
        "cache_hits": cache_after["hits"] - cache_before["hits"],
        "cache_misses": cache_after["misses"] - cache_before["misses"],
        "fast_path": {True: "mxu", False: "scatter",
                      None: "auto"}.get(use_fast, use_fast),
    }
    if spectral_dtype is not None:
        out["spectral_dtype"] = str(spectral_dtype)
    if refresh_hits is not None:
        # slot-preserving half-step refresh bookkeeping: hits took the
        # cheap re-gather, falls paid a full re-pack (drift bound blown)
        out["refresh_hits"] = refresh_hits
        out["repack_falls"] = steps - refresh_hits
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=256, help="target cells/axis")
    ap.add_argument("--n-lat", type=int, default=316)
    ap.add_argument("--n-lon", type=int, default=316)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--warmup", type=int, default=3)
    ap.add_argument("--dt", type=float, default=5e-5)
    ap.add_argument("--stages", type=str, default="64,128",
                    help="comma-separated ramp sizes run before --n")
    ap.add_argument("--compare-at", type=int, default=128,
                    help="grid size for the MXU-vs-scatter comparison "
                         "(0 disables)")
    ap.add_argument("--deadline", type=float, default=1500.0,
                    help="soft wall-clock budget (s); later stages are "
                         "skipped once exceeded")
    ap.add_argument("--sweep", action="store_true",
                    help="MXU tile/cap sweep at the comparison size")
    ap.add_argument("--profile", type=str, default="",
                    help="capture a jax device profile of the final "
                         "stage into this directory (TensorBoard/"
                         "Perfetto viewable)")
    ap.add_argument("--profile-stages", type=str, default="",
                    help="comma-separated fnmatch globs over stage "
                         "labels ('n256,packed*'); each matching ramp "
                         "stage (n<size>) or flagship leg captures its "
                         "device profile into <--profile>/<label>_"
                         "<gitrev>/ instead of only the final stage")
    ap.add_argument("--heartbeat", type=str, default="",
                    help="write a liveness heartbeat.json to this path "
                         "(or directory) so an external observer can "
                         "tell a hung run from a slow stage")
    ap.add_argument("--fleet", type=int, default=0,
                    help="also time a B-lane vmapped ensemble of the "
                         "small shell vs the same lanes sequentially "
                         "(0 disables)")
    ap.add_argument("--fleet-mesh", action="store_true",
                    help="also time the B x D pod fleet (PR 16): "
                         "B in {8,64,256} lanes sharded over an "
                         "8-device lane mesh, aggregate lane-steps/s "
                         "per B")
    ap.add_argument("--tune-grid", action="store_true",
                    help="also run the autotuner's small measured "
                         "engine grid (scatter vs packed x f32/bf16) "
                         "in a CPU child and trend the ranking")
    ap.add_argument("--soak", action="store_true",
                    help="also run the open-loop Poisson+burst soak "
                         "grid (arrival rate x duration) in a CPU "
                         "child and trend requests/s + shed rate")
    ap.add_argument("--elastic", action="store_true",
                    help="also run the elastic warm-pool drill (mix "
                         "shift + memory pressure + restart) in a "
                         "CPU child and trend scale-up/restart "
                         "latency")
    ap.add_argument("--grad", action="store_true",
                    help="also run the gradient microbench (primal vs "
                         "VJP wall + FFT/scatter census per piece) in "
                         "a CPU child and trend the adjoint ratios")
    ap.add_argument("--assim", action="store_true",
                    help="also run the clean assimilation cadence "
                         "(analysis wall vs chunk cadence, cycles/s "
                         "for a small and a large ensemble) in a CPU "
                         "child and trend the per-cycle analysis "
                         "cost")
    ap.add_argument("--record", type=str, default="",
                    help="arm a flight recorder on every ramp stage; a "
                         "diverged stage dumps a replay capsule under "
                         "this directory (tools/replay.py re-executes "
                         "it offline)")
    args = ap.parse_args()

    t_start = time.perf_counter()
    wd = None
    if args.heartbeat:
        from ibamr_tpu.utils.watchdog import RunWatchdog

        # generous floor: a 256^3 XLA compile is legitimately minutes;
        # any kill policy lives outside, this only keeps the file honest
        wd = RunWatchdog(heartbeat_path=args.heartbeat, interval_s=5.0,
                         stall_factor=4.0, min_stall_s=300.0,
                         on_stall=lambda rec: log(
                             f"[bench] WATCHDOG STALL: {rec}"))
        wd.start()
        wd.beat(step=0)
    result = {
        "metric": f"IB/explicit/ex4 3D shell {args.n}^3: timesteps/sec",
        "value": 0.0,
        "unit": "steps/s",
        "vs_baseline": None,
        "platform": None,
        "stages": [],
        "mxu_vs_scatter": None,
        "phases": None,
        "cpu_sharded_ref": None,
        "fleet": None,
        "fleet_mesh": None,
        "serve": None,
        "tune": None,
        "profiles": [],
        "error": None,
    }
    profile_rev = git_short_rev() if args.profile_stages else "norev"
    profile_dirs_used = {}

    def profile_dir_for(label: str) -> str:
        d = stage_profile_dir(args, label, profile_rev,
                              used=profile_dirs_used)
        if d:
            # manifest entries are dicts since PR 10 (was: bare path
            # strings — tools/obs.py compare still reads those from
            # old bench JSONs); attribute_profile fills bytes/summary
            # once the capture closes
            result["profiles"].append(
                {"dir": d, "stage": label, "rev": profile_rev,
                 "bytes": None, "attributed": False})
        return d

    def attribute_profile(d: str) -> None:
        """Post-capture: record the capture's on-disk weight and
        attribute it in-process (offline parsing — a failure costs the
        summary, never the bench)."""
        if not d:
            return
        entry = next((e for e in result["profiles"]
                      if isinstance(e, dict) and e.get("dir") == d),
                     None)
        if entry is None:
            return
        try:
            from ibamr_tpu.obs import deviceprof

            entry["bytes"] = deviceprof.capture_bytes(d)
            if not deviceprof.find_trace_files(d):
                # a guarded-child leg (pallas) or failed stage leaves
                # the dir empty: say so instead of writing a vacuous
                # all-zero summary
                raise FileNotFoundError("no trace files captured")
            summary = deviceprof.attribute_capture(d)
            probs = deviceprof.validate_summary(summary)
            if probs:
                raise ValueError("; ".join(probs))
            deviceprof.write_summary(d, summary)
            entry["summary"] = deviceprof.compact_summary(summary)
            entry["attributed"] = True
        except Exception as e:  # noqa: BLE001
            entry["error"] = f"{type(e).__name__}: {e}"
            log(f"[bench] profile attribution failed for {d}: "
                f"{entry['error']}")

    # no chip (and no explicit JAX_PLATFORMS=cpu) -> this raises; a
    # benchmark never continues on the CPU under an accelerator's name
    from ibamr_tpu.utils.backend_guard import auto_backend

    jax = auto_backend()
    platform = jax.devices()[0].platform
    result["platform"] = platform
    log(f"[bench] platform={platform}")
    try:
        sizes = [int(s) for s in args.stages.split(",") if s.strip()]
        sizes = sorted({s for s in sizes if s < args.n}) + [args.n]
        errors = []
        for n in sizes:
            if time.perf_counter() - t_start > args.deadline:
                log(f"[bench] deadline exceeded, skipping n={n}")
                errors.append(f"n={n}: skipped (deadline)")
                continue
            # marker count scales with grid size toward the north-star
            # 316x316 (~1e5) lattice at 256^3
            frac = n / args.n
            n_lat = max(16, int(round(args.n_lat * frac)))
            n_lon = max(16, int(round(args.n_lon * frac)))
            try:
                log(f"[bench] stage n={n} markers~{n_lat * n_lon} ...")
                t_stage = time.perf_counter()
                pd = (profile_dir_for(f"n{n}") if args.profile_stages
                      else (args.profile if n == args.n else ""))
                # the ramp pins the BUCKETED-MXU engine: it has been
                # the staged baseline since round 1, and keeping it
                # preserves the longitudinal r1/r3/r5 comparison now
                # that the model's auto default is the (faster)
                # packed engine; the shootout below times the fast
                # engines at the target size. run_stage owns the
                # profile capture (measured loop only — see its doc).
                stage = run_stage(jax, n, n_lat, n_lon, args.steps,
                                  args.warmup, args.dt,
                                  use_fast=True,
                                  record_dir=(os.path.join(
                                      args.record, f"n{n}")
                                      if args.record else None),
                                  profile_dir=(pd or None),
                                  profile_stage=f"n{n}")
                attribute_profile(pd)
                log(f"[bench] stage n={n}: {stage['steps_per_sec']} "
                    "steps/s")
                if wd is not None:
                    wd.beat(step=len(result["stages"]) + 1,
                            last_chunk_wall_s=(time.perf_counter()
                                               - t_stage))
                stage["platform"] = platform  # stages can straddle a
                # mid-run CPU->TPU upgrade; label each measurement
                result["stages"].append(stage)
                result["metric"] = (
                    f"IB/explicit/ex4 3D shell {n}^3, "
                    f"{stage['markers']} markers: timesteps/sec")
                result["value"] = stage["steps_per_sec"]
            except Exception as e:  # keep earlier stages on late failure
                log(f"[bench] stage n={n} FAILED: {e}")
                errors.append(f"n={n}: {type(e).__name__}: {e}")

        if (platform != "cpu"
                and any(s["n"] == args.n for s in result["stages"])
                and time.perf_counter() - t_start <= args.deadline):
            # flagship engine shootout: the main stage ran the default
            # (auto = bucketed MXU); the packed engines target exactly
            # its dominant cost (the low-utilization weight operands —
            # PERF.md round-3 breakdown), so time them at the SAME size
            # and report the best configuration as the headline value.
            # Each leg is deadline-guarded; the pallas leg runs in a
            # terminable child (remote-compile stall history).
            for label in ("packed", "packed_bf16", "pallas_packed",
                          "hybrid_bf16", "fluid_bf16"):
                if time.perf_counter() - t_start > args.deadline:
                    errors.append(f"flagship[{label}]: skipped "
                                  "(deadline)")
                    continue
                try:
                    t_leg = time.perf_counter()
                    pd = profile_dir_for(label)
                    st = run_engine_leg(jax, label, label, args.n,
                                        args.n_lat, args.n_lon,
                                        args, t_start, platform,
                                        profile_dir=(pd or None))
                    attribute_profile(pd)
                    st["platform"] = platform
                    log(f"[bench] flagship {label}: "
                        f"{st['steps_per_sec']} steps/s")
                    if wd is not None:
                        wd.beat(step=len(result["stages"]) + 1,
                                last_chunk_wall_s=(time.perf_counter()
                                                   - t_leg))
                    result["stages"].append(st)
                    if st["steps_per_sec"] > result["value"]:
                        result["value"] = st["steps_per_sec"]
                        result["metric"] = (
                            f"IB/explicit/ex4 3D shell {args.n}^3, "
                            f"{st['markers']} markers ({label} "
                            "transfers): timesteps/sec")
                except Exception as e:
                    errors.append(f"flagship[{label}]: "
                                  f"{type(e).__name__}: {e}")

        if args.compare_at and platform != "cpu" and any(
                s["n"] >= args.compare_at for s in result["stages"]):
            # (skipped on the CPU fallback: two more full stages would
            # triple the runtime and the transfer-engine question is a
            # TPU question)
            if time.perf_counter() - t_start <= args.deadline:
                try:
                    cn = args.compare_at
                    frac = cn / args.n
                    n_lat = max(16, int(round(args.n_lat * frac)))
                    n_lon = max(16, int(round(args.n_lon * frac)))
                    cmp = {}
                    # transfer-engine compare: scatter / MXU-bucketed /
                    # occupancy-packed /
                    # Pallas-packed / hybrid pallas-spread + bf16-interp
                    # (VERDICT round 2 item 5 + round 3 packed engines).
                    # A failed leg only loses that engine's entry.
                    for label, fast in (("mxu", True),
                                        ("scatter", False),
                                        ("packed", "packed"),
                                        ("pallas_packed",
                                         "pallas_packed"),
                                        ("hybrid_bf16",
                                         "hybrid_bf16")):
                        if time.perf_counter() - t_start > args.deadline:
                            errors.append(f"compare[{label}]: skipped "
                                          "(deadline)")
                            continue
                        try:
                            st = run_engine_leg(jax, label, fast, cn,
                                                n_lat, n_lon, args,
                                                t_start, platform)
                            cmp[label] = st["steps_per_sec"]
                            log(f"[bench] {label}@{cn}^3: "
                                f"{st['steps_per_sec']} steps/s")
                        except Exception as e:
                            cmp[label] = None
                            errors.append(f"compare[{label}]: "
                                          f"{type(e).__name__}: {e}")
                    cmp["n"] = cn
                    if cmp.get("mxu") and cmp.get("scatter"):
                        cmp["speedup"] = round(cmp["mxu"]
                                               / cmp["scatter"], 3)
                    result["mxu_vs_scatter"] = cmp

                    if args.sweep:
                        # MXU geometry sweep at the same size
                        sweep = []
                        for tile in (8, 16):
                            for cap in (256, 512, 1024):
                                if (time.perf_counter() - t_start
                                        > args.deadline):
                                    break
                                try:
                                    st = run_stage(
                                        jax, cn, n_lat, n_lon,
                                        args.steps, args.warmup,
                                        args.dt, use_fast=True,
                                        fast_opts=(tile, cap))
                                    sweep.append(
                                        {"tile": tile, "cap": cap,
                                         "steps_per_sec":
                                             st["steps_per_sec"]})
                                    log(f"[bench] mxu tile={tile} "
                                        f"cap={cap}: "
                                        f"{st['steps_per_sec']}")
                                except Exception as e:
                                    sweep.append(
                                        {"tile": tile, "cap": cap,
                                         "error": str(e)[:120]})
                        result["mxu_sweep"] = sweep
                except Exception as e:
                    errors.append(f"compare: {type(e).__name__}: {e}")

        if (platform != "cpu" and result["stages"]
                and time.perf_counter() - t_start <= args.deadline):
            # per-phase TimerManager-style table at the largest completed
            # size (SURVEY §6: name the dominant phase)
            try:
                bn = result["stages"][-1]["n"]
                frac = bn / args.n
                from ibamr_tpu.models.shell3d import build_shell_example

                integ, st = build_shell_example(
                    n_cells=bn,
                    n_lat=max(16, int(round(args.n_lat * frac))),
                    n_lon=max(16, int(round(args.n_lon * frac))),
                    radius=0.25, aspect=1.2, stiffness=1.0,
                    rest_length_factor=0.75, mu=0.05)
                result["phases"] = {"n": bn,
                                    **phase_breakdown(jax, integ, st,
                                                      args.dt)}
                log(f"[bench] phases@{bn}^3: {result['phases']}")
            except Exception as e:
                errors.append(f"phases: {type(e).__name__}: {e}")

        # chip-independent regression signal: ALWAYS emitted (child
        # process on the virtual CPU mesh), even when every TPU stage
        # above failed or was skipped
        try:
            # charged against the remaining deadline budget: the CPU
            # fallback's bounded-wall-clock guarantee (JSON always
            # lands inside the driver timeout) must survive this child
            remaining = args.deadline - (time.perf_counter() - t_start)
            if remaining < 30.0:
                result["cpu_sharded_ref"] = {
                    "error": "skipped (deadline exhausted)"}
            elif remaining > 1500.0:
                # room for the two-leg trend (round 5: the speedup
                # ratio gets its size trend, not just one number)
                result["cpu_sharded_ref"] = \
                    cpu_sharded_reference_with_trend()
            else:
                result["cpu_sharded_ref"] = cpu_sharded_reference(
                    timeout_s=min(300.0, remaining))
            log(f"[bench] cpu_sharded_ref: {result['cpu_sharded_ref']}")
        except Exception as e:
            result["cpu_sharded_ref"] = {"error": f"{type(e).__name__}: "
                                                  f"{e}"}

        if args.fleet:
            # ensemble-throughput leg (PR 7): like the sharded ref this
            # runs on a virtual CPU device in a child, so it lands in
            # every round's artifact
            try:
                remaining = args.deadline - (time.perf_counter()
                                             - t_start)
                if remaining < 30.0:
                    result["fleet"] = {
                        "error": "skipped (deadline exhausted)"}
                else:
                    result["fleet"] = fleet_reference(
                        B=args.fleet, timeout_s=min(600.0, remaining))
                log(f"[bench] fleet: {result['fleet']}")
            except Exception as e:
                result["fleet"] = {"error": f"{type(e).__name__}: {e}"}

        if args.fleet_mesh:
            # pod-fleet leg (PR 16): the lane axis sharded over the
            # 8-device virtual lane mesh — B in {8,64,256} so the
            # aggregate lane-steps/s scaling curve (and the
            # zero-quarantine invariant) trends across rounds
            try:
                remaining = args.deadline - (time.perf_counter()
                                             - t_start)
                if remaining < 30.0:
                    result["fleet_mesh"] = {
                        "error": "skipped (deadline exhausted)"}
                else:
                    result["fleet_mesh"] = fleet_mesh_reference(
                        timeout_s=min(900.0, remaining))
                log(f"[bench] fleet_mesh: {result['fleet_mesh']}")
            except Exception as e:
                result["fleet_mesh"] = {
                    "error": f"{type(e).__name__}: {e}"}

        # serving-latency leg: cold vs warm request-to-first-step
        # through the warm-pool router (PR 12). Like the sharded ref
        # this is a chip-independent CPU-child signal, so the
        # cold/warm ratio lands in every round's artifact
        try:
            remaining = args.deadline - (time.perf_counter() - t_start)
            if remaining < 30.0:
                result["serve"] = {
                    "error": "skipped (deadline exhausted)"}
            else:
                result["serve"] = serve_reference(
                    timeout_s=min(300.0, remaining))
            log("[bench] serve: " + str({
                k: v for k, v in (result["serve"] or {}).items()
                if k != "histograms"}))
        except Exception as e:
            result["serve"] = {"error": f"{type(e).__name__}: {e}"}

        # autotuner leg (PR 13): the measured scatter-vs-packed grid
        # in a CPU child, trending ranking + margin per round
        if args.tune_grid:
            try:
                remaining = (args.deadline
                             - (time.perf_counter() - t_start))
                if remaining < 30.0:
                    result["tune"] = {
                        "error": "skipped (deadline exhausted)"}
                else:
                    result["tune"] = tune_reference(
                        timeout_s=min(300.0, remaining))
                log(f"[bench] tune: {result['tune']}")
            except Exception as e:
                result["tune"] = {"error": f"{type(e).__name__}: {e}"}

        # sustained-traffic leg (PR 17): the open-loop soak grid in a
        # CPU child, trending requests/s + shed rate per round
        if args.soak:
            try:
                remaining = (args.deadline
                             - (time.perf_counter() - t_start))
                if remaining < 30.0:
                    result["soak"] = {
                        "error": "skipped (deadline exhausted)"}
                else:
                    result["soak"] = soak_reference(
                        timeout_s=min(300.0, remaining))
                log(f"[bench] soak: {result['soak']}")
            except Exception as e:
                result["soak"] = {"error": f"{type(e).__name__}: {e}"}

        # elasticity leg (PR 18): the mix-shift + restart drill in a
        # CPU child, trending scale-up/restart latency per round
        if args.elastic:
            try:
                remaining = (args.deadline
                             - (time.perf_counter() - t_start))
                if remaining < 30.0:
                    result["elastic"] = {
                        "error": "skipped (deadline exhausted)"}
                else:
                    result["elastic"] = elastic_reference(
                        timeout_s=min(300.0, remaining))
                log(f"[bench] elastic: {result['elastic']}")
            except Exception as e:
                result["elastic"] = {
                    "error": f"{type(e).__name__}: {e}"}

        # adjoint-cost leg (PR 19): primal-vs-VJP ratios + FFT/scatter
        # census in a CPU child, trending the reverse-pass price per
        # round (the "adjoint at primal cost" pins, measured)
        if args.grad:
            try:
                remaining = (args.deadline
                             - (time.perf_counter() - t_start))
                if remaining < 30.0:
                    result["grad"] = {
                        "error": "skipped (deadline exhausted)"}
                else:
                    result["grad"] = grad_reference(
                        timeout_s=min(300.0, remaining))
                log(f"[bench] grad: {result['grad']}")
            except Exception as e:
                result["grad"] = {"error": f"{type(e).__name__}: {e}"}

        # forecasting-cadence leg (PR 20): the clean assimilation run
        # in a CPU child, trending analysis wall + cycles/s per round
        if args.assim:
            try:
                remaining = (args.deadline
                             - (time.perf_counter() - t_start))
                if remaining < 30.0:
                    result["assim"] = {
                        "error": "skipped (deadline exhausted)"}
                else:
                    result["assim"] = assim_reference(
                        timeout_s=min(420.0, remaining))
                log(f"[bench] assim: {result['assim']}")
            except Exception as e:
                result["assim"] = {
                    "error": f"{type(e).__name__}: {e}"}

        if errors:
            msg = "; ".join(errors)
            result["error"] = (result["error"] + "; " + msg
                               if result["error"] else msg)
    except BaseException as e:
        result["error"] = (f"{type(e).__name__}: {e}\n"
                           + traceback.format_exc()[-1500:])

    if wd is not None:
        wd.beat(step=len(result["stages"]) + 1)   # final liveness mark
        wd.stop()
    if args.record:
        # incidents = real stage failures; replays = capsules on disk an
        # operator can hand straight to tools/replay.py
        import glob
        caps = sorted(os.path.dirname(m) for m in glob.glob(
            os.path.join(args.record, "**", "manifest.json"),
            recursive=True))
        result["incidents"] = len(
            [e for e in (result.get("error") or "").split("; ")
             if e and "skipped" not in e])
        result["replays"] = len(caps)
        result["replay_capsules"] = caps
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
