"""Fleet runner: B ensemble lanes through ONE vmapped trace (PR 7).

Runs ``--lanes B`` independently-perturbed instances of the 3-D shell
as a lane-stacked fleet: every state leaf carries a leading lane axis,
the chunk is ONE ``jax.vmap``-ped scan shared by all lanes, dt is a
(B,) vector and a (B,) lane-alive mask freezes quarantined lanes
in-graph — so B scenarios cost ONE compile and one host transfer per
chunk instead of B of each. Under ``ResilientDriver`` supervision a
lane that goes bad is rolled back alone (its slice restored from the
newest verified checkpoint, its dt backed off), and quarantined after
retry exhaustion — the other B-1 lanes never stop stepping.

Prints ONE JSON line (last line of stdout) with per-lane status
(steps completed, alive, dt, retries) and aggregate steps/s; progress
goes to stderr. ``--sequential`` also runs each lane alone as a B=1
fleet (the bitwise solo reference — docs/RESILIENCE.md "Lane
isolation") and reports the aggregate-vs-sequential speedup.

Examples::

    python tools/fleet.py --lanes 8 --steps 16 --dir /tmp/fleet
    python tools/fleet.py --lanes 64 --n 32 --sequential
    python tools/fleet.py --lanes 64 --mesh 8 --dir /tmp/pod  # B x D pod

``--mesh D`` composes the two scaling axes (PR 16): the lane axis is
sharded over a D-device lane mesh (``parallel.mesh.make_lane_mesh``),
each device owns B/D whole lanes, checkpoints go through the sharded
manifest path (elastic N→M restart re-places surviving lanes), and the
per-lane quarantine/dt machinery is untouched — sharded == replicated
bitwise in f64 (tests/test_fleet_mesh.py).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def perturb_lane(state, i: int, scale: float = 0.01):
    """Lane i's initial condition: the base state with a deterministic
    per-lane velocity perturbation (relative scale + a tiny absolute
    offset so lane 0 still differs from the unperturbed base)."""
    ins = state.ins
    u = tuple(c * (1.0 + scale * i) + 1e-4 * scale * (i + 1)
              for c in ins.u)
    return state._replace(ins=ins._replace(u=u))


def lane_steps(state, lane: int):
    """Steps completed by one lane (the per-lane fluid step counter)."""
    import numpy as np
    k = state.ins.k if hasattr(state, "ins") else state.k
    return int(np.asarray(k)[lane])


def build_fleet(n, n_lat, n_lon, mu, lanes, perturb, dtype):
    from ibamr_tpu.models.shell3d import build_shell_example
    from ibamr_tpu.utils.lanes import stack_lanes

    integ, st0 = build_shell_example(n_cells=n, n_lat=n_lat,
                                     n_lon=n_lon, mu=mu, dtype=dtype)
    lane_states = [perturb_lane(st0, i, perturb) for i in range(lanes)]
    return integ, lane_states, stack_lanes(lane_states)


def _emit_chunk_census(drv, stacked, cfg, lanes, lane_mesh):
    """Emit the structural comm census of the fleet chunk (PR 16) into
    the attached run ledger as one ``graph_census`` record, so the
    per-proc rollup (``tools/obs.py summary --fleet``) can show each
    process's hidden/unhidden collective split next to its measured
    ``comm_s`` share. One extra trace of the chunk per run; the traced
    signature is identical to the real run's, so the no-retrace
    contract (``trace_counts``) is untouched."""
    import jax
    import jax.numpy as jnp

    from ibamr_tpu import obs
    from ibamr_tpu.analysis.graph_census import structural_overlap_census

    n = min(cfg.health_interval, cfg.num_steps)
    fn = drv._chunk(n)
    fn = getattr(fn, "__wrapped__", fn)
    jx = jax.make_jaxpr(fn)(stacked, jnp.asarray(drv.lane_dt),
                            jnp.asarray(drv.lane_alive))
    c = structural_overlap_census(jx.jaxpr)
    obs.emit("graph_census", scope="fleet_chunk", chunk_length=n,
             lanes=lanes,
             mesh_devices=(int(lane_mesh.devices.size)
                           if lane_mesh is not None else 0),
             structural_collectives=c["structural_collectives"],
             hidden_collectives=c["hidden_collectives"],
             unhidden_collectives=c["unhidden_collectives"],
             hidden_fraction=c["hidden_fraction"])


def run_fleet(integ, stacked, cfg, lanes, directory=None,
              max_retries=2, dt_backoff=0.5, quarantine_threshold=0.5,
              heartbeat=None, lane_mesh=None):
    """One supervised fleet run; returns (summary dict, final state).

    With ``lane_mesh`` the lane axis is sharded over the mesh's devices
    (B×D pod fleet): the stacked state is device_put under the lane
    sharding, the chunk pins it there, and checkpoints/restores go
    through the sharded manifest path so an elastic N→M restart
    re-places surviving lanes."""
    import contextlib

    from ibamr_tpu import obs
    from ibamr_tpu.utils.health import HealthProbe
    from ibamr_tpu.utils.hierarchy_driver import HierarchyDriver
    from ibamr_tpu.utils.supervisor import ResilientDriver

    if lane_mesh is not None:
        from ibamr_tpu.parallel.mesh import place_lanes
        stacked = place_lanes(stacked, lane_mesh)
    probe = HealthProbe.for_integrator(integ)
    drv = HierarchyDriver(integ, cfg, lanes=lanes, health_probe=probe,
                          lane_mesh=lane_mesh)
    wd = None
    if heartbeat:
        from ibamr_tpu.utils.watchdog import RunWatchdog
        wd = RunWatchdog(heartbeat_path=heartbeat, interval_s=5.0,
                         min_stall_s=300.0)
    t0 = time.perf_counter()
    ledger_path = None
    ledger_seq = None
    if directory:
        # run ledger: spans/counters/incidents of THIS run land in one
        # seq-ordered stream, stamped with the flight-recorder run_id
        from ibamr_tpu.utils.flight_recorder import FlightRecorder
        try:
            fp = FlightRecorder(capacity=1).fingerprint(driver=drv)
        except Exception:
            fp = None
        ledger_path = os.path.join(directory, "ledger.jsonl")
        ledger_cm = obs.ledger(ledger_path, fingerprint=fp)
    else:
        ledger_cm = contextlib.nullcontext()
    if directory:
        sup = ResilientDriver(drv, directory, max_retries=max_retries,
                              dt_backoff=dt_backoff,
                              quarantine_threshold=quarantine_threshold,
                              handle_signals=False, watchdog=wd,
                              sharded=lane_mesh is not None,
                              mesh=lane_mesh,
                              incident_log=os.path.join(
                                  directory, "incidents.jsonl"))
        with ledger_cm as led:
            try:
                _emit_chunk_census(drv, stacked, cfg, lanes, lane_mesh)
            except Exception as e:  # noqa: BLE001 - census is advisory
                log(f"[fleet] chunk census skipped: "
                    f"{type(e).__name__}: {e}")
            final = sup.run(stacked)
        ledger_seq = led.last_seq if led is not None else None
        incidents = list(sup.incidents)
    else:
        if wd is not None:
            wd.start()
        try:
            final = drv.run(stacked)
        finally:
            if wd is not None:
                wd.stop()
        incidents = []
    wall = time.perf_counter() - t0

    per_lane = []
    total_steps = 0
    for i in range(lanes):
        k = lane_steps(final, i)
        total_steps += k
        per_lane.append({
            "lane": i,
            "steps": k,
            "alive": bool(drv.lane_alive[i]),
            "dt": float(drv.lane_dt[i]),
        })
    quarantined = sum(1 for rec in per_lane if not rec["alive"])
    backed_off = sum(1 for rec in per_lane
                     if rec["dt"] != float(cfg.dt))
    summary = {
        "lanes": lanes,
        "num_steps": cfg.num_steps,
        "wall_s": round(wall, 3),
        # aggregate throughput: lane-steps actually completed across
        # the whole fleet per wall second (compile included — both
        # legs of the sequential comparison pay it once)
        "aggregate_steps_per_s": round(total_steps / wall, 3),
        "lanes_quarantined": quarantined,
        "lanes_backed_off": backed_off,
        "trace_counts": dict(drv.trace_counts),
        "incidents": [r.get("event") for r in incidents],
        "per_lane": per_lane,
    }
    if lane_mesh is not None:
        summary["mesh_devices"] = int(lane_mesh.devices.size)
        summary["lanes_per_device"] = lanes // int(lane_mesh.devices.size)
    if ledger_path is not None:
        summary["ledger_path"] = ledger_path
        summary["ledger_records"] = (ledger_seq + 1
                                     if ledger_seq is not None else 0)
    return summary, final


def run_sequential(integ, lane_states, cfg):
    """Each lane alone as a B=1 fleet (the bitwise solo reference),
    back to back; returns aggregate steps/s over all lanes. The B=1
    trace is shared across lanes (identical signature), so compile is
    paid once here too — the comparison isolates the batching win."""
    from ibamr_tpu.utils.hierarchy_driver import HierarchyDriver
    from ibamr_tpu.utils.lanes import stack_lanes

    t0 = time.perf_counter()
    total = 0
    drv = HierarchyDriver(integ, cfg, lanes=1)
    for st in lane_states:
        final = drv.run(stack_lanes([st]))
        total += lane_steps(final, 0)
        # fresh per-lane dt/alive for the next lane; the compiled
        # chunk survives on the driver
        drv.lane_dt[0] = float(cfg.dt)
        drv.lane_alive[0] = True
    wall = time.perf_counter() - t0
    return {"wall_s": round(wall, 3),
            "aggregate_steps_per_s": round(total / wall, 3)}


def main():
    ap = argparse.ArgumentParser(
        description="vmapped ensemble fleet runner")
    ap.add_argument("--lanes", type=int, default=8,
                    help="fleet size B (8 and 64 are the reference "
                         "points)")
    ap.add_argument("--n", type=int, default=32, help="cells/axis")
    ap.add_argument("--n-lat", type=int, default=16)
    ap.add_argument("--n-lon", type=int, default=16)
    ap.add_argument("--mu", type=float, default=0.05)
    ap.add_argument("--steps", type=int, default=16)
    ap.add_argument("--dt", type=float, default=1e-3)
    ap.add_argument("--health-interval", type=int, default=4)
    ap.add_argument("--restart-interval", type=int, default=8)
    ap.add_argument("--perturb", type=float, default=0.01,
                    help="per-lane initial-velocity perturbation scale")
    ap.add_argument("--dir", type=str, default="",
                    help="checkpoint + incident directory (enables "
                         "per-lane rollback/quarantine supervision)")
    ap.add_argument("--max-retries", type=int, default=2)
    ap.add_argument("--dt-backoff", type=float, default=0.5)
    ap.add_argument("--quarantine-threshold", type=float, default=0.5)
    ap.add_argument("--heartbeat", type=str, default="",
                    help="heartbeat.json path (carries lanes_ok/"
                         "lanes_quarantined/lanes_retrying)")
    ap.add_argument("--mesh", type=int, nargs="?", const=0, default=None,
                    metavar="D",
                    help="shard the lane axis over a D-device lane "
                         "mesh (omit D to use every visible device); "
                         "lanes must divide D evenly — the B×D pod "
                         "fleet with per-lane quarantine/dt intact")
    ap.add_argument("--sequential", action="store_true",
                    help="also run every lane alone (B=1) and report "
                         "the speedup")
    ap.add_argument("--x64", action="store_true",
                    help="run the fleet in float64")
    args = ap.parse_args()

    result = {"lanes": args.lanes, "error": None}
    try:
        from ibamr_tpu.utils.backend_guard import auto_backend

        jax = auto_backend()
        result["platform"] = jax.devices()[0].platform
        if args.x64:
            jax.config.update("jax_enable_x64", True)
        from ibamr_tpu.utils.hierarchy_driver import RunConfig

        cfg = RunConfig(dt=args.dt, num_steps=args.steps,
                        health_interval=args.health_interval,
                        restart_interval=(args.restart_interval
                                          if args.dir else 0))
        log(f"[fleet] building {args.lanes} lanes of the "
            f"{args.n}^3 shell ({args.n_lat * args.n_lon} markers)")
        integ, lane_states, stacked = build_fleet(
            args.n, args.n_lat, args.n_lon, args.mu, args.lanes,
            args.perturb, "float64" if args.x64 else None)
        lane_mesh = None
        if args.mesh is not None:
            from ibamr_tpu.parallel.mesh import make_lane_mesh
            lane_mesh = make_lane_mesh(
                n_devices=args.mesh if args.mesh > 0 else None)
            result["mesh_devices"] = int(lane_mesh.devices.size)
            log(f"[fleet] lane mesh: {result['mesh_devices']} devices "
                f"x {args.lanes // result['mesh_devices']} lanes each")
        summary, _ = run_fleet(
            integ, stacked, cfg, args.lanes,
            directory=args.dir or None, max_retries=args.max_retries,
            dt_backoff=args.dt_backoff,
            quarantine_threshold=args.quarantine_threshold,
            heartbeat=args.heartbeat or None, lane_mesh=lane_mesh)
        result.update(summary)
        log(f"[fleet] {args.lanes} lanes x {args.steps} steps: "
            f"{summary['aggregate_steps_per_s']} lane-steps/s "
            f"({summary['lanes_quarantined']} quarantined)")
        if args.sequential:
            cfg_solo = RunConfig(dt=args.dt, num_steps=args.steps,
                                 health_interval=args.health_interval)
            seq = run_sequential(integ, lane_states, cfg_solo)
            result["sequential"] = seq
            if seq["aggregate_steps_per_s"] > 0:
                result["fleet_speedup"] = round(
                    summary["aggregate_steps_per_s"]
                    / seq["aggregate_steps_per_s"], 3)
            log(f"[fleet] sequential: {seq['aggregate_steps_per_s']} "
                f"lane-steps/s -> speedup "
                f"{result.get('fleet_speedup')}")
    except Exception as e:  # noqa: BLE001 - the JSON line must land
        import traceback
        result["error"] = (f"{type(e).__name__}: {e}\n"
                           + traceback.format_exc()[-1200:])
    print(json.dumps(result), flush=True)
    return 0 if result["error"] is None else 1


if __name__ == "__main__":
    sys.exit(main())
