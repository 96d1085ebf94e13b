"""Chip smoke: the flagship ex4 shell at 256^3 / 99,856 markers on one
TPU, end to end through ``examples/IB/explicit/ex4/main.py``.

One process. Without a TPU it exits non-zero and prints no result.

    python chip_smoke.py             # one chip: 40 steps + restart from 20
    python chip_smoke.py --chips 4   # ONLY the sharded path vs one device
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse
                                     # 16^3 walk of every phase; always fails

Earlier stdout lines are one JSON object per phase (one run, not a
benchmark); the last line is the verdict the driver reads.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import re
import shutil
import sys
import time
import warnings

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)
EX4 = os.path.join(REPO, "examples", "IB", "explicit", "ex4")
# run outputs (two ~470 MB checkpoints at 256^3) stay in the checkout;
# only the phase lines go where the chip tool brings files back from
OUT = os.path.join(REPO, "chip_smoke_out")
PHASE_LOG = os.path.join(REPO, "chiprun_out", "chip_smoke.jsonl")

# max-abs error over max-abs scale, one step, engine vs XLA scatter:
# the bf16 engines at the bound tests/test_interaction_packed.py pins,
# the exact-f32 engines at f32 roundoff
TOL_BF16, TOL_F32 = 8e-3, 1e-4
# sharded vs one device, 10 steps: f32 roundoff times steps (pressure is
# the projection's Lagrange multiplier, one order more sensitive)
TOL_SHARDED = {"u": 1e-4, "U": 1e-4, "X": 1e-4, "p": 1e-3}


def phase(name, **kv):
    line = json.dumps({"phase": name, **kv})
    print(line, flush=True)
    with open(PHASE_LOG, "a") as f:
        f.write(line + "\n")


def check(cond, msg):
    if not cond:
        print(f"chip_smoke: FAILED: {msg}", file=sys.stderr, flush=True)
        sys.exit(1)


def write_input(path, out, num_steps, viz, restart, rehearse):
    """input3d.northstar with only the run length, the dump cadences and
    the three output paths changed (``--rehearse`` also shrinks it)."""
    text = open(os.path.join(EX4, "input3d.northstar")).read()
    subs = {"num_steps": num_steps, "viz_dump_interval": viz,
            "restart_interval": restart,
            "log_file": f'"{out}/metrics.jsonl"',
            "viz_dirname": f'"{out}/viz"',
            "restart_dirname": f'"{out}/restart"'}
    if rehearse:
        subs.update(n_cells="16, 16, 16", n_lat=8, n_lon=8)
    for key, val in subs.items():
        text, n = re.subn(rf"(?m)^(\s*{key}\s*=).*$", rf"\g<1> {val}", text)
        check(n == 1, f"input key {key!r} matched {n} lines")
    with open(path, "w") as f:
        f.write(text)


def load_main():
    """ex4's main.py as a module, with timing spies on the three names
    it calls for build / checkpoint / restore (they only observe)."""
    spec = importlib.util.spec_from_file_location(
        "ex4_main", os.path.join(EX4, "main.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    seen = {"build": [], "save": [], "restore": []}

    def spy(key, fn):
        def wrapped(*a, **kw):
            t0 = time.perf_counter()
            out = fn(*a, **kw)
            seen[key].append((time.perf_counter() - t0, out))
            return out
        return wrapped

    mod.build_shell_example = spy("build", mod.build_shell_example)
    mod.save_checkpoint = spy("save", mod.save_checkpoint)
    mod.restore_checkpoint = spy("restore", mod.restore_checkpoint)
    return mod, seen


def run_main(mod, argv, ledger_path):
    """main(argv) under a run ledger; returns (state, wall, chunk walls)."""
    import jax

    from ibamr_tpu import obs

    t0 = time.perf_counter()
    with obs.ledger(ledger_path):
        state = mod.main(argv)
    jax.block_until_ready(state)
    wall = time.perf_counter() - t0
    chunks = [r["chunk_wall_s"] for r in obs.read_ledger(ledger_path)
              if r.get("kind") == "counters"
              and r.get("chunk_wall_s") is not None]
    return state, wall, chunks


def read_metrics(out):
    with open(os.path.join(out, "metrics.jsonl")) as f:
        return [json.loads(ln) for ln in f if ln.strip()]


def check_metrics(recs, vol0):
    import math

    for r in recs:
        for k, v in r.items():
            check(not k.endswith("_nonfinite") and v is not None
                  and (not isinstance(v, float) or math.isfinite(v)),
                  f"non-finite metric {k!r} at step {r.get('step')}")
    max_div = max(r["max_div"] for r in recs)
    drift = max(abs(r["volume"] - vol0) / vol0 for r in recs)
    phase("metrics", records=len(recs), max_div=max_div,
          volume_step0=vol0, volume_drift=drift,
          last=recs[-1])
    check(max_div < 1e-2, f"max_div {max_div} >= 1e-2")
    check(drift < 1e-2, f"shell volume drifted {drift} >= 1%")


def rel_diff(a, b):
    """max|a - b| / max|b| on the host (gathers a sharded array)."""
    a, b = np.asarray(a), np.asarray(b)
    return float(np.max(np.abs(a - b))) / max(float(np.max(np.abs(b))),
                                              1e-30)


def smoke_input(args, out, name, **cadence):
    """Write the smoke's input file; returns (path, parsed db, dt)."""
    from ibamr_tpu.utils import parse_input_file

    inp = os.path.join(out, name)
    write_input(inp, out, rehearse=args.rehearse, **cadence)
    db = parse_input_file(inp)
    return inp, db, db.get_database(
        "INSStaggeredHierarchyIntegrator").get_float("dt")


def cache_entries(d):
    return len(os.listdir(d)) if d and os.path.isdir(d) else 0


def peak_bytes(dev):
    stats = dev.memory_stats()
    return stats.get("peak_bytes_in_use") if stats else None


def one_chip(args, jax, out):
    import jax.numpy as jnp

    from ibamr_tpu import obs
    from ibamr_tpu.models.engine_resolver import resolve_engine
    from ibamr_tpu.models.shell3d import build_shell_example, shell_volume
    from ibamr_tpu.ops.delta import get_kernel

    check(len(jax.devices()) == 1,
          f"{len(jax.devices())} devices visible: the one-chip smoke "
          "needs exactly one (use --chips 4 for the sharded path)")
    inp, db, dt = smoke_input(args, out, "input3d.smoke",
                              num_steps=40, viz=20, restart=20)
    mod, seen = load_main()

    # ---- pass 1: 40 steps, dumps and checkpoints at 20 and 40
    state, wall, chunks = run_main(
        mod, ["main.py", inp], os.path.join(out, "ledger_pass1.jsonl"))
    build_s, (integ, state0) = seen["build"][0]
    n_markers = int(state0.X.shape[0])
    check(len(chunks) == 2, f"expected 2 chunks of 20 steps, got {chunks}")
    phase("run", n_cells=list(integ.ins.grid.n), markers=n_markers,
          steps=40, engine=integ.ib.engine_name, build_s=build_s,
          first_chunk_s=chunks[0], steady_chunk_s=chunks[1],
          steady_ms_per_step=chunks[1] / 20 * 1e3,
          checkpoint_write_s=[s for s, _ in seen["save"]],
          pass_wall_s=wall)
    vol0 = float(shell_volume(state0.X, (0.5, 0.5, 0.5)))
    recs = read_metrics(out)
    check([r["step"] for r in recs] == [20, 40], f"metrics steps {recs}")
    check_metrics(recs, vol0)
    for k in (20, 40):
        for f in (f"viz/markers.{k:06d}.csv", f"restart/restore.{k:08d}.npz"):
            check(os.path.exists(os.path.join(out, f)), f"missing {f}")

    # ---- pass 2: restore step 20, continue to 40, same answer
    _, wall2, chunks2 = run_main(
        mod, ["main.py", inp, os.path.join(out, "restart"), "20"],
        os.path.join(out, "ledger_pass2.jsonl"))
    restore_s, (_, restored_step, _) = seen["restore"].pop()
    del seen["build"][1:]     # drop pass 2's integrator and states
    check(restored_step == 20, f"restored step {restored_step}")
    again = read_metrics(out)[-1]
    check(again["step"] == 40, f"restart ended at step {again['step']}")
    diffs = {k: abs(again[k] - recs[-1][k]) for k in ("volume", "ke",
                                                      "max_div")}
    phase("restart", restore_s=restore_s, chunk_s=chunks2,
          pass_wall_s=wall2, step=again["step"], abs_diff=diffs)
    for k in ("volume", "ke"):
        check(diffs[k] <= 1e-5 * abs(recs[-1][k]),
              f"restart {k} differs by {diffs[k]}")
    # max_div of a projected field IS f32 roundoff, of size
    # eps * max|u| / dx (= eps * 0.5 / cfl_dt), and the chip's scatter
    # order is not repeatable: hold the difference to that scale
    div_tol = 8 * 1.2e-7 * 0.5 / recs[-1]["cfl_dt"]
    check(diffs["max_div"] <= div_tol,
          f"restart max_div differs by {diffs['max_div']} (> {div_tol})")

    # ---- the engine that ran is the engine the resolver names
    named = resolve_engine(integ.ins.grid.n, n_markers,
                           get_kernel(integ.ib.kernel)[0],
                           spectral_dtype=integ.ins.spectral_dtype)
    fast = integ.ib.fast
    fallbacks = {k: v for k, v in obs.metrics_snapshot()["counters"].items()
                 if k.startswith("engine_fallbacks_total") and v}
    phase("engine", resolved=named, ran=integ.ib.engine_name,
          engine_class=type(fast).__name__,
          interpret=getattr(fast, "interpret", None), fallbacks=fallbacks)
    check(integ.ib.engine_name == named,
          f"ran {integ.ib.engine_name!r}, resolver names {named!r}")
    check(not fallbacks, f"engine fallback recorded: {fallbacks}")
    check(getattr(fast, "interpret", False) is False,
          "Pallas engine in interpret mode")

    # ---- one step of the same state: resolved engine vs XLA scatter
    ref, _ = build_shell_example(
        input_db=db, dtype=jnp.float32,
        use_fast_interaction=False, engine_fallback=False)
    t0 = time.perf_counter()
    a = jax.block_until_ready(jax.jit(integ.step)(state0, dt))
    b = jax.block_until_ready(jax.jit(ref.step)(state0, dt))
    # u carries the spread, U (marker velocity) the interp; X moves by
    # dt*U, below one ulp of X after a single step, so it is compared
    # as a position, not as a displacement
    diffs = {"u": max(rel_diff(x, y) for x, y in zip(a.ins.u, b.ins.u)),
             "U": rel_diff(a.U, b.U), "X": rel_diff(a.X, b.X)}
    tol = TOL_BF16 if named.endswith("bf16") else TOL_F32
    tols = {"u": tol, "U": 2 * tol, "X": tol}   # U: spread, then interp
    phase("scatter_vs_engine", engine=named, rel_diff=diffs, tol=tols,
          seconds=time.perf_counter() - t0)
    check(all(diffs[k] < tols[k] for k in diffs),
          f"{named} vs scatter: {diffs} (tol {tols})")
    phase("memory", peak_bytes_in_use=peak_bytes(jax.devices()[0]))


def four_chips(args, jax, out):
    import jax.numpy as jnp

    from ibamr_tpu.models.shell3d import build_shell_example
    from ibamr_tpu.parallel import mesh as pmesh
    from ibamr_tpu.utils.hierarchy_driver import HierarchyDriver, RunConfig

    check(len(jax.devices()) == 4,
          f"--chips 4 needs 4 devices, found {len(jax.devices())}")
    inp, db, dt = smoke_input(args, out, "input3d.smoke4",
                              num_steps=10, viz=10, restart=0)
    mod, seen = load_main()

    # learn (not steer) whether the S2 marker facade engaged
    wrapped = []
    orig_wrap = pmesh._wrap_sharded_markers

    def wrap_spy(*a, **kw):
        wrapped.append(orig_wrap(*a, **kw))
        return wrapped[-1]

    pmesh._wrap_sharded_markers = wrap_spy
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        state4, wall, chunks = run_main(
            mod, ["main.py", inp], os.path.join(out, "ledger_4chip.jsonl"))
    pmesh._wrap_sharded_markers = orig_wrap
    _, (integ, _) = seen["build"][0]
    check(len(wrapped) == 1, "make_sharded_ib_step was not reached")
    transfers = ("S2 ShardedInteraction (owner-bucketed markers)"
                 if wrapped[0] is not None
                 else f"GSPMD over {integ.ib.engine_name}")
    phase("sharded_run", n_cells=list(integ.ins.grid.n),
          markers=int(state4.X.shape[0]), steps=10, chunk_s=chunks,
          pass_wall_s=wall, marker_transfers=transfers,
          resolved_engine=integ.ib.engine_name,
          warnings=[str(w.message) for w in caught])

    gshape = tuple(integ.ins.grid.n)
    grid_leaf_devs = []
    for path, leaf in jax.tree_util.tree_leaves_with_path(state4):
        devs = sorted(s.device.id for s in leaf.addressable_shards)
        per_dev = leaf.addressable_shards[0].data.nbytes
        phase("leaf", leaf=jax.tree_util.keystr(path),
              shape=list(leaf.shape), sharding=str(leaf.sharding),
              devices=devs, bytes_per_device=per_dev)
        if tuple(leaf.shape) == gshape:
            grid_leaf_devs.append((devs, per_dev * 4 == leaf.nbytes))
    check(grid_leaf_devs and all(len(d) == 4 and split
                                 for d, split in grid_leaf_devs),
          f"grid leaves are not spread over 4 devices: {grid_leaf_devs}")

    # ---- what it is compared with: 10 steps on ONE device, exact-f32
    # scatter transfers (the S2 engine's local arithmetic), same driver
    ref, s1 = build_shell_example(
        input_db=db, dtype=jnp.float32,
        use_fast_interaction=False, engine_fallback=False)
    step1 = jax.jit(lambda s, d: ref.step(s, d))
    t0 = time.perf_counter()
    state1 = jax.block_until_ready(HierarchyDriver(
        ref, RunConfig(dt=dt, num_steps=10, health_interval=10),
        step_fn=step1).run(s1))
    one_s = time.perf_counter() - t0
    check({d.id for d in state1.X.devices()} == {jax.devices()[0].id},
          "one-device reference is not on one device")
    diffs = {"u": max(rel_diff(x, y)
                      for x, y in zip(state4.ins.u, state1.ins.u)),
             "p": rel_diff(state4.ins.p, state1.ins.p),
             "X": rel_diff(state4.X, state1.X),
             "U": rel_diff(state4.U, state1.U)}
    phase("sharded_vs_one_device", rel_diff=diffs, tol=TOL_SHARDED,
          one_device_s=one_s,
          peak_bytes_in_use=[peak_bytes(d) for d in jax.devices()])
    for k, v in diffs.items():
        check(v < TOL_SHARDED[k], f"sharded vs one device: {k} differs "
              f"by {v} (tol {TOL_SHARDED[k]})")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(4,),
                    help="run ONLY the four-chip sharded path and the "
                         "one-device run it is compared with")
    ap.add_argument("--rehearse", action="store_true",
                    help="16^3, n_lat=n_lon=8: walk every phase off the "
                         "chip; always exits non-zero")
    args = ap.parse_args()

    from ibamr_tpu.serve import aot_cache
    from ibamr_tpu.utils.backend_guard import auto_backend

    jax = auto_backend()      # raises unless TPU (or JAX_PLATFORMS=cpu)
    dev = jax.devices()[0]
    check(dev.platform == "tpu" or args.rehearse,
          f"platform {dev.platform!r} is not a TPU")
    cache_dir = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
                 or os.path.join(aot_cache.REPO_ROOT, ".jax_cache"))
    before = cache_entries(cache_dir)
    out = OUT + ("_4chip" if args.chips else "")
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out)
    os.makedirs(os.path.dirname(PHASE_LOG), exist_ok=True)
    open(PHASE_LOG, "w").close()
    phase("start", platform=dev.platform, device_kind=dev.device_kind,
          devices=len(jax.devices()), jax=jax.__version__,
          rehearse=args.rehearse, out=out)

    (four_chips if args.chips else one_chip)(args, jax, out)

    phase("compile_cache", dir=cache_dir, entries_before=before,
          entries_after=cache_entries(cache_dir))
    check(dev.platform == "tpu" and not args.rehearse,
          "rehearsal walked every phase; not a chip run")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
