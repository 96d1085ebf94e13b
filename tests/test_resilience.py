"""Resilience layer (PR 2): atomic verified checkpoints, supervised
rollback-and-retry, graceful engine degradation, fault injection.

Every failure path the recovery machinery claims to handle is
EXERCISED here with a deterministic injected fault
(tools.fault_injection): torn/corrupt/uncommitted checkpoints, flaky
writes under the async writer, NaN divergence under the supervisor,
preemption signals, a monkeypatch-killed transfer engine, and a
SIGKILL-mid-write subprocess drill proving no crash sequence loses
more than one checkpoint interval.

PR 3 adds the SILENT failures: a finite exponential blow-up caught by
the fused health vitals BEFORE any NaN exists, a stagnating Krylov
solve escalated through its declared chain (and surfaced as a
structured ``SolverBreakdown`` when the chain exhausts), and a stalled
chunk flagged by the run watchdog's heartbeat.
"""

import json
import os
import signal
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ibamr_tpu.grid import StaggeredGrid
from ibamr_tpu.integrators.ins import INSStaggeredIntegrator
from ibamr_tpu.solvers.escalation import (ESCALATION_FALLBACKS,
                                          ESCALATION_LEVELS,
                                          SolverBreakdown, escalate_solve,
                                          escalation_chain,
                                          record_solve_stats)
from ibamr_tpu.solvers.krylov import SolveResult, bicgstab, fgmres
from ibamr_tpu.utils import checkpoint as ckpt
from ibamr_tpu.utils.checkpoint import (AsyncCheckpointWriter,
                                        CheckpointCorruptError,
                                        latest_step, restore_checkpoint,
                                        save_checkpoint,
                                        verify_checkpoint)
from ibamr_tpu.utils.health import (FATAL, OK, WARN, HealthDegraded,
                                    HealthProbe)
from ibamr_tpu.utils.hierarchy_driver import (HierarchyDriver, RunConfig,
                                              SimulationDiverged)
from ibamr_tpu.utils.supervisor import ResilientDriver
from ibamr_tpu.utils.watchdog import (RunWatchdog, heartbeat_age,
                                      read_heartbeat, write_heartbeat)
from tools.fault_injection import (corrupt_checkpoint, crash_state,
                                   drop_sidecar,
                                   failing_checkpoint_writes,
                                   growth_injector_step, inject_nan,
                                   nan_injector_step, slow_metrics,
                                   stagnating_operator,
                                   truncate_checkpoint)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _ins(n=16, mu=0.01, **kw):
    g = StaggeredGrid(n=(n, n), x_lo=(0.0, 0.0), x_up=(1.0, 1.0))
    return INSStaggeredIntegrator(g, rho=1.0, mu=mu, dtype=jnp.float64,
                                  **kw)


def _tg_state(integ):
    import math
    g = integ.grid
    xf, yc = g.face_centers(0, jnp.float64)
    xc, yf = g.face_centers(1, jnp.float64)
    u = jnp.sin(2 * math.pi * xf) * jnp.cos(2 * math.pi * yc) + 0 * yc
    v = -jnp.cos(2 * math.pi * xc) * jnp.sin(2 * math.pi * yf) + 0 * xc
    return integ.initialize(u0_arrays=(u, v))


# ---------------------------------------------------------------------------
# checkpoint integrity: every damage mode a crash/bad disk can inflict
# ---------------------------------------------------------------------------

def test_truncated_checkpoint_skipped(tmp_path):
    d = str(tmp_path)
    for s in (5, 10):
        save_checkpoint(d, crash_state(s), s)
    truncate_checkpoint(d, 10)
    assert not verify_checkpoint(d, 10)
    assert verify_checkpoint(d, 5)
    assert latest_step(d) == 5                      # newest VERIFIED
    assert latest_step(d, verified_only=False) == 10
    with pytest.warns(UserWarning, match="unverified"):
        st, k, _ = restore_checkpoint(d, crash_state(5))
    assert k == 5
    assert np.array_equal(np.asarray(st["u"]), crash_state(5)["u"])


def test_byte_flip_caught_by_whole_file_crc(tmp_path):
    d = str(tmp_path)
    save_checkpoint(d, crash_state(7), 7)
    assert verify_checkpoint(d, 7)
    corrupt_checkpoint(d, 7)                        # same size, one bit
    assert not verify_checkpoint(d, 7)
    with pytest.raises(CheckpointCorruptError):
        restore_checkpoint(d, crash_state(7), step=7)
    with pytest.warns(UserWarning), pytest.raises(FileNotFoundError,
                                                  match="all corrupt"):
        restore_checkpoint(d, crash_state(7))       # nothing to fall to
    with pytest.raises(FileNotFoundError):
        restore_checkpoint(d, crash_state(7), step=99)


def test_missing_sidecar_means_uncommitted(tmp_path):
    d = str(tmp_path)
    for s in (5, 10):
        save_checkpoint(d, crash_state(s), s)
    drop_sidecar(d, 10)
    assert not verify_checkpoint(d, 10)
    assert latest_step(d) == 5


def test_leaf_crc_mismatch_detected_and_fallback(tmp_path):
    """A tampered sidecar whose file-level digest still matches must be
    caught by the per-leaf CRC at load time, and step=None restore must
    fall back to the previous verified checkpoint."""
    d = str(tmp_path)
    for s in (5, 10):
        save_checkpoint(d, crash_state(s), s)
    side = os.path.join(d, "restore.00000010.json")
    with open(side) as f:
        meta = json.load(f)
    meta["integrity"]["leaves"]["u"] ^= 1
    with open(side, "w") as f:
        json.dump(meta, f)
    assert verify_checkpoint(d, 10)     # whole-file digest still OK...
    with pytest.raises(CheckpointCorruptError, match="CRC32"):
        restore_checkpoint(d, crash_state(10), step=10)
    with pytest.warns(UserWarning, match="skipping checkpoint step 10"):
        st, k, _ = restore_checkpoint(d, crash_state(5))
    assert k == 5


def test_prune_never_deletes_last_verified(tmp_path):
    d = str(tmp_path)
    for s in (1, 2, 3):
        save_checkpoint(d, crash_state(s), s, keep=0)    # keep=0: no prune
    corrupt_checkpoint(d, 2)
    corrupt_checkpoint(d, 3)
    ckpt._prune(d, keep=1)
    # doomed = {1, 2}; 1 is the newest verified so it is spared
    assert verify_checkpoint(d, 1)
    assert not os.path.exists(os.path.join(d, "restore.00000002.npz"))
    assert latest_step(d) == 1
    st, k, _ = restore_checkpoint(d, crash_state(1))
    assert k == 1


def test_async_writer_retries_flaky_write(tmp_path):
    d = str(tmp_path)
    w = AsyncCheckpointWriter(d, keep=3)
    try:
        with failing_checkpoint_writes({0}) as ctr:
            w.save(crash_state(4), 4)
            w.wait()
        assert ctr["calls"] == 2                    # attempt + retry
        assert verify_checkpoint(d, 4)
    finally:
        w.close()


def test_async_writer_double_failure_surfaces_once(tmp_path):
    d = str(tmp_path)
    w = AsyncCheckpointWriter(d, keep=3)
    try:
        with failing_checkpoint_writes({0, 1}):
            w.save(crash_state(4), 4)
            with pytest.raises(OSError, match="injected"):
                w.wait()
        # the failure must not poison later saves
        w.save(crash_state(8), 8)
        w.wait()
        assert latest_step(d) == 8
        assert not verify_checkpoint(d, 4)
    finally:
        w.close()


def test_inject_nan_matches_only_floating_leaves():
    st = inject_nan(crash_state(3), "u")
    assert np.all(np.isnan(np.asarray(st["u"])))
    assert int(st["k"]) == 3
    with pytest.raises(KeyError):
        inject_nan(crash_state(3), "nope")
    with pytest.raises(KeyError):
        inject_nan(crash_state(3), "k")             # int leaf: no match


# ---------------------------------------------------------------------------
# supervised rollback-and-retry
# ---------------------------------------------------------------------------

def _nan_driver(integ, dt0, *, gated=True, num_steps=12):
    cfg = RunConfig(dt=dt0, num_steps=num_steps, restart_interval=4,
                    health_interval=2)
    return HierarchyDriver(
        integ, cfg,
        step_fn=nan_injector_step(
            integ.step, at_step=6, leaf_path="u[0]",
            dt_gate=dt0 * 0.99 if gated else None))


def test_supervisor_recovers_from_divergence(tmp_path):
    """The acceptance drill: NaN at step 6 -> rollback to the step-4
    checkpoint, dt backoff (which disarms the dt-gated fault), run to
    completion, one structured JSONL incident — and the recovered run
    is BITWISE the clean run restarted from that checkpoint at the
    backed-off dt."""
    integ = _ins()
    st0 = _tg_state(integ)
    dt0 = 1e-3
    d = str(tmp_path)
    drv = _nan_driver(integ, dt0)
    sup = ResilientDriver(drv, d, max_retries=2, dt_backoff=0.5,
                          handle_signals=False)
    out = sup.run(st0)
    assert int(out.k) == 12
    assert bool(jnp.all(jnp.isfinite(out.u[0])))
    assert not sup.preempted
    assert drv.cfg.dt == pytest.approx(dt0 * 0.5)

    [rec] = [r for r in sup.incidents if r["event"] == "divergence"]
    assert rec["step"] == 6
    assert rec["bad_leaves"]
    assert rec["retry"] == 1 and rec["max_retries"] == 2
    assert rec["rollback_step"] == 4 and rec["from_checkpoint"]
    assert rec["dt_before"] == pytest.approx(dt0)
    assert rec["dt_after"] == pytest.approx(dt0 * 0.5)
    with open(os.path.join(d, "incidents.jsonl")) as f:
        lines = [json.loads(l) for l in f]
    assert [l["event"] for l in lines] == ["divergence"]
    assert all("time" in l for l in lines)

    # checkpoints landed at the cadence of the RECOVERED run
    assert latest_step(d) == 12

    # recovered == clean-restart-from-checkpoint, bitwise
    st4, k4, _ = restore_checkpoint(d, out, step=4)
    assert k4 == 4
    drv2 = _nan_driver(integ, dt0)
    drv2.cfg.dt = dt0 * 0.5
    ref = drv2.run(st4, start_step=4)
    for a, b in zip(jax.tree_util.tree_leaves(out),
                    jax.tree_util.tree_leaves(ref)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_supervisor_gives_up_after_max_retries(tmp_path):
    """An UNGATED fault re-fires on every retry: the supervisor must
    stop at max_retries, record a give_up incident, and re-raise."""
    integ = _ins()
    st0 = _tg_state(integ)
    drv = _nan_driver(integ, 1e-3, gated=False)
    sup = ResilientDriver(drv, str(tmp_path), max_retries=1,
                          dt_backoff=0.5, handle_signals=False)
    with pytest.raises(SimulationDiverged):
        sup.run(st0)
    assert [r["event"] for r in sup.incidents] == ["divergence",
                                                   "give_up"]
    assert sup.incidents[-1]["retries"] == 1


def test_supervisor_preemption_writes_final_checkpoint(tmp_path):
    """SIGTERM mid-run: the installed handler raises through the step
    loop; the supervisor drains the writer, writes a final synchronous
    checkpoint of the last healthy state, records the incident, and
    returns instead of dying."""
    integ = _ins()
    st0 = _tg_state(integ)
    d = str(tmp_path)
    cfg = RunConfig(dt=1e-3, num_steps=40, restart_interval=10,
                    health_interval=2)
    fired = []

    def metrics_fn(s, k):
        if k >= 6 and not fired:
            fired.append(k)
            os.kill(os.getpid(), signal.SIGTERM)
        return None

    drv = HierarchyDriver(integ, cfg, metrics_fn=metrics_fn)
    sup = ResilientDriver(drv, d, handle_signals=True)
    before = signal.getsignal(signal.SIGTERM)
    out = sup.run(st0)
    assert sup.preempted and sup.preempt_signum == signal.SIGTERM
    assert signal.getsignal(signal.SIGTERM) == before   # handler restored
    k_final = int(out.k)
    assert k_final >= 6
    assert latest_step(d) == k_final
    st, k, meta = restore_checkpoint(d, out)
    assert k == k_final and meta.get("preempted") is True
    [rec] = [r for r in sup.incidents if r["event"] == "preemption"]
    assert rec["signal"] == "SIGTERM"
    assert rec["checkpoint_step"] == k_final


# ---------------------------------------------------------------------------
# graceful engine degradation
# ---------------------------------------------------------------------------

def test_engine_fallback_vocabulary():
    from ibamr_tpu.models.engine_resolver import (ENGINES,
                                                  fallback_chain,
                                                  normalize_engine_name)

    assert normalize_engine_name(True) == "mxu"
    assert normalize_engine_name(False) == "scatter"
    assert normalize_engine_name(None) == "scatter"
    assert fallback_chain("hybrid_bf16") == [
        "hybrid_bf16", "packed_bf16", "packed", "scatter"]
    assert fallback_chain("pallas_packed") == [
        "pallas_packed", "packed", "scatter"]
    assert fallback_chain("scatter") == ["scatter"]
    for name in ENGINES:
        chain = fallback_chain(name)
        assert chain[-1] == "scatter"
        assert len(chain) == len(set(chain))        # no cycles
    with pytest.raises(KeyError):
        fallback_chain("no_such_engine")


def test_failed_engine_degrades_and_matches_fallback(monkeypatch):
    """A transfer engine whose build/compile probe fails must degrade
    down the registry chain with a warning — and the degraded model's
    step must be BITWISE the step of a model built directly on the
    fallback engine."""
    from ibamr_tpu.models.shell3d import build_shell_example
    from ibamr_tpu.ops import pallas_interaction

    def boom(self, *a, **kw):
        raise RuntimeError("injected engine failure")

    monkeypatch.setattr(pallas_interaction.HybridPackedInteraction,
                        "spread_vel", boom)
    with pytest.warns(RuntimeWarning, match="degrading to 'packed_bf16'"):
        integ, state = build_shell_example(
            n_cells=16, n_lat=8, n_lon=8,
            use_fast_interaction="hybrid_bf16", engine_fallback=True)
    assert type(integ.ib.fast).__name__ == "PackedInteraction"
    assert integ.ib.fast.compute_dtype == jnp.bfloat16

    integ2, state2 = build_shell_example(
        n_cells=16, n_lat=8, n_lon=8,
        use_fast_interaction="packed_bf16", engine_fallback=False)
    s1 = jax.jit(integ.step)(state, 1e-4)
    s2 = jax.jit(integ2.step)(state2, 1e-4)
    for a, b in zip(jax.tree_util.tree_leaves(s1),
                    jax.tree_util.tree_leaves(s2)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_engine_fallback_off_raises(monkeypatch):
    """With the fallback disabled a broken engine fails the build loudly
    (construction failure here: without the compile probe, a broken
    METHOD would only surface at first step)."""
    from ibamr_tpu.models.shell3d import build_shell_example
    from ibamr_tpu.ops import pallas_interaction

    def boom(self, *a, **kw):
        raise RuntimeError("injected engine failure")

    monkeypatch.setattr(pallas_interaction.HybridPackedInteraction,
                        "__init__", boom)
    with pytest.raises(RuntimeError, match="injected"):
        build_shell_example(n_cells=16, n_lat=8, n_lon=8,
                            use_fast_interaction="hybrid_bf16",
                            engine_fallback=False)


# ---------------------------------------------------------------------------
# satellites: retrace observable + overflow-pad debug check
# ---------------------------------------------------------------------------

def test_trace_counts_distinct_signatures():
    """trace_counts counts DISTINCT input signatures: a benign re-trace
    of a known signature (cache cleared) must not read as a retrace; a
    genuinely new signature must."""
    integ = _ins()
    st = _tg_state(integ)
    cfg = RunConfig(dt=1e-3, num_steps=20, health_interval=10)
    drv = HierarchyDriver(integ, cfg)
    out = drv.run(st)
    assert drv.trace_counts[10] == 1
    jax.clear_caches()                  # forces a re-trace next call
    drv._chunk(10)(out, 1e-3)
    assert drv.trace_counts[10] == 1    # same signature: no retrace
    drv._chunk(10)(out, jnp.asarray(1e-3, dtype=jnp.float32))
    assert drv.trace_counts[10] == 2    # new dt dtype: real retrace


def test_overflow_pad_debug_check_clean():
    """Debug mode asserts (in-jit, via host callback) that o_w == 0
    overflow pad entries contribute nothing; the clean path must pass
    and still match the scatter oracle."""
    from ibamr_tpu.ops import interaction
    from ibamr_tpu.ops import interaction_fast as ifast

    grid = StaggeredGrid(n=(32, 32), x_lo=(0, 0), x_up=(1, 1))
    rng = np.random.RandomState(2)
    X = jnp.asarray(0.1 + 0.05 * rng.rand(200, 2), dtype=jnp.float64)
    F = jnp.asarray(rng.randn(200, 2), dtype=jnp.float64)
    prev = ifast.debug_overflow_pad(True)
    try:
        assert prev is False
        fast = ifast.FastInteraction(grid, tile=8, cap=8)
        b = fast.buckets(X)
        assert bool(b.any_overflow)     # pads actually in play
        f_new = fast.spread_vel(F, X)
        jax.block_until_ready(f_new)    # host check ran, no violation
        f_ref = interaction.spread_vel(F, grid, X)
        for a, c in zip(f_ref, f_new):
            scale = float(jnp.max(jnp.abs(a))) + 1e-12
            assert float(jnp.max(jnp.abs(a - c))) < 1e-5 * scale
        u = tuple(jnp.asarray(rng.randn(32, 32), dtype=jnp.float64)
                  for _ in range(2))
        U = fast.interpolate_vel(u, X)
        jax.block_until_ready(U)
        U_ref = interaction.interpolate_vel(u, grid, X)
        assert float(jnp.max(jnp.abs(U_ref - U))) < 1e-5
    finally:
        ifast.debug_overflow_pad(prev)


# ---------------------------------------------------------------------------
# cross-mesh restore of a RECOVERED run
# ---------------------------------------------------------------------------

def test_cross_mesh_restore_of_recovered_run(tmp_path):
    """A supervised run that rolled back on one device resumes onto the
    virtual 8-device mesh: restored leaves are bitwise the single-device
    final state, the same-mesh continuation is bitwise, and the sharded
    continuation matches the single-device one to spectral-solver
    tolerance (the test_parallel cross-mesh bound)."""
    from jax.sharding import NamedSharding, PartitionSpec as PSpec

    from ibamr_tpu.parallel import make_mesh
    from ibamr_tpu.parallel.mesh import grid_pspec, make_sharded_ins_step

    integ = _ins()
    st0 = _tg_state(integ)
    dt0 = 1e-3
    d = str(tmp_path)
    drv = _nan_driver(integ, dt0)
    sup = ResilientDriver(drv, d, max_retries=2, dt_backoff=0.5,
                          handle_signals=False)
    out = sup.run(st0)
    assert [r["event"] for r in sup.incidents] == ["divergence"]
    assert latest_step(d) == 12
    dt2 = drv.cfg.dt                    # the backed-off dt resumes

    # same-mesh restore: bitwise state, bitwise continuation
    st1, k1, _ = restore_checkpoint(d, out)
    assert k1 == 12
    for a, b in zip(jax.tree_util.tree_leaves(st1),
                    jax.tree_util.tree_leaves(out)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    step1 = jax.jit(integ.step)
    one_a, one_b = step1(st1, dt2), step1(out, dt2)
    for a, b in zip(jax.tree_util.tree_leaves(one_a),
                    jax.tree_util.tree_leaves(one_b)):
        assert np.array_equal(np.asarray(a), np.asarray(b))

    # cross-mesh restore: grid-shaped leaves spatially sharded over 8
    # devices, scalars replicated (test_parallel resharder idiom)
    mesh = make_mesh(8, max_axes=2)
    spatial = NamedSharding(mesh, grid_pspec(mesh, 2))
    repl = NamedSharding(mesh, PSpec())

    def resharder(key, arr):
        sh = spatial if np.ndim(arr) == 2 else repl
        return jax.device_put(jnp.asarray(arr), sh)

    sh_st, k8, _ = restore_checkpoint(d, out, sharding_fn=resharder)
    assert k8 == 12
    assert len(sh_st.u[0].sharding.device_set) == 8
    for a, b in zip(jax.tree_util.tree_leaves(sh_st),
                    jax.tree_util.tree_leaves(out)):
        assert np.array_equal(np.asarray(a), np.asarray(b))

    stepN = make_sharded_ins_step(integ, mesh)
    sh, one = sh_st, st1
    for _ in range(3):
        sh = stepN(sh, dt2)
        one = step1(one, dt2)
    np.testing.assert_allclose(np.asarray(sh.u[0]), np.asarray(one.u[0]),
                               rtol=1e-10, atol=1e-11)
    np.testing.assert_allclose(np.asarray(sh.p), np.asarray(one.p),
                               rtol=1e-10, atol=1e-11)


# ---------------------------------------------------------------------------
# SIGKILL-mid-write subprocess drill (slow tier)
# ---------------------------------------------------------------------------

def _spawn_crash_child(d, steps=60, interval=5):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.Popen(
        [sys.executable, "-m", "tools.fault_injection",
         "--crash-child", str(d), "--steps", str(steps),
         "--interval", str(interval)],
        cwd=REPO_ROOT, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True, bufsize=1)


def test_kill_mid_write_loses_at_most_one_interval(tmp_path):
    """SIGKILL the checkpoint-writer child the instant a save lands,
    three crash cycles in a row: after every kill the newest VERIFIED
    checkpoint is no older than the last acknowledged save (at most
    the in-flight interval is lost) and restores bitwise against the
    closed-form trajectory. A deliberate corruption on top of the last
    crash costs exactly one more interval. Then the child runs to
    completion from the wreckage."""
    d = str(tmp_path)
    last_acked = 0
    for cycle in range(3):
        p = _spawn_crash_child(d)
        acked = None
        try:
            for line in p.stdout:
                if line.startswith("SAVED"):
                    acked = int(line.split()[1])
                    if acked > last_acked:
                        break           # kill mid-run, write just landed
                elif line.startswith("DONE"):
                    break
        finally:
            p.kill()
            p.wait()
        assert acked is not None and acked > last_acked, \
            f"cycle {cycle}: child made no progress"
        last_acked = acked
        ls = latest_step(d)
        assert ls is not None and ls >= acked       # <= 1 interval lost
        st, k, _ = restore_checkpoint(d, template=crash_state(ls),
                                      step=ls)
        assert k == ls
        assert np.array_equal(np.asarray(st["u"]), crash_state(ls)["u"])

    # compound the crash with bitrot on the newest checkpoint: the
    # fallback costs one more interval, never the whole chain
    newest = latest_step(d)
    corrupt_checkpoint(d, newest)
    ls2 = latest_step(d)
    assert ls2 is not None and ls2 >= newest - 5
    with pytest.warns(UserWarning):
        st, k, _ = restore_checkpoint(d, template=crash_state(ls2))
    assert k == ls2
    assert np.array_equal(np.asarray(st["u"]), crash_state(ls2)["u"])

    p = _spawn_crash_child(d)
    out, _ = p.communicate(timeout=300)
    assert p.returncode == 0, out
    assert "DONE" in out
    assert latest_step(d) == 60
    st, k, _ = restore_checkpoint(d, template=crash_state(60))
    assert k == 60
    assert np.array_equal(np.asarray(st["u"]), crash_state(60)["u"])


# ---------------------------------------------------------------------------
# PR 3: fail-fast input validation
# ---------------------------------------------------------------------------

def test_runconfig_rejects_bad_inputs():
    """A typo'd input file must die at construction with the offending
    field named — not produce a zero-length scan hours later."""
    with pytest.raises(ValueError, match="dt"):
        RunConfig(dt=0.0, num_steps=10)
    with pytest.raises(ValueError, match="dt"):
        RunConfig(dt=float("nan"), num_steps=10)
    with pytest.raises(ValueError, match="num_steps"):
        RunConfig(dt=1e-3, num_steps=-1)
    with pytest.raises(ValueError, match="restart_interval"):
        RunConfig(dt=1e-3, num_steps=10, restart_interval=-4)
    with pytest.raises(ValueError, match="health_interval"):
        RunConfig(dt=1e-3, num_steps=10, health_interval=0)
    with pytest.raises(ValueError, match="cfl"):
        RunConfig(dt=1e-3, num_steps=10, cfl=0.0)
    # the valid edge cases stay valid: zero steps, cadences off
    cfg = RunConfig(dt=1e-3, num_steps=0)
    assert cfg.restart_interval == 0


# ---------------------------------------------------------------------------
# PR 3: fused health vitals — jit side, host triage, end-to-end rollback
# ---------------------------------------------------------------------------

def test_health_probe_measure_matches_state():
    """The jit-side vitals vector must report the real physics numbers
    of the state it measured."""
    import math
    integ = _ins()
    st = _tg_state(integ)
    probe = HealthProbe.for_integrator(integ)
    dt = 1e-3
    v = np.asarray(jax.jit(probe.measure)(st, dt))
    assert v.shape == (len(HealthProbe.VITALS_FIELDS),) \
        and v.dtype == np.float32
    d = HealthProbe.unpack(v)
    assert d["finite"] == 1.0
    max_u = max(float(jnp.max(jnp.abs(c))) for c in st.u)
    assert d["max_u"] == pytest.approx(max_u, rel=1e-5)
    assert d["cfl"] == pytest.approx(max_u * dt / min(integ.grid.dx),
                                     rel=1e-5)
    assert d["div_norm"] >= 0.0
    assert math.isfinite(d["func"])     # default functional: KE
    assert d["func"] == pytest.approx(float(integ.kinetic_energy(st)),
                                      rel=1e-5)


def test_health_probe_triage_streaks_and_baseline():
    """Host-side triage: WARN streaks escalate only at ``sustain``,
    FATAL fires immediately, the functional baseline is the first
    observed value, and the streak resets after a raise so a supervised
    retry starts clean."""
    probe = HealthProbe(max_u_warn=1.0, max_u_fatal=10.0,
                        func_growth_warn=4.0, sustain=2)
    ok = np.array([1.0, 0.5, 0.0, 0.0, 1.0], np.float32)
    warn = np.array([1.0, 2.0, 0.0, 0.0, 1.0], np.float32)
    assert probe.check(ok, step=1, dt=1e-3)["level"] == OK
    rec = probe.check(warn, step=2, dt=1e-3)
    assert rec["level"] == WARN and rec["warn_streak"] == 1
    with pytest.raises(HealthDegraded) as ei:    # 2nd WARN = sustain
        probe.check(warn, step=3, dt=1e-3)
    e = ei.value
    assert isinstance(e, SimulationDiverged)     # supervisor catches it
    assert e.kind == "health_degraded"
    assert e.step == 3 and e.bad_leaves == []    # nothing non-finite
    assert e.reasons and "max_u" in e.reasons[0]
    assert set(e.incident_payload()) == {"reasons", "vitals"}
    # the raise reset the streak: one clean chunk, one WARN chunk, fine
    assert probe.check(ok, step=4, dt=1e-3)["level"] == OK
    grown = np.array([1.0, 0.5, 0.0, 0.0, 8.0], np.float32)
    rec = probe.check(grown, step=5, dt=1e-3)    # func baseline was 1.0
    assert rec["level"] == WARN
    assert rec["func_growth"] == pytest.approx(8.0)
    # FATAL needs no streak
    fatal = np.array([1.0, 50.0, 0.0, 0.0, 1.0], np.float32)
    with pytest.raises(HealthDegraded):
        probe.check(fatal, step=6, dt=1e-3)
    assert probe.history[-1]["level"] == FATAL
    with pytest.raises(ValueError, match="sustain"):
        HealthProbe(sustain=0)


def test_health_probe_adds_no_retrace():
    """The fused vitals vector rides the SAME one-transfer-per-chunk
    sync the plain finite bool paid: one trace per chunk length, every
    chunk classified, no extra signatures."""
    integ = _ins()
    st = _tg_state(integ)
    probe = HealthProbe.for_integrator(integ)
    cfg = RunConfig(dt=1e-3, num_steps=12, health_interval=4)
    drv = HierarchyDriver(integ, cfg, health_probe=probe)
    out = drv.run(st)
    assert int(out.k) == 12
    assert drv.trace_counts == {4: 1}           # 3 chunks, ONE signature
    assert len(probe.history) == 3
    assert [r["step"] for r in probe.history] == [4, 8, 12]
    assert all(r["finite"] >= 1.0 for r in probe.history)
    assert drv.last_vitals is probe.history[-1]


def test_health_rollback_before_any_nan(tmp_path):
    """The PR-3 acceptance drill: a FINITE exponential velocity growth
    (dt-gated) trips the functional-growth WARN streak; the supervisor
    rolls back to a checkpoint that predates the degradation and the dt
    backoff disarms the fault — with ZERO non-finite values ever
    observed anywhere, and at most one checkpoint interval lost."""
    integ = _ins(mu=0.05)
    st0 = _tg_state(integ)
    dt0 = 1e-3
    d = str(tmp_path)
    probe = HealthProbe.for_integrator(integ, func_growth_warn=8.0,
                                       sustain=2)
    cfg = RunConfig(dt=dt0, num_steps=12, restart_interval=4,
                    health_interval=2)
    drv = HierarchyDriver(
        integ, cfg,
        step_fn=growth_injector_step(integ.step, rate=1.5, leaf_path="u",
                                     dt_gate=dt0 * 0.99),
        health_probe=probe)
    sup = ResilientDriver(drv, d, max_retries=2, dt_backoff=0.5,
                          handle_signals=False)
    out = sup.run(st0)
    assert int(out.k) == 12
    assert all(bool(jnp.all(jnp.isfinite(l)))
               for l in jax.tree_util.tree_leaves(out)
               if hasattr(l, "dtype"))
    # the whole point: every chunk the probe ever classified — before,
    # during and after the blow-up — was still finite
    assert probe.history
    assert all(rec["finite"] >= 1.0 for rec in probe.history)

    [rec] = [r for r in sup.incidents if r["event"] == "divergence"]
    assert rec["kind"] == "health_degraded"
    assert rec["bad_leaves"] == []
    # WARN at step 6, fired at step 8 -> newest checkpoint is step 4:
    # at most one restart interval lost
    assert rec["step"] == 8
    assert rec["rollback_step"] == 4 and rec["from_checkpoint"]
    assert rec["reasons"] and "grew" in rec["reasons"][0]
    assert rec["vitals"]["func_growth"] > 8.0
    assert rec["dt_after"] == pytest.approx(dt0 * 0.5)

    # the JSONL mirror carries the v2 ``kind`` discriminator
    with open(os.path.join(d, "incidents.jsonl")) as f:
        lines = [json.loads(l) for l in f]
    assert [l["kind"] for l in lines] == ["health_degraded"]
    # the checkpoint chain finished clean and never held garbage
    assert latest_step(d) == 12
    st4, k4, _ = restore_checkpoint(d, out, step=4)
    assert k4 == 4
    assert all(bool(jnp.all(jnp.isfinite(l)))
               for l in jax.tree_util.tree_leaves(st4)
               if hasattr(l, "dtype"))


# ---------------------------------------------------------------------------
# PR 3: solver non-convergence surfacing + escalation
# ---------------------------------------------------------------------------

def test_escalation_chain_vocabulary():
    """The chain registry mirrors the transfer engines': one flat name->next
    dict, chains derived by walking it, terminal level ends every walk,
    no cycles, unknown names raise."""
    assert [l.name for l in escalation_chain()] == [
        "base", "restarts_x4", "deep_x4_inner_x2"]
    assert [l.name for l in escalation_chain("restarts_x4")] == [
        "restarts_x4", "deep_x4_inner_x2"]
    assert set(ESCALATION_FALLBACKS) == set(ESCALATION_LEVELS)
    for name in ESCALATION_LEVELS:
        chain = [l.name for l in escalation_chain(name)]
        assert chain[-1] == "deep_x4_inner_x2"
        assert len(chain) == len(set(chain))    # no cycles
    base = ESCALATION_LEVELS["base"]
    assert (base.restarts_scale, base.m_scale, base.inner_scale) == (1, 1, 1)
    with pytest.raises(KeyError, match="no_such_level"):
        escalation_chain("no_such_level")


def test_escalation_walks_chain_and_recovers():
    """A restarted-GMRES-hostile diagonal system fails at base and at
    restarts_x4, converges at deep_x4_inner_x2 — the walk stops there
    and lands ONE recovered ``solver_escalation`` incident."""
    w = jnp.logspace(0.0, 2.0, 48)
    A = lambda x: w * x                                     # noqa: E731
    b = jnp.ones(48)

    def attempt(level, i):
        return fgmres(A, b, m=8 * level.m_scale, tol=1e-4,
                      restarts=1 * level.restarts_scale)

    incidents = []
    sol = escalate_solve(attempt, context="drill",
                         on_incident=incidents.append)
    assert bool(sol.converged)
    [rec] = incidents
    assert rec["event"] == "solver_escalation"
    assert rec["kind"] == "solver_breakdown"
    assert rec["recovered"] is True and rec["context"] == "drill"
    assert rec["level"] == "deep_x4_inner_x2"
    assert [a["converged"] for a in rec["attempts"]] == [False, False,
                                                         True]
    assert [a["level"] for a in rec["attempts"]] == [
        "base", "restarts_x4", "deep_x4_inner_x2"]
    assert rec["attempts"][0]["resnorm"] > rec["attempts"][-1]["resnorm"]


def test_escalation_level0_converging_is_bitwise_plain_solve():
    """When the base geometry converges the walk must add NOTHING: no
    incident, and a result bitwise-identical to the plain solve."""
    A = lambda x: 2.0 * x                                   # noqa: E731
    b = jnp.ones(48)
    ref = fgmres(A, b, m=8, tol=1e-4, restarts=1)
    assert bool(ref.converged)

    incidents = []
    sol = escalate_solve(
        lambda level, i: fgmres(A, b, m=8 * level.m_scale, tol=1e-4,
                                restarts=1 * level.restarts_scale),
        on_incident=incidents.append)
    assert incidents == []
    assert np.array_equal(np.asarray(sol.x), np.asarray(ref.x))
    assert int(sol.iters) == int(ref.iters)
    assert float(sol.resnorm) == float(ref.resnorm)


def test_stagnating_solver_exhausts_chain():
    """A singular operator (``stagnating_operator``) leaves a residual
    floor no level can pass: the chain exhausts, the breakdown incident
    is recorded, and ``SolverBreakdown`` carries the full attempts list
    plus the supervisor-compatible divergence interface."""
    w = jnp.logspace(0.0, 2.0, 48)
    As = stagnating_operator(lambda x: w * x)
    b = jnp.ones(48)
    incidents = []
    with pytest.raises(SolverBreakdown) as ei:
        escalate_solve(
            lambda level, i: fgmres(As, b, m=8 * level.m_scale, tol=1e-4,
                                    restarts=1 * level.restarts_scale),
            context="drill", on_incident=incidents.append, step=42)
    e = ei.value
    assert isinstance(e, SimulationDiverged)
    assert e.kind == "solver_breakdown"
    assert e.step == 42 and e.bad_leaves == []
    assert len(e.attempts) == 3
    assert not any(a["converged"] for a in e.attempts)
    assert e.incident_payload() == {"context": "drill",
                                    "attempts": e.attempts}
    rec = incidents[-1]
    assert rec["event"] == "solver_breakdown"
    assert rec["recovered"] is False and rec["level"] is None
    assert rec["attempts"] == e.attempts


def test_record_solve_stats_eager_jit_and_mirror():
    """Stats surfacing contract: eager solves record synchronously (and
    onto every mirror — the FAC-preconditioner sharing path); traced
    solves record NOTHING unless the owner opted into the callback."""
    class Sink:
        last_solve_stats = None

    sink, mirror = Sink(), Sink()
    sol = SolveResult(x=jnp.zeros(3), iters=jnp.asarray(5),
                      resnorm=jnp.asarray(1e-9),
                      converged=jnp.asarray(True))
    record_solve_stats(sink, sol, solver="fgmres",
                       mirrors=(mirror, None))
    assert sink.last_solve_stats == {"iters": 5, "resnorm": 1e-9,
                                     "converged": True,
                                     "solver": "fgmres"}
    assert mirror.last_solve_stats is sink.last_solve_stats

    # traced, no opt-in: jitted/SPMD paths pay nothing
    silent = Sink()

    @jax.jit
    def f(b):
        record_solve_stats(
            silent, SolveResult(b, jnp.asarray(1), jnp.sum(b),
                                jnp.asarray(True)), solver="x")
        return b

    jax.block_until_ready(f(jnp.ones(3)))
    assert silent.last_solve_stats is None

    # traced WITH opt-in: the debug callback lands host-side
    tapped = Sink()

    @jax.jit
    def g(b):
        record_solve_stats(
            tapped, SolveResult(b, jnp.asarray(7), jnp.sum(b),
                                jnp.asarray(False)),
            solver="cg", use_callback=True)
        return b

    jax.block_until_ready(g(jnp.ones(3)))
    jax.effects_barrier()
    assert tapped.last_solve_stats == {"iters": 7, "resnorm": 3.0,
                                       "converged": False, "solver": "cg"}


def test_stokes_solve_escalated_level0_bitwise():
    """The production wiring: a converging StaggeredStokesSolver base
    solve records ``last_solve_stats`` and ``solve_escalated`` returns
    BITWISE the plain solve with no incident."""
    from ibamr_tpu.solvers.stokes import StaggeredStokesSolver, channel_bc

    n = (12, 12)
    solver = StaggeredStokesSolver(n, (1.0 / 12, 1.0 / 12), channel_bc(2),
                                   alpha=1.0, mu=0.01, tol=1e-8)
    rng = np.random.default_rng(3)
    u = tuple(jnp.asarray(rng.standard_normal(s)) for s in solver.shapes)
    p = jnp.asarray(rng.standard_normal(solver.n))
    rhs = solver.operator((u, p))
    ref = solver.solve(rhs)
    assert bool(ref.converged)
    stats = solver.last_solve_stats
    assert stats["converged"] is True and stats["solver"] == "fgmres"
    assert stats["iters"] == int(ref.iters)
    assert stats["resnorm"] == float(ref.resnorm)

    incidents = []
    sol = solver.solve_escalated(rhs, on_incident=incidents.append)
    assert incidents == []
    for a, b in zip(jax.tree_util.tree_leaves((sol.u, sol.p)),
                    jax.tree_util.tree_leaves((ref.u, ref.p))):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert int(sol.iters) == int(ref.iters)


def test_supervisor_treats_solver_breakdown_like_divergence(tmp_path):
    """A ``SolverBreakdown`` raised at the driver level (the host-side
    escalation seat, between chunks) must ride the SAME rollback + dt
    backoff as a NaN divergence, with the attempts list in the
    incident."""
    integ = _ins()
    st0 = _tg_state(integ)
    dt0 = 1e-3
    d = str(tmp_path)
    cfg = RunConfig(dt=dt0, num_steps=12, restart_interval=4,
                    health_interval=2)
    attempts = [{"level": "base", "iters": 8, "resnorm": 0.5,
                 "converged": False},
                {"level": "restarts_x4", "iters": 32, "resnorm": 0.4,
                 "converged": False},
                {"level": "deep_x4_inner_x2", "iters": 64, "resnorm": 0.3,
                 "converged": False}]
    drv = HierarchyDriver(integ, cfg)

    def metrics_fn(s, k):
        # dt-gated like a real breakdown: the backed-off dt converges
        if k == 6 and drv.cfg.dt >= dt0 * 0.99:
            raise SolverBreakdown("StaggeredStokesSolver", attempts,
                                  step=k)
        return None

    drv.metrics_fn = metrics_fn
    sup = ResilientDriver(drv, d, max_retries=2, dt_backoff=0.5,
                          handle_signals=False)
    out = sup.run(st0)
    assert int(out.k) == 12
    [rec] = [r for r in sup.incidents if r["event"] == "divergence"]
    assert rec["kind"] == "solver_breakdown"
    assert rec["context"] == "StaggeredStokesSolver"
    assert rec["attempts"] == attempts
    assert rec["step"] == 6
    assert rec["rollback_step"] == 4 and rec["from_checkpoint"]
    assert rec["dt_after"] == pytest.approx(dt0 * 0.5)
    with open(os.path.join(d, "incidents.jsonl")) as f:
        [line] = [json.loads(l) for l in f]
    assert line["kind"] == "solver_breakdown"
    assert line["attempts"] == attempts


def test_bicgstab_guard_returns_best_iterate():
    """The cg round-4 divergence guard, ported: a converging solve is
    untouched, and a WANDERING solve (this matrix drives the BiCGStab
    residual from |b| = 7.1 up to ~27 and it never comes back) must
    return the best iterate seen — so the returned residual norm can
    never exceed |b|, the x0 = 0 starting residual. The pre-guard code
    returned the final wandered iterate here, ~3.7x worse than doing
    nothing."""
    rng = np.random.RandomState(0)
    n = 24
    Mb = np.eye(n) + 0.1 * rng.randn(n, n)      # nonsymmetric, benign
    A = lambda x: jnp.asarray(Mb) @ x           # noqa: E731
    b = jnp.asarray(rng.randn(n))
    res = bicgstab(A, b, tol=1e-10, maxiter=200)
    assert bool(res.converged)
    assert float(jnp.linalg.norm(b - A(res.x))) \
        <= 1e-8 * float(jnp.linalg.norm(b))

    rng = np.random.RandomState(3)
    Mw = np.eye(40) * 2.0 + rng.randn(40, 40)   # the wander case
    Aw = lambda x: jnp.asarray(Mw) @ x          # noqa: E731
    bw = jnp.asarray(rng.randn(40))
    bnorm = float(jnp.linalg.norm(bw))
    res2 = bicgstab(Aw, bw, tol=1e-14, maxiter=400)
    assert not bool(res2.converged)
    assert bool(jnp.all(jnp.isfinite(res2.x)))
    assert float(res2.resnorm) <= bnorm * (1 + 1e-12)
    # and the claim holds for the TRUE residual of the returned iterate,
    # not just the recurred norm
    assert float(jnp.linalg.norm(bw - Aw(res2.x))) <= bnorm * (1 + 1e-10)
    # the guard's resnorm is a running min: non-increasing in maxiter
    # (the final-iterate residual oscillates; the best-seen cannot)
    cuts = [float(bicgstab(Aw, bw, tol=1e-14, maxiter=mi).resnorm)
            for mi in (5, 25, 100, 400)]
    assert all(a >= c - 1e-12 for a, c in zip(cuts, cuts[1:]))


# ---------------------------------------------------------------------------
# PR 3: run watchdog — heartbeat semantics + stall detection
# ---------------------------------------------------------------------------

def test_watchdog_rejects_bad_config():
    for kw in ({"interval_s": 0.0}, {"stall_factor": 1.0},
               {"min_stall_s": -1.0}, {"ema_alpha": 0.0},
               {"ema_alpha": 1.5}):
        with pytest.raises(ValueError):
            RunWatchdog(**kw)


def test_watchdog_heartbeat_and_stall_detection(tmp_path):
    """Deterministic (clock-injected) detector contract: heartbeat age
    tracks the last BEAT (not the last file write — the daemon keeps
    rewriting during a hang), the stall fires once per silence at
    max(min_stall_s, factor x EMA), and a new beat re-arms it."""
    import time as _time
    recs = []
    wd = RunWatchdog(heartbeat_path=str(tmp_path), interval_s=0.5,
                     stall_factor=3.0, min_stall_s=1.0,
                     on_incident=recs.append)
    # a directory path (existing or not) gets the canonical file name
    assert wd.heartbeat_path == os.path.join(str(tmp_path),
                                             "heartbeat.json")
    # before the first beat the detector stays silent forever
    assert wd.check(now=_time.monotonic() + 1e6) is None

    wd.beat(step=10, last_chunk_wall_s=0.2)
    wd.beat(step=20, last_chunk_wall_s=0.2)
    hb = read_heartbeat(wd.heartbeat_path)
    assert hb["step"] == 20 and hb["pid"] == os.getpid()
    assert hb["last_chunk_wall_s"] == pytest.approx(0.2)
    assert hb["steps_per_s"] is not None and hb["steps_per_s"] > 0

    # heartbeat_age follows the beat: a later rewrite with a fresher
    # ``written`` stamp must NOT make the file look younger
    age0 = heartbeat_age(wd.heartbeat_path)
    assert age0 is not None and age0 < 5.0
    write_heartbeat(wd.heartbeat_path,
                    dict(hb, written=hb["written"] + 100.0))
    assert heartbeat_age(wd.heartbeat_path) == pytest.approx(age0,
                                                             abs=5.0)
    assert heartbeat_age(os.path.join(str(tmp_path), "nope.json")) is None

    # threshold floors at min_stall_s (EMA of 0.2 s chunks x 3 < 1 s)
    assert wd.stall_threshold_s() == pytest.approx(1.0)
    t0 = wd._last_beat
    assert wd.check(now=t0 + 0.5) is None       # within threshold
    rec = wd.check(now=t0 + 2.0)                # past it: fires ONCE
    assert rec is not None
    assert rec["event"] == "stall" and rec["kind"] == "stall"
    assert rec["step"] == 20
    assert rec["beat_age_s"] == pytest.approx(2.0)
    assert rec["threshold_s"] == pytest.approx(1.0)
    assert recs == [rec] and wd.stalls == [rec]
    assert wd.check(now=t0 + 3.0) is None       # once per silence
    wd.beat(step=30)                            # the run moved: re-arm
    assert wd.check(now=wd._last_beat + 2.0) is not None
    assert len(wd.stalls) == 2


def test_watchdog_flags_stalled_supervised_run(tmp_path):
    """End-to-end (slow tier): a supervised run whose host callback
    hangs 1.2 s — indistinguishable from a hung compile from outside —
    gets a ``stall`` incident in the SAME incidents.jsonl, and the
    heartbeat file ends on the final real beat."""
    integ = _ins()
    st0 = _tg_state(integ)
    d = str(tmp_path)
    cfg = RunConfig(dt=1e-3, num_steps=8, health_interval=2)
    drv = HierarchyDriver(integ, cfg)
    drv.run(st0, start_step=6)          # warm the 2-step chunk compile
    stalls = []
    wd = RunWatchdog(heartbeat_path=d, interval_s=0.05, stall_factor=3.0,
                     min_stall_s=0.4, on_stall=stalls.append)
    drv.metrics_fn = slow_metrics(1.2, at_steps={4})
    sup = ResilientDriver(drv, d, handle_signals=False, watchdog=wd)
    out = sup.run(st0)
    assert int(out.k) == 8
    recs = [r for r in sup.incidents if r["kind"] == "stall"]
    assert recs, "stall never detected"
    assert recs[0]["step"] == 4         # the beat that preceded the hang
    assert recs[0]["beat_age_s"] > recs[0]["threshold_s"]
    assert stalls and stalls[0]["step"] == 4    # policy hook fired too
    hb = read_heartbeat(os.path.join(d, "heartbeat.json"))
    assert hb is not None and hb["step"] == 8
    with open(os.path.join(d, "incidents.jsonl")) as f:
        kinds = [json.loads(l)["kind"] for l in f]
    assert "stall" in kinds
