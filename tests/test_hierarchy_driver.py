"""HierarchyDriver run-loop skeleton + divergence guard (T13, §5.2 —
VERDICT round 1 item 8).
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ibamr_tpu.grid import StaggeredGrid
from ibamr_tpu.integrators.ins import INSStaggeredIntegrator
from ibamr_tpu.utils.hierarchy_driver import (HierarchyDriver, RunConfig,
                                              SimulationDiverged)


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


def test_run_matches_manual_stepping():
    integ = _ins()
    st0 = _tg_state(integ)
    cfg = RunConfig(dt=1e-3, num_steps=23, health_interval=7)
    drv = HierarchyDriver(integ, cfg)
    out = drv.run(st0)
    ref = st0
    for _ in range(23):
        ref = integ.step(ref, 1e-3)
    np.testing.assert_allclose(np.asarray(out.u[0]),
                               np.asarray(ref.u[0]), atol=1e-13)
    assert int(out.k) == 23


def test_callback_cadences_land_exactly():
    integ = _ins()
    st = _tg_state(integ)
    seen = {"viz": [], "ckpt": [], "metrics": []}
    cfg = RunConfig(dt=1e-3, num_steps=30, viz_dump_interval=6,
                    restart_interval=10, health_interval=7)
    drv = HierarchyDriver(
        integ, cfg,
        viz_fn=lambda s, k: seen["viz"].append(k),
        checkpoint_fn=lambda s, k: seen["ckpt"].append(k),
        metrics_fn=lambda s, k: seen["metrics"].append(k) or {})
    drv.run(st)
    assert seen["viz"] == [6, 12, 18, 24, 30]
    assert seen["ckpt"] == [10, 20, 30]
    assert seen["metrics"][-1] == 30


def test_divergence_halts_with_diagnostic():
    """A deliberately unstable config (convective CFL >> 1) must raise
    SimulationDiverged naming the bad leaves, and no checkpoint of the
    broken state may be written."""
    integ = _ins(n=32, mu=1e-4)
    st = _tg_state(integ)
    ckpts = []
    cfg = RunConfig(dt=0.5, num_steps=200, restart_interval=100,
                    health_interval=10)
    drv = HierarchyDriver(integ, cfg,
                          checkpoint_fn=lambda s, k: ckpts.append(k))
    with pytest.raises(SimulationDiverged) as ei:
        drv.run(st)
    assert ei.value.bad_leaves            # names the offending leaves
    assert any(".u" in n or "u[" in n or "u" in n
               for n in ei.value.bad_leaves)
    assert ckpts == []                    # nothing poisoned the chain


def test_cfl_dt_recompute_no_retrace():
    """dt is traced: changing it between chunks must not retrigger
    compilation (counted via the driver's trace counter)."""
    integ = _ins()
    st = _tg_state(integ)
    cfg = RunConfig(dt=2e-3, num_steps=40, health_interval=10, cfl=0.3)
    drv = HierarchyDriver(integ, cfg)
    out = drv.run(st)
    assert bool(jnp.all(jnp.isfinite(out.u[0])))
    assert len(drv._chunks) == 1                  # one chunk length
    # dt traced: no retrace. Counted by the driver's trace counter, not
    # jit._cache_size() — the process-global pjit LRU can evict a live
    # entry in a long test session (observed in the round-5 full gate:
    # _cache_size() == 0 after ~280 in-process tests) and the count
    # must survive that.
    assert drv.trace_counts[10] == 1


# -- the carried chunk: an integrator's context rides the scan ---------------

def _shell():
    from ibamr_tpu.models.shell3d import build_shell_example

    return build_shell_example(n_cells=16, n_lat=24, n_lon=24, radius=0.25,
                               use_fast_interaction="packed")


def _packs_before_the_scan(chunk, *args):
    """Marker-layout packs (``sort`` primitives) a chunk runs outside
    its scan: one where the layout is carried, none where every step
    packs its own."""
    def sorts(jaxpr):
        n = 0
        for e in jaxpr.eqns:
            n += e.primitive.name == "sort"
            if e.primitive.name != "scan":
                for v in e.params.values():
                    inner = getattr(v, "jaxpr", v)
                    if hasattr(inner, "eqns"):
                        n += sorts(inner)
        return n

    return sorts(jax.make_jaxpr(chunk)(*args).jaxpr)


def _equal(a, b):
    return all(np.array_equal(np.asarray(x), np.asarray(y)) for x, y in
               zip(jax.tree_util.tree_leaves(a),
                   jax.tree_util.tree_leaves(b)))


def test_only_the_plain_solo_chunk_threads_the_carry():
    # (f) step_fn=None carries the integrator's context; lanes, remat
    # and a caller's own step keep scanning ``step``
    integ, state = _shell()

    def driver(remat=None, **kw):
        cfg = RunConfig(dt=1e-4, num_steps=4, health_interval=2,
                        remat=remat)
        return HierarchyDriver(integ, cfg, **kw)

    drv = driver()
    assert drv._carried
    assert _packs_before_the_scan(drv._chunk(2), state, 1e-4) == 1
    for other in (driver(step_fn=integ.step), driver(remat="full")):
        assert not other._carried
        assert _packs_before_the_scan(other._chunk(2), state, 1e-4) == 0
    fleet = driver(lanes=2)
    assert not fleet._carried
    stacked = jax.tree_util.tree_map(lambda l: jnp.stack([l, l]), state)
    assert _packs_before_the_scan(fleet._chunk(2), stacked,
                                  jnp.full((2,), 1e-4),
                                  jnp.ones((2,), bool)) == 0

    # the callable the benchmark's spies and the serving cache hold on
    # to: a jit object with .lower, (state, dt) -> (state, health), and
    # health[0] the finite flag
    chunk = drv._chunk(2)
    assert chunk.lower(state, 1e-4).compile() is not None
    out, health = chunk(state, 1e-4)
    assert isinstance(out, type(state))
    assert np.asarray(health).tolist() == [1.0, 4.0, 0.0]


def test_chunked_run_equals_checkpoint_restart_bit_for_bit(tmp_path):
    # (g) a chunk packs from state.X at its start, so it depends on
    # nothing but the state: [n, n] == n, save, restore, n
    from ibamr_tpu.utils.checkpoint import (restore_checkpoint,
                                            save_checkpoint)

    integ, state = _shell()
    n = 3
    through = HierarchyDriver(
        integ, RunConfig(dt=1e-4, num_steps=2 * n,
                         health_interval=n)).run(state)
    half = HierarchyDriver(
        integ, RunConfig(dt=1e-4, num_steps=n,
                         health_interval=n)).run(state)
    save_checkpoint(str(tmp_path), half, n)
    restored, step, _ = restore_checkpoint(str(tmp_path), state, step=n)
    assert step == n and _equal(restored, half)
    resumed = HierarchyDriver(
        integ, RunConfig(dt=1e-4, num_steps=2 * n,
                         health_interval=n)).run(restored, start_step=n)
    assert _equal(resumed, through)


def test_falls_reach_the_counter_and_the_refresh_span():
    # (h) markers swept 0.3 cells a step break the layout's half-cell
    # bound inside a chunk: the falls leave with the chunk's one sync
    from ibamr_tpu import obs

    integ, state = _shell()
    u = (jnp.full_like(state.ins.u[0], 1.0),) + tuple(state.ins.u[1:])
    state = state._replace(ins=state.ins._replace(u=u))
    cfg = RunConfig(dt=0.3 / 16, num_steps=6, health_interval=3)
    falls0 = obs.counter("transfer_repack_falls_total").value
    refreshes0 = obs.counter("transfer_refreshes_total").value
    obs.clear_spans()
    out = HierarchyDriver(integ, cfg).run(state)
    spans = [s for s in obs.spans() if s["path"] == "driver/chunk/refresh"]
    assert [(s["attrs"]["step"], s["attrs"]["chunk"],
             s["attrs"]["refreshes"]) for s in spans] == [(0, 0, 6),
                                                          (3, 1, 6)]
    falls = sum(s["attrs"]["falls"] for s in spans)
    assert falls >= 2                   # each chunk outruns its pack
    assert obs.counter("transfer_repack_falls_total").value \
        == falls0 + falls
    assert obs.counter("transfer_refreshes_total").value \
        == refreshes0 + 12
    # and the carried run is the per-step run to roundoff
    ref = HierarchyDriver(integ, cfg, step_fn=integ.step).run(state)
    gap = max(float(jnp.max(jnp.abs(a - b))) for a, b in
              zip(jax.tree_util.tree_leaves(out),
                  jax.tree_util.tree_leaves(ref)))
    assert gap < 1e4 * float(jnp.finfo(ref.X.dtype).eps)


# -- the order of the loop at a chunk boundary (PR 31) -----------------------

class _Health:
    """Stands in for a chunk's health value: logs the driver's one sync."""

    def __init__(self, health, log, i):
        self.health, self.log, self.i = health, log, i

    def __array__(self, dtype=None, copy=None):
        self.log.append(("sync", self.i))
        return np.asarray(self.health)


class _LoggedDriver(HierarchyDriver):
    """Logs dispatches and syncs by chunk ordinal; ``fail`` makes the
    request for (``"ask"``) or the call of (``"dispatch"``) chunk
    ``fail_at`` raise."""

    def __init__(self, *a, log, fail=None, fail_at=1, **kw):
        super().__init__(*a, **kw)
        self.log, self.fail, self.fail_at, self.asked = log, fail, fail_at, 0

    def _chunk(self, n):
        i, fn = self.asked, super()._chunk(n)
        self.asked += 1
        if self.fail == "ask" and i == self.fail_at:
            raise RuntimeError("no chunk program")

        def call(state, *args):
            self.log.append(("dispatch", i))
            if self.fail == "dispatch" and i == self.fail_at:
                raise RuntimeError("dispatch failed")
            out, health = fn(state, *args)
            return out, _Health(health, self.log, i)
        return call


def _logged(log, integ, cfg, **kw):
    def cb(name):
        return lambda s, k: log.append((name, k))

    return _LoggedDriver(integ, cfg, log=log, metrics_fn=cb("metrics"),
                         viz_fn=cb("viz"), checkpoint_fn=cb("ckpt"), **kw)


_D0 = [("dispatch", 0), ("sync", 0), ("metrics", 2)]
_END = [("sync", 1), ("metrics", 4), ("viz", 4), ("ckpt", 4)]


@pytest.mark.parametrize("case, cfg_kw, regrid, expected", [
    # viz and checkpoint of step 2 wait for chunk 1's dispatch and are
    # done before its sync; step 4 is the last chunk's
    ("deferring", dict(num_steps=4, viz_dump_interval=2, restart_interval=2),
     False, _D0 + [("dispatch", 1), ("viz", 2), ("ckpt", 2)] + _END),
    ("donate", dict(num_steps=4, viz_dump_interval=2, restart_interval=2,
                    donate=True),
     False, _D0 + [("viz", 2), ("ckpt", 2), ("dispatch", 1)] + _END),
    ("regrid_due", dict(num_steps=4, viz_dump_interval=2, restart_interval=2,
                        regrid_interval=2),
     True, _D0 + [("viz", 2), ("ckpt", 2), ("regrid", 2), ("dispatch", 1)]
     + _END + [("regrid", 4)]),
    ("last_chunk", dict(num_steps=2, viz_dump_interval=2, restart_interval=2),
     False, _D0 + [("viz", 2), ("ckpt", 2)]),
    ("nothing_due", dict(num_steps=4, viz_dump_interval=4,
                         restart_interval=4),
     False, _D0 + [("dispatch", 1)] + _END),
])
def test_order_at_a_chunk_boundary(case, cfg_kw, regrid, expected):
    integ, log = _ins(), []

    def regrid_fn(s, k):
        log.append(("regrid", k))
        return s

    _logged(log, integ, RunConfig(dt=1e-3, health_interval=2, **cfg_kw),
            regrid_fn=regrid_fn if regrid else None).run(_tg_state(integ))
    assert log == expected


def _run_with_files(tmp, donate, step_fn=None, driver=None):
    """A 12-step run that dumps a CSV every 2 steps and checkpoints
    every 4."""
    from ibamr_tpu.utils.checkpoint import save_checkpoint

    integ = _ins()
    (tmp / "viz").mkdir()

    def viz_fn(s, k):
        np.savetxt(tmp / "viz" / f"u.{k:04d}.csv", np.asarray(s.u[0]),
                   delimiter=",")

    drv = (driver or HierarchyDriver)(
        integ, RunConfig(dt=1e-3, num_steps=12, health_interval=2,
                         viz_dump_interval=2, restart_interval=4,
                         donate=donate),
        viz_fn=viz_fn, step_fn=step_fn,
        metrics_fn=lambda s, k: {"step": k, "ke": float(
            integ.kinetic_energy(s))},
        checkpoint_fn=lambda s, k: save_checkpoint(str(tmp / "rst"), s, k))
    return drv, _tg_state(integ)


def _files(tmp):
    csv = {p.name: p.read_bytes() for p in sorted((tmp / "viz").iterdir())}
    crc = {p.name: json.loads(p.read_text())["integrity"]["npz_crc32"]
           for p in sorted((tmp / "rst").glob("*.json"))}
    return csv, crc


def test_deferred_run_equals_the_inline_run_bit_for_bit(tmp_path):
    # donation is the observable that keeps every boundary inline
    got = {}
    for name, donate in (("deferred", False), ("inline", True)):
        (tmp_path / name).mkdir()
        drv, state = _run_with_files(tmp_path / name, donate)
        out = drv.run(state)
        got[name] = (jax.tree_util.tree_map(np.asarray, out), drv.history,
                     *_files(tmp_path / name))
    (s_d, h_d, csv_d, crc_d), (s_i, h_i, csv_i, crc_i) = (got["deferred"],
                                                          got["inline"])
    assert _equal(s_d, s_i) and h_d == h_i
    assert sorted(csv_d) == [f"u.{k:04d}.csv" for k in range(2, 13, 2)]
    assert csv_d == csv_i
    assert sorted(crc_d) == [f"restore.{k:08d}.json" for k in (4, 8, 12)]
    assert crc_d == crc_i


def test_divergence_at_the_next_chunk_keeps_the_last_checkpoint(tmp_path):
    # steps 5 and 6 (chunk 2) blow up: the checkpoint of step 4, written
    # beside that chunk, is whole; none of step 6 or later exists
    from ibamr_tpu.utils.checkpoint import latest_step, verify_checkpoint

    integ = _ins()

    def step_fn(s, dt):
        return integ.step(s, jnp.where(s.k >= 4, jnp.nan, dt))

    drv, state = _run_with_files(tmp_path, False, step_fn=step_fn)
    with pytest.raises(SimulationDiverged) as ei:
        drv.run(state)
    assert ei.value.step == 6
    rst = str(tmp_path / "rst")
    assert latest_step(rst) == 4 and verify_checkpoint(rst, 4)
    assert sorted(p.name for p in (tmp_path / "rst").iterdir()) == [
        "restore.00000004.json", "restore.00000004.npz"]
    assert sorted(_files(tmp_path)[0]) == ["u.0002.csv", "u.0004.csv"]


@pytest.mark.parametrize("fail", ["ask", "dispatch"])
def test_a_failed_dispatch_still_writes_the_due_files(tmp_path, fail):
    # chunk 2 (from step 4) cannot start: step 4's dump and checkpoint,
    # deferred to after that dispatch, are on disk when the error arrives
    from ibamr_tpu.utils.checkpoint import verify_checkpoint

    log = []
    drv, state = _run_with_files(
        tmp_path, False, driver=lambda *a, **kw: _LoggedDriver(
            *a, log=log, fail=fail, fail_at=2, **kw))
    with pytest.raises(RuntimeError, match="chunk program|dispatch failed"):
        drv.run(state)
    assert sorted(_files(tmp_path)[0]) == ["u.0002.csv", "u.0004.csv"]
    assert verify_checkpoint(str(tmp_path / "rst"), 4)
    assert [h["step"] for h in drv.history] == [2, 4]


def test_deferred_and_inline_counters_and_span_attribute():
    from ibamr_tpu import obs

    def counts():
        snap = obs.metrics_snapshot()["counters"]
        return {(where, cb): snap.get(
            f'driver_callbacks_{where}_total{{callback="{cb}"}}', 0)
            for where in ("deferred", "inline")
            for cb in ("viz_fn", "checkpoint_fn")}

    integ, before = _ins(), counts()
    obs.clear_spans()
    # three boundaries: 2 (dump), 4 (dump + checkpoint), 6 (dump, last)
    HierarchyDriver(
        integ, RunConfig(dt=1e-3, num_steps=6, health_interval=2,
                         viz_dump_interval=2, restart_interval=4),
        viz_fn=lambda s, k: None, checkpoint_fn=lambda s, k: None,
    ).run(_tg_state(integ))
    after = counts()
    assert {k: after[k] - before[k] for k in after} == {
        ("deferred", "viz_fn"): 2, ("deferred", "checkpoint_fn"): 1,
        ("inline", "viz_fn"): 1, ("inline", "checkpoint_fn"): 0}
    ring = obs.spans()

    def named(path):
        return [s for s in ring if s["path"] == path]

    viz, ckpt = named("driver/viz_fn"), named("driver/checkpoint_fn")
    assert [(s["attrs"]["step"], s["attrs"]["chunk"], s["attrs"]["deferred"])
            for s in viz] == [(2, 0, True), (4, 1, True), (6, 2, False)]
    assert [(s["attrs"]["step"], s["attrs"]["deferred"])
            for s in ckpt] == [(4, True)]
    # a deferred callback of chunk k runs between chunk k+1's dispatch and
    # its sync, as a root span
    dispatch, sync = named("driver/chunk/dispatch"), named("driver/chunk/sync")
    for s in viz[:2] + ckpt:
        nxt = s["attrs"]["chunk"] + 1
        assert s["parent"] is None
        assert dispatch[nxt]["t1"] <= s["t0"] <= s["t1"] <= sync[nxt]["t0"]
    assert sync[2]["t1"] <= viz[2]["t0"]
    obs.clear_spans()
