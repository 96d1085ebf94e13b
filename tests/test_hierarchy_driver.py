"""HierarchyDriver run-loop skeleton + divergence guard (T13, §5.2 —
VERDICT round 1 item 8).
"""

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
