"""Slot-preserving half-step bucket refresh (ops.interaction_packed).

The midpoint IB step needs transfer contexts at X^n AND X^{n+1/2};
``refresh_packed`` re-gathers the drifted positions into the pack-time
chunk layout instead of paying a second full sort/bucket/pack. The
load-bearing claims pinned here:

- same-position refresh is a BITWISE identity;
- under drift within the footprint slack the refreshed context is
  exact against the scatter oracle (and bitwise-equal to a full
  re-pack when no bucket ids change — argsort is stable);
- the jittable drift bound checks BOTH staggered stencil origins per
  blocked axis (cell- and face-centered); the face-centered origin
  sits up to one cell above the cell-centered one used at pack time,
  so a bound on the cell origin alone silently corrupts component d
  along axis d (the regression test below);
- when the bound trips, the fallback is a full re-pack — bitwise
  identical to ``pack_markers`` at the new positions;
- the integrator pays ONE ``buckets`` build per step and reports the
  refresh outcome through ``step_with_stats``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ibamr_tpu.grid import StaggeredGrid
from ibamr_tpu.ops import interaction
from ibamr_tpu.ops.interaction_packed import PackedInteraction, pack_markers

F64 = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32


def _grid(n=32):
    return StaggeredGrid(n=(n, n), x_lo=(0.0, 0.0), x_up=(1.0, 1.0))


def _markers(n=32, N=200, seed=0):
    """Positions whose stencil origins sit away from floor boundaries,
    so sub-cell drift does not flip bucket ids (the bitwise tier needs
    a layout-stable placement; the drift tiers use it too and then
    drift far enough to flip origins on purpose)."""
    rng = np.random.default_rng(seed)
    i = rng.integers(0, n, size=(N, 2))
    u = rng.random((N, 2))
    return (i + 0.75 + 0.05 * u) / n, rng


def _bitwise_equal(a, b):
    return all(bool(jnp.array_equal(x, y)) for x, y in
               zip(jax.tree_util.tree_leaves(a),
                   jax.tree_util.tree_leaves(b)))


def _check_exact(eng, g, b, X, rng, tol=1e-10):
    N = X.shape[0]
    F = jnp.asarray(rng.standard_normal((N, 2)), dtype=F64)
    got = eng.spread_vel(F, X, b=b)
    ref = interaction.spread_vel(F, g, X, kernel="IB_4")
    for a, c in zip(ref, got):
        scale = max(float(jnp.max(jnp.abs(a))), 1.0)
        np.testing.assert_allclose(np.asarray(c), np.asarray(a),
                                   rtol=0, atol=tol * scale)
    U = eng.interpolate_vel(ref, X, b=b)
    Uref = interaction.interpolate_vel(ref, g, X, kernel="IB_4")
    scale = max(float(jnp.max(jnp.abs(Uref))), 1.0)
    np.testing.assert_allclose(np.asarray(U), np.asarray(Uref),
                               rtol=0, atol=tol * scale)


def test_refresh_same_position_is_bitwise_identity():
    g = _grid()
    base, _ = _markers()
    X = jnp.asarray(base, dtype=F64)
    eng = PackedInteraction(g, kernel="IB_4")
    b = eng.buckets(X)
    b2, hit = eng.refresh(b, X)
    assert bool(hit)
    assert _bitwise_equal(b, b2)


def test_refresh_small_drift_bitwise_equals_repack():
    # +0.2 dx keeps every bucket id: the stable argsort then produces
    # the SAME layout from a full re-pack, so refresh must match it
    # bit for bit
    g = _grid()
    base, rng = _markers()
    dx = 1.0 / 32
    X = jnp.asarray(base, dtype=F64)
    eng = PackedInteraction(g, kernel="IB_4")
    b = eng.buckets(X)
    Xd = X + 0.2 * dx
    b2, hit = eng.refresh(b, Xd)
    assert bool(hit)
    assert _bitwise_equal(b2, eng.buckets(Xd))
    _check_exact(eng, g, b2, Xd, rng)


def test_refresh_backward_drift_within_slack_exact():
    # -0.9 dx flips stencil origins downward for most markers; the
    # footprint's lower slack cell absorbs it, so the refresh must
    # HIT and stay exact against the scatter oracle
    g = _grid()
    base, rng = _markers(seed=1)
    dx = 1.0 / 32
    X = jnp.asarray(base, dtype=F64)
    eng = PackedInteraction(g, kernel="IB_4")
    b = eng.buckets(X)
    Xd = X - 0.9 * dx
    b2, hit = eng.refresh(b, Xd)
    assert bool(hit)
    _check_exact(eng, g, b2, Xd, rng)


def test_refresh_guards_face_centered_origin():
    # REGRESSION: markers placed just below a floor boundary, drifted
    # forward 0.9 dx. The cell-centered origin stays inside the
    # footprint but the FACE-centered origin (component d along blocked
    # axis d — one cell higher) escapes; a drift bound that only checks
    # the cell origin declares a hit and silently corrupts component 0
    # by O(1). The dual-origin bound must fall back — and the fallback
    # re-pack keeps the transfers exact.
    n = 32
    g = _grid(n)
    rng = np.random.default_rng(0)
    i = rng.integers(0, n, size=(200, 2))
    u = rng.random((200, 2))
    X = jnp.asarray((i + 0.45 + 0.1 * u) / n, dtype=F64)
    eng = PackedInteraction(g, kernel="IB_4")
    b = eng.buckets(X)
    Xd = X + 0.9 / n
    b2, hit = eng.refresh(b, Xd)
    assert not bool(hit)
    _check_exact(eng, g, b2, Xd, rng)


def test_refresh_far_drift_falls_back_to_full_repack():
    g = _grid()
    base, rng = _markers(seed=2)
    X = jnp.asarray(base, dtype=F64)
    eng = PackedInteraction(g, kernel="IB_4")
    b = eng.buckets(X)
    Xd = X + 3.2 / 32
    b2, hit = eng.refresh(b, Xd)
    assert not bool(hit)
    assert _bitwise_equal(b2, eng.buckets(Xd))
    _check_exact(eng, g, b2, Xd, rng)


def test_refresh_respects_marker_mask():
    g = _grid()
    base, rng = _markers(seed=3)
    dx = 1.0 / 32
    X = jnp.asarray(base, dtype=F64)
    mask = jnp.asarray(rng.random(200) > 0.3, dtype=F64)
    eng = PackedInteraction(g, kernel="IB_4")
    b = eng.buckets(X, mask)
    Xd = X + 0.2 * dx
    b2, hit = eng.refresh(b, Xd, weights=mask)
    assert bool(hit)
    F = jnp.asarray(rng.standard_normal((200, 2)), dtype=F64)
    got = eng.spread_vel(F, Xd, b=b2)
    ref = interaction.spread_vel(F, g, Xd, kernel="IB_4", weights=mask)
    for a, c in zip(ref, got):
        scale = max(float(jnp.max(jnp.abs(a))), 1.0)
        np.testing.assert_allclose(np.asarray(c), np.asarray(a),
                                   rtol=0, atol=1e-10 * scale)


def test_refresh_jits_and_matches_eager():
    g = _grid()
    base, _ = _markers(seed=4)
    X = jnp.asarray(base, dtype=F64)
    eng = PackedInteraction(g, kernel="IB_4")
    b = eng.buckets(X)
    Xd = X - 0.4 / 32
    b_e, hit_e = eng.refresh(b, Xd)
    b_j, hit_j = jax.jit(lambda bb, xx: eng.refresh(bb, xx))(b, Xd)
    assert bool(hit_e) == bool(hit_j) is True
    assert _bitwise_equal(b_e, b_j)


def test_integrator_pays_one_bucket_prep_per_step():
    from ibamr_tpu.models.shell3d import build_shell_example

    integ, state = build_shell_example(
        n_cells=16, n_lat=24, n_lon=24, radius=0.25,
        use_fast_interaction="packed")
    calls = {"n": 0}
    orig = integ.ib.fast.buckets

    def counting(*a, **k):
        calls["n"] += 1
        return orig(*a, **k)

    integ.ib.fast.buckets = counting
    lowered = jax.jit(integ.step_with_stats).lower(state, 1e-4)
    # the midpoint step needs contexts at X^n and X^{n+1/2}; with the
    # refresh path only ONE full pack is traced (the half-step context
    # is the re-gather + its cond fallback, which calls pack_markers
    # directly, not the engine's buckets entry point)
    assert calls["n"] == 1

    new_state, stats = lowered.compile()(state, 1e-4)
    assert stats["refresh_hit"] is not None
    assert bool(stats["refresh_hit"])
    assert bool(jnp.isfinite(new_state.X).all())

    # oracle: the scatter-path model advanced one step
    integ0, state0 = build_shell_example(
        n_cells=16, n_lat=24, n_lon=24, radius=0.25,
        use_fast_interaction=False)
    s0 = jax.jit(integ0.step)(state0, 1e-4)
    np.testing.assert_allclose(np.asarray(new_state.X),
                               np.asarray(s0.X), rtol=0, atol=5e-5)


def test_refresh_fallback_matches_pack_under_jit():
    # the lax.cond branches must agree in pytree structure AND the
    # taken fallback must equal an out-of-band pack bit for bit
    g = _grid()
    base, _ = _markers(seed=5)
    X = jnp.asarray(base, dtype=F64)
    eng = PackedInteraction(g, kernel="IB_4")
    b = eng.buckets(X)
    Xd = X + 2.5 / 32
    b_j, hit_j = jax.jit(lambda bb, xx: eng.refresh(bb, xx))(b, Xd)
    assert not bool(hit_j)
    assert _bitwise_equal(b_j, pack_markers(eng.geom, g, Xd, None,
                                            nchunks=eng.nchunks,
                                            overflow_cap=eng.overflow_cap))


# -- the carried form: the layout outlives the step --------------------------

def _shell(engine="packed"):
    from ibamr_tpu.models.shell3d import build_shell_example

    return build_shell_example(n_cells=16, n_lat=24, n_lon=24, radius=0.25,
                               use_fast_interaction=engine)


def _carried_steps(integ, state, dt, k, ctx="init"):
    """k carried steps, one jitted call each; the stats of every step."""
    if ctx == "init":
        ctx = jax.jit(integ.init_carry)(state)
    step = jax.jit(integ.step_carried)
    stats = []
    for _ in range(k):
        state, ctx, st = step(state, ctx, dt)
        stats.append({k_: int(v) for k_, v in st.items()})
    return state, ctx, stats


def _plain_steps(integ, state, dt, k):
    step = jax.jit(integ.step)
    for _ in range(k):
        state = step(state, dt)
    return state


def _moving(state, speed):
    """The state with a uniform fluid velocity along x: markers drift
    ``speed * dt`` a step."""
    u = (jnp.full_like(state.ins.u[0], speed),) + tuple(state.ins.u[1:])
    return state._replace(ins=state.ins._replace(u=u))


def _max_gap(a, b):
    return max(float(jnp.max(jnp.abs(x - y))) for x, y in
               zip(jax.tree_util.tree_leaves(a),
                   jax.tree_util.tree_leaves(b)))


def test_carried_steps_bitwise_equal_steps_while_no_tile_changes():
    # (a) at rest the shell moves ~1e-6 cells a step: every refresh is
    # bitwise the re-pack, so k carried steps ARE k steps
    integ, state = _shell()
    got, ctx, stats = _carried_steps(integ, state, 1e-4, 4)
    assert stats == [{"refreshes": 2, "falls": 0}] * 4
    assert _bitwise_equal(got, _plain_steps(integ, state, 1e-4, 4))
    # the layout handed on is the one a pack at the last X_half gives
    assert bool(jnp.array_equal(
        ctx.slot_of_marker,
        integ.ib.prepare(got.X, got.mask).slot_of_marker))


def test_carried_steps_equal_steps_to_roundoff_across_tile_changes():
    # (a) 0.3 cells a step for 5 steps: markers cross tile boundaries,
    # the carried layout keeps them in their old chunks (or re-packs
    # once a bound falls), and only the summation order differs
    integ, state = _shell()
    state = _moving(state, 1.0)
    dt = 0.3 / 16
    got, _, stats = _carried_steps(integ, state, dt, 5)
    ref = _plain_steps(integ, state, dt, 5)
    assert sum(s["falls"] for s in stats) >= 1
    assert float(jnp.max(jnp.abs(ref.X - state.X))) > 1.0 / 16
    eps = float(jnp.finfo(ref.X.dtype).eps)
    assert _max_gap(got, ref) < 1e4 * eps


def test_carried_fall_is_counted_once_and_the_new_layout_is_kept():
    # (b) a layout packed 3.2 cells away from where the markers are:
    # the refresh at X_n falls, the re-packed layout is what the step
    # hands on, and the next step hits on it
    integ, state = _shell()
    stale = jax.jit(integ.init_carry)(state)
    moved = state._replace(X=state.X + 3.2 / 16)
    got, ctx, stats = _carried_steps(integ, moved, 1e-4, 2, ctx=stale)
    assert stats == [{"refreshes": 2, "falls": 1},
                     {"refreshes": 2, "falls": 0}]
    assert not bool(jnp.array_equal(ctx.slot_of_marker,
                                    stale.slot_of_marker))
    # a fall re-packs at X_n, as ``step`` does: bitwise the same step
    assert _bitwise_equal(got, _plain_steps(integ, moved, 1e-4, 2))


def _sorts(jaxpr, in_scan=False, in_cond=False, out=None):
    """Where the ``sort`` primitives of a jaxpr sit: counts by
    (inside a scan body, inside a cond branch)."""
    out = {} if out is None else out
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "sort":
            out[(in_scan, in_cond)] = out.get((in_scan, in_cond), 0) + 1
        for v in eqn.params.values():
            subs = v if isinstance(v, (list, tuple)) else [v]
            for sub in subs:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _sorts(inner, in_scan or name == "scan",
                           in_cond or name == "cond", out)
    return out


def test_chunk_pays_one_bucket_prep_per_chunk():
    # (c) the structural pin beside the one-prep-per-step pin: the
    # driver's chunk sorts once before its scan, and inside the scan
    # body only under a cond (the refreshes' fallback)
    from ibamr_tpu.utils.hierarchy_driver import HierarchyDriver, RunConfig

    integ, state = _shell()
    cfg = RunConfig(dt=1e-4, num_steps=4, health_interval=4)
    for kw, expect in (({}, {(False, False): 1, (True, True): 2}),
                       ({"step_fn": integ.step},
                        {(True, False): 1, (True, True): 1})):
        chunk = HierarchyDriver(integ, cfg, **kw)._chunk(4)
        assert _sorts(jax.make_jaxpr(chunk)(state, 1e-4).jaxpr) == expect


@pytest.mark.parametrize("engine", [False, True], ids=["scatter", "mxu"])
def test_engine_without_refresh_carries_nothing(engine):
    # (d) no layout to keep: ctx is None and the carried step IS step
    integ, state = _shell(engine)
    assert jax.jit(integ.init_carry)(state) is None
    got, ctx, stats = _carried_steps(integ, state, 1e-4, 2, ctx=None)
    assert ctx is None
    assert stats == [{"refreshes": 0, "falls": 0}] * 2
    assert _bitwise_equal(got, _plain_steps(integ, state, 1e-4, 2))


def test_carried_masked_markers_stay_exempt_from_the_drift_bound():
    # (e) inactive markers (weight 0) parked far from where the layout
    # was packed must not trip the bound, on any step
    integ, state = _shell()
    rng = np.random.default_rng(7)
    mask = jnp.asarray(rng.random(state.X.shape[0]) > 0.25,
                       dtype=state.mask.dtype)
    state = state._replace(mask=mask)
    ctx = jax.jit(integ.init_carry)(state)
    parked = state._replace(
        X=jnp.where(mask[:, None] > 0, state.X, state.X + 5.0 / 16))
    got, _, stats = _carried_steps(integ, parked, 1e-4, 3, ctx=ctx)
    assert stats == [{"refreshes": 2, "falls": 0}] * 3
    # ``step`` packs the parked markers into other chunks: the same
    # sums in another order
    ref = _plain_steps(integ, parked, 1e-4, 3)
    assert _max_gap(got, ref) < 1e3 * float(jnp.finfo(ref.X.dtype).eps)
