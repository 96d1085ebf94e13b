"""The packed engine moves marker values between marker order and slot
order as ROWS: ``interpolate_vel`` brings the per-slot interpolants of
all components to marker order by one gather over ``slot_of_marker``,
``spread_vel`` takes the (N, dim) input to slot order by one gather
through ``marker_of_slot``. Pinned here: the two methods equal the
per-component transfers to every bit (with and without overflow, masked,
2D and 3D, on both operand dtypes; the components' spread is the
scatter-add over ``slot_of_marker``), the compiled steps hold that many
gathers and scatters and no more, and a traced chunk says so (three
trace-time counters, one span attribute)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ibamr_tpu import obs
from ibamr_tpu.analysis.graph_census import indexed_op_counts
from ibamr_tpu.grid import StaggeredGrid
from ibamr_tpu.models.shell3d import build_shell_example
from ibamr_tpu.ops import interaction_packed
from ibamr_tpu.ops.interaction_packed import (PackedInteraction,
                                              interpolate_packed,
                                              spread_packed)
from ibamr_tpu.utils.hierarchy_driver import HierarchyDriver, RunConfig

N_MARKERS = 300
GATHERS = "transfer_marker_gathers_total"
SCATTERS = "transfer_marker_scatters_total"
SLOT_GATHERS = "transfer_slot_gathers_total"

# engine name -> the operand dtype that is all that tells the two rows of
# the resolver's table apart
ENGINES = {"packed": None, "packed_bf16": jnp.bfloat16}

# case -> (nchunks, overflow_cap, masked): every marker packed; more
# markers than slots, the overflow inside its buffer (``any_overflow``);
# more overflow than the buffer holds (``exceeded``); a weights mask
CASES = {"packed_whole": (400, None, False),
         "buffered_overflow": (20, 512, False),
         "exceeded": (20, 16, False),
         "masked": (400, None, True),
         "masked_overflow": (20, 512, True)}


def _setting(engine, dim, case):
    nchunks, overflow_cap, masked = CASES[case]
    grid = StaggeredGrid(n=(16,) * dim, x_lo=(0.0,) * dim, x_up=(1.0,) * dim)
    eng = PackedInteraction(grid, tile=8, chunk=8, nchunks=nchunks,
                            overflow_cap=overflow_cap,
                            compute_dtype=ENGINES[engine])
    rng = np.random.RandomState(7)
    X = jnp.asarray(rng.rand(N_MARKERS, dim), jnp.float32)
    mask = (jnp.asarray(rng.rand(N_MARKERS) > 0.3, jnp.float32)
            if masked else None)
    b = eng.buckets(X, mask)
    assert bool(b.any_overflow) == (nchunks < 400)
    assert bool(b.exceeded) == (case == "exceeded")
    return grid, eng, rng, X, b


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("dim", [2, 3])
@pytest.mark.parametrize("engine", sorted(ENGINES))
def test_interpolate_vel_is_the_stacked_components(engine, dim, case):
    grid, eng, rng, X, b = _setting(engine, dim, case)
    u = tuple(jnp.asarray(rng.randn(*grid.n), jnp.float32)
              for _ in range(dim))
    rows = jax.jit(lambda u, X: eng.interpolate_vel(u, X, b=b))(u, X)
    cols = jax.jit(lambda u, X: jnp.stack(
        [interpolate_packed(eng.geom, grid, b, u[d], X, d, eng.kernel,
                            compute_dtype=eng.compute_dtype)
         for d in range(dim)], axis=-1))(u, X)
    assert rows.shape == (N_MARKERS, dim)
    assert float(jnp.max(jnp.abs(cols))) > 0
    np.testing.assert_array_equal(np.asarray(rows), np.asarray(cols))


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("dim", [2, 3])
@pytest.mark.parametrize("engine", sorted(ENGINES))
def test_spread_vel_is_the_tuple_of_components(engine, dim, case):
    grid, eng, rng, X, b = _setting(engine, dim, case)
    F = jnp.asarray(rng.randn(N_MARKERS, dim), jnp.float32)
    rows = jax.jit(lambda F, X: eng.spread_vel(F, X, b=b))(F, X)
    cols = jax.jit(lambda F, X: tuple(
        spread_packed(eng.geom, grid, b, F[:, d], X, d, eng.kernel,
                      compute_dtype=eng.compute_dtype)
        for d in range(dim)))(F, X)
    assert len(rows) == dim
    for a, c in zip(rows, cols):
        assert float(jnp.max(jnp.abs(c))) > 0
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))


# -- the compiled steps' marker-order gathers and scatters ---------------------

def test_carried_shell_step_marshals_once_per_transfer():
    """Two interpolations and one spread a step: two gathers with an index
    per marker (six when every component crossed alone) and one gather
    with an index per slot, no scatter with either; the ConstraintIB
    step's count is in test_falling_sphere.py."""
    integ, state = build_shell_example(
        n_cells=16, n_lat=24, n_lon=23, radius=0.25,
        use_fast_interaction="packed")
    n = state.X.shape[0]
    carry = jax.jit(integ.init_carry)(state)
    slots = carry.marker_of_slot.shape[0]
    assert slots != n
    text = jax.jit(lambda s, c: integ.step_carried(s, c, 5e-5)).lower(
        state, carry).compile().as_text()
    assert indexed_op_counts(text, n, "ib/interp") == \
        {"gather": 2, "scatter": 0}
    assert indexed_op_counts(text, n, "ib/spread") == \
        {"gather": 0, "scatter": 0}
    assert indexed_op_counts(text, slots, "ib/spread") == \
        {"gather": 1, "scatter": 0}


# -- what a trace says of it -----------------------------------------------------

def _counted(fn, *args):
    """The three counters' rise over one trace of ``fn``."""
    before = dict(obs.metrics_snapshot()["counters"])
    jax.make_jaxpr(fn)(*args)
    after = obs.metrics_snapshot()["counters"]
    return tuple(after.get(k, 0) - before.get(k, 0)
                 for k in (GATHERS, SCATTERS, SLOT_GATHERS))


@pytest.mark.parametrize("engine,expected", [
    ("packed", (2, 0, 1)), ("packed_bf16", (2, 0, 1)),
    # the engines that keep the scalar form: a gather and a scatter-add
    # per component and transfer (the hybrid interpolates as rows)
    ("mxu", (6, 3, 0)), ("pallas_packed", (6, 3, 0)),
    ("hybrid_bf16", (2, 3, 0))])
def test_counters_per_traced_shell_step(engine, expected):
    integ, state = build_shell_example(
        n_cells=16, n_lat=24, n_lon=23, radius=0.25,
        use_fast_interaction=engine)
    assert _counted(lambda s: integ.step(s, 5e-5), state) == expected


def test_chunk_span_says_how_the_values_crossed():
    integ, state = build_shell_example(
        n_cells=16, n_lat=24, n_lon=23, radius=0.25,
        use_fast_interaction="packed")
    before = dict(obs.metrics_snapshot()["counters"])
    n_span = len(obs.spans())
    HierarchyDriver(integ, RunConfig(dt=5e-5, num_steps=2,
                                     health_interval=2)).run(state)
    after = obs.metrics_snapshot()["counters"]
    chunk = [s for s in obs.spans()[n_span:] if s["path"] == "driver/chunk"]
    assert chunk[0]["attrs"]["transfer_marshal"] == "rows"
    # the scan's body is traced once: one step's transfers
    assert tuple(after.get(k, 0) - before.get(k, 0)
                 for k in (GATHERS, SCATTERS, SLOT_GATHERS)) == (2, 0, 1)


def test_plain_autodiff_spread_vel_matches_the_custom_vjp():
    """Without the custom VJP, the slot gather's transpose (a scatter-add
    with an index per slot) gives d(spread_vel)/dF as the VJP's
    interpolation through the same buckets does, to float32 rounding."""
    grid, eng, rng, X, b = _setting("packed", 3, "buffered_overflow")
    F = jnp.asarray(rng.randn(N_MARKERS, 3), jnp.float32)
    g = tuple(jnp.asarray(rng.randn(*grid.n), jnp.float32)
              for _ in range(3))

    def loss(F):
        return sum(jnp.sum(a * w) for a, w in
                   zip(eng.spread_vel(F, X, b=b), g))

    custom = jax.jit(jax.grad(loss))(F)
    with interaction_packed.plain_autodiff_transfers():
        plain = jax.jit(jax.grad(loss))(F)
    scale = float(jnp.max(jnp.abs(custom)))
    assert scale > 0
    np.testing.assert_allclose(np.asarray(plain), np.asarray(custom),
                               rtol=1e-5, atol=1e-5 * scale)
