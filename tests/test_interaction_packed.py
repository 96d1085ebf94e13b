"""Occupancy-packed chunk spread/interp: agreement with the scatter
oracle, adjointness, chunk-capacity overflow exactness, and clustered
(silhouette-like) distributions where packing beats the fixed-cap pool."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ibamr_tpu.grid import StaggeredGrid
from ibamr_tpu.ops import interaction
from ibamr_tpu.ops.interaction_packed import (PackedInteraction,
                                              pack_markers, suggest_chunks)

F64 = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32


def _markers(n, dim, seed=0):
    rng = np.random.RandomState(seed)
    return jnp.asarray(rng.rand(n, dim), dtype=F64)


@pytest.mark.parametrize("dim,n", [(2, 32), (3, 16)])
@pytest.mark.parametrize("kernel", ["IB_4", "IB_3", "BSPLINE_4"])
def test_matches_scatter_path(dim, n, kernel):
    grid = StaggeredGrid(n=(n,) * dim, x_lo=(0,) * dim, x_up=(1,) * dim)
    X = _markers(300, dim)
    rng = np.random.RandomState(1)
    F = jnp.asarray(rng.randn(300, dim), dtype=F64)
    mask = jnp.asarray((rng.rand(300) > 0.1).astype(np.float64), dtype=F64)
    Q = suggest_chunks(grid, X, kernel=kernel, tile=8, chunk=16)
    eng = PackedInteraction(grid, kernel=kernel, tile=8, chunk=16,
                            nchunks=Q)

    f_ref = interaction.spread_vel(F, grid, X, kernel=kernel, weights=mask)
    f_new = eng.spread_vel(F, X, weights=mask)
    for a, b in zip(f_ref, f_new):
        scale = float(jnp.max(jnp.abs(a))) + 1e-12
        assert float(jnp.max(jnp.abs(a - b))) < 1e-5 * scale

    u = tuple(jnp.asarray(rng.randn(*grid.n), dtype=F64)
              for _ in range(dim))
    U_ref = interaction.interpolate_vel(u, grid, X, kernel=kernel,
                                        weights=mask)
    U_new = eng.interpolate_vel(u, X, weights=mask)
    scale = float(jnp.max(jnp.abs(U_ref))) + 1e-12
    assert float(jnp.max(jnp.abs(U_ref - U_new))) < 1e-5 * scale


def test_hot_tile_takes_many_chunks_no_overflow():
    # all markers clustered in ONE tile: the fixed-cap engine would
    # overflow at cap=16; the packed engine allocates ceil(200/16)
    # chunks to that tile and stays on the dense path
    grid = StaggeredGrid(n=(32, 32), x_lo=(0, 0), x_up=(1, 1))
    rng = np.random.RandomState(2)
    X = jnp.asarray(0.1 + 0.05 * rng.rand(200, 2), dtype=F64)
    F = jnp.asarray(rng.randn(200, 2), dtype=F64)
    eng = PackedInteraction(grid, tile=8, chunk=16, nchunks=32)
    b = eng.buckets(X)
    assert not bool(b.any_overflow)
    # chunks of the hot tile are contiguous and share a tile id
    used = np.asarray(jnp.sum(b.wb > 0, axis=1))
    assert used.sum() == 200 and (used > 0).sum() == 13  # ceil(200/16)
    f_ref = interaction.spread_vel(F, grid, X)
    f_new = eng.spread_vel(F, X)
    for a, c in zip(f_ref, f_new):
        assert float(jnp.max(jnp.abs(a - c))) < 1e-5 * (
            float(jnp.max(jnp.abs(a))) + 1e-12)


def test_chunk_capacity_overflow_exact():
    # nchunks too small -> excess markers flow through the compact
    # scatter fallback; result must STILL match the oracle exactly
    grid = StaggeredGrid(n=(32, 32), x_lo=(0, 0), x_up=(1, 1))
    rng = np.random.RandomState(3)
    X = jnp.asarray(rng.rand(400, 2), dtype=F64)
    F = jnp.asarray(rng.randn(400, 2), dtype=F64)
    eng = PackedInteraction(grid, tile=8, chunk=8, nchunks=6)
    b = eng.buckets(X)
    assert bool(b.any_overflow)
    f_ref = interaction.spread_vel(F, grid, X)
    f_new = eng.spread_vel(F, X)
    for a, c in zip(f_ref, f_new):
        assert float(jnp.max(jnp.abs(a - c))) < 1e-5 * (
            float(jnp.max(jnp.abs(a))) + 1e-12)
    u = tuple(jnp.asarray(rng.randn(32, 32), dtype=F64) for _ in range(2))
    U_ref = interaction.interpolate_vel(u, grid, X)
    U_new = eng.interpolate_vel(u, X)
    assert float(jnp.max(jnp.abs(U_ref - U_new))) < 1e-5


def test_adjointness():
    grid = StaggeredGrid(n=(16, 16, 16), x_lo=(0,) * 3, x_up=(1,) * 3)
    X = _markers(150, 3, seed=3)
    rng = np.random.RandomState(4)
    F = jnp.asarray(rng.randn(150, 3), dtype=F64)
    u = tuple(jnp.asarray(rng.randn(16, 16, 16), dtype=F64)
              for _ in range(3))
    eng = PackedInteraction(grid, tile=8, chunk=32, nchunks=16)
    b = eng.buckets(X)
    f = eng.spread_vel(F, X, b=b)
    U = eng.interpolate_vel(u, X, b=b)
    h3 = float(np.prod(grid.dx))
    lhs = sum(float(jnp.sum(a * c)) for a, c in zip(f, u)) * h3
    rhs = float(jnp.sum(F * U))
    assert abs(lhs - rhs) < 1e-5 * (abs(lhs) + abs(rhs) + 1e-12)


def test_shell_silhouette_packing_efficiency():
    # flagship-shaped distribution (spherical shell): packed slots must
    # be a small multiple of N where the fixed-cap pool pads by ~10x
    from ibamr_tpu.models.shell3d import make_spherical_shell
    from ibamr_tpu.ops.interaction_fast import suggest_cap

    grid = StaggeredGrid(n=(64, 64, 64), x_lo=(0,) * 3, x_up=(1,) * 3)
    s = make_spherical_shell(80, 80, 0.25, (0.5, 0.5, 0.5), 1.0)
    N = s.vertices.shape[0]
    Q = suggest_chunks(grid, s.vertices, tile=8, chunk=64)
    packed_slots = Q * 64
    cap = suggest_cap(grid, s.vertices, tile=8)
    pool_slots = 8 * 8 * cap
    assert packed_slots < 4 * N
    assert packed_slots < pool_slots / 2

    eng = PackedInteraction(grid, tile=8, chunk=64, nchunks=Q)
    X = jnp.asarray(s.vertices, dtype=F64)
    b = eng.buckets(X)
    assert not bool(b.any_overflow)
    F = jnp.ones((N, 3), dtype=F64)
    f_ref = interaction.spread_vel(F, grid, X)
    f_new = eng.spread_vel(F, X)
    for a, c in zip(f_ref, f_new):
        assert float(jnp.max(jnp.abs(a - c))) < 1e-5 * (
            float(jnp.max(jnp.abs(a))) + 1e-12)


def test_jit_stability_and_position_reuse():
    grid = StaggeredGrid(n=(32, 32), x_lo=(0, 0), x_up=(1, 1))
    X = _markers(500, 2, seed=6)
    Q = suggest_chunks(grid, X, tile=8, chunk=32)
    eng = PackedInteraction(grid, tile=8, chunk=32, nchunks=Q)
    F = jnp.ones((500, 2), dtype=F64)

    @jax.jit
    def go(F, X):
        b = eng.buckets(X)
        f = eng.spread_vel(F, X, b=b)
        U = eng.interpolate_vel(f, X, b=b)
        return f, U

    f1, U1 = go(F, X)
    f2, U2 = go(F, X + 0.002)   # same shapes -> cached compile
    assert np.isfinite(np.asarray(f1[0])).all()
    assert np.isfinite(np.asarray(U2)).all()


def test_bf16_compute_matches_f32_within_tolerance():
    """bf16-compressed contraction operands (the HBM-halving opt-in):
    spread and interp agree with the exact-f32 engines to bf16 weight
    precision (~4e-3 relative), and adjointness survives at that
    tolerance."""
    g = StaggeredGrid(n=(32, 32, 32), x_lo=(0.0,) * 3, x_up=(1.0,) * 3)
    rng = np.random.default_rng(5)
    N = 3000
    X = jnp.asarray(0.15 + 0.7 * rng.random((N, 3)), jnp.float32)
    F = jnp.asarray(rng.standard_normal((N, 3)), jnp.float32)
    u = tuple(jnp.asarray(rng.standard_normal(g.n), jnp.float32)
              for _ in range(3))

    exact = PackedInteraction(g, tile=8, chunk=128, nchunks=64)
    comp = PackedInteraction(g, tile=8, chunk=128, nchunks=64,
                             compute_dtype=jnp.bfloat16)
    f0 = exact.spread_vel(F, X)
    f1 = comp.spread_vel(F, X)
    scale = max(float(jnp.max(jnp.abs(c))) for c in f0)
    err = max(float(jnp.max(jnp.abs(a - b)))
              for a, b in zip(f0, f1))
    assert err < 8e-3 * scale, (err, scale)

    U0 = exact.interpolate_vel(u, X)
    U1 = comp.interpolate_vel(u, X)
    uscale = float(jnp.max(jnp.abs(U0)))
    uerr = float(jnp.max(jnp.abs(U0 - U1)))
    assert uerr < 8e-3 * uscale, uerr

    # adjointness at bf16 tolerance: <spread(F), u> == <F, interp(u)>
    lhs = sum(float(jnp.sum(a * b)) for a, b in
              zip(comp.spread_vel(F, X), u))
    rhs = float(jnp.sum(F * comp.interpolate_vel(u, X))) \
        / float(np.prod(g.dx))
    assert abs(lhs - rhs) < 2e-2 * max(abs(lhs), abs(rhs), 1e-6), \
        (lhs, rhs)


def test_transfer_engine_input_key():
    """The reference-style input knob IBMethod{transfer_engine=...}
    selects the engine in build_shell_example; unknown names raise."""
    import pytest

    from ibamr_tpu.models.shell3d import build_shell_example
    from ibamr_tpu.utils.input_db import parse_input_string

    def db_for(eng):
        return parse_input_string(f'''
CartesianGeometry {{ n_cells = 16, 16, 16 }}
Shell {{ n_lat = 24 n_lon = 24 }}
IBMethod {{ transfer_engine = "{eng}" }}
''')

    for eng, cls in (("packed", "PackedInteraction"),
                     ("scatter", "NoneType"),
                     ("mxu", "FastInteraction"),
                     ("packed_bf16", "PackedInteraction")):
        integ, _ = build_shell_example(input_db=db_for(eng))
        assert type(integ.ib.fast).__name__ == cls, eng
    with pytest.raises(ValueError, match="transfer_engine"):
        build_shell_example(input_db=db_for("bf16"))
