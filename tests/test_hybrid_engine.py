"""Hybrid transfer engine: Pallas-packed spread + XLA packed interp
over one shared PackedBuckets context (round-5 composition, motivated
by the on-chip phases table: spread is cheapest in Pallas, interp in
XLA-with-bf16). Oracle: the XLA scatter path. The load-bearing claim
is that ONE context built by ``buckets`` serves both backends'
transfer directions without re-packing."""

import jax
import jax.numpy as jnp
import numpy as np

from ibamr_tpu.grid import StaggeredGrid
from ibamr_tpu.ops import interaction
from ibamr_tpu.ops.interaction_packed import suggest_chunks
from ibamr_tpu.ops.pallas_interaction import HybridPackedInteraction


def _engine(g, X, chunk=64, **kw):
    Q = suggest_chunks(g, X, tile=8, chunk=chunk, slack=1.3)
    return HybridPackedInteraction(g, kernel="IB_4", tile=8,
                                   chunk=chunk, nchunks=Q,
                                   interpret=True, **kw)


def test_hybrid_matches_scatter_shared_ctx():
    rng = np.random.default_rng(0)
    g = StaggeredGrid(n=(16, 16, 32), x_lo=(0.0,) * 3, x_up=(1.0,) * 3)
    X = jnp.asarray(rng.uniform(0, 1, (300, 3)), dtype=jnp.float32)
    F = jnp.asarray(rng.standard_normal((300, 3)), dtype=jnp.float32)
    eng = _engine(g, X)
    b = eng.buckets(X)          # ONE context for both directions
    f_hy = eng.spread_vel(F, X, b=b)
    f_ref = interaction.spread_vel(F, g, X, kernel="IB_4")
    for a, c in zip(f_ref, f_hy):
        scale = float(jnp.max(jnp.abs(a)))
        np.testing.assert_allclose(np.asarray(c), np.asarray(a),
                                   atol=2e-6 * scale)

    u = tuple(jnp.asarray(rng.standard_normal(g.n), dtype=jnp.float32)
              for _ in range(3))
    U_hy = eng.interpolate_vel(u, X, b=b)
    U_ref = interaction.interpolate_vel(u, g, X, kernel="IB_4")
    scale = float(jnp.max(jnp.abs(U_ref)))
    np.testing.assert_allclose(np.asarray(U_hy), np.asarray(U_ref),
                               atol=2e-6 * scale)


def test_hybrid_bf16_interp_tolerance():
    # bf16 compresses only the interp contraction operands; spread
    # stays f32 through the Pallas program — both within engine
    # tolerances of the scatter oracle
    rng = np.random.default_rng(2)
    g = StaggeredGrid(n=(16, 16, 16), x_lo=(0.0,) * 3, x_up=(1.0,) * 3)
    X = jnp.asarray(rng.uniform(0, 1, (200, 3)), dtype=jnp.float32)
    eng = _engine(g, X, compute_dtype=jnp.bfloat16)
    b = eng.buckets(X)
    u = tuple(jnp.asarray(rng.standard_normal(g.n), dtype=jnp.float32)
              for _ in range(3))
    U_hy = eng.interpolate_vel(u, X, b=b)
    U_ref = interaction.interpolate_vel(u, g, X, kernel="IB_4")
    scale = float(jnp.max(jnp.abs(U_ref)))
    np.testing.assert_allclose(np.asarray(U_hy), np.asarray(U_ref),
                               atol=2e-2 * scale)

    F = jnp.asarray(rng.standard_normal((200, 3)), dtype=jnp.float32)
    f_hy = eng.spread_vel(F, X, b=b)
    f_ref = interaction.spread_vel(F, g, X, kernel="IB_4")
    for a, c in zip(f_ref, f_hy):
        scale = float(jnp.max(jnp.abs(a)))
        np.testing.assert_allclose(np.asarray(c), np.asarray(a),
                                   atol=2e-6 * scale)


def test_hybrid_in_flagship_model():
    from ibamr_tpu.models.shell3d import build_shell_example

    integ, state = build_shell_example(
        n_cells=16, n_lat=16, n_lon=16, radius=0.25,
        use_fast_interaction="hybrid_bf16")
    step = jax.jit(lambda s, d: integ.step(s, d))
    s1 = step(state, 1e-4)
    assert bool(jnp.isfinite(s1.X).all())

    # oracle: the scatter-path model advanced one step
    integ0, state0 = build_shell_example(
        n_cells=16, n_lat=16, n_lon=16, radius=0.25,
        use_fast_interaction=False)
    s0 = jax.jit(lambda s, d: integ0.step(s, d))(state0, 1e-4)
    np.testing.assert_allclose(np.asarray(s1.X), np.asarray(s0.X),
                               rtol=0, atol=5e-5)


def test_hybrid_bf16_registry_name():
    """``hybrid_bf16`` is the one name of the pallas-spread +
    bf16-interp engine; both the python arg and the reference-style
    input knob must build the same configuration."""
    from ibamr_tpu.models.shell3d import build_shell_example
    from ibamr_tpu.utils.input_db import parse_input_string

    integ, _ = build_shell_example(
        n_cells=16, n_lat=16, n_lon=16,
        use_fast_interaction="hybrid_bf16")
    eng = integ.ib.fast
    assert type(eng).__name__ == "HybridPackedInteraction"
    assert eng._xla.compute_dtype == jnp.bfloat16

    db = parse_input_string('''
CartesianGeometry { n_cells = 16, 16, 16 }
Shell { n_lat = 16 n_lon = 16 }
IBMethod { transfer_engine = "hybrid_bf16" }
''')
    integ2, _ = build_shell_example(input_db=db)
    assert type(integ2.ib.fast).__name__ == "HybridPackedInteraction"
    assert integ2.ib.fast._xla.compute_dtype == jnp.bfloat16


def test_hybrid_refresh_shares_one_context():
    # the hybrid engine's refresh delegates to the XLA twin: ONE
    # refreshed PackedBuckets must serve the pallas spread AND the
    # bf16 interp at the drifted position
    rng = np.random.default_rng(5)
    g = StaggeredGrid(n=(16, 16, 16), x_lo=(0.0,) * 3, x_up=(1.0,) * 3)
    X = jnp.asarray(rng.uniform(0, 1, (180, 3)), dtype=jnp.float32)
    eng = _engine(g, X, compute_dtype=jnp.bfloat16)
    b = eng.buckets(X)
    Xd = X - jnp.float32(0.4 * float(g.dx[0]))
    b2, hit = eng.refresh(b, Xd)
    assert bool(hit)
    F = jnp.asarray(rng.standard_normal((180, 3)), dtype=jnp.float32)
    f_hy = eng.spread_vel(F, Xd, b=b2)
    f_ref = interaction.spread_vel(F, g, Xd, kernel="IB_4")
    for a, c in zip(f_ref, f_hy):
        scale = float(jnp.max(jnp.abs(a)))
        np.testing.assert_allclose(np.asarray(c), np.asarray(a),
                                   atol=2e-6 * scale)
    u = tuple(jnp.asarray(rng.standard_normal(g.n), dtype=jnp.float32)
              for _ in range(3))
    U_hy = eng.interpolate_vel(u, Xd, b=b2)
    U_ref = interaction.interpolate_vel(u, g, Xd, kernel="IB_4")
    np.testing.assert_allclose(
        np.asarray(U_hy), np.asarray(U_ref),
        atol=2e-2 * float(jnp.max(jnp.abs(U_ref))))
