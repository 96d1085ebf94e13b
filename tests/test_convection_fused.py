"""The slab-fused PPM operator (``ops/pallas_convection.py``), periodic
and walled, against its oracle, the ghost-padded ``convective_rate_bc``.

CPU, Pallas interpret mode, small tile-aligned shapes. What the chip's
compiler says of the kernel at 256^3 is in tests/test_tpu_compile.py.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ibamr_tpu import obs
from ibamr_tpu.grid import StaggeredGrid
from ibamr_tpu.integrators.ins import INSStaggeredIntegrator
from ibamr_tpu.integrators.ins_walls import pin_normal
from ibamr_tpu.ops import convection
from ibamr_tpu.ops.pallas_convection import (convective_rate_ppm_fused,
                                             fused_ppm_supported)

SHAPES = [(16, 8, 128), (24, 16, 128)]
DX = (0.11, 0.07, 0.05)


def _profile(n):
    """The 1D profile of test_tg_periodic's limiter test scaled to n
    cells: smooth extrema, a plateau with two fronts, a narrow peak, a
    one-cell spike."""
    x = np.arange(n) / n
    return (np.sin(2 * np.pi * x)
            + 1.5 * (np.abs(x - 0.25) < 0.09)
            + 0.8 * np.exp(-0.5 * ((x - 0.62) * n / 1.5) ** 2)
            + 0.3 * (np.arange(n) == (5 * n) // 6))


def _fields(kind, shape, seed=0):
    rng = np.random.default_rng(seed)
    if kind == "random":
        u = [rng.standard_normal(shape) for _ in range(3)]
    elif kind.startswith("front"):
        # every component carries the profile along one axis, over a
        # smooth transverse variation, so that each of the three
        # directional passes along that axis meets every branch
        ax = int(kind[-1])
        prof = _profile(shape[ax]).reshape(
            [-1 if a == ax else 1 for a in range(3)])
        grids = np.meshgrid(*[np.arange(m) / m for m in shape],
                            indexing="ij")
        u = [prof * (1.0 + 0.2 * np.cos(2 * np.pi * grids[(ax + 1 + c) % 3]))
             - 0.4 * c for c in range(3)]
    elif kind == "zeros":
        # exact zeros of the advecting velocity: whole planes, rows
        # and columns of every component, and isolated cells
        u = [rng.standard_normal(shape) for _ in range(3)]
        for c in u:
            c[::3] = 0.0
            c[:, ::4] = 0.0
            c[:, :, ::5] = 0.0
            c[rng.random(shape) < 0.2] = 0.0
    else:
        raise ValueError(kind)
    return tuple(jnp.asarray(c, jnp.float32) for c in u)


def _padded(u, dx=DX):
    return jax.jit(lambda v: convection.convective_rate_bc(v, dx, "ppm"))(u)


def _fused(u, dx=DX):
    return jax.jit(lambda v: convective_rate_ppm_fused(v, dx))(u)


def _gap(got, want):
    scale = max(float(jnp.max(jnp.abs(w))) for w in want)
    return max(float(jnp.max(jnp.abs(g - w)))
               for g, w in zip(got, want)) / scale


@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: "x".join(map(str, s)))
@pytest.mark.parametrize("kind", ["random", "front0", "front1", "front2",
                                  "zeros"])
def test_fused_equals_the_padded_operator(shape, kind):
    u = _fields(kind, shape)
    want, got = _padded(u), _fused(u)
    assert all(g.dtype == jnp.float32 and g.shape == shape for g in got)
    assert _gap(got, want) <= 1e-5
    if kind == "zeros":
        # the centred value at an exactly zero advecting velocity is a
        # branch of its own: it was taken
        adv = 0.5 * (u[0] + jnp.roll(u[0], -1, 0))
        assert int(jnp.sum(adv == 0.0)) > 100
    if kind.startswith("front"):
        # the limiter was at work: the rate differs from the centred one
        cen = convection.convective_rate(u, DX, "centered")
        assert _gap(want, cen) > 1e-2


@pytest.mark.parametrize("axis,k", [(0, 1), (0, 5), (0, -9), (1, 3),
                                    (1, -1), (2, 1), (2, 37)])
def test_fused_wraps_periodically(axis, k):
    shape = SHAPES[0]
    u = _fields("random", shape, seed=3)
    rolled = tuple(jnp.roll(c, k, axis) for c in u)
    want = tuple(jnp.roll(c, k, axis) for c in _fused(u))
    got = _fused(rolled)
    # a shift of the data is a shift of every stencil: the same
    # float32 operations on the same numbers
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


# -- walls (PR 33) -----------------------------------------------------------

WALLS = [(True, True, True), (True, False, False), (False, True, False),
         (False, False, True), (True, False, True)]
LID = {(0, 1, 1): 1.0}


def _axes(walls):
    return "".join(name for name, w in zip("xyz", walls or ()) if w)


def _slot0(d):
    """Index of component d's pinned slot along its own axis."""
    return (slice(None),) * d + (0,)


def _moving_walls(walls):
    """One moving wall per walled axis on both sides, unequal values."""
    tang = {}
    for e in range(3):
        if walls[e]:
            d = (e + 1) % 3
            tang[(d, e, 0)], tang[(d, e, 1)] = 0.6 + 0.3 * e, -1.1 + 0.2 * e
    return tang


TANGENTIAL = {
    "still": lambda walls: {},
    # the cavity's lid (as the oracle, the kernel takes no notice of it
    # where axis 1 has no wall)
    "lid": lambda walls: LID,
    "moving": _moving_walls,
}


def _walled_fields(kind, shape, walls):
    """``_fields`` made to honour the wall storage convention (component
    d's slot 0 along a walled axis d is the wall's 0); the front kinds
    also carry a step two cells from the lo wall and a spike two cells
    from the hi wall of their axis."""
    u = [np.array(c) for c in _fields(kind, shape)]
    if kind.startswith("front"):
        ax = int(kind[-1])
        i = np.arange(shape[ax]).reshape(
            [-1 if a == ax else 1 for a in range(3)])
        for c in u:
            c += 1.2 * (i < 2) - 0.9 * (i == shape[ax] - 2)
    return tuple(pin_normal(jnp.asarray(c, jnp.float32), d, walls)
                 for d, c in enumerate(u))


@functools.lru_cache(maxsize=None)
def _walled_ops(walls, tang_items):
    tang = dict(tang_items)
    return (jax.jit(lambda v: convection.convective_rate_bc(
                v, DX, "ppm", walls, tang)),
            jax.jit(lambda v: convective_rate_ppm_fused(
                v, DX, walls, tang_items)),
            jax.jit(lambda v: convection.convective_rate_bc(
                v, DX, "centered", walls, tang)))


@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: "x".join(map(str, s)))
@pytest.mark.parametrize("kind", ["random", "front0", "front1", "front2",
                                  "zeros"])
@pytest.mark.parametrize("tangential", list(TANGENTIAL))
@pytest.mark.parametrize("walls", WALLS, ids=_axes)
def test_fused_equals_the_padded_operator_with_walls(walls, tangential,
                                                     kind, shape):
    tang = TANGENTIAL[tangential](walls)
    padded, fused, centred = _walled_ops(walls, tuple(sorted(tang.items())))
    u = _walled_fields(kind, shape, walls)
    want, got = padded(u), fused(u)
    assert all(g.dtype == jnp.float32 and g.shape == shape for g in got)
    assert _gap(got, want) <= 1e-5
    # the wall-normal faces: rate d is exactly 0 on a walled axis d
    for d in range(3):
        if walls[d]:
            assert not np.asarray(got[d])[_slot0(d)].any()
    if kind.startswith("front") and walls[int(kind[-1])]:
        # the limiter was at work within 3 cells of each wall of the
        # front's axis: there the rate differs from the centred one
        ax = int(kind[-1])
        cen = centred(u)
        scale = max(float(jnp.max(jnp.abs(w))) for w in want)
        for near in (slice(0, 3), slice(shape[ax] - 3, shape[ax])):
            at = (slice(None),) * ax + (near,)
            assert max(float(jnp.max(jnp.abs(w[at] - c[at])))
                       for w, c in zip(want, cen)) > 1e-2 * scale


def _counts():
    c = obs.metrics_snapshot()["counters"]
    return (c.get("fluid_convect_fused_total", 0),
            c.get("fluid_convect_padded_total", 0))


def _integ(n, op="PPM", wall_axes=None, dtype=jnp.float32,
           wall_tangential=None):
    grid = StaggeredGrid(n=n, x_lo=(-math.pi,) * len(n),
                         x_up=(math.pi,) * len(n))
    return INSStaggeredIntegrator(grid, rho=1.0, mu=0.01,
                                  convective_op_type=op, dtype=dtype,
                                  wall_axes=wall_axes,
                                  wall_tangential=wall_tangential)


def _seeded(integ, seed=0):
    rng = np.random.default_rng(seed)
    return integ.initialize(u0_arrays=[
        0.1 * rng.standard_normal(integ.grid.n) for _ in integ.grid.n])


SELECTION = [
    ("aligned periodic 3D ppm", dict(n=(16, 8, 128)), True),
    ("walls", dict(n=(16, 8, 128), wall_axes=(False, True, False)), True),
    ("walls, traced tangential value",
     dict(n=(16, 8, 128), wall_axes=(False, True, False),
          traced_lid=True), False),
    ("cui", dict(n=(16, 8, 128), op="CUI"), False),
    ("2D", dict(n=(16, 128)), False),
    ("32^3", dict(n=(32, 32, 32)), False),
    ("float64", dict(n=(16, 8, 128), dtype=jnp.float64), False),
]


@pytest.mark.parametrize("what,kw,fused", SELECTION,
                         ids=[s[0] for s in SELECTION])
def test_selection_by_shape_dtype_and_boundary(what, kw, fused):
    kw = dict(kw)
    traced_lid = kw.pop("traced_lid", False)
    integ = _integ(**kw)
    if traced_lid:
        # a wall value that is no Python number (the integrator itself
        # takes none such: its solves lift the walls on the host)
        integ._convective = functools.partial(
            convection.convective_rate_select, scheme="ppm",
            wall_axes=integ.wall_axes,
            wall_tangential={(0, 1, 1): jnp.float32(1.0)})
    state = _seeded(integ)
    before = _counts()
    with obs.span("driver/chunk"):
        text = str(jax.make_jaxpr(integ.step)(state, 1e-3))
    after = _counts()
    assert (after[0] - before[0], after[1] - before[1]) == \
        ((1, 0) if fused else (0, 1))
    assert ("pallas_call" in text) == fused
    # ... and the span of the call that traced it was told
    attrs = obs.spans()[-1]["attrs"]
    assert attrs["convect_path"] == ("fused" if fused else "padded")
    assert attrs["convect_walls"] == _axes(kw.get("wall_axes"))


def test_the_sharded_wrapper_stays_on_the_padded_path(mesh8):
    from ibamr_tpu.parallel.mesh import make_sharded_ins_step
    integ = _integ((16, 8, 128))
    state = _seeded(integ)
    before = _counts()
    step = make_sharded_ins_step(integ, mesh8)
    out = step(state, 1e-3)
    after = _counts()
    assert (after[0] - before[0], after[1] - before[1]) == (0, 1)
    # the integrator it was made from still takes the kernel, and the
    # two evaluations advance the state alike
    one = jax.jit(integ.step)(state, 1e-3)
    assert _counts()[0] == after[0] + 1
    assert _gap(out.u, one.u) <= 1e-5


def test_not_supported_shapes_and_dtypes():
    f32 = lambda s: tuple(jnp.zeros(s, jnp.float32) for _ in range(3))
    assert fused_ppm_supported(f32((16, 8, 128)))
    assert fused_ppm_supported(f32((5, 16, 256)))
    assert not fused_ppm_supported(f32((16, 8, 128))[:2])
    assert not fused_ppm_supported(f32((16, 12, 128)))
    assert not fused_ppm_supported(f32((16, 8, 64)))
    assert not fused_ppm_supported(f32((2, 8, 128)))
    # 512 x 512 planes: no slab of them fits VMEM with its halo
    assert not fused_ppm_supported(tuple(
        jax.ShapeDtypeStruct((512,) * 3, jnp.float32) for _ in range(3)))
    assert fused_ppm_supported(tuple(
        jax.ShapeDtypeStruct((256,) * 3, jnp.float32) for _ in range(3)))
    assert not fused_ppm_supported(
        tuple(jnp.zeros((16, 8, 128), jnp.float64) for _ in range(3)))
    # an odd leading extent takes thinner slabs, not another path
    u = _fields("random", (5, 8, 128), seed=5)
    assert _gap(_fused(u), _padded(u)) <= 1e-5
    # ... with walls too: slabs of one plane, whose halo reaches the
    # wall from the second and third grid step as well
    walls = (True, True, True)
    u = _walled_fields("random", (5, 8, 128), walls)
    padded, fused, _ = _walled_ops(walls, tuple(sorted(LID.items())))
    assert _gap(fused(u), padded(u)) <= 1e-5
    # a pinned ghost plane -3 is the image of plane 3
    assert fused_ppm_supported(f32((3, 8, 128)))
    assert not fused_ppm_supported(f32((3, 8, 128)), (True, False, False))
    assert fused_ppm_supported(f32((4, 8, 128)), (True, False, False))


def test_grad_and_vmap_through_the_step():
    integ = _integ((16, 8, 128))
    state = _seeded(integ, seed=1)
    # AB2 from a previous rate, so that N(u) of this step counts
    state = jax.jit(integ.step)(state, 1e-3)

    def energy(step, u):
        out = step(state._replace(u=u), 1e-3)
        return sum(jnp.sum(c * c) for c in out.u)

    g_fused = jax.jit(jax.grad(lambda u: energy(integ.step, u)))(state.u)
    padded = _integ((16, 8, 128))
    padded._convective = padded._convective_padded
    g_padded = jax.jit(jax.grad(lambda u: energy(padded.step, u)))(state.u)
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in g_fused)
    assert _gap(g_fused, g_padded) <= 1e-4

    # the fleet chunk's batching: lanes of states through one step
    lanes = jax.tree_util.tree_map(
        lambda a: jnp.stack([a, 0.5 * a, -a]), state)
    out = jax.jit(jax.vmap(lambda s: integ.step(s, 1e-3)))(lanes)
    for b in range(3):
        one = jax.jit(integ.step)(
            jax.tree_util.tree_map(lambda a: a[b], lanes), 1e-3)
        assert _gap(tuple(c[b] for c in out.u), one.u) <= 1e-5


def test_a_walled_chunk_through_the_integrator():
    # a cavity: six walls, the wall y = hi moving in x
    from ibamr_tpu.utils.hierarchy_driver import scan_steps
    walls, n = (True, True, True), (16, 8, 128)
    fused = _integ(n, wall_axes=walls, wall_tangential=LID)
    padded = _integ(n, wall_axes=walls, wall_tangential=LID)
    padded._convective = padded._convective_padded
    state = fused.initialize(u0_arrays=[
        0.1 * np.asarray(c) for c in _walled_fields("random", n, walls)])
    # the pressure is (rho / dt) times a potential: at dt = 1e-3 the
    # float32 rounding of the right-hand side alone reads 1.5e-4 of p
    dt = 0.1

    def chunk(integ):
        def body(s, _):
            s = integ.step(s, dt)
            return s, s.u
        return jax.jit(lambda s: jax.lax.scan(body, s, None, length=4))

    before = _counts()
    (got, got_us), (want, _) = chunk(fused)(state), chunk(padded)(state)
    after = _counts()
    assert (after[0] - before[0], after[1] - before[1]) == (1, 1)
    assert _gap(got.u, want.u) <= 1e-5
    assert _gap((got.p,), (want.p,)) <= 1e-5
    # the configuration's guarantee: the normal velocity on all six
    # walls (slot 0 and its wrap image) is exactly 0 at every step
    for d in range(3):
        assert not np.asarray(got_us[d])[(slice(None),) + _slot0(d)].any()

    # the VJP carries the walls: AB2 from a previous rate, so that
    # N(u) of this step counts
    def energy(integ, u):
        out, _ = scan_steps(integ.step, got._replace(u=u), dt, 1)
        return sum(jnp.sum(c * c) for c in out.u)

    g_fused = jax.jit(jax.grad(lambda u: energy(fused, u)))(got.u)
    g_padded = jax.jit(jax.grad(lambda u: energy(padded, u)))(got.u)
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in g_fused)
    assert _gap(g_fused, g_padded) <= 1e-4


def test_the_tracing_chunk_span_names_the_path():
    from ibamr_tpu.utils.hierarchy_driver import HierarchyDriver, RunConfig
    integ = _integ((16, 8, 128))
    drv = HierarchyDriver(integ, RunConfig(dt=1e-3, num_steps=4,
                                           health_interval=2))
    obs.clear_spans()
    drv.run(_seeded(integ))
    chunks = [s for s in obs.spans() if s["path"] == "driver/chunk"]
    assert [(s["attrs"].get("convect_path"), s["attrs"].get("convect_walls"))
            for s in chunks] == [("fused", ""), (None, None)]


# -- the fast-diagonalization solves' own counters (PR 32) -------------------

def _transform_counts():
    c = obs.metrics_snapshot()["counters"]
    return (c.get("fluid_transform_dense_axes_total", 0),
            c.get("fluid_transform_fft_axes_total", 0))


# 4 solves a step (three velocity Helmholtz, one pressure Poisson), every
# axis forward and inverse: 4 x 3 x 2 = 24 axis transforms
TRANSFORMS = [
    ("walls on three axes", dict(n=(16, 16, 16),
                                 wall_axes=(True, True, True)),
     (24, 0), "dense"),
    ("walls on one axis", dict(n=(16, 16, 16),
                               wall_axes=(False, True, False)),
     (8, 16), "mixed"),
    ("periodic", dict(n=(16, 16, 16)), (0, 0), None),
]


@pytest.mark.parametrize("what,kw,raised,path", TRANSFORMS,
                         ids=[t[0] for t in TRANSFORMS])
def test_transform_counters_by_boundary(what, kw, raised, path):
    integ = _integ(**kw)
    state = _seeded(integ)
    before = _transform_counts()
    with obs.span("driver/chunk"):
        text = str(jax.make_jaxpr(integ.step)(state, 1e-3))
    after = _transform_counts()
    # a periodic step goes through solvers/spectral_plan, not fastdiag
    assert (after[0] - before[0], after[1] - before[1]) == raised
    assert obs.spans()[-1]["attrs"].get("transform_path") == path
    assert text.count("dot_general") == raised[0]


def test_the_tracing_chunk_span_names_the_transform_path():
    from ibamr_tpu.utils.hierarchy_driver import HierarchyDriver, RunConfig
    integ = _integ((16, 16, 16), wall_axes=(True, True, True))
    drv = HierarchyDriver(integ, RunConfig(dt=1e-3, num_steps=4,
                                           health_interval=2))
    obs.clear_spans()
    drv.run(_seeded(integ))
    chunks = [s for s in obs.spans() if s["path"] == "driver/chunk"]
    assert [tuple(s["attrs"].get(k) for k in
                  ("transform_path", "convect_path", "convect_walls"))
            for s in chunks] == [("dense", "padded", "xyz"), (None,) * 3]
