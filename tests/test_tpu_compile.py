"""The main path's kernels compile for the chip (no chip attached).

The TPU compiler is installed here and compiles for a DESCRIBED v5e
(on-chip-measurement guide section 2): Mosaic refusals (tiling,
VMEM), programs that do not fit HBM and unpartitionable kernels show
up here at no chip time. A compile that passes is NOT a chip run.

Rules this file keeps (one libtpu load per process, xdist-safe): the
topology is described inside a module-scoped fixture, never at import
/ collection; compiles run in the test's own process with the
persistent compile cache off; everything TPU lives in THIS one file.

Sizes: the interp kernels compile at the flagship's real width (256^3,
316x316 = 99,856 markers; ~5 s each) with the bucket pytree handed in
as shapes, so only the kernel compiles. The spread kernels with their
overlap-add take ~88 s EACH at 256^3 (measured PR 23: pallas_packed,
hybrid_bf16 and pallas all compiled there, ``tpu_custom_call``
present), so tier-1 compiles them at the 64^3 flagship geometry
(79x79 markers). The whole ``integ.step`` compiles at 64^3 too (the
256^3 step and scan-chunk compiles take ~2 min each and are made by
hand; CHANGES.md PR 23 records them).
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from ibamr_tpu.grid import StaggeredGrid
from ibamr_tpu.models.engine_resolver import construct_transfer_engine
from ibamr_tpu.models.shell3d import (build_shell_example,
                                      make_spherical_shell)

HBM_BYTES = 16e9          # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _chip_compile_mode():
    # a described-chip executable is written to the persistent cache
    # but cannot be read back without a chip; keep these compiles out
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    # production mode: conftest turns x64 on for the CPU convergence
    # tests; the chip program is f32/int32 (Mosaic lowers no 64-bit)
    with jax.enable_x64(False):
        yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _on(sharding, tree):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=sharding), tree)


def _compiled_mode(fast):
    """Steer a CPU-built engine out of Pallas interpret mode (the
    classes choose it from ``jax.default_backend()``, which is the CPU
    here): flip every ``interpret`` flag the engine carries."""
    flipped = 0
    for obj in (fast, getattr(fast, "_pal", None)):
        if obj is not None and hasattr(obj, "interpret"):
            obj.interpret = False
            flipped += 1
    assert flipped, f"{type(fast).__name__} carries no interpret flag"
    return fast


FLAGSHIP = {256: 316, 64: 79}     # grid extent -> shell lattice side
@functools.lru_cache(maxsize=None)
def _engine(name, n):
    """Registry-built engine at a flagship geometry (the sizing the run
    uses: suggest_chunks / suggest_cap from the real lattice)."""
    grid = StaggeredGrid(n=(n,) * 3, x_lo=(0.0,) * 3, x_up=(1.0,) * 3)
    verts = make_spherical_shell(FLAGSHIP[n], FLAGSHIP[n], 0.25,
                                 (0.5, 0.5, 0.5), 1.0,
                                 aspect=1.2).vertices
    fast = _compiled_mode(
        construct_transfer_engine(name, grid, verts, "IB_4"))
    X = jax.ShapeDtypeStruct(verts.shape, jnp.float32)
    return fast, X, jax.eval_shape(fast.buckets, X)


@pytest.mark.parametrize("name,op,n", [
    ("pallas_packed", "interp", 256),
    ("pallas_packed", "spread", 64),
    ("hybrid_bf16", "spread", 64),
])
def test_transfer_kernel_compiles(one_chip, name, op, n):
    fast, X, b = _engine(name, n)
    assert X.shape[0] == FLAGSHIP[n] ** 2 and fast.grid.n[-1] == n
    if op == "spread":
        def fn(F, X, b):
            return fast.spread_vel(F, X, b=b)
        lead = X                                   # forces: (N, 3)
    else:
        def fn(u, X, b):
            return fast.interpolate_vel(u, X, b=b)
        lead = tuple(jax.ShapeDtypeStruct(fast.grid.n, jnp.float32)
                     for _ in range(3))
    compiled = jax.jit(fn).lower(
        *_on(one_chip, (lead, X, b))).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("program", ["step", "carried_chunk"])
def test_whole_step_packed_bf16_fits_one_chip(one_chip, program):
    # the engine TUNING_DB.json names for the flagship on platform tpu:
    # the step alone, and the driver's chunk that carries the packed
    # marker layout through its scan (one pack before the loop, two
    # refreshes with their cond fallback inside it)
    integ, state = build_shell_example(
        n_cells=64, n_lat=79, n_lon=79,
        use_fast_interaction="packed_bf16")
    fn = jax.jit(integ.step)
    if program == "carried_chunk":
        from ibamr_tpu.utils.hierarchy_driver import (HierarchyDriver,
                                                      RunConfig)

        drv = HierarchyDriver(integ, RunConfig(dt=5e-5, num_steps=2,
                                               health_interval=2))
        assert drv._carried
        fn = drv._chunk(2)
    compiled = fn.lower(_on(one_chip, state), 5e-5).compile()
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes + ma.generated_code_size_in_bytes)
    assert 0 < total < HBM_BYTES, ma


def test_fluid_only_ppm_chunk_fits_one_chip(one_chip):
    # the Taylor-Green configuration's program (tg_256, PR 28): the
    # driver's scan chunk of the fluid solve alone with the ghost-padded,
    # limited PPM operator, which no shell input uses (the 256^3 chunk of
    # 20 steps compiles in ~30 s and takes 2.5 GiB; made by hand). At
    # 64^3 the last extent is no multiple of 128, so by the shape rule
    # of ops/convection.convective_rate_select this chunk stays on the
    # padded path (PR 29); the fused kernel has its own case below
    import math

    from ibamr_tpu.integrators.ins import INSStaggeredIntegrator
    from ibamr_tpu.utils.hierarchy_driver import HierarchyDriver, RunConfig

    grid = StaggeredGrid(n=(64,) * 3, x_lo=(-math.pi,) * 3,
                         x_up=(math.pi,) * 3)
    integ = INSStaggeredIntegrator(grid, rho=1.0, mu=1.0 / 1600,
                                   convective_op_type="PPM")
    state = jax.eval_shape(integ.initialize)
    drv = HierarchyDriver(integ, RunConfig(dt=0.02, num_steps=2,
                                           health_interval=2))
    compiled = drv._chunk(2).lower(_on(one_chip, state), 0.02).compile()
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes + ma.generated_code_size_in_bytes)
    assert 0 < total < HBM_BYTES, ma
    assert "/fluid/convect/" in compiled.as_text()


def test_walled_ppm_chunk_fits_one_chip_at_float32_products(one_chip):
    # the lid-driven cavity's program (cavity_256, PR 32): the driver's
    # scan chunk of the wall-bounded fluid solve, whose 24 axis
    # transforms a step are dense products on the matrix unit (the 256^3
    # chunk of 20 steps compiles in ~22 s and takes 2.8 GB; made by
    # hand). A float32 product with no stated precision is ONE bfloat16
    # pass on this chip, which a CPU run cannot see: every product under
    # /fluid/transforms/ has to state the highest
    import re

    from ibamr_tpu.integrators.ins import INSStaggeredIntegrator
    from ibamr_tpu.utils.hierarchy_driver import HierarchyDriver, RunConfig

    grid = StaggeredGrid(n=(64,) * 3, x_lo=(0.0,) * 3, x_up=(1.0,) * 3)
    integ = INSStaggeredIntegrator(
        grid, rho=1.0, mu=1e-3, convective_op_type="PPM",
        wall_axes=(True, True, True), wall_tangential={(0, 1, 1): 1.0})
    state = jax.eval_shape(integ.initialize)
    dt = 0.2 / 64
    drv = HierarchyDriver(integ, RunConfig(dt=dt, num_steps=2,
                                           health_interval=2))
    compiled = drv._chunk(2).lower(_on(one_chip, state), dt).compile()
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes + ma.generated_code_size_in_bytes)
    assert 0 < total < HBM_BYTES, ma
    text = compiled.as_text()
    assert "/fluid/transforms/" in text and "/fluid/convect/" in text
    products = [ln for ln in text.splitlines()
                if re.search(r"= \S+ (dot|convolution)\(", ln)
                and "/fluid/transforms/" in ln]
    assert len(products) == 24, len(products)
    for ln in products:
        assert "operand_precision={highest,highest}" in ln, ln


@pytest.mark.parametrize("walls,tangential", [
    ((False, False, False), {}),
    ((True, True, True), {(0, 1, 1): 1.0}),
], ids=["periodic", "six walls with the lid"])
def test_fused_ppm_operator_compiles_at_256(one_chip, monkeypatch, walls,
                                            tangential):
    # tg_256's convective operator since PR 29 and cavity_256's since
    # PR 33: the slab-fused PPM kernel alone at the cells' own size,
    # periodic and with the cavity's walls (Mosaic takes ~3 s each).
    # The custom call has to sit under /fluid/convect/ (the phase
    # metrics read its op_name), and the operator's intermediates in
    # VMEM: the padded path at this size has 1.14 GiB of temporaries
    # in HBM
    from ibamr_tpu.obs import deviceprof
    from ibamr_tpu.ops import convection

    # the kernel picks interpret mode from the default backend, which
    # is the CPU here: steer it in the test, not through an option
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    n, h = 256, 2 * 3.141592653589793 / 256

    def rate(u):
        with jax.named_scope("fluid"), jax.named_scope("convect"):
            return convection.convective_rate_select(
                u, (h, h, h), "ppm", walls, tangential)

    u = tuple(jax.ShapeDtypeStruct((n,) * 3, jnp.float32) for _ in range(3))
    compiled = jax.jit(rate).lower(_on(one_chip, u)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    names, phases = deviceprof.names_from_hlo(text)
    call = [i for i, name in names.items() if "pallas_call" in name]
    assert len(call) == 1 and "/fluid/convect/" in names[call[0]]
    assert "ppm_convect_fused" in names[call[0]]
    assert phases[call[0]] == "fluid/convect"
    assert compiled.memory_analysis().temp_size_in_bytes < 0.3 * 2 ** 30


def test_constraint_ib_chunk_lowers_at_the_tank_size(one_chip, monkeypatch):
    # falling_sphere_e4's program (PR 34): the driver's carried scan
    # chunk of the ConstraintIB strategy over the walled solve at the
    # configuration's own 160 x 160 x 256 with its 57,777 markers, LOWERED
    # for the TPU platform (3 s; the compile takes 95 s and 1.5 GB, made by
    # hand: 30 transform products and 9 transfer products, all
    # operand_precision={highest,highest}, one tpu_custom_call). What a
    # CPU run cannot see: on non-cubic planes of 160 x 256 the walled
    # fused PPM kernel is taken (one Mosaic custom call, not interpret
    # mode's inlined operations), and every product (the five solves' 30
    # axis transforms, the interpolation's three and the spreads' six
    # packed contractions) states the highest precision: a float32
    # product with none is ONE bfloat16 pass on this chip
    import re

    from ibamr_tpu.utils import parse_input_file
    from ibamr_tpu.utils.hierarchy_driver import HierarchyDriver, RunConfig
    from perfbench import harness

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    mod = harness.load_module(os.path.join(
        root, "examples", "ConstraintIB", "falling_sphere", "main.py"),
        "falling_sphere_for_the_chip")
    method, state = mod.build_falling_sphere_example(parse_input_file(
        os.path.join(root, "perfbench", "configs",
                     "falling_sphere_e4.input3d")))
    assert method.ins.grid.n == (160, 160, 256)
    assert state.X.shape == (57777, 3) and method.engine_name == "packed"
    # the kernel picks interpret mode from the default backend, which
    # is the CPU here: steer it in the test, not through an option
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    drv = HierarchyDriver(method, RunConfig(dt=5e-4, num_steps=2,
                                            health_interval=2))
    assert drv._carried
    text = drv._chunk(2).lower(_on(one_chip, state), 5e-4).as_text()
    assert text.count("tpu_custom_call") == 1
    products = re.findall(r"stablehlo\.dot_general.*", text)
    assert len(products) == 39, len(products)
    for ln in products:
        assert "precision = [HIGHEST, HIGHEST]" in ln, ln


def test_open_ib_chunk_lowers_at_the_duct_size(one_chip):
    # dfg3d_2z's program: the driver's carried scan chunk of the
    # target-point cylinder over the open-boundary coupled solve at the
    # configuration's own 84 x 84 x 512 with its 20,511 markers, LOWERED
    # for the TPU platform (4 s; the compile of the 2-step chunk takes
    # 182 s and 7.6 GB of temporaries, made by hand: 20 products, all
    # operand_precision={highest,highest}). What a CPU run cannot see: a
    # float32 product with no stated precision is ONE bfloat16 pass on
    # this chip, and CGS2 in bfloat16 loses the Krylov basis'
    # orthogonality, so every product of the solve (the Arnoldi
    # iteration's four, the update of the iterate, the small
    # least-squares solve's) has to state the highest
    import re

    from ibamr_tpu.utils import parse_input_file
    from ibamr_tpu.utils.hierarchy_driver import HierarchyDriver, RunConfig
    from perfbench import harness

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    mod = harness.load_module(os.path.join(
        root, "examples", "IB", "explicit", "dfg3d", "main.py"),
        "dfg3d_for_the_chip")
    integ, state = mod.build_dfg3d_example(parse_input_file(
        os.path.join(root, "perfbench", "configs", "dfg3d_2z.input3d")))
    assert integ.grid.n == (84, 84, 512) and state.X.shape == (20511, 3)
    drv = HierarchyDriver(integ, RunConfig(dt=1e-3, num_steps=2,
                                           health_interval=2))
    assert drv._carried
    text = drv._chunk(2).lower(_on(one_chip, state), 1e-3).as_text()
    products = re.findall(r"stablehlo\.dot_general.*", text)
    assert len(products) >= 5, len(products)
    for ln in products:
        assert "precision = [HIGHEST, HIGHEST]" in ln, ln
