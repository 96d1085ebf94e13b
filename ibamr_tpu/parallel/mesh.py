"""Device-mesh construction and GSPMD-sharded simulation steps.

Reference parity: SAMRAI `LoadBalancer` patch->rank assignment (S1,
SURVEY.md §2.3) — here the "patches" are equal blocks of each uniform
level, laid out over a 1D or 2D `jax.sharding.Mesh` so halo traffic rides
ICI neighbor links. Marker POSITIONS and force arithmetic stay
replicated (O(N) elementwise work, negligible next to the grid work),
but the spread/interp TRANSFERS — the actual hot path — run through the
S2 co-partitioned engine (parallel.lagrangian): owner-bucketed per-shard
marker pools, local scatter/gather, ppermute halo accumulation (the
VecScatter analog of §2.4 "irregular scatter").

The GSPMD contract: the step function is the SAME pure function as the
single-device path; only `with_sharding_constraint` pins where arrays
live. XLA then inserts `collective-permute` for the roll-stencil halos and
all-to-all/all-gather for the FFT transposes — the two communication
patterns SURVEY.md §5.7 identifies as nearest-neighbor halos + the FFT's
true long-range exchange.
"""

from __future__ import annotations

import math
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ibamr_tpu.grid import StaggeredGrid


def factor_devices(n: int, max_axes: int = 2) -> Tuple[int, ...]:
    """Near-square factorization of the device count into mesh axes
    (the analog of choosing a process grid for domain decomposition)."""
    if max_axes == 1 or n == 1:
        return (n,)
    a = int(math.isqrt(n))
    while a > 1 and n % a != 0:
        a -= 1
    if a == 1:
        return (n,)
    return (n // a, a)


def make_mesh(n_devices: Optional[int] = None,
              devices: Optional[Sequence] = None,
              axis_names: Tuple[str, ...] = ("x", "y"),
              max_axes: int = 2) -> Mesh:
    """Build a 1D/2D spatial mesh over the first ``n_devices`` devices."""
    if devices is None:
        devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    shape = factor_devices(len(devices), max_axes)
    import numpy as np
    dev_arr = np.array(devices).reshape(shape)
    return Mesh(dev_arr, axis_names[:len(shape)])


def grid_pspec(mesh: Mesh, grid_dim: int) -> P:
    """PartitionSpec sharding the leading grid axes over the mesh axes."""
    names = list(mesh.axis_names)[:grid_dim]
    return P(*names, *([None] * (grid_dim - len(names))))


def _pin(a, sharding):
    """``with_sharding_constraint`` under the ``comm`` named scope: the
    partitioner materializes its resharding collectives at these
    constraint boundaries, and the scope label is what lets
    obs/deviceprof classify that device time into the ``comm_s``
    op-class instead of leaving it anonymous. Every pin site in this
    module routes through here."""
    with jax.named_scope("comm"):
        return jax.lax.with_sharding_constraint(a, sharding)


def shard_state(state, grid: StaggeredGrid, mesh: Mesh):
    """Pin every grid-shaped array in the state pytree to the spatial
    sharding; everything else (markers, scalars) stays replicated."""
    spec = grid_pspec(mesh, grid.dim)
    sharding = NamedSharding(mesh, spec)
    gshape = tuple(grid.n)

    def constrain(a):
        if hasattr(a, "shape") and tuple(a.shape) == gshape:
            return _pin(a, sharding)
        return a

    return jax.tree_util.tree_map(constrain, state)


def _with_pencil_solvers(ins_integ, mesh: Mesh):
    """Shallow-copy an INS integrator with its spectral solves swapped for
    the pencil-decomposed distributed FFT (parallel.fftpar) — the solver
    seam of the north star's StaggeredStokesSolver interface."""
    import copy

    from ibamr_tpu.parallel.fftpar import PencilFFT

    if any(getattr(ins_integ, "wall_axes", ())):
        raise NotImplementedError(
            "sharded stepping currently supports fully periodic INS; "
            "wall-bounded fast-diagonalization solves are not yet "
            "distributed")
    pencil = PencilFFT(ins_integ.grid, mesh)
    integ2 = copy.copy(ins_integ)
    integ2.helmholtz_vel_solve = pencil.helmholtz_vel
    integ2.project = pencil.project_divergence_free
    # the fused single-device spectral path bypasses the seams above;
    # sharded stepping must go through the pencil transposes
    integ2.fused_stokes = None
    # and the slab-fused convective kernel is one device's: a
    # pallas_call does not partition, the ghost-padded path does
    if getattr(integ2, "_convective_padded", None) is not None:
        integ2._convective = integ2._convective_padded
    return integ2


# ---------------------------------------------------------------------------
# THE sharding seam (round 5, VERDICT item 7): one generic pinned-step
# wrapper + per-family PREPARE hooks + a name-dispatched entry point.
# Each integrator family contributes only what is genuinely its own —
# a solver-seam swap and/or a custom state pinner — and the wrapping,
# argument pinning, and jit live in exactly one place.
# ---------------------------------------------------------------------------

def _prepare_fluid(ins, mesh: Mesh):
    """Solver-seam prepare for a uniform INS integrator: periodic
    domains swap in the pencil-decomposed distributed FFT; wall-bounded
    domains keep their fast-diagonalization solves (dense per-axis
    eigenvector matmuls the SPMD partitioner distributes directly —
    the transform along a sharded axis becomes an MXU matmul with an
    all-gather, exactly a transpose-based distributed transform's
    communication)."""
    if any(getattr(ins, "wall_axes", ())):
        import copy

        ins = copy.copy(ins)
        ins.fused_stokes = None   # defensive: walls never set it
        return ins
    return _with_pencil_solvers(ins, mesh)


def _generic_pinned_step(integ, mesh: Mesh, prepare=None,
                         pin_state=None):
    """The one wrapper every simple (single-level) family uses: pin
    the state and every array argument to the family's sharding,
    call ``integ.step``, pin the result, jit. ``pin_state`` defaults
    to the exact-shape grid pinner (``shard_state``); rank-based
    layouts (face-complete open boundaries) pass ``_pin_rank_dim``."""
    if prepare is not None:
        integ = prepare(integ, mesh)
    if pin_state is None:
        grid = integ.grid

        def pin_state(t):
            return shard_state(t, grid, mesh)

    def step(state, *args, **kwargs):
        args = tuple(pin_state(a) for a in args)
        kwargs = {k: pin_state(v) for k, v in kwargs.items()}
        return pin_state(integ.step(pin_state(state), *args,
                                    **kwargs))

    return jax.jit(step)


def make_sharded_ins_step(integ, mesh: Mesh):
    """Jitted INS step with grid arrays sharded over ``mesh``
    (periodic: pencil-FFT solves; walls: partitioner-distributed
    fastdiag matmuls — see _prepare_fluid)."""
    return _generic_pinned_step(integ, mesh,
                                prepare=_prepare_fluid)


def _prepare_adv_diff(integ, mesh: Mesh):
    # Quantities with wall BCs keep their fast-diagonalization solves;
    # fully-periodic quantities get the pencil-FFT Helmholtz — the
    # integrator consults helmholtz_solve only where _wall_solvers[i]
    # is None, so the pencil plan is built exactly when some quantity
    # needs it (an all-wall integrator must not trip pencil
    # divisibility checks).
    import copy

    from ibamr_tpu.parallel.fftpar import PencilFFT

    integ = copy.copy(integ)
    if any(s is None for s in getattr(integ, '_wall_solvers', (None,))):
        pencil = PencilFFT(integ.grid, mesh)
        integ.helmholtz_solve = pencil.helmholtz_cc
    return integ


def make_sharded_adv_diff_step(integ, mesh: Mesh):
    """Jitted adv-diff step with grid arrays sharded over ``mesh``."""
    return _generic_pinned_step(integ, mesh,
                                prepare=_prepare_adv_diff)


def make_sharded_step(integ, mesh: Mesh, **opts):
    """THE sharding entry point (round 5, VERDICT item 7): dispatch
    any integrator to its family's sharded-step builder by class name.
    ``opts`` forward to the family builder (e.g. ``shard_window=`` for
    the composite families, ``sharded_markers=`` for IB). Integrators
    outside the table that expose ``.grid`` and ``.step`` get the
    generic exact-shape pinned wrapper — a new single-level family
    needs NO factory at all."""
    table = {
        "INSStaggeredIntegrator": make_sharded_ins_step,
        "AdvDiffSemiImplicitIntegrator": make_sharded_adv_diff_step,
        "INSVCStaggeredIntegrator": make_sharded_vc_step,
        "INSVCConservativeIntegrator": make_sharded_vc_step,
        "INSOpenIntegrator": make_sharded_open_ins_step,
        "IBOpenIntegrator": make_sharded_ib_open_step,
        "IBExplicitIntegrator": make_sharded_ib_step,
        "TwoLevelIBINS": make_sharded_two_level_ib_step,
        "MultiLevelAdvDiff": make_sharded_multilevel_step,
        "MultiLevelINS": make_sharded_multilevel_ins_step,
        "MultiLevelIBINS": make_sharded_multilevel_ib_step,
        "MultiBoxDynamicAdvDiff": make_sharded_multibox_step,
        "TwoLevelSmagorinskyINS": make_sharded_les_two_level_step,
        "CIBMethod": make_sharded_cib_constraint,
    }
    # walk the MRO so SUBCLASSES of a registered family inherit its
    # prepare seam (a name-only match would silently drop e.g. the
    # pencil-solver swap for a user's INSStaggeredIntegrator subclass)
    for klass in type(integ).__mro__:
        builder = table.get(klass.__name__)
        if builder is not None:
            return builder(integ, mesh, **opts)
    if hasattr(integ, "grid") and hasattr(integ, "step"):
        return _generic_pinned_step(integ, mesh, **opts)
    raise TypeError(
        f"no sharded-step builder for {type(integ).__name__}; expose "
        f".grid/.step for the generic wrapper or register a family "
        f"builder")


def make_sharded_multilevel_step(ml, mesh: Mesh):
    """Level-by-level AMR parallelism (S4): every level of a
    :class:`~ibamr_tpu.amr_multilevel.MultiLevelAdvDiff` hierarchy is
    sharded over the SAME device mesh (each level is a dense box array,
    so equal-block GSPMD sharding balances each level independently —
    the reference's per-level LoadBalancer pass). Coarse-fine transfer
    (quadratic ghost gathers, restriction, reflux slabs) crosses the
    level shardings as XLA-inserted collectives — the Refine/Coarsen
    schedule analog (SURVEY.md §2.3 S4)."""
    import copy

    dim = len(ml.levels[0].grid.n)
    ml = copy.copy(ml)
    # pin the level-synchronization arrays (CF ghost fills, post-update
    # level states) replicated: these are the hierarchy's boundary
    # exchanges, and leaving their sharding to SPMD propagation
    # miscompiles (wrong values, observed on the CPU mesh); flux and
    # stencil compute between the pins stays sharded
    ml.sync_sharding = NamedSharding(mesh, P(*([None] * dim)))

    shardings = []
    for spec in ml.levels:
        pspec = grid_pspec(mesh, len(spec.grid.n))
        shardings.append(NamedSharding(mesh, pspec))

    def constrain(Qs):
        return tuple(_pin(q, s)
                     for q, s in zip(Qs, shardings))

    def step(Qs, dt):
        return constrain(ml.step(constrain(tuple(Qs)), dt))

    return jax.jit(step)


def _wrap_sharded_markers(base_ib, grid: StaggeredGrid, mesh: Mesh,
                          marker_cap: Optional[int] = None,
                          marker_slack: float = 2.0,
                          warn_strategy: bool = False):
    """Build the S2 facade routing an IBMethod's transfers through the
    co-partitioned engine (parallel.lagrangian) on ``grid`` — markers
    owner-bucketed onto the mesh every step, local scatter/gather,
    ppermute halos. Returns None when the facade cannot engage —
    silently for a non-IBMethod strategy unless ``warn_strategy``
    (GSPMD is the intended route for IBFE/plugin couplings; explicit
    opt-ins pass True to learn their request was not honored), and
    with a warning when the (grid, mesh) geometry fails the engine's
    constraints (axis divisibility, halo >= local block) — callers
    then keep the GSPMD-resolved path. Shared by the uniform
    flagship step and the sharded-window composite step (S2 at the
    FINE level)."""
    from ibamr_tpu.integrators.ib import IBMethod
    from ibamr_tpu.parallel.lagrangian import ShardedInteraction

    if not isinstance(base_ib, IBMethod):
        # the GSPMD-resolved path is the INTENDED route for IBFE
        # quadrature couplings and custom plugins, so the default
        # (make_sharded_ib_step's sharded_markers=True) stays silent;
        # an EXPLICIT opt-in (the composite paths) warns so the user
        # learns their request was not honored
        if warn_strategy:
            import warnings

            warnings.warn(
                "sharded markers disabled: the S2 facade understands "
                f"marker-point IBMethod transfers only (got "
                f"{type(base_ib).__name__}); keeping the "
                "GSPMD-resolved path")
        return None
    try:
        ShardedInteraction(grid, mesh, kernel=base_ib.kernel, cap=8)
    except ValueError as e:
        import warnings

        warnings.warn(
            f"sharded markers disabled for this (grid, mesh): {e}")
        return None

    engines = {}

    def get_engine(N):
        # keyed by marker count: a retrace with a different N
        # must not reuse a capacity sized for the old N
        if N not in engines:
            engines[N] = ShardedInteraction(
                grid, mesh, kernel=base_ib.kernel, n_markers=N,
                cap=marker_cap, slack=marker_slack)
        return engines[N]

    class _ShardedIB:
        """IBMethod facade routing transfers through the S2 engine;
        force evaluation stays with the base method."""

        def __init__(self):
            self.specs = base_ib.specs
            self.kernel = base_ib.kernel

        def compute_force(self, X, U, t):
            return base_ib.compute_force(X, U, t)

        def prepare(self, X, mask):
            return get_engine(X.shape[0]).buckets(X, mask)

        def interpolate_velocity(self, u, g, X, mask, ctx=None):
            eng = get_engine(X.shape[0])
            if ctx is None:
                ctx = eng.buckets(X, mask)
            return eng.interpolate_vel(u, X, weights=mask, b=ctx)

        def spread_force(self, F, g, X, mask, ctx=None):
            eng = get_engine(X.shape[0])
            if ctx is None:
                ctx = eng.buckets(X, mask)
            return eng.spread_vel(F, X, weights=mask, b=ctx)

    return _ShardedIB()


def make_sharded_ib_step(integ, mesh: Mesh,
                         sharded_markers: Optional[bool] = None,
                         marker_cap: Optional[int] = None,
                         marker_slack: float = 2.0):
    """Jitted coupled IB step (interp -> force -> spread -> fluid solve ->
    correct) with the Eulerian state sharded over ``mesh``. This is the
    whole-timestep SPMD program of SURVEY.md §3.2's device-boundary note.

    With ``sharded_markers`` (default), the spread/interp transfers run
    through the S2 co-partitioned engine (parallel.lagrangian): markers
    are owner-bucketed onto the mesh every step and each device scatters
    /gathers only its own ~N/P markers, with ppermute halo exchange —
    instead of replicated markers + GSPMD-resolved transfers (round-1
    behavior, kept via ``sharded_markers=False``). Positions and forces
    stay replicated (O(N) arithmetic is negligible next to the grid
    work; SURVEY.md §2.3 S2)."""
    import copy

    grid = integ.ins.grid
    integ = copy.copy(integ)
    integ.ins = _prepare_fluid(integ.ins, mesh)

    # None = AUTO (default): use the S2 engine when eligible, fall back
    # silently (GSPMD is the intended route for IBFE/plugin strategies).
    # True = EXPLICIT request: warn if it cannot be honored.
    if sharded_markers is None or sharded_markers:
        wrapped = _wrap_sharded_markers(
            integ.ib, grid, mesh, marker_cap, marker_slack,
            warn_strategy=sharded_markers is True)
        if wrapped is not None:
            integ.ib = wrapped

    def pin_ib(st):
        if hasattr(st, "ins"):
            return st._replace(ins=shard_state(st.ins, grid, mesh))
        return st

    return _generic_pinned_step(integ, mesh, pin_state=pin_ib)


def make_sharded_two_level_ib_step(integ, mesh: Mesh,
                                   shard_window: bool = False,
                                   sharded_markers: bool = False,
                                   marker_cap: Optional[int] = None,
                                   marker_slack: float = 2.0):
    """Jitted composite two-level INS/IB step (S4 for the FLAGSHIP
    path) with the COARSE level sharded over ``mesh`` and the fine
    window either replicated (default) or ALSO sharded over the same
    mesh (``shard_window=True``), with explicit pins at every level
    crossing.

    Cost model for the default (window-replication): a SMALL fine
    window — it tracks the immersed structure (box_from_markers), so
    its cell count is O(structure volume), typically 5-25% of the
    coarse level's and often far less — does its per-step work
    (stencils + a fast-diagonalization solve whose dense axis matmuls
    saturate a single chip's MXU at window sizes <= ~128^3) without
    needing the mesh, and sharding it would put a latency-bound
    collective inside EVERY CF crossing (ghost fill, restriction,
    interface flux sync, and each FGMRES iteration's operator+precond
    application — ~m*restarts per projection).

    ``shard_window=True`` is the AT-SCALE mode (S4 depth, VERDICT
    round 3 missing #2): when the refined window carries the majority
    of the FLOPs (a 2x-refined window over a large structure has 2^dim
    times the cell density of the coarse level), replication makes the
    window the serial bottleneck and caps weak scaling. Sharding it
    divides the window stencils, the fastdiag dense axis matmuls
    (distributed by the SPMD partitioner exactly like the wall-bounded
    transforms), and the fine-resolution spread/interp scatter targets
    by the mesh size — the reference's per-level LoadBalancer behavior
    (every level distributed independently, SURVEY.md §2.3 S4). The
    CF crossings then carry the halo/restriction communication XLA
    inserts — O(window surface), the same asymptotics as the
    reference's Refine/Coarsen schedules.

    ``sharded_markers=True`` additionally routes the FINE-level marker
    transfers through the S2 owner-bucketed engine on the fine grid
    (local scatter/gather + ppermute halos instead of GSPMD-resolved
    transfers against the sharded window) — the full 'every level AND
    the transfers distributed' composition; pairs naturally with
    ``shard_window=True``. Ineligible strategies/geometries fall back
    with a warning.

    Either way the pins (CompositeProjection._pin_c/_pin_f) keep the
    SPMD partitioner from mis-propagating through the mixed
    scatter/gather level crossings (the round-2 wrong-values miscompile
    this replaces; same fix pattern as make_sharded_multilevel_step's
    sync pins). Equality with the single-device path at rtol 1e-12 for
    BOTH modes (1e-11 with S2 markers — segment-sum ordering) is
    pinned by tests/test_parallel.py."""
    import copy

    grid = integ.grid
    dim = grid.dim
    spatial = NamedSharding(mesh, grid_pspec(mesh, dim))
    replicated = NamedSharding(mesh, P())
    window_sh = spatial if shard_window else replicated

    integ = copy.copy(integ)
    integ.core = copy.copy(integ.core)
    proj = copy.copy(integ.core.proj)
    proj.level_sharding = spatial
    proj.window_sharding = window_sh
    proj.build_dense_coarse_solver()   # host-side: not legal mid-trace
    integ.core.proj = proj

    if sharded_markers:
        # S2 AT THE FINE LEVEL (the second half of VERDICT round 3
        # missing #2: "fine-level marker transfers over the mesh"):
        # owner-bucket the markers over the mesh against the FINE grid
        # and run local scatter/gather + ppermute halos there, instead
        # of GSPMD-resolved transfers against the sharded window.
        # Composes with shard_window (the natural pairing); ineligible
        # (fine grid, mesh) geometries fall back with a warning.
        wrapped = _wrap_sharded_markers(
            integ.ib, integ.fine_grid, mesh, marker_cap, marker_slack,
            warn_strategy=True)
        if wrapped is not None:
            integ.ib = wrapped

    def pin_state(st):
        # STRUCTURAL classification (coarse level vs everything else):
        # a shape heuristic would misclassify fine-window arrays
        # whenever ratio * box.shape == grid.n
        def pin(a, sh):
            return _pin(a, sh)

        fluid = st.fluid._replace(
            uc=tuple(pin(c, spatial) for c in st.fluid.uc),
            uf=tuple(pin(f, window_sh) for f in st.fluid.uf))
        return st._replace(fluid=fluid,
                           X=pin(st.X, replicated),
                           U=pin(st.U, replicated),
                           mask=pin(st.mask, replicated))

    def step(state, dt):
        return pin_state(integ.step(pin_state(state), dt))

    return jax.jit(step)


def _shard_multilevel_proj(core, mesh: Mesh, shard_boxes: bool = False):
    """Copy an L-level core integrator with its composite projection
    pinned for GSPMD: root level spatially sharded, box levels
    replicated by default (same cost model as
    make_sharded_two_level_ib_step — the boxes are usually the small
    levels) or ALSO sharded (``shard_boxes=True``, the at-scale S4
    depth mode: every level distributed independently, the reference's
    per-level LoadBalancer behavior)."""
    import copy

    core = copy.copy(core)
    proj = copy.copy(core.proj)
    spatial = NamedSharding(mesh, grid_pspec(mesh, core.grid.dim))
    proj.root_sharding = spatial
    proj.box_sharding = spatial if shard_boxes else NamedSharding(mesh,
                                                                  P())
    proj.build_dense_root_solver()    # host-side: not legal mid-trace
    core.proj = proj
    return core


def _pin_multilevel_us(us, spatial, box_sh):
    pin = _pin
    return tuple(
        tuple(pin(c, spatial if l == 0 else box_sh) for c in lev)
        for l, lev in enumerate(us))


def make_sharded_multilevel_ins_step(integ, mesh: Mesh,
                                     shard_boxes: bool = False):
    """Jitted L-level composite INS step
    (:class:`~ibamr_tpu.amr_ins_multilevel.MultiLevelINS`) with the
    root level sharded over ``mesh`` and every box level replicated
    (default) or every level sharded over the same mesh
    (``shard_boxes=True``), with explicit pins at every level crossing
    (S4 for the L-level FLUID hierarchy — the arbitrary-depth
    extension of make_sharded_two_level_ib_step; see its docstring for
    the replicate-vs-shard cost model)."""
    integ = _shard_multilevel_proj(integ, mesh, shard_boxes=shard_boxes)
    spatial = NamedSharding(mesh, grid_pspec(mesh, integ.grid.dim))
    box_sh = spatial if shard_boxes else NamedSharding(mesh, P())

    def pin_state(st):
        return st._replace(us=_pin_multilevel_us(st.us, spatial, box_sh))

    def step(state, dt):
        return pin_state(integ.step(pin_state(state), dt))

    return jax.jit(step)


def make_sharded_multilevel_ib_step(integ, mesh: Mesh,
                                    shard_boxes: bool = False):
    """Jitted L-level composite INS/IB step
    (:class:`~ibamr_tpu.amr_ins_multilevel.MultiLevelIBINS`): root
    level sharded, box levels replicated (default) or sharded
    (``shard_boxes=True`` — every level distributed, the S4-depth
    mode), markers replicated, pins at every level crossing. Removes
    the round-3 scope line "the L-level composite INS/IB runs
    replicated under sharding". Equality with the single-device step
    for both modes is pinned by tests/test_parallel.py."""
    import copy

    integ = copy.copy(integ)
    integ.core = _shard_multilevel_proj(integ.core, mesh,
                                        shard_boxes=shard_boxes)
    spatial = NamedSharding(mesh, grid_pspec(mesh, integ.grid.dim))
    replicated = NamedSharding(mesh, P())
    box_sh = spatial if shard_boxes else replicated
    pin = _pin

    def pin_state(st):
        fluid = st.fluid._replace(
            us=_pin_multilevel_us(st.fluid.us, spatial, box_sh))
        return st._replace(fluid=fluid,
                           X=pin(st.X, replicated),
                           U=pin(st.U, replicated),
                           mask=pin(st.mask, replicated))

    def step(state, dt):
        return pin_state(integ.step(pin_state(state), dt))

    return jax.jit(step)


def place_state(state, grid: StaggeredGrid, mesh: Mesh):
    """Device-put the initial state under the spatial sharding (so the
    first step doesn't start from a single-device layout)."""
    spec = grid_pspec(mesh, grid.dim)
    sharding = NamedSharding(mesh, spec)
    replicated = NamedSharding(mesh, P())
    gshape = tuple(grid.n)

    def put(a):
        a = jnp.asarray(a)
        if tuple(a.shape) == gshape:
            return jax.device_put(a, sharding)
        return jax.device_put(a, replicated)

    return jax.tree_util.tree_map(put, state)


# ---- fleet lane sharding (PR 16) ------------------------------------
# The SECOND scaling axis: where the spatial meshes above split ONE
# simulation's grid over D devices, a lane mesh splits a B-lane fleet
# (utils.lanes stacked state, lane axis ALWAYS axis 0) across devices —
# B/D whole lanes per device, zero cross-device traffic inside a step
# (lanes are independent), so a pod runs B×D-lane ensembles with the
# per-lane quarantine/dt machinery of HierarchyDriver untouched. The
# bitwise contract (sharded fleet == replicated fleet, f64) is pinned
# by tests/test_fleet_mesh.py.

LANE_AXIS = "lanes"


def make_lane_mesh(n_devices: Optional[int] = None,
                   devices: Optional[Sequence] = None) -> Mesh:
    """1-D mesh over the lane (batch) axis of a stacked fleet state."""
    if devices is None:
        devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    import numpy as np
    return Mesh(np.array(devices), (LANE_AXIS,))


def lane_pspec(mesh: Mesh) -> P:
    """PartitionSpec sharding axis 0 (the lane axis) over the lane mesh."""
    return P(mesh.axis_names[0])


def _check_lane_divisible(lanes: int, mesh: Mesh) -> None:
    d = int(mesh.devices.size)
    if lanes % d != 0:
        raise ValueError(
            f"fleet of {lanes} lanes does not divide the {d}-device "
            f"lane mesh evenly (lanes % devices must be 0 so every "
            f"device owns whole lanes)")


def shard_lanes(state, mesh: Mesh):
    """Constraint-pin every leaf's lane axis (axis 0) to the lane mesh.

    ``utils.lanes.stack_lanes`` gives EVERY leaf — scalars included — a
    leading (B,) lane axis, so the pin is unconditional; trailing axes
    stay unsharded (each device owns whole lanes)."""
    sharding = NamedSharding(mesh, lane_pspec(mesh))

    def constrain(a):
        if hasattr(a, "ndim") and a.ndim >= 1:
            return _pin(a, sharding)
        return a

    return jax.tree_util.tree_map(constrain, state)


def place_lanes(state, mesh: Mesh):
    """Device-put a lane-stacked fleet state under the lane sharding
    (so the first chunk doesn't start from a single-device layout, and
    so sharded checkpoints record the lane-sharded layout)."""
    sharding = NamedSharding(mesh, lane_pspec(mesh))
    replicated = NamedSharding(mesh, P())
    leaves = [l for l in jax.tree_util.tree_leaves(state)
              if hasattr(l, "shape") and getattr(l, "ndim", 0) >= 1]
    if leaves:
        _check_lane_divisible(int(leaves[0].shape[0]), mesh)

    def put(a):
        a = jnp.asarray(a)
        if a.ndim >= 1:
            return jax.device_put(a, sharding)
        return jax.device_put(a, replicated)

    return jax.tree_util.tree_map(put, state)


def make_sharded_vc_step(integ, mesh: Mesh):
    """Jitted variable-coefficient (multiphase) INS step with every
    grid field sharded over ``mesh`` — S1 for the P22 multiphase
    integrators (`INSVCStaggeredIntegrator` incl. the open-outlet
    tank / conservative form, walls or periodic). Everything inside
    the step is roll-stencil, CG (psum reductions), multigrid V-cycle,
    Godunov advection, and level-set reinitialization — all
    GSPMD-compatible. Equality pinned by tests/test_parallel.py."""
    return _generic_pinned_step(integ, mesh)


def _pin_rank_dim(mesh: Mesh, dim: int):
    """Pin every rank-``dim`` array of a pytree to the spatial sharding
    (the face-COMPLETE open-boundary layouts have +1 extents, so an
    exact-shape match cannot classify them; rank works because these
    states carry only grid-shaped fields at that rank)."""
    sharding = NamedSharding(mesh, grid_pspec(mesh, dim))

    def pin(a):
        if hasattr(a, "ndim") and a.ndim == dim:
            return _pin(a, sharding)
        return a

    def pin_state(st):
        return jax.tree_util.tree_map(pin, st)

    return pin_state


def make_sharded_multibox_step(mb, mesh: Mesh,
                               costs=None,
                               X=None, w_marker: float = 4.0):
    """Workload-BALANCED box->device placement for the K-window
    multi-box hierarchy (round 5, VERDICT item 4 — the real
    ``LoadBalancer::loadBalanceBoxLevel`` analog [U], closing S3):

    - per-window costs from the S3 cost model (fine cells +
      w_marker x markers, ``parallel.workload.box_costs``) unless
      given explicitly;
    - greedy LPT bin-packing assigns boxes to devices UNEVENLY
      (``parallel.workload.lpt_assign``) — a hot window (marker
      cluster) gets a device to itself while cold windows share;
    - the jitted step gathers the boxes into a device-major padded
      slot pool sharded over the mesh, runs all fine-window substeps
      (the dominant work) device-parallel via vmap against the
      pristine coarse predictor, then applies the cheap coarse
      restriction/reflux writebacks sequentially in box order — the
      SAME read-then-write (Jacobi) ordering the plain step uses, so
      1-vs-8 equality holds at stencil tolerance at EVERY window
      separation (tests/test_workload.py).

    Returns the jitted ``step(state, dt)``; ``step.placement()``
    yields the assignment/per-device loads for work-spread checks and
    ``step.rebuild(state)`` re-places after a host-side regrid moved
    the windows (placement is never checked on the hot path — no
    device sync per step).

    ``costs`` overrides the cost model for the INITIAL layout only; a
    ``rebuild`` after a regrid always re-derives costs from the new
    origins (an explicit stale-cost placement would silently defeat
    the balancing the rebuild exists to restore).
    """
    import numpy as _np

    from ibamr_tpu.parallel.workload import box_costs, lpt_assign

    D = int(_np.prod(mesh.devices.shape))
    K = mb.K
    win = mb.win
    state_holder = {"explicit_costs": costs}

    def build(lo_np):
        c = state_holder.pop("explicit_costs", None)
        if c is None:
            c = box_costs(lo_np, mb.win.box_shape, mb.grid,
                          ratio=mb.ratio, X=X, w_marker=w_marker)
        device_of_box, load = lpt_assign(c, D)
        M = int(max(1, _np.bincount(device_of_box,
                                    minlength=D).max()))
        slot_box = _np.zeros(D * M, dtype=_np.int64)   # pad: box 0
        slot_of_box = _np.zeros(K, dtype=_np.int64)
        fill = _np.zeros(D, dtype=_np.int64)
        for k in range(K):
            d = int(device_of_box[k])
            s = d * M + int(fill[d])
            fill[d] += 1
            slot_box[s] = k
            slot_of_box[k] = s
        return c, device_of_box, load, M, slot_box, slot_of_box

    placement = None

    def make(lo_np):
        nonlocal placement
        c, device_of_box, load, M, slot_box, slot_of_box = build(lo_np)
        placement = {
            "costs": c, "device_of_box": device_of_box,
            "load": load, "slots_per_device": M,
        }
        slot_box_j = jnp.asarray(slot_box)
        slot_of_box_j = jnp.asarray(slot_of_box)
        pool_sh = NamedSharding(mesh, P(mesh.axis_names[0]
                                        if len(mesh.axis_names) == 1
                                        else mesh.axis_names))
        replicated = NamedSharding(mesh, P())
        pin = _pin

        def step(state, dt):
            Qc = pin(state.Qc, replicated)
            Qf = pin(state.Qf, replicated)
            lo = pin(state.lo, replicated)
            Fc, Qc_new = win._coarse_advance(Qc, dt)
            Qf_slots = pin(jnp.take(Qf, slot_box_j, axis=0), pool_sh)
            lo_slots = jnp.take(lo, slot_box_j, axis=0)
            sub = jax.vmap(
                lambda qf, l: win._fine_substeps(Qc, Qc_new, qf, l,
                                                 dt))
            Qf_new_s, acc_lo_s, acc_hi_s = sub(Qf_slots, lo_slots)
            Qf_new_s = pin(Qf_new_s, pool_sh)
            for k in range(K):            # cheap, exact, box order
                s = int(slot_of_box[k])
                Qc_new = win._restrict_and_reflux(
                    Qc_new, Qf_new_s[s], lo[k], Fc,
                    [a[s] for a in acc_lo_s],
                    [a[s] for a in acc_hi_s], dt)
            Qf_new = pin(jnp.take(Qf_new_s, slot_of_box_j, axis=0),
                         replicated)
            from ibamr_tpu.amr_multibox import MultiBoxState

            return MultiBoxState(Qc=pin(Qc_new, replicated),
                                 Qf=Qf_new, lo=lo)

        return jax.jit(step)

    _compiled = [None]

    def stepper(state, dt):
        # placement built lazily on FIRST call; never re-checked on
        # the hot path (np.asarray(state.lo) would force a device
        # sync per step). Regrid callers invalidate via rebuild().
        if _compiled[0] is None:
            _compiled[0] = make(_np.asarray(state.lo))
        return _compiled[0](state, dt)

    def rebuild(state):
        """Re-place after a host-side regrid moved the windows."""
        _compiled[0] = make(_np.asarray(state.lo))

    def get_placement():
        return placement

    stepper.placement = get_placement
    stepper.rebuild = rebuild
    return stepper


def make_sharded_les_two_level_step(les, mesh: Mesh):
    """Jitted composite-window LES step (round 5, VERDICT item 3b
    sharded): the coarse level sharded over ``mesh``, the refined
    window replicated (the default cost model of
    make_sharded_two_level_ib_step), with the composite projection's
    level-crossing pins installed. The per-level eddy-stress forces
    are pure stencil work and follow their level's sharding."""
    import copy

    grid = les.grid
    spatial = NamedSharding(mesh, grid_pspec(mesh, grid.dim))
    replicated = NamedSharding(mesh, P())

    les = copy.copy(les)
    les.core = copy.copy(les.core)
    proj = copy.copy(les.core.proj)
    proj.level_sharding = spatial
    proj.window_sharding = replicated
    proj.build_dense_coarse_solver()   # host-side: not legal mid-trace
    les.core.proj = proj

    pin = _pin

    def pin_state(st):
        return st._replace(
            uc=tuple(pin(c, spatial) for c in st.uc),
            uf=tuple(pin(f, replicated) for f in st.uf))

    def step(state, dt):
        return pin_state(les.step(pin_state(state), dt))

    return jax.jit(step)


def make_sharded_cib_constraint(cibm, mesh: Mesh):
    """Jitted CIB prescribed-kinematics solve with the Eulerian fields
    of every nested mobility application (spread force, Stokes
    velocity) sharded over ``mesh`` and the marker arrays replicated —
    S1 through the CIB composition (round 5, VERDICT item 3c sharded;
    works for both the periodic and the WALLED domain, whose saddle
    FGMRES smoothers/reductions are the same GSPMD-compatible ops as
    the open-boundary path's)."""
    import copy

    spatial = NamedSharding(mesh, grid_pspec(mesh, cibm.grid.dim))
    replicated = NamedSharding(mesh, P())
    pin = _pin

    cibm = copy.copy(cibm)
    cibm.field_pin = lambda a: pin(a, spatial)

    def solve(X, U):
        X = pin(X, replicated)
        U = pin(U, replicated)
        lam, FT, info = cibm.solve_constraint(X, U)
        return pin(lam, replicated), pin(FT, replicated), info

    return jax.jit(solve)


def make_sharded_open_ins_step(integ, mesh: Mesh):
    """Jitted inflow/outflow (open-boundary) INS step sharded over
    ``mesh`` — S1 for the external-flow configuration: the coupled
    saddle solve's red-black smoothers are masked elementwise ops and
    its FGMRES reductions are psums, all GSPMD-compatible. Equality
    with the single-device step is pinned by tests/test_parallel.py."""
    return _generic_pinned_step(
        integ, mesh, pin_state=_pin_rank_dim(mesh, len(integ.n)))


def make_sharded_ib_open_step(integ, mesh: Mesh):
    """Jitted coupled IB step over the OPEN-BOUNDARY fluid
    (integrators.ib_open) with the Eulerian state sharded over
    ``mesh`` and markers replicated — flow past an immersed structure
    on the device mesh."""
    pin_fluid = _pin_rank_dim(mesh, len(integ.ins.n))
    replicated = NamedSharding(mesh, P())
    pin = _pin

    def pin_all(st):
        if hasattr(st, "fluid"):
            return st._replace(fluid=pin_fluid(st.fluid),
                               X=pin(st.X, replicated),
                               U=pin(st.U, replicated),
                               mask=pin(st.mask, replicated))
        return st        # scalars/aux passed through step args

    return _generic_pinned_step(integ, mesh, pin_state=pin_all)
