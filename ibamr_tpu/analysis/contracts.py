"""The artifact registry and its budgets (``GRAPH_BUDGETS.json``).

A *contract* pins one named compiled artifact — the solo step, the
fused spectral substep per dtype, each spread/interp engine, the
driver's scanned chunk, the lane-masked fleet chunk, the donated step,
the per-lane capsule fetch — to the budget-comparable slice of its
:func:`~ibamr_tpu.analysis.graph_census.graph_census`. Budgets live in
``GRAPH_BUDGETS.json`` at the repo root and are versioned with the
code: a refactor that adds a scatter, un-fuses an FFT, sneaks a host
transfer into the scan, widens a dtype, or silently drops donation
fails the gate (``tools/graph_audit.py``, exit 2) and the tier-1 pin
(``tests/test_graph_contracts.py``) on the same counting rules.

Measurement runs under ``jax.experimental.disable_x64()`` so the
numbers are the PRODUCTION (x64-off) graph regardless of caller
config — the pytest conftest enables x64 globally, and budgets must
not depend on which harness measured them.

Update workflow (see docs/ANALYSIS.md): change code, run
``python tools/graph_audit.py`` — exit 0 means no drift, exit 1 means
you improved a budgeted metric (run with ``--tighten`` to ratchet the
budget down and commit the diff), exit 2 names the regressed metrics.
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass, field
from typing import Callable, Dict, Optional, Tuple

from ibamr_tpu.analysis.graph_census import (
    BUDGET_MAX_METRICS,
    BUDGET_MIN_METRICS,
    budget_metrics,
    graph_census,
)

REPO_ROOT = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))
BUDGET_PATH = os.path.join(REPO_ROOT, "GRAPH_BUDGETS.json")

# shared flagship-miniature shape: big enough that every structural
# feature of the graph exists (buckets, packing, scan, probe fusion),
# small enough that the whole registry compiles in seconds on CPU.
_N, _N_LAT, _N_LON = 16, 8, 16
_DT = 5e-5


def _shell(engine="packed", spectral_dtype=None):
    from ibamr_tpu.models.shell3d import build_shell_example

    return build_shell_example(
        n_cells=_N, n_lat=_N_LAT, n_lon=_N_LON, radius=0.25,
        aspect=1.2, stiffness=1.0, rest_length_factor=0.75, mu=0.05,
        use_fast_interaction=engine, spectral_dtype=spectral_dtype)


def _unwrap(jitted):
    """The raw python callable behind a ``jax.jit`` wrapper, so the
    census controls jit/donation itself instead of nesting pjit."""
    return getattr(jitted, "__wrapped__", jitted)


# ---------------------------------------------------------------------------
# artifact builders — each returns (fn, args, donate_argnums)
# ---------------------------------------------------------------------------

def _build_solo_step(spectral_dtype=None):
    integ, state = _shell(spectral_dtype=spectral_dtype)
    return (lambda s: integ.step(s, _DT)), (state,), ()


def _build_fused_substep(spectral_dtype=None):
    from ibamr_tpu.solvers import fft as _fft

    integ, state = _shell(spectral_dtype=spectral_dtype)
    ins = integ.ins
    dx = ins.grid.dx
    alpha, beta = ins.rho / _DT, -0.5 * ins.mu

    def sub(rhs):
        return _fft.helmholtz_project_periodic(
            rhs, dx, alpha=alpha, beta=beta,
            pinc_coeffs=(alpha, beta), spectral_dtype=spectral_dtype)

    return sub, (state.ins.u,), ()


def _build_transfer(engine, piece):
    import jax.numpy as jnp

    integ, state = _shell(engine=engine)
    ib = integ.ib
    grid = integ.ins.grid
    X, mask = state.X, state.mask
    if piece == "spread":
        F = jnp.zeros_like(X)

        def spread(Xa, Fa, m):
            ctx = ib.prepare(Xa, m)
            return ib.spread_force(Fa, grid, Xa, m, ctx=ctx)

        return spread, (X, F, mask), ()
    u = state.ins.u

    def interp(ua, Xa, m):
        ctx = ib.prepare(Xa, m)
        return ib.interpolate_velocity(ua, grid, Xa, m, ctx=ctx)

    return interp, (u, X, mask), ()


def _driver(integ, lanes=None, donate=False, lane_mesh=None, remat=None):
    from ibamr_tpu.utils.health import HealthProbe
    from ibamr_tpu.utils.hierarchy_driver import HierarchyDriver, RunConfig

    cfg = RunConfig(dt=_DT, num_steps=4, health_interval=2,
                    donate=donate, remat=remat)
    return HierarchyDriver(integ, cfg, lanes=lanes, lane_mesh=lane_mesh,
                           health_probe=HealthProbe.for_integrator(integ))


# -- gradient artifacts (PR 19): the adjoint-at-primal-cost pins ------------

def _build_grad_substep(spectral_dtype=None):
    # full jax.vjp round trip of the fused spectral substep. The custom
    # VJP rides the SAME plan (conjugate symbol application), so the
    # whole forward+backward pass is pinned at <= 2x the primal's
    # batched FFT calls (fft_ops 4 vs the primal's 2) — the headline
    # "adjoint at primal cost" budget.
    import jax
    import jax.numpy as jnp

    sub, (rhs,), _ = _build_fused_substep(spectral_dtype=spectral_dtype)
    out_shape = jax.eval_shape(sub, rhs)
    ct = jax.tree_util.tree_map(
        lambda s: jnp.ones(s.shape, s.dtype), out_shape)

    def grad_sub(r, c):
        out, vjpf = jax.vjp(sub, r)
        return out, vjpf(c)

    return grad_sub, (rhs, ct), ()


def _build_grad_transfer(piece):
    # the packed-transfer BACKWARD pass in isolation (the bwd rule the
    # custom VJP installs), with the buckets closure-captured exactly as
    # reverse-mode residuals are: zero bucket preps in the graph, and
    # for grad_spread zero scatter primitives — d(spread) is an interp
    # through the SAME PackedBuckets (gather-only overflow merge
    # included). grad_interp's d/df IS the primal spread (the adjoint
    # of a gather is a scatter); its budget pins that no NEW scatter
    # shapes appear beyond the primal set.
    import jax
    import jax.numpy as jnp

    from ibamr_tpu.ops import interaction_packed as ip

    integ, state = _shell(engine="packed")
    eng = integ.ib.fast
    X, mask = state.X, state.mask
    b = eng.buckets(X, mask)
    nd = (eng.geom, eng.grid, 0, eng.kernel,
          jax.lax.Precision.HIGHEST, None)
    if piece == "spread":
        F = jnp.zeros(X.shape[0], X.dtype)
        g = jnp.zeros(eng.grid.n, X.dtype)

        def spread_bwd(Fa, Xa, ga):
            return ip._spread_bwd(*nd, (b, Fa, Xa), ga)[1:]

        return spread_bwd, (F, X, g), ()
    f = jnp.zeros(eng.grid.n, X.dtype)
    ct = jnp.zeros(X.shape[0], X.dtype)

    def interp_bwd(fa, Xa, ca):
        return ip._interp_bwd(*nd, (b, fa, Xa), ca)[1:]

    return interp_bwd, (f, X, ct), ()


def _build_grad_chunk():
    # reverse mode through the driver's remat-checkpointed scan chunk
    # (RunConfig(remat=), health probe fused in): the design loop's
    # unit of differentiation. host_transfers_in_scan == 0 and
    # f64_widenings == 0 are the pins — the cotangent scan must stay as
    # device-resident and dtype-clean as the primal one.
    import jax
    import jax.numpy as jnp

    integ, state = _shell()
    drv = _driver(integ, remat="dots")
    chunk = _unwrap(drv._chunk(4))

    def grad_chunk(st, dt):
        def loss(s):
            leaves = jax.tree_util.tree_leaves(chunk(s, dt))
            return sum(jnp.sum(l) for l in leaves
                       if jnp.issubdtype(l.dtype, jnp.inexact))

        # allow_int: the state pytree carries int32 counters (step
        # index, refresh bookkeeping) that get symbolic-zero cotangents
        return jax.grad(loss, allow_int=True)(st)

    return grad_chunk, (state, _DT), ()


def _build_solo_chunk():
    # the driver's scanned chunk WITH the fused health probe — the
    # scan body is where a stray host transfer would be catastrophic
    # (one D2H per step instead of one per chunk)
    integ, state = _shell()
    drv = _driver(integ)
    chunk = _unwrap(drv._chunk(4))
    return chunk, (state, _DT), ()


def _build_donated_chunk():
    # cfg.donate=True chunk: the whole-step in-place update. The budget
    # pins donated_args >= 1 — donation is a REQUEST; this artifact is
    # where it is verified against the compiled alias table.
    integ, state = _shell()
    drv = _driver(integ, donate=True)
    chunk = _unwrap(drv._chunk(4))
    return chunk, (state, _DT), (0,)


def _build_fleet_chunk():
    import jax.numpy as jnp

    from ibamr_tpu.utils import lanes as _lanes

    integ, state = _shell()
    drv = _driver(integ, lanes=2)
    chunk = _unwrap(drv._chunk(2))
    stacked = _lanes.stack_lanes([state, state])
    dt_vec = jnp.full((2,), _DT, dtype=jnp.float32)
    alive = jnp.ones((2,), dtype=bool)
    return chunk, (stacked, dt_vec, alive), ()


def _attach_contract_ledger():
    """Attach a live run ledger for a telemetry-on artifact build. The
    ledger stays attached THROUGH the census trace (detached and closed
    by :func:`measure_artifact`'s finally), so the chunk is lowered in
    exactly the configuration a supervised run uses — if telemetry ever
    leaks a ``jax.debug.callback``/``io_callback`` into the traced
    chunk, ``host_transfers_in_scan`` catches it here."""
    import tempfile

    from ibamr_tpu import obs

    path = os.path.join(tempfile.mkdtemp(prefix="obs-contract-"),
                        "ledger.jsonl")
    obs.attach(obs.RunLedger(path))


def _build_solo_chunk_telemetry():
    # the solo chunk exactly as the instrumented driver runs it: live
    # ledger attached, the chunk call wrapped in the driver's span, the
    # per-chunk counter/watermark flush issued after — all of which
    # must stay HOST-side (same FFT/scatter ceilings as solo_chunk,
    # host_transfers_in_scan == 0)
    from ibamr_tpu import obs

    integ, state = _shell()
    drv = _driver(integ)
    chunk = _unwrap(drv._chunk(4))
    _attach_contract_ledger()

    def run(st, dt):
        with obs.span("driver/chunk", step=0, length=4):
            out = chunk(st, dt)
        obs.chunk_boundary(step=4)
        return out

    return run, (state, _DT), ()


def _build_fleet_chunk_telemetry():
    import jax.numpy as jnp

    from ibamr_tpu import obs
    from ibamr_tpu.utils import lanes as _lanes

    integ, state = _shell()
    drv = _driver(integ, lanes=2)
    chunk = _unwrap(drv._chunk(2))
    stacked = _lanes.stack_lanes([state, state])
    dt_vec = jnp.full((2,), _DT, dtype=jnp.float32)
    alive = jnp.ones((2,), dtype=bool)
    _attach_contract_ledger()

    def run(st, dt, al):
        with obs.span("driver/chunk", step=0, length=2):
            out = chunk(st, dt, al)
        obs.chunk_boundary(step=2)
        return out

    return run, (stacked, dt_vec, alive), ()


def _build_donated_step():
    # IBExplicitIntegrator.jitted_step(donate=True) unwrapped: verifies
    # the integrator-level donation request actually aliases buffers
    integ, state = _shell()
    step = _unwrap(integ.jitted_step(donate=True))
    return step, (state, _DT), (0,)


def _build_lane_fetch():
    # the per-lane capsule/rollback fetch graph: lane_slice of a
    # 2-lane stacked state (must be a pure gather-free slice — zero
    # scatters, zero FFTs, zero host ops)
    from ibamr_tpu.utils import lanes as _lanes

    integ, state = _shell()
    stacked = _lanes.stack_lanes([state, state])
    return (lambda st: _lanes.lane_slice(st, 0)), (stacked,), ()


def _build_open_channel_step():
    # open-boundary stabilized-PPM step: the non-periodic code path
    # (saddle Stokes + boundary-band upwind blending). First-wave
    # finding lived here (_stab_mask hard-coded f64); the budget pins
    # the path dtype-clean from now on.
    from ibamr_tpu.integrators.ins_open import INSOpenIntegrator
    from ibamr_tpu.solvers.stokes import channel_bc

    io = INSOpenIntegrator(
        (_N, _N), (1.0 / _N, 1.0 / _N), channel_bc(2), mu=0.05,
        dt=_DT, bdry={(0, 0, 0): 1.0},
        convective_op_type="stabilized_ppm")
    state = io.initialize()
    return (lambda s: io.step(s)), (state,), ()


def _build_served_chunk():
    # the warm-pool router's first-step ack: a 1-step 2-lane fleet
    # chunk with ONE live lane and one dead-on-arrival padding lane
    # (pad_lanes). The serving path must lower the same in-scan
    # structure as the batch fleet chunk — a padded request bucket
    # cannot buy extra host transfers or scatters
    from ibamr_tpu.serve.aot_cache import ExecutableCache
    from ibamr_tpu.serve.router import BucketSpec, WarmPool

    pool = WarmPool(BucketSpec(n_cells=_N, n_lat=_N_LAT, n_lon=_N_LON,
                               lanes=2, engine="packed"),
                    ExecutableCache())
    return pool.contract_args(length=1, live=1)


def _require_devices(jax, n=8):
    if len(jax.devices()) < n:
        raise RuntimeError(
            f"sharded artifact needs {n} devices (virtual CPU devices "
            f"count) — got {len(jax.devices())}; the audit child forces "
            f"force_cpu({n}) and the test conftest sets "
            f"--xla_force_host_platform_device_count=8")


def _build_sharded_chunk():
    # the pod driver's unit of work: the dispatched sharded coupled IB
    # step (pencil-FFT solves + S2 co-partitioned transfers) scanned
    # over a 2-step chunk on the 8-device mesh. The collective/overlap
    # metrics pinned here are the comm-layer contract of ROADMAP item 2
    # (sharded_speedup diagnosis): a refactor that adds a transpose,
    # doubles a halo, or un-hides an async pair regresses the budget.
    import jax

    from ibamr_tpu.parallel import make_mesh
    from ibamr_tpu.parallel.mesh import make_sharded_step, place_state

    _require_devices(jax)
    integ, state0 = _shell()
    mesh = make_mesh(8)
    step = _unwrap(make_sharded_step(integ, mesh))
    state = place_state(state0, integ.ins.grid, mesh)

    def chunk(st, dt):
        def body(s, _):
            return step(s, dt), ()
        out, _ = jax.lax.scan(body, st, None, length=2)
        return out

    return chunk, (state, _DT), ()


def _build_fftpar_transpose():
    # the pencil-FFT Helmholtz solve in isolation: on the (4, 2) mesh
    # over the 16^3 grid this is exactly 4 all_to_all transposes in,
    # 4 back out — the framework's true long-range communication
    import jax

    from ibamr_tpu.parallel import make_mesh
    from ibamr_tpu.parallel.fftpar import PencilFFT

    _require_devices(jax)
    integ, state = _shell()
    mesh = make_mesh(8)
    pencil = PencilFFT(integ.ins.grid, mesh)
    rhs = state.ins.u[0]
    return (lambda r: pencil.helmholtz(r, 200.0, -0.025)), (rhs,), ()


def _build_lagrangian_exchange():
    # the S2 co-partition exchange in isolation: owner bucketing +
    # local spread + ppermute halo accumulate (parallel/lagrangian);
    # ppermute count/bytes per sharded axis are the budget
    import jax
    import jax.numpy as jnp

    from ibamr_tpu.parallel import ShardedInteraction, make_mesh

    _require_devices(jax)
    integ, state = _shell()
    mesh = make_mesh(8)
    si = ShardedInteraction(integ.ins.grid, mesh,
                            n_markers=state.X.shape[0])
    F = jnp.zeros_like(state.X)

    def exchange(Fa, Xa, m):
        b = si.buckets(Xa, m)
        return si.spread_vel(Fa, Xa, weights=m, b=b)

    return exchange, (F, state.X, state.mask), ()


def _build_fleet_mesh_chunk():
    # the pod fleet's unit of work (PR 16): the 8-lane fleet chunk with
    # its lane axis sharded over the 8-device lane mesh (B×D — each
    # device owns whole lanes). Lanes are independent, so the ONLY
    # collectives the partitioner may insert are boundary reshard pins;
    # the budget holds this at zero-traffic and keeps the per-lane
    # freeze/dt structure identical to fleet_chunk.
    import jax
    import jax.numpy as jnp

    from ibamr_tpu.parallel.mesh import make_lane_mesh, place_lanes
    from ibamr_tpu.utils import lanes as _lanes

    _require_devices(jax)
    integ, state = _shell()
    mesh = make_lane_mesh(8)
    drv = _driver(integ, lanes=8, lane_mesh=mesh)
    chunk = _unwrap(drv._chunk(2))
    stacked = place_lanes(_lanes.stack_lanes([state] * 8), mesh)
    dt_vec = jnp.full((8,), _DT, dtype=jnp.float32)
    alive = jnp.ones((8,), dtype=bool)
    return chunk, (stacked, dt_vec, alive), ()


def _build_krylov_reduce():
    # the Krylov layer's per-iteration global reductions under GSPMD:
    # a sharded CG on the (shifted) periodic Poisson operator. On the
    # CPU mesh every global dot lowers to a synchronous all-reduce, so
    # ``collective_sync_ops`` counts the syncs per compiled module —
    # PR 16's fused ``tree_dots`` turns the two scalar (r,z)/(r,r)
    # reductions per iteration into ONE (2,)-vector reduction and the
    # budget pins the lower count.
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    from ibamr_tpu.parallel import make_mesh
    from ibamr_tpu.solvers.krylov import cg

    _require_devices(jax)
    mesh = make_mesh(8)
    sh = NamedSharding(mesh, PartitionSpec(*mesh.axis_names))

    def A(x):
        x = jax.lax.with_sharding_constraint(x, sh)
        return (7.0 * x
                - jnp.roll(x, 1, 0) - jnp.roll(x, -1, 0)
                - jnp.roll(x, 1, 1) - jnp.roll(x, -1, 1)
                - jnp.roll(x, 1, 2) - jnp.roll(x, -1, 2))

    b = jax.device_put(jnp.ones((_N, _N, _N), jnp.float32), sh)
    return (lambda r: cg(A, r, maxiter=8).x), (b,), ()


def _build_solo_step_256():
    from ibamr_tpu.models.shell3d import build_shell_example

    integ, state = build_shell_example(
        n_cells=256, n_lat=316, n_lon=316, radius=0.25, aspect=1.2,
        stiffness=1.0, rest_length_factor=0.75, mu=0.05,
        use_fast_interaction="packed")
    return (lambda s: integ.step(s, _DT)), (state,), ()


def _build_assim_analysis():
    # the masked B-lane ESRF analysis step (PR 20): instrument-panel
    # observation operator vmapped over lanes + ensemble-space
    # square-root update + pack/unpack of the assimilated state
    # subset. The args carry ONE QUARANTINED LANE and one rejected
    # channel on purpose — quarantine and QC act through mask VALUES,
    # so this is the trace signature the whole failure surface rides.
    # Pins: zero in-scan host transfers, zero scatters (gather-only
    # interp + dense (B,B) algebra), zero f64 widenings.
    import jax
    import jax.numpy as jnp

    from ibamr_tpu.assim import (ObservationOperator, esrf_analysis,
                                 state_packer)
    from ibamr_tpu.instruments import InstrumentPanel, make_meters
    from ibamr_tpu.utils import lanes as _lanes

    integ, state = _shell()
    loops = [[2 * _N_LON + j for j in range(_N_LON)],
             [5 * _N_LON + j for j in range(_N_LON)]]
    panel = InstrumentPanel(integ.ins.grid,
                            make_meters(loops, closed=True))
    op = ObservationOperator(panel)
    B = 4
    stacked = _lanes.broadcast_lane(state, B)
    pack, unpack, _n = state_packer(state)

    def analyze(fleet, y, r, om, alive, lam):
        ens = jax.vmap(pack)(fleet)
        obs_ens = jax.vmap(op)(fleet)
        ana, diag = esrf_analysis(ens, obs_ens, y, r, alive, om, lam)
        return jax.vmap(unpack)(fleet, ana), diag

    m = op.n_obs
    y = jnp.zeros((m,), jnp.float32)
    r = jnp.full((m,), 1e-4, jnp.float32)
    om = jnp.array([True] * (m - 1) + [False])      # one QC reject
    alive = jnp.array([True] * (B - 1) + [False])   # one quarantined
    lam = jnp.asarray(1.0, jnp.float32)
    return analyze, (stacked, y, r, om, alive, lam), ()


@dataclass(frozen=True)
class Artifact:
    """One named compiled artifact under contract."""
    name: str
    build: Callable[[], Tuple]        # () -> (fn, args, donate_argnums)
    heavy: bool = False               # flagship-scale: slow-tier / --heavy
    notes: str = ""


ARTIFACTS: Dict[str, Artifact] = {
    a.name: a for a in (
        Artifact("solo_step", _build_solo_step,
                 notes="full coupled IB step, packed engine, f32"),
        Artifact("solo_step_bf16",
                 lambda: _build_solo_step(spectral_dtype="bf16"),
                 notes="full step with bf16 spectral transforms"),
        Artifact("fused_substep", _build_fused_substep,
                 notes="k-space-resident Helmholtz+projection substep "
                       "(<= 2 batched FFTs is the fusion pin)"),
        Artifact("fused_substep_bf16",
                 lambda: _build_fused_substep(spectral_dtype="bf16"),
                 notes="mixed-precision substep; bf16 rounding converts "
                       "are budgeted, widenings are not"),
        Artifact("spread_packed",
                 lambda: _build_transfer("packed", "spread"),
                 notes="occupancy-packed force spread (zero scatters)"),
        Artifact("interp_packed",
                 lambda: _build_transfer("packed", "interp"),
                 notes="occupancy-packed velocity interp"),
        Artifact("spread_mxu",
                 lambda: _build_transfer(True, "spread"),
                 notes="dense one-hot MXU spread (zero scatters)"),
        Artifact("interp_mxu",
                 lambda: _build_transfer(True, "interp"),
                 notes="dense one-hot MXU interp"),
        Artifact("grad_substep", _build_grad_substep,
                 notes="full vjp round trip of the fused substep: the "
                       "cotangent rides the SAME plan, <= 2x primal "
                       "batched FFTs (fft_ops 4 vs 2) is the headline "
                       "adjoint-at-primal-cost pin"),
        Artifact("grad_spread",
                 lambda: _build_grad_transfer("spread"),
                 notes="packed spread backward pass: an interp through "
                       "the SAME buckets — zero scatter prims, zero "
                       "bucket preps"),
        Artifact("grad_interp",
                 lambda: _build_grad_transfer("interp"),
                 notes="packed interp backward pass: d/df reuses the "
                       "primal spread's scatter set (no new shapes), "
                       "d/dX the oracle weight-derivative pullback"),
        Artifact("grad_chunk", _build_grad_chunk,
                 notes="reverse mode through the remat-checkpointed "
                       "driver chunk; cotangent scan stays device-"
                       "resident (zero in-scan transfers) and dtype-"
                       "clean (zero f64 widenings)"),
        Artifact("solo_chunk", _build_solo_chunk,
                 notes="driver scan chunk + fused health probe; "
                       "host_transfers_in_scan == 0 is the pin"),
        Artifact("donated_chunk", _build_donated_chunk,
                 notes="cfg.donate=True chunk; donated_args >= 1 "
                       "verifies whole-chunk buffer donation"),
        Artifact("fleet_chunk", _build_fleet_chunk,
                 notes="2-lane vmapped chunk with lane-freeze select"),
        Artifact("solo_chunk_telemetry", _build_solo_chunk_telemetry,
                 notes="solo chunk lowered with a live run ledger, "
                       "driver span and per-chunk flush attached; "
                       "telemetry must stay host-side (same ceilings "
                       "as solo_chunk, zero in-scan transfers)"),
        Artifact("fleet_chunk_telemetry", _build_fleet_chunk_telemetry,
                 notes="fleet chunk lowered telemetry-on; same "
                       "ceilings as fleet_chunk, zero in-scan "
                       "transfers"),
        Artifact("donated_step", _build_donated_step,
                 notes="integrator jitted_step(donate=True); verified "
                       "against the compiled alias table"),
        Artifact("lane_fetch", _build_lane_fetch,
                 notes="per-lane capsule fetch (lane_slice) — zero "
                       "scatter/fft/host budget"),
        Artifact("served_chunk", _build_served_chunk,
                 notes="warm-pool 1-step ack chunk, 1 live + 1 padded "
                       "lane; the serving path pins the same in-scan "
                       "ceilings as the batch fleet chunk"),
        Artifact("open_channel_step", _build_open_channel_step,
                 notes="open-boundary stabilized-PPM step (saddle "
                       "Stokes); dtype-clean pin after the f64 "
                       "stab-mask finding"),
        Artifact("solo_step_256", _build_solo_step_256, heavy=True,
                 notes="flagship 256^3 coupled step (slow tier; "
                       "graph_audit --heavy)"),
        Artifact("sharded_chunk", _build_sharded_chunk,
                 notes="8-device sharded coupled IB chunk (pencil FFT "
                       "+ S2 transfers); the collective/overlap census "
                       "is the pod comm-layer pin"),
        Artifact("fftpar_transpose", _build_fftpar_transpose,
                 notes="pencil-FFT Helmholtz on the (4,2) mesh; "
                       "all_to_all transpose count/bytes budgeted"),
        Artifact("lagrangian_exchange", _build_lagrangian_exchange,
                 notes="S2 owner-bucketed spread with ppermute halo "
                       "accumulate; ppermute count/bytes budgeted"),
        Artifact("fleet_mesh_chunk", _build_fleet_mesh_chunk,
                 notes="8-lane fleet chunk sharded over the 8-device "
                       "lane mesh (B x D pod fleet); lanes are "
                       "independent so collective traffic stays zero"),
        Artifact("assim_analysis", _build_assim_analysis,
                 notes="masked B-lane ESRF analysis between scan "
                       "chunks (PR 20): instrument-panel obs operator "
                       "+ ensemble-space square-root update, one "
                       "quarantined lane and one rejected channel in "
                       "the trace — gather-only, dtype-clean, zero "
                       "host transfers"),
        Artifact("krylov_reduce", _build_krylov_reduce,
                 notes="sharded CG global reductions; fused tree_dots "
                       "pins one all-reduce sync per iteration pair"),
    )
}


def measure_artifact(name: str) -> dict:
    """Build + census one artifact under x64-off (production mode).

    Returns the flat budget-comparable metric dict. Caller chooses the
    backend; the CI gate runs this in a ``JAX_PLATFORMS=cpu`` child."""
    import jax

    from ibamr_tpu import obs

    art = ARTIFACTS[name]
    prev = obs.current()
    try:
        with jax.enable_x64(False):
            fn, args, donate = art.build()
            census = graph_census(fn, args, donate_argnums=donate)
    finally:
        # telemetry-on builders attach a contract ledger that must stay
        # live through the census; restore whatever the CALLER had
        # attached (in-process test measurement must not steal a real
        # run's ledger)
        led = obs.current()
        if led is not prev:
            obs.detach()
            try:
                led.close()
            except Exception:
                pass
            if prev is not None:
                obs.attach(prev)
    return budget_metrics(census)


# ---------------------------------------------------------------------------
# budget load / diff
# ---------------------------------------------------------------------------

def load_budgets(path: Optional[str] = None) -> dict:
    with open(path or BUDGET_PATH) as f:
        doc = json.load(f)
    return doc.get("artifacts", {})


@dataclass
class Drift:
    """Per-artifact diff of measured metrics against the budget."""
    name: str
    regressions: Dict[str, Tuple[int, int]] = field(default_factory=dict)
    improvements: Dict[str, Tuple[int, int]] = field(default_factory=dict)
    missing: Tuple[str, ...] = ()     # budgeted metric absent in census

    @property
    def clean(self) -> bool:
        return not (self.regressions or self.improvements or self.missing)


def diff_budget(name: str, measured: dict, budget: dict) -> Drift:
    """Compare one artifact's measured metrics to its budget.

    Max metrics regress UP (measured > budget) and improve DOWN; the
    min metrics (``donated_args``) regress DOWN — a refactor that
    silently drops donation is a regression even though every other
    counter stays flat."""
    d = Drift(name)
    missing = []
    for metric, bound in budget.items():
        if metric not in measured:
            missing.append(metric)
            continue
        got = int(measured[metric])
        bound = int(bound)
        if metric in BUDGET_MIN_METRICS:
            if got < bound:
                d.regressions[metric] = (got, bound)
            elif got > bound:
                d.improvements[metric] = (got, bound)
        elif metric in BUDGET_MAX_METRICS:
            if got > bound:
                d.regressions[metric] = (got, bound)
            elif got < bound:
                d.improvements[metric] = (got, bound)
        # unknown metrics in the budget file are a budget-file bug:
        # surface as missing rather than silently passing
        else:
            missing.append(metric)
    d.missing = tuple(missing)
    return d


def report_drift(drifts) -> str:
    """Human-readable drift report (one block per non-clean artifact)."""
    lines = []
    for d in drifts:
        if d.clean:
            continue
        lines.append(f"[{d.name}]")
        for m, (got, bound) in sorted(d.regressions.items()):
            word = ("dropped below floor"
                    if m in BUDGET_MIN_METRICS else "exceeds budget")
            lines.append(f"  REGRESSED  {m}: {got} {word} {bound}")
        for m, (got, bound) in sorted(d.improvements.items()):
            lines.append(
                f"  improved   {m}: {got} (budget {bound}) — run "
                f"tools/graph_audit.py --tighten to ratchet")
        for m in d.missing:
            lines.append(f"  MISSING    {m}: not measurable / unknown "
                         f"metric — budget file and census disagree")
    return "\n".join(lines)
