"""3D elastic shell (the ex4-equivalent acceptance config).

Reference parity: ``examples/IB/explicit/ex4`` — a closed elastic shell
(pressurized/stretched spherical membrane discretized as a structured
marker lattice with spring + optional bending forces) immersed in a 3D
periodic incompressible fluid, IB_4 delta (BASELINE.json configs[1], the
north-star benchmark geometry: 128^3-256^3 grid, ~1e5 markers).

The shell is a latitude-longitude lattice: ``n_lat`` rings of ``n_lon``
markers each (poles excluded so every marker has full ring connectivity).
Springs run along rings (periodic) and along meridians (open chains);
``aspect`` != 1 starts the shell as a spheroid so taut springs drive a
relaxation flow — the 3D analog of the 2D ellipse-membrane test, with the
enclosed volume conserved by incompressibility.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import numpy as np

from ibamr_tpu.grid import StaggeredGrid
from ibamr_tpu.integrators.ib import IBExplicitIntegrator, IBMethod, IBState
from ibamr_tpu.integrators.ins import INSStaggeredIntegrator
from ibamr_tpu.io.structures import StructureData


def make_spherical_shell(n_lat: int, n_lon: int, radius: float,
                         center: Tuple[float, float, float],
                         stiffness: float,
                         rest_length_factor: float = 1.0,
                         aspect: float = 1.0,
                         bend_rigidity: float = 0.0) -> StructureData:
    """Structured spherical-shell marker lattice with ring + meridian
    springs (and optional meridian beams). ``aspect`` stretches the z axis
    (prolate for aspect > 1). Marker (i, j) = ring i, longitude j; index
    = i * n_lon + j."""
    # exclude poles: theta in (0, pi)
    theta = math.pi * (np.arange(n_lat) + 0.5) / n_lat        # (n_lat,)
    phi = 2.0 * math.pi * np.arange(n_lon) / n_lon            # (n_lon,)
    st, ct = np.sin(theta)[:, None], np.cos(theta)[:, None]
    cp, sp = np.cos(phi)[None, :], np.sin(phi)[None, :]
    x = center[0] + radius * st * cp
    y = center[1] + radius * st * sp
    z = center[2] + radius * aspect * ct * np.ones_like(cp)
    verts = np.stack([x.ravel(), y.ravel(), z.ravel()], axis=1)

    def gid(i, j):
        return i * n_lon + j % n_lon

    I, J = np.meshgrid(np.arange(n_lat), np.arange(n_lon), indexing="ij")
    # ring springs: (i,j)-(i,j+1), rest length = local ring arc length
    ring0 = gid(I, J).ravel()
    ring1 = gid(I, J + 1).ravel()
    ring_rest = np.repeat(2.0 * math.pi * radius * np.sin(theta) / n_lon,
                          n_lon)
    # meridian springs: (i,j)-(i+1,j), i < n_lat-1
    Im, Jm = np.meshgrid(np.arange(n_lat - 1), np.arange(n_lon),
                         indexing="ij")
    mer0 = gid(Im, Jm).ravel()
    mer1 = gid(Im + 1, Jm).ravel()
    mer_rest = np.full(mer0.shape, math.pi * radius / n_lat)

    idx0 = np.concatenate([ring0, mer0])
    idx1 = np.concatenate([ring1, mer1])
    rest = np.concatenate([ring_rest, mer_rest]) * rest_length_factor
    springs = np.stack([idx0, idx1,
                        np.full(idx0.shape, stiffness), rest], axis=1)

    data = StructureData(name="shell3d", vertices=verts, springs=springs)
    if bend_rigidity > 0.0:
        # meridian bending triples (i-1, i, i+1) for interior rings
        Ib, Jb = np.meshgrid(np.arange(1, n_lat - 1), np.arange(n_lon),
                             indexing="ij")
        beams = np.stack([
            gid(Ib - 1, Jb).ravel(), gid(Ib, Jb).ravel(),
            gid(Ib + 1, Jb).ravel(),
            np.full(Ib.size, bend_rigidity)], axis=1)
        data.beams = beams
    return data


def shell_volume(X: np.ndarray, center: Tuple[float, float, float]):
    """Approximate enclosed volume via the divergence theorem over the
    marker cloud treated as radial samples: V ~ mean(r^3) * 4 pi / 3.
    Diagnostic only (exact volume conservation is checked in 2D)."""
    import jax.numpy as jnp
    c = jnp.asarray(center, dtype=X.dtype)
    r = jnp.sqrt(jnp.sum((X - c) ** 2, axis=-1))
    return (4.0 / 3.0) * math.pi * jnp.mean(r ** 3)


def construct_transfer_engine(name, grid: StaggeredGrid, vertices,
                              kernel: str):
    """Registry builder: construct the named transfer engine against
    ``grid`` for a structure with marker positions ``vertices``.
    ``name`` uses the ``use_fast_interaction`` vocabulary (True/False/
    str); "scatter" returns None (the IBMethod scatter/gather path).
    Raises on unsatisfiable geometry (e.g. packed3 with no valid z
    tile) — :func:`build_engine_with_fallback` turns such failures
    into degradation instead of death."""
    import jax.numpy as jnp

    from ibamr_tpu.ops.interaction_packed import normalize_engine_name

    name = normalize_engine_name(name)
    if name == "scatter":
        return None
    n_markers = vertices.shape[0]

    def bounded_cap():
        # pole-clustered tiles overflow into the compact scatter
        # path; keep the dense capacity bounded so padding FLOPs
        # stay sane. Only the bucketed (mxu/pallas) layouts use a
        # per-tile cap — the packed layouts size chunks instead.
        from ibamr_tpu.ops.interaction_fast import suggest_cap
        return min(suggest_cap(grid, vertices, kernel=kernel, tile=8,
                               slack=1.2),
                   1024)

    if name == "pallas":
        from ibamr_tpu.ops.pallas_interaction import PallasInteraction
        return PallasInteraction(
            grid, kernel=kernel, tile=8, cap=bounded_cap(),
            overflow_cap=max(2048, n_markers // 4))
    if name in ("packed3", "packed3_bf16"):
        from ibamr_tpu.ops.interaction_packed3 import (
            PackedInteraction3, suggest_chunks3)
        # z-tile: the largest of (16, 8) that divides the z extent
        # AND leaves room for the footprint (extent >= tz+s+1, s=4
        # for IB_4 — make_geometry3's own constraints)
        from ibamr_tpu.ops.delta import get_kernel as _gk
        _s = _gk(kernel)[0]
        n = grid.n
        tz = next((t for t in (16, 8)
                   if n[-1] % t == 0 and n[-1] >= t + _s + 1
                   and t >= _s + 1), None)
        if tz is None:
            raise ValueError(
                f"packed3 engine: no valid z tile for n_z = "
                f"{n[-1]} with kernel {kernel!r} (need n_z "
                f"divisible by 8 or 16 with n_z >= tile+"
                f"{_s + 1}); use the 'packed' engine instead")
        Q3 = suggest_chunks3(grid, vertices, kernel=kernel, tile=8,
                             tile_last=tz, chunk=64, slack=1.3)
        return PackedInteraction3(
            grid, kernel=kernel, tile=8, tile_last=tz, chunk=64,
            nchunks=Q3,
            overflow_cap=max(2048, n_markers // 4),
            compute_dtype=(jnp.bfloat16 if name == "packed3_bf16"
                           else None))
    if name in ("packed", "pallas_packed", "packed_bf16",
                "hybrid_packed", "hybrid_packed_bf16", "hybrid_bf16"):
        from ibamr_tpu.ops.interaction_packed import (
            PackedInteraction, suggest_chunks)
        Q = suggest_chunks(grid, vertices, kernel=kernel, tile=8,
                           chunk=128, slack=1.3)
        if name == "pallas_packed":
            from ibamr_tpu.ops.pallas_interaction import (
                PallasPackedInteraction)
            return PallasPackedInteraction(
                grid, kernel=kernel, tile=8, chunk=128, nchunks=Q,
                overflow_cap=max(2048, n_markers // 4))
        if name in ("hybrid_packed", "hybrid_packed_bf16",
                    "hybrid_bf16"):
            # "hybrid_bf16" is the canonical name of the
            # pallas-spread + XLA-bf16-interp composition
            # ("hybrid_packed_bf16" kept as an alias)
            from ibamr_tpu.ops.pallas_interaction import (
                HybridPackedInteraction)
            return HybridPackedInteraction(
                grid, kernel=kernel, tile=8, chunk=128, nchunks=Q,
                overflow_cap=max(2048, n_markers // 4),
                compute_dtype=(jnp.bfloat16
                               if name in ("hybrid_packed_bf16",
                                           "hybrid_bf16") else None))
        return PackedInteraction(
            grid, kernel=kernel, tile=8, chunk=128, nchunks=Q,
            overflow_cap=max(2048, n_markers // 4),
            compute_dtype=(jnp.bfloat16 if name == "packed_bf16"
                           else None))
    if name in ("mxu", "mxu_bf16"):
        from ibamr_tpu.ops.interaction_fast import FastInteraction
        return FastInteraction(
            grid, kernel=kernel, tile=8, cap=bounded_cap(),
            overflow_cap=max(2048, n_markers // 4),
            compute_dtype=(jnp.bfloat16 if name == "mxu_bf16"
                           else None))
    raise ValueError(f"unknown transfer engine {name!r}")


def probe_transfer_engine(fast, vertices) -> None:
    """Trace AND compile (without executing) a bucket + spread +
    interp composition at the real marker shapes — the cheap stand-in
    for 'does this engine's first step survive': trace-time failures
    (a monkeypatched or buggy engine method) and XLA/Mosaic compile
    failures (the round-2 Pallas remote-compile stall) both surface
    here, at build time, where degradation is still possible."""
    if fast is None:
        return
    import jax
    import jax.numpy as jnp

    X = jnp.asarray(vertices)
    F = jnp.zeros_like(X)

    def fn(F, X):
        b = fast.buckets(X)
        g = fast.spread_vel(F, X, b=b)
        return fast.interpolate_vel(g, X, b=b)

    jax.jit(fn).lower(F, X).compile()


# engines worth a build-time compile probe: the Pallas-backed family,
# whose compile path (Mosaic lowering) is the one a chip's compiler can
# refuse. The plain-XLA
# engines skip the probe — construction errors still degrade, and
# probing them would tax every build for a failure mode never observed.
_PROBED_ENGINES = frozenset(
    {"pallas", "pallas_packed", "hybrid_packed", "hybrid_packed_bf16",
     "hybrid_bf16"})


def build_engine_with_fallback(name, grid: StaggeredGrid, vertices,
                               kernel: str, probe="auto"):
    """Construct ``name``'s transfer engine, degrading down the
    registry fallback chain (ops.interaction_packed.ENGINE_FALLBACKS)
    when construction or compile fails: each failure logs a warning
    naming the failed engine and its replacement, and the run
    continues on the next engine instead of dying. ``probe`` is True /
    False / "auto" (probe only the Pallas-backed engines). The
    terminal "scatter" link cannot fail (engine None). Returns
    ``(engine_or_None, engine_name)``."""
    import warnings

    from ibamr_tpu.ops.interaction_packed import fallback_chain

    chain = fallback_chain(name)
    for i, eng_name in enumerate(chain):
        try:
            fast = construct_transfer_engine(eng_name, grid, vertices,
                                             kernel)
            if probe is True or (probe == "auto"
                                 and eng_name in _PROBED_ENGINES):
                probe_transfer_engine(fast, vertices)
            return fast, eng_name
        except Exception as e:
            nxt = chain[i + 1]
            from ibamr_tpu.ops.interaction_packed import \
                record_engine_fallback
            record_engine_fallback(eng_name, nxt)
            warnings.warn(
                f"transfer engine {eng_name!r} failed to "
                f"build/compile ({type(e).__name__}: {e}); degrading "
                f"to {nxt!r}", RuntimeWarning)
    raise AssertionError("unreachable: scatter link cannot fail")


def build_shell_example(
        n_cells: int = 64,
        n_lat: int = 32,
        n_lon: int = 32,
        radius: float = 0.25,
        aspect: float = 1.2,
        stiffness: float = 1.0,
        rest_length_factor: float = 0.75,
        bend_rigidity: float = 0.0,
        rho: float = 1.0,
        mu: float = 0.05,
        kernel: str = "IB_4",
        convective_op_type: str = "centered",
        use_fast_interaction: Optional[bool] = None,
        dtype=None,
        input_db=None,
        engine_fallback: bool = False,
        spectral_dtype=None) -> Tuple[IBExplicitIntegrator,
                                      IBState]:
    """Assemble the ex4-equivalent simulation (3D periodic unit box).

    ``use_fast_interaction``: True = bucketed-MXU spread/interp engine
    (ops.interaction_fast); ``"packed"`` = the occupancy-packed chunk
    engine (ops.interaction_packed — best for surface structures whose
    tile occupancy is silhouette-clustered); ``"pallas"`` = the Pallas
    tile-kernel engine (ops.pallas_interaction); ``"pallas_packed"`` =
    occupancy-packed chunks driven by Pallas programs (no HBM weight
    intermediates); ``"mxu_bf16"`` / ``"packed_bf16"`` = the MXU /
    packed engines with bf16-compressed contraction operands (halves
    the dominant HBM traffic; ~3 decimal digits of delta-weight
    precision); False = XLA scatter/gather. None = auto, resolved by
    :mod:`ibamr_tpu.models.engine_resolver` (``IBAMR_TRANSFER_ENGINE``
    env override, ``IBAMR_TUNING_DB`` tuning file, else the built-in
    promotion: the occupancy-packed engine when the grid is
    tile-divisible and the marker count is large enough to matter,
    scatter otherwise). The resolved name lands on ``ib.engine_name``
    for fingerprinting/cache keying.

    ``engine_fallback`` (default False; knob ``IBMethod {
    engine_fallback = TRUE }``): a chosen engine that fails to build
    or compile stops the run. Opted in, it degrades down the registry
    fallback chain (docs/RESILIENCE.md) with a warning instead.
    """
    import jax.numpy as jnp

    if dtype is None:
        dtype = jnp.float32

    n = (n_cells,) * 3
    x_lo, x_up = (0.0,) * 3, (1.0,) * 3
    if input_db is not None:
        geo = input_db.get_database_with_default("CartesianGeometry")
        n = tuple(int(v) for v in geo.get_int_array("n_cells", list(n)))
        x_lo = tuple(float(v) for v in geo.get_array("x_lo", list(x_lo)))
        x_up = tuple(float(v) for v in geo.get_array("x_up", list(x_up)))
        ins_db = input_db.get_database_with_default(
            "INSStaggeredHierarchyIntegrator")
        rho = ins_db.get_float("rho", rho)
        mu = ins_db.get_float("mu", mu)
        convective_op_type = ins_db.get_string("convective_op_type",
                                               convective_op_type)
        # spectral transform precision knob (reference-style):
        # INSStaggeredHierarchyIntegrator { spectral_dtype = "bf16" }
        # — bf16/split-real transform operands, f32 twiddle/
        # accumulation; "f32" (default) is the full-precision path
        spectral_dtype = ins_db.get_string(
            "spectral_dtype",
            spectral_dtype if spectral_dtype is not None else "f32")
        ib_db = input_db.get_database_with_default("IBMethod")
        kernel = ib_db.get_string("delta_fcn", kernel)
        # reference-style engine knob: IBMethod { transfer_engine =
        # "auto"|"scatter"|"mxu"|"packed"|"pallas"|"pallas_packed"|
        # "mxu_bf16"|"packed_bf16"|...|"hybrid_bf16" }
        if use_fast_interaction is None:
            _KNOB = ("auto", "scatter", "mxu", "packed", "pallas",
                     "pallas_packed", "mxu_bf16", "packed_bf16",
                     "packed3", "packed3_bf16", "hybrid_packed",
                     "hybrid_packed_bf16", "hybrid_bf16")
            eng = ib_db.get_string("transfer_engine", "auto").lower()
            if eng not in _KNOB:
                raise ValueError(
                    f"IBMethod.transfer_engine = {eng!r}: expected one "
                    f"of {_KNOB}")
            use_fast_interaction = {
                "auto": None, "scatter": False, "mxu": True,
            }.get(eng, eng)
        # IBMethod { engine_fallback = TRUE } opts into degrading down
        # the fallback chain; by default a build/compile failure raises
        engine_fallback = ib_db.get_bool("engine_fallback",
                                         engine_fallback)
        sh = input_db.get_database_with_default("Shell")
        n_lat = sh.get_int("n_lat", n_lat)
        n_lon = sh.get_int("n_lon", n_lon)
        radius = sh.get_float("radius", radius)
        aspect = sh.get_float("aspect", aspect)
        stiffness = sh.get_float("stiffness", stiffness)
        rest_length_factor = sh.get_float("rest_length_factor",
                                          rest_length_factor)
        bend_rigidity = sh.get_float("bend_rigidity", bend_rigidity)

    grid = StaggeredGrid(n=n, x_lo=x_lo, x_up=x_up)
    ins = INSStaggeredIntegrator(grid, rho=rho, mu=mu,
                                 convective_op_type=convective_op_type,
                                 dtype=dtype,
                                 spectral_dtype=spectral_dtype)
    center = tuple(0.5 * (lo + hi) for lo, hi in zip(x_lo, x_up))
    structure = make_spherical_shell(
        n_lat, n_lon, radius, center=center,
        stiffness=stiffness, rest_length_factor=rest_length_factor,
        aspect=aspect, bend_rigidity=bend_rigidity)
    n_markers = structure.vertices.shape[0]
    from ibamr_tpu.ops.delta import get_kernel
    support, _ = get_kernel(kernel)
    if use_fast_interaction is None:
        # auto resolves through the pluggable resolver (env override,
        # tuning-DB file, else the built-in round-5 packed promotion)
        # so the flight-recorder fingerprint and the serving cache key
        # carry the RESOLVED engine, never the "auto" alias, and the
        # tune/ autotuner has a seam to publish winners into. The
        # spectral dtype is part of the query: the measured ranking can
        # differ between f32 and bf16 transform configurations.
        from ibamr_tpu.models.engine_resolver import resolve_engine
        resolved = resolve_engine(n, n_markers, support,
                                  spectral_dtype=spectral_dtype)
        use_fast_interaction = {
            "scatter": False, "mxu": True}.get(resolved, resolved)
    _ENGINES = (True, False, None, "pallas", "packed", "pallas_packed",
                "mxu_bf16", "packed_bf16", "packed3", "packed3_bf16",
                "hybrid_packed", "hybrid_packed_bf16", "hybrid_bf16")
    if use_fast_interaction not in _ENGINES:
        raise ValueError(
            f"unknown use_fast_interaction {use_fast_interaction!r}; "
            f"one of {_ENGINES}")
    if engine_fallback:
        fast, eng_name = build_engine_with_fallback(
            use_fast_interaction, grid, structure.vertices, kernel)
    else:
        from ibamr_tpu.ops.interaction_packed import normalize_engine_name
        fast = construct_transfer_engine(
            use_fast_interaction, grid, structure.vertices, kernel)
        eng_name = normalize_engine_name(use_fast_interaction)
    ib = IBMethod(structure.force_specs(dtype=dtype), kernel=kernel,
                  fast=fast)
    # the RESOLVED engine (post-auto-resolution, post-fallback): what
    # the flight-recorder fingerprint and the serving cache key carry
    ib.engine_name = eng_name
    integ = IBExplicitIntegrator(ins, ib, scheme="midpoint")
    state = integ.initialize(structure.vertices)
    return integ, state
