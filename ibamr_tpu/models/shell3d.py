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

from ibamr_tpu import obs
from ibamr_tpu.grid import StaggeredGrid
from ibamr_tpu.integrators.ib import IBExplicitIntegrator, IBMethod, IBState
from ibamr_tpu.integrators.ins import INSStaggeredIntegrator
from ibamr_tpu.io.structures import StructureData
from ibamr_tpu.models.engine_resolver import (
    RESOLVED_ENGINES, build_engine_with_fallback,
    construct_transfer_engine, normalize_engine_name, resolve_engine)


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


@obs.span("setup/build")
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

    ``use_fast_interaction``: a row name of
    :data:`ibamr_tpu.models.engine_resolver.ENGINES`, or True (the
    bucketed-MXU engine of ops.interaction_fast, row ``mxu``), or
    False (XLA scatter/gather, row ``scatter``). ``"packed"`` = the
    occupancy-packed chunk engine (ops.interaction_packed — best for
    surface structures whose tile occupancy is silhouette-clustered);
    ``"packed_bf16"`` = the same with bf16-compressed contraction
    operands (halves the dominant HBM traffic; ~3 decimal digits of
    delta-weight precision); ``"pallas_packed"`` / ``"hybrid_bf16"`` =
    the chunks driven by Pallas programs (ops.pallas_interaction).
    None = auto, resolved by that module (``IBAMR_TRANSFER_ENGINE``
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
        # "auto" | a row name of engine_resolver.ENGINES }
        if use_fast_interaction is None:
            knob = ("auto",) + RESOLVED_ENGINES
            eng = ib_db.get_string("transfer_engine", "auto").lower()
            if eng not in knob:
                raise ValueError(
                    f"IBMethod.transfer_engine = {eng!r}: expected one "
                    f"of {knob}")
            use_fast_interaction = None if eng == "auto" else eng
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
        # auto resolves through the resolver (env override, tuning-DB
        # file, else the built-in packed promotion) so the
        # flight-recorder fingerprint and the serving cache key carry
        # the RESOLVED engine, never the "auto" alias. The spectral
        # dtype is part of the query: the measured ranking can differ
        # between f32 and bf16 transform configurations.
        eng_name = resolve_engine(n, n_markers, support,
                                  spectral_dtype=spectral_dtype)
    else:
        eng_name = normalize_engine_name(use_fast_interaction)
        if eng_name not in RESOLVED_ENGINES:
            raise ValueError(
                f"unknown use_fast_interaction {use_fast_interaction!r}; "
                f"one of {(True, False, None) + RESOLVED_ENGINES}")
    if engine_fallback:
        fast, eng_name = build_engine_with_fallback(
            eng_name, grid, structure.vertices, kernel)
    else:
        fast = construct_transfer_engine(
            eng_name, grid, structure.vertices, kernel)
    ib = IBMethod(structure.force_specs(dtype=dtype), kernel=kernel,
                  fast=fast)
    # the RESOLVED engine (post-auto-resolution, post-fallback): what
    # the flight-recorder fingerprint and the serving cache key carry
    ib.engine_name = eng_name
    integ = IBExplicitIntegrator(ins, ib, scheme="midpoint")
    state = integ.initialize(structure.vertices)
    return integ, state
