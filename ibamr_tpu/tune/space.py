"""Search-space enumeration with static pruning.

A trial costs an AOT compile plus timed warm steps; a candidate that
cannot ship must never reach the runner. Pruning is STATIC (geometry
and BC facts the engine constructors themselves enforce) plus an
optional compile probe for the Pallas family:

- **tile divisibility + minimum extent** — every non-scatter engine
  blocks the xy plane in 8-tiles and needs the ``make_geometry``
  minimum extent (``tile + support + 1``), the same facts
  ``default_rule`` promotes on;
- **wall-BC bf16 refusal** — the bf16/split-real spectral transform
  path is periodic-only; a non-periodic config prunes every
  ``spectral_dtype="bf16"`` candidate instead of timing a
  configuration the solver would refuse;
- **Pallas compile probe** — the Pallas-backed engines have failed to
  compile in the field (the round-2 remote-compile stall); with a
  ``probe_fn`` the enumeration trace+compiles each Pallas candidate
  through the build-time probe
  (``engine_resolver.probe_transfer_engine``) and prunes the ones
  that die.

The marker-count heuristic (``n_markers >= 4096``) is deliberately
NOT a pruning rule: it is exactly the hand-tuned promotion threshold
this subsystem replaces with measurement — small-marker configs keep
their packed candidates and the measurement decides.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Optional, Sequence, Tuple

from ibamr_tpu.models.engine_resolver import (
    PROBED_ENGINES, RESOLVED_ENGINES, construct_transfer_engine,
    probe_transfer_engine)

# the default searched engine menu: every row of the resolver's table,
# in the table's order
DEFAULT_ENGINES = RESOLVED_ENGINES


@dataclass(frozen=True)
class Candidate:
    """One point of the engine x spectral_dtype x chunk-length grid."""
    engine: str
    spectral_dtype: str = "f32"
    chunk_length: int = 1

    def label(self) -> str:
        return (f"{self.engine}/{self.spectral_dtype}"
                f"/L{self.chunk_length}")


def _engine_eligible(engine: str, n: Sequence[int],
                     support: int) -> Optional[str]:
    """Static geometry eligibility; returns a prune reason or None."""
    if engine == "scatter":
        return None                      # the unconditional baseline
    if not all(v % 8 == 0 for v in n[:-1]):
        return (f"xy extents {tuple(n[:-1])} not divisible by the "
                f"8-tile")
    if not all(v >= 8 + support + 1 for v in n[:-1]):
        return (f"xy extents {tuple(n[:-1])} below the make_geometry "
                f"minimum (tile + support + 1 = {8 + support + 1})")
    return None


def enumerate_space(
        n: Sequence[int], n_markers: int, support: int, *,
        engines: Sequence[str] = DEFAULT_ENGINES,
        spectral_dtypes: Sequence[str] = ("f32", "bf16"),
        chunk_lengths: Sequence[int] = (1, 4),
        bc: str = "periodic",
        probe_fn: Optional[Callable[[str], None]] = None,
) -> Tuple[list, list]:
    """``(candidates, pruned)`` for one configuration key. ``pruned``
    is ``[(Candidate, reason), ...]`` — every grid point is accounted
    for, nothing is silently dropped. ``probe_fn(engine)`` raises (or
    returns) per Pallas-family engine; when omitted, probing is skipped
    (pure-static mode — the runner's own build still degrades safely).
    A probe failure prunes EVERY candidate of that engine."""
    for e in engines:
        if e not in RESOLVED_ENGINES:
            raise ValueError(
                f"unknown engine {e!r} in the search menu; expected "
                f"names from {RESOLVED_ENGINES}")
    candidates, pruned = [], []
    probe_verdict: dict = {}
    for engine in engines:
        geo_reason = _engine_eligible(engine, n, support)
        if geo_reason is None and probe_fn is not None \
                and engine in PROBED_ENGINES:
            if engine not in probe_verdict:
                try:
                    probe_fn(engine)
                    probe_verdict[engine] = None
                except Exception as e:  # noqa: BLE001 - prune, not die
                    probe_verdict[engine] = (
                        f"compile probe failed "
                        f"({type(e).__name__}: {e})")
            geo_reason = probe_verdict[engine]
        for sd in spectral_dtypes:
            sd = str(sd).lower()
            for L in chunk_lengths:
                cand = Candidate(engine=engine, spectral_dtype=sd,
                                 chunk_length=int(L))
                if geo_reason is not None:
                    pruned.append((cand, geo_reason))
                elif sd == "bf16" and bc != "periodic":
                    pruned.append((
                        cand,
                        f"bf16 spectral transforms are periodic-only "
                        f"(bc={bc!r})"))
                else:
                    candidates.append(cand)
    return candidates, pruned


def make_probe_fn(n: Sequence[int], n_lat: int, n_lon: int,
                  kernel: str = "IB_4") -> Callable[[str], None]:
    """The real compile probe: construct the engine against the actual
    grid + a representative shell lattice and trace+compile a
    bucket/spread/interp composition (the fallback machinery's
    build-time check). Raises on construction or compile failure."""
    def probe(engine: str) -> None:
        from ibamr_tpu.grid import StaggeredGrid
        from ibamr_tpu.models.shell3d import make_spherical_shell

        grid = StaggeredGrid(n=tuple(int(v) for v in n),
                             x_lo=(0.0,) * len(n), x_up=(1.0,) * len(n))
        s = make_spherical_shell(n_lat, n_lon, 0.25,
                                 tuple(0.5 for _ in n)[:3], 1.0,
                                 aspect=1.2)
        fast = construct_transfer_engine(engine, grid, s.vertices,
                                         kernel)
        probe_transfer_engine(fast, s.vertices)
    return probe
