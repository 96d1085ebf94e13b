"""Fast-tier HLO/jaxpr budget regression (round 6).

Pins the two structural guarantees the fused spectral substep makes at
compile time, on a small grid so the tier runs in seconds:

- the flagship IB step's jaxpr contains at most TWO batched ``fft``
  primitives for the fluid substep (one forward rfftn, one inverse
  irfftn) plus none smuggled in elsewhere, and
- the packed step's scatter-primitive count stays at its pinned (true,
  non-zero) value.

These are jaxpr censuses, not timings — backend-independent and safe
for the CPU CI tier (CPU lowers lax.fft to a ducc custom-call and
expands scatters before the optimized HLO, so both censuses MUST run
at the jaxpr level).
"""

import jax
import jax.numpy as jnp

from ibamr_tpu.analysis.graph_census import (iter_eqns,
                                             scatter_gather_census)
from ibamr_tpu.models.shell3d import build_shell_example


def count_fft(jaxpr) -> int:
    return sum(eqn.primitive.name == "fft" for eqn, _ in iter_eqns(jaxpr))


def _build(n=32):
    # explicit use_fast_interaction bypasses the auto-engine size
    # eligibility gate so the fast tier exercises the flagship path
    integ, st = build_shell_example(n_cells=n, n_lat=8, n_lon=16,
                                    use_fast_interaction="packed")
    return integ, st


def test_step_jaxpr_fft_budget():
    integ, st = _build()
    assert integ.ins.fused_stokes is not None   # flagship fused path on
    jaxpr = jax.make_jaxpr(lambda s: integ.step(s, 1e-3))(st)
    n_fft = count_fft(jaxpr.jaxpr)
    # one batched rfftn + one batched irfftn; anything more means the
    # substep fell off the k-space-resident path (e.g. back to the
    # chained per-field solves, which cost 8)
    assert 1 <= n_fft <= 2, f"fft primitive count {n_fft}, budget 2"


def test_step_jaxpr_fft_budget_chained_is_worse():
    # the guard itself: disabling fusion must blow the budget, so the
    # test above cannot pass vacuously
    integ, st = _build(n=16)
    integ.ins.fused_stokes = None
    jaxpr = jax.make_jaxpr(lambda s: integ.step(s, 1e-3))(st)
    assert count_fft(jaxpr.jaxpr) > 2


def test_step_jaxpr_scatter_census():
    # jaxpr-level on purpose: the XLA:CPU scatter expander rewrites
    # scatters before the optimized HLO, so an HLO-text pin on this
    # backend says nothing about the chip (the old HLO-text "zero
    # scatter" pin was vacuous). The packed step really carries 17
    # scatter primitives (25 until the refresh stopped rebuilding its
    # slot->marker inverse; 24 until the marker values of a velocity
    # transfer moved as rows: one scatter-add per spread_vel and one
    # compact-overflow merge per interpolate_vel, not one per
    # component; 18 until spread_vel took its rows to slot order by a
    # gather through marker_of_slot): the bucket build and its slot
    # bookkeeping (interaction_fast/interaction_packed), the
    # overlap-add of packed tiles, and the overflow fallback through the
    # scatter reference (ops/interaction.py). A change in this count is
    # a change in what the chip's serial scatter penalty is charged on.
    integ, st = _build(n=16)
    jaxpr = jax.make_jaxpr(lambda s: integ.step(s, 1e-3))(st)
    census = scatter_gather_census(jaxpr.jaxpr)
    assert census["scatter_prims"] == 17, census


def test_bf16_step_same_fft_budget():
    integ, st = build_shell_example(n_cells=16, n_lat=8, n_lon=16,
                                    use_fast_interaction="packed",
                                    spectral_dtype="bf16")
    jaxpr = jax.make_jaxpr(lambda s: integ.step(s, 1e-3))(st)
    # mixed precision changes operand dtypes, never transform count
    assert 1 <= count_fft(jaxpr.jaxpr) <= 2
