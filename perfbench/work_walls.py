"""Work the wall-bounded fluid solve's axis transforms need, computed from
shapes (as in ``work.py``: never from what the implementation happens to
move or multiply)."""

from __future__ import annotations

SOLVES = 4      # three velocity Helmholtz solves and the pressure's Poisson


def transform_flops_per_step(n) -> int:
    """Floating-point operations of ONE step's axis transforms as dense
    products, on an ``n[0] x n[1] x n[2]`` MAC grid with walls on all three
    axes: each of the four solves applies, forward and inverse, one
    ``m x m`` eigenvector matrix along every axis to every line of the
    field, 2 m^2 operations a line and ``cells / n[axis]`` lines; ``m`` is
    the axis' extent, or one less along the own axis of a velocity
    component (its wall face is pinned, not solved for; the other extents of
    that solve are counted whole, 0.2% over the exact count at 256^3).  A sine or cosine transform by FFT
    would need far fewer (5 m log2 m a line): the floor is of THIS
    algorithm, dense products, so that the share says what their precision
    and layout cost."""
    n = [int(v) for v in n]
    cells = n[0] * n[1] * n[2]
    flops = 0
    for solve in range(SOLVES):
        for axis in range(3):
            m = n[axis] - 1 if solve == axis else n[axis]
            flops += 2 * (2 * m * m * (cells // n[axis]))
    return flops


def transform_bytes_per_step(n, real_itemsize: int = 4) -> int:
    """Least HBM bytes of the same transforms: each solve reads its field
    once and writes it once, forward and again inverse (the three axis
    products of one direction fused into one pass; the diagonal divide
    between the two directions is not counted, nor any layout copy)."""
    n0, n1, n2 = (int(v) for v in n)
    return SOLVES * 2 * 2 * n0 * n1 * n2 * real_itemsize


def transform_least_s(n, peaks: dict) -> float:
    """The least time the chip could take for them: the larger of the
    operations over the matrix unit's peak and the bytes over the HBM peak.

    The yardstick of a float32 product is the chip's published bf16 peak:
    it is the only peak the matrix unit has.  A float32 product at
    ``Precision.HIGHEST`` is six bf16 passes (three at ``HIGH``, one by
    default), so against one pass' worth of operations the share reads what
    the passes cost, and a later change of precision or of layout moves it
    the right way.  The share cannot read over 100%: no precision makes
    fewer than one pass, and no layout moves fewer bytes."""
    return max(transform_flops_per_step(n) / peaks["bf16_flops_per_s"],
               transform_bytes_per_step(n) / peaks["hbm_bytes_per_s"])
