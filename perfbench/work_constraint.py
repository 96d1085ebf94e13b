"""Work the axis transforms of one ConstraintIB step over the wall-bounded
fluid solve need, computed from shapes (as in ``work_walls.py``: never from
what the implementation happens to move or multiply)."""

from __future__ import annotations

from perfbench import work_walls

# the fluid step's four solves (``work_walls.SOLVES``) and the second
# projection's Poisson solve for the imposed velocity
SOLVES = work_walls.SOLVES + 1


def reprojection_flops(n) -> int:
    """Floating-point operations of the second projection's axis transforms
    as dense products: one Neumann Poisson solve of a cell-centred field on
    ``n[0] x n[1] x n[2]`` cells, forward and inverse, one ``m x m``
    eigenvector matrix along every axis (2 m^2 a line, ``cells / m``
    lines; no axis is pinned)."""
    n = [int(v) for v in n]
    cells = n[0] * n[1] * n[2]
    return sum(2 * (2 * m * m * (cells // m)) for m in n)


def transform_flops_per_step(n) -> int:
    """The five solves' operations: the walled fluid step's four
    (``work_walls.transform_flops_per_step``, which takes three unequal
    extents) and the re-projection's one."""
    return work_walls.transform_flops_per_step(n) + reprojection_flops(n)


def transform_bytes_per_step(n, real_itemsize: int = 4) -> int:
    """Least HBM bytes of the same transforms: each of the five solves reads
    its field once and writes it once, forward and again inverse (the
    walled count's rule, one solve more)."""
    n0, n1, n2 = (int(v) for v in n)
    return SOLVES * 2 * 2 * n0 * n1 * n2 * real_itemsize


def transform_least_s(n, peaks: dict) -> float:
    """The least time the chip could take for them: the larger of the
    operations over the matrix unit's (bf16) peak and the bytes over the HBM
    peak, as ``work_walls.transform_least_s`` (and for its reasons: the bf16
    peak is the only one the matrix unit has, so the share says what the
    passes of a float32 product cost, and cannot read over 100%)."""
    return max(transform_flops_per_step(n) / peaks["bf16_flops_per_s"],
               transform_bytes_per_step(n) / peaks["hbm_bytes_per_s"])
