"""Work the algorithm needs, computed from shapes (never from what the
implementation happens to move: ``graph_census`` counts are not used)."""

from __future__ import annotations


def transform_bytes_per_step(n, real_itemsize: int = 4) -> int:
    """Least HBM bytes the fluid solve's transforms of ONE midpoint step
    must move on an ``n[0] x n[1] x n[2]`` periodic MAC grid.

    The midpoint IB step makes one fluid solve (the structure's two
    substeps share it).  That solve sends THREE real fields forward (the
    three components of the Helmholtz right-hand side) and brings FOUR
    back (the three projected velocity components and the pressure
    increment): seven real-to-complex or complex-to-real transforms of a
    whole field.  Each one reads its input once and writes its output once:
    a real field of ``n0*n1*n2`` numbers on one side and a half-spectrum of
    ``n0*n1*(n2//2+1)`` complex numbers on the other.  The diagonal k-space
    algebra between them, the passes a multi-pass FFT makes over the data,
    and every byte ``graph_census`` sees the implementation move are NOT
    counted: this is the algorithm's floor, so the share says how far the
    transforms are from one read and one write per field.  Bandwidth bounds
    it: an FFT's 5 n log2 n flops per point are far under the chip's
    flop/byte ridge."""
    n0, n1, n2 = (int(v) for v in n)
    real = n0 * n1 * n2 * real_itemsize
    half = n0 * n1 * (n2 // 2 + 1) * 2 * real_itemsize
    return 7 * (real + half)
