"""Work the fluid solve's phases need, computed from shapes (as in
``work.py``: never from what the implementation happens to move)."""

from __future__ import annotations


def convect_bytes_per_step(n, real_itemsize: int = 4) -> int:
    """Least HBM bytes the convective operator of ONE step must move on an
    ``n[0] x n[1] x n[2]`` MAC grid: the three velocity components read
    once and the three rates N(u)_d written once.  The ghost fills, the
    face states, the limiter's intermediates, the AB2 extrapolation's read
    of N(u^{n-1}) and every other pass the implementation makes are NOT
    counted: this is the floor of an operator fused into one pass, so the
    share says how far it is from that.  Bandwidth bounds it: the operator
    is elementwise (a few hundred flops a point on the vector unit; the
    chip's published flop peak is the matrix unit's and does not bound
    it)."""
    n0, n1, n2 = (int(v) for v in n)
    return 6 * n0 * n1 * n2 * real_itemsize
