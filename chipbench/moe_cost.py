"""Operations and bytes of the expert layer's grouped matmuls, counted
from shapes and from the experts that were TOUCHED.  The benchmark's
own copy, like ``flops.py``.

One grouped matmul ``lhs (R, K) x rhs (G, K, N) -> (R, N)`` has to
read every row of ``lhs``, the matrices of the groups that own at least
one row — never of all G: a step that touches 50 of 64 experts reads 50
matrices, and a roofline share computed from 64 would read over 100% —
and write every row of the result.  That is the least any kernel can
move; what a kernel re-reads (an expert's matrix once per row tile that
holds one of its rows, ``lhs`` once per column tile) is its own cost
and shows as a share under 100%.

One expert layer-step is three such matmuls over the same sorted rows:
gate and up (K = E, N = M) and down (K = M, N = E).
"""

from __future__ import annotations


def gmm_flops(rows: float, k: int, n: int) -> float:
    """Multiply-adds x 2 of the rows that exist (no padding rows)."""
    return 2.0 * rows * k * n


def gmm_bytes(rows: float, k: int, n: int, groups_touched: float,
              itemsize: int = 2) -> float:
    """Bytes one grouped matmul must move: ``lhs`` in, the touched
    groups' matrices in, the result out."""
    return itemsize * (rows * k + groups_touched * k * n + rows * n)


def expert_layer_flops(rows: float, embed: int, expert_dim: int) -> float:
    return 3 * gmm_flops(rows, embed, expert_dim)


def expert_layer_bytes(rows: float, embed: int, expert_dim: int,
                       experts_touched: float, itemsize: int = 2) -> float:
    """Gate + up + down of one layer-step over ``rows`` (token, expert)
    rows that touch ``experts_touched`` experts."""
    return (
        2 * gmm_bytes(rows, embed, expert_dim, experts_touched, itemsize)
        + gmm_bytes(rows, expert_dim, embed, experts_touched, itemsize)
    )


def mean_gmm_call_bytes(rows: float, embed: int, expert_dim: int,
                        experts_touched: float, itemsize: int = 2) -> float:
    """Bytes of the mean kernel execution of a layer-step (a third of
    the layer-step: the three calls differ only in which side is wide)."""
    return expert_layer_bytes(rows, embed, expert_dim, experts_touched, itemsize) / 3.0
