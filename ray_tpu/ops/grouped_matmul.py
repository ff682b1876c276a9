"""Grouped (ragged) matmul: row r of ``lhs`` times the matrix of the
group that row belongs to.

    out[r] = lhs[r] @ rhs[g(r)]      lhs (R, K), rhs (G, K, N) -> (R, N)

Rows are sorted by group: group g owns the ``group_sizes[g]`` rows
after those of groups 0..g-1 (sizes may be 0; they sum to at most R,
and rows past their sum come back undefined).  This is the expert
layer's matmul (``models/llama.py:_ffn``): every (token, expert) pair
is one row, so nothing is dropped and no expert is applied to a token
that did not choose it.

Two bodies, chosen by the platform in ONE place (``implementation``):

* ``pallas_gmm`` on a TPU: the Pallas kernel ``gmm`` of
  ``jax.experimental.pallas.ops.tpu.megablox`` — the custom call shows
  as ``gmm.N`` on the trace's op line.  It walks (row tile, group)
  pairs, so an expert's matrix is fetched once per row tile that holds
  one of its rows and an expert without rows is never read.  On a TPU
  there is no other body: a kernel that fails to build is an error.
* ``ragged_dot`` elsewhere: ``jax.lax.ragged_dot``, plain XLA.

Tile sizes (rows, K, N) were chosen on a v5e for the two shapes the
decode replica runs (``tile_for``; the sweep is in PERF.md section 6,
PR 26).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

#: rows per tile.  Swept on a v5e over 8..512 for both shapes the
#: replica runs (256 rows in 64 groups of ~4; 1,024-2,048 rows): 128 was
#: fastest or within 1% of it everywhere, 32 cost 6% and 8 cost 30% at
#: 256 rows (PERF.md section 6, PR 26)
ROW_TILE = 128
#: elements of one ``rhs`` tile: 4 MiB of bf16, double-buffered 8 of the
#: kernel's 16 MiB of fast memory; half of it cost 2-6%, a quarter 15%
RHS_TILE_ELEMENTS = 2 << 20


def implementation() -> str:
    """Which body ``grouped_matmul`` traces here: ``"pallas_gmm"`` where
    jax's default backend is a TPU, ``"ragged_dot"`` elsewhere."""
    return "pallas_gmm" if jax.default_backend() == "tpu" else "ragged_dot"


def tile_for(k: int, n: int) -> tuple:
    """(row tile, K tile, N tile) of the TPU kernel.  K is never split
    (one pass, no accumulator round trip: splitting it cost 5-10%), and
    N is split so that one tile of ``rhs`` holds ``RHS_TILE_ELEMENTS``:
    a whole 2048 x 1024 expert matrix at OLMoE's widths."""
    tn = min(n, max(128, RHS_TILE_ELEMENTS // k // 128 * 128))
    return ROW_TILE, k, tn


def _gmm_tpu(lhs, rhs, group_sizes, out_dtype):
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    rows, k = lhs.shape
    tm, tk, tn = tile_for(k, rhs.shape[2])
    padded = -(-rows // tm) * tm  # the kernel wants whole row tiles
    if padded != rows:
        lhs = jnp.pad(lhs, ((0, padded - rows), (0, 0)))
    out = gmm(lhs, rhs, group_sizes.astype(jnp.int32), out_dtype, (tm, tk, tn))
    return out[:rows]


def grouped_matmul(lhs, rhs, group_sizes, *, out_dtype=None):
    """lhs (R, K), rhs (G, K, N), group_sizes (G,) int -> (R, N) in
    ``out_dtype`` (``lhs``'s by default), accumulated in float32."""
    out_dtype = jnp.dtype(out_dtype or lhs.dtype)
    if implementation() == "pallas_gmm":
        return _gmm_tpu(lhs, rhs, group_sizes, out_dtype)
    return lax.ragged_dot(
        lhs, rhs, group_sizes.astype(jnp.int32),
        preferred_element_type=out_dtype,
    )
