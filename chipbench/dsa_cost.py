"""Bytes the sparse-attention path of a decode step has to move,
counted from what the program counted.  The benchmark's own copy, like
``flops.py`` and ``moe_cost.py``.

For one (row, layer) of a decode step at position ``pos`` the path has
to read the indexer's key of every VISIBLE position (``pos + 1`` keys of
``index_head_dim`` values: a key it has not scored it cannot rank), has
to read the ``min(index_topk, pos + 1)`` latent rows it selected (the
latent and the rotary key: ``kv_lora_rank + qk_rope_head_dim`` values,
never the padding behind them, never a row it did not select), and has
to write the new token's index key and latent row.  Queries, weights
and scores are noise beside them (32 rows x 64 heads x 576 values).
That is the least any kernel can move; a program that reads the whole
index-key slab whatever ``pos`` (XLA's does) or gathers padded rows
shows it as a share under 100%.  Memory-bound: a selected latent row is
read once for 64 heads x 2 x 576 multiply-adds, but the indexer's 32
heads x 128 multiply-adds a key byte-pair and the top-k are far from
the matrix unit's peak, so the bound that binds is the bytes'.
"""

from __future__ import annotations


def index_bytes(keys_visible: float, index_head_dim: int, itemsize: int = 2) -> float:
    return float(keys_visible) * index_head_dim * itemsize


def gather_bytes(keys_selected: float, latent_values: int, itemsize: int = 2) -> float:
    return float(keys_selected) * latent_values * itemsize


def write_bytes(queries: float, index_head_dim: int, latent_values: int,
                itemsize: int = 2) -> float:
    return float(queries) * (index_head_dim + latent_values) * itemsize


def sparse_attention_bytes(keys_visible: float, keys_selected: float, queries: float,
                           index_head_dim: int, latent_values: int,
                           itemsize: int = 2) -> float:
    """All (row, layer) queries of some decode steps together: the sums
    the program's counters hold."""
    return (
        index_bytes(keys_visible, index_head_dim, itemsize)
        + gather_bytes(keys_selected, latent_values, itemsize)
        + write_bytes(queries, index_head_dim, latent_values, itemsize)
    )
