"""TPU compute kernels: ring/flash attention, fused ops (Pallas + XLA).

The serving path's attention kernels each say by shape, in ONE function
``implementation``, whether the kernel or XLA's body runs:
``kv_decode_attention`` (a K/V config's every-row step; for a layer KIND
also key heads off a lane tile's edge, a sink, and a window kind's one block
of rolling slots), ``kv_prefill_attention`` (a layer kind's prefill: grouped
queries, keys wider than values, a window and a sink in one Pallas kernel
— a full layer's live tiles under the online softmax, a window layer's
whole band a grid step; XLA's dense body for toy heads),
``latent_decode_attention`` (a latent config's decode step) and
``latent_prefill_attention`` (a latent config's prefill: the flash kernel
for a run of four or more whole 512-token tiles with value heads of whole
128-lane tiles — a key head of another width goes padded with zeros to whole
lane tiles —
XLA's blocked body in ``models/llama.py:_latent_attention`` otherwise).
``topk_mask`` is a sparse-attention indexer's exact top-k as a mask: by
shape again (``implementation``), a Pallas kernel that counts its way to
the k-th score and the tie rule, or ``lax.top_k`` and a running count.
``gated_delta`` is a linear-attention layer's recurrence: the chunked form
for a prompt (plain XLA), and one token of every row — by shape once more
(``implementation``), the Pallas kernel ``gated_delta_step``, one pass over
the layer's rows of the cache's stacked state where they lie, or XLA's
body."""

from ray_tpu.ops.ring_attention import (  # noqa: F401
    ring_attention,
    ring_attention_manual,
)
