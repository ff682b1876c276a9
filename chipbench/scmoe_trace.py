"""Device time of a shortcut-connected double layer's parts: which
operations of a trace ran under the ``jax.named_scope``s
``ray_tpu/models/llama.py`` gives them — the two attentions
(``scmoe_attn0`` / ``scmoe_attn1``: norms, projections, ``mla_attn`` inside
each), the two dense SwiGLUs (``scmoe_dense0`` / ``scmoe_dense1``), the
expert layer (``scmoe_experts``) and inside it the router and the sort
(``moe_route``), the grouped matmuls (``moe_experts``), the un-sort and the
weighted sum (``moe_combine``) and the identity experts' term
(``moe_zero``).

``chipbench/mtp_trace.py``'s reduction, which reads its scopes' names from
its module when it is called, run with this list in their place — so
``mla_attn_device_s``, ``mla_attn_decode_device_s`` and
``decode_executions_traced`` are, by construction, what that module gives
the JoyAI cell's readers.  The scopes nest (``mla_attn`` under an
attention, the four ``moe_*`` under ``scmoe_experts``): each scope's time is
the union of its own instructions' intervals.  What the compiler fuses
across two scopes goes to the scope of the fusion's root.
"""

from __future__ import annotations

from typing import Dict
from unittest import mock

from chipbench import mtp_trace

SCOPES = ("scmoe_attn0", "scmoe_attn1", "scmoe_dense0", "scmoe_dense1",
          "scmoe_experts", "moe_route", "moe_experts", "moe_combine", "moe_zero",
          "mla_proj", "mla_attn")
SCOPE_FILE = mtp_trace.SCOPE_FILE


def _mine():
    return mock.patch.object(mtp_trace, "SCOPES", SCOPES)


def version(hlo_text: str) -> dict:
    """One compiled version of a program, as ``mtp_trace.version``."""
    with _mine():
        return mtp_trace.version(hlo_text)


def reduce(planes, versions) -> Dict[str, float]:
    with _mine():
        return mtp_trace.reduce(planes, versions)


def facts(trace_dir: str) -> Dict[str, float]:
    """The job's facts for the scope readers; {} where the replica wrote no
    programs' versions beside the trace, or the trace has no device plane."""
    with _mine():
        return mtp_trace.facts(trace_dir)
