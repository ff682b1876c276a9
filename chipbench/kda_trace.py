"""Device time of a Kimi-delta-attention layer's parts: which operations of a
trace ran under ``kda_proj`` (the projections, the low-rank gates and the
short convolution), ``kda_step`` (the one-token update of every row's state:
the kernel ``kda_step`` and what feeds it), ``kda_scan`` (a prefill's chunked
rule with a decay a key channel) and ``kda_out`` (gated norm and W_o), the
``jax.named_scope``s ``ray_tpu/models/llama.py:_kda_mixer`` gives them inside
the block's ``decode_attn``.

``chipbench/gdn_trace.py``'s reduction with this file's scopes in place of its
own (it says how a traced prefill finds its prompt's length): facts
``kda_<scope>_device_s`` (every program), ``kda_<scope>_decode_device_s``
(inside the decode program's executions), ``decode_executions_traced`` /
``decode_device_s_traced``, ``prefill_executions_traced`` /
``prefill_device_s_traced`` / ``prefill_tokens_traced``.  A program without
these scopes (a commit from before them) gives the readers nothing to read.
"""

from __future__ import annotations

from typing import Dict
from unittest import mock

from chipbench import gdn_trace

SCOPES = ("kda_proj", "kda_step", "kda_scan", "kda_out")
SCOPE_FILE = "kda_scopes.json"


def _mine():
    return mock.patch.multiple(gdn_trace, SCOPES=SCOPES, SCOPE_FILE=SCOPE_FILE)


def version(hlo_text: str) -> dict:
    """One compiled version of a program, as ``gdn_trace.version``."""
    with _mine():
        return gdn_trace.version(hlo_text)


def reduce(planes, versions, prompt_lens) -> Dict[str, float]:
    with _mine():
        return gdn_trace.reduce(planes, versions, prompt_lens)


def facts(trace_dir: str) -> Dict[str, float]:
    """The job's facts for the scope readers; {} where the replica wrote no
    programs' versions beside the trace, or the trace has no device plane."""
    with _mine():
        return gdn_trace.facts(trace_dir)
