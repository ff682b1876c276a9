"""Device time of the block attention of a block-diffusion step: which
operations of a trace ran under ``block_attn``, the ``jax.named_scope``
``ray_tpu/models/llama.py`` gives the attention of a configuration with a
block mask behind its projections (the block's K/V rows written into the
cache, the layer's slabs read — which XLA copies — scores, mask, softmax,
mix; the projections and the output projection are outside it).

``chipbench/mtp_trace.py``'s reduction with this file's scope in place of its
own (``chipbench/dsa_trace.py`` says why the scopes have to come from the
compiled programs' text, and how an execution finds its version): the facts
are ``block_attn_device_s`` (every program), ``block_attn_decode_device_s``
(inside the decode program's executions) and ``decode_executions_traced``.
"""

from __future__ import annotations

import json
import os
from typing import Dict
from unittest import mock

from chipbench import mtp_trace, trace_reduce

SCOPES = ("block_attn",)
SCOPE_FILE = "diffusion_scopes.json"


def version(hlo_text: str) -> dict:
    """One compiled version of a program, as ``mtp_trace.version``."""
    with mock.patch.object(mtp_trace, "SCOPES", SCOPES):
        return mtp_trace.version(hlo_text)


def reduce(planes, versions) -> Dict[str, float]:
    with mock.patch.object(mtp_trace, "SCOPES", SCOPES):
        return mtp_trace.reduce(planes, versions)


def facts(trace_dir: str) -> Dict[str, float]:
    """The job's facts for the scope readers; {} where the replica wrote no
    programs' versions beside the trace."""
    path = os.path.join(trace_dir, SCOPE_FILE)
    if not os.path.isfile(path):
        return {}
    with open(path) as f:
        versions = json.load(f)
    trace = trace_reduce.load_xplane(trace_reduce.find_xplane(trace_dir))
    planes = trace_reduce.device_planes(trace)
    if not planes:  # a CPU rehearsal has no device plane
        return {}
    return reduce(planes, versions)
