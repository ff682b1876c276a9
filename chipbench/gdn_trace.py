"""Device time of a gated-delta-rule layer's parts: which operations of a
trace ran under ``gdn_proj`` (the six projections and the short
convolution), ``gdn_step`` (the one-token update of every row's state),
``gdn_scan`` (a prefill's chunked rule) and ``gdn_out`` (gated norm and
W_o), the ``jax.named_scope``s ``ray_tpu/models/llama.py:
_gated_delta_mixer`` gives them inside the block's ``decode_attn``.

``chipbench/mtp_trace.py``'s reduction with this file's scopes in place of
its own (``chipbench/dsa_trace.py`` says why the scopes have to come from the
compiled programs' text, and how an execution finds its version), and one
thing beside it: a prefill's work goes by its prompt's length, so ``reduce``
also says WHICH version each traced prefill ran — the replica writes the
prefill's versions in the order of the mix's prompt lengths — and adds up
the tokens of the traced prefills.  Facts:
``gdn_<scope>_device_s`` (every program), ``gdn_<scope>_decode_device_s``
(inside the decode program's executions), ``decode_executions_traced`` /
``decode_device_s_traced``, ``prefill_executions_traced`` /
``prefill_device_s_traced`` / ``prefill_tokens_traced``.
"""

from __future__ import annotations

import bisect
import json
import os
from typing import Dict, List
from unittest import mock

from chipbench import mtp_trace, trace_reduce
from chipbench.dsa_trace import program_of

SCOPES = ("gdn_proj", "gdn_step", "gdn_scan", "gdn_out")
SCOPE_FILE = "gdn_scopes.json"
DECODE_PROGRAM, PREFILL_PROGRAM = "decode_step_rowwise", "prefill_into_slot"


def version(hlo_text: str) -> dict:
    """One compiled version of a program, as ``mtp_trace.version``."""
    with mock.patch.object(mtp_trace, "SCOPES", SCOPES):
        return mtp_trace.version(hlo_text)


def reduce(planes: List[dict], versions: Dict[str, List[dict]],
           prompt_lens: List[int]) -> Dict[str, float]:
    """``mtp_trace.reduce``'s seconds under this file's scopes, and the two
    programs' executions: how many, their device seconds, and the prefills'
    tokens.  ``versions``: program -> its compiled versions (``version``),
    the prefill's in the order of ``prompt_lens``.  A traced prefill whose
    operations fit several versions equally well counts the mean of their
    lengths."""
    with mock.patch.object(mtp_trace, "SCOPES", SCOPES):
        out = mtp_trace.reduce(planes, versions)
    plane = planes[0]
    modules = sorted(
        (s, s + d, program_of(name))
        for name, s, d, _st in trace_reduce.line(plane, trace_reduce.MODULES_LINE)["events"]
    )
    prefills = [m for m in modules if m[2] == PREFILL_PROGRAM]
    starts = [m[0] for m in prefills]
    seen = [set() for _ in prefills]  # per prefill: its operations' names
    for name, s, _d, _st in trace_reduce.line(plane, trace_reduce.OPS_LINE)["events"]:
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < prefills[i][1]:
            seen[i].add(name.partition(" = ")[0])
    tokens = 0.0
    for names in seen:
        fit = [len(names.intersection(v["names"])) for v in versions.get(PREFILL_PROGRAM, [])]
        best = [prompt_lens[i] for i, n in enumerate(fit) if n == max(fit)]
        tokens += sum(best) / max(1, len(best))
    decodes = [e - s for s, e, p in modules if p == DECODE_PROGRAM]
    out.update({
        "decode_device_s_traced": sum(decodes) / 1e9,
        "prefill_executions_traced": len(prefills),
        "prefill_device_s_traced": sum(e - s for s, e, _p in prefills) / 1e9,
        "prefill_tokens_traced": tokens,
    })
    return out


def facts(trace_dir: str) -> Dict[str, float]:
    """The job's facts for the scope readers; {} where the replica wrote no
    programs' versions beside the trace."""
    path = os.path.join(trace_dir, SCOPE_FILE)
    if not os.path.isfile(path):
        return {}
    with open(path) as f:
        wrote = json.load(f)
    trace = trace_reduce.load_xplane(trace_reduce.find_xplane(trace_dir))
    planes = trace_reduce.device_planes(trace)
    if not planes:  # a CPU rehearsal has no device plane
        return {}
    return reduce(planes, wrote["versions"], wrote["prompt_lens"])
