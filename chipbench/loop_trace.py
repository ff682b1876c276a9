"""Device time of a looped decoder's step by scope and by pass: which
operations of a trace ran under ``loop_pass`` (one (pass, layer) of the ONE
loop: the block, and behind a pass's last layer the final norm and the exit
gate) and under ``loop_attn`` (the new keys written and attention over the
cache, the kernel ``kv_decode`` in a decode step), the ``jax.named_scope``s
``ray_tpu/models/llama.py:_looped`` / ``_kv_attention`` give them.

``chipbench/gdn_trace.py``'s reduction with this file's scopes in place of its
own (``chipbench/dsa_trace.py`` says why the scopes have to come from the
compiled programs' text, and how an execution finds its version): facts
``loop_<scope>_device_s`` (every program), ``loop_<scope>_decode_device_s``
(inside the decode program's executions), ``decode_executions_traced`` /
``decode_device_s_traced``, ``prefill_executions_traced`` /
``prefill_device_s_traced`` / ``prefill_tokens_traced``.  And one thing beside
it, ``loop_pass_decode_device_s_by_pass``: the loop's body is ONE piece of
program that every (pass, layer) runs, so an operation of it runs ``passes x
layers`` times an execution (behind a pass's end: ``passes`` times), in order;
its i-th of n runs belongs to pass ``i * passes // n``.  A program without
these scopes (a commit from before them) gives the readers nothing to read.
"""

from __future__ import annotations

import bisect
import json
import os
from typing import Dict, List
from unittest import mock

from chipbench import gdn_trace, mtp_trace, trace_reduce
from chipbench.dsa_trace import program_of

SCOPES = ("loop_pass", "loop_attn")
SCOPE_FILE = "loop_scopes.json"


def _mine():
    return mock.patch.multiple(gdn_trace, SCOPES=SCOPES, SCOPE_FILE=SCOPE_FILE)


def version(hlo_text: str) -> dict:
    """One compiled version of a program, as ``gdn_trace.version``."""
    with _mine():
        return gdn_trace.version(hlo_text)


def by_pass(planes: List[dict], versions: Dict[str, List[dict]], passes: int) -> List[float]:
    """Seconds under ``loop_pass`` inside the decode program's executions on
    the first device, pass by pass (each a union of intervals)."""
    plane = planes[0]
    modules = sorted(
        (s, s + d) for name, s, d, _st in
        trace_reduce.line(plane, trace_reduce.MODULES_LINE)["events"]
        if program_of(name) == gdn_trace.DECODE_PROGRAM)
    starts = [m[0] for m in modules]
    inside = [{} for _ in modules]  # per execution: operation -> its runs
    for name, s, d, _st in trace_reduce.line(plane, trace_reduce.OPS_LINE)["events"]:
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < modules[i][1]:
            inside[i].setdefault(name.partition(" = ")[0], []).append((s, s + d))
    found = [[] for _ in range(passes)]
    for ops in inside:
        v = mtp_trace._version_of(versions.get(gdn_trace.DECODE_PROGRAM, []), set(ops))
        if v is None:
            continue
        for name in set(v["scopes"]["loop_pass"]).intersection(ops):
            runs = sorted(ops[name])
            for i, run in enumerate(runs):
                found[i * passes // len(runs)].append(run)
    return [trace_reduce.union_ns(runs) / 1e9 for runs in found]


def reduce(planes, versions, prompt_lens, passes: int) -> Dict[str, float]:
    with _mine():
        out = gdn_trace.reduce(planes, versions, prompt_lens)
    out["loop_pass_decode_device_s_by_pass"] = by_pass(planes, versions, passes)
    return out


def facts(trace_dir: str) -> Dict[str, float]:
    """The job's facts for the scope readers; {} where the replica wrote no
    programs' versions beside the trace, or the trace has no device plane."""
    path = os.path.join(trace_dir, SCOPE_FILE)
    if not os.path.isfile(path):
        return {}
    with open(path) as f:
        wrote = json.load(f)
    trace = trace_reduce.load_xplane(trace_reduce.find_xplane(trace_dir))
    planes = trace_reduce.device_planes(trace)
    if not planes:  # a CPU rehearsal has no device plane
        return {}
    return reduce(planes, wrote["versions"], wrote["prompt_lens"], wrote["passes"])
