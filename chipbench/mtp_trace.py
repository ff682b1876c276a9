"""Device time of a speculative step's parts: which operations of a trace
ran under ``mtp_draft`` (the multi-token-prediction module: embedding,
norms, projection, its block, the head) and under ``mla_attn`` (latent
attention, the module's block's too), the ``jax.named_scope``s
``ray_tpu/models/mtp.py`` and ``ray_tpu/models/llama.py`` give them.

As ``chipbench/dsa_trace.py`` (which says why the scopes have to come from
the compiled programs' text, and how an execution finds its version), with
one difference: the scopes here NEST — the module's attention runs under
both — so a version keeps, for each scope, every instruction whose
``op_name`` path holds it anywhere, and each scope's time is the union of
its own instructions' intervals.
"""

from __future__ import annotations

import bisect
import json
import os
import re
from typing import Dict, List

from chipbench import trace_reduce
from chipbench.dsa_trace import program_of

SCOPES = ("mtp_draft", "mla_attn")
DECODE_PROGRAM = "decode_step_rowwise"
SCOPE_FILE = "mtp_scopes.json"
_INSTRUCTION = re.compile(
    r'^\s*(?:ROOT )?%([\w.\-]+) = (?:.*?metadata=\{[^}]*?op_name="([^"]*)")?'
)


def version(hlo_text: str) -> dict:
    """One compiled version of a program: {"names": every instruction's
    name, "scopes": {scope: the names traced under it}}."""
    names, scopes = [], {s: [] for s in SCOPES}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        names.append(m.group(1))
        parts = (m.group(2) or "").split("/")
        for s in SCOPES:
            if s in parts:
                scopes[s].append(m.group(1))
    return {"names": names, "scopes": scopes}


def _version_of(versions: List[dict], seen: set):
    """The version an execution ran: the one with the most of the
    operations seen inside it among its instructions."""
    if not versions:
        return None
    return max(versions, key=lambda v: len(seen.intersection(v["names"])))


def reduce(planes: List[dict], versions: Dict[str, List[dict]]) -> Dict[str, float]:
    """Seconds on the first device, each a union of intervals:
    ``<scope>_device_s`` in every program, ``<scope>_decode_device_s``
    inside the decode program's executions; and
    ``decode_executions_traced``."""
    plane = planes[0]
    modules = sorted(
        (s, s + d, program_of(name))
        for name, s, d, _st in trace_reduce.line(plane, trace_reduce.MODULES_LINE)["events"]
    )
    starts = [m[0] for m in modules]
    inside = [[] for _ in modules]
    for name, s, d, _st in trace_reduce.line(plane, trace_reduce.OPS_LINE)["events"]:
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < modules[i][1]:
            inside[i].append((name.partition(" = ")[0], s, s + d))
    found = {s: [] for s in SCOPES}  # scope -> (start, end, program)
    for (_s, _e, program), ops in zip(modules, inside):
        v = _version_of(versions.get(program, []), {op[0] for op in ops})
        if v is None:
            continue
        for scope in SCOPES:
            mine = set(v["scopes"][scope])
            found[scope] += [(a, b, program) for n, a, b in ops if n in mine]
    out = {"decode_executions_traced": sum(1 for m in modules if m[2] == DECODE_PROGRAM)}
    for scope, events in found.items():
        out[scope + "_device_s"] = trace_reduce.union_ns(
            (a, b) for a, b, _p in events) / 1e9
        out[scope + "_decode_device_s"] = trace_reduce.union_ns(
            (a, b) for a, b, p in events if p == DECODE_PROGRAM) / 1e9
    return out


def facts(trace_dir: str) -> Dict[str, float]:
    """The job's facts for the scope readers; {} where the replica wrote
    no programs' versions beside the trace."""
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


def host_step_ms(ctx: dict) -> Dict[str, float]:
    """What the host does for one decode step, from the engine's own spans
    alone: medians, over the ``llm.step``s of the run that admitted nothing,
    of ``dispatch`` (``llm.step.build`` + ``llm.step.dispatch``), ``deliver``
    and ``serve_plane`` (``llm.step.yield``) in ms — ``span_reduce``'s three
    quantities of those names, as it reduces them.  Durations on the host's
    clock need no pairing of spans with the trace's executions, and this
    asks for none: ``span_reduce.align`` wants the offset between the two
    clocks known to 2 ms, which for this cell's step (a 41-layer program
    launched onto an idle device once an admission, its tokens fetched
    after it) comes out at 1.75-1.9 ms in every traced run (PERF.md section
    6, PR 32).  {} where the program records no spans or no such step.
    Reduced once a run and kept in ``ctx``."""
    if "_mtp_host_step_ms" not in ctx:
        from chipbench import span_reduce
        from chipbench.loadgen import percentile

        got = span_reduce.fetch(ctx)
        steps = [s for s in span_reduce.steps_of(got["spans"]) if not s["admitted"]] if got else []

        def ms(step, name):
            part = step["parts"][name]
            return (part["end_ns"] - part["start_ns"]) / 1e6

        ctx["_mtp_host_step_ms"] = {
            "dispatch": percentile([ms(s, "build") + ms(s, "dispatch") for s in steps], 50),
            "deliver": percentile([ms(s, "deliver") for s in steps], 50),
            "serve_plane": percentile([ms(s, "yield") for s in steps], 50),
        } if steps else {}
    return ctx["_mtp_host_step_ms"]
