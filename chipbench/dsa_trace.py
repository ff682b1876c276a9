"""Device time of the sparse-attention path: which operations of a
trace ran under which ``jax.named_scope``.

The profiler names an operation by its HLO instruction (``%fusion.583 =
bf16[65536,640] fusion(...)``, kept by ``trace_reduce.load_xplane`` as
``fusion.583 = fusion``) and carries nothing of the scope it was traced
under (looked for on the chip, PR 30: neither the instruction text nor
an event's stats hold an ``op_name``).  The compiled program's text
does: every instruction's ``metadata={op_name="jit(decode_step_rowwise)/
while/body/closed_call/decode_attn/dsa_select/gather"}``.  So the
replica, which holds the chip and the programs, writes beside the trace
what each compiled VERSION of each program says (``version`` of
``compiled.as_text()``; ``jobs/serve_dsa.py``): every instruction's name,
and the scope of those traced under one.  ``facts`` joins that with the
trace: an operation belongs to the program execution (module line) it
lies inside, and to the scope its name has in the version that
execution ran.  A prefill compiles once per prompt length and numbers
its instructions anew each time — ``fusion.816`` is attention's in one
version and the experts' in the other — and the trace names a module
``jit_prefill_into_slot(<a number no compiled object shows>)``, so an
execution's version is found by its operations: the one that has the
most of their names among its instructions (``scopes_for``).  A fused
operation carries the ``op_name`` of its root, so a fusion that spans
two scopes counts under its last operation's.

The scopes ``ray_tpu/models/llama.py`` gives the path: ``dsa_index``
(the indexer's scores over every visible key), ``dsa_select`` (exact
top-k and the gather of the chosen latent rows), ``mla_attn`` (attention
over them).

``run.py`` removes the trace directory after it has reduced it, before
any reader runs, so the job calls ``facts`` while the directory is
still there and hands the readers numbers.  Without the versions' file (a
program without the path: the parent commit) there are no facts, and
the two readers find nothing to read.
"""

from __future__ import annotations

import bisect
import json
import os
import re
from typing import Dict, List, Optional

from chipbench import trace_reduce

SCOPES = ("dsa_index", "dsa_select", "mla_attn")
DECODE_PROGRAM = "decode_step_rowwise"
SCOPE_FILE = "dsa_scopes.json"
_INSTRUCTION = re.compile(
    r'^\s*(?:ROOT )?%([\w.\-]+) = .*?metadata=\{[^}]*?op_name="([^"]*)"'
)
_NAME = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = ")


def scope_of(op_name: str, scopes=SCOPES) -> Optional[str]:
    """The innermost of ``scopes`` on an ``op_name`` path, or None."""
    best, at = None, -1
    parts = op_name.split("/")
    for s in scopes:
        if s in parts:
            i = len(parts) - 1 - parts[::-1].index(s)
            if i > at:
                best, at = s, i
    return best


def scope_map(hlo_text: str, scopes=SCOPES) -> Dict[str, str]:
    """Instruction name -> scope, for the instructions of a compiled
    program's text that were traced under one of ``scopes``."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m:
            scope = scope_of(m.group(2), scopes)
            if scope:
                out[m.group(1)] = scope
    return out


def version(hlo_text: str, scopes=SCOPES) -> dict:
    """One compiled version of a program: {"names": every instruction's
    name, "scopes": ``scope_map``}."""
    names = [m.group(1) for m in map(_NAME.match, hlo_text.splitlines()) if m]
    return {"names": names, "scopes": scope_map(hlo_text, scopes)}


def scopes_for(versions: List[dict], seen: set) -> Dict[str, str]:
    """The scope map of the version an execution ran, ``seen`` being the
    names of the operations inside it: the version with the most of them
    among its instructions.  Versions that have equally many (two
    lengths that compiled to the same numbering) give what they agree
    on: a name under a scope in one and under another or none in the
    next is left out, so nothing is counted that may not be the path's."""
    if not versions:
        return {}
    has = [len(seen.intersection(v["names"])) for v in versions]
    best = [v["scopes"] for v, n in zip(versions, has) if n == max(has)]
    return {name: scope for name, scope in best[0].items()
            if all(b.get(name) == scope for b in best[1:])}


def program_of(module_name: str) -> str:
    """``jit_decode_step_rowwise(4604659647685780638)`` ->
    ``decode_step_rowwise``."""
    name = module_name.split("(", 1)[0]
    return name[4:] if name.startswith("jit_") else name


def reduce(planes: List[dict], versions: Dict[str, List[dict]]) -> Dict[str, float]:
    """Seconds on the first device, each a union of intervals (a
    ``while`` or ``call`` under a scope covers its body's operations;
    none is counted twice): ``sparse_attn_device_s`` all three scopes in
    every program, ``sparse_attn_decode_device_s`` the part inside the
    decode program's executions, ``dsa_scope_s.<scope>`` each scope
    alone; and ``decode_executions_traced``.  ``versions``: program ->
    its compiled versions (``version``)."""
    plane = planes[0]
    modules = sorted(
        (s, s + d, program_of(name))
        for name, s, d, _st in trace_reduce.line(plane, trace_reduce.MODULES_LINE)["events"]
    )
    starts = [m[0] for m in modules]
    inside = [[] for _ in modules]  # per execution: (instruction, start, end)
    for name, s, d, _st in trace_reduce.line(plane, trace_reduce.OPS_LINE)["events"]:
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < modules[i][1]:
            inside[i].append((name.partition(" = ")[0], s, s + d))
    found = []  # (scope, start, end, program)
    for (_s, _e, program), ops in zip(modules, inside):
        scopes = scopes_for(versions.get(program, []), {op[0] for op in ops})
        found += [(scopes[n], a, b, program) for n, a, b in ops if n in scopes]

    def seconds(events):
        return trace_reduce.union_ns((a, b) for _sc, a, b, _p in events) / 1e9

    out = {
        "sparse_attn_device_s": seconds(found),
        "sparse_attn_decode_device_s": seconds(
            [e for e in found if e[3] == DECODE_PROGRAM]),
        "decode_executions_traced": sum(1 for m in modules if m[2] == DECODE_PROGRAM),
    }
    for scope in SCOPES:
        out["dsa_scope_s." + scope] = seconds([e for e in found if e[0] == scope])
    return out


def facts(trace_dir: str) -> Dict[str, float]:
    """The job's facts for the two sparse-attention readers; {} where
    the replica wrote no programs' versions beside the trace."""
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
