"""From a profiler trace (``.xplane.pb``) to numbers.

The profiler writes one *plane* per device (``/device:TPU:0`` ...) and
one for the host.  A device plane has several *lines* that cover the
same time at different grain — ``Steps`` contains ``XLA Modules``
(one event per execution of a compiled program) contains ``XLA Ops``
(one event per HLO operation) — so durations are only ever summed
within ONE line; summing a plane counts the same microsecond three
times and is how a busy time comes to exceed its window.

``load_xplane`` turns the protobuf into plain lists (the only place
that needs jax); every reduction below works on that plain form, which
is also what ``chipbench/testdata/*.json`` holds, so the arithmetic is
tested without a profiler.

Plain form::

    {"planes": [{"name": str, "lines": [{"name": str,
        "events": [[name, start_ns, duration_ns, {stat: value}], ...]}]}]}

On the op lines the profiler names an event by the whole text of its
HLO instruction (up to 2 KB: result shape, operands, attributes).
``compact_name`` keeps what the reductions read: ``fusion.363 = fusion``,
``closed_call.11 = custom-call:tpu_custom_call``, ``all-gather.3 =
all-gather``.  The op line nests too: a ``while`` or ``call`` covers the
operations of its body, which is why times are unions and the top
operations are ranked by *self* time.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
PALLAS_TARGET = "tpu_custom_call"
_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
#: stats kept of an event (the TPU's op events carry none; the CPU
#: rehearsal finds XLA's operations by these)
KEEP_STATS = ("hlo_module", "run_id")
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast")  # opcode prefixes


class TraceError(Exception):
    """The trace does not hold what a traced run must report."""


def compact_name(text: str) -> str:
    """``%name = <shape> opcode(operands), attrs`` -> ``name = opcode``
    (``name = custom-call:<target>`` for a custom call).  The opcode is
    the first lower-case word directly before a ``(`` after the ``=``:
    shapes hold only ``T(``/``S(`` tilings and ``[`` dimensions.  Text
    that is not an HLO instruction (a module, a step) is kept."""
    head, sep, rest = text.partition(" = ")
    if not sep or not head.startswith("%"):
        return text[:200]
    m = _OPCODE.search(" " + rest)
    opcode = m.group(1) if m else "?"
    if opcode == "custom-call":
        t = _TARGET.search(rest)
        opcode += ":" + (t.group(1) if t else "?")
    return f"{head[1:]} = {opcode}"


def opcode(name: str) -> str:
    """The opcode of a compact event name ('' if it has none)."""
    return name.partition(" = ")[2]


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    if not found:
        raise TraceError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load_xplane(path: str) -> dict:
    from jax.profiler import ProfileData  # opens no backend

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            events = []
            for e in line.events:
                stats = {}
                for k, v in e.stats:
                    if k in KEEP_STATS and isinstance(v, (str, int, float)):
                        stats[k] = v if not isinstance(v, str) else v[:200]
                events.append([compact_name(e.name), float(e.start_ns), float(e.duration_ns), stats])
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def device_planes(trace: dict) -> List[dict]:
    """The per-chip planes, in device order."""
    found = []
    for p in trace["planes"]:
        m = DEVICE_PLANE.match(p["name"])
        if m:
            found.append((int(m.group(1)), p))
    return [p for _i, p in sorted(found, key=lambda t: t[0])]


def rehearsal_device_planes(trace: dict) -> List[dict]:
    """CPU rehearsal only: the host has no device plane, XLA's CPU ops
    sit on host threads carrying an ``hlo_module`` stat.  Gathers them
    into one stand-in plane so the control flow of a traced run can be
    walked here.  Its numbers mean nothing and go nowhere."""
    ops = []
    for p in trace["planes"]:
        for ln in p["lines"]:
            ops += [e for e in ln["events"] if "hlo_module" in e[3] and e[2] > 0]
    if not ops:
        return []
    ops.sort(key=lambda e: e[1])
    modules: Dict[Tuple, List[float]] = {}
    for _n, s, d, st in ops:
        key = (st["hlo_module"], st.get("run_id"))
        lo_hi = modules.setdefault(key, [s, s + d])
        lo_hi[0], lo_hi[1] = min(lo_hi[0], s), max(lo_hi[1], s + d)
    mods = [[k[0], lo, hi - lo, {}] for k, (lo, hi) in modules.items()]
    return [{"name": "/device:TPU:0", "lines": [
        {"name": OPS_LINE, "events": ops},
        {"name": MODULES_LINE, "events": sorted(mods, key=lambda e: e[1])},
    ]}]


def line(plane: dict, name: str) -> Optional[dict]:
    for ln in plane["lines"]:
        if ln["name"] == name:
            return ln
    return None


def union_ns(intervals: Iterable[Tuple[float, float]]) -> float:
    """Total length covered by (start, end) intervals, overlaps once."""
    total, cur_lo, cur_hi = 0.0, None, None
    for lo, hi in sorted(intervals):
        if hi <= lo:
            continue
        if cur_hi is None or lo > cur_hi:
            if cur_hi is not None:
                total += cur_hi - cur_lo
            cur_lo, cur_hi = lo, hi
        elif hi > cur_hi:
            cur_hi = hi
    if cur_hi is not None:
        total += cur_hi - cur_lo
    return total


def _clip(events, lo, hi):
    for _n, s, d, _st in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            yield a, b


def window_ns(planes: List[dict]) -> Tuple[float, float]:
    """First start to last end over the op lines of all device planes
    (one clock): the least the traced interval can be (see ``busy``)."""
    lo, hi = None, None
    for p in planes:
        ln = line(p, OPS_LINE)
        for _n, s, d, _st in (ln["events"] if ln else ()):
            lo = s if lo is None else min(lo, s)
            hi = s + d if hi is None else max(hi, s + d)
    if lo is None or hi <= lo:
        raise TraceError("no operation ran on any device plane in the trace")
    return lo, hi


def busy(planes: List[dict], host_s: Optional[float] = None) -> Tuple[float, float]:
    """(busy_s, window_s): per device the union of the op line's
    intervals clipped to the window, then the MEAN over devices.

    ``host_s`` is the host-clock time between the profiler's start
    returning and its stop being called, all of which the trace covers.
    Where it is longer than first-operation-to-last, the device was
    idle at the edges and the window is ``host_s``; it is never taken
    shorter than the operations' own span, so busy_s <= window_s holds
    by construction."""
    if not planes:
        raise TraceError("the trace holds no device plane")
    lo, hi = window_ns(planes)
    per_device = []
    for p in planes:
        ln = line(p, OPS_LINE)
        if ln is None or not ln["events"]:
            raise TraceError(f"plane {p['name']} has no {OPS_LINE!r} events")
        per_device.append(union_ns(_clip(ln["events"], lo, hi)))
    busy_s = sum(per_device) / len(per_device) / 1e9
    window_s = max((hi - lo) / 1e9, host_s or 0.0)
    if not 0 < busy_s <= window_s:
        raise TraceError(f"busy {busy_s} s outside (0, window {window_s} s]")
    return busy_s, window_s


def op_seconds(planes: List[dict], match) -> float:
    """Mean over devices of the union of the op-line events whose
    compact name ``match`` accepts, clipped to the window, in seconds."""
    lo, hi = window_ns(planes)
    tot = 0.0
    for p in planes:
        ln = line(p, OPS_LINE)
        tot += union_ns(_clip(
            [e for e in (ln["events"] if ln else ()) if match(e[0])], lo, hi
        ))
    return tot / len(planes) / 1e9


def is_collective(name: str) -> bool:
    """all-gather, all-reduce, reduce-scatter, collective-permute ...
    and their ``-start``/``-done`` forms: on the op line a ``-done`` is
    the time the core waits for a transfer still in flight.  (The spans
    in flight are on the ``Async XLA Ops`` line, which the profiler
    writes for the first chip only; they overlap compute and each
    other and are not device time.)"""
    return opcode(name).startswith(COLLECTIVES)


def is_pallas(name: str) -> bool:
    """A Pallas (Mosaic) kernel: the custom call whose target is
    ``tpu_custom_call`` (XLA's own ``AllocateBuffer``/``ConcatBitcast``
    custom calls are not kernels)."""
    return opcode(name) == "custom-call:" + PALLAS_TARGET


def module_durations_ms(planes: List[dict], program: str) -> List[float]:
    """Device durations of each execution of the jitted program
    ``program`` (``jit_<program>``) on the first device's module line."""
    ln = line(planes[0], MODULES_LINE)
    if ln is None:
        raise TraceError(f"plane {planes[0]['name']} has no {MODULES_LINE!r} line")
    want = "jit_" + program
    return [
        d / 1e6 for name, _s, d, _st in ln["events"]
        if name == want or name.startswith(want + "(")
    ]


def self_times_ns(events) -> Dict[str, float]:
    """Per event name, the time in its events that no event nested
    inside them covers (a ``while`` minus its body's operations)."""
    tot: Dict[str, float] = {}
    stack: List[list] = []  # [name, end, self]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, _end, own = stack.pop()
            tot[name] = tot.get(name, 0.0) + max(own, 0.0)

    for name, s, d, _st in sorted(events, key=lambda e: (e[1], -e[2])):
        close(s)
        if stack:
            stack[-1][2] -= min(d, stack[-1][1] - s)
        stack.append([name, s + d, d])
    close(float("inf"))
    return tot


def top_ops(planes: List[dict], n: int = 10) -> List[list]:
    """The operations with most self time on the first device, by the
    names the trace gives: [[name, seconds], ...]."""
    tot = self_times_ns(line(planes[0], OPS_LINE)["events"])
    return [[k, tot[k] / 1e9] for k in sorted(tot, key=tot.get, reverse=True)[:n]]


def idle_gaps(planes: List[dict], n: int = 10) -> List[list]:
    """The longest idle gaps on the first device's op line.  Until the
    program has host spans a gap's cause is unknown, so each is named
    by the two operations it lies between."""
    ln = line(planes[0], OPS_LINE)
    events = sorted(ln["events"], key=lambda e: e[1])
    gaps, end, last = [], None, None
    for name, s, d, _st in events:
        if end is not None and s > end:
            gaps.append([f"unattributed: after {last[:40]} before {name[:40]}",
                         (s - end) / 1e9])
        if end is None or s + d > end:
            end, last = s + d, name
    return sorted(gaps, key=lambda g: g[1], reverse=True)[:n]
