"""From the program's ``rt.stall`` spans to the two per-layer numbers
that stand beside ``device_idle_share``: how much of the traced window
the host of the cell's chips stood still, and how much of that no change
to the program made.

The program's stall witness (``ray_tpu/core/stall.py``) records every
stop of a process's io loop from 20 ms as a span with its evidence, its
cause and ``profiling`` (true where a jax profiler session ran in that
process at both ends of the stop), and ``ray_tpu.util.state.stalls()``
joins the cluster's.  The readers ``chipbench/layer_metrics/
host_stall_share.py`` and ``host_stall_outside_share.py`` end here:
``reduce_stalls`` sums ``late_ms`` over the stops of the *holder* (the
process whose ``rt.start.chip_open`` opened the cell's chips:
``startup_reduce.holder_open``) that have ``profiling`` true, over the
traced window; which of them no change to the program made is the
program's to say (``outside``, decided in ``stall.join`` alone).  The profiler's session IS the window, so no clock has to
be aligned, and a cell whose engine records no step spans reads it too.

Plain lists in (``state.stalls()``'s dicts, span dicts), so it is tested
without a chip on ``chipbench/testdata/stall_spans.json``.  Nothing here
imports jax.
"""

from __future__ import annotations

import sys
from typing import Dict, List, Optional, Sequence

from chipbench import startup_reduce

METRICS = ("host_stall_share", "host_stall_outside_share")
ROWS = 30  # of the table: the longest stops


def _say(msg: str) -> None:
    print(f"[chipbench] stops: {msg}", file=sys.stderr, flush=True)


def reduce_stalls(stalls: Sequence[dict], opens: Sequence[dict],
                  window_s: float) -> Optional[dict]:
    """Both metrics in % of ``window_s``, with ``holder`` (its pid) and
    ``stops`` (how many were summed).  None where no process opened a
    chip: there is no holder to read."""
    opened = startup_reduce.holder_open(opens)
    if opened is None:
        return None
    own = [s for s in stalls if s["pid"] == opened["pid"] and s["profiling"]]
    total_ms = sum(s["late_ms"] for s in own)
    # which stop no change to the program made is the program's to say
    outside_ms = sum(s["late_ms"] for s in own if s["outside"])
    return {
        "holder": opened["pid"], "stops": len(own),
        "host_stall_share": 100.0 * total_ms / 1e3 / window_s,
        "host_stall_outside_share": 100.0 * outside_ms / 1e3 / window_s,
    }


def table(stalls: Sequence[dict], holder: Optional[int], t0_ns: int) -> str:
    """The run's stops: seconds by process and cause, then the ``ROWS``
    longest in order of start (``t0_ns``: what their start is told from)."""
    by: Dict[tuple, List[float]] = {}
    for s in stalls:
        who = f"{'holder' if s['pid'] == holder else s['role']} {s['pid']}"
        by.setdefault((who, s["cause"]), []).append(s["late_ms"])
    rows = ["process | cause | stops | s in all | longest s"]
    rows += [
        f"{who} | {cause} | {len(v)} | {sum(v) / 1e3:.3f} | {max(v) / 1e3:.3f}"
        for (who, cause), v in sorted(by.items(), key=lambda kv: -sum(kv[1]))
    ]
    rows.append("start s | process | late s | cause | reading | in the trace | where")
    longest = sorted(stalls, key=lambda s: -s["late_ms"])[:ROWS]
    for s in sorted(longest, key=lambda s: s["start_ns"]):
        rows.append(
            f"{(s['start_ns'] - t0_ns) / 1e9:.3f} | "
            f"{'holder' if s['pid'] == holder else s['role']} {s['pid']} | "
            f"{s['late_ms'] / 1e3:.3f} | {s['cause']} | {s['reading']} | "
            f"{'yes' if s['profiling'] else 'no'} | {s['where'] or '-'}"
        )
    return "\n".join(rows)


def fetch() -> Optional[dict]:
    """``{"stalls": [...], "opens": [...]}`` of this run from the GCS
    (the cluster is still up when readers run).  None where the program
    has no ``state.stalls``: a commit from before the witness kept a
    record."""
    from ray_tpu.util import state, tracing

    if not hasattr(state, "stalls"):
        return None
    return {"stalls": state.stalls(),
            "opens": tracing.collect(name_prefix="rt.start.chip_open")}


def value(ctx: dict, key: str) -> float:
    """What the reader of ``key`` returns.  The GCS is asked once a run,
    the table printed once, and the result kept in ``ctx`` (``run.py``
    hands every reader the same dict), as ``startup_reduce.value`` does.
    Where the program keeps no record of its stops, 0 stands in and the
    log says so."""
    if "_stall_reduce" not in ctx:
        got = fetch()
        reduced = got and reduce_stalls(got["stalls"], got["opens"], ctx["window_s"])
        if reduced:
            opened = startup_reduce.holder_open(got["opens"])
            _say("the run's\n" + table(got["stalls"], reduced["holder"],
                                       opened["start_ns"]))
            _say(f"holder {reduced['holder']}: {reduced['stops']} stop(s) inside "
                 f"the traced window of {ctx['window_s']:.3f} s; "
                 + ", ".join(f"{m} {reduced[m]:.3f}" for m in METRICS))
        ctx["_stall_reduce"] = reduced
    reduced = ctx["_stall_reduce"]
    if not reduced:
        _say(f"{key}: this program keeps no record of its stops (or no process "
             "opened a chip); 0 stands in")
        return 0.0
    return reduced[key]
