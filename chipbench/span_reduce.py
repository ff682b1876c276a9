"""From the program's own spans and counters to per-layer numbers.

The serving engine records spans of every iteration of its loop while a
profiler session runs in its process (``ray_tpu/util/tracing.py``,
``ray_tpu/serve/llm.py``; names in PERF.md section 3), and two
histograms always.  The readers under ``chipbench/layer_metrics/`` that
end here take them from the GCS after the job (``fetch``), put them on
the device trace's clock (``align``), and say which span each idle gap
of the device belongs to (``gap_owner``).

Two clocks: a span's ``start_ns`` / ``end_ns`` are ``time.time_ns()`` of
the replica's host; event times of the device planes are relative to
the profiler session's start.  Nothing here subtracts one from the
other.  ``align`` finds the one offset between them by cause and
effect, as an interval, and raises when it cannot: a reader that cannot
align fails the run, it does not print a number.  Every duration
reported is a host-span duration or a device duration.  Times are kept
as whole nanoseconds: a float holds an epoch stamp to 256 ns only.

The arithmetic works on plain lists (the plain form of
``trace_reduce`` and span dicts as ``tracing.collect`` returns them),
so it is tested without a chip on ``chipbench/testdata/serve_spans.json``.
Nothing here imports jax.
"""

from __future__ import annotations

import json
import math
import sys
from typing import Dict, List, Optional, Sequence, Tuple

from chipbench import trace_reduce
from chipbench.loadgen import percentile

DECODE = "decode_step_rowwise"
#: the engine loop's spans that can own a gap: of these, the ones with
#: no child among them (``llm.request`` spans a request's whole life)
LOOP_SPANS = ("llm.step", "llm.prefill", "llm.idle")
STEP_PARTS = ("build", "dispatch", "sync", "deliver", "yield")
MAX_INTERVAL_NS = 2e6   # a wider alignment interval is an error
MIN_MATCHED = 0.9       # of the decode executions in the trace
MIN_GAP_NS = 50e3       # idle gaps shorter than this have no owner
MAX_SHIFT = 3           # decode executions in flight when spans turned on


class SpanError(Exception):
    """The spans and the trace of a run do not fit together."""


def _say(msg: str) -> None:
    print(f"[chipbench] spans: {msg}", file=sys.stderr, flush=True)


# ---- what a run left behind -------------------------------------------------


def fetch(ctx: dict) -> Optional[dict]:
    """``{"spans": [...], "metrics": [...]}`` of this run from the GCS
    (the cluster is still up when readers run; its span table and its
    metrics outlive the replica).  None where the program has no span
    table to ask: a commit from before the engine recorded spans."""
    from ray_tpu.util import state, tracing

    if not hasattr(tracing, "collect"):
        return None
    return {"spans": tracing.collect(), "metrics": state.get_metrics()}


# ---- alignment --------------------------------------------------------------


def decode_events(planes: List[dict]) -> List[Tuple[int, int, int]]:
    """(start_ns, end_ns, done_ns) of each execution of the decode
    program on the first device's module line, in order, on the trace's
    clock.  ``done_ns`` is the end of the ``jit__argmax`` that follows it
    directly (what ``llm.step.sync`` waits for), else ``end_ns``."""
    ln = trace_reduce.line(planes[0], trace_reduce.MODULES_LINE)
    if ln is None:
        raise SpanError("the first device plane has no module line")
    want = "jit_" + DECODE
    mods = sorted(ln["events"], key=lambda e: e[1])
    out = []
    for i, (name, s, d, _st) in enumerate(mods):
        if name == want or name.startswith(want + "("):
            done = s + d
            if i + 1 < len(mods) and mods[i + 1][0].startswith("jit__argmax"):
                done = mods[i + 1][1] + mods[i + 1][2]
            out.append((int(s), int(s + d), int(done)))
    return out


def steps_of(spans: Sequence[dict]) -> List[dict]:
    """One entry per ``llm.step`` that decoded, in order: the span, its
    five parts by short name, ``launch_ns`` (when the decode program was
    called: the start of ``llm.step.launch`` inside the dispatch, else
    of the dispatch) and the requests it admitted."""
    kids: Dict[str, Dict[str, dict]] = {}
    launches = {}
    for s in spans:
        if s["name"] == "llm.step.launch":
            launches[s["parent_id"]] = s["start_ns"]
        elif s["name"].startswith("llm.step.") and s["parent_id"]:
            kids.setdefault(s["parent_id"], {})[s["name"][len("llm.step."):]] = s
    out = []
    for s in sorted(spans, key=lambda s: s["start_ns"]):
        parts = kids.get(s["span_id"], {})
        if s["name"] == "llm.step" and all(p in parts for p in STEP_PARTS):
            dispatch = parts["dispatch"]
            out.append({
                "span": s, "parts": parts,
                "launch_ns": launches.get(dispatch["span_id"], dispatch["start_ns"]),
                "admitted": s["attributes"].get("admitted", 0),
            })
    return out


def align(planes: List[dict], spans: Sequence[dict]) -> Tuple[int, int]:
    """The offset ``o`` with span clock = trace clock + ``o``, as the
    interval (lo, hi) that cause and effect allow.

    The k-th execution of the decode program in the trace belongs to the
    k-th ``llm.step`` (the profiler session turns the spans on; up to
    ``MAX_SHIFT`` executions were dispatched before the first whole
    step).  An execution cannot start before the program was called
    inside its ``llm.step.dispatch``, and it and the argmax behind it
    cannot end after its ``llm.step.sync`` returned: each pair bounds
    ``o`` from both sides, and the bounds of all pairs must meet.  A
    pairing that is off by a step is refused by any change of the step
    period over the window (a prefill) and, where the period is even,
    gives a narrower interval than the right one, so the widest wins."""
    events, steps = decode_events(planes), steps_of(spans)
    if not events or not steps:
        raise SpanError(
            f"nothing to align: {len(events)} executions of {DECODE} in the "
            f"trace, {len(steps)} llm.step spans"
        )
    best = None
    for shift in range(min(MAX_SHIFT, len(events) - 1) + 1):
        pairs = list(zip(events[shift:], steps))
        lo = max(st["launch_ns"] - ev[0] for ev, st in pairs)
        hi = min(st["parts"]["sync"]["end_ns"] - ev[2] for ev, st in pairs)
        if hi >= lo and (best is None or hi - lo > best[1] - best[0]):
            best = (lo, hi, shift, len(pairs))
    if best is None:
        raise SpanError(
            f"no offset puts the {len(events)} executions of {DECODE} inside "
            f"the {len(steps)} llm.step spans: the alignment interval is empty"
        )
    lo, hi, shift, matched = best
    if matched < MIN_MATCHED * len(events):
        raise SpanError(
            f"only {matched} of {len(events)} executions of {DECODE} found "
            "their llm.step"
        )
    if hi - lo > MAX_INTERVAL_NS:
        raise SpanError(
            f"the alignment interval is {(hi - lo) / 1e6:.3f} ms wide, over "
            f"{MAX_INTERVAL_NS / 1e6:.0f} ms"
        )
    _say(f"aligned {matched} of {len(events)} decode executions with "
         f"{len(steps)} steps ({shift} in flight before the first); the offset "
         f"is known to {(hi - lo) / 1e3:.1f} us")
    return lo, hi


# ---- idle gaps --------------------------------------------------------------


def idle_gaps(planes: List[dict]) -> List[Tuple[int, int]]:
    """(start_ns, end_ns) of every stretch over ``MIN_GAP_NS`` in which no
    operation ran on the first device's op line, on the trace's clock."""
    ln = trace_reduce.line(planes[0], trace_reduce.OPS_LINE)
    gaps, end = [], None
    for _name, s, d, _st in sorted(ln["events"], key=lambda e: e[1]):
        s, e = int(s), int(s + d)
        if end is not None and s - end > MIN_GAP_NS:
            gaps.append((end, s))
        end = e if end is None else max(end, e)
    return gaps


def leaf_spans(spans: Sequence[dict]) -> List[dict]:
    """The engine loop's spans that have no child among them."""
    loop = [s for s in spans if s["name"].startswith(LOOP_SPANS)]
    parents = {s["parent_id"] for s in loop}
    return [s for s in loop if s["span_id"] not in parents]


def gap_owner(planes: List[dict], spans: Sequence[dict],
              offset: int) -> List[dict]:
    """Every idle gap of the device with the leaf span that covers most
    of it: ``{"start_ns", "ns", "owner", "covered_ns"}`` on the trace's
    clock, ``owner`` None where no span covers any of it."""
    leaves = sorted(leaf_spans(spans), key=lambda s: s["start_ns"])
    out, first = [], 0
    for a, b in idle_gaps(planes):
        lo, hi = a + offset, b + offset
        while first < len(leaves) and leaves[first]["end_ns"] <= lo:
            first += 1  # gaps come in order: these end before every later one
        owner, covered = None, 0
        for s in leaves[first:]:
            if s["start_ns"] >= hi:
                break
            part = min(s["end_ns"], hi) - max(s["start_ns"], lo)
            if part > covered:
                owner, covered = s["name"], part
        out.append({"start_ns": a, "ns": b - a, "owner": owner,
                    "covered_ns": covered})
    return out


# ---- histograms -------------------------------------------------------------


def histogram_buckets(metrics: Sequence[dict], name: str,
                      tags: Optional[Dict[str, str]] = None) -> List[Tuple[float, float]]:
    """(upper boundary, cumulative count) of one tagged series of a
    ``util.metrics`` histogram as ``state.get_metrics()`` returns it,
    boundaries ascending, ``inf`` last.  Empty if it has none."""
    key = json.dumps(sorted((tags or {}).items())) + "|le="
    out = []
    for m in metrics:
        if m["name"] != name:
            continue
        for k, v in m["series"].items():
            if k.startswith(key):
                le = k[len(key):]
                out.append((math.inf if le == "+Inf" else float(le), float(v)))
    return sorted(out)


def histogram_percentile(series: Sequence[Tuple[float, float]], q: float) -> float:
    """The q-th percentile (0..100) of a cumulative histogram: the bucket
    that holds rank q/100 x count, interpolated on a logarithmic scale
    between its boundaries (the ladders here are geometric; the first
    bucket, which starts at 0, linearly)."""
    if not series or series[-1][1] <= 0:
        raise ValueError("percentile of an empty histogram")
    rank = q / 100.0 * series[-1][1]
    below, lower = 0.0, 0.0
    for upper, cum in series:
        if cum >= rank and cum > below:
            if math.isinf(upper):
                return lower  # beyond the ladder: all that is known
            frac = (rank - below) / (cum - below)
            if lower <= 0:
                return upper * frac
            return lower * (upper / lower) ** frac
        below, lower = cum, upper
    return lower


# ---- one run ----------------------------------------------------------------


def reduce_run(planes: List[dict], spans: Sequence[dict],
               metrics: Sequence[dict]) -> dict:
    """Every number the readers report, by the metric's name without its
    ``.batch`` / ``.chat`` suffix."""
    lo, hi = align(planes, spans)
    steps = [s for s in steps_of(spans) if not s["admitted"]]
    if not steps:
        raise SpanError("every llm.step of the traced window admitted a request")

    def ms(step, name):
        return (step["parts"][name]["end_ns"] - step["parts"][name]["start_ns"]) / 1e6

    def part_ms(name):
        return percentile([ms(s, name) for s in steps], 50)

    # ``llm.step.sync`` is no part of the host's own time: since the
    # engine keeps a step in flight it begins after the NEXT launch and
    # waits on the device, so it runs shorter than the device's step
    events = decode_events(planes)
    device_ms = percentile([(e - s) / 1e6 for s, e, _done in events], 50)
    out = {
        "step_dispatch_ms_p50": percentile(
            [ms(s, "build") + ms(s, "dispatch") for s in steps], 50),
        "step_deliver_ms_p50": part_ms("deliver"),
        "step_serve_plane_ms_p50": part_ms("yield"),
    }
    parts = sum(out.values())
    between = percentile(
        [(b[0] - a[1]) / 1e6 for a, b in zip(events, events[1:])], 50
    )
    owners = gap_owner(planes, spans, (lo + hi) // 2)
    idle = sum(g["ns"] for g in owners)
    if idle <= 0:
        raise SpanError("the device was never idle for over 50 us in the trace")
    by_owner: Dict[str, float] = {}
    for g in owners:
        by_owner[g["owner"] or "none"] = by_owner.get(g["owner"] or "none", 0.0) + g["ns"]
    out["idle_gap_attributed_share"] = 100.0 * (1.0 - by_owner.get("none", 0.0) / idle)
    _say(f"over {len(steps)} steps that admitted nothing: build+dispatch "
         f"{out['step_dispatch_ms_p50']:.3f} + deliver "
         f"{out['step_deliver_ms_p50']:.3f} + yield "
         f"{out['step_serve_plane_ms_p50']:.3f} = {parts:.3f} ms of the host's own "
         f"a step (sync {part_ms('sync'):.3f}, the device's step {device_ms:.3f}); "
         f"the median gap between decode executions in the trace is {between:.3f} ms")
    _say(f"{len(owners)} idle gaps, {idle / 1e6:.3f} ms: " + ", ".join(
        f"{k} {v / 1e6:.3f} ms" for k, v in sorted(by_owner.items(), key=lambda kv: -kv[1])))
    for key, name, tags in (
        ("engine_queue_wait_ms_p50", "llm_queue_wait_ms", {"outcome": "admitted"}),
        ("engine_ttft_ms_p50", "llm_engine_ttft_ms", None),
    ):
        buckets = histogram_buckets(metrics, name, tags)
        if not buckets:
            raise SpanError(f"the cluster's metrics hold no histogram {name}")
        out[key] = histogram_percentile(buckets, 50)
        _say(f"{name}: {buckets[-1][1]:.0f} observations, p50 {out[key]:.3f} "
             f"p95 {histogram_percentile(buckets, 95):.3f} ms")
    return out


def value(ctx: dict, key: str) -> Optional[float]:
    """What the reader of ``key`` returns.  The run is reduced once and
    kept in ``ctx`` (``run.py`` hands every reader the same dict).

    On the chip a run that recorded spans and cannot be reduced raises.
    In a ``--rehearse`` walk on the CPU (stand-in planes) it returns
    None, for which the harness puts 0.  Where the program has no span
    table at all, 0 stands in on the chip too and the log says so: the
    harness has no way to leave a declared metric out of the line, and
    a parent commit from before the spans must still print one."""
    if "_span_reduce" not in ctx:
        got = fetch(ctx)
        if got is None:
            ctx["_span_reduce"] = None
        else:
            try:
                ctx["_span_reduce"] = reduce_run(ctx["planes"], got["spans"], got["metrics"])
            except SpanError as e:
                if ctx["device"]["platform"] == "tpu":
                    raise
                _say(f"rehearsal: {e}")
                ctx["_span_reduce"] = {}
    got = ctx["_span_reduce"]
    if got is None:
        _say(f"{key}: this program records no spans; 0 stands in")
        return 0.0
    return got.get(key)
