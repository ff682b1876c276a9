"""From the program's start-up spans to the per-layer numbers under
``setup_s``.

The program records its own start-up whatever the tracing switch says
(``ray_tpu/util/tracing.py:startup``; names in PERF.md section 3): the
cluster's start in the driver, a worker's spawn in the raylet, its boot,
lease bind and chip opening in the worker, the replica's weights and
engine or the trainer's state, and one span per XLA trace / lowering /
backend compile with the program's ``fun_name``.  The six readers
``chipbench/layer_metrics/setup_*.py`` end here: ``reduce_spans`` splits
the seconds before the measured window by layer, and ``table`` is the
whole time line as text (``value`` prints it to stderr in every traced
run).

Which process matters: the *holder*, the worker whose
``rt.start.chip_open`` opened the cell's chips.  Where the set-up ends:
the readers are given no absolute time of the window's start, but the
cells' guard is that nothing compiles inside a window, so the holder's
``xla.*`` spans stop for ramp + window, and the set-up's are those before
the first silence of ``SILENCE_SHARE`` x ``run_seconds``.

Plain lists in (span dicts as ``tracing.collect`` returns them), so it
is tested without a chip on ``chipbench/testdata/startup_spans.json``.
Nothing here imports jax.
"""

from __future__ import annotations

import sys
from typing import Dict, List, Optional, Sequence, Tuple

from chipbench import contract, span_reduce

START_PREFIXES = ("rt.start.", "serve.start.", "train.start.", "llm.start.")
XLA_PREFIX = "xla."
STATE_SPANS = ("llm.start.weights", "llm.start.engine")
TRAIN_STATE_SPAN = "train.start.state"
SILENCE_SHARE = 0.9  # of run_seconds: no set-up is silent that long
TOP_PROGRAMS = 10
METRICS = (
    "setup_cluster_start_s", "setup_worker_ready_s", "setup_chip_open_s",
    "setup_state_init_s", "setup_xla_build_s", "setup_xla_cache_miss_s",
)


def _say(msg: str) -> None:
    print(f"[chipbench] start-up: {msg}", file=sys.stderr, flush=True)


# ---- intervals --------------------------------------------------------------


def union_ns(intervals: Sequence[Tuple[int, int]]) -> int:
    """Length of the union of (start, end) intervals."""
    total, reach = 0, None
    for a, b in sorted(intervals):
        if reach is None or a > reach:
            total += b - a
            reach = b
        elif b > reach:
            total += b - reach
            reach = b
    return total


def _inside(span: dict, others: Sequence[dict]) -> List[Tuple[int, int]]:
    """The parts of ``others`` that lie inside ``span``."""
    return [
        (max(o["start_ns"], span["start_ns"]), min(o["end_ns"], span["end_ns"]))
        for o in others
        if o["end_ns"] > span["start_ns"] and o["start_ns"] < span["end_ns"]
    ]


def self_ns(span: dict, spans: Sequence[dict]) -> int:
    """A span's duration less what the spans of its own process that lie
    within it cover (choosing-metrics guide, section 4).  By time, not by
    ``parent_id``: JAX's listener records an ``xla.*`` span under
    whatever span is open on its thread, or under none."""
    kids = [
        o for o in spans
        if o["span_id"] != span["span_id"] and o["pid"] == span["pid"]
        and o["start_ns"] >= span["start_ns"] and o["end_ns"] <= span["end_ns"]
    ]
    return (span["end_ns"] - span["start_ns"]) - union_ns(_inside(span, kids))


# ---- who is who -------------------------------------------------------------


def _named(spans: Sequence[dict], name: str, pid: Optional[int] = None) -> List[dict]:
    return sorted(
        (s for s in spans
         if s["name"] == name and (pid is None or s["pid"] == pid)),
        key=lambda s: s["start_ns"],
    )


def holder_open(spans: Sequence[dict]) -> Optional[dict]:
    """The ``rt.start.chip_open`` of the worker that holds the cell's
    chips: the one that opened most chips, the longest among equals (a
    worker on the host's CPU opens nothing worth a second)."""
    opens = _named(spans, "rt.start.chip_open")
    if not opens:
        return None

    def chips(s):
        return len([c for c in str(s["attributes"].get("chips", "")).split(",") if c])

    return max(opens, key=lambda s: (chips(s), s["end_ns"] - s["start_ns"]))


def set_up_xla(spans: Sequence[dict], pid: int, run_seconds: float) -> List[dict]:
    """The holder's ``xla.*`` spans up to the first silence of
    ``SILENCE_SHARE`` x ``run_seconds``, in order."""
    out, reach = [], None
    for s in sorted((s for s in spans
                     if s["pid"] == pid and s["name"].startswith(XLA_PREFIX)),
                    key=lambda s: s["start_ns"]):
        if reach is not None and (
            s["start_ns"] - reach >= SILENCE_SHARE * run_seconds * 1e9
        ):
            break
        out.append(s)
        reach = s["end_ns"] if reach is None else max(reach, s["end_ns"])
    return out


def roles(spans: Sequence[dict]) -> Dict[int, str]:
    """pid -> driver / raylet / holder / worker, by what it recorded."""
    out: Dict[int, str] = {}
    opened = holder_open(spans)
    for s in spans:
        role = {"rt.start.cluster": "driver", "rt.start.worker": "raylet",
                "rt.start.boot": "worker"}.get(s["name"])
        if role:
            out[s["pid"]] = role
    if opened is not None:
        out[opened["pid"]] = "holder"
    return out


# ---- one run ----------------------------------------------------------------


ASK_SPANS = ("serve.start.app", "train.start.workers")


def reduce_spans(spans: Sequence[dict], run_seconds: float) -> Optional[dict]:
    """The six metrics in seconds, and ``covered``: the share that the
    first five's intervals cover of the seconds from
    ``rt.start.cluster``'s start to the end of the last start-up span
    (``elapsed_s``).  Intervals, not the metrics' sum: a job builds on
    after the replica answers, and those seconds are ``setup_xla_build_s``'s
    and lie behind ``elapsed_s``.  None where the program recorded no
    start-up span: a commit from before it did."""
    cluster = _named(spans, "rt.start.cluster")
    if not cluster:
        return None
    cluster = cluster[0]
    out = dict.fromkeys(METRICS, 0.0)
    out["setup_cluster_start_s"] = (cluster["end_ns"] - cluster["start_ns"]) / 1e9
    parts = [(cluster["start_ns"], cluster["end_ns"])]
    opened = holder_open(spans)
    if opened is not None:
        pid = opened["pid"]
        own = [s for s in spans if s["pid"] == pid]
        out["setup_chip_open_s"] = (opened["end_ns"] - opened["start_ns"]) / 1e9
        parts.append((opened["start_ns"], opened["end_ns"]))
        ready = holder_ready(spans, pid)
        if ready is not None:
            out["setup_worker_ready_s"] = (ready[1] - ready[0]) / 1e9
            parts.append(ready)
        state = [s for name in STATE_SPANS for s in _named(own, name)]
        state = state or _named(own, TRAIN_STATE_SPAN)
        out["setup_state_init_s"] = sum(self_ns(s, own) for s in state) / 1e9
        built = set_up_xla(spans, pid, run_seconds)
        out["setup_xla_build_s"] = union_ns(
            [(s["start_ns"], s["end_ns"]) for s in built]) / 1e9
        out["setup_xla_cache_miss_s"] = union_ns(
            [(s["start_ns"], s["end_ns"]) for s in built
             if s["name"] == "xla.compile" and not s["attributes"].get("cache_hit")]
        ) / 1e9
        # a state span whole: its self time, and builds and chips counted above
        parts += [(s["start_ns"], s["end_ns"]) for s in state + built]
    last = max(s["end_ns"] for s in spans if s["name"].startswith(START_PREFIXES))
    out["elapsed_s"] = (last - cluster["start_ns"]) / 1e9
    out["covered"] = union_ns(
        [(a, min(b, last)) for a, b in parts if a < last]
    ) / (last - cluster["start_ns"])
    return out


def holder_ready(spans: Sequence[dict], pid: int) -> Optional[Tuple[int, int]]:
    """From the driver's ask to the moment the holder can run what it
    was leased for: ``serve.start.app`` / ``train.start.workers`` begins
    (the newest before the holder's spawn; the spawn itself where there
    is none) -> the holder's ``rt.start.actor_init`` begins (the end of
    its ``rt.start.lease_bind`` where it runs no actor).  Every process
    started on the way is inside: in serving the controller's worker,
    its deploy, then the holder's spawn, boot, lease bind and the
    unpickling of its class (``rt.start.actor_load``).  The chips open
    after it (``tpu.open_leased_chips``: in the replica's ``__init__``,
    in the train worker's loop thread)."""
    boots = _named(spans, "rt.start.boot", pid)
    ends = ([s["start_ns"] for s in _named(spans, "rt.start.actor_init", pid)]
            or [s["end_ns"] for s in _named(spans, "rt.start.lease_bind", pid)])
    if not boots or not ends:
        return None
    worker_id = boots[0]["attributes"].get("worker_id")
    spawned = [s for s in _named(spans, "rt.start.worker")
               if s["attributes"].get("worker_id") == worker_id]
    start = (spawned or boots)[0]["start_ns"]
    asked = [s["start_ns"] for name in ASK_SPANS for s in _named(spans, name)
             if s["start_ns"] <= start]
    return max(asked, default=start), ends[0]


def table(spans: Sequence[dict], run_seconds: float) -> str:
    """The start-up time line, one span a row in order of start, and the
    programs whose ``xla.*`` time in the holder's set-up is largest."""
    cluster = _named(spans, "rt.start.cluster")
    if not cluster:
        return "no rt.start.cluster span"
    t0, role = cluster[0]["start_ns"], roles(spans)
    names = {s["span_id"]: s["name"] for s in spans}
    rows = ["span | process | start s | duration s | self s | parent"]
    for s in sorted((s for s in spans if s["name"].startswith(START_PREFIXES)),
                    key=lambda s: s["start_ns"]):
        same_pid = [o for o in spans if o["pid"] == s["pid"] and (
            o["name"].startswith(START_PREFIXES + (XLA_PREFIX,)))]
        rows.append(
            f"{s['name']} | {role.get(s['pid'], 'worker')} {s['pid']} | "
            f"{(s['start_ns'] - t0) / 1e9:.3f} | "
            f"{(s['end_ns'] - s['start_ns']) / 1e9:.3f} | "
            f"{self_ns(s, same_pid) / 1e9:.3f} | "
            f"{names.get(s['parent_id'], s['parent_id'] or '-')}"
        )
    opened = holder_open(spans)
    if opened is not None:
        built = set_up_xla(spans, opened["pid"], run_seconds)
        if built:
            rows.append(
                f"xla.* of the holder's set-up: {len(built)} spans from "
                f"{(built[0]['start_ns'] - t0) / 1e9:.3f} s to "
                f"{(max(s['end_ns'] for s in built) - t0) / 1e9:.3f} s; longest "
                f"silence {longest_silence_s(built):.3f} s"
            )
        by_program: Dict[str, List[float]] = {}
        for s in built:
            stage = by_program.setdefault(program(s), [0.0, 0.0, 0.0, 0])
            stage[("xla.trace", "xla.lower", "xla.compile").index(s["name"])] += (
                s["end_ns"] - s["start_ns"]) / 1e9
            stage[3] += s["name"] == "xla.compile" and not s["attributes"].get("cache_hit")
        rows.append("program | trace s | lower s | compile s | compiles the cache missed")
        for name, (tr, lo, co, missed) in sorted(
            by_program.items(), key=lambda kv: -sum(kv[1][:3])
        )[:TOP_PROGRAMS]:
            rows.append(f"{name} | {tr:.3f} | {lo:.3f} | {co:.3f} | {missed}")
    return "\n".join(rows)


def program(span: dict) -> str:
    """An ``xla.*`` span's program: JAX names the traced function ``f``
    and its lowering and compile ``jit(f)``."""
    name = span["attributes"].get("fun_name") or "?"
    return name[4:-1] if name.startswith("jit(") and name.endswith(")") else name


def longest_silence_s(built: Sequence[dict]) -> float:
    """The longest stretch without any of ``built`` (in order of start):
    what ``SILENCE_SHARE`` x ``run_seconds`` must stay above."""
    longest, reach = 0, None
    for s in built:
        if reach is not None:
            longest = max(longest, s["start_ns"] - reach)
        reach = s["end_ns"] if reach is None else max(reach, s["end_ns"])
    return longest / 1e9


def value(ctx: dict, key: str) -> float:
    """What the reader of ``key`` returns.  The run is reduced once, its
    time line printed once, and the result kept in ``ctx`` (``run.py``
    hands every reader the same dict).  Where the program records no
    start-up span, 0 stands in and the log says so, as
    ``span_reduce.value`` does for a commit from before the spans."""
    if "_startup_reduce" not in ctx:
        got = span_reduce.fetch(ctx)
        run_seconds = contract.load_benchmark()["run_seconds"]
        reduced = reduce_spans(got["spans"], run_seconds) if got else None
        if reduced is not None:
            _say("time line\n" + table(got["spans"], run_seconds))
            _say(", ".join(f"{m} {reduced[m]:.3f}" for m in METRICS)
                 + f"; the first five cover {100 * reduced['covered']:.1f}% of the "
                 f"{reduced['elapsed_s']:.3f} s from rt.start.cluster to the end "
                 "of the last start-up span")
        ctx["_startup_reduce"] = reduced
    reduced = ctx["_startup_reduce"]
    if reduced is None:
        _say(f"{key}: this program records no start-up spans; 0 stands in")
        return 0.0
    return reduced[key]
