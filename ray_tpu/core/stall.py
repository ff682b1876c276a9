"""The stall witness: every stop of a process's io loop from 20 ms, as a
span with its evidence and one word for its cause.

``witness(role)`` is a 20 ms ticker on an asyncio loop (a driver's or a
worker's ``rt-io`` loop, the raylet's, the GCS's).  Woken more than
20 ms late it records one finished span ``rt.stall`` whatever the
tracing switch says (``tracing.record``): ``start_ns`` is the wake
before, the last instant the loop was seen alive, ``end_ns`` this wake,
so the span brackets the stop; ``late_ms`` is the excess over the tick
and is what every sum is made of.  The same seconds go to the counter
``loop_stall_seconds_total{role, cause}``, and a stop of a second or
more is also one WARNING line.

The evidence is taken where the stop happened (the span's attributes):
the CPU time the process and the loop's thread used meanwhile, the time
the garbage collector ran (a ``gc.callbacks`` pair), the share of the
host's CPUs that was busy (``/proc/stat``, read every fifth tick),
whether a jax profiler session ran at both ends, and what a daemon
thread of this module saw *while the stop lasted*.  That sampler looks
at a loop 42 ms after its last-alive stamp (the tick that was due is
over 20 ms late then), a tick later, then two, four, ... while the stop
goes on, and keeps
``where`` (the innermost frames of the loop's thread and of the other
threads that run Python: those that used CPU time since its first look),
``open_span``, and whether the loop's thread was parked in its own
selector (``loop_parked``).  The sampler's own lateness is evidence as
well (``sampler_late_ms``): late together with the loop, the process
did not run or C code kept the interpreter; on time, the loop alone was
held and ``where`` has the line.  ``classify`` turns the evidence into
the cause, ``join`` the stops of a cluster's processes into
``util.state.stalls()``.

The sampler is a Python thread, not ``faulthandler.
dump_traceback_later``: that one samples from a C thread that needs no
interpreter lock, but there is one of it a process (the tests' own time
limit uses it, ``tests/conftest.py``; so may an application), a re-arm
every tick starts and joins a thread (88 us of wall and 220 us of CPU
time on the chip's host, as much a second as this module's two threads
together: PERF.md section 6, PR 53), its text needs a file, and it
cannot say which thread used the CPU.  What a Python sampler cannot see
while C code keeps the interpreter, the ticker looks at itself at the
wake after (``samples`` 0).

A process records at most ``SPANS_PER_INTERVAL`` stops a push interval
(``cfg.metrics_push_interval_s``); past that the seconds still go to
the counter and one span ``rt.stall`` with ``folded=<n>`` closes the
interval, so a host with constant jitter cannot fill the GCS's span
table; the GCS keeps ``rt.stall`` rows in a ring of their own besides
(core/gcs.py ``STALL_TABLE_SIZE``), so that stops push out older stops
and never the start-up spans ``join`` and the set-up metrics read.
"""

from __future__ import annotations

import asyncio
import gc
import logging
import os
import sys
import threading
import time
from collections import Counter
from typing import Awaitable, Callable, Dict, List, NamedTuple, Optional, Sequence

from ray_tpu.common.config import cfg
from ray_tpu.util import tracing

# the runtime's logger: the line is the runtime's, as it was before the
# witness had a module of its own
logger = logging.getLogger("ray_tpu.core.runtime")

TICK_NS = 20_000_000
TICK_S = TICK_NS / 1e9
STALL_NS = 20_000_000  # a wake later than this is a stop
LOOK_NS = TICK_NS + STALL_NS + 2_000_000  # after a loop's last wake: the sampler's look
LOOK_EVERY_MAX = 8  # ticks between two looks of the sampler at one long stop
SAMPLER_GONE_NS = 2 * LOOK_EVERY_MAX * TICK_NS  # no wake for so long before a stop: none runs
SPANS_PER_INTERVAL = 64
LOG_FROM_S = 1.0  # the WARNING line
PUSH_FROM_S = 0.1  # a stop this long goes to the GCS at once
STAT_EVERY = 5  # ticks between two readings of /proc/stat
SPAN = "rt.stall"
CHIP_OPEN = "rt.start.chip_open"
SPANS_PREFIX = "rt.sta"  # one read of the span table for both names
SAMPLER = "rt-stall-sampler"  # the thread's name
CAUSES = ("gc", "loop_held", "interpreter_held", "loop_waited", "not_scheduled")
_RAY_TPU = os.sep + "ray_tpu" + os.sep
#: a thread whose innermost frame lies in one of these waits for work
_PARKED = ("threading.py", "selectors.py", "queue.py", "thread.py", "socket.py")
_PARKED_LOOP = "select (selectors.py:"  # where a loop waits for work
_HOST = os.uname().nodename
_TICK_MS = 1e3 / os.sysconf("SC_CLK_TCK")  # of a thread's utime / stime


def classify(ev: dict) -> str:
    """One word for a stop, from its evidence.  ``ev`` holds ``late_ms``,
    ``gc_ms``, ``loop_thread_cpu_ms``, ``process_cpu_ms``,
    ``sampler_late_ms`` and ``loop_parked``; each test of a time is "at
    least half the stop", in this order:

    - ``gc``: the collector ran that long (on whichever thread: it keeps
      the interpreter);
    - ``loop_held``: the loop's own thread used that much CPU time: a
      callback or a coroutine's step did not yield;
    - ``interpreter_held``: the process did, the loop's thread did not,
      and the sampler was late too: another thread kept the interpreter
      (the sampler on time means it could be had: that falls through);
    - ``not_scheduled``: nobody used CPU time and the sampler was late
      too: the process did not run; or the sampler, on time, found the
      loop's thread parked in its own selector past the timeout it had
      asked for: the thread alone was not woken (a sandboxed kernel
      whose event polling stalls while its sleeps do not);
    - ``loop_waited``: the rest: the loop's thread sat in a blocking
      call of the program's while the interpreter was free.
    """
    half = ev["late_ms"] / 2.0
    if ev["gc_ms"] >= half:
        return "gc"
    if ev["loop_thread_cpu_ms"] >= half:
        return "loop_held"
    if ev["sampler_late_ms"] >= half:
        if ev["process_cpu_ms"] >= half:
            return "interpreter_held"
        return "not_scheduled"
    return "not_scheduled" if ev["loop_parked"] else "loop_waited"


# ---- what the process keeps for every witness in it -------------------------

_gc_ns = 0  # time inside collections, in all
_gc_gen = -1  # the generation of the last one
_gc_t0 = 0
#: the sampler's last wake (monotonic ns; 0: none runs), how late it was,
#: and when its next is due
_sampler_wake = (0, 0, 0)
_WITNESSES: List["Witness"] = []
_LOCK = threading.Lock()
_sampler: Optional[threading.Thread] = None
_lost = None  # the counter, made with the first witness


def _after_fork() -> None:
    """A forked child has no sampler and none of its parent's loops."""
    global _sampler, _sampler_wake, _LOCK
    _WITNESSES.clear()
    _sampler, _sampler_wake, _LOCK = None, (0, 0, 0), threading.Lock()


os.register_at_fork(after_in_child=_after_fork)


def _gc_first(phase: str, info: dict) -> None:
    global _gc_t0
    if phase == "start":
        _gc_t0 = time.perf_counter_ns()


def _gc_last(phase: str, info: dict) -> None:
    global _gc_ns, _gc_gen
    if phase == "stop":
        _gc_ns += time.perf_counter_ns() - _gc_t0
        _gc_gen = info["generation"]


def _around_gc() -> None:
    """Keep the pair around every other callback of the collector: what
    those do is the collection's time too (JAX's, imported later, frees
    XLA's garbage at both ends of each collection)."""
    cbs = gc.callbacks
    if not cbs or cbs[0] is not _gc_first or cbs[-1] is not _gc_last:
        for cb in (_gc_first, _gc_last):
            if cb in cbs:
                cbs.remove(cb)
        cbs.insert(0, _gc_first)
        cbs.append(_gc_last)


def _at(frame) -> str:
    code = frame.f_code
    return f"{code.co_name} ({os.path.basename(code.co_filename)}:{frame.f_lineno})"


def _frames(frame) -> str:
    """``function (file:line)`` of the innermost frame and of the first
    three of ray_tpu's outside it (``gc:`` before it where the frame is
    this module's callback of a collection in progress)."""
    said = ""
    while frame.f_code in (_gc_first.__code__, _gc_last.__code__) and frame.f_back:
        said, frame = "gc: ", frame.f_back
    out = [_at(frame)]
    frame = frame.f_back
    while frame is not None and len(out) < 4:
        if _RAY_TPU in frame.f_code.co_filename:
            out.append(_at(frame))
        frame = frame.f_back
    return said + " < ".join(out)


def _thread_cpu_ms(native_id: int) -> float:
    """CPU time of one of this process's threads: ``utime`` + ``stime``
    of its ``stat``, in clock ticks of 10 ms (a sandboxed kernel keeps
    no ``schedstat``, and a thread's CPU clock by its ident is undefined
    once the thread is gone).  A thread that is gone: 0."""
    try:
        with open(f"/proc/self/task/{native_id}/stat", "rb", buffering=0) as f:
            fields = f.read(512).rsplit(b")", 1)[1].split()
        return (int(fields[11]) + int(fields[12])) * _TICK_MS
    except (OSError, ValueError, IndexError):
        return 0.0


def where(loop_thread: Optional[int], skip: Sequence[int] = (),
          cpu_was: Optional[Dict[int, float]] = None) -> tuple:
    """``(text, cpu, loop_at)``.  The text: what this process's threads
    are at, the loop's thread first (``loop_at``, alone), then at most
    four others that are running Python (never the sampler, nor
    ``skip``).  ``cpu`` is every thread's CPU time by its native id;
    given that of an earlier look (``cpu_was``), running means that the
    thread used a clock tick or more since (said in the text; most
    first), without it that it is not parked in a wait of the standard
    library."""
    threads = {t.ident: t for t in threading.enumerate()}
    cpu, rest = {}, []
    text = loop_at = ""
    for ident, frame in sys._current_frames().items():
        t = threads.get(ident)
        if t is None or ident in skip or t.name == SAMPLER:
            continue
        cpu[t.native_id] = now = _thread_cpu_ms(t.native_id)
        used = None if cpu_was is None else now - cpu_was.get(t.native_id, now)
        ran = "" if used is None else f" [{used:.0f} ms cpu]"
        if ident == loop_thread:
            loop_at = _frames(frame)
            text = f"loop: {loop_at}{ran}"
        elif used is None:
            if os.path.basename(frame.f_code.co_filename) not in _PARKED:
                rest.append((0.0, f"{t.name}: {_frames(frame)}"))
        elif used >= _TICK_MS:
            rest.append((-used, f"{t.name}: {_frames(frame)}{ran}"))
    rest = [at for _used, at in sorted(rest)[:4]]
    return " | ".join(([text] if text else []) + rest), cpu, loop_at


def _sample_loop() -> None:
    """Wake when a loop's next look is due: ``LOOK_NS`` after its
    last-alive stamp, so that every stop is looked at while it lasts and a
    quiet loop costs a wake every second tick; while a stop goes on, a
    tick after the first look, then two, four, ... (``LOOK_EVERY_MAX``
    ticks at most), so that a stop of 0.4 s costs five looks and not
    nineteen, each a read of every thread's ``stat``.  (A wake of this thread takes the interpreter from whoever
    runs Python: with one every tick, ``serve_ilm2_chat`` read 0.3-0.5%
    more ``itl_p95_ms``: PERF.md section 6, PR 53.)  A look that raises
    is logged once and the thread goes on: a sampler that died would
    leave every later stop reading "the sampler was late too"."""
    global _sampler, _sampler_wake
    due = time.monotonic_ns() + TICK_NS
    said = False
    while True:
        time.sleep(max(due - time.monotonic_ns(), 0) / 1e9)
        now = time.monotonic_ns()
        with _LOCK:  # against a witness that attaches as the last one leaves
            watched = list(_WITNESSES)
            if not watched:
                _sampler, _sampler_wake = None, (0, 0, 0)
                return
        late = now - due
        due = min(_next_look(w, now) for w in watched)
        _sampler_wake = (now, late, due)  # before the look: a ticker may wake meanwhile
        try:
            for w in watched:
                if now - w.alive_ns > STALL_NS:
                    _look(w)
        except Exception:  # noqa: BLE001 - the witness outlives a bad look
            if not said:
                said = True
                logger.exception("the stall witness's sampler: a look failed")


def _next_look(w: "Witness", now: int) -> int:
    if now - w.alive_ns <= STALL_NS:
        return w.alive_ns + LOOK_NS
    return now + TICK_NS * min(1 << len(w.samples), LOOK_EVERY_MAX)


def _look(w: "Witness") -> None:
    seen = w.samples  # read once: the loop's thread swaps the list as a stop ends
    # the first look of a stop is what the later ones tell the threads'
    # CPU time from
    first = seen[0][2] if seen else None
    at, cpu, loop_at = where(w.loop_thread, cpu_was=first)
    seen.append((at, tracing.open_span(), cpu, loop_at))


def _host_cpu() -> Optional[tuple]:
    """(busy, all) jiffies of all CPUs since boot: the first line of
    ``/proc/stat`` (user nice system idle iowait irq softirq steal)."""
    try:
        with open("/proc/stat", "rb", buffering=0) as f:
            v = [int(x) for x in f.read(256).split(b"\n", 1)[0].split()[1:9]]
        return sum(v) - v[3] - v[4], sum(v)
    except (OSError, ValueError, IndexError):
        return None


class Reading(NamedTuple):
    """The clocks at one wake of the ticker."""

    wall_ns: int
    mono_ns: int
    process_cpu_ns: int
    thread_cpu_ns: int
    gc_ns: int
    sampler_wake: tuple  # the sampler's last wake (0: none runs), how late, the next due
    profiling: bool


def read_clocks() -> Reading:
    return Reading(time.time_ns(), time.monotonic_ns(), time.process_time_ns(),
                   time.thread_time_ns(), _gc_ns, _sampler_wake,
                   tracing.profiling())


class Witness:
    """One loop's stops.  ``wake`` is given the clocks of every wake of
    the ticker and does the rest; it reads no clock itself, so a test
    feeds it the readings of stops that never were."""

    def __init__(self, role: str, first: Reading,
                 loop_thread: Optional[int] = None,
                 interval_s: Optional[float] = None):
        self.role = role
        self.loop_thread = loop_thread
        self.alive_ns = first.mono_ns  # what the sampler looks at
        #: (where, the open span, the threads' CPU times, the loop's own
        #: place), by the sampler
        self.samples: list = []
        self._was = first
        self._interval_ns = int(
            (cfg.metrics_push_interval_s if interval_s is None else interval_s) * 1e9)
        self._interval_end = first.mono_ns + self._interval_ns
        self._recorded = 0
        self._folded: list = []  # (start_ns, end_ns, late_ms, cause, profiling)
        self._ticks = 0
        self._host_cpu = _host_cpu()

    def wake(self, now: Reading) -> Optional[dict]:
        """The stop that ended at this wake as the span's attributes, or
        None where the loop woke in time."""
        was, self._was = self._was, now
        self.alive_ns = now.mono_ns
        late_ns = now.mono_ns - was.mono_ns - TICK_NS
        if now.mono_ns >= self._interval_end:
            self._close_interval(now)
        if late_ns <= STALL_NS:
            if self.samples:  # jitter, or the tail of a stop told already
                self.samples = []  # swapped, not cleared: the sampler may hold it
            self._ticks += 1
            if self._ticks % STAT_EVERY == 0:
                self._host_cpu = _host_cpu()
                _around_gc()
            return None
        samples, self.samples = self.samples, []
        # how late the sampler was: at its last wake if that fell into
        # the stop, and by now if it has not woken since
        woke, sampler_late, due = now.sampler_wake
        if woke <= was.mono_ns:
            sampler_late = 0
        if woke < was.mono_ns - SAMPLER_GONE_NS:
            # it was silent while this loop still ticked: it does not run
            # (a live one wakes every LOOK_NS), and says nothing
            samples = []
        elif woke:
            sampler_late = max(sampler_late, now.mono_ns - due)
        if samples:
            # the place the looks found the loop's thread at most often, as
            # they saw it last: the loop may have left the stop before this wake
            loop_at = Counter(s[3] for s in samples).most_common(1)[0][0]
            at, open_span = next(s[:2] for s in reversed(samples) if s[3] == loop_at)
        else:  # the sampler did not run meanwhile: what the threads are at now
            at, loop_at = where(None, (threading.get_ident(),))[0], ""
            open_span = tracing.open_span()
        cpu_was, self._host_cpu = self._host_cpu, _host_cpu()
        ev = {
            "role": self.role, "host": _HOST, "late_ms": late_ns / 1e6,
            "process_cpu_ms": (now.process_cpu_ns - was.process_cpu_ns) / 1e6,
            "loop_thread_cpu_ms": (now.thread_cpu_ns - was.thread_cpu_ns) / 1e6,
            "gc_ms": (now.gc_ns - was.gc_ns) / 1e6,
            "gc_generation": _gc_gen if now.gc_ns > was.gc_ns else -1,
            "host_cpu_busy_share": _busy_share(cpu_was, self._host_cpu),
            "open_span": open_span or "none",
            "profiling": was.profiling and now.profiling,
            "where": at, "samples": len(samples),
            "sampler_late_ms": sampler_late / 1e6,
            "loop_parked": loop_at.startswith(_PARKED_LOOP),
        }
        ev["cause"] = classify(ev)
        if self._recorded < SPANS_PER_INTERVAL:
            self._recorded += 1
            tracing.record(SPAN, was.wall_ns, now.wall_ns, **ev)
        else:
            self._folded.append((was.wall_ns, now.wall_ns, ev["late_ms"],
                                 ev["cause"], ev["profiling"]))
        return ev

    def _close_interval(self, now: Reading) -> None:
        """The stops past the cap, as one span: their seconds, the cause
        that most of them went to, ``profiling`` where all had it."""
        folded, self._folded = self._folded, []
        self._recorded = 0
        self._interval_end = now.mono_ns + self._interval_ns
        if not folded:
            return
        by_cause: Dict[str, float] = {}
        for _s, _e, late_ms, cause, _p in folded:
            by_cause[cause] = by_cause.get(cause, 0.0) + late_ms
        tracing.record(
            SPAN, folded[0][0], folded[-1][1], role=self.role, host=_HOST,
            folded=len(folded), late_ms=sum(f[2] for f in folded),
            cause=max(by_cause, key=by_cause.get),
            profiling=all(f[4] for f in folded),
        )


def _busy_share(was: Optional[tuple], now: Optional[tuple]) -> float:
    """Of all CPUs of the host between two readings; -1 where unknown
    (no ``/proc/stat``, or no jiffy went by)."""
    if was is None or now is None or now[1] <= was[1]:
        return -1.0
    return (now[0] - was[0]) / (now[1] - was[1])


def _attach(w: Witness) -> None:
    global _sampler, _lost
    with _LOCK:
        if _lost is None:
            from ray_tpu.util import metrics

            _lost = metrics.Counter(
                "loop_stall_seconds_total",
                "wall time by which an io loop's 20 ms ticker woke late, "
                "counted from 20 ms, by the stop's cause (rt.stall spans)",
                tag_keys=("role", "cause"),
            )
        _around_gc()
        _WITNESSES.append(w)
        if _sampler is None or not _sampler.is_alive():
            _sampler = threading.Thread(
                target=_sample_loop, name=SAMPLER, daemon=True)
            _sampler.start()


async def witness(role: str,
                  push: Optional[Callable[[], Awaitable]] = None) -> None:
    """Watch the running loop until cancelled.  ``push`` sends this
    process's finished spans to the GCS; a stop of ``PUSH_FROM_S`` or
    more is sent at once, so that who asks the GCS next
    (``state.stalls()``, the line ``shutdown()`` logs) has it.  The
    ticker is a chain of ``call_later``, not a loop around
    ``asyncio.sleep``: one callback a tick where that takes a future, a
    timer and a task's step (a quarter less CPU time a tick)."""
    w = Witness(role, read_clocks(), threading.get_ident())
    _attach(w)
    loop = asyncio.get_running_loop()
    pushing = None  # the loop holds tasks weakly

    def tick() -> None:
        nonlocal timer, pushing
        timer = loop.call_later(TICK_S, tick)
        ev = w.wake(read_clocks())
        if ev is None:
            return
        late_s = ev["late_ms"] / 1e3
        _lost.inc(late_s, {"role": role, "cause": ev["cause"]})
        if late_s >= LOG_FROM_S:
            logger.warning(
                "%s pid %d stood still: its io loop woke %.2f s late; "
                "meanwhile the process used %.2f s of CPU and the loop "
                "thread %.2f s; open span: %s; cause: %s; where: %s",
                role, os.getpid(), late_s, ev["process_cpu_ms"] / 1e3,
                ev["loop_thread_cpu_ms"] / 1e3, ev["open_span"],
                ev["cause"], ev["where"] or "unknown",
            )
        if push is not None and late_s >= PUSH_FROM_S:
            pushing = asyncio.ensure_future(push())

    timer = loop.call_later(TICK_S, tick)
    try:
        await loop.create_future()  # until cancelled
    finally:
        timer.cancel()
        with _LOCK:
            _WITNESSES.remove(w)


# ---- the cluster's stops ----------------------------------------------------


def join(spans: Sequence[dict]) -> List[dict]:
    """The ``rt.stall`` spans of a cluster (dicts as ``tracing.collect``
    returns them, ``rt.start.chip_open`` spans among them) as its stops,
    oldest first, each with its process's ``cause``, one cluster reading
    and ``outside``.  For a stop of ``not_scheduled``: ``chip_open``
    where it overlaps a ``rt.start.chip_open`` of another process of its
    host, ``host`` where it overlaps a stop of ``not_scheduled`` in
    another process of its host (the whole machine stopped).  ``host``
    also for a stop of any other cause that stops of ``not_scheduled``
    in TWO other processes of its host each cover half of: what stopped
    them stopped this one, whatever its own evidence reads (a replica's
    device threads use CPU time while every loop of the machine waits; a
    socket's write does not return).  ``process`` for the rest.

    ``outside`` is the one place that says "no change to the program
    made this stop": its cause is ``not_scheduled`` (whatever the
    reading) or its reading is ``host``.  Every sum of such stops (the
    benchmark's ``host_stall_outside_share``, ``summary``) reads it, so
    a later rule changes this function alone."""
    stops = sorted((s for s in spans if s["name"] == SPAN),
                   key=lambda s: s["start_ns"])
    opens = [s for s in spans if s["name"] == CHIP_OPEN]
    unscheduled = [s for s in stops
                   if s["attributes"].get("cause") == "not_scheduled"]

    def beside(stop: dict, others: Sequence[dict]) -> Dict[int, int]:
        """pid -> ns of ``stop`` that ``others`` of another process of
        its host lie over."""
        host, over = stop["attributes"].get("host"), {}
        for o in others:
            both = min(o["end_ns"], stop["end_ns"]) - max(o["start_ns"], stop["start_ns"])
            if (both > 0 and o["pid"] != stop["pid"]
                    and o["attributes"].get("host", host) == host):
                over[o["pid"]] = over.get(o["pid"], 0) + both
        return over

    out = []
    for s in stops:
        a = s["attributes"]
        stood = beside(s, unscheduled)
        half = (s["end_ns"] - s["start_ns"]) / 2
        reading = "process"
        if a.get("cause") == "not_scheduled":
            if beside(s, opens):
                reading = "chip_open"
            elif stood:
                reading = "host"
        elif sum(ns >= half for ns in stood.values()) >= 2:
            reading = "host"
        out.append({
            "pid": s["pid"], "role": a.get("role"), "host": a.get("host"),
            "start_ns": s["start_ns"], "end_ns": s["end_ns"],
            "late_ms": a.get("late_ms", 0.0), "cause": a.get("cause"),
            "reading": reading,
            "outside": a.get("cause") == "not_scheduled" or reading == "host",
            "where": a.get("where", ""),
            "profiling": bool(a.get("profiling")), "attributes": a,
        })
    return out


def summary(stops: Sequence[dict]) -> Optional[str]:
    """What ``shutdown()`` says of a run's stops, or None where they sum
    to under ``PUSH_FROM_S``: how many, the seconds, the same by the
    cluster's reading, what of them no change to the program made
    (``outside``), and the longest."""
    total_s = sum(s["late_ms"] for s in stops) / 1e3
    if total_s < PUSH_FROM_S:
        return None
    by = {}
    for s in stops:
        n, ms = by.get(s["reading"], (0, 0.0))
        by[s["reading"]] = (n + 1, ms + s["late_ms"])
    # a span of folded stops holds their sum: it is no one stop
    worst = max(stops, key=lambda s: (
        "folded" not in s["attributes"], s["late_ms"]))
    outside_s = sum(s["late_ms"] for s in stops if s["outside"]) / 1e3
    return (
        f"{len(stops)} stop(s) of an io loop since init, {total_s:.3f} s in all, "
        f"{outside_s:.3f} s of them outside the program ("
        + ", ".join(f"{r} {n} x {ms / 1e3:.3f} s" for r, (n, ms) in sorted(by.items()))
        + f"); the longest {worst['late_ms'] / 1e3:.3f} s: {worst['role']} pid "
        f"{worst['pid']}, cause {worst['cause']} ({worst['reading']}), where: "
        f"{worst['where'] or 'unknown'}"
    )
