"""The stall witness (ray_tpu/core/stall.py): every stop of an io loop
from 20 ms is a span ``rt.stall`` with its evidence and one word for its
cause; ``state.stalls()`` joins the cluster's.  No test here waits on a
real stop longer than a second."""

import asyncio
import gc
import os
import signal
import sys
import threading
import time

import pytest

from ray_tpu.core import stall
from ray_tpu.util import tracing

MS = 1_000_000


def _ev(late, gc_ms=0.0, loop=0.0, process=0.0, sampler=0.0, parked=False):
    return {"late_ms": late, "gc_ms": gc_ms, "loop_thread_cpu_ms": loop,
            "process_cpu_ms": process, "sampler_late_ms": sampler,
            "loop_parked": parked}


@pytest.mark.parametrize("ev, cause", [
    # one case a cause
    (_ev(300, gc_ms=280, loop=290, process=295), "gc"),
    (_ev(300, loop=290, process=295, sampler=5), "loop_held"),
    (_ev(300, process=290, sampler=280), "interpreter_held"),
    (_ev(300, process=2, sampler=1), "loop_waited"),
    (_ev(300, process=1, sampler=285), "not_scheduled"),
    # XLA's threads burn CPU without the interpreter: the sampler, on
    # time, says the interpreter could be had
    (_ev(300, process=2400, sampler=3), "loop_waited"),
    # the loop's thread waited in its own selector past its timeout while
    # the sampler woke on time: it alone was not woken (seen on the chip's
    # sandboxed host, PR 53); busy device threads change nothing
    (_ev(300, process=2, sampler=1, parked=True), "not_scheduled"),
    (_ev(300, process=2400, sampler=3, parked=True), "not_scheduled"),
    (_ev(300, process=290, sampler=280, parked=True), "interpreter_held"),
    # the ties at one half: "at least half the stop"
    (_ev(100, gc_ms=50), "gc"),
    (_ev(100, gc_ms=49.9, loop=50), "loop_held"),
    (_ev(100, loop=49.9, process=50, sampler=50), "interpreter_held"),
    (_ev(100, process=49.9, sampler=50), "not_scheduled"),
    (_ev(100, process=50, sampler=49.9), "loop_waited"),
    # in this order: the collector on the loop's thread is the collector
    (_ev(100, gc_ms=60, loop=100, process=100, sampler=100), "gc"),
    (_ev(100, loop=60, process=100, sampler=100), "loop_held"),
])
def test_classify(ev, cause):
    assert stall.classify(ev) == cause
    assert cause in stall.CAUSES


# ---- real stops, on a loop of the test's own --------------------------------


class _Loop:
    """An asyncio loop in a thread with the witness on it."""

    def __init__(self, role):
        self.role = role
        self.loop = asyncio.new_event_loop()
        self.thread = threading.Thread(target=self.loop.run_forever, daemon=True)
        self.thread.start()
        self.task = asyncio.run_coroutine_threadsafe(
            self._start(), self.loop).result(5)
        time.sleep(0.15)  # the ticker and the sampler are in step

    async def _start(self):
        return asyncio.get_running_loop().create_task(stall.witness(self.role))

    def stops(self):
        return _own(self.role)

    def one_stop(self, callback, want, wait_s=0.6, tries=4):
        """Run ``callback`` on the loop until it left a stop whose
        attributes ``want`` accepts: the longest such, and all it left.
        More than once only on a host so busy that the evidence itself
        reads otherwise (a spinning thread that got a third of a CPU did
        not hold the loop half the stop)."""
        for _ in range(tries):
            before = len(self.stops())
            self.loop.call_soon_threadsafe(callback)
            time.sleep(wait_s)
            new = self.stops()[before:]
            got = [s for s in new if want(s["attributes"])]
            if got:
                return max(got, key=lambda s: s["attributes"]["late_ms"]), new
        pytest.fail(f"no such stop in {tries} tries; the last left "
                    f"{[s['attributes'] for s in new]}")

    def close(self):
        self.loop.call_soon_threadsafe(self.task.cancel)
        time.sleep(0.05)
        self.loop.call_soon_threadsafe(self.loop.stop)
        self.thread.join(2)
        assert not self.thread.is_alive()


@pytest.fixture
def watched(request):
    lp = _Loop("test-" + request.node.name)
    yield lp
    lp.close()


@pytest.mark.limit(30)
def test_a_callback_that_spins_is_loop_held_and_where_names_it(watched):
    def spins_in_python():
        t = time.perf_counter()
        while time.perf_counter() - t < 0.3:
            pass

    gc.disable()  # `gc_ms == 0` below: no young-generation pass falls into the spin
    try:
        s, left = watched.one_stop(
            spins_in_python,
            lambda a: a["cause"] == "loop_held" and a["late_ms"] >= 250)
    finally:
        gc.enable()
    a = s["attributes"]
    assert len([x for x in left if x["attributes"]["late_ms"] >= 100]) == 1
    assert 250 <= a["late_ms"] <= 400 or a["host_cpu_busy_share"] > 0.9
    # the span brackets the stop: the wake before .. this wake
    assert 15 <= s["duration_ms"] - a["late_ms"] <= 45
    assert a["loop_thread_cpu_ms"] >= a["late_ms"] / 2
    assert a["where"].startswith("loop: spins_in_python (test_stall.py:")
    # the last look says what each thread used since the first
    assert " ms cpu]" in a["where"]
    # looks at 42, 62, 102, 182 ms of the stop (then 342): the sampler backs off
    assert 3 <= a["samples"] <= 6 and a["sampler_late_ms"] < a["late_ms"] / 2
    assert a["profiling"] is False and a["gc_ms"] == 0
    assert a["host"] == os.uname().nodename
    assert -1 <= a["host_cpu_busy_share"] <= 1


@pytest.mark.limit(30)
def test_a_callback_that_sleeps_is_loop_waited(watched):
    def sleeps():
        time.sleep(0.3)

    s, _ = watched.one_stop(
        sleeps, lambda a: a["cause"] == "loop_waited" and a["late_ms"] >= 250)
    a = s["attributes"]
    assert a["process_cpu_ms"] < a["late_ms"] / 2
    assert a["where"].startswith("loop: sleeps (test_stall.py:")
    assert "[0 ms cpu]" in a["where"]


@pytest.mark.limit(40)
def test_a_collection_of_a_large_cycle_is_gc(watched):
    class Node:
        pass

    def a_large_cycle():  # in the test's thread: only its collection is the loop's
        nodes = [Node() for _ in range(200_000)]
        for a, b in zip(nodes, nodes[1:] + nodes[:1]):
            a.next = b

    gc.collect()
    gc.freeze()  # what the process held before is not this collection's to walk
    gc.disable()
    try:
        for _ in range(4):
            a_large_cycle()
            time.sleep(0.1)  # what building it cost the loop is told by now
            before = len(watched.stops())
            watched.loop.call_soon_threadsafe(gc.collect)
            time.sleep(0.6)
            got = [s["attributes"] for s in watched.stops()[before:]
                   if s["attributes"]["cause"] == "gc"]
            if got:
                break
    finally:
        gc.enable()
        gc.unfreeze()
    assert got, [s["attributes"] for s in watched.stops()]
    a = got[0]
    assert a["gc_ms"] >= a["late_ms"] / 2 and a["gc_generation"] == 2
    assert a["late_ms"] < 1000


@pytest.mark.limit(40)
def test_a_thread_that_keeps_the_interpreter_is_interpreter_held(watched):
    """A thread spinning in Python lets go every 5 ms; with a switch
    interval of 0.25 s it keeps the interpreter as C code that never
    releases it does."""
    def spins():
        t = time.perf_counter()
        while time.perf_counter() - t < 0.4:
            pass

    def keeps_it():  # from the test's thread; the loop only has to want in
        was = sys.getswitchinterval()
        spinner = threading.Thread(target=spins, name="spinner", daemon=True)
        try:
            sys.setswitchinterval(0.25)
            spinner.start()
            spinner.join(2)
        finally:
            sys.setswitchinterval(was)
        assert not spinner.is_alive()

    for _ in range(4):
        before = len(watched.stops())
        keeps_it()
        time.sleep(0.1)
        got = [s["attributes"] for s in watched.stops()[before:]
               if s["attributes"]["cause"] == "interpreter_held"
               and s["attributes"]["late_ms"] >= 100]
        if got:
            break
    assert got, [s["attributes"] for s in watched.stops()]
    a = max(got, key=lambda a: a["late_ms"])
    assert a["late_ms"] <= 600
    assert a["process_cpu_ms"] >= a["late_ms"] / 2 > a["loop_thread_cpu_ms"]
    assert a["sampler_late_ms"] >= a["late_ms"] / 2


# ---- the cap, and profiling: readings of stops that never were --------------


def _reading(ms, profiling=False, cpu_ms=0):
    return stall.Reading(
        wall_ns=1_700_000_000_000_000_000 + ms * MS, mono_ns=ms * MS,
        process_cpu_ns=cpu_ms * MS, thread_cpu_ns=cpu_ms * MS, gc_ns=0,
        sampler_wake=(0, 0, 0), profiling=profiling)


def _own(role):
    return [s for s in tracing.spans()
            if s["name"] == "rt.stall" and s["attributes"]["role"] == role]


def test_the_cap_folds_the_65th_stop_of_an_interval():
    role, t = "test-cap", 0
    w = stall.Witness(role, _reading(0), interval_s=60.0)
    for _ in range(70):  # 70 stops of 50 ms, 70 ms apart: 4.9 s
        t += 70
        ev = w.wake(_reading(t, cpu_ms=t))
        assert ev["late_ms"] == 50 and ev["cause"] == "loop_held"
    assert len(_own(role)) == stall.SPANS_PER_INTERVAL == 64
    assert w.wake(_reading(t + 20, cpu_ms=t)) is None  # in time: no stop
    assert len(_own(role)) == 64
    # the first wake past the interval's end closes it with ONE span
    assert w.wake(_reading(60_020, cpu_ms=t)) is not None
    spans = _own(role)
    folded = [s for s in spans if "folded" in s["attributes"]]
    assert len(spans) == 66 and len(folded) == 1
    f = folded[0]
    assert f["attributes"]["folded"] == 6
    assert f["attributes"]["late_ms"] == 6 * 50
    assert f["attributes"]["cause"] == "loop_held"
    assert f["start_ns"] == _reading(64 * 70).wall_ns
    assert f["end_ns"] == _reading(70 * 70).wall_ns
    # every stop's seconds are in the spans, capped or not
    assert sum(s["attributes"]["late_ms"] for s in spans
               if s["end_ns"] < _reading(5000).wall_ns) == 70 * 50
    # and the next interval records again
    assert w.wake(_reading(60_100, cpu_ms=t)) is not None
    assert len(_own(role)) == 67


@pytest.mark.parametrize("before, after, want", [
    (True, True, True),
    (True, False, False),   # the stop ends after the session did: stop_trace's
    (False, True, False),   # it began before the session: start_trace's
    (False, False, False),
])
def test_profiling_is_true_only_inside_a_session(before, after, want):
    role = f"test-prof-{before}-{after}"
    w = stall.Witness(role, _reading(0, profiling=before))
    ev = w.wake(_reading(320, profiling=after))
    assert ev["profiling"] is want
    (span,) = _own(role)
    assert span["attributes"]["profiling"] is want
    assert span["start_ns"] == _reading(0).wall_ns
    assert span["end_ns"] == _reading(320).wall_ns
    assert span["attributes"]["late_ms"] == 300


def _sampled(ms, woke_ms, late_ms, due_ms):
    return _reading(ms)._replace(sampler_wake=(woke_ms * MS, late_ms * MS, due_ms * MS))


@pytest.mark.parametrize("wake, samples, want", [
    # it woke 18 ms before the stop began and not since: late with the loop
    ((982, 0, 1024), 0, ("not_scheduled", 296.0, 0)),
    # it looked on time all through the stop
    ((1182, 1, 1342), 3, ("loop_waited", 1.0, 3)),
    # a long stop just over, the next look up to 160 ms away: it runs
    ((850, 0, 1010), 0, ("not_scheduled", 310.0, 0)),
    # silent for 0.9 s while the loop ticked: it does not run, and neither
    # its lateness nor a list it left behind is evidence
    ((100, 0, 142), 2, ("loop_waited", 0.0, 0)),
    # none was ever started
    ((0, 0, 0), 0, ("loop_waited", 0.0, 0)),
])
def test_a_sampler_that_went_silent_is_no_evidence(wake, samples, want):
    w = stall.Witness(f"test-silent-{wake[0]}-{samples}", _sampled(980, *wake))
    assert w.wake(_sampled(1000, *wake)) is None
    w.samples = [("loop: f (x.py:1)", None, {}, "f (x.py:1)")] * samples
    ev = w.wake(_sampled(1320, *wake))
    assert (ev["cause"], ev["sampler_late_ms"], ev["samples"]) == want
    assert ev["late_ms"] == 300


@pytest.mark.limit(30)
def test_a_look_that_raises_does_not_end_the_sampler(watched, monkeypatch, caplog):
    real, calls = stall.where, []

    def where_fails_once(*a, **kw):
        calls.append(1)
        if len(calls) == 1:
            raise IndexError("a list swapped under the look")
        return real(*a, **kw)

    monkeypatch.setattr(stall, "where", where_fails_once)
    with caplog.at_level("ERROR", logger="ray_tpu.core.runtime"):
        for _ in range(2):
            s, _left = watched.one_stop(
                lambda: time.sleep(0.2), lambda a: a["late_ms"] >= 150)
    assert len([r for r in caplog.records if "a look failed" in r.getMessage()]) == 1
    a = s["attributes"]
    assert a["samples"] >= 2 and a["cause"] == "loop_waited"
    assert a["sampler_late_ms"] < a["late_ms"] / 2
    assert any(t.name == stall.SAMPLER and t.is_alive() for t in threading.enumerate())


# ---- the cluster's stops ----------------------------------------------------


def _span(name, pid, start_ms, end_ms, **attrs):
    return {"name": name, "pid": pid, "start_ns": start_ms * MS,
            "end_ns": end_ms * MS, "attributes": attrs}


def test_join_reads_host_chip_open_and_process():
    spans = [
        # the machine stood still: three processes at once
        _span("rt.stall", 11, 1000, 1320, role="driver", host="a", late_ms=300,
              cause="not_scheduled", where="", profiling=False),
        _span("rt.stall", 12, 1010, 1330, role="raylet", host="a", late_ms=300,
              cause="not_scheduled"),
        # the same instant on another host is another matter
        _span("rt.stall", 11, 1000, 1320, role="worker", host="b", late_ms=300,
              cause="not_scheduled"),
        # pid 13 opens a chip; 11 and 12 freeze under it, 13 itself does not
        _span("rt.start.chip_open", 13, 5000, 11000, chips="0"),
        _span("rt.stall", 11, 5100, 10900, role="driver", host="a", late_ms=5780,
              cause="not_scheduled"),
        _span("rt.stall", 13, 5100, 10900, role="worker", host="a", late_ms=5780,
              cause="loop_waited", where="loop: open_leased_chips (tpu.py:62)"),
        # alone, and of the program's own making
        _span("rt.stall", 12, 20000, 20100, role="raylet", host="a", late_ms=80,
              cause="gc", profiling=True),
        _span("rt.stall", 12, 30000, 30100, role="raylet", host="a", late_ms=80,
              cause="not_scheduled"),
        _span("llm.step", 13, 1000, 1400),
        # the replica's own evidence reads otherwise (its device threads use
        # CPU time, a socket's write does not return) while two other
        # processes did not run through most of the stop: the host's
        _span("rt.stall", 13, 40000, 41220, role="worker", host="a", late_ms=1200,
              cause="loop_waited", where="loop: write (selector_events.py:1086)"),
        _span("rt.stall", 11, 40010, 41100, role="driver", host="a", late_ms=1070,
              cause="not_scheduled"),
        _span("rt.stall", 12, 40010, 41100, role="raylet", host="a", late_ms=1070,
              cause="not_scheduled"),
        # one other process is a coincidence, and so are two that cover little
        _span("rt.stall", 13, 50000, 50320, role="worker", host="a", late_ms=300,
              cause="interpreter_held"),
        _span("rt.stall", 11, 50000, 50320, role="driver", host="a", late_ms=300,
              cause="not_scheduled"),
        _span("rt.stall", 13, 60000, 61020, role="worker", host="a", late_ms=1000,
              cause="loop_held"),
        _span("rt.stall", 11, 60100, 60150, role="driver", host="a", late_ms=30,
              cause="not_scheduled"),
        _span("rt.stall", 12, 60100, 60150, role="raylet", host="a", late_ms=30,
              cause="not_scheduled"),
    ]
    got = stall.join(spans)
    assert [s["start_ns"] for s in got] == sorted(s["start_ns"] for s in got)
    # ``outside`` is decided here and nowhere else
    assert all(s["outside"] == (s["cause"] == "not_scheduled" or s["reading"] == "host")
               for s in got)
    assert sum(s["outside"] for s in got) == 11
    more = {(s["pid"], s["start_ns"] // MS): (s["cause"], s["reading"])
            for s in got if s["start_ns"] >= 40000 * MS}
    assert more == {
        (13, 40000): ("loop_waited", "host"),
        (11, 40010): ("not_scheduled", "host"), (12, 40010): ("not_scheduled", "host"),
        (13, 50000): ("interpreter_held", "process"),
        (11, 50000): ("not_scheduled", "process"),
        (13, 60000): ("loop_held", "process"),
        (11, 60100): ("not_scheduled", "host"), (12, 60100): ("not_scheduled", "host"),
    }
    got = [s for s in got if s["start_ns"] < 40000 * MS]
    assert len(got) == 7
    read = {(s["pid"], s["host"], s["start_ns"] // MS): s["reading"] for s in got}
    assert read == {
        (11, "a", 1000): "host", (12, "a", 1010): "host",
        (11, "b", 1000): "process",
        (11, "a", 5100): "chip_open", (13, "a", 5100): "process",
        (12, "a", 20000): "process", (12, "a", 30000): "process",
    }
    by_start = {(s["pid"], s["start_ns"] // MS): s for s in got}
    assert by_start[(12, 20000)]["cause"] == "gc"
    assert by_start[(12, 20000)]["profiling"] is True
    assert by_start[(13, 5100)]["where"].startswith("loop: open_leased_chips")
    # the line shutdown() logs: from 0.1 s in all, the longest named
    assert stall.summary(got[-1:]) is None
    said = stall.summary(got)
    assert said.startswith("7 stop(s) of an io loop since init, 12.620 s in all, "
                           "6.760 s of them outside the program "
                           "(chip_open 1 x 5.780 s, host 2 x 0.600 s, process 4 x 6.240 s); ")
    assert "the longest 5.780 s: driver pid 11, cause not_scheduled (chip_open)" in said
    # a span of folded stops counts with its seconds and is no one stop
    many = _span("rt.stall", 11, 70000, 75000, role="driver", host="a",
                 late_ms=9000, cause="loop_waited", folded=200)
    said = stall.summary(stall.join(spans + [many]))
    assert said.startswith("16 stop(s) of an io loop since init, 26.620 s in all, ")
    assert "); the longest 5.780 s: driver pid 11" in said


@pytest.fixture(scope="module")
def cluster():
    import ray_tpu

    ray_tpu.init(num_cpus=2, num_tpus=0)
    yield ray_tpu
    ray_tpu.shutdown()


@pytest.mark.limit(90)
def test_a_stopped_worker_is_not_scheduled_and_two_are_the_host(cluster, caplog):
    """SIGSTOP / SIGCONT from outside: the worker alone (``process``),
    then the worker and its raylet at once (``host``); the stops reach
    the GCS at once, and ``shutdown()``'s line names the longest."""
    from ray_tpu.core import api
    from ray_tpu.util import state

    @cluster.remote
    class Idle:
        def pid(self):
            return os.getpid()

    actor = Idle.remote()
    worker = cluster.get(actor.pid.remote(), timeout=60)
    raylet = api._node_group.raylet_proc.pid
    time.sleep(0.3)

    def stop(pids):
        t0 = time.time_ns()
        for pid in pids:
            os.kill(pid, signal.SIGSTOP)
        try:
            time.sleep(0.3)
        finally:
            for pid in pids:
                os.kill(pid, signal.SIGCONT)
        time.sleep(0.25)  # a stop from 0.1 s is pushed at once
        return [s for s in state.stalls(since_ns=t0) if s["pid"] in pids
                and s["late_ms"] >= 200]

    (alone,) = stop([worker])
    assert alone["cause"] == "not_scheduled" and alone["reading"] == "process"
    assert alone["role"] == "worker" and 250 <= alone["late_ms"] <= 900
    assert alone["attributes"]["sampler_late_ms"] >= alone["late_ms"] / 2
    assert alone["attributes"]["process_cpu_ms"] < 50
    both = stop([worker, raylet])
    assert sorted(s["role"] for s in both) == ["raylet", "worker"]
    assert {s["cause"] for s in both} == {"not_scheduled"}
    assert {s["reading"] for s in both} == {"host"}

    with caplog.at_level("WARNING", logger="ray_tpu.core.api"):
        api._say_stalls(api.get_runtime())
    lines = [r.getMessage() for r in caplog.records
             if "of an io loop since init" in r.getMessage()]
    assert len(lines) == 1, lines
    assert "cause not_scheduled" in lines[0]


def test_the_gcs_keeps_stops_in_a_ring_of_their_own():
    """Stops push out older stops, never a start-up span; a push of spans
    alone (the witness's, after a stop of 0.1 s) leaves the reporter's
    last metric snapshot."""
    from ray_tpu.core import gcs as gcs_mod

    def row(name, end_ms):
        return (name, "t", f"s{end_ms}", None, (end_ms - 1) * MS, end_ms * MS, {})

    async def run():
        g = gcs_mod.GcsServer()
        tracing.drain()  # this process's own, of the tests before: not this table's
        await g.rpc_metrics_push(None, {
            "reporter": "w1", "metrics": [{"name": "m"}], "pid": 7,
            "spans": [row("rt.start.chip_open", 10)]})
        many = [row("rt.stall", 20 + i) for i in range(gcs_mod.STALL_TABLE_SIZE + 5)]
        await g.rpc_metrics_push(None, {"reporter": "w1", "pid": 7, "spans": many})
        assert g.metrics_by_reporter["w1"]["metrics"] == [{"name": "m"}]
        await g.rpc_metrics_push(None, {
            "reporter": "w1", "metrics": [], "pid": 7,
            "spans": [row("llm.step", 15)]})
        got = await g.rpc_list_spans(None, {})
        assert len(g.stall_spans) == gcs_mod.STALL_TABLE_SIZE and len(g.spans) == 2
        # oldest first by the span's end, whichever ring held it
        assert [s["name"] for s in got[:3]] == ["rt.start.chip_open", "llm.step", "rt.stall"]
        assert got[2]["end_ns"] == 25 * MS and got[2]["pid"] == 7
        both = await g.rpc_list_spans(None, {"name_prefix": stall.SPANS_PREFIX})
        assert len(both) == gcs_mod.STALL_TABLE_SIZE + 1
        assert both[0]["name"] == "rt.start.chip_open"

    asyncio.run(run())
