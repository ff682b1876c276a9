"""Distributed tracing: spans around submit/execute and inside the
decode engine, W3C context in the TaskSpec, cluster-wide aggregation in
the GCS's span table (one batched push per process per interval).

(reference: python/ray/util/tracing/tracing_helper.py — _ray_trace_ctx
propagation + submit/execute span wrappers; here the OpenTelemetry API
is bridged when an SDK provider exists and a built-in recorder serves
otherwise, since the image ships no OTel SDK.)
"""

import asyncio
import os
import time
import tracemalloc

import pytest

import ray_tpu
from ray_tpu.common.config import cfg
from ray_tpu.util import events, metrics, state, tracing

PUSH_INTERVAL_S = 0.5


@pytest.fixture(scope="module")
def traced_cluster():
    os.environ["RT_TRACING_ENABLED"] = "1"  # workers inherit
    os.environ["RT_METRICS_PUSH_INTERVAL_S"] = str(PUSH_INTERVAL_S)
    cfg.override("metrics_push_interval_s", PUSH_INTERVAL_S)
    tracing.enable()
    ray_tpu.init(num_cpus=4, num_tpus=0)
    yield
    ray_tpu.shutdown()
    tracing.disable()
    cfg.reset()
    os.environ.pop("RT_TRACING_ENABLED", None)
    os.environ.pop("RT_METRICS_PUSH_INTERVAL_S", None)


@pytest.fixture
def tracing_off():
    was = tracing._enabled
    tracing.disable()
    tracing.clear()
    yield
    if was:
        tracing.enable()


@pytest.fixture
def pushes(traced_cluster, monkeypatch):
    """Every metrics_push this process sends while the test runs."""
    from ray_tpu.core.runtime import get_runtime

    rt = get_runtime()
    sent = []
    notify = rt.gcs.notify

    async def counting(method, payload):
        if method == "metrics_push":
            sent.append((time.monotonic(), payload))
        return await notify(method, payload)

    monkeypatch.setattr(rt.gcs, "notify", counting)
    return sent


def _run_engine(requests=((4, 8), (5, 8), (3, 8)), later=()):
    """Tokens of a few concurrent requests through a tiny LLMEngine on
    two slots, so that one request waits for a slot; then those of
    ``later``, sent after the engine has stood idle for 0.1 s."""
    import jax

    from ray_tpu.models import llama
    from ray_tpu.serve.llm import LLMEngine

    async def main():
        config = llama.LlamaConfig.tiny()
        engine = LLMEngine(llama.init(jax.random.key(0), config), config,
                           max_slots=2, max_len=64)

        async def one(new, prompt_len):
            prompt = list(range(1, prompt_len + 1))
            return [t async for t in engine.stream(prompt, new)]

        out = await asyncio.gather(*(one(*r) for r in requests))
        if later:
            await asyncio.sleep(0.1)
            out += await asyncio.gather(*(one(*r) for r in later))
        return out

    return asyncio.run(main())


def _run_engine_traced(**kwargs):
    """``_run_engine`` with the operator's switch on: (tokens, spans)."""
    was = tracing._enabled
    tracing.enable()
    tracing.clear()
    try:
        out = _run_engine(**kwargs)
    finally:
        if not was:
            tracing.disable()
    return out, tracing.spans()


def _switched(rows):
    """Without the XLA build spans (``xla.*``): they are recorded
    whatever the switch says once a process listens to JAX's compile
    events (util/compile_cache.py), as earlier tests of a run make it.
    So are the stall witness's ``rt.stall``, whenever a loop of this
    process wakes late (core/stall.py)."""
    name = (lambda r: r["name"]) if rows and isinstance(rows[0], dict) else (
        lambda r: r[0])
    return [r for r in rows if not name(r).startswith(("xla.", "rt.stall"))]


def _histogram_count(name: str, tags_key: str = "[]") -> float:
    for m in metrics.registry_snapshot():
        if m["name"] == name:
            return m["series"].get(f"{tags_key}|le=+Inf", 0.0)
    return 0.0


class TestTracing:
    def test_carrier_is_w3c_traceparent(self):
        c = tracing.inject()
        ver, trace_id, span_id, flags = c["traceparent"].split("-")
        assert ver == "00" and flags == "01"
        assert len(trace_id) == 32 and len(span_id) == 16

    def test_task_execute_parents_under_submit(self, traced_cluster):
        tracing.clear()

        @ray_tpu.remote
        def traced_add(x):
            return x + 1

        assert ray_tpu.get(traced_add.remote(1), timeout=60) == 2
        local = tracing.spans()
        submit = [s for s in local if s["name"].startswith("submit")]
        assert submit, local
        trace_id = submit[-1]["trace_id"]
        # the worker-side execute span lands in the GCS span table with
        # the SAME trace id, parented under the submit span
        deadline = time.monotonic() + 30
        execs = []
        while time.monotonic() < deadline and not execs:
            execs = [
                e for e in state.list_spans(trace_id=trace_id)
                if e["name"].startswith("execute")
            ]
            time.sleep(0.2)
        assert execs, "no execute span exported"
        f = execs[0]
        assert f["parent_id"] == submit[-1]["span_id"]
        assert f["pid"] != os.getpid()  # actually ran in the worker
        assert isinstance(f["start_ns"], int) and f["end_ns"] >= f["start_ns"]
        # spans have a table of their own: none among the cluster events
        assert not [e for e in events.list_events(limit=2000)
                    if e.get("source") == "tracing"]

    def test_actor_call_chain_keeps_one_trace(self, traced_cluster):
        tracing.clear()

        @ray_tpu.remote
        def inner():
            return os.getpid()

        @ray_tpu.remote
        class Outer:
            def call_inner(self):
                # nested submit INSIDE the actor: its span must parent
                # under this actor's execute span (same trace)
                return ray_tpu.get(inner.remote(), timeout=60)

        o = Outer.remote()
        with tracing.span("driver-root"):
            ray_tpu.get(o.call_inner.remote(), timeout=60)
        root = tracing.spans()[-1]
        assert root["name"] == "driver-root"
        trace_id = root["trace_id"]
        deadline = time.monotonic() + 30
        names = set()
        while time.monotonic() < deadline:
            names = {
                e["name"] for e in tracing.collect(trace_id=trace_id)
            }
            if any(
                n.startswith("execute") and n.endswith("inner")
                and "call_inner" not in n
                for n in names
            ) and any(n.startswith("execute call_inner") for n in names):
                break
            time.sleep(0.2)
        assert any(n.startswith("execute call_inner") for n in names), names
        # plain tasks carry their qualified name; the nested task's
        # execute span is in the SAME trace
        assert any(
            n.startswith("execute") and n.endswith("inner")
            and "call_inner" not in n
            for n in names
        ), names

    def test_disabled_tracing_adds_nothing(self, pushes, tracing_off):
        """What the SWITCH governs.  The stall witness records its ``rt.stall``
        spans whatever the switch says (``tracing.record``, PR 53), and
        beside five other xdist workers this process's io loop does stop
        for 20 ms now and then: those spans are the witness's, not a span
        site's, and are left out of what is held to be empty (this test
        failed on the driver's tier-1 runs since PR 53 for want of that)."""
        from ray_tpu.core import stall
        from ray_tpu.core.runtime import get_runtime
        from ray_tpu.serve import llm

        def switched(spans):  # as dicts (``spans``) or rows (``drain``, pushes)
            return [s for s in spans
                    if (s["name"] if isinstance(s, dict) else s[0]) != stall.SPAN]

        @ray_tpu.remote
        def untraced():
            return 1

        ray_tpu.get(untraced.remote(), timeout=60)
        assert switched(tracing.spans()) == [] and switched(tracing.drain()) == []
        assert tracing.current() is None  # no context-variable write
        # a span site that is off allocates nothing in the recorder; a stop
        # the witness records meanwhile does, so such a round is taken again
        for _ in range(5):
            stops = len(tracing.spans())
            tracemalloc.start()
            try:
                before = tracemalloc.take_snapshot()
                for _ in range(1000):
                    if tracing.enabled():
                        tracing.span("never")
                    # the engine asks once per decode step, then hands its
                    # span sites the step's span: None while tracing is off
                    life = tracing.root("never") if tracing.enabled() else None
                    with llm._part(life, "never"):
                        pass
                after = tracemalloc.take_snapshot()
            finally:
                tracemalloc.stop()
            if len(tracing.spans()) == stops:
                break
        grown = [
            d for d in after.compare_to(before, "filename")
            if d.size_diff > 0 and d.traceback[0].filename in (
                tracing.__file__, llm.__file__)
        ]
        assert not grown, grown
        # and nothing is sent: a push now carries no span of a span site
        rt = get_runtime()
        rt._run(rt.push_telemetry())
        time.sleep(2 * PUSH_INTERVAL_S)
        assert not [p for _t, p in pushes if switched(p.get("spans") or [])]

    def test_span_records_error_attribute(self):
        with pytest.raises(ValueError):
            with tracing.span("boom"):
                raise ValueError("x")
        s = tracing.spans()[-1]
        assert s["name"] == "boom" and s["attributes"]["error"] == "ValueError"

    def test_spans_are_exported_in_batches(self, pushes):
        """N spans cost at most one RPC per push interval, and each is
        sent exactly once."""
        from ray_tpu.core.runtime import get_runtime

        rt = get_runtime()
        rt._run(rt.push_telemetry())  # what earlier tests left
        del pushes[:]
        t0 = time.monotonic()
        made = []
        while time.monotonic() - t0 < 2.5 * PUSH_INTERVAL_S:
            with tracing.span("batched") as sp:
                made.append(sp.span_id)
            time.sleep(0.002)
        time.sleep(1.5 * PUSH_INTERVAL_S)
        elapsed = time.monotonic() - t0
        with_spans = [p for _t, p in pushes if p.get("spans")]
        assert 1 <= len(with_spans) <= elapsed / PUSH_INTERVAL_S + 1
        assert len(pushes) <= elapsed / PUSH_INTERVAL_S + 1
        sent = [row[2] for p in with_spans for row in p["spans"]
                if row[0] == "batched"]
        assert sorted(sent) == sorted(made) and len(made) > 100
        got = tracing.collect(name_prefix="batched")
        assert sorted(s["span_id"] for s in got) == sorted(made)
        assert {s["pid"] for s in got} == {os.getpid()}

    def test_ring_is_drained_exactly_once(self):
        tracing.clear()
        for i in range(5):
            with tracing.span("drained", i=i):
                pass
        first = tracing.drain()
        assert [r[0] for r in first] == ["drained"] * 5
        assert [r[6]["i"] for r in first] == list(range(5))
        assert tracing.drain() == []
        assert len(tracing.spans()) == 5  # the ring keeps them for reading
        with tracing.span("drained", i=5):
            pass
        assert [r[6]["i"] for r in tracing.drain()] == [5]
        # ids: a per-process prefix and a counter, W3C sizes
        a, b = first[0], first[1]
        assert len(a[1]) == 32 and len(a[2]) == 16
        assert a[2][:8] == b[2][:8] and a[2] != b[2]

    def test_profiler_session_turns_spans_on(self, tracing_off, tmp_path):
        """Between start_trace and stop_trace spans are recorded without
        the operator's switch, and show in the trace's host plane."""
        import jax
        from jax.profiler import ProfileData

        from chipbench import trace_reduce

        jax.numpy.zeros(1).block_until_ready()
        assert not tracing.enabled()
        jax.profiler.start_trace(str(tmp_path))
        try:
            assert tracing.enabled()
            with tracing.span("probe.in_session", k=1) as sp:
                time.sleep(0.001)
        finally:
            jax.profiler.stop_trace()
        assert not tracing.enabled()
        with tracing.span("probe.after"):  # a site would not get here
            pass
        assert [s["name"] for s in _switched(tracing.spans())] == [
            "probe.in_session", "probe.after"]
        data = ProfileData.from_file(trace_reduce.find_xplane(str(tmp_path)))
        found = [
            (plane.name, dict(e.stats))
            for plane in data.planes for line in plane.lines
            for e in line.events if e.name == "probe.in_session"
        ]
        assert found, [p.name for p in data.planes]
        plane, stats = found[0]
        assert plane.startswith("/host:")
        # a hex id without a letter comes back from the profiler as a number
        assert str(stats.get("span_id")) in (sp.span_id, sp.span_id.lstrip("0"))

    def test_engine_spans_and_histograms(self):
        ttft0 = _histogram_count("llm_engine_ttft_ms")
        wait0 = _histogram_count("llm_queue_wait_ms",
                                 '[["outcome", "admitted"]]')
        out, rows = _run_engine_traced()
        assert [len(o) for o in out] == [4, 5, 3]
        # one llm.step is the life of one decode step; lives overlap and
        # end out of order, so they are read in the order they began
        steps = sorted((s for s in rows if s["name"] == "llm.step"),
                       key=lambda s: s["start_ns"])
        assert steps and [s["attributes"]["step"] for s in steps] == sorted(
            s["attributes"]["step"] for s in steps)
        decoding = [s for s in steps if s["attributes"]["active"]]
        assert [s["attributes"]["step"] for s in decoding] == list(
            range(1, len(decoding) + 1))
        launches, syncs = [], []
        for step in decoding:
            kids = sorted(
                (s for s in rows if s["parent_id"] == step["span_id"]),
                key=lambda s: s["start_ns"],
            )
            names = [k["name"] for k in kids]
            if step["attributes"]["admitted"]:
                assert names[0] == "llm.step.admit"
                names = names[1:]
            assert names == [
                "llm.step.build", "llm.step.dispatch", "llm.step.sync",
                "llm.step.deliver", "llm.step.yield",
            ]
            assert all(k["trace_id"] == step["trace_id"] for k in kids)
            assert kids[0]["start_ns"] >= step["start_ns"]
            assert kids[-1]["end_ns"] <= step["end_ns"]
            for a, b in zip(kids, kids[1:]):
                assert a["end_ns"] <= b["start_ns"]
            dispatch = kids[-4]
            launch, = [s for s in rows if s["parent_id"] == dispatch["span_id"]]
            assert launch["name"] == "llm.step.launch"
            assert dispatch["start_ns"] <= launch["start_ns"]
            assert launch["end_ns"] <= dispatch["end_ns"]
            launches.append(launch)
            syncs.append(kids[-3])
        # launched ahead: step k's sync begins after step k+1's launch;
        # else (the step after an admission) step k was delivered first
        assert not decoding[0]["attributes"]["ahead"]
        for k in range(len(decoding) - 1):
            if decoding[k + 1]["attributes"]["ahead"]:
                assert launches[k + 1]["end_ns"] <= syncs[k]["start_ns"]
            else:
                assert decoding[k + 1]["attributes"]["admitted"]
                assert syncs[k]["end_ns"] <= launches[k + 1]["start_ns"]
        assert sum(s["attributes"]["ahead"] for s in decoding) == len(decoding) - 2
        requests = [s for s in rows if s["name"] == "llm.request"]
        prefills = [s for s in rows if s["name"] == "llm.prefill"]
        assert len(requests) == len(prefills) == 3
        assert ({p["trace_id"] for p in prefills}
                == {r["trace_id"] for r in requests})
        admits = {s["span_id"] for s in rows if s["name"] == "llm.step.admit"}
        assert all(p["parent_id"] in admits for p in prefills)
        # the third request found both slots taken
        assert sorted(p["attributes"]["rows_stalled"] for p in prefills) == [0, 1, 1]
        assert sum(s["attributes"]["admitted"] for s in steps) == 3
        # the two histograms: one observation each per request
        assert _histogram_count("llm_engine_ttft_ms") == ttft0 + 3
        assert _histogram_count(
            "llm_queue_wait_ms", '[["outcome", "admitted"]]') == wait0 + 3

    def test_engine_spans_fit_the_benchmarks_reader(self):
        """``chipbench/span_reduce.py`` pairs the k-th execution of the
        decode program in a device trace with the k-th ``llm.step`` and
        raises on the chip where they do not fit.  The engine's real
        spans and a module line made for them — one execution a step,
        begun after its launch (and after the one before it), done
        before its sync returned — must align, to the few microseconds
        the line was made with.  (The 0.1 s the engine stands idle is a
        change of the step period that no pairing off by a step or two
        survives, as a prefill's is on the chip.)"""
        from chipbench import span_reduce

        _, spans = _run_engine_traced(later=((6, 8), (4, 8)))
        steps = span_reduce.steps_of(spans)
        assert [s["span"]["attributes"]["step"] for s in steps] == list(
            range(1, len(steps) + 1))
        assert len(steps) == len(
            [s for s in spans if s["name"] == "llm.step.launch"])
        assert any(s["span"]["attributes"]["ahead"] for s in steps)
        offset, slack = 1_700_000_000_000_000_000, 2_000  # ns
        events, done = [], 0
        for k, step in enumerate(steps):
            start = max(step["launch_ns"] - offset + slack, done + slack)
            done = step["parts"]["sync"]["end_ns"] - offset - slack
            assert start + slack < done
            events += [
                [f"jit_decode_step_rowwise({k})", start, done - start - slack, {}],
                ["jit__argmax(1)", done - slack // 2, slack // 2, {}],
            ]
        planes = [{"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": events}]}]
        lo, hi = span_reduce.align(planes, spans)
        assert lo <= offset <= hi and hi - lo <= 2 * slack

    def test_engine_records_nothing_when_off(self, tracing_off):
        ttft0 = _histogram_count("llm_engine_ttft_ms")
        out = _run_engine()
        assert [len(o) for o in out] == [4, 5, 3]
        assert _switched(tracing.spans()) == []
        assert _switched(tracing.drain()) == []
        assert tracing.open_span() is None
        # the counters are always on
        assert _histogram_count("llm_engine_ttft_ms") == ttft0 + 3

    def test_a_stalled_loop_says_so_once(self, traced_cluster, caplog):
        """The io loop held for over a second: one log line with the CPU
        time used meanwhile, the open span and the cause, the counter
        moves under the cause's tag, and the stop is a span."""
        from ray_tpu.core.runtime import get_runtime

        rt = get_runtime()

        def hold():
            with tracing.span("holds.the.loop"):
                time.sleep(1.4)

        with caplog.at_level("WARNING", logger="ray_tpu.core.runtime"):
            rt._loop.call_soon_threadsafe(hold)
            time.sleep(2.0)
        lines = [r.getMessage() for r in caplog.records
                 if "stood still" in r.getMessage()]
        assert len(lines) == 1, lines
        assert f"driver pid {os.getpid()}" in lines[0]
        assert "open span: holds.the.loop; cause: loop_waited; where: loop: hold (" in lines[0]
        (lost,) = [m for m in metrics.registry_snapshot()
                   if m["name"] == "loop_stall_seconds_total"]
        assert lost["series"]['[["cause", "loop_waited"], ["role", "driver"]]'] >= 1.0
        (stop,) = [s for s in tracing.spans() if s["name"] == "rt.stall"
                   and s["attributes"]["late_ms"] >= 1000]
        assert stop["attributes"]["open_span"] == "holds.the.loop"
        assert stop["attributes"]["role"] == "driver"
        assert 1400 <= stop["duration_ms"] <= 1700


# ---- start-up spans: recorded whatever the switch says (PR 34) --------------

_COLD_START = r"""
import json, os, sys, time
os.environ["RT_TPU_CHIPS_OVERRIDE"] = "1"
os.environ["RT_METRICS_PUSH_INTERVAL_S"] = "0.5"
os.environ.pop("RT_TRACING_ENABLED", None)
import ray_tpu
from ray_tpu import serve
from ray_tpu.serve.llm import LlamaDeployment
from ray_tpu.util import tracing

ray_tpu.init(num_cpus=2)
app = LlamaDeployment.options(ray_actor_options={"num_tpus": 1}).bind(
    max_slots=2, max_len=64)
handle = serve.run(app, name="cold", route_prefix=None)
tokens = handle.options(method_name="generate_all").remote(
    [1, 2, 3], max_new_tokens=10).result(timeout_s=150)
stats = handle.options(method_name="stats").remote().result(timeout_s=60)
time.sleep(1.6)  # the replica's next pushes carry its last compiles
deadline = time.time() + 20
while time.time() < deadline:  # the raylet pushes with its heartbeat
    spans = tracing.collect()
    names = {s["name"] for s in spans}
    if {"serve.start.app", "rt.start.worker", "llm.start.engine"} <= names:
        break
    time.sleep(0.3)
print("RESULT " + json.dumps({
    "enabled": tracing.enabled(), "tokens": tokens, "spans": spans,
    "compiles": stats["compiles"], "driver": os.getpid(),
}))
ray_tpu.shutdown()
"""


@pytest.fixture(scope="module")
def cold_start():
    """One cluster with a fake chip, the switch off, a tiny replica leased
    the chip through ``serve.run`` and one request served, in a process of
    its own (this one's cluster has tracing on): what its GCS holds."""
    import json
    import subprocess
    import sys

    env = {k: v for k, v in os.environ.items() if k != "RT_TRACING_ENABLED"}
    out = subprocess.run(
        [sys.executable, "-c", _COLD_START], env=env, capture_output=True,
        text=True, timeout=170,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert out.returncode == 0, out.stderr[-3000:]
    line = next(ln for ln in out.stdout.splitlines() if ln.startswith("RESULT "))
    return json.loads(line[len("RESULT "):])


def _one(spans, name, **attrs):
    got = [s for s in spans if s["name"] == name and all(
        s["attributes"].get(k) == v for k, v in attrs.items())]
    assert len(got) == 1, (name, attrs, [s["attributes"] for s in got])
    return got[0]


def _within(child, parent, slack_ns=0):
    return (parent["start_ns"] - slack_ns <= child["start_ns"]
            and child["end_ns"] <= parent["end_ns"] + slack_ns)


class TestStartUp:
    def test_recorded_with_the_switch_off_where_a_site_is_not(self, tracing_off):
        assert not tracing.enabled()
        with tracing.startup("probe.start", root=True, k=1) as boot:
            with tracing.startup("probe.start.child") as child:
                pass
            # a site asks the switch first, and the switch has not moved
            assert not tracing.enabled()
        later = tracing.startup("probe.start.later")  # no span is open any more
        later.finish()
        carried = tracing.startup("probe.start.carried", carrier={
            "traceparent": f"00-{'ab' * 16}-{'cd' * 8}-01"})
        carried.finish()
        rows = {s["name"]: s for s in _switched(tracing.spans())}
        assert list(rows) == ["probe.start.child", "probe.start",
                              "probe.start.later", "probe.start.carried"]
        assert rows["probe.start"]["attributes"] == {"k": 1}
        for name in ("probe.start.child", "probe.start.later"):
            assert rows[name]["parent_id"] == boot.span_id
            assert rows[name]["trace_id"] == boot.trace_id
        assert (rows["probe.start.carried"]["trace_id"],
                rows["probe.start.carried"]["parent_id"]) == ("ab" * 16, "cd" * 8)
        # outside any span a carrier names the process's start-up root
        assert tracing.inject()["traceparent"] == (
            f"00-{boot.trace_id}-{boot.span_id}-01")
        assert child.end_ns <= boot.end_ns

    def test_a_cold_start_is_one_trace_from_cluster_to_actor(self, cold_start):
        spans = cold_start["spans"]
        assert not cold_start["enabled"]
        cluster = _one(spans, "rt.start.cluster")
        assert cluster["pid"] == cold_start["driver"]
        assert cluster["attributes"] == {
            "nodes": 1, "tpu_detected_by": "RT_TPU_CHIPS_OVERRIDE"}
        for name in ("rt.start.gcs", "rt.start.raylet"):
            child = _one(spans, name)
            assert child["parent_id"] == cluster["span_id"]
            assert _within(child, cluster)
        opened = _one(spans, "rt.start.chip_open")
        assert opened["attributes"]["chips"] == "0"
        assert opened["attributes"]["device_kind"] == "cpu"  # a fake chip
        assert opened["attributes"]["process_cpu_s"] >= 0
        holder = opened["pid"]
        boot = next(s for s in spans
                    if s["name"] == "rt.start.boot" and s["pid"] == holder)
        spawned = _one(spans, "rt.start.worker",
                       worker_id=boot["attributes"]["worker_id"])
        bind = next(s for s in spans
                    if s["name"] == "rt.start.lease_bind" and s["pid"] == holder)
        init = _one(spans, "rt.start.actor_init", **{"class": "ReplicaActor"})
        assert init["pid"] == holder and len(init["attributes"]["actor_id"]) == 32
        # between the two: the class and its arguments unpickled (the imports)
        loaded = _one(spans, "rt.start.actor_load", **{"class": "ReplicaActor"})
        assert loaded["pid"] == holder
        assert loaded["attributes"]["actor_id"] == init["attributes"]["actor_id"]
        assert bind["end_ns"] <= loaded["start_ns"] <= loaded["end_ns"] <= init["start_ns"]
        chain = [cluster, spawned, boot, bind, opened, init]
        assert {s["trace_id"] for s in chain} == {cluster["trace_id"]}
        assert all(s["parent_id"] for s in chain[1:])
        in_time = [cluster, spawned, bind, init, opened]  # opened by __init__
        assert [s["start_ns"] for s in in_time] == sorted(
            s["start_ns"] for s in in_time)
        # every child inside its parent, where the parent is the span that
        # waits for it: the boot in its spawn (the OS stamps a start to a
        # clock tick), the chip's opening in the __init__ that asks for it
        assert boot["parent_id"] == spawned["span_id"]
        assert _within(boot, spawned, slack_ns=20_000_000)
        assert spawned["pid"] not in (holder, cluster["pid"])
        assert opened["parent_id"] == init["span_id"] and _within(opened, init)
        assert bind["attributes"] == {"chips": "0", "platform": "cpu"}

    def test_the_replicas_start_and_its_builds_hang_under_the_actor(self, cold_start):
        spans = cold_start["spans"]
        init = _one(spans, "rt.start.actor_init", **{"class": "ReplicaActor"})
        weights, engine = _one(spans, "llm.start.weights"), _one(spans, "llm.start.engine")
        for s in (weights, engine):
            assert s["parent_id"] == init["span_id"] and _within(s, init)
            assert s["trace_id"] == init["trace_id"]
        assert weights["end_ns"] <= engine["start_ns"]
        assert weights["attributes"]["param_bytes"] > 0
        assert engine["attributes"]["cache_bytes"] > 0
        # the chips are open before the first array: the weights'
        assert _one(spans, "rt.start.chip_open")["end_ns"] <= weights["start_ns"]
        # the driver's ask: from serve.run's call until the controller has
        # asked for the replica, whose own spans go on from there
        app = _one(spans, "serve.start.app")
        assert app["attributes"] == {"app": "cold", "replicas": 1}
        assert app["pid"] == cold_start["driver"]
        assert app["parent_id"] == _one(spans, "rt.start.cluster")["span_id"]
        assert app["trace_id"] == init["trace_id"]
        boot = next(s for s in spans
                    if s["name"] == "rt.start.boot" and s["pid"] == init["pid"])
        spawned = _one(spans, "rt.start.worker",
                       worker_id=boot["attributes"]["worker_id"])
        assert app["start_ns"] <= spawned["start_ns"]
        assert app["end_ns"] <= init["start_ns"]
        built = [s for s in spans
                 if s["name"].startswith("xla.") and s["pid"] == init["pid"]]
        assert {s["name"] for s in built} == {"xla.trace", "xla.lower", "xla.compile"}
        programs = {s["attributes"]["fun_name"] for s in built
                    if s["name"] == "xla.compile"}
        assert {"jit(prefill_into_slot)", "jit(decode_step_rowwise)"} <= programs
        assert all("cache_hit" in s["attributes"] for s in built
                   if s["name"] == "xla.compile")
        compiles = cold_start["compiles"]
        assert compiles["count"] == compiles["cache_hits"] + compiles["cache_misses"]
        assert compiles["count"] <= sum(s["name"] == "xla.compile" for s in built)

    def test_a_served_request_records_no_switched_span(self, cold_start):
        assert len(cold_start["tokens"]) == 10
        names = {s["name"] for s in cold_start["spans"]}
        assert not [n for n in names if n.startswith(
            ("llm.step", "llm.prefill", "llm.request", "llm.idle", "serve.stream",
             "submit", "execute"))]
        assert all(n.startswith(("rt.start.", "serve.start.", "llm.start.", "xla.",
                                 "rt.stall"))  # the always-on ones
                   for n in names), names


class TestChipOpen:
    def test_a_worker_leased_chips_opens_them_once_inside_a_span(
            self, tracing_off, monkeypatch):
        from ray_tpu.accelerators import tpu

        tracing.clear()
        monkeypatch.setattr(tpu, "_chips_opened", False)
        monkeypatch.delenv("TPU_VISIBLE_CHIPS", raising=False)
        tpu.open_leased_chips()  # no lease names a chip: nothing
        assert not tpu._chips_opened
        monkeypatch.setenv("TPU_VISIBLE_CHIPS", "2,3")
        with tracing.startup("probe.init") as parent:
            tpu.open_leased_chips()
            tpu.open_leased_chips()
        opened = [s for s in tracing.spans() if s["name"] == "rt.start.chip_open"]
        assert len(opened) == 1 and opened[0]["parent_id"] == parent.span_id
        attrs = opened[0]["attributes"]
        assert (attrs["chips"], attrs["device_kind"]) == ("2,3", "cpu")
        assert attrs["process_cpu_s"] >= 0


class TestCompileSpans:
    def test_a_first_call_is_three_spans_with_its_fun_name(self, tracing_off):
        import jax

        from ray_tpu.util import compile_cache

        compile_cache.configure()
        log = compile_cache.CompileLog()

        def startup_probe_program(x):
            return x * 3 + 1

        tracing.clear()
        with tracing.startup("probe.builds") as parent:
            jax.jit(startup_probe_program)(jax.numpy.arange(7)).block_until_ready()
        mine = [s for s in tracing.spans()
                if "startup_probe_program" in s["attributes"].get("fun_name", "")]
        assert [(s["name"], s["attributes"]["fun_name"]) for s in mine] == [
            ("xla.trace", "startup_probe_program"),
            ("xla.lower", "jit(startup_probe_program)"),
            ("xla.compile", "jit(startup_probe_program)"),
        ]
        assert all(s["parent_id"] == parent.span_id for s in mine)
        assert all(parent.start_ns <= s["start_ns"] <= s["end_ns"] <= parent.end_ns + 1000
                   for s in mine)
        assert mine[2]["attributes"]["cache_hit"] in (True, False)
        snap = log.snapshot()
        assert snap["count"] >= 1 and snap["trace_lower_seconds"] > 0
        assert set(snap) == {"count", "seconds", "cache_hits", "cache_misses",
                             "trace_lower_seconds", "last_compiled"}

    def test_the_two_ends_are_jaxs_own_and_a_hit_is_told_from_a_miss(self, tracing_off):
        """JAX's events sent by hand: a span's ends are the event's to the
        float's 256 ns, a cache hit and its load time are sent before the
        compile's own event and belong to it alone."""
        import jax.monitoring

        from ray_tpu.util import compile_cache

        compile_cache.configure()
        first, second = compile_cache.CompileLog(), None
        compile = "/jax/core/compile/backend_compile_duration"
        t0 = 1_790_000_000.25
        jax.monitoring.record_event_time_span(
            "/jax/core/compile/jaxpr_trace_duration", t0, t0 + 0.5, fun_name="by_hand")
        jax.monitoring.record_event_time_span(
            "/jax/core/compile/jaxpr_to_mlir_module_duration", t0 + 0.5, t0 + 0.75,
            fun_name="jit(by_hand)")
        jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
        jax.monitoring.record_event_duration_secs(
            "/jax/compilation_cache/cache_retrieval_time_sec", 0.125)
        jax.monitoring.record_event_time_span(
            compile, t0 + 0.75, t0 + 1.0, fun_name="jit(by_hand)")
        assert first.snapshot() == {
            "count": 1, "seconds": 0.25, "cache_hits": 1, "cache_misses": 0,
            "trace_lower_seconds": 0.75, "last_compiled": None}
        second = compile_cache.CompileLog()  # counts from here
        jax.monitoring.record_event_time_span(
            compile, t0 + 2.0, t0 + 4.0, fun_name="jit(missed)")
        jax.monitoring.record_event_time_span(
            "/jax/some/other/event", t0, t0 + 9.0, fun_name="ignored")
        assert second.snapshot() == {
            "count": 1, "seconds": 2.0, "cache_hits": 0, "cache_misses": 1,
            "trace_lower_seconds": 0.0, "last_compiled": "jit(missed)"}
        assert first.snapshot()["count"] == 2
        assert first.snapshot()["last_compiled"] == "jit(missed)"
        rows = [s for s in tracing.spans() if s["attributes"].get("fun_name") in (
            "by_hand", "jit(by_hand)", "jit(missed)", "ignored")]
        assert [(s["name"], s["attributes"]) for s in rows] == [
            ("xla.trace", {"fun_name": "by_hand"}),
            ("xla.lower", {"fun_name": "jit(by_hand)"}),
            ("xla.compile", {"fun_name": "jit(by_hand)", "cache_hit": True,
                             "load_s": 0.125}),
            ("xla.compile", {"fun_name": "jit(missed)", "cache_hit": False}),
        ]
        assert [(s["start_ns"], s["end_ns"]) for s in rows] == [
            (int(a * 1e9), int(b * 1e9)) for a, b in (
                (t0, t0 + 0.5), (t0 + 0.5, t0 + 0.75), (t0 + 0.75, t0 + 1.0),
                (t0 + 2.0, t0 + 4.0))]

    def test_only_a_programs_own_trace_is_a_span(self, tracing_off):
        """JAX reports a trace for every jitted function called inside
        another's trace and for every call that finds its jaxpr kept: a
        span is the outermost trace that a lowering follows."""
        import jax
        import jax.monitoring

        from ray_tpu.util import compile_cache

        compile_cache.configure()

        @jax.jit
        def startup_inner_program(x):
            return x + 1

        def startup_outer_program(x):
            return startup_inner_program(x) * 2

        tracing.clear()
        x = jax.numpy.arange(5)
        for _ in range(3):  # the second and third find everything kept
            jax.jit(startup_outer_program)(x).block_until_ready()
        for _ in range(4):  # eager: one program, looked up four times
            y = x + x
        y.block_until_ready()
        names = [(s["name"], s["attributes"]["fun_name"]) for s in tracing.spans()
                 if s["name"].startswith("xla.")]
        assert not [n for n in names if "startup_inner_program" in n[1]]
        assert [n for n in names if "startup_outer_program" in n[1]] == [
            ("xla.trace", "startup_outer_program"),
            ("xla.lower", "jit(startup_outer_program)"),
            ("xla.compile", "jit(startup_outer_program)"),
        ]
        assert names.count(("xla.trace", "add")) == names.count(
            ("xla.lower", "jit(add)")) <= 1
        # by hand: JAX sends a stage's start as a scalar; the inner trace
        # ends first and the outer holds it; no lowering follows the third
        trace = "/jax/core/compile/jaxpr_trace_duration"
        t0 = 1_790_000_100.0
        log = compile_cache.CompileLog()
        jax.monitoring.record_scalar(trace, t0, fun_name="outer_by_hand")
        jax.monitoring.record_scalar(trace, t0 + 1, fun_name="inner_by_hand")
        jax.monitoring.record_event_time_span(
            trace, t0 + 1, t0 + 2, fun_name="inner_by_hand")
        jax.monitoring.record_event_time_span(
            trace, t0, t0 + 3, fun_name="outer_by_hand")
        jax.monitoring.record_event_time_span(
            "/jax/core/compile/jaxpr_to_mlir_module_duration", t0 + 3, t0 + 4,
            fun_name="jit(outer_by_hand)")
        jax.monitoring.record_scalar(trace, t0 + 5, fun_name="kept_by_hand")
        jax.monitoring.record_event_time_span(
            trace, t0 + 5, t0 + 6, fun_name="kept_by_hand")
        assert [(s["name"], s["attributes"]["fun_name"]) for s in tracing.spans()
                if "by_hand" in s["attributes"].get("fun_name", "")] == [
            ("xla.trace", "outer_by_hand"), ("xla.lower", "jit(outer_by_hand)")]
        assert log.snapshot()["trace_lower_seconds"] == 4.0

    def test_one_listener_a_process_however_many_logs(self):
        from jax._src import monitoring

        from ray_tpu.util import compile_cache

        def listeners():
            return (len(monitoring.get_event_time_span_listeners()),
                    len(monitoring.get_event_listeners()),
                    len(monitoring.get_scalar_listeners()),
                    len(monitoring.get_event_duration_listeners()))

        compile_cache.configure()
        before = listeners()
        for _ in range(3):
            compile_cache.CompileLog()
            compile_cache.configure()
        assert listeners() == before

    @pytest.mark.limit(170)
    def test_a_second_process_with_the_same_cache_reports_the_hit(self, tmp_path):
        import json
        import subprocess
        import sys

        script = r"""
import json, os
import jax
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
from ray_tpu.util import compile_cache, tracing
assert compile_cache.configure() == os.environ["JAX_COMPILATION_CACHE_DIR"]
log = compile_cache.CompileLog()
def kept_between_processes(x):
    return (x @ x.T).sum()
jax.jit(kept_between_processes)(jax.numpy.ones((8, 8))).block_until_ready()
print("RESULT " + json.dumps({"snapshot": log.snapshot(), "spans": [
    s for s in tracing.spans()
    if s["attributes"].get("fun_name") == "jit(kept_between_processes)"
    and s["name"] == "xla.compile"]}))
"""
        env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path),
                   JAX_PLATFORMS="cpu")
        runs = []
        for _ in range(2):
            out = subprocess.run(
                [sys.executable, "-c", script], env=env, capture_output=True,
                text=True, timeout=80,
                cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            )
            assert out.returncode == 0, out.stderr[-2000:]
            runs.append(json.loads(next(
                ln for ln in out.stdout.splitlines() if ln.startswith("RESULT ")
            )[len("RESULT "):]))
        cold, warm = runs
        assert [s["attributes"]["cache_hit"] for s in cold["spans"]] == [False]
        assert cold["snapshot"]["last_compiled"] == "jit(kept_between_processes)"
        assert cold["snapshot"]["cache_misses"] >= 1
        assert [s["attributes"]["cache_hit"] for s in warm["spans"]] == [True]
        assert warm["spans"][0]["attributes"]["load_s"] > 0
        assert warm["snapshot"]["cache_hits"] >= 1
        assert warm["snapshot"]["last_compiled"] != "jit(kept_between_processes)"


class TestStepSitesUnchanged:
    """The switched sites inside ``llm.step`` record what they did before
    the start-up spans: ten decode steps of a tiny engine, counted."""

    REQUEST = ((10, 4),)  # one request: 10 new tokens after a 4-token prompt

    def test_ten_steps_switched_on(self):
        out, rows = _run_engine_traced(requests=self.REQUEST)
        assert [len(o) for o in out] == [10]
        count = {}
        for s in _switched(rows):
            count[s["name"]] = count.get(s["name"], 0) + 1
        steps = [s for s in rows if s["name"] == "llm.step"
                 and s["attributes"]["active"]]
        assert len(steps) == 9  # the prefill gives the first token
        for part in ("build", "dispatch", "launch", "sync", "deliver", "yield"):
            assert count[f"llm.step.{part}"] == 9, (part, count)
        assert count["llm.step.admit"] == count["llm.prefill"] == 1
        assert count["llm.request"] == 1
        assert set(count) <= {
            "llm.step", "llm.step.admit", "llm.prefill", "llm.step.build",
            "llm.step.dispatch", "llm.step.launch", "llm.step.sync",
            "llm.step.deliver", "llm.step.yield", "llm.request", "llm.idle"}

    def test_ten_steps_switched_off(self, tracing_off):
        out = _run_engine(requests=self.REQUEST)
        assert [len(o) for o in out] == [10]
        assert _switched(tracing.spans()) == []
