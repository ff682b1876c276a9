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
        from ray_tpu.core.runtime import get_runtime
        from ray_tpu.serve import llm

        @ray_tpu.remote
        def untraced():
            return 1

        ray_tpu.get(untraced.remote(), timeout=60)
        assert tracing.spans() == [] and tracing.drain() == []
        assert tracing.current() is None  # no context-variable write
        # a span site that is off allocates nothing in the recorder
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
        grown = [
            d for d in after.compare_to(before, "filename")
            if d.size_diff > 0 and d.traceback[0].filename in (
                tracing.__file__, llm.__file__)
        ]
        assert not grown, grown
        # and nothing is sent: a push now carries no spans
        rt = get_runtime()
        rt._run(rt.push_telemetry())
        time.sleep(2 * PUSH_INTERVAL_S)
        assert not [p for _t, p in pushes if p.get("spans")]

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
        assert [s["name"] for s in tracing.spans()][0] == "probe.in_session"
        data = ProfileData.from_file(trace_reduce.find_xplane(str(tmp_path)))
        found = [
            (plane.name, dict(e.stats))
            for plane in data.planes for line in plane.lines
            for e in line.events if e.name == "probe.in_session"
        ]
        assert found, [p.name for p in data.planes]
        plane, stats = found[0]
        assert plane.startswith("/host:")
        assert stats.get("span_id") == sp.span_id

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
        assert tracing.spans() == [] and tracing.drain() == []
        assert tracing.open_span() is None
        # the counters are always on
        assert _histogram_count("llm_engine_ttft_ms") == ttft0 + 3

    def test_a_stalled_loop_says_so_once(self, traced_cluster, caplog):
        """The io loop held for over a second: one log line with the CPU
        time used meanwhile and the open span, and the counter moves."""
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
        lost = [m for m in metrics.registry_snapshot()
                if m["name"] == "loop_stall_seconds_total"]
        assert lost and lost[0]["series"]['[["role", "driver"]]'] >= 1.0
