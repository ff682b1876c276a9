"""The arithmetic from the engine's spans to per-layer numbers, on a
small recording of one traced run on the chip
(``chipbench/testdata/serve_spans.json``: the device plane's module
line, its op line reduced to busy runs, the spans of the same seconds
and the two histograms)."""

import json
import math
import os
import random

import pytest

from chipbench import contract, span_reduce
from ray_tpu.util import metrics

DATA = os.path.join(contract.ROOT, "chipbench", "testdata", "serve_spans.json")
NEW_METRICS = {
    "step_dispatch_ms_p50": "program_span",
    "step_deliver_ms_p50": "program_span",
    "step_serve_plane_ms_p50": "program_span",
    "idle_gap_attributed_share": "program_span",
    "engine_queue_wait_ms_p50": "program_counter",
    "engine_ttft_ms_p50": "program_counter",
}


@pytest.fixture()
def rec():
    with open(DATA) as f:
        return json.load(f)


class TestAlign:
    def test_finds_the_offset(self, rec):
        lo, hi = span_reduce.align(rec["planes"], rec["spans"])
        assert 0 <= hi - lo < span_reduce.MAX_INTERVAL_NS
        off = (lo + hi) // 2
        # every execution then lies inside its step's dispatch..sync
        events = span_reduce.decode_events(rec["planes"])
        steps = span_reduce.steps_of(rec["spans"])
        inside = 0
        for s, e, _done in events:
            inside += any(
                st["parts"]["dispatch"]["start_ns"] <= s + off
                and e + off <= st["parts"]["sync"]["end_ns"] for st in steps
            )
        assert inside >= 0.9 * len(events) and inside >= 5

    def test_tells_a_step_in_flight_from_the_first_whole_one(self, rec):
        """The execution dispatched before the spans turned on has no
        step: the pairing skips it instead of sliding every pair, and
        finds the same offset when the trace began a step later."""
        want = span_reduce.align(rec["planes"], rec["spans"])
        modules = rec["planes"][0]["lines"][0]
        assert modules["name"] == "XLA Modules"
        first = min(e[1] for e in modules["events"]
                    if e[0].startswith("jit_decode_step_rowwise"))
        modules["events"] = [e for e in modules["events"] if e[1] > first]
        assert span_reduce.align(rec["planes"], rec["spans"]) == want

    def test_raises_on_an_empty_interval(self, rec):
        # one step's sync returns 5 ms before its execution ended
        # (steps_of hands out the recording's own span dicts)
        span_reduce.steps_of(rec["spans"])[3]["parts"]["sync"]["end_ns"] -= 5_000_000
        with pytest.raises(span_reduce.SpanError, match="empty"):
            span_reduce.align(rec["planes"], rec["spans"])

    def test_raises_on_a_wide_interval(self, rec):
        for s in rec["spans"]:
            if s["name"] == "llm.step.launch":
                s["start_ns"] -= 3_000_000
            if s["name"] == "llm.step.sync":
                s["end_ns"] += 3_000_000
        with pytest.raises(span_reduce.SpanError, match="wide"):
            span_reduce.align(rec["planes"], rec["spans"])

    def test_raises_when_executions_find_no_step(self, rec):
        steps = span_reduce.steps_of(rec["spans"])
        gone = {st["span"]["span_id"] for st in steps[len(steps) // 2:]}
        spans = [s for s in rec["spans"] if s["span_id"] not in gone]
        with pytest.raises(span_reduce.SpanError, match="found"):
            span_reduce.align(rec["planes"], spans)

    def test_raises_with_nothing_to_align(self, rec):
        with pytest.raises(span_reduce.SpanError, match="nothing to align"):
            span_reduce.align(rec["planes"], [])


class TestGaps:
    def test_owners_partition_the_idle_time(self, rec):
        lo, hi = span_reduce.align(rec["planes"], rec["spans"])
        owners = span_reduce.gap_owner(rec["planes"], rec["spans"], (lo + hi) // 2)
        gaps = span_reduce.idle_gaps(rec["planes"])
        assert [(g["start_ns"], g["ns"]) for g in owners] == [
            (a, b - a) for a, b in gaps]
        assert all(b - a > span_reduce.MIN_GAP_NS for a, b in gaps)
        assert math.isclose(sum(g["ns"] for g in owners),
                            sum(b - a for a, b in gaps))
        for g in owners:
            assert 0 <= g["covered_ns"] <= g["ns"]
            assert (g["owner"] is None) == (g["covered_ns"] == 0)
        named = {g["owner"] for g in owners if g["owner"]}
        assert named and named <= {
            "llm.step", "llm.step.admit", "llm.step.build",
            "llm.step.dispatch", "llm.step.sync", "llm.step.deliver",
            "llm.step.yield", "llm.prefill", "llm.idle"}

    def test_a_gap_no_span_covers_has_no_owner(self, rec):
        lo, hi = span_reduce.align(rec["planes"], rec["spans"])
        owners = span_reduce.gap_owner(rec["planes"], [], (lo + hi) // 2)
        assert owners and all(g["owner"] is None for g in owners)

    def test_leaves_are_the_spans_without_children(self, rec):
        leaves = span_reduce.leaf_spans(rec["spans"])
        ids = {s["span_id"] for s in leaves}
        assert not any(s["parent_id"] in ids for s in leaves)
        assert "llm.request" not in {s["name"] for s in leaves}
        assert "serve.stream_item" not in {s["name"] for s in leaves}


class TestReduceRun:
    def test_every_new_metric_has_a_value(self, rec):
        out = span_reduce.reduce_run(rec["planes"], rec["spans"], rec["metrics"])
        assert set(out) == set(NEW_METRICS)
        assert all(math.isfinite(v) and v > 0 for v in out.values()), out
        assert out["idle_gap_attributed_share"] <= 100.0

    def test_the_hosts_three_parts_fill_most_of_the_gap(self, rec):
        """What the host does itself between two executions (build and
        dispatch, deliver, yield) is most of the gap between them and
        never more; the rest is ``llm.step.sync``'s wake-up, which has
        had no reader since the engine keeps a step in flight (PR 29:
        the span then runs SHORTER than the device's step, and the
        difference PR 24 reported read negative)."""
        out = span_reduce.reduce_run(rec["planes"], rec["spans"], rec["metrics"])
        parts = sum(v for k, v in out.items() if k.startswith("step_"))
        events = span_reduce.decode_events(rec["planes"])
        between = sorted((b[0] - a[1]) / 1e6 for a, b in zip(events, events[1:]))
        gap = between[len(between) // 2]
        assert 0.8 * gap < parts < gap, (parts, gap)

    def test_a_run_without_histograms_is_an_error(self, rec):
        with pytest.raises(span_reduce.SpanError, match="llm_queue_wait_ms"):
            span_reduce.reduce_run(rec["planes"], rec["spans"], [])

    @pytest.mark.parametrize("name", sorted(NEW_METRICS))
    def test_declared_with_a_reader(self, name):
        """My entries are there, with these cells and this reader: each
        metric is in BENCHMARK.json for PR 24's cells (and whichever
        joined since), one entry a judged metric, and its ONE reader
        file reads its own key."""
        bench = contract.load_benchmark()
        entries = [m for m in bench["per_layer"]
                   if m["name"].rpartition(".")[0] == name]
        # PR 58 pruned the gap attribution's ``.chat`` and ``.mixed`` entries
        # (100.0 on every line of the ledger): ``.batch`` holds its reader
        want = {"serve_ilm2_chat"} if name.startswith("engine_") else {
            "serve_ilm2_batch"} if name == "idle_gap_attributed_share" else {
            "serve_ilm2_batch", "serve_ilm2_chat"}
        assert want <= {w for m in entries for w in m["workloads"]}
        assert len({m["moves"] for m in entries}) == len(entries)
        for m in entries:
            assert m["source"] == NEW_METRICS[name]
            path = contract.reader_path(m["name"])
            assert path and os.path.basename(path) == name + ".py"
            with open(path) as f:
                assert f'span_reduce.value(ctx, "{name}")' in f.read()

    def test_the_sync_overhead_and_the_seven_aliases_are_gone(self):
        """``step_sync_overhead_ms_p50`` measured nothing since PR 29, and
        the aliases repeated a reader under a second name because this
        file pinned the first to two cells: neither is declared, has a
        reader, or is a key of the reduction."""
        gone = ("sync_overhead", "engine_step_dispatch_ms_p50", "engine_step_deliver_ms_p50",
                "serve_plane_step_ms_p50", "device_idle_gap_attributed_share",
                "admitter_queue_wait_ms_p50", "engine_first_token_ms_p50")
        bench = contract.load_benchmark()
        readers = os.listdir(os.path.join(contract.ROOT, "chipbench", "layer_metrics"))
        for name in gone:
            assert not [m["name"] for m in bench["per_layer"] if name in m["name"]], name
            assert not [f for f in readers if name in f], name
        with open(DATA) as f:
            rec = json.load(f)
        out = span_reduce.reduce_run(rec["planes"], rec["spans"], rec["metrics"])
        assert not [k for k in out if "sync" in k]


class TestValue:
    def ctx(self, rec, platform):
        return {"planes": rec["planes"], "device": {"platform": platform}}

    def test_reduces_once_for_all_readers(self, rec, monkeypatch):
        calls = []

        def fetch(ctx):
            calls.append(1)
            return {"spans": rec["spans"], "metrics": rec["metrics"]}

        monkeypatch.setattr(span_reduce, "fetch", fetch)
        ctx = self.ctx(rec, "tpu")
        got = {k: span_reduce.value(ctx, k) for k in NEW_METRICS}
        assert len(calls) == 1 and all(v is not None for v in got.values())

    def test_a_program_without_spans_gets_zero(self, rec, monkeypatch):
        """The parent commit under this PR's benchmark files: the
        harness cannot leave a declared metric out, so 0 stands in."""
        monkeypatch.setattr(span_reduce, "fetch", lambda ctx: None)
        ctx = self.ctx(rec, "tpu")
        assert [span_reduce.value(ctx, k) for k in NEW_METRICS] == [0.0] * len(NEW_METRICS)

    def test_fetch_sees_a_program_without_a_span_table(self, monkeypatch):
        from ray_tpu.util import state, tracing  # noqa: F401 (state reads it)

        monkeypatch.delattr(tracing, "collect")
        assert span_reduce.fetch({}) is None

    @pytest.mark.parametrize("platform,raises", [("tpu", True), ("cpu", False)])
    def test_spans_that_do_not_fit(self, rec, monkeypatch, platform, raises):
        """On the chip the run fails; a rehearsal returns None, for
        which the harness puts 0."""
        monkeypatch.setattr(span_reduce, "fetch",
                            lambda ctx: {"spans": [], "metrics": []})
        ctx = self.ctx(rec, platform)
        if raises:
            with pytest.raises(span_reduce.SpanError):
                span_reduce.value(ctx, "step_deliver_ms_p50")
        else:
            assert span_reduce.value(ctx, "step_deliver_ms_p50") is None


class TestHistogram:
    def test_interpolates_inside_the_bucket(self):
        series = [(10.0, 0.0), (20.0, 100.0), (math.inf, 100.0)]
        assert span_reduce.histogram_percentile(series, 50) == pytest.approx(
            10.0 * 2 ** 0.5)
        assert span_reduce.histogram_percentile(series, 100) == pytest.approx(20.0)
        first = [(4.0, 8.0), (8.0, 8.0), (math.inf, 8.0)]
        assert span_reduce.histogram_percentile(first, 50) == pytest.approx(2.0)

    def test_beyond_the_ladder_is_its_last_boundary(self):
        series = [(10.0, 1.0), (20.0, 1.0), (math.inf, 10.0)]
        assert span_reduce.histogram_percentile(series, 50) == 20.0

    def test_an_empty_histogram_is_an_error(self):
        with pytest.raises(ValueError):
            span_reduce.histogram_percentile([], 50)
        with pytest.raises(ValueError):
            span_reduce.histogram_percentile([(1.0, 0.0), (math.inf, 0.0)], 50)

    @pytest.mark.parametrize("median_ms", [5.0, 37.0, 140.0, 900.0, 2000.0])
    def test_median_within_5_percent_on_the_engines_ladder(self, median_ms):
        """The engine's two histograms, read back as the readers do."""
        from ray_tpu.serve import llm

        rng = random.Random(int(median_ms))
        h = metrics.Histogram(f"test_ladder_{int(median_ms)}", "",
                              boundaries=llm._MS_LADDER, tag_keys=("outcome",))
        xs = [median_ms * math.exp(rng.gauss(0.0, 0.5)) for _ in range(2000)]
        for x in xs:
            h.observe(x, {"outcome": "admitted"})
        buckets = span_reduce.histogram_buckets(
            [h.snapshot()], h.name, {"outcome": "admitted"})
        assert buckets[-1] == (math.inf, 2000.0)
        assert span_reduce.histogram_buckets([h.snapshot()], h.name) == []
        true = sorted(xs)[1000]
        got = span_reduce.histogram_percentile(buckets, 50)
        assert abs(got - true) < 0.05 * true, (got, true)
