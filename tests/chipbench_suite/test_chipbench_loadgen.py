"""Load-generator and percentile arithmetic against hand-worked cases."""

import json
import os

import pytest

from chipbench import contract, loadgen


def traffic(name):
    with open(os.path.join(contract.ROOT, "chipbench", "traffic", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("values,p,want", [
    ([10.0], 95, 10.0),
    ([1.0, 2.0, 3.0, 4.0, 5.0], 50, 3.0),
    ([1.0, 2.0, 3.0, 4.0], 50, 2.5),
    ([4.0, 1.0, 3.0, 2.0], 25, 1.75),          # rank 0.75 between 1 and 2
    (list(map(float, range(1, 102))), 95, 96.0),  # rank 95 of 0..100
    ([0.0, 10.0], 95, 9.5),
])
def test_percentile_by_hand(values, p, want):
    assert loadgen.percentile(values, p) == pytest.approx(want)


def test_every_seed_offers_the_same_work_in_another_order():
    mix = traffic("chat_poisson")
    a = loadgen.schedule(mix, 1, 30.0, 1024)
    b = loadgen.schedule(mix, 3_000_000_019, 30.0, 1024)
    assert len(a) == len(b) == round(mix["rate_rps"] * 30.0) + round(mix["rate_rps"] * mix["ramp_s"])
    in_window = lambda rs: sorted((r.prompt_len, r.new_tokens) for r in rs if r.due_s >= 0)  # noqa: E731
    assert in_window(a) == in_window(b) and len(in_window(a)) == round(mix["rate_rps"] * 30.0)
    sizes = lambda rs: sorted((r.prompt_len, r.new_tokens) for r in rs)  # noqa: E731
    assert sizes(a) == sizes(b)
    assert [r.prompt_len for r in a] != [r.prompt_len for r in b]
    assert {r.prompt_len for r in a} <= set(loadgen.prompt_lengths(mix))
    assert all(r.prompt_len + r.new_tokens <= 1024 for r in a)
    assert all(64 <= r.new_tokens <= 256 for r in a)
    due = [r.due_s for r in a]
    assert due == sorted(due) and due[0] > -mix["ramp_s"] and due[-1] < 30.0
    assert a == loadgen.schedule(mix, 1, 30.0, 1024)


def test_closed_loop_clients_get_their_own_requests_and_a_staggered_first():
    mix = traffic("batch_closed64")
    reqs = loadgen.schedule(mix, 5, 30.0, 1024)
    assert len(reqs) == mix["clients"] * mix["requests_per_client"]
    first = [r for r in reqs if r.index < mix["clients"]]
    assert [r.new_tokens for r in first[:3]] == [8, 16, 24]
    assert {r.new_tokens for r in reqs if r.index >= mix["clients"]} == {256}
    lens = [r.prompt_len for r in reqs]
    assert lens.count(128) == lens.count(256)
    assert loadgen.prompt_lengths(mix) == [128, 256]


def test_summarize_by_hand():
    mk = lambda i, due: loadgen.Request(i, due, None, 128, 3, i)  # noqa: E731
    outcomes = [
        # due 1.0, sent 1.2 late; tokens at 1.5, 1.6, 1.8
        loadgen.Outcome(mk(0, 1.0), 1.2, [1.5, 1.6, 1.8], [1, 2, 3]),
        # ramp-up request (due before the window): its tokens inside the
        # window count for the rate, its latencies for nothing
        loadgen.Outcome(mk(1, -0.5), -0.5, [-0.1, 0.2, 0.4], [1, 2, 3]),
        # due inside, last token after the window closed at 2.0
        loadgen.Outcome(mk(2, 1.9), 1.9, [1.95, 2.05, 2.15], [1, 2, 3]),
    ]
    s = loadgen.summarize(outcomes, 2.0, open_loop=True)
    assert len(s["measured"]) == 2
    assert s["ttft_ms"] == pytest.approx([500.0, 50.0])
    assert sorted(s["itl_ms"]) == pytest.approx([100.0, 100.0, 100.0, 200.0])
    assert s["lag_ms"] == pytest.approx([200.0, 0.0])
    assert s["tokens_in_window"] == 3 + 2 + 1
    assert s["tokens_per_s"] == pytest.approx(3.0)


def test_a_short_or_out_of_vocabulary_answer_fails():
    req = loadgen.Request(0, 0.0, None, 128, 3, 0)
    ok = loadgen.Outcome(req, 0.0, [0.1, 0.2, 0.3], [1, 2, 3], finished=True)
    assert loadgen.request_failed(ok, 10) is None
    assert loadgen.request_failed(
        loadgen.Outcome(req, 0.0, [0.1], [1], finished=True), 10)
    assert loadgen.request_failed(
        loadgen.Outcome(req, 0.0, [0.1, 0.2, 0.3], [1, 2, 30], finished=True), 10)
    assert loadgen.request_failed(
        loadgen.Outcome(req, 0.0, [], [], error="RequestShedError: shed"), 10)
    # a stream cut by the client: late in an open loop, fine where a
    # closed loop's window ends, as long as what arrived was right
    cut = loadgen.Outcome(req, 0.0, [0.1, 0.2], [1, 2])
    assert loadgen.request_failed(cut, 10)
    assert loadgen.request_failed(cut, 10, cut_ok=True) is None
    cut.tokens[1] = 99
    assert loadgen.request_failed(cut, 10, cut_ok=True)


@pytest.mark.parametrize("second,measured,failed", [
    # still waiting for its first token when the client stopped reading:
    # queue filler, withdrawn unserved
    (dict(token_s=[], tokens=[]), [0], 0),
    # shed or errored before any token: attempted, and failed
    (dict(token_s=[], tokens=[], error="RequestShedError: shed"), [0, 1], 1),
    # a stream that ended without a token: attempted, and failed
    (dict(token_s=[], tokens=[], finished=True), [0, 1], 1),
    # cut where the window ends with part of its answer: as far as it got
    (dict(token_s=[0.95], tokens=[1]), [0, 1], 0),
    # errored after its first token
    (dict(token_s=[0.95], tokens=[1], error="RuntimeError: lost"), [0, 1], 1),
    # sent after the window closed: nobody's
    (dict(sent_s=1.2, token_s=[], tokens=[], error="RuntimeError: lost"), [0], 0),
])
def test_closed_loop_only_unserved_queue_filler_is_left_out(second, measured, failed):
    mk = lambda i: loadgen.Request(i, None, i, 128, 3, i)  # noqa: E731
    outcomes = [
        loadgen.Outcome(mk(0), 0.5, [0.6, 0.7, 0.8], [1, 2, 3], finished=True),
        loadgen.Outcome(mk(1), **{"sent_s": 0.9, **second}),
    ]
    s = loadgen.summarize(outcomes, 1.0, open_loop=False)
    assert [o.request.index for o in s["measured"]] == measured
    assert s["ttft_ms"][0] == pytest.approx(100.0)
    fails = [f for f in (loadgen.request_failed(o, 10, cut_ok=True)
                         for o in s["measured"]) if f]
    assert len(fails) == failed


def _replayed(mix, seed, seconds, froze_at, froze_s, service_s=0.06, gap_s=0.03):
    """The mix's own schedule against a replica that answers a request
    ``service_s`` after it is due and a token every ``gap_s``, except
    that nothing leaves it from ``froze_at`` for ``froze_s`` seconds."""
    thaw = lambda t: t if t < froze_at else max(t, froze_at + froze_s)  # noqa: E731
    out = []
    for r in loadgen.schedule(mix, seed, seconds, 1024):
        first = thaw(r.due_s + service_s)
        stamps = [thaw(first + k * gap_s) for k in range(r.new_tokens)]
        out.append(loadgen.Outcome(r, r.due_s, stamps, [1] * r.new_tokens, finished=True))
    return out


@pytest.mark.parametrize("seed", [1, 2_147_483_833, 3_000_000_019])
def test_a_traced_chat_run_is_held_to_the_requests_the_profiler_did_not_freeze(seed):
    """PR 44: ``stop_trace`` sent at 9 s of the window returns 25 s
    later.  Counted, the frozen requests make the run "incorrect";
    left out, the guard reads the program."""
    mix = traffic("chat_poisson")
    outcomes = _replayed(mix, seed, 40.0, froze_at=9.0, froze_s=25.0)
    plain = loadgen.summarize(outcomes, 40.0, open_loop=True)
    kept = loadgen.summarize(outcomes, 40.0, open_loop=True, frozen=(9.0, 34.0))
    assert loadgen.percentile(plain["ttft_ms"], 50) > mix["ttft_p50_limit_ms"]
    assert "over the mix's limit of 1000 ms" in loadgen.ttft_guard(
        plain["ttft_ms"], mix["ttft_p50_limit_ms"])
    assert loadgen.ttft_guard(kept["ttft_ms"], mix["ttft_p50_limit_ms"]) is None
    assert loadgen.percentile(kept["ttft_ms"], 95) == pytest.approx(60.0)
    in_freeze = [o for o in kept["measured"]
                 if 9.0 - loadgen.FROZEN_LEAD_S <= o.request.due_s <= 34.0]
    assert kept["ttft_left_out"] == len(in_freeze) > 30
    assert plain["ttft_left_out"] == 0
    assert len(kept["ttft_ms"]) == len(plain["ttft_ms"]) - len(in_freeze) >= 12
    for same in ("measured", "itl_ms", "lag_ms", "tokens_in_window", "tokens_per_s"):
        assert kept[same] == plain[same], same


def test_the_guard_wants_eight_requests_and_no_mix_without_a_limit():
    fast = [50.0] * 7
    assert loadgen.ttft_guard(fast, None) is None
    assert loadgen.ttft_guard([5e4] * 3, None) is None
    assert "only 7 request(s) are left" in loadgen.ttft_guard(fast, 1000)
    assert loadgen.ttft_guard(fast + [50.0], 1000) is None
    assert loadgen.ttft_guard(fast + [5e4], 1000) is None       # a median, not a tail
    assert "median time to first token 50000 ms" in loadgen.ttft_guard([5e4] * 8, 1000)
    # a freeze that leaves fewer than eight of a window's requests
    mk = lambda i: loadgen.Request(i, float(i), None, 128, 2, i)  # noqa: E731
    outcomes = [loadgen.Outcome(mk(i), float(i), [i + 0.05, i + 0.1], [1, 2], finished=True)
                for i in range(12)]
    kept = loadgen.summarize(outcomes, 12.0, open_loop=True, frozen=(5.5, 20.0))
    assert kept["ttft_left_out"] == 7 and len(kept["ttft_ms"]) == 5   # due 0..4 are left
    assert "only 5 request(s)" in loadgen.ttft_guard(kept["ttft_ms"], 1000)


def test_a_closed_loop_is_frozen_by_when_a_request_was_sent():
    mk = lambda i: loadgen.Request(i, None, i, 128, 2, i)  # noqa: E731
    sent = [0.5, 1.9, 2.0, 3.0, 4.0, 4.1]
    outcomes = [loadgen.Outcome(mk(i), s, [s + 0.1, s + 0.2], [1, 2], finished=True)
                for i, s in enumerate(sent)]
    plain = loadgen.summarize(outcomes, 5.0, open_loop=False)
    kept = loadgen.summarize(outcomes, 5.0, open_loop=False, frozen=(3.0, 4.0))
    assert len(plain["ttft_ms"]) == 6 and plain["ttft_left_out"] == 0
    assert kept["ttft_left_out"] == 3                      # sent at 2.0, 3.0 and 4.0
    assert kept["ttft_ms"] == pytest.approx([100.0] * 3)
    assert kept["measured"] == plain["measured"] and kept["itl_ms"] == plain["itl_ms"]


# ---- the mean between two quantiles (PR 52) ----------------------------------

@pytest.mark.parametrize("values,lo,hi,want", [
    (list(map(float, range(1000))), 90, 99, 944.5),       # ranks 900..989
    (list(map(float, range(1001))), 90, 99, 945.5),       # ceil(900.9)=901 .. ceil(990.99)-1=990
    (list(map(float, reversed(range(1000)))), 90, 99, 944.5),  # sorted first
    (list(map(float, range(1000))), 0, 100, 499.5),       # everything
    (list(map(float, range(2000))), 99.5, 100, 1994.5),   # ranks 1990..1999
    ([1.0] * 950 + [3.0] * 40 + [100.0] * 10, 90, 99, (50 * 1.0 + 40 * 3.0) / 90),
])
def test_interquantile_mean_by_hand(values, lo, hi, want):
    assert loadgen.interquantile_mean(values, lo, hi) == pytest.approx(want)


@pytest.mark.parametrize("values,lo,hi,match", [
    ([1.0] * 999, 90, 99, "under 1000"),
    ([], 90, 99, "under 1000"),
    ([1.0] * 1000, 99, 90, "lo < hi"),
    ([1.0] * 1000, 90, 101, "lo < hi"),
])
def test_interquantile_mean_refuses_a_short_window_and_a_range_that_is_none(values, lo, hi, match):
    with pytest.raises(ValueError, match=match):
        loadgen.interquantile_mean(values, lo, hi)


def test_the_top_hundredth_does_not_reach_the_tail_mean():
    """A host that stands still for 2 s lengthens one gap of each live
    row: 8 of 27,598.  A percentile does not see them, the mean of all
    gaps does, the mean under the 99th percentile does not."""
    gaps = [12.5] * 27_598
    stopped = gaps[:-8] + [2000.0] * 8
    assert loadgen.interquantile_mean(stopped, *loadgen.TAIL_MEAN_RANGE) == 12.5
    assert sum(stopped) / len(stopped) > 1.04 * 12.5
    assert loadgen.TAIL_MEAN_RANGE == (80, 99)


def test_share_over_median_by_hand():
    gaps = [10.0] * 90 + [14.9] * 4 + [15.1] * 4 + [40.0] * 2
    assert loadgen.share_over_median(gaps, 1.5) == pytest.approx(6.0)   # over 15.0
    assert loadgen.share_over_median(gaps, 3.0) == pytest.approx(2.0)
    assert loadgen.share_over_median([5.0] * 10, 1.5) == 0.0
    assert loadgen.STALLED_GAP_FACTOR == 1.5


def three_mode_window(cut_share, n=27_598):
    """The mixed cell's window as PERF.md section 7 (f) counted it: ``n``
    token gaps, ``cut_share`` of them behind a prefill — a fifth of those
    behind a 768-token one (about 50 ms), the rest behind a short one
    (about 28 ms) — and the others a plain decode step (about 12.5 ms);
    a little seeded jitter on each."""
    import random

    rng = random.Random(52)
    cut = round(cut_share * n)
    long = round(cut_share / 5 * n)
    modes = [50.0] * long + [28.0] * (cut - long) + [12.5] * (n - cut)
    return [m * (1.0 + rng.uniform(-0.03, 0.03)) for m in modes]


@pytest.fixture(scope="module")
def sweep():
    """The cut share stepped from 4.6% to 5.6% in steps of 0.1%: what an
    improving decode step does to this cell (the share is admissions a
    second x the step's time)."""
    shares = [round(0.046 + 0.001 * i, 4) for i in range(11)]
    windows = [three_mode_window(c) for c in shares]
    return {
        "p90": [loadgen.percentile(w, 90) for w in windows],
        "p95": [loadgen.percentile(w, 95) for w in windows],
        "p99": [loadgen.percentile(w, 99) for w in windows],
        "tail": [loadgen.interquantile_mean(w, *loadgen.TAIL_MEAN_RANGE) for w in windows],
        "tail_90_99": [loadgen.interquantile_mean(w, 90, 99) for w in windows],
        "stalled": [loadgen.share_over_median(w, 1.5) for w in windows],
    }


def steps(xs):
    return [abs(b - a) / a for a, b in zip(xs, xs[1:])]


def test_a_percentile_of_two_modes_is_a_cliff(sweep):
    """Why the cell is not judged on its p95, p90 or p99.  The p95 jumps
    by more than half between two neighbouring steps of 0.1% (the cut
    share crosses 5%); the p99 does where the long share crosses 1%; the
    p90 never leaves the plain mode, so it is blind to what the cell
    exists for."""
    for p in ("p95", "p99"):
        assert max(steps(sweep[p])) > 0.5
        # one cliff (the step onto the edge and the step off it), flat beside it
        assert max(sorted(steps(sweep[p]))[:-2]) < 0.01
    assert max(sweep["p90"]) < 1.04 * 12.5


def test_the_tail_mean_has_no_cliff(sweep):
    """The same sweep moves the mean of the 80th-99th percentile by one
    gap's weight a gap: under 1.5% a step of 0.1%, every step alike, and
    under 6% over the whole range, rising all the way.  The mean of the
    90th-99th (ISSUE 52's first choice) has no cliff either, but half the
    gaps under it, so every gap that changes mode weighs double: a tenth
    over the range — and the order of a seed's arrivals moved it by as
    much more on the chip (PERF.md section 2)."""
    for name, a_step, in_all in (("tail", 0.015, 0.06), ("tail_90_99", 0.015, 0.12)):
        tail = sweep[name]
        assert max(steps(tail)) < a_step, name
        assert all(b > a for a, b in zip(tail, tail[1:])), name
        assert max(steps(tail)) < 2.5 * min(steps(tail)), name
        assert 0.03 < tail[-1] / tail[0] - 1.0 < in_all, name
    assert max(steps(sweep["tail"])) < 0.6 * max(steps(sweep["tail_90_99"]))
    # about what the cell reads on the chip (17.0-17.6 ms): here 15.7-16.6,
    # the plain mode's own tail left out of the synthetic window
    assert 15.0 < sweep["tail"][0] < sweep["tail"][-1] < 18.0


def test_the_stalled_share_reads_the_cut_share_itself(sweep):
    for share, want in zip(sweep["stalled"], [4.6 + 0.1 * i for i in range(11)]):
        assert share == pytest.approx(want, abs=0.01)


def test_the_mixed_cell_is_judged_on_the_tail_mean_and_the_chat_cell_on_its_p95():
    bench = contract.load_benchmark()
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert e2e["itl_tail_mean_ms"] == {
        "name": "itl_tail_mean_ms", "unit": "ms", "better": "lower", "bound": 0.06,
        "source": "host_clock", "workloads": ["serve_ilm2_mixed"]}
    assert e2e["itl_p95_ms"]["workloads"] == ["serve_ilm2_chat"]
    # every end-to-end bound, in this ONE test: the next change of a bound
    # edits one file (PR 58 moved ``serve_tokens_per_s``'s here from the JoyAI
    # and SDAR tests).  ``itl_p95_ms``: 0.06 until PR 58; the driver's note on
    # PR 52's line asked for at most eight times its widest spread (0.42%:
    # 3.36%), and 0.03 is 3.1 times PR 58's own widest (PERF.md section 2)
    assert {"train_tokens_per_s_per_chip": 0.01, "itl_p95_ms": 0.03,
            "itl_tail_mean_ms": 0.06, "serve_tokens_per_s": 0.09, "setup_s": 0.1}.items() <= {
        m["name"]: m["bound"] for m in bench["end_to_end"]}.items()
    assert set(contract.declared_metrics(bench, "serve_ilm2_mixed", 0)) == {
        "itl_tail_mean_ms", "setup_s"}
    records = contract.declared_metrics(bench, "serve_ilm2_mixed", 1)
    assert records["itl_p95_ms.mixed"] == "ms" and records["itl_stalled_gap_share"] == "%"
    for name in ("itl_p95_ms.mixed", "itl_stalled_gap_share"):
        m = next(m for m in bench["per_layer"] if m["name"] == name)
        assert m["workloads"] == ["serve_ilm2_mixed"] and m["moves"] == "itl_tail_mean_ms"
        assert m["source"] == "host_clock"
    mix = traffic("mixed_poisson")
    assert "itl_tail_mean_ms" in mix["what"] and "5.1-5.2%" in mix["what"]
    assert (mix["rate_rps"], mix["population_seed"]) == (4.2, 20260927)     # PR 25's
    assert mix["prompt_len"] == {"kind": "cycle", "values": [128, 256, 128, 256, 768]}
