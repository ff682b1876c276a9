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
