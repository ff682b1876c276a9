"""The one general load generator, and the arithmetic on what came back.

A traffic mix is a data file (``chipbench/traffic/<name>.json``); this
module turns it and ``--seed`` into a schedule, and turns the recorded
token times into latencies and rates.  Pure Python + numpy, no jax, no
cluster: the serving job (``jobs/serve_llm.py``) only sends what this
says and stamps what arrives.

Every seed gets the *same* multiset of prompt lengths, answer lengths
and inter-arrival gaps, drawn once from the mix's ``population_seed``;
``--seed`` only permutes their order and picks the token ids.  So two
seeds offer the same work, and what differs between runs is the system
(a seed that changed the amount of work would read as noise in every
metric).

The arrival and percentile arithmetic follows ``ray_tpu/soak/load.py``
(PR 18: seeded exponential gaps, latency from when a request was due)
but is the benchmark's own, so that no later edit of the program can
move the yardstick.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import List, Optional, Sequence

import numpy as np


def percentile(values: Sequence[float], p: float) -> float:
    """The p-th percentile (0..100) by linear interpolation between
    order statistics (numpy's default): rank = p/100 * (n-1)."""
    if not values:
        raise ValueError("percentile of nothing")
    xs = sorted(values)
    rank = p / 100.0 * (len(xs) - 1)
    lo = math.floor(rank)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (rank - lo)


#: ``itl_tail_mean_ms`` is the mean of the window's token gaps between
#: these two percentiles.  The range holds both edges of the mixed mix's
#: gaps (4.9-5.4% of them lie behind a prefill, 1.1-1.5% behind a long
#: one: twelve chip runs of PR 52) and leaves out the hundredth that a
#: host standing still reaches.  Its lower end is the 80th and not the
#: 90th percentile because the ORDER of a seed's arrivals moves the mean
#: of the 90th-99th by 2.4% (one sigma): sets of three seeds each then
#: lay 3.8% apart, the mean of the 80th-99th 2.0% (PERF.md section 2)
TAIL_MEAN_RANGE = (80, 99)
#: a gap over this many times the median gap has waited for more than a
#: decode step (``itl_stalled_gap_share``): a step plus the shortest
#: prefill reads 2.1 x (26 against 12.5 ms)
STALLED_GAP_FACTOR = 1.5
#: under this many values a mean between two quantiles is refused: the
#: top hundredth it leaves out must hold ten samples, or it is no tail
MIN_INTERQUANTILE = 1000


def interquantile_mean(values: Sequence[float], lo: float, hi: float) -> float:
    """The mean of the values at or above the ``lo``-th percentile and
    below the ``hi``-th: of the n values sorted ascending, those at
    ranks ceil(lo/100 n) .. ceil(hi/100 n) - 1, counted from 0.

    What a cell is judged on where its values fall in separate modes (a
    token gap that a prefill cut, and one that none did): a percentile
    reads one mode or the other by which side of it the modes' shares
    fall, so it jumps when a share crosses it, and a share that an
    improving program moves walks toward the jump.  A mean over a range
    that holds the shares' edges moves by one value's weight when one
    value changes mode."""
    n = len(values)
    if n < MIN_INTERQUANTILE:
        raise ValueError(
            f"a mean between quantiles of {n} values, under {MIN_INTERQUANTILE}")
    if not 0 <= lo < hi <= 100:
        raise ValueError(f"want 0 <= lo < hi <= 100, got {lo}, {hi}")
    xs = sorted(values)[math.ceil(n * lo / 100.0):math.ceil(n * hi / 100.0)]
    return sum(xs) / len(xs)


def share_over_median(values: Sequence[float], factor: float) -> float:
    """Per cent of ``values`` over ``factor`` times their median: of a
    window's token gaps, the share that something other than a plain
    decode step lengthened."""
    limit = factor * percentile(values, 50)
    return 100.0 * sum(1 for v in values if v > limit) / len(values)


@dataclass(frozen=True)
class Request:
    """One request of the schedule.  ``due_s`` is its offset from the
    start of the measured window (negative: ramp-up, not measured);
    closed-loop requests have none (``None``) and belong to a client."""

    index: int
    due_s: Optional[float]
    client: Optional[int]
    prompt_len: int
    new_tokens: int
    token_seed: int


def _snap(x: float, buckets: Sequence[int]) -> int:
    return min(buckets, key=lambda b: abs(math.log(b) - math.log(x)))


def _draw_lengths(spec: dict, n: int, rng: np.random.Generator) -> List[int]:
    """``n`` lengths from one distribution of a traffic file."""
    kind = spec["kind"]
    if kind == "fixed":
        return [int(spec["value"])] * n
    if kind == "cycle":  # values in turn: exact shares, no sampling noise
        vals = [int(v) for v in spec["values"]]
        return [vals[i % len(vals)] for i in range(n)]
    if kind == "uniform_int":
        return [int(v) for v in rng.integers(spec["low"], spec["high"] + 1, n)]
    if kind == "lognormal_snapped":
        raw = np.exp(rng.normal(math.log(spec["median"]), spec["sigma"], n))
        return [_snap(float(x), spec["buckets"]) for x in raw]
    raise ValueError(f"unknown length distribution {kind!r}")


def prompt_lengths(traffic: dict) -> List[int]:
    """Every prompt length the mix can produce: what set-up must warm."""
    spec = traffic["prompt_len"]
    if spec["kind"] == "fixed":
        return [int(spec["value"])]
    if spec["kind"] == "cycle":
        return sorted({int(v) for v in spec["values"]})
    if spec["kind"] == "lognormal_snapped":
        return sorted(int(b) for b in spec["buckets"])
    raise ValueError(
        f"prompt lengths of kind {spec['kind']!r} are not a finite set; the "
        "engine compiles once per length"
    )


def schedule(traffic: dict, seed: int, seconds: float, max_len: int) -> List[Request]:
    """The requests of one run.

    Open loop: ``rate_rps * ramp_s`` requests before the window and
    ``rate_rps * seconds`` inside it, whose gaps are exponential draws
    of the population seed, permuted by ``seed`` and scaled to span
    exactly the ramp and exactly the window.  Closed loop:
    ``requests_per_client`` requests for each of ``clients`` clients
    (more than a window can finish); a client sends its next when the
    last has ended.  With ``stagger`` the first request of client i asks
    for ``step * (i % over + 1)`` tokens, so that slots free one by one
    from then on and not all in the same decode step.
    """
    pop = np.random.default_rng(int(traffic["population_seed"]))
    order = np.random.default_rng([int(seed) % (2**63), 7])
    loop = traffic["loop"]
    if loop == "open":
        reqs: List[Request] = []
        # ramp-up and window are two populations of their own, so that
        # every seed has exactly the same requests inside the window
        for lo, span in ((-float(traffic["ramp_s"]), float(traffic["ramp_s"])),
                         (0.0, float(seconds))):
            n = round(traffic["rate_rps"] * span)
            if n < 1:
                continue
            gaps = pop.exponential(1.0, n)[order.permutation(n)]
            p_len = _draw_lengths(traffic["prompt_len"], n, pop)
            n_new = _draw_lengths(traffic["new_tokens"], n, pop)
            perm = order.permutation(n)
            due = np.cumsum(gaps)
            due = lo + due / due[-1] * span * (1.0 - 0.5 / n)
            for i in range(n):
                pl = p_len[perm[i]]
                reqs.append(Request(
                    len(reqs), float(due[i]), None, pl,
                    min(n_new[perm[i]], max_len - pl),
                    int(order.integers(0, 2**31)),
                ))
        return reqs
    if loop == "closed":
        clients = int(traffic["clients"])
        per = int(traffic["requests_per_client"])
        n = clients * per
        p_len = _draw_lengths(traffic["prompt_len"], n, pop)
        n_new = _draw_lengths(traffic["new_tokens"], n, pop)
        perm = order.permutation(n)
        stagger = traffic.get("stagger")
        reqs = []
        for i in range(n):
            c, k = i % clients, i // clients
            pl, nn = p_len[perm[i]], n_new[perm[i]]
            if k == 0 and stagger:
                nn = stagger["step"] * (c % stagger["over"] + 1)
            nn = min(nn, max_len - pl)
            reqs.append(Request(i, None, c, pl, nn,
                                int(order.integers(0, 2**31))))
        return reqs
    raise ValueError(f"unknown loop kind {loop!r}")


def prompt_tokens(req: Request, vocab_size: int) -> List[int]:
    return np.random.default_rng(req.token_seed).integers(
        0, vocab_size, req.prompt_len
    ).tolist()


@dataclass
class Outcome:
    """What the client saw of one request, on the parent's monotonic
    clock, as offsets from the start of the measured window."""

    request: Request
    sent_s: float
    token_s: List[float]
    tokens: List[int]
    error: Optional[str] = None
    finished: bool = False  # the stream ended by itself, not cut by the client


def request_failed(o: Outcome, vocab_size: int, cut_ok: bool = False) -> Optional[str]:
    """Why this request counts as failed, or None.  A stream that had
    not ended when the client stopped reading is a failure ("not
    finished in time") unless ``cut_ok`` (a closed loop cut where its
    window ends): then what had arrived is held to the same rules as
    far as it got."""
    if o.error:
        return o.error
    if not o.finished and not cut_ok:
        return "not finished when the drain time ended"
    if not o.finished:
        if len(o.tokens) > o.request.new_tokens:
            return f"{len(o.tokens)} tokens for {o.request.new_tokens} asked"
    elif len(o.tokens) != o.request.new_tokens:
        return (f"{len(o.tokens)} tokens for {o.request.new_tokens} asked "
                f"(prompt {o.request.prompt_len})")
    if not all(isinstance(t, int) and 0 <= t < vocab_size for t in o.tokens):
        return "a token outside the vocabulary"
    return None


#: a request that starts this long before the replica froze still has
#: its first token inside the freeze (admission and prefill take 0.06-
#: 0.3 s at the mixes' loads: PERF.md section 6)
FROZEN_LEAD_S = 1.0


def summarize(outcomes: Sequence[Outcome], seconds: float,
              open_loop: bool, frozen: Optional[tuple] = None) -> dict:
    """Latencies and rates of one window.

    A request is *measured* if it was due (open loop) or sent (closed
    loop) inside [0, seconds).  Of a closed loop's requests only those
    still waiting for their first token when the client stopped reading
    are left out: they were queue filler, withdrawn unserved where the
    window closed.  One that errored, was shed or whose stream ended
    empty is measured, and ``request_failed`` counts it.  Time to first
    token runs from when the request was due — so a stalled generator
    or a stalled server both count — to its first token.  Inter-token
    gaps are those between consecutive tokens of one stream, over every
    measured request.  ``tokens_per_s`` counts every token that arrived
    inside the window, of any request, over the window's length.

    ``frozen`` = (t2, t3), window offsets between which a traced run's
    ``jax.profiler.stop_trace()`` held the replica: the time to first
    token of a measured request that started in [t2 - FROZEN_LEAD_S, t3]
    is the profiler's, not the program's, and is left out of
    ``ttft_ms`` (``ttft_left_out`` says how many were).  Nothing else
    changes, and an untraced run has no ``frozen``.
    """
    def start(o):
        return o.request.due_s if open_loop else o.sent_s

    measured = [
        o for o in outcomes
        if 0.0 <= start(o) < seconds
        and (open_loop or o.token_s or o.error or o.finished)
    ]
    # the profiler's seconds: an empty interval where nothing froze
    lo, hi = (frozen[0] - FROZEN_LEAD_S, frozen[1]) if frozen else (0.0, -1.0)
    first = [o for o in measured if o.token_s]
    ttft = [
        (o.token_s[0] - start(o)) * 1e3 for o in first
        if not lo <= start(o) <= hi
    ]
    itl = [
        (b - a) * 1e3
        for o in measured
        for a, b in zip(o.token_s, o.token_s[1:])
    ]
    lag = [(o.sent_s - o.request.due_s) * 1e3 for o in measured] if open_loop else []
    in_window = sum(
        1 for o in outcomes for t in o.token_s if 0.0 <= t < seconds
    )
    return {
        "measured": measured,
        "ttft_ms": ttft,
        "ttft_left_out": len(first) - len(ttft),
        "itl_ms": itl,
        "lag_ms": lag,
        "tokens_in_window": in_window,
        "tokens_per_s": in_window / seconds,
    }


#: a mix's ``ttft_p50_limit_ms`` is held against a median of at least
#: this many requests: fewer (a traced run whose freeze left them out)
#: say nothing about the program
MIN_TTFT_GUARDED = 8


def ttft_guard(ttft_ms: Sequence[float], limit_ms: Optional[float]) -> Optional[str]:
    """Why a run breaks its mix's ``ttft_p50_limit_ms``, or None (the
    mix states no limit, or half of ``ttft_ms`` lies within it)."""
    if limit_ms is None:
        return None
    if len(ttft_ms) < MIN_TTFT_GUARDED:
        return (f"only {len(ttft_ms)} request(s) are left to hold to the mix's "
                f"limit on the time to first token, under {MIN_TTFT_GUARDED}")
    p50 = percentile(ttft_ms, 50)
    if p50 > limit_ms:
        return (f"median time to first token {p50:.0f} ms is over the mix's "
                f"limit of {limit_ms:g} ms")
    return None
