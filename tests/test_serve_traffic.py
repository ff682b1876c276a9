"""Serve traffic plane: admission control, SLO-ordered dispatch,
depth-1 neutrality, and the @serve.batch queue hardening.

The traffic plane (ray_tpu/serve/traffic/) only activates for
deployments carrying a ``traffic_config``, so every test here builds
one explicitly; deployments without one pin the unchanged direct path.

The sustained-load autoscaling roundtrip lives in
test_zz_serve_autoscale.py: ``slow``-marked suites must be named
``test_zz_*`` so they sort past the tier-1 870 s truncation window
(enforced by the conftest collection guard).
"""

import asyncio
import time

import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu.serve.batching import _BatchQueue, batch
from ray_tpu.serve.traffic import RequestShedError, get_request_deadline  # noqa: F401


@pytest.fixture(scope="module")
def cluster():
    ray_tpu.init(num_cpus=4, num_tpus=0)
    serve.start()
    yield
    try:
        serve.shutdown()
    except Exception:
        pass
    ray_tpu.shutdown()


# ---------------------------------------------------------------------------
# Admission control + load shedding
# ---------------------------------------------------------------------------


class TestAdmission:
    def test_overload_sheds_instead_of_queueing(self, cluster):
        """A burst far past the bounded queue sheds synchronously with
        a Retry-After hint; everything ADMITTED completes.  The cap
        makes backpressure visible at the door instead of buffering
        unboundedly in the replica mailbox."""

        @serve.deployment(
            max_ongoing_requests=2,
            traffic_config={"slo_ms": 20000.0, "max_queue_depth": 4,
                            "shed_retry_after_s": 0.5},
        )
        class Slow:
            async def __call__(self):
                await asyncio.sleep(0.15)
                return "ok"

        h = serve.run(Slow.bind(), name="shed", route_prefix=None)
        assert h.remote().result(timeout_s=30) == "ok"  # direct warmup

        async def drive():
            h._router._refresh(force=True)
            admitted, sheds = [], []
            for _ in range(40):  # one tick: queue cap trips at 4
                try:
                    admitted.append(h.remote())
                except RequestShedError as e:
                    sheds.append(e)
            results = await asyncio.gather(
                *(r.result_async() for r in admitted)
            )
            # the scheduler counts a completion in a task of its own,
            # woken beside the caller's: give the last one its turn
            sched = h._router._traffic_scheduler
            deadline = time.monotonic() + 5
            while sched.stats()["inflight"] and time.monotonic() < deadline:
                await asyncio.sleep(0.01)
            return results, sheds, sched.stats()

        results, sheds, stats = asyncio.run(drive())
        # depth cap 4: only a handful admitted, the burst's tail shed
        assert len(sheds) >= 30, f"only {len(sheds)} of 40 shed"
        assert all(v == "ok" for v in results), results
        assert len(results) + len(sheds) == 40
        # the hint is actionable: at least the configured floor
        assert all(e.retry_after_s >= 0.5 for e in sheds)
        # the stats the autoscaler/bench consume count refusals too,
        # not just queue expiries
        assert stats["shed_total"] >= len(sheds), stats
        assert stats["completed_total"] == len(results), stats
        serve.delete("shed")

    def test_http_shed_is_503_with_retry_after(self, cluster):
        """Through the HTTP proxy the shed surfaces as the standard
        overload answer: 503 + whole-seconds Retry-After (RFC 9110),
        while admitted requests still return 200."""

        @serve.deployment(
            max_ongoing_requests=1,
            traffic_config={"slo_ms": 20000.0, "max_queue_depth": 2},
        )
        class Busy:
            async def __call__(self):
                await asyncio.sleep(0.3)
                return "ok"

        serve.run(Busy.bind(), name="http_shed", route_prefix="/busy",
                  http_port=18747)
        import httpx

        # readiness: the proxy learns routes on its poll
        deadline = time.time() + 30
        while time.time() < deadline:
            try:
                if httpx.get("http://127.0.0.1:18747/busy",
                             timeout=10).status_code == 200:
                    break
            except Exception:
                pass
            time.sleep(0.3)

        async def drive():
            async with httpx.AsyncClient(timeout=30) as client:
                rs = await asyncio.gather(*(
                    client.get("http://127.0.0.1:18747/busy")
                    for _ in range(12)
                ))
            return rs

        rs = asyncio.run(drive())
        codes = sorted(r.status_code for r in rs)
        assert 200 in codes and 503 in codes, codes
        shed = [r for r in rs if r.status_code == 503]
        for r in shed:
            assert int(r.headers["Retry-After"]) >= 1
        serve.delete("http_shed")


def test_options_normalizes_traffic_config_dict():
    """.options(traffic_config={...}) must coerce the dict like the
    decorator does — the controller reads drain_timeout_s etc. by
    attribute, and a raw dict would silently fall back to defaults."""
    from ray_tpu.serve.traffic import TrafficConfig

    @serve.deployment
    class D:
        def __call__(self):
            return 1

    d2 = D.options(
        traffic_config={"slo_ms": 200.0, "drain_timeout_s": 5.0}
    )
    assert isinstance(d2.traffic_config, TrafficConfig)
    assert d2.traffic_config.slo_ms == 200.0
    assert d2.traffic_config.drain_timeout_s == 5.0
    # a typo'd key raises at definition time, not silently at serve time
    with pytest.raises(TypeError):
        D.options(traffic_config={"slo_mss": 1.0})


# ---------------------------------------------------------------------------
# SLO-ordered (EDF) dispatch + deadline propagation
# ---------------------------------------------------------------------------


class TestSloOrdering:
    def test_tight_slo_overtakes_loose_at_the_queue(self, cluster):
        """Two requests queued behind a busy replica dispatch EDF: the
        tighter-SLO one submitted LATER overtakes the looser one."""

        @serve.deployment(
            max_ongoing_requests=1,
            traffic_config={"slo_ms": 30000.0, "max_queue_depth": 16},
        )
        class Recorder:
            def __init__(self):
                self.order = []

            async def __call__(self, tag=""):
                self.order.append(tag)
                if tag == "occupier":
                    await asyncio.sleep(0.4)
                return tag

            def get_order(self):
                return list(self.order)

        h = serve.run(Recorder.bind(), name="edf", route_prefix=None)
        h.remote(tag="warm").result(timeout_s=30)

        async def drive():
            h._router._refresh(force=True)
            occ = h.remote(tag="occupier")
            await asyncio.sleep(0.1)  # occupier takes the only slot
            loose = h.options(slo_ms=25000.0).remote(tag="loose")
            tight = h.options(slo_ms=5000.0).remote(tag="tight")
            await asyncio.gather(
                occ.result_async(), loose.result_async(),
                tight.result_async(),
            )
            return await (
                h.options(method_name="get_order").remote().result_async()
            )

        order = asyncio.run(drive())
        assert order.index("tight") < order.index("loose"), order
        serve.delete("edf")

    def test_deadline_visible_in_replica(self, cluster):
        """The scheduler smuggles the remaining budget to the replica,
        which re-anchors it on its own monotonic clock; direct calls
        (and actor reuse after one) see None."""

        @serve.deployment(traffic_config={"slo_ms": 5000.0})
        class DL:
            def __call__(self):
                from ray_tpu.serve.traffic import get_request_deadline

                d = get_request_deadline()
                return None if d is None else d - time.monotonic()

        h = serve.run(DL.bind(), name="dl", route_prefix=None)
        # off-loop direct dispatch: no traffic plane, no deadline
        assert h.remote().result(timeout_s=30) is None

        async def drive():
            h._router._refresh(force=True)
            return await h.remote().result_async()

        remaining = asyncio.run(drive())
        assert remaining is not None and 0.0 < remaining <= 5.0, remaining
        # a prior deadline must not leak into a later direct request
        assert h.remote().result(timeout_s=30) is None
        serve.delete("dl")

    def test_expired_request_is_shed_not_dispatched(self, cluster):
        """A request whose SLO lapses while queued fails with
        RequestShedError instead of burning replica compute."""

        @serve.deployment(
            max_ongoing_requests=1,
            traffic_config={"slo_ms": 30000.0, "max_queue_depth": 16},
        )
        class Busy:
            async def __call__(self, tag=""):
                if tag == "occupier":
                    await asyncio.sleep(0.6)
                return tag

        h = serve.run(Busy.bind(), name="expire", route_prefix=None)
        h.remote().result(timeout_s=30)

        async def drive():
            h._router._refresh(force=True)
            occ = h.remote(tag="occupier")
            await asyncio.sleep(0.1)
            # 150 ms budget, but the slot is busy for ~500 more
            doomed = h.options(slo_ms=150.0).remote(tag="doomed")
            with pytest.raises(RequestShedError, match="expired"):
                await doomed.result_async()
            return await occ.result_async()

        assert asyncio.run(drive()) == "occupier"
        serve.delete("expire")


# ---------------------------------------------------------------------------
# Depth-1 latency neutrality (mirrors test_taskplane_batching)
# ---------------------------------------------------------------------------


class TestDepth1Neutrality:
    def test_depth1_latency_neutral(self, cluster):
        """A lone request through the traffic plane (admission check +
        heap push + same-tick flush) must cost ~nothing over the direct
        path — the scheduler flushes via loop.call_soon, never a
        timer."""

        @serve.deployment
        class Plain:
            def __call__(self):
                return "ok"

        @serve.deployment(traffic_config={"slo_ms": 10000.0})
        class Managed:
            def __call__(self):
                return "ok"

        hp = serve.run(Plain.bind(), name="d1p", route_prefix=None)
        hm = serve.run(Managed.bind(), name="d1m", route_prefix=None)

        def median_ms(h, n=30):
            async def run():
                h._router._refresh(force=True)
                for _ in range(5):  # warm: routes, connection, policy
                    await h.remote().result_async()
                lats = []
                for _ in range(n):
                    t0 = time.perf_counter()
                    await h.remote().result_async()
                    lats.append(time.perf_counter() - t0)
                lats.sort()
                return lats[n // 2] * 1e3

            return asyncio.run(run())

        plain = median_ms(hp)
        managed = median_ms(hm)
        print(f"\ndepth-1 p50: direct {plain:.2f} ms, "
              f"traffic-plane {managed:.2f} ms")
        # loose relative + absolute bound (loaded CI host): a flush
        # timer or per-request round trip would blow both immediately
        assert managed < plain * 3 + 20, (plain, managed)
        assert managed < 100, managed
        serve.delete("d1p")
        serve.delete("d1m")


def test_failover_releases_the_retry_pick(monkeypatch):
    """Replica-death failover must release the RETRY replica's
    in-flight count when the retried request completes — settling
    before the redispatch would strand the new pick forever and skew
    the pow-2 load signal away from healthy replicas."""
    from ray_tpu.core.errors import ActorDiedError
    from ray_tpu.serve.handle import DeploymentResponse

    class FakeRouter:
        def __init__(self):
            self.inflight = {"B": 0}

        def drop(self, replica):
            self.inflight.pop(replica, None)
            self._traffic_scheduler = None

        _traffic_scheduler = None

        def done(self, replica):
            if replica in self.inflight:
                self.inflight[replica] = max(
                    0, self.inflight[replica] - 1
                )

    router = FakeRouter()
    router.inflight["A"] = 1  # the original pick

    def redispatch():
        router.inflight["B"] = router.inflight.get("B", 0) + 1
        return "B", "ref_ok"

    def fake_get(ref, timeout=None):
        if ref == "ref_dead":
            raise ActorDiedError("replica A died")
        return 42

    monkeypatch.setattr(ray_tpu, "get", fake_get)
    resp = DeploymentResponse(router, "A", "ref_dead", redispatch)
    assert resp.result(timeout_s=5) == 42
    assert "A" not in router.inflight  # dropped wholesale
    assert router.inflight["B"] == 0, router.inflight  # retry released


# ---------------------------------------------------------------------------
# @serve.batch _BatchQueue hardening (satellite: drainer lifecycle,
# _full reset, exception fan-out)
# ---------------------------------------------------------------------------


class TestBatchQueueHardening:
    def test_raising_batch_fn_fails_every_waiter(self):
        """A raising batch fn fans the exception to ALL waiters of that
        batch — no stranded futures (pre-fix, a waiter whose future the
        fn never reached would await forever)."""

        @batch(max_batch_size=4, batch_wait_timeout_s=0.02)
        async def boom(items):
            raise ValueError("bad batch")

        async def main():
            results = await asyncio.gather(
                *(boom(i) for i in range(4)), return_exceptions=True
            )
            assert len(results) == 4
            assert all(isinstance(r, ValueError) for r in results), results

        asyncio.run(main())

    def test_failed_batch_does_not_kill_the_queue(self):
        """After one batch fails, later submissions still run — the
        drainer survives (or restarts) past a batch-fn exception."""
        state = {"fail": True}

        @batch(max_batch_size=2, batch_wait_timeout_s=0.01)
        async def flaky(items):
            if state["fail"]:
                raise RuntimeError("first batch dies")
            return [i * 2 for i in items]

        async def main():
            r = await asyncio.gather(flaky(1), flaky(2),
                                     return_exceptions=True)
            assert all(isinstance(x, RuntimeError) for x in r), r
            state["fail"] = False
            assert await flaky(3) == 6

        asyncio.run(main())

    def test_drainer_restarts_after_idle(self):
        """The drainer exits when the queue empties; the next submit
        after an idle period restarts it."""
        batches = []

        @batch(max_batch_size=2, batch_wait_timeout_s=0.01)
        async def echo(items):
            batches.append(list(items))
            return [i * 10 for i in items]

        async def main():
            assert await echo(1) == 10
            await asyncio.sleep(0.1)  # drainer is done; queue idle
            assert await echo(2) == 20
            r = await asyncio.gather(echo(3), echo(4))
            assert r == [30, 40]

        asyncio.run(main())
        assert batches[0] == [1] and batches[1] == [2]
        assert sorted(x for b in batches[2:] for x in b) == [3, 4]

    def test_full_event_resets_between_batches(self):
        """A full batch must not leak its `_full` wakeup into the next
        partial batch: the remainder waits its window and batches
        correctly instead of firing early item-by-item."""
        batches = []

        @batch(max_batch_size=2, batch_wait_timeout_s=0.25)
        async def echo(items):
            batches.append(list(items))
            return list(items)

        async def main():
            t0 = time.perf_counter()
            f1 = asyncio.ensure_future(echo("a"))
            f2 = asyncio.ensure_future(echo("b"))
            f3 = asyncio.ensure_future(echo("c"))
            await asyncio.gather(f1, f2)
            first_two = time.perf_counter() - t0
            await f3
            third = time.perf_counter() - t0
            return first_two, third

        first_two, third = asyncio.run(main())
        assert batches[0] == ["a", "b"]
        assert batches[1] == ["c"]
        # the full batch fired immediately; the partial waited its window
        assert first_two < 0.2, first_two
        assert third - first_two > 0.1, (first_two, third)

    def test_cancelled_drainer_fails_stranded_waiters(self):
        """Killing the drainer mid-batch fails the in-flight batch's
        waiters with the cancellation and the still-queued remainder
        with a fast RuntimeError — nobody hangs; the next submit
        starts a fresh drainer."""

        async def main():
            started = asyncio.Event()

            async def fn(items):
                started.set()
                await asyncio.sleep(30)
                return items

            q = _BatchQueue(fn, None, 2, 0.01)
            f1 = asyncio.ensure_future(q.submit(1))
            f2 = asyncio.ensure_future(q.submit(2))
            f3 = asyncio.ensure_future(q.submit(3))  # behind the batch
            await started.wait()
            q._drainer.cancel()
            r = await asyncio.gather(f1, f2, f3, return_exceptions=True)
            assert all(
                isinstance(x, (asyncio.CancelledError, RuntimeError))
                for x in r
            ), r
            assert isinstance(r[2], RuntimeError), r

            # recovery: a fresh submit restarts a working drainer
            async def ok_fn(items):
                return [i + 100 for i in items]

            q._fn = ok_fn
            assert await q.submit(7) == 107

        asyncio.run(main())
