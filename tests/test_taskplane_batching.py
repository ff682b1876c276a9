"""Batched task plane: perf floors + latency-neutrality pins.

The tentpole mechanisms (spec templates, per-tick frame coalescing,
batched completion replies, flush-window GCS notifications) are all
invisible when they work — these tests make their regressions loud:

- the deterministic allocs/call ceiling (wall clock on a shared CI host
  is mood-dependent; container churn is not),
- a generous throughput floor for the windowed async path,
- the depth-1 latency-neutrality contract: a single un-pipelined
  call_soon flushes in the SAME loop tick (no flush timer), and a burst
  issued in one tick rides ONE wire frame,
- windowed put() announces still land at the GCS (flush-window
  visibility).
"""

import asyncio
import time

import pytest

import ray_tpu
from ray_tpu.core import rpc
from ray_tpu.core.runtime import get_runtime


def _load_bench():
    """Import the repo-root bench.py (not a package; tests/ is what
    pytest puts on sys.path) so the alloc-churn test runs the exact
    measurement bench.py emits."""
    import importlib
    import pathlib
    import sys

    root = str(pathlib.Path(__file__).resolve().parent.parent)
    if root not in sys.path:
        sys.path.insert(0, root)
    return importlib.import_module("bench")


@pytest.fixture(scope="module")
def cluster():
    ray_tpu.init(num_cpus=4, num_tpus=0)
    yield
    ray_tpu.shutdown()


@ray_tpu.remote
class Echo:
    def ping(self):
        return b"ok"


def test_taskplane_alloc_churn_ceiling(cluster):
    """gen0 container allocs per windowed async actor call (the r4
    methodology) must stay <= 9.  The measurement IS bench.py's
    `bench_taskplane_alloc_churn` — one implementation, so the ceiling
    pinned here and the `taskplane_alloc_churn` row BENCH.md quotes can
    never drift apart.  History: r4 band 12.2-13.3, cleared to ~2.5 by
    round 5's lease-reuse + inline-promotion fixes; the batched task
    plane holds ~2.4 (template savings roughly offset batch-accumulator
    bookkeeping — its wall-clock win is frames/parses/rpcs, not allocs).
    The ceiling catches per-call churn creeping back into the
    submission/dispatch/reply path."""
    bench = _load_bench()
    per_call = bench.bench_taskplane_alloc_churn(ray_tpu)
    print(f"\ntaskplane_alloc_churn: {per_call:.2f} container allocs/call")
    assert per_call <= 9, (
        f"taskplane alloc churn {per_call:.1f}/call blew the 9/call "
        "ceiling — per-call container churn crept back into the "
        "submission/dispatch/reply path (r5+ steady state is ~2.4)"
    )


def test_tasks_alloc_churn_ceiling(cluster):
    """Normal-task twin of the churn ceiling (data plane v2): gen0
    container allocs per windowed `.remote()` NORMAL task must stay
    <= 9.  The measurement IS bench.py's
    `bench_taskplane_alloc_churn_tasks`.  History: ~25/call through
    r10 — the per-call spec dict copy, the 9-key lineage entry dict +
    live-returns set, and (dominant on a saturated host) lease requests
    parked at the GCS in proportion to queue depth; the slotted-lineage
    + compact-template + bounded-lease-pipeline rebuild cleared it to
    ~4/call."""
    bench = _load_bench()
    per_call = bench.bench_taskplane_alloc_churn_tasks(ray_tpu)
    print(f"\ntaskplane_alloc_churn_tasks: {per_call:.2f} allocs/call")
    assert per_call <= 9, (
        f"normal-task alloc churn {per_call:.1f}/call blew the 9/call "
        "ceiling — per-call container churn crept back into the "
        "submit/lineage/dispatch/reply path (v2 steady state is ~4)"
    )


def test_windowed_actor_call_throughput_floor(cluster):
    """Generous wall-clock floor for the batched actor path: ~10-30x
    under the unloaded steady state, so only a structural collapse
    (lost pipelining, per-call GCS round trips, frame-per-call wire
    regressions) trips it on a loaded CI host."""
    a = Echo.remote()
    ray_tpu.get(a.ping.remote(), timeout=60)
    window = 500
    for _ in range(2):
        ray_tpu.get([a.ping.remote() for _ in range(window)], timeout=120)
    n = 0
    t0 = time.perf_counter()
    while True:
        ray_tpu.get([a.ping.remote() for _ in range(window)], timeout=120)
        n += window
        dt = time.perf_counter() - t0
        if dt >= 3.0:
            break
    rate = n / dt
    print(f"\nwindowed actor calls: {rate:.0f}/s")
    ray_tpu.kill(a)
    assert rate > 100, (
        f"windowed actor-call throughput {rate:.0f}/s fell through the "
        "100/s floor (bench-host steady state is >2,000/s)"
    )


def test_depth1_sync_call_latency_neutral(cluster):
    """A single un-pipelined sync call must still complete promptly —
    batching is per-tick, never per-timer, so depth-1 latency does not
    regress.  The bound is loose (loaded host) but a flush window that
    parked single calls on a timer would blow it immediately."""
    a = Echo.remote()
    ray_tpu.get(a.ping.remote(), timeout=60)
    for _ in range(20):  # warm: promotion + connection
        ray_tpu.get(a.ping.remote(), timeout=60)
    t0 = time.perf_counter()
    n = 50
    for _ in range(n):
        ray_tpu.get(a.ping.remote(), timeout=60)
    per_call_ms = (time.perf_counter() - t0) / n * 1e3
    print(f"\nsync call p50-ish: {per_call_ms:.2f} ms/call")
    ray_tpu.kill(a)
    # a 10 ms gcs_notify-style flush window accidentally applied to the
    # task path would push this past 10 ms/call even on a loaded host
    assert per_call_ms < 50, (
        f"single sync calls take {per_call_ms:.1f} ms — the depth-1 "
        "path is waiting on a batch window instead of flushing in-tick"
    )


def test_single_call_soon_flushes_same_tick():
    """rpc-level pin of the latency-neutrality contract: one call_soon
    with an idle loop writes its frame via loop.call_soon (same tick),
    not a timer, and round-trips immediately."""

    async def main():
        async def handler(conn, method, payload):
            return payload

        srv = rpc.Server(handler)
        await srv.start()
        conn = await rpc.connect(srv.address, name="t")
        try:
            fut = conn.call_soon("echo", 42)
            # queued but not yet written: flush is scheduled for THIS
            # tick's callback pass, no timer anywhere in the path
            assert conn._flush_scheduled
            assert len(conn._out_batch) == 1
            t0 = asyncio.get_running_loop().time()
            assert await asyncio.wait_for(fut, timeout=5.0) == 42
            dt = asyncio.get_running_loop().time() - t0
            # generous: one loop tick + one local TCP round trip
            assert dt < 1.0, f"depth-1 call_soon took {dt:.3f}s"
        finally:
            await conn.close()
            await srv.close()

    asyncio.run(main())


def test_a_handler_that_lost_another_peer_still_answers_its_caller():
    """A handler that calls a third process and finds it gone raises
    ConnectionLost, which says nothing about ITS caller's connection.
    Taken for that, the request went unanswered: the GCS waited out
    ``worker_start_timeout_s`` (60 s, the CPU debited meanwhile) for a
    raylet whose worker had died between ``worker_ready`` and
    ``bind_env``."""

    async def main():
        async def handler(conn, method, payload):
            raise rpc.ConnectionLost("connection worker@1 is closed")

        srv = rpc.Server(handler)
        await srv.start()
        conn = await rpc.connect(srv.address, name="t")
        try:
            with pytest.raises(rpc.RemoteCallError) as err:
                await conn.call("lease_worker", {}, timeout=5.0)
            assert isinstance(err.value.remote_exception, rpc.ConnectionLost)
            assert "worker@1" in str(err.value.remote_exception)
        finally:
            await conn.close()
            await srv.close()

    asyncio.run(main())


def test_burst_coalesces_into_one_frame():
    """A burst of call_soon requests issued within one tick must leave
    the client as ONE wire frame (the push_task_batch behavior), and
    the replies — completed within one tick on the server — must come
    back batched too."""

    async def main():
        async def handler(conn, method, payload):
            return payload

        srv = rpc.Server(handler)
        await srv.start()
        conn = await rpc.connect(srv.address, name="t")
        writes = []
        real_write = conn._write_frames

        def counting_write(bufs):
            writes.append(1)
            real_write(bufs)

        conn._write_frames = counting_write
        try:
            futs = [conn.call_soon("echo", i) for i in range(64)]
            out = await asyncio.gather(*futs)
            assert out == list(range(64))
            assert len(writes) == 1, (
                f"{len(writes)} frames written for a 64-call burst — "
                "per-tick coalescing regressed to frame-per-call"
            )
        finally:
            await conn.close()
            await srv.close()

    asyncio.run(main())


def test_large_payload_burst_respects_byte_cap():
    """A one-tick burst of LARGE messages must not coalesce into a
    single oversized frame the peer would reject (rpc_max_frame_bytes):
    the accumulator's byte cap (rpc_batch_max_bytes) splits the burst
    into multiple under-cap frames, and everything still round-trips."""
    from ray_tpu.common.config import cfg

    payload_mb = 3 * 1024 * 1024
    n_msgs = 8  # 24 MB total vs the 8 MB default cap

    async def main():
        async def handler(conn, method, payload):
            return len(payload)

        srv = rpc.Server(handler)
        await srv.start()
        conn = await rpc.connect(srv.address, name="t")
        frame_sizes = []
        real_write = conn._write_frames

        def sizing_write(bufs):
            frame_sizes.append(sum(len(b) for b in bufs))
            real_write(bufs)

        conn._write_frames = sizing_write
        try:
            futs = [
                conn.call_soon("echo", b"x" * payload_mb)
                for _ in range(n_msgs)
            ]
            out = await asyncio.gather(*futs)
            assert out == [payload_mb] * n_msgs
            assert len(frame_sizes) > 1, (
                "24 MB of one-tick messages rode a single frame — the "
                "rpc_batch_max_bytes cap is not being applied"
            )
            slack = cfg.rpc_batch_max_bytes + payload_mb + 4096
            assert max(frame_sizes) <= slack, (
                f"a coalesced frame reached {max(frame_sizes)} bytes"
            )
        finally:
            await conn.close()
            await srv.close()

    asyncio.run(main())


def test_urgent_heartbeat_jumps_coalesced_batch():
    """Health-plane latency pin: an ``urgent`` notify (the raylet
    heartbeat) must hit the wire as its own lone frame AHEAD of a big
    per-tick coalesced batch queued on the same connection — a loaded
    tick must not delay the failure detector's input past the
    heartbeat interval (the exact delay that manufactures false
    positives under load)."""

    async def main():
        arrivals = []

        async def handler(conn, method, payload):
            arrivals.append(method)
            return True

        srv = rpc.Server(handler)
        await srv.start()
        conn = await rpc.connect(srv.address, name="t")
        try:
            # one tick's worth of coalescing traffic, queued first
            futs = [conn.call_soon("bulk", b"x" * 4096) for _ in range(64)]
            assert conn._out_batch, "burst did not queue"
            t0 = asyncio.get_running_loop().time()
            # the heartbeat is order-independent liveness traffic: it
            # must NOT flush the queued batch ahead of itself
            await conn.notify("heartbeat", {"n": 1}, urgent=True)
            dt = asyncio.get_running_loop().time() - t0
            await asyncio.gather(*futs)
            # a sync barrier so every notify has been dispatched
            await conn.call("sync", None)
            hb_pos = arrivals.index("heartbeat")
            first_bulk = arrivals.index("bulk")
            assert hb_pos < first_bulk, (
                f"heartbeat arrived at {hb_pos}, after the batch "
                f"(first bulk at {first_bulk}) — urgent frames are "
                "queueing behind per-tick coalescing"
            )
            assert dt < 0.5, f"urgent notify send took {dt:.3f}s"
        finally:
            await conn.close()
            await srv.close()

    asyncio.run(main())


def test_warm_template_cache_stays_picklable(cluster):
    """The spec-template caches hold runtime-bound state (the Runtime,
    its loop futures).  Pickling a RemoteFunction or ActorMethod after
    the cache warmed must still work — workflow's save_dag cloudpickles
    FunctionNodes, and users ship `handle.method` in closures."""
    import cloudpickle

    @ray_tpu.remote
    def add(a, b):
        return a + b

    a = Echo.remote()
    ray_tpu.get(add.remote(1, 2), timeout=60)   # warms add._template
    ray_tpu.get(a.ping.remote(), timeout=60)    # warms ActorMethod cache
    f2 = cloudpickle.loads(cloudpickle.dumps(add))
    assert f2._template is None
    m2 = cloudpickle.loads(cloudpickle.dumps(a.ping))
    assert m2._skeleton is None and m2._rt is None
    assert ray_tpu.get(f2.remote(3, 4), timeout=60) == 7
    ray_tpu.kill(a)


def test_fault_hooks_are_noops_when_disabled():
    """With RT_FAULTS unset the chaos sites on the depth-1 hot path
    (rpc send/recv, store create, lease grant) are a single module-
    attribute None check: zero allocations, nothing measurable.  The
    alloc-churn ceiling above pins the hooks' cost on the REAL
    submission/dispatch/reply path (the sites live inside
    _write_frames/_dispatch_msg/create, all on that path); this test
    pins the guard shape itself so the hooks can never regress the
    depth-1 path."""
    import sys

    from ray_tpu.common import faults

    assert faults.ACTIVE is None, (
        "tier-1 must run with RT_FAULTS unset — the zero-cost contract "
        "only holds for the disabled plane"
    )
    name = "conn-name"

    def guard():
        # the exact site shape threaded through rpc.py/store.py
        fault_ctl = faults.ACTIVE
        if fault_ctl is not None:
            fault_ctl.hit("rpc.send.frame", name)

    guard()  # warm
    deltas = []
    for _ in range(5):
        before = sys.getallocatedblocks()
        for _ in range(10_000):
            guard()
        deltas.append(sys.getallocatedblocks() - before)
    # min-of-5: background runtime threads may allocate concurrently,
    # but at least one clean window must show the guard allocating
    # nothing
    assert min(deltas) <= 2, (
        f"disabled fault guard allocated (deltas={deltas}) — the "
        "RT_FAULTS-unset path must stay a bare None check"
    )
    t0 = time.perf_counter()
    for _ in range(100_000):
        guard()
    dt = time.perf_counter() - t0
    assert dt < 0.5, (
        f"100k disabled fault guards took {dt:.3f}s — the no-op path "
        "grew real work"
    )


def test_windowed_put_announces_land(cluster):
    """put() location announces ride the flush window; they must still
    become GCS-visible (window/count caps) without any export flush."""
    rt = get_runtime()
    refs = [ray_tpu.put(b"x" * 2048) for _ in range(20)]
    for r in refs:
        reply = rt._run(
            rt.gcs.call(
                "get_object_locations",
                {"object_id": r.object_id.binary(), "timeout": 5.0},
            )
        )
        assert reply["locations"], (
            "windowed add_object_location never flushed to the GCS"
        )
    del refs
