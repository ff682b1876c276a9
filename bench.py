"""Benchmark entry point for the driver.

Two families, mirroring BASELINE.md:

1. **TPU compute** (the project's headline): GPT-2-124M (ray_tpu.models.gpt2,
   real config, bf16, seq 1024) trained for N timed steps on the local chip →
   `tokens_per_sec_per_chip` and `mfu` (flops_per_token ÷ chip peak FLOPs).
   The reference publishes no GPT throughput numbers (BASELINE.md §ML), so
   `vs_baseline` for this row is MFU ÷ 0.40 — the 40%-MFU north-star target.

2. **Control plane / data plane**: the `ray_perf.py` microbenchmark family
   (ray: python/ray/_private/ray_perf.py:93) — actor calls sync/async 1:1 and
   n:n, tasks sync/async, shm put GB/s, small-object get/s, placement-group
   create+remove churn — each with `vs_baseline` against the reference's
   archived 2.12.0 release numbers (BASELINE.md tables).

Output: one JSON line per row as it completes; the FINAL line is the headline
object {"metric", "value", "unit", "vs_baseline", ..., "rows": [all rows]}
(the driver parses the last line; the full family rides along in "rows").
"""

import json
import os
import threading
import time

# Pipelining knob for the async benchmarks: allow multiple in-flight tasks
# per leased worker (reference analogue: direct-call pipelining).
os.environ.setdefault("RT_MAX_TASKS_IN_FLIGHT_PER_WORKER", "10")

# Reference baselines (BASELINE.md, release_logs/2.12.0/microbenchmark.json)
BASELINES = {
    "actor_calls_sync_1_1": 2056.0,
    "actor_calls_async_1_1": 8900.0,
    "actor_calls_async_n_n": 28166.0,
    "tasks_sync_single_client": 988.0,
    "tasks_async_single_client": 8176.0,
    "put_gigabytes_per_s": 19.6,
    "multi_client_put_gigabytes_per_s": 39.0,
    "get_calls_per_s": 10267.0,
    "placement_group_create_remove_per_s": 824.0,
}

# 1 GiB broadcast: the reference's scalability suite measures 16.81 s to
# broadcast 1 GiB to 50 nodes over the network
# (release/release_logs/2.12.0/scalability/object_store.json).  Our
# single-host analogue broadcasts through the shm arena to 8 worker
# processes; vs_baseline is reference_seconds / ours (higher = faster),
# with the topology difference noted in the row.
BROADCAST_BASELINE_S = 16.81

# bf16 peak FLOP/s per chip by device kind (public spec sheets).
TPU_PEAK_FLOPS = [
    ("v6", 918e12),  # Trillium / v6e
    ("v5p", 459e12),
    ("v5", 197e12),  # v5e / "TPU v5 lite"
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
]

ROWS = []
_PRINT_LOCK = threading.Lock()
_FINISHED = threading.Event()


def emit(metric, value, unit, baseline=None, **extra):
    row = {
        "metric": metric,
        "value": round(value, 3) if isinstance(value, float) else value,
        "unit": unit,
    }
    if baseline:
        row["vs_baseline"] = round(value / baseline, 3)
    row.update(extra)
    with _PRINT_LOCK:
        if _FINISHED.is_set():
            # the headline already printed (watchdog fired): nothing may
            # print after it — the driver parses the LAST line
            return row
        ROWS.append(row)
        print(json.dumps(row), flush=True)
    return row


def _headline(gpt2_stats):
    """The FINAL JSON line the driver parses.  Callable at any point —
    falls back to the control-plane flagship when no real-chip row
    exists yet."""
    if gpt2_stats and gpt2_stats.get("on_tpu"):
        mfu = gpt2_stats["mfu"] or 0.0
        return {
            "metric": "gpt2_124m_train_tokens_per_sec_per_chip",
            "value": round(gpt2_stats["tokens_per_sec_per_chip"], 1),
            "unit": "tokens/s/chip",
            # no published reference number (BASELINE.md §ML):
            # ratio vs the 40%-MFU north-star target
            "vs_baseline": round(mfu / 0.40, 3),
            "mfu": round(mfu, 4),
            "device": gpt2_stats["device"],
            "rows": ROWS,
        }
    sync_row = next(
        (r for r in ROWS if r["metric"] == "actor_calls_sync_1_1"), None
    )
    return {
        "metric": "actor_calls_sync_1_1",
        "value": sync_row["value"] if sync_row else 0.0,
        "unit": "calls/s",
        "vs_baseline": (
            sync_row.get("vs_baseline", 0.0) if sync_row else 0.0
        ),
        "rows": ROWS,
    }


def _print_final(gpt2_stats):
    with _PRINT_LOCK:
        if _FINISHED.is_set():
            return
        # set INSIDE the lock: any emit() that isn't already printing
        # will see the flag and drop its row, so the headline is
        # guaranteed to be the last line out
        _FINISHED.set()
        print(json.dumps(_headline(gpt2_stats)), flush=True)


def _start_watchdog(deadline: float, state: dict):
    """Absolute backstop: whatever wedges (a hung backend probe, a stuck
    cluster shutdown), the driver ALWAYS gets a parseable final line and
    rc=0 inside the budget.  r3's bench timed out (rc=124) inside its
    own TPU retry window and shipped no gpt2 row at all — the watchdog
    makes that failure mode impossible."""

    def run():
        while not _FINISHED.is_set():
            rem = deadline - time.monotonic()
            if rem <= 0:
                _print_final(state.get("gpt2"))
                os._exit(0)
            _FINISHED.wait(min(rem, 5.0))

    t = threading.Thread(target=run, daemon=True, name="bench-watchdog")
    t.start()
    return t


# ---------------------------------------------------------------------------
# TPU compute: GPT-2-124M training throughput + MFU
# ---------------------------------------------------------------------------


def bench_gpt2(steps: int = 10, scan_unroll: int = 12):
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu.models import gpt2

    # persistent compile cache: the fully-unrolled step takes minutes to
    # compile; keep the executable so repeat bench runs skip straight to
    # the timed loop
    from ray_tpu.util import compile_cache

    compile_cache.configure()

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    if on_tpu:
        # flash pallas attention + no remat + fully-unrolled layer scan:
        # measured fastest single-chip combination (dense+remat 175
        # ms/step → flash 98 ms → unrolled 80 ms at B=8 S=1024, v5e)
        config = gpt2.GPTConfig.gpt2_124m(
            attention_impl="flash", remat=False, scan_unroll=scan_unroll
        )
        batch, seq = 8, 1024
        kind = dev.device_kind
        peak = next(
            (f for key, f in TPU_PEAK_FLOPS if key in kind.lower()), 275e12
        )
    else:  # CPU smoke path so bench.py stays runnable anywhere
        config = gpt2.GPTConfig.tiny()
        batch, seq = 4, 128
        kind, peak = dev.device_kind, None

    params = gpt2.init(jax.random.key(0), config)
    opt = optax.adamw(3e-4, weight_decay=0.1)
    opt_state = opt.init(params)

    def train_step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(gpt2.loss_fn)(
            params, {"tokens": tokens}, config
        )
        updates, opt_state = opt.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    step = jax.jit(train_step, donate_argnums=(0, 1))
    tokens = jax.random.randint(
        jax.random.key(1), (batch, seq + 1), 0, config.vocab_size, jnp.int32
    )

    # warmup: compile + 2 steady-state steps, synchronized by fetching
    # the loss VALUE
    for _ in range(3):
        params, opt_state, loss = step(params, opt_state, tokens)
    float(loss)

    t0 = time.perf_counter()
    for _ in range(steps):
        params, opt_state, loss = step(params, opt_state, tokens)
    float(loss)
    dt = time.perf_counter() - t0

    tokens_per_step = batch * seq
    tok_s = tokens_per_step * steps / dt
    fpt = gpt2.flops_per_token(config, seq)
    mfu = (tok_s * fpt / peak) if peak else None
    return {
        "tokens_per_sec_per_chip": tok_s,
        "mfu": mfu,
        "device": kind,
        "loss": float(loss),
        "step_ms": dt / steps * 1e3,
        "flops_per_token": fpt,
        "batch": batch,
        "seq": seq,
        "on_tpu": on_tpu,
        "scan_unroll": scan_unroll,
    }


# ---------------------------------------------------------------------------
# Control-plane microbenchmarks (ray_perf.py family)
# ---------------------------------------------------------------------------


def _timed_loop(fn, duration_s=3.0, chunk=100):
    """Run fn() in chunks until duration elapses; ops/s."""
    n = 0
    t0 = time.perf_counter()
    while True:
        for _ in range(chunk):
            fn()
        n += chunk
        dt = time.perf_counter() - t0
        if dt >= duration_s:
            return n / dt


def bench_actor_calls_sync(ray_tpu, duration_s=3.0):
    @ray_tpu.remote
    class Echo:
        def ping(self):
            return b"ok"

    a = Echo.remote()
    for _ in range(50):
        ray_tpu.get(a.ping.remote(), timeout=60)
    v = _timed_loop(lambda: ray_tpu.get(a.ping.remote()), duration_s)
    ray_tpu.kill(a)
    return v


def bench_actor_calls_async(ray_tpu, duration_s=3.0, window=1000):
    @ray_tpu.remote
    class Echo:
        def ping(self):
            return b"ok"

    a = Echo.remote()
    ray_tpu.get(a.ping.remote(), timeout=60)
    # steady-state: one untimed window warms the worker, the connection
    # buffers, and the allocator before the clock starts (ray_perf runs
    # long enough that its ramp amortizes; a 3 s budget doesn't)
    ray_tpu.get([a.ping.remote() for _ in range(window)], timeout=120)
    n = 0
    t0 = time.perf_counter()
    while True:
        ray_tpu.get([a.ping.remote() for _ in range(window)])
        n += window
        dt = time.perf_counter() - t0
        if dt >= duration_s:
            break
    ray_tpu.kill(a)
    return n / dt


def bench_actor_calls_n_n(ray_tpu, duration_s=3.0, n_actors=8, window=200):
    @ray_tpu.remote
    class Echo:
        def ping(self):
            return b"ok"

    actors = [Echo.options(num_cpus=0.1).remote() for _ in range(n_actors)]
    ray_tpu.get([a.ping.remote() for a in actors], timeout=120)
    ray_tpu.get(  # untimed steady-state warmup round
        [a.ping.remote() for a in actors for _ in range(window)],
        timeout=120,
    )
    n = 0
    t0 = time.perf_counter()
    while True:
        refs = []
        for a in actors:
            refs.extend(a.ping.remote() for _ in range(window))
        ray_tpu.get(refs)
        n += len(refs)
        dt = time.perf_counter() - t0
        if dt >= duration_s:
            break
    for a in actors:
        ray_tpu.kill(a)
    return n / dt


def bench_taskplane_alloc_churn(ray_tpu, window=1000, rounds=5):
    """Deterministic task-plane churn row: gen0 container allocations per
    windowed async actor call, the round-4 methodology ((gen0 collections
    x threshold + count delta) / calls, process-wide).  Wall-clock on the
    1-core harness is mood-dependent; this is the regression signal that
    is not (r4 band: 12.2-13.3, ~2.4 since the r5 fixes + batched task
    plane; <= 9 pinned by tests/test_taskplane_batching.py)."""
    import gc

    @ray_tpu.remote
    class Echo:
        def ping(self):
            return b"ok"

    a = Echo.remote()
    ray_tpu.get(a.ping.remote(), timeout=60)
    for _ in range(3):  # steady state: leases, promotion, allocator
        ray_tpu.get([a.ping.remote() for _ in range(window)], timeout=120)
    gc.collect()
    th0 = gc.get_threshold()[0]
    c0 = gc.get_stats()[0]["collections"]
    n0 = gc.get_count()[0]
    for _ in range(rounds):
        ray_tpu.get([a.ping.remote() for _ in range(window)], timeout=120)
    c1 = gc.get_stats()[0]["collections"]
    n1 = gc.get_count()[0]
    ray_tpu.kill(a)
    return ((c1 - c0) * th0 + (n1 - n0)) / (rounds * window)


def bench_taskplane_alloc_churn_tasks(ray_tpu, window=1000, rounds=5):
    """Normal-task twin of the alloc-churn row: gen0 container
    allocations per windowed `.remote()` NORMAL task (submit + reply +
    get), same (gen0 collections x threshold + count delta)/calls
    methodology.  This is the path the data-plane-v2 slotted-lineage +
    compact-template work targets (r10 band: ~25/call via the per-call
    spec dict, lineage dict + live-returns set, and unbounded parked
    lease requests; ~4/call after; <= 9 pinned by
    tests/test_taskplane_batching.py)."""
    import gc

    @ray_tpu.remote
    def noop():
        return b"ok"

    ray_tpu.get(noop.remote(), timeout=60)
    for _ in range(3):  # steady state: leases, promotion, allocator
        ray_tpu.get([noop.remote() for _ in range(window)], timeout=120)
    gc.collect()
    th0 = gc.get_threshold()[0]
    c0 = gc.get_stats()[0]["collections"]
    n0 = gc.get_count()[0]
    for _ in range(rounds):
        ray_tpu.get([noop.remote() for _ in range(window)], timeout=120)
    c1 = gc.get_stats()[0]["collections"]
    n1 = gc.get_count()[0]
    return ((c1 - c0) * th0 + (n1 - n0)) / (rounds * window)


def bench_tasks_sync(ray_tpu, duration_s=3.0):
    @ray_tpu.remote
    def noop():
        return b"ok"

    ray_tpu.get(noop.remote(), timeout=60)
    return _timed_loop(lambda: ray_tpu.get(noop.remote()), duration_s, chunk=20)


def bench_tasks_async(ray_tpu, duration_s=3.0, window=1000):
    @ray_tpu.remote
    def noop():
        return b"ok"

    ray_tpu.get(noop.remote(), timeout=60)
    ray_tpu.get(  # untimed steady-state warmup window (lease ramp-up)
        [noop.remote() for _ in range(window)], timeout=120
    )
    n = 0
    t0 = time.perf_counter()
    while True:
        ray_tpu.get([noop.remote() for _ in range(window)])
        n += window
        dt = time.perf_counter() - t0
        if dt >= duration_s:
            break
    return n / dt


def bench_put_gigabytes(ray_tpu, total_mb=2048, chunk_mb=128):
    import numpy as np

    buf = np.random.bytes(chunk_mb * 1024 * 1024)

    def one_round():
        refs = []
        moved = 0
        t0 = time.perf_counter()
        while moved < total_mb * 1024 * 1024:
            refs.append(ray_tpu.put(buf))
            moved += len(buf)
        dt = time.perf_counter() - t0
        del refs
        return moved / dt / 1e9

    one_round()  # warm the arena: first-touch page faults dominate cold runs
    import gc

    gc.collect()
    time.sleep(1.0)  # let refcounting free the warmup objects
    return one_round()


def bench_multi_client_put(ray_tpu, n_clients=4, mb_per_client=512,
                           chunk_mb=64):
    """Aggregate put bandwidth with several worker processes writing the
    arena concurrently (reference: multi_client_put_gigabytes,
    release/microbenchmark — 39.0 GB/s on a 64-core host)."""

    @ray_tpu.remote
    def putter(total_mb, chunk_mb):
        import numpy as np
        import time as _t

        buf = np.random.bytes(chunk_mb * 1024 * 1024)
        moved = 0
        refs = []
        t0 = _t.perf_counter()
        while moved < total_mb * 1024 * 1024:
            refs.append(ray_tpu.put(buf))
            moved += len(buf)
        dt = _t.perf_counter() - t0
        del refs
        return moved, dt

    # warm: one small round so worker leases + arena pages exist
    ray_tpu.get(
        [putter.remote(chunk_mb, chunk_mb) for _ in range(n_clients)],
        timeout=120,
    )
    t0 = time.perf_counter()
    out = ray_tpu.get(
        [putter.remote(mb_per_client, chunk_mb) for _ in range(n_clients)],
        timeout=300,
    )
    wall = time.perf_counter() - t0
    total = sum(m for m, _ in out)
    return total / wall / 1e9


def bench_put_bandwidth_matrix(ray_tpu):
    """Data-plane-v2 put matrix: size x clients x inline/vectored.

    Small sizes report puts/s (the create/seal round trip, not memcpy,
    dominates); large sizes report GB/s (memcpy-bound).  The `_noinline`
    twin of the 4KB row runs with the slab disabled, isolating the
    inline fast path's win; the multi-client rows use worker processes
    writing the shared arena concurrently (sharded-index contention
    surface).  Returns {row_name: value}."""
    import gc
    import numpy as np
    from ray_tpu.common.config import cfg as _cfg

    out = {}

    def drain():
        gc.collect()
        time.sleep(0.5)

    # -- single-client small puts: inline slab vs forced create path --
    from ray_tpu.core.runtime import get_runtime

    del _cfg  # knobs ride the store-level switch below
    store = get_runtime().store
    small = b"s" * 4096
    # noinline first: its create-path warm round faults the arena ranges
    # the slab refills will recycle, so the inline row measures the warm
    # steady state (cold first-touch is paid once per range, by design at
    # slab batch-reserve time)
    for label, enabled in (("noinline", False), ("inline", True)):
        store.set_slab_enabled(enabled)
        try:
            n = 2500
            refs = [ray_tpu.put(small) for _ in range(n)]  # warm
            del refs
            drain()
            best = 0.0
            for _ in range(3):
                t0 = time.perf_counter()
                refs = [ray_tpu.put(small) for _ in range(n)]
                best = max(best, n / (time.perf_counter() - t0))
                del refs
                drain()
            out[f"put_4kb_1c_{label}_per_s"] = best
        finally:
            store.set_slab_enabled(True)

    # -- single-client medium/large puts (vectored path, GB/s) --
    for size_mb, total_mb in ((0.25, 128), (64, 1024)):
        buf = np.random.bytes(int(size_mb * 1024 * 1024))
        def one_round():
            refs, moved = [], 0
            t0 = time.perf_counter()
            while moved < total_mb * 1024 * 1024:
                refs.append(ray_tpu.put(buf))
                moved += len(buf)
            dt = time.perf_counter() - t0
            del refs
            return moved / dt / 1e9
        one_round()
        drain()
        key = f"put_{size_mb:g}mb_1c_gb_per_s".replace(".", "p")
        out[key] = one_round()
        drain()

    # -- multi-client rows: 4 workers writing the arena concurrently --
    @ray_tpu.remote
    def putter(n_small, large_mb):
        import time as _t
        res = {}
        if n_small:
            payload = b"m" * 4096
            refs = [ray_tpu.put(payload) for _ in range(200)]  # warm
            del refs
            t0 = _t.perf_counter()
            refs = [ray_tpu.put(payload) for _ in range(n_small)]
            res["small"] = (n_small, _t.perf_counter() - t0)
            del refs
        if large_mb:
            import numpy as _np
            buf = _np.random.bytes(32 * 1024 * 1024)
            moved, refs = 0, []
            t0 = _t.perf_counter()
            while moved < large_mb * 1024 * 1024:
                refs.append(ray_tpu.put(buf))
                moved += len(buf)
            res["large"] = (moved, _t.perf_counter() - t0)
            del refs
        return res

    n_clients = 4
    ray_tpu.get(  # warm leases + arenas
        [putter.remote(50, 32) for _ in range(n_clients)], timeout=120,
    )
    t0 = time.perf_counter()
    rs = ray_tpu.get(
        [putter.remote(2000, 0) for _ in range(n_clients)], timeout=300,
    )
    wall = time.perf_counter() - t0
    out["put_4kb_4c_per_s"] = sum(r["small"][0] for r in rs) / wall
    t0 = time.perf_counter()
    rs = ray_tpu.get(
        [putter.remote(0, 256) for _ in range(n_clients)], timeout=300,
    )
    wall = time.perf_counter() - t0
    out["put_32mb_4c_gb_per_s"] = sum(r["large"][0] for r in rs) / wall / 1e9
    return out


def bench_broadcast_1gib(ray_tpu, n_readers=8, gib=1.0):
    """Time to make one ~1 GiB object readable by n worker processes
    (single-host shm analogue of the reference's 1-GiB-to-50-nodes
    broadcast).  Returns seconds."""
    import numpy as np

    @ray_tpu.remote
    def reader(ref):
        # zero-copy map + checksum touch of the first/last pages
        arr = ray_tpu.get(ref[0])
        return int(arr[0]) + int(arr[-1])

    data = np.ones(int(gib * (1 << 30)), dtype=np.uint8)
    t0 = time.perf_counter()
    ref = ray_tpu.put(data)
    # pass in a list so the ref travels by reference, not auto-resolved
    out = ray_tpu.get(
        [reader.remote([ref]) for _ in range(n_readers)], timeout=300
    )
    wall = time.perf_counter() - t0
    assert all(o == 2 for o in out)
    del ref
    return wall


def bench_scheduler_scale(n_nodes=1000, n_leases=10_000):
    """1k virtual nodes on a fresh GCS, lease churn latency + GCS CPU
    (tests/test_scheduler_scale.py tier 2 is the full envelope proof;
    this row is the driver-captured excerpt).  Self-contained: own GCS
    subprocess, no ray_tpu.init needed."""
    import asyncio
    import tempfile

    from ray_tpu.core import node as node_mod
    from ray_tpu.util import sched_bench as sb

    prev = os.environ.get("RT_NODE_DEATH_TIMEOUT_S")
    os.environ["RT_NODE_DEATH_TIMEOUT_S"] = "600"  # single-loop stubs
    tmp = tempfile.mkdtemp(prefix="rt_bench_sched_")
    proc, address = node_mod.start_gcs(tmp)
    try:
        meter = sb.GcsCpuMeter(proc.pid)

        async def main():
            stubs, hb = await sb.start_fleet(address, n_nodes)
            clients = await sb.connect_clients(address, 8)
            lats, wall = await sb.lease_churn(clients, n_leases, 512)
            await sb.close_clients(clients)
            await sb.stop_fleet(stubs, hb)
            return lats, wall

        lats, wall = asyncio.run(main())
        cpu = meter.sample()
        return {
            "p50_ms": lats[len(lats) // 2] * 1e3,
            "p95_ms": lats[int(len(lats) * 0.95)] * 1e3,
            "rate": n_leases / wall,
            "gcs_cpu_frac": cpu["cpu_frac"],
        }
    finally:
        proc.terminate()
        proc.wait(timeout=10)
        if prev is None:
            os.environ.pop("RT_NODE_DEATH_TIMEOUT_S", None)
        else:
            os.environ["RT_NODE_DEATH_TIMEOUT_S"] = prev


def bench_get_calls(ray_tpu, duration_s=3.0):
    ref = ray_tpu.put(b"x" * 1024)
    ray_tpu.get(ref)
    return _timed_loop(lambda: ray_tpu.get(ref), duration_s)


def bench_pg_churn(ray_tpu, duration_s=3.0):
    from ray_tpu.util import placement_group, remove_placement_group

    def one():
        pg = placement_group([{"CPU": 0.1}], strategy="PACK")
        pg.wait(timeout_seconds=30)
        remove_placement_group(pg)

    one()  # warmup
    return _timed_loop(one, duration_s, chunk=10)


def bench_fault_recovery(ray_tpu):
    """Time-to-first-successful-result after an injected fault — the
    number the robustness plane is accountable for.

    Task leg: with a warm lease, the next push_task frame to the worker
    is chaos-reset (site rpc.send.frame, driver-side, deterministic);
    the lease breaks, the task requeues onto a fresh lease, and the
    clock stops at the result.  Collective leg: a 3-rank group loses one
    member to ray_tpu.kill; the clock runs from the kill through
    reform_collective_group (shrink to 2) to the first bit-exact
    allreduce among the survivors.
    """
    import numpy as np

    from ray_tpu.common import faults
    from ray_tpu.util import collective as col

    @ray_tpu.remote(max_retries=2)
    def probe():
        return 1

    ray_tpu.get(probe.remote(), timeout=60)  # warm lease + worker
    faults.install([faults.FaultPlan(
        site="rpc.send.frame", match="->worker", action="reset", nth=1,
    )])
    try:
        t0 = time.perf_counter()
        assert ray_tpu.get(probe.remote(), timeout=120) == 1
        task_ms = (time.perf_counter() - t0) * 1e3
        fired = len(faults.trace())
    finally:
        faults.clear()
    if not fired:
        raise RuntimeError("worker-conn reset never fired; task leg invalid")

    @ray_tpu.remote
    class _Rank:
        def init(self, world, rank, group):
            col.init_collective_group(world, rank, group_name=group)
            return True

        def reform(self, world, group):
            col.reform_collective_group(world, group_name=group)
            return True

        def allreduce(self, arr, group):
            return col.allreduce(arr, group_name=group)

    # collective leg failures must not discard the task-leg measurement
    # (each leg gets its own bench row): report the error alongside
    collective_ms = None
    collective_err = None
    try:
        group = "bench-fault-recovery"
        ranks = [_Rank.options(num_cpus=0).remote() for _ in range(3)]
        ray_tpu.get(
            [m.init.remote(3, i, group) for i, m in enumerate(ranks)],
            timeout=120,
        )
        data = np.arange(65536, dtype=np.float32)
        ray_tpu.get([m.allreduce.remote(data, group) for m in ranks],
                    timeout=120)  # warm the ring
        ray_tpu.kill(ranks[1])
        survivors = [ranks[0], ranks[2]]
        t0 = time.perf_counter()
        ray_tpu.get([m.reform.remote(2, group) for m in survivors],
                    timeout=120)
        out = ray_tpu.get(
            [m.allreduce.remote(data, group) for m in survivors],
            timeout=120,
        )
        collective_ms = (time.perf_counter() - t0) * 1e3
        for o in out:
            assert np.array_equal(o, data + data)
        for m in survivors:
            ray_tpu.kill(m)
    except Exception as e:  # noqa: BLE001
        collective_err = repr(e)
    return {"task_ms": task_ms, "collective_ms": collective_ms,
            "collective_err": collective_err}


def bench_collective_matrix():
    """Collectives v2 matrix: message size x algorithm x wire dtype
    over a TWO-NODE cluster (ranks 0/1 on the head, 2/3 on the second
    node — ring hops 1→2 and 3→0 cross the wire), plus an overlap row.

    Large rows report bus bandwidth ``2·(n-1)/n · tensor_bytes / wall``
    (the standard allreduce normalization, comparable across wire
    dtypes because the NUMERATOR stays the logical fp32 bytes — a
    quantized path that moves fewer wire bytes in the same time shows
    up as higher busbw).  Small rows report per-op latency.  The
    overlap rows time launch+compute+wait vs blocking-op-then-compute
    at equal compute, so their difference is the EXPOSED comm time.
    """
    import numpy as np

    import ray_tpu
    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.util.scheduling_strategies import (
        NodeAffinitySchedulingStrategy,
    )

    @ray_tpu.remote
    class _Rank:
        def init(self, world, rank, group):
            from ray_tpu.util import collective as col

            col.init_collective_group(world, rank, group_name=group)
            return True

        def timed_allreduce(self, n_elems, reps, group, wire, alg):
            from ray_tpu.util import collective as col

            x = ((np.arange(n_elems) % 1024).astype(np.float32)) / 7.0
            col.allreduce(x, group_name=group, wire_dtype=wire,
                          algorithm=alg)  # warm conns + codec
            col.barrier(group_name=group)
            t0 = time.perf_counter()
            for _ in range(reps):
                col.allreduce(x, group_name=group, wire_dtype=wire,
                              algorithm=alg)
            return (time.perf_counter() - t0) / reps

        def overlap_run(self, n_elems, compute_s, group, wire, mode):
            from ray_tpu.util import collective as col

            x = (np.arange(n_elems, dtype=np.float32)) / 3.0

            def spin(budget):
                z = np.ones(8192, np.float64)
                end = time.perf_counter() + budget
                while time.perf_counter() < end:
                    z = np.sqrt(z + 1.0)

            col.barrier(group_name=group)
            t0 = time.perf_counter()
            if mode == "overlap":
                w = col.allreduce_launch(x, group_name=group,
                                         wire_dtype=wire)
                spin(compute_s)
                w.wait(timeout=120)
            else:
                col.allreduce(x, group_name=group, wire_dtype=wire)
                spin(compute_s)
            return time.perf_counter() - t0

    rows = {}
    cluster = Cluster(initialize_head=True, connect=True,
                      head_node_args={"num_cpus": 4})
    try:
        second = cluster.add_node(num_cpus=4)
        cluster.wait_for_nodes(timeout=60)
        placement = [
            cluster.head_node.node_id, cluster.head_node.node_id,
            second.node_id, second.node_id,
        ]
        members = [
            _Rank.options(
                num_cpus=0,
                scheduling_strategy=NodeAffinitySchedulingStrategy(
                    node_id=nid, soft=False
                ),
            ).remote()
            for nid in placement
        ]
        n = len(members)
        group = "bench-cb4"
        ray_tpu.get(
            [m.init.remote(n, i, group) for i, m in enumerate(members)],
            timeout=120,
        )

        def run(n_elems, reps, wire, alg):
            ts = ray_tpu.get(
                [
                    m.timed_allreduce.remote(n_elems, reps, group, wire, alg)
                    for m in members
                ],
                timeout=600,
            )
            return max(ts)  # the group is as slow as its slowest rank

        # large: bandwidth regime (16 MB tensor), ring only
        big = 1 << 22  # f32 elems = 16 MiB
        logical = 2 * (n - 1) / n * big * 4
        for wire in ("fp32", "int8", "bf16"):
            t = run(big, 3, wire, "ring")
            rows[f"collective_16mb_ring_{wire}_gbps"] = logical / t / 1e9
        # small: latency regime (64 KB tensor), ring vs rd, fp32 + int8
        small = 16384
        for alg in ("ring", "rd"):
            for wire in ("fp32", "int8"):
                t = run(small, 10, wire, alg)
                rows[f"collective_64kb_{alg}_{wire}_ms"] = t * 1e3
        # overlap: equal caller compute (~the fp32 comm time) riding
        # launch/wait vs the blocking op; difference = exposed comm
        t_comm = logical / (rows["collective_16mb_ring_fp32_gbps"] * 1e9)
        compute_s = t_comm
        for mode in ("blocking", "overlap"):
            ts = ray_tpu.get(
                [
                    m.overlap_run.remote(big, compute_s, group, "fp32", mode)
                    for m in members
                ],
                timeout=600,
            )
            rows[f"collective_overlap_{mode}_total_ms"] = max(ts) * 1e3
        rows["collective_overlap_compute_ms"] = compute_s * 1e3
        rows["collective_overlap_exposed_comm_ms"] = (
            rows["collective_overlap_overlap_total_ms"] - compute_s * 1e3
        )
        for m in members:
            ray_tpu.kill(m)
    finally:
        try:
            ray_tpu.shutdown()
        except Exception:
            pass
        cluster.shutdown()
    return rows


def bench_failure_detection(seed: int = 2026):
    """Adaptive (phi-accrual) failure detection vs the fixed-timeout
    baseline — the health plane's quotable numbers.

    Deterministic seeded simulation driven through the PRODUCTION
    detector code (common/health.PhiAccrualDetector) and the PRODUCTION
    death rule (health.death_confirmed, the same function the GCS
    health loop calls); only the heartbeat trace is synthetic, so the
    row is reproducible on any host.

    Scenario (heartbeat interval h=100 ms):
      1. 40 beats at h with seeded 5% jitter (steady state),
      2. an induced 2x LOAD STALL: 15 beats at 2h (the node runs at 2x
         load) capped by one 5h convoy gap — the classic GC-pause /
         CPU-convoy shape that makes tightly-tuned fixed detectors
         mass-fire,
      3. recovery beats, then a TRUE partition (silence).

    Reported: false positives across phase 2 for each detector (the
    acceptance: adaptive 0, fixed >= 1), and confirmed-death latency
    after the true partition (acceptance: adaptive within 2x of the
    fixed baseline).  Fixed baseline timeout: 4h = 0.4 s, a tight
    production tuning for a 100 ms cadence; adaptive cap 1.2 s with a
    0.5x floor (the shipped health_death_floor_frac default).
    """
    import random

    from ray_tpu.common.health import PhiAccrualDetector, death_confirmed

    h = 0.1
    fixed_timeout = 4 * h
    cap = 1.2           # node_death_timeout_s for this cadence
    floor = 0.5 * cap   # cfg.health_death_floor_frac default
    phi_death = 8.0     # cfg.health_phi_death default

    rng = random.Random(seed)
    det = PhiAccrualDetector(min_std_frac=0.35, min_samples=5)
    t = 0.0
    beats = []
    for _ in range(40):                     # steady state
        t += h * (1 + rng.uniform(-0.05, 0.05))
        beats.append(t)
    stall_beats = []
    for i in range(15):                     # sustained 2x load
        t += 2 * h * (1 + rng.uniform(-0.05, 0.05))
        stall_beats.append(t)
    t += 5 * h                              # the convoy gap
    stall_beats.append(t)
    for _ in range(10):                     # recovered (still loaded)
        t += 2 * h * (1 + rng.uniform(-0.05, 0.05))
        stall_beats.append(t)

    # replay: sweep wall time in 10 ms steps, each detector fires at
    # most once per inter-beat gap (a real health loop latches death)
    fp_adaptive = fp_fixed = 0
    all_beats = beats + stall_beats
    last = None
    for hb in all_beats:
        if last is not None and hb in stall_beats:
            fired_a = fired_f = False
            s = last
            while s < hb:
                elapsed = s - last
                if not fired_f and elapsed > fixed_timeout:
                    fp_fixed += 1
                    fired_f = True
                if not fired_a and death_confirmed(
                    det.phi(s), elapsed, phi_death, floor, cap
                ):
                    fp_adaptive += 1
                    fired_a = True
                s += 0.01
        det.heartbeat(hb)
        last = hb

    # true partition: silence after the final beat
    def latency(fire):
        s = last
        while s - last < 10 * cap:
            if fire(s - last, s):
                return s - last
            s += 0.001
        return float("inf")

    lat_fixed = latency(lambda el, s: el > fixed_timeout)
    lat_adaptive = latency(
        lambda el, s: death_confirmed(det.phi(s), el, phi_death, floor, cap)
    )
    return {
        "false_positives_adaptive": fp_adaptive,
        "false_positives_fixed": fp_fixed,
        "detect_ms_adaptive": lat_adaptive * 1e3,
        "detect_ms_fixed": lat_fixed * 1e3,
        "latency_ratio": lat_adaptive / lat_fixed,
    }


def bench_preemption_recovery():
    """Graceful drain vs the reactive fault_recovery baseline.

    A 2-node cluster's worker node holds the sole copy of an object, a
    stateful checkpointable actor, and rank 1 of a 2-rank collective
    group.  ``ChaosController.preempt_node`` delivers the termination
    notice, the GCS drain migrates everything inside the deadline, and
    the node is then hard-killed.  Three legs, each reporting the
    BLACKOUT — time from the kill to the first successful post-kill
    result — which is what preemption costs goodput: the reactive
    ``fault_recovery`` task row pays detection + lease re-grant + worker
    spawn (~450 ms) plus recomputation *after* the kill, while graceful
    drain pays its migration *before* the kill, so the blackout is just
    the first call's routing latency.  ``drain_ms`` (notice → fully
    migrated) is reported alongside for the full picture.

    Own cluster + driver (multi-node); call after the single-node bench
    family has shut down.
    """
    import numpy as np

    import ray_tpu
    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.common.faults import ChaosController
    from ray_tpu.core.runtime import get_runtime
    from ray_tpu.util import collective as col  # noqa: F401 (workers use it)

    @ray_tpu.remote
    class _Ck:
        def __init__(self):
            self.n = 0

        def bump(self):
            self.n += 1
            return self.n

        def value(self):
            return self.n

        def init(self, world, rank, group):
            from ray_tpu.util import collective as _c

            _c.init_collective_group(world, rank, group_name=group)
            return rank

        def allreduce(self, arr, group):
            from ray_tpu.util import collective as _c

            return _c.allreduce(arr, group_name=group)

        def __rt_checkpoint__(self):
            return {"n": self.n}

        def __rt_restore__(self, state):
            self.n = state["n"]

    cluster = Cluster(initialize_head=True, connect=True,
                      head_node_args={"num_cpus": 4,
                                      "resources": {"h": 4.0}})
    try:
        victim = cluster.add_node(num_cpus=1, resources={"pre": 1.0})
        cluster.wait_for_nodes(timeout=60)

        @ray_tpu.remote(resources={"pre": 0.3})
        def big():
            return np.arange(400_000, dtype=np.int64)

        @ray_tpu.remote(resources={"pre": 0.3})
        def marker():
            return True

        group = "bench-preempt"
        home = _Ck.options(num_cpus=0, resources={"h": 0.5}).remote()
        moving = _Ck.options(
            num_cpus=0, resources={"pre": 0.3}, max_restarts=0
        ).remote()
        ray_tpu.get(
            [home.init.remote(2, 0, group), moving.init.remote(2, 1, group)],
            timeout=120,
        )
        data = np.arange(65536, dtype=np.float32)
        ray_tpu.get(
            [home.allreduce.remote(data, group),
             moving.allreduce.remote(data, group)],
            timeout=120,
        )  # warm the ring
        assert ray_tpu.get(moving.bump.remote(), timeout=60) == 1
        ref = big.remote()
        assert ray_tpu.get(marker.remote(), timeout=120) is True

        # the survivor the migration lands on
        cluster.add_node(num_cpus=1, resources={"pre": 1.0})
        cluster.wait_for_nodes(timeout=60)

        chaos = ChaosController(cluster, seed=7)
        t_notice = time.perf_counter()
        _, state = chaos.preempt_node(node=victim, deadline_s=30.0)
        t_killed = time.perf_counter()
        if state != "drained":
            raise RuntimeError(f"graceful drain did not complete: {state}")
        rt = get_runtime()
        st = rt._run(rt.gcs.call(
            "get_drain_status", {"node_id": victim.node_id}
        ))
        drain_ms = (st["finished_at"] - st["started_at"]) * 1e3

        # --- blackout legs (the node is dead NOW) ---
        t0 = time.perf_counter()
        arr = ray_tpu.get(ref, timeout=60)
        object_ms = (time.perf_counter() - t0) * 1e3
        assert arr[-1] == 399_999
        assert rt.reconstructions == 0, "evacuation leg reconstructed"

        t0 = time.perf_counter()
        assert ray_tpu.get(moving.value.remote(), timeout=120) == 1
        actor_ms = (time.perf_counter() - t0) * 1e3

        t0 = time.perf_counter()
        end = time.monotonic() + 60
        while True:  # survivors' reform rides pubsub; tolerate a beat
            try:
                outs = ray_tpu.get(
                    [home.allreduce.remote(data, group),
                     moving.allreduce.remote(data, group)],
                    timeout=60,
                )
                break
            except Exception:  # noqa: BLE001
                if time.monotonic() > end:
                    raise
                time.sleep(0.1)
        collective_ms = (time.perf_counter() - t0) * 1e3
        for o in outs:
            assert np.array_equal(o, data + data)
        return {
            "drain_ms": drain_ms,
            "notice_to_kill_ms": (t_killed - t_notice) * 1e3,
            "object_blackout_ms": object_ms,
            "actor_blackout_ms": actor_ms,
            "collective_blackout_ms": collective_ms,
        }
    finally:
        ray_tpu.shutdown()
        cluster.shutdown()


def bench_pipeline_gpt2(ray_tpu, steps: int = 6, trials: int = 3):
    """MPMD pipeline GPT-2, three interleaved arms per trial — p2p
    channel handoff / driver-ref handoff / single-gang local — so host
    drift hits every arm equally.

    CPU context: one host, so the tokens/s rows measure ORCHESTRATION
    overhead — per-micro-op actor calls plus the handoff plane — over
    identical math, not parallel speedup (that needs stages on distinct
    chips).  All arms run the same per-stage programs (train.pipeline's
    LocalPipelineRunner IS the pipeline partition run in one process),
    and the bitwise loss cross-check on BOTH distributed arms keeps the
    rows honest.

    The ``driver_rpcs_per_microop`` pair is the data-plane-v2 headline:
    outbound driver RPCs (``core.rpc.CALLS`` delta across the timed
    block — control submissions, ref promotions, store/GCS traffic)
    per ideal micro-op.  The p2p arm ships no data refs, so its count
    collapses to the pure control-ack floor.
    """
    from ray_tpu.core import rpc as rpc_mod
    from ray_tpu.models import gpt2 as gpt2_mod
    from ray_tpu.train.pipeline import (
        LocalPipelineRunner,
        PipelineConfig,
        PipelineTrainer,
        synthetic_batches,
    )

    cfg = gpt2_mod.GPTConfig.tiny(num_layers=4, max_seq_len=64)

    def make(handoff, name):
        return PipelineConfig(
            model_config=cfg, n_stages=2, n_micro=4, micro_batch=4,
            seq_len=64, optimizer={"name": "adam", "lr": 1e-3},
            name=name, handoff=handoff,
        )

    pc = make("p2p", "bench-pipe-p2p")
    pc_ref = make("driver", "bench-pipe-ref")
    tr = PipelineTrainer(pc, bundle={"CPU": 1})
    tr_ref = PipelineTrainer(pc_ref, bundle={"CPU": 1})
    try:
        tr.start()
        tr_ref.start()
        local = LocalPipelineRunner(pc)
        warm = synthetic_batches(pc, 1, seed=99)
        tr.train(warm)      # compile all arms outside the timed window
        tr_ref.train(warm)
        local.train(warm)
        tok_step = pc.tokens_per_step()
        p2p_s, ref_s, local_s = [], [], []
        p2p_calls = ref_calls = 0
        all_equal = True
        for t in range(trials):
            batches = synthetic_batches(pc, steps, seed=100 + t)
            c0 = rpc_mod.CALLS
            t0 = time.perf_counter()
            lp = tr.train(batches)
            p2p_s.append(time.perf_counter() - t0)
            p2p_calls += rpc_mod.CALLS - c0
            c0 = rpc_mod.CALLS
            t0 = time.perf_counter()
            lr = tr_ref.train(batches)
            ref_s.append(time.perf_counter() - t0)
            ref_calls += rpc_mod.CALLS - c0
            t0 = time.perf_counter()
            ll = local.train(batches)
            local_s.append(time.perf_counter() - t0)
            all_equal = all_equal and (lp == ll) and (lr == ll)
        p2p_tps = tok_step * steps / (sum(p2p_s) / trials)
        ref_tps = tok_step * steps / (sum(ref_s) / trials)
        local_tps = tok_step * steps / (sum(local_s) / trials)
        micro_ops = tr.ideal_micro_ops(steps) * trials
        return {
            "pipeline_tokens_per_s": p2p_tps,
            "pipeline_driver_tokens_per_s": ref_tps,
            "single_gang_tokens_per_s": local_tps,
            "ratio": p2p_tps / local_tps,
            "ratio_driver": ref_tps / local_tps,
            "driver_rpcs_per_microop": p2p_calls / micro_ops,
            "driver_rpcs_per_microop_ref": ref_calls / micro_ops,
            "rpc_reduction": (
                ref_calls / p2p_calls if p2p_calls else float("inf")
            ),
            "loss_bitwise_equal": all_equal,
            "n_stages": pc.n_stages,
            "n_micro": pc.n_micro,
        }
    finally:
        tr.shutdown()
        tr_ref.shutdown()


def bench_pipeline_preemption(steps: int = 8, seed: int = 2026):
    """Tokens lost to a seeded mid-run preemption of a pipeline stage
    host: run the SAME seeded schedule clean and with
    ``ChaosController.preempt_node`` against the middle stage's node,
    and charge the wall-clock overhead at the clean run's token rate.
    Also reports duplicate micro-op executions (re-executed work after
    the migration; the 1F1B bubble is the acceptance bound) and pins
    zero reconstructions + bitwise loss equality across the two runs.
    """
    import ray_tpu
    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.common.faults import ChaosController
    from ray_tpu.core.runtime import get_runtime
    from ray_tpu.models import gpt2 as gpt2_mod
    from ray_tpu.train.pipeline import (
        PipelineConfig,
        PipelineTrainer,
        bubble_micro_ops,
        synthetic_batches,
    )

    cfg = gpt2_mod.GPTConfig.tiny(num_layers=3, max_seq_len=32)
    pc = PipelineConfig(
        model_config=cfg, n_stages=3, n_micro=4, micro_batch=2,
        seq_len=32, optimizer={"name": "adam", "lr": 1e-3},
        name="bench-preempt",
    )
    h = {"num_cpus": 0, "resources": {"h": 0.5}}
    v = {"num_cpus": 0, "resources": {"pre": 0.4}}
    opts = [[dict(h)], [dict(v)], [dict(h)]]  # middle stage on the victim

    def one_run(preempt: bool):
        cluster = Cluster(
            initialize_head=True, connect=True,
            head_node_args={"num_cpus": 4, "resources": {"h": 4.0}},
        )
        try:
            victim = cluster.add_node(num_cpus=1, resources={"pre": 1.0})
            cluster.wait_for_nodes(timeout=60)
            tr = PipelineTrainer(pc, stage_actor_options=opts)
            tr.start()
            batches = synthetic_batches(pc, steps, seed=7)
            tr.train(batches[:2])  # warm/compile outside the timed window
            # migration target up-front in BOTH arms, so the timed
            # window charges only the preemption itself, not node
            # provisioning
            cluster.add_node(num_cpus=1, resources={"pre": 1.0})
            cluster.wait_for_nodes(timeout=60)
            import threading

            losses: list = []
            errs: list = []

            def loop():
                try:
                    for x, y in batches[2:]:
                        losses.append(tr.run_step(x, y))
                except BaseException as e:  # noqa: BLE001
                    errs.append(e)

            t0 = time.perf_counter()
            th = threading.Thread(target=loop, daemon=True)
            th.start()
            if preempt:
                chaos = ChaosController(cluster, seed=seed)
                chaos.preempt_node(node=victim, deadline_s=20.0)
            th.join(timeout=600)
            elapsed = time.perf_counter() - t0
            try:
                if th.is_alive() or errs:
                    raise RuntimeError(f"pipeline run failed: {errs!r}")
                cnt = tr.counters()
                executed = sum(
                    c["executed"] for lanes in cnt for c in lanes
                )
                recon = get_runtime().reconstructions
                return losses, elapsed, executed, recon
            finally:
                # daemon thread: a wedged run cannot keep the bench
                # process alive, and the gang always tears down
                tr.shutdown()
        finally:
            ray_tpu.shutdown()
            cluster.shutdown()

    clean_losses, t_clean, exec_clean, _ = one_run(False)
    chaos_losses, t_chaos, exec_chaos, recon = one_run(True)
    timed_steps = steps - 2
    clean_tps = pc.tokens_per_step() * timed_steps / t_clean
    overhead_s = max(0.0, t_chaos - t_clean)
    return {
        "tokens_lost": overhead_s * clean_tps,
        "overhead_s": overhead_s,
        "clean_tokens_per_s": clean_tps,
        "dup_micro_ops": exec_chaos - exec_clean,
        "bubble_micro_ops": bubble_micro_ops(pc.n_stages),
        "reconstructions": recon,
        "loss_bitwise_equal": clean_losses == chaos_losses,
    }


def bench_podracer_throughput(
    trials: int = 3, updates_per_window: int = 6, device_ms: float = 40.0,
):
    """Podracer throughput plane vs the synchronous EnvRunnerGroup.sample
    loop, interleaved A/B windows on the SAME 2-runner CartPole config.

    Arm A (podracer): free-running fleet — per-runner fragments land as
    shm refs, the central learner actor batches them with staleness
    bounds, weights fan out over one broadcast_tree.  Arm B (sync): the
    gang loop — sample both runners (payload through the driver),
    update in-driver, sync_weights, repeat.  Windows alternate A/B per
    trial so host drift hits both arms equally; the podracer fleet is
    drained (paused) outside its windows so arm B is never contended.

    BOTH arms train through the same device-proxy learner: a real (CPU)
    IMPALA update plus a ``device_ms`` non-CPU wait standing in for the
    accelerator step the plane is built around (the paper's learner is
    a TPU; this CI box is one CPU core, where a CPU-bound learner would
    falsely serialize against env stepping and hide the overlap the
    architecture exists to exploit).  The podracer arm overlaps env
    stepping with the device-blocked update; the gang loop cannot.
    ``device_ms=0`` gives the pure-CPU-learner number.

    Also reports: trained (not just sampled) env-steps/s for both arms,
    a bit-reproducibility precheck (two seeded train=False fleets must
    emit identical fragment payloads per (runner, seq)), the
    fragment-staleness histogram over trained fragments, and the
    weight_broadcast_ms fp32-vs-int8 A/B on the idle fleet.

    Own cluster (5 single-CPU actors across both arms outlive the
    family cluster's budget); call after the family runtime shut down.
    """
    import functools

    import numpy as np

    import ray_tpu
    from ray_tpu.rllib.algorithm import build_module_config, probe_env_spaces
    from ray_tpu.rllib.env_runner import EnvRunnerGroup
    from ray_tpu.rllib.impala import (
        IMPALAConfig,
        IMPALALearner,
        impala_batch_from_fragments,
    )
    from ray_tpu.rllib.podracer import PodracerConfig, PodracerRunner

    class DeviceProxyLearner(IMPALALearner):
        """IMPALA learner whose update blocks ``device_ms`` without
        consuming host CPU — the accelerator-step proxy (weights still
        really change; only the wall profile of update() differs)."""

        def update(self, batch):
            stats = super().update(batch)
            time.sleep(device_ms / 1e3)
            return stats

    FRAG, N_RUNNERS, N_ENVS = 16, 2, 4
    config = (
        IMPALAConfig()
        .environment("CartPole-v1")
        .env_runners(
            num_env_runners=N_RUNNERS, num_envs_per_env_runner=N_ENVS,
            rollout_fragment_length=FRAG,
        )
    )
    mc = build_module_config(config, probe_env_spaces(config.env, None))
    factory = functools.partial(DeviceProxyLearner, config, mc)

    def make_group(seed):
        return EnvRunnerGroup(
            config.env, mc, num_runners=N_RUNNERS,
            num_envs_per_runner=N_ENVS, seed=seed,
        )

    ray_tpu.init(num_cpus=8, num_tpus=0)
    try:
        # -- bit-reproducibility precheck (acceptance pin) --------------
        streams = []
        for _ in range(2):
            g = make_group(17)
            pr = PodracerRunner(
                g, factory, impala_batch_from_fragments,
                PodracerConfig(rollout_fragment_length=FRAG),
                train=False, keep_fragment_refs=True,
            )
            try:
                pr.run(min_fragments=4)
                streams.append({
                    (i, m["seq"]): ray_tpu.get(ref, timeout=60.0)
                    for i, m, ref in pr.fragment_log
                })
            finally:
                pr.stop()
                g.stop()
        common = set(streams[0]) & set(streams[1])
        bit_repro = bool(common) and all(
            np.array_equal(streams[0][k][f], streams[1][k][f])
            for k in common for f in streams[0][k]
        )
        del streams

        # -- interleaved A/B windows ------------------------------------
        group_a = make_group(0)
        pr = PodracerRunner(
            group_a, factory, impala_batch_from_fragments,
            PodracerConfig(
                rollout_fragment_length=FRAG, batch_fragments=2,
                max_policy_lag=4, weight_sync_period=2,
            ),
        )
        group_b = make_group(1)
        learner_b = DeviceProxyLearner(config, mc)
        group_b.sync_weights(learner_b.get_weights())

        def sync_window():
            """updates_per_window iterations of the gang loop; returns
            env steps sampled."""
            steps = 0
            for _ in range(updates_per_window):
                frags = group_b.sample(FRAG)
                batch = impala_batch_from_fragments(frags)
                learner_b.update(batch)
                group_b.sync_weights(learner_b.get_weights())
                steps += FRAG * N_ENVS * len(frags)
            return steps

        # warm both arms outside the timed windows (jit compile, actor
        # spin-up, first collective rendezvous)
        pr.run(min_updates=1)
        pr.drain_in_flight()
        sync_window()

        a_rates, a_trained, b_rates = [], [], []
        for _ in range(trials):
            t0 = time.perf_counter()
            trained0 = pr.learner_stats()["env_steps_trained"]
            out = pr.run(min_updates=updates_per_window)
            dt = time.perf_counter() - t0
            a_rates.append(out["env_steps_sampled"] / dt)
            a_trained.append(
                (pr.learner_stats()["env_steps_trained"] - trained0) / dt
            )
            pr.drain_in_flight()  # pause the fleet: arm B runs alone
            t0 = time.perf_counter()
            steps = sync_window()
            b_rates.append(steps / (time.perf_counter() - t0))
        a_med = sorted(a_rates)[len(a_rates) // 2]
        at_med = sorted(a_trained)[len(a_trained) // 2]
        b_med = sorted(b_rates)[len(b_rates) // 2]

        # -- weight fan-out fp32 vs int8 on the idle fleet --------------
        fp32_ms, int8_ms = [], []
        for _ in range(3):
            fp32_ms.append(pr.broadcast_weights(None))
            int8_ms.append(pr.broadcast_weights("int8"))
        stats = pr.learner_stats()
        pr.stop()
        group_a.stop()
        group_b.stop()
        return {
            "env_steps_per_s": a_med,
            "trained_env_steps_per_s": at_med,
            "sync_env_steps_per_s": b_med,
            "ratio": a_med / b_med,
            "trained_ratio": at_med / b_med,
            "learner_device_ms": device_ms,
            "bit_reproducible": bit_repro,
            "staleness_hist": stats["staleness_hist"],
            "max_trained_lag": stats["max_trained_lag"],
            "dropped_stale": stats["dropped_stale"],
            "weight_broadcast_fp32_ms": sorted(fp32_ms)[1],
            "weight_broadcast_int8_ms": sorted(int8_ms)[1],
        }
    finally:
        ray_tpu.shutdown()


def bench_serve_rps(ray_tpu, service_ms=100.0, max_ongoing=4,
                    slo_ms=750.0, max_queue_depth=12,
                    steady_s=4.0, overload_s=5.0):
    """Traffic-plane serve bench: open-loop HTTP load through the full
    path (aiohttp proxy → admission → RequestScheduler → replica) at
    ~0.5× and 2× the deployment's saturation rate.

    The deployment has a FIXED service time (async sleep), so saturation
    is arithmetic, not a mood of the host: capacity = max_ongoing ×
    (1000 / service_ms) = 40 req/s per replica.  One replica, so the 2×
    offered load MUST shed ~half — the row reports p50/p99 of admitted
    (200) responses and the shed (503) rate.  The bounded queue
    (`max_queue_depth`) keeps the p99 of what IS admitted inside the SLO
    budget: depth × service_ms / max_ongoing ≈ 300 ms of queueing versus
    the 750 ms budget.  Open-loop arrivals (fixed schedule, no waiting
    for responses) — closed-loop clients would self-throttle at
    saturation and hide the overload entirely.  The rates are sized so
    the aiohttp plumbing itself (client + proxy sharing this box's two
    cores) is NOT the bottleneck — the 2-core sandbox sustains ~50
     200-responses/s with a p99 under 100 ms, so an 80 req/s offered
    load saturates the DEPLOYMENT (capacity 40) while the proxy stays
    comfortable; sheds are cheap (no replica work).
    """
    import asyncio

    from ray_tpu import serve
    from ray_tpu.serve import api as serve_api

    @serve.deployment(
        max_ongoing_requests=max_ongoing,
        traffic_config={
            "slo_ms": slo_ms,
            "max_queue_depth": max_queue_depth,
            "shed_retry_after_s": 0.5,
        },
    )
    class Fixed:
        async def __call__(self):
            await asyncio.sleep(service_ms / 1000.0)
            return "ok"

    serve.start()
    serve.run(Fixed.bind(), name="rps_bench", route_prefix="/rps")
    proxy = serve_api._get_or_create_proxy(18755)
    port = ray_tpu.get(proxy.start.remote(), timeout=60)
    url = f"http://127.0.0.1:{port}/rps"
    capacity = max_ongoing * 1000.0 / service_ms

    # the open-loop client lives in ray_tpu.soak.load now (the soak
    # plane drives the same schedule); uniform arrivals preserve A/B
    # against the pre-extraction serve_rps records
    from ray_tpu.soak import load as soak_load

    def drive(rate, duration):
        offsets = soak_load.arrival_offsets(
            rate, duration, process="uniform"
        )
        records = asyncio.run(soak_load.drive_http(url, offsets))
        s = soak_load.summarize(records, elapsed_s=duration)
        return {
            "offered_rps": round(rate, 1),
            "admitted_rps": s["admitted_rps"],
            "p50_ms": s["p50_ms"],
            "p99_ms": s["p99_ms"],
            "shed_rate": s["shed_rate"],
            "errors": s["errors"],
        }

    async def depth1(n=50):
        """Sequential single-request latency — the neutrality number
        (the traffic plane must not tax the unloaded path)."""
        import aiohttp

        lats = []
        async with aiohttp.ClientSession() as sess:
            for _ in range(n):
                t0 = time.perf_counter()
                async with sess.get(url) as r:
                    await r.read()
                lats.append(time.perf_counter() - t0)
        lats.sort()
        return round(lats[len(lats) // 2] * 1000.0, 2)

    try:
        steady = drive(capacity * 0.5, steady_s)
        overload = drive(capacity * 2.0, overload_s)
        d1 = asyncio.run(depth1())
        return {
            "capacity_rps": round(capacity, 1),
            "slo_ms": slo_ms,
            "service_ms": service_ms,
            "steady": steady,
            "overload": overload,
            "depth1_p50_ms": d1,
        }
    finally:
        try:
            serve.delete("rps_bench")
        except Exception:
            pass


def bench_soak(profile: str = "short", seed: int = 7):
    """Soak-plane rows: the deterministic acceptance soak + the
    spot-fleet ledger, both pure functions of the seed (run twice and
    diff the bytes — that IS the regression check).

    Profiles: ``short`` simulates the 30 s acceptance scenario
    (finishes in seconds — the slow-marked test tier runs this);
    ``full`` simulates a 180 s storm with a kill added, the
    BENCH.md-record shape.
    """
    from ray_tpu.soak import (
        acceptance_scenario,
        economics_rows,
        run_sim,
        run_spot_economics,
    )

    if profile == "short":
        scenario = acceptance_scenario(seed=seed, duration_s=30.0)
    else:
        import dataclasses as _dc

        from ray_tpu.soak import StormSpec

        base = acceptance_scenario(seed=seed, duration_s=180.0)
        scenario = _dc.replace(
            base,
            name="acceptance_full",
            storm=StormSpec(preempts=2, partitions=2, node_kills=1,
                            partition_duration_s=2.0),
        )
    # the full storm downs more nodes than the fleet holds — it only
    # makes sense with the provider's min_workers replacement live
    res = run_sim(scenario, replace_nodes=(profile != "short"))
    rows = list(res.scorecard.to_rows())
    rows += economics_rows(run_spot_economics(scenario))
    for r in rows:
        r.setdefault("profile", profile)
    return rows


def soak_main(argv):
    """``python bench.py --soak [--full]``: emit the soak rows and a
    final headline line (same contract as the main bench: the driver
    parses the LAST line)."""
    profile = "full" if "--full" in argv else "short"
    rows = []
    try:
        rows = bench_soak(profile=profile)
        for r in rows:
            r = dict(r)
            emit(r.pop("metric"), r.pop("value"), r.pop("unit"), **r)
    except Exception as e:  # noqa: BLE001
        emit("soak_availability", 0.0, "frac", error=repr(e),
             profile=profile)
    with _PRINT_LOCK:
        _FINISHED.set()
        head = dict(ROWS[0]) if ROWS else {"metric": "soak_availability",
                                           "value": 0.0, "unit": "frac"}
        head["rows"] = ROWS
        print(json.dumps(head), flush=True)


def _tpu_probe_platform(timeout_s: float = 120.0):
    """Probe the backend in a short-lived subprocess: "tpu", "cpu" (host
    simply has no TPU — retrying is futile), or None (probe hung,
    worth retrying).  A hang cannot be interrupted in-process, hence
    the subprocess."""
    import subprocess
    import sys

    try:
        probe = subprocess.run(
            [sys.executable, "-c",
             "import jax; print('PLATFORM', jax.devices()[0].platform)"],
            capture_output=True, text=True, timeout=timeout_s,
        )
        for line in probe.stdout.splitlines():
            if line.startswith("PLATFORM "):
                return line.split(" ", 1)[1].strip()
        return None
    except subprocess.TimeoutExpired:
        return None


def _tpu_probe(timeout_s: float = 120.0) -> bool:
    return _tpu_probe_platform(timeout_s) == "tpu"


def _bench_gpt2_cpu_smoke(timeout_s: float = 300.0):
    """CPU fallback row so the bench stays runnable anywhere."""
    import subprocess
    import sys

    code = (
        "import os; os.environ['JAX_PLATFORMS'] = 'cpu'; "
        "import jax; jax.config.update('jax_platforms', 'cpu'); "
        "import bench, json; "
        "print('@@' + json.dumps(bench.bench_gpt2(scan_unroll=1)))"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=timeout_s, cwd=os.path.dirname(os.path.abspath(__file__)),
    )
    for line in out.stdout.splitlines():
        if line.startswith("@@"):
            r = json.loads(line[2:])
            r["backend_unavailable"] = True
            return r
    raise RuntimeError(
        f"TPU backend wedged and CPU fallback failed: {out.stderr[-500:]}"
    )


def _bench_gpt2_guarded(timeout_s: float = 400.0, prefer: str = "both"):
    """GPT-2 bench in timeboxed SUBPROCESSES.  ``prefer``:

    - "rolled": rolled scan only (scan_unroll=1; known-fast compile,
      MFU ~0.36 measured) — the land-a-row-almost-surely choice
    - "unrolled": full unroll only (MFU ~0.44, compile can take minutes
      cold) — the upgrade pass
    - "both": unrolled on most of the budget, rolled as fallback

    Subprocesses because a hung jax init/compile cannot be interrupted
    in-process.  Callers are expected to have probed the backend."""
    import subprocess
    import sys

    if prefer == "rolled":
        attempts = [(1, timeout_s)]
    elif prefer == "unrolled":
        attempts = [(None, timeout_s)]
    else:
        attempts = [(None, timeout_s * 0.7), (1, max(120.0, timeout_s * 0.3))]

    last_err = None
    for unroll, budget in attempts:
        arg = "" if unroll is None else f"scan_unroll={unroll}"
        code = (
            "import bench, json; "
            f"print('@@' + json.dumps(bench.bench_gpt2({arg})))"
        )
        try:
            out = subprocess.run(
                [sys.executable, "-c", code],
                capture_output=True, text=True, timeout=budget,
                cwd=os.path.dirname(os.path.abspath(__file__)),
            )
            for line in out.stdout.splitlines():
                if line.startswith("@@"):
                    return json.loads(line[2:])
            last_err = RuntimeError(
                f"gpt2 bench subprocess (unroll={unroll}) produced no "
                f"result: {out.stderr[-500:]}"
            )
        except subprocess.TimeoutExpired as e:
            last_err = e
    raise RuntimeError(f"gpt2 bench failed attempts ({prefer}): {last_err!r}")


def _emit_gpt2_row(gpt2_stats, err=None):
    if gpt2_stats is not None:
        emit(
            "gpt2_124m_train_tokens_per_sec_per_chip"
            if gpt2_stats["on_tpu"]
            else "gpt2_tiny_train_tokens_per_sec_cpu_smoke",
            gpt2_stats["tokens_per_sec_per_chip"],
            "tokens/s/chip",
            device=gpt2_stats["device"],
            mfu=round(gpt2_stats["mfu"], 4) if gpt2_stats["mfu"] else None,
            step_ms=round(gpt2_stats["step_ms"], 2),
            scan_unroll=gpt2_stats.get("scan_unroll"),
        )
    else:
        emit("gpt2_124m_train_tokens_per_sec_per_chip", 0.0,
             "tokens/s/chip", error=repr(err))


def main():
    """Hard-budgeted bench run.

    The whole run fits inside RT_BENCH_TOTAL_BUDGET_S (default 540 s —
    r1/r2 finished well inside the driver's window; r3 died rc=124
    chasing a 1800 s TPU retry window).  Structure:

      0. watchdog armed: the final line ALWAYS prints, rc is ALWAYS 0
      1. quick TPU probe (subprocess, bounded)
      2. TPU up → rolled-scan GPT-2 first (fast compile ⇒ a real-chip
         row lands with near-certainty), unrolled upgrade only if the
         remaining budget allows (~10% more MFU, minutes of compile)
      3. probe failed / no TPU → CPU smoke row IMMEDIATELY (the gpt2
         row must exist no matter what happens later)
      4. control-plane family, each row emitted as it completes
      5. leftover budget → one bounded TPU retry
      6. final headline line (driver parses the LAST line)
    """
    total_budget = float(os.environ.get("RT_BENCH_TOTAL_BUDGET_S", "540"))
    t_start = time.monotonic()
    deadline = t_start + total_budget
    state: dict = {"gpt2": None}
    _start_watchdog(deadline, state)

    def remaining():
        return deadline - time.monotonic()

    # reserve for: control-plane family (~150 s incl. the two new
    # bandwidth rows) + serve traffic rows (~30 s) + cpu smoke (~120 s)
    # + final print slack
    FAMILY_RESERVE = 330.0

    gpt2_err = None
    plat = _tpu_probe_platform(timeout_s=min(90.0, max(20.0, remaining() / 6)))
    if plat == "tpu" and remaining() > FAMILY_RESERVE + 60:
        try:
            # rolled scan first: known-fast compile, MFU ~0.36 — lands a
            # real-chip row almost surely; unrolled upgrade comes later
            state["gpt2"] = _bench_gpt2_guarded(
                timeout_s=remaining() - FAMILY_RESERVE, prefer="rolled"
            )
            _emit_gpt2_row(state["gpt2"])
        except Exception as e:  # noqa: BLE001
            gpt2_err = e

    if state["gpt2"] is None:
        # no TPU row yet: the gpt2 row must exist even if everything
        # after this point wedges — CPU smoke now, TPU retry later
        try:
            state["gpt2"] = _bench_gpt2_cpu_smoke(
                timeout_s=min(300.0, max(60.0, remaining() - 180))
            )
            _emit_gpt2_row(state["gpt2"])
        except Exception as e:  # noqa: BLE001
            gpt2_err = gpt2_err or e
            _emit_gpt2_row(None, err=gpt2_err)

    # Control-plane family on a local cluster.
    import ray_tpu

    family = [
        ("actor_calls_sync_1_1", bench_actor_calls_sync, "calls/s"),
        ("actor_calls_async_1_1", bench_actor_calls_async, "calls/s"),
        ("actor_calls_async_n_n", bench_actor_calls_n_n, "calls/s"),
        ("tasks_sync_single_client", bench_tasks_sync, "tasks/s"),
        ("tasks_async_single_client", bench_tasks_async, "tasks/s"),
        ("taskplane_alloc_churn", bench_taskplane_alloc_churn, "allocs/call"),
        ("taskplane_alloc_churn_tasks", bench_taskplane_alloc_churn_tasks,
         "allocs/call"),
        ("put_gigabytes_per_s", bench_put_gigabytes, "GB/s"),
        ("multi_client_put_gigabytes_per_s", bench_multi_client_put, "GB/s"),
        ("get_calls_per_s", bench_get_calls, "gets/s"),
        ("placement_group_create_remove_per_s", bench_pg_churn, "PGs/s"),
    ]
    try:
        ray_tpu.init(num_cpus=max(4, (os.cpu_count() or 4)), num_tpus=0)
        try:
            for name, fn, unit in family:
                if remaining() < 30:
                    emit(name, 0.0, unit, error="budget exhausted")
                    continue
                try:
                    v = fn(ray_tpu)
                    emit(name, v, unit, baseline=BASELINES.get(name))
                except Exception as e:  # noqa: BLE001
                    emit(name, 0.0, unit, error=repr(e))
            # put matrix (data plane v2): size x clients x inline/
            # vectored — puts/s for round-trip-bound small sizes, GB/s
            # for memcpy-bound large ones
            if remaining() > 120:
                try:
                    m = bench_put_bandwidth_matrix(ray_tpu)
                    for name, v in m.items():
                        emit(
                            name, v,
                            "puts/s" if "per_s" in name
                            and "gb" not in name else "GB/s",
                        )
                except Exception as e:  # noqa: BLE001
                    emit("put_bandwidth_matrix", 0.0, "rows", error=repr(e))
            # broadcast row: seconds, lower = better, so vs_baseline is
            # inverted (reference seconds / ours); single-host shm vs the
            # reference's 50-node network broadcast — topology noted
            if remaining() > 60:
                try:
                    secs = bench_broadcast_1gib(ray_tpu)
                    emit(
                        "broadcast_1gib_seconds", secs, "s",
                        vs_baseline=round(BROADCAST_BASELINE_S / secs, 3),
                        note="single-host shm, 8 readers; reference: "
                             "50-node network broadcast",
                    )
                except Exception as e:  # noqa: BLE001
                    emit("broadcast_1gib_seconds", 0.0, "s", error=repr(e))
            # serve traffic plane: full proxy→scheduler→replica path at
            # 0.5× and 2× saturation; deterministic capacity (fixed
            # service time), so the overload row is a real shed test
            if remaining() > 60:
                try:
                    s = bench_serve_rps(ray_tpu)
                    for variant in ("steady", "overload"):
                        v = s[variant]
                        emit(
                            f"serve_rps_{variant}", v["admitted_rps"],
                            "req/s",
                            offered_rps=v["offered_rps"],
                            p50_ms=v["p50_ms"], p99_ms=v["p99_ms"],
                            shed_rate=v["shed_rate"],
                            errors=v["errors"],
                            capacity_rps=s["capacity_rps"],
                            slo_ms=s["slo_ms"],
                        )
                    emit(
                        "serve_http_depth1_p50_ms", s["depth1_p50_ms"],
                        "ms", service_ms=s["service_ms"],
                        note="sequential; includes the deployment's "
                             "fixed service time (service_ms)",
                    )
                except Exception as e:  # noqa: BLE001
                    emit("serve_rps_overload", 0.0, "req/s", error=repr(e))
            # fault recovery: time-to-first-result after an injected
            # worker-conn reset (task plane) and after a collective
            # member kill + reform — the robustness plane's quotable row
            if remaining() > 45:
                try:
                    fr = bench_fault_recovery(ray_tpu)
                    emit(
                        "fault_recovery_task_ms", fr["task_ms"], "ms",
                        note="first result after injected worker-conn "
                             "reset; max_retries=2, warm lease",
                    )
                    if fr["collective_ms"] is not None:
                        emit(
                            "fault_recovery_collective_ms",
                            fr["collective_ms"], "ms",
                            note="3-rank group: kill 1 member, reform "
                                 "to 2, first bit-exact allreduce",
                        )
                    else:
                        emit("fault_recovery_collective_ms", 0.0, "ms",
                             error=fr["collective_err"])
                except Exception as e:  # noqa: BLE001
                    emit("fault_recovery_task_ms", 0.0, "ms", error=repr(e))
            # MPMD pipeline: orchestration overhead vs the single-gang
            # baseline at equal chips, interleaved p2p/driver/local
            # arms, bitwise-loss cross-checked on both distributed arms
            # (full context in BENCH.md "MPMD pipeline")
            if remaining() > 120:
                try:
                    pg = bench_pipeline_gpt2(ray_tpu)
                    emit(
                        "pipeline_gpt2_tokens_per_s",
                        pg["pipeline_tokens_per_s"], "tokens/s",
                        driver_arm=round(
                            pg["pipeline_driver_tokens_per_s"], 1),
                        single_gang=round(
                            pg["single_gang_tokens_per_s"], 1),
                        ratio=round(pg["ratio"], 3),
                        ratio_driver=round(pg["ratio_driver"], 3),
                        loss_bitwise_equal=pg["loss_bitwise_equal"],
                        n_stages=pg["n_stages"],
                        note="1 CPU host: measures actor-call + "
                             "handoff overhead over identical math, "
                             "not parallel speedup; headline arm is "
                             "the p2p channel handoff",
                    )
                    emit(
                        "pipeline_driver_rpcs_per_microop",
                        pg["driver_rpcs_per_microop"], "rpcs",
                        driver_ref_arm=round(
                            pg["driver_rpcs_per_microop_ref"], 2),
                        reduction=round(pg["rpc_reduction"], 2),
                        note="outbound driver RPCs (core.rpc.CALLS "
                             "delta) per ideal micro-op; p2p ships no "
                             "data refs so only control acks remain",
                    )
                except Exception as e:  # noqa: BLE001
                    emit("pipeline_gpt2_tokens_per_s", 0.0, "tokens/s",
                         error=repr(e))
            # failure detection: phi-accrual vs fixed timeout under an
            # induced 2x load stall + a true partition — deterministic
            # seeded simulation through the production detector code
            try:
                fd = bench_failure_detection()
                emit(
                    "failure_detection_false_positives",
                    fd["false_positives_adaptive"], "deaths",
                    fixed_baseline=fd["false_positives_fixed"],
                    note="induced 2x load stall + 500 ms convoy gap; "
                         "fixed baseline timeout 400 ms",
                )
                emit(
                    "failure_detection_latency_ms",
                    fd["detect_ms_adaptive"], "ms",
                    fixed_baseline_ms=round(fd["detect_ms_fixed"], 1),
                    ratio_vs_fixed=round(fd["latency_ratio"], 2),
                    note="true partition -> confirmed death; adaptive "
                         "floor 600 ms / cap 1200 ms at 100 ms beats",
                )
            except Exception as e:  # noqa: BLE001
                emit("failure_detection_false_positives", 0.0, "deaths",
                     error=repr(e))
        finally:
            ray_tpu.shutdown()
    except Exception as e:  # noqa: BLE001
        emit("control_plane_family", 0.0, "rows", error=repr(e))

    # preemption recovery: graceful drain (notice → migrated → kill)
    # vs the reactive fault_recovery rows — blackout = kill → first
    # successful result.  Own 3-node cluster; runs after the family's
    # single-node runtime shut down.
    if remaining() > 90:
        try:
            pr = bench_preemption_recovery()
            emit(
                "preemption_recovery_object_blackout_ms",
                pr["object_blackout_ms"], "ms",
                drain_ms=round(pr["drain_ms"], 1),
                note="sole-copy object evacuated pre-kill; 0 "
                     "reconstructions (reactive path: lineage re-exec)",
            )
            emit(
                "preemption_recovery_actor_blackout_ms",
                pr["actor_blackout_ms"], "ms",
                note="checkpointable actor migrated with state pre-kill "
                     "(reactive fault_recovery_task: ~lease+spawn "
                     "after the kill)",
            )
            emit(
                "preemption_recovery_collective_blackout_ms",
                pr["collective_blackout_ms"], "ms",
                note="2-rank group proactively re-formed pre-kill; "
                     "first bit-exact allreduce after the kill",
            )
        except Exception as e:  # noqa: BLE001
            emit("preemption_recovery_object_blackout_ms", 0.0, "ms",
                 error=repr(e))

    # collectives v2 matrix: size x algorithm x wire dtype across a
    # real two-node wire plane + the overlap (exposed-comm) rows.
    # Own cluster; runs after the family's runtime shut down.
    if remaining() > 120:
        try:
            cm = bench_collective_matrix()
            for name, v in sorted(cm.items()):
                emit(name, v, "GB/s" if name.endswith("gbps") else "ms")
        except Exception as e:  # noqa: BLE001
            emit("collective_matrix", 0.0, "rows", error=repr(e))

    # tokens lost to a seeded mid-run stage-host preemption: the MPMD
    # pipeline's survival number (clean vs preempted run of the same
    # seeded schedule; own clusters, after the family runtime is down)
    if remaining() > 150:
        try:
            pp = bench_pipeline_preemption()
            emit(
                "tokens_lost_to_preemption", pp["tokens_lost"], "tokens",
                overhead_s=round(pp["overhead_s"], 2),
                clean_tokens_per_s=round(pp["clean_tokens_per_s"], 1),
                dup_micro_ops=pp["dup_micro_ops"],
                bubble_micro_ops=pp["bubble_micro_ops"],
                reconstructions=pp["reconstructions"],
                loss_bitwise_equal=pp["loss_bitwise_equal"],
                note="seeded preempt_node vs clean run, same schedule; "
                     "overhead charged at the clean token rate",
            )
        except Exception as e:  # noqa: BLE001
            emit("tokens_lost_to_preemption", 0.0, "tokens", error=repr(e))

    # podracer throughput plane: free-running env fleet + central
    # learner vs the synchronous gang loop, interleaved windows on the
    # same 2-runner config, plus the fp32/int8 weight fan-out A/B (own
    # cluster; full protocol in BENCH.md "Podracer throughput")
    if remaining() > 120:
        try:
            pt = bench_podracer_throughput()
            emit(
                "env_steps_per_s", pt["env_steps_per_s"], "steps/s",
                sync_env_steps_per_s=round(pt["sync_env_steps_per_s"], 1),
                ratio=round(pt["ratio"], 3),
                trained_env_steps_per_s=round(
                    pt["trained_env_steps_per_s"], 1
                ),
                trained_ratio=round(pt["trained_ratio"], 3),
                learner_device_ms=pt["learner_device_ms"],
                bit_reproducible=pt["bit_reproducible"],
                staleness_hist={
                    str(k): v for k, v in pt["staleness_hist"].items()
                },
                max_trained_lag=pt["max_trained_lag"],
                dropped_stale=pt["dropped_stale"],
                note="2 runners x 4 CartPole envs, fragment 16; sync "
                     "arm = EnvRunnerGroup.sample + update + "
                     "sync_weights per iteration; both arms train "
                     "through the same device-proxy learner (real CPU "
                     "update + learner_device_ms device-blocked wait "
                     "standing in for the accelerator step)",
            )
            emit(
                "weight_broadcast_ms", pt["weight_broadcast_fp32_ms"],
                "ms",
                int8_ms=round(pt["weight_broadcast_int8_ms"], 3),
                int8_speedup=round(
                    pt["weight_broadcast_fp32_ms"]
                    / pt["weight_broadcast_int8_ms"], 3,
                ),
                note="broadcast_tree over learner+2 runners, idle "
                     "fleet, median of 3; int8 = block-quantized "
                     "wire (~1/4 bytes), replicas bit-identical",
            )
        except Exception as e:  # noqa: BLE001
            emit("env_steps_per_s", 0.0, "steps/s", error=repr(e))

    # scheduler scale excerpt: 1k virtual nodes, lease-churn latency
    # (full tier: tests/test_scheduler_scale.py).  After the cluster
    # shut down — it needs the host's whole core.
    if remaining() > 150:
        try:
            s = bench_scheduler_scale()
            emit(
                "scheduler_1k_nodes_lease_churn", s["rate"], "leases/s",
                p50_ms=round(s["p50_ms"], 1), p95_ms=round(s["p95_ms"], 1),
                gcs_cpu_frac=s["gcs_cpu_frac"],
            )
        except Exception as e:  # noqa: BLE001
            emit("scheduler_1k_nodes_lease_churn", 0.0, "leases/s",
                 error=repr(e))

    # Leftover budget: upgrade/recover the TPU row.  Upgrade = unrolled
    # scan (~0.44 MFU vs rolled ~0.36); recover = the probe failed
    # earlier, try once more.  Both bounded by what's actually left.
    have_tpu_row = bool(state["gpt2"] and state["gpt2"].get("on_tpu"))
    want_retry = (plat != "cpu") and (
        not have_tpu_row or state["gpt2"].get("scan_unroll") == 1
    )
    if want_retry and remaining() > 150:
        plat2 = _tpu_probe_platform(timeout_s=min(60.0, remaining() / 4))
        if plat2 == "tpu" and remaining() > 120:
            try:
                better = _bench_gpt2_guarded(
                    timeout_s=remaining() - 30,
                    prefer="unrolled" if have_tpu_row else "both",
                )
                if better.get("on_tpu") and (
                    not have_tpu_row
                    or better["tokens_per_sec_per_chip"]
                    > state["gpt2"]["tokens_per_sec_per_chip"]
                ):
                    state["gpt2"] = better
                    _emit_gpt2_row(better)
            except Exception:  # noqa: BLE001
                pass  # the earlier row (tpu, smoke, or error) stands

    _print_final(state["gpt2"])


if __name__ == "__main__":
    import sys

    if "--soak" in sys.argv[1:]:
        soak_main(sys.argv[1:])
    else:
        main()
