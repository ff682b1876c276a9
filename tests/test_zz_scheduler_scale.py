"""Scheduler envelope at 2,000 nodes: the storms too long for tier-1.

The same machinery as tests/test_scheduler_scale.py (a real GCS
process, stub raylets on one asyncio loop, ray_tpu/util/sched_bench.py)
at the reference's published node count (release/benchmarks/README.md:
5-13).  Both are ``slow``: the smaller one takes 922 s alone (901 of
them one wait: its docstring) and was 820-926 s of every tier-1 run,
whose other 1,100 tests take 380 s on six workers (CHANGES.md, PR 27).
What they cover that tier-1 keeps at smoke size:
test_scheduler_scale.py::test_smoke_64_nodes_5k_queued_backlog[hold-48]
(held backlog, partial drain, dead-driver abandonment) and
::test_1k_nodes_100k_queued_20k_actors_1k_pgs (backlog drain, actor and
placement-group storms at 1,000 nodes).  BENCH.md's envelope rows:
``python -m pytest tests/test_zz_scheduler_scale.py -m slow -s``, with
``RT_SCALE_TIER3=1`` for the 1M-queued one.
"""

import asyncio
import os
import time

import pytest

from ray_tpu.core import node as node_mod


@pytest.mark.slow
@pytest.mark.limit(1800)  # 922 s alone on 8 idle cores (PR 27)
def test_tier3_scaled_2k_nodes_100k_queued_10k_actors(tmp_path, monkeypatch):
    """Tier 3 at a tenth of its backlog (VERDICT next #8: the 2k-node
    envelope claim was re-proven only behind RT_SCALE_TIER3): the full
    tier-3 machinery — 2,000 stub nodes, a held beyond-capacity
    backlog, dead-driver abandonment, an actor FSM storm.  Sized for
    ~5 minutes when it was written (fleet 16s + backlog 179s + actor
    storm 60s); measured alone by PR 27: 922 s, of which 901 s are
    queued_backlog_hold waiting out its 900 s cap for leases that the
    GCS never releases (ROADMAP D8) and 20 s are work.  Full tier 3
    (1M queued / 40k actors) stays behind RT_SCALE_TIER3."""
    from ray_tpu.util import sched_bench as sb

    # 2000 stub heartbeat loops share this test's one asyncio loop with
    # the request storm; failure detection is not the envelope under
    # test, and queued entries must HOLD rather than expire into client
    # retries for the backlog to be genuinely ~170k deep on the server
    monkeypatch.setenv("RT_NODE_DEATH_TIMEOUT_S", "3600")
    monkeypatch.setenv("RT_SCHED_MAX_PENDING_LEASE_S", "7200")
    proc, address = node_mod.start_gcs(str(tmp_path))
    try:
        async def main():
            out = {}
            stubs, hb = await sb.start_fleet(address, 2000)
            clients = await sb.connect_clients(address, 8)
            (out["submit_wall"], out["peak_depth"], out["drain_wall"],
             out["abandon_wall"]) = await sb.queued_backlog_hold(
                address, clients, 100_000, drain_n=10_000
            )
            # backlog_hold closed its clients (the dead-driver abandon
            # path); the actor storm gets fresh connections
            clients = await sb.connect_clients(address, 8)
            reg_wall, kill_wall = await sb.actor_lifecycle_storm(
                clients, 10_000, concurrency=512
            )
            out["actor_reg_rate"] = 10_000 / reg_wall
            out["actor_kill_rate"] = 10_000 / kill_wall
            t0 = time.perf_counter()
            st = await clients[0].call("scheduler_stats", {}, timeout=60)
            out["probe_ms"] = (time.perf_counter() - t0) * 1e3
            out["nodes_alive"] = st["nodes_alive"]
            out["pending"] = st["pending_leases"]
            await sb.close_clients(clients)
            await sb.stop_fleet(stubs, hb)
            return out

        out = asyncio.run(main())
        print(
            f"\n2k-node scaled tier: 100k tasks submitted in "
            f"{out['submit_wall']:.0f}s, peak queue depth "
            f"{out['peak_depth']}, 10k drained in "
            f"{out['drain_wall']:.0f}s, 90k abandoned in "
            f"{out['abandon_wall']:.0f}s; 10k actors reg "
            f"{out['actor_reg_rate']:.0f}/s kill "
            f"{out['actor_kill_rate']:.0f}/s; post-storm stats probe "
            f"{out['probe_ms']:.0f}ms, {out['nodes_alive']} nodes alive"
        )
        assert out["nodes_alive"] == 2000
        # 2k nodes x 16 CPU = 32k slots; the held backlog must really
        # have been beyond-capacity deep on the server (~68k observed)
        assert out["peak_depth"] > 60_000, out["peak_depth"]
        assert out["probe_ms"] < 5_000
        assert out["actor_reg_rate"] > 150
        assert out["pending"] == 0, "abandoned backlog not compacted"
    finally:
        proc.terminate()
        proc.wait(timeout=10)


@pytest.mark.slow
@pytest.mark.limit(3600)  # not measured since BENCH.md's 10-20 min
@pytest.mark.skipif(
    os.environ.get("RT_SCALE_TIER3") != "1",
    reason="tier 3 (reference's full published envelope: 2,000 nodes / "
    "40k actors / 1M queued) runs ~10-20 min on a 1-core host; "
    "set RT_SCALE_TIER3=1 — numbers recorded in BENCH.md",
)
def test_2k_nodes_1m_queued_40k_actors(tmp_path, monkeypatch):
    """Reference envelope parity: 2,000 nodes, 1M queued tasks held +
    partially drained, 40k actors through the FSM
    (release/benchmarks/README.md:5-13)."""
    from ray_tpu.util import sched_bench as sb

    monkeypatch.setenv("RT_NODE_DEATH_TIMEOUT_S", "3600")
    # queued entries must HOLD (not expire into client retries) for the
    # backlog to be genuinely 1M deep on the server
    monkeypatch.setenv("RT_SCHED_MAX_PENDING_LEASE_S", "7200")
    proc, address = node_mod.start_gcs(str(tmp_path))
    try:
        meter = sb.GcsCpuMeter(proc.pid)

        async def main():
            out = {}
            stubs, hb = await sb.start_fleet(address, 2000)
            clients = await sb.connect_clients(address, 8)

            t = time.perf_counter()
            lats, wall = await sb.lease_churn(
                clients, 20_000, concurrency=512
            )
            out["churn"] = {
                "p50_ms": lats[len(lats) // 2] * 1e3,
                "p95_ms": lats[int(len(lats) * 0.95)] * 1e3,
                "rate": 20_000 / wall,
            }

            (out["submit_wall"], out["peak_depth"], out["drain_wall"],
             out["abandon_wall"]) = await sb.queued_backlog_hold(
                address, clients, 1_000_000, drain_n=50_000
            )
            # backlog_hold closed its clients (the dead-driver abandon
            # path); the actor storm gets fresh connections
            clients = await sb.connect_clients(address, 8)

            reg_wall, kill_wall = await sb.actor_lifecycle_storm(
                clients, 40_000, concurrency=512
            )
            out["actor_reg_rate"] = 40_000 / reg_wall
            out["actor_kill_rate"] = 40_000 / kill_wall

            # the GCS must still be interactive after the storm
            t0 = time.perf_counter()
            st = await clients[0].call("scheduler_stats", {}, timeout=60)
            out["probe_ms"] = (time.perf_counter() - t0) * 1e3
            out["nodes_alive"] = st["nodes_alive"]

            await sb.close_clients(clients)
            await sb.stop_fleet(stubs, hb)
            return out

        out = asyncio.run(main())
        cpu = meter.sample()
        print(
            f"\n2k-node tier: churn p50={out['churn']['p50_ms']:.1f}ms "
            f"p95={out['churn']['p95_ms']:.1f}ms "
            f"rate={out['churn']['rate']:.0f}/s; "
            f"1M tasks submitted in {out['submit_wall']:.0f}s, "
            f"peak queue depth {out['peak_depth']}, "
            f"50k drained in {out['drain_wall']:.0f}s, "
            f"950k abandoned in {out['abandon_wall']:.0f}s; "
            f"40k actors reg {out['actor_reg_rate']:.0f}/s "
            f"kill {out['actor_kill_rate']:.0f}/s; "
            f"post-storm stats probe {out['probe_ms']:.0f}ms, "
            f"{out['nodes_alive']} nodes alive; "
            f"GCS cpu {cpu['cpu_s']}s/{cpu['wall_s']}s "
            f"({cpu['cpu_frac']:.0%})"
        )
        assert out["nodes_alive"] == 2000
        assert out["peak_depth"] > 900_000, out["peak_depth"]
        assert out["probe_ms"] < 5_000
        assert out["actor_reg_rate"] > 200
    finally:
        proc.terminate()
        proc.wait(timeout=10)
