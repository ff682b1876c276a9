"""Partition-tolerant health plane: adaptive detection + fencing.

Drives the phi-accrual failure detector (common/health.py), the
ALIVE -> SUSPECT -> DEAD state machine, node incarnation fencing, and
the network-partition chaos primitives (``ChaosController.partition``
over the faults.py link-cut registry) end to end:

- a transient partition shorter than the suspicion window costs only
  placement preference (SUSPECT), never a kill: zero node deaths, zero
  actor restarts, zero collective reforms;
- a hard partition confirms death, fences the node's incarnation, and
  — after the heal — every stale-incarnation RPC from the zombie
  raylet is rejected, the zombie purges itself (workers killed, object
  copies discarded), and a named actor provably has ONE live copy;
- the chaos log + link-cut log are seeded and replayable.

NOTE on the filename: sorts past the tier-1 870 s truncation window on
purpose (see test_zz_chaos.py) — multi-process partition tests are
slow.  The fast pure-math detector tests live in test_common.py inside
the window.
"""

import asyncio
import os
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu.cluster_utils import Cluster
from ray_tpu.common import faults
from ray_tpu.common.faults import ChaosController
from ray_tpu.common.ids import NodeID
from ray_tpu.core import rpc
from ray_tpu.core.runtime import get_runtime
from ray_tpu.util import collective as col

#: fast-detection config for every cluster in this file: 0.1 s
#: heartbeats, death confirmed between 1.0 s (floor) and 2.0 s (cap)
FAST_HEALTH_ENV = {
    "RT_HEARTBEAT_INTERVAL_S": "0.1",
    "RT_NODE_DEATH_TIMEOUT_S": "2.0",
}


@pytest.fixture(autouse=True)
def _fast_health_env():
    saved = {k: os.environ.get(k) for k in FAST_HEALTH_ENV}
    os.environ.update(FAST_HEALTH_ENV)
    yield
    for k, v in saved.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v
    faults.clear()
    faults.clear_links()
    os.environ.pop("RT_FAULTS", None)


def _health(node_id_hex: str) -> dict:
    rt = get_runtime()
    return rt._run(rt.gcs.call("node_health", {}))[node_id_hex]


def _warm_detector(node_id_hex: str, samples: int = 20,
                   timeout: float = 20.0) -> None:
    """Wait until the GCS has enough inter-heartbeat history for the
    adaptive verdict (before min_samples, only the fixed cap decides)."""
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        if _health(node_id_hex)["samples"] >= samples:
            return
        time.sleep(0.1)
    raise AssertionError(
        f"detector for {node_id_hex[:12]} never warmed: "
        f"{_health(node_id_hex)}"
    )


def _list_actor(actor_id_hex: str) -> dict:
    rt = get_runtime()
    for r in rt._run(rt.gcs.call("list_actors", {})):
        if r["actor_id"] == actor_id_hex:
            return r
    raise AssertionError(f"actor {actor_id_hex} not in list_actors")


def _rank_data(rank: int, n: int = 16384) -> np.ndarray:
    rng = np.random.RandomState(77 + rank)
    return rng.randint(-1024, 1024, size=n).astype(np.float32)


@ray_tpu.remote
class Member:
    """One collective rank that can also report its group's reform
    generation (the 'zero reforms' witness)."""

    def init(self, world, rank, group):
        col.init_collective_group(world, rank, group_name=group)
        return col.get_rank(group)

    def allreduce(self, arr, group):
        return col.allreduce(arr, group_name=group)

    def reform_gen(self, group):
        from ray_tpu.util.collective.collective import _manager

        gh = _manager().groups.get(group)
        return None if gh is None else gh.spec.reform_gen

    def poisoned(self, group):
        from ray_tpu.util.collective.collective import _manager

        gh = _manager().groups.get(group)
        return None if gh is None else (gh.failed is not None)


# ---------------------------------------------------------------------------
# Acceptance: transient partition -> SUSPECT and back, nothing killed
# ---------------------------------------------------------------------------


class TestTransientPartition:
    def test_transient_partition_no_kill(self):
        """A seeded partition shorter than the suspicion->death window:
        the node passes through SUSPECT and back — zero node deaths,
        zero actor restarts, zero collective reforms, actor state
        intact, post-heal allreduce bit-exact."""
        cluster = Cluster(initialize_head=True, connect=True,
                          head_node_args={"num_cpus": 2})
        try:
            victim = cluster.add_node(num_cpus=2, resources={"vic": 1.0})
            cluster.wait_for_nodes(timeout=60)

            @ray_tpu.remote(resources={"vic": 0.5}, max_restarts=2)
            class Counter:
                def __init__(self):
                    self.n = 0

                def bump(self):
                    self.n += 1
                    return self.n

            c = Counter.remote()
            assert ray_tpu.get(c.bump.remote(), timeout=60) == 1

            # a 2-rank collective group spanning head + victim, idle
            # during the partition
            m0 = Member.options(num_cpus=0.5).remote()
            m1 = Member.options(resources={"vic": 0.4}).remote()
            ray_tpu.get([m0.init.remote(2, 0, "tp"),
                         m1.init.remote(2, 1, "tp")], timeout=120)
            want = _rank_data(0) + _rank_data(1)
            out = ray_tpu.get(
                [m0.allreduce.remote(_rank_data(0), "tp"),
                 m1.allreduce.remote(_rank_data(1), "tp")], timeout=120,
            )
            np.testing.assert_array_equal(out[0], want)
            gen0 = ray_tpu.get(m0.reform_gen.remote("tp"), timeout=60)

            _warm_detector(victim.node_id)
            inc0 = _health(victim.node_id)["incarnation"]

            chaos = ChaosController(cluster, seed=42)
            chaos.partition(victim, "gcs", duration_s=0.6)

            saw_suspect = False
            deadline = time.monotonic() + 2.5
            while time.monotonic() < deadline:
                h = _health(victim.node_id)
                assert h["alive"], (
                    f"transient partition killed the node: {h} "
                    f"(chaos log {chaos.log})"
                )
                saw_suspect = saw_suspect or h["suspect"]
                time.sleep(0.05)
            assert saw_suspect, "node never entered SUSPECT"
            h = _health(victim.node_id)
            assert h["alive"] and not h["suspect"]
            assert h["incarnation"] == inc0, "node was fenced"

            # zero actor restarts, state intact (counter continues)
            row = _list_actor(c._actor_id.hex())
            assert row["state"] == "ALIVE"
            assert row["restarts_used"] == 0
            assert ray_tpu.get(c.bump.remote(), timeout=60) == 2

            # zero collective reforms: same generation, not poisoned,
            # post-heal allreduce still bit-exact
            assert ray_tpu.get(m0.reform_gen.remote("tp"),
                               timeout=60) == gen0
            assert ray_tpu.get(m0.poisoned.remote("tp"),
                               timeout=60) is False
            out = ray_tpu.get(
                [m0.allreduce.remote(_rank_data(0), "tp"),
                 m1.allreduce.remote(_rank_data(1), "tp")], timeout=120,
            )
            np.testing.assert_array_equal(out[0], want)
            np.testing.assert_array_equal(out[1], want)
        finally:
            ray_tpu.shutdown()
            cluster.shutdown()

    def test_suspect_node_deprioritized_for_new_leases(self):
        """While SUSPECT, the scheduler places new work on healthy
        nodes when they can take it — the suspect node is the last
        resort, not an outage."""
        cluster = Cluster(initialize_head=True, connect=True,
                          head_node_args={"num_cpus": 4})
        try:
            victim = cluster.add_node(num_cpus=4)
            cluster.wait_for_nodes(timeout=60)
            _warm_detector(victim.node_id)

            chaos = ChaosController(cluster, seed=1)
            chaos.partition(victim, "gcs", duration_s=1.2)
            # wait for suspicion
            t0 = time.monotonic()
            while not _health(victim.node_id)["suspect"]:
                assert time.monotonic() - t0 < 2.0, "never suspected"
                time.sleep(0.05)

            @ray_tpu.remote(num_cpus=1)
            def where():
                return get_runtime().node_id

            # every placement while suspect prefers the healthy head
            spots = ray_tpu.get([where.remote() for _ in range(3)],
                                timeout=60)
            head = cluster.head_node.node_id
            assert all(s == head for s in spots), (
                f"lease(s) landed on the suspect node: {spots}"
            )
        finally:
            ray_tpu.shutdown()
            cluster.shutdown()


# ---------------------------------------------------------------------------
# Acceptance: hard partition -> fence at death, zombie rejected on heal
# ---------------------------------------------------------------------------


class TestHardPartitionFence:
    def test_hard_partition_fences_and_zombie_rejected(self):
        """The full split-brain closure: a partitioned node is declared
        dead (incarnation fenced), its named actor restarts elsewhere;
        after the heal the zombie raylet's stale-incarnation RPCs are
        rejected with FencedError, it purges (workers killed — the old
        worker process is provably dead, so the named actor never has
        two live copies) and re-joins as a fresh incarnation."""
        cluster = Cluster(initialize_head=True, connect=True,
                          head_node_args={"num_cpus": 2,
                                          "resources": {"pin": 1.0}})
        try:
            @ray_tpu.remote(resources={"pin": 1.0})
            class Blocker:
                def ok(self):
                    return True

            blocker = Blocker.remote()
            assert ray_tpu.get(blocker.ok.remote(), timeout=60)

            victim = cluster.add_node(num_cpus=1, resources={"pin": 1.0})
            cluster.wait_for_nodes(timeout=60)

            @ray_tpu.remote(resources={"pin": 1.0}, max_restarts=1,
                            name="counted")
            class Counted:
                def __init__(self):
                    self.n = 0

                def where(self):
                    self.n += 1
                    return (get_runtime().node_id, self.n)

            c = Counted.remote()
            node0, _ = ray_tpu.get(c.where.remote(), timeout=60)
            assert node0 == victim.node_id, "actor not on the victim"
            rt = get_runtime()
            old_addr = rt._run(rt.gcs.call(
                "get_actor", {"actor_id": c._actor_id.binary()}
            ))["worker_addr"]

            _warm_detector(victim.node_id)
            chaos = ChaosController(cluster, seed=3)
            chaos.partition(victim, "gcs")
            chaos.partition(victim, cluster.head_node)

            # confirmed death inside the floor..cap band (1.0 .. 2.0 s
            # at this config) — phi confirms well before the fixed cap
            t0 = time.monotonic()
            while _health(victim.node_id)["alive"]:
                assert time.monotonic() - t0 < 10, "death never confirmed"
                time.sleep(0.05)

            # replacement: free head capacity -> restart lands there
            ray_tpu.kill(blocker)
            node1, n1 = ray_tpu.get(c.where.remote(), timeout=60)
            assert node1 == cluster.head_node.node_id
            assert n1 == 1  # fresh (hook-less) restart

            # heal: the zombie's next heartbeat is fenced; it purges
            # and re-registers as a NEW incarnation
            chaos.heal()
            t0 = time.monotonic()
            while True:
                h = _health(victim.node_id)
                if h["alive"] and h["incarnation"] >= 3:
                    break
                assert time.monotonic() - t0 < 15, (
                    f"zombie never re-joined fresh: {h}"
                )
                time.sleep(0.1)

            # regression pin: stale-incarnation RPCs are rejected
            async def stale_probe():
                conn = await rpc.connect(cluster.address, name="zombie")
                try:
                    await conn.call("heartbeat", {
                        "node_id": NodeID.from_hex(
                            victim.node_id).binary(),
                        "incarnation": 1,
                    }, timeout=10)
                    return None
                except rpc.RemoteCallError as e:
                    return type(e.remote_exception).__name__
                finally:
                    await conn.close()

            assert asyncio.run(stale_probe()) == "FencedError"

            # the fence killed the zombie's workers: the OLD worker
            # process is dead — the named actor cannot execute there
            async def dial_old():
                try:
                    conn = await rpc.connect(old_addr, name="old",
                                             timeout=2.0)
                    await conn.close()
                    return True
                except Exception:
                    return False

            assert asyncio.run(dial_old()) is False, (
                "zombie worker still accepting connections after fence"
            )

            # exactly one live copy serves
            node2, n2 = ray_tpu.get(c.where.remote(), timeout=60)
            assert node2 == node1 and n2 == 2
        finally:
            ray_tpu.shutdown()
            cluster.shutdown()


# ---------------------------------------------------------------------------
# Fencing at the rpc level (no cluster: fake raylet against a GCS)
# ---------------------------------------------------------------------------


class TestIncarnationRpcFencing:
    def test_stale_incarnation_rpcs_rejected(self):
        """Unit-level fencing contract: a fresh registration bumps the
        incarnation; heartbeats/announces/registrations claiming the
        old one get FencedError."""
        from ray_tpu.core import node as node_mod

        sd = node_mod.default_session_dir()
        proc, addr = node_mod.start_gcs(sd)
        nid = NodeID.random()

        async def main():
            conn = await rpc.connect(addr, name="fake-raylet")
            probe = await rpc.connect(addr, name="probe")
            reg = {
                "node_id": nid.binary(), "address": "127.0.0.1:9",
                "resources": {"CPU": 1}, "labels": {},
                "incarnation": None,
            }
            r1 = await conn.call("register_node", dict(reg))
            assert r1["incarnation"] == 1
            # same-life reconnect keeps the incarnation
            r1b = await conn.call(
                "register_node", dict(reg, incarnation=1)
            )
            assert r1b["incarnation"] == 1
            # a fresh life bumps it
            r2 = await conn.call("register_node", dict(reg))
            assert r2["incarnation"] == 2

            async def expect_fenced(method, payload):
                try:
                    await conn.call(method, payload, timeout=10)
                except rpc.RemoteCallError as e:
                    return type(e.remote_exception).__name__
                return None

            assert await expect_fenced("heartbeat", {
                "node_id": nid.binary(), "incarnation": 1,
            }) == "FencedError"
            assert await expect_fenced("add_object_location", {
                "object_id": b"o" * 20, "node_id": nid.binary(),
                "incarnation": 1, "size": 8,
            }) == "FencedError"
            assert await expect_fenced("register_node", dict(
                reg, incarnation=1,
            )) == "FencedError"
            # the current life keeps working
            assert await conn.call("heartbeat", {
                "node_id": nid.binary(), "incarnation": 2,
            }, timeout=10) is True
            # node_health reports the surviving incarnation
            h = (await probe.call("node_health", {}))[nid.hex()]
            assert h["incarnation"] == 2
            await conn.close()
            await probe.close()

        try:
            asyncio.run(main())
        finally:
            proc.terminate()
            proc.wait(timeout=10)


# ---------------------------------------------------------------------------
# Interactions: partition during drain / during a collective op
# ---------------------------------------------------------------------------


class TestPartitionInteractions:
    def test_partition_during_drain_falls_back_to_hard_death(self):
        """A partition landing mid-drain starves the evacuation pulls:
        the drain must fail within its deadline and fall back to the
        hard node-death path — never wedge the cluster."""
        cluster = Cluster(initialize_head=True, connect=True,
                          head_node_args={"num_cpus": 2})
        try:
            victim = cluster.add_node(num_cpus=1, resources={"vic": 1.0})
            cluster.wait_for_nodes(timeout=60)

            @ray_tpu.remote(resources={"vic": 0.5})
            def big():
                return np.arange(200_000, dtype=np.int64)

            @ray_tpu.remote(resources={"vic": 0.5})
            def marker():
                return True

            big.remote()
            assert ray_tpu.get(marker.remote(), timeout=120) is True
            _warm_detector(victim.node_id)

            rt = get_runtime()
            rt._run(rt.gcs.call("drain_node", {
                "node_id": victim.node_id, "reason": "idle",
                "deadline_s": 4.0,
            }))
            chaos = ChaosController(cluster, seed=9)
            chaos.partition(victim, "gcs")
            chaos.partition(victim, cluster.head_node)

            t0 = time.monotonic()
            while True:
                st = rt._run(rt.gcs.call(
                    "get_drain_status", {"node_id": victim.node_id}
                ))
                if st.get("state") in ("failed", "dead"):
                    break
                assert time.monotonic() - t0 < 20, (
                    f"drain wedged under partition: {st}"
                )
                time.sleep(0.2)
            # the cluster still works: fresh tasks run on the survivor
            chaos.heal()

            @ray_tpu.remote(num_cpus=1)
            def alive():
                return "ok"

            assert ray_tpu.get(alive.remote(), timeout=60) == "ok"
        finally:
            ray_tpu.shutdown()
            cluster.shutdown()

    def test_collective_op_started_during_partition_is_rebuildable(self):
        """An allreduce initiated while its peer link is cut times out
        (chunks are not retransmitted — partition semantics), poisons
        the group with the documented error, and destroy+re-init on the
        healed network works bit-exactly.  The poison here is CONFIRMED
        (op timeout), not suspicion-driven."""
        os.environ["RT_COLLECTIVE_OP_TIMEOUT_S"] = "4.0"
        cluster = Cluster(initialize_head=True, connect=True,
                          head_node_args={"num_cpus": 2})
        try:
            victim = cluster.add_node(num_cpus=2, resources={"vic": 1.0})
            cluster.wait_for_nodes(timeout=60)
            m0 = Member.options(num_cpus=0.5).remote()
            m1 = Member.options(resources={"vic": 0.4}).remote()
            ray_tpu.get([m0.init.remote(2, 0, "pc"),
                         m1.init.remote(2, 1, "pc")], timeout=120)
            want = _rank_data(0) + _rank_data(1)
            out = ray_tpu.get(
                [m0.allreduce.remote(_rank_data(0), "pc"),
                 m1.allreduce.remote(_rank_data(1), "pc")], timeout=120,
            )
            np.testing.assert_array_equal(out[0], want)

            chaos = ChaosController(cluster, seed=5)
            chaos.partition(victim, cluster.head_node, duration_s=1.5)
            refs = [m0.allreduce.remote(_rank_data(0), "pc"),
                    m1.allreduce.remote(_rank_data(1), "pc")]
            with pytest.raises(Exception):
                ray_tpu.get(refs, timeout=120)

            # rebuild on the healed network
            time.sleep(0.5)
            ray_tpu.get([m0.init.remote(2, 0, "pc2"),
                         m1.init.remote(2, 1, "pc2")], timeout=120)
            out = ray_tpu.get(
                [m0.allreduce.remote(_rank_data(0), "pc2"),
                 m1.allreduce.remote(_rank_data(1), "pc2")], timeout=120,
            )
            np.testing.assert_array_equal(out[0], want)
            np.testing.assert_array_equal(out[1], want)
        finally:
            os.environ.pop("RT_COLLECTIVE_OP_TIMEOUT_S", None)
            ray_tpu.shutdown()
            cluster.shutdown()


# ---------------------------------------------------------------------------
# Serve router: suspect replicas are penalized, never dropped
# ---------------------------------------------------------------------------


class _FakeReplica:
    def __init__(self, hexid):
        self._hex = hexid
        self._actor_id = self

    def hex(self):
        return self._hex

    def __hash__(self):
        return hash(self._hex)

    def __eq__(self, other):
        return isinstance(other, _FakeReplica) and other._hex == self._hex


class TestRouterSuspectPenalty:
    def _router(self, replicas, suspect):
        from ray_tpu.serve.handle import Router

        r = Router(controller=None, app_name="a", deployment_name="d")
        r._last_refresh = time.monotonic() + 3600  # skip live refresh
        r._replicas = replicas
        r._suspect_ids = set(suspect)
        return r

    def test_pow2_avoids_suspect_while_healthy_exist(self):
        a, b, s = (_FakeReplica("aa"), _FakeReplica("bb"),
                   _FakeReplica("ss"))
        r = self._router([a, b, s], {"ss"})
        picks = {r.pick()._hex for _ in range(64)}
        assert "ss" not in picks
        assert picks == {"aa", "bb"}

    def test_all_suspect_still_serves(self):
        s1, s2 = _FakeReplica("s1"), _FakeReplica("s2")
        r = self._router([s1, s2], {"s1", "s2"})
        picks = {r.pick()._hex for _ in range(32)}
        assert picks <= {"s1", "s2"} and picks


# ---------------------------------------------------------------------------
# Determinism: chaos + link logs are replayable
# ---------------------------------------------------------------------------


class TestChaosLogDeterminism:
    def test_partition_schedule_is_seed_deterministic(self):
        """Two controllers with the same seed over the same cluster
        produce identical event logs (modulo timestamps) for a
        seeded-random partition/heal schedule, and the driver-side
        link-cut log replays the same cut/heal sequence."""
        cluster = Cluster(initialize_head=True, connect=True,
                          head_node_args={"num_cpus": 1})
        try:
            cluster.add_node(num_cpus=1)
            cluster.add_node(num_cpus=1)
            cluster.wait_for_nodes(timeout=60)

            def run_schedule(seed):
                faults.clear_links()
                chaos = ChaosController(cluster, seed=seed)
                for _ in range(4):
                    victim = chaos._pick_node()
                    dur = round(chaos.rng.uniform(0.05, 0.2), 3)
                    chaos.partition(victim, "gcs", duration_s=dur)
                    chaos.heal(victim, "gcs")
                def without_ts(log):
                    return [
                        {k: v for k, v in e.items() if k != "ts"}
                        for e in log
                    ]

                return without_ts(chaos.log), without_ts(faults.link_log())

            e1, l1 = run_schedule(1234)
            e2, l2 = run_schedule(1234)
            assert e1 == e2, "chaos log diverged across identical seeds"
            assert l1 == l2, "link-cut log diverged"
            e3, _ = run_schedule(99)
            assert e3 != e1, "seed has no effect on victim choice"
        finally:
            ray_tpu.shutdown()
            cluster.shutdown()


# ---------------------------------------------------------------------------
# Soak: randomized partition/heal against a live cluster (slow)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_partition_heal_soak():
    """Standing split-brain regression net: seeded random short/long
    partitions against a 3-node cluster with a named actor.  After
    every round the cluster must converge — every raylet either
    recovered (same incarnation) or was fenced and re-joined fresh —
    and the actor must keep serving from exactly one live worker.  The
    replayable chaos + link logs are attached on failure."""
    cluster = Cluster(initialize_head=True, connect=True,
                      head_node_args={"num_cpus": 2,
                                      "resources": {"pin": 1.0}})
    chaos = None
    try:
        n1 = cluster.add_node(num_cpus=1)
        n2 = cluster.add_node(num_cpus=1)
        cluster.wait_for_nodes(timeout=60)

        @ray_tpu.remote(resources={"pin": 0.5}, max_restarts=-1,
                        name="soak")
        class Soak:
            def __init__(self):
                self.n = 0

            def bump(self):
                self.n += 1
                return (get_runtime().node_id, self.n)

        s = Soak.remote()
        ray_tpu.get(s.bump.remote(), timeout=60)
        for n in (n1, n2):
            _warm_detector(n.node_id)

        chaos = ChaosController(cluster, seed=2026)
        rounds = 6
        for i in range(rounds):
            victim = chaos.rng.choice([n1, n2])
            dur = chaos.rng.choice([0.4, 0.4, 3.0])  # mostly transient
            chaos.partition(victim, "gcs", duration_s=dur)
            time.sleep(dur + 0.5)
            # convergence: the victim must come back alive (possibly as
            # a fresh incarnation) within the recovery window
            t0 = time.monotonic()
            while True:
                h = _health(victim.node_id)
                if h["alive"] and not h["suspect"]:
                    break
                assert time.monotonic() - t0 < 20, (
                    f"round {i}: node never converged: {h}\n"
                    f"chaos log: {chaos.log}\n"
                    f"link log: {faults.link_log()}"
                )
                time.sleep(0.2)
            # the actor keeps serving from one live worker
            node, _cnt = ray_tpu.get(s.bump.remote(), timeout=60)
            assert node == cluster.head_node.node_id, (
                f"round {i}: actor moved off its pinned node: {node}\n"
                f"chaos log: {chaos.log}"
            )
        # the whole schedule is recorded and replayable
        assert sum(1 for e in chaos.log if e["event"] == "partition") == rounds
    finally:
        ray_tpu.shutdown()
        cluster.shutdown()
