"""Multi-node scheduling, object transfer, and node-failure paths via the
in-process Cluster harness (ray: python/ray/cluster_utils.py:135 analogue;
test areas of ray: python/ray/tests/test_multi_node*.py).
"""

import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu.cluster_utils import Cluster


@pytest.fixture(scope="module")
def cluster():
    c = Cluster(initialize_head=True, head_node_args={"num_cpus": 2})
    c.add_node(num_cpus=2, resources={"side": 2.0})
    c.connect()
    c.wait_for_nodes()
    yield c
    ray_tpu.shutdown()
    c.shutdown()


class TestMultiNode:
    def test_cluster_resources(self, cluster):
        assert ray_tpu.cluster_resources()["CPU"] == 4.0

    def test_tasks_use_both_nodes(self, cluster):
        @ray_tpu.remote(num_cpus=0)
        class Arrivals:
            def __init__(self):
                self.n = 0

            def arrive(self):
                self.n += 1

            def count(self):
                return self.n

        @ray_tpu.remote
        def where(arrivals):
            # hold the CPU until all four run at once: with a sleep in
            # its place, a host loaded enough to start one node's
            # workers 2 s after the other's runs all four on one node,
            # two after two
            ray_tpu.get(arrivals.arrive.remote(), timeout=60)
            deadline = time.monotonic() + 60
            while ray_tpu.get(arrivals.count.remote(), timeout=60) < 4:
                assert time.monotonic() < deadline, "never four at once"
                time.sleep(0.05)
            return ray_tpu.get_runtime_context().node_id

        # 4 concurrent 1-CPU tasks need both 2-CPU nodes
        arrivals = Arrivals.remote()
        refs = [where.remote(arrivals) for _ in range(4)]
        nodes = set(ray_tpu.get(refs, timeout=120))
        assert len(nodes) == 2

    def test_a_task_queued_behind_busy_leases_asks_for_its_own(self, cluster):
        """What failed the test above in whole runs (`never four at
        once`): on a loaded host the driver's fourth ``.remote()`` can
        come after the first three leases were granted, and the lease
        pump counted those three, all busy, as capacity — so the fourth
        task asked for no lease and waited for one of theirs, with a CPU
        free.  Here the straggler is made, not hoped for."""
        @ray_tpu.remote(num_cpus=0)
        class Arrivals:
            def __init__(self):
                self.n = 0

            def arrive(self):
                self.n += 1

            def count(self):
                return self.n

        @ray_tpu.remote
        def hold(arrivals):
            ray_tpu.get(arrivals.arrive.remote(), timeout=60)
            deadline = time.monotonic() + 30
            while ray_tpu.get(arrivals.count.remote(), timeout=60) < 4:
                assert time.monotonic() < deadline, "the fourth never ran"
                time.sleep(0.05)
            return True

        arrivals = Arrivals.remote()
        refs = [hold.remote(arrivals) for _ in range(3)]
        deadline = time.monotonic() + 60
        while ray_tpu.get(arrivals.count.remote(), timeout=60) < 3:
            assert time.monotonic() < deadline, "three never ran"
            time.sleep(0.05)
        refs.append(hold.remote(arrivals))  # three leases busy, one CPU free
        assert ray_tpu.get(refs, timeout=120) == [True] * 4

    def test_a_lease_cannot_take_a_worker_spawned_for_another(self):
        """A fresh worker sat in the idle pool from its ``worker_ready``
        until the lease that spawned it woke from its 10 ms poll, and a
        lease arriving in between was handed the same worker, whose two
        leases' tasks then ran one behind the other."""
        import asyncio

        from ray_tpu.common.ids import WorkerID
        from ray_tpu.core import raylet as raylet_mod

        class Proc:
            poll = staticmethod(lambda: None)

        class Conn:
            closed = False

            def __init__(self):
                self.peer_info = {}

            async def call(self, method, payload):
                return True

        r = raylet_mod.Raylet.__new__(raylet_mod.Raylet)
        r.draining = r._fencing = r._closing = False
        r._idle_by_env, r.workers, spawned = {}, {}, []

        def spawn(**kw):
            w = raylet_mod.WorkerEntry(worker_id=WorkerID.random(), proc=Proc())
            r.workers[w.worker_id] = w
            spawned.append(w)
            return w

        r._spawn_worker = spawn

        def lease(n):
            return asyncio.ensure_future(r.rpc_lease_worker(
                None, {"lease_id": n, "resources": {"CPU": 1}}))

        async def ready(w, addr):
            await r.rpc_worker_ready(
                Conn(), {"worker_id": w.worker_id.binary(), "address": addr})

        async def scenario():
            first = lease(1)
            await asyncio.sleep(0)  # spawned its worker, now polls for it
            await ready(spawned[0], "w:1")
            second = lease(2)  # arrives before lease 1 wakes
            await asyncio.sleep(0)
            assert len(spawned) == 2, "lease 2 took lease 1's worker"
            await ready(spawned[1], "w:2")
            got = await asyncio.wait_for(asyncio.gather(first, second), 5)
            assert [g["worker_addr"] for g in got] == ["w:1", "w:2"]
            # returned, a worker is pooled for the next lease
            await r.rpc_release_worker(
                None, {"worker_id": spawned[0].worker_id.binary()})
            third = await asyncio.wait_for(lease(3), 5)
            assert third["worker_addr"] == "w:1" and len(spawned) == 2

        asyncio.run(scenario())

    def test_object_transfer_across_nodes(self, cluster):
        @ray_tpu.remote(resources={"side": 1})
        def produce():
            return np.arange(1 << 18, dtype=np.float32)

        @ray_tpu.remote(num_cpus=1)
        def consume(arr):
            return float(arr.sum())

        # producer pinned to the side node; consumer may run anywhere —
        # the value must travel through the store/transfer path
        ref = produce.remote()
        total = ray_tpu.get(consume.remote(ref), timeout=120)
        assert total == float(np.arange(1 << 18, dtype=np.float32).sum())

    def test_custom_resource_placement(self, cluster):
        @ray_tpu.remote(resources={"side": 1})
        def on_side():
            return ray_tpu.get_runtime_context().node_id

        @ray_tpu.remote(num_cpus=1)
        def anywhere():
            return ray_tpu.get_runtime_context().node_id

        side_node = ray_tpu.get(on_side.remote(), timeout=60)
        nodes = ray_tpu.nodes()
        by_id = {n["node_id"]: n for n in nodes}
        assert by_id[side_node]["resources_total"].get("side") == 2.0


class TestNodeFailure:
    def test_node_death_detected_and_actor_restarts(self, cluster):
        doomed = cluster.add_node(num_cpus=2, resources={"doomed": 1.0})
        cluster.wait_for_nodes()

        @ray_tpu.remote
        class Pinned:
            def node(self):
                return ray_tpu.get_runtime_context().node_id

        # pin to the doomed node via its custom resource, allow restart
        a = Pinned.options(
            resources={"doomed": 0.5}, max_restarts=1, max_task_retries=-1
        ).remote()
        first = ray_tpu.get(a.node.remote(), timeout=60)
        assert first == doomed.node_id

        cluster.remove_node(doomed)
        # the actor's resource demand is now infeasible -> it stays
        # RESTARTING; what we require is that the node death is seen
        deadline = time.time() + 30
        while time.time() < deadline:
            alive = [n for n in ray_tpu.nodes() if n["alive"]]
            if len(alive) == 2:
                break
            time.sleep(0.2)
        assert len([n for n in ray_tpu.nodes() if n["alive"]]) == 2

    def test_unpinned_actor_restarts_on_survivor(self, cluster):
        doomed = cluster.add_node(num_cpus=2, resources={"spot2": 1.0})
        cluster.wait_for_nodes()

        @ray_tpu.remote
        class Roamer:
            def node(self):
                return ray_tpu.get_runtime_context().node_id

        # node_affinity soft=False pins creation; after death the restart
        # uses the same strategy — use plain CPU demand instead so the
        # restart can land on a survivor
        from ray_tpu.util import NodeAffinitySchedulingStrategy

        a = Roamer.options(
            num_cpus=1,
            max_restarts=2,
            max_task_retries=-1,
            scheduling_strategy=NodeAffinitySchedulingStrategy(
                node_id=doomed.node_id, soft=True
            ),
        ).remote()
        first = ray_tpu.get(a.node.remote(), timeout=60)
        assert first == doomed.node_id
        cluster.remove_node(doomed)
        second = ray_tpu.get(a.node.remote(), timeout=90)
        assert second != doomed.node_id

    def test_store_file_cleanup_on_remove(self, cluster):
        import os

        n = cluster.add_node(num_cpus=1)
        cluster.wait_for_nodes()
        assert os.path.exists(n.store_path)
        cluster.remove_node(n)
        # generous window: SIGTERM→close tears down workers serially and
        # CI hosts can be single-core
        deadline = time.time() + 30
        while time.time() < deadline and os.path.exists(n.store_path):
            time.sleep(0.2)
        assert not os.path.exists(n.store_path)
