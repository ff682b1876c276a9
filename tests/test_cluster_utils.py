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
