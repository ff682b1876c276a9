"""Distributed refcounting, automatic object GC, and lineage reconstruction.

Mirrors the reference's reference-counting and object-recovery test areas
(ray: python/ray/tests/test_reference_counting.py,
test_object_reconstruction.py) — the invariants, not the protocol: here the
GCS tracks a holder set per object (worker processes, stored-object parents,
actor creation specs) and frees cluster-wide when it empties; lost objects
re-execute their producing task from owner-held lineage
(ray: src/ray/core_worker/reference_count.h:61, object_recovery_manager.h:41).
"""

import gc
import threading
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu.core.runtime import get_runtime


@pytest.fixture(scope="module")
def cluster():
    ray_tpu.init(num_cpus=4, num_tpus=0)
    yield
    ray_tpu.shutdown()


def _wait_for(pred, timeout=20.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(0.2)
    raise TimeoutError(f"never reached: {msg}")


class TestAutoFree:
    def test_put_release_frees_store(self, cluster):
        """Dropping the last ref to a put object frees its shm copy — a
        loop of puts shows bounded store usage (VERDICT r1 done-criterion)."""
        rt = get_runtime()
        base = rt.store.stats()["used"]
        chunk = 4 * 1024 * 1024
        for _ in range(50):  # 200 MB total through a store that keeps ~0
            ref = ray_tpu.put(np.zeros(chunk, np.uint8))
            del ref
        gc.collect()
        _wait_for(
            lambda: rt.store.stats()["used"] - base < 3 * chunk,
            msg="store usage bounded after refs dropped",
        )

    def test_live_ref_is_not_freed(self, cluster):
        ref = ray_tpu.put(np.arange(1000))
        time.sleep(1.5)  # flush + free-grace windows
        out = ray_tpu.get(ref, timeout=30)
        assert out[999] == 999

    def test_inline_results_released_from_memory_store(self, cluster):
        @ray_tpu.remote
        def tiny(i):
            return i

        rt = get_runtime()
        refs = [tiny.remote(i) for i in range(50)]
        assert ray_tpu.get(refs, timeout=60) == list(range(50))
        oids = [r.object_id.binary() for r in refs]
        del refs
        gc.collect()
        _wait_for(
            lambda: not any(oid in rt.memory_store for oid in oids),
            msg="inline results evicted from memory store",
        )

    def test_nested_ref_kept_alive_by_parent(self, cluster):
        """A stored object pins the refs serialized inside it: dropping
        every direct ref to the child must not free it while the parent
        lives (borrowing collapsed to GCS object→object edges)."""
        child = ray_tpu.put(np.full(300_000, 7, np.int64))  # big → shm only
        parent = ray_tpu.put({"inner": child})
        del child
        gc.collect()
        time.sleep(1.5)  # would be freed by now if the edge were missing
        inner = ray_tpu.get(parent, timeout=30)["inner"]
        assert ray_tpu.get(inner, timeout=30)[0] == 7

    def test_task_arg_held_while_in_flight(self, cluster):
        """The caller may drop its ref right after submit; the in-flight
        task still resolves the argument."""

        @ray_tpu.remote
        def consume(arr):
            time.sleep(0.5)
            return int(arr.sum())

        big = ray_tpu.put(np.ones(200_000, np.int64))
        out_ref = consume.remote(big)
        del big
        gc.collect()
        assert ray_tpu.get(out_ref, timeout=60) == 200_000


class TestLineageReconstruction:
    def test_lost_object_reexecutes_task(self, cluster):
        """Delete the only copy out from under the driver (simulating a
        lost node's store) — get() re-runs the producing task."""

        @ray_tpu.remote(max_retries=2)
        def produce():
            return np.full(100_000, 3, np.int64)  # > inline cutoff → shm

        ref = produce.remote()
        first = ray_tpu.get(ref, timeout=60)
        assert first[0] == 3
        rt = get_runtime()
        oid = ref.object_id.binary()
        # destroy the only copy: local shm delete + GCS directory wipe
        rt.store.delete(oid)
        rt._run(rt.gcs.call("free_objects", {"object_ids": [oid]}))
        again = ray_tpu.get(ref, timeout=120)
        assert again[0] == 3 and again.shape == first.shape

    def test_reconstruction_recovers_dependencies(self, cluster):
        """A lost object whose producing task consumed another lost object
        recovers the whole chain."""

        @ray_tpu.remote(max_retries=2)
        def stage1():
            return np.full(100_000, 5, np.int64)

        @ray_tpu.remote(max_retries=2)
        def stage2(x):
            return x * 2

        r1 = stage1.remote()
        r2 = stage2.remote(r1)
        assert ray_tpu.get(r2, timeout=60)[0] == 10
        rt = get_runtime()
        for r in (r1, r2):
            oid = r.object_id.binary()
            rt.store.delete(oid)
            rt._run(rt.gcs.call("free_objects", {"object_ids": [oid]}))
        assert ray_tpu.get(r2, timeout=120)[0] == 10


def _abandoned_generator(rt):
    """A streaming task's generator dropped mid-stream; gone means the
    runtime forgot the stream."""

    @ray_tpu.remote
    def count():
        for i in range(10_000):
            yield i

    gen = count.remote()
    assert ray_tpu.get(next(gen), timeout=60) == 0
    tid = gen.task_id
    return gen, lambda: tid not in rt._streams, 1.0


def _dropped_serve_stream(rt):
    """A serve stream dropped mid-iteration; gone means the router no
    longer counts a request in flight on the replica."""
    from ray_tpu import serve

    @serve.deployment
    class Inf:
        def forever(self):
            i = 0
            while True:
                yield i
                i += 1

    h = serve.run(Inf.bind(), name="dropped_stream", route_prefix=None)
    gen = h.options(method_name="forever", stream=True).remote()
    assert next(gen) == 0
    router = gen._router
    assert sum(router._inflight.values()) == 1
    return gen, lambda: sum(router._inflight.values()) == 0, 1.0


def _unfinished_dag(rt):
    """A compiled DAG nobody tore down; gone means its channels left
    /dev/shm, which ``teardown(1.0)`` does after waiting up to its
    second for the actors' loops."""
    import os

    from ray_tpu.dag import InputNode

    @ray_tpu.remote
    class Adder:
        def add(self, x):
            return x + 1

    a = Adder.remote()
    with InputNode() as inp:
        out = a.add.bind(inp)
    dag = out.experimental_compile()
    assert dag.execute(1).get(timeout=60) == 2
    names = list(dag._all_channel_names)
    assert names and all(os.path.exists(f"/dev/shm/{n}") for n in names)
    return dag, lambda: not any(
        os.path.exists(f"/dev/shm/{n}") for n in names
    ), 3.0


class TestFinalisersOnlyEnqueue:
    """The collector runs a finaliser on whatever thread allocated last,
    inside whatever that thread holds — the driver's io loop, under
    ``_ref_lock``, included.  So a finaliser says what died
    (``core/runtime.finalized``) and the loop does the work later."""

    @pytest.mark.limit(20)
    def test_a_ref_collected_inside_the_flush_leaves_the_loop_running(
        self, cluster, monkeypatch
    ):
        @ray_tpu.remote
        def one():
            return 1

        @ray_tpu.remote
        def slow():
            time.sleep(1.0)
            return 2

        rt = get_runtime()
        gc.collect()
        gc.disable()
        try:
            ref = one.remote()
            assert ray_tpu.get(ref, timeout=10) == 1
            oid = ref.object_id.binary()
            assert oid in rt.memory_store
            cycle = [ref]
            cycle.append(cycle)
            del ref, cycle  # only the cyclic collector finds it now

            # the flush re-arms itself under _ref_lock when an add has to
            # be looked at again (an in-flight task's return): make THAT
            # allocation the one the collector runs in
            real = rt._loop.call_soon_threadsafe
            collected = []

            def collecting(*args):
                if (
                    not collected
                    and threading.current_thread() is rt._thread
                    and rt._ref_lock.locked()
                ):
                    collected.append(True)
                    gc.collect()
                return real(*args)

            monkeypatch.setattr(
                rt._loop, "call_soon_threadsafe", collecting
            )
            pending = slow.remote()
            _wait_for(lambda: collected, timeout=5,
                      msg="a collection inside the flush")
            assert ray_tpu.get(one.remote(), timeout=10) == 1
            _wait_for(lambda: oid not in rt.memory_store, timeout=5,
                      msg="the collected ref's value released")
            assert ray_tpu.get(pending, timeout=10) == 2
        finally:
            gc.enable()

    @pytest.mark.limit(60)
    @pytest.mark.parametrize(
        "make",
        [_abandoned_generator, _dropped_serve_stream, _unfinished_dag],
    )
    def test_collected_on_the_io_loop_itself(self, cluster, make):
        """The work a finaliser asks for waits for the io loop (a
        cancel, a get, a wait): collected ON the loop it must neither
        wait for itself nor hold the loop while it runs."""
        rt = get_runtime()
        gc.collect()
        gc.freeze()  # the loop's collection walks what this test made, no more
        try:
            obj, gone, within = make(rt)
            assert not gone()
            cycle = [obj]
            cycle.append(cycle)
            del obj, cycle
            took = []

            def collect():
                t0 = time.monotonic()
                gc.collect()
                took.append(time.monotonic() - t0)

            rt._loop.call_soon_threadsafe(collect)
            _wait_for(lambda: took, timeout=10, msg="the collection")
            assert ray_tpu.get(ray_tpu.put(7), timeout=10) == 7
            deadline = time.monotonic() + within
            while not gone() and time.monotonic() < deadline:
                time.sleep(0.02)
            assert gone(), f"the finaliser's work was not done in {within} s"
            assert took[0] < 0.5, (
                f"the finaliser held the io loop for {took[0]:.2f} s"
            )
        finally:
            gc.unfreeze()
            if make is _dropped_serve_stream:
                from ray_tpu import serve

                serve.shutdown()
