"""Tests for the common substrate: ids, config, resources, serialization."""

import os
import pickle
import signal
import threading
import time

import numpy as np
import pytest

from ray_tpu.common import ids
from ray_tpu.common.config import cfg
from ray_tpu.common.resources import ResourceSet, validate_task_resources
from ray_tpu.common import serialization as ser


class TestIDs:
    def test_random_unique(self):
        a, b = ids.TaskID.random(), ids.TaskID.random()
        assert a != b
        assert len(a.binary()) == 16

    def test_kind_distinguishes(self):
        raw = os.urandom(16)
        assert ids.TaskID(raw) != ids.ActorID(raw)

    def test_object_id_derivation_deterministic(self):
        t = ids.TaskID.random()
        assert ids.ObjectID.for_task_return(t, 0) == ids.ObjectID.for_task_return(t, 0)
        assert ids.ObjectID.for_task_return(t, 0) != ids.ObjectID.for_task_return(t, 1)

    def test_hex_roundtrip(self):
        t = ids.NodeID.random()
        assert ids.NodeID.from_hex(t.hex()) == t

    def test_pickle_roundtrip(self):
        t = ids.ObjectID.random()
        assert pickle.loads(pickle.dumps(t)) == t

    def test_nil(self):
        assert ids.ActorID.nil().is_nil()
        assert not ids.ActorID.random().is_nil()


class TestConfig:
    def test_default(self):
        assert cfg.inline_object_max_bytes == 100 * 1024

    def test_env_override(self, monkeypatch):
        monkeypatch.setenv("RT_HEARTBEAT_INTERVAL_S", "2.5")
        cfg.reset()
        assert cfg.heartbeat_interval_s == 2.5
        cfg.reset()

    def test_unknown_flag_raises(self):
        with pytest.raises(AttributeError):
            cfg.not_a_flag


class TestResources:
    def test_covers(self):
        avail = ResourceSet({"CPU": 4, "TPU": 8})
        assert avail.covers(ResourceSet({"CPU": 1, "TPU": 4}))
        assert not avail.covers(ResourceSet({"CPU": 5}))
        assert not avail.covers(ResourceSet({"GPU": 1}))

    def test_fractional_exact(self):
        avail = ResourceSet({"CPU": 1})
        half = ResourceSet({"CPU": 0.5})
        rem = avail.subtract(half).subtract(half)
        assert rem.is_empty()

    def test_subtract_negative_raises(self):
        with pytest.raises(ValueError):
            ResourceSet({"CPU": 1}).subtract(ResourceSet({"CPU": 2}))

    def test_add(self):
        assert ResourceSet({"CPU": 1}).add(ResourceSet({"CPU": 2, "TPU": 1})).to_dict() == {
            "CPU": 3.0,
            "TPU": 1.0,
        }

    def test_validate_unit_instance(self):
        validate_task_resources({"TPU": 0.5})
        validate_task_resources({"TPU": 4})
        with pytest.raises(ValueError):
            validate_task_resources({"TPU": 2.5})

    def test_pickle(self):
        r = ResourceSet({"CPU": 1.5, "TPU": 2})
        assert pickle.loads(pickle.dumps(r)) == r


class TestSerialization:
    def test_roundtrip_simple(self):
        for obj in [42, "hello", {"a": [1, 2, (3, None)]}, b"raw"]:
            s = ser.serialize(obj)
            assert ser.deserialize(s.to_bytes()) == obj

    def test_numpy_out_of_band(self):
        arr = np.arange(1 << 16, dtype=np.float32)
        s = ser.serialize({"x": arr, "tag": 7})
        # big array must be out-of-band, not embedded in the metadata pickle
        assert len(s.meta) < 10_000
        assert sum(b.nbytes for b in s.buffers) >= arr.nbytes
        out = ser.deserialize(s.to_bytes())
        np.testing.assert_array_equal(out["x"], arr)
        assert out["tag"] == 7

    def test_lambda(self):
        f = lambda x: x * 3  # noqa: E731
        s = ser.serialize(f)
        assert ser.deserialize(s.to_bytes())(4) == 12

    def test_jax_array_to_numpy(self):
        import jax.numpy as jnp

        x = jnp.arange(100, dtype=jnp.float32) * 2
        s = ser.serialize([x, {"y": x}])
        out = ser.deserialize(s.to_bytes())
        assert isinstance(out[0], np.ndarray)
        np.testing.assert_array_equal(out[0], np.arange(100, dtype=np.float32) * 2)
        np.testing.assert_array_equal(out[1]["y"], out[0])

    def test_custom_reducer(self):
        class Weird:
            def __init__(self, v):
                self.v = v

        ctx = ser.SerializationContext()
        ctx.register_reducer(Weird, lambda w: (Weird, (w.v + 1,)))
        out = ctx.deserialize(ctx.serialize(Weird(1)).to_bytes())
        assert out.v == 2


class TestPhiAccrualDetector:
    """common/health.py: the adaptive failure detector's math contract
    (the cluster-level behavior lives in test_zz_partition.py)."""

    def _warm(self, interval=0.1, n=50, jitter=0.0, seed=0):
        import random

        from ray_tpu.common.health import PhiAccrualDetector

        rng = random.Random(seed)
        d = PhiAccrualDetector(min_std_frac=0.35, min_samples=5)
        t = 0.0
        for _ in range(n):
            t += interval * (1 + rng.uniform(-jitter, jitter))
            d.heartbeat(t)
        return d, t

    def test_phi_zero_at_arrival_and_monotonic_with_silence(self):
        d, t = self._warm(jitter=0.05)
        assert d.phi(t) == 0.0
        phis = [d.phi(t + s) for s in (0.1, 0.2, 0.4, 0.8, 1.6)]
        assert phis == sorted(phis)
        assert phis[-1] > 50  # long silence: unbounded suspicion

    def test_not_ready_before_min_samples(self):
        from ray_tpu.common.health import PhiAccrualDetector

        d = PhiAccrualDetector(min_samples=5)
        for i in range(4):
            d.heartbeat(i * 0.1)
        assert not d.ready()
        assert d.phi(10.0) == 0.0  # fixed-timeout fallback decides

    def test_regular_history_tolerates_2x_stall(self):
        """The false-positive mode the detector exists to remove: a
        metronome-regular history (std ~ 0) plus one 2x-late beat must
        NOT cross the death threshold (the std floor absorbs it)."""
        from ray_tpu.common.config import cfg

        d, t = self._warm(jitter=0.02)
        phi_2x = d.phi(t + 0.2)  # a 2x load stall
        assert phi_2x < cfg.health_phi_death
        # ...while a true partition's silence still explodes
        assert d.phi(t + 1.0) > cfg.health_phi_death

    def test_adapts_to_loaded_cadence(self):
        """Sustained 2x load (intervals double) becomes the new normal:
        the same absolute gap that was suspicious before is absorbed
        after the history adapts."""
        d, t = self._warm(interval=0.1, jitter=0.05)
        before = d.phi(t + 0.4)
        for _ in range(80):  # sustained 2x-slow heartbeats
            t += 0.2
            d.heartbeat(t)
        after = d.phi(t + 0.4)
        assert after < before

    def test_observer_stall_is_not_the_nodes_silence(self):
        """A worker opening a TPU chip freezes every process of the host
        for seconds (seen on v5e: 5-7 s).  The GCS and the raylet both
        lose that time; when they wake, the health loop sees ~8 s of
        silence — past the floor, phi through the roof — unless the
        time the observer itself lost is taken off (`excuse`)."""
        from ray_tpu.common.config import cfg
        from ray_tpu.common.health import death_confirmed

        floor = cfg.node_death_timeout_s * cfg.health_death_floor_frac
        d, t = self._warm(interval=1.0, jitter=0.02)
        woke = t + 1.0 + 7.0  # one interval, then a 7 s freeze
        assert death_confirmed(
            d.phi(woke), woke - d.last_heartbeat, cfg.health_phi_death,
            floor, cfg.node_death_timeout_s,
        )
        d.excuse(7.0, woke)
        assert d.phi(woke) < cfg.health_phi_suspect
        assert not death_confirmed(
            d.phi(woke), woke - d.last_heartbeat, cfg.health_phi_death,
            floor, cfg.node_death_timeout_s,
        )
        # never moved past the present: a beat that was already
        # processed before the loop woke stays in the past
        d.excuse(100.0, woke)
        assert d.last_heartbeat == woke

    def test_death_verdict_floor_and_cap(self):
        from ray_tpu.common.health import death_confirmed

        # phi says dead but silence is under the floor: NOT dead
        assert not death_confirmed(99.0, 0.4, 8.0, 1.0, 2.0)
        # phi + floor satisfied: dead
        assert death_confirmed(9.0, 1.2, 8.0, 1.0, 2.0)
        # silence past the cap: dead regardless of phi
        assert death_confirmed(0.0, 2.1, 8.0, 1.0, 2.0)
        # neither: alive
        assert not death_confirmed(3.0, 1.2, 8.0, 1.0, 2.0)


class TestTimeLimit:
    """tests/conftest.py:time_limit, the clock every test runs under
    (this one too: the fixture's handler and timer are what is found
    on entry and must be back on exit)."""

    def _state(self):
        return (
            signal.getsignal(signal.SIGALRM),
            signal.getitimer(signal.ITIMER_REAL)[0],
        )

    def test_body_past_its_limit_fails_by_name(self):
        from conftest import time_limit

        handler, remaining = self._state()
        t0 = time.monotonic()
        with pytest.raises(pytest.fail.Exception) as err:
            with time_limit(1, "tests/test_x.py::test_that_hangs"):
                threading.Event().wait(30)
        assert time.monotonic() - t0 < 5
        msg = str(err.value)
        assert msg.startswith(
            "tests/test_x.py::test_that_hangs ran past its limit of 1 s"
        ), msg[:200]
        # every thread's stack, this one's among them, parked in wait()
        assert "test_body_past_its_limit_fails_by_name" in msg
        assert "threading.py" in msg and "in wait" in msg
        handler_after, remaining_after = self._state()
        assert handler_after is handler
        assert remaining - 5 < remaining_after <= remaining

    def test_the_rest_of_a_module_that_hung_gets_the_short_limit(self):
        """A module-scoped cluster that hung one test hangs the next:
        they wait 30 s each for it, not 180."""
        import conftest

        hung = "tests/test_hung_module.py"
        assert conftest.limit_for(f"{hung}::test_b") == conftest.TEST_LIMIT_S
        with pytest.raises(pytest.fail.Exception):
            with conftest.time_limit(0.2, f"{hung}::TestA::test_a[case]"):
                threading.Event().wait(30)
        try:
            short = conftest.SUSPECT_MODULE_LIMIT_S
            assert conftest.limit_for(f"{hung}::test_b") == short
            assert conftest.limit_for(f"{hung}::test_c", 300) == short
            assert conftest.limit_for(f"{hung}::test_d", 20) == 20
            assert (
                conftest.limit_for("tests/test_sound_module.py::test_a")
                == conftest.TEST_LIMIT_S
            )
        finally:
            conftest._suspect_modules.discard(hung)

    def test_body_inside_its_limit_leaves_no_trace(self):
        from conftest import time_limit

        handler, remaining = self._state()
        with time_limit(30, "tests/test_x.py::test_that_returns"):
            assert signal.getsignal(signal.SIGALRM) is not handler
            assert 29 < signal.getitimer(signal.ITIMER_REAL)[0] <= 30
        handler_after, remaining_after = self._state()
        assert handler_after is handler
        assert remaining - 5 < remaining_after <= remaining
        time.sleep(0.05)  # nothing of the inner clock is left to fire
