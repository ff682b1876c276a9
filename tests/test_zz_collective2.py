"""Collectives v2: algorithm selection, block-quantized wire codecs,
reform config persistence, and the launch/wait progress engine.

The contract under test, in rough order of importance:

1. the fp32 DEFAULT path is bit-for-bit the PR 2 ring — pinned against
   an in-process simulation of the exact ring schedule at every world
   size in the suite (adversarial non-integer fp32 data, so any
   accumulation-order change shows);
2. codec round-trip error stays under each codec's published per-block
   bound on adversarial distributions (outlier blocks, zeros, ragged
   sizes), and non-finite input is rejected loudly;
3. quantized collectives leave ALL ranks bit-identical to each other
   (the replicated-consumer invariant);
4. reform_collective_group carries the full GroupOptions (wire dtype,
   algorithm, chunk size) through shrink AND replacement reforms —
   a migration never silently changes the wire format;
5. launch()/wait() runs the op on the runtime loop while the caller
   thread computes.

NOTE on the filename: ``test_zz_`` sorts past the tier-1 truncation
window on purpose (multi-actor gang tests are slow).
"""

import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu.util import collective as col
from ray_tpu.util.collective import CollectiveError, GroupOptions, ReduceOp
from ray_tpu.util.collective import algorithms, quantize
from ray_tpu.util.collective.rpc_backend import _segment_bounds


# ---------------------------------------------------------------------------
# codecs (no cluster)
# ---------------------------------------------------------------------------

def _adversarial_arrays(rng):
    """Distributions chosen to stress per-block scaling: outlier blocks
    next to tiny-valued blocks, zeros, constants, ragged tails."""
    spike = rng.standard_normal(8192).astype(np.float32)
    spike[2048:2060] *= 1e4  # one outlier block must not wreck others
    tiny = (rng.standard_normal(4096) * 1e-20).astype(np.float32)
    return [
        rng.standard_normal(5000).astype(np.float32),
        spike,
        tiny,
        np.zeros(1000, np.float32),
        np.full(777, -3.25, np.float32),
        rng.standard_normal(2048 * 3).astype(np.float32),  # exact blocks
        rng.standard_normal(2048 * 3 + 17).astype(np.float32),  # ragged
        np.array([], np.float32),
        np.array([42.0], np.float32),
    ]


class TestQuantizeCodecs:
    @pytest.mark.parametrize("name", ["int8", "bf16"])
    def test_round_trip_error_within_bound(self, name):
        rng = np.random.default_rng(2026)
        codec = quantize.get_codec(name)
        for arr in _adversarial_arrays(rng):
            wire = codec.encode(arr)
            assert wire.dtype == np.uint8
            assert wire.nbytes == codec.encoded_nbytes(arr.size)
            out = codec.decode(wire, arr.size)
            assert out.dtype == np.float32 and out.size == arr.size
            err = float(np.abs(out - arr).max()) if arr.size else 0.0
            assert err <= codec.error_bound(arr), (
                f"{name}: round-trip err {err} above bound "
                f"{codec.error_bound(arr)} (size {arr.size})"
            )

    def test_int8_outlier_block_does_not_poison_neighbors(self):
        """Per-BLOCK scales are the whole point (EQuARX): a 1e4 outlier
        in one block must leave other blocks' precision intact."""
        rng = np.random.default_rng(7)
        arr = rng.standard_normal(4096).astype(np.float32)
        arr[3000] = 1e4  # second block only
        codec = quantize.get_codec("int8", block=2048)
        out = codec.decode(codec.encode(arr), arr.size)
        first_block_err = np.abs(out[:2048] - arr[:2048]).max()
        # first block's bound is its OWN absmax/254, not the outlier's
        assert first_block_err <= np.abs(arr[:2048]).max() / 254.0 * 1.001

    @pytest.mark.parametrize("name", ["int8", "bf16"])
    def test_deterministic_encode(self, name):
        rng = np.random.default_rng(11)
        arr = rng.standard_normal(3000).astype(np.float32)
        codec = quantize.get_codec(name)
        assert np.array_equal(codec.encode(arr), codec.encode(arr))

    @pytest.mark.parametrize("name", ["int8", "bf16"])
    @pytest.mark.parametrize("bad", [np.inf, -np.inf, np.nan])
    def test_non_finite_rejected(self, name, bad):
        codec = quantize.get_codec(name)
        arr = np.ones(100, np.float32)
        arr[17] = bad
        with pytest.raises(CollectiveError, match="non-finite"):
            codec.encode(arr)

    @pytest.mark.parametrize("name", ["int8", "bf16"])
    def test_non_f32_rejected(self, name):
        codec = quantize.get_codec(name)
        with pytest.raises(CollectiveError, match="float32"):
            codec.encode(np.arange(10, dtype=np.int64))

    def test_bf16_exact_on_representable_values(self):
        """Small integers are exactly representable in bf16: the codec
        must be lossless there (weight-broadcast-of-integer-valued
        data stays bit-exact on the quantized path too)."""
        arr = np.arange(-128, 128, dtype=np.float32)
        codec = quantize.get_codec("bf16")
        assert np.array_equal(codec.decode(codec.encode(arr), arr.size), arr)

    def test_wire_size_savings(self):
        int8 = quantize.get_codec("int8", block=2048)
        bf16 = quantize.get_codec("bf16")
        n = 1 << 20
        assert int8.encoded_nbytes(n) < 4 * n / 3.8  # ~3.9x smaller
        assert bf16.encoded_nbytes(n) == 2 * n  # exactly 2x
        assert quantize.get_codec(None) is None
        assert quantize.get_codec("fp32") is None
        with pytest.raises(CollectiveError, match="unknown wire_dtype"):
            quantize.get_codec("fp8")


# ---------------------------------------------------------------------------
# selection table + topology (no cluster)
# ---------------------------------------------------------------------------

class TestAlgorithmSelection:
    def test_defaults_are_bit_compat(self):
        o = GroupOptions()
        # reductions: ring regardless of size (the fp32 bit-exact pin)
        for nbytes in (64, 1 << 10, 1 << 20, 1 << 25):
            assert algorithms.select(
                "allreduce", nbytes, 4, all_cohosted=False, options=o
            ) == "ring"
        # broadcast: bytes are routing-independent -> size-based table
        assert algorithms.select(
            "broadcast", 1024, 4, all_cohosted=False, options=o
        ) == "btree"
        assert algorithms.select(
            "broadcast", 1 << 25, 4, all_cohosted=False, options=o
        ) == "ring"

    def test_auto_table_and_pow2_gate(self):
        auto = GroupOptions(algorithm="auto")
        assert algorithms.select(
            "allreduce", 1024, 4, all_cohosted=False, options=auto
        ) == "rd"
        assert algorithms.select(  # non-pow2: falls back
            "allreduce", 1024, 3, all_cohosted=False, options=auto
        ) == "ring"
        assert algorithms.select(  # large: bandwidth wins
            "allreduce", 1 << 25, 4, all_cohosted=False, options=auto
        ) == "ring"
        # co-hosted plane doubles the small threshold
        border = int(1.5 * 64 * 1024)
        assert algorithms.select(
            "allreduce", border, 4, all_cohosted=True, options=auto
        ) == "rd"
        assert algorithms.select(
            "allreduce", border, 4, all_cohosted=False, options=auto
        ) == "ring"

    def test_suspect_steers_broadcast_to_btree(self):
        o = GroupOptions()
        assert algorithms.select(
            "broadcast", 1 << 25, 4, all_cohosted=False, options=o,
            any_suspect=True,
        ) == "btree"

    def test_group_override_is_lenient_per_op_is_strict(self):
        # group-wide "rd" steers allreduce but not broadcast, and falls
        # back on non-pow2 worlds (a shrink reform must not brick ops)
        rd = GroupOptions(algorithm="rd")
        assert algorithms.select(
            "broadcast", 1024, 4, all_cohosted=False, options=rd
        ) == "btree"
        assert algorithms.select(
            "allreduce", 1 << 25, 3, all_cohosted=False, options=rd
        ) == "ring"
        with pytest.raises(CollectiveError, match="power-of-two"):
            algorithms.select(
                "allreduce", 1024, 3, all_cohosted=False,
                options=GroupOptions(), override="rd",
            )
        with pytest.raises(CollectiveError, match="cannot run"):
            algorithms.select(
                "broadcast", 1024, 4, all_cohosted=False,
                options=GroupOptions(), override="rd",
            )

    @pytest.mark.parametrize("n", [2, 3, 4, 5, 7, 8, 16])
    def test_btree_reaches_every_rank_exactly_once(self, n):
        from collections import deque

        order = algorithms.btree_order(n, n // 2, frozenset())
        kids = {
            r: algorithms.btree_parent_children(order, r)[1] for r in order
        }
        q, reached = deque([order[0]]), set()
        while q:
            v = q.popleft()
            assert v not in reached
            reached.add(v)
            q.extend(kids[v])
        assert reached == set(range(n))
        for r in order[1:]:
            parent, _ = algorithms.btree_parent_children(order, r)
            assert r in kids[parent]

    def test_btree_suspects_are_leaves(self):
        order = algorithms.btree_order(8, 0, frozenset({3, 5}))
        assert order[-2:] in ([3, 5], [5, 3]) or set(order[-2:]) == {3, 5}
        for s in (3, 5):
            _, children = algorithms.btree_parent_children(order, s)
            assert children == [], "suspect rank must not gate a subtree"


# ---------------------------------------------------------------------------
# rendezvous options adoption (fake GCS, no cluster)
# ---------------------------------------------------------------------------

class _FakeGcs:
    def __init__(self):
        self.kv = {}

    async def call(self, method, payload, timeout=None):
        if method == "kv_put":
            self.kv[payload["key"]] = payload["value"]
            return True
        if method == "kv_get":
            return self.kv.get(payload["key"])
        if method == "kv_del":
            self.kv.pop(payload["key"], None)
            return True
        raise AssertionError(method)


class _FakeServer:
    class server:
        address = "127.0.0.1:0"


class _FakeRT:
    def __init__(self, gcs):
        self.gcs = gcs
        self._worker_server = _FakeServer()
        self.node_id = "aa" * 8
        self.worker_id = b"\x01" * 8


class TestRendezvousOptions:
    def _run(self, coro):
        import asyncio

        return asyncio.new_event_loop().run_until_complete(coro)

    def test_rank0_options_adopted_and_peekable(self):
        from ray_tpu.util.collective import rendezvous as rdv

        gcs = _FakeGcs()
        rt = _FakeRT(gcs)
        opts = GroupOptions(wire_dtype="int8", chunk_bytes=1 << 16)

        async def go():
            me0 = await rdv.declare(rt, "g", 2, 0, None, options=opts)
            me1 = await rdv.declare(rt, "g", 2, 1, None, options=None)
            # rank 1 declared defaults: adopts rank 0's copy
            members, inc, adopted = await rdv.await_members(
                rt, "g", 2, 1, me1, timeout=5.0, options=None
            )
            assert adopted.to_dict() == opts.to_dict()
            # the replacement-member path reads the same config back
            gen, peeked = await rdv.peek_record(rt, "g", 0)
            assert gen == 0 and peeked.to_dict() == opts.to_dict()
            return me0

        self._run(go())

    def test_conflicting_nondefault_options_rejected(self):
        from ray_tpu.util.collective import rendezvous as rdv

        gcs = _FakeGcs()
        rt = _FakeRT(gcs)

        async def go():
            await rdv.declare(
                rt, "g", 2, 0, None,
                options=GroupOptions(wire_dtype="int8"),
            )
            mine = GroupOptions(wire_dtype="bf16")
            me1 = await rdv.declare(rt, "g", 2, 1, None, options=mine)
            with pytest.raises(CollectiveError, match="must agree"):
                await rdv.await_members(
                    rt, "g", 2, 1, me1, timeout=5.0, options=mine
                )

        self._run(go())


# ---------------------------------------------------------------------------
# cluster tests
# ---------------------------------------------------------------------------

@ray_tpu.remote
class Member:
    def init(self, world, rank, group, options=None):
        col.init_collective_group(
            world, rank, group_name=group, options=options
        )
        return col.get_rank(group)

    def destroy(self, group):
        col.destroy_collective_group(group_name=group)
        return True

    def opts(self, group):
        return col.get_group_options(group).to_dict()

    def allreduce(self, arr, group, **kw):
        return col.allreduce(arr, group_name=group, **kw)

    def broadcast(self, arr, root, group, **kw):
        return col.broadcast(arr, src_rank=root, group_name=group, **kw)

    def barrier(self, group):
        return col.barrier(group_name=group)

    def broadcast_object(self, obj, root, group):
        return col.broadcast_object(obj, src_rank=root, group_name=group)

    def broadcast_tree(self, tree, root, group, **kw):
        return col.broadcast_tree(
            tree, src_rank=root, group_name=group, **kw
        )

    def launch_overlap(self, arr, group, compute_ms, **kw):
        """allreduce_launch + caller-thread compute + wait: returns
        (result, total_s, compute_s) for the overlap assertion."""
        t0 = time.perf_counter()
        work = col.allreduce_launch(arr, group_name=group, **kw)
        assert not isinstance(work.done(), Exception)
        c0 = time.perf_counter()
        deadline = c0 + compute_ms / 1000.0
        x = np.ones(4096, np.float64)
        while time.perf_counter() < deadline:
            x = np.sqrt(x + 1.0)  # keep the caller thread busy
        compute_s = time.perf_counter() - c0
        out = work.wait(timeout=120)
        return out, time.perf_counter() - t0, compute_s

    def blocking_then_compute(self, arr, group, compute_ms, **kw):
        t0 = time.perf_counter()
        out = col.allreduce(arr, group_name=group, **kw)
        c0 = time.perf_counter()
        deadline = c0 + compute_ms / 1000.0
        x = np.ones(4096, np.float64)
        while time.perf_counter() < deadline:
            x = np.sqrt(x + 1.0)
        return out, time.perf_counter() - t0

    def reform(self, world, group, rank=None):
        col.reform_collective_group(world, group_name=group, rank=rank)
        return col.get_group_options(group).to_dict()


@pytest.fixture(scope="module")
def cluster():
    ray_tpu.init(num_cpus=8, num_tpus=0)
    yield
    ray_tpu.shutdown()


def _make_group(n, group, options=None):
    ms = [Member.options(num_cpus=0).remote() for _ in range(n)]
    ranks = ray_tpu.get(
        [m.init.remote(n, i, group, options) for i, m in enumerate(ms)],
        timeout=120,
    )
    assert ranks == list(range(n))
    return ms


def _teardown(ms, group):
    try:
        ray_tpu.get([m.destroy.remote(group) for m in ms], timeout=60)
    except Exception:
        pass
    for m in ms:
        ray_tpu.kill(m)


def _ring_allreduce_reference(inputs):
    """Pure-numpy replay of the PR 2 ring schedule (reduce-scatter +
    allgather): the bit-exactness oracle for the default path.  Returns
    the array every rank must finish with."""
    n = len(inputs)
    flats = [x.reshape(-1).astype(np.float32, copy=True) for x in inputs]
    size = flats[0].size
    segs = _segment_bounds(size, n)
    for step in range(n - 1):
        # all sends leave from the PRE-step state (the sent segment is
        # never the one being updated this step, so this matches the
        # overlapped schedule exactly)
        msgs = []
        for r in range(n):
            prev = (r - 1) % n
            s_lo, s_hi = segs[(prev - step - 1) % n]
            msgs.append(flats[prev][s_lo:s_hi].copy())
        for r in range(n):
            r_lo, r_hi = segs[(r - step - 2) % n]
            flats[r][r_lo:r_hi] += msgs[r]
    # allgather circulates each owner's bits verbatim: segment j's
    # final value everywhere is rank j's post-RS copy
    out = np.empty(size, np.float32)
    for j in range(n):
        lo, hi = segs[j]
        out[lo:hi] = flats[j][lo:hi]
    return out


class TestFp32DefaultBitExactVsPr2Ring:
    @pytest.mark.parametrize("world", [2, 3, 4])
    def test_default_allreduce_is_the_pr2_ring_bitwise(self, cluster, world):
        """Adversarial (non-integer) fp32 data: any change to the
        default reduction order or wire format shows up as a bit
        difference against the schedule replay."""
        group = f"pin{world}"
        ms = _make_group(world, group)
        try:
            rng = np.random.default_rng(900 + world)
            inputs = [
                (rng.standard_normal(10007) * np.pi).astype(np.float32)
                for _ in range(world)
            ]
            expected = _ring_allreduce_reference(inputs)
            outs = ray_tpu.get(
                [
                    m.allreduce.remote(x, group)
                    for m, x in zip(ms, inputs)
                ],
                timeout=120,
            )
            for r, out in enumerate(outs):
                assert np.array_equal(out.reshape(-1), expected), (
                    f"world {world} rank {r}: default fp32 path diverged "
                    f"from the PR 2 ring schedule"
                )
        finally:
            _teardown(ms, group)


class TestQuantizedCollectives:
    def test_int8_ring_all_ranks_identical_and_bounded(self, cluster):
        group = "q4"
        ms = _make_group(4, group, options={"wire_dtype": "int8"})
        try:
            rng = np.random.default_rng(41)
            xs = [
                rng.standard_normal(30000).astype(np.float32)
                for _ in range(4)
            ]
            ref = xs[0] + xs[1] + xs[2] + xs[3]
            outs = ray_tpu.get(
                [m.allreduce.remote(x, group) for m, x in zip(ms, xs)],
                timeout=120,
            )
            for out in outs[1:]:
                assert np.array_equal(out, outs[0]), (
                    "quantized ring must leave all ranks bit-identical"
                )
            err = np.abs(outs[0] - ref).max()
            assert 0 < err < 0.02 * np.abs(ref).max(), err
            # per-op fp32 override on the quantized group: exact
            ys = [np.full(64, float(i + 1), np.float32) for i in range(4)]
            exact = ray_tpu.get(
                [
                    m.allreduce.remote(y, group, wire_dtype="fp32")
                    for m, y in zip(ms, ys)
                ],
                timeout=120,
            )
            assert np.array_equal(exact[0], np.full(64, 10.0, np.float32))
        finally:
            _teardown(ms, group)

    def test_rd_small_message_exact_and_identical(self, cluster):
        """Explicit rd on integer-valued fp32: pairwise sums of small
        ints are exact, so rd must equal numpy's sum bit-for-bit."""
        group = "rd4"
        ms = _make_group(4, group)
        try:
            rng = np.random.RandomState(5)
            xs = [
                rng.randint(-512, 512, 4001).astype(np.float32)
                for _ in range(4)
            ]
            outs = ray_tpu.get(
                [
                    m.allreduce.remote(x, group, algorithm="rd")
                    for m, x in zip(ms, xs)
                ],
                timeout=120,
            )
            expected = xs[0] + xs[1] + xs[2] + xs[3]
            for out in outs:
                assert np.array_equal(out, expected)
            # MEAN rides rd too (sum + divide)
            outs = ray_tpu.get(
                [
                    m.allreduce.remote(
                        x * 4.0, group, algorithm="rd", op=ReduceOp.MEAN
                    )
                    for m, x in zip(ms, xs)
                ],
                timeout=120,
            )
            exp = (xs[0] + xs[1] + xs[2] + xs[3])
            for out in outs:
                assert np.array_equal(out, exp)
        finally:
            _teardown(ms, group)

    def test_btree_broadcast_payload_identical(self, cluster):
        group = "bt4"
        ms = _make_group(4, group, options={"chunk_bytes": 8192})
        try:
            payload = np.random.default_rng(3).standard_normal(
                20000
            ).astype(np.float32)  # 80 KB over 8 KB chunks: multi-chunk
            outs = ray_tpu.get(
                [
                    ms[i].broadcast.remote(
                        payload if i == 2 else np.zeros_like(payload),
                        2, group, algorithm="btree",
                    )
                    for i in range(4)
                ],
                timeout=120,
            )
            for out in outs:
                assert np.array_equal(out, payload)
            # quantized broadcast: every rank (root incl.) returns the
            # decode of the one encoding
            outs = ray_tpu.get(
                [
                    ms[i].broadcast.remote(
                        payload if i == 0 else np.zeros_like(payload),
                        0, group, wire_dtype="bf16",
                    )
                    for i in range(4)
                ],
                timeout=120,
            )
            for out in outs[1:]:
                assert np.array_equal(out, outs[0])
            err = np.abs(outs[0] - payload).max()
            assert 0 < err <= np.abs(payload).max() * 2.0 ** -8
        finally:
            _teardown(ms, group)

    def test_barrier_and_object_ops_on_quantized_group(self, cluster):
        """Regression: barrier's int32 token and broadcast_object's
        pickle bytes are not float tensors — a group-level wire_dtype
        must not leak into them (it used to raise 'needs float32')."""
        group = "qb2"
        ms = _make_group(2, group, options={"wire_dtype": "int8"})
        try:
            assert all(
                ray_tpu.get(
                    [m.barrier.remote(group) for m in ms], timeout=120
                )
            )
            outs = ray_tpu.get(
                [
                    ms[i].broadcast_object.remote(
                        {"k": 7} if i == 0 else None, 0, group
                    )
                    for i in range(2)
                ],
                timeout=120,
            )
            assert outs[0]["k"] == 7 and outs[1]["k"] == 7
        finally:
            _teardown(ms, group)

    def test_non_finite_input_poisons_instead_of_wedging(self, cluster):
        """Regression: a NaN tensor on ONE rank of a quantized
        collective used to raise a usage-class error there (group left
        'usable') while peers wedged for the full op timeout.  It must
        poison and fan out so every rank fails fast."""
        group = "nan2"
        ms = _make_group(2, group, options={"wire_dtype": "int8"})
        try:
            bad = np.ones(5000, np.float32)
            bad[123] = np.nan
            good = np.ones(5000, np.float32)
            t0 = time.monotonic()
            refs = [
                ms[0].allreduce.remote(bad, group),
                ms[1].allreduce.remote(good, group),
            ]
            for ref in refs:
                with pytest.raises(Exception) as ei:
                    ray_tpu.get(ref, timeout=90)
                msg = str(ei.value)
                assert (
                    "poisoned" in msg or "aborted" in msg
                    or "non-finite" in msg or "failed" in msg
                ), msg
            # both failed far under the 120 s op timeout (fan-out, not
            # a peer timeout)
            assert time.monotonic() - t0 < 60
        finally:
            _teardown(ms, group)

    def test_invalid_broadcast_override_raises_on_every_rank(self, cluster):
        """Regression: an invalid per-op algorithm raised instantly at
        the root only, while non-roots parked in first_src until the
        op timeout and then poisoned the group.  Validation must be
        symmetric, and the group must stay usable afterwards."""
        group = "bo2"
        ms = _make_group(2, group)
        try:
            x = np.ones(64, np.float32)
            refs = [
                ms[i].broadcast.remote(x, 0, group, algorithm="rd")
                for i in range(2)
            ]
            t0 = time.monotonic()
            for ref in refs:
                with pytest.raises(Exception, match="cannot run"):
                    ray_tpu.get(ref, timeout=60)
            assert time.monotonic() - t0 < 30
            # usage error: the group survives and serves the next op
            outs = ray_tpu.get(
                [
                    ms[i].broadcast.remote(
                        x if i == 0 else np.zeros_like(x), 0, group
                    )
                    for i in range(2)
                ],
                timeout=120,
            )
            assert np.array_equal(outs[1], x)
        finally:
            _teardown(ms, group)

    def test_broadcast_tree_mixed_pytree(self, cluster):
        group = "wt2"
        ms = _make_group(2, group)
        try:
            src = {
                "w": np.arange(5000, dtype=np.float32) / 3.0,
                "meta": ("tag", np.arange(6, dtype=np.int32)),
                "nested": [np.ones((3, 4), np.float32)],
            }
            outs = ray_tpu.get(
                [
                    ms[i].broadcast_tree.remote(
                        src if i == 0 else None, 0, group,
                        wire_dtype="int8",
                    )
                    for i in range(2)
                ],
                timeout=120,
            )
            a, b = outs
            assert np.array_equal(a["w"], b["w"])
            assert a["meta"][0] == "tag"
            assert np.array_equal(
                a["meta"][1], np.arange(6, dtype=np.int32)
            )  # non-f32 leaves exact
            assert a["nested"][0].shape == (3, 4)
            bound = quantize.get_codec("int8").error_bound(src["w"])
            assert np.abs(a["w"] - src["w"]).max() <= bound
        finally:
            _teardown(ms, group)


class TestReformCarriesOptions:
    def test_shrink_reform_keeps_wire_format(self, cluster):
        """Satellite regression: reform used to rebuild the group with
        default backend options — a migration silently changed the wire
        format.  The full GroupSpec config must survive a shrink."""
        group = "rf4"
        opts = {
            "wire_dtype": "int8", "chunk_bytes": 1 << 16,
            "algorithm": "auto", "quant_block": 1024,
        }
        ms = _make_group(4, group, options=opts)
        try:
            ray_tpu.kill(ms[3])
            time.sleep(1.0)
            got = ray_tpu.get(
                [ms[i].reform.remote(3, group) for i in range(3)],
                timeout=120,
            )
            for od in got:
                assert od == opts, f"reform dropped group options: {od}"
            # and the group still works quantized at the new world size
            xs = [
                np.random.default_rng(i).standard_normal(2000).astype(
                    np.float32
                )
                for i in range(3)
            ]
            outs = ray_tpu.get(
                [
                    ms[i].allreduce.remote(xs[i], group)
                    for i in range(3)
                ],
                timeout=120,
            )
            for out in outs[1:]:
                assert np.array_equal(out, outs[0])
        finally:
            _teardown(ms[:3], group)

    def test_replacement_member_inherits_options(self, cluster):
        """A REPLACEMENT member has no local history: it must inherit
        the group config from the stale rendezvous record
        (peek_record), not re-join with defaults."""
        group = "rp3"
        opts = {"wire_dtype": "bf16", "chunk_bytes": 32768}
        ms = _make_group(3, group, options=opts)
        try:
            ray_tpu.kill(ms[1])
            time.sleep(1.0)
            fresh = Member.options(num_cpus=0).remote()
            refs = [
                ms[0].reform.remote(3, group),
                fresh.reform.remote(3, group, 1),
                ms[2].reform.remote(3, group),
            ]
            got = ray_tpu.get(refs, timeout=120)
            expected = GroupOptions.from_dict(opts).to_dict()
            for od in got:
                assert od == expected, (
                    f"replacement reform lost the group config: {od}"
                )
            ms[1] = fresh
            xs = [
                np.random.default_rng(10 + i).standard_normal(
                    1500
                ).astype(np.float32)
                for i in range(3)
            ]
            outs = ray_tpu.get(
                [
                    ms[i].allreduce.remote(xs[i], group)
                    for i in range(3)
                ],
                timeout=120,
            )
            for out in outs[1:]:
                assert np.array_equal(out, outs[0])
        finally:
            _teardown(ms, group)


class TestProgressEngine:
    def test_launch_wait_overlaps_compute(self, cluster):
        """launch + compute + wait must cost well under compute-then-op
        serialized: the op's chunked steps progress on the runtime loop
        while the caller thread is busy."""
        group = "ov2"
        ms = _make_group(2, group)
        try:
            rng = np.random.default_rng(6)
            xs = [
                rng.standard_normal(1 << 20).astype(np.float32)  # 4 MB
                for _ in range(2)
            ]
            expected = xs[0] + xs[1]
            compute_ms = 300.0
            ov_totals, ser_totals = [], []
            for _ in range(3):
                outs = ray_tpu.get(
                    [
                        m.launch_overlap.remote(x, group, compute_ms)
                        for m, x in zip(ms, xs)
                    ],
                    timeout=120,
                )
                for out, total_s, compute_s in outs:
                    assert np.array_equal(out, expected)
                    assert compute_s >= 0.9 * compute_ms / 1000.0
                ov_totals.append(max(t for _, t, _c in outs))
                # serialized reference on the same group
                ser = ray_tpu.get(
                    [
                        m.blocking_then_compute.remote(x, group, compute_ms)
                        for m, x in zip(ms, xs)
                    ],
                    timeout=120,
                )
                ser_totals.append(max(t for _, t in ser))
            # overlap must beat strict serialization by a real margin
            # (the op alone takes >> 30 ms at 4 MB on this plane).  The
            # best of three of each: one reading is 300 ms of compute,
            # the op, and whatever a loaded host adds, which can be as
            # much as the op
            ov_total, ser_total = min(ov_totals), min(ser_totals)
            assert ov_total < ser_total, (ov_totals, ser_totals)
        finally:
            _teardown(ms, group)

    def test_launch_surfaces_errors_at_wait(self, cluster):
        with pytest.raises(CollectiveError):
            # no group of this name in the driver process: the launch
            # itself must not be able to silently swallow it
            work = col.allreduce_launch(
                np.ones(4, np.float32), group_name="nope"
            )
            work.wait(timeout=30)


class TestChunkKnobSweepable:
    def test_group_chunk_bytes_override_used(self, cluster):
        """GroupOptions.chunk_bytes (satellite: the sweepable named
        knob) must actually chunk the wire traffic: a 64 KB payload
        over a 4 KB chunk limit works and round-trips exactly."""
        group = "ck2"
        ms = _make_group(2, group, options={"chunk_bytes": 4096})
        try:
            x = np.arange(16384, dtype=np.float32)  # 64 KB -> 16 chunks
            outs = ray_tpu.get(
                [m.allreduce.remote(x, group) for m in ms],
                timeout=120,
            )
            assert np.array_equal(outs[0], x * 2.0)
            assert np.array_equal(outs[1], x * 2.0)
        finally:
            _teardown(ms, group)
