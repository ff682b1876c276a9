"""The delta rule with a decay a key channel (``ops/gated_delta.py``: ``step``,
the kernel ``kda_step``, the chunked ``scan`` and its kernel ``kda_chunk``,
``recurrent``) against itself, against the scalar gate and against the plain
reference's token loop.  The model these serve is ``tests/test_solar_open2.py``'s;
the two are files of their own so that neither holds a tier-1 worker long."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import gated_delta as gd


def rule_inputs(seed, B, S, H, dk, dv, fast=True, strong=True):
    """q, k normed as the layer norms them, a decay a channel of which channel
    0 falls by e^-20 a token (``fast``), beta close under 2 (``strong``)."""
    ks = jax.random.split(jax.random.key(seed), 6)
    q = jax.random.normal(ks[0], (B, S, H, dk))
    k = jax.random.normal(ks[1], (B, S, H, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(dk)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (B, S, H, dv))
    g = -jax.nn.softplus(2.0 * jax.random.normal(ks[3], (B, S, H, dk)))
    if fast:
        g = g.at[..., 0].set(-20.0)
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(ks[4], (B, S, H)) + (4.0 if strong else 0.0))
    return q, k, v, g, beta, jax.random.normal(ks[5], (B, H, dk, dv))


@pytest.mark.parametrize("fast, strong", [(True, True), (True, False), (False, True)])
@pytest.mark.parametrize("S, chunk", [(150, 64), (64, 32), (37, 4)])
def test_the_chunked_rule_is_the_rule_token_by_token(S, chunk, fast, strong):
    q, k, v, g, beta, s0 = rule_inputs(S, 2, S, 2, 16, 24, fast, strong)
    if strong:
        assert float(beta.max()) > 1.98
    want_o, want_s = gd.recurrent(q, k, v, g, beta, s0)
    got_o, got_s = gd.scan(q, k, v, g, beta, s0, chunk=chunk)
    assert np.isfinite(np.asarray(got_o)).all() and np.isfinite(np.asarray(got_s)).all()
    np.testing.assert_allclose(got_o, want_o, atol=2e-5)
    np.testing.assert_allclose(got_s, want_s, atol=2e-5)


def test_a_fast_channel_would_overflow_the_scalar_forms_factors():
    """What ``_scan_channels`` is written around: e^-G of a channel that falls
    by 20 a token is past float32 after five tokens, and the chunk has 64."""
    g = jnp.cumsum(jnp.full((64,), -20.0))
    assert not np.isfinite(np.asarray(jnp.exp(-g))).all()
    q, k, v, la, beta, s0 = rule_inputs(1, 1, 64, 1, 8, 8)
    o, s = gd.scan(q, k, v, la, beta, s0)
    assert np.isfinite(np.asarray(o)).all() and np.isfinite(np.asarray(s)).all()
    # of the fast channel's row the run's first state is gone: e^-1280
    o2, s2 = gd.scan(q, k, v, la, beta, s0 + 100.0)
    np.testing.assert_allclose(s2[0, 0, 0], s[0, 0, 0], atol=1e-5)


def test_the_state_is_carried_from_one_run_to_the_next():
    q, k, v, g, beta, s0 = rule_inputs(3, 2, 200, 2, 16, 24)
    want_o, want_s = gd.scan(q, k, v, g, beta, s0)
    cut = 70  # in the middle of a chunk
    o1, s1 = gd.scan(q[:, :cut], k[:, :cut], v[:, :cut], g[:, :cut], beta[:, :cut], s0)
    o2, s2 = gd.scan(q[:, cut:], k[:, cut:], v[:, cut:], g[:, cut:], beta[:, cut:], s1)
    np.testing.assert_allclose(jnp.concatenate([o1, o2], 1), want_o, atol=2e-5)
    np.testing.assert_allclose(s2, want_s, atol=2e-5)


def test_positions_that_are_not_valid_leave_the_state_alone():
    q, k, v, g, beta, s0 = rule_inputs(4, 2, 40, 2, 8, 16)
    valid = jnp.arange(40)[None, :] < jnp.asarray([[25], [40]])
    _, got = gd.scan(q, k, v, g, beta, s0, valid=valid, chunk=16)
    _, want = gd.scan(q[:1, :25], k[:1, :25], v[:1, :25], g[:1, :25], beta[:1, :25],
                      s0[:1], chunk=16)
    np.testing.assert_allclose(got[0], want[0], atol=2e-5)


@pytest.mark.parametrize("what", ["step", "scan", "recurrent", "step_in_place"])
def test_a_decay_constant_over_the_channels_is_the_scalar_gate(what):
    """To 1e-5 of values of order one: the same algebra, the sums over d_k in
    another order (the scalar path multiplies by alpha after its sum, the
    channels' before)."""
    q, k, v, g, beta, s0 = rule_inputs(5, 8, 70, 2, 16, 128, fast=False)
    scalar = g[..., 1]
    wide = jnp.broadcast_to(scalar[..., None], g.shape)
    if what in ("scan", "recurrent"):
        f = getattr(gd, what)
        a, b = f(q, k, v, scalar, beta, s0), f(q, k, v, wide, beta, s0)
    elif what == "step":
        t = (q[:, 0], k[:, 0], v[:, 0])
        a = gd.step(*t, scalar[:, 0], beta[:, 0], s0)
        b = gd.step(*t, wide[:, 0], beta[:, 0], s0)
    else:
        leaf = jnp.stack([gd.packed(s0)] * 2)
        t = (q[:, 0], k[:, 0], v[:, 0])
        a = gd.step_in_place(*t, scalar[:, 0], beta[:, 0], leaf, jnp.int32(1))
        b = gd.step_in_place(*t, wide[:, 0], beta[:, 0], leaf, jnp.int32(1))
    for x, y in zip(a, b):
        np.testing.assert_allclose(x, y, atol=1e-5)


@pytest.mark.parametrize("B, H, dk, dv", [(2, 2, 16, 128), (8, 4, 32, 128), (16, 2, 128, 128),
                                          (8, 4, 16, 64)])
def test_the_kernel_is_the_step_and_moves_one_layer(B, H, dk, dv):
    """``kda_step`` in interpret mode: the very kernel."""
    assert gd.implementation(B, H, dk, dv) == "in_place"
    q, k, v, g, beta, s0 = rule_inputs(B + dk, B, 1, H, dk, dv)
    t = (q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0])
    leaf = jnp.stack([gd.packed(s0 + i) for i in range(3)])
    want_o, want_s = gd.step(*t, s0 + 1)
    o, out = gd.step_layer(*t, leaf, jnp.int32(1))
    np.testing.assert_allclose(o, want_o, atol=1e-4)
    np.testing.assert_allclose(gd.unpacked(out[1], H), want_s, atol=1e-4)
    np.testing.assert_array_equal(out[0], leaf[0])
    np.testing.assert_array_equal(out[2], leaf[2])


def _whole_tile_inputs(seed, S=128, fast=True):
    """The shape ``kda_chunk`` takes, small: 1 row x S tokens x 8 heads x (128,
    128): one group of heads, interpret mode."""
    return rule_inputs(seed, 1, S, 8, 128, 128, fast=fast)


# one trace and one compile a shape: the cases share two (128 tokens; 100 with
# ``valid``), interpret mode takes seconds for each
_scan, _scan_xla, _recurrent = (jax.jit(f) for f in (gd.scan, gd._scan_channels, gd.recurrent))


@pytest.mark.parametrize("case", [
    "state0", "ragged", "fast_channel", "continued", "constant_decay", "two_tiles_a_value"])
def test_the_kernel_is_the_chunked_rule(case):
    """``kda_chunk`` in interpret mode — the very kernel — against the rule
    token by token and against the ``jax.numpy`` body, at the tolerances that
    body is held to."""
    assert gd.scan_implementation(8, 128, 128, 64, True) == "kernel"

    def close(got, want, atol=2e-5):
        for g, w in zip(got, want):
            assert np.isfinite(np.asarray(g)).all()
            np.testing.assert_allclose(g, w, atol=atol)

    def first(n, *xs):
        return tuple(x[:, :n] for x in xs)

    if case == "state0":
        # a non-zero first state, beta up to 2, two chunks
        q, k, v, g, beta, s0 = _whole_tile_inputs(11)
        assert float(beta.max()) > 1.98 and float(jnp.abs(s0).max()) > 1
        got = _scan(q, k, v, g, beta, s0)
        close(got, _recurrent(q, k, v, g, beta, s0))
        close(got, _scan_xla(q, k, v, g, beta, s0))
    elif case == "ragged":
        # S no whole number of chunks, ``valid`` with an end inside a chunk
        q, k, v, g, beta, s0 = _whole_tile_inputs(12, 100)
        valid = jnp.arange(100)[None, :] < 70
        o, s = _scan(q, k, v, g, beta, s0, valid)
        want_o, want_s = gd.recurrent(*first(70, q, k, v, g, beta), s0)
        close((o[:, :70], s), (want_o, want_s))
        xla_o, xla_s = _scan_xla(q, k, v, g, beta, s0, valid)
        close((o[:, :70], s), (xla_o[:, :70], xla_s))
    elif case == "fast_channel":
        # a channel that falls by e^-20 a token (e^-1280 a chunk: its inverse is
        # past float32) beside one that does not decay: no inf, no nan, and
        # what the fast channel's row of the first state held is gone at once
        q, k, v, g, beta, s0 = _whole_tile_inputs(13)
        g = g.at[..., 1].set(0.0)
        o, s = _scan(q, k, v, g, beta, s0)
        close((o, s), _recurrent(q, k, v, g, beta, s0))
        close(_scan(q, k, v, g, beta, s0.at[:, :, 0].add(100.0)), (o, s), atol=1e-5)
    elif case == "continued":
        # two runs that continue each other are one run: 70 tokens (the cut in
        # the middle of a chunk), then the other 58 from the state they left
        run = _whole_tile_inputs(14)
        *tokens, s0 = run
        want_o, want_s = _scan(*run)
        o1, s1 = _scan(*first(100, *tokens), s0, jnp.arange(100)[None, :] < 70)
        rest = (jnp.pad(x[:, 70:], ((0, 0), (0, 42)) + ((0, 0),) * (x.ndim - 2)) for x in tokens)
        o2, s2 = _scan(*rest, s1, jnp.arange(100)[None, :] < 58)
        close((jnp.concatenate([o1[:, :70], o2[:, :58]], 1), s2), (want_o, want_s))
    elif case == "two_tiles_a_value":
        # d_v of two lane tiles beside d_k of one
        run = rule_inputs(16, 1, 64, 8, 128, 256)
        assert gd.scan_implementation(8, 128, 256, 64, True) == "kernel"
        close(gd.scan(*run), gd.recurrent(*run))
    else:
        # a decay constant over the channels is the scalar gate's ``scan``
        q, k, v, g, beta, s0 = _whole_tile_inputs(15, fast=False)
        scalar = g[..., 1]
        wide = jnp.broadcast_to(scalar[..., None], g.shape)
        close(_scan(q, k, v, wide, beta, s0), _scan(q, k, v, scalar, beta, s0), atol=1e-5)


def test_the_chunked_rules_body_is_chosen_by_shape_in_one_place(monkeypatch):
    """The toy widths take XLA's body, the served shape the kernel, a decay a
    head (B, S, H) never the kernel; ``scan`` asks ``scan_implementation`` and
    nothing else."""
    solar = (64, 128, 128, 64)
    assert gd.scan_implementation(*solar, True) == "kernel"
    assert gd.scan_implementation(*solar, False) == "xla"
    assert gd.scan_implementation(30, 96, 192, 64, False) == "xla"      # Olmo-Hybrid's
    assert gd.scan_implementation(4, 16, 16, 4, True) == "xla"          # tier-1's toy
    assert gd.scan_implementation(64, 128, 128, 32, True) == "xla"      # another chunk
    assert gd.scan_implementation(4, 128, 128, 64, True) == "xla"       # half a group of heads
    called = []
    monkeypatch.setattr(gd, "_scan_kernel", lambda *a: called.append("kernel") or (None, None))
    monkeypatch.setattr(gd, "_scan_channels", lambda *a: called.append("xla") or (None, None))
    toy = rule_inputs(1, 1, 8, 4, 16, 16)
    gd.scan(*toy, chunk=4)
    served = [jax.ShapeDtypeStruct(s, jnp.float32) for s in (
        (1, 64, 8, 128), (1, 64, 8, 128), (1, 64, 8, 128), (1, 64, 8, 128), (1, 64, 8),
        (1, 8, 128, 128))]
    gd.scan(*served)
    assert called == ["xla", "kernel"]
    # the scalar gate's body is ``scan``'s own, whatever the widths
    q, k, v, g, beta, s0 = rule_inputs(2, 1, 64, 8, 128, 128)
    o, s = gd.scan(q, k, v, g[..., 0], beta, s0)
    assert called == ["xla", "kernel"] and o.shape == v.shape


def test_toy_states_take_xlas_body_with_the_channels_too():
    q, k, v, g, beta, s0 = rule_inputs(9, 3, 1, 4, 8, 16)
    assert gd.implementation(3, 4, 8, 16) == "xla"
    t = (q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0])
    leaf = jnp.stack([gd.packed(s0)] * 2)
    o, out = gd.step_layer(*t, leaf, jnp.int32(0))
    want_o, want_s = gd.step(*t, s0)
    np.testing.assert_allclose(o, want_o, atol=1e-6)
    np.testing.assert_allclose(gd.unpacked(out[0], 4), want_s, atol=1e-6)


def test_the_reference_recurrence_is_the_ops_rule():
    """One head group of the reference's token loop against ``recurrent``."""
    q, k, v, g, beta, _ = rule_inputs(11, 1, 30, 2, 8, 16)
    want, _ = gd.recurrent(q, k, v, g, beta, jnp.zeros((1, 2, 8, 16)))

    def token(state, t):
        q_t, k_t, v_t, g_t, b_t = t
        state = jnp.exp(g_t)[..., None] * state
        seen = jnp.einsum("gkv,gk->gv", state, k_t)
        state = state + jnp.einsum("gk,gv->gkv", k_t, b_t[:, None] * (v_t - seen))
        return state, jnp.einsum("gkv,gk->gv", state, q_t)

    _, o = jax.lax.scan(token, jnp.zeros((2, 8, 16)), (q[0], k[0], v[0], g[0], beta[0]))
    np.testing.assert_allclose(o, want[0], atol=1e-5)
