"""The gated delta rule (Yang et al., "Gated Delta Networks",
arXiv:2412.06464): a linear-attention layer's fixed-size recurrent state
in place of a list of per-token keys and values.

Per head, state ``S`` (d_k, d_v) float32, token t with query ``q`` and key
``k`` (d_k; normed and scaled by the caller), value ``v`` (d_v), decay
``alpha`` in (0, 1] and write strength ``beta``::

    S' = alpha S            o = S_t^T q
    S_t = S' + k (x) (beta (v - S'^T k))

``step`` is that update for ONE token of every row, ``scan`` the CHUNKED
form for a run of tokens (section 3 of the paper; the algorithm of
``transformers``' ``torch_chunk_gated_delta_rule``): inside a chunk of
``chunk`` tokens the rule's triangular system is solved once, with matrix
products, and between chunks the state is carried — S / chunk sequential
steps of matmuls for a prompt, not S of outer products.

Both take the decay as ``log_alpha`` = log(alpha) <= 0: a chunk's
cumulative decay is a SUM there, where the product of 64 alphas underflows
float32 (a head whose A is 16 decays by e^-20 a token).  Everything is
float32 with the products at ``Precision.HIGHEST`` (the chip's default for
float32 operands is one bfloat16 pass, which would round the state);
plain ``jax.numpy``: XLA's body is the only body.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

#: tokens of a chunk: the paper's, ``fla``'s and ``transformers``' 64
CHUNK = 64
_EXACT = lax.Precision.HIGHEST


def step(q, k, v, log_alpha, beta, state):
    """One token of every row.  q, k: (B, H, d_k); v: (B, H, d_v);
    log_alpha, beta: (B, H); state: (B, H, d_k, d_v) float32.  Returns
    (o (B, H, d_v) float32, the new state).

    The state is read twice and written once: both contractions are
    taken of the OLD state in one pass (``S_t^T q = alpha S^T q + (k . q)
    delta``: the rule's own algebra, nothing approximated), the update is
    the second."""
    q, k, v, beta = (x.astype(jnp.float32) for x in (q, k, v, beta))
    alpha = jnp.exp(log_alpha.astype(jnp.float32))[..., None]       # (B, H, 1)
    seen_k = (state * k[..., None]).sum(-2)                          # S^T k
    seen_q = (state * q[..., None]).sum(-2)                          # S^T q
    delta = beta[..., None] * (v - alpha * seen_k)                   # (B, H, d_v)
    o = alpha * seen_q + (k * q).sum(-1, keepdims=True) * delta
    state = alpha[..., None] * state + k[..., None] * delta[..., None, :]
    return o, state


def scan(q, k, v, log_alpha, beta, state0, valid=None, chunk: int = CHUNK):
    """A run of S tokens of every row, from ``state0``.  q, k: (B, S, H,
    d_k); v: (B, S, H, d_v); log_alpha, beta: (B, S, H); state0: (B, H,
    d_k, d_v); valid: (B, S) bool, the run's real tokens (None: all).
    Returns (o (B, S, H, d_v) float32, the final state float32).

    A position that is not ``valid`` is the identity (alpha 1, beta 0):
    it leaves the state as it found it, and its own output is not to be
    read.  S is padded up to whole chunks with such positions, so the
    final state is that of the real tokens whatever S is."""
    B, S, H, dk = q.shape
    dv = v.shape[-1]
    pad = -S % chunk
    real = jnp.ones((B, S), bool) if valid is None else valid
    real = jnp.pad(real, ((0, 0), (0, pad)))[..., None]              # (B, S', 1)

    def chunked(x):  # (B, S or S', H, ...) -> (B, H, N, chunk, ...) float32
        x = x.astype(jnp.float32)
        x = jnp.pad(x, ((0, 0), (0, S + pad - x.shape[1])) + ((0, 0),) * (x.ndim - 2))
        x = x.reshape(B, -1, chunk, *x.shape[2:])
        return jnp.moveaxis(x, 3, 1)

    g = chunked(_masked(log_alpha, real, pad))
    b = chunked(_masked(beta, real, pad))
    q, k, v = chunked(q), chunked(k), chunked(v)                     # (B, H, N, C, d)
    g = jnp.cumsum(g, axis=-1)                                       # (B, H, N, C)
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    # decay from token j to token i of a chunk, i >= j; masked BEFORE the
    # exponential: above the diagonal the difference is positive
    decay = jnp.exp(jnp.where(lower, g[..., :, None] - g[..., None, :], -jnp.inf))
    k_beta, v_beta = k * b[..., None], v * b[..., None]

    def mm(eq, x, y):
        return jnp.einsum(eq, x, y, precision=_EXACT)

    # the rule inside a chunk: (I + A) u = beta (v - decayed S0^T k), A
    # strictly lower; its inverse by the finite series of a nilpotent
    # matrix, sum (-A)^n = prod (I + (-A)^(2^j)): log2(chunk) squarings
    a = -jnp.where(jnp.tril(lower, -1), mm("bhnid,bhnjd->bhnij", k_beta, k) * decay, 0.0)
    eye = jnp.eye(chunk, dtype=jnp.float32)
    solve, power = eye + a, a
    for _ in range(max(0, (chunk - 1).bit_length() - 1)):
        power = mm("bhnij,bhnjk->bhnik", power, power)
        solve = mm("bhnij,bhnjk->bhnik", solve, eye + power)
    u = mm("bhnij,bhnjd->bhnid", solve, v_beta)                      # (B, H, N, C, d_v)
    w = mm("bhnij,bhnjd->bhnid", solve, k_beta * jnp.exp(g)[..., None])
    within = mm("bhnid,bhnjd->bhnij", q, k) * decay                  # i >= j
    q_in = q * jnp.exp(g)[..., None]                                 # against the chunk's S0
    k_out = k * jnp.exp(g[..., -1:] - g)[..., None]                  # into the chunk's end
    total = jnp.exp(g[..., -1])[..., None, None]                     # (B, H, N, 1, 1)

    def one(state, c):
        u_c, w_c, within_c, q_c, k_c, total_c = c
        new = u_c - mm("bhid,bhdv->bhiv", w_c, state)
        o = mm("bhid,bhdv->bhiv", q_c, state) + mm("bhij,bhjv->bhiv", within_c, new)
        return total_c * state + mm("bhid,bhiv->bhdv", k_c, new), o

    over_chunks = tuple(jnp.moveaxis(x, 2, 0) for x in (u, w, within, q_in, k_out, total))
    state, o = lax.scan(one, state0.astype(jnp.float32), over_chunks)
    o = jnp.moveaxis(o, 0, 2).reshape(B, H, -1, dv)[:, :, :S]        # (B, H, S, d_v)
    return jnp.moveaxis(o, 1, 2), state


def _masked(x, real, pad):
    """(B, S, H) padded to whole chunks, zero where no real token is."""
    return jnp.where(real, jnp.pad(x.astype(jnp.float32), ((0, 0), (0, pad), (0, 0))), 0.0)


def recurrent(q, k, v, log_alpha, beta, state0):
    """``scan``'s result by ``step`` token after token: the rule as it is
    written, for tests and references; no serving path calls it."""
    def one(state, t):
        o, state = step(*t, state)
        return state, o

    over_tokens = tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, log_alpha, beta))
    state, o = lax.scan(one, state0.astype(jnp.float32), over_tokens)
    return jnp.moveaxis(o, 0, 1), state
