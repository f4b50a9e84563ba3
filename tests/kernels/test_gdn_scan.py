"""The chunked gated delta rule as one Pallas kernel
(``kernels/gdn_scan.py``), through the interpreter at small widths,
against two oracles: the rule a position at a time
(``models/gated_deltanet.py:delta_rule_step``, what a decode step runs)
and the einsum form of the chunked arrangement that the kernel replaced
in the model (PR 37), kept here."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_llm_tpu.kernels.gdn_scan import CHUNK, gdn_scan
from megatron_llm_tpu.models import gated_deltanet as gdn

_PREC = jax.lax.Precision.HIGHEST


def recurrence(q, k, v, g, beta, S):
    """``q k`` [b, s, h, dk], ``v`` [b, s, h, dv], ``g beta`` [b, s, h],
    ``S`` [b, h, dk, dv] → ``(o [b, s, h, dv], S)``."""
    def step(S, x):
        o, S = gdn.delta_rule_step(*x, S)
        return S, o

    S, o = jax.lax.scan(step, S, jax.tree.map(
        lambda a: jnp.moveaxis(a, 1, 0), (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), S


def chunked_einsum(q, k, v, g, beta, S):
    """Shapes as ``recurrence``, ``s`` a multiple of ``CHUNK``.  Within a
    chunk the rule's ``d_t`` solve ``(I + L) D = beta V - (beta K e^G)
    S0``; everything that does not depend on ``S`` is computed for all
    chunks at once, and a ``lax.scan`` carries ``S`` alone."""
    b, s, h, dk = q.shape
    dv, n, c = v.shape[-1], s // CHUNK, CHUNK

    def chunks(x):       # [b, s, h, ...] -> [n, b, h, c, ...]
        x = x.reshape((b, n, c, h) + x.shape[3:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 2), 1, 0)

    q, k, v, g, beta = map(chunks, (q, k, v, g, beta))
    G = jnp.cumsum(g, axis=-1)                          # [n, b, h, c]
    diff = G[..., :, None] - G[..., None, :]
    lower = jnp.tril(jnp.ones((c, c), bool))
    decay = jnp.exp(jnp.where(lower, diff, -jnp.inf))   # i >= j, else 0
    strict = jnp.tril(jnp.ones((c, c), bool), -1)
    kb, vb = k * beta[..., None], v * beta[..., None]
    kk = jnp.einsum("nbhik,nbhjk->nbhij", kb, k, precision=_PREC)
    m = jnp.where(strict, -kk * decay, 0.0)             # -L
    eye = jnp.eye(c, dtype=jnp.float32)
    t = eye + m
    for _ in range(int(math.log2(c)) - 1):
        m = jnp.einsum("nbhij,nbhjl->nbhil", m, m, precision=_PREC)
        t = t + jnp.einsum("nbhij,nbhjl->nbhil", t, m, precision=_PREC)
    u = jnp.einsum("nbhij,nbhjv->nbhiv", t, vb, precision=_PREC)
    w = jnp.einsum("nbhij,nbhjk->nbhik", t, kb * jnp.exp(G)[..., None],
                   precision=_PREC)
    qk = jnp.einsum("nbhik,nbhjk->nbhij", q, k, precision=_PREC) * decay
    q_in = q * jnp.exp(G)[..., None]
    k_out = k * jnp.exp(G[..., -1:] - G)[..., None]
    g_end = jnp.exp(G[..., -1])                         # [n, b, h]

    def step(S, xs):
        u_c, w_c, qk_c, q_c, k_c, ge = xs
        d = u_c - jnp.einsum("bhik,bhkv->bhiv", w_c, S, precision=_PREC)
        o = (jnp.einsum("bhik,bhkv->bhiv", q_c, S, precision=_PREC)
             + jnp.einsum("bhij,bhjv->bhiv", qk_c, d, precision=_PREC))
        S = S * ge[..., None, None] + jnp.einsum(
            "bhik,bhiv->bhkv", k_c, d, precision=_PREC)
        return S, o

    S, o = jax.lax.scan(step, S, (u, w, qk, q_in, k_out, g_end))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3)       # [b, n, c, h, dv]
    return o.reshape(b, s, h, dv), S


def inputs(seed, b, s, nk, nv, dk, dv, zero_state=False):
    ks = jax.random.split(jax.random.key(seed), 6)
    q = jax.random.normal(ks[0], (b, s, nk, dk)) * dk ** -0.5
    k = jax.random.normal(ks[1], (b, s, nk, dk))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (b, s, nv, dv))
    g = -jax.nn.softplus(jax.random.normal(ks[3], (b, s, nv)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, nv)))
    S = jax.random.normal(ks[5], (b, nv, dk, dv))
    return q, k, v, g, beta, jnp.zeros_like(S) if zero_state else S


def run_kernel(q, k, v, g, beta, S, valid=None):
    """The kernel as ``gdn_block`` calls it: rows of heads x width, the
    positions past ``valid`` and up to a whole chunk with ``beta = g =
    0``; q and k keep their own (key) heads."""
    b, s, nv, dv = v.shape
    if valid is not None:
        g, beta = g * valid[..., None], beta * valid[..., None]
    pad = -s % CHUNK
    flat = [a.reshape(b, s, -1) for a in (q, k, v)]
    o, S = jax.jit(gdn_scan)(*(jnp.pad(a, [(0, 0), (0, pad), (0, 0)])
                               for a in (*flat, g, beta)), S)
    return o[:, :s].reshape(b, s, nv, dv), S


def per_value_head(q, k, nv):
    r = nv // q.shape[2]
    return jnp.repeat(q, r, axis=2), jnp.repeat(k, r, axis=2)


CASES = {
    # b, s, key heads, value heads, dk, dv
    "one_chunk": (1, 64, 2, 2, 16, 16),
    "ragged_last_chunk": (1, 100, 2, 2, 16, 16),
    "three_chunks": (1, 192, 2, 2, 16, 16),
    "many_chunks_two_grid_steps": (1, 64 * 16, 1, 1, 16, 16),
    "two_value_heads_a_key_head": (1, 192, 2, 4, 16, 16),
    "three_value_heads_a_key_head": (1, 128, 1, 3, 16, 16),
    "key_width_is_not_value_width": (1, 128, 2, 4, 16, 8),
    "batch_2": (2, 128, 2, 4, 16, 8),
}


@pytest.mark.parametrize("oracle", [recurrence, chunked_einsum])
@pytest.mark.parametrize("zero_state", [True, False],
                         ids=["from_zero", "from_S0"])
@pytest.mark.parametrize("case", list(CASES))
def test_the_kernel_is_the_rule(case, zero_state, oracle):
    b, s, nk, nv, dk, dv = CASES[case]
    q, k, v, g, beta, S0 = inputs(len(case), b, s, nk, nv, dk, dv,
                                  zero_state)
    o_got, S_got = run_kernel(q, k, v, g, beta, S0)
    pad = -s % CHUNK if oracle is chunked_einsum else 0
    want = jax.jit(oracle)(*(
        jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
        for a in (*per_value_head(q, k, nv), v, g, beta)), S0)
    np.testing.assert_allclose(o_got, want[0][:, :s], atol=2e-5)
    np.testing.assert_allclose(S_got, want[1], atol=2e-5)


def test_a_padded_tail_changes_neither_state_nor_real_outputs():
    """A row of a prefill bucket whose ``valid`` tail is padding: whatever
    the tail holds, the state is the one after the real positions and
    their outputs are the unpadded run's."""
    b, s, nk, nv, dk, dv = 2, 192, 2, 4, 16, 8
    q, k, v, g, beta, S0 = inputs(3, b, s, nk, nv, dk, dv)
    real = jnp.array([70, 128])
    valid = (jnp.arange(s)[None] < real[:, None]).astype(jnp.float32)
    o_got, S_got = run_kernel(q, k, v, g, beta, S0, valid)
    for i, n in enumerate(map(int, real)):
        row = [a[i:i + 1, :n] for a in (*per_value_head(q, k, nv), v, g,
                                        beta)]
        o_want, S_want = jax.jit(recurrence)(*row, S0[i:i + 1])
        np.testing.assert_allclose(o_got[i:i + 1, :n], o_want, atol=2e-5)
        np.testing.assert_allclose(S_got[i:i + 1], S_want, atol=2e-5)
    # and the state is not the one after the padded tail
    through = run_kernel(q, k, v, g, beta, S0)[1]
    assert float(jnp.abs(through - S_got).max()) > 1e-3
