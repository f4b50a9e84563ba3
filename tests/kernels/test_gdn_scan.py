"""A prompt through a Gated DeltaNet mixer between its two projections as
one Pallas kernel (``kernels/gdn_scan.py``), through the interpreter at
small widths, against an oracle that composes the stages as ``jax.numpy``
wrote them before the kernel took them on (the convolution and its SiLU,
the L2 norms, the rule, RMSNorm x scale x SiLU(z)), kept here.  The rule
itself comes two ways: a position at a time
(``models/gated_deltanet.py:delta_rule_step``, what a decode step runs)
and the einsum form of the chunked arrangement that the kernel replaced
in the model (PR 37)."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_llm_tpu.kernels import gdn_scan as kernel
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


EPS = 1e-6


def stages(rule, nk, qkvz, g, beta, S, tail, conv_w, scale):
    """The mixer between its projections as ``jax.numpy`` stages: ``qkvz``
    [b, s, q | k | v | z], ``g beta`` [b, s, value heads], ``S`` [b,
    value heads, dk, dv], ``tail`` [b, taps - 1, q | k | v], ``conv_w``
    [taps, q | k | v], ``scale`` [dv] → ``(o [b, s, value heads x dv],
    S)``, the rule being ``rule``."""
    b, s, _ = qkvz.shape
    _, nv, dk, dv = S.shape
    kd, vd, taps = nk * dk, nv * dv, conv_w.shape[0]
    mixed, z = qkvz[..., :2 * kd + vd], qkvz[..., 2 * kd + vd:]
    full = jnp.concatenate([tail, mixed], axis=1)
    mixed = jax.nn.silu(sum(full[:, j:j + s] * conv_w[j]
                            for j in range(taps)))

    def unit(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True)
                                 + 1e-6)

    q = unit(mixed[..., :kd].reshape(b, s, nk, dk)) * dk ** -0.5
    k = unit(mixed[..., kd:2 * kd].reshape(b, s, nk, dk))
    v = mixed[..., 2 * kd:].reshape(b, s, nv, dv)
    pad = -s % CHUNK            # whole chunks for the einsum form: no-ops
    o, S = rule(*(jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
                  for a in (jnp.repeat(q, nv // nk, axis=2),
                            jnp.repeat(k, nv // nk, axis=2), v, g, beta)), S)
    o = o[:, :s]
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + EPS)
    o = o * scale * jax.nn.silu(z.reshape(b, s, nv, dv))
    return o.reshape(b, s, vd), S


def inputs(seed, b, s, nk, nv, dk, dv, zero_state=False):
    """``(qkvz, g, beta, S, tail, conv_w, scale)``; ``zero_state``: the
    start of a sequence, ``S`` and the tail zeros."""
    ks = jax.random.split(jax.random.key(seed), 7)
    ch = 2 * nk * dk + nv * dv
    qkvz = jax.random.normal(ks[0], (b, s, ch + nv * dv))
    g = -jax.nn.softplus(jax.random.normal(ks[1], (b, s, nv)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[2], (b, s, nv)))
    S = jax.random.normal(ks[3], (b, nv, dk, dv))
    tail = jax.random.normal(ks[4], (b, 3, ch))
    conv_w = jax.random.uniform(ks[5], (4, ch), minval=-0.5, maxval=0.5)
    scale = 1.0 + 0.3 * jax.random.normal(ks[6], (dv,))
    if zero_state:
        S, tail = jnp.zeros_like(S), jnp.zeros_like(tail)
    return qkvz, g, beta, S, tail, conv_w, scale


def run_kernel(qkvz, g, beta, *rest, valid=None):
    """The kernel as ``gdn_block`` calls it: the positions past ``valid``
    and up to a whole chunk with ``beta = g = 0``."""
    s = qkvz.shape[1]
    if valid is not None:
        g, beta = g * valid[..., None], beta * valid[..., None]
    pad = -s % CHUNK
    o, S = gdn_scan(*(jnp.pad(a, [(0, 0), (0, pad), (0, 0)])
                      for a in (qkvz, g, beta)), *rest, EPS)
    return o[:, :s], S


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
                         ids=["from_zero", "from_S0_and_tail"])
@pytest.mark.parametrize("case", list(CASES))
def test_the_kernel_is_the_stages(case, zero_state, oracle):
    b, s, nk, nv, dk, dv = CASES[case]
    args = inputs(len(case), b, s, nk, nv, dk, dv, zero_state)
    o_got, S_got = run_kernel(*args)
    o_want, S_want = jax.jit(stages, static_argnums=(0, 1))(oracle, nk, *args)
    np.testing.assert_allclose(o_got, o_want, atol=2e-5)
    np.testing.assert_allclose(S_got, S_want, atol=2e-5)


def test_a_padded_tail_changes_neither_state_nor_real_outputs():
    """A row of a prefill bucket whose ``valid`` tail is padding: whatever
    the tail holds, the state is the one after the real positions and
    their outputs are the unpadded run's."""
    b, s, nk, nv, dk, dv = 2, 192, 2, 4, 16, 8
    args = inputs(3, b, s, nk, nv, dk, dv)
    real = jnp.array([70, 128])
    valid = (jnp.arange(s)[None] < real[:, None]).astype(jnp.float32)
    o_got, S_got = run_kernel(*args, valid=valid)
    for i, n in enumerate(map(int, real)):
        row = [a[i:i + 1, :n] for a in args[:3]] + [a[i:i + 1]
                                                    for a in args[3:5]]
        o_want, S_want = jax.jit(stages, static_argnums=(0, 1))(
            recurrence, nk, *row, *args[5:])
        np.testing.assert_allclose(o_got[i:i + 1, :n], o_want, atol=2e-5)
        np.testing.assert_allclose(S_got[i:i + 1], S_want, atol=2e-5)
    # and the state is not the one after the padded tail
    through = run_kernel(*args)[1]
    assert float(jnp.abs(through - S_got).max()) > 1e-3


def test_the_convolution_reaches_across_every_border_of_grid_steps():
    """Eleven chunks go a chunk a grid step (no count from 2 to
    ``_STEP_CHUNKS`` divides them), so every chunk's first ``taps - 1``
    positions are convolved with raw rows that only the scratch still
    holds, and the first chunk's with the state's tail."""
    chunks, nk = 11, 2
    assert all(chunks % i for i in range(2, kernel._STEP_CHUNKS + 1))
    args = inputs(11, 1, chunks * CHUNK, nk, 4, 16, 16)
    o_got, S_got = run_kernel(*args)
    o_want, S_want = jax.jit(stages, static_argnums=(0, 1))(
        recurrence, nk, *args)
    np.testing.assert_allclose(o_got, o_want, atol=2e-5)
    np.testing.assert_allclose(S_got, S_want, atol=2e-5)
    # with another tail the first positions differ, and only they
    other = run_kernel(*args[:4], args[4] + 1.0, *args[5:])[0]
    assert float(jnp.abs(other - o_got)[:, :3].max()) > 1e-3
    np.testing.assert_allclose(other[:, CHUNK:], o_got[:, CHUNK:], atol=2e-5)


@pytest.mark.parametrize("case,in_place", [
    ("two_value_heads_a_key_head", True),     # every offset a whole block
    ("three_value_heads_a_key_head", False),  # v at 32 in blocks of 48
])
def test_operands_are_read_in_place_where_offsets_are_whole_blocks(
        case, in_place):
    """q, k, v and z are column blocks of the projection's one output (and
    the tail's and the taps' of theirs) where every part starts on a
    whole block of its width; else the same kernel takes XLA's slices."""
    _, _, nk, nv, dk, dv = CASES[case]
    args = inputs(0, *CASES[case])
    outer = jax.make_jaxpr(lambda *a: gdn_scan(*a, EPS))(*args)
    (jitted,) = outer.eqns
    (call,) = [e for e in jitted.params["jaxpr"].eqns
               if e.primitive.name == "pallas_call"]
    widths = [v.aval.shape[-1] for v in call.invars[:10]]
    whole, ch = args[0].shape[-1], args[4].shape[-1]
    parts = [nk * dk, nk * dk, nv * dv]
    assert widths == ([whole] * 4 + [ch] * 6 if in_place
                      else parts + [nv * dv] + parts * 2)
