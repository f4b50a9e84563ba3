"""The latent walk (kernels/mla_decode.py), the flash forward at a value
width of its own, and the grouped experts' kernel addressing its layer of
a stack: each interpreted at its smallest tiles against the plain
composition."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_llm_tpu.kernels.flash_attention import flash_attention
from megatron_llm_tpu.kernels.grouped_matmul import grouped_mlp
from megatron_llm_tpu.kernels.mla_decode import _walk_blocks, mla_decode
from megatron_llm_tpu.ops.attention import dot_product_attention

S, T, BK, H, R, P, L = 5, 6, 8, 4, 32, 8, 3
SCALE = 0.2


def plain_walk(q_lat, q_pe, c_pool, pe_pool, tables, fills, c_new, pe_new,
               layer):
    """Each slot's rows gathered by its table, the new row behind them,
    one softmax over the pooled rows under the fill and the new one."""
    out = []
    for s in range(S):
        c = c_pool[layer, tables[s], 0].reshape(T * BK, R)[:fills[s]]
        pe = pe_pool[layer, tables[s], 0].reshape(T * BK, P)[:fills[s]]
        c = jnp.concatenate([c, c_new[s]])
        pe = jnp.concatenate([pe, pe_new[s]])
        p = jax.nn.softmax((q_lat[s] @ c.T + q_pe[s] @ pe.T) * SCALE, -1)
        out.append(p @ c)
    return jnp.stack(out)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-6),
                                       (jnp.bfloat16, 2e-2)])
def test_the_latent_walk_against_the_plain_composition(dtype, tol):
    """Fills that end inside a block, on its edge, on an iteration's edge
    (three blocks an iteration here), an empty slot and a full table;
    a layer in the middle of the pool; the table's dead entries point at
    the trash block, which holds NaN: the walk never reads past a fill."""
    ks = jax.random.split(jax.random.key(0), 6)
    nb = S * T + 1
    assert _walk_blocks(BK, R + P, 4, T) == 6 and _walk_blocks(
        128, 576, 2, 132) == 4
    rnd = lambda k, shape: jax.random.normal(k, shape).astype(dtype)  # noqa: E731
    c_pool = rnd(ks[0], (L, nb, 1, BK, R)).at[:, 0].set(jnp.nan)
    pe_pool = rnd(ks[1], (L, nb, 1, BK, P)).at[:, 0].set(jnp.nan)
    q_lat, q_pe = rnd(ks[2], (S, H, R)), rnd(ks[3], (S, H, P))
    c_new, pe_new = rnd(ks[4], (S, 1, R)), rnd(ks[5], (S, 1, P))
    fills = np.array([0, 5, 8, 24, 47])
    tables = np.random.default_rng(0).permutation(
        np.arange(1, nb)).reshape(S, T)
    live = -(-fills // BK)
    tables = np.where(np.arange(T)[None] < live[:, None], tables, 0)
    got = jax.jit(lambda *a: mla_decode(
        *a, softmax_scale=SCALE, interpret=True))(
            q_lat, q_pe, c_pool, pe_pool, jnp.asarray(tables),
            jnp.asarray(fills), c_new, pe_new, jnp.int32(1))
    f32 = lambda a: a.astype(jnp.float32)     # noqa: E731
    want = jax.jit(lambda *a: plain_walk(*a[:4], tables, fills, *a[4:], 1))(
        *map(f32, (q_lat, q_pe, c_pool, pe_pool, c_new, pe_new)))
    assert got.dtype == jnp.float32 and got.shape == (S, H, R)
    np.testing.assert_allclose(got, want, atol=tol)
    # the empty slot attends its own new row alone: that row's latent
    np.testing.assert_allclose(got[0], jnp.broadcast_to(
        f32(c_new[0]), (H, R)), atol=1e-6)


def test_flash_forward_at_a_value_width_of_its_own():
    """Keys of 24 beside values of 16 (latent attention's expanded form:
    192 beside 128), a length that is no whole tile; the same call at one
    width goes through the kernel's custom derivative as before."""
    ks = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(ks[0], (2, 150, 4, 24))
    k = jax.random.normal(ks[1], (2, 150, 4, 24))
    v = jax.random.normal(ks[2], (2, 150, 4, 16))
    got = flash_attention(q, k, v, causal=True, softmax_scale=SCALE,
                          interpret=True)
    want = dot_product_attention(q, k, v, causal=True, softmax_scale=SCALE)
    assert got.shape == (2, 150, 4, 16)
    np.testing.assert_allclose(got, want, atol=2e-6)
    grad = jax.grad(lambda v: flash_attention(
        q, k, v, causal=True, softmax_scale=SCALE,
        interpret=True).sum())(jnp.pad(v, ((0, 0),) * 3 + ((0, 8),)))
    assert grad.shape == (2, 150, 4, 24)


def test_the_grouped_experts_address_their_layer_of_a_stack():
    """The kernel handed a whole stack's matrices and a layer gives what
    it gives handed that layer's matrices: bit for bit."""
    E, h, f, g, k = 4, 128, 32, 12, 2
    ks = jax.random.split(jax.random.key(2), 5)
    w_gate, w_up = (jax.random.normal(kk, (3, E, h, f)) for kk in ks[:2])
    w_down = jax.random.normal(ks[2], (3, E, f, h))
    x = jax.random.normal(ks[3], (g, h))
    choice = jax.random.randint(ks[4], (g, k), 0, E)
    pairs = (jnp.arange(g) << 1)[:, None] | jnp.arange(k)
    keys = jnp.sort(((choice << 8) | pairs).reshape(-1))
    sizes = jnp.bincount(keys >> 8, length=E)
    act = lambda a: jax.nn.silu(a[..., :f]) * a[..., f:]    # noqa: E731
    run = jax.jit(lambda *w, **kw: grouped_mlp(
        x, keys & 255, sizes, *w, act, choices=k, interpret=True, **kw))
    for layer in (0, 2):
        np.testing.assert_array_equal(
            run(w_gate, w_up, w_down, layer=jnp.int32(layer)),
            run(w_gate[layer], w_up[layer], w_down[layer]))
