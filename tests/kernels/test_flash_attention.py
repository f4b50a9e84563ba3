"""Pallas flash-attention kernel vs the XLA einsum reference.

Parity target mirrors the reference's use of flash_attn as a numerically
interchangeable fast path (megatron/model/transformer.py:508-523): same
math, tighter memory.  Runs in Pallas interpret mode on CPU.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_llm_tpu.kernels.flash_attention import flash_attention
from megatron_llm_tpu.ops.attention import dot_product_attention


def _rand_qkv(rng, b, sq, sk, hq, hk, d, dtype=jnp.float32):
    q = jnp.asarray(rng.standard_normal((b, sq, hq, d)), dtype)
    k = jnp.asarray(rng.standard_normal((b, sk, hk, d)), dtype)
    v = jnp.asarray(rng.standard_normal((b, sk, hk, d)), dtype)
    return q, k, v


@pytest.mark.parametrize(
    "b,sq,sk,hq,hk,d,causal",
    [
        (2, 256, 256, 4, 4, 64, True),     # MHA causal
        (2, 256, 256, 8, 2, 64, True),     # GQA causal
        (1, 256, 256, 4, 1, 64, True),     # MQA causal
        (2, 256, 256, 4, 4, 64, False),    # full attention
        (1, 200, 200, 4, 2, 64, True),     # non-multiple seq → padding path
        (1, 128, 256, 4, 4, 64, True),     # cross lengths (kv longer)
    ],
)
def test_forward_matches_reference(rng, b, sq, sk, hq, hk, d, causal):
    q, k, v = _rand_qkv(rng, b, sq, sk, hq, hk, d)
    out = flash_attention(q, k, v, causal=causal, block_q=128, block_k=128,
                          interpret=True)
    ref = dot_product_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_segment_ids_match_reference(rng):
    b, s, hq, hk, d = 2, 256, 4, 2, 64
    q, k, v = _rand_qkv(rng, b, s, s, hq, hk, d)
    # Packed sequences: 3 documents of uneven length per row.
    seg = np.zeros((b, s), np.int32)
    for row in range(b):
        bounds = sorted(rng.choice(np.arange(16, s - 16), 2, replace=False))
        seg[row, bounds[0]:bounds[1]] = 1
        seg[row, bounds[1]:] = 2
    seg = jnp.asarray(seg)
    out = flash_attention(q, k, v, causal=True, segment_ids=seg,
                          block_q=128, block_k=128, interpret=True)
    ref = dot_product_attention(q, k, v, causal=True, segment_ids=seg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("hq,hk", [(4, 4), (8, 2)])
def test_gradients_match_reference(rng, hq, hk):
    b, s, d = 1, 256, 64
    q, k, v = _rand_qkv(rng, b, s, s, hq, hk, d)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=True, block_q=128, block_k=128,
                            interpret=True)
        return jnp.sum(o * jnp.cos(jnp.arange(o.size).reshape(o.shape)))

    def loss_ref(q, k, v):
        o = dot_product_attention(q, k, v, causal=True)
        return jnp.sum(o * jnp.cos(jnp.arange(o.size).reshape(o.shape)))

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gr), atol=5e-5, rtol=5e-4,
            err_msg=f"d{name} mismatch")


def test_segment_gradients_match_reference(rng):
    b, s, hq, hk, d = 1, 256, 4, 2, 64
    q, k, v = _rand_qkv(rng, b, s, s, hq, hk, d)
    seg = jnp.asarray(
        np.repeat(np.arange(4), s // 4)[None, :].repeat(b, 0), jnp.int32)

    def loss(fn):
        def f(q, k, v):
            o = fn(q, k, v)
            return jnp.sum(jnp.tanh(o))
        return f

    g_flash = jax.grad(
        loss(lambda q, k, v: flash_attention(
            q, k, v, causal=True, segment_ids=seg, block_q=128, block_k=128,
            interpret=True)), argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(
        loss(lambda q, k, v: dot_product_attention(
            q, k, v, causal=True, segment_ids=seg)),
        argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gr), atol=5e-5, rtol=5e-4,
            err_msg=f"d{name} mismatch")


def test_bf16_inputs(rng):
    b, s, hq, hk, d = 1, 256, 4, 2, 64
    q, k, v = _rand_qkv(rng, b, s, s, hq, hk, d, jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True, block_q=128, block_k=128,
                          interpret=True)
    ref = dot_product_attention(q, k, v, causal=True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=3e-2, rtol=3e-2)


def test_jit_under_mesh(rng):
    """Kernel must be jittable (it runs inside the sharded train step)."""
    b, s, hq, hk, d = 1, 256, 4, 2, 64
    q, k, v = _rand_qkv(rng, b, s, s, hq, hk, d)
    f = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, causal=True, block_q=128, block_k=128, interpret=True))
    out = f(q, k, v)
    ref = dot_product_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("parallel,hq,hk,segs", [
    (dict(tensor_parallel=2), 4, 2, False),                    # heads / tp
    (dict(data_parallel=2, tensor_parallel=2), 4, 4, True),    # batch / dp
    (dict(tensor_parallel=2), 3, 1, False),    # MQA: tp divides no heads
], ids=["tp2_gqa", "dp2_tp2_segs", "tp2_mqa_replicated"])
def test_flash_under_mesh_matches_reference(rng, parallel, hq, hk, segs):
    """``attention(impl="flash")`` under a mesh runs the kernel inside a
    fully manual shard_map (the only form jax lowers for the TPU) — same
    values and gradients as the einsum path, however the axes split."""
    from megatron_llm_tpu.config import ParallelConfig
    from megatron_llm_tpu.ops.attention import attention
    from megatron_llm_tpu.parallel import mesh as mesh_lib

    mesh = mesh_lib.build_mesh(ParallelConfig(**parallel))
    b, s, d = 2, 128, 64
    q, k, v = _rand_qkv(rng, b, s, s, hq, hk, d)
    seg = None
    if segs:
        seg = jnp.asarray(np.repeat([[0, 1]], b, 0).repeat(s // 2, 1))

    def loss(impl):
        def f(q, k, v):
            with mesh_lib.use_mesh(mesh):
                o = attention(q, k, v, impl=impl, segment_ids=seg)
            return jnp.sum(o * o), o
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2),
                                          has_aux=True))

    (_, out), grads = loss("flash")(q, k, v)
    (_, ref), ref_grads = loss("dot")(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    for g, r in zip(grads, ref_grads):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   atol=1e-4, rtol=1e-4)
