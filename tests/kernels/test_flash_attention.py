"""Pallas flash-attention kernel vs the XLA einsum reference.

Parity target mirrors the reference's use of flash_attn as a numerically
interchangeable fast path (megatron/model/transformer.py:508-523): same
math, tighter memory.  Runs in Pallas interpret mode on CPU.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_llm_tpu.kernels.flash_attention import (
    flash_attention,
    tile_plan,
)
from megatron_llm_tpu.ops.attention import dot_product_attention


def _rand_qkv(rng, b, sq, sk, hq, hk, d, dtype=jnp.float32, dv=None):
    q = jnp.asarray(rng.standard_normal((b, sq, hq, d)), dtype)
    k = jnp.asarray(rng.standard_normal((b, sk, hk, d)), dtype)
    v = jnp.asarray(rng.standard_normal((b, sk, hk, dv or d)), dtype)
    return q, k, v


@pytest.mark.parametrize("sq,sk,bound,causal,want", [
    # one length under the default bound: (block, live, masked, padded)
    (1024, 1024, 1024, True, (1024, 1024, 1, 1, 0)),
    (1280, 1280, 1024, True, (640, 640, 3, 2, 0)),
    (1408, 1408, 1024, True, (768, 768, 3, 2, 128)),
    (1536, 1536, 1024, True, (768, 768, 3, 2, 0)),
    (1792, 1792, 1024, True, (896, 896, 3, 2, 0)),
    (2048, 2048, 1024, True, (1024, 1024, 3, 2, 0)),
    (300, 300, 1024, True, (384, 384, 1, 1, 84)),
    # 16 row blocks: 136 tiles on or under the diagonal, 16 on it
    (16384, 16384, 1024, True, (1024, 1024, 136, 16, 0)),
    # a wider bound: one tile where it fits, four row blocks at 16 384
    (300, 300, 4096, True, (384, 384, 1, 1, 84)),
    (1024, 1024, 4096, True, (1024, 1024, 1, 1, 0)),
    (1280, 1280, 4096, True, (1280, 1280, 1, 1, 0)),
    (1536, 1536, 4096, True, (1536, 1536, 1, 1, 0)),
    (1792, 1792, 4096, True, (1792, 1792, 1, 1, 0)),
    (1408, 1408, 4096, True, (1408, 1408, 1, 1, 0)),
    (2048, 2048, 4096, True, (2048, 2048, 1, 1, 0)),
    (16384, 16384, 4096, True, (4096, 4096, 10, 4, 0)),
    # the diagonal off the corner: row i keeps columns <= i + 128, so
    # both column blocks are live and only the second is crossed
    (128, 256, 128, True, (128, 128, 2, 1, 0)),
    # and the other way: row i keeps columns <= i - 128, so the diagonal
    # crosses the second row block's one tile (the first row block sees
    # nothing and still runs its first tile, masked, to write its rows)
    (256, 128, 128, True, (128, 128, 2, 2, 0)),
    # nothing causal: every tile, a mask on the ragged last column alone
    (1280, 1280, 1024, False, (640, 640, 4, 0, 0)),
    (1300, 1300, 1024, False, (768, 768, 4, 2, 236)),
], ids=lambda v: str(v).replace(" ", ""))
def test_tile_plan_counts_what_the_kernel_walks(sq, sk, bound, causal, want):
    """The schedule is a pure function of shapes: the fewest equal blocks
    under the bound, each a multiple of 128, so the padding is under 128
    rows a block; ``live`` and ``masked`` are the counts by hand."""
    plan = tile_plan(sq, sk, bound, bound, causal)
    assert tuple(plan) == want
    blocks = -(-sq // plan.block_q)
    assert plan.block_q % 128 == 0 and plan.block_q <= max(bound, 128)
    assert 0 <= plan.padded_rows < 128 * blocks
    assert blocks == -(-sq // bound)      # no more blocks than the old cut
    assert plan.masked <= plan.live <= blocks * -(-sk // plan.block_k)


@pytest.mark.parametrize("sq,sk,hq,hk,d,dv,segs", [
    (1280, 1280, 2, 1, 64, 64, False),     # blocks of 640
    (1792, 1792, 2, 1, 64, 64, False),     # blocks of 896
    (1408, 1408, 1, 1, 64, 64, False),     # 2 x 768 with 128 padded rows
    (640, 1408, 2, 2, 64, 64, False),      # sk > sq: diagonal off the corner
    (1280, 1280, 2, 1, 64, 64, True),      # packed documents over 4 tiles
    (1536, 1536, 2, 2, 192, 128, False),   # a value width of its own
], ids=["1280", "1792", "1408_padded", "sk_gt_sq", "segment_ids",
        "192_128"])
def test_forward_on_tiles_cut_from_the_length(rng, sq, sk, hq, hk, d, dv,
                                              segs):
    """Under the default bound of 1024 these lengths run in blocks of 640,
    896 and 768: the tiles under and on the diagonal and at the ragged
    end, none above it."""
    q, k, v = _rand_qkv(rng, 1, sq, sk, hq, hk, d, dv=dv)
    seg = None
    if segs:
        seg = jnp.asarray(np.repeat([[0, 1, 2, 3, 4]], sq // 5, 1))
    out = flash_attention(q, k, v, causal=True, segment_ids=seg,
                          interpret=True)
    ref = dot_product_attention(q, k, v, causal=True, segment_ids=seg)
    assert out.shape == (1, sq, hq, dv)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("bound", [128, 256],
                         ids=["a_dead_row_block", "dead_rows_in_a_live_tile"])
def test_queries_that_see_no_key_come_out_zero(rng, bound):
    """Causal with more queries than keys: the first ``sq - sk`` queries
    lie before every key.  Their row block's first tile runs all the same
    (every output block is written) and they come out 0; the rest is the
    square problem."""
    sq, sk = 256, 128
    q, k, v = _rand_qkv(rng, 1, sq, sk, 2, 1, 64)
    out = flash_attention(q, k, v, causal=True, block_q=bound,
                          block_k=bound, interpret=True)
    ref = dot_product_attention(q[:, sq - sk:], k, v, causal=True)
    np.testing.assert_array_equal(np.asarray(out[:, :sq - sk]), 0.0)
    np.testing.assert_allclose(np.asarray(out[:, sq - sk:]),
                               np.asarray(ref), atol=2e-5, rtol=2e-5)


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


@pytest.mark.parametrize("hq,hk,s,block", [
    (4, 4, 256, 128), (8, 2, 256, 128),
    (2, 1, 1280, 1024),     # no multiple of its old block: 2 x 640
], ids=["mha", "gqa", "1280_at_640"])
def test_gradients_match_reference(rng, hq, hk, s, block):
    b, d = 1, 64
    q, k, v = _rand_qkv(rng, b, s, s, hq, hk, d)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=True, block_q=block,
                            block_k=block, interpret=True)
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
