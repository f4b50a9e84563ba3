"""The two kernel forms a stack of runs opens, in interpret mode against
the plain composition: ``flash_attention`` under a window and at a value
head shared by consecutive key heads (the band's live tiles counted), and
the paged walk at such a value head of a width of its own; and the
kernel of a window layer's decode step on the stacked rings."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_llm_tpu.kernels import flash_attention as fa
from megatron_llm_tpu.kernels import flash_decode as fd
from megatron_llm_tpu.kernels.flash_attention import (flash_attention,
                                                       tile_plan)
from megatron_llm_tpu.kernels.ring_decode import ring_decode
from megatron_llm_tpu.ops.attention import dot_product_attention

# An interpreted kernel is traced and lowered in Python for seconds a bare
# call: the decode kernels below are called through one jit each, so the
# calls of one set of shapes (the two fills, a ring and its stale twin, the
# two layers: a traced index) are traced once a process.
_paged = jax.jit(functools.partial(fd.flash_decode_paged, softmax_scale=0.25,
                                   interpret=True))
_ring = jax.jit(functools.partial(ring_decode, softmax_scale=0.25,
                                  interpret=True))


def _rand(k, shape):
    return jax.random.normal(jax.random.key(k), shape, jnp.float32)


def _masked_reference(q, k, v, window):
    """``dot_product_attention`` under an explicit band, the value heads
    repeated up to the key heads."""
    s = q.shape[1]
    pos = jnp.arange(s)
    keep = pos[None, :] <= pos[:, None]
    if window:
        keep = keep & (pos[None, :] > pos[:, None] - window)
    bias = jnp.where(keep, 0.0, -jnp.inf)[None, None]
    v = jnp.repeat(v, k.shape[2] // v.shape[2], axis=2)
    return dot_product_attention(q, k, v, causal=False, bias=bias)


@pytest.mark.parametrize("s,window,block,live", [
    (512, 128, 128, 7),      # four row blocks: the diagonal and one before
    (300, 100, 128, 5),      # a ragged end; three row blocks
    (384, 384, 128, 6),      # a window as long as the prompt: the triangle
    (256, 1, 128, 2),        # a query sees itself alone
])
def test_a_window_is_the_masked_composition_on_the_bands_tiles(
        s, window, block, live):
    q, k, v = _rand(0, (2, s, 8, 16)), _rand(1, (2, s, 4, 16)), _rand(
        2, (2, s, 2, 32))
    got = flash_attention(q, k, v, window=window, block_q=block,
                          block_k=block, interpret=True)
    want = _masked_reference(q, k, v, window)
    assert got.shape == (2, s, 8, 32)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    plan = tile_plan(s, s, block, block, True, window)
    assert plan.live == live
    # and the kernel's grid is those tiles, no more
    traced = str(jax.make_jaxpr(lambda q, k, v: flash_attention(
        q, k, v, window=window, block_q=block, block_k=block,
        interpret=True))(q, k, v))
    assert f"grid=(2, 8, {live})" in traced
    # the triangle it replaces is larger wherever the window is shorter
    assert live <= tile_plan(s, s, block, block, True).live


def test_a_window_off_by_one_is_another_result():
    q, k, v = _rand(0, (1, 256, 4, 16)), _rand(1, (1, 256, 2, 16)), _rand(
        2, (1, 256, 1, 32))
    got = flash_attention(q, k, v, window=65, block_q=128, block_k=128,
                          interpret=True)
    want = _masked_reference(q, k, v, 64)
    assert float(jnp.abs(got - want).max()) > 1e-3


def test_a_shared_value_head_without_a_window_is_causal_attention():
    q, k, v = _rand(0, (1, 256, 8, 16)), _rand(1, (1, 256, 4, 16)), _rand(
        2, (1, 256, 2, 32))
    got = flash_attention(q, k, v, block_q=128, block_k=128, interpret=True)
    np.testing.assert_allclose(got, _masked_reference(q, k, v, 0),
                               atol=2e-5, rtol=2e-5)


def test_the_backward_kernels_refuse_a_window_loudly():
    q, k, v = _rand(0, (1, 128, 2, 16)), _rand(1, (1, 128, 2, 16)), _rand(
        2, (1, 128, 2, 16))
    with pytest.raises(NotImplementedError, match="forward only"):
        jax.grad(lambda q: flash_attention(
            q, k, v, window=32, interpret=True).sum())(q)
    # no window, as many value heads: the backward stands
    jax.grad(lambda q: flash_attention(q, k, v, interpret=True).sum())(q)


def test_a_tile_plan_without_a_window_is_what_it_was():
    assert tile_plan(1280, 1280) == tile_plan(1280, 1280, 1024, 1024, True,
                                              0)
    live, masked = fa._tiles(1280, 1280, 640, 640, True)
    assert (int(live.sum()), int(masked.sum())) == (3, 2)


@pytest.mark.parametrize("fills", [(0, 1, 15, 16, 17, 40, 62, 63),
                                   (63, 63, 3, 0, 33, 20, 5, 48)])
def test_the_paged_walk_at_a_value_head_shared_by_two_key_heads(fills):
    """8 query heads of 16 on 4 key heads of 16 and 2 value heads of 32:
    key head h attends with value head h // 2, the new token's own rows
    folded in, against the plain composition over the gathered rows."""
    S, T, bk, d, dv = len(fills), 4, 16, 16, 32
    nb = S * T + 1
    kp, vp = _rand(0, (2, nb, 4, bk, d)), _rand(1, (2, nb, 2, bk, dv))
    q = _rand(2, (S, 8, d))
    kn, vn = _rand(3, (S, 4, 1, d)), _rand(4, (S, 2, 1, dv))
    fills = np.asarray(fills, np.int32)
    tables = np.zeros((S, T), np.int32)
    ids = np.random.default_rng(0).permutation(np.arange(1, nb))
    n = 0
    for i in range(S):
        used = -(-int(fills[i] + 1) // bk)
        tables[i, :used] = ids[n:n + used]
        n += used
    got = _paged(q, kp, vp, tables, fills, new_rows=(kn, vn),
                 layer=jnp.int32(1))
    assert got.shape == (S, 8, dv)
    kd = jnp.moveaxis(kp[1][tables], 2, 1).reshape(S, 4, T * bk, d)
    vd = jnp.moveaxis(vp[1][tables], 2, 1).reshape(S, 2, T * bk, dv)
    kd, vd = (jnp.concatenate([a, b], axis=2) for a, b in ((kd, kn),
                                                           (vd, vn)))
    cols = jnp.arange(T * bk + 1)[None, :]
    keep = (cols < fills[:, None]) | (cols == T * bk)
    scores = jnp.einsum("shgd,shkd->shgk", q.reshape(S, 4, 2, d), kd) * 0.25
    probs = jax.nn.softmax(jnp.where(keep[:, None, None], scores, -jnp.inf),
                           axis=-1)
    want = jnp.einsum("shgk,shkw->shgw", probs,
                      jnp.repeat(vd, 2, axis=1)).reshape(S, 8, dv)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_the_walks_shape_at_a_shared_value_head():
    """The cell's geometry: 20 key heads of 64, packed by pairs into ten
    of 128, in blocks of 128 rows take all ten heads and two blocks an
    iteration (256 columns a term; a float32 pool's five: two grid steps
    a slot); the plain rule is what it was, with two blocks where 2.5
    MiB hold them and 2 did not (20 heads of 64 a copy: no cell's)."""
    assert fd._walk_shape(10, 128, 128, 2, 64, packed=True) == (10, 2)
    assert fd._walk_shape(10, 128, 128, 4, 64, packed=True) == (5, 2)
    assert fd._walk_shape(10, 128, 128, 2, 1, packed=True) == (10, 1)
    assert fd._walk_shape(20, 128, 64, 2, 64) == (20, 2)
    assert fd._walk_shape(1, 128, 64, 2, 16) == (1, 4)


@pytest.mark.parametrize("layer", [0, 2])
def test_the_ring_kernel_is_the_plain_composition(layer):
    """``ring_decode`` on layer ``layer`` of three stacked rings of 8
    positions (8 query heads of 16 on 4 key heads of 16 and 2 value heads
    of 32) against ``attend_ring``'s plain composition: an empty ring, a
    ring not yet full, the first wrap, and far past it — the row the
    new position will take is never counted."""
    from megatron_llm_tpu.config import phi4flash_config
    from megatron_llm_tpu.models import diff_attention

    L, S, kv, d, W = 3, 6, 4, 16, 8
    q = _rand(0, (S, 8, d))
    ring_k, ring_v = (_rand(i, (L, S, 2, W, 2 * d)) for i in (1, 2))
    kn, vn = _rand(3, (S, kv, 1, d)), _rand(4, (S, 2, 1, 2 * d))
    pos = jnp.asarray([0, 3, 7, 8, 13, 30], jnp.int32)
    got = _ring(q, ring_k, ring_v, kn, vn, pos, jnp.int32(layer))
    cfg = phi4flash_config(attention_multiplier=0.25)
    assert cfg.attention_impl != "flash"
    want = diff_attention.attend_ring(cfg, q[:, None], ring_k, ring_v,
                                      jnp.int32(layer), kn, vn, pos)[:, 0]
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    # the stale row holds anything: it must not reach the output
    stale = ring_k.at[layer, 4, :, 13 % W].set(1e4)
    again = _ring(q, stale, ring_v, kn, vn, pos, jnp.int32(layer))
    np.testing.assert_array_equal(again[4], got[4])


# --- the same two kernels at the period scan's "window" kind: ordinary
# grouped heads, 8 query heads a KV head, a ring row ONE key head's (r = 1)

@pytest.mark.parametrize("s,window,block,live", [
    (512, 128, 128, 7),
    (300, 100, 128, 5),
    (640, 256, 128, 12),     # a window of two tiles: three tiles a row block
])
def test_a_window_at_eight_query_heads_a_kv_head(s, window, block, live):
    """``flash_attention(window=)`` at 16 query heads on 2 KV heads, keys
    and values of one width (no shared value head): the masked plain
    product, on the band's tiles alone."""
    q, k, v = _rand(0, (2, s, 16, 16)), _rand(1, (2, s, 2, 16)), _rand(
        2, (2, s, 2, 16))
    got = flash_attention(q, k, v, window=window, block_q=block,
                          block_k=block, interpret=True)
    want = _masked_reference(q, k, v, window)
    assert got.shape == (2, s, 16, 16)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    assert tile_plan(s, s, block, block, True, window).live == live
    off = flash_attention(q, k, v, window=window + 1, block_q=block,
                          block_k=block, interpret=True)
    assert np.abs(off - want).max() > 1e-3


def _ring_reference(q, ring_k, ring_v, kn, vn, pos, scale):
    """One new position a slot on its ring, written out: slot ``b`` at
    position ``p`` sees the ring's rows that hold positions ``p - W + 1 ..
    p - 1`` (row ``c`` holds one where ``c < p`` and ``c != p % W``) and
    its own new row; query head ``j`` reads KV head ``j // (heads / kv)``."""
    S, heads, d = q.shape
    kv, W = ring_k.shape[1], ring_k.shape[2]
    out = np.zeros((S, heads, d), np.float32)
    for b in range(S):
        p = int(pos[b])
        rows = [c for c in range(W) if c < p and c != p % W]
        for j in range(heads):
            g = j // (heads // kv)
            keys = np.concatenate([ring_k[b, g, rows], kn[b, g]])
            vals = np.concatenate([ring_v[b, g, rows], vn[b, g]])
            sc = keys @ q[b, j] * scale
            w = np.exp(sc - sc.max())
            out[b, j] = (w / w.sum()) @ vals
    return out


@pytest.mark.parametrize("layer", [0, 2])
def test_the_ring_kernel_at_one_key_head_a_row(layer):
    """``ring_decode`` at ``r = 1``: 16 query heads of 16 on 2 KV heads of
    16, 8 query rows a KV head, on layer ``layer`` of three stacked rings
    of 8 positions, against a masked plain product written out; and
    ``attend_ring``'s plain composition agrees.  The rows hold keys
    already rotated at their own positions, so neither knows of a
    rotation: the count mask stands as it is."""
    from megatron_llm_tpu.config import laguna_config
    from megatron_llm_tpu.models import diff_attention

    L, S, kv, d, W, heads = 3, 6, 2, 16, 8, 16
    q = _rand(0, (S, heads, d))
    ring_k, ring_v = (_rand(i, (L, S, kv, W, d)) for i in (1, 2))
    kn, vn = _rand(3, (S, kv, 1, d)), _rand(4, (S, kv, 1, d))
    pos = jnp.asarray([0, 3, 7, 8, 13, 30], jnp.int32)
    got = _ring(q, ring_k, ring_v, kn, vn, pos, jnp.int32(layer))
    want = _ring_reference(*(np.asarray(a) for a in (
        q, ring_k[layer], ring_v[layer], kn, vn, pos)), 0.25)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    cfg = laguna_config(
        hidden_size=64, num_attention_heads=4, window_attention_heads=heads,
        num_kv_heads=kv, kv_channels=d, num_experts=8, moe_top_k=2,
        sliding_window=W, vocab_size=512, make_vocab_size_divisible_by=8,
        attention_multiplier=0.25).window_layer_config
    assert cfg.attention_impl != "flash"
    plain = diff_attention.attend_ring(cfg, q[:, None], ring_k, ring_v,
                                       jnp.int32(layer), kn, vn, pos)[:, 0]
    np.testing.assert_allclose(plain, want, atol=2e-5, rtol=2e-5)
    # the stale row holds anything: it must not reach the output
    stale = ring_k.at[layer, 4, :, 13 % W].set(1e4)
    again = _ring(q, stale, ring_v, kn, vn, pos, jnp.int32(layer))
    np.testing.assert_array_equal(again[4], got[4])
