"""Latent attention (MLA): the attention part of a ``"full"`` block where
``cfg.kv_lora_rank > 0`` (DeepSeek-V2/V3's, ``model_type: deepseek_v3``).

A position's keys and values, for all heads, are expanded from ONE latent
``c`` of ``kv_lora_rank`` values (after an RMSNorm of its own) through
``wkv_b``; beside it one rotated key part ``k_pe`` of ``qk_rope_head_dim``
is shared by all heads.  A head's query is ``q_nope | q_pe`` (the second
rotated as ``k_pe`` is), its key ``k_nope | k_pe``, its value
``v_head_dim`` wide; the softmax scale is that of the whole query width.
What is cached a position a layer is the row ``c | k_pe``
(``cfg.latent_row_width`` values, no head axis), kept in the cache
family's two leaves: ``c`` where another stack keeps keys, ``k_pe`` where
it keeps values (``models/model.py:init_kv_cache``).

Two forms of the one layer, from one parameter tree:

* *expanded*, wherever more than one position is computed and nothing of
  the sequence lies in a cache yet (a prompt, the uncached forward):
  ``c @ wkv_b`` gives every head's ``k_nope`` and ``v``, and causal
  attention runs at query width ``nope + rope`` and value width
  ``v_head_dim`` (the flash kernel, grown by a value width);
* *absorbed*, for new positions against cached rows (a decode step): with
  ``wkv_b`` read a head as ``W_uk | W_uv``, ``q_lat = q_nope @ W_uk^T``
  lives in the latent, the score is ``[q_lat | q_pe] . [c | k_pe]``, the
  weighted sum of the rows' first ``kv_lora_rank`` columns is ``o_lat``
  and ``o = o_lat @ W_uv``: multi-query attention of all heads on ONE row
  a position, whose value is the row's own head (``kernels/mla_decode.py``
  through the block tables; plain ``jax.numpy`` over a dense cache
  elsewhere).  ``W_uk``/``W_uv`` are views of ``wkv_b``, taken in the
  step.

Precision: the latent's norm and both rotations are float32, the row is
rounded once to the pool's precision; ``q_lat`` is accumulated in float32
and rounded once for the kernel, as the expanded form rounds ``k_nope``.
The rotation pairs adjacent columns (``rope_interleave``) and leaves them
where they lie; the published forward moves a pair's halves apart, q's
and k's alike, so the scores are the same.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..config import ModelConfig
from ..ops.norms import norm_apply, norm_init

Params = dict


def init_mla_params(key: jax.Array, cfg: ModelConfig, std: float,
                    out_std: float) -> Params:
    """``wq`` [h, heads x (nope + rope)], ``wkv_a`` [h, rank + rope],
    ``kv_norm`` [rank], ``wkv_b`` [rank, heads x (nope + v)] (a head:
    its key's part, then its value's), ``wo`` [heads x v, h]: the
    published shapes, transposed to this program's ``x @ w``."""
    h, nq, dtype = cfg.hidden_size, cfg.num_attention_heads, cfg.dtype
    r, dn, dr, dv = (cfg.kv_lora_rank, cfg.qk_nope_head_dim,
                     cfg.qk_rope_head_dim, cfg.v_head_dim)
    keys = jax.random.split(key, 4)

    def normal(k, shape, s):
        return (s * jax.random.normal(k, shape, jnp.float32)).astype(dtype)

    return {"wq": normal(keys[0], (h, nq * (dn + dr)), std),
            "wkv_a": normal(keys[1], (h, r + dr), std),
            "kv_norm": norm_init(cfg.norm_type, r, dtype),
            "wkv_b": normal(keys[2], (r, nq * (dn + dv)), std),
            "wo": normal(keys[3], (nq * dv, h), out_std)}


def _rotate(x, position_ids, theta: float, still: int, rope: int):
    """``x`` [b, s, heads x (still + rope)] as a matmul leaves it: of
    every head's columns the last ``rope`` rotated by position, adjacent
    columns a pair, in float32 → float32.  The angles come from
    ``position_ids`` [b, s] (no table), the ``still`` columns stand at
    angle 0, and a pair's partner is taken by a shift along the row
    (``ops/rope.py:apply_rope_flat``'s reason: a projection cut into
    heads at once is compiled by XLA:TPU as a dot with two output
    dimensions, which re-lays the whole weight in every layer of every
    call)."""
    assert still % 2 == 0 and rope % 2 == 0, (still, rope)
    inv_freq = 1.0 / (theta ** (
        jnp.arange(0, rope, 2, dtype=jnp.float32) / rope))
    ang = position_ids.astype(jnp.float32)[..., None] * inv_freq
    ang = jnp.concatenate([jnp.zeros(ang.shape[:-1] + (still,), jnp.float32),
                           jnp.repeat(ang, 2, axis=-1)], axis=-1)
    ang = jnp.tile(ang, x.shape[-1] // (still + rope))
    xf = x.astype(jnp.float32)
    even = jnp.arange(x.shape[-1]) % 2 == 0
    partner = jnp.where(even, -jnp.roll(xf, -1, axis=-1),
                        jnp.roll(xf, 1, axis=-1))
    return xf * jnp.cos(ang) + partner * jnp.sin(ang)


def latent_rows(cfg: ModelConfig, p: Params, x, position_ids, dtype):
    """``x`` [b, s, h] → the rows as the cache holds them, ``c`` [b, s,
    rank] and ``k_pe`` [b, s, rope]: the latent under its norm, the
    shared key part rotated, both in float32, rounded once to ``dtype``."""
    r = cfg.kv_lora_rank
    kv = jnp.dot(x, p["wkv_a"], preferred_element_type=jnp.float32)
    c = norm_apply(cfg.norm_type, kv[..., :r], p["kv_norm"], cfg.norm_eps)
    k_pe = _rotate(kv[..., r:], position_ids, cfg.rope_theta, 0,
                   cfg.qk_rope_head_dim)
    return c.astype(dtype), k_pe.astype(dtype)


def _queries(cfg: ModelConfig, p: Params, x, position_ids):
    """→ ``q_nope`` [b, s, heads, nope], ``q_pe`` [b, s, heads, rope]
    (rotated in float32, on the row as the matmul leaves it), in ``x``'s
    precision."""
    b, s, _ = x.shape
    dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    q = x @ p["wq"]
    q = _rotate(q, position_ids, cfg.rope_theta, dn, dr).astype(q.dtype)
    q = q.reshape(b, s, cfg.num_attention_heads, dn + dr)
    return q[..., :dn], q[..., dn:]


def _absorbed_over_dense(q_lat, q_pe, c_rows, pe_rows, cache_len,
                         scale: float):
    """``q_lat`` [b, s, heads, rank] and ``q_pe`` [b, s, heads, rope]
    against the dense cache ``c_rows`` [b, max_len, rank], ``pe_rows``
    [b, max_len, rope] holding the new positions already: query ``i``
    attends the rows up to ``cache_len + i`` (a scalar, or [b] fills) →
    ``o_lat`` [b, s, heads, rank] in the rows' precision, float32
    softmax."""
    s, max_len = q_lat.shape[1], c_rows.shape[1]
    scores = (jnp.einsum("bshc,bwc->bhsw", q_lat, c_rows,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bshc,bwc->bhsw", q_pe, pe_rows,
                           preferred_element_type=jnp.float32)) * scale
    first = jnp.asarray(cache_len, jnp.int32)
    last = first.reshape(-1, 1) + jnp.arange(s, dtype=jnp.int32)  # [b|1, s]
    keep = jnp.arange(max_len)[None, None, :] <= last[..., None]
    scores = jnp.where(keep[:, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(c_rows.dtype)
    return jnp.einsum("bhsw,bwr->bshr", probs, c_rows)


@jax.named_scope("attention")
def mla_block(cfg: ModelConfig, p: Params, x: jax.Array, side,
              kv_cache=None):
    """The latent-attention part on ``x`` [b, s, h] → ``out`` [b, s, h];
    with ``kv_cache`` → ``(out, (c_rows, pe_rows))``, the new positions'
    rows [b, 1, s, rank] and [b, 1, s, rope] for the caller's one write.

    ``kv_cache``: None (the expanded form, nothing kept); ``(c_cache,
    pe_cache, cache_len)``, the dense form [b, 1, max_len, rank | rope]
    (expanded where ``side.cache_is_empty`` promises an empty cache and
    there is more than one position, else absorbed over the cache); a
    ``transformer.PagedKV`` whose ``k_pool`` holds the latents and
    ``v_pool`` the rotated key parts (absorbed, through the block
    tables)."""
    from ..ops.attention import attention, latent_decode_attention

    b, s, _ = x.shape
    nq = cfg.num_attention_heads
    r, dn, dr, dv = (cfg.kv_lora_rank, cfg.qk_nope_head_dim,
                     cfg.qk_rope_head_dim, cfg.v_head_dim)
    scale = (1.0 / (dn + dr) ** 0.5 if cfg.attention_multiplier is None
             else cfg.attention_multiplier)
    position_ids = side.position_ids
    if position_ids is None:
        if kv_cache is not None:
            raise ValueError("kv_cache requires explicit position_ids")
        position_ids = jnp.broadcast_to(
            jnp.arange(s, dtype=jnp.int32)[None, :], (b, s))
    paged = hasattr(kv_cache, "tables")
    expanded = kv_cache is None or (
        not paged and side.cache_is_empty and s > 1)
    with jax.named_scope("mla_proj"):
        c, k_pe = latent_rows(cfg, p, x, position_ids, x.dtype)
        q_nope, q_pe = _queries(cfg, p, x, position_ids)
        if expanded:
            kv = (c @ p["wkv_b"]).reshape(b, s, nq, dn + dv)
            k = jnp.concatenate(
                [kv[..., :dn], jnp.broadcast_to(
                    k_pe[:, :, None, :], (b, s, nq, dr))], axis=-1)
            q = jnp.concatenate([q_nope, q_pe], axis=-1)
        else:
            # W_uk, a view of wkv_b: the query's key part into the latent
            w_kvb = p["wkv_b"].reshape(r, nq, dn + dv)
            q_lat = jnp.einsum("bshd,rhd->bshr", q_nope, w_kvb[..., :dn],
                               preferred_element_type=jnp.float32
                               ).astype(x.dtype)
    new = (c[:, None], k_pe[:, None])
    if expanded:
        ctx = attention(q, k, kv[..., dn:], impl=cfg.attention_impl,
                        causal=side.causal, segment_ids=side.segment_ids,
                        softmax_scale=scale, block_q=cfg.flash_block_q,
                        block_k=cfg.flash_block_k)
    else:
        if paged:
            o_lat = latent_decode_attention(
                q_lat, q_pe, kv_cache.k_pool, kv_cache.v_pool,
                kv_cache.tables, kv_cache.fills, *new, kv_cache.layer,
                softmax_scale=scale)
        else:
            from ..ops.kv_quant import cache_update

            c_cache, pe_cache, cache_len = kv_cache
            c_cache = cache_update(c_cache, new[0], cache_len)
            pe_cache = cache_update(pe_cache, new[1], cache_len)
            o_lat = _absorbed_over_dense(q_lat, q_pe, c_cache[:, 0],
                                         pe_cache[:, 0], cache_len, scale)
        with jax.named_scope("mla_proj"):
            # W_uv, the other view: the latent's weighted sum into heads
            ctx = jnp.einsum("bshr,rhd->bshd", o_lat.astype(x.dtype),
                             w_kvb[..., dn:])
    with jax.named_scope("mla_proj"):
        # (float32 out: it is added to the float32 stream as it is)
        out = jnp.dot(ctx.reshape(b, s, nq * dv), p["wo"],
                      preferred_element_type=jnp.float32)
    if kv_cache is not None:
        return out, new
    return out
