"""Differential attention (arXiv 2410.05258) as the attention part of a
stack of runs: the "full", "window" and "cross" block kinds.

Adjacent query heads pair (``q_{p,1}, q_{p,2}``, ``p`` = 0..heads/2 - 1),
adjacent key and value heads pair (``g`` = 0..kv_heads/2 - 1), query pair
``p`` reads key/value pair ``g = p // 2``.  With ``V_g = [v_{g,1} |
v_{g,2}]`` (twice a head wide)::

    A_{p,j} = softmax(q_{p,j} k_{g,j}^T / sqrt(d)) V_g          j = 1, 2
    lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam_init(layer)
    lam_init(i) = 0.8 - 0.6 exp(-0.3 i)
    o_p = (1 - lam_init) * RMSNorm(A_{p,1} - lam * A_{p,2})
    out = [o_0 | o_1 | ...] W_o + b_o

So the two softmaxes are ORDINARY attention at ``heads`` query heads of
``d``, ``kv_heads`` key heads of ``d`` and ``kv_heads / 2`` value heads of
``2 d``: nothing differential reaches a kernel.  The kernels group
consecutive query heads over a key head, and the pairing above puts the
query heads ``4g + j`` and ``4g + 2 + j`` on key head ``2g + j``: the
queries go in with the two middle axes of ``[g, pair of the two, j]``
swapped, and the outputs come back the same way (``_to_kernel_order``).

What is cached a position is one row of keys ``[kv_heads, d]`` and one of
values ``[kv_heads / 2, 2 d]``: the same bytes as ``kv_heads`` value
heads of ``d``, read as they lie, no second copy.

The forms, by what the keys and values are:

* ``attend_seq``: a whole sequence on itself, causal, with or without a
  window (the flash kernel, or the plain composition).
* ``attend_rows``: a few query rows, each at a position of its own, on
  dense keys and values ``[b, heads, S, width]`` (a prefill's one row
  past the boundary between the decoders; the dense decode route).
* ``attend_ring``: one new position a slot on the slot's ring of the
  last ``window`` keys and values (a "window" layer's decode step).
* ``ops/attention.py:paged_decode_attention``: one new position a slot
  on the block pool (the "full" and "cross" layers' decode step).

Every form takes its operands in the weights' precision and returns the
attention output in it; ``finish`` works in float32.  ``attend_full`` and
``attend_handed`` are the "full" and the "cross" layer's attention part
whole: the projections, the form its cache asks for, ``finish``.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..config import ModelConfig
from ..ops.norms import rmsnorm_ref

Params = dict

LAMBDA_STD = 0.1


def lambda_init(layer):
    """``0.8 - 0.6 exp(-0.3 layer)``; ``layer`` may be traced."""
    return 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(layer, jnp.float32))


def init_diff_attn_params(key: jax.Array, cfg: ModelConfig,
                          cross: bool = False) -> Params:
    """A layer's attention part: ``wq wk wv wo`` with their biases, the
    four ``lam`` vectors (float32) and the pair norm's weight.  A
    ``cross`` layer projects a query alone."""
    h, d, dtype, std = (cfg.hidden_size, cfg.head_dim, cfg.dtype,
                        cfg.init_method_std)
    nq, nkv = cfg.num_attention_heads, cfg.kv_heads
    ks = jax.random.split(key, 8)

    def normal(k, shape, s=std, dt=dtype):
        return (s * jax.random.normal(k, shape, jnp.float32)).astype(dt)

    p = {"wq": normal(ks[0], (h, nq * d)), "bq": jnp.zeros((nq * d,), dtype),
         "wo": normal(ks[3], (nq * d, h)), "bo": jnp.zeros((h,), dtype),
         "lam": normal(ks[4], (4, d), LAMBDA_STD, jnp.float32),
         "pair_norm": {"scale": jnp.ones((2 * d,), dtype)}}
    if not cross:
        p.update(wk=normal(ks[1], (h, nkv * d)),
                 bk=jnp.zeros((nkv * d,), dtype),
                 wv=normal(ks[2], (h, nkv * d)),
                 bv=jnp.zeros((nkv * d,), dtype))
    return p


def _to_kernel_order(x, cfg: ModelConfig):
    """``x`` [..., heads, w], heads as ``[g, which pair of the two, j]``
    <-> as ``[g, j, which pair]``: consecutive heads share a key head.
    Its own inverse."""
    lead, (n, w) = x.shape[:-2], x.shape[-2:]
    x = x.reshape(lead + (n // 4, 2, 2, w))
    return jnp.swapaxes(x, -2, -3).reshape(lead + (n, w))


def project_q(cfg: ModelConfig, p: Params, u):
    """``u`` [b, s, h] -> the queries [b, s, heads, d] in kernel order."""
    b, s, _ = u.shape
    # (the barrier: cut into heads and reordered at once, the product has
    # three output dimensions and XLA:TPU re-lays wq for it in every call)
    q = jax.lax.optimization_barrier(u @ p["wq"] + p["bq"])
    return _to_kernel_order(
        q.reshape(b, s, cfg.num_attention_heads, cfg.head_dim), cfg)


def project_kv(cfg: ModelConfig, p: Params, u):
    """``u`` [b, s, h] -> head-major rows as they are cached: keys [b,
    kv_heads, s, d] and values [b, kv_heads / 2, s, 2 d]."""
    b, s, _ = u.shape
    k = (u @ p["wk"] + p["bk"]).reshape(b, s, cfg.kv_heads, cfg.head_dim)
    v = (u @ p["wv"] + p["bv"]).reshape(b, s, cfg.v_heads, cfg.v_head_width)
    return jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2)


def pair_rows(cfg: ModelConfig, k):
    """Keys head-major [b, kv_heads, s, d] -> [b, kv_heads / 2, s, 2 d]:
    the keys of the heads that share a value head side by side in a row,
    as a ring keeps them (the values lie so already)."""
    b, _, s, d = k.shape
    k = k.reshape(b, cfg.v_heads, -1, s, d)
    return jnp.moveaxis(k, 2, 3).reshape(b, cfg.v_heads, s, -1)


def pair_scale(lam0):
    """What a pair's normalised difference is multiplied by."""
    return 1.0 - lam0


def finish(cfg: ModelConfig, p: Params, attn, layer):
    """``attn`` [b, s, heads, 2 d] in kernel order -> the layer's output
    [b, s, h]: the pair's difference under ``lam``, its RMSNorm, ``1 -
    lam_init`` and the output projection."""
    b, s, n, w = attn.shape
    a = _to_kernel_order(attn.astype(jnp.float32), cfg).reshape(
        b, s, n // 2, 2, w)
    lam0 = lambda_init(layer)
    lq1, lk1, lq2, lk2 = p["lam"]
    lam = jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2)) + lam0
    o = rmsnorm_ref(a[..., 0, :] - lam * a[..., 1, :],
                    p["pair_norm"]["scale"], cfg.norm_eps) * pair_scale(lam0)
    # (the barrier: as in project_q, for wo)
    o = jax.lax.optimization_barrier(
        o.reshape(b, s, n // 2 * w).astype(p["wo"].dtype))
    return o @ p["wo"] + p["bo"]


def _scale(cfg: ModelConfig) -> float:
    return (cfg.head_dim ** -0.5 if cfg.attention_multiplier is None
            else cfg.attention_multiplier)


def _plain(q, k, v, keep, scale):
    """``q`` [b, r, heads, d], ``k`` [b, kv, S, d], ``v`` [b, kv / 2, S,
    2 d], ``keep`` [b, r, S] bool -> [b, r, heads, 2 d]: the plain
    composition, float32 softmax."""
    b, r, n, d = q.shape
    nk, nv = k.shape[1], v.shape[1]
    qg = q.reshape(b, r, nv, nk // nv, n // nk, d)
    kg = k.reshape(b, nv, nk // nv, k.shape[2], d)
    scores = jnp.einsum("brvjid,bvjsd->bvjirs", qg, kg,
                        preferred_element_type=jnp.float32) * scale
    scores = jnp.where(keep[:, None, None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bvjirs,bvsw->brvjiw", probs, v)
    return out.reshape(b, r, n, v.shape[-1])


def flash_blocks(cfg: ModelConfig, window: int = 0) -> tuple:
    """``flash_attention``'s block bounds: the configuration's, and under
    a window no wider than the window (the band is then two tiles a row
    block whatever the bound)."""
    if not window:
        return cfg.flash_block_q, cfg.flash_block_k
    return (min(cfg.flash_block_q, max(128, window)),
            min(cfg.flash_block_k, max(128, window)))


def attend_seq(cfg: ModelConfig, q, k, v, window: int = 0):
    """A sequence on itself, causal; ``window`` > 0: a query keeps that
    many keys, its own among them.  ``q`` [b, s, heads, d] (kernel
    order), ``k v`` head-major (``project_kv``) -> [b, s, heads, 2 d]."""
    s = q.shape[1]
    if cfg.attention_impl == "flash":
        from ..kernels.flash_attention import flash_attention

        block_q, block_k = flash_blocks(cfg, window)
        return flash_attention(
            q, jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2), causal=True,
            window=window, softmax_scale=_scale(cfg), block_q=block_q,
            block_k=block_k)
    pos = jnp.arange(s)
    keep = pos[None, :] <= pos[:, None]
    if window:
        keep = keep & (pos[None, :] > pos[:, None] - window)
    return _plain(q, k, v, jnp.broadcast_to(keep, (q.shape[0], s, s)),
                  _scale(cfg))


def attend_rows(cfg: ModelConfig, q, k, v, last):
    """Query rows ``q`` [b, r, heads, d] (kernel order), row ``i`` of
    batch ``b`` at position ``last[b, i]``, on dense ``k v`` head-major
    [b, ., S, .]: each sees the positions up to its own."""
    keep = jnp.arange(k.shape[2])[None, None, :] <= last[:, :, None]
    return _plain(q, k, v, keep, _scale(cfg))


def attend_ring(cfg: ModelConfig, q, ring_k, ring_v, layer, k_new, v_new,
                pos):
    """One new position a slot on its ring.  ``q`` [b, 1, heads, d]
    (consecutive heads on a key head: kernel order); ``ring_k ring_v``
    [window layers, b, kv / r, W, r d] the stacked rings (``r`` key heads
    a value head: ``pair_rows``' 2 under differential attention, 1 for
    ordinary grouped heads), of which ``layer``'s (a traced scalar in a
    layer scan) is attended: it holds position ``t`` at row ``t % W``;
    ``k_new`` [b, kv, 1, d] and ``v_new`` [b, kv / r, 1, r d] the new
    position ``pos`` [b], not in the ring yet.  The order of the rows does
    not matter: where the model rotates, a row holds its key rotated at
    its own position already, and ``q`` comes rotated at ``pos``.  Row
    ``c`` counts where it holds one of the ``W - 1`` positions before
    ``pos``, which is ``c < pos`` but for the row the new position will
    take.  The kernel (kernels/ring_decode.py) where the configuration
    asks for kernels, else the plain composition.  ``cfg``: the
    configuration whose heads ``q`` has (a window layer's view of it)."""
    if cfg.attention_impl == "flash":
        from ..kernels.ring_decode import ring_decode

        return ring_decode(q[:, 0], ring_k, ring_v, k_new, v_new, pos, layer,
                           softmax_scale=_scale(cfg))[:, None]
    ring_k, ring_v = (jax.lax.dynamic_index_in_dim(a, layer, 0, False)
                      for a in (ring_k, ring_v))
    b, _, n, d = q.shape
    nk, nv, W = k_new.shape[1], ring_v.shape[1], ring_v.shape[2]
    r = jnp.arange(W)[None, :]
    keep = (r < pos[:, None]) & (r != (pos % W)[:, None])
    qg = q[:, 0].reshape(b, nv, nk // nv, n // nk, d)
    # the ring where it lies and the new row beside it: two score blocks
    # under one softmax, so the ring is read and never copied
    old = jnp.einsum("bvjid,bvsjd->bvjis", qg,
                     ring_k.reshape(b, nv, W, nk // nv, d),
                     preferred_element_type=jnp.float32) * _scale(cfg)
    old = jnp.where(keep[:, None, None, None], old, -jnp.inf)
    own = jnp.einsum("bvjid,bvjd->bvji", qg,
                     k_new[:, :, 0].reshape(b, nv, nk // nv, d),
                     preferred_element_type=jnp.float32) * _scale(cfg)
    probs = jax.nn.softmax(
        jnp.concatenate([old, own[..., None]], axis=-1), axis=-1)
    out = jnp.einsum("bvjis,bvsw->bvjiw",
                     probs[..., :W].astype(ring_v.dtype), ring_v,
                     preferred_element_type=jnp.float32) \
        + probs[..., W:] * v_new[:, :, None, None, 0].astype(jnp.float32)
    return out.reshape(b, 1, n, ring_v.shape[-1]).astype(q.dtype)


def ring_of(rows, length, window: int):
    """A prompt's rows [b, heads, s, w] (head-major) -> the ring after
    ``length`` [b] positions, [b, heads, window, w]: row ``r`` holds the
    last position under ``length`` that is ``r`` modulo ``window``
    (rows at or past ``length`` hold what a step will never count)."""
    r = jnp.arange(window)[None, :]
    at = r + window * jnp.maximum((length[:, None] - 1 - r) // window, 0)
    at = jnp.minimum(at, rows.shape[2] - 1)
    return jnp.take_along_axis(rows, at[:, None, :, None], axis=2)


class KVHand(NamedTuple):
    """What the one "full" layer of a stack of runs hands to the "cross"
    layers behind it: its keys and values, in the form the cross layers
    attend them.  ``form`` "seq": ``k v`` head-major rows of the whole
    sequence, a query at every position; "rows": dense ``k v`` [b, ., S,
    .] and the positions ``last`` [b, r] of the few query rows; "paged":
    ``paged`` the pool (``transformer.PagedKV``) and ``k v`` the step's own
    rows, which are not in it yet."""

    form: str
    k: jax.Array
    v: jax.Array
    last: Optional[jax.Array] = None
    paged: Optional[tuple] = None


def attend_handed(cfg: ModelConfig, p: Params, u, layer, hand: KVHand,
                  q_rows=None):
    """A layer's differential attention with the keys and values of
    ``hand``: its own query projection (of the rows ``q_rows`` [b] alone
    where given), the form's attention, the pairs' difference and the
    output projection."""
    if q_rows is not None:
        u = jnp.take_along_axis(u, q_rows[:, None, None], axis=1)
    q = project_q(cfg, p, u)
    if hand.form == "seq":
        a = attend_seq(cfg, q, hand.k, hand.v)
    elif hand.form == "rows":
        a = attend_rows(cfg, q, hand.k, hand.v, hand.last)
    else:
        from ..ops.attention import paged_decode_attention

        pg = hand.paged
        a = paged_decode_attention(
            q, pg.k_pool, pg.v_pool, pg.tables, pg.fills, hand.k, hand.v,
            pg.layer, softmax_scale=_scale(cfg))
    return finish(cfg, p, a, layer)


def attend_full(cfg: ModelConfig, p: Params, u, side, layer, cache,
                cut_rows):
    """The "full" layer of a stack of runs: attention on its own keys and
    values, all of them.  ``cache``: None (a whole sequence, nothing
    kept), the dense ``(k_cache, v_cache, cache_len)`` or a
    ``transformer.PagedKV``.  ``cut_rows`` [b] (a prompt into an empty cache
    alone): the keys and values of every row, the query and the output
    of that row only.  -> ``(out, the new rows or None, the hand)``."""
    k, v = project_kv(cfg, p, u)
    if hasattr(cache, "tables"):
        k, v = k.astype(cache.k_pool.dtype), v.astype(cache.v_pool.dtype)
        hand = KVHand("paged", k, v, paged=cache)
    elif cache is None or side.cache_is_empty:
        hand = (KVHand("seq", k, v) if cut_rows is None
                else KVHand("rows", k, v, last=cut_rows[:, None]))
    else:
        # one new position on the dense view of the gather route
        from ..ops.kv_quant import cache_update

        k_cache, v_cache, cache_len = cache
        hand = KVHand("rows", cache_update(k_cache, k, cache_len),
                      cache_update(v_cache, v, cache_len),
                      last=side.position_ids)
    out = attend_handed(cfg, p, u, layer, hand, cut_rows)
    return out, (None if cache is None else (k, v)), hand


@jax.named_scope("gmu")
def gmu_block(p: Params, u, memory):
    """The gated memory unit: ``(memory * SiLU(u W_1)) W_2``; ``memory``
    [b, s, inner] float32, an earlier Mamba-1 layer's at the same
    positions."""
    from ..ops.precision import dot_rounded

    gate = jax.nn.silu(dot_rounded(u, p["w_in"]))
    return dot_rounded(memory * gate, p["w_out"]).astype(u.dtype)


def init_gmu_params(key: jax.Array, cfg: ModelConfig) -> Params:
    k1, k2 = jax.random.split(key)
    h, di, std = cfg.hidden_size, cfg.mamba1_inner, cfg.init_method_std

    def normal(k, shape):
        return (std * jax.random.normal(k, shape, jnp.float32)).astype(
            cfg.dtype)

    return {"w_in": normal(k1, (h, di)), "w_out": normal(k2, (di, h))}
