"""Decoder transformer stack — functional init/apply, scan-over-layers.

Covers the reference's ``ParallelTransformer`` family
(megatron/model/transformer.py:897-1252): pre-LN residual blocks, GQA/MQA
attention with RoPE, GLU or plain MLPs, Falcon-style parallel attention
(+ parallel LayerNorm for 40B), dropout, and full/selective activation
recompute.  Key TPU-first departures from the reference:

- Parameters for all layers are **stacked on a leading layer axis** and the
  stack is executed with ``jax.lax.scan`` — one compiled layer body regardless
  of depth (the reference python-loops over ``ParallelTransformerLayer``
  modules, transformer.py:1158-1246).  The stacked layout is also what the
  pipeline-parallel schedule shards over the ``pp`` mesh axis.
- Activations are [batch, seq, hidden] (batch-major); the reference's
  [seq, batch, hidden] layout is a CUDA kernel artifact.
- Tensor parallelism is expressed by PartitionSpecs on the stacked weights
  (see models/sharding.py), not by distinct Column/RowParallel module classes
  (reference: megatron/core/tensor_parallel/layers.py:410,566) — GSPMD
  inserts the same all-reduce/all-gather/reduce-scatter collectives those
  classes perform by hand.
- Recompute is ``jax.checkpoint`` with a policy, replacing the RNG-juggling
  CheckpointFunction (megatron/core/tensor_parallel/random.py:183-248).
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..config import KINDS, ModelConfig, PositionEmbeddingType
from ..ops.activations import get_activation, is_glu
from ..ops.attention import _mesh_active, attention
from ..ops.norms import norm_apply, norm_init
from ..ops.quant import int8_training_matmul, is_quantized, mm
from ..ops.rope import (
    apply_rope,
    apply_rope_flat,
    apply_rope_partial,
    precompute_rope_freqs,
    rotation_of,
)
from . import diff_attention, gated_deltanet, mamba1, mamba2, mla
from .gated_deltanet import GDNState, gdn_block, init_gdn_params

Params = dict


def proj(cfg, x, w):
    """Projection matmul dispatch: serving-quantized weights → dequantizing
    ``mm``; ``quantize_matmuls="int8"`` training → W8A8 on the int8 MXU
    with straight-through backward (ops/quant.py); else plain ``@``."""
    if cfg.quantize_matmuls == "int8" and not is_quantized(w):
        return int8_training_matmul(x, w)
    return mm(x, w)


# ---------------------------------------------------------------------------
# Initialization (reference init methods: megatron/model/utils.py init_method_
# normal / scaled_init_method_normal)
# ---------------------------------------------------------------------------


def _normal(key, shape, std, dtype):
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def _init_stds(cfg: ModelConfig):
    """→ (std, the output layers': scaled by 1/sqrt(2*num_layers))."""
    std = cfg.init_method_std
    return std, (std / (2.0 * cfg.num_layers) ** 0.5 if cfg.use_scaled_init
                 else std)


def _init_attn(keys, cfg: ModelConfig, kind: str) -> Params:
    """An attention part's parameters: differential, latent or grouped
    heads, by what the model's attention is."""
    h, d, nkv, dtype = cfg.hidden_size, cfg.head_dim, cfg.kv_heads, cfg.dtype
    std, out_std = _init_stds(cfg)
    if cfg.diff_attention:
        return diff_attention.init_diff_attn_params(
            keys[0], cfg, cross=KINDS[kind].reads == "kv")
    if cfg.kv_lora_rank and KINDS[kind].keeps == "kv":
        return mla.init_mla_params(keys[0], cfg, std, out_std)
    # (a "window" layer of plain attention has a head count of its own)
    nq = (cfg.window_layer_config if KINDS[kind].keeps == "window"
          else cfg).num_attention_heads
    attn: Params = {
        # with an output gate: per head, the query's columns then the
        # gate's
        "wq": _normal(keys[0], (h, nq * d * (2 if cfg.attn_output_gate
                                             else 1)), std, dtype),
        "wk": _normal(keys[1], (h, nkv * d), std, dtype),
        "wv": _normal(keys[2], (h, nkv * d), std, dtype),
        "wo": _normal(keys[3], (nq * d, h), out_std, dtype),
    }
    if cfg.use_bias or cfg.qkv_bias:
        attn["bq"] = jnp.zeros((nq * d,), dtype)
        attn["bk"] = jnp.zeros((nkv * d,), dtype)
        attn["bv"] = jnp.zeros((nkv * d,), dtype)
    if cfg.use_bias:
        attn["bo"] = jnp.zeros((h,), dtype)
    if cfg.qk_norm:
        attn["q_norm"] = norm_init(cfg.norm_type, d, dtype)
        attn["k_norm"] = norm_init(cfg.norm_type, d, dtype)
    if cfg.attn_head_gate:
        # kept in float32, as the router is: one scalar a head
        attn["wg"] = std * jax.random.normal(
            jax.random.fold_in(keys[0], 1), (h, nq), jnp.float32)
    return attn


def init_layer_params(key: jax.Array, cfg: ModelConfig,
                      kind: str = "full") -> Params:
    """Parameters of one transformer layer (unstacked), of one of
    ``config.KINDS``: under ``"input_norm"`` the kind's mixer, under the
    name ``MIXERS`` gives it (none for an ``"mlp"`` block), and where the
    kind has a feed-forward part ``"mlp"``, under a norm of its own
    where both are there."""
    h, ffn, dtype = cfg.hidden_size, cfg.ffn_size, cfg.dtype
    std, out_std = _init_stds(cfg)
    keys = jax.random.split(key, 8)
    layer: Params = {"input_norm": norm_init(cfg.norm_type, h, dtype)}
    mixer = MIXERS[kind]
    if mixer.name:
        layer[mixer.name] = mixer.init(keys, cfg, kind)
    if not KINDS[kind].ffn:
        return layer                     # a mixer alone

    if cfg.num_experts > 0:
        from .moe import init_moe_params

        mlp: Params = init_moe_params(keys[4], cfg)
    else:
        mlp = {}
        if is_glu(cfg.activation):
            mlp["w_gate"] = _normal(keys[4], (h, ffn), std, dtype)
            mlp["w_up"] = _normal(keys[5], (h, ffn), std, dtype)
        else:
            mlp["w_up"] = _normal(keys[5], (h, ffn), std, dtype)
        mlp["w_down"] = _normal(keys[6], (ffn, h), out_std, dtype)
        if cfg.use_bias:
            if is_glu(cfg.activation):
                mlp["b_gate"] = jnp.zeros((ffn,), dtype)
            mlp["b_up"] = jnp.zeros((ffn,), dtype)
            mlp["b_down"] = jnp.zeros((h,), dtype)
    layer["mlp"] = mlp
    if not mixer.name:
        return layer                     # the feed-forward part alone
    if cfg.parallel_attn:
        if cfg.parallel_layernorm:
            # Falcon-40B: separate LN for the MLP branch
            # (reference: megatron/model/transformer.py:686-694).
            layer["mlp_norm"] = norm_init(cfg.norm_type, h, dtype)
    else:
        layer["post_attn_norm"] = norm_init(cfg.norm_type, h, dtype)
    return layer


def init_stack_params(key: jax.Array, cfg: ModelConfig,
                      num_layers: Optional[int] = None) -> Params:
    """All layers of the scan, stacked on a leading axis (scan/pipeline
    layout).  A hybrid stack (``cfg.layer_pattern``) is a list with one
    such tree a position of the period, each stacked over the periods (a
    stack written as runs, ``cfg.layer_runs``: a list of such lists, one a
    run); leading dense layers are not among them
    (``init_lead_params``)."""
    n = num_layers if num_layers is not None else cfg.scanned_layers
    keys = jax.random.split(key, n)
    if not cfg.layer_pattern:
        return jax.vmap(lambda k: init_layer_params(k, cfg))(keys)
    runs, at = [], 0
    for period, times in cfg.stack_runs:
        mine = keys[at:at + len(period) * times]
        runs.append([jax.vmap(lambda k, kind=kind: init_layer_params(
            k, cfg, kind))(mine[j::len(period)])
            for j, kind in enumerate(period)])
        at += len(period) * times
    # (a stack written as runs: a list of runs, each such a list)
    return runs if cfg.layer_runs else runs[0]


def init_lead_params(key: jax.Array, cfg: ModelConfig) -> Params:
    """The ``cfg.moe_first_dense_layers`` leading layers, stacked on a
    leading axis beside the scanned stack (``params["lead_layers"]``): a
    block of ``cfg.lead_kind`` (the period's first kind, or the one
    stated) with a dense MLP of ``cfg.moe_dense_ffn_size`` in place of
    the experts (``cfg.lead_layer_config``)."""
    keys = jax.random.split(key, cfg.moe_first_dense_layers)
    return jax.vmap(lambda k: init_layer_params(
        k, cfg.lead_layer_config, cfg.lead_kind))(keys)


def _lead_layers(lead):
    """The leading layers' trees, one a layer, in order."""
    if lead is None:
        return []
    n = jax.tree.leaves(lead)[0].shape[0]
    return [jax.tree.map(lambda a, i=i: a[i], lead) for i in range(n)]


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class AttnSideInputs:
    """Non-parameter inputs shared by all layers."""

    rope_cos: Optional[jax.Array] = None
    rope_sin: Optional[jax.Array] = None
    position_ids: Optional[jax.Array] = None  # [b, s]
    segment_ids: Optional[jax.Array] = None  # [b, s] packed sequences
    dropout_rng: Optional[jax.Array] = None
    deterministic: bool = True
    # False → bidirectional self-attention (BERT/T5-encoder stacks;
    # reference AttnMaskType.padding, megatron/model/enums.py).  Padding is
    # expressed through segment_ids (pad tokens get their own segment).
    causal: bool = True
    # Mesh axes the sequence dim of the residual stream is constrained to at
    # layer boundaries — Megatron sequence parallelism (reference:
    # core/tensor_parallel/layers.py:225-296).  Callers set this from
    # cfg.sequence_parallel_axis (+ the cp axis when cp is GSPMD-auto; the
    # pipeline omits cp because cp is manual inside its shard_map).
    seq_shard_axes: tuple = ()
    # Explicit additive attention bias [b, 1, sq, sk] (fp32, -inf = masked).
    # Used where the mask is *data-dependent* — the split-rank
    # encoder-decoder pipeline selects causal-vs-bidirectional per stage at
    # runtime (parallel/pipeline_encdec.py), which a static ``causal`` flag
    # can't express.  Forces the einsum attention path (a bias rules out the
    # flash kernel's implicit-mask layout).
    attn_bias: Optional[jax.Array] = None
    # STATIC promise that the KV cache holds no valid rows yet (first
    # prefill): cached attention then runs ordinary causal attention over
    # the window (flash kernel) instead of contracting against the whole
    # cache buffer (model.py:forward_cached(empty_cache=True)).
    cache_is_empty: bool = False
    # [b, s] bool: the positions that are there, a prefix of each row (a
    # prefill bucket's padded tail and a decode step's free slots are
    # not).  Where a layer keeps state that every position advances (the
    # Gated DeltaNet state, the expert counters) the others leave it as
    # it was; attention needs no such mask, its cache is masked by fill.
    valid: Optional[jax.Array] = None


class PagedKV(NamedTuple):
    """The paged form of ``attention_block``'s ``kv_cache``: the serving
    block pool read through per-slot block tables, one new token a slot
    (ops/attention.py:paged_decode_attention).  ``k_pool``/``v_pool`` are
    the whole pool ``[L, n_blocks, nkv, block, d]`` (int8 ``{"q",
    "scale"}`` dicts for a quantized pool), of which ``layer`` — a traced
    int32 inside the layer scan — names the layer attended, holding
    ``fills[s]`` rows of slot ``s``."""

    k_pool: object
    v_pool: object
    tables: jax.Array            # [b, T] int32
    fills: jax.Array             # [b] int32
    layer: jax.Array             # int32 scalar


def seq_constrain(x: jax.Array, axes: tuple):
    """Constrain [b, s, h] activations to seq-sharding over ``axes``.

    Batch/hidden dims stay UNCONSTRAINED so GSPMD keeps whatever dp/ep
    layout is already in flight.  No-op outside a mesh context (delegates
    to models.sharding.constrain)."""
    if not axes:
        return x
    from .sharding import constrain

    U = jax.sharding.PartitionSpec.UNCONSTRAINED
    return constrain(x, jax.sharding.PartitionSpec(U, tuple(axes), U))


def _dropout(x, rate, rng, deterministic):
    """Inverted dropout; ``rate`` may be a traced scalar (LIMA per-layer
    ramp) — the zero-rate short-circuit only applies to static rates."""
    if deterministic or rng is None:
        return x
    if isinstance(rate, (int, float)) and rate == 0.0:
        return x
    keep_p = 1.0 - rate
    keep = jax.random.bernoulli(rng, keep_p, x.shape)
    return jnp.where(keep, x / keep_p, 0.0)


def _drop_path(x, rate, rng, deterministic):
    """Stochastic depth: zero the whole residual branch per *sample*
    (reference DropPath, megatron/model/transformer.py:43-64)."""
    if deterministic or rng is None:
        return x
    keep_p = 1.0 - rate
    shape = (x.shape[0],) + (1,) * (x.ndim - 1)
    keep = jax.random.bernoulli(rng, keep_p, shape)
    return jnp.where(keep, x / keep_p, 0.0)


def _layer_rates(cfg: ModelConfig, layer_idx):
    """Per-layer (hidden_dropout, drop_path) rates for global layer
    ``layer_idx`` (may be traced — the scanned stack and the pipeline pass
    the running index).  linspace(0, rate, L) semantics as the reference
    (transformer.py:962-971)."""
    denom = max(cfg.num_layers - 1, 1)
    frac = layer_idx / denom
    hidden = (cfg.hidden_dropout * frac if cfg.lima_dropout
              else cfg.hidden_dropout)
    return hidden, cfg.drop_path_rate * frac


def _lora_add(y: jax.Array, x: jax.Array, lora, target: str) -> jax.Array:
    """Add the grouped LoRA epilogue for ``target`` onto projection output
    ``y`` (input ``x``), or return ``y`` untouched when the layer's lora
    bundle is absent or doesn't adapt this target.

    ``lora`` is ``(factors, mask)``: per-layer arena slices
    ``{target: {"a": [in, Sr], "b": [Sr, out]}}`` plus the per-row column
    mask ``[b, Sr]`` (ops/lora.py:slot_mask).  The delta is fp32 with ±0
    contributions from masked columns, so rows whose slot is -1 (or whose
    adapter differs) are bitwise-unaffected at the token level."""
    if lora is None:
        return y
    factors, mask = lora
    f = factors.get(target)
    if f is None:
        return y
    from ..ops.lora import lora_delta

    return (y + lora_delta(x, f["a"], f["b"], mask)).astype(y.dtype)


def _project_heads(cfg: ModelConfig, p: Params, x: jax.Array,
                   side: AttnSideInputs, paged: bool = False, lora=None):
    """An attention part's projections, cut into heads and rotated:
    ``x`` [b, s, h] -> ``(q [b, s, heads, d], k, v [b, s, kv_heads, d],
    the element-wise output gate [b, s, heads * d] or None)``.  ``paged``:
    the paged route's few rows, which a table's rotation takes as the
    matmul leaves them."""
    b, s, h = x.shape
    d = cfg.head_dim
    nq = cfg.num_attention_heads
    nkv = cfg.kv_heads

    q = _lora_add(proj(cfg, x, p["wq"]), x, lora, "wq")
    k = _lora_add(proj(cfg, x, p["wk"]), x, lora, "wk")
    v = _lora_add(proj(cfg, x, p["wv"]), x, lora, "wv")
    gate = None
    if cfg.attn_output_gate:
        q = q.reshape(b, s, nq, 2 * d)
        gate = q[..., d:].reshape(b, s, nq * d)
        q = q[..., :d].reshape(b, s, nq * d)
    if "bq" in p:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    position_ids = side.position_ids

    rotary = cfg.position_embedding_type == PositionEmbeddingType.ROTARY
    partial = rotary and (cfg.rotary_percent < 1.0 or cfg.rope_rotate_half)
    # the paged route's few rows are rotated as the matmul leaves them:
    # cut into heads first, the q projection re-lays wq in every call
    # (apply_rope_flat).  Not under a mesh, where tp splits the row and
    # the shift along it would cross shards in every layer.
    flat = (rotary and not partial and paged and not _mesh_active())
    if flat:
        q = apply_rope_flat(q, side.rope_cos, side.rope_sin, position_ids, d)
        k = apply_rope_flat(k, side.rope_cos, side.rope_sin, position_ids, d)
    q = q.reshape(b, s, nq, d)
    k = k.reshape(b, s, nkv, d)
    v = v.reshape(b, s, nkv, d)
    if cfg.qk_norm:
        q = norm_apply(cfg.norm_type, q, p["q_norm"], cfg.norm_eps)
        k = norm_apply(cfg.norm_type, k, p["k_norm"], cfg.norm_eps)
    if partial:
        pos = position_ids if position_ids is not None else \
            jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None, :], (b, s))
        rot, inv_freq, scale = rotation_of(cfg)
        q = apply_rope_partial(q, pos, rot, cfg.rope_theta, inv_freq, scale)
        k = apply_rope_partial(k, pos, rot, cfg.rope_theta, inv_freq, scale)
    elif rotary and not flat:
        q = apply_rope(q, side.rope_cos, side.rope_sin, position_ids)
        k = apply_rope(k, side.rope_cos, side.rope_sin, position_ids)
    return q, k, v, gate


def _project_out(cfg: ModelConfig, p: Params, ctx: jax.Array, gate,
                 gate_x, lora=None):
    """An attention part's way out: ``ctx`` [b, s, heads, d] under its
    gate (the element-wise one projected beside q, or one scalar a head
    from the layer's input ``gate_x``, in float32) through the output
    projection."""
    b, s = ctx.shape[:2]
    nq, d = cfg.num_attention_heads, cfg.head_dim
    if cfg.attn_head_gate:
        g = jax.nn.sigmoid(jnp.dot(
            gate_x.astype(jnp.float32), p["wg"].astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST))
        ctx = (ctx.reshape(b, s, nq, d).astype(jnp.float32)
               * g[..., None]).astype(ctx.dtype)
    ctx2d = ctx.reshape(b, s, nq * d)
    if gate is not None:
        ctx2d = (ctx2d * jax.nn.sigmoid(gate.astype(jnp.float32))
                 ).astype(ctx2d.dtype)
    out = _lora_add(proj(cfg, ctx2d, p["wo"]), ctx2d, lora, "wo")
    if "bo" in p:
        out = out + p["bo"]
    return out


@jax.named_scope("attention")
def attention_block(cfg: ModelConfig, p: Params, x: jax.Array,
                    side: AttnSideInputs, layer_rng,
                    kv_cache: Optional[tuple] = None, lora=None,
                    gate_x=None):
    """QKV projection → RoPE → attention → output projection.

    Parity: megatron/model/transformer.py:412-565 (ParallelAttention) with
    GQA/MQA handled inside the attention einsum rather than by tiling K/V.

    ``kv_cache`` is an optional ``(k_cache, v_cache, length)`` triple
    (head-major [b, nkv, max_len, d] ×2 + scalar int32) for incremental
    decoding (the reference's InferenceParams KV cache,
    transformer.py:423-496).  When given, the return value is
    ``(out, (new_k_rows, new_v_rows))`` — the new tokens' [b, nkv, s, d]
    rows, NOT an updated cache; the caller owns the write-back.  Its
    paged form is a :class:`PagedKV` (one new token a slot, KV read
    through the block tables by the paged kernel); the rows then come
    back in the form the pool stores them (``kv_quant.rows_as_stored``).

    ``lora`` is the per-layer ``(factors, mask)`` bundle (see
    :func:`_lora_add`); deltas land right after each base projection,
    before bias/reshape/RoPE.

    ``gate_x``: what a gate a head (``cfg.attn_head_gate``) reads, the
    layer's input as the float32 stream has it (None: ``x``).
    """
    if kv_cache is not None and side.position_ids is None:
        raise ValueError("kv_cache requires explicit position_ids "
                         "(forward_cached supplies them)")
    q, k, v, gate = _project_heads(cfg, p, x, side,
                                   isinstance(kv_cache, PagedKV), lora)
    softmax_scale = (1.0 / (cfg.head_dim ** 0.5)
                     if cfg.attention_multiplier is None
                     else cfg.attention_multiplier)
    # (cfg.apply_query_key_layer_scaling: the reference scales by 1/layer
    # inside softmax and compensates in the matmul, transformer.py:191-236;
    # the net effect is the standard scale, so only the fp32 softmax is kept)
    drop_rng = None
    if not side.deterministic and cfg.attention_dropout > 0.0:
        drop_rng = jax.random.fold_in(layer_rng, 1)

    if isinstance(kv_cache, PagedKV):
        from ..ops.attention import paged_decode_attention
        from ..ops.kv_quant import rows_as_stored

        new_k = rows_as_stored(kv_cache.k_pool,
                               jnp.transpose(k, (0, 2, 1, 3)))
        new_v = rows_as_stored(kv_cache.v_pool,
                               jnp.transpose(v, (0, 2, 1, 3)))
        ctx = paged_decode_attention(
            q, kv_cache.k_pool, kv_cache.v_pool, kv_cache.tables,
            kv_cache.fills, new_k, new_v, kv_cache.layer,
            softmax_scale=softmax_scale)
    elif kv_cache is not None:
        from ..ops.attention import decode_attention
        from ..ops.kv_quant import cache_update

        k_cache, v_cache, cache_len = kv_cache  # [b, nkv, max_len, d]
        # head-major rows [b, nkv, s, d] — contiguous with the cache layout
        new_k = jnp.transpose(k, (0, 2, 1, 3))
        new_v = jnp.transpose(v, (0, 2, 1, 3))
        k_cache = cache_update(k_cache, new_k, cache_len)
        v_cache = cache_update(v_cache, new_v, cache_len)
        if side.cache_is_empty and x.shape[1] > 1:
            # prefill fast path: no prior rows to attend, so this is
            # ordinary causal attention over the window — the flash
            # kernel at O(s²) instead of the cached-score einsum at
            # O(s·max_len), whose scores are materialized a layer
            ctx = attention(
                q, k, v,
                impl=cfg.attention_impl,
                causal=True,
                softmax_scale=softmax_scale,
                block_q=cfg.flash_block_q,
                block_k=cfg.flash_block_k,
            )
        else:
            ctx = decode_attention(
                q, k_cache, v_cache, cache_len,
                softmax_scale=softmax_scale,
            )
    else:
        ctx = attention(
            q, k, v,
            impl=cfg.attention_impl,
            causal=side.causal,
            segment_ids=side.segment_ids,
            softmax_scale=softmax_scale,
            dropout_rate=0.0 if side.deterministic else cfg.attention_dropout,
            dropout_rng=drop_rng,
            bias=side.attn_bias,
            cp_axis=cfg.context_parallel_axis,
            cp_zigzag=cfg.context_parallel_zigzag,
            block_q=cfg.flash_block_q,
            block_k=cfg.flash_block_k,
        )
    out = _project_out(cfg, p, ctx, gate, x if gate_x is None else gate_x,
                       lora)
    if kv_cache is not None:
        # return only the NEW rows [b, nkv, s, d] — the caller writes them
        # into its persistent cache with a row-sized dynamic_update_slice,
        # so decode never copies the O(max_len) cache
        return out, (new_k, new_v)
    return out


def mlp_block(cfg: ModelConfig, p: Params, x: jax.Array,
              lora=None) -> jax.Array:
    """(gated) MLP.  Parity: megatron/model/transformer.py:77-141
    (ParallelMLP) with the GLU split expressed as two separate projections so
    tensor sharding never slices across the gate/up boundary."""
    act = get_activation(cfg.activation)
    if is_glu(cfg.activation):
        gate = _lora_add(proj(cfg, x, p["w_gate"]), x, lora, "w_gate")
        up = _lora_add(proj(cfg, x, p["w_up"]), x, lora, "w_up")
        if "b_gate" in p:
            gate = gate + p["b_gate"]
            up = up + p["b_up"]
        # GLU activations act on the concatenated tensor in the reference
        # (glu_activations.py); composing on the split halves is identical.
        hidden = jnp.concatenate([gate, up], axis=-1)
        hidden = act(hidden)
    else:
        hidden = _lora_add(proj(cfg, x, p["w_up"]), x, lora, "w_up")
        if "b_up" in p:
            hidden = hidden + p["b_up"]
        hidden = act(hidden)
    out = _lora_add(proj(cfg, hidden, p["w_down"]), hidden, lora, "w_down")
    if "b_down" in p:
        out = out + p["b_down"]
    return out


@jax.named_scope("mlp")
def _mlp_dispatch(cfg: ModelConfig, p: Params, x: jax.Array, lora=None,
                  valid=None):
    """Dense or routed MLP → ``(out, aux)``.

    ``aux`` is a scalar 0 for dense models and the MoE stats dict
    {aux, dropped, load} for routed ones (models/moe.py); accumulate with
    ``jax.tree.map`` and read the loss term via ``moe.aux_loss_of``."""
    if cfg.moe_dropless:
        from .moe import moe_dropless_block

        return moe_dropless_block(cfg, p, x, valid=valid)
    if cfg.num_experts > 0:
        from .moe import moe_block

        # MoE experts are never LoRA targets (registry rejects mlp
        # targets for num_experts > 0); attention adapters still apply
        return moe_block(cfg, p, x)
    return mlp_block(cfg, p, x, lora=lora), jnp.zeros((), jnp.float32)


class Handed(NamedTuple):
    """What earlier layers hand a block at the same positions, in a stack
    whose kinds read such (``config.BlockKind.reads``): the last Mamba-1
    layer's ``memory`` (its scan's output before the gate) and the one
    "full" layer's keys and values ``kv`` (``diff_attention.KVHand``).  And
    ``cut_rows`` [b] (a prompt into an empty cache alone): the row of
    each sequence that is carried on from that "full" layer's attention
    (``cfg.row_cut_layer``)."""

    memory: Optional[jax.Array] = None
    kv: Optional[diff_attention.KVHand] = None
    cut_rows: Optional[jax.Array] = None


class _Call(NamedTuple):
    """What a block hands its mixer beside the norm's output and the
    cache (``Mixer.apply``)."""

    rng: object
    lora: object
    gate_x: jax.Array
    layer: object
    handed: Handed


def _mixer_part(cfg: ModelConfig, p: Params, x, h1, side: AttnSideInputs,
                kind: str, rng, cache, layer_idx, lora, handed: Handed):
    """A block's mixer on ``h1``, the stream ``x`` under the block's
    first norm → ``(the stream the result is added to, the result (None:
    the kind has no mixer), the mixer's new cache, what is handed on)``."""
    mixer = MIXERS[kind]
    if not mixer.name:
        return x, None, None, handed
    # a hybrid stack's residual stream is float32 (``STREAM_DTYPE``);
    # attention computes in the model's own precision, and a gate a head
    # reads the stream's norm as it is
    u = h1
    if cfg.layer_pattern and mixer.name == "attn":
        u = h1.astype(cfg.dtype)
    out, new_cache, handed = mixer.apply(
        cfg, p[mixer.name], u, side, cache,
        _Call(rng, lora, h1, layer_idx, handed))
    if handed.cut_rows is not None and KINDS[kind].keeps == "kv":
        # the boundary between the decoders: from here on, one row of
        # each sequence
        cut = lambda a: jnp.take_along_axis(  # noqa: E731
            a, handed.cut_rows[:, None, None], axis=1)
        x = cut(x)
        if handed.memory is not None:
            handed = handed._replace(memory=cut(handed.memory))
    return x, out, new_cache, handed


def layer_forward(cfg: ModelConfig, p: Params, x: jax.Array,
                  side: AttnSideInputs, layer_rng=None,
                  kv_cache: Optional[tuple] = None,
                  layer_idx=None, lora=None, kind: str = "full",
                  handed: Optional[Handed] = None):
    """One pre-LN residual block of ``kind`` (``config.KINDS``): the
    kind's mixer (``MIXERS``) and then the feed-forward part, each under
    a norm of its own, sequential or Falcon-parallel; a kind of one part
    is that part under one norm, ``x + f(norm(x))``.

    Parity: megatron/model/transformer.py:695-817
    (ParallelTransformerLayer.forward).  Returns ``(out, moe_aux)``; with
    ``kv_cache`` (what the kind's mixer keeps: ``Mixer.apply``)
    ``(out, moe_aux, new_cache)``; with ``handed`` (a layer scan's:
    :class:`Handed`) what this block hands on comes last.

    ``layer_idx`` (global layer number, may be traced) drives the LIMA
    dropout ramp and per-layer drop-path rate; None → flat rates.
    """
    if layer_idx is not None and (cfg.lima_dropout
                                  or cfg.drop_path_rate > 0.0):
        hidden_dropout, dp_rate = _layer_rates(cfg, layer_idx)
    else:
        hidden_dropout, dp_rate = cfg.hidden_dropout, 0.0

    def branch_drop(out, salt):
        """dropout then stochastic-depth on a residual branch (reference
        order: residual + drop_path(dropout(out)), transformer.py:717-734).
        """
        if layer_rng is None:
            return out
        out = _dropout(out, hidden_dropout,
                       jax.random.fold_in(layer_rng, salt),
                       side.deterministic)
        if isinstance(dp_rate, (int, float)) and dp_rate == 0.0:
            return out
        return _drop_path(out, dp_rate,
                          jax.random.fold_in(layer_rng, salt + 2),
                          side.deterministic)

    # Sequence parallelism: the residual stream enters/leaves each layer
    # seq-sharded; GSPMD turns this into the all-gather-before-qkv /
    # reduce-scatter-after-wo/w_down pattern the reference's
    # ColumnParallel(gather_output=False, sequence_parallel=True) layers
    # hand-code (core/tensor_parallel/layers.py:225-296).
    x = seq_constrain(x, side.seq_shard_axes)
    h1 = norm_apply(cfg.norm_type, x, p["input_norm"], cfg.norm_eps,
                    impl=cfg.norm_impl)
    ffn = KINDS[kind].ffn
    aux = None if ffn else _aux_zero(cfg)    # a mixer alone counts nothing
    x, attn_out, new_cache, handed_on = _mixer_part(
        cfg, p, x, h1, side, kind, layer_rng, kv_cache, layer_idx, lora,
        handed or Handed())
    if cfg.parallel_attn:
        if cfg.parallel_layernorm:
            mlp_in = norm_apply(cfg.norm_type, x, p["mlp_norm"],
                                cfg.norm_eps, impl=cfg.norm_impl)
        else:
            mlp_in = h1
        mlp_out, aux = _mlp_dispatch(cfg, p["mlp"], mlp_in, lora=lora)
        x = x + _scaled(cfg, branch_drop(attn_out + mlp_out, 2))
    else:
        if attn_out is not None:
            x = x + _scaled(cfg, branch_drop(attn_out, 2))
            if ffn:
                h1 = norm_apply(cfg.norm_type, x, p["post_attn_norm"],
                                cfg.norm_eps, impl=cfg.norm_impl)
        if ffn:
            m, aux = _mlp_dispatch(cfg, p["mlp"], h1, lora=lora,
                                   valid=side.valid)
            x = x + _scaled(cfg, branch_drop(m, 3))
    result = (seq_constrain(x, side.seq_shard_axes), aux)
    if kv_cache is not None:
        result += (new_cache,)
    return result if handed is None else result + (handed_on,)


def ffn_input(cfg: ModelConfig, p: Params, x: jax.Array,
              side: AttnSideInputs, kind: str = "full") -> jax.Array:
    """What the feed-forward part of a two-part attention block reads:
    the stream with the attention part's result added, under the block's
    second norm (``models/model.py:level_router_bias``)."""
    h1 = norm_apply(cfg.norm_type, x, p["input_norm"], cfg.norm_eps,
                    impl=cfg.norm_impl)
    x, out, _new, _on = _mixer_part(cfg, p, x, h1, side, kind, None, None,
                                    None, None, Handed())
    return norm_apply(cfg.norm_type, x + _scaled(cfg, out),
                      p["post_attn_norm"], cfg.norm_eps, impl=cfg.norm_impl)


def _scaled(cfg: ModelConfig, out):
    """A part's result as it is added to the residual stream: times
    ``cfg.residual_multiplier`` where the architecture has one."""
    if cfg.residual_multiplier == 1.0:
        return out
    return out * jnp.asarray(cfg.residual_multiplier, out.dtype)


def _remat_policy(cfg: ModelConfig):
    if cfg.recompute == "full":
        return jax.checkpoint_policies.nothing_saveable
    if cfg.recompute == "selective":
        # Save matmul outputs, recompute elementwise/softmax — the analogue of
        # the reference's selective recompute of core attention
        # (megatron/model/transformer.py:1080-1146).
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    return None


def stack_forward(cfg: ModelConfig, stacked: Params, x: jax.Array,
                  side: AttnSideInputs, base_rng=None, layer_offset=0,
                  lora=None, lead=None):
    """Run all layers with lax.scan over the stacked parameter pytree.

    Returns ``(hidden, moe_aux)`` — the aux load-balance loss summed over
    layers (0 for dense models).  ``layer_offset`` is the global index of
    the first layer in ``stacked`` (nonzero for pipeline chunks) so the
    LIMA/drop-path per-layer rate ramps stay global.

    ``lora`` is ``(arenas, mask)`` with layer-stacked arena factors
    (leading L axis, joining the scan xs) — the LoRA finetune path runs
    through here with the factors as the differentiable operand.

    ``lead``: the leading dense layers (``init_lead_params``), which run
    before the scan.
    """
    if cfg.layer_pattern:
        # (no rematerialisation: such a stack is served, not trained)
        assert lora is None, "a hybrid stack takes no adapters"
        x, _rows, _states, aux = scan_stack(
            cfg, stacked, x, side, lead=lead, base_rng=base_rng,
            layer_offset=layer_offset)
        return x, (jax.tree.map(lambda a: a.sum(0), aux)
                   if cfg.num_experts else _aux_zero(cfg))
    arenas, mask = lora if lora is not None else (None, None)

    def body(carry, inp):
        h, idx, aux_sum = carry
        if arenas is not None:
            layer_params, ar_l = inp
            layer_lora = (ar_l, mask)
        else:
            layer_params, = inp
            layer_lora = None
        rng = None
        if base_rng is not None:
            rng = jax.random.fold_in(base_rng, idx)
        h, aux = layer_forward(cfg, layer_params, h, side, rng,
                               layer_idx=layer_offset + idx,
                               lora=layer_lora)
        return (h, idx + 1, jax.tree.map(jnp.add, aux_sum, aux)), None

    policy = _remat_policy(cfg)
    if policy is not None:
        body = jax.checkpoint(body, policy=policy, prevent_cse=False)
    elif cfg.recompute != "none":
        body = jax.checkpoint(body, prevent_cse=False)

    xs = (stacked,) if arenas is None else (stacked, arenas)
    (x, _, aux), _ = jax.lax.scan(body, (x, 0, _aux_zero(cfg)), xs)
    return x, aux


def _aux_zero(cfg: ModelConfig):
    """The layer scan's accumulator for ``_mlp_dispatch``'s ``aux``."""
    if cfg.num_experts > 0:
        from .moe import stats_zero

        return stats_zero(cfg)
    return jnp.zeros((), jnp.float32)


# A hybrid stack carries its residual stream in float32 from the embedding
# to the final norm, and its mixers and experts add float32 results to it:
# every product still takes its operands in the weights' precision.  Each
# layer's router picks ten of 512 nearly level scores from that stream,
# and a pick that differs moves the token's output by a tenth at once, so
# rounding the stream to bfloat16 a few times a layer shows in the logits
# as it does not in a dense stack (PERF.md, PR 35).
STREAM_DTYPE = jnp.float32


class _Stacked(NamedTuple):
    """A recurrent mixer's two stacked state arrays and the number of the
    layer among them that a call is about."""

    S: jax.Array
    conv: jax.Array
    at: jax.Array


def _rec_state_at(mixer: "Mixer", stacked: dict, at, one_position: bool):
    """Layer ``at`` of ``mixer``'s stacked states ``{name: [layers, b,
    ...]}``; for one position both arrays stay stacked (the state's
    ``at``): the kernel picks the layer's state and tail where they
    lie."""
    S, conv = (stacked[name] for name in mixer.names)
    if one_position or mixer.state is _Stacked:
        return mixer.state(S, conv, at)
    conv, S = (jax.lax.dynamic_index_in_dim(a, at, 0, keepdims=False)
               for a in (conv, S))
    return mixer.state(S, conv)


def _rec_write_back(mixer: "Mixer", stacked: dict, new, at) -> dict:
    """``new`` as layer ``at`` of ``mixer``'s stacked states, in place: a
    prompt's end state and tail (XLA fuses the update into the write,
    whose operation is this one: so it stands under the scope of the form
    that made the state).  One position's kernel, and a mixer that takes
    the arrays stacked at any length, has written its layer into both
    already (``new.at``)."""
    if new.at is not None:
        return dict(zip(mixer.names, new[:2]))
    with jax.named_scope(mixer.scope):
        return {name: jax.lax.dynamic_update_index_in_dim(
            stacked[name], a, at, 0) for name, a in zip(mixer.names, new)}


@jax.named_scope("swa")
def _window_attend(cfg: ModelConfig, p: Params, u, side: AttnSideInputs,
                   ring, c: _Call):
    """A "window" layer's mixer (``Mixer.apply``): a sequence on itself
    under the window (``ring`` None: nothing kept; True: a prompt, whose
    ring comes back), or one new position a slot on the slot's ring
    ``(ring_k, ring_v, at)`` (the window layers' rings stacked, and which
    of them), whose new rows come back.  -> ``(out, None | the ring | the
    new rows, what was handed)``.

    The ring, its install (``diff_attention.ring_of``), its attention
    (``attend_ring``) and the rows' one write (``ring_append_rows``) are
    one mechanism; what differs by stack is read off ``cfg``.  Under
    differential attention: the pairs' projections, a ring row the keys
    of a pair side by side, the pairs' difference (``c.layer``).  Else:
    grouped heads at the window layers' own head count and rotation
    (``cfg.window_layer_config``), a ring row one key head's, and the gate
    a head (``c.gate_x``).  A key goes to the ring ROTATED at its own
    position, from the prompt and from a step alike, and a query is
    rotated at its own: the ring's rows need no positions, the count mask
    stands as it is."""
    if cfg.diff_attention:
        w = cfg
        q = diff_attention.project_q(cfg, p, u)
        k, v = diff_attention.project_kv(cfg, p, u)
        as_rows = lambda k_: diff_attention.pair_rows(cfg, k_)  # noqa: E731
        finish = lambda a: diff_attention.finish(  # noqa: E731
            cfg, p, a, c.layer)
    else:
        w = cfg.window_layer_config
        q, k, v, _gate = _project_heads(w, p, u, side)
        k, v = jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2)   # head-major
        as_rows = lambda k_: k_  # noqa: E731
        finish = lambda a: _project_out(  # noqa: E731
            w, p, a, None, c.gate_x)
    kept = None
    if ring is None or ring is True:
        a = diff_attention.attend_seq(w, q, k, v, cfg.sliding_window)
        if ring is True:
            n = (jnp.full((u.shape[0],), u.shape[1], jnp.int32)
                 if side.valid is None
                 else jnp.sum(side.valid, axis=1, dtype=jnp.int32))
            ring_k, ring_v = (diff_attention.ring_of(
                a_, n, cfg.sliding_window) for a_ in (k, v))
            kept = (as_rows(ring_k), ring_v)
    else:
        a = diff_attention.attend_ring(w, q, *ring, k, v,
                                       side.position_ids[:, 0])
        kept = (as_rows(k), v)
    return finish(a), kept, c.handed


# under these names the serving state tree keeps the "window" layers'
# rings, stacked over those layers (models/model.py:init_rec_state)
RING_NAMES = ("win_k", "win_v")


@jax.named_scope("swa")
def ring_append_rows(rings, rows, positions):
    """Write a step's new rows into the "window" layers' rings, in
    place: ``rings`` (k and v, each ``[window layers, slots, heads, W,
    width]``), ``rows`` the same with one position, slot ``s``'s at row
    ``positions[s] % W`` (a free slot rewrites a row of its own dead
    ring).  One ``dynamic_update_slice`` a slot over all the layers, as
    ``cache_append_rows`` writes the pool."""
    zero = jnp.int32(0)

    def ap(ring, r):
        at = positions % ring.shape[3]
        for s_ in range(r.shape[1]):
            ring = jax.lax.dynamic_update_slice(
                ring, r[:, s_:s_ + 1].astype(ring.dtype),
                [zero, jnp.int32(s_), zero, at[s_], zero])
        return ring

    return tuple(ap(ring, r) for ring, r in zip(rings, rows))


class Mixer(NamedTuple):
    """The models' half of a block kind (``config.KINDS`` holds the static
    half): the ``name`` a layer's tree holds the mixer's parameters under
    ("": the kind is the feed-forward part alone), ``init(keys, cfg,
    kind)`` which makes them, and ``apply(cfg, p, u, side, cache, call) →
    (out, the new cache, what is handed on)`` on the block's normed input
    ``u``; ``cache`` is what the kind keeps (``scan_stack``), None where
    nothing is kept.  A recurrent mixer also: the class of a layer's
    ``state`` (``S``, ``conv``, ``at``; :class:`_Stacked`: ``apply`` takes
    the stacked arrays and its layer's number at any length and slices and
    writes its layer itself), the ``names`` of the two stacked arrays in
    ``models/model.py:init_rec_state``'s tree, ``start(cfg, batch)`` a
    sequence's, and the ``scope`` of the form that makes a prompt's end
    state."""

    name: str = ""
    init: object = None
    apply: object = None
    state: Optional[type] = None
    names: tuple = ()
    start: object = None
    scope: str = ""


def _mix_attention(cfg, p, u, side, cache, c: _Call):
    """Softmax attention over K/V a KV head, latent attention
    (``cfg.kv_lora_rank``) or, differential, the "full" layer of its
    family, whose keys and values are handed on."""
    if cfg.diff_attention:
        with jax.named_scope("attention"):
            out, new, kv = diff_attention.attend_full(
                cfg, p, u, side, c.layer, cache, c.handed.cut_rows)
        return out, new, c.handed._replace(kv=kv)
    if cfg.kv_lora_rank:
        out = mla.mla_block(cfg, p, u, side, cache)
    else:
        out = attention_block(cfg, p, u, side, c.rng, cache, lora=c.lora,
                              gate_x=c.gate_x)
    return (out if cache is not None else (out, None)) + (c.handed,)


def _mix_cross(cfg, p, u, side, cache, c: _Call):
    with jax.named_scope("xattn"):
        return diff_attention.attend_handed(
            cfg, p, u, c.layer, c.handed.kv), None, c.handed


def _mix_mamba1(cfg, p, u, side, cache, c: _Call):
    """``cache``: the Mamba-1 layers' stacked states and this layer's
    number; its slice goes through the mixer and is written back here,
    under the scope of the form that made it."""
    state = None if cache is None else mamba1.Mamba1State(*(
        jax.lax.dynamic_index_in_dim(a, cache.at, 0, keepdims=False)
        for a in cache[:2]))
    out, new, memory = mamba1.mamba1_block(cfg, p, u, state, side.valid)
    if cache is not None:
        with jax.named_scope("mamba1/" + (
                "mamba1_scan" if side.cache_is_empty else "mamba1_step")):
            new = _Stacked(*(jax.lax.dynamic_update_index_in_dim(
                a, n, cache.at, 0) for a, n in zip(cache[:2], new)),
                cache.at)
    return out, new, c.handed._replace(memory=memory)


_ATTENTION = Mixer("attn", _init_attn, _mix_attention)
_MAMBA2 = Mixer(
    "mamba", lambda keys, cfg, kind: mamba2.init_mamba_params(keys[7], cfg),
    lambda cfg, p, u, side, cache, c: mamba2.mamba_block(
        cfg, p, u, cache, side.valid) + (c.handed,),
    mamba2.MambaState, mamba2.STATE_NAMES, mamba2.init_state,
    "mamba/mamba_scan")
MIXERS = {
    "full": _ATTENTION,
    "attention": _ATTENTION,
    "window": Mixer("attn", _init_attn, _window_attend),
    "cross": Mixer("attn", _init_attn, _mix_cross),
    "linear": Mixer(
        "gdn", lambda keys, cfg, kind: init_gdn_params(keys[7], cfg),
        lambda cfg, p, u, side, cache, c: gdn_block(
            cfg, p, u, cache, side.valid) + (c.handed,),
        GDNState, gated_deltanet.STATE_NAMES, gated_deltanet.init_state,
        "gdn/gdn_scan"),
    "ssm": _MAMBA2,
    "mamba": _MAMBA2,
    "ssm1": Mixer(
        "mamba1",
        lambda keys, cfg, kind: mamba1.init_mamba1_params(keys[7], cfg),
        _mix_mamba1, _Stacked, mamba1.STATE_NAMES, mamba1.init_state),
    "gmu": Mixer(
        "gmu",
        lambda keys, cfg, kind: diff_attention.init_gmu_params(keys[7], cfg),
        lambda cfg, p, u, side, cache, c: (
            diff_attention.gmu_block(p, u, c.handed.memory), None, c.handed)),
    "mlp": Mixer(),
}
assert set(MIXERS) == set(KINDS)
# the recurrent mixers, by what their kinds keep (``BlockKind.keeps``)
REC_MIXERS = {KINDS[kind].keeps: mixer for kind, mixer in MIXERS.items()
              if mixer.names}


def scan_stack(cfg: ModelConfig, stacked, x, side: AttnSideInputs,
               kv_of=None, rec: Optional[dict] = None, kv_xs: tuple = (),
               lead=None, cut_rows=None, base_rng=None, layer_offset=0):
    """Every form of a hybrid stack: the leading dense layers ``lead``
    (``init_lead_params``), then the runs of ``cfg.stack_runs`` one after
    the other, each a scan over its periods whose body runs one period's
    layers in order through ``layer_forward``, each of the kind its
    position has.  (``stacked``: ``init_stack_params``' tree; a
    ``layer_pattern``'s list of trees is the one run.)

    Without ``rec``: a whole sequence, every recurrent mixer from a zero
    state, nothing kept (``base_rng``, ``layer_offset``: the dropout of
    ``stack_forward``).  With ``rec``
    (``models/model.py:init_rec_state``): a prompt into an empty cache
    (``side.cache_is_empty``) or one new position a slot.  What a layer
    keeps it is given by its kind (``config.BlockKind.keeps``):

    - "kv": ``kv_of(kv_layer, *slices of kv_xs)``, ``kv_layer`` counting
      those layers alone, the leading ones first: the KV cache's own
      layer axis; its new rows come back.
    - a recurrent state: the states ride in the scan's carry; a prompt's
      layer reads and rewrites its own slice in place, a step's kernel
      takes them stacked and advances its layer where it lies
      (``_rec_state_at``, ``_rec_write_back``); they advance over the
      positions ``side.valid`` marks.
    - "window": a ring a slot under ``RING_NAMES``, not in the carry: a
      prompt's rings come back whole, a step attends the stacked rings
      where they lie (the scan closes over them) and its new rows are
      written behind the scan, once (``ring_append_rows``); either way
      they are among the states returned.

    Handed from layer to layer and from run to run (:class:`Handed`): the
    memory of the last "ssm1" layer before a "gmu" layer, and the "full"
    layer's keys and values for the "cross" layers, which therefore
    stands in a run that is written out (one period, in a stack of
    several runs): a scan hands on no keys and no stream cut to one row.
    ``cut_rows`` [b]: from that layer's attention on only that row of
    each sequence is carried (``cfg.row_cut_layer``): [b, 1, h] comes
    back.

    → ``(hidden, (rows_k, rows_v) stacked over the layers that keep
    "kv" or None, the states {name: array} advanced and the rings
    among them, counts: every layer's ``_mlp_dispatch``
    aux stacked [layers, ...], of which ``load`` and ``rows`` are what
    the engine counts: the experts the valid positions chose and the
    (token, choice) rows each layer's experts multiplied and skipped;
    zero rows for a layer without experts, the leading ones among
    them)``."""
    runs = cfg.stack_runs
    trees = stacked if cfg.layer_runs else [stacked]
    several = len(runs) > 1
    n_lead = cfg.moe_first_dense_layers
    kinds = cfg.layer_kinds[n_lead:]            # the scanned layers'
    prompt = side.cache_is_empty
    assert rec is None or prompt or x.shape[1] == 1, (
        "a prompt into an empty cache, or one new position a slot")
    assert cut_rows is None or cfg.row_cut_layer is not None
    x = x.astype(STREAM_DTYPE)
    lead_rows = []
    for i, layer_params in enumerate(_lead_layers(lead)):
        x, _aux, *new = layer_forward(
            cfg.lead_layer_config, layer_params, x, side,
            kv_cache=None if kv_of is None else kv_of(
                jnp.int32(i), *(a[i] for a in kv_xs)))
        lead_rows += new
    if n_lead:
        kv_xs = tuple(a[n_lead:] for a in kv_xs)
    states = {} if rec is None else {
        name: rec[name] for keeps, mixer in REC_MIXERS.items()
        if cfg.layers_keeping(keeps) for name in mixer.names}
    rings = (tuple(rec[name] for name in RING_NAMES)
             if rec is not None and cfg.window_layers else ())

    def nth(first, idx, per, j):
        """The number, among its like, of the ``j``-th of the ``per``
        such layers of period ``idx`` of a run before which ``first``
        stand (one run needs no first: spelled as the cells' programs
        were lowered)."""
        at = idx * per
        if several:
            at = first + at
        return at + j

    flat = lambda a: a.reshape(  # noqa: E731
        (a.shape[0] * a.shape[1],) + a.shape[2:])
    stack = lambda xs_: jax.tree.map(  # noqa: E731
        lambda *a: jnp.stack(a), *xs_) if xs_ else ()
    out = ([], [], [])                  # rows, counts, kept: a part a run
    memory_in, kv_in = None, None       # what the runs before hand on
    layer0, first = 0, {}       # the layers, and those that keep a thing,
    #                             before the run
    for (period, times), run_trees in zip(runs, trees):
        n = len(period)
        keeps = [KINDS[kind].keeps for kind in period]
        per = {k: keeps.count(k) for k in keeps}
        later = kinds[layer0 + n * times:]
        reads = [KINDS[kind].reads for kind in later]
        # (a run's memory is carried on where a later run gates with it
        # before making its own)
        hands_memory = "ssm1" in per and "memory" in reads and (
            "ssm1" not in [KINDS[kind].keeps
                           for kind in later[:reads.index("memory")]])
        # Where a run scans more than one period, the routed experts'
        # matrices do not ride in its xs: a per-layer slice of them is a
        # copy of every expert for the kernel's custom call.  The scan
        # closes over the stack's and the kernel addresses its layer
        # (``expert_layer``).  With one period XLA makes no copy, and the
        # two expert cells' programs stay what they were (their digests
        # are held to the parent's): one way for both is a change to
        # those cells, to be measured on them (PERF.md section 7 w).
        experts = {}
        if times > 1 and cfg.moe_dropless:
            run_trees = list(run_trees)
            for j, tree in enumerate(run_trees):
                mlp = tree.get("mlp", {})
                experts[j] = {k: mlp[k] for k in ("w_gate", "w_up", "w_down")
                              if k in mlp}
                run_trees[j] = {**tree, "mlp": {
                    k: v for k, v in mlp.items() if k not in experts[j]}}

        # (called in this turn of the loop, written out or under the scan:
        # it reads the turn's own variables)
        def body(carry, inp):
            h, idx, states, memory = carry
            period_params, kv_p = inp
            handed = Handed(memory_in if memory is None else memory, kv_in,
                            cut_rows)
            rows, counts, kept = [], [], []
            seen = dict.fromkeys(per, 0)
            for j, (kind, keep, p) in enumerate(
                    zip(period, keeps, period_params)):
                if experts.get(j):
                    p = {**p, "mlp": {**p["mlp"], **experts[j],
                                      "expert_layer": idx}}
                layer = nth(layer0, idx, n, j)
                if n_lead:
                    layer = layer + n_lead
                cache, mixer = None, REC_MIXERS.get(keep)
                if keep:
                    at = nth(first.get(keep, 0), idx, per[keep], seen[keep])
                if keep == "kv" and kv_of is not None:
                    cache = kv_of(at + n_lead if n_lead else at,
                                  *(a[seen[keep]] for a in kv_p))
                elif keep == "window" and rec is not None:
                    cache = True if prompt else rings + (at,)
                elif mixer and rec is not None:
                    cache = _rec_state_at(mixer, states, at, h.shape[1] == 1)
                if keep:
                    seen[keep] += 1
                h, aux, *new, handed = layer_forward(
                    cfg, p, h, side,
                    None if base_rng is None
                    else jax.random.fold_in(base_rng, layer),
                    kv_cache=cache,
                    layer_idx=layer_offset + layer if layer_offset else layer,
                    kind=kind, handed=handed)
                if cache is not None and mixer:
                    states = {**states,
                              **_rec_write_back(mixer, states, new[0], at)}
                elif cache is not None:
                    (rows if keep == "kv" else kept).extend(new)
                counts.append(aux if isinstance(aux, dict) else {
                    "load": jnp.zeros((0,), jnp.float32),
                    "rows": jnp.zeros((2,), jnp.float32)})
            carry = (h, idx + 1, states,
                     handed.memory if hands_memory else None)
            return carry, (stack(rows), stack(counts), stack(kept)), handed.kv

        lo, n_kv = first.get("kv", 0), per.get("kv", 0)
        xs = (tuple(run_trees), tuple(
            (a[lo:lo + times * n_kv] if several else a)
            .reshape((times, n_kv) + a.shape[1:]) for a in kv_xs))
        carry = (x, jnp.int32(0), states, None)
        if several and times == 1:
            # written out: the period may hand on what a scan could not
            # (the keys and values, a stream cut to one row)
            carry, ys, kv_in = body(carry, jax.tree.map(lambda a: a[0], xs))
            ys = jax.tree.map(lambda a: a[None], ys)
        else:
            assert "kv" not in per or not cfg.cross_layers, (
                "the layer whose keys and values are handed on stands in "
                "a run of one period, of several runs")
            if hands_memory:
                carry = carry[:3] + (jnp.zeros(
                    x.shape[:2] + (cfg.mamba1_inner,), jnp.float32),)
            carry, ys = jax.lax.scan(lambda c, i: body(c, i)[:2], carry, xs)
        x, _, states, memory_in = carry
        for parts, ys_ in zip(out, ys):
            if jax.tree.leaves(ys_):
                parts.append(jax.tree.map(flat, ys_))
        layer0 += n * times
        for k in per:
            first[k] = first.get(k, 0) + times * per[k]
    rows, counts, kept = (
        jax.tree.map(lambda *a: jnp.concatenate(a), *parts) if parts else None
        for parts in out)
    if kept is not None:
        # a prompt's rings whole; a step's rows, written behind the scan
        states = {**states, **dict(zip(RING_NAMES, (
            kept if prompt else ring_append_rows(
                rings, kept, side.position_ids[:, 0]))))}
    if lead_rows:
        rows = jax.tree.map(lambda *a: jnp.concatenate(
            [jnp.stack(a[:-1]), a[-1]]), *lead_rows, rows)
    if n_lead:
        counts = jax.tree.map(lambda a: jnp.concatenate(
            [jnp.zeros((n_lead,) + a.shape[1:], a.dtype), a]), counts)
    return x, rows, states, counts


def _scan_layers_cached(cfg: ModelConfig, stacked: Params, x: jax.Array,
                        side: AttnSideInputs, xs_extra: tuple, kv_of,
                        lora=None):
    """The decode paths' layer scan: ``kv_of(idx, *extra_l)`` builds layer
    ``idx``'s ``kv_cache`` argument from its slices of ``xs_extra`` (read-
    only xs beside the stacked parameters); each layer returns only its
    new token rows, which stack on a leading layer axis as ys.  Returns
    ``(hidden, (rows_k, rows_v))``."""
    arenas, mask = lora if lora is not None else (None, None)
    n_extra = len(xs_extra)

    def body(carry, inp):
        h, idx = carry
        layer_params, extra = inp[0], inp[1:1 + n_extra]
        layer_lora = (inp[-1], mask) if arenas is not None else None
        h, _aux, rows = layer_forward(
            cfg, layer_params, h, side, None,
            kv_cache=kv_of(idx, *extra), lora=layer_lora)
        return (h, idx + 1), rows

    xs = (stacked,) + tuple(xs_extra) + (() if arenas is None
                                          else (arenas,))
    (x, _), rows = jax.lax.scan(body, (x, jnp.int32(0)), xs)
    return x, rows


def stack_forward_cached(cfg: ModelConfig, stacked: Params, x: jax.Array,
                         side: AttnSideInputs,
                         k_cache: jax.Array,  # [L, b, nkv, max_len, d]
                         v_cache: jax.Array,
                         cache_len: jax.Array, lora=None):
    """Scan over layers threading a per-layer KV cache (decode path).

    The cache is stacked on the leading layer axis, mirroring the stacked
    parameter layout, so one compiled layer body serves every depth.  The
    caches enter the scan as read-only *xs* (per-layer slices); each layer
    returns only its new token rows ([L, b, nkv, s, d] stacked ys) and one
    batched dynamic_update_slice after the scan writes them back (caches
    threaded through the scan's ys are re-stacked, copied whole, a step).
    Returns ``(hidden, new_k_cache, new_v_cache)``; the caller advances
    ``cache_len``.  Parity: the reference's InferenceParams threading
    through ParallelTransformer (transformer.py:423-496,1158-1246).
    """
    x, (rows_k, rows_v) = _scan_layers_cached(
        cfg, stacked, x, side, (k_cache, v_cache),
        lambda _idx, k_l, v_l: (k_l, v_l, cache_len), lora=lora)
    # one batched row write [L, b, nkv, s_new, d] — XLA aliases the DUS
    # with the loop-carried cache buffer, so decode writes s_new rows
    # instead of round-tripping the whole cache.  cache_update also
    # quantizes the rows when the cache is the int8 form (kv_quant.py).
    from ..ops.kv_quant import cache_update

    new_k = cache_update(k_cache, rows_k, cache_len)
    new_v = cache_update(v_cache, rows_v, cache_len)
    return x, new_k, new_v


def stack_forward_paged(cfg: ModelConfig, stacked: Params, x: jax.Array,
                        side: AttnSideInputs,
                        k_pool,              # [L, n_blocks, nkv, bk, d]
                        v_pool,
                        tables: jax.Array,   # [b, T] int32
                        fills: jax.Array,    # [b] int32
                        lora=None):
    """The paged twin of ``stack_forward_cached``: one new token a slot,
    each layer's attention reading its KV out of the block pool through
    the tables (:class:`PagedKV`).  The pool is never written here and no
    dense view of it is built: returns ``(hidden, rows_k, rows_v)``, the
    layers' new rows ``[L, b, nkv, 1(, d)]`` in the form the pool stores
    them, for the caller's one ``cache_append_rows``.

    The scan closes over the whole pool and each layer's kernel addresses
    its own layer through the index maps: a per-layer slice taken by the
    scan is a copy of that slice for every custom call.  The weights are
    the scan's xs, and every projection takes its layer's slice inside
    its matmul's fusion, read once where it lies; that holds while no
    operation wants the weight laid out another way, which is why
    ``attention_block`` rotates this route's q and k before it cuts them
    into heads (obs/hlo_audit.py audits the executable for it)."""
    x, (rows_k, rows_v) = _scan_layers_cached(
        cfg, stacked, x, side, (),
        lambda idx: PagedKV(k_pool, v_pool, tables, fills, idx), lora=lora)
    return x, rows_k, rows_v


def rope_tables(cfg: ModelConfig, dtype=jnp.float32):
    if (cfg.position_embedding_type != PositionEmbeddingType.ROTARY
            or cfg.rotary_percent < 1.0
            or cfg.rope_rotate_half):       # rotated from the positions
        return None, None
    return precompute_rope_freqs(
        cfg.head_dim,
        cfg.max_position_embeddings,
        theta=cfg.rope_theta,
        scaling_factor=cfg.rope_scaling_factor,
        scaling_type=cfg.rope_scaling_type,
        low_freq_factor=cfg.rope_low_freq_factor,
        high_freq_factor=cfg.rope_high_freq_factor,
        original_max_positions=cfg.rope_original_max_positions,
        beta_fast=cfg.rope_beta_fast,
        beta_slow=cfg.rope_beta_slow,
        attention_factor=cfg.rope_attention_factor,
        dtype=dtype,
    )
