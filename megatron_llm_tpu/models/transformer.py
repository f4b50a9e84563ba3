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

from ..config import (
    KV_KINDS,
    MAMBA_KINDS,
    ModelConfig,
    PositionEmbeddingType,
)
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


def init_layer_params(key: jax.Array, cfg: ModelConfig,
                      kind: str = "full") -> Params:
    """Parameters of one transformer layer (unstacked), of one of
    ``config.BLOCK_KINDS``.  A block of two parts holds a mixer
    (``"attn"``, ``"gdn"`` for a ``"linear"`` layer, ``"mamba"`` for an
    ``"ssm"`` layer), ``"mlp"`` and a norm for each; a block of one part
    holds that part (``"attn"``, ``"mamba"`` or ``"mlp"``) under
    ``"input_norm"`` alone."""
    h = cfg.hidden_size
    d = cfg.head_dim
    # (a "window" layer of the period scan has a head count of its own)
    nq = (cfg.window_layer_config if kind == "window"
          else cfg).num_attention_heads
    nkv = cfg.kv_heads
    ffn = cfg.ffn_size
    dtype = cfg.dtype
    std = cfg.init_method_std
    # output-layer init scaled by 1/sqrt(2*num_layers)
    out_std = std / (2.0 * cfg.num_layers) ** 0.5 if cfg.use_scaled_init else std

    keys = jax.random.split(key, 8)
    layer: Params = {"input_norm": norm_init(cfg.norm_type, h, dtype)}
    if cfg.diff_attention and kind in ("full", "window", "cross"):
        layer["attn"] = diff_attention.init_diff_attn_params(
            keys[0], cfg, cross=kind == "cross")
    elif kind == "ssm1":
        layer["mamba1"] = mamba1.init_mamba1_params(keys[7], cfg)
    elif kind == "gmu":
        layer["gmu"] = diff_attention.init_gmu_params(keys[7], cfg)
    elif kind in KV_KINDS and cfg.kv_lora_rank:
        layer["attn"] = mla.init_mla_params(keys[0], cfg, std, out_std)
    elif kind in KV_KINDS or kind == "window":
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
        layer["attn"] = attn
    elif kind == "linear":
        layer["gdn"] = init_gdn_params(keys[7], cfg)
    elif kind in MAMBA_KINDS:
        layer["mamba"] = mamba2.init_mamba_params(keys[7], cfg)
    if kind in ("attention", "mamba"):
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
    if kind == "mlp":
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
    such tree a position of the period, each stacked over the periods;
    leading dense layers are not among them (``init_lead_params``)."""
    n = num_layers if num_layers is not None else cfg.scanned_layers
    if cfg.layer_runs:
        # a list of runs, each a list with one tree a position of the
        # run's period, stacked over the run's periods
        keys, runs, at = jax.random.split(key, cfg.num_layers), [], 0
        for period, times in cfg.layer_runs:
            mine = keys[at:at + len(period) * times]
            runs.append([jax.vmap(lambda k, kind=kind: init_layer_params(
                k, cfg, kind))(mine[j::len(period)])
                for j, kind in enumerate(period)])
            at += len(period) * times
        return runs
    if cfg.layer_pattern:
        kinds = cfg.layer_pattern
        keys = jax.random.split(key, n)
        return [jax.vmap(lambda k, kind=kind: init_layer_params(
            k, cfg, kind))(keys[j::len(kinds)])
            for j, kind in enumerate(kinds)]
    keys = jax.random.split(key, n)
    return jax.vmap(lambda k: init_layer_params(k, cfg))(keys)


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
    if cfg.apply_query_key_layer_scaling:
        # reference scales by 1/layer inside softmax and compensates in the
        # matmul (transformer.py:191-236); net effect is standard scale, so
        # only the numerically-relevant fp32 softmax is kept.
        pass

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
            # O(s·max_len) (which at s=1024, max_len=1152 materialized
            # ~300 MB of scores per layer: measured 30.9k tok/s prefill
            # vs ~4x that through this path on v5e)
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
        # so decode never copies the O(max_len) cache (measured 8-30x of
        # the whole per-step cost before this change)
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


def layer_forward(cfg: ModelConfig, p: Params, x: jax.Array,
                  side: AttnSideInputs, layer_rng=None,
                  kv_cache: Optional[tuple] = None,
                  layer_idx=None, lora=None, kind: str = "full"):
    """One pre-LN residual block, sequential or Falcon-parallel.

    Parity: megatron/model/transformer.py:695-817
    (ParallelTransformerLayer.forward).  Returns ``(out, moe_aux)``; with
    ``kv_cache`` returns ``(out, moe_aux, new_cache)``.

    ``layer_idx`` (global layer number, may be traced) drives the LIMA
    dropout ramp and per-layer drop-path rate; None → flat rates.

    A block of one part (a hybrid stack's ``"attention"``, ``"mamba"``
    and ``"mlp"`` kinds) is ``_one_part_forward``'s.  ``kind`` "window":
    the block's attention part is ``_window_attend``'s, and its
    ``kv_cache`` the ring's forms there (True or the stacked rings).
    """
    if "mlp" not in p or not any(m in p for m in ("attn", "gdn", "mamba")):
        return _one_part_forward(cfg, p, x, side, layer_rng, kv_cache)
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
    residual = x
    h1 = norm_apply(cfg.norm_type, x, p["input_norm"], cfg.norm_eps,
                    impl=cfg.norm_impl)
    new_cache = None
    gate_x = h1          # a gate a head reads the stream's norm as it is
    if cfg.layer_pattern:
        # a hybrid stack's residual stream is float32 (``stream_dtype``);
        # attention computes in the model's own precision
        h1 = h1 if "attn" not in p else h1.astype(cfg.dtype)
    if "gdn" in p:
        # a linear layer: its "cache" is the recurrent state, carried or
        # (None) started at zero; the new one is dropped with no cache
        attn_out, new_cache = gdn_block(cfg, p["gdn"], h1, kv_cache,
                                        side.valid)
    elif "mamba" in p:
        # an ssm layer: the same, with the state-space state
        attn_out, new_cache = mamba2.mamba_block(cfg, p["mamba"], h1,
                                                 kv_cache, side.valid)
    elif kind == "window":
        with jax.named_scope("swa"):
            attn_out, new_cache = _window_attend(
                cfg, p["attn"], h1, side, layer_idx, kv_cache, gate_x)
    else:
        attn_out, new_cache = _attend(cfg, p["attn"], h1, side, layer_rng,
                                      kv_cache, lora, gate_x)

    if cfg.parallel_attn:
        if cfg.parallel_layernorm:
            mlp_in = norm_apply(cfg.norm_type, x, p["mlp_norm"],
                                cfg.norm_eps, impl=cfg.norm_impl)
        else:
            mlp_in = h1
        mlp_out, aux = _mlp_dispatch(cfg, p["mlp"], mlp_in, lora=lora)
        result = residual + _scaled(
            cfg, branch_drop(attn_out + mlp_out, 2))
    else:
        x = residual + _scaled(cfg, branch_drop(attn_out, 2))
        h2 = norm_apply(cfg.norm_type, x, p["post_attn_norm"],
                        cfg.norm_eps, impl=cfg.norm_impl)
        m, aux = _mlp_dispatch(cfg, p["mlp"], h2, lora=lora,
                               valid=side.valid)
        result = x + _scaled(cfg, branch_drop(m, 3))
    result = seq_constrain(result, side.seq_shard_axes)
    if kv_cache is not None:
        return result, aux, new_cache
    return result, aux


def _attend(cfg: ModelConfig, p: Params, h1: jax.Array,
            side: AttnSideInputs, layer_rng, kv_cache, lora=None,
            gate_x=None):
    """A block's attention part, softmax attention over K/V a KV head or
    latent attention (``cfg.kv_lora_rank``), → ``(out, the new rows or
    None)``."""
    if cfg.kv_lora_rank:
        out = mla.mla_block(cfg, p, h1, side, kv_cache)
    elif kv_cache is not None:
        out = attention_block(cfg, p, h1, side, layer_rng, kv_cache,
                              lora=lora, gate_x=gate_x)
    else:
        out = attention_block(cfg, p, h1, side, layer_rng, lora=lora,
                              gate_x=gate_x)
    return out if kv_cache is not None else (out, None)


def _one_part_forward(cfg: ModelConfig, p: Params, x: jax.Array,
                      side: AttnSideInputs, layer_rng, kv_cache,
                      kind: str = "full"):
    """A block of one part under one norm, ``x + f(norm(x))``: softmax
    attention, a Mamba-2 mixer or the feed-forward part alone, by what
    ``p`` holds.  Returns as ``layer_forward`` does; the feed-forward
    part keeps no cache (``kv_cache`` None, two results)."""
    h1 = norm_apply(cfg.norm_type, x, p["input_norm"], cfg.norm_eps,
                    impl=cfg.norm_impl)
    aux, new_cache = _aux_zero(cfg), None
    if "mamba" in p:
        # its "cache" is the state-space state, carried or (None) started
        # at zero; the new one is dropped with no cache
        out, new_cache = mamba2.mamba_block(cfg, p["mamba"], h1, kv_cache,
                                            side.valid)
    elif kind == "window":
        with jax.named_scope("swa"):
            out, new_cache = _window_attend(
                cfg, p["attn"], h1.astype(cfg.dtype), side, None, kv_cache,
                h1)
    elif "attn" in p:
        # attention computes in the model's own precision
        out, new_cache = _attend(cfg, p["attn"], h1.astype(cfg.dtype), side,
                                 layer_rng, kv_cache, gate_x=h1)
    else:
        out, aux = _mlp_dispatch(cfg, p["mlp"], h1, valid=side.valid)
    result = x + _scaled(cfg, out)
    if kv_cache is not None:
        return result, aux, new_cache
    return result, aux


def ffn_input(cfg: ModelConfig, p: Params, x: jax.Array,
              side: AttnSideInputs, kind: str = "full") -> jax.Array:
    """What the feed-forward part of a two-part attention block reads:
    the stream with the attention part's result added, under the block's
    second norm (``models/model.py:level_router_bias``)."""
    x = _one_part_forward(cfg, {k: p[k] for k in ("input_norm", "attn")},
                          x, side, None, None, kind)[0]
    return norm_apply(cfg.norm_type, x, p["post_attn_norm"], cfg.norm_eps,
                      impl=cfg.norm_impl)


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
    if cfg.layer_runs:
        assert lora is None and lead is None
        return scan_runs_cached(cfg, stacked, x, side)[0], _aux_zero(cfg)
    if cfg.layer_pattern:
        assert lora is None, "a hybrid stack takes no adapters"
        return _stack_forward_periods(cfg, stacked, x, side, base_rng,
                                      layer_offset, lead)
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


def _stack_forward_periods(cfg: ModelConfig, stacked, x, side, base_rng,
                           layer_offset, lead=None):
    """``stack_forward`` for a hybrid stack: the scan runs over the
    periods, and its body runs one period's layers in order, each of the
    kind its position has.  Every recurrent mixer starts from a zero state
    and drops the one it ends with: a whole sequence, no cache.  (No
    rematerialisation: such a stack is served, not trained.)"""
    n_pos = len(cfg.layer_pattern)
    n_lead = cfg.moe_first_dense_layers
    x = x.astype(STREAM_DTYPE)
    for layer_params in _lead_layers(lead):
        x = layer_forward(cfg.lead_layer_config, layer_params, x, side)[0]

    def body(carry, period):
        h, idx, aux_sum = carry
        for j, layer_params in enumerate(period):
            layer = idx * n_pos + j
            if n_lead:
                layer = layer + n_lead
            rng = (None if base_rng is None
                   else jax.random.fold_in(base_rng, layer))
            h, aux = layer_forward(cfg, layer_params, h, side, rng,
                                   layer_idx=layer_offset + layer,
                                   kind=cfg.layer_pattern[j])[:2]
            aux_sum = jax.tree.map(jnp.add, aux_sum, aux)
        return (h, idx + 1, aux_sum), None

    (x, _, aux), _ = jax.lax.scan(body, (x, 0, _aux_zero(cfg)),
                                  tuple(stacked))
    return x, aux


class _RecMixer(NamedTuple):
    """A kind of recurrent mixer as ``scan_periods_cached`` carries its
    state: the class of a layer's state (``S``, ``conv``, ``at``), the
    names of the two stacked arrays in ``models/model.py:init_rec_state``'s
    tree, and the scope of the form that makes a prompt's end state."""

    state: type
    names: tuple
    scope: str


_GDN = _RecMixer(GDNState, gated_deltanet.STATE_NAMES, "gdn/gdn_scan")
_MAMBA = _RecMixer(mamba2.MambaState, mamba2.STATE_NAMES,
                   "mamba/mamba_scan")
_REC_KINDS = {"linear": _GDN, **{kind: _MAMBA for kind in MAMBA_KINDS}}


def _rec_state_at(mixer: _RecMixer, stacked: dict, at, one_position: bool):
    """Layer ``at`` of ``mixer``'s stacked states ``{name: [layers, b,
    ...]}``; for one position both arrays stay stacked (the state's
    ``at``): the kernel picks the layer's state and tail where they
    lie."""
    S, conv = (stacked[name] for name in mixer.names)
    if one_position:
        return mixer.state(S, conv, at)
    conv, S = (jax.lax.dynamic_index_in_dim(a, at, 0, keepdims=False)
               for a in (conv, S))
    return mixer.state(S, conv)


def _rec_write_back(mixer: _RecMixer, stacked: dict, new, at) -> dict:
    """``new`` as layer ``at`` of ``mixer``'s stacked states, in place: a
    prompt's end state and tail (XLA fuses the update into the write,
    whose operation is this one: so it stands under the scope of the form
    that made the state).  One position's kernel has written its layer
    into both stacked arrays already (``new.at``)."""
    if new.at is not None:
        return dict(zip(mixer.names, new[:2]))
    with jax.named_scope(mixer.scope):
        return {name: jax.lax.dynamic_update_index_in_dim(
            stacked[name], a, at, 0) for name, a in zip(mixer.names, new)}


def scan_periods_cached(cfg: ModelConfig, stacked, x, side, kv_of, rec,
                        kv_xs: tuple = (), lead=None):
    """The cached forms of a hybrid stack, prefill and decode alike: a
    scan over the periods whose body gives each layer that attends
    (``"full"``, ``"attention"``) its ``kv_cache`` (``kv_of(kv_layer,
    *slices of kv_xs)``, ``kv_layer`` counting those layers alone: the KV
    cache's own layer axis) and each recurrent mixer its state out of
    ``rec`` (``models/model.py:init_rec_state``): a ``"linear"`` layer
    ``{"S": [linear layers, b, ...], "conv": [...]}``, a ``"mamba"`` or
    ``"ssm"`` layer ``{"ssm": [mamba layers, b, ...], "ssm_conv":
    [...]}``.  The states of either kind ride in the scan's carry: a
    prompt's layer reads and rewrites its own slice in place, a decode
    step's kernel takes them stacked and advances its layer where it lies
    (``_rec_state_at``, ``_rec_write_back``).  A ``"window"`` layer
    (``_window_attend``) keeps no cache layer and a ring a slot under
    ``RING_NAMES``: a prompt's rings come back whole, a step attends the
    stacked rings where they lie and its new rows are written behind the
    scan, once (``ring_append_rows``); either way they are among the
    states returned.

    → ``(hidden, (rows_k, rows_v) stacked over the attending layers, rec's
    states advanced over the positions ``side.valid`` marks, counts
    ``{"load": [layers, router outputs], "rows": [layers, 2]}``: the
    experts those positions chose, and the (token, choice) rows each
    layer's experts multiplied and skipped; zero for a layer without
    experts)``.

    The leading dense layers ``lead`` (``init_lead_params``) run before
    the scan, each attending with the first of the KV cache's layers and
    counting no expert; their rows and zero counts come first."""
    kinds = cfg.layer_pattern
    n_per = cfg.scanned_layers // len(kinds)
    n_full = sum(kind in KV_KINDS for kind in kinds)
    n_lead = cfg.moe_first_dense_layers
    x = x.astype(STREAM_DTYPE)
    lead_rows = []
    for i, layer_params in enumerate(_lead_layers(lead)):
        x, _aux, new = layer_forward(
            cfg.lead_layer_config, layer_params, x, side, None,
            kv_cache=kv_of(jnp.int32(i), *(a[i] for a in kv_xs)))
        lead_rows.append(new)
    if n_lead:
        kv_xs = tuple(a[n_lead:] for a in kv_xs)

    def by_period(a, n):
        return a.reshape((n_per, n) + a.shape[1:])

    # Where the scan has more than one period, the routed experts'
    # matrices do not ride in its xs: a per-layer slice of them is a copy
    # of every expert for the kernel's custom call.  The scan closes over
    # the stack's and the kernel addresses its layer (``expert_layer``).
    # With one period XLA makes no copy, and the two expert cells'
    # programs stay what they were (their digests are held to the
    # parent's): one way for both is a change to those cells, to be
    # measured on them (PERF.md section 7 w).
    experts = {}
    if n_per > 1 and cfg.moe_dropless:
        stacked = list(stacked)
        for j, tree in enumerate(stacked):
            mlp = tree.get("mlp", {})
            experts[j] = {k: mlp[k] for k in ("w_gate", "w_up", "w_down")
                          if k in mlp}
            stacked[j] = {**tree, "mlp": {k: v for k, v in mlp.items()
                                          if k not in experts[j]}}
    xs = (tuple(stacked), tuple(by_period(a, n_full) for a in kv_xs))
    # a layer's recurrent mixer (None: it keeps no such state), its place
    # among that mixer's layers of a period, and how many those are
    mixers = [_REC_KINDS.get(kind) for kind in kinds]
    place = [mixers[:j].count(mixer) for j, mixer in enumerate(mixers)]
    n_rec = {mixer: mixers.count(mixer) for mixer in mixers if mixer}
    states = {name: rec[name] for mixer in n_rec for name in mixer.names}
    # a period's "window" layers: their rings do not ride in the carry.
    # A prompt's come back whole as the scan's ys; a step's kernel reads
    # the stacked rings where they lie (the scan closes over them) and
    # its new ROWS come back, for one write behind the scan
    # (``ring_append_rows``): PR 56's way, the one way both scans have
    n_win = kinds.count("window")
    prompt = side.cache_is_empty
    rings = tuple(rec[name] for name in RING_NAMES) if n_win else ()
    assert not n_win or prompt or x.shape[1] == 1, (
        "a \"window\" layer: a prompt into an empty cache, or one new "
        "position a slot")

    def body(carry, inp):
        h, idx, states = carry
        period, kv_p = inp
        rows, counts, kept, f = [], [], [], 0
        for at_j, (layer_params, kind, mixer, j) in enumerate(
                zip(period, kinds, mixers, place)):
            if experts.get(at_j):
                layer_params = {**layer_params, "mlp": {
                    **layer_params["mlp"], **experts[at_j],
                    "expert_layer": idx}}
            attends, cache = kind in KV_KINDS, None
            if attends:
                kv_layer = idx * n_full + f
                if n_lead:
                    kv_layer = kv_layer + n_lead
                cache = kv_of(kv_layer, *(a[f] for a in kv_p))
                f += 1
            elif kind == "window":
                cache = True if prompt else rings + (
                    idx * n_win + kinds[:at_j].count("window"),)
            elif mixer:
                at = idx * n_rec[mixer] + j
                cache = _rec_state_at(mixer, states, at, h.shape[1] == 1)
            h, aux, *new = layer_forward(cfg, layer_params, h, side, None,
                                         kv_cache=cache, kind=kind)
            if attends:
                rows += new
            elif kind == "window":
                kept += new
            elif mixer:
                states = {**states,
                          **_rec_write_back(mixer, states, new[0], at)}
            counts.append(
                {name: aux[name] for name in ("load", "rows")}
                if isinstance(aux, dict) else
                {"load": jnp.zeros((0,), jnp.float32),
                 "rows": jnp.zeros((2,), jnp.float32)})
        stack = lambda xs_: jax.tree.map(lambda *a: jnp.stack(a), *xs_) \
            if xs_ else ()
        return (h, idx + 1, states), (stack(rows), stack(counts),
                                      stack(kept))

    (x, _, states), (rows, counts, kept) = jax.lax.scan(
        body, (x, jnp.int32(0), states), xs)
    flat = lambda a: a.reshape((a.shape[0] * a.shape[1],) + a.shape[2:])
    rows, counts = jax.tree.map(flat, rows), jax.tree.map(flat, counts)
    if n_win:
        kept = jax.tree.map(flat, kept)
        states = {**states, **dict(zip(RING_NAMES, (
            kept if prompt else ring_append_rows(
                rings, kept, side.position_ids[:, 0]))))}
    if lead_rows:
        rows = jax.tree.map(lambda *a: jnp.concatenate(
            [jnp.stack(a[:-1]), a[-1]]), *lead_rows, rows)
        counts = jax.tree.map(lambda a: jnp.concatenate(
            [jnp.zeros((n_lead,) + a.shape[1:], a.dtype), a]), counts)
    return x, rows, states, counts


class KVHand(NamedTuple):
    """What the one "full" layer of a stack of runs hands to the "cross"
    layers behind it: its keys and values, in the form the cross layers
    attend them.  ``form`` "seq": ``k v`` head-major rows of the whole
    sequence, a query at every position; "rows": dense ``k v`` [b, ., S,
    .] and the positions ``last`` [b, r] of the few query rows; "paged":
    ``paged`` the pool (:class:`PagedKV`) and ``k v`` the step's own
    rows, which are not in it yet."""

    form: str
    k: jax.Array
    v: jax.Array
    last: Optional[jax.Array] = None
    paged: Optional[PagedKV] = None


def _diff_attend(cfg: ModelConfig, p: Params, u, layer, hand: KVHand,
                 q_rows=None):
    """A layer's differential attention with the keys and values of
    ``hand``: its own query projection (of the rows ``q_rows`` [b] alone
    where given), the form's attention, the pairs' difference and the
    output projection."""
    if q_rows is not None:
        u = jnp.take_along_axis(u, q_rows[:, None, None], axis=1)
    q = diff_attention.project_q(cfg, p, u)
    if hand.form == "seq":
        a = diff_attention.attend_seq(cfg, q, hand.k, hand.v)
    elif hand.form == "rows":
        a = diff_attention.attend_rows(cfg, q, hand.k, hand.v, hand.last)
    else:
        from ..ops.attention import paged_decode_attention

        pg = hand.paged
        a = paged_decode_attention(
            q, pg.k_pool, pg.v_pool, pg.tables, pg.fills, hand.k, hand.v,
            pg.layer, softmax_scale=diff_attention._scale(cfg))
    return diff_attention.finish(cfg, p, a, layer)


def _full_attend(cfg: ModelConfig, p: Params, u, side: AttnSideInputs,
                 layer, cache, cut_rows):
    """The "full" layer of a stack of runs: attention on its own keys and
    values, all of them.  ``cache``: None (a whole sequence, nothing
    kept), the dense ``(k_cache, v_cache, cache_len)`` or a
    :class:`PagedKV`.  ``cut_rows`` [b] (a prompt into an empty cache
    alone): the keys and values of every row, the query and the output
    of that row only.  -> ``(out, the new rows or None, the hand)``."""
    k, v = diff_attention.project_kv(cfg, p, u)
    if isinstance(cache, PagedKV):
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
    out = _diff_attend(cfg, p, u, layer, hand, cut_rows)
    return out, (None if cache is None else (k, v)), hand


def _window_attend(cfg: ModelConfig, p: Params, u, side: AttnSideInputs,
                   layer, ring, gate_x=None):
    """A "window" layer, of a stack of runs or of the period scan: a
    sequence on itself under the window (``ring`` None: nothing kept;
    True: a prompt, whose ring comes back), or one new position a slot on
    the slot's ring ``(ring_k, ring_v, at)`` (the window layers' rings
    stacked, and which of them), whose new rows come back.  -> ``(out,
    None | the ring | the new rows)``.

    The ring, its install (``diff_attention.ring_of``), its attention
    (``attend_ring``) and the rows' one write (``ring_append_rows``) are
    one mechanism; what differs by stack is read off ``cfg``.  Under
    differential attention: the pairs' projections, a ring row the keys
    of a pair side by side, the pairs' difference (``layer``).  Else:
    grouped heads at the window layers' own head count and rotation
    (``cfg.window_layer_config``), a ring row one key head's, and the gate
    a head (``gate_x``).  A key goes to the ring ROTATED at its own
    position, from the prompt and from a step alike, and a query is
    rotated at its own: the ring's rows need no positions, the count mask
    stands as it is."""
    if cfg.diff_attention:
        w = cfg
        q = diff_attention.project_q(cfg, p, u)
        k, v = diff_attention.project_kv(cfg, p, u)
        as_rows = lambda k_: diff_attention.pair_rows(cfg, k_)  # noqa: E731
        finish = lambda a: diff_attention.finish(  # noqa: E731
            cfg, p, a, layer)
    else:
        w = cfg.window_layer_config
        q, k, v, _gate = _project_heads(w, p, u, side)
        k, v = jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2)   # head-major
        as_rows = lambda k_: k_  # noqa: E731
        finish = lambda a: _project_out(  # noqa: E731
            w, p, a, None, u if gate_x is None else gate_x)
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
    return finish(a), kept


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


def scan_runs_cached(cfg: ModelConfig, stacked, x, side: AttnSideInputs,
                     kv_of=None, rec: Optional[dict] = None,
                     kv_xs: tuple = (), cut_rows=None):
    """A stack of runs (``cfg.layer_runs``), every form of it: each run a
    scan over its periods (a run of one period is written out), one run
    after the other.  Without ``rec``: a whole sequence, every Mamba-1
    mixer from a zero state, nothing kept.  With ``rec``
    (``models/model.py:init_rec_state``): a prompt into an empty cache
    (``side.cache_is_empty``: the Mamba-1 states end at each row's last
    valid position, every "window" layer's ring is what the prompt leaves
    in it) or one new position a slot (the states advance where
    ``side.valid``, the rings are read where they lie and their new rows
    come back).  The "full" layer gets its cache from ``kv_of(kv layer,
    *slices of kv_xs)`` as ``scan_periods_cached`` gives it.

    Handed from run to run: the memory of the last "ssm1" layer before a
    "gmu" layer, and the "full" layer's keys and values (:class:`KVHand`)
    for the "cross" layers.  ``cut_rows`` [b]: from the "full" layer's
    attention on, only that row of each sequence is carried
    (``cfg.row_cut_layer``): its output is [b, 1, h].

    -> ``(hidden, (rows_k, rows_v) of the "full" layers stacked or None,
    the Mamba-1 states {name: array} or None, the "window" layers' (k,
    v) or None: after a prompt their rings ``[window layers, b, ., W,
    .]``, after a step their new ROWS ``[window layers, b, ., 1, .]``
    for the caller's one write)``."""
    x = x.astype(STREAM_DTYPE)
    step = rec is not None and not side.cache_is_empty
    assert not step or x.shape[1] == 1
    assert cut_rows is None or cfg.row_cut_layer is not None
    states = None if rec is None else {
        name: rec[name] for name in mamba1.STATE_NAMES if name in rec}
    kinds = cfg.layer_kinds
    memory_in, hand_in = None, None    # what the runs before hand on
    all_rows, rings = [], []
    layer0 = 0
    for (period, times), trees in zip(cfg.layer_runs, stacked):
        n = len(period)
        later = kinds[layer0 + n * times:]
        # (a run's memory is carried on where a later run gates with it
        # before making its own)
        hands_memory = "ssm1" in period and "gmu" in later and (
            "ssm1" not in later[:later.index("gmu")])
        base = {kind: kinds[:layer0].count(kind)
                for kind in ("ssm1", "window", "full")}
        per = {kind: period.count(kind) for kind in base}

        # (called in this turn of the loop, written out or under the scan:
        # it reads the turn's own variables)
        def body(carry, inp):
            h, idx, states, memory = carry
            period_params, kv_p = inp
            memory = memory_in if memory is None else memory
            hand = hand_in
            rows, kept = [], []
            seen = {kind: 0 for kind in per}
            for j, (kind, p) in enumerate(zip(period, period_params)):
                layer = layer0 + idx * n + j
                at = base.get(kind, 0) + idx * per.get(kind, 0) \
                    + seen.get(kind, 0)
                if kind in seen:
                    seen[kind] += 1
                u = norm_apply(cfg.norm_type, h, p["input_norm"],
                               cfg.norm_eps, impl=cfg.norm_impl)
                if kind == "ssm1":
                    state = None if states is None else mamba1.Mamba1State(
                        *(jax.lax.dynamic_index_in_dim(
                            states[name], at, 0, keepdims=False)
                          for name in mamba1.STATE_NAMES))
                    out, new, memory = mamba1.mamba1_block(
                        cfg, p["mamba1"], u, state, side.valid)
                    if states is not None:
                        with jax.named_scope(
                                "mamba1/" + ("mamba1_step" if step
                                             else "mamba1_scan")):
                            states = {name: jax.lax.
                                      dynamic_update_index_in_dim(
                                          states[name], a, at, 0)
                                      for name, a in zip(
                                          mamba1.STATE_NAMES, new)}
                elif kind == "gmu":
                    out = diff_attention.gmu_block(p["gmu"], u, memory)
                elif kind == "window":
                    with jax.named_scope("swa"):
                        ring = (None if rec is None else True if not step
                                else tuple(rec[name] for name in RING_NAMES)
                                + (at,))
                        out, keep = _window_attend(
                            cfg, p["attn"], u.astype(cfg.dtype), side,
                            layer, ring)
                    if keep is not None:
                        kept.append(keep)
                elif kind == "full":
                    cache = None if kv_of is None else kv_of(
                        at, *(a[seen[kind] - 1] for a in kv_p))
                    with jax.named_scope("attention"):
                        out, new, hand = _full_attend(
                            cfg, p["attn"], u.astype(cfg.dtype), side, layer,
                            cache, cut_rows)
                    if new is not None:
                        rows.append(new)
                    if cut_rows is not None:
                        # the boundary between the decoders: from here
                        # on, one row of each sequence
                        cut = lambda a: jnp.take_along_axis(  # noqa: E731
                            a, cut_rows[:, None, None], axis=1)
                        h = cut(h)
                        memory = None if memory is None else cut(memory)
                else:                                   # "cross"
                    with jax.named_scope("xattn"):
                        out = _diff_attend(cfg, p["attn"],
                                           u.astype(cfg.dtype), layer, hand)
                h = h + out
                u = norm_apply(cfg.norm_type, h, p["post_attn_norm"],
                               cfg.norm_eps, impl=cfg.norm_impl)
                h = h + _mlp_dispatch(cfg, p["mlp"], u)[0]
            stack = lambda xs_: jax.tree.map(  # noqa: E731
                lambda *a: jnp.stack(a), *xs_) if xs_ else ()
            carry = (h, idx + 1, states, memory if hands_memory else None)
            return carry, (stack(rows), stack(kept), hand)

        def by_period(a, kind):
            a = a[base[kind]:base[kind] + times * per[kind]]
            return a.reshape((times, per[kind]) + a.shape[1:])

        xs = (tuple(trees), tuple(by_period(a, "full") for a in kv_xs))
        carry = (x, jnp.int32(0), states, None)
        if times == 1:
            # written out: the period may hand on what a scan could not
            # (the keys and values, a stream cut to one row)
            carry, (rows, kept, hand_in) = body(
                carry, jax.tree.map(lambda a: a[0], xs))
            rows, kept = jax.tree.map(lambda a: a[None], (rows, kept))
        else:
            assert "full" not in period or "cross" not in kinds, (
                "the layer whose keys and values are handed on stands in "
                "a run of one period")
            if hands_memory:
                carry = carry[:3] + (jnp.zeros(
                    x.shape[:2] + (cfg.mamba1_inner,), jnp.float32),)

            def scanned(c, i):
                c, (rows, kept, _hand) = body(c, i)
                return c, (rows, kept)

            carry, (rows, kept) = jax.lax.scan(scanned, carry, xs)
        x, _, states, memory_in = carry
        flat = lambda a: a.reshape(  # noqa: E731
            (a.shape[0] * a.shape[1],) + a.shape[2:])
        if per["full"] and kv_of is not None:
            all_rows.append(jax.tree.map(flat, rows))
        if per["window"] and rec is not None:
            rings.append(jax.tree.map(flat, kept))
        layer0 += n * times
    cat = lambda parts: jax.tree.map(  # noqa: E731
        lambda *a: jnp.concatenate(a), *parts) if parts else None
    return x, cat(all_rows), states, cat(rings)


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
    batched dynamic_update_slice after the scan writes them back — earlier
    designs that threaded updated caches through the scan ys re-stacked
    (copied) the entire cache every decode step, which dominated decode
    latency (3x measured at max_len=256, worse as the window grows).
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
