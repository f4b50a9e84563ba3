"""Encoder / encoder-decoder models: BERT and T5.

Reference parity (secondary model families, SURVEY §2.3):
- ``BertModel`` (megatron/model/bert_model.py): bidirectional encoder,
  pooler, MLM ``lm_head`` (dense→gelu→LN→tied-embedding logits + bias) and
  the binary (NSP) head; losses = masked-LM CE + sentence-pair CE.
- ``T5Model`` (megatron/model/t5_model.py): shared-embedding encoder/decoder
  with cross-attention, learned absolute positions (Megatron's T5 uses
  absolute embeddings, not T5 relative bias), tied logits + bias.

TPU-first shape: both reuse the scanned decoder blocks of
``models/transformer.py`` — the encoder is the same stack with
``causal=False`` and padding expressed as segment ids; the T5 decoder adds a
cross-attention block between self-attention and MLP, scanned the same way.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..config import ModelConfig
from ..ops.attention import attention
from ..ops.norms import norm_apply, norm_init
from ..parallel.cross_entropy import cross_entropy, masked_mean_loss
from .transformer import (
    AttnSideInputs,
    Params,
    _dropout,
    _normal,
    attention_block,
    init_stack_params,
    layer_forward,
    mlp_block,
    proj,
)


def _pad_segments(pad_mask: jax.Array) -> jax.Array:
    """[b, s] 1/0 pad mask → segment ids where pads live in segment 0 and
    content in segment 1, so content never attends to padding."""
    return pad_mask.astype(jnp.int32)


def _encoder_side(pad_mask: Optional[jax.Array],
                  deterministic: bool) -> AttnSideInputs:
    return AttnSideInputs(
        segment_ids=None if pad_mask is None else _pad_segments(pad_mask),
        deterministic=deterministic,
        causal=False,
    )


def encoder_forward(cfg: ModelConfig, stacked: Params, x: jax.Array,
                    pad_mask: Optional[jax.Array],
                    base_rng=None, deterministic: bool = True) -> jax.Array:
    """Bidirectional stack (no RoPE — BERT/T5 use absolute positions)."""
    side = _encoder_side(pad_mask, deterministic)

    def body(carry, inp):
        h, idx = carry
        layer_params, = inp
        rng = (jax.random.fold_in(base_rng, idx)
               if base_rng is not None else None)
        h, _ = layer_forward(cfg, layer_params, h, side, rng)
        return (h, idx + 1), None

    if cfg.recompute != "none":
        body = jax.checkpoint(body, prevent_cse=False)
    (x, _), _ = jax.lax.scan(body, (x, 0), (stacked,))
    return x


# ---------------------------------------------------------------------------
# BERT  (reference: megatron/model/bert_model.py)
# ---------------------------------------------------------------------------


def init_bert_params(key: jax.Array, cfg: ModelConfig, tp: int = 1) -> Params:
    assert not cfg.parallel_attn, "BERT/T5 use sequential residual blocks"
    assert cfg.num_experts == 0, (
        "MoE is not plumbed through the encoder stacks (the aux "
        "load-balance loss would be silently dropped)")
    h = cfg.hidden_size
    dtype = cfg.dtype
    std = cfg.init_method_std
    v = cfg.padded_vocab_size(tp)
    keys = jax.random.split(key, 8)
    params: Params = {
        "embedding": {
            "word": _normal(keys[0], (v, h), std, dtype),
            "position": _normal(keys[1], (cfg.max_position_embeddings, h),
                                std, dtype),
            "tokentype": _normal(keys[2], (max(cfg.tokentype_size, 2), h),
                                 std, dtype),
        },
        "embed_norm": norm_init(cfg.norm_type, h, dtype),
        "layers": init_stack_params(keys[3], cfg),
        "final_norm": norm_init(cfg.norm_type, h, dtype),
        # MLM transform (BertLMHead: dense → gelu → LN → decoder(tied) + bias)
        "lm_head": {
            "dense": _normal(keys[4], (h, h), std, dtype),
            "dense_bias": jnp.zeros((h,), dtype),
            "norm": norm_init(cfg.norm_type, h, dtype),
            "bias": jnp.zeros((v,), jnp.float32),
        },
        # pooler + binary (NSP) head (bert_model.py pooler/binary_head)
        "pooler": {"w": _normal(keys[5], (h, h), std, dtype),
                   "b": jnp.zeros((h,), dtype)},
        "binary_head": {"w": _normal(keys[6], (h, 2), std, dtype),
                        "b": jnp.zeros((2,), dtype)},
    }
    return params


def bert_encode(cfg: ModelConfig, params: Params, tokens: jax.Array,
                pad_mask: jax.Array,
                tokentype_ids: Optional[jax.Array] = None,
                rng=None, deterministic: bool = True):
    """Shared BERT trunk → (hidden [b,s,h], pooled [CLS] [b,h]).

    Used by both the pretraining heads (bert_forward) and downstream
    classification (tasks/classification.py), so the embed/encode/pool path
    exists exactly once."""
    b, s = tokens.shape
    if tokentype_ids is None:
        tokentype_ids = jnp.zeros((b, s), jnp.int32)
    pos = jnp.arange(s)[None, :]
    x = (params["embedding"]["word"][tokens]
         + params["embedding"]["position"][pos]
         + params["embedding"]["tokentype"][tokentype_ids])
    x = norm_apply(cfg.norm_type, x, params["embed_norm"], cfg.norm_eps,
                   impl=cfg.norm_impl)
    x = encoder_forward(cfg, params["layers"], x, pad_mask, rng,
                        deterministic)
    x = norm_apply(cfg.norm_type, x, params["final_norm"], cfg.norm_eps,
                   impl=cfg.norm_impl)
    pooled = jnp.tanh(x[:, 0] @ params["pooler"]["w"]
                      + params["pooler"]["b"])
    return x, pooled


def bert_forward(cfg: ModelConfig, params: Params, tokens: jax.Array,
                 pad_mask: jax.Array,
                 tokentype_ids: Optional[jax.Array] = None,
                 rng=None, deterministic: bool = True):
    """→ (mlm_logits [b,s,v] fp32, binary_logits [b,2] fp32)."""
    x, pooled = bert_encode(cfg, params, tokens, pad_mask, tokentype_ids,
                            rng, deterministic)

    head = params["lm_head"]
    t = x @ head["dense"] + head["dense_bias"]
    t = jax.nn.gelu(t)
    t = norm_apply(cfg.norm_type, t, head["norm"], cfg.norm_eps,
                   impl=cfg.norm_impl)
    mlm_logits = (t @ params["embedding"]["word"].T).astype(jnp.float32)
    mlm_logits = mlm_logits + head["bias"]

    binary_logits = (pooled @ params["binary_head"]["w"]
                     + params["binary_head"]["b"]).astype(jnp.float32)
    return mlm_logits, binary_logits


def bert_loss(cfg: ModelConfig, params: Params, batch: dict,
              rng=None, deterministic: bool = True, mean=masked_mean_loss):
    """Masked-LM + NSP loss (reference bert_model.py post_language_model_
    processing + pretrain_bert.py forward_step).  Every mean over the batch
    goes through ``mean``: the train step hands a rank's slice of a
    microbatch its own (training/step.py:BatchAxisSum)."""
    mlm_logits, bin_logits = bert_forward(
        cfg, params, batch["tokens"], batch["pad_mask"],
        batch.get("tokentype_ids"), rng, deterministic)
    lm = cross_entropy(mlm_logits, batch["labels"],
                       vocab_size=cfg.vocab_size)
    lm_loss = mean(lm, batch["loss_mask"])
    total = lm_loss
    if "is_random" in batch:
        nsp = cross_entropy(bin_logits[:, None, :],
                            batch["is_random"][:, None], vocab_size=2)
        total = total + mean(nsp, jnp.ones_like(nsp))
    return total


# ---------------------------------------------------------------------------
# T5  (reference: megatron/model/t5_model.py)
# ---------------------------------------------------------------------------


def init_t5_decoder_layer_extras(key: jax.Array, cfg: ModelConfig) -> Params:
    """Cross-attention weights + its pre-norm, stacked per decoder layer."""
    h = cfg.hidden_size
    d = cfg.head_dim
    nq = cfg.num_attention_heads
    nkv = cfg.kv_heads
    dtype = cfg.dtype
    std = cfg.init_method_std
    out_std = (std / (2.0 * cfg.num_layers) ** 0.5
               if cfg.use_scaled_init else std)
    keys = jax.random.split(key, 4)
    return {
        "norm": norm_init(cfg.norm_type, h, dtype),
        "wq": _normal(keys[0], (h, nq * d), std, dtype),
        "wk": _normal(keys[1], (h, nkv * d), std, dtype),
        "wv": _normal(keys[2], (h, nkv * d), std, dtype),
        "wo": _normal(keys[3], (nq * d, h), out_std, dtype),
    }


def num_decoder_layers(cfg: ModelConfig) -> int:
    return cfg.num_decoder_layers or cfg.num_layers


def init_t5_params(key: jax.Array, cfg: ModelConfig, tp: int = 1) -> Params:
    assert not cfg.parallel_attn, "BERT/T5 use sequential residual blocks"
    assert cfg.num_experts == 0, (
        "MoE is not plumbed through the encoder stacks (the aux "
        "load-balance loss would be silently dropped)")
    h = cfg.hidden_size
    dtype = cfg.dtype
    std = cfg.init_method_std
    v = cfg.padded_vocab_size(tp)
    nd = num_decoder_layers(cfg)
    keys = jax.random.split(key, 6)
    cross = jax.vmap(
        lambda k: init_t5_decoder_layer_extras(k, cfg)
    )(jax.random.split(keys[3], nd))
    return {
        "embedding": {
            "word": _normal(keys[0], (v, h), std, dtype),
            "position": _normal(keys[1], (cfg.max_position_embeddings, h),
                                std, dtype),
        },
        "encoder": init_stack_params(keys[2], cfg),
        "decoder": init_stack_params(keys[4], cfg, num_layers=nd),
        "cross": cross,
        "enc_norm": norm_init(cfg.norm_type, h, dtype),
        "dec_norm": norm_init(cfg.norm_type, h, dtype),
        "lm_head_bias": jnp.zeros((v,), jnp.float32),
    }


def cross_attention_block(cfg: ModelConfig, p: Params, x: jax.Array,
                          enc_out: jax.Array,
                          enc_pad_mask: Optional[jax.Array]) -> jax.Array:
    """Decoder queries attend over encoder outputs (t5_model.py decoder
    cross-attention; mask = encoder padding only)."""
    b, s, h = x.shape
    d = cfg.head_dim
    nq = cfg.num_attention_heads
    nkv = cfg.kv_heads
    se = enc_out.shape[1]
    q = proj(cfg, x, p["wq"]).reshape(b, s, nq, d)
    k = proj(cfg, enc_out, p["wk"]).reshape(b, se, nkv, d)
    v = proj(cfg, enc_out, p["wv"]).reshape(b, se, nkv, d)
    bias = None
    if enc_pad_mask is not None:
        bias = jnp.where(enc_pad_mask[:, None, None, :] > 0, 0.0, -jnp.inf
                         ).astype(jnp.float32)
    ctx = attention(q, k, v, impl="dot", causal=False, bias=bias,
                    softmax_scale=1.0 / (d ** 0.5))
    return proj(cfg, ctx.reshape(b, s, nq * d), p["wo"])


def t5_decoder_forward(cfg: ModelConfig, stacked: Params, cross: Params,
                       x: jax.Array, enc_out: jax.Array,
                       dec_pad_mask: Optional[jax.Array],
                       enc_pad_mask: Optional[jax.Array],
                       base_rng=None, deterministic: bool = True):
    side = AttnSideInputs(
        segment_ids=(None if dec_pad_mask is None
                     else _pad_segments(dec_pad_mask)),
        deterministic=deterministic,
        causal=True,
    )

    def body(carry, inp):
        h, idx = carry
        layer_params, cross_params = inp
        rng = (jax.random.fold_in(base_rng, idx)
               if base_rng is not None else None)
        det = deterministic

        def drop(x, salt):
            if rng is None:
                return x
            return _dropout(x, cfg.hidden_dropout,
                            jax.random.fold_in(rng, salt), det)

        # reference ordering (t5_model.py decoder layer): self-attn →
        # cross-attn → MLP, each as a pre-norm residual with hidden dropout.
        h1 = norm_apply(cfg.norm_type, h, layer_params["input_norm"],
                        cfg.norm_eps, impl=cfg.norm_impl)
        h = h + drop(attention_block(cfg, layer_params["attn"], h1, side,
                                     rng), 2)

        c_norm = norm_apply(cfg.norm_type, h, cross_params["norm"],
                            cfg.norm_eps, impl=cfg.norm_impl)
        h = h + drop(cross_attention_block(cfg, cross_params, c_norm,
                                           enc_out, enc_pad_mask), 3)

        m_norm = norm_apply(cfg.norm_type, h,
                            layer_params["post_attn_norm"],
                            cfg.norm_eps, impl=cfg.norm_impl)
        h = h + drop(mlp_block(cfg, layer_params["mlp"], m_norm), 4)
        return (h, idx + 1), None

    if cfg.recompute != "none":
        body = jax.checkpoint(body, prevent_cse=False)
    (x, _), _ = jax.lax.scan(body, (x, 0), (stacked, cross))
    return x


def t5_forward(cfg: ModelConfig, params: Params,
               enc_tokens: jax.Array, dec_tokens: jax.Array,
               enc_pad_mask: Optional[jax.Array] = None,
               dec_pad_mask: Optional[jax.Array] = None,
               rng=None, deterministic: bool = True) -> jax.Array:
    """→ decoder logits [b, s_dec, padded_vocab] fp32."""
    emb = params["embedding"]

    def embed(tokens):
        pos = jnp.arange(tokens.shape[1])[None, :]
        return emb["word"][tokens] + emb["position"][pos]

    enc_rng = dec_rng = None
    if rng is not None:
        enc_rng, dec_rng = jax.random.split(rng)

    enc = encoder_forward(cfg, params["encoder"], embed(enc_tokens),
                          enc_pad_mask, enc_rng, deterministic)
    enc = norm_apply(cfg.norm_type, enc, params["enc_norm"], cfg.norm_eps,
                     impl=cfg.norm_impl)
    dec = t5_decoder_forward(cfg, params["decoder"], params["cross"],
                             embed(dec_tokens), enc, dec_pad_mask,
                             enc_pad_mask, dec_rng, deterministic)
    dec = norm_apply(cfg.norm_type, dec, params["dec_norm"], cfg.norm_eps,
                     impl=cfg.norm_impl)
    logits = (dec @ emb["word"].T).astype(jnp.float32)
    return logits + params["lm_head_bias"]


def t5_loss(cfg: ModelConfig, params: Params, batch: dict,
            rng=None, deterministic: bool = True, mean=masked_mean_loss):
    logits = t5_forward(cfg, params, batch["enc_tokens"],
                        batch["dec_tokens"], batch.get("enc_pad_mask"),
                        batch.get("dec_pad_mask"), rng, deterministic)
    per_tok = cross_entropy(logits, batch["labels"],
                            vocab_size=cfg.vocab_size)
    return mean(per_tok, batch["loss_mask"])


# ---------------------------------------------------------------------------
# Tensor-parallel PartitionSpecs (full-stack parallelism for the secondary
# families — the reference trains BERT/T5 through the same TP machinery as
# GPT, megatron/core/parallel_state.py + pretrain_bert.py/pretrain_t5.py).
#
# Encoder/decoder SPLIT-RANK pipeline parallelism
# (parallel_state.py:110-112,177-184 — pipeline stages partitioned between
# the two stacks) lives in parallel/pipeline_encdec.py: the encoder output
# rides the ppermute ring into every decoder stage's cross-attention, and
# BERT runs the same ring encoder-only.
# ---------------------------------------------------------------------------




def bert_param_specs(cfg: ModelConfig, parallel) -> Params:
    """Specs matching ``init_bert_params``: vocab-parallel embedding +
    Column/Row-parallel encoder stack; the small heads (MLM dense, pooler,
    NSP) stay replicated as in the reference (bert_model.py uses plain
    ``get_linear_layer`` for them)."""
    from jax.sharding import PartitionSpec as P

    from .sharding import _layer_specs, norm_specs

    return {
        "embedding": {
            "word": P("tp", None),
            "position": P(None, None),
            "tokentype": P(None, None),
        },
        "embed_norm": norm_specs(cfg),
        "layers": _layer_specs(cfg, None, parallel.tensor_parallel),
        "final_norm": norm_specs(cfg),
        "lm_head": {
            "dense": P(None, None),
            "dense_bias": P(None),
            "norm": norm_specs(cfg),
            "bias": P("tp"),  # matches the vocab-sharded tied logits
        },
        "pooler": {"w": P(None, None), "b": P(None)},
        "binary_head": {"w": P(None, None), "b": P(None)},
    }


def t5_param_specs(cfg: ModelConfig, parallel) -> Params:
    """Specs matching ``init_t5_params``: both stacks Column/Row-parallel,
    cross-attention sharded like self-attention (q/k/v column, output row)."""
    from jax.sharding import PartitionSpec as P

    from .sharding import _layer_specs, kv_shard_axes, norm_specs

    kv_tp = kv_shard_axes(cfg, parallel.tensor_parallel)
    return {
        "embedding": {
            "word": P("tp", None),
            "position": P(None, None),
        },
        "encoder": _layer_specs(cfg, None, parallel.tensor_parallel),
        "decoder": _layer_specs(cfg, None, parallel.tensor_parallel),
        "cross": {
            "norm": norm_specs(cfg),  # [nd, h] leaves; unsharded
            "wq": P(None, None, "tp"),
            "wk": P(None, None, kv_tp),
            "wv": P(None, None, kv_tp),
            "wo": P(None, "tp", None),
        },
        "enc_norm": norm_specs(cfg),
        "dec_norm": norm_specs(cfg),
        "lm_head_bias": P("tp"),
    }
