"""Model-family entry points: Llama 1/2, Code Llama, Falcon, GPT.

The reference expresses families as thin subclasses asserting architecture
flags (megatron/model/llama_model.py:22-30, falcon_model.py:18-29,
gpt_model.py); here a family is a ``ModelConfig`` preset (config.py) plus
these constructor/validation helpers.  All families share the same
init/forward (models/model.py).
"""

from __future__ import annotations

import jax

from ..config import (
    ModelConfig,
    PositionEmbeddingType,
    codellama_config,
    falcon_config,
    gpt_config,
    laguna_config,
    llama1_config,
    llama2_config,
    phi4flash_config,
)
from . import model as _model


def validate_llama(cfg: ModelConfig) -> ModelConfig:
    """Reference assertions: megatron/model/llama_model.py:22-30 — rotary
    positions, swiglu, RMSNorm, no bias, untied embeddings."""
    assert cfg.position_embedding_type == PositionEmbeddingType.ROTARY
    assert cfg.activation == "swiglu"
    assert cfg.norm_type == "rmsnorm"
    assert not cfg.use_bias
    assert not cfg.tie_embed_logits
    return cfg


def validate_falcon(cfg: ModelConfig) -> ModelConfig:
    """Reference assertions: megatron/model/falcon_model.py:18-29 — MQA/GQA,
    parallel attention, LayerNorm, rotary."""
    assert cfg.position_embedding_type == PositionEmbeddingType.ROTARY
    assert cfg.parallel_attn
    assert cfg.norm_type == "layernorm"
    return cfg


def validate_gpt(cfg: ModelConfig) -> ModelConfig:
    assert cfg.tie_embed_logits
    return cfg


class CausalLM:
    """Convenience object bundling config + init/apply (stateless)."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    def init(self, key: jax.Array, tp: int = 1):
        return _model.init_params(key, self.cfg, tp)

    def __call__(self, params, tokens, **kw):
        return _model.forward(self.cfg, params, tokens, **kw)

    forward = __call__


def llama(size: str = "7b", version: int = 2, **overrides) -> CausalLM:
    cfg = (llama2_config if version == 2 else llama1_config)(size, **overrides)
    return CausalLM(validate_llama(cfg))


def code_llama(size: str = "34b", **overrides) -> CausalLM:
    return CausalLM(validate_llama(codellama_config(size, **overrides)))


def falcon(size: str = "7b", **overrides) -> CausalLM:
    return CausalLM(validate_falcon(falcon_config(size, **overrides)))


def gpt(size: str = "345m", **overrides) -> CausalLM:
    return CausalLM(validate_gpt(gpt_config(size, **overrides)))


def phi4flash(size: str = "mini-flash-reasoning", **overrides) -> CausalLM:
    """Phi-4-mini-flash-reasoning (``model_type: phi4flash``): a stack of
    runs, served only (docs/serving.md, "A stack of runs"); the engine
    wants ``prefix_cache_blocks=0`` for it as for every hybrid stack."""
    return CausalLM(phi4flash_config(size, **overrides))


def laguna(size: str = "xs.2-pp8-stage0", **overrides) -> CausalLM:
    """Laguna-XS.2 (``model_type: laguna``): window and full attention
    layers in one scanned period, sparse experts; served only
    (docs/serving.md, "The ring under rotation"); the engine wants
    ``prefix_cache_blocks=0`` for it as for every hybrid stack."""
    return CausalLM(laguna_config(size, **overrides))


def draft_model(name: str, target: ModelConfig, **overrides) -> CausalLM:
    """Resolve a resident draft-model config from a preset name
    (config.PRESETS, e.g. ``"tiny"``) for tree speculation against
    ``target`` (serving/engine.py, server CLI ``--draft_model``).

    The draft's vocabulary is forced to the target's — every drafted
    token must be verifiable by the target's argmax — and its position
    range is widened to the target's so draft positions cover any slot
    the engine can decode.  Everything else (depth, width, heads) stays
    the preset's: the whole point is a model small enough that a handful
    of draft forwards cost less than the tokens they save."""
    import dataclasses

    from ..config import get_preset

    cfg = get_preset(name)
    cfg = dataclasses.replace(
        cfg,
        vocab_size=target.vocab_size,
        make_vocab_size_divisible_by=target.make_vocab_size_divisible_by,
        seq_length=max(cfg.seq_length, target.seq_length),
        max_position_embeddings=max(cfg.max_position_embeddings,
                                    target.max_position_embeddings),
        **overrides)
    return CausalLM(cfg.validate())
