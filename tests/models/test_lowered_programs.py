"""What every preset's served programs lower to: the sha256 of the text
``engine._prefill_impl`` and the decode step lower to on the CPU, at each
preset's tiny test size.  A PR that means to leave a program as it was
leaves its digest; one that changes what a preset lowers to replaces the
digest on purpose and says what in the text differs.  (On the CPU a decode
step takes the gather arm: these cover the prompt path and
``forward_cached_hybrid``; the paged arm's programs are lowered for the
TPU by tests/kernels/test_tpu_compile.py.)"""

import functools
import hashlib

import jax
import jax.numpy as jnp
import pytest

from megatron_llm_tpu.config import (
    falcon_config,
    granite_hybrid_config,
    nemotron_h_config,
    qwen3_next_config,
)
from megatron_llm_tpu.models import model as model_lib
from megatron_llm_tpu.serving import engine as engine_lib

_TINY = dict(vocab_size=512, make_vocab_size_divisible_by=8,
             params_dtype="float32")


def _of(module):
    """The tiny configuration of another test file of this directory."""
    def make():
        import importlib

        return importlib.import_module(f"tests.models.{module}").tiny()
    return make


PRESETS = {
    "falcon": lambda: falcon_config(
        "7b", hidden_size=64, num_layers=2, num_attention_heads=4,
        ffn_hidden_size=128, **_TINY),
    "falcon40b": lambda: falcon_config(
        "40b", hidden_size=64, num_layers=2, num_attention_heads=8,
        num_kv_heads=2, ffn_hidden_size=128, **_TINY),
    "qwen3_next": lambda: qwen3_next_config(
        "80b-a3b-ep2-rank0", num_layers=4, hidden_size=64,
        num_attention_heads=4, num_kv_heads=2, kv_channels=16,
        linear_num_key_heads=2, linear_num_value_heads=4,
        linear_key_head_dim=8, linear_value_head_dim=8, ffn_hidden_size=32,
        num_experts=4, moe_router_experts=8, moe_top_k=2,
        moe_shared_expert_size=32, moe_group_size=64, **_TINY),
    # (the published heads and groups: the state step's tiling is theirs)
    "nemotron_h": lambda: nemotron_h_config(
        "3-super-120b-a12b-ep4-rank0", num_layers=4,
        layer_pattern=("attention", "mlp", "mamba", "mlp"), hidden_size=64,
        num_attention_heads=4, num_kv_heads=2, kv_channels=16,
        ffn_hidden_size=32, moe_shared_expert_size=48, moe_latent_size=32,
        num_experts=4, moe_router_experts=16, moe_top_k=6,
        mamba_num_heads=128, mamba_head_dim=8, mamba_n_groups=8,
        mamba_state_size=16, mamba_chunk_size=8,
        max_position_embeddings=512, moe_group_size=64, **_TINY),
    "granite": lambda: granite_hybrid_config(
        "4.0-h-micro", num_layers=4, layer_pattern=("ssm", "full"),
        hidden_size=64, num_attention_heads=4, num_kv_heads=2,
        kv_channels=16, ffn_hidden_size=96, mamba_num_heads=8,
        mamba_head_dim=8, mamba_n_groups=1, mamba_state_size=16,
        mamba_chunk_size=8, max_position_embeddings=512, **_TINY),
    "kanana": _of("test_mla_stack"),
    "phi4flash": _of("test_phi4flash_stack"),
    "laguna": _of("test_laguna_stack"),
}

# Taken with ``lowered`` on a parent commit, each before the first edit
# of the PR that pinned it.  Both of "falcon": b731d33 (PR 47).
# ("nemotron_h", "decode"): PR 50's, whose one kernel takes a state-space
# layer's whole step between its two projections; both ("qwen3_next",
# ...): PR 51's (the delta rule's states ride in the scan's carry, a
# decode step's DeltaNet layer is one kernel on the stacked states); all
# four of those two presets: PR 53's (a dropless layer's router takes its
# k experts from one kernel's k rounds of max-and-mask).  "falcon40b",
# "granite": af79da7 (PR 51).  "kanana", "phi4flash": 5dab4d3 (PR 57).
# "laguna": ef31f64 (PR 59).  PR 60 folded the period scan and the run
# scan into ``transformer.scan_stack`` and left fifteen of the sixteen;
# ("phi4flash", "decode") is PR 60's (parent: f2c80b6a703c909b): on the
# dense view of the gather route, which no cell runs on the chip, the one
# scan slices a written-out layer's dense cache before the layer's norm
# and writes a step's ring rows before the dense cache's, as the period
# scan always did, where the run scan did both after: the same
# operations on the same operands, two groups of them in another place.
LOWERED = {
    ("falcon", "prefill"): "8cebe19aaf9ad16b",
    ("falcon", "decode"): "ba47a517f99fe833",
    ("falcon40b", "prefill"): "7b996084de3b6922",
    ("falcon40b", "decode"): "c6ede0563478fc50",
    ("qwen3_next", "prefill"): "ca62eb8f3f806c11",
    ("qwen3_next", "decode"): "b182f5e7cd4193b0",
    ("nemotron_h", "prefill"): "a0390066aa4053b5",
    ("nemotron_h", "decode"): "e40cbf71ee017751",
    ("granite", "prefill"): "06ca9e872d67bd6b",
    ("granite", "decode"): "5c662790af38fefc",
    ("kanana", "prefill"): "96123cb1191b441e",
    ("kanana", "decode"): "c3a130d9a47ee2af",
    ("phi4flash", "prefill"): "efefa58cbb0956d8",
    ("phi4flash", "decode"): "2aa8a8ac8dff9262",
    ("laguna", "prefill"): "b4b7cc85c46675a9",
    ("laguna", "decode"): "7c0b879fbd01c823",
}


@functools.lru_cache(maxsize=2)
def _param_shapes(cfg):
    """(a preset's two programs are neighbours and read one tree)"""
    return jax.eval_shape(lambda k: model_lib.init_params(k, cfg),
                          jax.random.key(0))


def lowered(cfg, program, slots=2, blocks=4, bk=16) -> str:
    i32, f32 = jnp.int32, jnp.float32
    params = _param_shapes(cfg)
    if program == "prefill":
        return engine_lib._prefill_impl.lower(
            cfg, params, jax.ShapeDtypeStruct((1, 32), i32),
            jax.ShapeDtypeStruct((1,), i32), max_seq_len=64,
            want_logprobs=False).as_text()
    pool = jax.eval_shape(
        lambda: model_lib.init_kv_pool(cfg, slots * blocks + 1, bk))
    vec = lambda d: jax.ShapeDtypeStruct((slots,), d)  # noqa: E731
    state = {}
    if cfg.layer_pattern:
        state = dict(rec=jax.eval_shape(
            lambda: model_lib.init_rec_state(cfg, slots)), live=vec(bool))
    return engine_lib._decode_plain.lower(
        cfg, params, *pool, jax.ShapeDtypeStruct((slots, blocks), i32),
        vec(i32), vec(i32), vec(jnp.uint32), vec(i32), vec(bool), vec(f32),
        vec(i32), vec(f32), **state).as_text()


# (a preset's two programs are neighbours: one worker, one set of traces)
@pytest.mark.parametrize("preset,program", list(LOWERED))
def test_a_preset_lowers_to_what_it_did(preset, program):
    text = lowered(PRESETS[preset](), program)
    digest = hashlib.sha256(text.encode()).hexdigest()[:16]
    assert digest == LOWERED[preset, program], (preset, program, digest)
