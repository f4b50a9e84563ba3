"""The plain reference against ``models/`` at tiny widths, both Falcon
block shapes: one LayerNorm and MQA (7B), two LayerNorms and GQA (40B)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import falcon as reference
from megatron_llm_tpu.config import falcon_config
from megatron_llm_tpu.models import model as model_lib

SHAPES = {
    "7b": dict(hidden_size=128, num_attention_heads=2, ffn_hidden_size=512),
    "40b": dict(hidden_size=256, num_attention_heads=4, num_kv_heads=2,
                ffn_hidden_size=512),
}


def tiny(size, dtype):
    cfg = falcon_config(size, num_layers=2, vocab_size=500,
                        params_dtype=dtype, make_vocab_size_divisible_by=4,
                        **SHAPES[size])
    params = model_lib.init_params(jax.random.key(0), cfg)
    # norms away from (1, 0), so that scale and bias are exercised
    noise = iter(jax.random.split(jax.random.key(1), 64))
    params = jax.tree.map(
        lambda a: (a + 0.1 * jax.random.normal(next(noise), a.shape)
                   ).astype(a.dtype), params)
    return cfg, params


@pytest.mark.parametrize("size", ["7b", "40b"])
def test_logprobs_match_the_program_in_float32(size):
    cfg, params = tiny(size, "float32")
    toks = np.random.default_rng(0).integers(0, 500, size=33)
    logits = model_lib.forward(cfg, params, jnp.asarray(toks[None, :-1]))
    lp = np.asarray(jax.nn.log_softmax(logits[0, :, :cfg.vocab_size], -1))
    want = np.take_along_axis(lp, toks[1:, None], 1)[:, 0]
    meta = reference.meta_of(cfg)
    got = np.asarray(reference.token_logprobs(params, toks, meta))
    # float32 against float32: rounding only
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert reference.loss(params, [toks, toks], meta) == pytest.approx(
        -want.mean(), abs=2e-5)


def test_a_lower_precision_is_told_apart():
    """The tolerance logic: bf16 weights stay close to their own float32
    reference, and a model whose weights were rounded to 8 bits does not."""
    cfg, params = tiny("7b", "bfloat16")
    toks = np.random.default_rng(1).integers(0, 500, size=65)
    meta = reference.meta_of(cfg)
    want = np.asarray(reference.token_logprobs(params, toks, meta))

    def program(p):
        logits = model_lib.forward(cfg, p, jnp.asarray(toks[None, :-1]))
        lp = np.asarray(jax.nn.log_softmax(
            logits[0, :, :cfg.vocab_size].astype(jnp.float32), -1))
        return np.take_along_axis(lp, toks[1:, None], 1)[:, 0]

    def to_8_bits(a):
        if a.ndim < 2:
            return a
        scale = jnp.max(jnp.abs(a.astype(jnp.float32))) / 7.0
        return (jnp.round(a.astype(jnp.float32) / scale) * scale
                ).astype(a.dtype)

    near = np.abs(program(params) - want).mean()
    far = np.abs(program(jax.tree.map(to_8_bits, params)) - want).mean()
    assert near < 0.03 < far, (near, far)
