"""Granite 4.0-H's own mechanisms at tiny widths, float32: the two-part
block whose mixer is Mamba-2, the chunked form at the published chunk of
256 against the recurrence, the four multipliers' defaults (which leave
every other preset's lowered program as it was), and the preset's sizes."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_llm_tpu import config as config_lib
from megatron_llm_tpu.config import granite_hybrid_config, tiny_config
from megatron_llm_tpu.models import mamba2
from megatron_llm_tpu.models import model as model_lib
from megatron_llm_tpu.models import transformer

TINY = dict(num_layers=4, layer_pattern=("ssm", "full"), hidden_size=64,
            num_attention_heads=4, num_kv_heads=2, kv_channels=16,
            ffn_hidden_size=96, vocab_size=512, mamba_num_heads=8,
            mamba_head_dim=8, mamba_n_groups=1, mamba_state_size=16,
            mamba_chunk_size=8, params_dtype="float32",
            max_position_embeddings=1024, make_vocab_size_divisible_by=8)


def tiny(**kw):
    return granite_hybrid_config("4.0-h-micro", **{**TINY, **kw})


@pytest.fixture(scope="module")
def model():
    cfg = tiny()
    return cfg, jax.jit(lambda k: model_lib.init_params(k, cfg))(
        jax.random.key(0))


def test_the_preset_is_the_published_model():
    full = granite_hybrid_config("4.0-h-micro")
    assert config_lib.get_preset("granite-4.0-h-micro") == full
    assert (full.num_layers, full.hidden_size, full.head_dim,
            full.kv_heads, full.ffn_size) == (40, 2048, 64, 8, 8192)
    assert full.layer_pattern == ("ssm",) * 5 + ("full",) + ("ssm",) * 4
    assert [i for i, k in enumerate(full.layer_kinds) if k == "full"] == [
        5, 15, 25, 35]
    assert (full.kv_layers, full.mamba_layers, full.linear_layers,
            full.moe_layer_ids) == (4, 36, 0, ())
    assert (full.mamba_inner, full.mamba_conv_channels,
            full.mamba_chunk_size) == (4096, 4352, 256)
    assert (full.embedding_multiplier, full.residual_multiplier,
            full.attention_multiplier, full.logits_scaling) == (
        12.0, 0.22, 1 / 64, 8.0)
    assert full.tie_embed_logits and full.vocab_size == 100352
    # 40 published layers are four periods of ten, and nothing else is
    with pytest.raises(AssertionError, match="whole periods"):
        granite_hybrid_config("4.0-h-micro", num_layers=36)
    assert granite_hybrid_config("4.0-h-micro", num_layers=10).mamba_layers \
        == 9
    # every other preset keeps the defaults, which change nothing
    for name in config_lib.PRESETS:
        if name != "granite-4.0-h-micro":
            c = config_lib.get_preset(name)
            assert (c.embedding_multiplier, c.residual_multiplier,
                    c.attention_multiplier, c.logits_scaling) == (
                1.0, 1.0, None, 1.0), name
    # the fused training head computes its logits itself
    with pytest.raises(AssertionError, match="logits_scaling"):
        tiny_config(logits_scaling=2.0, fused_lm_head=True)


def test_a_two_part_block_holds_a_mixer_and_the_mlp(model):
    cfg, params = model
    ssm, full = params["layers"]
    assert sorted(ssm) == ["input_norm", "mamba", "mlp", "post_attn_norm"]
    assert sorted(full) == ["attn", "input_norm", "mlp", "post_attn_norm"]
    assert sorted(ssm["mlp"]) == ["w_down", "w_gate", "w_up"]
    assert ssm["mamba"]["w_in"].shape == (2, 64, 64 + 96 + 8)
    assert "lm_head" not in params
    assert (config_lib.KINDS["ssm"], config_lib.KINDS["full"]) == (
        config_lib.BlockKind("mamba"), config_lib.BlockKind("kv"))
    rec = model_lib.init_rec_state(cfg, 3)
    # two periods: the tail is kept flat, whole tiles of (slots, lanes);
    # one period keeps its three rows apart
    assert rec["ssm"].shape == (2, 3, 8, 8, 16)
    assert rec["ssm_conv"].shape == (2, 3, 3 * 96)
    assert model_lib.init_rec_state(tiny(num_layers=2), 3)[
        "ssm_conv"].shape == (1, 3, 3, 96)
    # one layer, by hand: x + r mixer(norm x), then x + r mlp(norm x)
    p = jax.tree.map(lambda a: a[0], ssm)
    x = jax.random.normal(jax.random.key(1), (2, 24, 64))
    got, _ = transformer.layer_forward(cfg, p, x, transformer.AttnSideInputs(),
                                       kind="ssm")
    rms = lambda v, w: w * v * jax.lax.rsqrt(  # noqa: E731
        jnp.mean(v * v, -1, keepdims=True) + cfg.norm_eps)
    r = cfg.residual_multiplier
    mid = x + r * mamba2.mamba_block(
        cfg, p["mamba"], rms(x, p["input_norm"]["scale"]))[0]
    want = mid + r * transformer.mlp_block(
        cfg, p["mlp"], rms(mid, p["post_attn_norm"]["scale"]))
    np.testing.assert_allclose(got, want, atol=1e-5)
    assert r == 0.22 and float(jnp.abs(got - x).max()) > 1e-3


def recurrence(x, B, C, dt, A):
    """``S <- a S + (dt x) (x) B;  y = S C`` a position at a time, float64,
    from a zero state.  ``x`` [s, H, P], ``B C`` [s, G, N]."""
    x, B, C, dt, A = (np.asarray(a, np.float64) for a in (x, B, C, dt, A))
    s, H, P = x.shape
    per = H // B.shape[1]
    S, y = np.zeros((H, P, B.shape[2])), np.zeros(x.shape)
    for t in range(s):
        for h in range(H):
            S[h] = np.exp(dt[t, h] * A[h]) * S[h] + np.outer(
                dt[t, h] * x[t, h], B[t, h // per])
            y[t, h] = S[h] @ C[t, h // per]
    return y, S


@pytest.mark.parametrize("s", [256, 768])
def test_the_chunked_form_at_the_published_chunk_is_the_recurrence(s):
    """One group of heads, chunks of 256 positions: one chunk, and three
    whose states are handed on."""
    H, P, N = 4, 8, 16
    ks = jax.random.split(jax.random.key(s), 5)
    x = jax.random.normal(ks[0], (1, s, H, P))
    B = jax.random.normal(ks[1], (1, s, 1, N))
    C = jax.random.normal(ks[2], (1, s, 1, N))
    dt = 0.1 * jax.nn.softplus(jax.random.normal(ks[3], (1, s, H)))
    A = -jnp.exp(jax.random.uniform(ks[4], (H,), minval=0.0, maxval=2.5))
    y, S = jax.jit(mamba2.ssd_chunked, static_argnums=6)(
        x, B, C, dt, A, jnp.zeros((1, H, P, N)), 256)
    want_y, want_S = recurrence(x[0], B[0], C[0], dt[0], A)
    np.testing.assert_allclose(y[0], want_y, atol=2e-4, rtol=1e-4)
    np.testing.assert_allclose(S[0], want_S, atol=2e-4, rtol=1e-4)


def test_a_padded_prefill_then_steps_is_the_whole_sequence(model):
    """A prompt in a padded bucket, then one position a step through the
    carried K/V and states, against one forward pass over it all."""
    cfg, params = model
    toks = jax.random.randint(jax.random.key(2), (1, 50), 1, 500)
    want = jax.jit(lambda t: model_lib.forward(cfg, params, t))(toks)[0]
    k, v = model_lib.init_kv_cache(cfg, 1, 64)
    prompt, bucket = 37, 48
    padded = jnp.pad(toks[:, :prompt], ((0, 0), (0, bucket - prompt)))
    valid = jnp.arange(bucket)[None] < prompt
    logits, k, v, rec = jax.jit(
        lambda t, k, v, valid: model_lib.forward_cached_hybrid(
            cfg, params, t, k, v, jnp.int32(0),
            model_lib.init_rec_state(cfg, 1), valid=valid,
            empty_cache=True))(padded, k, v, valid)
    np.testing.assert_allclose(logits[0, :prompt], want[:prompt], atol=2e-5)
    # one executable for the thirteen steps (op by op, each step traced
    # and compiled its scan again)
    step = jax.jit(lambda t, k, v, n, rec: model_lib.forward_cached_hybrid(
        cfg, params, t, k, v, n, rec))
    for i in range(prompt, 50):
        l, k, v, rec = step(toks[:, i:i + 1], k, v,
                            jnp.full((1,), i, jnp.int32), rec)
        np.testing.assert_allclose(l[0, 0], want[i], atol=2e-5)


# --- what the new fields leave as it was --------------------------------

def test_a_multiplier_at_its_default_is_not_in_the_program():
    """The same tiny stack with the four at their defaults and with each
    written out at the value that changes nothing lowers to one text; away
    from it the text differs."""
    base = tiny(embedding_multiplier=1.0, residual_multiplier=1.0,
                attention_multiplier=None, logits_scaling=1.0)
    from tests.models.test_lowered_programs import lowered

    text = lowered(base, "decode")
    assert lowered(dataclasses.replace(
        base, attention_multiplier=16 ** -0.5), "decode") == text
    for change in (dict(embedding_multiplier=2.0),
                   dict(residual_multiplier=0.5),
                   dict(attention_multiplier=0.1),
                   dict(logits_scaling=2.0)):
        assert lowered(dataclasses.replace(base, **change),
                       "decode") != text, change
