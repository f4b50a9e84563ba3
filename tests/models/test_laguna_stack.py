"""The Laguna stack (``config.laguna_config``: a leading dense "full"
layer, then periods of three "window" layers and a "full" one, a head
count and a rotation by kind, a gate a head, sigmoid-routed experts
beside a shared one) at tiny widths, float32, against the plain
reference ``benchmarks/reference/laguna.py``: the whole forward, then a
bucket-padded prefill and 40 decode steps through the cache and the
rings; each omission the reference must catch; and the older presets'
lowered programs, which this PR leaves byte for byte."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import laguna as ref
from megatron_llm_tpu import config as config_lib
from megatron_llm_tpu.config import laguna_config
from megatron_llm_tpu.models import diff_attention, moe
from megatron_llm_tpu.models import model as model_lib
from megatron_llm_tpu.models import transformer
from megatron_llm_tpu.ops import rope

TINY = dict(hidden_size=64, num_attention_heads=4, window_attention_heads=8,
            num_kv_heads=2, kv_channels=16, ffn_hidden_size=32,
            moe_dense_ffn_size=96, moe_shared_expert_size=32, num_experts=8,
            moe_top_k=2, sliding_window=8, vocab_size=512,
            make_vocab_size_divisible_by=8, max_position_embeddings=4096,
            rope_original_max_positions=16, moe_group_size=256,
            params_dtype="float32", num_layers=5)
PROMPT, BUCKET, STEPS = 19, 24, 40     # a prompt past two windows of 8

# float32 on both sides, the same equations in another order of
# operations (grouped heads, a ring, a sorted dispatch of the experts):
# logits of magnitude ~0.6 agree to a few 1e-7; anything this file calls
# an omission moves them by 1e-3 or more
TOL = 5e-6


def tiny(**kw):
    return laguna_config(**{**TINY, **kw})


@pytest.fixture(scope="module")
def model():
    cfg = tiny()
    params = jax.jit(lambda k: model_lib.init_params(k, cfg))(
        jax.random.key(0))
    params = jax.tree_util.tree_map_with_path(_shake, params)
    tokens = jax.random.randint(jax.random.key(1), (1, PROMPT + STEPS + 1),
                                1, 500)
    served_programs(cfg)        # before any test's body, so any patch
    return cfg, params, tokens


def _shake(path, a):
    """Attention loud beside the MLPs, as a trained model's is: at a
    seeded start of 0.02 a softmax is flat and a ring, a rotation or a
    window is worth 1e-6 of a logit, so no omission could show.  The query
    and key projections 12-fold (a softmax that is not flat), the output
    projections 4-fold."""
    name = jax.tree_util.keystr(path)
    if "'attn'" in name and ("'wq'" in name or "'wk'" in name):
        return 12.0 * a
    if "'attn'" in name and "'wo'" in name:
        return 4.0 * a
    return a


def reference_logits(cfg, params, tokens):
    return np.asarray(ref.logits_of(params, np.asarray(tokens[0]),
                                    ref.meta_of(cfg)))


def program_logits(cfg, params, tokens):
    # (jitted anew a call: an omission patched in is traced with it)
    with jax.default_matmul_precision("highest"):
        return np.asarray(jax.jit(
            lambda p, t: model_lib.forward(cfg, p, t))(params, tokens)[0])


def _programs(cfg):
    """The jitted prefill and decode step ``served_logits`` runs, traced
    where they are first called: an omission patched in is served through
    a pair of its own (``programs=_programs``), the faithful model
    through ``served_programs``."""
    @jax.jit
    def prefill(params, padded, k, v, rec):
        valid = jnp.arange(BUCKET)[None, :] < PROMPT
        return model_lib.forward_cached_hybrid(
            cfg, params, padded, k, v, jnp.int32(0), rec, valid=valid,
            empty_cache=True, logit_rows=jnp.array([PROMPT - 1]))

    @jax.jit
    def step(params, token, k, v, t, rec):
        return model_lib.forward_cached_hybrid(
            cfg, params, token, k, v, t, rec, valid=jnp.ones((1, 1), bool))

    return prefill, step


def served_logits(cfg, params, tokens, steps=STEPS, programs=None):
    """A bucket-padded prefill, then ``steps`` decode steps on the dense
    view of the gather route, every step's logits."""
    prefill, step = (programs or served_programs)(cfg)
    with jax.default_matmul_precision("highest"):
        k, v = model_lib.init_kv_cache(cfg, 1, 128)
        rec = model_lib.init_rec_state(cfg, 1)
        padded = jnp.zeros((1, BUCKET), jnp.int32).at[:, :PROMPT].set(
            tokens[:, :PROMPT])
        logits, k, v, rec = prefill(params, padded, k, v, rec)
        out = [np.asarray(logits[0, 0])]
        for t in range(PROMPT, PROMPT + steps):
            logits, k, v, rec = step(params, tokens[:, t:t + 1], k, v,
                                     jnp.array([t]), rec)
            out.append(np.asarray(logits[0, 0]))
    return np.stack(out), rec


@functools.lru_cache(maxsize=None)
def served_programs(cfg):
    """One pair a configuration a process: every test that serves the
    faithful program (here and in test_window_kind.py) runs the same two
    executables.  Both are run once here, on zeros, before the pair is
    handed out, and the module's fixture builds its configuration's pair
    before any test's body runs: a test that patches the model and forgets
    ``programs=_programs`` reads the faithful pair and fails, and cannot
    leave a pair traced under its patch to the tests behind it."""
    pair = _programs(cfg)
    params = jax.tree.map(
        lambda a: jnp.zeros(a.shape, a.dtype),
        jax.eval_shape(lambda: model_lib.init_params(jax.random.key(0), cfg)))
    served_logits(cfg, params, jnp.zeros((1, PROMPT + 1), jnp.int32),
                  steps=1, programs=lambda _cfg: pair)
    return pair


def test_the_preset_is_the_published_stage():
    stage = laguna_config("xs.2-pp8-stage0")
    assert config_lib.get_preset("laguna-xs.2-pp8-stage0") == stage
    assert stage.layer_kinds == ("full", "window", "window", "window",
                                 "full")
    assert (stage.kv_layers, stage.window_layers, stage.moe_layer_ids) == (
        2, 3, (1, 2, 3, 4))
    assert (stage.hidden_size, stage.head_dim, stage.kv_heads,
            stage.ffn_size, stage.v_heads, stage.v_head_width) == (
        2048, 128, 8, 512, 8, 128)
    assert (stage.num_attention_heads, stage.window_attention_heads) == (
        48, 64)
    w = stage.window_layer_config
    assert (w.num_attention_heads, w.rope_theta, w.rotary_percent,
            w.rope_scaling_factor) == (64, 10000.0, 1.0, 1.0)
    assert stage.attn_head_gate and not stage.attn_output_gate
    assert not stage.tie_embed_logits and stage.vocab_size == 100352
    # the published depth ends in a run of three window layers: refused
    # by name until a last partial period is carried
    with pytest.raises(ValueError, match="whole periods"):
        laguna_config("xs.2")
    # a window layer of the period scan validates what it cannot carry
    with pytest.raises(AssertionError, match="sliding_window"):
        tiny(sliding_window=0)
    with pytest.raises(AssertionError, match="rope_rotate_half"):
        tiny(rope_rotate_half=False)
    with pytest.raises(AssertionError, match="whole groups"):
        tiny(window_attention_heads=7)
    with pytest.raises(AssertionError, match="window_attention_heads"):
        tiny(layer_pattern=("full",), num_layers=2)


def test_the_tree_is_a_leading_layer_and_a_period_by_kind(model):
    cfg, params, _ = model
    lead, period = params["lead_layers"], params["layers"]
    assert len(period) == 4
    assert lead["attn"]["wq"].shape == (1, 64, 4 * 16)        # full: 4 heads
    assert lead["attn"]["wg"].shape == (1, 64, 4)
    assert lead["mlp"]["w_up"].shape == (1, 64, 96)           # dense
    for j in range(3):                                        # window: 8
        assert period[j]["attn"]["wq"].shape == (1, 64, 8 * 16)
        assert period[j]["attn"]["wo"].shape == (1, 8 * 16, 64)
        assert period[j]["attn"]["wg"].shape == (1, 64, 8)
        assert period[j]["attn"]["wk"].shape == (1, 64, 2 * 16)
        assert period[j]["mlp"]["w_up"].shape == (1, 8, 64, 32)
    assert period[3]["attn"]["wq"].shape == (1, 64, 4 * 16)
    assert period[3]["attn"]["wg"].dtype == jnp.float32
    assert set(period[3]["mlp"]) >= {"router", "router_bias", "shared"}
    rec = model_lib.init_rec_state(cfg, 3)
    assert rec["win_k"].shape == rec["win_v"].shape == (3, 3, 2, 8, 16)
    assert rec["load"].shape == (5, 8)
    # the pool and the dense cache hold the FULL layers alone
    k, v = model_lib.init_kv_cache(cfg, 1, 32)
    assert k.shape == v.shape == (2, 1, 2, 32, 16)
    k_pool, _ = model_lib.init_kv_pool(cfg, 5, 16)
    assert k_pool.shape == (2, 5, 2, 16, 16)


def test_the_whole_forward_is_the_references(model):
    cfg, params, tokens = model
    want = reference_logits(cfg, params, tokens)
    got = program_logits(cfg, params, tokens)[:, :cfg.vocab_size]
    assert np.abs(want).max() > 0.3
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


def test_a_prefill_then_forty_steps_is_the_references_forward(model):
    """Prefill of 19 positions in a bucket of 24 (window 8: the rings
    wrap at install), then 40 steps through the dense cache of the two
    full layers and the rings of the three window layers (five more
    wraps), every key rotated at its own position once."""
    cfg, params, tokens = model
    want = reference_logits(cfg, params, tokens)[PROMPT - 1:PROMPT + STEPS]
    got, rec = served_logits(cfg, params, tokens)
    np.testing.assert_allclose(got[:, :cfg.vocab_size], want, atol=TOL,
                               rtol=0)
    # the experts' counters: every fed position chose top_k experts in
    # each of the four expert layers, none in the dense one
    assert np.asarray(rec["load"]).sum(axis=1).tolist() == [
        0] + [2 * (PROMPT + STEPS)] * 4


# --- what the reference must catch ----------------------------------------

def _rounded(a):
    return a.astype(jnp.bfloat16).astype(a.dtype)


def _bf16_ring(mp):
    ring_of, append = diff_attention.ring_of, transformer.ring_append_rows
    mp.setattr(diff_attention, "ring_of", lambda *a: _rounded(ring_of(*a)))
    mp.setattr(transformer, "ring_append_rows", lambda rings, rows, pos:
               append(rings, jax.tree.map(_rounded, rows), pos))


def _bf16_gate(mp):
    out = transformer._project_out

    def project_out(cfg, p, ctx, gate, gate_x, lora=None):
        return out(cfg, {**p, "wg": _rounded(p["wg"])}, ctx, gate,
                   _rounded(gate_x), lora)

    mp.setattr(transformer, "_project_out", project_out)


def _window_off_by_one(mp):
    seq, ring = diff_attention.attend_seq, diff_attention.attend_ring
    mp.setattr(diff_attention, "attend_seq", lambda cfg, q, k, v, window=0:
               seq(cfg, q, k, v, window and window + 1))
    # a step that also counts the row the new position will take: the key
    # `window` positions back
    mp.setattr(diff_attention, "attend_ring", lambda cfg, q, rk, rv, layer,
               k, v, pos: ring(cfg, q, rk, rv, layer, k, v, pos + 10 ** 6))


def _ring_row_at_the_wrong_position(mp):
    """A step's key goes to the ring rotated one position late."""
    heads = transformer._project_heads

    def project_heads(cfg, p, x, side, paged=False, lora=None):
        q, k, v, gate = heads(cfg, p, x, side, paged, lora)
        if x.shape[1] == 1 and cfg.num_attention_heads == 8:
            late = dataclasses.replace(
                side, position_ids=side.position_ids + 1)
            k = heads(cfg, p, x, late, paged, lora)[1]
        return q, k, v, gate

    mp.setattr(transformer, "_project_heads", project_heads)


def _full_rotation_on_the_whole_head(mp):
    of = rope.rotation_of
    mp.setattr(transformer, "rotation_of", lambda cfg: (
        (cfg.head_dim,) + of(dataclasses.replace(
            cfg, rotary_percent=1.0))[1:]
        if cfg.rotary_percent < 1.0 else of(cfg)))


def _no_yarn_factor(mp):
    of = rope.rotation_of
    mp.setattr(transformer, "rotation_of",
               lambda cfg: of(cfg)[:2] + (1.0,))


def _window_layers_at_the_full_layers_ratio(mp):
    """Query head j of a window layer reads KV head j // 2 as a full
    layer's would (4 heads on 2 KV heads), not j // 4."""
    heads = transformer._project_heads

    def project_heads(cfg, p, x, side, paged=False, lora=None):
        q, k, v, gate = heads(cfg, p, x, side, paged, lora)
        if cfg.num_attention_heads == 8:
            q = q[:, :, jnp.array([0, 1, 4, 5, 2, 3, 6, 7])]
        return q, k, v, gate

    mp.setattr(transformer, "_project_heads", project_heads)


def _no_gate(mp):
    out = transformer._project_out
    mp.setattr(transformer, "_project_out",
               lambda cfg, p, ctx, gate, gate_x, lora=None: out(
                   dataclasses.replace(cfg, attn_head_gate=False), p, ctx,
                   gate, gate_x, lora))


def _bias_in_the_weights(mp):
    top_k = moe.router_top_k
    mp.setattr(moe, "router_top_k", lambda score, bias, counted, k, **kw:
               top_k(score + bias, None, counted, k, **kw))


def _no_routed_scaling(mp):
    route = moe._route
    mp.setattr(moe, "_route", lambda cfg, *a: route(
        dataclasses.replace(cfg, moe_routed_scaling=1.0), *a))


OMISSIONS = {
    "a ring rounded to bf16": (_bf16_ring, "served"),
    "the gate in bf16": (_bf16_gate, "forward"),
    "a window off by one": (_window_off_by_one, "served"),
    "a ring row rotated at the wrong position": (
        _ring_row_at_the_wrong_position, "served"),
    "the full layers' rotation on all of the head": (
        _full_rotation_on_the_whole_head, "forward"),
    "the full layers' rotation without its factor": (
        _no_yarn_factor, "forward"),
    "the window layers at the full layers' head ratio": (
        _window_layers_at_the_full_layers_ratio, "forward"),
    "the gate left out": (_no_gate, "forward"),
    "the bias used in the weights": (_bias_in_the_weights, "forward"),
    "the scaling left off": (_no_routed_scaling, "forward"),
}


@pytest.mark.parametrize("what", sorted(OMISSIONS))
def test_the_reference_catches(model, monkeypatch, what):
    """Each omission moves the logits past the tolerance the faithful
    program holds: the comparison sees it."""
    cfg, params, tokens = model
    patch, path = OMISSIONS[what]
    patch(monkeypatch)
    moe._dropless.clear_cache()          # (jitted by itself: traced anew)
    try:
        if path == "forward":
            want = reference_logits(cfg, params, tokens)
            got = program_logits(cfg, params, tokens)
        else:
            want = reference_logits(cfg, params, tokens)[
                PROMPT - 1:PROMPT + STEPS]
            got, _rec = served_logits(cfg, params, tokens,
                                      programs=_programs)
    finally:
        monkeypatch.undo()
        moe._dropless.clear_cache()
    assert np.abs(got[:, :cfg.vocab_size] - want).max() > 20 * TOL, what


def test_the_gate_reads_the_float32_stream(model):
    """The gate a head is computed from the layer's normed input as the
    float32 stream has it, by float32 weights: in a bf16 model too."""
    cfg, params, _ = model
    bf16 = dataclasses.replace(cfg, params_dtype="bfloat16")
    p = jax.eval_shape(lambda k: model_lib.init_params(k, bf16),
                       jax.random.key(0))
    assert p["layers"][0]["attn"]["wg"].dtype == jnp.float32
    assert p["layers"][0]["attn"]["wq"].dtype == jnp.bfloat16
    assert p["layers"][0]["mlp"]["router"].dtype == jnp.float32


def test_a_full_layer_rotates_half_the_head_by_yarn():
    """``rotation_of``: the full layers' view gives 64 rotated dimensions
    of 128 with YaRN's frequencies over THOSE and the published factor;
    the window layers' view the whole head, bare."""
    cfg = laguna_config()
    rot, inv_freq, scale = rope.rotation_of(cfg)
    assert rot == 64 and inv_freq.shape == (32,)
    assert scale == pytest.approx(1.4158883083359672)
    want = ref.yarn_inv_freq(64, 500000.0, 64.0, 4096, 64.0, 1.0)
    np.testing.assert_allclose(inv_freq, want, rtol=1e-6)
    # the fastest dimension is extrapolated (as it was), the slowest
    # interpolated (divided by the factor)
    bare = np.asarray(rope.rotary_inv_freq(64, 500000.0))
    assert inv_freq[0] == pytest.approx(bare[0])
    assert inv_freq[-1] == pytest.approx(bare[-1] / 64.0)
    assert rope.rotation_of(cfg.window_layer_config) == (128, None, 1.0)


# --- what the new fields leave as it was ----------------------------------

def test_a_window_field_at_its_default_is_not_in_the_program():
    """A stack without a "window" layer lowers to one text whether the
    new fields are written out at their defaults or left."""
    from tests.models.test_granite_hybrid_stack import tiny as older
    from tests.models.test_lowered_programs import lowered

    base = older()
    assert lowered(dataclasses.replace(
        base, attn_head_gate=False, rope_rotate_half=False,
        lead_layer_kind=None), "decode") == lowered(base, "decode")
