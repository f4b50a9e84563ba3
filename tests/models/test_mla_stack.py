"""Latent attention (MLA) on the normal path, at tiny widths on the CPU:
the two forms of the one layer, the pool of latent rows, the leading
dense layer before the scanned expert layers, what ``validate`` refuses,
and what the new fields leave of the older presets' programs."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import deepseek_v3 as reference
from megatron_llm_tpu.config import deepseek_v3_config
from megatron_llm_tpu.models import mla
from megatron_llm_tpu.models import model as model_lib
from megatron_llm_tpu.models.transformer import AttnSideInputs, PagedKV


TINY = dict(num_layers=3, hidden_size=64, num_attention_heads=4,
            kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, ffn_hidden_size=32, moe_dense_ffn_size=96,
            num_experts=8, moe_top_k=2, moe_shared_expert_size=64,
            vocab_size=500, make_vocab_size_divisible_by=4,
            max_position_embeddings=512, moe_group_size=64,
            params_dtype="float32")


def tiny(**kw):
    return deepseek_v3_config("kanana-2-30b-a3b-pp8-stage0",
                              **{**TINY, **kw})


@pytest.fixture(scope="module")
def model():
    cfg = tiny()
    params = jax.jit(lambda k: model_lib.init_params(k, cfg))(
        jax.random.key(0))
    for tree in (params["lead_layers"], params["layers"][0]):
        for k in ("wq", "wkv_b"):        # a softmax that is not flat
            tree["attn"][k] = 6.0 * tree["attn"][k]
    return cfg, params


@pytest.fixture(scope="module")
def wanted(model):
    """50 tokens and the reference's full forward: every position's
    log-probabilities of the next token."""
    cfg, params = model
    toks = np.asarray(jax.random.randint(jax.random.key(2), (50,), 1, 499))
    want = reference.token_logprobs(params, toks, reference.meta_of(cfg))
    return toks, np.asarray(want)


def picked(logits, toks):
    lp = jax.nn.log_softmax(jnp.asarray(logits)[..., :500], -1)
    return np.take_along_axis(np.asarray(lp), np.asarray(toks)[:, None],
                              1)[:, 0]


def test_the_published_shapes_and_one_tree_for_both_forms(model):
    cfg, params = model
    big = deepseek_v3_config("kanana-2-30b-a3b")
    assert (big.num_layers, big.head_dim, big.latent_row_width) == (
        48, 64, 576)
    shapes = jax.eval_shape(
        lambda k: mla.init_mla_params(k, big, 0.02, 0.02), jax.random.key(0))
    assert {k: v.shape for k, v in shapes.items() if k != "kv_norm"} == {
        "wq": (2048, 32 * 192), "wkv_a": (2048, 576),
        "wkv_b": (512, 32 * 256), "wo": (32 * 128, 2048)}
    assert shapes["kv_norm"]["scale"].shape == (512,)
    # one leading dense layer beside four (here: two) scanned expert layers
    assert jax.tree.leaves(params["lead_layers"])[0].shape[0] == 1
    assert params["lead_layers"]["mlp"]["w_up"].shape == (1, 64, 96)
    assert params["layers"][0]["mlp"]["w_up"].shape == (2, 8, 64, 32)
    assert cfg.moe_layer_ids == (1, 2) and cfg.kv_layers == 3


def test_prefill_then_decode_through_the_pool_against_the_reference(
        model, wanted):
    """The uncached forward, then a 23-token prompt in a 32-wide bucket
    (the expanded form) and 18 steps through a paged pool whose blocks of
    8 lie scattered (the absorbed form, on the gathered view off the
    TPU), against the reference's full forward.  float32 weights: the two
    forms are the same mathematics, so 2e-5 is float32 rounding through
    three layers; a cache that lost or shifted a row would read 1e-1."""
    cfg, params = model
    toks, want = wanted
    full = jax.jit(lambda p, t: model_lib.forward(cfg, p, t))(
        params, jnp.asarray(toks[None, :-1]))
    np.testing.assert_allclose(picked(full[0], toks[1:]), want, atol=2e-5)
    prompt, bk = 23, 8
    k, v = model_lib.init_kv_cache(cfg, 1, 64)
    padded = jnp.zeros((1, 32), jnp.int32).at[0, :prompt].set(toks[:prompt])
    logits, k, v, rec = jax.jit(
        lambda p, t, k, v: model_lib.forward_cached_hybrid(
            cfg, p, t, k, v, jnp.int32(0), model_lib.init_rec_state(cfg, 1),
            valid=jnp.arange(32)[None] < prompt, empty_cache=True))(
                params, padded, k, v)
    np.testing.assert_allclose(picked(logits[0, :prompt], toks[1:prompt + 1]),
                               want[:prompt], atol=2e-5)
    # two slots: slot 0 holds another sequence's 5 rows, slot 1 this one
    kp, vp = model_lib.init_kv_pool(cfg, 20, bk)
    assert (kp.shape, vp.shape) == ((3, 20, 1, bk, 32), (3, 20, 1, bk, 8))
    tables = np.zeros((2, 8), np.int32)
    tables[0, 0], tables[1] = 4, [17, 3, 9, 11, 2, 19, 6, 13]
    kp = model_lib.cache_scatter_blocks(kp, k, tables[1])
    vp = model_lib.cache_scatter_blocks(vp, v, tables[1])
    rec = model_lib.init_rec_state(cfg, 2)
    step = jax.jit(lambda p, t, kp, vp, fills, rec:
                   model_lib.forward_paged_hybrid(
                       cfg, p, t, kp, vp, jnp.asarray(tables), fills, rec,
                       jnp.asarray([True, True])))
    for i in range(prompt, 41):
        l, kp, vp, rec = step(params, jnp.asarray([[7], [toks[i]]]), kp, vp,
                              jnp.asarray([i - prompt + 5 if i < 26 else 7,
                                           i]), rec)
        np.testing.assert_allclose(picked(l[1], toks[i + 1:i + 2]),
                                   want[i:i + 1], atol=2e-5)
    # the experts were counted by layer: none for the dense first layer
    load = np.asarray(rec["load"])
    assert load.shape == (3, 8) and load[0].sum() == 0
    assert (load[1:].sum(axis=1) == 2 * 2 * 18).all()


def test_the_pool_holds_one_row_of_576_a_position_and_nothing_twice():
    cfg = deepseek_v3_config("kanana-2-30b-a3b-pp8-stage0")
    pool = jax.eval_shape(lambda: model_lib.init_kv_pool(cfg, 5281, 128))
    leaves = jax.tree.leaves(pool)
    assert [a.shape for a in leaves] == [(6, 5281, 1, 128, 512),
                                         (6, 5281, 1, 128, 64)]
    assert sum(a.size * a.dtype.itemsize for a in leaves) == \
        6 * 5281 * 128 * 576 * 2
    # 1152 bytes a position a layer; Falcon-7B's MQA keeps 256
    assert sum(a.size * a.dtype.itemsize for a in leaves) \
        // (6 * 5281 * 128) == 1152


def test_absorbed_and_expanded_are_one_layer(model):
    """One input through both forms of one layer's attention part: 12
    cached positions and 5 new ones against the 17 at once."""
    cfg, params = model
    p = jax.tree.map(lambda a: a[1], params["layers"][0]["attn"])
    x = jax.random.normal(jax.random.key(5), (2, 17, 64))
    pos = jnp.broadcast_to(jnp.arange(17)[None], (2, 17))
    block = jax.jit(mla.mla_block, static_argnums=(0, 3))
    whole = block(cfg, p, x, AttnSideInputs())
    cache = (jnp.zeros((2, 1, 24, 32)), jnp.zeros((2, 1, 24, 8)))
    at = lambda lo, hi, **kw: (x[:, lo:hi], pos[:, lo:hi])  # noqa: E731

    @jax.jit
    def cached(x, pos, cache, filled, empty=False):
        return mla.mla_block(
            cfg, p, x, AttnSideInputs(position_ids=pos,
                                      cache_is_empty=empty),
            (*cache, filled))

    _, (c, pe) = jax.jit(lambda x, pos, cache: mla.mla_block(
        cfg, p, x, AttnSideInputs(position_ids=pos, cache_is_empty=True),
        (*cache, jnp.int32(0))))(*at(0, 12), cache)
    cache = (cache[0].at[:, :, :12].set(c), cache[1].at[:, :, :12].set(pe))
    out, (c, pe) = cached(*at(12, 17), cache, jnp.int32(12))
    np.testing.assert_allclose(out, whole[:, 12:], atol=3e-6)
    assert c.shape == (2, 1, 5, 32) and pe.shape == (2, 1, 5, 8)
    # and through the block tables: the kernel, interpreted
    pool = tuple(jnp.zeros((1, 7, 1, 8, w)) for w in (32, 8))
    tables = jnp.asarray([[3, 5, 0], [6, 2, 0]])
    pool = tuple(a.at[0, tables[:, :2].reshape(-1)].set(
        b[:, 0, :16].reshape(4, 8, -1)[:, None])
        for a, b in zip(pool, cache))
    step, _ = jax.jit(lambda x, pos, pool: mla.mla_block(
        cfg, p, x, AttnSideInputs(position_ids=pos),
        PagedKV(*pool, tables, jnp.asarray([12, 12]), jnp.int32(0))))(
            *at(12, 13), pool)
    np.testing.assert_allclose(step, whole[:, 12:13], atol=3e-6)


def test_the_dense_first_layer_runs_first(model, wanted):
    """The same tree without its leading layer is another function: the
    reference, which runs the dense layer first, agrees with the stack as
    it is alone."""
    cfg, params = model
    toks, want = wanted
    t = jnp.asarray(toks[None, :-1])
    forward = jax.jit(model_lib.forward, static_argnums=0)
    full = picked(forward(cfg, params, t)[0], toks[1:])
    np.testing.assert_allclose(full, want, atol=2e-5)
    no_lead = dataclasses.replace(cfg, num_layers=2,
                                  moe_first_dense_layers=0)
    bare = {k: v for k, v in params.items() if k != "lead_layers"}
    without = picked(forward(no_lead, bare, t)[0], toks[1:])
    assert np.abs(without - want).max() > 0.05


@pytest.mark.parametrize("change,said", [
    (dict(q_lora_rank=1536), "q_lora_rank"),
    (dict(rope_scaling_type="yarn", rope_scaling_factor=40.0,
          rope_original_max_positions=4096), "mscale"),
    (dict(moe_n_group=8), "moe_n_group"),
    (dict(kv_cache_quant="int8"), "8-bit"),
])
def test_what_this_row_does_not_have_is_refused_by_name(change, said):
    with pytest.raises(ValueError, match=said):
        tiny(**change)


def test_a_latent_attention_stack_is_not_trained():
    from megatron_llm_tpu.config import RuntimeConfig
    from megatron_llm_tpu.training.driver import setup_train_state

    with pytest.raises(ValueError, match="served, not trained"):
        setup_train_state(RuntimeConfig(model=tiny()))
