"""Nemotron-H's own mechanisms at tiny widths, float32: the one-part
blocks, the two forms of the Mamba-2 recurrence, a padded prefill, the
sigmoid router with its selection bias, the experts in their latent, the
un-gated experts through the grouped kernel, and the preset's sizes."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_llm_tpu.config import (NEMOTRON_3_SUPER_PATTERN,
                                     nemotron_h_config)
from megatron_llm_tpu.kernels.grouped_matmul import grouped_mlp
from megatron_llm_tpu.models import mamba2, moe
from megatron_llm_tpu.models import model as model_lib
from megatron_llm_tpu.ops.activations import squared_relu

TINY = dict(num_layers=4, layer_pattern=("attention", "mlp", "mamba", "mlp"),
            hidden_size=64, num_attention_heads=4, num_kv_heads=2,
            kv_channels=16, ffn_hidden_size=32, moe_shared_expert_size=48,
            moe_latent_size=32, num_experts=4, moe_router_experts=16,
            moe_top_k=6, vocab_size=512, mamba_num_heads=4,
            mamba_head_dim=8, mamba_n_groups=2, mamba_state_size=16,
            mamba_chunk_size=8, params_dtype="float32",
            max_position_embeddings=1024, make_vocab_size_divisible_by=8,
            moe_group_size=64)


def tiny(**kw):
    return nemotron_h_config("3-super-120b-a12b-ep4-rank0", **{**TINY, **kw})


@pytest.fixture(scope="module")
def model():
    cfg = tiny()
    return cfg, jax.jit(lambda k: model_lib.init_params(k, cfg))(
        jax.random.key(0))


def test_the_preset_is_the_published_model_and_its_share():
    full = nemotron_h_config("3-super-120b-a12b")
    assert (full.num_layers, full.hidden_size, full.head_dim) == (88, 4096,
                                                                   128)
    assert len(NEMOTRON_3_SUPER_PATTERN) == 88
    assert (full.kv_layers, full.mamba_layers, len(full.moe_layer_ids),
            full.linear_layers) == (8, 40, 40, 0)
    assert (full.num_experts, full.router_experts, full.moe_top_k,
            full.moe_latent_size, full.ffn_size) == (512, 512, 22, 1024, 2688)
    assert (full.mamba_inner, full.mamba_conv_channels) == (8192, 10240)
    # the pattern is not periodic: it is one period of 88, and nothing else
    # is whole periods of it
    with pytest.raises(AssertionError, match="whole periods"):
        nemotron_h_config("3-super-120b-a12b", num_layers=44)
    share = nemotron_h_config("3-super-120b-a12b-ep4-rank0", num_layers=11)
    assert "".join({"attention": "*", "mlp": "E", "mamba": "M"}[k]
                   for k in share.layer_pattern) == "*EMEMEMEMEM" \
        == NEMOTRON_3_SUPER_PATTERN[25:36] == NEMOTRON_3_SUPER_PATTERN[36:47]
    assert (share.num_experts, share.router_experts, share.moe_expert_offset,
            share.vocab_size) == (128, 512, 0, 32768)
    assert (share.kv_layers, share.mamba_layers, share.moe_layer_ids) == (
        1, 5, (1, 3, 5, 7, 9))
    with pytest.raises(AssertionError, match="unknown block kind"):
        tiny(layer_pattern=("mamba", "conv"))


def test_a_one_part_block_holds_one_part_under_one_norm(model):
    cfg, params = model
    kinds = [sorted(p) for p in params["layers"]]
    assert kinds == [["attn", "input_norm"], ["input_norm", "mlp"],
                     ["input_norm", "mamba"], ["input_norm", "mlp"]]
    mlp = params["layers"][1]["mlp"]
    # two matrices an expert, in the latent; no gate anywhere
    assert sorted(mlp) == ["latent_down", "latent_up", "router",
                           "router_bias", "shared", "w_down", "w_up"]
    assert mlp["w_up"].shape == (1, 4, 32, 32)
    assert sorted(mlp["shared"]) == ["w_down", "w_up"]
    assert float(jnp.abs(mlp["router_bias"]).min()) > 0
    assert "position" not in params["embedding"]       # no position at all


@pytest.mark.parametrize("s,lengths", [(8, (8, 8)), (21, (21, 13)),
                                       (40, (33, 40))])
def test_the_chunked_form_is_the_recurrence_across_chunk_edges(s, lengths):
    """8 positions a chunk: one whole chunk, two and a ragged third, five;
    a row's padded tail (``valid`` false) advances neither the state nor
    the convolution's tail."""
    cfg = tiny()
    p = mamba2.init_mamba_params(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(s), (2, s, cfg.hidden_size))
    valid = jnp.arange(s)[None, :] < jnp.asarray(lengths)[:, None]
    start = mamba2.MambaState(*(
        0.3 * jax.random.normal(jax.random.key(7 + i), a.shape)
        for i, a in enumerate(mamba2.init_state(cfg, 2)[:2])))
    block = jax.jit(mamba2.mamba_block, static_argnums=0)
    out, end = block(cfg, p, x, start, valid)
    state, outs = start, []
    for t in range(s):
        o, state = block(cfg, p, x[:, t:t + 1], state, valid[:, t:t + 1])
        outs.append(o)
    want = jnp.concatenate(outs, axis=1)
    for row, n in enumerate(lengths):
        np.testing.assert_allclose(out[row, :n], want[row, :n], atol=2e-6)
    np.testing.assert_allclose(end.S, state.S, atol=2e-6)
    np.testing.assert_allclose(end.conv, state.conv, atol=1e-6)
    # the state did move, and past a row's length it did not
    assert float(jnp.abs(end.S - start.S).max()) > 1e-3
    if lengths[1] < s:
        cut, _ = block(cfg, p, x[1:, :lengths[1]], jax.tree.map(
            lambda a: a[1:], start), None)
        np.testing.assert_allclose(out[1, :lengths[1]], cut[0], atol=2e-6)


def test_a_prompt_takes_the_chunked_form_and_a_step_the_recurrence():
    cfg = tiny()
    p = mamba2.init_mamba_params(jax.random.key(0), cfg)
    for s, chunked in ((2, True), (19, True), (1, False)):
        x = jax.ShapeDtypeStruct((1, s, cfg.hidden_size), jnp.float32)
        text = str(jax.make_jaxpr(
            lambda p, x: mamba2.mamba_block(cfg, p, x))(p, x))
        # the one loop of the chunked form hands the state from chunk to
        # chunk; a step is the kernel
        assert ("mamba_step" in text) != chunked, s
        assert not chunked or text.count("scan[") == 1, s


def test_a_padded_prefill_is_the_unpadded_one(model):
    cfg, params = model
    n, width = 45, 64
    toks = jax.random.randint(jax.random.key(2), (1, width), 1, 500)

    @jax.jit
    def prefill(tokens, valid):
        k, v = model_lib.init_kv_cache(cfg, 1, 128)
        return model_lib.forward_cached_hybrid(
            cfg, params, tokens, k, v, jnp.int32(0),
            model_lib.init_rec_state(cfg, 1), valid=valid, empty_cache=True)

    exact = prefill(toks[:, :n], None)
    padded = prefill(toks, (jnp.arange(width) < n)[None])
    np.testing.assert_allclose(padded[0][:, :n], exact[0], atol=2e-5)
    assert sorted(exact[3]) == ["load", "rows", "ssm", "ssm_conv"]
    for key in ("ssm", "ssm_conv"):
        np.testing.assert_allclose(padded[3][key], exact[3][key], atol=2e-5)
    # only the layers that route count choices
    np.testing.assert_array_equal(padded[3]["load"], exact[3]["load"])
    per_layer = np.asarray(exact[3]["load"]).sum(axis=1)
    assert per_layer.tolist() == [0, n * cfg.moe_top_k, 0,
                                  n * cfg.moe_top_k]
    # (every position marked: the padded call's executable)
    through = prefill(toks, jnp.ones((1, width), bool))
    assert float(jnp.abs(through[3]["ssm"] - exact[3]["ssm"]).max()) > 1e-4


def test_decode_continues_the_prefill_and_skips_dead_rows(model):
    cfg, params = model
    toks = jax.random.randint(jax.random.key(3), (2, 34), 1, 500)
    want = jax.jit(lambda t: model_lib.forward(cfg, params, t))(toks)
    k, v = model_lib.init_kv_cache(cfg, 2, 64)
    _, k, v, rec = jax.jit(lambda t, k, v: model_lib.forward_cached_hybrid(
        cfg, params, t, k, v, jnp.int32(0),
        model_lib.init_rec_state(cfg, 2), empty_cache=True))(
            toks[:, :30], k, v)
    live = jnp.asarray([[True], [False]])
    step = jax.jit(lambda t, k, v, n, rec: model_lib.forward_cached_hybrid(
        cfg, params, t, k, v, n, rec, valid=live))
    for i in range(30, 34):
        before = rec
        logits, k, v, rec = step(toks[:, i:i + 1], k, v,
                                 jnp.full((2,), i, jnp.int32), rec)
        np.testing.assert_allclose(logits[0, 0], want[0, i], atol=2e-5)
        for key in ("ssm", "ssm_conv"):   # the row that is not live keeps
            np.testing.assert_array_equal(rec[key][:, 1], before[key][:, 1])
            assert float(jnp.abs(rec[key][:, 0]
                                 - before[key][:, 0]).max()) > 0


def test_a_stack_may_keep_both_kinds_of_recurrent_state():
    """A delta-rule layer and a state-space layer in one period: each
    kind's state under its own names, both advanced by a step."""
    cfg = tiny(layer_pattern=("mamba", "linear", "attention", "mlp"),
               linear_num_key_heads=2, linear_num_value_heads=4,
               linear_key_head_dim=16, linear_value_head_dim=16)
    params = jax.jit(lambda k: model_lib.init_params(k, cfg))(
        jax.random.key(1))
    rec = model_lib.init_rec_state(cfg, 2)
    assert sorted(model_lib.rec_states(rec)) == ["S", "conv", "ssm",
                                                 "ssm_conv"]
    toks = jax.random.randint(jax.random.key(3), (2, 20), 1, 500)
    # (one executable a program: op by op, each of a scan's hundreds of
    # operations is compiled and kept by itself)
    want = jax.jit(lambda t: model_lib.forward(cfg, params, t))(toks)
    k, v = model_lib.init_kv_cache(cfg, 2, 32)
    logits, k, v, rec = jax.jit(
        lambda t, k, v, rec: model_lib.forward_cached_hybrid(
            cfg, params, t, k, v, jnp.int32(0), rec, empty_cache=True))(
                toks[:, :19], k, v, rec)
    np.testing.assert_allclose(logits, want[:, :19], atol=2e-5)
    last, *_ = jax.jit(
        lambda t, k, v, n, rec: model_lib.forward_cached_hybrid(
            cfg, params, t, k, v, n, rec))(
                toks[:, 19:], k, v, jnp.full((2,), 19, jnp.int32), rec)
    np.testing.assert_allclose(last[:, 0], want[:, 19], atol=2e-5)


def test_the_bias_a_seed_brings_levels_the_stacks_own_load():
    """``init_params`` sets each feed-forward block's selection bias
    against the load this random stack really has: over tokens it has not
    seen, the busiest of 64 experts draws about 1.5 times the mean (what
    512 tokens' chance leaves), where the bias as drawn leaves 7 to 10
    times: every token's stream shares a direction, and it lifts the same
    experts for all of them."""
    cfg = tiny(num_experts=16, moe_router_experts=64)
    params = jax.jit(lambda k: model_lib.init_params(k, cfg))(
        jax.random.key(0))
    toks = jax.random.randint(jax.random.key(99), (1, 512), 1, 500)

    @jax.jit
    def load_of(p):
        k, v = model_lib.init_kv_cache(cfg, 1, 512)
        return model_lib.forward_cached_hybrid(
            cfg, p, toks, k, v, jnp.int32(0),
            model_lib.init_rec_state(cfg, 1), empty_cache=True)[3]["load"]

    def busiest_over_mean(p):
        load = np.asarray(load_of(p))
        return (load[[1, 3]].max(axis=1) / load[[1, 3]].mean(axis=1)).max()

    drawn = [dict(layer) for layer in params["layers"]]
    for j in (1, 3):
        bias = drawn[j]["mlp"]["router_bias"]
        drawn[j]["mlp"] = {**drawn[j]["mlp"], "router_bias":
                           0.05 * jax.random.normal(jax.random.key(j),
                                                    bias.shape)}
    assert busiest_over_mean(params) < 1.8
    assert busiest_over_mean({**params, "layers": drawn}) > 4.0


def test_the_bias_moves_the_choice_and_never_the_weight(model):
    """With a bias that lifts expert 2 over every other, every token
    chooses it; its weight is still its own score over the chosen scores'
    sum, times the scaling factor: the layer's output is the same as with
    a bias a hundred times as large."""
    cfg, params = model
    p = jax.tree.map(lambda a: a[0], params["layers"][1]["mlp"])
    # (at their initial 0.02 the routed experts add a thousandth of what
    # the shared one does: made large enough to be seen)
    p = {**p, **{k: 8.0 * p[k] for k in ("w_up", "w_down", "latent_down",
                                           "latent_up")}}
    x = jax.random.normal(jax.random.key(4), (1, 40, cfg.hidden_size))
    run = jax.jit(moe.moe_dropless_block, static_argnums=0)
    plain, stats = run(cfg, p, x)
    assert float(stats["load"][2]) < 40          # not everybody's choice
    lifted = {**p, "router_bias": p["router_bias"].at[2].set(10.0)}
    out, stats = run(cfg, lifted, x)
    assert float(stats["load"][2]) == 40
    assert float(jnp.abs(out - plain).max()) > 0.05 * float(
        jnp.abs(plain).max())
    higher, _ = run(cfg, {**p, "router_bias":
                          p["router_bias"].at[2].set(1000.0)}, x)
    np.testing.assert_array_equal(higher, out)
    # by hand, token 0: the 6 largest of sigmoid + bias, weights from the
    # sigmoid alone, experts 0-3 held, in the latent, then the shared one
    h = x[0, 0]
    score = jax.nn.sigmoid(h @ p["router"])
    chosen = np.argsort(-np.asarray(score + lifted["router_bias"]))[:6]
    weight = score[chosen] / score[chosen].sum() * cfg.moe_routed_scaling
    u = h @ p["latent_down"]
    r = sum(w * (squared_relu(u @ p["w_up"][e]) @ p["w_down"][e])
            for e, w in zip(chosen, weight) if e < 4)
    want = r @ p["latent_up"] + squared_relu(
        h @ p["shared"]["w_up"]) @ p["shared"]["w_down"]
    np.testing.assert_allclose(out[0, 0], want, rtol=1e-4, atol=1e-6)
    assert cfg.moe_routed_scaling == 5.0 and 2 in chosen


@pytest.mark.parametrize("dtype,tol", [("float32", 1e-6), ("bfloat16", 3e-2)])
def test_the_grouped_kernel_takes_experts_of_two_matrices(dtype, tol):
    """``grouped_mlp`` with ``w_gate=None`` (never run before this model):
    every pair's row is ``act(x W1_e) W2_e`` of its expert; rows of pairs
    in no group are not written."""
    g, k, h, f, E = 24, 4, 32, 16, 5
    ks = jax.random.split(jax.random.key(9), 4)
    x = jax.random.normal(ks[0], (g, h))
    w_up = jax.random.normal(ks[1], (E, h, f)).astype(dtype) * 0.2
    w_down = jax.random.normal(ks[2], (E, f, h)).astype(dtype) * 0.2
    expert = jax.random.randint(ks[3], (g, k), 0, E + 2)   # E, E+1: absent
    expert = jnp.minimum(expert, E)
    cbits = (k - 1).bit_length()
    bits = (g - 1).bit_length() + cbits
    pairs = (jnp.arange(g)[:, None] << cbits) | jnp.arange(k)
    keys = jnp.sort(((expert << bits) | pairs).reshape(-1))
    bounds = jnp.searchsorted(keys, jnp.arange(E + 1) << bits)
    out = grouped_mlp(x, keys & ((1 << bits) - 1), bounds[1:] - bounds[:-1],
                      None, w_up, w_down, squared_relu, choices=k,
                      interpret=True).reshape(g, k, h)
    xr = x.astype(dtype).astype(jnp.float32)
    for t in range(g):
        for c in range(k):
            e = int(expert[t, c])
            if e < E:
                hid = squared_relu(xr @ w_up[e].astype(jnp.float32))
                want = hid.astype(dtype).astype(jnp.float32)[t] \
                    @ w_down[e].astype(jnp.float32)
                np.testing.assert_allclose(out[t, c], want, atol=tol,
                                           rtol=tol)


def test_the_experts_route_in_chunks_or_at_once_alike(model):
    cfg, params = model
    p = jax.tree.map(lambda a: a[0], params["layers"][1]["mlp"])
    x = jax.random.normal(jax.random.key(5), (1, 96, cfg.hidden_size))
    run = jax.jit(moe.moe_dropless_block, static_argnums=0)
    at_once, a = run(dataclasses.replace(cfg, moe_group_size=96), p, x)
    in_chunks, b = run(dataclasses.replace(cfg, moe_group_size=32), p, x)
    np.testing.assert_allclose(in_chunks, at_once, atol=1e-6)
    np.testing.assert_array_equal(a["rows"], b["rows"])
    assert float(a["rows"].sum()) == 96 * cfg.moe_top_k
