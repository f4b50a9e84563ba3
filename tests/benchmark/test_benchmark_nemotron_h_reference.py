"""The plain Nemotron-H reference against ``models/`` and the serving
engine at tiny widths: attention, experts and a Mamba-2 layer each a layer
alone, a quarter of the experts held."""

import ast
import dataclasses
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import nemotron_h as reference
from megatron_llm_tpu.config import nemotron_h_config
from megatron_llm_tpu.models import mamba2
from megatron_llm_tpu.models import model as model_lib
from megatron_llm_tpu.models import moe
from megatron_llm_tpu.serving import EngineConfig, ServingEngine

TINY = dict(num_layers=4, layer_pattern=("attention", "mlp", "mamba", "mlp"),
            hidden_size=64, num_attention_heads=4, num_kv_heads=2,
            kv_channels=16, ffn_hidden_size=32, moe_shared_expert_size=48,
            moe_latent_size=32, num_experts=4, moe_router_experts=16,
            moe_top_k=6, vocab_size=500, mamba_num_heads=4,
            mamba_head_dim=8, mamba_n_groups=2, mamba_state_size=16,
            mamba_chunk_size=8, max_position_embeddings=512,
            make_vocab_size_divisible_by=4, moe_group_size=64)


def tiny(dtype, **kw):
    cfg = nemotron_h_config("3-super-120b-a12b-ep4-rank0",
                            params_dtype=dtype, **{**TINY, **kw})
    params = jax.jit(lambda k: model_lib.init_params(k, cfg))(
        jax.random.key(0))
    # norm weights, D and the biases away from their initial values, and
    # the routed experts large enough to be seen beside the shared one
    noise = iter(jax.random.split(jax.random.key(1), 256))
    params = jax.tree.map(
        lambda a: (a + 0.1 * jax.random.normal(next(noise), a.shape)
                   ).astype(a.dtype) if a.ndim <= 2 and a.shape[-1] <= 64
        else a, params)
    # and the Mamba-2 input projection at the size it has at the published
    # width (0.02 x sqrt(4096)): at 0.02 x sqrt(64) the state adds a
    # hundredth of what the skip ``D x`` does, and nothing would see it
    def times(tree, keys, by):
        for k in keys:
            tree[k] = (by * tree[k]).astype(tree[k].dtype)

    for layer in params["layers"]:
        if "mlp" in layer:
            times(layer["mlp"], ("w_up", "w_down", "latent_down",
                                 "latent_up"), 4.0)
        if "mamba" in layer:
            times(layer["mamba"], ("w_in",), 8.0)
    return cfg, params


def program_logprobs(cfg, params, toks):
    logits = jax.jit(lambda p, t: model_lib.forward(cfg, p, t))(
        params, jnp.asarray(toks[None, :-1]))
    lp = np.asarray(jax.nn.log_softmax(
        logits[0, :, :cfg.vocab_size].astype(jnp.float32), -1))
    return np.take_along_axis(lp, toks[1:, None], 1)[:, 0]


def test_the_reference_imports_nothing_of_the_program():
    tree = ast.parse(Path(reference.__file__).read_text())
    names = {a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
             for a in n.names} | {n.module for n in ast.walk(tree)
                                  if isinstance(n, ast.ImportFrom)}
    assert names == {"__future__", "functools", "math", "jax", "jax.numpy"}
    cfg, _ = tiny("float32")
    meta = reference.meta_of(cfg)
    hash(meta)                               # a static argument of its jits
    assert dict(meta)["pattern"] == ("attention", "mlp", "mamba", "mlp")
    assert (dict(meta)["held"], dict(meta)["expert_offset"],
            dict(meta)["top_k"]) == (4, 0, 6)


def test_logits_match_the_program_in_float32():
    """Logits, not tokens: the hidden states through the final norm and
    the head, every position, float32 against float32, the recurrence
    against the chunked form: rounding only."""
    cfg, params = tiny("float32")
    toks = np.random.default_rng(0).integers(0, 500, size=150)
    meta = reference.meta_of(cfg)
    with jax.default_matmul_precision("highest"):
        x = reference.hidden_states(params, jnp.asarray(toks), meta)
        x = reference._rms(x, params["final_norm"]["scale"], cfg.norm_eps)
        want = x @ params["lm_head"]
    got = model_lib.forward(cfg, params, jnp.asarray(toks[None]))[0]
    np.testing.assert_allclose(got[:, :cfg.vocab_size],
                               want[:, :cfg.vocab_size], atol=2e-5)
    got = np.asarray(reference.token_logprobs(params, toks, meta))
    np.testing.assert_allclose(got, program_logprobs(cfg, params, toks),
                               atol=2e-5)


def in_bf16(fn):
    """``fn``'s result rounded to bfloat16: what a program that kept this
    quantity in the weights' precision would compute."""
    def rounded(*a, **k):
        out = fn(*a, **k)
        return jax.tree.map(
            lambda x: x.astype(jnp.bfloat16).astype(x.dtype), out)
    return rounded


def stepwise_logprobs(cfg, params, toks, prompt=30):
    """A prefill of ``prompt`` positions, then one position a step through
    the carried states: the cached path without the engine around it."""
    t = jnp.asarray(toks[None])
    k, v = model_lib.init_kv_cache(cfg, 1, 128)
    logits, k, v, rec = model_lib.forward_cached_hybrid(
        cfg, params, t[:, :prompt], k, v, jnp.int32(0),
        model_lib.init_rec_state(cfg, 1), empty_cache=True)
    step = jax.jit(lambda t, k, v, n, rec: model_lib.forward_cached_hybrid(
        cfg, params, t, k, v, n, rec))
    out = [logits[0]]
    for i in range(prompt, len(toks) - 1):
        l, k, v, rec = step(t[:, i:i + 1], k, v,
                            jnp.full((1,), i, jnp.int32), rec)
        out.append(l[0])
    lp = np.asarray(jax.nn.log_softmax(
        jnp.concatenate(out)[:, :cfg.vocab_size], -1))
    return np.take_along_axis(lp, toks[1:, None], 1)[:, 0]


@pytest.mark.parametrize("what", ["as_stated", "router", "state"])
def test_bf16_where_the_configuration_states_float32_is_told_apart(
        monkeypatch, what):
    """Prefill, then 89 decode steps, against the reference's one forward
    pass.  As the configuration states it (router and state in float32)
    the two differ by summation order alone: 1e-6 measured, 1e-5 allowed.
    A router whose scores are rounded to bfloat16 picks other experts
    among near-ties (3e-5 with the bias levelled; 1e-3 with a drawn one,
    whose skewed load leaves more of them), and a state-space state
    rounded to bfloat16 at every step loses what small steps add to it
    (7e-5): either fails that tolerance."""
    cfg, params = tiny("float32")
    toks = np.random.default_rng(0).integers(1, 500, size=120)
    want = np.asarray(reference.token_logprobs(params, toks,
                                               reference.meta_of(cfg)))
    if what == "router":
        monkeypatch.setattr(jax.nn, "sigmoid", in_bf16(jax.nn.sigmoid))
    elif what == "state":
        monkeypatch.setattr(mamba2, "ssd_step", in_bf16(mamba2.ssd_step))
    jax.clear_caches()
    try:
        off = np.abs(stepwise_logprobs(cfg, params, toks) - want).max()
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    if what == "as_stated":
        assert off < 1e-5, off
    else:
        assert off > {"router": 2e-5, "state": 5e-5}[what], off


def test_a_lower_precision_is_told_apart():
    """bf16 weights stay close to their own float32 reference, and a
    model whose weights were rounded to 8 bits does not: by the mean
    limit of the harness (0.03)."""
    cfg, params = tiny("bfloat16")
    toks = np.random.default_rng(1).integers(0, 500, size=130)
    want = np.asarray(reference.token_logprobs(params, toks,
                                               reference.meta_of(cfg)))

    def to_8_bits(a):
        if a.ndim < 2 or a.dtype != jnp.bfloat16:
            return a
        scale = jnp.max(jnp.abs(a.astype(jnp.float32))) / 7.0
        return (jnp.round(a.astype(jnp.float32) / scale) * scale
                ).astype(a.dtype)

    near = np.abs(program_logprobs(cfg, params, toks) - want).mean()
    far = np.abs(program_logprobs(cfg, jax.tree.map(to_8_bits, params),
                                  toks) - want).mean()
    assert near < 0.03 < far, (near, far)


@pytest.mark.parametrize("dtype,atol", [("float32", 5e-5),
                                        ("bfloat16", 0.15)])
def test_the_engine_prefills_and_decodes_to_the_reference(dtype, atol):
    """Through submit, the queue, admission into a padded bucket, the
    block pool and the slot state, then paged decode steps: every
    position's log-probability, prompt and generated, against the
    reference's one full forward.  130 and 77 are no multiple of the
    8-position chunk or of the bucket."""
    cfg, params = tiny(dtype)
    eng = ServingEngine(cfg, params, EngineConfig(
        max_batch_size=2, max_seq_len=256, kv_block_size=16,
        prefill_bucket=64, prefix_cache_blocks=0)).start()
    try:
        rng = np.random.default_rng(2)
        handles = [eng.submit(rng.integers(1, 499, size=n).tolist(), 9,
                              use_eos_stop=False, return_logprobs=True,
                              seed=0) for n in (130, 77, 64)]
        for h in handles:
            got = h.result(timeout=300)
            want = np.asarray(reference.token_logprobs(
                params, got.tokens, reference.meta_of(cfg)))
            assert len(got.logprobs) == len(got.tokens) - 1
            np.testing.assert_allclose(got.logprobs, want, atol=atol)
    finally:
        eng.shutdown()


def test_the_four_shares_of_a_layer_add_up_to_the_uncut_reference():
    """Expert parallel 4 (guide section 4): each rank routes over all 16
    outputs, sums the chosen experts it holds in the latent, pushes that
    partial sum through the up-projection every rank holds whole, and
    adds the shared expert; the four partial results, the shared expert
    counted once, are the uncut layer."""
    cfg, params = tiny("float32", num_experts=16, moe_router_experts=16)
    whole = jax.tree.map(lambda a: a[0], params["layers"][1]["mlp"])
    x = jax.random.normal(jax.random.key(3), (1, 90, cfg.hidden_size))
    uncut = reference.moe(whole, x[0], dict(reference.meta_of(cfg)))

    def share(rank):
        c = dataclasses.replace(cfg, num_experts=4,
                                moe_expert_offset=4 * rank)
        p = {**whole, **{k: whole[k][4 * rank:4 * rank + 4]
                         for k in ("w_up", "w_down")}}
        out, stats = jax.jit(moe.moe_dropless_block, static_argnums=0)(
            c, p, x)
        ref = reference.moe(p, x[0], dict(reference.meta_of(c)))
        np.testing.assert_allclose(out[0], ref, atol=1e-5)
        return out[0], stats["load"]

    parts, loads = zip(*(share(rank) for rank in range(4)))
    only_shared = reference.moe(
        {**whole, **{k: whole[k][:0] for k in ("w_up", "w_down")}},
        x[0], {**dict(reference.meta_of(cfg)), "held": 0})
    np.testing.assert_allclose(sum(parts) - 3 * only_shared, uncut,
                               atol=1e-5)
    # every rank counts the same choices: the router is whole on each
    for load in loads[1:]:
        np.testing.assert_array_equal(load, loads[0])
    # and the routed experts are a part one can see
    assert float(np.abs(parts[0] - parts[1]).max()) > 1e-3
    assert float(np.abs(uncut - only_shared).max()) > 1e-2
