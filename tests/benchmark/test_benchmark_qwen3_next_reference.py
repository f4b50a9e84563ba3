"""The plain Qwen3-Next reference against ``models/`` and the serving
engine at tiny widths: one whole period (three Gated DeltaNet layers, one
gated attention layer), half of the experts held."""

import ast
import dataclasses
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import qwen3_next as reference
from megatron_llm_tpu.config import qwen3_next_config
from megatron_llm_tpu.models import model as model_lib
from megatron_llm_tpu.models import moe
from megatron_llm_tpu.serving import EngineConfig, ServingEngine

TINY = dict(num_layers=4, hidden_size=64, num_attention_heads=4,
            num_kv_heads=2, kv_channels=32, ffn_hidden_size=32,
            moe_shared_expert_size=32, num_experts=8, moe_router_experts=16,
            moe_top_k=4, vocab_size=500, linear_num_key_heads=2,
            linear_num_value_heads=4, linear_key_head_dim=16,
            linear_value_head_dim=16, max_position_embeddings=512,
            make_vocab_size_divisible_by=4, moe_group_size=64)


def tiny(dtype, **kw):
    cfg = qwen3_next_config("80b-a3b-ep2-rank0", params_dtype=dtype,
                            **{**TINY, **kw})
    params = jax.jit(lambda k: model_lib.init_params(k, cfg))(
        jax.random.key(0))
    # norm weights away from their initial 0 and 1, so that they are seen
    noise = iter(jax.random.split(jax.random.key(1), 256))
    params = jax.tree.map(
        lambda a: (a + 0.1 * jax.random.normal(next(noise), a.shape)
                   ).astype(a.dtype) if a.ndim <= 2 and a.shape[-1] <= 64
        else a, params)
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
    assert dict(meta)["pattern"] == ("linear", "linear", "linear", "full")
    assert (dict(meta)["held"], dict(meta)["expert_offset"]) == (8, 0)


def test_logprobs_match_the_program_in_float32():
    cfg, params = tiny("float32")
    toks = np.random.default_rng(0).integers(0, 500, size=150)
    got = np.asarray(reference.token_logprobs(params, toks,
                                              reference.meta_of(cfg)))
    # float32 against float32, recurrence against chunks: rounding only
    np.testing.assert_allclose(got, program_logprobs(cfg, params, toks),
                               atol=2e-5)


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
    64-position chunk or of the bucket."""
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


def test_the_two_shares_of_a_layer_add_up_to_the_uncut_reference():
    """Expert parallel 2: each rank routes over all 16 outputs, sums the
    chosen experts it holds and adds the shared expert; the two partial
    sums, the shared expert counted once, are the uncut layer."""
    cfg, params = tiny("float32", num_experts=16, moe_router_experts=16)
    whole = jax.tree.map(lambda a: a[0], params["layers"][1]["mlp"])
    x = jax.random.normal(jax.random.key(3), (1, 90, cfg.hidden_size))
    uncut = reference.moe(whole, x[0], dict(reference.meta_of(cfg)))

    def share(rank):
        c = dataclasses.replace(cfg, num_experts=8, moe_expert_offset=8 * rank)
        p = {**whole, **{k: whole[k][8 * rank:8 * rank + 8]
                         for k in ("w_gate", "w_up", "w_down")}}
        out, stats = jax.jit(moe.moe_dropless_block, static_argnums=0)(
            c, p, x)
        ref = reference.moe(p, x[0], dict(reference.meta_of(c)))
        np.testing.assert_allclose(out[0], ref, atol=1e-5)
        return out[0], stats["load"]

    (a, load_a), (b, load_b) = share(0), share(1)
    only_shared = reference.moe(
        {**whole, **{k: whole[k][:0] for k in ("w_gate", "w_up", "w_down")}},
        x[0], {**dict(reference.meta_of(cfg)), "held": 0})
    np.testing.assert_allclose(a + b - only_shared, uncut, atol=1e-5)
    # both ranks count the same choices: the router is whole on each
    np.testing.assert_array_equal(load_a, load_b)
    assert float(np.abs(a - b).max()) > 1e-4
