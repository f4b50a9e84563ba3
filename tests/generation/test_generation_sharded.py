"""Sharded (tp) generation parity: BASELINE config 2's regime.

The reference serves TP-sharded models through its text-generation server
(megatron/text_generation/*); here generation is one jitted program over
the mesh and GSPMD moves activations — greedy decode must be identical to
the unsharded run.
"""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from megatron_llm_tpu.config import ParallelConfig, tiny_config
from megatron_llm_tpu.generation.generation import generate_tokens
from megatron_llm_tpu.models import model as model_lib
from megatron_llm_tpu.models import sharding as shard_lib
from megatron_llm_tpu.parallel import mesh as mesh_lib


def test_tp_sharded_greedy_matches_unsharded():
    tp = 4
    cfg = tiny_config(
        num_layers=2, hidden_size=64, num_attention_heads=8, num_kv_heads=8,
        ffn_hidden_size=128, vocab_size=256,
        make_vocab_size_divisible_by=8 * tp,
        params_dtype="float32", attention_impl="dot", recompute="none",
        seq_length=48, max_position_embeddings=48,
    )
    params = model_lib.init_params(jax.random.key(0), cfg, tp=tp)

    g = np.random.default_rng(0)
    b, prompt_len, max_seq = 2, 16, 48
    tokens = np.zeros((b, max_seq), np.int32)
    tokens[:, :prompt_len] = g.integers(3, cfg.vocab_size, (b, prompt_len))
    tokens = jnp.asarray(tokens)
    lengths = jnp.full((b,), prompt_len, jnp.int32)

    want = generate_tokens(cfg, params, tokens, lengths, use_eos_stop=False)

    parallel = ParallelConfig(tensor_parallel=tp)
    mesh = mesh_lib.build_mesh(parallel)
    specs = shard_lib.param_specs(cfg, parallel)
    sharded = shard_lib.shard_params(params, specs, mesh)
    with mesh_lib.use_mesh(mesh):
        got = generate_tokens(cfg, sharded, tokens, lengths,
                              use_eos_stop=False)

    np.testing.assert_array_equal(np.asarray(got.tokens),
                                  np.asarray(want.tokens))
    np.testing.assert_array_equal(np.asarray(got.lengths),
                                  np.asarray(want.lengths))


def test_pp_serving_relayout_greedy_matches_unsharded():
    """Serving under pp (BASELINE config 3/5 serving regime): the serving
    re-layout shards heads over tp and the stacked layer axis over pp
    (models/sharding.py:serving_param_specs) — greedy decode must be
    identical to unsharded."""
    pp, tp = 2, 2
    cfg = tiny_config(
        num_layers=4, hidden_size=64, num_attention_heads=8, num_kv_heads=8,
        ffn_hidden_size=128, vocab_size=256,
        make_vocab_size_divisible_by=8 * pp * tp,
        params_dtype="float32", attention_impl="dot", recompute="none",
        seq_length=48, max_position_embeddings=48,
    )
    params = model_lib.init_params(jax.random.key(1), cfg, tp=pp * tp)

    g = np.random.default_rng(1)
    b, prompt_len, max_seq = 2, 16, 48
    tokens = np.zeros((b, max_seq), np.int32)
    tokens[:, :prompt_len] = g.integers(3, cfg.vocab_size, (b, prompt_len))
    tokens = jnp.asarray(tokens)
    lengths = jnp.full((b,), prompt_len, jnp.int32)

    want = generate_tokens(cfg, params, tokens, lengths, use_eos_stop=False)

    parallel = ParallelConfig(data_parallel=2, pipeline_parallel=pp,
                              tensor_parallel=tp)
    mesh = mesh_lib.build_mesh(parallel)
    specs = shard_lib.serving_param_specs(cfg, parallel)
    sharded = shard_lib.shard_params(params, specs, mesh)
    with mesh_lib.use_mesh(mesh):
        got = generate_tokens(cfg, sharded, tokens, lengths,
                              use_eos_stop=False)

    np.testing.assert_array_equal(np.asarray(got.tokens),
                                  np.asarray(want.tokens))
    np.testing.assert_array_equal(np.asarray(got.lengths),
                                  np.asarray(want.lengths))


def test_pp_serving_relayout_beam_matches_unsharded():
    from megatron_llm_tpu.generation.generation import beam_search

    pp, tp = 2, 2
    cfg = tiny_config(
        num_layers=4, hidden_size=64, num_attention_heads=8, num_kv_heads=8,
        ffn_hidden_size=128, vocab_size=256,
        make_vocab_size_divisible_by=8 * pp * tp,
        params_dtype="float32", attention_impl="dot", recompute="none",
        seq_length=32, max_position_embeddings=32,
    )
    params = model_lib.init_params(jax.random.key(2), cfg, tp=pp * tp)

    g = np.random.default_rng(2)
    prompt_len, max_seq = 12, 32
    tokens = np.zeros((max_seq,), np.int32)
    tokens[:prompt_len] = g.integers(3, cfg.vocab_size, (prompt_len,))
    tokens = jnp.asarray(tokens)

    want = beam_search(cfg, params, tokens, prompt_len, beam_size=3)

    parallel = ParallelConfig(pipeline_parallel=pp, tensor_parallel=tp)
    mesh = mesh_lib.build_mesh(parallel)
    specs = shard_lib.serving_param_specs(cfg, parallel)
    sharded = shard_lib.shard_params(params, specs, mesh)
    with mesh_lib.use_mesh(mesh):
        got = beam_search(cfg, sharded, tokens, prompt_len, beam_size=3)

    np.testing.assert_array_equal(np.asarray(got.tokens),
                                  np.asarray(want.tokens))
    np.testing.assert_allclose(np.asarray(got.scores),
                               np.asarray(want.scores), rtol=1e-5)

