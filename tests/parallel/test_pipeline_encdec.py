"""Split-rank encoder/decoder pipeline correctness vs the unpipelined
models (reference: pipeline_model_parallel_split_rank,
megatron/core/parallel_state.py:110-112 — validated there only by real
multi-GPU runs; here exactly on the hermetic 8-device CPU mesh)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from megatron_llm_tpu.config import (
    ModelConfig,
    OptimizerConfig,
    ParallelConfig,
    RuntimeConfig,
    TrainConfig,
)
from megatron_llm_tpu.models import encdec
from megatron_llm_tpu.parallel import mesh as mesh_lib
from megatron_llm_tpu.parallel import pipeline_encdec as pipe


def _t5_cfg(num_layers=4, num_decoder_layers=4, **over):
    base = dict(
        vocab_size=96, hidden_size=48, num_layers=num_layers,
        num_decoder_layers=num_decoder_layers, num_attention_heads=4,
        num_kv_heads=4, ffn_hidden_size=96, max_position_embeddings=64,
        norm_type="layernorm", activation="gelu",
        position_embedding_type="absolute", use_bias=True,
        tie_embed_logits=True, tokentype_size=0,
        params_dtype="float32", attention_impl="dot", recompute="none",
        make_vocab_size_divisible_by=8, seq_length=32,
    )
    base.update(over)
    return ModelConfig(**base).validate()


def _bert_cfg(num_layers=4, **over):
    return _t5_cfg(num_layers=num_layers, num_decoder_layers=None,
                   tokentype_size=2, **over)


def _runtime(cfg, parallel):
    return RuntimeConfig(model=cfg, parallel=parallel,
                         optimizer=OptimizerConfig(),
                         train=TrainConfig(seq_length=cfg.seq_length))


def _t5_batch(cfg, M, mb, s_enc, s_dec, seed=0):
    g = np.random.default_rng(seed)
    v = cfg.vocab_size
    enc_pad = np.ones((M, mb, s_enc), np.float32)
    dec_pad = np.ones((M, mb, s_dec), np.float32)
    # ragged padding in both streams exercises the bias masking
    enc_pad[:, :, s_enc - 3:] = 0.0
    dec_pad[:, 0, s_dec - 2:] = 0.0
    return {
        "enc_tokens": jnp.asarray(
            g.integers(0, v, (M, mb, s_enc)), jnp.int32),
        "dec_tokens": jnp.asarray(
            g.integers(0, v, (M, mb, s_dec)), jnp.int32),
        "labels": jnp.asarray(g.integers(0, v, (M, mb, s_dec)), jnp.int32),
        "loss_mask": jnp.asarray(dec_pad),
        "enc_pad_mask": jnp.asarray(enc_pad),
        "dec_pad_mask": jnp.asarray(dec_pad),
    }


def _bert_batch(cfg, M, mb, s, seed=0):
    g = np.random.default_rng(seed)
    v = cfg.vocab_size
    pad = np.ones((M, mb, s), np.float32)
    pad[:, :, s - 3:] = 0.0
    return {
        "tokens": jnp.asarray(g.integers(0, v, (M, mb, s)), jnp.int32),
        "pad_mask": jnp.asarray(pad),
        "labels": jnp.asarray(g.integers(0, v, (M, mb, s)), jnp.int32),
        "loss_mask": jnp.asarray(pad * (g.random((M, mb, s)) < 0.3)),
        "tokentype_ids": jnp.asarray(
            g.integers(0, 2, (M, mb, s)), jnp.int32),
        "is_random": jnp.asarray(g.integers(0, 2, (M, mb)), jnp.int32),
    }


def _t5_reference_loss(cfg, params, batch):
    M = batch["enc_tokens"].shape[0]

    def one(m):
        return encdec.t5_loss(cfg, params, {
            k: batch[k][m] for k in batch})

    return jnp.mean(jax.vmap(one)(jnp.arange(M)))


def _bert_reference_loss(cfg, params, batch):
    M = batch["tokens"].shape[0]

    def one(m):
        return encdec.bert_loss(cfg, params,
                                {k: batch[k][m] for k in batch})

    return jnp.mean(jax.vmap(one)(jnp.arange(M)))


def _place(staged, specs, mesh):
    return jax.tree.map(
        lambda x, sp: jax.device_put(x, NamedSharding(mesh, sp)),
        staged, specs, is_leaf=lambda v: isinstance(v, P))


@pytest.mark.parametrize(
    "dp,pp,tp,split,M,s_enc,s_dec,W",
    [
        (1, 2, 1, 1, 3, 16, 16, 0),     # minimal split: 1 enc + 1 dec stage
        (1, 4, 1, 2, 4, 16, 8, 0),      # uneven seq lengths (padded carry)
        (2, 2, 2, 1, 4, 16, 16, 0),     # dp x pp x tp composed
        (1, 4, 1, 2, 6, 16, 16, 3),     # windowed remat over the tick loop
        (1, 4, 1, 1, 4, 8, 16, 0),      # asymmetric split (1 enc, 3 dec)
    ],
)
def test_t5_pipeline_matches_reference(dp, pp, tp, split, M, s_enc, s_dec,
                                       W):
    enc_stages, dec_stages = split, pp - split
    lpc = 1     # a stage's chunk is one layer: every stage still has its own
    cfg = _t5_cfg(num_layers=enc_stages * lpc,
                  num_decoder_layers=dec_stages * lpc,
                  seq_length=max(s_enc, s_dec),
                  max_position_embeddings=max(s_enc, s_dec))
    parallel = ParallelConfig(
        data_parallel=dp, pipeline_parallel=pp, tensor_parallel=tp,
        pipeline_split_rank=split, num_microbatches=M,
        pipeline_remat_window=W,
    ).validate()
    mesh = mesh_lib.build_mesh(parallel)

    params = encdec.init_t5_params(jax.random.key(0), cfg)
    batch = _t5_batch(cfg, M, mb=2, s_enc=s_enc, s_dec=s_dec)

    ref_loss, ref_grads = jax.jit(jax.value_and_grad(
        lambda p: _t5_reference_loss(cfg, p, batch)))(params)

    staged = pipe.t5_to_pipeline_params(params, parallel)
    specs = pipe.t5_pipeline_param_specs(cfg, parallel)
    staged = _place(staged, specs, mesh)
    runtime = _runtime(cfg, parallel)

    # one program gives the loss and its gradients; the forward alone,
    # the program evaluation runs (no residuals, no remat window), is
    # compiled and compared at the minimal split
    with mesh_lib.use_mesh(mesh):
        pl_loss, pl_grads = jax.jit(jax.value_and_grad(
            lambda p: pipe.t5_pipeline_loss(runtime, p, batch, mesh=mesh)
        ))(staged)
        if (dp, pp, tp) == (1, 2, 1):
            np.testing.assert_allclose(
                np.asarray(jax.jit(
                    lambda p, b: pipe.t5_pipeline_loss(
                        runtime, p, b, mesh=mesh))(staged, batch)),
                np.asarray(ref_loss), rtol=2e-5, atol=2e-5)

    np.testing.assert_allclose(np.asarray(pl_loss), np.asarray(ref_loss),
                               rtol=2e-5, atol=2e-5)

    # grads: map the staged layout back and compare every leaf
    back = pipe.t5_from_pipeline_params(pl_grads, parallel)
    flat_ref, _ = jax.tree_util.tree_flatten_with_path(ref_grads)
    flat_got = dict(jax.tree_util.tree_flatten_with_path(back)[0])
    for path, g_ref in flat_ref:
        g_got = flat_got[path]
        np.testing.assert_allclose(
            np.asarray(g_got), np.asarray(g_ref), rtol=1e-4, atol=1e-4,
            err_msg=jax.tree_util.keystr(path))


def test_t5_pipeline_dummy_cross_grads_are_zero():
    """Encoder stages' zero cross-attention weights must receive exactly
    zero cotangents (the is_decoder mask), so they stay a fixed point of
    training and never perturb encoder math."""
    pp, split, lpc, M, s = 2, 1, 1, 3, 16
    cfg = _t5_cfg(num_layers=split * lpc,
                  num_decoder_layers=(pp - split) * lpc, seq_length=s)
    parallel = ParallelConfig(
        pipeline_parallel=pp, pipeline_split_rank=split,
        num_microbatches=M).validate()
    mesh = mesh_lib.build_mesh(parallel)
    params = encdec.init_t5_params(jax.random.key(0), cfg)
    batch = _t5_batch(cfg, M, mb=2, s_enc=s, s_dec=s)
    staged = pipe.t5_to_pipeline_params(params, parallel)
    staged = _place(staged, pipe.t5_pipeline_param_specs(cfg, parallel),
                    mesh)
    runtime = _runtime(cfg, parallel)
    with mesh_lib.use_mesh(mesh):
        grads = jax.jit(jax.grad(
            lambda p: pipe.t5_pipeline_loss(runtime, p, batch, mesh=mesh)
        ))(staged)
    for leaf in jax.tree.leaves(
            jax.tree.map(lambda g: g[:split], grads["cross"])):
        assert float(jnp.abs(leaf).max()) == 0.0
    # ...while the real (decoder-stage) cross weights train
    total = sum(float(jnp.abs(leaf[split:]).sum())
                for leaf in jax.tree.leaves(grads["cross"]))
    assert total > 0.0


@pytest.mark.parametrize(
    "dp,pp,tp,M,W",
    [
        (1, 2, 1, 3, 0),
        (1, 4, 1, 4, 0),
        (2, 2, 2, 4, 0),
        (1, 4, 1, 6, 3),   # windowed remat
    ],
)
def test_bert_pipeline_matches_reference(dp, pp, tp, M, W):
    s = 16
    cfg = _bert_cfg(num_layers=pp, seq_length=s)     # one layer a stage
    parallel = ParallelConfig(
        data_parallel=dp, pipeline_parallel=pp, tensor_parallel=tp,
        num_microbatches=M, pipeline_remat_window=W,
    ).validate()
    mesh = mesh_lib.build_mesh(parallel)

    params = encdec.init_bert_params(jax.random.key(0), cfg)
    batch = _bert_batch(cfg, M, mb=2, s=s)

    ref_loss, ref_grads = jax.jit(jax.value_and_grad(
        lambda p: _bert_reference_loss(cfg, p, batch)))(params)

    staged = pipe.bert_to_pipeline_params(params, parallel)
    specs = pipe.bert_pipeline_param_specs(cfg, parallel)
    staged = _place(staged, specs, mesh)
    runtime = _runtime(cfg, parallel)

    # one program gives the loss and its gradients; the forward alone,
    # the program evaluation runs (no residuals, no remat window), is
    # compiled and compared at the minimal split
    with mesh_lib.use_mesh(mesh):
        pl_loss, pl_grads = jax.jit(jax.value_and_grad(
            lambda p: pipe.bert_pipeline_loss(runtime, p, batch, mesh=mesh)
        ))(staged)
        if (dp, pp, tp) == (1, 2, 1):
            np.testing.assert_allclose(
                np.asarray(jax.jit(
                    lambda p, b: pipe.bert_pipeline_loss(
                        runtime, p, b, mesh=mesh))(staged, batch)),
                np.asarray(ref_loss), rtol=2e-5, atol=2e-5)

    np.testing.assert_allclose(np.asarray(pl_loss), np.asarray(ref_loss),
                               rtol=2e-5, atol=2e-5)

    back = pipe.bert_from_pipeline_params(pl_grads, parallel)
    flat_ref, _ = jax.tree_util.tree_flatten_with_path(ref_grads)
    flat_got = dict(jax.tree_util.tree_flatten_with_path(back)[0])
    for path, g_ref in flat_ref:
        np.testing.assert_allclose(
            np.asarray(flat_got[path]), np.asarray(g_ref),
            rtol=1e-4, atol=1e-4, err_msg=jax.tree_util.keystr(path))


def test_split_rank_validation():
    with pytest.raises(AssertionError):
        ParallelConfig(pipeline_parallel=4,
                       pipeline_split_rank=4).validate()
    with pytest.raises(AssertionError):
        ParallelConfig(pipeline_parallel=4,
                       pipeline_split_rank=0).validate()
    # unequal layers-per-chunk across the split is rejected with a message
    cfg = _t5_cfg(num_layers=4, num_decoder_layers=2)
    parallel = ParallelConfig(pipeline_parallel=2, pipeline_split_rank=1,
                              num_microbatches=2).validate()
    params = encdec.init_t5_params(jax.random.key(0), cfg)
    with pytest.raises(AssertionError, match="layers-per-stage"):
        pipe.t5_to_pipeline_params(params, parallel)
