"""Checkpoint tests: save/load round trip, tracker semantics, resume
equivalence, reshard-on-load across different meshes (the capability
tools/checkpoint_util.py provides offline in the reference)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from megatron_llm_tpu import checkpointing as ckpt
from megatron_llm_tpu.config import (
    OptimizerConfig,
    ParallelConfig,
    RuntimeConfig,
    TrainConfig,
    tiny_config,
)
from megatron_llm_tpu.models import model as model_lib
from megatron_llm_tpu.models import sharding as shard_lib
from megatron_llm_tpu.training.step import init_train_state, make_train_step


def _cfg():
    return RuntimeConfig(
        model=tiny_config(),
        optimizer=OptimizerConfig(lr=1e-3, lr_warmup_iters=2),
        train=TrainConfig(train_iters=10, micro_batch_size=2,
                          global_batch_size=4, seq_length=16),
    ).validate()


def _batch(cfg, seed=0):
    rng = np.random.default_rng(seed)
    shape = (2, 2, 16)
    toks = rng.integers(0, 255, shape)
    return {
        "tokens": jnp.asarray(toks, jnp.int32),
        "labels": jnp.asarray(np.roll(toks, -1, -1), jnp.int32),
        "loss_mask": jnp.ones(shape, jnp.float32),
    }


def test_tracker_roundtrip(tmp_path):
    assert ckpt.read_tracker(tmp_path) is None
    ckpt.write_tracker(tmp_path, 42)
    assert ckpt.read_tracker(tmp_path) == 42
    ckpt.write_tracker(tmp_path, "release")
    assert ckpt.read_tracker(tmp_path) == "release"


def test_save_load_resume_equivalence(tmp_path):
    """Save at iter 3, keep training to 6; reload at 3 and retrain — states
    must match exactly (resumable training semantics)."""
    cfg = _cfg()
    params = model_lib.init_params(jax.random.key(0), cfg.model)
    state = init_train_state(cfg, params)
    step = make_train_step(cfg)
    rng = jax.random.key(9)
    batch = _batch(cfg)
    for _ in range(3):
        state, _ = step(state, batch, rng)
    ckpt.save_checkpoint(str(tmp_path), state, cfg)
    assert ckpt.read_tracker(str(tmp_path)) == 3

    cont = state
    for _ in range(3):
        cont, m1 = step(cont, batch, rng)

    restored, it = ckpt.load_checkpoint(str(tmp_path), init_train_state(
        cfg, model_lib.init_params(jax.random.key(1), cfg.model)))
    assert it == 3
    assert int(restored.iteration) == 3
    for _ in range(3):
        restored, m2 = step(restored, batch, rng)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                               rtol=1e-6)
    for a, b in zip(jax.tree.leaves(cont.params),
                    jax.tree.leaves(restored.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_config_in_checkpoint(tmp_path):
    cfg = _cfg()
    params = model_lib.init_params(jax.random.key(0), cfg.model)
    state = init_train_state(cfg, params)
    ckpt.save_checkpoint(str(tmp_path), state, cfg)
    loaded = ckpt.load_config_from_checkpoint(str(tmp_path))
    assert loaded.model.hidden_size == cfg.model.hidden_size
    assert loaded.train.global_batch_size == cfg.train.global_batch_size


def test_a_stored_config_with_the_retired_decode_switch_loads(tmp_path):
    """Every checkpoint written while ``ModelConfig`` had ``fused_decode``
    stores the key: ``RuntimeConfig.from_dict`` drops exactly that one,
    and any other unknown key still fails."""
    import json

    from megatron_llm_tpu.checkpointing import checkpoint_dir

    cfg = _cfg()
    params = model_lib.init_params(jax.random.key(0), cfg.model)
    ckpt.save_checkpoint(str(tmp_path), init_train_state(cfg, params), cfg)
    stored = checkpoint_dir(str(tmp_path), 0) / "config.json"
    d = json.loads(stored.read_text())
    assert "fused_decode" not in d["model"]
    d["model"]["fused_decode"] = True
    stored.write_text(json.dumps(d))
    assert ckpt.load_config_from_checkpoint(str(tmp_path)).model == cfg.model
    d["model"]["no_such_field"] = 1
    with pytest.raises(TypeError, match="no_such_field"):
        RuntimeConfig.from_dict(d)


def test_reshard_on_load(tmp_path, devices):
    """Save unsharded, load tp=8-sharded (and back) — values identical.
    This is the reference's checkpoint_util TP-resharding capability, free
    via logical arrays."""
    cfg = _cfg()
    mcfg = tiny_config(make_vocab_size_divisible_by=64)
    params = model_lib.init_params(jax.random.key(0), mcfg, tp=8)
    ckpt.save_release_params(str(tmp_path), params)

    mesh = Mesh(np.asarray(devices).reshape(1, 1, 1, 1, 8),
                ("dp", "pp", "cp", "ep", "tp"))
    pspecs = shard_lib.param_specs(mcfg, ParallelConfig(tensor_parallel=8))
    template = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=NamedSharding(mesh, s)),
        params, pspecs)
    sharded = ckpt.load_release_params(str(tmp_path), template)
    wq = sharded["layers"]["attn"]["wq"]
    assert wq.sharding.spec == P(None, None, "tp")
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(sharded)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # and back to host/unsharded
    unsharded = ckpt.load_release_params(
        str(tmp_path), jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params))
    np.testing.assert_array_equal(
        np.asarray(unsharded["final_norm"]["scale"]),
        np.asarray(params["final_norm"]["scale"]))


def test_load_checkpoint_handles_release(tmp_path):
    """Tracker says 'release' → params restored, fresh optimizer state."""
    cfg = _cfg()
    params = model_lib.init_params(jax.random.key(0), cfg.model)
    ckpt.save_release_params(str(tmp_path), params, cfg)
    template = init_train_state(
        cfg, model_lib.init_params(jax.random.key(1), cfg.model))
    state, it = ckpt.load_checkpoint(str(tmp_path), template)
    assert it == "release"
    np.testing.assert_array_equal(
        np.asarray(state.params["final_norm"]["scale"]),
        np.asarray(params["final_norm"]["scale"]))
    assert int(state.opt.step) == 0


def test_meta_roundtrip(tmp_path):
    cfg = _cfg()
    params = model_lib.init_params(jax.random.key(0), cfg.model)
    state = init_train_state(cfg, params)
    ckpt.save_checkpoint(str(tmp_path), state, cfg,
                         meta={"consumed_samples": 2**40})
    assert ckpt.load_meta(str(tmp_path))["consumed_samples"] == 2**40


def test_random_sampler_resume_matches_uninterrupted():
    """Resume arithmetic uses the active (full-batch) epoch size."""
    from megatron_llm_tpu.data.samplers import RandomSampler
    import itertools

    def take(sampler, n):
        return list(itertools.islice(iter(sampler), n))

    full = take(RandomSampler(10, 0, 4, seed=3), 6)  # active=8/epoch → 2/epoch
    resumed = take(RandomSampler(10, 16, 4, seed=3), 2)  # 16 = 2 epochs
    assert resumed == full[4:6]


def test_load_params_for_inference(tmp_path):
    """Serving path: params-only restore from a full training checkpoint
    (partial restore — no optimizer state read) and from a 'release'
    params-only checkpoint."""
    cfg = _cfg()
    params = model_lib.init_params(jax.random.key(3), cfg.model)
    state = init_train_state(cfg, params)
    ckpt.save_checkpoint(str(tmp_path), state, cfg, iteration=5)
    loaded = ckpt.load_params_for_inference(str(tmp_path), cfg.model)
    jax.tree.map(np.testing.assert_array_equal, loaded, params)

    rel = tmp_path / "rel"
    ckpt.save_release_params(str(rel), params, cfg)
    loaded_rel = ckpt.load_params_for_inference(str(rel), cfg.model)
    jax.tree.map(np.testing.assert_array_equal, loaded_rel, params)
