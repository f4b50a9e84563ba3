"""Two-process multihost dryrun on localhost CPU devices.

Exercises every multi-*process* code path that single-process tests cannot:
``initialize.initialize_distributed`` rendezvous, a global mesh spanning
processes (dp axis across hosts), per-process data feeding
(``jax.make_array_from_callback`` over the global batch sharding), the
``_cluster_any`` signal consensus (driver.DistSignalHandler's agreement
primitive), rank-0 printing, and a coordinated orbax save + load.

Reference parity: megatron/initialize.py:124-151 (init_process_group),
dist_signal_handler.py:50-81 (all-gather receipt), checkpointing.py:243-333
(rank-coordinated save).

Run directly (spawns its own two workers):
    python tools/multihost_dryrun.py
Each worker gets 4 local CPU devices → an 8-device global mesh (dp=2, tp=4).
Also wrapped as a test in tests/training/test_multihost.py.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile


def worker(process_id: int, num_processes: int, coordinator: str,
           ckpt_dir: str) -> None:
    import jax

    from megatron_llm_tpu.initialize import initialize_distributed

    initialize_distributed(
        coordinator_address=coordinator,
        num_processes=num_processes,
        process_id=process_id,
    )
    assert jax.process_count() == num_processes, jax.process_count()
    assert jax.process_index() == process_id

    import jax.numpy as jnp
    import numpy as np

    from megatron_llm_tpu.config import (
        OptimizerConfig,
        ParallelConfig,
        RuntimeConfig,
        TrainConfig,
        tiny_config,
    )
    from megatron_llm_tpu import checkpointing
    from megatron_llm_tpu.parallel import mesh as mesh_lib
    from megatron_llm_tpu.training import driver as driver_lib

    n_global = len(jax.devices())
    assert n_global == 8, f"expected 8 global devices, got {n_global}"

    # dp=2 spans the two processes (each holds 4 local devices → tp=4 local).
    parallel = ParallelConfig(data_parallel=2, tensor_parallel=4,
                              use_distributed_optimizer=True)
    cfg = RuntimeConfig(
        model=tiny_config(
            hidden_size=64, num_layers=2, num_attention_heads=8,
            num_kv_heads=8, ffn_hidden_size=128, vocab_size=256,
            seq_length=32, make_vocab_size_divisible_by=32),
        parallel=parallel,
        optimizer=OptimizerConfig(lr=1e-3, clip_grad=1.0),
        train=TrainConfig(train_iters=2, micro_batch_size=2,
                          global_batch_size=4, seq_length=32),
    ).validate()

    art = driver_lib.setup_train_state(cfg)
    driver_lib.print_rank_0("multihost: state sharded over",
                            dict(art.mesh.shape))

    # Per-process data feeding: every process computes the same global numpy
    # batch deterministically and contributes only its addressable shards.
    rng = np.random.default_rng(0)
    shape = (1, 4, 32)  # [accum, batch(dp-sharded), seq]
    toks = rng.integers(0, 256, shape)
    np_batch = {
        "tokens": toks.astype(np.int32),
        "labels": np.roll(toks, -1, -1).astype(np.int32),
        "loss_mask": np.ones(shape, np.float32),
    }
    batch = {
        k: jax.make_array_from_callback(
            v.shape, art.batch_sharding, lambda idx, v=v: v[idx])
        for k, v in np_batch.items()
    }

    state = art.state
    losses = []
    for _ in range(2):
        state, metrics = art.step_fn(state, batch, None)
        losses.append(float(metrics["loss"]))
    assert all(np.isfinite(l) for l in losses), losses

    # Signal consensus: only process 1 "receives" the signal; every process
    # must still agree True (and all-False must agree False).
    assert driver_lib._cluster_any(process_id == 1) is True
    assert driver_lib._cluster_any(False) is False

    # Coordinated orbax save from all processes, then a fresh load against
    # the sharded template (resharding-on-load path included).
    checkpointing.save_checkpoint(ckpt_dir, state, cfg=cfg,
                                  meta={"consumed_samples": 8})
    restored, it = checkpointing.load_checkpoint(ckpt_dir, state)
    assert int(it) == 2, it
    np.testing.assert_allclose(
        np.asarray(jax.device_get(restored.opt.step)),
        np.asarray(jax.device_get(state.opt.step)))
    meta = checkpointing.load_meta(ckpt_dir)
    assert meta.get("consumed_samples") == 8, meta

    driver_lib.print_rank_0(json.dumps({
        "multihost": "ok",
        "processes": num_processes,
        "mesh": dict(art.mesh.shape),
        "losses": [round(l, 4) for l in losses],
    }))


def launch(num_processes: int = 2, port: int = 12657) -> int:
    """Spawn the workers and wait; returns the first nonzero exit code."""
    env_base = dict(os.environ)
    env_base.pop("JAX_PLATFORMS", None)
    with tempfile.TemporaryDirectory() as ckpt_dir:
        procs = []
        for pid in range(num_processes):
            env = dict(
                env_base,
                JAX_PLATFORMS="cpu",
                XLA_FLAGS="--xla_force_host_platform_device_count=4",
                MEGATRON_TPU_MULTIHOST_WORKER=str(pid),
                MEGATRON_TPU_MULTIHOST_COORD=f"localhost:{port}",
                MEGATRON_TPU_MULTIHOST_N=str(num_processes),
                MEGATRON_TPU_MULTIHOST_CKPT=ckpt_dir,
            )
            procs.append(subprocess.Popen(
                [sys.executable, os.path.abspath(__file__)], env=env))
        rcs = [p.wait(timeout=600) for p in procs]
    return next((rc for rc in rcs if rc), 0)


if __name__ == "__main__":
    wid = os.environ.get("MEGATRON_TPU_MULTIHOST_WORKER")
    if wid is None:
        sys.exit(launch())
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    worker(int(wid),
           int(os.environ["MEGATRON_TPU_MULTIHOST_N"]),
           os.environ["MEGATRON_TPU_MULTIHOST_COORD"],
           os.environ["MEGATRON_TPU_MULTIHOST_CKPT"])
