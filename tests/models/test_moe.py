"""Mixture-of-experts layer + expert parallelism tests.

MoE is an extension beyond the reference (SURVEY §2.1: "EP ❌"); these
validate the routed MLP math (capacity, top-k combine, aux loss), parity of
the ep-sharded run with the unsharded one, and end-to-end training.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from megatron_llm_tpu.config import (
    ModelConfig,
    OptimizerConfig,
    ParallelConfig,
    RuntimeConfig,
    TrainConfig,
    deepseek_v3_config,
    nemotron_h_config,
    qwen3_next_config,
    tiny_config,
)
from megatron_llm_tpu.models import model as model_lib
from megatron_llm_tpu.models import moe as moe_lib
from megatron_llm_tpu.models import sharding as shard_lib
from megatron_llm_tpu.parallel import mesh as mesh_lib


def moe_cfg(**overrides):
    base = dict(
        vocab_size=64, hidden_size=32, num_layers=2, num_attention_heads=4,
        num_kv_heads=4, ffn_hidden_size=64, max_position_embeddings=64,
        seq_length=32, params_dtype="float32", attention_impl="dot",
        recompute="none", make_vocab_size_divisible_by=8,
        num_experts=4, moe_top_k=2,
    )
    base.update(overrides)
    return ModelConfig(**base).validate()


def test_moe_block_shapes_and_aux():
    cfg = moe_cfg()
    p = moe_lib.init_moe_params(jax.random.key(0), cfg)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 32, 32)),
                    jnp.float32)
    out, stats = moe_lib.moe_block(cfg, p, x)
    assert out.shape == x.shape
    aux = moe_lib.aux_loss_of(stats)
    assert np.isfinite(float(aux))
    # aux ≥ 1 (it is E·Σf·p with Σf = Σp = 1; minimum at uniform balance)
    assert float(aux) >= 0.99
    # observability stats: dropped fraction in [0,1], loads sum to 1
    assert 0.0 <= float(stats["dropped"]) <= 1.0
    np.testing.assert_allclose(float(jnp.sum(stats["load"])), 1.0,
                               rtol=1e-6)


def test_moe_top1_selects_single_expert():
    """With top_k=1 and ample capacity every token's output must equal the
    chosen expert's MLP applied to it, scaled by the router prob (Switch
    keeps the un-renormalized top-1 gate)."""
    cfg = moe_cfg(moe_top_k=1, moe_capacity_factor=8.0)
    p = moe_lib.init_moe_params(jax.random.key(1), cfg)
    x = jnp.asarray(np.random.default_rng(1).normal(size=(1, 8, 32)),
                    jnp.float32)
    out, _ = moe_lib.moe_block(cfg, p, x)

    logits = np.asarray(x.astype(jnp.float32) @ p["router"])
    probs = np.asarray(jax.nn.softmax(jnp.asarray(logits), axis=-1))
    choice = logits.argmax(-1)[0]  # [s]
    from megatron_llm_tpu.ops.activations import get_activation

    act = get_activation(cfg.activation)
    for t in range(8):
        e = int(choice[t])
        xt = x[0, t]
        gate = xt @ p["w_gate"][e]
        up = xt @ p["w_up"][e]
        hidden = act(jnp.concatenate([gate, up]))
        want = probs[0, t, e] * (hidden @ p["w_down"][e])
        np.testing.assert_allclose(np.asarray(out[0, t]), np.asarray(want),
                                   atol=1e-5, rtol=1e-5)


def test_moe_capacity_drops_overflow():
    """Capacity masking: with a uniform router every token picks expert 0
    (argmax tie → lowest index) and only the first C tokens per group get
    dispatched — all later positions must come out exactly zero."""
    cfg = moe_cfg(moe_top_k=1, moe_capacity_factor=0.1)
    C = moe_lib.capacity(cfg, 32)
    assert C == 1
    p = moe_lib.init_moe_params(jax.random.key(2), cfg)
    p["router"] = jnp.zeros_like(p["router"])  # uniform probs → all pick e0
    x = jnp.asarray(np.random.default_rng(2).normal(size=(1, 32, 32)),
                    jnp.float32)
    out, stats = moe_lib.moe_block(cfg, p, x)
    out = np.asarray(out)
    assert np.abs(out[0, 0]).sum() > 0  # first token served by expert 0
    np.testing.assert_array_equal(out[0, 1:], 0.0)  # overflow dropped
    assert np.isfinite(float(moe_lib.aux_loss_of(stats)))
    # 31 of 32 assignments overflow the C=1 capacity
    np.testing.assert_allclose(float(stats["dropped"]), 31 / 32, rtol=1e-6)


def test_moe_model_forward_and_grad():
    cfg = moe_cfg()
    params = model_lib.init_params(jax.random.key(0), cfg)
    tokens = jnp.asarray(
        np.random.default_rng(3).integers(0, 64, (2, 32)), jnp.int32)
    logits, aux = model_lib.forward(cfg, params, tokens, return_aux=True)
    assert logits.shape == (2, 32, cfg.padded_vocab_size())
    assert np.isfinite(np.asarray(logits)).all()

    def loss(p):
        lg, a = model_lib.forward(cfg, p, tokens, return_aux=True)
        return jnp.mean(lg ** 2) + 0.01 * moe_lib.aux_loss_of(a)

    grads = jax.grad(loss)(params)
    gn = sum(float(jnp.sum(jnp.abs(g))) for g in jax.tree.leaves(grads))
    assert np.isfinite(gn) and gn > 0
    # router gets gradient through both the combine weights and the aux loss
    assert float(jnp.sum(jnp.abs(grads["layers"]["mlp"]["router"]))) > 0


def test_moe_ep_sharded_matches_unsharded(devices):
    cfg = moe_cfg()
    params = model_lib.init_params(jax.random.key(0), cfg)
    tokens = jnp.asarray(
        np.random.default_rng(4).integers(0, 64, (2, 32)), jnp.int32)
    want = model_lib.forward(cfg, params, tokens)

    devs = np.asarray(devices).reshape(2, 1, 1, 1, 4, 1, 1)  # dp2 × ep4
    mesh = Mesh(devs, mesh_lib.AXIS_ORDER)
    parallel = ParallelConfig(data_parallel=2, expert_parallel=4)
    specs = shard_lib.param_specs(cfg, parallel)
    sharded = shard_lib.shard_params(params, specs, mesh)
    tok = jax.device_put(tokens, NamedSharding(mesh, P("dp", None)))
    with mesh_lib.use_mesh(mesh):
        got = jax.jit(lambda p, t: model_lib.forward(cfg, p, t))(sharded, tok)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_moe_train_step_ep():
    """End-to-end: MoE model trains under dp×ep with ZeRO-1; loss finite and
    equal to the unsharded MoE loss."""
    from megatron_llm_tpu.training.driver import setup_train_state

    gen = np.random.default_rng(5)
    tokens = gen.integers(0, 64, (1, 4, 32))
    batch = {
        "tokens": jnp.asarray(tokens, jnp.int32),
        "labels": jnp.asarray(np.roll(tokens, -1, -1), jnp.int32),
        "loss_mask": jnp.ones((1, 4, 32), jnp.float32),
    }

    def run(ep):
        cfg = RuntimeConfig(
            model=tiny_config(num_experts=4, moe_top_k=2),
            parallel=ParallelConfig(data_parallel=2, expert_parallel=ep,
                                    use_distributed_optimizer=True),
            optimizer=OptimizerConfig(lr=1e-3, clip_grad=1.0),
            train=TrainConfig(train_iters=2, micro_batch_size=2,
                              global_batch_size=4, seq_length=32, save=None),
        ).validate()
        params = model_lib.init_params(jax.random.key(0), cfg.model)
        art = setup_train_state(cfg, params=params)
        _, metrics = art.step_fn(art.state, batch, None)
        return float(metrics["loss"]), metrics

    loss_ep, metrics = run(4)
    loss_ref, _ = run(1)
    assert np.isfinite(loss_ep)
    np.testing.assert_allclose(loss_ep, loss_ref, rtol=1e-4, atol=1e-4)
    # routing observability surfaces in the train metrics
    assert 0.0 <= float(metrics["moe_dropped_frac"]) <= 1.0
    assert float(metrics["moe_load_imbalance"]) >= 0.99
    assert np.isfinite(float(metrics["moe_aux_loss"]))


def test_dispatch_memory_scaling():
    """The grouped dispatch tensors must be E-independent (E·C is constant
    at fixed group size): XLA temp bytes equal at E=4 vs E=16 — the
    documented E-scaling property (models/moe.py docstring)."""
    def temp_bytes(E):
        cfg = moe_cfg(num_experts=E, hidden_size=64, ffn_hidden_size=128,
                      seq_length=256, max_position_embeddings=256)
        p = moe_lib.init_moe_params(jax.random.key(0), cfg)
        x = jnp.zeros((2, 256, 64), jnp.float32)
        c = jax.jit(
            lambda p, x: moe_lib.moe_block(cfg, p, x)).lower(p, x).compile()
        return c.memory_analysis().temp_size_in_bytes

    b4, b16 = temp_bytes(4), temp_bytes(16)
    assert abs(b16 - b4) / b4 < 0.1, (b4, b16)


def test_moe_through_pipeline():
    """MoE stats/aux tree flows through the pipelined schedule (pp=2)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from megatron_llm_tpu.parallel import pipeline as pipe

    cfg = tiny_config(num_layers=4, num_experts=4, moe_top_k=2,
                      params_dtype="float32", recompute="none",
                      seq_length=32, max_position_embeddings=32)
    parallel = ParallelConfig(pipeline_parallel=2, num_microbatches=3)
    runtime = RuntimeConfig(model=cfg, parallel=parallel,
                            optimizer=OptimizerConfig(),
                            train=TrainConfig(seq_length=32)).validate()
    mesh = mesh_lib.build_mesh(parallel)
    params = model_lib.init_params(jax.random.key(0), cfg)
    p_params = pipe.to_pipeline_params(params, parallel)
    specs = pipe.pipeline_param_specs(
        shard_lib.param_specs(cfg, parallel), parallel)
    p_params = jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        p_params, specs, is_leaf=lambda v: isinstance(v, P))
    g = np.random.default_rng(0)
    batch = {
        "tokens": jnp.asarray(
            g.integers(0, cfg.vocab_size, (3, 2, 32)), jnp.int32),
        "labels": jnp.asarray(
            g.integers(0, cfg.vocab_size, (3, 2, 32)), jnp.int32),
        "loss_mask": jnp.ones((3, 2, 32), jnp.float32),
    }
    with mesh_lib.use_mesh(mesh):
        loss = jax.jit(
            lambda p, b: pipe.pipeline_loss(runtime, p, b, mesh=mesh)
        )(p_params, batch)
        grads = jax.jit(jax.grad(
            lambda p: pipe.pipeline_loss(runtime, p, batch, mesh=mesh)
        ))(p_params)
    assert np.isfinite(float(loss))
    assert all(np.isfinite(np.asarray(l)).all()
               for l in jax.tree.leaves(grads))


# --- the dropless router: k rounds of max-and-mask against top_k ---------

ROUTERS = {
    # router_experts / top k, the scoring, the scaling: the three presets'
    "qwen3_next": lambda: qwen3_next_config("80b-a3b-ep2-rank0"),
    "nemotron_h": lambda: nemotron_h_config("3-super-120b-a12b-ep4-rank0"),
    "deepseek_v3": lambda: deepseek_v3_config("kanana-2-30b-a3b-pp8-stage0"),
}


def _route_by_sort(cfg, p, xt, counted):
    """The router as it was composed before ``kernels/moe_router.py``: the
    reference the kernel is held to (``lax.top_k``, the un-biased scores
    by ``take_along_axis``, the load by a scatter-add of single
    elements)."""
    k = cfg.moe_top_k
    logits = jnp.dot(xt.astype(jnp.float32), p["router"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    if cfg.moe_router_scoring == "sigmoid":
        score = jax.nn.sigmoid(logits)
        _, idx = jax.lax.top_k(score + p["router_bias"], k)
        weight = jnp.take_along_axis(score, idx, axis=-1)
        weight = weight / (jnp.sum(weight, axis=-1, keepdims=True) + 1e-20)
    else:
        weight, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)
        weight = weight / jnp.sum(weight, axis=-1, keepdims=True)
    if cfg.moe_routed_scaling != 1.0:
        weight = weight * cfg.moe_routed_scaling
    load = jnp.zeros((cfg.router_experts,), jnp.float32).at[
        idx.reshape(-1)].add(jnp.repeat(counted, k))
    return idx, weight, load


def _ulps(a, b) -> int:
    """The largest distance between two float32 arrays of one sign, in
    units in the last place."""
    a, b = (np.asarray(v, np.float32).view(np.int32).astype(np.int64)
            for v in (a, b))
    return int(np.abs(a - b).max())


@pytest.mark.parametrize("rows", [1, 44, 128, 1500, 2048])
@pytest.mark.parametrize("preset", sorted(ROUTERS))
def test_the_router_chooses_as_top_k_does(preset, rows):
    """``moe._route`` (the kernel, interpreted here) against the parent's
    composition at the three presets' routers: the same experts in the
    same order, the same load, the weights within 2 units in the last
    place.  Among the tokens: two experts of one score in every row (two
    equal columns of the router and equal biases), a row of zeros (every
    logit equal, and with biases on a grid of sixteenths many equal
    biased scores: the k-th and the (k+1)-th among them) and, past the
    first two thirds, padded positions that are not counted."""
    cfg = ROUTERS[preset]()
    R, k, h = cfg.router_experts, cfg.moe_top_k, 64
    assert (R, k) == {"qwen3_next": (512, 10), "nemotron_h": (512, 22),
                      "deepseek_v3": (128, 6)}[preset]
    ks = jax.random.split(jax.random.key(rows), 3)
    router = jax.random.normal(ks[0], (h, R), jnp.float32)
    router = router.at[:, 5].set(router[:, 3]).at[:, 0].set(router[:, R - 1])
    p = {"router": router}
    if cfg.moe_router_scoring == "sigmoid":
        bias = jnp.round(0.05 * jax.random.normal(ks[1], (R,)) * 16) / 16
        p["router_bias"] = bias.at[5].set(bias[3]).at[0].set(bias[R - 1])
    xt = jax.random.normal(ks[2], (rows, h), jnp.float32).at[0].set(0.0)
    counted = (jnp.arange(rows) <= 2 * rows // 3).astype(jnp.float32)
    want = jax.jit(_route_by_sort, static_argnums=0)(cfg, p, xt, counted)
    got = jax.jit(moe_lib._route, static_argnums=(0, 1))(
        cfg, True, p, xt, counted)
    # the cases are there: the zero row's k-th and (k+1)-th are one score
    flat = jnp.full((R,), 0.5) + p.get("router_bias", 0.0)
    ranked = jnp.sort(flat)[::-1]
    assert ranked[k - 1] == ranked[k]
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[2], want[2])
    assert float(want[2].sum()) == k * float(counted.sum())
    assert _ulps(got[1], want[1]) <= 2, _ulps(got[1], want[1])
