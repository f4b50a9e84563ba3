"""The gradient's sum over the ranks that split the batch is taken once a
step, after the microbatch loop (training/step.py:BatchAxisSum): the
compiled step's structure, read by obs/collectives.py, and one optimizer
step's values against the same global batch on one device."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_llm_tpu.config import (
    OptimizerConfig,
    ParallelConfig,
    RuntimeConfig,
    TrainConfig,
    tiny_config,
)
from megatron_llm_tpu.models.transformer import rope_tables
from megatron_llm_tpu.obs.collectives import (
    grad_collectives,
    param_shard_shapes,
)
from megatron_llm_tpu.training.driver import (
    grad_collectives_of,
    setup_train_state,
)
from megatron_llm_tpu.training.step import compute_loss, make_train_step

SEQ, ROWS = 32, 4          # a microbatch is ROWS sequences over all ranks
LAYOUTS = {
    "dp2": dict(data_parallel=2),
    "dp2_tp2_sp": dict(data_parallel=2, tensor_parallel=2,
                       sequence_parallel=True),
    "dp2_cp2": dict(data_parallel=2, context_parallel=2),
}


def _cfg(layout: dict, dist: bool, accum: int, dtype="float32",
         clip=1.0) -> RuntimeConfig:
    dp = layout.get("data_parallel", 1)
    return RuntimeConfig(
        model=tiny_config(params_dtype=dtype),
        parallel=ParallelConfig(use_distributed_optimizer=dist, **layout),
        optimizer=OptimizerConfig(lr=1e-3, clip_grad=clip, weight_decay=0.1),
        train=TrainConfig(train_iters=20, micro_batch_size=ROWS // dp,
                          global_batch_size=ROWS * accum, seq_length=SEQ),
    ).validate()


def _batch(accum: int, poison: bool = False) -> dict:
    """Masks that differ between the ranks' slices (weights 0, 1, 2; one
    row at a quarter), so a mean of per-rank means would not pass."""
    rng = np.random.default_rng(0)
    shape = (accum, ROWS, SEQ)
    tokens = rng.integers(0, 256, shape)
    mask = ((rng.random(shape) < 0.6) * rng.integers(1, 3, shape)).astype(
        np.float32)
    mask[:, 0] *= 0.25
    if poison:
        mask[accum - 1, ROWS - 1, 3] = np.nan
    return {"tokens": tokens.astype(np.int32),
            "labels": np.roll(tokens, -1, -1).astype(np.int32),
            "loss_mask": mask}


@functools.lru_cache(maxsize=None)
def _built(layout, dist, accum, dtype="float32", clip=1.0):
    """``(cfg, art)`` of one configuration, set up once a process: the
    value cases that run one configuration again (another batch) call one
    ``art.step_fn`` and compile once.  The step donates its state: who
    runs it hands over a copy (``_one_step``)."""
    cfg = _cfg(LAYOUTS.get(layout, {}), dist, accum, dtype, clip)
    return cfg, setup_train_state(cfg)


def _old_way(cfg, art):
    """The step as the parent built it: a custom ``loss_fn`` keeps the
    microbatch loop GSPMD's, and this one is the decoder-LM loss."""
    rope = rope_tables(cfg.model)

    def loss_fn(cfg, p, mb, rng, deterministic):
        return compute_loss(cfg, p, mb, rng=rng, deterministic=deterministic,
                            rope=rope)

    return make_train_step(cfg, art.mesh, art.state_sharding,
                           art.batch_sharding, loss_fn=loss_fn)


def _lowered(art, step_fn, accum):
    """Lowered under the mesh context the train loop calls the step in,
    as the driver's reading lowers it: jax keeps a jit's executable by
    what it was lowered for, so ``grad_collectives_of`` of the same step
    then compiles nothing (measured: 2.4 s outside the context, 0.04 s
    under it)."""
    batch = {k: jax.device_put(v, art.batch_sharding)
             for k, v in _batch(accum).items()}
    with art.mesh:
        return step_fn.lower(art.state, batch, jax.random.key(0))


def _reading(art, lowered, accum):
    return grad_collectives(
        lowered.compile().as_text(), dict(art.mesh.shape), ("dp", "cp"),
        param_shard_shapes(art.state.params, art.state_sharding.params),
        accum)


@functools.lru_cache(maxsize=None)
def _old_reading(layout, accum):
    """What the old order does inside the loop, read under the plain
    optimizer: what it does with the sums after the loop is the
    optimizer's and is not read, so one compile a layout a process."""
    cfg, art = _built(layout, False, accum)
    return _reading(art, _lowered(art, _old_way(cfg, art), accum), accum)


# (the two optimizer layouts of one layout and loop one after the other:
# with three microbatches they read one compile of the old order, where
# one worker runs both)
STRUCTURES = [(layout, dist, accum) for layout in LAYOUTS
              for accum in (1, 3) for dist in (True, False)]


@pytest.mark.parametrize(
    "layout,dist,accum", STRUCTURES,
    ids=["%s-%s-%d" % (layout, "zero1" if dist else "plain", accum)
         for layout, dist, accum in STRUCTURES])
def test_no_batch_axis_reduction_inside_the_microbatch_loop(layout, dist,
                                                            accum):
    cfg, art = _built(layout, dist, accum)
    n_leaves = len(jax.tree.leaves(art.state.params))
    lowered = _lowered(art, art.step_fn, accum)
    new = _reading(art, lowered, accum)
    if accum == 1:
        # no loop to hoist out of: the parent's program.  The two lower to
        # one text (all six cases, PR 59), and one text is one reading: the
        # old order is compiled only where that stops being so
        old = _lowered(art, _old_way(cfg, art), accum)
        assert (old.as_text() == lowered.as_text()
                or _reading(art, old, accum) == new)
        assert new["in_loop"] == 0
        return
    # the helper sees what it guards: the old order reduces every layer's
    # gradients in every microbatch (and the embedding's beside them)
    old = _old_reading(layout, accum)
    assert old["in_loop"] >= cfg.model.num_layers * accum
    assert old["after_loop"] == 0
    assert new["in_loop"] == 0
    # once a leaf and axis: dp alone reduces each leaf exactly once
    per_leaf = 2 if "context_parallel" in LAYOUTS[layout] else 1
    assert n_leaves <= new["leaves"] <= per_leaf * n_leaves
    if per_leaf == 1 and not dist:
        assert new["after_loop"] == 1          # XLA combines the psums
    assert ("reduce-scatter" in new["kind"]) == dist
    assert new["bytes"] > 0
    # the driver's reading of the same step
    assert grad_collectives_of(art, cfg.train.global_batch_size) == new


# ---------------------------------------------------------------------------
# values
# ---------------------------------------------------------------------------


def _one_step(layout, dist, accum, dtype="float32", clip=1.0, old_way=False,
              poison=False):
    cfg, art = _built(layout, dist, accum, dtype, clip)
    step_fn = _old_way(cfg, art) if old_way else art.step_fn
    batch = {k: jax.device_put(jnp.asarray(v), art.batch_sharding)
             for k, v in _batch(cfg.grad_accum_steps, poison).items()}
    before = jax.device_get(art.state.params)
    with art.mesh:
        state, metrics = step_fn(jax.tree.map(jnp.copy, art.state), batch,
                                 jax.random.key(0))
    return before, jax.device_get(state), jax.device_get(metrics)


@functools.lru_cache(maxsize=None)
def _on_one_device(dtype, clip, poison):
    return _one_step("dp1", False, 3, dtype, clip, poison=poison)


def _gap(a, b) -> float:
    return max(float(np.max(np.abs(np.asarray(x, np.float64)
                                   - np.asarray(y, np.float64))))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


# f32 parameters: the ranks' f32 sums reduced in f32 against one device's
# sums differ by summation order alone (measured: moments 1.4e-9, nu
# 3e-11, grad_norm 6e-8, loss 0).  The first AdamW step divides m by
# sqrt(v) + 1e-8, a sign function where |g| is near the rounding noise,
# so the parameters agree to lr * 5e-2.
F32 = dict(loss=1e-6, grad_norm=5e-7, mu=1e-8, nu=1e-9, params=5e-5)
# bf16 parameters: the gradients of a microbatch leave the backward in
# bf16, rounded once for the whole microbatch on one device and once a
# rank's half here, so either order is within bf16 rounding of one device
# and no closer (measured, mu: this order 3.5e-5, the old one 3.3e-5 — the
# CPU backend promotes the old order's bf16 all-reduce to f32, so the
# rounding of each reduced sum that the TPU adds does not show here).
BF16 = dict(loss=5e-4, grad_norm=5e-3, mu=2e-4, nu=2e-6, params=4e-3)


@pytest.mark.parametrize("case", ["f32", "clip", "bf16"])
def test_step_matches_one_device(case):
    dtype = "bfloat16" if case == "bf16" else "float32"
    clip = 0.5 if case == "clip" else 1.0
    tol = BF16 if case == "bf16" else F32
    _, ref, ref_m = _on_one_device(dtype, clip, False)
    _, got, got_m = _one_step("dp2_tp2_sp", True, 3, dtype, clip)
    if case == "clip":
        assert float(ref_m["grad_norm"]) > clip      # the clip engages
    assert abs(got_m["loss"] - ref_m["loss"]) <= tol["loss"]
    assert abs(got_m["grad_norm"] - ref_m["grad_norm"]) <= tol["grad_norm"]
    assert int(got_m["skipped"]) == 0
    assert _gap(got.opt.mu, ref.opt.mu) <= tol["mu"]
    assert _gap(got.opt.nu, ref.opt.nu) <= tol["nu"]
    assert _gap(got.params, ref.params) <= tol["params"]


def test_non_finite_gradient_skips_the_step_on_every_rank():
    """A NaN in one rank's slice of the last microbatch: the step is
    skipped as on one device — parameters and moments bitwise as before,
    the scheduler's step not advanced."""
    _, ref, ref_m = _on_one_device("float32", 1.0, True)
    before, got, got_m = _one_step("dp2_tp2_sp", True, 3, poison=True)
    assert int(ref_m["skipped"]) == int(got_m["skipped"]) == 1
    assert not np.isfinite(got_m["grad_norm"])
    assert _gap(got.params, before) == 0.0
    assert _gap(got.opt.mu, jax.tree.map(np.zeros_like, got.opt.mu)) == 0.0
    assert int(got.opt.step) == int(ref.opt.step) == 0
    assert int(got.skipped) == 1


@pytest.mark.parametrize("layout,accum", [("dp1", 3), ("dp2_tp2_sp", 1)])
def test_one_rank_or_one_microbatch_takes_no_rank_sum(layout, accum):
    """With nothing to move the sum out of, the step is the one a custom
    ``loss_fn`` without ``mean`` gets, which is GSPMD's order: bitwise.
    (That this is the parent's very program is shown where it matters, on
    the chip: the 7B cell's step comes out of the parent's compile-cache
    entry, PERF.md section 6, PR 28.)"""
    _, new, new_m = _one_step(layout, True, accum)
    _, old, old_m = _one_step(layout, True, accum, old_way=True)
    assert _gap((new.params, new.opt.mu, new.opt.nu),
                (old.params, old.opt.mu, old.opt.nu)) == 0.0
    assert float(new_m["loss"]) == float(old_m["loss"])
    assert float(new_m["grad_norm"]) == float(old_m["grad_norm"])


# ---------------------------------------------------------------------------
# a custom loss that takes ``mean`` goes the same way; the reading fails soft
# ---------------------------------------------------------------------------


def _bert_step(dp, with_mean=True):
    """(step_fn, state, batch, mesh, reading) for a tiny BERT with ZeRO-1
    over ``dp`` ranks and 3 microbatches of uneven masks."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from megatron_llm_tpu.models import encdec
    from megatron_llm_tpu.parallel import mesh as mesh_lib
    from megatron_llm_tpu.training.driver import _shard_train_state

    accum = 3
    model = tiny_config(
        norm_type="layernorm", activation="gelu", use_bias=True,
        position_embedding_type="absolute", tokentype_size=2,
        hidden_dropout=0.0, attention_dropout=0.0,
        max_position_embeddings=SEQ, seq_length=SEQ)
    cfg = RuntimeConfig(
        model=model,
        parallel=ParallelConfig(data_parallel=dp,
                                use_distributed_optimizer=dp > 1),
        optimizer=OptimizerConfig(lr=1e-3, clip_grad=1.0, weight_decay=0.1),
        train=TrainConfig(train_iters=20, micro_batch_size=ROWS // dp,
                          global_batch_size=ROWS * accum, seq_length=SEQ),
    ).validate()
    params = encdec.init_bert_params(jax.random.key(0), cfg.model)
    mesh = mesh_lib.build_mesh(cfg.parallel)
    with mesh_lib.use_mesh(mesh):
        state, sharding = _shard_train_state(
            cfg, mesh, params, encdec.bert_param_specs(cfg.model,
                                                       cfg.parallel))
    batch_sharding = NamedSharding(mesh, P(None, "dp"))
    if with_mean:
        def loss_fn(cfg, p, mb, rng, deterministic,
                    mean=encdec.masked_mean_loss):
            return encdec.bert_loss(cfg.model, p, mb, rng, deterministic,
                                    mean)
    else:
        def loss_fn(cfg, p, mb, rng, deterministic):
            return encdec.bert_loss(cfg.model, p, mb, rng, deterministic)
    step_fn = make_train_step(cfg, mesh, sharding, batch_sharding,
                              loss_fn=loss_fn)
    lm = _batch(accum)
    rng = np.random.default_rng(1)
    batch = dict(lm, pad_mask=np.ones((accum, ROWS, SEQ), np.float32),
                 tokentype_ids=rng.integers(0, 2, (accum, ROWS, SEQ)
                                            ).astype(np.int32),
                 is_random=rng.integers(0, 2, (accum, ROWS)
                                        ).astype(np.int32))
    batch = {k: jax.device_put(jnp.asarray(v), NamedSharding(
        mesh, P(*tuple(batch_sharding.spec)[:v.ndim])))
        for k, v in batch.items()}
    # one compile for the reading and the run
    with mesh:
        compiled = step_fn.lower(state, batch, jax.random.key(0)).compile()
    reading = None
    if dp > 1:
        reading = grad_collectives(
            compiled.as_text(), dict(mesh.shape), ("dp",),
            param_shard_shapes(state.params, sharding.params), accum)
    with mesh:
        new_state, metrics = compiled(state, batch, jax.random.key(0))
    return jax.device_get(new_state), jax.device_get(metrics), reading


def test_a_loss_fn_that_takes_mean_is_cut_into_rank_shares():
    """BERT's masked-LM mean and its NSP mean have different weights; both
    go through ``mean``, so the step may hand the loss a rank's slice."""
    ref, ref_m, _ = _bert_step(1)
    got, got_m, reading = _bert_step(2)
    _, _, old_reading = _bert_step(2, with_mean=False)
    assert reading["in_loop"] == 0 and "reduce-scatter" in reading["kind"]
    assert old_reading["in_loop"] >= 3 and old_reading["after_loop"] == 0
    assert abs(got_m["loss"] - ref_m["loss"]) <= F32["loss"]
    assert abs(got_m["grad_norm"] - ref_m["grad_norm"]) <= F32["grad_norm"]
    assert _gap(got.opt.mu, ref.opt.mu) <= F32["mu"]
    assert _gap(got.params, ref.params) <= F32["params"]


def test_an_unreadable_step_costs_a_log_line_not_the_run(monkeypatch):
    """The reader raises where it finds no microbatch loop; the driver
    reports that instead of a count and goes on."""
    cfg, art = _built("dp2", True, 3)
    hlo = "ENTRY %main (p: f32[4]) -> f32[4] {\n  ROOT %p = f32[4] parameter(0)\n}\n"
    with pytest.raises(ValueError, match="no loop of 3 trips"):
        grad_collectives(hlo, {"dp": 2}, ("dp",), {(4,)}, 3)
    from megatron_llm_tpu.obs import collectives

    def unseen(*a, **k):
        raise AttributeError("'NoneType' object has no attribute 'group'")

    monkeypatch.setattr(collectives, "grad_collectives", unseen)
    reading = grad_collectives_of(art, cfg.train.global_batch_size)
    assert list(reading) == ["unreadable"] and "group" in reading["unreadable"]
