"""The jitted train step: fwd/bwd with microbatch accumulation, mixed
precision, clipping, NaN-skip, and the optimizer update.

Reference mapping (megatron/training.py:393-459 ``train_step``):
- zero grad buffer → fp32 grad accumulator initialized per step
- forward_backward schedule (no pipelining) → ``lax.scan`` over microbatches
  accumulating fp32 grads (the schedule variants live in parallel/pipeline.py)
- ``optimizer.reduce_model_grads``'s DP all-reduce → once a step, after the
  microbatch loop (``BatchAxisSum``): the loop runs manual over the mesh
  axes that split the batch (dp, cp), every rank adds its own microbatches'
  gradients up in fp32, and each leaf is then reduce-scattered into the
  distributed optimizer's shard of it (all-reduced without one).  Left to
  GSPMD the loop's carry must hold a reduced gradient, so every leaf is
  all-reduced in every microbatch (17.9 % of the Falcon-40B dp2 × tp2
  step, PERF.md §6 PR 28).  One microbatch, a pipelined schedule, and a
  loss that couples the ranks' samples (MoE's auxiliary loss, a custom
  ``loss_fn`` that takes no ``mean``) keep GSPMD's order
- unscale → check inf → clip → adam → copy params
  (optimizer/optimizer.py:407-466) → explicit jnp chain below, with the
  skipped-iteration semantics on non-finite grads
- loss averaging across DP for logging (megatron/utils.py:70) → the masked
  mean over the whole dp-sharded microbatch (under ``BatchAxisSum`` the psum
  of the ranks' shares of it)
"""

from __future__ import annotations

import inspect
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..config import RuntimeConfig
from ..models import model as model_lib
from ..models.transformer import rope_tables
from ..parallel.cross_entropy import cross_entropy, masked_mean_loss
from ..resilience.anomaly import (
    GuardState,
    guard_spec,  # noqa: F401  (re-exported for spec-construction sites)
    guard_update,
    init_guard_state,
)
from . import optimizer as opt_lib
from . import schedule

PyTree = Any


class TrainState(NamedTuple):
    params: PyTree
    opt: opt_lib.OptState
    iteration: jax.Array  # i32: completed train steps (incl. skipped)
    skipped: jax.Array  # i32: iterations skipped (non-finite grads/loss,
    #                     loss spikes — any anomalous step)
    guard: GuardState  # anomaly-defense scalars (resilience/anomaly.py):
    #                    loss EWMA/variance + consecutive-anomaly run,
    #                    carried in-state so skip decisions survive
    #                    donation and checkpointing
    # NOTE: consumed_samples (the resumable-sampling counter) is NOT part of
    # the device state: it can exceed int32 on long pretraining runs, so the
    # training driver keeps it as a python int (like the reference's
    # args.consumed_train_samples) and persists it via checkpoint metadata.


def init_train_state(cfg: RuntimeConfig, params: PyTree) -> TrainState:
    use_scaler = cfg.model.params_dtype in ("float16", "fp16")
    return TrainState(
        params=params,
        opt=opt_lib.init_opt_state(params, cfg.optimizer,
                                   use_fp16_scaler=use_scaler),
        iteration=jnp.zeros((), jnp.int32),
        skipped=jnp.zeros((), jnp.int32),
        guard=init_guard_state(),
    )


def zigzag_permute_batch(cfg: RuntimeConfig, batch: dict) -> dict:
    """Zigzag cp layout: permute the (tiny int/float) batch arrays into
    chunk order [r, 2n-1-r] per cp shard and hand RoPE the global
    positions.  Per-token CE, masked means and the registry metrics are
    order-invariant, so losses need no un-permutation.  No-op unless
    ``cfg.model.context_parallel_zigzag``.  Used by BOTH the train loss and
    the eval step — the model's attention is unconditionally zigzag once
    the flag is set, so any natural-order batch would be silently wrong.
    """
    if not cfg.model.context_parallel_zigzag:
        return batch
    from ..parallel.ring_attention import zigzag_indices

    pi = zigzag_indices(batch["tokens"].shape[-1],
                        cfg.parallel.context_parallel)
    pos = batch.get("position_ids")
    batch = dict(batch)
    for key in ("tokens", "labels", "loss_mask", "segment_ids",
                "assistant_mask", "pad_mask"):
        if batch.get(key) is not None:
            batch[key] = batch[key][..., pi]
    batch["position_ids"] = (
        pos[..., pi] if pos is not None
        else jnp.broadcast_to(jnp.asarray(pi, jnp.int32),
                              batch["tokens"].shape))
    return batch


def compute_loss(cfg: RuntimeConfig, params, batch: dict, rng=None,
                 deterministic: bool = True, rope=None,
                 return_moe_stats: bool = False, mean=None):
    """Forward + masked LM loss for one microbatch.

    ``batch``: tokens [b,s], labels [b,s], loss_mask [b,s] (float weights —
    supports the instruction-tuning scalar-weighted masks of
    finetune.py:148-161), optional position_ids/segment_ids.
    ``return_moe_stats`` additionally returns the layer-summed MoE stats
    dict (models/moe.py) for routing observability.

    ``mean(per_token, mask)`` stands in for ``masked_mean_loss`` where
    the batch is one rank's slice of the microbatch, already in its cp
    layout (``BatchAxisSum``: the rank's share of the microbatch's mean).
    """
    # Fused linear+CE head: streams the unembedding matmul over vocab
    # blocks with an online logsumexp so the [b, s, vocab] fp32 logits are
    # never materialized — a large HBM saving when the head dominates.
    # Gated off under tp (vocab-sharded CE runs via GSPMD on the plain
    # path) and cp (flattening the cp-sharded seq would reshard).
    if mean is None:
        batch = zigzag_permute_batch(cfg, batch)

    fused_head = (cfg.model.fused_lm_head
                  and cfg.parallel.tensor_parallel == 1
                  and cfg.parallel.context_parallel == 1)
    if fused_head:
        from ..models.model import forward_hidden, unembed_weight
        from ..parallel.cross_entropy import fused_linear_cross_entropy

        hidden, moe_aux = forward_hidden(
            cfg.model, params, batch["tokens"],
            position_ids=batch.get("position_ids"),
            segment_ids=batch.get("segment_ids"),
            rng=rng, deterministic=deterministic, rope=rope,
        )
        b, s, h = hidden.shape
        # one fused op is both the head and its loss
        with jax.named_scope("lm_head"), jax.named_scope("cross_entropy"):
            per_token = fused_linear_cross_entropy(
                hidden.reshape(b * s, h), unembed_weight(cfg.model, params),
                batch["labels"].reshape(b * s), cfg.model.vocab_size,
            ).reshape(b, s)
    else:
        logits, moe_aux = model_lib.forward(
            cfg.model, params, batch["tokens"],
            position_ids=batch.get("position_ids"),
            segment_ids=batch.get("segment_ids"),
            rng=rng, deterministic=deterministic, rope=rope,
            return_aux=True,
        )
        per_token = cross_entropy(
            logits, batch["labels"], vocab_size=cfg.model.vocab_size
        )
    loss = (mean or masked_mean_loss)(per_token, batch["loss_mask"])
    if cfg.model.num_experts > 0:
        from ..models.moe import aux_loss_of

        loss = loss + cfg.model.moe_aux_loss_coeff * aux_loss_of(moe_aux)
    if return_moe_stats:
        return loss, moe_aux
    return loss


def spec_axes(spec) -> set:
    """Mesh axes a PartitionSpec names."""
    return {a for part in spec if part is not None
            for a in ((part,) if isinstance(part, str) else part)}


def batch_axes(mesh, batch_spec) -> tuple:
    """The mesh axes that split the batch: those ``batch_spec`` names, of
    size > 1, in the mesh's order."""
    named = spec_axes(batch_spec)
    return tuple(a for a in mesh.axis_names
                 if a in named and mesh.shape[a] > 1)


class BatchAxisSum(NamedTuple):
    """Where the gradient's sum over the ranks that split the batch is
    taken: after the microbatch loop, once a step.

    ``axes`` are the mesh axes of size > 1 that the batch sharding names
    (dp, and cp when it is on).  A parameter leaf whose spec names none of
    them is replicated there, so every such rank holds a partial gradient
    of it.  ``wrap`` runs the microbatch loop manual over ``axes`` (tp, ep
    and sp stay GSPMD's): each rank adds its own partial gradients up in
    fp32 with no gradient crossing ``axes`` in the loop, and after it each
    leaf is reduced once — a reduce-scatter onto the dim where
    ``grad_specs`` (the optimizer moments' specs: ``zero1_specs`` under the
    distributed optimizer, the parameter's own otherwise) names the axis,
    an all-reduce where it names none.  Left to GSPMD, the loop's carry
    has to hold a reduced gradient, which costs an all-reduce of every
    leaf in every microbatch.

    A rank sees its slice of a microbatch only, so the loss has to be one
    that cuts into rank shares: every mean it takes over the batch goes
    through the ``mean`` it is handed (``share_of_mean``), and samples meet
    nowhere else.  ``compute_loss`` is such a loss; a custom ``loss_fn``
    says so by taking a ``mean`` keyword (BERT, T5).
    """

    mesh: Any
    axes: tuple
    batch_spec: Any           # PartitionSpec of [accum, micro_batch, seq]
    param_specs: PyTree
    grad_specs: PyTree

    @classmethod
    def of(cls, cfg: RuntimeConfig, mesh, state_sharding, batch_sharding,
           loss_fn) -> Optional["BatchAxisSum"]:
        """From what the step is compiled with, or None where the loop
        stays GSPMD's: nothing splits the batch; the pipelined schedule
        (its own manual region); a loss that couples the ranks' samples —
        a ``loss_fn`` that takes no ``mean`` (ICT's in-batch negatives),
        or one whose sequences the batch axes cut (the cp layout is
        ``compute_loss``'s business), and MoE, whose auxiliary loss and
        capacity are functions of the whole microbatch's routing."""
        if (mesh is None or state_sharding is None
                or not hasattr(batch_sharding, "spec")
                or cfg.parallel.pipeline_parallel > 1
                or cfg.model.num_experts > 0):
            return None
        spec = batch_sharding.spec
        axes = batch_axes(mesh, spec)
        if loss_fn is not None and (
                "mean" not in inspect.signature(loss_fn).parameters
                or batch_axes(mesh, tuple(spec)[2:])):
            return None
        if not axes:
            return None

        def specs(shardings):
            return jax.tree.map(lambda s: s.spec, shardings)

        return cls(mesh, axes, spec, specs(state_sharding.params),
                   specs(state_sharding.opt.mu))

    def _manual(self, spec, keep=None):
        """``spec`` with only this region's manual axes left in it."""
        keep = set(self.axes) if keep is None else keep

        def part(p):
            names = tuple(a for a in ((p,) if isinstance(p, str)
                                      else (p or ())) if a in keep)
            return names[0] if len(names) == 1 else (names or None)

        return jax.sharding.PartitionSpec(*(part(p) for p in spec))

    def _scatter_dims(self, pspec, gspec) -> dict:
        """axis -> dim of a leaf on which that axis' sum is scattered."""
        todo = [a for a in self.axes if a not in spec_axes(pspec)]
        return {a: list(gspec).index(a) for a in todo if a in list(gspec)}

    def share_of_mean(self, per_sample, weights):
        """``masked_mean_loss`` on one rank's slice: its weighted sum over
        the whole microbatch's weight — the one batch-axis collective left
        in the loop, a scalar.  The ranks' shares add up to the mean."""
        weights = weights.astype(per_sample.dtype)
        return jnp.sum(per_sample * weights) / jnp.maximum(
            jax.lax.psum(jnp.sum(weights), self.axes), 1.0)

    def wrap(self, loop):
        """``loop(params, batch, rng, mean) -> (grad sums, loss sum)`` over
        one rank's slice of every microbatch, then the one sum over the
        ranks; takes and returns global arrays."""
        P = jax.sharding.PartitionSpec
        is_spec = lambda x: isinstance(x, P)  # noqa: E731

        def reduce_leaf(g, pspec, gspec):
            scatter = self._scatter_dims(pspec, gspec)
            for axis, dim in scatter.items():
                g = jax.lax.psum_scatter(g, axis, scatter_dimension=dim,
                                         tiled=True)
            rest = tuple(a for a in self.axes
                         if a not in spec_axes(pspec) and a not in scatter)
            return jax.lax.psum(g, rest) if rest else g

        def out_spec(pspec, gspec):
            own = spec_axes(pspec).intersection(self.axes)
            return self._manual(
                gspec, own.union(self._scatter_dims(pspec, gspec)))

        # The rank sum runs manual over the other axes too, on each
        # device's own tp / ep shard of the sums (the parameter's spec):
        # handed a reduce-scatter whose operand it shards itself, GSPMD
        # gathers the operand over tp first; handed the ranks' sums on a
        # leading axis to add up, it all-reduces them whole (PERF.md §6,
        # PR 28).
        others = set(self.mesh.axis_names).difference(self.axes)
        own_shard = jax.tree.map(lambda s: self._manual(s, others),
                                 self.param_specs, is_leaf=is_spec)

        def local(params, batch, rng):
            if rng is not None:
                # distinct dropout streams per rank (GSPMD got this from
                # sharding one global mask)
                for a in self.axes:
                    rng = jax.random.fold_in(rng, jax.lax.axis_index(a))
            grads, loss_sum = loop(params, batch, rng, self.share_of_mean)
            with jax.named_scope("grad_accum"):
                grads = jax.shard_map(
                    lambda g: jax.tree.map(reduce_leaf, g, self.param_specs,
                                           self.grad_specs),
                    in_specs=(own_shard,), out_specs=own_shard,
                    axis_names=others, check_vma=False)(grads)
            return grads, jax.lax.psum(loss_sum, self.axes)

        def run(params, batch, rng):
            fn = jax.shard_map(
                local, mesh=self.mesh,
                in_specs=(
                    jax.tree.map(self._manual, self.param_specs,
                                 is_leaf=is_spec),
                    {k: self._manual(P(*tuple(self.batch_spec)[:v.ndim]))
                     for k, v in batch.items()},
                    P()),
                out_specs=(jax.tree.map(out_spec, self.param_specs,
                                        self.grad_specs, is_leaf=is_spec),
                           P()),
                axis_names=set(self.axes), check_vma=False)
            grads, loss_sum = fn(params, batch, rng)
            # the tp / ep part of the target, which a manual region's
            # out_specs cannot name
            grads = jax.tree.map(
                lambda g, s: jax.lax.with_sharding_constraint(
                    g, jax.sharding.NamedSharding(self.mesh, s)),
                grads, self.grad_specs)
            return grads, loss_sum

        return run

    def cp_layout(self, cfg: RuntimeConfig, batch: dict) -> dict:
        """``compute_loss``'s batch as the manual region takes it.  A rank
        sees only its slice, so what is a function of the whole sequence
        is applied here: the zigzag cp permutation, and RoPE's positions
        where cp cuts the sequence."""
        batch = zigzag_permute_batch(cfg, batch)
        if (batch.get("position_ids") is None
                and batch_axes(self.mesh, tuple(self.batch_spec)[2:])):
            batch = dict(batch, position_ids=jnp.broadcast_to(
                jnp.arange(batch["tokens"].shape[-1], dtype=jnp.int32),
                batch["tokens"].shape))
        return {k: v for k, v in batch.items() if v is not None}


def _accumulate_grads(cfg: RuntimeConfig, params, batch, rng, rope,
                      loss_scale, loss_fn=None, rank_sum=None):
    """Scan microbatches, accumulating fp32 grads and the mean loss.

    ``batch`` leaves are [accum, micro_batch, ...].  ``loss_fn(cfg, params,
    microbatch, rng, deterministic)`` overrides the decoder-LM loss — the
    analogue of the reference's ``forward_step_func`` argument to
    ``pretrain`` (training.py:55), used by the BERT/T5 entry points.
    ``rank_sum`` (``BatchAxisSum``) moves the sum over the ranks that split
    the batch from every microbatch to the end of the loop.
    """
    accum = jax.tree.leaves(batch)[0].shape[0]
    want_moe = loss_fn is None and cfg.model.num_experts > 0

    def scaled_loss_fn(p, mb, mb_rng, mean=None):
        # (shared by the accum==1 fast path below)
        share = {} if mean is None else {"mean": mean}
        if loss_fn is not None:
            loss = loss_fn(cfg, p, mb, mb_rng, mb_rng is None, **share)
            stats = None
        elif want_moe:
            loss, stats = compute_loss(cfg, p, mb, rng=mb_rng,
                                       deterministic=(mb_rng is None),
                                       rope=rope, return_moe_stats=True)
        else:
            loss = compute_loss(cfg, p, mb, rng=mb_rng,
                                deterministic=(mb_rng is None), rope=rope,
                                **share)
            stats = None
        return loss * loss_scale, (loss, stats)

    grad_fn = jax.value_and_grad(scaled_loss_fn, has_aux=True)

    if accum == 1:
        # Single-microbatch fast path: the scan's fp32 zero-init + add
        # costs a full extra param-tree read/write per step and buys
        # nothing when there is only one gradient.  Cast once instead of
        # accumulate.
        mb = jax.tree.map(lambda x: x[0], batch)
        mb_rng = jax.random.fold_in(rng, 0) if rng is not None else None
        (_, (loss, stats)), grads = grad_fn(params, mb, mb_rng)
        grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
        moe_stats = None
        if stats is not None:
            norm = 1.0 / cfg.model.num_layers
            moe_stats = jax.tree.map(
                lambda s: jax.lax.stop_gradient(s) * norm, stats)
        return grads, loss, moe_stats

    stats0 = None
    if want_moe:
        from ..models.moe import stats_zero

        stats0 = stats_zero(cfg.model)

    def loop(params, batch, rng, mean=None):
        """The microbatch loop over ``batch`` — the whole of it, or inside
        ``rank_sum``'s region one rank's slice → (fp32 grad sums, loss
        sum, MoE stats sum)."""
        def body(carry, mb_and_idx):
            grads_acc, loss_acc, stats_acc = carry
            mb, idx = mb_and_idx
            mb_rng = (jax.random.fold_in(rng, idx) if rng is not None
                      else None)
            (_, (loss, stats)), grads = grad_fn(params, mb, mb_rng, mean)
            with jax.named_scope("grad_accum"):
                grads_acc = jax.tree.map(
                    lambda a, g: a + g.astype(jnp.float32), grads_acc, grads)
            if stats is not None:
                stats_acc = jax.tree.map(
                    lambda a, s: a + jax.lax.stop_gradient(s),
                    stats_acc, stats)
            return (grads_acc, loss_acc + loss, stats_acc), None

        zeros = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        carry, _ = jax.lax.scan(
            body, (zeros, jnp.zeros((), jnp.float32), stats0),
            (batch, jnp.arange(accum)))
        return carry

    if rank_sum is None:
        grads, loss_sum, stats_sum = loop(params, batch, rng)
    else:
        stats_sum = None
        if loss_fn is None:
            batch = rank_sum.cp_layout(cfg, batch)
        grads, loss_sum = rank_sum.wrap(
            lambda *a: loop(*a)[:2])(params, batch, rng)
    inv = 1.0 / accum
    grads = jax.tree.map(lambda g: g * inv, grads)
    # normalize layer-and-microbatch sums to per-layer means
    moe_stats = None
    if stats_sum is not None:
        norm = 1.0 / (accum * cfg.model.num_layers)
        moe_stats = jax.tree.map(lambda s: s * norm, stats_sum)
    return grads, loss_sum * inv, moe_stats


def _pipeline_grads(cfg: RuntimeConfig, params, batch, rng, rope,
                    loss_scale, mesh, pipeline_loss_fn=None):
    """Grads via a pipelined schedule when pp > 1 — the decoder-LM ring
    (parallel/pipeline.py) by default, or a family-specific schedule via
    ``pipeline_loss_fn`` (parallel/pipeline_encdec.py).

    The microbatch loop *is* the pipeline here — one differentiable program
    whose jax.grad is the backward pipeline (reference: schedules.py:606-722
    drives backward through autograd send/recv hooks instead).
    """
    if pipeline_loss_fn is None:
        from ..parallel import pipeline as pipe

        def loss_of(p32):
            return pipe.pipeline_loss(cfg, p32, batch, mesh=mesh, rng=rng,
                                      rope=rope)
    else:
        def loss_of(p32):
            return pipeline_loss_fn(cfg, p32, batch, mesh=mesh, rng=rng)

    def scaled_loss(p32):
        loss = loss_of(p32)
        return loss * loss_scale, loss

    # Differentiate w.r.t. an fp32 view: the pipelined losses cast to
    # compute dtype at each per-tick use site, so the scan transposes
    # accumulate weight cotangents across microbatches in fp32 — the same
    # invariant _accumulate_grads keeps via its per-microbatch fp32 sum.
    params32 = jax.tree.map(lambda p: p.astype(jnp.float32), params)
    (_, loss), grads = jax.value_and_grad(scaled_loss, has_aux=True)(params32)
    return grads, loss


def train_step(cfg: RuntimeConfig, state: TrainState, batch: dict,
               base_rng: Optional[jax.Array] = None, rope=None, mesh=None,
               loss_fn=None, pipeline_loss_fn=None, rank_sum=None):
    """One optimizer step over ``grad_accum`` microbatches.

    Returns (new_state, metrics).  Donate ``state`` when jitting.

    ``pipeline_loss_fn(cfg, params, batch, mesh=, rng=)`` supplies a
    family-specific pipelined schedule for pp > 1 (the encoder-decoder
    split-rank pipelines of parallel/pipeline_encdec.py); without it pp > 1
    uses the decoder-LM pipeline of parallel/pipeline.py.
    """
    if (loss_fn is not None and cfg.parallel.pipeline_parallel > 1
            and pipeline_loss_fn is None):
        raise NotImplementedError(
            "custom loss_fn is not supported with pipeline parallelism "
            "(pass pipeline_loss_fn for the encdec families)")
    if loss_fn is not None and cfg.model.context_parallel_zigzag:
        # the zigzag batch permutation lives in compute_loss; a custom loss
        # would silently run zigzag attention on natural-order tokens
        raise NotImplementedError(
            "custom loss_fn is not supported with the zigzag cp layout")
    train_iters = cfg.train.train_iters
    it = state.iteration
    rng = None
    if base_rng is not None:
        rng = jax.random.fold_in(base_rng, it)

    scaler = state.opt.scaler
    loss_scale = scaler.scale if scaler is not None else jnp.float32(1.0)

    moe_stats = None
    if cfg.parallel.pipeline_parallel > 1:
        # MoE routing stats are not fanned out of the pipelined schedule —
        # only the aux loss crosses the shard_map boundary
        grads, loss = _pipeline_grads(cfg, state.params, batch, rng, rope,
                                      loss_scale, mesh, pipeline_loss_fn)
    else:
        grads, loss, moe_stats = _accumulate_grads(
            cfg, state.params, batch, rng, rope, loss_scale, loss_fn,
            rank_sum)
    # unscale (reference: optimizer.py:384-404 unscale-and-check-inf)
    grads = jax.tree.map(lambda g: g / loss_scale, grads)
    grad_norm = opt_lib.global_grad_norm(grads)
    found_inf = ~jnp.isfinite(grad_norm)

    # Anomaly defense (resilience/anomaly.py): widen the skip condition
    # from non-finite grads to non-finite loss and EWMA loss spikes, and
    # track the consecutive-data-anomaly run the driver's rollback watches.
    guard_new, anomalous, data_anomaly = guard_update(
        state.guard, loss, found_inf,
        z_threshold=cfg.train.anomaly_z_threshold,
        alpha=cfg.train.anomaly_ewma_alpha,
        warmup_steps=cfg.train.anomaly_warmup_steps)

    if cfg.optimizer.clip_grad > 0:
        grads, _ = opt_lib.clip_by_global_norm(
            grads, cfg.optimizer.clip_grad, norm=grad_norm)

    # Schedules advance with *successful* updates only (reference steps the
    # opt_param_scheduler inside `if update_successful`, training.py:439-446),
    # so warmup is not consumed by loss-scale-overflow skips.
    sched_it = state.opt.step
    lr = schedule.learning_rate(cfg.optimizer, sched_it, train_iters)
    wd = schedule.weight_decay(cfg.optimizer, sched_it, train_iters)

    # Skipped-iteration semantics on any anomalous step — non-finite grads
    # (reference: optimizer/optimizer.py:418-432), non-finite loss, or an
    # EWMA loss spike: keep params & moments bitwise.
    def pick(new, old):
        return jax.tree.map(
            lambda n, o: jnp.where(anomalous, o, n), new, old)

    # one scope over the update and its undoing: XLA fuses the two, and a
    # fusion carries the name of its root (the select)
    with jax.named_scope("optimizer"):
        new_params, new_opt = opt_lib.optimizer_step(
            cfg.optimizer, state.params, grads, state.opt, lr, wd)
        new_params = pick(new_params, state.params)
        new_opt = opt_lib.OptState(
            step=jnp.where(anomalous, state.opt.step, new_opt.step),
            mu=pick(new_opt.mu, state.opt.mu),
            nu=pick(new_opt.nu, state.opt.nu),
            master=(pick(new_opt.master, state.opt.master)
                    if state.opt.master is not None else None),
            # the loss scaler reacts to overflow only — a data anomaly
            # says nothing about the fp16 dynamic range
            scaler=(opt_lib.scaler_update(scaler, found_inf, cfg.optimizer)
                    if scaler is not None else None),
        )

    new_state = TrainState(
        params=new_params,
        opt=new_opt,
        iteration=it + 1,
        skipped=state.skipped + anomalous.astype(jnp.int32),
        guard=guard_new,
    )
    metrics = {
        "loss": loss,
        "grad_norm": grad_norm,
        "lr": lr,
        "weight_decay": wd,
        "skipped": anomalous.astype(jnp.int32),
        "anomaly": data_anomaly.astype(jnp.int32),
        "anomaly_run": guard_new.run,
        "loss_scale": loss_scale,
    }
    if moe_stats is not None:
        # dropped: mean fraction of (token, choice) assignments lost to
        # capacity overflow; imbalance: E·max(f_e) — 1.0 when perfectly
        # balanced (capacity-factor tuning signals, VERDICT weak #8)
        E = cfg.model.num_experts
        load = moe_stats["load"]
        metrics["moe_dropped_frac"] = moe_stats["dropped"]
        metrics["moe_load_imbalance"] = (
            E * jnp.max(load) / jnp.maximum(jnp.sum(load), 1e-9))
        metrics["moe_aux_loss"] = moe_stats["aux"]
    return new_state, metrics


def make_train_step(cfg: RuntimeConfig, mesh=None, state_sharding=None,
                    batch_sharding=None, loss_fn=None,
                    pipeline_loss_fn=None):
    """jit-compile ``train_step`` with donated state.

    RoPE tables are closed over as constants (computed once, not per step —
    the reference precomputes freqs_cis at model build,
    megatron/model/positional_embeddings.py).
    """
    rope = rope_tables(cfg.model)
    rank_sum = BatchAxisSum.of(cfg, mesh, state_sharding, batch_sharding,
                               loss_fn)

    def step(state, batch, base_rng):
        # Establish the mesh context at *trace* time: mesh-needing ops
        # inside the model (ring attention's shard_map) resolve it via
        # parallel.mesh.current_mesh(), and jit may trace this function
        # long after the caller's `use_mesh` block has exited.
        import contextlib

        from ..parallel import mesh as mesh_lib

        ctx = (mesh_lib.use_mesh(mesh) if mesh is not None
               else contextlib.nullcontext())
        with ctx:
            return train_step(cfg, state, batch, base_rng, rope=rope,
                              mesh=mesh, loss_fn=loss_fn,
                              pipeline_loss_fn=pipeline_loss_fn,
                              rank_sum=rank_sum)

    kwargs = {}
    if state_sharding is not None:
        kwargs["in_shardings"] = (state_sharding, batch_sharding, None)
        kwargs["out_shardings"] = (state_sharding, None)
    return jax.jit(step, donate_argnums=(0,), **kwargs)
