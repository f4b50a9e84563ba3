"""Training orchestration: the ``pretrain()`` driver.

TPU-native counterpart of megatron/training.py:55-961:
- ``setup_train_state``   ← get_model + get_megatron_optimizer + load_checkpoint
  (training.py:199-304, 353-391): builds the mesh-sharded TrainState with
  ZeRO-1 optimizer-state specs and the jitted train step
- ``pretrain``            ← pretrain + _train (training.py:55-169, 654-770):
  data iterators, train loop, logging, eval/save/exit hooks, SIGTERM
  checkpointing, batch-size rampup, consumed-samples resume
- ``evaluate``            ← evaluate + evaluate_and_print_results
  (training.py:773-876) with the pluggable metrics registry (metrics.py)
- ``training_log``        ← training.py:462-641: loss/lr/norm/skip logging,
  tokens-per-second counter (finetune.py:124-135) and per-phase timers

Host/device split: the device state (params, moments, iteration) lives in the
jitted step; host state (consumed_samples, wall-clock, signal flags, the
microbatch calculator) lives here — matching the reference's division between
CUDA tensors and the args namespace.
"""

from __future__ import annotations

import datetime
import signal
import sys
import time
from typing import Any, Callable, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import checkpointing, metrics as metrics_lib
from ..config import RuntimeConfig
from ..data.samplers import BatchIterator
from ..models import model as model_lib
from ..models import sharding as shard_lib
from ..models.transformer import rope_tables
from ..obs import compile as obs_compile
from ..obs import profile as obs_profile
from ..obs.logging import EVENT_LOG
from ..obs.trace import TRAIN_TRACE
from ..obs.registry import REGISTRY as obs_registry
from ..parallel import mesh as mesh_lib
from ..parallel.cross_entropy import cross_entropy, masked_mean_loss
from ..resilience import chaos, guard_spec
from ..utils.timers import Timers
from ..utils.writers import NullWriter, build_writer
from . import optimizer as opt_lib
from .microbatches import build_num_microbatches_calculator
from .step import (TrainState, batch_axes, init_train_state,
                   make_train_step)

PyTree = Any


def print_rank_0(*args, **kwargs):
    """Reference rank-printing discipline (megatron/utils.py:197-228); under
    multi-controller JAX, process 0 speaks."""
    if jax.process_index() == 0:
        print(*args, **kwargs, flush=True)


# ---------------------------------------------------------------------------
# SIGTERM checkpointing (reference: megatron/dist_signal_handler.py:50-81,
# training.py:731-737)
# ---------------------------------------------------------------------------


class DistSignalHandler:
    """Capture a signal and expose cluster-consensus receipt.

    The reference all-gathers per-rank receipt flags so every rank agrees to
    checkpoint; with multi-controller JAX each process polls its local flag
    and agreement comes from ``process_allgather`` when more than one
    process exists.
    """

    def __init__(self, sig: Optional[int] = signal.SIGTERM):
        self.sig = sig               # None (no such signal here): inert
        self._received = False
        self._prev = None

    def __enter__(self):
        def handler(signum, frame):
            self._received = True

        if self.sig is not None:
            self._prev = signal.signal(self.sig, handler)
        return self

    def __exit__(self, *exc):
        if self._prev is not None:
            signal.signal(self.sig, self._prev)
        return False

    def signals_received(self) -> bool:
        return _cluster_any(self._received)

    def take_local(self) -> bool:
        """This process's own flag, cleared: for a signal that asks one
        process for something and needs no agreement."""
        got, self._received = self._received, False
        return got


def _cluster_any(local_flag: bool) -> bool:
    """True iff any process observed the flag — the analogue of the
    reference's all-reduce-MAX exit flags (training.py:745-767), so every
    host takes the same branch and no collective is left half-entered."""
    if jax.process_count() == 1:
        return bool(local_flag)
    try:
        from jax.experimental import multihost_utils

        flags = multihost_utils.process_allgather(np.asarray([local_flag]))
        return bool(np.any(flags))
    except Exception as e:
        # A degraded collective must NOT silently fall back to the local
        # flag: per-host decisions are exactly the half-entered-collective
        # hang this consensus exists to prevent.  Fail loudly instead.
        raise RuntimeError(
            "multi-host consensus allgather failed; refusing to fall back "
            "to a per-host decision (hosts would diverge and deadlock the "
            "next collective)") from e


# ---------------------------------------------------------------------------
# State construction (reference get_model + optimizer setup,
# training.py:199-391)
# ---------------------------------------------------------------------------


class TrainingArtifacts:
    """Everything ``pretrain`` needs per run: sharded state + jitted step."""

    def __init__(self, cfg, mesh, state, state_sharding, batch_sharding,
                 step_fn, param_specs):
        self.cfg = cfg
        self.mesh = mesh
        self.state = state
        self.state_sharding = state_sharding
        self.batch_sharding = batch_sharding
        self.step_fn = step_fn
        self.param_specs = param_specs


def setup_train_state(
    cfg: RuntimeConfig,
    mesh=None,
    init_rng: Optional[jax.Array] = None,
    params: Optional[PyTree] = None,
) -> TrainingArtifacts:
    """Build mesh-sharded TrainState + jitted step for ``cfg``.

    Mirrors _setup_model_and_optimizer (training.py:353-391): model init (or
    externally supplied params, e.g. from an HF conversion), sharding
    placement, optimizer-state init with ZeRO-1 dp specs, jit compile.
    """
    if cfg.model.kv_lora_rank:
        raise ValueError(
            f"a latent-attention stack (kv_lora_rank "
            f"{cfg.model.kv_lora_rank}) is served, not trained: the "
            "training step runs neither its dropless experts nor the "
            "leading dense layers kept beside the scanned stack, and no "
            "sharding rule places the latent projections (ROADMAP R4)")
    parallel = cfg.parallel
    if mesh is None:
        mesh = mesh_lib.build_mesh(parallel)
    if init_rng is None:
        init_rng = jax.random.key(cfg.train.seed)

    with mesh_lib.use_mesh(mesh):
        from ..parallel import pipeline as pipe_lib

        if params is None:
            params = model_lib.init_params(
                init_rng, cfg.model, tp=parallel.tensor_parallel)
        pspecs = shard_lib.param_specs(cfg.model, parallel)
        if parallel.pipeline_parallel > 1:
            params = pipe_lib.to_pipeline_params(params, parallel)
            pspecs = pipe_lib.pipeline_param_specs(pspecs, parallel)
        state, state_sharding = _shard_train_state(cfg, mesh, params, pspecs)
        # [accum, micro_batch, seq] leaves: batch over dp, seq over cp (the
        # cp axis is size 1 unless context parallelism is on).
        batch_sharding = NamedSharding(mesh, P(None, "dp", "cp"))

        # batch sharding is a pytree prefix: one sharding broadcast over
        # whatever keys the batch dict carries
        step_fn = make_train_step(cfg, mesh, state_sharding, batch_sharding)
    return TrainingArtifacts(cfg, mesh, state, state_sharding, batch_sharding,
                             step_fn, pspecs)


def grad_collectives_of(art: TrainingArtifacts,
                        global_batch_size: int) -> Optional[dict]:
    """What ``art.step_fn``, compiled for ``global_batch_size``, does with
    the gradients across the ranks that split the batch
    (obs/collectives.py:grad_collectives): how many reductions a step run
    inside the microbatch loop and after it, their kind and bytes.  None
    where nothing splits the batch, and for the pipelined schedule (the
    microbatch loop is the pipeline there).  The step is compiled under
    the mesh context the train loop calls it in, for the batch the loop
    will hand it: jit's cache is keyed on both, so the first train step
    finds this very executable and the reading costs nothing but the
    compile's being done early (another batch structure, extra keys say,
    compiles once more)."""
    from ..obs import collectives

    mesh, cfg = art.mesh, art.cfg
    axes = batch_axes(mesh, art.batch_sharding.spec)
    if not axes or cfg.parallel.pipeline_parallel > 1:
        return None
    accum = global_batch_size // (
        cfg.train.micro_batch_size * cfg.parallel.data_parallel)
    shape = (accum, global_batch_size // accum, cfg.train.seq_length)
    batch = {k: jax.ShapeDtypeStruct(shape, dt, sharding=art.batch_sharding)
             for k, dt in (("tokens", jnp.int32), ("labels", jnp.int32),
                           ("loss_mask", jnp.float32))}
    state = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        art.state, art.state_sharding)
    with mesh:
        hlo = art.step_fn.lower(
            state, batch,
            jax.eval_shape(jax.random.key, 0)).compile().as_text()
    try:
        return collectives.grad_collectives(
            hlo, dict(mesh.shape), axes,
            collectives.param_shard_shapes(art.state.params,
                                           art.state_sharding.params), accum)
    except Exception as e:  # noqa: BLE001
        # an HLO form the reader has not met costs a log line, not the run
        return {"unreadable": f"{type(e).__name__}: {e}"}


def _shard_train_state(cfg: RuntimeConfig, mesh, params: PyTree,
                       pspecs: PyTree):
    """Shard params + fresh optimizer state (incl. ZeRO-1 dp specs when
    enabled) onto ``mesh`` → (state, state_sharding).  Single home for the
    sequence shared by setup_train_state and pretrain_custom."""
    params = shard_lib.shard_params(params, pspecs, mesh)
    state = init_train_state(cfg, params)
    ospecs = opt_lib.opt_state_specs(pspecs, params, cfg.parallel, state.opt)
    state_spec = TrainState(
        params=pspecs, opt=ospecs, iteration=P(), skipped=P(),
        guard=guard_spec())
    state_sharding = jax.tree.map(
        lambda s: NamedSharding(mesh, s), state_spec,
        is_leaf=lambda x: isinstance(x, P))
    state = jax.tree.map(
        lambda x, s: jax.device_put(x, s), state, state_sharding)
    return _dedupe_buffers(state), state_sharding


def _put_batch(batch: dict, sharding) -> dict:
    return {k: jax.device_put(jnp.asarray(v), sharding)
            for k, v in batch.items()}


def _dedupe_buffers(state: TrainState) -> TrainState:
    """Materialize distinct buffers for the freshly-zeroed optimizer leaves.

    The backend can deduplicate identical eagerly-created constants (the
    same-shape zero moment/scaler/counter leaves) into one buffer, and
    donation rejects a buffer appearing twice in a call.  Copying exactly
    those leaves allocates only memory the train state needs anyway;
    params and the fp32 master copies (unique, never aliased) are left
    untouched, so peak HBM does not grow.
    """
    def cp(t):
        if t is None:
            return None
        return jax.tree.map(lambda x: jnp.array(x, copy=True), t)

    return state._replace(
        opt=state.opt._replace(
            step=cp(state.opt.step),
            mu=cp(state.opt.mu),
            nu=cp(state.opt.nu),
            scaler=cp(state.opt.scaler),
        ),
        iteration=cp(state.iteration),
        skipped=cp(state.skipped),
        guard=cp(state.guard),
    )


# ---------------------------------------------------------------------------
# Evaluation (reference evaluate, training.py:773-826; metrics wired like
# finetune.py:206-211)
# ---------------------------------------------------------------------------


def make_eval_step(cfg: RuntimeConfig, metric_names=(), mesh=None,
                   batch_sharding=None, param_specs=None):
    """Jitted forward-only step returning lm loss + registry metrics."""
    metrics_lib.validate_metric_names(metric_names)
    rope = rope_tables(cfg.model)

    def eval_step(params, batch):
        # Mesh context at trace time — ring attention under cp resolves the
        # mesh via parallel.mesh.current_mesh() (same dance as
        # make_train_step; jit may trace long after the caller's block).
        import contextlib

        from .step import zigzag_permute_batch

        batch = zigzag_permute_batch(cfg, batch)
        ctx = (mesh_lib.use_mesh(mesh) if mesh is not None
               else contextlib.nullcontext())
        with ctx:
            logits = model_lib.forward(
                cfg.model, params, batch["tokens"],
                position_ids=batch.get("position_ids"),
                segment_ids=batch.get("segment_ids"),
                deterministic=True, rope=rope,
            )
        per_token = cross_entropy(
            logits, batch["labels"], vocab_size=cfg.model.vocab_size)
        loss = masked_mean_loss(per_token, batch["loss_mask"])
        out = {"lm_loss": loss}
        out.update(metrics_lib.compute_metrics(
            metric_names, batch, logits, per_token))
        return out

    kwargs = {}
    if param_specs is not None and mesh is not None:
        in_sharding = jax.tree.map(
            lambda s: NamedSharding(mesh, s), param_specs,
            is_leaf=lambda x: isinstance(x, P))
        kwargs["in_shardings"] = (in_sharding, batch_sharding)
    return jax.jit(eval_step, **kwargs)


def make_pipeline_eval_step(cfg: RuntimeConfig, mesh, metric_names=()):
    """Forward-only loss + registry metrics via the pipelined schedule for
    pp > 1.  The streamed pipeline head (parallel/pipeline.py) emits
    per-token fp32 loss and argmax-correctness stats from the last stage, so
    every registry metric works at any parallelism — matching the reference
    (megatron/metrics.py:62-110 computes metrics wherever logits land)."""
    from ..parallel import pipeline as pipe

    metrics_lib.validate_metric_names(metric_names)
    rope = rope_tables(cfg.model)

    def eval_step(params, batch):
        if not metric_names:
            # no registry metrics requested: skip the per-tick argmax and
            # the [M, mb, s] stat buffers entirely
            loss = pipe.pipeline_loss(cfg, params, batch, mesh=mesh,
                                      rng=None, rope=rope)
            return {"lm_loss": loss}
        loss, stats = pipe.pipeline_loss(
            cfg, params, batch, mesh=mesh, rng=None, rope=rope,
            return_stats=True)
        out = {"lm_loss": loss}

        # flatten [M, mb, ...] → [M*mb, ...]: metrics are per-token
        # reductions, invariant to the microbatch grouping
        def flat(v):
            return jnp.reshape(v, (-1,) + v.shape[2:])

        flat_batch = {k: flat(v) for k, v in batch.items()
                      if v is not None}
        out.update(metrics_lib.compute_metrics(
            metric_names, flat_batch, None,
            flat(stats["per_token_loss"]),
            correct=flat(stats["correct"])))
        return out

    return jax.jit(eval_step)


def evaluate(cfg: RuntimeConfig, params, data_iterator, eval_step,
             eval_iters: Optional[int] = None,
             batch_sharding=None, flatten: bool = True) -> dict[str, float]:
    """Average eval metrics over ``eval_iters`` batches
    (reference training.py:773-826).  ``flatten=False`` keeps the
    [accum, micro, ...] layout for the pipelined eval step."""
    if eval_iters is None:
        eval_iters = cfg.train.eval_iters
    totals: dict[str, float] = {}
    n = 0
    for _ in range(eval_iters):
        try:
            batch = next(data_iterator)
        except StopIteration:
            break
        if flatten:
            # [accum, micro, ...] → [accum*micro, ...] for the plain
            # forward-only step
            flat = {k: np.reshape(v, (-1,) + v.shape[2:])
                    for k, v in batch.items()}
        else:
            flat = batch
        if batch_sharding is not None:
            flat = {k: jax.device_put(jnp.asarray(v), batch_sharding)
                    for k, v in flat.items()}
        out = eval_step(params, flat)
        out = jax.device_get(out)
        for k, v in out.items():
            totals[k] = totals.get(k, 0.0) + float(v)
        n += 1
    return {k: v / max(n, 1) for k, v in totals.items()}


def evaluate_and_print_results(prefix: str, cfg, params, data_iterator,
                               eval_step, writer=None, iteration: int = 0,
                               batch_sharding=None,
                               flatten: bool = True) -> dict[str, float]:
    """Reference evaluate_and_print_results (training.py:829-876)."""
    results = evaluate(cfg, params, data_iterator, eval_step,
                       batch_sharding=batch_sharding, flatten=flatten)
    string = f" validation loss at {prefix} | "
    for k, v in results.items():
        string += f"{k}: {v:.6E} | "
        if writer is not None:
            writer.add_scalar(f"valid/{k}", v, iteration)
        if k == "lm_loss":
            ppl = float(np.exp(min(20.0, v)))
            string += f"lm loss PPL: {ppl:.6E} | "
            if writer is not None:
                writer.add_scalar("valid/lm_loss_ppl", ppl, iteration)
    length = len(string) + 1
    print_rank_0("-" * length)
    print_rank_0(string)
    print_rank_0("-" * length)
    return results


# ---------------------------------------------------------------------------
# Logging (reference training_log, training.py:462-641)
# ---------------------------------------------------------------------------


class _LogState:
    def __init__(self):
        self.total_loss = 0.0
        self.count = 0
        self.skipped_total = 0
        self.anomaly_total = 0
        self.tokens = 0
        self.t_start = time.perf_counter()
        # set-up facts that ride on the first log_window event
        self.once: dict = {}
        # the compilation records' sequence number at the last event
        self.compile_seq = obs_compile.COMPILES.seq

    def reset_window(self):
        self.total_loss = 0.0
        self.count = 0
        self.tokens = 0
        self.t_start = time.perf_counter()


def training_log(cfg: RuntimeConfig, log: _LogState, metrics: dict,
                 iteration: int, consumed_samples: int, writer,
                 timers: Timers) -> None:
    loss = float(metrics["loss"])
    anomalous = bool(int(metrics.get("anomaly", 0)))
    if anomalous:
        # an anomalous step's loss (possibly NaN) must not poison the
        # logged window average; the event is counted instead
        log.anomaly_total += 1
        metrics_lib.RESILIENCE_EVENTS.inc("anomalies")
    else:
        log.total_loss += loss
        log.count += 1
    log.skipped_total += int(metrics["skipped"])

    if (not cfg.train.log_interval
            or iteration % cfg.train.log_interval != 0):
        return
    elapsed = time.perf_counter() - log.t_start
    per_iter = elapsed / max(log.count, 1)
    tokens_per_sec = log.tokens / elapsed if elapsed > 0 else 0.0
    flops = model_lib.flops_per_token(cfg.model, cfg.train.seq_length)
    tflops = tokens_per_sec * flops / 1e12

    avg_loss = log.total_loss / max(log.count, 1)
    lr = float(metrics["lr"])
    grad_norm = float(metrics["grad_norm"])
    loss_scale = float(metrics.get("loss_scale", 1.0))

    line = (
        f" iteration {iteration:8d}/{cfg.train.train_iters:8d} |"
        f" consumed samples: {consumed_samples:12d} |"
        f" elapsed time per iteration (ms): {per_iter * 1000.0:.1f} |"
        f" tokens per second: {tokens_per_sec:.1f} |"
        f" model TFLOPs: {tflops:.1f} |"
        f" learning rate: {lr:.3E} |"
        f" lm loss: {avg_loss:.6E} |"
        f" loss scale: {loss_scale:.1f} |"
        f" grad norm: {grad_norm:.3f} |"
        f" number of skipped iterations: {log.skipped_total:3d} |"
        f" number of anomalous iterations: {log.anomaly_total:3d} |"
    )
    if "moe_dropped_frac" in metrics:
        line += (
            f" moe dropped frac: {float(metrics['moe_dropped_frac']):.4f} |"
            f" moe load imbalance: "
            f"{float(metrics['moe_load_imbalance']):.3f} |")
    print_rank_0(line)
    # shared obs registry (GET /metrics?format=prometheus serves these
    # next to the serving and resilience metrics) + one structured JSON
    # log line per window with the same fields the console line carries
    obs_registry.gauge(
        "training_iteration", "current training iteration").set(iteration)
    obs_registry.gauge(
        "training_tokens_per_sec",
        "training throughput over the last log window").set(tokens_per_sec)
    obs_registry.gauge(
        "training_lm_loss", "window-averaged LM loss").set(avg_loss)
    obs_registry.gauge(
        "training_learning_rate", "current learning rate").set(lr)
    obs_registry.gauge(
        "training_grad_norm", "last step's gradient norm").set(grad_norm)
    obs_registry.gauge(
        "training_consumed_samples",
        "samples consumed since the start of the run").set(consumed_samples)
    obs_registry.gauge(
        "training_anomalous_iterations",
        "anomalous (skipped-loss) iterations so far").set(log.anomaly_total)
    obs_registry.histogram(
        "training_step_time_seconds",
        "per-iteration wall time over log windows",
        buckets=(0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0,
                 10.0, 30.0, 60.0)).observe(per_iter)
    # the executables built or loaded since the last event, by program:
    # which step recompiled is a grep for "compiles"
    log.compile_seq, compiles = obs_compile.COMPILES.executables_since(
        log.compile_seq)
    if compiles:
        log.once["compiles"] = compiles
    EVENT_LOG.emit(
        "training", "log_window", iteration=iteration,
        consumed_samples=consumed_samples, lm_loss=round(avg_loss, 6),
        tokens_per_sec=round(tokens_per_sec, 3),
        step_time_s=round(per_iter, 6), learning_rate=lr,
        grad_norm=round(grad_norm, 6), skipped=log.skipped_total,
        anomalies=log.anomaly_total, **log.once)
    log.once = {}
    if writer is not None:
        if "moe_dropped_frac" in metrics:
            writer.add_scalar("train/moe_dropped_frac",
                              float(metrics["moe_dropped_frac"]), iteration)
            writer.add_scalar("train/moe_load_imbalance",
                              float(metrics["moe_load_imbalance"]),
                              iteration)
            writer.add_scalar("train/moe_aux_loss",
                              float(metrics["moe_aux_loss"]), iteration)
        writer.add_scalar("train/lm_loss", avg_loss, iteration)
        writer.add_scalar("train/learning_rate", lr, iteration)
        writer.add_scalar("train/grad_norm", grad_norm, iteration)
        writer.add_scalar("train/loss_scale", loss_scale, iteration)
        writer.add_scalar("train/tokens_per_sec", tokens_per_sec, iteration)
        writer.add_scalar("train/consumed_samples", consumed_samples,
                          iteration)
        writer.add_scalar("train/anomalous_iterations", log.anomaly_total,
                          iteration)
        metrics_lib.RESILIENCE_EVENTS.write(writer, iteration)
        timers.write(writer, iteration, reset=False)
    timers.log(normalizer=max(log.count, 1),
               printer=print if jax.process_index() == 0 else None)
    log.reset_window()


# ---------------------------------------------------------------------------
# The driver (reference pretrain + _train, training.py:55-169,654-770)
# ---------------------------------------------------------------------------

# steps traced when a running job gets SIGUSR1 (into cfg.train.profile_dir)
SIGNAL_TRACE_STEPS = 3


class _StepTrace:
    """The loop's side of a step-trace request: opens the profile session
    when a request falls due, closes it after the request's last step."""

    def __init__(self):
        self.until = None            # last iteration of the open trace

    def poll(self, next_it: int) -> None:
        """Top of an iteration, on the normal and the skip path alike."""
        if self.until is not None:
            return
        req = obs_profile.take_step_request(next_it)
        if req is None:
            return
        try:
            obs_profile.start(req.dir)
        except RuntimeError as e:    # someone else's session is running
            print_rank_0(f" profiler: request dropped ({e})")
            return
        self.until = next_it + req.steps - 1
        print_rank_0(f" profiler: tracing iterations {next_it}.."
                     f"{self.until} -> {req.dir}")

    def done(self, it: int) -> None:
        if self.until is not None and it >= self.until:
            self.close("window complete")

    def close(self, reason: str = "closed at loop exit") -> None:
        if self.until is not None:
            self.until = None
            obs_profile.stop()
            print_rank_0(f" profiler: trace written ({reason})")


def _build_train_iterator(cfg: RuntimeConfig, dataset, consumed_samples: int,
                          global_batch_size: int, shuffle: bool,
                          eod_token=None) -> Iterator[dict]:
    accum = global_batch_size // (
        cfg.train.micro_batch_size * cfg.parallel.data_parallel)
    it = BatchIterator(
        dataset,
        global_batch_size=global_batch_size,
        grad_accum=accum,
        seq_length=cfg.train.seq_length,
        consumed_samples=consumed_samples,
        shuffle=shuffle,
        seed=cfg.train.seed,
        eod_token=eod_token,
    )

    def checked():
        """Validate the first batch's token range once: out-of-vocab ids
        don't crash XLA gathers the way they assert on CUDA — they yield a
        silent NaN loss with finite-looking grad norms, which costs users
        hours to trace back to the corpus/tokenizer mismatch."""
        vocab = cfg.model.vocab_size
        first = True
        for batch in it:
            if first:
                first = False
                hi = int(batch["tokens"].max())
                lo = int(batch["tokens"].min())
                if hi >= vocab or lo < 0:
                    raise ValueError(
                        f"dataset token ids span [{lo}, {hi}] but "
                        f"model vocab_size is {vocab}: the corpus was "
                        f"tokenized with a different vocabulary than the "
                        f"model config (this would train to a NaN loss)")
            yield batch

    return checked()


class _PersistentEvalIterator:
    """Validation batches that advance across eval hooks instead of
    restarting at sample 0 each time (every eval would otherwise score the
    same leading batches; the reference advances one persistent valid
    iterator for the whole run, training.py:877-961).  Wraps to the top of
    the valid set on exhaustion; rebuilds position-preserving when batch
    rampup changes the global batch size."""

    def __init__(self, cfg, dataset, eod_token):
        self.cfg, self.dataset, self.eod = cfg, dataset, eod_token
        self.consumed = 0
        self._gbs = None
        self._it = None

    def iterator(self, gbs: int) -> "_PersistentEvalIterator":
        if self._it is None or gbs != self._gbs:
            self._gbs = gbs
            self._it = _build_train_iterator(
                self.cfg, self.dataset, self.consumed, gbs, False, self.eod)
        return self

    def __iter__(self):
        return self

    def __next__(self):
        try:
            batch = next(self._it)
        except StopIteration:
            self.consumed = 0
            self._it = _build_train_iterator(
                self.cfg, self.dataset, 0, self._gbs, False, self.eod)
            batch = next(self._it)  # empty valid set → StopIteration out
        self.consumed += self._gbs
        return batch


def pretrain(
    cfg: RuntimeConfig,
    train_dataset=None,
    valid_dataset=None,
    test_dataset=None,
    params: Optional[PyTree] = None,
    batch_provider: Optional[Callable[[int, int], Iterator[dict]]] = None,
    shuffle: bool = True,
    eod_token: Optional[int] = None,
) -> TrainState:
    """Train ``cfg.train.train_iters`` iterations; returns the final state.

    ``batch_provider(consumed_samples, global_batch_size)`` overrides the
    dataset-based iterator (the reference's ``train_valid_test_dataset
    provider`` indirection, training.py:877-961).
    """
    cfg.validate()
    t_start = time.time()
    obs_compile.install()
    # the loop's timers are also its spans (obs/trace.py:TRAIN_TRACE)
    timers = Timers(spans=TRAIN_TRACE)
    writer = NullWriter()
    if jax.process_index() == 0:
        writer = build_writer(cfg.train.tensorboard_dir,
                              cfg.train.wandb_project, cfg.train.wandb_name,
                              config=cfg.to_dict())

    timers("setup", log_level=0).start()
    art = setup_train_state(cfg, params=params)
    state = art.state

    # --- resume (reference load_checkpoint, checkpointing.py:562-678) ---
    iteration = 0
    consumed_samples = 0
    if cfg.train.load:
        try:
            state, tag = checkpointing.load_checkpoint(
                cfg.train.load, state, retries=cfg.train.checkpoint_retries)
            # meta must come from the iteration actually loaded — under
            # torn-tracker fallback that can differ from the tracker target
            meta = checkpointing.load_meta(cfg.train.load, tag)
            if tag != checkpointing.RELEASE:
                iteration = int(tag)
                consumed_samples = int(meta.get("consumed_samples", 0))
            print_rank_0(f" loaded checkpoint from {cfg.train.load} at "
                         f"iteration {tag} "
                         f"(consumed_samples={consumed_samples})")
        except FileNotFoundError:
            print_rank_0(f" no checkpoint under {cfg.train.load}; "
                         "starting from scratch")
    timers("setup").stop()

    calculator = build_num_microbatches_calculator(
        cfg.train.global_batch_size, cfg.train.micro_batch_size,
        cfg.parallel.data_parallel, cfg.train.rampup_batch_size)
    calculator.update(consumed_samples, False)

    # --- data iterators ---
    def make_train_iter(consumed, gbs):
        if batch_provider is not None:
            return batch_provider(consumed, gbs)
        assert train_dataset is not None, "no training data"
        return _build_train_iterator(cfg, train_dataset, consumed, gbs,
                                     shuffle, eod_token)

    current_gbs = calculator.get_current_global_batch_size()
    train_iter = make_train_iter(consumed_samples, current_gbs)
    first_event = {}      # set-up facts for the first log_window event
    grad_reading = grad_collectives_of(art, current_gbs)
    if grad_reading is not None:
        print_rank_0(" dp_grad_collectives: " + " ".join(
            f"{k}={v}" for k, v in grad_reading.items()))
        first_event["dp_grad_collectives"] = grad_reading

    eval_step = None
    eval_flatten = True
    eval_batch_sharding = None
    persistent_valid = (None if valid_dataset is None else
                        _PersistentEvalIterator(cfg, valid_dataset, eod_token))
    if valid_dataset is not None or test_dataset is not None:
        if cfg.parallel.pipeline_parallel > 1:
            # pipelined eval: streamed per-token stats from the last stage
            # drive the full metric registry; keeps [accum, micro, ...]
            eval_step = make_pipeline_eval_step(
                cfg, art.mesh, tuple(cfg.train.metrics))
            eval_flatten = False
            eval_batch_sharding = art.batch_sharding
        else:
            eval_batch_sharding = NamedSharding(art.mesh, P("dp", "cp"))
            eval_step = make_eval_step(cfg, tuple(cfg.train.metrics),
                                       art.mesh, eval_batch_sharding,
                                       art.param_specs)

    base_rng = jax.random.key(cfg.train.seed)
    log = _LogState()
    log.once = first_event
    skip_set = set(cfg.train.skip_iters)
    exit_reason = None
    # Traces are taken on request ("the next n steps into dir",
    # obs/profile.py), polled at the top of every iteration; the configured
    # window is one such request made here.  A resumed run that starts past
    # the window drops it.
    step_trace = _StepTrace()
    if cfg.train.profile_dir:
        obs_profile.request_steps(
            cfg.train.profile_step_end - cfg.train.profile_step_start + 1,
            cfg.train.profile_dir, first=cfg.train.profile_step_start)

    # Anomaly rollback needs a checkpoint to roll back TO; anchor the run
    # with an initial save when none exists yet.
    rollbacks = 0
    if (cfg.train.anomaly_rollback_after and cfg.train.save
            and checkpointing.latest_complete_iteration(cfg.train.save)
            is None):
        print_rank_0(" anomaly rollback enabled with no checkpoint on "
                     "disk; writing the initial rollback anchor")
        _save(cfg, state, iteration, consumed_samples, timers)

    print_rank_0(f" training starts at iteration {iteration} / "
                 f"{cfg.train.train_iters}")
    with DistSignalHandler() as sig, DistSignalHandler(
            getattr(signal, "SIGUSR1", None)) as usr1, art.mesh:
      try:
        while iteration < cfg.train.train_iters:
            # SIGUSR1: trace the next few steps of this running job
            if usr1.take_local():
                if cfg.train.profile_dir:
                    obs_profile.request_steps(SIGNAL_TRACE_STEPS,
                                              cfg.train.profile_dir)
                else:
                    print_rank_0(" SIGUSR1 ignored: no profile_dir to "
                                 "trace into")
            step_trace.poll(iteration + 1)
            timers.cause = iteration + 1
            # fault injection: --skip_iters (training.py:397-399,422-426)
            if (iteration + 1) in skip_set:
                try:
                    next(train_iter)
                except StopIteration:
                    train_iter = make_train_iter(consumed_samples, current_gbs)
                    next(train_iter)
                iteration += 1
                consumed_samples += current_gbs
                calculator.update(consumed_samples, True)
                state = state._replace(
                    iteration=state.iteration + jnp.int32(1))
                print_rank_0(f" skipping iteration {iteration} (fault "
                             "injection)")
                step_trace.done(iteration)
                continue

            # batch-size ramp: rebuild the iterator (and step shapes) on rung
            # changes (reference microbatch calculator update,
            # training.py:420)
            new_gbs = calculator.get_current_global_batch_size()
            if new_gbs != current_gbs:
                current_gbs = new_gbs
                train_iter = make_train_iter(consumed_samples, current_gbs)
                print_rank_0(f" global batch size ramped to {current_gbs}")

            with jax.profiler.StepTraceAnnotation("train",
                                                  step_num=iteration + 1):
                timers("batch-generator", log_level=1).start()
                try:
                    batch = next(train_iter)
                except StopIteration:
                    train_iter = make_train_iter(consumed_samples,
                                                 current_gbs)
                    batch = next(train_iter)
                # chaos hook (inert unless a test armed poison_batches): NaN
                # batches exercise the skip/rollback defenses end-to-end
                batch = chaos().corrupt_batch(batch, iteration + 1)
                dev_batch = _put_batch(batch, art.batch_sharding)
                timers("batch-generator").stop()

                timers("train-step", log_level=0).start()
                timers("dispatch", log_level=2).start()
                state, step_metrics = art.step_fn(state, dev_batch, base_rng)
                timers("dispatch").stop()
                timers("metrics_fetch", log_level=2).start()
                step_metrics = jax.device_get(step_metrics)
                timers("metrics_fetch").stop()
                timers("train-step").stop(wait_for=step_metrics)

                iteration += 1
                consumed_samples += current_gbs
                calculator.update(consumed_samples, True)
                log.tokens += current_gbs * cfg.train.seq_length
                timers("log", log_level=2).start()
                training_log(cfg, log, step_metrics, iteration,
                             consumed_samples, writer, timers)
                timers("log").stop()

            # a trace ends right after its last step and that step's log
            # line, BEFORE the eval / save hooks below, so the capture is
            # steady-state train steps (a hook firing on an earlier traced
            # iteration is still captured — ask for steps clear of
            # eval/save intervals)
            step_trace.done(iteration)

            # --- anomaly rollback (resilience/anomaly.py) ---
            # K consecutive data anomalies: the poisoned window is wider
            # than per-step skips can absorb — restore the last complete
            # checkpoint and keep consumed_samples where it is, so the
            # resumed iterations read *past* the poisoned data.
            k_roll = cfg.train.anomaly_rollback_after
            if k_roll and int(step_metrics.get("anomaly_run", 0)) >= k_roll:
                state, iteration = rollback_to_last_checkpoint(
                    cfg, state, rollbacks + 1)
                rollbacks += 1
                print_rank_0(
                    f" ANOMALY ROLLBACK #{rollbacks}: {k_roll} consecutive "
                    f"anomalous iterations; restored iteration {iteration} "
                    f"and skipping the poisoned data window "
                    f"(consumed_samples stays at {consumed_samples})")
                log.reset_window()
                continue

            # --- eval hook ---
            if (valid_dataset is not None and eval_step is not None
                    and cfg.train.eval_interval
                    and iteration % cfg.train.eval_interval == 0):
                timers("eval", log_level=0).start()
                valid_iter = persistent_valid.iterator(current_gbs)
                params_for_eval = state.params
                evaluate_and_print_results(
                    f"iteration {iteration}", cfg, params_for_eval,
                    valid_iter, eval_step, writer, iteration,
                    eval_batch_sharding, flatten=eval_flatten)
                timers("eval").stop()

            # --- save hook ---
            if (cfg.train.save and cfg.train.save_interval
                    and iteration % cfg.train.save_interval == 0):
                _save(cfg, state, iteration, consumed_samples, timers)

            # --- exit conditions (training.py:731-767) ---
            # Multi-host signal consensus is a collective; polling it every
            # iteration would host-sync each step, so multi-host runs check
            # on the log cadence (every process evaluates the same
            # iteration condition, keeping the collective aligned).
            check_signal = (
                jax.process_count() == 1
                or not cfg.train.log_interval
                or iteration % cfg.train.log_interval == 0)
            if check_signal and sig.signals_received():
                exit_reason = "signal"
            elif (cfg.train.exit_interval
                    and iteration % cfg.train.exit_interval == 0):
                exit_reason = "exit_interval"
            elif cfg.train.exit_duration_mins is not None and check_signal:
                # Clock skew between hosts must not split the exit decision:
                # consensus on the same cadence as the signal check.
                mins = (time.time() - t_start) / 60.0
                if _cluster_any(mins > cfg.train.exit_duration_mins):
                    exit_reason = "exit_duration"
            if exit_reason:
                break
      finally:
        # on every exit path — incl. exceptions mid-trace, where the
        # partial capture is exactly what's needed
        step_trace.close()
        obs_profile.cancel_step_request()   # nobody is left to take one

    if exit_reason:
        print_rank_0(f" exiting at iteration {iteration}: {exit_reason}")
        if cfg.train.save:
            _save(cfg, state, iteration, consumed_samples, timers)
        if exit_reason == "signal":
            writer.flush()
            sys.exit(0)
    elif cfg.train.save:
        _save(cfg, state, iteration, consumed_samples, timers)

    # final validation + test (reference pretrain tail, training.py:144-169)
    if valid_dataset is not None and eval_step is not None:
        valid_iter = persistent_valid.iterator(current_gbs)
        evaluate_and_print_results(
            "the end of training for val data", cfg, state.params,
            valid_iter, eval_step, writer, iteration, eval_batch_sharding,
            flatten=eval_flatten)
    if test_dataset is not None and eval_step is not None:
        test_iter = _build_train_iterator(
            cfg, test_dataset, 0, current_gbs, False, eod_token)
        evaluate_and_print_results(
            "the end of training for test data", cfg, state.params,
            test_iter, eval_step, writer, iteration, eval_batch_sharding,
            flatten=eval_flatten)

    writer.flush()
    elapsed = datetime.timedelta(seconds=int(time.time() - t_start))
    print_rank_0(f" training finished in {elapsed} at iteration {iteration}")
    return state


def _save(cfg: RuntimeConfig, state, iteration: int, consumed_samples: int,
          timers: Timers) -> None:
    timers("save-checkpoint", log_level=0).start()
    path = checkpointing.save_checkpoint(
        cfg.train.save, state, cfg, iteration,
        meta={"consumed_samples": consumed_samples},
        retries=cfg.train.checkpoint_retries,
        keep=cfg.train.keep_latest_checkpoints)
    timers("save-checkpoint").stop()
    print_rank_0(f" saved checkpoint to {path}")


def rollback_to_last_checkpoint(cfg: RuntimeConfig, state, attempt: int = 1):
    """Restore the newest complete checkpoint over ``state`` →
    ``(restored_state, iteration)``.  ``attempt`` is the 1-based rollback
    count this run; exceeding ``anomaly_max_rollbacks`` aborts instead of
    thrashing forever on data that never recovers."""
    if attempt > cfg.train.anomaly_max_rollbacks:
        raise RuntimeError(
            f"giving up after {cfg.train.anomaly_max_rollbacks} anomaly "
            "rollbacks — the loss anomaly persists beyond skip-ahead "
            "recovery (bad data shard? diverged run?)")
    root = cfg.train.save or cfg.train.load
    if not root:
        raise RuntimeError(
            "anomaly_rollback_after is set but neither train.save nor "
            "train.load provides a checkpoint root to roll back to")
    state, tag = checkpointing.load_checkpoint(
        root, state, retries=cfg.train.checkpoint_retries)
    metrics_lib.RESILIENCE_EVENTS.inc("rollbacks")
    EVENT_LOG.emit("training", "rollback", checkpoint_root=str(root),
                   restored_tag=str(tag))
    return state, (0 if tag == checkpointing.RELEASE else int(tag))


# ---------------------------------------------------------------------------
# Generic (non-decoder-LM) pretraining loop — the forward_step_func hook of
# the reference's pretrain() (training.py:55), used by pretrain_bert.py /
# pretrain_t5.py for models whose batches and losses don't fit compute_loss.
# ---------------------------------------------------------------------------


def pretrain_custom(
    cfg: RuntimeConfig,
    dataset,
    params: PyTree,
    loss_fn,
    valid_dataset=None,
    eval_loss_fn=None,
    param_specs: Optional[PyTree] = None,
    pipeline_loss_fn=None,
) -> TrainState:
    """Training loop for an arbitrary model family (BERT/T5/biencoder).

    ``dataset[i]`` yields a dict of numpy arrays; batches are stacked to
    [accum, micro_total, ...] and the step runs ``loss_fn(cfg, params,
    microbatch, rng, deterministic)``.  With ``param_specs`` the params
    (and optimizer state, incl. ZeRO-1 over dp) are mesh-sharded — tensor
    parallelism via GSPMD, the same full-stack path the reference gives
    BERT/T5 (megatron/core/parallel_state.py); without it params stay
    replicated (dp only).

    With ``pipeline_loss_fn`` (and ``pipeline_parallel > 1``) the step
    instead differentiates the family's pipelined schedule
    (parallel/pipeline_encdec.py: T5 split-rank, BERT encoder pipeline);
    ``params``/``param_specs`` must then already be in the stage-stacked
    pipeline layout, and the grad-accum count doubles as the microbatch
    count of the schedule (the reference derives num_microbatches the
    same way, megatron/microbatches.py).
    """
    cfg.validate()
    if pipeline_loss_fn is not None:
        assert cfg.parallel.pipeline_parallel > 1 and param_specs is not None
        assert cfg.grad_accum_steps == cfg.parallel.num_microbatches, (
            f"global_batch_size/(micro_batch*dp) = {cfg.grad_accum_steps} "
            f"must equal parallel.num_microbatches "
            f"({cfg.parallel.num_microbatches}) for the pipelined step")
        assert eval_loss_fn is None, (
            "eval_loss_fn is not supported with pipeline_loss_fn — "
            "evaluation reuses the pipelined schedule")
    timers = Timers()
    writer = NullWriter()
    if jax.process_index() == 0:
        writer = build_writer(cfg.train.tensorboard_dir,
                              cfg.train.wandb_project, cfg.train.wandb_name,
                              config=cfg.to_dict())

    mesh = mesh_lib.build_mesh(cfg.parallel)
    if param_specs is not None:
        with mesh_lib.use_mesh(mesh):
            state, state_sharding = _shard_train_state(
                cfg, mesh, params, param_specs)
    else:
        state = init_train_state(cfg, params)
        # Replicated params + dp-sharded batch; aliased constant buffers
        # are copied so donation never sees the same buffer twice.
        replicated = NamedSharding(mesh, P())
        state_sharding = jax.tree.map(lambda _: replicated, state)
        state = _dedupe_buffers(jax.device_put(state, replicated))
    batch_sharding = NamedSharding(mesh, P(None, "dp"))
    step_fn = make_train_step(cfg, mesh, state_sharding, batch_sharding,
                              loss_fn=loss_fn,
                              pipeline_loss_fn=pipeline_loss_fn)

    iteration = 0
    consumed = 0
    if cfg.train.load or (cfg.train.save and checkpointing.read_tracker(
            cfg.train.save) is not None):
        root = cfg.train.load or cfg.train.save
        try:
            state, it = checkpointing.load_checkpoint(root, state)
            if it != "release":
                iteration = int(it)
                consumed = checkpointing.load_meta(root, it).get(
                    "consumed_samples", 0)
        except FileNotFoundError:
            pass

    gbs = cfg.train.global_batch_size
    accum = cfg.grad_accum_steps
    micro_total = gbs // accum
    n = len(dataset)
    log = _LogState()

    import functools

    @functools.lru_cache(maxsize=2)
    def epoch_order(epoch: int) -> np.ndarray:
        """Deterministic per-epoch permutation: sample order is a pure
        function of (seed, consumed), so resume reproduces it exactly and
        eval-time randomness can't perturb it (the resumable-sampler
        contract of data_samplers.py:49-96 in the reference).  Cached — a
        batch may straddle at most two epochs."""
        return np.random.default_rng(
            (cfg.train.seed, epoch)).permutation(n)

    def sample_index(position: int) -> int:
        return int(epoch_order(position // n)[position % n])

    if pipeline_loss_fn is not None:
        # Evaluation reuses the pipelined schedule on a single
        # microbatch group: [micro_total, ...] → [1, micro_total, ...].
        eval_jit = jax.jit(lambda p, mb: pipeline_loss_fn(
            cfg, p, jax.tree.map(lambda x: x[None], mb), mesh=mesh,
            rng=None))
    else:
        eval_fn = eval_loss_fn or loss_fn
        eval_jit = jax.jit(lambda p, mb: eval_fn(cfg, p, mb, None, True))
    eval_rng = np.random.default_rng(cfg.train.seed + 977)

    base_rng = jax.random.key(cfg.train.seed)
    while iteration < cfg.train.train_iters:
        idxs = [sample_index(consumed + j) for j in range(gbs)]
        samples = [dataset[i] for i in idxs]
        batch = {
            k: np.stack([s[k] for s in samples]).reshape(
                (accum, micro_total) + np.asarray(samples[0][k]).shape)
            for k in samples[0]
        }
        batch = {k: jax.device_put(jnp.asarray(v), batch_sharding)
                 for k, v in batch.items()}

        timers("train-step", log_level=0).start()
        state, metrics = step_fn(state, batch, base_rng)
        timers("train-step").stop()
        iteration += 1
        consumed += gbs
        log.tokens += gbs * cfg.train.seq_length
        training_log(cfg, log, metrics, iteration, consumed, writer, timers)

        if (cfg.train.save and cfg.train.save_interval
                and iteration % cfg.train.save_interval == 0):
            _save(cfg, state, iteration, consumed, timers)

        if (valid_dataset is not None and cfg.train.eval_interval
                and iteration % cfg.train.eval_interval == 0
                and cfg.train.eval_iters):
            losses = []
            nv = len(valid_dataset)
            vi = eval_rng.integers(0, nv, size=cfg.train.eval_iters)
            for v0 in vi:
                vs = [valid_dataset[int((v0 + j) % nv)]
                      for j in range(micro_total)]
                vb = {k: jnp.asarray(np.stack([s[k] for s in vs]))
                      for k in vs[0]}
                losses.append(float(eval_jit(state.params, vb)))
            print_rank_0(f" validation loss at iteration {iteration}: "
                         f"{np.mean(losses):.6E}")
            writer.add_scalar("valid/loss", float(np.mean(losses)),
                              iteration)

    if cfg.train.save:
        _save(cfg, state, iteration, consumed, timers)
    writer.flush()
    return state
