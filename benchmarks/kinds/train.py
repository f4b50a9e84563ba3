"""``kind: train`` — ``training.driver.pretrain`` on seeded sequences.

The job is the one a user starts: the driver's own dataset iterator,
train step, logging and exits.  The benchmark watches the trainer's
``log_window`` events (``log_interval=1``) through the event log's
stream hook, which runs on the training thread: the window opens when
the last warm-up step has logged, and once ``--seconds`` have passed the
next logged step sends this process the SIGTERM that the trainer's
``DistSignalHandler`` turns into a clean exit.

``--trace 2``: at the window's close the watch sends no SIGTERM; it asks
the running job for a trace of its next steps (``obs/profile.py``:
``request_steps``, what SIGUSR1 does for an operator), and the first
step logged after that session has closed sends the SIGTERM.  The
train loop's spans (``obs/trace.py:TRAIN_TRACE``, fed by the loop's
timers) are on in every mode, so that ``--trace 0`` and ``--trace 2``
run the same program up to the window's close.
"""

from __future__ import annotations

import contextlib
import importlib
import json
import math
import os
import signal
import time

from benchmarks import common, trace_reduce, traffic
from benchmarks.common import Ctx, Result, build_model, say, scaled

# |program loss - reference loss| on the first batch, both from the same
# bf16 parameters: the program multiplies in bf16 with float32
# accumulation, the reference in float32 ("highest").  Per-token errors
# are of the order of 1e-2 and mostly cancel in the mean over >= 12288
# tokens; measured 0.00001-0.00026 on the chip (PERF.md, PR 23).  An 8-bit
# matmul path moves the mean by several hundredths and fails.
LOSS_TOL = 0.001


class _Watch:
    """The event log's stream: called on the training thread at every
    event, keeps the ``log_window`` ones and ends the run."""

    def __init__(self, warmup_steps: int, seconds: float, clock,
                 trace_after=None):
        self.warmup, self.seconds, self.clock = warmup_steps, seconds, clock
        self.trace_after = trace_after     # --trace 2: (steps, directory)
        self.events, self.stamps = [], []  # stamps: perf_counter
        self.t_open = self.t_close = None
        self.asked_to_stop = False
        self.n_close = None                # events when the window closed
        self.compiles_at_open = None
        self.compiles_at_close = None
        self.untouched = None

    def write(self, line: str) -> None:
        if '"log_window"' not in line:
            return
        now = time.perf_counter()
        self.events.append(json.loads(line))
        self.stamps.append(now)
        n = len(self.events)
        if n == self.warmup:
            self.t_open = now
            self.compiles_at_open = self.clock.backend_compiles
        elif (self.t_open is not None and self.compiles_at_close is None
              and now - self.t_open >= self.seconds):
            self.compiles_at_close = self.clock.backend_compiles
            self.t_close, self.n_close = now, n
            if self.trace_after is None:
                os.kill(os.getpid(), signal.SIGTERM)
            else:
                self._ask_for_trace()
        elif self.n_close is not None and not self.asked_to_stop:
            from megatron_llm_tpu.obs import profile

            # the traced steps are done once the session the request
            # opened has closed
            last = profile.last()
            if profile.active() is None and last is not None \
                    and last.dir == self.trace_after[1]:
                self.asked_to_stop = True
                os.kill(os.getpid(), signal.SIGTERM)

    def _ask_for_trace(self) -> None:
        """The window's numbers exist; on this, the training thread,
        between two steps: ask the loop for a trace of its next steps
        (one more than ``trace_steps``: the window runs from the first
        step's start to the last one's)."""
        from megatron_llm_tpu.obs import profile

        steps, trace_dir = self.trace_after
        self.untouched = common.before_traced_phase()
        profile.request_steps(steps + 1, trace_dir)

    def flush(self) -> None:
        pass


def run(ctx: Ctx) -> Result:
    import jax
    import numpy as np
    from megatron_llm_tpu.config import (OptimizerConfig, ParallelConfig,
                                         RuntimeConfig, TrainConfig)
    from megatron_llm_tpu.data.samplers import BatchIterator
    from megatron_llm_tpu.models import model as model_lib
    from megatron_llm_tpu.models import sharding as shard_lib
    from megatron_llm_tpu.obs import profile
    from megatron_llm_tpu.obs.logging import EVENT_LOG
    from megatron_llm_tpu.obs.trace import TRAIN_TRACE
    from megatron_llm_tpu.parallel import mesh as mesh_lib
    from megatron_llm_tpu.training.driver import pretrain

    from benchmarks import flops

    mix = scaled(ctx.mix, ctx.rehearsal)
    seq, gb, mb = mix["seq_length"], mix["sequences_per_step"], \
        mix["micro_batch"]
    warmup, trace_steps = int(mix["warmup_steps"]), int(mix["trace_steps"])
    model = build_model(ctx, "train", seq_length=seq,
                        **({"max_position_embeddings": seq}
                           if ctx.rehearsal else {}))
    parallel = ParallelConfig(**ctx.config.get("layout", {}))
    first_traced = warmup + 2
    cfg = RuntimeConfig(
        model=model, parallel=parallel,
        optimizer=OptimizerConfig(**ctx.config["train"]["optimizer"]),
        train=TrainConfig(
            train_iters=10 ** 6, micro_batch_size=mb, global_batch_size=gb,
            seq_length=seq, seed=traffic.device_seed(ctx.seed),
            log_interval=1,
            profile_dir=ctx.trace_dir if ctx.trace == 1 else None,
            profile_step_start=first_traced,
            profile_step_end=first_traced + trace_steps)).validate()
    say(f"train: hidden {model.hidden_size}, {model.num_attention_heads} "
        f"heads x {model.head_dim}, {model.kv_heads} KV heads, ffn "
        f"{model.ffn_size}, vocab {model.vocab_size}, {model.params_dtype}, "
        f"{model.num_layers} layers; {gb} x {seq} tokens a step (mb {mb} x "
        f"accum {cfg.grad_accum_steps}), layout {ctx.config.get('layout')}")

    dataset = traffic.train_dataset(mix, ctx.seed, model.vocab_size)

    # weights: one jitted call from the seed, placed as the trainer shards
    # them, so that it takes them over without a copy
    mesh = mesh_lib.build_mesh(parallel)
    specs = shard_lib.param_specs(cfg.model, parallel)
    shardings = jax.tree.map(
        lambda s: jax.sharding.NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
    with mesh_lib.use_mesh(mesh):
        params = jax.jit(
            lambda k: model_lib.init_params(
                k, cfg.model, tp=parallel.tensor_parallel),
            out_shardings=shardings)(jax.random.key(cfg.train.seed))

    # the reference's loss on the first batch, from the same parameters,
    # on one device, before the trainer's state takes the memory
    ref_mod = importlib.import_module(
        f"benchmarks.reference.{ctx.config['reference']}")
    t = time.perf_counter()
    one = jax.devices()[0]
    ref_params = params if ctx.chips == 1 else jax.device_put(params, one)
    # the batch the trainer's own iterator yields first (epoch-shuffled
    # by the run's seed, wrapping when the dataset is used up)
    first = next(iter(BatchIterator(
        dataset, global_batch_size=gb, grad_accum=cfg.grad_accum_steps,
        seq_length=seq, shuffle=True, seed=cfg.train.seed)))
    first_batch = np.concatenate(
        [first["tokens"], first["labels"][..., -1:]], axis=-1
    ).reshape(gb, seq + 1)
    ref_loss = ref_mod.loss(ref_params, first_batch, ref_mod.meta_of(model))
    del ref_params
    say(f"reference loss on the first batch: {ref_loss:.6f} "
        f"({time.perf_counter() - t:.1f} s)")

    watch = _Watch(warmup, ctx.seconds, ctx.clock,
                   trace_after=((trace_steps, ctx.trace_dir)
                                if ctx.trace == 2 else None))
    EVENT_LOG.clear()
    EVENT_LOG.configure(stream=watch)
    TRAIN_TRACE.clear()
    TRAIN_TRACE.enabled = True     # this run reads the loop's spans
    # a configuration whose optimizer state the program cannot make on one
    # chip asks for it to be made in host memory (its file says why)
    where = (jax.default_device(jax.devices("cpu")[0])
             if ctx.config["train"].get("init_state_on_host")
             else contextlib.nullcontext())
    try:
        with where:
            pretrain(cfg, dataset, params=params, shuffle=True)
        raise RuntimeError("pretrain returned before the window closed")
    except SystemExit as e:          # the trainer's clean exit on SIGTERM
        if e.code not in (0, None):
            raise
    finally:
        EVENT_LOG.configure(stream=None)
        TRAIN_TRACE.enabled = False
    del params

    ev = watch.events
    window = ev[warmup:watch.n_close]
    spans = common.recorder_spans(TRAIN_TRACE, watch.t_open, watch.t_close)
    losses = [e["lm_loss"] for e in ev]
    say("loss per step: " + " ".join(f"{x:.4f}" for x in losses[:12])
        + (" ..." if len(losses) > 12 else ""))
    step_s = [e["step_time_s"] for e in window]
    tokens = gb * seq * len(window)
    rate = tokens / sum(step_s)
    say(f"window: {len(window)} steps, {sum(step_s):.3f} s of step time, "
        f"median step {1e3 * float(np.median(step_s)):.2f} ms")
    by_name = {}
    for name, _t0, dur, _a in spans:
        by_name.setdefault(name, []).append(dur)
    say("the loop's spans over the window, median ms: " + ", ".join(
        f"{n} {1e3 * float(np.median(d)):.3f}" for n, d in by_name.items()))

    bad_steps = int(ev[-1]["skipped"]) + int(ev[-1]["anomalies"]) + sum(
        not math.isfinite(e["lm_loss"]) for e in window)
    loss_gap = abs(losses[0] - ref_loss)
    compiles = watch.compiles_at_close - watch.compiles_at_open
    notes = [f"first-batch loss {losses[0]:.6f} vs reference "
             f"{ref_loss:.6f}: distance {loss_gap:.6f} (tolerance "
             f"{LOSS_TOL})",
             f"compilations inside the window: {compiles}",
             f"skipped or anomalous or non-finite steps: {bad_steps}"]
    not_finite = sum(not math.isfinite(x) for x in losses)
    correct = (loss_gap <= LOSS_TOL and compiles == 0 and bad_steps == 0
               and not_finite == 0)
    sizes = flops.sizes_of(model)
    traced = {}
    if ctx.trace == 2:
        notes.append(watch.untouched)
        session = profile.last()
        traced["trace"] = trace_reduce.load(
            trace_reduce.find_xplane(ctx.trace_dir))
        _off, traced["host_spans"] = common.on_trace_clock(
            traced["trace"], session, common.recorder_spans(
                TRAIN_TRACE, session.t_sync - 60.0, session.t_stop))
        steps = [e for e, t in zip(ev, watch.stamps)
                 if session.t_sync <= t <= session.t_stop]
        if steps:
            say("traced steps' step_time_s: " + " ".join(
                f"{1e3 * e['step_time_s']:.2f}" for e in steps)
                + f" ms (the window's median "
                f"{1e3 * float(np.median(step_s)):.2f} ms)")
    return Result(
        correct=correct, attempted=len(window), failed=bad_steps,
        end_to_end={"train_tokens_per_s": rate,
                    "setup_s": watch.t_open - ctx.t0},
        evidence={**traced, "log_window": window,
                  "recorder_spans": [(n, t0, d) for n, t0, d, _a in spans],
                  "tokens_per_step": gb * seq,
                  "train_flops_per_token": flops.train_flops_per_token(
                      sizes, seq),
                  "step_module": "jit_step"},
        notes=notes,
        compared={"first_batch_loss_gap": (loss_gap, LOSS_TOL),
                  "compiles_in_window": (compiles, 0),
                  "bad_steps": (bad_steps, 0),
                  "losses_not_finite": (not_finite, 0)})
