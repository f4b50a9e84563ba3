#!/usr/bin/env python
"""The quickest proof that the system still starts on the chip.

Drives the repo's main path once on one TPU v5e, through the entry points
a user calls, at Llama-2-7B's published widths (hidden 4096, 32 heads x
128, 32 KV heads, ffn 11008, vocab 32000, bf16).  Only depth is cut, to
what ``compiled.memory_analysis()`` says fits, and the cut is printed.
Weights are random, made from ``--seed``.  Two phases, because the repo is
a trainer and a server over one model library:

* trainer — ``training.driver.pretrain`` with flash attention, selective
  remat and AdamW over fp32 masters at seq 4096, then the checkpoint it
  saved read back with ``checkpointing.load_params_for_inference``;
* server — ``generation.server.MegatronServer`` over a paged KV pool,
  started as tools/run_text_generation_server.py starts it, answering
  ``PUT /api`` requests over loopback and ``GET /metrics``.

Each phase is compared, not just completed: the same steps and the same
token sequences go through the plain XLA path of the same params
(``attention_impl="dot"``, one-shot
``generation.generate_tokens``).  Random weights make argmax ties, so the
criterion is distance of loss and of log-probability, never token
identity.

``--chips 4`` runs the sharded paths instead, and nothing else: training
at dp2 x tp2 and pp2 x tp2 against one device, and two tp=2 replicas
behind the router against a one-device engine.

The last stdout line is one JSON object, ``{"ok": ..., "device": {...}}``
with the device as JAX reports it.  Without a TPU the script fails; the
tiny ``--cpu-rehearsal`` finds wrong paths and arguments and can by
construction never report ``"platform": "tpu"``.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import json
import math
import sys
import tempfile
import threading
import time
import traceback
import urllib.request

# bf16 tolerances, stated before the run and printed beside the measured
# maxima.  Loss: one step's mean over >= 4096 tokens, flash vs einsum
# attention from identical params, then drifting apart through AdamW's
# sign-like updates.  Log-probability: one token's, through every layer.
LOSS_TOL = 0.01
LOGPROB_TOL = 0.25
# share of the device's memory a planned step may need (the compiler
# counts one program, not the allocator's fragmentation)
FIT_FRACTION = 0.95


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Everything that differs between the chip run and the rehearsal."""
    model: dict                 # llama2_config("7b", **model): the widths
    train_seq: int
    train_depth_mb: tuple       # (num_layers, micro_batch) candidates
    compare_seqs: tuple         # flash-vs-dot sequence lengths to try
    train_steps: int
    lr_warmup_iters: int
    serve_depths: tuple         # num_layers candidates
    slots: int
    max_seq_len: int
    prompt_lens: tuple          # R0, R1, R2, A, B(=C)
    shared_prefix: int
    new_tokens: int


REAL = Sizes(
    model={}, train_seq=4096, train_depth_mb=((2, 2), (2, 1), (1, 1)),
    compare_seqs=(2048, 1024), train_steps=5, lr_warmup_iters=2000,
    serve_depths=(6, 4, 2),
    slots=8, max_seq_len=2048, prompt_lens=(100, 700, 1000, 690, 700),
    shared_prefix=512, new_tokens=64)
TINY = Sizes(
    model=dict(hidden_size=256, num_attention_heads=2, ffn_hidden_size=512,
               vocab_size=512, max_position_embeddings=512),
    train_seq=256, train_depth_mb=((2, 2),), compare_seqs=(256,),
    train_steps=5, lr_warmup_iters=10, serve_depths=(2,), slots=4,
    max_seq_len=512,
    prompt_lens=(20, 150, 200, 290, 300), shared_prefix=256, new_tokens=8)


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class Fail(Exception):
    """A check of this script did not hold."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise Fail(what)
    say(f"ok: {what}")


# ---------------------------------------------------------------------------
# device, memory, compile accounting
# ---------------------------------------------------------------------------

class CompileClock:
    """Seconds jax spent tracing, lowering and compiling (a read from the
    persistent cache counts as its compile), so each phase reports them
    apart from the seconds it ran."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.backend_compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration
            if event.endswith("backend_compile_duration"):
                self.backend_compiles += 1


@contextlib.contextmanager
def phase(name: str, clock: CompileClock):
    t0, c0, n0 = time.perf_counter(), clock.seconds, clock.backend_compiles
    say(f"--- {name}")
    yield
    wall = time.perf_counter() - t0
    say(f"--- {name}: {wall:.1f} s wall, of which {clock.seconds - c0:.1f} "
        f"s trace + lower + compile of {clock.backend_compiles - n0} "
        f"executables (summed over threads: replicas compile at once)")


def memory(label: str, devices=None) -> dict:
    """Print and return the first device's allocator statistics."""
    import jax

    first = None
    for d in devices or jax.devices()[:1]:
        st = d.memory_stats() or {}
        first = st if first is None else first
        say(f"memory {label} device {d.id}: bytes_in_use="
            f"{st.get('bytes_in_use')} peak_bytes_in_use="
            f"{st.get('peak_bytes_in_use')} bytes_limit="
            f"{st.get('bytes_limit')}")
    return first


def bytes_limit() -> float:
    import jax

    st = jax.devices()[0].memory_stats() or {}
    return float(st.get("bytes_limit") or math.inf)


def released(label: str) -> None:
    """The phase's device memory must come back: the next phase shares
    this chip (round 5's bench lost rows to a finished point's memory)."""
    gc.collect()
    st = memory(label)
    used = st.get("bytes_in_use")
    if used is not None:
        check(used <= 0.05 * bytes_limit(),
              f"{label}: bytes_in_use fell to {used} "
              f"(<= 5% of the limit) before the next phase")


def planned_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return int(m.argument_size_in_bytes + m.output_size_in_bytes
               - m.alias_size_in_bytes + m.temp_size_in_bytes
               + m.generated_code_size_in_bytes)


def compile_or_none(lowered, what: str):
    """Compile; None when the compiler says the program does not fit the
    device (its answer to a sizing question).  Anything else raises."""
    import jax

    try:
        return lowered.compile()
    except jax.errors.JaxRuntimeError as e:
        if "RESOURCE_EXHAUSTED" not in str(e):
            raise
        say(f"plan {what}: does not fit — "
            f"{str(e).splitlines()[0][:200]}")
        return None


def has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


# ---------------------------------------------------------------------------
# trainer
# ---------------------------------------------------------------------------

def train_config(sz: Sizes, *, layers, mb, seq, steps, impl, seed,
                 parallel=None, global_batch=None, save=None):
    from megatron_llm_tpu.config import (OptimizerConfig, ParallelConfig,
                                         RuntimeConfig, TrainConfig,
                                         llama2_config)

    model = llama2_config("7b", **{
        **sz.model, "num_layers": layers, "seq_length": seq,
        "attention_impl": impl, "recompute": "selective"})
    return RuntimeConfig(
        model=model,
        parallel=parallel or ParallelConfig(),
        # Llama-2's published recipe: peak 3e-4 after 2000 warm-up steps
        # (the first steps run at 1.5e-7 * step; at 3e-4 flat the first
        # update of a 4096-wide model overshoots: loss 11.2 -> 30.6 on the
        # chip), AdamW (0.9, 0.95), weight decay 0.1, clip 1.0
        optimizer=OptimizerConfig(lr=3e-4,
                                  lr_warmup_iters=sz.lr_warmup_iters),
        train=TrainConfig(
            train_iters=steps, micro_batch_size=mb,
            global_batch_size=global_batch or mb, seq_length=seq,
            seed=seed, log_interval=1, save=save),
    ).validate()


def token_set(cfg, seed: int):
    """A small fixed token set from the seed — one global batch, drawn
    from a skewed distribution so that a few steps can learn something
    (the marginal) and the loss can fall."""
    import numpy as np

    rng = np.random.default_rng(seed)
    v = cfg.model.vocab_size
    p = 1.0 / np.arange(1, v + 1)
    p /= p.sum()
    n = cfg.train.global_batch_size
    text = rng.choice(v, size=(n, cfg.train.seq_length + 1), p=p)
    return [{"text": row.astype(np.int32)} for row in text]


def lower_train_step(cfg):
    """The step ``pretrain(cfg)`` will compile, lowered over shapes only —
    no device memory is committed before ``memory_analysis()`` has been
    asked.  ``setup_train_state`` runs under ``eval_shape``: the state it
    returns is abstract, the jitted step and the shardings beside it are
    the real ones."""
    import jax
    import jax.numpy as jnp
    from megatron_llm_tpu.training import driver

    box = {}

    def abstract_state():
        box["art"] = driver.setup_train_state(cfg)
        return box["art"].state

    shapes = jax.eval_shape(abstract_state)
    art = box["art"]
    state = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        shapes, art.state_sharding)
    accum = cfg.grad_accum_steps
    shape = (accum, cfg.train.global_batch_size // accum,
             cfg.train.seq_length)
    batch = {
        k: jax.ShapeDtypeStruct(shape, dt, sharding=art.batch_sharding)
        for k, dt in (("tokens", jnp.int32), ("labels", jnp.int32),
                      ("loss_mask", jnp.float32))}
    return art.step_fn.lower(state, batch, jax.eval_shape(jax.random.key, 0))


def plan_train(cfg, what: str, want_kernel: bool, on_tpu: bool):
    """→ planned bytes of ``cfg``'s train step, or None if it does not
    fit; checks the executable for the kernel it should (not) hold."""
    compiled = compile_or_none(lower_train_step(cfg), what)
    if compiled is None:
        return None
    need, kernel = planned_bytes(compiled), has_kernel(compiled)
    say(f"plan {what}: {need / 2**30:.2f} GiB of "
        f"{bytes_limit() / 2**30:.2f} GiB; tpu_custom_call in the train "
        f"step: {kernel}")
    if on_tpu:
        check(kernel == want_kernel,
              f"{what}: train step "
              f"{'holds' if want_kernel else 'holds no'} tpu_custom_call")
    return need if need <= FIT_FRACTION * bytes_limit() else None


def run_pretrain(cfg, dataset, keep_state: bool = False):
    """``pretrain`` → loss per step (and the final state, if asked: it
    holds most of the device's memory for as long as it is referenced)."""
    from megatron_llm_tpu.obs.logging import EVENT_LOG
    from megatron_llm_tpu.training.driver import pretrain

    EVENT_LOG.clear()
    state = pretrain(cfg, dataset)
    losses = [e["lm_loss"] for e in EVENT_LOG.recent(event="log_window")]
    check(len(losses) == cfg.train.train_iters,
          f"{cfg.train.train_iters} steps logged a loss each")
    say("loss per step: " + " ".join(f"{x:.4f}" for x in losses))
    check(all(math.isfinite(x) for x in losses), "every loss is finite")
    return (state, losses) if keep_state else losses


def trainer_phase(sz: Sizes, seed: int, on_tpu: bool, reduced: list):
    import jax
    import numpy as np
    from megatron_llm_tpu import checkpointing

    with tempfile.TemporaryDirectory(prefix="chip_smoke_ckpt_") as save:
        for layers, mb in sz.train_depth_mb:
            cfg = train_config(sz, layers=layers, mb=mb, seq=sz.train_seq,
                               steps=sz.train_steps, impl="flash", seed=seed,
                               save=save)
            if plan_train(cfg, f"train flash L={layers} mb={mb} "
                          f"seq={sz.train_seq}", True, on_tpu) is not None:
                break
        else:
            raise Fail("no trainer candidate fits this device")
        m = cfg.model
        say(f"trainer: hidden {m.hidden_size}, {m.num_attention_heads} "
            f"heads x {m.head_dim}, {m.kv_heads} KV heads, ffn {m.ffn_size}, "
            f"vocab {m.vocab_size}, {m.params_dtype}; num_layers {layers} "
            f"(published 32), micro batch {mb}, seq {sz.train_seq}, "
            f"selective remat, AdamW with fp32 master")
        reduced.append(f"trainer num_layers 32 -> {layers}")

        state, losses = run_pretrain(cfg, token_set(cfg, seed),
                                     keep_state=True)
        # random N(0, init_std) logits over hidden-many unit-RMS features:
        # E[loss] = ln V + hidden * init_std^2 / 2, not ln V alone
        expect = math.log(m.vocab_size) \
            + m.hidden_size * m.init_method_std ** 2 / 2
        say(f"ln(vocab) = {math.log(m.vocab_size):.4f}; expected first "
            f"loss at this init = {expect:.4f}")
        check(abs(losses[0] - expect) <= 0.5,
              f"first loss {losses[0]:.4f} within 0.5 of {expect:.4f}")
        check(losses[-1] < losses[0],
              f"last loss {losses[-1]:.4f} below first {losses[0]:.4f}")
        live = jax.device_get(state.params)
        del state
        loaded = checkpointing.load_params_for_inference(save, cfg.model)
        same = jax.tree.map(
            lambda a, b: bool(np.array_equal(np.asarray(a), np.asarray(b))),
            live, loaded)
        check(all(jax.tree.leaves(same)),
              "params read back with load_params_for_inference equal the "
              "live ones")
        del live, loaded

    # the same steps through the plain XLA path of the same params
    for seq in (sz.train_seq,) + tuple(
            s for s in sz.compare_seqs if s != sz.train_seq):
        ref = train_config(sz, layers=layers, mb=mb, seq=seq,
                           steps=sz.train_steps, impl="dot", seed=seed)
        if plan_train(ref, f"train dot L={layers} mb={mb} seq={seq}",
                      False, on_tpu) is not None:
            break
        say(f"the einsum path's s^2 scores do not fit at seq {seq}")
    else:
        raise Fail("no sequence length fits the einsum reference")
    if seq != sz.train_seq:
        say(f"comparison steps run at seq {seq}, not {sz.train_seq}")
        cfg = train_config(sz, layers=layers, mb=mb, seq=seq,
                           steps=sz.train_steps, impl="flash", seed=seed)
        losses = run_pretrain(cfg, token_set(cfg, seed))
    ref_losses = run_pretrain(ref, token_set(ref, seed))
    worst = max(abs(a - b) for a, b in zip(losses, ref_losses))
    check(worst <= LOSS_TOL,
          f"flash vs einsum loss per step at seq {seq}: max distance "
          f"{worst:.5f} <= tolerance {LOSS_TOL}")


# ---------------------------------------------------------------------------
# server
# ---------------------------------------------------------------------------

def serve_config(sz: Sizes, layers: int):
    from megatron_llm_tpu.config import llama2_config

    return llama2_config("7b", **{**sz.model, "num_layers": layers,
                                  "attention_impl": "flash"})


BLOCK = 128           # kv_block_size
PREFILL_BUCKET = 64   # the server CLI's default
PREFIX_BLOCKS = 256   # EngineConfig's default prefix-cache budget


def plan_server(sz: Sizes, cfg, on_tpu: bool):
    """→ planned bytes of the engine's decode step beside its default
    pool, or None if that does not fit.  Compiles the executables the
    engine will dispatch (serving/engine.py) over shapes only."""
    import jax
    import jax.numpy as jnp
    from megatron_llm_tpu.models import model as model_lib
    from megatron_llm_tpu.serving import engine as eng

    what = f"serve L={cfg.num_layers}"
    S, T = sz.slots, -(-sz.max_seq_len // BLOCK)
    n_blocks = 1 + S * T + PREFIX_BLOCKS   # engine.py's default pool
    params = jax.eval_shape(
        lambda: model_lib.init_params(jax.random.key(0), cfg))
    k, v = jax.eval_shape(
        lambda: model_lib.init_kv_pool(cfg, n_blocks, BLOCK))

    def vec(dtype):
        return jax.ShapeDtypeStruct((S,), dtype)

    decode = compile_or_none(eng._decode_donated.lower(
        cfg, params, k, v, jax.ShapeDtypeStruct((S, T), jnp.int32),
        vec(jnp.int32), vec(jnp.int32), vec(jnp.uint32), vec(jnp.int32),
        vec(jnp.bool_), vec(jnp.float32), vec(jnp.int32), vec(jnp.float32)),
        what + " decode")
    if decode is None:
        return None
    longest = -(-max(sz.prompt_lens) // PREFILL_BUCKET) * PREFILL_BUCKET
    prefill = compile_or_none(eng._prefill_impl.lower(
        cfg, params, jax.ShapeDtypeStruct((1, longest), jnp.int32),
        jax.ShapeDtypeStruct((1,), jnp.int32), max_seq_len=T * BLOCK,
        want_logprobs=True), what + " prefill")
    if prefill is None:
        return None
    pool_bytes = sum(a.size * a.dtype.itemsize
                     for a in jax.tree.leaves((k, v)))
    need = max(planned_bytes(decode), planned_bytes(prefill) + pool_bytes)
    m = decode.memory_analysis()
    say(f"plan {what}: pool {n_blocks} blocks x {BLOCK} tokens = "
        f"{pool_bytes / 2**30:.2f} GiB; decode step temp "
        f"{m.temp_size_in_bytes / 2**30:.2f} GiB (the composed path's "
        f"dense gather of every slot's table); needs "
        f"{need / 2**30:.2f} GiB of {bytes_limit() / 2**30:.2f} GiB")
    say(f"plan {what}: tpu_custom_call in the decode step: "
        f"{has_kernel(decode)}, in the prefill: {has_kernel(prefill)}")
    if on_tpu:
        check(has_kernel(decode) and has_kernel(prefill),
              f"{what}: prefill and decode step hold tpu_custom_call")
    return need if need <= FIT_FRACTION * bytes_limit() else None


def prompts_for(sz: Sizes, vocab: int, seed: int) -> dict:
    """R0..R2 of the given lengths; A and B share a ``shared_prefix``;
    C is B again.  Tokens avoid the last id (NullTokenizer's EOD)."""
    import numpy as np

    rng = np.random.default_rng(seed)
    r0, r1, r2, a, b = sz.prompt_lens

    def draw(n):
        return rng.integers(1, vocab - 1, size=n).tolist()

    prefix = draw(sz.shared_prefix)
    out = {"R0": draw(r0), "R1": draw(r1), "R2": draw(r2),
           "A": prefix + draw(a - sz.shared_prefix),
           "B": prefix + draw(b - sz.shared_prefix)}
    out["C"] = list(out["B"])
    return out


def put_api(port: int, prompt, new_tokens: int, logprobs: bool) -> dict:
    body = json.dumps({
        "prompts": [" ".join(map(str, prompt))],
        "tokens_to_generate": new_tokens, "logprobs": logprobs,
        "no_early_termination": True}).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/api", data=body, method="PUT",
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=600) as resp:
        out = json.loads(resp.read())
    return {"tokens": [int(t) for t in out["text"][0].split()],
            "logprobs": (out["logprobs"] or [None])[0]}


def serve_round(port: int, prompts: dict, new_tokens: int) -> dict:
    """The traffic: R0 and R1 together, R2 joining while they decode;
    then A alone, so that its retirement offers the shared prefix; then
    B (no logprobs: the prefix-cache path) and C (B's cold twin)
    together."""
    results, errors = {}, []

    def client(name, delay):
        try:
            time.sleep(delay)
            results[name] = put_api(port, prompts[name], new_tokens,
                                    logprobs=name != "B")
        except BaseException as e:  # noqa: BLE001 — re-raised below
            errors.append(e)

    for wave in ((("R0", 0.0), ("R1", 0.0), ("R2", 0.3)), (("A", 0.0),),
                 (("B", 0.0), ("C", 0.0))):
        threads = [threading.Thread(target=client, args=a) for a in wave]
        for t in threads:
            t.start()
        for t in threads:
            t.join(900)
            if t.is_alive():
                raise Fail("a request did not return in 900 s")
        if errors:
            raise errors[0]
    return results


def reference_logprobs(cfg, params, sequences: dict) -> dict:
    """Log-probability of every token of ``sequences`` given its prefix,
    by one-shot ``generate_tokens`` over the plain XLA path: each
    sequence enters as a "prompt" of its full length, so the loop
    teacher-forces it and records the log-probs as it goes."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from megatron_llm_tpu.generation import generation

    ref_cfg = dataclasses.replace(cfg, attention_impl="dot")
    names = list(sequences)
    lengths = [len(sequences[n]) for n in names]
    # one slot of room to "generate" into; a width that is no multiple of
    # 128 keeps the Pallas decode kernel out (ops/attention.py:
    # decode_kernel_eligible) — checked below, not assumed
    width = max(lengths) + 1
    width += width % 128 == 0
    tokens = np.zeros((len(names), width), np.int32)
    for i, n in enumerate(names):
        tokens[i, :lengths[i]] = sequences[n]
    tokens, lengths_a = jnp.asarray(tokens), jnp.asarray(lengths, jnp.int32)
    # Pallas lowers to a custom call before XLA sees the module, so the
    # lowered text answers for the executable without a second compile
    lowered = generation._generate_impl.lower(
        ref_cfg, params, tokens, lengths_a, jax.random.key(0),
        jnp.float32(1.0), jnp.float32(0.0), min_prompt_len=min(lengths),
        eos_id=-1, top_k=0, sample_mode="greedy", return_logprobs=True,
        use_eos_stop=False)
    check("tpu_custom_call" not in lowered.as_text(),
          "the reference generate_tokens is plain XLA: no tpu_custom_call")
    out = generation.generate_tokens(
        ref_cfg, params, tokens, lengths_a, eos_id=-1,
        return_logprobs=True, use_eos_stop=False)
    lps = np.asarray(out.logprobs)
    return {n: lps[i, :lengths[i] - 1] for i, n in enumerate(names)}


def exercise_cow(engine) -> None:
    """Copy-on-write through the donated executable, on the live pool.
    The engine shares prefix blocks whole (block-aligned, ref-counted),
    so serving traffic never copies one; share a block by hand and ask
    for a writable copy, on the scheduler thread that owns the pool."""
    import numpy as np

    def cow():
        pool = engine.slots.pool
        bid = next(iter(pool.ref_counts()))      # a cached prefix block
        before = np.asarray(pool.k_pool[:, bid])
        pool.incref(bid)                         # now shared
        assert pool.reserve(1)
        new = pool.ensure_writable(bid)          # copies, drops our ref
        ok = (new != bid and np.array_equal(before, np.asarray(
            pool.k_pool[:, new])) and np.array_equal(
                before, np.asarray(pool.k_pool[:, bid])))
        pool.decref(new)
        return ok

    check(engine.call_in_scheduler(cow, timeout=300),
          "copy-on-write of a shared block copied it (off the CPU, through "
          "the donated executable)")


def server_phase(sz: Sizes, seed: int, on_tpu: bool, reduced: list,
                 clock: CompileClock):
    import jax
    import numpy as np
    from megatron_llm_tpu.analysis.sanitizers import no_recompiles
    from megatron_llm_tpu.generation.server import MegatronServer
    from megatron_llm_tpu.models import model as model_lib
    from megatron_llm_tpu.tokenizer.tokenizer import NullTokenizer

    for layers in sz.serve_depths:
        cfg = serve_config(sz, layers)
        if plan_server(sz, cfg, on_tpu) is not None:
            break
    else:
        raise Fail("no server candidate fits this device")
    say(f"server: hidden {cfg.hidden_size}, {cfg.num_attention_heads} "
        f"heads x {cfg.head_dim}, {cfg.kv_heads} KV heads, ffn "
        f"{cfg.ffn_size}, vocab {cfg.vocab_size}, {cfg.params_dtype}; "
        f"num_layers {layers} (published 32); {sz.slots} slots x "
        f"{sz.max_seq_len}, kv_block_size {BLOCK}, default pool "
        f"({1 + sz.slots * -(-sz.max_seq_len // BLOCK) + PREFIX_BLOCKS} "
        f"blocks), pipelined decode, prefix cache")
    reduced.append(f"server num_layers 32 -> {layers}")

    params = model_lib.init_params(jax.random.key(seed), cfg)
    # as tools/run_text_generation_server.py:main builds it
    server = MegatronServer(
        cfg, params, NullTokenizer(cfg.vocab_size),
        max_batch_size=sz.slots, engine_max_seq_len=sz.max_seq_len,
        prefill_bucket=PREFILL_BUCKET, kv_block_size=BLOCK,
        prefix_cache_blocks=PREFIX_BLOCKS, pipeline_decode=True)
    server.run("127.0.0.1", 0, block=False)
    try:
        port = server.port
        # Warm up with the window's own traffic over other tokens until a
        # whole round compiles nothing.  One round is not enough: which
        # executables the scheduler reaches depends on what is in flight
        # when a request joins (engine.py:_merge_pending), and a round
        # that stops to compile joins differently from one that does not.
        rounds = 0
        while True:
            rounds += 1
            if rounds > 4:
                raise Fail("four warm-up rounds and still compiling")
            t0, c0 = time.perf_counter(), clock.seconds
            with no_recompiles(allow=10**6) as counter:
                serve_round(port,
                            prompts_for(sz, cfg.vocab_size, seed + rounds),
                            sz.new_tokens)
            say(f"warm-up round {rounds}: {time.perf_counter() - t0:.1f} "
                f"s, {counter.count} executables, "
                f"{clock.seconds - c0:.1f} s compile")
            if counter.count == 0:
                break
        prompts = prompts_for(sz, cfg.vocab_size, seed)
        t0 = time.perf_counter()
        with no_recompiles() as counter:
            got = serve_round(port, prompts, sz.new_tokens)
        say(f"serving window: {len(got)} PUT /api requests in "
            f"{time.perf_counter() - t0:.1f} s")
        check(counter.count == 0,
              "zero compilations after warm-up in the serving window")
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=60) as resp:
            snap = json.loads(resp.read())
        say(f"GET /metrics: completed={snap['completed']} "
            f"paged_steps={snap['paged_steps']} "
            f"fallback_steps={snap['fallback_steps']} "
            f"prefix_hits={snap['prefix_hits']} "
            f"cow_copies_total={snap['cow_copies_total']} "
            f"decode_tokens={snap['decode_tokens']}")
        check(snap["completed"] == 6 * (rounds + 1),
              f"all {6 * (rounds + 1)} requests of {rounds + 1} rounds "
              f"completed")
        check(snap["prefix_hits"] == rounds + 1,
              "B took the shared prefix from the cache in every round")
        exercise_cow(server.service.engine)
        memory("server up")
    finally:
        server.shutdown()

    for name, r in got.items():
        p = prompts[name]
        check(r["tokens"][:len(p)] == p
              and len(r["tokens"]) == len(p) + sz.new_tokens,
              f"{name}: prompt of {len(p)} echoed, {sz.new_tokens} "
              f"new tokens")
    ref = reference_logprobs(cfg, params,
                             {n: r["tokens"] for n, r in got.items()})
    worst = 0.0
    for name, r in got.items():
        if r["logprobs"] is None:
            continue
        d = np.abs(np.asarray(r["logprobs"]) - ref[name])
        check(bool(np.all(np.isfinite(d))), f"{name}: logprobs finite")
        worst = max(worst, float(d.max()))
    check(worst <= LOGPROB_TOL,
          f"engine vs one-shot logprobs of the chosen tokens (prompt and "
          f"generated): max distance {worst:.4f} <= tolerance "
          f"{LOGPROB_TOL}")
    # B returned no logprobs (that is what let it use the prefix cache);
    # it must agree with its cold twin C until a tie, judged by the
    # reference's log-probs of both continuations
    b, c = got["B"]["tokens"], got["C"]["tokens"]
    split = next((i for i in range(len(b)) if b[i] != c[i]), None)
    if split is None:
        say("B (prefix-cache hit) and C (cold) chose the same tokens")
    else:
        gap = abs(float(ref["B"][split - 1] - ref["C"][split - 1]))
        check(gap <= LOGPROB_TOL,
              f"B and C part at token {split}, a tie by the reference: "
              f"log-probs {gap:.4f} apart <= {LOGPROB_TOL}")
    del params, server


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def four_chip_train(sz: Sizes, seed: int, on_tpu: bool, reduced: list):
    from megatron_llm_tpu.config import ParallelConfig

    layers, steps, gb = 2, 3, 2
    reduced.append(f"trainer num_layers 32 -> {layers}")
    layouts = {
        "one device": (ParallelConfig(), 2),
        "dp2 x tp2 (sequence parallel, distributed optimizer)": (
            ParallelConfig(data_parallel=2, tensor_parallel=2,
                           sequence_parallel=True,
                           use_distributed_optimizer=True), 1),
        "pp2 x tp2 (1F1B)": (
            ParallelConfig(pipeline_parallel=2, tensor_parallel=2,
                           num_microbatches=2), 1),
    }
    losses = {}
    for name, (parallel, mb) in layouts.items():
        say(f"train layout: {name}")
        cfg = train_config(sz, layers=layers, mb=mb, seq=sz.train_seq,
                           steps=steps, impl="flash", seed=seed,
                           parallel=parallel, global_batch=gb)
        check(plan_train(cfg, f"train {name}", True, on_tpu) is not None,
              f"{name}: the step fits")
        losses[name] = run_pretrain(cfg, token_set(cfg, seed))
    ref = losses.pop("one device")
    for name, got in losses.items():
        worst = max(abs(a - b) for a, b in zip(got, ref))
        check(worst <= LOSS_TOL,
              f"{name} vs one device, loss per step: max distance "
              f"{worst:.5f} <= tolerance {LOSS_TOL}")


def cluster_answers(cfg, params, ec, specs, devices):
    """The requests through two tp=2 replicas behind the router.  Its own
    function so that no local outlives it: the engines hold their
    devices' memory for as long as anything references them."""
    import jax
    from megatron_llm_tpu.config import ParallelConfig
    from megatron_llm_tpu.serving import build_cluster

    router = build_cluster(cfg, params, ec, replicas=2,
                           parallel=ParallelConfig(tensor_parallel=2))
    router.start()
    try:
        got = [h.result(timeout=900) for h in router.submit_many(specs)]
        for i, rep in enumerate(router.replicas):
            own = set(devices[2 * i:2 * i + 2])
            placed = [a.sharding.device_set for a in jax.tree.leaves(
                (rep.engine.params, rep.engine.slots.k_pool,
                 rep.engine.slots.v_pool))]
            check(all(s == own for s in placed),
                  f"replica {i}: params and pool live on devices "
                  f"{sorted(d.id for d in own)} and nowhere else")
        snap = router.snapshot()["router"]
        say("router: " + " ".join(
            f"{k}={snap[k]}" for k in ("replicas", "usable", "routed_total",
                                       "completed_total",
                                       "failovers_total")))
        # a replica that dies must not hide behind the router's failover
        check(snap["completed_total"] == len(specs) and snap["usable"] == 2
              and snap["failovers_total"] == 0,
              f"the router completed all {len(specs)} requests on two "
              f"live replicas, no failover")
        memory("cluster up", devices)
    finally:
        router.shutdown()
    return got


def single_answers(cfg, params, ec, specs, device):
    """The same requests through one engine on one device."""
    import jax
    from megatron_llm_tpu.serving import ServingEngine

    engine = ServingEngine(cfg, jax.device_put(params, device), ec)
    try:
        return [h.result(timeout=900) for h in engine.submit_many(specs)]
    finally:
        engine.shutdown()


def four_chip_serve(sz: Sizes, seed: int, reduced: list):
    import jax
    import numpy as np
    from megatron_llm_tpu.models import model as model_lib
    from megatron_llm_tpu.serving import EngineConfig

    devices = jax.devices()
    cfg = serve_config(sz, sz.serve_depths[-1])
    say(f"cluster: num_layers {cfg.num_layers}, 2 replicas x tp=2")
    reduced.append(f"cluster num_layers 32 -> {cfg.num_layers}")
    # host copy: the replicas place their own shards, device 0 keeps none
    params = jax.device_get(
        model_lib.init_params(jax.random.key(seed), cfg))
    ec = EngineConfig(max_batch_size=sz.slots, max_seq_len=sz.max_seq_len,
                      prefill_bucket=PREFILL_BUCKET, kv_block_size=BLOCK)
    prompts = prompts_for(sz, cfg.vocab_size, seed)
    specs = [dict(prompt=prompts[n], max_new_tokens=sz.new_tokens,
                  use_eos_stop=False, return_logprobs=True)
             for n in ("R0", "R1", "A", "C")]

    got = cluster_answers(cfg, params, ec, specs, devices)
    gc.collect()
    memory("cluster shut down", devices)
    want = single_answers(cfg, params, ec, specs, devices[0])
    gc.collect()
    worst = 0.0
    for g, w, spec in zip(got, want, specs):
        check(g.finish_reason == "length" and w.finish_reason == "length",
              f"prompt of {len(spec['prompt'])}: both finished by length")
        n = next((i for i in range(len(g.tokens))
                  if g.tokens[i] != w.tokens[i]), len(g.tokens))
        # logprobs[i] belongs to token i + 1: compared while the tokens
        # before it agree, the parting token included (a tie if close)
        d = np.abs(np.asarray(g.logprobs[:n]) - np.asarray(w.logprobs[:n]))
        worst = max(worst, float(d.max()))
        say(f"prompt of {len(spec['prompt'])}: tokens agree for {n} of "
            f"{len(g.tokens)}; max logprob distance {float(d.max()):.4f}")
    check(worst <= LOGPROB_TOL,
          f"tp=2 replicas vs one-device engine: max logprob distance "
          f"{worst:.4f} <= tolerance {LOGPROB_TOL}")


# ---------------------------------------------------------------------------

def run(args, device: dict, reduced: list) -> None:
    import jax
    from megatron_llm_tpu.utils.compile_cache import enable_compile_cache

    if args.cpu_rehearsal:
        # before the backend starts: the rehearsal sees the CPU and only
        # the CPU, so it can never report (or hold) a TPU
        jax.config.update("jax_platforms", "cpu")
    devs = jax.devices()
    device.update(platform=devs[0].platform, kind=devs[0].device_kind,
                  count=len(devs))
    on_tpu = devs[0].platform == "tpu"
    say(f"jax {jax.__version__}; devices: {len(devs)} x "
        f"{devs[0].device_kind} ({devs[0].platform})")
    if not on_tpu and not args.cpu_rehearsal:
        raise Fail(f"no TPU: jax.devices()[0].platform is "
                   f"{devs[0].platform!r}")
    if len(devs) < args.chips:
        raise Fail(f"--chips {args.chips} needs {args.chips} devices, "
                   f"jax sees {len(devs)}")
    sz = TINY if args.cpu_rehearsal else REAL
    say(f"compile cache: {enable_compile_cache()}")
    clock = CompileClock()
    memory("at start", devs if args.chips == 4 else None)

    if args.chips == 4:
        with phase("four chips: training layouts", clock):
            four_chip_train(sz, args.seed, on_tpu, reduced)
        memory("after training layouts", devs)
        with phase("four chips: two tp=2 replicas behind the router",
                   clock):
            four_chip_serve(sz, args.seed, reduced)
        memory("at end", devs)
        return
    with phase("trainer", clock):
        trainer_phase(sz, args.seed, on_tpu, reduced)
    released("after the trainer phase")
    with phase("server", clock):
        server_phase(sz, args.seed, on_tpu, reduced, clock)
    released("after the server phase")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="4: the sharded paths and what they are compared "
                         "with, and no other phase")
    ap.add_argument("--seed", type=int, default=0,
                    help="weights, training tokens and prompts")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="tiny sizes on the CPU backend: finds wrong "
                         "paths, proves nothing about the chip")
    args = ap.parse_args(argv)
    device, reduced = {}, []
    try:
        run(args, device, reduced)
    except BaseException as e:  # noqa: BLE001 — reported, then exit 1
        traceback.print_exc()
        sys.stderr.flush()
        say(f"FAILED: {type(e).__name__}: {e}")
        print(json.dumps({"ok": False, "device": device,
                          "error": f"{type(e).__name__}: {e}"[:500]}),
              flush=True)
        return 1
    say("reduced: " + "; ".join(reduced))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
