"""Benchmark: training + serving throughput on one TPU chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...extras}.

Headline anchor (BASELINE.md): the reference trains Llama-2-7B on 8× A100-80GB
at ≈890 tokens/s/GPU (bf16, flash-attn, sequence-parallel, selective
recompute) ⇒ model FLOPs utilization ≈ 0.12 of A100 bf16 peak (312 TFLOP/s)
counting 6·N·D + attention FLOPs with the reference's recompute settings.
A single v5e chip cannot hold 7B training state, so the bench trains a
Llama-architecture model sized to the chip and reports **MFU**, which is the
hardware-normalized apples-to-apples number; vs_baseline = our MFU / 0.12.

Besides the headline (seq 1024, the reference's finetune config), the JSON
carries: a seq-length MFU curve through 32k (BASELINE config 4's
long-context regime), a 7B-width training row, decode rows (bf16 via the
fused whole-stack Pallas decode kernel, int8, and 7B-width), prompt-lookup
speculative decoding rows on repetitive/random prompt mixes, and prefill
at both the decode point's 128-token prompts and an amortized 1024-token
prompt with its own MFU.

Process isolation (round 5): every point runs in a SUBPROCESS.  Round-5's
first in-process run had the 32k row's HBM footprint leak into every
subsequent point (ResourceExhausted on even the small decode jobs despite
del + clear_caches — intermittent; round 4 ran the same sequence clean).
A fresh backend per point makes the record insensitive to allocator state,
and a hung point is killed by the parent's timeout instead of sinking the
whole record.

Measurement notes (v5e, 2026-07, don't re-derive):
- head_dim 128 beats 64 by +24% MFU (MXU lane width); mb=12 beats 8/16.
- Rounds 1-5 paid ~0.8-1.1 ms per DISPATCH on the host side, so their
  decode rates put the token loop on-device inside one executable
  (lax.while_loop / fori_loop) — per-step dispatches timed the host, not
  the chip.  Not re-measured with the chip attached directly.
- Decode was op-chain-bound (~100us/layer vs 38us/layer read floor); the
  fused decode-step kernel (kernels/decode_step.py) removes the chain
  (93us/layer measured in-loop, 2.4x end-to-end).  Sibling-GEMV fusion
  measured 1.01x (XLA already overlaps independent matmuls) — dead end.
- The decode rate subtracts a separately-timed prefill; at a 128-token
  horizon the subtraction amplifies timing jitter ±40%, so the horizon is
  512 tokens (prefill correction ~few %).
"""

from __future__ import annotations

import json
import sys
import time

import numpy as np


def _model_flops_per_token(cfg, seq_len: int) -> float:
    """6·N·D-style training FLOPs/token (fwd+bwd = 3× fwd) + attention."""
    h = cfg.hidden_size
    d = cfg.head_dim
    nq = cfg.num_attention_heads
    nkv = cfg.kv_heads
    ffn = cfg.ffn_size
    n_mlp = 3 if cfg.is_glu else 2
    per_layer_fwd = (
        2 * h * (nq * d) + 2 * 2 * h * (nkv * d) + 2 * (nq * d) * h
        + n_mlp * 2 * h * ffn
        + 2 * 2 * nq * d * seq_len  # scores + context, causal-halved ×2
    )
    fwd = cfg.num_layers * per_layer_fwd + 2 * h * cfg.padded_vocab_size()
    return 3.0 * fwd  # fwd + bwd


def chip_peak_flops(device_kind: str) -> float:
    """bf16 peak FLOP/s per chip for MFU normalization (also used by the
    tests_tpu MFU regression guard)."""
    peaks = {
        "v5 lite": 197e12, "v5e": 197e12,
        "v5p": 459e12, "v5": 459e12,
        "v4": 275e12, "v6e": 918e12, "v6 lite": 918e12,
    }
    kind = device_kind.lower().replace("tpu ", "")
    return next((v for k, v in peaks.items() if k in kind), 197e12)


def chip_hbm_bandwidth(device_kind: str) -> float:
    """HBM bytes/s per chip, for the decode bandwidth roofline."""
    bws = {
        "v5 lite": 819e9, "v5e": 819e9,
        "v5p": 2765e9, "v5": 2765e9,
        "v4": 1228e9, "v6e": 1640e9, "v6 lite": 1640e9,
    }
    kind = device_kind.lower().replace("tpu ", "")
    return next((v for k, v in bws.items() if k in kind), 819e9)


def _bench_model(seq: int, recompute: str):
    from megatron_llm_tpu.config import llama2_config

    # Llama-architecture model sized to one chip.  8 heads × d=128 (not
    # 16 × 64): the 128-wide head dim matches the MXU lane width and
    # measures ~24% faster at identical params/FLOPs.
    return llama2_config(
        "7b",
        hidden_size=1024,
        num_layers=24,
        num_attention_heads=8,
        num_kv_heads=8,
        ffn_hidden_size=2816,
        seq_length=seq,
        max_position_embeddings=seq,
        params_dtype="bfloat16",
        attention_impl="flash",
        recompute=recompute,
    )


def _bench_model_7b_width(seq: int, num_layers: int,
                          recompute: str = "selective"):
    """Llama-2-7B *width* (hidden 4096, ffn 11008, 32 q-heads × d128) at
    reduced depth so the state fits one chip; GQA (8 kv-heads) trims the
    kv projections the way the 34B/70B presets do.  MFU / decode rates at
    this width are the numbers comparable to the BASELINE 7B configs —
    per-layer matmul shapes are exactly the 7B ones, depth repeats them."""
    from megatron_llm_tpu.config import llama2_config

    return llama2_config(
        "7b",
        hidden_size=4096,
        num_layers=num_layers,
        num_attention_heads=32,
        num_kv_heads=8,
        ffn_hidden_size=11008,
        seq_length=seq,
        max_position_embeddings=seq,
        params_dtype="bfloat16",
        attention_impl="flash",
        recompute=recompute,
    )


def _train_point(seq: int, mb: int, recompute: str, iters: int, peak: float,
                 wide_layers: int = 0):
    """One training-throughput measurement → (tokens/sec, mfu, loss, n)."""
    import jax
    import jax.numpy as jnp

    from megatron_llm_tpu.config import (
        OptimizerConfig,
        ParallelConfig,
        RuntimeConfig,
        TrainConfig,
    )
    from megatron_llm_tpu.models import model as model_lib
    from megatron_llm_tpu.training.step import init_train_state, make_train_step

    model = (_bench_model_7b_width(seq, wide_layers, recompute)
             if wide_layers else _bench_model(seq, recompute))
    cfg = RuntimeConfig(
        model=model,
        parallel=ParallelConfig(),
        optimizer=OptimizerConfig(lr=1e-4, clip_grad=1.0),
        train=TrainConfig(train_iters=100, micro_batch_size=mb,
                          global_batch_size=mb, seq_length=seq),
    ).validate()

    params = model_lib.init_params(jax.random.key(0), cfg.model)
    n_params = sum(p.size for p in jax.tree.leaves(params))
    state = init_train_state(cfg, params)
    step = make_train_step(cfg)

    rng = np.random.default_rng(0)
    shape = (1, mb, seq)  # one microbatch per step
    tokens = rng.integers(0, cfg.model.vocab_size, shape)
    batch = {
        "tokens": jnp.asarray(tokens, jnp.int32),
        "labels": jnp.asarray(np.roll(tokens, -1, -1), jnp.int32),
        "loss_mask": jnp.ones(shape, jnp.float32),
    }
    key = jax.random.key(0)

    # warmup / compile — two steps: the first compiles, the second flushes
    # remaining lazy one-time work (allocator growth, executable warm-in)
    state, metrics = step(state, batch, key)
    float(metrics["loss"])
    state, metrics = step(state, batch, key)
    float(metrics["loss"])

    # Timing via an explicit host fetch of the last loss: the steps chain
    # through the donated state, so the fetch transitively waits for all.
    t0 = time.perf_counter()
    for _ in range(iters):
        state, metrics = step(state, batch, key)
    loss = float(metrics["loss"])
    dt = time.perf_counter() - t0

    tokens_per_sec = iters * mb * seq / dt
    mfu = tokens_per_sec * _model_flops_per_token(cfg.model, seq) / peak
    return tokens_per_sec, mfu, loss, n_params


def _decode_roofline_tps(cfg, param_bytes: int, batch: int,
                         avg_cache_len: int, hbm_bw: float) -> float:
    """Bandwidth-bound decode tokens/s: each decode step must stream the
    weights once (shared across the batch; ``param_bytes`` = actual stored
    bytes, so int8 quantization moves the roofline) plus each sequence's
    KV cache; tokens/s = batch / (bytes_per_step / HBM_BW).  Compute and
    the int32 token traffic are negligible beside these two terms, so the
    bound is tight for small batches (the reference publishes no decode
    number; this roofline is the stated target per BASELINE.md)."""
    kv_elt_bytes = (1 + 4 / cfg.head_dim
                    if cfg.kv_cache_quant == "int8" else 2)
    kv_bytes = int(batch * 2 * cfg.num_layers * cfg.kv_heads
                   * cfg.head_dim * avg_cache_len * kv_elt_bytes)
    return batch / ((param_bytes + kv_bytes) / hbm_bw)


def _audited_decode_bytes(cfg, params, batch: int, avg_cache_len: int):
    """Per-step bytes a decode step actually streams → (weight_bytes,
    kv_bytes, by_class).  The naive roofline denominator (sum of every
    stored param byte + analytic KV bytes) overstates quantized decode
    traffic in one place: the word-embedding table.  Decode *gathers*
    ``batch`` rows of it per step — the full table only streams when it
    doubles as the unembedding matrix (tied embeddings).  Weight leaves
    are counted at stored width, so an int8 {q, scale} subtree
    contributes 1 byte/element + its scales and an int4 one ½ byte +
    group scales; KV bytes come from the cache's own per-position leaf
    sizes (exact {q, scale} traffic for int8 caches) rather than an
    analytic elt-size formula.

    ``by_class`` splits the weight term per tensor class — attn / mlp /
    embedding / norms / other — each as {"bytes", "precision"}, so a
    record shows *where* the decode bytes gap lives (round 9: with int8
    attn+MLP the embedding table and norms dominate the residual)."""
    import jax

    from megatron_llm_tpu.models import model as model_lib
    from megatron_llm_tpu.ops import quant

    def stored(leaf) -> int:
        if isinstance(leaf, dict):
            return sum(a.size * a.dtype.itemsize
                       for a in jax.tree.leaves(leaf))
        return leaf.size * leaf.dtype.itemsize

    def precision(leaf) -> str:
        if isinstance(leaf, dict):
            return f"int{quant.weight_bits(leaf)}"
        return str(leaf.dtype)

    by_class: dict = {}

    def tally(cls: str, nbytes: int, prec: str) -> None:
        row = by_class.setdefault(cls, {"bytes": 0, "precision": set()})
        row["bytes"] += int(nbytes)
        row["precision"].add(prec)

    flat, _ = jax.tree_util.tree_flatten_with_path(
        params, is_leaf=quant.is_quantized)
    weight_bytes = 0
    for path, leaf in flat:
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        if "embedding" in name or "lm_head" in name:
            cls = "embedding"
        elif "norm" in name:
            cls = "norms"
        elif "attn" in name:
            cls = "attn"
        elif "mlp" in name:
            cls = "mlp"
        else:
            cls = "other"
        nbytes = stored(leaf)
        weight_bytes += nbytes
        tally(cls, nbytes, precision(leaf))

    word = params["embedding"]["word"]
    if not cfg.tie_embed_logits:
        stored_word = stored(word)
        if isinstance(word, dict):
            # int8-resident table: gather streams batch quantized rows
            # plus their per-row scales (ops/quant.py:embedding_lookup)
            gathered = batch * (
                word["q"].shape[-1] * word["q"].dtype.itemsize
                + word["scale"].dtype.itemsize)
        else:
            gathered = batch * word.shape[-1] * word.dtype.itemsize
        weight_bytes += gathered - stored_word
        by_class["embedding"]["bytes"] += gathered - stored_word
    for row in by_class.values():
        row["precision"] = "+".join(sorted(row["precision"]))
    # one cache position's stored bytes across all layers/heads/sides
    k1, v1 = model_lib.init_kv_cache(cfg, batch, 1)
    per_pos = sum(a.size * a.dtype.itemsize
                  for a in jax.tree.leaves((k1, v1)))
    return int(weight_bytes), int(per_pos * avg_cache_len), by_class


def _min_time(run, n=3):
    """Best-of-n wall time: host latency drifted between runs in rounds
    1-5, and subtraction-based rates amplify single-shot jitter — minimums of
    repeated samples keep the record off the noise tails."""
    import jax

    best = float("inf")
    for _ in range(n):
        t0 = time.perf_counter()
        jax.device_get(run())
        best = min(best, time.perf_counter() - t0)
    return best


def _decode_point(hbm_bw: float, quantize=False,
                  wide_layers: int = 0):
    """→ dict with decode tokens/sec, roofline tokens/sec, prefill
    tokens/sec.  ``quantize`` names a weight precision policy
    (ops/quant.py:POLICIES — "int8", "int4", "mixed"; ``True`` is
    accepted as "int8" for pre-v5 specs); any policy also puts the KV
    cache (ops/kv_quant.py) at int8, and every roofline term shrinks.
    With ``wide_layers`` the model is 7B-width at that depth (the fused
    decode kernel bows out on VMEM fit; the composed path serves)."""
    import jax
    import jax.numpy as jnp

    from megatron_llm_tpu.models import model as model_lib
    from megatron_llm_tpu.generation.generation import generate_tokens

    if quantize is True:
        quantize = "int8"

    # gen 512 (not 128): the decode rate is derived by subtracting a
    # separately-timed prefill from the full-generate window; at 512
    # steps the prefill correction is a few percent (see module notes).
    b, prompt_len, gen_len = 8, 128, 512
    cfg = (_bench_model_7b_width(prompt_len + gen_len, wide_layers)
           if wide_layers else _bench_model(prompt_len + gen_len,
                                            "selective"))
    if quantize:
        import dataclasses

        cfg = dataclasses.replace(cfg, kv_cache_quant="int8").validate()
    params = model_lib.init_params(jax.random.key(0), cfg)
    if quantize:
        from megatron_llm_tpu.ops.quant import (quantize_params,
                                                resolve_policy)

        params = quantize_params(params, resolve_policy(quantize))

    rng = np.random.default_rng(1)
    tokens = np.zeros((b, prompt_len + gen_len), np.int32)
    tokens[:, :prompt_len] = rng.integers(1, cfg.vocab_size,
                                          (b, prompt_len))
    tokens = jnp.asarray(tokens)
    lengths = jnp.full((b,), prompt_len, jnp.int32)

    out = generate_tokens(cfg, params, tokens, lengths,
                          use_eos_stop=False)  # warmup/compile
    jax.device_get(out.tokens)
    dt_full = _min_time(lambda: generate_tokens(
        cfg, params, tokens, lengths, use_eos_stop=False).tokens)

    # The roofline models per-step decode streaming only, so subtract the
    # prefill forward (the same [b, prompt_len] cached forward the
    # generate loop runs before its first decode step).
    rope = model_lib.rope_tables(cfg)

    @jax.jit
    def prefill(p, toks):
        k, v = model_lib.init_kv_cache(cfg, b, prompt_len + gen_len)
        logits, k, v = model_lib.forward_cached(
            cfg, p, toks, k, v, jnp.int32(0), rope=rope, empty_cache=True,
            last_logit_only=True)
        return logits[:, -1]

    jax.device_get(prefill(params, tokens[:, :prompt_len]))  # compile
    dt_prefill = _min_time(lambda: prefill(params, tokens[:, :prompt_len]))

    dt = max(dt_full - dt_prefill, 1e-9)
    tps = b * gen_len / dt
    prefill_tps = b * prompt_len / max(dt_prefill, 1e-9)
    param_bytes = sum(p.size * p.dtype.itemsize
                      for p in jax.tree.leaves(params))
    roof = _decode_roofline_tps(cfg, param_bytes, b,
                                prompt_len + gen_len // 2, hbm_bw)
    n_params = sum(p.size for p in jax.tree.leaves(params))
    result = {
        "tokens_per_sec": round(tps, 1),
        "roofline_tokens_per_sec": round(roof, 1),
        "roofline_frac": round(tps / roof, 4),
        "prefill_tokens_per_sec": round(prefill_tps, 1),
        "model_params": n_params,
    }
    if quantize:
        # per-step bytes-moved audit for the quantized points: the naive
        # denominator streams the (untied, gathered-not-streamed) word
        # embedding table every step, understating roofline_frac; the
        # audited denominator counts actual {q, scale} traffic
        # (docs/inference.md files the residual gap as a measured number)
        weight_bytes, kv_bytes, by_class = _audited_decode_bytes(
            cfg, params, b, prompt_len + gen_len // 2)
        roof_a = b * hbm_bw / (weight_bytes + kv_bytes)
        result.update({
            "step_weight_bytes": weight_bytes,
            "step_kv_bytes": kv_bytes,
            "step_bytes_by_class": by_class,
            "naive_roofline_frac": result["roofline_frac"],
            "roofline_tokens_per_sec": round(roof_a, 1),
            "roofline_frac": round(tps / roof_a, 4),
        })
    return result


def _pld_point(wide_layers: int = 0):
    """Prompt-lookup speculative decoding → dict of tokens/verify-forward,
    effective tok/s and full-window speedup vs the plain greedy loop, on a
    repetitive prompt mix (n-gram lookup can hit) and an incompressible
    random mix (it can't — measures graceful degradation).  All greedy,
    512-token horizon.

    Two rows ride in the record: the 374M bench model (random-init
    acceptance is measurable there: ~1.4-1.9 tokens/verify) and 7B width
    (acceptance on a RANDOM-INIT model is ~1.0 — its greedy continuation
    of a repeated motif does not repeat — so that row evidences graceful
    degradation: speedup ~0.998, i.e. the verify overhead is free).  Note
    the fused decode-step kernel now accelerates the 374M plain loop past
    PLD's composed-path verifies (measured 0.89x/0.69x); a fused
    multi-token verify step would recompose them (noted future work,
    kernels/decode_step.py)."""
    import jax
    import jax.numpy as jnp

    from megatron_llm_tpu.models import model as model_lib
    from megatron_llm_tpu.generation.generation import generate_tokens
    from megatron_llm_tpu.generation.speculative import generate_tokens_pld

    b, prompt_len, gen_len = 8, 128, 512
    cfg = (_bench_model_7b_width(prompt_len + gen_len, wide_layers)
           if wide_layers else _bench_model(prompt_len + gen_len,
                                            "selective"))
    params = model_lib.init_params(jax.random.key(0), cfg)
    rng = np.random.default_rng(2)

    def make_tokens(repetitive: bool):
        tokens = np.zeros((b, prompt_len + gen_len), np.int32)
        if repetitive:
            motif = rng.integers(1, cfg.vocab_size, (b, 16))
            tokens[:, :prompt_len] = np.tile(motif, (1, prompt_len // 16))
        else:
            tokens[:, :prompt_len] = rng.integers(1, cfg.vocab_size,
                                                  (b, prompt_len))
        return jnp.asarray(tokens), jnp.full((b,), prompt_len, jnp.int32)

    result = {"pld_model_width": cfg.hidden_size,
              "pld_model_layers": cfg.num_layers}
    for name, repetitive in (("repetitive", True), ("random", False)):
        tokens, lengths = make_tokens(repetitive)
        out = generate_tokens_pld(cfg, params, tokens, lengths,
                                  use_eos_stop=False)
        steps = float(np.max(np.asarray(out.steps)))
        dt_pld = _min_time(lambda: generate_tokens_pld(
            cfg, params, tokens, lengths, use_eos_stop=False).tokens, n=2)
        out2 = generate_tokens(cfg, params, tokens, lengths,
                               use_eos_stop=False)
        jax.device_get(out2.tokens)
        dt_plain = _min_time(lambda: generate_tokens(
            cfg, params, tokens, lengths, use_eos_stop=False).tokens, n=2)
        result[f"pld_tokens_per_verify_{name}"] = round(gen_len / steps, 2)
        result[f"pld_tokens_per_sec_{name}"] = round(b * gen_len / dt_pld, 1)
        result[f"pld_speedup_{name}"] = round(dt_plain / dt_pld, 3)
    return result


def _prefill_point(peak: float):
    """Amortized prefill: one cached forward over 1024-token prompts
    (b=8) → tokens/sec + prefill MFU.  The decode point's 128-token
    prompt prefill is dominated by dispatch latency; this is the
    capability number (VERDICT r4 weak #4)."""
    import jax
    import jax.numpy as jnp

    from megatron_llm_tpu.models import model as model_lib

    b, prompt_len = 8, 1024
    cfg = _bench_model(prompt_len + 128, "selective")
    params = model_lib.init_params(jax.random.key(0), cfg)
    rope = model_lib.rope_tables(cfg)
    rng = np.random.default_rng(3)
    toks = jnp.asarray(rng.integers(1, cfg.vocab_size, (b, prompt_len)),
                       jnp.int32)

    @jax.jit
    def prefill(p, toks):
        k, v = model_lib.init_kv_cache(cfg, b, prompt_len + 128)
        logits, k, v = model_lib.forward_cached(
            cfg, p, toks, k, v, jnp.int32(0), rope=rope, empty_cache=True,
            last_logit_only=True)
        return logits[:, -1]

    jax.device_get(prefill(params, toks))  # compile
    dt = _min_time(lambda: prefill(params, toks), n=5)
    tps = b * prompt_len / dt
    fwd_flops = _model_flops_per_token(cfg, prompt_len) / 3.0
    return {
        "prefill_long_tokens_per_sec": round(tps, 1),
        "prefill_long_mfu": round(tps * fwd_flops / peak, 4),
    }


def _serving_point():
    """Continuous-batching serving throughput (megatron_llm_tpu/serving/):
    24 concurrent requests over 8 KV slots → requests/s, aggregate decode
    tokens/s, mean/p95 per-token latency, TTFT, and the max per-iteration
    decode batch.  Unlike the one-shot decode row (a single fixed batch in
    one jitted loop), this pays per-iteration host scheduling — the number
    a real traffic mix gets from the engine the REST server now runs."""
    import jax

    from megatron_llm_tpu.models import model as model_lib
    from megatron_llm_tpu.serving.bench import run_serving_bench

    prompt_len, gen_len = 128, 128
    cfg = _bench_model(prompt_len + gen_len, "selective")
    params = model_lib.init_params(jax.random.key(0), cfg)
    return run_serving_bench(cfg, params, num_requests=24,
                             prompt_len=prompt_len, gen_len=gen_len,
                             slots=8)


def _serving_mixed_point(quantize: bool = False):
    """Mixed-workload serving (megatron_llm_tpu/serving/bench.py): varied
    prompt lengths with the long prompts arriving mid-decode, chunked
    prefill + pipelined decode on → aggregate tok/s, TTFT and ITL
    p50/p99, and the device/host step breakdown (device_idle_frac ~0 is
    the pipelining evidence).  This is the point where chunked prefill's
    ITL effect is visible: without it every long admission freezes the
    active streams for a whole-prompt prefill.

    With ``quantize`` the model serves fully int8-resident (int8 weights
    + int8 KV), the configuration the fused decode kernel's int8 path
    targets — the engine's fused_steps counter tells whether the slot
    batch actually took it.

    The plain (non-int8) point also reruns the identical workload with
    the span recorder off (trace=False) and stamps the untraced ITL
    percentiles into the same dict — the traced/untraced pair feeds the
    --compare tracing-overhead gate (docs/observability.md)."""
    import jax

    from megatron_llm_tpu.models import model as model_lib
    from megatron_llm_tpu.serving.bench import run_mixed_serving_bench

    max_prompt_len, gen_len = 256, 64
    cfg = _bench_model(max_prompt_len + gen_len, "selective")
    if quantize:
        import dataclasses

        cfg = dataclasses.replace(cfg, kv_cache_quant="int8").validate()
    params = model_lib.init_params(jax.random.key(0), cfg)
    if quantize:
        from megatron_llm_tpu.ops.quant import quantize_params

        params = quantize_params(params)
    out = run_mixed_serving_bench(cfg, params, num_requests=24,
                                  gen_len=gen_len, slots=8,
                                  max_prompt_len=max_prompt_len,
                                  prefill_chunk=64)
    if not quantize:
        # same workload, recorder off; jit caches are warm from the
        # traced run so this pays only its measurement window
        bare = run_mixed_serving_bench(cfg, params, num_requests=24,
                                       gen_len=gen_len, slots=8,
                                       max_prompt_len=max_prompt_len,
                                       prefill_chunk=64, trace=False)
        out["serving_mixed_itl_ms_p50_untraced"] = \
            bare["serving_mixed_itl_ms_p50"]
        out["serving_mixed_itl_ms_p99_untraced"] = \
            bare["serving_mixed_itl_ms_p99"]
    return out


def _serving_prefix_point():
    """Prefix-cache serving point (serving/prefix_cache.py): a wave of
    requests sharing one 896-token system prompt (64-token blocks) vs a
    wave with distinct prefixes, each request timed submit -> first
    token.  Headline ``serving_prefix_ttft_speedup`` = cold TTFT p50 /
    hit TTFT p50 — the acceptance bar is ≥ 3x at this geometry (the hit
    path runs one fused cache-assembly dispatch plus a 64-token bucket
    prefill instead of 928 prompt rows) — plus the hit rate; both gate
    in --compare."""
    import jax

    from megatron_llm_tpu.models import model as model_lib
    from megatron_llm_tpu.serving.bench import run_prefix_serving_bench

    shared_len, unique_len, gen_len = 896, 32, 16
    cfg = _bench_model(shared_len + unique_len + gen_len + 64, "selective")
    params = model_lib.init_params(jax.random.key(0), cfg)
    return run_prefix_serving_bench(
        cfg, params, num_requests=16, shared_len=shared_len,
        unique_len=unique_len, gen_len=gen_len, slots=8, block=64)


def _serving_paged_point():
    """Paged-KV serving point (serving/block_pool.py): mixed
    32/512/4096-token traffic at a FIXED HBM pool budget, paged 64-token
    blocks vs the fixed-stride baseline (``kv_block_size = max_seq_len``,
    the pre-paging one-row-per-slot layout) at the same pool bytes.
    Fixed stride pins a full max-length row per request whatever its real
    length, capping concurrency at the pool's whole-sequence count;
    paging allocates per 64 tokens of actual fill.  Headline
    ``serving_paged_max_concurrency`` gates in --compare; the acceptance
    bar is ≥ 2x the fixed-stride concurrency at this geometry, with paged
    ITL p50 riding along for the latency story."""
    import jax

    from megatron_llm_tpu.models import model as model_lib
    from megatron_llm_tpu.serving.bench import run_paged_serving_bench

    gen_len = 64
    cfg = _bench_model(4096 + gen_len, "selective")
    params = model_lib.init_params(jax.random.key(0), cfg)
    return run_paged_serving_bench(
        cfg, params, num_requests=12, prompt_lens=(32, 512, 4096),
        gen_len=gen_len, kv_block_size=64, pool_seqs=4)


def _serving_spec_point():
    """Speculative-decoding serving point (serving/engine.py spec path):
    repetitive traffic (tiled 8-token motifs, the workload prompt-lookup
    drafting exists for) spec on vs off at identical engine geometry,
    plus an incompressible random-traffic control where the acceptance
    EWMA must back the batch off to the plain pipelined path.  Headline
    ``serving_spec_itl_speedup`` = off ITL p50 / on ITL p50 gates in
    --compare (acceptance bar ≥ 1.3x at this geometry), with the
    acceptance rate riding along; ``serving_spec_random_overhead`` is
    the enabled-but-useless cost and must stay ≤ 1.05."""
    import jax

    from megatron_llm_tpu.models import model as model_lib
    from megatron_llm_tpu.serving.bench import run_spec_serving_bench

    prompt_len, gen_len = 256, 128
    cfg = _bench_model(prompt_len + gen_len, "selective")
    params = model_lib.init_params(jax.random.key(0), cfg)
    return run_spec_serving_bench(
        cfg, params, num_requests=16, prompt_len=prompt_len,
        gen_len=gen_len, slots=8, draft_len=4, ngram=3)


def _serving_spec_tree_point(wide_layers: int = 0):
    """Resident-draft tree-speculation serving point (serving/engine.py
    draft path, docs/serving.md "Tree speculation & resident drafts"):
    draft on vs off at identical engine geometry on random AND
    repetitive traffic.  Random traffic is the headline — it is exactly
    where the n-gram drafter's acceptance is ~0 (the PLD ceiling), so
    ``serving_spec_tree_itl_speedup`` (draft-off ITL p50 / draft-on, on
    random prompts) gating in --compare is the beat-the-ceiling claim
    (acceptance bar > 1.0).  Runs at 7B width (hidden 4096, L8 depth,
    the decode_7b geometry) when ``wide_layers`` is set so the headline
    is quoted at deployment-relevant matmul shapes; the bench draft is
    the perfect-oracle self-draft (a random-init target has no
    distilled partner — see serving/bench.py)."""
    import jax

    from megatron_llm_tpu.models import model as model_lib
    from megatron_llm_tpu.serving.bench import run_spec_tree_serving_bench

    prompt_len, gen_len = 256, 128
    cfg = (_bench_model_7b_width(prompt_len + gen_len, wide_layers)
           if wide_layers else _bench_model(prompt_len + gen_len,
                                            "selective"))
    params = model_lib.init_params(jax.random.key(0), cfg)
    return run_spec_tree_serving_bench(
        cfg, params, num_requests=16, prompt_len=prompt_len,
        gen_len=gen_len, slots=8, draft_len=4)


def _serving_cluster_point():
    """Multi-chip serving point (serving/cluster/, docs/serving.md
    "Multi-chip serving"): mixed traffic through ``build_cluster`` at 1
    vs 2 engine replicas on disjoint device slices, plus per-device
    resident param bytes at tp=1 vs tp=2 under the serving re-layout.
    Headlines ``serving_cluster_qps_ratio`` (acceptance bar ≥ 1.8x at 2
    replicas on real multi-chip hardware; on the CPU device-count
    simulation all "devices" share the host cores, so the simulated
    ratio only tracks plumbing cost) and
    ``serving_cluster_tp_model_size_ratio`` (≈ 2.0: a 2x larger model
    per chip) gate in --compare."""
    import jax

    from megatron_llm_tpu.models import model as model_lib
    from megatron_llm_tpu.serving.bench import run_cluster_serving_bench

    n_dev = len(jax.devices())
    if n_dev < 2:
        return {"serving_cluster_skipped":
                f"needs >= 2 devices, have {n_dev}"}
    gen_len, max_prompt_len = 32, 128
    cfg = _bench_model(max_prompt_len + gen_len, "selective")
    params = model_lib.init_params(jax.random.key(0), cfg)
    return run_cluster_serving_bench(
        cfg, params, num_requests=16, gen_len=gen_len, slots=4,
        max_prompt_len=max_prompt_len, replicas=2, tp=2)


def _serving_pp_point():
    """Pipeline-parallel serving point (docs/serving.md
    "Pipeline-parallel decode"): pp=2 as a real serving axis vs tp=2 at
    EQUAL device count.  Headlines ``serving_pp_param_bytes_ratio``
    (≈ 2.0: the layer-sharded layout halves per-device resident param
    bytes, so a 2x larger model fits the same per-chip HBM) in
    --compare; the ITL-vs-tp pair and the bitwise flag ride along.  As
    with serving_cluster, the CPU device-count simulation shares host
    cores across "devices", so only the residency ratio is a hardware-
    faithful claim in simulated runs."""
    import jax

    from megatron_llm_tpu.models import model as model_lib
    from megatron_llm_tpu.serving.bench import run_pp_serving_bench

    n_dev = len(jax.devices())
    if n_dev < 2:
        return {"serving_pp_skipped":
                f"needs >= 2 devices, have {n_dev}"}
    gen_len, max_prompt_len = 32, 128
    cfg = _bench_model(max_prompt_len + gen_len, "selective")
    params = model_lib.init_params(jax.random.key(0), cfg)
    return run_pp_serving_bench(
        cfg, params, num_requests=16, gen_len=gen_len, slots=4,
        max_prompt_len=max_prompt_len, pp=2)


def _serving_disagg_point(platform: str):
    """Disaggregated prefill/decode point (serving/cluster/,
    docs/serving.md "Disaggregated prefill/decode"): long-prompt traffic
    through ``build_disagg_cluster`` (1 prefill + 1 decode replica) vs
    ``build_cluster`` (2 colocated replicas) at EQUAL device count, plus
    a prefill-chunk MFU sweep on a single engine.  Headlines
    ``serving_disagg_ttft_p99_ratio`` (colocated TTFT p99 / disagg TTFT
    p99 — above 1 means shipping KV blocks out of a dedicated prefill
    engine beats interleaving admissions with decode),
    ``serving_disagg_qps_ratio``, and ``serving_disagg_prefill_mfu``
    (acceptance bar > 0.174 — above the training headline — on real
    hardware) gate in --compare.  As with serving_cluster, the CPU
    device-count simulation shares the host cores across "devices", so
    simulated ratios and MFU only track plumbing cost, not the claims."""
    import jax

    from megatron_llm_tpu.models import model as model_lib
    from megatron_llm_tpu.serving.bench import run_disagg_serving_bench

    n_dev = len(jax.devices())
    if n_dev < 2:
        return {"serving_disagg_skipped":
                f"needs >= 2 devices, have {n_dev}"}
    prompt_len, gen_len = 512, 32
    cfg = _bench_model(prompt_len + gen_len, "selective")
    params = model_lib.init_params(jax.random.key(0), cfg)
    return run_disagg_serving_bench(
        cfg, params, num_requests=16, gen_len=gen_len, slots=4,
        prompt_len=prompt_len, prefill_chunk=64,
        chunk_sweep=(64, 128, 256, 512),
        peak_flops=chip_peak_flops(platform))


def _serving_lora_point():
    """Multi-tenant LoRA serving point (serving/adapters/, docs/serving.md
    "Multi-tenant LoRA & live weight swap"): adapter-decorated traffic vs
    the same traffic on an adapter-less engine at identical geometry,
    plus a tenant-rotation wave through the LRU slot arena.  Gates:
    ``serving_lora_itl_overhead`` — resident-adapter ITL p50 over the
    base engine's — must stay ≤ 10% (lora_overhead_check; the price of
    the always-compiled grouped epilogue), and
    ``serving_lora_cache_hit_rate`` (repeat-pair tenant arrivals hitting
    the pinned arena slot) gates in --compare."""
    import jax

    from megatron_llm_tpu.models import model as model_lib
    from megatron_llm_tpu.serving.bench import run_lora_serving_bench

    prompt_len, gen_len = 128, 64
    cfg = _bench_model(prompt_len + gen_len, "selective")
    params = model_lib.init_params(jax.random.key(0), cfg)
    return run_lora_serving_bench(
        cfg, params, num_requests=16, prompt_len=prompt_len,
        gen_len=gen_len, slots=8, n_adapters=8, cache_slots=4, rank=8)


def _serving_tiered_point():
    """Tiered-KV serving point (serving/block_pool.py:HostKVTier,
    docs/serving.md "Tiered KV"): mixed-QoS traffic — low-priority batch
    decodes whose worst-case reservation covers the whole (deliberately
    small) device pool, plus high-priority interactive arrivals — with a
    host-RAM tier vs the queue-head-parking baseline at identical
    geometry.  Gates: ``serving_tiered_qps_ratio`` — interactive-class
    sustained QPS, tiered over parking (acceptance ≥ 1.5x: preemption
    serves the interactive class immediately instead of wedging it
    behind a batch decode) — and the interactive ITL p50 pair feeding
    tiered_overhead_check (swap pumping may cost ≤ 5% ITL p50)."""
    import jax

    from megatron_llm_tpu.models import model as model_lib
    from megatron_llm_tpu.serving.bench import run_tiered_serving_bench

    batch_prompt_len, batch_gen_len = 64, 128
    cfg = _bench_model(batch_prompt_len + batch_gen_len, "selective")
    params = model_lib.init_params(jax.random.key(0), cfg)
    return run_tiered_serving_bench(
        cfg, params, num_interactive=10, num_batch=2,
        interactive_prompt_len=32, interactive_gen_len=16,
        batch_prompt_len=batch_prompt_len, batch_gen_len=batch_gen_len,
        kv_block_size=32, slots=4)


def _transient_error_types():
    """The error classes worth retrying: XLA runtime errors, which can be
    transient (a compile or allocation that succeeds on a clean second
    try).  Deterministic bugs (NameError, TypeError, ...) must NOT be
    retried."""
    import jax

    return (jax.errors.JaxRuntimeError,)


def _retry(fn, *args, **kw):
    """One retry, transient (XLA runtime) errors only."""
    try:
        return fn(*args, **kw)
    except _transient_error_types() as e:
        print(f"# bench point failed ({type(e).__name__}); retrying once",
              flush=True)
        import jax

        jax.clear_caches()
        time.sleep(5)
        return fn(*args, **kw)


# ---------------------------------------------------------------------------
# Regression compare (--compare PREV.json [CURRENT.json])
# ---------------------------------------------------------------------------

# Metrics whose >10% regression fails CI (exit nonzero).  "mfu" is the
# record's "value" field (surfaced under its real name by _flatten_metrics).
_HEADLINE_METRICS = ("mfu", "decode_tokens_per_sec",
                     "decode_int8_roofline_frac",
                     # round 9 decode-bytes-gap points: int4 weight
                     # residency and the mixed (int8 attn / int4 MLP)
                     # policy must keep beating the int8 audited roofline
                     "decode_int4_roofline_frac",
                     "decode_mixed_roofline_frac",
                     "serving_prefix.serving_prefix_ttft_speedup",
                     "serving_prefix.serving_prefix_hit_rate",
                     "serving_paged.serving_paged_max_concurrency",
                     "serving_spec.serving_spec_itl_speedup",
                     "serving_spec.serving_spec_acceptance_rate",
                     # resident-draft tree speculation: the random-
                     # traffic ITL speedup (> 1.0 = beating the n-gram
                     # drafter's ceiling) with acceptance riding along
                     "serving_spec_tree.serving_spec_tree_itl_speedup",
                     "serving_spec_tree.serving_spec_tree_acceptance_rate",
                     # multi-chip serving: replica QPS scaling (≥ 1.8x at
                     # 2 replicas on real hardware) and the tp=2 per-chip
                     # model-size win (≈ 2.0)
                     "serving_cluster.serving_cluster_qps_ratio",
                     "serving_cluster.serving_cluster_tp_model_size_ratio",
                     # same ≈ tp gate over the mixed-precision tree
                     # (quantized subtrees + int8 embedding must shard)
                     "serving_cluster."
                     "serving_cluster_tp_quant_model_size_ratio",
                     # disaggregated prefill/decode vs colocated at equal
                     # device count: TTFT tail + QPS must not regress,
                     # and the prefill-chunk sweep's best MFU (> 0.174
                     # bar on real hardware) is the prefill-engine claim
                     "serving_disagg.serving_disagg_ttft_p99_ratio",
                     "serving_disagg.serving_disagg_qps_ratio",
                     "serving_disagg.serving_disagg_prefill_mfu",
                     # multi-tenant LoRA: repeat-pair tenant arrivals must
                     # keep hitting the pinned arena slot (a drop means
                     # admission stopped reusing residency); the ITL
                     # overhead gate rides separately in
                     # lora_overhead_check because smaller is better there
                     "serving_lora.serving_lora_cache_hit_rate",
                     # tiered KV: interactive-class QPS with host-RAM
                     # preemption over the queue-head-parking baseline
                     # (≥ 1.5x acceptance); the swap-overhead ITL gate
                     # rides separately in tiered_overhead_check
                     "serving_tiered.serving_tiered_qps_ratio",
                     # pipeline-parallel serving: the layer-sharded
                     # layout's per-device param-bytes win at pp=2
                     # (≈ 2.0; KV pool shards the same way)
                     "serving_pp.serving_pp_param_bytes_ratio")
_REGRESSION_TOLERANCE = 0.10
# Tracing must stay effectively free on the serving hot path: the mixed
# point's ITL p50 with the span recorder on may exceed the untraced rerun
# riding in the same record by at most this fraction.
_TRACE_OVERHEAD_TOLERANCE = 0.10
# The grouped LoRA epilogue rides in the fused decode step whenever a
# registry is attached; serving_lora's resident-adapter ITL p50 may
# exceed the adapter-less engine's by at most this fraction.
_LORA_OVERHEAD_TOLERANCE = 0.10
# Demote copies pump through the scheduler host phase; serving_tiered's
# interactive ITL p50 with the host tier on may exceed the parking
# baseline's by at most this fraction.
_TIERED_OVERHEAD_TOLERANCE = 0.05

# Bumped when the record's shape changes (new points / renamed keys) so
# --compare across old records is interpretable.
# v3: + serving_spec point (speculative decoding ITL speedup + acceptance)
# v4: + serving_cluster point (replica QPS scaling + tp model-size ratio)
# v5: + decode int4/mixed points, per-tensor-class step-bytes breakdown,
#     decode specs carry a precision-policy string in "quantize"
# v6: + serving_disagg point (disaggregated prefill/decode TTFT/QPS vs
#     colocated at equal devices + prefill-chunk MFU sweep)
# v7: + serving_spec_tree point (resident-draft tree speculation: random-
#     traffic ITL speedup vs draft-off + acceptance; the n-gram
#     serving_spec point rides unchanged for the PLD baseline)
# v8: + serving_lora point (multi-tenant LoRA: resident-adapter ITL vs
#     adapter-less base engine + LRU arena hit rate under tenant
#     rotation)
# v9: + serving_tiered point (tiered KV: interactive-class QPS with
#     host-RAM preemption vs queue-head parking + the swap-overhead ITL
#     pair)
# v10: + serving_pp point (pipeline-parallel decode: per-device param-
#      bytes ratio at pp=2 / fsdp=2 vs single-mesh, ITL vs tp=2 at
#      equal devices, bitwise flag)
_BENCH_SCHEMA_VERSION = 10


def _run_metadata(platform: str, device_count: int) -> dict:
    """Provenance stamped into the record as ``run_meta``: without the
    git sha + jax version + device geometry, two BENCH_*.json files a few
    rounds apart cannot be attributed to code vs toolchain vs topology."""
    import os
    import subprocess

    meta = {
        "schema_version": _BENCH_SCHEMA_VERSION,
        "device_kind": platform,
        "device_count": device_count,
    }
    here = os.path.dirname(os.path.abspath(__file__))
    try:
        sha = subprocess.run(
            ["git", "rev-parse", "--short=12", "HEAD"],
            capture_output=True, text=True, timeout=10, cwd=here)
        if sha.returncode == 0 and sha.stdout.strip():
            meta["git_sha"] = sha.stdout.strip()
            dirty = subprocess.run(
                ["git", "status", "--porcelain"],
                capture_output=True, text=True, timeout=10, cwd=here)
            if dirty.returncode == 0 and dirty.stdout.strip():
                meta["git_dirty"] = True
    except (OSError, subprocess.TimeoutExpired):
        pass  # not a git checkout / git missing: record stays attributable
    try:
        import importlib.metadata

        meta["jax_version"] = importlib.metadata.version("jax")
    except Exception:  # noqa: BLE001 — provenance only, never fatal
        pass
    return meta


def _flatten_metrics(record: dict, prefix: str = "") -> dict:
    """Numeric leaves of a BENCH record as a flat {dotted.name: float}.
    The headline "value" field is renamed "mfu"; lists (the mfu_vs_seq
    curve) are skipped — their rows move between runs — and so is
    run_meta (provenance, not a measurement; device_count deltas must
    not read as regressions)."""
    out = {}
    for key, val in record.items():
        name = f"{prefix}{key}"
        if key == "value" and not prefix:
            name = "mfu"
        if key == "run_meta" and not prefix:
            continue
        if isinstance(val, bool):
            continue
        if isinstance(val, (int, float)):
            out[name] = float(val)
        elif isinstance(val, dict):
            out.update(_flatten_metrics(val, prefix=f"{name}."))
    return out


def trace_overhead_check(record: dict):
    """→ (line, ok): the tracing-overhead gate.  The serving_mixed point
    records ITL p50 with the span recorder on AND off; tracing is only
    acceptable as an always-on default while the traced number stays
    within _TRACE_OVERHEAD_TOLERANCE of the untraced one (the --no_trace
    server flag is the escape hatch if this ever trips)."""
    sm = record.get("serving_mixed") or {}
    traced = sm.get("serving_mixed_itl_ms_p50")
    untraced = sm.get("serving_mixed_itl_ms_p50_untraced")
    if not traced or not untraced:
        return ("# trace-overhead gate: skipped "
                "(no traced/untraced ITL pair in record)"), True
    overhead = traced / untraced - 1.0
    ok = traced <= (1.0 + _TRACE_OVERHEAD_TOLERANCE) * untraced
    line = (f"# trace-overhead gate: serving_mixed_itl_ms_p50 {traced:g} "
            f"traced vs {untraced:g} untraced ({overhead:+.1%}, limit "
            f"+{_TRACE_OVERHEAD_TOLERANCE:.0%})"
            + ("" if ok else "  << REGRESSION"))
    return line, ok


def lora_overhead_check(record: dict):
    """→ (line, ok): the LoRA-epilogue-overhead gate.  The serving_lora
    point records resident-adapter ITL p50 against the adapter-less base
    engine's at identical geometry; attaching a registry is only
    acceptable as a serving default while the adapter-decorated number
    stays within _LORA_OVERHEAD_TOLERANCE of base (running without a
    registry — which keeps the pre-LoRA executable — is the escape
    hatch if this ever trips)."""
    sl = record.get("serving_lora") or {}
    lora = sl.get("serving_lora_itl_ms_p50")
    base = sl.get("serving_lora_base_itl_ms_p50")
    if not lora or not base:
        return ("# lora-overhead gate: skipped "
                "(no lora/base ITL pair in record)"), True
    overhead = lora / base - 1.0
    ok = lora <= (1.0 + _LORA_OVERHEAD_TOLERANCE) * base
    line = (f"# lora-overhead gate: serving_lora_itl_ms_p50 {lora:g} "
            f"with adapters vs {base:g} base ({overhead:+.1%}, limit "
            f"+{_LORA_OVERHEAD_TOLERANCE:.0%})"
            + ("" if ok else "  << REGRESSION"))
    return line, ok


def tiered_overhead_check(record: dict):
    """→ (line, ok): the tiered-KV swap-overhead gate.  The
    serving_tiered point records interactive ITL p50 with the host tier
    on against the parking baseline at identical geometry; keeping the
    tier on is only acceptable while pumping demote copies through the
    scheduler host phase costs at most _TIERED_OVERHEAD_TOLERANCE of
    interactive ITL p50 (``--host_kv_blocks 0`` — which removes the
    tier and the pump entirely — is the escape hatch if this trips)."""
    st = record.get("serving_tiered") or {}
    tiered = st.get("serving_tiered_itl_ms_p50")
    base = st.get("serving_tiered_parked_itl_ms_p50")
    if not tiered or not base:
        return ("# tiered-overhead gate: skipped "
                "(no tiered/parked ITL pair in record)"), True
    overhead = tiered / base - 1.0
    ok = tiered <= (1.0 + _TIERED_OVERHEAD_TOLERANCE) * base
    line = (f"# tiered-overhead gate: serving_tiered_itl_ms_p50 {tiered:g} "
            f"with host tier vs {base:g} parked ({overhead:+.1%}, limit "
            f"+{_TIERED_OVERHEAD_TOLERANCE:.0%})"
            + ("" if ok else "  << REGRESSION"))
    return line, ok


def compare_records(prev: dict, cur: dict):
    """Per-metric deltas between two BENCH records → (lines, regressed).

    ``lines`` is a human-readable report (one line per metric present in
    either record); ``regressed`` lists the headline metrics that dropped
    more than _REGRESSION_TOLERANCE — latency-style metrics are reported
    but never gate, because for every headline metric here bigger is
    better."""
    p, c = _flatten_metrics(prev), _flatten_metrics(cur)
    lines, regressed = [], []
    for name in sorted(set(p) | set(c)):
        if name not in p:
            lines.append(f"  {name}: (new) {c[name]:g}")
            continue
        if name not in c:
            lines.append(f"  {name}: {p[name]:g} -> MISSING")
            if name in _HEADLINE_METRICS:
                regressed.append(name)
            continue
        pv, cv = p[name], c[name]
        delta = (cv - pv) / abs(pv) if pv else 0.0
        mark = ""
        if name in _HEADLINE_METRICS and delta < -_REGRESSION_TOLERANCE:
            regressed.append(name)
            mark = "  << REGRESSION"
        lines.append(f"  {name}: {pv:g} -> {cv:g} ({delta:+.1%}){mark}")
    return lines, regressed


def _load_record(path: str) -> dict:
    """Last JSON-object line of a BENCH_*.json file (the bench prints
    '#'-prefixed progress lines before the record)."""
    record = None
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line.startswith("{"):
                record = json.loads(line)
    if record is None:
        raise ValueError(f"no JSON record line in {path}")
    return record


def _run_compare(prev_path: str, cur_record: dict) -> int:
    prev = _load_record(prev_path)
    for tag, rec in (("prev", prev), ("cur", cur_record)):
        meta = rec.get("run_meta")
        if meta:
            print(f"# {tag} run_meta: {json.dumps(meta, sort_keys=True)}",
                  flush=True)
    lines, regressed = compare_records(prev, cur_record)
    print(f"# compare vs {prev_path} "
          f"(gate: {', '.join(_HEADLINE_METRICS)} "
          f"> {_REGRESSION_TOLERANCE:.0%} drop):", flush=True)
    for line in lines:
        print("#" + line, flush=True)
    trace_line, trace_ok = trace_overhead_check(cur_record)
    print(trace_line, flush=True)
    lora_line, lora_ok = lora_overhead_check(cur_record)
    print(lora_line, flush=True)
    tiered_line, tiered_ok = tiered_overhead_check(cur_record)
    print(tiered_line, flush=True)
    if regressed or not trace_ok or not lora_ok or not tiered_ok:
        if regressed:
            print(f"# REGRESSED: {', '.join(regressed)}", flush=True)
        if not trace_ok:
            print("# REGRESSED: tracing overhead over limit", flush=True)
        if not lora_ok:
            print("# REGRESSED: LoRA epilogue overhead over limit",
                  flush=True)
        if not tiered_ok:
            print("# REGRESSED: tiered-KV swap overhead over limit",
                  flush=True)
        return 1
    print("# no headline regression", flush=True)
    return 0


# ---------------------------------------------------------------------------
# Orchestration: one subprocess per point (see module docstring)
# ---------------------------------------------------------------------------

_CHILD_MARK = "##BENCH_POINT##"


def _relay_progress(text: str) -> None:
    """Forward a child's '#'-prefixed progress lines to our stdout."""
    for line in text.splitlines():
        if line.startswith("#") and not line.startswith(_CHILD_MARK):
            print(line, flush=True)


def _child_main(spec_json: str) -> None:
    from megatron_llm_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    spec = json.loads(spec_json)
    platform = spec["platform"]
    peak = chip_peak_flops(platform)
    hbm_bw = chip_hbm_bandwidth(platform)
    kind = spec["kind"]
    if kind == "train":
        out = _retry(_train_point, spec["seq"], spec["mb"], spec["rc"],
                     spec["iters"], peak, spec.get("wide_layers", 0))
    elif kind == "decode":
        out = _retry(_decode_point, hbm_bw, spec.get("quantize", False),
                     spec.get("wide_layers", 0))
    elif kind == "pld":
        out = _retry(_pld_point, spec.get("wide_layers", 0))
    elif kind == "prefill":
        out = _retry(_prefill_point, peak)
    elif kind == "serving":
        out = _retry(_serving_point)
    elif kind == "serving_mixed":
        out = _retry(_serving_mixed_point, spec.get("quantize", False))
    elif kind == "serving_prefix":
        out = _retry(_serving_prefix_point)
    elif kind == "serving_paged":
        out = _retry(_serving_paged_point)
    elif kind == "serving_lora":
        out = _retry(_serving_lora_point)
    elif kind == "serving_tiered":
        out = _retry(_serving_tiered_point)
    elif kind == "serving_spec":
        out = _retry(_serving_spec_point)
    elif kind == "serving_spec_tree":
        out = _retry(_serving_spec_tree_point, spec.get("wide_layers", 0))
    elif kind == "serving_cluster":
        out = _retry(_serving_cluster_point)
    elif kind == "serving_pp":
        out = _retry(_serving_pp_point)
    elif kind == "serving_disagg":
        out = _retry(_serving_disagg_point, platform)
    else:  # pragma: no cover - parent and child ship together
        raise ValueError(f"unknown point kind {kind!r}")
    print(_CHILD_MARK + json.dumps(out), flush=True)


def _point(label: str, spec: dict, timeout_s: int = 900,
           env: dict | None = None):
    """Run one measurement in a fresh subprocess → parsed result or None.

    Isolation is the point: a crashed, hung, or HBM-leaking measurement
    cannot take the rest of the record down with it (round 2 lost the
    train curve to a late crash; round 5 lost decode rows to in-process
    HBM contamination)."""
    import os
    import subprocess

    t0 = time.perf_counter()
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--point",
             json.dumps(spec)],
            capture_output=True, text=True, timeout=timeout_s,
            cwd=os.path.dirname(os.path.abspath(__file__)),
            env=(None if env is None else {**os.environ, **env}))
    except subprocess.TimeoutExpired as e:
        # surface the child's progress lines so the hung stage (compile /
        # warmup / timed window) is identifiable without a rerun
        partial = e.stdout or b""
        if isinstance(partial, bytes):
            partial = partial.decode(errors="replace")
        _relay_progress(partial)
        print(f"# bench point {label} TIMED OUT after {timeout_s}s",
              flush=True)
        return None
    _relay_progress(proc.stdout or "")
    if proc.returncode != 0:
        tail = (proc.stderr or "").strip().splitlines()[-1:] or ["?"]
        print(f"# bench point {label} FAILED (rc={proc.returncode}): "
              f"{tail[0]}", flush=True)
        return None
    for line in (proc.stdout or "").splitlines():
        if line.startswith(_CHILD_MARK):
            print(f"# bench point {label} ok "
                  f"({time.perf_counter() - t0:.0f}s)", flush=True)
            return json.loads(line[len(_CHILD_MARK):])
    print(f"# bench point {label} produced no result line", flush=True)
    return None


def _detect_device(timeout_s: int = 240):
    """First device's kind + visible device count, probed in a SUBPROCESS
    with a hard timeout.

    The probe exits before any point runs, so this parent never touches
    JAX and never holds the chip its ``--point`` children need (one
    process per chip).  The timeout bounds a backend that hangs at
    start-up *inside a C call* — a benchmark that hangs is worse for the
    driver than one that emits a structured failure record quickly."""
    import subprocess

    try:
        out = subprocess.run(
            [sys.executable, "-c",
             "import jax; ds = jax.devices(); "
             "print(ds[0].device_kind); print(len(ds))"],
            capture_output=True, text=True, timeout=timeout_s)
    except subprocess.TimeoutExpired:
        raise TimeoutError(
            f"device probe exceeded {timeout_s}s "
            "(accelerator unreachable?)")
    if out.returncode != 0:
        tail = (out.stderr or "").strip().splitlines()[-1:] or ["?"]
        raise RuntimeError(f"device probe failed: {tail[0]}")
    lines = (out.stdout or "").strip().splitlines()
    if not lines:
        raise RuntimeError("device probe printed nothing")
    if len(lines) >= 2 and lines[-1].isdigit():
        return lines[-2], int(lines[-1])
    return lines[-1], 1


def main() -> None:
    if len(sys.argv) >= 3 and sys.argv[1] == "--point":
        _child_main(sys.argv[2])
        return
    compare_prev = None
    if len(sys.argv) >= 2 and sys.argv[1] == "--compare":
        if len(sys.argv) >= 4:
            # file-vs-file mode: no measurement, pure CI gate
            raise SystemExit(_run_compare(sys.argv[2],
                                          _load_record(sys.argv[3])))
        if len(sys.argv) == 3:
            # run the bench, then gate the fresh record against PREV
            compare_prev = sys.argv[2]
        else:
            raise SystemExit("usage: bench.py --compare PREV.json "
                             "[CURRENT.json]")

    try:
        platform, device_count = _detect_device()
    except (TimeoutError, RuntimeError, OSError) as e:
        print(json.dumps({
            "metric": "mfu", "value": None, "unit": "fraction_of_peak",
            "vs_baseline": None,
            "error": f"{type(e).__name__}: {e}",
        }))
        raise SystemExit(1)

    def train_spec(seq, mb, rc, iters, wide_layers=0):
        return {"kind": "train", "platform": platform, "seq": seq,
                "mb": mb, "rc": rc, "iters": iters,
                "wide_layers": wide_layers}

    # Headline: seq 1024 (the reference's finetune config), measured
    # single-chip sweet spot mb=12, selective recompute; mb=8 fallback.
    headline = _point("train@1024", train_spec(1024, 12, "selective", 30))
    headline_config = "mb12"
    if headline is None:
        headline = _point("train@1024/fallback",
                          train_spec(1024, 8, "selective", 10))
        headline_config = "mb8-fallback"

    curve = []
    if headline is not None:
        tps, mfu, loss, n_params = headline
        curve.append({"seq_length": 1024, "mfu": round(mfu, 4),
                      "tokens_per_sec": round(tps, 1)})

    # MFU-vs-seq curve (BASELINE config 4 regime at 32k): selective remat
    # while it fits, full remat beyond 8k.
    for seq, mb, rc, iters in ((4096, 3, "selective", 10),
                               (8192, 1, "selective", 10),
                               (16384, 1, "full", 5),
                               (32768, 1, "full", 5)):
        p = _point(f"train@{seq}", train_spec(seq, mb, rc, iters))
        if p is not None:
            c_tps, c_mfu, _, _ = p
            curve.append({"seq_length": seq, "mfu": round(c_mfu, 4),
                          "tokens_per_sec": round(c_tps, 1)})

    # 7B-width training point.  Measured ladder on v5e (2026-07-31):
    # L3/mb2/selective 0.556, L2/mb2/selective 0.535, L3/mb1/full 0.441 —
    # mb ≥ 2 + selective remat is the lever.
    for layers, mb, rc in ((3, 2, "selective"), (2, 2, "selective"),
                           (2, 1, "full")):
        wide = _point(f"train@4096/7b-width-L{layers}",
                      train_spec(4096, mb, rc, 5, wide_layers=layers))
        if wide is not None:
            w_tps, w_mfu, _, w_params = wide
            curve.append({"seq_length": 4096, "mfu": round(w_mfu, 4),
                          "tokens_per_sec": round(w_tps, 1),
                          "config": f"7b-width-L{layers}-mb{mb}-{rc}",
                          "model_params": w_params})
            break

    decode = _point("decode", {"kind": "decode", "platform": platform})
    decode_q = _point("decode/int8", {"kind": "decode",
                                      "platform": platform,
                                      "quantize": "int8"})
    decode_i4 = _point("decode/int4", {"kind": "decode",
                                       "platform": platform,
                                       "quantize": "int4"})
    decode_mx = _point("decode/mixed", {"kind": "decode",
                                        "platform": platform,
                                        "quantize": "mixed"})
    decode_7b = _point("decode/7b-width-L8",
                       {"kind": "decode", "platform": platform,
                        "wide_layers": 8}, timeout_s=1200)
    pld = _point("decode/pld", {"kind": "pld", "platform": platform},
                 timeout_s=1200)
    pld_7b = _point("decode/pld-7b-width",
                    {"kind": "pld", "platform": platform,
                     "wide_layers": 8}, timeout_s=1200)
    prefill_long = _point("prefill@1024", {"kind": "prefill",
                                           "platform": platform})
    serving = _point("serving", {"kind": "serving", "platform": platform},
                     timeout_s=1200)
    serving_mixed = _point("serving/mixed",
                           {"kind": "serving_mixed", "platform": platform},
                           timeout_s=1200)
    serving_mixed_q = _point("serving/mixed-int8",
                             {"kind": "serving_mixed", "platform": platform,
                              "quantize": True},
                             timeout_s=1200)
    serving_prefix = _point("serving/prefix",
                            {"kind": "serving_prefix",
                             "platform": platform},
                            timeout_s=1200)
    serving_paged = _point("serving/paged",
                           {"kind": "serving_paged",
                            "platform": platform},
                           timeout_s=1800)
    serving_spec = _point("serving/spec",
                          {"kind": "serving_spec",
                           "platform": platform},
                          timeout_s=1800)
    serving_lora = _point("serving/lora",
                          {"kind": "serving_lora",
                           "platform": platform},
                          timeout_s=1800)
    serving_tiered = _point("serving/tiered",
                            {"kind": "serving_tiered",
                             "platform": platform},
                            timeout_s=1800)
    # headline quoted at 7B width (decode_7b geometry) so the
    # beat-the-PLD-ceiling claim holds at deployment matmul shapes; on
    # CPU the wide model would blow the point timeout, so the simulated
    # record carries the standard bench-model geometry instead
    serving_spec_tree = _point(
        "serving/spec-tree",
        {"kind": "serving_spec_tree", "platform": platform,
         "wide_layers": 0 if platform == "cpu" else 8},
        timeout_s=1800)
    # CPU runs simulate 8 devices so the replica/tp topology exercises
    # end to end; on real hardware the flag is inert (jax ignores the
    # host-platform count when an accelerator is present)
    cluster_env = None
    if platform == "cpu":
        import os as _os

        cluster_env = {"XLA_FLAGS": (
            _os.environ.get("XLA_FLAGS", "") +
            " --xla_force_host_platform_device_count=8").strip()}
    serving_cluster = _point("serving/cluster",
                             {"kind": "serving_cluster",
                              "platform": platform},
                             timeout_s=1800, env=cluster_env)
    serving_pp = _point("serving/pp",
                        {"kind": "serving_pp", "platform": platform},
                        timeout_s=1800, env=cluster_env)
    serving_disagg = _point("serving/disagg",
                            {"kind": "serving_disagg",
                             "platform": platform},
                            timeout_s=1800, env=cluster_env)

    baseline_mfu = 0.12  # reference 890 tok/s/GPU on A100 ⇒ ~0.12 MFU
    record = {
        "metric": "mfu",
        "value": None,
        "unit": "fraction_of_peak",
        "vs_baseline": None,
        "seq_length": 1024,
        "device": platform,
        "run_meta": _run_metadata(platform, device_count),
        "mfu_vs_seq": curve,
    }
    if decode is not None:
        record.update({
            "decode_tokens_per_sec": decode["tokens_per_sec"],
            "decode_roofline_tokens_per_sec":
                decode["roofline_tokens_per_sec"],
            "decode_roofline_frac": decode["roofline_frac"],
            "prefill_tokens_per_sec": decode["prefill_tokens_per_sec"],
        })
    for tag, dq in (("int8", decode_q), ("int4", decode_i4),
                    ("mixed", decode_mx)):
        if dq is None:
            continue
        record.update({
            f"decode_tokens_per_sec_{tag}": dq["tokens_per_sec"],
            f"decode_{tag}_roofline_frac": dq["roofline_frac"],
        })
        if "step_weight_bytes" in dq:
            # bytes-moved audit (definition change vs pre-audit records:
            # roofline_frac now uses the audited denominator; the naive
            # value rides along for continuity — docs/inference.md) plus
            # the v5 per-tensor-class breakdown showing where the
            # residual decode bytes live
            record.update({
                f"decode_{tag}_step_weight_bytes":
                    dq["step_weight_bytes"],
                f"decode_{tag}_step_kv_bytes": dq["step_kv_bytes"],
                f"decode_{tag}_step_bytes_by_class":
                    dq["step_bytes_by_class"],
                f"decode_{tag}_naive_roofline_frac":
                    dq["naive_roofline_frac"],
            })
    if decode_7b is not None:
        record["decode_7b_width"] = decode_7b
    if pld is not None:
        record.update(pld)
    if pld_7b is not None:
        record["pld_7b_width"] = pld_7b
    if prefill_long is not None:
        record.update(prefill_long)
    if serving is not None:
        record["serving"] = serving
    if serving_mixed is not None:
        record["serving_mixed"] = serving_mixed
    if serving_mixed_q is not None:
        record["serving_mixed_int8"] = serving_mixed_q
    if serving_prefix is not None:
        record["serving_prefix"] = serving_prefix
    if serving_paged is not None:
        record["serving_paged"] = serving_paged
    if serving_spec is not None:
        record["serving_spec"] = serving_spec
    if serving_lora is not None:
        record["serving_lora"] = serving_lora
    if serving_tiered is not None:
        record["serving_tiered"] = serving_tiered
    if serving_spec_tree is not None:
        record["serving_spec_tree"] = serving_spec_tree
    if serving_cluster is not None:
        record["serving_cluster"] = serving_cluster
    if serving_pp is not None:
        record["serving_pp"] = serving_pp
    if serving_disagg is not None:
        record["serving_disagg"] = serving_disagg
    if headline is not None:
        record.update({
            "value": round(mfu, 4),
            "vs_baseline": round(mfu / baseline_mfu, 3),
            "tokens_per_sec_per_chip": round(tps, 1),
            "model_params": n_params,
            "loss": loss,
            "headline_config": headline_config,
        })
    print(json.dumps(record))
    if compare_prev is not None:
        raise SystemExit(_run_compare(compare_prev, record))


if __name__ == "__main__":
    main()
