"""Pipeline parallelism: circular shift-register 1F1B over the ``pp`` mesh axis.

This module is the TRAINING schedule.  Serving uses the same ``pp``
mesh axis differently: the serving re-layout shards the stacked layer
axis of params and the paged KV pool over pp
(models/sharding.py:serving_param_specs / kv_pool_specs, stage ranges
from parallel/mesh.py:stage_layer_ranges) and the engine microbatch-
interleaves decode steps across the stages
(serving/engine.py:_dispatch_decode) — GSPMD derives the stage-to-stage
transfers from the specs, so no explicit 1F1B schedule exists there.

Reference mapping (megatron/schedules.py:18-722):

- ``forward_backward_no_pipelining`` (schedules.py:213) → the plain
  microbatch ``lax.scan`` in ``training/step.py`` (pp = 1).
- ``forward_backward_pipelining_without_interleaving`` — 1F1B
  (schedules.py:606) → ``pipeline_loss`` with ``vpp = 1``.
- ``forward_backward_pipelining_with_interleaving`` — virtual stages
  (schedules.py:253) → ``pipeline_loss`` with ``vpp > 1`` (the circular
  schedule: each device holds ``vpp`` layer chunks and every microbatch
  passes around the ring ``vpp`` times).
- ``p2p_communication.py``'s batched isend/irecv between stage neighbours →
  a single ``jax.lax.ppermute`` over the ring per tick.

Design: torch autograd drives the reference's backward passes through
send/recv hooks; in JAX the whole pipelined forward is one differentiable
SPMD program (``ppermute`` has a well-defined transpose = the reverse
permutation), so ``jax.grad`` of the pipelined loss *is* the backward
pipeline — warmup/steady/cooldown bookkeeping (schedules.py:606-722) never
has to be re-derived.  Compute-wise every device runs every tick and the
bubble shows up as ticks whose results are masked out, which costs exactly
the same wall-clock as an idle bubble.

Schedule shape (T = ticks):
- vpp = 1:  T = M + pp - 1           (M = num microbatches)
- vpp > 1:  T = M·vpp + pp - 1, requiring M ≥ pp.  When M % pp == 0 (the
  divisibility the reference's interleaved schedule also asserts) the
  *tight* group-interleaved order runs: microbatches advance in groups of
  pp, each group cycling through all vpp chunks, and the ring shift itself
  delivers chunk→chunk re-entry (the wrap the last stage emits at tick t-1
  is exactly what stage 0 consumes at tick t) — no re-entry buffer exists.
  Otherwise the legacy order parks finished microbatches in an [M, ...]
  circular buffer and re-enters them after a full round of M ticks.
Bubble fraction = (pp-1)/(M·vpp + pp - 1): interleaving divides the bubble
by vpp exactly as in the reference's interleaved 1F1B.

Memory design (docs/pipeline_memory.md derives and measures this):
microbatches are *streamed*.  The shard_map boundary carries only int32
tokens/labels/masks and scalar losses — stage 0 embeds microbatch ``t`` on
demand inside the tick and the last stage runs the CE head on each finished
microbatch inside the tick, so no ``[M, mb, s, h]`` hidden-state buffer
(input, output, or fp32 boundary copy) ever exists.  Per-device activation
memory is T boundary tensors ``[mb, s_local, h]`` (scan residuals, compute
dtype) + the model's own remat-policy residuals per tick + (legacy
non-divisible-M interleaving only) the ``[M, mb, s_local, h]`` circular
re-entry buffer.  The reference's 1F1B
bounds in-flight microbatches at ≤pp (schedules.py:606-722); the streamed
scan holds M·vpp boundary tensors instead, which at BASELINE config-5 shapes
(70B, s=4096, mb=1, pp=8, M=16) is ~1.5 GB bf16 per device — small next to
params+opt state, and the price of getting the backward schedule for free
from ``jax.grad``.  At grad-accum counts M ≥ 64 the O(T) term stops being
small; ``ParallelConfig.pipeline_remat_window`` = W checkpoints the tick
loop in windows of W, restoring an O(T/W + W·lpc) bound (the large-M
equivalent of the reference's ≤pp in-flight rule) for one extra forward
replay per window.

Layer→stage assignment matches the reference (megatron/model/
transformer.py:1015-1060): chunk v on stage s holds global layers
``[(v·pp + s)·lpc, (v·pp + s + 1)·lpc)`` — i.e. ``layers.reshape(vpp, pp,
lpc, ...)``.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..config import ModelConfig, ParallelConfig, RuntimeConfig
from ..models.transformer import AttnSideInputs, stack_forward
from ..models import model as model_lib
from ..ops.norms import norm_apply
from .cross_entropy import cross_entropy
from . import mesh as mesh_lib

PyTree = Any
PP = mesh_lib.PIPELINE_AXIS


# ---------------------------------------------------------------------------
# Stage-stacked parameter layout
# ---------------------------------------------------------------------------


def layers_per_chunk(num_layers: int, pp: int, vpp: int = 1) -> int:
    return mesh_lib.pipeline_stage_layers(num_layers, pp, vpp)[0]


def to_stage_layers(stacked: PyTree, pp: int, vpp: int = 1) -> PyTree:
    """[L, ...] layer stack → [vpp, pp, lpc, ...] stage-stacked layout."""

    def split(x):
        lpc = layers_per_chunk(x.shape[0], pp, vpp)
        return x.reshape(vpp, pp, lpc, *x.shape[1:])

    return jax.tree.map(split, stacked)


def from_stage_layers(staged: PyTree) -> PyTree:
    """Inverse of :func:`to_stage_layers` (for checkpoints / HF interop)."""
    return jax.tree.map(
        lambda x: x.reshape(x.shape[0] * x.shape[1] * x.shape[2],
                            *x.shape[3:]),
        staged,
    )


def to_pipeline_params(params: PyTree, parallel: ParallelConfig) -> PyTree:
    """Model params with the layer stack re-laid-out for the pipeline."""
    pp = parallel.pipeline_parallel
    if pp == 1:
        return params
    out = dict(params)
    out["layers"] = to_stage_layers(
        params["layers"], pp, parallel.virtual_pipeline_stages)
    return out


def from_pipeline_params(params: PyTree, parallel: ParallelConfig) -> PyTree:
    if parallel.pipeline_parallel == 1:
        return params
    out = dict(params)
    out["layers"] = from_stage_layers(params["layers"])
    return out


def stage_layer_specs(layer_specs: PyTree) -> PyTree:
    """Turn per-layer-stack specs P(None, *dims) into staged specs
    P(None, 'pp', None, *dims).  The first (layer) axis of the flat spec is
    dropped and replaced by (vpp, pp, lpc)."""
    def conv(spec: P) -> P:
        rest = tuple(spec)[1:] if len(spec) else ()
        return P(None, PP, None, *rest)

    return jax.tree.map(conv, layer_specs,
                        is_leaf=lambda s: isinstance(s, P))


def pipeline_param_specs(specs: PyTree, parallel: ParallelConfig) -> PyTree:
    """Full-model spec tree with the layer stack staged over 'pp'."""
    if parallel.pipeline_parallel == 1:
        return specs
    out = dict(specs)
    out["layers"] = stage_layer_specs(specs["layers"])
    return out


# ---------------------------------------------------------------------------
# The pipelined stack
# ---------------------------------------------------------------------------



def tight_indices(rel, pp: int, vpp: int):
    """(microbatch, chunk) worked at ``rel`` ticks into a stage's schedule
    under the tight group-interleaved order — microbatches advance in
    groups of pp, each group cycling through all vpp chunks.  Pure
    arithmetic: works on traced jnp values (the tick body) and Python
    ints (tests) alike; callers clamp/mask out-of-range ``rel``.
    """
    g = rel // pp
    return (g // vpp) * pp + rel % pp, g % vpp


def _stage_tick(cfg: ModelConfig, chunks: PyTree, chunk_idx, x, side,
                rng, layer_offset=0):
    """Apply this device's current layer chunk to one microbatch.

    ``chunks``: [vpp, lpc, ...] local layer params; ``chunk_idx`` selects
    which virtual chunk this tick runs (traced, device-varying).
    ``layer_offset`` is the chunk's first *global* layer index (keeps the
    LIMA/drop-path per-layer ramps global across stages).

    The cast to compute dtype happens *here*, per tick: when the caller holds
    fp32 params, the scan transpose then accumulates each tick's (bf16)
    weight cotangents into an fp32 buffer — the analogue of the reference's
    fp32 main_grad accumulation (megatron/model/distributed.py:75-200,
    fused wgrad accum fused_weight_gradient_dense.cu).
    """
    def index_and_cast(path, c):
        c = jax.lax.dynamic_index_in_dim(c, chunk_idx, 0, keepdims=False)
        # The MoE router deliberately stays fp32 (models/moe.py:
        # routing decisions are precision-sensitive) — don't round it to the
        # compute dtype like the matmul weights.
        if path and getattr(path[-1], "key", None) == "router":
            return c
        return c.astype(cfg.dtype)

    chunk = jax.tree_util.tree_map_with_path(index_and_cast, chunks)
    return stack_forward(cfg, chunk, x, side, rng,
                         layer_offset=layer_offset)


# ---------------------------------------------------------------------------
# Analytic activation-memory model (validated by
# tests/parallel/test_pipeline_memory.py; derived in docs/pipeline_memory.md)
# ---------------------------------------------------------------------------


def pipeline_activation_bytes(
    cfg: ModelConfig,
    *,
    pp: int,
    vpp: int,
    M: int,
    mb: int,
    seq_shard: int,
    recompute: str = "full",
    window: int = 0,
) -> dict:
    """Estimated per-device activation memory of one pipelined train step.

    ``seq_shard`` is the per-device sequence length *after* sequence/context
    sharding (s / (tp_sp · cp)).  Returns the individual terms plus an
    ``upper_bound`` with 2× slack that the memory test asserts against
    ``compile().memory_analysis().temp_size_in_bytes``.

    Terms (B = compute-dtype bytes, T = M·vpp + pp - 1, lpc = layers/chunk):

    - ``boundary``: the scan saves each tick's input and output boundary
      tensor [mb, seq_shard, h] for the backward replay → 2·T·mb·s·h·B.
      With ``window`` W > 0 (vpp=1) only the ceil(T/W) window-entry carries
      plus one in-flight window's 2·W tick boundaries are live.
    - ``layer_residuals``: per-tick per-layer saved values, governed by the
      remat policy: 'full' saves only each layer's checkpoint input (c=1),
      'selective' keeps a few mlp/attn boundaries (c≈4), 'none' keeps all
      internals (c≈4 + 3·ffn/h, GLU counted).  Windowed: only one window's
      W ticks hold residuals at a time (they exist during that window's
      backward replay, not across the whole schedule).
    - ``circ``: the vpp>1 circular re-entry buffer, M·mb·s·h·B.
    - ``head``: transient fp32 logits blocks, ≈3·mb·s·V·4 (fwd value,
      softmax, dlogits — the head is checkpointed so these never stack
      across ticks).
    - ``io_grads``: fp32 cotangent accumulators for the replicated
      embedding/head params, ≈2·V·h·4.
    """
    h = cfg.hidden_size
    lpc = cfg.num_layers // (pp * vpp)
    T = M * vpp + pp - 1
    B = 2 if cfg.dtype == jnp.bfloat16 else 4
    v = cfg.padded_vocab_size()

    per_boundary = mb * seq_shard * h * B
    c = _recompute_cost(cfg, recompute)
    tight = vpp == 1 or M % pp == 0
    if window == -1:  # the auto sentinel resolves to the same W the
        window = auto_remat_window(cfg, pp=pp, vpp=vpp, M=M)  # loss runs
    if window and window > 0 and tight and T > window:
        n_win = -(-T // window)
        boundary = (n_win + 2 * window) * per_boundary
        layer_residuals = int(window * lpc * c * per_boundary)
    else:
        boundary = 2 * T * per_boundary
        layer_residuals = int(T * lpc * c * per_boundary)
    # The M-sized circular re-entry buffer exists only on the legacy
    # (non-divisible-M) interleaved path; the tight schedule re-enters
    # through the ring shift itself.
    circ = (M * per_boundary) if (vpp > 1 and not tight) else 0
    head = 3 * mb * seq_shard * v * 4
    io_grads = 2 * v * h * 4
    terms = {
        "boundary": boundary,
        "layer_residuals": layer_residuals,
        "circ": circ,
        "head": head,
        "io_grads": io_grads,
    }
    terms["total"] = sum(terms.values())
    terms["upper_bound"] = 2 * terms["total"]
    return terms


def _recompute_cost(cfg: ModelConfig, recompute: str) -> float:
    """Saved-values-per-layer coefficient of the analytic memory model —
    the single source for both the estimator and the auto window choice
    (validated by tests/parallel/test_pipeline_memory.py)."""
    return {"full": 1.0,
            "selective": 4.0,
            "none": 4.0 + 3.0 * cfg.ffn_size / cfg.hidden_size}[recompute]


def auto_remat_window(cfg: ModelConfig, *, pp: int, vpp: int, M: int) -> int:
    """Memory-minimizing window size for the tick-loop remat.

    From the analytic model (pipeline_activation_bytes): live boundaries
    ≈ ceil(T/W) window carries + (2 + lpc·c)·W in-window tensors, so the
    optimum is W* = sqrt(T / (2 + lpc·c)).  Selected by
    ``pipeline_remat_window = -1`` (CLI ``--pipeline_remat_window -1``).
    """
    T = M * vpp + pp - 1
    lpc = cfg.num_layers // (pp * vpp)
    c = _recompute_cost(cfg, cfg.recompute)
    w = int(round((T / (2.0 + lpc * c)) ** 0.5))
    return max(w, 1)


# ---------------------------------------------------------------------------
# Full-model pipelined loss (streamed)
# ---------------------------------------------------------------------------


def pipeline_loss(
    cfg: RuntimeConfig,
    params: PyTree,  # pipeline layout (to_pipeline_params)
    batch: dict,  # leaves [M, mb, ...]
    *,
    mesh,
    rng: Optional[jax.Array] = None,
    rope=None,
    return_stats: bool = False,
):
    """Mean masked LM loss over M microbatches through the pipeline.

    Mirrors the per-microbatch loss averaging of the reference schedules
    (schedules.py:129-139 collects per-microbatch losses; training.py:444-452
    averages).  Embedding and CE head are *streamed inside the tick loop*:
    stage 0 embeds microbatch ``t`` on demand and the last stage runs the
    head on each finished microbatch — the wall-clock equivalent of the
    reference's first/last-stage placement, without ever materializing
    ``[M, mb, s, h]`` hidden-state buffers on every device, and the
    tied-embedding all-reduce of module.py:52-121 becomes unnecessary
    (the tied embedding is one logical array whose cotangents from the
    embed and head use sites accumulate through the shard_map transpose).

    ``return_stats`` additionally returns per-token fp32 eval statistics
    ``{"per_token_loss": [M, mb, s], "correct": [M, mb, s]}`` so the
    registry metrics (metrics.py) work under pp > 1 — the reference computes
    metrics at any parallelism (megatron/metrics.py:62-110).
    """
    model_cfg = cfg.model
    parallel = cfg.parallel
    pp = parallel.pipeline_parallel
    vpp = parallel.virtual_pipeline_stages

    if rope is None:
        from ..models.transformer import rope_tables
        rope = rope_tables(model_cfg)
    cos, sin = rope

    tokens = batch["tokens"]  # [M, mb, s]
    M = tokens.shape[0]
    if vpp > 1:
        assert M >= pp, (
            f"interleaved pipeline needs num_microbatches ≥ pp ({M} < {pp})"
        )
    # "Tight" schedule: group-interleaved microbatch order whose re-entry
    # rides the ring shift itself (no circular buffer).  Requires
    # M % pp == 0 when vpp > 1 — the same divisibility the reference's
    # interleaved schedule asserts (schedules.py:253).  At vpp = 1 the
    # group order degenerates to plain 1F1B for any M.
    tight = vpp == 1 or M % pp == 0
    T = M * vpp + pp - 1
    ring = [(s, (s + 1) % pp) for s in range(pp)]
    compute_dtype = model_cfg.dtype

    embed_rng = stack_rng = None
    if rng is not None:
        embed_rng, stack_rng = jax.random.split(rng)
    deterministic = rng is None

    # Per-use-site cast to compute dtype: callers may hold fp32 params so
    # that cross-tick cotangent accumulation (the scan transposes) runs in
    # fp32, matching _accumulate_grads' per-microbatch fp32 sum.
    def cast(tree):
        return jax.tree.map(lambda x: x.astype(model_cfg.dtype), tree)

    position_ids = batch.get("position_ids")
    cp_axis = model_cfg.context_parallel_axis
    if cp_axis is not None and position_ids is None:
        # Inside the manual-cp pipeline body each shard sees only its local
        # sequence chunk, so RoPE needs explicit *global* positions.
        s = tokens.shape[-1]
        position_ids = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32),
                                        tokens.shape)

    # Embedding + head params cross the shard_map boundary replicated over
    # the manual axes (auto axes — tp — still shard them via GSPMD).
    io_params = {"embedding": params["embedding"],
                 "final_norm": params["final_norm"]}
    if "lm_head" in params:
        io_params["lm_head"] = params["lm_head"]

    labels = batch["labels"]
    loss_mask = batch["loss_mask"]
    seg = batch.get("segment_ids")
    # cp is *manual* inside this shard_map, so only the (auto) tp
    # sequence-parallel axis may appear in residual-stream constraints.
    sp_axes = ((model_cfg.sequence_parallel_axis,)
               if model_cfg.sequence_parallel_axis else ())

    # dp is manual too (microbatch dim sharded explicitly): a dp-sharded
    # batch argument entering a pp-manual shard_map as an *auto*-axis
    # operand trips an XLA SPMD-partitioner grouping CHECK
    # (spmd_partitioner_util.cc) at dp×pp×tp — and explicit dp also makes
    # the DP loss/grad reduction visible, mirroring the reference's DDP
    # all-reduce (megatron/model/distributed.py:202).  Param cotangents
    # psum over dp through the shard_map transpose (params enter
    # dp-replicated), exactly as they already do for cp.
    dp_axis = (mesh_lib.DATA_AXIS
               if (mesh_lib.DATA_AXIS in mesh.axis_names
                   and dict(mesh.shape).get(mesh_lib.DATA_AXIS, 1) > 1)
               else None)

    def pipelined(chunks, io_p, tokens, labels, loss_mask, pos_mb, seg_mb):
        # chunks: [vpp, 1, lpc, ...] (pp axis manual) → squeeze stage dim
        chunks_local = jax.tree.map(lambda c: c[:, 0], chunks)
        stage = jax.lax.axis_index(PP)

        embed_rng_l, stack_rng_l = embed_rng, stack_rng
        if dp_axis is not None and stack_rng_l is not None:
            # distinct dropout streams per dp shard (auto-dp got this from
            # GSPMD sharding one global mask; manual-dp must fold the
            # shard index)
            dpi = jax.lax.axis_index(dp_axis)
            embed_rng_l = jax.random.fold_in(embed_rng_l, dpi)
            stack_rng_l = jax.random.fold_in(stack_rng_l, dpi)

        mb_shape = tokens.shape[1:] + (model_cfg.hidden_size,)
        circ = (jnp.zeros((M,) + mb_shape, compute_dtype)
                if vpp > 1 and not tight else None)
        stats0 = None
        if return_stats:
            stats0 = (jnp.zeros(tokens.shape, jnp.float32),   # per-token CE
                      jnp.zeros(tokens.shape, jnp.float32))   # argmax correct

        def cp_sum(x):
            """Token-space sums must span every manual axis that shards
            tokens: cp (seq) and dp (batch)."""
            axes = tuple(a for a in (cp_axis, dp_axis) if a is not None)
            return jax.lax.psum(x, axes) if axes else x

        def head_fn(h, lab, msk):
            """Final norm → unembed → CE on one finished microbatch.

            Runs on every device each tick (SPMD); the result is masked to
            the last stage.  Checkpointed so the [mb, s, vocab] fp32 logits
            are a transient of each tick, not a saved residual.
            """
            hp = cast(io_p)
            h = norm_apply(model_cfg.norm_type, h, hp["final_norm"],
                           model_cfg.norm_eps, impl=model_cfg.norm_impl)
            logits = model_lib.unembed(model_cfg, hp, h)
            per_token = cross_entropy(logits, lab,
                                      vocab_size=model_cfg.vocab_size)
            msk = msk.astype(jnp.float32)
            # masked mean with cp-global sums (the head runs inside the
            # manual-cp region, so seq reductions need explicit psums)
            num = cp_sum(jnp.sum(per_token * msk))
            den = jnp.maximum(cp_sum(jnp.sum(msk)), 1.0)
            correct = None
            if return_stats:
                correct = (jnp.argmax(logits, axis=-1) == lab
                           ).astype(jnp.float32)
            return num / den, per_token, correct

        head_fn = jax.checkpoint(head_fn, prevent_cse=False)

        def tick(carry, t):
            state, circ, aux_sum, loss_sum, stats = carry
            # Which microbatch / chunk this stage works on at tick t.
            rel = t - stage  # ticks since this stage first saw work
            relc = jnp.clip(rel, 0, None)
            if tight:
                # Group-interleaved order (the reference's interleaved
                # 1F1B, schedules.py:253, which likewise requires
                # M % pp == 0): microbatches advance in groups of pp and
                # each group runs all vpp chunks before the next group
                # starts.  Re-entry is then *tight*: the wrap the last
                # stage ppermutes at tick t-1 is exactly the
                # (m, chunk-1) boundary stage 0 needs at tick t, so no
                # M-sized circular buffer exists and windowed remat
                # composes the same as at vpp = 1.
                m_raw, chunk_idx = tight_indices(relc, pp, vpp)
                m_idx = jnp.clip(m_raw, 0, M - 1)
            else:
                m_idx = relc % M
                chunk_idx = jnp.clip(rel // M, 0, vpp - 1)

            # Stage-0 input: embed a fresh microbatch on demand when a
            # microbatch enters chunk 0, wrapped re-entries otherwise
            # (ring state if tight, circular storage if not).  The embed
            # is computed everywhere and selected on stage 0 — its
            # cotangent is zero elsewhere (the jnp.where transpose), so
            # embedding grads are exact.
            t_in = m_idx if tight else jnp.minimum(t, M - 1)
            tok = jax.lax.dynamic_index_in_dim(tokens, t_in, 0,
                                               keepdims=False)
            pos_in = (None if pos_mb is None else
                      jax.lax.dynamic_index_in_dim(pos_mb, t_in, 0,
                                                   keepdims=False))
            er = (None if embed_rng_l is None
                  else jax.random.fold_in(embed_rng_l, t_in))
            fresh = model_lib.embed(
                model_cfg, {"embedding": cast(io_p["embedding"])},
                tok, pos_in, None, er, deterministic,
            ).astype(compute_dtype)
            if tight:
                current = jnp.where((stage == 0) & (chunk_idx == 0),
                                    fresh, state)
            else:
                wrapped = jax.lax.dynamic_index_in_dim(
                    circ, t % M, 0, keepdims=False)
                inp = jnp.where(t < M, fresh, wrapped)
                current = jnp.where(stage == 0, inp, state)

            tick_rng = None
            if stack_rng_l is not None:
                # unique stream per (microbatch, ring position)
                tick_rng = jax.random.fold_in(
                    jax.random.fold_in(stack_rng_l, m_idx),
                    chunk_idx * pp + stage)

            sel_side = AttnSideInputs(
                rope_cos=cos, rope_sin=sin,
                position_ids=(None if pos_mb is None else
                              jax.lax.dynamic_index_in_dim(
                                  pos_mb, m_idx, 0, keepdims=False)),
                segment_ids=(None if seg_mb is None else
                             jax.lax.dynamic_index_in_dim(
                                 seg_mb, m_idx, 0, keepdims=False)),
                deterministic=deterministic,
                seq_shard_axes=sp_axes,
            )

            lpc = model_cfg.num_layers // (pp * vpp)
            out, tick_aux = _stage_tick(
                model_cfg, chunks_local, chunk_idx, current, sel_side,
                tick_rng, layer_offset=(chunk_idx * pp + stage) * lpc)
            # Bubble ticks (warmup garbage / cooldown re-runs) must not
            # contribute MoE aux loss/stats.
            tick_valid = (rel >= 0) & (rel < M * vpp)
            aux_sum = jax.tree.map(
                lambda a, t: a + jnp.where(tick_valid, t, 0.0),
                aux_sum, tick_aux)

            # Streamed head: the microbatch finishing at tick t (last
            # chunk, last stage) goes through norm→unembed→CE right here.
            # The bounds matter for the windowed schedule's padding ticks
            # (t ≥ T), which must not re-count any microbatch.
            if tight:
                rel_l = t - (pp - 1)  # last stage's rel at this tick
                relc_l = jnp.clip(rel_l, 0, None)
                out_idx, chunk_l = tight_indices(relc_l, pp, vpp)
                head_valid = ((rel_l >= 0) & (rel_l < M * vpp)
                              & (chunk_l == vpp - 1) & (stage == pp - 1))
            else:
                out_idx = t - (vpp - 1) * M - (pp - 1)
                head_valid = ((out_idx >= 0) & (out_idx < M)
                              & (stage == pp - 1))
            w_idx = jnp.clip(out_idx, 0, M - 1)
            lab_m = jax.lax.dynamic_index_in_dim(labels, w_idx, 0,
                                                 keepdims=False)
            msk_m = jax.lax.dynamic_index_in_dim(loss_mask, w_idx, 0,
                                                 keepdims=False)
            mb_loss, per_tok, correct = head_fn(out, lab_m, msk_m)
            loss_sum = loss_sum + jnp.where(head_valid, mb_loss, 0.0)

            if stats is not None:
                pt_buf, ok_buf = stats
                sel = head_valid.astype(jnp.float32)
                pt_old = jax.lax.dynamic_index_in_dim(pt_buf, w_idx, 0,
                                                      keepdims=False)
                ok_old = jax.lax.dynamic_index_in_dim(ok_buf, w_idx, 0,
                                                      keepdims=False)
                pt_buf = jax.lax.dynamic_update_index_in_dim(
                    pt_buf, sel * per_tok + (1 - sel) * pt_old, w_idx, 0)
                ok_buf = jax.lax.dynamic_update_index_in_dim(
                    ok_buf, sel * correct + (1 - sel) * ok_old, w_idx, 0)
                stats = (pt_buf, ok_buf)

            # Rotate the ring: stage s → s+1; stage 0 receives the wrap
            # from the last stage.
            shifted = jax.lax.ppermute(out, PP, ring)

            if circ is not None:
                # The wrap produced at tick t is microbatch (t-(pp-1)) mod M
                # finishing a chunk round; park it for re-entry.
                c_idx = jnp.clip(t - (pp - 1), 0, None) % M
                c_valid = t >= pp - 1
                c_existing = jax.lax.dynamic_index_in_dim(
                    circ, c_idx, 0, keepdims=False)
                circ = jax.lax.dynamic_update_index_in_dim(
                    circ, jnp.where(c_valid, shifted, c_existing), c_idx, 0)

            return (shifted, circ, aux_sum, loss_sum, stats), None

        if model_cfg.num_experts > 0:
            from ..models.moe import stats_zero

            aux0 = stats_zero(model_cfg)
        else:
            aux0 = jnp.zeros((), jnp.float32)
        init = (jnp.zeros(mb_shape, compute_dtype), circ,
                aux0, jnp.zeros((), jnp.float32),
                stats0)
        W = parallel.pipeline_remat_window
        if W == -1:
            W = auto_remat_window(model_cfg, pp=pp, vpp=vpp, M=M)
        if W and W > 0 and tight and T > W:
            # Windowed rematerialization: the plain scan saves every tick's
            # boundary in/out for the backward replay (2·T tensors); at
            # grad-accum counts M ≥ 64 that dwarfs the reference's ≤pp
            # in-flight 1F1B bound (schedules.py:606-722).  Checkpointing
            # windows of W ticks keeps only ceil(T/W) window carries plus
            # one window's residuals live — memory ~O(T/W + W), at the cost
            # of one extra forward replay per window in backward.  Under
            # the tight interleaved schedule the carry is still a single
            # boundary tensor (no circular buffer), so this composes with
            # vpp > 1 unchanged.  Padding ticks (t ≥ T) are no-ops: every
            # update in `tick` is masked by tick_valid / head_valid /
            # c_valid, all false there.
            n_win = -(-T // W)
            ticks = jnp.arange(n_win * W).reshape(n_win, W)

            def window_body(carry, ts):
                carry, _ = jax.lax.scan(tick, carry, ts)
                return carry, None

            (_, _, aux_sum, loss_sum, stats), _ = jax.lax.scan(
                jax.checkpoint(window_body, prevent_cse=False), init, ticks)
        else:
            (_, _, aux_sum, loss_sum, stats), _ = jax.lax.scan(
                tick, init, jnp.arange(T))

        # Only the last stage accumulated real losses; the psums make the
        # scalars (and the small [M, mb, s] eval stats) pp-invariant.  All
        # boundary collectives here are fp32 — partial-auto shard_map lowers
        # bf16 all-reduces to a form that crashes XLA:CPU's
        # AllReducePromotion pass (jax 0.9.0), and the streamed design only
        # ever reduces fp32 scalars/stats anyway.
        # mb losses are already cp/dp-global (cp_sum in head_fn), so only
        # the pp-sum remains; it makes the scalar identical on all shards.
        loss_total = jax.lax.psum(loss_sum, PP)
        # Each (stage, chunk) processed every microbatch exactly once, so
        # the pp-sum of the local aux sums covers all L layers × M
        # microbatches; cp/dp shards see equal token counts → mean over
        # those axes.
        aux = jax.lax.psum(aux_sum, PP)
        for ax in (cp_axis, dp_axis):
            if ax is not None:
                aux = jax.lax.pmean(aux, ax)
        if stats is not None:
            stats = tuple(jax.lax.psum(b, PP) for b in stats)
        return loss_total, aux, stats

    layer_in_specs = jax.tree.map(lambda _: P(None, PP), params["layers"])
    manual_axes = {PP}
    if dp_axis is not None:
        manual_axes.add(dp_axis)
    if cp_axis is not None:
        manual_axes.add(cp_axis)
        side_spec = P(None, dp_axis, cp_axis)  # [M, mb, s]
        assert position_ids is not None
    else:
        side_spec = P(None, dp_axis) if dp_axis is not None else P()
    stats_spec = (side_spec, side_spec) if return_stats else None
    fn = jax.shard_map(
        pipelined,
        mesh=mesh,
        in_specs=(layer_in_specs, P(), side_spec, side_spec, side_spec,
                  side_spec, side_spec),
        out_specs=(P(), P(), stats_spec),
        axis_names=manual_axes,
        check_vma=False,
    )
    loss_total, moe_aux, stats = fn(params["layers"], io_params, tokens,
                                    labels, loss_mask, position_ids, seg)

    loss = loss_total / M
    if model_cfg.num_experts > 0:
        from ..models.moe import aux_loss_of

        # moe_aux sums over all layers and microbatches; per-microbatch mean
        # matches the non-pipelined compute_loss accounting.
        loss = loss + model_cfg.moe_aux_loss_coeff * aux_loss_of(moe_aux) / M
    if return_stats:
        return loss, {"per_token_loss": stats[0], "correct": stats[1]}
    return loss
