"""Top-level causal language model: embedding → decoder stack → lm head.

Parity with the reference's ``TransformerLanguageModel`` + ``GPTModel``
(megatron/model/language_model.py:56-638, megatron/model/gpt_model.py:18-124):
vocab(-parallel) word embedding, optional learned absolute positions, the
decoder stack, final norm, and an untied lm_head or tied-embedding logits.
The loss (vocab-parallel cross entropy) lives in
``megatron_llm_tpu.parallel.cross_entropy``.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..config import ModelConfig, PositionEmbeddingType
from .transformer import (
    REC_MIXERS,
    RING_NAMES,
    STREAM_DTYPE,
    AttnSideInputs,
    Params,
    _dropout,
    PagedKV,
    _lead_layers,
    ffn_input,
    init_lead_params,
    init_stack_params,
    layer_forward,
    norm_init,
    rope_tables,
    scan_stack,
    stack_forward,
    stack_forward_cached,
    stack_forward_paged,
)
from ..ops.norms import norm_apply


def init_params(key: jax.Array, cfg: ModelConfig, tp: int = 1) -> Params:
    """Full model parameter pytree.

    The vocab is padded to divide the TP axis
    (reference: megatron/tokenizer/tokenizer.py:39-63).
    """
    h = cfg.hidden_size
    dtype = cfg.dtype
    v = cfg.padded_vocab_size(tp)
    k_embed, k_pos, k_stack, k_head = jax.random.split(key, 4)

    params: Params = {
        "embedding": {
            "word": (cfg.init_method_std
                     * jax.random.normal(k_embed, (v, h), jnp.float32)
                     ).astype(dtype),
        },
        "layers": init_stack_params(k_stack, cfg),
        "final_norm": norm_init(cfg.norm_type, h, dtype),
    }
    if cfg.position_embedding_type == PositionEmbeddingType.ABSOLUTE:
        params["embedding"]["position"] = (
            cfg.init_method_std
            * jax.random.normal(k_pos, (cfg.max_position_embeddings, h),
                                jnp.float32)
        ).astype(dtype)
    if cfg.tokentype_size:
        params["embedding"]["tokentype"] = (
            cfg.init_method_std
            * jax.random.normal(jax.random.fold_in(k_pos, 1),
                                (cfg.tokentype_size, h), jnp.float32)
        ).astype(dtype)
    if not cfg.tie_embed_logits:
        # untied lm_head Parameter (reference:
        # megatron/model/language_model.py:437-457)
        params["lm_head"] = (
            cfg.init_method_std
            * jax.random.normal(k_head, (h, v), jnp.float32)
        ).astype(dtype)
    if cfg.moe_first_dense_layers:
        params["lead_layers"] = init_lead_params(
            jax.random.fold_in(k_stack, 1), cfg)
    if cfg.layer_pattern and cfg.moe_router_scoring == "sigmoid":
        params = level_router_bias(cfg, params, jax.random.fold_in(key, 1))
    return params


def level_router_bias(cfg: ModelConfig, params: Params, key: jax.Array,
                      tokens: int = 2048) -> Params:
    """A tree made from a seed has no training behind its routers'
    selection bias, and without one a random stack routes unevenly: what
    its relu^2 and SiLU parts add to the stream has a direction every
    token shares, which lifts the same experts' scores for all of them
    (the busiest held expert of Nemotron-3-Super's 11-layer run drew 8
    times the mean: PERF.md, PR 44).  Training sets the bias against just
    that, so ``init_params`` does what it would have done: one sequence
    of ``tokens`` seeded tokens goes through the stack, and each
    feed-forward block's bias is set on the way (``moe.level_bias``)
    before the block runs, so that the blocks after it see a levelled
    layer's output: a block of the feed-forward part alone, and a
    two-part block whose mixer is attention (its experts read the stream
    after the attention part: ``ffn_input``).  Blocks that hold another
    mixer keep the bias they drew."""
    from .moe import level_bias

    toks = jax.random.randint(key, (1, tokens), 1, cfg.vocab_size - 1)
    position_ids = jnp.arange(tokens, dtype=jnp.int32)[None]
    cos, sin = rope_tables(cfg)
    side = AttnSideInputs(rope_cos=cos, rope_sin=sin,
                          position_ids=position_ids, deterministic=True)
    x = embed(cfg, params, toks, position_ids).astype(STREAM_DTYPE)
    runs = [list(trees) for trees in (
        params["layers"] if cfg.layer_runs else [params["layers"]])]
    for p in _lead_layers(params.get("lead_layers")):
        x = layer_forward(cfg.lead_layer_config, p, x, side)[0]
    for (period, times), stacks in zip(cfg.stack_runs, runs):
        for i in range(times):
            for j, kind in enumerate(period):
                p = jax.tree.map(lambda a: a[i], stacks[j])
                mlp = stacks[j].get("mlp", {})
                if "router_bias" in mlp and (kind == "mlp"
                                             or "attn" in stacks[j]):
                    h1 = (norm_apply(cfg.norm_type, x, p["input_norm"],
                                     cfg.norm_eps, impl=cfg.norm_impl)
                          if kind == "mlp"
                          else ffn_input(cfg, p, x, side, kind))
                    bias = level_bias(cfg, p["mlp"], h1[0])
                    p = {**p, "mlp": {**p["mlp"], "router_bias": bias}}
                    stacks[j] = {**stacks[j], "mlp": {
                        **mlp, "router_bias": mlp["router_bias"].at[i].set(
                            bias)}}
                x = layer_forward(cfg, p, x, side, kind=kind)[0]
    return {**params, "layers": runs if cfg.layer_runs else runs[0]}


@jax.named_scope("embed")
def embed(cfg: ModelConfig, params: Params, tokens: jax.Array,
          position_ids: Optional[jax.Array] = None,
          tokentype_ids: Optional[jax.Array] = None,
          dropout_rng=None, deterministic: bool = True) -> jax.Array:
    """Token (+position, +tokentype) embedding with embedding dropout
    (reference: megatron/model/language_model.py:133-327).

    The word table may be the int8 per-row ``{"q", "scale"}`` form of
    ops/quant.py:quantize_embedding — the gather dequantizes only the
    looked-up rows, keeping the table int8-resident in HBM."""
    from ..ops.quant import embedding_lookup

    x = embedding_lookup(params["embedding"]["word"], tokens, cfg.dtype)
    if cfg.embedding_multiplier != 1.0:
        # (a hybrid stack's stream is float32: scaled there, not rounded)
        x = x.astype(STREAM_DTYPE if cfg.layer_pattern else x.dtype)
        x = x * jnp.asarray(cfg.embedding_multiplier, x.dtype)
    if "position" in params["embedding"]:
        if position_ids is None:
            position_ids = jnp.arange(tokens.shape[1])[None, :]
        x = x + params["embedding"]["position"][position_ids]
    if tokentype_ids is not None and "tokentype" in params["embedding"]:
        x = x + params["embedding"]["tokentype"][tokentype_ids]
    x = _dropout(x, cfg.hidden_dropout, dropout_rng, deterministic)
    return x


@jax.named_scope("lm_head")
def unembed(cfg: ModelConfig, params: Params, x: jax.Array) -> jax.Array:
    """Project hidden states to (padded-)vocab logits, float32
    (reference: parallel_lm_logits, megatron/model/language_model.py:24-53).
    The cast is made here, under the scope: XLA fuses it into the matmul,
    and a fusion is named after its root.  Where the architecture scales
    its logits (``cfg.logits_scaling``) they are divided here.

    A tied head contracts the hidden axis of ``x`` with the hidden axis of
    the table as it is stored: the program holds no transposed table
    (serving's rule that a weight is read once, where it lies —
    docs/inference.md; tests/serving/test_decode_weights.py)."""
    if cfg.tie_embed_logits:
        logits = jax.lax.dot_general(
            x, params["embedding"]["word"],
            (((x.ndim - 1,), (1,)), ((), ())))
    else:
        logits = x @ params["lm_head"]
    logits = logits.astype(jnp.float32)
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    return logits


def unembed_weight(cfg: ModelConfig, params: Params) -> jax.Array:
    """[h, padded_vocab] unembedding matrix (tied or untied), for the
    training loss's fused linear + cross-entropy head, which streams it
    over the vocabulary; ``unembed`` reads a tied table as it lies."""
    if cfg.tie_embed_logits:
        return params["embedding"]["word"].T
    return params["lm_head"]


def forward_hidden(
    cfg: ModelConfig,
    params: Params,
    tokens: jax.Array,  # [b, s] int32
    *,
    position_ids: Optional[jax.Array] = None,
    segment_ids: Optional[jax.Array] = None,
    tokentype_ids: Optional[jax.Array] = None,
    rng: Optional[jax.Array] = None,
    deterministic: bool = True,
    rope: Optional[tuple] = None,
    lora=None,
):
    """Forward through the final norm → ``(hidden [b,s,h], moe_aux)``.

    The pre-unembedding split lets the training loss use the fused
    linear+CE head (parallel/cross_entropy.fused_linear_cross_entropy)
    without materializing fp32 logits.

    ``lora`` is ``(arenas, mask)`` — layer-stacked LoRA arena factors
    plus the per-row column mask (ops/lora.py) — applied as projection
    epilogues down the stack; None means base weights only."""
    if rope is None:
        cos, sin = rope_tables(cfg)
    else:
        cos, sin = rope

    embed_rng = stack_rng = None
    if not deterministic:
        if rng is None and (cfg.hidden_dropout > 0 or cfg.attention_dropout > 0):
            raise ValueError(
                "deterministic=False with dropout enabled requires an rng key"
            )
        if rng is not None:
            embed_rng, stack_rng = jax.random.split(rng)

    x = embed(cfg, params, tokens, position_ids, tokentype_ids,
              embed_rng, deterministic)
    # cp is a GSPMD-auto axis on this (non-pipelined) path, so it joins the
    # sequence-sharding constraint alongside the sequence-parallel tp axis.
    seq_axes = tuple(a for a in (cfg.context_parallel_axis,
                                 cfg.sequence_parallel_axis) if a)
    side = AttnSideInputs(
        rope_cos=cos, rope_sin=sin,
        position_ids=position_ids, segment_ids=segment_ids,
        deterministic=deterministic,
        seq_shard_axes=seq_axes,
    )
    x, moe_aux = stack_forward(cfg, params["layers"], x, side, stack_rng,
                               lora=lora, lead=params.get("lead_layers"))
    # (a hybrid stack hands its float32 stream to the final norm)
    x = norm_apply(cfg.norm_type, x, params["final_norm"], cfg.norm_eps,
                   impl=cfg.norm_impl).astype(cfg.dtype)
    return x, moe_aux


def forward(
    cfg: ModelConfig,
    params: Params,
    tokens: jax.Array,  # [b, s] int32
    *,
    position_ids: Optional[jax.Array] = None,
    segment_ids: Optional[jax.Array] = None,
    tokentype_ids: Optional[jax.Array] = None,
    rng: Optional[jax.Array] = None,
    deterministic: bool = True,
    rope: Optional[tuple] = None,
    return_aux: bool = False,
    lora=None,
):
    """Full forward to logits [b, s, padded_vocab] (fp32).

    With ``return_aux`` also returns the MoE load-balance aux loss
    (0 for dense models) — the training loss adds it scaled by
    ``cfg.moe_aux_loss_coeff``.
    """
    x, moe_aux = forward_hidden(
        cfg, params, tokens, position_ids=position_ids,
        segment_ids=segment_ids, tokentype_ids=tokentype_ids, rng=rng,
        deterministic=deterministic, rope=rope, lora=lora)
    logits = unembed(cfg, params, x)
    if return_aux:
        return logits, moe_aux
    return logits


def forward_cached(
    cfg: ModelConfig,
    params: Params,
    tokens: jax.Array,  # [b, s] int32 — the *new* tokens only
    k_cache: jax.Array,  # [L, b, kv_heads, max_len, head_dim]
    v_cache: jax.Array,
    cache_len: jax.Array,  # int32 scalar (or [b] per-sample fills) —
    #                        tokens already in the cache
    *,
    rope: Optional[tuple] = None,
    empty_cache: bool = False,
    last_logit_only: bool = False,
    logit_rows: Optional[jax.Array] = None,
    lora=None,
):
    """Incremental forward for generation: consume ``tokens`` positioned at
    ``cache_len..cache_len+s``, append their K/V to the cache, and return
    ``(logits[b, s, vocab] fp32, new_k_cache, new_v_cache)``.

    ``last_logit_only=True`` unembeds only the final position (logits come
    back [b, 1, vocab]) — prefill callers that just seed the decode loop
    skip the full [b, s, padded_vocab] projection, which XLA does NOT
    narrow through a later slice.

    The caller owns advancing ``cache_len`` (reference: InferenceParams
    sequence-offset bookkeeping, megatron/text_generation/forward_step.py).

    ``empty_cache=True`` is the caller's STATIC promise that
    ``cache_len == 0`` (the first prefill): attention then runs ordinary
    causal attention over the window — the flash kernel — instead of the
    O(s·max_len) cached-score einsum.  The cache K/V writes are identical
    either way.
    """
    if rope is None:
        cos, sin = rope_tables(cfg)
    else:
        cos, sin = rope
    b, s = tokens.shape
    cache_len = jnp.asarray(cache_len, jnp.int32)
    if cache_len.ndim == 1:
        # per-sample fill levels (ragged speculative decoding): each
        # sample's new tokens sit at its own positions
        position_ids = cache_len[:, None] + jnp.arange(s, dtype=jnp.int32)
    else:
        position_ids = jnp.broadcast_to(
            (cache_len + jnp.arange(s, dtype=jnp.int32))[None, :], (b, s))
    x = embed(cfg, params, tokens, position_ids)

    side = AttnSideInputs(rope_cos=cos, rope_sin=sin,
                          position_ids=position_ids, deterministic=True,
                          cache_is_empty=empty_cache)
    x, new_k, new_v = stack_forward_cached(
        cfg, params["layers"], x, side, k_cache, v_cache, cache_len,
        lora=lora)
    x = norm_apply(cfg.norm_type, x, params["final_norm"], cfg.norm_eps,
                   impl=cfg.norm_impl)
    if last_logit_only:
        x = x[:, -1:]
    elif logit_rows is not None:
        x = jnp.take_along_axis(
            x, logit_rows.astype(jnp.int32)[:, None, None], axis=1)
    logits = unembed(cfg, params, x)
    return logits, new_k, new_v


def paged_decode_eligible(cfg: ModelConfig, k_pool, s: int = 1,
                          mesh=None) -> bool:
    """Whether ``forward_cached_paged``'s composed route takes the paged
    kernel for this pool (ops/attention.py:paged_decode_route): asked by
    the route itself under the trace's own mesh, and by the engine at
    ``start()`` — with its mesh — to label its decode steps."""
    from ..ops import attention as attn_ops

    block = jax.tree.leaves(k_pool)[0].shape[3]
    # (a pool of latent rows: one row of that width and no head axis,
    # walked by kernels/mla_decode.py)
    kv_heads, width = ((1, cfg.latent_row_width) if cfg.latent_row_width
                       else (cfg.kv_heads, cfg.head_dim))
    return attn_ops.paged_decode_route(
        s, cfg.num_attention_heads, kv_heads, width, block,
        mesh if mesh is not None else attn_ops._active_mesh())


def forward_cached_paged(
    cfg: ModelConfig,
    params: Params,
    tokens: jax.Array,   # [b, 1] int32 — one pending token per slot
    k_pool: jax.Array,   # [L, n_blocks, kv_heads, block, head_dim] (pytree)
    v_pool: jax.Array,
    tables: jax.Array,   # [b, T] int32 per-slot block tables
    fills: jax.Array,    # [b] int32 per-slot fill levels
    *,
    rope: Optional[tuple] = None,
    allow_paged: bool = True,
    lora=None,
):
    """Single-token decode over the paged block pool.

    The paged analogue of ``forward_cached`` for the serving engine's
    slot batch: each slot's token attends the blocks its table names and
    its new K/V row is scattered into block ``tables[s, fill//bk]`` at
    offset ``fill % bk``.  Two routes, one caller-visible contract:

    * the *paged* route, where ``paged_decode_route`` says the
      paged attention kernel runs (a TPU, block size a multiple of 128,
      head width a multiple of 64, a mesh whose tp divides the heads —
      bf16 and int8 pools alike): the layers are scanned with every
      layer's attention reading its KV out of the pool through the
      tables (``stack_forward_paged``), and the step's new rows are
      appended in place afterwards.  Nothing of the pool's size, or of
      slots × ``max_seq_len``, is built or copied.
    * the *gather* route everywhere else (the CPU, odd block
      sizes): gather the tables into a dense working view
      (``cache_gather_blocks``) and run the ordinary ``forward_cached``
      path over it, then scatter back only the appended rows.  Gathered
      garbage beyond a slot's fill is masked by score replacement, so
      this route is bitwise-identical to a contiguously grown cache.

    The step decides between the two itself from what it observes
    (``paged_decode_eligible``).  ``allow_paged=False`` holds it to the
    gather route: the engine passes it while it speculates, because
    ``forward_cached_paged_verify`` walks the gather route's arithmetic
    and a verify step must round as a decode step does.

    Returns ``(logits [b, 1, vocab] fp32, new_k_pool, new_v_pool)``.
    """
    if rope is None:
        rope = rope_tables(cfg)
    cos, sin = rope
    fills = jnp.asarray(fills, jnp.int32)
    tables = jnp.asarray(tables, jnp.int32)
    bk = jax.tree.leaves(k_pool)[0].shape[3]
    bids = jnp.take_along_axis(tables, (fills // bk)[:, None], axis=1)[:, 0]
    offs = fills % bk
    if allow_paged and paged_decode_eligible(cfg, k_pool, tokens.shape[1]):
        x = embed(cfg, params, tokens, fills[:, None])
        side = AttnSideInputs(rope_cos=cos, rope_sin=sin,
                              position_ids=fills[:, None],
                              deterministic=True)
        x, k_rows, v_rows = stack_forward_paged(
            cfg, params["layers"], x, side, k_pool, v_pool, tables, fills,
            lora=lora)
        k_pool = cache_append_rows(k_pool, k_rows, bids, offs)
        v_pool = cache_append_rows(v_pool, v_rows, bids, offs)
        x = norm_apply(cfg.norm_type, x, params["final_norm"], cfg.norm_eps,
                       impl=cfg.norm_impl)
        return unembed(cfg, params, x), k_pool, v_pool
    k_dense = cache_gather_blocks(k_pool, tables)
    v_dense = cache_gather_blocks(v_pool, tables)
    logits, k_dense, v_dense = forward_cached(
        cfg, params, tokens, k_dense, v_dense, fills, rope=rope, lora=lora)
    k_pool = cache_append_rows(
        k_pool, cache_rows_at(k_dense, fills), bids, offs)
    v_pool = cache_append_rows(
        v_pool, cache_rows_at(v_dense, fills), bids, offs)
    return logits, k_pool, v_pool


def forward_cached_paged_verify(
    cfg: ModelConfig,
    params: Params,
    window: jax.Array,   # [S, W] int32 — pending token + drafted tokens
    k_pool: jax.Array,   # [L, n_blocks, kv_heads, block, head_dim] (pytree)
    v_pool: jax.Array,
    tables: jax.Array,   # [S, T] int32 per-slot block tables
    fills: jax.Array,    # [S] int32 per-slot fill levels
    bids: jax.Array,     # [S*W] int32 destination block per window row
    offs: jax.Array,     # [S*W] int32 in-block offset per window row
    *,
    rope: Optional[tuple] = None,
    tree: Optional[tuple] = None,
    lora=None,
):
    """Batched variable-length speculative *verify* over the paged pool.

    Row ``s`` of ``window`` holds ``[pending, d_1 .. d_{W-1}]`` — its last
    committed token followed by ``W-1`` draft tokens (rows with fewer
    real drafts are padded; the engine ignores their logits).  One
    dispatch runs the whole stack at positions ``fills[s] .. fills[s]+W-1``
    per row with per-row causal masking, returns logits for every window
    position, and appends the window's K/V rows to the pool.

    ``tree`` switches the window from a linear token run to a candidate
    *tree*: ``tree = (depths [S, W] int32, anc [S, W, W] int32)`` where
    window column ``j`` is a tree node at depth ``depths[s, j]`` whose
    ancestor at depth ``dd < depths[s, j]`` is node ``anc[s, j, dd]``
    (entries at or past a node's depth are ignored and may be
    arbitrary).  Nodes must be in BFS order — node 0 is the root (the
    pending token, depth 0), parents precede children, and depths are
    non-decreasing — so the deepest node is last.  Each node runs at
    position ``fills[s] + depths[s, j]`` attending only to the committed
    prefix plus its own root path, which makes every root-to-leaf path
    bitwise-equal to sequentially decoding that path; K/V rows land
    *node-indexed* at the caller's ``(bids, offs)`` (the engine passes
    ``offs = fill + node``), and the caller compacts the accepted path
    to depth-indexed positions afterwards (``cache_move_rows``).
    A chain tree (``depths[s, j] = j``, ``anc[s, j, dd] = dd``)
    reproduces the linear window exactly.

    Rollback is the caller's concern and costs nothing here: rejected
    rows were written to ``(bids, offs)`` slots that the next step simply
    overwrites (the engine routes suppressed rows to the trash block), and
    the fill vector just doesn't advance past the accepted prefix.

    Each verify position is bitwise-identical to the corresponding
    sequential single-token step, which is what makes
    accept-longest-greedy-prefix exact rather than approximate.  The
    window is walked one token at a time over a single gathered dense
    view — the same fixed-arity buffer shape and op sequence as
    ``forward_cached_paged``'s gather route, because XLA's reductions
    are only bitwise-stable when the shapes match exactly (a one-pass
    W-token batch reassociates the attention sums and drifts ~1e-7).
    The gather/append pool round-trip equals in-place dense updates
    leaf-for-leaf (int8 rows requantize through the identical
    ``quantize_rows``), so walking a persistent dense view matches
    re-gathering every step.  "The sequential step" is
    ``forward_cached_paged``'s *gather* route: an
    engine that speculates passes ``allow_paged=False`` to its plain decode
    steps too, so that decode and verify stay one arithmetic.

    The window writes land at ``fills[s] .. fills[s]+W-1``, which the
    caller must keep inside the table capacity (the engine reserves
    blocks and clamps draft length near ``max_seq_len``); the dense
    view is deliberately *not* padded — padding would change the
    attention reduction length and break bitwise equality.

    Returns ``(logits [S, W, vocab] fp32, new_k_pool, new_v_pool)``.
    """
    if rope is None:
        rope = rope_tables(cfg)
    S, W = window.shape
    fills = jnp.asarray(fills, jnp.int32)
    tables = jnp.asarray(tables, jnp.int32)
    bids = jnp.asarray(bids, jnp.int32).reshape(S * W)
    offs = jnp.asarray(offs, jnp.int32).reshape(S * W)
    depths = anc = None
    if tree is not None:
        depths = jnp.asarray(tree[0], jnp.int32)
        anc = jnp.asarray(tree[1], jnp.int32)
    k_dense = cache_gather_blocks(k_pool, tables)
    v_dense = cache_gather_blocks(v_pool, tables)
    if tree is None:
        steps = []
        for j in range(W):
            lj, k_dense, v_dense = forward_cached(
                cfg, params, window[:, j:j + 1], k_dense, v_dense, fills + j,
                rope=rope, lora=lora)
            steps.append(lj)
        logits = jnp.concatenate(steps, axis=1)
        k_pool = cache_append_rows(
            k_pool, cache_rows_range(k_dense, fills, W), bids, offs)
        v_pool = cache_append_rows(
            v_pool, cache_rows_range(v_dense, fills, W), bids, offs)
        return logits, k_pool, v_pool
    # Tree walk over the same gathered dense view: before each node's
    # single-token step, overlay its ancestors' stored rows at dense
    # positions fills+0 .. fills+depth-1 (deeper spec columns are never
    # attended — forward_cached masks columns >= cache_len — so stale
    # rows from a sibling path are invisible).  The per-step shapes and
    # op sequence match sequential decode of the node's root path
    # exactly, which is the bitwise guarantee; the extract/overlay
    # round trip is pure gather/scatter at the dense dtype.
    node_shape = lambda a: a.shape[:3] + (W,) + a.shape[4:]
    k_nodes = jax.tree.map(lambda a: jnp.zeros(node_shape(a), a.dtype),
                           k_dense)
    v_nodes = jax.tree.map(lambda a: jnp.zeros(node_shape(a), a.dtype),
                           v_dense)

    def overlay(dense, nodes, j):
        dj = depths[:, j]
        for dd in range(W - 1):
            a_idx = anc[:, j, dd]

            def one(nd, dn):
                idx = a_idx.reshape((1, -1) + (1,) * (nd.ndim - 2))
                row = jnp.take_along_axis(nd, idx, axis=3)
                cols = jnp.arange(dn.shape[3], dtype=jnp.int32)
                hit = (cols[None, :] == (fills + dd)[:, None]) \
                    & (dd < dj)[:, None]
                hit = hit.reshape((1, S, 1, dn.shape[3])
                                  + (1,) * (dn.ndim - 4))
                return jnp.where(hit, row, dn)

            dense = jax.tree.map(one, nodes, dense)
        return dense

    steps = []
    for j in range(W):
        k_dense = overlay(k_dense, k_nodes, j)
        v_dense = overlay(v_dense, v_nodes, j)
        pj = fills + depths[:, j]
        lj, k_dense, v_dense = forward_cached(
            cfg, params, window[:, j:j + 1], k_dense, v_dense, pj,
            rope=rope, lora=lora)
        steps.append(lj)
        kr = cache_rows_at(k_dense, pj)
        vr = cache_rows_at(v_dense, pj)
        k_nodes = jax.tree.map(
            lambda n, r: n.at[:, :, :, j:j + 1].set(r), k_nodes, kr)
        v_nodes = jax.tree.map(
            lambda n, r: n.at[:, :, :, j:j + 1].set(r), v_nodes, vr)
    logits = jnp.concatenate(steps, axis=1)

    def node_rows(nodes):
        def f(a):
            tail = tuple(a.shape[4:])
            r = jnp.moveaxis(a, 3, 2)                # [L, S, W, kv(,d)]
            return r.reshape((a.shape[0], S * W, a.shape[2], 1) + tail)
        return jax.tree.map(f, nodes)

    k_pool = cache_append_rows(k_pool, node_rows(k_nodes), bids, offs)
    v_pool = cache_append_rows(v_pool, node_rows(v_nodes), bids, offs)
    return logits, k_pool, v_pool


def init_kv_cache(cfg: ModelConfig, batch_size: int, max_len: int,
                  dtype=None):
    """Allocate an empty stacked KV cache ([L, b, kv_heads, max_len, d] ×2).

    Head-major layout: each (layer, batch, head)'s [max_len, d] block is
    contiguous, so the decode GEMVs contract straight over it — the
    seq-major layout forced XLA to materialize a transposed copy of the
    whole cache every step.

    With ``cfg.kv_cache_quant == "int8"`` each side is the int8
    {"q", "scale"} form of ops/kv_quant.py — half the decode cache
    traffic; the whole decode path threads it as a pytree.

    A latent-attention stack's cache is of another kind, latent rows:
    ``[L, b, 1, max_len, kv_lora_rank]`` (the latent ``c``) and ``[L, b,
    1, max_len, qk_rope_head_dim]`` (the shared rotated key part
    ``k_pe``), together ``cfg.latent_row_width`` values a position."""
    if cfg.latent_row_width:
        # latent attention keeps ONE row a position a layer, with no head
        # axis (models/mla.py), in the two leaves every cache-family
        # helper maps over: the latent on the key side, the rotated key
        # part all heads share on the value side.  Nothing is kept twice
        # and no per-head K or V; the latent's 512 columns are whole
        # lane tiles and XLA:TPU lays the 64-wide part out with the
        # positions as lanes, so neither leaf is padded (one leaf of 576
        # columns is padded to 640 in HBM)
        shape = (cfg.kv_layers, batch_size, 1, max_len)
        return (jnp.zeros(shape + (cfg.kv_lora_rank,), dtype or cfg.dtype),
                jnp.zeros(shape + (cfg.qk_rope_head_dim,),
                          dtype or cfg.dtype))
    if cfg.kv_cache_quant == "int8":
        from ..ops.kv_quant import init_quantized_cache

        shape = (cfg.kv_layers, batch_size, cfg.kv_heads, max_len,
                 cfg.head_dim)
        return init_quantized_cache(shape), init_quantized_cache(shape)
    dtype = dtype or cfg.dtype
    shape = (cfg.kv_layers, batch_size, cfg.kv_heads, max_len, cfg.head_dim)
    if cfg.diff_attention:
        # the two value heads of a pair side by side as one: the same
        # bytes a position, in the form every reader takes them
        return (jnp.zeros(shape, dtype),
                jnp.zeros(shape[:2] + (cfg.v_heads, max_len,
                                       cfg.v_head_width), dtype))
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)


def init_rec_state(cfg: ModelConfig, batch_size: int) -> dict:
    """What a hybrid stack keeps a sequence beside its keys and values,
    for ``batch_size`` sequences (the serving engine: one a slot), zero
    at a sequence's start, float32, each kind of recurrent mixer under
    names of its own and only where the stack has such layers: every
    Gated DeltaNet layer's state ``S`` [linear layers, b, value heads,
    key width, value width] and convolution tail ``conv`` [linear layers,
    b, taps - 1, channels]; every Mamba-2 layer's state ``ssm`` [mamba
    layers, b, heads, head width, state width] and tail ``ssm_conv``
    [mamba layers, b, taps - 1, channels] (flat, [.., (taps - 1) x
    channels], where the scan has more than one period:
    ``mamba2.init_state``)
    (``REC_STATE_KINDS`` names them by what a kind keeps).  And two
    counters carried
    on the device and read when somebody asks: ``load`` [layers, router
    outputs] int32, how often each expert was chosen by the positions
    these states were advanced over (zero rows for the layers that do
    not route), and ``rows`` [layers, 2, 2] int32, the (token, choice)
    rows the layer's experts multiplied and skipped (``models/moe.py``),
    each count as two words (``add_rows``: a long prompt adds 10^5 to
    it)."""
    rec = {}
    for keeps, mixer in REC_MIXERS.items():
        n = cfg.layers_keeping(keeps)
        if n:
            for name, a in zip(mixer.names, mixer.start(cfg, batch_size)):
                rec[name] = jnp.zeros((n,) + a.shape, a.dtype)
    if cfg.window_layers:
        # a "window" layer's ring: the last ``sliding_window`` keys and
        # values a sequence, position t at row t % window, in the
        # weights' precision and head-major: a row holds the keys (the
        # values) of the key heads that share a value head side by side
        # (``diff_attention.pair_rows``), so the two rings have one shape,
        # a row is as wide as the lanes, and a step's new row is one
        # contiguous write
        ring = (cfg.window_layers, batch_size, cfg.v_heads,
                cfg.sliding_window, cfg.v_head_width)
        for name in RING_NAMES:
            rec[name] = jnp.zeros(ring, cfg.dtype)
    return {**rec,
            "load": jnp.zeros((cfg.num_layers, cfg.router_experts),
                              jnp.int32),
            "rows": jnp.zeros((cfg.num_layers, 2, 2), jnp.int32)}


# the names of ``init_rec_state``'s state arrays, by what the block kinds
# that keep them keep (``config.BlockKind.keeps``: a Gated DeltaNet
# layer's, a Mamba-2 mixer's, a Mamba-1 mixer's, a "window" layer's rings)
REC_STATE_KINDS = {**{keeps: mixer.names
                      for keeps, mixer in REC_MIXERS.items()},
                   "window": RING_NAMES}


def rec_states(rec: dict) -> dict:
    """The state arrays of ``rec`` (its counters left out)."""
    return {name: rec[name] for names in REC_STATE_KINDS.values()
            for name in names if name in rec}


_ROWS_WORD = 30


def add_rows(rows, more):
    """``rows`` [..., 2] int32, counts as ``(high, low)`` words of
    ``_ROWS_WORD`` bits, plus ``more`` (the same form) → their sum: a
    count that outgrows a word carries over, it does not wrap."""
    low = rows[..., 1] + more[..., 1]
    return jnp.stack([rows[..., 0] + more[..., 0] + (low >> _ROWS_WORD),
                      low & ((1 << _ROWS_WORD) - 1)], axis=-1)


def rows_total(rows):
    """``add_rows``'s words, fetched → the counts, int64."""
    rows = np.asarray(rows).astype(np.int64)
    return (rows[..., 0] << _ROWS_WORD) + rows[..., 1]


def _counted(cfg: ModelConfig, rec: dict, new: dict, counts: dict) -> dict:
    """``new`` with ``rec``'s counters advanced by ``counts``."""
    if cfg.layer_runs and not cfg.num_experts:
        # (a stack written as runs that routes nothing hands its counters
        # through untouched: the phi-4 cell's programs were lowered so; a
        # pattern's dense stack adds its zeros, as the granite cell's
        # were: PERF.md section 7)
        return {**new, "load": rec["load"], "rows": rec["rows"]}
    one = counts["rows"].astype(jnp.int32)
    return {**new, "load": rec["load"] + counts["load"].astype(jnp.int32),
            "rows": add_rows(rec["rows"],
                             jnp.stack([jnp.zeros_like(one), one], axis=-1))}


def forward_cached_hybrid(cfg: ModelConfig, params: Params, tokens,
                          k_cache, v_cache, cache_len, rec: dict, *,
                          valid=None, empty_cache: bool = False,
                          logit_rows=None):
    """``forward_cached`` for a hybrid stack (``cfg.layer_pattern``): the
    full layers append to the dense cache ``[full layers, b, kv heads,
    max_len, d]`` as there, the recurrent mixers continue ``rec``
    (``init_rec_state``) over the positions ``valid`` [b, s] marks (None:
    all; a prefix of each row) and leave it untouched over the others.
    → ``(logits, new_k_cache, new_v_cache, new_rec)``.

    A prompt into an empty cache whose ``logit_rows`` names one row a
    sequence is cut to that row at the boundary between two decoders,
    where the stack has one (``cfg.row_cut_layer``): every row goes
    through the layers before it and through the "full" layer's key and
    value projection, that row alone through its attention, every later
    layer, the final norm and the head; the states, the rings and the
    cache are what every row left."""
    from ..ops.kv_quant import cache_update

    b, s = tokens.shape
    cache_len = jnp.asarray(cache_len, jnp.int32)
    start = cache_len[:, None] if cache_len.ndim == 1 else cache_len
    position_ids = jnp.broadcast_to(
        start + jnp.arange(s, dtype=jnp.int32)[None, :], (b, s))
    x = embed(cfg, params, tokens, position_ids)
    side = AttnSideInputs(position_ids=position_ids, deterministic=True,
                          cache_is_empty=empty_cache, valid=valid)
    cut = (logit_rows.astype(jnp.int32)
           if empty_cache and logit_rows is not None
           and cfg.row_cut_layer is not None else None)
    x, (rows_k, rows_v), new, counts = scan_stack(
        cfg, params["layers"], x, side,
        lambda _idx, k_l, v_l: (k_l, v_l, cache_len), rec,
        kv_xs=(k_cache, v_cache), lead=params.get("lead_layers"),
        cut_rows=cut)
    k_cache = cache_update(k_cache, rows_k, cache_len)
    v_cache = cache_update(v_cache, rows_v, cache_len)
    x = norm_apply(cfg.norm_type, x, params["final_norm"], cfg.norm_eps,
                   impl=cfg.norm_impl).astype(cfg.dtype)
    if logit_rows is not None and cut is None:
        x = jnp.take_along_axis(
            x, logit_rows.astype(jnp.int32)[:, None, None], axis=1)
    return (unembed(cfg, params, x), k_cache, v_cache,
            _counted(cfg, rec, new, counts))


def forward_paged_hybrid(cfg: ModelConfig, params: Params, tokens, k_pool,
                         v_pool, tables, fills, rec: dict, live):
    """``forward_cached_paged`` for a hybrid stack: one new token a slot,
    the full layers reading their keys and values out of the block pool
    (``[full layers, n_blocks, ...]``) by the composed routes there (the
    paged kernel where ``paged_decode_eligible``, else the gathered dense
    view), the recurrent mixers advancing slot ``i``'s row of ``rec`` where
    ``live[i]`` and leaving it where not (a free slot rides along with
    whatever its row holds).  → ``(logits [b, 1, vocab], new_k_pool,
    new_v_pool, new_rec)``."""
    fills = jnp.asarray(fills, jnp.int32)
    tables = jnp.asarray(tables, jnp.int32)
    bk = jax.tree.leaves(k_pool)[0].shape[3]
    bids = jnp.take_along_axis(tables, (fills // bk)[:, None], axis=1)[:, 0]
    offs = fills % bk
    valid = live[:, None]
    if paged_decode_eligible(cfg, k_pool, tokens.shape[1]):
        x = embed(cfg, params, tokens, fills[:, None])
        side = AttnSideInputs(position_ids=fills[:, None],
                              deterministic=True, valid=valid)
        x, (rows_k, rows_v), new, counts = scan_stack(
            cfg, params["layers"], x, side,
            lambda idx: PagedKV(k_pool, v_pool, tables, fills, idx), rec,
            lead=params.get("lead_layers"))
        x = norm_apply(cfg.norm_type, x, params["final_norm"],
                       cfg.norm_eps, impl=cfg.norm_impl).astype(cfg.dtype)
        logits, rec = unembed(cfg, params, x), _counted(cfg, rec, new, counts)
    else:
        k_dense = cache_gather_blocks(k_pool, tables)
        v_dense = cache_gather_blocks(v_pool, tables)
        logits, k_dense, v_dense, rec = forward_cached_hybrid(
            cfg, params, tokens, k_dense, v_dense, fills, rec, valid=valid)
        rows_k = cache_rows_at(k_dense, fills)
        rows_v = cache_rows_at(v_dense, fills)
    k_pool = cache_append_rows(k_pool, rows_k, bids, offs)
    v_pool = cache_append_rows(v_pool, rows_v, bids, offs)
    return logits, k_pool, v_pool, rec


def init_kv_pool(cfg: ModelConfig, n_blocks: int, block_size: int,
                 dtype=None):
    """Allocate an empty paged KV block pool ([L, n_blocks, kv_heads,
    block_size, d] ×2) — the same layout family as ``init_kv_cache`` with
    the batch axis reinterpreted as the block axis, so every cache-family
    helper (and the int8 ``{"q", "scale"}`` pytree form) applies verbatim.

    The paged serving engine (serving/block_pool.py) owns one pool and
    hands out blocks by integer id; block 0 is reserved as the trash
    block so fixed-arity gathers/scatters can point unused table entries
    somewhere harmless.

    On a pp>1 serving submesh the leading [L] axis is sharded over the
    pp stages (models/sharding.py:kv_pool_specs): each stage holds its
    own layers' slice of every block, while the ids and the host ledger
    stay global — the pool is layer-sharded, never id-partitioned."""
    return init_kv_cache(cfg, n_blocks, block_size, dtype)


@jax.named_scope("kv_cache")
def cache_gather_blocks(pool, tables):
    """Gather per-slot block tables into a dense working cache.

    ``pool`` leaves are [L, n_blocks, kv, bk(, d)]; ``tables`` is an
    [S, T] int32 block-id matrix (entries past a slot's fill point at the
    trash block).  Returns leaves [L, S, kv, T·bk(, d)] — the dense
    layout every existing attention/decode path consumes.  Rows gathered
    from trash or beyond-fill blocks hold finite garbage that the decode
    attention masks by *replacing* scores with NEG_INF, so the gathered
    view is bitwise-equivalent to a contiguously grown cache.
    """
    S, T = tables.shape
    flat = tables.reshape(-1)

    def g(a):
        L, _, kv, bk = a.shape[:4]
        tail = tuple(a.shape[4:])
        x = jnp.take(a, flat, axis=1)                # [L, S·T, kv, bk(,d)]
        x = x.reshape((L, S, T, kv, bk) + tail)
        x = jnp.moveaxis(x, 2, 3)                    # [L, S, kv, T, bk(,d)]
        return x.reshape((L, S, kv, T * bk) + tail)

    return jax.tree.map(g, pool)


@jax.named_scope("kv_cache")
def cache_scatter_blocks(pool, dense, bids):
    """Publish a batch-1 dense cache's blocks into pool blocks ``bids``.

    ``dense`` leaves are [L, 1, kv, T·bk(, d)] (an admission prefill
    cache); block i of the dense sequence axis lands in pool block
    ``bids[i]``.  Entries pointing at the trash block (id 0) are how the
    caller skips publishing a block (shared prefix blocks, padding past
    the prompt) while keeping ONE fixed-arity compiled scatter; duplicate
    trash writes are harmless because trash contents are never unmasked.
    """
    bids = jnp.asarray(bids, jnp.int32)

    def sc(p, d_):
        L, _, kv, W = d_.shape[:4]
        tail = tuple(d_.shape[4:])
        bk = p.shape[3]
        T = W // bk
        x = d_[:, 0].reshape((L, kv, T, bk) + tail)
        x = jnp.moveaxis(x, 2, 1)                    # [L, T, kv, bk(,d)]
        return p.at[:, bids].set(x.astype(p.dtype))

    return jax.tree.map(sc, pool, dense)


@jax.named_scope("kv_cache")
def cache_append_rows(pool, rows, bids, offs):
    """Write one new K/V row per slot into the pool, in place.

    ``rows`` leaves are [L, S, kv, 1(, d)] (the rows a decode step
    appended: the layer scan's ys, or extracted from the dense working
    view); slot s's row lands at offset ``offs[s]`` of
    pool block ``bids[s]``.  Inactive slots target (trash, 0) and
    overwrite each other there, in slot order.  The int8 {q, scale}
    pytree is written leaf-wise, so quantized rows move verbatim.

    One ``dynamic_update_slice`` of ``[L, 1, kv, 1(, d)]`` a slot, and
    not one scatter ``p.at[:, bids, :, offs].set``: XLA:TPU answers a row
    scatter across all layers with a re-layout of the whole (donated)
    pool into the order the scatter wants, and back — two pool-sized
    copies a leaf in every decode step (2.86 ms against 0.19 ms a pool at
    Falcon-7B's 32 x 513 blocks; PERF.md finding 25)."""
    bids = jnp.asarray(bids, jnp.int32)
    offs = jnp.asarray(offs, jnp.int32)
    zero = jnp.int32(0)

    def ap(p, r):
        r = r.astype(p.dtype)
        for s_ in range(r.shape[1]):
            start = (zero, bids[s_], zero, offs[s_]) + (zero,) * (p.ndim - 4)
            p = jax.lax.dynamic_update_slice(p, r[:, s_:s_ + 1], start)
        return p

    return jax.tree.map(ap, pool, rows)


@jax.named_scope("kv_cache")
def cache_move_rows(pool, src_bids, src_offs, dst_bids, dst_offs):
    """Copy pool rows ``(src_bids[i], src_offs[i])`` to
    ``(dst_bids[i], dst_offs[i])`` in one functional gather-then-scatter
    (every source row is read before any destination row is written, so
    overlapping src/dst — tree-verify compaction moving accepted node
    rows down to their depth positions — behaves as a simultaneous
    move).  No-op entries point both sides at the trash block; duplicate
    trash destinations collapse to one harmless write.  The int8
    {q, scale} pytree moves leaf-wise, so quantized rows relocate
    verbatim without a requantize round trip."""
    src_bids = jnp.asarray(src_bids, jnp.int32)
    src_offs = jnp.asarray(src_offs, jnp.int32)
    dst_bids = jnp.asarray(dst_bids, jnp.int32)
    dst_offs = jnp.asarray(dst_offs, jnp.int32)

    def mv(p):
        rows = p[:, src_bids, :, src_offs]       # [M, L, kv(, d)]
        return p.at[:, dst_bids, :, dst_offs].set(rows)

    return jax.tree.map(mv, pool)


@jax.named_scope("kv_cache")
def cache_rows_at(dense, fills):
    """Extract each slot's row at its own fill level from a dense cache
    ([L, S, kv, W(, d)] leaves → [L, S, kv, 1(, d)]) — the rows the
    decode step just appended, ready for ``cache_append_rows``."""
    fills = jnp.asarray(fills, jnp.int32)

    def f(a):
        idx = fills.reshape((1, -1) + (1,) * (a.ndim - 2))
        return jnp.take_along_axis(a, idx, axis=3)

    return jax.tree.map(f, dense)


def cache_rows_range(dense, fills, width: int):
    """Extract ``width`` consecutive rows starting at each slot's own fill
    level from a dense cache (leaves [L, S, kv, Wd(, d)]), flattened to
    the [L, S·width, kv, 1(, d)] row layout ``cache_append_rows``
    consumes — row ``s*width + j`` is slot ``s``'s window position ``j``.
    The ``width == 1`` case degenerates to ``cache_rows_at``; the verify
    path uses it to pull a whole speculative window's appended K/V out of
    the padded working view in one gather."""
    fills = jnp.asarray(fills, jnp.int32)

    def f(a):
        S, kv = a.shape[1], a.shape[2]
        tail = tuple(a.shape[4:])
        idx = fills[:, None] + jnp.arange(width, dtype=jnp.int32)[None, :]
        idx = idx.reshape((1, S, 1, width) + (1,) * (a.ndim - 4))
        rows = jnp.take_along_axis(a, idx, axis=3)   # [L, S, kv, W(,d)]
        rows = jnp.moveaxis(rows, 3, 2)              # [L, S, W, kv(,d)]
        return rows.reshape((a.shape[0], S * width, kv, 1) + tail)

    return jax.tree.map(f, dense)


def cache_slot_update(cache, slot_cache, slot):
    """Write a single-sequence cache (batch axis 1 of size 1) into batch
    slot ``slot`` of a larger cache of identical layout.

    The serving engine (megatron_llm_tpu/serving/) prefills each admitted
    request into its own ``[L, 1, kv_heads, max_len, d]`` cache, then
    splices it into the long-lived ``[L, slots, ...]`` batch cache here —
    the whole slot is replaced, so stale rows from the slot's previous
    occupant can never leak into attention.  Handles both the plain-array
    cache and the int8 ``{"q", "scale"}`` pytree (ops/kv_quant.py): every
    leaf carries the batch on axis 1.
    """
    slot = jnp.asarray(slot, jnp.int32)

    def upd(big, small):
        start = (jnp.int32(0), slot) + (jnp.int32(0),) * (big.ndim - 2)
        return jax.lax.dynamic_update_slice(
            big, small.astype(big.dtype), start)

    return jax.tree.map(upd, cache, slot_cache)


def cache_slot_read(cache, slot):
    """Extract batch slot ``slot`` as a batch-1 cache (inverse of
    ``cache_slot_update``; used by slot-allocator tests)."""
    slot = jnp.asarray(slot, jnp.int32)
    return jax.tree.map(
        lambda a: jax.lax.dynamic_slice_in_dim(a, slot, 1, axis=1), cache)


def cache_slot_copy(dst_cache, src_cache, dst_slot, dst_pos, src_slot,
                    src_pos, length: int):
    """Copy ``length`` sequence rows of K/V from one cache's batch slot
    into another's, at arbitrary (and possibly different) row offsets.

    The prefix cache (serving/prefix_cache.py) uses this to splice cached
    shared-prefix blocks into a fresh admission cache before the suffix
    prefill runs.  Every leaf carries the sequence on axis 3 of the
    ``[L, b, kv_heads, max_len(, d)]`` layout — true for the plain array
    cache AND both leaves of the int8 ``{"q", "scale"}`` pytree
    (ops/kv_quant.py), so quantized rows move verbatim: the {q, scale}
    pair is copied bit-identical, never dequantized.  ``length`` must be
    static (it fixes the slice shape); positions/slots may be traced.
    """
    dst_slot = jnp.asarray(dst_slot, jnp.int32)
    src_slot = jnp.asarray(src_slot, jnp.int32)
    dst_pos = jnp.asarray(dst_pos, jnp.int32)
    src_pos = jnp.asarray(src_pos, jnp.int32)

    def cp(dst, src):
        zeros = (jnp.int32(0),) * (src.ndim - 4)
        rows = jax.lax.dynamic_slice(
            src, (jnp.int32(0), src_slot, jnp.int32(0), src_pos) + zeros,
            (src.shape[0], 1, src.shape[2], length) + tuple(src.shape[4:]))
        return jax.lax.dynamic_update_slice(
            dst, rows.astype(dst.dtype),
            (jnp.int32(0), dst_slot, jnp.int32(0), dst_pos) + zeros)

    return jax.tree.map(cp, dst_cache, src_cache)


def flops_per_token(cfg: ModelConfig, seq_len: int) -> float:
    """Analytic FLOPs/token for MFU reporting (reference FLOP estimate:
    megatron/model/language_model.py:370-384)."""
    h = cfg.hidden_size
    L = cfg.num_layers
    d = cfg.head_dim
    nq = cfg.num_attention_heads
    nkv = cfg.kv_heads
    ffn = cfg.ffn_size
    n_mlp_mat = 3 if cfg.is_glu else 2
    # MoE: each token activates top_k experts' MLPs (+ the router matmul)
    mlp_mult = cfg.moe_top_k if cfg.num_experts > 0 else 1
    router = 2 * h * cfg.num_experts if cfg.num_experts > 0 else 0
    per_layer = (
        2 * h * (nq * d)  # wq
        + 2 * h * (nkv * d) * 2  # wk, wv
        + 2 * (nq * d) * h  # wo
        + 2 * 2 * nq * d * seq_len  # attention scores + context (causal ÷2 *2)
        + mlp_mult * n_mlp_mat * 2 * h * ffn  # mlp matmuls
        + router
    )
    head = 2 * h * cfg.padded_vocab_size()
    return float(L * per_layer + head)
