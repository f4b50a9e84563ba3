"""PartitionSpec trees: Megatron's Column/Row/Vocab parallel layout as specs.

The reference implements tensor parallelism as module classes that hand-code
collectives (ColumnParallelLinear / RowParallelLinear / VocabParallelEmbedding,
megatron/core/tensor_parallel/layers.py:128,410,566).  On TPU the same layout
is a ``PartitionSpec`` per parameter; GSPMD derives the identical comm
pattern (all-reduce after row-parallel matmuls, all-gather/reduce-scatter for
sequence parallelism) from the specs.  Mapping:

- ColumnParallelLinear weight [in, out]      → P(None, 'tp')
- RowParallelLinear weight [in, out]         → P('tp', None)
- VocabParallelEmbedding [vocab, hidden]     → P('tp', None)
- untied lm_head [hidden, vocab]             → P(None, 'tp')
- norms / biases of row-parallel outputs     → replicated

Layer parameters are stacked on a leading layer axis; that axis is sharded
over 'pp' when pipeline parallelism is active (each stage owns a contiguous
slab of layers — the spec equivalent of the reference's layer-offset logic in
megatron/model/transformer.py:1015-1060).
"""

from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..config import ModelConfig, ParallelConfig

Params = dict  # same alias as models.transformer (kept import-free so the
               # transformer can import this module's helpers)

TP = "tp"
PP = "pp"
DP = "dp"
CP = "cp"
EP = "ep"
FSDP = "fsdp"  # serving weight-residency axis (parallel/mesh.py:FSDP_AXIS)
SP = "sp"      # named-but-size-1 sequence axis (parallel/mesh.py:SEQ_AXIS)


def kv_shard_axes(cfg: ModelConfig, tp_size: int, tp_axes=TP):
    """Mesh axes for K/V projections: shard over tp only if the kv heads
    divide evenly — MQA (Falcon-7B kv=1) keeps K/V replicated on every tp
    shard, which is what the reference does implicitly by tiling
    (transformer.py:449-456)."""
    return tp_axes if cfg.kv_heads % max(tp_size, 1) == 0 else None


def norm_specs(cfg: ModelConfig, layer_axis: Optional[str] = None) -> Params:
    """Spec subtree for one norm ({scale[, bias]}), optionally layer-stacked."""
    s = {"scale": P(layer_axis, None) if layer_axis else P(None)}
    if cfg.norm_type == "layernorm":
        s["bias"] = P(layer_axis, None) if layer_axis else P(None)
    return s


def _layer_specs(cfg: ModelConfig, layer_axis: Optional[str],
                 tp_size: int, tp_axes=TP, fsdp_axes=None) -> Params:
    """Specs for one (stacked) layer pytree; leading dim = layer axis.

    ``tp_axes`` is the mesh axis (or axis tuple) carrying the tensor
    sharding — 'tp' everywhere now that the serving re-layout shards
    layers over 'pp' instead of joining pp into tp.  ``fsdp_axes``
    (serving re-layout with ParallelConfig.fsdp > 1) additionally splits
    each weight's NON-tp dimension — the ("dp","fsdp","sp")-family
    partition rules: q/k/v ('fsdp' on the input dim, tp on heads),
    o_proj/down_proj ('fsdp' on the output dim) — so resident bytes fall
    1/(tp·fsdp) per device while the matmul sharding GSPMD derives stays
    the familiar column/row-parallel pattern plus a gather."""
    L = layer_axis  # None (scan only) or 'pp'
    TP = tp_axes  # noqa: N806 — shadows the module constant on purpose
    F = fsdp_axes  # None (no residency split) or 'fsdp'
    kv_tp = kv_shard_axes(cfg, tp_size, tp_axes)
    attn = {
        "wq": P(L, F, TP),
        "wk": P(L, F, kv_tp),
        "wv": P(L, F, kv_tp),
        "wo": P(L, TP, F),
    }
    if cfg.use_bias or cfg.qkv_bias:
        attn["bq"] = P(L, TP)
        attn["bk"] = P(L, kv_tp)
        attn["bv"] = P(L, kv_tp)
    if cfg.use_bias:
        attn["bo"] = P(L, None)

    if cfg.num_experts > 0:
        # Expert-stacked weights [E, h, f]: experts over 'ep', ffn over 'tp';
        # GSPMD inserts the token all-to-alls from the dispatch einsums
        # (models/moe.py).  Router stays replicated (tiny, fp32).
        mlp = {"router": P(L, None, None)}
        if cfg.is_glu:
            mlp["w_gate"] = P(L, EP, F, TP)
        mlp["w_up"] = P(L, EP, F, TP)
        mlp["w_down"] = P(L, EP, TP, F)
    else:
        mlp = {}
        if cfg.is_glu:
            mlp["w_gate"] = P(L, F, TP)
        mlp["w_up"] = P(L, F, TP)
        mlp["w_down"] = P(L, TP, F)
        if cfg.use_bias:
            if cfg.is_glu:
                mlp["b_gate"] = P(L, TP)
            mlp["b_up"] = P(L, TP)
            mlp["b_down"] = P(L, None)

    def norm_spec():
        s = {"scale": P(L, None)}
        if cfg.norm_type == "layernorm":
            s["bias"] = P(L, None)
        return s

    layer = {"input_norm": norm_spec(), "attn": attn, "mlp": mlp}
    if cfg.parallel_attn:
        if cfg.parallel_layernorm:
            layer["mlp_norm"] = norm_spec()
    else:
        layer["post_attn_norm"] = norm_spec()
    return layer


def param_specs(cfg: ModelConfig, parallel: ParallelConfig) -> Params:
    """PartitionSpec pytree matching ``models.model.init_params`` output."""
    layer_axis = PP if parallel.pipeline_parallel > 1 else None
    specs: Params = {
        "embedding": {"word": P(TP, None)},
        "layers": _layer_specs(cfg, layer_axis, parallel.tensor_parallel),
        "final_norm": {"scale": P(None)},
    }
    if cfg.norm_type == "layernorm":
        specs["final_norm"]["bias"] = P(None)
    if cfg.position_embedding_type == "absolute":
        specs["embedding"]["position"] = P(None, None)
    if cfg.tokentype_size:
        specs["embedding"]["tokentype"] = P(None, None)
    if not cfg.tie_embed_logits:
        specs["lm_head"] = P(None, TP)
    return specs


def serving_param_specs(cfg: ModelConfig,
                        parallel: ParallelConfig) -> Params:
    """Inference re-layout: 'pp' shards LAYERS, 'fsdp' shards residency.

    Earlier revisions folded pp into wider head sharding (tp_eff = pp·tp)
    on the argument that a layer-sharded scan moves weights per token
    step.  That fold capped the layout at head divisibility (a model
    whose heads don't divide pp·tp refused to shard at all) and kept
    per-device *param and KV-pool bytes* flat in pp — the opposite of
    what a 70B-on-a-pod geometry needs.  This layout reverses the
    decision:

    - **pp** places each pipeline stage's contiguous layer slab (the
      stacked layer axis of params AND of the paged KV pool,
      kv_pool_specs) on its own mesh slice, so residency scales with
      pipeline depth.  The engine fills the per-stage bubbles by
      splitting the slot batch into pp microbatches and keeping pp
      group dispatches in flight (serving/engine.py:_dispatch_decode);
      GSPMD inserts the stage-boundary movement the reference hand-codes
      as p2p in its ForwardStep
      (megatron/text_generation/forward_step.py:44-213).
    - **tp** stays the only head-sharding axis (serving_head_axes), so
      head divisibility constrains tp alone: heads % tp, layers % pp —
      independent, per-axis constraints.
    - **fsdp** (ParallelConfig.fsdp) splits each weight's non-tp dim and
      the word embedding's vocab dim along ('tp', 'fsdp') — the
      EasyDel/fjformer ("dp","fsdp","sp") partition-rule family — so a
      deployment can halve resident bytes again without touching head
      or layer divisibility.

    At pp == fsdp == 1 this is exactly the training ``param_specs``
    layout, and the single-mesh engine's executable is untouched.
    """
    pp = parallel.pipeline_parallel
    fsdp = getattr(parallel, "fsdp", 1)
    if pp == 1 and fsdp == 1:
        return param_specs(cfg, parallel)
    layer_axis = PP if pp > 1 else None
    f = FSDP if fsdp > 1 else None
    embed_axes = (TP, FSDP) if fsdp > 1 else TP
    specs: Params = {
        "embedding": {"word": P(embed_axes, None)},
        "layers": _layer_specs(cfg, layer_axis, parallel.tensor_parallel,
                               fsdp_axes=f),
        "final_norm": {"scale": P(None)},
    }
    if cfg.norm_type == "layernorm":
        specs["final_norm"]["bias"] = P(None)
    if cfg.position_embedding_type == "absolute":
        specs["embedding"]["position"] = P(None, None)
    if cfg.tokentype_size:
        specs["embedding"]["tokentype"] = P(None, None)
    if not cfg.tie_embed_logits:
        specs["lm_head"] = P(f, TP)
    return specs


def assert_serving_geometry(cfg: ModelConfig, parallel: ParallelConfig,
                            what: str = "model") -> None:
    """Per-axis divisibility guards for the serving re-layout.

    pp no longer folds into tp, so the old single "heads % pp·tp" guard
    splits into independent per-axis constraints with per-axis messages:
    heads divide tp, layers divide pp, hidden/vocab divide the fsdp
    residency split."""
    tp = parallel.tensor_parallel
    pp = parallel.pipeline_parallel
    fsdp = getattr(parallel, "fsdp", 1)
    assert cfg.num_attention_heads % max(tp, 1) == 0, (
        f"serving re-layout shards {what} attention heads over tp = {tp}, "
        f"which must divide num_attention_heads = "
        f"{cfg.num_attention_heads} (pp shards layers now, not heads — "
        f"pick tp that divides the head count and put the rest of the "
        f"submesh on pp/fsdp)")
    if pp > 1:
        assert cfg.num_layers % pp == 0, (
            f"serving re-layout shards the {what} layer stack over pp = "
            f"{pp}, which must divide num_layers = {cfg.num_layers} "
            f"(each pipeline stage owns a contiguous slab of layers)")
    if fsdp > 1:
        assert cfg.hidden_size % fsdp == 0, (
            f"fsdp = {fsdp} splits each {what} weight's non-tp dim and "
            f"must divide hidden_size = {cfg.hidden_size}")
        assert cfg.padded_vocab_size(tp) % (tp * fsdp) == 0, (
            f"fsdp = {fsdp} splits the {what} word embedding along "
            f"('tp', 'fsdp') and tp·fsdp = {tp * fsdp} must divide the "
            f"padded vocab {cfg.padded_vocab_size(tp)}")


def shard_for_serving(params: Params, cfg: ModelConfig,
                      parallel: ParallelConfig) -> tuple[Params, Mesh]:
    """One-call serving setup: build the mesh, re-layout ``params`` with
    :func:`serving_param_specs`, return (sharded_params, mesh): the
    generation server CLI's layout logic, in one place."""
    from ..parallel import mesh as mesh_lib

    assert_serving_geometry(cfg, parallel)
    mesh = mesh_lib.build_mesh(parallel)
    specs = serving_param_specs(cfg, parallel)
    # quantized trees have {"q", "scale"} subtrees where the spec tree
    # has one weight leaf; mirror the structure params-aware so int8,
    # int4 group-wise, and the int8 embedding each get co-sharded scale
    # specs (quantize_specs docstring).
    from ..ops import quant

    if any(quant.is_quantized(w)
           for w in jax.tree.leaves(params,
                                    is_leaf=quant.is_quantized)
           if isinstance(w, dict)):
        specs = quant.quantize_specs(specs, params)
    return shard_params(params, specs, mesh), mesh


def serving_head_axes(cfg: ModelConfig, mesh: Mesh):
    """Mesh axes carrying the kv-head sharding under the serving
    re-layout, or None when the pool's head dim must stay replicated.

    tp is the ONLY head-sharding axis now — pp shards the layer axis
    (``serving_param_specs`` / ``kv_pool_specs``) and fsdp never touches
    the pool (block ids must stay global integers).  MQA/GQA pools whose
    kv-head count does not divide tp replicate their head dim — the same
    rule as ``kv_shard_axes`` for the K/V projections, derived from the
    mesh instead of a ParallelConfig so the serving engine can resolve it
    from the mesh it was handed."""
    if (TP in mesh.axis_names and mesh.shape[TP] > 1
            and cfg.kv_heads % mesh.shape[TP] == 0):
        return (TP,)
    return None


def kv_pool_specs(cfg: ModelConfig, mesh: Mesh) -> tuple:
    """(k_spec, v_spec) PartitionSpec pytrees for the paged KV block pool
    ``[L, n_blocks, kv_heads, block, d]`` (models/model.py:init_kv_pool).

    The LAYER axis shards over 'pp' (each pipeline stage holds its own
    layer slab of the pool — KV residency scales with pipeline depth,
    matching the layer-sharded params) and heads shard over 'tp'.  The
    block/row/depth dims stay unsharded so block ids remain global
    integers: every stage's shard holds the same block-id space for its
    layer slice, the host-side ledger stays ONE ledger, and the
    allocator / prefix cache / COW / tiered machinery stays
    topology-blind — the slot block tables are replicated host int32 and
    move verbatim.  A pool whose layer count doesn't divide pp (e.g. a
    resident draft model's shallow stack) keeps its layer axis
    replicated.  For an int8 pool, the ``{"q", "scale"}`` leaves shard
    on the same axes (scale is ``[L, n_blocks, kv_heads, block]``)."""
    ax = serving_head_axes(cfg, mesh)
    pp = mesh.shape[PP] if PP in mesh.axis_names else 1
    L = PP if (pp > 1 and cfg.num_layers % pp == 0) else None
    if cfg.kv_cache_quant == "int8":
        spec = {"q": P(L, None, ax, None, None),
                "scale": P(L, None, ax, None)}
    else:
        spec = P(L, None, ax, None, None)
    return spec, spec


def init_sharded_kv_pool(cfg: ModelConfig, n_blocks: int, block_size: int,
                         mesh: Mesh):
    """An empty block pool (models/model.py:init_kv_pool) allocated
    directly under :func:`kv_pool_specs` on the serving mesh.  Created
    on the default device and moved afterwards, every replica's whole
    pool would pass through device 0 — which then transiently holds all
    of them."""
    from . import model as model_lib

    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s),
                             kv_pool_specs(cfg, mesh),
                             is_leaf=lambda x: isinstance(x, P))
    # tpulint: allow[recompile] one zero-fill per engine start, never hot
    return jax.jit(lambda: model_lib.init_kv_pool(cfg, n_blocks, block_size),
                   out_shardings=shardings)()


def shard_params(params: Params, specs: Params, mesh: Mesh) -> Params:
    """Place a param pytree onto the mesh according to the spec tree."""
    return jax.tree.map(
        lambda p, s: jax.device_put(p, NamedSharding(mesh, s)), params, specs
    )


def activation_spec(parallel: ParallelConfig) -> P:
    """[batch, seq, hidden] activation layout: batch over dp, seq over cp."""
    return P(DP, CP, None)


def sequence_parallel_spec(parallel: ParallelConfig) -> P:
    """Megatron sequence parallelism: in norm/dropout regions activations are
    sharded 1/tp along the sequence dim (reference:
    core/tensor_parallel/layers.py:225-296).  Expressed as a constraint the
    model applies around norms when ``parallel.sequence_parallel``."""
    if parallel.sequence_parallel and parallel.tensor_parallel > 1:
        return P(DP, (CP, TP), None)
    return activation_spec(parallel)


def logits_spec(parallel: ParallelConfig) -> P:
    return P(DP, CP, TP)


def constrain(x, spec: P):
    """``with_sharding_constraint`` that is a no-op outside jit/mesh."""
    try:
        return jax.lax.with_sharding_constraint(x, spec)
    except Exception:
        return x
