"""Sharded serving engines over tp×pp(×fsdp) submeshes.

One engine instance = one submesh.  The existing partition rules do all
the layout work: params re-shard with ``serving_param_specs`` (heads
over tp, the stacked LAYER axis over pp — true pipeline stages — and
weight residency split 1/fsdp along the non-tp dim; int8
``{"q", "scale"}`` subtrees via ``quantize_specs``), the paged block
pool shards its kv-head axis over tp and its layer axis over pp
(``kv_pool_specs`` — each stage holds its own layers' slice of every
block), and the slot block tables stay replicated host int32 — block
ids are global on every shard and every stage, so the engine's entire
ledger (free list, refs, reservations, prefix trie) is untouched.

On a pp>1 submesh the engine additionally microbatch-interleaves its
decode steps (engine.py:_dispatch_decode): the slot batch splits into
pp groups whose dispatches chain through the KV pool, filling the
pipeline bubble while keeping tokens bitwise equal to the single-mesh
path.

A resident draft model (tree speculation, docs/serving.md) rides the
same machinery: its params re-shard with ``serving_param_specs`` of the
*draft* config onto the same submesh, so sharded and disaggregated
decode replicas speculate exactly like the single-chip engine.  Draft
KV never ships — each decode replica rebuilds it with one cheap dense
prefill on install.

At tp=pp=fsdp=1 this builds the plain single-chip engine — same
executable, bitwise-identical tokens — so the cluster path has no
single-chip tax.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax

from ...config import ModelConfig, ParallelConfig
from ..adapters.registry import AdapterRegistry
from ..engine import EngineConfig, ServingEngine
from ..metrics import ServingMetrics


def _shard_for_serving(cfg: ModelConfig, params, parallel, mesh):
    """Re-lay a param tree (target or draft) onto a serving submesh,
    routing any int8/int4 ``{"q", "scale"}`` subtrees through
    ``quantize_specs`` so quantized residency survives the reshard."""
    from ...models import sharding as shard_lib
    from ...ops import quant

    specs = shard_lib.serving_param_specs(cfg, parallel)
    if any(quant.is_quantized(w)
           for w in jax.tree.leaves(params, is_leaf=quant.is_quantized)
           if isinstance(w, dict)):
        specs = quant.quantize_specs(specs, params)
    return shard_lib.shard_params(params, specs, mesh)


def build_sharded_engine(cfg: ModelConfig, params,
                         engine_config: Optional[EngineConfig] = None,
                         parallel: Optional[ParallelConfig] = None,
                         devices: Optional[Sequence[jax.Device]] = None,
                         metrics: Optional[ServingMetrics] = None,
                         draft_cfg: Optional[ModelConfig] = None,
                         draft_params=None,
                         adapters: Optional[AdapterRegistry] = None,
                         ) -> ServingEngine:
    """One engine over one submesh.

    ``devices`` is the submesh's device slice (defaults to the first
    pp·tp·fsdp of ``jax.devices()``); ``params`` are re-laid-out onto
    it with the serving re-layout, and ``draft_params`` (resident draft
    model, if any) follow with their own config's specs.  With
    pp·tp·fsdp == 1 and no explicit devices this returns the ordinary
    single-chip engine (mesh=None).

    ``adapters`` (multi-tenant LoRA registry) is handed to the engine
    as-is; the arenas are tiny (rank · hidden per slot per target) and
    jit re-lays them onto the submesh at first use, so no explicit
    reshard pass is needed.
    """
    from ...parallel import mesh as mesh_lib

    parallel = parallel or ParallelConfig()
    # Rebuild recipe for the cluster supervisor: everything needed to
    # re-run this builder on the ORIGINAL submesh after a crash.  Holds
    # the host param tree by reference (it is alive in the caller
    # anyway); ``adapters`` is overridden by the cluster builders to the
    # shared source registry so a rebuilt replica re-clones the *live*
    # adapter store, including adapters registered after build.
    spec = dict(cfg=cfg, params=params, engine_config=engine_config,
                parallel=parallel, devices=devices, draft_cfg=draft_cfg,
                draft_params=draft_params, adapters=adapters)
    from ...models import sharding as shard_lib

    n_sub = (parallel.pipeline_parallel * parallel.tensor_parallel
             * getattr(parallel, "fsdp", 1))
    if n_sub == 1 and devices is None:
        eng = ServingEngine(cfg, params, engine_config, metrics=metrics,
                            draft_cfg=draft_cfg,
                            draft_params=draft_params, adapters=adapters)
        eng.rebuild_spec = spec
        return eng
    # Per-axis geometry guards (heads divide tp, layers divide pp, vocab
    # and hidden divide fsdp) — each failure names its own axis, never a
    # fused pp·tp product, because pp shards LAYERS in this layout.
    shard_lib.assert_serving_geometry(cfg, parallel)
    if draft_cfg is not None:
        shard_lib.assert_serving_geometry(draft_cfg, parallel,
                                          what="draft model")
    mesh = mesh_lib.build_mesh(parallel, devices=devices)
    sharded = _shard_for_serving(cfg, params, parallel, mesh)
    sharded_draft = (None if draft_params is None else
                     _shard_for_serving(draft_cfg, draft_params, parallel,
                                        mesh))
    eng = ServingEngine(cfg, sharded, engine_config, metrics=metrics,
                        mesh=mesh, draft_cfg=draft_cfg,
                        draft_params=sharded_draft, adapters=adapters)
    eng.rebuild_spec = spec
    return eng


def build_cluster(cfg: ModelConfig, params,
                  engine_config: Optional[EngineConfig] = None,
                  *, replicas: int = 1,
                  parallel: Optional[ParallelConfig] = None,
                  router_config=None,
                  devices: Optional[Sequence[jax.Device]] = None,
                  draft_cfg: Optional[ModelConfig] = None,
                  draft_params=None,
                  adapters: Optional[AdapterRegistry] = None):
    """N sharded engine replicas on disjoint device slices behind one
    :class:`~..cluster.router.Router`.

    Replica metrics are constructed with ``register=False`` so they
    don't fight over the process-wide ``"serving"`` collector; the
    router registers one ``"cluster"`` collector aggregating them.

    An ``adapters`` registry is ``clone()``d per replica — arena slots
    and pin counts are scheduler-thread state and must stay replica-
    local, while the host-side adapter store is shared by reference.
    Adapters registered *after* the cluster is built go through
    ``Router.register_adapter`` so every replica sees them.
    """
    from ...parallel import mesh as mesh_lib
    from .router import Router, RouterConfig

    parallel = parallel or ParallelConfig()
    engine_config = engine_config or EngineConfig()
    n_sub = (parallel.pipeline_parallel * parallel.tensor_parallel
             * getattr(parallel, "fsdp", 1))
    if devices is None:
        devices = jax.devices()
    engines = []
    if replicas == 1 and n_sub == 1:
        eng = ServingEngine(
            cfg, params, engine_config,
            metrics=ServingMetrics(engine_config.max_batch_size,
                                   register=False),
            draft_cfg=draft_cfg, draft_params=draft_params,
            adapters=adapters)
        eng.rebuild_spec = dict(
            cfg=cfg, params=params, engine_config=engine_config,
            parallel=parallel, devices=None, draft_cfg=draft_cfg,
            draft_params=draft_params, adapters=adapters)
        engines.append(eng)
    else:
        meshes = mesh_lib.replica_submeshes(parallel, replicas,
                                            devices=devices)
        for mesh in meshes:
            engines.append(build_sharded_engine(
                cfg, params, engine_config, parallel,
                devices=mesh.devices.flatten().tolist(),
                metrics=ServingMetrics(engine_config.max_batch_size,
                                       register=False),
                draft_cfg=draft_cfg, draft_params=draft_params,
                adapters=None if adapters is None else adapters.clone()))
        for eng in engines:
            # rebuilds re-clone from the SHARED store, not the dead
            # incarnation's clone (see build_sharded_engine)
            eng.rebuild_spec["adapters"] = adapters
    return Router(engines, router_config or RouterConfig())


def build_disagg_cluster(cfg: ModelConfig, params,
                         engine_config: Optional[EngineConfig] = None,
                         *, prefill_replicas: int = 1,
                         decode_replicas: int = 1,
                         parallel: Optional[ParallelConfig] = None,
                         prefill_parallel: Optional[ParallelConfig] = None,
                         decode_parallel: Optional[ParallelConfig] = None,
                         router_config=None,
                         devices: Optional[Sequence[jax.Device]] = None,
                         draft_cfg: Optional[ModelConfig] = None,
                         draft_params=None,
                         adapters: Optional[AdapterRegistry] = None):
    """Disaggregated prefill/decode cluster: ``prefill_replicas``
    prefill-specialized engines + ``decode_replicas`` decode engines on
    disjoint device slices behind one phase-routing Router
    (docs/serving.md, "Disaggregated prefill/decode").

    The prefill replicas run with ``role="prefill"`` — the router routes
    every new request to them, and after the prefill (+ first token)
    they ship the request's KV blocks to a decode replica via
    ``BlockPool.export_blocks`` / ``import_blocks``.  When the model
    runs the flash-attention path, prefill replicas additionally get a
    prefill-tuned grid (``kernels.flash_attention.prefill_block_sizes``)
    — wider q tiles for the compute-bound long-sequence regime.  The
    grid only shapes the attention *schedule*, never its math, but it is
    applied strictly per-role so the dot-product fallback configs stay
    byte-identical across roles.

    A resident draft model is handed to every replica, but only decode
    (and mixed) roles ever run it: prefill-role engines skip the draft
    prefill entirely and the adopting decode replica rebuilds the draft
    KV from the shipped request's tokens — a shipment carries no draft
    state.

    An ``adapters`` registry is cloned per replica (see
    ``build_cluster``); a shipment carries only the request's
    ``adapter_id``, and the adopting decode replica re-pins the adapter
    out of its own clone at install.

    ``prefill_parallel`` / ``decode_parallel`` give the two roles
    independent submesh geometries (both default to ``parallel``): the
    canonical split keeps prefill replicas on wide tp (prefill is
    compute-bound and head-parallel) and decode replicas on deep pp +
    fsdp (decode is residency-bound; layer sharding scales weight AND
    KV bytes per device).  KV shipments re-shard in flight — the import
    path's ``device_put`` into the destination pool's sharding splits
    each shipped block's layer/head axes to the decode geometry, so no
    extra transfer code is needed.
    """
    import dataclasses as _dc

    from ...parallel import mesh as mesh_lib
    from .router import Router, RouterConfig

    assert prefill_replicas >= 1 and decode_replicas >= 1, (
        "a disaggregated cluster needs at least one prefill and one "
        "decode replica (use build_cluster for colocated serving)")
    parallel = parallel or ParallelConfig()
    prefill_parallel = prefill_parallel or parallel
    decode_parallel = decode_parallel or parallel
    engine_config = engine_config or EngineConfig()
    if devices is None:
        devices = jax.devices()
    # disjoint contiguous device slices per role, prefill first (the
    # roles may have different per-replica sizes, so the uniform
    # replica_submeshes partition runs once per role)
    n_prefill_devs = prefill_replicas * prefill_parallel.world_size
    meshes = (mesh_lib.replica_submeshes(
                  prefill_parallel, prefill_replicas,
                  devices=devices[:n_prefill_devs])
              + mesh_lib.replica_submeshes(
                  decode_parallel, decode_replicas,
                  devices=devices[n_prefill_devs:]))
    prefill_cfg = cfg
    if cfg.attention_impl == "flash":
        from ...kernels.flash_attention import prefill_block_sizes

        bq, bk = prefill_block_sizes(cfg)
        prefill_cfg = _dc.replace(cfg, flash_block_q=bq, flash_block_k=bk)
    engines = []
    for i, mesh in enumerate(meshes):
        is_prefill = i < prefill_replicas
        ec = _dc.replace(engine_config,
                         role="prefill" if is_prefill else "decode")
        engines.append(build_sharded_engine(
            prefill_cfg if is_prefill else cfg, params, ec,
            prefill_parallel if is_prefill else decode_parallel,
            devices=mesh.devices.flatten().tolist(),
            metrics=ServingMetrics(ec.max_batch_size, register=False),
            draft_cfg=draft_cfg, draft_params=draft_params,
            adapters=None if adapters is None else adapters.clone()))
    for eng in engines:
        eng.rebuild_spec["adapters"] = adapters
    return Router(engines, router_config or RouterConfig())
