"""Launch the REST text-generation server on a checkpoint.

Parity: tools/run_text_generation_server.py in the reference.  Usage::

    python -m megatron_llm_tpu.tools.run_text_generation_server \
        --load /path/to/ckpt --model llama2 --size 7b \
        --tokenizer_type SentencePieceTokenizer \
        --tokenizer_model /path/tokenizer.model --port 5000
"""

from __future__ import annotations

import argparse


def _start_metrics_logger(service, interval_s: float):
    """Daemon thread printing a one-line JSON serving summary every
    ``interval_s`` — the operational counters (queue/slots/tokens) plus
    the prefix-cache hit rate, without scraping GET /metrics."""
    import json
    import threading
    import time

    def loop():
        while True:
            time.sleep(interval_s)
            snap = service.metrics_snapshot()
            if "router" in snap:
                # cluster mode: the router-shaped snapshot (GET /cluster
                # has the full per-replica view)
                print(json.dumps({"cluster_metrics": snap["router"]}),
                      flush=True)
                continue
            print(json.dumps({"serving_metrics": {
                "completed": snap["completed"],
                "running": snap["running"],
                "queued": snap["queued"],
                "decode_tokens": snap["decode_tokens"],
                "ttft_p50_s": round(snap["ttft"]["p50_s"], 4),
                "prefix_hits": snap["prefix_hits"],
                "prefix_misses": snap["prefix_misses"],
                "prefix_hit_rate": round(snap["prefix_hit_rate"], 4),
                "prefix_blocks": snap["prefix_blocks"],
                "prefix_promotions": snap.get(
                    "prefix_promotions_total", 0),
                "spec_proposed": snap["spec_proposed"],
                "spec_accepted": snap["spec_accepted"],
                "spec_acceptance_rate": round(
                    snap["spec_acceptance_rate"], 4),
                "accepted_tokens_per_step_mean": round(
                    snap["accepted_tokens_per_step"]["mean"], 3),
                # tiered KV (all zero when --host_kv_blocks is unset)
                "swap_out_blocks": snap.get("swap_out_blocks_total", 0),
                "swap_in_blocks": snap.get("swap_in_blocks_total", 0),
                "swap_bytes": snap.get("swap_bytes_total", 0),
                "preemptions": snap.get("preemptions_total", 0),
                "host_blocks_used": snap.get("host_blocks_used", 0),
                "host_blocks_free": snap.get("host_blocks_free", 0),
            }}), flush=True)

    t = threading.Thread(target=loop, name="serving-metrics-log",
                         daemon=True)
    t.start()
    return t


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--load", required=True, help="checkpoint directory")
    ap.add_argument("--model", default="llama2",
                    choices=["llama", "llama2", "codellama", "falcon", "gpt",
                             "laguna", "phi4flash"],
                    help="phi4flash (--size mini-flash-reasoning) and "
                         "laguna (--size xs.2-pp8-stage0) are hybrid "
                         "stacks: start them with "
                         "--prefix_cache_blocks 0 (docs/serving.md)")
    ap.add_argument("--size", default="7b")
    ap.add_argument("--tokenizer_type", default="SentencePieceTokenizer")
    ap.add_argument("--tokenizer_model", default=None)
    ap.add_argument("--host", default="0.0.0.0")
    ap.add_argument("--port", type=int, default=5000)
    ap.add_argument("--max_batch_size", type=int, default=8,
                    help="KV slots = max CONCURRENT decodes in the "
                         "continuous-batching engine (docs/serving.md); "
                         "prompts beyond this queue, they are not rejected")
    ap.add_argument("--max_tokens_to_generate", type=int, default=1024)
    ap.add_argument("--queue_size", type=int, default=32,
                    help="bounded admission queue depth; beyond it requests "
                         "get 503 + Retry-After instead of unbounded latency")
    ap.add_argument("--max_seq_len", type=int, default=None,
                    help="per-slot cache width (prompt + generation); "
                         "default: the model's max_position_embeddings")
    ap.add_argument("--prefill_bucket", type=int, default=64,
                    help="pad prompt lengths up to a multiple of this "
                         "before the admission prefill so the number of "
                         "compiled prefill shapes stays bounded under real "
                         "traffic (1 = exact lengths = one executable per "
                         "distinct prompt length, a compile-storm)")
    ap.add_argument("--prefill_chunk", type=int, default=None,
                    help="chunked prefill admission: prefill at most this "
                         "many prompt tokens per scheduler iteration, "
                         "interleaved with decode steps, so a long prompt "
                         "doesn't freeze active streams (docs/serving.md); "
                         "supersedes --prefill_bucket; default: off")
    ap.add_argument("--no_pipeline_decode", action="store_true",
                    help="disable the one-step pipelined decode loop "
                         "(diagnostic; docs/serving.md fast path)")
    ap.add_argument("--prefix_cache_blocks", type=int, default=256,
                    help="automatic prefix caching HBM budget, in blocks "
                         "of --prefill_chunk (or --prefill_bucket) tokens "
                         "each: requests sharing a block-aligned prompt "
                         "prefix (system prompts, few-shot templates) "
                         "reuse cached K/V instead of re-prefilling "
                         "(docs/serving.md, 'Prefix caching'); sampled "
                         "tokens are bitwise unaffected")
    ap.add_argument("--no_prefix_cache", action="store_true",
                    help="disable automatic prefix caching (diagnostic)")
    ap.add_argument("--kv_block_size", type=int, default=None,
                    help="paged KV cache block size in tokens "
                         "(serving/block_pool.py): slots hold per-block "
                         "tables into a shared pool instead of a fixed "
                         "max_seq_len stride, so mixed-length traffic "
                         "packs more concurrent requests into the same "
                         "HBM (docs/serving.md, 'Paged KV cache'); "
                         "default: engine default (prefill chunk/bucket "
                         "rounded to the kernel lane width)")
    ap.add_argument("--kv_pool_blocks", type=int, default=None,
                    help="paged KV pool size in blocks of --kv_block_size "
                         "tokens (plus the reserved trash block); sets "
                         "the total KV HBM budget independently of "
                         "--max_batch_size; default: engine default "
                         "(max_batch_size full-length sequences)")
    ap.add_argument("--host_kv_blocks", type=int, default=0,
                    help="tiered KV: host-RAM arena size in blocks of "
                         "--kv_block_size tokens (docs/serving.md, "
                         "'Tiered KV').  Enables prefix-cache spill to "
                         "host, priority-based decode preemption, and "
                         "oversubscribed admission against the host "
                         "tier instead of queue-head parking; size it "
                         "so steady demote traffic stays under the "
                         "host<->device copy bandwidth.  0 = off")
    ap.add_argument("--default_priority", type=int, default=0,
                    help="QoS class for requests whose JSON body has no "
                         "'priority' field (higher = admitted sooner; "
                         "with --host_kv_blocks a higher class may "
                         "preempt lower-class decodes to the host tier)")
    ap.add_argument("--metrics_interval_s", type=float, default=60.0,
                    help="periodically print a one-line JSON serving-"
                         "metrics summary (prefix-cache hit rate "
                         "included) to stdout; 0 disables")
    ap.add_argument("--profile_dir", default=None,
                    help="directory POST /profile writes device profiles "
                         "under (obs/profile.py); unset refuses the route")
    ap.add_argument("--no_trace", action="store_true",
                    help="disable per-request span tracing (obs/trace.py, "
                         "GET /trace).  Tracing is on by default; this "
                         "is the escape hatch if a deployment wants the "
                         "last few microseconds back")
    ap.add_argument("--log_json", action="store_true",
                    help="emit the structured JSON event log "
                         "(obs/logging.py: request lifecycle lines with "
                         "request_id correlation ids) to stderr")
    ap.add_argument("--retry_after_s", type=float, default=1.0,
                    help="Retry-After hint returned with 503 backpressure")
    ap.add_argument("--request_deadline_s", type=float, default=None,
                    help="per-request wall-clock budget: requests still "
                         "queued or decoding past this finish with reason "
                         "'timeout' instead of holding a KV slot forever "
                         "(docs/serving.md, robustness); default: none")
    ap.add_argument("--drain_timeout_s", type=float, default=30.0,
                    help="on SIGTERM, how long to let in-flight requests "
                         "finish before the listener stops")
    ap.add_argument("--weight_quant", default=None,
                    choices=["int8", "int4", "mixed"],
                    help="weight-only quantization applied after load "
                         "(ops/quant.py precision policies: int8 halves "
                         "decode HBM traffic; int4 = group-wise int4 "
                         "projections + int8 embedding, quarters it; "
                         "mixed = int8 attention / int4 MLP / int8 "
                         "embedding). All three dequantize into the "
                         "matmul (ops/quant.py:mm); compose with "
                         "--kv_quant int8 for full low-bit residency")
    ap.add_argument("--quant_group_size", type=int, default=None,
                    help="int4 group size (rows per scale group) for "
                         "--weight_quant int4/mixed; default 128")
    ap.add_argument("--quantize", default=None, choices=["int8"],
                    help="compatibility alias for --weight_quant")
    ap.add_argument("--kv_quant", default=None, choices=["int8"],
                    help="int8 KV cache (halves decode cache traffic; "
                         "ops/kv_quant.py)")
    ap.add_argument("--speculative", default=None, choices=["pld"],
                    help="prompt-lookup speculative decoding for greedy "
                         "requests (multi-token decode steps; "
                         "generation/speculative.py)")
    ap.add_argument("--draft_len", type=int, default=0,
                    help="engine-side speculative decoding: max draft "
                         "tokens per slot per step, proposed by the host "
                         "n-gram drafter and checked in one batched "
                         "verify forward (docs/serving.md, 'Speculative "
                         "decoding').  Composes with continuous batching, "
                         "paged KV, and the int8 cache; a per-slot "
                         "acceptance EWMA backs it off to plain decode on "
                         "text that doesn't repeat.  0 = off")
    ap.add_argument("--spec_ngram", type=int, default=3,
                    help="trailing n-gram length the speculative drafter "
                         "matches on (with --draft_len)")
    ap.add_argument("--draft_model", default=None,
                    help="resident draft model preset (config.PRESETS, "
                         "e.g. 'tiny') for tree speculation: a small "
                         "model lives on-device next to the target, "
                         "drafts top-k branch trees each iteration, and "
                         "the target verifies the whole tree in one "
                         "forward (docs/serving.md, 'Tree "
                         "speculation & resident drafts').  Beats the "
                         "n-gram drafter on random traffic; requires "
                         "--draft_len > 0.  Draft vocab/positions are "
                         "forced to the target's "
                         "(models/families.py:draft_model)")
    ap.add_argument("--draft_load", default=None,
                    help="checkpoint directory for --draft_model; "
                         "required unless --allow_random_draft is given")
    ap.add_argument("--allow_random_draft", action="store_true",
                    help="allow --draft_model without --draft_load: the "
                         "draft runs RANDOM-INIT (trajectories stay "
                         "bitwise-correct — a bad draft only lowers the "
                         "acceptance rate — but expect no speedup; "
                         "smoke-test escape hatch, refused otherwise)")
    ap.add_argument("--spec_reprobe_interval", type=int, default=None,
                    help="decode steps between speculation re-probes "
                         "after a slot's acceptance EWMA backs it off "
                         "to plain decode; default: engine default "
                         "(EngineConfig.spec_reprobe_interval)")
    ap.add_argument("--no_spec", action="store_true",
                    help="force engine-side speculative decoding off "
                         "(overrides --draft_len; diagnostic)")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel shards for serving")
    ap.add_argument("--pp", type=int, default=1,
                    help="pipeline-parallel serving stages: pp shards the "
                         "LAYER stack — params and the paged KV pool alike "
                         "(models/sharding.py:serving_param_specs / "
                         "kv_pool_specs) — and the engine microbatch-"
                         "interleaves decode steps across the stages "
                         "(docs/serving.md 'Pipeline-parallel decode')")
    ap.add_argument("--replicas", type=int, default=1,
                    help="engine replicas on disjoint pp·tp device slices "
                         "behind the health-aware cluster router "
                         "(serving/cluster/; docs/serving.md 'Multi-chip "
                         "serving'): least-loaded dispatch, sticky streams, "
                         "drain-based failover.  Needs replicas x tp x pp "
                         "<= visible devices")
    ap.add_argument("--router", action="store_true",
                    help="route through the cluster router even with a "
                         "single replica (uniform ops surface: GET "
                         "/cluster, per-replica drain); implied by "
                         "--replicas > 1")
    ap.add_argument("--disagg", default=None, metavar="N:M",
                    help="disaggregated prefill/decode: N prefill-"
                         "specialized + M decode replicas on disjoint "
                         "pp·tp device slices (docs/serving.md "
                         "'Disaggregated prefill/decode').  Prefill "
                         "replicas run each request's prefill with a "
                         "prefill-tuned attention grid and ship its KV "
                         "blocks to a decode replica; the router routes "
                         "by phase and live-migrates decodes.  "
                         "Supersedes --replicas; needs (N+M) x tp x pp "
                         "<= visible devices")
    ap.add_argument("--role", default="mixed",
                    choices=["prefill", "decode", "mixed"],
                    help="engine role for a SINGLE-engine server joining "
                         "an externally assembled disaggregated cluster "
                         "(reported by GET /cluster); --disagg sets "
                         "roles per replica itself")
    ap.add_argument("--supervise", action="store_true",
                    help="cluster self-healing (docs/robustness.md "
                         "'Cluster self-healing'): a ReplicaSupervisor "
                         "rebuilds crashed replicas on their original "
                         "submesh, re-warms them off-rotation, and "
                         "rejoins them at a bumped generation; requires "
                         "a router front-end (--router / --replicas / "
                         "--disagg)")
    ap.add_argument("--hang_timeout_s", type=float, default=10.0,
                    help="hung-step watchdog: a replica whose scheduler "
                         "iteration heartbeat is staler than this while "
                         "its thread is alive is declared wedged, "
                         "killed, and rebuilt (0 disables; only with "
                         "--supervise)")
    args = ap.parse_args(argv)

    from ..utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    from ..checkpointing import load_params_for_inference
    from ..models import families
    from ..tokenizer.tokenizer import build_tokenizer

    factory = {"llama": lambda s: families.llama(s, version=1),
               "llama2": lambda s: families.llama(s, version=2),
               "codellama": families.code_llama,
               "falcon": families.falcon,
               "gpt": families.gpt,
               "phi4flash": families.phi4flash,
               "laguna": families.laguna}[args.model]
    lm = factory(args.size)
    if args.kv_quant:
        import dataclasses

        from ..models.families import CausalLM

        lm = CausalLM(dataclasses.replace(
            lm.cfg, kv_cache_quant=args.kv_quant).validate())
    tokenizer = build_tokenizer(args.tokenizer_type, args.tokenizer_model)
    params = load_params_for_inference(args.load, lm.cfg)
    wq = args.weight_quant or args.quantize
    if wq:
        import dataclasses as _dc

        from ..ops.quant import quantize_params, resolve_policy

        pol = resolve_policy(wq)
        if args.quant_group_size:
            pol = _dc.replace(pol, group_size=args.quant_group_size)
        params = quantize_params(params, pol)
        print(f"weights quantized: policy={wq} (attn={pol.attn or 'fp'}, "
              f"mlp={pol.mlp or 'fp'}, embedding={pol.embedding or 'fp'}, "
              f"group_size={pol.group_size})")

    draft_cfg = None
    draft_params = None
    if args.draft_model and not args.no_spec and args.draft_len > 0:
        import jax as _jax

        from ..models import model as _model_lib

        # Mirror the target's KV quantization so both paged pools share
        # one residency policy; vocab/positions are forced inside
        # families.draft_model.
        draft_lm = families.draft_model(
            args.draft_model, lm.cfg,
            kv_cache_quant=lm.cfg.kv_cache_quant)
        draft_cfg = draft_lm.cfg
        if args.draft_load:
            draft_params = load_params_for_inference(args.draft_load,
                                                     draft_cfg)
        elif args.allow_random_draft:
            draft_params = _model_lib.init_params(_jax.random.key(0),
                                                  draft_cfg)
            print("draft model: no --draft_load given — RANDOM INIT "
                  "(tokens stay bitwise-correct, but acceptance will "
                  "be near zero; load a trained draft for speedup)")
        else:
            # A random draft silently serves at a *loss* (every verify
            # forward wasted); make that an explicit opt-in, not a
            # default a typo'd --draft_load path can fall into.
            ap.error("--draft_model without --draft_load would serve a "
                     "random-init draft (near-zero acceptance, pure "
                     "overhead); pass --draft_load CKPT, or "
                     "--allow_random_draft for smoke tests")

    cluster = args.replicas > 1 or args.router or args.disagg is not None
    if args.supervise and not cluster:
        ap.error("--supervise needs a router front-end; add --router, "
                 "--replicas N, or --disagg N:M")
    mesh_ctx = None
    if args.disagg is not None:
        print(f"disaggregated cluster: {args.disagg} prefill:decode "
              f"replicas x tp={args.tp} pp={args.pp} submeshes "
              "behind the phase-routing router (GET /cluster; "
              "docs/serving.md 'Disaggregated prefill/decode')")
    elif cluster:
        # cluster mode: each replica engine shards its own params onto
        # its submesh (serving/cluster/sharded.py) and runs under that
        # mesh on its scheduler thread — no ambient process-wide mesh
        print(f"cluster: {args.replicas} replica(s) x "
              f"tp={args.tp} pp={args.pp} submeshes behind the "
              "router (GET /cluster; docs/serving.md 'Multi-chip "
              "serving')")
    elif args.tp > 1 or args.pp > 1:
        from ..config import ParallelConfig
        from ..models.sharding import shard_for_serving
        from ..parallel import mesh as mesh_lib

        parallel = ParallelConfig(pipeline_parallel=args.pp,
                                  tensor_parallel=args.tp)
        params, mesh = shard_for_serving(params, lm.cfg, parallel)
        mesh_ctx = mesh_lib.use_mesh(mesh)
        print(f"serving layout: {dict(mesh.shape)} "
              f"(tp={args.tp} heads, pp={args.pp} layer stages)")

    from ..generation.server import MegatronServer

    if args.log_json:
        import sys

        from ..obs.logging import EVENT_LOG

        EVENT_LOG.configure(stream=sys.stderr)

    prefix_blocks = 0 if args.no_prefix_cache else args.prefix_cache_blocks
    server = MegatronServer(
        lm.cfg, params, tokenizer,
        max_batch_size=args.max_batch_size,
        max_tokens_to_generate=args.max_tokens_to_generate,
        speculative=args.speculative,
        queue_size=args.queue_size,
        engine_max_seq_len=args.max_seq_len,
        retry_after_s=args.retry_after_s,
        request_deadline_s=args.request_deadline_s,
        prefill_bucket=args.prefill_bucket,
        prefill_chunk=args.prefill_chunk,
        pipeline_decode=not args.no_pipeline_decode,
        prefix_cache_blocks=prefix_blocks,
        kv_block_size=args.kv_block_size,
        kv_pool_blocks=args.kv_pool_blocks,
        host_kv_blocks=args.host_kv_blocks,
        default_priority=args.default_priority,
        spec_draft_len=0 if args.no_spec else args.draft_len,
        spec_ngram=args.spec_ngram,
        spec_reprobe_interval=args.spec_reprobe_interval,
        draft_cfg=draft_cfg,
        draft_params=draft_params,
        trace=not args.no_trace,
        profile_dir=args.profile_dir,
        tensor_parallel=args.tp if cluster else 1,
        pipeline_parallel=args.pp if cluster else 1,
        replicas=args.replicas,
        router=args.router,
        disagg=args.disagg,
        role=args.role,
        supervise=args.supervise,
        hang_timeout_s=args.hang_timeout_s)
    if args.supervise:
        print(f"self-healing: replica supervisor armed "
              f"(hang_timeout_s={args.hang_timeout_s}; "
              "docs/robustness.md 'Cluster self-healing')")
    if prefix_blocks:
        block_tokens = args.prefill_chunk or max(1, args.prefill_bucket)
        print(f"prefix cache: {prefix_blocks} blocks x {block_tokens} "
              f"tokens (budget {prefix_blocks * block_tokens} cached "
              "prompt tokens; docs/serving.md 'Prefix caching')")
    else:
        print("prefix cache: disabled")
    if args.kv_block_size or args.kv_pool_blocks:
        print(f"paged KV: block_size={args.kv_block_size or 'auto'} "
              f"pool_blocks={args.kv_pool_blocks or 'auto'} "
              "(GET /kv; tools/dump_kv_pool.py)")
    if args.draft_len and not args.no_spec:
        if draft_cfg is not None:
            print(f"speculative decoding: draft_len={args.draft_len} "
                  f"draft_model={args.draft_model} (resident draft + "
                  "tree verification; docs/serving.md 'Tree "
                  "speculation & resident drafts')")
        else:
            print(f"speculative decoding: draft_len={args.draft_len} "
                  f"ngram={args.spec_ngram} (greedy requests; "
                  "docs/serving.md 'Speculative decoding')")
    print("tracing: " + ("disabled (--no_trace)" if args.no_trace
                         else "on (GET /trace; tools/dump_trace.py)"))
    if args.metrics_interval_s > 0:
        _start_metrics_logger(server.service, args.metrics_interval_s)
    print(f"serving on {args.host}:{args.port}")
    if mesh_ctx is not None:
        with mesh_ctx:
            server.run(args.host, args.port,
                       drain_timeout_s=args.drain_timeout_s)
    else:
        server.run(args.host, args.port,
                   drain_timeout_s=args.drain_timeout_s)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
