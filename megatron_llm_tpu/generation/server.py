"""REST text-generation server.

Parity with the reference's Flask ``MegatronServer``
(megatron/text_generation_server.py:17-241): ``PUT /api`` takes a JSON body
with ``prompts`` plus sampling knobs, returns ``{"text", "segments",
"logprobs"}`` (or beam-search results when ``beam_width`` is set), with the
same field validation and error strings.  Flask is not available in this
image, so the server is built on the stdlib ``http.server``
(``ThreadingHTTPServer``) — one SPMD process, no rank-0
``send_do_generate`` controller choreography.

Generation requests no longer serialize behind a global lock: they submit
to the continuous-batching engine (megatron_llm_tpu/serving/, see
docs/serving.md), which interleaves concurrent requests at decode-iteration
granularity over a slot-managed KV cache.  Consequences for the HTTP
contract:

- any number of prompts per request is accepted (the old hard
  ``400 "Maximum number of prompts is N"`` is gone) — prompts beyond the
  free slots simply queue and join the running batch as slots free up;
- ``400`` remains only for a prompt whose length + ``tokens_to_generate``
  exceeds the per-slot sequence budget;
- when the bounded queue is full the server answers ``503`` with a
  ``Retry-After`` hint instead of blocking the HTTP thread;
- on SIGTERM the server drains gracefully: in-flight generations run to
  completion (bounded by a drain timeout) while new submissions get
  ``503``, then the listener stops (docs/serving.md, robustness).

Beam search and scoring (``tokens_to_generate=0``) keep the legacy
one-shot path behind the lock — they run as dedicated jitted programs, not
the slot decode loop.
"""

from __future__ import annotations

import json
import math
import os
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional
from urllib.parse import parse_qs, urlparse

from ..analysis.sanitizers import make_lock
from ..config import ModelConfig
from ..obs import profile as obs_profile
from ..obs.logging import EVENT_LOG
from ..obs.registry import REGISTRY
from ..tokenizer.tokenizer import Tokenizer
from .api import (
    beam_search_and_post_process,
    generate_and_post_process,
    pld_eligible,
    score_and_post_process,
)

# the longest profile POST /profile takes: traces grow with time, and the
# handler's thread holds the process's one session that long
MAX_PROFILE_SECONDS = 60.0


class GenerationService:
    """Validates requests and runs generation.  Separated from HTTP plumbing
    so it is directly unit-testable (and reusable from the CLI)."""

    def __init__(self, cfg: ModelConfig, params, tokenizer: Tokenizer,
                 max_batch_size: int = 8, max_tokens_to_generate: int = 1024,
                 speculative: str | None = None,
                 engine=None, queue_size: int = 32,
                 engine_max_seq_len: int | None = None,
                 retry_after_s: float = 1.0,
                 request_deadline_s: float | None = None,
                 prefill_bucket: int = 1,
                 prefill_chunk: int | None = None,
                 pipeline_decode: bool = True,
                 prefix_cache_blocks: int | None = None,
                 kv_block_size: int | None = None,
                 kv_pool_blocks: int | None = None,
                 host_kv_blocks: int = 0,
                 default_priority: int = 0,
                 spec_draft_len: int = 0,
                 spec_ngram: int = 3,
                 spec_reprobe_interval: int | None = None,
                 draft_cfg: ModelConfig | None = None,
                 draft_params=None,
                 trace: bool = True,
                 profile_dir: str | None = None,
                 tensor_parallel: int = 1,
                 pipeline_parallel: int = 1,
                 replicas: int = 1,
                 router: bool = False,
                 router_config=None,
                 disagg: str | None = None,
                 role: str = "mixed",
                 supervise: bool = False,
                 hang_timeout_s: float = 10.0,
                 supervisor_config=None):
        self.cfg = cfg
        self.params = params
        self.tokenizer = tokenizer
        # max_batch_size now sizes the engine's KV slots (max CONCURRENT
        # decodes), not a per-request prompt-count cap
        self.max_batch_size = max_batch_size
        self.max_tokens_to_generate = max_tokens_to_generate
        # "pld": greedy requests (ragged prompts included) run
        # prompt-lookup speculative decoding (generation/speculative.py);
        # ineligible requests use the continuous-batching engine, and the
        # response's "speculative" field says which path served it.
        self.speculative = speculative
        self.queue_size = queue_size
        self.engine_max_seq_len = min(
            engine_max_seq_len or cfg.max_position_embeddings,
            cfg.max_position_embeddings)
        self.retry_after_s = retry_after_s
        # wall-clock budget per generation request (docs/serving.md,
        # robustness): expired requests finish with reason "timeout"
        # instead of holding a KV slot or queue position forever
        self.request_deadline_s = request_deadline_s
        # admission knobs (docs/serving.md): prefill_bucket bounds the
        # number of compiled prefill shapes under ragged prompt lengths;
        # prefill_chunk interleaves admission with decode chunk-at-a-time
        self.prefill_bucket = prefill_bucket
        self.prefill_chunk = prefill_chunk
        self.pipeline_decode = pipeline_decode
        # automatic prefix caching (serving/prefix_cache.py): HBM budget
        # in blocks; 0 disables, None keeps the engine default
        self.prefix_cache_blocks = prefix_cache_blocks
        # paged KV cache (serving/block_pool.py): block size in tokens and
        # pool size in blocks; None keeps the engine defaults
        # (docs/serving.md, 'Paged KV cache')
        self.kv_block_size = kv_block_size
        self.kv_pool_blocks = kv_pool_blocks
        # tiered KV (docs/serving.md, 'Tiered KV'): host-RAM arena in
        # blocks backing prefix spill, decode preemption, and
        # oversubscribed admission; 0 disables the tier
        self.host_kv_blocks = host_kv_blocks
        # QoS class for requests that don't send a "priority" JSON field
        # (higher preempts lower when the tier is enabled)
        self.default_priority = default_priority
        # engine-side speculative decoding (serving/engine.py): per-slot
        # n-gram drafts checked by a batched verify step; 0 disables.
        # Distinct from the one-shot PLD path behind ``speculative="pld"``
        self.spec_draft_len = spec_draft_len
        self.spec_ngram = spec_ngram
        # stalled-slot re-probe cadence; None keeps the engine default
        self.spec_reprobe_interval = spec_reprobe_interval
        # resident draft model (tree speculation, docs/serving.md): a
        # small model drafting candidate trees on-device, replacing the
        # host n-gram probe when present.  Shares the target vocabulary.
        self.draft_cfg = draft_cfg
        self.draft_params = draft_params
        # per-request span tracing (obs/trace.py, GET /trace); the CLI's
        # --no_trace escape hatch lands here
        self.trace_enabled = trace
        # where POST /profile writes device profiles (obs/profile.py);
        # None refuses the route
        self.profile_dir = profile_dir
        # multi-chip serving (serving/cluster/, docs/serving.md): shard
        # each engine over a pp·tp submesh and/or replicate engines on
        # disjoint device slices behind the health-aware router.  The
        # Router presents the engine surface (submit_many / drain /
        # metrics / trace / kv_snapshot), so everything below it is
        # topology-blind.  router=True forces the router front-end even
        # at replicas=1 (uniform ops surface: GET /cluster, drain API).
        self.tensor_parallel = tensor_parallel
        self.pipeline_parallel = pipeline_parallel
        self.replicas = replicas
        self.router = router
        self.router_config = router_config
        # disaggregated prefill/decode (docs/serving.md): disagg="N:M"
        # builds N prefill-specialized + M decode replicas behind the
        # phase-routing router (supersedes `replicas`); `role` tags a
        # single-engine server's role in an externally assembled cluster
        self.disagg = self._parse_disagg(disagg)
        self.role = role
        # cluster self-healing (serving/cluster/supervisor.py,
        # docs/robustness.md): supervise=True attaches a
        # ReplicaSupervisor that rebuilds dead replicas on their original
        # submesh and kills wedged ones (iteration heartbeat stale for
        # hang_timeout_s).  Only meaningful behind a router front-end.
        self.supervise = supervise
        self.hang_timeout_s = hang_timeout_s
        self.supervisor_config = supervisor_config
        # the lock now guards only the legacy one-shot paths (beam search,
        # scoring, PLD); standard generation goes through the engine
        self.lock = make_lock("server.generate")
        self._engine = engine
        self._engine_init_lock = make_lock("server.engine_init")
        self._draining = False

    @staticmethod
    def _parse_disagg(disagg: str | None) -> tuple[int, int] | None:
        if disagg is None:
            return None
        try:
            n, m = (int(x) for x in str(disagg).split(":"))
        except ValueError:
            raise ValueError(
                f"--disagg expects N:M (prefill:decode replicas), "
                f"got {disagg!r}") from None
        if n < 1 or m < 1:
            raise ValueError(
                f"--disagg needs at least one replica per role, "
                f"got {disagg!r}")
        return n, m

    @property
    def engine(self):
        """The continuous-batching engine, created lazily so beam/score-only
        services never allocate the slot cache."""
        with self._engine_init_lock:
            if self._engine is None:
                from ..serving import EngineConfig, ServingEngine

                extra = {}
                if self.prefix_cache_blocks is not None:
                    extra["prefix_cache_blocks"] = self.prefix_cache_blocks
                if self.kv_block_size is not None:
                    extra["kv_block_size"] = self.kv_block_size
                if self.kv_pool_blocks is not None:
                    extra["kv_pool_blocks"] = self.kv_pool_blocks
                if self.host_kv_blocks:
                    extra["host_kv_blocks"] = self.host_kv_blocks
                if self.spec_reprobe_interval is not None:
                    extra["spec_reprobe_interval"] = \
                        self.spec_reprobe_interval
                draft_kw = {}
                if self.draft_cfg is not None:
                    draft_kw = {"draft_cfg": self.draft_cfg,
                                "draft_params": self.draft_params}
                engine_config = EngineConfig(
                    max_batch_size=self.max_batch_size,
                    max_seq_len=self.engine_max_seq_len,
                    max_queue_size=self.queue_size,
                    retry_after_s=self.retry_after_s,
                    default_deadline_s=self.request_deadline_s,
                    prefill_bucket=self.prefill_bucket,
                    prefill_chunk=self.prefill_chunk,
                    pipeline_decode=self.pipeline_decode,
                    spec_draft_len=self.spec_draft_len,
                    spec_ngram=self.spec_ngram,
                    trace=self.trace_enabled,
                    role=self.role,
                    **extra)
                shards = self.tensor_parallel * self.pipeline_parallel
                if self.disagg is not None:
                    from ..config import ParallelConfig
                    from ..serving import build_disagg_cluster

                    n, m = self.disagg
                    self._engine = build_disagg_cluster(
                        self.cfg, self.params, engine_config,
                        prefill_replicas=n, decode_replicas=m,
                        parallel=ParallelConfig(
                            pipeline_parallel=self.pipeline_parallel,
                            tensor_parallel=self.tensor_parallel),
                        router_config=self.router_config, **draft_kw)
                elif self.router or self.replicas > 1 or shards > 1:
                    from ..config import ParallelConfig
                    from ..serving import build_cluster

                    self._engine = build_cluster(
                        self.cfg, self.params, engine_config,
                        replicas=self.replicas,
                        parallel=ParallelConfig(
                            pipeline_parallel=self.pipeline_parallel,
                            tensor_parallel=self.tensor_parallel),
                        router_config=self.router_config, **draft_kw)
                else:
                    self._engine = ServingEngine(self.cfg, self.params,
                                                 engine_config, **draft_kw)
                if self.supervise and hasattr(self._engine, "replicas"):
                    from ..serving import (ReplicaSupervisor,
                                           SupervisorConfig)

                    sc = self.supervisor_config or SupervisorConfig(
                        hang_timeout_s=self.hang_timeout_s)
                    # Router.shutdown stops the supervisor it carries
                    ReplicaSupervisor(self._engine, sc).start()
            return self._engine

    def metrics_snapshot(self) -> dict:
        """Point-in-time serving metrics (GET /metrics).  An engine that
        was never created reports an empty-engine snapshot rather than
        instantiating the slot cache just to be scraped."""
        with self._engine_init_lock:
            engine = self._engine
        if engine is None:
            from ..serving import ServingMetrics

            # register=False: a scrape-only throwaway must not displace a
            # live engine's collector in the shared obs registry
            return ServingMetrics(self.max_batch_size,
                                  register=False).snapshot()
        return engine.metrics.snapshot()

    def prometheus_metrics(self) -> str:
        """Prometheus text exposition of the shared obs registry
        (GET /metrics?format=prometheus): serving + resilience + training
        metrics from one scrape."""
        # the resilience collector registers when ..metrics imports; a
        # serving-only process would otherwise never pull that module in
        from .. import metrics as _resilience  # noqa: F401

        return REGISTRY.prometheus_text()

    def trace_snapshot(self) -> dict:
        """Chrome trace-event JSON of the engine's span ring (GET /trace).
        An engine that was never created reports an empty trace."""
        with self._engine_init_lock:
            engine = self._engine
        if engine is None:
            return {"traceEvents": [], "displayTimeUnit": "ms",
                    "otherData": {"dropped_events": 0}}
        return engine.trace.chrome_trace()

    def profile(self, body: dict) -> tuple:
        """``POST /profile {"seconds": n}``: one device profile of the
        next ``n`` seconds of whatever this process serves, written under
        the configured profile directory → ``(status, payload)``.  The
        handler's own thread waits out the seconds; the engine is not
        told.  The reply carries the session's clock-sync pair, which
        joins ``GET /trace`` spans to the profile (docs/observability.md)."""
        if not self.profile_dir:
            return 403, "no profile directory is configured"
        try:
            seconds = float(body.get("seconds", 3.0))
        except (TypeError, ValueError, AttributeError):
            return 400, "seconds must be a number"
        if not 0.0 < seconds <= MAX_PROFILE_SECONDS:
            return 400, (f"seconds must be above 0 and at most "
                         f"{MAX_PROFILE_SECONDS}")
        out = os.path.join(self.profile_dir,
                           time.strftime("%Y%m%d-%H%M%S"))
        try:
            obs_profile.start(out)
        except RuntimeError as e:          # a session is already active
            return 409, str(e)
        try:
            time.sleep(seconds)
        finally:
            session = obs_profile.stop()
        return 200, {"dir": out, "seconds": seconds,
                     "clock_sync": session.clock_sync()}

    def kv_snapshot(self) -> dict:
        """Debug view of the paged KV pool (GET /kv,
        tools/dump_kv_pool.py): pool stats, per-slot block tables, ref
        counts, fragmentation.  An engine that was never created reports
        an empty pool."""
        with self._engine_init_lock:
            engine = self._engine
        if engine is None:
            return {"pool": None, "slots": {}}
        return engine.kv_snapshot()

    def cluster_snapshot(self) -> dict:
        """Cluster topology + health view (GET /cluster): router
        dispatch/failover counters and per-replica probes when serving
        through the cluster router, a single-engine summary otherwise.
        An engine that was never created reports an empty cluster."""
        with self._engine_init_lock:
            engine = self._engine
        if engine is None:
            return {"router": None, "replicas": []}
        if hasattr(engine, "replicas"):  # serving.cluster.Router
            return engine.snapshot()
        return {"router": None, "replicas": [{
            "id": "engine-0",
            "role": engine.config.role,
            "alive": engine._scheduler_error is None,
            "queue_depth": len(engine.queue),
            "slots_active": (engine.slots.active_slots
                             if engine.slots is not None else 0),
        }]}

    def drain(self, timeout: float | None = 30.0) -> bool:
        """Stop accepting generation requests and wait for the in-flight
        ones to complete.  True once idle (trivially so if the engine was
        never created), False if the timeout expired first."""
        with self._engine_init_lock:
            # sticky: the lazy `engine` property must not resurrect a
            # fresh, accepting engine after the drained one is closed
            self._draining = True
            engine = self._engine
        if engine is None:
            return True
        return engine.drain(timeout)

    def close(self) -> None:
        with self._engine_init_lock:
            if self._engine is not None:
                self._engine.shutdown()
                self._engine = None

    def handle(self, body: dict) -> tuple[int, dict | str]:
        """Returns (http_status, response_json_or_error_string).

        Validation parity: text_generation_server.py:31-188.
        """
        if "prompts" not in body:
            return 400, "prompts argument required"
        if "max_len" in body:
            return 400, ("max_len is no longer used.  "
                         "Replace with tokens_to_generate")
        if "sentences" in body:
            return 400, "sentences is no longer used.  Replace with prompts"
        prompts = body["prompts"]
        if not isinstance(prompts, list) or \
                not all(isinstance(p, str) for p in prompts):
            return 400, "prompts is not a list of strings"
        if len(prompts) == 0:
            return 400, "prompts is empty"
        # No per-request prompt-count cap: prompts beyond the free KV slots
        # queue in the engine and join the running batch as slots free up.

        tokens_to_generate = body.get("tokens_to_generate", 64)
        if not isinstance(tokens_to_generate, int) or \
                isinstance(tokens_to_generate, bool):
            return 400, "tokens_to_generate must be an integer greater than 0"
        if tokens_to_generate < 0:
            return 400, ("tokens_to_generate must be an integer greater "
                         "than or equal to 0")
        if tokens_to_generate > self.max_tokens_to_generate:
            return 400, (f"tokens_to_generate must be at most "
                         f"{self.max_tokens_to_generate}")

        logprobs = body.get("logprobs", False)
        if not isinstance(logprobs, bool):
            return 400, "logprobs must be a boolean value"
        if tokens_to_generate == 0 and not logprobs:
            return 400, "tokens_to_generate=0 implies logprobs should be True"

        temperature = body.get("temperature", 1.0)
        if not isinstance(temperature, (int, float)) or \
                not 0.0 < temperature <= 100.0:
            return 400, "temperature must be a positive number less than " \
                        "or equal to 100.0"
        top_k = body.get("top_k", 0)
        if not isinstance(top_k, int) or isinstance(top_k, bool) or \
                not 0 <= top_k <= 1000:
            return 400, "top_k must be an integer equal to or greater " \
                        "than 0 and less than or equal to 1000"
        top_p = body.get("top_p", 0.0)
        if not isinstance(top_p, (int, float)) or not 0.0 <= top_p <= 1.0:
            return 400, "top_p must be less than or equal to 1 and greater " \
                        "than or equal to 0"
        if top_p > 0.0 and top_k > 0:
            return 400, "cannot set both top-k and top-p samplings"

        add_BOS = body.get("add_BOS", False)
        if not isinstance(add_BOS, bool):
            return 400, "add_BOS must be a boolean value"
        if any(len(p) == 0 for p in prompts) and not add_BOS:
            return 400, "Empty prompts require add_BOS=true"

        random_seed = body.get("random_seed", -1)
        if not isinstance(random_seed, int) or isinstance(random_seed, bool):
            return 400, "random_seed must be integer"
        if random_seed < -1:
            return 400, "random_seed must be a positive integer"

        no_early_term = body.get("no_early_termination", False)
        if not isinstance(no_early_term, bool):
            return 400, "no_early_termination must be a boolean value"

        priority = body.get("priority", self.default_priority)
        if not isinstance(priority, int) or isinstance(priority, bool):
            return 400, "priority must be an integer (higher = sooner; " \
                        "may preempt lower classes under tiered KV)"

        beam_width = body.get("beam_width", None)
        if beam_width is not None:
            if not isinstance(beam_width, int) or beam_width < 1:
                return 400, "beam_width must be an integer > 0"
            if len(prompts) > 1:
                return 400, "When doing beam_search, batch size must be 1"
        stop_token = body.get("stop_token", None)
        length_penalty = body.get("length_penalty", 1.0)

        if beam_width is not None:
            with self.lock:
                try:
                    res = beam_search_and_post_process(
                        self.cfg, self.params, self.tokenizer, prompts[0],
                        tokens_to_generate=tokens_to_generate,
                        beam_size=beam_width,
                        stop_token=stop_token,
                        length_penalty=length_penalty,
                        num_return_gen=beam_width,
                        add_BOS=add_BOS, return_segments=True)
                    return 200, {"text": res.texts,
                                 "segments": res.segments,
                                 "scores": res.scores}
                except ValueError as e:
                    return 400, str(e)
        if tokens_to_generate == 0:
            with self.lock:
                try:
                    res = score_and_post_process(
                        self.cfg, self.params, self.tokenizer, prompts)
                    return 200, {"text": res.texts,
                                 "logprobs": res.logprobs}
                except ValueError as e:
                    return 400, str(e)
        return self._handle_generate(
            prompts, tokens_to_generate, logprobs=logprobs, top_k=top_k,
            top_p=top_p, temperature=temperature, add_BOS=add_BOS,
            use_eos_stop=not no_early_term, random_seed=random_seed,
            priority=priority)

    def _handle_generate(self, prompts, tokens_to_generate, *, logprobs,
                         top_k, top_p, temperature, add_BOS, use_eos_stop,
                         random_seed, priority=0):
        """Standard generation through the continuous-batching engine.

        Keeps the legacy batch contract: the shared buffer is
        ``max(prompt_len) + tokens_to_generate``, so in a ragged batch the
        shorter prompts may generate extra tokens (exactly what the
        one-shot path produced).
        """
        # -- tokenize (parity: api.tokenize_prompts, per prompt) ----------
        try:
            ids = []
            for p in prompts:
                t = self.tokenizer.tokenize(p)
                if add_BOS and self.tokenizer.bos is not None:
                    t = [self.tokenizer.bos] + t
                if len(t) == 0:
                    raise ValueError(
                        "a prompt tokenized to zero tokens (empty prompt "
                        "with a BOS-less tokenizer?)")
                ids.append(t)
        except ValueError as e:
            return 400, str(e)
        lengths = [len(t) for t in ids]
        total_budget = max(lengths) + tokens_to_generate
        # 400 only for the sequence budget (satellite contract): the
        # engine's per-slot cache width and the model's positions
        budget = min(self.engine_max_seq_len,
                     self.cfg.max_position_embeddings)
        if total_budget > budget:
            return 400, (f"prompt + tokens_to_generate = {total_budget} "
                         f"exceeds the sequence budget = {budget}")

        spec_tag = None
        if self.speculative == "pld":
            ok, reason = pld_eligible("pld", top_k, top_p, logprobs,
                                      lengths)
            if ok:
                # PLD's multi-token verify loop is its own jitted program;
                # eligible requests keep it (legacy one-shot path)
                with self.lock:
                    try:
                        res = generate_and_post_process(
                            self.cfg, self.params, self.tokenizer, prompts,
                            tokens_to_generate=tokens_to_generate,
                            return_output_log_probs=logprobs,
                            return_segments=True,
                            top_k_sampling=top_k, top_p_sampling=top_p,
                            temperature=temperature, add_BOS=add_BOS,
                            use_eod_token_for_early_termination=use_eos_stop,
                            random_seed=random_seed,
                            speculative="pld")
                    except ValueError as e:
                        return 400, str(e)
                return 200, {"text": res.texts, "segments": res.segments,
                             "logprobs": res.logprobs,
                             "speculative": res.speculative}
            spec_tag = f"fallback:{reason}"

        # -- submit to the engine (all-or-nothing) ------------------------
        from ..serving import QueueFull

        if self._draining:
            return 503, {"message": "server is draining (shutting down); "
                                    "not accepting generation requests",
                         "retry_after": int(math.ceil(self.retry_after_s))}

        specs = []
        for i, t in enumerate(ids):
            specs.append(dict(
                prompt=t,
                max_new_tokens=total_budget - len(t),
                eos_id=self.tokenizer.eod,
                temperature=temperature, top_k=top_k, top_p=top_p,
                seed=(None if random_seed < 0 else random_seed + i),
                use_eos_stop=use_eos_stop, return_logprobs=logprobs,
                priority=priority))
        try:
            handles = self.engine.submit_many(specs)
        except QueueFull as e:
            return 503, {"message": str(e),
                         "retry_after": int(math.ceil(e.retry_after_s))}
        except ValueError as e:
            return 400, str(e)
        rids = [h.rid for h in handles]
        try:
            results = [h.result() for h in handles]
        except RuntimeError as e:
            for rid in rids:
                EVENT_LOG.emit("server", "http_response", request_id=rid,
                               status=500)
            return 500, str(e)

        texts, segments, lps = [], [], []
        for r in results:
            texts.append(self.tokenizer.detokenize(r.tokens))
            segments.append(
                [self.tokenizer.detokenize([t]) for t in r.tokens])
            if logprobs:
                lps.append(r.logprobs)
        resp = {"text": texts, "segments": segments,
                "logprobs": lps if logprobs else None,
                # correlation ids (one per prompt): the same ids every
                # engine log line and trace span for these prompts carry
                "request_ids": rids}
        if spec_tag is not None:
            # surface PLD-vs-fallback so clients can see when the
            # requested speculative path did not serve them
            resp["speculative"] = spec_tag
        for rid, r in zip(rids, results):
            EVENT_LOG.emit("server", "http_response", request_id=rid,
                           status=200, finish_reason=r.finish_reason)
        return 200, resp


class _Handler(BaseHTTPRequestHandler):
    service: GenerationService  # injected by make_server

    def log_message(self, *args):  # quiet by default
        pass

    def _respond(self, status: int, payload, ctype: str | None = None):
        if isinstance(payload, str):
            body = payload.encode()
            ctype = ctype or "text/plain"
        else:
            body = json.dumps(payload).encode()
            ctype = ctype or "application/json"
        self.send_response(status)
        self.send_header("Content-Type", ctype)
        self.send_header("Content-Length", str(len(body)))
        if status == 503 and isinstance(payload, dict) \
                and "retry_after" in payload:
            # bounded-queue backpressure: tell the client when to come back
            self.send_header("Retry-After", str(payload["retry_after"]))
        self.end_headers()
        self.wfile.write(body)

    def do_PUT(self):
        if self.path.rstrip("/") != "/api":
            self._respond(404, "not found")
            return
        try:
            n = int(self.headers.get("Content-Length", 0))
            body = json.loads(self.rfile.read(n) or b"{}")
        except (ValueError, json.JSONDecodeError):
            self._respond(400, "invalid JSON body")
            return
        status, payload = self.service.handle(body)
        self._respond(status, payload)

    def do_POST(self):
        if self.path.rstrip("/") != "/profile":
            self.do_PUT()   # convenience; the reference accepts PUT only
            return
        try:
            n = int(self.headers.get("Content-Length", 0))
            body = json.loads(self.rfile.read(n) or b"{}")
        except (ValueError, json.JSONDecodeError):
            self._respond(400, "invalid JSON body")
            return
        self._respond(*self.service.profile(body))

    def do_GET(self):
        url = urlparse(self.path)
        route = url.path.rstrip("/")
        if route == "/metrics":
            fmt = parse_qs(url.query).get("format", ["json"])[0]
            if fmt == "prometheus":
                # the shared obs registry (serving + resilience +
                # training) in text exposition format
                self._respond(
                    200, self.service.prometheus_metrics(),
                    ctype="text/plain; version=0.0.4; charset=utf-8")
                return
            # counters, gauges (incl. the device/host step breakdown), and
            # latency histograms — see serving/metrics.py:snapshot
            self._respond(200, self.service.metrics_snapshot())
            return
        if route == "/trace":
            # Chrome trace-event JSON of the engine's span ring — load in
            # chrome://tracing or Perfetto (obs/trace.py)
            self._respond(200, self.service.trace_snapshot())
            return
        if route == "/kv":
            # paged KV pool debug view: block tables, ref counts,
            # fragmentation (serving/block_pool.py, tools/dump_kv_pool.py)
            self._respond(200, self.service.kv_snapshot())
            return
        if route == "/cluster":
            # multi-chip topology + health: router dispatch/failover
            # counters, per-replica probes (serving/cluster/router.py)
            self._respond(200, self.service.cluster_snapshot())
            return
        self._respond(404, "not found")


class MegatronServer:
    """HTTP front-end (reference: MegatronServer,
    text_generation_server.py:234-241)."""

    def __init__(self, cfg: ModelConfig, params, tokenizer: Tokenizer,
                 **service_kw):
        self.service = GenerationService(cfg, params, tokenizer, **service_kw)
        self._httpd: Optional[ThreadingHTTPServer] = None
        self._prev_sigterm = None

    def run(self, host: str = "0.0.0.0", port: int = 5000,
            block: bool = True, graceful_sigterm: bool = True,
            drain_timeout_s: float = 30.0):
        handler = type("Handler", (_Handler,), {"service": self.service})
        self._httpd = ThreadingHTTPServer((host, port), handler)
        self._drain_timeout_s = drain_timeout_s
        if graceful_sigterm:
            self._install_sigterm_handler()
        if block:
            self._httpd.serve_forever()
        else:
            t = threading.Thread(target=self._httpd.serve_forever,
                                 daemon=True)
            t.start()
        return self._httpd

    @property
    def port(self) -> int:
        assert self._httpd is not None
        return self._httpd.server_address[1]

    def _install_sigterm_handler(self) -> None:
        import signal

        try:
            self._prev_sigterm = signal.signal(signal.SIGTERM,
                                               self._on_sigterm)
        except ValueError:
            # signal.signal is only legal on the main thread (tests and
            # embedders start the server elsewhere) — drain on request only
            self._prev_sigterm = None

    def _on_sigterm(self, signum, frame) -> None:
        # The handler may run on the thread blocked in serve_forever();
        # httpd.shutdown() would deadlock there, so drain on a worker.
        threading.Thread(target=self.graceful_shutdown,
                         name="sigterm-drain", daemon=True).start()

    def graceful_shutdown(self, drain_timeout_s: float | None = None) -> bool:
        """Drain in-flight generations (new submissions get 503), then stop
        the HTTP listener.  Returns whether the drain completed in time."""
        if drain_timeout_s is None:
            drain_timeout_s = getattr(self, "_drain_timeout_s", 30.0)
        drained = self.service.drain(drain_timeout_s)
        self.shutdown()
        return drained

    def shutdown(self):
        if self._prev_sigterm is not None:
            import signal

            try:
                signal.signal(signal.SIGTERM, self._prev_sigterm)
            except ValueError:
                pass
            self._prev_sigterm = None
        if self._httpd is not None:
            self._httpd.shutdown()
            self._httpd.server_close()
            self._httpd = None
        self.service.close()
