"""Serving counters, gauges, and latency histograms.

The engine records scheduler-level observability through this object:
request lifecycle counters (submitted/admitted/completed/rejected/
cancelled), slot-occupancy gauges, decode-iteration stats (including the
max per-iteration batch — the direct evidence that requests actually
shared a decode step), and latency histograms (time-to-first-token,
per-token, end-to-end).

Export paths: every ``ServingMetrics`` registers itself as the
``"serving"`` collector in the process-global ``obs.REGISTRY`` (newest
instance wins), so Prometheus scrapes via
``GET /metrics?format=prometheus`` see serving, resilience, and training
metrics side by side; ``snapshot()`` backs the JSON ``GET /metrics``
shape; ``write`` exports scalars to the tensorboard-style writer
interface the training metrics use.  An ``obs.SLOTracker`` rides along
(``self.slo``), fed from the TTFT / decode-iteration / finish observers,
so router health checks can read burn rates per replica.

Everything is host-side and lock-guarded: the writers are the scheduler
thread and HTTP threads, the readers are tests / monitoring pollers.
"""

from __future__ import annotations

from typing import List, Optional, Sequence

from ..analysis.sanitizers import make_lock
from ..obs.registry import REGISTRY, MetricFamily, summary_family
from ..obs.slo import SLOConfig, SLOTracker
from ..utils.timers import Timers


class LatencyHistogram:
    """Bounded reservoir of latency samples with mean / percentile readout.

    Keeps the most recent ``max_samples`` observations — serving wants
    *recent* tail latency, and an unbounded list would grow forever on a
    long-lived engine.  Mean and percentiles cover the same retained
    window so they stay mutually consistent on long-lived engines;
    ``total_count`` / ``total`` are the all-time aggregates."""

    def __init__(self, max_samples: int = 4096):
        self.max_samples = max_samples
        self._samples: list[float] = []
        self._count = 0
        self._total = 0.0
        self._window_total = 0.0

    def observe(self, seconds: float) -> None:
        self._count += 1
        self._total += seconds
        self._samples.append(seconds)
        self._window_total += seconds
        if len(self._samples) > self.max_samples:
            evict = len(self._samples) - self.max_samples
            self._window_total -= sum(self._samples[:evict])
            del self._samples[:evict]

    @property
    def count(self) -> int:
        """All-time observation count (kept for back-compat; alias of
        ``total_count``)."""
        return self._count

    @property
    def total_count(self) -> int:
        """All-time observation count, across every retained window."""
        return self._count

    @property
    def window_count(self) -> int:
        """Observations inside the retained window."""
        return len(self._samples)

    @property
    def total(self) -> float:
        """All-time sum of observations (Prometheus summary ``_sum``)."""
        return self._total

    def mean(self) -> float:
        """Mean over the retained window — same window as percentiles."""
        if not self._samples:
            return 0.0
        return self._window_total / len(self._samples)

    def percentile(self, p: float) -> float:
        """p in [0, 100], nearest-rank over the retained window."""
        if not self._samples:
            return 0.0
        xs = sorted(self._samples)
        idx = min(len(xs) - 1, max(0, int(round(p / 100.0 * (len(xs) - 1)))))
        return xs[idx]

    def snapshot(self, suffix: str = "_s") -> dict:
        """Windowed stats under unified keys: ``count`` (windowed),
        ``total_count`` (all-time), ``mean``/``p50``/``p95``/``p99`` with
        ``suffix`` appended (``"_s"`` for latencies, ``""`` for unitless
        reservoirs like prefix-hit token counts)."""
        out = {"count": len(self._samples), "total_count": self._count,
               f"mean{suffix}": self.mean()}
        for p in (50, 95, 99):
            out[f"p{p}{suffix}"] = self.percentile(p)
        return out

    def quantiles(self, qs: Sequence[float] = (0.5, 0.95, 0.99)) -> dict:
        """{q: value} for Prometheus summary export."""
        return {q: self.percentile(100.0 * q) for q in qs}


_COUNTERS = (
    "submitted", "admitted", "completed", "cancelled", "timeouts",
    "rejected_queue_full", "rejected_invalid", "rejected_draining",
    "prefills", "prefill_chunks", "decode_iterations", "decode_tokens",
    # decode-step routing.  paged_steps: decode steps whose attention
    # read the KV pool through the block tables inside the paged kernel
    # (models/model.py:forward_cached_paged) — on a TPU every decode
    # step of a non-speculating engine should land here, not in
    # fallback_steps (the gather route, and every verify step).
    "fallback_steps", "paged_steps",
    # decode/verify iterations in which at least one slot sampled, so the
    # step's ``cond`` took the sampler's branch (engine.py:_sample_slots:
    # one ordering of the vocabulary, the nucleus, the draw).  All-greedy
    # traffic must leave it 0; sampled_steps over the route counters' sum
    # is the share of steps that pay for sampling.
    "sampled_steps",
    # automatic prefix caching (serving/prefix_cache.py): admissions that
    # reused cached shared-prefix K/V vs prefilled cold, and blocks LRU-
    # evicted under the prefix_cache_blocks budget.  A workload expected
    # to share system prompts but showing prefix_misses climbing means
    # prompts diverge inside the first block (check block alignment).
    "prefix_hits", "prefix_misses", "prefix_evicted_blocks",
    # paged KV cache (serving/block_pool.py): copy-on-write block copies.
    # Normal engine flow never COWs (appends always target exclusively
    # owned blocks); anything nonzero on a pure prefix-hit workload means
    # zero-copy sharing broke (tests/serving/test_prefix_cache.py).
    "cow_copies_total",
    # whole-prompt admissions that found a free slot and too few free
    # pool blocks for the request's worst case (serving/engine.py:
    # _prefill_into_slot parks it at the queue's head): requests that
    # parked, once each, and the seconds from a request's first parking
    # to its admission.  Nonzero under a pool set smaller than its
    # slots' worst case (kv_pool_blocks): the price of that setting.
    "admissions_parked", "admission_parked_seconds_total",
    # speculative decoding (serving/engine.py): draft tokens proposed by
    # the host n-gram drafter vs draft tokens the batched verify step
    # accepted, plus verify iterations run.  The acceptance ratio is the
    # whole economics of speculation — on incompressible traffic it
    # collapses toward zero and the per-slot EWMA policy stops drafting,
    # so spec_steps flat-lining while decode_iterations climbs is the
    # policy working, not a bug.
    "spec_proposed", "spec_accepted", "spec_steps",
    # multi-tenant LoRA (serving/adapters/): admissions whose adapter was
    # already arena-resident vs installed cold, unpinned adapters evicted
    # under the adapter_cache_slots budget, and arena column installs.
    # A steady workload showing adapter_misses climbing means the live
    # adapter set exceeds the arena (raise adapter_cache_slots).
    "adapter_hits", "adapter_misses", "adapter_evictions",
    "adapter_installs",
    # live base-weight swap (engine.swap_params): completed swaps
    "param_swaps",
    # disaggregated prefill/decode (serving/cluster/): KV-block shipments
    # this engine exported (prefill handoffs + migrations out) and
    # adopted (installs in).  On a prefill-role replica ships_out
    # tracking prefills is the disaggregation working; a persistent gap
    # between a cluster's summed ships_out and ships_in means shipments
    # are falling back to local decode (check router ship_failed events).
    # ship_failures_total counts this engine's own fallbacks: KV exports
    # that failed before moving anything plus handoffs the router could
    # not place (both decode locally — availability cost, never a
    # correctness one).
    "ships_out_total", "ships_in_total", "ship_failures_total",
    # tiered KV (serving/block_pool.py:HostKVTier): blocks swapped between
    # the device pool and the host-RAM tier, total bytes moved both ways,
    # low-priority decodes suspended to host (preemptions) and resumed,
    # and prefix-cache trie entries promoted back from host on a hit.
    # swap_out climbing with swap_in flat means the host tier is filling
    # without paying off (demoted prefixes never re-hit — shrink
    # host_kv_blocks); preemptions without resumes means starvation
    # (check priority spread vs pool size).
    "swap_out_blocks_total", "swap_in_blocks_total", "swap_bytes_total",
    "preemptions_total", "resumes_total", "prefix_promotions_total",
)

# (attribute, prometheus family name, help) for the latency reservoirs
_PROM_SUMMARIES = (
    ("ttft", "serving_ttft_seconds", "time to first token"),
    ("per_token", "serving_per_token_latency_seconds",
     "per-token decode latency (one sample per token per iteration)"),
    ("e2e", "serving_e2e_latency_seconds", "request end-to-end latency"),
    ("device_step", "serving_device_step_seconds",
     "decode dispatch to tokens-on-host"),
    ("sched_host", "serving_sched_host_seconds",
     "scheduler host bookkeeping per iteration"),
    ("prefix_hit_tokens", "serving_prefix_hit_tokens",
     "tokens per admission served from the prefix cache"),
    ("accepted_per_step", "serving_accepted_tokens_per_step",
     "tokens committed per participating slot per speculative verify step"),
    ("resume_latency", "serving_resume_latency_seconds",
     "preempted-decode resume latency (host swap-in to decodable)"),
)


class ServingMetrics:
    """Thread-safe serving counter/gauge/histogram registry.

    Unless ``register=False``, the instance installs itself as the
    ``"serving"`` collector of ``obs.REGISTRY`` — replacing any previous
    instance, so the newest engine's metrics are the ones scraped."""

    def __init__(self, num_slots: int = 0,
                 slo: Optional[SLOConfig] = None, register: bool = True):
        self._lock = make_lock("serving.metrics")
        self.counters = {name: 0 for name in _COUNTERS}
        self.num_slots = num_slots
        self.slots_active = 0
        self.queue_depth = 0
        # largest number of requests that shared one decode iteration —
        # >= 2 is the proof of true continuous batching (not serialized)
        self.max_decode_batch = 0
        self.ttft = LatencyHistogram()
        self.per_token = LatencyHistogram()
        self.e2e = LatencyHistogram()
        # device-vs-host breakdown (engine._step): where a decode
        # iteration's wall time actually goes.  device_step = dispatch ->
        # tokens on host; sched_host = Python bookkeeping per iteration;
        # device_idle_frac = EWMA of the fraction of inter-dispatch wall
        # time the device sat idle waiting on the host (~0 when the
        # pipelined scheduler keeps a step in flight — the direct evidence
        # that host overhead is overlapped, not inferred from tok/s).
        self.device_step = LatencyHistogram()
        self.sched_host = LatencyHistogram()
        self.device_idle_frac: Optional[float] = None
        # tokens served from the prefix cache per hit (the reservoir is
        # generic; samples here are token counts, not seconds)
        self.prefix_hit_tokens = LatencyHistogram()
        self.prefix_blocks = 0   # gauge: blocks resident in the cache
        # multi-tenant LoRA arena gauges (serving/adapters/registry.py)
        self.adapter_resident = 0
        self.adapter_resident_bytes = 0
        # tokens committed per participating slot per speculative verify
        # step (accepted draft prefix + the bonus token; samples are
        # token counts, not seconds)
        self.accepted_per_step = LatencyHistogram()
        # paged KV pool gauges (engine._update_pool_gauges): free/used
        # block counts and the allocated-token / pool-token fraction
        self.blocks_free = 0
        self.blocks_used = 0
        self.kv_cache_util = 0.0
        # tiered KV: host-RAM tier occupancy gauges and the preempted-
        # decode resume latency reservoir (engine._resume_suspended)
        self.host_blocks_used = 0
        self.host_blocks_free = 0
        self.resume_latency = LatencyHistogram()
        # hybrid stacks (serving/slots.py): bytes of per-slot recurrent
        # and convolution state beside the pool, by the block kind that
        # keeps it ("linear", "mamba", "ssm1", "window": a ring of keys
        # and values), and the slots it is for
        self.rec_state_bytes: dict = {}
        self.rec_state_slots = 0
        # bytes of the paged pool by the kind of row it holds: "kv" (keys
        # and values a KV head) or "latent" (one row a position, latent
        # attention's)
        self.kv_pool_bytes: dict = {}
        # positions the state-space (Mamba-2) layers advanced their
        # states over, by phase: a prompt's length at its prefill, the
        # live slots of every decode step
        self.ssm_positions = {"prefill": 0, "decode": 0}
        # cached positions the decode steps' paged walks read, by the
        # kind of layer that walked them: "full" (the layer whose keys
        # and values the pool holds) and "cross" (a layer that reads
        # another's): live positions x readers.  Counted where some layer
        # reads a cache it does not write.
        self.kv_walks: dict = {}
        # per-layer, per-expert assignment counts, carried on the device
        # in the step's own state: ``expert_load`` (set by the engine)
        # fetches them → (counts [layers, router outputs], first held
        # expert, held experts), and is called only when somebody asks
        # (``snapshot``, ``collect``), never by a step
        self.expert_load = None
        # the layers that route (a hybrid stack's mixer-alone layers do
        # not): the rows of ``expert_load``'s counts that are reported
        self.expert_layers: tuple = ()
        # ``expert_rows`` likewise: the rows the experts' kernel
        # multiplied and skipped
        self.expert_rows = None
        # paged/fallback decode iterations keyed by the weight precision
        # route (ops/quant.py:precision_route: fp32/int8/int4/mixed)
        self.step_routes: dict = {}
        # speculative counters broken down by where the draft came from
        # ("ngram" = host prompt-lookup, "model" = resident draft model
        # proposing trees) — the source label is how a run shows
        # the resident draft carrying random traffic that PLD cannot
        self.spec_by_source: dict = {}
        # per-slot acceptance EWMA gauges (the value the engine's budget
        # controller actually steers on), refreshed every verify step
        self.slot_spec_ewma: dict = {}
        self.timers = Timers(log_level=2)
        self.slo = SLOTracker(slo or SLOConfig())
        if register:
            REGISTRY.register_collector("serving", self.collect)

    def inc(self, name: str, by: int = 1) -> None:
        with self._lock:
            self.counters[name] += by

    def inc_step(self, route: str, precision: str = "fp32",
                 sampling: bool = False) -> None:
        """One decode/verify iteration by the ``route`` it took —
        ``"paged"`` or ``"fallback"``: bumps the aggregate
        ``<route>_steps`` counter AND its per-precision breakdown
        (``precision`` from ops/quant.py:precision_route), and
        ``sampled_steps`` where a slot of it ``sampling``."""
        with self._lock:
            self.counters[f"{route}_steps"] += 1
            self.counters["sampled_steps"] += bool(sampling)
            r = self.step_routes.setdefault(
                precision, {"paged": 0, "fallback": 0})
            r[route] += 1

    def add_ssm_positions(self, phase: str, n: int) -> None:
        with self._lock:
            self.ssm_positions[phase] += n

    def add_kv_walks(self, positions: int, readers: dict) -> None:
        """A decode step over ``positions`` cached positions in all,
        walked by ``readers[layer kind]`` layers of each kind."""
        with self._lock:
            for kind, n in readers.items():
                self.kv_walks[kind] = (self.kv_walks.get(kind, 0)
                                       + positions * n)

    def set_gauges(self, *, slots_active: Optional[int] = None,
                   queue_depth: Optional[int] = None,
                   prefix_blocks: Optional[int] = None,
                   blocks_free: Optional[int] = None,
                   blocks_used: Optional[int] = None,
                   kv_cache_util: Optional[float] = None,
                   num_slots: Optional[int] = None,
                   adapter_resident: Optional[int] = None,
                   adapter_resident_bytes: Optional[int] = None,
                   host_blocks_used: Optional[int] = None,
                   host_blocks_free: Optional[int] = None,
                   rec_state_bytes: Optional[dict] = None,
                   rec_state_slots: Optional[int] = None,
                   kv_pool_bytes: Optional[dict] = None) -> None:
        with self._lock:
            if kv_pool_bytes is not None:
                self.kv_pool_bytes = kv_pool_bytes
            if rec_state_bytes is not None:
                self.rec_state_bytes = rec_state_bytes
            if rec_state_slots is not None:
                self.rec_state_slots = rec_state_slots
            if num_slots is not None:
                self.num_slots = num_slots
            if slots_active is not None:
                self.slots_active = slots_active
            if queue_depth is not None:
                self.queue_depth = queue_depth
            if prefix_blocks is not None:
                self.prefix_blocks = prefix_blocks
            if blocks_free is not None:
                self.blocks_free = blocks_free
            if blocks_used is not None:
                self.blocks_used = blocks_used
            if kv_cache_util is not None:
                self.kv_cache_util = kv_cache_util
            if adapter_resident is not None:
                self.adapter_resident = adapter_resident
            if adapter_resident_bytes is not None:
                self.adapter_resident_bytes = adapter_resident_bytes
            if host_blocks_used is not None:
                self.host_blocks_used = host_blocks_used
            if host_blocks_free is not None:
                self.host_blocks_free = host_blocks_free

    def observe_decode_iteration(self, batch: int, seconds: float) -> None:
        """One scheduler decode step over ``batch`` active slots."""
        with self._lock:
            self.counters["decode_iterations"] += 1
            self.counters["decode_tokens"] += batch
            self.max_decode_batch = max(self.max_decode_batch, batch)
            for _ in range(batch):
                self.per_token.observe(seconds)
        self.slo.record_itl(seconds, n=batch)

    def observe_step_breakdown(self, *, device_s: Optional[float] = None,
                               host_s: Optional[float] = None,
                               gap_frac: Optional[float] = None) -> None:
        """Per-iteration device/host split from the engine's step loop."""
        with self._lock:
            if device_s is not None:
                self.device_step.observe(device_s)
            if host_s is not None:
                self.sched_host.observe(host_s)
            if gap_frac is not None:
                gap_frac = min(1.0, max(0.0, gap_frac))
                self.device_idle_frac = (
                    gap_frac if self.device_idle_frac is None
                    else 0.9 * self.device_idle_frac + 0.1 * gap_frac)

    def observe_prefix_hit_tokens(self, tokens: int) -> None:
        """Tokens whose prefill one prefix-cache hit skipped."""
        with self._lock:
            self.prefix_hit_tokens.observe(float(tokens))

    def observe_spec_step(self, proposed: int, accepted: int,
                          committed: Sequence[int],
                          source: str = "ngram",
                          slot_ewmas: Optional[dict] = None) -> None:
        """One speculative verify step: ``proposed`` draft tokens across
        the batch, ``accepted`` of them confirmed against greedy decode,
        ``committed`` tokens landed per participating slot (the accepted
        prefix plus the bonus token, truncated by EOS/budget).
        ``source`` labels who drafted ("ngram" host prompt-lookup,
        "model" resident draft model); ``slot_ewmas`` refreshes the
        per-slot acceptance-EWMA gauges (slot -> ewma)."""
        with self._lock:
            self.counters["spec_steps"] += 1
            self.counters["spec_proposed"] += proposed
            self.counters["spec_accepted"] += accepted
            src = self.spec_by_source.setdefault(
                source, {"steps": 0, "proposed": 0, "accepted": 0})
            src["steps"] += 1
            src["proposed"] += proposed
            src["accepted"] += accepted
            if slot_ewmas:
                self.slot_spec_ewma.update(slot_ewmas)
            for n in committed:
                self.accepted_per_step.observe(float(n))

    def observe_ttft(self, seconds: float) -> None:
        with self._lock:
            self.ttft.observe(seconds)
        self.slo.record_ttft(seconds)

    def observe_e2e(self, seconds: float) -> None:
        with self._lock:
            self.e2e.observe(seconds)

    def observe_resume(self, seconds: float) -> None:
        """Preempted-decode resume latency: host swap-in start to the
        request being decodable again (tiered KV)."""
        with self._lock:
            self.resume_latency.observe(seconds)

    def observe_finish(self, ok: bool) -> None:
        """Request retired; ``ok`` False on timeout/error (availability)."""
        self.slo.record_request(ok)

    def snapshot(self) -> dict:
        """Point-in-time dict of every counter, gauge, and histogram."""
        with self._lock:
            out = dict(self.counters)
            out.update({
                "running": self.slots_active,
                "queued": self.queue_depth,
                "slots_total": self.num_slots,
                "slot_occupancy": (self.slots_active / self.num_slots
                                   if self.num_slots else 0.0),
                "max_decode_batch": self.max_decode_batch,
                "ttft": self.ttft.snapshot(),
                "per_token_latency": self.per_token.snapshot(),
                "e2e_latency": self.e2e.snapshot(),
                "device_step_time": self.device_step.snapshot(),
                "sched_host_time": self.sched_host.snapshot(),
                "device_idle_frac": (self.device_idle_frac
                                     if self.device_idle_frac is not None
                                     else 0.0),
                # prefix cache (the histogram samples are token counts,
                # hence the unitless suffix)
                "prefix_hit_rate": (
                    self.counters["prefix_hits"]
                    / max(1, self.counters["prefix_hits"]
                          + self.counters["prefix_misses"])),
                "prefix_blocks": self.prefix_blocks,
                "prefix_hit_tokens": self.prefix_hit_tokens.snapshot(
                    suffix=""),
                # multi-tenant LoRA arena residency
                "adapter_hit_rate": (
                    self.counters["adapter_hits"]
                    / max(1, self.counters["adapter_hits"]
                          + self.counters["adapter_misses"])),
                "adapter_resident": self.adapter_resident,
                "adapter_resident_bytes": self.adapter_resident_bytes,
                # paged KV pool occupancy
                "blocks_free": self.blocks_free,
                "blocks_used": self.blocks_used,
                "kv_cache_util": self.kv_cache_util,
                # tiered KV host-RAM tier
                "host_blocks_used": self.host_blocks_used,
                "host_blocks_free": self.host_blocks_free,
                "resume_latency": self.resume_latency.snapshot(),
                # hybrid stacks: per-slot recurrent state beside the pool
                "rec_state_bytes": sum(self.rec_state_bytes.values()),
                "rec_state_bytes_by_kind": dict(self.rec_state_bytes),
                "rec_state_slots": self.rec_state_slots,
                "kv_pool_bytes_by_kind": dict(self.kv_pool_bytes),
                "ssm_positions": dict(self.ssm_positions),
                "kv_walks": dict(self.kv_walks),
                # speculative decoding (histogram samples are token
                # counts per participating slot per verify step)
                "spec_acceptance_rate": (
                    self.counters["spec_accepted"]
                    / max(1, self.counters["spec_proposed"])),
                # per-source breakdown (spec_draft_source label):
                # "ngram" prompt-lookup vs "model" resident draft
                "spec_by_source": {
                    source: dict(src)
                    for source, src in sorted(self.spec_by_source.items())},
                # per-slot acceptance EWMA (the budget controller input)
                "slot_spec_ewma": {
                    str(slot): ewma
                    for slot, ewma in sorted(self.slot_spec_ewma.items())},
                "accepted_tokens_per_step":
                    self.accepted_per_step.snapshot(suffix=""),
                # decode-step routing by weight precision (inc_step)
                "fallback_steps_by_precision": {
                    route: r["fallback"]
                    for route, r in sorted(self.step_routes.items())},
                "paged_steps_by_precision": {
                    route: r["paged"]
                    for route, r in sorted(self.step_routes.items())},
            })
        out["slo"] = self.slo.snapshot()
        load = self._held_expert_load()
        if load is not None:
            counts, lo, held = load
            here = counts[list(self.expert_layers),
                          lo:lo + held].astype(float)
            out["expert_load"] = {
                "assignments": int(counts.sum()),
                "held_share": float(here.sum() / max(1, counts.sum())),
                "max_over_mean_by_layer": [
                    float(row.max() / row.mean()) if row.sum() else 0.0
                    for row in here],
                "rows": self.expert_rows()}
        return out

    def _held_expert_load(self):
        """``expert_load()`` outside the lock (it may wait for the
        scheduler thread), or None where no engine counts experts."""
        return self.expert_load() if self.expert_load is not None else None

    def collect(self) -> List[MetricFamily]:
        """obs.REGISTRY collector: every counter, gauge, and reservoir
        summary under ``serving_*`` names, plus SLO burn-rate gauges."""
        fams: List[MetricFamily] = []
        with self._lock:
            for name in _COUNTERS:
                # counters already carrying the Prometheus "_total" suffix
                # (cow_copies_total) must not have it doubled
                pname = (f"serving_{name}" if name.endswith("_total")
                         else f"serving_{name}_total")
                fams.append(MetricFamily(
                    pname, "counter",
                    f"serving lifecycle counter: {name}").add(
                        self.counters[name]))
            if self.step_routes:
                fb_fam = MetricFamily(
                    "serving_fallback_steps_by_precision_total", "counter",
                    "gather-route decode and verify iterations by weight "
                    "precision route")
                paged_fam = MetricFamily(
                    "serving_paged_steps_by_precision_total", "counter",
                    "decode iterations through the paged attention "
                    "kernel by weight precision route")
                for route, r in sorted(self.step_routes.items()):
                    fb_fam.add(r["fallback"], labels={"precision": route})
                    paged_fam.add(r["paged"], labels={"precision": route})
                fams.extend([fb_fam, paged_fam])
            if self.spec_by_source:
                by_src = {
                    "steps": MetricFamily(
                        "serving_spec_steps_by_source_total", "counter",
                        "speculative verify steps by draft source"),
                    "proposed": MetricFamily(
                        "serving_spec_proposed_by_source_total", "counter",
                        "speculative draft tokens proposed by draft source"),
                    "accepted": MetricFamily(
                        "serving_spec_accepted_by_source_total", "counter",
                        "speculative draft tokens accepted by draft source"),
                }
                for source, src in sorted(self.spec_by_source.items()):
                    for key, fam in by_src.items():
                        fam.add(src[key],
                                labels={"spec_draft_source": source})
                fams.extend(by_src.values())
            if self.slot_spec_ewma:
                ewma_fam = MetricFamily(
                    "serving_spec_slot_ewma", "gauge",
                    "per-slot speculative acceptance EWMA (budget "
                    "controller input)")
                for slot, ewma in sorted(self.slot_spec_ewma.items()):
                    ewma_fam.add(ewma, labels={"slot": str(slot)})
                fams.append(ewma_fam)
            hits = self.counters["prefix_hits"]
            misses = self.counters["prefix_misses"]
            for gname, help_, value in (
                    ("serving_slots_active", "slots currently decoding",
                     self.slots_active),
                    ("serving_slots_total", "configured KV slots",
                     self.num_slots),
                    ("serving_queue_depth", "requests waiting for a slot",
                     self.queue_depth),
                    ("serving_max_decode_batch",
                     "largest decode batch observed", self.max_decode_batch),
                    ("serving_device_idle_frac",
                     "EWMA fraction of step wall time the device sat idle",
                     self.device_idle_frac or 0.0),
                    ("serving_prefix_blocks",
                     "K/V blocks resident in the prefix cache",
                     self.prefix_blocks),
                    ("serving_prefix_hit_rate",
                     "prefix-cache admission hit rate",
                     hits / max(1, hits + misses)),
                    ("serving_adapter_resident",
                     "LoRA adapters resident in the arena",
                     self.adapter_resident),
                    ("serving_adapter_resident_bytes",
                     "fp32 factor bytes resident in the LoRA arena",
                     self.adapter_resident_bytes),
                    ("serving_adapter_hit_rate",
                     "adapter-cache admission hit rate",
                     self.counters["adapter_hits"]
                     / max(1, self.counters["adapter_hits"]
                           + self.counters["adapter_misses"])),
                    ("serving_blocks_free",
                     "KV pool blocks on the free list", self.blocks_free),
                    ("serving_blocks_used",
                     "KV pool blocks allocated to slots or the prefix cache",
                     self.blocks_used),
                    ("serving_kv_cache_util",
                     "allocated-token fraction of the KV pool",
                     self.kv_cache_util),
                    ("serving_host_blocks_used",
                     "host-RAM tier KV blocks in use", self.host_blocks_used),
                    ("serving_host_blocks_free",
                     "host-RAM tier KV blocks free", self.host_blocks_free),
                    ("serving_rec_state_slots",
                     "slots that keep a recurrent state beside their KV",
                     self.rec_state_slots),
                    ("serving_spec_acceptance_rate",
                     "speculative draft tokens accepted / proposed",
                     self.counters["spec_accepted"]
                     / max(1, self.counters["spec_proposed"]))):
                fams.append(MetricFamily(gname, "gauge", help_).add(value))
            fam = MetricFamily(
                "serving_rec_state_bytes", "gauge",
                "bytes of per-slot recurrent and convolution state, by "
                "the block kind that keeps it (a one-kind stack: 0)")
            for kind, n in sorted(self.rec_state_bytes.items()):
                fam.add(n, labels={"kind": kind})
            fams.append(fam if self.rec_state_bytes else fam.add(0))
            fam = MetricFamily(
                "serving_kv_pool_bytes", "gauge",
                "bytes of the paged pool, by the kind of row it holds "
                "(kv: keys and values a KV head; latent: one row a "
                "position)")
            for kind, n in sorted(self.kv_pool_bytes.items()):
                fam.add(n, labels={"kind": kind})
            fams.append(fam if self.kv_pool_bytes else fam.add(0))
            if self.kv_walks:
                fam = MetricFamily(
                    "serving_kv_walks_total", "counter",
                    "cached positions the decode steps' paged walks read, "
                    "by the kind of layer that walked them (live "
                    "positions x readers)")
                for kind, n in sorted(self.kv_walks.items()):
                    fam.add(n, labels={"layer_kind": kind})
                fams.append(fam)
            if {"mamba", "ssm1"} & set(self.rec_state_bytes):
                fam = MetricFamily(
                    "serving_ssm_positions_total", "counter",
                    "positions the state-space layers advanced their "
                    "states over, by phase")
                for phase, n in sorted(self.ssm_positions.items()):
                    fam.add(n, labels={"phase": phase})
                fams.append(fam)
            for attr, pname, help_ in _PROM_SUMMARIES:
                hist: LatencyHistogram = getattr(self, attr)
                fams.append(summary_family(
                    pname, help_, count=hist.total_count, total=hist.total,
                    quantiles=hist.quantiles()))
        fams.extend(self.slo.collect(prefix="serving_slo"))
        load = self._held_expert_load()
        if load is not None:
            counts, lo, held = load
            fam = MetricFamily(
                "serving_expert_assignments_total", "counter",
                "times a layer's router chose an expert (held: this "
                "engine's parameter tree holds it)")
            for layer in self.expert_layers:
                for e, n in enumerate(counts[layer]):
                    fam.add(float(n), labels={
                        "layer": str(layer), "expert": str(e),
                        "held": str(int(lo <= e < lo + held))})
            fams.append(fam)
            fam = MetricFamily(
                "serving_expert_rows_total", "counter",
                "(token, choice) rows handed to the experts' kernel, by "
                "outcome (multiplied: a held expert's; skipped)")
            for outcome, n in self.expert_rows().items():
                fam.add(float(n), labels={"outcome": outcome})
            fams.append(fam)
        return fams

    def write(self, writer, iteration: int,
              names: Optional[Sequence[str]] = None) -> None:
        """Export scalars to a tensorboard-style writer (``add_scalar``),
        mirroring utils/timers.py:Timers.write."""
        snap = self.snapshot()
        for name in (names or _COUNTERS):
            writer.add_scalar(f"serving/{name}", snap[name], iteration)
        writer.add_scalar("serving/running", snap["running"], iteration)
        writer.add_scalar("serving/queued", snap["queued"], iteration)
        writer.add_scalar("serving/slot_occupancy", snap["slot_occupancy"],
                          iteration)
        writer.add_scalar("serving/max_decode_batch",
                          snap["max_decode_batch"], iteration)
        writer.add_scalar("serving/device_idle_frac",
                          snap["device_idle_frac"], iteration)
        writer.add_scalar("serving/prefix_hit_rate",
                          snap["prefix_hit_rate"], iteration)
        writer.add_scalar("serving/prefix_blocks",
                          snap["prefix_blocks"], iteration)
        writer.add_scalar("serving/blocks_free", snap["blocks_free"],
                          iteration)
        writer.add_scalar("serving/blocks_used", snap["blocks_used"],
                          iteration)
        writer.add_scalar("serving/kv_cache_util", snap["kv_cache_util"],
                          iteration)
        writer.add_scalar("serving/prefix_hit_tokens_mean",
                          snap["prefix_hit_tokens"]["mean"], iteration)
        writer.add_scalar("serving/spec_acceptance_rate",
                          snap["spec_acceptance_rate"], iteration)
        writer.add_scalar("serving/accepted_tokens_per_step_mean",
                          snap["accepted_tokens_per_step"]["mean"],
                          iteration)
        for hist, key in ((self.ttft, "ttft"),
                          (self.per_token, "per_token_latency"),
                          (self.e2e, "e2e_latency"),
                          (self.device_step, "device_step_time"),
                          (self.sched_host, "sched_host_time")):
            writer.add_scalar(f"serving/{key}_mean_s", hist.mean(), iteration)
            writer.add_scalar(f"serving/{key}_p95_s", hist.percentile(95),
                              iteration)
            writer.add_scalar(f"serving/{key}_p99_s", hist.percentile(99),
                              iteration)
        self.timers.write(writer, iteration)
