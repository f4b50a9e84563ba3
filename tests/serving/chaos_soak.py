"""The compound-fault chaos soak that
``test_selfheal.py::test_chaos_soak_compound_faults`` asserts on: a test
helper, not part of the package (docs/robustness.md, "Cluster
self-healing")."""

import dataclasses
import time

import jax
import numpy as np

from megatron_llm_tpu.analysis.sanitizers import DeliveryLedger
from megatron_llm_tpu.config import ParallelConfig
from megatron_llm_tpu.ops.lora import init_lora_adapter
from megatron_llm_tpu.resilience.chaos import chaos
from megatron_llm_tpu.serving.adapters.registry import AdapterRegistry
from megatron_llm_tpu.serving.cluster import build_cluster
from megatron_llm_tpu.serving.cluster.router import RouterConfig
from megatron_llm_tpu.serving.cluster.supervisor import (
    ReplicaSupervisor,
    SupervisorConfig,
)
from megatron_llm_tpu.serving.engine import EngineConfig


def run_chaos_soak(cfg, params, *, num_requests: int = 64,
                   gen_len: int = 12, slots: int = 4,
                   max_prompt_len: int = 48, replicas: int = 3,
                   n_adapters: int = 2, rank: int = 4,
                   draft_len: int = 2, hang_timeout_s: float = 2.0,
                   hang_s: float = 6.0, seed: int = 0) -> dict:
    """Compound-fault chaos soak (docs/robustness.md, "Cluster
    self-healing"): mixed traffic — speculative greedy, multi-tenant
    LoRA, shared-prefix hits, a live migration — through a supervised
    ``replicas``-wide cluster while a randomized storm of cluster-grade
    faults plays out underneath:

    - a **scheduler-step crash** (``chaos crash_at("serve-step")``) —
      some replica dies raw mid-iteration;
    - a **wedged device dispatch** (``hang_at("serve-dispatch")``) —
      a live-but-stuck scheduler the hung-step watchdog must catch;
    - a **shipment export fault** (``fail_io("ship-export")``) under a
      live migration — the request must keep decoding at home.

    Every kill runs the full kill→rebuild→re-warm→rejoin cycle.  The
    returned dict carries the soak's verdicts — ``delivery_violations``
    (every accepted token delivered exactly once, per
    :class:`~megatron_llm_tpu.analysis.sanitizers.DeliveryLedger`), ``leaked_blocks``
    (ledger balance on every incarnation, live and dead), and
    ``ended_full_strength`` — alongside the fault/heal counters.  The
    chaos-marked soak test (tests/serving/test_selfheal.py) asserts on
    these.
    """
    rng = np.random.default_rng(seed)
    bucket = 16
    # mixed prompt population: ragged lengths, a shared-prefix family
    # (prefix-cache hits), greedy sampling throughout so draft_len > 0
    # engages n-gram speculation
    shared = rng.integers(1, cfg.vocab_size, bucket).tolist()
    prompts, adapter_ids = [], []
    ids = [f"tenant-{i}" for i in range(n_adapters)]
    for i in range(num_requests):
        n = int(rng.integers(8, max_prompt_len + 1))
        if i % 4 == 0:  # shared-prefix family
            p = shared + rng.integers(1, cfg.vocab_size,
                                      max(1, n - bucket)).tolist()
        else:
            p = rng.integers(1, cfg.vocab_size, n).tolist()
        prompts.append(p)
        adapter_ids.append(ids[i % n_adapters]
                           if n_adapters and i % 3 == 0 else None)

    registry = None
    if n_adapters:
        registry = AdapterRegistry(cfg, n_slots=max(2, n_adapters),
                                   rank=rank)
        for i, aid in enumerate(ids):
            ad = init_lora_adapter(cfg, jax.random.key(1000 + i), rank)
            registry.register(aid, dataclasses.replace(ad, factors={
                t: {"a": f["a"],
                    "b": jax.random.normal(jax.random.key(2000 + i),
                                           f["b"].shape,
                                           f["b"].dtype) * 0.02}
                for t, f in ad.factors.items()}))

    ec = EngineConfig(
        max_batch_size=slots,
        max_seq_len=min(max_prompt_len + gen_len,
                        cfg.max_position_embeddings),
        max_queue_size=2 * num_requests,
        prefill_bucket=bucket,
        prefill_chunk=bucket,
        prefix_cache_blocks=8,
        spec_draft_len=draft_len,
        sanitize=True,  # per-iteration ledger audit on every incarnation
    )
    # warm specs shaped like the traffic: the prefill bucket, the full
    # decode length (so n-gram speculation engages and the verify
    # executable compiles) and the adapter epilogue — rebuilt replicas
    # rejoin with their serving executables compiled, and the initial
    # warmup below runs the same specs so the serving window never pays
    # a compile (the watchdog's compile amnesty is the backstop, not
    # the plan)
    warm = [{"prompt": shared[:bucket], "max_new_tokens": gen_len,
             "use_eos_stop": False}]
    if n_adapters:
        warm.append({"prompt": shared[:bucket], "max_new_tokens": gen_len,
                     "use_eos_stop": False, "adapter_id": ids[0]})
    router = build_cluster(
        cfg, params, ec, replicas=replicas, parallel=ParallelConfig(),
        router_config=RouterConfig(probe_interval_s=0.02, max_resubmits=5,
                                   quarantine_after=2),
        adapters=registry)
    sup = ReplicaSupervisor(router, SupervisorConfig(
        interval_s=0.02, hang_timeout_s=hang_timeout_s,
        warm_specs=warm))
    ledger = DeliveryLedger()

    def heal(timeout: float = 300.0) -> bool:
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < timeout:
            if all(r.alive() and not r.dead for r in router.replicas):
                return True
            time.sleep(0.05)
        return False

    chaos().reset()
    waves = 4
    per_wave = num_requests // waves
    results: list = [None] * num_requests
    faults = {"crash": 0, "hang": 0, "ship_io": 0}
    try:
        router.start()
        # deterministic per-replica warm: every replica compiles every
        # serving executable (prefill bucket, spec decode, adapter
        # epilogue) before the storm starts.  The supervisor arms only
        # AFTER the warm — the watchdog's compile amnesty needs at
        # least one completed compile per scheduler thread before it
        # can excuse a compile-stalled iteration, so supervising a
        # stone-cold cluster with a sub-compile hang_timeout_s would
        # false-trip on the very first dispatch (docs/robustness.md).
        for r in router.replicas:
            sup._warm(r.engine)  # identical warm to a rebuild's
        sup.start()
        for w in range(waves):
            lo = w * per_wave
            hi = num_requests if w == waves - 1 else lo + per_wave
            handles = router.submit_many([
                dict(prompt=prompts[i], max_new_tokens=gen_len,
                     use_eos_stop=False, seed=i,
                     adapter_id=adapter_ids[i],
                     on_token=ledger.on_token(i))
                for i in range(lo, hi)])
            if w == 0:    # raw scheduler-step crash on whoever steps next
                chaos().crash_at("serve-step")
                faults["crash"] += 1
            elif w == 1:  # wedged dispatch: watchdog territory
                chaos().hang_at("serve-dispatch", seconds=hang_s)
                faults["hang"] += 1
            elif w == 2:  # shipment export fault under a live migration
                chaos().fail_io("ship-export")
                faults["ship_io"] += 1
                for h in handles:
                    if not h.done() and router.migrate_request(h):
                        break
            for i, h in zip(range(lo, hi), handles):
                results[i] = h.result(timeout=600)
            heal()  # full strength before the next wave (bounded wait)
        healed = heal()

        # -- verdicts -----------------------------------------------------
        finish = {}
        delivery_violations = 0
        for i, res in enumerate(results):
            finish[res.finish_reason] = finish.get(res.finish_reason,
                                                   0) + 1
            try:
                ledger.check(i, res.tokens, res.prompt_len,
                             exact=res.finish_reason not in
                             ("quarantined", "timeout"))
            except AssertionError:
                delivery_violations += 1
        generations = {r.id: r.generation for r in router.replicas}
        rebuilt = sup.rebuilt_total
        trips = sup.watchdog_trips_total
        quarantined = router.quarantined_total
        failovers = router.failovers_total
        fired = [s for _, s in chaos().events]
    finally:
        chaos().reset()
        router.shutdown()
    # ledger balance on every incarnation: the final engines report
    # leaks at shutdown, dead incarnations were archived by the
    # supervisor at kill time
    leaked = sum(len(r.engine.sanitizer_report) for r in router.replicas)
    leaked += sum(len(rep) for reps in sup.incarnation_reports.values()
                  for rep in reps)
    return {
        "serving_chaos_num_requests": num_requests,
        "serving_chaos_replicas": replicas,
        "serving_chaos_faults_injected": faults,
        "serving_chaos_fired": fired,
        "serving_chaos_finish_reasons": finish,
        "serving_chaos_failovers": failovers,
        "serving_chaos_quarantined": quarantined,
        "serving_chaos_replicas_rebuilt": rebuilt,
        "serving_chaos_watchdog_trips": trips,
        "serving_chaos_generations": generations,
        "serving_chaos_delivery_violations": delivery_violations,
        "serving_chaos_leaked_blocks": leaked,
        "serving_chaos_ended_full_strength": bool(healed),
    }


