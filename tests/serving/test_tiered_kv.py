"""Tiered KV: host-RAM block offload, decode preemption, prefix spill.

The acceptance bar for the tier (docs/serving.md, 'Tiered KV'):

* block contents round-trip the host arena **bitwise** — fp32 and int8
  ``{q, scale}`` pools alike — through the same fixed-arity export /
  import executables shipping uses (zero new compiled programs);
* a preempted decode resumes **bitwise**: fill arithmetic and the
  per-request RNG fold counter travel with the suspension, so the final
  token stream equals an uninterrupted run's;
* a prefix spilled to host and re-promoted on the next match serves the
  exact tokens a never-evicted hit serves;
* oversubscribed admission storms keep every ledger balanced — device
  pool AND host tier audited by the LedgerSanitizer each iteration;
* chaos faults at ``host-swap-out`` / ``host-swap-in`` lose nothing:
  a failed demote leaves the device copy decoding in place, a failed
  promote leaves the host copy resident for the re-fetch.
"""

import dataclasses
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_llm_tpu.analysis.sanitizers import no_recompiles
from megatron_llm_tpu.config import tiny_config
from megatron_llm_tpu.models import model as model_lib
from megatron_llm_tpu.obs import compile as obs_compile
from megatron_llm_tpu.resilience.chaos import chaos
from megatron_llm_tpu.serving import EngineConfig, ServingEngine
from megatron_llm_tpu.serving.block_pool import BlockPool, HostKVTier
from megatron_llm_tpu.serving.queue import RequestQueue
from tests.serving.one_shot import reference as _reference


@pytest.fixture(scope="module")
def tiny():
    cfg = tiny_config(num_layers=2, vocab_size=64,
                      make_vocab_size_divisible_by=8)
    params = model_lib.init_params(jax.random.key(0), cfg)
    return cfg, params


def _engine(cfg, params, **overrides):
    kw = dict(max_batch_size=4, max_seq_len=64, max_queue_size=16,
              idle_wait_s=0.005, kv_block_size=8)
    kw.update(overrides)
    return ServingEngine(cfg, params, EngineConfig(**kw))


def _prompt(cfg, n, seed):
    rng = np.random.default_rng(seed)
    return rng.integers(1, cfg.vocab_size, n).tolist()


# ---------------------------------------------------------------------------
# HostKVTier unit: bitwise round trip, ledger, bandwidth bound
# ---------------------------------------------------------------------------


def _patterned_pool(cfg, n_blocks, bk, bids):
    """A pool whose ``bids`` carry per-block recognizable contents."""
    pool = BlockPool(cfg, n_blocks, bk)

    def stamp(leaf):
        a = np.array(leaf)  # writable copy (np.asarray aliases on CPU)
        for bid in bids:
            fill = (np.arange(a[:, bid].size, dtype=np.float64)
                    % 97 + bid).reshape(a[:, bid].shape)
            a[:, bid] = fill.astype(a.dtype)
        return jnp.asarray(a)

    pool.k_pool = jax.tree.map(stamp, pool.k_pool)
    pool.v_pool = jax.tree.map(stamp, pool.v_pool)
    return pool


@pytest.mark.parametrize("quant", ["fp32", "int8"])
def test_host_tier_roundtrip_bitwise(quant):
    """demote -> pump -> promote restores the exact device bytes into
    fresh blocks, for fp32 and int8 ``{q, scale}`` pools alike."""
    cfg = tiny_config(num_layers=2, vocab_size=64,
                      make_vocab_size_divisible_by=8)
    if quant == "int8":
        cfg = dataclasses.replace(cfg, kv_cache_quant="int8")
    pool = _patterned_pool(cfg, 8, 4, bids=[1, 2, 3])
    before_k = jax.tree.map(lambda a: np.asarray(a).copy(), pool.k_pool)
    before_v = jax.tree.map(lambda a: np.asarray(a).copy(), pool.v_pool)
    tier = HostKVTier(pool, n_host_blocks=4, arity=4)

    pool.reserve(3)
    src = [pool.alloc_reserved() for _ in range(3)]
    assert sorted(src) == [1, 2, 3]
    hids = tier.begin_demote(src, owner="req-a")
    assert tier.in_flight == 1 and tier.host_used == 3
    for bid in src:
        pool.decref(bid)  # staged dense leaves own the bytes now
    assert tier.pump() == 1
    assert tier.in_flight == 0
    assert tier.bw_bytes_per_s > 0 and tier.bw_bytes_per_s != float("inf")

    pool.reserve(3)
    dst = [pool.alloc_reserved() for _ in range(3)]
    tier.promote(hids, dst)
    tier.free(hids)
    assert tier.host_used == 0 and tier.owners() == {}

    for before, after in ((before_k, pool.k_pool), (before_v, pool.v_pool)):
        for b, a in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
            for s, d in zip(src, dst):
                np.testing.assert_array_equal(np.asarray(a)[:, d], b[:, s])


def test_host_tier_ledger_and_bandwidth_bound():
    cfg = tiny_config(num_layers=2, vocab_size=64,
                      make_vocab_size_divisible_by=8)
    pool = BlockPool(cfg, 8, 4)
    tier = HostKVTier(pool, n_host_blocks=2, arity=4)
    assert tier.can_store(2) and not tier.can_store(3)
    assert tier.swap_ok()  # empty backlog always ok
    pool.reserve(2)
    bids = [pool.alloc_reserved(), pool.alloc_reserved()]
    hids = tier.begin_demote(bids, owner="r1")
    with pytest.raises(AssertionError):
        tier.free(hids)  # still in flight
    tier.pump()
    with pytest.raises(AssertionError):
        tier.begin_demote(bids, owner="r2")  # tier exhausted
    tier.free(hids)
    with pytest.raises(AssertionError):
        tier.free(hids)  # double free caught
    stats = tier.stats()
    assert stats["swap_out_blocks"] == 2 and stats["host_blocks_free"] == 2


def test_priority_queue_pop_order():
    """Highest class first, FIFO within a class, FIFO when untagged."""

    class R:
        def __init__(self, name, priority=0):
            self.name, self.priority = name, priority

    q = RequestQueue(max_size=8)
    q.put_many([R("a"), R("b", 2), R("c"), R("d", 2), R("e", 1)])
    assert [q.pop().name for _ in range(5)] == ["b", "d", "e", "a", "c"]
    assert q.pop() is None
    q.put_many([R("x"), R("y"), R("z")])  # all one class: plain FIFO
    assert [q.pop().name for _ in range(3)] == ["x", "y", "z"]


# ---------------------------------------------------------------------------
# Engine: bitwise preemption / resume, oversubscription, observability
# ---------------------------------------------------------------------------

# pool sized so the high-priority admission CANNOT reserve without
# suspending the low-priority decode: 6 usable blocks, victim reserves 4
_PREEMPT_KW = dict(max_batch_size=2, kv_pool_blocks=7, host_kv_blocks=8,
                   prefix_cache_blocks=0, sanitize=True)


def _run_preemption(engine, cfg):
    """Low-priority long decode + a high-priority arrival that must
    preempt it.  Returns (low_result, high_result, low_prompt, hi_prompt,
    low_max_new, hi_max_new)."""
    low_prompt, hi_prompt = _prompt(cfg, 17, 5), _prompt(cfg, 9, 6)
    low_new, hi_new = 12, 10
    started = threading.Event()
    h_low = engine.submit(low_prompt, max_new_tokens=low_new,
                          use_eos_stop=False, priority=0,
                          on_token=lambda t: started.set())
    assert started.wait(timeout=600), "low-priority decode never started"
    h_hi = engine.submit(hi_prompt, max_new_tokens=hi_new,
                         use_eos_stop=False, priority=1)
    r_hi = h_hi.result(timeout=600)
    r_low = h_low.result(timeout=600)
    return r_low, r_hi, low_prompt, hi_prompt, low_new, hi_new


@pytest.mark.parametrize("quant", ["fp32", "int8"])
def test_preempt_resume_bitwise(tiny, quant):
    """A suspended-and-resumed decode produces the exact token stream an
    uninterrupted run produces — KV rows round-trip the host arena
    verbatim and the RNG folds on (seed, count), not slot identity."""
    cfg, params = tiny
    if quant == "int8":
        cfg = dataclasses.replace(cfg, kv_cache_quant="int8")
        params = model_lib.init_params(jax.random.key(0), cfg)
    engine = _engine(cfg, params, **_PREEMPT_KW).start()
    try:
        r_low, r_hi, low_p, hi_p, low_n, hi_n = _run_preemption(engine, cfg)
        snap = engine.metrics.snapshot()
        assert snap["preemptions_total"] >= 1, snap
        assert snap["resumes_total"] >= 1, snap
        assert snap["swap_out_blocks_total"] >= 1
        assert snap["swap_in_blocks_total"] >= 1
        engine.drain(timeout=60)
        assert engine.sanitizer_report == []
    finally:
        engine.shutdown()
    assert engine._scheduler_error is None, engine._scheduler_error
    assert r_low.tokens == _reference(cfg, params, low_p, low_n)
    assert r_hi.tokens == _reference(cfg, params, hi_p, hi_n)


def test_preempt_resume_sampled_rng_carried(tiny):
    """Same bar for a SAMPLED low-priority request: the RNG fold counter
    rides through suspension, so the post-resume samples continue the
    stream a never-preempted run draws."""
    cfg, params = tiny
    low_prompt = _prompt(cfg, 17, 7)
    spec = dict(max_new_tokens=12, temperature=0.9, top_k=5, seed=11,
                use_eos_stop=False)
    # baseline: same sampled request, no competition, no preemption
    engine = _engine(cfg, params, **_PREEMPT_KW).start()
    try:
        baseline = engine.submit(low_prompt, **spec).result(timeout=600)
        assert engine.metrics.snapshot()["preemptions_total"] == 0
    finally:
        engine.shutdown()
    engine = _engine(cfg, params, **_PREEMPT_KW).start()
    try:
        started = threading.Event()
        h_low = engine.submit(low_prompt, priority=0,
                              on_token=lambda t: started.set(), **spec)
        assert started.wait(timeout=600)
        h_hi = engine.submit(_prompt(cfg, 9, 8), max_new_tokens=10,
                             use_eos_stop=False, priority=1)
        h_hi.result(timeout=600)
        preempted = h_low.result(timeout=600)
        assert engine.metrics.snapshot()["preemptions_total"] >= 1
        engine.drain(timeout=60)
        assert engine.sanitizer_report == []
    finally:
        engine.shutdown()
    assert engine._scheduler_error is None, engine._scheduler_error
    assert preempted.tokens == baseline.tokens


def test_oversubscribed_storm_ledgers_balanced(tiny):
    """Admission storm at 2x logical oversubscription under
    MEGATRON_SANITIZE semantics (EngineConfig.sanitize): mixed-priority
    traffic whose worst-case reservations exceed HBM by design.  Every
    request completes with its reference tokens, preemptions actually
    fire, and the drain report is clean — host-owned blocks included."""
    cfg, params = tiny
    # every request needs 4 of the 6 usable device blocks, so two can
    # never co-reside: each higher-class arrival MUST preempt the
    # running lower-class decode (18 host blocks hold several victims)
    engine = _engine(cfg, params, max_batch_size=2, kv_pool_blocks=7,
                     host_kv_blocks=18, prefix_cache_blocks=0,
                     sanitize=True).start()
    jobs = []  # (handle, prompt, max_new)
    try:
        for i in range(9):
            prompt = _prompt(cfg, 17, 100 + i)  # 17 + 14 -> 4 blocks
            h = engine.submit(prompt, max_new_tokens=14,
                              use_eos_stop=False, priority=i % 3)
            jobs.append((h, prompt, 14))
            time.sleep(0.01)  # stagger so decodes are live when the
            #                   next class arrives (preemption pressure)
        results = [h.result(timeout=600) for h, _, _ in jobs]
        snap = engine.metrics.snapshot()
        assert snap["preemptions_total"] >= 1, \
            "storm never exercised preemption; resize the pool"
        assert snap["resumes_total"] == snap["preemptions_total"]
        engine.drain(timeout=120)
        assert engine.sanitizer_report == []
        assert engine.host_tier.host_used == 0
        assert engine.host_tier.in_flight == 0
    finally:
        engine.shutdown()
    assert engine._scheduler_error is None, engine._scheduler_error
    for r, (_, prompt, max_new) in zip(results, jobs):
        assert r.finish_reason == "length"
        assert r.tokens == _reference(cfg, params, prompt, max_new)


# What a second preempt/resume cycle builds once more in a process that
# has not run one yet (ROADMAP S8): the first swap-in commits the pool to
# its device (``BlockPool.import_blocks`` puts the host rows on the pool's
# sharding), and these take a committed pool for the first time
# (``_decode_impl`` does so within the first cycle, after its swap-in).  A
# stall for whoever sets ``host_kv_blocks``; named here so that it can
# neither grow nor hide.  The repair (commit the pool where it is built) empties
# this set.
_BUILT_AGAIN_FOR_A_COMMITTED_POOL = frozenset({
    "jit(_insert_plain)", "jit(_export_gather)",
    "jit(_import_scatter_plain)", "jit(_merge_pending)"})


def test_tiered_zero_recompiles_after_warmup(tiny):
    """The tier adds no compiled programs: after one warmup
    preempt/resume cycle the second builds nothing but the programs
    named above, each once, and every later cycle runs on warm
    executables."""
    cfg, params = tiny
    compiles = obs_compile.install()
    engine = _engine(cfg, params, **_PREEMPT_KW).start()
    try:
        _run_preemption(engine, cfg)  # warm: prefill/decode/export/import
        assert engine.metrics.snapshot()["preemptions_total"] >= 1
        seq = compiles.seq
        _run_preemption(engine, cfg)
        _, again = compiles.executables_since(seq)
        assert set(again) <= _BUILT_AGAIN_FOR_A_COMMITTED_POOL, again
        assert set(again.values()) <= {1}, again
        with no_recompiles():
            r_low, r_hi, low_p, hi_p, low_n, hi_n = \
                _run_preemption(engine, cfg)
    finally:
        engine.shutdown()
    assert engine._scheduler_error is None, engine._scheduler_error
    assert r_low.tokens == _reference(cfg, params, low_p, low_n)
    assert r_hi.tokens == _reference(cfg, params, hi_p, hi_n)


def test_kv_snapshot_and_metrics_surface(tiny):
    """GET /kv and /metrics report the host tier: arena occupancy,
    per-request swapped-out counts while suspended, swap/preemption
    counters, resume-latency histogram, and the Prometheus gauges."""
    cfg, params = tiny
    engine = _engine(cfg, params, **_PREEMPT_KW).start()
    try:
        low_prompt = _prompt(cfg, 17, 9)
        started = threading.Event()
        h_low = engine.submit(low_prompt, max_new_tokens=30,
                              use_eos_stop=False, priority=0,
                              on_token=lambda t: started.set())
        assert started.wait(timeout=600)
        h_hi = engine.submit(_prompt(cfg, 9, 10), max_new_tokens=10,
                             use_eos_stop=False, priority=1)
        # while the high-priority decode runs, the low one is suspended:
        # the snapshot must name it with its host-resident block count
        seen_suspended = {}
        deadline = time.monotonic() + 600
        while not seen_suspended and time.monotonic() < deadline:
            host = engine.kv_snapshot().get("host_tier") or {}
            seen_suspended = dict(host.get("suspended", {}))
            time.sleep(0.002)
        h_hi.result(timeout=600)
        h_low.result(timeout=600)
        assert seen_suspended, "suspended request never surfaced in /kv"
        info = seen_suspended[h_low.rid]
        assert info["blocks"] >= 1 and info["priority"] == 0

        snap = engine.kv_snapshot()
        host = snap["host_tier"]
        assert host["n_host_blocks"] == 8
        assert host["swap_out_blocks"] >= 1
        assert host["swap_bw_bytes_per_s"] > 0.0

        m = engine.metrics.snapshot()
        assert m["preemptions_total"] >= 1
        assert m["swap_bytes_total"] > 0
        assert m["resume_latency"]["count"] >= 1
        assert m["prefix_promotions_total"] == 0  # no cache configured
        assert "host_blocks_used" in m and "host_blocks_free" in m
        prom_names = {f.name for f in engine.metrics.collect()}
        assert "serving_host_blocks_used" in prom_names
        assert "serving_host_blocks_free" in prom_names
        assert "serving_swap_out_blocks_total" in prom_names
        assert "serving_preemptions_total" in prom_names
        assert "serving_resume_latency_seconds" in prom_names
    finally:
        engine.shutdown()
    assert engine._scheduler_error is None, engine._scheduler_error


# ---------------------------------------------------------------------------
# Prefix-cache spill -> promote
# ---------------------------------------------------------------------------


def test_prefix_spill_promote_hit_equals_never_evicted(tiny):
    """A prefix evicted under budget pressure spills to host and serves
    the NEXT identical prompt via promotion, token-for-token equal to a
    never-evicted hit — the effective prefix cache is RAM-sized."""
    cfg, params = tiny
    prompt_a = _prompt(cfg, 17, 21)  # 2 cached blocks at bk=8
    prompt_b = _prompt(cfg, 17, 22)
    max_new = 6
    kw = dict(max_batch_size=2, prefix_cache_blocks=2, host_kv_blocks=8,
              sanitize=True)

    # never-evicted baseline: A twice back to back, second is a pure hit
    engine = _engine(cfg, params, **kw).start()
    try:
        engine.submit(prompt_a, max_new_tokens=max_new,
                      use_eos_stop=False).result(timeout=600)
        never_evicted = engine.submit(prompt_a, max_new_tokens=max_new,
                                      use_eos_stop=False).result(timeout=600)
        assert engine.metrics.snapshot()["prefix_hits"] >= 1
    finally:
        engine.shutdown()

    engine = _engine(cfg, params, **kw).start()
    try:
        engine.submit(prompt_a, max_new_tokens=max_new,
                      use_eos_stop=False).result(timeout=600)
        # B's retirement offer overflows the 2-block budget: A's blocks
        # spill to the host tier instead of dropping
        engine.submit(prompt_b, max_new_tokens=max_new,
                      use_eos_stop=False).result(timeout=600)
        deadline = time.monotonic() + 600
        while (engine.prefix_cache.host_blocks < 1
               and time.monotonic() < deadline):
            time.sleep(0.002)
        assert engine.prefix_cache.host_blocks >= 1, "eviction never spilled"
        spilled_hit = engine.submit(prompt_a, max_new_tokens=max_new,
                                    use_eos_stop=False).result(timeout=600)
        snap = engine.metrics.snapshot()
        assert snap["prefix_promotions_total"] >= 1, snap
        assert snap["prefix_hits"] >= 1
        engine.drain(timeout=60)
        assert engine.sanitizer_report == []
    finally:
        engine.shutdown()
    assert engine._scheduler_error is None, engine._scheduler_error
    assert spilled_hit.tokens == never_evicted.tokens
    assert spilled_hit.tokens == _reference(cfg, params, prompt_a, max_new)


# ---------------------------------------------------------------------------
# Chaos: swap faults lose nothing
# ---------------------------------------------------------------------------


@pytest.mark.chaos
def test_chaos_swap_out_fault_keeps_device_copy(tiny):
    """host-swap-out armed: the demote fails BEFORE any state mutates,
    so the victim keeps decoding on device (no preemption) and both
    requests still finish with their reference tokens, ledgers clean."""
    cfg, params = tiny
    engine = _engine(cfg, params, **_PREEMPT_KW).start()
    try:
        chaos().fail_io("host-swap-out", times=100)
        r_low, r_hi, low_p, hi_p, low_n, hi_n = _run_preemption(engine, cfg)
        snap = engine.metrics.snapshot()
        assert snap["preemptions_total"] == 0, \
            "demote fault must abort the preemption"
        assert engine.host_tier.host_used == 0
        engine.drain(timeout=120)
        assert engine.sanitizer_report == []
    finally:
        chaos().reset()
        engine.shutdown()
    assert engine._scheduler_error is None, engine._scheduler_error
    assert r_low.tokens == _reference(cfg, params, low_p, low_n)
    assert r_hi.tokens == _reference(cfg, params, hi_p, hi_n)


@pytest.mark.chaos
def test_chaos_swap_in_fault_refetches(tiny):
    """host-swap-in armed for exactly one attempt: the first resume
    faults with the host copy intact, a later scheduler iteration
    re-fetches, and the resumed trajectory is still bitwise."""
    cfg, params = tiny
    engine = _engine(cfg, params, **_PREEMPT_KW).start()
    try:
        chaos().fail_io("host-swap-in", times=1)
        r_low, r_hi, low_p, hi_p, low_n, hi_n = _run_preemption(engine, cfg)
        snap = engine.metrics.snapshot()
        assert snap["preemptions_total"] >= 1
        assert snap["resumes_total"] >= 1
        engine.drain(timeout=120)
        assert engine.sanitizer_report == []
        assert engine.host_tier.host_used == 0
    finally:
        chaos().reset()
        engine.shutdown()
    assert engine._scheduler_error is None, engine._scheduler_error
    assert r_low.tokens == _reference(cfg, params, low_p, low_n)
    assert r_hi.tokens == _reference(cfg, params, hi_p, hi_n)


@pytest.mark.chaos
def test_chaos_prefix_spill_fault_drops_cleanly(tiny):
    """host-swap-out armed during prefix eviction: _spill fails before
    mutating, the victim falls back to a plain drop, and the next
    identical prompt simply re-prefills — correct, just cold."""
    cfg, params = tiny
    prompt_a, prompt_b = _prompt(cfg, 17, 31), _prompt(cfg, 17, 32)
    engine = _engine(cfg, params, max_batch_size=2, prefix_cache_blocks=2,
                     host_kv_blocks=8, sanitize=True).start()
    try:
        engine.submit(prompt_a, max_new_tokens=6,
                      use_eos_stop=False).result(timeout=600)
        chaos().fail_io("host-swap-out", times=100)
        engine.submit(prompt_b, max_new_tokens=6,
                      use_eos_stop=False).result(timeout=600)
        # B's offer overflowed the budget while the swap site faulted:
        # A's blocks were plain-dropped, nothing landed on the host
        assert engine.prefix_cache.host_blocks == 0
        chaos().reset()
        # A is gone from the cache entirely — this is a cold re-prefill,
        # not a promotion (its own retirement may spill B; that's fine)
        r = engine.submit(prompt_a, max_new_tokens=6,
                          use_eos_stop=False).result(timeout=600)
        assert engine.metrics.snapshot()["prefix_promotions_total"] == 0
        engine.drain(timeout=60)
        assert engine.sanitizer_report == []
    finally:
        chaos().reset()
        engine.shutdown()
    assert engine._scheduler_error is None, engine._scheduler_error
    assert r.tokens == _reference(cfg, params, prompt_a, 6)
