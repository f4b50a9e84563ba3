"""Multi-tenant LoRA serving (serving/adapters/ + engine plumbing).

The load-bearing invariant: every token of a mixed-adapter decode batch
is bitwise-equal to the same request run ALONE on the same engine —
across fp32/int8/int4 weights, paged/fixed-stride KV, and speculative
decoding on/off — because slot-masked arena columns contribute exact
±0.0 to other rows.  Plus the cache mechanics (LRU + ref pinning under
an eviction storm), live weight swap mid-traffic, and the
zero-recompile guarantee as adapters rotate through the arena.
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
from megatron_llm_tpu.ops.lora import init_lora_adapter
from megatron_llm_tpu.serving import (
    AdapterRegistry,
    EngineConfig,
    ServingEngine,
)

PROMPT = [3, 5, 7, 11, 13]
# repetitive so the prompt-lookup drafter engages in the spec variants
REP_PROMPT = [5, 9, 3, 5, 9, 3, 5, 9, 3, 5, 9]


@pytest.fixture(scope="module")
def tiny():
    cfg = tiny_config(num_layers=2, vocab_size=64,
                      make_vocab_size_divisible_by=8)
    params = model_lib.init_params(jax.random.key(0), cfg)
    return cfg, params


def _adapter(cfg, seed, rank=4, **kw):
    """Adapter with non-trivial B so its delta actually moves logits."""
    ad = init_lora_adapter(cfg, jax.random.key(seed), rank, alpha=32.0,
                           **kw)
    return dataclasses.replace(ad, factors={
        t: {"a": f["a"],
            "b": jax.random.normal(jax.random.key(seed + 500),
                                   f["b"].shape, f["b"].dtype) * 0.05}
        for t, f in ad.factors.items()})


def _registry(cfg, n_adapters=3, n_slots=2, rank=4):
    reg = AdapterRegistry(cfg, n_slots=n_slots, rank=rank)
    for i in range(n_adapters):
        reg.register(f"t{i}", _adapter(cfg, 100 + i, rank))
    return reg


# ---------------------------------------------------------------------------
# registry unit behavior
# ---------------------------------------------------------------------------


class TestRegistry:
    def test_register_validates(self, tiny):
        cfg, _ = tiny
        reg = AdapterRegistry(cfg, n_slots=2, rank=4)
        with pytest.raises(ValueError, match="rank"):
            reg.register("r8", _adapter(cfg, 1, rank=8))
        reg.register("a", _adapter(cfg, 2))
        assert reg.known("a") and not reg.known("b")
        with pytest.raises(KeyError):
            reg.acquire("never-registered")

    def test_lru_eviction_and_ref_pinning(self, tiny):
        cfg, _ = tiny
        reg = _registry(cfg, n_adapters=4, n_slots=2)
        s0 = reg.acquire("t0")
        s1 = reg.acquire("t1")
        assert {s0, s1} == {0, 1}
        # arena full, both pinned: no victim available
        assert reg.acquire("t2") is None
        reg.release("t0")                       # t0 unpinned -> evictable
        s2 = reg.acquire("t2")
        assert s2 == s0 and not reg.is_resident("t0")
        assert reg.is_resident("t1")            # pinned survivor
        # re-acquiring the resident is a hit, not an install
        assert reg.acquire("t1") == s1
        reg.release("t1")
        reg.release("t1")
        reg.release("t2")
        assert all(reg.pins(a) == 0 for a in reg.resident())

    def test_resident_adapter_cannot_be_replaced(self, tiny):
        cfg, _ = tiny
        reg = _registry(cfg, n_adapters=2, n_slots=1)
        reg.acquire("t0")
        with pytest.raises(ValueError, match="resident"):
            reg.register("t0", _adapter(cfg, 9))
        reg.release("t0")
        # parked is still resident (its arena columns are live)
        with pytest.raises(ValueError, match="resident"):
            reg.register("t0", _adapter(cfg, 9))
        reg.acquire("t1")                       # evicts the parked t0
        reg.register("t0", _adapter(cfg, 9))    # evicted: replace is fine
        reg.release("t1")

    def test_clone_shares_store_not_residency(self, tiny):
        cfg, _ = tiny
        reg = _registry(cfg, n_adapters=2, n_slots=2)
        reg.acquire("t0")
        twin = reg.clone()
        assert twin.known("t0") and twin.known("t1")
        assert not twin.is_resident("t0")       # fresh arena, no pins
        assert reg.is_resident("t0")            # original untouched
        twin.register("t9", _adapter(cfg, 77))
        assert not reg.known("t9")              # stores diverge after clone
        reg.release("t0")


# ---------------------------------------------------------------------------
# the bitwise acceptance matrix
# ---------------------------------------------------------------------------


class TestMixedBatchBitwise:
    """Mixed-adapter batch tokens == per-request-alone tokens, bitwise,
    on the SAME engine (fixed batch geometry): fp32/int8/int4 weights x
    paged/fixed-stride KV x speculative decoding on/off."""

    @pytest.fixture(scope="class")
    def quantized(self, tiny):
        from megatron_llm_tpu.ops.quant import (quantize_params,
                                                resolve_policy)

        cfg, params = tiny
        return {
            "fp32": params,
            "int8": quantize_params(params, resolve_policy("int8")),
            "int4": quantize_params(params, resolve_policy("int4")),
        }

    def _drive(self, cfg, params, spec, **overrides):
        kw = dict(max_batch_size=4, max_seq_len=64, max_queue_size=16,
                  adapter_cache_slots=2, prefix_cache_blocks=0)
        if spec:
            kw["spec_draft_len"] = 3
        kw.update(overrides)
        reg = _registry(cfg, n_adapters=2, n_slots=2)
        prompt = REP_PROMPT if spec else PROMPT
        max_new = 16 if spec else 8
        specs = [dict(adapter_id="t0"), dict(), dict(adapter_id="t1"),
                 dict(adapter_id="t0")]
        engine = ServingEngine(cfg, params, EngineConfig(**kw),
                               adapters=reg).start()
        try:
            alone = [engine.submit(prompt, max_new, use_eos_stop=False,
                                   **s).result(600).tokens
                     for s in specs]
            handles = [engine.submit(prompt, max_new, use_eos_stop=False,
                                     **s) for s in specs]
            mixed = [h.result(600).tokens for h in handles]
            snap = engine.metrics.snapshot()
        finally:
            engine.shutdown()
        assert mixed == alone                    # bitwise, per request
        assert alone[0] != alone[1]              # t0 really diverges
        assert alone[2] != alone[1]              # t1 really diverges
        assert alone[2] != alone[0]              # ...differently
        assert snap["max_decode_batch"] >= 2     # batch actually mixed
        if spec:
            assert snap["spec_steps"] > 0, "drafter never engaged"
        assert engine.sanitizer_report == []
        return snap

    @pytest.mark.parametrize("spec", [False, True], ids=["plain", "spec"])
    @pytest.mark.parametrize("layout", ["paged", "dense"])
    @pytest.mark.parametrize("precision", ["fp32", "int8", "int4"])
    def test_matrix(self, tiny, quantized, precision, layout, spec):
        cfg, _ = tiny
        block = 8 if layout == "paged" else 64
        self._drive(cfg, quantized[precision], spec, kv_block_size=block)


# ---------------------------------------------------------------------------
# cache churn, parking, and the ledger
# ---------------------------------------------------------------------------


def test_eviction_storm_ref_pinning(tiny):
    """8 concurrent requests over 4 adapters through a 2-slot arena:
    admission parks when every slot is pinned, evictions rotate parked
    adapters in as pins drop, and every stream still equals its alone
    run bitwise.  Pins return to zero and the block ledger balances."""
    cfg, params = tiny
    reg = _registry(cfg, n_adapters=4, n_slots=2)
    ecfg = EngineConfig(max_batch_size=2, max_seq_len=64,
                        max_queue_size=32, adapter_cache_slots=2,
                        prefix_cache_blocks=0)
    engine = ServingEngine(cfg, params, ecfg, adapters=reg).start()
    try:
        # pairs: the second request of each pair finds its adapter
        # pinned by the first (a hit); across pairs the arena thrashes
        ids = [f"t{(i // 2) % 4}" for i in range(8)]
        alone = {aid: engine.submit(PROMPT, 8, use_eos_stop=False,
                                    adapter_id=aid).result(600).tokens
                 for aid in sorted(set(ids))}
        handles = [engine.submit(PROMPT, 8, use_eos_stop=False,
                                 adapter_id=aid) for aid in ids]
        results = [h.result(600).tokens for h in handles]
        snap = engine.metrics.snapshot()
    finally:
        engine.shutdown()
    for aid, toks in zip(ids, results):
        assert toks == alone[aid]
    assert snap["adapter_evictions"] > 0        # the storm really churned
    assert snap["adapter_hits"] > 0
    assert all(reg.pins(a) == 0 for a in reg.resident())
    assert engine.sanitizer_report == []


def test_unknown_adapter_rejected_at_submit(tiny):
    cfg, params = tiny
    reg = _registry(cfg, n_adapters=1, n_slots=2)
    ecfg = EngineConfig(max_batch_size=2, max_seq_len=64,
                        adapter_cache_slots=2)
    engine = ServingEngine(cfg, params, ecfg, adapters=reg).start()
    try:
        with pytest.raises(ValueError, match="unknown adapter"):
            engine.submit(PROMPT, 4, adapter_id="never-registered")
        # and with no registry at all, naming any adapter is an error
    finally:
        engine.shutdown()
    bare = ServingEngine(cfg, params, EngineConfig(
        max_batch_size=2, max_seq_len=64)).start()
    try:
        with pytest.raises(ValueError, match="adapter"):
            bare.submit(PROMPT, 4, adapter_id="t0")
    finally:
        bare.shutdown()


def test_no_recompiles_as_adapters_rotate(tiny):
    """After warmup, adapter churn — cache hits, misses with installs,
    evictions, base-only rows — must not compile anything new: the slot
    mask is built inside the jit from a traced operand and the install
    executable is slot-traced."""
    cfg, params = tiny
    reg = _registry(cfg, n_adapters=3, n_slots=2)
    ecfg = EngineConfig(max_batch_size=4, max_seq_len=64,
                        max_queue_size=16, adapter_cache_slots=2,
                        prefix_cache_blocks=0)
    engine = ServingEngine(cfg, params, ecfg, adapters=reg).start()
    try:
        # warmup: prefill + decode + install, with and without adapter;
        # the second joins while the first decodes, as the rotation's
        # requests join each other (the pipelined step's merge of a
        # newcomer is an executable too: one after the other, this test
        # passed only behind a test that had compiled it).  Submitted from
        # the first's second token (its first decode step's), on the
        # scheduler's own thread: whatever the machine's load, it is
        # admitted with a step of the first in flight
        seen, second = [], []

        def join(tok):
            seen.append(tok)
            if len(seen) == 2:
                second.append(engine.submit(PROMPT, 4, use_eos_stop=False))

        engine.submit(PROMPT, 8, use_eos_stop=False, adapter_id="t0",
                      on_token=join).result(600)
        second[0].result(600)
        with no_recompiles():
            handles = [
                engine.submit(PROMPT, 6, use_eos_stop=False,
                              adapter_id=aid)
                for aid in ("t0", "t1", "t2", None)]
            for h in handles:
                h.result(600)
    finally:
        engine.shutdown()


# ---------------------------------------------------------------------------
# live weight swap
# ---------------------------------------------------------------------------


def test_swap_params_mid_traffic_loses_no_tokens(tiny):
    """swap_params fences at an iteration boundary: an in-flight stream
    keeps decoding across the swap, every token is delivered exactly
    once, and the old tree comes back to the caller."""
    cfg, params = tiny
    reg = _registry(cfg, n_adapters=1, n_slots=2)
    ecfg = EngineConfig(max_batch_size=2, max_seq_len=96,
                        adapter_cache_slots=2, prefix_cache_blocks=0)
    engine = ServingEngine(cfg, params, ecfg, adapters=reg).start()
    params2 = model_lib.init_params(jax.random.key(99), cfg)
    got = []
    try:
        h = engine.submit(PROMPT, 48, use_eos_stop=False,
                          adapter_id="t0", on_token=got.append)
        time.sleep(0.05)
        old = engine.swap_params(params2)
        r = h.result(600)
    finally:
        engine.shutdown()
    assert old is params
    gen = r.tokens[len(PROMPT):]
    assert len(gen) == 48                      # nothing lost
    assert got == gen                          # nothing duplicated
    assert engine.metrics.snapshot()["param_swaps"] == 1
    assert engine.sanitizer_report == []


def test_swap_params_rejects_mismatched_tree(tiny):
    cfg, params = tiny
    engine = ServingEngine(cfg, params, EngineConfig(
        max_batch_size=2, max_seq_len=64)).start()
    bad_cfg = tiny_config(num_layers=1, vocab_size=64,
                          make_vocab_size_divisible_by=8)
    try:
        with pytest.raises(ValueError, match="structure|shape"):
            engine.swap_params(model_lib.init_params(jax.random.key(1),
                                                     bad_cfg))
        # the engine still serves after the refused swap
        r = engine.submit(PROMPT, 4, use_eos_stop=False).result(600)
        assert len(r.tokens) == len(PROMPT) + 4
    finally:
        engine.shutdown()
