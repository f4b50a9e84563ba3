"""Continuous-batching engine tests (CPU, tiny model).

The load-bearing test is ``test_continuous_batching_matches_one_shot``:
eight staggered ragged requests through a 4-slot engine must return,
per prompt, exactly the tokens the one-shot ``generate_tokens`` path
produces (the pre-engine server trajectory), AND at least two requests
must have shared a decode iteration (``max_decode_batch``) — the direct
evidence of batching rather than serialization.
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_llm_tpu.config import tiny_config
from megatron_llm_tpu.generation import score_tokens
from megatron_llm_tpu.models import model as model_lib
from megatron_llm_tpu.serving import EngineConfig, QueueFull, ServingEngine
from tests.serving.one_shot import reference as _reference


@pytest.fixture(scope="module")
def tiny():
    cfg = tiny_config(num_layers=2, vocab_size=64,
                      make_vocab_size_divisible_by=8)
    params = model_lib.init_params(jax.random.key(0), cfg)
    return cfg, params


def _engine(cfg, params, **overrides):
    kw = dict(max_batch_size=4, max_seq_len=64, max_queue_size=16)
    kw.update(overrides)
    return ServingEngine(cfg, params, EngineConfig(**kw))


def test_continuous_batching_matches_one_shot(tiny):
    cfg, params = tiny
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size,
                            int(rng.integers(3, 11))).tolist()
               for _ in range(8)]
    max_new = 12
    engine = _engine(cfg, params).start()
    try:
        handles = []
        for p in prompts:  # staggered arrivals
            handles.append(engine.submit(p, max_new_tokens=max_new,
                                         use_eos_stop=False))
            time.sleep(0.002)
        results = [h.result(timeout=600) for h in handles]
    finally:
        engine.shutdown()

    for p, r in zip(prompts, results):
        assert r.finish_reason == "length"
        assert r.prompt_len == len(p)
        assert r.tokens == _reference(cfg, params, p, max_new)

    snap = engine.metrics.snapshot()
    assert snap["completed"] == 8
    assert snap["admitted"] == 8 and snap["prefills"] == 8
    # ≥ 2 requests decoded in the same batch iteration = true continuous
    # batching (8 requests over 4 slots would serialize otherwise)
    assert snap["max_decode_batch"] >= 2


def test_engine_logprobs_match_score(tiny):
    """Engine-reported logprobs (prompt positions + generated tokens) must
    equal post-hoc scoring of the final sequence, the same invariant
    test_generation.py::test_logprobs_match_score checks for the one-shot
    loop."""
    cfg, params = tiny
    engine = _engine(cfg, params).start()
    try:
        r = engine.submit([5, 9, 3, 7], max_new_tokens=5,
                          use_eos_stop=False,
                          return_logprobs=True).result(timeout=600)
    finally:
        engine.shutdown()
    assert len(r.logprobs) == len(r.tokens) - 1
    scored = np.asarray(score_tokens(
        cfg, params, jnp.asarray([r.tokens], jnp.int32)))[0]
    np.testing.assert_allclose(r.logprobs, scored, atol=2e-4, rtol=2e-4)


def test_slot_reuse_across_staggered_arrivals(tiny):
    """Five requests through two slots: every slot must be recycled and
    every request completed."""
    cfg, params = tiny
    engine = _engine(cfg, params, max_batch_size=2).start()
    try:
        handles = [engine.submit([3 + i, 7, 11], max_new_tokens=6,
                                 use_eos_stop=False) for i in range(5)]
        results = [h.result(timeout=600) for h in handles]
    finally:
        engine.shutdown()
    assert all(r.finish_reason == "length" for r in results)
    snap = engine.metrics.snapshot()
    assert snap["admitted"] == 5 and snap["completed"] == 5
    assert snap["max_decode_batch"] <= 2  # only two slots exist
    assert engine.slots.free_slots == 2   # all returned to the free list


def test_eos_retires_mid_batch(tiny):
    """One request hitting EOS must leave the batch alone: the other
    request keeps decoding to its full budget."""
    cfg, params = tiny
    prompt = [5, 9, 3]
    ref = _reference(cfg, params, prompt, 8)
    gen = ref[len(prompt):]
    eos = gen[2]  # a token the greedy rollout actually emits
    other = [7, 8, 9, 10]
    engine = _engine(cfg, params).start()
    try:
        engine.pause()  # both requests enter the batch together
        ha = engine.submit(prompt, max_new_tokens=8, eos_id=eos)
        hb = engine.submit(other, max_new_tokens=8, use_eos_stop=False)
        engine.resume()
        ra = ha.result(timeout=600)
        rb = hb.result(timeout=600)
    finally:
        engine.shutdown()
    assert ra.finish_reason == "eos"
    stop = gen.index(eos) + 1  # generation stops AT the EOS token
    assert ra.tokens == ref[:len(prompt) + stop]
    assert rb.finish_reason == "length"
    assert rb.tokens == _reference(cfg, params, other, 8)


def test_cancel_queued_request(tiny):
    cfg, params = tiny
    engine = _engine(cfg, params).start()
    engine.pause()  # keep it queued
    try:
        h = engine.submit([5, 9, 3], max_new_tokens=4)
        h.cancel()
        r = h.result(timeout=60)
    finally:
        engine.shutdown()
    assert r.finish_reason == "cancelled"
    assert r.tokens == [5, 9, 3]  # nothing generated
    assert engine.metrics.snapshot()["cancelled"] == 1


def test_cancel_running_request(tiny):
    """Cancellation of an in-flight request lands at an iteration boundary:
    some tokens generated, far fewer than the budget."""
    cfg, params = tiny
    got_first = threading.Event()

    def on_token(tok):
        got_first.set()
        time.sleep(0.02)  # throttle decode so the cancel lands mid-flight

    engine = _engine(cfg, params).start()
    try:
        h = engine.submit([5, 9, 3], max_new_tokens=50, use_eos_stop=False,
                          on_token=on_token)
        assert got_first.wait(timeout=300)
        h.cancel()
        r = h.result(timeout=60)
    finally:
        engine.shutdown()
    assert r.finish_reason == "cancelled"
    assert 1 <= len(r.tokens) - r.prompt_len < 50
    # the slot went back to the free list
    assert engine.slots.free_slots == 4


def test_streaming_callback_order(tiny):
    cfg, params = tiny
    streamed = []
    engine = _engine(cfg, params).start()
    try:
        r = engine.submit([5, 9, 3], max_new_tokens=6, use_eos_stop=False,
                          on_token=streamed.append).result(timeout=600)
    finally:
        engine.shutdown()
    assert streamed == r.tokens[r.prompt_len:]


def test_sampled_trajectory_independent_of_batch(tiny):
    """A seeded sampled request must produce the same tokens whether it
    runs alone (slot 0) or lands in a different slot alongside greedy
    companions — the per-request RNG stream is folded on the request's own
    token counter, never on batch state."""
    cfg, params = tiny
    spec = dict(prompt=[5, 9, 3], max_new_tokens=8, use_eos_stop=False,
                temperature=0.8, top_k=8, seed=123)
    engine = _engine(cfg, params).start()
    try:
        alone = engine.submit(**spec).result(timeout=600)
        engine.pause()  # companions admitted first → spec lands in slot 3
        comps = [engine.submit([7 + i, 11], max_new_tokens=8,
                               use_eos_stop=False) for i in range(3)]
        h = engine.submit(**spec)
        engine.resume()
        shared = h.result(timeout=600)
        for c in comps:
            c.result(timeout=600)
        reseeded = engine.submit(**{**spec, "seed": 124}).result(timeout=600)
    finally:
        engine.shutdown()
    assert shared.tokens == alone.tokens
    assert reseeded.tokens != alone.tokens  # overwhelmingly


def test_sampling_request_rides_the_greedy_executable_and_is_counted(tiny):
    """One decode executable serves greedy, sampling and mixed batches:
    the device reads ``any(~greedy)`` from the vector the step is handed.
    So a sampling request that joins greedy ones compiles nothing, and
    the host, which filled that vector, counts the steps it rode
    (``sampled_steps``, the ``engine_step`` span's ``sampling``); an
    all-greedy run leaves the counter 0 and every span false."""
    from megatron_llm_tpu.analysis.sanitizers import no_recompiles
    from megatron_llm_tpu.obs import REGISTRY

    cfg, params = tiny
    # not pipelined: a pipelined engine dispatches one more (masked) step
    # for a request before it learns the request is done
    engine = _engine(cfg, params, pipeline_decode=False).start()

    def run(first, last):
        """Three greedy requests and ``last``; prompts from ``first`` on,
        so that a second round shares no prefix with the first (a prefix
        hit prefills through another executable)."""
        engine.pause()   # one admission round, so the four share steps
        hs = [engine.submit([first + i, 11, 3], max_new_tokens=12,
                            use_eos_stop=False) for i in range(3)]
        hs.append(engine.submit([first + 3, 9, 3], use_eos_stop=False,
                                **last))
        engine.resume()
        return [h.result(timeout=600) for h in hs]

    def steps():
        return [e["args"]["sampling"]
                for e in engine.trace.chrome_trace()["traceEvents"]
                if e["name"] == "engine_step"]

    try:
        run(7, dict(max_new_tokens=5))
        greedy_steps = steps()
        assert greedy_steps and not any(greedy_steps)
        assert engine.metrics.snapshot()["sampled_steps"] == 0
        with no_recompiles():
            out = run(20, dict(max_new_tokens=5, temperature=0.8, top_k=8,
                               seed=123))
        snap = engine.metrics.snapshot()
        mixed_steps = steps()[len(greedy_steps):]
    finally:
        engine.shutdown()
    assert all(r.finish_reason == "length" for r in out)
    # the first of its 5 tokens is the prefill's; 4 decode steps carried it
    assert snap["sampled_steps"] == 4 == sum(mixed_steps)
    assert len(mixed_steps) == 11 and mixed_steps[:4] == [True] * 4
    assert "serving_sampled_steps_total 4" in REGISTRY.prometheus_text()


def test_admission_validation(tiny):
    cfg, params = tiny
    engine = _engine(cfg, params)
    try:
        with pytest.raises(ValueError, match="empty prompt"):
            engine.submit([], max_new_tokens=4)
        with pytest.raises(ValueError, match="max_new_tokens"):
            engine.submit([5], max_new_tokens=0)
        with pytest.raises(ValueError, match="sequence budget"):
            engine.submit(list(range(1, 61)), max_new_tokens=5)  # 60+5 > 64
        assert engine.metrics.snapshot()["rejected_invalid"] == 3
    finally:
        engine.shutdown()


def test_queue_full_backpressure(tiny):
    cfg, params = tiny
    engine = _engine(cfg, params, max_batch_size=1, max_queue_size=2,
                     retry_after_s=3.0).start()
    engine.pause()  # nothing drains: deterministic queue pressure
    try:
        engine.submit([5], max_new_tokens=2)
        engine.submit([6], max_new_tokens=2)
        with pytest.raises(QueueFull) as ei:
            engine.submit([7], max_new_tokens=2)
        assert ei.value.retry_after_s == 3.0
        snap = engine.metrics.snapshot()
        assert snap["rejected_queue_full"] == 1
        assert snap["queued"] == 2
    finally:
        engine.shutdown()


def test_scheduler_failure_during_prefill_fails_request(tiny):
    """A crash while a request is mid-admission (popped from the queue but
    not yet slotted) must still fail THAT request — it is in neither the
    queue nor the active set at that moment."""
    import megatron_llm_tpu.serving.engine as engine_mod
    cfg, params = tiny

    def boom(*args, **kwargs):
        raise RuntimeError("injected prefill failure")

    orig = engine_mod._prefill_impl
    engine_mod._prefill_impl = boom
    engine = _engine(cfg, params)
    try:
        engine.start()
        h = engine.submit([5, 9, 3], max_new_tokens=4)
        with pytest.raises(RuntimeError, match="scheduler failed"):
            h.result(timeout=300)
    finally:
        engine_mod._prefill_impl = orig
        engine.shutdown()


def test_scheduler_failure_fails_requests_loudly(tiny):
    """A dead scheduler must not leave result() blocked forever: in-flight
    requests finish with reason "error" and result() raises."""
    cfg, params = tiny

    def boom(*args, **kwargs):
        raise RuntimeError("injected decode failure")

    engine = _engine(cfg, params)
    engine._decode = boom
    engine.start()
    try:
        h = engine.submit([5, 9, 3], max_new_tokens=8, use_eos_stop=False)
        with pytest.raises(RuntimeError, match="scheduler failed"):
            h.result(timeout=300)
        assert h.done()
    finally:
        engine.shutdown()


class TestPagedEquivalence:
    """The paged acceptance matrix (docs/serving.md, 'Paged KV cache'):
    with a SMALL block size — mixed-length requests spanning many blocks,
    lazy decode-time growth crossing block boundaries, zero-copy prefix
    hits — every committed token must equal the one-shot
    ``generate_tokens`` trajectory bitwise.  fp32 and fully-int8, whole-
    prompt and chunked admission, pipelined decode on and off; plus the
    degenerate fixed-stride configuration (``kv_block_size ==
    max_seq_len``), which must be the same code path with one block per
    slot."""

    @pytest.fixture(scope="class")
    def tiny_int8(self, tiny):
        import dataclasses

        from megatron_llm_tpu.ops.quant import quantize_params

        cfg, params = tiny
        return (dataclasses.replace(cfg, kv_cache_quant="int8"),
                quantize_params(params))

    def _drive(self, cfg, params, **overrides):
        """Mixed-length ragged batch through a paged engine; returns the
        results plus a metrics snapshot."""
        rng = np.random.default_rng(23)
        prompts = [rng.integers(1, cfg.vocab_size, n).tolist()
                   for n in (3, 17, 30, 9)]  # 1..4 blocks at bk=8
        max_news = [20, 9, 14, 5]            # growth crosses boundaries
        kw = dict(max_batch_size=4, max_seq_len=64, max_queue_size=16,
                  kv_block_size=8)
        kw.update(overrides)
        engine = ServingEngine(cfg, params, EngineConfig(**kw)).start()
        try:
            handles = [engine.submit(p, max_new_tokens=n,
                                     use_eos_stop=False)
                       for p, n in zip(prompts, max_news)]
            results = [h.result(timeout=600) for h in handles]
        finally:
            engine.shutdown()
        for p, n, r in zip(prompts, max_news, results):
            assert r.finish_reason == "length"
            assert r.tokens == _reference(cfg, params, p, n)
        return engine.metrics.snapshot()

    @pytest.mark.parametrize("pipeline", [True, False],
                             ids=["pipelined", "sync"])
    def test_fp32_whole_prompt(self, tiny, pipeline):
        snap = self._drive(*tiny, pipeline_decode=pipeline)
        assert snap["max_decode_batch"] >= 2
        assert snap["blocks_used"] >= 0 and snap["blocks_free"] >= 0

    @pytest.mark.parametrize("pipeline", [True, False],
                             ids=["pipelined", "sync"])
    def test_fp32_chunked_admission(self, tiny, pipeline):
        snap = self._drive(*tiny, prefill_chunk=8,
                           pipeline_decode=pipeline)
        assert snap["prefill_chunks"] > 4  # really ran chunk-at-a-time

    def test_int8_whole_prompt(self, tiny_int8):
        self._drive(*tiny_int8)

    def test_int8_chunked_pipelined(self, tiny_int8):
        self._drive(*tiny_int8, prefill_chunk=8, pipeline_decode=True)

    def test_fixed_stride_degenerate_block(self, tiny):
        """kv_block_size == max_seq_len: one block per slot — the
        pre-paging layout expressed in the same engine code path."""
        snap = self._drive(*tiny, kv_block_size=64)
        assert snap["blocks_used"] <= 4 + 1  # <= one block per slot

    def test_prefix_hit_with_small_blocks(self, tiny):
        """Zero-copy sharing under real paging: sequential shared-prefix
        requests hit and stay bitwise equal, with no COW copies."""
        cfg, params = tiny
        rng = np.random.default_rng(29)
        prompt = rng.integers(1, cfg.vocab_size, 21).tolist()
        engine = ServingEngine(cfg, params, EngineConfig(
            max_batch_size=2, max_seq_len=64, max_queue_size=8,
            kv_block_size=8, prefix_cache_blocks=16)).start()
        try:
            a = engine.submit(prompt, max_new_tokens=10,
                              use_eos_stop=False).result(timeout=600)
            b = engine.submit(prompt, max_new_tokens=10,
                              use_eos_stop=False).result(timeout=600)
        finally:
            engine.shutdown()
        ref = _reference(cfg, params, prompt, 10)
        assert a.tokens == ref and b.tokens == ref
        snap = engine.metrics.snapshot()
        assert snap["prefix_hits"] == 1
        assert snap["cow_copies_total"] == 0

    def test_pool_exhaustion_parks_and_recovers(self, tiny):
        """A pool too small for all requests at once: admission parks at
        the queue head until retirements free blocks — every request
        still completes with the exact one-shot trajectory (FIFO, no
        deadlock, no corruption)."""
        cfg, params = tiny
        rng = np.random.default_rng(31)
        prompts = [rng.integers(1, cfg.vocab_size, 16).tolist()
                   for _ in range(5)]
        # 9 usable blocks of 8 = 72 tokens; each request needs
        # ceil((16+8)/8) = 3 blocks, so at most 3 can run concurrently
        engine = ServingEngine(cfg, params, EngineConfig(
            max_batch_size=5, max_seq_len=32, max_queue_size=8,
            kv_block_size=8, kv_pool_blocks=10)).start()
        try:
            handles = [engine.submit(p, max_new_tokens=8,
                                     use_eos_stop=False) for p in prompts]
            results = [h.result(timeout=600) for h in handles]
        finally:
            engine.shutdown()
        for p, r in zip(prompts, results):
            assert r.tokens == _reference(cfg, params, p, 8)
        snap = engine.metrics.snapshot()
        assert snap["max_decode_batch"] <= 3  # the pool really bounded it


class TestSpeculative:
    """Speculative decoding acceptance matrix (docs/serving.md,
    'Speculative decoding'): with per-slot prompt-lookup drafts, a
    batched variable-length verify step, and rollback over paged
    blocks, every committed token must equal the one-shot
    ``generate_tokens`` trajectory bitwise — spec on/off x fp32/int8 x
    paged/fixed-stride x pipelined/sync.  The repetitive prompts below
    are chosen so the random-init model settles into a cycle and the
    drafter actually engages (asserted via ``spec_steps``), so the
    accept-and-commit path — not just the gate — is what's equal."""

    REP_PROMPTS = [[5, 9, 3, 5, 9, 3, 5, 9, 3, 5, 9],
                   [7, 7, 7, 7, 7, 7, 7],
                   [4, 8, 2, 4, 8, 2, 4, 8],
                   [11, 6, 11, 6, 11, 6, 11]]
    MAX_NEW = 20

    @pytest.fixture(scope="class")
    def tiny_int8(self, tiny):
        import dataclasses

        from megatron_llm_tpu.ops.quant import quantize_params

        cfg, params = tiny
        return (dataclasses.replace(cfg, kv_cache_quant="int8"),
                quantize_params(params))

    def _drive(self, cfg, params, draft_len=3, prompts=None,
               max_new=None, **overrides):
        kw = dict(max_batch_size=4, max_seq_len=64, max_queue_size=16,
                  spec_draft_len=draft_len)
        kw.update(overrides)
        prompts = prompts or self.REP_PROMPTS
        max_new = max_new or self.MAX_NEW
        engine = ServingEngine(cfg, params, EngineConfig(**kw)).start()
        try:
            handles = [engine.submit(p, max_new_tokens=max_new,
                                     use_eos_stop=False) for p in prompts]
            results = [h.result(timeout=600) for h in handles]
        finally:
            engine.shutdown()
        return results, engine.metrics.snapshot()

    def _check(self, cfg, params, **overrides):
        results, snap = self._drive(cfg, params, **overrides)
        for p, r in zip(self.REP_PROMPTS, results):
            assert r.finish_reason == "length"
            assert r.tokens == _reference(cfg, params, p, self.MAX_NEW)
        assert snap["spec_steps"] > 0, "drafter never engaged"
        assert 0 < snap["spec_acceptance_rate"] <= 1
        assert 1 <= snap["accepted_tokens_per_step"]["mean"] <= \
            overrides.get("draft_len", 3) + 1
        return snap

    @pytest.mark.parametrize("pipeline", [True, False],
                             ids=["pipelined", "sync"])
    def test_fp32_paged(self, tiny, pipeline):
        self._check(*tiny, kv_block_size=8, pipeline_decode=pipeline)

    @pytest.mark.parametrize("pipeline", [True, False],
                             ids=["pipelined", "sync"])
    def test_fp32_fixed_stride(self, tiny, pipeline):
        """kv_block_size == max_seq_len: the pre-paging dense layout,
        same engine code path (one block per slot)."""
        self._check(*tiny, kv_block_size=64, pipeline_decode=pipeline)

    @pytest.mark.slow
    def test_int8_paged(self, tiny_int8):
        self._check(*tiny_int8, kv_block_size=8)

    def test_int8_fixed_stride_sync(self, tiny_int8):
        self._check(*tiny_int8, kv_block_size=64, pipeline_decode=False)

    @pytest.mark.slow
    def test_composes_with_chunked_prefill_and_prefix_cache(self, tiny):
        self._check(*tiny, kv_block_size=8, prefill_chunk=8,
                    prefix_cache_blocks=16)

    def test_sampled_riders_unchanged(self, tiny):
        """Sampled requests carry empty drafts but ride verify batches
        (position-0 sampling with the same seed/counter stream), so
        their trajectories must be bitwise identical spec on vs off."""
        cfg, params = tiny
        reqs = [dict(prompt=self.REP_PROMPTS[0], max_new_tokens=12,
                     temperature=0.8, top_k=8, seed=123,
                     use_eos_stop=False),
                dict(prompt=self.REP_PROMPTS[1], max_new_tokens=12,
                     use_eos_stop=False)]

        def run(draft_len):
            engine = ServingEngine(cfg, params, EngineConfig(
                max_batch_size=4, max_seq_len=64,
                spec_draft_len=draft_len)).start()
            try:
                hs = [engine.submit(**r) for r in reqs]
                toks = [h.result(timeout=600).tokens for h in hs]
            finally:
                engine.shutdown()
            return toks, engine.metrics.snapshot()

        on, snap = run(3)
        off, _ = run(0)
        assert on == off
        assert snap["spec_steps"] > 0  # the greedy rider did speculate

    def test_eos_mid_window(self, tiny):
        """EOS landing inside an accepted draft span: the request must
        stop at exactly the token plain decode stops at — the commit
        loop retires the slot mid-window and discards the rest."""
        cfg, params = tiny
        prompt = [9, 2, 9, 2, 9, 2, 9]
        ref = _reference(cfg, params, prompt, 20)
        eos = int(ref[-1])

        def run(draft_len):
            engine = ServingEngine(cfg, params, EngineConfig(
                max_batch_size=2, max_seq_len=64,
                spec_draft_len=draft_len)).start()
            try:
                return engine.submit(prompt, max_new_tokens=20,
                                     eos_id=eos,
                                     use_eos_stop=True).result(timeout=600)
            finally:
                engine.shutdown()

        r_on, r_off = run(4), run(0)
        assert r_on.tokens == r_off.tokens
        assert r_on.finish_reason == r_off.finish_reason

    def test_capacity_tail_gate(self, tiny):
        """Generation running to the sequence cap: within W rows of the
        table width the whole batch must fall back to plain steps (the
        verify forward writes masked rows at fill..fill+W-1), and the
        trajectory stays identical to spec-off."""
        cfg, params = tiny
        prompt = self.REP_PROMPTS[0][:8]

        def run(draft_len):
            engine = ServingEngine(cfg, params, EngineConfig(
                max_batch_size=2, max_seq_len=32,
                spec_draft_len=draft_len)).start()
            try:
                return engine.submit(prompt, max_new_tokens=24,
                                     use_eos_stop=False
                                     ).result(timeout=600).tokens
            finally:
                engine.shutdown()

        assert run(4) == run(0)

    def _rejecting_prompt(self, cfg, params, length, rng):
        """A prompt whose first draft is wrong by construction.

        ``[a, b, c, x] + filler + [a, b]``: once the model has produced
        its first token, the context's trailing 3-gram is ``(a, b,
        first)``.  ``c`` is scanned until the reference's first token IS
        ``c``, so the drafter's match is the planted one and its draft
        starts with ``x`` — a token the reference does not produce next.
        ``a`` and ``b`` appear nowhere else, so no other match exists."""
        a, b, x = cfg.vocab_size - 1, cfg.vocab_size - 2, 1
        for _ in range(8):
            filler = rng.integers(2, cfg.vocab_size - 2,
                                  length - 6).tolist()
            for c in range(2, cfg.vocab_size - 2):
                prompt = [a, b, c, x] + filler + [a, b]
                # (hundreds of two-token rollouts: as short as they are)
                first, second = _reference(cfg, params, prompt, 2,
                                           window=length + 2)[length:]
                if first == c and second != x:
                    return prompt
        pytest.fail(f"no rejecting prompt of length {length} found")

    @pytest.mark.parametrize("block,draft_len",
                             [(4, 3), (4, 2), (8, 3), (8, 5)])
    def test_block_boundary_rollback(self, tiny, block, draft_len):
        """Rejected drafts across block edges.  Every prompt ends one or
        two rows short of a block edge and carries a planted n-gram whose
        continuation the model does not produce, so each slot's first
        verify window straddles the edge, is rejected at its first
        token, and leaves its rows in a freshly allocated block; the
        cycle the model then settles into supplies the accepted windows.
        Rollback is fill arithmetic — the trajectory stays exact, no
        COW copies fire (no sharing here), and the sanitizer's block
        ledger stays balanced through drain."""
        cfg, params = tiny
        rng = np.random.default_rng(block * 16 + draft_len)
        prompts = [self._rejecting_prompt(cfg, params, n, rng)
                   for n in (3 * block - 1, 3 * block - 2,
                             4 * block - 1, 4 * block - 2)]
        engine = ServingEngine(cfg, params, EngineConfig(
            max_batch_size=4, max_seq_len=64, max_queue_size=16,
            kv_block_size=block, spec_draft_len=draft_len,
            sanitize=True)).start()
        try:
            handles = [engine.submit(p, max_new_tokens=self.MAX_NEW,
                                     use_eos_stop=False)
                       for p in prompts]
            results = [h.result(timeout=600) for h in handles]
            engine.drain(timeout=60)
            assert engine.sanitizer_report == []
        finally:
            engine.shutdown()
        for p, r in zip(prompts, results):
            assert r.tokens == _reference(cfg, params, p, self.MAX_NEW)
        snap = engine.metrics.snapshot()
        assert snap["spec_steps"] > 0
        assert snap["spec_accepted"] < snap["spec_proposed"], \
            "no rejection ever happened; the rollback path went untested"
        assert snap["cow_copies_total"] == 0

    def test_spec_metrics_shape(self, tiny):
        """The serving metrics surface for speculation: counters,
        derived acceptance rate, and the accepted-per-step histogram
        all present in snapshot() and consistent with each other."""
        _, snap = self._drive(*tiny, kv_block_size=8)
        assert snap["spec_proposed"] >= snap["spec_accepted"] >= 0
        assert snap["spec_steps"] > 0
        hist = snap["accepted_tokens_per_step"]
        assert hist["count"] > 0
        # per participating slot-step, committed = accepted + 1 bonus
        # (mid-window EOS retirement can only truncate, never add)
        total_committed = hist["mean"] * hist["count"]
        assert total_committed <= \
            snap["spec_accepted"] + hist["count"] + 1e-6
        assert hist["mean"] >= 1.0


class TestPrecisionPolicies:
    """int4 / mixed weight policies through the engine (round 9): every
    committed token must equal the one-shot ``generate_tokens``
    trajectory on the SAME quantized tree — bitwise reproducibility
    across engine modes — and the decode-step metrics must attribute
    iterations to the right precision route."""

    @pytest.mark.parametrize("policy", ["int4", "mixed"])
    def test_policy_paged_matches_one_shot(self, tiny, policy):
        import dataclasses

        from megatron_llm_tpu.ops import quant

        cfg, params = tiny
        pol = dataclasses.replace(quant.POLICIES[policy], group_size=32)
        qparams = quant.quantize_params(params, pol)
        rng = np.random.default_rng(37)
        prompts = [rng.integers(1, cfg.vocab_size, n).tolist()
                   for n in (3, 17, 9)]
        engine = ServingEngine(cfg, qparams, EngineConfig(
            max_batch_size=4, max_seq_len=64, max_queue_size=16,
            kv_block_size=8)).start()
        try:
            handles = [engine.submit(p, max_new_tokens=10,
                                     use_eos_stop=False) for p in prompts]
            results = [h.result(timeout=600) for h in handles]
        finally:
            engine.shutdown()
        for p, r in zip(prompts, results):
            assert r.finish_reason == "length"
            assert r.tokens == _reference(cfg, qparams, p, 10)

        # decode iterations attributed to the policy's precision route
        # (on CPU every step takes the gather route, so the fallback
        # breakdown is where the label must land)
        snap = engine.metrics.snapshot()
        assert set(snap["fallback_steps_by_precision"]) == {policy}
        assert snap["fallback_steps_by_precision"][policy] > 0
        assert snap["paged_steps_by_precision"] == {policy: 0}
