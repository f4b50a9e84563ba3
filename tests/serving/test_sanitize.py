"""Runtime-sanitizer integration tests on the serving engine.

Two guarantees from the PR's acceptance bar:

* the fast-path and paged serving loops perform **zero** backend
  compiles after warmup — proven by running a full mixed batch inside
  ``no_recompiles()``;
* a KV block-pool ref-count leak (injected via the chaos harness at
  the slot-release site) is caught by the ledger sanitizer within one
  scheduler iteration and reported with the owning request id.
"""

import time

import jax
import numpy as np
import pytest

from megatron_llm_tpu.analysis.sanitizers import LedgerError, no_recompiles
from megatron_llm_tpu.config import tiny_config
from megatron_llm_tpu.models import model as model_lib
from megatron_llm_tpu.resilience.chaos import chaos
from megatron_llm_tpu.serving import EngineConfig, ServingEngine
from tests.serving.one_shot import reference as _reference


@pytest.fixture(scope="module")
def tiny():
    cfg = tiny_config(num_layers=2, vocab_size=64,
                      make_vocab_size_divisible_by=8)
    params = model_lib.init_params(jax.random.key(0), cfg)
    return cfg, params


def _engine(cfg, params, **overrides):
    kw = dict(max_batch_size=4, max_seq_len=64, max_queue_size=16,
              idle_wait_s=0.005)
    kw.update(overrides)
    return ServingEngine(cfg, params, EngineConfig(**kw))


def _mixed_batch(cfg):
    rng = np.random.default_rng(31)
    prompts = [rng.integers(1, cfg.vocab_size, n).tolist()
               for n in (3, 17, 30, 9)]
    max_news = [12, 7, 10, 5]
    return prompts, max_news


def _run(engine, prompts, max_news):
    handles = [engine.submit(p, max_new_tokens=n, use_eos_stop=False)
               for p, n in zip(prompts, max_news)]
    return [h.result(timeout=600) for h in handles]


def _assert_zero_recompiles_after_warmup(cfg, params, **overrides):
    prompts, max_news = _mixed_batch(cfg)
    engine = _engine(cfg, params, **overrides).start()
    try:
        # warmup twice: the second pass exercises the prefix-cache hit
        # path (identical prompts), so its gather executable is warm too
        _run(engine, prompts, max_news)
        _run(engine, prompts, max_news)
        with no_recompiles():
            results = _run(engine, prompts, max_news)
    finally:
        engine.shutdown()
    for p, n, r in zip(prompts, max_news, results):
        assert r.finish_reason == "length"
        assert r.tokens == _reference(cfg, params, p, n)


def test_fastpath_zero_recompiles_after_warmup(tiny):
    """Pipelined decode + chunked prefill: steady state never retraces."""
    cfg, params = tiny
    _assert_zero_recompiles_after_warmup(
        cfg, params, pipeline_decode=True, prefill_chunk=16)


def test_paged_zero_recompiles_after_warmup(tiny):
    """Small-block paged KV with decode-time growth crossing block
    boundaries: steady state never retraces."""
    cfg, params = tiny
    _assert_zero_recompiles_after_warmup(cfg, params, kv_block_size=8)


def test_sanitized_engine_runs_clean(tiny):
    """EngineConfig.sanitize audits the ledger every scheduler iteration
    and a healthy run produces no report."""
    cfg, params = tiny
    prompts, max_news = _mixed_batch(cfg)
    engine = _engine(cfg, params, kv_block_size=8, sanitize=True).start()
    try:
        results = _run(engine, prompts, max_news)
        assert all(r.finish_reason == "length" for r in results)
        assert engine._sanitizer is not None
        assert engine._sanitizer.checks > 0
        engine.drain(timeout=60)
        assert engine.sanitizer_report == []
        assert engine._scheduler_error is None
    finally:
        engine.shutdown()


@pytest.mark.chaos
def test_chaos_injected_block_leak_is_reported(tiny):
    """Drop one decref on the floor at slot release (chaos site
    ``slots-release``): the ledger sanitizer must fail the engine loudly
    within one iteration and name the leaked block's last owner."""
    cfg, params = tiny
    engine = _engine(cfg, params, kv_block_size=8, prefix_cache_blocks=0,
                     sanitize=True).start()
    try:
        # a clean request first: the sanitizer has passing checks and a
        # recorded owner map before the fault fires
        ok = engine.submit([5, 9, 3, 7], max_new_tokens=4,
                           use_eos_stop=False).result(timeout=600)
        assert ok.finish_reason == "length"
        assert engine._sanitizer.checks > 0

        chaos().leak_kv_blocks("slots-release")
        h = engine.submit([2, 4, 6, 8, 10], max_new_tokens=4,
                          use_eos_stop=False)
        rid = h.rid
        h.result(timeout=600)  # completes; its release leaks one ref

        deadline = time.monotonic() + 60
        while engine._scheduler_error is None and time.monotonic() < deadline:
            time.sleep(0.01)
        err = engine._scheduler_error
        assert isinstance(err, LedgerError), f"no ledger failure: {err!r}"
        assert "leaked" in str(err)

        report = engine._sanitizer.leak_report(engine)
        assert report, "leak_report should name the leaked block"
        assert any(rid in leak["last_owners"] for leak in report), \
            f"{rid} missing from {report}"
        assert any(("kv_leak", "slots-release") == ev[:2]
                   for ev in chaos().events)
    finally:
        chaos().reset()
        engine.shutdown()


_REP_PROMPTS = [[5, 9, 3, 5, 9, 3, 5, 9, 3, 5, 9],
                [7, 7, 7, 7, 7, 7, 7],
                [4, 8, 2, 4, 8, 2, 4, 8],
                [11, 6, 11, 6, 11, 6, 11]]


def test_spec_zero_recompiles_after_warmup(tiny):
    """Speculative serving in steady state never retraces: the verify
    executable has one fixed [slots, W] shape whatever mix of draft
    lengths the slots carry (short drafts pad into the window), and the
    accept/rollback bookkeeping is pure host arithmetic.  Repetitive
    prompts so the drafter really engages — asserted, else this test
    would vouch for a path it never ran."""
    cfg, params = tiny
    engine = _engine(cfg, params, kv_block_size=8, spec_draft_len=3).start()
    try:
        # two warmup passes: prefill/decode/verify executables plus the
        # prefix-cache hit path (identical prompts) all compile here
        _run(engine, _REP_PROMPTS, [20] * 4)
        _run(engine, _REP_PROMPTS, [20] * 4)
        with no_recompiles():
            results = _run(engine, _REP_PROMPTS, [20] * 4)
    finally:
        engine.shutdown()
    for p, r in zip(_REP_PROMPTS, results):
        assert r.finish_reason == "length"
        assert r.tokens == _reference(cfg, params, p, 20)
    assert engine.metrics.snapshot()["spec_steps"] > 0


@pytest.mark.chaos
def test_chaos_block_leak_reported_under_spec(tiny):
    """The ledger sanitizer keeps its one-iteration detection bar with
    speculation on: verify steps allocate draft rows through the same
    append path, and a dropped decref at slot release is still caught
    and attributed."""
    cfg, params = tiny
    engine = _engine(cfg, params, kv_block_size=8, prefix_cache_blocks=0,
                     spec_draft_len=3, sanitize=True).start()
    try:
        ok = engine.submit(_REP_PROMPTS[1], max_new_tokens=20,
                           use_eos_stop=False).result(timeout=600)
        assert ok.finish_reason == "length"
        assert engine.metrics.snapshot()["spec_steps"] > 0
        assert engine._sanitizer.checks > 0

        chaos().leak_kv_blocks("slots-release")
        h = engine.submit(_REP_PROMPTS[2], max_new_tokens=20,
                          use_eos_stop=False)
        rid = h.rid
        h.result(timeout=600)

        deadline = time.monotonic() + 60
        while engine._scheduler_error is None and time.monotonic() < deadline:
            time.sleep(0.01)
        err = engine._scheduler_error
        assert isinstance(err, LedgerError), f"no ledger failure: {err!r}"
        report = engine._sanitizer.leak_report(engine)
        assert any(rid in leak["last_owners"] for leak in report), \
            f"{rid} missing from {report}"
    finally:
        chaos().reset()
        engine.shutdown()


# ---------------------------------------------------------------------------
# Resident draft model + tree verification (round 15)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_draft():
    """A draft even tinier than the target: one layer, quarter hidden."""
    cfg = tiny_config(num_layers=1, hidden_size=32, vocab_size=64,
                      make_vocab_size_divisible_by=8)
    params = model_lib.init_params(jax.random.key(1), cfg)
    return cfg, params


def _tree_engine(tiny, draft, **overrides):
    cfg, params = tiny
    dcfg, dparams = draft
    kw = dict(max_batch_size=4, max_seq_len=64, max_queue_size=16,
              idle_wait_s=0.005, kv_block_size=8, spec_draft_len=3)
    kw.update(overrides)
    return ServingEngine(cfg, params, EngineConfig(**kw),
                         draft_cfg=dcfg, draft_params=dparams)


def test_tree_spec_trajectories_bitwise_across_modes(tiny, tiny_draft):
    """Resident-draft tree speculation end to end: greedy trajectories
    equal the non-speculative generate_tokens reference in pipelined AND
    sync decode, a sampled rider produces the identical token stream in
    both modes (its seed/counter bookkeeping is untouched by tree
    commits), and the spec counters attribute the steps to the model
    drafter."""
    cfg, params = tiny
    prompts, max_news = _mixed_batch(cfg)
    rider_tokens = {}
    for pipelined in (True, False):
        engine = _tree_engine(tiny, tiny_draft,
                              pipeline_decode=pipelined).start()
        try:
            results = _run(engine, prompts, max_news)
            h2 = engine.submit(prompts[0], max_new_tokens=8,
                               temperature=0.9, top_k=5, seed=7,
                               use_eos_stop=False)
            rider_tokens[pipelined] = h2.result(timeout=600).tokens
        finally:
            engine.shutdown()
        assert engine._scheduler_error is None, engine._scheduler_error
        for p, n, r in zip(prompts, max_news, results):
            assert r.tokens == _reference(cfg, params, p, n)
        snap = engine.metrics.snapshot()
        assert snap["spec_steps"] > 0
        assert "model" in snap["spec_by_source"]
    assert len(rider_tokens[True]) == len(prompts[0]) + 8
    assert rider_tokens[True] == rider_tokens[False]


def test_tree_spec_zero_recompiles_after_warmup(tiny, tiny_draft):
    """With a draft model resident, steady state still never retraces:
    draft prefill/absorb/expand and the tree verify all have one fixed
    shape each (trees pad to the static node budget), so the third pass
    runs entirely on warm executables.  Random prompts — the model
    drafter engages on ANY traffic, no repetition needed."""
    cfg, params = tiny
    prompts, max_news = _mixed_batch(cfg)
    engine = _tree_engine(tiny, tiny_draft).start()
    try:
        _run(engine, prompts, max_news)
        _run(engine, prompts, max_news)
        with no_recompiles():
            results = _run(engine, prompts, max_news)
    finally:
        engine.shutdown()
    for p, n, r in zip(prompts, max_news, results):
        assert r.finish_reason == "length"
        assert r.tokens == _reference(cfg, params, p, n)
    assert engine.metrics.snapshot()["spec_steps"] > 0
    assert "model" in engine.metrics.snapshot()["spec_by_source"]


def test_tree_spec_block_boundary_ledger_balanced(tiny, tiny_draft):
    """Trees crossing KV block boundaries under the ledger sanitizer:
    kv_block_size=8 with draft_len=3 means accepted paths regularly
    straddle block edges (target AND shadow draft pool), and the
    per-iteration ledger audit plus the drain report must stay clean."""
    cfg, params = tiny
    prompts, max_news = _mixed_batch(cfg)
    engine = _tree_engine(tiny, tiny_draft, sanitize=True).start()
    try:
        results = _run(engine, prompts, max_news)
        assert all(r.finish_reason == "length" for r in results)
        assert engine._sanitizer is not None
        assert engine._sanitizer.checks > 0
        engine.drain(timeout=60)
        assert engine.sanitizer_report == []
        assert engine._scheduler_error is None
    finally:
        engine.shutdown()
    for p, n, r in zip(prompts, max_news, results):
        assert r.tokens == _reference(cfg, params, p, n)


def test_tree_spec_eos_mid_tree(tiny):
    """EOS landing in the MIDDLE of an accepted tree path: a self-draft
    (draft == target) accepts whole chains, so the EOS token is committed
    inside a multi-token burst — generation must stop AT the EOS token
    with the exact reference prefix, and the tokens drafted past it must
    never surface."""
    cfg, params = tiny
    prompt = [5, 9, 3]
    ref = _reference(cfg, params, prompt, 8)
    gen = ref[len(prompt):]
    eos = gen[2]  # a token the greedy rollout actually emits
    engine = _tree_engine(tiny, (cfg, params)).start()
    try:
        r = engine.submit(prompt, max_new_tokens=8,
                          eos_id=eos).result(timeout=600)
    finally:
        engine.shutdown()
    assert engine._scheduler_error is None, engine._scheduler_error
    assert r.finish_reason == "eos"
    stop = gen.index(eos) + 1
    assert r.tokens == ref[:len(prompt) + stop]
    assert engine.metrics.snapshot()["spec_steps"] > 0


def test_tree_spec_perfect_draft_acceptance(tiny):
    """Self-draft (draft == target) is the acceptance upper bound: the
    main chain always matches target argmax, so the accepted-per-proposed
    rate must be high while trajectories stay bitwise."""
    cfg, params = tiny
    prompts, max_news = _mixed_batch(cfg)
    engine = _tree_engine(tiny, (cfg, params)).start()
    try:
        results = _run(engine, prompts, max_news)
    finally:
        engine.shutdown()
    assert engine._scheduler_error is None, engine._scheduler_error
    for p, n, r in zip(prompts, max_news, results):
        assert r.tokens == _reference(cfg, params, p, n)
    snap = engine.metrics.snapshot()
    rate = snap["spec_accepted"] / max(1, snap["spec_proposed"])
    assert rate > 0.5, snap


def test_tree_spec_forced_hedge_compaction(tiny, monkeypatch):
    """Force the accept walk onto the HEDGE branch: patch the draft's
    chain heads so the main chain carries a deliberately wrong token and
    the hedge seat carries the draft's true head.  Acceptance then lands
    on a node whose index differs from its depth, exercising the
    cache_move_rows re-pack — trajectories must stay bitwise through it."""
    from megatron_llm_tpu.serving import engine as engine_mod

    cfg, params = tiny
    prompts, max_news = _mixed_batch(cfg)
    real_absorb = engine_mod.ServingEngine._draft_absorb
    hedge_hits = {"n": 0}

    def fake_absorb(self, plans, tables):
        heads = real_absorb(self, plans, tables)
        out = {}
        for slot, toks in heads.items():
            wrong = (int(toks[0]) + 1) % cfg.vocab_size
            out[slot] = [wrong, int(toks[0])]
            hedge_hits["n"] += 1
        return out

    monkeypatch.setattr(engine_mod.ServingEngine, "_draft_absorb",
                        fake_absorb)
    engine = _tree_engine(tiny, (cfg, params)).start()
    try:
        results = _run(engine, prompts, max_news)
    finally:
        engine.shutdown()
    assert engine._scheduler_error is None, engine._scheduler_error
    for p, n, r in zip(prompts, max_news, results):
        assert r.tokens == _reference(cfg, params, p, n)
    assert hedge_hits["n"] > 0
    assert engine.metrics.snapshot()["spec_accepted"] > 0
