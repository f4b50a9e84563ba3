"""The phi4flash stack through the serving engine: five kinds of slot
state in one manager (paged K/V for the ONE full layer, which seven cross
layers read; a Mamba-1 state and tail, and a window ring, a slot), the
prefill cut to one row past the boundary between the two decoders, and
everything the engine refuses for it."""

import dataclasses

import jax
import numpy as np
import pytest

from benchmarks.reference import phi4flash as ref
from megatron_llm_tpu.config import phi4flash_config
from megatron_llm_tpu.kernels.flash_decode import pool_walk
from megatron_llm_tpu.models import model as model_lib
from megatron_llm_tpu.obs.registry import REGISTRY
from megatron_llm_tpu.serving import EngineConfig, ServingEngine
from megatron_llm_tpu.serving import engine as engine_lib
from megatron_llm_tpu.serving.adapters.registry import AdapterRegistry

TINY = dict(layer_runs=((("ssm1", "window"), 2), (("ssm1", "full"), 1),
                        (("gmu", "cross"), 2)),
            hidden_size=64, num_attention_heads=8, num_kv_heads=4,
            kv_channels=8, ffn_hidden_size=96, sliding_window=8,
            mamba1_inner=128, mamba1_state_size=4, mamba1_dt_rank=4,
            vocab_size=512, params_dtype="float32",
            make_vocab_size_divisible_by=8, max_position_embeddings=1024)
ENGINE = dict(max_batch_size=2, max_seq_len=128, kv_block_size=16,
              prefill_bucket=32, prefix_cache_blocks=0, max_queue_size=64)


@pytest.fixture(scope="module")
def model():
    cfg = phi4flash_config(**TINY)
    return cfg, jax.jit(lambda k: model_lib.init_params(k, cfg))(
        jax.random.key(0))


def serve(cfg, params, prompts, new=12, logprobs=True, **kw):
    eng = ServingEngine(cfg, params, EngineConfig(**{**ENGINE, **kw})).start()
    try:
        handles = [eng.submit(p, new, use_eos_stop=False,
                              return_logprobs=logprobs, seed=0)
                   for p in prompts]
        return [h.result(timeout=300) for h in handles], eng
    finally:
        eng.shutdown()


def prompts_of(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 500, size=n).tolist() for n in lengths]


def test_a_reused_slot_serves_as_a_fresh_engine_does(model):
    """Five requests over two slots, pipelined: a slot's states and rings
    are replaced whole at admission, so its last tenant (and the
    speculative step that advanced them after it retired) leaves nothing
    behind; and what is served is the reference's forward."""
    cfg, params = model
    prompts = prompts_of([40, 57, 33, 64, 21])
    shared, eng = serve(cfg, params, prompts)
    meta = ref.meta_of(cfg)
    longest = max(len(r.tokens) for r in shared)
    for p, got in zip(prompts, shared):
        (alone,), _ = serve(cfg, params, [p])
        assert got.tokens == alone.tokens
        np.testing.assert_allclose(got.logprobs, alone.logprobs, atol=2e-5)
        # float32 on both sides: the engine's prefill, its every-row
        # log-prob pass and its 12 decode steps through pool, rings
        # (which wrap: window 8) and states, against every row through
        # every layer
        # (at one length for every request, the reference's forward being
        # compiled a length: a position depends on no token behind it)
        padded = got.tokens + [1] * (longest - len(got.tokens))
        want = np.asarray(ref.token_logprobs(params, padded, meta))[
            :len(got.tokens) - 1]
        np.testing.assert_allclose(got.logprobs, want, atol=2e-5)
    rec = eng.slots.rec
    assert rec["ssm1"].shape == (3, 2, 4, 128)       # [layers, slots, ...]
    assert rec["win_k"].shape == (2, 2, 2, 8, 16)
    # the pool pages the one full layer's K/V only: 4 x 8 keys and 2 x 16
    # values a position, no second copy of the values
    k_pool, v_pool = eng.slots.k_pool, eng.slots.v_pool
    assert k_pool.shape[0] == v_pool.shape[0] == cfg.kv_layers == 1
    assert k_pool.shape[2:] == (4, 16, 8) and v_pool.shape[2:] == (2, 16, 16)


def test_the_state_and_the_pool_are_gauged_by_kind(model):
    cfg, params = model
    prompts = prompts_of([40, 50], seed=1)
    _, eng = serve(cfg, params, prompts, new=5)
    snap = eng.metrics.snapshot()
    rec = eng.slots.rec
    by_kind = {"ssm1": rec["ssm1"].nbytes + rec["ssm1_conv"].nbytes,
               "window": rec["win_k"].nbytes + rec["win_v"].nbytes}
    assert snap["rec_state_bytes_by_kind"] == by_kind
    # a slot: 3 x (4 x 128 + 3 x 128) float32 and 2 x 8 rows of 2 x 32
    assert by_kind == {"ssm1": 2 * 3 * 7 * 128 * 4,
                       "window": 2 * 2 * 8 * 64 * 4}
    pool = eng.slots.k_pool.nbytes + eng.slots.v_pool.nbytes
    assert snap["kv_pool_bytes_by_kind"] == {"kv": pool}
    blocks = eng.slots.k_pool.shape[1]
    assert pool == blocks * 16 * 64 * 4              # ONE layer's rows
    fams = {f.name: f for f in REGISTRY.collect()}
    assert {s.labels["kind"]: s.value for s in
            fams["serving_rec_state_bytes"].samples} == by_kind
    assert {s.labels["kind"]: s.value for s in
            fams["serving_kv_pool_bytes"].samples} == {"kv": pool}
    # every prompt position once at its prefill; every fed token of every
    # live slot a step (the pipelined step's one speculative token a
    # request may be counted too)
    assert snap["ssm_positions"]["prefill"] == 90
    assert 2 * 4 <= snap["ssm_positions"]["decode"] <= 2 * 4 + 2
    assert {s.labels["phase"]: s.value for s in
            fams["serving_ssm_positions_total"].samples} == \
        snap["ssm_positions"]
    # the walks: a step's live positions, once by the full layer and once
    # by each of the two cross layers
    walks = snap["kv_walks"]
    assert walks["cross"] == 2 * walks["full"] > 0
    assert 4 * 90 <= walks["full"] <= 5 * 90 + 30
    assert {s.labels["layer_kind"]: s.value for s in
            fams["serving_kv_walks_total"].samples} == walks


def test_the_spans_say_what_a_prefill_and_a_step_did(model):
    cfg, params = model
    eng = ServingEngine(cfg, params, EngineConfig(**ENGINE)).start()
    try:
        a = eng.submit(prompts_of([40])[0], 4, use_eos_stop=False,
                       return_logprobs=True, seed=0).result(timeout=300)
        b = eng.submit(prompts_of([50])[0], 4, use_eos_stop=False,
                       seed=0).result(timeout=300)
    finally:
        eng.shutdown()
    assert len(a.tokens) == 44 and len(b.tokens) == 54
    spans = eng.trace.chrome_trace()["traceEvents"]
    prefills = [e for e in spans if e["name"] == "prefill"]
    decodes = [e for e in spans if e["name"] == "decode"]
    assert len(prefills) == 2 and decodes
    assert all(e["args"]["state_kinds"] == "ssm1+window"
               for e in prefills + decodes)
    # the log-prob request's later layers ran its whole bucket in a
    # second pass, the other's one row
    assert [e["args"]["cross_rows"] for e in prefills] == [64, 1]
    assert all(e["args"]["state_installed_bytes"]
               == 3 * 7 * 128 * 4 + 2 * 8 * 64 * 4 for e in prefills)
    assert all(e["args"]["live"] == 1 for e in decodes)
    assert {e["args"]["live_positions"] for e in decodes} >= {40, 41, 50}
    # a call of the step's paged walk: one live slot's head groups, every
    # one but the call's first with its first copies already in flight
    heads, kvg, _ = pool_walk(eng.slots.k_pool, eng.slots.v_pool,
                              eng.slots.tables.shape[1])
    assert all((e["args"]["walk_steps"], e["args"]["walk_prefetched"])
               == (heads // kvg, heads // kvg - 1) for e in decodes)
    # no field of the other state-space mixer's kernel
    assert not any("ssm_step" in e["args"] or "gdn" in e["args"]
                   for e in prefills + decodes)


def test_a_checked_request_decodes_from_the_timed_prefill(model, monkeypatch):
    """A request that wants its prompt's log-probs: its caches, its
    states, its rings and its first token come from the program every
    prefill runs (one row through the second decoder), the prompt's
    log-probs from a second pass that keeps nothing."""
    cfg, params = model
    calls = []
    timed, second = engine_lib._prefill_impl, engine_lib._prompt_logprobs_impl

    def prefill(*a, **kw):
        calls.append(("prefill", kw["want_logprobs"]))
        out = timed(*a, **kw)
        assert out[1] is None            # no log-probs from this program
        return out

    def logprobs(*a, **kw):
        calls.append(("logprobs", None))
        return second(*a, **kw)

    monkeypatch.setattr(engine_lib, "_prefill_impl", prefill)
    monkeypatch.setattr(engine_lib, "_prompt_logprobs_impl", logprobs)
    prompt = prompts_of([45], seed=3)
    (checked,), _ = serve(cfg, params, prompt, new=10, logprobs=True)
    assert calls == [("prefill", False), ("logprobs", None)]
    del calls[:]
    (plain,), _ = serve(cfg, params, prompt, new=10, logprobs=False)
    assert calls == [("prefill", False)]
    # the same program, the same caches: the same tokens, first to last
    assert checked.tokens == plain.tokens
    assert len(checked.logprobs) == 45 - 1 + 10


def test_a_stack_without_a_row_cut_takes_its_logprobs_from_the_prefill():
    from megatron_llm_tpu.config import tiny_config

    cfg = tiny_config()
    eng = ServingEngine(cfg, model_lib.init_params(jax.random.key(0), cfg),
                        EngineConfig(max_batch_size=2, max_seq_len=64))
    assert not eng._row_cut and not eng._kv_readers
    assert "kv_walks" in eng.metrics.snapshot()
    assert eng.metrics.snapshot()["kv_walks"] == {}


REFUSED = {
    "prefix_cache": (dict(prefix_cache_blocks=8), {}, "prefix_cache_blocks"),
    "speculation": (dict(spec_draft_len=2), {}, "speculation"),
    "draft_model": ({}, dict(draft=True), "speculation"),
    "int8_pool": ({}, dict(model=dict(kv_cache_quant="int8")), "int8"),
    "mesh": ({}, dict(mesh=True), "mesh"),
    "adapters": ({}, dict(adapters=True), "adapters"),
    "chunked_prefill": (dict(prefill_chunk=32), {}, "prefill_chunk"),
    "host_tier": (dict(host_kv_blocks=8), {}, "host_kv_blocks"),
    "disaggregation": (dict(role="prefill"), {}, "role"),
}


@pytest.mark.parametrize("case", sorted(REFUSED))
def test_what_moves_kv_alone_is_refused_at_construction(model, case):
    cfg, params = model
    engine_kw, extra, said = REFUSED[case]
    cfg = dataclasses.replace(cfg, **extra.get("model", {}))
    kw = {}
    if extra.get("draft"):
        kw.update(draft_cfg=cfg, draft_params=params)
    if extra.get("mesh"):
        from megatron_llm_tpu.config import ParallelConfig
        from megatron_llm_tpu.parallel.mesh import build_mesh

        kw["mesh"] = build_mesh(ParallelConfig(tensor_parallel=2),
                                devices=jax.devices()[:2])
    if extra.get("adapters"):
        kw["adapters"] = AdapterRegistry.__new__(AdapterRegistry)
    with pytest.raises(ValueError, match="hybrid stack") as err:
        ServingEngine(cfg, params, EngineConfig(**{**ENGINE, **engine_kw}),
                      **kw)
    assert said in str(err.value)


def test_a_slot_is_not_shipped(model):
    cfg, params = model
    eng = ServingEngine(cfg, params, EngineConfig(**ENGINE)).start()
    try:
        with pytest.raises(RuntimeError, match="no recurrent state"):
            eng.call_in_scheduler(lambda: eng._extract_slot(0))
        with pytest.raises(RuntimeError, match="no recurrent state"):
            eng.call_in_scheduler(lambda: eng.install_shipment(None))
    finally:
        eng.shutdown()


def test_the_normal_entry_point_names_the_family():
    """``run_text_generation_server --model phi4flash --size
    mini-flash-reasoning`` builds the preset the benchmark runs."""
    import inspect

    from megatron_llm_tpu.models import families
    from megatron_llm_tpu.tools import run_text_generation_server as tool

    assert families.phi4flash().cfg == phi4flash_config()
    assert families.phi4flash("mini-flash-reasoning", **TINY).cfg \
        == phi4flash_config(**TINY)
    source = inspect.getsource(tool.main)
    assert '"phi4flash": families.phi4flash' in source
    assert '"phi4flash"],' in source
