"""Granite 4.0-H through the serving engine: every layer but the attention
ones keeps a state-space state a slot, so the slots are bounded by state
beside a small pool; what its spans, gauges and counter say of it."""

import jax
import numpy as np
import pytest

from megatron_llm_tpu.config import granite_hybrid_config
from megatron_llm_tpu.kernels.mamba_step import heads_per_step
from megatron_llm_tpu.models import model as model_lib
from megatron_llm_tpu.obs.registry import REGISTRY
from megatron_llm_tpu.serving import EngineConfig, ServingEngine

# three periods of (ssm, ssm, full): six state layers, three that attend
TINY = dict(num_layers=9, layer_pattern=("ssm", "ssm", "full"),
            hidden_size=64, num_attention_heads=4, num_kv_heads=2,
            kv_channels=16, ffn_hidden_size=96, vocab_size=512,
            mamba_num_heads=8, mamba_head_dim=8, mamba_n_groups=1,
            mamba_state_size=16, mamba_chunk_size=8,
            params_dtype="float32", max_position_embeddings=512,
            make_vocab_size_divisible_by=8, embedding_multiplier=3.0,
            residual_multiplier=0.5, attention_multiplier=0.1,
            logits_scaling=2.0)
ENGINE = dict(max_batch_size=2, max_seq_len=128, kv_block_size=16,
              prefill_bucket=32, prefix_cache_blocks=0, max_queue_size=64)


@pytest.fixture(scope="module")
def model():
    cfg = granite_hybrid_config("4.0-h-micro", **TINY)
    return cfg, jax.jit(lambda k: model_lib.init_params(k, cfg))(
        jax.random.key(0))


def serve(cfg, params, prompts, new=6):
    eng = ServingEngine(cfg, params, EngineConfig(**ENGINE)).start()
    try:
        handles = [eng.submit(p, new, use_eos_stop=False,
                              return_logprobs=True, seed=0) for p in prompts]
        return [h.result(timeout=300) for h in handles], eng
    finally:
        eng.shutdown()


def prompts_of(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 500, size=n).tolist() for n in lengths]


def test_a_reused_slot_serves_as_a_fresh_engine_does(model):
    """Five requests over two slots, pipelined, across three periods of
    the scan: a slot's state is replaced whole at admission, and a slot's
    neighbour never touches it."""
    cfg, params = model
    prompts = prompts_of([40, 57, 33, 64, 21])
    shared, eng = serve(cfg, params, prompts)
    for p, got in zip(prompts, shared):
        (alone,), _ = serve(cfg, params, [p])
        assert got.tokens == alone.tokens
        np.testing.assert_allclose(got.logprobs, alone.logprobs, atol=2e-5)
    rec = eng.slots.rec
    assert sorted(rec) == ["load", "rows", "ssm", "ssm_conv"]
    # [state layers, slots, ...]: six of the nine layers keep state, the
    # pool pages the other three's K/V
    assert rec["ssm"].shape == (6, 2, 8, 8, 16)
    assert rec["ssm_conv"].shape == (6, 2, 3 * 96)
    assert rec["load"].shape == (9, 0)          # no layer routes
    assert eng.slots.k_pool.shape[0] == cfg.kv_layers == 3


def test_the_spans_say_how_the_state_was_stepped_and_installed(model):
    cfg, params = model
    _, eng = serve(cfg, params, prompts_of([40, 50], seed=1), new=5)
    snap = eng.metrics.snapshot()
    rec = eng.slots.rec
    state = rec["ssm"].nbytes + rec["ssm_conv"].nbytes
    assert snap["rec_state_bytes_by_kind"] == {"mamba": state}
    assert snap["rec_state_slots"] == 2
    assert snap["ssm_positions"]["prefill"] == 90
    fams = {f.name: f for f in REGISTRY.collect()}
    assert {s.labels["kind"]: s.value for s in
            fams["serving_rec_state_bytes"].samples} == {"mamba": state}
    spans = eng.trace.chrome_trace()["traceEvents"]
    prefills = [e for e in spans if e["name"] == "prefill"]
    decodes = [e for e in spans if e["name"] == "decode"]
    assert len(prefills) == 2 and decodes
    assert all(e["args"]["state_kinds"] == "mamba"
               for e in prefills + decodes)
    # a prefill's install writes one slot's share of the state; a decode
    # span says how many heads of a slot a grid step of the kernel took
    assert {e["args"]["state_installed_bytes"] for e in prefills} == {
        state // 2}
    assert all(e["args"]["ssm_step"] == "mixer"
               and e["args"]["ssm_tile"] == heads_per_step(8, 1) == 8
               for e in decodes)
    assert not any("ssm_tile" in e["args"] for e in prefills)
    assert not any("state_installed_bytes" in e["args"] for e in decodes)
    assert {e["args"]["live"] for e in decodes} <= {1, 2}
