"""Import smoke tests (parity: reference tests/test_basic.py)."""


def test_imports():
    import megatron_llm_tpu
    from megatron_llm_tpu import config
    from megatron_llm_tpu.models import families, model, sharding, transformer
    from megatron_llm_tpu.ops import activations, attention, norms, rope
    from megatron_llm_tpu.parallel import cross_entropy, mesh

    assert megatron_llm_tpu.__version__


def test_presets():
    from megatron_llm_tpu.config import PRESETS, get_preset

    for name in PRESETS:
        cfg = get_preset(name)
        # a head's width is hidden / heads, or stated (kv_channels: 48
        # heads of 128 on a 2048-wide stream)
        assert (cfg.kv_channels
                or cfg.hidden_size % cfg.num_attention_heads == 0)


def test_tiny_forward():
    import jax
    import jax.numpy as jnp

    from megatron_llm_tpu.config import tiny_config
    from megatron_llm_tpu.models import model

    cfg = tiny_config()
    params = model.init_params(jax.random.key(0), cfg)
    tokens = jnp.zeros((2, 16), dtype=jnp.int32)
    logits = jax.jit(lambda p, t: model.forward(cfg, p, t))(params, tokens)
    assert logits.shape == (2, 16, cfg.padded_vocab_size())
    assert bool(jnp.all(jnp.isfinite(logits)))


def test_initialize_distributed_single_host_noop():
    """No coordinator configured → single-host no-op, idempotent."""
    from megatron_llm_tpu.initialize import (
        initialize_distributed,
        is_initialized,
    )

    initialize_distributed()
    assert is_initialized()
    initialize_distributed()  # second call is a no-op


def test_performance_xla_flags_wellformed():
    from megatron_llm_tpu.initialize import (PERFORMANCE_XLA_FLAGS,
                                             performance_xla_flags)

    s = performance_xla_flags()
    assert all(f.startswith("--xla") and "=" in f
               for f in PERFORMANCE_XLA_FLAGS)
    assert all(f in s for f in PERFORMANCE_XLA_FLAGS)
