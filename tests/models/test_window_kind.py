"""The "window" block kind means one thing in every stack: a ring of
``sliding_window`` rows a slot under ``RING_NAMES``, installed from a
prompt's last rows (``diff_attention.ring_of``), attended by
``diff_attention.attend_ring`` (its kernel where the configuration asks
for kernels), written once a step (``ring_append_rows``).  The same tests
on PR 56's stack of runs (differential attention, no rotation, a ring row
a pair of key heads) and on the Laguna stack of one run (grouped
heads, keys rotated at their own positions, a ring row a key head)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_llm_tpu.models import diff_attention
from megatron_llm_tpu.models import model as model_lib
from megatron_llm_tpu.models import transformer
from tests.models import test_laguna_stack, test_phi4flash_stack

STACKS = {"runs": test_phi4flash_stack, "period": test_laguna_stack}
TOL = 5e-6


@pytest.fixture(scope="module", params=sorted(STACKS))
def stack(request):
    mod = STACKS[request.param]
    cfg = mod.tiny()
    params = jax.jit(lambda k: model_lib.init_params(k, cfg))(
        jax.random.key(0))
    params = jax.tree_util.tree_map_with_path(mod._shake, params)
    tokens = jax.random.randint(
        jax.random.key(1), (1, mod.PROMPT + mod.STEPS + 1), 1, 500)
    mod.served_programs(cfg)    # before any test's body, so any patch
    return mod, cfg, params, tokens


def test_one_kind_one_state(stack):
    """Both stacks keep their window layers' rings under the same names,
    in one layout: [window layers, slots, value heads, window, a value
    head's width], and no pool layer."""
    _mod, cfg, _params, _tokens = stack
    rec = model_lib.init_rec_state(cfg, 2)
    shape = (cfg.window_layers, 2, cfg.v_heads, cfg.sliding_window,
             cfg.v_head_width)
    assert tuple(transformer.RING_NAMES) == ("win_k", "win_v")
    assert rec["win_k"].shape == rec["win_v"].shape == shape
    assert model_lib.REC_STATE_KINDS["window"] == transformer.RING_NAMES
    assert cfg.kv_layers == cfg.layer_kinds.count("full")
    assert not hasattr(model_lib, "ring_append_rows")   # the scan's write


def test_a_prompts_install_fills_the_ring_and_the_steps_rewrite_it(stack):
    """A bucket-padded prompt of 19 at window 8 leaves no row of a ring
    empty (the install wrapped), and the 40 steps behind it rewrite every
    row (five more wraps); what the rows hold is held by the logits
    (``test_with_the_kernels_on...``, the stacks' own tests)."""
    mod, cfg, params, tokens = stack
    _logits, rec0 = mod.served_logits(cfg, params, tokens, steps=0)
    W = cfg.sliding_window
    whole, rec = mod.served_logits(cfg, params, tokens, steps=mod.STEPS)
    assert whole.shape[0] == mod.STEPS + 1
    for name in transformer.RING_NAMES:
        a0, a = np.asarray(rec0[name]), np.asarray(rec[name])
        assert a0.shape[3] == W and np.abs(a0).min(axis=(0, 1, 2, 4)).all()
        # every row was rewritten by the steps (40 > 8)
        assert (np.abs(a - a0).max(axis=(0, 1, 2, 4)) > 0).all()


def test_with_the_kernels_on_the_steps_are_the_plain_steps(stack):
    """The same prefill and twelve steps with ``attention_impl`` "flash"
    (the kernels interpreted): the window layers' prompt under
    ``flash_attention``'s band, their steps by ``kernels/ring_decode.py``
    on the stacked rings and the layer's index (window 8: the rings wrap
    twice) give the plain composition's logits and rings."""
    mod, cfg, params, tokens = stack
    want, rec = mod.served_logits(cfg, params, tokens, steps=12)
    got, rec_k = mod.served_logits(mod.tiny(attention_impl="flash"), params,
                                   tokens, steps=12)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)
    for name in transformer.RING_NAMES:
        np.testing.assert_allclose(rec_k[name], rec[name], atol=1e-5)


def test_the_paged_step_writes_the_rings_in_place(stack, monkeypatch):
    """Three decode steps on the paged route (the walk interpreted): the
    full layers' rows go to the pool, the rings take a row each at
    ``position % window`` by ONE ``ring_append_rows`` in the step's
    program; logits and rings are the dense route's."""
    from megatron_llm_tpu.ops import attention as attn_ops

    mod, cfg, params, tokens = stack
    P, B = mod.PROMPT, mod.BUCKET
    got_dense, rec = mod.served_logits(cfg, params, tokens, steps=3)
    writes = []
    append = transformer.ring_append_rows
    monkeypatch.setattr(transformer, "ring_append_rows", lambda *a: (
        writes.append(1), append(*a))[1])
    with jax.default_matmul_precision("highest"):
        k, v = model_lib.init_kv_cache(cfg, 1, 128)
        rec0 = model_lib.init_rec_state(cfg, 1)
        padded = jnp.zeros((1, B), jnp.int32).at[:, :P].set(tokens[:, :P])
        # (a prefill of its own, traced under the patch above)
        _l, k, v, rec0 = mod._programs(cfg)[0](params, padded, k, v, rec0)
        assert not writes                 # a prompt installs, it appends not
        bk, T = 16, 8
        tables = jnp.arange(1, T + 1, dtype=jnp.int32)[None]
        k_pool, v_pool = model_lib.init_kv_pool(cfg, T + 1, bk)
        k_pool, v_pool = (model_lib.cache_scatter_blocks(p_, d_, tables[0])
                          for p_, d_ in ((k_pool, k), (v_pool, v)))
        monkeypatch.setattr(attn_ops, "_backend", lambda: "tpu")
        monkeypatch.setattr(
            attn_ops, "paged_decode_kernel_eligible",
            lambda s, d, block, platform: s == 1)
        # one executable for the three steps
        step = jax.jit(lambda token, k_pool, v_pool, t, rec:
                       model_lib.forward_paged_hybrid(
                           cfg, params, token, k_pool, v_pool, tables, t, rec,
                           jnp.ones((1,), bool)))
        out = []
        for t in range(P, P + 3):
            logits, k_pool, v_pool, rec0 = step(
                tokens[:, t:t + 1], k_pool, v_pool, jnp.array([t]), rec0)
            out.append(np.asarray(logits[0, 0]))
    np.testing.assert_allclose(np.stack(out), got_dense[1:], atol=TOL)
    assert len(writes) == 1               # in the one trace of the step
    assert k_pool.shape[0] == v_pool.shape[0] == cfg.kv_layers
    for name in transformer.RING_NAMES:
        np.testing.assert_allclose(rec0[name], rec[name], atol=1e-5)


def test_a_stale_row_is_never_counted(stack):
    """A ring that holds garbage where a short sequence has not written
    yet: a step at position 3 (window 8) counts rows 0-2 and its own,
    whatever rows 3-7 hold."""
    _mod, cfg, _params, _tokens = stack
    w = cfg if cfg.diff_attention else cfg.window_layer_config
    kv, nv, d = cfg.kv_heads, cfg.v_heads, cfg.head_dim
    W, heads = cfg.sliding_window, w.num_attention_heads
    keys = iter(jax.random.split(jax.random.key(3), 8))
    ring = lambda: jax.random.normal(  # noqa: E731
        next(keys), (2, 1, nv, W, cfg.v_head_width), jnp.float32)
    ring_k, ring_v = ring(), ring()
    q = jax.random.normal(next(keys), (1, 1, heads, d), jnp.float32)
    k_new = jax.random.normal(next(keys), (1, kv, 1, d), jnp.float32)
    v_new = jax.random.normal(next(keys), (1, nv, 1, cfg.v_head_width),
                              jnp.float32)
    pos = jnp.array([3])
    with jax.default_matmul_precision("highest"):
        a = diff_attention.attend_ring(w, q, ring_k, ring_v, 1, k_new, v_new,
                                       pos)
        junk = lambda r: r.at[:, :, :, 3:].set(1e3)  # noqa: E731
        b = diff_attention.attend_ring(w, q, junk(ring_k), junk(ring_v), 1,
                                       k_new, v_new, pos)
    np.testing.assert_allclose(a, b, atol=1e-6)
