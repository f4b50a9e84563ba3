"""The phi4flash stack (``config.phi4flash_config``: three runs of
periods, Mamba-1, window / full / cross differential attention, gated
memory units) at tiny widths, float32, against the plain reference
``benchmarks/reference/phi4flash.py``: the whole forward, then a prefill
cut to one row and 40 decode steps through the cache, the rings and the
states; and each omission the reference must catch."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import phi4flash as ref
from megatron_llm_tpu import config as config_lib
from megatron_llm_tpu.config import phi4flash_config
from megatron_llm_tpu.models import diff_attention, mamba1
from megatron_llm_tpu.models import model as model_lib
from megatron_llm_tpu.models import transformer

RUNS = ((("ssm1", "window"), 2), (("ssm1", "full"), 1),
        (("gmu", "cross"), 2))
TINY = dict(layer_runs=RUNS, hidden_size=64, num_attention_heads=8,
            num_kv_heads=4, kv_channels=8, ffn_hidden_size=96,
            sliding_window=8, mamba1_inner=128, mamba1_state_size=4,
            mamba1_dt_rank=4, vocab_size=512, params_dtype="float32",
            make_vocab_size_divisible_by=8, max_position_embeddings=1024)
PROMPT, BUCKET, STEPS = 19, 24, 40     # a prompt past two windows of 8

# float32 on both sides, the same equations in another order of
# operations (a kernel-shaped attention, stacked states, a cut of rows):
# logits of magnitude ~0.6 agree to a few 1e-7; anything this PR's tests
# call an omission moves them by 1e-3 or more
TOL = 5e-6


def tiny(**kw):
    return phi4flash_config(**{**TINY, **kw})


@pytest.fixture(scope="module")
def model():
    cfg = tiny()
    params = jax.jit(lambda k: model_lib.init_params(k, cfg))(
        jax.random.key(0))
    params = jax.tree_util.tree_map_with_path(_shake, params)
    tokens = jax.random.randint(jax.random.key(1), (1, PROMPT + STEPS + 1),
                                1, 500)
    served_programs(cfg)        # before any test's body, so any patch
    return cfg, params, tokens


def _shake(path, a):
    """The biases away from zero, so that nothing passes by being zero;
    and every mixer loud beside the MLPs, as trained ones are: at a
    seeded start of 0.02 a recurrent state, a ring or a memory is worth a
    1e-7 of a logit and no omission could show.  The selection 20-fold (B
    and C: the state's way in and out) with a small skip, the mixers'
    output projections 8-fold."""
    name = jax.tree_util.keystr(path)
    if any(k in name for k in ("'bq'", "'bk'", "'bv'", "'bo'", "'bias'")):
        key = jax.random.fold_in(jax.random.key(7), sum(map(ord, name)))
        return 0.1 * jax.random.normal(key, a.shape, a.dtype)
    if "'w_x'" in name:
        return 20.0 * a
    if "'D'" in name:
        return 0.1 * a
    if "'mlp'" not in name and ("'wo'" in name or "'w_out'" in name):
        return 8.0 * a
    return a


def reference_logits(cfg, params, tokens):
    return np.asarray(ref.logits_of(params, np.asarray(tokens[0]),
                                    ref.meta_of(cfg)))


def program_logits(cfg, params, tokens):
    # (jitted anew a call: an omission patched in is traced with it; and
    # one executable a call, where op-by-op execution would leave
    # hundreds mapped in the worker)
    with jax.default_matmul_precision("highest"):
        return np.asarray(jax.jit(
            lambda p, t: model_lib.forward(cfg, p, t))(params, tokens)[0])


def _programs(cfg):
    """The jitted prefill and decode step ``served_logits`` runs, traced
    where they are first called: an omission patched in is served through
    a pair of its own (``programs=_programs``), the faithful model
    through ``served_programs``."""
    @jax.jit
    def prefill(params, padded, k, v, rec):
        valid = jnp.arange(BUCKET)[None, :] < PROMPT
        return model_lib.forward_cached_hybrid(
            cfg, params, padded, k, v, jnp.int32(0), rec, valid=valid,
            empty_cache=True, logit_rows=jnp.array([PROMPT - 1]))

    @jax.jit
    def step(params, token, k, v, t, rec):
        return model_lib.forward_cached_hybrid(
            cfg, params, token, k, v, t, rec, valid=jnp.ones((1, 1), bool))

    return prefill, step


def served_logits(cfg, params, tokens, steps=STEPS, programs=None):
    """A bucket-padded prefill cut to its last row, then ``steps`` decode
    steps on the dense view of the gather route, every step's logits."""
    prefill, step = (programs or served_programs)(cfg)
    with jax.default_matmul_precision("highest"):
        k, v = model_lib.init_kv_cache(cfg, 1, 128)
        rec = model_lib.init_rec_state(cfg, 1)
        padded = jnp.zeros((1, BUCKET), jnp.int32).at[:, :PROMPT].set(
            tokens[:, :PROMPT])
        logits, k, v, rec = prefill(params, padded, k, v, rec)
        out = [np.asarray(logits[0, 0])]
        for t in range(PROMPT, PROMPT + steps):
            logits, k, v, rec = step(params, tokens[:, t:t + 1], k, v,
                                     jnp.array([t]), rec)
            out.append(np.asarray(logits[0, 0]))
    return np.stack(out), rec


@functools.lru_cache(maxsize=None)
def served_programs(cfg):
    """One pair a configuration a process: every test that serves the
    faithful program (here and in test_window_kind.py) runs the same two
    executables.  Both are run once here, on zeros, before the pair is
    handed out, and the module's fixture builds its configuration's pair
    before any test's body runs: a test that patches the model and forgets
    ``programs=_programs`` reads the faithful pair and fails, and cannot
    leave a pair traced under its patch to the tests behind it."""
    pair = _programs(cfg)
    params = jax.tree.map(
        lambda a: jnp.zeros(a.shape, a.dtype),
        jax.eval_shape(lambda: model_lib.init_params(jax.random.key(0), cfg)))
    served_logits(cfg, params, jnp.zeros((1, PROMPT + 1), jnp.int32),
                  steps=1, programs=lambda _cfg: pair)
    return pair


def test_the_preset_is_the_published_model():
    full = phi4flash_config("mini-flash-reasoning")
    assert config_lib.get_preset("phi-4-mini-flash-reasoning") == full
    kinds = full.layer_kinds
    assert len(kinds) == full.num_layers == 32
    assert kinds[:16] == ("ssm1", "window") * 8
    assert kinds[16:18] == ("ssm1", "full")
    assert kinds[18:] == ("gmu", "cross") * 7
    assert (full.kv_layers, full.mamba1_layers, full.window_layers,
            full.cross_layers, full.row_cut_layer) == (1, 9, 8, 7, 17)
    assert (full.hidden_size, full.head_dim, full.kv_heads, full.ffn_size,
            full.v_heads, full.v_head_width) == (2560, 64, 20, 10240, 10,
                                                 128)
    assert full.diff_attention and full.tie_embed_logits
    # a stack of runs validates what it cannot carry
    with pytest.raises(AssertionError, match="one \"full\" layer|full"):
        tiny(layer_runs=((("ssm1", "full"), 2), (("gmu", "cross"), 1)))
    with pytest.raises(AssertionError, match="memory"):
        tiny(layer_runs=((("gmu", "full"), 1),))
    assert tiny(layer_runs=((("ssm1", "window"), 2),)).row_cut_layer is None


def test_the_tree_is_runs_of_periods_in_the_published_layout(model):
    cfg, params, _ = model
    runs = params["layers"]
    assert [len(r) for r in runs] == [2, 2, 2]
    ssm, win = runs[0]
    assert ssm["mamba1"]["w_in"].shape == (2, 64, 256)        # [xs | z]
    assert ssm["mamba1"]["w_x"].shape == (2, 128, 4 + 4 + 4)  # [dt|B|C]
    assert ssm["mamba1"]["A_log"].shape == (2, 128, 4)
    assert win["attn"]["wk"].shape == (2, 64, 32)
    assert runs[1][1]["attn"]["wq"].shape == (1, 64, 64)
    gmu, cross = runs[2]
    assert set(cross["attn"]) == {"wq", "bq", "wo", "bo", "lam",
                                  "pair_norm"}                # a query alone
    assert set(gmu["gmu"]) == {"w_in", "w_out"}
    rec = model_lib.init_rec_state(cfg, 3)
    assert rec["ssm1"].shape == (3, 3, 4, 128)
    assert rec["ssm1_conv"].shape == (3, 3, 3 * 128)
    assert rec["win_k"].shape == (2, 3, 2, 8, 16)    # a pair's keys a row
    assert rec["win_v"].shape == (2, 3, 2, 8, 16)    # its values a row
    assert rec["ssm1"].dtype == jnp.float32
    k, v = model_lib.init_kv_cache(cfg, 1, 32)
    assert k.shape == (1, 1, 4, 32, 8) and v.shape == (1, 1, 2, 32, 16)
    assert set(model_lib.REC_STATE_KINDS) >= {"ssm1", "window"}


def test_the_whole_forward_is_the_references(model):
    cfg, params, tokens = model
    want = reference_logits(cfg, params, tokens)
    got = program_logits(cfg, params, tokens)[:, :cfg.vocab_size]
    assert np.abs(want).max() > 0.3
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


def test_a_cut_prefill_then_forty_steps_is_the_references_forward(model):
    """Prefill of 19 positions in a bucket of 24 (window 8: the rings
    wrap at install), then 40 steps through the dense cache, the rings
    (five more wraps) and the Mamba-1 states."""
    cfg, params, tokens = model
    want = reference_logits(cfg, params, tokens)[PROMPT - 1:PROMPT + STEPS]
    got, _rec = served_logits(cfg, params, tokens)
    np.testing.assert_allclose(got[:, :cfg.vocab_size], want, atol=TOL,
                               rtol=0)


def test_the_timed_prefill_leaves_what_the_every_row_pass_leaves(model):
    """The program every prefill runs (one row through the second
    decoder) writes the cache, the rings and the states the every-row
    pass writes, and its row's logits are that pass's."""
    cfg, params, tokens = model
    with jax.default_matmul_precision("highest"):
        k, v = model_lib.init_kv_cache(cfg, 1, 128)
        rec = model_lib.init_rec_state(cfg, 1)
        padded = jnp.zeros((1, BUCKET), jnp.int32).at[:, :PROMPT].set(
            tokens[:, :PROMPT])
        valid = jnp.arange(BUCKET)[None, :] < PROMPT
        # (op by op on both sides: the leaves are compared bit for bit,
        # which two programs compiled whole do not promise)
        cut = model_lib.forward_cached_hybrid(
            cfg, params, padded, k, v, jnp.int32(0), rec, valid=valid,
            empty_cache=True, logit_rows=jnp.array([PROMPT - 1]))
        whole = model_lib.forward_cached_hybrid(
            cfg, params, padded, k, v, jnp.int32(0), rec, valid=valid,
            empty_cache=True)
    assert cut[0].shape == (1, 1, 512) and whole[0].shape == (1, BUCKET, 512)
    np.testing.assert_allclose(cut[0][0, 0], whole[0][0, PROMPT - 1],
                               atol=1e-6)
    for a, b in zip(jax.tree.leaves(cut[1:]), jax.tree.leaves(whole[1:])):
        np.testing.assert_array_equal(a, b)
    # the padded tail advanced nothing: the states are those of the
    # prompt alone
    alone = jax.jit(lambda prompt: model_lib.forward_cached_hybrid(
        cfg, params, prompt, k, v, jnp.int32(0), rec,
        empty_cache=True, logit_rows=jnp.array([PROMPT - 1])))(
            tokens[:, :PROMPT])
    for name in ("ssm1", "ssm1_conv"):
        np.testing.assert_allclose(cut[3][name], alone[3][name], atol=1e-6)


def _rounded(a):
    return a.astype(jnp.bfloat16).astype(a.dtype)


def _bf16_state(monkeypatch):
    step, prompt = mamba1.s6_step, mamba1._prompt
    monkeypatch.setattr(mamba1, "s6_step", lambda *a: (
        lambda y, z, st: (y, z, st._replace(S=_rounded(st.S))))(*step(*a)))
    monkeypatch.setattr(mamba1, "_prompt", lambda *a: (
        lambda y, z, st: (y, z, st._replace(S=_rounded(st.S))))(*prompt(*a)))


def _bf16_ring(monkeypatch):
    ring_of, append = diff_attention.ring_of, transformer.ring_append_rows
    monkeypatch.setattr(diff_attention, "ring_of",
                        lambda *a: _rounded(ring_of(*a)))
    monkeypatch.setattr(transformer, "ring_append_rows", lambda rings, rows,
                        pos: append(rings, jax.tree.map(_rounded, rows), pos))


def _no_pair_scale(monkeypatch):
    monkeypatch.setattr(diff_attention, "pair_scale", lambda lam0: 1.0)


def _memory_after_the_gate(monkeypatch):
    monkeypatch.setattr(mamba1, "memory_of",
                        lambda y, z: y * jax.nn.silu(z))


def _cross_reads_its_own_input(monkeypatch):
    """A cross layer whose keys and values are projected from ITS input
    (with the full layer's weights) and not handed from the full layer."""
    attend, full = diff_attention.attend_handed, diff_attention.attend_full
    held = {}

    def full_attend(cfg, p, u, *a):
        held["p"] = p
        return full(cfg, p, u, *a)

    def diff_attend(cfg, p, u, layer, hand, q_rows=None):
        if "wk" not in p and hand.form == "seq":
            k, v = diff_attention.project_kv(cfg, held["p"], u)
            hand = hand._replace(k=k, v=v)
        return attend(cfg, p, u, layer, hand, q_rows)

    monkeypatch.setattr(diff_attention, "attend_full", full_attend)
    monkeypatch.setattr(diff_attention, "attend_handed", diff_attend)


@pytest.mark.parametrize("omission,served", [
    (_bf16_state, True), (_bf16_ring, True), (_no_pair_scale, False),
    (_memory_after_the_gate, False), (_cross_reads_its_own_input, False),
    ("window_off_by_one", True)],
    ids=["bf16_state", "bf16_ring", "no_one_minus_lambda_init",
         "memory_after_the_gate", "cross_reads_its_own_input",
         "window_off_by_one"])
def test_each_omission_fails_the_comparison(model, monkeypatch, omission,
                                            served):
    """What the chip's limits are too wide to see (0.15 / 0.03 on
    log-probabilities), this comparison is not: each departure from the
    equations moves the logits by far more than ``TOL``."""
    cfg, params, tokens = model
    want = reference_logits(cfg, params, tokens)
    if omission == "window_off_by_one":
        cfg = dataclasses.replace(cfg, sliding_window=9)
    else:
        omission(monkeypatch)
    if served:
        got, _ = served_logits(cfg, params, tokens, programs=_programs)
        want = want[PROMPT - 1:PROMPT + STEPS]
    else:
        got = program_logits(cfg, params, tokens)
    gap = np.abs(got[:, :cfg.vocab_size] - want).max()
    assert gap > 10 * TOL, gap


def test_the_prompt_form_is_its_step_a_position_at_a_time(model):
    cfg, params, _ = model
    p = jax.tree.map(lambda a: a[0], params["layers"][0][0]["mamba1"])
    x = 0.5 * jax.random.normal(jax.random.key(3), (2, 21, 64))
    valid = jnp.arange(21)[None, :] < jnp.array([[21], [13]])
    with jax.default_matmul_precision("highest"):
        out, state, mem = mamba1.mamba1_block(cfg, p, x, None, valid)
        st = mamba1.init_state(cfg, 2)
        outs, mems = [], []
        for t in range(21):
            o, st, m = mamba1.mamba1_block(cfg, p, x[:, t:t + 1], st,
                                           valid[:, t:t + 1])
            outs.append(o), mems.append(m)
    # (at the positions that are there: what a padded one gives is read
    # by nobody)
    there = np.asarray(valid)[..., None]
    np.testing.assert_allclose(out * there, jnp.concatenate(outs, 1) * there,
                               atol=2e-6)
    np.testing.assert_allclose(mem * there, jnp.concatenate(mems, 1) * there,
                               atol=2e-6)
    # both end at each row's TRUE last position
    np.testing.assert_allclose(state.S, st.S, atol=2e-6)
    np.testing.assert_allclose(state.conv, st.conv, atol=2e-6)
    assert state.S.shape == (2, 4, 128) and state.conv.shape == (2, 384)
    # the second row's state is that of its 13 positions alone
    _o, short, _m = mamba1.mamba1_block(cfg, p, x[1:, :13])
    np.testing.assert_allclose(state.S[1], short.S[0], atol=2e-6)


def test_the_paged_step_writes_the_pool_once_and_the_rings_in_place(
        model, monkeypatch):
    """One decode step on the paged route (the kernel interpreted): the
    full layer's row goes to the pool's ONE layer, every cross layer
    walked that layer with the step's own rows beside it, the rings took
    a row each at ``position % window``; its logits are the dense
    route's."""
    from megatron_llm_tpu.ops import attention as attn_ops

    cfg, params, tokens = model
    got_dense, rec = served_logits(cfg, params, tokens, steps=3)
    # the same three steps from the same prefill, paged
    with jax.default_matmul_precision("highest"):
        k, v = model_lib.init_kv_cache(cfg, 1, 128)
        rec0 = model_lib.init_rec_state(cfg, 1)
        padded = jnp.zeros((1, BUCKET), jnp.int32).at[:, :PROMPT].set(
            tokens[:, :PROMPT])
        _l, k, v, rec0 = served_programs(cfg)[0](params, padded, k, v, rec0)
        bk, T = 16, 8
        tables = jnp.arange(1, T + 1, dtype=jnp.int32)[None]
        k_pool, v_pool = model_lib.init_kv_pool(cfg, T + 1, bk)
        k_pool, v_pool = (model_lib.cache_scatter_blocks(p_, d_, tables[0])
                          for p_, d_ in ((k_pool, k), (v_pool, v)))
        monkeypatch.setattr(attn_ops, "_backend", lambda: "tpu")
        monkeypatch.setattr(
            attn_ops, "paged_decode_kernel_eligible",
            lambda s, d, block, platform: s == 1)
        calls = []
        from megatron_llm_tpu.kernels import flash_decode as fd
        walk = fd.flash_decode_paged
        monkeypatch.setattr(fd, "flash_decode_paged", lambda *a, **kw: (
            calls.append(kw["new_rows"][1].shape), walk(*a, **kw))[1])
        # one executable for the three steps, traced under the patches
        step = jax.jit(lambda token, k_pool, v_pool, t, rec:
                       model_lib.forward_paged_hybrid(
                           cfg, params, token, k_pool, v_pool, tables, t, rec,
                           jnp.ones((1,), bool)))
        out = []
        for t in range(PROMPT, PROMPT + 3):
            logits, k_pool, v_pool, rec0 = step(
                tokens[:, t:t + 1], k_pool, v_pool, jnp.array([t]), rec0)
            out.append(np.asarray(logits[0, 0]))
    np.testing.assert_allclose(np.stack(out), got_dense[1:], atol=TOL)
    # one walk by the full layer and one by each of the two cross layers,
    # a step (the scan's body is traced once: two calls a step's trace)
    assert calls and set(calls) == {(1, 2, 1, 16)}
    assert k_pool.shape[0] == v_pool.shape[0] == 1
    for name in ("ssm1", "ssm1_conv", "win_k", "win_v"):
        np.testing.assert_allclose(rec0[name], rec[name], atol=1e-5)


def test_with_the_kernels_on_the_steps_are_the_plain_steps(model):
    """The same cut prefill and twelve steps with ``attention_impl``
    "flash" (the kernels interpreted): the window layers' prompt under
    ``flash_attention``'s band, their steps by ``kernels/ring_decode.py``
    on the stacked rings and the layer's index (window 8: the rings wrap
    twice) give the plain composition's logits, rings and states."""
    cfg, params, tokens = model
    want, rec = served_logits(cfg, params, tokens, steps=12)
    got, rec_k = served_logits(tiny(attention_impl="flash"), params, tokens,
                               steps=12)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)
    for name in ("ssm1", "ssm1_conv", "win_k", "win_v"):
        np.testing.assert_allclose(rec_k[name], rec[name], atol=1e-5)


def test_a_plain_period_stack_is_untouched_by_the_runs():
    """``layer_runs`` empty: the period scan, its parameters a list a
    position; nothing of a stack of runs is asked of it."""
    from megatron_llm_tpu.config import granite_hybrid_config

    cfg = granite_hybrid_config(
        "4.0-h-micro", num_layers=4, layer_pattern=("ssm", "full"),
        hidden_size=64, num_attention_heads=4, num_kv_heads=2,
        kv_channels=16, ffn_hidden_size=96, vocab_size=512,
        mamba_num_heads=8, mamba_head_dim=8, mamba_n_groups=1,
        mamba_state_size=16, mamba_chunk_size=8, params_dtype="float32",
        make_vocab_size_divisible_by=8)
    assert cfg.layer_runs == () and cfg.row_cut_layer is None
    assert (cfg.mamba1_layers, cfg.window_layers, cfg.cross_layers) == (
        0, 0, 0)
    assert (cfg.v_heads, cfg.v_head_width) == (2, 16)
    rec = jax.eval_shape(lambda: model_lib.init_rec_state(cfg, 2))
    assert set(rec) == {"ssm", "ssm_conv", "load", "rows"}
