"""The hybrid stack's own mechanisms at tiny widths, float32: the two
forms of the gated delta rule, a padded prefill, dropless routing, the
attention variants, and the preset's sizes."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_llm_tpu.config import qwen3_next_config
from megatron_llm_tpu.models import gated_deltanet as gdn
from megatron_llm_tpu.models import model as model_lib
from megatron_llm_tpu.models import moe
from megatron_llm_tpu.ops.norms import norm_apply, norm_init
from megatron_llm_tpu.ops.precision import dot_f32
from megatron_llm_tpu.ops.rope import apply_rope_partial

TINY = dict(num_layers=4, hidden_size=64, num_attention_heads=4,
            num_kv_heads=2, kv_channels=32, ffn_hidden_size=32,
            moe_shared_expert_size=32, num_experts=8, moe_router_experts=16,
            moe_top_k=4, vocab_size=512, linear_num_key_heads=2,
            linear_num_value_heads=4, linear_key_head_dim=16,
            linear_value_head_dim=16, params_dtype="float32",
            max_position_embeddings=1024, make_vocab_size_divisible_by=8,
            moe_group_size=64)


def tiny(**kw):
    return qwen3_next_config("80b-a3b-ep2-rank0", **{**TINY, **kw})


@pytest.fixture(scope="module")
def model():
    cfg = tiny()
    return cfg, jax.jit(lambda k: model_lib.init_params(k, cfg))(
        jax.random.key(0))


def test_the_preset_is_the_published_model_and_its_share():
    full = qwen3_next_config("80b-a3b")
    assert (full.num_layers, full.hidden_size, full.head_dim) == (48, 2048,
                                                                   256)
    assert full.layer_kinds.count("full") == 12 == full.kv_layers
    assert full.layer_kinds[:4] == ("linear", "linear", "linear", "full")
    assert (full.num_experts, full.router_experts, full.moe_top_k) == (
        512, 512, 10)
    share = qwen3_next_config("80b-a3b-ep2-rank0", num_layers=4)
    assert (share.num_experts, share.router_experts,
            share.moe_expert_offset) == (256, 512, 0)
    assert share.vocab_size == share.padded_vocab_size() == 75968
    assert (share.kv_layers, share.linear_layers) == (1, 3)
    # a JSON list is as good as a tuple, and the config stays hashable
    assert hash(tiny(layer_pattern=["linear", "full"])) == hash(
        tiny(layer_pattern=("linear", "full")))
    with pytest.raises(AssertionError, match="whole periods"):
        tiny(num_layers=6)


@pytest.mark.parametrize("s", [64, 100, 192])
def test_a_prompt_through_the_mixer_is_its_positions_one_by_one(s):
    """64 positions a chunk: one whole chunk, one and a ragged second
    (padded inside ``gdn_block``), three.  The kernel's side of
    ``gdn_block`` (``s > 1``) against the one-position side run ``s``
    times, from a state and a tail that are not zero."""
    cfg = tiny()
    b = 2
    nk, nv, dk, dv, ch = gdn.dims(cfg)
    ks = jax.random.split(jax.random.key(s), 5)
    p = gdn.init_gdn_params(ks[0], cfg)
    p["norm"]["scale"] = 1.0 + 0.3 * jax.random.normal(ks[1], (dv,))
    x = 3.0 * jax.random.normal(ks[2], (b, s, cfg.hidden_size))
    state = gdn.GDNState(jax.random.normal(ks[3], (b, nv, dk, dv)),
                         jax.random.normal(ks[4], (b, 3, ch)))

    def step(state, x_t):
        out, state = gdn.gdn_block(cfg, p, x_t[:, None], state)
        return state, out[:, 0]

    want_state, want = jax.jit(lambda st, xs: jax.lax.scan(step, st, xs))(
        state, jnp.moveaxis(x, 1, 0))
    got, got_state = jax.jit(
        lambda x, st: gdn.gdn_block(cfg, p, x, st))(x, state)
    np.testing.assert_allclose(got, jnp.moveaxis(want, 0, 1), atol=2e-5)
    np.testing.assert_allclose(got_state.S, want_state.S, atol=2e-5)
    np.testing.assert_allclose(got_state.conv, want_state.conv, atol=1e-6)


def test_the_mixer_runs_a_kernel_for_a_prompt_and_another_for_one():
    """``gdn_block`` has one path a shape, one kernel between its two
    projections: every ``s > 1`` lowers to ``gdn_scan``, ``s == 1`` to
    ``gdn_step``."""
    cfg = tiny()
    p = gdn.init_gdn_params(jax.random.key(0), cfg)
    for s, kernel in ((2, "gdn_scan"), (70, "gdn_scan"), (1, "gdn_step")):
        x = jax.ShapeDtypeStruct((1, s, cfg.hidden_size), jnp.float32)
        text = str(jax.make_jaxpr(
            lambda p, x: gdn.gdn_block(cfg, p, x))(p, x))
        assert text.count("pallas_call") == 1, s
        assert f"name={kernel}" in text, s
        # the only loop left is the prompt kernel's own, over a step's
        # chunks
        assert text.count("scan[") + text.count("while[") == (s > 1), s


def _parents_step(cfg, p, qkvz, ba, state, valid):
    """A decode step between the two projections as the parent ran it:
    the layer's state out of the stack, the plain ``jax.numpy``
    composition, and the result written into the stack."""
    S, conv, at = state
    o, new = gdn.one_position(cfg, p, qkvz, ba,
                              gdn.GDNState(S[at], conv[at]), valid)
    return o, gdn.GDNState(S.at[at].set(new.S), conv.at[at].set(new.conv),
                           at)


def test_a_step_through_the_carry_is_the_parents_form(monkeypatch):
    """Two periods, so that a layer's place in the stacked states is a
    traced scalar: three decode steps after a prompt, slot 1 dead in the
    second of them, by the kernel on the carried stack and by the plain
    composition on a layer's slice: the logits, and every state of every
    layer after each step."""
    cfg = tiny(num_layers=8)
    params = jax.jit(lambda k: model_lib.init_params(k, cfg))(
        jax.random.key(1))
    toks = jax.random.randint(jax.random.key(5), (3, 23), 1, 500)

    # the prompt once: ``_one_position`` is the one-position side's alone
    # (``gdn_block``: ``s == 1``), so the patch below is not in it
    k, v = model_lib.init_kv_cache(cfg, 3, 32)
    _, k0, v0, rec0 = jax.jit(
        lambda t, k, v: model_lib.forward_cached_hybrid(
            cfg, params, t, k, v, jnp.int32(0),
            model_lib.init_rec_state(cfg, 3), empty_cache=True))(
                toks[:, :20], k, v)

    def run():
        k, v, rec = k0, v0, rec0
        step = jax.jit(lambda t, k, v, n, rec, live:
                       model_lib.forward_cached_hybrid(
                           cfg, params, t, k, v, n, rec, valid=live))
        out = []
        for i in range(20, 23):
            live = jnp.asarray([[True], [i != 21], [True]])
            logits, k, v, rec = step(toks[:, i:i + 1], k, v,
                                     jnp.full((3,), i, jnp.int32), rec, live)
            out.append((logits, rec))
        return out

    got = run()
    monkeypatch.setattr(gdn, "_one_position", _parents_step)
    want = run()
    assert got[0][1]["S"].shape == (6, 3, 4, 16, 16)
    for i, ((logits, rec), (want_logits, want_rec)) in enumerate(
            zip(got, want)):
        rows = [0, 2] if i == 1 else [0, 1, 2]
        np.testing.assert_allclose(logits[rows, 0], want_logits[rows, 0],
                                   atol=2e-5)
        for key in ("S", "conv"):
            np.testing.assert_allclose(rec[key], want_rec[key], atol=2e-5)
        np.testing.assert_array_equal(rec["load"], want_rec["load"])
    for key in ("S", "conv"):    # the dead slot's rows, every layer's
        np.testing.assert_array_equal(got[1][1][key][:, 1],
                                      got[0][1][key][:, 1])
        assert float(jnp.abs(got[2][1][key][:, 1]
                             - got[1][1][key][:, 1]).max()) > 0


def _makers(jaxpr, shapes, path=()):
    """``(primitive, the primitives around it)`` of every equation of
    ``jaxpr`` and of the jaxprs inside it that makes an array of one of
    ``shapes``."""
    out = []
    for eqn in jaxpr.eqns:
        if any(getattr(v.aval, "shape", None) in shapes
               for v in eqn.outvars):
            out.append((eqn.primitive.name, path))
        if eqn.primitive.name == "pallas_call":
            continue        # a kernel's body works on blocks
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out += _makers(sub, shapes, path + (eqn.primitive.name,))
    return out


def test_a_decode_step_makes_the_stacked_states_in_its_kernel_alone():
    """The decode program of the toy at two periods: an array of the
    stacked states' or tails' shape comes out of the one-position kernel
    (and out of what only hands it on: the jitted call around the kernel,
    the scan that carries it), and of nothing else: no slice is stacked
    again, no layer written back beside the kernel.  What the chip's
    compiler makes of the same program at the published widths is
    audited in tests/kernels/test_tpu_compile.py (``relayout_bytes``)."""
    cfg = tiny(num_layers=8)
    slots = 2
    params = jax.eval_shape(lambda k: model_lib.init_params(k, cfg),
                            jax.random.key(0))
    rec = jax.eval_shape(lambda: model_lib.init_rec_state(cfg, slots))
    k, v = jax.eval_shape(lambda: model_lib.init_kv_cache(cfg, slots, 32))
    shapes = {rec["S"].shape, rec["conv"].shape}
    assert shapes == {(6, slots, 4, 16, 16), (6, slots, 3, 128)}
    jaxpr = jax.make_jaxpr(
        lambda p, t, k, v, n, rec, live: model_lib.forward_cached_hybrid(
            cfg, p, t, k, v, n, rec, valid=live))(
                params, jax.ShapeDtypeStruct((slots, 1), jnp.int32), k, v,
                jax.ShapeDtypeStruct((slots,), jnp.int32), rec,
                jax.ShapeDtypeStruct((slots, 1), bool))
    made = _makers(jaxpr.jaxpr, shapes)
    # a period's three linear layers; around each kernel its jitted call,
    # which hands the tails over with the rows outermost (a relabelling on
    # the chip: tests/kernels/test_tpu_compile.py::test_gdn_step)
    assert [m for m in made if m[1] == ()] == [("scan", ())]
    inside = [m for m in made if m[1] != ()]
    assert sorted(inside) == sorted(
        3 * [("jit", ("scan",)), ("pallas_call", ("scan", "jit")),
             ("transpose", ("scan", "jit"))]), made


def test_bf16_weights_read_a_float32_activation_in_two_passes():
    """The mixer's projections (and the shared expert's) take the float32
    stream as it is: against
    the same bf16 weights in float32 the product is off by ~2^-17, where
    an activation rounded to bf16 first is off by ~2^-9."""
    ks = jax.random.split(jax.random.key(7), 2)
    x = jax.random.normal(ks[0], (96, 256))
    w = jax.random.normal(ks[1], (256, 128)).astype(jnp.bfloat16)
    want = jnp.dot(x, w.astype(jnp.float32), precision="highest")

    def off(got):
        return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))

    one_pass = jnp.dot(x.astype(jnp.bfloat16), w,
                       preferred_element_type=jnp.float32)
    assert off(jax.jit(dot_f32)(x, w)) < 2e-5 < 1e-3 < off(one_pass)
    # an activation that has the weight's precision already: one product
    xb = x.astype(jnp.bfloat16)
    np.testing.assert_array_equal(dot_f32(xb, w), one_pass)
    assert dot_f32(xb, w).dtype == jnp.float32


def test_a_padded_prefill_is_the_unpadded_one(model):
    """A bucket's padded tail changes neither the logits of the real
    positions nor the state and convolution tail handed to decode."""
    cfg, params = model
    n, width = 70, 128
    toks = jax.random.randint(jax.random.key(2), (1, width), 1, 500)

    @jax.jit
    def prefill(tokens, valid):
        k, v = model_lib.init_kv_cache(cfg, 1, 256)
        return model_lib.forward_cached_hybrid(
            cfg, params, tokens, k, v, jnp.int32(0),
            model_lib.init_rec_state(cfg, 1), valid=valid, empty_cache=True)

    exact = prefill(toks[:, :n], None)
    padded = prefill(toks, (jnp.arange(width) < n)[None])
    np.testing.assert_allclose(padded[0][:, :n], exact[0], atol=2e-5)
    for key in ("S", "conv"):
        np.testing.assert_allclose(padded[3][key], exact[3][key], atol=2e-5)
    np.testing.assert_array_equal(padded[3]["load"], exact[3]["load"])
    assert int(exact[3]["load"].sum()) == cfg.num_layers * n * cfg.moe_top_k
    # and the state is not the one after the padded tail
    # (every position marked: the padded call's executable)
    through = prefill(toks, jnp.ones((1, width), bool))
    assert float(jnp.abs(through[3]["S"] - exact[3]["S"]).max()) > 1e-3


def test_decode_continues_the_prefill_and_skips_dead_rows(model):
    cfg, params = model
    toks = jax.random.randint(jax.random.key(3), (2, 34), 1, 500)
    want = jax.jit(lambda t: model_lib.forward(cfg, params, t))(toks)
    k, v = model_lib.init_kv_cache(cfg, 2, 64)
    _, k, v, rec = jax.jit(lambda t, k, v: model_lib.forward_cached_hybrid(
        cfg, params, t, k, v, jnp.int32(0),
        model_lib.init_rec_state(cfg, 2), empty_cache=True))(
            toks[:, :30], k, v)
    live = jnp.asarray([[True], [False]])
    step = jax.jit(lambda t, k, v, n, rec: model_lib.forward_cached_hybrid(
        cfg, params, t, k, v, n, rec, valid=live))
    for i in range(30, 34):
        before = rec
        logits, k, v, rec = step(toks[:, i:i + 1], k, v,
                                 jnp.full((2,), i, jnp.int32), rec)
        np.testing.assert_allclose(logits[0, 0], want[0, i], atol=2e-5)
        for key in ("S", "conv"):     # the row that is not live keeps its
            np.testing.assert_array_equal(rec[key][:, 1], before[key][:, 1])
            assert float(jnp.abs(rec[key][:, 0]
                                 - before[key][:, 0]).max()) > 0
    assert int(rec["load"].sum()) == cfg.num_layers * cfg.moe_top_k * (
        2 * 30 + 4)
    # the experts' rows are every position's, live or not, by outcome
    rows = model_lib.rows_total(rec["rows"])
    assert rows.shape == (cfg.num_layers, 2) and (rows > 0).all()
    assert int(rows.sum()) == cfg.num_layers * cfg.moe_top_k * 2 * (30 + 4)


def test_a_row_count_carries_into_its_high_word():
    word = 1 << model_lib._ROWS_WORD
    rows = jnp.asarray([[0, word - 3], [2, 5]], jnp.int32)
    more = jnp.asarray([[0, 163840], [0, 0]], jnp.int32)
    total = model_lib.rows_total(model_lib.add_rows(rows, more))
    assert total.tolist() == [word - 3 + 163840, 2 * word + 5]
    assert model_lib.add_rows(rows, more)[0].tolist() == [1, 163837]


def test_a_tokens_experts_do_not_depend_on_its_batch(model):
    """Dropless: no capacity, so no neighbour can take a token's place;
    and routed in chunks or at once is the same."""
    cfg, params = model
    p = jax.tree.map(lambda a: a[0], params["layers"][0]["mlp"])
    x = jax.random.normal(jax.random.key(4), (1, 96, cfg.hidden_size))
    run = jax.jit(moe.moe_dropless_block, static_argnums=0)
    alone, _ = run(cfg, p, x[:, :1])
    # the same token among 95 others that all prefer its experts
    crowd = x.at[:, 1:].set(x[:, :1] + 1e-3 * x[:, 1:])
    among, stats = run(cfg, p, crowd)
    np.testing.assert_allclose(among[:, 0], alone[:, 0], atol=1e-6)
    assert float(stats["load"].max()) >= 90      # a capacity would drop
    at_once, _ = run(dataclasses.replace(cfg, moe_group_size=96), p, crowd)
    in_chunks, _ = run(dataclasses.replace(cfg, moe_group_size=32), p, crowd)
    np.testing.assert_allclose(in_chunks, at_once, atol=1e-6)


def former_held_experts(cfg, _interpret, p, x, local, weight):
    """``moe._held_experts`` as it was before ``kernels/grouped_matmul.py``
    (PR 42), the oracle: a stable argsort, all ``g * k`` rows gathered,
    three ``lax.ragged_dot`` calls, the order inverted by a scatter."""
    g, k = local.shape
    E = cfg.num_experts
    act = moe.get_activation(cfg.activation)
    x = x.astype(p["w_up"].dtype)
    flat = local.reshape(-1)
    order = jnp.argsort(flat, stable=True)
    sizes = jnp.zeros((E + 1,), jnp.int32).at[flat].add(1)[:E]
    rows = x[order // k]
    gate = jax.lax.ragged_dot(rows, p["w_gate"], sizes)
    up = jax.lax.ragged_dot(rows, p["w_up"], sizes)
    hidden = act(jnp.concatenate([gate, up], axis=-1))
    out = jax.lax.ragged_dot(hidden, p["w_down"], sizes)
    back = jnp.zeros_like(order).at[order].set(
        jnp.arange(g * k, dtype=order.dtype))
    out = out[back].reshape(g, k, -1)
    out = jnp.where((local < E)[..., None], out, 0).astype(jnp.float32)
    held = jnp.sum(sizes)
    return (out * weight[..., None]).sum(axis=1), jnp.stack(
        [held, g * k - held])


@pytest.mark.parametrize("dtype,tol", [("float32", 1e-6), ("bfloat16", 3e-2)])
@pytest.mark.parametrize("group", [256, 32], ids=["at_once", "in_chunks"])
def test_the_grouped_kernel_is_the_former_ragged_products(
        monkeypatch, group, dtype, tol):
    """``moe_dropless_block`` at the rehearsal widths against itself with
    the former formulation in the kernel's place, routed at once and in
    chunks (the ``lax.map`` arm)."""
    cfg = tiny(params_dtype=dtype, moe_group_size=group)
    p = moe.init_moe_params(jax.random.key(7), cfg)
    x = jax.random.normal(jax.random.key(8), (2, 48, cfg.hidden_size))
    got, stats = jax.jit(moe.moe_dropless_block, static_argnums=0)(cfg, p, x)
    monkeypatch.setattr(moe, "_held_experts", former_held_experts)
    jax.clear_caches()      # the block is jitted: trace it again
    want, former = jax.jit(moe.moe_dropless_block, static_argnums=0)(
        cfg, p, x)
    np.testing.assert_allclose(got, want, atol=tol, rtol=tol)
    np.testing.assert_array_equal(stats["rows"], former["rows"])
    np.testing.assert_array_equal(stats["load"], former["load"])
    lo, n = cfg.moe_expert_offset, cfg.num_experts
    assert float(stats["rows"][0]) == float(stats["load"][lo:lo + n].sum())
    assert float(stats["rows"].sum()) == 2 * 48 * cfg.moe_top_k
    assert 0 < float(stats["rows"][1])        # some choices are not here


def test_rotary_turns_a_quarter_of_the_head_and_keeps_the_norm():
    x = jax.random.normal(jax.random.key(5), (1, 7, 2, 32))
    pos = jnp.arange(7)[None] + 1000
    y = apply_rope_partial(x, pos, 8, 1e7)
    np.testing.assert_array_equal(y[..., 8:], x[..., 8:])
    np.testing.assert_allclose(jnp.linalg.norm(y[..., :8], axis=-1),
                               jnp.linalg.norm(x[..., :8], axis=-1),
                               rtol=1e-5)
    assert float(jnp.abs(y[..., :8] - x[..., :8]).max()) > 0.1
    np.testing.assert_array_equal(
        apply_rope_partial(x, jnp.zeros((1, 7), jnp.int32), 8, 1e7), x)


def test_the_zero_centred_norm_scales_by_one_plus_its_weight():
    p = norm_init("rmsnorm_zero", 16)
    assert float(jnp.abs(p["scale"]).max()) == 0.0
    x = jax.random.normal(jax.random.key(6), (3, 16))
    unit = norm_apply("rmsnorm_zero", x, p, 1e-6)
    np.testing.assert_allclose(jnp.mean(unit * unit, axis=-1), 1.0,
                               rtol=1e-4)
    np.testing.assert_allclose(
        norm_apply("rmsnorm_zero", x, {"scale": p["scale"] + 0.5}, 1e-6),
        1.5 * unit, rtol=1e-6)
