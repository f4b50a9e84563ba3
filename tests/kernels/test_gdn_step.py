"""A Gated DeltaNet layer's decode step between its two projections as
one kernel (``kernels/gdn_step.py``, in interpret mode here) against the
plain composition ``models/gated_deltanet.py:one_position`` runs a stage
at a time: the convolution against the tail, the L2 norms, the gates, the
delta rule, the output norm and its gate."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_llm_tpu.config import qwen3_next_config
from megatron_llm_tpu.kernels.gdn_step import gdn_step, heads_per_step
from megatron_llm_tpu.models import gated_deltanet as gdn

TOY = dict(num_layers=4, hidden_size=64, num_attention_heads=4,
           num_kv_heads=2, kv_channels=32, ffn_hidden_size=32,
           moe_shared_expert_size=32, num_experts=8, moe_router_experts=16,
           moe_top_k=4, vocab_size=512, linear_num_key_heads=2,
           linear_num_value_heads=4, linear_key_head_dim=16,
           linear_value_head_dim=16, max_position_embeddings=1024,
           make_vocab_size_divisible_by=8, moe_group_size=64)


def config(**kw):
    return qwen3_next_config("80b-a3b-ep2-rank0", **{**TOY, **kw})


def layer(key, cfg, slots, layers, dead=None):
    """A layer's parameters, one position's projections, the stacked
    states and tails, and ``live`` with slot ``dead`` not marked (None:
    the last)."""
    _nk, nv, dk, dv, ch = gdn.dims(cfg)
    ks = jax.random.split(key, 7)
    p = gdn.init_gdn_params(ks[0], cfg)
    p["norm"]["scale"] = (1.0 + 0.3 * jax.random.normal(
        ks[1], (dv,))).astype(p["norm"]["scale"].dtype)
    qkvz = 2.0 * jax.random.normal(ks[2], (slots, 1, ch + nv * dv))
    ba = 2.0 * jax.random.normal(ks[3], (slots, 1, 2 * nv))
    S = jax.random.normal(ks[4], (layers, slots, nv, dk, dv))
    tail = jax.random.normal(
        ks[5], (layers, slots, cfg.linear_conv_kernel - 1, ch))
    live = jnp.ones((slots,), bool).at[
        slots - 1 if dead is None else dead].set(False)
    return p, qkvz, ba, live, S, tail


def run(cfg, p, qkvz, ba, live, S, tail, at):
    # the layer as a traced scalar, as the scan over periods hands it
    # over: the kernel's own jitted call takes it so, and the cases that
    # differ in the layer alone run one executable
    return gdn_step(
        qkvz[:, 0], ba[:, 0], p["conv"], p["A_log"], p["dt_bias"],
        p["norm"]["scale"], live, S, tail, jnp.int32(at), eps=cfg.norm_eps)


_plain = jax.jit(gdn.one_position, static_argnums=0)


def check(cfg, p, qkvz, ba, live, S, tail, at, tol=2e-5):
    """The kernel's three results against the plain composition's; the
    other layers and the dead slots as they were, bit for bit."""
    o, new, new_tail = run(cfg, p, qkvz, ba, live, S, tail, at)
    want_o, want = _plain(cfg, p, qkvz, ba, gdn.GDNState(S[at], tail[at]),
                          live[:, None])
    assert o.shape == want_o[:, 0].shape and o.dtype == jnp.float32
    np.testing.assert_allclose(o, want_o[:, 0], atol=tol, rtol=tol)
    np.testing.assert_allclose(new[at], want.S, atol=tol, rtol=tol)
    np.testing.assert_array_equal(new_tail[at], want.conv)
    for other in set(range(S.shape[0])) - {at}:
        np.testing.assert_array_equal(new[other], S[other])
        np.testing.assert_array_equal(new_tail[other], tail[other])
    alive = np.asarray(live)
    np.testing.assert_array_equal(new[at][~alive], S[at][~alive])
    np.testing.assert_array_equal(new_tail[at][~alive], tail[at][~alive])
    if alive.any():
        first = int(np.argmax(alive))
        assert float(jnp.abs(new[at, first] - S[at, first]).max()) > 1e-3
        assert float(jnp.abs(new_tail[at, first]
                             - tail[at, first]).max()) > 1e-3


# the toy preset's heads at each slot count (1, 3, one block of eight, 11:
# no whole blocks) and, at 11, each layer (one executable: the layer is
# traced); the other head counts (one head; 16 key and 32 value heads: two
# grid steps a slot; four value heads a key head) at two of those
CASES = [((2, 4), 1, "first"), ((2, 4), 3, "middle"), ((2, 4), 8, "last"),
         ((2, 4), 11, "first"), ((2, 4), 11, "middle"), ((2, 4), 11, "last")
         ] + [(heads, slots, where)
              for heads in ((1, 1), (16, 32), (4, 16))
              for slots, where in ((3, "first"), (11, "last"))]


@pytest.mark.parametrize(
    "heads,slots,where", CASES,
    ids=["%dk%dv-%d-%s" % (*h, s, w) for h, s, w in CASES])
def test_one_layer_of_the_stacked_states_is_advanced_where_it_lies(
        heads, slots, where):
    nk, nv = heads
    cfg = config(linear_num_key_heads=nk, linear_num_value_heads=nv,
                 params_dtype="float32")
    at = {"first": 0, "middle": 1, "last": 2}[where]
    check(cfg, *layer(jax.random.key(nv * 100 + slots * 10 + at), cfg,
                      slots, 3), at)


@pytest.mark.parametrize("nk,nv,tile", [
    (16, 32, 16),         # the published mixer: two grid steps a slot
    (2, 4, 4), (1, 1, 1),
    (4, 16, 16),          # four value heads a key head
    (12, 24, 12),         # six key heads' value heads: no more divide 12
    (1, 32, 32),          # one key head's value heads are not cut
])
def test_a_grid_step_takes_whole_key_heads(nk, nv, tile):
    assert heads_per_step(nv, nk) == tile


def test_the_mixer_at_the_published_size():
    """The long-document cell's shapes: 44 slots (no whole blocks of
    eight), 16 key / 32 value heads x 128, taps 4, three stacked layers;
    the layer's parameters in bfloat16 as the cell holds them.  A dead
    slot in the middle of a block of eight and the last one."""
    cfg = config(linear_num_key_heads=16, linear_num_value_heads=32,
                 linear_key_head_dim=128, linear_value_head_dim=128,
                 params_dtype="bfloat16")
    assert gdn.dims(cfg) == (16, 32, 128, 128, 8192)
    p, qkvz, ba, live, S, tail = layer(jax.random.key(2), cfg, 44, 3,
                                       dead=10)
    check(cfg, p, qkvz, ba, live.at[43].set(False), S, tail, 2, tol=1e-4)


def test_every_slot_dead_changes_nothing():
    cfg = config(params_dtype="float32")
    p, qkvz, ba, _, S, tail = layer(jax.random.key(7), cfg, 4, 2)
    _, new, new_tail = run(cfg, p, qkvz, ba, jnp.zeros((4,), bool), S, tail,
                           0)
    np.testing.assert_array_equal(new, S)
    np.testing.assert_array_equal(new_tail, tail)


def test_a_state_that_is_not_stacked_goes_through_as_a_stack_of_one():
    """``gdn_block`` at one position with one layer's state (no ``at``):
    the kernel's path, the state back in the form it came, equal to the
    plain composition between the same two projections."""
    cfg = config(params_dtype="float32")
    p, _, _, live, S, tail = layer(jax.random.key(3), cfg, 3, 1)
    x = jax.random.normal(jax.random.key(4), (3, 1, cfg.hidden_size))
    state = gdn.GDNState(S[0], tail[0])
    text = str(jax.make_jaxpr(
        lambda x, st: gdn.gdn_block(cfg, p, x, st, live[:, None]))(x, state))
    assert text.count("pallas_call") == 1
    out, new = jax.jit(lambda x, st: gdn.gdn_block(
        cfg, p, x, st, live[:, None]))(x, state)
    assert new.at is None and new.S.shape == S[0].shape \
        and new.conv.shape == tail[0].shape
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(gdn, "_one_position", gdn.one_position)
        want_out, want = jax.jit(lambda x, st: gdn.gdn_block(
            cfg, p, x, st, live[:, None]))(x, state)
    np.testing.assert_allclose(out, want_out, atol=2e-5)
    np.testing.assert_allclose(new.S, want.S, atol=2e-5)
    np.testing.assert_array_equal(new.conv, want.conv)
