"""Deviceless compiles for a described TPU v5e: what Mosaic and XLA:TPU
accept, asked of the compiler itself.

Pallas interpret mode (every other kernel test here) enforces neither the
(8, 128) block rule, nor vector layouts, nor the partitioning of a
``pallas_call`` under a mesh — a kernel can pass all of them and stop at
its first compile on a chip.  The TPU compiler is installed without the
chip and compiles for a topology that is described, not attached
(``jax.experimental.topologies``), so each case below lowers one kernel
with ``interpret=False`` at a real width and compiles it for ``v5e:2x2``.
Nothing runs: a pass says "compiles", never "correct" or "fast".

Nine whole programs of the engine at published widths are compiled here
too (beside the Llama-family steps and verify walks at toy widths, 2-10 s
each), because what they hold shows only in the chip's compiler's output
at a cell's shapes, and ``chip_smoke.py``'s planning pass compiles a
Llama-2-7B-width train step and decode step and no other: the decode step
of each of six served families at its cell's slots, tables and pool (a
donated stack of states, rings or latent rows aliased and never copied, N
kernels a layer, the bytes ``memory_analysis`` states), one dropless
prefill, and Falcon's composed step on one chip and under tp=2 (no weight
re-laid, the sampler's sorts under their ``conditional``).  They are at
the cell's or the published depth, which costs nothing: a scan's body is
compiled once (``_SMALL_VOCAB`` says what does cost, what was cut, and
which two steps hold the head, the table and the sampler at a published
vocabulary).  13-17 s each alone on eight cores at 2048 entries and 28 s
at phi-4-flash's 200 064, 25-45 s beside five other workers; a new family
adds one, within the budget ROADMAP D17 (b) sets for a PR's tests.
"""

import contextlib
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import pytest  # noqa: E402
from jax.sharding import (  # noqa: E402
    NamedSharding,
    PartitionSpec as P,
    SingleDeviceSharding,
)

from megatron_llm_tpu import kernels  # noqa: E402
from megatron_llm_tpu.config import (  # noqa: E402
    ParallelConfig,
    deepseek_v3_config,
    llama2_config,
    nemotron_h_config,
    qwen3_next_config,
)
from megatron_llm_tpu.kernels import flash_decode as fd  # noqa: E402
from megatron_llm_tpu.kernels.flash_attention import flash_attention  # noqa: E402
from megatron_llm_tpu.kernels.grouped_matmul import (  # noqa: E402
    grouped_mlp,
)
from megatron_llm_tpu.kernels.gdn_step import gdn_step  # noqa: E402
from megatron_llm_tpu.kernels.mamba_step import mamba_step  # noqa: E402
from megatron_llm_tpu.kernels.moe_router import router_top_k  # noqa: E402
from megatron_llm_tpu.kernels.rmsnorm import (  # noqa: E402
    layernorm_pallas,
    rmsnorm_pallas,
)
from megatron_llm_tpu.models import model as model_lib  # noqa: E402
from megatron_llm_tpu.obs.hlo_audit import (  # noqa: E402
    ops_by_conditional,
    ops_under_scopes,
    relayout_bytes,
)
from megatron_llm_tpu.ops import attention as attn_ops  # noqa: E402
from megatron_llm_tpu.ops import lora as lora_ops  # noqa: E402
from megatron_llm_tpu.ops import quant  # noqa: E402
from megatron_llm_tpu.parallel import mesh as mesh_lib  # noqa: E402

BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu / unknown topology
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e}")


@pytest.fixture(scope="module", autouse=True)
def _as_the_program_compiles():
    """Two settings of the test bootstrap do not hold for these compiles.
    A deviceless executable is written to the persistent cache but can
    never be read back without a chip (each later compile would warn and
    recompile), so the cache stays off.  And tests/conftest.py pins
    ``jax_default_matmul_precision=highest`` for tight CPU numerics,
    which no entry point sets and Mosaic refuses for bf16 operands
    ("Bad lhs type"): the kernels compile at the default precision."""
    from jax.experimental.compilation_cache import compilation_cache

    cache = jax.config.jax_enable_compilation_cache
    precision = jax.config.jax_default_matmul_precision
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_default_matmul_precision", None)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", cache)
    jax.config.update("jax_default_matmul_precision", precision)
    compilation_cache.reset_cache()


def _compile(fn, args, sharding):
    """Lower ``fn`` over shape-only ``args`` placed by ``sharding`` (one
    sharding, or a pytree of them matching ``args``) and compile it for
    the described chip; the kernel must be IN the executable."""
    if not isinstance(sharding, (tuple, list)):
        sharding = jax.tree.map(lambda _: sharding, args)
    args = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        args, tuple(sharding))
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _sds(shape, dtype=BF16):
    return jax.ShapeDtypeStruct(shape, dtype)


# What a whole-step compile costs here (measured, PR 59: 23-28 s each alone
# on eight cores at the published vocabularies, 13-17 s at 2048) is the
# backend's code generation for ONE trip of each scan's body at the cell's
# slots (a quarter of a second a slot) and the sampler's ordering of the
# vocabulary (10 s at 100 352 entries, 24 s at 128 256): not the depth (8
# layers or 4, 40 or 20, 32 or 10 compile in the same seconds: a scan's
# body is compiled once) and not the experts.  So the depth and the slots
# stay the cell's, which the bytes asserted below need, and four decode
# steps (kanana, granite, Qwen3-Next, Laguna) and Falcon-40B's width under
# tp=2 compile at 2048 entries, with ``argument_size_in_bytes`` AND
# ``temp_size_in_bytes`` restated from the compile at 2048: each temp
# bound is the parent's times (reading at 2048 / reading at the published
# vocabulary), so it is as far above its reading as it was (the readings
# beside each bound).  At 2048 entries the table is 8 MiB, the least
# ``relayout_bytes`` reports, and a sort that small may be turned into a
# select: what reads the head, the table and the sampler AT SIZE is held
# by two steps that keep their published vocabulary,
# ``test_a_stack_of_runs_decode_step_at_its_published_depth`` (phi-4-mini-
# flash, 200 064 entries, the table tied to the head: no re-layout of
# 8 MiB in the step, the bytes) and
# ``test_composed_decode_step_touches_only_live_kv[falcon7b_one_chip]``
# (65 024: the table read where it lies, the sorts under their
# ``conditional``).
_SMALL_VOCAB = 2048


# -- attention / norm kernels at published widths --------------------------

@pytest.mark.parametrize("heads,kv,d,segs", [
    (32, 32, 128, False),   # Llama-2-7B MHA
    (32, 8, 128, False),    # GQA (Llama-2-70B group shape)
    (71, 1, 64, False),     # Falcon-7B MQA, 64-wide heads
    (32, 32, 128, True),    # packed documents
], ids=["mha_d128", "gqa_32_8", "mqa_71_1_d64", "segment_ids"])
def test_flash_attention_fwd_bwd(topo, heads, kv, d, segs):
    s = 1024
    one = SingleDeviceSharding(topo.devices[0])

    def loss(q, k, v, seg):
        o = flash_attention(q, k, v, segment_ids=seg if segs else None,
                            interpret=False)
        return o.astype(jnp.float32).sum()

    text = _compile(
        jax.grad(loss, argnums=(0, 1, 2)),
        (_sds((1, s, heads, d)), _sds((1, s, kv, d)), _sds((1, s, kv, d)),
         _sds((1, s), jnp.int32)), one)
    assert text.count("tpu_custom_call") >= 3  # fwd, dq, dkv


def test_flash_attention_forward_at_a_value_width_of_its_own(topo):
    """Latent attention's expanded form: 32 heads, queries and keys of
    192 (1.5 lane tiles) beside values of 128; the forward kernel alone."""
    s = 2048
    text = _compile(
        lambda q, k, v: flash_attention(q, k, v, interpret=False),
        (_sds((1, s, 32, 192)), _sds((1, s, 32, 192)),
         _sds((1, s, 32, 128))), SingleDeviceSharding(topo.devices[0]))
    assert text.count('custom_call_target="tpu_custom_call"') == 1


@pytest.mark.parametrize("s,heads,kv,d,dv,block,live", [
    (1280, 71, 1, 64, 64, 640, 3),          # a Falcon-7B bucket of 1280
    (16384, 32, 32, 192, 128, 1024, 136),   # latent attention's longest
], ids=["1280_d64", "16384_192_128"])
def test_flash_attention_tiles_cut_from_the_length(topo, s, heads, kv, d,
                                                   dv, block, live):
    """The traced program holds the forward kernel's blocks and its walk
    (a Mosaic kernel's body is bytecode in the lowered text): 1280 rows
    run as 2 x 640 with no ``pad`` of ``q`` (they ran as 2048), 16 384
    rows as 1024s over the 136 live tiles of 256."""
    args = (_sds((1, s, heads, d)), _sds((1, s, kv, d)),
            _sds((1, s, kv, dv)))
    fn = lambda q, k, v: flash_attention(q, k, v, interpret=False)  # noqa: E731
    traced = str(jax.make_jaxpr(fn)(*args))
    assert f"grid=(1, {heads}, {live})" in traced
    blocks = set(re.findall(r"Ref\{bf16\[1,1,(\d+),(\d+)\]\}", traced))
    assert blocks == {(str(block), str(d)), (str(block), str(dv))}
    assert " pad[" not in traced
    text = _compile(fn, args, SingleDeviceSharding(topo.devices[0]))
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert " pad(" not in text


@pytest.mark.parametrize("s,live", [(1536, 5), (4096, 15)])
def test_flash_attention_under_a_window_at_paired_value_heads(topo, s,
                                                              live):
    """The phi-4-mini-flash cell's window layers: 40 query heads of 64 on
    20 key heads of 64 and 10 value heads of 128 (differential attention
    as ordinary attention), a window of 512 in blocks of 512: the band is
    the diagonal tile and the one before it, 2 n - 1 tiles of n (n + 1) /
    2; forward only."""
    args = (_sds((1, s, 40, 64)), _sds((1, s, 20, 64)),
            _sds((1, s, 10, 128)))
    fn = lambda q, k, v: flash_attention(  # noqa: E731
        q, k, v, window=512, block_q=512, block_k=512, interpret=False)
    traced = str(jax.make_jaxpr(fn)(*args))
    assert f"grid=(1, 40, {live})" in traced
    text = _compile(fn, args, SingleDeviceSharding(topo.devices[0]))
    assert text.count('custom_call_target="tpu_custom_call"') == 1


def test_the_paged_walk_at_a_value_head_shared_by_two_key_heads(topo):
    """The phi-4-mini-flash cell's eight readers of one pool layer: 64
    slots of up to 64 blocks of 128 rows, 40 query heads on 20 key heads
    of 64 and 10 value heads of 128, the step's own rows beside the pool.
    The key leaf lies with its 128 positions as lanes and is taken so,
    the value leaf row-major: neither is copied."""
    S, T = 64, 64
    blocks = S * T + 1
    text = _compile(
        lambda q, k, v, tb, n, kn, vn, layer: fd.flash_decode_paged(
            q, k, v, tb, n, new_rows=(kn, vn), layer=layer[0],
            softmax_scale=0.125, interpret=False),
        (_sds((S, 40, 64)), _sds((1, blocks, 20, 128, 64)),
         _sds((1, blocks, 10, 128, 128)), _sds((S, T), jnp.int32),
         _sds((S,), jnp.int32), _sds((S, 20, 1, 64)),
         _sds((S, 10, 1, 128)), _sds((1,), jnp.int32)),
        SingleDeviceSharding(topo.devices[0]))
    _no_copy_of(text, f"bf16[1,{blocks},")
    assert relayout_bytes(text) == {}


def test_mla_decode(topo):
    """The latent walk at the kanana cell's shapes: 44 slots of up to 132
    blocks of 128 rows, 32 heads on one row of 512 + 64 a position, a
    layer of a six-layer pool.  Neither leaf of the pool is copied or
    re-laid: the 64-wide one lies with its 128 positions as lanes, and
    the kernel takes it so."""
    from megatron_llm_tpu.kernels.mla_decode import mla_decode

    S, T, blocks = 44, 132, 44 * 132 + 1
    text = _compile(
        lambda *a: mla_decode(*a, softmax_scale=0.07, interpret=False),
        (_sds((S, 32, 512)), _sds((S, 32, 64)),
         _sds((6, blocks, 1, 128, 512)), _sds((6, blocks, 1, 128, 64)),
         _sds((S, T), jnp.int32), _sds((S,), jnp.int32),
         _sds((S, 1, 512)), _sds((S, 1, 64)), _sds((), jnp.int32)),
        SingleDeviceSharding(topo.devices[0]))
    _no_copy_of(text, f"bf16[6,{blocks},1,128,512]")
    _no_copy_of(text, f"bf16[6,{blocks},1,128,64]")
    _no_copy_of(text, f"bf16[6,{blocks},1,64,128]")
    assert relayout_bytes(text) == {}


def _engine_decode_step(topo, monkeypatch, cfg, slots, table, blocks,
                        block=128):
    """The engine's decode step of ``cfg`` as a serving process on the
    chip traces it (the kernels not interpreted), lowered for one
    described chip over shape-only arguments: ``slots`` slots, tables of
    ``table`` columns, a pool of ``blocks`` blocks of ``block`` rows →
    ``(lowered, pool, rec)``, the shapes of what the step is donated."""
    from megatron_llm_tpu.serving import engine as engine_lib

    monkeypatch.setattr(attn_ops, "_backend", lambda: "tpu")
    monkeypatch.setattr(kernels, "default_interpret", lambda: False)
    one = SingleDeviceSharding(topo.devices[0])
    place = lambda tree: jax.tree.map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), tree)
    params = jax.eval_shape(
        lambda key: model_lib.init_params(key, cfg), jax.random.key(0))
    pool = jax.eval_shape(lambda: model_lib.init_kv_pool(cfg, blocks, block))
    rec = jax.eval_shape(lambda: model_lib.init_rec_state(cfg, slots))
    i32, f32 = jnp.int32, jnp.float32
    vec = lambda dtype: place(_sds((slots,), dtype))  # noqa: E731
    lowered = engine_lib._decode_donated.lower(
        cfg, place(params), *place(pool), place(_sds((slots, table), i32)),
        vec(i32), vec(i32), vec(jnp.uint32), vec(i32), vec(bool), vec(f32),
        vec(i32), vec(f32), rec=place(rec), live=vec(bool))
    return lowered, pool, rec


def test_a_latent_attention_decode_step_copies_no_pool_and_no_expert(
        topo, monkeypatch):
    """The engine's decode executable for the kanana-2 stage whole: the
    dense layer before the scan, five expert layers in its ``while``, 44
    slots of 16 896 positions, the vocabulary cut to 2048 (``_SMALL_VOCAB``:
    1.0 GB of table and head less): 11.7 GB of arguments of which the 5.1
    GB pool of latent rows is donated and aliased.  The scan closes over the
    stack's experts and the grouped kernel addresses its layer: sliced
    out for the custom call, a layer's 128 experts were three copies of
    0.4 GB, 6 GB a step (PR 52).  And ``wq`` lies where it lies: the
    query is rotated as the matmul leaves it (cut into heads at once the
    product re-laid 25 MB a layer)."""
    S, T, bk = 44, 132, 128
    cfg = deepseek_v3_config("kanana-2-30b-a3b-pp8-stage0",
                             attention_impl="flash", vocab_size=_SMALL_VOCAB)
    lowered, pool, _rec = _engine_decode_step(topo, monkeypatch, cfg, S, T,
                                              S * T + 1)
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    held = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(pool))
    assert held == 6 * (S * T + 1) * bk * 576 * 2
    assert held <= mem.alias_size_in_bytes < held + 2 ** 20
    assert 11.6e9 < mem.argument_size_in_bytes < 11.8e9
    # (4.29e6 read; 100.6e6 under 0.2e9 at the published 128 256 entries)
    assert mem.temp_size_in_bytes < 8.5e6
    text = compiled.as_text()
    # the walk once in the dense layer and once in the scan's body
    assert len(ops_under_scopes(text, ["mla_decode"], {"custom-call"})) == 2
    moved = relayout_bytes(text)
    assert not [k for k in moved if k.startswith(("bf16[128,", "bf16[5,128,",
                                                  "bf16[1,2048,6144]"))]
    # what is left: W_uk and W_uv, views of wkv_b taken in the step
    assert sum(moved.values()) < 0.12e9, moved
    for leaf in jax.tree.leaves(pool):
        shape = ",".join(map(str, leaf.shape))
        _no_copy_of(text, f"bf16[{shape}]")


def _no_copy_of(text, shape):
    """No instruction of the compiled module other than a parameter, a
    tuple access, a relabelling ``bitcast`` or an in-place
    ``dynamic-update-slice`` produces ``shape`` (an HLO shape prefix such
    as ``bf16[2,513,1,128,64]``)."""
    bad = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = (\S+) ([\w-]+)\(", line)
        if m and m.group(2).startswith(shape) and m.group(3) not in (
                "parameter", "get-tuple-element", "bitcast",
                "dynamic-update-slice"):
            bad.append(f"{m.group(1)}: {m.group(3)} {m.group(2)}")
    assert not bad, bad


# (heads, KV heads, head width, slots, table columns) the composed paged
# route serves: Falcon-7B (71 query heads over one KV head of width 64),
# Falcon-40B's GQA at that width, Llama-2-7B, whose layers the fused
# kernel's VMEM budget declines, and Qwen3-Next's full-attention layer under
# the long-document cell's 44 slots of up to 16 640 rows
_POOL_GEOMETRY = {"mqa64": (71, 1, 64, 16, 16), "gqa64": (128, 8, 64, 16, 16),
                  "mha128": (32, 32, 128, 16, 16),
                  "gqa256": (16, 2, 256, 44, 130)}


@pytest.mark.parametrize("variant", ["dense", "dense_int8", "paged",
                                     "paged_int8", "paged_pool_mqa64",
                                     "paged_pool_int8_mqa64",
                                     "paged_pool_gqa64",
                                     "paged_pool_mha128",
                                     "paged_pool_gqa256"])
def test_flash_decode(topo, variant):
    b, h, kv, d, max_len, bk = 8, 32, 32, 128, 2048, 128
    nb, t = 64, max_len // bk
    one = SingleDeviceSharding(topo.devices[0])
    if variant.startswith("paged_pool"):
        # the composed decode route's call, slots × table columns: the
        # whole [L, ...] pool, a traced layer index, the new token's rows
        # beside it.  At head width 64 the pool lies with its 128-row
        # dimension as lanes, and the kernel must take it as it lies: no
        # copy of the pool
        h, kv, d, b, t = _POOL_GEOMETRY[variant.rsplit("_", 1)[1]]
        layers, nb = 2, 513
        int8 = "int8" in variant
        pool = [_sds((layers, nb, kv, bk, d), jnp.int8 if int8 else BF16)]
        if int8:
            pool.append(_sds((layers, nb, kv, bk), jnp.float32))
        rows = _sds((b, kv, 1, d), jnp.float32 if int8 else BF16)
        call = fd.flash_decode_paged_int8 if int8 else fd.flash_decode_paged

        def fn(q, *rest):
            *leaves, tb, n, kn, vn, layer = rest
            return call(q, *leaves, tb, n, new_rows=(kn, vn),
                        layer=layer[0], interpret=False)

        text = _compile(fn, (_sds((b, h, d)), *pool, *pool,
                             _sds((b, t), jnp.int32), _sds((b,), jnp.int32),
                             rows, rows, _sds((1,), jnp.int32)), one)
        _no_copy_of(text, f"{'s8' if int8 else 'bf16'}[{layers},{nb},")
        return
    q, lens = _sds((b, h, d)), _sds((b,), jnp.int32)
    if variant == "dense":
        fn = lambda q, k, v, n: fd.flash_decode(  # noqa: E731
            q, k, v, n, interpret=False)
        args = (q, _sds((b, kv, max_len, d)), _sds((b, kv, max_len, d)),
                lens)
    elif variant == "dense_int8":
        fn = lambda q, k, ks, v, vs, n: fd.flash_decode_int8(  # noqa: E731
            q, k, ks, v, vs, n, interpret=False)
        c, sc = (_sds((b, kv, max_len, d), jnp.int8),
                 _sds((b, kv, max_len), jnp.float32))
        args = (q, c, sc, c, sc, lens)
    elif variant == "paged":
        fn = lambda q, k, v, tb, n: fd.flash_decode_paged(  # noqa: E731
            q, k, v, tb, n, interpret=False)
        p = _sds((nb, kv, bk, d))
        args = (q, p, p, _sds((b, t), jnp.int32), lens)
    else:
        fn = lambda q, k, ks, v, vs, tb, n: (  # noqa: E731
            fd.flash_decode_paged_int8(q, k, ks, v, vs, tb, n,
                                       interpret=False))
        p, sc = (_sds((nb, kv, bk, d), jnp.int8),
                 _sds((nb, kv, bk), jnp.float32))
        args = (q, p, sc, p, sc, _sds((b, t), jnp.int32), lens)
    _compile(fn, args, one)


@pytest.mark.parametrize("norm,hidden", [("rmsnorm", 4096),
                                         ("layernorm", 4544)])
def test_norm_fwd_bwd(topo, norm, hidden):
    one = SingleDeviceSharding(topo.devices[0])
    x, w = _sds((2, 1024, hidden)), _sds((hidden,))
    if norm == "rmsnorm":
        def loss(x, w):
            return rmsnorm_pallas(x, w, 1e-5, False).astype(
                jnp.float32).sum()
        _compile(jax.grad(loss, argnums=(0, 1)), (x, w), one)
    else:
        def loss(x, w, b):
            return layernorm_pallas(x, w, b, 1e-5, False).astype(
                jnp.float32).sum()
        _compile(jax.grad(loss, argnums=(0, 1, 2)), (x, w, w), one)


@pytest.mark.parametrize("s", [2048, 16384])
def test_gdn_block(topo, monkeypatch, s):
    """A prompt through the Gated DeltaNet mixer at the published widths
    of Qwen3-Next (16 key and 32 value heads of width 128, conv 4, hidden
    2048): between the two projections the kernel is the whole of it.
    One custom call, no ``while`` left of the scan over chunks; the
    projection's ``[1, s, 8192]`` output goes into the kernel as it lies
    and the kernel's ``[1, s, 4096]`` output into ``w_out``: nothing
    outside the projections' own fusions makes a float32 array of ``s``
    rows by 2048, 4096 or 8192, and nothing is re-laid (the mixer of PR
    37 moved 1664 MiB a 16k prompt around its kernel); ``g`` and ``beta``
    (1/400 of the bytes) are the only arrays transposed.  The kernel's
    blocks and temporaries take the VMEM stated here."""
    from megatron_llm_tpu.config import qwen3_next_config
    from megatron_llm_tpu.models import gated_deltanet as gdn

    monkeypatch.setattr(kernels, "default_interpret", lambda: False)
    one = SingleDeviceSharding(topo.devices[0])
    cfg = qwen3_next_config("80b-a3b-ep2-rank0", num_layers=4,
                            attention_impl="flash")
    assert gdn.dims(cfg) == (16, 32, 128, 128, 8192)
    f32 = jnp.float32
    params = jax.eval_shape(lambda key: gdn.init_gdn_params(key, cfg),
                            jax.random.key(0))
    state = jax.eval_shape(lambda: gdn.init_state(cfg, 1))
    text = _compile(
        lambda p, x, S, conv, valid: gdn.gdn_block(
            cfg, p, x, gdn.GDNState(S, conv), valid),
        (params, _sds((1, s, cfg.hidden_size), f32), state.S, state.conv,
         _sds((1, s), jnp.bool_)), one)
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert " while(" not in text
    assert not relayout_bytes(text, min_bytes=4 * s * 2048)
    made = []
    for line in text.splitlines():
        m = re.match(rf"\s*(?:ROOT )?%(\S+) = f32\[1,{s},(?:2048|4096|8192)\]"
                     r"\S* ([\w-]+)\(", line)
        if m and "/gdn_proj/" not in line and m.group(2) not in (
                "parameter", "get-tuple-element", "bitcast", "copy-start",
                "copy-done"):
            made.append(line.strip()[:160])
    assert not made, made
    call, = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    vmem, = re.findall(r'"used_scoped_memory_configs":\[\{"memory_space":'
                       r'"1","offset":"0","size":"(\d+)"', call)
    # 512 rows a step of q, k (128 wide), v, z and o (256 wide), double
    # buffered: 4 MiB; two heads' state in and out: 0.5 MiB; the rest is
    # what a chunk's matrices spill.  Of 16 MiB a kernel may have by
    # default
    assert 4.5 * 2 ** 20 < int(vmem) < 9 * 2 ** 20, vmem


def test_gdn_step(topo):
    """The one-position DeltaNet mixer's kernel alone at the published
    widths and the long-document cell's 44 slots (no whole blocks of
    eight): 16 key / 32 value heads x 128, the three layers' states (277
    MB) and their tails of three rows stacked and donated, the layer a
    traced scalar.  Both are aliased to the outputs and nothing of their
    size is made beside them: XLA:TPU keeps the three rows outermost of a
    layer, and the kernel takes them so."""
    one = SingleDeviceSharding(topo.devices[0])
    L, b, nk, nv, dk, dv, taps = 3, 44, 16, 32, 128, 128, 4
    ch = 2 * nk * dk + nv * dv
    f32 = jnp.float32
    args = [jax.ShapeDtypeStruct(s, d, sharding=one) for s, d in (
        ((b, ch + nv * dv), f32), ((b, 2 * nv), f32), ((taps, ch), BF16),
        ((nv,), f32), ((nv,), f32), ((dv,), BF16), ((b,), bool),
        ((L, b, nv, dk, dv), f32), ((L, b, taps - 1, ch), f32),
        ((1,), jnp.int32))]
    compiled = jax.jit(
        lambda qkvz, ba, w, alog, dtb, scale, live, S, tails, at:
        gdn_step(qkvz, ba, w, alog, dtb, scale, live, S, tails, at[0],
                 eps=1e-6, interpret=False),
        donate_argnums=(7, 8)).lower(*args).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    # (the tails' 44 slots are padded to whole tiles of eight)
    assert mem.alias_size_in_bytes == 4 * L * (
        b * nv * dk * dv + (taps - 1) * 48 * ch)
    assert mem.temp_size_in_bytes < 2 ** 20
    _no_copy_of(text, _shape((L, b, nv, dk, dv)))
    _no_copy_of(text, _shape((L, b, taps - 1, ch)))
    _no_copy_of(text, _shape((L, taps - 1, b, ch)))


@pytest.mark.parametrize("tokens", [16384, 44], ids=["prompt", "step"])
def test_grouped_mlp(topo, tokens):
    """The held experts' kernel at the published widths of Qwen3-Next (256
    held experts of 2048 x 512, ten choices a token): a 16 384-token
    prompt's 163 840 pairs in tiles of 128 rows, a 44-slot decode step's
    440 in tiles of 16.  Mosaic takes the row copies (a row is two
    (8, 128) tiles of float32 in a ``[rows x 16, 128]`` view), the sorted
    pairs fit the scalar memory, and three whole matrices of an expert,
    double buffered, fit the VMEM the kernel asks for."""
    from megatron_llm_tpu.ops.activations import swiglu

    one = SingleDeviceSharding(topo.devices[0])
    E, h, f, k = 256, 2048, 512, 10
    i32 = jnp.int32
    text = _compile(
        lambda x, order, sizes, *w: grouped_mlp(
            x, order, sizes, *w, swiglu, choices=k, interpret=False),
        (_sds((tokens, h), jnp.float32), _sds((tokens * k,), i32),
         _sds((E,), i32), _sds((E, h, f)), _sds((E, h, f)),
         _sds((E, f, h))), one)
    assert f"f32[{tokens * k * 16},128]" in text        # rows as they lie
    assert not relayout_bytes(text, min_bytes=2 * E * h * f)
    call, = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    vmem, = re.findall(r'"used_scoped_memory_configs":\[\{"memory_space":'
                       r'"1","offset":"0","size":"(\d+)"', call)
    # an expert's three matrices twice: 12 MiB; the rows in and out, two
    # tiles each: 4 MiB at 128 rows; what a tile's products spill
    assert 12 * 2 ** 20 < int(vmem) <= 64 * 2 ** 20, vmem


@pytest.mark.parametrize("tokens", [2048, 128], ids=["prompt", "step"])
def test_grouped_mlp_of_two_matrices_in_a_latent(topo, tokens):
    """The same kernel at Nemotron-3-Super's widths: 128 held experts of
    two matrices (1024 x 2688, relu^2 between: ``w_gate=None``) in the
    1024-wide latent, 22 choices a token: a 2048-token bucket's 45 056
    pairs, a 128-slot decode step's 2816.  A row is one (8, 128) tile."""
    from megatron_llm_tpu.ops.activations import squared_relu

    one = SingleDeviceSharding(topo.devices[0])
    E, h, f, k = 128, 1024, 2688, 22
    i32 = jnp.int32
    text = _compile(
        lambda x, order, sizes, *w: grouped_mlp(
            x, order, sizes, None, *w, squared_relu, choices=k,
            interpret=False),
        (_sds((tokens, h), jnp.float32), _sds((tokens * k,), i32),
         _sds((E,), i32), _sds((E, h, f)), _sds((E, f, h))), one)
    assert f"f32[{tokens * k * 8},128]" in text         # rows as they lie
    assert not relayout_bytes(text, min_bytes=2 * E * h * f)
    assert text.count('custom_call_target="tpu_custom_call"') == 1


def _mixer_alone(topo, L, b, H, P, N, G, flat):
    """The one-position mixer's kernel alone for a described chip, the
    stacked states and tails donated: ``(text, memory analysis, the two
    stacked shapes)``."""
    one = SingleDeviceSharding(topo.devices[0])
    di, taps = H * P, 4
    ch = di + 2 * G * N
    tail = (L, b, (taps - 1) * ch) if flat else (L, b, taps - 1, ch)
    f32 = jnp.float32
    args = [jax.ShapeDtypeStruct(s, d, sharding=one) for s, d in (
        ((b, di + ch + H), f32), ((taps, ch), BF16), ((ch,), BF16),
        ((H,), f32), ((H,), f32), ((H,), f32), ((di,), BF16), ((b,), bool),
        ((L, b, H, P, N), f32), (tail, f32), ((1,), jnp.int32))]
    compiled = jax.jit(
        lambda zx, w, bias, dtb, alog, D, scale, live, ssm, tails, at:
        mamba_step(zx, w, bias, dtb, alog, D, scale, live, ssm, tails,
                   at[0], eps=1e-5, interpret=False),
        donate_argnums=(8, 9)).lower(*args).compile()
    return compiled.as_text(), compiled.memory_analysis(), (
        (L, b, H, P, N), tail)


def _shape(dims):
    return "f32[" + ",".join(map(str, dims)) + "]"


def test_mamba_step(topo):
    """The one-position mixer's kernel alone at Nemotron-3-Super's
    widths: 128 slots of 128 heads x 64 in 8 groups, state width 128, the
    five layers' states and their tails of three rows stacked and
    donated, the layer a traced scalar.  The 2.7 GB of states and the 79
    MB of tails are aliased to the outputs and nothing of their size is
    made beside them: XLA:TPU keeps the three rows outermost of a layer,
    and the kernel takes them so (as ``[.., slots, 3, channels]`` blocks
    the whole array was copied at both ends of the call)."""
    L, b, H, P, N, G = 5, 128, 128, 64, 128, 8
    text, mem, (ssm, tail) = _mixer_alone(topo, L, b, H, P, N, G, False)
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert mem.alias_size_in_bytes == 4 * L * b * (
        H * P * N + 3 * (H * P + 2 * G * N))
    assert mem.temp_size_in_bytes < 2 ** 20
    _no_copy_of(text, _shape(ssm))
    _no_copy_of(text, _shape(tail))
    _no_copy_of(text, _shape((L, 3, b, tail[-1])))


def _beside_the_mixer(text, slots):
    """What a compiled step's state-space layers run at one position
    between their two projections (under ``mamba``, outside
    ``mamba_proj``) besides the kernel: nothing but relabellings and,
    once a program, the ``live`` mask turned into the integers the kernel
    prefetches (alone, or in one fusion with another conversion of the
    same mask: the float the router's kernel counts by, PR 53)."""
    relabels = {"custom-call", "get-tuple-element", "bitcast", "tuple",
                "constant", "parameter"}
    return [(op, result, path)
            for op, result, path in ops_under_scopes(text, ["mamba"], None)
            if "mamba_proj" not in path.split("/") and op not in relabels
            and not re.match(rf"\(?s32\[{slots}(,1)?\]", result)]


def test_a_state_space_decode_step_rewrites_its_states_in_place(
        topo, monkeypatch):
    """The engine's decode executable for Nemotron-3-Super's 11-layer run
    at the published widths and 128 slots: 12.6 GB of arguments (weights,
    pool, 2.7 GB of state-space states), every donated byte aliased to an
    output and under 0.3 GB of temporaries: each Mamba-2 layer's kernel
    (``kernels/mamba_step.py``) takes the stacked states and tails and
    advances its layer where it lies, no other operation of the step
    touches them, and between the layer's two projections the kernel is
    all that runs.  A second copy of the states would not fit the chip."""
    S, blocks = 128, 32
    cfg = nemotron_h_config("3-super-120b-a12b-ep4-rank0", num_layers=11,
                            attention_impl="flash")
    lowered, pool, rec = _engine_decode_step(topo, monkeypatch, cfg, S,
                                             blocks, S * blocks + 1)
    assert rec["ssm"].shape == (5, S, 128, 64, 128)
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    donated = sum(a.size * a.dtype.itemsize
                  for a in jax.tree.leaves((pool, rec)))
    assert 12.4e9 < mem.argument_size_in_bytes < 12.8e9
    # (small arrays are padded to whole tiles)
    assert donated <= mem.alias_size_in_bytes < donated + 2 ** 20
    assert mem.temp_size_in_bytes < 0.3e9
    text = compiled.as_text()
    assert text.count("flash_decode") and text.count("grouped_experts")
    # under the step's scope the kernel once a Mamba-2 layer; and whatever
    # makes an array of a layer's states or of them all (the parameter,
    # the kernels, the accesses to their results) is taken by those and
    # the program's result alone
    assert len(ops_under_scopes(text, ["mamba_step"], {"custom-call"})) \
        == cfg.mamba_layers == 5
    assert _beside_the_mixer(text, S) == []
    assert not [k for k in relayout_bytes(text) if k.startswith("f32[5,")]
    layer = re.escape("%d,%d,%d,%d]" % rec["ssm"].shape[1:])
    made = set(re.findall(rf"(%\S+) = [^=]*{layer}[^=]* [\w-]+\(", text))
    assert len(made) == 1 + 2 * cfg.mamba_layers, made
    takers = [line for line in text.splitlines() if " = " in line
              and made & set(re.findall(r"%[\w.-]+", line.split(" = ")[1]))
              and not re.search(r" (custom-call|get-tuple-element|tuple)\(",
                                line)]
    assert not takers, takers


def test_mamba_step_at_one_group_of_64_heads(topo):
    """The kernel at granite-4.0-h-micro's widths: 64 slots of 64 heads x
    64 in ONE group, state width 128, 36 layers' states (4.8 GB) and flat
    tails (120 MB) stacked and donated.  A group of 64 is cut into blocks
    of 16, two a grid step; the group's norm spans both grid steps of a
    slot; the projection's row of 8512 is no whole number of registers."""
    from megatron_llm_tpu.kernels.mamba_step import heads_per_step

    L, b, H, P, N, G = 36, 64, 64, 64, 128, 1
    assert heads_per_step(H, G) == heads_per_step(128, 8) == 32
    text, mem, (ssm, tail) = _mixer_alone(topo, L, b, H, P, N, G, True)
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert mem.alias_size_in_bytes == 4 * L * b * (
        H * P * N + 3 * (H * P + 2 * G * N))
    assert mem.temp_size_in_bytes < 2 ** 21
    _no_copy_of(text, _shape(ssm))
    _no_copy_of(text, _shape(tail))


def test_a_whole_depth_hybrid_step_moves_no_stacked_state(topo, monkeypatch):
    """The engine's decode executable and its state install for
    granite-4.0-h-micro at its depth: 40 layers in four scanned periods,
    64 slots, the vocabulary cut to 2048 (``_SMALL_VOCAB``), 12.5 GB of
    arguments of which the 6.6 GB of pool and slot state are donated and
    aliased.  Inside the scan's ``while`` every
    state-space layer advances its own layer of the stacked states where
    they lie (between its two projections the kernel is all that runs),
    and nothing copies or re-lays an array of all the layers' states or
    tails: with the tail stacked as ``[36, 64, 3, 4352]`` (three
    rows padded to a tile of four) XLA:TPU re-laid the whole 160 MB array
    twice between every two layers of a period, 7 GB a step (PR 49)."""
    from megatron_llm_tpu.config import granite_hybrid_config
    from megatron_llm_tpu.serving import slots as slots_lib

    S, blocks = 64, 24
    cfg = granite_hybrid_config("4.0-h-micro", attention_impl="flash",
                                vocab_size=_SMALL_VOCAB)
    lowered, pool, rec = _engine_decode_step(topo, monkeypatch, cfg, S,
                                             blocks, S * blocks + 1)
    assert rec["ssm"].shape == (36, S, 64, 64, 128)
    assert rec["ssm_conv"].shape == (36, S, 3 * 4352)
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    donated = sum(a.size * a.dtype.itemsize
                  for a in jax.tree.leaves((pool, rec)))
    assert 12.4e9 < mem.argument_size_in_bytes < 12.7e9
    assert donated <= mem.alias_size_in_bytes < donated + 2 ** 20
    # (5.32e6 read; 83.3e6 under 0.15e9 at the published 100 352 entries)
    assert mem.temp_size_in_bytes < 9.5e6
    text = compiled.as_text()
    # the kernel once a state-space layer of a period's body
    assert len(ops_under_scopes(text, ["mamba_step"], {"custom-call"})) \
        == 9
    assert _beside_the_mixer(text, S) == []
    assert not [k for k in relayout_bytes(text) if k.startswith("f32[36,")]
    assert "remat_compressed" not in text
    # and the install writes one slot's rows into the donated stack
    one = SingleDeviceSharding(topo.devices[0])
    place = lambda tree: jax.tree.map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), tree)
    slot = jax.eval_shape(lambda: model_lib.init_rec_state(cfg, 1))
    mem = slots_lib._install_rec_donated.lower(
        place(rec), place(slot), place(_sds((), jnp.int32))).compile(
        ).memory_analysis()
    states = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(rec))
    assert states <= mem.alias_size_in_bytes < states + 2 ** 20
    assert mem.temp_size_in_bytes < 2 ** 20


def test_a_stack_of_runs_decode_step_at_its_published_depth(topo,
                                                            monkeypatch):
    """The engine's decode executable for phi-4-mini-flash-reasoning
    whole: 32 layers in three runs, 64 slots of 8192 positions, the
    published 200 064 entries in a table tied to the head (the largest
    head served, and kept at its size: ``_SMALL_VOCAB``), 11.96 GB of
    arguments of which the 4.25 GB of pool, Mamba-1 states and window
    rings are donated and aliased, 0.33 GB of temporaries (the logits and
    the sampler's buffers are most of them: 18 MB at 2048 entries).  Three
    kernels in the program text
    (the full layer's walk, written out, the cross layers' in their run's
    ``while``, and the window layers' ring kernel in theirs, which takes
    the stacked rings and the layer's index: no ring is sliced or
    copied), no weight re-laid (the query and output projections are cut
    into heads behind a barrier), the stacked Mamba-1 states advanced
    where they lie."""
    from megatron_llm_tpu.config import phi4flash_config

    S, blocks, bk = 64, 64, 128
    cfg = phi4flash_config(attention_impl="flash")
    lowered, pool, rec = _engine_decode_step(topo, monkeypatch, cfg, S,
                                             blocks, S * blocks + 1)
    assert rec["ssm1"].shape == (9, S, 16, 5120)
    assert rec["win_k"].shape == (8, S, 10, 512, 128)
    assert rec["win_v"].shape == (8, S, 10, 512, 128)
    assert [a.shape for a in pool] == [(1, S * blocks + 1, 20, bk, 64),
                                       (1, S * blocks + 1, 10, bk, 128)]
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    donated = sum(a.size * a.dtype.itemsize
                  for a in jax.tree.leaves((pool, rec)))
    assert 11.9e9 < mem.argument_size_in_bytes < 12.0e9
    assert donated <= mem.alias_size_in_bytes < donated + 2 ** 20
    assert mem.temp_size_in_bytes < 0.4e9
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    assert relayout_bytes(text) == {}
    _no_copy_of(text, "bf16[8,64,10,512,128]")
    _no_copy_of(text, "bf16[1,64,10,512,128]")
    assert "remat_compressed" not in text
    _no_copy_of(text, f"bf16[1,{S * blocks + 1},")
    # (a layer's new state is computed in the fusion whose root writes it
    # into the stacked array: in place, by the alias above)
    assert not [k for k in relayout_bytes(text) if k.startswith("f32[9,")]


def test_a_delta_rule_decode_step_advances_its_states_where_they_lie(
        topo, monkeypatch):
    """The engine's decode executable for Qwen3-Next at the published
    widths and the long-document cell's 44 slots, two periods with 64 held
    experts and a vocabulary of 2048 (``_SMALL_VOCAB``): the scan is a
    ``while`` and a layer's place in the stacked
    states a traced scalar (the cell's stage of one period unrolls; it
    read the same under these assertions in a scratch compile and in the
    chip's trace, PERF.md, PR 51).  Each DeltaNet layer's kernel
    (``kernels/gdn_step.py``) takes the stacked states and tails from the
    scan's carry and advances its layer where it lies: between the
    layer's two projections the kernel is all that runs, and nothing else
    makes, copies or re-lays an array of all the layers' states or tails
    (the parent's step re-stacked the states after the scan, 554 MB a
    step, and its update made four passes; PR 49's tails were re-laid
    whole between the layers of a ``while``)."""
    periods = 2
    S, blocks = 44, 130
    cfg = qwen3_next_config(
        "80b-a3b-ep2-rank0", num_layers=4 * periods, attention_impl="flash",
        num_experts=64, vocab_size=_SMALL_VOCAB)
    lowered, pool, rec = _engine_decode_step(topo, monkeypatch, cfg, S,
                                             blocks, S * blocks + 1)
    L = 3 * periods
    assert rec["S"].shape == (L, S, 32, 128, 128)
    assert rec["conv"].shape == (L, S, 3, 8192)
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    donated = sum(a.size * a.dtype.itemsize
                  for a in jax.tree.leaves((pool, rec)))
    # (the tails' 44 slots are padded to whole tiles of eight)
    assert donated <= mem.alias_size_in_bytes < donated + 2 ** 22
    # (7.97e6 read; 24.4e6 under 0.5e9 at the rank's 75 968 entries)
    assert mem.temp_size_in_bytes < 0.16e9
    text = compiled.as_text()
    # the kernel once a DeltaNet layer of a period's body, and beside it
    # between the projections relabellings and constants alone
    assert len(ops_under_scopes(text, ["gdn_step"], {"custom-call"})) == 3
    beside = {op for op, _, path in ops_under_scopes(text, ["gdn"], None)
              if "gdn_proj" not in path.split("/")}
    assert beside <= {"custom-call", "get-tuple-element", "bitcast",
                      "constant", "convert", "tuple", "parameter"}, beside
    assert not [k for k in relayout_bytes(text) if k.startswith(f"f32[{L},")]
    assert "remat_compressed" not in text
    # whatever makes an array of all the layers' states is a kernel, or
    # hands a kernel's result on
    made = {m.group(2) for m in re.finditer(
        rf"%(\S+) = f32\[{L},{S},32,128,128\]\S* ([\w-]+)\(", text)}
    assert made <= {"parameter", "custom-call", "get-tuple-element",
                    "bitcast"}, made


def test_a_dropless_prefill_routes_through_the_grouped_kernel(topo,
                                                              monkeypatch):
    """The engine's prefill executable for one period of Qwen3-Next at the
    published widths, a 2048-token bucket: under the experts' two scopes
    the kernel is there, and no scatter, no ragged product and no gather
    of rows is left (``searchsorted``'s 257 looked-up keys are the only
    gather): the pairs are sorted, the kernel fetches and places the
    rows, one dense pass sums a token's choices."""
    from megatron_llm_tpu.config import qwen3_next_config
    from megatron_llm_tpu.serving import engine as engine_lib

    monkeypatch.setattr(attn_ops, "_backend", lambda: "tpu")
    monkeypatch.setattr(kernels, "default_interpret", lambda: False)
    one = SingleDeviceSharding(topo.devices[0])
    s = 2048
    cfg = qwen3_next_config("80b-a3b-ep2-rank0", num_layers=4,
                            attention_impl="flash")
    place = lambda tree: jax.tree.map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), tree)
    params = jax.eval_shape(
        lambda key: model_lib.init_params(key, cfg), jax.random.key(0))
    text = engine_lib._prefill_impl.lower(
        cfg, place(params), place(_sds((1, s), jnp.int32)),
        place(_sds((1,), jnp.int32)), max_seq_len=s + 256,
        want_logprobs=False).compile().as_text()
    scopes = ("moe_dispatch", "moe_experts")
    kernels_there = [path for _op, _type, path in ops_under_scopes(
        text, scopes, {"custom-call"}) if "grouped_experts" in path]
    assert len(kernels_there) == cfg.num_layers
    assert not ops_under_scopes(text, scopes, {"scatter", "ragged-dot"})
    assert "ragged" not in text
    rows = s * cfg.moe_top_k
    for _op, result, path in ops_under_scopes(text, scopes, {"gather"}):
        assert "searchsorted" in path and f"[{rows}" not in result, (
            result, path)



# -- the period scan's "window" kind at Laguna-XS.2's widths ---------------

@pytest.mark.parametrize("s,live", [(2048, 7), (16384, 63)])
def test_flash_attention_under_a_window_at_eight_query_heads_a_kv_head(
        topo, s, live):
    """The Laguna cell's window layers: 64 query heads of 128 on 8 KV
    heads of 128 (ordinary grouped heads, keys and values of one width),
    a window of 512 in blocks of 512, at the smallest and the largest of
    the cell's eight prefill buckets: the band is the diagonal tile and
    the one before it; forward only."""
    args = (_sds((1, s, 64, 128)), _sds((1, s, 8, 128)),
            _sds((1, s, 8, 128)))
    fn = lambda q, k, v: flash_attention(  # noqa: E731
        q, k, v, window=512, block_q=512, block_k=512, interpret=False)
    traced = str(jax.make_jaxpr(fn)(*args))
    assert f"grid=(1, 64, {live})" in traced
    text = _compile(fn, args, SingleDeviceSharding(topo.devices[0]))
    assert text.count('custom_call_target="tpu_custom_call"') == 1


def test_the_ring_kernel_at_one_key_head_a_row(topo):
    """The Laguna cell's window layers' decode step: 40 slots, three
    stacked rings of 512 rows of 8 KV heads of 128 (r = 1), 64 query heads
    as 8 rows a KV head; the stacked rings are read where they lie."""
    from megatron_llm_tpu.kernels.ring_decode import ring_decode

    S = 40
    text = _compile(
        lambda q, rk, rv, kn, vn, pos, layer: ring_decode(
            q, rk, rv, kn, vn, pos, layer[0], softmax_scale=128 ** -0.5,
            interpret=False),
        (_sds((S, 64, 128)), _sds((3, S, 8, 512, 128)),
         _sds((3, S, 8, 512, 128)), _sds((S, 8, 1, 128)),
         _sds((S, 8, 1, 128)), _sds((S,), jnp.int32),
         _sds((1,), jnp.int32)),
        SingleDeviceSharding(topo.devices[0]))
    _no_copy_of(text, "bf16[3,40,8,512,128]")
    _no_copy_of(text, "bf16[1,40,8,512,128]")
    assert relayout_bytes(text) == {}


def test_grouped_mlp_at_256_experts_of_512_and_eight_choices(topo):
    """The held experts' kernel at Laguna-XS.2's widths (256 experts of
    2048 x 512, eight choices a token): a 16 384-token prompt's 131 072
    pairs and a 40-slot step's 320."""
    from megatron_llm_tpu.ops.activations import swiglu

    one = SingleDeviceSharding(topo.devices[0])
    E, h, f, k = 256, 2048, 512, 8
    i32 = jnp.int32
    for tokens in (16384, 40):
        text = _compile(
            lambda x, order, sizes, *w: grouped_mlp(
                x, order, sizes, *w, swiglu, choices=k, interpret=False),
            (_sds((tokens, h), jnp.float32), _sds((tokens * k,), i32),
             _sds((E,), i32), _sds((E, h, f)), _sds((E, h, f)),
             _sds((E, f, h))), one)
        assert not relayout_bytes(text, min_bytes=2 * E * h * f)


def test_a_window_and_full_decode_step_at_the_cells_size(topo, monkeypatch):
    """The engine's decode executable for the Laguna cell: five layers (a
    leading dense full layer, three window layers, a full expert layer),
    40 slots, a pool of 2560 blocks over the TWO full layers, three rings
    a slot, the vocabulary cut to 2048 (``_SMALL_VOCAB``).  9.88 GB of
    arguments of which pool and rings (2.94 GB) are donated and aliased; thirteen kernels in the program text (two paged
    walks, three ring kernels, four routers and four grouped expert
    kernels); neither the pool nor the stacked rings nor an expert stack
    is copied."""
    from megatron_llm_tpu.config import laguna_config

    S, blocks, bk, T = 40, 2560, 128, 144
    cfg = laguna_config(attention_impl="flash", vocab_size=_SMALL_VOCAB)
    lowered, pool, rec = _engine_decode_step(topo, monkeypatch, cfg, S, T,
                                             blocks)
    assert rec["win_k"].shape == rec["win_v"].shape == (3, S, 8, 512, 128)
    assert [a.shape for a in pool] == [(2, blocks, 8, bk, 128)] * 2
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    donated = sum(a.size * a.dtype.itemsize
                  for a in jax.tree.leaves((pool, rec)))
    assert 9.8e9 < mem.argument_size_in_bytes < 10.0e9
    assert donated <= mem.alias_size_in_bytes < donated + 2 ** 20
    # (27.5e6 read; 28.2e6 under 0.2e9 at the published 100 352 entries)
    assert mem.temp_size_in_bytes < 0.19e9
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 13
    _no_copy_of(text, "bf16[3,40,8,512,128]")
    _no_copy_of(text, "bf16[1,40,8,512,128]")
    _no_copy_of(text, f"bf16[2,{blocks},")
    _no_copy_of(text, f"bf16[1,{blocks},")
    _no_copy_of(text, "bf16[256,2048,512]")
    _no_copy_of(text, "bf16[1,256,2048,512]")

# -- the dropless router's choice -------------------------------------------

@pytest.mark.parametrize("tokens,experts,k,biased", [
    (11264, 512, 10, False), (44, 512, 10, False),
    (1280, 512, 22, True), (128, 512, 22, True),
    (12288, 128, 6, True), (48, 128, 6, True),
    (1531, 128, 6, True), (1, 512, 10, False),
], ids=["longdoc_prompt", "longdoc_step", "reasoning_prompt",
        "reasoning_step", "longqa_prompt", "longqa_step", "a_ragged_tile",
        "one_token"])
def test_moe_router(topo, tokens, experts, k, biased):
    """The router's choice at the three expert cells' widths, a prompt's
    tokens and a decode step's: Mosaic takes a round's two reductions down
    the sublanes of an ``[experts, tokens]`` tile, a round's row stored at
    a traced row of the ``[k, tokens]`` outputs, tiles of fewer lanes than
    a register (44, 48, 1) and a last tile that reaches past the tokens;
    XLA hands the scores over experts-major and takes the results back
    tokens-major, and nothing under the call sorts, gathers or
    scatters."""
    one = SingleDeviceSharding(topo.devices[0])
    f32 = jnp.float32
    if biased:
        fn, args = (lambda s, b, c: router_top_k(s, b, c, k, interpret=False),
                    (_sds((tokens, experts), f32), _sds((experts,), f32),
                     _sds((tokens,), f32)))
    else:
        fn, args = (lambda s, c: router_top_k(s, None, c, k, interpret=False),
                    (_sds((tokens, experts), f32), _sds((tokens,), f32)))
    text = _compile(fn, args, one)
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert not re.search(r" (sort|scatter|gather|topk)\(", text)


_ROUTED = {
    "qwen3_next": lambda: qwen3_next_config(
        "80b-a3b-ep2-rank0", num_layers=4, attention_impl="flash"),
    "nemotron_h": lambda: nemotron_h_config(
        "3-super-120b-a12b-ep4-rank0", num_layers=11, attention_impl="flash"),
    "deepseek_v3": lambda: deepseek_v3_config(
        "kanana-2-30b-a3b-pp8-stage0", attention_impl="flash"),
}


@pytest.mark.parametrize("program", ["prefill", "decode"])
@pytest.mark.parametrize("preset", sorted(_ROUTED))
def test_a_router_chooses_without_a_sort_a_gather_or_a_scatter(
        topo, monkeypatch, preset, program):
    """The engine's prefill and decode programs of the three expert
    presets at their published widths, lowered for the chip (nothing is
    compiled): under ``moe_router`` stand the float32 product at
    ``Precision.HIGHEST``, the scoring and ONE call of the kernel
    (``_dropless`` is traced once a program), and no ``topk`` (a sort on
    this backend), no ``sort``, no ``gather`` and no ``scatter``: the
    parent's programs held a ``topk`` and a ``scatter`` there, each
    (PERF.md, PR 53)."""
    from jax._src.lib import xla_client

    from megatron_llm_tpu.serving import engine as engine_lib

    cfg = _ROUTED[preset]()
    if program == "prefill":
        monkeypatch.setattr(attn_ops, "_backend", lambda: "tpu")
        monkeypatch.setattr(kernels, "default_interpret", lambda: False)
        one = SingleDeviceSharding(topo.devices[0])
        place = lambda tree: jax.tree.map(  # noqa: E731
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
            tree)
        params = jax.eval_shape(
            lambda key: model_lib.init_params(key, cfg), jax.random.key(0))
        lowered = engine_lib._prefill_impl.lower(
            cfg, place(params), place(_sds((1, 2048), jnp.int32)),
            place(_sds((1,), jnp.int32)), max_seq_len=2048 + 256,
            want_logprobs=False)
    else:
        S, blocks = 8, 4
        lowered, _pool, _rec = _engine_decode_step(
            topo, monkeypatch, cfg, S, blocks, S * blocks + 1)
    # the program as it was written, with every operation's scope path
    options = xla_client._xla.HloPrintOptions()
    options.print_metadata = True
    options.print_large_constants = False
    text = lowered.compiler_ir(dialect="hlo").as_hlo_module().to_string(
        options)
    under = ops_under_scopes(text, ["moe_router"], None)
    ops = [op for op, _type, _path in under]
    assert ops.count("custom-call") == 1 and ops.count("dot") == 1, ops
    assert not {"sort", "topk", "gather", "scatter"} & set(ops), ops
    product, = [line for line in text.splitlines()
                if " dot(" in line and 'op_name="moe_router/' in line]
    assert "= f32[" in product and \
        "operand_precision={highest,highest}" in product, product


# -- the decode and verify steps of a Llama-family stack, by operand kind ---

def _stack_cfg(kv_quant="none", wide=False):
    # Llama stacks (RMSNorm, SwiGLU, rotary, heads of 128) as an engine
    # with quantised weights, an int8 pool, adapters or speculation runs
    # them.  wide: the 374M Llama geometry.  Else a quarter of its hidden
    # size at the SAME ffn, so w_down still has 11 int4 scale groups of
    # 256 rows
    hidden, heads = (1024, 8) if wide else (256, 2)
    return llama2_config(
        "7b", hidden_size=hidden, num_layers=2, num_attention_heads=heads,
        num_kv_heads=heads, ffn_hidden_size=2816, seq_length=1024,
        max_position_embeddings=1024, params_dtype="bfloat16",
        kv_cache_quant=kv_quant, attention_impl="flash", vocab_size=2048)


def _stack_params(cfg, policy):
    def build():
        p = model_lib.init_params(jax.random.key(0), cfg)
        return quant.quantize_params(p, policy) if policy else p
    return jax.eval_shape(build)


_PRECISIONS = {
    "bf16": (None, "none"), "int8": ("int8", "int8"),
    "int4": ("int4", "none"), "mixed": ("mixed", "none"),
    "lora": (None, "none"),
}
_LORA_SLOTS, _LORA_RANK = 16, 8


def _step_args(cfg, params, slots, t, bk, nb, one, lora=False):
    """Shape-only arguments of the engine's step programs on one chip,
    in their order up to the sampling knobs, and the adapters' keywords."""
    place = lambda tree: jax.tree.map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), tree)
    vec = lambda dtype, *s: place(_sds(s or (slots,), dtype))  # noqa: E731
    pools = jax.eval_shape(lambda: model_lib.init_kv_pool(cfg, nb, bk))
    knobs = (vec(jnp.uint32), vec(jnp.int32), vec(jnp.bool_),
             vec(jnp.float32), vec(jnp.int32), vec(jnp.float32))
    kw = {}
    if lora:
        arenas = jax.eval_shape(lambda: lora_ops.make_arenas(
            cfg, _LORA_SLOTS, _LORA_RANK, lora_ops.LORA_TARGETS))
        kw = dict(lora_arenas=place(arenas), lora_slots=vec(jnp.int32),
                  lora_rank=_LORA_RANK)
    return place(params), place(pools), vec, knobs, kw


@pytest.mark.parametrize("precision", list(_PRECISIONS))
@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_composed_decode_step(topo, monkeypatch, paged, precision):
    """The one decode step there is, compiled for the chip over each kind
    of operand a Llama-family engine hands it: bf16, int8 weights over an
    int8 cache, int4, mixed, and adapter arenas.  ``dense``:
    ``forward_cached`` over per-slot fills (a verify walk's step, the
    one-shot generator's).  ``paged``: the engine's decode executable,
    whose attention reads the pool through the tables inside the paged
    kernel — nothing gathers the pool or has its shape."""
    from megatron_llm_tpu.serving import engine as engine_lib

    monkeypatch.setattr(attn_ops, "_backend", lambda: "tpu")
    monkeypatch.setattr(kernels, "default_interpret", lambda: False)
    policy, kvq = _PRECISIONS[precision]
    cfg = _stack_cfg(kvq, wide=precision == "bf16")
    params = _stack_params(cfg, policy)
    one = SingleDeviceSharding(topo.devices[0])
    # (a pool too large for XLA to stage in fast memory, as a served one
    # is: else the module copies it there and back)
    b, max_len, bk, nb = 8, 1024, 128, 2049
    t = max_len // bk
    lora = precision == "lora"
    params, pools, vec, knobs, kw = _step_args(cfg, params, b, t, bk, nb,
                                               one, lora)
    if not paged:
        cache = jax.eval_shape(
            lambda: model_lib.init_kv_cache(cfg, b, max_len))

        def fn(params, tokens, k, v, fills, arenas, slots):
            return model_lib.forward_cached(
                cfg, params, tokens, k, v, fills, lora=(
                    engine_lib._lora_operand(arenas, slots, _LORA_RANK)
                    if lora else None))

        text = _compile(fn, (params, _sds((b, 1), jnp.int32), *cache,
                             _sds((b,), jnp.int32), kw.get("lora_arenas"),
                             kw.get("lora_slots")), one)
        assert "flash_decode" in text
        return
    assert model_lib.paged_decode_eligible(cfg, pools[0])
    text = engine_lib._decode_donated.lower(
        cfg, params, *pools, vec(jnp.int32, b, t), vec(jnp.int32),
        vec(jnp.int32), *knobs, **kw).compile().as_text()
    assert "tpu_custom_call" in text and "flash_decode" in text
    pool = "s8" if kvq == "int8" else "bf16"
    heads = cfg.kv_heads
    _no_copy_of(text, f"{pool}[{cfg.num_layers},{nb},{heads},{bk}")
    # (the gather route's dense view of every slot's table)
    _no_copy_of(text, f"{pool}[{cfg.num_layers},{b * t},{heads},{bk}")


@pytest.mark.parametrize("variant", ["linear", "linear_int8", "tree"])
def test_composed_verify(topo, monkeypatch, variant):
    """The engine's verify executables — a linear window over bf16 and
    over int8 weights and pool, and a candidate tree — compile for the
    chip: the window walked a token at a time over one gathered view,
    each step's attention the dense decode kernel."""
    from megatron_llm_tpu.serving import engine as engine_lib

    monkeypatch.setattr(attn_ops, "_backend", lambda: "tpu")
    monkeypatch.setattr(kernels, "default_interpret", lambda: False)
    kvq = "int8" if variant == "linear_int8" else "none"
    cfg = _stack_cfg(kvq)
    params = _stack_params(cfg, "int8" if kvq == "int8" else None)
    one = SingleDeviceSharding(topo.devices[0])
    s, w, max_len, bk, nb = 4, 4, 1024, 128, 33
    t = max_len // bk
    params, pools, vec, knobs, _ = _step_args(cfg, params, s, t, bk, nb,
                                              one)
    i32 = jnp.int32
    window, rows = vec(i32, s, w), vec(i32, s * w)
    if variant == "tree":
        lowered = engine_lib._verify_tree_donated.lower(
            cfg, params, *pools, vec(i32, s, t), window, vec(i32, s, w),
            vec(i32, s, w, w), vec(i32), rows, rows, *knobs)
    else:
        lowered = engine_lib._verify_donated.lower(
            cfg, params, *pools, vec(i32, s, t), window, vec(i32), rows,
            rows, *knobs)
    text = lowered.compile().as_text()
    assert text.count("flash_decode") >= w * cfg.num_layers


# -- kernels under a mesh: every pallas_call inside a fully manual shard_map

def _tp2_mesh(topo):
    return mesh_lib.build_mesh(ParallelConfig(tensor_parallel=2),
                               devices=topo.devices[:2])


@pytest.mark.parametrize("h,kv,d", [(32, 32, 128), (128, 8, 64)],
                         ids=["mha128", "falcon40b_gqa64"])
def test_sharded_paged_decode_attention(topo, monkeypatch, h, kv, d):
    mesh = _tp2_mesh(topo)
    monkeypatch.setattr(attn_ops, "_backend", lambda: "tpu")
    monkeypatch.setattr(kernels, "default_interpret", lambda: False)
    b, bk, nb, t = 8, 128, 64, 16
    assert attn_ops.paged_decode_route(1, h, kv, d, bk, mesh)
    # MQA's one KV head is nothing tp can split: the route declines
    assert not attn_ops.paged_decode_route(1, 71, 1, 64, bk, mesh)
    heads = NamedSharding(mesh, P(None, None, "tp", None))
    pool = NamedSharding(mesh, P(None, None, "tp", None, None))
    rows = NamedSharding(mesh, P(None, "tp", None, None))
    rep = NamedSharding(mesh, P())
    layers = 4

    def fn(q, k, v, tables, fills, k_new, v_new, layer):
        with mesh_lib.use_mesh(mesh):
            return attn_ops.paged_decode_attention(q, k, v, tables, fills,
                                                   k_new, v_new, layer)

    text = _compile(
        fn, (_sds((b, 1, h, d)), _sds((layers, nb, kv, bk, d)),
             _sds((layers, nb, kv, bk, d)), _sds((b, t), jnp.int32),
             _sds((b,), jnp.int32), _sds((b, kv, 1, d)),
             _sds((b, kv, 1, d)), _sds((), jnp.int32)),
        (heads, pool, pool, rep, rep, rows, rows, rep))
    _no_copy_of(text, f"bf16[{layers},{nb},{kv // 2},{bk},{d}]")


@pytest.mark.parametrize("size,tp", [("7b", 0), ("40b", 2)],
                         ids=["falcon7b_one_chip", "falcon40b_width_tp2"])
def test_composed_decode_step_touches_only_live_kv(topo, monkeypatch, size,
                                                   tp):
    """The engine's decode executable on the composed paged route, at 2
    layers of Falcon-7B width on one chip (MQA) and of Falcon-40B width
    under a tp=2 mesh (GQA 8 x 64, the pool split over its KV heads),
    over 16 slots × a 16-block table: the paged kernel is in it, and
    nothing but the pool arguments and the in-place row writes has the
    pool's shape or the shape of the gathered dense view
    ``[L, S·T, kv, bk, d]`` — the re-layouts a row scatter draws and the
    gather are gone — and on one chip no copy, transpose or stand-alone
    slice writes 8 MiB or more: every weight is read once, where it
    lies.  The sampler's ordering of the vocabulary stayed under a
    ``conditional`` (XLA turns small ones into selects that run both
    sides): a step whose every slot is greedy sorts nothing.  On one chip
    at the published 65 024 entries; under tp=2 at ``_SMALL_VOCAB``, so
    what that case holds is the pool's split and ``wq``: the head's
    matmul split over the vocabulary, the table's masked lookup and the
    gathered logits' sort are NOT read at their size under tp here."""
    from megatron_llm_tpu.config import falcon_config
    from megatron_llm_tpu.models import sharding as sharding_lib
    from megatron_llm_tpu.serving import engine as engine_lib

    monkeypatch.setattr(attn_ops, "_backend", lambda: "tpu")
    monkeypatch.setattr(kernels, "default_interpret", lambda: False)
    layers, slots, t, bk = 2, 16, 16, 128
    # a pool too large for XLA to stage in fast memory, as the 32-layer
    # pool is: else the module copies it there and back
    nb = 1 + 16 * (slots * t + 256)
    # (the published 65 024 entries on one chip; ``_SMALL_VOCAB`` under tp)
    cfg = falcon_config(size, num_layers=layers, attention_impl="flash",
                        **({"vocab_size": _SMALL_VOCAB} if tp else {}))
    params = jax.eval_shape(
        lambda k: model_lib.init_params(k, cfg), jax.random.key(0))
    pools = jax.eval_shape(lambda: model_lib.init_kv_pool(cfg, nb, bk))
    if tp:
        par = ParallelConfig(tensor_parallel=tp)
        mesh = mesh_lib.build_mesh(par, devices=topo.devices[:tp])
        at = lambda spec: NamedSharding(mesh, spec)  # noqa: E731
        param_at = jax.tree.map(
            at, sharding_lib.serving_param_specs(cfg, par))
        pool_at = tuple(map(at, sharding_lib.kv_pool_specs(cfg, mesh)))
        rest_at = at(P())
    else:
        mesh = None
        rest_at = SingleDeviceSharding(topo.devices[0])
        param_at = jax.tree.map(lambda _: rest_at, params)
        pool_at = (rest_at, rest_at)
    place = lambda tree, where: jax.tree.map(  # noqa: E731
        lambda a, w: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=w),
        tree, where)
    vec = lambda dtype, *s: place(  # noqa: E731
        _sds(s or (slots,), dtype), rest_at)
    i32, f32 = jnp.int32, jnp.float32
    assert model_lib.paged_decode_eligible(cfg, pools[0], mesh=mesh)
    with mesh_lib.use_mesh(mesh) if tp else contextlib.nullcontext():
        text = engine_lib._decode_donated.lower(
            cfg, place(params, param_at), *place(pools, pool_at),
            vec(i32, slots, t), vec(i32), vec(i32), vec(i32), vec(i32),
            vec(jnp.bool_), vec(f32), vec(i32), vec(f32)
            ).compile().as_text()
    assert "tpu_custom_call" in text
    kv = cfg.kv_heads // max(tp, 1)             # a device's share
    _no_copy_of(text, f"bf16[{layers},{nb},{kv},{bk},64]")
    _no_copy_of(text, f"bf16[{layers},{slots * t},{kv},{bk},64]")
    assert text.count("dynamic-update-slice(") >= 2 * slots
    # nor is a weight moved before it is read (obs/hlo_audit.py): the q
    # projection takes its layer's wq inside its fusion, the embedding
    # rows are read where the table lies.  Under tp the step keeps the
    # parent's rotary and lookup and still re-lays wq (ROADMAP S3)
    assert set(relayout_bytes(text)) <= ({"bf16[1,8192,4096]"} if tp
                                         else set())
    sorts, always = ops_by_conditional(text, "sort")
    assert 1 <= len(sorts) <= 2 and not always, (sorts, always)


def test_sharded_flash_attention_fwd_bwd(topo, monkeypatch):
    mesh = _tp2_mesh(topo)
    monkeypatch.setattr(kernels, "default_interpret", lambda: False)
    heads = NamedSharding(mesh, P(None, None, "tp", None))

    def loss(q, k, v):
        with mesh_lib.use_mesh(mesh):
            o = attn_ops.attention(q, k, v, impl="flash")
        return o.astype(jnp.float32).sum()

    qkv = _sds((2, 1024, 32, 128))
    _compile(jax.grad(loss, argnums=(0, 1, 2)), (qkv, qkv, qkv),
             (heads, heads, heads))
