"""Pallas decode-attention kernel vs the einsum reference (interpret mode)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_llm_tpu.kernels.flash_decode import flash_decode
from megatron_llm_tpu.ops.attention import decode_attention


@pytest.mark.parametrize("heads,kv_heads,cache_len", [
    (8, 8, 17), (8, 2, 100), (4, 1, 511), (8, 8, 0),
])
def test_matches_einsum_reference(heads, kv_heads, cache_len):
    b, max_len, d = 2, 512, 128
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(b, 1, heads, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, kv_heads, max_len, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, kv_heads, max_len, d)), jnp.float32)

    want = decode_attention(q, k, v, jnp.int32(cache_len))  # einsum path
    got = flash_decode(q[:, 0], k, v, jnp.int32(cache_len) + 1,
                       interpret=True)[:, None]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_bf16_matches_fp32_reference():
    b, heads, kv_heads, max_len, d = 1, 8, 4, 1024, 128
    rng = np.random.default_rng(1)
    q = rng.normal(size=(b, 1, heads, d)).astype(np.float32)
    k = rng.normal(size=(b, kv_heads, max_len, d)).astype(np.float32)
    v = rng.normal(size=(b, kv_heads, max_len, d)).astype(np.float32)
    want = decode_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                            jnp.int32(700))
    got = flash_decode(jnp.asarray(q[:, 0], jnp.bfloat16),
                       jnp.asarray(k, jnp.bfloat16),
                       jnp.asarray(v, jnp.bfloat16),
                       jnp.int32(701), interpret=True)[:, None]
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), rtol=0.05, atol=0.05)


def test_per_sample_fill_levels_match_einsum():
    """[b] per-sample cache fills (ragged speculative decoding): each
    sample masks at its own level, matching the einsum path's vector
    masking."""
    b, heads, kv_heads, max_len, d = 3, 4, 2, 512, 128
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.normal(size=(b, 1, heads, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, kv_heads, max_len, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, kv_heads, max_len, d)), jnp.float32)
    lens = jnp.asarray([17, 300, 511], jnp.int32)

    want = decode_attention(q, k, v, lens)  # einsum path, vector mask
    got = flash_decode(q[:, 0], k, v, lens + 1, interpret=True)[:, None]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# Paged gather mode (block-table pool): vs the dense kernel, and bitwise
# whatever the physical layout
# ---------------------------------------------------------------------------

from megatron_llm_tpu.kernels.flash_decode import (  # noqa: E402
    _walk_shape,
    flash_decode_int8,
    flash_decode_paged,
    flash_decode_paged_int8,
    pool_walk,
    walk_counts,
)


# Interpret mode traces and lowers a kernel in Python for ~5 s of a call
# before anything runs, and a bare call does it again every time: the
# paged walks below go through one jit a pool form, so the calls of one
# set of shapes (a geometry's fills, a slot's neighbourhoods, a pool's
# shuffles) are traced, lowered and compiled once a process.
_paged = jax.jit(functools.partial(flash_decode_paged, interpret=True))
_paged_int8 = jax.jit(functools.partial(flash_decode_paged_int8,
                                        interpret=True))


def _shuffled_tables(b, T, rng):
    """Per-row block tables with deliberately non-contiguous physical
    ids (1..b*T shuffled; id 0 is the trash block)."""
    return (rng.permutation(b * T) + 1).reshape(b, T).astype(np.int32)


def _paged_layout(dense_leaves, bk, tables, garbage):
    """Scatter dense [b, kv, max_len, *] leaves into pool blocks at the
    physical ids named by ``tables``, trash block 0 filled with large
    finite garbage — the invariant under test is that table indirection
    plus fill masking reproduces the dense cache's attention, bitwise the
    same no matter the physical layout."""
    b, kv = dense_leaves[0].shape[:2]
    T = tables.shape[1]
    pools = []
    for leaf in dense_leaves:
        pool = np.full((1 + b * T, kv, bk) + leaf.shape[3:], garbage,
                       leaf.dtype)
        for bi in range(b):
            for j in range(T):
                pool[tables[bi, j]] = leaf[bi, :, j * bk:(j + 1) * bk]
        pools.append(jnp.asarray(pool))
    return pools


def test_paged_equals_dense_fp32_bitwise_across_layouts():
    """flash_decode_paged over a shuffled pool == flash_decode over the
    dense cache to float32 rounding (the walk attends several pool blocks
    as one online-softmax term, the dense kernel one ``block_k`` a term,
    and online softmax is not partition-invariant), and BITWISE the same
    over another shuffle of the same logical rows."""
    b, heads, kv_heads, max_len, d, bk = 3, 8, 2, 512, 128, 128
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(b, heads, d)), jnp.float32)
    k = rng.normal(size=(b, kv_heads, max_len, d)).astype(np.float32)
    v = rng.normal(size=(b, kv_heads, max_len, d)).astype(np.float32)
    lens = jnp.asarray([1, 200, 512], jnp.int32)

    want = flash_decode(q, jnp.asarray(k), jnp.asarray(v), lens,
                        block_k=bk, interpret=True)
    got = []
    for _ in range(2):
        tables = _shuffled_tables(b, max_len // bk, rng)
        k_pool, v_pool = _paged_layout([k, v], bk, tables, 1e4)
        got.append(np.asarray(_paged(q, k_pool, v_pool, jnp.asarray(tables),
                                     lens)))
    np.testing.assert_allclose(got[0], np.asarray(want), rtol=1e-6,
                               atol=1e-6)
    np.testing.assert_array_equal(got[0], got[1])


def test_paged_equals_dense_int8_bitwise_across_layouts():
    """Same bar for the int8 {q, scale} pool form: quantized codes and
    per-row scales gathered through the table."""
    b, heads, kv_heads, max_len, d, bk = 3, 4, 2, 512, 128, 128
    rng = np.random.default_rng(4)
    q = jnp.asarray(rng.normal(size=(b, heads, d)), jnp.float32)
    k_q = rng.integers(-127, 128, (b, kv_heads, max_len, d)).astype(np.int8)
    v_q = rng.integers(-127, 128, (b, kv_heads, max_len, d)).astype(np.int8)
    k_s = rng.uniform(0.01, 0.1,
                      (b, kv_heads, max_len)).astype(np.float32)
    v_s = rng.uniform(0.01, 0.1,
                      (b, kv_heads, max_len)).astype(np.float32)
    lens = jnp.asarray([17, 384, 511], jnp.int32)

    want = flash_decode_int8(q, *(jnp.asarray(a) for a in
                                  (k_q, k_s, v_q, v_s)),
                             lens, block_k=bk, interpret=True)
    got = []
    for _ in range(2):
        tables = _shuffled_tables(b, max_len // bk, rng)
        kq_p, vq_p = _paged_layout([k_q, v_q], bk, tables, 127)
        ks_p, vs_p = _paged_layout([k_s, v_s], bk, tables, 1e4)
        got.append(np.asarray(_paged_int8(
            q, kq_p, ks_p, vq_p, vs_p, jnp.asarray(tables), lens)))
    np.testing.assert_allclose(got[0], np.asarray(want), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_array_equal(got[0], got[1])


# ---------------------------------------------------------------------------
# Paged walk with the new token's row folded in (the composed decode
# route's kernel): vs the gathered-einsum reference
# ---------------------------------------------------------------------------

from megatron_llm_tpu.ops.kv_quant import (  # noqa: E402
    cache_update,
    dequantize_cache,
    quantize_rows,
)

# heads, kv heads, head width, slots
_FALCON = (71, 1, 64, 16)   # MQA: one query group of 71 rows, g_pad 72
_GQA128 = (32, 8, 128, 9)   # the edge fills alone: interpret mode is slow
_GQA256 = (16, 2, 256, 9)   # the long-document cell's full-attention layer
# the long-generation cell's: 20 key heads of 64 on 10 value heads of 128,
# packed by pairs; a bfloat16 pool's ten packed heads are one copy, a
# float32 pool's five: TWO grid steps a slot
_PAIRS = (40, 20, 64, 9)


def _value_heads(geometry):
    """(value heads, their width): a pair of key heads shares one, twice
    as wide, where the geometry is the packed one."""
    _, kv, d, _ = geometry
    return (kv // 2, 2 * d) if geometry is _PAIRS else (kv, d)


def _walk_blocks(geometry, t, bk, dtype):
    """Pool blocks one iteration of the kernel's walk attends."""
    _, kv, d, _ = geometry
    vh, dv = _value_heads(geometry)
    return pool_walk(jax.ShapeDtypeStruct((1, kv, bk, d), dtype),
                     jax.ShapeDtypeStruct((1, vh, bk, dv), dtype), t)[2]


def _ragged_fills(b, t, bk, n, rng):
    """Every edge of the walk: an empty row, one row, exactly one
    iteration's ``n`` blocks, one row past them, every table column full
    (the new row lies behind the table), one short of a block, a block
    boundary, one past it, a table whose last row is the new one; random
    rest, so that one call holds rows of very different fills."""
    edge = [0, 1, n * bk, n * bk + 1, t * bk, bk - 1, bk, bk + 1, t * bk - 1]
    rest = rng.integers(0, t * bk, max(0, b - len(edge))).tolist()
    return np.asarray((edge + rest)[:b], np.int32)


def _fills(kind, b, t, bk, n, rng):
    """What the copy stream meets between grid steps.  ``edges``: above.
    ``empty_between``: empty slots, one and two in a row, between live
    ones.  ``ends_empty``: the call's first and last slots empty.
    ``all_empty``.  ``one_iteration``: runs of slots of one iteration
    each — a whole one, one block, one row — between longer ones, so a
    step's only iteration is also the one that starts the next step's."""
    if kind == "edges":
        return _ragged_fills(b, t, bk, n, rng)
    f = rng.integers(1, t * bk, b)
    if kind == "empty_between":
        f[[1, 3, 4, 6]] = 0
    elif kind == "ends_empty":
        f[[0, b - 1]] = 0
    elif kind == "all_empty":
        f[:] = 0
    elif kind == "one_iteration":
        f[:3] = [n * bk, 1, bk]
        f[4:7] = [n * bk - 1, 7, n * bk]
        f[b - 1] = 2
    return f.astype(np.int32)


def _plain_walk(q, k_dense, v_dense, k_new, v_new, fills, scale):
    """The plain composition over the dense view with the new row behind
    it, at any ratio of query, key and value heads."""
    b, heads, d = q.shape
    kv, vh = k_dense.shape[1], v_dense.shape[1]
    kd = jnp.concatenate([k_dense, k_new], axis=2).astype(jnp.float32)
    vd = jnp.concatenate([v_dense, v_new], axis=2).astype(jnp.float32)
    cols = jnp.arange(kd.shape[2])[None, :]
    keep = (cols < fills[:, None]) | (cols == kd.shape[2] - 1)
    s = jnp.einsum("bkgd,bktd->bkgt", q.astype(jnp.float32).reshape(
        b, kv, heads // kv, d), kd) * scale
    p = jax.nn.softmax(jnp.where(keep[:, None, None], s, -jnp.inf), axis=-1)
    out = jnp.einsum("bvgt,bvtw->bvgw", p.reshape(b, vh, heads // vh, -1), vd)
    return out.reshape(b, heads, -1)


_GEOMETRIES = {"falcon71x64mqa": _FALCON, "gqa32x128kv8": _GQA128,
               "gqa16x256kv2": _GQA256, "pairs40x64kv20v10": _PAIRS}
_BETWEEN = ("empty_between", "ends_empty", "all_empty", "one_iteration")
# (a geometry and pool's fills one after another: on one worker they run
# one executable)
_WALKS = [(g, p, f)
          for g, pools, kinds in (
              ("falcon71x64mqa", ("fp32", "bf16", "int8"), ()),
              ("gqa32x128kv8", ("fp32", "bf16", "int8"), _BETWEEN),
              ("gqa16x256kv2", ("fp32", "bf16", "int8"), ()),
              ("pairs40x64kv20v10", ("fp32",), _BETWEEN),
              ("pairs40x64kv20v10", ("bf16",), ()))
          for p in pools for f in ("edges",) + tuple(kinds)]


@pytest.mark.parametrize("geometry,pool,fill_kind", _WALKS,
                         ids=["-".join(w) for w in _WALKS])
def test_paged_new_row_matches_gathered_einsum(geometry, pool, fill_kind):
    """Slots × a 16-block table of 128-row blocks, shuffled physical
    ids, trash and unowned blocks holding large finite values: the paged
    kernel reading ``fills`` rows through the tables, the new token's row
    handed over beside the pool, equals the masked einsum over the
    gathered dense view into which that row was written first."""
    geometry = _GEOMETRIES[geometry]
    heads, kv, d, b = geometry
    vh, dv = _value_heads(geometry)
    t, bk = 16, 128
    rng = np.random.default_rng(heads)
    dt = jnp.float32 if pool == "fp32" else jnp.bfloat16
    tol = 2e-5 if pool == "fp32" else 0.03
    q = jnp.asarray(rng.normal(size=(b, heads, d)), dt)
    fills = _fills(
        fill_kind, b, t, bk,
        _walk_blocks(geometry, t, bk, jnp.int8 if pool == "int8" else dt),
        rng)
    # a table names only the blocks its slot has reached; the rest of
    # the row is the trash block, as the engine's allocator leaves it
    tables = _shuffled_tables(b, t, rng)
    owned = np.arange(t)[None, :] <= (fills[:, None] // bk)
    tables = np.where(owned, tables, 0).astype(np.int32)
    k_new = jnp.asarray(rng.normal(size=(b, kv, 1, d)), dt)
    v_new = jnp.asarray(rng.normal(size=(b, vh, 1, dv)), dt)
    dense_shape = (b, kv, t * bk, d)
    f = jnp.asarray(fills)

    if pool == "int8":
        kq = rng.integers(-127, 128, dense_shape).astype(np.int8)
        vq = rng.integers(-127, 128, dense_shape).astype(np.int8)
        ks = rng.uniform(0.01, 0.1, dense_shape[:3]).astype(np.float32)
        vs = rng.uniform(0.01, 0.1, dense_shape[:3]).astype(np.float32)
        kq_p, vq_p = _paged_layout([kq, vq], bk, tables, 127)
        ks_p, vs_p = _paged_layout([ks, vs], bk, tables, 1e4)
        kn, vn = quantize_rows(k_new), quantize_rows(v_new)
        got = _paged_int8(
            q, kq_p, ks_p, vq_p, vs_p, jnp.asarray(tables),
            jnp.asarray(fills),
            new_rows=(dequantize_cache(kn), dequantize_cache(vn)))
        k_dense = {"q": jnp.asarray(kq), "scale": jnp.asarray(ks)}
        v_dense = {"q": jnp.asarray(vq), "scale": jnp.asarray(vs)}
    else:
        k = rng.normal(size=dense_shape).astype(np.float32)
        v = rng.normal(size=(b, vh, t * bk, dv)).astype(np.float32)
        k_dense, v_dense = jnp.asarray(k, dt), jnp.asarray(v, dt)
        k_p, = _paged_layout([np.asarray(k_dense)], bk, tables, 1e4)
        v_p, = _paged_layout([np.asarray(v_dense)], bk, tables, 1e4)
        got = _paged(q, k_p, v_p, jnp.asarray(tables), jnp.asarray(fills),
                     new_rows=(k_new, v_new))
    assert np.isfinite(np.asarray(got, np.float32)).all()

    if geometry is _PAIRS:
        want = _plain_walk(q, k_dense, v_dense, k_new, v_new, f,
                           1.0 / np.sqrt(d))
    else:
        # one spare block behind the table so a full table's new row has
        # a place in the dense view; the einsum masks everything else
        pad = lambda a: jnp.pad(  # noqa: E731
            a, ((0, 0), (0, 0), (0, bk)) + ((0, 0),) * (a.ndim - 3))
        k_ref = cache_update(jax.tree.map(pad, k_dense), k_new, f)
        v_ref = cache_update(jax.tree.map(pad, v_dense), v_new, f)
        want = decode_attention(q[:, None], k_ref, v_ref, f)[:, 0]
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)
    # an empty slot attends its own row alone: the output is that V row
    vn_seen = dequantize_cache(vn) if pool == "int8" else v_new
    for i in np.flatnonzero(fills == 0):
        np.testing.assert_allclose(
            np.asarray(got[i], np.float32).reshape(vh, heads // vh, dv),
            np.broadcast_to(np.asarray(vn_seen[i], np.float32),
                            (vh, heads // vh, dv)), rtol=tol, atol=tol)


@pytest.mark.parametrize("pool", ["bf16", "int8"])
def test_paged_slot_never_sees_what_an_earlier_slot_copied(pool):
    """The walk's VMEM buffers outlive a grid step: a slot whose blocks
    hold NaN (K, V and the int8 pool's scales) fills them, and the next
    slot's one live block leaves the rest of its iteration dead, masked
    — and untouched by what lies there: its output is bitwise what it
    is when the first slot's blocks are clean."""
    heads, kv, d, _ = _GQA128
    t, bk = 4, 128
    assert _walk_blocks(_GQA128, t, bk,
                        jnp.int8 if pool == "int8" else jnp.bfloat16) > 1
    rng = np.random.default_rng(11)
    q = jnp.asarray(rng.normal(size=(2, heads, d)), jnp.bfloat16)
    fills = jnp.asarray([t * bk, 1], jnp.int32)
    tables = jnp.asarray(1 + np.arange(2 * t).reshape(2, t), jnp.int32)
    shape = (1 + 2 * t, kv, bk, d)
    if pool == "int8":
        clean = [jnp.asarray(rng.integers(-127, 128, shape), jnp.int8),
                 jnp.asarray(rng.uniform(0.01, 0.1, shape[:3]), jnp.float32)]
        clean = clean + [clean[0][::-1], clean[1][::-1]]
        call = _paged_int8
    else:
        clean = [jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
                 for _ in range(2)]
        call = _paged
    dirty = [a if a.dtype == jnp.int8 else a.at[1:1 + t].set(jnp.nan)
             for a in clean]
    want = call(q, *clean, tables, fills)
    got = call(q, *dirty, tables, fills)
    assert np.isfinite(np.asarray(got[1], np.float32)).all()
    np.testing.assert_array_equal(np.asarray(got[1], np.float32),
                                  np.asarray(want[1], np.float32))


@pytest.mark.parametrize("geometry", [_FALCON, _GQA256],
                         ids=["falcon71x64mqa", "gqa16x256kv2"])
def test_paged_whole_pool_layer_index_equals_layer_view(geometry):
    """``layer=`` addresses one layer of the whole [L, ...] pool inside
    the kernel's copies: bitwise what the kernel returns for that layer's
    view handed over alone."""
    heads, kv, d, _ = geometry
    t, bk, layers = 6, 128, 3
    n = _walk_blocks(geometry, t, bk, jnp.float32)
    fills = jnp.asarray([0, 1, 300, n * bk, n * bk + 1, t * bk], jnp.int32)
    b = len(fills)
    rng = np.random.default_rng(9)
    q = jnp.asarray(rng.normal(size=(b, heads, d)), jnp.float32)
    pools = [jnp.asarray(rng.normal(size=(layers, 1 + b * t, kv, bk, d)),
                         jnp.float32) for _ in range(2)]
    rows = [jnp.asarray(rng.normal(size=(b, kv, 1, d)), jnp.float32)
            for _ in range(2)]
    tables = jnp.asarray(_shuffled_tables(b, t, rng))
    for layer in (0, 2):
        want = _paged(q, pools[0][layer], pools[1][layer], tables, fills,
                      new_rows=rows)
        got = _paged(q, *pools, tables, fills, new_rows=rows,
                     layer=jnp.int32(layer))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _neighbourhood(geometry, rng, mine, fills, nan_blocks):
    """Five slots of which slot 2 is ``mine`` (its query, its rows, its
    table, its fill, its new row; an int8 pool where its leaves are
    four), the others drawn from ``rng`` with ``fills``; ``nan_blocks``:
    the neighbours' blocks hold NaN."""
    heads, kv, d, _ = geometry
    vh, dv = _value_heads(geometry)
    b, t, bk = 5, 6, 128
    dt = mine["q"].dtype
    tables = 1 + np.arange(b * t).reshape(b, t).astype(np.int32)
    q = jnp.asarray(rng.normal(size=(b, heads, d)), dt).at[2].set(mine["q"])
    rows = [jnp.asarray(rng.normal(size=(b, h, 1, w)), dt
                        ).at[2].set(mine[name])
            for name, h, w in (("k_new", kv, d), ("v_new", vh, dv))]
    own = tables[2]
    if len(mine["leaves"]) == 4:
        leaves = [jnp.asarray(rng.integers(-127, 128, (1 + b * t, kv, bk, d)),
                              jnp.int8),
                  jnp.asarray(rng.uniform(0.01, 0.1, (1 + b * t, kv, bk)),
                              jnp.float32)] * 2
        call = _paged_int8
    else:
        leaves = [jnp.asarray(rng.normal(size=(1 + b * t, h, bk, w)), dt)
                  for h, w in ((kv, d), (vh, dv))]
        call = _paged
    if nan_blocks:
        leaves = [a if a.dtype == jnp.int8 else a.at[1:].set(jnp.nan)
                  for a in leaves]
    leaves = [a.at[own].set(m) for a, m in zip(leaves, mine["leaves"])]
    fills = np.asarray(fills, np.int32)
    fills[2] = mine["fill"]
    return call(q, *leaves, jnp.asarray(tables), jnp.asarray(fills),
                new_rows=rows)[2]


@pytest.mark.parametrize("pool", ["bf16", "int8", "pairs"])
def test_paged_slot_output_does_not_depend_on_its_neighbours(pool):
    """The copy stream crosses grid steps — a slot's first blocks are
    started by the step before it, into whichever buffer half that step
    leaves free — and a slot's output is bitwise the same whatever its
    neighbours hold: empty ones (the slot primes itself, or is looked
    ahead to past them), long ones whose blocks are NaN (the halves it
    reads were theirs a moment before), one-iteration ones of odd and
    even counts (it starts in either half)."""
    geometry = _PAIRS if pool == "pairs" else _GQA128
    heads, kv, d, _ = geometry
    vh, dv = _value_heads(geometry)
    t, bk = 6, 128
    # the pairs from a float32 pool: five of ten packed heads a copy, so
    # the slot is two grid steps and its second is started by its first
    dt = jnp.float32 if pool == "pairs" else jnp.bfloat16
    n = _walk_blocks(geometry, t, bk, jnp.int8 if pool == "int8" else dt)
    if pool == "pairs":
        assert pool_walk(jax.ShapeDtypeStruct((1, kv, bk, d), dt),
                         jax.ShapeDtypeStruct((1, vh, bk, dv), dt),
                         t)[:2] == (10, 5)
    rng = np.random.default_rng(21)
    if pool == "int8":
        own = [jnp.asarray(rng.integers(-127, 128, (t, kv, bk, d)), jnp.int8),
               jnp.asarray(rng.uniform(0.01, 0.1, (t, kv, bk)), jnp.float32)]
        own = own + [own[0][::-1], own[1][::-1]]
    else:
        own = [jnp.asarray(rng.normal(size=(t, h, bk, w)), dt)
               for h, w in ((kv, d), (vh, dv))]
    mine = {"q": jnp.asarray(rng.normal(size=(heads, d)), dt),
            "k_new": jnp.asarray(rng.normal(size=(kv, 1, d)), dt),
            "v_new": jnp.asarray(rng.normal(size=(vh, 1, dv)), dt),
            "leaves": own, "fill": n * bk + 1}
    full = t * bk
    got = [np.asarray(_neighbourhood(geometry, np.random.default_rng(seed),
                                     mine, fills, nan), np.float32)
           for seed, fills, nan in (
               (1, [0, 0, 0, 0, 0], False),
               (2, [full, full - 3, 0, full, 1], True),
               (3, [0, n * bk, 0, 7, n * bk + 1], True))]
    assert np.isfinite(got[0]).all()
    for other in got[1:]:
        np.testing.assert_array_equal(other, got[0])


def _walked(fills, kv_heads, kvg, n, block_k):
    """The grid walked step by step as the kernel walks it: which steps
    have a live row, and which of them found their first iteration's
    copies started by the live step before."""
    steps = prefetched = 0
    started = False
    order = [(s, g) for s in range(len(fills))
             for g in range(kv_heads // kvg)]
    for i, (s, _) in enumerate(order):
        trips = -(-(-(-int(fills[s]) // block_k)) // n)
        if trips == 0:
            continue          # the phase passes through untouched
        steps += 1
        prefetched += started
        # its last iteration starts the next live step's first
        started = any(fills[s2] > 0 for s2, _ in order[i + 1:])
    return steps, prefetched


@pytest.mark.parametrize("kv_heads,kvg,n,block_k", [
    (10, 5, 2, 128), (10, 10, 2, 128), (8, 8, 4, 128), (1, 1, 4, 128),
    (2, 1, 3, 16)])
def test_walk_counts_are_the_grid_walked_step_by_step(kv_heads, kvg, n,
                                                      block_k):
    rng = np.random.default_rng(kv_heads * 100 + kvg)
    cases = [np.zeros(6, np.int32), np.full(64, 3000, np.int32),
             np.asarray([0, 0, 5, 0, 0]), np.asarray([7]), np.asarray([0])]
    cases += [np.where(rng.random(12) < 0.4, 0,
                       rng.integers(1, 40 * block_k, 12)) for _ in range(6)]
    for fills in cases:
        assert walk_counts(fills, kv_heads, kvg) == _walked(
            fills, kv_heads, kvg, n, block_k), fills
    # the long-generation cell's call with every slot live
    if (kv_heads, kvg) == (10, 5):
        assert walk_counts(np.full(64, 3000), kv_heads, kvg) == (128, 127)
