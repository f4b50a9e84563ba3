"""Paged slot management: per-slot block tables over a shared block pool.

A *slot* is a row in the decode batch.  Unlike the original design —
where every slot owned a contiguous ``max_seq_len`` stripe of a batched
cache and admission spliced a batch-1 prefill cache over the whole row —
a slot now owns only an int32 *block table*: ``T`` entries mapping the
slot's logical block ``i`` (token positions ``[i*bk, (i+1)*bk)``) to a
physical block id in the :class:`~.block_pool.BlockPool`.  Unused
entries point at the pool's trash block (id 0), so gathers and scatters
always run at fixed arity ``T`` and every consumer compiles exactly
once: the pool shape is static and only the integer tables change.

Memory therefore scales with actual fill, not ``max_seq_len``: a
32-token request pins one block while a 4096-token neighbour pins 32,
and blocks shared with the prefix cache appear in many tables at once
under ref counting — retirement decrements refs instead of copying rows.

A hybrid stack (``cfg.layer_pattern``) keeps further kinds of state a
slot, ``SlotAllocator.rec``: fixed-size, slot-indexed, beside the pool
(see ``__init__``); ``insert`` installs it with the prefill's K/V.

``insert`` publishes an admission prefill's dense batch-1 cache into
freshly allocated pool blocks in ONE fixed-arity scatter; shared prefix
blocks are skipped (their scatter target is the trash block), so a
prefix hit never copies K/V.  Per-step row appends and the block-table
gather consumed by decode live in ``models/model.py``
(``cache_append_rows`` / ``cache_gather_blocks``).

Pipelined-scheduler ordering contract (engine.py fast path): the engine
may call ``insert`` while a decode step is still in flight.  That is
safe because the engine adopts the dispatched step's output pools
(``set_pools``) *before* inserting, so the scatter consumes the step's
result as a data dependency — XLA orders it after the step's speculative
row write, and the scatter overwrites the whole block.  A lazily
allocated append block is only ever unmasked after its new owner's own,
later-ordered write to it, so block recycling under the one-step lag is
race-free without host synchronization.
"""

from __future__ import annotations

import functools
from typing import List, Optional, Sequence

import jax
import numpy as np

from ..models import model as model_lib
from ..resilience.chaos import chaos
from .block_pool import BlockPool


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _insert_donated(k_pool, v_pool, k_small, v_small, scatter):
    return (model_lib.cache_scatter_blocks(k_pool, k_small, scatter),
            model_lib.cache_scatter_blocks(v_pool, v_small, scatter))


@jax.jit
def _insert_plain(k_pool, v_pool, k_small, v_small, scatter):
    return (model_lib.cache_scatter_blocks(k_pool, k_small, scatter),
            model_lib.cache_scatter_blocks(v_pool, v_small, scatter))


def _install_rec_impl(rec, one, slot):
    """Replace slot ``slot``'s recurrent state by ``one``'s (batch 1: what
    a prefill ended with) and add the experts that prefill counted."""
    out = model_lib.cache_slot_update(
        model_lib.rec_states(rec), model_lib.rec_states(one), slot)
    return {**out, "load": rec["load"] + one["load"],
            "rows": model_lib.add_rows(rec["rows"], one["rows"])}


_install_rec_donated = jax.jit(_install_rec_impl, donate_argnums=(0,))
_install_rec_plain = jax.jit(_install_rec_impl)


class SlotAllocator:
    """Tracks slot occupancy and per-slot block tables over a BlockPool.

    ``table_blocks`` (``T``) is the fixed table arity:
    ``ceil(max_seq_len / block_size)``.  The working sequence width seen
    by dense consumers is ``width = T * block_size >= max_seq_len``.

    Only the scheduler thread touches this object — no locking here.
    """

    def __init__(self, cfg, num_slots: int, max_seq_len: int,
                 pool: BlockPool):
        assert num_slots >= 1 and max_seq_len >= 2
        self.cfg = cfg
        self.num_slots = num_slots
        self.max_seq_len = max_seq_len
        self.pool = pool
        bk = pool.block_size
        self.table_blocks = -(-max_seq_len // bk)
        self.width = self.table_blocks * bk
        self.tables = np.zeros((num_slots, self.table_blocks),
                               dtype=np.int32)
        # this slot's share of the pool's outstanding reservation: blocks
        # the request may still allocate (lazy decode growth / the insert)
        self.reserved = np.zeros(num_slots, dtype=np.int64)
        self._free = list(range(num_slots - 1, -1, -1))  # pop() -> slot 0 first
        self._insert = (_insert_plain if jax.default_backend() == "cpu"
                        else _insert_donated)
        # A hybrid stack's other kinds of slot state (cfg.layer_pattern;
        # models/model.py:init_rec_state): for every recurrent mixer (a
        # Gated DeltaNet layer, a Mamba-2 layer: each kind under names of
        # its own) a fixed-size state and convolution tail a SLOT, indexed
        # by slot and not paged, allocated once beside the pool.  A slot's
        # row is replaced whole when a prefill is installed (``insert``),
        # advanced by every decode step the slot is live in, and dead
        # between a retirement and the next install: nothing reads it, so
        # release() moves no device memory.  The decode step consumes and
        # re-emits the tree like the pools (donated on a TPU).
        self.rec = (model_lib.init_rec_state(cfg, num_slots)
                    if cfg.layer_pattern else None)
        self._install_rec = (
            _install_rec_plain if jax.default_backend() == "cpu"
            else _install_rec_donated)

    # -- occupancy ------------------------------------------------------
    @property
    def free_slots(self) -> int:
        return len(self._free)

    @property
    def active_slots(self) -> int:
        return self.num_slots - len(self._free)

    def alloc(self) -> Optional[int]:
        """Claim a free slot index, or None when all slots are occupied."""
        return self._free.pop() if self._free else None

    def release(self, slot: int) -> None:
        """Return a slot: drop one ref on every table entry, hand back any
        unused reservation, reset the row."""
        assert 0 <= slot < self.num_slots and slot not in self._free
        leak = chaos().should_leak_kv_block("slots-release")
        for bid in self.tables[slot]:
            if leak and int(bid) != BlockPool.TRASH:
                leak = False  # chaos: drop exactly one ref on the floor
                continue
            self.pool.decref(int(bid))
        self.tables[slot] = BlockPool.TRASH
        if self.reserved[slot]:
            self.pool.unreserve(int(self.reserved[slot]))
            self.reserved[slot] = 0
        self._free.append(slot)

    def set_reservation(self, slot: int, n: int) -> None:
        """Record that ``n`` of the pool's reserved blocks belong to this
        slot (the engine already called ``pool.reserve(n)``)."""
        assert self.reserved[slot] == 0
        self.reserved[slot] = n

    def live_bids(self, slot: int) -> List[int]:
        """The slot's allocated block ids in table order.  Non-TRASH
        entries always form a prefix of the row (blocks are granted in
        fill order), which is what lets shipping and the tiered-KV
        demote path move ``live_bids`` as one dense fixed-arity slice."""
        bids: List[int] = []
        for b in self.tables[slot]:
            if int(b) == BlockPool.TRASH:
                break
            bids.append(int(b))
        return bids

    # -- cache views ----------------------------------------------------
    @property
    def k_pool(self):
        return self.pool.k_pool

    @property
    def v_pool(self):
        return self.pool.v_pool

    def set_pools(self, k_pool, v_pool, rec=None) -> None:
        """Adopt the pools (and a hybrid stack's ``rec``) returned by a
        decode step (the step consumes and re-emits them; on TPU they are
        donated through)."""
        self.pool.k_pool = k_pool
        self.pool.v_pool = v_pool
        if rec is not None:
            self.rec = rec

    # -- admission ------------------------------------------------------
    @property
    def rec_state_bytes(self) -> dict:
        """Bytes of per-slot recurrent state, by the block kind that
        keeps it (``model.REC_STATE_KINDS``); {} for a one-kind stack."""
        if self.rec is None:
            return {}
        return {kind: sum(int(self.rec[name].nbytes) for name in names)
                for kind, names in model_lib.REC_STATE_KINDS.items()
                if names[0] in self.rec}

    def insert(self, slot: int, k_small, v_small, n_tokens: int,
               shared_bids: Sequence[int] = (), rec_small=None) -> None:
        """Publish a dense batch-1 cache (leaves ``[L, 1, kv, width(,d)]``)
        into the slot's table.

        The first ``len(shared_bids)`` logical blocks come from the
        prefix cache by ref bump — ZERO copies; only the blocks the
        prefill actually computed (``covered - shared``) are scattered
        into freshly allocated pool blocks.  Allocation draws from the
        reservation the engine made at admission, so it cannot fail.
        """
        pool = self.pool
        bk = pool.block_size
        covered = -(-n_tokens // bk)
        assert covered <= self.table_blocks
        n_shared = len(shared_bids)
        assert n_shared <= covered
        table = np.full(self.table_blocks, BlockPool.TRASH, dtype=np.int32)
        # shared prefix blocks: ref bump only; their scatter target stays
        # the trash block so the fixed-arity scatter skips them
        scatter = np.full(self.table_blocks, BlockPool.TRASH, dtype=np.int32)
        for i, bid in enumerate(shared_bids):
            pool.incref(int(bid))
            table[i] = bid
        for i in range(n_shared, covered):
            bid = pool.alloc_reserved()
            self.reserved[slot] -= 1
            table[i] = bid
            scatter[i] = bid
        assert self.reserved[slot] >= 0
        self.tables[slot] = table
        pool.k_pool, pool.v_pool = self._insert(
            pool.k_pool, pool.v_pool, k_small, v_small,
            np.ascontiguousarray(scatter))
        if self.rec is not None:
            # the prefill's own end state (at the prompt's true last
            # position) replaces whatever the slot's last tenant left
            self.rec = self._install_rec(self.rec, rec_small,
                                         np.int32(slot))

    # -- decode-time lazy growth ---------------------------------------
    def append_block_id(self, slot: int, fill: int) -> int:
        """Return the block id that will receive the row written at
        position ``fill``, allocating lazily (from the slot's
        reservation) and applying copy-on-write if the boundary block is
        shared.  Called on the host before dispatching the decode step
        that writes position ``fill``."""
        pool = self.pool
        i = fill // pool.block_size
        bid = int(self.tables[slot][i])
        if bid == BlockPool.TRASH:
            bid = pool.alloc_reserved()
            self.reserved[slot] -= 1
            self.tables[slot][i] = bid
        else:
            new = pool.ensure_writable(bid)
            if new != bid:
                self.reserved[slot] -= 1
                self.tables[slot][i] = new
                bid = new
        assert self.reserved[slot] >= 0
        return bid

    # -- introspection --------------------------------------------------
    def snapshot(self, fills: Optional[dict] = None) -> dict:
        """Host-side debug view for the GET /kv endpoint."""
        pool = self.pool
        bk = pool.block_size
        slots = {}
        live_tokens = 0
        free = set(self._free)
        for s in range(self.num_slots):
            if s in free:
                continue
            row = [int(b) for b in self.tables[s]]
            fill = int(fills.get(s, 0)) if fills else 0
            live_tokens += fill
            slots[str(s)] = {
                "table": row,
                "fill": fill,
                "blocks": sum(1 for b in row if b != BlockPool.TRASH),
            }
        used_tokens = pool.used_blocks * bk
        frag = (1.0 - live_tokens / used_tokens) if used_tokens else 0.0
        return {
            "pool": pool.stats(),
            "ref_counts": {str(k): v for k, v in pool.ref_counts().items()},
            "slots": slots,
            "table_blocks": self.table_blocks,
            "block_size": bk,
            "fragmentation": frag,
        }
