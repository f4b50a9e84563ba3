"""Device-resident paged KV block pool with host-side bookkeeping.

The pool owns TWO pytrees (K and V) of shape ``[L, n_blocks, kv_heads,
block_size, head_dim]`` — the ``init_kv_cache`` layout family with the
batch axis reinterpreted as a block axis, so the int8 ``{"q", "scale"}``
quantized-cache form works verbatim.  All allocation state (free list,
ref counts, reservations) lives on the host as plain numpy; the device
arrays never change *shape*, so every consumer compiles exactly once and
only the integer block tables vary between steps.  Block *contents* can
leave the pool: ``export_blocks`` / ``import_blocks`` move a block-table-
ordered slice between pools (possibly on different submeshes) for
disaggregated prefill/decode and live migration (docs/serving.md,
"Disaggregated prefill/decode") — the fixed arity keeps both sides on
one compiled executable each.

Conventions:

* Block id 0 is the **trash block**.  It is permanently allocated and
  every unused table entry points at it, which lets gathers and scatters
  run at a fixed arity (pad entries read/write trash) without masking.
  Trash contents are finite garbage; the decode attention masks by
  REPLACING scores beyond a row's fill with -1e30, so trash rows can
  never perturb outputs (exp underflows to exactly 0.0 in fp32 and
  0.0 x finite = 0.0 bitwise).
* Blocks are ref-counted.  The prefix cache pins shared prefix blocks by
  holding a ref; a slot's table holds one ref per entry.  ``decref``
  returns a block to the free list when the count hits zero.
* ``ensure_writable`` implements copy-on-write at a slot's boundary
  block: if the block about to receive appended rows is shared
  (ref > 1), its contents are copied into a fresh block on device and
  the table retargets — counted in the ``cow_copies_total`` metric.
* Reservations make admission sound: the engine reserves the worst-case
  block count for a request up front (``reserve``) and lazy per-step
  allocation draws from that reservation (``alloc_reserved``), so a
  decode step can never fail to find a block mid-flight.
"""

from __future__ import annotations

import functools
from typing import Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..models import model as model_lib
from ..resilience.chaos import chaos


@functools.partial(jax.jit, donate_argnums=(0,), static_argnums=())
def _copy_block_donated(pool, src, dst):
    def cp(a):
        blk = jax.lax.dynamic_index_in_dim(a, src, axis=1, keepdims=True)
        return jax.lax.dynamic_update_slice_in_dim(a, blk, dst, axis=1)

    return jax.tree.map(cp, pool)


@jax.jit
def _copy_block_plain(pool, src, dst):
    def cp(a):
        blk = jax.lax.dynamic_index_in_dim(a, src, axis=1, keepdims=True)
        return jax.lax.dynamic_update_slice_in_dim(a, blk, dst, axis=1)

    return jax.tree.map(cp, pool)


@jax.jit
def _export_gather(k_pool, v_pool, table):
    # table [1, T]: a one-row block table — the dense leaves come back in
    # *table order* ([L, 1, kv, T*bk(, d)]), pad entries reading trash.
    return (model_lib.cache_gather_blocks(k_pool, table),
            model_lib.cache_gather_blocks(v_pool, table))


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _import_scatter_donated(k_pool, v_pool, k_dense, v_dense, scatter):
    return (model_lib.cache_scatter_blocks(k_pool, k_dense, scatter),
            model_lib.cache_scatter_blocks(v_pool, v_dense, scatter))


@jax.jit
def _import_scatter_plain(k_pool, v_pool, k_dense, v_dense, scatter):
    return (model_lib.cache_scatter_blocks(k_pool, k_dense, scatter),
            model_lib.cache_scatter_blocks(v_pool, v_dense, scatter))


class BlockPool:
    """Fixed pool of KV blocks + free-list / ref-count / reservation state.

    ``n_blocks`` includes the reserved trash block 0, so ``n_blocks - 1``
    blocks are actually allocatable.
    """

    TRASH = 0

    def __init__(self, cfg, n_blocks: int, block_size: int,
                 on_cow: Optional[Callable[[], None]] = None, mesh=None):
        if n_blocks < 2:
            raise ValueError("BlockPool needs at least 2 blocks "
                             "(one is the reserved trash block)")
        self.cfg = cfg
        self.n_blocks = int(n_blocks)
        self.block_size = int(block_size)
        # With a serving submesh the pool is born on it: kv heads sharded
        # over tp and the stacked layer axis over pp, so each pipeline
        # stage holds only its own layer slice of every block
        # (models/sharding.py:kv_pool_specs).  The host-side ledger
        # (block ids, free list, refs) is sharding-agnostic — block ids
        # stay global integers on every shard and on every stage, which
        # is what keeps the allocator, prefix cache, COW, and the host
        # tier topology-blind.
        self.mesh = mesh
        if mesh is None:
            self.k_pool, self.v_pool = model_lib.init_kv_pool(
                cfg, n_blocks, block_size)
        else:
            from ..models import sharding as shard_lib

            self.k_pool, self.v_pool = shard_lib.init_sharded_kv_pool(
                cfg, n_blocks, block_size, mesh)
        self._ref = np.zeros(n_blocks, dtype=np.int32)
        self._ref[self.TRASH] = 1  # permanently pinned
        self._free: List[int] = list(range(n_blocks - 1, 0, -1))
        self._reserved = 0
        self._on_cow = on_cow
        # Off the CPU the pool is donated through the COW copy and the
        # shipment scatter, so neither holds two pools at once; XLA:CPU
        # does not donate (it would warn on every call), so it takes the
        # plain twins.  Only a chip run executes the donated ones:
        # chip_smoke.py drives one COW copy; the shipment scatter has
        # not run on a chip.
        self._copy = (_copy_block_plain
                      if jax.default_backend() == "cpu"
                      else _copy_block_donated)
        self._import = (_import_scatter_plain
                        if jax.default_backend() == "cpu"
                        else _import_scatter_donated)
        self.cow_copies = 0
        # in-flight shipments: ship_id -> {"request_id", "bids", "nbytes"}.
        # Each recorded block holds one ref on behalf of the shipment so
        # the blocks cannot be recycled (and the LedgerSanitizer can
        # attribute them) while the transfer is in flight.
        self.shipments: dict = {}

    # ------------------------------------------------------------------
    # capacity / reservations
    # ------------------------------------------------------------------
    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def used_blocks(self) -> int:
        return self.n_blocks - 1 - len(self._free)

    @property
    def usable_blocks(self) -> int:
        return self.n_blocks - 1

    @property
    def reserved_blocks(self) -> int:
        return self._reserved

    def can_reserve(self, n: int) -> bool:
        return len(self._free) - self._reserved >= n

    def reserve(self, n: int) -> bool:
        """Set aside ``n`` blocks for future allocation; False if the pool
        cannot guarantee them right now."""
        if not self.can_reserve(n):
            return False
        self._reserved += n
        return True

    def unreserve(self, n: int) -> None:
        assert self._reserved >= n, "unreserve() exceeds reservation"
        self._reserved -= n

    # ------------------------------------------------------------------
    # alloc / ref counting
    # ------------------------------------------------------------------
    def alloc_reserved(self) -> int:
        """Allocate one block against an existing reservation."""
        assert self._reserved > 0, "alloc_reserved() without reservation"
        self._reserved -= 1
        return self._pop_free()

    def _pop_free(self) -> int:
        assert self._free, "BlockPool exhausted despite reservation"
        bid = self._free.pop()
        assert self._ref[bid] == 0
        self._ref[bid] = 1
        return bid

    def incref(self, bid: int) -> None:
        assert bid != self.TRASH and self._ref[bid] > 0, \
            f"incref on unallocated block {bid}"
        self._ref[bid] += 1

    def decref(self, bid: int) -> None:
        if bid == self.TRASH:
            return
        assert self._ref[bid] > 0, f"double free of block {bid}"
        self._ref[bid] -= 1
        if self._ref[bid] == 0:
            self._free.append(bid)

    def ref(self, bid: int) -> int:
        return int(self._ref[bid])

    # ------------------------------------------------------------------
    # copy-on-write
    # ------------------------------------------------------------------
    def ensure_writable(self, bid: int) -> int:
        """Return a block id safe to append rows into.

        If ``bid`` is exclusively owned it is returned as-is.  If it is
        shared (ref > 1) — or is the trash block — a fresh block is
        allocated against the caller's reservation, the shared contents
        are copied on device, the caller's ref on ``bid`` is dropped, and
        the new id is returned.
        """
        if bid != self.TRASH and self._ref[bid] == 1:
            return bid
        new = self.alloc_reserved()
        if bid != self.TRASH:
            self.k_pool = self._copy(self.k_pool, bid, new)
            self.v_pool = self._copy(self.v_pool, bid, new)
            self.decref(bid)
            self.cow_copies += 1
            if self._on_cow is not None:
                self._on_cow()
        return new

    # ------------------------------------------------------------------
    # cross-pool shipping (disaggregated prefill/decode, live migration)
    # ------------------------------------------------------------------
    def export_blocks(self, bids: Sequence[int], arity: int):
        """Gather ``bids`` into dense table-ordered leaves for shipping.

        ``arity`` is the fixed table width (the engine's
        ``slots.table_blocks``) so every export compiles exactly once per
        pool shape; positions beyond ``len(bids)`` read the trash block.
        Leaves come back verbatim in the pool's own dtypes — int8
        ``{"q", "scale"}`` ships quantized, never dequantized.  Returns
        ``(k_dense, v_dense)`` with leaves ``[L, 1, kv, arity*bk(, d)]``.
        """
        assert len(bids) <= arity
        chaos().io_attempt("ship-export")
        table = np.full((1, arity), self.TRASH, dtype=np.int32)
        table[0, :len(bids)] = np.asarray(bids, dtype=np.int32)
        return _export_gather(self.k_pool, self.v_pool, table)

    def import_blocks(self, k_dense, v_dense, scatter) -> None:
        """Scatter shipped dense leaves into this pool's blocks.

        ``scatter`` is a full-arity int32 vector mapping each dense
        column group to a destination block id (trash for pad columns —
        those columns carry the source pool's trash garbage and land
        harmlessly in this pool's trash block).  The dense leaves may
        live on a *different* submesh: each leaf is first re-placed onto
        the matching pool leaf's sharding via ``jax.device_put`` (a
        resharding copy), then written by the same fixed-arity scatter
        admission uses.  Block contents transfer bitwise — no dequantize
        round trip for int8 ``{"q", "scale"}`` leaves.
        """
        chaos().io_attempt("ship-import")
        k_dense = jax.tree.map(
            lambda d, p: jax.device_put(d, p.sharding), k_dense, self.k_pool)
        v_dense = jax.tree.map(
            lambda d, p: jax.device_put(d, p.sharding), v_dense, self.v_pool)
        self.k_pool, self.v_pool = self._import(
            self.k_pool, self.v_pool, k_dense, v_dense,
            np.ascontiguousarray(np.asarray(scatter, dtype=np.int32)))

    def begin_ship(self, ship_id: str, request_id: str,
                   bids: Sequence[int], nbytes: int) -> None:
        """Open a shipment: take one ref per block on the shipment's
        behalf and record it in the in-flight ledger.

        Called *before* the source slot releases its table refs, so the
        blocks' counts never touch zero mid-transfer — the handoff is
        atomic from the ledger's point of view and the LedgerSanitizer
        attributes the refs to ``shipment:<request_id>`` until
        ``end_ship`` reconciles them."""
        assert ship_id not in self.shipments
        for bid in bids:
            self.incref(int(bid))
        self.shipments[ship_id] = {
            "request_id": request_id,
            "bids": [int(b) for b in bids],
            "nbytes": int(nbytes),
        }

    def end_ship(self, ship_id: str) -> None:
        """Close a shipment: drop the shipment's refs (freeing blocks no
        table still points at) and reconcile the in-flight ledger."""
        ship = self.shipments.pop(ship_id)
        for bid in ship["bids"]:
            self.decref(bid)

    # ------------------------------------------------------------------
    # introspection
    # ------------------------------------------------------------------
    def stats(self) -> dict:
        used = self.used_blocks
        usable = self.usable_blocks
        return {
            "n_blocks": self.n_blocks,
            "block_size": self.block_size,
            "blocks_free": self.free_blocks,
            "blocks_used": used,
            "blocks_reserved": self._reserved,
            "kv_cache_util": (used / usable) if usable else 0.0,
            "cow_copies": self.cow_copies,
            "shipments_in_flight": len(self.shipments),
        }

    def ref_counts(self) -> dict:
        """Non-zero ref counts by block id (trash excluded)."""
        return {int(b): int(self._ref[b])
                for b in np.nonzero(self._ref)[0] if b != self.TRASH}


class _PendingSwap:
    """One in-flight demote: dense device staging leaves draining to the
    host arena.  The *source pool blocks* are already free — the staged
    gather output owns the bytes — so the device side never waits on the
    host copy."""

    __slots__ = ("hids", "k_dense", "v_dense", "nbytes", "owner")

    def __init__(self, hids, k_dense, v_dense, nbytes, owner):
        self.hids = hids
        self.k_dense = k_dense
        self.v_dense = v_dense
        self.nbytes = nbytes
        self.owner = owner


class HostKVTier:
    """Host-RAM tier of KV blocks behind a device ``BlockPool``.

    Pinned host numpy arenas mirror the pool's leaf pytree with the block
    axis resized to ``n_host_blocks``; block *contents* move through the
    same fixed-arity ``export_blocks`` / ``import_blocks`` primitives
    disaggregated shipping uses (block-table-ordered dense slices, int8
    ``{q, scale}`` leaves verbatim), so the tier adds ZERO new compiled
    executables and transfers are bitwise both ways.

    Demotes are asynchronous and double-buffered: ``begin_demote`` issues
    the device gather and an async host copy, returning immediately with
    the staged dense leaves owning the bytes — the caller may free the
    source pool blocks at once, and ``pump`` (called from the scheduler's
    host phase) drains completed copies into the arena without stalling
    decode.  Promotes (``promote``) are synchronous: a hit needs the rows
    now, and the import scatter is one device dispatch.

    Chaos sites: ``host-swap-out`` fires *before* any state mutates, so a
    fault mid-demote leaves the device copy untouched; ``host-swap-in``
    fires before the import, so a fault mid-promote leaves the host copy
    resident for a later re-fetch.

    The tier keeps its own conservation ledger (free list + owner map,
    audited by the ``LedgerSanitizer``) and measures sustained swap
    bandwidth (EWMA over completed host copies) so oversubscribed
    admission can bound itself by what the swap path actually delivers.
    """

    def __init__(self, pool: BlockPool, n_host_blocks: int, arity: int,
                 metrics=None, max_backlog_s: float = 0.25):
        assert n_host_blocks >= 1
        self.pool = pool
        self.n_host_blocks = int(n_host_blocks)
        self.arity = int(arity)
        self._metrics = metrics  # the engine's ServingMetrics, or None
        self.max_backlog_s = float(max_backlog_s)
        bk = pool.block_size

        def arena(leaf):
            shp = (leaf.shape[0], self.n_host_blocks) + tuple(leaf.shape[2:])
            return np.zeros(shp, dtype=leaf.dtype)

        self.k_arena = jax.tree.map(arena, pool.k_pool)
        self.v_arena = jax.tree.map(arena, pool.v_pool)
        self.block_nbytes = sum(
            leaf[:, :1].nbytes
            for leaf in (jax.tree.leaves(self.k_arena)
                         + jax.tree.leaves(self.v_arena)))
        self._free: List[int] = list(range(self.n_host_blocks - 1, -1, -1))
        self._owner: dict = {}          # hid -> owner label
        self._pending: List[_PendingSwap] = []
        self._inflight_hids: set = set()
        # EWMA of measured host-copy bandwidth; optimistic seed so the
        # first oversubscribed admission is not starved before any
        # measurement exists.
        self.bw_bytes_per_s = float("inf")
        self.swaps_out = 0
        self.swaps_in = 0

    # -- bookkeeping -------------------------------------------------------
    @property
    def host_free(self) -> int:
        return len(self._free)

    @property
    def host_used(self) -> int:
        return self.n_host_blocks - len(self._free)

    @property
    def in_flight(self) -> int:
        return len(self._pending)

    def can_store(self, n: int) -> bool:
        return len(self._free) >= n

    def owners(self) -> dict:
        """owner label -> host block count (snapshot / sanitizer)."""
        out: dict = {}
        for owner in self._owner.values():
            out[owner] = out.get(owner, 0) + 1
        return out

    def free(self, hids: Sequence[int]) -> None:
        for hid in hids:
            hid = int(hid)
            assert hid in self._owner, f"double free of host block {hid}"
            assert hid not in self._inflight_hids, \
                f"freeing host block {hid} mid-swap"
            del self._owner[hid]
            self._free.append(hid)

    def swap_ok(self) -> bool:
        """True while the demote backlog is within ``max_backlog_s`` of
        measured bandwidth — the admission bound for oversubscription."""
        backlog = sum(p.nbytes for p in self._pending)
        if backlog == 0:
            return True
        if self.bw_bytes_per_s == float("inf"):
            return len(self._pending) <= 2
        return backlog / self.bw_bytes_per_s <= self.max_backlog_s

    # -- demote (device -> host), async double-buffered --------------------
    def begin_demote(self, bids: Sequence[int], owner: str) -> List[int]:
        """Start swapping ``bids`` out.  Issues the fixed-arity export
        gather plus an async host copy and returns the host block ids at
        once; the staged dense leaves own the bytes, so the caller frees
        the source pool blocks immediately.  Raises ``OSError`` if the
        ``host-swap-out`` chaos site is armed — *before* any state
        mutates, so the device copy is never lost."""
        assert len(bids) >= 1 and len(bids) <= self.arity
        assert self.can_store(len(bids)), "host tier exhausted"
        chaos().io_attempt("host-swap-out")
        k_dense, v_dense = self.pool.export_blocks(bids, self.arity)
        for leaf in jax.tree.leaves(k_dense) + jax.tree.leaves(v_dense):
            if hasattr(leaf, "copy_to_host_async"):
                leaf.copy_to_host_async()
        hids = []
        for _ in bids:
            hid = self._free.pop()
            self._owner[hid] = owner
            self._inflight_hids.add(hid)
            hids.append(hid)
        nbytes = self.block_nbytes * len(bids)
        self._pending.append(_PendingSwap(hids, k_dense, v_dense,
                                          nbytes, owner))
        m = self._metrics
        if m is not None:
            m.inc("swap_out_blocks_total", by=len(bids))
            m.inc("swap_bytes_total", by=nbytes)
        self.swaps_out += len(bids)
        return hids

    def _finalize(self, swap: _PendingSwap) -> None:
        import time as _time

        t0 = _time.perf_counter()
        bk = self.pool.block_size

        def land(dense, arena):
            d = np.asarray(dense)  # completes the async copy
            for i, hid in enumerate(swap.hids):
                arena[:, hid] = d[:, 0, :, i * bk:(i + 1) * bk]

        jax.tree.map(land, swap.k_dense, self.k_arena)
        jax.tree.map(land, swap.v_dense, self.v_arena)
        swap.k_dense = swap.v_dense = None
        for hid in swap.hids:
            self._inflight_hids.discard(hid)
        dt = max(_time.perf_counter() - t0, 1e-9)
        bw = swap.nbytes / dt
        self.bw_bytes_per_s = (bw if self.bw_bytes_per_s == float("inf")
                               else 0.8 * self.bw_bytes_per_s + 0.2 * bw)

    def pump(self, max_swaps: Optional[int] = None) -> int:
        """Drain completed demote copies into the arena (scheduler host
        phase).  Returns the number of swaps finalized."""
        done = 0
        while self._pending and (max_swaps is None or done < max_swaps):
            self._finalize(self._pending.pop(0))
            done += 1
        return done

    def _ensure_resident(self, hids: Sequence[int]) -> None:
        want = {int(h) for h in hids}
        while want & self._inflight_hids:
            self._finalize(self._pending.pop(0))

    # -- promote (host -> device), synchronous ------------------------------
    def promote(self, hids: Sequence[int], dest_bids: Sequence[int]) -> None:
        """Swap host blocks back into freshly allocated pool blocks via
        the fixed-arity import scatter.  Bitwise: the arena holds the
        exact exported bytes (int8 ``{q, scale}`` included) and the
        import path never dequantizes.  Raises ``OSError`` if the
        ``host-swap-in`` chaos site is armed — the host copy stays
        resident, so the caller unwinds its device allocations and a
        later attempt re-fetches."""
        assert len(hids) == len(dest_bids) and len(hids) <= self.arity
        self._ensure_resident(hids)
        chaos().io_attempt("host-swap-in")
        bk = self.pool.block_size

        def gather(arena):
            L, _, kv = arena.shape[:3]
            rest = arena.shape[3:]
            shp = (L, 1, kv, self.arity * bk) + tuple(rest[1:])
            dense = np.zeros(shp, dtype=arena.dtype)
            for i, hid in enumerate(hids):
                dense[:, 0, :, i * bk:(i + 1) * bk] = arena[:, int(hid)]
            return dense

        k_dense = jax.tree.map(gather, self.k_arena)
        v_dense = jax.tree.map(gather, self.v_arena)
        scatter = np.full(self.arity, BlockPool.TRASH, dtype=np.int32)
        scatter[:len(dest_bids)] = np.asarray(dest_bids, dtype=np.int32)
        self.pool.import_blocks(k_dense, v_dense, scatter)
        nbytes = self.block_nbytes * len(hids)
        m = self._metrics
        if m is not None:
            m.inc("swap_in_blocks_total", by=len(hids))
            m.inc("swap_bytes_total", by=nbytes)
        self.swaps_in += len(hids)

    # -- introspection ------------------------------------------------------
    def stats(self) -> dict:
        return {
            "n_host_blocks": self.n_host_blocks,
            "host_blocks_used": self.host_used,
            "host_blocks_free": self.host_free,
            "swaps_in_flight": self.in_flight,
            "swap_bw_bytes_per_s": (
                0.0 if self.bw_bytes_per_s == float("inf")
                else self.bw_bytes_per_s),
            "swap_out_blocks": self.swaps_out,
            "swap_in_blocks": self.swaps_in,
            "owners": self.owners(),
        }
