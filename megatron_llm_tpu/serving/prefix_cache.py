"""Automatic prefix caching: zero-copy shared-prefix KV reuse.

Every admission used to recompute its prompt from token 0 even when the
first few hundred tokens were the same system prompt every other request
carried, and that recompute dominates TTFT for exactly the traffic the
engine targets.
This module is the RadixAttention / vLLM-automatic-prefix-caching idea
over the paged block pool: a host-side trie over **block-aligned**
token-id prefixes whose nodes hold *pool block ids*, consulted at
admission and fed at retirement.  Since the pool rebase the cache moves
ZERO K/V bytes: a hit is a ref-count bump that places the shared block
ids directly into the admitted slot's block table, and an offer is a
ref-count bump on blocks the retiring slot already owns.  (The old
design extracted rows at retirement and concatenated-and-padded a fresh
admission cache per hit — one device dispatch each way; both are gone.)

Block granularity.  A trie node covers exactly ``block_tokens`` token
positions, and ``block_tokens`` MUST equal the pool's ``block_size`` so
a cached block IS a pool block — that identity is what makes sharing
free.  The engine therefore derives both from the same
``kv_block_size``.  RoPE is applied at a token's absolute position
before K enters the pool, and a prefix occupies the same absolute
positions in every sequence that shares it — shared blocks are valid
verbatim, no re-rotation, and int8 ``{q, scale}`` leaves are never
touched at all.

Admission (``match_and_acquire``).  The longest cached block-aligned
prefix STRICTLY shorter than the prompt is matched (at least one real
token must run through the suffix prefill to produce the logits the
first sampled token needs).  Matched nodes are **trie-pinned**
(``ref``-counted against eviction) for the life of the request, and the
lease's ``bids`` go to ``SlotAllocator.insert`` which bumps the pool
ref of each shared block as it enters the slot's table.  The engine
prefills only the uncached suffix into the gathered working view.
Because the shared blocks hold the exact rows a cold prefill would
write, sampling, logprobs, and the pipelined decode path are bitwise
identical to a cold admission (asserted against ``generate_tokens`` in
tests/serving/test_prefix_cache.py, fp32 + int8).

Retirement (``offer``).  The slot's block-aligned prompt prefix is
walked into the trie; blocks already present are LRU-touched, missing
ones — always one contiguous tail of the walk — are adopted from the
slot's own table by pool ``incref``: the trie simply becomes one more
owner of blocks that already exist.  Decode appends at fill >= plen, so
offered prefix blocks are never written after retirement (the boundary
block a successor might append into is copy-on-write in the pool).

Eviction.  A soft budget of ``max_blocks`` trie blocks: when an offer
pushes past it, least-recently-used nodes with ``ref == 0`` and no
children are dropped (evicting a middle node would orphan its
descendants' match path) and their pool ref released.  Pinned chains can
transiently exceed the budget — correctness over strict accounting.
``evict_blocks`` additionally lets the engine force eviction when the
*pool* (not the trie budget) is the scarce resource at admission.

Host cost is O(prompt/block) dict lookups per admission; no device work.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

from ..config import ModelConfig
from .block_pool import BlockPool
from .metrics import ServingMetrics


class _Node:
    """One cached block: ``key`` is its block_tokens token ids, ``bid``
    the pool block holding its K/V rows (the trie owns one pool ref).
    A *spilled* node instead holds ``hid`` — a host-tier block id — with
    ``bid`` back at trash: the rows live in host RAM and re-promote into
    a fresh pool block on the next match (tiered KV, docs/serving.md)."""

    __slots__ = ("key", "parent", "children", "bid", "hid", "ref", "tick")

    def __init__(self, key: Tuple[int, ...], parent: "_Node"):
        self.key = key
        self.parent = parent
        self.children: dict = {}
        self.bid = BlockPool.TRASH
        self.hid = None     # host-tier block id when spilled
        self.ref = 0        # live leases pinning this block
        self.tick = 0       # LRU clock at last touch


class PrefixLease:
    """A matched chain of blocks, pinned against eviction until
    ``PrefixCache.release``.  ``tokens`` is the matched prefix length;
    ``bids`` the pool block ids to place in the slot's table."""

    __slots__ = ("nodes", "tokens")

    def __init__(self, nodes: List[_Node], tokens: int):
        self.nodes = nodes
        self.tokens = tokens

    @property
    def bids(self) -> List[int]:
        return [n.bid for n in self.nodes]


class PrefixCache:
    """Block-granular radix cache over token-id prefixes (module doc)."""

    def __init__(self, cfg: ModelConfig, *, pool: BlockPool,
                 max_blocks: int, max_seq_len: int,
                 metrics: Optional[ServingMetrics] = None,
                 host_tier=None):
        assert max_blocks >= 1
        self.cfg = cfg
        self.pool = pool
        self.block_tokens = int(pool.block_size)
        self.max_blocks = int(max_blocks)
        self.max_seq_len = int(max_seq_len)
        self._metrics = metrics
        # optional HostKVTier: eviction victims demote to host RAM
        # instead of being dropped, and re-promote on the next match
        self.host_tier = host_tier
        self._root = _Node((), None)
        self._blocks = 0
        self._host_blocks = 0
        self._tick = 0

    @property
    def blocks(self) -> int:
        """Blocks currently resident (tooling / budget introspection)."""
        return self._blocks

    @property
    def host_blocks(self) -> int:
        """Spilled trie blocks resident in the host tier."""
        return self._host_blocks

    def _touch(self, node: _Node) -> None:
        self._tick += 1
        node.tick = self._tick

    def _keys(self, tokens: Sequence[int], n_blocks: int):
        b = self.block_tokens
        for i in range(n_blocks):
            yield tuple(int(t) for t in tokens[i * b:(i + 1) * b])

    # -- admission side ----------------------------------------------------

    def match_and_acquire(self,
                          tokens: Sequence[int]) -> Optional[PrefixLease]:
        """Pin and return the longest cached block-aligned prefix of
        ``tokens`` that is strictly shorter than it, or None on a miss.

        The strict cap — at most ``(len - 1) // block`` blocks — leaves
        >= 1 real token for the suffix prefill, whose last-row logits
        seed the first sampled token exactly as a cold prefill's would.
        """
        usable = (len(tokens) - 1) // self.block_tokens
        nodes: List[_Node] = []
        cur = self._root
        for key in self._keys(tokens, usable):
            child = cur.children.get(key)
            if child is None:
                break
            if child.hid is not None and not self._promote(child):
                # spilled block that could not come back (pool full or a
                # host-swap-in fault, host copy retained) — the match
                # stops here and a later admission re-fetches
                break
            nodes.append(child)
            cur = child
        m = self._metrics
        if not nodes:
            if m is not None:
                m.inc("prefix_misses")
            return None
        for n in nodes:
            n.ref += 1
            self._touch(n)
        matched = len(nodes) * self.block_tokens
        if m is not None:
            m.inc("prefix_hits")
            m.observe_prefix_hit_tokens(matched)
        return PrefixLease(nodes, matched)

    def release(self, lease: Optional[PrefixLease]) -> None:
        """Unpin a lease (request retired or aborted); then trim any
        over-budget blocks the pin was protecting."""
        if lease is None:
            return
        nodes, lease.nodes = lease.nodes, []  # idempotent
        for n in nodes:
            n.ref -= 1
        if nodes:
            self._evict()

    # -- retirement side ---------------------------------------------------

    def offer(self, tokens: Sequence[int], table: Sequence[int]) -> int:
        """Adopt the block-aligned prefix of ``tokens`` from a retiring
        slot's block ``table``.  Blocks already cached are LRU-touched;
        missing ones — one contiguous tail of the walk — enter the trie
        by pool ``incref`` on the ids the slot already owns.  No device
        work.  Returns the number of newly adopted blocks."""
        n_blocks = len(tokens) // self.block_tokens
        keys = list(self._keys(tokens, n_blocks))
        # A missing block can only be followed by missing blocks (a
        # node's descendants exist only under a present node), so the
        # blocks to adopt are one contiguous tail of the walk.
        cur = self._root
        first_missing = n_blocks
        for i, key in enumerate(keys):
            child = cur.children.get(key)
            if child is None:
                first_missing = i
                break
            self._touch(child)
            cur = child
        added = n_blocks - first_missing
        for i in range(first_missing, n_blocks):
            bid = int(table[i])
            assert bid != BlockPool.TRASH, \
                "offered prompt prefix has an unallocated block"
            self.pool.incref(bid)
            child = _Node(keys[i], cur)
            child.bid = bid
            cur.children[keys[i]] = child
            self._touch(child)
            self._blocks += 1
            cur = child
        if added:
            self._evict()
        return added

    # -- host-tier spill / promote ----------------------------------------

    def _promote(self, node: _Node) -> bool:
        """Bring a spilled node's rows back from the host tier into a
        fresh pool block.  False (node stays spilled, host copy intact)
        when the pool has no block to give or the swap-in faults."""
        if not self.pool.reserve(1):
            return False
        bid = self.pool.alloc_reserved()
        try:
            self.host_tier.promote([node.hid], [bid])
        except OSError:
            self.pool.decref(bid)
            return False
        self.host_tier.free([node.hid])
        node.hid = None
        node.bid = bid
        self._host_blocks -= 1
        self._blocks += 1
        m = self._metrics
        if m is not None:
            m.inc("prefix_promotions_total")
        return True

    def _spill(self, victim: _Node) -> bool:
        """Demote an eviction victim's block to the host tier, keeping
        the node in the trie as a spilled entry.  When the tier is full,
        the LRU childless *spilled* node is dropped outright to make
        room.  False -> caller falls back to a plain drop."""
        tier = self.host_tier
        if tier is None:
            return False
        if not tier.can_store(1):
            self._drop_lru_spilled()
        if not tier.can_store(1) or not tier.swap_ok():
            return False
        try:
            hids = tier.begin_demote([victim.bid], owner="prefix-cache")
        except OSError:
            return False  # device copy untouched; plain drop is safe
        self.pool.decref(victim.bid)
        victim.bid = BlockPool.TRASH
        victim.hid = hids[0]
        self._blocks -= 1
        self._host_blocks += 1
        return True

    def _drop_lru_spilled(self) -> None:
        victim = None
        stack = list(self._root.children.values())
        while stack:
            n = stack.pop()
            if (n.hid is not None and not n.children
                    and (victim is None or n.tick < victim.tick)):
                victim = n
            stack.extend(n.children.values())
        if victim is None:
            return
        del victim.parent.children[victim.key]
        self.host_tier.free([victim.hid])
        victim.hid = None
        victim.parent = None
        self._host_blocks -= 1

    # -- eviction ----------------------------------------------------------

    def evict_blocks(self, n: int) -> int:
        """Force-evict up to ``n`` unpinned blocks regardless of the trie
        budget — the engine calls this when the POOL is the scarce
        resource at admission.  Returns the number actually evicted."""
        return self._evict(want=n)

    def _evict(self, want: int = 0) -> int:
        """LRU-evict unpinned childless blocks until within budget (or,
        with ``want``, until that many are gone), stopping early when
        everything left is pinned — soft budget."""
        evicted = 0
        while (self._blocks > self.max_blocks) or (evicted < want
                                                   and self._blocks > 0):
            # victim = LRU unpinned resident node with no RESIDENT child.
            # A spilled child does not protect its parent — spilling
            # keeps the node in the trie, so whole chains can demote
            # leaf-up instead of wedging after the first leaf.
            victim = None
            stack = list(self._root.children.values())
            while stack:
                n = stack.pop()
                if (n.ref == 0 and n.hid is None
                        and all(c.hid is not None
                                for c in n.children.values())
                        and (victim is None or n.tick < victim.tick)):
                    victim = n
                stack.extend(n.children.values())
            if victim is None:
                break
            if self._spill(victim):
                # demoted to the host tier: the pool block is freed (the
                # eviction's goal) but the cached prefix survives spilled
                evicted += 1
                continue
            if victim.children:
                # can't spill and can't plain-drop a node with spilled
                # children without orphaning them; stop here (soft)
                break
            del victim.parent.children[victim.key]
            self.pool.decref(victim.bid)
            victim.bid = BlockPool.TRASH
            victim.parent = None
            self._blocks -= 1
            evicted += 1
        if evicted:
            m = self._metrics
            if m is not None:
                m.inc("prefix_evicted_blocks", by=evicted)
        return evicted
