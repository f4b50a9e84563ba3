"""Adapter registry + arena residency manager (LRU + ref pinning).

The registry answers one question for the engine's admission path:
*which arena slot holds this request's adapter?*  ``acquire`` pins the
adapter for the life of the engine slot (``release`` on retirement /
extraction), installing it into a free or LRU-evicted arena slot on a
miss.  When every arena slot is pinned by an active request the acquire
returns ``None`` and the engine parks the request at the queue head —
the exact backpressure shape the block pool's reservation failure
produces, so admission order is preserved under adapter-cache pressure
just like under KV pressure.

The device arena is the punica/S-LoRA trick from ``ops/lora.py``: one
``A_flat [L, in, n_slots·r]`` / ``B_flat [L, n_slots·r, out]`` pair per
target projection, α/r folded into B at install.  Installs go through
ONE jitted ``dynamic_update_slice`` executable with a *traced* slot
index — admissions never recompile, however many adapters rotate
through.  Reads never materialize per-request factor tensors (tpulint
R8): the hot path consumes the resident arena + a per-row slot vector.

Thread-safety mirrors ``PrefixCache``: a single lock over the host-side
residency maps; the arena swap is a reference assignment (the jitted
install returns new arrays).  The engine only calls acquire/release
from its scheduler thread, but tests and tools may poke the registry
directly.
"""

from __future__ import annotations

import functools
from collections import OrderedDict
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from ...config import ModelConfig
from ...ops import lora as lora_lib
from ...analysis import sanitizers
from ..metrics import ServingMetrics

# one compiled install executable per factor geometry: the slot index is
# a traced operand, so adapter churn never recompiles (the same pattern
# as the engine's donated/plain jitted-impl pairs — donate the old arena
# on TPU, skip donation where the backend can't use it)
_install_donated = functools.partial(
    jax.jit, static_argnames=("scale", "rank"),
    donate_argnums=(0,))(lora_lib.install_adapter)
_install_plain = functools.partial(
    jax.jit, static_argnames=("scale", "rank"))(lora_lib.install_adapter)


class AdapterRegistry:
    """LoRA adapter store + device-arena residency for one engine.

    ``n_slots`` arena slots (``EngineConfig.adapter_cache_slots``), all
    adapters sharing one ``rank`` and one target set — the price of a
    single stacked arena and one compiled step.  Register
    any number of adapters host-side; at most ``n_slots`` are device-
    resident at once.
    """

    def __init__(self, cfg: ModelConfig, n_slots: int, rank: int,
                 targets=None, *,
                 metrics: Optional[ServingMetrics] = None):
        if n_slots < 1:
            raise ValueError("AdapterRegistry needs n_slots >= 1")
        if rank < 1:
            raise ValueError("AdapterRegistry needs rank >= 1")
        self.cfg = cfg
        self.n_slots = int(n_slots)
        self.rank = int(rank)
        self.targets = (tuple(targets) if targets is not None
                        else lora_lib.DEFAULT_TARGETS)
        unknown = [t for t in self.targets
                   if t not in lora_lib.lora_target_shapes(cfg)]
        if unknown:
            raise ValueError(f"unknown LoRA targets {unknown}")
        if cfg.num_experts > 0:
            moe = [t for t in self.targets
                   if t in ("w_gate", "w_up", "w_down")]
            if moe:
                # the MoE dispatch routes tokens through per-expert
                # weights the stacked arena doesn't model; _mlp_dispatch
                # would silently skip the delta, so refuse up front
                raise ValueError(
                    f"LoRA MLP targets {moe} unsupported with MoE "
                    f"(num_experts={cfg.num_experts}); use attention "
                    "targets only")
        self._lock = sanitizers.make_lock("serving.adapters")
        self._metrics = metrics  # bound by the engine it is handed to
        self._store: Dict[str, lora_lib.LoRAAdapter] = {}
        self._slot_of: Dict[str, int] = {}        # resident id -> slot
        self._ids: list = [None] * self.n_slots   # slot -> id | None
        self._refs: list = [0] * self.n_slots     # pin counts
        self._lru: "OrderedDict[str, None]" = OrderedDict()  # unpinned
        self._free: list = list(range(self.n_slots - 1, -1, -1))
        self.arenas = lora_lib.make_arenas(cfg, self.n_slots, self.rank,
                                           self.targets)
        self._install = (_install_donated
                         if jax.default_backend() == "tpu"
                         else _install_plain)

    # -- host-side store ---------------------------------------------------

    def register(self, adapter_id: str,
                 adapter: lora_lib.LoRAAdapter) -> None:
        """Add (or replace) an adapter in the host-side store.  All
        registered adapters must share the registry's rank/targets —
        replacement of a *resident* adapter is rejected (swap the id)."""
        if adapter.rank != self.rank:
            raise ValueError(
                f"adapter {adapter_id!r} rank {adapter.rank} != registry "
                f"rank {self.rank}")
        if set(adapter.targets) != set(self.targets):
            raise ValueError(
                f"adapter {adapter_id!r} targets {adapter.targets} != "
                f"registry targets {self.targets}")
        lora_lib.validate_adapter(self.cfg, adapter)
        with self._lock:
            if adapter_id in self._slot_of:
                raise ValueError(
                    f"adapter {adapter_id!r} is arena-resident; "
                    "register updates under a new id")
            self._store[adapter_id] = adapter

    def register_path(self, adapter_id: str, path: str) -> None:
        """Load an adapter checkpoint directory and register it."""
        self.register(adapter_id, lora_lib.load_adapter(path))

    def known(self, adapter_id: str) -> bool:
        with self._lock:
            return adapter_id in self._store

    def clone(self) -> "AdapterRegistry":
        """A fresh registry — own arena, empty residency, no pins —
        sharing this one's host-side adapter store by reference.  One
        per engine replica in a cluster: arena slots and pin counts are
        scheduler-thread state and must never cross replicas, but the
        (immutable) registered factor trees are safely shared."""
        out = AdapterRegistry(self.cfg, self.n_slots, self.rank,
                              self.targets)
        with self._lock:
            out._store = dict(self._store)
        return out

    @property
    def sr(self) -> int:
        """Total stacked rank of the arena (n_slots · rank)."""
        return self.n_slots * self.rank

    # -- residency ---------------------------------------------------------

    def acquire(self, adapter_id: str) -> Optional[int]:
        """Pin ``adapter_id`` and return its arena slot; ``None`` when
        every slot is pinned by other adapters (caller parks and
        retries).  Raises ``KeyError`` for an unregistered id."""
        with self._lock:
            adapter = self._store.get(adapter_id)
            if adapter is None:
                raise KeyError(f"unknown adapter {adapter_id!r}")
            slot = self._slot_of.get(adapter_id)
            if slot is not None:
                self._refs[slot] += 1
                self._lru.pop(adapter_id, None)
                self._inc("adapter_hits")
                return slot
            slot = self._evict_or_free()
            if slot is None:
                self._inc("adapter_misses")
                return None
            self._inc("adapter_misses")
            self._inc("adapter_installs")
            self._ids[slot] = adapter_id
            self._slot_of[adapter_id] = slot
            self._refs[slot] = 1
            self.arenas = self._install(
                self.arenas, adapter.factors, jnp.int32(slot),
                scale=adapter.scale, rank=self.rank)
            self._gauges()
            return slot

    def release(self, adapter_id: str) -> None:
        """Drop one pin.  The adapter stays arena-resident (an LRU
        candidate) until eviction pressure reclaims its slot."""
        with self._lock:
            slot = self._slot_of.get(adapter_id)
            if slot is None:
                return
            self._refs[slot] = max(0, self._refs[slot] - 1)
            if self._refs[slot] == 0:
                self._lru[adapter_id] = None
                self._lru.move_to_end(adapter_id)

    def _evict_or_free(self) -> Optional[int]:
        """A free slot, else the LRU unpinned resident's slot (lock
        held).  The evicted slot's arena columns are overwritten by the
        caller's install — no zeroing round-trip needed."""
        if self._free:
            return self._free.pop()
        if not self._lru:
            return None
        victim, _ = self._lru.popitem(last=False)
        slot = self._slot_of.pop(victim)
        # tpulint: allow[lock-discipline] lock held by the only caller
        # (acquire) — the docstring is the contract
        self._ids[slot] = None
        # tpulint: allow[lock-discipline] as above, acquire holds the lock
        self._refs[slot] = 0
        self._inc("adapter_evictions")
        return slot

    # -- introspection -----------------------------------------------------

    def resident(self) -> Dict[str, int]:
        """adapter_id -> arena slot of every resident adapter."""
        with self._lock:
            return dict(self._slot_of)

    def is_resident(self, adapter_id: str) -> bool:
        with self._lock:
            return adapter_id in self._slot_of

    def pins(self, adapter_id: str) -> int:
        with self._lock:
            slot = self._slot_of.get(adapter_id)
            return 0 if slot is None else self._refs[slot]

    def resident_bytes(self) -> int:
        with self._lock:
            return sum(self._store[a].nbytes for a in self._slot_of)

    # -- metrics -----------------------------------------------------------

    def _inc(self, name: str) -> None:
        m = self._metrics
        if m is not None:
            m.inc(name)

    def _gauges(self) -> None:
        m = self._metrics
        if m is not None:
            m.set_gauges(
                adapter_resident=len(self._slot_of),
                adapter_resident_bytes=sum(
                    self._store[a].nbytes for a in self._slot_of))
