"""Multi-tenant LoRA serving: adapter residency over one base model.

The registry (:class:`AdapterRegistry`) owns the stacked device arena
the decode, verify and prefill steps read, plus the
LRU + ref-pinning residency manager that decides which of the
(potentially thousands of) registered adapters occupy its
``EngineConfig.adapter_cache_slots`` arena slots at any moment —
mirroring the prefix-cache/block-pool design: pinned while any engine
slot decodes under the adapter, unpinned adapters evicted LRU on
pressure, metrics for hits/evictions/resident bytes.

Pure math + the adapter checkpoint format live in ``ops/lora.py``.
"""

from ...ops.lora import (DEFAULT_TARGETS, LORA_TARGETS, LoRAAdapter,
                         init_lora_adapter, load_adapter, merge_adapter,
                         save_adapter, slot_mask)
from .registry import AdapterRegistry

__all__ = [
    "AdapterRegistry",
    "LoRAAdapter",
    "LORA_TARGETS",
    "DEFAULT_TARGETS",
    "init_lora_adapter",
    "load_adapter",
    "save_adapter",
    "merge_adapter",
    "slot_mask",
]
