"""Reads the serving engine's per-layer, per-expert assignment counter:
the family ``params["family"]`` of the program's metrics registry
(``obs/registry.py``), one sample a (layer, expert) with a ``held``
label.  The engine carries the counts on the device and fetches them
when the registry is scraped, which is here, once, after the run.

The metric: the busiest held expert's count over the mean held expert's
count, in the worst layer (1.0 = the router spreads its choices
evenly).  None where the program has no such family."""

from __future__ import annotations

FAMILY = "serving_expert_assignments_total"


def _samples(family: str):
    try:
        from megatron_llm_tpu.obs.registry import REGISTRY
    except ImportError:
        return []
    return [s for fam in REGISTRY.collect() if fam.name == family
            for s in fam.samples]


def held_share(family: str = FAMILY):
    """Share of all counted choices that fell on a held expert, or None."""
    rows = _samples(family)
    total = sum(s.value for s in rows)
    if not total:
        return None
    return sum(s.value for s in rows if s.labels.get("held") == "1") / total


def read(evidence: dict, params: dict):
    by_layer: dict = {}
    for s in _samples(params["family"]):
        if s.labels.get("held") == "1":
            by_layer.setdefault(s.labels["layer"], []).append(s.value)
    worst = [max(v) * len(v) / sum(v) for v in by_layer.values() if sum(v)]
    return max(worst) if worst else None
