"""Serving metrics registry: counters, gauges, latency histograms, and the
tensorboard-style export (same fake-writer idiom as the training metrics
tests)."""

from megatron_llm_tpu.serving import LatencyHistogram, ServingMetrics


class FakeWriter:
    def __init__(self):
        self.scalars = {}

    def add_scalar(self, name, value, iteration):
        self.scalars[name] = (value, iteration)


def test_histogram_stats():
    h = LatencyHistogram(max_samples=4)
    for x in (1.0, 2.0, 3.0, 4.0):
        h.observe(x)
    assert h.count == 4
    assert h.mean() == 2.5
    assert h.percentile(0) == 1.0
    assert h.percentile(100) == 4.0
    # the sample window is bounded, and the mean covers the SAME window
    # as the percentiles; all-time aggregates live in total_count/total
    h.observe(5.0)
    assert h.count == 5 and h.total_count == 5 and h.window_count == 4
    assert h.mean() == 3.5  # mean over the retained window [2, 3, 4, 5]
    assert h.total == 15.0
    assert h.percentile(0) == 2.0  # 1.0 evicted from the window
    snap = h.snapshot()
    assert snap["count"] == 4 and snap["total_count"] == 5
    assert snap["mean_s"] == 3.5


def test_empty_histogram():
    h = LatencyHistogram()
    assert h.count == 0 and h.mean() == 0.0 and h.percentile(95) == 0.0
    assert h.snapshot() == {"count": 0, "total_count": 0, "mean_s": 0.0,
                            "p50_s": 0.0, "p95_s": 0.0, "p99_s": 0.0}
    # unitless reservoirs (prefix_hit_tokens) share the same helper with
    # an empty suffix
    assert h.snapshot(suffix="") == {"count": 0, "total_count": 0,
                                     "mean": 0.0, "p50": 0.0, "p95": 0.0,
                                     "p99": 0.0}


def test_counters_gauges_and_decode_stats():
    m = ServingMetrics(num_slots=4)
    m.inc("submitted", by=3)
    m.inc("completed")
    m.set_gauges(slots_active=2, queue_depth=5)
    m.observe_decode_iteration(3, 0.01)
    m.observe_decode_iteration(2, 0.01)
    snap = m.snapshot()
    assert snap["submitted"] == 3 and snap["completed"] == 1
    assert snap["running"] == 2 and snap["queued"] == 5
    assert snap["slots_total"] == 4 and snap["slot_occupancy"] == 0.5
    assert snap["decode_iterations"] == 2
    assert snap["decode_tokens"] == 5  # 3 + 2 slots served
    assert snap["max_decode_batch"] == 3
    assert snap["per_token_latency"]["count"] == 5


def test_the_exposition_names_two_decode_routes():
    """A decode or verify step is ``paged`` or ``fallback``: the
    snapshot and the Prometheus families say so by weight precision, no
    third route has a counter, and an unknown route is an error."""
    import pytest

    m = ServingMetrics(num_slots=2)
    m.inc_step("paged", "int8")
    m.inc_step("paged", "int8", sampling=True)
    m.inc_step("fallback", "fp32")
    snap = m.snapshot()
    assert (snap["paged_steps"], snap["fallback_steps"]) == (2, 1)
    assert snap["sampled_steps"] == 1
    assert snap["paged_steps_by_precision"] == {"fp32": 0, "int8": 2}
    assert snap["fallback_steps_by_precision"] == {"fp32": 1, "int8": 0}
    assert not [k for k in snap if "fused" in k]
    families = {f.name: f for f in m.collect()}
    routed = sorted(n for n in families if "_steps_by_precision" in n)
    assert routed == ["serving_fallback_steps_by_precision_total",
                      "serving_paged_steps_by_precision_total"]
    assert not [n for n in families if "fused" in n]
    with pytest.raises(KeyError):
        m.inc_step("fused")


def test_prefix_cache_counters_and_hit_rate():
    m = ServingMetrics(num_slots=2)
    snap = m.snapshot()
    assert snap["prefix_hits"] == 0 and snap["prefix_hit_rate"] == 0.0
    m.inc("prefix_hits", by=3)
    m.inc("prefix_misses")
    m.inc("prefix_evicted_blocks", by=7)
    m.set_gauges(prefix_blocks=12)
    for n in (64, 64, 128):
        m.observe_prefix_hit_tokens(n)
    snap = m.snapshot()
    assert snap["prefix_hits"] == 3 and snap["prefix_misses"] == 1
    assert snap["prefix_hit_rate"] == 0.75
    assert snap["prefix_evicted_blocks"] == 7
    assert snap["prefix_blocks"] == 12
    hist = snap["prefix_hit_tokens"]
    assert hist["count"] == 3 and hist["mean"] == 256.0 / 3
    assert hist["p50"] == 64.0


def test_write_exports_serving_scalars():
    m = ServingMetrics(num_slots=2)
    m.inc("submitted")
    m.inc("rejected_queue_full", by=2)
    m.observe_ttft(0.5)
    m.observe_decode_iteration(2, 0.1)
    w = FakeWriter()
    m.write(w, iteration=7)
    assert w.scalars["serving/submitted"] == (1, 7)
    assert w.scalars["serving/rejected_queue_full"] == (2, 7)
    assert w.scalars["serving/max_decode_batch"] == (2, 7)
    assert w.scalars["serving/ttft_mean_s"] == (0.5, 7)
    assert w.scalars["serving/slot_occupancy"] == (0.0, 7)
    for key in ("serving/running", "serving/queued",
                "serving/per_token_latency_p95_s",
                "serving/e2e_latency_mean_s",
                "serving/prefix_hits", "serving/prefix_misses",
                "serving/prefix_hit_rate", "serving/prefix_blocks",
                "serving/prefix_hit_tokens_mean"):
        assert key in w.scalars
