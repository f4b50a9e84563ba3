"""Operations and bytes the Laguna-XS.2 cell's algorithm needs, computed
from the configuration file's own numbers (``benchmarks/configs/
laguna-xs.2.json``, a ``laguna`` config) and from nothing of the program:
the work is counted the same whatever implements it.

A multiply-add is two operations.  Attention is counted at what its mask
keeps: in a window layer the BAND (a query keeps ``min(position + 1,
sliding_window)`` keys), in a full layer the triangle; a kept (query,
key) pair costs a head ``head_dim`` multiply-adds for the score and
``head_dim`` for the weighted sum, at the layer's OWN head count
(``num_attention_heads_per_layer``).  A position meets ``top_k`` routed
experts and the shared one.  A decode step's bytes are what it must read:
every weight outside the routed experts once (the embedding table gives a
row a slot), the routed experts its choices touch, the live rows of the
window layers' rings and the live positions of the full layers' pool.
"""

from __future__ import annotations

BF16 = 2


def sizes_of(doc: dict, layers: int | None = None) -> dict:
    """The sizes the functions below need, from the configuration file,
    for its first ``layers`` layers."""
    layers = int(layers if layers is not None else doc["num_hidden_layers"])
    kinds = ["window" if t == "sliding_attention" else "full"
             for t in doc["layer_types"][:layers]]
    assert len(kinds) == layers
    return dict(
        hidden=doc["hidden_size"], layers=layers, kinds=kinds,
        heads=list(doc["num_attention_heads_per_layer"][:layers]),
        dense=[t == "dense" for t in doc["mlp_layer_types"][:layers]],
        kv_heads=doc["num_key_value_heads"], head_dim=doc["head_dim"],
        window=doc["sliding_window"], dense_width=doc["intermediate_size"],
        expert_width=doc["moe_intermediate_size"],
        shared_width=doc["shared_expert_intermediate_size"],
        experts=doc["num_experts"], top_k=doc["num_experts_per_tok"],
        vocab=doc["vocab_size"],
        window_layers=kinds.count("window"),
        full_layers=kinds.count("full"))


def attention_params(s: dict, heads: int) -> int:
    """W_q, W_k, W_v, W_o and the gate's ``hidden x heads``."""
    h, d = s["hidden"], s["head_dim"]
    return (h * heads * d + 2 * h * s["kv_heads"] * d + heads * d * h
            + h * heads)


def expert_params(s: dict) -> int:
    """One routed expert: three matrices of ``hidden x expert_width``."""
    return 3 * s["hidden"] * s["expert_width"]


def ffn_params(s: dict, dense: bool, routed: float | None = None) -> float:
    """A layer's feed-forward parameters: the dense MLP, or the router
    (its matrix and its selection bias), the shared expert and ``routed``
    routed experts (None: all of them)."""
    h = s["hidden"]
    if dense:
        return 3 * h * s["dense_width"]
    routed = s["experts"] if routed is None else routed
    return (h * s["experts"] + s["experts"] + 3 * h * s["shared_width"]
            + routed * expert_params(s))


def layer_param_counts(s: dict) -> list:
    """Every layer's parameters: attention, feed-forward, two norms."""
    return [attention_params(s, H) + ffn_params(s, dense) + 2 * s["hidden"]
            for H, dense in zip(s["heads"], s["dense"])]


def param_count(s: dict) -> int:
    """Every parameter this chip holds: its layers with all their
    experts, the embedding, the untied head and the final norm."""
    return int(sum(layer_param_counts(s))
               + 2 * s["vocab"] * s["hidden"] + s["hidden"])


def weight_bytes(s: dict, bytes_per_param: int = BF16) -> int:
    """Bytes of the weights this chip holds, at the configuration's two
    bytes a parameter (the program keeps routers and gates in float32:
    under a megabyte more a layer)."""
    return param_count(s) * bytes_per_param


def band_pairs(n: int, window: int) -> float:
    """(query, key) pairs a prompt of ``n`` keeps under the window:
    position i keeps min(i + 1, window)."""
    ramp = min(n, window)
    return ramp * (ramp + 1) / 2.0 + max(0, n - window) * window


def triangle_pairs(n: int) -> float:
    return n * (n + 1) / 2.0


def pair_flops(s: dict, heads: int) -> float:
    """A kept (query, key) pair: a score and a weighted sum a head."""
    return 2.0 * heads * 2 * s["head_dim"]


def window_flops(s: dict, prompts) -> float:
    """The window layers' attention for prompts of these lengths: the
    band's pairs alone."""
    pairs = sum(band_pairs(n, s["window"]) for n in prompts)
    return sum(pair_flops(s, H) * pairs
               for H, kind in zip(s["heads"], s["kinds"])
               if kind == "window")


def full_attention_flops(s: dict, prompts) -> float:
    """The full layers' attention: the causal triangle."""
    pairs = sum(triangle_pairs(n) for n in prompts)
    return sum(pair_flops(s, H) * pairs
               for H, kind in zip(s["heads"], s["kinds"]) if kind == "full")


def expert_flops(s: dict, tokens: float) -> float:
    """The routed experts' products for ``tokens`` positions, all expert
    layers: each position meets its ``top_k`` experts' three matrices
    twice (the shared expert and the router are not the grouped
    kernel's)."""
    return (tokens * s["dense"].count(False) * s["top_k"]
            * 2.0 * expert_params(s))


def prefill_flops(s: dict, prompts, logit_rows: float | None = None) -> float:
    """Forward pass of prompts of these lengths: every matmul parameter a
    position meets twice (of the routed experts its ``top_k``; the
    selection bias is no product), the band in the window layers and the
    triangle in the full ones, and the head for ``logit_rows`` rows (None:
    one a prompt, what a prefill that samples its first token asks)."""
    prompts = list(prompts)
    tokens = float(sum(prompts))
    per_token = sum(
        2.0 * (attention_params(s, H)
               + ffn_params(s, dense, s["top_k"])
               - (0 if dense else s["experts"]))
        for H, dense in zip(s["heads"], s["dense"]))
    rows = len(prompts) if logit_rows is None else logit_rows
    return (tokens * per_token + window_flops(s, prompts)
            + full_attention_flops(s, prompts)
            + rows * 2.0 * s["hidden"] * s["vocab"])


def kv_bytes_per_position(s: dict, bytes_per_value: int = BF16) -> int:
    """Keys and values of one cached position: the FULL layers' alone (a
    window layer keeps no pool layer)."""
    return (2 * s["full_layers"] * s["kv_heads"] * s["head_dim"]
            * bytes_per_value)


def ring_bytes_per_slot(s: dict, bytes_per_value: int = BF16) -> int:
    """The window layers' rings: ``window`` rows of keys and values."""
    return (s["window_layers"] * s["window"] * 2 * s["kv_heads"]
            * s["head_dim"] * bytes_per_value)


def ring_read_bytes(s: dict, ring_rows: float,
                    bytes_per_value: int = BF16) -> float:
    """A step's reads of the window layers' rings: ``ring_rows`` live
    rows (a slot's positions up to the window's width), in every window
    layer."""
    return ring_rows * ring_bytes_per_slot(s, bytes_per_value) / s["window"]


def walk_bytes(s: dict, live_positions: float,
               bytes_per_value: int = BF16) -> float:
    """A step's paged walks: the live positions' rows in the full
    layers."""
    return live_positions * kv_bytes_per_position(s, bytes_per_value)


def touched_experts(s: dict, live: float, shares=None) -> float:
    """The routed experts of a layer that a step of ``live`` slots reads:
    those at least one of its ``live x top_k`` choices fell on.
    ``shares``: each expert's share of all choices (the engine's
    counters; None: an even router)."""
    e = s["experts"]
    if shares is None:
        shares = [1.0 / e] * e
    # a token's top_k choices are distinct experts: it misses expert i
    # with probability 1 - top_k x share_i
    return sum(1.0 - max(0.0, 1.0 - s["top_k"] * p) ** live for p in shares)


def decode_step_bytes(s: dict, live: float, live_positions: float,
                      ring_rows: float, touched: float | None = None,
                      bytes_per_param: int = BF16) -> float:
    """Least bytes one decode step of ``live`` slots must read: every
    weight outside the routed experts once (the head; the embedding gives
    a row a slot), ``touched`` routed experts a layer (None: an even
    router's), the live ring rows and the live positions of the pool."""
    if touched is None:
        touched = touched_experts(s, live)
    weights = sum(attention_params(s, H) + ffn_params(s, dense, touched)
                  + 2 * s["hidden"]
                  for H, dense in zip(s["heads"], s["dense"]))
    weights += s["vocab"] * s["hidden"] + s["hidden"]
    return (weights * bytes_per_param
            + ring_read_bytes(s, ring_rows, bytes_per_param)
            + walk_bytes(s, live_positions, bytes_per_param))
