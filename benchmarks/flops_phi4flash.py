"""Operations and bytes the phi-4-mini-flash-reasoning cell's algorithm
needs, computed from the configuration file's own numbers
(``benchmarks/configs/phi-4-mini-flash-reasoning.json``) and from nothing
of the program: the work is counted the same whatever implements it.

A multiply-add is two operations.  Attention is counted at what its mask
keeps: the window's band and not the triangle, the keys up to a
position's own for the full and the cross layers.  The selective scan is
counted as the Mamba reference counts it, ``9 x d_inner x d_state``
operations a position a layer (the decay, its exponential, the input's
outer product, the state's update and the read-out).

``prefill_flops`` counts what a TIMED prefill computes: every row
through the first decoder (layers 0-16) and through layer 17's key and
value projection, and ONE row a prompt through layer 17's attention, the
second decoder (layers 18-31), the final norm and the head.  A prefill
that sent every row through the second decoder too would do about twice
the work; that is not what the cell times.
"""

from __future__ import annotations

BF16, F32 = 2, 4


def sizes_of(doc: dict) -> dict:
    """The sizes the functions below need, from the configuration file:
    the published ones and, from ``derived`` and ``assumed``, the ones
    the family's defaults give."""
    kinds = list(doc["derived"]["layer_types"])
    assert len(kinds) == doc["num_hidden_layers"]
    h, derived = doc["hidden_size"], doc["derived"]
    return dict(
        hidden=h, layers=len(kinds), kinds=kinds,
        heads=doc["num_attention_heads"],
        kv_heads=doc["num_key_value_heads"],
        head_dim=h // doc["num_attention_heads"],
        window=doc["sliding_window"],
        inner=derived["mamba_d_inner"], state=derived["mamba_d_state"],
        conv_taps=derived["mamba_d_conv"], dt_rank=derived["mamba_dt_rank"],
        mlp_width=doc["intermediate_size"], vocab=doc["vocab_size"],
        mamba_layers=kinds.count("mamba"),
        window_layers=kinds.count("window"),
        full_layers=kinds.count("full"), gmu_layers=kinds.count("gmu"),
        cross_layers=kinds.count("cross"),
        boundary=kinds.index("full"))


def matmul_params(s: dict) -> dict:
    """The parameters a token meets in a product, by part."""
    h, di = s["hidden"], s["inner"]
    qd, kvd = s["heads"] * s["head_dim"], s["kv_heads"] * s["head_dim"]
    return {
        # [xs | z] in, [dt | B | C], the step's bottleneck, out
        "mamba": (h * 2 * di + di * (s["dt_rank"] + 2 * s["state"])
                  + s["dt_rank"] * di + di * h),
        "q": h * qd, "kv": 2 * h * kvd, "o": qd * h,
        "gmu": 2 * h * di,
        # [gate | up] in, down out
        "mlp": 3 * h * s["mlp_width"],
    }


def layer_params(s: dict) -> dict:
    """Every parameter of a mixer, by the layer's kind (the two
    LayerNorms of a layer and its MLP are counted in ``param_count``)."""
    p = matmul_params(s)
    h, di, d = s["hidden"], s["inner"], s["head_dim"]
    qd, kvd = s["heads"] * d, s["kv_heads"] * d
    lam_and_norm = 4 * d + 2 * d
    attn = p["q"] + qd + p["o"] + h + lam_and_norm
    return {
        # the products', the convolution and its bias, b_dt, A_log, D
        "mamba": (p["mamba"] + (s["conv_taps"] + 1) * di + di
                  + di * s["state"] + di),
        "window": attn + p["kv"] + 2 * kvd,
        "full": attn + p["kv"] + 2 * kvd,
        "cross": attn,
        "gmu": p["gmu"],
    }


def param_count(s: dict) -> int:
    """Every parameter of the model: a mixer, an MLP and two LayerNorms
    (weight and bias) a layer, the final norm, the embedding table once
    (the head is tied to it)."""
    per = layer_params(s)
    h = s["hidden"]
    return (sum(per[kind] for kind in s["kinds"])
            + s["layers"] * (matmul_params(s)["mlp"] + 4 * h)
            + 2 * h + s["vocab"] * h)


def weight_bytes(s: dict, bytes_per_param: int = BF16) -> int:
    return param_count(s) * bytes_per_param


def attention_flops_per_key(s: dict) -> float:
    """A query position's operations a key it keeps: every query head's
    score (head_dim) and its product with the pair's values (2 x
    head_dim)."""
    return 2.0 * s["heads"] * 3 * s["head_dim"]


def band_keys(n: int, window: int) -> float:
    """Keys the positions of a prompt of ``n`` keep under the window, in
    all: position i keeps min(i + 1, window)."""
    full = max(0, n - window)
    ramp = min(n, window)
    return ramp * (ramp + 1) / 2.0 + full * window


def scan_flops_per_token(s: dict) -> float:
    return 9.0 * s["inner"] * s["state"]


def prefill_flops(s: dict, prompts) -> float:
    """What the timed prefills of ``prompts`` (their lengths) compute."""
    p = matmul_params(s)
    tokens = float(sum(prompts))
    first = s["boundary"]                 # layers wholly before the cut
    mamba = (2.0 * p["mamba"] + 2.0 * s["conv_taps"] * s["inner"]
             + scan_flops_per_token(s))
    window = 2.0 * (p["q"] + p["kv"] + p["o"])
    every_row = tokens * (s["mamba_layers"] * mamba
                          + s["window_layers"] * window
                          + first * 2.0 * p["mlp"]
                          + 2.0 * p["kv"])
    every_row += attention_flops_per_key(s) * s["window_layers"] * sum(
        band_keys(n, s["window"]) for n in prompts)
    # the one row past the boundary: layer 17's query, attention and
    # output, the cross layers' the same, the gated memory units, the
    # MLPs of layers 17-31, the head
    readers = s["full_layers"] + s["cross_layers"]
    one_row = len(prompts) * (
        readers * 2.0 * (p["q"] + p["o"])
        + s["gmu_layers"] * 2.0 * p["gmu"]
        + (s["layers"] - first) * 2.0 * p["mlp"]
        + 2.0 * s["hidden"] * s["vocab"])
    one_row += attention_flops_per_key(s) * readers * tokens
    return every_row + one_row


def kv_bytes_per_position(s: dict, bytes_per_value: int = BF16) -> int:
    """Keys and values of one cached position: ONE layer's."""
    return (2 * s["full_layers"] * s["kv_heads"] * s["head_dim"]
            * bytes_per_value)


def state_bytes_per_slot(s: dict) -> int:
    """The Mamba-1 layers' states and convolution tails, float32."""
    return s["mamba_layers"] * F32 * s["inner"] * (
        s["state"] + s["conv_taps"] - 1)


def ring_bytes_per_slot(s: dict, bytes_per_value: int = BF16) -> int:
    """The window layers' rings: ``window`` rows of keys and values."""
    return (s["window_layers"] * s["window"] * 2 * s["kv_heads"]
            * s["head_dim"] * bytes_per_value)


def ring_read_bytes(s: dict, live: float, live_positions: float,
                    bytes_per_value: int = BF16) -> float:
    """A step's reads of the window layers' rings: the live rows (a slot
    past the window holds all of them)."""
    return (min(live_positions, live * s["window"])
            * ring_bytes_per_slot(s, bytes_per_value) / s["window"])


def state_bytes(s: dict, live: float) -> float:
    """A step's Mamba-1 traffic: every live slot's states and tails read
    once and written once."""
    return 2.0 * live * state_bytes_per_slot(s)


def walk_bytes(s: dict, live_positions: float,
               bytes_per_value: int = BF16) -> float:
    """A step's paged walks: the live positions' rows, read once by the
    layer that wrote them and once by every cross layer."""
    return (live_positions * kv_bytes_per_position(s, bytes_per_value)
            * (1 + s["cross_layers"] / s["full_layers"]))


def decode_step_bytes(s: dict, live: float, live_positions: float,
                      bytes_per_param: int = BF16) -> float:
    """Least bytes one decode step of ``live`` slots holding
    ``live_positions`` cached positions must move: every weight once (the
    tied table as the head reads it; the embedding takes a row a slot),
    the Mamba-1 states read and written, the live rows of the rings (a
    slot past the window holds all of them), the live positions eight
    times."""
    return (weight_bytes(s, bytes_per_param) + state_bytes(s, live)
            + ring_read_bytes(s, live, live_positions, bytes_per_param)
            + walk_bytes(s, live_positions, bytes_per_param))
