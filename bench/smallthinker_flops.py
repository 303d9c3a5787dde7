"""Operations and bytes of SmallThinker-21BA3B-Instruct from its shapes: a
sparse decoder three of whose four layers attend over a window. A
multiply-add is two operations; norms, rotary embedding, the softmaxes, the
sort and the gathers are left out; a token uses its `experts_per_token`
experts' matrices and no others, and the router's product is counted.

`dims` is `spec.dims_of(cfg, file)`: the ten sizes every decoder states and
the file's own `num_experts`, `experts_per_token`, `moe_intermediate_size`
(ONE expert's width; `d_ff` is stated in the file, the public config has no
dense width and nothing here uses it), `sliding_window_size`,
`sliding_window_layout` and `rope_layout` (one entry a layer of the depth
that runs; a depth other than the lists' takes the lists' share of window
layers).
"""

from __future__ import annotations

from typing import Dict

BF16_BYTES = 2
F32_BYTES = 4


def attention_params(m: Dict) -> int:
    """q, k, v, o projections of one layer."""
    d, hd = m["d_model"], m["head_dim"]
    return 2 * d * hd * (m["n_heads"] + m["n_kv_heads"])


def router_params(m: Dict) -> int:
    return m["d_model"] * m["num_experts"]


def expert_params(m: Dict) -> int:
    """One expert's three matrices: gate, up, down."""
    return 3 * m["d_model"] * m["moe_intermediate_size"]


def layer_params_held(m: Dict) -> int:
    """Matrix parameters of one layer as stored: every expert."""
    return (attention_params(m) + router_params(m)
            + m["num_experts"] * expert_params(m))


def layer_params_used(m: Dict) -> int:
    """Matrix parameters of one layer that one token multiplies by."""
    return (attention_params(m) + router_params(m)
            + m["experts_per_token"] * expert_params(m))


def table_params(m: Dict) -> int:
    """One [vocab, d] table: the embedding, or the untied head."""
    return m["d_model"] * m["vocab_size"]


def params_held(m: Dict, n_layers: int) -> int:
    return n_layers * layer_params_held(m) + 2 * table_params(m)


def params_used_per_token(m: Dict, n_layers: int) -> int:
    """What one token multiplies by; the embedding is a lookup, so one
    table counts."""
    return n_layers * layer_params_used(m) + table_params(m)


def window_share(m: Dict) -> float:
    """The share of layers that attend over the window."""
    layout = m["sliding_window_layout"]
    return sum(bool(w) for w in layout) / len(layout)


def keys_seen(m: Dict, context: float) -> float:
    """Keys a position with `context` positions behind and at it attends
    to, a mean over the layers: all of them in a full layer, the window's
    at most in a window layer."""
    share = window_share(m)
    return ((1.0 - share) * context
            + share * min(context, m["sliding_window_size"]))


def forward_flops_per_token(m: Dict, n_layers: int, context: float) -> float:
    """One position's forward pass with `context` positions to attend
    over."""
    matrix = 2.0 * params_used_per_token(m, n_layers)
    return matrix + n_layers * 4.0 * keys_seen(m, context) * (
        m["n_heads"] * m["head_dim"])


def train_flops_per_token(m: Dict, n_layers: int, seq: int) -> float:
    """Forward and backward of a causal sequence, per token (a position
    sees half the sequence; under the window every layer is a full one):
    what the harness requires of an operations module. No cell trains this
    model."""
    return 3.0 * forward_flops_per_token(m, n_layers, seq / 2.0)


def kv_row_bytes(m: Dict) -> int:
    """One position's keys and values in one layer."""
    return 2 * m["n_kv_heads"] * m["head_dim"] * BF16_BYTES


def expert_bytes(m: Dict, experts_hit: float, n_layers: int = 1) -> float:
    """The bytes of expert weights one call of a step program must read:
    the three bf16 matrices of every expert that received a row, in each
    of `n_layers` layers (`readers/moe.py` passes the window's mean experts
    hit a layer). The rows and what comes back are under 2% of it at a
    step's 192 assignments and are left out."""
    return float(n_layers) * experts_hit * expert_params(m) * BF16_BYTES


def window_attention_bytes(m: Dict, rows: float) -> float:
    """The bytes decode attention over the window must read for `rows`
    cached rows (summed over slots and window layers: a slot's rows in a
    window layer are the lesser of its length and the window): their keys
    and values once. The queries, the results and the one row a slot a
    layer writes are under 1% at thousands of rows a slot and are left
    out."""
    return float(rows) * kv_row_bytes(m)


def window_attention_flops(m: Dict, rows: float) -> float:
    """The operations of the same: every query head's score against, and
    weighted sum over, each of its key-value head's `rows` rows."""
    return 4.0 * float(rows) * m["n_heads"] * m["head_dim"]


def cache_bytes(m: Dict, n_layers: int, slots: int, max_len: int,
                ring_rows: int) -> Dict:
    """What the cache holds: tables of `max_len` positions a slot for the
    full layers, a ring of `ring_rows` a slot for the window layers, and
    what one table for every layer would take."""
    n_window = round(n_layers * window_share(m))
    row = kv_row_bytes(m)
    return {
        "full": (n_layers - n_window) * slots * max_len * row,
        "ring": n_window * slots * ring_rows * row,
        "one_table_for_every_layer": n_layers * slots * max_len * row,
    }


def step_bytes(m: Dict, n_layers: int, slots: int, context: float,
               experts_hit: float) -> Dict:
    """The bytes one decode step over `slots` slots at a mean of `context`
    positions must move, by part: the attention matrices and routers, the
    experts that received a row (`experts_hit` a layer), the cached rows
    read (a full layer's all of them, a window layer's the window's at
    most), the head, and the float32 logits of a row a slot written and
    read."""
    parts = {
        "attention_weights": n_layers * (
            attention_params(m) + router_params(m)) * BF16_BYTES,
        "experts": expert_bytes(m, experts_hit, n_layers),
        "rows_read": n_layers * slots * keys_seen(m, context)
        * kv_row_bytes(m),
        "head": table_params(m) * BF16_BYTES,
        "logits": 2 * slots * m["vocab_size"] * F32_BYTES,
    }
    parts["total"] = float(sum(parts.values()))
    return parts
