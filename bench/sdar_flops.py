"""Operations and bytes of SDAR-30B-A3B-Chat from its shapes: a sparse
decoder whose serving step is a PASS over a block of positions a slot
(`ray_tpu.serve.paged_kv.block_pass_paged`). A multiply-add is two
operations; norms, rotary embedding, the softmaxes, the sort and the
gathers are left out; a token uses its `experts_per_token` experts'
matrices and no others, and the router's product is counted.

`dims` is `spec.dims_of(cfg, file)`: the ten sizes every decoder states and
the file's own `num_experts`, `experts_per_token`, `moe_intermediate_size`
(ONE expert's width; `d_ff`, the published `intermediate_size` 6144, is
used by no layer and by nothing here), `block_length`, `denoise_steps`.
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
    return n_layers * layer_params_used(m) + 2 * table_params(m)


def forward_flops_per_token(m: Dict, n_layers: int, context: float) -> float:
    """One position's forward pass attending over `context` keys; the
    embedding is a lookup, so one table counts."""
    matrix = 2.0 * (n_layers * layer_params_used(m) + table_params(m))
    return matrix + n_layers * 4.0 * context * m["n_heads"] * m["head_dim"]


def train_flops_per_token(m: Dict, n_layers: int, seq: int) -> float:
    """Forward and backward of a block-causal sequence, per token (a
    position sees half the sequence and its block): what the harness
    requires of an operations module. No cell trains this model."""
    return 3.0 * forward_flops_per_token(
        m, n_layers, seq / 2.0 + m["block_length"] / 2.0)


def expert_bytes(m: Dict, experts_hit: float, n_layers: int = 1) -> float:
    """The bytes of expert weights one call of a step program must read:
    the three bf16 matrices of every expert that received a row, in each
    of `n_layers` layers (`readers/moe.py` passes the window's mean experts
    hit a layer). The rows and what comes back are under 3% of it at a
    pass's 3,072 assignments and are left out."""
    return float(n_layers) * experts_hit * expert_params(m) * BF16_BYTES


def pass_bytes(m: Dict, n_layers: int, slots: int, rows_cached: float) -> Dict:
    """The bytes one block pass over `slots` slots must move, by part: every
    layer's weights once (a pass of 96 slots x 4 rows x 8 choices hits all
    128 experts), the pages of `rows_cached` rows a slot read once a layer
    (keys and values) and the block's rows written, the head once, and the
    float32 logits of the `block_length / denoise_steps` rows a slot the
    sampler sees, written and read."""
    kv_row = 2 * m["n_kv_heads"] * m["head_dim"] * BF16_BYTES
    filled = m["block_length"] // m["denoise_steps"]
    parts = {
        "layers": n_layers * layer_params_held(m) * BF16_BYTES,
        "pages_read": n_layers * slots * rows_cached * kv_row,
        "pages_written": n_layers * slots * m["block_length"] * kv_row,
        "head": table_params(m) * BF16_BYTES,
        "logits": 2 * slots * filled * m["vocab_size"] * F32_BYTES,
    }
    parts["total"] = float(sum(parts.values()))
    return parts
