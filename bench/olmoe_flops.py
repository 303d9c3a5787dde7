"""Operations and bytes of the OLMoE decoder from its shapes: the
benchmark's own arithmetic for a sparse model, beside `flops.py`'s for a
dense one and by the same rules. Counts are what the algorithm needs: a
token uses its `experts_per_token` experts' matrices and no others, the
router's product is counted, norms, rotary embedding, the two softmaxes,
the sort and the gathers are left out, causal attention counts the half of
the score matrix it needs, and a multiply-add is two operations.

`dims` is `spec.dims_of(cfg, file)`: the ten sizes every decoder states
and the file's own `num_experts`, `experts_per_token` (`d_ff` is ONE
expert's width).
"""

from __future__ import annotations

from typing import Dict

BF16_BYTES = 2


def attention_params(m: Dict) -> int:
    """q, k, v, o projections of one layer."""
    d, hd = m["d_model"], m["head_dim"]
    return d * m["n_heads"] * hd * 2 + d * m["n_kv_heads"] * hd * 2


def expert_params(m: Dict) -> int:
    """One expert's three matrices: gate, up, down."""
    return 3 * m["d_model"] * m["d_ff"]


def layer_params_used(m: Dict) -> int:
    """Matrix parameters of one layer that one token's forward pass
    multiplies by: attention, the router, and its k experts."""
    return (attention_params(m) + m["d_model"] * m["num_experts"]
            + m["experts_per_token"] * expert_params(m))


def layer_params_held(m: Dict) -> int:
    """Matrix parameters of one layer as stored: every expert."""
    return (attention_params(m) + m["d_model"] * m["num_experts"]
            + m["num_experts"] * expert_params(m))


def table_params(m: Dict) -> int:
    """One [vocab, d] table: the embedding, or the untied head."""
    return m["d_model"] * m["vocab_size"]


def params_used_per_token(m: Dict, n_layers: int) -> int:
    """What a model card calls the active parameters: the layers' used
    matrices and both tables (the embedding row a token reads is counted
    as the table, as the cards do)."""
    return n_layers * layer_params_used(m) + 2 * table_params(m)


def params_held(m: Dict, n_layers: int) -> int:
    return n_layers * layer_params_held(m) + 2 * table_params(m)


def forward_flops_per_token(m: Dict, n_layers: int, context: float) -> float:
    """One token's forward pass attending over `context` keys. The
    embedding is a lookup, not a product: one table counts."""
    matrix = 2.0 * (n_layers * layer_params_used(m) + table_params(m))
    attention = n_layers * 4.0 * context * m["n_heads"] * m["head_dim"]
    return matrix + attention


def train_flops_per_token(m: Dict, n_layers: int, seq: int) -> float:
    """Forward and backward (twice the forward) of a causal sequence of
    `seq` tokens, per token; recomputation not counted."""
    return 3.0 * forward_flops_per_token(m, n_layers, seq / 2.0)


def expert_bytes(m: Dict, experts_hit: float, n_layers: int = 1) -> float:
    """The bytes of expert weights one call of a step program must read:
    the three bf16 matrices of every expert that received a row, in each of
    `n_layers` layers. `experts_hit` is a layer's number of experts with a
    row (a mean over calls may be fractional). The rows themselves and
    what comes back (rows x (2 d + 3 ff) values) are under 2% of it at a
    decode step's 128 rows and are left out."""
    return float(n_layers) * experts_hit * expert_params(m) * BF16_BYTES


def grouped_flops(m: Dict, rows: int, n_layers: int = 1) -> float:
    """The operations of the three grouped products over `rows` sorted
    assignments (rows = tokens x experts_per_token)."""
    return float(n_layers) * 2.0 * rows * expert_params(m)
