"""Operations and bytes of LFM2-24B-A2B (gated short convolutions among
attention layers, a dense MLP in the leading layers, then routed experts)
from its shapes: the benchmark's own arithmetic, beside `flops.py`'s and by
the same rules. Counts are what the algorithm needs: a token uses its
`experts_per_token` experts' matrices and no others, the router's product is
counted, norms, rotary embedding, softmaxes, the sort and the gathers are
left out, causal attention counts the half of the score matrix it needs,
and a multiply-add is two operations.

`dims` is `spec.dims_of(cfg, file)`: the ten sizes every decoder states and
the file's own (`layer_pattern` the kind of every layer, `conv_L_cache` the
convolution's taps, `first_k_dense_replace` the leading dense layers,
`num_experts`, `experts_per_token`, `moe_intermediate_size`; `d_ff` is the
dense MLP's width). The head is held apart from the table: two tables are
held, one is multiplied by (the embedding is a lookup).
"""

from __future__ import annotations

from typing import Dict, Tuple

BF16_BYTES = 2
ATTENTION = ("attention", "full_attention")


def conv_mixer_params(m: Dict) -> int:
    """One conv mixer: `in_proj` [d, 3d], `out_proj` [d, d], the taps."""
    d = m["d_model"]
    return 3 * d * d + d * d + d * m["conv_L_cache"]


def attention_params(m: Dict) -> int:
    """q, k, v, o projections of one attention layer."""
    d, hd = m["d_model"], m["head_dim"]
    return d * m["n_heads"] * hd * 2 + d * m["n_kv_heads"] * hd * 2


def dense_mlp_params(m: Dict) -> int:
    return 3 * m["d_model"] * m["d_ff"]


def expert_params(m: Dict) -> int:
    """One routed expert's three matrices: gate, up, down."""
    return 3 * m["d_model"] * m["moe_intermediate_size"]


def expert_layer_params(m: Dict) -> int:
    """An expert layer's 64 experts and its router (without its mixer)."""
    return (m["num_experts"] * expert_params(m)
            + m["d_model"] * m["num_experts"])


def table_params(m: Dict) -> int:
    return m["d_model"] * m["vocab_size"]


def kinds(m: Dict, n_layers: int) -> Tuple[int, int, int, int]:
    """(conv layers, attention layers, dense MLPs, expert layers) among
    the first `n_layers`."""
    types = m["layer_pattern"][:n_layers]
    attn = sum(t in ATTENTION for t in types)
    dense = min(m["first_k_dense_replace"], n_layers)
    return n_layers - attn, attn, dense, n_layers - dense


def params_held(m: Dict, n_layers: int) -> int:
    """Matrix parameters (and the taps) of the first `n_layers` layers, the
    table and the head."""
    conv, attn, dense, moe = kinds(m, n_layers)
    return (conv * conv_mixer_params(m) + attn * attention_params(m)
            + dense * dense_mlp_params(m) + moe * expert_layer_params(m)
            + 2 * table_params(m))


def params_used_per_token(m: Dict, n_layers: int) -> int:
    """The matrices one token's forward pass multiplies by: what a model
    card calls the active parameters, the embedding's lookup apart."""
    conv, attn, dense, moe = kinds(m, n_layers)
    return (conv * conv_mixer_params(m) + attn * attention_params(m)
            + dense * dense_mlp_params(m)
            + moe * (m["experts_per_token"] * expert_params(m)
                     + m["d_model"] * m["num_experts"])
            + table_params(m))


def forward_flops_per_token(m: Dict, n_layers: int, context: float) -> float:
    """One token's forward pass, its attention layers attending over
    `context` keys. The embedding is a lookup: the table counts once, as
    the head."""
    attn = kinds(m, n_layers)[1]
    return (2.0 * params_used_per_token(m, n_layers)
            + attn * 4.0 * context * m["n_heads"] * m["head_dim"])


def train_flops_per_token(m: Dict, n_layers: int, seq: int) -> float:
    """Forward and backward (twice the forward) of a causal sequence of
    `seq` tokens, per token; recomputation not counted."""
    return 3.0 * forward_flops_per_token(m, n_layers, seq / 2.0)


def expert_bytes(m: Dict, experts_hit: float, n_layers: int = 1) -> float:
    """The bytes of expert weights one call of a step program must read:
    the three bf16 matrices of every expert that received a row, in each of
    `n_layers` EXPERT layers (a leading dense layer has none).
    `experts_hit` is a layer's number of experts with a row (a mean over
    calls may be fractional). The rows themselves and what comes back are
    under 2% of it at a decode step's 384 rows and are left out."""
    return float(n_layers) * experts_hit * expert_params(m) * BF16_BYTES


def grouped_flops(m: Dict, rows: int, n_layers: int = 1) -> float:
    """The operations of the three grouped products over `rows` sorted
    assignments (rows = tokens x experts_per_token) in each of `n_layers`
    expert layers."""
    return float(n_layers) * 2.0 * rows * expert_params(m)


def conv_mixer_flops(m: Dict, tokens: float, n_layers: int = 1) -> float:
    """The operations of `n_layers` conv mixers over `tokens` tokens: the
    two projections, the two gates and the taps."""
    d = m["d_model"]
    per_token = 2.0 * (3 * d * d + d * d) + 2.0 * d * m["conv_L_cache"] + 2 * d
    return float(n_layers) * tokens * per_token


def conv_mixer_bytes(m: Dict, rows: float, n_layers: int = 1) -> float:
    """The bytes `n_layers` conv mixers must move in one call that advances
    `rows` sequences: each mixer's weights read once, and each sequence's
    carried inputs (`conv_L_cache - 1` rows of d, bf16) read and written."""
    d = m["d_model"]
    carried = 2 * rows * (m["conv_L_cache"] - 1) * d
    return float(n_layers) * (conv_mixer_params(m) + carried) * BF16_BYTES
