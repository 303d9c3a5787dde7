"""Operations and bytes of dots.vlm1.inst's language model (DeepSeek-V3's
block: latent attention, a dense MLP in the leading layers, then routed
experts beside a shared one) from its shapes, for ONE CHIP'S SHARE of a
layer's experts: the benchmark's own arithmetic, beside `flops.py`'s and by
the same rules. Counts are what the algorithm needs: a token uses its
`experts_per_token` experts' matrices and no others, the router's product is
counted, norms, rotary embedding, softmaxes, the sort and the gathers are
left out, causal attention counts the half of the score matrix it needs,
and a multiply-add is two operations.

`dims` is `spec.dims_of(cfg, file)`: the ten sizes every decoder states and
the file's own (`q_lora_rank`, `kv_lora_rank`, the three head widths,
`moe_intermediate_size`, `num_experts` the router's width, `experts_held`
the experts whose weights are here, `n_shared_experts`,
`experts_per_token`, `first_k_dense_replace`; `d_ff` is the dense MLP's
width).
"""

from __future__ import annotations

from typing import Dict

BF16_BYTES = 2


def attention_params(m: Dict) -> int:
    """One layer's attention matrices: the query's two (one without a
    bottleneck), the latent's and the rope key's, every head's keys and
    values out of the latent, and the output's."""
    d, h = m["d_model"], m["n_heads"]
    dn, dr, dv = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    rank, qr = m["kv_lora_rank"], m["q_lora_rank"]
    query = d * qr + qr * h * (dn + dr) if qr else d * h * (dn + dr)
    return query + d * (rank + dr) + rank * h * (dn + dv) + h * dv * d


def expert_params(m: Dict) -> int:
    """One routed expert's three matrices: gate, up, down."""
    return 3 * m["d_model"] * m["moe_intermediate_size"]


def held(m: Dict) -> int:
    return m["experts_held"] or m["num_experts"]


def layer_params_held(m: Dict, dense: bool) -> int:
    """Matrix parameters of one layer as this chip stores it."""
    if dense:
        return attention_params(m) + 3 * m["d_model"] * m["d_ff"]
    return (attention_params(m) + m["d_model"] * m["num_experts"]
            + (held(m) + m["n_shared_experts"]) * expert_params(m))


def layer_params_used(m: Dict, dense: bool) -> float:
    """Matrix parameters of one layer that one token's forward pass
    multiplies by HERE: of its k experts, the share that is held on a mean
    (k * held / num_experts)."""
    if dense:
        return layer_params_held(m, True)
    here = m["experts_per_token"] * held(m) / m["num_experts"]
    return (attention_params(m) + m["d_model"] * m["num_experts"]
            + (here + m["n_shared_experts"]) * expert_params(m))


def table_params(m: Dict) -> int:
    """One [vocab, d] table: the embedding, or the untied head."""
    return m["d_model"] * m["vocab_size"]


def kinds(m: Dict, n_layers: int):
    """(dense layers, expert layers) among the first `n_layers`."""
    dense = min(m["first_k_dense_replace"], n_layers)
    return dense, n_layers - dense


def params_held(m: Dict, n_layers: int) -> int:
    dense, moe = kinds(m, n_layers)
    return (dense * layer_params_held(m, True)
            + moe * layer_params_held(m, False) + 2 * table_params(m))


def forward_flops_per_token(m: Dict, n_layers: int, context: float) -> float:
    """One token's forward pass here, attending over `context` keys in the
    expanded form (a head's 192-wide score and 128-wide weighted sum; the
    keys' and values' expansion is in `attention_params`)."""
    dense, moe = kinds(m, n_layers)
    matrix = 2.0 * (dense * layer_params_used(m, True)
                    + moe * layer_params_used(m, False) + table_params(m))
    width = m["qk_nope_head_dim"] + m["qk_rope_head_dim"] + m["v_head_dim"]
    return matrix + n_layers * 2.0 * context * m["n_heads"] * width


def train_flops_per_token(m: Dict, n_layers: int, seq: int) -> float:
    """Forward and backward (twice the forward) of a causal sequence of
    `seq` tokens, per token; recomputation not counted."""
    return 3.0 * forward_flops_per_token(m, n_layers, seq / 2.0)


def expert_bytes(m: Dict, experts_hit: float, n_layers: int = 1) -> float:
    """The bytes of routed-expert weights one call of a step program must
    read: the three bf16 matrices of every HELD expert that received a row,
    in each of `n_layers` expert layers. `experts_hit` is a layer's number
    of held experts with a row (a mean over calls may be fractional)."""
    return float(n_layers) * experts_hit * expert_params(m) * BF16_BYTES


def latent_row_bytes(m: Dict) -> int:
    """What a cache holds of one token in one layer: the latent and the
    rope key, bf16, nothing a head (576 values, 1,152 B at the published
    widths; padding to whole lanes is not needed and not counted)."""
    return (m["kv_lora_rank"] + m["qk_rope_head_dim"]) * BF16_BYTES


def latent_attention_flops(m: Dict, rows: float, n_layers: int = 1) -> float:
    """The operations of a decode call's attention in the absorbed form
    over `rows` cached rows (summed over the slots) in each of `n_layers`
    layers: every head's score against the latent and the rope key, and its
    weighted sum of latents (278,528 a row at the published widths). The
    two products with W_uk and W_uv are a slot's, not a row's, and belong
    to the projections."""
    rank, dr = m["kv_lora_rank"], m["qk_rope_head_dim"]
    return float(n_layers) * rows * m["n_heads"] * 2.0 * (2 * rank + dr)


def latent_attention_bytes(m: Dict, rows: float, n_layers: int = 1) -> float:
    """The bytes that attention must read: each cached row once."""
    return float(n_layers) * rows * latent_row_bytes(m)
