"""Operations and bytes of Solar-Open2-250B (channel-gated delta-rule layers
among gated NoPE attention layers, every layer's MLP routed experts beside
a shared one) from its shapes, for ONE CHIP'S SHARE of a layer's experts:
the benchmark's own arithmetic, beside `flops.py`'s and by the same rules.
Counts are what the algorithm needs: a token uses its `experts_per_token`
experts' matrices and no others, the router's product is counted, norms,
softmaxes, sigmoids, the sort and the gathers are left out, causal
attention counts the half of the score matrix it needs, the chunked delta
rule counts the whole of a block's two score matrices and its solve (the
masked half is computed with the rest: it is how the blocked algorithm is
defined), and a multiply-add is two operations.

`dims` is `spec.dims_of(cfg, file)`: the ten sizes every decoder states and
the file's own (`layer_pattern` the kind of every layer; `kda_num_heads`,
`kda_head_dim`, `kda_short_conv_kernel_size`; `num_experts` the router's
width, `experts_held` the experts whose weights are here,
`n_shared_experts`, `experts_per_token`, `moe_intermediate_size`,
`first_k_dense_replace`; `d_ff` is the published `intermediate_size`, which
no layer uses at `first_k_dense_replace` 0). The head is held apart from
the table: two tables are held, one is multiplied by.
"""

from __future__ import annotations

from typing import Dict, Tuple

BF16_BYTES = 2
F32_BYTES = 4
ATTENTION = ("attention", "full_attention")
# Tokens of a block of the chunked delta rule (`ray_tpu.models.kda.BLOCK`).
BLOCK = 64


def kda_inner(m: Dict) -> int:
    return m["kda_num_heads"] * m["kda_head_dim"]


def kda_mixer_params(m: Dict) -> int:
    """One delta-rule mixer: q, k, v and o projections, the decay's and the
    gate's low-rank pairs through `kda_head_dim`, beta's, the three
    convolutions' taps (A_log, dt_bias and the head norm's scale apart)."""
    d, width, rank = m["d_model"], kda_inner(m), m["kda_head_dim"]
    return (4 * d * width + 2 * (d * rank + rank * width)
            + d * m["kda_num_heads"]
            + 3 * width * m["kda_short_conv_kernel_size"])


def attention_params(m: Dict) -> int:
    """q, k, v, o projections of one attention layer and its output gate."""
    d, hd = m["d_model"], m["head_dim"]
    gate = d * m["n_heads"] * hd if m.get("attn_output_gate") else 0
    return d * m["n_heads"] * hd * 2 + d * m["n_kv_heads"] * hd * 2 + gate


def expert_params(m: Dict) -> int:
    """One routed expert's three matrices: gate, up, down."""
    return 3 * m["d_model"] * m["moe_intermediate_size"]


def held(m: Dict) -> int:
    return m.get("experts_held") or m["num_experts"]


def expert_layer_params(m: Dict) -> int:
    """An expert layer's MLP as this chip stores it: the held experts, the
    shared ones, the router (its published width)."""
    return ((held(m) + m["n_shared_experts"]) * expert_params(m)
            + m["d_model"] * m["num_experts"])


def expert_layer_used(m: Dict) -> float:
    """The MLP matrices one token's forward pass multiplies by HERE: of its
    k experts the share that is held on a mean, the shared ones, the
    router."""
    here = m["experts_per_token"] * held(m) / m["num_experts"]
    return ((here + m["n_shared_experts"]) * expert_params(m)
            + m["d_model"] * m["num_experts"])


def table_params(m: Dict) -> int:
    return m["d_model"] * m["vocab_size"]


def kinds(m: Dict, n_layers: int) -> Tuple[int, int]:
    """(delta-rule layers, attention layers) among the first `n_layers`."""
    types = m["layer_pattern"][:n_layers]
    attn = sum(t in ATTENTION for t in types)
    return len(types) - attn, attn


def params_held(m: Dict, n_layers: int) -> int:
    """Matrix parameters (and the taps) of the first `n_layers` layers as
    this chip stores them, the table and the head."""
    n_kda, n_attn = kinds(m, n_layers)
    return (n_kda * kda_mixer_params(m) + n_attn * attention_params(m)
            + n_layers * expert_layer_params(m) + 2 * table_params(m))


def state_bytes_per_row(m: Dict) -> int:
    """One sequence's recurrent state in one delta-rule layer: a float32
    matrix `[head_dim, head_dim]` a head."""
    return m["kda_num_heads"] * m["kda_head_dim"] ** 2 * F32_BYTES


def state_update_bytes(m: Dict, rows: int, n_layers=None) -> float:
    """The bytes a decode step's state update must move for `rows` slot
    rows: every delta-rule layer's state of every row read once and
    written once (the row's q, k, v, g and o are under 1% of it and left
    out)."""
    n_kda, _ = kinds(m, n_layers or m["n_layers"])
    return float(rows) * n_kda * 2 * state_bytes_per_row(m)


def scan_flops(m: Dict, tokens: int, n_layers=None) -> float:
    """The operations of the chunked delta rule over `tokens` tokens in
    blocks of `BLOCK`, in every delta-rule layer, a token and head: its row
    of the block's two score matrices (k . k and q . k with the decay, 3
    operations a channel and entry), its row of the triangular solve
    against both right-hand sides (BLOCK / 2 multiply-adds over K + V
    columns), the correction from the state (K x V), the output from the
    state and from the block (K x V + BLOCK x V) and what the row adds to
    the state (K x V)."""
    n_kda, _ = kinds(m, n_layers or m["n_layers"])
    dk = m["kda_head_dim"]
    per_head = (2 * 3.0 * BLOCK * dk + 2.0 * (BLOCK / 2) * 2 * dk
                + 3 * 2.0 * dk * dk + 2.0 * BLOCK * dk)
    return float(tokens) * n_kda * m["kda_num_heads"] * per_head


def scan_bytes(m: Dict, tokens: int, calls: int, n_layers=None) -> float:
    """The bytes the scan of `calls` chunks holding `tokens` tokens must
    move in every delta-rule layer: q, k, v and g read and o written once a
    token (float32, as the convolution and the gates leave them; beta is a
    scalar a head), the sequence's state read and written once a chunk."""
    n_kda, _ = kinds(m, n_layers or m["n_layers"])
    per_token = (5 * kda_inner(m) + m["kda_num_heads"]) * F32_BYTES
    return n_kda * (float(tokens) * per_token
                    + 2.0 * calls * state_bytes_per_row(m))


def forward_flops_per_token(m: Dict, n_layers: int, context: float) -> float:
    """One token's forward pass here, its attention layers attending over
    `context` keys and its delta-rule layers scanned in blocks. The
    embedding is a lookup: the table counts once, as the head."""
    n_kda, n_attn = kinds(m, n_layers)
    # A tap is one multiply-add a token, as a matrix's entry is.
    matrix = 2.0 * (n_kda * kda_mixer_params(m) + n_attn * attention_params(m)
                    + n_layers * expert_layer_used(m) + table_params(m))
    attention = n_attn * 4.0 * context * m["n_heads"] * m["head_dim"]
    return matrix + attention + scan_flops(m, 1, n_layers)


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


def grouped_flops(m: Dict, rows: int, n_layers: int = 1) -> float:
    """The operations of the three grouped products over `rows` sorted
    assignments to held experts in each of `n_layers` expert layers."""
    return float(n_layers) * 2.0 * rows * expert_params(m)
