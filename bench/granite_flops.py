"""Operations and bytes of the Granite 4.0 hybrid decoder (Mamba-2 layers
among attention layers, a dense gated MLP after each) from its shapes: the
benchmark's own arithmetic, beside `flops.py`'s and by the same rules.
Counts are what the algorithm needs: norms, softmax, softplus, the gate and
the skip term are left out, a multiply-add is two operations, causal
attention counts the half of the score matrix it needs, and the chunked
scan counts the whole quadratic form of each block (the masked half is
computed with the rest: it is how the blocked algorithm is defined).

`dims` is `spec.dims_of(cfg, file)`: the ten sizes every decoder states and
the file's own (`layer_pattern`, the public `layer_types`; the `mamba_*` sizes; `d_ff` is the MLP's
width, which the published config calls `shared_intermediate_size`).
"""

from __future__ import annotations

from typing import Dict

BF16_BYTES = 2
F32_BYTES = 4


def inner(m: Dict) -> int:
    return m["mamba_n_heads"] * m["mamba_d_head"]


def conv_channels(m: Dict) -> int:
    return inner(m) + 2 * m["mamba_n_groups"] * m["mamba_d_state"]


def kinds(m: Dict, n_layers: int):
    """(Mamba layers, attention layers) among the first `n_layers`."""
    types = m["layer_pattern"][:n_layers]
    return (sum(t == "mamba" for t in types),
            sum(t == "attention" for t in types))


def mamba_params(m: Dict) -> int:
    """One Mamba-2 mixer: in_proj [d, 2 inner + 2 G N + heads], the
    depthwise convolution with its bias, out_proj, the gated norm, A_log, D
    and dt_bias."""
    d, heads = m["d_model"], m["mamba_n_heads"]
    return (d * (inner(m) + conv_channels(m) + heads)
            + conv_channels(m) * (m["mamba_d_conv"] + 1)
            + inner(m) * d + inner(m) + 3 * heads)


def attention_params(m: Dict) -> int:
    d, hd = m["d_model"], m["head_dim"]
    return d * m["n_heads"] * hd * 2 + d * m["n_kv_heads"] * hd * 2


def mlp_params(m: Dict) -> int:
    return 3 * m["d_model"] * m["d_ff"]


def table_params(m: Dict) -> int:
    return m["d_model"] * m["vocab_size"]


def params_held(m: Dict, n_layers: int) -> int:
    """Every parameter but the 2 norm scales a layer and the last norm
    (tied table: counted once)."""
    n_ssm, n_attn = kinds(m, n_layers)
    return (n_ssm * mamba_params(m) + n_attn * attention_params(m)
            + n_layers * mlp_params(m) + table_params(m))


def state_bytes_per_row(m: Dict) -> int:
    """One sequence's recurrent state in one Mamba layer, float32."""
    return (m["mamba_n_heads"] * m["mamba_d_head"] * m["mamba_d_state"]
            * F32_BYTES)


def state_update_bytes(m: Dict, rows: int, n_layers=None) -> float:
    """The bytes a decode step's state update must move for `rows` slot
    rows: every Mamba layer's state of every row read once and written
    once (the row's x, B, C, dt and y are under 1% of it and left out)."""
    n_ssm, _ = kinds(m, n_layers or m["n_layers"])
    return float(rows) * n_ssm * 2 * state_bytes_per_row(m)


def scan_flops(m: Dict, tokens: int, n_layers=None) -> float:
    """The operations of the chunked scan over `tokens` tokens in blocks
    of `mamba_chunk_size`, in every Mamba layer: a token's C . B of its
    block and the block's decayed scores times x (the quadratic form),
    what the block adds to the state (B^T x) and what the state at the
    block's start gives the token (C . S)."""
    n_ssm, _ = kinds(m, n_layers or m["n_layers"])
    q, n, g = m["mamba_chunk_size"], m["mamba_d_state"], m["mamba_n_groups"]
    per_token = 2.0 * q * n * g + 2.0 * q * inner(m) + 4.0 * inner(m) * n
    return float(tokens) * n_ssm * per_token


def scan_bytes(m: Dict, tokens: int, calls: int, n_layers=None) -> float:
    """The bytes the scan of `calls` chunks holding `tokens` tokens must
    move in every Mamba layer: x, B, C and dt read and y written once a
    token (float32, as the convolution leaves them), the sequence's state
    read and written once a chunk."""
    n_ssm, _ = kinds(m, n_layers or m["n_layers"])
    per_token = (inner(m) + conv_channels(m) + m["mamba_n_heads"]) * F32_BYTES
    return n_ssm * (float(tokens) * per_token
                    + 2.0 * calls * state_bytes_per_row(m))


def forward_flops_per_token(m: Dict, n_layers: int, context: float) -> float:
    """One token's forward pass, attending over `context` keys in the
    attention layers and scanned in blocks of min(mamba_chunk_size,
    2 context) in the Mamba layers."""
    n_ssm, n_attn = kinds(m, n_layers)
    matrix = 2.0 * (n_ssm * (mamba_params(m) - inner(m) - 3 * m["mamba_n_heads"])
                    + n_attn * attention_params(m) + n_layers * mlp_params(m)
                    + table_params(m))
    attention = n_attn * 4.0 * context * m["n_heads"] * m["head_dim"]
    block = dict(m, mamba_chunk_size=min(m["mamba_chunk_size"],
                                         max(1, int(2 * context))))
    return matrix + attention + scan_flops(block, 1, n_layers)


def train_flops_per_token(m: Dict, n_layers: int, seq: int) -> float:
    """Forward and backward (twice the forward) of a causal sequence of
    `seq` tokens, per token; recomputation not counted."""
    return 3.0 * forward_flops_per_token(m, n_layers, seq / 2.0)
