"""Operations and bytes from shapes, and the table of peaks: the
benchmark's own arithmetic, so that no PR that claims a gain can move it.

Counts are what the algorithm needs, not what the program executes:
recomputation under `remat` and the chunked loss's second head product are
not counted, norms, rotary embedding and softmax are left out (under 1% at
these widths), and causal attention counts the half of the score matrix it
needs. A multiply-add is two operations.
"""

from __future__ import annotations

import json
import os
from typing import Dict

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> Dict:
    """Published peaks of one chip, by what the chip calls itself."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}: add it to "
            "bench/peaks.json with its source")
    return table[device_kind]


def layer_matrix_params(m: Dict) -> int:
    """Matrix parameters of one dense layer (every one is used by every
    token): q, k, v, o projections and the gated MLP's three."""
    d, hd = m["d_model"], m["head_dim"]
    attn = d * m["n_heads"] * hd * 2 + d * m["n_kv_heads"] * hd * 2
    return attn + 3 * d * m["d_ff"]


def head_params(m: Dict) -> int:
    """The output head (the tied table read as [d, vocab])."""
    return m["d_model"] * m["vocab_size"]


def forward_flops_per_token(m: Dict, n_layers: int, context: float) -> float:
    """One token's forward pass attending over `context` keys (for a causal
    sequence of S tokens the mean context is S / 2)."""
    matrix = 2.0 * (n_layers * layer_matrix_params(m) + head_params(m))
    attention = n_layers * 4.0 * context * m["n_heads"] * m["head_dim"]
    return matrix + attention


def train_flops_per_token(m: Dict, n_layers: int, seq: int) -> float:
    """Forward and backward (twice the forward) of a causal sequence of
    `seq` tokens, per token; recomputation not counted."""
    return 3.0 * forward_flops_per_token(m, n_layers, seq / 2.0)


def flash_forward(m: Dict, batch: int, seq: int) -> Dict:
    """Causal attention forward over [batch, seq] for one layer: the
    operations it needs and the bytes it must move (q, k, v read once and
    the output written once, in bf16; grouped-query K and V counted at
    their own width, not repeated)."""
    h, kvh, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    flops = 4.0 * batch * h * hd * seq * seq / 2.0
    nbytes = 2.0 * batch * seq * hd * (2 * h + 2 * kvh)
    return {"flops": flops, "bytes": nbytes}
