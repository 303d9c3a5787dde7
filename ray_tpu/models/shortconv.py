"""The gated short convolution (LFM2's mixer, `model_type` lfm2 and
lfm2_moe): a recurrent layer whose whole memory is its last few inputs.

For normed activations `h [B, L, d]`:

    [B | C | u] = h W_in                  (three blocks of d columns: the
                                           published in_proj)
    v = B * u
    y_t = sum_j w[:, j] * v_{t-(K-1)+j}   (depthwise, causal, K taps a
                                           channel, no bias, no activation)
    out = (C * y) W_out

What lives from call to call is `v`'s last `K - 1` rows a sequence, `[K -
1, d]` in the activations' dtype (K is `conv_L_cache`, 3 as published):
no state that accumulates. `mixer` is the one entry point of training (a
whole sequence behind zeros), the prefill chunk (behind the rows the chunk
before it left) and the decode step (one token); the convolution itself is
`mamba2.depthwise_conv`, which the Mamba-2 mixer takes too. `n_valid [B]`
says how many of the `L` rows of each sequence are real: the rows kept are
the `K - 1` before row `n_valid` of the joined stream, so padding advances
nothing and a decode step's idle slot (0) keeps what it had.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import mamba2

F32 = jnp.float32
# A sequence's rows in the pool, in the order `mixer` takes and returns them.
ROWS = ("conv",)
# None of them is advanced where it lies in the pool: `mixer` takes no
# `layer=`, and a walk slices the layer's rows out and sets them back.
IN_POOL = ()


def init_state(cfg, layers: int, rows: int) -> Dict:
    """The recurrent pool of `layers` conv layers and `rows` sequences,
    zeros: `conv [layers, rows, K-1, d]` in the model's dtype and nothing
    else."""
    return {"conv": jnp.zeros(
        (layers, rows, cfg.conv_L_cache - 1, cfg.d_model), cfg.dtype)}


def mixer(h, lp: Dict, cfg, conv, n_valid) -> Tuple[jax.Array, jax.Array]:
    """The whole mixer on normed activations `h [B, L, d]` behind the
    sequence's last gated inputs `conv [B, K-1, d]`; `n_valid [B]` rows of
    each sequence are real. Returns the mixer's output `[B, L, d]` in h's
    dtype and the inputs to keep, those before row `n_valid`."""
    with jax.named_scope("conv.project"):
        b_gate, c_gate, u = jnp.split(h @ lp["w_in"], 3, axis=-1)
        v = b_gate * u
    with jax.named_scope("conv.mix"):
        y, conv = mamba2.depthwise_conv(v, conv, lp, n_valid)
        y = (c_gate.astype(F32) * y).astype(h.dtype)
    return y @ lp["w_out"], conv
