"""The Mamba-2 mixer (arXiv:2405.21060): a state-space layer with a
scalar decay a head, as the hybrid decoders use it among attention layers.

For normed activations `h [B, L, d]` and a head's state `S [P, N]`:

    [z | xBC] = h W_in;  dt = h W_dt      (d_inner + conv_dim | heads columns:
                                           the published in_proj, its dt
                                           columns kept as a leaf of their own)
    xBC = silu(causal_depthwise_conv(xBC) + b_conv)
    [x | B | C] = xBC                     (d_inner + groups*N + groups*N)
    dt = softplus(dt + dt_bias);  A = -exp(A_log)
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T;   y_t = S_t C_t + D x_t
    out = (rmsnorm(y * silu(z)) * w_norm) W_out

The recurrence exists in two forms that compute the same thing, one
function each: `ssd_chunked` (a whole sequence or a prefill chunk: the
quadratic form inside blocks of `mamba_chunk_size`, the state handed from
block to block, an initial state taken and the final one returned) and
`ssd_step` (one token: the state advanced in place). `mixer` is the one
entry point of training, generation, the prefill chunk and the decode
step; it takes the form by the static length of what it is given.

The one-token form has two homes, and who takes which goes by what the
caller holds. A caller with a bare state array `[B, H, P, N]` (the cached
forward, a test, the kernel's reference) gets `ssd_step` here, the plain
form: the compiler makes two fusions of it, one that writes the state and
one that reads it again for `y`. A caller with the whole recurrent pool
`[layers, B, H, P, N]` and a layer's index (the engine's decode program,
`paged_kv.decode_paged` through `_walk_hybrid`) passes both to `mixer`
and gets `ops.ssm_update`: on the chip a kernel that passes over that
layer's rows once, where they lie in the pool; elsewhere `ssd_step` on
the layer sliced out and set back.

What lives from call to call is a row of fixed size a sequence: the state
`[heads, d_head, d_state]` in float32 (it accumulates over the sequence's
whole life) and the convolution's last `d_conv - 1` inputs `[d_conv - 1,
conv_dim]` in the activations' dtype. `n_valid [B]` says how many of the
`L` rows of each sequence are real: the rest get `dt = 0`, which leaves
the state exactly as it was (`exp(0) S + 0`), and stay out of the saved
convolution inputs. A decode step passes its active mask as 0 or 1.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.ops.ssm_update import ssm_update

F32 = jnp.float32


def d_inner(cfg) -> int:
    return cfg.mamba_n_heads * cfg.mamba_d_head


def conv_dim(cfg) -> int:
    return d_inner(cfg) + 2 * cfg.mamba_n_groups * cfg.mamba_d_state


def in_proj_dim(cfg) -> int:
    """`w_in`'s columns, [z | xBC]. The published `in_proj` has `heads`
    more, dt's, which are the leaf `w_dt`: 8512 columns are 66.5 tiles of
    128 lanes, and the compiler then copies the whole stack of layers
    into another tiling at every call of a step program (1.25 GB at
    granite-4.0-h-micro, seen in the program compiled for the v5e)."""
    return d_inner(cfg) + conv_dim(cfg)


# A sequence's rows in the pool, in the order `mixer` takes and returns them.
ROWS = ("state", "conv")
# Those of them that `mixer` given `layer=` takes as the whole pool `[layers,
# ...]` and advances where they lie.
IN_POOL = ("state",)


def init_state(cfg, layers: int, rows: int) -> Dict:
    """The recurrent pool of `layers` Mamba layers and `rows` sequences,
    zeros: `state [layers, rows, H, P, N]` float32, `conv [layers, rows,
    K-1, C]` in the model's dtype."""
    return {
        "state": jnp.zeros((layers, rows, cfg.mamba_n_heads, cfg.mamba_d_head,
                            cfg.mamba_d_state), F32),
        "conv": jnp.zeros((layers, rows, cfg.mamba_d_conv - 1, conv_dim(cfg)),
                          cfg.dtype),
    }


def depthwise_conv(x, prev, lp, n_valid) -> Tuple[jax.Array, jax.Array]:
    """The depthwise causal convolution of `x [B, L, C]` behind the
    sequence's last inputs `prev [B, K-1, C]`, float32 (with `lp["conv_b"]`
    where there is one, and no activation), and the inputs to keep for the
    next call: the K-1 rows before row `n_valid [B]` of the joined stream
    (the old ones where `n_valid` is 0). The gated short convolution
    (models/shortconv.py) is this as it stands."""
    k1 = prev.shape[1]
    length = x.shape[1]
    stream = jnp.concatenate([prev.astype(x.dtype), x], axis=1)
    w = lp["conv_w"].astype(F32)                           # [C, K]
    out = sum(stream[:, k:k + length].astype(F32) * w[:, k]
              for k in range(k1 + 1))
    if "conv_b" in lp:
        out = out + lp["conv_b"].astype(F32)
    keep = jax.vmap(
        lambda s, n: jax.lax.dynamic_slice_in_dim(s, n, k1, axis=0)
    )(stream, n_valid.astype(jnp.int32))
    return out, keep.astype(prev.dtype)


def causal_conv(xbc, prev, lp, n_valid) -> Tuple[jax.Array, jax.Array]:
    """silu(`depthwise_conv`) of `xbc [B, L, C]`, and the inputs to keep."""
    with jax.named_scope("ssm.conv"):
        out, keep = depthwise_conv(xbc, prev, lp, n_valid)
        return jax.nn.silu(out), keep


def ssd_chunked(x, dt, a, b, c, state, chunk: int):
    """The chunked form. x [B, L, H, P], dt [B, L, H] (0 on rows that must
    not advance the state), a [H] negative, b and c [B, L, G, N], state
    [B, H, P, N]: all float32. Returns y [B, L, H, P] (without the skip
    term) and the state after row L."""
    with jax.named_scope("ssm.scan"):
        bsz, length, heads, p = x.shape
        g, n = b.shape[2:]
        e = heads // g
        q = min(chunk, length)
        pad = -length % q
        if pad:
            x, dt, b, c = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                           for t in (x, dt, b, c))
        nc = (length + pad) // q
        x = x.reshape(bsz, nc, q, g, e, p)
        b = b.reshape(bsz, nc, q, g, n)
        c = c.reshape(bsz, nc, q, g, n)
        dt = dt.reshape(bsz, nc, q, g, e)
        xdt = x * dt[..., None]
        # Log-decay summed from a block's start to each of its rows.
        a_cs = jnp.cumsum(jnp.moveaxis(dt, 2, -1) * a.reshape(g, e, 1),
                          axis=-1)                           # [B,nc,G,E,Q]
        rows_last = lambda t: jnp.moveaxis(t, -1, 2)[..., None]  # [B,nc,Q,G,E,1]
        # Inside a block: row i takes row j <= i through C_i . B_j, decayed
        # from j to i.
        scores = jnp.einsum("bcign,bcjgn->bcgij", c, b)
        causal = jnp.tril(jnp.ones((q, q), dtype=bool))
        decay = jnp.exp(jnp.where(
            causal, a_cs[..., :, None] - a_cs[..., None, :], -jnp.inf))
        y = jnp.einsum("bcgeij,bcjgep->bcigep", scores[:, :, :, None] * decay,
                       xdt)
        # What each block adds to the state by its end, and the state at
        # each block's start (a short scan over blocks).
        to_end = jnp.exp(a_cs[..., -1:] - a_cs)
        added = jnp.einsum("bcjgn,bcjgep->bcgepn", b, xdt * rows_last(to_end))
        block_decay = jnp.exp(a_cs[..., -1])                 # [B,nc,G,E]
        state = state.reshape(bsz, g, e, p, n)

        def hand_on(s, block):
            decay_c, added_c = block
            return decay_c[..., None, None] * s + added_c, s

        state, starts = jax.lax.scan(
            hand_on, state,
            (jnp.moveaxis(block_decay, 1, 0), jnp.moveaxis(added, 1, 0)))
        starts = jnp.moveaxis(starts, 0, 1)                  # [B,nc,G,E,P,N]
        y = y + (jnp.einsum("bcign,bcgepn->bcigep", c, starts)
                 * rows_last(jnp.exp(a_cs)))
        y = y.reshape(bsz, nc * q, heads, p)[:, :length]
        return y, state.reshape(bsz, heads, p, n)


def ssd_step(x, dt, a, b, c, state):
    """The one-token form. x [B, H, P], dt [B, H] (0 where the row must
    keep its state), a [H], b and c [B, G, N], state [B, H, P, N]: all
    float32. Returns y [B, H, P] (without the skip term) and the state."""
    with jax.named_scope("ssm.update"):
        bsz, heads, p = x.shape
        g, n = b.shape[1:]
        e = heads // g
        bh = jnp.repeat(b, e, axis=1)                        # [B, H, N]
        ch = jnp.repeat(c, e, axis=1)
        state = (jnp.exp(dt * a)[..., None, None] * state
                 + (dt[..., None] * x)[..., None] * bh[:, :, None, :])
        y = jnp.sum(state * ch[:, :, None, :], axis=-1)
        return y, state


def gate_norm(y, z, w, cfg):
    """rmsnorm(y * silu(z)) * w over each of the `mamba_n_groups` groups
    of the inner width."""
    with jax.named_scope("ssm.gate_norm"):
        v = y.astype(F32) * jax.nn.silu(z.astype(F32))
        grouped = v.reshape(*v.shape[:-1], cfg.mamba_n_groups, -1)
        grouped = grouped * jax.lax.rsqrt(
            jnp.mean(grouped * grouped, axis=-1, keepdims=True) + cfg.norm_eps)
        return grouped.reshape(v.shape) * w.astype(F32)


def mixer(h, lp: Dict, cfg, state, conv, n_valid, layer=None):
    """The whole mixer on normed activations `h [B, L, d]` from `state [B,
    H, P, N]` float32 and the saved convolution inputs `conv [B, K-1, C]`;
    `n_valid [B]` rows of each sequence are real. Returns the mixer's
    output `[B, L, d]` in h's dtype, the state and the convolution inputs
    after the last real row. One token (L == 1) takes the one-token form,
    anything longer the chunked one.

    With `layer`, a scalar, `state` is the whole recurrent pool `[layers,
    B, H, P, N]` and the pool comes back, that layer's rows advanced where
    they lie (`ops.ssm_update`): one token only."""
    bsz, length, _ = h.shape
    heads, p, n, g = (cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state,
                      cfg.mamba_n_groups)
    inner = heads * p
    proj, dt = h @ lp["w_in"], h @ lp["w_dt"]
    if "b_in" in lp:
        proj, dt = proj + lp["b_in"], dt + lp["b_dt"]
    z, xbc = jnp.split(proj, [inner], axis=-1)
    xbc, conv = causal_conv(xbc, conv, lp, n_valid)
    x, b, c = jnp.split(xbc, [inner, inner + g * n], axis=-1)
    x = x.reshape(bsz, length, heads, p)
    b = b.reshape(bsz, length, g, n)
    c = c.reshape(bsz, length, g, n)
    real = jnp.arange(length)[None, :] < n_valid[:, None]
    dt = jnp.where(real[..., None],
                   jax.nn.softplus(dt.astype(F32) + lp["dt_bias"].astype(F32)),
                   0.0)
    a = -jnp.exp(lp["a_log"].astype(F32))
    if layer is not None:
        assert length == 1, "a pool and a layer: the one-token form"
        y, state = ssm_update(x[:, 0], dt[:, 0], a, b[:, 0], c[:, 0], state,
                              layer)
        y = y[:, None]
    elif length == 1:
        y, state = ssd_step(x[:, 0], dt[:, 0], a, b[:, 0], c[:, 0], state)
        y = y[:, None]
    else:
        y, state = ssd_chunked(x, dt, a, b, c, state, cfg.mamba_chunk_size)
    y = y + lp["d_skip"].astype(F32)[:, None] * x
    y = gate_norm(y.reshape(bsz, length, inner), z, lp["gate_norm"], cfg)
    out = y.astype(h.dtype) @ lp["w_out"]
    if "b_out" in lp:
        out = out + lp["b_out"]
    return out, state, conv
