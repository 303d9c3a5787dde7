"""Linear attention by the channel-gated delta rule (Kimi Linear's KDA,
arXiv:2510.26692, as `model_type` solar_open2 uses it among gated NoPE
attention layers): a recurrent layer whose state is a matrix a head,
decayed a channel at a time and corrected by a rank-one update a token.

For normed activations `h [B, L, d]`, per head (`kda_num_heads` heads of
`kda_head_dim` = K = V wide, `S [K, V]` float32):

    [q | k | v] = silu(causal_depthwise_conv(h W_qkv))   (three blocks of
                                          heads * K columns: the published
                                          q_proj, k_proj, v_proj and their
                                          three convolutions, no bias)
    q = q / |q| * K ** -0.5;  k = k / |k|               (an L2 norm a head)
    g = -exp(A_log) * softplus((h W_fa) W_fb + dt_bias) ([K] a head: a
                                          log-decay a channel, A_log a head)
    beta = 2 * sigmoid(h W_beta)          (a scalar a head; the 2 is
                                           `kda_allow_neg_eigval`, else 1)
    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t
    out = (rmsnorm_head(o_t) * w_norm * sigmoid((h W_ga) W_gb)) W_out

The recurrence exists in three forms that compute the same thing, one
function each: `kda_scan` (the recurrence as written, a scan over tokens:
what the tests hold the others to), `kda_chunked` (a whole sequence or a
prefill chunk in blocks of `BLOCK` tokens: the delta rule's triangular
solve inside a block, the state handed from block to block, an initial
state taken and the final one returned) and `kda_step` (one token).
`mixer` is the one entry point of training, the prefill chunk and the
decode step; it takes the form by the static length of what it is given,
as `mamba2.mixer` does.

The one-token form has two homes, as Mamba-2's has, and who takes which
goes by what the caller holds. A caller with a bare state array `[B, H, K,
V]` (the cached forward, a test, the kernel's reference) gets `kda_step`
here, the plain form: the compiler makes two fusions of it, one that reads
the state for both sums over it and one that reads it again to write it. A
caller with the whole recurrent pool `[layers, B, H, K, V]` and a layer's
index (the engine's decode program, `paged_kv.decode_paged` through
`_walk_hybrid`) passes both to `mixer` and gets `ops.kda_update`: on the
chip a kernel that passes over that layer's rows once each way, where they
lie in the pool; elsewhere `kda_step` on the layer sliced out and set back.

Inside a block a decay is always `exp(G_i - G_j)` of log-decays summed
from the block's start, with `i >= j`, so its exponent is never positive:
taken a channel at a time inside the sums that make the block's two score
matrices, never as `exp(G_i) / exp(G_j)`, whose divisor underflows float32
where a channel decays hard over a block.

What lives from call to call is a row of fixed size a sequence: the state
`[heads, K, V]` in float32 (it accumulates over the sequence's whole life)
and the convolution's last `kernel - 1` inputs `[kernel - 1, 3 * heads *
K]` in the activations' dtype. `n_valid [B]` says how many of the `L` rows
of each sequence are real: the rest get `g = 0` and `beta = 0`, which
leaves the state bit for bit (`1 * S + 0`), and stay out of the saved
convolution inputs. A decode step passes its active mask as 0 or 1.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from ray_tpu.models import mamba2
from ray_tpu.ops.kda_update import kda_update

F32 = jnp.float32
# Tokens of a block of the chunked form.
BLOCK = 64
# Added to a head's sum of squares under the L2 norm of q and k.
L2_EPS = 1e-6
_EXACT = jax.lax.Precision.HIGHEST
# A sequence's rows in the pool, in the order `mixer` takes and returns them.
ROWS = ("state", "conv")
# Those of them that `mixer` given `layer=` takes as the whole pool `[layers,
# ...]` and advances where they lie.
IN_POOL = ("state",)


def inner(cfg) -> int:
    return cfg.kda_num_heads * cfg.kda_head_dim


def init_state(cfg, layers: int, rows: int) -> Dict:
    """The recurrent pool of `layers` delta-rule layers and `rows`
    sequences, zeros: `state [layers, rows, H, K, V]` float32, `conv
    [layers, rows, kernel - 1, 3 H K]` in the model's dtype."""
    heads, dk = cfg.kda_num_heads, cfg.kda_head_dim
    return {
        "state": jnp.zeros((layers, rows, heads, dk, dk), F32),
        "conv": jnp.zeros((layers, rows, cfg.kda_short_conv_kernel_size - 1,
                           3 * inner(cfg)), cfg.dtype),
    }


def kda_scan(q, k, v, g, beta, state):
    """The recurrence as written, a token at a time. q, k, g `[B, L, H,
    K]`, v `[B, L, H, V]`, beta `[B, L, H]` (g and beta 0 on rows that
    must not advance the state), state `[B, H, K, V]`: all float32.
    Returns o `[B, L, H, V]` and the state after row L."""
    def token(s, row):
        q_t, k_t, v_t, g_t, beta_t = row
        s = jnp.exp(g_t)[..., None] * s
        u = jnp.sum(k_t[..., None] * s, axis=-2)
        s = s + (beta_t[..., None] * k_t)[..., None] * (v_t - u)[..., None, :]
        return s, jnp.sum(q_t[..., None] * s, axis=-2)

    state, o = jax.lax.scan(
        token, state, tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), state


def kda_step(q, k, v, g, beta, state):
    """The one-token form. q, k, g `[B, H, K]`, v `[B, H, V]`, beta `[B,
    H]` (g and beta 0 where the row must keep its state), state `[B, H, K,
    V]`: all float32. Returns o `[B, H, V]` and the state.

    `S~ = Diag(exp(g)) S; u = S~^T k; S' = S~ + beta k (v - u)^T`, and
    `o = S'^T q` taken as `S~^T q + beta (k . q) (v - u)`: both sums over
    the state then read `S~` and neither waits for `S'`."""
    with jax.named_scope("kda.update"):
        decayed = jnp.exp(g)[..., None] * state
        u = jnp.sum(k[..., None] * decayed, axis=-2)
        from_old = jnp.sum(q[..., None] * decayed, axis=-2)
        delta = beta[..., None] * (v - u)                    # [B, H, V]
        state = decayed + k[..., None] * delta[..., None, :]
        o = from_old + jnp.sum(k * q, axis=-1, keepdims=True) * delta
        return o, state


def kda_chunked(q, k, v, g, beta, state):
    """The chunked form, shapes as `kda_scan`'s. Within a block of `C`
    rows from the state `S_0` at its start, with `G_i` the log-decays
    summed up to row i and `w_i = beta_i (v_i - (Diag(exp(g_i)) S_{i-1})^T
    k_i)` the row's correction:

        S_i = Diag(exp(G_i)) S_0 + sum_{j <= i} Diag(exp(G_i - G_j)) k_j w_j^T
        (I + Diag(beta) A) W = Diag(beta) (V - (K * exp(G)) S_0),
            A_ij = sum_c k_ic k_jc exp(G_ic - G_jc),  j < i
        o_i = S_0^T (q_i * exp(G_i)) + sum_{j <= i} B_ij w_j,
            B_ij = sum_c q_ic k_jc exp(G_ic - G_jc)

    The solve is taken once a block for both right-hand sides (`V` and `K *
    exp(G)`: `W` is linear in `S_0`); a short scan over blocks hands the
    state on. Products that touch the state are float32 in full (the
    state's 24 bits are what a sequence's whole life accumulates in)."""
    with jax.named_scope("kda.scan"):
        bsz, length, heads, dk = q.shape
        dv = v.shape[-1]
        c = min(BLOCK, length)
        pad = -length % c
        if pad:
            q, k, v, g, beta = (
                jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                for t in (q, k, v, g, beta))
        nc = (length + pad) // c

        def blocks(t):                       # [B, L, H, x] -> [B, nc, H, C, x]
            return jnp.moveaxis(t.reshape(bsz, nc, c, heads, -1), 2, 3)

        q, k, v, g, beta = map(blocks, (q, k, v, g, beta))
        g_sum = jnp.cumsum(g, axis=-2)                        # G [.., C, K]
        causal = jnp.tril(jnp.ones((c, c), dtype=bool))
        decay = jnp.exp(jnp.where(
            causal[..., None],
            g_sum[..., :, None, :] - g_sum[..., None, :, :], -jnp.inf))
        k_cols = k[..., None, :, :] * decay                   # [.., C, C, K]
        kk = jnp.sum(k[..., :, None, :] * k_cols, axis=-1)    # A, and i == j
        qk = jnp.sum(q[..., :, None, :] * k_cols, axis=-1)    # B
        lower = jnp.where(jnp.tril(causal, -1), beta * kk, 0.0)
        solved = jax.scipy.linalg.solve_triangular(
            lower + jnp.eye(c, dtype=F32),
            beta * jnp.concatenate([v, k * jnp.exp(g_sum)], axis=-1),
            lower=True, unit_diagonal=True)
        from_v, from_k = solved[..., :dv], solved[..., dv:]   # [.., C, V|K]
        q_in = q * jnp.exp(g_sum)
        g_end = g_sum[..., -1:, :]
        k_out = k * jnp.exp(g_end - g_sum)
        block_decay = jnp.exp(g_end[..., 0, :])               # [B, nc, H, K]

        def hand_on(s, blk):
            from_v, from_k, q_in, qk, k_out, block_decay = blk
            w = from_v - jnp.einsum("bhck,bhkv->bhcv", from_k, s,
                                    precision=_EXACT)
            o = (jnp.einsum("bhck,bhkv->bhcv", q_in, s, precision=_EXACT)
                 + jnp.einsum("bhij,bhjv->bhiv", qk, w, precision=_EXACT))
            s = block_decay[..., None] * s + jnp.einsum(
                "bhck,bhcv->bhkv", k_out, w, precision=_EXACT)
            return s, o

        state, o = jax.lax.scan(
            hand_on, state, tuple(jnp.moveaxis(t, 1, 0) for t in (
                from_v, from_k, q_in, qk, k_out, block_decay)))
        o = jnp.moveaxis(o, 0, 1)                             # [B, nc, H, C, V]
        o = jnp.moveaxis(o, 2, 3).reshape(bsz, nc * c, heads, dv)
        return o[:, :length], state


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def gate_norm(o, h, lp: Dict, cfg):
    """rmsnorm over each head's width of `o [B, L, H, V]`, times `w_norm
    [V]` and the sigmoid gate of `h` through its low-rank pair: `[B, L, H *
    V]` float32."""
    with jax.named_scope("kda.gate_norm"):
        o = o * jax.lax.rsqrt(
            jnp.mean(o * o, axis=-1, keepdims=True) + cfg.norm_eps)
        o = (o * lp["gate_norm"].astype(F32)).reshape(*o.shape[:2], -1)
        return o * jax.nn.sigmoid(((h @ lp["w_ga"]) @ lp["w_gb"]).astype(F32))


def mixer(h, lp: Dict, cfg, state, conv, n_valid, layer=None):
    """The whole mixer on normed activations `h [B, L, d]` from `state [B,
    H, K, V]` float32 and the saved convolution inputs `conv [B, kernel -
    1, 3 H K]`; `n_valid [B]` rows of each sequence are real. Returns the
    mixer's output `[B, L, d]` in h's dtype, the state and the convolution
    inputs after the last real row. One token (L == 1) takes the one-token
    form, anything longer the chunked one.

    With `layer`, a scalar, `state` is the whole recurrent pool `[layers,
    B, H, K, V]` and the pool comes back, that layer's rows advanced where
    they lie (`ops.kda_update`): one token only."""
    bsz, length, _ = h.shape
    heads, dk = cfg.kda_num_heads, cfg.kda_head_dim
    with jax.named_scope("kda.conv"):
        qkv, conv = mamba2.depthwise_conv(h @ lp["w_qkv"], conv, lp, n_valid)
        q, k, v = (t.reshape(bsz, length, heads, dk)
                   for t in jnp.split(jax.nn.silu(qkv), 3, axis=-1))
    with jax.named_scope("kda.gates"):
        q, k = _l2norm(q) * dk ** -0.5, _l2norm(k)
        real = (jnp.arange(length)[None, :] < n_valid[:, None])[..., None]
        rate = jax.nn.softplus(
            ((h @ lp["w_fa"]) @ lp["w_fb"]).astype(F32)
            + lp["dt_bias"].astype(F32)).reshape(bsz, length, heads, dk)
        g = jnp.where(real[..., None],
                      -jnp.exp(lp["a_log"].astype(F32))[:, None] * rate, 0.0)
        beta = jnp.where(
            real, (2.0 if cfg.kda_allow_neg_eigval else 1.0)
            * jax.nn.sigmoid((h @ lp["w_beta"]).astype(F32)), 0.0)
    if layer is not None:
        assert length == 1, "a pool and a layer: the one-token form"
        o, state = kda_update(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0],
                              state, layer)
        o = o[:, None]
    elif length == 1:
        o, state = kda_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0],
                            state)
        o = o[:, None]
    else:
        o, state = kda_chunked(q, k, v, g, beta, state)
    return gate_norm(o, h, lp, cfg).astype(h.dtype) @ lp["w_out"], state, conv
