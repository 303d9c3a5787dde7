"""Plain reference of the Granite 4.0 hybrid decoder (`model_type`
granitemoehybrid: the public config and modelling code of
granite-4.0-h-micro; its state-space layers are Mamba-2, arXiv:2405.21060):
the forward pass, the next-token loss and, through `jax.grad`, its
gradients, in straightforward `jax.numpy`, float32,
`jax.default_matmul_precision("highest")`. No kernels, no cache, no chunked
form: the recurrence is a plain `lax.scan` over tokens. Nothing is imported
from the program: it takes the sizes as a plain dict and the weights as a
tree of arrays named as the program names them (`layers/ssm/*` one entry a
Mamba layer, `layers/attn/*` one an attention layer, `layers/mlp/*` one a
layer).

    x = embed[tokens] * embedding_multiplier
    every layer i, of kind layer_types[i] (`layer_pattern` in `dims`):
        x = x + residual_multiplier * mixer(rmsnorm(x, its input norm))
        x = x + residual_multiplier * (silu(h Wgate) * (h Wup)) Wdown,
                                        h = rmsnorm(x, mlp_norm)
    logits = rmsnorm(x, final_norm) embed^T / logits_scaling        (tied)

The attention mixer: q, k, v = h Wq, h Wk, h Wv in heads of head_dim, NO
position embedding ("nope"; rope where a configuration says so), a =
softmax(q k^T * attention_multiplier + causal mask) v, each KV head serving
n_heads / n_kv_heads queries; a Wo.

The Mamba-2 mixer, d_inner = mamba_n_heads * mamba_d_head, G groups, N =
mamba_d_state:

    [z | xBC | dt] = h [Win | Wdt]  (d_inner | d_inner + 2 G N | heads)
    xBC = silu(conv(xBC) + b_conv)  depthwise, causal, mamba_d_conv taps:
                                    out[t] = sum_k w[:, k] xBC[t - K + 1 + k]
    [x | B | C] = xBC               (d_inner | G N | G N); a head uses the
                                    B, C of its group
    dt = softplus(dt + dt_bias);  A = -exp(A_log)          a head each
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T             S [d_head, N]
    y_t = S_t C_t + D x_t
    y = rmsnorm(y * silu(z), gate_norm)   over each group's d_inner / G
    out = y Wout

rmsnorm(x, w) = x / sqrt(mean(x^2) + eps) * w.

Departures from the published model, each noted in the configuration file:
weights are random from a seed; the context is whatever sequence is passed;
`num_local_experts` is 0 in the published config, so there is no routed
part beside the shared MLP; the published `time_step_limit` (0, inf) clamps
nothing and is absent; `mamba_proj_bias` false, so the two projections have
no bias (a tree that has `b_in`, `b_dt`, `b_out` gets them added); the
program holds the published `in_proj`'s last `heads` columns, dt's, as a
leaf of their own (`w_dt` beside `w_in`), and they are joined here.

Weights arrive in the dtype the system holds them in and are upcast here,
one layer at a time where memory matters (`hidden_layerwise`).
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp

from reference.draws import normal, ones

F32 = jnp.float32


# Mamba-2's published initialisation: A uniform in [1, 16] kept as its
# log, dt log-uniform in [0.001, 0.1] kept as its inverse softplus, the
# depthwise convolution (weights and bias, a torch Conv1d's default)
# uniform in +-1/sqrt(taps); D and every norm scale ones; every matrix
# normal at d_model ** -0.5, the tied table among them.
def log_of_uniform(key, shape, lo, hi):
    return jnp.log(jax.random.uniform(key, shape, F32, lo, hi))


def inverse_softplus_of_log_uniform(key, shape, lo, hi):
    dt = jnp.exp(jax.random.uniform(key, shape, F32, jnp.log(lo), jnp.log(hi)))
    return dt + jnp.log(-jnp.expm1(-dt))


def uniform(key, shape, bound):
    return jax.random.uniform(key, shape, F32, -bound, bound)


_MATRICES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "w_in",
             "w_dt", "w_out", "lm_head")


def leaf_init(path, m: Dict):
    """The rule by which bench/weights.py draws the leaf at `path`, the
    tuple of keys from the root of the program's parameter tree; `m` is
    `dims` and `tie_embeddings`."""
    name = path[-1]
    if name in _MATRICES:
        return (normal, m["d_model"] ** -0.5)
    if name == "embed":
        return (normal, m["d_model"] ** -0.5 if m["tie_embeddings"] else 1.0)
    if name == "a_log":
        return (log_of_uniform, 1.0, 16.0)
    if name == "dt_bias":
        return (inverse_softplus_of_log_uniform, 0.001, 0.1)
    if name in ("conv_w", "conv_b"):
        return (uniform, m["mamba_d_conv"] ** -0.5)
    return (ones,)


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x [T, H, hd]; position t rotates pair (i, i + hd/2) by
    t * theta**(-2i/hd)."""
    t, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention_mixer(y, lp: Dict, m: Dict):
    """The attention mixer on one sequence's normed activations y [T, d]."""
    t = y.shape[0]
    h, kvh, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    q = (y @ lp["wq"]).reshape(t, h, hd)
    k = (y @ lp["wk"]).reshape(t, kvh, hd)
    v = (y @ lp["wv"]).reshape(t, kvh, hd)
    if m["position_embedding_type"] == "rope":
        q, k = _rope(q, m["rope_theta"]), _rope(k, m["rope_theta"])
    k = jnp.repeat(k, h // kvh, axis=1)
    v = jnp.repeat(v, h // kvh, axis=1)
    scale = m["attention_multiplier"] or hd ** -0.5
    scores = jnp.einsum("qhd,khd->hqk", q, k) * scale
    causal = jnp.tril(jnp.ones((t, t), dtype=bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    return a.reshape(t, h * hd) @ lp["wo"]


def mamba_mixer(y, lp: Dict, m: Dict):
    """The Mamba-2 mixer on one sequence's normed activations y [T, d],
    from a zero state, one token after another."""
    t = y.shape[0]
    heads, p, n, g, taps = (m["mamba_n_heads"], m["mamba_d_head"],
                            m["mamba_d_state"], m["mamba_n_groups"],
                            m["mamba_d_conv"])
    inner, bc = heads * p, g * n
    proj = y @ jnp.concatenate([lp["w_in"], lp["w_dt"]], axis=1)
    if "b_in" in lp:
        proj = proj + jnp.concatenate([lp["b_in"], lp["b_dt"]])
    z, xbc, dt = jnp.split(proj, [inner, 2 * inner + 2 * bc], axis=-1)
    behind = jnp.concatenate([jnp.zeros((taps - 1, xbc.shape[1]), F32), xbc])
    conv = sum(behind[k:k + t] * lp["conv_w"][:, k] for k in range(taps))
    if "conv_b" in lp:
        conv = conv + lp["conv_b"]
    x, b, c = jnp.split(jax.nn.silu(conv), [inner, inner + bc], axis=-1)
    x = x.reshape(t, heads, p)
    b = jnp.repeat(b.reshape(t, g, n), heads // g, axis=1)      # [T, H, N]
    c = jnp.repeat(c.reshape(t, g, n), heads // g, axis=1)
    dt = jax.nn.softplus(dt + lp["dt_bias"])                     # [T, H]
    a = -jnp.exp(lp["a_log"])                                    # [H]

    def token(state, row):
        x_t, b_t, c_t, dt_t = row
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        y_t = jnp.einsum("hpn,hn->hp", state, c_t) + lp["d_skip"][:, None] * x_t
        return state, y_t

    _, ys = jax.lax.scan(token, jnp.zeros((heads, p, n), F32), (x, b, c, dt))
    gated = (ys.reshape(t, inner) * jax.nn.silu(z)).reshape(t, g, inner // g)
    normed = gated * jax.lax.rsqrt(
        jnp.mean(gated * gated, axis=-1, keepdims=True) + m["norm_eps"])
    out = (normed.reshape(t, inner) * lp["gate_norm"]) @ lp["w_out"]
    return out + lp["b_out"] if "b_out" in lp else out


def layer(x, kind: str, mixer_lp: Dict, mlp: Dict, m: Dict):
    """One decoder layer of `kind` on one sequence x [T, d], float32
    weights: its mixer's leaves and its MLP's."""
    eps, res = m["norm_eps"], m["residual_multiplier"]
    if kind == "mamba":
        mixed = mamba_mixer(_rmsnorm(x, mixer_lp["norm"], eps), mixer_lp, m)
    else:
        mixed = attention_mixer(_rmsnorm(x, mixer_lp["attn_norm"], eps),
                                mixer_lp, m)
    x = x + res * mixed
    y = _rmsnorm(x, mlp["mlp_norm"], eps)
    return x + res * (
        (jax.nn.silu(y @ mlp["w_gate"]) * (y @ mlp["w_up"])) @ mlp["w_down"])


def _layers(m: Dict):
    """(kind, stack, index within the stack) of every layer in order."""
    seen = {"mamba": 0, "attention": 0}
    for kind in m["layer_pattern"]:
        yield kind, "ssm" if kind == "mamba" else "attn", seen[kind]
        seen[kind] += 1


def _at(stack: Dict, i: int):
    return jax.tree.map(lambda a: a[i], stack)


def _head(params: Dict):
    """The output head as a [vocab, d] table: the embedding itself when
    tied (the published model), else the separate head transposed."""
    return params["lm_head"].T if "lm_head" in params else params["embed"]


def _upcast(tree):
    return jax.tree.map(lambda a: a.astype(F32), tree)


def loss(params: Dict, tokens, m: Dict):
    """Mean next-token cross-entropy of one sequence `tokens` [T + 1],
    differentiable in `params` (float32); the layers one after another."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens[:-1]] * m["embedding_multiplier"]
        for i, (kind, stack, j) in enumerate(_layers(m)):
            x = layer(x, kind, _at(params["layers"][stack], j),
                      _at(params["layers"]["mlp"], i), m)
        x = _rmsnorm(x, params["final_norm"], m["norm_eps"])
        logits = x @ _head(params).T / m["logits_scaling"]
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, tokens[1:, None], axis=-1))


def _dims(m: Dict):
    return tuple(sorted(m.items()))


@functools.partial(jax.jit, static_argnames=("dims",))
def _loss_and_grads(params, tokens, dims):
    return jax.value_and_grad(
        lambda p: loss(p, tokens, dict(dims)))(_upcast(params))


def loss_and_grads(params: Dict, tokens, m: Dict):
    """Reference loss and gradients on the system's weights upcast whole
    (for a configuration whose float32 copy and gradients fit); loss and
    gradients are float32."""
    return _loss_and_grads(params, tokens, _dims(m))


@functools.partial(jax.jit, static_argnames=("kind", "dims"))
def _layer_upcast(x, mixer_lp, mlp, kind, dims):
    with jax.default_matmul_precision("highest"):
        return layer(x, kind, _upcast(mixer_lp), _upcast(mlp), dict(dims))


@jax.jit
def _logits_block(rows, table):
    with jax.default_matmul_precision("highest"):
        return rows @ table.astype(F32).T


def hidden_layerwise(params: Dict, tokens, m: Dict):
    """Final-norm hidden states [T, d] of one sequence, upcasting one
    layer's weights at a time (the model never exists in float32). One
    program a kind of layer serves every layer of that kind."""
    x = params["embed"][tokens].astype(F32) * m["embedding_multiplier"]
    for i, (kind, stack, j) in enumerate(_layers(m)):
        x = _layer_upcast(x, _at(params["layers"][stack], j),
                          _at(params["layers"]["mlp"], i), kind, _dims(m))
    return _rmsnorm(x, params["final_norm"].astype(F32), m["norm_eps"])


def logits_rows(params: Dict, hidden_rows, m: Dict, chunk: int = 16384):
    """Logits [R, vocab] of a few hidden rows, the table upcast a block of
    rows at a time."""
    table = _head(params)
    return jnp.concatenate(
        [_logits_block(hidden_rows, table[i:i + chunk])
         for i in range(0, table.shape[0], chunk)],
        axis=-1) / m["logits_scaling"]


def loss_layerwise(params: Dict, tokens, m: Dict, rows: int = 128):
    """`loss` without gradients and without a float32 copy of the model
    (for a configuration whose float32 state does not fit)."""
    x = hidden_layerwise(params, tokens[:-1], m)
    total = 0.0
    for i in range(0, x.shape[0], rows):
        logp = jax.nn.log_softmax(logits_rows(params, x[i:i + rows], m), -1)
        total += float(-jnp.sum(jnp.take_along_axis(
            logp, tokens[1 + i:1 + i + rows, None], axis=-1)))
    return total / x.shape[0]
