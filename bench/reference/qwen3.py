"""Plain reference of the Qwen3 dense decoder (arXiv:2505.09388, and the
model's public config and modelling code): the forward pass, the next-token
loss and, through `jax.grad`, its gradients, in straightforward
`jax.numpy`, float32, `jax.default_matmul_precision("highest")`. No
kernels, no cache, no batching tricks, and nothing imported from the
program: it takes the sizes as a plain dict and the weights as a tree of
arrays named as the program names them.

One layer, for x [T, d]:

    h = rmsnorm(x, attn_norm)
    q, k, v = h Wq, h Wk, h Wv            split into heads of head_dim
    q, k = rmsnorm(q, q_norm), rmsnorm(k, k_norm)     per head (QK-norm)
    q, k = rope(q), rope(k)               half-split pairs, theta 1e6
    a = softmax(q k^T / sqrt(head_dim) + causal mask) v   each KV head
                                          serves n_heads / n_kv_heads queries
    x = x + a Wo
    h = rmsnorm(x, mlp_norm)
    x = x + (silu(h Wgate) * (h Wup)) Wdown

then a final rmsnorm and the head, which is the embedding table transposed
(tied; a configuration with a separate
head uses that). rmsnorm(x, w) = x / sqrt(mean(x^2) + eps) * w.

Departures from the published model, each the program's own and noted in
the configuration files: weights are random from a seed, and the context is
whatever sequence is passed (no sliding window or YaRN scaling is involved
at these lengths).

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


# The model's initialisation as the program's `init_params` has it: the
# input projections and the head at d**-0.5, the two projections that write
# the residual stream at d**-0.5 * (2L)**-0.5, a tied embedding table at
# the head's scale (PERF.md finding 7) and an untied one at 1, the norm
# scales ones.
_PROJECTIONS = ("wq", "wk", "wv", "w_gate", "w_up", "lm_head")
_RESIDUAL_WRITERS = ("wo", "w_down")


def leaf_init(path, m: Dict):
    """The rule (reference/draws.py) by which bench/weights.py draws the
    leaf at `path`, the tuple of keys from the root of the program's
    parameter tree; `m` is `dims` and `tie_embeddings`."""
    name, base = path[-1], m["d_model"] ** -0.5
    if name in _PROJECTIONS:
        return (normal, base)
    if name in _RESIDUAL_WRITERS:
        return (normal, base * (2 * m["n_layers"]) ** -0.5)
    if name == "embed":
        return (normal, base if m["tie_embeddings"] else 1.0)
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


def layer(x, lp: Dict, m: Dict):
    """One decoder layer on one sequence x [T, d], float32 weights."""
    t = x.shape[0]
    h, kvh, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    eps, theta = m["norm_eps"], m["rope_theta"]
    y = _rmsnorm(x, lp["attn_norm"], eps)
    q = (y @ lp["wq"]).reshape(t, h, hd)
    k = (y @ lp["wk"]).reshape(t, kvh, hd)
    v = (y @ lp["wv"]).reshape(t, kvh, hd)
    q = _rope(_rmsnorm(q, lp["q_norm"], eps), theta)
    k = _rope(_rmsnorm(k, lp["k_norm"], eps), theta)
    k = jnp.repeat(k, h // kvh, axis=1)
    v = jnp.repeat(v, h // kvh, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(F32(hd))
    causal = jnp.tril(jnp.ones((t, t), dtype=bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    x = x + a.reshape(t, h * hd) @ lp["wo"]
    y = _rmsnorm(x, lp["mlp_norm"], eps)
    return x + (jax.nn.silu(y @ lp["w_gate"]) * (y @ lp["w_up"])) @ lp["w_down"]


def _head(params: Dict):
    """The output head as a [vocab, d] table: the embedding itself when
    tied (the published model), else the separate head transposed."""
    return params["lm_head"].T if "lm_head" in params else params["embed"]


def _upcast(tree):
    return jax.tree.map(lambda a: a.astype(F32), tree)


def loss(params: Dict, tokens, m: Dict):
    """Mean next-token cross-entropy of one sequence `tokens` [T + 1],
    differentiable in `params` (float32, layers stacked on axis 0; the scan
    only walks the stack, so that one layer is compiled once)."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens[:-1]]
        x, _ = jax.lax.scan(lambda x, lp: (layer(x, lp, m), None), x,
                            params["layers"])
        x = _rmsnorm(x, params["final_norm"], m["norm_eps"])
        logits = x @ _head(params).T
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, tokens[1:, None], axis=-1))


@functools.partial(jax.jit, static_argnames=("dims",))
def _loss_and_grads(params, tokens, dims):
    return jax.value_and_grad(
        lambda p: loss(p, tokens, dict(dims)))(_upcast(params))


def loss_and_grads(params: Dict, tokens, m: Dict):
    """Reference loss and gradients on the system's weights upcast whole
    (for a configuration whose float32 copy and gradients fit); loss and
    gradients are float32."""
    return _loss_and_grads(params, tokens, _dims(m))


def _dims(m: Dict):
    return tuple(sorted(m.items()))


@functools.partial(jax.jit, static_argnames=("dims",))
def _layer_upcast(x, lp, dims):
    with jax.default_matmul_precision("highest"):
        return layer(x, _upcast(lp), dict(dims))


@jax.jit
def _logits_block(rows, table):
    with jax.default_matmul_precision("highest"):
        return rows @ table.astype(F32).T


def hidden_layerwise(params: Dict, tokens, m: Dict):
    """Final-norm hidden states [T, d] of one sequence, upcasting one
    layer's weights at a time (a 36-layer model never exists in float32).
    One layer program serves every layer."""
    x = params["embed"][tokens].astype(F32)
    for i in range(m["n_layers"]):
        x = _layer_upcast(x, jax.tree.map(lambda a: a[i], params["layers"]),
                          _dims(m))
    return _rmsnorm(x, params["final_norm"].astype(F32), m["norm_eps"])


def logits_rows(params: Dict, hidden_rows, m: Dict, chunk: int = 16384):
    """Logits [R, vocab] of a few hidden rows, the table upcast a block of
    rows at a time."""
    table = _head(params)
    return jnp.concatenate(
        [_logits_block(hidden_rows, table[i:i + chunk])
         for i in range(0, table.shape[0], chunk)], axis=-1)


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
