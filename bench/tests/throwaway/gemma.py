"""Plain reference of a Gemma-family decoder (arXiv:2403.08295) at the toy
size the program calls `tiny_gemma`: a second architecture for
bench/tests/test_add_cell.py to add as files. It is not a benchmark
configuration (the family is excluded) and no cell of BENCHMARK.json names
it; the test copies it to `reference/gemma.py` of a temporary checkout.

What it computes that `reference/qwen3.py` does not: embeddings scaled by
sqrt(d_model), one KV head serving every query head, no QK-norm, a GeGLU
MLP (tanh-approximated gelu) and a tanh softcap on the final logits, whose
cap arrives in `dims` because the configuration file states it as a
further published size. float32, matmul precision "highest", nothing
imported from the program.
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp

from reference.draws import normal, ones

F32 = jnp.float32


def leaf_init(path, m: Dict):
    """The program's `init_params` table for this family: projections at
    d**-0.5, the residual stream's two writers at d**-0.5 * (2L)**-0.5,
    the tied table at the head's scale, norm scales ones."""
    name, base = path[-1], m["d_model"] ** -0.5
    if name in ("wq", "wk", "wv", "w_gate", "w_up", "embed"):
        return (normal, base)
    if name in ("wo", "w_down"):
        return (normal, base * (2 * m["n_layers"]) ** -0.5)
    return (ones,)


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    t, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def layer(x, lp: Dict, m: Dict):
    t = x.shape[0]
    h, kvh, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    y = _rmsnorm(x, lp["attn_norm"], m["norm_eps"])
    q = _rope((y @ lp["wq"]).reshape(t, h, hd), m["rope_theta"])
    k = _rope((y @ lp["wk"]).reshape(t, kvh, hd), m["rope_theta"])
    v = (y @ lp["wv"]).reshape(t, kvh, hd)
    k, v = (jnp.repeat(a, h // kvh, axis=1) for a in (k, v))
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(F32(hd))
    causal = jnp.tril(jnp.ones((t, t), dtype=bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    x = x + a.reshape(t, h * hd) @ lp["wo"]
    y = _rmsnorm(x, lp["mlp_norm"], m["norm_eps"])
    mlp = (jax.nn.gelu(y @ lp["w_gate"], approximate=True)
           * (y @ lp["w_up"])) @ lp["w_down"]
    return x + mlp


def _upcast(tree):
    return jax.tree.map(lambda a: a.astype(F32), tree)


def _hidden(params: Dict, tokens, m: Dict):
    x = params["embed"][tokens] * jnp.sqrt(F32(m["d_model"]))
    for i in range(m["n_layers"]):
        x = layer(x, jax.tree.map(lambda a: a[i], params["layers"]), m)
    return _rmsnorm(x, params["final_norm"], m["norm_eps"])


def _logits(params: Dict, rows, m: Dict):
    cap = m["final_logit_softcap"]
    return cap * jnp.tanh(rows @ params["embed"].T / cap)  # tied head


def _loss(params: Dict, tokens, m: Dict):
    logp = jax.nn.log_softmax(
        _logits(params, _hidden(params, tokens[:-1], m), m), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[1:, None], axis=-1))


def _jit(fn):
    """`fn(params, x, dims)` on the system's weights upcast, the sizes
    static, every product at the highest precision."""
    @functools.partial(jax.jit, static_argnames=("dims",))
    def run(params, x, dims):
        with jax.default_matmul_precision("highest"):
            return fn(_upcast(params), x, dict(dims))
    return lambda params, x, m: run(params, x, tuple(sorted(m.items())))


hidden_layerwise = _jit(_hidden)
logits_rows = _jit(_logits)
loss_layerwise = _jit(_loss)
loss_and_grads = _jit(lambda p, tokens, m: jax.value_and_grad(_loss)(p, tokens, m))
