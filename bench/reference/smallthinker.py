"""Plain reference of SmallThinker-21BA3B-Instruct (PowerInfer,
arXiv:2507.20984; the model's public config.json, `model_name`
smallthinker_21b_instruct, and for what the config has no key for the
released modelling code): the forward pass, the next-token loss and, through
`jax.grad`, its gradients, in straightforward `jax.numpy`, float32,
`jax.default_matmul_precision("highest")`. No kernels, no cache, no ring, no
pages, no sorting, no grouped products, and nothing imported from the
program: the sizes come as a plain dict and the weights as a tree of arrays
named as the program names them.

One layer `l`, for x [T, d] at positions 0 .. T - 1:

    r = x Wr                                    the router reads the layer's
                                                INPUT, before any norm
    h = rmsnorm(x, attn_norm)
    q = h Wq;  k = h Wk;  v = h Wv              28 query and 4 key-value heads
                                                of 128, no bias, no QK-norm;
                                                query head g reads kv head g // 7
    where rope_layout[l]: rope (rotate-half, theta 1.5e6) on q and k
    allowed(i, j) = j <= i and (not sliding_window_layout[l]
                                or i - j < sliding_window_size)
        a window layer sees itself and the 4,095 positions before it
    a_i = sum_j softmax_j(q_i . k_j / sqrt(128) over allowed) v_j
    x = x + a Wo
    h = rmsnorm(x, mlp_norm)
    (w_1..w_6, e_1..e_6) = the 6 largest of r;  w = softmax(w_1..w_6)
        (the softmax over all 64 renormalised over the chosen 6 is the same
        numbers: `moe_primary_router_apply_softmax`, `norm_topk_prob`)
    x = x + sum_j w_j (relu(h Wgate[e_j]) * (h Wup[e_j])) Wdown[e_j]

then a final rmsnorm and the untied head; the logits at position p are for
the token at p + 1. rmsnorm(x, w) = x / sqrt(mean(x^2) + eps) * w. The
experts' sum is computed the dense way: EVERY expert is applied to EVERY
token, one expert at a time, and its output multiplied by the token's
weight for that expert, which is zero outside the token's six.

Attention runs a block of queries at a time against every key (`BLOCK`
rows: the scores of one block at a check's 12,032 positions are 0.34 GB in
float32, where all rows' would be 16 GB), under `allowed` as a mask: a
window layer computes the scores it then masks, and nothing is skipped.

Departures from the published model, each noted in the configuration's
file: weights are random from a seed; the depth is whatever `n_layers` and
the two lists say; the loss is the plain next-token cross-entropy (the
published training's balance terms are not in config.json).

Weights arrive in the dtype the system holds them in and are upcast here, a
layer's attention weights together and ONE EXPERT AT A TIME (a float32 copy
of one layer's 64 experts is 1.5 GB, beside 11.9 GB of the replica's own
arguments).
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp

from reference.draws import normal, ones

F32 = jnp.float32
EXPERT_LEAVES = ("w_gate", "w_up", "w_down")
BLOCK = 256  # queries a step of the attention's loop

# The program's `init_params` at the program's config: every matrix normal
# at d_model ** -0.5, the two writers of the residual stream (`wo`, an
# expert's `w_down`) at (2 x n_layers) ** -0.5 of that, `n_layers` being the
# layers that RUN, the table at 1, the untied head at d_model ** -0.5, norms
# ones.
_PROJECTIONS = ("wq", "wk", "wv", "router", "w_gate", "w_up", "lm_head")
_RESIDUAL_WRITERS = ("wo", "w_down")


def leaf_init(path, m: Dict):
    """The rule (reference/draws.py) by which bench/weights.py draws the
    leaf at `path`, the tuple of keys from the root of the program's
    parameter tree; `m` is `dims`."""
    name, base = path[-1], m["d_model"] ** -0.5
    if name in _PROJECTIONS:
        return (normal, base)
    if name in _RESIDUAL_WRITERS:
        return (normal, base * (2 * m["n_layers"]) ** -0.5)
    if name == "embed":
        return (normal, 1.0)
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


def _attend(q, k, v, window: int):
    """q [T, KVH, G, hd] against k, v [T, KVH, hd] under `allowed`
    (`window` 0: a full layer), a block of queries at a time: [T, KVH, G,
    hd]."""
    t, hd = q.shape[0], q.shape[-1]
    blocks = -(-t // BLOCK)
    padded = jnp.pad(q, ((0, blocks * BLOCK - t),) + ((0, 0),) * 3)
    k_pos = jnp.arange(t)

    def block(args):
        qb, first = args
        q_pos = first + jnp.arange(BLOCK)
        behind = q_pos[:, None] - k_pos[None, :]
        allowed = behind >= 0
        if window:
            allowed &= behind < window
        scores = jnp.einsum("qhgd,khd->hgqk", qb, k) / jnp.sqrt(F32(hd))
        scores = jnp.where(allowed, scores, -jnp.inf)
        return jnp.einsum("hgqk,khd->qhgd", jax.nn.softmax(scores, -1), v)

    out = jax.lax.map(block, (padded.reshape((blocks, BLOCK) + q.shape[1:]),
                              jnp.arange(blocks) * BLOCK))
    return out.reshape((blocks * BLOCK,) + q.shape[1:])[:t]


def attention(x, lp: Dict, m: Dict, rope: bool, window: int):
    """The attention half of a layer on one sequence x [T, d], float32
    weights: x + attention(rmsnorm(x)); `rope` and `window` are the
    layer's own (`window` 0: it sees everything behind it)."""
    t = x.shape[0]
    h, kvh, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    y = _rmsnorm(x, lp["attn_norm"], m["norm_eps"])
    q = (y @ lp["wq"]).reshape(t, h, hd)
    k = (y @ lp["wk"]).reshape(t, kvh, hd)
    v = (y @ lp["wv"]).reshape(t, kvh, hd)
    if rope:
        q, k = _rope(q, m["rope_theta"]), _rope(k, m["rope_theta"])
    a = _attend(q.reshape(t, kvh, h // kvh, hd), k, v, window)
    return x + a.reshape(t, h * hd) @ lp["wo"]


def route(x_in, router, m: Dict):
    """Each token's weight for every expert [T, E] (zero outside its top
    k) from the layer's input `x_in`: the k largest logits, a softmax over
    them."""
    logits = x_in @ router
    w, chosen = jax.lax.top_k(logits, m["experts_per_token"])
    w = jax.nn.softmax(w, axis=-1)
    rows = jnp.arange(x_in.shape[0])[:, None]
    return jnp.zeros_like(logits).at[rows, chosen].set(w)


def experts(x, gates, lp: Dict, m: Dict, expert_at):
    """The expert half of a layer: x + sum_e gates[:, e] * expert_e(
    rmsnorm(x)), every expert applied to every token, one at a time.
    `expert_at(e)` gives expert e's (Wgate, Wup, Wdown) in float32."""
    y = _rmsnorm(x, lp["mlp_norm"], m["norm_eps"])

    def add_expert(acc, e):
        w_gate, w_up, w_down = expert_at(e)
        out = (jax.nn.relu(y @ w_gate) * (y @ w_up)) @ w_down
        return acc + gates[:, e][:, None] * out, None

    acc, _ = jax.lax.scan(add_expert, jnp.zeros_like(x),
                          jnp.arange(m["num_experts"]))
    return x + acc


def layer_flags(m: Dict, l: int):
    """(whether layer `l` rotates, its window or 0) from the two lists."""
    return (bool(m["rope_layout"][l]),
            m["sliding_window_size"] if m["sliding_window_layout"][l] else 0)


def layer(x, lp: Dict, m: Dict, rope: bool, window: int, expert_at=None):
    """One decoder layer on one sequence x [T, d], float32 weights (the
    expert stacks [E, ..] whole unless `expert_at` reads them)."""
    gates = route(x, lp["router"], m)
    x = attention(x, lp, m, rope, window)
    return experts(x, gates, lp, m, expert_at or (
        lambda e: tuple(lp[n][e] for n in EXPERT_LEAVES)))


def _upcast(tree):
    return jax.tree.map(lambda a: a.astype(F32), tree)


def loss(params: Dict, tokens, m: Dict):
    """Mean next-token cross-entropy of one sequence `tokens` [T + 1],
    differentiable in `params` (float32, layers stacked on axis 0)."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens[:-1]]
        for l in range(m["n_layers"]):
            x = layer(x, jax.tree.map(lambda a: a[l], params["layers"]), m,
                      *layer_flags(m, l))
        x = _rmsnorm(x, params["final_norm"], m["norm_eps"])
        logp = jax.nn.log_softmax(x @ params["lm_head"], axis=-1)
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


@functools.partial(jax.jit, static_argnames=("dims", "rope", "window"))
def _layer_at(x, layers, i, dims, rope, window):
    """Layer `i` of the stacked `layers` (the system's dtype) on x: the
    small leaves upcast together, the experts read from the stack and
    upcast one at a time."""
    with jax.default_matmul_precision("highest"):
        lp = {n: a[i].astype(F32) for n, a in layers.items()
              if n not in EXPERT_LEAVES}

        def expert_at(e):
            return tuple(jax.lax.dynamic_slice(
                layers[n], (i, e, 0, 0), (1, 1) + layers[n].shape[2:]
            )[0, 0].astype(F32) for n in EXPERT_LEAVES)

        return layer(x, lp, dict(dims), rope, window, expert_at)


def _walk(params: Dict, tokens, m: Dict):
    """Hidden states before the final norm, layer by layer."""
    x = params["embed"][tokens].astype(F32)
    for l in range(m["n_layers"]):
        x = _layer_at(x, params["layers"], jnp.int32(l), _dims(m),
                      *layer_flags(m, l))
    return x


def hidden_layerwise(params: Dict, tokens, m: Dict):
    """Final-norm hidden states [T, d] of one sequence; the model never
    exists in float32, nor does one layer of it."""
    return _rmsnorm(_walk(params, tokens, m),
                    params["final_norm"].astype(F32), m["norm_eps"])


@jax.jit
def _logits_block(rows, head_columns):
    with jax.default_matmul_precision("highest"):
        return rows @ head_columns.astype(F32)


def logits_rows(params: Dict, hidden_rows, m: Dict, chunk: int = 16384):
    """Logits [R, vocab] of a few hidden rows, the untied head [d, vocab]
    upcast a block of columns at a time."""
    head = params["lm_head"]
    return jnp.concatenate(
        [_logits_block(hidden_rows, head[:, i:i + chunk])
         for i in range(0, head.shape[1], chunk)], axis=-1)


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
