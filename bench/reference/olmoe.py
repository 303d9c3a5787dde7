"""Plain reference of the OLMoE decoder (arXiv:2409.02060, and the model's
public config and modelling code, `OLMoE-1B-7B-0125-Instruct`): the forward
pass, the next-token loss with its load-balancing term and, through
`jax.grad`, its gradients, in straightforward `jax.numpy`, float32,
`jax.default_matmul_precision("highest")`. No kernels, no cache, no sorting,
no grouped products, and nothing imported from the program: it takes the
sizes as a plain dict and the weights as a tree of arrays named as the
program names them.

One layer, for x [T, d]:

    h = rmsnorm(x, attn_norm)
    q = rmsnorm(h Wq, q_norm);  k = rmsnorm(h Wk, k_norm);  v = h Wv
        the QK-norm is over the WHOLE projection (n_heads * head_dim wide),
        before the split into heads
    q, k -> heads of head_dim, rope (half-split pairs, theta 10000)
    a = softmax(q k^T / sqrt(head_dim) + causal mask) v
    x = x + a Wo
    h = rmsnorm(x, mlp_norm)
    p = softmax(h Wr) over all E experts
    (w_1..w_k, e_1..e_k) = top-k of p;  the weights are NOT renormalised
        (`norm_topk_prob` false; renormalised where a configuration says so)
    x = x + sum_j w_j * Wdown[e_j] (silu(Wgate[e_j] h) * (Wup[e_j] h))

then a final rmsnorm and the untied head. rmsnorm(x, w) = x / sqrt(mean(x^2)
+ eps) * w. The experts' sum is computed the dense way: EVERY expert is
applied to EVERY token and its output multiplied by the token's weight for
that expert, which is zero outside the token's top k.

Training adds AUX_COEFFICIENT * E * sum_e P_e * sum_j f_{j,e}, where P_e is
the mean router probability of expert e and f_{j,e} the share of tokens
whose j-th choice is e, both means taken over every token of every layer
together (the published code concatenates the layers' router outputs).

Departures from the published model, each the program's own and noted in
the configuration files: weights are random from a seed; the context is
whatever sequence is passed; `clip_qkv` is null in the published config and
absent here; AUX_COEFFICIENT is the modelling code's default
(`router_aux_loss_coef` 0.01), which the catalog's config does not state.

Weights arrive in the dtype the system holds them in and are upcast here:
a layer's attention weights together, ONE EXPERT AT A TIME (a float32 copy
of one layer's 64 experts is 1.68 GB, and the serving check runs beside
15.99 GB of the replica's own arguments). `hidden_layerwise` never slices
a layer out of the stack either: its one program takes the stack and the
layer's index and reads one expert's three matrices (12.6 MB in bf16, 25.2
MB upcast) per step of its loop. Its own peak at a check's T = 448
positions: 67 MB of upcast attention weights, 25 MB of one expert, 13 MB
of scores and under 20 MB of activations: about 0.13 GB.
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp

from reference.draws import normal, ones

F32 = jnp.float32
AUX_COEFFICIENT = 0.01
EXPERT_LEAVES = ("w_gate", "w_up", "w_down")


# The model's initialisation as the program's `init_params` has it: the
# input projections, the router and the untied head at d**-0.5, the two
# projections that write the residual stream at d**-0.5 * (2L)**-0.5, the
# embedding table at 1, the norm scales ones.
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


def attention(x, lp: Dict, m: Dict):
    """The attention half of a layer on one sequence x [T, d], float32
    weights: x + attention(rmsnorm(x))."""
    t = x.shape[0]
    h, kvh, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    eps, theta = m["norm_eps"], m["rope_theta"]
    y = _rmsnorm(x, lp["attn_norm"], eps)
    q = _rmsnorm(y @ lp["wq"], lp["q_norm"], eps).reshape(t, h, hd)
    k = _rmsnorm(y @ lp["wk"], lp["k_norm"], eps).reshape(t, kvh, hd)
    v = (y @ lp["wv"]).reshape(t, kvh, hd)
    q, k = _rope(q, theta), _rope(k, theta)
    k = jnp.repeat(k, h // kvh, axis=1)
    v = jnp.repeat(v, h // kvh, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(F32(hd))
    causal = jnp.tril(jnp.ones((t, t), dtype=bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    return x + a.reshape(t, h * hd) @ lp["wo"]


def route(y, router, m: Dict):
    """Router probabilities p [T, E], the chosen experts [T, k] and each
    token's weight for every expert [T, E] (zero outside its top k)."""
    p = jax.nn.softmax(y @ router, axis=-1)
    w, chosen = jax.lax.top_k(p, m["experts_per_token"])
    if m["norm_topk_prob"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    rows = jnp.arange(y.shape[0])[:, None]
    return p, chosen, jnp.zeros_like(p).at[rows, chosen].set(w)


def experts(x, lp: Dict, m: Dict, expert_at):
    """The expert half of a layer: x + sum_e gate[:, e] * expert_e(
    rmsnorm(x)), every expert applied to every token, one at a time.
    `expert_at(e)` gives expert e's (Wgate, Wup, Wdown) in float32. Also
    returns the router's probabilities and choices."""
    y = _rmsnorm(x, lp["mlp_norm"], m["norm_eps"])
    p, chosen, gates = route(y, lp["router"], m)

    def add_expert(acc, e):
        w_gate, w_up, w_down = expert_at(e)
        out = (jax.nn.silu(y @ w_gate) * (y @ w_up)) @ w_down
        return acc + gates[:, e][:, None] * out, None

    acc, _ = jax.lax.scan(add_expert, jnp.zeros_like(x),
                          jnp.arange(m["num_experts"]))
    return x + acc, p, chosen


def layer(x, lp: Dict, m: Dict):
    """One decoder layer on one sequence x [T, d], float32 weights (the
    expert stacks [E, ..] whole). Returns (x, router probabilities [T, E],
    chosen experts [T, k])."""
    x = attention(x, lp, m)
    return experts(x, lp, m,
                   lambda e: tuple(lp[n][e] for n in EXPERT_LEAVES))


def load_balancing(probs, chosen, m: Dict):
    """E * sum_e P_e * sum_j f_{j,e} over router outputs `probs [N, E]`
    and choices `chosen [N, k]` of N tokens (all layers' concatenated)."""
    mask = jax.nn.one_hot(chosen, m["num_experts"], dtype=F32)  # [N, k, E]
    f = jnp.mean(mask, axis=0)                                  # [k, E]
    p = jnp.mean(probs, axis=0)                                 # [E]
    return m["num_experts"] * jnp.sum(f * p[None, :])


def _upcast(tree):
    return jax.tree.map(lambda a: a.astype(F32), tree)


def _token_loss(x, params, tokens, m):
    x = _rmsnorm(x, params["final_norm"], m["norm_eps"])
    logp = jax.nn.log_softmax(x @ params["lm_head"], axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[1:, None], axis=-1))


def loss(params: Dict, tokens, m: Dict):
    """Mean next-token cross-entropy of one sequence `tokens` [T + 1] plus
    the load-balancing term, differentiable in `params` (float32, layers
    stacked on axis 0; the scan only walks the stack)."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens[:-1]]

        def step(x, lp):
            x, p, chosen = layer(x, lp, m)
            return x, (p, chosen)

        x, (p, chosen) = jax.lax.scan(step, x, params["layers"])
        aux = load_balancing(p.reshape(-1, p.shape[-1]),
                             chosen.reshape(-1, chosen.shape[-1]), m)
        return _token_loss(x, params, tokens, m) + AUX_COEFFICIENT * aux


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


@functools.partial(jax.jit, static_argnames=("dims",))
def _layer_at(x, layers, i, dims):
    """Layer `i` of the stacked `layers` (the system's dtype) on x: the
    small leaves upcast together, the experts read from the stack and
    upcast one at a time."""
    m = dict(dims)
    with jax.default_matmul_precision("highest"):
        lp = {n: a[i].astype(F32) for n, a in layers.items()
              if n not in EXPERT_LEAVES}
        x = attention(x, lp, m)

        def expert_at(e):
            return tuple(jax.lax.dynamic_slice(
                layers[n], (i, e, 0, 0), (1, 1) + layers[n].shape[2:]
            )[0, 0].astype(F32) for n in EXPERT_LEAVES)

        return experts(x, lp, m, expert_at)


def _walk(params: Dict, tokens, m: Dict):
    """Hidden states before the final norm, and every layer's router
    probabilities and choices, layer by layer."""
    x = params["embed"][tokens].astype(F32)
    probs, chosen = [], []
    for i in range(m["n_layers"]):
        x, p, c = _layer_at(x, params["layers"], jnp.int32(i), _dims(m))
        probs.append(p)
        chosen.append(c)
    return x, probs, chosen


def hidden_layerwise(params: Dict, tokens, m: Dict):
    """Final-norm hidden states [T, d] of one sequence; the model never
    exists in float32, nor does one layer of it."""
    x, _, _ = _walk(params, tokens, m)
    return _rmsnorm(x, params["final_norm"].astype(F32), m["norm_eps"])


def routing_layerwise(params: Dict, tokens, m: Dict):
    """The experts the reference chooses, [layers, T, k], sorted within a
    token: what a served model's choices are compared with (bf16 rounding
    of the hidden state swaps near-tied experts)."""
    _, _, chosen = _walk(params, tokens, m)
    return jnp.sort(jnp.stack(chosen), axis=-1)


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
    x, probs, chosen = _walk(params, tokens[:-1], m)
    x = _rmsnorm(x, params["final_norm"].astype(F32), m["norm_eps"])
    total = 0.0
    for i in range(0, x.shape[0], rows):
        logp = jax.nn.log_softmax(logits_rows(params, x[i:i + rows], m), -1)
        total += float(-jnp.sum(jnp.take_along_axis(
            logp, tokens[1 + i:1 + i + rows, None], axis=-1)))
    aux = load_balancing(jnp.concatenate(probs), jnp.concatenate(chosen), m)
    return total / x.shape[0] + AUX_COEFFICIENT * float(aux)
