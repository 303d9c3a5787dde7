"""Plain reference of LFM2-24B-A2B (the model's public config.json,
`model_type` lfm2_moe; Liquid AI's LFM2 technical report and the lfm2_moe
modelling code of Hugging Face transformers): the forward pass and the
next-token loss in straightforward `jax.numpy`, float32,
`jax.default_matmul_precision("highest")`. No cache, no chunks, no carried
convolution inputs, no sorting, no grouped products, and nothing imported
from the program: it takes the sizes as a plain dict and the weights as a
tree of arrays named as the program names them.

For the residual stream x [T, d] of one sequence (norm(x, w) = x /
sqrt(mean(x^2) + norm_eps) * w; no bias anywhere, `conv_bias` is false):

    every layer l:  x = x + mixer_l(norm(x, operator norm))
                    x = x + mlp_l(norm(x, ffn norm))
    conv mixer, of h [T, d] (`layer_types[l]` "conv"):
        [B | C | u] = h W_in                 W_in [d, 3d], three blocks of d
        v   = B * u
        y_t = sum_{j < K} w[:, j] * v_{t-(K-1)+j}    depthwise, causal, K =
              conv_L_cache taps a channel, v zero before the sequence's
              start, no activation
        out = (C * y) W_out
    attention mixer ("full_attention"):
        q, k, v = h Wq, h Wk, h Wv           H and KVH heads of head_dim
        q, k = norm over each head's head_dim, one learned scale [head_dim]
               for q's heads and one for k's
        rope at rope_theta on q and k, pairs (i, i + head_dim / 2)
        causal softmax(q . k * head_dim^-0.5) v, H / KVH queries a key
        head, then Wo
    MLP of a layer below `first_k_dense_replace` (the public
    `num_dense_layers`):  (silu(h W1) * (h W3)) W2, width d_ff
    MLP of any other layer:
        s   = sigmoid(h W_r)                                  [T, E]
        the k experts of a token: the k largest of s + expert_bias
        their weights: s (NOT s + bias) at the chosen, over their sum +
              1e-6, times routed_scaling_factor
        sum over the chosen of weight_e * (silu(h W1_e) * (h W3_e)) W2_e,
        width moe_intermediate_size; no shared expert

then one more norm (the published code's `embedding_norm`) and the head
(`lm_head`, or the table transposed where the tree has none). The experts' sum is computed the dense way:
every expert is applied to every token and its output multiplied by the
token's weight for it, which is zero where the token did not choose it.

Departures from the published model, each noted in the configuration file
too: weights are random from a seed (`leaf_init`); `expert_bias`, a trained
buffer, is drawn normal at 0.05; the router's product `h W_r` is float32
here as everything is, where the published code takes it in the weights'
dtype (the program accumulates it in float32); the published code names
the leaves otherwise (`operator_norm`, `ffn_norm`, `conv.in_proj`,
`feed_forward.gate`, `expert_bias`, `w1`/`w3`/`w2`), the program's names
are `norm`/`attn_norm`, `mlp_norm`, `w_in`, `router`, `router_bias`,
`w_gate`/`w_up`/`w_down`, and a matrix is held [in, out].

Weights arrive in the dtype the system holds them in and are upcast here a
block at a time: a layer's mixer whole (34 MB of float32 for a conv mixer),
a dense MLP whole (0.29 GB), ONE expert of an expert layer (38 MB), one
key head's four query heads' scores (4 x T x T float32, 67 MB at a check's
T = 2,048): under 0.5 GB beside the engine, where a float32 expert layer
would be 2.4 GB.
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp

from reference.draws import normal, ones

F32 = jnp.float32
EXPERT_LEAVES = ("w_gate", "w_up", "w_down")
ATTENTION = ("attention", "full_attention")
# The published code's `router` normalisation: weights / (sum + 1e-6).
NORM_TOPK_EPS = 1e-6


def uniform(key, shape, bound):
    return jax.random.uniform(key, shape, F32, -bound, bound)


# The published depth. A writer of the residual stream (`wo`, `w_out`,
# `w_down`) is drawn at (2 x depth) ** -0.5 of its fan-in ** -0.5, the
# program's `init_params` rule, and the depth is the model's, not the cut's:
# a pipeline stage's weights are the 40-layer model's.
PUBLISHED_LAYERS = 40


def leaf_init(path, m: Dict):
    """The rule by which bench/weights.py draws the leaf at `path`, which is
    the program's own (`transformer.init_params`, and the rule of the other
    sparse configuration's reference): every matrix normal at its fan-in **
    -0.5, the writers of the residual stream at (2 x 40) ** -0.5 of that,
    the embedding table at 1 and the head at d ** -0.5 (a tied table takes
    the head's scale), norms one; the convolution's taps uniform in +-K **
    -0.5 (a torch Conv1d's default); `router_bias` (the published
    `expert_bias`, a trained buffer that a trained model does not leave at
    zero) normal at 0.05, a tenth of the spread of sigmoid scores around
    one half.

    Why the writers are scaled and the table is not: with every matrix at
    its fan-in ** -0.5 and a tied table at d ** -0.5 (this PR's first
    draws) the residual stream after one layer is that layer's output and
    holds nothing exact, bfloat16 puts 0.7% of error into it a layer, and a
    router that takes 4 of 64 near-tied sigmoid scores then chooses other
    experts than the float32 reference at a quarter of the token-layers;
    the served model's first logits read 0.16-0.35 from the reference's on
    the chip (PERF.md section 6, PR 56). Under the program's rule the
    stream is the exact embedding plus small updates, as in a trained
    pre-norm model, and a flipped choice moves a logit by 2% of its rms."""
    name, d = path[-1], m["d_model"]
    out = (2 * PUBLISHED_LAYERS) ** -0.5
    if name in ("wq", "wk", "wv", "w_in", "router", "w_gate", "w_up",
                "lm_head"):
        return (normal, d ** -0.5)
    if name == "w_out":
        return (normal, d ** -0.5 * out)
    if name == "wo":
        return (normal, (m["n_heads"] * m["head_dim"]) ** -0.5 * out)
    if name == "w_down":
        ff = m["d_ff"] if path[1] == "mlp" else m["moe_intermediate_size"]
        return (normal, ff ** -0.5 * out)
    if name == "embed":
        return (normal, d ** -0.5 if m["tie_embeddings"] else 1.0)
    if name == "conv_w":
        return (uniform, m["conv_L_cache"] ** -0.5)
    if name == "router_bias":
        return (normal, 0.05)
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


def conv_mixer(y, lp: Dict, m: Dict):
    """The gated short convolution on one sequence's normed activations y
    [T, d], behind zeros."""
    t, taps = y.shape[0], m["conv_L_cache"]
    b, c, u = jnp.split(y @ lp["w_in"], 3, axis=-1)
    v = b * u
    behind = jnp.concatenate([jnp.zeros((taps - 1, v.shape[1]), F32), v])
    conv = sum(behind[j:j + t] * lp["conv_w"][:, j] for j in range(taps))
    return (c * conv) @ lp["w_out"]


def attention_mixer(y, lp: Dict, m: Dict):
    """The attention mixer on one sequence's normed activations y [T, d],
    one key head (and its H / KVH query heads) at a time."""
    t = y.shape[0]
    h, kvh, hd, eps = m["n_heads"], m["n_kv_heads"], m["head_dim"], m["norm_eps"]
    group = h // kvh
    q = _rmsnorm((y @ lp["wq"]).reshape(t, h, hd), lp["q_norm"], eps)
    k = _rmsnorm((y @ lp["wk"]).reshape(t, kvh, hd), lp["k_norm"], eps)
    v = (y @ lp["wv"]).reshape(t, kvh, hd)
    q, k = _rope(q, m["rope_theta"]), _rope(k, m["rope_theta"])
    causal = jnp.tril(jnp.ones((t, t), dtype=bool))

    def key_head(_, qkv):
        q_g, k_g, v_g = qkv                       # [T, group, hd], [T, hd] x 2
        scores = jnp.einsum("qgd,kd->gqk", q_g, k_g) * hd ** -0.5
        scores = jnp.where(causal[None], scores, -jnp.inf)
        return None, jnp.einsum("gqk,kd->qgd",
                                jax.nn.softmax(scores, axis=-1), v_g)

    _, a = jax.lax.scan(key_head, None, (
        jnp.moveaxis(q.reshape(t, kvh, group, hd), 1, 0),
        jnp.moveaxis(k, 1, 0), jnp.moveaxis(v, 1, 0)))   # [KVH, T, group, hd]
    return jnp.moveaxis(a, 0, 1).reshape(t, h * hd) @ lp["wo"]


def _swiglu(y, w_gate, w_up, w_down):
    return (jax.nn.silu(y @ w_gate) * (y @ w_up)) @ w_down


def route(y, router, bias, m: Dict):
    """The chosen experts [T, k] and each token's weight for every expert
    [T, E] (zero outside its choice). `bias` None: a model without one."""
    s = jax.nn.sigmoid(y @ router)
    _, chosen = jax.lax.top_k(s if bias is None else s + bias,
                              m["experts_per_token"])
    rows = jnp.arange(y.shape[0])[:, None]
    w = s[rows, chosen]
    if m["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + NORM_TOPK_EPS)
    w = w * m["routed_scaling_factor"]
    return chosen, jnp.zeros_like(s).at[rows, chosen].set(w)


def experts(y, lp: Dict, expert_at, m: Dict):
    """The routed MLP on normed activations y [T, d]: every expert applied
    to every token, one at a time; `expert_at(e)` gives expert e's (W1, W3,
    W2) in float32. Returns the sum and the chosen experts."""
    chosen, gates = route(y, lp["router"], lp.get("router_bias"), m)

    def add_expert(acc, e):
        gate = jax.lax.dynamic_index_in_dim(gates, e, axis=1)
        return acc + gate * _swiglu(y, *expert_at(e)), None

    acc, _ = jax.lax.scan(add_expert, jnp.zeros_like(y),
                          jnp.arange(m["num_experts"]))
    return acc, chosen


def layer(x, kind: str, mixer_lp: Dict, mlp: Dict, expert_at, m: Dict):
    """One decoder layer of `kind` on one sequence x [T, d], float32
    weights: its mixer's leaves and its MLP's (a dense MLP's whole; of a
    routed one the norm, the router and its bias, the experts through
    `expert_at`). Returns x and the chosen experts (None, dense)."""
    eps = m["norm_eps"]
    if kind in ATTENTION:
        x = x + attention_mixer(_rmsnorm(x, mixer_lp["attn_norm"], eps),
                                mixer_lp, m)
    else:
        x = x + conv_mixer(_rmsnorm(x, mixer_lp["norm"], eps), mixer_lp, m)
    y = _rmsnorm(x, mlp["mlp_norm"], eps)
    if "router" not in mlp:
        return x + _swiglu(y, mlp["w_gate"], mlp["w_up"], mlp["w_down"]), None
    out, chosen = experts(y, mlp, expert_at, m)
    return x + out, chosen


def _layers(m: Dict):
    """(kind, mixer stack, index in it, MLP stack, index in it) of every
    layer in order: the first `first_k_dense_replace` MLPs are dense."""
    seen = {"conv": 0, "attn": 0}
    n_dense = m["first_k_dense_replace"] if m["num_experts"] else m["n_layers"]
    for i, kind in enumerate(m["layer_pattern"][:m["n_layers"]]):
        stack = "attn" if kind in ATTENTION else "conv"
        yield (kind, stack, seen[stack],
               *(("mlp", i) if i < n_dense else ("moe", i - n_dense)))
        seen[stack] += 1


def _at(stack: Dict, i):
    return jax.tree.map(lambda a: a[i], stack)


def _upcast(tree):
    return jax.tree.map(lambda a: a.astype(F32), tree)


def _head(params: Dict):
    """The output head as a [vocab, d] table: the separate head
    transposed, or the embedding itself where the tree is tied."""
    return params["lm_head"].T if "lm_head" in params else params["embed"]


def _dims(m: Dict):
    return tuple(sorted(m.items()))


@functools.partial(jax.jit, static_argnames=("kind", "dims"))
def _layer_upcast(x, mixer_lp, mlp, stacks, j, kind, dims):
    """One layer on x: the mixer's leaves and the MLP's small ones upcast
    whole, expert `e` of expert layer `j` read from the whole `stacks` (the
    system's dtype) and upcast alone."""
    def expert_at(e):
        return tuple(jax.lax.dynamic_slice(
            stacks[n], (j, e, 0, 0), (1, 1) + stacks[n].shape[2:]
        )[0, 0].astype(F32) for n in EXPERT_LEAVES)

    with jax.default_matmul_precision("highest"):
        return layer(x, kind, _upcast(mixer_lp), _upcast(mlp), expert_at,
                     dict(dims))


def _walk(params: Dict, tokens, m: Dict):
    layers = params["layers"]
    x = params["embed"][tokens].astype(F32)
    chosen = []
    for kind, stack, j, mlp_stack, i in _layers(m):
        mlp, stacks = layers[mlp_stack], None
        if mlp_stack == "moe":
            stacks = {n: mlp[n] for n in EXPERT_LEAVES}
            mlp = {n: w for n, w in mlp.items() if n not in stacks}
        x, c = _layer_upcast(x, _at(layers[stack], j), _at(mlp, i), stacks,
                             jnp.int32(i), kind, _dims(m))
        if c is not None:
            chosen.append(c)
    return x, chosen


def hidden_layerwise(params: Dict, tokens, m: Dict):
    """Final-norm hidden states [T, d] of one sequence; the model never
    exists in float32, nor does an expert layer of it."""
    x, _ = _walk(params, tokens, m)
    return _rmsnorm(x, params["final_norm"].astype(F32), m["norm_eps"])


def routing_layerwise(params: Dict, tokens, m: Dict):
    """The experts the reference chooses, [expert layers, T, k], sorted
    within a token: what a routing flip is told from a fault by."""
    _, chosen = _walk(params, tokens, m)
    return jnp.sort(jnp.stack(chosen), axis=-1)


@jax.jit
def _logits_block(rows, table):
    with jax.default_matmul_precision("highest"):
        return rows @ table.astype(F32).T


def logits_rows(params: Dict, hidden_rows, m: Dict, chunk: int = 16384):
    """Logits [R, vocab] of a few hidden rows, the table upcast a block of
    rows at a time."""
    table = _head(params)
    return jnp.concatenate(
        [_logits_block(hidden_rows, table[i:i + chunk])
         for i in range(0, table.shape[0], chunk)], axis=-1)


def loss_layerwise(params: Dict, tokens, m: Dict, rows: int = 128):
    """Mean next-token cross-entropy of one sequence `tokens` [T + 1],
    without a float32 copy of the model."""
    x = hidden_layerwise(params, tokens[:-1], m)
    total = 0.0
    for i in range(0, x.shape[0], rows):
        logp = jax.nn.log_softmax(logits_rows(params, x[i:i + rows], m), -1)
        total += float(-jnp.sum(jnp.take_along_axis(
            logp, tokens[1 + i:1 + i + rows, None], axis=-1)))
    return total / x.shape[0]


def loss(params: Dict, tokens, m: Dict):
    """The same loss, differentiable in float32 `params` held whole (the
    next-token term alone: a balance term belongs to a training recipe,
    and no cell trains this model)."""
    with jax.default_matmul_precision("highest"):
        layers = params["layers"]
        x = params["embed"][tokens[:-1]]
        for kind, stack, j, mlp_stack, i in _layers(m):
            mlp = _at(layers[mlp_stack], i)
            x, _ = layer(x, kind, _at(layers[stack], j), mlp,
                         lambda e, mlp=mlp: tuple(
                             mlp[n][e] for n in EXPERT_LEAVES), m)
        x = _rmsnorm(x, params["final_norm"], m["norm_eps"])
        logp = jax.nn.log_softmax(x @ _head(params).T, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, tokens[1:, None], axis=-1))


@functools.partial(jax.jit, static_argnames=("dims",))
def _loss_and_grads(params, tokens, dims):
    return jax.value_and_grad(
        lambda p: loss(p, tokens, dict(dims)))(_upcast(params))


def loss_and_grads(params: Dict, tokens, m: Dict):
    """Reference loss and gradients on the system's weights upcast whole
    (for a size whose float32 copy and gradients fit), float32."""
    return _loss_and_grads(params, tokens, _dims(m))
