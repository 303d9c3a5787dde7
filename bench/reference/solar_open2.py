"""Plain reference of Solar-Open2-250B (the model's public config.json,
`model_type` solar_open2; its delta-rule layers are Kimi Linear's,
arXiv:2510.26692, its router DeepSeek-V3's, arXiv:2412.19437), for ONE
CHIP'S SHARE of each layer's routed experts: the forward pass and the
next-token loss in straightforward `jax.numpy`, float32,
`jax.default_matmul_precision("highest")`. No cache, no chunks, no blocks,
no carried state, no sorting, no grouped products, and nothing imported
from the program: it takes the sizes as a plain dict and the weights as a
tree of arrays named as the program names them.

For the residual stream x [T, d] of one sequence (norm(x, w) = x /
sqrt(mean(x^2) + norm_eps) * w; no bias anywhere):

    every layer l:  x = x + mixer_l(norm(x, the mixer's norm))
                    x = x + mlp_l(norm(x, the MLP's norm))
    delta-rule mixer, of h [T, d] (`layer_pattern[l]` "kda"), a head at a
    time (kda_num_heads heads of K = V = kda_head_dim):
        [q | k | v] = silu(conv(h W_qkv))    depthwise, causal, `kernel`
              taps a channel, zeros before the sequence's start, no bias
        q = q / sqrt(|q|^2 + 1e-6) * K^-0.5;  k = k / sqrt(|k|^2 + 1e-6)
        g_t = -exp(A_log) * softplus((h W_fa) W_fb + dt_bias)     [K]
        beta_t = 2 * sigmoid(h W_beta)        (1 * without
                                               kda_allow_neg_eigval)
        S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1}
              + beta_t k_t v_t^T              S_0 = 0, a scan over tokens
        o_t = S_t^T q_t
        out = (o_t / sqrt(mean(o_t^2) + norm_eps) * w_norm
               * sigmoid((h W_ga) W_gb)) W_out      the norm over a head's V
    attention mixer ("full_attention"):
        q, k, v = h Wq, h Wk, h Wv           H and KVH heads of head_dim,
              no position embedding, no QK-norm
        a = causal softmax(q . k * head_dim^-0.5) v, H / KVH queries a key
              head
        out = (a * sigmoid(h Wg)) Wo          where the tree has `wg`
    MLP of every layer (`first_k_dense_replace` 0; a leading dense layer
    would be (silu(h W1) * (h W3)) W2 of width d_ff):
        s   = sigmoid(h W_r)                                  [T, E]
        the k experts of a token: the k largest of s + router_bias
        their weights: s (NOT s + bias) at the chosen, over their sum +
              1e-20, times routed_scaling_factor
        sum over the chosen experts THAT ARE HELD of weight_e *
              (silu(h W1_e) * (h W3_e)) W2_e, width moe_intermediate_size
        + the shared expert whole, (silu(h Ws1) * (h Ws3)) Ws2

then one more norm and the head. The router is `num_experts` wide and the
expert stacks hold `experts_held` of them, experts [held * share, held *
(share + 1)): a token's chosen experts that are not held add nothing, here
as in the program; nothing stands in for the chips that hold them. The
held experts' sum is computed the dense way: every held expert is applied
to every token and its output multiplied by the token's weight for it,
which is zero where the token did not choose it.

Departures from the published model, each noted in the configuration file
too: weights are random from a seed (`leaf_init`); the choice bias, a
trained buffer, is drawn normal at 0.05; the published code holds q_proj,
k_proj, v_proj and their three convolutions apart where the tree has them
side by side (`w_qkv`, `conv_w`), names the decay's and the gate's pairs
`f_a_proj`/`f_b_proj` and `g_a_proj`/`g_b_proj`, and holds a matrix [out,
in] where the tree holds it [in, out].

Weights arrive in the dtype the system holds them in and are upcast here a
block at a time: a layer's mixer whole (0.55 GB of float32 for a delta-rule
mixer at the published widths), ONE expert of a layer (63 MB), one key
head's eight query heads' scores (8 x T x T float32, 0.13 GB at a check's
T = 2,048), and in the delta-rule scan one state of [heads, K, V] (4 MB).
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
# DeepSeek-V3's router: weights / (sum + 1e-20).
NORM_TOPK_EPS = 1e-20
# Under the L2 norm of a head's q and k.
L2_EPS = 1e-6

# The published depth. A writer of the residual stream (`wo`, `w_out`,
# `w_down`, `shared_down`) is drawn at (2 x depth) ** -0.5 of its fan-in
# ** -0.5, the program's `init_params` rule, and the depth is the model's,
# not the cut's: a pipeline stage's weights are the 48-layer model's.
PUBLISHED_LAYERS = 48


def uniform(key, shape, bound):
    return jax.random.uniform(key, shape, F32, -bound, bound)


def log_uniform_1_16(key, shape):
    """`A_log`: the log of a uniform draw in [1, 16] (Mamba-2's rule, which
    Kimi Linear keeps)."""
    return jnp.log(jax.random.uniform(key, shape, F32, 1.0, 16.0))


def dt_inverse_softplus(key, shape):
    """`dt_bias`: a rate log-uniform in [0.001, 0.1], kept as the value
    whose softplus it is."""
    dt = jnp.exp(jax.random.uniform(key, shape, F32, jnp.log(0.001),
                                    jnp.log(0.1)))
    return dt + jnp.log(-jnp.expm1(-dt))


def leaf_init(path, m: Dict):
    """The rule by which bench/weights.py draws the leaf at `path`, which is
    the program's own (`transformer.init_params`, and the rule of the other
    sparse configurations' references), fixed before any run of this
    configuration: every matrix normal at its fan-in ** -0.5, the writers of
    the residual stream at (2 x 48) ** -0.5 of that, the embedding table at
    1 and the head at d ** -0.5, norms one; the convolutions' taps uniform
    in +-kernel ** -0.5 (a torch Conv1d's default); `a_log` the log of a
    uniform in [1, 16], `dt_bias` the inverse softplus of a log-uniform in
    [0.001, 0.1]; `router_bias` (a trained buffer that a trained model does
    not leave at zero) normal at 0.05. No scale is tuned to a tolerance."""
    name, d = path[-1], m["d_model"]
    out = (2 * PUBLISHED_LAYERS) ** -0.5
    rank, width = m["kda_head_dim"], m["kda_num_heads"] * m["kda_head_dim"]
    if name in ("wq", "wk", "wv", "wg", "w_qkv", "w_fa", "w_ga", "w_beta",
                "router", "w_gate", "w_up", "shared_gate", "shared_up",
                "lm_head"):
        return (normal, d ** -0.5)
    if name in ("w_fb", "w_gb"):
        return (normal, rank ** -0.5)
    if name == "w_out":
        return (normal, width ** -0.5 * out)
    if name == "wo":
        return (normal, (m["n_heads"] * m["head_dim"]) ** -0.5 * out)
    if name == "w_down":
        ff = m["d_ff"] if path[1] == "mlp" else m["moe_intermediate_size"]
        return (normal, ff ** -0.5 * out)
    if name == "shared_down":
        ff = m["n_shared_experts"] * m["moe_intermediate_size"]
        return (normal, ff ** -0.5 * out)
    if name == "embed":
        return (normal, d ** -0.5 if m["tie_embeddings"] else 1.0)
    if name == "conv_w":
        return (uniform, m["kda_short_conv_kernel_size"] ** -0.5)
    if name == "a_log":
        return (log_uniform_1_16,)
    if name == "dt_bias":
        return (dt_inverse_softplus,)
    if name == "router_bias":
        return (normal, 0.05)
    return (ones,)


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def delta_rule(q, k, v, g, beta, state_dtype=F32):
    """The recurrence, a token at a time from a zero state. q, k, g [T, H,
    K], v [T, H, V], beta [T, H]: o [T, H, V] and the state after row T.
    `state_dtype` is float32; a control keeps the state in another
    (`tests/test_solar_open2.py`: the state after a long sequence tells the
    two apart, which first logits do not)."""
    heads, dk, dv = q.shape[1], q.shape[2], v.shape[2]

    def token(s, row):
        q_t, k_t, v_t, g_t, beta_t = row
        s = jnp.exp(g_t)[..., None] * s.astype(F32)
        u = jnp.einsum("hk,hkv->hv", k_t, s)
        s = s + jnp.einsum("hk,hv->hkv", beta_t[:, None] * k_t, v_t - u)
        return s.astype(state_dtype), jnp.einsum("hk,hkv->hv", q_t, s)

    state, o = jax.lax.scan(token, jnp.zeros((heads, dk, dv), state_dtype),
                            (q, k, v, g, beta))
    return o, state


def kda_mixer(y, lp: Dict, m: Dict):
    """The delta-rule mixer on one sequence's normed activations y [T, d],
    behind zeros and from a zero state."""
    t = y.shape[0]
    heads, dk, taps = (m["kda_num_heads"], m["kda_head_dim"],
                       m["kda_short_conv_kernel_size"])
    x = y @ lp["w_qkv"]
    behind = jnp.concatenate([jnp.zeros((taps - 1, x.shape[1]), F32), x])
    conv = sum(behind[j:j + t] * lp["conv_w"][:, j] for j in range(taps))
    q, k, v = (a.reshape(t, heads, dk)
               for a in jnp.split(jax.nn.silu(conv), 3, axis=-1))
    q, k = _l2norm(q) * dk ** -0.5, _l2norm(k)
    rate = jax.nn.softplus((y @ lp["w_fa"]) @ lp["w_fb"] + lp["dt_bias"])
    g = -jnp.exp(lp["a_log"])[:, None] * rate.reshape(t, heads, dk)
    beta = ((2.0 if m["kda_allow_neg_eigval"] else 1.0)
            * jax.nn.sigmoid(y @ lp["w_beta"]))
    o, _ = delta_rule(q, k, v, g, beta)
    o = _rmsnorm(o, lp["gate_norm"], m["norm_eps"]).reshape(t, heads * dk)
    return (o * jax.nn.sigmoid((y @ lp["w_ga"]) @ lp["w_gb"])) @ lp["w_out"]


def attention_mixer(y, lp: Dict, m: Dict):
    """The attention mixer on one sequence's normed activations y [T, d],
    one key head (and its H / KVH query heads) at a time: no position
    embedding, a sigmoid gate of y on the heads' outputs."""
    t = y.shape[0]
    h, kvh, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    group = h // kvh
    q = (y @ lp["wq"]).reshape(t, kvh, group, hd)
    k = (y @ lp["wk"]).reshape(t, kvh, hd)
    v = (y @ lp["wv"]).reshape(t, kvh, hd)
    causal = jnp.tril(jnp.ones((t, t), dtype=bool))

    def key_head(_, qkv):
        q_g, k_g, v_g = qkv                       # [T, group, hd], [T, hd] x 2
        scores = jnp.einsum("qgd,kd->gqk", q_g, k_g) * hd ** -0.5
        scores = jnp.where(causal[None], scores, -jnp.inf)
        return None, jnp.einsum("gqk,kd->qgd",
                                jax.nn.softmax(scores, axis=-1), v_g)

    _, a = jax.lax.scan(key_head, None, (
        jnp.moveaxis(q, 1, 0), jnp.moveaxis(k, 1, 0), jnp.moveaxis(v, 1, 0)))
    a = jnp.moveaxis(a, 0, 1).reshape(t, h * hd)      # [KVH, T, group, hd]
    if "wg" in lp:
        a = a * jax.nn.sigmoid(y @ lp["wg"])
    return a @ lp["wo"]


def _swiglu(y, w_gate, w_up, w_down):
    return (jax.nn.silu(y @ w_gate) * (y @ w_up)) @ w_down


def route(y, router, bias, m: Dict):
    """The chosen experts [T, k] and each token's weight for every expert
    [T, E] (zero outside its choice), over ALL `num_experts`."""
    s = jax.nn.sigmoid(y @ router)
    _, chosen = jax.lax.top_k(s if bias is None else s + bias,
                              m["experts_per_token"])
    rows = jnp.arange(y.shape[0])[:, None]
    w = s[rows, chosen]
    if m["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + NORM_TOPK_EPS)
    w = w * m["routed_scaling_factor"]
    return chosen, jnp.zeros_like(s).at[rows, chosen].set(w)


def held_run(m: Dict):
    """(first, count) of the routed experts whose weights the tree holds."""
    count = m.get("experts_held") or m["num_experts"]
    return m.get("expert_share", 0) * count, count


def experts(y, lp: Dict, expert_at, m: Dict):
    """The routed MLP on normed activations y [T, d]: every HELD expert
    applied to every token, one at a time (`expert_at(e)` gives the e-th
    held expert's (W1, W3, W2) in float32), and the shared expert whole.
    Returns the sum and the chosen experts."""
    chosen, gates = route(y, lp["router"], lp.get("router_bias"), m)
    first, count = held_run(m)

    def add_expert(acc, e):
        gate = jax.lax.dynamic_index_in_dim(gates, first + e, axis=1)
        return acc + gate * _swiglu(y, *expert_at(e)), None

    acc, _ = jax.lax.scan(add_expert, jnp.zeros_like(y), jnp.arange(count))
    if "shared_gate" in lp:
        acc = acc + _swiglu(y, lp["shared_gate"], lp["shared_up"],
                            lp["shared_down"])
    return acc, chosen


def layer(x, kind: str, mixer_lp: Dict, mlp: Dict, expert_at, m: Dict):
    """One decoder layer of `kind` on one sequence x [T, d], float32
    weights: its mixer's leaves and its MLP's (a dense MLP's whole; of a
    routed one the norm, the router and its bias and the shared expert, the
    held experts through `expert_at`). Returns x and the chosen experts
    (None, dense)."""
    eps = m["norm_eps"]
    if kind in ATTENTION:
        x = x + attention_mixer(_rmsnorm(x, mixer_lp["attn_norm"], eps),
                                mixer_lp, m)
    else:
        x = x + kda_mixer(_rmsnorm(x, mixer_lp["norm"], eps), mixer_lp, m)
    y = _rmsnorm(x, mlp["mlp_norm"], eps)
    if "router" not in mlp:
        return x + _swiglu(y, mlp["w_gate"], mlp["w_up"], mlp["w_down"]), None
    out, chosen = experts(y, mlp, expert_at, m)
    return x + out, chosen


def _layers(m: Dict):
    """(kind, mixer stack, index in it, MLP stack, index in it) of every
    layer in order: the first `first_k_dense_replace` MLPs are dense."""
    seen = {"kda": 0, "attn": 0}
    n_dense = m["first_k_dense_replace"] if m["num_experts"] else m["n_layers"]
    for i, kind in enumerate(m["layer_pattern"][:m["n_layers"]]):
        stack = "attn" if kind in ATTENTION else "kda"
        yield (kind, stack, seen[stack],
               *(("mlp", i) if i < n_dense else ("moe", i - n_dense)))
        seen[stack] += 1


def _at(stack: Dict, i):
    return jax.tree.map(lambda a: a[i], stack)


def _upcast(tree):
    return jax.tree.map(lambda a: a.astype(F32), tree)


def _dims(m: Dict):
    return tuple(sorted(m.items()))


@functools.partial(jax.jit, static_argnames=("kind", "dims"))
def _layer_upcast(x, mixer_lp, mlp, stacks, j, kind, dims):
    """One layer on x: the mixer's leaves and the MLP's small ones upcast
    whole, held expert `e` of expert layer `j` read from the whole `stacks`
    (the system's dtype) and upcast alone."""
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
        return rows @ table.astype(F32)


def logits_rows(params: Dict, hidden_rows, m: Dict, chunk: int = 16384):
    """Logits [R, vocab] of a few hidden rows, the head `[d, vocab]` upcast
    a block of columns at a time."""
    head = params["lm_head"]
    return jnp.concatenate(
        [_logits_block(hidden_rows, head[:, i:i + chunk])
         for i in range(0, head.shape[1], chunk)], axis=-1)


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
        logp = jax.nn.log_softmax(x @ params["lm_head"], axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, tokens[1:, None], axis=-1))


@functools.partial(jax.jit, static_argnames=("dims",))
def _loss_and_grads(params, tokens, dims):
    return jax.value_and_grad(
        lambda p: loss(p, tokens, dict(dims)))(_upcast(params))


def loss_and_grads(params: Dict, tokens, m: Dict):
    """Reference loss and gradients on the system's weights upcast whole
    (for a size whose float32 copy and gradients fit), float32."""
    return _loss_and_grads(params, tokens, _dims(m))
