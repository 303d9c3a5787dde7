"""Plain reference of dots.vlm1.inst's language model, one chip's share of
it (the model's public config.json, `model_type` dots_vlm; the block is
DeepSeek-V3's, arXiv:2412.19437, its attention arXiv:2405.04434, its rope
scaling arXiv:2309.00071): the forward pass and the next-token loss in
straightforward `jax.numpy`, float32, `jax.default_matmul_precision(
"highest")`. No cache, no absorbed products, no sorting, no grouped
products, no running softmax, and nothing imported from the program: it
takes the sizes as a plain dict and the weights as a tree of arrays named
as the program names them.

One layer, for x [T, d] (RMSNorm eps from the sizes; norm(x, w) = x /
sqrt(mean(x^2) + eps) * w):

    h   = norm(x, attn_norm)
    c_q = norm(h Wq_a, q_a_norm)                            [T, q_lora_rank]
    q_n = c_q Wq_n  as [T, H, dn];  q_r = c_q Wq_r  as [T, H, dr]
    c   = norm(h Wkv_a, kv_a_norm)                          [T, kv_lora_rank]
    k_r = h Wk_r                                            [T, dr], ONE a token
    k_n = c W_uk  as [T, H, dn];    v = c W_uv  as [T, H, dv]
    rope (YaRN's table, dimension dr) on q_r and k_r alone
    s   = (q_n . k_n + q_r . k_r) * (dn + dr)^-0.5 * m^2,
          m = 0.1 * mscale_all_dim * ln(factor) + 1
    a   = causal softmax(s) v,  x = x + a Wo
    h   = norm(x, mlp_norm)
    a layer below first_k_dense_replace:  x = x + SwiGLU(h), width d_ff
    any other layer:
        sc  = sigmoid(h Wr)                                 [T, E], E = 256
        sel = sc + router_bias           (to choose with, never to weigh with)
        n_group groups of E / n_group; a group's score is the sum of its two
        largest sel; the topk_group best groups are kept, the others masked
        out; the k largest sel among the kept are the token's experts
        w   = sc (not sel) at the chosen, over their sum + 1e-20, times
              routed_scaling_factor
        x   = x + sum_{e chosen AND held here} w_e SwiGLU_e(h)
                + SwiGLU_shared(h)

then a final norm and the untied head. The experts' sum is computed the
dense way: every HELD expert is applied to every token and its output
multiplied by the token's weight for that expert, which is zero where the
token did not choose it.

THE SHARE. This is one of the chips that share every layer: `experts_held`
of the `num_experts` the router chooses among, the `expert_share`-th run of
them (experts [held * share, held * (share + 1))). The router is E wide and
chooses among all E; the stacks hold the held experts' matrices alone; what
the experts that are not here would have added is left out, and that
partial result goes on to the next layer, here as in the program
(model-configs guide, section 4). With every expert held the same code is
the uncut layer, which is how tests/test_mla.py adds the shares up.

How the program holds the published matrices, and that it changes nothing
with seeded weights: `q_b_proj`'s columns, a head's [nope | rope] side by
side in the published layout, are held as the two blocks `wq_n` (every
head's nope columns) and `wq_r` (every head's rope columns);
`kv_a_proj_with_mqa` as `wkv_a` (the latent) and `wk_r` (the rope key);
`kv_b_proj`, a head's [k_nope | v], as `w_uk` and `w_uv`. Each is a
permutation of the published matrix's columns. The published code rotates
INTERLEAVED pairs (2i, 2i + 1) of the rope part; the program and this file
rotate HALF-SPLIT pairs (i, i + dr / 2), as every model of this repo does:
with seeded weights that is a permutation of `wq_r`'s and `wk_r`'s columns
within a head, and the scores are those of the permuted weights.

Departures from the published model, each noted in the configuration file:
weights are random from a seed; `router_bias` (`e_score_correction_bias`)
is zero as published checkpoints start it; no sequence passes
`rope_original_max_position`; the image tower and the multi-token-prediction
module are not here; the loss is the next-token term alone (`seq_aux`'s
balance term belongs to training, and no cell trains this model).

Weights arrive in the dtype the system holds them in and are upcast here a
block at a time: 8 heads of a layer's attention, 2048 columns of the dense
MLP, one expert. At a check's T = 2,816 positions the largest temporaries
are a block's scores and their softmax (8 x T x T float32, 0.25 GB each)
and one block's upcast weights (under 0.2 GB): about 0.9 GB beside the
engine, where a whole layer's scores would be 4 GB and a float32 dense
layer 2.3 GB.
"""

from __future__ import annotations

import functools
import math
from typing import Dict

import jax
import jax.numpy as jnp

from reference.draws import normal, ones

F32 = jnp.float32
EXPERT_LEAVES = ("w_gate", "w_up", "w_down")
HEAD_BLOCK = 8       # heads whose scores exist at once
COLUMN_BLOCK = 2048  # columns of a dense MLP upcast at once

# Fan-in ** -0.5 for every matrix, the writers of the residual stream at
# (2 n_layers) ** -0.5 of that, the embedding table at 1, norms one, the
# router's bias zero: the program's `init_params`.
_FAN_IN = {
    "wq_a": "d_model", "wkv_a": "d_model", "wk_r": "d_model",
    "w_uk": "kv_lora_rank", "w_uv": "kv_lora_rank", "router": "d_model",
    "w_gate": "d_model", "w_up": "d_model", "shared_gate": "d_model",
    "shared_up": "d_model", "lm_head": "d_model",
}


def zeros(key, shape):
    return jnp.zeros(shape, F32)


def leaf_init(path, m: Dict):
    """The rule by which bench/weights.py draws the leaf at `path`."""
    name = path[-1]
    out = (2 * m["n_layers"]) ** -0.5
    if name in _FAN_IN:
        return (normal, m[_FAN_IN[name]] ** -0.5)
    if name in ("wq_n", "wq_r"):
        return (normal, (m["q_lora_rank"] or m["d_model"]) ** -0.5)
    if name == "wo":
        return (normal, (m["n_heads"] * m["v_head_dim"]) ** -0.5 * out)
    if name == "w_down":
        ff = m["d_ff"] if path[1] == "dense" else m["moe_intermediate_size"]
        return (normal, ff ** -0.5 * out)
    if name == "shared_down":
        ff = m["n_shared_experts"] * m["moe_intermediate_size"]
        return (normal, ff ** -0.5 * out)
    if name == "embed":
        return (normal, 1.0)
    if name == "router_bias":
        return (zeros,)
    return (ones,)


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def yarn_inv_freq(m: Dict):
    """The rope part's frequencies [dr / 2]: plain rope's f_i =
    theta^(-2i/dr), divided by `factor` for the pairs that turn fewer than
    `beta_slow` times over the original length, kept for those that turn
    more than `beta_fast` times, blended linearly in i between."""
    dr, theta = m["qk_rope_head_dim"], m["rope_theta"]
    f = theta ** (-jnp.arange(0, dr, 2, dtype=F32) / dr)
    if m["rope_factor"] <= 1.0:
        return f

    def pair_of(rotations):
        return (dr * math.log(m["rope_original_max_position"]
                              / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(pair_of(m["rope_beta_fast"])), 0)
    high = min(math.ceil(pair_of(m["rope_beta_slow"])), dr - 1)
    ramp = jnp.clip((jnp.arange(dr // 2, dtype=F32) - low)
                    / max(high - low, 0.001), 0.0, 1.0)
    return f / m["rope_factor"] * ramp + f * (1.0 - ramp)


def _mscale(factor, coefficient):
    if factor <= 1.0 or not coefficient:
        return 1.0
    return 0.1 * coefficient * math.log(factor) + 1.0


def _rope(x, m: Dict):
    """x [T, H, dr]; position t rotates pair (i, i + dr/2) by t *
    inv_freq_i; cos and sin times mscale(factor, mscale) / mscale(factor,
    mscale_all_dim)."""
    t, _, dr = x.shape
    ang = jnp.arange(t, dtype=F32)[:, None] * yarn_inv_freq(m)[None, :]
    mag = (_mscale(m["rope_factor"], m["rope_mscale"])
           / _mscale(m["rope_factor"], m["rope_mscale_all_dim"]))
    cos, sin = (jnp.cos(ang) * mag)[:, None, :], (jnp.sin(ang) * mag)[:, None, :]
    x1, x2 = x[..., : dr // 2], x[..., dr // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _score_scale(m: Dict):
    dim = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    return dim ** -0.5 * _mscale(m["rope_factor"],
                                 m["rope_mscale_all_dim"]) ** 2


def _head_block(m: Dict):
    return max(b for b in range(1, HEAD_BLOCK + 1) if m["n_heads"] % b == 0)


def attention(x, weight, m: Dict):
    """The attention half of a layer on one sequence x [T, d]: x +
    attention(norm(x)), the heads a block at a time. `weight(name, first,
    count)` gives columns (rows, for `wo`) [first, first + count) of the
    layer's leaf `name` in float32, `weight(name)` a whole leaf."""
    t = x.shape[0]
    h, dn, dr, dv = (m["n_heads"], m["qk_nope_head_dim"],
                     m["qk_rope_head_dim"], m["v_head_dim"])
    eps, hb = m["norm_eps"], _head_block(m)
    y = _rmsnorm(x, weight("attn_norm"), eps)
    c_q = y
    if m["q_lora_rank"]:
        c_q = _rmsnorm(y @ weight("wq_a"), weight("q_a_norm"), eps)
    c = _rmsnorm(y @ weight("wkv_a"), weight("kv_a_norm"), eps)
    k_r = _rope((y @ weight("wk_r"))[:, None, :], m)[:, 0]   # [T, dr]
    causal = jnp.tril(jnp.ones((t, t), dtype=bool))

    def block(acc, g):
        q_n = (c_q @ weight("wq_n", g * hb * dn, hb * dn)).reshape(t, hb, dn)
        q_r = _rope((c_q @ weight("wq_r", g * hb * dr, hb * dr)
                     ).reshape(t, hb, dr), m)
        k_n = (c @ weight("w_uk", g * hb * dn, hb * dn)).reshape(t, hb, dn)
        v = (c @ weight("w_uv", g * hb * dv, hb * dv)).reshape(t, hb, dv)
        scores = (jnp.einsum("qhd,khd->hqk", q_n, k_n)
                  + jnp.einsum("qhd,kd->hqk", q_r, k_r)) * _score_scale(m)
        scores = jnp.where(causal[None], scores, -jnp.inf)
        a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
        return acc + a.reshape(t, hb * dv) @ weight("wo", g * hb * dv,
                                                    hb * dv), None

    out, _ = jax.lax.scan(block, jnp.zeros_like(x), jnp.arange(h // hb))
    return x + out


def route(y, router, bias, m: Dict):
    """Scores sc [T, E], the chosen experts [T, k] and each token's weight
    for every expert [T, E] (zero outside its choice)."""
    t, e = y.shape[0], m["num_experts"]
    k, groups = m["experts_per_token"], m["n_group"]
    if m["scoring_func"] != "sigmoid":
        raise ValueError("this reference scores by sigmoid")
    sc = jax.nn.sigmoid(y @ router)
    sel = sc + bias
    if groups > 1:
        grouped = sel.reshape(t, groups, e // groups)
        group_score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
        _, kept = jax.lax.top_k(group_score, m["topk_group"])
        keep = jnp.any(kept[:, :, None] == jnp.arange(groups)[None, None, :],
                       axis=1)                                  # [T, G]
        sel = jnp.where(keep[:, :, None], grouped, -jnp.inf).reshape(t, e)
    _, chosen = jax.lax.top_k(sel, k)
    rows = jnp.arange(t)[:, None]
    w = sc[rows, chosen]
    if m["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * m["routed_scaling_factor"]
    return sc, chosen, jnp.zeros_like(sc).at[rows, chosen].set(w)


def _swiglu(y, w_gate, w_up, w_down):
    return (jax.nn.silu(y @ w_gate) * (y @ w_up)) @ w_down


def dense_mlp(x, weight, m: Dict):
    """x + SwiGLU(norm(x)) of width d_ff, a block of columns at a time."""
    y = _rmsnorm(x, weight("mlp_norm"), m["norm_eps"])
    ff = m["d_ff"]
    cb = COLUMN_BLOCK if ff % COLUMN_BLOCK == 0 else ff

    def block(acc, g):
        return acc + _swiglu(y, weight("w_gate", g * cb, cb),
                             weight("w_up", g * cb, cb),
                             weight("w_down", g * cb, cb)), None

    out, _ = jax.lax.scan(block, jnp.zeros_like(x), jnp.arange(ff // cb))
    return x + out


def held_range(m: Dict):
    held = m["experts_held"] or m["num_experts"]
    return m["expert_share"] * held, held


def experts(x, weight, expert_at, m: Dict):
    """The expert half of a layer: x + sum over the HELD experts of gate[:,
    e] * expert_e(norm(x)) + the shared expert, every held expert applied to
    every token, one at a time. `expert_at(j)` gives the j-th held expert's
    (Wgate, Wup, Wdown) in float32. Also returns the chosen experts."""
    y = _rmsnorm(x, weight("mlp_norm"), m["norm_eps"])
    _, chosen, gates = route(y, weight("router"), weight("router_bias"), m)
    first, held = held_range(m)

    def add_expert(acc, j):
        out = _swiglu(y, *expert_at(j))
        gate = jax.lax.dynamic_index_in_dim(gates, first + j, axis=1)
        return acc + gate * out, None

    acc, _ = jax.lax.scan(add_expert, jnp.zeros_like(x), jnp.arange(held))
    if m["n_shared_experts"]:
        acc = acc + _swiglu(y, weight("shared_gate"), weight("shared_up"),
                            weight("shared_down"))
    return x + acc, chosen


def _dims(m: Dict):
    return tuple(sorted(m.items()))


@functools.partial(jax.jit, static_argnames=("dims", "kind"))
def _layer_at(x, stack, i, dims, kind):
    """Layer `i` of its kind's `stack` (the system's dtype) on x, every
    weight read from the stack and upcast a block at a time."""
    m = dict(dims)

    def weight(name, first=None, count=None):
        leaf = stack[name]
        if first is None:
            return leaf[i].astype(F32)
        if name in ("wo", "w_down"):  # a block of rows
            return jax.lax.dynamic_slice(
                leaf, (i, first, 0), (1, count, leaf.shape[2]))[0].astype(F32)
        return jax.lax.dynamic_slice(
            leaf, (i, 0, first), (1, leaf.shape[1], count))[0].astype(F32)

    def expert_at(j):
        return tuple(jax.lax.dynamic_slice(
            stack[n], (i, j, 0, 0), (1, 1) + stack[n].shape[2:]
        )[0, 0].astype(F32) for n in EXPERT_LEAVES)

    with jax.default_matmul_precision("highest"):
        x = attention(x, weight, m)
        if kind == "dense":
            return dense_mlp(x, weight, m), None
        return experts(x, weight, expert_at, m)


def _kinds(params: Dict, m: Dict):
    """(kind, index within its kind's stack) of every layer: the first
    `first_k_dense_replace` (all, of a model without experts) are dense."""
    n_dense = (m["first_k_dense_replace"] if "moe" in params["layers"]
               else m["n_layers"])
    return [("dense", i) if i < n_dense else ("moe", i - n_dense)
            for i in range(m["n_layers"])]


def _walk(params: Dict, tokens, m: Dict):
    x = params["embed"][tokens].astype(F32)
    chosen = []
    for kind, i in _kinds(params, m):
        x, c = _layer_at(x, params["layers"][kind], jnp.int32(i), _dims(m),
                         kind)
        if c is not None:
            chosen.append(c)
    return x, chosen


def hidden_layerwise(params: Dict, tokens, m: Dict):
    """Final-norm hidden states [T, d] of one sequence; the model never
    exists in float32, nor does one layer of it."""
    x, _ = _walk(params, tokens, m)
    return _rmsnorm(x, params["final_norm"].astype(F32), m["norm_eps"])


def routing_layerwise(params: Dict, tokens, m: Dict):
    """The experts the reference chooses, [expert layers, T, k], sorted
    within a token."""
    _, chosen = _walk(params, tokens, m)
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
    """The same loss, differentiable in float32 `params` held whole."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens[:-1]]
        for kind, i in _kinds(params, m):
            stack = params["layers"][kind]

            def weight(name, first=None, count=None, stack=stack, i=i):
                w = stack[name][i]
                if first is None:
                    return w
                if name in ("wo", "w_down"):
                    return jax.lax.dynamic_slice_in_dim(w, first, count, 0)
                return jax.lax.dynamic_slice_in_dim(w, first, count, 1)

            x = attention(x, weight, m)
            if kind == "dense":
                x = dense_mlp(x, weight, m)
            else:
                x, _ = experts(
                    x, weight, lambda j, stack=stack, i=i: tuple(
                        stack[n][i][j] for n in EXPERT_LEAVES), m)
        x = _rmsnorm(x, params["final_norm"], m["norm_eps"])
        logp = jax.nn.log_softmax(x @ params["lm_head"], axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, tokens[1:, None], axis=-1))


@functools.partial(jax.jit, static_argnames=("dims",))
def _loss_and_grads(params, tokens, dims):
    upcast = jax.tree.map(lambda a: a.astype(F32), params)
    return jax.value_and_grad(lambda p: loss(p, tokens, dict(dims)))(upcast)


def loss_and_grads(params: Dict, tokens, m: Dict):
    """Reference loss and gradients on the system's weights upcast whole
    (for a size whose float32 copy and gradients fit), float32."""
    return _loss_and_grads(params, tokens, _dims(m))
