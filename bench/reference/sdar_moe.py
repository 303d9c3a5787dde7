"""Plain reference of SDAR-30B-A3B-Chat (`model_type` sdar_moe; SDAR,
"Synergistic Diffusion-AutoRegression", arXiv:2510.06303; the model's public
config.json and, for what the config has no key for, Qwen3-MoE's modelling
code, which sdar_moe derives from): the decoder's forward pass under its
block-causal mask, and a REPLAY of generation by diffusion over blocks, in
straightforward `jax.numpy`, float32, `jax.default_matmul_precision(
"highest")`. No kernels, no cache, no pages, no sorting, no grouped
products, and nothing imported from the program: the sizes come as a plain
dict and the weights as a tree of arrays named as the program names them.

One layer, on rows x_i at positions p_i, block of a position b_i = p_i // B:

    h = rmsnorm(x, attn_norm)
    q = rmsnorm_head(h Wq, q_norm);  k = rmsnorm_head(h Wk, k_norm);  v = h Wv
        32 query and 4 key-value heads of 128; the norm over the 128 of a head
    rope (rotate-half, theta 1e6) at p_i;  query head g reads kv head g // 8
    a_i = sum_j softmax_j(q_i . k_j / sqrt(128) + M_ij) v_j
        M_ij = 0 where b_j <= b_i, else -inf: whole blocks before, and every
        position of the own block, later ones too
    x = x + a Wo
    h = rmsnorm(x, mlp_norm);  s = softmax(h Wr) over the 128 experts
    the 8 largest, their weights divided by their sum (`norm_topk_prob`)
    x = x + sum_e w_e (silu(h Wgate_e) * (h Wup_e)) Wdown_e

then a final rmsnorm and the untied head. NO SHIFT: the logits at position p
are for the token AT p (a masked position predicts itself). The experts' sum
is computed the dense way, every expert on every row, one expert at a time.

Generation, as the program's engine runs it and as `replay` recomputes it
from the tokens alone. A block of B positions starts as the mask token
everywhere (but where a prompt's remainder fills it). A DENOISING pass runs
the block's B rows (what is filled; the mask token where not) against every
earlier block CLEAN and against itself whole, and fills some masked positions
with tokens drawn from the logits at those positions; when none is masked a
COMMIT pass runs the B clean tokens, and what it computes is what later
blocks see. So the token at position p, drawn in pass s of its block, comes
from a forward pass whose input is: every earlier block clean; of its own
block the positions filled before pass s, and the mask token at the others.
`replay` takes `pass_of [T]`, the pass in which each position was drawn (-1:
never drawn, clean from the start: a prompt), and computes ALL blocks at
once as `1 + passes` streams of length T, layer by layer: the clean stream
(block-causal), and one noised stream a pass `s` whose input at position p
is the token where `pass_of[p] < s` and the mask token elsewhere, and whose
keys and values are the CLEAN stream's for earlier blocks and its own for
its own block. Row p of the result is the final-norm hidden state of stream
`pass_of[p]` at p: what the token at p was drawn from.

`hidden_layerwise` is the harness's entry (`bench/serve_cell.py`
`reference_check`, written for next-token models: it hands over `prompt +
served[:-1]` padded with zeros, takes rows `[len(prompt) - 1 : len(seq)]`
and holds served token `i` to the top of row `i`'s logits, so that ROW j IS
READ AS THE STATE FROM WHICH THE TOKEN AT POSITION j + 1 IS DRAWN). It
replays the strategy "sequential" (each pass fills the next `B /
denoise_steps` masked positions from the left) with EVERY block treated as
generated: `pass_of[p] = (p % B) // (B / denoise_steps)`, and returns the
replay shifted by one row, row T - 1 zeros. A prompt's rows are then states
no request was drawn from, and the harness reads none of them but the last,
row `len(prompt) - 1`, the state of the first generated position: so a
prompt must end on a block boundary (the configuration's `check.prompt_lens`
are multiples of B), and the zeros behind the sequence never reach a row
that is read (a later block is not seen; of the last block's own positions
a noised stream sees the mask token at and after the one it draws). An
order chosen by confidence cannot be replayed from tokens alone; a caller
that knows the order (the tier-1 tests: the engine reports it) passes it to
`replay`.

Departures from the published model, each noted in the configuration's file:
weights are random from a seed; `block_length`, `denoise_steps` and
`mask_token_id` are the released generation settings and reach this file in
`dims`; the training loss needs a noise schedule the published config does
not give, so `loss_and_grads` and `loss_layerwise` raise.

Weights arrive in the dtype the system holds them in and are upcast here, a
layer's attention weights together and ONE EXPERT AT A TIME (a float32 copy
of one layer's 128 experts is 2.4 GB, beside 11.7 GB of the replica's own
arguments). At a check's T = 2,048 and three streams the peak is the scores
of one key-value head's 8 query heads against 2T keys, 0.27 GB in float32 and
as much again for their softmax, and 50 MB of activations.
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp

from reference.draws import normal, ones

F32 = jnp.float32
STACKS = ("w_gate", "w_up", "w_down")


def leaf_init(path, m: Dict):
    """The rule (reference/draws.py) by which bench/weights.py draws the
    leaf at `path`: the program's (`transformer.init_params`). Every matrix
    normal at its fan-in ** -0.5; the two writers of the residual stream
    (`wo`, an expert's `w_down`) at (2 x n_layers) ** -0.5 of that,
    `n_layers` being the layers that RUN (6 in the benchmark's cut, as
    `init_params` takes them from the program's config), not the published
    48; the table at 1 (the mask token's row a row like any other) and the
    untied head at d ** -0.5; norms ones.

    Why the depth that runs: every masked position enters as the same row
    of the table, so what a drawn token depends on beside that row is what
    the layers add. At (2 x 48) ** -0.5 six layers add a tenth of the
    stream, a served block is the same token four times, and a pass that
    attends to the wrong rows reads like a sound one (PERF.md section 6,
    PR 68: three controls at 0.0114-0.0116 beside sound runs at
    0.0058-0.0135)."""
    name, d = path[-1], m["d_model"]
    out = (2 * m["n_layers"]) ** -0.5
    if name in ("wq", "wk", "wv", "router", "w_gate", "w_up", "lm_head"):
        return (normal, d ** -0.5)
    if name == "wo":
        return (normal, (m["n_heads"] * m["head_dim"]) ** -0.5 * out)
    if name == "w_down":
        return (normal, m["moe_intermediate_size"] ** -0.5 * out)
    if name == "embed":
        return (normal, 1.0)
    return (ones,)


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rotate(x, theta):
    """x [T, H, hd] at positions 0 .. T-1: pair (i, i + hd/2) turned by
    t * theta ** (-2i / hd)."""
    t, _, hd = x.shape
    freq = theta ** (-jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = jnp.arange(t, dtype=F32)[:, None, None] * freq[None, None, :]
    lo, hi = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([lo * jnp.cos(ang) - hi * jnp.sin(ang),
                            hi * jnp.cos(ang) + lo * jnp.sin(ang)], axis=-1)


def _heads(x, lp: Dict, m: Dict):
    """Queries `[T, KVH, H/KVH, hd]`, keys and values `[T, KVH, hd]` of
    the rows x [T, d] of one stream, and the normed input is not kept."""
    t = x.shape[0]
    h, kvh, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    y = _norm(x, lp["attn_norm"], m["norm_eps"])
    q = _norm((y @ lp["wq"]).reshape(t, h, hd), lp["q_norm"], m["norm_eps"])
    k = _norm((y @ lp["wk"]).reshape(t, kvh, hd), lp["k_norm"], m["norm_eps"])
    v = (y @ lp["wv"]).reshape(t, kvh, hd)
    q, k = _rotate(q, m["rope_theta"]), _rotate(k, m["rope_theta"])
    return q.reshape(t, kvh, h // kvh, hd), k, v


def _attend(q, keys, values, seen, m: Dict):
    """`a [T, H * hd]` of queries `[T, KVH, G, hd]` over `keys`, `values
    [K, KVH, hd]` where `seen [T, K]`; a key-value head at a time."""

    def one(args):
        qh, kh, vh = args                           # [T, G, hd], [K, hd] x 2
        scores = jnp.einsum("tgd,kd->gtk", qh, kh) / jnp.sqrt(F32(m["head_dim"]))
        p = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("gtk,kd->tgd", p, vh)

    a = jax.lax.map(one, (q.swapaxes(0, 1), keys.swapaxes(0, 1),
                          values.swapaxes(0, 1)))    # [KVH, T, G, hd]
    return a.swapaxes(0, 1).reshape(q.shape[0], -1)


def attention(xs, lp: Dict, m: Dict):
    """The attention half of a layer on the streams xs [1 + S, T, d],
    stream 0 clean: x + attention(rmsnorm(x)) Wo for each. The clean stream
    sees clean keys block-causally; a noised stream sees the clean stream's
    keys of earlier blocks and its own keys of its own block."""
    t = xs.shape[1]
    blk = jnp.arange(t) // m["block_length"]
    before, own = blk[None, :] < blk[:, None], blk[None, :] == blk[:, None]
    q0, k0, v0 = _heads(xs[0], lp, m)
    out = [xs[0] + _attend(q0, k0, v0, before | own, m) @ lp["wo"]]
    for x in xs[1:]:
        q, k, v = _heads(x, lp, m)
        a = _attend(q, jnp.concatenate([k0, k]), jnp.concatenate([v0, v]),
                    jnp.concatenate([before, own], axis=1), m)
        out.append(x + a @ lp["wo"])
    return jnp.stack(out)


def route(y, router, m: Dict):
    """Each row's weight for every expert [R, E]: the softmax over all
    experts, the `experts_per_token` largest kept and, where
    `norm_topk_prob`, divided by their sum; zero elsewhere."""
    p = jax.nn.softmax(y @ router, axis=-1)
    w, chosen = jax.lax.top_k(p, m["experts_per_token"])
    if m["norm_topk_prob"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return jnp.zeros_like(p).at[jnp.arange(y.shape[0])[:, None], chosen].set(w)


def experts(x, lp: Dict, m: Dict, expert_at):
    """The expert half of a layer on rows x [R, d]: x + sum_e gates[:, e] *
    expert_e(rmsnorm(x)), every expert on every row, one at a time;
    `expert_at(e)` gives expert e's (Wgate, Wup, Wdown) in float32."""
    y = _norm(x, lp["mlp_norm"], m["norm_eps"])
    gates = route(y, lp["router"], m)

    def add(acc, e):
        w_gate, w_up, w_down = expert_at(e)
        return acc + gates[:, e, None] * (
            (jax.nn.silu(y @ w_gate) * (y @ w_up)) @ w_down), None

    acc, _ = jax.lax.scan(add, jnp.zeros_like(x),
                          jnp.arange(m["num_experts"]))
    return x + acc


def _dims(m: Dict):
    return tuple(sorted(m.items()))


@functools.partial(jax.jit, static_argnames=("dims",))
def _layer_at(xs, layers, i, dims):
    """Layer `i` of the stacked `layers` (the system's dtype) on the
    streams xs [1 + S, T, d]: the small leaves upcast together, an expert
    read from the stack and upcast when its turn comes."""
    m = dict(dims)
    with jax.default_matmul_precision("highest"):
        lp = {n: a[i].astype(F32) for n, a in layers.items()
              if n not in STACKS}
        xs = attention(xs, lp, m)

        def expert_at(e):
            return tuple(jax.lax.dynamic_slice(
                layers[n], (i, e, 0, 0), (1, 1) + layers[n].shape[2:]
            )[0, 0].astype(F32) for n in STACKS)

        rows = experts(xs.reshape(-1, xs.shape[-1]), lp, m, expert_at)
        return rows.reshape(xs.shape)


def _streams(params: Dict, tokens, m: Dict, pass_of, passes: int):
    """Final-norm hidden states [1 + passes, T, d] of the clean stream and
    of one noised stream a pass."""
    mask = jnp.int32(m["mask_token_id"])
    fed = jnp.stack([tokens] + [jnp.where(pass_of < s, tokens, mask)
                                for s in range(passes)])
    xs = params["embed"][fed].astype(F32)
    for i in range(m["n_layers"]):
        xs = _layer_at(xs, params["layers"], jnp.int32(i), _dims(m))
    return _norm(xs, params["final_norm"].astype(F32), m["norm_eps"])


def clean_hidden(params: Dict, tokens, m: Dict):
    """Final-norm hidden states [T, d] of the clean sequence under the
    block-causal mask: the model's plain forward pass, what a prefill and
    the commit passes leave behind."""
    return _streams(params, tokens, m, jnp.zeros_like(tokens), 0)[0]


def replay(params: Dict, tokens, m: Dict, pass_of, passes: int):
    """[T, d]: row p is the final-norm hidden state from which the token
    AT position p was drawn, `pass_of [T]` saying in which pass of its
    block (0 .. passes - 1; -1: never drawn, and the row is the clean
    stream's)."""
    pass_of = jnp.asarray(pass_of, jnp.int32)
    hidden = _streams(params, tokens, m, pass_of, passes)
    return jnp.take_along_axis(hidden, (pass_of + 1)[None, :, None],
                               axis=0)[0]


def sequential_passes(length: int, m: Dict):
    """`pass_of [length]` of the strategy "sequential" with every block
    generated: position p is drawn in pass (p % B) // (B / steps)."""
    per_pass = m["block_length"] // m["denoise_steps"]
    return (jnp.arange(length) % m["block_length"]) // per_pass


def hidden_layerwise(params: Dict, tokens, m: Dict):
    """The harness's rows [T, d], indexed as for a next-token model: row j
    is the state from which the token at position j + 1 is drawn under
    "sequential", every block generated (the module's docstring has why
    that is what `reference_check` can read); row T - 1 is zeros."""
    rows = replay(params, tokens, m, sequential_passes(tokens.shape[0], m),
                  m["denoise_steps"])
    return jnp.concatenate([rows[1:], jnp.zeros_like(rows[:1])])


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


def _no_loss(*_args, **_kwargs):
    raise NotImplementedError(
        "SDAR's training loss is over noised blocks, and the noise schedule "
        "is not in the published config (the catalog marks it not_given); "
        "this reference serves and replays generation, and no training "
        "cell names it")


loss_and_grads = _no_loss
loss_layerwise = _no_loss
