"""The mixture-of-experts block: dropless, sorted, grouped matmuls.

The reference has no MoE support (SURVEY.md §2.4: EP "Absent"). One
function serves the training layer, the cached forward and the engine's
step programs. A token's router picks its `experts_per_token` experts in
float32; the `tokens x k` assignments are sorted by expert, so that each
expert's rows lie together; the three expert products run as grouped
matmuls over the `[E, d, ff]` stacks in the weights' dtype with float32
accumulation; the rows go back to token order, weighted, and are summed per
token. Every assignment is computed: there is no capacity and nothing is
dropped.

The grouped matmul is the Pallas kernel JAX ships
(`jax.experimental.pallas.ops.tpu.megablox`): it visits only the groups
that have rows, so a decode step reads the experts it hit and no others.
`jax.lax.ragged_dot` lowers to the same kind of kernel on the TPU with a
row tile of the compiler's choosing, which at a prefill chunk's 512 rows
is compute-bound on masked rows: 2.53 against 1.15 ms a layer at OLMoE's
sizes, and 1.15 against 0.96 ms at a decode step's 128 rows (chip, PR 27;
PERF.md section 6). Off the TPU the kernel runs in Pallas's interpreter,
so the CPU tests run the same code.

Under a mesh with `ep > 1` the same function runs on expert stacks sharded
over "ep"; placing the kernel per shard belongs to the expert-parallel
work (ROADMAP.md B6).
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

EXPERT_LEAVES = ("w_gate", "w_up", "w_down")
# The kernel's tiles: `(tm, tk, tn)` over an `[m, k] x [G, k, n]` product.
# Rows of one tile belong to at most a few experts, and the weight tile
# `(tk, tn)` is what a group with rows costs to read. What a step moves
# besides it is the rows' tile `(tm, tk)`, fetched again at every step
# unless one tile spans `k` (the block's index then stays put from group to
# group): `tm / tn` of the weights' bytes, an eighth at (128, 1024, 1024).
# A width that no tile divides costs a step that is mostly past the edge
# in `n`, and in `k` a mask over both operands on the last step. The kernel
# alone on the chip (`tools/gmm_sweep.py`, PR 64; PERF.md section 6), share
# of 819 GB/s a decode step's product reads its weights at: `k` whole
# 88-92% at every width of the four serving cells (OLMoE 2048 x 1024 and
# back, LFM2 2048 x 1536, Solar 4096 x 1280, dots' 2048 x 7168), `k` split
# 81-82% at `tn` 1024, 83-84% at 1280, 86-87% at 2048, 74% at 512,
# whatever `tk`; (128, 1024, 1024) over 1280 and 1536 59-81%; a row tile
# under 128 loses at 5-6 rows a group (a group astride two row tiles is
# read twice). The scoped VMEM a kernel compiles under is 16 MiB.
_ROW_TILE = 128
_WEIGHT_TILE = 1024       # the plain tile's side: what no divisor falls back to
_STEP_BYTES = 13 << 20    # a step's buffers; the rest is the compiler's


def _row_tile(m: int) -> int:
    return min(_ROW_TILE, -(-m // 8) * 8)


def _sides(x: int):
    """The tile sides that leave a dimension of `x` no remainder: `x` whole
    up to the plain tile, beyond it the divisors that are multiples of 128
    and at least half the plain tile."""
    if x <= _WEIGHT_TILE:
        return [x]
    return [t for t in range(_WEIGHT_TILE // 2, x + 1, 128) if x % t == 0]


def gmm_tiling(m: int, k: int, n: int, itemsize: int) -> Tuple[int, int, int]:
    """The `(tm, tk, tn)` of an `[m, k] x [G, k, n]` grouped product whose
    operands have `itemsize` bytes an element, from the shapes alone: of
    the tiles that divide `k` and `n` and fit, the one that fetches the
    rows' tile least often, then the largest."""
    tm = _row_tile(m)

    def fits(tk, tn):
        # Two buffers of each operand and of the float32 result, and the
        # float32 accumulator.
        return (2 * (tk * tn + tm * tk) * itemsize + 3 * tm * tn * 4
                <= _STEP_BYTES)

    pairs = [(tk, tn) for tk in _sides(k) for tn in _sides(n) if fits(tk, tn)]
    if not pairs:  # nothing divides and fits: the kernel's masks take the rest
        return tm, min(k, _WEIGHT_TILE), min(n, _WEIGHT_TILE)
    tk, tn = max(pairs, key=lambda t: (
        0 if t[0] == k else -tm / t[1], t[0] * t[1]))
    return tm, tk, tn


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


@jax.custom_vjp
def _gmm(x: jax.Array, w: jax.Array, group_sizes: jax.Array) -> jax.Array:
    """The kernel on `x` of whole row tiles. The two products of the
    backward pass keep the plain tile: their shapes are other shapes, and
    `tgmm` holds a float32 `(tk, tn)` where `gmm` holds `(tm, tn)`."""
    return _gmm_fwd(x, w, group_sizes)[0]


def _gmm_fwd(x, w, group_sizes):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

    (m, k), n = x.shape, w.shape[-1]
    out = gmm(x, w, group_sizes, jnp.float32,
              gmm_tiling(m, k, n, w.dtype.itemsize), interpret=_interpret())
    return out, (x, w, group_sizes)


def _gmm_bwd(residual, grad):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm

    x, w, group_sizes = residual
    (m, k), n = x.shape, w.shape[-1]
    plain = (_row_tile(m), min(k, _WEIGHT_TILE), min(n, _WEIGHT_TILE))
    grad_x = gmm(grad, w, group_sizes, x.dtype, plain, transpose_rhs=True,
                 interpret=_interpret())
    grad_w = tgmm(x.swapaxes(0, 1), grad, group_sizes, w.dtype, plain,
                  num_actual_groups=w.shape[0], interpret=_interpret())
    return grad_x, grad_w, None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def grouped_matmul(x: jax.Array, w: jax.Array,
                   group_sizes: jax.Array) -> jax.Array:
    """`x [m, k]` whose rows lie grouped, in the order of `w [G, k, n]`'s
    groups and `group_sizes [G]` long each, times each row's own group's
    matrix: `[m, n]` float32. Groups without rows are not read."""
    m = x.shape[0]
    # Rows past the groups' total belong to no group and come back zero.
    x = jnp.pad(x, ((0, -m % _row_tile(m)), (0, 0)))
    return _gmm(x, w, group_sizes)[:m]


def load_balancing_loss(prob_mean: jax.Array, counts: jax.Array,
                        tokens: int) -> jax.Array:
    """E * sum_e P_e * sum_j f_{j,e}: the published load-balancing term
    (Switch's, as Mixtral's and OLMoE's modelling code has it, before its
    coefficient). `prob_mean [.., E]` is the router probability of each
    expert averaged over the tokens, `counts [.., E]` the assignments each
    expert received from those `tokens` tokens, so counts / tokens is
    sum_j f_{j,e}, the shares of tokens whose j-th choice is e. Leading
    axes (layers) are averaged first: the published code concatenates every
    layer's router outputs and takes one mean. Even routing gives k."""
    num_experts = prob_mean.shape[-1]
    p = prob_mean.reshape(-1, num_experts).mean(0)
    f = (counts.astype(jnp.float32) / tokens).reshape(-1, num_experts).mean(0)
    return num_experts * jnp.sum(p * f)


def route(logits: jax.Array, cfg, bias: Optional[jax.Array] = None):
    """A token's choice of experts from its router logits `[tokens, E]`
    float32: `(scores [tokens, E], weights [tokens, k], chosen [tokens,
    k])`. "softmax": the k largest probabilities, renormalised where
    `norm_topk_prob` says so (Mixtral, OLMoE). "sigmoid" (DeepSeek-V3's
    `noaux_tc`, lfm2_moe's router): every expert's sigmoid score; the
    choice is made on score + `bias [E]` (the layer's `router_bias`, where
    the model has one) and, with `n_group` > 1, within the `topk_group`
    groups whose two best sum highest; the weights are the chosen experts'
    scores without the bias, over their sum + `norm_topk_eps` where
    `norm_topk_prob` (1e-20 as DeepSeek-V3's code has it, 1e-6 as
    lfm2_moe's), times `routed_scaling_factor`."""
    k = cfg.experts_per_token
    if cfg.scoring_func == "softmax":
        probs = jax.nn.softmax(logits, axis=-1)
        weights, chosen = jax.lax.top_k(probs, k)            # [tokens, k]
        if cfg.norm_topk_prob:
            weights = weights / weights.sum(-1, keepdims=True)
        return probs, weights, chosen
    if cfg.scoring_func != "sigmoid":
        raise ValueError(f"unknown scoring_func {cfg.scoring_func!r}: "
                         "expected 'softmax' or 'sigmoid'")
    tokens, num_experts = logits.shape
    scores = jax.nn.sigmoid(logits)
    choose = scores if bias is None else scores + bias.astype(jnp.float32)
    if cfg.n_group > 1:
        groups = choose.reshape(tokens, cfg.n_group, -1)
        best = jax.lax.top_k(groups, 2)[0].sum(-1)           # [tokens, G]
        _, kept = jax.lax.top_k(best, cfg.topk_group)
        keep = jnp.zeros(best.shape, bool).at[
            jnp.arange(tokens)[:, None], kept].set(True)
        choose = jnp.where(keep[:, :, None], groups, -jnp.inf).reshape(
            tokens, num_experts)
    _, chosen = jax.lax.top_k(choose, k)
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    if cfg.norm_topk_prob:
        weights = weights / (weights.sum(-1, keepdims=True)
                             + cfg.norm_topk_eps)
    return scores, weights * cfg.routed_scaling_factor, chosen


def moe_block(h: jax.Array, lp: Dict, cfg,
              layer: Optional[jax.Array] = None,
              router_input: Optional[jax.Array] = None
              ) -> Tuple[jax.Array, Dict]:
    """The expert half of a layer on normed activations `h [tokens, d]`.
    The router multiplies `router_input [tokens, d]` where a caller hands
    one (a model whose router reads another tensor than its experts do:
    `cfg.router_reads`), else `h`.

    `lp` holds the layer's `router [d, E]` and the expert stacks `w_gate`,
    `w_up [E, d, ff]`, `w_down [E, ff, d]`. A caller that walks the depth
    axis by index passes `layer` and the whole model's stacks `[L, E, ..]`
    instead: they are then read in place as `L * E` groups of which only
    this layer's have rows. A kernel takes its operands whole, so a layer
    sliced out of the stack inside a loop is first copied: 0.8 GB a layer
    at OLMoE's sizes, 3.3 against 0.96 ms (compiler and chip, PR 27).

    One chip's share of the experts (`cfg.experts_held` of `num_experts`,
    the `cfg.expert_share`-th run of them): the router still chooses among
    ALL experts, the stacks hold the held ones alone, and the result is the
    part the held experts add. An assignment to an expert that is not here
    sorts behind every held expert's rows, belongs to no group, is not
    multiplied and adds nothing; nothing stands in for the chips that hold
    the others or for the exchange with them. Shared experts (`shared_gate`,
    `shared_up`, `shared_down` in `lp`) are every chip's alike and are
    added whole.

    Returns `(y [tokens, d] in h's dtype, stats)`; `stats["counts"]` is the
    assignments each expert received `[E] int32`, held here or not,
    `stats["prob_mean"]` its mean router score `[E] float32` (what the
    load-balancing term is made of) and `stats["experts"]` each token's
    choices `[tokens, k]`."""
    from ray_tpu.models.transformer import _act, dense_mlp

    tokens = h.shape[0]
    k, num_experts = cfg.experts_per_token, cfg.num_experts
    held = cfg.experts_held or num_experts
    with jax.named_scope("moe.route"):
        logits = jnp.dot(h if router_input is None else router_input,
                         lp["router"], preferred_element_type=jnp.float32)
        probs, weights, chosen = route(logits, cfg, lp.get("router_bias"))
        flat = chosen.reshape(-1)
        counts = jnp.bincount(flat, length=num_experts).astype(jnp.int32)
        group_sizes = counts
        if held < num_experts:
            first = cfg.expert_share * held
            flat = jnp.where((flat >= first) & (flat < first + held),
                             flat - first, held)  # not here: sorts last
            group_sizes = counts[first:first + held]
        # Assignments in expert order; ties keep token order (stable).
        order = jnp.argsort(flat, stable=True)
        rows = h[order // k]                                 # [tokens*k, d]
    with jax.named_scope("moe.experts"):
        stacks = [lp[name] for name in EXPERT_LEAVES]
        if layer is not None:
            depth = stacks[0].shape[0]
            stacks = [w.reshape((depth * held,) + w.shape[2:])
                      for w in stacks]
            group_sizes = jax.lax.dynamic_update_slice(
                jnp.zeros((depth * held,), jnp.int32), group_sizes,
                (layer * held,))
        w_gate, w_up, w_down = stacks
        inner = (_act(cfg)(grouped_matmul(rows, w_gate, group_sizes))
                 * grouped_matmul(rows, w_up, group_sizes))
        out = grouped_matmul(inner.astype(h.dtype), w_down, group_sizes)
        if held < num_experts:
            # Rows behind the last group are whatever the buffer held.
            here = jnp.arange(tokens * k) < group_sizes.sum()
            out = jnp.where(here[:, None], out, 0.0)
    with jax.named_scope("moe.combine"):
        # Back to (token, choice) order, weighted, summed over the choices.
        out = out[jnp.argsort(order)].reshape(tokens, k, -1)
        y = jnp.einsum("tk,tkd->td", weights, out).astype(h.dtype)
    if "shared_gate" in lp:
        with jax.named_scope("moe.shared"):
            y = y + dense_mlp(h, {"w_gate": lp["shared_gate"],
                                  "w_up": lp["shared_up"],
                                  "w_down": lp["shared_down"]}, cfg)
    return y, {"counts": counts, "prob_mean": probs.mean(0),
               "experts": chosen}
