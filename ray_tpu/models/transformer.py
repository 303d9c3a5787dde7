"""Llama-family decoder-only transformer, TPU-first.

Design choices for the TPU:
  * params are a pytree with the layer stack as a leading axis and the
    forward pass is a `lax.scan` over layers — one compiled layer body,
    O(1) compile time in depth, and the natural substrate for pipeline
    parallelism (the "stage" axis shards over "pp").
  * every parameter carries logical sharding axes (param_logical_axes) so
    DP/FSDP/TP are pure annotations; GSPMD inserts the collectives.
  * attention is the fused flash kernel (ops/flash_attention.py) by
    default, ring attention (parallel/ring_attention.py) when the config
    enables sequence sharding.
  * bfloat16 activations/params by default — MXU native.

This is the model stack the reference lacks natively (it delegates to
torch models inside user train loops; SURVEY.md §2.4) — here it is part of
the framework so JaxTrainer/Serve/RL all share it.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field, replace
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ray_tpu.ops import apply_rope, flash_attention, rmsnorm, rope_frequencies, softmax_cross_entropy
from ray_tpu.ops.cross_entropy import chunked_lm_head_ce
from ray_tpu.ops.paged_attention import grouped_attention
from ray_tpu.ops.rope import yarn_inv_freq, yarn_mscale
from ray_tpu.models import kda, mamba2, shortconv
from ray_tpu.parallel.mesh import DEFAULT_RULES, with_sharding_constraint
from ray_tpu.parallel.moe import load_balancing_loss, moe_block


@dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    d_model: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 32
    d_ff: int = 11008
    max_seq: int = 4096
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    # MoE (0 experts = dense)
    num_experts: int = 0
    experts_per_token: int = 2
    # Whether a token's top-k router probabilities are renormalised to sum
    # to one (Mixtral) or used as the softmax over all experts gave them
    # (OLMoE: `norm_topk_prob` false in its published config).
    norm_topk_prob: bool = False
    # attention implementation: "flash" | "ring" | "ulysses"
    attn_impl: str = "flash"
    # Flash-attention Pallas block sizes. bk=512 benches ~7% faster than
    # 256 on v5e (fewer kv-loop iterations per MXU-resident q block);
    # larger blocks blow the ~16MB VMEM scoped budget.
    attn_block_q: int = 256
    attn_block_k: int = 512
    remat: bool = True
    # Rematerialization policy under remat=True: "full" recomputes the
    # whole layer (min memory, the safe default); "dots_nobatch" saves
    # non-batch matmul outputs
    # (jax.checkpoint_policies.dots_with_no_batch_dims_saveable) — ~12%
    # faster than full on the 0.8B bench at the cost of activation memory;
    # "dots" saves every matmul. Opt in per config/run.
    remat_policy: str = "full"
    # Pipeline parallelism: microbatches per step when the mesh has pp>1
    # (0 = auto: 2*stages when the batch divides, else stages, else 1).
    pp_microbatches: int = 0
    # Chunked lm_head + cross-entropy: compute the loss in sequence
    # chunks of this many tokens so the full [B, S, vocab] logits tensor
    # (1.5GB at the 0.8B bench shape) is never materialized — the
    # backward recomputes each chunk's logits (~3% extra FLOPs) in
    # exchange for the freed HBM. 0 = off (single fused matmul).
    ce_chunk: int = 0
    # Family knobs beyond Llama (Gemma et al., arXiv:2403.08295):
    # MLP activation ("silu" = Llama SwiGLU, "gelu" = Gemma GeGLU),
    # tanh softcap on final logits (0 = off), input/output embedding
    # tying, and sqrt(d_model) embedding scaling.
    activation: str = "silu"
    final_logit_softcap: float = 0.0
    tie_embeddings: bool = False
    scale_embeddings: bool = False
    # Qwen3-style QK-norm (arXiv:2505.09388): learned per-head-dim
    # RMSNorm on q and k before RoPE, stabilizing attention logits at
    # scale (replaces Qwen2's QKV bias).
    qk_norm: bool = False
    # What one QK-norm spans: "head" (Qwen3: each head's head_dim, scales
    # [head_dim]) or "projection" (OLMoE, arXiv:2409.02060: the whole q or
    # k projection before the split into heads, scales [n_heads * head_dim]
    # and [n_kv_heads * head_dim]).
    qk_norm_extent: str = "head"
    # Explicit head dim when it differs from d_model/n_heads (Qwen3
    # uses 128-wide heads at every scale). 0 = derive from d_model.
    custom_head_dim: int = 0
    # A hybrid decoder's published sizes (granitemoehybrid's, lfm2_moe's and
    # solar_open2's config keys): the kind of every layer under the public
    # config's own strings (`layer_types`; empty: every layer is attention),
    # attention ("attention", "full_attention") among recurrent layers of
    # ONE kind, "mamba" (the Mamba-2 mixer, models/mamba2.py, shaped by
    # `mamba_*`), "conv" (the gated short convolution, models/shortconv.py,
    # whose kernel is `conv_L_cache` long) or "kda" (the channel-gated delta
    # rule, models/kda.py: `linear_attn_config`'s heads, their width and the
    # short convolution's taps, and whether beta reaches 2). A hybrid's MLP
    # is dense in its first `first_k_dense_replace` layers and routed in the
    # others where `num_experts` is set, dense in every layer where it is
    # not.
    layer_pattern: Tuple[str, ...] = ()
    conv_L_cache: int = 3
    kda_num_heads: int = 0
    kda_head_dim: int = 0
    kda_short_conv_kernel_size: int = 4
    kda_allow_neg_eigval: bool = False
    # A sigmoid gate of the layer's input on the attention heads' outputs,
    # before the output projection (solar_open2's `use_gqa_gate`; the leaf
    # `wg [d, heads * head_dim]`).
    attn_output_gate: bool = False
    mamba_n_heads: int = 0
    mamba_d_head: int = 0
    mamba_d_state: int = 0
    mamba_d_conv: int = 4
    mamba_n_groups: int = 1
    mamba_expand: int = 2
    mamba_chunk_size: int = 256
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    # Granite's four multipliers: on the embeddings, on the attention
    # scores in place of head_dim ** -0.5 (0 = that), on what every mixer
    # and MLP adds to the residual stream, and under the logits.
    embedding_multiplier: float = 1.0
    attention_multiplier: float = 0.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    # "rope", or "nope": attention without a position embedding.
    position_embedding_type: str = "rope"
    # Multi-head latent attention (DeepSeek-V2/V3, arXiv:2405.04434), under
    # the public config's keys: queries through a `q_lora_rank` bottleneck
    # (0: projected directly), keys and values through one `kv_lora_rank`
    # latent a token (0: no latent attention), a head's query and key made
    # of a `qk_nope_head_dim` part without a position and a
    # `qk_rope_head_dim` part with one, the key's shared by all heads, and
    # values `v_head_dim` wide. What is cached is the latent and the rope
    # key (`project_latent`).
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # A decoder of two kinds of layer (DeepSeek-V3's keys): the first
    # `first_k_dense_replace` layers have a dense MLP of width `d_ff`, the
    # others `num_experts` routed experts of width `moe_intermediate_size`
    # (0: `d_ff`) beside `n_shared_experts` that every token takes. Router
    # scores are a "softmax" or a "sigmoid" (`scoring_func`); with
    # `n_group` > 1 a token chooses within its `topk_group` best groups of
    # experts; chosen weights are multiplied by `routed_scaling_factor`.
    first_k_dense_replace: int = 0
    moe_intermediate_size: int = 0
    n_shared_experts: int = 0
    scoring_func: str = "softmax"
    n_group: int = 1
    topk_group: int = 1
    routed_scaling_factor: float = 1.0
    # Sigmoid routing's two further published settings: whether a learned
    # bias a layer and expert is added to the scores to CHOOSE with (the
    # leaf `router_bias`; lfm2_moe's `use_expert_bias`, always there under
    # latent attention), and what is added to the chosen scores' sum before
    # it divides them (DeepSeek-V3's code adds 1e-20, lfm2_moe's 1e-6).
    use_expert_bias: bool = False
    norm_topk_eps: float = 1e-20
    # One chip's share of a layer's routed experts: `experts_held` of the
    # `num_experts` the router chooses among (0: all), the `expert_share`-th
    # such run, experts [held * share, held * (share + 1)). The layer then
    # computes what its own experts add and nothing else (parallel/moe.py).
    experts_held: int = 0
    expert_share: int = 0
    # YaRN (arXiv:2309.00071, the public config's `rope_scaling`): factor
    # (1: plain rope), the length the model was first trained at, the two
    # rotation counts between which frequencies blend, and the two
    # attention-temperature coefficients.
    rope_factor: float = 1.0
    rope_original_max_position: int = 0
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 0.0
    # Generation by diffusion over blocks (SDAR, arXiv:2510.06303):
    # `block_length` positions are filled in at once (0: a next-token
    # model). Position p lies in block p // block_length and attends to
    # every earlier block and to its own block whole, later positions of it
    # too; the logits at p are for the token AT p. A position not yet
    # filled is fed `mask_token_id`; a block is filled in `denoise_steps`
    # passes of block_length / denoise_steps positions each and then
    # committed (serve/paged_kv.block_pass_paged).
    block_length: int = 0
    mask_token_id: int = 0
    denoise_steps: int = 1
    # Attention over a window in some layers (SmallThinker, arXiv:2507.20984,
    # under its public config's keys): a layer `l` with
    # `sliding_window_layout[l]` set sees itself and the
    # `sliding_window_size - 1` positions before it, any other layer
    # everything behind it (empty, or a size of 0: no layer has a window).
    # `rope_layout[l]` says whether layer `l` rotates its queries and keys
    # (empty: `position_embedding_type` decides for every layer). Two
    # published lists, and two fields, whether or not they are equal.
    sliding_window_size: int = 0
    sliding_window_layout: Tuple[int, ...] = ()
    rope_layout: Tuple[int, ...] = ()
    # What a layer's router multiplies: "mlp_input", the normed stream
    # behind attention that its experts multiply too, or "layer_input", the
    # layer's own input before any norm (SmallThinker's router, which can
    # so be worked out before attention).
    router_reads: str = "mlp_input"

    @property
    def head_dim(self) -> int:
        if self.kv_lora_rank and not self.custom_head_dim:
            return self.qk_nope_head_dim + self.qk_rope_head_dim
        return self.custom_head_dim or self.d_model // self.n_heads

    @property
    def attention_scale(self) -> float:
        if self.kv_lora_rank:  # YaRN's temperature, on both q and k
            return self.head_dim ** -0.5 * yarn_mscale(
                self.rope_factor, self.rope_mscale_all_dim) ** 2
        return self.attention_multiplier or self.head_dim ** -0.5

    @property
    def expert_ff(self) -> int:
        """One routed expert's width."""
        return self.moe_intermediate_size or self.d_ff

    @property
    def held(self) -> int:
        """Routed experts of a layer whose weights are here."""
        return self.experts_held or self.num_experts

    @property
    def expert_layers(self) -> int:
        return (self.n_layers - self.first_k_dense_replace
                if self.num_experts else 0)

    def layers_of(self, kind: str) -> int:
        """How many of a hybrid's layers are of `kind`."""
        return sum(t == kind for t in self.layer_pattern)

    @property
    def attention_layers(self) -> int:
        """A hybrid's attention layers, under either published string."""
        return sum(t in ATTENTION_KINDS for t in self.layer_pattern)

    @property
    def recurrent_kind(self) -> str:
        """The kind of a hybrid's recurrent layers, a key of
        `RECURRENT_KINDS`."""
        return next(t for t in self.layer_pattern if t not in ATTENTION_KINDS)

    @property
    def recurrent_layers(self) -> int:
        return len(self.layer_pattern) - self.attention_layers

    @property
    def window_layout(self) -> Tuple[bool, ...]:
        """For every layer, whether it attends over the window; empty for a
        model none of whose layers does."""
        if not (self.sliding_window_size and any(self.sliding_window_layout)):
            return ()
        return tuple(bool(w) for w in self.sliding_window_layout)

    @property
    def window_layers(self) -> int:
        return sum(self.window_layout)

    @property
    def rope_layers(self) -> Tuple[bool, ...]:
        """For every layer, whether it rotates its queries and keys."""
        if self.rope_layout:
            return tuple(bool(r) for r in self.rope_layout)
        return (self.position_embedding_type == "rope",) * self.n_layers

    @property
    def layer_period(self) -> int:
        """The shortest run of layers after which the per-layer lists
        repeat: 1 for a model whose layers are all alike."""
        lists = [t for t in (self.window_layout, self.rope_layers) if t]
        return next(p for p in range(1, self.n_layers + 1)
                    if self.n_layers % p == 0
                    and all(t[i] == t[i % p] for t in lists
                            for i in range(self.n_layers)))


# The published strings of a hybrid's attention layers (granitemoehybrid's,
# lfm2_moe's) and, for each kind of recurrent layer, its stack's name under
# `params["layers"]` and the module whose `mixer` and `init_state` it takes.
ATTENTION_KINDS = ("attention", "full_attention")
RECURRENT_KINDS = {"mamba": ("ssm", mamba2), "conv": ("conv", shortconv),
                   "kda": ("kda", kda)}

# Where parallel.mesh.DEFAULT_RULES put activations, for the kernels that
# run on each device's block (ops.per_shard).
_ACT_SPEC = P(("dp", "fsdp"), "sp", None)          # [B, L, D]
_HEADS_SPEC = P(("dp", "fsdp"), None, "tp", None)  # [B, L, H, head_dim]


def _dense_init(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, dtype=jnp.float32) * scale).astype(dtype)


def _check_experts(cfg: TransformerConfig) -> None:
    """A layer's routed experts: the held share divides them and is one of
    the shares, the groups divide them and hold `topk_group`."""
    if cfg.num_experts % cfg.held or not (
            0 <= cfg.expert_share < cfg.num_experts // cfg.held):
        raise ValueError(
            f"experts_held {cfg.held} must divide num_experts "
            f"{cfg.num_experts}, and expert_share {cfg.expert_share} "
            "name one of the shares")
    if cfg.num_experts % cfg.n_group or cfg.topk_group > cfg.n_group:
        raise ValueError("n_group must divide num_experts and hold "
                         "topk_group")


def _check_hybrid(cfg: TransformerConfig) -> None:
    """What a hybrid may be: attention layers among recurrent layers of one
    kind (Mamba-2, gated short convolutions or the channel-gated delta
    rule), a QK-norm, a rope and an output gate or none of them in the
    attention layers, and an MLP that is dense in every layer or dense in
    the leading layers (there may be none) and routed in the rest: all of
    a layer's experts or one chip's share of them (`experts_held`), with or
    without shared experts beside them, softmax or sigmoid scores, a choice
    bias. Refused by name: an unknown kind, recurrent layers of two kinds
    in one model, latent attention or a router on the layer's input beside
    recurrent layers."""
    kinds = set(cfg.layer_pattern)
    if not kinds <= {*RECURRENT_KINDS, *ATTENTION_KINDS}:
        raise ValueError(f"unknown layer kinds {sorted(kinds)} in "
                         f"layer_pattern: expected one of {list(RECURRENT_KINDS)}"
                         ", and 'attention' or 'full_attention'")
    if len(cfg.layer_pattern) != cfg.n_layers:
        raise ValueError(f"layer_pattern names {len(cfg.layer_pattern)} layers, "
                         f"n_layers is {cfg.n_layers}")
    recurrent = kinds - set(ATTENTION_KINDS)
    if not recurrent or recurrent == kinds:
        raise ValueError("a hybrid has layers of both kinds, recurrent and "
                         f"attention; layer_pattern has only {sorted(kinds)}")
    if len(recurrent) > 1:
        raise ValueError("a hybrid's recurrent layers are of one kind: "
                         f"{sorted(recurrent)} in one model (two recurrent "
                         "pools in one walk) are not written")
    if cfg.kv_lora_rank:
        raise ValueError("latent attention beside recurrent layers is not "
                         "written")
    if cfg.router_reads != "mlp_input":
        raise ValueError("a router that reads the layer's input beside "
                         "recurrent layers is not written")
    if cfg.num_experts:
        if not 0 <= cfg.first_k_dense_replace < cfg.n_layers:
            raise ValueError("first_k_dense_replace must lie in [0, n_layers)")
        _check_experts(cfg)
    if "mamba" in kinds and (
            cfg.mamba_expand * cfg.d_model != mamba2.d_inner(cfg)):
        raise ValueError("mamba_n_heads * mamba_d_head must be "
                         "mamba_expand * d_model")
    if "kda" in kinds and not (cfg.kda_num_heads and cfg.kda_head_dim):
        raise ValueError("delta-rule layers need kda_num_heads and "
                         "kda_head_dim")


def _init_hybrid_layers(key, cfg: TransformerConfig) -> Dict:
    """A hybrid's layers: one stack a kind of mixer (`ssm`, `conv` or
    `kda`, and `attn`, each with its input norm), the dense MLPs of the
    leading layers (`mlp`: of all layers in a model without experts, absent
    where every layer is routed) and the routed MLPs of the others (`moe`,
    the experts `[expert layers, held, ...]`, shared experts as
    `shared_*`), so three or four stacks of unlike length. `A_log`,
    `dt_bias` and the convolution are drawn by Mamba-2's published rule
    (arXiv:2405.21060), the delta rule's alike (a `dt_bias` a channel); a
    short convolution's taps are uniform in +-K ** -0.5."""
    _check_hybrid(cfg)
    d, h, kvh, hd, ff = (cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                         cfg.head_dim, cfg.d_ff)
    n_rec, n_attn, n = cfg.recurrent_layers, cfg.attention_layers, cfg.n_layers
    n_moe = cfg.expert_layers
    scale, out_scale = d ** -0.5, d ** -0.5 * (2 * n) ** -0.5
    # Further runs of keys behind the first sixteen, so that a model that
    # needed no more draws what it drew before there were expert stacks,
    # and then shared experts, a gate and delta-rule layers.
    keys = iter([*jax.random.split(key, 16),
                 *jax.random.split(jax.random.fold_in(key, 1), 8),
                 *jax.random.split(jax.random.fold_in(key, 2), 16)])

    def normal(count, shape, scale):
        return _dense_init(next(keys), (count, *shape), scale, cfg.dtype)

    def uniform(shape, lo, hi):
        return jax.random.uniform(next(keys), shape, jnp.float32, lo, hi)

    layers = {}
    if cfg.recurrent_kind == "conv":
        bound = cfg.conv_L_cache ** -0.5
        layers["conv"] = {
            "norm": jnp.ones((n_rec, d), cfg.dtype),
            "w_in": normal(n_rec, (d, 3 * d), scale),
            "conv_w": uniform((n_rec, d, cfg.conv_L_cache), -bound,
                              bound).astype(cfg.dtype),
            "w_out": normal(n_rec, (d, d), out_scale),
        }
    elif cfg.recurrent_kind == "kda":
        layers["kda"] = _init_kda_stack(cfg, n_rec, normal, uniform)
    else:
        layers["ssm"] = _init_mamba_stack(cfg, n_rec, normal, uniform,
                                          scale, out_scale)
    attn = {
        "attn_norm": jnp.ones((n_attn, d), cfg.dtype),
        "wq": normal(n_attn, (d, h * hd), scale),
        "wk": normal(n_attn, (d, kvh * hd), scale),
        "wv": normal(n_attn, (d, kvh * hd), scale),
        "wo": normal(n_attn, (h * hd, d), out_scale),
    }
    if cfg.attn_output_gate:
        attn["wg"] = normal(n_attn, (d, h * hd), scale)
    if cfg.qk_norm:
        full = cfg.qk_norm_extent == "projection"
        attn["q_norm"] = jnp.ones((n_attn, h * hd if full else hd), cfg.dtype)
        attn["k_norm"] = jnp.ones((n_attn, kvh * hd if full else hd),
                                  cfg.dtype)
    layers["attn"] = attn
    if n - n_moe:
        layers["mlp"] = {
            "mlp_norm": jnp.ones((n - n_moe, d), cfg.dtype),
            "w_gate": normal(n - n_moe, (d, ff), scale),
            "w_up": normal(n - n_moe, (d, ff), scale),
            "w_down": normal(n - n_moe, (ff, d), out_scale),
        }
    if n_moe:
        e, held, eff = cfg.num_experts, cfg.held, cfg.expert_ff
        down = eff ** -0.5 * (2 * n) ** -0.5
        layers["moe"] = {
            "mlp_norm": jnp.ones((n_moe, d), cfg.dtype),
            "router": normal(n_moe, (d, e), scale),
            "w_gate": normal(n_moe, (held, d, eff), scale),
            "w_up": normal(n_moe, (held, d, eff), scale),
            "w_down": normal(n_moe, (held, eff, d), down),
        }
        if cfg.use_expert_bias:
            # Added to the scores to choose with, never to weigh with; zero
            # as a checkpoint starts it, float32 as it is kept.
            layers["moe"]["router_bias"] = jnp.zeros((n_moe, e), jnp.float32)
        if cfg.n_shared_experts:
            sff = cfg.n_shared_experts * eff
            layers["moe"].update({
                "shared_gate": normal(n_moe, (d, sff), scale),
                "shared_up": normal(n_moe, (d, sff), scale),
                "shared_down": normal(n_moe, (sff, d),
                                      sff ** -0.5 * (2 * n) ** -0.5),
            })
    return layers


def _init_kda_stack(cfg, n_kda, normal, uniform) -> Dict:
    """The delta-rule mixers' leaves: `w_qkv` the published q, k and v
    projections side by side and `conv_w` their three convolutions', the
    decay's and the output gate's low-rank pairs (`w_fa`, `w_fb`; `w_ga`,
    `w_gb`) through `kda_head_dim`, `a_log` a head, `dt_bias` a channel."""
    d, heads, dk, width = (cfg.d_model, cfg.kda_num_heads, cfg.kda_head_dim,
                           kda.inner(cfg))
    taps = cfg.kda_short_conv_kernel_size
    bound = taps ** -0.5
    dt_bias = _dt_bias(uniform, (n_kda, width))
    return {
        "norm": jnp.ones((n_kda, d), cfg.dtype),
        "w_qkv": normal(n_kda, (d, 3 * width), d ** -0.5),
        "conv_w": uniform((n_kda, 3 * width, taps), -bound,
                          bound).astype(cfg.dtype),
        "w_fa": normal(n_kda, (d, dk), d ** -0.5),
        "w_fb": normal(n_kda, (dk, width), dk ** -0.5),
        "dt_bias": dt_bias.astype(cfg.dtype),
        "a_log": jnp.log(uniform((n_kda, heads), 1.0, 16.0)).astype(cfg.dtype),
        "w_beta": normal(n_kda, (d, heads), d ** -0.5),
        "w_ga": normal(n_kda, (d, dk), d ** -0.5),
        "w_gb": normal(n_kda, (dk, width), dk ** -0.5),
        "gate_norm": jnp.ones((n_kda, dk), cfg.dtype),
        "w_out": normal(n_kda, (width, d),
                        width ** -0.5 * (2 * cfg.n_layers) ** -0.5),
    }


def _dt_bias(uniform, shape):
    """A rate log-uniform in [0.001, 0.1], kept as the value whose softplus
    it is (Mamba-2's published rule), float32."""
    dt = jnp.exp(uniform(shape, jnp.log(0.001), jnp.log(0.1)))
    return dt + jnp.log(-jnp.expm1(-dt))


def _init_mamba_stack(cfg, n_ssm, normal, uniform, scale, out_scale) -> Dict:
    d = cfg.d_model
    heads, inner, conv = (cfg.mamba_n_heads, mamba2.d_inner(cfg),
                          mamba2.conv_dim(cfg))
    dt_bias = _dt_bias(uniform, (n_ssm, heads))
    bound = cfg.mamba_d_conv ** -0.5
    ssm = {
        "norm": jnp.ones((n_ssm, d), cfg.dtype),
        "w_in": normal(n_ssm, (d, mamba2.in_proj_dim(cfg)), scale),
        "w_dt": normal(n_ssm, (d, heads), scale),
        "conv_w": uniform((n_ssm, conv, cfg.mamba_d_conv), -bound,
                          bound).astype(cfg.dtype),
        "dt_bias": dt_bias.astype(cfg.dtype),
        "a_log": jnp.log(uniform((n_ssm, heads), 1.0, 16.0)).astype(cfg.dtype),
        "d_skip": jnp.ones((n_ssm, heads), cfg.dtype),
        "gate_norm": jnp.ones((n_ssm, inner), cfg.dtype),
        "w_out": normal(n_ssm, (inner, d), out_scale),
    }
    if cfg.mamba_conv_bias:
        ssm["conv_b"] = uniform((n_ssm, conv), -bound, bound).astype(cfg.dtype)
    if cfg.mamba_proj_bias:
        ssm["b_in"] = jnp.zeros((n_ssm, mamba2.in_proj_dim(cfg)), cfg.dtype)
        ssm["b_dt"] = jnp.zeros((n_ssm, heads), cfg.dtype)
        ssm["b_out"] = jnp.zeros((n_ssm, d), cfg.dtype)
    return ssm


def _check_latent(cfg: TransformerConfig) -> None:
    if not (cfg.qk_nope_head_dim and cfg.qk_rope_head_dim and cfg.v_head_dim):
        raise ValueError("latent attention needs qk_nope_head_dim, "
                         "qk_rope_head_dim and v_head_dim")
    if cfg.layer_pattern or cfg.qk_norm:
        raise ValueError("latent attention beside state-space layers, or "
                         "under a QK-norm, is not written")
    if not 0 <= cfg.first_k_dense_replace <= cfg.n_layers:
        raise ValueError("first_k_dense_replace must lie in [0, n_layers]")
    if cfg.num_experts:
        _check_experts(cfg)


def _init_latent_layers(key, cfg: TransformerConfig) -> Dict:
    """The layers of a decoder with latent attention, a stack a kind:
    `dense` (the first `first_k_dense_replace` layers, or all of a model
    without experts) and `moe` (the others), each a whole layer's leaves.
    The published `q_b_proj`, `kv_a_proj_with_mqa` and `kv_b_proj` are held
    as the column blocks the program multiplies by: `wq_n | wq_r` (every
    head's part without and with a position), `wkv_a | wk_r` (the latent
    and the shared rope key) and `w_uk | w_uv` (every head's keys and
    values out of the latent). Every matrix at its fan-in ** -0.5, the
    writers of the residual stream at (2 n_layers) ** -0.5 of that."""
    _check_latent(cfg)
    d, h, n = cfg.d_model, cfg.n_heads, cfg.n_layers
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    rank, qr = cfg.kv_lora_rank, cfg.q_lora_rank
    keys = iter(jax.random.split(key, 40))
    out = (2 * n) ** -0.5

    def normal(count, shape, fan_in, scale=1.0):
        return _dense_init(next(keys), (count, *shape),
                           fan_in ** -0.5 * scale, cfg.dtype)

    def attention(count):
        lp = {"attn_norm": jnp.ones((count, d), cfg.dtype),
              "mlp_norm": jnp.ones((count, d), cfg.dtype)}
        q_in = d
        if qr:
            lp["wq_a"] = normal(count, (d, qr), d)
            lp["q_a_norm"] = jnp.ones((count, qr), cfg.dtype)
            q_in = qr
        lp.update({
            "wq_n": normal(count, (q_in, h * dn), q_in),
            "wq_r": normal(count, (q_in, h * dr), q_in),
            "wkv_a": normal(count, (d, rank), d),
            "kv_a_norm": jnp.ones((count, rank), cfg.dtype),
            "wk_r": normal(count, (d, dr), d),
            "w_uk": normal(count, (rank, h * dn), rank),
            "w_uv": normal(count, (rank, h * dv), rank),
            "wo": normal(count, (h * dv, d), h * dv, out),
        })
        return lp

    n_moe = cfg.expert_layers
    layers = {}
    if n - n_moe:
        ff = cfg.d_ff
        layers["dense"] = {
            **attention(n - n_moe),
            "w_gate": normal(n - n_moe, (d, ff), d),
            "w_up": normal(n - n_moe, (d, ff), d),
            "w_down": normal(n - n_moe, (ff, d), ff, out),
        }
    if n_moe:
        e, held, ff = cfg.num_experts, cfg.held, cfg.expert_ff
        layers["moe"] = {
            **attention(n_moe),
            "router": normal(n_moe, (d, e), d),
            # `e_score_correction_bias`: added to the scores to choose
            # with, never to weigh with; zero as published checkpoints
            # start it, float32 as they keep it.
            "router_bias": jnp.zeros((n_moe, e), jnp.float32),
            "w_gate": normal(n_moe, (held, d, ff), d),
            "w_up": normal(n_moe, (held, d, ff), d),
            "w_down": normal(n_moe, (held, ff, d), ff, out),
        }
        if cfg.n_shared_experts:
            sff = cfg.n_shared_experts * ff
            layers["moe"].update({
                "shared_gate": normal(n_moe, (d, sff), d),
                "shared_up": normal(n_moe, (d, sff), d),
                "shared_down": normal(n_moe, (sff, d), sff, out),
            })
    return layers


def init_params(key: jax.Array, cfg: TransformerConfig) -> Dict:
    """Initialize the full parameter pytree (layers stacked on axis 0; a
    hybrid's as `_init_hybrid_layers` says, a latent-attention model's as
    `_init_latent_layers`)."""
    keys = jax.random.split(key, 10)
    d, h, kvh, hd, ff = (
        cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_ff
    )
    L = cfg.n_layers
    scale = d ** -0.5

    def stack(k, shape, scale):
        ks = jax.random.split(k, L)
        return jnp.stack([_dense_init(ks[i], shape, scale, cfg.dtype) for i in range(L)])

    if cfg.layer_pattern:
        return _with_tables(_init_hybrid_layers(keys[0], cfg), keys, cfg)
    if cfg.kv_lora_rank:
        return _with_tables(_init_latent_layers(keys[0], cfg), keys, cfg)
    layer = {
        "attn_norm": jnp.ones((L, d), dtype=cfg.dtype),
        "wq": stack(keys[0], (d, h * hd), scale),
        "wk": stack(keys[1], (d, kvh * hd), scale),
        "wv": stack(keys[2], (d, kvh * hd), scale),
        "wo": stack(keys[3], (h * hd, d), scale * (2 * L) ** -0.5),
        "mlp_norm": jnp.ones((L, d), dtype=cfg.dtype),
    }
    if cfg.qk_norm:
        full = cfg.qk_norm_extent == "projection"
        layer["q_norm"] = jnp.ones((L, h * hd if full else hd), dtype=cfg.dtype)
        layer["k_norm"] = jnp.ones((L, kvh * hd if full else hd),
                                   dtype=cfg.dtype)
    if cfg.num_experts == 0:
        layer.update(
            {
                "w_gate": stack(keys[4], (d, ff), scale),
                "w_up": stack(keys[5], (d, ff), scale),
                "w_down": stack(keys[6], (ff, d), scale * (2 * L) ** -0.5),
            }
        )
    else:
        E, eff = cfg.num_experts, cfg.expert_ff
        sub = jax.random.split(keys[4], 3)
        layer.update(
            {
                "router": stack(keys[7], (d, E), scale),
                "w_gate": stack(sub[0], (E, d, eff), scale),
                "w_up": stack(sub[1], (E, d, eff), scale),
                "w_down": stack(sub[2], (E, eff, d), scale * (2 * L) ** -0.5),
            }
        )
    return _with_tables(layer, keys, cfg)


def _with_tables(layer: Dict, keys, cfg: TransformerConfig) -> Dict:
    """The parameter tree around its `layers`: the embedding table, the
    last norm and, where it is not the table, the output head."""
    d, scale = cfg.d_model, cfg.d_model ** -0.5
    # A tied table is also the output head, so it takes the head's scale:
    # at unit scale every token predicts itself with a logit of about d
    # (a first loss of 1,887 at Qwen3-4B widths, where ln(vocab) is 11.9).
    params = {
        "embed": _dense_init(keys[8], (cfg.vocab_size, d),
                             scale if cfg.tie_embeddings else 1.0, cfg.dtype),
        "layers": layer,
        "final_norm": jnp.ones((d,), dtype=cfg.dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = _dense_init(
            keys[9], (d, cfg.vocab_size), scale, cfg.dtype
        )
    return params


def param_logical_axes(cfg: TransformerConfig) -> Dict:
    """Logical sharding axes mirroring init_params' tree.

    Mapped through parallel.mesh.DEFAULT_RULES: "embed"->fsdp, "mlp"/
    "heads"/"vocab"->tp, "expert"->ep, layer-stack axis -> "stage" (pp).
    A hybrid's recurrent stack is replicated but for its two projections'
    model axis: heads, groups and the convolution's channels do not split
    without a partitioned mixer. Shared experts are replicated.
    """
    if cfg.kv_lora_rank:
        # Replicated but for the stack axis: a latent pool and a held share
        # of experts are one chip's (serve/llm.py refuses them under tp).
        shapes = jax.eval_shape(lambda k: init_params(k, cfg),
                                jax.random.PRNGKey(0))
        return jax.tree_util.tree_map_with_path(
            lambda path, leaf: (("stage",) if path[0].key == "layers" else ())
            + (None,) * (leaf.ndim - (path[0].key == "layers")), shapes)
    if cfg.layer_pattern:
        shapes = jax.eval_shape(lambda k: init_params(k, cfg),
                                jax.random.PRNGKey(0))
        split = {"wq": ("stage", "embed", "heads"),
                 "wk": ("stage", "embed", "heads"),
                 "wv": ("stage", "embed", "heads"),
                 "wo": ("stage", "heads", "embed"),
                 "w_gate": ("stage", "embed", "mlp"),
                 "w_up": ("stage", "embed", "mlp"),
                 "w_down": ("stage", "mlp", "embed"),
                 "wg": ("stage", "embed", "heads"),
                 "w_in": ("stage", "embed", None),
                 "w_dt": ("stage", "embed", None),
                 "w_qkv": ("stage", "embed", None),
                 "w_out": ("stage", None, "embed"),
                 "embed": ("vocab", "embed"), "lm_head": ("embed", "vocab")}
        experts = {"w_gate": ("stage", "expert", "embed", "mlp"),
                   "w_up": ("stage", "expert", "embed", "mlp"),
                   "w_down": ("stage", "expert", "mlp", "embed")}

        def axes_of(path, leaf):
            table = (experts if len(path) > 1 and path[1].key == "moe"
                     else split)
            return table.get(
                path[-1].key,
                (("stage",) if path[0].key == "layers" else ())
                + (None,) * (leaf.ndim - (path[0].key == "layers")))

        return jax.tree_util.tree_map_with_path(axes_of, shapes)
    layer = {
        "attn_norm": ("stage", None),
        "wq": ("stage", "embed", "heads"),
        "wk": ("stage", "embed", "heads"),
        "wv": ("stage", "embed", "heads"),
        "wo": ("stage", "heads", "embed"),
        "mlp_norm": ("stage", None),
    }
    if cfg.qk_norm:
        layer["q_norm"] = ("stage", None)
        layer["k_norm"] = ("stage", None)
    if cfg.num_experts == 0:
        layer.update(
            {
                "w_gate": ("stage", "embed", "mlp"),
                "w_up": ("stage", "embed", "mlp"),
                "w_down": ("stage", "mlp", "embed"),
            }
        )
    else:
        layer.update(
            {
                "router": ("stage", "embed", None),
                "w_gate": ("stage", "expert", "embed", "mlp"),
                "w_up": ("stage", "expert", "embed", "mlp"),
                "w_down": ("stage", "expert", "mlp", "embed"),
            }
        )
    axes = {
        "embed": ("vocab", "embed"),
        "layers": layer,
        "final_norm": (None,),
    }
    if not cfg.tie_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    return axes


def _act(cfg: TransformerConfig):
    if cfg.activation == "silu":
        return jax.nn.silu
    if cfg.activation == "gelu":
        return lambda x: jax.nn.gelu(x, approximate=True)
    if cfg.activation == "relu":
        return jax.nn.relu
    raise ValueError(f"unknown activation {cfg.activation!r}")


def _embed_tokens(params, tokens, cfg: TransformerConfig):
    x = params["embed"][tokens].astype(cfg.dtype)
    if cfg.scale_embeddings:  # Gemma normalizes the embedding scale
        x = x * jnp.asarray(cfg.d_model ** 0.5, dtype=cfg.dtype)
    if cfg.embedding_multiplier != 1.0:
        x = x * jnp.asarray(cfg.embedding_multiplier, dtype=cfg.dtype)
    return x


def lm_head_weight(params, cfg: TransformerConfig):
    """[D, V] output projection (the embedding transposed when tied)."""
    if cfg.tie_embeddings:
        return params["embed"].T
    return params["lm_head"]


def project_logits(x, params, cfg: TransformerConfig):
    logits = x @ lm_head_weight(params, cfg)
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    if cfg.final_logit_softcap:
        cap = cfg.final_logit_softcap
        logits = cap * jnp.tanh(logits.astype(jnp.float32) / cap)
    return logits


def project_qkv(h, lp, cfg: TransformerConfig):
    """The layer's q, k, v heads `[B, L, heads, head_dim]` of normed
    activations `h [B, L, D]`, QK-norm applied at the config's extent
    (elementwise, so XLA fuses it into the rope/attention pipeline; the
    pallas rmsnorm kernel targets [.., D] rows)."""
    b, l, _ = h.shape
    extent = cfg.qk_norm_extent if cfg.qk_norm else None
    if extent not in (None, "head", "projection"):
        raise ValueError(f"unknown qk_norm_extent {extent!r}: expected "
                         "'head' or 'projection'")

    def normed(q, k):
        return (rmsnorm(q, lp["q_norm"], cfg.norm_eps, use_pallas=False),
                rmsnorm(k, lp["k_norm"], cfg.norm_eps, use_pallas=False))

    # The products are held back from the norm. Fused into a product, a
    # per-head sum of squares has the chip's compiler slice `wq` and `wk`
    # out of the layer stack and copy each heads-major, every layer of an
    # engine step (26 MB a layer at Qwen3-4B's widths, a millisecond of a
    # 15 ms decode step); held back, they are read where they lie, as `wv`
    # is, and a train step of 8 x 1024 tokens is 0.6% shorter too. The
    # arithmetic is the same, yet results equal the fused ones bit for bit
    # on the CPU only: on the chip the norm reads the product as rounded
    # to the model's dtype (what the program states and the reference
    # computes), where fused it read the f32 accumulator.
    q, k, v = jax.lax.optimization_barrier(
        (h @ lp["wq"], h @ lp["wk"], h @ lp["wv"]))
    if extent == "projection":
        q, k = normed(q, k)
    q = q.reshape(b, l, cfg.n_heads, cfg.head_dim)
    k = k.reshape(b, l, cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(b, l, cfg.n_kv_heads, cfg.head_dim)
    if extent == "head":
        q, k = normed(q, k)
    return q, k, v


def gate_attention(attn, h, lp):
    """The heads' outputs `attn [B, L, H * head_dim]` times the sigmoid
    gate of the layer's normed input `h`, for a layer that has the leaf
    `wg` (`attn_output_gate`); any other layer's as they are."""
    if "wg" not in lp:
        return attn
    with jax.named_scope("attn.gate"):
        gate = jax.nn.sigmoid((h @ lp["wg"]).astype(jnp.float32))
        return (attn.astype(jnp.float32) * gate).astype(attn.dtype)


def block_causal(q_pos, k_pos, block_length: int):
    """Which keys a query of a block-diffusion model sees, `[.., Lq, Lk]`
    of positions `q_pos [.., Lq]` and `k_pos [.., Lk]`: every key whose
    block is not after the query's."""
    return (k_pos[..., None, :] // block_length
            <= q_pos[..., :, None] // block_length)


def window_causal(q_pos, k_pos, window: int):
    """Which keys a query of a layer with a window sees, `[.., Lq, Lk]` of
    positions `q_pos [.., Lq]` and `k_pos [.., Lk]`: itself and the
    `window - 1` positions before it."""
    behind = q_pos[..., :, None] - k_pos[..., None, :]
    return (behind >= 0) & (behind < window)


def _attention(cfg: TransformerConfig, q, k, v, mesh, positions,
               window=None):
    """`window`: a flag (traced, a layer's own) of a model with window
    layers, None for any other model."""
    if cfg.block_length or window is not None:
        # The plain form: the flash kernel's mask is causal and no other.
        pos = (jnp.arange(q.shape[1])[None] if positions is None
               else positions)
        if cfg.block_length:
            seen = block_causal(pos, pos, cfg.block_length)
        else:
            seen = window_causal(pos, pos, jnp.where(
                window, cfg.sliding_window_size, q.shape[1] + 1))
        return grouped_attention(
            q, k.astype(jnp.float32), v.astype(jnp.float32), seen,
            cfg.attention_scale)
    if cfg.attention_scale != cfg.head_dim ** -0.5:
        # The kernels scale scores by head_dim ** -0.5: q carries the rest.
        q = q * jnp.asarray(cfg.attention_scale * cfg.head_dim ** 0.5, q.dtype)
    if cfg.attn_impl == "ring" and mesh is not None and mesh.shape.get("sp", 1) > 1:
        from ray_tpu.parallel.ring_attention import ring_attention

        spec = P(("dp", "fsdp"), "sp", "tp", None)
        return ring_attention(q, k, v, mesh, axis_name="sp", causal=True,
                              query_spec=spec)
    if cfg.attn_impl == "ulysses" and mesh is not None and mesh.shape.get("sp", 1) > 1:
        from ray_tpu.parallel.ulysses import ulysses_attention

        spec = P(("dp", "fsdp"), "sp", "tp", None)
        return ulysses_attention(q, k, v, mesh, axis_name="sp", causal=True,
                                 query_spec=spec)
    return flash_attention(q, k, v, causal=True,
                           block_q=cfg.attn_block_q, block_k=cfg.attn_block_k,
                           mesh=mesh, spec=_HEADS_SPEC)


def dense_mlp(h, lp, cfg: TransformerConfig):
    """(act(h W_gate) * (h W_up)) W_down, gate and product in float32."""
    gate = _act(cfg)((h @ lp["w_gate"]).astype(jnp.float32))
    up = (h @ lp["w_up"]).astype(jnp.float32)
    return ((gate * up).astype(h.dtype)) @ lp["w_down"]


def rotate(q, k, cos, sin, positions, rope=None):
    """q and k with the position embedding applied; `rope`, a flag (traced,
    a layer's own) of a model with a per-layer list: only where it is set."""
    rotated = (apply_rope(q, cos, sin, positions),
               apply_rope(k, cos, sin, positions))
    if rope is None:
        return rotated
    return jnp.where(rope, rotated[0], q), jnp.where(rope, rotated[1], k)


def router_input(x, cfg: TransformerConfig):
    """What a layer's router multiplies, `[tokens, d]` of the layer's input
    `x [B, L, D]`, for `moe_block`: None where it reads what the experts
    read (`cfg.router_reads`)."""
    if cfg.router_reads == "mlp_input":
        return None
    if cfg.router_reads != "layer_input":
        raise ValueError(f"unknown router_reads {cfg.router_reads!r}: "
                         "expected 'mlp_input' or 'layer_input'")
    return x.reshape(-1, x.shape[-1])


def residual(x, y, cfg: TransformerConfig):
    """x + residual_multiplier * y (a model without one adds y as it is)."""
    if cfg.residual_multiplier == 1.0:
        return x + y
    return x + cfg.residual_multiplier * y


def attention_mixer(x, lp, cfg: TransformerConfig, cos, sin, positions,
                    attend, mesh=None, spec=P(), rope=None):
    """The attention half of a layer on `x [B, L, D]`, for the train step,
    `generate` and the engine's walks alike: the norm, the heads
    (`project_qkv`), the position embedding, `attend`, the output gate
    (`gate_attention`) and the product with `wo`. What it returns is what
    the mixer adds, BEFORE the residual (a hybrid's training walk takes it
    under a `lax.cond` beside the recurrent mixer), and `attend`'s cache.

    `attend(q, k, v) -> (attention [B, L, H, D], cache)` is how the queries
    meet the keys, and nothing else says it: over the sequence itself
    (`_attention`; no cache: None), through `generate`'s one-length cache,
    through pages, a ring or a block (serve/paged_kv.py). The score scale
    is `attend`'s too: every one honours `cfg.attention_scale`.

    `cos` None: a model, or a layer, without a position embedding. `rope`:
    a flag (traced, a layer's own) where the caller scans stacked layers of
    a model with a per-layer list (`rotate`). `mesh`, `spec`: the norm
    kernel's (`_ACT_SPEC` in training; the engine's activations are whole
    on every device)."""
    b, l, _ = x.shape
    h = rmsnorm(x, lp["attn_norm"], cfg.norm_eps, mesh=mesh, spec=spec)
    q, k, v = project_qkv(h, lp, cfg)
    if cos is not None:
        q, k = rotate(q, k, cos, sin, positions, rope)
    attn, cache = attend(q, k, v)
    attn = gate_attention(attn.reshape(b, l, -1), h, lp)
    return (attn @ lp["wo"]).astype(x.dtype), cache


def mlp_half(x, lp, cfg: TransformerConfig, mesh=None, spec=P(), layer=None,
             read_by_router=None):
    """The MLP half of a layer of any kind on `x [B, L, D]`, residual
    included: the norm, then `dense_mlp` where `lp` has no router and
    `moe_block` where it has (`layer` is `moe_block`'s: the index at which
    `lp`'s expert stacks, then the whole model's, are read in place;
    `read_by_router`, the layer's `router_input`). Returns x and
    `moe_block`'s statistics whole (None for a dense layer): a cached walk
    takes `["counts"]`, the train step its auxiliary loss."""
    b, l, d = x.shape
    h = rmsnorm(x, lp["mlp_norm"], cfg.norm_eps, mesh=mesh, spec=spec)
    if "router" not in lp:
        return residual(x, dense_mlp(h, lp, cfg), cfg), None
    y, routing = moe_block(h.reshape(b * l, d), lp, cfg, layer,
                           read_by_router)
    return residual(x, y.reshape(b, l, d), cfg), routing


def attention_layer(x, lp, cfg: TransformerConfig, cos, sin, positions,
                    attend, mesh=None, spec=P(), rope=None, layer=None):
    """One attention layer on `x [B, L, D]`: `attention_mixer`, the
    residual, `mlp_half`; their arguments. Returns the layer's output, its
    routing statistics (None for a dense layer) and `attend`'s cache, as
    `latent_layer` does."""
    read_by_router = router_input(x, cfg)
    y, cache = attention_mixer(x, lp, cfg, cos, sin, positions, attend, mesh,
                               spec, rope)
    x, routing = mlp_half(residual(x, y, cfg), lp, cfg, mesh, spec, layer,
                          read_by_router)
    return x, routing, cache


def layer_kinds(cfg: TransformerConfig):
    """For every layer of a hybrid: whether it is a recurrent layer `[n]
    bool` and its index within its own kind's stack `[n] int32`."""
    is_recurrent = np.array([t not in ATTENTION_KINDS
                             for t in cfg.layer_pattern])
    within = np.where(is_recurrent, np.cumsum(is_recurrent),
                      np.cumsum(~is_recurrent)) - 1
    return is_recurrent, within.astype(np.int32)


def mix_recurrent(h, lp: Dict, cfg: TransformerConfig, rows: Dict, n_valid,
                  layer=None):
    """A recurrent layer's mixer on normed activations `h [B, L, D]` from
    its pool's rows by their names, `n_valid [B]` of the rows real: the
    mixer's output and the rows after the last real one. The mixer is the
    one of the kind's module (`RECURRENT_KINDS`), which names the rows it
    takes and returns (`ROWS`): a gated short convolution keeps `conv`
    alone, a Mamba-2 and a delta-rule layer a `state` and a `conv`. With
    `layer`, a scalar, the rows the module names `IN_POOL` are the whole
    pool `[layers, ...]` and come back whole, that layer's rows advanced
    where they lie (one token only; a module that names none is not given
    a `layer`)."""
    module = RECURRENT_KINDS[cfg.recurrent_kind][1]
    out, *after = module.mixer(
        h, lp, cfg, *(rows[name] for name in module.ROWS), n_valid,
        **({} if layer is None else {"layer": layer}))
    return out, dict(zip(module.ROWS, after))


def at_layer(stack: Dict, i):
    """Layer `i` of a stack of leaves, read where it lies: a consumer
    fuses the index, as it does a scan's own slice of its inputs."""
    return jax.tree.map(lambda w: w[i], stack)


def _hybrid_layers(params, x, cfg: TransformerConfig, mesh, positions):
    """A hybrid's layers over whole sequences `x [B, L, D]`, every
    recurrent layer from a zero state: a scan a kind of MLP (ONE over all
    layers for a model without experts; the leading dense layers', then
    the expert layers'), each layer taking its kind's mixer under a
    `lax.cond` and reading its weights from its kind's stack at its own
    index (the MLPs, one a layer, are the scan's inputs). Differentiable;
    nothing is carried but `x`. Returns x and the expert layers' stacked
    routing statistics (None without experts)."""
    _check_hybrid(cfg)
    layers = params["layers"]
    b, l, _ = x.shape
    stack_name, recurrent = RECURRENT_KINDS[cfg.recurrent_kind]
    fresh = at_layer(recurrent.init_state(cfg, 1, b), 0)
    every_row = jnp.full((b,), l, jnp.int32)
    cos, sin = rope_tables(cfg, cfg.max_seq)

    def attend(q, k, v):
        return _attention(cfg, q, k, v, mesh, positions), None

    def recurrent_mixer(x, j):
        lp = at_layer(layers[stack_name], j)
        h = rmsnorm(x, lp["norm"], cfg.norm_eps, mesh=mesh, spec=_ACT_SPEC)
        return mix_recurrent(h, lp, cfg, fresh, every_row)[0]

    def attn_mixer(x, j):
        return attention_mixer(x, at_layer(layers["attn"], j), cfg, cos, sin,
                               positions, attend, mesh, _ACT_SPEC)[0]

    def body(x, inputs):
        mlp, is_recurrent, j = inputs
        x = residual(x, jax.lax.cond(
            is_recurrent, recurrent_mixer, attn_mixer, x, j).astype(x.dtype),
            cfg)
        return mlp_half(x, mlp, cfg, mesh, _ACT_SPEC)

    if cfg.remat:
        body = jax.checkpoint(body)
    is_recurrent, within = layer_kinds(cfg)
    n_dense = cfg.n_layers - cfg.expert_layers
    routing = None
    for name, span in (("mlp", slice(0, n_dense)),
                       ("moe", slice(n_dense, cfg.n_layers))):
        if span.stop > span.start:
            x, stats = jax.lax.scan(
                body, x, (layers[name], is_recurrent[span], within[span]))
            routing = stats if stats is not None else routing
    return x, routing


def rope_tables(cfg: TransformerConfig, length: int):
    """(cos, sin) `[length, rotated width // 2]` of the model's position
    embedding, or (None, None) for a model without one. Latent attention
    rotates its `qk_rope_head_dim` alone, at YaRN's frequencies where the
    config has a factor."""
    if cfg.position_embedding_type != "rope":
        return None, None
    if not cfg.kv_lora_rank:
        return rope_frequencies(cfg.head_dim, length, cfg.rope_theta)
    inv_freq, magnitude = None, 1.0
    if cfg.rope_factor > 1.0:
        inv_freq = yarn_inv_freq(
            cfg.qk_rope_head_dim, cfg.rope_theta, cfg.rope_factor,
            cfg.rope_original_max_position, cfg.rope_beta_fast,
            cfg.rope_beta_slow)
        magnitude = (yarn_mscale(cfg.rope_factor, cfg.rope_mscale)
                     / yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim))
    return rope_frequencies(cfg.qk_rope_head_dim, length, cfg.rope_theta,
                            inv_freq=inv_freq, magnitude=magnitude)


def project_latent(h, lp, cfg: TransformerConfig, cos, sin, positions):
    """Latent attention's four products of normed activations `h [B, L,
    D]`: every head's query without a position `q_n [B, L, H, dn]` and
    with one `q_r [B, L, H, dr]`, the token's normed latent `c [B, L,
    rank]` and its one rope key `k_r [B, L, dr]`, both rotated. `c` and
    `k_r` side by side are what a cache holds of the token."""
    b, l, _ = h.shape
    with jax.named_scope("mla.project"):
        cq = h
        if cfg.q_lora_rank:
            cq = rmsnorm(h @ lp["wq_a"], lp["q_a_norm"], cfg.norm_eps,
                         use_pallas=False)
        q_n = (cq @ lp["wq_n"]).reshape(b, l, cfg.n_heads, -1)
        q_r = (cq @ lp["wq_r"]).reshape(b, l, cfg.n_heads, -1)
        c = rmsnorm(h @ lp["wkv_a"], lp["kv_a_norm"], cfg.norm_eps,
                    use_pallas=False)
        k_r = (h @ lp["wk_r"])[:, :, None, :]
        if cos is not None:
            q_r = apply_rope(q_r, cos, sin, positions)
            k_r = apply_rope(k_r, cos, sin, positions)
        return q_n, q_r, c, k_r[:, :, 0]


def expand_latent(c, lp, cfg: TransformerConfig):
    """Every head's keys without a position `[B, K, H, dn]` and values
    `[B, K, H, dv]` out of latents `c [B, K, rank]`."""
    b, k, _ = c.shape
    return ((c @ lp["w_uk"]).reshape(b, k, cfg.n_heads, -1),
            (c @ lp["w_uv"]).reshape(b, k, cfg.n_heads, -1))


def _latent_attention_full(q_n, q_r, c, k_r, lp, cfg: TransformerConfig):
    """Causal latent attention of whole sequences from position 0, in the
    expanded form and plain `jax.numpy` (differentiable): `[B, L, H * dv]`."""
    b, l = c.shape[:2]
    with jax.named_scope("mla.attend"):
        k_n, v = expand_latent(c, lp, cfg)
        scores = (jnp.einsum("bqhn,bkhn->bhqk", q_n, k_n,
                             preferred_element_type=jnp.float32)
                  + jnp.einsum("bqhr,bkr->bhqk", q_r, k_r,
                               preferred_element_type=jnp.float32))
        causal = jnp.tril(jnp.ones((l, l), dtype=bool))
        scores = jnp.where(causal, scores * cfg.attention_scale, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
        return jnp.einsum("bhqk,bkhv->bqhv", probs, v).reshape(b, l, -1)


def latent_layer(x, lp, cfg: TransformerConfig, cos, sin, positions, attend,
                 mesh=None, layer=None):
    """One layer of a latent-attention decoder on `x [B, L, D]`, for the
    training forward and the cached walks alike. `attend(q_n, q_r, c, k_r)
    -> (attention [B, L, H * dv], cache)` is how the queries meet the keys:
    over the sequence itself (no cache: None), or through a cache it also
    writes. The MLP is dense where `lp` has no router; `layer` is
    `moe_block`'s. Returns the layer's output, its routing statistics
    (None for a dense layer) and `attend`'s cache."""
    h = rmsnorm(x, lp["attn_norm"], cfg.norm_eps, mesh=mesh, spec=_ACT_SPEC)
    attn, cache = attend(*project_latent(h, lp, cfg, cos, sin, positions))
    x = residual(x, (attn @ lp["wo"]).astype(x.dtype), cfg)
    x, routing = mlp_half(x, lp, cfg, mesh, _ACT_SPEC, layer)
    return x, routing, cache


def latent_stacks(params):
    """A latent-attention decoder's stacks in the order its layers run,
    `(kind, stack, first layer)`: the dense layers lead."""
    layers, first, out = params["layers"], 0, []
    for kind in ("dense", "moe"):
        if kind in layers:
            out.append((kind, layers[kind], first))
            first += layers[kind]["attn_norm"].shape[0]
    return out


def _latent_layers(params, x, cfg: TransformerConfig, mesh, positions):
    """Whole sequences through a latent-attention decoder's layers: a scan
    a kind. Returns x and the expert layers' stacked routing statistics."""
    _check_latent(cfg)
    cos, sin = rope_tables(cfg, cfg.max_seq)
    routing = None
    for _kind, stack, _first in latent_stacks(params):
        def body(x, lp):
            return latent_layer(
                x, lp, cfg, cos, sin, positions,
                lambda *qck: (_latent_attention_full(*qck, lp, cfg), None),
                mesh)[:2]

        if cfg.remat:
            body = jax.checkpoint(body)
        x, stats = jax.lax.scan(body, x, stack)
        routing = stats if stats is not None else routing
    return x, routing


def _layer_fn(cfg: TransformerConfig, mesh, cos, sin, positions):
    """Build the per-layer body used by lax.scan. A model with per-layer
    lists (`layers_inputs`) is scanned over its layers and each layer's
    two flags, whether it rotates and whether it has the window."""
    per_layer = bool(cfg.window_layout or cfg.rope_layout)

    def body(x, inputs):
        # x: [B, L, D]
        lp, rope, window = inputs if per_layer else (inputs, None, None)

        def attend(q, k, v):
            return _attention(cfg, q, k, v, mesh, positions, window), None

        return attention_layer(x, lp, cfg, cos, sin, positions, attend, mesh,
                               _ACT_SPEC, rope)[:2]

    if cfg.remat:
        if cfg.remat_policy == "dots_nobatch":
            body = jax.checkpoint(
                body,
                policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            )
        elif cfg.remat_policy == "dots":
            body = jax.checkpoint(
                body, policy=jax.checkpoint_policies.dots_saveable
            )
        elif cfg.remat_policy == "full":
            body = jax.checkpoint(body)  # recompute everything (min memory)
        else:
            raise ValueError(
                f"unknown remat_policy {cfg.remat_policy!r}: "
                "expected 'full', 'dots', or 'dots_nobatch'"
            )
    return body


def layers_inputs(layers: Dict, cfg: TransformerConfig):
    """What `_layer_fn`'s body is scanned over: the stacked layers, and for
    a model with per-layer lists each layer's two flags beside them."""
    if not (cfg.window_layout or cfg.rope_layout):
        return layers
    window = cfg.window_layout or (False,) * cfg.n_layers
    for name, flags in (("rope_layout", cfg.rope_layers),
                        ("sliding_window_layout", window)):
        if len(flags) != cfg.n_layers:
            raise ValueError(f"{name} names {len(flags)} layers, n_layers "
                             f"is {cfg.n_layers}")
    return layers, jnp.asarray(cfg.rope_layers), jnp.asarray(window)


def _aux_loss(routing, tokens: int):
    """The load-balancing term of a forward pass over `tokens` tokens from
    the layers' stacked routing statistics (zero for a dense model)."""
    if routing is None:
        return jnp.zeros((), dtype=jnp.float32)
    return load_balancing_loss(routing["prob_mean"], routing["counts"],
                               tokens)


def forward(
    params: Dict,
    tokens: jax.Array,  # [batch, seq] int32
    cfg: TransformerConfig,
    mesh=None,
    positions: Optional[jax.Array] = None,
    return_hidden: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (logits [B, L, vocab], aux_loss scalar); with
    return_hidden, the pre-lm_head hidden states [B, L, D] instead of
    logits (the chunked-CE loss applies lm_head itself)."""
    x = _embed_tokens(params, tokens, cfg)
    if cfg.layer_pattern:
        x, routing = _hybrid_layers(params, x, cfg, mesh, positions)
    elif cfg.kv_lora_rank:
        x, routing = _latent_layers(params, x, cfg, mesh, positions)
    else:
        cos, sin = rope_tables(cfg, cfg.max_seq)
        body = _layer_fn(cfg, mesh, cos, sin, positions)
        x, routing = jax.lax.scan(body, x,
                                  layers_inputs(params["layers"], cfg))
    aux = _aux_loss(routing, tokens.size)
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps, mesh=mesh,
                spec=_ACT_SPEC)
    if return_hidden:
        return x, aux
    return project_logits(x, params, cfg), aux


def forward_pipelined(
    params: Dict,
    tokens: jax.Array,  # [batch, seq] int32
    cfg: TransformerConfig,
    mesh,
    num_microbatches: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Pipeline-parallel forward: the layer stack shards over "pp" stages.

    Each pp rank holds n_layers/S contiguous layers; microbatches stream
    through the GPipe schedule of parallel.pipeline.pipeline_stages (all
    stages inside one compiled program, activations rotated with ppermute).
    Embedding and the LM head are replicated — they run on every rank, but
    only the layer stack (the bulk of the FLOPs) is pipelined.
    """
    from ray_tpu.parallel.pipeline import pipeline_stages

    S = mesh.shape["pp"]
    dp_extent = mesh.shape.get("dp", 1) * mesh.shape.get("fsdp", 1)
    b, l = tokens.shape
    M = num_microbatches or cfg.pp_microbatches
    if not M:
        # Auto: prefer 2*S microbatches, but each microbatch's batch dim
        # must still split over dp/fsdp.
        for cand in (2 * S, S, 1):
            if b % cand == 0 and (b // cand) % dp_extent == 0:
                M = cand
                break
        else:
            raise ValueError(
                f"batch {b} cannot form pp microbatches divisible by the "
                f"dp extent {dp_extent}; pick batch = k * {S} * {dp_extent}"
            )
    if b % M != 0:
        raise ValueError(f"batch {b} not divisible by {M} pp microbatches")
    if (b // M) % dp_extent != 0:
        raise ValueError(
            f"microbatch size {b // M} not divisible by dp extent "
            f"{dp_extent} (batch {b}, {M} microbatches)"
        )
    if cfg.n_layers % S != 0:
        raise ValueError(f"n_layers {cfg.n_layers} not divisible by pp={S}")
    if cfg.num_experts:
        raise ValueError(
            "pipeline parallelism currently supports dense layers only "
            "(the MoE aux loss does not thread through the pp schedule)"
        )
    if (cfg.layer_pattern or cfg.kv_lora_rank or cfg.window_layout
            or cfg.rope_layout):
        raise ValueError(
            "pipeline parallelism needs stages of like layers: stacks of "
            "unlike kinds (a hybrid's mixers, dense layers before expert "
            "layers, window layers among full ones) do not split into pp "
            "stages")

    x = _embed_tokens(params, tokens, cfg)
    cos, sin = rope_tables(cfg, cfg.max_seq)
    body = _layer_fn(cfg, mesh, cos, sin, None)

    def stage_fn(stage_layers, act):
        # stage_layers: leaves [n_layers/S, ...] — this rank's stage.
        act, _ = jax.lax.scan(body, act, stage_layers)
        return act

    xm = x.reshape(M, b // M, l, x.shape[-1])
    # pp composes with data parallelism: each microbatch's batch dim
    # splits over dp/fsdp inside the pipeline shard_map, so a dp×pp mesh
    # runs dp-many replicas of every pipeline stage.
    dp_axes = tuple(
        a for a in ("dp", "fsdp") if mesh.shape.get(a, 1) > 1
    )
    x_spec = P(None, dp_axes) if dp_axes else P()
    ym = pipeline_stages(
        stage_fn, params["layers"], xm, mesh, axis_name="pp", x_spec=x_spec
    )
    x = ym.reshape(b, l, x.shape[-1])
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps, mesh=mesh,
                spec=_ACT_SPEC)
    return project_logits(x, params, cfg), jnp.zeros((), dtype=jnp.float32)


def _head_axes(cfg: TransformerConfig):
    """`lm_head_weight`'s logical axes `[D, V]`, from the parameter's own."""
    axes = param_logical_axes(cfg)
    return axes["embed"][::-1] if cfg.tie_embeddings else axes["lm_head"]


def _table_split_on_model_axis(cfg: TransformerConfig, mesh) -> bool:
    """Whether `mesh` splits the head's table on its model axis."""
    return (mesh is not None
            and any(mesh.shape.get(a, 1) > 1 for a in DEFAULT_RULES["embed"])
            and "embed" in _head_axes(cfg))


def _chunked_loss(hidden, table, labels, cfg: TransformerConfig):
    return chunked_lm_head_ce(hidden, table, labels, cfg.ce_chunk,
                              softcap=cfg.final_logit_softcap)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _chunked_loss_table_whole(hidden, table, labels, cfg, mesh, axes):
    """`_chunked_loss` of a table that `mesh` splits over "fsdp" on its
    model axis (`axes`, its logical axes): gathered once for both of the
    loss's scans (the forward, and the backward's, which recomputes each
    chunk's logits), its gradient accumulated whole over the chunks and
    reduce-scattered once after them. The layouts are constraints; the
    collectives are the partitioner's.

    The rule is written out because the hint alone costs memory where the
    step is deepest. Nothing but the returned loss reads the forward scan,
    so the scheduler puts it last and keeps the gathered table through the
    layers' backward; it puts the reduce-scatter after the layers'
    backward too and keeps the gathered gradient as long. The two barriers
    say what the data does not: the backward scan follows the forward one,
    and the layers' backward follows the reduce-scatter."""
    return _table_whole_fwd(hidden, table, labels, cfg, mesh, axes)[0]


def _whole_on_model_axis(x, mesh, axes):
    return with_sharding_constraint(
        x, tuple(None if a == "embed" else a for a in axes), mesh)


def _table_whole_fwd(hidden, table, labels, cfg, mesh, axes):
    table = _whole_on_model_axis(table, mesh, axes)
    loss = _chunked_loss(hidden, table, labels, cfg)
    return loss, (hidden, table, labels, loss)


def _table_whole_bwd(cfg, mesh, axes, residuals, g):
    hidden, table, labels, loss = residuals
    hidden, table, _ = jax.lax.optimization_barrier((hidden, table, loss))
    _, vjp = jax.vjp(lambda h, t: _chunked_loss(h, t, labels, cfg),
                     hidden, table)
    d_hidden, d_table = vjp(g)
    # Whole as the table was, so that the backward scan's carry is; then
    # as the parameter lies: the one reduce-scatter.
    d_table = _whole_on_model_axis(d_table, mesh, axes)
    d_table = with_sharding_constraint(d_table, axes, mesh)
    return (*jax.lax.optimization_barrier((d_hidden, d_table)), None)


_chunked_loss_table_whole.defvjp(_table_whole_fwd, _table_whole_bwd)


def loss_fn(params, tokens, cfg: TransformerConfig, mesh=None,
            aux_weight: float = 0.01):
    """Next-token LM loss. tokens: [B, L]; predicts tokens[:, 1:].

    With a pp>1 mesh the forward runs the GPipe microbatch pipeline; the
    backward differentiates straight through it (static-bound scan), which
    is what makes MeshConfig(pp=...) a real training capability.

    The chunked loss is handed the head's table whole over "fsdp" (its
    own logical axes with "embed" left whole, so still split over "tp"
    on the vocabulary): the table does not change during the step, so it
    crosses "fsdp" once each way, one all-gather before the loss's scans
    and one reduce-scatter of its gradient after them. Left as the
    parameter lies, the partitioner gathers it inside every chunk's body,
    forward and backward, and reduce-scatters its gradient once a chunk.
    """
    if cfg.block_length:
        raise NotImplementedError(
            "a block-diffusion model's loss is over noised blocks, and its "
            "noise schedule is not in the published config: nothing trains "
            "it here, and the next-token loss does not stand in")
    labels = tokens[:, 1:]
    if mesh is not None and mesh.shape.get("pp", 1) > 1:
        logits, aux = forward_pipelined(params, tokens[:, :-1], cfg, mesh)
    elif cfg.ce_chunk:
        hidden, aux = forward(params, tokens[:, :-1], cfg, mesh,
                              return_hidden=True)
        table = lm_head_weight(params, cfg)
        if _table_split_on_model_axis(cfg, mesh):
            loss = _chunked_loss_table_whole(hidden, table, labels, cfg,
                                             mesh, _head_axes(cfg))
        else:
            loss = _chunked_loss(hidden, table, labels, cfg)
        return loss + aux_weight * aux
    else:
        logits, aux = forward(params, tokens[:, :-1], cfg, mesh)
    loss = softmax_cross_entropy(logits, labels).mean()
    return loss + aux_weight * aux
