"""Named model configurations.

Llama-2 family dimensions follow the published architecture (Touvron et
al., arXiv:2307.09288); tiny/test configs keep the same structure at toy
scale for CPU tests. The hybrids: `granite-4.0-h-micro` (Mamba-2 among
attention layers, dense MLPs; `tiny_granite_h`) and `lfm2-24b-a2b` (gated
short convolutions among attention layers, routed experts after two dense
layers; `lfm2-24b-a2b-l10`, its first ten layers, is what one chip serves;
`tiny_lfm2_moe`). `sdar-30b-a3b` generates by diffusion over blocks of 4
positions (`sdar-30b-a3b-l6`, its first six layers, is what one chip
serves; `tiny_sdar_moe`).
"""

from __future__ import annotations

from dataclasses import replace

import jax.numpy as jnp

from ray_tpu.models.transformer import TransformerConfig

# -- test-scale ------------------------------------------------------------

tiny = TransformerConfig(
    vocab_size=256,
    d_model=64,
    n_layers=2,
    n_heads=4,
    n_kv_heads=4,
    d_ff=128,
    max_seq=128,
    dtype=jnp.float32,
    remat=False,
)

tiny_gqa = TransformerConfig(
    vocab_size=256,
    d_model=64,
    n_layers=2,
    n_heads=8,
    n_kv_heads=2,
    d_ff=128,
    max_seq=128,
    dtype=jnp.float32,
    remat=False,
)

tiny_moe = TransformerConfig(
    vocab_size=256,
    d_model=64,
    n_layers=2,
    n_heads=4,
    n_kv_heads=4,
    d_ff=128,
    max_seq=128,
    dtype=jnp.float32,
    num_experts=4,
    experts_per_token=2,
    remat=False,
)

# -- benchmark-scale (fits one v5e chip in bf16 for forward benches) -------

llama2_1b = TransformerConfig(
    vocab_size=32000,
    d_model=2048,
    n_layers=16,
    n_heads=16,
    n_kv_heads=16,
    d_ff=5504,
    max_seq=2048,
)

# -- production-scale ------------------------------------------------------

llama2_7b = TransformerConfig(
    vocab_size=32000,
    d_model=4096,
    n_layers=32,
    n_heads=32,
    n_kv_heads=32,
    d_ff=11008,
    max_seq=4096,
)

llama2_13b = TransformerConfig(
    vocab_size=32000,
    d_model=5120,
    n_layers=40,
    n_heads=40,
    n_kv_heads=40,
    d_ff=13824,
    max_seq=4096,
)

llama2_70b = TransformerConfig(
    vocab_size=32000,
    d_model=8192,
    n_layers=80,
    n_heads=64,
    n_kv_heads=8,  # GQA
    d_ff=28672,
    max_seq=4096,
)

llama3_8b = TransformerConfig(
    vocab_size=128256,
    d_model=4096,
    n_layers=32,
    n_heads=32,
    n_kv_heads=8,
    d_ff=14336,
    max_seq=8192,
    rope_theta=500000.0,
)

tiny_gemma = TransformerConfig(
    vocab_size=256,
    d_model=64,
    n_layers=2,
    n_heads=4,
    n_kv_heads=1,
    d_ff=256,
    max_seq=128,
    dtype=jnp.float32,
    remat=False,
    activation="gelu",
    final_logit_softcap=30.0,
    tie_embeddings=True,
    scale_embeddings=True,
)

# Gemma-2B architecture (arXiv:2403.08295: GeGLU MLP, MQA, tied
# embeddings, sqrt(d) embedding scaling, final logit softcap).
gemma_2b = TransformerConfig(
    vocab_size=256128,
    d_model=2048,
    n_layers=18,
    n_heads=8,
    n_kv_heads=1,
    d_ff=16384,
    max_seq=8192,
    activation="gelu",
    final_logit_softcap=30.0,
    tie_embeddings=True,
    scale_embeddings=True,
)

mixtral_8x7b = TransformerConfig(
    vocab_size=32000,
    d_model=4096,
    n_layers=32,
    n_heads=32,
    n_kv_heads=8,
    d_ff=14336,
    max_seq=4096,
    num_experts=8,
    experts_per_token=2,
    norm_topk_prob=True,
)

tiny_qwen = TransformerConfig(
    vocab_size=256,
    d_model=64,
    n_layers=2,
    n_heads=4,
    n_kv_heads=2,
    d_ff=256,
    max_seq=128,
    dtype=jnp.float32,
    remat=False,
    qk_norm=True,
    custom_head_dim=32,  # wider than d_model/n_heads, the Qwen3 shape
)

# Qwen3-4B architecture (arXiv:2505.09388): GQA with fixed 128-wide
# heads, per-head-dim QK-norm instead of QKV bias, SwiGLU, 1M rope theta.
qwen3_4b = TransformerConfig(
    vocab_size=151936,
    d_model=2560,
    n_layers=36,
    n_heads=32,
    n_kv_heads=8,
    d_ff=9728,
    max_seq=32768,
    rope_theta=1000000.0,
    qk_norm=True,
    custom_head_dim=128,
    tie_embeddings=True,
)

tiny_olmoe = TransformerConfig(
    vocab_size=256,
    d_model=64,
    n_layers=2,
    n_heads=4,
    n_kv_heads=4,
    d_ff=32,  # one expert's width
    max_seq=128,
    norm_eps=1e-5,
    dtype=jnp.float32,
    remat=False,
    num_experts=8,
    experts_per_token=2,
    qk_norm=True,
    qk_norm_extent="projection",
)

# OLMoE-1B-7B-0125-Instruct (arXiv:2409.02060; the model's public
# config.json): 64 experts of width 1024, 8 a token, none shared, router
# weights not renormalised; multi-head attention with a QK-norm over the
# whole q and k projections; untied 50,304-row vocabulary. 6.92 B
# parameters, 1.28 B used by a token.
olmoe_1b_7b = TransformerConfig(
    vocab_size=50304,
    d_model=2048,
    n_layers=16,
    n_heads=16,
    n_kv_heads=16,
    d_ff=1024,  # one expert's width
    max_seq=4096,
    rope_theta=10000.0,
    norm_eps=1e-5,
    num_experts=64,
    experts_per_token=8,
    qk_norm=True,
    qk_norm_extent="projection",
)

# Both kinds of layer, two Mamba layers in a row, and blocks of 8 rows so
# that a test prompt spans several.
tiny_granite_h = TransformerConfig(
    vocab_size=256,
    d_model=64,
    n_layers=4,
    n_heads=4,
    n_kv_heads=2,
    d_ff=128,
    max_seq=128,
    norm_eps=1e-5,
    dtype=jnp.float32,
    remat=False,
    tie_embeddings=True,
    layer_pattern=("mamba", "mamba", "attention", "mamba"),
    mamba_n_heads=8,
    mamba_d_head=16,
    mamba_d_state=16,
    mamba_chunk_size=8,
    embedding_multiplier=12.0,
    attention_multiplier=0.0625,
    residual_multiplier=0.22,
    logits_scaling=8.0,
    position_embedding_type="nope",
)

# granite-4.0-h-micro (the model's public config.json, `model_type`
# granitemoehybrid; Mamba-2 is arXiv:2405.21060): 36 Mamba-2 layers and 4
# attention layers (5, 15, 25, 35) without a position embedding, every
# layer followed by a dense SwiGLU MLP of width 8192 (no routed experts),
# Granite's four multipliers, a tied 100,352-row vocabulary. 3.19 B
# parameters.
granite_4_0_h_micro = TransformerConfig(
    vocab_size=100352,
    d_model=2048,
    n_layers=40,
    n_heads=32,
    n_kv_heads=8,
    d_ff=8192,
    max_seq=131072,
    rope_theta=10000.0,  # published, and unused: no position embedding
    norm_eps=1e-5,
    tie_embeddings=True,
    layer_pattern=tuple("attention" if i in (5, 15, 25, 35) else "mamba"
                      for i in range(40)),
    mamba_n_heads=64,
    mamba_d_head=64,
    mamba_d_state=128,
    mamba_d_conv=4,
    mamba_n_groups=1,
    mamba_expand=2,
    mamba_chunk_size=256,
    mamba_conv_bias=True,
    mamba_proj_bias=False,
    embedding_multiplier=12.0,
    attention_multiplier=0.015625,
    residual_multiplier=0.22,
    logits_scaling=8.0,
    position_embedding_type="nope",
)

# Every mechanism of the model below at toy widths: latent attention with a
# query bottleneck, one leading dense layer, sigmoid scores chosen within 2
# of 4 groups, a shared expert, YaRN, and blocks of 16 cached rows so that a
# test prompt spans several.
tiny_dots = TransformerConfig(
    vocab_size=256,
    d_model=64,
    n_layers=3,
    n_heads=4,
    n_kv_heads=4,
    d_ff=128,
    max_seq=128,
    dtype=jnp.float32,
    remat=False,
    q_lora_rank=32,
    kv_lora_rank=32,
    qk_nope_head_dim=16,
    qk_rope_head_dim=8,
    v_head_dim=16,
    first_k_dense_replace=1,
    moe_intermediate_size=32,
    num_experts=16,
    experts_per_token=4,
    n_shared_experts=1,
    scoring_func="sigmoid",
    n_group=4,
    topk_group=2,
    norm_topk_prob=True,
    routed_scaling_factor=2.5,
    rope_factor=4.0,
    rope_original_max_position=32,
    rope_beta_fast=4.0,
    rope_mscale=1.0,
    rope_mscale_all_dim=1.0,
)

# dots.vlm1.inst's language model (the model's public config.json,
# `model_type` dots_vlm; the block is DeepSeek-V3's, arXiv:2412.19437):
# 61 layers of latent attention (128 heads of 128 + 64, a 512-wide latent
# and one 64-wide rope key a token, queries through 1536), the first 3
# with a dense MLP of 18432, the others 256 routed experts of 2048, 8 a
# token chosen by sigmoid scores within 4 of 8 groups and weighted 2.5
# times their normalised scores, beside 1 shared expert; YaRN by 40 over
# 4096; an untied 129,280-row vocabulary. 672 B parameters, 37 B used by a
# token. Its image tower and its multi-token-prediction module are not
# here (bench/configs/dots-vlm1-ep16-serve.json says why).
dots_vlm1 = TransformerConfig(
    vocab_size=129280,
    d_model=7168,
    n_layers=61,
    n_heads=128,
    n_kv_heads=128,
    d_ff=18432,
    max_seq=4096,
    rope_theta=10000.0,
    norm_eps=1e-6,
    q_lora_rank=1536,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    first_k_dense_replace=3,
    moe_intermediate_size=2048,
    num_experts=256,
    experts_per_token=8,
    n_shared_experts=1,
    scoring_func="sigmoid",
    n_group=8,
    topk_group=4,
    norm_topk_prob=True,
    routed_scaling_factor=2.5,
    rope_factor=40.0,
    rope_original_max_position=4096,
    rope_beta_fast=32.0,
    rope_beta_slow=1.0,
    rope_mscale=1.0,
    rope_mscale_all_dim=1.0,
)

# One chip's share of it, as one of 16 chips that share each layer: 16 of
# the 256 routed experts (share 0: experts 0-15; the router's width stays
# 256), attention, the shared expert, the router and the dense layer whole,
# an eighth of the vocabulary, and ONE leading dense layer (leading dense
# layers count once in a cut of depth; the benchmark's file gives the depth
# it runs, 6: the others would lie on further chips, as pipeline stages).
dots_vlm1_ep16 = replace(
    dots_vlm1, vocab_size=16160, first_k_dense_replace=1,
    experts_held=16, expert_share=0)

# Every mechanism of the model below at toy widths: gated short
# convolutions and attention layers (a QK-norm by head, a rope), two conv
# layers in a row, ONE leading dense layer, 8 routed experts of which a
# token takes 2 by sigmoid scores and a choice bias (zero here: a test draws
# it), blocks of 8 cached rows so that a test prompt spans several.
tiny_lfm2_moe = TransformerConfig(
    vocab_size=256,
    d_model=64,
    n_layers=5,
    n_heads=4,
    n_kv_heads=2,
    d_ff=128,
    max_seq=128,
    rope_theta=1e6,
    norm_eps=1e-5,
    dtype=jnp.float32,
    remat=False,
    qk_norm=True,
    layer_pattern=("conv", "conv", "full_attention", "conv",
                   "full_attention"),
    conv_L_cache=3,
    first_k_dense_replace=1,
    moe_intermediate_size=32,
    num_experts=8,
    experts_per_token=2,
    scoring_func="sigmoid",
    norm_topk_prob=True,
    use_expert_bias=True,
    norm_topk_eps=1e-6,
)

# LFM2-24B-A2B (the model's public config.json, `model_type` lfm2_moe;
# Liquid AI's LFM2 technical report): 40 layers, 30 gated short
# convolutions (kernel 3, no bias) and 10 attention layers at 2, 6, ..., 38
# (32 query and 8 KV heads of 64, an RMSNorm over each head of q and k, a
# rope at theta 1e6); the first 2 layers have a dense SwiGLU MLP of 11776,
# the other 38 have 64 routed experts of 1536, 4 a token chosen by sigmoid
# score + a learned bias and weighted by their scores over the sum + 1e-6;
# a 65,536-row vocabulary, the head held apart from the table (the public
# config read here has no key for tying; apart, 23.98 B parameters, tied
# 23.84 B; bench/configs/lfm2-24b-a2b-serve.json says why apart). 2.3 B
# used by a token.
lfm2_24b_a2b = TransformerConfig(
    vocab_size=65536,
    d_model=2048,
    n_layers=40,
    n_heads=32,
    n_kv_heads=8,
    d_ff=11776,
    max_seq=128000,
    rope_theta=1e6,
    norm_eps=1e-5,
    qk_norm=True,
    layer_pattern=tuple("full_attention" if i % 4 == 2 else "conv"
                        for i in range(40)),
    conv_L_cache=3,
    first_k_dense_replace=2,
    moe_intermediate_size=1536,
    num_experts=64,
    experts_per_token=4,
    scoring_func="sigmoid",
    norm_topk_prob=True,
    routed_scaling_factor=1.0,
    use_expert_bias=True,
    norm_topk_eps=1e-6,
)

# Its first 10 layers exactly as published, what one chip holds whole
# (conv, conv, attention, conv, conv, conv, attention, conv, conv, conv:
# both dense layers and eight expert layers, two periods of the pattern);
# the other 30 would lie on four further chips, as pipeline stages.
lfm2_24b_a2b_l10 = replace(
    lfm2_24b_a2b, n_layers=10, layer_pattern=lfm2_24b_a2b.layer_pattern[:10])

# Every mechanism of the model below at toy widths: two periods of one
# gated NoPE attention layer and three delta-rule layers (4 heads of 16 x
# 16, a 4-tap convolution, beta up to 2), every layer's MLP 8 routed experts
# (2 a token by sigmoid scores and a choice bias, zero here: a test draws
# it) of which this share holds 4, beside a shared expert.
tiny_solar_open2 = TransformerConfig(
    vocab_size=256,
    d_model=64,
    n_layers=8,
    n_heads=4,
    n_kv_heads=2,
    d_ff=128,
    max_seq=128,
    norm_eps=1e-5,
    dtype=jnp.float32,
    remat=False,
    custom_head_dim=16,
    position_embedding_type="nope",
    attn_output_gate=True,
    layer_pattern=("full_attention", "kda", "kda", "kda") * 2,
    kda_num_heads=4,
    kda_head_dim=16,
    kda_short_conv_kernel_size=4,
    kda_allow_neg_eigval=True,
    moe_intermediate_size=32,
    num_experts=8,
    experts_per_token=2,
    n_shared_experts=1,
    scoring_func="sigmoid",
    norm_topk_prob=True,
    use_expert_bias=True,
    experts_held=4,
    expert_share=0,
)

# Solar-Open2-250B (the model's public config.json, `model_type`
# solar_open2, upstage; the delta-rule layers are Kimi Linear's,
# arXiv:2510.26692): 48 layers of hidden size 4096; layers 0, 4, ..., 44
# (`gqa_layers`) are softmax attention with 64 query and 8 KV heads of 128,
# no position embedding (`use_rope` false) and a sigmoid gate on the heads'
# outputs (`use_gqa_gate`), the three between are channel-gated delta-rule
# layers (64 heads of 128 x 128, a 4-tap convolution on q, k and v, beta up
# to 2); every layer's MLP is 320 routed experts of 1280, 8 a token by
# sigmoid scores and a choice bias, their weights over their sum, beside
# one shared expert of 1280; an untied 196,608-row vocabulary. 250 B
# parameters, 15 B used by a token. `d_ff` is the published
# `intermediate_size`, which no layer uses (`first_k_dense_replace` 0).
solar_open2_250b = TransformerConfig(
    vocab_size=196608,
    d_model=4096,
    n_layers=48,
    n_heads=64,
    n_kv_heads=8,
    d_ff=10240,
    max_seq=1048576,
    rope_theta=10000.0,  # published, and unused: no position embedding
    norm_eps=1e-5,
    custom_head_dim=128,
    position_embedding_type="nope",
    attn_output_gate=True,
    layer_pattern=tuple("full_attention" if i % 4 == 0 else "kda"
                        for i in range(48)),
    kda_num_heads=64,
    kda_head_dim=128,
    kda_short_conv_kernel_size=4,
    kda_allow_neg_eigval=True,
    first_k_dense_replace=0,
    moe_intermediate_size=1280,
    num_experts=320,
    experts_per_token=8,
    n_shared_experts=1,
    scoring_func="sigmoid",
    norm_topk_prob=True,
    routed_scaling_factor=1.0,
    use_expert_bias=True,
)

# One chip's share of it, as one of 8 chips that share each layer: 40 of
# the 320 routed experts (share 0: experts 0-39; the router's width stays
# 320), both kinds of mixer, the shared expert and the router whole, an
# eighth of the vocabulary, and its first period (layers 0-3: the attention
# layer and three delta-rule layers); the other 44 layers would lie on
# further groups of eight, as pipeline stages.
solar_open2_250b_ep8_l4 = replace(
    solar_open2_250b, n_layers=4,
    layer_pattern=solar_open2_250b.layer_pattern[:4], vocab_size=24576,
    experts_held=40, expert_share=0)

# SDAR-30B-A3B-Chat (arXiv:2510.06303; the model's public config.json,
# `model_type` sdar_moe, derived from Qwen3-MoE's modelling code): 48 layers
# of grouped-query attention (32 query and 4 key-value heads of 128, a
# QK-norm a head) and 128 experts of width 768, 8 a token, softmax scores
# renormalised over the chosen 8, none shared; `intermediate_size` 6144 is
# published and no layer uses it. Untied 151,936 rows. Generation by
# diffusion over blocks: the block length (4, the -Chat models' released
# default), the mask token (the tokenizer's `<|MASK|>`) and the passes a
# block (2) are the released generation settings, not in config.json.
sdar_30b_a3b = TransformerConfig(
    vocab_size=151936,
    d_model=2048,
    n_layers=48,
    n_heads=32,
    n_kv_heads=4,
    d_ff=6144,
    max_seq=4096,
    rope_theta=1000000.0,
    norm_eps=1e-6,
    num_experts=128,
    experts_per_token=8,
    moe_intermediate_size=768,
    norm_topk_prob=True,
    qk_norm=True,
    custom_head_dim=128,
    block_length=4,
    mask_token_id=151669,
    denoise_steps=2,
)
sdar_30b_a3b_l6 = replace(sdar_30b_a3b, n_layers=6)

tiny_sdar_moe = TransformerConfig(
    vocab_size=256,
    d_model=64,
    n_layers=2,
    n_heads=4,
    n_kv_heads=2,
    d_ff=128,
    max_seq=128,
    dtype=jnp.float32,
    remat=False,
    num_experts=8,
    experts_per_token=2,
    moe_intermediate_size=32,
    norm_topk_prob=True,
    qk_norm=True,
    custom_head_dim=16,
    block_length=4,
    mask_token_id=255,
    denoise_steps=2,
)

# SmallThinker-21BA3B-Instruct (PowerInfer, arXiv:2507.20984; the model's
# public config.json, `model_name` smallthinker_21b_instruct): 52 layers of
# grouped-query attention (28 query and 4 key-value heads of 128, no bias,
# no QK-norm) and 64 ReGLU experts of width 768, 6 a token, softmax over the
# chosen six, none shared and no dense layer. `sliding_window_layout` and
# `rope_layout` are the same published list, [0, 1, 1, 1] x 13: layers 0, 4,
# 8, ... attend to everything behind them and have no position embedding;
# the three between attend over `sliding_window_size` 4,096 and rotate. The
# router reads the layer's input, before attention's norm. Untied 151,936
# rows.
_SMALLTHINKER_PERIOD = (0, 1, 1, 1)
smallthinker_21b_a3b = TransformerConfig(
    vocab_size=151936,
    d_model=2560,
    n_layers=52,
    n_heads=28,
    n_kv_heads=4,
    d_ff=768,
    max_seq=16384,  # max_position_embeddings
    rope_theta=1.5e6,
    norm_eps=1e-6,
    num_experts=64,
    experts_per_token=6,
    moe_intermediate_size=768,
    norm_topk_prob=True,
    activation="relu",
    custom_head_dim=128,
    sliding_window_size=4096,
    sliding_window_layout=_SMALLTHINKER_PERIOD * 13,
    rope_layout=_SMALLTHINKER_PERIOD * 13,
    router_reads="layer_input",
)
smallthinker_21b_a3b_l8 = replace(
    smallthinker_21b_a3b, n_layers=8,
    sliding_window_layout=_SMALLTHINKER_PERIOD * 2,
    rope_layout=_SMALLTHINKER_PERIOD * 2)

# A window that is no multiple of the engine tests' page (4) and shorter
# than their sequences: the window's edge, a page in the window only in
# part and the ring's wrap are in every test.
tiny_smallthinker = TransformerConfig(
    vocab_size=256,
    d_model=64,
    n_layers=4,
    n_heads=4,
    n_kv_heads=2,
    d_ff=32,
    max_seq=128,
    dtype=jnp.float32,
    remat=False,
    num_experts=8,
    experts_per_token=2,
    moe_intermediate_size=32,
    norm_topk_prob=True,
    activation="relu",
    custom_head_dim=16,
    sliding_window_size=6,
    sliding_window_layout=_SMALLTHINKER_PERIOD,
    rope_layout=_SMALLTHINKER_PERIOD,
    router_reads="layer_input",
)

NAMED_CONFIGS = {
    "tiny": tiny,
    "tiny_gqa": tiny_gqa,
    "tiny_moe": tiny_moe,
    "llama2-1b": llama2_1b,
    "llama2-7b": llama2_7b,
    "llama2-13b": llama2_13b,
    "llama2-70b": llama2_70b,
    "llama3-8b": llama3_8b,
    "tiny_gemma": tiny_gemma,
    "gemma-2b": gemma_2b,
    "mixtral-8x7b": mixtral_8x7b,
    "tiny_qwen": tiny_qwen,
    "qwen3-4b": qwen3_4b,
    "tiny_olmoe": tiny_olmoe,
    "olmoe-1b-7b": olmoe_1b_7b,
    "tiny_granite_h": tiny_granite_h,
    "granite-4.0-h-micro": granite_4_0_h_micro,
    "tiny_dots": tiny_dots,
    "dots-vlm1": dots_vlm1,
    "dots-vlm1-ep16": dots_vlm1_ep16,
    "tiny_lfm2_moe": tiny_lfm2_moe,
    "lfm2-24b-a2b": lfm2_24b_a2b,
    "lfm2-24b-a2b-l10": lfm2_24b_a2b_l10,
    "tiny_solar_open2": tiny_solar_open2,
    "solar-open2-250b": solar_open2_250b,
    "solar-open2-250b-ep8-l4": solar_open2_250b_ep8_l4,
    "tiny_sdar_moe": tiny_sdar_moe,
    "sdar-30b-a3b": sdar_30b_a3b,
    "sdar-30b-a3b-l6": sdar_30b_a3b_l6,
    "tiny_smallthinker": tiny_smallthinker,
    "smallthinker-21b-a3b": smallthinker_21b_a3b,
    "smallthinker-21b-a3b-l8": smallthinker_21b_a3b_l8,
}


def get_config(name: str) -> TransformerConfig:
    if name not in NAMED_CONFIGS:
        raise KeyError(f"unknown model config {name!r}; have {list(NAMED_CONFIGS)}")
    return NAMED_CONFIGS[name]
