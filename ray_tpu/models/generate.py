"""Autoregressive generation with a KV cache.

The inference half of the model stack: prefill runs the full forward once
(flash attention), then decode steps append one token at a time against a
preallocated KV cache — static shapes throughout so the decode step
compiles once and stays on the TPU (`lax.scan` over steps, masked
attention against the cache). What a layer computes is
`transformer.attention_layer`'s, the train step's and the serving engine's
too; this module's own are the cache, the `attend(q, k, v)` that writes and
reads it, and the sampler: an independent loop over an independent cache,
the tests' reference for the engine's tokens.

The reference has no analog (models live in user code); this is what
`serve`-ing an LLM on TPU needs: one jitted `prefill` + one jitted
`decode_step` per (batch, max_len) shape.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from ray_tpu.models.transformer import (
    TransformerConfig,
    _embed_tokens,
    attention_layer,
    layers_inputs,
    project_logits,
    rope_tables,
)
from ray_tpu.ops import rmsnorm

NEG_INF = -1e30


def init_kv_cache(cfg: TransformerConfig, batch: int, max_len: int) -> Dict:
    """Preallocated [layers, batch, max_len, kv_heads, head_dim] cache."""
    if cfg.layer_pattern:
        raise ValueError(
            "generate's cache is keys and values for every layer: a model "
            "with recurrent layers decodes through ContinuousBatchingEngine "
            "(serve/paged_kv.py carries its recurrent pool)")
    shape = (cfg.n_layers, batch, max_len, cfg.n_kv_heads, cfg.head_dim)
    return {
        "k": jnp.zeros(shape, dtype=cfg.dtype),
        "v": jnp.zeros(shape, dtype=cfg.dtype),
        "length": jnp.zeros((), dtype=jnp.int32),
    }


def _cached_attention(q, k_cache, v_cache, cache_len, scale, window=None):
    """q: [B, Lq, H, D] against cache [B, Lmax, KVH, D] (first cache_len
    valid), scores times `scale`. GQA via grouped einsum — decode is
    HBM-bandwidth-bound, so the cache must be read at its native size,
    never repeat-materialized in the hot loop. Causal masking by absolute
    position; `window`, where a layer has one: a query sees itself and the
    `window - 1` keys before it (the cache holds every position all the
    same)."""
    b, lq, h, d = q.shape
    kvh = k_cache.shape[2]
    group = h // kvh
    lmax = k_cache.shape[1]
    # Query i sits at absolute position cache_len - lq + i; key j at j.
    q_pos = cache_len - lq + jax.lax.broadcasted_iota(
        jnp.int32, (lq, lmax), 0
    )
    k_pos = jax.lax.broadcasted_iota(jnp.int32, (lq, lmax), 1)
    valid = (k_pos <= q_pos) & (k_pos < cache_len)
    if window is not None:
        valid &= q_pos - k_pos < window
    kf = k_cache.astype(jnp.float32)
    vf = v_cache.astype(jnp.float32)
    if group == 1:  # MHA: plain 4-D einsum (the 5-D form costs ~10%)
        s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), kf) * scale
        s = jnp.where(valid[None, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bhqk,bkhd->bqhd", p, vf)
        return out.astype(q.dtype)
    qg = q.reshape(b, lq, kvh, group, d).astype(jnp.float32)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, kf) * scale
    s = jnp.where(valid[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p, vf).reshape(b, lq, h, d)
    return out.astype(q.dtype)


def _forward_with_cache(params, tokens, cache, cfg: TransformerConfig):
    """Forward over `tokens` (appended at cache['length']); returns
    (logits for the final position, updated cache)."""
    x = _embed_tokens(params, tokens, cfg)
    lq = tokens.shape[1]
    lmax = cache["k"].shape[2]
    cos, sin = rope_tables(cfg, lmax)
    start = cache["length"]
    positions = start + jnp.arange(lq, dtype=jnp.int32)[None, :]

    per_layer = bool(cfg.window_layout or cfg.rope_layout)

    def layer(x, inputs):
        layer_in, k_cache_l, v_cache_l = inputs
        # A model with per-layer lists: the layer's two flags, whether it
        # rotates and whether it has the window (`layers_inputs`).
        lp, rope, window = layer_in if per_layer else (layer_in, None, None)

        def attend(q, k, v):
            kc = jax.lax.dynamic_update_slice(
                k_cache_l, k.astype(k_cache_l.dtype), (0, start, 0, 0))
            vc = jax.lax.dynamic_update_slice(
                v_cache_l, v.astype(v_cache_l.dtype), (0, start, 0, 0))
            return _cached_attention(
                q, kc, vc, start + lq, cfg.attention_scale,
                None if window is None else jnp.where(
                    window, cfg.sliding_window_size, lmax + 1)), (kc, vc)

        x, _, cache_l = attention_layer(x, lp, cfg, cos, sin, positions,
                                        attend, rope=rope)
        return x, cache_l

    x, (k_new, v_new) = jax.lax.scan(
        layer, x, (layers_inputs(params["layers"], cfg), cache["k"],
                   cache["v"])
    )
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = project_logits(x[:, -1], params, cfg)  # [B, vocab]
    new_cache = {"k": k_new, "v": v_new, "length": start + lq}
    return logits, new_cache


def prefill(params, tokens, cache, cfg: TransformerConfig):
    """Run the prompt through the model, filling the cache.

    Returns (last-position logits [B, vocab], cache).
    """
    return _forward_with_cache(params, tokens, cache, cfg)


def decode_step(params, token, cache, cfg: TransformerConfig):
    """One incremental decode step. token: [B] int32."""
    return _forward_with_cache(params, token[:, None], cache, cfg)


def _filter_top_k(logits: jax.Array, k: int) -> jax.Array:
    """Mask all but the k highest logits to -inf (compiler-friendly:
    lax.top_k + threshold compare, no gather/scatter)."""
    kth = jax.lax.top_k(logits, k)[0][..., -1:]
    return jnp.where(logits < kth, -jnp.inf, logits)


def _filter_top_p(logits: jax.Array, p: float) -> jax.Array:
    """Nucleus filtering: keep the smallest prefix of the probability-
    sorted vocab whose mass reaches p; mask the rest to -inf."""
    sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    # Position i is kept while the mass BEFORE it is < p (so the token
    # that crosses p stays included — standard nucleus convention).
    keep = (cum - probs) < p
    # Threshold logit = smallest kept sorted logit per row.
    threshold = jnp.min(
        jnp.where(keep, sorted_logits, jnp.inf), axis=-1, keepdims=True
    )
    return jnp.where(logits < threshold, -jnp.inf, logits)


@functools.lru_cache(maxsize=64)
def _compiled_generate(cfg: TransformerConfig, max_new_tokens: int,
                       temperature: float, top_k: Optional[int],
                       top_p: Optional[float], eos_id: Optional[int]):
    """One jitted end-to-end program (prefill + scanned decode + pick)
    per (config, sampling signature); jax.jit's own cache handles
    distinct prompt shapes underneath. Without this, generate() ran
    eagerly — every layer op a separate dispatch, every decode step a
    host round trip."""

    def run(params, prompt, rng):
        b, lp = prompt.shape
        max_len = lp + max_new_tokens
        cache = init_kv_cache(cfg, b, max_len)
        logits, cache = prefill(params, prompt, cache, cfg)

        def pick(logits, key):
            if temperature and temperature > 0.0:
                logits = logits / temperature
                if top_k is not None:
                    logits = _filter_top_k(logits, top_k)
                if top_p is not None and top_p < 1.0:
                    logits = _filter_top_p(logits, top_p)
                return jax.random.categorical(key, logits, axis=-1)
            return jnp.argmax(logits, axis=-1)

        rng, key0 = jax.random.split(rng)
        first = pick(logits, key0).astype(jnp.int32)
        done0 = (
            first == eos_id if eos_id is not None
            else jnp.zeros((b,), dtype=bool)
        )

        def step(carry, key):
            token, cache, done = carry
            logits, cache = decode_step(params, token, cache, cfg)
            nxt = pick(logits, key).astype(jnp.int32)
            if eos_id is not None:
                nxt = jnp.where(done, jnp.int32(eos_id), nxt)
                done = done | (nxt == eos_id)
            return (nxt, cache, done), nxt

        if max_new_tokens == 1:
            return first[:, None]
        keys = jax.random.split(rng, max_new_tokens - 1)
        (_, _, _), rest = jax.lax.scan(
            step, (first, cache, done0), keys
        )
        return jnp.concatenate([first[:, None], rest.T], axis=1)

    return jax.jit(run)


def generate(
    params,
    prompt: jax.Array,  # [B, Lp] int32
    cfg: TransformerConfig,
    max_new_tokens: int = 32,
    temperature: float = 0.0,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
    rng: Optional[jax.Array] = None,
    eos_id: Optional[int] = None,
) -> jax.Array:
    """Greedy (temperature=0) or sampled generation with optional top-k /
    nucleus (top-p) filtering; returns [B, max_new_tokens] generated ids
    (padded with eos after stopping). The whole pipeline — prefill,
    the scanned decode loop, and token picks — is ONE jitted program,
    cached per (config, sampling signature): repeat calls at the same
    shapes pay a single dispatch, no per-step host traffic.
    """
    if cfg.block_length:
        raise NotImplementedError(
            "a block-diffusion model fills in a block of positions at once: "
            "the serving engine generates with it (serve.llm), this "
            "one-token-a-step loop does not")
    b, _ = prompt.shape
    if max_new_tokens <= 0:
        return jnp.zeros((b, 0), dtype=jnp.int32)
    if rng is None:
        rng = jax.random.PRNGKey(0)
    fn = _compiled_generate(
        cfg, int(max_new_tokens),
        float(temperature) if temperature else 0.0,
        None if top_k is None else int(top_k),
        None if top_p is None else float(top_p),
        None if eos_id is None else int(eos_id),
    )
    return fn(params, jnp.asarray(prompt, dtype=jnp.int32), rng)
