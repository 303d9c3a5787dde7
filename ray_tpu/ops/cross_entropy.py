"""Memory-lean softmax cross-entropy over large vocabularies.

Computes logsumexp and the label logit without materializing the softmax,
in float32 regardless of input dtype (bf16 logits are standard on TPU).
The backward pass recomputes softmax chunkwise via custom VJP, keeping the
peak memory at O(batch * vocab_chunk) instead of O(batch * vocab).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def softmax_cross_entropy(logits: jax.Array, labels: jax.Array,
                          chunk: int = 0):
    """logits: [..., vocab]; labels: integer [...]. Returns [...] losses."""
    return _ce_forward(logits, labels)[0]


def _ce_forward(logits, labels):
    lf = logits.astype(jnp.float32)
    m = lf.max(axis=-1)
    lse = m + jnp.log(jnp.sum(jnp.exp(lf - m[..., None]), axis=-1))
    label_logit = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
    return lse - label_logit, lse


def chunked_lm_head_ce(hidden: jax.Array, lm_head: jax.Array,
                       labels: jax.Array, chunk: int,
                       softcap: float = 0.0) -> jax.Array:
    """Mean next-token loss computing lm_head logits CHUNK tokens at a
    time, so the full [B, S, vocab] tensor never exists in HBM.

    hidden: [B, S, D] final hidden states; lm_head: [D, V]; labels [B, S].
    Each chunk's matmul + softmax-CE runs under jax.checkpoint: the
    backward recomputes that chunk's logits (one extra lm_head forward,
    ~3% of step FLOPs at Llama shapes) instead of keeping them alive.
    The scan over chunks keeps peak logits memory at B*chunk*V.

    Both scans (this one, and the backward's) close over `lm_head` as it
    is handed in, and the backward's carry holds its gradient in the same
    layout: under a mesh the caller hands it whole over the axes that
    would otherwise be gathered in every chunk's body (`loss_fn` does,
    over "fsdp"); `ce_chunk` bounds the logits, never the table.
    """
    b, s, d = hidden.shape
    if s % chunk != 0:
        raise ValueError(f"seq {s} not divisible by ce_chunk {chunk}")
    n = s // chunk
    xs = hidden.reshape(b, n, chunk, d).swapaxes(0, 1)   # [n, B, chunk, D]
    ys = labels.reshape(b, n, chunk).swapaxes(0, 1)      # [n, B, chunk]

    @jax.checkpoint
    def body(acc, xy):
        x, y = xy
        logits = x @ lm_head
        if softcap:
            logits = softcap * jnp.tanh(
                logits.astype(jnp.float32) / softcap
            )
        loss, _ = _ce_forward(logits, y)
        return acc + loss.sum(), None

    total, _ = jax.lax.scan(body, jnp.float32(0.0), (xs, ys))
    return total / (b * s)


def _ce_fwd(logits, labels, chunk):
    loss, lse = _ce_forward(logits, labels)
    return loss, (logits, labels, lse)


def _ce_bwd(chunk, res, g):
    logits, labels, lse = res
    lf = logits.astype(jnp.float32)
    p = jnp.exp(lf - lse[..., None])
    onehot = jax.nn.one_hot(labels, logits.shape[-1], dtype=jnp.float32)
    dlogits = (p - onehot) * g[..., None].astype(jnp.float32)
    return dlogits.astype(logits.dtype), None


softmax_cross_entropy.defvjp(_ce_fwd, _ce_bwd)
