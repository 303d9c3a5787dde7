"""Pipeline parallelism: GPipe-style microbatch schedule over a mesh axis.

The reference leaves PP unimplemented (SURVEY.md §2.4: "the compiled-DAG
substrate is the intended future home"). The TPU-native design runs all
pipeline stages inside ONE compiled program: stage weights are sharded over
the "pp" mesh axis, microbatches stream through a lax.scan whose body runs
every stage in parallel (on different devices) and rotates activations to
the next stage with ppermute — the standard JAX SPMD pipelining pattern
(cf. the public scaling-book / praxis approach, re-derived here).

Schedule: with S stages and M microbatches the scan runs S+M-1 ticks;
stage s is active on ticks [s, s+M). Bubble fraction (S-1)/(S+M-1).
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map


def pipeline_stages(
    stage_fn: Callable,
    params_stacked,
    x_microbatches: jax.Array,
    mesh: Mesh,
    axis_name: str = "pp",
    params_spec: P = None,
    x_spec: P = None,
):
    """Run a stage-stacked pipeline.

    Args:
      stage_fn: (stage_params, activation) -> activation. One stage's
        compute. `stage_params` is the DEVICE-LOCAL shard of
        `params_stacked`: leaves keep a leading axis of layers-per-stage
        (stack_len / S), so a stage holding several transformer layers
        scans over them inside stage_fn.
      params_stacked: pytree whose leaves have a leading stack axis
        divisible by S, sharded over `axis_name`.
      x_microbatches: [M, microbatch, ...] input microbatches (replicated
        over the pp axis).
      mesh: mesh with the `axis_name` axis of size S.

    Returns [M, microbatch, ...] outputs of the final stage. Differentiable
    (the tick loop has static bounds, so it lowers to scan).
    """
    S = mesh.shape[axis_name]
    M = x_microbatches.shape[0]
    if params_spec is None:
        params_spec = P(axis_name)
    if x_spec is None:
        x_spec = P()

    def local_fn(params_local, xs):
        # params_local: leaves [stack/S, ...] (this device's stage layers);
        # xs: [M, mb, ...]
        stage_params = params_local
        stage_idx = jax.lax.axis_index(axis_name)
        total_ticks = S + M - 1

        buf_shape = xs.shape[1:]
        state = jnp.zeros(buf_shape, dtype=xs.dtype)  # current activation
        outputs = jnp.zeros_like(xs)

        def tick(t, carry):
            state, outputs = carry
            # Stage 0 ingests microbatch t (when valid); others take the
            # activation rotated from the previous stage.
            mb_idx = jnp.clip(t, 0, M - 1)
            injected = jnp.where(
                (stage_idx == 0) & (t < M), xs[mb_idx], state
            )
            out = stage_fn(stage_params, injected)
            # Last stage emits microbatch t - (S-1).
            emit_idx = t - (S - 1)
            valid_emit = (stage_idx == S - 1) & (emit_idx >= 0)
            outputs = jax.lax.cond(
                valid_emit,
                lambda o: o.at[jnp.clip(emit_idx, 0, M - 1)].set(out),
                lambda o: o,
                outputs,
            )
            # Rotate activations forward: stage s -> s+1 (last wraps to 0,
            # its payload is ignored by the injection select above).
            perm = [(j, (j + 1) % S) for j in range(S)]
            state = jax.lax.ppermute(out, axis_name, perm)
            return state, outputs

        _, outputs = jax.lax.fori_loop(0, total_ticks, tick, (state, outputs))
        # Only the last stage holds real outputs; broadcast them to all
        # pp ranks so the caller sees replicated results.
        outputs = jax.lax.all_gather(outputs, axis_name)[S - 1]
        return outputs

    return shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(params_spec, x_spec),
        out_specs=x_spec,
        check_vma=False,
    )(params_stacked, x_microbatches)
