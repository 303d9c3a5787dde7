"""Device mesh construction and sharding rules.

The scaling-book recipe: pick a mesh, annotate shardings on model/optimizer
pytrees with logical axis names, let GSPMD insert the collectives, profile,
iterate. Axis conventions:

  * "dp"   — pure data parallelism (replicated params, sharded batch)
  * "fsdp" — data parallelism with parameter sharding (ZeRO-3 style:
             XLA all-gathers params per layer, reduce-scatters grads)
  * "tp"   — tensor (megatron-style) parallelism over hidden/head dims
  * "sp"   — sequence/context parallelism (ring attention axis)
  * "pp"   — pipeline stages
  * "ep"   — expert parallelism for MoE

The reference has no analog (its TP/PP/SP rows are empty, SURVEY.md §2.4);
this module is the TPU-native replacement for what DeepSpeed/Megatron do in
the CUDA world.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

MESH_AXES = ("dp", "fsdp", "tp", "sp", "pp", "ep")


@dataclass(frozen=True)
class MeshConfig:
    """Logical mesh shape. Axes of size 1 are kept (GSPMD treats them as
    no-ops) so sharding rules never need to special-case missing axes."""

    dp: int = 1
    fsdp: int = 1
    tp: int = 1
    sp: int = 1
    pp: int = 1
    ep: int = 1

    @property
    def num_devices(self) -> int:
        return self.dp * self.fsdp * self.tp * self.sp * self.pp * self.ep

    def axis_sizes(self) -> Dict[str, int]:
        return {a: getattr(self, a) for a in MESH_AXES}

    @staticmethod
    def for_devices(n: int, tp: int = 1, sp: int = 1, pp: int = 1, ep: int = 1,
                    pure_dp: int = 1) -> "MeshConfig":
        """FSDP-first factorization: whatever is not spent on tp/sp/pp/ep/dp
        becomes the fsdp axis (the usual TPU default)."""
        rest = n // (tp * sp * pp * ep * pure_dp)
        if rest * tp * sp * pp * ep * pure_dp != n:
            raise ValueError(
                f"cannot factor {n} devices into dp={pure_dp} tp={tp} sp={sp} "
                f"pp={pp} ep={ep}"
            )
        return MeshConfig(dp=pure_dp, fsdp=rest, tp=tp, sp=sp, pp=pp, ep=ep)


def build_mesh(config: MeshConfig, devices: Optional[Sequence] = None) -> Mesh:
    """Build a jax.sharding.Mesh with the canonical axis order.

    Axis order puts "tp" and "sp" innermost so they map to the
    fastest/nearest ICI links on real TPU topologies (tensor-parallel
    collectives are the most latency-sensitive), and "dp"/"pp" outermost
    (they tolerate DCN).
    """
    if devices is None:
        devices = jax.devices()
    n = config.num_devices
    if len(devices) < n:
        raise ValueError(f"need {n} devices, have {len(devices)}")
    arr = np.array(devices[:n]).reshape(
        config.dp, config.pp, config.ep, config.fsdp, config.sp, config.tp
    )
    # Mesh axis names must match the reshape order above.
    return Mesh(arr, axis_names=("dp", "pp", "ep", "fsdp", "sp", "tp"))


# ---------------------------------------------------------------------------
# Logical axis rules (flax-style rules table, but self-contained)
# ---------------------------------------------------------------------------

# Logical activation/parameter axis -> mesh axes.
DEFAULT_RULES: Dict[str, Optional[Tuple[str, ...]]] = {
    # activations
    "batch": ("dp", "fsdp"),
    "seq": ("sp",),
    "act_embed": None,
    "act_heads": ("tp",),
    "act_mlp": ("tp",),
    # params
    "embed": ("fsdp",),      # ZeRO-3: shard the non-tp dim over fsdp
    "mlp": ("tp",),
    "heads": ("tp",),
    "kv": None,
    "qkv_embed": ("fsdp",),
    "vocab": ("tp",),
    "expert": ("ep",),
    "stage": ("pp",),
    "norm": None,
}


def logical_to_physical(logical_axes: Sequence[Optional[str]],
                        rules: Optional[Dict] = None) -> P:
    """Map a tuple of logical axis names to a PartitionSpec."""
    rules = rules or DEFAULT_RULES
    spec = []
    used: set = set()
    for name in logical_axes:
        if name is None:
            spec.append(None)
            continue
        axes = rules.get(name)
        if axes is None:
            spec.append(None)
            continue
        axes = tuple(a for a in axes if a not in used)
        used.update(axes)
        if not axes:
            spec.append(None)
        elif len(axes) == 1:
            spec.append(axes[0])
        else:
            spec.append(axes)
    return P(*spec)


def logical_shardings(logical_axes_tree, mesh: Mesh, rules=None):
    """A NamedSharding on `mesh` for every leaf of a logical axes tree
    (tuples of logical names, or None for replicated): what `shard_params`
    places by, and what `jax.jit(init, out_shardings=...)` takes to build
    parameters where they live."""
    def to_sharding(axes):
        spec = P() if axes is None else logical_to_physical(axes, rules)
        return NamedSharding(mesh, spec)

    return jax.tree.map(to_sharding, logical_axes_tree,
                        is_leaf=lambda x: x is None or isinstance(x, tuple))


def shard_params(params, logical_axes_tree, mesh: Mesh, rules=None):
    """Device-put a parameter pytree according to its logical axes tree.

    `logical_axes_tree` mirrors `params` with tuples of logical names (or
    None for replicated). This is the explicit analog of flax's
    `nn.with_partitioning` + `logical_to_mesh`.
    """
    return jax.device_put(
        params, logical_shardings(logical_axes_tree, mesh, rules))


def with_sharding_constraint(x, logical_axes, mesh: Optional[Mesh] = None,
                             rules=None):
    """Annotate an intermediate activation inside jit.

    Uses the ambient mesh when available (inside `jax.sharding.use_mesh` or
    shard_map); falls back to unconstrained outside.
    """
    spec = logical_to_physical(logical_axes, rules)
    try:
        return jax.lax.with_sharding_constraint(x, spec) if mesh is None else (
            jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
        )
    except (ValueError, RuntimeError):
        return x
