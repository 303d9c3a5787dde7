"""Run a kernel on each device's block of its operands.

A Pallas TPU kernel lowers to a custom call that GSPMD cannot partition
("Mosaic kernels cannot be automatically partitioned. Please wrap the
call in a shard_map"), so under a sharded `jit` every kernel call goes
through here with the layout its caller knows.
"""

from __future__ import annotations

import jax


def is_per_device(mesh) -> bool:
    """Whether code here runs on one device's block already: no mesh, one
    device, or inside a region that is per-device (the pipeline schedule's
    own shard_map)."""
    return (mesh is None or mesh.size == 1
            or bool(jax.sharding.get_abstract_mesh().manual_axes))


def per_shard(fn, mesh, in_specs, out_specs):
    """`fn` over each shard of `mesh`; where `is_per_device`, `fn` itself.

    A caller that differentiates through the region owes it specs that
    name every mesh axis wider than one. The region is made with the
    replication check off, so its transpose is the conservative one: an
    output's cotangent is divided by the devices along each axis its spec
    leaves out, and an input's is summed over each axis its spec leaves
    out. An operand replicated over "tp" thus costs a pass and an
    all-reduce of its own size that compute the identity. Where a spec has
    to leave an axis out (the norm's activations), give the whole-array
    function its own `jax.custom_vjp` whose rule runs a second region,
    with the sums it needs written inside (ops.rmsnorm)."""
    if is_per_device(mesh):
        return fn
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
