"""Run a kernel on each device's block of its operands.

A Pallas TPU kernel lowers to a custom call that GSPMD cannot partition
("Mosaic kernels cannot be automatically partitioned. Please wrap the
call in a shard_map"), so under a sharded `jit` every kernel call goes
through here with the layout its caller knows.
"""

from __future__ import annotations

import jax


def per_shard(fn, mesh, in_specs, out_specs):
    """`fn` over each shard of `mesh`. With no mesh, one device, or
    inside a region that is already per-device (the pipeline schedule's
    own shard_map), `fn` itself."""
    if (mesh is None or mesh.size == 1
            or jax.sharding.get_abstract_mesh().manual_axes):
        return fn
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
