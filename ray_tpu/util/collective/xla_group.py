"""Eager collectives over the devices attached to this process, via XLA.

This is the TPU-native replacement for the reference's NCCLGroup
(python/ray/util/collective/collective_group/nccl_collective_group.py:127):
on a TPU host one process owns all local chips, so "eager" collectives are
tiny jit-compiled programs over a persistent local mesh — the compiled
graph runs the reduction on ICI. (SURVEY.md §7 hard parts: "the eager
backend must JIT tiny collective programs and keep a persistent mesh
context per group".)

In tests, the same code runs over the 8 virtual CPU devices.
"""

from __future__ import annotations

import functools
from typing import List, Optional

from ray_tpu.util.collective.types import ReduceOp

_REDUCERS = {
    ReduceOp.SUM: "sum",
    ReduceOp.PRODUCT: "prod",
    ReduceOp.MIN: "min",
    ReduceOp.MAX: "max",
}


class XlaLocalGroup:
    """Collectives across this process's local devices.

    The "ranks" of this group are local devices, not processes: values are
    lists with one array per device (matching the reference's multi-GPU
    collective entry points, e.g. allreduce_multigpu).
    """

    def __init__(self, num_devices: Optional[int] = None):
        import jax

        devices = jax.local_devices()
        if num_devices is not None:
            devices = devices[:num_devices]
        self.devices = devices
        self.world_size = len(devices)
        import numpy as np
        from jax.sharding import Mesh

        self.mesh = Mesh(np.array(self.devices), axis_names=("rank",))
        # Same shape DcnGroup records, so the collective metrics/observer
        # stream covers both tiers. "bytes" is the LOGICAL per-device
        # message size — ICI wire bytes are XLA's business, not ours.
        self.last_op_info: dict = {}

    def _record_op(self, op_name: str, dtype, nbytes: int) -> None:
        self.last_op_info = {
            "op": op_name,
            "algo": "psum",
            "tier": "ici",
            "bytes": int(nbytes),
            "dtype": str(dtype),
            "quant": None,
        }

    @functools.lru_cache(maxsize=32)
    def _allreduce_fn(self, op: ReduceOp):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from jax import shard_map

        reducer = {
            ReduceOp.SUM: jax.lax.psum,
            ReduceOp.MAX: jax.lax.pmax,
            ReduceOp.MIN: jax.lax.pmin,
        }.get(op)

        if reducer is None:  # product: log-space trick is lossy; use prod
            def reducer(x, axis_name):
                return jax.lax.all_gather(x, axis_name).prod(axis=0)

        @jax.jit
        def fn(stacked):
            # stacked: [world, ...] sharded over ranks on dim 0.
            def body(x):
                return reducer(x[0], "rank")[None]

            return shard_map(
                body,
                mesh=self.mesh,
                in_specs=P("rank"),
                out_specs=P("rank"),
            )(stacked)

        return fn

    def _stack(self, tensors):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        stacked = jnp.stack([jnp.asarray(t) for t in tensors])
        return jax.device_put(
            stacked, NamedSharding(self.mesh, P("rank"))
        )

    def allreduce(self, tensors: List, op: ReduceOp = ReduceOp.SUM) -> List:
        import numpy as np

        if len(tensors) != self.world_size:
            raise ValueError(
                f"need one tensor per device ({self.world_size}), got {len(tensors)}"
            )
        out = self._allreduce_fn(op)(self._stack(tensors))
        arr0 = np.asarray(tensors[0])
        self._record_op("allreduce", arr0.dtype, arr0.nbytes)
        return [out[i] for i in range(self.world_size)]

    def allgather(self, tensors: List) -> List[List]:
        import jax
        import numpy as np

        stacked = self._stack(tensors)
        gathered = [stacked[i] for i in range(self.world_size)]
        arr0 = np.asarray(tensors[0])
        self._record_op("allgather", arr0.dtype, arr0.nbytes)
        return [list(gathered) for _ in range(self.world_size)]

    def reducescatter(self, tensors: List, op: ReduceOp = ReduceOp.SUM) -> List:
        import numpy as np

        reduced = self.allreduce(tensors, op)
        outs = []
        for i in range(self.world_size):
            chunks = np.array_split(np.asarray(reduced[i]).reshape(-1), self.world_size)
            outs.append(chunks[i])
        arr0 = np.asarray(tensors[0])
        self._record_op("reducescatter", arr0.dtype, arr0.nbytes)
        return outs

    def broadcast(self, tensors: List, root_rank: int = 0) -> List:
        import jax.numpy as jnp
        import numpy as np

        src = jnp.asarray(tensors[root_rank])
        arr = np.asarray(tensors[root_rank])
        self._record_op("broadcast", arr.dtype, arr.nbytes)
        return [src for _ in range(self.world_size)]

    def barrier(self):
        import jax.numpy as jnp
        import numpy as np

        self.allreduce([jnp.zeros(1) for _ in range(self.world_size)])
        self._record_op("barrier", np.dtype(np.float32), 0)

    def destroy(self):
        pass
