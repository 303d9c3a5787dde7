"""TPU accelerator manager: detection, pod topology, gang resources.

Rebuilt from the reference's TPUAcceleratorManager
(python/ray/_private/accelerators/tpu.py:75):

  * chip count via GKE env vars or /dev/vfio* & /dev/accel* globs (tpu.py:101)
  * pod type from GCE metadata (tpu.py:199) / env
  * TPU_VISIBLE_CHIPS isolation (ray_constants.py:414, set at tpu.py:158),
    with the all-chips passthrough: when a task takes every chip on the
    host, the env var is NOT set so libtpu owns the whole host — here that
    is first-class ("whole-host lease") because JAX SPMD wants exactly one
    process per host.
  * pod gang scheduling (tpu.py:335 get_current_node_additional_resources):
    every host in a pod advertises `{pod_name}: 1`; worker 0 additionally
    advertises `TPU-{pod_type}-head: 1`. A job targets the head resource,
    then fans out one whole-host task per worker via the pod-name resource.
  * one process per chip: libtpu gives a chip to the first process that
    opens it, and a second one fails or hangs. The raylet spawns every
    worker with JAX held to the CPU (`hide_chips`), hands chip indices out
    with a `TPU` grant (`ChipPool`), and the granted worker turns to its
    chips before its first backend initialisation (`take_chips`).

Nothing here initialises a JAX backend: detection reads device files and
the environment only, because the process that detects is the driver's.
"""

from __future__ import annotations

import glob
import math
import os
import sys
from typing import Dict, List, Optional

from ray_tpu._private.accelerators.accelerator import AcceleratorManager

TPU_RESOURCE_NAME = "TPU"
TPU_VISIBLE_CHIPS_ENV = "TPU_VISIBLE_CHIPS"
# GKE injects these (reference tpu.py:34-44).
GKE_TPU_ACCELERATOR_ENV = "TPU_ACCELERATOR_TYPE"
GKE_TPU_WORKER_ID_ENV = "TPU_WORKER_ID"
GKE_TPU_NAME_ENV = "TPU_NAME"
GKE_TPU_WORKER_HOSTNAMES_ENV = "TPU_WORKER_HOSTNAMES"
# GCE metadata paths would be queried on real TPU VMs (tpu.py:199); in this
# build metadata access is injected via env for testability.
TPU_CHIPS_PER_HOST_BOUNDS = {"v2": 4, "v3": 4, "v4": 4, "v5p": 4, "v5litepod": 8, "v6e": 8}

_VALID_CHIP_COUNTS = (1, 2, 4, 8)
# libtpu lays a process's chips out on these bounds; a process confined to
# one or two chips of a larger host needs them to match what it can see
# (reference tpu.py TPU_CHIPS_PER_HOST_BOUNDS_*_CHIP_CONFIG).
TPU_CHIPS_PER_HOST_BOUNDS_ENV = "TPU_CHIPS_PER_HOST_BOUNDS"
TPU_HOST_BOUNDS_ENV = "TPU_HOST_BOUNDS"
_SUBSET_BOUNDS = {1: "1,1,1", 2: "1,2,1"}
JAX_PLATFORMS_ENV = "JAX_PLATFORMS"
# The platform list the node itself runs under, kept in a worker's
# environment for the day it is granted chips.
NODE_JAX_PLATFORMS_ENV = "RT_NODE_JAX_PLATFORMS"


class TPUAcceleratorManager(AcceleratorManager):
    @staticmethod
    def get_resource_name() -> str:
        return TPU_RESOURCE_NAME

    @staticmethod
    def get_visible_accelerator_ids_env_var() -> str:
        return TPU_VISIBLE_CHIPS_ENV

    @staticmethod
    def get_current_node_num_accelerators() -> int:
        """Chip count: explicit env > device files. One `/dev/vfio/<n>`
        group per chip beside the `/dev/vfio/vfio` control node (v5e, v5p,
        v6e), or one `/dev/accel<n>` per chip (v2-v4)."""
        explicit = os.environ.get("RT_TPU_CHIPS")
        if explicit:
            return int(explicit)
        vfio = [p for p in glob.glob("/dev/vfio/*")
                if os.path.basename(p) != "vfio"]
        return len(vfio) or len(glob.glob("/dev/accel*"))

    @staticmethod
    def get_current_node_accelerator_type() -> Optional[str]:
        accel_type = os.environ.get(GKE_TPU_ACCELERATOR_ENV) or os.environ.get(
            "RT_TPU_ACCELERATOR_TYPE"
        )
        if accel_type:
            # "v5litepod-16" -> "TPU-V5LITEPOD"
            generation = accel_type.split("-")[0]
            return f"TPU-{generation.upper()}"
        return None

    @staticmethod
    def get_current_node_tpu_pod_type() -> Optional[str]:
        """e.g. "v5litepod-16" (reference tpu.py:199)."""
        return os.environ.get(GKE_TPU_ACCELERATOR_ENV) or os.environ.get(
            "RT_TPU_ACCELERATOR_TYPE"
        )

    @staticmethod
    def get_current_node_tpu_name() -> Optional[str]:
        """Unique pod/slice name (reference tpu.py:232)."""
        return os.environ.get(GKE_TPU_NAME_ENV) or os.environ.get("RT_TPU_NAME")

    @staticmethod
    def get_current_node_tpu_worker_id() -> Optional[int]:
        """This host's index within the pod slice (reference tpu.py:258)."""
        wid = os.environ.get(GKE_TPU_WORKER_ID_ENV) or os.environ.get(
            "RT_TPU_WORKER_ID"
        )
        return int(wid) if wid is not None else None

    @staticmethod
    def get_num_workers_in_current_tpu_pod() -> Optional[int]:
        """Hosts in this pod slice (reference tpu.py:275)."""
        hostnames = os.environ.get(GKE_TPU_WORKER_HOSTNAMES_ENV) or os.environ.get(
            "RT_TPU_WORKER_HOSTNAMES"
        )
        if hostnames:
            return len(hostnames.split(","))
        explicit = os.environ.get("RT_TPU_POD_WORKER_COUNT")
        if explicit:
            return int(explicit)
        return None

    @staticmethod
    def get_current_node_additional_resources() -> Dict[str, float]:
        """Pod gang resources (reference tpu.py:335).

        Every pod host advertises `{tpu_name}: 1`; worker 0 additionally
        advertises `TPU-{pod_type}-head: 1`.
        """
        out: Dict[str, float] = {}
        name = TPUAcceleratorManager.get_current_node_tpu_name()
        pod_type = TPUAcceleratorManager.get_current_node_tpu_pod_type()
        worker_id = TPUAcceleratorManager.get_current_node_tpu_worker_id()
        if name:
            out[name] = 1.0
        if pod_type is not None and worker_id == 0:
            out[f"TPU-{pod_type}-head"] = 1.0
        return out

    @staticmethod
    def validate_resource_request_quantity(quantity: float):
        if quantity not in _VALID_CHIP_COUNTS:
            return (
                False,
                f"TPU request must be one of {_VALID_CHIP_COUNTS} chips "
                f"(got {quantity}); multi-host slices use pod gang resources",
            )
        return True, None

    @staticmethod
    def get_current_process_visible_accelerator_ids() -> Optional[List[str]]:
        raw = os.environ.get(TPU_VISIBLE_CHIPS_ENV)
        if raw is None:
            return None
        if raw == "":
            return []
        return raw.split(",")

    @staticmethod
    def set_current_process_visible_accelerator_ids(
        ids: List[str], node_total: Optional[int] = None
    ) -> None:
        """Confine this process to specific chips.

        The all-chips passthrough (reference tpu.py:158): when the process
        takes every chip on the host we *unset* the variable so libtpu owns
        the full host — the whole-host lease JAX SPMD needs. `node_total`
        is the count the lease came with (a node started with an explicit
        `num_tpus` need not match what this process would detect).
        """
        total = (node_total if node_total is not None
                 else TPUAcceleratorManager.get_current_node_num_accelerators())
        if total and len(ids) >= total:
            os.environ.pop(TPU_VISIBLE_CHIPS_ENV, None)
            return
        os.environ[TPU_VISIBLE_CHIPS_ENV] = ",".join(str(i) for i in ids)
        bounds = _SUBSET_BOUNDS.get(len(ids))
        if bounds:
            os.environ[TPU_CHIPS_PER_HOST_BOUNDS_ENV] = bounds
            os.environ[TPU_HOST_BOUNDS_ENV] = "1,1,1"


def chips_wanted(resources: Optional[Dict[str, float]]) -> int:
    """Whole chips a resource request holds: a chip is one process's, so a
    fraction takes the chip."""
    return math.ceil((resources or {}).get(TPU_RESOURCE_NAME, 0))


class ChipPool:
    """The chip indices of one node. The raylet takes them with a `TPU`
    grant and gives them back when the worker that held them is gone."""

    def __init__(self, total: int):
        self.total = total
        self._free = list(range(total))

    @property
    def free(self) -> int:
        return len(self._free)

    def take(self, n: int) -> List[int]:
        if n > len(self._free):
            raise ValueError(
                f"{n} chip(s) wanted, {len(self._free)} of {self.total} free"
            )
        taken, self._free = self._free[:n], self._free[n:]
        return taken

    def give_back(self, chips: List[int]) -> None:
        self._free = sorted(set(self._free) | set(chips))

    def lease(self, chips: List[int]) -> Dict[str, object]:
        """What the granted worker is sent (see take_chips)."""
        return {"chips": chips, "node_chips": self.total}


def hide_chips(env: Dict[str, str]) -> None:
    """Spawn environment of a worker: JAX held to the CPU, so that a
    worker that was granted no chip (a serve proxy importing the engine,
    a data task, an env runner) cannot open one first."""
    env[NODE_JAX_PLATFORMS_ENV] = env.get(JAX_PLATFORMS_ENV, "")
    env[JAX_PLATFORMS_ENV] = "cpu"


def take_chips(lease: Dict[str, object]) -> None:
    """In the worker a `TPU` grant reached: turn this process to its
    chips. Runs before the task or the actor's constructor, hence before
    user code can initialise a backend; a process that already has one
    cannot switch, which is why the raylet grants chips to fresh workers
    only."""
    platforms = os.environ.get(NODE_JAX_PLATFORMS_ENV, "")
    jax = sys.modules.get("jax")
    if jax is not None:
        from jax._src import xla_bridge

        if xla_bridge.backends_are_initialized():
            raise RuntimeError(
                "this worker initialised a JAX backend before it was "
                f"granted chips {lease['chips']}; it cannot take them"
            )
        jax.config.update("jax_platforms", platforms or None)
    if platforms:
        os.environ[JAX_PLATFORMS_ENV] = platforms
    else:
        os.environ.pop(JAX_PLATFORMS_ENV, None)
    TPUAcceleratorManager.set_current_process_visible_accelerator_ids(
        [str(c) for c in lease["chips"]], lease["node_chips"]
    )
    if platforms != "cpu":  # a node held to the CPU has no chip to compile for
        from ray_tpu.util.compile_cache import place_compile_cache

        place_compile_cache()


def get_current_pod_name() -> Optional[str]:
    """Public helper (reference python/ray/util/accelerators/tpu.py:7)."""
    return TPUAcceleratorManager.get_current_node_tpu_name()


def get_current_pod_worker_count() -> Optional[int]:
    """Public helper (reference python/ray/util/accelerators/tpu.py:19)."""
    return TPUAcceleratorManager.get_num_workers_in_current_tpu_pod()
