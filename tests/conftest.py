"""Test fixtures.

Mirrors the reference's python/ray/tests/conftest.py fixture strategy
(ray_start_regular at conftest.py:411, ray_start_cluster at :492) and its
CPU-device collective testing approach (SURVEY.md §4.2): JAX runs on a
virtual 8-device CPU mesh so all sharding/collective code paths execute
without TPU hardware.
"""

import os

# Tests always run on a virtual 8-device CPU mesh: both variables are read
# when JAX is imported, just below, and worker processes inherit them.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("RT_TPU_CHIPS", "0")  # no fake TPU detection in tests

import jax  # noqa: E402,F401

import pytest  # noqa: E402


def pytest_collection_modifyitems(config, items):
    """`sanitizer` tests compile the native store under TSan/UBSan and
    run a multithreaded stress binary — minutes of compiler time that
    the default (and even `slow`) tiers shouldn't pay. They run only
    when explicitly selected: `-m sanitizer` (what `make lint` does)."""
    if "sanitizer" in (config.option.markexpr or ""):
        return
    skip = pytest.mark.skip(reason="opt-in: select with -m sanitizer")
    for item in items:
        if "sanitizer" in item.keywords:
            item.add_marker(skip)


@pytest.fixture
def rt_local():
    import ray_tpu as rt

    rt.init(local_mode=True)
    yield rt
    rt.shutdown()


@pytest.fixture
def rt_start(request):
    """A real single-node runtime: in-process GCS+raylet, subprocess workers."""
    import ray_tpu as rt

    kwargs = getattr(request, "param", {}) or {}
    kwargs.setdefault("num_cpus", 4)
    rt.init(**kwargs)
    yield rt
    rt.shutdown()


@pytest.fixture
def rt_cluster():
    """Multi-raylet cluster harness (reference: cluster_utils.Cluster)."""
    from ray_tpu.cluster_utils import Cluster

    cluster = Cluster()
    yield cluster
    cluster.shutdown()
