"""One process for each chip, the compile cache and the peak table: the
pieces, without worker processes. tests/test_chip_smoke.py (slow tier)
rehearses them together; chip_smoke.py proves them on the chip."""

import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

from ray_tpu._private.accelerators import tpu
from ray_tpu._private.accelerators.tpu import (
    ChipPool,
    chips_wanted,
    hide_chips,
    take_chips,
)
from ray_tpu.util import compile_cache, device_peaks

CHIP_ENV = ("JAX_PLATFORMS", tpu.NODE_JAX_PLATFORMS_ENV, "TPU_VISIBLE_CHIPS",
            "TPU_CHIPS_PER_HOST_BOUNDS", "TPU_HOST_BOUNDS")


def _forget_chip_env(monkeypatch):
    """Unset every variable a lease may touch, registered with
    monkeypatch so that what take_chips writes is undone too."""
    for name in CHIP_ENV:
        monkeypatch.setenv(name, "registered")
        monkeypatch.delenv(name)


@pytest.fixture
def worker_env(monkeypatch):
    """This process posing as a freshly spawned worker of a node whose
    own environment says JAX_PLATFORMS=tpu,cpu: every variable the lease
    may touch is restored afterwards, JAX looks not yet imported, and the
    compile cache is placed from outside."""
    spawn_env = {"JAX_PLATFORMS": "tpu,cpu", "TPU_CHIPS_PER_HOST_BOUNDS": "2,2,1"}
    hide_chips(spawn_env)
    _forget_chip_env(monkeypatch)
    for name, value in spawn_env.items():
        monkeypatch.setenv(name, value)
    monkeypatch.setenv(compile_cache.CACHE_DIR_ENV, "/placed/from/outside")
    monkeypatch.delitem(sys.modules, "jax")
    return spawn_env


def test_spawn_env_holds_a_worker_to_the_cpu():
    env = {"JAX_PLATFORMS": "tpu,cpu", "PATH": "/bin"}
    hide_chips(env)
    # Exactly this: the platform fixed, the node's own list kept for the
    # day of a lease, and no other variable added, moved or stripped.
    assert env == {"JAX_PLATFORMS": "cpu", "PATH": "/bin",
                   tpu.NODE_JAX_PLATFORMS_ENV: "tpu,cpu"}
    # A node with no platform list of its own: JAX's default comes back.
    bare = {}
    hide_chips(bare)
    assert bare == {"JAX_PLATFORMS": "cpu", tpu.NODE_JAX_PLATFORMS_ENV: ""}


def test_worker_without_a_lease_stays_on_the_cpu(worker_env):
    assert os.environ["JAX_PLATFORMS"] == "cpu"
    assert "TPU_VISIBLE_CHIPS" not in os.environ


def test_lease_turns_the_worker_to_its_chips(worker_env):
    take_chips(ChipPool(4).lease([2]))
    assert os.environ["JAX_PLATFORMS"] == "tpu,cpu"
    assert os.environ["TPU_VISIBLE_CHIPS"] == "2"
    # libtpu's layout must match the one chip the process can see.
    assert os.environ["TPU_CHIPS_PER_HOST_BOUNDS"] == "1,1,1"
    assert os.environ["TPU_HOST_BOUNDS"] == "1,1,1"


def test_whole_host_lease_leaves_visible_chips_unset(worker_env):
    os.environ["TPU_VISIBLE_CHIPS"] = "0"
    take_chips(ChipPool(4).lease([0, 1, 2, 3]))
    assert "TPU_VISIBLE_CHIPS" not in os.environ
    assert os.environ["TPU_CHIPS_PER_HOST_BOUNDS"] == "2,2,1"  # the node's
    assert os.environ["JAX_PLATFORMS"] == "tpu,cpu"


def test_node_total_comes_with_the_lease_not_from_detection(worker_env,
                                                            monkeypatch):
    """rt.init(num_tpus=1) on a host whose files show four chips: the one
    chip is the whole node."""
    monkeypatch.setenv("RT_TPU_CHIPS", "4")
    take_chips(ChipPool(1).lease([0]))
    assert "TPU_VISIBLE_CHIPS" not in os.environ


def test_no_platform_list_on_the_node_restores_jax_default(monkeypatch):
    _forget_chip_env(monkeypatch)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv(tpu.NODE_JAX_PLATFORMS_ENV, "")
    monkeypatch.setenv(compile_cache.CACHE_DIR_ENV, "/placed/from/outside")
    monkeypatch.delitem(sys.modules, "jax")
    take_chips(ChipPool(1).lease([0]))
    assert "JAX_PLATFORMS" not in os.environ


def test_a_worker_that_touched_jax_cannot_take_chips(monkeypatch):
    import jax

    jax.devices()  # this process has a backend by now
    _forget_chip_env(monkeypatch)
    monkeypatch.setenv(tpu.NODE_JAX_PLATFORMS_ENV, "tpu,cpu")
    with pytest.raises(RuntimeError, match="before it was granted"):
        take_chips(ChipPool(4).lease([1]))
    assert os.environ.get("TPU_VISIBLE_CHIPS") is None


def test_chip_indices_are_handed_out_and_returned():
    pool = ChipPool(4)
    first, second = pool.take(1), pool.take(2)
    assert (first, second, pool.free) == ([0], [1, 2], 1)
    with pytest.raises(ValueError, match="2 chip"):
        pool.take(2)
    pool.give_back(first)
    assert pool.take(2) == [0, 3]
    pool.give_back([1, 2])
    pool.give_back([1, 2])  # a second notice of one death changes nothing
    assert pool.free == 2 and pool.lease([1]) == {"chips": [1], "node_chips": 4}


@pytest.mark.parametrize("resources,chips", [
    (None, 0), ({"CPU": 1}, 0), ({"TPU": 1}, 1), ({"TPU": 4.0}, 4),
    ({"TPU": 0.5}, 1),  # a chip is one process's: a fraction takes it
    ({"TPU-v5litepod-16-head": 1}, 0),  # a gang resource, not chips
])
def test_chips_wanted(resources, chips):
    assert chips_wanted(resources) == chips


def test_only_a_fresh_worker_is_offered_for_chips():
    from ray_tpu._private.raylet import Raylet, WorkerHandle

    used, fresh = WorkerHandle(None, b"u"), WorkerHandle(None, b"f")
    used.fresh = False
    used.conn = fresh.conn = object()
    pool = SimpleNamespace(workers={b"u": used, b"f": fresh})
    assert Raylet._idle_worker(pool) is used
    assert Raylet._idle_worker(pool, fresh=True) is fresh
    fresh.idle = False
    assert Raylet._idle_worker(pool, fresh=True) is None


def test_detection_reads_device_files_only(monkeypatch):
    monkeypatch.delenv("RT_TPU_CHIPS", raising=False)
    files = {"/dev/vfio/*": ["/dev/vfio/3", "/dev/vfio/vfio"],
             "/dev/accel*": []}
    monkeypatch.setattr(tpu.glob, "glob", lambda pattern: files[pattern])
    manager = tpu.TPUAcceleratorManager
    assert manager.get_current_node_num_accelerators() == 1  # a v5e slice
    files["/dev/vfio/*"] = [f"/dev/vfio/{i}" for i in range(4)] + ["/dev/vfio/vfio"]
    assert manager.get_current_node_num_accelerators() == 4
    files.update({"/dev/vfio/*": [], "/dev/accel*": ["/dev/accel0", "/dev/accel1"]})
    assert manager.get_current_node_num_accelerators() == 2
    files["/dev/accel*"] = []
    assert manager.get_current_node_num_accelerators() == 0


# -- compile cache ----------------------------------------------------------


def test_cache_helper_does_nothing_where_the_variable_is_set(monkeypatch):
    import jax

    monkeypatch.setenv(compile_cache.CACHE_DIR_ENV, "/placed/from/outside")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.place_compile_cache() == "/placed/from/outside"
    assert jax.config.jax_compilation_cache_dir == before
    assert not os.path.exists("/placed/from/outside")


def test_cache_helper_uses_one_fixed_path_under_the_checkout(monkeypatch):
    import jax

    monkeypatch.delenv(compile_cache.CACHE_DIR_ENV, raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    before = jax.config.jax_compilation_cache_dir
    try:
        first = compile_cache.place_compile_cache()
        assert first == os.path.join(repo, ".cache", "jax")
        assert compile_cache.place_compile_cache() == first
        assert jax.config.jax_compilation_cache_dir == first
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    env = {k: v for k, v in os.environ.items()
           if k != compile_cache.CACHE_DIR_ENV}
    env["PYTHONPATH"] = repo
    other = subprocess.run(
        [sys.executable, "-c",
         "from ray_tpu.util.compile_cache import default_cache_dir;"
         "print(default_cache_dir())"],
        env=env, cwd="/", capture_output=True, text=True, timeout=60,
        check=True,
    ).stdout.strip()
    assert other == first  # another process, another cwd, the same key


# -- peaks -------------------------------------------------------------------


def test_peak_table_is_keyed_by_device_kind():
    def device(platform, kind):
        return SimpleNamespace(platform=platform, device_kind=kind)

    peak = device_peaks.peak_flops_per_s
    assert peak(device("tpu", "TPU v5 lite")) == 197e12  # what a v5e says
    assert peak(device("cpu", "cpu")) is None
    with pytest.raises(ValueError, match="TPU v9"):
        peak(device("tpu", "TPU v9"))
