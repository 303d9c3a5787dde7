"""CPU rehearsal of chip_smoke.py's control flow at toy widths (the first
two rehearsals of the `on-chip-measurement` guide, section 2): the same
entry points, checks and pickling as the chip run, in a process of its
own because the driver side must end with no JAX backend. It says
nothing about the chip; `python chip_smoke.py` through the chip tool
does."""

import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DRIVER = """
import sys

import cloudpickle

import chip_smoke

# By value, as when the script runs as __main__.
cloudpickle.register_pickle_by_value(chip_smoke)
tiny = dict(platform="cpu", slots=2, max_len=64, prompt_lens=(3, 20, 40),
            new_tokens=4, train_layers=2, batch=4, seq=32, ce_chunk=8,
            lr=1e-2)
if sys.argv[1] == "1":
    device = chip_smoke.run(
        chip_smoke.Plan(model="tiny_qwen", num_tpus=1, **tiny), 1)
else:
    # What exists only across chips, on four virtual devices. The two
    # one-chip actors are left to the chip: every CPU worker sees all of
    # the virtual devices whatever TPU_VISIBLE_CHIPS says.
    chip_smoke.start_runtime(chip_smoke.Plan(num_tpus=4), 4)
    try:
        device = chip_smoke.mesh_train(
            chip_smoke.Plan(model="tiny_qwen", num_tpus=4, **tiny))
        # tp=4 must divide the KV heads: `tiny` has four.
        assert device == chip_smoke.tp_serve(
            chip_smoke.Plan(model="tiny", num_tpus=4, **tiny))
    finally:
        chip_smoke.rt.shutdown()
    chip_smoke.driver_stayed_off_jax()
print("REHEARSED", device)
"""


@pytest.mark.parametrize("chips", [1, 4])
def test_chip_smoke_rehearsal_on_virtual_devices(chips):
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": f"--xla_force_host_platform_device_count={chips}",
        "PYTHONPATH": REPO,
        "RT_TPU_CHIPS": "0",
    })
    done = subprocess.run(
        [sys.executable, "-c", DRIVER, str(chips)], env=env, cwd=REPO,
        capture_output=True, text=True, timeout=600,
    )
    assert done.returncode == 0, done.stdout[-3000:] + done.stderr[-3000:]
    assert (f"REHEARSED {{'platform': 'cpu', 'kind': 'cpu', 'count': "
            f"{chips}}}") in done.stdout
    assert "compiles after warm-up: 0" in done.stdout
    assert "driver process initialised a JAX backend: False" in done.stdout


def test_chip_smoke_fails_where_the_node_shows_no_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu", RT_TPU_CHIPS="0")
    done = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")], env=env,
        cwd=REPO, capture_output=True, text=True, timeout=300,
    )
    assert done.returncode != 0
    assert '"ok"' not in done.stdout
    assert "nothing was run" in done.stderr
