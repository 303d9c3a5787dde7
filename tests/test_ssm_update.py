"""`ops.ssm_update`, the one-pass state update of a Mamba-2 layer over the
recurrent pool, on the CPU: the kernel in Pallas's interpreter against
`mamba2.ssd_step`, its plain form, on drawn float32 inputs.

Both sides compute in float32 and differ by the order of a sum of 128
products at most (1e-7 of `y`'s size was read); the limit is 1e-5 of the
largest value, a hundred times that and a thousand times under what one
operand rounded to bfloat16 would give. What must not move is held bit for
bit: the row of a slot whose `dt` is 0, and every layer but the indexed
one."""

import functools
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import configs, init_params, mamba2
from ray_tpu.ops import ssm_update as op

F32 = jnp.float32
LAYERS, SLOTS, HEADS, P, N = 3, 5, 4, 64, 128
IDLE = (1, 3)                       # slots that keep their state
TOLERANCE = 1e-5


def drawn(groups, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    dt = jax.nn.softplus(jax.random.normal(ks[0], (SLOTS, HEADS), F32))
    return dict(
        x=jax.random.normal(ks[1], (SLOTS, HEADS, P), F32),
        dt=dt.at[jnp.asarray(IDLE)].set(0.0),
        a=-jnp.exp(jax.random.normal(ks[2], (HEADS,), F32)),
        b=jax.random.normal(ks[3], (SLOTS, groups, N), F32),
        c=jax.random.normal(ks[4], (SLOTS, groups, N), F32),
        pool=jax.random.normal(ks[5], (LAYERS, SLOTS, HEADS, P, N), F32))


def close(got, want):
    got, want = np.asarray(got), np.asarray(want)
    return np.abs(got - want).max() <= TOLERANCE * np.abs(want).max()


@pytest.mark.parametrize("groups", [1, 2])
@pytest.mark.parametrize("layer", [0, LAYERS // 2, LAYERS - 1],
                         ids=["first", "middle", "last"])
def test_the_kernel_is_the_one_token_form(layer, groups):
    """The kernel on layer `layer` of a pool of several against `ssd_step`
    on that layer's slice: `y` and the layer's rows to float32 rounding, a
    slot with `dt` 0 its row bit for bit, every other layer bit for bit."""
    ins = drawn(groups, seed=layer + 10 * groups)
    pool = ins.pop("pool")
    assert op.kernel_takes(pool, groups)
    want_y, want_state = mamba2.ssd_step(**ins, state=pool[layer])
    y, new = jax.jit(functools.partial(op.ssm_update, interpret=True))(
        ins["x"], ins["dt"], ins["a"], ins["b"], ins["c"], pool,
        jnp.int32(layer))
    assert y.shape == want_y.shape and new.shape == pool.shape
    assert close(y, want_y) and close(new[layer], want_state)
    idle = np.asarray(IDLE)
    np.testing.assert_array_equal(np.asarray(new[layer])[idle],
                                  np.asarray(pool[layer])[idle])
    others = [i for i in range(LAYERS) if i != layer]
    np.testing.assert_array_equal(np.asarray(new)[others],
                                  np.asarray(pool)[others])
    # A live slot's row did move.
    assert not np.array_equal(np.asarray(new[layer, 0]),
                              np.asarray(pool[layer, 0]))


def test_the_plain_form_runs_where_the_kernel_does_not():
    """Off the TPU, and for a pool the kernel does not take (a state
    narrower than a lane tile), `ssm_update` is `ssd_step` on the layer
    sliced out and set back, bit for bit."""
    ins = drawn(1)
    pool = ins.pop("pool")
    for p in (pool, pool[..., :16]):
        sub = {**ins, "b": ins["b"][..., :p.shape[-1]],
               "c": ins["c"][..., :p.shape[-1]]}
        want_y, want_state = mamba2.ssd_step(**sub, state=p[1])
        y, new = op.ssm_update(sub["x"], sub["dt"], sub["a"], sub["b"],
                               sub["c"], p, 1)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(want_y))
        np.testing.assert_array_equal(np.asarray(new[1]),
                                      np.asarray(want_state))
    assert not op.kernel_takes(pool[..., :16], 1)
    assert not op.kernel_takes(pool.astype(jnp.bfloat16), 1)
    assert not op.kernel_takes(pool, 3)


@pytest.mark.parametrize("form", ["plain", "kernel"])
def test_mixer_with_the_pool_and_a_layer_is_mixer_with_the_slice(
        form, monkeypatch):
    """`mamba2.mixer` given the whole pool and a layer's index against
    `mixer` given that layer's slice: the same output, the same state in
    that layer to float32 rounding (one side is jitted), the other layers
    and the idle slots' rows bit for bit; through the plain form (what the
    CPU runs) and through the kernel."""
    if form == "kernel":
        monkeypatch.setattr(mamba2, "ssm_update", functools.partial(
            op.ssm_update, interpret=True))
    cfg = replace(configs.get_config("tiny_granite_h"), mamba_n_heads=HEADS,
                  mamba_d_head=P, mamba_d_state=N, mamba_n_groups=2,
                  mamba_expand=HEADS * P // 64)
    stack = init_params(jax.random.PRNGKey(3), cfg)["layers"]["ssm"]
    lp = jax.tree.map(lambda a: a[1], stack)
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    rec = mamba2.init_state(cfg, LAYERS, SLOTS)
    pool = jax.random.normal(ks[0], rec["state"].shape, F32)
    conv = jax.random.normal(ks[1], rec["conv"].shape[1:], cfg.dtype)
    h = jax.random.normal(ks[2], (SLOTS, 1, cfg.d_model), cfg.dtype)
    n_valid = jnp.asarray([1, 0, 1, 0, 1], jnp.int32)
    layer = 2
    want_out, want_state, want_conv = mamba2.mixer(
        h, lp, cfg, pool[layer], conv, n_valid)
    out, new, new_conv = jax.jit(
        lambda *a: mamba2.mixer(*a[:2], cfg, *a[2:5], layer=a[5]))(
            h, lp, pool, conv, n_valid, jnp.int32(layer))
    assert new.shape == pool.shape
    np.testing.assert_array_equal(np.asarray(new_conv), np.asarray(want_conv))
    np.testing.assert_array_equal(np.asarray(new[:layer]),
                                  np.asarray(pool[:layer]))
    idle = np.flatnonzero(np.asarray(n_valid) == 0)
    np.testing.assert_array_equal(np.asarray(new[layer])[idle],
                                  np.asarray(pool[layer])[idle])
    assert close(out, want_out) and close(new[layer], want_state)
