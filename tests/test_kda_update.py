"""`ops.kda_update`, the one-pass state update of a delta-rule layer over
the recurrent pool, on the CPU: the kernel in Pallas's interpreter against
`kda.kda_step`, its plain form, on drawn float32 inputs.

Both sides compute in float32 in one order (the kernel's arithmetic is
`kda_step`'s, term for term) and differ by a fused multiply-add at most (0
was read here and on the chip); the limit is 1e-6 of the largest value, a
thousand times under what a state rounded to bfloat16 gives in one token.
What must not move is held bit for bit: the row of a slot whose `g` and
`beta` are 0, and every layer but the indexed one. The serving cell's
`correct` passes a bfloat16 state (PERF.md section 7), so what a sequence's
whole life does to the state is held here too: 300 tokens through the
kernel against `kda_scan`."""

import functools
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import configs, init_params, kda
from ray_tpu.ops import kda_update as op

F32 = jnp.float32
LAYERS, SLOTS = 3, 4
IDLE = (1,)                         # slots that keep their state
TOLERANCE = 1e-6
# heads, K, V: the serving cell's 64 heads of 128 x 128, and a head of
# fewer rows than a tile turns over and two lane tiles of values.
SHAPES = {"solar": (64, 128, 128), "narrow-k-wide-v": (8, 64, 256)}


def drawn(heads, dk, dv, seed=0, slots=SLOTS, layers=LAYERS):
    """q and k normed as the mixer norms them, a log-decay a channel, beta
    up to 2 and a non-zero pool; the `IDLE` slots' g and beta 0."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q, k = (jax.random.normal(key, (slots, heads, dk), F32) for key in ks[:2])
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    g = -0.3 * jnp.exp(jax.random.normal(ks[2], (slots, heads, dk), F32))
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(ks[3], (slots, heads), F32))
    idle = jnp.asarray(IDLE)
    return dict(
        q=q, k=k, v=jax.random.normal(ks[4], (slots, heads, dv), F32),
        g=g.at[idle].set(0.0), beta=beta.at[idle].set(0.0),
        pool=jax.random.normal(ks[5], (layers, slots, heads, dk, dv), F32))


def close(got, want, tolerance=TOLERANCE):
    got, want = np.asarray(got), np.asarray(want)
    return np.abs(got - want).max() <= tolerance * np.abs(want).max()


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("layer", [0, LAYERS - 1], ids=["first", "last"])
def test_the_kernel_is_the_one_token_form(layer, shape):
    """The kernel on layer `layer` of a pool of several against `kda_step`
    on that layer's slice: `o` and the whole pool to float32 rounding, a
    slot with `g` and `beta` 0 its row bit for bit, every other layer bit
    for bit."""
    ins = drawn(*SHAPES[shape], seed=layer + 10 * len(shape))
    pool = ins.pop("pool")
    assert op.kernel_takes(pool)
    assert float(ins["beta"].max()) > 1.0              # beta reaches above 1
    want_o, want_state = kda.kda_step(**ins, state=pool[layer])
    o, new = jax.jit(functools.partial(op.kda_update, interpret=True))(
        *ins.values(), pool, jnp.int32(layer))
    assert o.shape == want_o.shape and new.shape == pool.shape
    assert close(o, want_o) and close(new, pool.at[layer].set(want_state))
    idle = np.asarray(IDLE)
    np.testing.assert_array_equal(np.asarray(new[layer])[idle],
                                  np.asarray(pool[layer])[idle])
    others = [i for i in range(LAYERS) if i != layer]
    np.testing.assert_array_equal(np.asarray(new)[others],
                                  np.asarray(pool)[others])
    # A live slot's row did move.
    assert not np.array_equal(np.asarray(new[layer, 0]),
                              np.asarray(pool[layer, 0]))


@pytest.mark.parametrize("why", ["off-the-tpu", "bfloat16", "narrow-v"])
def test_the_plain_form_runs_where_the_kernel_does_not(why):
    """Off the TPU, and for a pool the kernel does not take (a bfloat16
    state; values narrower than a lane tile), `kda_update` is `kda_step` on
    the layer sliced out and set back, bit for bit, asked for the
    interpreter or not."""
    ins = drawn(8, 16, 128, slots=2)
    pool = ins.pop("pool")
    interpret = why != "off-the-tpu"
    if why == "bfloat16":
        pool = pool.astype(jnp.bfloat16)
    elif why == "narrow-v":
        pool, ins["v"] = pool[..., :16], ins["v"][..., :16]
    assert op.kernel_takes(pool) == (why == "off-the-tpu")
    want_o, want_state = kda.kda_step(**ins, state=pool[1])
    o, new = op.kda_update(*ins.values(), pool, 1, interpret=interpret)
    assert new.dtype == pool.dtype
    np.testing.assert_array_equal(np.asarray(o), np.asarray(want_o))
    np.testing.assert_array_equal(
        np.asarray(new.astype(F32)),
        np.asarray(pool.at[1].set(want_state).astype(F32)))


def test_the_kernel_takes_whole_groups_of_heads_that_fit_its_memory():
    """A slot's row in and out, two buffers each, has to fit the kernel's
    VMEM: the serving cell's 4.19 MB row does, four times the heads do
    not; and the heads are turned over eight at a time, so they come in
    whole eights (shapes only: nothing this large is made)."""
    def takes(heads):
        return op.kernel_takes(jax.ShapeDtypeStruct(
            (3, 128, heads, 128, 128), F32))

    assert takes(64) and takes(128) and not takes(256) and not takes(60)


@pytest.mark.parametrize("form", ["plain", "kernel"])
def test_mixer_with_the_pool_and_a_layer_is_mixer_with_the_slice(
        form, monkeypatch):
    """`kda.mixer` given the whole pool and a layer's index against `mixer`
    given that layer's slice: the same output, the same state in that
    layer to float32 rounding (one side is jitted), the other layers and
    the idle slots' rows bit for bit; through the plain form (what the CPU
    runs) and through the kernel."""
    if form == "kernel":
        monkeypatch.setattr(kda, "kda_update", functools.partial(
            op.kda_update, interpret=True))
    cfg = replace(configs.get_config("tiny_solar_open2"), kda_num_heads=8,
                  kda_head_dim=128)
    stack = init_params(jax.random.PRNGKey(3), cfg)["layers"]["kda"]
    lp = jax.tree.map(lambda a: a[1], stack)
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    rec = kda.init_state(cfg, LAYERS, 5)
    assert op.kernel_takes(rec["state"])
    pool = jax.random.normal(ks[0], rec["state"].shape, F32)
    conv = jax.random.normal(ks[1], rec["conv"].shape[1:], cfg.dtype)
    h = jax.random.normal(ks[2], (5, 1, cfg.d_model), cfg.dtype)
    n_valid = jnp.asarray([1, 0, 1, 0, 1], jnp.int32)
    layer = 2
    want_out, want_state, want_conv = kda.mixer(
        h, lp, cfg, pool[layer], conv, n_valid)
    out, new, new_conv = jax.jit(
        lambda *a: kda.mixer(*a[:2], cfg, *a[2:5], layer=a[5]))(
            h, lp, pool, conv, n_valid, jnp.int32(layer))
    assert new.shape == pool.shape
    np.testing.assert_array_equal(np.asarray(new_conv), np.asarray(want_conv))
    np.testing.assert_array_equal(np.asarray(new[:layer]),
                                  np.asarray(pool[:layer]))
    idle = np.flatnonzero(np.asarray(n_valid) == 0)
    np.testing.assert_array_equal(np.asarray(new[layer])[idle],
                                  np.asarray(pool[layer])[idle])
    assert close(out, want_out, 1e-5) and close(new[layer], want_state)
    with pytest.raises(AssertionError, match="one-token"):
        kda.mixer(jnp.concatenate([h, h], axis=1), lp, cfg, pool, conv,
                  n_valid, layer=layer)


@pytest.mark.parametrize("state", ["float32", "bfloat16"])
def test_a_long_run_of_the_kernel_is_the_scan(state):
    """300 tokens through `kda_update`, the pool handed from call to call,
    against `kda_scan`'s float32 state after them: through the kernel the
    relative rms is float32's (7.1e-8 was read, where the chunked form
    reads 7.7e-7 against the reference), and a pool held in bfloat16,
    which takes the plain form and rounds the state a token, is thirty
    thousand times further (2.4e-3 was read, the chunked form's control
    alike): any limit between 1e-5 and 1e-3 parts them, which the serving
    cell's `correct` does not (PERF.md section 7)."""
    length, heads, dk, dv = 300, 8, 16, 128
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    q, k = (jax.random.normal(key, (1, length, heads, dk), F32)
            for key in ks[:2])
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (1, length, heads, dv), F32)
    g = -0.3 * jnp.exp(jax.random.normal(ks[3], (1, length, heads, dk), F32))
    beta = 2.0 * jax.nn.sigmoid(
        jax.random.normal(ks[4], (1, length, heads), F32))
    _, want = kda.kda_scan(q, k, v, g, beta, jnp.zeros((1, heads, dk, dv)))
    pool = jnp.zeros((2, 1, heads, dk, dv), state)
    assert op.kernel_takes(pool) == (state == "float32")
    step = jax.jit(functools.partial(op.kda_update, interpret=True))
    for t in range(length):
        _, pool = step(q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t], pool,
                       jnp.int32(1))
    assert not np.asarray(pool[0].astype(F32)).any()
    size = float(jnp.sqrt(jnp.mean(want ** 2)))
    apart = float(jnp.sqrt(jnp.mean(
        (pool[1].astype(F32) - want) ** 2))) / size
    if state == "float32":
        assert apart < 1e-5, apart
    else:
        assert apart > 1e-3, apart
