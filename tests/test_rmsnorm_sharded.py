"""`ops.rmsnorm` under a mesh, on the tier-1 mesh of virtual devices with
the kernels in Pallas's interpreter: the differentiation rule lies outside
the per-device region (`ops.per_shard`), and writes the one sum it needs,
the scale's gradient over the axes the rows are split over, itself."""

import re

import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from ray_tpu.models.transformer import _ACT_SPEC
from ray_tpu.ops import rmsnorm
from ray_tpu.parallel import MeshConfig, build_mesh

# The activations' spec leaves "tp" out (a device's rows are replicated
# over it); the other names every axis, the sequence split over "tp".
SPECS = {"act": _ACT_SPEC, "every-axis": P(("dp", "fsdp"), ("sp", "tp"), None)}


@pytest.mark.parametrize("rows_over", sorted(SPECS))
def test_norm_between_parallel_products_matches_unsharded(rows_over):
    """A norm between a row-parallel and a column-parallel product under
    fsdp=2 x tp=2, as a layer has it: every gradient equals the unsharded
    one, the scale's included (its sum over the batch axes is still
    there). Where the spec leaves "tp" out, nothing is summed over it: no
    region is transposed, so no cotangent is divided by the replicas and
    no dx all-reduced over them."""
    spec = SPECS[rows_over]
    mesh = build_mesh(MeshConfig(fsdp=2, tp=2), jax.devices()[:4])
    ks = jax.random.split(jax.random.PRNGKey(21), 4)
    a = jax.random.normal(ks[0], (4, 16, 32))
    w_row = jax.random.normal(ks[1], (32, 64)) * 0.2
    scale = jax.random.normal(ks[2], (64,)) * 0.1 + 1.0
    w_col = jax.random.normal(ks[3], (64, 32)) * 0.2

    def loss(a, w_row, scale, w_col, mesh=None):
        h = rmsnorm(a @ w_row, scale, interpret=True, mesh=mesh, spec=spec)
        return ((h @ w_col) ** 2).sum()

    whole = jax.value_and_grad(loss, argnums=(0, 1, 2, 3))(
        a, w_row, scale, w_col)
    put = lambda x, s: jax.device_put(x, NamedSharding(mesh, s))  # noqa: E731
    args = (put(a, P(("dp", "fsdp"), None, "tp")), put(w_row, P("tp", None)),
            put(scale, P()), put(w_col, P(None, "tp")))
    grad = jax.value_and_grad(lambda *x: loss(*x, mesh=mesh),
                              argnums=(0, 1, 2, 3))
    for got, want in zip(jax.tree.leaves(jax.jit(grad)(*args)),
                         jax.tree.leaves(whole)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)
    summed_over = re.findall(r"psum\[[^\]]*?axes=\(([^)]*)\)",
                             str(jax.make_jaxpr(grad)(*args)))
    assert summed_over and all(("tp" in axes) == (rows_over == "every-axis")
                               for axes in summed_over), summed_over


def test_rule_leaves_the_sum_to_a_region_that_is_already_per_device():
    """Inside a caller's own shard_map (the pipeline schedule's) the op
    runs on the device's rows as they are and sums nothing: the region's
    maker owns the sum, here a psum written beside the call."""
    mesh = build_mesh(MeshConfig(fsdp=2, tp=2), jax.devices()[:4])
    ks = jax.random.split(jax.random.PRNGKey(22), 2)
    x = jax.random.normal(ks[0], (4, 16, 64))
    scale = jax.random.normal(ks[1], (64,)) * 0.1 + 1.0

    def loss(x, scale, mesh=None):
        return (rmsnorm(x, scale, interpret=True, mesh=mesh,
                        spec=_ACT_SPEC) ** 2).sum()

    grad = jax.grad(lambda *a: loss(*a, mesh=mesh), argnums=(0, 1))

    def local(x, scale):
        assert "psum" not in str(jax.make_jaxpr(grad)(x, scale))
        dx, dw = grad(x, scale)
        return dx, jax.lax.psum(dw, ("dp", "fsdp", "sp"))

    got = jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=(_ACT_SPEC, P()),
        out_specs=(_ACT_SPEC, P()), check_vma=False))(x, scale)
    want = jax.grad(loss, argnums=(0, 1))(x, scale)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-4)
