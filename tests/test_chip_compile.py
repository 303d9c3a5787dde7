"""The Pallas kernels of the main path and the serving engine's step
programs, compiled for a TPU v5e that is described and not attached (the
`on-chip-measurement` guide, section 2).

Interpret mode (tests/test_ops.py) checks a kernel's arithmetic; it
cannot see what the chip's compiler refuses: a block that overflows
scoped VMEM, a slice off the tiling, a custom call under a sharded jit.
Nothing runs here, so these say nothing about results or speed.
"""

import dataclasses
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from ray_tpu.models import configs
from ray_tpu.ops.flash_attention import flash_attention
from ray_tpu.ops.rmsnorm import rmsnorm
from ray_tpu.serve import llm

QWEN = configs.get_config("qwen3-4b")
# Every width a production-scale configuration trains at.
WIDTHS = sorted({c.d_model for c in configs.NAMED_CONFIGS.values()
                 if c.d_model >= 2048})
TRAIN_ROWS = 8 * 1024


@pytest.fixture(scope="module")
def v5e():
    """The four devices of a described v5e 2x2 host, with the persistent
    compile cache off: an entry compiled for a described chip is written
    but cannot be read back without one, and the next compile warns."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this install
        pytest.skip(f"cannot describe a v5e topology: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo.devices
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _kernel_count(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


@pytest.mark.parametrize("batch,seq", [(8, 1024), (2, 4096)])
@pytest.mark.parametrize("backward", [False, True], ids=["fwd", "bwd"])
def test_flash_attention_compiles_at_qwen3_shapes(v5e, batch, seq, backward):
    one = SingleDeviceSharding(v5e[0])
    q = jax.ShapeDtypeStruct((batch, seq, QWEN.n_heads, QWEN.head_dim),
                             jnp.bfloat16, sharding=one)
    kv = jax.ShapeDtypeStruct((batch, seq, QWEN.n_kv_heads, QWEN.head_dim),
                              jnp.bfloat16, sharding=one)

    def attend(q, k, v):
        return flash_attention(q, k, v, block_q=QWEN.attn_block_q,
                               block_k=QWEN.attn_block_k, use_pallas=True)

    def loss(q, k, v):
        return attend(q, k, v).astype(jnp.float32).sum()

    fn = jax.grad(loss, argnums=(0, 1, 2)) if backward else attend
    assert _kernel_count(_compile(fn, q, kv, kv)) >= (3 if backward else 1)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("d_model", WIDTHS)
def test_rmsnorm_compiles_at_every_width(v5e, d_model, dtype):
    """Forward and backward at a train step's row count. Width 4096 and
    up overflowed scoped VMEM while the row block was a constant 256."""
    one = SingleDeviceSharding(v5e[0])
    x = jax.ShapeDtypeStruct((TRAIN_ROWS, d_model), dtype, sharding=one)
    w = jax.ShapeDtypeStruct((d_model,), dtype, sharding=one)

    def loss(x, w):
        return rmsnorm(x, w, use_pallas=True).astype(jnp.float32).sum()

    fn = jax.value_and_grad(loss, argnums=(0, 1))
    assert _kernel_count(_compile(fn, x, w)) == 2


@pytest.mark.parametrize("rows", [4, 64])
def test_rmsnorm_compiles_at_engine_rows(v5e, rows):
    """The engine norms one row per decode slot and one prefill chunk."""
    one = SingleDeviceSharding(v5e[0])
    x = jax.ShapeDtypeStruct((rows, 1, QWEN.d_model), jnp.bfloat16,
                             sharding=one)
    w = jax.ShapeDtypeStruct((QWEN.d_model,), jnp.bfloat16, sharding=one)
    fn = lambda x, w: rmsnorm(x, w, use_pallas=True)  # noqa: E731
    assert _kernel_count(_compile(fn, x, w)) == 1


@pytest.fixture(scope="module")
def sharded_mesh(v5e):
    from ray_tpu.parallel import MeshConfig, build_mesh

    return build_mesh(MeshConfig(fsdp=2, tp=2), v5e)


def test_kernels_compile_under_a_sharded_jit(sharded_mesh):
    """GSPMD cannot partition a Mosaic kernel; given the mesh, the ops
    run it on each device's block, and the gradient of the replicated
    scale comes back through a collective."""
    from ray_tpu.models.transformer import _ACT_SPEC, _HEADS_SPEC

    mesh = sharded_mesh

    def struct(shape, spec):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                                    sharding=NamedSharding(mesh, spec))

    x = struct((8, 1024, QWEN.d_model), _ACT_SPEC)
    w = struct((QWEN.d_model,), P())
    q = struct((8, 1024, QWEN.n_heads, QWEN.head_dim), _HEADS_SPEC)
    kv = struct((8, 1024, QWEN.n_kv_heads, QWEN.head_dim), _HEADS_SPEC)

    def loss(x, w, q, k, v):
        h = rmsnorm(x, w, use_pallas=True, mesh=mesh, spec=_ACT_SPEC)
        a = flash_attention(q, k, v, use_pallas=True, mesh=mesh,
                            spec=_HEADS_SPEC)
        return (h.astype(jnp.float32).sum() + a.astype(jnp.float32).sum())

    compiled = _compile(jax.grad(loss, argnums=(0, 1, 2, 3, 4)),
                        x, w, q, kv, kv)
    assert _kernel_count(compiled) >= 4
    assert "all-reduce" in compiled.as_text()

    def unwrapped(x, w):
        return rmsnorm(x, w, use_pallas=True)

    with pytest.raises(NotImplementedError, match="shard_map"):
        _compile(unwrapped, x, w)

    # The decode-attention kernel under the engine's mesh: KV heads (the
    # pool's last axis, the queries' heads) over "tp", the rest whole.
    from ray_tpu.ops.paged_attention import paged_decode_attention

    def whole(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, P()))

    pool = struct((DEPTH, SLOTS * (MAX_LEN // PAGE) + 1, PAGE,
                   QWEN.n_kv_heads * QWEN.head_dim), P(None, None, None, "tp"))
    queries = struct((SLOTS, QWEN.n_heads, QWEN.head_dim), P(None, "tp"))

    def attend(q, k, v, layer, tables, rows):
        return paged_decode_attention(q, k, v, layer, tables, rows,
                                      QWEN.attention_scale, use_pallas=True,
                                      mesh=mesh)

    compiled = _compile(attend, queries, pool, pool, whole(()),
                        whole((SLOTS, MAX_LEN // PAGE)), whole((SLOTS,)))
    assert "paged_decode_attention" in compiled.as_text()


COLLECTIVE = r"(?:all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"


def _results_of(text, op, shape):
    """(name, op_name) of every instruction of a compiled text whose
    result is `shape` (as the compiler prints it, layout apart) and whose
    operation matches `op`, the asynchronous form's start included."""
    line = re.compile(rf"^\s*(?:ROOT )?(\S+) = \(?{re.escape(shape)}[^=]*? "
                      rf"{op}(?:-start)?\(.*$", re.MULTILINE)
    found = []
    for m in line.finditer(text):
        op_name = re.search(r'op_name="([^"]*)"', m.group(0))
        found.append((m.group(1), op_name.group(1) if op_name else ""))
    return found


@pytest.fixture(scope="module")
def sharded_step(sharded_mesh):
    """`train-fsdp2tp2-8x1024`'s step (bench/train_cell.py: AdamW over
    `value_and_grad(loss_fn)`, `dots_nobatch`, a loss chunk of 256, 8 x
    1025 tokens), the layers being one scan, compiled once for each
    (tied, depth) asked for: its text and its `memory_analysis()`."""
    import optax

    from ray_tpu.models import loss_fn, param_logical_axes
    from ray_tpu.models.transformer import init_params
    from ray_tpu.parallel import logical_shardings

    mesh = sharded_mesh
    replicated = NamedSharding(mesh, P())
    compiled = {}

    def compile_step(tied=True, depth=2):
        if (tied, depth) in compiled:
            return compiled[tied, depth]
        cfg = dataclasses.replace(
            QWEN, n_layers=depth, max_seq=1024, remat=True,
            remat_policy="dots_nobatch", ce_chunk=256, tie_embeddings=tied)
        params = jax.tree.map(
            lambda a, sharding: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                     sharding=sharding),
            jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg)),
            logical_shardings(param_logical_axes(cfg), mesh))
        optimizer = optax.adamw(3e-4)
        # The moments lie as their parameters do, the count is whole.
        moments, *rest = jax.eval_shape(optimizer.init, params)
        opt_state = (moments._replace(
            count=jax.ShapeDtypeStruct((), jnp.int32, sharding=replicated),
            mu=params, nu=params), *rest)
        layouts = jax.tree.map(lambda x: x.sharding, (params, opt_state))
        tokens = jax.ShapeDtypeStruct(
            (8, 1025), jnp.int32,
            sharding=NamedSharding(mesh, P(("dp", "fsdp"), None)))

        def step(params, opt_state, tokens):
            loss, grads = jax.value_and_grad(loss_fn)(params, tokens, cfg,
                                                      mesh)
            updates, opt_state = optimizer.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state, loss

        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(jax, "default_backend", lambda: "tpu")
            exe = jax.jit(
                step, donate_argnums=(0, 1),
                out_shardings=(*layouts, replicated),
            ).lower(params, opt_state, tokens).compile()
        text = exe.as_text()
        assert text.count("tpu_custom_call") >= 8  # the kernels are in it
        compiled[tied, depth] = text, exe.memory_analysis()
        return compiled[tied, depth]

    return compile_step


def test_sharded_train_step_reduces_an_activation_four_times_a_layer(
        sharded_step):
    """At depth 2 the all-reduces of a device's activation
    `bf16[4,1024,2560]` are the two a layer that `tp=2` requires forward
    (after `wo` and `w_down`) and the two backward (the cotangents of the
    norms' outputs, from the column-parallel products), all the
    partitioner's. While the norm's rule lay inside its per-device
    region, JAX transposed a `shard_map` whose operand was replicated
    over "tp": the backward body halved each cotangent (`checkpoint/div`)
    and all-reduced each dx over the same pairs again (`shard_map/psum`),
    21 MB each that computed the identity, 64 + 1 times a step at depth
    32."""
    text, _ = sharded_step()
    activation = f"bf16[4,1024,{QWEN.d_model}]"
    reduced = _results_of(text, "all-reduce", activation)
    in_body = [op for _, op in reduced if "/while/body/" in op]
    assert len(reduced) == len(in_body) == 4, reduced
    assert sum("transpose(" in op for op in in_body) == 2, reduced
    assert all(op.endswith("/dot_general") for op in in_body), reduced
    assert "shard_map/psum" not in " ".join(
        op for _, op in _results_of(text, COLLECTIVE, activation))
    # Nothing passes over an activation to divide it by the replicas.
    assert not [op for _, op in _results_of(text, "fusion", activation)
                if op.endswith("/div")]


def _moves_of_width(text, width):
    """(kind, op_name) of every instruction of a compiled text that moves
    an array with a dimension of `width` between devices: a collective, or
    the fusion the compiler makes of a reduce-scatter."""
    line = re.compile(
        rf"^\s*(?:ROOT )?\S+ = \(?\w+\[(?:\d+,)*{width}(?:,\d+)*\][^=]*? "
        rf"(?:({COLLECTIVE})(?:-start)?\(|fusion\(.*calls=%(all-reduce-scatter))"
        rf".*$", re.MULTILINE)
    found = []
    for m in line.finditer(text):
        op_name = re.search(r'op_name="([^"]*)"', m.group(0))
        found.append((m.group(1) or m.group(2),
                      op_name.group(1) if op_name else ""))
    return found


@pytest.mark.parametrize("head", ["tied", "untied"])
def test_sharded_train_step_moves_the_head_table_over_fsdp_once(
        sharded_step, head):
    """The chunked loss closes over the head's table in two scans (forward,
    and the backward that recomputes each chunk's logits). Left as the
    parameter lies, split over "fsdp" on its model axis, the partitioner
    gathered it inside both bodies and reduce-scattered its gradient
    inside the backward's: eight gathers and four reduce-scatters of 389
    MB a step at 8 x 1024 tokens. `loss_fn` hands the loss the table
    whole over "fsdp": one gather before the scans, one reduce-scatter
    after, none of that width in a `while` body, for `embed.T` and for a
    head of its own. The ties that keep the gathered table and gradient
    out of the layers' backward show as memory: no more temporaries than
    the step had before (2,008 MB; the hint alone 2,262)."""
    text, memory = sharded_step(tied=head == "tied")
    moves = _moves_of_width(text, QWEN.vocab_size // 2)
    named = [(kind, op) for kind, op in moves if op]  # a fusion's insides
    assert not [m for m in named if "/while/body/" in m[1]], named
    assert sorted(kind for kind, _ in named) == [
        "all-gather", "all-reduce-scatter"], named
    assert memory.temp_size_in_bytes <= 2.0e9, memory.temp_size_in_bytes


def test_sharded_train_step_fits_its_chip_at_the_cell_s_depth(sharded_step):
    """At the cell's 32 layers the step's arguments and temporaries stay
    under the 15.2 GB a chip by which its configuration chose the depth
    (`bench/configs/qwen3-4b-train-fsdp2tp2.json`, `depth_note`: 14.59
    then). A gathered table or gradient that outlives the loss lands on
    the step's deepest point: the hint alone compiled at 15.92."""
    _, memory = sharded_step(depth=32)
    assert (memory.argument_size_in_bytes
            + memory.temp_size_in_bytes) <= 15.2e9


def test_flash_attention_backward_under_a_mesh_moves_no_operand(sharded_mesh):
    """`_HEADS_SPEC` names every axis of this mesh that is wider than
    one, so the transpose of the attention's per-device region neither
    divides nor sums: no collective carries anything of q's, k's or v's
    shape, a device's block or the whole."""
    from ray_tpu.models.transformer import _HEADS_SPEC

    def struct(heads):
        return jax.ShapeDtypeStruct(
            (8, 1024, heads, QWEN.head_dim), jnp.bfloat16,
            sharding=NamedSharding(sharded_mesh, _HEADS_SPEC))

    def loss(q, k, v):
        return flash_attention(q, k, v, use_pallas=True, mesh=sharded_mesh,
                               spec=_HEADS_SPEC).astype(jnp.float32).sum()

    q, kv = struct(QWEN.n_heads), struct(QWEN.n_kv_heads)
    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv).as_text()
    assert text.count("tpu_custom_call") >= 3
    for batch in (8, 4):
        for heads in {QWEN.n_heads, QWEN.n_heads // 2,
                      QWEN.n_kv_heads, QWEN.n_kv_heads // 2}:
            shape = f"bf16[{batch},1024,{heads},{QWEN.head_dim}]"
            assert not _results_of(text, COLLECTIVE, shape), shape


# The serving cell's geometry (bench/configs/qwen3-4b-serve.json): 24 slots
# of 1024 positions, pages of 16 rows and the NULL page, chunks of 64. Depth
# is cut to 4: what is asserted does not depend on it.
SLOTS, MAX_LEN, PAGE, CHUNK, DEPTH = 24, 1024, 16, 64, 4
ENGINE_PROGRAMS = ["decode_paged", "prefill_chunk_paged"]
# A step program and, for a prefill pass, its rows: one, and every count
# up to the engine's widest (it compiles one row and four at a chunk of
# 64, one and two at a chunk of 256).
ENGINE_STEPS = [("decode_paged", None), *(
    ("prefill_chunk_paged", rows) for rows in (1, 2, llm.PASS_ROWS))]
STEP_IDS = [f"{name}-{rows}" if rows else name for name, rows in ENGINE_STEPS]
USABLE_BYTES = 16.9e9  # of a v5e's memory, PERF.md section 3


def _whole_model_fits(compiled, args, cfg, depth):
    """The program's arguments at all of `cfg`'s layers (it was compiled
    at `depth` of them: a leaf with the layers in front grows by their
    ratio, and the temporaries of a scan over layers do not) and its
    temporaries lie inside the chip's memory."""
    grown = sum(
        a.size * a.dtype.itemsize * (
            cfg.n_layers / depth if a.ndim > 1 and a.shape[0] == depth else 1)
        for a in jax.tree.leaves(args))
    temporaries = compiled.memory_analysis().temp_size_in_bytes
    return grown + temporaries < USABLE_BYTES


def _engine_program(name, cfg, one, slots=SLOTS, rows=1, chunk=CHUNK):
    """(function, donated arguments, argument shapes, the cache's shape)
    of one of the engine's step programs, as `ContinuousBatchingEngine`
    jits it on one chip; decode is the sampled variant, a prefill pass is
    `rows` rows of `chunk` tokens."""
    from ray_tpu.models.transformer import init_params
    from ray_tpu.serve import paged_kv

    def struct(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    params = jax.tree.map(
        lambda a: struct(a.shape, a.dtype),
        jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg)))
    per_slot = MAX_LEN // PAGE
    shape = jax.eval_shape(lambda: paged_kv.init_paged_cache(
        cfg, slots, slots * per_slot + 1, PAGE, per_slot))["k"].shape
    cache = struct(shape, cfg.dtype)
    lengths = struct((slots,))
    table = struct((slots, per_slot))
    if name == "decode_paged":
        sampling = (struct((slots,), jnp.float32), struct((slots,)),
                    struct((slots,), jnp.float32), struct((2,), jnp.uint32))
        args = (params, struct((slots,)), cache, cache, lengths,
                struct((slots,), jnp.bool_), table, *sampling)
        fn = lambda p, t, k, v, ln, a, bt, *s: paged_kv.decode_paged(  # noqa: E731
            p, t, k, v, ln, a, bt, *s, cfg, MAX_LEN)
        return fn, (2, 3), args, shape
    row = struct((rows,))
    args = (params, struct((rows, chunk)), row, row, row, cache, cache,
            lengths, table)
    fn = lambda p, t, n, s, o, k, v, ln, bt: paged_kv.prefill_chunk_paged(  # noqa: E731
        p, t, n, s, o, k, v, ln, bt, cfg, MAX_LEN)
    return fn, (5, 6), args, shape


@pytest.fixture(scope="module")
def qwen_step(v5e):
    """Qwen3-4B's step programs at depth 4 as the engine jits them on one
    described chip: (compiled, argument shapes, the cache's shape), each
    compiled once for the tests that read it."""
    compiled = {}

    def step(program, rows):
        if (program, rows) not in compiled:
            fn, donated, args, shape = _engine_program(
                program, dataclasses.replace(QWEN, n_layers=DEPTH),
                SingleDeviceSharding(v5e[0]), rows=rows)
            compiled[program, rows] = (
                jax.jit(fn, donate_argnums=donated).lower(*args).compile(),
                args, shape)
        return compiled[program, rows]

    return step


@pytest.mark.parametrize("program,rows", ENGINE_STEPS, ids=STEP_IDS)
def test_engine_step_updates_its_cache_in_place(qwen_step, program, rows,
                                                monkeypatch):
    """The KV cache rides in the layer scan's carry, so a step with its
    caches donated scatters into the caller's buffers: no second cache
    among the temporaries (scanned over and stacked back a cache is two
    buffers, 3.73 GB of temporaries at 36 layers) and no copy of a whole
    one, the decode-attention kernel reading the carried pool at the
    layer's index included (a layer sliced out for it would be copied)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled, args, shape = qwen_step(program, rows)
    one_cache = 2 * DEPTH * SLOTS * MAX_LEN * QWEN.n_kv_heads * QWEN.head_dim
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < one_cache // 2
    assert _whole_model_fits(compiled, args, QWEN, DEPTH)
    # Both caches come back in the buffers they came in.
    assert memory.alias_size_in_bytes >= 2 * one_cache
    dims = ",".join(map(str, shape))
    whole_cache_copies = re.findall(
        rf"= bf16\[{dims}\]\S* copy\(", compiled.as_text())
    assert not whole_cache_copies


@pytest.mark.parametrize("program,rows", ENGINE_STEPS, ids=STEP_IDS)
def test_engine_step_reads_wq_and_wk_in_place(qwen_step, program, rows,
                                              monkeypatch):
    """Qwen3-4B's step programs read a layer's `wq` and `wk` where they lie
    in the stack, as they read `wv`: the slice is a nested computation of
    the product. With the per-head norm's sum of squares fused into the
    product the compiler wants the weight heads-major, so every layer of
    every step sliced `bf16[1,2560,4096]` and `bf16[1,2560,1024]` out of
    the stack (`constant_dynamic-slice_fusion`) and copied each into the
    other order: 0.31 s of 2.6 s busy in `serve-chat-steady`.
    `project_qkv` holds the products back from the norm."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text = qwen_step(program, rows)[0].as_text()
    for heads in (QWEN.n_heads, QWEN.n_kv_heads):
        # Top level: a fusion's or a copy's result. Inside a fused
        # computation the slice is a `dynamic-slice(` and costs nothing.
        layer = f"{QWEN.d_model},{heads * QWEN.head_dim}"
        assert not re.findall(
            rf"= bf16\[1,{layer}\]\S* (?:copy|fusion)\(", text)


@pytest.mark.parametrize("model,slots,depth", [
    ("qwen3-4b", 24, DEPTH), ("olmoe-1b-7b", 16, 2)])
def test_sampled_decode_sorts_no_vocabulary(v5e, model, slots, depth,
                                            monkeypatch):
    """Top-p's threshold is found by bisection (`_top_p_threshold`), so
    the sampled decode program at a serving cell's slots and vocabulary
    has no sort over the vocabulary: with one (`sort.6 f32[24,151936]`)
    it was the largest single name on the device in both Qwen3 cells,
    5.7 ms of a 28 ms step. Top-k's cut is bisected too, in a loop that
    makes no trip unless a slot asks for one: `lax.top_k`'s custom fusion
    over `[slots, MAX_TOP_K]` is in no sampled program, and no
    conditional is, which would take the logits out of the chip's vector
    memory to hand them to its branch."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = dataclasses.replace(configs.get_config(model), n_layers=depth)
    fn, donated, args, _ = _engine_program(
        "decode_paged", cfg, SingleDeviceSharding(v5e[0]), slots)
    compiled = jax.jit(fn, donate_argnums=donated).lower(*args).compile()
    vocabulary_wide = re.compile(rf"[\[,]{cfg.vocab_size}\]")
    text = compiled.as_text()
    sorts = [line for line in text.splitlines()
             if " sort(" in line and vocabulary_wide.search(line)]
    assert not sorts
    assert not [line for line in text.splitlines()
                if 'custom_call_target="TopK"' in line
                and "moe.route" not in line]  # the router's is its own
    assert not re.findall(r" conditional\(", text)
    # The sorted float32 copy and its cumsum went with it: what is left
    # is under one float32 `[slots, vocabulary]` (0.76 MB where the
    # sort's program had 15.26 MB; 2.0 MB at OLMoE's, which held its
    # gathered cache in float32, 136 MB, until attention read the pool
    # in place).
    one_copy = 4 * slots * cfg.vocab_size
    assert compiled.memory_analysis().temp_size_in_bytes < one_copy


def _gathered_results(text, slots, row_elements):
    """Lines of a compiled decode program whose result is a slot's whole
    table brought together: `[slots x pages a slot, page, ...]` or
    `[slots, pages a slot, page, ...]` in any type, or a float32 result
    of the whole table's size (the cast cache, whichever way it lies)."""
    per_slot = MAX_LEN // PAGE
    by_page = re.compile(
        rf"= \w+\[(?:{slots * per_slot}|{slots},{per_slot}),{PAGE},[\d,]+\]")
    whole_f32 = re.compile(r"= f32\[([\d,]+)\]")

    def elements(dims):
        n = 1
        for d in dims.split(","):
            n *= int(d)
        return n

    return [line.strip()[:160] for line in text.splitlines()
            if by_page.search(line) or any(
                elements(m) >= slots * MAX_LEN * row_elements
                for m in whole_f32.findall(line.split(" = ", 1)[-1][:80]))]


@pytest.mark.parametrize("model,slots,depth", [
    ("qwen3-4b", 24, DEPTH), ("olmoe-1b-7b", 16, 2),
    ("granite-4.0-h-micro", 48, None)])
def test_sampled_decode_reads_its_live_pages_in_place(v5e, model, slots, depth,
                                                      monkeypatch):
    """The sampled decode program at each serving cell's model and slots
    holds the decode-attention kernel's custom call and nothing of the
    gather it replaced: no result of the gathered shapes
    (`bf16[1536,16,8,128]` and its float32 cast were 8.5 ms of Qwen3's
    22 ms step, `f32[1024,16,16,128]` twice and the scores over them 11.8
    of OLMoE's 32.6), and temporaries under one slot-major copy of a
    layer's keys. The hybrid's narrow row `[8 x 64]` goes through the same
    kernel, its 40 layers whole."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    one = SingleDeviceSharding(v5e[0])
    cfg = configs.get_config(model)
    if cfg.layer_pattern:
        fn, donated, args, _ = _hybrid_program("decode_paged", one)
    else:
        cfg = dataclasses.replace(cfg, n_layers=depth)
        fn, donated, args, _ = _engine_program("decode_paged", cfg, one, slots)
    compiled = jax.jit(fn, donate_argnums=donated).lower(*args).compile()
    text = compiled.as_text()
    assert len(re.findall(r"%paged_decode_attention[.\d]* = f32\[", text)) == 1
    row = cfg.n_kv_heads * cfg.head_dim
    assert not _gathered_results(text, slots, row)
    one_gather = 2 * slots * MAX_LEN * row
    assert compiled.memory_analysis().temp_size_in_bytes < one_gather


@pytest.mark.parametrize("program,rows", ENGINE_STEPS, ids=STEP_IDS)
def test_expert_model_step_reads_its_expert_stacks_in_place(
        v5e, program, rows, monkeypatch):
    """OLMoE's step programs at its published widths (depth cut to 2): the
    three grouped products of a layer are the Mosaic kernel, and no layer's
    experts are copied out of the stack for it. A kernel takes its operands
    whole, so a layer sliced out of `[L, E, d, ff]` inside the scan is a
    268 MB temporary a matrix, 0.8 GB a layer (3.3 against 0.96 ms on the
    chip, PERF.md section 6); `_scan_layers` keeps the stacks out of the
    scan and `moe_block` reads them at the layer's index."""
    # The program asks the backend which branch to take; the chip's is
    # the kernel outside Pallas's interpreter.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    olmoe = configs.get_config("olmoe-1b-7b")
    cfg = dataclasses.replace(olmoe, n_layers=2)
    # The serving cell's geometry: 16 slots x 1024, chunks of 256.
    fn, donated, args, shape = _engine_program(
        program, cfg, SingleDeviceSharding(v5e[0]), 16, rows, 256)
    compiled = jax.jit(fn, donate_argnums=donated).lower(*args).compile()
    text = compiled.as_text()
    assert len(re.findall(r"%gmm[.\d]* = f32\[", text)) == 3
    assert _whole_model_fits(compiled, args, olmoe, 2)
    dims = ",".join(map(str, shape))
    assert not re.findall(rf"= bf16\[{dims}\]\S* copy\(", text)
    # Sliced out for a kernel, a layer's matrix is the result of a fusion
    # (`dynamic-slice_bitcast_fusion`) or a copy, `bf16[E, d, ff]`.
    for inner in (f"{cfg.d_model},{cfg.d_ff}", f"{cfg.d_ff},{cfg.d_model}"):
        stack = f"{cfg.num_experts},{inner}"
        assert not re.findall(
            rf"= bf16\[(?:1,)?{stack}\]\S* (?:copy|fusion)\(", text)
    # What is left among the temporaries is a chunk's one slot of gathered
    # pages and a step's activations, far under one expert matrix's 268 MB.
    one_matrix = 2 * cfg.num_experts * cfg.d_model * cfg.d_ff
    assert compiled.memory_analysis().temp_size_in_bytes < one_matrix


# The cells' grouped products: rows of a decode step and of the widest
# prefill pass, `k`, `n`, groups in the stack the kernel is handed.
GROUPED_PRODUCTS = {
    "olmoe": ((128, 4096), 2048, 1024, 16 * 64),
    "dots": ((512, 4096), 7168, 2048, 5 * 16),
    "lfm2": ((384, 2048), 2048, 1536, 8 * 64),
    "solar": ((1024, 4096), 4096, 1280, 4 * 40),
    "sdar": ((3072, 4096), 2048, 768, 6 * 128),
}


@pytest.mark.parametrize("backward", [False, True], ids=["fwd", "bwd"])
@pytest.mark.parametrize("product", ["gate-up", "down"])
@pytest.mark.parametrize("cell", GROUPED_PRODUCTS)
def test_grouped_product_compiles_at_the_cells_shapes(
        v5e, cell, product, backward, monkeypatch):
    """The grouped matmul alone with the tiles `gmm_tiling` works out for
    each serving cell's two products, inside the scoped VMEM a kernel
    compiles under; and its gradient, whose two products keep the plain
    tile (`tgmm` holds a float32 weight tile: with the forward product's
    tiles an expert model's train step would not compile)."""
    from ray_tpu.parallel.moe import gmm_tiling, grouped_matmul

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    one = SingleDeviceSharding(v5e[0])
    rows, k, n, groups = GROUPED_PRODUCTS[cell]
    if product == "down":
        k, n = n, k
    sizes = jax.ShapeDtypeStruct((groups,), jnp.int32, sharding=one)
    w = jax.ShapeDtypeStruct((groups, k, n), jnp.bfloat16, sharding=one)
    for m in rows[:1] if backward else rows:
        x = jax.ShapeDtypeStruct((m, k), jnp.bfloat16, sharding=one)
        _, tk, tn = gmm_tiling(m, k, n, 2)
        assert (k % tk, n % tn) == (0, 0)
        fn = grouped_matmul
        if backward:
            fn = jax.grad(lambda x, w, sizes: grouped_matmul(
                x, w, sizes).sum(), argnums=(0, 1))
        text = _compile(fn, x, w, sizes).as_text()
        # The forward product under the name the cells' metrics match; of a
        # sum's gradient the two cotangents' products alone are left.
        assert text.count("tpu_custom_call") == (2 if backward else 1)
        assert len(re.findall(r"%gmm[.\d]* = f32\[", text)) == (not backward)


def _hybrid_program(program, one, rows=1):
    """`_engine_program` for granite-4.0-h-micro at its published sizes,
    all 40 layers, at the serving cell's 48 slots x 1024 (`rows` chunks of
    256 a prefill pass): (function, donated arguments, argument shapes,
    the cache's shapes)."""
    from ray_tpu.models.transformer import init_params
    from ray_tpu.serve import paged_kv

    cfg = configs.get_config("granite-4.0-h-micro")
    slots, chunk, per_slot = 48, 256, MAX_LEN // PAGE

    def described(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one), tree)

    params = described(jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg)))
    cache = described(jax.eval_shape(lambda: paged_kv.init_paged_cache(
        cfg, slots, slots * per_slot + 1, PAGE, per_slot)))
    count = described(jax.eval_shape(paged_kv.init_ssm_counters))

    def struct(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    pool = (cache["k"], cache["v"], cache["lengths"])
    if program == "decode_paged":
        fn = lambda p, t, k, v, ln, a, bt, tp, tk, tpp, key, rec, c: (  # noqa: E731
            paged_kv.decode_paged(p, t, k, v, ln, a, bt, tp, tk, tpp, key,
                                  cfg, MAX_LEN, None, None, rec, c))
        args = (params, struct((slots,)), *pool, struct((slots,), jnp.bool_),
                cache["block_tables"], struct((slots,), jnp.float32),
                struct((slots,)), struct((slots,), jnp.float32),
                struct((2,), jnp.uint32), cache["rec"], count)
        return fn, (2, 3, 11), args, cache
    fn = lambda p, t, n, s, o, k, v, ln, bt, rec, c: (  # noqa: E731
        paged_kv.prefill_chunk_paged(p, t, n, s, o, k, v, ln, bt, cfg,
                                     MAX_LEN, None, None, rec, c))
    row = struct((rows,))
    args = (params, struct((rows, chunk)), row, row, row, *pool,
            cache["block_tables"], cache["rec"], count)
    return fn, (5, 6, 9), args, cache


@pytest.mark.parametrize("program,rows", ENGINE_STEPS, ids=STEP_IDS)
def test_hybrid_step_updates_both_pools_in_place(v5e, program, rows,
                                                 monkeypatch):
    """granite-4.0-h-micro's step programs at its published sizes, all 40
    layers, at the serving cell's 48 slots x 1024 (chunks of 256): the
    pages and the recurrent pool ride in the layer walk's carry and come
    back in the buffers they came in, no stack of weights and no pool is
    copied, and what is left among the temporaries is a step's own (a
    chunk's decay matrix and its one slot's gathered pages). Seen here
    before the cell ran: `in_proj` held whole
    `[36, 2048, 8512]` was copied into another tiling at every call (1.25
    GB of temporaries), and pages laid `[.., 8, 64]` were turned over whole
    between a layer's scatter and its gather."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    fn, donated, args, cache = _hybrid_program(
        program, SingleDeviceSharding(v5e[0]), rows)
    params = args[0]
    assert cache["k"].shape == (4, 48 * (MAX_LEN // PAGE) + 1, PAGE, 8 * 64)
    assert cache["rec"]["state"].shape == (36, 48, 64, 64, 128)
    assert cache["rec"]["conv"].shape == (36, 48, 3, 4352)
    compiled = jax.jit(fn, donate_argnums=donated).lower(*args).compile()
    memory = compiled.memory_analysis()
    pools = sum(a.size * a.dtype.itemsize for a in (
        cache["k"], cache["v"], *cache["rec"].values()))
    assert memory.alias_size_in_bytes >= pools
    # 63 MB a row of a prefill pass; 1.7 MB in the decode program, 71 MB
    # while it gathered an attention layer's pages and cast them.
    assert memory.temp_size_in_bytes < 128 * 2**20 * (rows or 1)
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            < USABLE_BYTES)
    text = compiled.as_text()
    # (The convolution's saved inputs are 45 MB of the 3.7 GB pool, and a
    # chunk does turn those over: the decode program wants the 3 inputs of
    # all slots side by side, a chunk one slot's, whichever way they lie.)
    whole = [",".join(map(str, a.shape)) for a in (
        cache["k"], cache["rec"]["state"],
        *params["layers"]["ssm"].values(), *params["layers"]["mlp"].values())
        if a.size > 2**24]  # not the 9 MB of dt columns, prefetched whole
    for dims in whole:
        assert not re.findall(rf"= \w+\[{dims}\]\S* copy\(", text), dims


def _bench_on_path():
    """The benchmark's modules (`spec`, `readers`, `xplane`) importable, for
    the tests that hold a kernel's line to a metric's pattern."""
    import sys

    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "bench")
    if bench not in sys.path:
        sys.path.insert(0, bench)


def _takes_the_state_pool(text, pool="f32[36,48,64,64,128]"):
    """The fusions and custom calls of a compiled program that have the
    recurrent pool (the Mamba hybrid's `f32[36,48,64,64,128]` unless told)
    among their operands, as (own name, whole line): what passes over the
    state."""
    shapes, found = {}, []
    lines = [line.strip() for line in text.splitlines() if " = " in line]
    for line in lines:
        own, rest = line.removeprefix("ROOT ").split(" = ", 1)
        shapes[own] = re.match(r"\(?([a-z0-9]+\[[0-9,]*\])?", rest).group(1)
    for line in lines:
        m = re.search(r" (?:fusion|custom-call)\((.*?)\)", line)
        if m and any(shapes.get(o) == pool
                     for o in re.findall(r"%[\w.\-]+", m.group(1))):
            found.append((line.removeprefix("ROOT ").split(" = ")[0], line))
    return found


def test_hybrid_decode_passes_over_its_state_once(v5e, monkeypatch):
    """The hybrid's decode program compiled for the described v5e holds
    `ops.ssm_update`'s custom call once a compiled Mamba layer (twice: the
    scan's run and the tail's) and nothing else that takes the state pool:
    no fusion `f32[48,64,64]` that reads a layer's rows again for `y`
    beside one that writes them (the parent's two: 15.7 ms of a 27.6 ms
    step, 10.9 GB moved where 7.25 must). The pool is still aliased and
    not copied. Each call's line is one that `ssm.update_time_share` and
    `ssm.update_roofline_share` match, in a program their `contains_op`
    picks: in a trace an operation goes by its own name and its FIRST
    result's shape (`bench/xplane/reduce.py`), so the pool is the
    kernel's first result and `y` its second. The prefill pass does not
    reach the kernel: what takes the pool there is the parent's count (a
    slot's row read by two fusions and written by one, a compiled Mamba
    layer)."""
    _bench_on_path()
    import spec
    from xplane import reduce

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    one = SingleDeviceSharding(v5e[0])
    fn, donated, args, cache = _hybrid_program("decode_paged", one)
    compiled = jax.jit(fn, donate_argnums=donated).lower(*args).compile()
    text = compiled.as_text()
    passes = _takes_the_state_pool(text)
    assert len(passes) == 2, [own for own, _ in passes]
    for own, line in passes:
        assert re.fullmatch(r"%ssm_update[.\d]*", own), own
        assert "tpu_custom_call" in line
    pools = sum(a.size * a.dtype.itemsize for a in (
        cache["k"], cache["v"], *cache["rec"].values()))
    assert compiled.memory_analysis().alias_size_in_bytes >= pools
    assert not re.findall(r"= f32\[36,48,64,64,128\]\S* copy\(", text)
    short = [reduce._short(line.strip().removeprefix("ROOT "))
             for line in text.splitlines() if " = " in line]
    for metric in ("ssm.update_time_share", "ssm.update_roofline_share"):
        how = spec.layer_metric_spec(metric)
        match = re.compile(how["match"])
        for _, line in passes:
            assert match.search(reduce._short(line)), (metric, line[:120])
        if "contains_op" in how:  # the decode program is one the reader picks
            assert any(re.search(how["contains_op"], op) for op in short)
    fn, donated, args, _ = _hybrid_program("prefill_chunk_paged", one)
    text = jax.jit(fn, donate_argnums=donated).lower(*args).compile().as_text()
    passes = _takes_the_state_pool(text)
    assert len(passes) == 6 and not any("ssm_update" in own
                                        for own, _ in passes)


LATENT_SLOTS, LATENT_MAX_LEN, LATENT_CHUNK = 64, 4096, 512


def _latent_program(program, one):
    """`decode_paged` or `prefill_chunk_paged` at the sizes of the cell
    `serve-mla-docqa-closed` (one chip's share of dots.vlm1.inst's
    language model, 1 dense + 5 expert layers, 64 slots x 4096, pages of
    16, a pass of one row of 512), on shapes: (fn, donated, args, the
    cache's shapes, cfg)."""
    from ray_tpu.models.transformer import init_params
    from ray_tpu.serve import paged_kv

    cfg = dataclasses.replace(configs.get_config("dots-vlm1-ep16"),
                              n_layers=6, remat=False)
    slots, max_len = LATENT_SLOTS, LATENT_MAX_LEN
    per_slot = max_len // PAGE

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one), tree)

    def struct(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    params = on_chip(jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg)))
    cache = on_chip(jax.eval_shape(lambda: paged_kv.init_paged_cache(
        cfg, slots, slots * per_slot + 1, PAGE, per_slot)))
    moe = on_chip(jax.eval_shape(
        lambda: paged_kv.init_routing_counters(cfg)))
    if program == "decode_paged":
        fn = lambda p, t, k, ln, a, bt, tp, tk, tpp, key, moe: (  # noqa: E731
            paged_kv.decode_paged(p, t, k, None, ln, a, bt, tp, tk, tpp, key,
                                  cfg, max_len, moe=moe))
        args = (params, struct((slots,)), cache["k"], cache["lengths"],
                struct((slots,), jnp.bool_), cache["block_tables"],
                struct((slots,), jnp.float32), struct((slots,)),
                struct((slots,), jnp.float32), struct((2,), jnp.uint32), moe)
        donated = (2,)
    else:
        fn = lambda p, t, n, s, o, k, ln, bt, moe: (  # noqa: E731
            paged_kv.prefill_chunk_paged(p, t, n, s, o, k, None, ln, bt, cfg,
                                         max_len, moe=moe))
        row = struct((1,))
        args = (params, struct((1, LATENT_CHUNK)), row, row, row, cache["k"],
                cache["lengths"], cache["block_tables"], moe)
        donated = (5,)
    return fn, donated, args, cache, cfg


@pytest.mark.parametrize("program", ["decode_paged", "prefill_chunk_paged"])
def test_latent_pool_steps_fit_and_leave_the_pool_where_it_lies(
        v5e, program, monkeypatch):
    """The cell `serve-mla-docqa-closed`'s step programs at its own sizes
    (`_latent_program`): they fit the chip beside 13.02 GB of arguments,
    the one pool of 640-wide rows is updated where it lies (at the 576
    values a row needs, the compiler re-laid the whole pool at the step's
    start and end: 2 GB of temporaries and two copies of 1.8 GB a step),
    and the held experts' stacks are read in place by the three grouped
    products."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    fn, donated, args, cache, _ = _latent_program(
        program, SingleDeviceSharding(v5e[0]))
    assert cache["v"] is None and cache["k"].shape[-1] == 640
    compiled = jax.jit(fn, donate_argnums=donated).lower(*args).compile()
    memory = compiled.memory_analysis()
    assert 12.9e9 < memory.argument_size_in_bytes < 13.1e9
    assert memory.temp_size_in_bytes < 0.5e9  # the pool is 2.01 GB
    text = compiled.as_text()
    pool = ",".join(map(str, cache["k"].shape))
    assert not re.findall(rf"= bf16\[{pool}\]\S* copy\(", text)
    assert len(re.findall(r"%gmm[.\d]* = f32\[", text)) == 3
    assert not re.findall(r"= bf16\[(?:80|5,16),7168,2048\]\S* copy\(", text)


def test_latent_decode_reads_its_live_pages_in_place(v5e, monkeypatch):
    """The sampled decode program of `serve-mla-docqa-closed` at its own
    sizes holds the latent kernel's custom call once a stack of layers
    (the dense layers' scan and the expert layers') and nothing of the
    loop it replaced: no block of every slot's pages gathered
    (`bf16[2048,16,640]` a trip, read twice, was a third of a 24.5 ms
    step), temporaries under that one block's copy. The kernel's line is
    one that `mla.decode_attn_time_share` and `_roofline_share` match, the
    pattern filled as `bench/readers/mla.py` fills it: in a trace an
    operation goes by its own name and its first result's shape
    (`bench/xplane/reduce.py`), so a result of another shape would leave
    the share of the roofline to the queries' pad alone."""
    _bench_on_path()
    import spec
    from readers import mla
    from xplane import reduce

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    fn, donated, args, cache, cfg = _latent_program(
        "decode_paged", SingleDeviceSharding(v5e[0]))
    compiled = jax.jit(fn, donate_argnums=donated).lower(*args).compile()
    text = compiled.as_text()
    calls = [line.strip() for line in text.splitlines()
             if re.search(r"%latent_decode_attention[.\d]* = ", line)]
    assert len(calls) == 2 and all("tpu_custom_call" in c for c in calls)
    row = cache["k"].shape[-1]
    assert not re.findall(rf"= bf16\[\d+,{PAGE},{row}\]", text)
    one_block = 2 * LATENT_SLOTS * 512 * row
    assert compiled.memory_analysis().temp_size_in_bytes < one_block
    how = spec.layer_metric_spec("mla.decode_attn_time_share")
    model = {"num_slots": LATENT_SLOTS, "dims": {
        "n_heads": cfg.n_heads, "d_model": cfg.d_model, **{
            name: getattr(cfg, name) for name in (
                "kv_lora_rank", "qk_rope_head_dim", "qk_nope_head_dim",
                "v_head_dim")}}}
    match, program = (re.compile(mla._sized(how[k], model))
                      for k in ("match", "contains_op"))
    for call in calls:
        assert match.search(reduce._short(call)), call[:120]
    # And the decode program is one the reader picks.
    assert any(program.search(reduce._short(line.strip()))
               for line in text.splitlines() if " = " in line)


LFM2_SLOTS, LFM2_MAX_LEN, LFM2_CHUNK = 96, 2560, 256
LFM2_FILE = "lfm2-24b-a2b-serve.json"


def _lfm2_program(program, one, rows=1):
    """`decode_paged` or `prefill_chunk_paged` at the sizes of the cell
    `serve-lfm2-gen-closed` (LFM2-24B-A2B's first ten layers, 96 slots x
    2560, pages of 16, a pass of `rows` rows of 256), on shapes, with both
    accumulators in the tail as the engine passes them: (fn, donated, args,
    the cache's shapes, cfg)."""
    from ray_tpu.models.transformer import init_params
    from ray_tpu.serve import paged_kv

    cfg = dataclasses.replace(configs.get_config("lfm2-24b-a2b-l10"),
                              remat=False)
    slots, per_slot = LFM2_SLOTS, LFM2_MAX_LEN // PAGE

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one), tree)

    def struct(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    params = on_chip(jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg)))
    cache = on_chip(jax.eval_shape(lambda: paged_kv.init_paged_cache(
        cfg, slots, slots * per_slot + 1, PAGE, per_slot)))
    moe = on_chip(jax.eval_shape(
        lambda: paged_kv.init_routing_counters(cfg)))
    count = on_chip(jax.eval_shape(paged_kv.init_ssm_counters))
    pool = (cache["k"], cache["v"], cache["lengths"])
    if program == "decode_paged":
        fn = lambda p, t, k, v, ln, a, bt, tp, tk, tpp, key, moe, rec, c: (  # noqa: E731
            paged_kv.decode_paged(p, t, k, v, ln, a, bt, tp, tk, tpp, key,
                                  cfg, LFM2_MAX_LEN, None, moe, rec, c))
        args = (params, struct((slots,)), *pool, struct((slots,), jnp.bool_),
                cache["block_tables"], struct((slots,), jnp.float32),
                struct((slots,)), struct((slots,), jnp.float32),
                struct((2,), jnp.uint32), moe, cache["rec"], count)
        return fn, (2, 3, 12), args, cache, cfg
    fn = lambda p, t, n, s, o, k, v, ln, bt, moe, rec, c: (  # noqa: E731
        paged_kv.prefill_chunk_paged(p, t, n, s, o, k, v, ln, bt, cfg,
                                     LFM2_MAX_LEN, None, moe, rec, c))
    row = struct((rows,))
    args = (params, struct((rows, LFM2_CHUNK)), row, row, row, *pool,
            cache["block_tables"], moe, cache["rec"], count)
    return fn, (5, 6, 10), args, cache, cfg


# The cell's per-layer metrics that read a trace by an operation's name,
# and the step program in which each has to find one.
LFM2_TRACE_METRICS = {
    "decode_paged": ("moe.expert_time_share.lfm2",
                     "moe.dispatch_time_share.lfm2",
                     "moe.expert_roofline_share.lfm2",
                     "conv.mixer_time_share.lfm2",
                     "attn.decode_time_share.lfm2",
                     "sampler.time_share.lfm2"),
    "prefill_chunk_paged": ("moe.expert_time_share.lfm2",
                            "moe.dispatch_time_share.lfm2",
                            "conv.mixer_time_share.lfm2"),
}


@pytest.mark.parametrize("program,rows", [
    ("decode_paged", 1), ("prefill_chunk_paged", 1),
    ("prefill_chunk_paged", 2)], ids=["decode", "prefill-1", "prefill-2"])
def test_conv_expert_hybrid_steps_fit_and_read_their_stacks_in_place(
        v5e, program, rows, monkeypatch):
    """The cell `serve-lfm2-gen-closed`'s step programs at its own sizes
    (`_lfm2_program`): the arguments are the bytes its configuration file
    states, the pages and the conv pool come back in the buffers they came
    in, the expert stacks `[8, 64, ...]` are read in place by the grouped
    products of the three compiled expert layers (a conv layer in the
    scan's run, the attention layer, a conv layer in the tail) and by none
    in the two leading dense layers, and every trace metric the cell adds
    finds an operation of its pattern among the names the compiler
    prints."""
    import json

    _bench_on_path()
    import spec
    from xplane import reduce

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    fn, donated, args, cache, cfg = _lfm2_program(
        program, SingleDeviceSharding(v5e[0]), rows)
    assert sorted(cache["rec"]) == ["conv"]
    assert cache["rec"]["conv"].shape == (8, 96, 2, 2048)
    assert cache["k"].shape == (2, 96 * 160 + 1, PAGE, 512)
    compiled = jax.jit(fn, donate_argnums=donated).lower(*args).compile()
    memory = compiled.memory_analysis()
    with open(os.path.join(spec.BENCH, "configs", LFM2_FILE)) as f:
        stated = json.load(f)["compiled"]
    key = "decode" if program == "decode_paged" else f"prefill_{rows}"
    assert memory.argument_size_in_bytes == stated[key]["arguments_bytes"]
    assert memory.temp_size_in_bytes <= stated[key]["temporaries_bytes_at_most"]
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 12.1e9
    pools = sum(a.size * a.dtype.itemsize for a in (
        cache["k"], cache["v"], cache["rec"]["conv"]))
    assert memory.alias_size_in_bytes >= pools
    text = compiled.as_text()
    assert len(re.findall(r"%gmm[.\d]* = f32\[", text)) == 9
    assert len(re.findall(r"%paged_decode_attention[.\d]* = f32\[", text)) == (
        program == "decode_paged")
    for inner in ("2048,1536", "1536,2048"):
        assert not re.findall(
            rf"= bf16\[(?:1,|8,)?64,{inner}\]\S* (?:copy|fusion)\(", text)
        assert not re.findall(rf"= bf16\[512,{inner}\]\S* copy\(", text)
    short = [reduce._short(line.strip().removeprefix("ROOT "))
             for line in text.splitlines() if " = " in line]
    for metric in LFM2_TRACE_METRICS[program]:
        how = spec.layer_metric_spec(metric)
        assert any(re.search(how["match"], op) for op in short), metric
    step = ("decode.device_ms_per_step.lfm2" if program == "decode_paged"
            else "prefill.device_ms_per_chunk.lfm2")
    other = ("prefill.device_ms_per_chunk.lfm2" if program == "decode_paged"
             else "decode.device_ms_per_step.lfm2")
    assert any(re.search(spec.layer_metric_spec(step)["contains_op"], op)
               for op in short)
    assert not any(re.search(spec.layer_metric_spec(other)["contains_op"], op)
                   for op in short)


SOLAR_SLOTS, SOLAR_MAX_LEN, SOLAR_CHUNK = 128, 2560, 256
SOLAR_FILE = "solar-open2-250b-ep8-serve.json"


def _solar_program(program, one, rows=1):
    """`decode_paged` or `prefill_chunk_paged` at the sizes of the cell
    `serve-kda-reason-closed` (Solar-Open2-250B's first period as one chip
    of eight, 128 slots x 2560, pages of 16, a pass of `rows` rows of 256),
    on shapes, with both accumulators in the tail as the engine passes
    them: (fn, donated, args, the cache's shapes, cfg)."""
    from ray_tpu.models.transformer import init_params
    from ray_tpu.serve import paged_kv

    cfg = dataclasses.replace(configs.get_config("solar-open2-250b-ep8-l4"),
                              remat=False)
    slots, per_slot = SOLAR_SLOTS, SOLAR_MAX_LEN // PAGE

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one), tree)

    def struct(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    params = on_chip(jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg)))
    cache = on_chip(jax.eval_shape(lambda: paged_kv.init_paged_cache(
        cfg, slots, slots * per_slot + 1, PAGE, per_slot)))
    moe = on_chip(jax.eval_shape(
        lambda: paged_kv.init_routing_counters(cfg)))
    count = on_chip(jax.eval_shape(paged_kv.init_ssm_counters))
    pool = (cache["k"], cache["v"], cache["lengths"])
    if program == "decode_paged":
        fn = lambda p, t, k, v, ln, a, bt, tp, tk, tpp, key, moe, rec, c: (  # noqa: E731
            paged_kv.decode_paged(p, t, k, v, ln, a, bt, tp, tk, tpp, key,
                                  cfg, SOLAR_MAX_LEN, None, moe, rec, c))
        args = (params, struct((slots,)), *pool, struct((slots,), jnp.bool_),
                cache["block_tables"], struct((slots,), jnp.float32),
                struct((slots,)), struct((slots,), jnp.float32),
                struct((2,), jnp.uint32), moe, cache["rec"], count)
        return fn, (2, 3, 12), args, cache, cfg
    fn = lambda p, t, n, s, o, k, v, ln, bt, moe, rec, c: (  # noqa: E731
        paged_kv.prefill_chunk_paged(p, t, n, s, o, k, v, ln, bt, cfg,
                                     SOLAR_MAX_LEN, None, moe, rec, c))
    row = struct((rows,))
    args = (params, struct((rows, SOLAR_CHUNK)), row, row, row, *pool,
            cache["block_tables"], moe, cache["rec"], count)
    return fn, (5, 6, 10), args, cache, cfg


# The cell's per-layer metrics that read a trace by an operation's name,
# and the step program in which each has to find one.
SOLAR_TRACE_METRICS = {
    "decode_paged": ("moe.expert_time_share.solar",
                     "moe.dispatch_time_share.solar",
                     "moe.expert_roofline_share.solar",
                     "kda.update_time_share",
                     "kda.update_roofline_share",
                     "kda.mixer_time_share",
                     "attn.decode_time_share.solar",
                     "sampler.time_share.solar"),
    "prefill_chunk_paged": ("moe.expert_time_share.solar",
                            "moe.dispatch_time_share.solar",
                            "kda.scan_time_share",
                            "kda.scan_roofline_share",
                            "kda.mixer_time_share"),
}


@pytest.mark.parametrize("program,rows", [
    ("decode_paged", 1), ("prefill_chunk_paged", 1),
    ("prefill_chunk_paged", 2)], ids=["decode", "prefill-1", "prefill-2"])
def test_delta_rule_hybrid_steps_fit_and_update_their_pools_in_place(
        v5e, program, rows, monkeypatch):
    """The cell `serve-kda-reason-closed`'s step programs at its own sizes
    (`_solar_program`): the arguments are the bytes its configuration file
    states (and what the file says the chip stands for, within 2%), the
    pages, the matrix states and the convolution rows come back in the
    buffers they came in, the held experts' stacks `[4, 40, ...]` are read
    in place by the grouped products of the two compiled expert layers (the
    attention layer in the scan, a delta-rule layer in the tail), the
    decode step writes the pool of states in no fusion (the kernel does:
    `test_delta_rule_decode_passes_over_its_state_once`), and every trace
    metric the cell adds finds an operation of its pattern among the names
    the compiler prints."""
    import json

    _bench_on_path()
    import solar_flops
    import spec
    from xplane import reduce

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    fn, donated, args, cache, cfg = _solar_program(
        program, SingleDeviceSharding(v5e[0]), rows)
    assert sorted(cache["rec"]) == ["conv", "state"]
    assert cache["rec"]["state"].shape == (3, 128, 64, 128, 128)
    assert cache["rec"]["state"].dtype == jnp.float32
    assert cache["rec"]["conv"].shape == (3, 128, 3, 24576)
    assert cache["k"].shape == (1, 128 * 160 + 1, PAGE, 1024)
    compiled = jax.jit(fn, donate_argnums=donated).lower(*args).compile()
    memory = compiled.memory_analysis()
    with open(os.path.join(spec.BENCH, "configs", SOLAR_FILE)) as f:
        doc = json.load(f)
    stated = doc["compiled"]
    key = "decode" if program == "decode_paged" else f"prefill_{rows}"
    assert memory.argument_size_in_bytes == stated[key]["arguments_bytes"]
    assert memory.temp_size_in_bytes <= stated[key]["temporaries_bytes_at_most"]
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 10.2e9
    # What the file's `stands_for` counts: the weights by the benchmark's
    # arithmetic and the three pools, within 2% of the compiler's arguments.
    dims = {field: doc[k] for k, field in spec._published(doc).items()
            if field != "tie_embeddings"}
    pools = sum(a.size * a.dtype.itemsize for a in (
        cache["k"], cache["v"], cache["rec"]["state"], cache["rec"]["conv"]))
    counted = 2 * solar_flops.params_held(
        dict(dims, layer_pattern=tuple(dims["layer_pattern"])), 4) + pools
    assert abs(counted / memory.argument_size_in_bytes - 1) < 0.02
    assert memory.alias_size_in_bytes >= pools
    text = compiled.as_text()
    assert len(re.findall(r"%gmm[.\d]* = f32\[", text)) == 6
    assert len(re.findall(r"%paged_decode_attention[.\d]* = f32\[", text)) == (
        program == "decode_paged")
    for inner in ("4096,1280", "1280,4096"):
        assert not re.findall(
            rf"= bf16\[(?:1,|4,)?40,{inner}\]\S* (?:copy|fusion)\(", text)
        assert not re.findall(rf"= bf16\[160,{inner}\]\S* copy\(", text)
    # No copy of the pool of states, and in the decode step no fusion that
    # writes it.
    assert not re.findall(r"= f32\[3,128,64,128,128\]\S* copy\(", text)
    if program == "decode_paged":
        assert not re.findall(
            r"= f32\[3,128,64,128,128\]\S* fusion\(", text)
    short = [reduce._short(line.strip().removeprefix("ROOT "))
             for line in text.splitlines() if " = " in line]
    for metric in SOLAR_TRACE_METRICS[program]:
        how = spec.layer_metric_spec(metric)
        assert any(re.search(how["match"], op) for op in short), metric
    step = ("decode.device_ms_per_step.solar" if program == "decode_paged"
            else "prefill.device_ms_per_chunk.solar")
    other = ("prefill.device_ms_per_chunk.solar" if program == "decode_paged"
             else "decode.device_ms_per_step.solar")
    assert any(re.search(spec.layer_metric_spec(step)["contains_op"], op)
               for op in short)
    assert not any(re.search(spec.layer_metric_spec(other)["contains_op"], op)
                   for op in short)
    for metric in ("kda.update_roofline_share", "kda.scan_roofline_share"):
        how = spec.layer_metric_spec(metric)
        own = step.startswith("decode") == (metric == "kda.update_roofline_share")
        assert any(re.search(how["contains_op"], op) for op in short) == own


SOLAR_POOL = "f32[3,128,64,128,128]"


def test_delta_rule_decode_passes_over_its_state_once(v5e, monkeypatch):
    """Solar's decode program compiled for the described v5e at the cell's
    sizes holds `ops.kda_update`'s custom call once a compiled delta-rule
    layer (once: the tail's three; the run before the one attention layer
    is empty and compiled away) and nothing else that takes the pool of
    matrix states: no fusion
    `f32[128,64,128]` that reads a layer's decayed states for both sums
    beside one that reads them again to write them (the parent's two: 7.4
    ms of a 19.9 ms step, 4.83 GB moved where 3.22 must). The pool is
    still aliased and not copied. Each call's line is one that
    `kda.update_time_share`, `kda.update_roofline_share` and
    `kda.mixer_time_share` match, in a program their `contains_op` picks:
    in a trace an operation goes by its own name and its FIRST result's
    shape (`bench/xplane/reduce.py`), so the pool is the kernel's first
    result and `o` its second. The prefill pass does not reach the kernel:
    what takes the pool there is the parent's count."""
    _bench_on_path()
    import spec
    from xplane import reduce

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    one = SingleDeviceSharding(v5e[0])
    fn, donated, args, cache, _ = _solar_program("decode_paged", one)
    compiled = jax.jit(fn, donate_argnums=donated).lower(*args).compile()
    text = compiled.as_text()
    passes = _takes_the_state_pool(text, SOLAR_POOL)
    assert len(passes) == 1, [own for own, _ in passes]
    for own, line in passes:
        assert re.fullmatch(r"%kda_update[.\d]*", own), own
        assert "tpu_custom_call" in line
        assert f" = ({SOLAR_POOL}" in line                 # the first result
    assert not re.findall(
        r"%multiply_reduce_fusion[.\d]* = \(?f32\[128,64,128\]", text)
    pools = sum(a.size * a.dtype.itemsize for a in (
        cache["k"], cache["v"], *cache["rec"].values()))
    assert compiled.memory_analysis().alias_size_in_bytes >= pools
    assert not re.findall(r"= f32\[3,128,64,128,128\]\S* copy\(", text)
    short = [reduce._short(line.strip().removeprefix("ROOT "))
             for line in text.splitlines() if " = " in line]
    for metric in ("kda.update_time_share", "kda.update_roofline_share",
                   "kda.mixer_time_share"):
        how = spec.layer_metric_spec(metric)
        match = re.compile(how["match"])
        for _, line in passes:
            assert match.search(reduce._short(line)), (metric, line[:120])
        if "contains_op" in how:  # the decode program is one the reader picks
            assert any(re.search(how["contains_op"], op) for op in short)
    # Of what runs on the device (fusions and custom calls), the update's
    # pattern finds the kernel's calls and nothing else.
    update = re.compile(
        spec.layer_metric_spec("kda.update_time_share")["match"])
    ran = [reduce._short(line.strip().removeprefix("ROOT "))
           for line in text.splitlines()
           if re.search(r" (?:fusion|custom-call)\(", line)]
    assert sorted(op for op in ran if update.search(op)) == sorted(
        reduce._short(line) for _, line in passes)
    fn, donated, args, _, _ = _solar_program("prefill_chunk_paged", one)
    text = jax.jit(fn, donate_argnums=donated).lower(*args).compile().as_text()
    assert "kda_update" not in text
    assert _takes_the_state_pool(text, SOLAR_POOL)


SDAR_SLOTS, SDAR_MAX_LEN, SDAR_CHUNK = 96, 2560, 256
SDAR_FILE = "sdar-30b-a3b-serve.json"


def _sdar_program(program, one, rows=1):
    """`block_pass_paged` (sampled, or `block_pass_greedy`) or
    `prefill_chunk_paged` at the sizes of the cell `serve-sdar-blockgen`
    (SDAR-30B-A3B-Chat's first six layers, 96 slots x 2560, pages of 16,
    blocks of 4, a prefill pass of `rows` rows of 256), on shapes, with the
    routing accumulator in the tail as the engine passes it: (fn, donated,
    args, the cache's shapes, cfg)."""
    from ray_tpu.models.transformer import init_params
    from ray_tpu.serve import paged_kv

    cfg = dataclasses.replace(configs.get_config("sdar-30b-a3b-l6"),
                              remat=False)
    slots, per_slot = SDAR_SLOTS, SDAR_MAX_LEN // PAGE

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one), tree)

    def struct(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    params = on_chip(jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg)))
    cache = on_chip(jax.eval_shape(lambda: paged_kv.init_paged_cache(
        cfg, slots, slots * per_slot + 1, PAGE, per_slot)))
    moe = on_chip(jax.eval_shape(
        lambda: paged_kv.init_routing_counters(cfg)))
    pool = (cache["k"], cache["v"], cache["lengths"])
    if program.startswith("block_pass"):
        state = on_chip(jax.eval_shape(
            lambda: paged_kv.init_block_state(cfg, slots)))
        sampling = ((None,) * 4 if program == "block_pass_greedy" else (
            struct((slots,), jnp.float32), struct((slots,)),
            struct((slots,), jnp.float32), struct((2,), jnp.uint32)))
        fn = lambda p, st, k, v, ln, a, bt, tp, tk, tpp, key, moe: (  # noqa: E731
            paged_kv.block_pass_paged(p, st, k, v, ln, a, bt, tp, tk, tpp,
                                      key, cfg, SDAR_MAX_LEN, None, moe))
        args = (params, state, *pool, struct((slots,), jnp.bool_),
                cache["block_tables"], *sampling, moe)
        return fn, (2, 3), args, cache, cfg
    fn = lambda p, t, n, s, o, k, v, ln, bt, moe: (  # noqa: E731
        paged_kv.prefill_chunk_paged(p, t, n, s, o, k, v, ln, bt, cfg,
                                     SDAR_MAX_LEN, None, moe))
    row = struct((rows,))
    args = (params, struct((rows, SDAR_CHUNK)), row, row, row, *pool,
            cache["block_tables"], moe)
    return fn, (5, 6), args, cache, cfg


# The cell's per-layer metrics that read a trace by an operation's name,
# and the step program in which each has to find one.
SDAR_TRACE_METRICS = {
    "block_pass": ("moe.expert_time_share.sdar",
                   "moe.dispatch_time_share.sdar",
                   "moe.expert_roofline_share.sdar",
                   "attn.decode_time_share.sdar",
                   "sampler.time_share.sdar"),
    "block_pass_greedy": ("moe.expert_time_share.sdar",
                          "attn.decode_time_share.sdar"),
    "prefill_chunk_paged": ("moe.expert_time_share.sdar",
                            "moe.dispatch_time_share.sdar"),
}


@pytest.mark.parametrize("program,rows", [
    ("block_pass", 1), ("block_pass_greedy", 1), ("prefill_chunk_paged", 1),
    ("prefill_chunk_paged", 2)],
    ids=["block-pass", "block-pass-greedy", "prefill-1", "prefill-2"])
def test_block_diffusion_steps_fit_and_read_their_stacks_in_place(
        v5e, program, rows, monkeypatch):
    """The cell `serve-sdar-blockgen`'s step programs at its own sizes
    (`_sdar_program`): the arguments are the bytes its configuration file
    states, the pages come back in the buffers they came in, a block pass
    runs the decode-attention kernel ONCE (a block's 4 positions folded
    into its groups of queries: 4 groups of 32 rows a slot, which the
    kernel's queries, results and page buffers fit beside in 16 MiB of
    scoped VMEM at 96 slots) and a prefill pass does not, the expert
    stacks `[6, 128, ...]` are read in place by the scanned layer's three
    grouped products, and every trace metric the cell adds finds an
    operation of its pattern among the names the compiler prints."""
    import json

    _bench_on_path()
    import spec
    from xplane import reduce

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    fn, donated, args, cache, cfg = _sdar_program(
        program, SingleDeviceSharding(v5e[0]), rows)
    assert cache["k"].shape == (6, 96 * 160 + 1, PAGE, 512)
    compiled = jax.jit(fn, donate_argnums=donated).lower(*args).compile()
    memory = compiled.memory_analysis()
    with open(os.path.join(spec.BENCH, "configs", SDAR_FILE)) as f:
        stated = json.load(f)["compiled"]
    key = program if program.startswith("block_pass") else f"prefill_{rows}"
    assert memory.argument_size_in_bytes == stated[key]["arguments_bytes"]
    assert memory.temp_size_in_bytes <= stated[key]["temporaries_bytes_at_most"]
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 12.1e9
    pools = sum(a.size * a.dtype.itemsize for a in (cache["k"], cache["v"]))
    assert memory.alias_size_in_bytes >= pools
    text = compiled.as_text()
    assert len(re.findall(r"%gmm[.\d]* = f32\[", text)) == 3
    kernel = re.findall(r"%paged_decode_attention[.\d]* = f32\[([\d,]+)\]",
                        text)
    assert kernel == (["96,4,32,128"] if program.startswith("block_pass")
                      else [])
    for inner in ("2048,768", "768,2048"):
        assert not re.findall(
            rf"= bf16\[(?:1,|6,)?128,{inner}\]\S* (?:copy|fusion)\(", text)
        assert not re.findall(rf"= bf16\[768,{inner}\]\S* copy\(", text)
    short = [reduce._short(line.strip().removeprefix("ROOT "))
             for line in text.splitlines() if " = " in line]
    for metric in SDAR_TRACE_METRICS[program]:
        how = spec.layer_metric_spec(metric)
        assert any(re.search(how["match"], op) for op in short), metric
    if program == "block_pass":  # each part of the sampler's pattern
        for op in ("%fusion.1 f32[192,64]", "%select_reduce_fusion.7 f32[192]",
                   "%iota_reduce_fusion bf16[192]", "%xor.3 u32[192,151936]"):
            assert re.search(spec.layer_metric_spec(
                "sampler.time_share.sdar")["match"], op)
        # What the sampler runs now: two bisections over the 16 bits of
        # the bfloat16 logits, each a masked sum a trip that works the
        # image and the weights out of the logits again: top-k's, whose
        # trip count is data (none unless a slot asks), and top-p's. So
        # the head's product is the one array of `[192, 151936]` any
        # fusion writes; no `lax.top_k`, so nothing of `[192, MAX_TOP_K]`
        # anywhere; no conditional for the pool or the logits to be
        # handed to; and the temporaries inside what the configuration
        # states (above), under one bfloat16 copy of the logits.
        ran = [reduce._short(line.strip().removeprefix("ROOT "))
               for line in text.splitlines()
               if re.search(r" (?:fusion|custom-call)\(", line)]
        assert " ".join(ran).count("%select_reduce_fusion") >= 2
        assert "[192,64]" not in text
        assert 'custom_call_target="TopK"' not in text
        assert not [line for line in text.splitlines()
                    if " sort(" in line and "151936]" in line]
        assert not re.findall(r" conditional\(", text)
        written = [m for line in text.splitlines() if " fusion(" in line
                   for m in re.findall(r"(\w+)\[192,151936\]",
                                       line.split(" fusion(")[0])]
        assert written == ["bf16"]
        assert memory.temp_size_in_bytes < 2 * 192 * 151936
    step = ("decode.device_ms_per_step.sdar"
            if program.startswith("block_pass")
            else "prefill.device_ms_per_chunk.sdar")
    other = ("prefill.device_ms_per_chunk.sdar"
             if program.startswith("block_pass")
             else "decode.device_ms_per_step.sdar")
    assert any(re.search(spec.layer_metric_spec(step)["contains_op"], op)
               for op in short)
    assert not any(re.search(spec.layer_metric_spec(other)["contains_op"], op)
                   for op in short)


SWA_SLOTS, SWA_MAX_LEN, SWA_CHUNK = 32, 16384, 512
SWA_FILE = "smallthinker-21b-a3b-serve.json"


def _swa_program(program, one):
    """`decode_paged` (sampled, or `decode_greedy`) or `prefill_chunk_paged`
    at the sizes of the cell `serve-swa-longdoc` (SmallThinker-21BA3B's
    first eight layers, 32 slots x 16,384, pages of 16, a prefill pass of
    one row of 512), on shapes, with the routing accumulator and the window
    layers' ring pool in the tail as the engine passes them: (fn, donated,
    args, the cache's shapes, cfg)."""
    from ray_tpu.models.transformer import init_params
    from ray_tpu.serve import paged_kv

    cfg = dataclasses.replace(configs.get_config("smallthinker-21b-a3b-l8"),
                              remat=False)
    slots, per_slot = SWA_SLOTS, SWA_MAX_LEN // PAGE

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one), tree)

    def struct(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    params = on_chip(jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg)))
    cache = on_chip(jax.eval_shape(lambda: paged_kv.init_paged_cache(
        cfg, slots, slots * per_slot + 1, PAGE, per_slot,
        prefill_chunk=SWA_CHUNK)))
    moe = on_chip(jax.eval_shape(
        lambda: paged_kv.init_routing_counters(cfg)))
    pool = (cache["k"], cache["v"], cache["lengths"])
    if program.startswith("decode"):
        sampling = ((None,) * 4 if program == "decode_greedy" else (
            struct((slots,), jnp.float32), struct((slots,)),
            struct((slots,), jnp.float32), struct((2,), jnp.uint32)))
        fn = lambda p, t, k, v, ln, a, bt, tp, tk, tpp, key, moe, ring: (  # noqa: E731
            paged_kv.decode_paged(p, t, k, v, ln, a, bt, tp, tk, tpp, key,
                                  cfg, SWA_MAX_LEN, None, moe, ring=ring))
        args = (params, struct((slots,)), *pool, struct((slots,), jnp.bool_),
                cache["block_tables"], *sampling, moe, cache["ring"])
        return fn, (2, 3, 12), args, cache, cfg
    fn = lambda p, t, n, s, o, k, v, ln, bt, moe, ring: (  # noqa: E731
        paged_kv.prefill_chunk_paged(p, t, n, s, o, k, v, ln, bt, cfg,
                                     SWA_MAX_LEN, None, moe, ring=ring))
    row = struct((1,))
    args = (params, struct((1, SWA_CHUNK)), row, row, row, *pool,
            cache["block_tables"], moe, cache["ring"])
    return fn, (5, 6, 10), args, cache, cfg


# The cell's per-layer metrics that read a trace by an operation's name,
# and the step program in which each has to find one.
SWA_TRACE_METRICS = {
    "decode": ("moe.expert_time_share.swa", "moe.dispatch_time_share.swa",
               "moe.expert_roofline_share.swa",
               "attn.window_decode_time_share",
               "attn.window_decode_roofline_share",
               "attn.full_decode_time_share.swa", "sampler.time_share.swa"),
    "decode_greedy": ("moe.expert_time_share.swa",
                      "attn.window_decode_time_share",
                      "attn.full_decode_time_share.swa"),
    "prefill_chunk_paged": ("moe.expert_time_share.swa",
                            "moe.dispatch_time_share.swa",
                            "attn.prefill_time_share.swa"),
}


@pytest.mark.parametrize(
    "program", ["decode", "decode_greedy", "prefill_chunk_paged"])
def test_window_model_steps_fit_and_keep_both_pools_in_place(
        v5e, program, monkeypatch):
    """The cell `serve-swa-longdoc`'s step programs at its own sizes
    (`_swa_program`): the compiler takes 32 slots x 16,384; the arguments
    are the bytes its configuration file states (the full layers' pages
    `[2, 32 x 1024 + 1, ..]` and the window layers' rings `[6, 32 x 288 + 1,
    ..]` where one table for all eight layers would be 8.59 GB); both pools
    come back in the buffers they came in; a decode step runs the full
    layers' kernel once and the ring's kernel three times in the scanned
    period (full, window, window, window) under its own name, and a
    prefill pass runs neither; the expert stacks `[8, 64, ...]` are read in
    place; and every trace metric the cell adds finds an operation of its
    pattern among the names the compiler prints.

    A prefill pass walks blocks of 512 rows of a table or a ring with a
    running softmax (`paged_kv._paged_attention`, PR 71): one loop a
    walking layer of the period, the full layer's over its table of 1,024
    pages and each window layer's over its ring of 288, no float32 result
    as wide as a table or a ring anywhere, and the temporaries a fiftieth
    of the plain form's 948 MB. Of `attn.prefill_time_share.swa`'s
    alternatives only `f32[4,7,512]` still finds an operation (the running
    maximum and sum a block): the gathers, the mask, the scores and the
    weighted sum it names by a width of 16,384 or 4,608 or as `[4,128,7,
    512]` are no longer made (a block's are `bf16[32,16,512]`,
    `pred[512,512]`, `f32[1,4,7,512,512]`, `f32[1,4,7,512,128]`), so the
    entry reads a part of the attention (`PERF.md` section 7)."""
    import json

    _bench_on_path()
    import spec
    from xplane import reduce

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    fn, donated, args, cache, cfg = _swa_program(
        program, SingleDeviceSharding(v5e[0]))
    assert cache["k"].shape == (2, 32 * 1024 + 1, PAGE, 512)
    assert cache["ring"]["k"].shape == (6, 32 * 288 + 1, PAGE, 512)
    compiled = jax.jit(fn, donate_argnums=donated).lower(*args).compile()
    memory = compiled.memory_analysis()
    with open(os.path.join(spec.BENCH, "configs", SWA_FILE)) as f:
        stated = json.load(f)["compiled"]
    key = program if program.startswith("decode") else "prefill_1"
    assert memory.argument_size_in_bytes == stated[key]["arguments_bytes"]
    assert memory.temp_size_in_bytes <= stated[key]["temporaries_bytes_at_most"]
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 13.0e9
    pools = sum(a.size * a.dtype.itemsize
                for a in (cache["k"], cache["v"], *cache["ring"].values()))
    assert round(pools / 1e9, 2) == 3.96
    assert memory.alias_size_in_bytes >= pools
    text = compiled.as_text()
    assert len(re.findall(r"%gmm[.\d]* = f32\[", text)) == 12
    kernels = {name: re.findall(rf"%{name}[.\d]* = f32\[([\d,]+)\]", text)
               for name in ("paged_decode_attention",
                            "window_decode_attention")}
    if program.startswith("decode"):
        assert kernels == {"paged_decode_attention": ["32,4,8,128"],
                           "window_decode_attention": ["32,4,8,128"] * 3}
    else:
        assert kernels == {"paged_decode_attention": [],
                           "window_decode_attention": []}
        assert memory.temp_size_in_bytes < 0.2e9
        assert not re.findall(r"f32\[[\d,]*,(?:16384|4608)\]", text)
        walks = [line for line in text.splitlines()
                 if " while(" in line and "f32[1,4,7,512,128]" in line]
        assert sorted(re.search(r"s32\[1,(\d+)\]", line).group(1)
                      for line in walks) == ["1024", "288", "288", "288"]
        how = spec.layer_metric_spec("attn.prefill_time_share.swa")
        found = {op.split(" ", 1)[1] for op in (
            reduce._short(line.strip().removeprefix("ROOT "))
            for line in text.splitlines() if " = " in line)
            if re.search(how["match"], op)}
        assert found == {"f32[4,7,512]"}
    for inner in ("2560,768", "768,2560"):
        assert not re.findall(
            rf"= bf16\[(?:1,|8,)?64,{inner}\]\S* (?:copy|fusion)\(", text)
        assert not re.findall(rf"= bf16\[512,{inner}\]\S* copy\(", text)
    assert not re.findall(r" conditional\(", text)
    short = [reduce._short(line.strip().removeprefix("ROOT "))
             for line in text.splitlines() if " = " in line]
    for metric in SWA_TRACE_METRICS[program]:
        how = spec.layer_metric_spec(metric)
        assert any(re.search(how["match"], op) for op in short), metric
    step, other = ("decode.device_ms_per_step.swa",
                   "prefill.device_ms_per_chunk.swa")
    if not program.startswith("decode"):
        step, other = other, step
    assert any(re.search(spec.layer_metric_spec(step)["contains_op"], op)
               for op in short)
    assert not any(re.search(spec.layer_metric_spec(other)["contains_op"], op)
                   for op in short)
