"""ZeRO over a model that scans its layers: ``dp`` never lands on the layer
axis, and the compiled step gathers one layer where it is used.

Shapes that show it: ``num_layers`` divisible by ``dp`` (tier-1's 2-layer
models and the chip cell's 2-layer rehearsal never put ``dp`` on the layer
axis, so the whole-stack gather inside every scan iteration was only ever
seen on the chip: PERF.md, PR 27)."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import deepspeed_tpu as ds
from deepspeed_tpu.models.gpt import GPT, GPTConfig, lm_loss_fn
from deepspeed_tpu.parallel import mesh as mesh_lib
from deepspeed_tpu.runtime.sharding import ShardingRules, path_str

MICRO, GAS, SEQ, VOCAB = 3, 2, 16, 512
# (num_layers, engine mesh): dp=4 nested beside tp=2, and dp over all 8
SHAPES = [(4, {"tp": 2}), (8, {})]


def _model(layers, dtype):
    return GPT(GPTConfig(
        vocab_size=VOCAB, max_seq_len=SEQ, num_layers=layers, num_heads=2,
        d_model=64, d_ff=256, rotary=True, rotary_pct=0.25,
        parallel_residual=True, tie_embeddings=False,
        remat_policy="dots_no_batch", dtype=dtype))


ADAMW = {"type": "AdamW", "params": {"lr": 1e-3}}


def _engine(stage, layers, mesh, bf16=True, optimizer=ADAMW):
    model = _model(layers, jnp.bfloat16 if bf16 else jnp.float32)
    params = model.init(jax.random.PRNGKey(0),
                        np.zeros((1, 8), np.int32))["params"]
    engine, *_ = ds.initialize(
        model=model, model_parameters=params, loss_fn=lm_loss_fn,
        config={"train_micro_batch_size_per_gpu": MICRO,
                "gradient_accumulation_steps": GAS,
                "bf16": {"enabled": bf16}, "mesh": mesh,
                "zero_optimization": {"stage": stage,
                                      "param_persistence_threshold": 0},
                "optimizer": optimizer,
                "steps_per_print": 10 ** 9})
    return engine


def _micro_batches(engine, seed):
    rng = np.random.default_rng(seed)
    rows = MICRO * engine.dp_world_size
    return iter([{"input_ids": rng.integers(0, VOCAB, (rows, SEQ))
                  .astype(np.int32)} for _ in range(GAS)])


def _specs(engine, kind):
    tree = getattr(engine.rules, kind + "_specs")(engine.state["master"])
    flat, _ = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda x: isinstance(x, P))
    return {path_str(p): s for p, s in flat}


# ------------------------------------------------------------------- specs
@pytest.mark.parametrize("layers,mesh", SHAPES, ids=["dp4xtp2", "dp8"])
@pytest.mark.parametrize("stage", [1, 2, 3])
def test_dp_never_takes_the_scanned_axis(stage, layers, mesh):
    engine = _engine(stage, layers, mesh)
    dp, tp = engine.mesh.shape["dp"], engine.mesh.shape["tp"]
    assert layers % dp == 0        # the old rule WOULD have taken axis 0
    param, grad, master = (_specs(engine, k)
                           for k in ("param", "grad", "master"))
    blocks = [p for p in master if p.startswith("blocks/")]
    assert len(blocks) == 12
    for p in blocks:
        for spec in (param[p], grad[p], master[p]):
            assert spec[0] is None, (p, spec)
        # the master is dp-sharded from stage 1, inside the layer (a bias
        # whose one inner dim tp took has no free dim left)
        assert "dp" in tuple(master[p]) or None not in tuple(master[p])[1:]
        # and whichever of the other two the stage shards lies where it does:
        # the cast and the update move nothing between chips
        assert grad[p] == (master[p] if stage >= 2 else param[p])
        assert param[p] == (master[p] if stage >= 3
                            else P(*[a if a != "dp" else None
                                     for a in master[p]]))
    t = "tp" if tp > 1 else None
    # tp keeps the dim it had; dp takes the first free one inside the layer
    assert master["blocks/attn/qkv/kernel"] == P(None, "dp", t)
    assert master["blocks/mlp/down_proj/kernel"] == (
        P(None, "tp", "dp") if tp > 1 else P(None, "dp", None))
    assert master["blocks/ln_1/scale"] == P(None, "dp")
    # leaves outside the scan keep the placement they had
    assert master["lm_head/kernel"] == P("dp", t)
    assert master["ln_f/scale"] == P("dp")
    if stage >= 3:
        assert param["wte/embedding"] == P(("tp", "dp") if tp > 1 else "dp",
                                           None)
        plan = engine.zero3_gather_plan()
        assert plan["stacked_on_layer_axis"] == 0
        # column-parallel biases have one inner dim and tp took it
        assert plan["stacked_inside_layer"] == (10 if tp > 1 else 12)
        assert plan["outside_scan"] == 3    # lm_head and the final norm's two
        assert plan["layers"] == layers
        # bf16 bytes of one layer's gathered leaves, the (dp-1)/dp a chip
        # does not hold; tp-sharded kernels stay 1/tp
        d, f = 64, 256
        kernels = (d * 3 * d + d * d + d * f + f * d) // tp
        rest = (0 if tp > 1 else 3 * d + f) + d + d + 4 * d  # biases, norms
        assert plan["layer_gather_bytes"] == \
            (kernels + rest) * 2 * (dp - 1) // dp
        # a micro-step: every layer in the forward and in the backward loop,
        # lm_head and the final norm in each direction
        outside = d * VOCAB // tp + 2 * d
        assert plan["gather_bytes_per_micro_step"] == \
            2 * (layers * (kernels + rest) + outside) * 2 * (dp - 1) // dp
    else:
        assert engine.zero3_gather_plan() is None


def test_persisted_and_expert_leaves_keep_their_placement():
    mesh = Mesh(np.asarray(jax.devices()).reshape(4, 1, 2, 1, 1),
                mesh_lib.MESH_AXES)
    rules = ShardingRules(mesh, zero_stage=3,
                          param_persistence_threshold=100_000)
    # [L, d] norm scales and biases sit under the threshold: replicated
    assert rules.param_spec("blocks/ln_1/scale", (4, 64)) == P(None, None)
    assert rules.master_spec("blocks/ln_1/scale", (4, 64)) == P(None, "dp")
    # a scanned expert bank [L, E, d, f]: ep keeps the expert dim, dp goes
    # further in, never onto L
    bank = "blocks/moe/Experts_0/experts/inner/up_proj/kernel"
    for fn in (rules.param_spec, rules.grad_spec, rules.master_spec):
        assert fn(bank, (4, 4, 64, 256), 1) == P(None, "ep", "dp", None)
    # unscanned layers are not stacked: the rule they had
    assert rules.master_spec("block_0/mlp/up_proj/kernel", (64, 256)) == \
        P("dp", None)
    assert rules.master_spec("block_0/moe/Experts_0/experts/inner/up_proj/"
                             "kernel", (4, 64, 256)) == P("ep", "dp", None)


# ----------------------------------------------------------------- program
_COLLECTIVE = re.compile(
    r"= (\(?[a-z0-9]+\[.*?) (all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-start)?\(")
_CALLED = re.compile(r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)")


def _computations(hlo):
    comps, cur = {}, None
    for line in hlo.splitlines():
        m = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$", line)
        if m:
            cur = comps.setdefault(m.group(1), [])
        elif line.startswith("}"):
            cur = None
        elif cur is not None:
            cur.append(line)
    return comps


def _layer_loop_collectives(hlo):
    """``(kind, shapes)`` of every collective inside a while body that
    iterates over the layers: a loop nested in the micro-step loop whose
    ops carry a ``blocks/`` scope. Shapes are the result's dims, a list per
    tuple element."""
    comps = _computations(hlo)

    def reach(name, seen):
        if name in seen or name not in comps:
            return seen
        seen.add(name)
        for line in comps[name]:
            for m in _CALLED.finditer(line):
                reach(m.group(1), seen)
        return seen

    bodies = {m.group(1) for lines in comps.values() for line in lines
              if " while(" in line
              for m in [re.search(r"body=%?([\w.\-]+)", line)] if m}
    inner = [b for b in bodies
             if not (reach(b, set()) - {b}) & bodies     # holds no other loop
             and any("/blocks/" in line for c in reach(b, set())
                     for line in comps[c])]
    assert len(inner) >= 2, "a forward and a backward loop over the layers"
    found = []
    for b in inner:
        for c in reach(b, set()):
            for line in comps[c]:
                m = _COLLECTIVE.search(line)
                if m:
                    shapes = [tuple(int(d) for d in dims.split(",") if d)
                              for dims in re.findall(r"\[([\d,]*)\]",
                                                     m.group(1))]
                    found.append((m.group(2), shapes))
    return found


def _compiled_train_step(engine, devices=None):
    """The engine's own train step, compiled from shapes. ``devices``: as
    many DESCRIBED devices as the engine's mesh has (no chip attached); the
    engine's shardings are moved onto them for the compile."""
    rows = MICRO * engine.dp_world_size
    if devices is None:
        mesh = engine.mesh
    else:
        mesh = Mesh(np.asarray(devices).reshape(engine.mesh.devices.shape),
                    engine.mesh.axis_names)
        move = lambda tree: jax.tree.map(
            lambda s: NamedSharding(mesh, s.spec), tree,
            is_leaf=lambda x: isinstance(x, NamedSharding))
        for name in ("master_shardings", "param_shardings", "grad_shardings",
                     "opt_shardings", "_state_shardings"):
            setattr(engine, name, move(getattr(engine, name)))
        engine.mesh = engine.rules.mesh = mesh
        mesh_lib.set_global_mesh(mesh, mesh_lib.get_global_mesh_shape())
    state = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=NamedSharding(mesh, s.spec)),
        engine.state, engine._state_shardings)
    batches = {"input_ids": jax.ShapeDtypeStruct(
        (GAS, rows, SEQ), jnp.int32,
        sharding=NamedSharding(mesh, P(None, "dp")))}
    return engine._build_train_jit().lower(state, batches, {}).compile()


def _assert_one_layer_gathered(hlo, layers, dp, kernel_shapes):
    found = _layer_loop_collectives(hlo)
    rows = MICRO * dp               # 12 or 24: no model dim has this size
    for kind, shapes in found:
        for shape in shapes:
            assert not (len(shape) >= 2 and shape[0] == layers), \
                f"{kind} of the whole stack in the layer loop: {shape}"
            assert rows not in shape[:1], \
                f"{kind} carries the global batch in the layer loop: {shape}"
    gathered = {s for kind, shapes in found if kind == "all-gather"
                for s in shapes}
    for shape in kernel_shapes:
        assert (1,) + shape in gathered or shape in gathered, \
            (shape, sorted(gathered))


@pytest.mark.parametrize("layers,mesh", SHAPES, ids=["dp4xtp2", "dp8"])
def test_compiled_step_gathers_one_layer_in_the_loop(layers, mesh):
    engine = _engine(3, layers, mesh)
    dp, tp = engine.mesh.shape["dp"], engine.mesh.shape["tp"]
    hlo = _compiled_train_step(engine).as_text()
    _assert_one_layer_gathered(
        hlo, layers, dp,
        [(64, 192 // tp), (64 // tp, 64), (64, 256 // tp), (256 // tp, 64)])


@pytest.fixture
def v5e_2x2():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2").devices
    except Exception as e:      # no libtpu here: skipped by name
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def test_compiled_for_v5e_2x2_gathers_one_layer_in_the_loop(v5e_2x2,
                                                             monkeypatch):
    # the engine meshes every device it finds: hand it four of the eight
    four = jax.devices()[:4]
    monkeypatch.setattr(jax, "devices", lambda *a, **k: four)
    try:
        engine = _engine(3, 4, {})
        assert engine.mesh.shape["dp"] == 4
        # an ahead-of-time TPU executable cannot be read back from the
        # persistent cache without a chip
        cache = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        try:
            compiled = _compiled_train_step(engine, v5e_2x2)
        finally:
            jax.config.update("jax_enable_compilation_cache", cache)
        _assert_one_layer_gathered(
            compiled.as_text(), 4, 4,
            [(64, 192), (64, 64), (64, 256), (256, 64)])
        assert compiled.memory_analysis() is not None
    finally:
        mesh_lib.reset_global_mesh()


def test_live_rows_decode_read_compiles_for_v5e_at_serve_batchs_shape(
        v5e_2x2, monkeypatch):
    """The serving path's kernel, here because ONE file of a test run may
    load the TPU's compiler (the fixture above): Mosaic takes
    ``live_decode_attention`` at 8 lanes x 2048 rows x 32 heads of 128 in
    bf16 inside a layer loop that carries the two 2.1 GB leaves and writes
    a token into them first, and the program holds no copy of a layer's
    rows (temporaries of kilobytes beside 4.29 GB of aliased arena)."""
    compiled = _live_read_in_a_layer_loop(v5e_2x2, monkeypatch, L=16, b=8,
                                          rows=(2048,))
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') \
        == 1
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == 2 * 16 * 8 * 2048 * 32 * 128 * 2
    assert mem.temp_size_in_bytes < 4 * 2 ** 20, mem


def _live_read_in_a_layer_loop(devices, monkeypatch, L, b, rows):
    """A layer loop of ``L`` that carries a (key, value) pair of
    ``[L, b, n, 32, 128]`` bf16 leaves for every ``n`` of ``rows``, writes a
    token into each and reads the live rows of all of them under one
    softmax, compiled for the first of ``devices``."""
    from jax.sharding import SingleDeviceSharding
    from deepspeed_tpu.models.gpt import _kv_write
    from deepspeed_tpu.ops.pallas import decode_attention as da
    monkeypatch.setattr(da, "interpret_mode", lambda: False)
    h, d = 32, 128

    def step(x, leaves, cur):
        def body(c, layer):
            x, leaves = c
            q = x.reshape(b, 1, h, d)
            leaves = [_kv_write(leaf, q, cur % leaf.shape[2], layer)
                      for leaf in leaves]
            o = da.live_decode_attention(
                q, [(k, v, cur % k.shape[2] + 1)
                    for k, v in zip(leaves[::2], leaves[1::2])], layer)
            return (o.reshape(b, h * d), leaves), None
        return jax.lax.scan(body, (x, leaves),
                            jnp.arange(L, dtype=jnp.int32))[0]

    one = SingleDeviceSharding(devices[0])
    leaves = [jax.ShapeDtypeStruct((L, b, n, h, d), jnp.bfloat16,
                                   sharding=one) for n in rows for _ in "kv"]
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        return jax.jit(step, donate_argnums=(1,)).lower(
            jax.ShapeDtypeStruct((b, h * d), jnp.bfloat16, sharding=one),
            leaves,
            jax.ShapeDtypeStruct((b,), jnp.int32, sharding=one)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)


def test_two_pair_live_read_compiles_for_v5e_at_serve_longdocs_shape(
        v5e_2x2, monkeypatch):
    """``serve-longdoc``'s decode read: the window pair and the summary
    pair, 16 lanes x (2,048 + 2,048) rows x 32 heads of 128 in bf16, inside
    a layer loop of 8 that carries the four leaves and writes into them
    first. ONE kernel call, the four leaves aliased (8.59 GB) and no copy
    of a layer's rows beside them."""
    compiled = _live_read_in_a_layer_loop(v5e_2x2, monkeypatch, L=8, b=16,
                                          rows=(2048, 2048))
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') \
        == 1
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == 4 * 8 * 16 * 2048 * 32 * 128 * 2
    assert mem.temp_size_in_bytes < 4 * 2 ** 20, mem


def test_latent_live_read_compiles_for_v5e_at_serve_reasons_shape(
        v5e_2x2, monkeypatch):
    """``serve-reason``'s decode read: 64 lanes x 4,096 rows of 640 under 128
    heads in bf16, inside a layer loop of 5 that carries the ONE 1.68 GB
    latent leaf and writes a token into it first. ONE kernel call, the leaf
    aliased and no copy of a layer's rows beside it; queries and results
    (10.5 and 8.4 MB) stay in HBM, so the kernel fits Mosaic's default
    scoped VMEM at every block size ISSUE 33 had measured."""
    from jax.sharding import SingleDeviceSharding
    from deepspeed_tpu.models.gpt import _kv_write
    from deepspeed_tpu.ops.pallas import decode_attention as da
    monkeypatch.setattr(da, "interpret_mode", lambda: False)
    L, b, S, h, row, r = 5, 64, 4096, 128, 640, 512

    def step(block):
        def run(x, leaf, cur):
            def body(c, layer):
                x, leaf = c
                leaf = _kv_write(leaf, x[:, :1], cur, layer)
                o = da.live_latent_attention(x, leaf, cur + 1, layer, 0.07,
                                             r, block_k=block)
                return (jnp.pad(o, ((0, 0), (0, 0), (0, row - r))), leaf), None
            return jax.lax.scan(body, (x, leaf),
                                jnp.arange(L, dtype=jnp.int32))[0]
        return run

    one = SingleDeviceSharding(v5e_2x2[0])
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        for block in (da._LIVE_LATENT_BLOCK, 128, 256, 512):
            compiled = jax.jit(step(block), donate_argnums=(1,)).lower(
                jax.ShapeDtypeStruct((b, h, row), jnp.bfloat16, sharding=one),
                jax.ShapeDtypeStruct((L, b, S, row), jnp.bfloat16,
                                     sharding=one),
                jax.ShapeDtypeStruct((b,), jnp.int32, sharding=one)).compile()
            hlo = compiled.as_text()
            assert hlo.count('custom_call_target="tpu_custom_call"') == 1
            assert "mla_decode_attention_live" in hlo
            mem = compiled.memory_analysis()
            assert mem.alias_size_in_bytes >= L * b * S * row * 2
            assert mem.temp_size_in_bytes < 4 * 2 ** 20, mem
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)


def test_grouped_live_read_compiles_for_v5e_at_serve_agents_shape(
        v5e_2x2, monkeypatch):
    """``serve-agent``'s decode read: 64 lanes, flat rows of 4 key heads of
    128 under 32 query heads in bf16, a ring pair of 4 x 2,048 rows beside
    a global pair of 1 x 20,480; a scan over the four expert layers
    writes a token into both pairs and reads the pair its layer owns under
    ``lax.cond``, as ``models/afmoe.py`` does. One kernel call a branch at
    the block ``live_block`` gives (512 rows), the four leaves aliased
    (3.76 GB) and no copy of a layer's rows beside them."""
    from jax.sharding import SingleDeviceSharding
    from deepspeed_tpu.models.gpt import _kv_write
    from deepspeed_tpu.ops.pallas import decode_attention as da
    monkeypatch.setattr(da, "interpret_mode", lambda: False)
    b, h, d, row, w, S = 64, 32, 128, 512, 2048, 20480
    assert da.live_block(w, row * 2) == 512

    def step(q, leaves, cur):
        def body(c, at):
            q, (wk, wv, gk, gv) = c
            full, slot = at
            k = q.reshape(b, 1, h * d)[..., :row]
            wk, wv = (_kv_write(x, k, cur % w, slot) for x in (wk, wv))
            gk, gv = (_kv_write(x, k, cur, slot) for x in (gk, gv))
            o = jax.lax.cond(
                full,
                lambda: da.live_decode_attention(q, [(gk, gv, cur + 1)], slot),
                lambda: da.live_decode_attention(
                    q, [(wk, wv, jnp.minimum(cur + 1, w))], slot))
            return (o, (wk, wv, gk, gv)), None
        return jax.lax.scan(body, (q, tuple(leaves)), (
            jnp.array([False, False, True, False]),
            jnp.array([1, 2, 0, 3], jnp.int32)))[0]

    one = SingleDeviceSharding(v5e_2x2[0])
    leaves = [jax.ShapeDtypeStruct((n, b, rows, row), jnp.bfloat16,
                                   sharding=one)
              for n, rows in ((4, w), (4, w), (1, S), (1, S))]
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        compiled = jax.jit(step, donate_argnums=(1,)).lower(
            jax.ShapeDtypeStruct((b, 1, h, d), jnp.bfloat16, sharding=one),
            leaves,
            jax.ShapeDtypeStruct((b,), jnp.int32, sharding=one)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
    hlo = compiled.as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 2
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == 2 * 64 * (4 * w + S) * row * 2
    assert mem.temp_size_in_bytes < 16 * 2 ** 20, mem


def test_band_kernel_compiles_for_v5e_at_serve_agents_longest_prefill(
        v5e_2x2, monkeypatch):
    """``serve-agent``'s prefill attention at its longest bucket: 16,384
    tokens, 32 query heads on 4 key heads of 128 in bf16, the window a traced
    scalar. Mosaic takes ``flash_attention_band`` over the flat ``[B, S,
    H x D]`` rows (the heads as 128-lane column blocks, the key head by
    ``head // 8``, the key block's index clamped into the band)."""
    from jax.sharding import SingleDeviceSharding
    import importlib
    # (the package exports a function of the module's name)
    fa = importlib.import_module("deepspeed_tpu.ops.pallas.flash_attention")
    monkeypatch.setattr(fa, "interpret_mode", lambda: False)
    one = SingleDeviceSharding(v5e_2x2[0])
    s, h, hk, d = 16384, 32, 4, 128

    def attend(q, k, v, window):
        return fa.flash_attention_band(q, k, v, window)

    def shape(heads):
        return jax.ShapeDtypeStruct((1, s, heads, d), jnp.bfloat16,
                                    sharding=one)

    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        compiled = jax.jit(attend).lower(
            shape(h), shape(hk), shape(hk),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=one)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') \
        == 1
    # at most one relayout of q's [.., 32, 128] tiles into flat rows (none
    # where the projection's own layout is free, as in the model's program);
    # the kernel the repo had transposes q, k, v and the output
    assert compiled.memory_analysis().temp_size_in_bytes \
        < 1.5 * s * h * d * 2


@pytest.mark.parametrize("tokens,held,d,f", [
    (64, 128, 2048, 1024), (64, 16, 7680, 2048), (256, 16, 7680, 2048)],
    ids=["serve-agent-decode", "serve-reason-decode", "serve-reason-256"])
def test_expert_tiles_kernel_compiles_for_v5e_at_the_cells_shapes(
        v5e_2x2, monkeypatch, past_auto_path, tokens, held, d, f):
    """The expert tiles of a decode step of both sparse cells and of
    ``serve-reason``'s 256-token prefill, top-8, tiles of 32 rows, the banks
    of four layers read at a traced layer: Mosaic takes ``grouped_mlp`` with
    an expert of ``[2048, 1024]`` in one grid step (both buffers 25.2 MB)
    and one of ``[7680, 2048]`` in blocks of 512 columns (47.2 MB) under its
    100 MiB limit, the transposed one-hot product among its dots, and the
    program holds ONE custom call and no copy of a bank beside it."""
    from jax.sharding import SingleDeviceSharding
    from deepspeed_tpu.moe.grouped import grouped_experts
    from deepspeed_tpu.ops.pallas import grouped_mlp as gm
    monkeypatch.setattr(gm, "interpret_mode", lambda: False)
    one = SingleDeviceSharding(v5e_2x2[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    def experts(x, choice, weights, gate, up, down, layer):
        return grouped_experts(x, choice, weights, gate, up, down,
                               lead=(layer,), tile=32)

    bank = 4 * held * d * f * 2
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        compiled = jax.jit(experts).lower(
            sds((tokens, d), jnp.bfloat16), sds((tokens, 8), jnp.int32),
            sds((tokens, 8), jnp.float32),
            sds((4, held, d, f), jnp.bfloat16),
            sds((4, held, d, f), jnp.bfloat16),
            sds((4, held, f, d), jnp.bfloat16),
            sds((), jnp.int32)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
    assert ("grouped_mlp", None) in past_auto_path
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') \
        == 1
    assert compiled.memory_analysis().temp_size_in_bytes < bank // 100


# ------------------------------------------------------------------ parity
@pytest.mark.parametrize("layers,mesh", SHAPES, ids=["dp4xtp2", "dp8"])
@pytest.mark.parametrize("stage", [1, 2, 3])
def test_losses_and_weights_match_stage0(stage, layers, mesh):
    # float32 compute (in bf16 the order of a reduction shows in the loss)
    # and SGD (Adam turns the rounding of a gradient that is zero, a key
    # bias's, into a step of the learning rate)
    kw = dict(bf16=False,
              optimizer={"type": "SGD", "params": {"lr": 0.1}})
    base, zero = _engine(0, layers, mesh, **kw), \
        _engine(stage, layers, mesh, **kw)
    l0 = [float(base.train_batch(_micro_batches(base, i))) for i in range(2)]
    lz = [float(zero.train_batch(_micro_batches(zero, i))) for i in range(2)]
    np.testing.assert_allclose(l0, lz, rtol=2e-5)
    for a, b in zip(jax.tree.leaves(base.state["master"]),
                    jax.tree.leaves(zero.state["master"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=1e-6)


def test_checkpoint_written_with_dp_on_the_layer_axis_loads(tmp_path,
                                                            monkeypatch):
    """A checkpoint is logical arrays: one saved from state laid out as the
    old rule laid it (``dp`` on the layer axis) loads into the new layout,
    bit for bit, and trains on."""
    from deepspeed_tpu.runtime import sharding

    with monkeypatch.context() as m:
        m.setattr(sharding, "_scan_dims", lambda path: 0)
        old = _engine(3, 8, {})
    spec = old.state["master"]["blocks"]["mlp"]["up_proj"]["kernel"] \
        .sharding.spec
    assert spec == P("dp", None, None)
    old.train_batch(_micro_batches(old, 0))
    old.save_checkpoint(str(tmp_path), tag="old")

    new = _engine(3, 8, {})
    new.load_checkpoint(str(tmp_path), tag="old")
    leaf = new.state["master"]["blocks"]["mlp"]["up_proj"]["kernel"]
    assert leaf.sharding.spec == P(None, "dp", None)
    for a, b in zip(jax.tree.leaves(old.state["master"]),
                    jax.tree.leaves(new.state["master"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(old.state["opt"]),
                    jax.tree.leaves(new.state["opt"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    l_old = float(old.train_batch(_micro_batches(old, 1)))
    l_new = float(new.train_batch(_micro_batches(new, 1)))
    np.testing.assert_allclose(l_old, l_new, rtol=2e-5)
