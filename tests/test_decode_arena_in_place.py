"""The KV arena stays where it lies through a decode step (PERF.md, PR 25).

When a ``cache`` collection is passed IN to ``GPT.__call__`` the layer loop
carries the layer-stacked leaves and each layer writes its tokens at
``(layer, lane, pos)`` in place; a cache CREATED by the call is scanned as
before. Two kinds of test hold that:

* structural — the engine's donating chunk program, compiled on the CPU,
  aliases the whole arena and keeps its temporaries under half of it (the
  scanned cache held 1.25x the arena: every layer's rows sliced out,
  restacked into a fresh array and copied back into the chunk's carry);
* parity — after K steps the tokens and EVERY arena leaf equal those of the
  plain reference kept here: the same weights through a twin model that
  does not scan its layers, each layer's rows sliced out of the arena,
  written and stacked back (what the scanned loop did).
"""

import dataclasses

import numpy as np
import pytest

L, B, S, H, D, V = 3, 3, 16, 2, 8, 32


# ------------------------------------------------------------- structural
@pytest.mark.parametrize("mode", ["dense", "int8", "paged", "latent"])
def test_chunk_program_keeps_the_arena_in_place(mode):
    """FLOAT32 on purpose: the CPU compiler turns a bf16 arena into float32
    whole, which buries the signal. 12 layers so that an int8 layer's
    dequantized float32 view (4/L of its arena, inherent to the read) stays
    well under the threshold too. ``latent``: the second block's ONE
    layer-stacked latent leaf (models/mla.py), a dense layer and eleven
    expert layers writing into it."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models.gpt import GPT, GPTConfig
    from deepspeed_tpu.models.mla import LatentBlockConfig
    from deepspeed_tpu.serving import ServingEngine
    from deepspeed_tpu.telemetry.memory import compiled_memory_analysis

    cfg = GPTConfig(vocab_size=128, max_seq_len=256, num_layers=12,
                    num_heads=4, d_model=128, d_ff=256, dtype=jnp.float32,
                    param_dtype=jnp.float32, rotary=True,
                    parallel_residual=True)
    if mode == "latent":
        cfg = dataclasses.replace(
            cfg, parallel_residual=False, tie_embeddings=False,
            block=LatentBlockConfig(
                q_lora_rank=48, kv_lora_rank=96, qk_nope_head_dim=32,
                qk_rope_head_dim=32, v_head_dim=32, n_routed_experts=16,
                experts_per_token=2, moe_d_ff=64, experts_held=4))
    model = GPT(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 4), jnp.int32))["params"]
    kw = {"dense": {}, "int8": {"kv_dtype": "int8"},
          "paged": {"paged": True}, "latent": {}}[mode]
    eng = ServingEngine(model, model_parameters=params, dtype=jnp.float32,
                        max_batch=4, decode_chunk=8, **kw)
    arena = sum(x.nbytes for x in jax.tree.leaves(eng.kv.cache))
    rep = compiled_memory_analysis(eng._jit_decode_chunk,
                                   *eng._abstract_chunk_args())
    assert rep is not None, "the CPU backend reports no memory analysis"
    assert rep["alias_bytes"] >= arena, (rep, arena)
    assert rep["temp_bytes"] < arena / 2, (
        f"the chunk program holds {rep['temp_bytes'] / arena:.2f}x the "
        f"arena in temporaries: some pass over the arena is back")


# ------------------------------------------------------------------ parity
def _model(**kw):
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models.gpt import GPT, GPTConfig
    cfg = GPTConfig(vocab_size=V, max_seq_len=S, num_layers=L, num_heads=H,
                    d_model=H * D, d_ff=32, dtype=jnp.float32,
                    param_dtype=jnp.float32, rotary=True, remat=False, **kw)
    model = GPT(cfg)
    params = model.init(jax.random.PRNGKey(1),
                        jnp.zeros((1, 4), jnp.int32))["params"]
    return model, params


def _random_arena(cache, rng):
    """Every payload leaf holds a previous occupant's values, so that a
    write that should drop, and does not, shows."""
    import jax

    def leaf(path, x):
        if "cache_index" in jax.tree_util.keystr(path):
            return x
        if x.dtype == np.int8:
            return rng.integers(-127, 128, x.shape).astype(np.int8)
        return rng.standard_normal(x.shape).astype(x.dtype)
    return jax.tree_util.tree_map_with_path(leaf, cache)


def _reference_step(model, params, cache, ids, positions):
    """The plain reference: the same weights through a twin that does not
    scan its layers. Layer l's rows are sliced out of the stacked arena,
    the twin's block l writes and attends over that copy, and the copies
    are stacked into a fresh arena — the scanned-cache loop, by hand."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models.gpt import GPT
    twin = GPT(dataclasses.replace(model.cfg, scan_layers=False))
    n = model.cfg.num_layers

    def unstack(tree):
        return {f"block_{i}": jax.tree.map(lambda x: x[i], tree["blocks"])
                for i in range(n)}
    p = {k: v for k, v in params.items() if k != "blocks"}
    logits, vc = twin.apply(
        {"params": {**p, **unstack(params)}, "cache": unstack(cache)},
        ids, positions=positions, mutable=["cache"])
    new = jax.tree.map(lambda *xs: jnp.stack(xs),
                       *[vc["cache"][f"block_{i}"] for i in range(n)])
    return logits, {"blocks": new}


def _with_cursor(cache, write_pos):
    """As the engine's ``_with_write_index``: every ``cache_index`` leaf
    takes this step's write positions."""
    import jax
    import jax.numpy as jnp

    def leaf(path, x):
        if "cache_index" in jax.tree_util.keystr(path):
            return jnp.broadcast_to(jnp.asarray(write_pos, x.dtype), x.shape)
        return x
    return jax.tree_util.tree_map_with_path(leaf, cache)


def _run(step, model, params, cache, fills, width, steps, per_lane=True):
    """``steps`` greedy steps of ``width`` tokens a lane from ``fills``
    (the max_seq_len sentinel pins a lane); returns tokens and the arena."""
    import jax
    import jax.numpy as jnp
    cache = jax.tree.map(jnp.asarray, cache)
    pos = np.asarray(fills, np.int32)
    tok = np.arange(1, 1 + len(pos) * width, dtype=np.int32).reshape(
        len(pos), width)
    toks = []
    for _ in range(steps):
        cache = _with_cursor(cache, pos if per_lane else pos[0])
        qpos = np.minimum(pos[:, None] + np.arange(width), S - 1)
        logits, cache = step(model, params, cache, jnp.asarray(tok),
                             jnp.asarray(qpos))
        tok = np.asarray(jnp.argmax(logits, axis=-1), np.int32)
        toks.append(tok)
        pos = np.where(pos >= S, pos, pos + 1).astype(np.int32)
    return np.stack(toks), cache


def _carried_step(model, params, cache, ids, positions):
    logits, vc = model.apply({"params": params, "cache": cache}, ids,
                             positions=positions, mutable=["cache"])
    return logits, vc["cache"]


def _arena(model, params, per_lane=True, seed=0):
    """The arena as SlotKVCacheManager builds it (eval_shape of a [B, 1]
    step, ``cache_index`` widened to a per-lane vector)."""
    import jax
    import jax.numpy as jnp
    from functools import partial
    ids = jnp.zeros((B, 1), jnp.int32)
    shapes = jax.eval_shape(partial(model.apply, mutable=["cache"]),
                            {"params": params}, ids, positions=ids)[1]["cache"]

    def build(path, leaf):
        if "cache_index" in jax.tree_util.keystr(path):
            return np.zeros(leaf.shape + ((B,) if per_lane else ()), np.int32)
        return np.zeros(leaf.shape, leaf.dtype)
    cache = jax.tree_util.tree_map_with_path(build, shapes)
    return _random_arena(cache, np.random.default_rng(seed))


def _assert_same(got, want):
    import jax
    flat_g = jax.tree_util.tree_flatten_with_path(got)[0]
    flat_w = jax.tree_util.tree_flatten_with_path(want)[0]
    assert [p for p, _ in flat_g] == [p for p, _ in flat_w]
    for (path, g), (_, w) in zip(flat_g, flat_w):
        g, w = np.asarray(g), np.asarray(w)
        assert g.shape == w.shape and g.dtype == w.dtype, path
        if g.dtype == np.int8:      # a rounding tie may fall either way
            assert np.abs(g.astype(np.int32) - w).max() <= 1, path
        else:
            np.testing.assert_allclose(g, w, rtol=0, atol=1e-5,
                                       err_msg=jax.tree_util.keystr(path))


CASES = {
    # name: (model kwargs, fills, tokens a lane a step, per-lane cursor)
    "fills_differ": ({}, (3, 7, 11), 1, True),
    "sentinel_lane": ({}, (3, S, 11), 1, True),
    "spec_near_row_end": ({}, (S - 2, S - 1, 5), 3, True),
    "scalar_index": ({}, (6, 6, 6), 1, False),
    "int8_scales": ({"kv_cache_dtype": "int8"}, (3, S, 11), 1, True),
    "int8_spec": ({"kv_cache_dtype": "int8"}, (S - 2, 4, S), 3, True),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_carried_arena_equals_the_scanned_reference(case):
    kw, fills, width, per_lane = CASES[case]
    model, params = _model(**kw)
    arena = _arena(model, params, per_lane)
    steps = 4
    toks, got = _run(_carried_step, model, params, arena, fills, width,
                     steps, per_lane)
    ref_toks, want = _run(_reference_step, model, params, arena, fills,
                          width, steps, per_lane)
    np.testing.assert_array_equal(toks, ref_toks)
    _assert_same(got, want)
    # the tree, shapes and dtypes are the arena's own
    import jax
    assert jax.tree.structure(got) == jax.tree.structure(arena)

    # and, without the reference: what had to drop dropped, in every layer
    for name, before in arena["blocks"]["attn"].items():
        if name == "cache_index":
            continue
        after = np.asarray(got["blocks"]["attn"][name])
        assert after.shape[:2] == (L, B), name
        for lane, fill in enumerate(fills):
            first = min(fill, S)
            last = min(first + steps - 1 + width, S)
            # untouched below the lane's first write and above its last:
            # a sentinel lane (first == S) keeps every row, and a write
            # past the row's end never clamps back onto its last rows
            np.testing.assert_array_equal(after[:, lane, :first],
                                          before[:, lane, :first])
            np.testing.assert_array_equal(after[:, lane, last:],
                                          before[:, lane, last:])
            if first < S:
                assert (after[:, lane, first:last]
                        != before[:, lane, first:last]).any(), (name, lane)


def test_paged_pool_equals_the_scanned_reference():
    """The stacked block pool [L, nb, bs, h*d] under the carry: writes
    scatter through the tables into the layer's own blocks, a sentinel
    lane and a position past a lane's reservation drop in every layer
    (neither dirties block 0 nor the NEXT layer's blocks)."""
    import jax
    model, params = _model()
    bs, T = 4, S // 4
    nb = B * T
    rng = np.random.default_rng(2)
    tables = np.arange(nb, dtype=np.int32).reshape(B, T)
    tables[2, 2:] = nb                    # lane 2 leased two blocks only
    attn = {
        "cached_key": rng.standard_normal((L, nb, bs, H * D)).astype(
            np.float32),
        "cached_value": rng.standard_normal((L, nb, bs, H * D)).astype(
            np.float32),
        "cache_index": np.zeros((L, B), np.int32),
        "block_tables": np.broadcast_to(tables, (L, B, T)).copy(),
    }
    pool = {"blocks": {"attn": attn}}
    fills, width, steps = (5, S, 6), 3, 2    # lane 2 runs off its blocks
    toks, got = _run(_carried_step, model, params, pool, fills, width, steps)
    ref_toks, want = _run(_reference_step, model, params, pool, fills, width,
                          steps)
    np.testing.assert_array_equal(toks, ref_toks)
    _assert_same(got, want)
    assert jax.tree.structure(got) == jax.tree.structure(pool)
    for name in ("cached_key", "cached_value"):
        after = np.asarray(got["blocks"]["attn"][name])
        # lane 1 (sentinel) owns blocks T..2T-1: untouched in every layer
        np.testing.assert_array_equal(after[:, T:2 * T],
                                      attn[name][:, T:2 * T])
        # lane 2 wrote positions 6..7 of its second block and nothing past
        # it: its unleased blocks' would-be targets keep their values
        np.testing.assert_array_equal(after[:, 2 * T + 2:],
                                      attn[name][:, 2 * T + 2:])
        assert (after[:, 2 * T + 1, 2:] != attn[name][:, 2 * T + 1, 2:]).any()
