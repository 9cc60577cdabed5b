"""The second block this repo runs (models/mla.py, moe/grouped.py) against
its plain float32 reference (chipbench/archs/pangu_ultra_moe.py, which
imports nothing of the program): latent attention through the cache, the
chip's share of a sigmoid-routed expert layer without dropped tokens, and
the whole cut model through ServingEngine. Toy widths, seeded weights,
float32 on the CPU.
"""

import dataclasses

import numpy as np
import pytest

from chipbench.archs import pangu_ultra_moe as arch

# published KEYS at toy values: 1 dense + 2 expert layers, 4 experts held
# of 16 (experts 4-7), top-2
TOY = {
    "arch": "pangu_ultra_moe", "hidden_size": 32, "intermediate_size": 64,
    "num_attention_heads": 4, "q_lora_rank": 24, "kv_lora_rank": 16,
    "qk_nope_head_dim": 8, "qk_rope_head_dim": 4, "v_head_dim": 8,
    "moe_intermediate_size": 16, "n_routed_experts": 4,
    "n_shared_experts": 1, "num_experts_per_tok": 2, "norm_topk_prob": True,
    "routed_scaling_factor": 2.5, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "vocab_size": 64,
    "max_position_embeddings": 32, "rms_norm_eps": 1e-5,
    "rope_theta": 25600000, "tie_word_embeddings": False,
    "published": {"n_routed_experts": 16},
    "deployment_share": {"expert_offset": 4},
    "model": {"dtype": "float32", "param_dtype": "float32", "remat": False},
}
ATOL = 2e-4


@pytest.fixture(scope="module")
def toy():
    import jax
    import jax.numpy as jnp
    model = arch.build_model(TOY)
    params = model.init(jax.random.PRNGKey(3),
                        jnp.zeros((1, 4), jnp.int32))["params"]
    # gains away from one, so that a norm left out or applied twice shows
    params = jax.tree.map(
        lambda x: x * 1.3 if x.ndim <= 2 and x.shape[-1] != 64 else x, params)
    return model, params


def _ids(rng, b, s):
    return rng.integers(0, TOY["vocab_size"], (b, s)).astype(np.int32)


def _with_cursor(cache, cur):
    import jax
    import jax.numpy as jnp

    def leaf(path, x):
        if "cache_index" in jax.tree_util.keystr(path):
            cur_ = jnp.asarray(cur, x.dtype)
            return jnp.broadcast_to(cur_, x.shape[:1] + cur_.shape)
        return x
    return jax.tree_util.tree_map_with_path(leaf, cache)


# ------------------------------------------------------------- (a) the model
def test_full_forward_equals_the_reference(toy):
    import jax
    model, params = toy
    ids = _ids(np.random.default_rng(0), 2, 12)
    (logits, routed) = model.apply({"params": params}, ids)
    ref, _ = arch.reference_logits(TOY, params, ids)
    assert np.max(np.abs(np.asarray(logits) - np.asarray(ref))) < ATOL
    assert routed["expert_choice"].shape == (2, 2, 12, 2)
    assert arch.param_count(TOY) == sum(
        int(np.prod(p.shape)) for p in jax.tree.leaves(params))


@pytest.mark.parametrize("cursors", ["per_lane", "scalar"])
def test_prefill_then_decode_through_the_cache_equals_the_full_forward(
        toy, cursors):
    """Padded prefill creates the cache (expanded attention), then every
    further token goes through it one at a time (absorbed attention):
    the logits at EVERY position equal the reference's one full forward."""
    import jax.numpy as jnp
    model, params = toy
    rng = np.random.default_rng(1)
    total = 14
    lens = np.array([5, 9] if cursors == "per_lane" else [7, 7], np.int32)
    ids = _ids(rng, 2, total)
    ref = np.asarray(arch.reference_logits(TOY, params, ids)[0])
    width = int(lens.max())
    padded = np.where(np.arange(width)[None] < lens[:, None],
                      ids[:, :width], 0)
    (logits, _), vc = model.apply({"params": params}, jnp.asarray(padded),
                                  mutable=["cache"])
    cache = vc["cache"]
    assert set(cache["blocks"]) == {"latent", "cache_index"}
    assert cache["blocks"]["latent"].shape == (3, 2, 32, 128)   # 20 -> 128
    for i, n in enumerate(lens):
        assert np.max(np.abs(np.asarray(logits)[i, :n] - ref[i, :n])) < ATOL
    pos = lens.copy()
    while (pos < total).any():
        cur = pos if cursors == "per_lane" else pos[0]
        tok = ids[np.arange(2), np.minimum(pos, total - 1)]
        (logits, _), vc = model.apply(
            {"params": params, "cache": _with_cursor(cache, cur)},
            jnp.asarray(tok)[:, None], positions=jnp.asarray(pos)[:, None],
            mutable=["cache"])
        cache = vc["cache"]
        for i in range(2):
            if pos[i] < total:
                assert np.max(np.abs(np.asarray(logits)[i, 0]
                                     - ref[i, pos[i]])) < ATOL, (i, pos)
        pos = np.minimum(pos + 1, total)


def test_absorbed_attention_equals_expanded(toy):
    """The same tokens through one layer's attention twice: expanded heads
    over the call's own tokens, and absorbed over a cache that the call
    itself fills from position 0."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models.mla import latent_attention
    model, params = toy
    cfg = model.cfg
    p = {k: v[1] for k, v in params["blocks"]["sparse"].items()
         if not k.startswith("expert_")}
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 6, cfg.d_model))
    positions = jnp.broadcast_to(jnp.arange(6)[None], (2, 6))
    expanded, _ = latent_attention(cfg, p, x, positions, None, None, 0, False)
    empty = jnp.zeros((3, 2, cfg.max_seq_len, cfg.block.cache_row))
    absorbed, cache = latent_attention(cfg, p, x, positions, empty,
                                       jnp.zeros((2,), jnp.int32), 2, True)
    assert np.max(np.abs(np.asarray(expanded - absorbed))) < ATOL
    assert not np.asarray(cache[:2]).any() and np.asarray(cache[2, :, :6]).any()
    assert not np.asarray(cache[2, :, 6:]).any()
    assert not np.asarray(cache[..., cfg.block.latent_dim:]).any()


# ------------------------------------------------------ (b) the expert layer
def _experts(rng, held=4, d=32, f=16):
    return (rng.standard_normal((held, d, f)).astype(np.float32) / 6,
            rng.standard_normal((held, d, f)).astype(np.float32) / 6,
            rng.standard_normal((held, f, d)).astype(np.float32) / 4)


def _silu(x):
    return x / (1.0 + np.exp(-x))


def _plain_experts(x, choice, weights, gate, up, down, offset):
    """sum over the chosen experts held of w_i E_i(x), a loop."""
    out = np.zeros_like(x)
    for t in range(x.shape[0]):
        for e, w in zip(choice[t], weights[t]):
            if offset <= e < offset + gate.shape[0]:
                g, u, dn = gate[e - offset], up[e - offset], down[e - offset]
                out[t] += w * ((_silu(x[t] @ g) * (x[t] @ u)) @ dn)
    return out


def test_router_weights_are_normalised_over_all_chosen_and_scaled():
    import jax.numpy as jnp
    from deepspeed_tpu.moe.grouped import sigmoid_topk
    rng = np.random.default_rng(2)
    x = rng.standard_normal((9, 32)).astype(np.float32)
    router = rng.standard_normal((32, 16)).astype(np.float32)
    choice, w = sigmoid_topk(jnp.asarray(x), jnp.asarray(router), 3, 2.5)
    scores = 1.0 / (1.0 + np.exp(-(x @ router)))
    want = np.argsort(-scores, axis=-1)[:, :3]
    assert (np.sort(np.asarray(choice), -1) == np.sort(want, -1)).all()
    np.testing.assert_allclose(np.asarray(w).sum(-1), 2.5, rtol=1e-5)
    top = np.take_along_axis(scores, np.asarray(choice), -1)
    np.testing.assert_allclose(np.asarray(w), 2.5 * top / top.sum(-1,
                               keepdims=True), rtol=1e-5)
    _, raw = sigmoid_topk(jnp.asarray(x), jnp.asarray(router), 3, 1.0,
                          normalize=False)
    np.testing.assert_allclose(np.asarray(raw), top, rtol=1e-5)


@pytest.mark.parametrize("tile", [8, 64])
def test_no_token_is_dropped_when_every_token_picks_the_same_experts(tile):
    """300 tokens, all on experts 5 and 6 (both held), so each gets 300
    rows: more than a tile, and 0 for the other two held experts."""
    import jax.numpy as jnp
    from deepspeed_tpu.moe.grouped import grouped_experts
    rng = np.random.default_rng(3)
    x = rng.standard_normal((300, 32)).astype(np.float32)
    gate, up, down = _experts(rng)
    choice = np.tile(np.array([[5, 6]], np.int32), (300, 1))
    weights = rng.uniform(0.5, 1.5, (300, 2)).astype(np.float32)
    got = grouped_experts(jnp.asarray(x), jnp.asarray(choice),
                          jnp.asarray(weights), jnp.asarray(gate),
                          jnp.asarray(up), jnp.asarray(down),
                          expert_offset=4, tile=tile)
    want = _plain_experts(x, choice, weights, gate, up, down, 4)
    assert np.abs(want).min(axis=-1).max() > 0        # every token has a row
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)


@pytest.mark.parametrize("tokens,rows", [
    (64, 32), (512, 64), (2048, 256), (3 * 1024, 256), (4 * 2048, 256)])
def test_an_expert_tile_is_never_over_256_rows(tokens, rows):
    """Four times an expert's even share as a power of two, from 32 to 256:
    at 512 rows the prefill of three prompts of 1,024 took 0.63 s on the chip
    where two took 0.08 (PERF.md, PR 34)."""
    from deepspeed_tpu.models.mla import _tile_rows
    cfg = arch.build_model({
        **TOY, "n_routed_experts": 16, "num_experts_per_tok": 8,
        "published": {"n_routed_experts": 256},
        "deployment_share": {"expert_offset": 0}}).cfg
    assert _tile_rows(cfg, tokens) == rows


def test_the_banks_are_read_at_their_layer():
    import jax.numpy as jnp
    from deepspeed_tpu.moe.grouped import grouped_experts
    rng = np.random.default_rng(4)
    x = rng.standard_normal((10, 32)).astype(np.float32)
    layers = [_experts(rng) for _ in range(3)]
    stacked = [jnp.asarray(np.stack([l[i] for l in layers]))
               for i in range(3)]
    choice = rng.integers(0, 16, (10, 2)).astype(np.int32)
    choice[:, 1] = (choice[:, 0] + 1 + choice[:, 1] % 15) % 16   # distinct
    weights = rng.uniform(0.5, 1.5, (10, 2)).astype(np.float32)
    got = grouped_experts(jnp.asarray(x), jnp.asarray(choice),
                          jnp.asarray(weights), *stacked, lead=(2,),
                          expert_offset=4, tile=8)
    np.testing.assert_allclose(
        np.asarray(got), _plain_experts(x, choice, weights, *layers[2], 4),
        atol=2e-5)


def test_the_shares_add_up_to_the_uncut_layer(toy):
    """Guide, section 4: the routed parts that all four shares of a layer
    compute (4 experts each of 16), plus what every chip computes alike (the
    shared expert) ONCE, equal the uncut reference layer."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models.mla import expert_ffn
    model, params = toy
    rng = np.random.default_rng(5)
    d, f = 32, 16
    x = rng.standard_normal((2, 7, d)).astype(np.float32)
    p = {k: np.asarray(v[0]) for k, v in params["blocks"]["sparse"].items()
         if not k.startswith("expert_")}
    banks = _experts(rng, held=16)
    # the uncut layer, plainly: shared + the 2 chosen of ALL 16 experts
    flat = x.reshape(-1, d)
    scores = 1.0 / (1.0 + np.exp(-(flat @ p["router"])))
    choice = np.argsort(-scores, -1)[:, :2]
    top = np.take_along_axis(scores, choice, -1)
    weights = 2.5 * top / top.sum(-1, keepdims=True)
    shared = (_silu(flat @ p["shared_gate"]) * (flat @ p["shared_up"])) \
        @ p["shared_down"]
    whole = shared + _plain_experts(flat, choice, weights, *banks, 0)
    # the four shares through the program's layer
    total = np.zeros_like(whole)
    for share in range(4):
        cfg = dataclasses.replace(model.cfg, block=dataclasses.replace(
            model.cfg.block, expert_offset=4 * share))
        held = {name: jnp.asarray(b[None, 4 * share:4 * share + 4])
                for name, b in zip(("expert_gate", "expert_up",
                                    "expert_down"), banks)}
        out, chosen = expert_ffn(cfg, jax.tree.map(jnp.asarray, p),
                                 (held, 0), jnp.asarray(x))
        assert (np.sort(np.asarray(chosen).reshape(-1, 2), -1)
                == np.sort(choice, -1)).all()      # every share routes alike
        total += np.asarray(out).reshape(-1, d) - shared
    np.testing.assert_allclose(total + shared, whole, atol=5e-5)


def test_routing_counters_count_live_tokens_only():
    import jax.numpy as jnp
    from deepspeed_tpu.moe.grouped import COUNTERS, routing_counters
    # 2 layers, 2 rows of 3 tokens, top-2 of 16; experts 4-7 held
    choice = np.array([[[[4, 9], [4, 5], [0, 1]], [[7, 4], [8, 9], [5, 6]]],
                       [[[4, 5], [6, 7], [4, 15]], [[4, 5], [1, 2], [3, 4]]]],
                      np.int32)
    live = np.array([[True, True, False], [True, False, False]])
    got = {k: float(v) for k, v in routing_counters(
        jnp.asarray(choice), jnp.asarray(live), expert_offset=4,
        experts_held=4, tile=2).items()}
    assert set(got) == set(COUNTERS)
    # layer 0 live: (4,9) (4,5) (7,4) -> loads 4:3 5:1 7:1; layer 1 live:
    # (4,5) (6,7) (4,5) -> loads 4:2 5:2 6:1 7:1
    assert got["pairs_held"] == 5 + 6 and got["pairs_absent"] == 12 - 11
    assert got["experts_touched"] == 3 + 4
    assert got["load_max"] == 3 + 2
    assert got["load_mean"] == pytest.approx(11 / 4)
    assert got["steps"] == 2
    # tiles are what RAN, live or not: layer 0 loads 4:3 5:2 6:1 7:1 at two
    # rows a tile, layer 1 loads 4:4 5:2 6:1 7:1; none through the kernel here
    assert got["tiles"] == (2 + 1 + 1 + 1) + (2 + 1 + 1 + 1)
    assert got["kernel_tiles"] == 0
    none = routing_counters(jnp.asarray(choice), jnp.zeros((2, 3), bool),
                            expert_offset=4, experts_held=4, tile=2)
    assert all(float(v) == 0 for k, v in none.items() if k != "tiles")
    assert float(none["tiles"]) == got["tiles"]
    through = routing_counters(jnp.asarray(choice), jnp.asarray(live),
                               expert_offset=4, experts_held=4, tile=2,
                               kernel=True)
    assert float(through["kernel_tiles"]) == got["tiles"]


def test_a_decode_step_reads_a_touched_expert_once(toy):
    """64 tokens, every lane live, tiles of 32 rows: no held expert gets a
    second tile, so ``tiles / experts_touched`` is 1.0; on the CPU ``auto``
    takes the loop, so none of them went through the kernel."""
    import jax.numpy as jnp
    model, params = toy
    ids = _ids(np.random.default_rng(12), 64, 1)
    _, routed = model.apply({"params": params}, ids)
    got = {k: float(v) for k, v in model.routing_counters(
        routed, jnp.ones((64, 1), bool)).items()}
    assert got["tiles"] == got["experts_touched"] > 0
    assert got["kernel_tiles"] == 0


# ----------------------------------------- (c) the cut model, through serving
@pytest.fixture(scope="module")
def served(toy):
    """Five requests through a two-lane engine: two prefill buckets, a
    chunk of four steps, lanes admitted while others are mid-answer."""
    import jax.numpy as jnp
    from deepspeed_tpu.serving import ServingEngine
    model, params = toy
    eng = ServingEngine(model, model_parameters=params, dtype=jnp.float32,
                        max_batch=2, decode_chunk=4, max_prompt_len=16,
                        prefill_buckets=[8, 16])
    rng = np.random.default_rng(6)
    prompts = [_ids(rng, 1, n)[0] for n in (3, 11, 6, 16, 2)]
    reqs = [eng.submit(p, max_new_tokens=m)
            for p, m in zip(prompts, (9, 5, 13, 3, 7))]
    for _ in range(200):
        if not (eng.scheduler.has_work() or eng.chunk_in_flight):
            break
        eng.pump()
    return eng, params, prompts, reqs


def test_served_tokens_are_the_reference_argmax_on_logits(served):
    eng, params, prompts, reqs = served
    for prompt, req in zip(prompts, reqs):
        assert req.status == "done"
        full = np.concatenate([prompt, np.asarray(req.tokens, np.int32)])
        ref = np.asarray(arch.reference_logits(TOY, params, full[None])[0])[0]
        for j, tok in enumerate(req.tokens):
            row = ref[len(prompt) - 1 + j]
            assert row.max() - row[tok] < 1e-3, (len(prompt), j)


def test_the_arena_is_the_latent_leaf_and_is_counted_by_what_it_is(served):
    import jax
    eng = served[0]
    leaves = {jax.tree_util.keystr(p): x for p, x in
              jax.tree_util.tree_flatten_with_path(eng.kv.cache)[0]}
    assert set(leaves) == {"['blocks']['latent']", "['blocks']['cache_index']"}
    latent = leaves["['blocks']['latent']"]
    assert latent.shape == (3, 2, 32, 128)
    rep = eng.kv.arena_report()
    assert rep["kv_bytes"] == latent.nbytes
    assert rep["index_bytes"] == 3 * 2 * 4
    assert rep["bytes_per_token"] == 3 * 128 * 4
    assert rep["int8_payload_bytes"] == rep["scale_bytes"] == 0
    assert eng.kv.head_dim(4) is None        # a latent row has no heads


def test_the_programs_count_what_they_routed(served):
    eng, _, prompts, reqs = served
    r = eng.metrics.routing
    layers, k = 2, 2
    prompt_tokens = sum(len(p) for p in prompts)
    # a request's last token is sampled and never fed back
    decode_tokens = sum(len(q.tokens) - 1 for q in reqs)
    assert r["prefill_pairs_held"] + r["prefill_pairs_absent"] \
        == prompt_tokens * layers * k
    assert r["decode_pairs_held"] + r["decode_pairs_absent"] \
        == decode_tokens * layers * k
    assert 0 < r["decode_experts_touched"] <= r["decode_steps"] * 4
    assert r["decode_load_max"] >= r["decode_load_mean"] > 0


def test_a_lane_patch_is_one_shape_whatever_the_lanes(served):
    """Patches of one lane and of two, admissions and retirements: one
    shape, traced once for a state that came from the host and once for one
    that a chunk carried (a scatter per vector at the lanes' indices was a
    program per COUNT of lanes)."""
    eng = served[0]
    assert eng._jit_lane_patch._cache_size() <= 2


# ------------------------------------------------------- what the model forced
def test_head_size_comes_from_the_cache_leaves():
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.serving.kv_cache import declared_head_dim
    leaf = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)     # noqa: E731
    idx = jax.ShapeDtypeStruct((3,), jnp.int32)
    four = {"attn": {"cache_index": idx, "cached_key": leaf(3, 2, 16, 4, 8)}}
    flat = {"attn": {"cache_index": idx, "cached_key": leaf(3, 2, 16, 32)}}
    latent = {"blocks": {"cache_index": idx, "latent": leaf(3, 2, 16, 128)}}
    assert declared_head_dim(four, 2, 4) == 8
    assert declared_head_dim(flat, 2, 4) == 8
    assert declared_head_dim(latent, 2, 4) is None


def test_a_latent_leaf_is_refused_on_tp_by_name():
    from deepspeed_tpu.runtime.sharding import kv_spec
    assert tuple(kv_spec("blocks/latent", (5, 8, 64, 640), 1)) \
        == (None,) * 4
    with pytest.raises(ValueError, match="latent"):
        kv_spec("blocks/latent", (5, 8, 64, 640), 2)


def test_rotary_base_reaches_both_blocks():
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models.gpt import GPT, GPTConfig, rotary_embedding
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 5, 2, 8))
    pos = jnp.arange(5)[None]
    assert np.allclose(rotary_embedding(x, pos, 8),
                       rotary_embedding(x, pos, 8, 10000.0))
    assert not np.allclose(rotary_embedding(x, pos, 8),
                           rotary_embedding(x, pos, 8, 25.6e6))
    kw = dict(vocab_size=32, max_seq_len=16, num_layers=1, num_heads=2,
              d_model=16, d_ff=32, rotary=True, dtype=jnp.float32,
              param_dtype=jnp.float32, remat=False)
    ids = jnp.arange(8)[None]
    a, b = GPT(GPTConfig(**kw)), GPT(GPTConfig(rotary_base=500.0, **kw))
    params = a.init(jax.random.PRNGKey(1), ids)["params"]
    assert not np.allclose(a.apply({"params": params}, ids),
                           b.apply({"params": params}, ids), atol=1e-4)


def test_flash_forward_takes_a_v_of_another_head_size():
    import jax
    from deepspeed_tpu.ops.pallas._utils import KernelUnsupported
    from deepspeed_tpu.ops.pallas.flash_attention import (flash_attention,
                                                          reference_attention)
    k0, k1, k2 = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(k0, (1, 256, 2, 24))
    k = jax.random.normal(k1, (1, 256, 2, 24))
    v = jax.random.normal(k2, (1, 256, 2, 16))
    out = flash_attention(q, k, v, sm_scale=0.2, block_q=128, block_k=128)
    assert out.shape == (1, 256, 2, 16)
    assert np.max(np.abs(out - reference_attention(q, k, v, True, 0.2))) < 1e-5
    with pytest.raises(KernelUnsupported):
        jax.grad(lambda q: flash_attention(q, k, v, block_q=128,
                                           block_k=128).sum())(q)
