"""The fourth block this repo runs (models/afmoe.py) against its plain float32
reference (chipbench/archs/afmoe.py, which imports nothing of the program but
to build it): gated grouped-query attention, sliding-window layers beside
global ones as two kinds of KV leaf under one cursor, an expert layer that
holds every expert behind a router with a selection bias; through the cache
across the window's edge and after the ring has wrapped, and through
ServingEngine with lanes at different depths in one chunk. Toy widths (hidden
64, 4 query heads on 2 key heads, window 16, 8 experts top-2, 1 dense + 4
expert layers in the published pattern), seeded weights, float32 on the CPU.
"""

import dataclasses

import numpy as np
import pytest

from chipbench.archs import afmoe as arch
from tests.test_latent_block import _with_cursor

S, F = "sliding_attention", "full_attention"
TOY = {
    "arch": "afmoe", "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 96,
    "moe_intermediate_size": 32, "num_hidden_layers": 5,
    "num_dense_layers": 1, "num_experts": 8, "num_experts_per_tok": 2,
    "num_shared_experts": 1, "vocab_size": 97, "sliding_window": 16,
    "layer_types": [S, S, S, F, S], "max_position_embeddings": 64,
    "rms_norm_eps": 1e-5, "rope_theta": 10000, "route_norm": True,
    "route_scale": 2.826, "mup_enabled": True, "tie_word_embeddings": False,
    "model": {"dtype": "float32", "param_dtype": "float32"},
}
# float32 on both sides; the sums run in another order (heads widened to the
# flat rows, experts grouped, a ring) and agree to 2e-6 here. bfloat16 in the
# program's or the cache's place moves a logit by 1e-2 and more
# (test_a_lower_precision_fails_the_comparison)
ATOL = 2e-4
W, MAX = 16, 64
# the served fixture's prompt lengths and answer budgets, request by request
PROMPT_LENS, BUDGETS = (13, 3, 30, 5, 17, 2), (25, 30, 11, 30, 9, 21)


@pytest.fixture(scope="module")
def toy():
    import jax
    model = arch.build_model(TOY)
    return model, jax.jit(lambda k: arch.init_params(model, k))(
        jax.random.PRNGKey(3))


def _ids(rng, b, s):
    return rng.integers(0, TOY["vocab_size"], (b, s)).astype(np.int32)


def _through_the_cache(model, params, prompts, steps, width=None, mark=None):
    """Padded prefill told where each row ends, then ``steps`` greedy decode
    steps with a cursor a lane. Returns the logits that chose each of the
    first ``steps + 1`` tokens ``[n, steps + 1, V]``, those tokens and the
    last cache. ``mark``: a function of the prefilled cache, applied before
    the first step."""
    import jax
    import jax.numpy as jnp
    n = len(prompts)
    lens = np.asarray([len(p) for p in prompts], np.int32)
    ids = np.zeros((n, width or int(max(lens))), np.int32)
    for i, p in enumerate(prompts):
        ids[i, :len(p)] = p

    @jax.jit
    def prefill(params, ids, lens):
        return model.apply({"params": params}, ids, mutable=["cache"],
                           lengths=lens)

    @jax.jit
    def decode(params, cache, tok, pos):
        return model.apply(
            {"params": params, "cache": _with_cursor(cache, pos)},
            tok[:, None], positions=pos[:, None], mutable=["cache"])

    (logits, _), vc = prefill(params, jnp.asarray(ids), jnp.asarray(lens))
    assert logits.shape[1] == 1             # each row's last position alone
    cache = vc["cache"] if mark is None else mark(vc["cache"])
    out, toks = [np.asarray(logits[:, 0])], []
    for j in range(steps):
        toks.append(out[-1].argmax(-1).astype(np.int32))
        (logits, _), vc = decode(params, cache, jnp.asarray(toks[-1]),
                                 jnp.asarray(lens + j))
        cache = vc["cache"]
        out.append(np.asarray(logits[:, -1]))
    toks.append(out[-1].argmax(-1).astype(np.int32))
    return np.stack(out, 1), np.stack(toks, 1), cache


def _worst(config, params, prompts, got, toks, steps):
    """Largest |logit difference| of the cache path's rows from ONE full
    forward of the reference over prompt + those tokens, row by row."""
    full = np.zeros((len(prompts), max(map(len, prompts)) + steps + 1),
                    np.int32)
    for i, p in enumerate(prompts):
        full[i, :len(p)] = p
        full[i, len(p):len(p) + steps + 1] = toks[i]
    ref = np.asarray(arch.reference_logits(config, params, full)[0])
    return [float(np.max(np.abs(got[i] - ref[i, len(p) - 1:len(p) + steps])))
            for i, p in enumerate(prompts)]


# ------------------------------------------------------------- (a) the model
def test_full_forward_equals_the_reference(toy):
    import jax
    model, params = toy
    ids = _ids(np.random.default_rng(0), 2, 40)
    logits, routed = model.apply({"params": params}, ids)
    ref, report = arch.reference_logits(
        TOY, params, ids, program_choice=np.asarray(routed["expert_choice"]))
    assert np.max(np.abs(np.asarray(logits) - np.asarray(ref))) < ATOL
    assert routed["expert_choice"].shape == (4, 2, 40, 2)
    assert report["sets"] == 4 * 2 * 40 and report["sets_differing"] == 0
    assert arch.param_count(TOY) == sum(
        int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    # the head over a few rows alone is the same rows
    some, _ = arch.reference_logits(TOY, params, ids, rows=[(3, 5), (38, 40)])
    assert np.allclose(some[0], np.asarray(ref)[0, 3:5], atol=1e-5)
    assert np.allclose(some[1], np.asarray(ref)[1, 38:40], atol=1e-5)


def test_prefill_then_decode_through_the_cache_equals_the_full_forward(toy):
    """ONE batch of lanes at different depths, padded to 40: a lane that ends
    before the window (5: positions 5-17 cross its edge while decoding), one
    whose prompt fills the ring to its last row (16: its first decoded token
    wraps), one a token past the edge (17), one whose prompt has wrapped the
    ring twice (37: 2 x 16 + 5), and twelve decode steps each, so the last
    lane wraps a third time through the cache."""
    model, params = toy
    rng = np.random.default_rng(1)
    prompts = [_ids(rng, 1, n)[0] for n in (5, 14, 16, 17, 37)]
    got, toks, cache = _through_the_cache(model, params, prompts, 12, 40)
    assert max(_worst(TOY, params, prompts, got, toks, 12)) < ATOL
    leaves = cache["blocks"]
    assert leaves["window_key"].shape == (4, 5, W, 32)
    assert leaves["global_key"].shape == (1, 5, MAX, 32)
    assert leaves["cache_index"].shape == (1, 5)


def test_the_padded_prefill_has_to_be_told_where_each_row_ends(toy):
    """Untold, a prompt of 17 padded to 40 hands out the ring of positions
    24-39 (padding) and the logits of every position; the ring a lane is
    handed holds its LAST 16 real tokens."""
    import jax.numpy as jnp
    model, params = toy
    p = _ids(np.random.default_rng(2), 1, 17)
    ids = np.zeros((1, 40), np.int32)
    ids[:, :17] = p
    (full, _), untold = model.apply({"params": params}, jnp.asarray(ids),
                                    mutable=["cache"])
    (last, _), told = model.apply({"params": params}, jnp.asarray(ids),
                                  mutable=["cache"], lengths=jnp.array([17]))
    (_, _), alone = model.apply({"params": params}, jnp.asarray(p),
                                mutable=["cache"])
    assert full.shape == (1, 40, 97) and last.shape == (1, 1, 97)
    assert np.allclose(np.asarray(last)[0, 0], np.asarray(full)[0, 16],
                       atol=1e-5)
    for name in ("window_key", "window_value"):
        assert np.allclose(told["cache"]["blocks"][name],
                           alone["cache"]["blocks"][name], atol=1e-5)
        assert not np.allclose(untold["cache"]["blocks"][name],
                               alone["cache"]["blocks"][name], atol=1e-2)
    # the global leaf holds the bucket's rows (the padding's are under no
    # fill) and zeros up to max_seq_len
    glob = np.asarray(told["cache"]["blocks"]["global_key"])[0, 0]
    assert np.all(glob[40:] == 0) and np.all(glob[:17].any(axis=-1))


def test_a_cursor_at_max_seq_len_writes_into_neither_leaf(toy):
    """The serving engine's retired-lane sentinel: ``64 mod 16`` is row 0 of
    the ring, in range, so the block sends it past the leaf; the global
    leaf's row 64 is past its end already."""
    import jax.numpy as jnp
    model, params = toy
    prompts = [_ids(np.random.default_rng(3), 1, n)[0] for n in (9, 20)]
    _, _, cache = _through_the_cache(model, params, prompts, 2)
    before = {k: np.asarray(v) for k, v in cache["blocks"].items()}
    cur = np.asarray([MAX, 22], np.int32)
    (logits, _), vc = model.apply(
        {"params": params, "cache": _with_cursor(cache, cur)},
        jnp.asarray([[1], [2]]), positions=jnp.asarray(cur)[:, None],
        mutable=["cache"])
    assert np.isfinite(np.asarray(logits)).all()
    after = vc["cache"]["blocks"]
    for name in ("window_key", "window_value", "global_key", "global_value"):
        assert np.array_equal(before[name][:, 0],
                              np.asarray(after[name])[:, 0])
        assert not np.array_equal(before[name][:, 1],
                                  np.asarray(after[name])[:, 1])


def test_a_call_that_is_handed_the_cache_takes_one_token_a_lane(toy):
    import jax.numpy as jnp
    model, params = toy
    ids = _ids(np.random.default_rng(4), 1, 6)
    _, vc = model.apply({"params": params}, jnp.asarray(ids),
                        mutable=["cache"])
    with pytest.raises(NotImplementedError, match="wrap the ring"):
        model.apply({"params": params, "cache": vc["cache"]},
                    jnp.asarray(ids[:, :3]), mutable=["cache"])


def test_the_selection_bias_moves_the_choice_and_not_the_weights():
    """A bias that lifts expert 5 over every score makes every token choose
    it, and the weights stay ``scale * s_i / sum of the chosen s``: the
    bias is in neither numerator nor denominator. ``None`` is the router
    ``serve-reason`` runs."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.moe.grouped import sigmoid_topk
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(12, 16)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(16, 8)) / 4, jnp.float32)
    scores = np.asarray(jax.nn.sigmoid(x @ router))
    plain_c, plain_w = sigmoid_topk(x, router, 2, 2.826, True)
    zero_c, zero_w = sigmoid_topk(x, router, 2, 2.826, True,
                                  bias=jnp.zeros((8,)))
    assert np.array_equal(plain_c, zero_c) and np.allclose(plain_w, zero_w)
    bias = np.zeros(8, np.float32)
    bias[5] = 2.0
    choice, weights = sigmoid_topk(x, router, 2, 2.826, True,
                                   bias=jnp.asarray(bias))
    choice, weights = np.asarray(choice), np.asarray(weights)
    assert (choice[:, 0] == 5).all()
    assert not (np.asarray(plain_c) == 5).any(axis=-1).all()
    top = np.take_along_axis(scores, choice, axis=-1)
    assert np.allclose(weights, 2.826 * top / top.sum(-1, keepdims=True),
                       atol=1e-6)
    # the second choice is the largest plain score among the others
    rest = scores.copy()
    rest[:, 5] = -1
    assert np.array_equal(choice[:, 1], rest.argmax(-1))


def test_the_seeded_bias_is_in_the_models_choice(toy):
    """With the bias zeroed the model routes differently somewhere, and the
    reference then refuses to follow the program's sets (a gap of the
    bias's size, over ROUTE_EPS)."""
    import jax
    model, params = toy
    ids = _ids(np.random.default_rng(6), 2, 40)
    _, routed = model.apply({"params": params}, ids)
    sparse = dict(params["blocks"]["sparse"])
    sparse["router_bias"] = sparse["router_bias"] * 0
    bare = {**params, "blocks": {**params["blocks"], "sparse": sparse}}
    _, unbiased = model.apply({"params": bare}, ids)
    theirs = np.asarray(unbiased["expert_choice"])
    assert not np.array_equal(np.sort(theirs, -1),
                              np.sort(np.asarray(routed["expert_choice"]), -1))
    _, report = arch.reference_logits(TOY, params, ids, program_choice=theirs)
    assert report["sets_differing"] > 0 and report["largest_gap"] > 0


def test_every_pair_falls_on_a_held_expert(toy):
    """The routing counters of a layer that holds every expert:
    ``pairs_absent`` 0, so ``routed_here`` reads 100 %."""
    import jax.numpy as jnp
    model, params = toy
    ids = _ids(np.random.default_rng(7), 3, 20)
    _, routed = model.apply({"params": params}, ids)
    live = jnp.arange(20)[None, :] < jnp.array([20, 7, 0])[:, None]
    got = {k: float(v) for k, v in
           model.routing_counters(routed, live).items()}
    assert got["pairs_absent"] == 0
    assert got["pairs_held"] == 27 * 4 * 2
    assert got["steps"] == 4 and 0 < got["experts_touched"] <= 4 * 8
    assert got["load_mean"] == 27 * 4 * 2 / 8


# ---------------------------------------------------- (b) the band kernel
@pytest.mark.parametrize("window", [24, 64, 100, 256, 10 ** 6])
def test_the_band_kernel_equals_a_dense_band_mask_at_four_key_heads(window):
    """``flash_attention_band`` (the Pallas interpreter here) in tiles of 64
    against ``reference_attention``'s softmax under a dense band mask with
    the key heads repeated: windows inside a tile, of a tile, across tiles
    and past the sequence (plain causal attention), as a TRACED scalar."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention_band
    rng = np.random.default_rng(8)
    b, s, h, hk, d = 2, 256, 8, 4, 128
    q, k, v = (jnp.asarray(rng.normal(size=(b, s, n, d)), jnp.float32)
               for n in (h, hk, hk))
    got = jax.jit(lambda q, k, v, w: flash_attention_band(
        q, k, v, w, block_q=64, block_k=64))(q, k, v, jnp.int32(window))
    kr, vr = (np.repeat(np.asarray(a), h // hk, axis=2) for a in (k, v))
    logits = np.einsum("bqhd,bkhd->bhqk", np.asarray(q), kr) / np.sqrt(d)
    i, j = np.arange(s)[:, None], np.arange(s)[None, :]
    logits = np.where((i >= j) & (i - j < window), logits, -np.inf)
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    ref = np.einsum("bhqk,bkhd->bqhd", probs, vr)
    # float32 both sides, the kernel's sums in tiles: 1e-6 found
    assert np.max(np.abs(np.asarray(got) - ref)) < 1e-5


def test_the_band_kernel_refuses_by_name():
    from deepspeed_tpu.ops.pallas._utils import KernelUnsupported
    from deepspeed_tpu.ops.pallas.flash_attention import (
        flash_attention_band, flash_band_refusal)
    assert flash_band_refusal(16384, 128, 32, 4) is None
    assert "128-lane" in flash_band_refusal(256, 64, 8, 4)
    assert "do not divide" in flash_band_refusal(256, 128, 6, 4)
    assert "no tile" in flash_band_refusal(2052, 128, 32, 4)
    q = np.zeros((1, 8, 4, 16), np.float32)
    with pytest.raises(KernelUnsupported, match="128-lane"):
        flash_attention_band(q, q[:, :, :2], q[:, :, :2], 4)


def test_the_model_takes_the_band_kernel_where_its_gate_accepts(
        past_auto_path):
    """At heads of 128 ``"auto"`` resolved as on a TPU runs prefill through
    the kernel (interpreted here) and gives the einsum's numbers."""
    import jax
    config = dict(TOY, hidden_size=128, head_dim=128, num_hidden_layers=2,
                  layer_types=[S, F], sliding_window=16)
    model = arch.build_model(config)
    params = arch.init_params(model, jax.random.PRNGKey(9))
    ids = _ids(np.random.default_rng(9), 1, 48)
    logits, _ = model.apply({"params": params}, ids)
    assert ("attention_band", None) in past_auto_path
    ref, _ = arch.reference_logits(config, params, ids)
    assert np.max(np.abs(np.asarray(logits) - np.asarray(ref))) < ATOL


# -------------------------------------------- (c) scanned against unrolled
def test_the_scanned_stack_equals_the_same_layers_unrolled(toy):
    """The expert layers under ``lax.scan`` with traced kinds and slots
    (``lax.cond`` on the read) against ``afmoe_block`` called layer by layer
    with static ones, over the same weights: a full forward, and a decode
    step that is handed the cache."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models import afmoe
    model, params = toy
    cfg = model.cfg
    is_full, slot, _, _ = afmoe.layer_tables(cfg)
    assert is_full.tolist() == [False, False, False, True, False]
    assert slot.tolist() == [0, 1, 2, 0, 3]
    banks = {k: params["blocks"]["sparse"][k] for k in afmoe._BANKS}

    def unrolled(x, positions, leaves, cur):
        x = x * np.sqrt(cfg.d_model).astype(np.float32)
        for n in range(cfg.num_layers):
            group = "dense" if n == 0 else "sparse"
            i = n if n == 0 else n - 1
            p = {k: v[i] for k, v in params["blocks"][group].items()
                 if k not in afmoe._BANKS}
            x, state, _ = afmoe.afmoe_block(
                cfg, p, None if n == 0 else (banks, i), x, positions,
                bool(is_full[n]), leaves, cur, int(slot[n]))
            if leaves is not None:
                leaves = state
        return x, leaves

    def head(x):
        x = afmoe.rms_norm(x, params["ln_f"]["scale"], cfg.layer_norm_eps)
        return x @ params["lm_head"]["kernel"]

    ids = _ids(np.random.default_rng(10), 2, 24)
    embed = jnp.take(params["wte"]["embedding"], ids, axis=0)
    positions = jnp.broadcast_to(jnp.arange(24)[None], (2, 24))
    logits, _ = model.apply({"params": params}, ids)
    assert np.allclose(head(unrolled(embed, positions, None, None)[0]),
                       logits, atol=1e-5)
    # a decode step: lanes at 24 (the ring wrapped) and, retired, at 64
    (_, _), vc = model.apply({"params": params}, ids, mutable=["cache"])
    blocks = vc["cache"]["blocks"]
    cur = jnp.asarray([24, 7], jnp.int32)
    tok = jnp.asarray([[3], [4]])
    (got, _), after = model.apply(
        {"params": params, "cache": _with_cursor(vc["cache"], cur)}, tok,
        positions=cur[:, None], mutable=["cache"])
    x, leaves = unrolled(jnp.take(params["wte"]["embedding"], tok, axis=0),
                         cur[:, None],
                         tuple(blocks[k] for k in afmoe._LEAVES), cur)
    assert np.allclose(head(x), got, atol=1e-5)
    for name, leaf in zip(afmoe._LEAVES, leaves):
        assert np.allclose(leaf, after["cache"]["blocks"][name], atol=1e-6)


# ------------------------------------------ (d) what a lower precision does
@pytest.mark.parametrize("lowered", ["the program", "the cache"])
def test_a_lower_precision_fails_the_comparison(toy, lowered):
    """bfloat16 in the program's place (weights as they are), or in the
    cache's alone (the prefilled leaves rounded once), moves a logit of the
    cache path by more than ATOL allows."""
    import jax
    import jax.numpy as jnp
    model, params = toy
    rng = np.random.default_rng(11)
    prompts = [_ids(rng, 1, n)[0] for n in (5, 17, 37)]
    mark = None
    if lowered == "the program":
        model = type(model)(dataclasses.replace(model.cfg,
                                                dtype=jnp.bfloat16))
    else:
        def mark(cache):
            return jax.tree.map(
                lambda x: x.astype(jnp.bfloat16).astype(x.dtype)
                if x.dtype == jnp.float32 else x, cache)
    got, toks, _ = _through_the_cache(model, params, prompts, 4, mark=mark)
    worst = max(_worst(TOY, params, prompts, np.asarray(got, np.float32),
                       toks, 4))
    assert worst > 10 * ATOL, worst


# ---------------------------------------------- (e) the model, through serving
def _serve(config, params, **kw):
    """Six requests through a three-lane engine with chunks of 8 steps, so
    that inside ONE chunk a lane crosses the window's edge (prompt 13:
    positions 13-20), a lane is far from it (prompt 3), a lane is taken
    after a longer occupant (the 5-token prompt follows the 30-token one
    into its lane, whose ring has wrapped) and a lane is retired, its cursor
    pinned at ``max_seq_len``."""
    import jax.numpy as jnp
    from deepspeed_tpu.serving import ServingEngine
    model = arch.build_model(config)
    eng = ServingEngine(model, model_parameters=params, dtype=jnp.float32,
                        max_batch=3, decode_chunk=8, max_prompt_len=32,
                        prefill_buckets=[16, 32], **kw)
    rng = np.random.default_rng(6)
    prompts = [_ids(rng, 1, n)[0] for n in PROMPT_LENS]
    reqs = [eng.submit(p, max_new_tokens=m)
            for p, m in zip(prompts, BUDGETS)]
    snaps = []
    for _ in range(200):
        if not (eng.scheduler.has_work() or eng.chunk_in_flight):
            break
        eng.pump()
        snaps.append((dict(eng.scheduler.running),
                      {k: np.asarray(v) for k, v in
                       eng.kv.cache["blocks"].items()}))
    return eng, params, prompts, reqs, snaps


@pytest.fixture(scope="module")
def served(toy):
    return _serve(TOY, toy[1])


def test_served_tokens_are_the_model_s_own_token_for_token(served):
    """Against the model alone: greedy over ONE full forward at a time; and
    each token's reference logit is its row's largest."""
    import jax
    eng, params, prompts, reqs, _ = served
    model = arch.build_model(TOY)
    # causal: the padding behind a row changes nothing before it
    full = np.zeros((len(reqs), MAX), np.int32)
    for i, (prompt, req, budget) in enumerate(zip(prompts, reqs, BUDGETS)):
        assert req.status == "done" and len(req.tokens) == budget
        full[i, :len(prompt)] = prompt
        full[i, len(prompt):len(prompt) + budget] = req.tokens
    logits = np.asarray(jax.jit(
        lambda p, ids: model.apply({"params": p}, ids)[0])(params, full))
    ref = np.asarray(arch.reference_logits(TOY, params, full)[0])
    for i, (prompt, req) in enumerate(zip(prompts, reqs)):
        for j, tok in enumerate(req.tokens):
            row = len(prompt) - 1 + j
            assert tok == int(logits[i, row].argmax()), (len(prompt), j)
            assert ref[i, row].max() - ref[i, row][tok] < 1e-3, \
                (len(prompt), j)


def test_a_retired_lane_s_rows_stay_as_they_were(served):
    """From the pump after a lane's request ended to the pump before its
    next occupant's prefill is inserted, chunks run with that lane's cursor
    at ``max_seq_len``: all four of its leaves are bit for bit what they
    were."""
    assert _retired_lanes_held(served[4]) >= 3


def _retired_lanes_held(snaps):
    """(pump, lane) pairs a lane was nobody's on both sides of, after
    asserting that all four of its leaves are bit for bit what they were."""
    held = 0
    for (run0, leaves0), (run1, leaves1) in zip(snaps, snaps[1:]):
        for lane in range(3):
            if lane in run0 or lane in run1:
                continue                            # somebody's, or refilled
            for name in ("window_key", "window_value", "global_key",
                         "global_value"):
                assert np.array_equal(leaves0[name][:, lane],
                                      leaves1[name][:, lane]), (name, lane)
            held += 1
    return held


def test_the_arena_is_two_kinds_of_leaf_and_a_layer_owns_one(served):
    eng = served[0]
    leaves = eng.kv.cache["blocks"]
    assert leaves["window_key"].shape == (4, 3, W, 32)
    assert leaves["global_key"].shape == (1, 3, MAX, 32)
    assert leaves["cache_index"].shape == (1, 3)
    rep = eng.kv.arena_report()
    per_slot = (4 * W + MAX) * 2 * 32 * 4       # rows x (k, v) x 32 x f32
    assert rep["kv_bytes"] == 3 * per_slot
    assert rep["bytes_per_slot"] == per_slot
    assert rep["rows_per_slot"] == (4 * W + MAX) // 5 == 25
    assert eng.kv.head_dim(4) is None       # flat rows: no leaf has heads
    assert arch.lane_bytes(dict(TOY)) == per_slot // 2      # at 2 B a value
    # on the CPU "auto" takes the einsum (and the toy's flat rows of 32
    # values are not whole 128-lane rows): the step reads both kinds'
    # leaves of every lane whole
    assert eng.module.decode_read_block(3) is None
    assert eng._kv_read_block is None
    m = eng.metrics
    assert m.kv_blocks_read == m.kv_blocks_arena == m.decode_steps * 8 * 3


def test_the_chunk_program_counts_live_rows_from_positions(served):
    """The counters against a replay by hand: every decode step of a live
    lane at position t counts ``min(t + 1, 16)`` ring rows in each of the
    four sliding layers and ``t + 1`` global rows in the full one; every
    step reads both kinds' leaves of all three lanes; every pair is held."""
    eng, _, prompts, reqs, _ = served
    got = eng.metrics.state_rows
    ring = glob = steps = 0
    for prompt, req in zip(prompts, reqs):
        # a request's last token is sampled and never fed back
        for t in range(len(prompt), len(prompt) + len(req.tokens) - 1):
            ring += 4 * min(t + 1, W)
            glob += t + 1
            steps += 1
    assert got["kv_window_rows_live"] == ring
    assert got["kv_global_rows_live"] == glob
    assert got["kv_rows_read"] == eng.metrics.decode_steps * 8 * 3 \
        * (4 * W + MAX) > ring + glob
    routing = eng.metrics.routing
    assert routing["decode_pairs_absent"] == routing["prefill_pairs_absent"] \
        == 0
    assert routing["decode_pairs_held"] == steps * 4 * 2
    assert routing["prefill_pairs_held"] == sum(PROMPT_LENS) * 4 * 2


# 8 query heads on 2 key heads of 64: flat rows of 128 values, the widths
# the live-rows read takes (the toy's rows of 32 values are not); float32
# holds h to whole tiles of 8 rows. Blocks of 16 rows: the whole ring, a
# quarter of a global leaf
KERNEL_TOY = dict(TOY, num_attention_heads=8, head_dim=64)
KERNEL_BLOCK = 16


@pytest.fixture(scope="module")
def served_by_both_reads():
    """``KERNEL_TOY``'s requests as ``served``'s, once through the masked
    einsum and once with the live-rows read's ``"auto"`` resolved as on a
    TPU (the kernel, interpreted) for as long as the engine is built and
    runs; every other kernel's choice stays the CPU's."""
    import jax
    from deepspeed_tpu.ops.pallas import _utils as kernels
    model = arch.build_model(KERNEL_TOY)
    params = jax.jit(lambda k: arch.init_params(model, k))(
        jax.random.PRNGKey(3))
    einsum = _serve(KERNEL_TOY, params)
    cpu_path = kernels.auto_path
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(kernels, "auto_path", lambda kernel, why: (
            why is None if kernel == "decode_attention"
            else cpu_path(kernel, why)))
        return einsum, _serve(KERNEL_TOY, params)


def test_served_tokens_through_the_live_read_are_the_einsum_s(
        served_by_both_reads):
    """Token for token the einsum path's, lanes crossing the window's edge,
    wrapped rings and retired lanes in the same chunks."""
    einsum, kernel = served_by_both_reads
    assert einsum[0]._kv_read_block is None
    assert kernel[0]._kv_read_block == KERNEL_BLOCK
    for by_einsum, by_kernel, budget in zip(einsum[3], kernel[3], BUDGETS):
        assert by_einsum.status == by_kernel.status == "done"
        assert len(by_kernel.tokens) == budget
        assert list(by_kernel.tokens) == list(by_einsum.tokens)


def test_the_live_read_counts_the_blocks_it_reads(served_by_both_reads):
    """``kv_rows_read`` against a replay by hand: a live lane at position t
    reads ``ceil(min(t + 1, 16) / 16)`` blocks of 16 rows in each of the
    four sliding layers and ``ceil((t + 1) / 16)`` in the full one; a
    retired lane reads nothing, so the rows read follow the requests alone
    and not the chunks. The rows live are the einsum path's."""
    einsum, kernel = served_by_both_reads
    prompts, reqs = kernel[2], kernel[3]
    ring = glob = 0
    for prompt, req in zip(prompts, reqs):
        for t in range(len(prompt), len(prompt) + len(req.tokens) - 1):
            ring += 4 * -(-min(t + 1, W) // KERNEL_BLOCK)
            glob += -(-(t + 1) // KERNEL_BLOCK)
    got = kernel[0].metrics.state_rows
    assert got["kv_rows_read"] == KERNEL_BLOCK * (ring + glob)
    assert got["kv_rows_read"] < einsum[0].metrics.state_rows["kv_rows_read"]
    for name in ("kv_window_rows_live", "kv_global_rows_live"):
        assert got[name] == einsum[0].metrics.state_rows[name]
    m = kernel[0].metrics
    assert 0 < m.kv_blocks_read < m.kv_blocks_arena


def test_a_retired_lane_s_rows_stay_as_they_were_through_the_live_read(
        served_by_both_reads):
    assert _retired_lanes_held(served_by_both_reads[1][4]) >= 3


def test_step_counters_count_live_lanes_only(toy):
    import jax.numpy as jnp
    model = toy[0]
    got = model.step_counters(jnp.array([7, 16, 40, 64]),
                              jnp.array([True, True, False, False]))
    assert {k: int(v) for k, v in got.items()} == {
        "kv_window_rows_live": 4 * (8 + 16), "kv_global_rows_live": 8 + 17,
        "kv_rows_read": 4 * (4 * 16 + 64)}
    assert model.lane_rows() == 25
    assert model.blocks_read(np.array([7, 16, 40]), 8).tolist() \
        == [(4 * 1 + 1) / 5, (4 * 2 + 3) / 5, (4 * 2 + 6) / 5]


def _cell_cfg(**over):
    """``trinity-mini-l5``'s attention at the cell's shape: 32 query heads
    on 4 key heads of 128, a ring of 2,048 rows beside 20,480 global rows,
    bfloat16."""
    import jax.numpy as jnp
    from deepspeed_tpu.models import afmoe
    from deepspeed_tpu.models.gpt import GPTConfig
    block = dict(num_kv_heads=4, head_dim=128, sliding_window=2048,
                 layer_types=(S, S, S, F, S), n_routed_experts=128,
                 moe_d_ff=1024)
    block.update({k: v for k, v in over.items() if k in block})
    kw = dict(vocab_size=200192, max_seq_len=20480, num_layers=5,
              num_heads=32, d_model=2048, d_ff=6144, dtype=jnp.bfloat16)
    kw.update({k: v for k, v in over.items() if k not in block})
    return GPTConfig(block=afmoe.AfmoeBlockConfig(**block), **kw)


def test_the_cells_shape_takes_the_live_read_in_blocks_of_512_rows(
        past_auto_path):
    """``"auto"`` resolved as on a TPU: the live-rows read takes grouped
    heads over flat rows, asked by the name the NeoX read is asked by, in
    blocks of 512 flat rows of 512 bf16 values (512 KiB a leaf)."""
    from deepspeed_tpu.models import afmoe
    assert afmoe.decode_read_block(_cell_cfg(), 64) == 512
    assert past_auto_path == [("decode_attention", None)]
    assert afmoe.decode_read_block(_cell_cfg(decode_impl="xla"), 64) is None


@pytest.mark.parametrize("over,named", [
    (dict(head_dim=16), "a flat row of 64 values is not whole 128-lane rows"),
    (dict(num_heads=12, num_kv_heads=3), "h=12 heads are not whole 16-row"),
    (dict(num_heads=32, num_kv_heads=3, head_dim=128),
     "not key heads of d=128 that h=32 query heads share evenly"),
    (dict(max_seq_len=20000), "20000 is not a multiple of the 512-row"),
    (dict(sliding_window=1280), "1280 is not a multiple of the 512-row"),
], ids=["row of 64", "12 heads", "3 key heads", "global rows", "ring rows"])
def test_the_grouped_gate_refuses_by_name(past_auto_path, over, named):
    """What the read cannot take, each refused by name through
    ``auto_path``: the step then reads both leaves whole with the einsum."""
    from deepspeed_tpu.models import afmoe
    assert afmoe.decode_read_block(_cell_cfg(**over), 64) is None
    (kernel, refusal), = past_auto_path
    assert kernel == "decode_attention" and named in refusal, refusal


@pytest.mark.parametrize("asked,named", [
    (dict(speculative=True), "speculative"),
    (dict(fused_prefill=True), "fused_prefill"),
    (dict(paged=True), "paged"),
    (dict(kv_dtype="int8"), "int8"),
    (dict(tp=2), "tp=2"),
])
def test_what_the_block_cannot_be_served_with_raises_at_construction(
        toy, asked, named):
    import jax.numpy as jnp
    from deepspeed_tpu.serving import ServingEngine
    model, params = toy
    with pytest.raises(NotImplementedError, match=named):
        ServingEngine(model, model_parameters=params, dtype=jnp.float32,
                      max_batch=2, max_prompt_len=32, **asked)


def test_the_block_kind_names_its_own_stack(toy):
    """``GPT`` finds the kind by the module of its config: the stack, the
    final norm, the counters and the refusals are ``models/afmoe.py``'s; the
    head is the trunk's untied one."""
    from deepspeed_tpu.models import afmoe
    from deepspeed_tpu.models.gpt import _kind
    model, params = toy
    kind = _kind(model.cfg.block)
    assert kind is afmoe and kind.Stack is afmoe.AfmoeStack
    assert not hasattr(kind, "Head")
    assert model.prefill_takes_lengths
    assert set(params) == {"wte", "blocks", "ln_f", "lm_head"}
    assert model.serving_refusal() is None
    with pytest.raises(ValueError, match="layer_types"):
        afmoe.AfmoeBlockConfig(num_kv_heads=2, head_dim=16, sliding_window=8,
                               layer_types=("sliding", "full_attention"))
    short = dataclasses.replace(model.cfg, num_layers=4)
    with pytest.raises(ValueError, match="5 layer_types for 4 layers"):
        afmoe.layer_tables(short)
