"""The decode step's read of each lane's LIVE rows (ISSUE 29):
``ops/pallas/decode_attention.live_decode_attention`` against the masked
einsum, its gate, the model's choice (``models/gpt.live_read_block``), the
serving engine through it, and the two counters that say what a step read.

On the CPU the kernel runs in the Pallas interpreter and ``"auto"`` takes
the einsum, so the tests that drive the kernel through the model force the
choice past ``kernels.auto_path`` (the gate still decides)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.models.gpt import GPT, GPTConfig, live_read_block
from deepspeed_tpu.ops.pallas import _utils as kernels
from deepspeed_tpu.ops.pallas import decode_attention as da
from deepspeed_tpu.ops.pallas.decode_attention import (
    live_decode_attention, live_decode_refusal, masked_cache_attention)
from deepspeed_tpu.serving import ServingEngine

L, B, S, D, BK = 3, 5, 64, 128, 16
SENTINEL = S + 1        # the fill of a lane whose write position is max_seq
S2 = 2 * S              # rows of the second pair's leaves (the summaries)


def _leaves(dtype, h, seed=0, rows=(S,)):
    """q and a (k leaf, v leaf) a pair, layer-stacked."""
    keys = iter(jax.random.split(jax.random.PRNGKey(seed),
                                 1 + 2 * len(rows)))
    q = jax.random.normal(next(keys), (B, 1, h, D), dtype)
    return (q,) + tuple(jax.random.normal(next(keys), (L, B, n, h, D), dtype)
                        for n in rows for _ in "kv")


def _one_softmax(q, pairs, layer):
    """The reference over the whole leaves: the masked einsum for one pair,
    ``eva._joint_attention`` for a window pair beside a summary pair."""
    from deepspeed_tpu.models.eva import _joint_attention
    if len(pairs) == 1:
        (k, v, fills), = pairs
        return masked_cache_attention(q, k[layer], v[layer], fills - 1,
                                      1.0 / np.sqrt(D))
    seen = [jnp.arange(k.shape[2]) < f[:, None, None, None]
            for k, _, f in pairs]
    (kw, vw, _), (ks, vs, _) = pairs
    return _joint_attention(q, kw[layer], vw[layer], seen[0], ks[layer],
                            vs[layer], seen[1], q.dtype)


def _dead_rows_filled(leaf, layer, fills, blocks, partial):
    """``leaf`` with ``blocks`` in every row of ``layer`` that lies in a
    block NO live lane's fill reaches (a masked lane's rows all do) and
    ``partial`` in the dead rows of a block a fill ends inside."""
    rows = leaf.shape[2]
    fills = np.asarray(fills)
    reach = np.where(fills > rows, 0, -(-fills // BK) * BK)[:, None]
    at = np.arange(rows)[None, :]
    fill = np.where(fills > rows, 0, fills)[:, None]
    garbage = np.where(at >= reach, blocks,
                       np.where(at >= fill, partial, 0.0))
    mask = (at >= fill)[..., None, None]
    rows_l = jnp.where(mask, jnp.asarray(garbage, leaf.dtype)[..., None, None],
                       leaf[layer])
    return leaf.at[layer].set(rows_l)


# one pair: a NeoX block's keys and values, the fills of five lanes
ONE_PAIR = {
    "edges": [(1, BK, BK + 1, S, SENTINEL)],    # a masked lane last
    "masked-between": [(SENTINEL, 2 * BK, SENTINEL, 7, S - 1)],
    "full": [(S, S, S, S, S)],                  # every block of every lane
    "all-masked": [(SENTINEL,) * B],            # nothing live: no DMA at all
}
# two pairs under one softmax: a window of S rows beside S2 summary rows. A
# lane inside its first window (no summary block), on a window's last row,
# on a window's first row, a dead lane (both fills past their leaves), a
# fill that ends inside a summary block
TWO_PAIRS = {
    "window-and-summaries": [(5, S, 1, S + 1, BK + 3),
                             (0, 3 * BK, 2 * BK, S2 + 1, 2 * BK + 5)],
    "first-window-only": [(1, BK, S, BK + 1, 7), (0,) * B],
    "both-full": [(S,) * B, (S2,) * B],
    "all-dead": [(S + 1,) * B, (S2 + 1,) * B],
}
CASES = dict(ONE_PAIR, **TWO_PAIRS)


@pytest.mark.parametrize("case", list(CASES))
@pytest.mark.parametrize("dtype,h,tol", [(jnp.bfloat16, 16, 2e-2),
                                         (jnp.float32, 8, 2e-5)],
                         ids=["bf16", "f32"])
def test_live_rows_read_matches_the_masked_einsum(dtype, h, tol, case):
    """Layer-stacked leaves at a TRACED layer index: live lanes agree with
    the ONE masked softmax over that layer's whole rows (one pair: the
    masked einsum; two: ``eva._joint_attention``) to the einsum's own
    tolerance, a masked lane's output is zeros nobody reads. What a dead
    row holds never reaches the output: NaN in every block no fill reaches
    (never read), 1e30 in the dead rows of a block a fill ends in (read and
    masked)."""
    fills = [jnp.asarray(f, jnp.int32) for f in CASES[case]]
    rows = (S, S2)[:len(fills)]
    q, *leaves = _leaves(dtype, h, rows=rows)
    layer = 1
    pairs = [(leaves[2 * i], leaves[2 * i + 1], f)
             for i, f in enumerate(fills)]
    dirty = [tuple(_dead_rows_filled(x, layer, f, np.nan, 1e30)
                   for x in (k, v)) + (f,) for k, v, f in pairs]
    clean = [tuple(_dead_rows_filled(x, layer, f, 0.0, 0.0)
                   for x in (k, v)) + (f,) for k, v, f in pairs]
    got = jax.jit(lambda q, pairs, i: live_decode_attention(
        q, pairs, i, block_k=BK))(q, dirty, jnp.int32(layer))
    assert got.shape == q.shape and got.dtype == q.dtype
    ref = _one_softmax(q, clean, layer)
    live = np.all([np.asarray(f) <= n for f, n in zip(fills, rows)], axis=0)
    got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
    np.testing.assert_allclose(got[live], ref[live], atol=tol, rtol=tol)
    assert not got[~live].any()


# grouped heads over FLAT rows (models/afmoe.py): 32 query heads on 4 key
# heads of 128, a ring-sized pair (a few blocks) and a global-sized one. The
# five lanes: an empty fill, one row, a fill inside a block, the whole
# leaf, a fill past the leaf (a masked lane)
GH, GHK = 32, 4
GROUPED_FILLS = {"ring": (4 * BK, (0, 1, BK + 5, 4 * BK, 4 * BK + 1)),
                 "global": (16 * BK, (0, 1, 3 * BK + 7, 16 * BK, 16 * BK + 1))}


@pytest.mark.parametrize("pair", list(GROUPED_FILLS))
@pytest.mark.parametrize("dtype,tol", [(jnp.bfloat16, 2e-2),
                                       (jnp.float32, 2e-5)],
                         ids=["bf16", "f32"])
def test_grouped_heads_over_flat_rows_match_cache_attention(dtype, tol,
                                                            pair):
    """Layer-stacked flat leaves ``[L, b, S, hk * d]`` at a traced layer:
    lanes with a live row agree with ``afmoe.cache_attention`` (the queries
    widened to the rows' columns, the masked einsum over the layer's whole
    rows) to the einsum's own tolerance; a lane with no live row, or with
    a fill past the leaf, reads nothing and is zeros. NaN in every block no
    fill reaches (never read) and 1e30 in the dead rows of a block a fill
    ends in (read and masked) never reach the output."""
    from deepspeed_tpu.models.afmoe import cache_attention
    rows, fills = GROUPED_FILLS[pair]
    fills = jnp.asarray(fills, jnp.int32)
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(keys[0], (B, 1, GH, D), dtype)
    k, v = (jax.random.normal(key, (L, B, rows, GHK * D), dtype)
            for key in keys[1:])
    layer = 2

    def filled(leaf, blocks, partial):
        return _dead_rows_filled(leaf[..., None], layer, fills, blocks,
                                 partial)[..., 0]
    got = jax.jit(lambda q, k, v, f, i: live_decode_attention(
        q, [(k, v, f)], i, block_k=BK))(
            q, filled(k, np.nan, 1e30), filled(v, np.nan, 1e30), fills,
            jnp.int32(layer))
    assert got.shape == q.shape and got.dtype == q.dtype
    seen = jnp.arange(rows)[None, :] < fills[:, None]
    ref = cache_attention(q, filled(k, 0.0, 0.0)[layer],
                          filled(v, 0.0, 0.0)[layer], seen, GHK, dtype)
    live = np.asarray((fills > 0) & (fills <= rows))
    got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
    np.testing.assert_allclose(got[live], ref[live], atol=tol, rtol=tol)
    assert not got[~live].any()


@pytest.mark.parametrize("layout", ["rank-5 heads", "flat grouped rows"])
def test_one_kernel_reads_either_layout_by_the_leaf_s_rank(layout):
    """The rank-5 leaf of a head a query head (NeoX, EvaByte) and the flat
    leaf of grouped heads go through the ONE kernel, told apart by the
    leaf's rank alone: each equals its own masked einsum at the same
    fills."""
    from deepspeed_tpu.models.afmoe import cache_attention
    fills = jnp.asarray((1, BK, BK + 1, S, 7), jnp.int32)
    keys = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(keys[0], (B, 1, 8, D), jnp.float32)
    if layout == "rank-5 heads":
        k, v = (jax.random.normal(key, (L, B, S, 8, D)) for key in keys[1:])
        ref = masked_cache_attention(q, k[1], v[1], fills - 1,
                                     1.0 / np.sqrt(D))
    else:
        k, v = (jax.random.normal(key, (L, B, S, 2 * D)) for key in keys[1:])
        ref = cache_attention(q, k[1], v[1],
                              jnp.arange(S)[None, :] < fills[:, None], 2,
                              jnp.float32)
    got = live_decode_attention(q, [(k, v, fills)], 1, block_k=BK)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def _kernel_ops(fn, *args):
    """(primitive name, output shape) of every equation of the one
    ``pallas_call``'s kernel that ``fn`` traces to, nested bodies included."""
    def walk(jaxpr):
        for e in jaxpr.eqns:
            yield e.primitive.name, tuple(tuple(v.aval.shape)
                                          for v in e.outvars)
            for p in e.params.values():
                for sub in (p if isinstance(p, (list, tuple)) else [p]):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        yield from walk(sub)
    calls = [e for e in jax.make_jaxpr(fn)(*args).jaxpr.eqns
             if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    return list(walk(calls[0].params["jaxpr"]))


@pytest.mark.parametrize("pairs", [1, 2], ids=["one pair", "two pairs"])
def test_the_rank5_call_traces_none_of_the_flat_branch(pairs):
    """The layout is chosen while tracing, from the leaf's rank: the rank-5
    kernel (NeoX one pair, EvaByte two) meets the h queries with
    ``block_k * h`` key rows, scores ``[h, block_k * h]`` under the
    own-head mask ``col % h == row``, and keeps the whole ``[h, d]``
    context a lane; none of the flat branch's ``[h, block_k]`` scores or
    its per-key-head selection of the result is in it. The flat call is
    the other way round."""
    h, hk = 16, 4
    q = jnp.zeros((B, 1, h, D), jnp.bfloat16)
    f = jnp.zeros((B,), jnp.int32)
    rank5 = [jnp.zeros((L, B, n, h, D), jnp.bfloat16) for n in (S, S2)]
    flat = [jnp.zeros((L, B, n, hk * D), jnp.bfloat16) for n in (S, S2)]

    def call(*leaves):
        return live_decode_attention(
            q, [(k, k, f) for k in leaves[:pairs]], 1, block_k=BK)
    for leaves, mine, other in ((rank5, (h, BK * h), (h, BK)),
                                (flat, (h, BK), (h, BK * h))):
        ops = _kernel_ops(call, *leaves)
        dots = [outs[0] for name, outs in ops if name == "dot_general"]
        assert mine in dots and other not in dots
        own_head_mask = any(name == "rem" and out == ((h, BK * h),)
                            for name, out in ops)
        head_select = any(name == "iota" and out == ((h, 1),)
                          for name, out in ops)
        assert own_head_mask == (leaves is rank5)
        assert head_select == (leaves is flat)


def test_one_layers_own_leaf_and_a_scalar_fill():
    """``layer`` None is the unscanned model's [b, S, h, d] leaf, a scalar
    fill the single-stream ``generate()``: the same read."""
    q, kl, vl = _leaves(jnp.float32, 8, seed=1)
    got = live_decode_attention(q, [(kl[2], vl[2], jnp.int32(BK + 3))],
                                block_k=BK)
    ref = masked_cache_attention(q, kl[2], vl[2], BK + 2, 1.0 / np.sqrt(D))
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


REFUSED = {
    "speculative width": (dict(s=5), "more than one query"),
    "prefill width": (dict(s=512), "more than one query"),
    "int8 cache": (dict(dtype=jnp.int8), "no dequant"),
    "head of 64": (dict(d=64), "lane-padded"),
    "12 heads": (dict(h=12), "sublane"),
    "ragged length": (dict(S=2000), "not a multiple"),
}


@pytest.mark.parametrize("case", list(REFUSED))
def test_the_gate_names_why_it_refuses(case):
    shape = dict(b=8, S=2048, h=32, d=128, dtype=jnp.bfloat16, s=1)
    over, names = REFUSED[case]
    shape.update(over)
    reason = live_decode_refusal(**shape)
    assert reason is not None and names in reason, reason
    with pytest.raises(kernels.KernelUnsupported, match=names):
        s, dt = shape["s"], shape["dtype"]
        leaf = jnp.zeros((2, shape["b"], shape["S"], shape["h"], shape["d"]),
                         dt)
        live_decode_attention(
            jnp.zeros((shape["b"], s, shape["h"], shape["d"]), jnp.bfloat16),
            [(leaf, leaf, jnp.ones((shape["b"],), jnp.int32))], 0)


def test_the_gate_accepts_the_cells_shape():
    """``serve-batch``: 8 lanes of 2048 rows, 32 heads of 128, bf16."""
    assert live_decode_refusal(8, 2048, 32, 128, jnp.bfloat16) is None
    assert da.live_block(2048) == 128


@pytest.mark.parametrize("S,row_bytes,block", [
    (2048, None, 128),          # rows of a head a query head: 128 rows
    (2048, 8192, 128),          # 4,096 bf16 values a flat row: never fewer
    (2048, 1024, 512),          # serve-agent's 512 bf16 values: 512 KiB
    (20480, 768, 512),          # 384 values: the power of two under 682
    (16, 512, 16),              # never more rows than the leaf holds
], ids=["heads", "4096 values", "512 values", "384 values", "short leaf"])
def test_a_block_of_flat_rows_is_chosen_by_their_bytes(S, row_bytes, block):
    assert da.live_block(S, row_bytes) == block


def test_the_gate_is_asked_of_every_pairs_leaf():
    """``serve-longdoc``: 16 lanes of 2,048 window rows beside 2,048
    summary rows; one block size serves both leaves."""
    assert live_decode_refusal(16, (2048, 2048), 32, 128, jnp.bfloat16) is None
    assert "2000 is not a multiple of the 128-row block" in \
        live_decode_refusal(16, (2048, 2000), 32, 128, jnp.bfloat16)
    assert "96 is not a multiple of the 64-row block" in \
        live_decode_refusal(16, (96, 64), 32, 128, jnp.bfloat16)


def _cell_cfg(**kw):
    return GPTConfig(vocab_size=50432, max_seq_len=2048, num_layers=16,
                     num_heads=32, d_model=4096, d_ff=16384, rotary=True,
                     rotary_pct=0.25, parallel_residual=True,
                     tie_embeddings=False, dtype=jnp.bfloat16, **kw)


def test_the_default_is_auto_and_the_cpu_keeps_the_einsum():
    assert GPTConfig().decode_impl == "auto"
    assert live_read_block(_cell_cfg(), 8) is None      # Pallas would interpret


@pytest.mark.parametrize("case,kw,call,block", [
    ("the cell", {}, dict(s=1), 128),
    ("speculative width", {}, dict(s=5), None),
    ("fused-prefill width", {}, dict(s=16), None),
    ("window layer", {}, dict(s=1, window=256), None),
    ("int8 cache", dict(kv_cache_dtype="int8"), dict(s=1), None),
    ("xla by name", dict(decode_impl="xla"), dict(s=1), None),
    ("the flat kernel by name", dict(decode_impl="pallas"), dict(s=1), None),
], ids=lambda v: v if isinstance(v, str) else None)
def test_what_the_model_chooses_from_what_a_trace_sees(past_auto_path, case,
                                                       kw, call, block):
    assert live_read_block(_cell_cfg(**kw), 8, **call) == block
    if block is None and "decode_impl" not in kw:
        (kernel, refusal), = past_auto_path
        assert kernel == "decode_attention" and refusal      # named, logged


def _tiny_engine(max_seq=48):
    import deepspeed_tpu as ds
    cfg = GPTConfig(vocab_size=64, max_seq_len=max_seq, num_layers=2,
                    num_heads=8, d_model=8 * D, d_ff=64, dtype=jnp.float32,
                    param_dtype=jnp.float32, remat=False)
    model = GPT(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 4), jnp.int32))["params"]
    return ds.init_inference(model, model_parameters=params,
                             dtype=jnp.float32)


@pytest.fixture(scope="module")
def tiny_engine():
    return _tiny_engine()


@pytest.fixture
def blocks_of_16(monkeypatch):
    monkeypatch.setattr(da, "_LIVE_BLOCK", BK)


@pytest.mark.parametrize("decode_chunk", [1, 8])
def test_greedy_parity_with_generate_through_the_live_read(
        tiny_engine, past_auto_path, blocks_of_16, decode_chunk):
    """More requests than lanes, fills that cross block edges, lanes that
    retire mid-chunk (the sentinel beside live lanes): every request's
    tokens are ``generate()``'s, both through the kernel."""
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 64, (n,)).astype(np.int32)
               for n in [3, 15, 5, 9, 14, 6]]
    serving = ServingEngine(engine=tiny_engine, max_batch=3,
                            max_prompt_len=16, max_queue=8,
                            decode_chunk=decode_chunk)
    assert serving._kv_read_block == BK
    results = serving.run(prompts, max_new_tokens=20)
    assert ("decode_attention", None) in past_auto_path
    for p, r in zip(prompts, results):
        assert r.status == "done"
        ref = np.asarray(tiny_engine.generate(
            p[None], max_new_tokens=20, temperature=0.0))[0]
        np.testing.assert_array_equal(r.output_ids, ref)
    m = serving.metrics
    assert 0 < m.kv_blocks_read < m.kv_blocks_arena


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def test_the_chunk_program_slices_no_arena_leaf(tiny_engine, past_auto_path):
    """The kernel is handed the layer-stacked leaves WHOLE: no
    ``dynamic_slice`` anywhere in the chunk program produces a layer's rows
    (a custom call's operand has to exist, so a slice of the leaf before it
    is a copy of a layer's cache every layer, every step)."""
    serving = ServingEngine(engine=tiny_engine, max_batch=3,
                            max_prompt_len=16, max_queue=8, decode_chunk=8)
    b, s, h = 3, 48, 8
    state = (jnp.zeros((b,), jnp.int32), jnp.zeros((b,), jnp.int32),
             jnp.ones((b,), bool), jnp.full((b,), -1, jnp.int32),
             jnp.full((b,), 4, jnp.int32))
    jaxpr = jax.make_jaxpr(serving._jit_decode_chunk)(
        serving._decode_params, serving.kv.cache, *state,
        jax.random.PRNGKey(0)).jaxpr
    eqns = list(_eqns(jaxpr))
    calls = [e for e in eqns if e.primitive.name == "pallas_call"]
    assert calls, "the forced chunk program holds no kernel"
    for call in calls:
        leaves = [v.aval.shape for v in call.invars
                  if len(v.aval.shape) == 5]
        assert leaves == [(2, b, s, h, D)] * 2, leaves
    rows = {(b, s, h, D), (1, b, s, h, D)}
    sliced = [e for e in eqns
              if e.primitive.name in ("dynamic_slice", "gather", "slice")
              and any(v.aval.shape in rows for v in e.outvars)]
    assert not sliced, sliced


def _run_one(engine, prompt_len, new_tokens, decode_chunk):
    serving = ServingEngine(engine=engine, max_batch=3, max_prompt_len=16,
                            max_queue=8, decode_chunk=decode_chunk)
    prompt = np.arange(prompt_len, dtype=np.int32) % 64
    res, = serving.run([prompt], max_new_tokens=new_tokens)
    assert res.status == "done" and len(res.tokens) == new_tokens
    return serving


def test_the_counters_against_a_hand_count(tiny_engine, past_auto_path,
                                           blocks_of_16, telemetry_on):
    """One request, 14 prompt rows, 6 tokens: prefill samples the first,
    five decode steps read fills 15..19 of one lane in blocks of 16:
    1 + 1 + 2 + 2 + 2 = 8 blocks. The arena a step would read is 3 lanes x
    48 / 16 blocks, 8 steps a chunk."""
    serving = _run_one(tiny_engine, 14, 6, decode_chunk=8)
    m = serving.metrics
    chunks = m.decode_steps
    assert m.kv_blocks_read == 8
    assert m.kv_blocks_arena == chunks * 8 * 3 * 3
    totals = telemetry_on.counter_totals()
    assert totals["serve/kv_blocks_read"] == 8
    assert totals["serve/kv_blocks_arena"] == m.kv_blocks_arena
    assert m.snapshot(0, 0.0)["serving/kv_read_share"] == \
        pytest.approx(8 / m.kv_blocks_arena)


@pytest.mark.parametrize("family", [{}, dict(speculative=True, spec_k=3),
                                    dict(paged=True, kv_block_size=8)],
                         ids=["dense", "speculative", "paged"])
def test_the_einsum_path_counts_the_whole_arena(tiny_engine, family):
    """On the CPU, and at every width and layout the gate refuses, a step
    reads every row of every lane: the share is 100 %."""
    serving = ServingEngine(engine=tiny_engine, max_batch=3,
                            max_prompt_len=16, max_queue=8, decode_chunk=8,
                            **family)
    assert serving._kv_read_block is None
    serving.run([np.arange(9, dtype=np.int32)], max_new_tokens=6)
    m = serving.metrics
    assert m.kv_blocks_read == m.kv_blocks_arena > 0
    assert m.kv_read_share == 1.0


@pytest.mark.parametrize("heads,d_model,refused", [(12, 768, None),
                                                   (3, 60, r"h\*d=60")],
                         ids=["125m", "h*d=60"])
def test_megakernels_gate_is_asked_before_the_arena_exists(
        monkeypatch, heads, d_model, refused):
    """``megakernel=True`` on the chip rebuilds the module with
    ``decode_impl="pallas"`` and asks the all-lanes kernel's gate at
    construction, BEFORE ``self.kv`` is built (on a v5e the check read
    ``self.kv`` and died with an AttributeError: my chip run, PR 29; the
    CPU never reaches that line). Driven here on a stand-in for the
    half-built engine with the platform patched."""
    from types import SimpleNamespace
    from deepspeed_tpu.utils import platform
    monkeypatch.setattr(platform, "on_chip", lambda: True)
    cfg = GPTConfig(vocab_size=50304, max_seq_len=1024, num_layers=1,
                    num_heads=heads, d_model=d_model, d_ff=64,
                    dtype=jnp.bfloat16, decode_impl="pallas")
    half_built = SimpleNamespace(
        max_batch=8, max_seq_len=1024, engine=SimpleNamespace(mesh=None),
        speculative=False, spec_k=0, fused_prefill=False, prefill_chunk=16,
        kv_dtype="auto", paged=False)
    check = lambda: ServingEngine._check_megakernel_gates(half_built, cfg, 16)
    if refused is None:
        check()
    else:
        with pytest.raises(kernels.KernelUnsupported, match=refused):
            check()
