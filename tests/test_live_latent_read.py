"""The latent block's decode read of each lane's LIVE rows (ISSUE 33):
``ops/pallas/decode_attention.live_latent_attention`` against
``models/mla.py``'s masked einsum over the whole leaf, its gate, the model's
choice (``mla.decode_read_block``), the serving engine through it, and the
two counters that say what a step read.

On the CPU the kernel runs in the Pallas interpreter and ``"auto"`` takes
the einsum, so the tests that drive the kernel through the model force the
choice past ``kernels.auto_path`` (the gate still decides)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chipbench.archs import pangu_ultra_moe as arch
from deepspeed_tpu.models import mla
from deepspeed_tpu.ops.pallas import _utils as kernels
from deepspeed_tpu.ops.pallas import decode_attention as da
from deepspeed_tpu.ops.pallas.decode_attention import (
    live_latent_attention, live_latent_refusal)
from deepspeed_tpu.serving import ServingEngine

L, B, S, ROW, R, BK = 3, 5, 64, 256, 128, 16
SENTINEL = S + 1        # the fill of a lane whose write position is max_seq
SCALE = 0.09


def _operands(dtype, h, seed=0, lanes=B, rows=S):
    kq, kl = jax.random.split(jax.random.PRNGKey(seed))
    return (jax.random.normal(kq, (lanes, h, ROW), dtype),
            jax.random.normal(kl, (L, lanes, rows, ROW), dtype))


def _whole_leaf(q_row, rows, fills, v_width=R):
    """``models/mla.py``'s masked einsum over every row of every lane, its
    value columns, rounded as the model rounds them (the operands widened
    first: the CPU's dot takes no bfloat16 pair into a float32 result)."""
    out = mla._absorbed_over_the_whole_leaf(
        q_row[:, None].astype(jnp.float32), rows.astype(jnp.float32),
        jnp.asarray(fills, jnp.int32) - 1, SCALE)
    return out[:, 0, :, :v_width].astype(q_row.dtype)


def _dead_rows_filled(leaf, layer, fills, block, blocks, partial):
    """``leaf`` with ``blocks`` in every row of ``layer`` that lies in a
    block NO fill of its lane reaches (a masked lane's rows all do) and
    ``partial`` in the dead rows of a block a fill ends inside."""
    rows = leaf.shape[2]
    fills = np.asarray(fills)
    reach = np.where(fills > rows, 0, -(-fills // block) * block)[:, None]
    fill = np.where(fills > rows, 0, fills)[:, None]
    at = np.arange(rows)[None, :]
    garbage = np.where(at >= reach, blocks,
                       np.where(at >= fill, partial, 0.0))
    rows_l = jnp.where((at >= fill)[..., None],
                       jnp.asarray(garbage, leaf.dtype)[..., None],
                       leaf[layer])
    return leaf.at[layer].set(rows_l)


FILLS = {
    "edges": (1, BK - 1, BK, S, SENTINEL),      # a masked lane last
    "masked-between": (SENTINEL, 2 * BK, SENTINEL, 7, S - 1),
    "one-over-an-edge": (BK + 1, 2 * BK + 1, 3 * BK + 1, 2, 3),
    "full": (S,) * B,                           # every block of every lane
    "all-masked": (SENTINEL,) * B,              # nothing live: no read
}


def _check(dtype, h, tol, fills, block, lanes=B, rows=S, layer=1):
    q_row, leaf = _operands(dtype, h, lanes=lanes, rows=rows)
    fills = jnp.asarray(fills, jnp.int32)
    dirty = _dead_rows_filled(leaf, layer, fills, block, np.nan, 1e30)
    clean = _dead_rows_filled(leaf, layer, fills, block, 0.0, 0.0)
    got = jax.jit(lambda q, leaf, f, i: live_latent_attention(
        q, leaf, f, i, SCALE, R, block_k=block))(
            q_row, dirty, fills, jnp.int32(layer))
    assert got.shape == (lanes, h, R) and got.dtype == dtype
    ref = _whole_leaf(q_row, clean[layer], fills)
    live = np.asarray(fills) <= rows
    got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
    np.testing.assert_allclose(got[live], ref[live], atol=tol, rtol=tol)
    assert not got[~live].any()


@pytest.mark.parametrize("case", list(FILLS))
@pytest.mark.parametrize("dtype,h,tol", [(jnp.bfloat16, 16, 2e-2),
                                         (jnp.float32, 8, 2e-5)],
                         ids=["bf16", "f32"])
def test_latent_live_read_matches_the_masked_einsum(dtype, h, tol, case):
    """The stacked leaf at a TRACED layer index: live lanes agree with
    ``mla.py``'s softmax over that layer's whole rows to the einsum's own
    tolerance, a masked lane's output is zeros nobody reads. What a dead
    row holds never reaches the output: NaN in every block no fill reaches
    (never read), 1e30 in the dead rows of a block a fill ends in (read and
    masked)."""
    _check(dtype, h, tol, FILLS[case], BK)


@pytest.mark.parametrize("block", [128, 256, 512])
def test_every_candidate_block_size_reads_the_same(block):
    """The three block sizes ISSUE 33 had measured, over 1,024 rows: a fill
    of one, one under, on and over each size's edge, the whole lane, a
    masked lane."""
    fills = (1, block - 1, block, block + 1, 1024, 1025)
    _check(jnp.bfloat16, 16, 2e-2, fills, block, lanes=len(fills), rows=1024,
           layer=2)


def test_a_scalar_fill_and_a_static_layer():
    """A scalar fill is the single-stream ``generate()``, a Python int the
    layer index of the dense layer outside the scan; the whole row may be
    asked for as the value."""
    q_row, leaf = _operands(jnp.float32, 8, seed=1)
    got = live_latent_attention(q_row, leaf, jnp.int32(BK + 3), 2, SCALE,
                                ROW, block_k=BK)
    ref = _whole_leaf(q_row, leaf[2], jnp.full((B,), BK + 3), ROW)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_a_value_narrower_than_a_lane_tile_is_cut_out_of_it():
    """The toy models' ``kv_lora_rank`` 16: the kernel computes the first
    128 columns, the caller gets 16."""
    q_row, leaf = _operands(jnp.float32, 8, seed=2)
    fills = jnp.asarray(FILLS["edges"], jnp.int32)
    got = live_latent_attention(q_row, leaf, fills, 0, SCALE, 16, block_k=BK)
    assert got.shape == (B, 8, 16)
    ref = _whole_leaf(q_row, leaf[0], fills, 16)
    np.testing.assert_allclose(np.asarray(got)[:4], np.asarray(ref)[:4],
                               atol=2e-5, rtol=2e-5)


CELL = dict(b=64, S=4096, h=128, row=640, dtype=jnp.bfloat16, s=1)
REFUSED = {
    "speculative width": (dict(s=5), "more than one query"),
    "fused-prefill width": (dict(s=16), "more than one query"),
    "int8 leaf": (dict(dtype=jnp.int8), "no dequant"),
    "unpadded row": (dict(row=576), "not whole 128-lane tiles"),
    "12 heads": (dict(h=12), "sublane"),
    "ragged length": (dict(S=4000), "not a multiple"),
}


@pytest.mark.parametrize("case", list(REFUSED))
def test_the_gate_names_why_it_refuses(case):
    over, names = REFUSED[case]
    shape = dict(CELL, **over)
    reason = live_latent_refusal(**shape)
    assert reason is not None and names in reason, reason
    if shape["s"] == 1:         # the function takes one query a lane only
        with pytest.raises(kernels.KernelUnsupported, match=names):
            live_latent_attention(
                jnp.zeros((2, shape["h"], shape["row"]), jnp.bfloat16),
                jnp.zeros((2, 2, shape["S"], shape["row"]), shape["dtype"]),
                jnp.ones((2,), jnp.int32), 0, SCALE, 128)


def test_the_gate_accepts_the_cells_shape_at_every_candidate_block():
    """``serve-reason``: 64 lanes of 4,096 rows of 640, 128 heads, bf16."""
    assert live_latent_refusal(**CELL) is None
    for block in (128, 256, 512):
        assert live_latent_refusal(**CELL, block_k=block) is None
    assert da.live_latent_block(4096) == da._LIVE_LATENT_BLOCK
    assert da._LIVE_LATENT_BLOCK in (128, 256, 512)


def test_rows_of_another_width_than_the_queries_are_refused():
    with pytest.raises(kernels.KernelUnsupported, match="against rows of"):
        live_latent_attention(jnp.zeros((2, 16, 256), jnp.bfloat16),
                              jnp.zeros((1, 2, 64, 128), jnp.bfloat16), 1, 0,
                              SCALE, 128)


# ------------------------------------------------- the model's choice, served
# tests/test_latent_block.py's toy with heads in whole float32 sublane tiles
TOY = {
    "arch": "pangu_ultra_moe", "hidden_size": 32, "intermediate_size": 64,
    "num_attention_heads": 8, "q_lora_rank": 24, "kv_lora_rank": 16,
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


@pytest.fixture(scope="module")
def toy():
    model = arch.build_model(TOY)
    params = model.init(jax.random.PRNGKey(3),
                        jnp.zeros((1, 4), jnp.int32))["params"]
    params = jax.tree.map(
        lambda x: x * 1.3 if x.ndim <= 2 and x.shape[-1] != 64 else x, params)
    return model, params


@pytest.fixture
def blocks_of_16(monkeypatch):
    monkeypatch.setattr(da, "_LIVE_LATENT_BLOCK", BK)


def _engine(toy, **kw):
    model, params = toy
    return ServingEngine(model, model_parameters=params, dtype=jnp.float32,
                         max_batch=2, decode_chunk=4, max_prompt_len=16,
                         prefill_buckets=[8, 16], **kw)


def _cell_cfg(**kw):
    """``serve-reason``'s configuration as the benchmark builds it."""
    import os
    from chipbench import spec
    config = spec.load_json(os.path.join(
        spec.REPO_ROOT, "chipbench", "configs",
        "pangu-ultra-moe-ep16-l5.json"))
    config["model"] = dict(config.get("model", {}), **kw)
    return arch.build_model(config).cfg


def test_the_cpu_keeps_the_einsum():
    assert mla.decode_read_block(_cell_cfg(), 64) is None


@pytest.mark.parametrize("case,kw,block", [
    ("the cell", {}, da._LIVE_LATENT_BLOCK),
    ("xla by name", dict(decode_impl="xla"), None),
    ("the flat kernel by name", dict(decode_impl="pallas"), None),
], ids=lambda v: v if isinstance(v, str) else None)
def test_what_the_model_chooses_from_what_a_trace_sees(past_auto_path, case,
                                                       kw, block):
    assert mla.decode_read_block(_cell_cfg(**kw), 64) == block
    if block is not None:
        assert past_auto_path == [("mla_decode_attention", None)]


def test_a_mesh_of_several_devices_is_refused_by_name(past_auto_path,
                                                      monkeypatch):
    """Compiled by Mosaic the kernel runs on one device
    (``gpt._decode_mesh_refusal``); the test mesh has eight."""
    from deepspeed_tpu.utils import platform
    monkeypatch.setattr(platform, "on_chip", lambda: True)
    assert mla.decode_read_block(_cell_cfg(), 64) is None
    (kernel, refusal), = past_auto_path
    assert kernel == "mla_decode_attention" and "mesh of" in refusal


def test_served_tokens_are_the_reference_argmax_through_the_live_read(
        toy, past_auto_path, blocks_of_16):
    """``test_latent_block.py``'s served run once more, the decode steps
    through the kernel: five requests through a two-lane engine, lanes
    admitted while others are mid-answer, fills that cross a block edge,
    lanes that retire mid-chunk (the sentinel beside a live lane)."""
    eng = _engine(toy)
    assert eng._kv_read_block == BK
    rng = np.random.default_rng(6)
    prompts = [rng.integers(0, 64, (n,)).astype(np.int32)
               for n in (3, 11, 6, 16, 2)]
    reqs = [eng.submit(p, max_new_tokens=m)
            for p, m in zip(prompts, (9, 5, 13, 3, 7))]
    for _ in range(200):
        if not (eng.scheduler.has_work() or eng.chunk_in_flight):
            break
        eng.pump()
    assert ("mla_decode_attention", None) in past_auto_path
    _, params = toy
    for prompt, req in zip(prompts, reqs):
        assert req.status == "done"
        full = np.concatenate([prompt, np.asarray(req.tokens, np.int32)])
        ref = np.asarray(arch.reference_logits(TOY, params, full[None])[0])[0]
        for j, tok in enumerate(req.tokens):
            row = ref[len(prompt) - 1 + j]
            assert row.max() - row[tok] < 1e-3, (len(prompt), j)
    m = eng.metrics
    assert 0 < m.kv_blocks_read < m.kv_blocks_arena


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def test_the_chunk_program_slices_no_arena_leaf(toy, past_auto_path):
    """The kernel is handed the layer-stacked latent leaf WHOLE, in the
    dense layer outside the scan and in the expert layers inside it: no
    ``dynamic_slice`` anywhere in the chunk program produces a layer's rows
    (a custom call's operand has to exist, so a slice of the leaf before it
    is a copy of a layer's cache every layer, every step)."""
    eng = _engine(toy)
    b, s, row = 2, 32, 128
    state = (jnp.zeros((b,), jnp.int32), jnp.zeros((b,), jnp.int32),
             jnp.ones((b,), bool), jnp.full((b,), -1, jnp.int32),
             jnp.full((b,), 4, jnp.int32))
    jaxpr = jax.make_jaxpr(eng._jit_decode_chunk)(
        eng._decode_params, eng.kv.cache, *state,
        jax.random.PRNGKey(0)).jaxpr
    eqns = list(_eqns(jaxpr))
    calls = [e for e in eqns if e.primitive.name == "pallas_call"]
    assert len(calls) == 2, "one call in the dense layer, one in the scan"
    for call in calls:
        assert call.params["name"] == "mla_decode_attention_live"
        leaves = [v.aval.shape for v in call.invars
                  if len(v.aval.shape) == 4]
        assert leaves == [(3, b, s, row)], leaves
    rows = {(b, s, row), (1, b, s, row)}
    sliced = [e for e in eqns
              if e.primitive.name in ("dynamic_slice", "gather", "slice")
              and any(v.aval.shape in rows for v in e.outvars)]
    assert not sliced, sliced


def test_the_counters_against_a_hand_count(toy, past_auto_path, blocks_of_16,
                                           telemetry_on):
    """One request, 14 prompt rows, 6 tokens: prefill samples the first,
    five decode steps read fills 15..19 of one lane in blocks of 16:
    1 + 1 + 2 + 2 + 2 = 8 blocks. The arena a step would read is 2 lanes x
    32 / 16 blocks, 4 steps a chunk."""
    eng = _engine(toy)
    res, = eng.run([np.arange(14, dtype=np.int32)], max_new_tokens=6)
    assert res.status == "done" and len(res.tokens) == 6
    m = eng.metrics
    assert m.kv_blocks_read == 8
    assert m.kv_blocks_arena == m.decode_steps * 4 * 2 * 2
    totals = telemetry_on.counter_totals()
    assert totals["serve/kv_blocks_read"] == 8
    assert totals["serve/kv_blocks_arena"] == m.kv_blocks_arena


@pytest.mark.parametrize("family", [{}, dict(speculative=True, spec_k=3)],
                         ids=["the cpu", "speculative"])
def test_the_einsum_path_counts_the_whole_arena(toy, family, request):
    """On the CPU, and under the speculative family wherever it runs (its
    verify hands the block more than one query a lane), a step reads every
    row of every lane: the two counters are equal."""
    if family:
        request.getfixturevalue("past_auto_path")
    eng = _engine(toy, **family)
    assert eng._kv_read_block is None
    eng.run([np.arange(9, dtype=np.int32)], max_new_tokens=6)
    m = eng.metrics
    assert m.kv_blocks_read == m.kv_blocks_arena > 0
    assert m.kv_read_share == 1.0
