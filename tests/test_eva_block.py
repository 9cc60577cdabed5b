"""The third block this repo runs (models/eva.py) against its plain float32
reference (chipbench/archs/evabyte.py, which imports nothing of the program
but to build it): a window that is exact beside chunk summaries of everything
older, as two cache leaves under one cursor, through the cache across window
edges and through ServingEngine with lanes at different depths in one chunk.
Toy widths (window 16, chunk 4), seeded weights, float32 on the CPU.
"""

import numpy as np
import pytest

from chipbench.archs import evabyte as arch
from tests.test_latent_block import TOY as LATENT_TOY, _with_cursor

# published KEYS at toy values; all eight prediction heads come out
TOY = {
    "arch": "evabyte", "hidden_size": 32, "intermediate_size": 48,
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "num_hidden_layers": 3, "vocab_size": 24, "num_pred_heads": 8,
    "window_size": 16, "chunk_size": 4, "max_position_embeddings": 128,
    "rms_norm_eps": 1e-5, "rope_theta": 100000,
    "tie_word_embeddings": False,
    "model": {"dtype": "float32", "param_dtype": "float32", "heads_out": 8},
}
SERVED = dict(TOY, model={"dtype": "float32", "param_dtype": "float32"})
ATOL = 2e-4
W, C, S = 16, 4, 128
# the served fixture's prompt lengths and answer budgets, request by request
PROMPT_LENS, BUDGETS = (13, 3, 30, 5, 17, 2), (40, 60, 11, 30, 9, 21)


@pytest.fixture(scope="module")
def toy():
    import jax
    model = arch.build_model(TOY)
    return model, arch.init_params(model, jax.random.PRNGKey(3))


def _ids(rng, b, s):
    return rng.integers(0, TOY["vocab_size"], (b, s)).astype(np.int32)


# the toy at widths the live-rows read takes (8 heads of 128 in float32) and
# blocks of 8 rows: a window is two blocks, and a summary fill (4 a closed
# window) ends inside a block in every other window
KERNEL_TOY = dict(TOY, hidden_size=1024, num_attention_heads=8,
                  num_key_value_heads=8, num_hidden_layers=2,
                  num_pred_heads=2,
                  model={"dtype": "float32", "param_dtype": "float32",
                         "heads_out": 2})
KERNEL_SERVED = dict(KERNEL_TOY, model={"dtype": "float32",
                                        "param_dtype": "float32"})
BLOCK = 8


@pytest.fixture(scope="module")
def kernel_toy():
    import jax
    model = arch.build_model(KERNEL_TOY)
    return model, arch.init_params(model, jax.random.PRNGKey(5))


@pytest.fixture
def through_the_kernel(past_auto_path, monkeypatch):
    """``"auto"`` resolved as on a TPU (the kernel then runs in the
    interpreter), in blocks of 8 rows."""
    from deepspeed_tpu.ops.pallas import decode_attention as da
    monkeypatch.setattr(da, "_LIVE_BLOCK", BLOCK)
    return past_auto_path


# ------------------------------------------------------------- (a) the model
def test_full_forward_equals_the_reference_on_all_eight_heads(toy):
    import jax
    model, params = toy
    ids = _ids(np.random.default_rng(0), 2, 55)      # three windows and a bit
    logits = np.asarray(model.apply({"params": params}, ids))
    ref = np.asarray(arch.reference_logits(TOY, params, ids)[0])
    assert logits.shape == ref.shape == (2, 55, 8, 24)
    assert np.max(np.abs(logits - ref)) < ATOL
    assert arch.param_count(TOY) == sum(
        int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    # the serving path multiplies head 0 alone, of the same kernel
    served = arch.build_model(SERVED)
    one = np.asarray(served.apply({"params": params}, ids))
    assert one.shape == (2, 55, 24)
    assert np.max(np.abs(one - ref[:, :, 0])) < ATOL


@pytest.mark.parametrize("cursors,read", [
    ("per_lane", "einsum"), ("scalar", "einsum"), ("per_lane", "kernel")])
def test_prefill_then_decode_through_the_cache_equals_the_full_forward(
        request, cursors, read):
    """Padded prefill, told where each row ends, creates both leaves; then
    every further token goes through them one at a time, across two window
    edges and more, from prompt lengths that are no multiple of the chunk:
    the logits at EVERY position equal the reference's one full forward.
    ``kernel``: the decode steps read each lane's live blocks of both leaf
    pairs (``live_decode_attention``, interpreted) where the einsum reads
    both leaves whole."""
    import jax
    import jax.numpy as jnp
    if read == "kernel":
        asked = request.getfixturevalue("through_the_kernel")
    config = {"einsum": TOY, "kernel": KERNEL_TOY}[read]
    model, params = request.getfixturevalue(
        {"einsum": "toy", "kernel": "kernel_toy"}[read])
    layers, h = config["num_hidden_layers"], config["num_attention_heads"]
    dh = config["hidden_size"] // h
    rng = np.random.default_rng(1)
    total = 53
    lens = np.array([7, 21] if cursors == "per_lane" else [13, 13], np.int32)
    ids = _ids(rng, 2, total)
    ref = np.asarray(arch.reference_logits(config, params, ids)[0])
    width = int(lens.max()) + 3                     # a bucket's padding
    padded = np.where(np.arange(width)[None] < lens[:, None],
                      ids[:, :width], 0)
    logits, vc = model.apply({"params": params}, jnp.asarray(padded),
                             lengths=jnp.asarray(lens), mutable=["cache"])
    cache = vc["cache"]["blocks"]
    assert set(cache) == {"window_key", "window_value", "chunk_key",
                          "chunk_value", "cache_index"}
    assert cache["window_key"].shape == (layers, 2, W, h, dh)
    assert cache["chunk_value"].shape == (layers, 2, S // C, h, dh)
    for i, n in enumerate(lens):
        assert np.max(np.abs(np.asarray(logits)[i, :n] - ref[i, :n])) < ATOL
    step = jax.jit(lambda c, tok, pos: model.apply(
        {"params": params, "cache": c}, tok[:, None],
        positions=pos[:, None], mutable=["cache"]))
    cache, pos = vc["cache"], lens.copy()
    edges = 0
    while (pos < total).any():
        cur = pos if cursors == "per_lane" else pos[0]
        tok = ids[np.arange(2), np.minimum(pos, total - 1)]
        logits, vc = step(_with_cursor(cache, cur), jnp.asarray(tok),
                          jnp.asarray(pos))
        cache = vc["cache"]
        for i in range(2):
            if pos[i] < total:
                assert np.max(np.abs(np.asarray(logits)[i, 0]
                                     - ref[i, pos[i]])) < ATOL, (i, pos)
        edges += int((pos[pos < total] % W == 0).sum())
        pos = np.minimum(pos + 1, total)
    assert edges >= 4           # each lane began at least two windows
    assert model.decode_read_block(2) == (BLOCK if read == "kernel" else None)
    if read == "kernel":
        assert ("decode_attention", None) in asked


def test_inside_one_window_it_is_plain_causal_softmax_attention(toy):
    """The same weights through a block written here with plain causal
    attention over ALL tokens: equal while the context is inside one window,
    and no longer once a second window has begun."""
    model, params = toy
    p = {k: np.asarray(v, np.float64) for k, v in params["blocks"].items()}
    h, d = 4, 8

    def rms(x, g):
        return x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-5) * (1 + g)

    def rot(x):                                     # [s, h, d], half-split
        ang = np.arange(len(x))[:, None] / 100000.0 ** (np.arange(d // 2)
                                                        / (d // 2))
        cos, sin = np.cos(ang)[:, None], np.sin(ang)[:, None]
        a, b = x[..., :d // 2], x[..., d // 2:]
        return np.concatenate([a * cos - b * sin, b * cos + a * sin], -1)

    def plain(ids):
        x = np.asarray(params["wte"]["embedding"], np.float64)[ids]
        s = len(ids)
        for i in range(3):
            u = rms(x, p["ln_1"][i])
            q = rot((u @ p["q_proj"][i]).reshape(s, h, d))
            k = rot((u @ p["k_proj"][i]).reshape(s, h, d))
            v = (u @ p["v_proj"][i]).reshape(s, h, d)
            sc = np.einsum("qhd,khd->hqk", q, k) / np.sqrt(d)
            sc = np.where(np.tril(np.ones((s, s), bool)), sc, -np.inf)
            pr = np.exp(sc - sc.max(-1, keepdims=True))
            pr /= pr.sum(-1, keepdims=True)
            x = x + np.einsum("hqk,khd->qhd", pr, v).reshape(s, -1) \
                @ p["o_proj"][i]
            u = rms(x, p["ln_2"][i])
            g = u @ p["gate_proj"][i]
            x = x + (g / (1 + np.exp(-g)) * (u @ p["up_proj"][i])) \
                @ p["down_proj"][i]
        out = rms(x, np.asarray(params["ln_f"]["scale"], np.float64))
        return out @ np.asarray(params["lm_head"]["kernel"], np.float64)

    ids = _ids(np.random.default_rng(2), 1, 40)[0]
    got = np.asarray(model.apply({"params": params}, ids[None]))[0]
    want = plain(ids).reshape(40, 8, 24)
    assert np.max(np.abs(got[:W] - want[:W])) < ATOL
    assert np.max(np.abs(got[W:] - want[W:])) > 100 * ATOL


def test_live_rows_and_the_masks_they_stand_for():
    from deepspeed_tpu.models import eva
    cfg = arch.build_model(TOY).cfg
    assert eva.summary_rows(cfg) == 32 and eva.lane_rows(cfg) == 48
    for t, rows in [(0, (1, 0)), (15, (16, 0)), (16, (1, 4)), (17, (2, 4)),
                    (47, (16, 8)), (48, (1, 12)), (127, (16, 28))]:
        assert eva.live_rows(cfg, t) == rows
        assert arch.live_rows(TOY, t) == rows
    n_win, n_old = eva.live_rows(cfg, np.array([5, 16, 37]))
    assert n_win.tolist() == [6, 1, 6] and n_old.tolist() == [0, 4, 8]
    with pytest.raises(ValueError, match="two windows"):
        eva.EvaBlockConfig(window_size=16, chunk_size=5)
    with pytest.raises(ValueError, match="prediction heads"):
        eva.EvaBlockConfig(window_size=16, chunk_size=4, num_pred_heads=2,
                           heads_out=3)


def test_a_cursor_at_max_seq_len_writes_into_neither_leaf(toy):
    """The serving engine pins a retired lane's cursor at ``max_seq_len``,
    and ``128 mod 16`` is row 0 of the window leaf, in range: the write has to
    be dropped all the same, in both leaves, while the lane beside it
    writes."""
    import jax.numpy as jnp
    model, params = toy
    ids = _ids(np.random.default_rng(3), 2, 9)
    _, vc = model.apply({"params": params}, jnp.asarray(ids),
                        mutable=["cache"])
    before = {k: np.asarray(v) for k, v in vc["cache"]["blocks"].items()}
    cur = np.array([S, 9], np.int32)
    _, vc = model.apply(
        {"params": params, "cache": _with_cursor(vc["cache"], cur)},
        jnp.asarray(ids[:, :1]), positions=jnp.asarray(cur)[:, None],
        mutable=["cache"])
    after = {k: np.asarray(v) for k, v in vc["cache"]["blocks"].items()}
    for name in ("window_key", "window_value", "chunk_key", "chunk_value"):
        assert np.array_equal(before[name][:, 0], after[name][:, 0]), name
        assert not np.array_equal(before[name][:, 1], after[name][:, 1]), name


def test_a_call_that_is_handed_the_cache_takes_one_token_a_lane(toy):
    import jax.numpy as jnp
    model, params = toy
    ids = _ids(np.random.default_rng(4), 1, 6)
    _, vc = model.apply({"params": params}, jnp.asarray(ids),
                        mutable=["cache"])
    with pytest.raises(NotImplementedError, match="window edge"):
        model.apply({"params": params, "cache": vc["cache"]},
                    jnp.asarray(ids[:, :3]), mutable=["cache"])


# ----------------------------------------------- (b) the model, through serving
def _serve(config, params):
    """Six requests through a three-lane engine with chunks of 8 steps, so
    that inside ONE chunk a lane crosses a window edge (prompt 13: positions
    13-20), a lane is mid-window (prompt 3), a lane is taken after a longer
    occupant (the 5-token prompt follows the 30-token one into its lane)
    and a lane is retired, its cursor pinned at ``max_seq_len``."""
    import jax.numpy as jnp
    from deepspeed_tpu.serving import ServingEngine
    model = arch.build_model(config)
    eng = ServingEngine(model, model_parameters=params, dtype=jnp.float32,
                        max_batch=3, decode_chunk=8, max_prompt_len=32,
                        prefill_buckets=[16, 32])
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
    return _serve(SERVED, toy[1])


@pytest.fixture(scope="module")
def served_through_the_kernel(kernel_toy):
    """The same requests at the widths the live-rows read takes, ``"auto"``
    resolved as on a TPU for as long as the engine is built and runs."""
    from deepspeed_tpu.ops.pallas import _utils as kernels
    from deepspeed_tpu.ops.pallas import decode_attention as da
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(kernels, "auto_path", lambda kernel, why: why is None)
        patch.setattr(da, "_LIVE_BLOCK", BLOCK)
        return _serve(KERNEL_SERVED, kernel_toy[1])


@pytest.mark.parametrize("read", ["einsum", "kernel"])
def test_served_tokens_are_the_model_s_own_token_for_token(request, read):
    """Against the model alone: greedy over ONE full forward at a time."""
    config = {"einsum": SERVED, "kernel": KERNEL_SERVED}[read]
    eng, params, prompts, reqs, _ = request.getfixturevalue(
        {"einsum": "served", "kernel": "served_through_the_kernel"}[read])
    assert eng._kv_read_block == (BLOCK if read == "kernel" else None)
    model = arch.build_model(config)
    for prompt, req, budget in zip(prompts, reqs, BUDGETS):
        assert req.status == "done" and len(req.tokens) == budget
        full = np.concatenate([prompt, np.asarray(req.tokens, np.int32)])
        logits = np.asarray(model.apply({"params": params}, full[None]))[0]
        ref = np.asarray(arch.reference_logits(
            config, params, full[None])[0])[0, :, 0]
        for j, tok in enumerate(req.tokens):
            row = len(prompt) - 1 + j
            assert tok == int(logits[row].argmax()), (len(prompt), j)
            assert ref[row].max() - ref[row][tok] < 1e-3, (len(prompt), j)


@pytest.mark.parametrize("served_by", ["served",
                                       "served_through_the_kernel"])
def test_a_retired_lane_s_rows_stay_as_they_were(request, served_by):
    """From the pump after a lane's request ended to the pump before its
    next occupant's prefill is inserted, chunks run with that lane's cursor
    at ``max_seq_len``: both its leaves are bit for bit what they were."""
    snaps = request.getfixturevalue(served_by)[4]
    held = 0
    for (run0, leaves0), (run1, leaves1) in zip(snaps, snaps[1:]):
        for lane in range(3):
            if lane in run0 or lane in run1:
                continue                            # somebody's, or refilled
            for name in ("window_key", "window_value", "chunk_key",
                         "chunk_value"):
                assert np.array_equal(leaves0[name][:, lane],
                                      leaves1[name][:, lane]), (name, lane)
            held += 1
    assert held >= 3


def test_the_arena_is_two_leaves_and_is_counted_in_rows(served):
    eng = served[0]
    leaves = eng.kv.cache["blocks"]
    assert leaves["window_key"].shape == (3, 3, W, 4, 8)
    assert leaves["chunk_key"].shape == (3, 3, S // C, 4, 8)
    assert leaves["cache_index"].shape == (3, 3)
    rep = eng.kv.arena_report()
    per_slot = 3 * (W + S // C) * 2 * 4 * 8 * 4     # layers x rows x k,v
    assert rep["kv_bytes"] == 3 * per_slot
    assert rep["bytes_per_slot"] == per_slot
    assert rep["rows_per_slot"] == W + S // C == 48
    assert rep["bytes_per_row"] == 3 * 2 * 4 * 8 * 4
    assert rep["bytes_per_token"] == per_slot // S  # a full lane's positions
    assert eng.kv.head_dim(4) == 8
    assert eng.module.decode_read_block(3) is None
    # what a step would read of the arena is counted in the lane's ROWS
    m = eng.metrics
    assert m.kv_blocks_read == m.kv_blocks_arena == m.decode_steps * 8 * 3


def test_the_chunk_program_counts_live_rows_from_positions(served):
    """The counters against a replay by hand: every decode step of a live
    lane at position t counts (t mod 16) + 1 window rows and 4 * (t // 16)
    summary rows; every step reads both leaves of all three lanes."""
    eng, _, prompts, reqs, _ = served
    got = eng.metrics.state_rows
    win = old = closed = 0
    for prompt, req in zip(prompts, reqs):
        # a request's last token is sampled and never fed back
        for t in range(len(prompt), len(prompt) + len(req.tokens) - 1):
            win += t % W + 1
            old += (W // C) * (t // W)
            closed += t % W == W - 1
    assert got["eva_window_rows_live"] == win
    assert got["eva_summary_rows_live"] == old
    assert got["eva_windows_closed"] == closed > 0
    assert got["eva_rows_read"] == eng.metrics.decode_steps * 8 * 3 * 48
    assert got["eva_rows_read"] > win + old


def test_the_live_rows_read_counts_the_blocks_it_took(
        served_through_the_kernel):
    """Where the kernel runs, the device's ``eva_rows_read`` and the host's
    ``serve/kv_blocks_read`` are the same replay by hand: every decode step
    of a live lane at position t took ``ceil(((t mod 16) + 1) / 8)`` window
    blocks and ``ceil(4 * (t // 16) / 8)`` summary blocks of 8 rows, and
    nothing of an idle lane; the arena a step would read is 3 lanes x 48 / 8
    blocks."""
    eng, _, prompts, reqs, _ = served_through_the_kernel
    m = eng.metrics
    blocks = live = 0
    for prompt, req in zip(prompts, reqs):
        for t in range(len(prompt), len(prompt) + len(req.tokens) - 1):
            n_win, n_old = t % W + 1, (W // C) * (t // W)
            blocks += -(-n_win // BLOCK) + -(-n_old // BLOCK)
            live += n_win + n_old
    assert m.state_rows["eva_rows_read"] == blocks * BLOCK
    assert m.state_rows["eva_window_rows_live"] \
        + m.state_rows["eva_summary_rows_live"] == live
    assert live < blocks * BLOCK < m.decode_steps * 8 * 3 * 48
    assert m.kv_blocks_read == blocks
    assert m.kv_blocks_arena == m.decode_steps * 8 * 3 * (48 // BLOCK)


def test_step_counters_count_live_lanes_only(toy):
    import jax.numpy as jnp
    model = toy[0]
    got = model.step_counters(jnp.array([15, 16, 40, 128]),
                              jnp.array([True, True, False, False]))
    assert {k: int(v) for k, v in got.items()} == {
        "eva_window_rows_live": 16 + 1, "eva_summary_rows_live": 0 + 4,
        "eva_rows_read": 4 * 48, "eva_windows_closed": 1}


def test_step_counters_count_the_live_lanes_blocks_where_the_kernel_runs(
        kernel_toy, through_the_kernel):
    """Position 15: two window blocks of 8; position 16: one window block
    and the block that holds the first window's four summaries; the lanes
    that are nobody's read nothing."""
    import jax.numpy as jnp
    got = kernel_toy[0].step_counters(jnp.array([15, 16, 40, 128]),
                                      jnp.array([True, True, False, False]))
    assert {k: int(v) for k, v in got.items()} == {
        "eva_window_rows_live": 16 + 1, "eva_summary_rows_live": 0 + 4,
        "eva_rows_read": BLOCK * (2 + 0 + 1 + 1), "eva_windows_closed": 1}
    assert kernel_toy[0].blocks_read(np.array([15, 16, 40]), BLOCK).tolist() \
        == [2, 2, 3]


def _cell_model(**kw):
    from chipbench import spec
    config = spec.load_cell(spec.load_benchmark(), "serve-longdoc")["config"]
    return arch.build_model(dict(config, model=dict(config["model"], **kw)))


@pytest.mark.parametrize("case,model,block,names", [
    ("the cell", lambda: _cell_model(), 128, None),
    ("xla by name", lambda: _cell_model(decode_impl="xla"), None, None),
    ("heads of 8", lambda: arch.build_model(SERVED), None, "lane-padded"),
], ids=lambda v: v if isinstance(v, str) else None)
def test_what_the_block_chooses_from_what_a_trace_sees(past_auto_path, case,
                                                       model, block, names):
    """``serve-longdoc``'s 16 lanes of 2,048 + 2,048 rows, 32 heads of 128 in
    bf16 take the live-rows read where ``"auto"`` may; a refusal is named and
    logged; on the CPU the einsum stays."""
    assert model().decode_read_block(16) == block
    if names is not None:
        (kernel, refusal), = past_auto_path
        assert kernel == "decode_attention" and names in refusal


def test_on_the_cpu_the_cell_keeps_the_einsum():
    assert _cell_model().decode_read_block(16) is None


# ------------------------------------------------------- what the model forced
def test_the_block_kind_names_its_own_stack():
    """``GPT`` goes through the module that defines the block's config: the
    latent kind and this one come in beside each other, ``GPT`` knowing
    neither by name, and the first block answers for itself."""
    import jax.numpy as jnp
    from deepspeed_tpu.models import eva, gpt, mla
    from chipbench.archs import pangu_ultra_moe
    assert gpt._kind(None) is None
    assert gpt._kind(arch.build_model(TOY).cfg.block) is eva
    latent = pangu_ultra_moe.build_model(LATENT_TOY)
    assert gpt._kind(latent.cfg.block) is mla
    assert mla.Stack is mla.LatentStack and eva.Stack is eva.EvaStack
    assert not latent.prefill_takes_lengths and latent.lane_rows() == 32
    assert latent.step_counters(jnp.zeros(2), jnp.ones(2, bool)) is None
    assert arch.build_model(TOY).prefill_takes_lengths
    plain = gpt.GPT(gpt.GPTConfig(num_layers=1, num_heads=2, d_model=16,
                                  d_ff=32, max_seq_len=24))
    assert not plain.prefill_takes_lengths and plain.lane_rows() == 24
    assert plain.step_counters(jnp.zeros(2), jnp.ones(2, bool)) is None


def test_the_engine_tells_the_prefill_where_each_row_ends(toy):
    """Two prompts of one bucket whose last tokens lie in different windows:
    without ``lengths`` the padding would pick the window."""
    import jax.numpy as jnp
    from deepspeed_tpu.serving import ServingEngine
    params = toy[1]
    model = arch.build_model(SERVED)
    eng = ServingEngine(model, model_parameters=params, dtype=jnp.float32,
                        max_batch=2, decode_chunk=4, max_prompt_len=64,
                        prefill_buckets=[64])
    rng = np.random.default_rng(7)
    prompts = [_ids(rng, 1, n)[0] for n in (9, 50)]
    reqs = eng.run(prompts, max_new_tokens=6)
    for prompt, req in zip(prompts, reqs):
        full = np.concatenate([prompt, np.asarray(req.tokens, np.int32)])
        logits = np.asarray(model.apply({"params": params}, full[None]))[0]
        assert [int(logits[len(prompt) - 1 + j].argmax())
                for j in range(6)] == [int(t) for t in req.tokens]
