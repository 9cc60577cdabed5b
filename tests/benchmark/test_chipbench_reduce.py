"""trace_reduce.py on a small hand-built trace, and flops.py against hand
counts for both models."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from chipbench import flops, spec, trace_reduce as tr  # noqa: E402

US = 1000          # ns


def _trace():
    """Two devices, a 1000 us window. Device 0: a while of 400 us holding
    two fusions (100, 200 us), a flash kernel of 100 us, an all-reduce of
    50 us that overlaps nothing, and an async all-gather of 300 us that runs
    beside the while. Names as a TPU trace writes them."""
    dev0 = [
        ("%while.7 = (s32[], f32[8]) while(...)", 0, 400 * US),
        ("%fusion.1 = bf16[8] fusion(...)", 10 * US, 100 * US),
        ("%fusion.2 = bf16[8] fusion(...)", 150 * US, 200 * US),
        ("%flash_attention_fwd.3 = bf16[4] custom-call(...)", 500 * US, 100 * US),
        ("%all-reduce.5 = f32[8] all-reduce(...)", 700 * US, 50 * US),
    ]
    dev1 = [("%fusion.1 = bf16[8] fusion(...)", 0, 1000 * US)]
    mods = {0: [("jit_train_step(123)", 0, 400 * US),
                ("jit_train_step(123)", 500 * US, 250 * US)],
            1: [("jit_train_step(123)", 0, 1000 * US)]}
    asy = {0: [("%all-gather-start.2 = (...) all-gather-start(...)",
                100 * US, 300 * US)]}
    host = [("chipbench/feed", 390 * US, 120 * US),
            ("chipbench/wait", 590 * US, 500 * US)]
    return {0: dev0, 1: dev1}, mods, host, asy


def test_busy_union_and_idle_share():
    dev, mods, host, asy = _trace()
    s = tr.reduce_events(dev, mods, host, (0, 1000 * US), async_events=asy)
    assert s.n_devices == 2 and s.window_s == pytest.approx(1e-3)
    # device 0 busy 400 + 100 + 50 = 550 us (nested ops count once; the
    # async all-gather is not an op that keeps the core busy); device 1 1000
    assert s.busy_s == pytest.approx((550e-6 + 1000e-6) / 2)
    assert s.idle_share == pytest.approx(1 - 0.775)


def test_op_time_by_name_is_self_time():
    dev, mods, host, asy = _trace()
    s = tr.reduce_events({0: dev[0]}, {0: mods[0]}, host, (0, 1000 * US))
    assert s.op_seconds["fusion"] == pytest.approx(300e-6)
    assert s.op_seconds["while"] == pytest.approx(100e-6)   # 400 - 300 inside
    assert s.op_seconds["flash_attention_fwd"] == pytest.approx(100e-6)
    assert s.op_counts["fusion"] == 2 and s.op_counts["flash_attention_fwd"] == 1
    assert s.op_time("flash_attention") == pytest.approx(100e-6)
    assert s.top_ops(1)[0][0] == "fusion"
    secs, count = s.module_time("train_step")
    assert count == 2 and secs == pytest.approx(650e-6)


def test_collective_time_is_a_union_over_both_lines():
    dev, mods, host, asy = _trace()
    s = tr.reduce_events({0: dev[0]}, {}, host, (0, 1000 * US),
                         async_events={0: asy[0]})
    assert s.collective_s == pytest.approx(350e-6)          # 300 + 50
    s = tr.reduce_events({0: dev[0]}, {}, host, (0, 1000 * US))
    assert s.collective_s == pytest.approx(50e-6)


def test_idle_gaps_carry_the_host_span_that_covers_them():
    dev, mods, host, asy = _trace()
    s = tr.reduce_events({0: dev[0]}, {}, host, (0, 1000 * US))
    gaps = dict(s.idle_gaps)
    # 400-500 under "feed" (covers 100 of it), 600-700 and 750-1000 under
    # "wait"
    assert gaps["chipbench/feed"] == pytest.approx(100e-6)
    assert gaps["chipbench/wait"] == pytest.approx(350e-6)
    assert sum(gaps.values()) == pytest.approx(s.window_s - s.busy_s)
    s2 = tr.reduce_events({0: dev[0]}, {}, [], (0, 1000 * US))
    assert dict(s2.idle_gaps) == {"unlabelled": pytest.approx(450e-6)}


def test_window_clips_events_and_nothing_on_a_device_reads_as_nothing():
    dev, mods, host, asy = _trace()
    s = tr.reduce_events({0: dev[0]}, {0: mods[0]}, host, (200 * US, 600 * US))
    assert s.busy_s == pytest.approx(300e-6)        # 200-400 and 500-600
    assert tr.reduce_events({}, {}, host) is None
    assert tr.reduce_events({0: []}, {}, host) is None


@pytest.mark.parametrize("raw,want", [
    ("%fusion.436 = bf16[8,2048]{1,0} fusion(bf16[8] %p.1)", "fusion"),
    ("%flash_attention_bwd_dkv.13 = (bf16[4]) custom-call(...)",
     "flash_attention_bwd_dkv"),
    ("jit_decode_chunk_fn(8196109933328934910)", "jit_decode_chunk_fn"),
    ("%all-gather-start.2 = (...)", "all-gather-start"),
    ("while.180", "while"),
])
def test_base_name(raw, want):
    assert tr.base_name(raw) == want


def test_union_and_gaps_primitives():
    assert tr.union_ns([(0, 10), (5, 20), (30, 40), (32, 35)]) == 30
    assert tr.gaps_ns([(5, 10), (20, 30)], 0, 40) == [(0, 5), (10, 20), (30, 40)]
    assert tr.union_ns([]) == 0


# --------------------------------------------------------------- flops.py
BENCH = spec.load_benchmark()


def _cfg(name):
    entry = next(c for c in BENCH["configs"] if c["name"] == name)
    return spec.load_json(os.path.join(spec.REPO_ROOT, entry["file"]))


def test_param_counts_match_the_published_models():
    full = _cfg("pythia-1.4b")
    assert flops.param_count(full) == 1_414_647_808        # Pythia-1.4B
    big = dict(_cfg("pythia-6.9b-l16"), num_hidden_layers=32)
    assert flops.param_count(big) == 6_857_302_016         # Pythia-6.9B
    half = _cfg("pythia-6.9b-l16")
    assert half["num_hidden_layers"] == 16
    assert flops.param_count(half) == 3_635_224_576


@pytest.mark.parametrize("name", ["pythia-1.4b", "pythia-1.4b-cut",
                                  "pythia-6.9b-l16"])
def test_train_flops_6n_plus_attention_head_counted_once(name):
    c = _cfg(name)
    d, f, v, L = (c["hidden_size"], c["intermediate_size"], c["vocab_size"],
                  c["num_hidden_layers"])
    s = 2048
    per_layer = 4 * d * d + 2 * d * f          # qkv+out, up+down
    n = L * per_layer + v * d                  # the head once; embedding = gather
    assert flops.matmul_params(c) == n
    # attention, causal: QK^T and PV are 2 * 2 * s * d FLOPs a token forward,
    # halved by the mask, three times that with the backward
    attn = 3 * L * (4 * s * d / 2)
    assert flops.train_flops_per_token(c, s) == pytest.approx(6 * n + attn)


def test_train_flops_of_the_whole_model_by_hand():
    # Pythia-1.4B at 2048: 6 x 1.311 B matmul weights + 6 x 24 x 2048 x 2048
    assert flops.train_flops_per_token(_cfg("pythia-1.4b"), 2048) == \
        pytest.approx(6 * (24 * 50_331_648 + 50304 * 2048) + 603_979_776)


def test_decode_bytes_are_weights_plus_live_kv():
    c = _cfg("pythia-6.9b-l16")
    assert flops.kv_bytes_per_token(c) == 2 * 16 * 4096 * 2 == 262_144
    weights = flops.matmul_params(c) * 2
    assert flops.decode_step_bytes(c, 0) == weights
    assert flops.decode_step_bytes(c, 5000) == weights + 5000 * 262_144
    # the whole arena (12 x 2048 positions) is 6.4 GB: what a path that
    # reads it all pays beyond the live part
    assert 12 * 2048 * flops.kv_bytes_per_token(c) == 6_442_450_944


def test_flash_flops_unit_is_the_causal_half():
    c = _cfg("pythia-1.4b-cut")
    # QK^T and PV over 2048 x 2048 x d, the masked half skipped
    assert flops.attention_flops_fwd(c, 2048) == 4 * 2048 * 2048 * 2048 / 2
    assert flops.attention_flops_fwd(c, 2048, causal=False) == \
        4 * 2048 * 2048 * 2048
