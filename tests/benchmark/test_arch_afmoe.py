"""The benchmark's part of the fourth architecture: the plain reference of
chipbench/archs/afmoe.py against a tiny case worked by hand in numpy, the
mutations its limit has to catch, its counts by hand; the configuration file
against the catalog's row, the enlarged BENCHMARK.json with the new entries
found BY NAME, the rehearsal of ``serve-agent`` through ``chipbench/run.py``
and every new reader on a synthetic trace and counters; the float8 control
through the new driver; and every assertion of the directory's red tests
that an appended entry leaves true, with a table of architectures that an
unknown ``arch`` falls through."""

import json
import math
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import spec, trace_reduce  # noqa: E402
from chipbench.archs import afmoe as arch  # noqa: E402
from chipbench.archs import evabyte, pangu_ultra_moe  # noqa: E402
from chipbench.harness import SUFFIX_REHEARSAL  # noqa: E402

BENCH = spec.load_benchmark()
CONFIG = spec.load_json(os.path.join(
    ROOT, "chipbench", "configs", "trinity-mini-l5.json"))
SEED = 3_000_000_032
NEW_READERS = ["decode_hbm_roofline.agent", "kv_rows_read_over_live.agent",
               "experts_touched.agent", "expert_load_max_over_mean.agent"]
S, F = "sliding_attention", "full_attention"

# window 4: a 10-token row leaves it twice; a dense sliding layer, then a
# sliding and a full expert layer; 2 query heads on 1 key head
TINY = {
    "arch": "afmoe", "hidden_size": 8, "num_attention_heads": 2,
    "num_key_value_heads": 1, "head_dim": 4, "intermediate_size": 12,
    "moe_intermediate_size": 6, "num_hidden_layers": 3,
    "num_dense_layers": 1, "num_experts": 4, "num_experts_per_tok": 2,
    "num_shared_experts": 1, "vocab_size": 11, "sliding_window": 4,
    "layer_types": [S, S, F], "max_position_embeddings": 32,
    "rms_norm_eps": 1e-5, "rope_theta": 100.0, "route_norm": True,
    "route_scale": 2.826, "mup_enabled": True, "tie_word_embeddings": False,
}


def _tiny_params(rng):
    d, hd, kd, f_dense, f, e = 8, 8, 4, 12, 6, 4

    def w(*shape):
        return (rng.standard_normal(shape) / math.sqrt(shape[-2])
                ).astype(np.float32)

    def g(*shape):
        return (1 + 0.3 * rng.standard_normal(shape)).astype(np.float32)

    def attention(n):
        return {"ln_in": g(n, d), "ln_post_attn": g(n, d),
                "ln_pre_mlp": g(n, d), "ln_post_mlp": g(n, d),
                "q_proj": w(n, d, hd), "k_proj": w(n, d, kd),
                "v_proj": w(n, d, kd), "attn_gate": w(n, d, hd),
                "o_proj": w(n, hd, d), "q_norm": g(n, 4), "k_norm": g(n, 4)}

    dense = dict(attention(1), gate_proj=w(1, d, f_dense),
                 up_proj=w(1, d, f_dense), down_proj=w(1, f_dense, d))
    sparse = dict(
        attention(2), router=w(2, d, e),
        router_bias=(0.3 * rng.standard_normal((2, e))).astype(np.float32),
        shared_gate=w(2, d, f), shared_up=w(2, d, f), shared_down=w(2, f, d),
        expert_gate=w(2, e, d, f), expert_up=w(2, e, d, f),
        expert_down=w(2, e, f, d))
    return {"wte": {"embedding": rng.standard_normal((11, d)
                                                     ).astype(np.float32)},
            "blocks": {"dense": dense, "sparse": sparse},
            "ln_f": {"scale": g(d)}, "lm_head": {"kernel": w(d, 11)}}


def _by_hand(p, ids, bias_in_weights=False):
    """The module docstring's equations in numpy, token by token, for ONE
    row: no batching, no blocks of queries, loops over heads and experts.
    ``bias_in_weights``: the FAULT of a selection bias that enters the
    weights (``s + b`` in numerator and denominator)."""
    cfg = TINY
    d, h, dh, w, k = 8, 2, 4, 4, 2

    def rms(x, gain):
        return x / np.sqrt(np.mean(x * x, -1, keepdims=True) + 1e-5) * gain

    def rot(x, t):                                  # [dh] at position t
        half = dh // 2
        ang = t / (100.0 ** (np.arange(half) / half))
        a, b = x[:half], x[half:]
        return np.concatenate([a * np.cos(ang) - b * np.sin(ang),
                               b * np.cos(ang) + a * np.sin(ang)])

    def silu(x):
        return x / (1 + np.exp(-x))

    def mlp(x, gate, up, down):
        return (silu(x @ gate) * (x @ up)) @ down

    x = p["wte"]["embedding"][ids].astype(np.float64) * math.sqrt(d)
    n = len(ids)
    for layer in range(3):
        group, i = ("dense", 0) if layer == 0 else ("sparse", layer - 1)
        q_ = {name: np.asarray(v[i], np.float64)
              for name, v in p["blocks"][group].items()}
        full = cfg["layer_types"][layer] == F
        u = rms(x, q_["ln_in"])
        q = rms((u @ q_["q_proj"]).reshape(n, h, dh), q_["q_norm"])
        key = rms((u @ q_["k_proj"]).reshape(n, 1, dh), q_["k_norm"])
        val = (u @ q_["v_proj"]).reshape(n, 1, dh)
        gate = u @ q_["attn_gate"]
        if not full:
            q = np.stack([[rot(q[t, a], t) for a in range(h)]
                          for t in range(n)])
            key = np.stack([[rot(key[t, 0], t)] for t in range(n)])
        ctx = np.zeros((n, h, dh))
        for t in range(n):
            first = 0 if full else max(0, t - w + 1)
            for a in range(h):          # both heads read key head 0
                sc = np.array([q[t, a] @ key[j, 0] / math.sqrt(dh)
                               for j in range(first, t + 1)])
                pr = np.exp(sc - sc.max())
                pr /= pr.sum()
                ctx[t, a] = pr @ val[first:t + 1, 0]
        o = (ctx.reshape(n, h * dh) / (1 + np.exp(-gate))) @ q_["o_proj"]
        hid = x + rms(o, q_["ln_post_attn"])
        f_in = rms(hid, q_["ln_pre_mlp"])
        if group == "dense":
            f = mlp(f_in, q_["gate_proj"], q_["up_proj"], q_["down_proj"])
        else:
            f = mlp(f_in, q_["shared_gate"], q_["shared_up"],
                    q_["shared_down"])
            s = 1 / (1 + np.exp(-(f_in @ q_["router"])))
            for t in range(n):
                biased = s[t] + q_["router_bias"]
                chosen = np.argsort(-biased)[:k]
                top = (biased if bias_in_weights else s[t])[chosen]
                weights = 2.826 * top / (top.sum() + 1e-20)
                for e, we in zip(chosen, weights):
                    f[t] += we * mlp(f_in[t], q_["expert_gate"][e],
                                     q_["expert_up"][e], q_["expert_down"][e])
        x = hid + rms(f, q_["ln_post_mlp"])
    return rms(x, np.asarray(p["ln_f"]["scale"], np.float64)) \
        @ np.asarray(p["lm_head"]["kernel"], np.float64)


@pytest.fixture(scope="module")
def tiny():
    rng = np.random.default_rng(32)
    return _tiny_params(rng), rng.integers(0, 11, (2, 10)).astype(np.int32)


def test_reference_equals_a_tiny_case_worked_by_hand(tiny):
    """float32 under "highest" against float64 numpy: 1e-5 found, 1e-4 asked;
    ten tokens leave the window of 4 twice, and both rows differ."""
    params, ids = tiny
    got, report = arch.reference_logits(TINY, params, ids)
    for row in range(2):
        assert np.max(np.abs(np.asarray(got)[row]
                             - _by_hand(params, ids[row]))) < 1e-4
    assert report["sets"] == 0      # no program's routing was handed in


@pytest.mark.parametrize("fault", [
    "window_off_by_one", "rotary_on_the_full_layer", "embedding_unscaled",
    "gate_left_out", "head_norm_gain_left_out", "bias_in_the_weights",
    "key_norm_gain_turned"])
def test_the_limit_refuses_what_the_issue_lists(tiny, fault):
    """Each fault moves a logit of the tiny case by more than LOGIT_ATOL."""
    params, ids = tiny
    good = np.asarray(arch.reference_logits(TINY, params, ids)[0])
    config, p = dict(TINY), params
    sparse, dense = dict(p["blocks"]["sparse"]), dict(p["blocks"]["dense"])
    if fault == "window_off_by_one":
        config["sliding_window"] = 5
    elif fault == "rotary_on_the_full_layer":
        config["layer_types"] = [S, S, S]
        config["sliding_window"] = 32           # the mask left as it was
    elif fault == "embedding_unscaled":
        config["mup_enabled"] = False
    elif fault == "gate_left_out":              # sigmoid(0): a half
        sparse["attn_gate"] = sparse["attn_gate"] * 0
        dense["attn_gate"] = dense["attn_gate"] * 0
    elif fault == "head_norm_gain_left_out":
        sparse["q_norm"] = np.ones_like(sparse["q_norm"])
        sparse["k_norm"] = np.ones_like(sparse["k_norm"])
    elif fault == "bias_in_the_weights":
        # no parameter of the reference says it: the case by hand does
        bad = _by_hand(params, ids[0], bias_in_weights=True)
        assert np.max(np.abs(bad - good[0])) > arch.LOGIT_ATOL
        return
    elif fault == "key_norm_gain_turned":
        # the key norm's gain applied to the wrong features
        sparse["k_norm"] = sparse["k_norm"][:, ::-1]
        dense["k_norm"] = dense["k_norm"][:, ::-1]
    p = {**p, "blocks": {"dense": dense, "sparse": sparse}}
    bad = np.asarray(arch.reference_logits(config, p, ids)[0])
    assert np.max(np.abs(bad - good)) > arch.LOGIT_ATOL, fault


def test_a_lower_precision_reads_further_from_the_reference(tiny):
    """float8 operands move the tiny case's logits more than bfloat16 ones,
    and past the limit (the chip's reading of the same control at the
    published widths is in PERF.md)."""
    import jax.numpy as jnp
    params, ids = tiny
    good = np.asarray(arch.reference_logits(TINY, params, ids)[0])
    diff = {name: float(np.max(np.abs(good - np.asarray(arch.reference_logits(
        TINY, params, ids, lower=jnp.dtype(name).type)[0]))))
        for name in ("bfloat16", "float8_e4m3fn")}
    assert diff["bfloat16"] < arch.LOGIT_ATOL < diff["float8_e4m3fn"]


def test_the_reference_follows_the_program_inside_epsilon_only():
    """The rule itself, on biased scores by hand: a program's set that takes
    an expert whose biased score lies within ROUTE_EPS of the one it
    displaces is followed; one that takes an expert from further off is
    refused and reported, and the reference keeps its own."""
    biased = np.array([[0.9, 0.899, 0.5, 0.1], [0.9, 0.2, 0.88, 0.1]])
    assert arch._own_choice(biased, 2).tolist() == [[0, 1], [0, 2]]
    report = {"sets": 0, "sets_differing": 0, "sets_refused": 0,
              "largest_gap": 0.0, "pairs_swapped": 0}
    got = arch._route(biased, 2, np.array([[0, 2], [0, 1]]), report)
    # token 0: 1 displaced by 2, a gap of 0.399; token 1: 2 by 1, 0.68
    assert got.tolist() == [[0, 1], [0, 2]]
    assert (report["sets"], report["sets_differing"], report["sets_refused"],
            report["pairs_swapped"]) == (2, 2, 2, 0)
    assert report["largest_gap"] == pytest.approx(0.68)
    report = {"sets": 0, "sets_differing": 0, "sets_refused": 0,
              "largest_gap": 0.0, "pairs_swapped": 0}
    near = np.array([[0.9, 0.5, 0.499, 0.1], [0.9, 0.5, 0.4, 0.1]])
    got = arch._route(near, 2, np.array([[0, 2], [1, 0]]), report)
    assert got.tolist() == [[0, 2], [0, 1]]     # a set in another order: own
    assert (report["sets"], report["sets_differing"], report["sets_refused"],
            report["pairs_swapped"]) == (2, 1, 0, 1)
    assert report["largest_gap"] == pytest.approx(0.001)
    # a token the program did not run (-1) is the reference's own
    assert arch._route(near, 2, np.array([[-1, -1], [-1, -1]]),
                       dict(report)).tolist() == [[0, 1], [0, 1]]
    assert arch.ROUTE_EPS < 0.02 < arch.LOGIT_ATOL


PUBLISHED = {       # the catalog's row, by hand
    "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 6144,
    "load_balance_coeff": 0.001, "model_type": "afmoe",
    "moe_intermediate_size": 1024, "mup_enabled": True, "n_group": 1,
    "num_attention_heads": 32, "num_expert_groups": 1, "num_experts": 128,
    "num_experts_per_tok": 8, "num_key_value_heads": 4,
    "num_limited_groups": 1, "num_shared_experts": 1, "rms_norm_eps": 1e-5,
    "rope_scaling": None, "rope_theta": 10000, "route_norm": True,
    "route_scale": 2.826, "score_func": "sigmoid", "sliding_window": 2048,
    "tie_word_embeddings": False, "topk_group": 1, "use_grouped_mm": True,
    "vocab_size": 200192}
AFMOE_REHEARSAL_KEYS = {
    "hidden_size", "num_attention_heads", "num_hidden_layers",
    "intermediate_size", "vocab_size", "max_position_embeddings", "engine",
    "moe_intermediate_size", "head_dim", "num_key_value_heads",
    "num_experts", "num_experts_per_tok", "sliding_window", "model"}


def test_the_configuration_holds_every_published_key_and_says_its_cut():
    for key, value in PUBLISHED.items():
        assert CONFIG[key] == value, key
    assert CONFIG["reduced"] == ["num_hidden_layers", "num_dense_layers",
                                 "layer_types", "max_position_embeddings"]
    assert (CONFIG["num_hidden_layers"], CONFIG["num_dense_layers"],
            CONFIG["max_position_embeddings"]) == (5, 1, 20480)
    assert CONFIG["layer_types"] == [S, S, S, F, S]
    assert CONFIG["published"]["num_hidden_layers"] == 32
    assert CONFIG["published"]["num_dense_layers"] == 2
    assert CONFIG["published"]["max_position_embeddings"] == 131072
    assert set(CONFIG["reduced_notes"]) == set(CONFIG["reduced"])
    assert CONFIG["source"] == \
        "https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/config.json"
    assert "27 layers left out" in CONFIG["deployment"]
    assert CONFIG["engine"]["serving_engine"]["max_batch"] == 64
    assert CONFIG["engine"]["serving_engine"]["max_prompt_len"] == 16384
    assert CONFIG["engine"]["serving_engine"]["prefill_buckets"][-1] == 16384
    assert CONFIG["engine"]["frontend"] == {"feed_depth": 1}
    for needle in ("sqrt(hidden_size)", "four RMSNorms", "output gate",
                   "RMS-normed over the head_dim", "NO positional encoding",
                   "selection bias", "0.02 x normal", "RMS of 4"):
        assert any(needle in a for a in CONFIG["assumed"]), needle
    assert any("accumulation points" in a for a in CONFIG["departures"])
    assert any("last position alone" in a for a in CONFIG["departures"])
    assert any("prefill programs" in n for n in CONFIG["engine_notes"])
    assert any("cold first run" in n for n in CONFIG["engine_notes"])
    # the rehearsal changes sizes, the CPU's dtype and the engine only
    assert set(CONFIG["rehearsal"]) <= AFMOE_REHEARSAL_KEYS
    r = CONFIG["rehearsal"]
    assert (r["hidden_size"], r["num_attention_heads"],
            r["num_key_value_heads"], r["sliding_window"], r["num_experts"],
            r["num_experts_per_tok"]) == (64, 4, 2, 16, 8, 2)
    # the longest request stays inside the lane
    assert 16384 + 4096 <= CONFIG["max_position_embeddings"]


def test_counts_from_shapes_are_the_issue_s_table():
    assert arch.attention_params(CONFIG) \
        == 3 * 2048 * 4096 + 2 * 2048 * 512 + 256 == 27_263_232
    assert arch.expert_params(CONFIG) == 3 * 2048 * 1024 == 6_291_456
    assert arch.dense_layer_params(CONFIG) == 65_020_160
    assert arch.expert_layer_params(CONFIG) \
        == 27_263_232 + 8192 + 129 * 6_291_456 + 2048 * 128 + 128 \
        == 839_131_520
    assert arch.param_count(CONFIG) \
        == 65_020_160 + 4 * 839_131_520 + 2 * 200192 * 2048 + 2048 \
        == 4_241_534_720
    assert arch.row_bytes(CONFIG) == 2048
    assert arch.lane_bytes(CONFIG) == (4 * 2048 + 20480) * 2048 == 58_720_256
    assert 64 * arch.lane_bytes(CONFIG) == 3_758_096_384
    # a decode step by hand: the matmul weights outside the experts, 126
    # touched experts in each of four layers, and live rows at 2,048 B
    fixed = (5 * (27_263_232 - 256) + 3 * 2048 * 6144
             + 4 * (6_291_456 + 2048 * 128) + 200192 * 2048)
    assert fixed * 2 == 1_220_542_464
    got = arch.decode_step_bytes(CONFIG, 64 * 4 * 2048, 64 * 5200, 126.0)
    assert got == fixed * 2 + 4 * 126 * 12_582_912 \
        + (64 * 4 * 2048 + 64 * 5200) * 2048
    assert arch.decode_step_bytes(CONFIG, 0, 0, 0) == fixed * 2
    ring, glob = arch.live_rows(CONFIG, np.array([0, 2047, 2048, 9000]))
    assert ring.tolist() == [1, 2048, 2048, 2048]
    assert glob.tolist() == [1, 2048, 2049, 9001]


def test_check_lengths_reach_what_the_first_driver_s_never_do():
    groups = arch.check_lengths(CONFIG)
    assert groups == [[5, 16, 28, 40], list(range(2044, 2053)), [4107]]
    # decoding four tokens from 2044 writes ring row 2047 and then wraps
    assert 2044 + 4 == 2048 and 4107 > 2 * 2048
    assert arch.check_lengths(dict(CONFIG, sliding_window=16)) \
        == [[5, 8, 11, 14], list(range(12, 21)), [43]]


def test_build_model_maps_the_published_keys():
    model = arch.build_model(CONFIG)
    cfg, block = model.cfg, model.cfg.block
    assert (cfg.d_model, cfg.num_heads, cfg.num_layers, cfg.d_ff) \
        == (2048, 32, 5, 6144)
    assert (cfg.vocab_size, cfg.max_seq_len) == (200192, 20480)
    assert cfg.rotary_base == 10000.0 and not cfg.tie_embeddings
    assert cfg.layer_norm_eps == 1e-5
    assert (block.num_kv_heads, block.head_dim, block.sliding_window,
            block.row) == (4, 128, 2048, 512)
    assert block.layer_types == (S, S, S, F, S) and block.dense_layers == 1
    assert (block.n_routed_experts, block.experts_per_token,
            block.moe_d_ff) == (128, 8, 1024)
    assert block.routed_scaling_factor == 2.826 and block.norm_topk_prob
    assert block.embed_scale
    assert model.lane_rows() == (4 * 2048 + 20480) // 5
    assert model.prefill_takes_lengths
    from deepspeed_tpu.models.afmoe import _tile_rows
    assert _tile_rows(cfg, 64) == 32 and _tile_rows(cfg, 512) == 128
    assert _tile_rows(cfg, 1536) == _tile_rows(cfg, 16384) == 256


def _entry(group, name):
    return next(e for e in BENCH[group] if e["name"] == name)


NINE_BATCH = {"occupancy.batch", "kv_live_share.batch",
              "decode_step_ms.batch", "device_idle.batch",
              "ttft_ms.p50.batch", "device_starved.batch",
              "host_ms_per_chunk.batch", "queue_wait_ms.mean.batch",
              "lane_to_first_token_ms.mean.batch"}


def test_benchmark_json_holds_the_new_entries_and_still_validates():
    spec.validate(BENCH)
    assert len(BENCH["configs"]) >= 6 and len(BENCH["workloads"]) >= 6
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1
    entry = _entry("configs", "trinity-mini-l5")
    assert entry["source"] == CONFIG["source"]
    assert entry["reduced"] == CONFIG["reduced"]
    assert entry["file"] == "chipbench/configs/trinity-mini-l5.json"
    cell = _entry("workloads", "serve-agent")
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("trinity-mini-l5", "agent-closed", 1)
    assert "1 layer in 5 global" in cell["why"]
    mine = {m["name"] for m in spec.metrics_of_cell(BENCH, "serve-agent",
                                                    "per_layer")}
    assert mine == set(NEW_READERS) | NINE_BATCH
    for name in NEW_READERS:
        m = _entry("per_layer", name)
        assert m["workloads"] == ["serve-agent"]
        assert m["moves"] == "serve_tokens_per_s"
    assert _entry("per_layer", "decode_hbm_roofline.agent")["layer"] \
        == "kernels"
    assert _entry("per_layer", "kv_rows_read_over_live.agent")["layer"] \
        == "KV cache"
    assert {m["name"] for m in spec.metrics_of_cell(
        BENCH, "serve-agent", "end_to_end")} == {"serve_tokens_per_s",
                                                 "setup_s"}
    mix = spec.load_json(spec.find_mix(BENCH, "agent-closed"))
    assert mix["kind"] == "serve_closed_long_routed"
    assert mix["clients"] == 128 == 2 * 64
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 3072,
                                 "sigma": 1.0, "min": 256, "max": 16384}
    assert mix["output_len"] == {"dist": "lognormal", "median": 1024,
                                 "sigma": 0.7, "min": 128, "max": 4096}
    assert mix["population"] % 64 == 0 and mix["warm_s"] == 10.0
    assert (mix["open_after_ended"], mix["trace_s"]) == (2, 4.0)


def test_the_parent_s_benchmark_is_in_this_one_entry_for_entry():
    """Appended entries and ``serve-agent`` appended to ``workloads`` lists:
    nothing the accepted benchmark had is changed, moved or taken away."""
    old = json.loads(subprocess.run(
        ["git", "show", "cf86b87633d357b0f27f27553db8ee8821fc3a42:"
         "BENCHMARK.json"], cwd=ROOT, capture_output=True, text=True
    ).stdout or "null")
    if old is None:
        pytest.skip("no git history here (a checkout without .git)")
    for key in ("command", "paths", "run_seconds"):
        assert BENCH[key] == old[key]
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for was, now in zip(old[group], BENCH[group]):
            if "workloads" in was and "serve-agent" in now["workloads"]:
                assert now["workloads"] == was["workloads"] + ["serve-agent"]
                now = dict(now, workloads=was["workloads"])
            assert now == was, was["name"]


NEOX_REHEARSAL_KEYS = {
    "hidden_size", "num_attention_heads", "num_hidden_layers",
    "intermediate_size", "vocab_size", "max_position_embeddings", "engine"}
LATENT_REHEARSAL_KEYS = NEOX_REHEARSAL_KEYS | {
    "kv_lora_rank", "q_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
    "v_head_dim", "moe_intermediate_size", "num_key_value_heads",
    "num_experts_per_tok", "n_routed_experts", "published", "model"}
EVA_REHEARSAL_KEYS = NEOX_REHEARSAL_KEYS | {
    "num_key_value_heads", "num_pred_heads", "window_size", "chunk_size",
    "max_seq_length", "model"}
PR24_FOUR = ["device_starved.batch", "host_ms_per_chunk.batch",
             "queue_wait_ms.mean.batch", "lane_to_first_token_ms.mean.batch"]
PR26_FOUR = ["decode_hbm_roofline.reason", "experts_touched.reason",
             "expert_load_max_over_mean.reason", "routed_here.reason"]
PR30_THREE = ["decode_hbm_roofline.longdoc",
              "state_rows_read_over_live.longdoc", "prefill_share.longdoc"]
# an architecture's module, its rehearsal keys, and where its heads of 128
# show; an ``arch`` this table does not know falls through to what every
# configuration is asked
ARCHS = {
    "pangu_ultra_moe": (pangu_ultra_moe, LATENT_REHEARSAL_KEYS,
                        lambda held, cfg: held["qk_nope_head_dim"]
                        == held["v_head_dim"] == 128),
    "evabyte": (evabyte, EVA_REHEARSAL_KEYS,
                lambda held, cfg: cfg.head_dim == 128),
    "afmoe": (arch, AFMOE_REHEARSAL_KEYS,
              lambda held, cfg: held["head_dim"] == cfg.block.head_dim == 128),
}


def test_every_assertion_of_the_red_tests_that_an_appended_entry_leaves():
    """Five tests of this directory ask that what THEIR PR appended be the
    last of its list, or that every configuration be of an architecture they
    know: ``test_chipbench_harness.py::test_configuration_files_hold_what_
    the_contract_asks`` and ``test_chipbench_serve_spans.py::test_the_four_
    entries_are_appended_for_serve_batch_alone`` (red since PR 26),
    ``test_arch_pangu_ultra_moe.py::test_benchmark_json_holds_the_new_
    entries_and_still_validates`` and ``::test_every_assertion_of_the_two_
    red_tests_that_an_appended_entry_leaves`` (since PR 30), and, since this
    PR's entries, ``test_arch_evabyte.py::test_every_assertion_of_the_red_
    tests_that_an_appended_entry_leaves`` (it looks a configuration's
    ``arch`` up in a table of two). The PR that appends may edit no file
    here, so they stay red until a ``benchmark`` PR rewrites them (PERF.md,
    section 7). Every assertion of theirs is held here, entries found BY
    NAME and an architecture's keys asked of its own configurations through
    a table that an unknown ``arch`` falls through, so that nothing the repo
    checked goes unchecked and the next appended entry leaves this test
    green."""
    for c in BENCH["configs"]:
        held = spec.load_json(os.path.join(ROOT, c["file"]))
        assert held["source"] == c["source"]
        assert held["reduced"] == c["reduced"]
        assert set(held["reduced"]) == set(held["reduced_notes"])
        for key in ("assumed", "deployment", "model", "engine", "chips",
                    "rehearsal", "architecture"):
            assert key in held, (c["name"], key)
        if "arch" not in held:
            kw = spec.gpt_config_kwargs(held)
            assert kw["d_model"] == held["hidden_size"]
            assert kw["d_model"] // kw["num_heads"] == 128     # heads of 128
            assert set(held["rehearsal"]) <= NEOX_REHEARSAL_KEYS
        elif held["arch"] in ARCHS:  # its own mapping (archs/<arch>.py)
            module, rehearsal_keys, heads_of_128 = ARCHS[held["arch"]]
            cfg = module.build_model(held).cfg
            assert cfg.d_model == held["hidden_size"]
            assert heads_of_128(held, cfg), c["name"]
            # a rehearsal never changes a key that is not a size
            assert set(held["rehearsal"]) <= rehearsal_keys
        else:                       # a later architecture: its file is there
            assert os.path.isfile(os.path.join(
                ROOT, "chipbench", "archs", held["arch"] + ".py"))
    cut = spec.load_json(os.path.join(
        ROOT, "chipbench/configs/pythia-1.4b-cut.json"))
    full = spec.load_json(os.path.join(
        ROOT, "chipbench/configs/pythia-1.4b.json"))
    differ = {k for k in full
              if k not in ("reduced", "reduced_notes", "engine", "chips",
                           "deployment") and full[k] != cut[k]}
    assert differ == {"num_hidden_layers"}     # cut in depth only

    spec.validate(BENCH)
    names = [m["name"] for m in BENCH["per_layer"]]
    # PR 24's four in order, PR 26's four behind them, PR 30's three behind
    # those, this PR's four behind those
    at = names.index(PR24_FOUR[0])
    assert names[at:at + 8] == PR24_FOUR + PR26_FOUR
    assert names[at + 8:at + 11] == PR30_THREE
    assert names[at + 11:at + 15] == NEW_READERS
    four = BENCH["per_layer"][at:at + 4]
    layers = {m["layer"] for m in BENCH["per_layer"][:at]}
    for m in four:
        # serve-batch first, then each cell in the order it was appended
        assert m["workloads"][:4] == ["serve-batch", "serve-reason",
                                      "serve-longdoc", "serve-agent"]
        assert m["source"] == "program_span" and m["better"] == "lower"
        assert m["moves"] == "serve_tokens_per_s"
    assert four[0]["layer"] in layers           # a layer already named
    assert {m["name"] for m in spec.metrics_of_cell(
        BENCH, "serve-batch", "per_layer")} >= set(PR24_FOUR)
    # PR 26's and PR 30's entries, by name
    order = [c["name"] for c in BENCH["configs"]]
    assert order.index("pangu-ultra-moe-ep16-l5") == 3
    assert order.index("evabyte-6.5b-l8") == 4
    for cell, config, traffic, theirs_only, not_theirs in (
            ("serve-reason", "pangu-ultra-moe-ep16-l5", "reason-closed",
             PR26_FOUR, PR30_THREE + NEW_READERS),
            ("serve-longdoc", "evabyte-6.5b-l8", "longdoc-closed",
             PR30_THREE, PR26_FOUR + NEW_READERS)):
        entry = _entry("workloads", cell)
        assert (entry["config"], entry["traffic"], entry["chips"]) \
            == (config, traffic, 1)
        theirs = {m["name"] for m in spec.metrics_of_cell(BENCH, cell,
                                                          "per_layer")}
        assert set(theirs_only) | NINE_BATCH == theirs
        assert "decode_hbm_roofline.batch" not in theirs
        assert not set(not_theirs) & theirs
        for name in theirs_only:
            m = _entry("per_layer", name)
            assert m["workloads"] == [cell]
            assert m["moves"] == "serve_tokens_per_s"
        assert {m["name"] for m in spec.metrics_of_cell(
            BENCH, cell, "end_to_end")} == {"serve_tokens_per_s", "setup_s"}
    mix = spec.load_json(spec.find_mix(BENCH, "longdoc-closed"))
    assert mix["kind"] == "serve_closed_long" and mix["clients"] == 32


# ------------------------------------------------------------- the rehearsal
def _env():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1",
               PYTHONPATH=ROOT)
    env.pop("BENCH_RUN", None)
    return env


@pytest.fixture(scope="module")
def rehearsal():
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", "serve-agent", "--seed", str(SEED), "--seconds", "2",
         "--trace", "1", "--rehearsal"],
        cwd=ROOT, env=_env(), capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:] + r.stdout[-3000:]
    return r.stdout


def test_traced_rehearsal_is_correct_and_prints_the_new_counters(rehearsal):
    last = json.loads(rehearsal.strip().splitlines()[-1])
    assert last["correct"] is True, rehearsal[-3000:]
    assert last["attempted"] > 0 and last["failed"] == 0
    metrics = {n[:-len(SUFFIX_REHEARSAL)]: m["value"]
               for n, m in last["metrics"].items()}
    # a CPU trace has no device plane: the device_trace metrics are silent
    assert "decode_hbm_roofline.agent" not in metrics
    assert "decode_step_ms.batch" not in metrics
    # both kinds' leaves of four lanes read whole: 4 x 16 + 128 rows a lane
    # at the toy sizes, over the live ones
    assert 1.0 < metrics["kv_rows_read_over_live.agent"] < 192.0
    assert 0.0 < metrics["experts_touched.agent"] <= 100.0
    assert metrics["expert_load_max_over_mean.agent"] >= 1.0
    assert {"occupancy.batch", "kv_live_share.batch", "ttft_ms.p50.batch",
            "device_starved.batch", "host_ms_per_chunk.batch"} <= set(metrics)


def test_rehearsal_holds_the_server_to_the_reference_past_the_window(
        rehearsal):
    for needle in ("parameters on the device, archs/afmoe.py",
                   "14 prompts of 5-14, 12-20, 43 tokens, prefill + 4 "
                   "decode steps through the cache vs the float32 reference",
                   "routing under rounding",
                   "routing counters over the check's tokens",
                   "14 reference prompts through the real server",
                   "programs built inside the measured window"):
        lines = [ln for ln in rehearsal.splitlines() if needle in ln]
        assert lines and all("] ok: " in ln for ln in lines), needle
    counted = [ln for ln in rehearsal.splitlines()
               if "routing counters over the check's tokens" in ln][0]
    assert " 0 on absent ones" in counted
    opened = [ln for ln in rehearsal.splitlines()
              if "the window opens on delivery" in ln]
    assert len(opened) == 1
    assert int(opened[0].split(",")[-1].split()[0]) >= 2


@pytest.mark.parametrize("control, passes", [("float8_e4m3fn", False),
                                             ("bfloat16", True)])
def test_the_control_goes_through_the_new_driver_s_own_comparison(control,
                                                                  passes):
    """The reference with float8 operands in the program's place, over the
    arch file's lengths and along the program's routing, has to come out as
    not correct by the cell's own limit; with the configuration's own
    precision it passes (at toy widths here; PERF.md has the reading at the
    published widths, from the same command on the chip)."""
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "drivers",
                                      "serve_closed_long_routed.py"),
         "--workload", "serve-agent", "--seed", str(SEED), str(SEED + 1),
         "--control", control, "--rehearsal"],
        cwd=ROOT, env=_env(), capture_output=True, text=True, timeout=600)
    lines = [json.loads(ln) for ln in r.stdout.splitlines()
             if ln.startswith("{")]
    assert len(lines) == 2, r.stderr[-3000:] + r.stdout[-3000:]
    assert r.returncode == (1 if passes else 0)
    for line in lines:
        assert line["correct"] is passes and line["control"] == control
        assert (line["logit_diff"] <= line["limit"]) is passes
        assert line["limit"] == arch.LOGIT_ATOL
    assert "CONTROL, the reference with" in r.stdout
    assert "by group" in r.stdout


def test_the_new_driver_leaves_the_two_before_it_as_they_were():
    from chipbench.drivers import serve_closed_arch as base
    from chipbench.drivers import serve_closed_long as long
    from chipbench.drivers import serve_closed_long_routed as drv
    assert issubclass(drv.RoutedLongServer, long.LongServer)
    assert issubclass(long.LongServer, base.ArchServer)
    seen = []
    assert drv._routed(lambda: seen.append(base.ArchServer) or 7) == 7
    assert seen == [drv.RoutedLongServer]
    assert base.ArchServer is not drv.RoutedLongServer
    assert base.ArchServer is not long.LongServer


# ------------------------------------------------------------ the new readers
def _reader(name):
    return spec.load_module(spec.find_reader(BENCH, name))


def _trace(step_ms, chunks=3, k=8):
    return trace_reduce.TraceSummary(
        window_s=1.0, n_devices=1, busy_s=0.9, op_seconds={}, op_counts={},
        module_seconds={"jit_decode_chunk_fn": chunks * k * step_ms / 1e3},
        module_counts={"jit_decode_chunk_fn": chunks}, collective_s=0.0,
        idle_gaps=[])


CELL = {"config": CONFIG}
PEAKS = {"hbm_bytes_per_s": 819e9}
WHOLE = 64 * (4 * 2048 + 20480)     # rows of both kinds' leaves, every lane


def _counters(ring_per_step, glob_per_step, touched=126.0, chunks=3.0, k=8):
    steps = chunks * k
    state = {"chunks": chunks, "kv_window_rows_live": ring_per_step * steps,
             "kv_global_rows_live": glob_per_step * steps,
             "kv_rows_read": WHOLE * steps,
             "moe_decode_steps": 4 * steps,
             "moe_decode_experts_touched": touched * 4 * steps,
             "moe_decode_load_max": 10.5 * 4 * steps,
             "moe_decode_load_mean": 4.0 * 4 * steps}
    return {"decode_chunk": k, "max_batch": 64, "peaks": PEAKS,
            "traced": dict(state), "window": dict(state)}


def test_roofline_share_counts_touched_experts_and_live_rows():
    read = _reader("decode_hbm_roofline.agent").read
    # a step that takes exactly what every expert and EVERY row of both
    # kinds' leaves cost at the peak reads 100 % with all of it live and
    # touched, and under it with less
    all_ring, all_glob = 64 * 4 * 2048, 64 * 20480
    whole = arch.decode_step_bytes(CONFIG, all_ring, all_glob, 128.0) \
        / 819e9 * 1e3
    full = _counters(all_ring, all_glob, touched=128.0)
    assert read(_trace(whole), {}, full, CELL) == pytest.approx(100.0)
    mean = read(_trace(whole), {}, _counters(64 * 4 * 1900, 64 * 5200), CELL)
    fixed = 1_220_542_464
    assert mean == pytest.approx(
        100 * (fixed + 4 * 126 * 12_582_912
               + (64 * 4 * 1900 + 64 * 5200) * 2048)
        / (fixed + 4 * 128 * 12_582_912 + WHOLE * 2048), rel=1e-9)
    assert mean < 100.0
    assert read(_trace(2 * whole), {}, full, CELL) == pytest.approx(50.0)
    # a program without the counters (the parent), no trace, no decode
    # chunk in the stretch: nothing to read, and no error
    bare = {"decode_chunk": 8, "peaks": PEAKS,
            "traced": {"chunks": 3.0}, "window": {}}
    assert read(_trace(whole), {}, bare, CELL) is None
    routed_only = {"decode_chunk": 8, "peaks": PEAKS, "window": {},
                   "traced": {"chunks": 3.0, "moe_decode_steps": 96.0,
                              "moe_decode_experts_touched": 96.0 * 126}}
    assert read(_trace(whole), {}, routed_only, CELL) is None
    assert read(None, {}, full, CELL) is None
    assert read(_trace(whole, chunks=0), {}, full, CELL) is None
    assert read(_trace(whole), {}, dict(full, peaks=None), CELL) is None


def test_counter_readers_on_synthetic_input():
    ratio = _reader("kv_rows_read_over_live.agent").read
    c = _counters(64 * 4 * 1900, 64 * 5200)
    assert ratio(None, {}, c, CELL) \
        == pytest.approx(WHOLE / (64 * 4 * 1900 + 64 * 5200))
    assert ratio(None, {}, _counters(64 * 4 * 2048, 64 * 20480), CELL) \
        == pytest.approx(1.0)
    assert ratio(None, {}, {"window": {"tokens_out": 5}}, CELL) is None
    touched = _reader("experts_touched.agent").read
    assert touched(None, {}, c, CELL) == pytest.approx(100 * 126 / 128)
    assert touched(None, {}, {"window": {}}, CELL) is None
    straggler = _reader("expert_load_max_over_mean.agent").read
    assert straggler(None, {}, c, CELL) == pytest.approx(10.5 / 4.0)
    assert straggler(None, {}, {"window": {}}, CELL) is None
