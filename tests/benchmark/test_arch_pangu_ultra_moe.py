"""The benchmark's part of the second architecture: the plain reference of
chipbench/archs/pangu_ultra_moe.py against a tiny case worked by hand, its
routing rule, its counts; the configuration file, the enlarged
BENCHMARK.json, the rehearsal of ``serve-reason`` and every new reader on a
synthetic trace and counters; the float8 control through the driver; and
every assertion of the directory's two red tests that an appended entry
leaves true."""

import copy
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
from chipbench.archs import pangu_ultra_moe as arch  # noqa: E402
from chipbench.harness import SUFFIX_REHEARSAL  # noqa: E402

BENCH = spec.load_benchmark()
CONFIG = spec.load_json(os.path.join(
    ROOT, "chipbench", "configs", "pangu-ultra-moe-ep16-l5.json"))
SEED = 3_000_000_019
NEW_READERS = ["decode_hbm_roofline.reason", "experts_touched.reason",
               "expert_load_max_over_mean.reason", "routed_here.reason"]

TINY = {
    "arch": "pangu_ultra_moe", "hidden_size": 8, "intermediate_size": 12,
    "num_attention_heads": 2, "q_lora_rank": 6, "kv_lora_rank": 4,
    "qk_nope_head_dim": 4, "qk_rope_head_dim": 4, "v_head_dim": 4,
    "moe_intermediate_size": 6, "n_routed_experts": 2, "n_shared_experts": 1,
    "num_experts_per_tok": 2, "norm_topk_prob": True,
    "routed_scaling_factor": 2.5, "num_hidden_layers": 2,
    "first_k_dense_replace": 1, "vocab_size": 10,
    "max_position_embeddings": 8, "rms_norm_eps": 1e-5, "rope_theta": 100.0,
    "tie_word_embeddings": False, "published": {"n_routed_experts": 4},
    "deployment_share": {"expert_offset": 1},
}


def _tiny_params(rng):
    d, h, rq, r, dn, dr, dv, fd, f = 8, 2, 6, 4, 4, 4, 4, 12, 6

    def w(*shape):
        return (rng.standard_normal(shape) / math.sqrt(shape[-2])
                ).astype(np.float32)

    def g(*shape):
        return rng.uniform(0.5, 1.5, shape).astype(np.float32)

    def layer(n):
        return {"ln_in": g(n, d), "ln_post_attn": g(n, d),
                "ln_pre_mlp": g(n, d), "ln_post_mlp": g(n, d),
                "q_down": w(n, d, rq), "q_norm": g(n, rq),
                "q_up": w(n, rq, h * (dn + dr)), "kv_down": w(n, d, r + dr),
                "kv_norm": g(n, r), "k_up": w(n, r, h * dn),
                "v_up": w(n, r, h * dv), "out_proj": w(n, h * dv, d)}
    dense = dict(layer(1), gate_proj=w(1, d, fd), up_proj=w(1, d, fd),
                 down_proj=w(1, fd, d))
    sparse = dict(layer(1), router=w(1, d, 4), shared_gate=w(1, d, f),
                  shared_up=w(1, d, f), shared_down=w(1, f, d),
                  expert_gate=w(1, 2, d, f), expert_up=w(1, 2, d, f),
                  expert_down=w(1, 2, f, d))
    return {"wte": {"embedding": w(10, d)},
            "blocks": {"dense": dense, "sparse": sparse},
            "ln_f": {"scale": g(d)}, "lm_head": {"kernel": w(d, 10)}}


def _by_hand(p, ids):
    """The docstring's equations with loops over tokens, heads and pairs, in
    float64: nothing shared with the reference but the parameter names."""
    c = TINY
    h, r = c["num_attention_heads"], c["kv_lora_rank"]
    dn, dr, dv = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                  c["v_head_dim"])
    eps = c["rms_norm_eps"]
    f64 = lambda t: {k: np.asarray(v, np.float64) for k, v in t.items()}  # noqa: E731

    def rms(x, gain):
        return x / math.sqrt(float(np.mean(x * x)) + eps) * gain

    def turn(x, pos):               # pairs (x0, x1), (x2, x3), ...
        out = np.empty_like(x)
        for j in range(len(x) // 2):
            a = pos / (c["rope_theta"] ** (2 * j / len(x)))
            out[2 * j] = x[2 * j] * math.cos(a) - x[2 * j + 1] * math.sin(a)
            out[2 * j + 1] = x[2 * j + 1] * math.cos(a) \
                + x[2 * j] * math.sin(a)
        return out

    def silu(x):
        return x / (1.0 + np.exp(-x))

    def mlp(x, gate, up, down):
        return (silu(x @ gate) * (x @ up)) @ down

    def attention(xs, w):
        n = len(xs)
        q, kn, kr, v = [], [], [], []
        for t in range(n):
            y = rms(xs[t], w["ln_in"])
            qt = (rms(y @ w["q_down"], w["q_norm"]) @ w["q_up"]
                  ).reshape(h, dn + dr)
            ckv = y @ w["kv_down"]
            lat = rms(ckv[:r], w["kv_norm"])
            q.append([np.concatenate([qt[i, :dn], turn(qt[i, dn:], t)])
                      for i in range(h)])
            kn.append((lat @ w["k_up"]).reshape(h, dn))
            v.append((lat @ w["v_up"]).reshape(h, dv))
            kr.append(turn(ckv[r:], t))
        out = []
        for t in range(n):
            heads = []
            for i in range(h):
                s = np.array([(q[t][i][:dn] @ kn[u][i]
                               + q[t][i][dn:] @ kr[u])
                              / math.sqrt(dn + dr) for u in range(t + 1)])
                pr = np.exp(s - s.max())
                pr /= pr.sum()
                heads.append(sum(pr[u] * v[u][i] for u in range(t + 1)))
            out.append(xs[t] + rms(np.concatenate(heads) @ w["out_proj"],
                                   w["ln_post_attn"]))
        return out

    emb = np.asarray(p["wte"]["embedding"], np.float64)
    xs = [emb[i] for i in ids]
    w = f64({k: v[0] for k, v in p["blocks"]["dense"].items()})
    hs = attention(xs, w)
    xs = [hid + rms(mlp(rms(hid, w["ln_pre_mlp"]), w["gate_proj"],
                        w["up_proj"], w["down_proj"]), w["ln_post_mlp"])
          for hid in hs]
    w = f64({k: v[0] for k, v in p["blocks"]["sparse"].items()})
    hs = attention(xs, w)
    xs = []
    for hid in hs:
        y = rms(hid, w["ln_pre_mlp"])
        s = 1.0 / (1.0 + np.exp(-(y @ w["router"])))
        top = np.argsort(-s)[:2]
        ffn = mlp(y, w["shared_gate"], w["shared_up"], w["shared_down"])
        for e in top:
            if 1 <= e < 3:          # experts 1 and 2 are held here
                ffn = ffn + 2.5 * s[e] / (s[top].sum() + 1e-20) * mlp(
                    y, w["expert_gate"][e - 1], w["expert_up"][e - 1],
                    w["expert_down"][e - 1])
        xs.append(hid + rms(ffn, w["ln_post_mlp"]))
    gain = np.asarray(p["ln_f"]["scale"], np.float64)
    head = np.asarray(p["lm_head"]["kernel"], np.float64)
    return np.stack([rms(x, gain) @ head for x in xs])


# ------------------------------------------------------------ the reference
def test_reference_equals_a_tiny_case_worked_by_hand():
    rng = np.random.default_rng(11)
    p = _tiny_params(rng)
    for ids in ([3, 1, 4, 1, 5], [9, 2, 6]):
        got, _ = arch.reference_logits(TINY, p, np.asarray([ids], np.int32))
        np.testing.assert_allclose(np.asarray(got)[0], _by_hand(p, ids),
                                   atol=2e-5)
    assert arch.param_count(TINY) == sum(
        x.size for g in (p["wte"], p["blocks"]["dense"],
                         p["blocks"]["sparse"], p["ln_f"], p["lm_head"])
        for x in g.values())


@pytest.mark.parametrize("fault", ["rotary base", "routed sum unscaled",
                                   "a post-norm's gain left out",
                                   "router input rounded to float8"])
def test_the_limits_refuse_what_the_issue_lists(fault):
    """At toy widths already: each of these moves logits past LOGIT_ATOL or
    a choice past ROUTE_EPS."""
    import jax.numpy as jnp
    rng = np.random.default_rng(12)
    p = _tiny_params(rng)
    ids = rng.integers(0, 10, (2, 8)).astype(np.int32)
    ref, _ = arch.reference_logits(TINY, p, ids)
    bad_cfg, bad_p = copy.deepcopy(TINY), copy.deepcopy(p)
    if fault == "rotary base":
        bad_cfg["rope_theta"] = 10000.0
    elif fault == "routed sum unscaled":
        bad_cfg["routed_scaling_factor"] = 1.0
    elif fault == "a post-norm's gain left out":
        bad_p["blocks"]["sparse"]["ln_post_mlp"][:] = 1.0
    else:
        def choice_of(lower, n=4096):
            x = np.random.default_rng(14).standard_normal((n, 8)
                                                          ).astype(np.float32)
            router = np.asarray(p["blocks"]["sparse"]["router"][0])
            scores = 1.0 / (1.0 + np.exp(-(x @ router)))
            seen = np.asarray(jnp.asarray(x).astype(lower).astype(jnp.float32))
            theirs = np.argsort(-(seen @ router), -1, kind="stable")[:, :2]
            report = {"sets": 0, "sets_differing": 0, "sets_refused": 0,
                      "largest_gap": 0.0, "pairs_swapped": 0}
            arch._route(scores, 2, theirs.astype(np.int32), report)
            return report
        fp8, bf16 = choice_of(jnp.float8_e4m3fn), choice_of(jnp.bfloat16)
        assert fp8["sets_refused"] > 0 and fp8["largest_gap"] > arch.ROUTE_EPS
        # the configuration's own precision changes places too, inside it
        assert bf16["sets_differing"] > 0 and bf16["sets_refused"] == 0
        return
    bad, _ = arch.reference_logits(bad_cfg, bad_p, ids)
    assert float(np.max(np.abs(np.asarray(bad) - np.asarray(ref)))) \
        > arch.LOGIT_ATOL


def test_routing_rule_follows_inside_epsilon_and_refuses_outside():
    eps = arch.ROUTE_EPS
    scores = np.array([[0.9, 0.8, 0.5, 0.5 - eps / 2, 0.1],
                       [0.9, 0.8, 0.5, 0.5 - 2 * eps, 0.1],
                       [0.9, 0.8, 0.5, 0.4, 0.1]], np.float32)
    theirs = np.array([[0, 1, 3], [0, 1, 3], [-1, -1, -1]], np.int32)
    report = {"sets": 0, "sets_differing": 0, "sets_refused": 0,
              "largest_gap": 0.0, "pairs_swapped": 0}
    out = arch._route(scores, 3, theirs, report)
    assert sorted(out[0]) == [0, 1, 3]      # inside epsilon: follows
    assert sorted(out[1]) == [0, 1, 2]      # outside: keeps its own
    assert sorted(out[2]) == [0, 1, 2]      # the program ran no such token
    assert report == {"sets": 2, "sets_differing": 2, "sets_refused": 1,
                      "largest_gap": pytest.approx(2 * eps, rel=1e-3),
                      "pairs_swapped": 1}     # the one set it followed
    same = {"sets": 0, "sets_differing": 0, "sets_refused": 0,
            "largest_gap": 0.0, "pairs_swapped": 0}
    arch._route(scores[:1], 3, np.array([[1, 2, 0]], np.int32), same)
    assert same["sets"] == 1 and same["sets_differing"] == 0


def test_a_lower_precision_reads_further_from_the_reference():
    """The reading that places LOGIT_ATOL from above (PERF.md has it at the
    published widths): the reference with every matmul operand rounded to
    float8 differs from itself by more than bfloat16 rounding does."""
    import jax.numpy as jnp
    rng = np.random.default_rng(13)
    p = _tiny_params(rng)
    ids = rng.integers(0, 10, (2, 8)).astype(np.int32)
    ref = np.asarray(arch.reference_logits(TINY, p, ids)[0])
    fp8 = np.asarray(arch.reference_logits(
        TINY, p, ids, lower=jnp.float8_e4m3fn)[0])
    bf16 = np.asarray(arch.reference_logits(
        TINY, p, ids, lower=jnp.bfloat16)[0])
    assert np.abs(fp8 - ref).max() > 4 * np.abs(bf16 - ref).max() > 0


# --------------------------------------------- the configuration and its cell
PUBLISHED = {       # the catalog's row, by hand: every width, rank, head size
    "hidden_size": 7680, "intermediate_size": 18432, "kv_lora_rank": 512,
    "q_lora_rank": 1536, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "v_head_dim": 128, "moe_intermediate_size": 2048,
    "num_attention_heads": 128, "num_key_value_heads": 128,
    "num_experts_per_tok": 8, "n_shared_experts": 1, "norm_topk_prob": True,
    "routed_scaling_factor": 2.5, "rms_norm_eps": 1e-5,
    "rope_theta": 25600000, "sandwich_norm": True, "hidden_act": "silu",
    "attention_bias": False, "tie_word_embeddings": False,
    "model_type": "pangu_ultra_moe"}
REDUCED = {"num_hidden_layers": (5, 61), "first_k_dense_replace": (1, 3),
           "n_routed_experts": (16, 256), "vocab_size": (19200, 153600),
           "max_position_embeddings": (4096, 131072),
           "num_nextn_predict_layers": (0, 1)}


def test_the_configuration_holds_every_published_width_and_says_its_cut():
    for key, value in PUBLISHED.items():
        assert CONFIG[key] == value, key
    assert sorted(CONFIG["reduced"]) == sorted(REDUCED)
    for key, (here, published) in REDUCED.items():
        assert CONFIG[key] == here and CONFIG["published"][key] == published
        assert key in CONFIG["reduced_notes"]
        assert not key.endswith(("_dim", "_rank"))
    assert CONFIG["deployment_share"] == {
        "chips_per_layer": 16, "expert_offset": 0, "vocab_slices": 8,
        "vocab_slice": 0}
    assert CONFIG["engine"]["serving_engine"] == {
        "max_batch": 64, "max_prompt_len": 2048,
        "prefill_buckets": [128, 256, 512, 1024, 2048]}
    assert CONFIG["engine"]["frontend"] == {"feed_depth": 4}
    assert any("sigmoid" in a for a in CONFIG["assumed"])
    assert any("multi-token-prediction" in a for a in CONFIG["departures"])
    # the rehearsal changes sizes and the share's published count only
    assert set(CONFIG["rehearsal"]) <= set(PUBLISHED) | set(REDUCED) | {
        "published", "model", "engine"}


def test_counts_from_shapes_are_the_issue_s_table():
    assert arch.attention_params(CONFIG) == 196_577_280
    assert arch.dense_layer_params(CONFIG) == 621_281_280
    assert arch.expert_params(CONFIG) == 47_185_920
    assert arch.expert_layer_params(CONFIG) == 1_000_734_720
    assert arch.param_count(CONFIG) == 4_919_139_840
    assert arch.latent_bytes_per_token(CONFIG) == 5_760
    # every weight but the embedding's rows and the norms, all 16 experts
    # touched, nothing live: 4.92 B - 19200 x 7680 - norms, in bf16
    norms = 5 * (4 * 7680 + 1536 + 512) + 7680
    assert arch.decode_step_bytes(CONFIG, 0, 16) == 2 * (
        4_919_139_840 - 19200 * 7680 - norms)
    assert arch.decode_step_bytes(CONFIG, 1000, 16) \
        - arch.decode_step_bytes(CONFIG, 0, 16) == 5_760_000
    assert arch.decode_step_bytes(CONFIG, 0, 16) \
        - arch.decode_step_bytes(CONFIG, 0, 15) == 4 * 2 * 47_185_920


def test_build_model_maps_the_published_keys():
    model = arch.build_model(CONFIG)
    cfg, block = model.cfg, model.cfg.block
    assert (cfg.d_model, cfg.num_heads, cfg.num_layers, cfg.d_ff) \
        == (7680, 128, 5, 18432)
    assert (cfg.vocab_size, cfg.max_seq_len) == (19200, 4096)
    assert cfg.rotary_base == 25.6e6 and not cfg.tie_embeddings
    assert (block.q_lora_rank, block.kv_lora_rank, block.qk_nope_head_dim,
            block.qk_rope_head_dim, block.v_head_dim) \
        == (1536, 512, 128, 64, 128)
    assert (block.n_routed_experts, block.experts_held, block.expert_offset,
            block.experts_per_token, block.moe_d_ff, block.dense_layers) \
        == (256, 16, 0, 8, 2048, 1)
    assert block.routed_scaling_factor == 2.5 and block.norm_topk_prob
    assert (block.latent_dim, block.cache_row) == (576, 640)


def test_benchmark_json_holds_the_new_entries_and_still_validates():
    spec.validate(BENCH)
    entry = BENCH["configs"][-1]
    assert entry["name"] == "pangu-ultra-moe-ep16-l5"
    assert entry["source"] == CONFIG["source"]
    assert entry["reduced"] == CONFIG["reduced"]
    cell = BENCH["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) \
        == ("serve-reason", "pangu-ultra-moe-ep16-l5", "reason-closed", 1)
    mine = {m["name"] for m in spec.metrics_of_cell(BENCH, "serve-reason",
                                                    "per_layer")}
    assert set(NEW_READERS) <= mine
    assert "decode_hbm_roofline.batch" not in mine    # a NeoX block's bytes
    for m in BENCH["per_layer"][-4:]:
        assert m["name"] in NEW_READERS
        assert m["workloads"] == ["serve-reason"]
        assert m["moves"] == "serve_tokens_per_s"
    assert {m["name"] for m in spec.metrics_of_cell(
        BENCH, "serve-reason", "end_to_end")} == {"serve_tokens_per_s",
                                                  "setup_s"}
    mix = spec.load_json(spec.find_mix(BENCH, "reason-closed"))
    assert mix["kind"] == "serve_closed_arch" and mix["clients"] == 128
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 512,
                                 "sigma": 0.9, "min": 32, "max": 2048}
    assert mix["output_len"] == {"dist": "lognormal", "median": 768,
                                 "sigma": 0.7, "min": 64, "max": 2048}
    assert mix["population"] % 64 == 0 and mix["warm_s"] == 8.0


NEOX_REHEARSAL_KEYS = {
    "hidden_size", "num_attention_heads", "num_hidden_layers",
    "intermediate_size", "vocab_size", "max_position_embeddings", "engine"}
# the latent block's sizes, the share's published count, and the CPU's dtype
ARCH_REHEARSAL_KEYS = NEOX_REHEARSAL_KEYS | {
    "kv_lora_rank", "q_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
    "v_head_dim", "moe_intermediate_size", "num_key_value_heads",
    "num_experts_per_tok", "n_routed_experts", "published", "model"}
PR24_FOUR = ["device_starved.batch", "host_ms_per_chunk.batch",
             "queue_wait_ms.mean.batch", "lane_to_first_token_ms.mean.batch"]


def test_every_assertion_of_the_two_red_tests_that_an_appended_entry_leaves():
    """``test_chipbench_harness.py::test_configuration_files_hold_what_the_
    contract_asks`` asks GPT-NeoX keys and heads of 128 of EVERY configuration
    and ``test_chipbench_serve_spans.py::test_the_four_entries_are_appended_
    for_serve_batch_alone`` asks that PR 24's four entries be the LAST and
    list ``serve-batch`` alone: ISSUE 26's entries make both fail, no file of
    this directory may be edited by the PR that appends them, and they stay
    red until a ``benchmark`` PR rewrites them (PERF.md, section 7). Every
    assertion of theirs is held here, word for word where it can be, so
    that nothing the repo checked goes unchecked meanwhile."""
    for c in BENCH["configs"]:
        held = spec.load_json(os.path.join(ROOT, c["file"]))
        assert held["source"] == c["source"]
        assert held["reduced"] == c["reduced"]
        assert set(held["reduced"]) == set(held["reduced_notes"])
        for key in ("assumed", "deployment", "model", "engine", "chips",
                    "rehearsal", "architecture"):
            assert key in held, (c["name"], key)
        if "arch" in held:          # its own mapping (archs/<arch>.py)
            cfg = arch.build_model(held).cfg
            assert cfg.d_model == held["hidden_size"]
            assert held["qk_nope_head_dim"] == held["v_head_dim"] == 128
            # a rehearsal never changes a key that is not a size
            assert set(held["rehearsal"]) <= ARCH_REHEARSAL_KEYS
        else:
            kw = spec.gpt_config_kwargs(held)
            assert kw["d_model"] == held["hidden_size"]
            assert kw["d_model"] // kw["num_heads"] == 128     # heads of 128
            assert set(held["rehearsal"]) <= NEOX_REHEARSAL_KEYS
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
    # PR 24's four, in order, and behind them this PR's readers alone
    at = names.index(PR24_FOUR[0])
    assert names[at:] == PR24_FOUR + NEW_READERS
    four = BENCH["per_layer"][at:at + 4]
    layers = {m["layer"] for m in BENCH["per_layer"][:at]}
    for m in four:
        assert m["workloads"] == ["serve-batch", "serve-reason"]
        assert m["source"] == "program_span" and m["better"] == "lower"
        assert m["moves"] == "serve_tokens_per_s"
    assert four[0]["layer"] in layers           # a layer already named
    assert {m["name"] for m in spec.metrics_of_cell(
        BENCH, "serve-batch", "per_layer")} >= set(PR24_FOUR)


# ------------------------------------------------------------- the rehearsal
@pytest.fixture(scope="module")
def rehearsal():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1",
               PYTHONPATH=ROOT)
    env.pop("BENCH_RUN", None)
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", "serve-reason", "--seed", str(SEED), "--seconds", "2",
         "--trace", "1", "--rehearsal"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:] + r.stdout[-3000:]
    return r.stdout


def test_traced_rehearsal_is_correct_and_prints_the_new_counters(rehearsal):
    last = json.loads(rehearsal.strip().splitlines()[-1])
    assert last["correct"] is True, rehearsal[-3000:]
    assert last["attempted"] > 0 and last["failed"] == 0
    metrics = {n[:-len(SUFFIX_REHEARSAL)]: m["value"]
               for n, m in last["metrics"].items()}
    # a CPU trace has no device plane: the roofline share stays silent
    assert set(NEW_READERS) - {"decode_hbm_roofline.reason"} <= set(metrics)
    assert 0 < metrics["experts_touched.reason"] <= 100
    assert metrics["expert_load_max_over_mean.reason"] >= 1
    assert 0 < metrics["routed_here.reason"] < 100


def test_rehearsal_holds_the_server_to_the_reference(rehearsal):
    for needle in ("parameters on the device, archs/pangu_ultra_moe.py",
                   "decode steps through the cache vs the float32 reference",
                   "routing under rounding", "routing counters over the",
                   "reference prompts through the real server",
                   "programs built inside the measured window"):
        lines = [ln for ln in rehearsal.splitlines() if needle in ln]
        assert lines and all("] ok: " in ln for ln in lines), needle


def test_the_window_opens_on_an_event_and_says_what_it_was_made_of(rehearsal):
    opened = [ln for ln in rehearsal.splitlines()
              if "the window opens on delivery" in ln]
    assert len(opened) == 1
    ended = int(opened[0].split(",")[-1].split()[0])
    assert ended >= 2           # the mix's open_after_ended
    anatomy = [ln for ln in rehearsal.splitlines() if "window anatomy" in ln]
    assert len(anatomy) == 1 and "tokens/s by fifths" in anatomy[0]


def test_window_anatomy_on_a_hand_made_log():
    from types import SimpleNamespace as NS
    from chipbench.drivers import serve_closed_arch as drv
    # deliveries every 0.2 s, a prefill of 0.5 s before the 4th and the 9th
    times, t = [], 0.0
    for i in range(12):
        t += 0.2 + (0.5 if i in (3, 8) else 0.0)
        times.append(round(t, 3))
    plan = NS(prompt=[0] * 100)
    tracked = [NS(first_t=times[3], plan=plan), NS(first_t=times[3], plan=plan),
               NS(first_t=times[8], plan=plan), NS(first_t=None, plan=plan)]
    client = NS(token_log=[(x, 512) for x in times], all=lambda: tracked,
                tokens_between=lambda a, b: sum(
                    512 for x in times if a < x <= b))
    said = drv.window_anatomy(client, times[0], times[-1])
    assert said.startswith("11 gaps between deliveries, ms: p10 200.0 p50 "
                           "200.0 p90 700.0 max 700.0, 2 over 1.5 x p50")
    assert "3 requests started (300 prompt tokens) in deliveries of " \
        "{1: 1, 2: 1}" in said
    assert drv.window_anatomy(client, times[0], times[3]).startswith("too few")


@pytest.mark.parametrize("control, passes", [("float8_e4m3fn", False),
                                             ("bfloat16", True)])
def test_the_control_goes_through_the_drivers_own_comparison(control, passes):
    """The reference with float8 operands in the program's place has to come
    out as not correct, by the cell's own limit; with the configuration's own
    precision it passes (at toy widths here; PERF.md has the reading at the
    published widths, from the same command on the chip)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1",
               PYTHONPATH=ROOT)
    r = subprocess.run(
        [sys.executable,
         os.path.join(ROOT, "chipbench", "drivers", "serve_closed_arch.py"),
         "--workload", "serve-reason", "--seed", str(SEED), str(SEED + 1),
         "--control", control, "--rehearsal"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    lines = [json.loads(ln) for ln in r.stdout.splitlines()
             if ln.startswith("{")]
    assert len(lines) == 2, r.stderr[-3000:] + r.stdout[-3000:]
    assert r.returncode == (1 if passes else 0)
    for line in lines:
        assert line["correct"] is passes and line["control"] == control
        assert (line["logit_diff"] <= line["limit"]) is passes
        assert line["limit"] == arch.LOGIT_ATOL
    assert "CONTROL, the reference with" in r.stdout


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


def _counters(touched_per_step, steps=96.0, live=64 * 1450.0):
    routed = {"moe_decode_steps": steps,
              "moe_decode_experts_touched": touched_per_step * steps,
              "moe_decode_load_max": 5.0 * steps,
              "moe_decode_load_mean": 2.0 * steps,
              "moe_decode_pairs_held": 32.0 * steps,
              "moe_decode_pairs_absent": 480.0 * steps,
              "moe_prefill_pairs_held": 100.0,
              "moe_prefill_pairs_absent": 1500.0}
    return {"decode_chunk": 8, "peaks": PEAKS, "kv_live_mean_traced": live,
            "traced": dict(routed), "window": dict(routed)}


def test_roofline_share_counts_touched_experts_and_stays_under_100():
    read = _reader("decode_hbm_roofline.reason").read
    # a step that takes exactly what ALL 16 experts and a FULL arena cost
    # at the peak reads 100 %, and under it when fewer were touched or live
    full = arch.decode_step_bytes(CONFIG, 64 * 4096, 16) / 819e9 * 1e3
    assert read(_trace(full), {}, _counters(16, live=64 * 4096.0), CELL) \
        == pytest.approx(100.0)
    some = read(_trace(full), {}, _counters(13.9), CELL)
    assert 80 < some < 100
    assert read(_trace(2 * full), {}, _counters(16, live=64 * 4096.0),
                CELL) == pytest.approx(50.0)
    # a program without the counters (the parent), no trace, no decode
    # chunk in the stretch: nothing to read, and no error
    bare = {"decode_chunk": 8, "peaks": PEAKS, "kv_live_mean_traced": 1.0,
            "traced": {}, "window": {}}
    assert read(_trace(full), {}, bare, CELL) is None
    assert read(None, {}, _counters(16), CELL) is None
    assert read(_trace(full, chunks=0), {}, _counters(16), CELL) is None


def test_counter_readers_on_synthetic_counters():
    c = _counters(13.9)
    assert _reader("experts_touched.reason").read(None, {}, c, CELL) \
        == pytest.approx(100 * 13.9 / 16)
    assert _reader("expert_load_max_over_mean.reason").read(
        None, {}, c, CELL) == pytest.approx(2.5)
    assert _reader("routed_here.reason").read(None, {}, c, CELL) \
        == pytest.approx(100 * (32 * 96 + 100) / (512 * 96 + 1600))
    bare = {"window": {"tokens_out": 5}, "traced": {}}
    for name in NEW_READERS[1:]:
        assert _reader(name).read(None, {}, bare, CELL) is None
