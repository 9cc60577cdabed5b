"""The benchmark's part of the third architecture: the plain reference of
chipbench/archs/evabyte.py against a tiny case worked by hand, the mutations
it has to catch, its counts; the configuration file, the enlarged
BENCHMARK.json, the rehearsal of ``serve-longdoc`` through ``chipbench/run.py``
and every new reader on a synthetic trace and counters; the float8 control
through the new driver; and every assertion of the directory's red tests
that an appended entry leaves true."""

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
from chipbench.archs import evabyte as arch  # noqa: E402
from chipbench.archs import pangu_ultra_moe  # noqa: E402
from chipbench.harness import SUFFIX_REHEARSAL  # noqa: E402

BENCH = spec.load_benchmark()
CONFIG = spec.load_json(os.path.join(
    ROOT, "chipbench", "configs", "evabyte-6.5b-l8.json"))
SEED = 3_000_000_030
NEW_READERS = ["decode_hbm_roofline.longdoc",
               "state_rows_read_over_live.longdoc", "prefill_share.longdoc"]

# window 8, chunk 4: three windows in 20 tokens, two summaries a window
TINY = {
    "arch": "evabyte", "hidden_size": 8, "intermediate_size": 12,
    "num_attention_heads": 2, "num_key_value_heads": 2,
    "num_hidden_layers": 2, "vocab_size": 10, "num_pred_heads": 2,
    "window_size": 8, "chunk_size": 4, "max_position_embeddings": 32,
    "rms_norm_eps": 1e-5, "rope_theta": 100.0, "tie_word_embeddings": False,
}


def _tiny_params(rng):
    d, h, dh, f, n = 8, 2, 4, 12, 2

    def w(*shape):
        return (rng.standard_normal(shape) / math.sqrt(shape[-2])
                ).astype(np.float32)

    def g(*shape):
        return (0.3 * rng.standard_normal(shape)).astype(np.float32)

    blocks = {"ln_1": g(n, d), "ln_2": g(n, d), "q_proj": w(n, d, d),
              "k_proj": w(n, d, d), "v_proj": w(n, d, d),
              "o_proj": w(n, d, d),
              "adaptive_mu_k": rng.standard_normal((n, h, dh)
                                                   ).astype(np.float32),
              "adaptive_phi": rng.standard_normal((n, h, dh)
                                                  ).astype(np.float32),
              "gate_proj": w(n, d, f), "up_proj": w(n, d, f),
              "down_proj": w(n, f, d)}
    return {"wte": {"embedding": rng.standard_normal((10, d)
                                                     ).astype(np.float32)},
            "blocks": blocks, "ln_f": {"scale": g(d)},
            "lm_head": {"kernel": w(d, 20)}}


def _by_hand(p, ids, early=False):
    """The docstring's equations with loops over tokens, heads, chunks and
    pairs, in float64: nothing shared with the reference but the parameter
    names. ``early``: the fault of seeing a whole chunk of the RUNNING window
    as a summary too (summaries visible one window early)."""
    c = TINY
    h, w, ch = c["num_attention_heads"], c["window_size"], c["chunk_size"]
    dh = c["hidden_size"] // h
    eps = c["rms_norm_eps"]

    def rms(x, gain):
        return x / math.sqrt(float(np.mean(x * x)) + eps) * (1.0 + gain)

    def turn(x, pos):               # pairs (x_i, x_{i + dh/2})
        out = np.empty_like(x)
        half = len(x) // 2
        for j in range(half):
            a = pos / (c["rope_theta"] ** (j / half))
            out[j] = x[j] * math.cos(a) - x[j + half] * math.sin(a)
            out[j + half] = x[j + half] * math.cos(a) + x[j] * math.sin(a)
        return out

    def softmax(s):
        e = np.exp(np.asarray(s) - max(s))
        return e / e.sum()

    emb = np.asarray(p["wte"]["embedding"], np.float64)
    xs = [emb[i] for i in ids]
    n = len(xs)
    for layer in range(c["num_hidden_layers"]):
        wt = {k: np.asarray(v[layer], np.float64)
              for k, v in p["blocks"].items()}
        q, k, v = [], [], []
        for t in range(n):
            u = rms(xs[t], wt["ln_1"])
            q.append([turn((u @ wt["q_proj"])[i * dh:(i + 1) * dh], t)
                      for i in range(h)])
            k.append([turn((u @ wt["k_proj"])[i * dh:(i + 1) * dh], t)
                      for i in range(h)])
            v.append([(u @ wt["v_proj"])[i * dh:(i + 1) * dh]
                      for i in range(h)])
        hs = []
        for t in range(n):
            heads = []
            for i in range(h):
                keys, vals = [], []
                for m in range(t // w * w, t + 1):      # its own window
                    keys.append(k[m][i])
                    vals.append(v[m][i])
                for j in range(n // ch + 1):            # chunks before it
                    seen = (j * ch) // w < t // w
                    if early:
                        seen = seen or ((j * ch) // w == t // w
                                        and (j + 1) * ch <= t)
                    if not seen:
                        continue
                    toks = range(j * ch, (j + 1) * ch)
                    pk = softmax([wt["adaptive_mu_k"][i] @ k[m][i]
                                  for m in toks])
                    pv = softmax([wt["adaptive_phi"][i] @ k[m][i]
                                  for m in toks])
                    keys.append(sum(a * k[m][i] for a, m in zip(pk, toks)))
                    vals.append(sum(a * v[m][i] for a, m in zip(pv, toks)))
                pr = softmax([q[t][i] @ key / math.sqrt(dh) for key in keys])
                heads.append(sum(a * val for a, val in zip(pr, vals)))
            hs.append(xs[t] + np.concatenate(heads) @ wt["o_proj"])
        xs = []
        for hid in hs:
            u = rms(hid, wt["ln_2"])
            gate = u @ wt["gate_proj"]
            xs.append(hid + (gate / (1.0 + np.exp(-gate))
                             * (u @ wt["up_proj"])) @ wt["down_proj"])
    gain = np.asarray(p["ln_f"]["scale"], np.float64)
    head = np.asarray(p["lm_head"]["kernel"], np.float64)
    return np.stack([rms(x, gain) @ head for x in xs]).reshape(n, 2, 10)


# ------------------------------------------------------------ the reference
def test_reference_equals_a_tiny_case_worked_by_hand():
    rng = np.random.default_rng(11)
    p = _tiny_params(rng)
    for n in (20, 9, 3):            # three windows; an edge; inside one
        ids = rng.integers(0, 10, (n,)).astype(np.int32)
        got, report = arch.reference_logits(TINY, p, ids[None])
        assert report is None
        np.testing.assert_allclose(np.asarray(got)[0], _by_hand(p, ids),
                                   atol=3e-5)
    assert arch.param_count(TINY) == sum(
        x.size for g in (p["wte"], p["blocks"], p["ln_f"], p["lm_head"])
        for x in g.values())


@pytest.mark.parametrize("fault", [
    "summaries visible one window early", "mu and phi swapped",
    "the norm's unit offset dropped", "another rotary base"])
def test_the_limit_refuses_what_the_issue_lists(fault):
    """At toy widths already each of these moves logits past LOGIT_ATOL."""
    rng = np.random.default_rng(12)
    p = _tiny_params(rng)
    ids = rng.integers(0, 10, (20,)).astype(np.int32)
    ref = np.asarray(arch.reference_logits(TINY, p, ids[None])[0])[0]
    bad_cfg, bad_p = copy.deepcopy(TINY), copy.deepcopy(p)
    if fault == "summaries visible one window early":
        bad = _by_hand(p, ids, early=True)
        # the fault is the rule's alone: inside the first chunk nothing moves
        assert np.max(np.abs(bad[:4] - ref[:4])) < 3e-5
    else:
        if fault == "mu and phi swapped":
            b = bad_p["blocks"]
            b["adaptive_mu_k"], b["adaptive_phi"] = \
                b["adaptive_phi"], b["adaptive_mu_k"]
        elif fault == "the norm's unit offset dropped":
            # x / rms * g in place of x / rms * (1 + g)
            for tree, name in ((bad_p["blocks"], "ln_1"),
                               (bad_p["blocks"], "ln_2"),
                               (bad_p["ln_f"], "scale")):
                tree[name] = tree[name] - 1.0
        else:
            bad_cfg["rope_theta"] = 10000.0
        bad = np.asarray(arch.reference_logits(bad_cfg, bad_p,
                                               ids[None])[0])[0]
    assert float(np.max(np.abs(bad - ref))) > arch.LOGIT_ATOL, fault
    if fault == "mu and phi swapped":
        # and only where a summary is seen: the first window is untouched
        assert np.max(np.abs(bad[:8] - ref[:8])) < 3e-5


def test_a_lower_precision_reads_further_from_the_reference():
    """The reading that places LOGIT_ATOL from above (PERF.md has it at the
    published widths): the reference with every matmul operand rounded to
    float8 differs from itself by more than bfloat16 rounding does."""
    import jax.numpy as jnp
    rng = np.random.default_rng(13)
    p = _tiny_params(rng)
    ids = rng.integers(0, 10, (2, 20)).astype(np.int32)
    ref = np.asarray(arch.reference_logits(TINY, p, ids)[0])
    fp8 = np.asarray(arch.reference_logits(
        TINY, p, ids, lower=jnp.float8_e4m3fn)[0])
    bf16 = np.asarray(arch.reference_logits(
        TINY, p, ids, lower=jnp.bfloat16)[0])
    assert np.abs(fp8 - ref).max() > 4 * np.abs(bf16 - ref).max() > 0


# --------------------------------------------- the configuration and its cell
PUBLISHED = {       # the catalog's row, by hand
    "attention_bias": False, "attention_class": "eva", "chunk_size": 16,
    "fp32_ln": False, "fp32_logits": True, "fp32_skip_add": True,
    "hidden_act": "silu", "hidden_size": 4096, "init_cutoff_factor": None,
    "init_fn": "v2", "init_std": 0.01275, "intermediate_size": 11008,
    "lazy_init": True, "max_position_embeddings": 32768,
    "max_seq_length": 32768, "mixedp_attn": True, "model_type": "evabyte",
    "norm_add_unit_offset": True, "num_attention_heads": 32,
    "num_chunks": None, "num_key_value_heads": 32, "num_pred_heads": 8,
    "rms_norm_eps": 1e-5, "rope_scaling": None, "rope_theta": 100000,
    "tie_word_embeddings": False, "vocab_size": 320, "window_size": 2048}


def test_the_configuration_holds_every_published_key_and_says_its_cut():
    for key, value in PUBLISHED.items():
        assert CONFIG[key] == value, key
    assert CONFIG["reduced"] == ["num_hidden_layers"]
    assert CONFIG["num_hidden_layers"] == 8
    assert CONFIG["published"] == {"num_hidden_layers": 32}
    assert set(CONFIG["reduced_notes"]) == {"num_hidden_layers"}
    assert CONFIG["source"] == \
        "https://huggingface.co/EvaByte/EvaByte/blob/main/config.json"
    assert "four pipeline stages of 8 layers" in CONFIG["deployment"]
    assert CONFIG["engine"]["serving_engine"] == {
        "max_batch": 16, "max_prompt_len": 16384,
        "prefill_buckets": [2048, 4096, 8192, 16384]}
    assert CONFIG["engine"]["frontend"] == {"feed_depth": 1}
    for needle in ("ROTATED", "head-major", "0.1 x normal", "unit RMS"):
        assert any(needle in a for a in CONFIG["assumed"]), needle
    assert any("seven further prediction heads" in a
               for a in CONFIG["departures"])
    assert any("rounded" in a for a in CONFIG["departures"])
    # the rehearsal changes sizes, the CPU's dtype and the engine only
    assert set(CONFIG["rehearsal"]) <= EVA_REHEARSAL_KEYS
    r = CONFIG["rehearsal"]
    assert (r["window_size"], r["chunk_size"], r["hidden_size"],
            r["num_attention_heads"], r["num_hidden_layers"],
            r["max_position_embeddings"], r["num_pred_heads"]) \
        == (16, 4, 64, 4, 3, 256, 2)


def test_counts_from_shapes_are_the_issue_s_table():
    assert arch.attention_params(CONFIG) == 67_117_056
    assert arch.mlp_params(CONFIG) == 135_266_304
    assert arch.layer_params(CONFIG) == 202_391_552
    assert arch.param_count(CONFIG) - 8 * arch.layer_params(CONFIG) \
        == 320 * 4096 + 4096 * 2560 + 4096 == 11_800_576
    assert arch.param_count(CONFIG) == 1_630_932_992
    assert arch.row_bytes(CONFIG) == 16_384
    # a lane's state in a layer: 2,048 window rows and 32,768 / 16 summary
    # rows; 16 lanes x 8 layers of it
    lane = (2048 + 32768 // 16) * 16_384
    assert lane == 67_108_864 and 16 * 8 * lane == 8_589_934_592
    # a step with nothing live: every matmul weight of the layers (mu and
    # phi among them) and head 0's 320 columns, in bf16
    weights = 8 * (67_117_056 + 135_266_304) + 4096 * 320
    assert arch.decode_step_bytes(CONFIG, 0, 0) == 2 * weights
    # the issue's mean lane: 1,024 window rows and 544 summary rows live in
    # each of 16 lanes, through 8 layers: 3.29 GB
    live = arch.decode_step_bytes(CONFIG, 16 * 1024, 16 * 544) - 2 * weights
    assert live == 16 * (1024 + 544) * 8 * 16_384 == 3_288_334_336
    assert arch.decode_step_bytes(CONFIG, 1, 0) \
        - arch.decode_step_bytes(CONFIG, 0, 0) == 8 * 16_384
    for t, rows in [(0, (1, 0)), (2047, (2048, 0)), (2048, (1, 128)),
                    (8700, (509, 512)), (32767, (2048, 1920))]:
        assert arch.live_rows(CONFIG, t) == rows


def test_check_lengths_reach_what_the_first_driver_s_never_do():
    groups = arch.check_lengths(CONFIG)
    assert groups == [[5, 16, 28, 40], [2044, 2045, 2046, 2047, 2048],
                      [4107], [6013]]
    assert 4107 % 16 and 4107 > 2 * 2048    # two windows, a partial chunk
    flat = [n for g in groups for n in g]
    assert max(flat) + 5 <= CONFIG["engine"]["serving_engine"][
        "max_prompt_len"]
    toy = dict(CONFIG, **{k: v for k, v in CONFIG["rehearsal"].items()
                          if k not in ("model", "engine")})
    assert arch.check_lengths(toy) == [[5, 8, 11, 14], [12, 13, 14, 15, 16],
                                       [37], [44]]


def test_build_model_maps_the_published_keys():
    model = arch.build_model(CONFIG)
    cfg, block = model.cfg, model.cfg.block
    assert (cfg.d_model, cfg.num_heads, cfg.num_layers, cfg.d_ff) \
        == (4096, 32, 8, 11008)
    assert (cfg.vocab_size, cfg.max_seq_len, cfg.head_dim) \
        == (320, 32768, 128)
    assert cfg.rotary_base == 100000.0 and not cfg.tie_embeddings
    assert cfg.layer_norm_eps == 1e-5
    assert (block.window_size, block.chunk_size, block.num_pred_heads,
            block.heads_out, block.chunks_per_window) == (2048, 16, 8, 1, 128)
    assert model.lane_rows() == 4096 and model.prefill_takes_lengths


def _entry(group, name):
    return next(e for e in BENCH[group] if e["name"] == name)


def test_benchmark_json_holds_the_new_entries_and_still_validates():
    spec.validate(BENCH)
    entry = _entry("configs", "evabyte-6.5b-l8")
    assert entry["source"] == CONFIG["source"]
    assert entry["reduced"] == CONFIG["reduced"] == ["num_hidden_layers"]
    cell = _entry("workloads", "serve-longdoc")
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("evabyte-6.5b-l8", "longdoc-closed", 1)
    mine = {m["name"] for m in spec.metrics_of_cell(BENCH, "serve-longdoc",
                                                    "per_layer")}
    assert set(NEW_READERS) <= mine
    assert {"occupancy.batch", "kv_live_share.batch", "decode_step_ms.batch",
            "device_idle.batch", "ttft_ms.p50.batch", "device_starved.batch",
            "host_ms_per_chunk.batch", "queue_wait_ms.mean.batch",
            "lane_to_first_token_ms.mean.batch"} <= mine
    # a NeoX block's bytes, an expert layer's counters: not this cell's
    assert not {n for n in mine if n.endswith(".reason")}
    assert "decode_hbm_roofline.batch" not in mine
    for name in NEW_READERS:
        m = _entry("per_layer", name)
        assert m["workloads"] == ["serve-longdoc"]
        assert m["moves"] == "serve_tokens_per_s"
    assert {m["name"] for m in spec.metrics_of_cell(
        BENCH, "serve-longdoc", "end_to_end")} == {"serve_tokens_per_s",
                                                   "setup_s"}
    mix = spec.load_json(spec.find_mix(BENCH, "longdoc-closed"))
    assert mix["kind"] == "serve_closed_long" and mix["clients"] == 32
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 6144,
                                 "sigma": 0.7, "min": 1024, "max": 16384}
    assert mix["output_len"] == {"dist": "lognormal", "median": 2048,
                                 "sigma": 0.6, "min": 256, "max": 8192}
    assert mix["population"] % 16 == 0 and mix["warm_s"] == 10.0
    assert (mix["open_after_ended"], mix["trace_s"]) == (2, 4.0)
    # the longest request stays inside the positions
    assert 16384 + 8192 <= CONFIG["max_position_embeddings"]


NEOX_REHEARSAL_KEYS = {
    "hidden_size", "num_attention_heads", "num_hidden_layers",
    "intermediate_size", "vocab_size", "max_position_embeddings", "engine"}
# the latent block's sizes, the share's published count, and the CPU's dtype
LATENT_REHEARSAL_KEYS = NEOX_REHEARSAL_KEYS | {
    "kv_lora_rank", "q_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
    "v_head_dim", "moe_intermediate_size", "num_key_value_heads",
    "num_experts_per_tok", "n_routed_experts", "published", "model"}
# the window, the chunk, the prediction heads, and the CPU's dtype
EVA_REHEARSAL_KEYS = NEOX_REHEARSAL_KEYS | {
    "num_key_value_heads", "num_pred_heads", "window_size", "chunk_size",
    "max_seq_length", "model"}
PR24_FOUR = ["device_starved.batch", "host_ms_per_chunk.batch",
             "queue_wait_ms.mean.batch", "lane_to_first_token_ms.mean.batch"]
PR26_FOUR = ["decode_hbm_roofline.reason", "experts_touched.reason",
             "expert_load_max_over_mean.reason", "routed_here.reason"]


def test_every_assertion_of_the_red_tests_that_an_appended_entry_leaves():
    """Four tests of this directory ask that what THEIR PR appended be the
    last of its list, or that every configuration be of their architecture:
    ``test_chipbench_harness.py::test_configuration_files_hold_what_the_
    contract_asks`` and ``test_chipbench_serve_spans.py::test_the_four_
    entries_are_appended_for_serve_batch_alone`` (red since PR 26), and, since
    this PR's entries, ``test_arch_pangu_ultra_moe.py::test_benchmark_json_
    holds_the_new_entries_and_still_validates`` and ``::test_every_assertion_
    of_the_two_red_tests_that_an_appended_entry_leaves``. The PR that appends
    may edit no file here, so they stay red until a ``benchmark`` PR rewrites
    them (PERF.md, section 7). Every assertion of theirs is held here, with
    entries found BY NAME and an architecture's keys asked of its own
    configurations, so that nothing the repo checked goes unchecked and the
    next appended entry leaves this test green."""
    archs = {"pangu_ultra_moe": (pangu_ultra_moe, LATENT_REHEARSAL_KEYS),
             "evabyte": (arch, EVA_REHEARSAL_KEYS)}
    for c in BENCH["configs"]:
        held = spec.load_json(os.path.join(ROOT, c["file"]))
        assert held["source"] == c["source"]
        assert held["reduced"] == c["reduced"]
        assert set(held["reduced"]) == set(held["reduced_notes"])
        for key in ("assumed", "deployment", "model", "engine", "chips",
                    "rehearsal", "architecture"):
            assert key in held, (c["name"], key)
        if "arch" in held:          # its own mapping (archs/<arch>.py)
            module, rehearsal_keys = archs[held["arch"]]
            cfg = module.build_model(held).cfg
            assert cfg.d_model == held["hidden_size"]
            if held["arch"] == "pangu_ultra_moe":
                assert held["qk_nope_head_dim"] == held["v_head_dim"] == 128
            else:
                assert cfg.head_dim == 128
            # a rehearsal never changes a key that is not a size
            assert set(held["rehearsal"]) <= rehearsal_keys
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
    # PR 24's four in order, PR 26's four behind them, this PR's behind those
    at = names.index(PR24_FOUR[0])
    assert names[at:at + 8] == PR24_FOUR + PR26_FOUR
    assert names[at + 8:at + 11] == NEW_READERS
    four = BENCH["per_layer"][at:at + 4]
    layers = {m["layer"] for m in BENCH["per_layer"][:at]}
    for m in four:
        # serve-batch first, then each cell in the order it was appended
        assert m["workloads"][:3] == ["serve-batch", "serve-reason",
                                      "serve-longdoc"]
        assert m["source"] == "program_span" and m["better"] == "lower"
        assert m["moves"] == "serve_tokens_per_s"
    assert four[0]["layer"] in layers           # a layer already named
    assert {m["name"] for m in spec.metrics_of_cell(
        BENCH, "serve-batch", "per_layer")} >= set(PR24_FOUR)
    # PR 26's entries, by name (its own test asks that they be the last)
    assert [c["name"] for c in BENCH["configs"]].index(
        "pangu-ultra-moe-ep16-l5") == 3
    reason = _entry("workloads", "serve-reason")
    assert (reason["config"], reason["traffic"], reason["chips"]) \
        == ("pangu-ultra-moe-ep16-l5", "reason-closed", 1)
    theirs = {m["name"] for m in spec.metrics_of_cell(BENCH, "serve-reason",
                                                      "per_layer")}
    assert set(PR26_FOUR) <= theirs
    assert "decode_hbm_roofline.batch" not in theirs
    assert not set(NEW_READERS) & theirs
    for name in PR26_FOUR:
        m = _entry("per_layer", name)
        assert m["workloads"] == ["serve-reason"]
        assert m["moves"] == "serve_tokens_per_s"
    assert {m["name"] for m in spec.metrics_of_cell(
        BENCH, "serve-reason", "end_to_end")} == {"serve_tokens_per_s",
                                                  "setup_s"}


# ------------------------------------------------------------- the rehearsal
@pytest.fixture(scope="module")
def rehearsal():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1",
               PYTHONPATH=ROOT)
    env.pop("BENCH_RUN", None)
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", "serve-longdoc", "--seed", str(SEED), "--seconds", "2",
         "--trace", "1", "--rehearsal"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:] + r.stdout[-3000:]
    return r.stdout


def test_traced_rehearsal_is_correct_and_prints_the_new_counter(rehearsal):
    last = json.loads(rehearsal.strip().splitlines()[-1])
    assert last["correct"] is True, rehearsal[-3000:]
    assert last["attempted"] > 0 and last["failed"] == 0
    metrics = {n[:-len(SUFFIX_REHEARSAL)]: m["value"]
               for n, m in last["metrics"].items()}
    # a CPU trace has no device plane: the two device_trace metrics are silent
    assert "state_rows_read_over_live.longdoc" in metrics
    assert not {"decode_hbm_roofline.longdoc", "prefill_share.longdoc"} \
        & set(metrics)
    # both leaves of four lanes read whole: 80 rows a lane at the toy sizes,
    # over 1 to 16 + 48 live ones
    assert 1.0 < metrics["state_rows_read_over_live.longdoc"] < 80.0
    assert {"occupancy.batch", "kv_live_share.batch", "ttft_ms.p50.batch",
            "device_starved.batch", "host_ms_per_chunk.batch"} <= set(metrics)


def test_rehearsal_holds_the_server_to_the_reference_past_window_edges(
        rehearsal):
    for needle in ("parameters on the device, archs/evabyte.py",
                   "11 prompts of 5-14, 12-16, 37, 44 tokens, prefill + 4 "
                   "decode steps through the cache vs the float32 reference",
                   "11 reference prompts through the real server",
                   "programs built inside the measured window"):
        lines = [ln for ln in rehearsal.splitlines() if needle in ln]
        assert lines and all("] ok: " in ln for ln in lines), needle
    opened = [ln for ln in rehearsal.splitlines()
              if "the window opens on delivery" in ln]
    assert len(opened) == 1
    assert int(opened[0].split(",")[-1].split()[0]) >= 2


@pytest.mark.parametrize("control, passes", [("float8_e4m3fn", False),
                                             ("bfloat16", True)])
def test_the_control_goes_through_the_new_driver_s_own_comparison(control,
                                                                  passes):
    """The reference with float8 operands in the program's place, over the
    arch file's lengths, has to come out as not correct by the cell's own
    limit; with the configuration's own precision it passes (at toy widths
    here; PERF.md has the reading at the published widths, from the same
    command on the chip)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1",
               PYTHONPATH=ROOT)
    r = subprocess.run(
        [sys.executable,
         os.path.join(ROOT, "chipbench", "drivers", "serve_closed_long.py"),
         "--workload", "serve-longdoc", "--seed", str(SEED), str(SEED + 1),
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
    assert "by group" in r.stdout


def test_the_new_driver_leaves_the_first_one_as_it_was():
    from chipbench.drivers import serve_closed_arch as base
    from chipbench.drivers import serve_closed_long as drv
    assert base.ArchServer is not drv.LongServer
    assert issubclass(drv.LongServer, base.ArchServer)
    seen = []
    assert drv._as_arch_server(lambda: seen.append(base.ArchServer) or 7) == 7
    assert seen == [drv.LongServer] and base.ArchServer is not drv.LongServer


# ------------------------------------------------------------ the new readers
def _reader(name):
    return spec.load_module(spec.find_reader(BENCH, name))


def _trace(step_ms, chunks=3, k=8, prefill_s=0.0, busy_s=0.9):
    modules = {"jit_decode_chunk_fn": chunks * k * step_ms / 1e3}
    counts = {"jit_decode_chunk_fn": chunks}
    if prefill_s:
        modules["jit_prefill"], counts["jit_prefill"] = prefill_s, 2
    return trace_reduce.TraceSummary(
        window_s=1.0, n_devices=1, busy_s=busy_s, op_seconds={},
        op_counts={}, module_seconds=modules, module_counts=counts,
        collective_s=0.0, idle_gaps=[])


CELL = {"config": CONFIG}
PEAKS = {"hbm_bytes_per_s": 819e9}


def _counters(win_per_step, old_per_step, chunks=3.0, k=8):
    steps = chunks * k
    state = {"chunks": chunks, "eva_window_rows_live": win_per_step * steps,
             "eva_summary_rows_live": old_per_step * steps,
             "eva_rows_read": 16 * 4096 * steps, "eva_windows_closed": 1.0}
    return {"decode_chunk": k, "max_batch": 16, "peaks": PEAKS,
            "traced": dict(state), "window": dict(state)}


def test_roofline_share_counts_live_rows_and_stays_under_100():
    read = _reader("decode_hbm_roofline.longdoc").read
    # a step that takes exactly what EVERY row of both leaves costs at the
    # peak reads 100 % with every row live, and under it with fewer
    whole = arch.decode_step_bytes(CONFIG, 16 * 2048, 16 * 2048) / 819e9 * 1e3
    full = _counters(16 * 2048, 16 * 2048)
    assert read(_trace(whole), {}, full, CELL) == pytest.approx(100.0)
    mean = read(_trace(whole), {}, _counters(16 * 1024, 16 * 544), CELL)
    weights = 2 * (8 * (67_117_056 + 135_266_304) + 4096 * 320)
    assert mean == pytest.approx(100 * (weights + 3_288_334_336)
                                 / (weights + 8_589_934_592), rel=1e-9)
    assert read(_trace(2 * whole), {}, full, CELL) == pytest.approx(50.0)
    # a program without the counters (the parent), no trace, no decode
    # chunk in the stretch: nothing to read, and no error
    bare = {"decode_chunk": 8, "peaks": PEAKS,
            "traced": {"chunks": 3.0}, "window": {}}
    assert read(_trace(whole), {}, bare, CELL) is None
    assert read(None, {}, full, CELL) is None
    assert read(_trace(whole, chunks=0), {}, full, CELL) is None
    assert read(_trace(whole), {}, dict(full, peaks=None), CELL) is None


def test_counter_and_trace_readers_on_synthetic_input():
    ratio = _reader("state_rows_read_over_live.longdoc").read
    assert ratio(None, {}, _counters(16 * 1024, 16 * 544), CELL) \
        == pytest.approx(4096 / (1024 + 544))
    assert ratio(None, {}, _counters(16 * 2048, 16 * 2048), CELL) \
        == pytest.approx(1.0)
    assert ratio(None, {}, {"window": {"tokens_out": 5}}, CELL) is None
    share = _reader("prefill_share.longdoc").read
    assert share(_trace(10.0, prefill_s=0.18), {}, {}, CELL) \
        == pytest.approx(20.0)
    assert share(_trace(10.0), {}, {}, CELL) is None
    assert share(None, {}, {}, CELL) is None
