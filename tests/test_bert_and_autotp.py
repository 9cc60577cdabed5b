"""BERT family + auto-TP injection + fused decode tests (BASELINE config
#5: BERT-large TP int8 inference; reference replace_policy.py:50 HFBert,
replace_module.py:502 policy-free TP, softmax_context decode kernel)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.bert import (BertConfig, BertForMaskedLM,
                                       BertModel, bert_large)


def _tiny_hf_bert(seed=0):
    import torch
    from transformers import BertConfig as HFBertConfig
    from transformers import BertModel as HFBertModel
    hf_cfg = HFBertConfig(
        vocab_size=128, hidden_size=64, num_hidden_layers=3,
        num_attention_heads=4, intermediate_size=128,
        max_position_embeddings=64, type_vocab_size=2,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0)
    torch.manual_seed(seed)
    return HFBertModel(hf_cfg).eval(), hf_cfg


def _convert(hf, hf_cfg):
    from deepspeed_tpu.module_inject.policies import HFBertPolicy
    cfg = HFBertPolicy.config_from_hf(hf_cfg)
    params = HFBertPolicy.convert(dict(hf.state_dict()), cfg.num_layers)
    return cfg, params


def _hf_outputs(hf, ids, mask, tt):
    import torch
    with torch.no_grad():
        out = hf(input_ids=torch.tensor(ids.astype(np.int64)),
                 attention_mask=torch.tensor(mask.astype(np.int64)),
                 token_type_ids=torch.tensor(tt.astype(np.int64)))
    return out.last_hidden_state.numpy(), out.pooler_output.numpy()


def _inputs(seed=0, b=2, s=16, vocab=128):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, vocab, (b, s)).astype(np.int32)
    mask = np.ones((b, s), np.int32)
    mask[-1, s - 6:] = 0
    tt = np.zeros((b, s), np.int32)
    tt[:, s // 2:] = 1
    return ids, mask, tt


def test_bert_logit_parity_vs_hf():
    hf, hf_cfg = _tiny_hf_bert()
    cfg, params = _convert(hf, hf_cfg)
    ids, mask, tt = _inputs()
    seq, pooled = BertModel(cfg).apply(
        {"params": jax.tree.map(jnp.asarray, params)},
        jnp.asarray(ids), jnp.asarray(tt), jnp.asarray(mask))
    ref_seq, ref_pool = _hf_outputs(hf, ids, mask, tt)
    live = mask.astype(bool)
    assert np.abs(np.asarray(seq) - ref_seq)[live].max() < 2e-5
    assert np.abs(np.asarray(pooled) - ref_pool).max() < 2e-5


def test_bert_tp8_int8_inference():
    """BASELINE config #5: BERT TP=8 with int8 weights — logits must match
    the fp32 single-device reference within int8 tolerance."""
    import deepspeed_tpu as ds
    hf, hf_cfg = _tiny_hf_bert()
    cfg, params = _convert(hf, hf_cfg)
    ids, mask, tt = _inputs()
    model = BertModel(cfg)

    engine = ds.init_inference(model, mp_size=8, dtype=jnp.float32,
                               model_parameters=params, quantize_bits=8)
    seq, pooled = engine.forward(jnp.asarray(ids), token_type_ids=jnp.asarray(tt),
                                 attention_mask=jnp.asarray(mask))
    ref_seq, ref_pool = _hf_outputs(hf, ids, mask, tt)
    live = mask.astype(bool)
    err = np.abs(np.asarray(seq) - ref_seq)[live].max()
    assert err < 0.1, err         # int8 grouped quantization tolerance
    # int8 tree is TP-sharded at rest: the column-split qkv kernel's q8
    # leaf ([out, L, in] after the moveaxis) splits its out dim 8 ways
    qkv_q8 = engine.params["blocks"]["attn"]["qkv"]["kernel"]["q8"]
    assert qkv_q8.dtype == jnp.int8
    assert max(sh.data.size for sh in qkv_q8.addressable_shards) == \
        qkv_q8.size // 8


def test_bert_large_config():
    cfg = bert_large()
    assert cfg.num_layers == 24 and cfg.d_model == 1024
    assert cfg.head_dim == 64


def test_bert_mlm_head_runs():
    cfg = BertConfig(vocab_size=64, num_layers=2, num_heads=2, d_model=32,
                     d_ff=64, max_seq_len=32, hidden_dropout=0.0)
    model = BertForMaskedLM(cfg)
    ids = jnp.zeros((2, 8), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids)["params"]
    logits = model.apply({"params": params}, ids)
    assert logits.shape == (2, 8, 64)


# ---------------------------------------------------------------- auto-TP

def test_auto_tp_classification():
    from deepspeed_tpu.module_inject.auto_tp import classify
    assert classify("['blocks']['attn']['qkv']['kernel']", (4, 64, 192)) == "column"
    assert classify("['blocks']['attn']['out_proj']['kernel']", (4, 64, 64)) == "row"
    assert classify("['wte']['embedding']", (1000, 64)) == "embed"
    # shape heuristics for unknown names
    assert classify("['x']['mystery_a']['kernel']", (64, 256)) == "column"
    assert classify("['x']['mystery_b']['kernel']", (256, 64)) == "row"
    # unknown square kernels stay replicated (safe default)
    assert classify("['x']['mystery_c']['kernel']", (64, 64)) is None


def test_auto_tp_specs_on_generic_model():
    """A policy-free flax model gets consistent TP specs and produces the
    same outputs under mp=8 as replicated execution."""
    import flax.linen as nn
    from jax.sharding import NamedSharding, PartitionSpec as P
    from deepspeed_tpu.module_inject.auto_tp import infer_tp_specs
    from deepspeed_tpu.parallel import mesh as mesh_lib

    class Mystery(nn.Module):
        @nn.compact
        def __call__(self, x):
            h = nn.Dense(256, name="expand")(x)      # 64 -> 256: column
            h = nn.relu(h)
            return nn.Dense(64, name="contract")(h)  # 256 -> 64: row

    model = Mystery()
    x = jnp.asarray(np.random.default_rng(0).normal(size=(4, 64)),
                    jnp.float32)
    params = model.init(jax.random.PRNGKey(0), x)["params"]
    specs = infer_tp_specs(params)
    assert specs["expand"]["kernel"] == P(None, "tp")
    assert specs["expand"]["bias"] == P("tp")
    assert specs["contract"]["kernel"] == P("tp", None)
    assert specs["contract"]["bias"] == P(None)   # replicated

    ref = model.apply({"params": params}, x)
    mesh = mesh_lib.build_mesh(mesh_lib.MeshShape.infer(8, tp=8))
    sharded = jax.device_put(params, jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda s: isinstance(s, P)))
    out = jax.jit(lambda p, x: model.apply({"params": p}, x))(sharded, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_init_inference_replace_method_auto():
    import flax.linen as nn
    import deepspeed_tpu as ds

    class Mystery(nn.Module):
        @nn.compact
        def __call__(self, x):
            h = nn.Dense(256, name="expand")(x)
            return nn.Dense(64, name="contract")(nn.relu(h))

    model = Mystery()
    x = jnp.asarray(np.random.default_rng(0).normal(size=(4, 64)),
                    jnp.float32)
    params = model.init(jax.random.PRNGKey(0), x)["params"]
    ref = model.apply({"params": params}, x)
    engine = ds.init_inference(model, mp_size=8, dtype=jnp.float32,
                              model_parameters=params,
                              replace_method="auto")
    out = engine.forward(x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


# ---------------------------------------------------------------- decode

def test_fused_decode_matches_masked_einsum():
    from deepspeed_tpu.ops.pallas.decode_attention import (
        decode_attention, masked_cache_attention)
    rng = np.random.default_rng(0)
    b, S, h, d = 2, 512, 12, 64     # h=12 exercises head padding
    q = jnp.asarray(rng.normal(size=(b, 1, h, d)), jnp.float32)
    ck = jnp.asarray(rng.normal(size=(b, S, h, d)), jnp.float32)
    cv = jnp.asarray(rng.normal(size=(b, S, h, d)), jnp.float32)
    for clen in (1, 7, 128, 300, 512):
        got = decode_attention(q, ck, cv, jnp.int32(clen))
        want = masked_cache_attention(q, ck, cv, jnp.int32(clen - 1),
                                      1.0 / np.sqrt(d))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, err_msg=f"clen={clen}")


def test_generate_with_fused_decode():
    """End-to-end generation through the pallas decode path matches the xla
    decode path token-for-token (greedy)."""
    import deepspeed_tpu as ds
    from deepspeed_tpu.models.gpt import GPT, GPTConfig

    ids = jnp.asarray(np.random.default_rng(0).integers(0, 100, (2, 8)),
                      jnp.int32)
    outs = {}
    for impl in ("xla", "pallas"):
        # h*d = 128: a lane tile, so the kernel's gate accepts it (at the
        # old d_model=64 "pallas" quietly ran the einsum; now it raises)
        cfg = GPTConfig(vocab_size=100, max_seq_len=128, num_layers=2,
                        num_heads=4, d_model=128, d_ff=128,
                        dtype=jnp.float32, param_dtype=jnp.float32,
                        attention_impl="xla", decode_impl=impl)
        model = GPT(cfg)
        params = model.init(jax.random.PRNGKey(0), ids)["params"]
        engine = ds.init_inference(model, mp_size=1, dtype=jnp.float32,
                                   model_parameters=params)
        outs[impl] = np.asarray(engine.generate(
            ids, max_new_tokens=6, temperature=0.0))
    np.testing.assert_array_equal(outs["xla"], outs["pallas"])


def test_export_roundtrip_bert():
    """convert -> export reproduces the HF state dict exactly (the
    revert_transformer_layer analogue)."""
    import torch
    from deepspeed_tpu.module_inject.policies import export_hf_state_dict
    hf, hf_cfg = _tiny_hf_bert()
    cfg, params = _convert(hf, hf_cfg)
    back = export_hf_state_dict("bert", params)
    sd = {k: v.detach().numpy() for k, v in hf.state_dict().items()}
    for k in sd:
        np.testing.assert_array_equal(back[k], sd[k], err_msg=k)


def test_export_roundtrip_gpt2():
    import torch
    from transformers import GPT2Config, GPT2LMHeadModel
    from deepspeed_tpu.module_inject.policies import (HFGPT2Policy,
                                                      export_hf_state_dict)
    torch.manual_seed(0)
    hf = GPT2LMHeadModel(GPT2Config(
        vocab_size=128, n_positions=64, n_embd=64, n_layer=2, n_head=4,
        resid_pdrop=0.0, embd_pdrop=0.0, attn_pdrop=0.0)).eval()
    params = HFGPT2Policy.convert(dict(hf.state_dict()), 2)
    back = export_hf_state_dict("gpt2", params)
    sd = {k: v.detach().numpy() for k, v in hf.state_dict().items()
          if "attn.bias" not in k and "masked_bias" not in k}
    for k in sd:
        np.testing.assert_array_equal(back[k], sd[k], err_msg=k)


# ------------------------------------------------------------- DistilBERT

def test_distilbert_logit_parity_vs_hf():
    """DistilBERT injection policy (reference HFDistilBertLayerPolicy —
    the last per-architecture policy missing from the table): exact
    hidden-state parity vs the HF torch model."""
    import torch
    from transformers import DistilBertConfig as HFDBConfig
    from transformers import DistilBertModel as HFDBModel
    from deepspeed_tpu.module_inject.policies import HFDistilBertPolicy

    hf_cfg = HFDBConfig(vocab_size=128, dim=64, n_layers=3, n_heads=4,
                        hidden_dim=128, max_position_embeddings=64,
                        dropout=0.0, attention_dropout=0.0,
                        sinusoidal_pos_embds=False)
    torch.manual_seed(0)
    hf = HFDBModel(hf_cfg).eval()
    cfg = HFDistilBertPolicy.config_from_hf(hf_cfg)
    assert cfg.type_vocab_size == 0 and not cfg.use_pooler
    params = HFDistilBertPolicy.convert(dict(hf.state_dict()),
                                        cfg.num_layers)
    ids, mask, _ = _inputs()
    with torch.no_grad():
        ref = hf(input_ids=torch.tensor(ids.astype(np.int64)),
                 attention_mask=torch.tensor(mask.astype(np.int64)))
    seq, cls = BertModel(cfg).apply(
        {"params": jax.tree.map(jnp.asarray, params)},
        jnp.asarray(ids), None, jnp.asarray(mask))
    live = mask.astype(bool)
    err = np.abs(np.asarray(seq) - ref.last_hidden_state.numpy())[live].max()
    assert err < 2e-5, err
    np.testing.assert_allclose(np.asarray(cls),
                               np.asarray(seq)[:, 0], atol=0)


def test_distilbert_policy_registered():
    from deepspeed_tpu.module_inject.policies import (HFDistilBertPolicy,
                                                      policy_for)
    assert policy_for("distilbert") is HFDistilBertPolicy
