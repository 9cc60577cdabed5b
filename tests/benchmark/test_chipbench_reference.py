"""The plain reference's training side: per-token negative log-likelihoods
and the layer-at-a-time gradient norm, and that single tokens catch what a
mean over random targets lets through."""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from chipbench import reference, spec  # noqa: E402

TRAIN = spec.load_module(os.path.join(ROOT, "chipbench", "drivers",
                                      "train.py"))


def _toy(tied=False, layers=2):
    cfg = spec.load_json(os.path.join(ROOT, "chipbench", "configs",
                                      "pythia-1.4b-cut.json"))
    cfg = spec._deep_update(cfg, cfg["rehearsal"])
    cfg.update(tie_word_embeddings=tied, num_hidden_layers=layers)
    from deepspeed_tpu.models.gpt import GPT, GPTConfig
    model = GPT(GPTConfig(**spec.gpt_config_kwargs(cfg)))
    params = jax.jit(lambda k: model.init(
        k, np.zeros((1, 8), np.int32))["params"])(jax.random.PRNGKey(3))
    ids = np.random.default_rng(0).integers(
        0, cfg["vocab_size"], (2, 64)).astype(np.int32)
    ids[0, 5] = ids[0, 9]       # a token read twice: its row gathers both
    return cfg, params, ids


@pytest.mark.parametrize("tied", [False, True])
def test_layer_at_a_time_gradient_norm_is_jax_grads(tied):
    cfg, params, ids = _toy(tied)
    loss, grads = jax.value_and_grad(
        lambda p: reference.reference_lm_loss(cfg, p, ids))(params)
    want = float(jnp.sqrt(sum(jnp.sum(jnp.square(g))
                              for g in jax.tree.leaves(grads))))
    nll, got = reference.reference_nll_and_grad_norm(cfg, params, ids)
    assert nll.shape == (2, 63)
    assert abs(float(jnp.mean(nll)) - float(loss)) < 1e-5
    assert abs(float(got) - want) < 1e-4 * want
    # pulled onto one device a layer at a time: the same numbers
    again = reference.reference_token_nll(cfg, params, ids,
                                          device=jax.devices()[0])
    assert float(jnp.max(jnp.abs(again - nll))) < 1e-5


def test_single_tokens_catch_what_a_mean_over_random_targets_passes():
    """Targets drawn independently of the model: the mean NLL of a model
    with a layer missing stays within a few hundredths of the whole model's
    (it is ln V + var/2 for any logits of that spread; 0.04 here, and the
    standard error of a mean over 1,016 tokens is 0.03), while single
    tokens differ by many times their tolerance."""
    cfg, params, _ = _toy(layers=3)
    ids = np.random.default_rng(1).integers(
        0, cfg["vocab_size"], (8, 128)).astype(np.int32)
    whole = np.asarray(reference.reference_token_nll(cfg, params, ids))
    less = dict(cfg, num_hidden_layers=2)
    short = np.asarray(reference.reference_token_nll(cfg | less, params, ids))
    assert abs(whole.mean() - short.mean()) < 0.05
    assert np.max(np.abs(whole - short)) > 20 * TRAIN.NLL_ATOL
    # and most single probes see it, so eight of them together always do
    assert np.mean(np.abs(whole - short) > TRAIN.NLL_ATOL) > 0.8
