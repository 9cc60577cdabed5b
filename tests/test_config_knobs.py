"""Every parsed config knob must change the compiled program or error
loudly — never silently no-op (reference: zero/config.py stage-3 working-set
knobs consumed by partitioned_param_coordinator.py:240-356; activation
checkpointing knobs consumed by checkpointing.py:122,493)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.parallel import mesh as mesh_lib
from deepspeed_tpu.runtime.sharding import ShardingRules


def _mesh(**axes):
    shape = mesh_lib.MeshShape.infer(8, **axes)
    mesh = mesh_lib.build_mesh(shape)
    mesh_lib.set_global_mesh(mesh, shape)
    return mesh


def _tiny(seed=0, **cfg_kw):
    from deepspeed_tpu.models.gpt import GPT, GPTConfig, lm_loss_fn
    cfg = GPTConfig(vocab_size=64, max_seq_len=16, num_layers=2, num_heads=2,
                    d_model=32, d_ff=64, dtype=jnp.float32,
                    param_dtype=jnp.float32, **cfg_kw)
    model = GPT(cfg)
    ids = np.random.default_rng(seed).integers(0, 64, (4, 16)).astype(np.int32)
    params = model.init(jax.random.PRNGKey(0), ids[:1])["params"]
    return model, params, ids, lm_loss_fn


# --------------------------------------------------- param persistence
def test_param_persistence_threshold_keeps_small_leaves_replicated():
    mesh = _mesh(dp=8)
    rules = ShardingRules(mesh, zero_stage=3, param_persistence_threshold=1000)
    # leaves of the scanned stack as the model has them: [L, ...]
    bias = rules.param_spec("blocks/attn/qkv/bias", (2, 96))
    kernel = rules.param_spec("blocks/mlp/up_proj/kernel", (2, 256, 1024))
    assert all(a != "dp" for a in bias), \
        f"sub-threshold leaf should persist (stay replicated), got {bias}"
    assert "dp" in tuple(kernel), \
        f"above-threshold leaf should shard over dp, got {kernel}"
    # master/opt state shards over dp regardless of persistence
    mbias = rules.master_spec("blocks/attn/qkv/bias", (2, 96))
    assert "dp" in tuple(mbias)


def test_param_persistence_threshold_zero_shards_everything():
    mesh = _mesh(dp=8)
    rules = ShardingRules(mesh, zero_stage=3, param_persistence_threshold=0)
    bias = rules.param_spec("blocks/attn/qkv/bias", (2, 96))
    assert "dp" in tuple(bias)


def test_stage3_prefixed_aliases_accepted():
    from deepspeed_tpu.runtime.config import DeepSpeedConfig
    cfg = DeepSpeedConfig({
        "train_batch_size": 8,
        "zero_optimization": {
            "stage": 3,
            "stage3_param_persistence_threshold": 12345,
            "stage3_prefetch_bucket_size": 777,
            "stage3_max_live_parameters": 10 ** 9,
        },
    }, dp_world_size=8)
    assert cfg.zero_config.param_persistence_threshold == 12345
    assert cfg.zero_config.prefetch_bucket_size == 777
    assert cfg.zero_config.max_live_parameters == 10 ** 9


# --------------------------------------------------- max_live_parameters
def _engine_cfg(zero_extra=None, ac=None):
    cfg = {
        "train_batch_size": 32,
        "train_micro_batch_size_per_gpu": 4,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 3, **(zero_extra or {})},
    }
    if ac is not None:
        cfg["activation_checkpointing"] = ac
    return cfg


def test_max_live_parameters_below_floor_rejected():
    model, params, ids, loss_fn = _tiny()
    with pytest.raises(ValueError, match="working-set floor"):
        ds.initialize(model=model, model_parameters=params,
                      config=_engine_cfg({"max_live_parameters": 10}),
                      loss_fn=loss_fn)


def test_max_live_parameters_satisfiable_accepted():
    model, params, ids, loss_fn = _tiny()
    eng, _, _, _ = ds.initialize(
        model=model, model_parameters=params,
        config=_engine_cfg({"stage3_max_live_parameters": 10 ** 9}),
        loss_fn=loss_fn)
    assert eng.zero_stage == 3


# --------------------------------------------------- activation ckpt knobs
def test_unhonorable_activation_knobs_rejected():
    model, params, ids, loss_fn = _tiny()
    with pytest.raises(ValueError, match="contiguous_memory_optimization"):
        ds.initialize(model=model, model_parameters=params,
                      config=_engine_cfg(
                          ac={"contiguous_memory_optimization": True}),
                      loss_fn=loss_fn)
    with pytest.raises(ValueError, match="synchronize_checkpoint_boundary"):
        ds.initialize(model=model, model_parameters=params,
                      config=_engine_cfg(
                          ac={"synchronize_checkpoint_boundary": True}),
                      loss_fn=loss_fn)


def test_partition_activations_wires_into_model():
    model, params, ids, loss_fn = _tiny()
    eng, _, _, _ = ds.initialize(
        model=model, model_parameters=params,
        config=_engine_cfg(ac={"partition_activations": True}),
        loss_fn=loss_fn)
    assert eng.module.cfg.partition_activations is True


def test_partition_activations_grad_parity():
    """Sequence-partitioned saved activations change layout, not math."""
    _mesh(tp=2, dp=4)
    model0, params, ids, loss_fn = _tiny(remat=True)
    model1, _, _, _ = _tiny(remat=True, partition_activations=True)
    batch = {"input_ids": jnp.asarray(ids)}

    def grad_of(m):
        def loss(p, b):
            return loss_fn(m.apply({"params": p}, b["input_ids"],
                                   deterministic=True), b)
        return jax.jit(jax.grad(loss))(params, batch)

    g0, g1 = grad_of(model0), grad_of(model1)
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=1e-5)


def test_partition_activations_changes_compiled_sharding():
    """The knob must be visible in the lowered program: the residual stream
    carries a sharding constraint over tp on its sequence dim."""
    _mesh(tp=2, dp=4)
    model1, params, ids, loss_fn = _tiny(remat=True,
                                         partition_activations=True)
    batch = {"input_ids": jnp.asarray(ids)}

    def loss(p, b):
        return loss_fn(model1.apply({"params": p}, b["input_ids"],
                                    deterministic=True), b)

    txt = jax.jit(jax.grad(loss)).lower(params, batch).as_text()
    # residual stream [B, S, D] constrained [{dp}, {tp}, {}] (shardy) at the
    # block boundary — the saved activation is stored sequence-sharded
    assert 'sharding_constraint' in txt
    assert '[{"dp"}, {"tp"}, {}]> : tensor<4x16x32xf32>' in txt


def test_cpu_checkpointing_grad_parity():
    """Host-offloaded remat residuals: same grads, device saves nothing."""
    _mesh(dp=8)
    model0, params, ids, loss_fn = _tiny(remat=True)
    model1, _, _, _ = _tiny(remat=True, cpu_checkpointing=True)
    batch = {"input_ids": jnp.asarray(ids)}

    def grad_of(m):
        def loss(p, b):
            return loss_fn(m.apply({"params": p}, b["input_ids"],
                                   deterministic=True), b)
        return jax.jit(jax.grad(loss))(params, batch)

    g0, g1 = grad_of(model0), grad_of(model1)
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)


def test_cpu_checkpointing_requires_remat():
    from deepspeed_tpu.models.gpt import GPTConfig
    with pytest.raises(ValueError, match="remat"):
        GPTConfig(cpu_checkpointing=True, remat=False)


def test_cpu_checkpointing_engine_multichip_trains():
    """Rounds 1-4 hard-rejected cpu_checkpointing on mesh.size > 1 (the
    SPMD partitioner RET_CHECKed the host-offload placement annotations
    under explicit out_shardings). The engine now constrains state
    shardings in-program instead (engine._jit_state_step), so the SAME
    config that used to raise must train; the deeper multi-mesh +
    memory-savings evidence lives in
    tests/test_engine.py::test_cpu_checkpointing_multichip."""
    model, params, ids, loss_fn = _tiny(remat=True)
    engine, *_ = ds.initialize(
        model=model, model_parameters=params,
        config=_engine_cfg(ac={"cpu_checkpointing": True}),
        loss_fn=loss_fn)
    assert engine._ckpt_offload
    loss = engine.train_batch(iter([{"input_ids": ids}]
                                   * engine.gradient_accumulation_steps()))
    assert np.isfinite(float(jax.device_get(loss)))


# --------------------------------------------------- prefetch_bucket_size
def test_prefetch_bucket_size_widens_nvme_window(tmp_path):
    from deepspeed_tpu.runtime.zero.offload import HostOffloadOptimizer
    tree = {"a": np.ones((64, 8), np.float32),
            "b": np.full((256,), 2.0, np.float32),
            "c": np.full((128,), 3.0, np.float32)}
    grads = [np.full(512, 0.5, np.float32), np.ones(256, np.float32),
             np.ones(128, np.float32)]

    deep = HostOffloadOptimizer(tree, lr=0.1, mirror_dtype="float32",
                                nvme_path=str(tmp_path / "deep"),
                                prefetch_numel=2048)
    assert deep.swapper.num_slots > 3, \
        "prefetch_bucket_size should widen the staging window"

    shallow = HostOffloadOptimizer(tree, lr=0.1, mirror_dtype="float32",
                                   nvme_path=str(tmp_path / "shallow"),
                                   prefetch_numel=0)
    from deepspeed_tpu.runtime.zero.offload import NVMeLeafSwapper
    assert shallow.swapper.num_slots == NVMeLeafSwapper.slot_count(1)

    for _ in range(3):
        deep.step([g.copy() for g in grads], lr=0.1)
        shallow.step([g.copy() for g in grads], lr=0.1)
    a, b = deep.master_tree(), shallow.master_tree()
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(x, y)


def test_communication_data_type_changes_program_and_validates():
    """communication_data_type must change the compiled program (the dp
    grad reduction runs narrow) and reject unknown names — never silently
    no-op (reference engine.py allreduce dtype override)."""
    import deepspeed_tpu as ds
    from simple_model import SimpleModel, mse_loss, random_batch

    model = SimpleModel(hidden_dim=16)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((2, 16)))["params"]

    def eng(cdt):
        cfg = {"train_micro_batch_size_per_gpu": 8,
               "gradient_accumulation_steps": 1,
               "zero_optimization": {"stage": 2},
               "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
               "steps_per_print": 10000}
        if cdt:
            cfg["communication_data_type"] = cdt
        e, *_ = ds.initialize(model=model, model_parameters=params,
                              loss_fn=mse_loss, config=cfg)
        return e

    base = eng(None)
    narrow = eng("bf16")
    lb = float(jax.device_get(base.train_batch(iter([random_batch(8)]))))
    ln = float(jax.device_get(narrow.train_batch(iter([random_batch(8)]))))
    assert np.isfinite(lb) and np.isfinite(ln)
    # the narrow reduction quantizes grads: trajectories must NOT be
    # bit-identical after a few steps (the knob provably does something)
    for s in range(3):
        lb = float(jax.device_get(base.train_batch(iter([random_batch(8, seed=s)]))))
        ln = float(jax.device_get(narrow.train_batch(iter([random_batch(8, seed=s)]))))
    assert lb != ln, "communication_data_type had no effect"
    assert abs(lb - ln) < 0.05, (lb, ln)   # but it's a small perturbation

    with pytest.raises(ValueError, match="communication_data_type"):
        e = eng("int7")
        e.train_batch(iter([random_batch(8)]))


def test_amp_rejected_and_untested_optimizer_gated():
    """amp (Apex) has no TPU analogue -> reject; a client optax optimizer
    under ZeRO needs the explicit zero_allow_untested_optimizer opt-in
    (reference _do_sanity_check)."""
    import optax
    import deepspeed_tpu as ds
    from simple_model import SimpleModel, mse_loss

    model = SimpleModel(hidden_dim=16)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((2, 16)))["params"]
    base = {"train_micro_batch_size_per_gpu": 8,
            "gradient_accumulation_steps": 1,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
            "steps_per_print": 10000}

    with pytest.raises(ValueError, match="amp"):
        ds.initialize(model=model, model_parameters=params, loss_fn=mse_loss,
                      config=dict(base, amp={"enabled": True}))

    with pytest.raises(ValueError, match="untested"):
        ds.initialize(model=model, model_parameters=params, loss_fn=mse_loss,
                      config=dict(base, zero_optimization={"stage": 1}),
                      optimizer=optax.sgd(1e-2))

    # the opt-in accepts it and it trains
    e, *_ = ds.initialize(
        model=model, model_parameters=params, loss_fn=mse_loss,
        config=dict(base, zero_optimization={"stage": 1},
                    zero_allow_untested_optimizer=True),
        optimizer=optax.sgd(1e-2))
    from simple_model import random_batch
    loss = float(jax.device_get(e.train_batch(iter([random_batch(8)]))))
    assert np.isfinite(loss)


def test_stochastic_rounding_rejects_onebit():
    """bf16.stochastic_rounding cannot apply on the 1-bit path (the
    OnebitRunner casts master->compute inside its fused step) — the knob
    must reject loudly, not silently round-to-nearest."""
    model, params, ids, loss_fn = _tiny()
    cfg = _engine_cfg()
    cfg["bf16"] = {"enabled": True, "stochastic_rounding": True}
    cfg["optimizer"] = {"type": "OneBitAdam",
                       "params": {"lr": 1e-3, "freeze_step": 2}}
    with pytest.raises(NotImplementedError, match="1-bit"):
        ds.initialize(model=model, model_parameters=params, config=cfg,
                      loss_fn=loss_fn)
