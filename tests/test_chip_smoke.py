"""chip_smoke.py and the rules it stands on (PR 21 bring-up), on the CPU:

* the ``--rehearsal`` mode runs every leg end to end at toy size;
* a real-size run without a chip — and a run in a directory that holds
  nothing of the repo but the script — exits non-zero and prints no result;
* the compile cache is placed from outside (env var respected, otherwise
  the fixed path under the checkout);
* ``interpret_mode()`` refuses a backend that is neither tpu nor cpu, and a
  kernel asked for by name outside its gate raises;
* every kernel in ops/pallas/ LOWERS for the TPU at GPT-2 125M shapes
  (cross-platform lowering: the block-shape rules jax checks before Mosaic
  ever sees the kernel — what refused the first sampling kernel), and the
  flash kernel sits under shard_map on a multi-device mesh (what stopped
  the trainer on four chips);
* the FLOP count MFU divides by counts each matmul weight once;
* no bench file ends a timed window in ``device_get``;
* an orchestrating parent stays off the chip: logging initialises no
  backend, and the autotuner's process isolation probes in a child.
"""

import glob
import json
import os
import re
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(args, cwd=REPO, **env):
    full = dict(os.environ, JAX_PLATFORMS="cpu", **env)
    return subprocess.run([sys.executable] + args, cwd=cwd, env=full,
                          capture_output=True, text=True, timeout=600)


def _result_lines(stdout):
    out = []
    for line in stdout.splitlines():
        if line.startswith("{"):
            try:
                out.append(json.loads(line))
            except ValueError:
                pass
    return out


def test_rehearsal_runs_every_leg(tmp_path):
    """Four virtual devices, so the dp=4 ZeRO-1 and dp=2 x tp=2 ZeRO-3
    branches the four-chip run takes are rehearsed too; the cache goes
    where the environment says."""
    cache = tmp_path / "cache"
    p = _run([SMOKE, "--rehearsal"],
             XLA_FLAGS="--xla_force_host_platform_device_count=4",
             JAX_COMPILATION_CACHE_DIR=str(cache))
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last == {"ok": True, "rehearsal": True,
                    "device": {"platform": "cpu", "kind": "cpu", "count": 4}}
    assert "REHEARSAL" in p.stdout
    for leg in ("[trainer zero1 tp=1]", "[trainer zero3 tp=2]",
                "[server default]", "[server megakernel]", "[kernels]"):
        assert leg in p.stdout, leg
    assert f"compile cache at {cache}" in p.stdout
    assert not os.path.exists(os.path.join(str(tmp_path), ".jax_compile_cache"))


def test_real_size_without_a_chip_fails_and_prints_no_result():
    p = _run([SMOKE])
    assert p.returncode != 0
    assert _result_lines(p.stdout) == []
    assert "needs a TPU" in p.stderr


def test_script_alone_fails_and_prints_no_result(tmp_path):
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    p = _run(["chip_smoke.py", "--rehearsal"], cwd=str(tmp_path),
             PYTHONPATH="")
    assert p.returncode != 0
    assert _result_lines(p.stdout) == []


def test_compile_cache_is_placed_from_outside(monkeypatch):
    from deepspeed_tpu.utils import platform
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(platform.CACHE_ENV, "/somewhere/else")
        assert platform.enable_compile_cache() == "/somewhere/else"
        # nothing in code set a directory: JAX reads the variable itself
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv(platform.CACHE_ENV)
        fixed = os.path.join(REPO, ".jax_compile_cache")
        assert platform.enable_compile_cache() == fixed
        assert jax.config.jax_compilation_cache_dir == fixed
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    with open(os.path.join(REPO, ".gitignore")) as fh:
        assert ".jax_compile_cache/" in fh.read().split()


def test_interpret_mode_refuses_an_unknown_backend(monkeypatch):
    from deepspeed_tpu.ops.pallas._utils import interpret_mode
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert interpret_mode() is True
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert interpret_mode() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="unsupported JAX backend 'gpu'"):
        interpret_mode()


def test_kernels_named_in_a_configuration_raise_outside_their_gate():
    from deepspeed_tpu.models.gpt import GPT, GPTConfig, causal_attention
    from deepspeed_tpu.ops.pallas import KernelUnsupported
    from deepspeed_tpu.serving import ServingEngine
    q = jnp.zeros((1, 16, 2, 16))
    with pytest.raises(KernelUnsupported, match="local-window"):
        causal_attention(q, q, q, dtype=jnp.float32, impl="pallas", window=4)
    # decode_impl='pallas' at h*d = 60: not a lane multiple
    cfg = GPTConfig(vocab_size=64, max_seq_len=128, num_layers=1,
                    num_heads=3, d_model=60, d_ff=64, dtype=jnp.float32,
                    decode_impl="pallas")
    model = GPT(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 4), jnp.int32))["params"]
    with pytest.raises(KernelUnsupported, match=r"h\*d=60"):
        model.apply({"params": params}, jnp.zeros((1, 4), jnp.int32),
                    mutable=["cache"])
    # megakernel=True names the fused sampler: vocab 64 is under a lane tile
    with pytest.raises(KernelUnsupported, match="vocab 64"):
        ServingEngine(GPT(GPTConfig(**{**cfg.__dict__, "decode_impl": "xla"})),
                      model_parameters=params, dtype=jnp.float32,
                      max_batch=2, megakernel=True)


def test_every_kernel_lowers_for_tpu_at_125m_shapes(monkeypatch):
    from deepspeed_tpu.ops.pallas import _utils
    from deepspeed_tpu.ops.pallas.decode_attention import (
        decode_attention, paged_decode_attention)
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
    from deepspeed_tpu.ops.pallas.gelu import bias_gelu
    from deepspeed_tpu.ops.pallas.layer_norm import layer_norm
    from deepspeed_tpu.ops.pallas.sampling import (fused_sample,
                                                   threshold_filter_logits)
    from deepspeed_tpu.ops.pallas.softmax import fused_softmax
    monkeypatch.setattr(_utils, "on_chip", lambda: True)   # interpret=False
    B, S, H, D, V, DM, DFF = 8, 1024, 12, 64, 50304, 768, 3072
    sd = jax.ShapeDtypeStruct
    bf, f32, i32, i8 = jnp.bfloat16, jnp.float32, jnp.int32, jnp.int8
    total = lambda t: jnp.sum(t.astype(f32))

    def kernels(fn, *args):
        text = jax.jit(fn).trace(*args).lower(
            lowering_platforms=("tpu",)).as_text()
        return set(re.findall(r'kernel_name = "([^"]+)"', text))

    q4 = sd((B, S, H, D), bf)
    assert kernels(jax.grad(lambda q, k, v: total(flash_attention(q, k, v)),
                            argnums=(0, 1, 2)), q4, q4, q4) == {
        "flash_attention_fwd", "flash_attention_bwd_dq",
        "flash_attention_bwd_dkv"}
    bs, nb = 32, B * S // 32
    for s in (1, 4):
        q = sd((B, s, H, D), bf)
        for dt, scales in ((bf, ()), (i8, (sd((B, S), f32),) * 2)):
            assert kernels(
                lambda q, k, v, n, *sc: decode_attention(
                    q, k, v, n, k_scale=sc[0] if sc else None,
                    v_scale=sc[1] if sc else None),
                q, sd((B, S, H * D), dt), sd((B, S, H * D), dt),
                sd((B,), i32), *scales) == {"decode_attention"}
        for dt, scales in ((bf, ()), (i8, (sd((nb, bs), f32),) * 2)):
            assert kernels(
                lambda q, k, v, t, n, *sc: paged_decode_attention(
                    q, k, v, t, n, impl="pallas",
                    k_scale=sc[0] if sc else None,
                    v_scale=sc[1] if sc else None),
                q, sd((nb, bs, H * D), dt), sd((nb, bs, H * D), dt),
                sd((B, S // bs), i32), sd((B,), i32),
                *scales) == {"paged_decode_attention"}
    logits = sd((B, V), f32)
    assert kernels(lambda x: fused_sample(x, None, 0.0, None, None),
                   logits) == {"sampling"}
    assert kernels(lambda x, g: fused_sample(x, g, 0.7, 8, 0.9),
                   logits, logits) == {"sampling"}
    assert kernels(lambda x: threshold_filter_logits(x, 0.7, 8, 0.9),
                   sd((5 * B, V), f32)) == {"sampling"}
    x = sd((B, S, DM), bf)
    g = sd((DM,), f32)
    assert kernels(layer_norm, x, g, g) == {"layer_norm_fwd"}
    assert kernels(jax.grad(lambda x, g, b: total(layer_norm(x, g, b))),
                   x, g, g) == {"layer_norm_bwd"}
    assert kernels(jax.grad(lambda x: total(fused_softmax(x, True))),
                   sd((2, H, S, S), bf)) == {"softmax_fwd", "softmax_bwd"}
    hx, hb = sd((B, S, DFF), bf), sd((DFF,), bf)
    assert kernels(bias_gelu, hx, hb) == {"bias_gelu_fwd"}
    assert kernels(jax.grad(lambda x, b: total(bias_gelu(x, b))),
                   hx, hb) == {"bias_gelu_bwd"}


def test_no_bench_file_ends_a_timed_window_in_device_get():
    """``jax.block_until_ready`` is the sync (on-chip-measurement guide
    section 3); a ``device_get`` of a scalar adds a transfer to every
    window and was only ever there for a runtime that is gone."""
    files = [os.path.join(REPO, "bench.py"), SMOKE]
    files += glob.glob(os.path.join(REPO, "benchmarks", "*.py"))
    files += glob.glob(os.path.join(REPO, "deepspeed_tpu", "benchmarks",
                                    "**", "*.py"), recursive=True)
    files += [os.path.join(REPO, "deepspeed_tpu", "autotuning", f)
              for f in ("autotuner.py", "runner.py")]
    assert len(files) > 12
    offenders = [os.path.relpath(f, REPO) for f in files
                 if "device_get" in open(f).read()]
    assert offenders == []


def test_flash_kernel_sits_under_shard_map_on_a_multi_device_mesh(monkeypatch):
    """Found on four v5e chips (PR 21): a Pallas call inside a jit over
    several devices does not lower — "Mosaic kernels cannot be
    automatically partitioned. Please wrap the call in a shard_map". The
    model's attention therefore runs the kernel under shard_map, batch over
    dp and heads over tp; cross-platform lowering shows both on the CPU."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from deepspeed_tpu.models.gpt import causal_attention
    from deepspeed_tpu.ops.pallas import _utils
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
    from deepspeed_tpu.parallel import mesh as mesh_lib
    monkeypatch.setattr(_utils, "on_chip", lambda: True)
    shape = mesh_lib.MeshShape.infer(8, tp=2)
    mesh = mesh_lib.build_mesh(shape)
    mesh_lib.set_global_mesh(mesh, shape)
    q = jax.ShapeDtypeStruct(
        (8, 256, 4, 64), jnp.bfloat16,
        sharding=NamedSharding(mesh, P("dp", None, "tp", None)))

    def lower(fn):
        return jax.jit(fn).trace(q).lower(
            lowering_platforms=("tpu",)).as_text()

    with pytest.raises(NotImplementedError, match="shard_map"):
        lower(lambda q: flash_attention(q, q, q))
    for impl in ("pallas", "auto"):
        text = lower(lambda q: causal_attention(q, q, q, dtype=jnp.bfloat16,
                                                impl=impl))
        assert 'kernel_name = "flash_attention_fwd"' in text
        # the kernel sees its own shard: batch 8/dp=4, heads 4/tp=2
        # (kernel layout [B, H, S, D])
        assert "tensor<2x2x256x64xbf16>" in text


def test_flops_per_token_counts_each_matmul_weight_once():
    """Found by the first real run of bench.py (PR 21): the formula counted
    the MLP twice and the embedding twice, 1.43 GFLOP/token for a 124M
    model, and the flagship read 0.88 MFU at a throughput worth 0.53."""
    from deepspeed_tpu.models.gpt import (GPT, gpt2_125m,
                                          gpt_flops_per_token)
    cfg = gpt2_125m(max_seq_len=1024)
    shapes = jax.eval_shape(
        lambda: GPT(cfg).init(jax.random.PRNGKey(0),
                              jnp.zeros((1, 8), jnp.int32))["params"])
    matmul_weights = sum(
        leaf.size for path, leaf in jax.tree_util.tree_leaves_with_path(shapes)
        if jax.tree_util.keystr(path).endswith(("['kernel']",
                                                "['embedding']")))
    attention = 12 * cfg.num_layers * cfg.d_model * 1024
    assert gpt_flops_per_token(cfg, 1024) == 6 * matmul_weights + attention


def test_logging_never_initialises_a_backend():
    """``log_dist`` used to call ``jax.process_index()``, which takes the
    chip: one log line in an orchestrating parent and its children die at
    backend init."""
    code = ("import jax\n"
            "from jax._src import xla_bridge\n"
            "from deepspeed_tpu.utils.logging import log_dist\n"
            "log_dist('hello', ranks=[0])\n"
            "assert not xla_bridge.backends_are_initialized()\n")
    p = _run(["-c", code], PYTHONPATH=REPO)
    assert p.returncode == 0, p.stderr[-2000:]


def test_autotuner_process_isolation_keeps_its_parent_off_the_chip(
        monkeypatch):
    from deepspeed_tpu.autotuning.autotuner import Autotuner
    from deepspeed_tpu.launcher import env_report
    probe = {"backend": "tpu", "devices": ["TPU_0", "TPU_1", "TPU_2",
                                           "TPU_3"], "hbm": 16909336064}
    monkeypatch.setattr(env_report, "probe_devices", lambda timeout: probe)

    def tuner():
        return Autotuner(None, None, {"train_micro_batch_size_per_gpu": 1},
                         isolation="process", factory_path="mod:fn")
    # device facts come from the probe child, not from this process's JAX
    assert tuner()._device_facts() == (4, 16909336064.0)
    # a parent that already holds a chip cannot start children that need it
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="already initialised"):
        tuner()._device_facts()
