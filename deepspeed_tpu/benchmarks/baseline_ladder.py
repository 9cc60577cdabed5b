"""The five BASELINE acceptance workloads (BASELINE.json configs ladder):

  1. gpt2_125m  — ZeRO-1 bf16 training throughput/MFU (bench.py flagship)
  2. gpt_1_3b   — ZeRO-3 + CPU-offloaded optimizer training step
  3. gpt3_175b  — Infinity-style fits check: abstract construction + tier
                  memory arithmetic (no chip large enough to time it here)
  4. pr_moe     — PR-MoE expert-parallel training throughput
  5. bert_large — int8 TP inference latency

Emits one JSON line per rung. ``--quick`` (default) scales model sizes to
what a single attached chip compiles in seconds while keeping every
structural feature on (scan layers, offload tiers, MoE dispatch, int8);
``--full`` runs the real sizes where the hardware allows.

Usage: python -m deepspeed_tpu.benchmarks.baseline_ladder [--quick|--full]
"""

from __future__ import annotations

import argparse
import json
import time


def _train_tput(engine, batch_iter_factory, tokens_per_step, steps=4,
                warmup=2):
    import jax
    for _ in range(warmup):
        loss = engine.train_batch(batch_iter_factory())
    jax.block_until_ready(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = engine.train_batch(batch_iter_factory())
    jax.block_until_ready(loss)
    dt = (time.perf_counter() - t0) / steps
    return tokens_per_step / dt, dt


def rung_gpt125m(quick: bool):
    import numpy as np
    import jax, jax.numpy as jnp
    import deepspeed_tpu as ds
    from deepspeed_tpu.models.gpt import (GPT, gpt2_125m, gpt_flops_per_token,
                                          lm_loss_fn)
    seq, batch, gas = (256, 4, 2) if quick else (1024, 8, 16)
    cfg = gpt2_125m(max_seq_len=seq, dtype=jnp.bfloat16)
    model = GPT(cfg)
    ids = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (batch, seq)).astype(np.int32)
    params = model.init(jax.random.PRNGKey(0), ids[:1, :8])["params"]
    engine, *_ = ds.initialize(
        model=model, model_parameters=params, loss_fn=lm_loss_fn,
        config={"train_micro_batch_size_per_gpu": batch,
                "gradient_accumulation_steps": gas,
                "bf16": {"enabled": True},
                "zero_optimization": {"stage": 1},
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
                "steps_per_print": 10_000})
    toks, dt = _train_tput(engine, lambda: iter([{"input_ids": ids}] * gas),
                           batch * gas * seq)
    # gpt_flops_per_token is already the full training number (6N + attn)
    flops = toks * gpt_flops_per_token(cfg, seq)
    return {"config": "gpt2_125m_zero1", "tokens_per_sec": round(toks),
            "tflops": round(flops / 1e12, 1), "step_ms": round(dt * 1e3, 1)}


def rung_gpt13b(quick: bool):
    import numpy as np
    import jax, jax.numpy as jnp
    import deepspeed_tpu as ds
    from deepspeed_tpu.models.gpt import (GPT, GPTConfig, gpt2_1_3b,
                                          lm_loss_fn)
    if quick:
        cfg = GPTConfig(vocab_size=8192, max_seq_len=256, num_layers=4,
                        num_heads=8, d_model=512, d_ff=2048,
                        dtype=jnp.bfloat16)
        batch, seq = 2, 256
    else:
        cfg = gpt2_1_3b(dtype=jnp.bfloat16)
        batch, seq = 1, 1024
    model = GPT(cfg)
    ids = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (batch, seq)).astype(np.int32)
    from deepspeed_tpu.runtime.zero.partition_params import abstract_init
    tree = abstract_init(model, jax.random.PRNGKey(0),
                         jnp.zeros((1, 8), jnp.int32))
    engine, *_ = ds.initialize(
        model=model, model_parameters=tree, loss_fn=lm_loss_fn,
        config={"train_micro_batch_size_per_gpu": batch,
                "gradient_accumulation_steps": 1,
                "bf16": {"enabled": True},
                "zero_optimization": {
                    "stage": 3, "offload_optimizer": {"device": "cpu"}},
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
                "steps_per_print": 10_000})
    toks, dt = _train_tput(engine, lambda: iter([{"input_ids": ids}]),
                           batch * seq, steps=3, warmup=1)
    return {"config": ("gpt_1.3b" if not quick else "gpt_1.3b_structure")
            + "_zero3_offload", "tokens_per_sec": round(toks),
            "step_ms": round(dt * 1e3, 1),
            "host_params": engine.host_optimizer.numel()}


def rung_175b_fits():
    import numpy as np
    import jax, jax.numpy as jnp
    from deepspeed_tpu.autotuning.memory import model_states_memory_per_chip
    from deepspeed_tpu.models.gpt import GPT, gpt3_175b
    from deepspeed_tpu.runtime.zero.partition_params import (abstract_init,
                                                             num_params)
    cfg = gpt3_175b()
    tree = abstract_init(GPT(cfg), jax.random.PRNGKey(0),
                         jnp.zeros((1, 8), jnp.int32))
    n = num_params(tree)
    # v5p-64: 64 chips x 95GB HBM, 16 hosts
    hbm_per_chip = model_states_memory_per_chip(n, zero_stage=3, dp=64)
    # Infinity tiers: master+moments on NVMe, bf16 mirrors on NVMe,
    # host DRAM = staging buffers only
    return {"config": "gpt3_175b_fits", "params": n,
            "zero3_hbm_per_chip_gb": round(hbm_per_chip / 1e9, 1),
            "fits_v5p64_hbm": bool(hbm_per_chip < 90e9),
            "nvme_bytes_per_host_gb": round(n * (12 + 2) / 16 / 1e9, 1)}


def rung_moe(quick: bool):
    import numpy as np
    import jax, jax.numpy as jnp
    import deepspeed_tpu as ds
    from deepspeed_tpu.models.gpt import GPT, GPTConfig, lm_loss_fn
    ne = 8 if quick else 64
    cfg = GPTConfig(vocab_size=8192, max_seq_len=256, num_layers=2,
                    num_heads=4, d_model=256, d_ff=1024,
                    dtype=jnp.bfloat16, moe=True, num_experts=ne,
                    moe_top_k=1, moe_use_residual=True)
    model = GPT(cfg)
    batch, seq = 4, 256
    ids = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (batch, seq)).astype(np.int32)
    params = model.init(jax.random.PRNGKey(0), ids[:1, :8])["params"]
    engine, *_ = ds.initialize(
        model=model, model_parameters=params, loss_fn=lm_loss_fn,
        config={"train_micro_batch_size_per_gpu": batch,
                "gradient_accumulation_steps": 1,
                "bf16": {"enabled": True},
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
                "steps_per_print": 10_000})
    toks, dt = _train_tput(engine, lambda: iter([{"input_ids": ids}]),
                           batch * seq, steps=3, warmup=1)
    return {"config": f"pr_moe_{ne}e", "tokens_per_sec": round(toks),
            "step_ms": round(dt * 1e3, 1)}


def rung_bert(quick: bool):
    import numpy as np
    import jax, jax.numpy as jnp
    import deepspeed_tpu as ds
    from deepspeed_tpu.models.bert import BertConfig, BertModel, bert_large
    cfg = (BertConfig(num_layers=4, num_heads=8, d_model=512, d_ff=2048,
                      hidden_dropout=0.0) if quick
           else bert_large(hidden_dropout=0.0))
    model = BertModel(cfg)
    b, s = 8, 128
    ids = np.random.default_rng(0).integers(0, cfg.vocab_size,
                                            (b, s)).astype(np.int32)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    engine = ds.init_inference(model, mp_size=1, dtype=jnp.bfloat16,
                               model_parameters=params, quantize_bits=8)
    rng2 = np.random.default_rng(1)
    batches = [jnp.asarray(rng2.integers(0, cfg.vocab_size,
                                         (b, s)).astype(np.int32))
               for _ in range(10)]
    out = engine.forward(jnp.asarray(ids))
    jax.block_until_ready(out)
    # distinct inputs per iteration, so no layer can serve a repeat from
    # a cache and read as speed
    t0 = time.perf_counter()
    iters = len(batches)
    for x in batches:
        out = engine.forward(x)
    jax.block_until_ready(out)
    dt = (time.perf_counter() - t0) / iters
    return {"config": ("bert_large" if not quick else "bert_structure")
            + "_int8", "batch": b, "seq": s,
            "latency_ms": round(dt * 1e3, 2),
            "samples_per_sec": round(b / dt)}


def rung_long_context(quick: bool):
    """Sequence-length scaling on one chip: flash attention keeps memory
    O(S) (no S^2 score matrix); with the sp mesh axis the same config
    scales context by the ring/ulysses degree (tests/test_sequence_parallel)."""
    import numpy as np
    import jax, jax.numpy as jnp
    import deepspeed_tpu as ds
    from deepspeed_tpu.models.gpt import GPT, GPTConfig, lm_loss_fn
    seq = 4096 if quick else 16384
    cfg = GPTConfig(vocab_size=8192, max_seq_len=seq, num_layers=4,
                    num_heads=8, d_model=512, d_ff=2048,
                    dtype=jnp.bfloat16, sequence_parallel=False)
    model = GPT(cfg)
    ids = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (1, seq)).astype(np.int32)
    params = model.init(jax.random.PRNGKey(0), ids[:, :8])["params"]
    engine, *_ = ds.initialize(
        model=model, model_parameters=params, loss_fn=lm_loss_fn,
        config={"train_micro_batch_size_per_gpu": 1,
                "gradient_accumulation_steps": 1,
                "bf16": {"enabled": True},
                "zero_optimization": {"stage": 1},
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
                "steps_per_print": 10_000})
    toks, dt = _train_tput(engine, lambda: iter([{"input_ids": ids}]),
                           seq, steps=3, warmup=2)
    return {"config": f"long_context_seq{seq}", "tokens_per_sec": round(toks),
            "step_ms": round(dt * 1e3, 1)}


def rung_decode(quick: bool):
    """Autoregressive decode throughput (reference weak-point: decode
    tokens/s measured on chip): whole decode loop is one scan-jit."""
    import numpy as np
    import jax, jax.numpy as jnp
    import deepspeed_tpu as ds
    from deepspeed_tpu.models.gpt import GPT, GPTConfig, gpt2_125m
    if quick:
        cfg = GPTConfig(vocab_size=8192, max_seq_len=512, num_layers=4,
                        num_heads=8, d_model=512, d_ff=2048,
                        dtype=jnp.bfloat16)
    else:
        cfg = gpt2_125m(max_seq_len=1024, dtype=jnp.bfloat16)
    model = GPT(cfg)
    b, prompt, new = 8, 32, 128
    ids = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (b, prompt)).astype(np.int32)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.asarray(ids[:1, :8]))["params"]
    engine = ds.init_inference(model, mp_size=1, dtype=jnp.bfloat16,
                               model_parameters=params)
    out = engine.generate(ids, max_new_tokens=new, temperature=0.0)
    jax.block_until_ready(out)
    # distinct prompts per iteration (see the rung_bert note)
    rng2 = np.random.default_rng(1)
    prompts = [rng2.integers(0, cfg.vocab_size, (b, prompt)).astype(np.int32)
               for _ in range(3)]
    t0 = time.perf_counter()
    iters = len(prompts)
    for p in prompts:
        out = engine.generate(p, max_new_tokens=new, temperature=0.0)
    jax.block_until_ready(out)
    dt = (time.perf_counter() - t0) / iters
    return {"config": "decode_throughput", "batch": b, "new_tokens": new,
            "decode_tokens_per_sec": round(b * new / dt),
            "ms_per_token": round(dt / new * 1e3, 2)}


def main(argv=None):
    from ..utils.platform import enable_compile_cache
    enable_compile_cache()       # before any compile
    parser = argparse.ArgumentParser(prog="baseline_ladder")
    parser.add_argument("--full", action="store_true")
    parser.add_argument("--rungs", nargs="+",
                        default=["125m", "1.3b", "175b", "moe", "bert",
                                 "longctx", "decode"])
    args = parser.parse_args(argv)
    quick = not args.full
    rungs = {
        "125m": lambda: rung_gpt125m(quick),
        "1.3b": lambda: rung_gpt13b(quick),
        "175b": rung_175b_fits,
        "moe": lambda: rung_moe(quick),
        "bert": lambda: rung_bert(quick),
        "longctx": lambda: rung_long_context(quick),
        "decode": lambda: rung_decode(quick),
    }
    results = []
    for name in args.rungs:
        try:
            r = rungs[name]()
        except Exception as e:  # report the rung as failed, keep climbing
            r = {"config": name, "error": f"{type(e).__name__}: {e}"}
        results.append(r)
        print(json.dumps(r))
    return results


if __name__ == "__main__":
    main()
