#!/usr/bin/env python3
"""chip_smoke.py — does the system still start on the chip?

One process, no children. Drives the two main paths through the entry
points a user calls, at the published widths of GPT-2 125M (12 layers,
d 768, 12 heads of 64, vocab 50304, 1024 positions, bf16; random weights
from a seed), and checks what comes out against the repo's plain float32
reference (``deepspeed_tpu/models/gpt_reference.py``):

  trainer leg   ``ds.initialize`` -> ``engine.train_batch`` x a few on one
                seeded batch (ZeRO-1 over ``dp`` = every chip found; with
                four chips also ZeRO-3 over dp=2 x tp=2, and the optimizer
                state / parameters are shown divided over the chips)
  server leg    ``ds.init_inference`` -> ``ServingEngine`` behind
                ``ServingFrontend``: requests of mixed prompt lengths
                streamed to ``done``, twice, at the default configuration
                (XLA decode) and with ``megakernel=True`` (Pallas decode +
                fused sampling epilogue)
  kernel leg    every kernel in ``deepspeed_tpu/ops/pallas/`` compiled by
                Mosaic (``interpret=False``) at this model's shapes and
                compared on the device with its XLA reference

It exits non-zero — and prints no result line — when JAX finds no TPU or
when any check fails; nothing here catches a leg's failure. The last line
of a passing run is one JSON object: ``{"ok": true, "device": {...}}``.

``--rehearsal`` is the CPU rehearsal (what tests/test_chip_smoke.py
drives): the same legs at toy size with the kernels in the Pallas
interpreter. It says so on every line that matters and in the result.

Every time and rate this script prints is a SMOKE READING: one cold run,
no warm-up discipline, no repeats. Benchmarks live in bench.py.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time

import numpy as np

# ---------------------------------------------------------------- tolerances
# bf16 compute against the float32 reference. bf16 keeps 8 significant
# bits (unit roundoff 2^-9 ~ 0.002 per rounding); matmuls accumulate in
# float32, so the error is the rounding of each layer's activations
# carried through 12 layers, not a sum over the contraction.
#
# Logits: the measured max |bf16 - f32| on a v5e at these shapes is 0.052,
# the same through the einsum cache path and through the Pallas decode
# kernel, on reference logits spanning [-5.1, 5.3] (PR 21 chip runs, one
# chip and four). 0.15 is ~3x that and 1.5% of the logit range — a wrong
# mask, a stale cache row or a mis-scaled head moves logits by O(1).
LOGIT_ATOL = 0.15
# A greedy token is the argmax of the server's own bf16 logits; under the
# reference it can trail the reference argmax by at most the error on the
# two logits involved (measured: 0.0 einsum path, 0.008 Pallas path).
TOKEN_GAP_ATOL = 2 * LOGIT_ATOL
# Loss: a mean over >= 8k tokens, so per-token rounding averages out and
# what remains is bias. Measured |bf16 - f32| on a v5e: 0.0001 at a loss
# of 11.32, on one chip, dp=4 and dp=2 x tp=2 alike (PR 21 chip runs);
# 0.01 is 100x that and still 0.1% of the loss.
LOSS_ATOL = 0.01
# Kernels take bf16, compute in float32 and round their output to bf16
# once; their XLA references run in float32 at highest matmul precision on
# the same inputs. The bound is relative to the LARGEST MAGNITUDE in the
# compared tensor (a gradient element is a sum of up to 1024 terms, so
# elementwise relative error means nothing near zero): 2e-2 is ~10 output
# roundings. Measured on a v5e (PR 21 chip run): 0.2-0.5% for every kernel
# but the softmax backward at 1.1%, which re-reads its own bf16 output.
KERNEL_RTOL_BF16 = 2e-2
KERNEL_RTOL_F32 = 1e-4

SEED = 0


class SmokeFailure(AssertionError):
    """A check failed. Never caught: the run ends non-zero."""


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(leg: str, msg: str) -> None:
    print(f"[{leg}] {msg}", flush=True)


# --------------------------------------------------------------------- sizes
def model_config(rehearsal: bool):
    import jax.numpy as jnp
    from deepspeed_tpu.models.gpt import GPTConfig, gpt2_125m
    if rehearsal:
        # toy: two layers at one lane-tile of width, so every gate that
        # accepts the real shapes accepts these too
        return GPTConfig(num_layers=2, num_heads=2, d_model=128, d_ff=256,
                         vocab_size=512, max_seq_len=128, dtype=jnp.bfloat16)
    return gpt2_125m(max_seq_len=1024, dtype=jnp.bfloat16)


def sizes(rehearsal: bool) -> dict:
    if rehearsal:
        return dict(micro_batch=2, gas=2, train_steps=4, lr=1e-4,
                    prompt_lens=(3, 9, 17, 30), max_prompt_len=32,
                    max_new=6, logit_steps=3, softmax_batch=1)
    return dict(micro_batch=8, gas=2, train_steps=5, lr=1e-4,
                prompt_lens=(5, 17, 40, 100, 200, 33), max_prompt_len=256,
                max_new=12, logit_steps=4, softmax_batch=2)


# ------------------------------------------------------------------- helpers
def mosaic_kernels(lowered) -> list:
    """Names of the Mosaic custom calls in a lowered program, read from the
    lowered text (the flag that asked for them is not evidence)."""
    return re.findall(r'@tpu_custom_call\(.*?kernel_name = "([^"]+)"',
                      lowered.as_text(), flags=re.S)


def kernel_operand(lowered, name: str):
    """Type of the first operand of the Mosaic call ``name`` in a lowered
    program — its per-device shape when the call sits under shard_map."""
    m = re.search(r'@tpu_custom_call\([^\n]*?kernel_name = "' + name
                  + r'"[^\n]*?: \((tensor<[^>]+>)', lowered.as_text())
    return m.group(1) if m else None


def require_kernels(leg: str, what: str, lowered, wanted, on_chip: bool):
    """On the chip every selected kernel must be a Mosaic custom call in
    the lowered program. In the CPU rehearsal the interpreter inlines the
    kernels, so there is nothing to find and the line says so."""
    found = sorted(set(mosaic_kernels(lowered)))
    if on_chip:
        missing = sorted(set(wanted) - set(found))
        check(not missing, f"{what}: lowered program lacks Mosaic custom "
              f"calls {missing} (found {found})")
        say(leg, f"{what}: Mosaic custom calls in the lowered program: "
            f"{found}")
    else:
        say(leg, f"{what}: REHEARSAL — kernels run in the Pallas "
            f"interpreter, no custom calls to read (found {found})")
    return found


def device_shares(tree, devices) -> list:
    """Fraction of a pytree's bytes held on each device, from
    ``addressable_shards``."""
    import jax
    held = {d.id: 0 for d in devices}
    total = 0
    for leaf in jax.tree.leaves(tree):
        if not hasattr(leaf, "addressable_shards"):
            continue
        total += leaf.nbytes
        for shard in leaf.addressable_shards:
            held[shard.device.id] += shard.data.nbytes
    return [round(held[d.id] / max(total, 1), 4) for d in devices]


class CompileClock:
    """Splits a leg's wall time into calls that compiled and calls that
    did not, by watching the persistent-cache request counter."""

    def __init__(self, counter):
        self.counter = counter
        self.compile_s = 0.0
        self.steady = []
        self.start = counter.snapshot()

    def timed(self, fn):
        import jax
        before = self.counter.snapshot()
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn())
        dt = time.perf_counter() - t0
        if self.counter.since(before)["requests"]:
            self.compile_s += dt
        else:
            self.steady.append(dt)
        return out

    def report(self) -> dict:
        cache = self.counter.since(self.start)
        return {"compile_s": round(self.compile_s, 2),
                "steady_s_median": (round(float(np.median(self.steady)), 4)
                                    if self.steady else None),
                "cache": cache}


# ------------------------------------------------------------- trainer leg
def trainer_leg(cfg, sz, devices, counter, *, zero_stage: int, tp: int,
                on_chip: bool) -> None:
    import jax
    import deepspeed_tpu as ds
    from deepspeed_tpu.models.gpt import GPT, lm_loss_fn
    from deepspeed_tpu.models.gpt_reference import reference_lm_loss
    from deepspeed_tpu.parallel import mesh as mesh_lib

    leg = f"trainer zero{zero_stage} tp={tp}"
    n = len(devices)
    dp = n // tp
    rows = sz["micro_batch"] * dp
    model = GPT(cfg)
    ids = np.random.default_rng(SEED).integers(
        0, cfg.vocab_size, (rows, cfg.max_seq_len)).astype(np.int32)
    params = model.init(jax.random.PRNGKey(SEED), ids[:1, :8])["params"]

    # float32 reference loss of the untouched weights, a row at a time
    # (a whole batch of float32 [rows, S, V] logits is gigabytes)
    ref_row = jax.jit(lambda p, x: reference_lm_loss(cfg, p, x))
    ref_loss = float(np.mean([float(ref_row(params, ids[i:i + 1]))
                              for i in range(rows)]))

    mesh_lib.reset_global_mesh()
    config = {"train_micro_batch_size_per_gpu": sz["micro_batch"],
              "gradient_accumulation_steps": sz["gas"],
              "bf16": {"enabled": True},
              "zero_optimization": {"stage": zero_stage},
              "optimizer": {"type": "AdamW", "params": {"lr": sz["lr"]}},
              "steps_per_print": 100_000}
    if tp > 1:
        config["mesh"] = {"tp": tp}
    engine, *_ = ds.initialize(model=model, model_parameters=params,
                               loss_fn=lm_loss_fn, config=config)
    mesh = {k: v for k, v in dict(engine.mesh.shape).items() if v > 1}
    check(dict(engine.mesh.shape)["dp"] == dp,
          f"{leg}: engine mesh {dict(engine.mesh.shape)} is not dp={dp}")
    say(leg, f"mesh={mesh or {'dp': 1}} over {n} device(s), "
        f"global batch {rows}x{cfg.max_seq_len}x{sz['gas']} tokens/step")

    batch = {"input_ids": ids}
    clock = CompileClock(counter)
    losses = [float(clock.timed(
        lambda: engine.train_batch(iter([batch] * sz["gas"]))))
        for _ in range(sz["train_steps"])]
    say(leg, f"losses {[round(x, 4) for x in losses]}  "
        f"float32 reference at step 1: {ref_loss:.4f}")
    check(all(np.isfinite(losses)), f"{leg}: non-finite loss {losses}")
    check(losses[-1] < losses[0],
          f"{leg}: loss did not fall on the repeated batch: {losses}")
    check(abs(losses[0] - ref_loss) <= LOSS_ATOL,
          f"{leg}: first-step loss {losses[0]:.4f} vs float32 reference "
          f"{ref_loss:.4f}: off by more than {LOSS_ATOL}")

    # which attention ran: read it from the program, not from the config
    stacked = jax.tree.map(lambda *xs: np.stack(xs), *([batch] * sz["gas"]))
    lowered = engine._jit_train.lower(
        engine.state, engine._shard_batch(stacked, stacked=True),
        engine._forward_extras())
    flash = ["flash_attention_fwd", "flash_attention_bwd_dq",
             "flash_attention_bwd_dkv"]
    found = require_kernels(leg, "train step", lowered, flash, on_chip)
    path = "pallas" if set(flash) <= set(found) else "xla (reference)"
    say(leg, f"attention path: {path} (attention_impl="
        f"{cfg.attention_impl!r})")
    if n > 1 and on_chip:
        # GSPMD cannot partition a Mosaic call: the kernel must sit under
        # shard_map on its own shard, batch over dp and heads over tp
        want = (f"tensor<{sz['micro_batch']}x{cfg.num_heads // tp}x"
                f"{cfg.max_seq_len}x{cfg.head_dim}xbf16>")
        got = kernel_operand(lowered, "flash_attention_fwd")
        say(leg, f"flash kernel operand per device: {got}")
        check(got == want, f"{leg}: flash kernel operand {got}, expected "
              f"the per-device shard {want}")

    shares = {}
    if n > 1:
        shares["opt"] = device_shares(engine.state["opt"], devices)
        bound = 1.1 / dp
        check(max(shares["opt"]) <= bound and min(shares["opt"]) > 0,
              f"{leg}: optimizer state not divided over dp={dp}: per-device "
              f"shares {shares['opt']}")
        if zero_stage == 3:
            shares["params"] = device_shares(engine.state["master"], devices)
            check(max(shares["params"]) <= bound
                  and min(shares["params"]) > 0,
                  f"{leg}: ZeRO-3 parameters not divided over dp={dp}: "
                  f"per-device shares {shares['params']}")
        say(leg, f"per-device share of bytes {shares} (whole = 1.0)")

    timing = clock.report()
    tokens = rows * cfg.max_seq_len * sz["gas"]
    say(leg, f"SMOKE READING compile {timing['compile_s']}s, steady step "
        f"{timing['steady_s_median']}s ({tokens} tokens/step), "
        f"persistent cache {timing['cache']}")


# -------------------------------------------------------------- server leg
def cache_path_logits(module, params, prompts, outputs, steps: int):
    """Prefill then ``steps`` decode steps through the KV cache, the way
    ServingEngine's programs drive the model (bucket-padded prefill,
    per-row write cursors), returning the logits that chose each of the
    first ``steps + 1`` tokens: [n, steps + 1, V] float32."""
    import jax
    import jax.numpy as jnp

    n = len(prompts)
    lens = np.asarray([len(p) for p in prompts], np.int32)
    width = int(max(lens))
    ids = np.zeros((n, width), np.int32)
    for i, p in enumerate(prompts):
        ids[i, :len(p)] = p

    @jax.jit
    def prefill(params, ids, lens):
        positions = jnp.arange(ids.shape[1])[None, :]
        logits, vc = module.apply({"params": params}, ids,
                                  positions=positions, mutable=["cache"])
        last = jnp.take_along_axis(logits, (lens - 1)[:, None, None],
                                   axis=1)[:, 0]
        return last, vc["cache"]

    def with_cursor(cache, positions):
        # the engine owns the write cursor: every per-layer cache_index
        # leaf [layers(, n)] becomes the per-row positions [layers, n]
        def leaf(path, x):
            if "cache_index" in jax.tree_util.keystr(path):
                return jnp.broadcast_to(positions.astype(x.dtype),
                                        (x.shape[0], n))
            return x
        return jax.tree_util.tree_map_with_path(leaf, cache)

    @jax.jit
    def decode(params, cache, tokens, positions):
        logits, vc = module.apply(
            {"params": params, "cache": with_cursor(cache, positions)},
            tokens[:, None], positions=positions[:, None], mutable=["cache"])
        return logits[:, -1], vc["cache"]

    last, cache = prefill(params, jnp.asarray(ids), jnp.asarray(lens))
    out = [last]
    for j in range(steps):
        tokens = jnp.asarray([o[j] for o in outputs], jnp.int32)
        last, cache = decode(params, cache, tokens, jnp.asarray(lens + j))
        out.append(last)
    return np.stack([np.asarray(x, np.float32) for x in out], axis=1)


def serve_once(eng, sz, prompts, megakernel: bool, deadline_s: float = 600.0):
    """One fresh ServingEngine behind a ServingFrontend; every request
    streamed (polled) to a terminal status."""
    from deepspeed_tpu.serving.engine import ServingEngine
    from deepspeed_tpu.serving.frontend.frontend import ServingFrontend
    srv = ServingEngine(engine=eng, max_batch=8,
                        max_prompt_len=sz["max_prompt_len"],
                        megakernel=megakernel)
    t0 = time.perf_counter()
    with ServingFrontend(srv) as fe:
        handles = [fe.submit(p, max_new_tokens=sz["max_new"])
                   for p in prompts]
        streamed = [[] for _ in handles]
        while not all(h.done for h in handles):
            check(time.perf_counter() - t0 < deadline_s,
                  f"server: requests not terminal after {deadline_s}s: "
                  f"{[h.status for h in handles]}")
            for got, h in zip(streamed, handles):
                got.extend(h.poll())
            time.sleep(0.005)
        for got, h in zip(streamed, handles):
            got.extend(h.poll())
    wall = time.perf_counter() - t0
    return srv, streamed, [(h.status, h.error) for h in handles], wall


def server_leg(cfg, sz, params, counter, *, megakernel: bool,
               on_chip: bool) -> None:
    import jax
    import jax.numpy as jnp
    import deepspeed_tpu as ds
    from deepspeed_tpu.models.gpt import GPT
    from deepspeed_tpu.models.gpt_reference import reference_logits
    from deepspeed_tpu.parallel import mesh as mesh_lib

    leg = "server megakernel" if megakernel else "server default"
    mesh_lib.reset_global_mesh()
    eng = ds.init_inference(GPT(cfg), model_parameters=params,
                            dtype=cfg.dtype)
    rng = np.random.default_rng(SEED + 1)
    prompts = [rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in sz["prompt_lens"]]

    start = counter.snapshot()
    srv, toks, status, wall1 = serve_once(eng, sz, prompts, megakernel)
    first_cache = counter.since(start)
    check(all(s == "done" for s, _ in status),
          f"{leg}: not every request resolved done: {status}")
    for p, t in zip(prompts, toks):
        check(len(t) == sz["max_new"],
              f"{leg}: prompt of {len(p)} got {len(t)} tokens, asked "
              f"{sz['max_new']}")
        check(all(0 <= x < cfg.vocab_size for x in t),
              f"{leg}: token outside the vocabulary: {t}")
    say(leg, f"{len(prompts)} requests (prompt lengths "
        f"{list(sz['prompt_lens'])}) all done, {sz['max_new']} tokens each")

    again = counter.snapshot()
    _, toks2, status2, wall2 = serve_once(eng, sz, prompts, megakernel)
    second_cache = counter.since(again)
    check(all(s == "done" for s, _ in status2) and toks2 == toks,
          f"{leg}: the same requests gave different tokens the second "
          f"time: {toks} vs {toks2}")
    say(leg, "same requests, fresh engine: same tokens")

    # ---- logit level: the float32 reference's full forward over
    # prompt + generated tokens, against (a) the logits the cache path
    # computes and (b) the tokens the server actually emitted
    steps = sz["logit_steps"]
    total = max(len(p) for p in prompts) + sz["max_new"]
    full = np.zeros((len(prompts), total), np.int32)
    for i, (p, t) in enumerate(zip(prompts, toks)):
        full[i, :len(p)] = p
        full[i, len(p):len(p) + len(t)] = t
    ref = np.asarray(jax.jit(lambda p, x: reference_logits(cfg, p, x))(
        params, jnp.asarray(full)))                      # [n, total, V]
    got = cache_path_logits(srv.module, eng.params, prompts, toks, steps)
    worst_logit = worst_gap = 0.0
    for i, (p, t) in enumerate(zip(prompts, toks)):
        rows = ref[i, len(p) - 1:len(p) - 1 + len(t)]    # row j chose t[j]
        worst_logit = max(worst_logit, float(np.max(np.abs(
            got[i] - rows[:steps + 1]))))
        gaps = rows.max(axis=-1) - rows[np.arange(len(t)), t]
        worst_gap = max(worst_gap, float(gaps.max()))
    say(leg, f"prefill + {steps} decode steps through the cache vs float32 "
        f"reference: max |logit diff| {worst_logit:.4f} (tol {LOGIT_ATOL}, "
        f"reference logits span [{ref.min():.2f}, {ref.max():.2f}])")
    say(leg, f"every emitted token's reference logit is within "
        f"{worst_gap:.4f} of the reference argmax (tol {TOKEN_GAP_ATOL})")
    check(np.isfinite(got).all(), f"{leg}: non-finite logits")
    check(worst_logit <= LOGIT_ATOL,
          f"{leg}: cache-path logits off the float32 reference by "
          f"{worst_logit:.4f} > {LOGIT_ATOL}")
    check(worst_gap <= TOKEN_GAP_ATOL,
          f"{leg}: an emitted token trails the reference argmax by "
          f"{worst_gap:.4f} > {TOKEN_GAP_ATOL}")

    # ---- which decode ran: read the lowered decode-chunk program
    B = srv.max_batch
    i32 = jax.ShapeDtypeStruct((B,), np.int32)
    abst = lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype)
    lowered = srv._jit_decode_chunk.lower(
        jax.tree.map(abst, eng.params), jax.tree.map(abst, srv.kv.cache),
        i32, i32, jax.ShapeDtypeStruct((B,), bool), i32, i32,
        abst(srv._rng))
    wanted = ["decode_attention", "sampling"] if megakernel else []
    found = require_kernels(leg, "decode chunk", lowered, wanted, on_chip)
    if not megakernel:
        check(not found, f"{leg}: default configuration (decode_impl="
              f"{srv.module.cfg.decode_impl!r}) lowered Mosaic calls {found}")
    if not on_chip:
        path = ("REHEARSAL: einsum decode; " + (
            "fused sampling epilogue in the interpreter" if megakernel
            else "sort-based sampler"))
    elif megakernel:
        path = "pallas decode kernel + fused sampling epilogue"
    else:
        path = ("einsum decode (the live-rows read's gate refuses heads "
                "of 64) + sort-based sampler")
    say(leg, f"decode path: {path} (decode_impl="
        f"{srv.module.cfg.decode_impl!r}, megakernel={megakernel})")
    devices = jax.devices()
    if len(devices) > 1:
        say(leg, f"engine mesh {dict(eng.mesh.shape)} over {len(devices)} "
            f"devices; per-device share of bytes: weights "
            f"{device_shares(eng.params, devices)}, KV arena "
            f"{device_shares(srv.kv.cache, devices)} (whole = 1.0)")
    say(leg, f"SMOKE READING first pass {wall1:.2f}s (compiles: cache "
        f"{first_cache}), second pass with a fresh engine {wall2:.2f}s "
        f"(cache {second_cache})")


def megakernel_refuses_a_mesh(cfg, sz, params) -> None:
    """On several chips the inference engine's mesh takes them all, and
    the decode and sampling kernels are not wrapped in shard_map: asked
    for by name there, ``megakernel=True`` must raise at construction with
    the reason, not die in its first compile."""
    import deepspeed_tpu as ds
    from deepspeed_tpu.models.gpt import GPT
    from deepspeed_tpu.ops.pallas import KernelUnsupported
    from deepspeed_tpu.parallel import mesh as mesh_lib
    from deepspeed_tpu.serving.engine import ServingEngine
    mesh_lib.reset_global_mesh()
    eng = ds.init_inference(GPT(cfg), model_parameters=params,
                            dtype=cfg.dtype)
    try:
        ServingEngine(engine=eng, max_batch=8,
                      max_prompt_len=sz["max_prompt_len"], megakernel=True)
    except KernelUnsupported as e:
        say("server megakernel", f"not run on this mesh; asked by name it "
            f"raises: {e}")
    else:
        raise SmokeFailure("megakernel=True on a multi-device mesh neither "
                           "raised nor was expected to run")


# -------------------------------------------------------------- kernel leg
def kernel_leg(cfg, sz, on_chip: bool) -> None:
    """Every kernel in ops/pallas/ at this model's shapes: compiled (Mosaic
    on the chip, the interpreter in rehearsal), run, and compared on the
    device with its XLA reference."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.ops.pallas.decode_attention import (
        decode_attention, live_decode_attention, live_latent_attention,
        masked_cache_attention, paged_decode_attention)
    from deepspeed_tpu.ops.pallas.flash_attention import (
        flash_attention, reference_attention)
    from deepspeed_tpu.ops.pallas.gelu import bias_gelu, bias_gelu_reference
    from deepspeed_tpu.ops.pallas.layer_norm import (layer_norm,
                                                     layer_norm_reference)
    from deepspeed_tpu.ops.pallas.sampling import (fused_sample,
                                                   threshold_filter_logits)
    from deepspeed_tpu.ops.pallas.softmax import (fused_softmax,
                                                  softmax_reference)
    from deepspeed_tpu.ops.quantizer import dequantize_kv, quantize_kv
    from deepspeed_tpu.serving.sampling import filter_logits

    leg = "kernels"
    B, S = sz["micro_batch"], cfg.max_seq_len
    H, D, V = cfg.num_heads, cfg.head_dim, cfg.vocab_size
    dt = cfg.dtype
    rtol = KERNEL_RTOL_BF16 if dt == jnp.bfloat16 else KERNEL_RTOL_F32
    rng = np.random.default_rng(SEED + 2)
    f32 = lambda t: t.astype(jnp.float32)

    def rn(*shape, dtype=dt):
        return jnp.asarray(rng.standard_normal(shape), dtype)

    def ref_of(fn, *args):
        """The XLA reference ``fn`` in float32 at highest matmul precision
        on the kernel's own (bf16-valued) inputs."""
        up = [f32(a) if jnp.issubdtype(a.dtype, jnp.floating) else a
              for a in args]
        with jax.default_matmul_precision("highest"):
            return jax.jit(fn)(*up)

    def agree(name, fn, args, ref, names, exact=False):
        """Lower ``fn`` (compile evidence), run it, hold the result (a
        pytree matching ``ref``) to the bound."""
        t0 = time.perf_counter()
        jitted = jax.jit(fn)
        require_kernels(leg, name, jitted.lower(*args), names, on_chip)
        got = jax.block_until_ready(jitted(*args))
        worst = 0.0
        for g, r in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
            check(g.shape == r.shape,
                  f"{name}: shape {g.shape} vs reference {r.shape}")
            check(bool(jnp.all(jnp.isfinite(f32(g)))), f"{name}: non-finite")
            if exact:
                check(bool(jnp.all(g == r)), f"{name}: differs from its "
                      f"reference in {int(jnp.sum(g != r))} places")
                continue
            err = float(jnp.max(jnp.abs(f32(g) - f32(r))))
            scale = float(jnp.max(jnp.abs(f32(r))))
            worst = max(worst, err / max(scale, 1e-30))
            check(err <= rtol * scale, f"{name}: max |kernel - reference| "
                  f"{err:.4g} > {rtol} x max|reference| {scale:.4g}")
        say(leg, f"{name}: agrees with its XLA reference "
            f"({'bitwise' if exact else f'rel err {worst:.2e} <= {rtol}'}, "
            f"{time.perf_counter() - t0:.1f}s)")

    # ---- flash attention, forward and backward
    q, k, v = rn(B, S, H, D), rn(B, S, H, D), rn(B, S, H, D)
    scale = 1.0 / D ** 0.5
    sq = lambda fn: (lambda *a: jnp.sum(f32(fn(*a)) ** 2))
    fl = lambda q, k, v: flash_attention(q, k, v, causal=True)
    rf = lambda q, k, v: reference_attention(q, k, v, True, scale)
    agree("flash_attention fwd", fl, (q, k, v), ref_of(rf, q, k, v),
          ["flash_attention_fwd"])
    agree("flash_attention bwd", jax.grad(sq(fl), argnums=(0, 1, 2)),
          (q, k, v), ref_of(jax.grad(sq(rf), argnums=(0, 1, 2)), q, k, v),
          ["flash_attention_bwd_dq", "flash_attention_bwd_dkv"])

    # ---- decode attention: dense and paged, s=1 and s=4, bf16 and int8
    bs = 32                       # a kv block both bf16 and int8 can DMA
    T = S // bs
    perm = rng.permutation(B * T).astype(np.int32)
    table = jnp.asarray(perm.reshape(B, T))

    def to_pool(x):               # [B, S, f] rows -> [B*T, bs, f] by table
        blocks = np.asarray(x).reshape(B * T, bs, -1)
        pool = np.empty_like(blocks)
        pool[perm] = blocks
        return jnp.asarray(pool)

    kc, vc = rn(B, S, H * D), rn(B, S, H * D)
    kq, ks = quantize_kv(kc)
    vq, vs = quantize_kv(vc)
    for s in (1, 4):
        qs = rn(B, s, H, D)
        fills = jnp.asarray(rng.integers(s + 1, S, (B,)), jnp.int32)
        ref = ref_of(lambda q, k, v, n: masked_cache_attention(
            q, k.reshape(B, S, H, D), v.reshape(B, S, H, D), n - s, scale),
            qs, kc, vc, fills)
        ref8 = ref_of(lambda q, k, v, a, b, n: masked_cache_attention(
            q, dequantize_kv(k, a, jnp.float32).reshape(B, S, H, D),
            dequantize_kv(v, b, jnp.float32).reshape(B, S, H, D), n - s,
            scale), qs, kq, vq, ks, vs, fills)
        agree(f"decode_attention s={s} {jnp.dtype(dt).name}",
              lambda q, k, v, n: decode_attention(q, k, v, n, scale=scale),
              (qs, kc, vc, fills), ref, ["decode_attention"])
        agree(f"decode_attention s={s} int8",
              lambda q, k, v, n, a, b: decode_attention(
                  q, k, v, n, scale=scale, k_scale=a, v_scale=b),
              (qs, kq, vq, fills, ks[..., 0], vs[..., 0]), ref8,
              ["decode_attention"])
        agree(f"paged_decode_attention s={s} {jnp.dtype(dt).name} "
              f"block={bs}",
              lambda q, k, v, t, n: paged_decode_attention(
                  q, k, v, t, n, scale=scale, impl="pallas"),
              (qs, to_pool(kc), to_pool(vc), table, fills), ref,
              ["paged_decode_attention"])
        agree(f"paged_decode_attention s={s} int8 block={bs}",
              lambda q, k, v, t, n, a, b: paged_decode_attention(
                  q, k, v, t, n, scale=scale, impl="pallas", k_scale=a,
                  v_scale=b),
              (qs, to_pool(kq), to_pool(vq), table, fills,
               to_pool(ks)[..., 0], to_pool(vs)[..., 0]), ref8,
              ["paged_decode_attention"])

    # ---- the default decode read ("auto" where heads are whole 128-lane
    # rows, which this model's heads of 64 are not): each lane's live
    # blocks of the layer-stacked rank-4 leaves at a traced layer index,
    # one lane masked (fill past S)
    Ll, Hl, Dl = 2, 16, 128
    kl, vl = rn(Ll, B, S, Hl, Dl), rn(Ll, B, S, Hl, Dl)
    ql = rn(B, 1, Hl, Dl)
    fills = np.asarray(rng.integers(1, S + 1, (B,)), np.int32)
    fills[0], fills[-1] = S, S + 1
    live = jnp.asarray(fills <= S)[:, None, None, None]
    agree("live_decode_attention layer-stacked rows",
          lambda q, k, v, n, i: jnp.where(
              live, live_decode_attention(q, [(k, v, n)], i), 0),
          (ql, kl, vl, jnp.asarray(fills), jnp.int32(1)),
          ref_of(lambda q, k, v, n: jnp.where(live, masked_cache_attention(
              q, k[1], v[1], n - 1, 1.0 / Dl ** 0.5), 0),
              ql, kl, vl, jnp.asarray(fills)),
          ["decode_attention_live"])

    # ---- the latent block's decode read (models/mla.py): each lane's live
    # blocks of the ONE layer-stacked leaf whose rows are key and value of
    # every head, the value the row's first columns; the same fills
    rowl, rl = 256, 128
    lat, qrow = rn(Ll, B, S, rowl), rn(B, Hl, rowl)
    seen = jnp.arange(S)[None, None, :] < jnp.asarray(fills)[:, None, None]

    def absorbed(q, rows):
        p = jax.nn.softmax(jnp.where(
            seen, jnp.einsum("bhc,btc->bht", q, rows) / 16.0, -1e10), -1)
        return jnp.where(live[:, 0], jnp.einsum("bht,btc->bhc", p,
                                                rows[..., :rl]), 0)
    agree("live_latent_attention layer-stacked rows",
          lambda q, leaf, n, i: jnp.where(live[:, 0], live_latent_attention(
              q, leaf, n, i, 1 / 16.0, rl), 0),
          (qrow, lat, jnp.asarray(fills), jnp.int32(1)),
          ref_of(lambda q, leaf: absorbed(q, leaf[1]), qrow, lat),
          ["mla_decode_attention_live"])

    # ---- sampling epilogue: first-index argmax and the kept sets are
    # exact by construction. The references run under jit like the kernel:
    # XLA rewrites x / temperature the same way on both sides.
    logits = rn(B, V, dtype=jnp.float32)
    gumbel = jax.random.gumbel(jax.random.PRNGKey(SEED), (B, V), jnp.float32)
    ref_filter = jax.jit(lambda x: filter_logits(x, 0.7, 8, 0.9))
    agree("sampling greedy", lambda x: fused_sample(x, None, 0.0, None, None),
          (logits,), jnp.argmax(logits, axis=-1).astype(jnp.int32),
          ["sampling"], exact=True)
    agree("sampling top-k/top-p filter",
          lambda x: threshold_filter_logits(x, 0.7, 8, 0.9), (logits,),
          ref_filter(logits), ["sampling"], exact=True)
    agree("sampling top-k/top-p gumbel draw",
          lambda x, g: fused_sample(x, g, 0.7, 8, 0.9), (logits, gumbel),
          jax.jit(lambda x, g: jnp.argmax(ref_filter(x) + g, axis=-1).astype(
              jnp.int32))(logits, gumbel), ["sampling"], exact=True)

    # ---- layer_norm, softmax, bias_gelu: forward and backward
    x = rn(B, S, cfg.d_model)
    gam = rn(cfg.d_model, dtype=jnp.float32) + 1.0
    bet = rn(cfg.d_model, dtype=jnp.float32)
    agree("layer_norm fwd", layer_norm, (x, gam, bet),
          ref_of(layer_norm_reference, x, gam, bet), ["layer_norm_fwd"])
    agree("layer_norm bwd", jax.grad(sq(layer_norm), argnums=(0, 1, 2)),
          (x, gam, bet),
          ref_of(jax.grad(sq(layer_norm_reference), argnums=(0, 1, 2)),
                 x, gam, bet), ["layer_norm_bwd"])

    sc = rn(sz["softmax_batch"], H, S, S)
    sm = lambda x: fused_softmax(x, True)
    smr = lambda x: softmax_reference(x, True)
    agree("softmax causal fwd", sm, (sc,), ref_of(smr, sc), ["softmax_fwd"])
    agree("softmax causal bwd", jax.grad(sq(sm)), (sc,),
          ref_of(jax.grad(sq(smr)), sc), ["softmax_bwd"])

    hx, hb = rn(B, S, cfg.d_ff), rn(cfg.d_ff)
    agree("bias_gelu fwd", bias_gelu, (hx, hb),
          ref_of(bias_gelu_reference, hx, hb), ["bias_gelu_fwd"])
    agree("bias_gelu bwd", jax.grad(sq(bias_gelu), argnums=(0, 1)), (hx, hb),
          ref_of(jax.grad(sq(bias_gelu_reference), argnums=(0, 1)), hx, hb),
          ["bias_gelu_bwd"])

    # ---- by name, outside the gate: the default paged block with int8
    from deepspeed_tpu.ops.pallas import KernelUnsupported
    try:
        paged_decode_attention(
            rn(B, 1, H, D), jnp.zeros((B * S // 16, 16, H * D), jnp.int8),
            jnp.zeros((B * S // 16, 16, H * D), jnp.int8),
            jnp.zeros((B, S // 16), jnp.int32), jnp.ones((B,), jnp.int32),
            impl="pallas", k_scale=jnp.ones((B * S // 16, 16)),
            v_scale=jnp.ones((B * S // 16, 16)))
    except KernelUnsupported as e:
        say(leg, f"asked by name outside its gate, raises: {e}")
    else:
        raise SmokeFailure("paged int8 decode at kv block 16 neither ran "
                           "the kernel nor raised")


# --------------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearsal", action="store_true",
                    help="CPU rehearsal at toy size (Pallas interpreter); "
                    "not a chip check")
    args = ap.parse_args(argv)
    t_start = time.perf_counter()

    import jax
    import jaxlib
    try:
        import libtpu
        libtpu_version = getattr(libtpu, "__version__", "unknown")
    except ImportError:
        libtpu_version = "not installed"
    devices = jax.devices()
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    say("env", f"jax={jax.__version__} jaxlib={jaxlib.__version__} "
        f"libtpu={libtpu_version} platform={dev.platform} "
        f"device_kind={dev.device_kind!r} count={len(devices)}")
    if args.rehearsal:
        if dev.platform != "cpu":
            print("chip_smoke.py --rehearsal is the CPU rehearsal; on a "
                  "chip run it without the flag", file=sys.stderr)
            return 2
        say("env", "REHEARSAL: toy size on the CPU, Pallas kernels in the "
            "interpreter — this is not a chip check")
    elif dev.platform != "tpu":
        print(f"chip_smoke.py needs a TPU and JAX found {device}; it does "
              f"not carry on without one (CPU rehearsal: --rehearsal)",
              file=sys.stderr)
        return 1
    on_chip = dev.platform == "tpu"

    from deepspeed_tpu.ops.op_builder import get_native_lib
    from deepspeed_tpu.utils.platform import (CACHE_ENV, CompileCacheCounter,
                                              enable_compile_cache)
    cache_dir = enable_compile_cache()
    counter = CompileCacheCounter()
    say("env", f"compile cache at {cache_dir} "
        f"({CACHE_ENV} {'set' if os.environ.get(CACHE_ENV) else 'not set'}; "
        f"{len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0} "
        f"entries before this run)")
    say("env", f"native={get_native_lib() is not None} (csrc/ host library "
        f"behind cpu_adam and aio; the offload paths are outside this smoke)")

    cfg = model_config(args.rehearsal)
    sz = sizes(args.rehearsal)
    n = len(devices)
    trainer_leg(cfg, sz, devices, counter, zero_stage=1, tp=1,
                on_chip=on_chip)
    if n >= 4 and n % 2 == 0:
        trainer_leg(cfg, sz, devices, counter, zero_stage=3, tp=2,
                    on_chip=on_chip)
    else:
        say("trainer", f"{n} device(s): the dp x tp ZeRO-3 leg needs four")

    from deepspeed_tpu.models.gpt import GPT
    params = GPT(cfg).init(jax.random.PRNGKey(SEED),
                           np.zeros((1, 8), np.int32))["params"]
    server_leg(cfg, sz, params, counter, megakernel=False, on_chip=on_chip)
    if on_chip and n > 1:
        megakernel_refuses_a_mesh(cfg, sz, params)
    else:
        server_leg(cfg, sz, params, counter, megakernel=True,
                   on_chip=on_chip)
    kernel_leg(cfg, sz, on_chip)

    total = counter.snapshot()
    say("done", f"all legs passed in {time.perf_counter() - t_start:.0f}s; "
        f"persistent cache over the run: {total} "
        f"({'hits' if total['hits'] else 'no hits'}: "
        f"{'warm' if total['hits'] > total['writes'] else 'cold'} cache)")
    result = {"ok": True, "device": device}
    if args.rehearsal:
        result["rehearsal"] = True
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
