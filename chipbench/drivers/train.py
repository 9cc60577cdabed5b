"""Mix kind ``train``: whole optimizer steps of one job on the trainer.

Set-up (counted in ``setup_s``): weights on the device from the seed in one
jitted call, the float32 reference of a sample micro-batch (per-token
negative log-likelihoods and, for its first row, the norm of the gradient),
the engine, the engine held to that reference, then steps until two in a row
build no program. Window: ``--seconds`` of steps, the host never more than
one step ahead of the device, ended by ``block_until_ready``. With
``--trace 1`` a few more steps follow under the profiler.

What ``correct`` holds the trainer to, all outside the window:

* forward: the engine's own ``eval_batch`` with a one-hot ``loss_mask``
  returns ONE token's negative log-likelihood; ``NLL_PROBES`` of them,
  first and last position included, each within ``NLL_ATOL`` of the
  reference. (A mean over thousands of random targets is ln V + var/2 for
  any logits of the same spread, and would pass a wrong mask.) The mean
  over the sample too, within ``LOSS_ATOL``.
* backward: the first step is given one row repeated, so the gradient the
  engine averages over rows, micro-steps and chips is that row's; its
  global norm (``get_global_grad_norm``) within ``GNORM_RTOL`` of the
  reference's, and the step's loss within ``LOSS_ATOL`` of the row's.
* optimizer: after that step the row's loss is lower than before it.
* every loss finite, the layout's share of state on every chip, and no
  program built inside the window.
"""

from __future__ import annotations

import time

import numpy as np

from chipbench import flops, reference, spec, traffic
from chipbench.harness import (TracedStretch, annotate, device_shares,
                               memory_line)

# The reference is given the weights rounded to bf16 as the engine's compute
# copy is, so what is compared is the arithmetic: bf16 matmuls accumulated in
# float32 against float32 at highest precision. Each tolerance is a few times
# the worst read on the chip (my chip runs, PR 23: three runs of train-1chip,
# one of train-zero3-4chip, seeds 3000000011 to 3000000047).
#
# One token's NLL is a log-sum-exp less one logit, so its error is about one
# logit's: 0.005 to 0.0145 read over 32 probes, the NLL itself spanning 7 to
# 15.6. A wrong mask, a dropped layer or a stale position moves a token's
# NLL by O(1) (its spread over tokens is ~1; a layer missing moved four in
# five tokens by over 0.15 in the tests), fp8 products (3 bits of mantissa
# against bf16's 8) by ~0.3.
NLL_ATOL = 0.06
NLL_PROBES = 8
# a mean over >= 2k tokens: per-token rounding averages out and what remains
# is bias; 0.00002 to 0.00016 read at a loss of 11.3 (the CPU rehearsal's 63
# tokens a row average less and read 0.0016)
LOSS_ATOL = 0.005
# the norm of a gradient of ~1e9 bf16-computed terms against float32: the
# errors are independent and the norm averages them; 0.04 to 0.06 % read
GNORM_RTOL = 0.005


def run(ctx):
    import jax
    import deepspeed_tpu as ds
    from deepspeed_tpu.models.gpt import GPT, GPTConfig, lm_loss_fn
    from deepspeed_tpu.runtime.dataloader import (DeepSpeedDataLoader,
                                                  RepeatingLoader)

    config, mix = ctx.cell["config"], ctx.cell["mix"]
    eng_cfg = config["engine"]
    n = len(ctx.devices)
    kw = spec.gpt_config_kwargs(config)
    seq = int(mix["seq_len"])
    if seq > kw["max_seq_len"]:
        raise spec.SpecError(f"mix seq_len {seq} > the model's "
                             f"{kw['max_seq_len']} positions")
    cfg = GPTConfig(**dict(kw, max_seq_len=seq))
    mesh = dict(eng_cfg.get("mesh", {}))
    dp = int(mesh.get("dp", 1))
    micro, gas = int(mix["micro_batch_per_chip"]), \
        int(mix["gradient_accumulation_steps"])
    rows = micro * dp
    tokens_per_step = rows * gas * seq
    model = GPT(cfg)

    # ---- weights: one jitted call, on the device(s), from the seed. Over
    # several chips each leaf is born divided along its first axis that
    # divides, so that no chip ever holds the whole float32 model twice
    # (set-up would otherwise own the memory peak, not training).
    ids0 = np.zeros((1, 8), np.int32)
    init = lambda k: model.init(k, ids0)["params"]
    key = jax.random.PRNGKey(ctx.seed % (2 ** 31))
    if n == 1:
        params = jax.jit(init)(key)
    else:
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        line = Mesh(np.array(ctx.devices), ("all",))

        def divided(leaf):
            axis = next((i for i, d in enumerate(leaf.shape) if d % n == 0),
                        None)
            spec = [None] * len(leaf.shape)
            if axis is not None:
                spec[axis] = "all"
            return NamedSharding(line, P(*spec))

        params = jax.jit(init, out_shardings=jax.tree.map(
            divided, jax.eval_shape(init, key)))(key)
    n_params = sum(int(p.size) for p in jax.tree.leaves(params))
    want = flops.param_count(config)
    ctx.say(f"{n_params:,} parameters on the device (flops.py counts "
            f"{want:,})")
    correct = n_params == want

    # ---- the data: a fresh seeded batch every step, through the loader
    dataset = traffic.train_dataset(ctx.seed, cfg.vocab_size, seq)
    loader = RepeatingLoader(DeepSpeedDataLoader(dataset, batch_size=rows))
    # the sample the reference judges: one micro-batch worth of rows, from
    # beyond what the window can reach, divisible over dp
    n_sample = micro if micro % dp == 0 else rows
    sample = np.stack([dataset[(1 << 19) + i]["input_ids"]
                       for i in range(n_sample)])

    # ---- the reference, before the engine takes its memory: a row at a
    # time and a layer at a time on ONE chip, pulling each layer from the
    # tree where it lies (float32 [rows, S, V] logits at once would be
    # gigabytes, and a whole copy on one chip would own the memory peak)
    import jax.numpy as jnp
    rounded = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)  # as
    # the engine's compute copy is, under ds_config's bf16
    dev0 = ctx.devices[0]
    row_nll, ref_gnorm = reference.reference_nll_and_grad_norm(
        config, rounded, sample[:1], device=dev0)
    ref_nll = np.concatenate(
        [np.asarray(row_nll)]
        + [np.asarray(reference.reference_token_nll(
            config, rounded, sample[i:i + 1], device=dev0))
           for i in range(1, n_sample)])              # [n_sample, seq - 1]
    ref_gnorm = float(ref_gnorm)
    del rounded, row_nll
    ctx.say("reference taken: " + memory_line(ctx.devices))

    ds_config = {"train_micro_batch_size_per_gpu": micro,
                 "gradient_accumulation_steps": gas,
                 "bf16": {"enabled": True},
                 "zero_optimization": {"stage": int(eng_cfg["zero_stage"])},
                 "optimizer": mix["optimizer"],
                 "steps_per_print": 1_000_000_000}
    extra_mesh = {k: v for k, v in mesh.items() if k != "dp" and v > 1}
    if extra_mesh:
        ds_config["mesh"] = extra_mesh
    engine, *_ = ds.initialize(model=model, model_parameters=params,
                               loss_fn=lm_loss_fn, config=ds_config)
    del params
    got_mesh = {k: v for k, v in dict(engine.mesh.shape).items() if v > 1}
    want_mesh = {k: v for k, v in mesh.items() if v > 1}
    if got_mesh != want_mesh:
        raise RuntimeError(f"engine mesh {got_mesh} is not the "
                           f"configuration's {want_mesh}")
    ctx.say("engine built: " + memory_line(ctx.devices))

    def check(ok, what):
        nonlocal correct
        ctx.say(("ok: " if ok else "FAILED: ") + what)
        correct &= bool(ok)

    def masked_loss(mask):
        """The engine's own eval path (its cast, sharding, kernels and
        loss): sum(nll x mask) / sum(mask)."""
        return float(engine.eval_batch({"input_ids": sample,
                                        "loss_mask": mask}))

    # ---- forward: single tokens' NLL, and the mean
    rng = np.random.default_rng([ctx.seed, 0x9A11])
    spots = [0, seq - 2] + [int(x) for x in
                            rng.integers(1, seq - 2, NLL_PROBES - 2)]
    worst = 0.0
    for k, pos in enumerate(spots):
        mask = np.zeros((n_sample, seq), np.float32)
        mask[k % n_sample, pos] = 1.0
        worst = max(worst, abs(masked_loss(mask)
                               - float(ref_nll[k % n_sample, pos])))
    check(worst <= NLL_ATOL,
          f"{len(spots)} single tokens' NLL through eval_batch vs the "
          f"float32 reference: max |diff| {worst:.4f} (tolerance "
          f"{NLL_ATOL}; the reference's NLL spans "
          f"[{ref_nll.min():.2f}, {ref_nll.max():.2f}])")
    got_loss = masked_loss(np.ones((n_sample, seq), np.float32))
    check(abs(got_loss - float(ref_nll.mean())) <= LOSS_ATOL,
          f"loss of the seeded weights on {n_sample}x{seq} sample tokens: "
          f"{got_loss:.5f}, float32 reference {ref_nll.mean():.5f} "
          f"(tolerance {LOSS_ATOL})")

    # ---- per-device share of parameters and optimizer state
    shares = {"opt": device_shares(engine.state["opt"], ctx.devices),
              "master": device_shares(engine.state["master"], ctx.devices)}
    stage = int(eng_cfg["zero_stage"])
    if dp > 1:
        bound = 1.1 / dp
        ok = max(shares["opt"]) <= bound and min(shares["opt"]) > 0
        if stage == 3:
            ok &= max(shares["master"]) <= bound and min(shares["master"]) > 0
        ctx.say(f"per-device share of bytes (whole = 1.0): optimizer state "
                f"{[round(x, 4) for x in shares['opt']]}, parameters "
                f"{[round(x, 4) for x in shares['master']]} — "
                f"{'as' if ok else 'NOT as'} ZeRO-{stage} over dp={dp} says")
        correct &= ok

    def step():
        with annotate("train_batch"):
            return engine.train_batch(loader)

    # ---- backward and optimizer: one step on the sample's first row
    # repeated (through the same loader, so the step program is the one the
    # window runs); it is also the first warm-up step
    class OneRow:
        def __len__(self):
            return rows

        def __getitem__(self, i):
            return {"input_ids": sample[0]}

    before = ctx.watch.programs()
    t0 = time.perf_counter()
    with annotate("train_batch"):
        row_loss = float(engine.train_batch(iter(RepeatingLoader(
            DeepSpeedDataLoader(OneRow(), batch_size=rows)))))
    got_gnorm = float(engine.get_global_grad_norm())
    ctx.say(f"reference step: {time.perf_counter() - t0:.2f}s, "
            f"{ctx.watch.programs() - before} program(s) built")
    want_row = float(ref_nll[0].mean())
    check(abs(got_gnorm - ref_gnorm) <= GNORM_RTOL * ref_gnorm
          and abs(row_loss - want_row) <= LOSS_ATOL,
          f"a step on one row repeated: gradient norm {got_gnorm:.5f}, "
          f"float32 reference {ref_gnorm:.5f} (tolerance {GNORM_RTOL:.1%}); "
          f"loss {row_loss:.5f}, reference {want_row:.5f}")
    mask = np.zeros((n_sample, seq), np.float32)
    mask[0] = 1.0
    after = masked_loss(mask)
    check(after < want_row,
          f"the row's loss after that optimizer step: {after:.5f} "
          f"(before it {want_row:.5f})")

    # ---- warm-up: until two steps in a row build nothing
    quiet, warm = 0, 0
    losses = [row_loss]
    while quiet < 2:
        before = ctx.watch.programs()
        t0 = time.perf_counter()
        losses.append(float(jax.block_until_ready(step())))
        warm += 1
        built = ctx.watch.programs() - before
        quiet = 0 if built else quiet + 1
        ctx.say(f"warm-up step {warm}: {time.perf_counter() - t0:.2f}s, "
                f"{built} program(s) built, loss {losses[-1]:.4f}")
        if warm > 12:
            raise RuntimeError("the train step still builds programs after "
                               "12 steps")

    # ---- the measured window
    setup_s = time.perf_counter() - ctx.t_start
    at_start = ctx.watch.programs()
    t0 = time.perf_counter()
    steps, prev = 0, None
    while True:
        cur = step()
        steps += 1
        if prev is not None:
            losses.append(float(prev))          # waits for step k-1 only
        prev = cur
        if time.perf_counter() - t0 >= ctx.seconds:
            break
    losses.append(float(jax.block_until_ready(prev)))
    window_s = time.perf_counter() - t0
    built_in_window = ctx.watch.programs() - at_start
    ctx.say("after the window: " + memory_line(ctx.devices))
    finite = bool(np.all(np.isfinite(losses)))
    ctx.say(f"window: {steps} steps of {tokens_per_step} tokens in "
            f"{window_s:.3f}s; programs built inside it: {built_in_window}; "
            f"losses {losses[warm + 1]:.4f} -> {losses[-1]:.4f}")
    correct &= finite and built_in_window == 0
    tokens_per_s = steps * tokens_per_step / window_s

    # ---- the traced stretch: a few more steady steps under the profiler
    summary, outline, trace_steps = None, [], int(mix.get("trace_steps", 3))
    if ctx.trace:
        stretch = TracedStretch(ctx)
        with stretch:
            for _ in range(trace_steps):
                prev = step()
            with annotate("wait"):
                jax.block_until_ready(prev)
        summary, outline = stretch.summary, stretch.outline

    return {
        "correct": bool(correct), "attempted": steps,
        "failed": 0 if finite else int(np.sum(~np.isfinite(losses))),
        "setup_s": setup_s,
        "end_to_end": {"train_tokens_per_s": tokens_per_s},
        "trace": summary, "outline": outline, "spans": {},
        "counters": {"train_tokens_per_s": tokens_per_s,
                     "seq_len": seq, "steps": steps,
                     "chips": n},
    }
