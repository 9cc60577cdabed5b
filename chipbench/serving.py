"""What the two serving drivers share: bringing the server up, warming every
shape its traffic can reach, and holding it to the float32 reference.

The server is the program's own: ``ds.init_inference`` -> ``ServingEngine``
-> ``ServingFrontend``, every keyword argument from the configuration's
``engine`` group, every path flag at its default.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List

import numpy as np

from chipbench import flops, reference, spec
from chipbench.client import ClosedLoop
from chipbench.traffic import PlannedRequest

# bf16 compute against the float32 reference given the same bf16-rounded
# weights. bf16 keeps 8 significant bits; matmuls accumulate in float32, so
# the error is the rounding of each layer's activations carried through the
# depth. PR 21 measured max |bf16 - f32| = 0.052 on logits spanning +-5.2
# at 12 layers of 768; wider layers average more terms and 16 layers carry
# a little more. 0.25 is ~2.5% of the logit range here; a wrong mask, a
# stale cache row or a mis-scaled head moves logits by O(1), and fp8 or
# int8 arithmetic would not stay inside it.
LOGIT_ATOL = 0.25
# a greedy token is the argmax of the server's own bf16 logits; under the
# reference it can trail the reference argmax by the error on two logits
TOKEN_GAP_ATOL = 2 * LOGIT_ATOL
LOGIT_STEPS = 4


def cache_path_logits(module, params, prompts, steps: int):
    """Prefill then ``steps`` greedy decode steps through the KV cache, the
    way ServingEngine's programs drive the model (padded prefill, per-row
    write cursors). Returns the logits that chose each of the first
    ``steps + 1`` tokens, [n, steps + 1, V] float32, and those tokens,
    [n, steps + 1]. (After chip_smoke.py, PR 21.)"""
    import jax
    import jax.numpy as jnp

    n = len(prompts)
    lens = np.asarray([len(p) for p in prompts], np.int32)
    ids = np.zeros((n, int(max(lens))), np.int32)
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
    out, toks = [np.asarray(last, np.float32)], []
    for j in range(steps):
        toks.append(out[-1].argmax(axis=-1).astype(np.int32))
        last, cache = decode(params, cache, jnp.asarray(toks[-1]),
                             jnp.asarray(lens + j))
        out.append(np.asarray(last, np.float32))
    toks.append(out[-1].argmax(axis=-1).astype(np.int32))
    return np.stack(out, axis=1), np.stack(toks, axis=1)


class Server:
    """The server under test plus what the benchmark knows about it."""

    def __init__(self, ctx):
        import jax
        import deepspeed_tpu as ds
        from deepspeed_tpu import telemetry
        from deepspeed_tpu.models.gpt import GPT, GPTConfig
        from deepspeed_tpu.serving.engine import ServingEngine

        self.ctx = ctx
        config = ctx.cell["config"]
        self.config = config
        self.cfg = GPTConfig(**spec.gpt_config_kwargs(config))
        self.srv_kw = dict(config["engine"]["serving_engine"])
        self.fe_kw = dict(config["engine"]["frontend"])
        self.correct = True
        if ctx.trace:
            telemetry.enable()      # spans are read in the traced run only

        # ---- weights: on the device from the seed, one jitted call, in
        # the dtype they are served in
        model = GPT(self.cfg)
        ids0 = np.zeros((1, 8), np.int32)
        params = jax.jit(lambda k: model.init(k, ids0)["params"])(
            jax.random.PRNGKey(ctx.seed % (2 ** 31)))
        n_params = sum(int(p.size) for p in jax.tree.leaves(params))
        self._check(n_params == flops.param_count(config),
                    f"{n_params:,} parameters on the device, flops.py "
                    f"counts {flops.param_count(config):,}")
        self.inference = ds.init_inference(model, model_parameters=params,
                                           dtype=self.cfg.dtype)
        del params

        # ---- logits, before the arena takes its memory: prefill and four
        # decode steps through the cache against the reference's full
        # forward over prompt + the reference's own greedy tokens
        self._check_logits(model)

        from chipbench.harness import memory_line
        ctx.say("weights and reference check: " + memory_line(ctx.devices))
        self.engine = ServingEngine(engine=self.inference, **self.srv_kw)
        ctx.say("arena built: " + memory_line(ctx.devices))
        self.buckets = list(self.engine._buckets)
        self.vocab = self.cfg.vocab_size

    def _check(self, ok: bool, what: str) -> None:
        self.ctx.say(("ok: " if ok else "FAILED: ") + what)
        self.correct &= bool(ok)

    def _check_logits(self, model) -> None:
        import jax.numpy as jnp
        rng = np.random.default_rng([self.ctx.seed, 0x10617])
        lo = min(5, self.cfg.max_seq_len // 8)
        hi = min(40, self.cfg.max_seq_len // 3)
        prompts = [rng.integers(0, self.cfg.vocab_size, (n,)).astype(np.int32)
                   for n in (lo, hi)]
        steps = LOGIT_STEPS
        got, toks = cache_path_logits(model, self.inference.params, prompts,
                                      steps)
        # ONE full forward of the reference over prompt + those tokens
        # (causal: the padding behind a row changes nothing before it)
        full = np.zeros((len(prompts), hi + steps + 1), np.int32)
        for i, p in enumerate(prompts):
            full[i, :len(p)] = p
            full[i, len(p):len(p) + steps + 1] = toks[i]
        ref = np.asarray(reference.reference_logits(
            self.config, self.inference.params, jnp.asarray(full)))
        refs = [ref[i, len(p) - 1:len(p) + steps]     # row j chose token j
                for i, p in enumerate(prompts)]
        worst = max(float(np.max(np.abs(got[i] - refs[i])))
                    for i in range(len(prompts)))
        span = (min(float(r.min()) for r in refs),
                max(float(r.max()) for r in refs))
        self._check(np.isfinite(got).all() and worst <= LOGIT_ATOL,
                    f"prefill + {steps} decode steps through the cache vs "
                    f"the float32 reference: max |logit diff| {worst:.4f} "
                    f"(tolerance {LOGIT_ATOL}; reference logits span "
                    f"[{span[0]:.2f}, {span[1]:.2f}])")
        self._ref_prompts, self._ref_rows, self._ref_toks = \
            prompts, refs, toks

    # ------------------------------------------------------------ warm-up
    def warm_prefill_family(self) -> None:
        """Every ``(n, bucket)`` prefill program one admission can reach: n
        requests of a bucket's length submitted together, one token each,
        through the engine's own ``submit``/``pump`` before the front end
        owns it."""
        eng = self.engine
        depth = int(self.fe_kw.get("feed_depth") or eng.max_batch)
        rng = np.random.default_rng([self.ctx.seed, 0x3A93])
        t0 = time.perf_counter()
        before = self.ctx.watch.programs()
        for bucket in self.buckets:
            for n in range(1, min(depth, eng.max_batch) + 1):
                reqs = [eng.submit(rng.integers(0, self.vocab, (bucket,)
                                                ).astype(np.int32),
                                   max_new_tokens=1) for _ in range(n)]
                while eng.scheduler.has_work() or eng.chunk_in_flight:
                    eng.pump()
                if not all(r.status == "done" for r in reqs):
                    raise RuntimeError(
                        f"warm-up prefill n={n} bucket={bucket}: "
                        f"{[r.status for r in reqs]}")
        eng.scheduler.finished.clear()
        shapes = sorted(eng._prefill_shapes)
        self.ctx.say(f"prefill family warmed: {len(shapes)} (n, bucket) "
                     f"shapes over buckets {self.buckets} x n<={depth}, "
                     f"{self.ctx.watch.programs() - before} programs built, "
                     f"{time.perf_counter() - t0:.1f}s")
        self.warm_shapes = set(shapes)
        self.warm_decode_family()

    def warm_decode_family(self) -> None:
        """The serve loop patches its device-carried lane state with eager
        scatters whose index length is the number of lanes admitted or
        retired since the last chunk (``ServingEngine._device_state``): one
        small program for every count. Light traffic only ever meets the
        small counts, so a burst inside the window would build the rest
        there. Waves through the engine's own ``submit``/``pump`` meet every
        count: beside one long request that keeps a chunk in flight, k
        requests are admitted together (k up to ``feed_depth``) and k
        requests retire in the same chunk (k up to ``max_batch - 1``; a
        second, shorter wave one pump later ends with the first)."""
        eng = self.engine
        lanes, chunk = eng.max_batch, eng.decode_chunk
        depth = min(int(self.fe_kw.get("feed_depth") or lanes), lanes - 1)
        rng = np.random.default_rng([self.ctx.seed, 0xDEC0])
        length = min(self.buckets[0], 8)

        def submit(n, new_tokens):
            return [eng.submit(rng.integers(0, self.vocab, (length,)
                                            ).astype(np.int32),
                               max_new_tokens=new_tokens) for _ in range(n)]

        t0 = time.perf_counter()
        before = self.ctx.watch.programs()
        waves = lanes - 1
        runner = submit(1, min(eng.max_seq_len - length,
                               (4 * waves + 8) * chunk))[0]
        eng.pump()
        eng.pump()
        for k in range(1, lanes):
            first = submit(min(k, depth), 1 + (2 if k > depth else 1) * chunk)
            eng.pump()
            second = submit(k - depth, 1 + chunk) if k > depth else []
            for _ in range(8):
                if all(r.status == "done" for r in first + second):
                    break
                eng.pump()
            else:
                raise RuntimeError(f"warm-up wave of {k} did not retire: "
                                   f"{[r.status for r in first + second]}")
        eng.cancel(runner)
        while eng.scheduler.has_work() or eng.chunk_in_flight:
            eng.pump()
        eng.scheduler.finished.clear()
        self.ctx.say(f"decode family warmed: waves of 1..{lanes - 1} lanes "
                     f"admitted (<= {depth} together) and retired together, "
                     f"{self.ctx.watch.programs() - before} programs built, "
                     f"{time.perf_counter() - t0:.1f}s")

    def frontend(self):
        from deepspeed_tpu.serving.frontend.frontend import ServingFrontend
        fe = ServingFrontend(self.engine, trace_keep_last=8,
                             **self.fe_kw)
        self.queue_waits: Dict[int, float] = {}
        fe.tracing.add_listener(self._on_trace)
        return fe

    def _on_trace(self, trace) -> None:
        if trace.queue_wait_s is not None:
            self.queue_waits[trace.uid] = trace.queue_wait_s

    def warm_traffic(self, fe, plan: List[PlannedRequest], clients: int,
                     *, seconds: float) -> ClosedLoop:
        """Seeded traffic through the front end for ``seconds``: a fixed
        time, so that set-up is the same from every seed. The two families
        above have built every program by now (0 built here in every chip
        run of PR 23); should one be built all the same, the traffic goes
        on until a second after the last. Returns the loop, still full."""
        quiet_s, limit_s = 1.0, 120.0
        loop = ClosedLoop(fe, plan, clients)
        t0 = time.perf_counter()
        state = {"n": self.ctx.watch.programs(), "since": t0 - quiet_s}

        def steady() -> bool:
            now = time.perf_counter()
            n = self.ctx.watch.programs()
            if n != state["n"]:
                state["n"], state["since"] = n, now
            if now - t0 > limit_s:
                raise RuntimeError(f"still building programs after "
                                   f"{limit_s}s of warm-up traffic")
            return now - t0 >= seconds and now - state["since"] >= quiet_s

        before = self.ctx.watch.programs()
        loop.run_until(steady)
        self.ctx.say(f"warm-up traffic: {time.perf_counter() - t0:.1f}s, "
                     f"{len(loop.client.finished)} requests finished, "
                     f"{self.ctx.watch.programs() - before} programs built")
        return loop

    # ---------------------------------------------------------- checking
    def check_emitted_tokens(self, fe) -> None:
        """The two reference prompts through the REAL server: each emitted
        token's reference logit must be within TOKEN_GAP_ATOL of the
        reference argmax (logits, not tokens: with random weights the
        largest logit changes on rounding)."""
        handles = [fe.submit(p, max_new_tokens=LOGIT_STEPS + 1)
                   for p in self._ref_prompts]
        worst = 0.0
        for h, rows, path in zip(handles, self._ref_rows, self._ref_toks):
            h.result(timeout=120.0)
            toks = h.tokens
            if h.status != "done" or len(toks) != LOGIT_STEPS + 1:
                self._check(False, f"reference prompt: status {h.status}, "
                            f"{len(toks)} tokens")
                return
            # reference row j is conditioned on the cache path's tokens
            # before j: it judges the server's token j while the server has
            # followed that path
            for j, tok in enumerate(toks):
                worst = max(worst, float(rows[j].max() - rows[j][tok]))
                if tok != int(path[j]):
                    break
        self._check(worst <= TOKEN_GAP_ATOL,
                    f"tokens the server emitted for the reference prompts "
                    f"trail the reference argmax by at most {worst:.4f} "
                    f"(tolerance {TOKEN_GAP_ATOL})")

    def check_window(self, tracked, built: List[str]) -> None:
        """Every finished request has exactly its ``max_new_tokens``; no
        program was built inside the window; no prefill shape outside the
        warmed family appeared."""
        bad = [t for t in tracked if t.status is not None and not t.ok]
        self._check(not bad, f"{len(tracked) - len(bad)} of {len(tracked)} "
                    f"requests ended done with exactly their max_new_tokens"
                    + (f"; first bad: status {bad[0].status}, "
                       f"{bad[0].n_tokens}/{bad[0].plan.max_new_tokens}"
                       if bad else ""))
        self._check(not built, f"{len(built)} programs built inside the "
                    f"measured window" + (f": {built}" if built else ""))
        new = set(self.engine._prefill_shapes) - self.warm_shapes
        self._check(not new, f"prefill shapes outside the warmed family: "
                    f"{sorted(new)}")

    # ---------------------------------------------------------- counters
    def counters(self) -> Dict[str, float]:
        m = self.engine.metrics
        return {"tokens_out": m.tokens_out, "chunks": m.decode_steps,
                "prefill_prompt_tokens": m.prefill_prompt_tokens,
                "prefill_padded_tokens": m.prefill_padded_tokens,
                "rejected": m.rejected, "requests_done": m.requests_done}

    def span_totals(self) -> Dict[str, Dict[str, float]]:
        from deepspeed_tpu import telemetry
        return {k: {"count": v["count"], "total_s": v["total_s"]}
                for k, v in telemetry.get_runtime().span_stats().items()}

    def facts(self) -> Dict[str, Any]:
        eng = self.engine
        return {"max_batch": eng.max_batch, "decode_chunk": eng.decode_chunk,
                "arena_positions": eng.max_batch * eng.max_seq_len}


def delta(after: Dict[str, float], before: Dict[str, float]) -> Dict[str, float]:
    return {k: after[k] - before.get(k, 0) for k in after}


def span_delta(after, before) -> Dict[str, Dict[str, float]]:
    out = {}
    for name, a in after.items():
        b = before.get(name, {"count": 0, "total_s": 0.0})
        out[name] = {"count": a["count"] - b["count"],
                     "total_s": a["total_s"] - b["total_s"]}
    return out
