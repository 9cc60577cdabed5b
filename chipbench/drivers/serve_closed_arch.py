"""Mix kind ``serve_closed_arch``: the closed loop of ``serve_closed`` over a
configuration that names its architecture file (``"arch": "<stem>"`` ->
``chipbench/archs/<stem>.py``), so that an architecture which is not
GPT-NeoX comes in as files: the arch file builds the program's model and
holds the reference, the counts from shapes and the comparison's limits
(its docstring lists the interface), and this driver is the same for all.

N clients that each send their next request when their last one ends; the
lanes are full when the window opens and stay full; the number is output
tokens delivered to clients per second of the window. What ``correct``
holds the server to, beyond ``serving.Server``'s window checks: the
parameters on the device equal the arch file's count; prefill and four
decode steps through the cache, the engine's programs' way, against ONE
full forward of the reference over prompt + those tokens, on logits; the
routing rule of the arch file where the model routes; the routing counters
against the count of the reference's OWN routing; the same
``CHECK_PROMPTS`` prompts through the real server, token by token.

**The control of the logit limit** runs through the same comparison:

    python3 chipbench/drivers/serve_closed_arch.py --workload <cell> \
        --seed <n> [<n> ...] [--control float8_e4m3fn] [--rehearsal]

puts the REFERENCE, computed with every matmul operand rounded to the
nearest precision below the configuration's, in the program's place, and
prints one line a seed. It has to say ``"correct": false``: a limit that lets
float8 arithmetic through holds the program to nothing. Exit 0 if every
seed was refused, 1 if one passed.
"""

from __future__ import annotations

import importlib
import os
import sys
import time
from itertools import groupby
from statistics import median

import numpy as np

if __name__ == "__main__":      # the control, run as a script
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))

from chipbench import serving, traffic  # noqa: E402
from chipbench.harness import (TracedStretch, in_thread,  # noqa: E402
                               memory_line, percentile)

LOGIT_STEPS = serving.LOGIT_STEPS
# the check's prompts (serving.Server has two): through the cache against the
# reference on logits, then through the real server
CHECK_PROMPTS = 16


def load_arch(config):
    return importlib.import_module(f"chipbench.archs.{config['arch']}")


def cache_path(module, params, prompts, steps: int):
    """``serving.cache_path_logits`` for a model that may hand out the
    experts its tokens chose beside the logits. Returns the logits that
    chose each of the first ``steps + 1`` tokens ``[n, steps + 1, V]``,
    those tokens ``[n, steps + 1]``, and the program's choice over the
    reference's full forward (prompt + tokens) ``[layers, n, longest prompt
    + steps + 1, k]``, -1 where the program ran no such token (None for a
    model that does not route), with the routing counters the program's own
    function sums over exactly those tokens."""
    import jax
    import jax.numpy as jnp

    n = len(prompts)
    lens = np.asarray([len(p) for p in prompts], np.int32)
    width = int(max(lens))
    ids = np.zeros((n, width), np.int32)
    for i, p in enumerate(prompts):
        ids[i, :len(p)] = p
    count = getattr(module, "routing_counters", None)

    def split(out, live):
        if not isinstance(out, tuple):
            return out, None, None
        if count is None or not isinstance(out[1], dict):
            return out[0], None, None
        return out[0], out[1]["expert_choice"], count(out[1], live)

    @jax.jit
    def prefill(params, ids, lens):
        positions = jnp.arange(ids.shape[1])[None, :]
        out, vc = module.apply({"params": params}, ids, positions=positions,
                               mutable=["cache"])
        logits, choice, counted = split(out, positions < lens[:, None])
        last = jnp.take_along_axis(logits, (lens - 1)[:, None, None],
                                   axis=1)[:, 0]
        return last, vc["cache"], choice, counted

    def with_cursor(cache, positions):
        def leaf(path, x):
            if "cache_index" in jax.tree_util.keystr(path):
                return jnp.broadcast_to(positions.astype(x.dtype),
                                        (x.shape[0], n))
            return x
        return jax.tree_util.tree_map_with_path(leaf, cache)

    @jax.jit
    def decode(params, cache, tokens, positions):
        out, vc = module.apply(
            {"params": params, "cache": with_cursor(cache, positions)},
            tokens[:, None], positions=positions[:, None], mutable=["cache"])
        logits, choice, counted = split(out, jnp.ones((n, 1), bool))
        return logits[:, -1], vc["cache"], choice, counted

    last, cache, choice, counted = prefill(params, jnp.asarray(ids),
                                           jnp.asarray(lens))
    counters = {k: float(v) for k, v in (counted or {}).items()}
    chosen = None
    if choice is not None:
        choice = np.asarray(choice)
        chosen = np.full(choice.shape[:2] + (width + steps + 1,)
                         + choice.shape[3:], -1, np.int32)
        for i in range(n):
            chosen[:, i, :lens[i]] = choice[:, i, :lens[i]]
    out, toks = [np.asarray(last, np.float32)], []
    for j in range(steps):
        toks.append(out[-1].argmax(axis=-1).astype(np.int32))
        last, cache, choice, counted = decode(
            params, cache, jnp.asarray(toks[-1]), jnp.asarray(lens + j))
        out.append(np.asarray(last, np.float32))
        if chosen is not None:
            chosen[:, np.arange(n), lens + j] = np.asarray(choice)[:, :, 0]
            for k, v in counted.items():
                counters[k] += float(v)
    toks.append(out[-1].argmax(axis=-1).astype(np.int32))
    return np.stack(out, axis=1), np.stack(toks, axis=1), chosen, counters


class ArchServer(serving.Server):
    """``serving.Server`` with the model, the count and the reference taken
    from the configuration's arch file."""

    def __init__(self, ctx, control=None):
        """``control``: a dtype name. The reference with every matmul operand
        rounded to it takes the program's place in the logit comparison, and
        nothing is served (the module docstring's control)."""
        import jax
        import deepspeed_tpu as ds
        from deepspeed_tpu import telemetry
        from deepspeed_tpu.serving.engine import ServingEngine

        self.ctx = ctx
        config = self.config = ctx.cell["config"]
        self.arch = load_arch(config)
        self.srv_kw = dict(config["engine"]["serving_engine"])
        self.fe_kw = dict(config["engine"]["frontend"])
        self.correct = True
        if ctx.trace:
            telemetry.enable()      # spans are read in the traced run only

        model = self.arch.build_model(config)
        self.cfg = model.cfg
        params = jax.jit(lambda k: self.arch.init_params(model, k))(
            jax.random.PRNGKey(ctx.seed % (2 ** 31)))
        n_params = sum(int(p.size) for p in jax.tree.leaves(params))
        want = self.arch.param_count(config)
        self._check(n_params == want, f"{n_params:,} parameters on the "
                    f"device, archs/{config['arch']}.py counts {want:,}")
        self.inference = ds.init_inference(model, model_parameters=params,
                                           dtype=self.cfg.dtype)
        del params
        ctx.say("weights: " + memory_line(ctx.devices))
        self._check_logits(model, control)
        ctx.say("reference check: " + memory_line(ctx.devices))
        if control is not None:
            return
        self.engine = ServingEngine(engine=self.inference, **self.srv_kw)
        ctx.say("arena built: " + memory_line(ctx.devices))
        self.buckets = list(self.engine._buckets)
        self.vocab = self.cfg.vocab_size

    def _check_logits(self, model, control=None) -> None:
        import jax.numpy as jnp
        arch, steps = self.arch, LOGIT_STEPS
        rng = np.random.default_rng([self.ctx.seed, 0x10617])
        lo = min(5, self.cfg.max_seq_len // 8)
        hi = min(40, self.cfg.max_seq_len // 3)
        prompts = [rng.integers(0, self.cfg.vocab_size, (n,)).astype(np.int32)
                   for n in np.linspace(lo, hi, CHECK_PROMPTS).astype(int)]
        got, toks, chosen, counted = cache_path(
            model, self.inference.params, prompts, steps)
        # ONE full forward of the reference over prompt + those tokens
        # (causal: the padding behind a row changes nothing before it)
        full = np.zeros((len(prompts), hi + steps + 1), np.int32)
        for i, p in enumerate(prompts):
            full[i, :len(p)] = p
            full[i, len(p):len(p) + steps + 1] = toks[i]
        ref, routing = arch.reference_logits(
            self.config, self.inference.params, full, program_choice=chosen)

        def rows(logits):       # row j chose token j
            logits = np.asarray(logits)
            return [logits[i, len(p) - 1:len(p) + steps]
                    for i, p in enumerate(prompts)]

        refs, what = rows(ref), (f"{len(prompts)} prompts of {lo}-{hi} "
                                 f"tokens, prefill + {steps} decode steps "
                                 f"through the cache")
        if control is not None:
            got = rows(arch.reference_logits(
                self.config, self.inference.params, full,
                program_choice=chosen, lower=jnp.dtype(control).type)[0])
            what = (f"CONTROL, the reference with {control} operands in the "
                    f"program's place, over the same rows")
        worst = max(float(np.max(np.abs(got[i] - refs[i])))
                    for i in range(len(prompts)))
        span = (min(float(r.min()) for r in refs),
                max(float(r.max()) for r in refs))
        self.logit_diff = worst
        self._check(all(np.isfinite(g).all() for g in got)
                    and worst <= arch.LOGIT_ATOL,
                    f"{what} vs the float32 reference: max |logit diff| "
                    f"{worst:.4f} (tolerance {arch.LOGIT_ATOL}; reference "
                    f"logits span [{span[0]:.2f}, {span[1]:.2f}])")
        if control is not None:
            return
        if chosen is not None:
            self._check(
                routing["sets_refused"] == 0,
                f"routing under rounding: {routing['sets_differing']} of "
                f"{routing['sets']} (token, expert layer) sets differ from "
                f"the reference's own, the largest score difference of a "
                f"displaced expert from the one taken instead "
                f"{routing['largest_gap']:.5f} (the reference follows the "
                f"program under {arch.ROUTE_EPS}; {routing['sets_refused']} "
                f"at or over it)")
            held, absent = (int(counted[k])
                            for k in ("pairs_held", "pairs_absent"))
            self._check(
                held + absent == routing["pairs_held"]
                + routing["pairs_absent"]
                and abs(held - routing["pairs_held"])
                <= routing["pairs_swapped"],
                f"routing counters over the check's tokens: the program "
                f"counts {held} pairs on held experts and {absent} on absent "
                f"ones, the reference's own routing "
                f"{routing['pairs_held']} and {routing['pairs_absent']} "
                f"(they may differ by the {routing['pairs_swapped']} experts "
                f"swapped inside epsilon)")
        self._ref_prompts, self._ref_rows, self._ref_toks = \
            prompts, refs, toks

    def warm_decode_family(self) -> None:
        """The chunk program and the one lane-patch program (a patch has one
        shape whatever the number of lanes: nothing to warm per count): two
        requests admitted beside a running one, retired a chunk apart."""
        eng = self.engine
        rng = np.random.default_rng([self.ctx.seed, 0xDEC0])
        t0 = time.perf_counter()
        before = self.ctx.watch.programs()
        length = min(self.buckets[0], 8)
        for new_tokens in (1 + 4 * eng.decode_chunk, 1 + eng.decode_chunk,
                           1 + 2 * eng.decode_chunk):
            eng.submit(rng.integers(0, self.vocab, (length,)
                                    ).astype(np.int32),
                       max_new_tokens=new_tokens)
            eng.pump()
        while eng.scheduler.has_work() or eng.chunk_in_flight:
            eng.pump()
        eng.scheduler.finished.clear()
        self.ctx.say(f"decode family warmed: "
                     f"{self.ctx.watch.programs() - before} programs built, "
                     f"{time.perf_counter() - t0:.1f}s")

    def check_emitted_tokens(self, fe) -> None:
        """``serving.Server.check_emitted_tokens`` over the check's
        ``CHECK_PROMPTS`` prompts and under the arch file's limit: through
        the REAL server (its bucketed prefill programs with several prompts
        to a call, the lane patch, the chunk program over all its lanes with
        a cursor a lane), each emitted token's reference logit within
        ``TOKEN_GAP_ATOL`` of its row's largest. A reference row is
        conditioned on the cache path's tokens before it, so it judges the
        server's token j while the server has followed that path."""
        limit = self.arch.TOKEN_GAP_ATOL
        t0, before = time.perf_counter(), self.ctx.watch.programs()
        handles = [fe.submit(p, max_new_tokens=LOGIT_STEPS + 1)
                   for p in self._ref_prompts]
        worst, judged = 0.0, 0
        for h, rows, path in zip(handles, self._ref_rows, self._ref_toks):
            h.result(timeout=120.0)
            toks = h.tokens
            if h.status != "done" or len(toks) != LOGIT_STEPS + 1:
                self._check(False, f"reference prompt: status {h.status}, "
                            f"{len(toks)} tokens")
                return
            for j, tok in enumerate(toks):
                worst = max(worst, float(rows[j].max() - rows[j][tok]))
                judged += 1
                if tok != int(path[j]):
                    break
        self._check(worst <= limit,
                    f"{len(handles)} reference prompts through the real "
                    f"server: the {judged} tokens it emitted on the cache "
                    f"path (of {len(handles) * (LOGIT_STEPS + 1)}) trail "
                    f"their reference rows' largest logit by at most "
                    f"{worst:.4f} (tolerance {limit}); "
                    f"{self.ctx.watch.programs() - before} programs built, "
                    f"{time.perf_counter() - t0:.1f}s")

    def counters(self):
        """Beside ``serving.Server``'s: what the expert layers routed, as
        the programs summed it on the device (``moe_<prefill|decode>_<name>``,
        names of ``deepspeed_tpu/moe/grouped.py::COUNTERS``). A program
        without such counters, as the parent's, adds none."""
        routing = getattr(self.engine.metrics, "routing", {})
        return dict(super().counters(),
                    **{f"moe_{k}": v for k, v in routing.items()})


def window_anatomy(client, t0: float, t1: float) -> str:
    """What a window was made of, from the client's own clock, so that two
    windows that read differently can be told apart without a trace: the
    time between deliveries (a chunk, plus the prefills that ran between
    two), tokens/s by fifths of the window, and how the requests that got
    their first token were grouped (requests that start in one delivery
    were prefilled before one chunk)."""
    log = [(t, n) for t, n in client.token_log if t0 <= t <= t1]
    gaps = sorted((b[0] - a[0]) * 1e3 for a, b in zip(log, log[1:]))
    if len(gaps) < 5:
        return "too few deliveries to describe"
    fifth = (t1 - t0) / 5
    rates = [client.tokens_between(t0 + i * fifth, t0 + (i + 1) * fifth)
             / fifth for i in range(5)]
    started = sorted((t for t in client.all()
                      if t.first_t is not None and t0 < t.first_t <= t1),
                     key=lambda t: t.first_t)
    groups = [len(list(g)) for _, g in groupby(started,
                                               key=lambda t: t.first_t)]
    by_size = {n: groups.count(n) for n in sorted(set(groups))}
    prompt = sum(len(t.plan.prompt) for t in started)
    return (f"{len(gaps)} gaps between deliveries, ms: p10 "
            f"{percentile(gaps, 10):.1f} p50 {percentile(gaps, 50):.1f} "
            f"p90 {percentile(gaps, 90):.1f} max {gaps[-1]:.1f}, "
            f"{sum(1 for g in gaps if g > 1.5 * percentile(gaps, 50))} over "
            f"1.5 x p50; tokens/s by fifths "
            f"{[round(r) for r in rates]}; {len(started)} requests started "
            f"({prompt} prompt tokens) in deliveries of {by_size} "
            f"(requests together: count)")


def run(ctx):
    mix = ctx.cell["mix"]
    server = ArchServer(ctx)
    server.warm_prefill_family()
    plan = traffic.closed_loop_plan(mix, ctx.seed, server.vocab)
    fe = server.frontend()
    try:
        server.check_emitted_tokens(fe)
        # warm-up traffic: the mix's own requests, from the far end of its
        # cycle, for a fixed time; the lanes are full when it ends and stay
        # so (drivers/serve_closed.py)
        loop = server.warm_traffic(fe, plan[::-1], int(mix["clients"]),
                                   seconds=float(mix["warm_s"]))
        loop.plan, loop.taken = plan, 0
        client = loop.client
        # ... and on until ``open_after_ended`` of those requests have
        # ended: the order and the lengths are the same in every run, so an
        # EVENT of the schedule opens every window at the same point of it,
        # where a time on the clock falls a chunk earlier or later and moves
        # a whole prefill call (up to 1 % of a window) across its edge
        n_ended = int(mix["open_after_ended"])
        loop.run_until(lambda: len(client.finished) >= n_ended)

        # ---- the measured window opens and closes ON a delivery of tokens
        # (drivers/serve_closed.py): whole chunks over the time they took
        def next_delivery():
            seen = len(client.token_log)
            loop.run_until(lambda: len(client.token_log) > seen)
            return client.token_log[-1][0]

        next_delivery()
        setup_s = time.perf_counter() - ctx.t_start
        ctx.say(f"the window opens on delivery {len(client.token_log)} of "
                f"the loop, {len(client.finished)} requests ended")
        built0 = ctx.watch.programs()
        c0, s0 = server.counters(), server.span_totals()
        t0 = client.token_log[-1][0]
        loop.run_until(lambda: time.perf_counter() >= t0 + ctx.seconds)
        t1 = next_delivery()
        built = ctx.watch.names_since(built0)
        counters = serving.delta(server.counters(), c0)
        spans = serving.span_delta(server.span_totals(), s0)

        # ---- the traced stretch: the same load, a few seconds more
        summary, outline, traced, ts0, ts1 = None, [], {}, 0.0, 0.0
        if ctx.trace:
            stretch = TracedStretch(ctx)
            starter = in_thread(stretch.start)    # the load keeps going
            loop.run_until(lambda: not starter.is_alive())
            tc0, ts0 = server.counters(), time.perf_counter()
            loop.run_for(float(mix["trace_s"]))
            traced, ts1 = (serving.delta(server.counters(), tc0),
                           time.perf_counter())
            stopper = in_thread(stretch.stop)
            loop.run_until(lambda: not stopper.is_alive())
            summary, outline = stretch.summary, stretch.outline
        loop.stop()
    finally:
        fe.close(timeout=60.0)

    ended = [t for t in client.finished if t0 < t.done_t <= t1]
    server.check_window(ended, built)
    tokens = client.tokens_between(t0, t1)      # received in (t0, t1]
    window_s = t1 - t0
    ttfts = [t.ttft_s() for t in ended if t.ttft_s() is not None]

    def kv_live_mean(a, b):
        kv = [live for at, live in client.kv_samples if a < at <= b]
        return sum(kv) / len(kv) if kv else None

    ctx.say(f"window {window_s:.3f}s: {tokens} tokens to clients, "
            f"{len(ended)} requests ended, {counters['chunks']} chunks, "
            f"prefills padded {counters['prefill_prompt_tokens']} prompt "
            f"tokens to {counters['prefill_padded_tokens']}, "
            f"server rejected {counters['rejected']}; "
            + memory_line(ctx.devices))
    ctx.say("window anatomy: " + window_anatomy(client, t0, t1))
    # one schedule in every run: two windows' lines differ where time was lost
    ctx.say("deliveries, s into the window: " + " ".join(
        f"{t - t0:.2f}" for t, _ in client.token_log if t0 < t <= t1))
    first_tokens = sum(1 for t in client.all()
                       if t.first_t is not None and t0 < t.first_t <= t1)
    return {
        "correct": server.correct, "attempted": len(ended),
        "failed": sum(1 for t in ended if not t.ok),
        "setup_s": setup_s,
        "end_to_end": {"serve_tokens_per_s": tokens / window_s},
        "trace": summary, "outline": outline, "spans": spans,
        "counters": dict(
            server.facts(), window=counters, traced=traced,
            window_s=window_s, client_tokens=tokens,
            first_tokens=first_tokens,
            kv_live_mean=kv_live_mean(t0, t1),
            kv_live_mean_traced=kv_live_mean(ts0, ts1),
            ttft_ms_p50=median(ttfts) * 1e3 if ttfts else None,
            ttft_ms_p95=percentile(ttfts, 95) * 1e3 if ttfts else None,
            requests_per_s=len(ended) / window_s),
    }


def main(argv=None) -> int:
    """The control (module docstring): one line a seed, ``correct`` false
    where the limit refused the lower precision."""
    import argparse
    import dataclasses
    import json

    from chipbench import run as runner, spec

    ap = argparse.ArgumentParser(description=main.__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, nargs="+", default=[0])
    ap.add_argument("--control", default="float8_e4m3fn")
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)
    bench = spec.load_benchmark()
    cell = spec.load_cell(bench, args.workload, rehearsal=args.rehearsal)
    ctx = runner.open_ctx(cell, seed=args.seed[0], seconds=0.0, trace=False,
                          rehearsal=args.rehearsal)
    if isinstance(ctx, int):
        return ctx
    passed = 0
    for seed in args.seed:
        server = ArchServer(dataclasses.replace(ctx, seed=seed),
                            control=args.control)
        passed += server.correct
        print(json.dumps({
            "correct": server.correct, "control": args.control,
            "seed": seed, "logit_diff": server.logit_diff,
            "limit": server.arch.LOGIT_ATOL}), flush=True)
        del server
    return 1 if passed else 0


if __name__ == "__main__":
    sys.exit(main())
