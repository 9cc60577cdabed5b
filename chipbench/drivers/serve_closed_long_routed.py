"""Mix kind ``serve_closed_long_routed``: ``serve_closed_arch``'s closed loop,
window and counters, for an architecture that ROUTES and whose mechanism only
shows at lengths that driver's check never reaches. ``serve_closed_arch``
hands the reference the program's routing but draws its check prompts at 5-40
tokens; ``serve_closed_long`` takes the check's lengths from the arch file
but passes no routing (and reads head 0 of eight). A block whose sliding
layers are plain causal attention under a window's length AND whose expert
layers route needs both, and neither file may be edited, so this third one
imports their parts:

  * the check's prompt lengths from ``check_lengths(config)``: groups of
    lengths, each group prefilled together, padded to the smallest prefill
    bucket of the configuration that holds it (as the server pads), told
    where each row ends where the model asks, then four decode steps through
    the cache, against ONE full forward of the reference over prompt + those
    tokens, on logits, under the arch file's ``LOGIT_ATOL``. The reference
    is handed the program's routing and follows it inside ``ROUTE_EPS``;
    its head is multiplied over the rows compared alone
    (``reference_logits(..., rows=)``: a vocabulary of 200,192);
  * the routing rule and the routing counters, as ``serve_closed_arch``;
  * the same prompts through the real server, token by token, under
    ``TOKEN_GAP_ATOL`` (``ArchServer.check_emitted_tokens``, as it is).

Everything else is ``serve_closed_arch``'s, imported: the loop, the window
that opens on an event of the one schedule, the control
(``--control float8_e4m3fn`` puts the float8-operand reference in the
program's place and has to print ``"correct": false``):

    python3 chipbench/drivers/serve_closed_long_routed.py --workload <cell> \
        --seed <n> [<n> ...] [--control float8_e4m3fn] [--rehearsal]

Like ``serve_closed_long`` it puts its subclass under the name
``ArchServer`` for the length of a call (``serving.Server`` has no hook for
it: PERF.md section 7 asks a ``benchmark`` PR for one).
"""

from __future__ import annotations

import os
import sys
from unittest import mock

import numpy as np

if __name__ == "__main__":      # the control, run as a script
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))

from chipbench.drivers import serve_closed_arch as base  # noqa: E402
from chipbench.drivers.serve_closed_long import LongServer  # noqa: E402

LOGIT_STEPS = base.LOGIT_STEPS


def cache_path(module, params, prompts, steps: int, width: int):
    """``serve_closed_arch.cache_path`` for a model whose prefill is told
    where each padded row ends (and may then hand out each row's last logits
    alone): prefill over the prompts padded to ``width``, then ``steps``
    greedy decode steps through the cache with a cursor a row. Returns the
    logits that chose each of the first ``steps + 1`` tokens ``[n, steps + 1,
    V]`` float32, those tokens ``[n, steps + 1]``, the program's choice over
    the reference's full forward ``[layers, n, longest prompt + steps + 1,
    k]`` (-1 where the program ran no such token; None for a model that
    does not route) and the routing counters its own function sums over
    exactly those tokens."""
    import jax
    import jax.numpy as jnp

    n = len(prompts)
    lens = np.asarray([len(p) for p in prompts], np.int32)
    ids = np.zeros((n, width), np.int32)
    for i, p in enumerate(prompts):
        ids[i, :len(p)] = p
    told = getattr(module, "prefill_takes_lengths", False)
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
        out, vc = module.apply(
            {"params": params}, ids, positions=positions, mutable=["cache"],
            **({"lengths": lens} if told else {}))
        logits, choice, counted = split(out, positions < lens[:, None])
        last = logits[:, 0] if logits.shape[1] == 1 else \
            jnp.take_along_axis(logits, (lens - 1)[:, None, None],
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
        chosen = np.full(choice.shape[:2] + (int(max(lens)) + steps + 1,)
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


class RoutedLongServer(LongServer):
    """``LongServer`` (the arch file's lengths, the model's step counters)
    whose check hands the reference the program's routing."""

    def _check_logits(self, model, control=None) -> None:
        import jax.numpy as jnp
        arch, steps = self.arch, LOGIT_STEPS
        rng = np.random.default_rng([self.ctx.seed, 0x10617])
        groups = arch.check_lengths(self.config)
        buckets = sorted(self.srv_kw.get("prefill_buckets") or [])
        lower = None if control is None else jnp.dtype(control).type
        prompts, refs, got, toks = [], [], [], []
        routing = {}
        counted = {}
        for lengths in groups:
            mine = [rng.integers(0, self.cfg.vocab_size, (n,)
                                 ).astype(np.int32) for n in lengths]
            width = min([b for b in buckets if b >= max(lengths)]
                        or [max(lengths)])
            logits, chosen_toks, choice, counters = cache_path(
                model, self.inference.params, mine, steps, width)
            # ONE full forward of the reference over prompt + those tokens
            # (causal: the padding behind a row changes nothing before it)
            full = np.zeros((len(mine), max(lengths) + steps + 1), np.int32)
            for i, p in enumerate(mine):
                full[i, :len(p)] = p
                full[i, len(p):len(p) + steps + 1] = chosen_toks[i]
            rows = [(len(p) - 1, len(p) + steps) for p in mine]

            def reference(operands):    # row j chose token j
                ref, report = arch.reference_logits(
                    self.config, self.inference.params, full,
                    program_choice=choice, lower=operands, rows=rows)
                return [np.asarray(r) for r in ref], report

            ref, report = reference(None)
            refs += ref
            got += list(logits) if control is None else reference(lower)[0]
            prompts += mine
            toks += list(chosen_toks)
            for k, v in report.items():
                routing[k] = max(routing.get(k, 0.0), v) \
                    if k == "largest_gap" else routing.get(k, 0) + v
            for k, v in counters.items():
                counted[k] = counted.get(k, 0.0) + v
        spans = ", ".join(f"{g[0]}-{g[-1]}" if len(g) > 1 else str(g[0])
                          for g in groups)
        what = (f"{len(prompts)} prompts of {spans} tokens, prefill + "
                f"{steps} decode steps through the cache")
        if control is not None:
            what = (f"CONTROL, the reference with {control} operands in the "
                    f"program's place, over the same rows")
        by_group, at = [], 0
        for lengths in groups:
            by_group.append(max(
                float(np.max(np.abs(got[i] - refs[i])))
                for i in range(at, at + len(lengths))))
            at += len(lengths)
        worst = max(by_group)
        span = (min(float(r.min()) for r in refs),
                max(float(r.max()) for r in refs))
        self.logit_diff = worst
        self._check(all(np.isfinite(g).all() for g in got)
                    and worst <= arch.LOGIT_ATOL,
                    f"{what} vs the float32 reference: max |logit diff| "
                    f"{worst:.4f} (by group "
                    f"{', '.join(f'{d:.4f}' for d in by_group)}; tolerance "
                    f"{arch.LOGIT_ATOL}; reference logits span "
                    f"[{span[0]:.2f}, {span[1]:.2f}])")
        if control is not None:
            return
        if routing.get("sets"):
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
                f"{routing['pairs_held']} and {routing['pairs_absent']}")
        self._ref_prompts, self._ref_rows, self._ref_toks = \
            prompts, refs, toks


def _routed(fn, *args):
    with mock.patch.object(base, "ArchServer", RoutedLongServer):
        return fn(*args)


def run(ctx):
    return _routed(base.run, ctx)


def main(argv=None) -> int:
    """The control (module docstring), through ``serve_closed_arch.main``."""
    return _routed(base.main, argv)


if __name__ == "__main__":
    sys.exit(main())
