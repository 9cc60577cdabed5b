"""Mix kind ``serve_closed_long``: ``serve_closed_arch``'s closed loop, window
and counters, for an architecture whose mechanism only shows at lengths that
driver's check never reaches. ``serve_closed_arch`` draws its 16 check prompts
at 5-40 tokens; a block that keeps an exact window beside chunk summaries of
everything older is plain causal attention there, and the check would hold
nothing of it to the reference. Here the check's prompt lengths come from the
arch file (``check_lengths(config)``: groups of lengths, each group prefilled
together, padded to its longest as the server pads to a bucket):

  * prefill (told where each padded row ends, as ``ServingEngine``'s prefill
    program tells a model that asks) and four decode steps through the cache
    against ONE full forward of the reference over prompt + those tokens, on
    logits, under the arch file's ``LOGIT_ATOL``;
  * the same prompts through the real server, token by token, under
    ``TOKEN_GAP_ATOL`` (``ArchServer.check_emitted_tokens``, as it is).

Everything else is ``serve_closed_arch``'s, imported: the loop, the window
that opens on an event of the one schedule, the counters, the control
(``--control float8_e4m3fn`` puts the float8-operand reference in the
program's place and has to print ``"correct": false``):

    python3 chipbench/drivers/serve_closed_long.py --workload <cell> \
        --seed <n> [<n> ...] [--control float8_e4m3fn] [--rehearsal]

That driver builds its server by the name ``ArchServer``; this one puts its
subclass under that name for the length of the call (``serving.Server`` has
no hook for it: PERF.md section 7 asks a ``benchmark`` PR for one). **A third
architecture** whose check needs its own lengths gives ``check_lengths`` in
its arch file and names this kind in its mix; one that 5-40 tokens hold to
its reference names ``serve_closed_arch``.
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

LOGIT_STEPS = base.LOGIT_STEPS


def cache_path(module, params, prompts, steps: int):
    """``serving.cache_path_logits`` for a model whose prefill is told where
    each padded row ends: prefill over the prompts padded to the longest,
    then ``steps`` greedy decode steps through the cache with a cursor a row.
    Returns the logits that chose each of the first ``steps + 1`` tokens
    ``[n, steps + 1, V]`` float32 and those tokens ``[n, steps + 1]``."""
    import jax
    import jax.numpy as jnp

    n = len(prompts)
    lens = np.asarray([len(p) for p in prompts], np.int32)
    ids = np.zeros((n, int(max(lens))), np.int32)
    for i, p in enumerate(prompts):
        ids[i, :len(p)] = p
    told = getattr(module, "prefill_takes_lengths", False)

    @jax.jit
    def prefill(params, ids, lens):
        positions = jnp.arange(ids.shape[1])[None, :]
        logits, vc = module.apply(
            {"params": params}, ids, positions=positions, mutable=["cache"],
            **({"lengths": lens} if told else {}))
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


class LongServer(base.ArchServer):
    """``ArchServer`` with the check's prompts at the arch file's lengths."""

    def _check_logits(self, model, control=None) -> None:
        import jax.numpy as jnp
        arch, steps = self.arch, LOGIT_STEPS
        rng = np.random.default_rng([self.ctx.seed, 0x10617])
        groups = arch.check_lengths(self.config)
        lower = None if control is None else jnp.dtype(control).type
        prompts, refs, got, toks = [], [], [], []
        for lengths in groups:
            mine = [rng.integers(0, self.cfg.vocab_size, (n,)
                                 ).astype(np.int32) for n in lengths]
            logits, chosen = cache_path(model, self.inference.params, mine,
                                        steps)
            # ONE full forward of the reference over prompt + those tokens
            # (causal: the padding behind a row changes nothing before it)
            full = np.zeros((len(mine), max(lengths) + steps + 1), np.int32)
            for i, p in enumerate(mine):
                full[i, :len(p)] = p
                full[i, len(p):len(p) + steps + 1] = chosen[i]

            def rows(operands):     # row j chose token j; head 0's logits
                ref = np.asarray(arch.reference_logits(
                    self.config, self.inference.params, full,
                    lower=operands)[0])[:, :, 0]
                return [ref[i, len(p) - 1:len(p) + steps]
                        for i, p in enumerate(mine)]

            refs += rows(None)
            got += list(logits) if control is None else rows(lower)
            prompts += mine
            toks += list(chosen)
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
        self._ref_prompts, self._ref_rows, self._ref_toks = \
            prompts, refs, toks

    def counters(self):
        """Beside ``ArchServer``'s: what the model's own step counters
        summed on the device (``ServingMetrics.state_rows``, under the names
        the model gave them). A program without such counters, as the
        parent's, adds none."""
        return dict(super().counters(),
                    **getattr(self.engine.metrics, "state_rows", {}))


def _as_arch_server(fn, *args):
    with mock.patch.object(base, "ArchServer", LongServer):
        return fn(*args)


def run(ctx):
    return _as_arch_server(base.run, ctx)


def main(argv=None) -> int:
    """The control (module docstring), through ``serve_closed_arch.main``."""
    return _as_arch_server(base.main, argv)


if __name__ == "__main__":
    sys.exit(main())
