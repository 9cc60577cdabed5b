"""The expert loop as one kernel (ops/pallas/grouped_mlp.py), in the Pallas
interpreter: held to ``moe/grouped.py``'s ``while_loop`` on the same inputs
and to a dense float32 reference; the tile schedule it walks; its gate."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
jnp = jax.numpy

from deepspeed_tpu.moe import grouped                      # noqa: E402
from deepspeed_tpu.ops.pallas import grouped_mlp as gm     # noqa: E402
from deepspeed_tpu.ops.pallas._utils import KernelUnsupported   # noqa: E402


def _silu(x):
    return x / (1.0 + np.exp(-x))


def _inputs(rng, T, k, E, H, d, f, L, choice=None):
    bf = lambda a: jnp.asarray(a, jnp.bfloat16)             # noqa: E731
    x = bf(rng.standard_normal((T, d)))
    gate = bf(rng.standard_normal((L, H, d, f)) / np.sqrt(d))
    up = bf(rng.standard_normal((L, H, d, f)) / np.sqrt(d))
    down = bf(rng.standard_normal((L, H, f, d)) / np.sqrt(f))
    if choice is None:
        choice = np.stack([rng.permutation(E)[:k] for _ in range(T)])
    weights = rng.uniform(0.5, 1.5, (T, k)).astype(np.float32)
    return (x, jnp.asarray(choice, jnp.int32), jnp.asarray(weights), gate,
            up, down)


def _dense(x, choice, weights, gate, up, down, layer, offset):
    """Every held pair through its expert in float32, hidden unrounded."""
    x, gate, up, down = (np.asarray(a, np.float32)
                         for a in (x, gate[layer], up[layer], down[layer]))
    out = np.zeros_like(x)
    for t, (row, ws) in enumerate(zip(np.asarray(choice),
                                      np.asarray(weights))):
        for e, w in zip(row - offset, ws):
            if 0 <= e < gate.shape[0]:
                out[t] += w * ((_silu(x[t] @ gate[e]) * (x[t] @ up[e]))
                               @ down[e])
    return out


def _popular(T, k, E, favourite):
    """Every token names ``favourite`` first, then k - 1 others in turn."""
    rest = [e for e in range(E) if e != favourite]
    return np.array([[favourite] + [rest[(t * (k - 1) + i) % len(rest)]
                                    for i in range(k - 1)] for t in range(T)])


CASES = {
    # T, k, E (published), H (held), offset, d, f, tile, budget, choice
    "uneven loads, untouched experts":
        (40, 2, 16, 16, 0, 128, 256, 32, None, "few"),
    "an expert with more than one tile":
        (48, 3, 8, 8, 0, 128, 128, 32, None, "popular"),
    "pairs on absent experts (offset 4, 4 held of 16)":
        (64, 4, 16, 4, 4, 128, 128, 32, None, None),
    "tiles of 256 rows":
        (256, 2, 4, 4, 0, 128, 128, 256, None, None),
    "f in two blocks under a float32 accumulator":
        (64, 4, 16, 16, 0, 128, 256, 32, 2 * 3 * 128 * 128 * 2 + 2 * 32 * 128 * 4,
         "popular"),
    "no pair held at all":
        (24, 2, 16, 4, 8, 128, 128, 32, None, "low"),
    "three tokens (a check's decode step)":
        (3, 2, 8, 8, 0, 128, 128, 32, None, None),
}


@pytest.mark.parametrize("name", list(CASES))
def test_the_kernel_equals_the_loop_and_the_dense_reference(
        name, past_auto_path, monkeypatch):
    """``grouped_experts`` with ``auto`` resolved as on the chip (the kernel,
    interpreted) against itself on the loop and against every held pair in
    float32, the banks read at a TRACED layer under ``lax.scan``."""
    T, k, E, H, offset, d, f, tile, budget, how = CASES[name]
    rng = np.random.default_rng(sorted(CASES).index(name))
    choice = {"few": lambda: rng.choice([1, 2, 5, 11], (T, 1))
              + np.array([[0, 1]]),
              "popular": lambda: _popular(T, k, E, 3),
              "low": lambda: np.stack([rng.permutation(8)[:k]
                                       for _ in range(T)]),
              None: lambda: None}[how]()
    L = 3
    args = _inputs(rng, T, k, E, H, d, f, L, choice)
    if budget is not None:
        monkeypatch.setattr(gm, "_WEIGHT_BUDGET", budget)
        assert gm.block_of_f(d, f, tile, 2) == f // 2

    def every_layer(*args):
        def one(_, layer):
            return None, grouped.grouped_experts(
                *args, lead=(layer,), expert_offset=offset, tile=tile)
        return jax.lax.scan(one, None, jnp.arange(L))[1]

    got = np.asarray(jax.jit(every_layer)(*args))
    assert ("grouped_mlp", None) in past_auto_path
    monkeypatch.undo()                  # "auto" as on the CPU: the loop
    loop = np.asarray(jax.jit(every_layer)(*args))
    scale = max(np.abs(loop).max(), 1.0)
    np.testing.assert_allclose(got, loop, atol=1e-5 * scale)
    for layer in range(L):
        want = _dense(*args, layer, offset)
        np.testing.assert_allclose(got[layer], want, atol=2e-2 * scale)
    if how == "low":
        assert not got.any()
    else:
        assert np.abs(got).max() > 0.1


@pytest.mark.parametrize("n_f", [1, 2])
def test_the_schedule_names_a_touched_expert_in_consecutive_tiles(n_f):
    """Sizes 0, 70, 0, 5, 33, 0 at 32 rows a tile: tiles of experts 1, 1, 1,
    3, 4, 4 and then the last live tile again, so the weight blocks the
    pipeline fetches (a fetch where a step's block index differs from the
    step before) are touched experts x blocks when an expert is one block,
    and tiles x blocks when it is several (they cycle within a tile)."""
    sizes = jnp.asarray([0, 70, 0, 5, 33, 0], jnp.int32)
    n_tiles = 6 + 108 // 32
    expert, first, n_live = (np.asarray(a) for a in
                             grouped.tile_schedule(sizes, 32, n_tiles))
    assert n_live == 6
    assert expert.tolist() == [1, 1, 1, 3, 4, 4] + [4] * (n_tiles - 6)
    assert first.tolist() == [0, 32, 64, 70, 75, 107] + [107] * (n_tiles - 6)
    runs = [e for i, e in enumerate(expert[:n_live])
            if i == 0 or e != expert[i - 1]]
    assert runs == sorted(set(runs)) == [1, 3, 4]
    fetched, last = 0, None
    for t in range(n_tiles):
        for j in range(n_f):
            at, blk = (int(v) for v in gm.live_step(t, j, n_live, n_f))
            assert (at, blk) == ((t, j) if t < n_live
                                 else (n_live - 1, n_f - 1))
            block = (int(expert[at]), blk)
            fetched += block != last
            last = block
    assert fetched == (3 if n_f == 1 else 6 * n_f)
    # nothing held: one empty tile of the last expert, no live step
    expert, first, n_live = grouped.tile_schedule(jnp.zeros(6, jnp.int32),
                                                  32, 4)
    assert int(n_live) == 0 and not np.asarray(first).any()
    assert [int(v) for v in gm.live_step(2, 0, 0, n_f)] == [0, n_f - 1]


REFUSALS = {
    "banks of float32": dict(dtype=jnp.float32),
    "x of float32 against banks of bfloat16": dict(x_dtype=jnp.float32),
    "banks indexed at 2 leading dimensions": dict(lead_dims=2),
    "300 tokens": dict(tokens=300),
    "tiles of 8 rows": dict(tile=8),
    "an expert of [96, 128]": dict(d=96),
    "no block of f=128": dict(f=128, d=1 << 20),
}


@pytest.mark.parametrize("reason", list(REFUSALS))
def test_the_gate_refuses_by_name(reason):
    shape = dict(tokens=64, d=128, f=128, tile=32, dtype=jnp.bfloat16)
    assert gm.grouped_mlp_refusal(**shape) is None
    said = gm.grouped_mlp_refusal(**{**shape, **REFUSALS[reason]})
    assert said is not None and said.startswith(reason), said


def test_a_refused_shape_raises_by_name_and_auto_keeps_the_loop(
        past_auto_path):
    """Calling the kernel IS asking by name: ``KernelUnsupported`` through
    ``_utils.refuse``. ``grouped_experts`` asks the gate first, and a
    refusal under ``auto`` is the loop's answer, to the last bit."""
    rng = np.random.default_rng(1)
    args = _inputs(rng, 300, 2, 8, 8, 128, 128, 1)
    x, choice, weights, gate, up, down = args
    sel = jnp.zeros((4, 32, 300), jnp.bfloat16)
    with pytest.raises(KernelUnsupported, match="grouped_mlp refused.*"
                       "300 tokens: x and the float32 result stay in fast"):
        gm.grouped_mlp(x, jnp.zeros(4, jnp.int32), 0, sel,
                       jnp.zeros((4, 32)), gate, up, down)
    got = grouped.grouped_experts(*args, lead=(0,), tile=32)
    (asked, refusal), = past_auto_path
    assert asked == "grouped_mlp" and refusal.startswith("300 tokens")
    want = _dense(*args, 0, 0)
    np.testing.assert_allclose(np.asarray(got), want,
                               atol=2e-2 * np.abs(want).max())


def test_on_the_cpu_auto_takes_the_loop(monkeypatch):
    """Nothing patched: the gate accepts the shape, and the CPU's ``auto``
    still takes the loop (the kernel would run in the interpreter)."""
    def never(*a, **kw):
        raise AssertionError("the kernel ran under auto on the CPU")
    monkeypatch.setattr(gm, "grouped_mlp", never)
    args = _inputs(np.random.default_rng(2), 16, 2, 8, 8, 128, 128, 1)
    assert gm.grouped_mlp_refusal(16, 128, 128, 32, jnp.bfloat16) is None
    assert not grouped.takes_kernel(16, 128, 128, 32, jnp.bfloat16)
    got = grouped.grouped_experts(*args, lead=(0,), tile=32)
    want = _dense(*args, 0, 0)
    np.testing.assert_allclose(np.asarray(got), want,
                               atol=2e-2 * np.abs(want).max())
