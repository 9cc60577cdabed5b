"""Parameter sharding rules: how ZeRO + TP map onto the mesh.

This module is the TPU-native core of the ZeRO subsystem (reference:
``runtime/zero/stage_1_and_2.py:91`` and ``stage3.py:80``). The reference
implements partitioning imperatively — flatten param groups, slice per rank,
hook grad accumulation, all-gather updated shards. On TPU the same three
stages are *declarative*: a PartitionSpec per tensor, enforced with
``with_sharding_constraint`` / ``out_shardings``, and XLA emits the
all-gathers and reduce-scatters (overlapped with compute by the latency-hiding
scheduler — the analogue of the reference's ``overlap_comm`` side stream).

Stage semantics (ZeRO paper / reference zero/config.py):
  stage 0: params+grads+opt replicated; grad psum over dp.
  stage 1: optimizer state (and fp32 master) sharded over dp.
  stage 2: + grads reduce-scattered over dp (grad spec = sharded).
  stage 3: + parameters sharded over dp; all-gathered per use.

Where ``dp`` lies on a leaf. A model that scans over its layers stacks each
block leaf ``[L, ...]`` and the scan slices dim 0 at a traced index. ``dp``
therefore never takes that dim (``_scan_dims``): it takes the first free
dim INSIDE the layer, the same one for the compute copy, the gradient and
the master, so a scan step's slice of a leaf is itself dp-sharded. The
model states the gather where the layer is used (``gathered_where_used``:
the slice is constrained to its dp-replicated spec in the scan body): one
layer's weights are all-gathered per iteration and die with it, and its
gradient leaves through a reduce-scatter into the accumulator's shard. With ``dp`` on dim 0 each chip would own L/dp
whole layers, and a slice at a traced index along a sharded dim makes the
partitioner all-gather the WHOLE stack inside every iteration (PERF.md,
PR 27: 65 % of a ZeRO-3 step over four chips).

Tensor parallelism: Megatron-style column/row split keyed on parameter path
(the reference only *consumes* an mpu for training and produces TP via
module_inject for inference, replace_module.py:502; here TP is first-class).
"""

from __future__ import annotations

import contextlib
import re
import threading
from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Column-parallel (shard output dim) / row-parallel (shard input dim) name
# patterns, matched against the parameter path.
_COLUMN_PAT = re.compile(r"(qkv|up_proj|q_proj|k_proj|v_proj|lm_head|fc_in|wi|gate_proj)")
_ROW_PAT = re.compile(r"(out_proj|down_proj|o_proj|fc_out|wo)")
_EMBED_PAT = re.compile(r"(wte|embed|embedding)")
# Expert-stacked params (leading dim = experts; see moe/experts.py). The
# gate (`wg`) is NOT expert-stacked and stays replicated over ep.
_EXPERT_PAT = re.compile(r"(^|/)experts(/|$)")
# KV-cache payload leaves (serving arenas / paged pools). Everything else
# in the cache collection (cache_index cursors, int8 scale leaves, block
# tables) is tiny control state and stays replicated.
_KV_PAYLOAD_PAT = re.compile(r"(cached_key|cached_value)")
# models/mla.py: [c | k_rope] of a position, ONE row for all heads
_KV_LATENT_PAT = re.compile(r"(^|/)latent$")
# The module a model scans over its layers (models/gpt.py, models/bert.py:
# ``nn.scan(...)(cfg, name="blocks")``); unscanned layers are ``block_<i>``.
_SCANNED_PAT = re.compile(r"(^|/)blocks(/|$)")


def path_str(path) -> str:
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        else:
            parts.append(str(k))
    return "/".join(parts)


def tp_spec(path: str, ndim: int) -> P:
    """TP PartitionSpec on the *trailing* dims (leading scan/stack dims get
    None). Biases of column-parallel layers shard their single dim."""
    spec: list = [None] * ndim
    is_kernel = path.endswith("kernel") or path.endswith("embedding")
    is_bias = path.endswith("bias")
    if _EMBED_PAT.search(path) and is_kernel:
        spec[-2 if ndim >= 2 else -1] = "tp"   # vocab dim
    elif _COLUMN_PAT.search(path):
        if is_kernel and ndim >= 2:
            spec[-1] = "tp"
        elif is_bias:
            spec[-1] = "tp"
    elif _ROW_PAT.search(path):
        if is_kernel and ndim >= 2:
            spec[-2] = "tp"
        # row-parallel bias is replicated (added after the psum)
    return P(*spec)


def kv_spec(path: str, shape: Tuple[int, ...], tp: int,
            head_dim: Optional[int] = None) -> P:
    """TP PartitionSpec for one serving KV-cache leaf.

    The cache payload mirrors the attention activations the TP-sharded
    QKV projections produce, so sharding it the same way keeps decode
    reads/writes local to each tp shard:

    * flat layout ``[.., S, h*d]`` — shard the fused heads*head_dim dim
      (detected: last dim is a multiple of ``tp * head_dim``);
    * 4D layout ``[.., S, h, d]`` — shard the heads dim (dim -2);
    * anything that doesn't divide, plus control leaves (``cache_index``,
      scales, block tables) — replicated.

    Like ``tp_spec`` for params, a leaf only ever shards ONE dim and a
    non-divisible dim falls back to replication rather than erroring."""
    ndim = len(shape)
    spec: list = [None] * ndim
    if tp > 1 and _KV_LATENT_PAT.search(path):
        raise ValueError(
            f"cache leaf {path!r} {shape} is a latent row that every head "
            f"reads whole: its {shape[-1]} values are not heads and do not "
            f"shard over tp={tp}; serve this model data-parallel (tp=1)")
    if tp <= 1 or not _KV_PAYLOAD_PAT.search(path) or ndim < 2:
        return P(*spec)
    last = shape[-1]
    if head_dim and last != head_dim and last % (tp * head_dim) == 0:
        spec[-1] = "tp"                          # flat [.., S, h*d]
    elif head_dim and last == head_dim and shape[-2] % tp == 0:
        spec[-2] = "tp"                          # 4D [.., S, h, d]
    elif not head_dim and last % tp == 0:
        spec[-1] = "tp"                          # layout unknown: best effort
    return P(*spec)


def kv_shardings(cache, mesh: Mesh, head_dim: Optional[int] = None):
    """NamedShardings for a serving KV-cache pytree (arena or paged pool)
    over ``mesh``'s tp axis — the placement a tp-sharded serving engine
    commits its cache with so the insert/decode programs never start from
    an unsharded arena (which would retrace once placement settles)."""
    tp = mesh.shape.get("tp", 1)

    def leaf(p, x):
        return NamedSharding(
            mesh, kv_spec(path_str(p), tuple(x.shape), tp, head_dim))
    return jax.tree_util.tree_map_with_path(leaf, cache)


def _scan_dims(path: str) -> int:
    """How many leading dims of the leaf at ``path`` a scan over layers
    slices: 1 under the scanned ``blocks`` module, else 0."""
    return 1 if _SCANNED_PAT.search(path) else 0


def _add_axis(spec: P, shape: Tuple[int, ...], axis_name: str, axis_size: int,
              first_dim: int = 0) -> P:
    """Extend `spec` by sharding the first free, divisible dim from
    `first_dim` on over `axis_name`; no-op if nothing fits (tensor stays
    replicated over it)."""
    if axis_size <= 1:
        return spec
    parts = list(spec) + [None] * (len(shape) - len(spec))
    for i in range(first_dim, len(shape)):
        d = shape[i]
        if parts[i] is None and d % axis_size == 0 and d >= axis_size:
            parts[i] = axis_name
            return P(*parts)
    return P(*parts)


def _constrained_forward(x, sharding):
    """``x`` held to ``sharding`` on the way in; its cotangent passes as it
    comes. (``with_sharding_constraint`` alone transposes to the SAME
    constraint: a gathered weight's gradient would be all-reduced to every
    chip and sliced after the loop, where the accumulator's dp shard makes
    it a reduce-scatter.)"""
    @jax.custom_vjp
    def use(x):
        return jax.lax.with_sharding_constraint(x, sharding)
    use.defvjp(lambda x: (use(x), None), lambda _, g: (g,))
    return use(x)


# ``.rules``: the rules of the ZeRO-3 trainer whose model this thread is
# tracing, for the model's hook (``gathered_where_used``); unset elsewhere.
_tracing = threading.local()


def gathered_where_used(block):
    """Hook for a model that scans over its layers: wrap the block class
    the scan runs so that, when a ZeRO-3 trainer traces the model, each
    iteration's slice of the stacked params is constrained to its
    ``dp``-replicated spec before the block reads it. Wrap INSIDE the
    block's remat: the gathered layer is then gathered again for the
    recomputed forward and dies with the iteration; outside it every
    gathered layer would be saved for the backward pass. Returns ``block``
    itself anywhere else (init, inference, stages 0-2, ``dp`` of 1)."""
    rules = getattr(_tracing, "rules", None)
    if rules is None:
        return block
    import flax.linen as nn
    return nn.map_variables(block, "params", trans_in_fn=rules.gather_layer)


class ShardingRules:
    """Computes the sharding trees for params / grads / optimizer state given
    a ZeRO stage and mesh."""

    def __init__(self, mesh: Mesh, zero_stage: int = 0, use_tp: bool = True,
                 param_persistence_threshold: int = 0):
        """``param_persistence_threshold``: stage-3 leaves at or below this
        many elements stay replicated over ``dp`` ("persisted") instead of
        being sharded + re-gathered every layer — the declarative form of the
        reference's persistence set (zero/config.py
        stage3_param_persistence_threshold, kept live by the coordinator,
        partitioned_param_coordinator.py:240-356). Biases/LN scales are tiny;
        gathering them per layer costs a collective for ~KBs of savings."""
        self.mesh = mesh
        self.stage = zero_stage
        self.dp = mesh.shape.get("dp", 1)
        self.tp = mesh.shape.get("tp", 1) if use_tp else 1
        self.ep = mesh.shape.get("ep", 1)
        self.param_persistence_threshold = int(param_persistence_threshold)

    def _base_spec(self, path: str, shape: Tuple[int, ...],
                   expert_dim: int = 0) -> P:
        """TP + EP structural sharding shared by all three state kinds.
        Expert-stacked params shard their expert dim over ``ep`` (reference:
        expert params tagged allreduce=False + group_name, moe/experts.py:9-34,
        reduced over expert groups at engine.py:2171). ``expert_dim`` is 0
        for plain expert banks [E, ...] and 1 under scan-over-layers
        [L, E, ...] (see _expert_axis)."""
        spec = tp_spec(path, len(shape)) if self.tp > 1 else P(*([None] * len(shape)))
        if self.tp > 1:
            # drop tp from dims the axis doesn't divide (e.g. a 2-row
            # token-type embedding under tp=8): stay replicated there
            parts = [None if (a == "tp" and shape[i] % self.tp != 0) else a
                     for i, a in enumerate(list(spec) +
                                           [None] * (len(shape) - len(spec)))]
            spec = P(*parts)
        if self.ep > 1 and _EXPERT_PAT.search(path) \
                and len(shape) > expert_dim and shape[expert_dim] % self.ep == 0:
            parts = list(spec) + [None] * (len(shape) - len(spec))
            if parts[expert_dim] is None:
                parts[expert_dim] = "ep"
            spec = P(*parts)
        return spec

    def param_spec(self, path: str, shape: Tuple[int, ...],
                   expert_dim: int = 0) -> P:
        spec = self._base_spec(path, shape, expert_dim)
        if self.stage >= 3:
            numel = 1
            for d in shape:
                numel *= d
            if numel > self.param_persistence_threshold:
                if self._is_embed_table(path, shape):
                    spec = self._stage3_embed_spec(path, shape, spec)
                else:
                    spec = self._add_dp(path, shape, spec)
            # else: persisted — replicated over dp, no per-layer gather.
            # (Stacked [L, ...] leaves compare their full stacked size, the
            # conservative direction: a leaf persists only when the whole
            # stack is small. Master/opt state stays dp-sharded either way.)
        return spec

    def _add_dp(self, path: str, shape: Tuple[int, ...], spec: P) -> P:
        """``dp`` on the first free, divisible dim inside the layer: never
        on a dim a scan over layers slices (module docstring)."""
        return _add_axis(spec, shape, "dp", self.dp,
                         first_dim=_scan_dims(path))

    @staticmethod
    def _is_embed_table(path: str, shape: Tuple[int, ...]) -> bool:
        is_table = path.endswith("kernel") or path.endswith("embedding")
        return bool(_EMBED_PAT.search(path) and is_table and len(shape) >= 2)

    def _stage3_embed_spec(self, path: str, shape: Tuple[int, ...],
                           spec: P) -> P:
        """Embedding tables shard ``dp`` on the VOCAB dim (nested with tp),
        never on the feature dim. A feature-sharded table poisons the token
        lookup: the gather output is born feature-sharded while activations
        want [dp, sp, ·], and XLA's only escape is an involuntary full
        rematerialization (replicate-then-repartition of [B, S, D] every
        microbatch — the SPMD warning the r2 dryrun logged). Vocab-sharded
        operands instead partition the gather by its (dp, sp)-sharded
        indices with a mask+psum, and the output is born with the right
        sharding. When the vocab dim doesn't divide, the table stays
        REPLICATED over dp (memory for bandwidth — feature-dim dp would
        reintroduce the per-microbatch remat)."""
        vdim = len(shape) - 2   # vocab dim, matching tp_spec
        parts = list(spec) + [None] * (len(shape) - len(spec))
        if parts[vdim] == "tp" and shape[vdim] % (self.tp * self.dp) == 0:
            parts[vdim] = ("tp", "dp")
            return P(*parts)
        if parts[vdim] is None and shape[vdim] % self.dp == 0:
            parts[vdim] = "dp"
            return P(*parts)
        from ..utils.logging import logger
        logger.warning(
            f"stage-3: embedding table {path} {shape} keeps its vocab dim "
            f"replicated over dp={self.dp} (dim {shape[vdim]} doesn't "
            f"divide); pad the vocab to a multiple of tp*dp to shard it")
        return P(*parts)

    def master_spec(self, path: str, shape: Tuple[int, ...],
                    expert_dim: int = 0) -> P:
        """fp32 master copy / optimizer moments: sharded from stage 1 on."""
        spec = self._base_spec(path, shape, expert_dim)
        if self.stage >= 1:
            spec = self._add_dp(path, shape, spec)
        return spec

    def grad_spec(self, path: str, shape: Tuple[int, ...],
                  expert_dim: int = 0) -> P:
        """Gradients: reduce-scattered from stage 2 on (constraining the grad
        output to the sharded spec turns the dp psum into psum_scatter)."""
        spec = self._base_spec(path, shape, expert_dim)
        if self.stage >= 2:
            spec = self._add_dp(path, shape, spec)
        return spec

    # -- the gather, stated where a scanned layer is used -------------------
    @contextlib.contextmanager
    def stating_layer_gathers(self):
        """While a stage-3 trainer traces its model: ``gathered_where_used``
        blocks constrain their layer's params by these rules. ZeRO-3's
        contract is "the layer's weights are gathered, the batch stays
        home"; left to the declaration alone the partitioner may keep a
        contraction-sharded weight where it lies, gather the batch and
        all-reduce activations instead."""
        if self.stage < 3 or self.dp <= 1:
            yield
            return
        prev = getattr(_tracing, "rules", None)
        _tracing.rules = self
        try:
            yield
        finally:
            _tracing.rules = prev

    def gather_layer(self, layer_vars):
        """One scan step's slice of the stacked block params (a flax
        variables dict, paths relative to the block), each leaf constrained
        to its spec with ``dp`` gathered and ``tp``/``ep`` kept."""
        def leaf(path, x):
            spec = self._base_spec(path_str(path), tuple(x.shape))
            return _constrained_forward(x, NamedSharding(self.mesh, spec))
        return jax.tree_util.tree_map_with_path(leaf, layer_vars)

    # -- tree-level helpers -------------------------------------------------
    @staticmethod
    def _expert_axis(tree) -> int:
        """Which dim of expert-stacked params is the expert dim: 0 normally,
        1 when the model scans over layers (params then stack [L, E, ...]).
        Detected from the gate kernel's rank ([d, E] plain vs [L, d, E]
        scanned) — the gate always lives beside the expert bank."""
        leaves, _ = jax.tree_util.tree_flatten_with_path(tree)
        for path, leaf in leaves:
            p = path_str(path)
            if "gate/wg" in p and p.endswith("kernel"):
                return max(getattr(leaf, "ndim", 2) - 2, 0)
        return 0

    def _tree_specs(self, tree, fn):
        expert_dim = self._expert_axis(tree)

        def leaf(path, x):
            return fn(path_str(path), tuple(x.shape), expert_dim)
        return jax.tree_util.tree_map_with_path(leaf, tree)

    def param_specs(self, params):
        return self._tree_specs(params, self.param_spec)

    def master_specs(self, params):
        return self._tree_specs(params, self.master_spec)

    def grad_specs(self, params):
        return self._tree_specs(params, self.grad_spec)

    def shardings(self, spec_tree):
        return jax.tree.map(lambda s: NamedSharding(self.mesh, s), spec_tree,
                            is_leaf=lambda x: isinstance(x, P))

    def opt_state_shardings(self, opt_state, master_shardings, params_template):
        """Optimizer state leaves that mirror a param keep its sharding;
        scalars/others replicate. Matching is by shape."""
        by_shape = {}
        leaves, _ = jax.tree_util.tree_flatten_with_path(params_template)
        m_leaves = jax.tree.leaves(master_shardings)
        for (path, p), sh in zip(leaves, m_leaves):
            by_shape.setdefault(tuple(p.shape), sh)
        rep = NamedSharding(self.mesh, P())

        def leaf(x):
            return by_shape.get(tuple(getattr(x, "shape", ())), rep)

        return jax.tree.map(leaf, opt_state)
