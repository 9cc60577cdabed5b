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

Tensor parallelism: Megatron-style column/row split keyed on parameter path
(the reference only *consumes* an mpu for training and produces TP via
module_inject for inference, replace_module.py:502; here TP is first-class).
"""

from __future__ import annotations

import re
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


def _add_axis(spec: P, shape: Tuple[int, ...], axis_name: str, axis_size: int) -> P:
    """Extend `spec` by sharding the first free, divisible dim over
    `axis_name`; no-op if nothing fits (tensor stays replicated over it)."""
    if axis_size <= 1:
        return spec
    parts = list(spec) + [None] * (len(shape) - len(spec))
    for i, d in enumerate(shape):
        if parts[i] is None and d % axis_size == 0 and d >= axis_size:
            parts[i] = axis_name
            return P(*parts)
    return P(*parts)


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
                    spec = _add_axis(spec, shape, "dp", self.dp)
            # else: persisted — replicated over dp, no per-layer gather.
            # (Stacked [L, ...] leaves compare their full stacked size, the
            # conservative direction: a leaf persists only when the whole
            # stack is small. Master/opt state stays dp-sharded either way.)
        return spec

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
            spec = _add_axis(spec, shape, "dp", self.dp)
        return spec

    def grad_spec(self, path: str, shape: Tuple[int, ...],
                  expert_dim: int = 0) -> P:
        """Gradients: reduce-scattered from stage 2 on (constraining the grad
        output to the sharded spec turns the dp psum into psum_scatter)."""
        spec = self._base_spec(path, shape, expert_dim)
        if self.stage >= 2:
            spec = _add_axis(spec, shape, "dp", self.dp)
        return spec

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
