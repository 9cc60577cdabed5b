"""Inference engine: TP-sharded, KV-cached, jit-compiled serving.

Reference analogue: ``deepspeed/inference/engine.py:25`` —
``InferenceEngine`` with TP group creation (:151), injection-policy
application (:233), checkpoint loading with train->infer mp resharding
(:289), dtype conversion (:343), and CUDA-graph capture/replay (:363-391).

TPU-native mapping:
  * TP groups        -> the global mesh's ``tp`` axis; weights get the same
    column/row PartitionSpecs as training (runtime/sharding.py), XLA
    inserts the psum the reference codes as ``LinearAllreduce``
    (module_inject/replace_module.py:13).
  * kernel injection -> the model's attention runs the KV-cache decode path
    (models/gpt.py SelfAttention._decode_attention) and can route hot ops
    through the Pallas kernels; policies (module_inject/policies.py here)
    map HF checkpoints into our param trees.
  * CUDA graphs      -> jit compilation cache: prefill and decode are two
    fixed-shape jitted programs, replayed every call for free.
  * mp resharding    -> loading places weights against the current mesh's
    NamedShardings; any train-time dp/tp layout re-lands automatically
    (the SDLoader merge/split math becomes a device_put).
"""

from __future__ import annotations

import os
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import comm
from ..parallel import mesh as mesh_lib
from ..runtime.sharding import ShardingRules
from ..utils.logging import log_dist


class InferenceEngine:
    def __init__(self, model, config=None, *, mp_size: int = 1,
                 ep_size: int = 1,
                 dtype=jnp.bfloat16, model_parameters=None,
                 checkpoint: Optional[str] = None,
                 replace_with_kernel_inject: bool = False,
                 injection_policy=None, quantize_bits: Optional[int] = None,
                 quantize_mode: str = "symmetric",
                 max_tokens: Optional[int] = None,
                 replace_method: Optional[str] = None):
        """``ep_size``: expert-parallel degree for MoE models (reference
        InferenceEngine EP group creation, inference/engine.py:166, and the
        dedicated MoE inference module, moe_inference.py:210). Expert banks
        shard their expert dim over the mesh's ``ep`` axis — per-device
        expert HBM divides by ep_size — and the dispatch/combine all-to-all
        runs inside the jitted prefill/decode programs."""
        if replace_method == "auto" and ep_size > 1:
            raise ValueError(
                "ep_size > 1 with replace_method='auto' is unsupported: "
                "auto-TP classifies plain Linear kernels and knows nothing "
                "about expert banks; use the native MoE model path")
        comm.init_distributed()
        n_dev = len(jax.devices())
        shape = mesh_lib.MeshShape.infer(n_dev, tp=mp_size, ep=ep_size)
        self.mesh = mesh_lib.build_mesh(shape)
        mesh_lib.set_global_mesh(self.mesh, shape)
        self.mp_world_size = mp_size
        self.ep_world_size = ep_size
        self.module = model
        self.dtype = dtype
        self.rules = ShardingRules(self.mesh, zero_stage=0)

        if model_parameters is None and checkpoint is not None:
            model_parameters = self._load_checkpoint(checkpoint)
        if model_parameters is None:
            raise ValueError("pass model_parameters or checkpoint")

        if injection_policy is not None:
            model_parameters = injection_policy(model_parameters)

        # dtype conversion (reference _convert_to_dtype :343)
        params = jax.tree.map(
            lambda x: jnp.asarray(x).astype(dtype)
            if jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating) else
            jnp.asarray(x), model_parameters)

        if replace_method == "auto":
            # policy-free auto-TP (reference replace_wo_policy,
            # replace_module.py:502): classify every kernel column/row by
            # name+shape and let GSPMD insert the allreduces
            from ..module_inject.auto_tp import auto_tp_shardings
            self.param_shardings = auto_tp_shardings(params, self.mesh)
        else:
            param_specs = self.rules.param_specs(params)
            self.param_shardings = self.rules.shardings(param_specs)
            if ep_size > 1:
                # an ep axis that shards nothing is a misconfiguration, not
                # a degradation to silently absorb: the operator believes
                # expert HBM divided by ep when every bank stayed replicated
                # (no MoE layers, or num_experts % ep_size != 0)
                specs = jax.tree.leaves(param_specs,
                                        is_leaf=lambda x: isinstance(x, P))
                if not any("ep" in tuple(ax for e in s for ax in
                                         ((e,) if isinstance(e, str)
                                          else (e or ())))
                           for s in specs):
                    raise ValueError(
                        f"ep_size={ep_size} sharded no parameter: the model "
                        f"has no expert banks whose expert dim divides by "
                        f"{ep_size} (check num_experts % ep_size == 0, or "
                        f"drop ep_size)")
        if quantize_mode not in ("symmetric", "asymmetric"):
            raise ValueError(
                f"quantize_mode {quantize_mode!r}: use 'symmetric' or "
                f"'asymmetric'")
        if quantize_mode != "symmetric" and quantize_bits != 8:
            raise ValueError(
                "quantize_mode='asymmetric' without quantize_bits=8 would "
                "silently run unquantized; pass quantize_bits=8")
        if quantize_bits == 8:
            from ..ops.quantizer import quantize_shardings, quantize_tree
            # int8 weights live in HBM; dequant happens INSIDE the jitted
            # programs so XLA fuses the scale-multiply into the matmuls and
            # the TP sharding constraint applies to the dequantized tree.
            # The int8 tree itself is placed TP-sharded at rest (q8 leaves
            # inherit the fp leaf's spec, per-group scales follow), so
            # mp_size>1 actually divides the HBM footprint
            # mode: "symmetric" (absmax) or "asymmetric" (min/max range +
            # per-column zero point, reference ds_quantize_asym) — asym
            # buys accuracy on skewed weight distributions for one extra
            # f32 per output column
            q = quantize_tree(params, mode=quantize_mode)
            self.params = self._place(
                q, quantize_shardings(q, self.param_shardings, self.mesh))
            self.quantized = True
        else:
            self.quantized = False
            self.params = self._place(params, self.param_shardings)

        self._jit_forward = None
        self._jit_prefill = None
        self._jit_decode = {}          # keyed by (temperature, top_k)
        self.cache = None
        # the mesh takes EVERY device (dp = n / (tp*ep)) and nothing here
        # shards over dp: each dp replica holds a whole copy of the weights
        # and one ServingEngine drives them as one program (R9 places one
        # engine per chip instead)
        log_dist(f"inference engine ready: mesh={dict(self.mesh.shape)} "
                 f"over {n_dev} device(s) tp={mp_size} ep={ep_size} "
                 f"dtype={jnp.dtype(dtype).name} quantized={self.quantized}",
                 ranks=[0])

    # ----------------------------------------------------- multi-process
    @staticmethod
    def _place(tree, shardings):
        """Place a host tree against shardings. Multi-host (reference: the
        InferenceEngine is rank-per-GPU; here one process per host), a
        plain device_put of host-local data onto non-addressable devices is
        illegal — every process holds the SAME full values (deterministic
        init / same checkpoint) and contributes its addressable shards."""
        if jax.process_count() == 1:
            return jax.device_put(tree, shardings)
        return jax.tree.map(
            lambda a, sh: jax.make_array_from_process_local_data(
                sh, np.asarray(a), global_shape=np.asarray(a).shape),
            tree, shardings)

    def _global_input(self, x):
        if jax.process_count() == 1:
            return jnp.asarray(x)
        sh = NamedSharding(self.mesh, P())
        x = np.asarray(x)
        return jax.make_array_from_process_local_data(
            sh, x, global_shape=x.shape)

    # ------------------------------------------------------------ forward
    def _materialize(self, params):
        """Traced params: dequantize (if int8) and constrain to the TP
        shardings — called INSIDE every jitted program."""
        if self.quantized:
            from ..ops.quantizer import dequantize_tree
            params = dequantize_tree(params, self.dtype)
            params = jax.tree.map(jax.lax.with_sharding_constraint, params,
                                  self.param_shardings)
        return params

    def forward(self, input_ids, **kwargs):
        """Plain (non-incremental) forward — jit-cached per shape, the
        CUDA-graph replay analogue. Extra model inputs (attention_mask,
        token_type_ids, ...) ride as traced kwargs.

        Output contract: a `(logits, scalar)` pair (MoE aux loss) is
        unwrapped to bare logits — inference callers never consume the
        training-only aux loss. Genuine multi-head outputs (e.g. BERT's
        sequence + pooled pair, both non-scalar) pass through as tuples."""
        if self._jit_forward is None:
            def f(params, ids, kw):
                out = self.module.apply(
                    {"params": self._materialize(params)}, ids, **kw)
                if (isinstance(out, tuple) and len(out) == 2
                        and jnp.ndim(out[1]) == 0):
                    out = out[0]
                return out
            self._jit_forward = jax.jit(f)
        kw = {k: self._global_input(v) for k, v in kwargs.items()
              if v is not None}
        return self._jit_forward(self.params, self._global_input(input_ids),
                                 kw)

    __call__ = forward

    # ----------------------------------------------------------- generate
    def generate(self, input_ids, max_new_tokens: int = 32,
                 temperature: float = 1.0, top_k: Optional[int] = None,
                 rng: Optional[jax.Array] = None, eos_token_id=None):
        """Greedy/temperature sampling with KV cache: one jitted prefill
        over the prompt, then a jitted per-token decode replayed
        max_new_tokens times."""
        ids = np.asarray(input_ids)
        if ids.ndim == 1:
            ids = ids[None]
        ids = self._global_input(ids)
        b, s = ids.shape
        max_len = getattr(getattr(self.module, "cfg", None), "max_seq_len",
                          None)
        if max_len is not None and s + max_new_tokens > max_len:
            raise ValueError(
                f"prompt ({s}) + max_new_tokens ({max_new_tokens}) exceeds "
                f"the model's max_seq_len ({max_len}) — the KV cache would "
                f"silently clamp")
        if rng is None:
            rng = jax.random.PRNGKey(0)

        if self._jit_prefill is None:
            def prefill(params, ids):
                positions = jnp.arange(ids.shape[1])[None, :].repeat(
                    ids.shape[0], axis=0)
                logits, cache = self.module.apply(
                    {"params": self._materialize(params)}, ids,
                    positions=positions, mutable=["cache"])
                if isinstance(logits, tuple):
                    logits = logits[0]
                return logits[:, -1], cache["cache"]
            self._jit_prefill = jax.jit(prefill)

        def sample(logits, rng):
            logits = logits.astype(jnp.float32)
            if temperature not in (0.0, 1.0):
                logits = logits / temperature
            if top_k is not None:
                kth = jax.lax.top_k(logits, top_k)[0][:, -1:]
                logits = jnp.where(logits < kth, -1e10, logits)
            rng, sub = jax.random.split(rng)
            if temperature == 0.0:
                nxt = jnp.argmax(logits, axis=-1)
            else:
                nxt = jax.random.categorical(sub, logits, axis=-1)
            return nxt.astype(jnp.int32), rng

        # whole decode loop as ONE jitted scan — no per-token dispatch and
        # no per-token host sync on eos (the reference's generate breaks the
        # host loop on eos, engine weak-point #9: every such sync stalls the
        # device behind a host round trip). Rows that hit eos keep emitting
        # eos; the loop is static-length and the padding is what HF-style
        # generate produces anyway.
        key = (float(temperature), top_k, eos_token_id, max_new_tokens)
        if key not in self._jit_decode:
            def gen(params, cache, token, pos, rng):
                pm = self._materialize(params)

                def body(carry, _):
                    token, cache, pos, rng, done = carry
                    logits, new_vars = self.module.apply(
                        {"params": pm, "cache": cache}, token[:, None],
                        positions=pos[:, None], mutable=["cache"])
                    if isinstance(logits, tuple):
                        logits = logits[0]
                    nxt, rng = sample(logits[:, -1], rng)
                    if eos_token_id is not None:
                        nxt = jnp.where(done, eos_token_id, nxt)
                        done = done | (nxt == eos_token_id)
                    return (nxt, new_vars["cache"], pos + 1, rng, done), nxt

                done = (jnp.full(token.shape, False) if eos_token_id is None
                        else token == eos_token_id)
                (_, cache, _, _, _), toks = jax.lax.scan(
                    body, (token, cache, pos, rng, done),
                    None, length=max_new_tokens - 1)
                return jnp.moveaxis(toks, 0, 1)        # [b, steps]
            # donate the cache: the KV arena is updated in place — the
            # model's layer loop carries a cache that is passed in and
            # writes each layer's token with one dynamic_update_slice at
            # (layer, 0, cache_index) (models/gpt.py::_kv_write)
            self._jit_decode[key] = jax.jit(gen, donate_argnums=(1,))
        gen_fn = self._jit_decode[key]

        last_logits, cache = self._jit_prefill(self.params, ids)
        rng, sub = jax.random.split(rng)
        token, _ = sample(last_logits, sub)
        pos = jnp.full((b,), s, jnp.int32)
        if max_new_tokens == 1:
            return jnp.concatenate([ids, token[:, None]], axis=1)
        rest = gen_fn(self.params, cache, token, pos, rng)
        return jnp.concatenate([ids, token[:, None], rest], axis=1)

    # --------------------------------------------------------- checkpoint
    def _load_checkpoint(self, checkpoint: str):
        from ..checkpoint import saving as ckpt_saving
        if os.path.isdir(checkpoint):
            tag = ckpt_saving.read_latest_tag(checkpoint)
            path = os.path.join(checkpoint, tag or "", "model_states.npz")
        else:
            path = checkpoint
        tree = ckpt_saving.unflatten_tree(ckpt_saving.load_tree_arrays(path))
        log_dist(f"loaded inference checkpoint from {path}", ranks=[0])
        return tree
