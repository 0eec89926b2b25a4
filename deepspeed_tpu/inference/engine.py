"""InferenceEngine — TP-sharded serving with a compiled KV-cache decode loop.

TPU-native re-design of the reference inference engine
(reference deepspeed/inference/engine.py:89 ``InferenceEngine``). The torch
engine mutates the module in place (kernel injection, CUDA graphs); here the
engine owns a params pytree sharded over the 'model' mesh axis and three
compiled programs:

  prefill:  [B, T_prompt] -> (logits, cache)     (cache write 0..T)
  decode:   one token through the cache          (reference softmax_context,
            csrc/transformer/inference/csrc/pt_binding.cpp:1747)
  generate: prefill + lax.scan over decode steps + sampling, ONE dispatch
            per generate() call — the XLA answer to CUDA-graph capture
            (reference inference/engine.py:500 _capture_graph).

TP serving reuses the model's training partition rules through the stage-0
sharding planner (reference auto-TP, module_inject/auto_tp.py:13, falls out
of the same rules). Sampling: greedy / temperature / top-k, with EOS
short-circuit semantics matching HF generate defaults.
"""

import inspect
import math
from collections import OrderedDict
from contextlib import nullcontext
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..models.api import ModelSpec
from ..telemetry.trace import avals_of, get_tracer
from ..parallel.topology import (DeviceMeshManager, default_devices,
                                 initialize_mesh, get_mesh_manager)
from ..runtime.zero.partition import ZeroShardingPlanner
from ..utils.logging import log_dist, logger
from .config import DeepSpeedInferenceConfig
from .kv_quant import (QuantizedSlotPool, init_pool, insert_lane,
                       is_quantized_pool, lane_slice, lane_update,
                       pool_from_fp, pool_to_fp, read_lane, write_lane)
from .speculative import (row_keys, sample_rows, sampling_arrays,
                          unmask_rows)

# compile-ledger labels of the pool programs whose key in ``_slot_fns``
# spells its kind shorter; every other kind is its own label
_POOL_LABELS = {"slot_suffix": "slot_suffix_prefill",
                "slot_chunk": "slot_chunk_prefill"}
# a pool program outside a serving tick's accounting: no (prep, dispatch)
_NO_PHASES = (nullcontext(), nullcontext())


def _next_pow2(n: int) -> int:
    return 1 << max(0, (n - 1)).bit_length()


def _sample_one(logits_row, temp, top_k, top_p, seed, col, vocab):
    """Single-row sampling at cache column ``col`` (the position the
    sampled token will be FED at): the key derives only from
    ``(seed, col)``, so serving replays — across ticks, slots, and
    replicas — regenerate the identical token (speculative.row_keys)."""
    keys = row_keys(seed[None], col[None])
    return sample_rows(logits_row[None], temp[None], top_k[None],
                       top_p[None], keys, vocab)[0]


class InferenceEngine:
    """Callable engine: ``engine(input_ids)`` -> logits;
    ``engine.generate(...)`` -> token ids."""

    def __init__(self, model, config: DeepSpeedInferenceConfig = None,
                 params=None, mesh_manager: Optional[DeviceMeshManager] = None):
        if config is None:
            config = DeepSpeedInferenceConfig()
        self._config = config
        self.dtype = config.dtype

        # HF torch modules (the reference's primary input) are converted by
        # the injection layer into a deepspeed_tpu model spec + params.
        if not isinstance(model, ModelSpec):
            from ..module_inject import replace_transformer_layer
            model, params = replace_transformer_layer(model, config)
        self.module = model

        tp = config.tensor_parallel.tp_size
        if mesh_manager is not None:
            self.mesh_manager = mesh_manager
        else:
            devices = default_devices()
            if len(devices) % tp != 0:
                raise ValueError(
                    f"tp_size={tp} does not divide device count {len(devices)}")
            self.mesh_manager = initialize_mesh(
                dp=len(devices) // tp, tp=tp, devices=devices)
        self.mesh = self.mesh_manager.mesh

        rules = model.partition_rules() if hasattr(model, "partition_rules") \
            else []
        self.planner = ZeroShardingPlanner(self.mesh_manager, stage=0,
                                           rules=rules)

        rng = jax.random.PRNGKey(config.seed)
        param_shapes = jax.eval_shape(model.init, rng)
        self.param_shardings = self.planner.param_shardings(param_shapes)
        # int8 weight-only serving (reference GroupQuantizer at injection,
        # module_inject/replace_module.py:140): block weights become
        # QuantizedWeight pytree nodes; fp-layout shardings are kept for
        # checkpoint loads, which land in fp then quantize.
        self._quant = (config.quant
                       if config.quant is not None and config.quant.enabled
                       else None)
        self._fp_shardings = self.param_shardings
        self._fp_template = param_shapes
        if self._quant is not None:
            from .quantization import quantized_shardings
            self.param_shardings = quantized_shardings(self._fp_shardings,
                                                       param_shapes)
        self._recast_fn = None
        self._leaf_fns = {}      # quantize_resident's programs, by leaf
        #: the checkpoint weights_version these params came from (0 =
        #: unversioned: fresh init or a pre-rollout checkpoint); the
        #: rollout plane compares it across replicas and KV handoffs
        self.weights_version = 0
        with self.mesh:
            if params is not None:
                self.params = self.recast(params)
            else:
                self.params = self._quantize_resident(jax.jit(
                    lambda r: jax.tree.map(self._cast_leaf, model.init(r)),
                    out_shardings=self._fp_shardings)(rng))
        if config.checkpoint:
            self.load_checkpoint(config.checkpoint)
        if self._quant is not None:
            from .quantization import describe
            log_dist(describe(self.params), ranks=[0])

        self._cache_rules = (model.cache_partition_rules()
                             if hasattr(model, "cache_partition_rules") else [])
        # Compiled-program cache, LRU-capped at config.compiled_cache_size:
        # shape buckets accumulate across a serving process's lifetime
        # (every distinct (batch, prompt, new_tokens) is an entry) and each
        # holds a compiled executable. Slot-serving programs live in
        # _slot_fns, exempt from eviction — the continuous-batching decode
        # step must compile exactly once per pool shape.
        self._fns: "OrderedDict[Any, Any]" = OrderedDict()
        self._slot_fns: Dict[Any, Any] = {}
        # (bucket, max_len) -> does that slot prefill take the flash kernel
        self._prefill_kernel: Dict[Any, bool] = {}
        # compile ledger (telemetry/compileplane.py), attached by the
        # serving layer when its compile_plane block is on: every serving
        # program (forward, generate bucket, prefill bucket, fused decode,
        # pool init) becomes a compile event with an arg fingerprint
        self.compile_plane = None
        self._tracer = get_tracer()     # the slot programs' phase records
        # a model with a routed expert layer: slot_prefill and
        # slot_decode_step read its routing stats back with the tokens
        self._routed = bool(getattr(model, "routed_experts", False))
        self._routing = None
        # a model whose pool holds a recurrent state or a window ring
        # beside K and V: the prefills tell it each prompt's real length
        self._recurrent = bool(getattr(model, "lane_end_state", ()))
        n_params = sum(int(np.prod(s.shape))
                       for s in jax.tree.leaves(param_shapes))
        log_dist(f"InferenceEngine initialized: params={n_params/1e6:.1f}M "
                 f"tp={tp} dtype={jnp.dtype(self.dtype).name} "
                 f"max_tokens={config.max_tokens}", ranks=[0])

    # ------------------------------------------------------------------ utils
    def _cast_leaf(self, x):
        if jnp.issubdtype(x.dtype, jnp.floating):
            return x.astype(self.dtype)
        return x

    def _quantize_resident(self, params, cast=None):
        """Parameters as they are served: themselves, or int8 where
        weight-only quantization is on, a leaf at a time
        (``quantize_resident``). ``cast=None``: fp parameters of the
        engine's own, on the device in the serving layout, consumed."""
        if self._quant is None:
            return params
        from .quantization import quantize_resident
        return quantize_resident(params, self.param_shardings,
                                 self._quant.group_size, self._quant.bits,
                                 cast=cast, programs=self._leaf_fns)

    def recast(self, params):
        """Cast/re-shard a params tree into the serving layout (quantizing
        when int8 serving is on, through the one leaf-at-a-time path) —
        compiled per input structure; the hybrid engine refreshes fp
        training params through this after every optimizer step. The
        caller's tree is left as it is."""
        from .quantization import is_quantized
        with self.mesh:
            if self._quant is not None:
                return self._quantize_resident(params, cast=self._cast_leaf)
            if self._recast_fn is None:
                self._recast_fn = jax.jit(
                    lambda p: jax.tree.map(
                        lambda x: x if is_quantized(x)
                        else self._cast_leaf(x), p, is_leaf=is_quantized),
                    out_shardings=self.param_shardings)
            return self._recast_fn(params)

    def _batch_sharding(self, batch_size: int):
        """Serving batches can be any size: shard over the dp axes only when
        divisible, else replicate (small-batch decode)."""
        if batch_size % self.mesh_manager.dp_world_size == 0:
            return self.mesh_manager.batch_sharding(False)
        return NamedSharding(self.mesh, P())

    def _cache_shardings(self, cache_shapes, rules=None):
        """Cache-rule shardings for KV-cache leaves, with any mesh axis that
        does not divide its dimension dropped to replication (a pool's
        num_slots is operator-chosen and rarely divides the dp axes; the
        pool's stored rows are ``Hk * hd / 128`` where heads are narrower
        than 128 lanes, which 'model' need not divide although it divides
        the heads)."""
        planner = ZeroShardingPlanner(self.mesh_manager, stage=0,
                                      rules=self._cache_rules
                                      if rules is None else rules)

        def divides(ax, dim):
            names = ax if isinstance(ax, (tuple, list)) else (ax,)
            return dim % math.prod(self.mesh.shape[n] for n in names) == 0

        def fits(sh, leaf):
            spec = tuple(sh.spec) + (None,) * (len(leaf.shape) - len(sh.spec))
            return NamedSharding(self.mesh, P(*(
                ax if ax is not None and divides(ax, dim) else None
                for ax, dim in zip(spec, leaf.shape))))

        return jax.tree.map(fits, planner.param_shardings(cache_shapes),
                            cache_shapes)

    def _observe_compile(self, label, fn, args, names=None):
        """Compile-ledger hook: no-op unless the serving layer attached a
        ledger. Observes BEFORE the call: every slot program that returns
        a pool donates the one it is given, so its args can only be read
        while they are still live."""
        cp = self.compile_plane
        if cp is None:
            return
        try:
            cp.observe(label, fn, args, names=names, mesh=self.mesh)
        except Exception as e:   # observability must never fail a request
            logger.warning(f"compile plane: observe failed: {e}")

    def _fn_get(self, key):
        """LRU lookup in the compiled-program cache."""
        fn = self._fns.get(key)
        if fn is not None:
            self._fns.move_to_end(key)
        return fn

    def _fn_put(self, key, fn):
        """Insert into the compiled-program cache, evicting the least
        recently used entries past config.compiled_cache_size."""
        self._fns[key] = fn
        self._fns.move_to_end(key)
        cap = getattr(self._config, "compiled_cache_size", 0) or 0
        while cap > 0 and len(self._fns) > cap:
            old_key, _ = self._fns.popitem(last=False)
            logger.debug(
                f"InferenceEngine: evicting compiled program {old_key} "
                f"(compiled_cache_size={cap})")
        return fn

    def load_checkpoint(self, load_dir, tag=None):
        """Load a deepspeed_tpu training checkpoint (any source mp/dp layout
        — universal reshard-on-load) into the serving shardings. Checkpoints
        are fp; int8 serving quantizes after the reshard."""
        from ..runtime.checkpointing import read_weights_version
        self.params = self._load_params(load_dir, tag)
        self.weights_version = read_weights_version(load_dir, tag=tag)
        return load_dir

    def _load_params(self, load_dir, tag=None):
        """Checkpoint params resharded into this engine's serving layout
        (structure-gated: a drifted leaf set raises with the per-leaf
        diff BEFORE anything moves to device)."""
        from ..runtime.checkpointing import load_params_for_inference
        with self.mesh:
            params = load_params_for_inference(
                load_dir, tag=tag, like=self._fp_template,
                shardings=self._fp_shardings, cast=self._cast_leaf)
            return self._quantize_resident(params)

    def with_params(self, params, weights_version=None):
        """A shallow engine view sharing this engine's module, mesh,
        planner, and compiled-program caches but serving ``params`` —
        the rollout plane's vNext standup. Identical shapes mean the
        shared executables serve both versions with ZERO new compiles;
        only the params pointer (and the reported version) differ."""
        import copy
        view = copy.copy(self)
        view.params = params
        if weights_version is not None:
            view.weights_version = int(weights_version)
        return view

    def load_version(self, load_dir, tag=None):
        """Load a checkpoint WITHOUT mutating this engine: returns a
        shallow view (``with_params``) serving the new weights at the
        checkpoint's ``weights_version``. The structure gate and the
        integrity manifest both run before the view exists, so a bad
        checkpoint aborts here — never after traffic moved."""
        from ..runtime.checkpointing import read_weights_version
        params = self._load_params(load_dir, tag)
        return self.with_params(
            params, read_weights_version(load_dir, tag=tag))

    # ---------------------------------------------------------------- forward
    def forward(self, input_ids, **kwargs):
        """Full-sequence logits (scoring path, no cache)."""
        input_ids = jnp.asarray(input_ids)
        key = ("fwd", input_ids.shape)
        fn = self._fn_get(key)
        if fn is None:
            def fwd(params, ids):
                logits, _ = self.module.logits(params, ids, train=False,
                                               return_aux_loss=True)
                return logits
            fn = self._fn_put(key, jax.jit(
                fwd, in_shardings=(self.param_shardings,
                                   self._batch_sharding(input_ids.shape[0]))))
        self._observe_compile("fwd", fn, (self.params, input_ids),
                              names=("params", "input_ids"))
        with self.mesh:
            return fn(self.params, input_ids)

    __call__ = forward

    # --------------------------------------------------------------- generate
    def generate(self, input_ids, max_new_tokens: int = 64,
                 temperature: float = 0.0, top_k: int = 0,
                 eos_token_id: Optional[int] = None, seed: int = 0,
                 max_length: Optional[int] = None, top_p: float = 1.0,
                 num_beams: int = 1, attention_mask=None,
                 length_penalty: float = 1.0):
        """Autoregressive generation, one compiled program per
        (prompt_shape, max_new_tokens) bucket. Returns [B, T+max_new_tokens]
        (prompt + generated; positions after EOS hold eos_token_id).
        ``num_beams > 1`` runs deterministic beam search (temperature/
        top-k/top-p must be off). ``attention_mask`` [B, T] (HF convention,
        1 = real token) serves LEFT-padded batches of uneven prompts: pad
        columns never act as keys and logical positions shift per row."""
        if not 0.0 < top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {top_p}")
        if temperature <= 0.0 and (top_k or top_p < 1.0):
            raise ValueError(
                "top_k/top_p require temperature > 0 (temperature<=0 means "
                "greedy decoding, which would silently ignore them); pass "
                "temperature=1.0 for plain top-k/top-p sampling")
        if num_beams < 1:
            raise ValueError(f"num_beams must be >= 1, got {num_beams}")
        if num_beams == 1 and length_penalty != 1.0:
            raise ValueError(
                "length_penalty only applies to beam search "
                f"(got length_penalty={length_penalty} with num_beams=1)")
        if num_beams > 1 and (temperature > 0 or top_k or top_p < 1.0):
            raise ValueError(
                "beam search is deterministic: temperature/top_k/top_p "
                "cannot be combined with num_beams > 1")
        input_ids = jnp.asarray(input_ids)
        if input_ids.ndim == 1:
            input_ids = input_ids[None]
        b, t = input_ids.shape
        pad_counts = None
        if attention_mask is not None:
            attention_mask = jnp.asarray(attention_mask)
            if attention_mask.shape != (b, t):
                raise ValueError(
                    f"attention_mask shape {attention_mask.shape} != "
                    f"input_ids shape {(b, t)}")
            if num_beams > 1:
                raise NotImplementedError(
                    "attention_mask (padded prompts) + beam search is not "
                    "supported yet")
            # HF left-padding: mask must be 0..0 1..1 per row — enforce it
            # (a right-padded mask would silently shift positions wrongly
            # and sample from a pad token's hidden state)
            pad_counts = (t - attention_mask.sum(-1)).astype(jnp.int32)
            expect = jnp.arange(t)[None, :] >= pad_counts[:, None]
            if not bool(jnp.all(attention_mask.astype(bool) == expect)):
                raise ValueError(
                    "attention_mask must be contiguous LEFT padding "
                    "(rows of 0..0 1..1); right-padded or interior-zero "
                    "masks are not supported")
        if max_length is not None:
            max_new_tokens = max(0, max_length - t)
        if max_new_tokens <= 0:
            return input_ids  # prompt already at/over max_length
        n_pos = getattr(getattr(self.module, "config", None),
                        "n_positions", None)
        if n_pos is not None and t + max_new_tokens > n_pos:
            raise ValueError(
                f"generate: prompt {t} + max_new_tokens {max_new_tokens} "
                f"exceeds the model's context length n_positions={n_pos}")
        cache_len = min(_next_pow2(t + max_new_tokens),
                        max(self._config.max_tokens, t + max_new_tokens))
        if t + max_new_tokens > self._config.max_tokens:
            logger.warning(
                f"generate: {t}+{max_new_tokens} tokens exceeds config "
                f"max_tokens={self._config.max_tokens} "
                f"(reference inference/engine.py:588 guard); growing cache")

        key = ("gen", b, t, max_new_tokens, float(temperature), top_k,
               float(top_p), eos_token_id, num_beams, pad_counts is not None,
               float(length_penalty))
        fn = self._fn_get(key)
        if fn is None:
            if num_beams > 1:
                fn = self._build_beam_generate(
                    b, t, cache_len, max_new_tokens, num_beams, eos_token_id,
                    length_penalty)
            else:
                fn = self._build_generate(
                    b, t, cache_len, max_new_tokens, temperature, top_k,
                    top_p, eos_token_id, padded=pad_counts is not None)
            self._fn_put(key, fn)
        tr = get_tracer()
        gen_key = jax.random.PRNGKey(seed)
        gen_args = (self.params, input_ids, gen_key) if num_beams > 1 else \
            (self.params, input_ids, gen_key, pad_counts)
        self._observe_compile("generate", fn, gen_args,
                              names=("params", "input_ids", "rng",
                                     "pad_counts"))
        with tr.span("generate", cat="inference",
                     args={"batch": b, "prompt_len": t,
                           "max_new_tokens": max_new_tokens,
                           "num_beams": num_beams}) as sp:
            with self.mesh:
                out = fn(*gen_args)
            if tr.sync_spans:
                sp.sync_on(out)
        return out

    def _build_generate(self, b, t, cache_len, max_new_tokens, temperature,
                        top_k, top_p, eos_token_id, padded=False):
        model = self.module
        vocab = model.config.vocab_size

        def sample(logits, key):
            # logits [B, V_padded]; restrict to the real vocab
            logits = logits[:, :vocab].astype(jnp.float32)
            if temperature <= 0.0:
                return jnp.argmax(logits, axis=-1).astype(jnp.int32)
            logits = logits / temperature
            if top_k or top_p < 1.0:     # one descending sort serves both
                desc = jnp.sort(logits, axis=-1)[:, ::-1]
            if top_k:
                logits = jnp.where(logits < desc[:, top_k - 1][:, None],
                                   -jnp.inf, logits)
                # top-k survivors are exactly the first k sorted entries
                desc = jnp.where(
                    jnp.arange(desc.shape[-1])[None] < top_k, desc, -jnp.inf)
            if top_p < 1.0:
                # nucleus: keep the smallest prefix of descending-prob
                # tokens whose mass reaches top_p (always >= 1 token),
                # computed on the top-k-RENORMALIZED distribution — HF's
                # TopK-then-TopP warper order
                probs = jax.nn.softmax(desc, axis=-1)
                cum = jnp.cumsum(probs, axis=-1)
                keep = (cum - probs) < top_p
                thresh = jnp.min(jnp.where(keep, desc, jnp.inf), axis=-1,
                                 keepdims=True)
                logits = jnp.where(logits >= thresh, logits, -jnp.inf)
            return jax.random.categorical(key, logits, axis=-1).astype(
                jnp.int32)

        cache_shapes = jax.eval_shape(
            lambda: model.init_kv_cache(b, cache_len, dtype=self.dtype))
        cache_specs = jax.tree.map(
            lambda sh: sh.spec, self._cache_shardings(cache_shapes))

        def constrain(cache):
            return lax.with_sharding_constraint(cache, cache_specs)

        def run(params, prompt, key, pad_counts=None):
            pc = pad_counts if padded else None
            cache = constrain(
                model.init_kv_cache(b, cache_len, dtype=self.dtype))
            logits, cache = model.apply_with_cache(params, prompt, cache,
                                                   jnp.int32(0),
                                                   pad_counts=pc)
            tok = sample(logits[:, -1], key)
            finished = (jnp.zeros((b,), jnp.bool_) if eos_token_id is None
                        else tok == eos_token_id)

            def step(carry, i):
                cache, tok, finished, key = carry
                key, sub = jax.random.split(key)
                # tok was sampled for position t+i-1; write its K/V there
                logits, cache = model.apply_with_cache(
                    params, tok[:, None], cache, t + i - 1, pad_counts=pc)
                cache = constrain(cache)
                nxt = sample(logits[:, -1], sub)
                if eos_token_id is not None:
                    nxt = jnp.where(finished, eos_token_id, nxt)
                    finished = finished | (nxt == eos_token_id)
                return (cache, nxt, finished, key), tok

            if max_new_tokens > 1:
                (_, last, _, _), toks = lax.scan(
                    step, (cache, tok, finished, key),
                    jnp.arange(1, max_new_tokens, dtype=jnp.int32))
                toks = jnp.concatenate([toks.T, last[:, None]], axis=-1)
            else:
                toks = tok[:, None]
            return jnp.concatenate([prompt, toks], axis=-1)

        return jax.jit(run, in_shardings=(
            self.param_shardings, self._batch_sharding(b), None, None))

    def _build_beam_generate(self, b, t, cache_len, max_new_tokens, k,
                             eos_token_id, length_penalty=1.0):
        """Deterministic beam search, fully in-jit (reference parity:
        inference/engine.py:588 delegates beams to HF generate; here the
        whole search — expand, score, reorder-cache, backtrack-free
        sequence buffer — is one compiled program)."""
        model = self.module
        vocab = model.config.vocab_size
        NEG = jnp.float32(-1e30)
        cache_shapes = jax.eval_shape(
            lambda: model.init_kv_cache(b * k, cache_len, dtype=self.dtype))
        cache_specs = jax.tree.map(
            lambda sh: sh.spec, self._cache_shardings(cache_shapes))

        def run(params, prompt, _key):
            # prefill ONCE at batch B, then tile the cache to B*K beams
            small = model.init_kv_cache(b, cache_len, dtype=self.dtype)
            logits, small = model.apply_with_cache(params, prompt, small,
                                                   jnp.int32(0))
            cache = lax.with_sharding_constraint(
                jax.tree.map(lambda c: jnp.repeat(c, k, axis=1), small),
                cache_specs)
            logp = jax.nn.log_softmax(
                logits[:, -1, :vocab].astype(jnp.float32), axis=-1)
            logp = jnp.repeat(logp, k, axis=0).reshape(b, k, vocab)
            # beams start identical: only beam 0 may propose, or the top-k
            # picks would be k copies of the same token
            first = jnp.where(jnp.arange(k)[None, :, None] == 0, logp[:, :1],
                              NEG)
            scores, flat = lax.top_k(first.reshape(b, k * vocab), k)
            tok = (flat % vocab).astype(jnp.int32)          # [B, K]
            finished = (tok == eos_token_id) if eos_token_id is not None \
                else jnp.zeros((b, k), jnp.bool_)
            lengths = jnp.ones((b, k), jnp.float32)   # generated incl. EOS
            seqs = jnp.zeros((b, k, max_new_tokens), jnp.int32)
            seqs = seqs.at[:, :, 0].set(tok)

            def step(carry, i):
                cache, seqs, tok, scores, finished, lengths = carry
                logits, cache = model.apply_with_cache(
                    params, tok.reshape(b * k, 1), cache, t + i - 1)
                logp = jax.nn.log_softmax(
                    logits[:, -1, :vocab].astype(jnp.float32), axis=-1)
                logp = logp.reshape(b, k, vocab)
                if eos_token_id is not None:
                    # finished beams: frozen score, only-EOS continuation
                    only_eos = jnp.where(
                        jnp.arange(vocab)[None, None] == eos_token_id,
                        0.0, NEG)
                    logp = jnp.where(finished[..., None], only_eos, logp)
                total = scores[..., None] + logp            # [B, K, V]
                scores, flat = lax.top_k(total.reshape(b, k * vocab), k)
                parent = flat // vocab                      # [B, K]
                tok = (flat % vocab).astype(jnp.int32)
                # reorder beam state by parent
                gather = jnp.take_along_axis
                seqs = gather(seqs, parent[..., None], axis=1)
                seqs = seqs.at[:, :, i].set(tok)
                finished = gather(finished, parent, axis=1)
                lengths = gather(lengths, parent, axis=1)
                # unfinished beams grew by one token (incl. a fresh EOS);
                # already-finished beams' appended EOS is padding
                lengths = lengths + (~finished).astype(jnp.float32)
                if eos_token_id is not None:
                    finished = finished | (tok == eos_token_id)
                flat_parent = (jnp.arange(b)[:, None] * k +
                               parent).reshape(b * k)
                cache = lax.with_sharding_constraint(
                    jax.tree.map(
                        lambda c: jnp.take(c, flat_parent, axis=1), cache),
                    cache_specs)
                return (cache, seqs, tok, scores, finished, lengths), None

            if max_new_tokens > 1:
                (cache, seqs, tok, scores, finished, lengths), _ = lax.scan(
                    step, (cache, seqs, tok, scores, finished, lengths),
                    jnp.arange(1, max_new_tokens, dtype=jnp.int32))
            # HF default semantics: pick by score / length**length_penalty
            # (length_penalty 1.0) so beams that hit EOS early are not
            # unconditionally favored
            norm = scores / jnp.power(jnp.maximum(lengths, 1.0),
                                      jnp.float32(length_penalty))
            best = jnp.argmax(norm, axis=-1)                # [B]
            out = jnp.take_along_axis(seqs, best[:, None, None],
                                      axis=1)[:, 0]         # [B, max_new]
            if eos_token_id is not None:
                # positions after EOS hold eos_token_id (sampled-path
                # semantics)
                hit = jnp.cumsum(
                    (out == eos_token_id).astype(jnp.int32), axis=-1)
                out = jnp.where(hit > 1, eos_token_id, out)
            return jnp.concatenate([prompt, out], axis=-1)

        return jax.jit(run, in_shardings=(
            self.param_shardings, self._batch_sharding(b), None))

    # ------------------------------------------------- slot-serving protocol
    # Entry points for the continuous-batching serving layer
    # (deepspeed_tpu/serving/): a fixed pool of decode slots — batch rows of
    # one statically-shaped KV cache — so admission/retirement of requests
    # never changes a compiled shape. Every program over a pool (the slot
    # prefills per pow2 bucket, the lane copies, the fused all-slot decode
    # step that compiles EXACTLY once per (num_slots, max_len), the pool
    # inits, and the draft and verify programs of speculative decoding) is
    # built by ``_pool_program`` and called by ``_pool_call``, which decide
    # once what each entry point below only names: its key and flavour in
    # ``_slot_fns``, its shardings, its donation, the names the compile
    # ledger reads. How a pool is stored (fp or int8) is inference/
    # kv_quant.py's business: the bodies go through its converters. All
    # are exempt from the _fns LRU: evicting the decode step would silently
    # recompile the serving hot path.

    def _pool_shardings(self, num_slots: int, max_len: int,
                        quantize: bool = False, model=None):
        """``_cache_shardings`` of the slot pool (heads-over-'model' TP is
        the sharding that matters for serving). With ``quantize``, returns
        a QuantizedSlotPool of shardings: q leaves keep the fp spec,
        per-column scale leaves keep it minus the trailing axis.
        ``model`` overrides the cached model (the speculative DRAFT pool
        follows the draft model's cache rules)."""
        rules = None
        if model is None:
            model = self.module
        else:
            rules = (model.cache_partition_rules()
                     if hasattr(model, "cache_partition_rules") else [])
        shapes = jax.eval_shape(
            lambda: model.init_kv_cache(num_slots, max_len,
                                        dtype=self.dtype))
        fixed = self._cache_shardings(shapes, rules=rules)
        if not quantize:
            return fixed

        def drop_hd(sh, leaf):
            spec = tuple(sh.spec) + (None,) * (len(leaf.shape) - len(sh.spec))
            return NamedSharding(self.mesh, P(*spec[:-1]))

        return QuantizedSlotPool(
            q=fixed, scales=jax.tree.map(drop_hd, fixed, shapes))

    def _pool_dims(self, pool, model=None):
        """(num_slots, max_len, quantized) from any pool flavor of
        ``model`` (``None``: the engine's own; a draft's pool: the draft).
        Every program that returns a pool consumes the one it was given
        (``_pool_program`` donates it): a pool that was handed over already
        is refused here, by name, before XLA refuses its buffers."""
        # a full-length leaf, by the name the model declares
        # (``GPT2Model.lane_leaves``: ``k``, or a latent leaf): a pool may
        # hold leaves of another shape beside it (a recurrent state and a
        # ring have no max_len), and they may sort first
        name = getattr(model or self.module, "lane_leaves", ("k",))[0]
        leaf = (pool.q if is_quantized_pool(pool) else pool)[name]
        if leaf.is_deleted():
            raise RuntimeError(
                "this KV pool was consumed by an earlier slot_* call (or by "
                "one that raised after its dispatch): every pool program "
                "donates its pool, so rebind the pool from the call's "
                "return and never reuse the argument")
        return (int(leaf.shape[1]), int(leaf.shape[2]),
                is_quantized_pool(pool))

    @staticmethod
    def _pool_key(kind, dims, quantized):
        """A pool program's key in ``_slot_fns``: its kind, the static
        sizes it was built for, and ``"q8"`` last where its pool is int8
        (fp and int8 pools are separate programs)."""
        return (kind, *dims) + (("q8",) if quantized else ())

    def _pool_program(self, kind, dims, shape, outs: Optional[int] = 0,
                      draft=None):
        """The one builder of programs over a KV pool, as a decorator of
        the program's body: ``@self._pool_program(...)`` over ``def
        dec(params, pool, ...)`` gives the compiled program, the cached
        one after the first time (the body is then ignored). ``shape`` is
        the pool's ``(num_slots, max_len, quantized)`` (``_pool_dims``);
        ``outs`` says how many values the body returns after the pool, or
        ``None`` where it returns no pool; ``draft`` makes it a program
        over the draft model's pool and parameters. Decided here and
        nowhere else:

        - the key (``_pool_key``), under which ``_slot_fns`` keeps it;
        - the shardings, read off the body's signature: the parameters'
          at an argument named ``...params``, the pool's at one named
          ``...pool`` and at the first output, whole on every device at
          the outputs behind it and at an argument named ``prev`` (such an
          output fed back), none elsewhere;
        - donation: a program that returns a pool donates the pool it
          is given, always. Aliased to the output, a lane write
          (``dynamic_update_slice``) or a decode step's rows change the
          pool in place; undonated, XLA allocates a second pool and
          copies all of it, two pool-sized buffers stay live across the
          step (the kv_slots HBM doubling ds_tpu_lint's HLO005 flags),
          and the runtime holds the next call until the old pool is free
          (docs/serving.md, "Who owns the pool"). Every caller rebinds
          the pool from the return;
        - the label and the argument names the compile ledger is given:
          the body's own parameter names (analysis/artifacts.py maps
          them to roles).

        The module name of the program is ``jit_<body's name>``: ``jit_pf``
        and ``jit_dec`` are how chipbench/workloads/*.json find the
        prefill and decode programs in a device trace
        (tests/unit/test_phases.py pins them)."""
        num_slots, max_len, quantized = shape
        key = self._pool_key(kind, dims, quantized)

        def build(body):
            fn = self._slot_fns.get(key)
            if fn is not None:
                return fn
            names = tuple(inspect.signature(body).parameters)
            on_draft = draft is not None
            pool_sh = self._pool_shardings(
                num_slots, max_len, quantize=quantized,
                model=draft.model if on_draft else None)
            param_sh = draft.param_shardings if on_draft \
                else self.param_shardings
            # what a program returns beside the pool is read by the host or
            # fed back as the next call's ``prev``: whole on every device,
            # said at both ends (left to the compiler, tokens [S] may come
            # out split over the slots, and fed back they would be a
            # second executable)
            whole = NamedSharding(self.mesh, P())
            in_sh = tuple(param_sh if n.endswith("params") else
                          pool_sh if n.endswith("pool") else
                          whole if n == "prev" else None for n in names)
            if outs is None:        # returns no pool: nothing to alias
                out_sh, donated = None, ()
            else:
                out_sh = (pool_sh,) + (whole,) * outs if outs else pool_sh
                donated = tuple(i for i, n in enumerate(names)
                                if n.endswith("pool"))
            fn = self._slot_fns[key] = jax.jit(
                body, in_shardings=in_sh, out_shardings=out_sh,
                donate_argnums=donated)
            fn.label = _POOL_LABELS.get(kind, kind)
            fn.arg_names = names
            fn.key, fn.noted = key, False
            return fn

        return build

    def _pool_call(self, fn, prep, phases=_NO_PHASES):
        """Call pool program ``fn`` with the arguments ``prep()`` makes
        (host arrays put on the device). ``phases`` are the two phase
        records of a serving tick's program, (prep, dispatch); a program
        outside the tick's accounting records none."""
        prep_phase, dispatch_phase = phases
        with prep_phase:
            args = prep()
            if not fn.noted:        # the program's first call
                fn.noted = True
                self._tracer.note_program("jit_" + fn.__name__, fn.key, fn,
                                          avals_of(args), self.mesh)
            # observed BEFORE the call: the program donates its pool, so
            # its arguments can only be read while they are still live
            self._observe_compile(fn.label, fn, args, names=fn.arg_names)
        with dispatch_phase, self.mesh:
            return fn(*args)

    def scope_tables(self):
        """``{module name: {program key: {instruction name: scope}}}``: what
        each instruction of the process's compiled programs is for, by the
        ``named_scope`` words of ``telemetry.hlo_cost.SCOPES`` — the join of
        a ``jax.profiler`` device trace to the program's own names
        (docs/observability.md, "Device time by scope"). ``Tracer
        .scope_tables`` by hand: builds the tables that are not built yet
        (one cached compile each) and returns every one the tracer holds."""
        return self._tracer.scope_tables()

    def slot_executables(self, kind: str, *dims,
                         quantized: Optional[bool] = None) -> int:
        """Compiled executables behind the pool program ``(kind, *dims)``
        (``dims`` as in its key: ``"slot_decode", num_slots, max_len``;
        ``"slot_chunk", num_slots, bucket, max_len``; ``"slot_verify",
        num_slots, max_len, k``) — the compile-once evidence the serving
        tests assert: 1 per pool flavour. ``quantized`` selects one
        flavour; None sums both."""
        flavours = (False, True) if quantized is None else (quantized,)
        fns = (self._slot_fns.get(self._pool_key(kind, dims, q))
               for q in flavours)
        return sum(fn._cache_size() for fn in fns if fn is not None)

    def _slot_arrays(self, toks, positions, temps, top_ks, top_ps, seeds):
        """The six per-slot arrays of a decode-shaped program on the
        device, in its argument order; a sampling array left ``None`` is
        the neutral one (greedy, no truncation)."""
        sampling = (temps, top_ks, top_ps, seeds)
        if any(x is None for x in sampling):
            sampling = tuple(
                d if x is None else x for x, d in zip(
                    sampling, sampling_arrays(len(np.reshape(positions, -1)))))
        return (jnp.asarray(toks, jnp.int32),
                jnp.asarray(positions, jnp.int32),
                *(jnp.asarray(x, dt) for x, dt in zip(
                    sampling,
                    (jnp.float32, jnp.int32, jnp.float32, jnp.int32))))

    def _init_pool(self, num_slots, max_len, quantize=False, draft=None):
        """An empty pool of the target model, or of ``draft``'s."""
        if draft is None:
            kind, dims, model = "slot_pool", (num_slots, max_len), self.module
        else:
            kind, dims, model = "draft_pool", \
                (num_slots, max_len, draft.key), draft.model

        @self._pool_program(kind, dims, (num_slots, max_len, quantize),
                            draft=draft)
        def init():
            return init_pool(model, num_slots, max_len, self.dtype, quantize)

        return self._pool_call(init, lambda: ())

    def init_slot_pool(self, num_slots: int, max_len: int,
                       quantize: bool = False):
        """Allocate the slot-pool KV cache [L, num_slots, max_len, H, hd],
        once, at static shape (token-major: one token's K or V of all
        heads is one row, so a decode step writes num_slots rows a layer;
        ``models/gpt2.py:init_kv_cache``). ``quantize=True`` allocates it
        int8 with per-row f32 scales [L, num_slots, max_len, H]
        (inference/kv_quant.py) — ~4x the slots per HBM byte; the slot
        programs take either flavour."""
        return self._init_pool(num_slots, max_len, quantize)

    def slot_prefill(self, pool, slot: int, prompt, temperature: float = 0.0,
                     top_k: int = 0, top_p: float = 1.0, seed: int = 0):
        """Prefill ``prompt`` (1-D int array) into ``pool`` slot ``slot`` and
        sample the first generated token. The prompt is right-padded to a
        pow2 bucket (one compile per bucket; pad K/V beyond the prompt is
        masked until overwritten by decode writes, and a model with a
        recurrent state is told the real length and stores its state at
        the prompt's last token: ``_real_length``). Sampling is
        deterministic per ``(seed, position)`` — replay-safe. Returns
        (new_pool, first_token:int)."""
        model = self.module
        vocab = model.config.vocab_size
        prompt = np.asarray(prompt, dtype=np.int32).reshape(-1)
        t = prompt.shape[0]
        shape = self._pool_dims(pool)
        max_len = shape[1]
        if not 0 < t <= max_len:
            raise ValueError(f"prompt length {t} not in [1, {max_len}]")
        bucket = min(_next_pow2(t), max_len)
        blocks = getattr(model, "block_length", 1) > 1
        if blocks and t % model.block_length:
            raise ValueError(
                f"a prompt of {t} tokens is no whole number of blocks of "
                f"{model.block_length}: the tokens left over open the first "
                f"block (slot_block_dispatch)")

        @self._pool_program("slot_prefill", (bucket, max_len), shape, outs=1)
        def pf(params, ids, pool, slot, last_idx, temperature, top_k,
               top_p, seed):
            with jax.named_scope("kv_write"):   # the lane, empty
                mini = model.init_kv_cache(1, max_len, dtype=self.dtype)
            # column 0 as a number, not an array: a model sees at trace
            # time that the bucket is a whole prefill (``prefill_kernel``)
            logits, mini, *stats = model.apply_with_cache(
                params, ids, mini, 0, routing=self._routed,
                **self._real_length(last_idx))
            with jax.named_scope("kv_write"):
                pool = write_lane(pool, mini, slot)
            if blocks:
                # whole blocks of context: the first tokens are the first
                # block's (``slot_block_dispatch``), the head is dead code
                tok = jnp.int32(0)
            else:
                # the first token is FED at column last_idx + 1
                with jax.named_scope("sample"):
                    last = jnp.take(logits[0], last_idx, axis=0)
                    tok = _sample_one(last, temperature, top_k, top_p, seed,
                                      last_idx + 1, vocab)
            # routed experts: [token, touched, largest], one read-back
            return pool, \
                jnp.concatenate([tok[None], *stats]) if stats else tok

        pool, tok = self._slot_prefill_call(
            pf, pool, slot, prompt, bucket,
            (np.int32(t - 1), np.float32(temperature), np.int32(top_k),
             np.float32(top_p), np.int32(seed)))
        with self._tracer.phase("serve/prefill_wait"):
            tok = self._read_back(np.asarray(tok).reshape(-1), 1)
        return pool, int(tok[0])

    def _real_length(self, last_idx):
        """What a prefill's body hands the cached forward beside its
        right-padded bucket: nothing for a model whose pool is K and V
        (padding columns are masked until overwritten, and its programs
        stay the ones they were), the row's real length for one with a
        recurrent state, which must be stored at the last real token."""
        return {"lengths": (last_idx + 1)[None]} if self._recurrent else {}

    def _lane_from(self, pool, slot, start_pos):
        """Slot ``slot``'s lane as the mini cache a suffix or chunk prefill
        goes on from at column ``start_pos``. K and V below it are the
        lane's; so is a recurrent state, except at column 0, where nothing
        came before: there it is zero, whatever the slot's last occupant
        or the dummy rows of the decode ticks since have left in it."""
        mini = read_lane(pool, slot, self.dtype)
        for name in getattr(self.module, "recurrent_state", ()):
            mini[name] = jnp.where(start_pos == 0, 0, mini[name])
        return mini

    def _read_back(self, out, n):
        """What a slot program read back: the first ``n`` entries are its
        tokens; a model with routed experts appends (experts touched,
        largest count any expert got), summed over layers — kept for
        ``take_routing``."""
        if not self._routed:
            return out
        self._routing = (int(out[n]), int(out[n + 1]))
        return out[:n]

    def take_routing(self):
        """(experts touched, largest count) of the last ``slot_prefill`` or
        ``slot_decode_step`` of a model with routed experts, once; ``None``
        after any other call and for a dense model."""
        routing, self._routing = self._routing, None
        return routing

    def _slot_prefill_call(self, fn, pool, slot, tokens, bucket,
                           scalars=(), draft=None):
        """Shared tail of the prefills: right-pad ``tokens`` to ``bucket``,
        put the ids and every scalar on the device, and call
        ``fn(params, ids, pool, slot, *scalars)``. The three slot
        prefills record it as two phases, ``serve/prefill_prep`` (tokens,
        bucket) up to the compile's observation and
        ``serve/prefill_dispatch`` (bucket), the call; the draft's
        prefill (``draft``: its parameters) rides an admission that has
        recorded its prefill already, and records none."""
        tr = self._tracer
        t = tokens.shape[0]

        def prep():
            ids = np.zeros((1, bucket), np.int32)
            ids[0, :t] = tokens
            return ((self if draft is None else draft).params,
                    jnp.asarray(ids), pool, jnp.int32(slot),
                    *map(jnp.asarray, scalars))

        return self._pool_call(
            fn, prep, _NO_PHASES if draft is not None else
            (tr.phase("serve/prefill_prep", t, bucket),
             tr.phase("serve/prefill_dispatch", bucket)))

    def slot_suffix_prefill(self, pool, slot: int, tokens, start_pos: int,
                            temperature: float = 0.0, top_k: int = 0,
                            top_p: float = 1.0, seed: int = 0):
        """Prefill only the SUFFIX ``tokens`` of a prompt into slot
        ``slot`` whose lane already holds valid K/V for cache columns
        ``[0, start_pos)`` — the prefix-reuse fast path
        (serving/fleet/prefix_cache.py): after ``slot_copy_lane`` from a
        cached donor, only the tokens past the shared prefix run through
        the stack. The suffix is right-padded to a pow2 bucket (one
        compile per bucket, shared with every start_pos — the offset is a
        traced scalar); callers size the bucket via
        ``prefix_cache.reuse_plan`` so ``start_pos + bucket <= max_len``.
        A recurrent state goes on from what the lane holds (from nothing
        at ``start_pos`` 0: ``_lane_from``), so for such a model the lane
        must have been prefilled up to exactly ``start_pos`` and no decode
        step may have run over the pool since: a step pushes a row into
        EVERY slot's state. Returns (new_pool, next_token:int)."""
        model = self.module
        vocab = model.config.vocab_size
        tokens = np.asarray(tokens, dtype=np.int32).reshape(-1)
        t = tokens.shape[0]
        shape = self._pool_dims(pool)
        max_len = shape[1]
        if t < 1:
            raise ValueError("suffix must carry at least one token (the "
                             "sampled next token needs a query position)")
        bucket = min(_next_pow2(t), max_len)
        if start_pos < 0 or start_pos + bucket > max_len:
            raise ValueError(
                f"suffix bucket [{start_pos}, {start_pos + bucket}) exceeds "
                f"max_len={max_len}; plan the reuse offset with "
                f"prefix_cache.reuse_plan")

        @self._pool_program("slot_suffix", (bucket, max_len), shape, outs=1)
        def spf(params, ids, pool, slot, start_pos, last_idx, temperature,
                top_k, top_p, seed):
            with jax.named_scope("kv_read"):
                mini = self._lane_from(pool, slot, start_pos)
            logits, mini = model.apply_with_cache(
                params, ids, mini, start_pos, **self._real_length(last_idx))
            with jax.named_scope("kv_write"):
                pool = write_lane(pool, mini, slot)
            with jax.named_scope("sample"):
                last = jnp.take(logits[0], last_idx, axis=0)
                tok = _sample_one(last, temperature, top_k, top_p, seed,
                                  start_pos + last_idx + 1, vocab)
            return pool, tok

        pool, tok = self._slot_prefill_call(
            spf, pool, slot, tokens, bucket,
            (np.int32(start_pos), np.int32(t - 1), np.float32(temperature),
             np.int32(top_k), np.float32(top_p), np.int32(seed)))
        with self._tracer.phase("serve/prefill_wait"):
            tok = int(tok)
        return pool, tok

    def slot_chunk_prefill(self, pool, slot: int, tokens, start_pos: int):
        """Write ONE CHUNK of a prompt's K/V into slot ``slot`` at cache
        columns ``[start_pos, start_pos+len(tokens))`` without sampling —
        the building block of chunked prefill (serving/scheduler.py): a
        long prompt is admitted as a sequence of fixed-size chunks
        interleaved with decode ticks, so no decode tick ever waits on
        more than ``chunk_tokens`` of prefill work. The chunk is
        right-padded to a pow2 bucket (one compiled program per
        (bucket, pool) flavor — the scheduler always sends full
        ``chunk_tokens`` chunks, so steady state is exactly ONE flavor);
        the logits head is dead code and XLA eliminates it
        (``chunk_prefill_with_cache``). Pad columns past the chunk hold
        garbage K/V until the next chunk (or a decode write) overwrites
        them, exactly like a fresh prefill's pad tail. The FINAL chunk of
        a prompt never comes through here — it runs
        ``slot_suffix_prefill`` so the first token is sampled at the same
        ``(seed, position)`` key a monolithic prefill would use (bitwise
        token parity). Returns the new pool."""
        model = self.module
        tokens = np.asarray(tokens, dtype=np.int32).reshape(-1)
        t = tokens.shape[0]
        shape = self._pool_dims(pool)
        num_slots, max_len, _ = shape
        if t < 1:
            raise ValueError("chunk must carry at least one token")
        bucket = min(_next_pow2(t), max_len)
        if start_pos < 0 or start_pos + bucket > max_len:
            raise ValueError(
                f"chunk bucket [{start_pos}, {start_pos + bucket}) exceeds "
                f"max_len={max_len}; size chunks so every bucket fits")
        if self._recurrent and t != bucket:
            raise ValueError(
                f"a chunk of {t} tokens would be padded to {bucket}: a model "
                f"with a recurrent state takes whole pow2 chunks (the state "
                f"is stored at the chunk's last token; a prompt's tail goes "
                f"through slot_suffix_prefill), one after another with no "
                f"decode step between them (``slot_suffix_prefill``)")

        @self._pool_program("slot_chunk", (num_slots, bucket, max_len),
                            shape)
        def cpf(params, ids, pool, slot, start_pos):
            with jax.named_scope("kv_read"):
                mini = self._lane_from(pool, slot, start_pos)
            mini = model.chunk_prefill_with_cache(params, ids, mini,
                                                  start_pos)
            with jax.named_scope("kv_write"):
                return write_lane(pool, mini, slot)

        return self._slot_prefill_call(cpf, pool, slot, tokens, bucket,
                                       (np.int32(start_pos),))

    def slot_copy_lane(self, pool, src: int, dst: int):
        """Copy slot ``src``'s whole cache lane over slot ``dst``'s —
        device-side, no host round-trip, quantized lanes copy their q and
        scale slices verbatim (no requantization). The prefix-reuse
        admission path: copy the donor lane, then suffix-prefill from the
        shared-prefix boundary; stale donor columns past the new request's
        length are masked until decode overwrites them, exactly like a
        fresh prefill's pad columns."""
        shape = self._pool_dims(pool)

        @self._pool_program("slot_copy", shape[:2], shape)
        def cp(pool, src, dst):
            return jax.tree.map(
                lambda leaf: lane_update(leaf, lane_slice(leaf, src), dst),
                pool)

        return self._pool_call(
            cp, lambda: (pool, jnp.int32(src), jnp.int32(dst)))

    def slot_extract_lane(self, pool, slot: int):
        """Slot ``slot``'s cache lane as a HOST pytree (np arrays) — the
        payload of a KVHandoff (serving/fleet/handoff.py). Quantized pools
        hand off their int8 q + f32 scale slices directly: the wire cost
        of a disaggregated prefill→decode transfer is the quantized lane,
        not a dequantized copy. The one pool program that returns no
        pool: the pool stays the caller's."""
        shape = self._pool_dims(pool)

        @self._pool_program("slot_extract", shape[:2], shape, outs=None)
        def ex(pool, slot):
            return jax.tree.map(lambda leaf: lane_slice(leaf, slot), pool)

        return jax.device_get(
            self._pool_call(ex, lambda: (pool, jnp.int32(slot))))

    def slot_insert_lane(self, pool, slot: int, lane):
        """Insert a lane (from ``slot_extract_lane``, possibly another
        replica's pool) into slot ``slot``. Handles every quantization
        pairing: fp lanes quantize on the way into a quantized pool,
        quantized lanes dequantize into an fp pool — so a prefill replica
        and a decode replica need not share a KV storage format (one
        program per pairing: the lane's flavour is part of the key)."""
        shape = self._pool_dims(pool)

        @self._pool_program("slot_insert",
                            shape[:2] + (is_quantized_pool(lane),), shape)
        def ins(pool, lane, slot):
            return insert_lane(pool, lane, slot, self.dtype)

        return self._pool_call(ins, lambda: (pool, lane, jnp.int32(slot)))

    def decode_kernel_block(self, num_slots: int, max_len: int):
        """The columns one block of the decode-attention kernel holds where
        ``slot_decode_step`` over a pool of this shape takes it, else
        ``None`` (``GPT2Model.decode_kernel_block``, asked under this
        engine's mesh and of the pool as the step sees it: an int8 pool is
        attended as its float copy)."""
        model = self.module
        with self.mesh:
            return model.decode_kernel_block(jax.eval_shape(
                lambda: model.init_kv_cache(num_slots, max_len,
                                            dtype=self.dtype)))

    def prefill_kernel(self, tokens: int, max_len: int) -> bool:
        """Whether ``slot_prefill`` of a prompt of ``tokens`` into a lane
        of ``max_len`` columns attends the bucket's own keys in the packed
        flash kernel (``GPT2Model.prefill_kernel``, asked under this
        engine's mesh and of the empty lane the program builds, once a
        bucket)."""
        model = self.module
        key = (min(_next_pow2(tokens), max_len), max_len)
        if key not in self._prefill_kernel:
            with self.mesh:
                self._prefill_kernel[key] = model.prefill_kernel(
                    jax.eval_shape(lambda: model.init_kv_cache(
                        1, max_len, dtype=self.dtype)),
                    key[0], 0, None, self.dtype)
        return self._prefill_kernel[key]

    def slot_decode_step(self, pool, toks, positions, temps, top_ks=None,
                         top_ps=None, seeds=None):
        """One fused decode step over ALL slots: feed token ``toks[s]`` at
        cache column ``positions[s]`` and sample the next token per slot
        (greedy where temps[s] <= 0; per-row top-k/top-p with keys
        derived from ``(seeds[s], position)`` otherwise — deterministic
        replay). Inactive slots pass dummy inputs and their outputs are
        ignored by the scheduler. Returns (new_pool, next_tokens [S]):
        ``slot_decode_dispatch`` and ``slot_decode_read`` in a row."""
        pool, out = self.slot_decode_dispatch(
            pool, toks, positions, temps, top_ks=top_ks, top_ps=top_ps,
            seeds=seeds)
        return pool, self.slot_decode_read(out)

    def slot_decode_dispatch(self, pool, toks, positions, temps,
                             top_ks=None, top_ps=None, seeds=None,
                             prev=None, from_host=None):
        """The first half of ``slot_decode_step``: send the step and return
        (new_pool, out) with ``out`` still on the device, un-read (the
        sampled tokens [S]; a model with routed experts appends its two
        stats). A scheduler that keeps a step in flight hands the next
        call ``prev``, the ``out`` of the call before, and ``from_host``
        [S] bool: a row feeds ``toks[s]`` where it is set (a slot bound
        since that call: its token came from a prefill's read-back) and
        ``prev[s]`` where it lies otherwise, so step k + 1 can be sent
        before step k is read. Left out, every row feeds ``toks``. ONE
        program either way."""
        model = self.module
        vocab = model.config.vocab_size
        shape = self._pool_dims(pool)
        num_slots = shape[0]

        @self._pool_program("slot_decode", shape[:2], shape, outs=1)
        def dec(params, pool, toks, positions, temps, top_ks, top_ps,
                seeds, prev, from_host):
            toks = jnp.where(from_host, toks, prev[:toks.shape[0]])
            # an int8 pool is seen whole as fp by the step and stored back
            # on the way out: per-column scales make the round-trip of
            # every column this step did not write exact
            logits, fp, *stats = model.decode_with_slots(
                params, toks[:, None], pool_to_fp(pool, self.dtype),
                positions, routing=self._routed)
            # the sampled token will be FED at column positions + 1
            with jax.named_scope("sample"):
                keys = row_keys(seeds, positions + 1)
                nxt = sample_rows(logits[:, -1], temps, top_ks,
                                  top_ps, keys, vocab)
            # routed experts: the stats ride behind the tokens
            return pool_from_fp(fp, pool), \
                jnp.concatenate([nxt, *stats]) if stats else nxt

        def prep():
            # zeros where nothing was in flight, placed as the program's
            # output is: the same input type, so the same executable
            fed = jax.device_put(
                np.zeros(num_slots + 2 * self._routed, np.int32),
                NamedSharding(self.mesh, P())) if prev is None else prev
            mask = np.ones(num_slots, bool) if from_host is None \
                else from_host
            return (self.params, pool, *self._slot_arrays(
                toks, positions, temps, top_ks, top_ps, seeds), fed,
                jnp.asarray(mask, bool))

        tr = self._tracer
        return self._pool_call(
            dec, prep, (tr.phase("serve/decode_prep"),
                        tr.phase("serve/decode_dispatch")))

    def slot_decode_read(self, out):
        """The second half of ``slot_decode_step``: wait for a dispatched
        step and read its tokens back as a host array [S] (a routed
        model's stats are kept for ``take_routing``)."""
        with self._tracer.phase("serve/decode_wait"):
            out = np.asarray(out)
            return self._read_back(out, out.shape[0] - 2 * self._routed)

    # ------------------------------------------------- block-diffusion protocol
    def slot_block_dispatch(self, pool, ids, flags, positions, temps,
                            top_ks=None, top_ps=None, seeds=None, fix=1,
                            prev=None, from_host=None):
        """One PASS over all slots of a family that generates by diffusion
        over blocks (``model.block_length`` B > 1): slot s's block ``ids[s]``
        [B] stands at columns ``positions[s] .. positions[s] + B - 1``, the
        ``[MASK]`` row at the positions ``flags[s]`` says are still masked.
        The pass forwards it under the family's block mask, writes the B
        columns' keys and values (a later pass of the block overwrites
        them; the pass that finds nothing flagged leaves the final ones),
        and fixes of each slot's flagged positions the ``fix`` most
        confident (``speculative.unmask_rows``). Returns (new_pool, out)
        with ``out`` on the device, un-read: the blocks and flags as they
        stand after the pass (a routed model's two stats behind them).
        ``prev`` / ``from_host`` as in ``slot_decode_dispatch``: a row whose
        ``from_host`` is unset takes its block and flags from ``prev``, the
        ``out`` of the pass before, on the device, so a scheduler sends
        pass k + 1 before it reads pass k. ONE program a pool shape and
        ``fix``, whatever the passes are of (unmasking or writing)."""
        model = self.module
        vocab = model.config.vocab_size
        b, mask_id = model.block_length, model.mask_token_id
        shape = self._pool_dims(pool)
        num_slots = shape[0]

        @self._pool_program("slot_block", shape[:2] + (fix,), shape, outs=1)
        def blk(params, pool, ids, flags, positions, temps, top_ks, top_ps,
                seeds, prev, from_host):
            held = prev[:num_slots * 2 * b].reshape(num_slots, 2, b)
            ids = jnp.where(from_host[:, None], ids, held[:, 0])
            flags = jnp.where(from_host[:, None], flags, held[:, 1] > 0)
            logits, fp, *stats = model.verify_with_slots(
                params, jnp.where(flags, mask_id, ids),
                pool_to_fp(pool, self.dtype), positions,
                routing=self._routed)
            # a position's key is its own column's, whatever the pass
            with jax.named_scope("unmask"):
                cols = positions[:, None] + jnp.arange(b)[None, :]
                ids, flags = unmask_rows(
                    logits, ids, flags, temps, top_ks, top_ps,
                    row_keys(jnp.repeat(seeds, b), cols.reshape(-1)),
                    vocab, fix)
                out = jnp.stack([ids, flags.astype(jnp.int32)],
                                axis=1).reshape(-1)
            return pool_from_fp(fp, pool), \
                jnp.concatenate([out, *stats]) if stats else out

        def prep():
            fed = jax.device_put(
                np.zeros(num_slots * 2 * b + 2 * self._routed, np.int32),
                NamedSharding(self.mesh, P())) if prev is None else prev
            mask = np.ones(num_slots, bool) if from_host is None \
                else from_host
            block, pos, *sampling = self._slot_arrays(
                ids, positions, temps, top_ks, top_ps, seeds)
            return (self.params, pool, block, jnp.asarray(flags, bool), pos,
                    *sampling, fed, jnp.asarray(mask, bool))

        tr = self._tracer
        return self._pool_call(
            blk, prep, (tr.phase("serve/decode_prep"),
                        tr.phase("serve/decode_dispatch")))

    def slot_block_read(self, out):
        """Wait for a dispatched pass and read it back: (ids [S, B], flags
        [S, B] bool) as they stand after it (a routed model's stats are
        kept for ``take_routing``)."""
        with self._tracer.phase("serve/decode_wait"):
            out = np.asarray(out)
            out = self._read_back(out, out.shape[0] - 2 * self._routed)
        out = out.reshape(-1, 2, self.module.block_length)
        return out[:, 0], out[:, 1] > 0

    # -------------------------------------------- speculative decode protocol
    # Draft-model speculation over the slot pool (inference/speculative.py):
    # a cheap draft proposes K tokens per slot in ONE compiled lax.scan,
    # the target verifies all K in ONE batched verify_with_slots forward,
    # and per-slot accept/rollback of KV columns happens INSIDE the
    # compiled verify step. Both pools are donated like any other
    # (``_pool_program``; ds_tpu_lint HLO005 audits the lowered programs).

    def init_draft(self, draft_cfg):
        """Build (or fetch the cached) DraftRuntime for ``draft_cfg`` —
        co-resident replicas sharing this engine share draft weights."""
        from .speculative import build_draft, draft_key
        if not hasattr(self, "_drafts"):
            self._drafts: Dict[Any, Any] = {}
        key = draft_key(draft_cfg)
        draft = self._drafts.get(key)
        if draft is None:
            draft = self._drafts[key] = build_draft(self, draft_cfg)
            log_dist(f"InferenceEngine: draft runtime ready "
                     f"({draft.describe})", ranks=[0])
        return draft

    def init_draft_pool(self, draft, num_slots: int, max_len: int):
        """Allocate the draft model's slot-pool KV cache (fp — the draft
        is already the cheap side of the trade), once, at static shape."""
        return self._init_pool(num_slots, max_len, draft=draft)

    def draft_prefill(self, draft, dpool, slot: int, prompt):
        """Prefill ``prompt`` into the DRAFT pool's slot lane (pow2
        buckets like slot_prefill; logits are discarded — only the K/V
        matter, XLA dead-code-eliminates the head). The draft pool is
        donated. Returns the new draft pool."""
        prompt = np.asarray(prompt, dtype=np.int32).reshape(-1)
        t = prompt.shape[0]
        shape = self._pool_dims(dpool, draft.model)
        num_slots, max_len, _ = shape
        if not 0 < t <= max_len:
            raise ValueError(f"prompt length {t} not in [1, {max_len}]")
        bucket = min(_next_pow2(t), max_len)

        @self._pool_program("draft_prefill",
                            (bucket, num_slots, max_len, draft.key), shape,
                            draft=draft)
        def dpf(draft_params, ids, draft_pool, slot):
            mini = draft.model.init_kv_cache(1, max_len, dtype=self.dtype)
            _logits, mini = draft.model.apply_with_cache(
                draft_params, ids, mini, jnp.int32(0))
            return write_lane(draft_pool, mini, slot)

        return self._slot_prefill_call(dpf, dpool, slot, prompt, bucket,
                                       draft=draft)

    def slot_draft_propose(self, draft, dpool, toks, positions, temps,
                           top_ks, top_ps, seeds, k: int):
        """Propose ``k`` draft tokens per slot: a single compiled
        ``lax.scan`` of k+1 draft decode steps (the extra step writes the
        last proposal's K/V so a fully-accepted block leaves no gap in
        the draft lane). The draft samples with the SAME
        ``(seed, column)`` keys the target verify uses — the coupling
        that maximizes exact-match acceptance. Draft pool donated.
        Returns (new_dpool, draft_tokens [S, k])."""
        vocab = draft.model.config.vocab_size
        shape = self._pool_dims(dpool, draft.model)

        @self._pool_program("slot_draft", shape[:2] + (int(k), draft.key),
                            shape, outs=1, draft=draft)
        def prop(draft_params, draft_pool, toks, positions, temps, top_ks,
                 top_ps, seeds):
            def body(carry, _):
                draft_pool, tok, pos = carry
                logits, draft_pool = draft.model.decode_with_slots(
                    draft_params, tok[:, None], draft_pool, pos)
                keys = row_keys(seeds, pos + 1)
                nxt = sample_rows(logits[:, -1], temps, top_ks, top_ps,
                                  keys, vocab)
                return (draft_pool, nxt, pos + 1), nxt

            (draft_pool, _, _), drafts = lax.scan(
                body, (draft_pool, toks, positions), None, length=k + 1)
            return draft_pool, jnp.transpose(drafts[:k])      # [S, k]

        dpool, drafts = self._pool_call(
            prop, lambda: (draft.params, dpool, *self._slot_arrays(
                toks, positions, temps, top_ks, top_ps, seeds)))
        return dpool, np.asarray(drafts)

    def slot_verify_step(self, pool, toks, draft_toks, positions, temps,
                         top_ks=None, top_ps=None, seeds=None):
        """Verify ``k`` draft tokens per slot in ONE batched forward and
        advance every slot by its accepted prefix plus one target token.
        Acceptance is EXACT MATCH against the target's own deterministic
        per-position sample (greedy argmax at temps<=0), so the emitted
        stream is bitwise what the non-speculative path would emit.
        Rejected KV columns are rolled back INSIDE the compiled step:
        every column past ``positions[s] + accepts[s]`` is restored to
        its pre-verify value (for int8 pools the restore is exact by the
        per-column-scale round-trip guarantee). The target pool is
        donated. Returns (new_pool, target_tokens [S, k+1],
        accepts [S] in [0, k]) — the emitted tokens for slot s are
        ``target_tokens[s, :accepts[s] + 1]``."""
        model = self.module
        vocab = model.config.vocab_size
        shape = self._pool_dims(pool)
        max_len = shape[1]
        draft_toks = np.asarray(draft_toks, np.int32)
        k = int(draft_toks.shape[1])

        @self._pool_program("slot_verify", shape[:2] + (k,), shape, outs=2)
        def ver(params, pool, toks, draft_toks, positions, temps, top_ks,
                top_ps, seeds):
            block = jnp.concatenate([toks[:, None], draft_toks], axis=1)
            logits, fp_new = model.verify_with_slots(
                params, block, pool_to_fp(pool, self.dtype),
                positions)                                 # [S, k+1, V]
            # target's candidate at offset j would be FED at column
            # positions + j + 1 — the same key the plain decode path
            # (and the draft) derives for that position
            with jax.named_scope("verify"):
                cols = positions[:, None] + 1 + \
                    jnp.arange(k + 1)[None, :]             # [S, k+1]
                tgt = jax.vmap(
                    lambda lg, cs: sample_rows(
                        lg, temps, top_ks, top_ps,
                        row_keys(seeds, cs), vocab),
                    in_axes=(1, 1), out_axes=1)(logits, cols)
                match = (draft_toks == tgt[:, :k]).astype(jnp.int32)
                accepts = jnp.sum(jnp.cumprod(match, axis=1), axis=1)
            # rollback INSIDE the step: only columns this verify
            # WROTE and the accept prefix covers keep their new
            # values — everything else (untouched columns AND
            # rejected writes) restores to the pre-verify lane
            with jax.named_scope("verify"):
                cols_ax = jnp.arange(max_len)[None, :]
                keep = (cols_ax >= positions[:, None]) & \
                    (cols_ax <= (positions + accepts)[:, None])  # [S, C]

                def rb(new, old):
                    # keep [S, C] over a leaf [L, S, C, ...]
                    return jnp.where(keep.reshape(
                        (1,) + keep.shape + (1,) * (new.ndim - 3)),
                        new, old)

                # the restore happens in the pool's own storage: for an
                # int8 pool the original q/scale BYTES are copied verbatim
                # for every non-kept column, so rolled-back int8 lanes are
                # bit-exact — the untouched-column guarantee by
                # construction, immune even to ulp-level requantization
                # drift
                out_pool = jax.tree.map(rb, pool_from_fp(fp_new, pool), pool)
            return out_pool, tgt, accepts.astype(jnp.int32)

        def prep():
            toks_d, *rest = self._slot_arrays(toks, positions, temps, top_ks,
                                              top_ps, seeds)
            return (self.params, pool, toks_d,
                    jnp.asarray(draft_toks, jnp.int32), *rest)

        pool, tgt, accepts = self._pool_call(ver, prep)
        return pool, np.asarray(tgt), np.asarray(accepts)

    # ------------------------------------------------------------- properties
    @property
    def config(self):
        return self._config

    @property
    def mp_world_size(self):
        return self.mesh_manager.tp

    def eval(self):
        return self

    def half(self):
        """Reference API: cast to fp16 (here: the configured low dtype)."""
        self.params = self.recast(self.params)
        return self
