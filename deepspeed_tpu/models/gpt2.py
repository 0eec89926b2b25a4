"""GPT-2 family — the flagship training model.

TPU-native design (not a port of any torch modeling file): parameters are a
flat pytree with **stacked** per-layer leaves ([L, ...] leading layer dim) so
the decoder runs as one ``lax.scan`` over layers. That gives O(1) compile time
in depth, makes ``jax.checkpoint`` (activation checkpointing, reference
runtime/activation_checkpointing/checkpointing.py) a one-line policy, and is
the shape ZeRO-3 wants: leaves sharded over the dp axes are gathered
layer-by-layer inside the scan, which XLA overlaps with compute — replacing
the reference's entire fetch/prefetch coordinator
(runtime/zero/partitioned_param_coordinator.py).

Attention dispatches to the flash-attention op (Pallas on TPU).
"""

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .api import ModelSpec
from ..ops import memory_efficient as me
from ..ops.seq_parallel import sp_attention


@dataclasses.dataclass(frozen=True)
class GPT2Config:
    vocab_size: int = 50257
    n_positions: int = 1024
    n_embd: int = 768
    n_layer: int = 12
    n_head: int = 12
    dropout: float = 0.0
    layer_norm_epsilon: float = 1e-5
    initializer_range: float = 0.02
    activation: str = "gelu"       # gelu | relu (OPT family)
    mlp_ratio: int = 4
    pos_offset: int = 0            # learned-position offset (OPT uses 2)
    remat: bool = False            # activation checkpointing over the layer scan
    remat_policy: Optional[str] = None  # see runtime/activation_checkpointing
    # layer-scan unroll factor (forwarded to lax.scan). 1 = rolled while
    # loop (O(1) compile). >= n_layer inlines every layer into the step
    # program — what the bucketed ZeRO overlap schedule
    # (runtime/zero/overlap_schedule.py) needs so per-layer-chunk
    # collectives get per-layer compute between issue and first use
    # instead of one opaque while op
    scan_unroll: int = 1
    # vocab-chunked online-softmax loss: "auto" = only when the full logits
    # tensor would be large (the chunked path trades ~one extra vocab matmul
    # of recompute for never materializing [B,T,V])
    loss_chunking: str = "auto"    # auto | always | never
    loss_chunk_target: int = 8192  # vocab-chunk width of the chunked loss
    attn_backend: str = "auto"     # auto | pallas | xla
    sp_attention: str = "ulysses"  # ulysses | ring (when the 'seq' axis is live)
    dtype: str = "float32"         # compute dtype; params always fp32 masters
    pad_vocab_to_multiple: int = 128

    @property
    def padded_vocab(self):
        m = self.pad_vocab_to_multiple
        return ((self.vocab_size + m - 1) // m) * m

    @property
    def head_dim(self):
        return self.n_embd // self.n_head


# presets matching BASELINE.md configs
GPT2_125M = GPT2Config(n_embd=768, n_layer=12, n_head=12)
GPT2_350M = GPT2Config(n_embd=1024, n_layer=24, n_head=16)
GPT2_760M = GPT2Config(n_embd=1536, n_layer=24, n_head=16)
GPT2_1_3B = GPT2Config(n_embd=2048, n_layer=24, n_head=32)


def _activation(x, name):
    """gelu = tanh approximation (GPT-2 'gelu_new'); gelu_exact = erf GELU
    (HF 'gelu', the NeoX/BERT default). All route through the
    memory-efficient custom-VJP ops (ops/memory_efficient.py) whose
    backward recomputes from the input instead of stashing wide
    intermediates."""
    if name == "relu":
        return jax.nn.relu(x)
    if name == "gelu":
        return me.gelu(x)
    if name == "gelu_exact":
        return me.gelu_exact(x)
    if name == "silu":
        return me.silu(x)
    if name == "quick_gelu":             # CLIP: x * sigmoid(1.702 x)
        return me.quick_gelu(x)
    raise ValueError(f"unknown activation {name!r}")


def _token_dropout(x, rng, train, salt, rate):
    if not train or rate == 0.0 or rng is None:
        return x
    key = jax.random.fold_in(rng, salt)
    keep = jax.random.bernoulli(key, 1.0 - rate, x.shape)
    return x * keep / (1.0 - rate)


def _params_compute_dtype(params, fallback):
    """Compute dtype follows the param dtype (engine casts fp32 masters to
    bf16/fp16 before apply — the mixed-precision contract)."""
    wte_dtype = params["wte"].dtype
    return (wte_dtype if jnp.issubdtype(wte_dtype, jnp.floating)
            else jnp.dtype(fallback))


#: lanes of a TPU vector row: XLA pads a narrower minor dimension up to it
_LANES = 128


def _kv_row_shape(kv_heads: int, head_dim: int):
    """``(G, W)``: how the KV pool stores one token's ``Hk * hd`` values of a
    layer. ``(Hk, hd)``; but where a head is narrower than a vector row and
    a whole number of heads fills one (hd 64: two), that many heads share a
    stored row, ``(Hk * hd / 128, 128)`` — the same bytes in the same
    order. On the chip a minor dimension under 128 lanes is padded up to it:
    the unpacked shape would make the compiler double a bf16 hd-64 pool, or
    lay it out position-minor and copy all of it to write one token."""
    pack = _LANES // head_dim if _LANES % head_dim == 0 else 1
    if kv_heads % pack:
        pack = 1
    return kv_heads // pack, head_dim * pack


def _latent_row_width(values: int) -> int:
    """The stored width of a pool row of ``values`` values that is no
    whole number of vector rows: the next multiple of 128 lanes, the rest
    zeros (``_kv_row_shape``'s rule met from the other side). Compiled for
    a v5e, a bfloat16 leaf ``[L, S, max_len, 1, 576]`` is laid out with
    ``max_len`` in the lanes (576 is 72 sublane rows of 8: nothing to pad),
    and the decode step, which wants a token's row in the lanes, then
    copies the whole pool in and out of its layer loop (6.3 GB of
    temporaries at 48 slots x 8192); the tiled row of 576 takes 640 lanes
    either way."""
    return -(-values // _LANES) * _LANES


def _layer_norm(x, scale, bias, eps):
    return me.layer_norm(x, scale, bias, eps)


def _einsum_f32(spec, a, b):
    """``einsum`` of two arrays of one type, accumulated and handed back in
    float32: what the MXU does with bfloat16 operands at no cost, where a
    bfloat16 result would round the sums. The CPU's dot has no bfloat16
    pair with a float32 result: there (tests, rehearsals) the operands are
    widened first."""
    from ..parallel.topology import on_tpu
    if not on_tpu() and a.dtype != jnp.float32:
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return jnp.einsum(spec, a, b, preferred_element_type=jnp.float32)


class GPT2Model(ModelSpec):

    def __init__(self, config: GPT2Config = GPT2_125M):
        self.config = config

    # ------------------------------------------------------------------ init
    def init(self, rng):
        cfg = self.config
        d, l, v = cfg.n_embd, cfg.n_layer, cfg.padded_vocab
        std = cfg.initializer_range
        proj_std = std / math.sqrt(2 * l)
        keys = jax.random.split(rng, 8)

        def norm(key, shape, s):
            return (jax.random.normal(key, shape, jnp.float32) * s)

        blocks = {
            "ln1_scale": jnp.ones((l, d)),
            "ln1_bias": jnp.zeros((l, d)),
            "qkv_w": norm(keys[0], (l, d, 3 * d), std),
            "qkv_b": jnp.zeros((l, 3 * d)),
            "attn_proj_w": norm(keys[1], (l, d, d), proj_std),
            "attn_proj_b": jnp.zeros((l, d)),
            "ln2_scale": jnp.ones((l, d)),
            "ln2_bias": jnp.zeros((l, d)),
            "mlp_fc_w": norm(keys[2], (l, d, cfg.mlp_ratio * d), std),
            "mlp_fc_b": jnp.zeros((l, cfg.mlp_ratio * d)),
            "mlp_proj_w": norm(keys[3], (l, cfg.mlp_ratio * d, d), proj_std),
            "mlp_proj_b": jnp.zeros((l, d)),
        }
        return {
            "wte": norm(keys[4], (v, d), std),
            "wpe": norm(keys[5], (cfg.n_positions + cfg.pos_offset, d), std),
            "blocks": blocks,
            "ln_f_scale": jnp.ones((d,)),
            "ln_f_bias": jnp.zeros((d,)),
        }

    # ------------------------------------------------- family hook points
    # Subclass families (LLaMA/BLOOM/NeoX/BERT) override these instead of
    # re-implementing hidden_states / apply_with_cache / pipeline_spec.
    has_position_table = True   # families without a wpe table set False
    causal_attention = True     # bidirectional towers (CLIP vision) set False
    #: leaves of ``init_kv_cache`` that hold a RECURRENT state: what a layer
    #: keeps of a lane is the state after its last token, ``[L', S, n, d]``,
    #: not a row per token (``_state_shift``). A family that names one must
    #: be told each row's real length by whoever pads it
    #: (``_forward_with_cache``'s ``lengths``), and what is valid "up to any
    #: column" of a KV lane (a shared prefix, a rolled-back draft) is not
    #: valid of it: ``ServingEngine`` fences those mechanisms
    recurrent_state = ()
    #: the two leaves of ``init_kv_cache`` (keys, values) that are RINGS of
    #: a window layer's last W columns, ``[L', S, W, G, w]``, position p in
    #: column ``p mod W`` (``_window_attend``); such a layer hands its
    #: ``attn_fn`` ``ring=`` them. Like a recurrent state a ring is valid
    #: at the lane's end alone (``lane_end_state``)
    window_rings = ()
    #: the one leaf of ``init_kv_cache`` that holds a LATENT row a token,
    #: ``[L, S, max_len, 1, w]`` (``_latent_row_width``), in place of ``k``
    #: and ``v``: what a
    #: layer's keys and values are expanded from, or attended through
    #: (``_latent_attend``). Such a layer hands its ``attn_fn`` the row as
    #: ``k``, ``v=None`` and ``latent=`` the up-projection. A row per token,
    #: so the lane is valid up to any column, as K and V are
    latent_cache = ()
    #: positions a pass of the serving tick advances a slot by: 1, a token
    #: a step; a family that generates by diffusion over blocks
    #: (``models/sdar.py``) says its block's length, and its mask keeps a
    #: query's whole block (``_decode_attn_mask``)
    block_length = 1
    #: the pool leaves whose columns past the last whole block are
    #: PROVISIONAL in such a family: every pass of a block writes its
    #: columns and only the pass after the last unmasking leaves them
    #: final, so a lane is valid up to a block's boundary and no further
    denoised_blocks = ()

    @property
    def lane_leaves(self):
        """The pool leaves that keep a row per token over the lane's full
        length, the first of which says ``max_len`` (``_pool_dims``): ``k``
        and ``v``, or the family's latent leaf."""
        return tuple(self.latent_cache) or ("k", "v")

    @property
    def lane_end_state(self):
        """The pool leaves that hold a lane as it stands after its LAST
        token, not a row per token: whoever pads a row must say its real
        length, and nothing may go on from an earlier column of the lane."""
        return tuple(self.recurrent_state) + tuple(self.window_rings)

    def _compute_dtype(self, params):
        return _params_compute_dtype(params, self.config.dtype)

    def _embed(self, params, input_ids, start_pos=0, positions=None):
        """Token + learned-position embeddings in compute dtype (no dropout).
        ``start_pos`` may be a traced scalar (decode); ``positions`` [B, T]
        overrides it for per-row offsets (left-padded serving batches)."""
        cfg = self.config
        dt = self._compute_dtype(params)
        t = input_ids.shape[-1]
        if positions is not None:
            wpe = params["wpe"].astype(dt)[positions + cfg.pos_offset]
        else:
            wpe = lax.dynamic_slice(
                params["wpe"], (start_pos + cfg.pos_offset, 0),
                (t, cfg.n_embd)).astype(dt)
        return params["wte"].astype(dt)[input_ids] + wpe

    def _final_norm(self, params, x):
        return _layer_norm(x, params["ln_f_scale"], params["ln_f_bias"],
                           self.config.layer_norm_epsilon)

    def _open_streams(self, x):
        """What the layers carry, from the embedding ``x`` [B, T, D]: ``x``.
        A family that widens the residual path (``models/xing.py``: several
        streams a token, mixed around every sublayer) opens them here, after
        ``embed``, and closes them before ``head`` (``_close_streams``);
        its ``_block`` / ``_decode_block`` take and give what this gives."""
        return x

    def _close_streams(self, x):
        """The layers' carry as the one row a token [B, T, D] that the
        final norm and the head take."""
        return x

    def _unembed_weight(self, params, dtype):
        """[V, D] weight of the LM head (tied to wte for GPT-2/OPT)."""
        return params["wte"].astype(dtype)

    def _head_bias(self, params, dtype):
        """[V] LM-head bias or None (GPT-J has one)."""
        return None

    @property
    def kv_heads(self) -> int:
        return self.config.n_head

    # ----------------------------------------------------------------- block
    def _attn_sublayer(self, x, p, rng, train, attn_fn=None, start_pos=0,
                       positions=None, extra=None):
        """ln1 → qkv → flash attention → proj → residual (+dropout).

        ``attn_fn(q, k, v) -> attn`` overrides the attention inner — the
        decode path injects its KV-cache attention here so train and serve
        share one block implementation."""
        cfg = self.config
        b, t, d = x.shape
        h, hd = cfg.n_head, cfg.head_dim
        with jax.named_scope("qkv"):
            ln1 = _layer_norm(x, p["ln1_scale"], p["ln1_bias"],
                              cfg.layer_norm_epsilon)
            qkv = ln1 @ p["qkv_w"].astype(ln1.dtype) + \
                p["qkv_b"].astype(ln1.dtype)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        bias = None if attn_fn is not None else self._train_attn_bias_ex(
            t, extra)
        dropping = train and cfg.dropout > 0 and rng is not None
        if (attn_fn is None and bias is None and not dropping and
                self.causal_attention and self._packed_attn_ok(t, hd, h)):
            # packed [B, T, H*D] Pallas path: q/k/v stay in the layout the
            # qkv matmul produced — no head transposes in fwd OR bwd, and
            # no duplicate [B,H,T,D] residual save (round-3 profiling:
            # ~5 ms/micro of relayout copies at 125M)
            from ..ops.flash_attention import pallas_per_device
            from ..ops.pallas.flash_attention_packed import \
                packed_flash_attention
            from ..parallel.topology import on_tpu
            interpret = not on_tpu()
            attn = pallas_per_device(
                lambda q, k, v, n: packed_flash_attention(
                    q, k, v, n, interpret=interpret),
                q, k, v, h, packed=True)
            with jax.named_scope("out_proj"):
                attn = attn @ p["attn_proj_w"].astype(attn.dtype) + \
                    p["attn_proj_b"].astype(attn.dtype)
                return x + self._dropout(attn, rng, train, 0)
        q = q.reshape(b, t, h, hd).transpose(0, 2, 1, 3)
        k = k.reshape(b, t, h, hd).transpose(0, 2, 1, 3)
        v = v.reshape(b, t, h, hd).transpose(0, 2, 1, 3)
        if attn_fn is not None:
            attn = attn_fn(q, k, v)
        else:
            drop_rng = None
            if dropping:
                drop_rng = jax.random.fold_in(rng, 3)
            attn = sp_attention(q, k, v, causal=self.causal_attention,
                                dropout_rate=cfg.dropout if train else 0.0,
                                dropout_rng=drop_rng, impl=cfg.sp_attention,
                                backend=cfg.attn_backend,
                                bias=bias)
        with jax.named_scope("out_proj"):
            attn = attn.transpose(0, 2, 1, 3).reshape(b, t, d)
            attn = attn @ p["attn_proj_w"].astype(attn.dtype) + \
                p["attn_proj_b"].astype(attn.dtype)
            return x + self._dropout(attn, rng, train, 0)

    def _packed_attn_ok(self, t: int, hd: int, h: int) -> bool:
        """Packed-layout Pallas attention eligibility: TPU pallas backend,
        no live 'seq' axis (sp uses the [B,H,T,D] kernels), and shapes the
        packed kernel supports."""
        from ..ops.pallas.flash_attention_packed import supported
        from ..ops.seq_parallel import seq_axis_size
        from ..parallel.constraints import active_mesh
        from ..parallel.topology import MODEL_AXIS, on_tpu
        # auto engages on real TPU; backend 'pallas' also engages on CPU
        # (interpret mode — the parity-test path)
        if self.config.attn_backend == "pallas":
            pass
        elif self.config.attn_backend != "auto" or not on_tpu():
            return False
        # under tensor parallelism each device's kernel sees h / tp heads
        # (ops/flash_attention.pallas_per_device)
        mesh = active_mesh()
        tp = int(mesh.shape.get(MODEL_AXIS, 1)) if mesh is not None else 1
        if h % tp:
            tp = 1
        return seq_axis_size() == 1 and supported(t, hd, h // tp, True, None)

    def _mlp_sublayer(self, x, p, rng, train):
        """ln2 → fc → gelu → proj → residual (+dropout). Returns (x, aux)."""
        cfg = self.config
        ln2 = _layer_norm(x, p["ln2_scale"], p["ln2_bias"], cfg.layer_norm_epsilon)
        hmid = ln2 @ p["mlp_fc_w"].astype(ln2.dtype) + p["mlp_fc_b"].astype(ln2.dtype)
        hmid = _activation(hmid, cfg.activation)
        out = hmid @ p["mlp_proj_w"].astype(hmid.dtype) + p["mlp_proj_b"].astype(hmid.dtype)
        return x + self._dropout(out, rng, train, 1), jnp.float32(0.0)

    def _block(self, x, layer_params, rng, train, extra=None, **routed):
        """One decoder block. Returns (x, aux_loss) — aux is nonzero only for
        MoE variants. ``extra``: this layer's slice of _layer_extras().
        ``routed``: ``stacked=(whole, layer)`` from a serving scan whose
        family left leaves whole (``_scan_split``), for its MLP sublayer.

        The ``named_scope`` words (``telemetry.hlo_cost.SCOPES``) feed the
        flops profiler's per-phase attribution and survive into the
        compiled program's ``op_name`` metadata, where
        ``hlo_cost.scope_table`` reads them to name a device trace's
        operations — they cost nothing at runtime."""
        with jax.named_scope("attn"):
            x = self._attn_sublayer(x, layer_params, rng, train, extra=extra)
        with jax.named_scope("mlp"):
            return self._mlp_sublayer(x, layer_params, rng, train, **routed)

    def _decode_block(self, x, layer_params, attn_fn, start_pos,
                      positions=None, extra=None):
        """One block on the KV-cache decode path (no dropout/rng). Returns
        x; a family with a routed expert layer returns ``(x, exp_counts)``
        (rows each expert got), which the cache forwards sum up over layers
        for ``routing=True`` callers."""
        with jax.named_scope("attn"):
            x = self._attn_sublayer(x, layer_params, None, False,
                                    attn_fn=attn_fn, start_pos=start_pos,
                                    positions=positions, extra=extra)
        with jax.named_scope("mlp"):
            x, _ = self._mlp_sublayer(x, layer_params, None, False)
        return x

    @staticmethod
    def _split_routing(out):
        """``_decode_block``'s return as ``(x, stats)``: stats is None for a
        dense block, else int32 [2] = (experts that got a row, the largest
        count any one expert got) of this layer."""
        if not isinstance(out, tuple):
            return out, None
        x, counts = out
        return x, jnp.stack([jnp.sum(counts > 0), jnp.max(counts)]
                            ).astype(jnp.int32)

    @staticmethod
    def _cache_return(logits, cache, stats, routing):
        """(logits, cache), and for ``routing=True`` the layers' routing
        stats summed to int32 [2] (None from a dense model)."""
        if not routing:
            return logits, cache
        return logits, cache, None if stats is None else stats.sum(axis=0)

    # ---- per-layer constants (scanned alongside the stacked params) ----
    def _layer_extras(self):
        """Optional [L, ...] array of per-layer constants scanned alongside
        the blocks subtree (NOT parameters: no grads, no optimizer state).
        Families with layer-dependent attention (GPT-Neo's alternating
        local/global) return a flag vector; base models return None."""
        return None

    def _scan_split(self, blocks, cached):
        """``params["blocks"]`` as a layer scan takes it: (the subtree it
        slices a layer at a time, leaves its body closes over whole, or
        None). A family with routed experts hands back their matmul leaves
        (``MOELayer.take_whole``); its block then takes
        ``stacked=(whole, layer)`` and reads them where they lie.
        ``cached``: the scan is a cache forward's (else
        ``hidden_states``' with ``train=False``). Dense: nothing whole."""
        return blocks, None

    def _scan_layers(self, body, carry, blocks, indexed=False, unroll=1):
        """Run ``body(carry, xs) -> (carry, out)`` over every layer in
        order and return ``(carry, outs)``: for a family whose layers are
        alike, the one ``lax.scan`` over its stacked ``blocks``. ``xs`` is
        what the two scans of this class hand their bodies:
        ``layer_params`` (with ``_layer_extras``: ``(layer_params,
        extra)``) from ``hidden_states``, and with ``indexed`` (the cache
        forward) ``(layer_params, layer, extra)``, where ``layer`` is the
        layer's index in the pool's leaves. A family whose layers are of
        several kinds, each kind with its own stacked tree and its own
        pool leaves, overrides this (``models/lfm2.py``): ``layer_params``
        is then whatever its own ``_block`` / ``_decode_block`` take, and
        ``outs`` the stacked outputs of the layers that gave one."""
        extras = self._layer_extras()
        if indexed:
            xs = (blocks, jnp.arange(self.config.n_layer), extras)
        else:
            xs = blocks if extras is None else (blocks, extras)
        return lax.scan(body, carry, xs, unroll=unroll)

    def _train_attn_bias_ex(self, t, extra):
        """Layer-aware training attention bias; base defers to the
        layer-independent hook."""
        return self._train_attn_bias(t)

    def _decode_attn_mask_ex(self, q_pos, k_pos, extra):
        """Layer-aware decode keep-mask; base defers to the
        layer-independent hook."""
        return self._decode_attn_mask(q_pos, k_pos)

    def _dropout(self, x, rng, train, salt):
        return _token_dropout(x, rng, train, salt, self.config.dropout)

    # --------------------------------------------------------------- forward
    def hidden_states(self, params, input_ids, rng=None, train=True,
                      pld_theta=None, ltd_keep=None, act_bits=None):
        """Transformer stack up to the final LN. Returns (x [B,T,D],
        aux_loss, wte in compute dtype) — the loss path projects to vocab
        CHUNK-WISE (never materializing [B,T,V]).

        ``pld_theta``: progressive-layer-drop keep anneal (traced scalar;
        reference engine.py:1667 injects it into forward kwargs) — layer i
        runs with probability 1 - (i+1)/L*(1-theta), identity otherwise (the
        PLD paper trains without 1/p rescaling since theta anneals to its
        target). ``ltd_keep``: random-LTD token budget (static int;
        reference data_routing/basic_layer.py:14) — each block runs on a
        random sorted subset of ltd_keep tokens, the rest bypass via the
        residual. Both are train-time-only and need an rng.
        ``act_bits``: activation fake-quant at block inputs (static int;
        the compression library's QuantAct, reference
        compression/basic_layer.py — block granularity here)."""
        cfg = self.config
        # compute dtype follows the param dtype: the engine casts fp32 masters
        # to bf16/fp16 before apply (mixed-precision contract); cfg.dtype is
        # the fallback for direct use.
        compute_dtype = self._compute_dtype(params)
        with jax.named_scope("embed"):
            x = self._embed(params, input_ids)
        x = self._dropout(x, rng, train, 2)
        use_wrappers = train and rng is not None
        t = x.shape[1]
        x = self._open_streams(x)
        extras = self._layer_extras()
        # serving runs the code the cache forwards run; training's scan
        # slices every leaf
        blocks, whole = (params["blocks"], None) if train else \
            self._scan_split(params["blocks"], cached=False)

        def body(carry, xs):
            layer_params, extra = xs if extras is not None else (xs, None)
            h, i, aux = carry
            layer_rng = None if rng is None else jax.random.fold_in(rng, i)
            routed = {} if whole is None else {"stacked": (whole, i)}

            def blk(hh):
                if act_bits is not None:
                    from ..ops.quantizer_ops import fake_quantize
                    hh = fake_quantize(hh, bits=act_bits)
                return self._block(hh, layer_params, layer_rng, train,
                                   extra=extra, **routed)

            run = blk
            if use_wrappers and ltd_keep is not None and ltd_keep < t:
                from ..ops.random_ltd_ops import (sample_token_indices,
                                                  token_gather, token_scatter)

                def run(hh, _blk=run):
                    idx = sample_token_indices(
                        jax.random.fold_in(layer_rng, 1001),
                        ltd_keep, hh.shape[0], t)
                    out, l_aux = _blk(token_gather(hh, idx))
                    return token_scatter(hh, out, idx), l_aux

            if use_wrappers and pld_theta is not None:
                from ..runtime.progressive_layer_drop import \
                    keep_prob_for_layer

                def run(hh, _run=run):
                    keep_p = keep_prob_for_layer(pld_theta, i, cfg.n_layer)
                    coin = jax.random.bernoulli(
                        jax.random.fold_in(layer_rng, 1002), keep_p)
                    return lax.cond(coin, _run,
                                    lambda v: (v, jnp.float32(0.0)), hh)

            h, l_aux = run(h)
            return (h, i + 1, aux + l_aux), None

        body_fn = body
        if cfg.remat:
            from ..runtime.activation_checkpointing.checkpointing import \
                get_policy
            body_fn = jax.checkpoint(body, policy=get_policy(cfg.remat_policy))
        with jax.named_scope("layers"):
            (x, _, aux_total), _ = self._scan_layers(
                body_fn, (x, 0, jnp.float32(0.0)), blocks,
                unroll=min(max(1, int(getattr(cfg, "scan_unroll", 1))),
                           cfg.n_layer))

        x = self._final_norm(params, self._close_streams(x))
        return x, aux_total / cfg.n_layer, \
            self._unembed_weight(params, compute_dtype)

    def logits(self, params, input_ids, rng=None, train=True,
               return_aux_loss=False):
        x, aux, wte = self.hidden_states(params, input_ids, rng=rng,
                                         train=train)
        with jax.named_scope("head"):
            logits = x @ wte.T
            head_b = self._head_bias(params, logits.dtype)
            if head_b is not None:
                logits = logits + head_b
        if return_aux_loss:
            return logits, aux
        return logits

    def aux_loss_weight(self) -> float:
        return 0.0

    def _lm_loss(self, logits, batch):
        """Shifted next-token NLL; labels with -100 = ignore (HF convention)."""
        cfg = self.config
        input_ids = batch["input_ids"] if isinstance(batch, dict) else batch
        if isinstance(batch, dict) and "labels" in batch:
            shift_logits, shift_labels = logits[:, :-1], batch["labels"][:, 1:]
        else:
            shift_logits, shift_labels = logits[:, :-1], input_ids[:, 1:]
        valid = (shift_labels >= 0) & (shift_labels < cfg.vocab_size)
        safe_labels = jnp.where(valid, shift_labels, 0)
        total = me.dense_xent_sum(shift_logits,
                                  safe_labels.astype(jnp.int32), valid)
        return total / jnp.maximum(valid.sum(), 1)

    @staticmethod
    def _loss_chunk(v: int, target: int = 8192) -> int:
        """Vocab-chunk width of the online-softmax loss: the largest
        divisor of v that is <= target, UNLESS that divisor is tiny (prime
        or near-prime vocabs would degrade to a scan of thousands of
        near-empty matmuls) — then plain `target` with a masked ragged
        tail."""
        for c in range(min(target, v), 0, -1):
            if v % c == 0:
                if c >= min(target, v) // 8:
                    return c
                break  # largest divisor is tiny: use padding instead
        return min(target, v)

    def _chunked_lm_loss(self, h, wte, batch, head_b=None):
        """Shifted next-token NLL WITHOUT materializing [B,T,V] logits: an
        online-logsumexp scan over vocab chunks (the memory/bandwidth
        equivalent of the reference's fused softmax-xent kernels,
        csrc/transformer/softmax_kernels.cu — [B,T,V] in fp32 is the
        single largest activation of GPT-2 training and caps the micro
        batch). The chunk body is rematerialized in backward, so the
        residual is just (m, s, target_logit) per token."""
        cfg = self.config
        input_ids = batch["input_ids"] if isinstance(batch, dict) else batch
        labels_src = (batch["labels"] if isinstance(batch, dict) and
                      "labels" in batch else input_ids)
        h = h[:, :-1]
        labels = labels_src[:, 1:]
        valid = (labels >= 0) & (labels < cfg.vocab_size)
        safe = jnp.where(valid, labels, 0)
        b, tm1, d = h.shape
        n = b * tm1
        hf = h.reshape(n, d)
        lf = safe.reshape(n)
        v = wte.shape[0]
        chunk = self._loss_chunk(v, self.config.loss_chunk_target)
        k = -(-v // chunk)
        if head_b is None:
            head_b = jnp.zeros((v,), wte.dtype)
        if k * chunk != v:  # ragged tail: pad rows, mask their logits below
            wte = jnp.pad(wte, ((0, k * chunk - v), (0, 0)))
            head_b = jnp.pad(head_b, (0, k * chunk - v))
        w_chunks = wte.reshape(k, chunk, d)
        b_chunks = head_b.reshape(k, chunk)

        def body(carry, xs):
            m, s, tgt = carry
            wc, bc, ki = xs
            logits = (hf @ wc.T + bc[None, :]).astype(jnp.float32)  # [n, chunk]
            if k * chunk != v:
                col = ki * chunk + jnp.arange(chunk)
                logits = jnp.where(col[None, :] < v, logits, -jnp.inf)
            cmax = jnp.max(logits, axis=1)
            nm = jnp.maximum(m, cmax)
            s = s * jnp.exp(m - nm) + \
                jnp.sum(jnp.exp(logits - nm[:, None]), axis=1)
            base = ki * chunk
            inb = (lf >= base) & (lf < base + chunk)
            idx = jnp.clip(lf - base, 0, chunk - 1)
            tl = jnp.take_along_axis(logits, idx[:, None], axis=1)[:, 0]
            tgt = jnp.where(inb, tl, tgt)
            return (nm, s, tgt), None

        init = (jnp.full((n,), -jnp.inf, jnp.float32),
                jnp.zeros((n,), jnp.float32), jnp.zeros((n,), jnp.float32))
        (m, s, tgt), _ = lax.scan(jax.checkpoint(body), init,
                                  (w_chunks, b_chunks, jnp.arange(k)))
        nll = (m + jnp.log(s)) - tgt
        nll = jnp.where(valid.reshape(n), nll, 0.0)
        return nll.sum() / jnp.maximum(valid.sum(), 1)

    # dense-logits path above this many logit elements would cost multiple
    # GB of f32 activations — switch to the chunked loss there
    _DENSE_LOSS_MAX_ELEMS = 600_000_000

    def _head_loss_from_hidden(self, x, wte, batch, head_b=None):
        """Dense-vs-chunked dispatch, shared by apply() and the pipeline
        head (one place to evolve the policy)."""
        cfg = self.config
        n_logits = x.shape[0] * max(1, x.shape[1] - 1) * wte.shape[0]
        use_chunked = (cfg.loss_chunking == "always" or
                       (cfg.loss_chunking == "auto" and
                        n_logits > self._DENSE_LOSS_MAX_ELEMS))
        if use_chunked:
            with jax.named_scope("loss"):
                return self._chunked_lm_loss(x, wte, batch, head_b=head_b)
        logits = x @ wte.T
        if head_b is not None:
            logits = logits + head_b
        return self._lm_loss(logits, batch)

    def apply(self, params, batch, rng=None, train=True, pld_theta=None,
              ltd_keep=None, act_bits=None):
        """Next-token LM loss. batch: {'input_ids': [B,T]} (+ optional
        'labels' [B,T]). pld_theta/ltd_keep/act_bits: see hidden_states."""
        input_ids = batch["input_ids"] if isinstance(batch, dict) else batch
        x, aux, wte = self.hidden_states(params, input_ids, rng=rng,
                                         train=train, pld_theta=pld_theta,
                                         ltd_keep=ltd_keep,
                                         act_bits=act_bits)
        with jax.named_scope("head"):
            loss = self._head_loss_from_hidden(
                x, wte, batch, head_b=self._head_bias(params, wte.dtype))
        w = self.aux_loss_weight()
        return loss + w * aux if w else loss

    # ------------------------------------------------------------- sharding
    def partition_rules(self):
        """TP (megatron-style) + PP logical rules; ZeRO layering happens in
        runtime/zero/partition.py. Stacked leaves: axis 0 is the layer axis —
        sharded over 'pipe' when pp>1 (the planner drops size-1 axes)."""
        return [
            (r"wte$", ("model", None)),
            (r"wpe$", (None, None)),
            (r"blocks/qkv_w$", ("pipe", None, "model")),
            (r"blocks/qkv_b$", ("pipe", "model")),
            (r"blocks/attn_proj_w$", ("pipe", "model", None)),
            (r"blocks/mlp_fc_w$", ("pipe", None, "model")),
            (r"blocks/mlp_fc_b$", ("pipe", "model")),
            (r"blocks/mlp_proj_w$", ("pipe", "model", None)),
            (r"blocks/", ("pipe",)),       # remaining stacked leaves (LNs, biases)
        ]

    # ------------------------------------------------------- pipeline protocol
    def pipeline_spec(self):
        """Hooks for the compiled ppermute pipeline (runtime/pipe/engine.py):
        embed → per-layer block over the stacked 'blocks' subtree → head
        loss. The layer axis (dim 0 of every blocks leaf) is what the engine
        slices across pipeline stages."""

        def embed(params, batch, rng, train):
            input_ids = batch["input_ids"] if isinstance(batch, dict) else batch
            x = self._embed(params, input_ids)
            return self._dropout(x, rng, train, 2)

        def block(block_params, x, rng, train):
            return self._block(x, block_params, rng, train)  # (x, aux)

        def head_loss(params, x, batch):
            x = self._final_norm(params, x)
            return self._head_loss_from_hidden(
                x, self._unembed_weight(params, x.dtype), batch,
                head_b=self._head_bias(params, x.dtype))

        return {"blocks_key": "blocks", "embed": embed, "block": block,
                "head_loss": head_loss,
                "aux_loss_weight": self.aux_loss_weight()}

    # ------------------------------------------------------- decode protocol
    # The inference engine's counterpart to the reference's fused inference
    # modules (reference model_implementations/transformers/ds_transformer.py,
    # csrc/transformer/inference/csrc/pt_binding.cpp:1747 softmax_context —
    # attention with KV-cache append). Functional: the cache is a pytree the
    # caller threads through compiled prefill/decode steps.
    def init_kv_cache(self, batch_size: int, max_len: int, dtype=jnp.bfloat16):
        """The KV pool: ``k`` and ``v`` leaves ``[L, S, max_len, Hk, hd]``,
        token-major. One token's K (or V) of all heads is one contiguous
        row of ``Hk * hd``, a prefill's T tokens are one contiguous block,
        and a layer's slab ``[S, max_len, Hk, hd]`` is contracted against
        where it lies. Heads narrower than a vector row are stored
        ``128 // hd`` to a row (``_kv_row_shape``): the same bytes in the
        same order. ``_kv_write`` and ``_kv_attend`` are the only code that
        indexes inside a lane."""
        cfg = self.config
        shape = (cfg.n_layer, batch_size, max_len) + \
            _kv_row_shape(self.kv_heads, cfg.head_dim)
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}

    @staticmethod
    def _kv_write(pool, layer, new, start):
        """Write ``new`` [S, T, Hk, hd] into layer ``layer`` of one pool leaf
        and return the leaf; nothing but those S * T rows is touched, so a
        donated pool carried through the layer scan is updated in place.
        ``start`` is a scalar (every row's block begins at column
        ``start``: one ``dynamic_update_slice``) or [S] (row s's block
        begins at ``start[s]``: an indexed update of S * T rows, of which
        those at or past ``max_len`` are dropped)."""
        s, t = new.shape[:2]
        new = new.reshape((s, t) + pool.shape[3:]).astype(pool.dtype)
        if jnp.ndim(start) == 0:
            return lax.dynamic_update_slice(pool, new[None],
                                            (layer, 0, start, 0, 0))
        cols = start[:, None] + jnp.arange(t)[None, :]            # [S, T]
        return pool.at[layer, jnp.arange(s)[:, None], cols].set(
            new, mode="drop", unique_indices=True, indices_are_sorted=True)

    #: the queries a slot from which ``_kv_attend`` sees stored rows that
    #: hold several whole-lane heads as heads (one re-laying of the keys it
    #: reads) and below which it lays each query into its own head's lanes:
    #: a prefill against a decode step. A family whose tick carries a few
    #: queries a slot (``models/sdar.py``: a block) raises it past them
    _rows_as_heads_from = 2

    @staticmethod
    def _kv_attend(q, k_pool, v_pool, layer, mask, bias, heads_from=2):
        """Attention of ``q`` [S, H, T, hd] over every column of layer
        ``layer``'s slab of the pool, read where it lies: no copy, no
        transpose, and grouped KV heads are contracted per group, not
        repeated. ``mask`` (keep) and ``bias`` (additive or None) broadcast
        to [S, H, T, max_len]. The mathematics of ``reference_attention``:
        scores in q's dtype, bias, mask and softmax in float32. Where a
        stored row holds several heads (``_kv_row_shape``), each query is
        laid into its own head's lanes of a zero row and the output taken
        from them: the products with the other heads' lanes are exact
        zeros."""
        ks = lax.dynamic_index_in_dim(k_pool, layer, 0, keepdims=False)
        vs = lax.dynamic_index_in_dim(v_pool, layer, 0, keepdims=False)
        s, h, t, hd = q.shape
        max_len, g, w = ks.shape[1:]
        if t >= heads_from and w > hd and hd % _LANES == 0:
            # a prefill over rows that hold several whole-lane heads (a
            # family's own choice for its decode step: ``models/lfm2.py``):
            # the zero-padded query below would multiply w / hd times the
            # scores' FLOPs, which a block of T queries pays T times; the
            # rows seen as heads cost one re-laying of the keys it reads
            ks = ks.reshape(s, max_len, g * w // hd, hd)
            vs = vs.reshape(s, max_len, g * w // hd, hd)
            g, w = g * w // hd, hd
        pack = w // hd                   # KV heads to a stored row
        rep = h // (g * pack)            # query heads to a KV head
        # own[j, j']: head j of a row owns lane block j'
        own = jnp.eye(pack, dtype=q.dtype)[:, None, None, :, None]
        qg = q.reshape(s, g, pack, rep, t, 1, hd)
        qg = (qg * own).reshape(s, g, pack * rep, t, w)
        logits = jnp.einsum("bgrqw,bkgw->bgrqk", qg, ks.astype(q.dtype)) * \
            (1.0 / jnp.sqrt(hd))
        logits = logits.reshape(s, h, t, max_len).astype(jnp.float32)
        if bias is not None:
            logits = logits + bias.astype(jnp.float32)
        logits = jnp.where(mask, logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
        out = jnp.einsum("bgrqk,bkgw->bgrqw",
                         probs.reshape(s, g, pack * rep, t, max_len),
                         vs.astype(q.dtype))
        out = out.reshape(s, g, pack, rep, t, pack, hd)
        return (out * own).sum(axis=5).reshape(s, h, t, hd)

    #: bytes of float32 scores one call of ``_kv_attend`` may hold: a prefill
    #: of more queries than fit goes in blocks of queries (``_query_block``)
    _attend_scores_bytes = 1 << 30

    def _query_block(self, t: int, heads: int, keys: int) -> int:
        """How many of a prefill's ``t`` queries attend ``keys`` columns in
        one piece: the largest power of two (8 at least) whose float32
        scores ``[heads, block, keys]`` stay inside
        ``_attend_scores_bytes``. ``t`` itself where that is no fewer: one
        piece, the program it always was (a decode step; every bucket of a
        pool of 32 heads up to 2048 columns)."""
        rows = max(self._attend_scores_bytes // (heads * keys * 4), 8)
        return min(1 << (rows.bit_length() - 1), t)

    @staticmethod
    def _in_row_blocks(fn, block, axis, *rows):
        """``fn(at, *cut) -> (out, extra)`` over arrays that hold a row of
        their own a token along ``axis``, cut alike into blocks of ``block``
        rows and taken one after another (``lax.map``), so that what ``fn``
        holds at a time is a block's; ``at`` is the block's first row. The
        whole blocks go through the map, and what is left over (fewer than
        ``block`` rows, where ``block`` does not divide them) through one
        call more of its own: no length falls back to one piece. ``out``
        has its rows on ``axis`` and is laid end to end; ``extra`` (a
        pytree, or None) is added up over the blocks. Rows that fit in one
        block are the plain call. For work in which a row needs no other
        row's: a query's row of a softmax, a token's feed-forward."""
        t = rows[0].shape[axis]
        n, rest = divmod(t, block)
        if n == 0 or (n == 1 and not rest):
            return fn(0, *rows)

        def cut(a, lo, size):
            return lax.slice_in_dim(a, lo, lo + size, axis=axis)

        def blocks(a):
            a = cut(a, 0, n * block)
            return jnp.moveaxis(a.reshape(
                a.shape[:axis] + (n, block) + a.shape[axis + 1:]), axis, 0)

        out, extra = lax.map(lambda xs: fn(xs[0], *xs[1:]),
                             (jnp.arange(n) * block,) + tuple(
                                 blocks(a) for a in rows))
        out = jnp.moveaxis(out, 0, axis)
        out = out.reshape(out.shape[:axis] + (n * block,) +
                          out.shape[axis + 2:])
        extra = jax.tree.map(lambda e: e.sum(axis=0), extra)
        if rest:
            last, more = fn(n * block, *(cut(a, n * block, rest)
                                         for a in rows))
            out = jnp.concatenate([out, last], axis=axis)
            extra = jax.tree.map(jnp.add, extra, more)
        return out, extra

    #: queries of a window layer's prefill that go against their band of
    #: keys in one piece: scores [heads, block, W + block]
    _window_query_block = 256

    def _window_attend(self, q, k, v, ring_k, ring_v, layer, start,
                       lengths=None):
        """A WINDOW layer over its ring: pool leaves ``ring_k`` / ``ring_v``
        ``[Lw, S, W, G, w]`` that keep a slot's last W positions, position
        p in column ``p mod W``, whatever the lane's length. q, k, v
        [S, H|Hk, T, hd] are the new tokens', row s's token j at position
        ``start + j``; a query at p sees the keys at ``p - W + 1 ... p``.
        Returns ``(attention [S, H, T, hd], ring_k, ring_v)``.

        - ``T == 1`` (a decode step; ``start`` a scalar or [S]): the row is
          written at ``start mod W`` (``_kv_write``: S rows and nothing
          else) and the ring attended where it lies (``_kv_attend``).
          Every column then holds one of the last W positions, or nothing
          yet: column c is kept where ``c <= start``.
        - ``T > 1`` (a prefill, a chunk; ``start`` a scalar): the W
          positions before ``start`` (the ring's rows in order; under 0:
          masked) and the block's T are one strip of ``W + T`` keys, and
          the queries go in blocks of ``_window_query_block``
          (``_in_row_blocks``) against the band of ``W + block`` keys
          beside them: scores
          ``[H, block, W + block]``, whatever T. Then the last W rows up
          to row ``lengths[s] - 1`` (``None``: T; a right-padded bucket's
          padding never enters the ring) are written at their positions'
          columns."""
        s, t = q.shape[0], q.shape[2]
        window = ring_k.shape[2]
        row = ring_k.shape[3:]
        k = k.transpose(0, 2, 1, 3)
        v = v.transpose(0, 2, 1, 3)                         # [S, T, Hk, hd]
        if t == 1:
            pos = jnp.broadcast_to(start, (s,))
            ring_k = self._kv_write(ring_k, layer, k, pos % window)
            ring_v = self._kv_write(ring_v, layer, v, pos % window)
            keep = (jnp.arange(window)[None, :] <= pos[:, None])
            return self._kv_attend(q, ring_k, ring_v, layer,
                                   keep[:, None, None, :], None), \
                ring_k, ring_v
        if jnp.ndim(start) != 0:
            raise NotImplementedError(
                "a block of several tokens at a position of its own a row "
                "(verify_with_slots) cannot go over a window ring: rows a "
                "rejected draft wrote have replaced columns still in the "
                "window")
        order = (start + jnp.arange(window)) % window   # row r: start - W + r
        strips = []
        for ring, new in ((ring_k, k), (ring_v, v)):
            hist = lax.dynamic_index_in_dim(ring, layer, 0, keepdims=False)
            strips.append(jnp.concatenate(
                [hist[:, order], new.reshape((s, t) + row).astype(ring.dtype)],
                axis=1))                                    # [S, W + T, G, w]

        def attend(at, qb):
            # a block of queries against the band of W + block keys beside
            # it: query i and key j of the band lie i - j + W positions
            # apart; key j lies at position start - W + at + j
            block = qb.shape[2]
            band = window + block
            apart = window + jnp.arange(block)[:, None] - \
                jnp.arange(band)[None, :]
            real = start - window + at + jnp.arange(band) >= 0
            keys = [lax.dynamic_slice_in_dim(strip, at, band, axis=1)
                    for strip in strips]
            return self._kv_attend(
                qb, keys[0][None], keys[1][None], 0,
                (apart >= 0) & (apart < window) & real[None, :], None), None

        out, _ = self._in_row_blocks(attend, self._window_query_block, 2, q)
        # the ring keeps positions end - W ... end - 1, end = start + lengths
        ends = jnp.full((s,), t) if lengths is None else lengths
        cols = (start + ends[:, None] + jnp.arange(window)[None, :]) % window
        rings = []
        for ring, strip in zip((ring_k, ring_v), strips):
            last = jax.vmap(lambda rows, at: lax.dynamic_slice_in_dim(
                rows, at, window, axis=0))(strip, ends)
            rings.append(ring.at[layer, jnp.arange(s)[:, None], cols].set(
                last, unique_indices=True))
        return out, rings[0], rings[1]

    def _latent_attend(self, q, q_pos, slab, latent, block, keep):
        """Attention of ``q`` [S, H, T, n + r] over a layer's LATENT slab
        ``[S, keys, w]``: a key column holds a token's compressed row
        ``c_kv`` (c values), then the one rotated key ``k_r`` (r values)
        that all heads share, then zeros up to whole vector rows
        (``w >= c + r``). ``latent = (up, scale, r)``: ``up`` [c, H, n + v]
        expands ``c_kv`` to a head's un-rotated key (n) and value (v); a
        score is ``(q_n . k_n + q_r . k_r) * scale``. ``keep(q_pos)`` is
        the keep-mask of the queries at ``q_pos``, broadcastable to
        [S, H, T, keys]. Scores and softmax in float32. Two paths, by what
        can be observed:

        - ``T > 1`` (a prefill, a chunk): the slab is EXPANDED once to
          per-head keys and values (``latent_up``) and the queries go in
          blocks of ``block`` against them (``_in_row_blocks``), a head
          at a time: compiled for a v5e, the heads' scores as ONE batched
          product handed back in float32 and fused into the softmax ran
          98 ms a block of 1,024 queries over 8,192 keys, a head after
          another 2.7 (PERF.md, PR 46).
        - ``T == 1`` (a decode step) is ABSORBED: the query goes through
          the key half of ``up`` into the latent space, scores and the
          weighted sum run over the slab itself where it lies, read once
          for all heads, and the sum goes through the value half
          (``absorb``): nothing of ``keys`` x H is made."""
        up, scale, r = latent
        s, h, t, _ = q.shape
        c, n, w = up.shape[0], q.shape[-1] - r, slab.shape[-1]
        up = up.astype(q.dtype)
        slab = slab.astype(q.dtype)

        def soft(scores, q_pos):
            scores = jnp.where(keep(q_pos), scores * scale, -1e30)
            return jax.nn.softmax(scores, axis=-1).astype(q.dtype)

        if t == 1:
            with jax.named_scope("absorb"):
                q_c = jnp.einsum("shn,chn->shc", q[:, :, 0, :n], up[..., :n])
            q_l = jnp.concatenate(
                [q_c, q[:, :, 0, n:], jnp.zeros((s, h, w - c - r), q.dtype)],
                axis=-1)
            probs = soft(_einsum_f32("shw,skw->shk", q_l, slab)[:, :, None],
                         q_pos)
            # over the whole row, and on through a value half that is zero
            # past ``c``: a slice of the slab, or of the sum (which the
            # compiler moves onto the slab), would be a copy of it
            out = jnp.einsum("shk,skw->shw", probs[:, :, 0], slab)
            with jax.named_scope("absorb"):
                up_v = jnp.pad(up[..., n:], ((0, w - c), (0, 0), (0, 0)))
                return jnp.einsum("shw,whv->shv", out, up_v)[:, :, None]
        with jax.named_scope("latent_up"):      # heads first: [H, S, ...]
            kv = jnp.einsum("skc,chd->hskd", slab[..., :c], up)
            keys = jnp.concatenate(
                [kv[..., :n], jnp.broadcast_to(
                    slab[None, :, :, c:c + r], kv.shape[:3] + (r,))], axis=-1)
            values = kv[..., n:]

        def attend(at, qb, q_pos):
            kept = keep(q_pos)              # [S | 1, H | 1, T, keys]
            by_head = kept.shape[1] != 1

            def head(xs):
                qh, kh, vh = xs[:3]         # [S, T, .], [S, keys, .]
                scores = _einsum_f32("sqd,skd->sqk", qh, kh)
                scores = jnp.where(xs[3] if by_head else kept[:, 0],
                                   scores * scale, -1e30)
                # the softmax's division on the [T, v] sums, not on the
                # [T, keys] weights: a pass over the scores less
                weights = jnp.exp(scores - lax.stop_gradient(
                    scores.max(axis=-1, keepdims=True)))
                out = _einsum_f32("sqk,skv->sqv", weights.astype(q.dtype), vh)
                return (out / weights.sum(axis=-1, keepdims=True)
                        ).astype(q.dtype)

            out = lax.map(head, (jnp.moveaxis(qb, 1, 0), keys, values) + (
                (jnp.moveaxis(kept, 1, 0),) if by_head else ()))
            return jnp.moveaxis(out, 0, 1), None

        return self._in_row_blocks(attend, block, 2, q, q_pos)[0]

    def _decode_attn_mask(self, q_pos, k_pos):
        """Boolean keep-mask over the cache columns: ``q_pos`` [B|1, 1, T, 1]
        against ``k_pos`` [1, 1, 1, max_len]. Sliding-window families
        tighten it."""
        return k_pos <= q_pos

    def _decode_attn_bias(self, q_pos, k_pos):
        """Additive attention bias on the cache path (broadcastable to
        [B, H, T, max_len], or None), from the same ``q_pos`` / ``k_pos``
        as the mask. ALiBi families override."""
        return None

    def _train_attn_bias(self, t):
        """Additive attention bias for the [t, t] training case ([H, t, t] or
        None). ALiBi families override."""
        return None

    def decode_kernel_block(self, cache):
        """Whether a ``decode_with_slots`` step traced now over ``cache``
        (``init_kv_cache``'s leaves, or their shapes) takes the Pallas
        decode-attention kernel (``ops/pallas/decode_attention.py``), which
        fetches a slot's live column blocks and no others: the columns one
        such block holds, or ``None`` where the step contracts over the
        whole slab (``_kv_attend``). Decided by what can be observed, here
        and nowhere else (the serving scheduler's ``serve/kv_read`` rests on
        the same answer):

        - the family's mask over a lane is the plain causal one: no layer
          extras (GPT-Neo's local layers), no additive bias (ALiBi), and the
          query at the lane's last column keeps every column (a sliding
          window does not);
        - the program will run on a TPU, on one device: GSPMD cannot
          partition a Mosaic kernel, and the pool may be sharded over
          ``model``;
        - the pool holds K and V (a latent leaf is attended by
          ``_latent_attend``), in stored rows the kernel takes
          (``decode_attention.block_columns``)."""
        from ..ops.pallas import decode_attention
        from ..parallel.constraints import active_mesh
        from ..parallel.topology import on_tpu
        if self.latent_cache:       # the kernel contracts K and V rows
            return None
        leaf = cache["k"]
        max_len = leaf.shape[2]
        mesh = active_mesh()
        if not on_tpu() or (mesh is not None and mesh.devices.size > 1):
            return None
        if self._layer_extras() is not None:
            return None
        last = np.full((1, 1, 1, 1), max_len - 1)
        cols = np.arange(max_len)[None, None, None, :]
        with jax.ensure_compile_time_eval():
            if self._decode_attn_bias(last, cols) is not None or \
                    not np.all(self._decode_attn_mask(last, cols)):
                return None
        return decode_attention.block_columns(leaf.shape[3:], max_len,
                                              leaf.dtype)

    #: the shortest whole prefill that takes the flash kernel. Every bucket
    #: on it traces and lowers one more kernel shape at each start of a
    #: serving process, 0.3 s on the chip's host whatever the compile cache
    #: holds (PERF.md, PR 58: four buckets were +1.3 s of warm start-up in
    #: the chat cells), and 128 queries over a lane have little to gain
    _flash_prefill_from = 256

    def prefill_kernel(self, cache, t, start, pad_counts=None, dtype=None):
        """Whether a cached forward of ``t`` tokens a row from column
        ``start``, traced now over ``cache`` (``init_kv_cache``'s leaves, or
        their shapes), computes its attention with the packed flash kernel
        (``ops/pallas/flash_attention_packed.py``, the training forward's)
        on the block's OWN q, k and v, the scores never leaving VMEM,
        instead of over every column of the lane (``_kv_attend``: float32
        scores ``[H, t, max_len]`` through HBM). From column 0 the columns
        below ``t`` hold what this call has just computed and the causal
        mask drops every column past them, so attention over the lane IS
        causal self-attention among the block's tokens; K and V are written
        to the lane as ever. Decided by what can be observed, here and
        nowhere else (the serving scheduler's ``serve/kernel_prefills``
        rests on the same answer):

        - a WHOLE prefill: ``start`` is a concrete 0 as the program is
          traced (a suffix's or a chunk's ``start`` is traced and the
          columns below it live; a verify step's is [S]), no left padding,
          ``_flash_prefill_from`` tokens or more. A right-padded bucket
          needs no length: padding keys lie past every real query;
        - the family's mask of those queries over the lane is the plain
          causal one and it adds no bias: no layer extras (GPT-Neo's local
          layers), no ALiBi, no window shorter than ``t``, no blocks that
          see ahead (``models/sdar.py``);
        - the pool holds K and V (a latent leaf is attended by
          ``_latent_attend``) in ``dtype``, the compute dtype (the config's
          where ``None``): what is read back from an int8 or float32 lane
          is not what was computed;
        - as many KV heads as query heads, at a length and a head width the
          kernel takes (``flash_attention_packed.supported``);
        - the program will run on a TPU, on one device (GSPMD cannot
          partition a Mosaic kernel). Elsewhere ``attn_backend="pallas"``
          runs the kernel in interpret mode, the parity tests' path, as it
          does for training (``_packed_attn_ok``)."""
        from ..ops.pallas.flash_attention_packed import supported
        from ..parallel.constraints import active_mesh
        from ..parallel.topology import on_tpu
        cfg = self.config
        if isinstance(start, jax.core.Tracer) or jnp.ndim(start) != 0 \
                or int(start) != 0 or pad_counts is not None \
                or t < self._flash_prefill_from:
            return False
        if self.latent_cache or self._layer_extras() is not None:
            return False
        dtype = jnp.dtype(cfg.dtype if dtype is None else dtype)
        if cache["k"].dtype != dtype or cache["v"].dtype != dtype:
            return False
        if self.kv_heads != cfg.n_head or \
                not supported(t, cfg.head_dim, cfg.n_head, True, None):
            return False
        mesh = active_mesh()
        if (mesh is not None and mesh.devices.size > 1) or \
                not (on_tpu() or cfg.attn_backend == "pallas"):
            return False
        max_len = cache["k"].shape[2]
        q_pos = np.arange(t)[None, None, :, None]
        k_pos = np.arange(max_len)[None, None, None, :]
        with jax.ensure_compile_time_eval():
            if self._decode_attn_bias(q_pos[:, :, -1:], k_pos) is not None:
                return False
            keep = np.asarray(self._decode_attn_mask(q_pos, k_pos))
        return bool(np.array_equal(
            np.broadcast_to(keep, (1, 1, t, max_len))[0, 0],
            (k_pos <= q_pos)[0, 0]))

    @staticmethod
    def _self_attend(q, k, v):
        """Causal attention of ``q`` [S, H, T, hd] over the block's own
        ``k`` and ``v`` (as many heads) in the packed flash kernel, where
        ``prefill_kernel`` says so: the three as ``[S, T, H * hd]``, the
        layout the qkv matmul gave and ``kv_write`` takes (the compiler
        cancels the transposes against those around the call), the
        training forward's kernel with the tiles its shape resolves to,
        and back. Scores and softmax in float32, bf16 probabilities into V
        with float32 sums: no lower a precision than ``_kv_attend``'s."""
        from ..ops.pallas.flash_attention_packed import packed_flash_attention
        from ..parallel.topology import on_tpu
        s, h, t, hd = q.shape
        q, k, v = (a.transpose(0, 2, 1, 3).reshape(s, t, h * hd)
                   for a in (q, k, v))
        out = packed_flash_attention(q, k, v, h, interpret=not on_tpu())
        return out.reshape(s, t, h, hd).transpose(0, 2, 1, 3)

    @staticmethod
    def _state_shift(state, layer, rows, lengths=None):
        """Push ``rows`` [S, T, d] through layer ``layer`` of a recurrent
        pool leaf ``state`` [L', S, n, d], which keeps the last ``n`` rows a
        slot has seen: returns ``(history, leaf)``, the ``n`` rows before
        the block as they stood, and the leaf with layer ``layer`` holding
        the last ``n`` rows up to and including row ``lengths[s] - 1`` of
        the block (``None``: every row is real). What a right-padded
        prefill feeds after its last real token never enters the state,
        and cannot be masked out afterwards as a KV column can. Nothing but
        layer ``layer``'s S * n rows is written, so a donated pool carried
        through the layers is updated in place."""
        hist = lax.dynamic_index_in_dim(state, layer, 0, keepdims=False)
        n, t = hist.shape[1], rows.shape[1]
        seen = jnp.concatenate([hist, rows.astype(hist.dtype)], axis=1)
        if lengths is None:
            last = seen[:, t:]
        else:
            last = jax.vmap(lambda row, at: lax.dynamic_slice_in_dim(
                row, at, n, axis=0))(seen, lengths)
        return hist, lax.dynamic_update_slice(state, last[None],
                                              (layer, 0, 0, 0))

    def _forward_with_cache(self, params, input_ids, cache, start,
                            pad_counts=None, routing=False, lengths=None):
        """The one cached forward behind ``apply_with_cache`` (``start`` a
        scalar), ``decode_with_slots`` and ``verify_with_slots`` (``start``
        [S], one cache position per row): input_ids [S, T]; row s's token j
        is written at column ``start + j`` and attends the columns the
        model's mask keeps at or below it. The pool is carried through the
        layers (``_scan_layers``; not scanned over), each layer writes its
        S * T rows (``_kv_write``) and reads its slab where it lies
        (``_kv_attend``): with the pool donated to the jitted program XLA
        aliases it through the loop, and a step writes the new tokens' K
        and V and nothing else. A family with ``recurrent_state`` leaves
        is handed ``state_fn(name, rows) -> history`` beside ``attn_fn``
        (``_state_shift`` on the layer's own index), and ``lengths`` [S]
        says how many of each row's T tokens are real (``None``: all)."""
        s, t = input_ids.shape
        max_len = cache[self.lane_leaves[0]].shape[2]
        compute_dtype = self._compute_dtype(params)
        if jnp.ndim(start) != 0:
            positions = start[:, None] + jnp.arange(t)[None, :]      # [S, T]
            q_pos = positions[:, None, :, None]
            start_pos = jnp.int32(0)
        else:
            cols = (start + jnp.arange(t))[None, :]                  # [1, T]
            q_pos = cols[:, None, :, None]
            start_pos = start
            positions = None if pad_counts is None else \
                jnp.maximum(cols - pad_counts[:, None], 0)
        with jax.named_scope("embed"):
            x = self._open_streams(self._embed(
                params, input_ids, start_pos=start_pos, positions=positions))
        block = self._query_block(t, self.config.n_head, max_len)
        k_pos = jnp.arange(max_len)[None, None, None, :]
        pad_valid = None
        if pad_counts is not None:     # left-pad columns are never valid keys
            pad_valid = (jnp.arange(max_len)[None, :] >=
                         pad_counts[:, None])[:, None, None, :]
        extras = self._layer_extras()
        blocks, whole = self._scan_split(params["blocks"], cached=True)

        def keep_mask(extra, q_pos=q_pos):
            mask = self._decode_attn_mask_ex(q_pos, k_pos, extra)
            return mask if pad_valid is None else mask & pad_valid

        # a decode step, one token a slot at a position of its own: the
        # kernel that is told the lengths, where it takes this pool
        kernel = t == 1 and jnp.ndim(start) != 0 and pad_counts is None \
            and self.decode_kernel_block(cache) is not None
        if kernel:
            from ..ops.pallas.decode_attention import decode_attend
        # a whole prefill from column 0: the block's own keys, where the
        # packed flash kernel takes them
        flash = self.prefill_kernel(cache, t, start, pad_counts,
                                    compute_dtype)
        # a family whose tick carries a few queries a slot says from how
        # many its stored rows are seen as heads; every other call is as it was
        few = {} if self._rows_as_heads_from == 2 else \
            {"heads_from": self._rows_as_heads_from}
        # one piece: mask and bias are made once, outside the layers (the
        # flash kernel makes its own mask, tile by tile)
        whole_mask = block == t and extras is None
        base_mask = keep_mask(None) if whole_mask and not flash else None
        base_bias = self._decode_attn_bias(q_pos, k_pos) if block == t \
            else None

        def body(carry, xs):
            x, pool = carry
            layer_params, layer, extra = xs
            routed = {} if whole is None else {"stacked": (whole, layer)}
            pool = dict(pool)

            def mask_and_bias(q_pos):
                if block == t:
                    return (base_mask if whole_mask else keep_mask(extra),
                            base_bias)
                return keep_mask(extra, q_pos), \
                    self._decode_attn_bias(q_pos, k_pos)

            def attend(at, q, q_pos):
                return self._kv_attend(q, pool["k"], pool["v"], layer,
                                       *mask_and_bias(q_pos), **few), None

            def cached_attn(q, k, v, ring=None, latent=None):
                # q, k, v arrive [S, H, T, hd]. The kv_write / kv_read
                # scopes nest inside "attn": a device trace's reader
                # (``hlo_cost.scope_table``) sees the cache's writes and
                # the attend over it apart from the projections around
                # them. ``ring``: a window layer names its two ring
                # leaves of the pool (``_window_attend``) in place of
                # ``k`` and ``v``. ``latent``: ``k`` is the token's latent
                # row [S, T, w] and there is no ``v`` (``_latent_attend``)
                if latent is not None:
                    name, = self.latent_cache
                    with jax.named_scope("kv_write"):
                        pool[name] = self._kv_write(pool[name], layer,
                                                    k[:, :, None], start)
                    with jax.named_scope("kv_read"):
                        return self._latent_attend(
                            q, q_pos, lax.dynamic_index_in_dim(
                                pool[name], layer, 0, keepdims=False)[:, :, 0],
                            latent, block, lambda qp: mask_and_bias(qp)[0])
                if ring is not None:
                    out, pool[ring[0]], pool[ring[1]] = self._window_attend(
                        q, k, v, pool[ring[0]], pool[ring[1]], layer, start,
                        lengths)
                    return out
                with jax.named_scope("kv_write"):
                    pool["k"] = self._kv_write(pool["k"], layer,
                                               k.transpose(0, 2, 1, 3), start)
                    pool["v"] = self._kv_write(pool["v"], layer,
                                               v.transpose(0, 2, 1, 3), start)
                with jax.named_scope("kv_read"):
                    if kernel:
                        return decode_attend(
                            q[:, :, 0], pool["k"], pool["v"], layer,
                            start + 1)[:, :, None]
                    if flash:
                        return self._self_attend(q, k, v)
                    return self._in_row_blocks(attend, block, 2, q, q_pos)[0]

            if self.recurrent_state:
                def cached_state(name, rows):
                    hist, pool[name] = self._state_shift(pool[name], layer,
                                                         rows, lengths)
                    return hist
                routed["state_fn"] = cached_state

            x, stats = self._split_routing(self._decode_block(
                x, layer_params, cached_attn, start_pos,
                positions=positions, extra=extra, **routed))
            return (x, pool), stats

        with jax.named_scope("layers"):
            (x, pool), stats = self._scan_layers(body, (x, dict(cache)),
                                                 blocks, indexed=True)
        with jax.named_scope("head"):
            x = self._final_norm(params, self._close_streams(x))
            logits = x @ self._unembed_weight(params, compute_dtype).T
            head_b = self._head_bias(params, logits.dtype)
            if head_b is not None:
                logits = logits + head_b
        return self._cache_return(logits, pool, stats, routing)

    def apply_with_cache(self, params, input_ids, cache, start_pos,
                         pad_counts=None, routing=False, lengths=None):
        """Forward with KV cache. input_ids: [B, T] (prompt for prefill,
        [B, 1] for decode); start_pos: traced scalar — tokens occupy cache
        columns [start_pos, start_pos+T), one contiguous block a row.
        ``pad_counts`` [B]: number of LEFT-padding tokens per row (serving
        batches of uneven prompts) — cache columns below pad_counts[b] are
        masked out and logical positions shift down by pad_counts[b] (ALiBi
        needs no shift: a per-row constant is softmax-invariant). Returns
        (logits [B,T,V], new_cache); with ``routing=True`` also the routed
        expert layers' stats (``_cache_return``). ``lengths`` [B]: how many
        of each row's T tokens are real, the rest RIGHT padding (a slot
        prefill's pow2 bucket). A KV column of padding is masked until it
        is overwritten and the argument changes nothing there; a family
        with ``recurrent_state`` stores its state at the last real token,
        and is always told."""
        return self._forward_with_cache(params, input_ids, cache, start_pos,
                                        pad_counts=pad_counts,
                                        routing=routing, lengths=lengths)

    def chunk_prefill_with_cache(self, params, input_ids, cache, start_pos):
        """K/V-write-only forward for chunked prefill: one chunk of a
        long prompt through the stack, cache columns
        ``[start_pos, start_pos+T)`` written, NO logits. The intermediate
        chunks of a chunked admission never sample a token, so the final
        norm + unembedding (the largest matmul of a small-batch prefill)
        are dead code here — returning only the cache lets XLA eliminate
        them, which is what makes a chunk strictly cheaper than the same
        tokens through ``apply_with_cache``. The last chunk of a prompt
        does NOT come through here: it runs the regular suffix-prefill
        path so the first token is sampled from real logits at the same
        ``(seed, position)`` key a monolithic prefill would use."""
        _logits, cache = self.apply_with_cache(params, input_ids, cache,
                                               start_pos)
        return cache

    def decode_with_slots(self, params, input_ids, cache, positions,
                          routing=False):
        """One decode token per batch row with PER-ROW cache positions — the
        continuous-batching serving step (deepspeed_tpu/serving/): each row
        of ``cache`` is an independent decode SLOT at its own sequence
        length, so one compiled program advances every in-flight request by
        one token regardless of when each was admitted.

        input_ids [S, 1]; positions [S] (traced): row s's token K/V is
        written at cache column positions[s] and attends columns
        <= positions[s]. The pool is token-major, so the write is S rows of
        ``Hk * hd`` a layer at ``[layer, s, positions[s]]`` — shapes are
        static, the step compiles exactly once per (S, max_len), and no
        other byte of the pool is written. Returns (logits [S, 1, V],
        new_cache); ``routing`` as in ``apply_with_cache``."""
        t = input_ids.shape[1]
        if t != 1:
            raise ValueError(f"decode_with_slots is single-token: got T={t}")
        return self._forward_with_cache(params, input_ids, cache, positions,
                                        routing=routing)

    def verify_with_slots(self, params, input_ids, cache, positions,
                          routing=False):
        """Multi-token block forward with PER-ROW cache positions — the
        speculative-decoding verify step (deepspeed_tpu/serving/): row
        ``s`` feeds a block of T tokens (its pending token followed by
        T-1 draft proposals), token j's K/V is written at cache column
        ``positions[s] + j``, and it attends columns
        ``<= positions[s] + j`` (block-causal over the slot lane). One
        statically-shaped program verifies every draft position of every
        slot in ONE forward — the trade XLA rewards: T target positions
        for one weight pass instead of T sequential decode dispatches.

        input_ids [S, T]; positions [S] (traced). The write is
        ``decode_with_slots``'s with T rows a slot (S * T rows of
        ``Hk * hd`` a layer), so each (S, max_len, T) flavor compiles
        exactly once. Writes whose column would land at or past
        ``max_len`` are dropped; their logits are garbage by construction
        and the serving layer never consumes them (a request's budget keeps
        every live position in range). Returns (logits [S, T, V],
        new_cache). T=1 is ``decode_with_slots`` bit for bit (which stays
        the steady-state program — its compiled flavor is pinned by the
        serving tests). A family that generates by diffusion over blocks
        (``block_length``) runs its pass of the tick through here, under
        its own mask: the same forward, ``routing`` as in
        ``apply_with_cache``."""
        return self._forward_with_cache(params, input_ids, cache, positions,
                                        routing=routing)

    def cache_partition_rules(self):
        """Sharding for the KV cache: heads over 'model' (TP), batch over the
        dp axes."""
        return [(r"(k|v)$", (None, ("data", "expert"), None, "model", None))]

    def flops_per_token(self, seq_len: Optional[int] = None):
        """Training FLOPs/token: 6N + attention term (12·L·D·T)."""
        cfg = self.config
        d, l = cfg.n_embd, cfg.n_layer
        block_params = (4 + 2 * cfg.mlp_ratio) * l * d * d
        n_params = block_params + cfg.padded_vocab * d
        if self.has_position_table:
            n_params += (cfg.n_positions + cfg.pos_offset) * d
        flops = 6 * n_params
        if seq_len:
            flops += 12 * l * d * seq_len  # attention matmuls (fwd+bwd)
        return flops
