"""Pipeline engine.

Re-design of the reference PipelineEngine (runtime/pipe/engine.py:40): the
reference interprets instruction streams host-side, exchanging activations
with NCCL p2p (+ meta handshakes). TPU-native design: the ENTIRE 1F1B
schedule compiles into one XLA program —

  - ``jax.shard_map`` manual over the 'pipe' mesh axis (auto/GSPMD over
    data/expert/seq/model, so ZeRO + TP + MoE compose untouched)
  - ``lax.scan`` over M + S - 1 pipeline ticks; at tick t stage s computes
    micro-batch t - s
  - ``lax.ppermute`` shifts activations stage→stage (the reference's
    SendActivation/RecvActivation pair, pipe/p2p.py:50,71)
  - jax.grad reverses the whole thing: reverse-ppermute = SendGrad/RecvGrad,
    reverse-scan = the cooldown backward passes. The 1F1B ordering the
    reference hand-schedules becomes XLA's latency hiding.

Two execution modes:
  1. compiled (models exposing ``pipeline_spec()``: embed/block/head_loss
     over a stacked layer axis) — the performant path; requires
     n_layer % pp == 0.
  2. interpreted (heterogeneous ``PipelineModule`` layer lists) — executes
     the declarative ``TrainSchedule`` exactly as the reference's
     ``_exec_schedule`` instruction loop (engine.py:1286,_INSTRUCTION_MAP
     :1273), with jax.vjp per stage instead of autograd hooks. Reference
     semantics for tied weights (ReduceTiedGrads) included.
"""

import os
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ... import comm
from ...parallel.topology import PIPE_AXIS
from ...utils.logging import log_dist
from ..engine import DeepSpeedEngine, _cast_tree
from . import schedule as sched
from .module import PipelineModule


def _pipe_shard_map(body, mesh, in_specs, out_specs):
    """shard_map manual over ONLY the 'pipe' axis, replication check off
    (outputs are made consistent by the explicit ppermute/psum legs)."""
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, axis_names={PIPE_AXIS},
                         check_vma=False)


class PipelineEngine(DeepSpeedEngine):
    """Training engine for pp > 1. train_batch() consumes gradient_
    accumulation_steps micro-batches per global step (reference
    pipe/engine.py:285: gas == micro-batches per train_batch)."""

    def __init__(self, *args, **kwargs):
        model = kwargs.get("model") or (args[1] if len(args) > 1 else None)
        self._interpreted = isinstance(model, PipelineModule)
        self._stage_fn_cache = {}
        self._eager_interpret = bool(int(
            os.environ.get("DSTPU_PIPE_EAGER", "0")))
        if not self._interpreted:
            if not hasattr(model, "pipeline_spec"):
                raise ValueError("pipeline_parallel_size>1 needs a model "
                                 "with pipeline_spec() (e.g. GPT2Model) or a "
                                 "PipelineModule")
            self._pspec = model.pipeline_spec()
        super().__init__(*args, **kwargs)

    def _pre_init_validate(self):
        cfg = self._config
        routing = dict(dict(cfg.data_efficiency or {}).get("data_routing")
                       or {})
        if dict(cfg.progressive_layer_drop or {}).get("enabled") or \
                dict(routing.get("random_ltd") or {}).get("enabled"):
            raise ValueError(
                "progressive_layer_drop / random_ltd are not supported "
                "under pipeline parallelism (the pipeline stage functions "
                "bypass the model's forward kwargs)")
        if self._interpreted:
            return
        blocks = self.param_shapes[self._pspec["blocks_key"]]
        n_layer = jax.tree.leaves(blocks)[0].shape[0]
        pp = self.mesh_manager.pp
        if n_layer % pp != 0:
            raise ValueError(f"n_layer={n_layer} must divide by "
                             f"pipeline_parallel_size={pp}")
        if self.mesh_manager.sp > 1 and \
                getattr(getattr(self.module, "config", None),
                        "sp_attention", "ulysses") == "ring":
            raise ValueError(
                "ring attention nests a shard_map inside the pipeline's "
                "manual region; use sp_attention='ulysses' with pp>1")

    # ------------------------------------------------------------------
    # compiled 1F1B
    # ------------------------------------------------------------------
    def _pipeline_loss(self, params, batch, rng, train=True):
        """Mean micro-batch loss of the pipelined forward. batch leaves are
        [M, B, ...]; M = micro-batches (= gas)."""
        pspec = self._pspec
        mesh = self.mesh
        S = self.mesh_manager.pp
        blocks_key = pspec["blocks_key"]
        embed_fn, block_fn = pspec["embed"], pspec["block"]
        head_fn = pspec["head_loss"]
        aux_w = pspec.get("aux_loss_weight", 0.0)
        cdtype = self._compute_dtype or jnp.float32

        params = _cast_tree(params, self._compute_dtype)
        blocks = params[blocks_key]
        rest = {k: v for k, v in params.items() if k != blocks_key}
        M = jax.tree.leaves(batch)[0].shape[0]
        n_layer = jax.tree.leaves(self.param_shapes[blocks_key])[0].shape[0]
        lps = n_layer // S  # layers per stage

        # Embed ALL micro-batches OUTSIDE the shard_map, under plain GSPMD:
        # grad-of-gather (the wte scatter-add) inside a partial-manual
        # shard_map hard-crashes XLA's SPMD partitioner, and embedding on
        # every stage per tick would be redundant compute anyway.
        if rng is None:
            x_embeds = jax.vmap(
                lambda mb: embed_fn(rest, mb, None, train))(batch)
        else:
            erngs = jax.vmap(lambda i: jax.random.fold_in(rng, i))(
                jnp.arange(M))
            x_embeds = jax.vmap(
                lambda mb, r: embed_fn(rest, mb, r, train))(batch, erngs)
        # keep the shard_map boundary f32: the transpose of a replicated
        # (P()) input is a psum over 'pipe', and a bf16 cotangent psum at a
        # manual-region boundary crashes XLA's SPMD partitioner; the cast to
        # compute dtype happens inside the body instead
        x_embeds = x_embeds.astype(jnp.float32)

        def body(blocks_local, x_embeds, rng):
            sid = lax.axis_index(PIPE_AXIS)
            x_embeds = x_embeds.astype(cdtype)

            def run_stage(x, micro_idx):
                """Scan my lps layers over activation x."""
                def layer(carry, lp):
                    h, li = carry
                    lrng = (None if rng is None else
                            jax.random.fold_in(jax.random.fold_in(rng, micro_idx), li))
                    h, aux = block_fn(lp, h, lrng, train)
                    return (h, li + 1), aux
                (x, _), auxs = lax.scan(layer, (x, sid * lps), blocks_local)
                return x, jnp.sum(auxs)

            # remat each stage body: the tick-scan then stashes only the
            # [B,T,D] stage boundaries (the reference's activation-
            # checkpointing-between-stages default, pipe/module.py:302)
            run_stage = jax.checkpoint(
                run_stage, policy=jax.checkpoint_policies.nothing_saveable)

            def tick(carry, t):
                state, aux_sum = carry
                x = jnp.where(sid == 0, x_embeds[jnp.clip(t, 0, M - 1)],
                              state.astype(cdtype))
                micro_idx = t - sid
                x, aux = run_stage(x, micro_idx)
                valid = (micro_idx >= 0) & (micro_idx < M)
                aux_sum = aux_sum + jnp.where(valid, aux, 0.0)
                # comm.ppermute, not raw lax: byte-identical HLO, but the
                # stage hop lands in the wire accounting (ds_tpu_lint
                # AST001 polices raw collectives outside comm/ and ops/)
                nxt = comm.ppermute(x, [(i, i + 1) for i in range(S - 1)],
                                    PIPE_AXIS)
                return (nxt, aux_sum), x

            init = (jnp.zeros(x_embeds.shape[1:], cdtype), jnp.float32(0.0))
            (_, aux_sum), ys = lax.scan(tick, init, jnp.arange(M + S - 1))
            # my stage's outputs per tick: [M+S-1, B, T, D]. The last M ticks
            # of the LAST stage are the final activations of micros 0..M-1 —
            # sliced outside via the stacked out_spec (a static slice; no
            # collective, and its transpose is a zero-pad, not a scatter)
            outs = ys[S - 1:]
            aux = comm.all_reduce(aux_sum, axis_name=PIPE_AXIS)
            return outs, aux

        outs, aux = _pipe_shard_map(
            body, mesh,
            in_specs=(P(PIPE_AXIS), P(), P()),
            out_specs=(P(PIPE_AXIS), P()),
        )(blocks, x_embeds, rng)
        # stacked over stages: [S*M, B, T, D]; the last stage's block holds
        # the pipeline outputs. head + loss run out here under plain GSPMD
        # (take_along_axis grads = scatter, which the manual-pipe region
        # cannot partition).
        final = outs[(S - 1) * M:]
        micro_losses = jax.vmap(
            lambda x, mb: head_fn(rest, x, mb))(final, batch)
        loss = jnp.mean(micro_losses)
        if aux_w:
            loss = loss + aux_w * aux / (M * n_layer)
        return loss

    def _compile_fns(self):
        if self._interpreted:
            super()._compile_fns()
            self._init_interpreter()
            return
        mesh = self.mesh
        rep = NamedSharding(mesh, P())

        # pld_theta/random-ltd modifiers are not supported by the compiled
        # pipeline (the stage functions bypass the model's forward kwargs);
        # configs enabling them raise in __init__ — the arg exists only to
        # match the base train_batch calling convention.
        def train_step(params, opt_state, scaler_state, batch, lr, rng,
                       pld_theta=None, loss_mul=None):
            scale = scaler_state.scale
            if loss_mul is not None:   # nan_loss fault point (resilience)
                scale = scale * loss_mul

            def scaled_loss(p):
                return self._pipeline_loss(p, batch, rng) * scale

            loss, grads = jax.value_and_grad(scaled_loss)(params)
            grads = lax.with_sharding_constraint(
                grads, jax.tree.map(lambda s: s.spec, self.grad_shardings))
            new_params, new_opt, new_scaler, finite, grad_norm, applied = \
                self._apply_update(params, opt_state, scaler_state, grads, lr,
                                   denom=jnp.float32(1.0))
            metrics = {
                "loss": loss / scale,
                "grad_norm": grad_norm,
                "loss_scale": scaler_state.scale,
                "overflow": ~finite,
                "applied": applied,
            }
            return new_params, new_opt, new_scaler, metrics

        self._train_step_fn = jax.jit(
            train_step,
            in_shardings=(self.param_shardings, self.opt_state_shardings,
                          None, self._batch_sharding(True), None, None,
                          None, None),
            out_shardings=(self.param_shardings, self.opt_state_shardings,
                           None, None),
            donate_argnums=(0, 1, 2)) if self.optimizer is not None else None

        def eval_loss(params, batch):
            return self._pipeline_loss(params, batch, None, train=False)

        self._eval_fn = jax.jit(
            eval_loss,
            in_shardings=(self.param_shardings, self._batch_sharding(True)),
            out_shardings=rep)

        # reference-style forward/backward/step API is not meaningful at
        # micro granularity for a compiled pipeline; train_batch is the API
        # (reference pipe/engine.py:285 likewise forbids engine.forward)
        self._micro_grad_fn = None
        self._acc_fn = None

        def apply_step(params, opt_state, scaler_state, grads, lr, denom):
            new_params, new_opt, new_scaler, finite, grad_norm, applied = \
                self._apply_update(params, opt_state, scaler_state, grads, lr,
                                   denom)
            return new_params, new_opt, new_scaler, {
                "grad_norm": grad_norm, "overflow": ~finite,
                "applied": applied, "loss_scale": scaler_state.scale}

        self._apply_fn = jax.jit(
            apply_step,
            in_shardings=(self.param_shardings, self.opt_state_shardings,
                          None, self.grad_shardings, None, None),
            out_shardings=(self.param_shardings, self.opt_state_shardings,
                           None, None),
            donate_argnums=(0, 1, 2, 3)) if self.optimizer is not None else None

    def forward(self, *a, **k):
        if not self._interpreted:
            raise RuntimeError("PipelineEngine does not expose forward(); "
                               "use train_batch/eval_batch (reference "
                               "pipe/engine.py TRAIN_BATCH-only API)")
        return super().forward(*a, **k)

    # ------------------------------------------------------------------
    # interpreted mode: execute the declarative TrainSchedule with vjp
    # ------------------------------------------------------------------
    def _init_interpreter(self):
        """Heterogeneous PipelineModule execution. On a pp>1 mesh each
        stage's layers are PLACED on that stage's slice of the 'pipe' axis
        (reference: one process group per stage, pipe/engine.py); the host
        drives the TrainSchedule, and async dispatch overlaps stage s's
        micro t with stage s+1's micro t-1 — real pipelining, arbitrary
        per-layer shapes (no ppermute shape constraint)."""
        self._stage_cache: Dict[Any, Any] = {}
        pp = self.mesh_manager.pp
        self._stage_shardings = None
        if pp > 1:
            from jax.sharding import Mesh
            axes = tuple(a for a in self.mesh.axis_names if a != PIPE_AXIS)
            pipe_pos = self.mesh.axis_names.index(PIPE_AXIS)
            self._stage_shardings = []
            for s in range(pp):
                devs = np.take(self.mesh.devices, s, axis=pipe_pos)
                sub = Mesh(devs, axes)
                self._stage_shardings.append(NamedSharding(sub, P()))
            self._restage_params()

    def _stage_for_layer(self, layer_idx: int, ranges) -> int:
        for s, (a, b) in enumerate(ranges):
            if a <= layer_idx < b:
                return s
        return len(ranges) - 1

    def _restage_params(self):
        """Move each layer's params onto its stage's devices; tied subtrees
        are replicated per consuming stage lazily (cached per step)."""
        if self._stage_shardings is None:
            return
        ranges = self._stage_ranges(self.mesh_manager.pp)
        layers = list(self.params["layers"])
        for i in range(len(layers)):
            sh = self._stage_shardings[self._stage_for_layer(i, ranges)]
            layers[i] = jax.device_put(layers[i], sh)
        self.params = dict(self.params, layers=layers)

    def _tied_for_stage(self, tied_p, s):
        if self._stage_shardings is None:
            return tied_p
        key = ("tied", s, self.global_steps)
        if key not in self._stage_cache:
            self._stage_cache = {k: v for k, v in self._stage_cache.items()
                                 if k[2] == self.global_steps}
            self._stage_cache[key] = jax.device_put(
                tied_p, self._stage_shardings[s])
        return self._stage_cache[key]

    def _to_stage(self, x, s):
        if self._stage_shardings is None:
            return x
        return jax.device_put(x, self._stage_shardings[s])

    def _stage_ranges(self, stages: int):
        module: PipelineModule = self.module
        module.num_stages = stages
        parts = module._partition_layers()
        return [(parts[i], parts[i + 1]) for i in range(stages)]

    def _stage_apply(self, a: int, b: int, last: bool):
        """Callable: (layer_params a..b, tied, x_or_batch, batch, rng) →
        activation or loss."""
        module: PipelineModule = self.module

        def fn(stage_params, tied, x, batch, rng):
            if a == 0:
                if isinstance(x, dict) and "inputs" in x:
                    x = x["inputs"]
                if module.batch_fn is not None:
                    x = module.batch_fn(x)
            for j, layer_idx in enumerate(range(a, b)):
                layer = module._layers[layer_idx]
                p = module.layer_params(stage_params[j], tied, layer_idx)
                lrng = None if rng is None else jax.random.fold_in(rng, layer_idx)
                x = layer.apply(p, x, rng=lrng, train=True)
            if last and module.loss_fn is not None:
                return module.loss_fn(x, batch)
            return x

        return fn

    def _compiled_stage_fns(self, a: int, b: int, last: bool):
        """Jitted forward and backward for one stage of the interpreted
        executor. The schedule stays host-interpreted (mailboxes, stage
        hops), but per-micro compute compiles ONCE per stage instead of
        re-tracing jax.vjp on every micro (round-2 review: the eager
        interpreter was the only path for heterogeneous PipelineModules
        and far slower than it needed to be). jax.vjp runs INSIDE the
        jitted forward — its returned VJP is a tree_util.Partial pytree
        (residual arrays as leaves), so it crosses the jit boundary and
        feeds the jitted backward with no forward recompute. Set
        DSTPU_PIPE_EAGER=1 to restore the eager path (debugging)."""
        key = (a, b, last)
        if key not in self._stage_fn_cache:
            fn = self._stage_apply(a, b, last)

            def fwd(stage_p, tied, x, batch, rng):
                return jax.vjp(
                    lambda sp, tp, xx: fn(sp, tp, xx, batch, rng),
                    stage_p, tied, x)

            self._stage_fn_cache[key] = (jax.jit(fwd),
                                         jax.jit(lambda vjp, g: vjp(g)))
        return self._stage_fn_cache[key]

    @staticmethod
    @jax.jit
    def _tree_add(t1, t2):
        return jax.tree.map(jnp.add, t1, t2)

    def train_batch(self, data_iter=None, batch=None):
        if self._interpreted and self.mesh_manager.pp > 1:
            if batch is None:
                batch = self._next_gas_batch(data_iter)
            # same pre-step hooks as the base path (curriculum, throughput)
            batch = self._apply_curriculum(batch)
            self.tput_timer.start()
            loss = self.train_batch_interpreted(
                batch, num_stages=self.mesh_manager.pp)
            self.tput_timer.stop(global_step=True)
            return loss
        return super().train_batch(data_iter=data_iter, batch=batch)

    def train_batch_interpreted(self, batch, num_stages: int = None):
        """Run one global step by interpreting TrainSchedule instruction
        streams — the reference execution model (_exec_schedule). On a
        pp>1 mesh each stage computes on ITS devices (activations/grads
        hop stage→stage via device_put, the p2p of pipe/p2p.py); on pp=1
        the stages are virtual (semantic reference for parity tests)."""
        assert self._interpreted
        cfg = self._config
        module: PipelineModule = self.module
        if num_stages is None:
            num_stages = max(2, self.mesh_manager.pp)
        batch = self._to_device_batch(batch)
        micros = [jax.tree.map(lambda x: x[i], batch)
                  for i in range(jax.tree.leaves(batch)[0].shape[0])]
        M, S = len(micros), num_stages
        ranges = self._stage_ranges(S)
        rng = jax.random.fold_in(self._base_rng, self.global_steps)

        layers_p = self.params["layers"]
        tied_p = self.params["tied"]
        grads_layers = jax.tree.map(jnp.zeros_like, layers_p)
        grads_tied_acc = [jax.tree.map(jnp.zeros_like, tied_p)]
        act_mail: Dict[Any, Any] = {}
        grad_mail: Dict[Any, Any] = {}
        vjps: Dict[Any, Any] = {}
        losses: List[Any] = []

        schedules = [list(sched.TrainSchedule(M, S, s)) for s in range(S)]
        iters = [iter(s) for s in schedules]
        pending = [next(i, None) for i in iters]
        stage_inputs: Dict[Any, Any] = {}

        def deps_ready(s, cmds):
            for c in cmds:
                if isinstance(c, sched.RecvActivation) and \
                        (s - 1, c.buffer_id) not in act_mail:
                    return False
                if isinstance(c, sched.RecvGrad) and \
                        (s + 1, c.buffer_id) not in grad_mail:
                    return False
            return True

        while any(p is not None for p in pending):
            progressed = False
            for s in range(S):
                cmds = pending[s]
                if cmds is None or not deps_ready(s, cmds):
                    continue
                a, b = ranges[s]
                stage_p = [layers_p[i] for i in range(a, b)]
                last = s == S - 1
                for c in cmds:
                    m = getattr(c, "buffer_id", None)
                    if isinstance(c, sched.LoadMicroBatch):
                        stage_inputs[(s, m)] = self._to_stage(micros[m], s)
                    elif isinstance(c, sched.RecvActivation):
                        # the stage→stage activation hop (pipe/p2p.py recv)
                        stage_inputs[(s, m)] = self._to_stage(
                            act_mail.pop((s - 1, m)), s)
                    elif isinstance(c, sched.ForwardPass):
                        x = stage_inputs[(s, m)]
                        mrng = jax.random.fold_in(rng, m)
                        tied_s = self._tied_for_stage(tied_p, s)
                        mb_s = self._to_stage(micros[m], s) if last else \
                            micros[m]
                        if self._eager_interpret:
                            fn = self._stage_apply(a, b, last)
                            out, vjp = jax.vjp(
                                lambda sp, tp, xx: fn(sp, tp, xx, mb_s,
                                                      mrng),
                                stage_p, tied_s, x)
                            vjps[(s, m)] = vjp
                        else:
                            fwd, _ = self._compiled_stage_fns(a, b, last)
                            out, vjp = fwd(stage_p, tied_s, x, mb_s, mrng)
                            vjps[(s, m)] = vjp
                        if last:
                            losses.append(out)
                        else:
                            stage_inputs[(s, m, "out")] = out
                    elif isinstance(c, sched.SendActivation):
                        act_mail[(s, m)] = stage_inputs.pop((s, m, "out"))
                    elif isinstance(c, sched.RecvGrad):
                        # the grad hop back (pipe/p2p.py SendGrad/RecvGrad)
                        stage_inputs[(s, m, "gin")] = self._to_stage(
                            grad_mail.pop((s + 1, m)), s)
                    elif isinstance(c, sched.BackwardPass):
                        # loss cotangent: mean over micros, scaled for fp16 (the
                        # _apply_fn unscales by scaler_state.scale). Placed
                        # on the stage: scaler_state is committed to the
                        # FULL mesh after a step, and a full-mesh cotangent
                        # against stage-placed residuals is a device clash.
                        g = (self._to_stage(
                            jnp.float32(1.0 / M) * self.scaler_state.scale, s)
                             if last else stage_inputs.pop((s, m, "gin")))
                        if self._eager_interpret:
                            dstage, dtied, dx = vjps.pop((s, m))(g)
                        else:
                            _, bwd_fn = self._compiled_stage_fns(a, b, last)
                            dstage, dtied, dx = bwd_fn(vjps.pop((s, m)), g)
                        for j, layer_idx in enumerate(range(a, b)):
                            grads_layers[layer_idx] = self._tree_add(
                                grads_layers[layer_idx], dstage[j])
                        if self._stage_shardings is not None:
                            # tied grads accumulate across STAGES — bring
                            # them to a common placement first
                            dtied = jax.device_put(
                                dtied, NamedSharding(self.mesh, P()))
                        grads_tied_acc[0] = self._tree_add(grads_tied_acc[0],
                                                           dtied)
                        stage_inputs[(s, m, "gout")] = dx
                    elif isinstance(c, sched.SendGrad):
                        grad_mail[(s, m)] = stage_inputs.pop((s, m, "gout"))
                    elif isinstance(c, sched.ReduceTiedGrads):
                        pass  # accumulated into grads_tied_acc already
                    elif isinstance(c, sched.ReduceGrads):
                        pass  # single-controller: grads are already global
                    elif isinstance(c, sched.OptimizerStep):
                        pass  # applied once below
                pending[s] = next(iters[s], None)
                progressed = True
            assert progressed, "schedule deadlock (invalid instruction stream)"

        grads = {"layers": grads_layers, "tied": grads_tied_acc[0]}
        lr = jnp.float32(self.get_lr()[0])
        with self.mesh:
            (self.params, self.opt_state, self.scaler_state,
             metrics) = self._apply_fn(self.params, self.opt_state,
                                       self.scaler_state, grads, lr,
                                       jnp.float32(1.0))
        self._restage_params()  # updated layers back onto their stages
        self.micro_steps += M
        loss = jnp.mean(jnp.stack(losses))
        metrics = dict(metrics)
        metrics["loss"] = loss
        self._post_step(metrics)
        return loss
