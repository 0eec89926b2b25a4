"""Real lowered artifacts for the HLO auditors (ds_tpu_lint Plane A).

Each ``lower_*`` function builds the ACTUAL program the repo ships —
the ZeRO-3 train step with the bucketed overlap schedule and quantized
hierarchical collectives, the fused ``decode_with_slots`` serving step,
the compiled 1F1B pipe step, and the expert-parallel MoE step — lowers
it under the ambient backend (CPU-runnable: ``JAX_PLATFORMS=cpu`` with
8 virtual devices, exactly like benchmarks/overlap.py), and packages
compiled HLO + lowered StableHLO + argument roles + the comm dispatch's
per-op trace delta into an :class:`HloArtifact`.

Sizes: ``tiny`` keeps the tier-1 gate fast (the audited PROGRAM
STRUCTURE — bucket legs, replica groups, donation map — is identical
to the bench shape; only dims shrink); ``bench`` matches
benchmarks/overlap.py for the CLI / postmortem runs.

jax and deepspeed_tpu are imported inside the functions so the AST
plane (and audits of saved ``.hlo`` files) never pays the backend
import.
"""

from typing import Dict, List, Optional, Sequence

from .hlo_audit_rules import HloArtifact

__all__ = ["lower_train_step", "lower_decode_step", "lower_prefill_step",
           "lower_pipe_step", "lower_moe_step", "lower_spec_verify_step",
           "lower_spec_draft_step", "default_artifacts", "ARTIFACT_NAMES"]

ARTIFACT_NAMES = ("train_step_zero3", "decode_with_slots", "pipe_step",
                  "moe_step", "spec_verify", "spec_draft", "slot_prefill",
                  "slot_suffix_prefill", "slot_copy_lane", "slot_insert_lane")

#: model dims per size knob: (n_layer, n_embd, n_head, seq)
_SIZES = {"tiny": (4, 64, 4, 32), "bench": (8, 512, 8, 128)}


def _leaf_counts(*trees) -> List[int]:
    import jax
    return [len(jax.tree_util.tree_leaves(t)) for t in trees]


def _reset_mesh():
    from ..parallel import topology
    topology.reset_mesh()


def _train_engine(config_extra: Dict, size: str, model=None):
    import deepspeed_tpu
    from ..models.gpt2 import GPT2Config, GPT2Model
    n_layer, n_embd, n_head, seq = _SIZES[size]
    _reset_mesh()
    if model is None:
        model = GPT2Model(GPT2Config(
            vocab_size=256, n_positions=seq + 1, n_embd=n_embd,
            n_layer=n_layer, n_head=n_head, pad_vocab_to_multiple=8,
            scan_unroll=n_layer))
    config = {
        "train_micro_batch_size_per_gpu": 2,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "gradient_clipping": 1.0, "steps_per_print": 0,
    }
    config.update(config_extra)
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=config)
    return engine, seq


def _lower_engine_step(engine, seq: int, name: str,
                       donatable, donation_min_bytes: int) -> HloArtifact:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from .. import comm

    rng = np.random.default_rng(0)
    gbs = engine.train_micro_batch_size_per_gpu * engine.dp_world_size
    gas = engine.gradient_accumulation_steps
    batch = engine._to_device_batch({"input_ids": rng.integers(
        0, 250, (gas, gbs, seq), dtype=np.int32)})
    args = (engine.params, engine.opt_state, engine.scaler_state, batch,
            jnp.float32(1e-3), jax.random.PRNGKey(0), None,
            jnp.float32(1.0))
    per_before = comm.comm_per_op_stats()
    before = comm.comm_stats()
    with engine.mesh:
        lowered = engine._train_step_fn.lower(*args)
        stablehlo = lowered.as_text()
        hlo = lowered.compile().as_text()
    after = comm.comm_stats()
    per_after = comm.comm_per_op_stats()
    counts = _leaf_counts(*args)
    roles = ["params", "optimizer_state", "scaler", "batch"] + \
        ["scalar"] * (len(counts) - 4)
    return HloArtifact(
        name=name,
        hlo_texts=[hlo],
        stablehlo=stablehlo,
        arg_roles=list(zip(roles, counts)),
        donatable_roles=set(donatable),
        traced_per_op={k: per_after.get(k, 0) - per_before.get(k, 0)
                       for k in per_after},
        comm_delta={k: after[k] - before[k] for k in after},
        donation_min_bytes=donation_min_bytes,
        meta={"dp": engine.dp_world_size, "gas": gas},
    )


def lower_train_step(size: str = "tiny",
                     donation_min_bytes: Optional[int] = None
                     ) -> HloArtifact:
    """The bucketed + compressed ZeRO-3 bench train step — the PR-10
    schedule under the PR-6 wire (overlap_schedule on, int8
    hierarchical reduce-scatter): the artifact with the richest
    collective structure the repo emits."""
    if donation_min_bytes is None:
        donation_min_bytes = (16 << 10) if size == "tiny" else (1 << 20)
    engine, seq = _train_engine({
        "zero_optimization": {"stage": 3,
                              "stage3_param_persistence_threshold": 0},
        "overlap_schedule": {"enabled": True,
                             "bucket_bytes": (64 << 10) if size == "tiny"
                             else (4 << 20)},
        "comm_compression": {"all_gather": "int8", "reduce_scatter": "int8",
                             "hierarchical": True, "devices_per_host": 4},
    }, size)
    try:
        return _lower_engine_step(engine, seq, "train_step_zero3",
                                  ("params", "optimizer_state", "scaler"),
                                  donation_min_bytes)
    finally:
        engine.close()


def lower_pipe_step(size: str = "tiny", pp: int = 8,
                    donation_min_bytes: Optional[int] = None
                    ) -> HloArtifact:
    """The compiled 1F1B pipeline step (shard_map over 'pipe', ppermute
    stage hops through the comm dispatch). pp spans the whole mesh
    (dp=1): under an older jax, shard_map crashed XLA's partitioner
    on partial-manual regions with a non-trivial auto axis; not retested
    on jax 0.9 (ROADMAP B3), so the artifact keeps the pp-only layout —
    the collective
    structure under audit (per-tick ppermute chain + aux psum) is
    identical."""
    from ..models.gpt2 import GPT2Config, GPT2Model
    _, n_embd, n_head, seq = _SIZES[size]
    if donation_min_bytes is None:
        donation_min_bytes = (16 << 10) if size == "tiny" else (1 << 20)
    model = GPT2Model(GPT2Config(
        vocab_size=256, n_positions=seq + 1, n_embd=n_embd,
        n_layer=pp, n_head=n_head, pad_vocab_to_multiple=8))
    engine, seq = _train_engine({
        "train_batch_size": 16,
        "train_micro_batch_size_per_gpu": 8,
        "gradient_accumulation_steps": 2,
        "pipeline_parallel_size": pp,
        "zero_optimization": {"stage": 0},
    }, size, model=model)
    try:
        return _lower_engine_step(engine, seq, "pipe_step",
                                  ("params", "optimizer_state", "scaler"),
                                  donation_min_bytes)
    finally:
        engine.close()


def lower_moe_step(size: str = "tiny", ep: int = 4,
                   donation_min_bytes: Optional[int] = None
                   ) -> HloArtifact:
    """The expert-parallel MoE train step. Its dispatch/combine einsums
    reshard tokens data-axes ↔ expert-axis, which GSPMD lowers to an
    all-to-all that never passes through comm/comm.py — the HLO006
    finding this artifact exists to keep visible (waived with a
    tracking note: ROADMAP item 3)."""
    from ..models.gpt2_moe import GPT2MoEConfig, GPT2MoEModel
    if donation_min_bytes is None:
        donation_min_bytes = (16 << 10) if size == "tiny" else (1 << 20)
    n_layer, n_embd, n_head, seq = _SIZES["tiny"]   # MoE audit: structure,
    model = GPT2MoEModel(GPT2MoEConfig(             # not scale

        vocab_size=128, n_positions=seq + 1, n_embd=n_embd,
        n_layer=2, n_head=n_head, num_experts=ep, top_k=1,
        pad_vocab_to_multiple=8))
    engine, seq = _train_engine({
        "train_micro_batch_size_per_gpu": 4,
        "zero_optimization": {"stage": 2},
        "expert_parallel_size": ep,
    }, "tiny", model=model)
    try:
        return _lower_engine_step(engine, seq, "moe_step",
                                  ("params", "optimizer_state", "scaler"),
                                  donation_min_bytes)
    finally:
        engine.close()


class _LowerBeforeCall:
    """Stands in for an InferenceEngine's compile plane while one slot
    program is called: lowers the program observed under ``label`` with
    the very arguments the call is about to hand it. The engine observes
    BEFORE its call, so a pool the program consumes is still live here."""

    def __init__(self, label: str):
        self.label = label
        self.lowered = None     # (stablehlo, hlo, [(argument name, leaves)])

    def observe(self, label, fn, args, names=None, mesh=None):
        if label != self.label:
            return
        with mesh:
            lowered = fn.lower(*args)
            self.lowered = (lowered.as_text(), lowered.compile().as_text(),
                            list(zip(names, _leaf_counts(*args))))


def _slot_engine(max_len: int):
    import deepspeed_tpu
    from ..models.gpt2 import GPT2Config, GPT2Model

    _reset_mesh()
    model = GPT2Model(GPT2Config(vocab_size=128, n_positions=max_len * 2,
                                 n_embd=64, n_layer=2, n_head=4,
                                 pad_vocab_to_multiple=1, dtype="float32"))
    return deepspeed_tpu.init_inference(model, config={"dtype": "float32"})


def _lower_slot_program(engine, call, label: str, name: str,
                        donation_min_bytes: int, meta: Dict) -> HloArtifact:
    """Run ``call()`` (one InferenceEngine slot program, compile-plane
    label ``label``) and package the program it ran. Roles follow the
    names the engine gives its arguments: ``*params`` are the read-only
    weights, ``*pool`` is the KV pool — state in, state out in every
    slot program that returns one, so the one donatable role — and the
    rest is io."""
    from .. import comm

    per_before = comm.comm_per_op_stats()
    plane = engine.compile_plane = _LowerBeforeCall(label)
    try:
        call()
    finally:
        engine.compile_plane = None
    per_after = comm.comm_per_op_stats()
    stablehlo, hlo, named = plane.lowered
    roles = [("weights" if n.endswith("params") else
              "kv_slots" if n.endswith("pool") else "io", count)
             for n, count in named]
    return HloArtifact(
        name=name,
        hlo_texts=[hlo],
        stablehlo=stablehlo,
        arg_roles=roles,
        donatable_roles={"kv_slots"},
        traced_per_op={k: per_after.get(k, 0) - per_before.get(k, 0)
                       for k in per_after},
        donation_min_bytes=donation_min_bytes,
        meta=meta,
    )


def _decode_inputs(num_slots: int):
    import numpy as np
    return (np.zeros((num_slots,), np.int32),       # toks
            np.ones((num_slots,), np.int32),        # positions
            np.zeros((num_slots,), np.float32),     # temps
            np.zeros((num_slots,), np.int32),       # top_ks
            np.ones((num_slots,), np.float32),      # top_ps
            np.zeros((num_slots,), np.int32))       # seeds


def lower_decode_step(num_slots: int = 4, max_len: int = 32,
                      donation_min_bytes: int = 1 << 10) -> HloArtifact:
    """The fused all-slot decode step (``GPT2Model.decode_with_slots``
    under the slot pool) — the serving fleet's steady-state program.
    KV lanes are the donatable role here: an undonated pool doubles
    kv_slots HBM per tick."""
    engine = _slot_engine(max_len)
    pool = engine.init_slot_pool(num_slots, max_len)
    toks, positions, temps, *_ = _decode_inputs(num_slots)
    return _lower_slot_program(
        engine, lambda: engine.slot_decode_step(pool, toks, positions,
                                                temps),
        "slot_decode", "decode_with_slots", donation_min_bytes,
        {"num_slots": num_slots, "max_len": max_len})


#: the programs that write one lane of the pool they are given, by
#: artifact name: (compile-plane label, the call on (engine, pool)).
#: ``slot_chunk_prefill`` is not here: its logits head is dead code, jit
#: prunes the head's weights from the lowered signature, and roles in
#: flatten order would name the wrong arguments
#: (tests/unit/test_pool_donation.py holds it to the same rule).
_LANE_WRITERS = {
    "slot_prefill": ("slot_prefill", lambda e, pool: e.slot_prefill(
        pool, 1, list(range(5)))),
    "slot_suffix_prefill": ("slot_suffix_prefill",
                            lambda e, pool: e.slot_suffix_prefill(
                                pool, 1, list(range(5)), 3)),
    "slot_copy_lane": ("slot_copy", lambda e, pool: e.slot_copy_lane(
        pool, 0, 1)),
    "slot_insert_lane": ("slot_insert", lambda e, pool: e.slot_insert_lane(
        pool, 1, e.slot_extract_lane(pool, 0))),
}


def lower_prefill_step(program: str = "slot_prefill", num_slots: int = 4,
                       max_len: int = 32, quantize: bool = False,
                       donation_min_bytes: int = 1 << 10) -> HloArtifact:
    """One of the programs that change a single lane of the slot pool —
    the prefill, the suffix prefill, the prefix-reuse lane copy, the
    hand-off lane insert (``_LANE_WRITERS``). Each takes the pool and
    returns it, so the pool is the donatable role exactly as in decode:
    undonated, XLA allocates a second pool and copies all of it to
    change one lane (ISSUE 28: 5.6 GB and two stalled calls per prefill
    tick)."""
    label, call = _LANE_WRITERS[program]
    engine = _slot_engine(max_len)
    pool = engine.init_slot_pool(num_slots, max_len, quantize=quantize)
    return _lower_slot_program(
        engine, lambda: call(engine, pool), label, program,
        donation_min_bytes,
        {"num_slots": num_slots, "max_len": max_len, "quantize": quantize})


def _spec_engine(max_len: int):
    engine = _slot_engine(max_len)
    from ..serving.config import DraftConfig
    return engine, engine.init_draft(DraftConfig(mode="self", layers=1))


def lower_spec_verify_step(num_slots: int = 4, max_len: int = 32,
                           k: int = 2,
                           donation_min_bytes: int = 1 << 10) -> HloArtifact:
    """The speculative verify step (``GPT2Model.verify_with_slots`` +
    in-step accept/rollback under the slot pool) — one batched forward
    verifying k draft tokens per slot. The TARGET KV pool is the
    donatable role: verify is state-in/state-out per tick exactly like
    decode, so an undonated pool doubles kv_slots HBM."""
    import numpy as np

    engine, _draft = _spec_engine(max_len)
    pool = engine.init_slot_pool(num_slots, max_len)
    toks, positions, *sampling = _decode_inputs(num_slots)
    drafts = np.zeros((num_slots, k), np.int32)
    return _lower_slot_program(
        engine, lambda: engine.slot_verify_step(pool, toks, drafts,
                                                positions, *sampling),
        "slot_verify", "spec_verify", donation_min_bytes,
        {"num_slots": num_slots, "max_len": max_len, "k": k})


def lower_spec_draft_step(num_slots: int = 4, max_len: int = 32,
                          k: int = 2,
                          donation_min_bytes: int = 1 << 10) -> HloArtifact:
    """The speculative draft-propose step (k+1 draft decode steps in one
    compiled ``lax.scan``). The DRAFT KV pool is the donatable role —
    the draft pool rides the same state-in/state-out contract as the
    target pool, and HLO005 holds both sides to it."""
    engine, draft = _spec_engine(max_len)
    dpool = engine.init_draft_pool(draft, num_slots, max_len)
    return _lower_slot_program(
        engine, lambda: engine.slot_draft_propose(
            draft, dpool, *_decode_inputs(num_slots), k),
        "slot_draft", "spec_draft", donation_min_bytes,
        {"num_slots": num_slots, "max_len": max_len, "k": k,
         "draft": "self(layers=1)"})


def default_artifacts(size: str = "tiny",
                      include: Optional[Sequence[str]] = None
                      ) -> List[HloArtifact]:
    """The audited artifact set, in the ISSUE/tier-1 order. ``include``
    filters by artifact name."""
    builders = {
        "train_step_zero3": lambda: lower_train_step(size),
        "decode_with_slots": lambda: lower_decode_step(),
        "pipe_step": lambda: lower_pipe_step(size),
        "moe_step": lambda: lower_moe_step(size),
        "spec_verify": lambda: lower_spec_verify_step(),
        "spec_draft": lambda: lower_spec_draft_step(),
        **{name: (lambda name=name: lower_prefill_step(name))
           for name in _LANE_WRITERS},
    }
    names = include or ARTIFACT_NAMES
    return [builders[n]() for n in names]
