"""Activation checkpointing subsystem.

Capability match for the reference activation-checkpointing module
(runtime/activation_checkpointing/checkpointing.py — Megatron-compatible
``checkpoint()`` at :708, ``configure()`` from JSON at :789, partitioned
activations :366, CPU checkpointing :461). TPU-native translation:

  - ``checkpoint(fn)``        → ``jax.checkpoint`` (remat) with a policy
  - partition_activations     → policy `nothing_saveable` (recompute all;
                                the minimal-residency answer — under GSPMD
                                saved activations are already sharded, so
                                the reference's manual MP-rank partitioning
                                of saved tensors has no separate analogue)
  - cpu_checkpointing         → policy `offload_dots` (XLA host-offload of
                                saved dot outputs)
  - default                   → `dots_with_no_batch_dims_saveable`

The default keeps what is dear to recompute and recomputes the elementwise
work (norms, activation, residual adds): every matmul's output (nine
``[B, T, d]`` tensors a GPT-2 layer: qkv 3, out_proj 1, fc 4, proj 1) AND
the attention kernel's two results, which no dot policy can see inside a
``pallas_call``. The kernels' forward names them
(``ops/pallas/flash_attention_packed.py``: ``ATTN_OUT_NAME``,
``ATTN_LSE_NAME``) and the policy keeps them by name; without them the
backward ran the whole forward kernel a second time (5% of a GPT-2 350M
step) to have ``out_proj``'s input and the fused backward's residuals. Cost:
one more ``[B, T, d]`` tensor a layer (16 MiB at 8 x 1024 x 1024 bf16) and
an ``lse`` of 32 B a position and group of heads. An attention on the XLA
path carries no names and keeps what a dot policy keeps. Every policy, what
it keeps and what it costs: docs/activation_checkpointing.md.

``configure()`` records the module-level policy; models pick it up through
``current_policy()`` (GPT2Model applies it around its layer-scan body), and
the engine calls configure() when the user's JSON has an
`activation_checkpointing` block — the config is consumed, not just parsed.
"""

from typing import Optional

import jax

from ...ops.pallas.flash_attention_packed import (ATTN_LSE_NAME,
                                                  ATTN_OUT_NAME)
from ...utils.logging import log_dist


def _and_attention_residuals(dot_policy):
    """``dot_policy``, and the attention kernels' named output and lse."""
    return jax.checkpoint_policies.save_from_both_policies(
        dot_policy, jax.checkpoint_policies.save_only_these_names(
            ATTN_OUT_NAME, ATTN_LSE_NAME))


POLICIES = {
    "everything_saveable": jax.checkpoint_policies.everything_saveable,
    "nothing_saveable": jax.checkpoint_policies.nothing_saveable,
    "dots_saveable": _and_attention_residuals(
        jax.checkpoint_policies.dots_saveable),
    "dots_with_no_batch_dims_saveable": _and_attention_residuals(
        jax.checkpoint_policies.dots_with_no_batch_dims_saveable),
    "offload_dots":
        getattr(jax.checkpoint_policies, "offload_dot_with_no_batch_dims",
                None),
}

_config = None
_policy_name = "dots_with_no_batch_dims_saveable"


def policy_name_from_config(accfg) -> str:
    if accfg is None:
        return "dots_with_no_batch_dims_saveable"
    if accfg.cpu_checkpointing and POLICIES["offload_dots"] is not None:
        return "offload_dots"
    if accfg.partition_activations:
        return "nothing_saveable"
    return "dots_with_no_batch_dims_saveable"


DEFAULT_POLICY = "dots_with_no_batch_dims_saveable"


def get_policy(name: Optional[str] = None):
    """Resolve a policy by NAME. name=None is the static default — NOT the
    configure()d global (a model that wants the configured policy receives
    its name explicitly, e.g. via the engine; resolving globals here would
    leak one engine's config into unrelated models in the process)."""
    name = name or DEFAULT_POLICY
    if name in POLICIES and POLICIES[name] is None:
        raise ValueError(
            f"remat policy {name!r} is not available in this jax version "
            f"(jax.checkpoint_policies.offload_dot_with_no_batch_dims "
            f"missing)")
    policy = POLICIES.get(name)
    if policy is None:
        raise ValueError(
            f"unknown remat policy {name!r}; choose from "
            f"{sorted(k for k, v in POLICIES.items() if v is not None)}")
    if name == "offload_dots":
        # factory: offload saved dots to pinned host memory
        return policy("device", "pinned_host")
    return policy


def configure(mpu_=None, deepspeed_config=None, partition_activations=None,
              contiguous_checkpointing=None, num_checkpoints=None,
              checkpoint_in_cpu=None, synchronize=None, profile=None):
    """Reference configure() signature (checkpointing.py:789): flags given
    directly override the JSON block."""
    global _config, _policy_name
    accfg = getattr(deepspeed_config, "activation_checkpointing", None) \
        if deepspeed_config is not None else None
    if accfg is not None:
        _config = accfg
    if _config is not None:
        if partition_activations is not None:
            _config.partition_activations = partition_activations
        if checkpoint_in_cpu is not None:
            _config.cpu_checkpointing = checkpoint_in_cpu
        if num_checkpoints is not None:
            _config.number_checkpoints = num_checkpoints
    _policy_name = policy_name_from_config(_config)
    log_dist(f"activation checkpointing configured: policy={_policy_name}",
             ranks=[0])
    return _policy_name


def current_policy_name() -> str:
    return _policy_name


def is_configured() -> bool:
    return _config is not None


def checkpoint(function, *args, policy: Optional[str] = None):
    """Megatron-compatible: returns function(*args) under remat
    (reference checkpoint() :708). Uses the configure()d policy when none
    is given — this global-consuming surface IS the reference contract."""
    return jax.checkpoint(function,
                          policy=get_policy(policy or _policy_name))(*args)


def checkpoint_wrapper(function, policy: Optional[str] = None):
    """Wrap a function for later calls (the scan-body use case)."""
    return jax.checkpoint(function,
                          policy=get_policy(policy or _policy_name))
