"""Flops profiler — jaxpr cost analysis + engine step hook.

Capability match for the reference flops profiler
(profiling/flops_profiler/profiler.py:23 ``FlopsProfiler``: monkey-patches
~50 torch functionals to count FLOPs/MACs, module-tree report, engine
activation at a configured step). TPU-native translation: the model is a
traced program, so instead of patching call sites we WALK THE JAXPR —
every dot_general/conv/elementwise equation contributes analytically, scans
multiply by trip count — and cross-check totals against XLA's own
``compiled.cost_analysis()``. The per-primitive table replaces the torch
module tree (function-level attribution; jax has no module hierarchy at
trace time).

Engine hook: at ``flops_profiler.profile_step`` the engine profiles its
compiled train step and prints/writes the report (reference
engine.py:1646-1664 start/stop wiring).
"""

from typing import Any, Dict, Optional

import jax
import numpy as np

from ..telemetry.hlo_cost import SCOPES, scope_words


def _prod(xs):
    out = 1
    for x in xs:
        out *= int(x)
    return out


def _dot_general_flops(eqn) -> int:
    a, b = eqn.invars[0].aval, eqn.invars[1].aval
    dims = eqn.params["dimension_numbers"]
    (contract_a, _), (batch_a, _) = dims
    batch = _prod(a.shape[i] for i in batch_a)
    contract = _prod(a.shape[i] for i in contract_a)
    m = _prod(a.shape[i] for i in range(len(a.shape))
              if i not in contract_a and i not in batch_a)
    n = _prod(b.shape[i] for i in range(len(b.shape))
              if i not in dims[0][1] and i not in dims[1][1])
    return 2 * batch * m * n * contract


def _conv_flops(eqn) -> int:
    rhs = eqn.invars[1].aval
    out = eqn.outvars[0].aval
    # per output element the fan-in is kernel_spatial x in_channels =
    # prod(kernel shape) / out_channels (default HWIO kernel layout)
    fan_in = _prod(rhs.shape) // max(1, rhs.shape[-1])
    return 2 * _prod(out.shape) * fan_in


_ELEMENTWISE = {
    "add", "sub", "mul", "div", "max", "min", "pow", "exp", "log", "tanh",
    "logistic", "rsqrt", "sqrt", "neg", "abs", "sign", "floor", "ceil",
    "round", "erf", "integer_pow", "select_n", "clamp", "and", "or", "xor",
    "not", "lt", "le", "gt", "ge", "eq", "ne", "convert_element_type",
    "cos", "sin",
}
_REDUCE = {"reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
           "reduce_and", "reduce_or", "argmax", "argmin", "cumsum",
           "cumlogsumexp", "cummax"}


#: the named_scope words recognised in name stacks: the one vocabulary the
#: models and the engines set (reference profiler.py:239 prints the torch
#: module tree — the phase tree is the jax equivalent, since there is no
#: module hierarchy at trace time, only the name stack)
PHASES = SCOPES


def _phase_of(eqn) -> str:
    """The equation's outermost scope word, looking through ``layers`` (the
    layer scan, which holds ``attn`` and ``mlp``) to the word below it."""
    try:
        stack = str(eqn.source_info.name_stack)
    except Exception:
        return "other"
    words = scope_words(stack)
    if words[:1] == ["layers"] and len(words) > 1:
        words = words[1:]
    return words[0] if words else "other"


def jaxpr_flops(jaxpr, breakdown: Optional[Dict[str, int]] = None,
                mult: int = 1, phases: Optional[Dict[str, int]] = None) -> int:
    """Analytic FLOPs of a (closed) jaxpr; scans multiply by length.
    ``phases`` collects per-named-scope-phase totals (embed/attn/mlp/...)."""
    if hasattr(jaxpr, "jaxpr"):
        jaxpr = jaxpr.jaxpr
    total = 0
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        flops = 0
        inner_mult = mult
        if name == "dot_general":
            flops = _dot_general_flops(eqn)
        elif name == "conv_general_dilated":
            flops = _conv_flops(eqn)
        elif name in _ELEMENTWISE:
            flops = _prod(eqn.outvars[0].aval.shape)
        elif name in _REDUCE:
            flops = _prod(eqn.invars[0].aval.shape)
        elif name == "scan":
            length = eqn.params.get("length", 1)
            total += jaxpr_flops(eqn.params["jaxpr"], breakdown,
                                 mult * length, phases)
            continue
        elif name == "while":
            # trip count unknown at trace time: count one iteration
            total += jaxpr_flops(eqn.params["body_jaxpr"], breakdown, mult,
                                 phases)
            continue
        elif name == "cond":
            branches = eqn.params.get("branches", ())
            if branches:  # one branch executes: take the max, and merge
                #           only ITS breakdown (totals must match the table)
                flops_per = []
                for b in branches:
                    bd, ph = {}, {}
                    flops_per.append((jaxpr_flops(b, bd, mult, ph), bd, ph))
                best_flops, best_bd, best_ph = max(flops_per,
                                                   key=lambda t: t[0])
                total += best_flops
                if breakdown is not None:
                    for k, v in best_bd.items():
                        breakdown[k] = breakdown.get(k, 0) + v
                if phases is not None:
                    for k, v in best_ph.items():
                        phases[k] = phases.get(k, 0) + v
            continue
        elif "jaxpr" in eqn.params:  # pjit / remat / custom_vjp call, etc.
            total += jaxpr_flops(eqn.params["jaxpr"], breakdown, mult,
                                 phases)
            continue
        elif "call_jaxpr" in eqn.params:
            total += jaxpr_flops(eqn.params["call_jaxpr"], breakdown, mult,
                                 phases)
            continue
        flops *= inner_mult
        total += flops
        if flops:
            if breakdown is not None:
                breakdown[name] = breakdown.get(name, 0) + flops
            if phases is not None:
                ph = _phase_of(eqn)
                phases[ph] = phases.get(ph, 0) + flops
    return total


def _num_to_string(num, precision=2):
    for unit, scale in (("T", 1e12), ("G", 1e9), ("M", 1e6), ("K", 1e3)):
        if abs(num) >= scale:
            return f"{num / scale:.{precision}f} {unit}"
    return str(num)


class FlopsProfiler:
    """profile(fn, *args) → dict report. fn may be jitted or plain."""

    def __init__(self, config=None):
        self.config = config

    def profile(self, fn, *args, **kwargs) -> Dict[str, Any]:
        breakdown: Dict[str, int] = {}
        xla_flops = None
        if hasattr(fn, "lower"):
            # cost_analysis on the LOWERED stage only (no .compile() — an
            # AOT compile would NOT hit the jit executable cache and can
            # cost minutes on a real model mid-training); normalized by
            # the shared HLO cost core (telemetry/hlo_cost.py), the same
            # parser hlo_audit and the compile ledger consume
            try:
                from ..telemetry.hlo_cost import cost_summary
                cost = cost_summary(fn.lower(*args, **kwargs).cost_analysis())
                xla_flops = cost.get("flops")
            except Exception:
                pass
        closed = jax.make_jaxpr(lambda *a, **k: fn(*a, **k))(*args, **kwargs)
        phases: Dict[str, int] = {}
        total = jaxpr_flops(closed, breakdown, phases=phases)
        return {"flops": total, "macs": total // 2,
                "xla_flops": xla_flops, "per_primitive": breakdown,
                "per_phase": phases}

    def report(self, prof: Dict[str, Any], params: Optional[int] = None,
               latency_s: Optional[float] = None, top: int = 10,
               wall_fractions: Optional[Dict[str, float]] = None) -> str:
        """Reference-style tree report (profiler.py:239 prints the torch
        module tree; the phase tree is the jax equivalent). Pass
        ``wall_fractions`` ({phase: share}, e.g. summed from a device trace
        joined to ``engine.scope_tables()``: docs/observability.md) for
        MEASURED per-phase wall — otherwise the wall column is
        flops-proportional and labelled so."""
        if not wall_fractions:
            wall_fractions = None   # {} = no trace found: honest fallback
        lines = ["-" * 60, "deepspeed_tpu flops profiler", "-" * 60]
        if params is not None:
            lines.append(f"params:               {_num_to_string(params)}")
        lines.append(f"flops (analytic):     {_num_to_string(prof['flops'])}")
        if prof.get("xla_flops"):
            lines.append(
                f"flops (XLA cost):     {_num_to_string(prof['xla_flops'])}")
        lines.append(f"MACs:                 {_num_to_string(prof['macs'])}")
        if latency_s:
            lines.append(f"latency:              {latency_s * 1e3:.2f} ms")
            lines.append(
                f"achieved:             "
                f"{_num_to_string(prof['flops'] / latency_s)}FLOPS")
        per_phase = prof.get("per_phase") or {}
        if per_phase:
            wall_src = "measured" if wall_fractions else "flops-proportional"
            lines.append(f"model tree (phases; wall = {wall_src}):")
            order = [p for p in PHASES if p in per_phase] + \
                sorted(k for k in per_phase if k not in PHASES)
            for ph in order:
                fl = per_phase[ph]
                pct = 100.0 * fl / max(1, prof["flops"])
                if wall_fractions is not None and ph not in wall_fractions:
                    # never mix units: a phase the trace didn't see prints
                    # n/a instead of smuggling in its flops fraction
                    wall_col = "  n/a wall"
                    wf = None
                else:
                    wf = (wall_fractions or {}).get(
                        ph, fl / max(1, prof["flops"]))
                    wall_col = f"{100 * wf:5.1f}% wall"
                line = (f"  {ph:<10} {_num_to_string(fl):>12}  "
                        f"{pct:5.1f}% flops  {wall_col}")
                if latency_s and wf is not None:
                    line += f"  ({wf * latency_s * 1e3:7.2f} ms)"
                lines.append(line)
        items = sorted(prof["per_primitive"].items(), key=lambda kv: -kv[1])
        lines.append("top primitives:")
        for name, fl in items[:top]:
            pct = 100.0 * fl / max(1, prof["flops"])
            lines.append(f"  {name:<28} {_num_to_string(fl):>12}  {pct:5.1f}%")
        lines.append("-" * 60)
        return "\n".join(lines)


def get_model_profile(model, batch, rng=None) -> Dict[str, Any]:
    """Reference get_model_profile(): profile a ModelSpec's forward."""
    params = model.init(rng if rng is not None else jax.random.PRNGKey(0))
    prof = FlopsProfiler().profile(
        lambda p, b: model.apply(p, b, rng=None, train=False), params, batch)
    prof["params"] = sum(int(np.prod(x.shape))
                         for x in jax.tree.leaves(params))
    return prof
