"""Device time under the program's own names (``_scope_join``): what a
prefill costs a bucket token in its attention over the cache, in its
feed-forward layers and outside the layer scan; what the sampler takes of a
decode step; a train step by pass and its optimizer; the part of a step a
collective covers alone; and the share of the busy time that no scope
names. Each returns ``None`` without a trace, for a program that keeps no
scope tables (the parent of the PR that added them), and where the cell ran
no call of the program it reads."""

import bisect
import json

from chipbench import trace as T
from chipbench.layer_metrics import _scope_join as J

ATTEND = {"kv_read", "attend_window", "attend_full"}
FFN = {"mlp", "dense_mlp", "moe"}       # moe holds its experts and router
OUTSIDE = {"embed", "head", "sample"}


def _per_token(match):
    def read(ctx, record, trace):
        got = J.load(ctx, trace)
        if got is None or not got.bucket_tokens:
            return None
        return got.under("jit_pf", match) / got.bucket_tokens * 1e6
    return read


def _per_call(module, match):
    def read(ctx, record, trace):
        got = J.load(ctx, trace)
        if got is None or not got.calls.get(module) or \
                module not in got.seconds:
            return None
        return got.under(module, match) / got.calls[module] * 1e3
    return read


def collective_exposed_ms(ctx, record, trace):
    """A step's time on device 0 that a collective covers and no other
    operation does: the union of the collectives of "XLA Ops" and of the
    start-to-done spans of "Async XLA Ops", less the union of every other
    innermost operation (a loop's own pieces left out), inside the train
    step's module intervals, over their count. The log splits it by the
    pass of the scope of the collective that covers each piece (the one
    that started last)."""
    got = J.load(ctx, trace)
    if got is None:
        return None
    steps = [(k, m) for k, m in enumerate(got.intervals)
             if m[2] == "jit_train_step"]
    dev = trace.devices[0]
    named = sorted(x for x in list(dev.ops) + list(dev.async_ops)
                   if T.COLLECTIVE.match(x[2]))
    if not steps or not named:
        return None
    pieces = T._kept(dev, "self", lambda: T.self_events(dev.ops))
    other = T.union((s, e) for s, e, name in pieces
                    if not T.COLLECTIVE.match(name)
                    and not J.ENCLOSING.match(name))
    covered = T.union((s, e) for s, e, _ in named)
    alone = T.Merged(T.subtract(covered, other))
    table = next((got.tables_of[k] for k, _ in steps
                  if got.tables_of[k]), {})
    starts = [x[0] for x in named]
    by_pass = {}
    for _, (lo, hi, _) in steps:
        for s, e in alone.within(lo, hi):
            s, e = max(s, lo), min(e, hi)
            at = bisect.bisect_right(starts, s)
            name = next((named[k][2] for k in range(at - 1, max(at - 65, -1),
                                                    -1) if named[k][1] > s),
                        "")
            scope = table.get(name.replace(J.MARKER, "")) or "None"
            part = scope.lstrip("?").split("/")[0]
            by_pass[part] = by_pass.get(part, 0.0) + e - s
    n = len(steps)
    ctx.log(f"collective time alone on device 0, ms a step over {n} steps, "
            f"by the pass of the collective's scope: " + json.dumps(
                {k: round(v / n * 1e3, 4) for k, v in sorted(by_pass.items())})
            + f"; collectives cover "
            f"{sum(T.Merged(covered).seconds(lo, hi) for _, (lo, hi, _) in steps) / n * 1e3:.3f} "
            f"ms a step in all")
    return sum(by_pass.values()) / n * 1e3


def _unnamed(kind):
    """Busy time no scope names: on instructions that read ``None`` and in
    modules without a table. (Time under an inferred scope, ``?``, is not
    in it: ``_scope_join`` logs that share beside it.)"""
    def read(ctx, record, trace):
        got = J.load(ctx, trace) if ctx.cell["job"] == kind else None
        busy = trace.busy_s(0) if got is not None else 0.0
        if busy <= 0 or not got.seconds:
            return None
        return 100.0 * (got.untabled + sum(got.unnamed.values())) / busy
    return read


_has = lambda words: lambda parts: bool(words & set(parts))
_pass = lambda name: lambda parts: parts[0] == name

METRICS = {
    "prefill_attend_us_per_token": _per_token(_has(ATTEND)),
    "prefill_ffn_us_per_token": _per_token(_has(FFN)),
    "prefill_head_us_per_token": _per_token(
        lambda parts: "layers" not in parts and bool(OUTSIDE & set(parts))),
    "decode_sample_ms": _per_call("jit_dec", _has({"sample"})),
    "step_forward_ms": _per_call("jit_train_step", _pass("forward")),
    "step_remat_ms": _per_call("jit_train_step", _pass("remat")),
    "step_backward_ms": _per_call("jit_train_step", _pass("backward")),
    "step_optimizer_ms": _per_call("jit_train_step", _has({"optimizer"})),
    "collective_exposed_ms": collective_exposed_ms,
    "scope_unnamed_share.serve": _unnamed("serve"),
    "scope_unnamed_share.train": _unnamed("train"),
}
