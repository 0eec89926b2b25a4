"""The benchmark's one command.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Looks up ``workloads/<cell>.json``, then the configuration, traffic file,
generator, job runner and per-layer metric readers those name; it holds no
cell's name and no model's. One process, no children. Exits non-zero and
prints no result line unless JAX reports a TPU with at least the cell's
``chips`` devices. The last line of stdout is the one JSON result object;
everything else (each number compared beside its limit, medians, counts) is
on earlier lines.

Harness-only flags, never used by the driver:
  --rehearse   tiny sizes from the cell's ``rehearse`` block on whatever
               platform JAX was given; the result names that platform
  --rate R     override the traffic file's arrival rate (the knee sweep);
               not the cell's traffic, so reported as not correct
  --control    put the lower-precision control in the program's place in
               the output check; prints its readings, reports not correct
"""

import time
T_START = time.perf_counter()

import argparse
import collections
import glob
import importlib
import json
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from chipbench.model import load_json, merge            # noqa: E402

TRACE_DIR = os.path.join(HERE, ".trace")


def log(msg):
    print(f"[chipbench] {msg}", flush=True)


class Ctx:
    """What a job runner is handed: the cell, its configuration and traffic
    as loaded, the flags, and a place for its own state."""

    def __init__(self, **kw):
        self.__dict__.update(kw)
        self.state = {}

    def span(self, name):
        import jax
        return jax.profiler.TraceAnnotation("chipbench/" + name)


def readers():
    """{metric name: reader} from every module of ``layer_metrics/``."""
    out = {}
    for path in sorted(glob.glob(os.path.join(HERE, "layer_metrics", "*.py"))):
        name = os.path.basename(path)[:-3]
        if name.startswith("_"):
            continue
        mod = importlib.import_module(f"chipbench.layer_metrics.{name}")
        for metric, fn in mod.METRICS.items():
            if metric in out:
                raise SystemExit(f"two readers for per-layer metric {metric}")
            out[metric] = fn
    return out


def applies(metric, cell_name):
    return "workloads" not in metric or cell_name in metric["workloads"]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--rate", type=float, default=None)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args()

    manifest = load_json(os.pardir, "BENCHMARK.json")
    cell = load_json("workloads", args.workload + ".json")
    config = load_json("configs", cell["config"] + ".json")
    traffic = load_json("traffic", cell["traffic"] + ".json")
    if args.rehearse:
        reh = cell.get("rehearse", {})
        config = merge(config, reh.get("config"))
        traffic = merge(traffic, reh.get("traffic"))
        cell = merge(cell, reh.get("cell"))
    if args.trace and "trace_seconds" in cell:      # a shorter traced window
        args.seconds = min(args.seconds, cell["trace_seconds"])

    import jax
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    cache_dir = None
    if not args.rehearse:       # a rehearsal leaves nothing in the checkout
        cache_dir = enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devs = jax.devices()
    d0 = devs[0]
    log(f"platform={d0.platform} kind={d0.device_kind!r} count={len(devs)} "
        f"cell={args.workload} seed={args.seed} seconds={args.seconds} "
        f"trace={args.trace} compile_cache={cache_dir}")
    if not args.rehearse and d0.platform != "tpu":
        raise SystemExit(f"chipbench: JAX found no accelerator (platform "
                         f"{d0.platform!r}); the benchmark runs on a TPU only")
    if len(devs) < cell["chips"]:
        raise SystemExit(f"chipbench: the cell needs {cell['chips']} chips, "
                         f"JAX sees {len(devs)}")
    devices = devs[:cell["chips"]]
    peaks = load_json("peaks.json")
    if d0.device_kind not in peaks and not args.rehearse:
        raise SystemExit(f"chipbench: no published peak for device kind "
                         f"{d0.device_kind!r} in peaks.json")
    peak = peaks.get(d0.device_kind) or next(iter(peaks.values()))

    events = collections.Counter()

    def on_event(event, **_):
        events[event] += 1

    def on_duration(event, _secs, **_kw):
        events[event] += 1
    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    # a program lowered is a program compiled or fetched from the cache:
    # either way it is work no measured window may hold
    lowered = lambda: events["/jax/core/compile/jaxpr_to_mlir_module_duration"]

    generator = importlib.import_module(
        f"chipbench.generators.{traffic['generator']}")
    job = importlib.import_module(f"chipbench.jobs.{cell['job']}")
    ctx = Ctx(args=args, cell=cell, config=config, traffic=traffic,
              generator=generator, devices=devices, peak=peak, log=log)
    t_import = time.perf_counter() - T_START
    job.setup(ctx)
    t_built = time.perf_counter() - T_START
    job.warm(ctx)
    cache_misses = events["/jax/compilation_cache/cache_misses"]
    setup_s = time.perf_counter() - T_START
    log(f"setup_s={setup_s:.3f} (imports and device {t_import:.1f}, engine "
        f"and inputs {t_built - t_import:.1f}, warm-up "
        f"{setup_s - t_built:.1f}) cache_hits="
        f"{events['/jax/compilation_cache/cache_hits']} "
        f"cache_misses={cache_misses}")

    if args.trace:
        # a rehearsal leaves nothing in the checkout, and two at once (the
        # tests' workers) do not share a directory
        trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") \
            if args.rehearse else TRACE_DIR
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    before = lowered()
    opened = [ctx.span("window")]
    opened[0].__enter__()

    spent = {}      # seconds of a traced run's parts after set-up, by part

    def end_window():
        """Closes the traced window, once. A job whose run goes on after its
        timed seconds (a drain) calls it there; it is called again when the
        job returns."""
        if opened:
            opened.pop().__exit__(None, None, None)
            if args.trace:
                t = time.perf_counter()
                jax.profiler.stop_trace()
                spent["stop_trace"] = time.perf_counter() - t
    ctx.end_window = end_window
    t = time.perf_counter()
    record = job.measure(ctx, args.seconds)
    end_window()
    spent["measure"] = time.perf_counter() - t - spent.get("stop_trace", 0.0)
    compiles_in_window = lowered() - before
    trace = None
    if args.trace:
        from chipbench import trace as trace_mod
        t = time.perf_counter()
        trace = trace_mod.load(trace_dir, allow_host_ops=args.rehearse)
        spent["load"] = time.perf_counter() - t
        shutil.rmtree(trace_dir, ignore_errors=True)
    record.update(setup_s=setup_s, compile_misses=cache_misses,
                  compiles_in_window=compiles_in_window)

    t = time.perf_counter()
    checks = job.check(ctx, record)
    spent["check"] = time.perf_counter() - t
    checks.append(("compiles_in_window", compiles_in_window, 0))
    correct = True
    for name, value, limit in checks:
        ok = value <= limit            # a NaN compares false: not correct
        correct &= bool(ok)
        log(f"check {name}: {value!r} limit {limit!r} "
            f"{'ok' if ok else 'NOT CORRECT'}")
    if args.control:
        log("control run: the lower-precision control stood in the "
            "program's place; reported as not correct whatever it read")
        correct = False
    if args.rate is not None:
        log("rate override: not the cell's traffic; reported as not correct")
        correct = False

    cell_name = args.workload
    metrics = {}
    t = time.perf_counter()
    if args.trace:
        found = readers()
        for m in manifest["per_layer"]:
            if not applies(m, cell_name) or m["name"] not in found:
                continue
            value = found[m["name"]](ctx, record, trace)
            if value is None:
                continue
            if m["unit"] == "%" and value > 100.0 and (
                    "roofline" in m["name"] or "mfu" in m["name"]
                    or "share" in m["name"]):
                raise SystemExit(f"chipbench: {m['name']} reads {value}% — "
                                 f"over 100% is a fault of the count")
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in manifest["end_to_end"]:
            if applies(m, cell_name) and m["name"] in record:
                metrics[m["name"]] = {"value": record[m["name"]],
                                      "unit": m["unit"]}
    for name, m in metrics.items():
        log(f"metric {name} = {m['value']!r} {m['unit']}")

    peak_bytes = max(((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for d in devices), default=0)
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devs), "memory_peak_bytes": peak_bytes}
    result = {"correct": correct, "attempted": record["attempted"],
              "failed": record["failed"], "metrics": metrics,
              "device": device}
    if trace is not None:
        device["busy_s"] = trace.busy_s()
        device["window_s"] = trace.window_s
        ops = trace.devices[0].op_self_seconds(trace.lo, trace.hi)
        result["breakdown"] = {
            "device_ops": [[k, v] for k, v in sorted(
                ops.items(), key=lambda kv: -kv[1])[:10]],
            "idle_gaps": [[k, v] for k, v in trace.gaps(0, top=10)]}
        log("idle seconds by host span: " + json.dumps(trace.idle_by_span(0)))
        log("modules: " + json.dumps(
            trace.devices[0].module_seconds(trace.lo, trace.hi)))
        spent["readers"] = time.perf_counter() - t
        held = collections.Counter(name for _, _, name in trace.spans)
        log("traced run: " + " ".join(
            f"{k}={spent[k]:.1f}s" for k in
            ("measure", "stop_trace", "load", "check", "readers")) +
            f"; the trace holds {sum(len(d.ops) for d in trace.devices)} "
            f"device events and the spans {json.dumps(held)}; trace_ticks "
            f"{cell.get('trace_ticks')} reached: "
            f"{record.get('trace_ticks_reached')}")
    job.close(ctx)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
