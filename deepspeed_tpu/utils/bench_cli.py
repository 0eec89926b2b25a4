"""ds_tpu_bench — collective/compute micro-benchmarks from the CLI.

Capability match for the reference ``ds_bench`` (reference bin/ds_bench →
benchmarks/communication/run_all.py): sweep message sizes through the
framework's collective wrappers and report latency + algorithmic
bandwidth, plus a matmul roofline probe. TPU translation: collectives run
as jitted lax collectives over the live mesh via shard_map (single
process drives every local device), so the tool needs no launcher — run
it directly, or under `deepspeed_tpu` for multi-host meshes.
"""

import argparse
import json
import time


def _bw_mb(nbytes, seconds, world):
    alg = nbytes / seconds / 1e9
    # ring allreduce moves 2(n-1)/n of the payload per link
    bus = alg * (2 * (world - 1) / world) if world > 1 else alg
    return round(alg, 3), round(bus, 3)


def run_collectives(sizes_mb, trials, mesh_axis="data"):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from ..parallel.topology import get_mesh_manager

    mm = get_mesh_manager()
    mesh = mm.mesh
    world = mesh.shape[mesh_axis]
    results = []
    for mb in sizes_mb:
        n = int(mb * 1e6 / 4)
        x = jnp.ones((world, n), jnp.float32)

        @jax.jit
        def allreduce(x):
            # the 1/world rescale rides inside the jitted program so the
            # timed loop dispatches exactly one executable per trial
            return jax.shard_map(
                lambda s: jax.lax.psum(s / world, mesh_axis), mesh=mesh,
                in_specs=P(mesh_axis), out_specs=P(mesh_axis))(x)

        y = allreduce(x)
        jax.block_until_ready(y)
        t0 = time.perf_counter()
        for _ in range(trials):
            y = allreduce(y)
        jax.block_until_ready(y)
        dt = (time.perf_counter() - t0) / trials
        alg, bus = _bw_mb(n * 4, dt, world)
        results.append({"op": "all_reduce", "size_mb": mb, "world": world,
                        "latency_ms": round(dt * 1e3, 3),
                        "algbw_gbps": alg, "busbw_gbps": bus})
    return results


def run_matmul(trials):
    import jax
    import jax.numpy as jnp
    from jax import lax

    m = 4096
    a = jnp.ones((m, m), jnp.bfloat16)

    @jax.jit
    def chain(a):
        def body(x, _):
            return (x @ a * 1e-3).astype(jnp.bfloat16), None
        x, _ = lax.scan(body, a, None, length=trials)
        return jnp.sum(x.astype(jnp.float32))

    float(chain(a))
    t0 = time.perf_counter()
    float(chain(a))
    dt = (time.perf_counter() - t0) / trials
    tflops = 2 * m ** 3 / dt / 1e12
    return {"op": "matmul_bf16", "m": m, "ms": round(dt * 1e3, 3),
            "tflops": round(tflops, 1)}


def main(argv=None):
    import os
    p = argparse.ArgumentParser(description="deepspeed_tpu micro-bench")
    p.add_argument("--sizes-mb", default="1,16,64",
                   help="comma list of allreduce payloads")
    p.add_argument("--trials", type=int, default=10)
    p.add_argument("--skip-collectives", action="store_true")
    p.add_argument("--skip-matmul", action="store_true")
    p.add_argument("--cpu", action="store_true",
                   help="run on an 8-device virtual CPU mesh")
    args = p.parse_args(argv)
    cpu = (args.cpu or
           os.environ.get("JAX_PLATFORMS", "").lower().startswith("cpu") or
           os.environ.get("DSTPU_ACCELERATOR", "").lower() == "cpu")
    if cpu:
        from .hermetic import force_cpu
        force_cpu(device_count=8)   # idempotent if bin/ds_tpu_bench already
        #                             ran it before the package import
    else:
        import jax
        if jax.devices()[0].platform == "cpu":
            # never publish CPU time under the name of a chip
            print(json.dumps({"error": "jax found no accelerator; use "
                                       "--cpu for the virtual mesh"}))
            return 2
    out = {"collectives": [], "compute": None}
    if not args.skip_collectives:
        sizes = [float(s) for s in args.sizes_mb.split(",") if s]
        out["collectives"] = run_collectives(sizes, args.trials)
    if not args.skip_matmul:
        out["compute"] = run_matmul(args.trials)
    print(json.dumps(out, indent=2))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
