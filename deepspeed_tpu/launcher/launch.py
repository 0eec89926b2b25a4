"""Per-node process spawner with elastic group restart.

TPU-native re-design of the reference per-node launcher
(deepspeed/launcher/launch.py:216) plus the DSElasticAgent restart
behavior (deepspeed/elasticity/elastic_agent.py:28): spawns the worker
processes for THIS node, wires the rendezvous env (RANK / WORLD_SIZE /
MASTER_ADDR / MASTER_PORT → consumed by comm.init_distributed →
jax.distributed.initialize), forwards signals, and tears the whole tree
down if any child dies. With ``--max_restarts N`` a failed worker group is
respawned up to N times with exponential backoff and a fresh rendezvous
port (torch-elastic's whole-group restart semantics — user scripts resume
from their latest checkpoint). If ``DSTPU_ELASTIC_CONFIG`` holds a JSON
config with an ``elasticity`` block, a group that fails repeatedly is
re-planned to the next smaller valid world size from
``compute_elastic_config`` before the retry.

A JAX SPMD job runs ONE process per host (the process drives all local TPU
chips), so the default --nproc_per_node is 1 — unlike the reference's
process-per-GPU model. >1 is for the CPU-backend test rig only, where N
single-device processes emulate N hosts on one machine: a chip belongs to
one process at a time and nothing here gives each worker its own, so off
the CPU backend >1 is refused.
"""

import argparse
import json
import os
import signal
import subprocess
import sys
import time

from ..utils.logging import logger


def parse_args(argv=None):
    p = argparse.ArgumentParser(description="deepspeed_tpu per-node launcher")
    p.add_argument("--node_rank", type=int, default=0)
    p.add_argument("--nnodes", type=int, default=1)
    p.add_argument("--nproc_per_node", type=int, default=1)
    p.add_argument("--master_addr", default="127.0.0.1")
    p.add_argument("--master_port", type=int, default=29500)
    p.add_argument("--max_restarts", type=int, default=0,
                   help="group restarts after a worker failure "
                        "(reference DSElasticAgent behavior); "
                        "single-node only — multi-node groups have no "
                        "cross-node restart coordinator yet")
    p.add_argument("--restart_backoff", type=float, default=1.0,
                   help="base seconds of exponential restart backoff")
    p.add_argument("--elastic_training", action="store_true",
                   help="opt in to shrinking the worker group on repeated "
                        "failures (DSTPU_ELASTIC_CONFIG elasticity block)")
    p.add_argument("--module", action="store_true",
                   help="run the script as 'python -m <script>'")
    p.add_argument("--no_python", action="store_true",
                   help="exec the script directly (not via the interpreter)")
    p.add_argument("training_script")
    p.add_argument("training_script_args", nargs=argparse.REMAINDER)
    return p.parse_args(argv)


def build_cmd(args):
    if args.no_python:
        cmd = [args.training_script]
    elif args.module:
        cmd = [sys.executable, "-m", args.training_script]
    else:
        cmd = [sys.executable, args.training_script]
    return cmd + list(args.training_script_args)


def _elastic_replan(nproc: int) -> int:
    """Next smaller valid world size from the DSTPU_ELASTIC_CONFIG
    elasticity block, or ``nproc`` unchanged if no config / no smaller
    size exists. (Single-node form of the reference agent's
    re-rendezvous-with-fewer-workers.)"""
    raw = os.environ.get("DSTPU_ELASTIC_CONFIG")
    if not raw:
        return nproc
    try:
        cfg = json.loads(raw) if raw.lstrip().startswith("{") else \
            json.load(open(raw))
        from ..elasticity.elasticity import compute_elastic_config
        _, valid = compute_elastic_config(cfg)[:2]
    except Exception as exc:  # noqa: BLE001 — a bad plan must not kill the
        logger.warning(f"elastic re-plan unavailable: {exc}")  # launcher
        return nproc
    smaller = [g for g in valid if g < nproc]
    if not smaller:
        logger.warning(f"elastic re-plan: no valid world size below "
                       f"{nproc} in {valid}; keeping {nproc}")
        return nproc
    return max(smaller)


def _run_group(args, attempt: int, nproc: int) -> int:
    """Spawn one worker group and babysit it; returns the group rc."""
    world_size = args.nnodes * nproc
    port = args.master_port + attempt     # fresh rendezvous per attempt
    procs = []

    def terminate(sig=signal.SIGTERM):
        for proc in procs:
            if proc.poll() is None:
                try:
                    os.killpg(os.getpgid(proc.pid), sig)
                except ProcessLookupError:
                    pass

    def handler(signum, frame):
        logger.info(f"launch: forwarding signal {signum} to workers")
        terminate(signum)
        sys.exit(128 + signum)

    signal.signal(signal.SIGINT, handler)
    signal.signal(signal.SIGTERM, handler)

    for local_rank in range(nproc):
        rank = args.node_rank * nproc + local_rank
        env = dict(os.environ)
        env.update({
            "RANK": str(rank),
            "LOCAL_RANK": str(local_rank),
            "WORLD_SIZE": str(world_size),
            "MASTER_ADDR": args.master_addr,
            "MASTER_PORT": str(port),
            "DSTPU_NUM_PROCESSES": str(world_size),
            "NODE_RANK": str(args.node_rank),
            "DSTPU_RESTART_COUNT": str(attempt),
        })
        cmd = build_cmd(args)
        logger.info(f"launch: rank {rank} (attempt {attempt}) -> "
                    f"{' '.join(cmd)}")
        procs.append(subprocess.Popen(cmd, env=env, start_new_session=True))

    # babysit: if one worker dies, kill the rest (reference launch.py:119
    # sigkill-the-tree behavior)
    exit_code = 0
    alive = set(range(len(procs)))
    while alive:
        for i in sorted(alive):
            rc = procs[i].poll()
            if rc is None:
                continue
            alive.discard(i)
            if rc != 0:
                logger.error(f"launch: worker {i} exited rc={rc}; "
                             f"terminating remaining workers")
                exit_code = rc
                terminate(signal.SIGTERM)
                deadline = time.time() + 10
                for p in procs:
                    try:
                        p.wait(timeout=max(0.1, deadline - time.time()))
                    except subprocess.TimeoutExpired:
                        terminate(signal.SIGKILL)
                alive.clear()
                break
        time.sleep(0.2)
    return exit_code


def main(argv=None):
    args = parse_args(argv)
    if args.max_restarts > 0 and args.nnodes > 1:
        # each node's launcher retries independently — without a
        # cross-node coordinator the rendezvous ports/attempts
        # desynchronize, so restarts are single-node only for now
        logger.warning("launch: --max_restarts requires a cross-node "
                       "restart coordinator and nnodes>1 has none; "
                       "disabling restarts (kill-the-tree semantics)")
        args.max_restarts = 0
    nproc = args.nproc_per_node
    on_cpu = os.environ.get("JAX_PLATFORMS", "").lower().startswith("cpu") \
        or os.environ.get("DSTPU_ACCELERATOR", "").lower() == "cpu"
    if nproc > 1 and not on_cpu:
        logger.error(
            f"launch: --nproc_per_node={nproc} would start {nproc} "
            f"processes that all claim this host's TPU chips; one process "
            f"drives all local chips (use the default 1), or set "
            f"JAX_PLATFORMS=cpu for the CPU test rig")
        return 2
    failures = 0
    for attempt in range(args.max_restarts + 1):
        rc = _run_group(args, attempt, nproc)
        if rc == 0:
            return 0
        failures += 1
        if attempt >= args.max_restarts:
            break
        # after two consecutive failures at this size, re-plan smaller
        # (an unhealthy member keeps killing the group — the reference
        # agent's shrink-on-re-rendezvous). Opt-in via --elastic_training.
        # nnodes==1 here, so nproc IS the world size compute_elastic_config
        # validates against.
        if failures >= 2 and args.elastic_training:
            new_nproc = _elastic_replan(nproc)
            if new_nproc != nproc:
                logger.warning(f"launch: elastic re-plan "
                               f"{nproc} -> {new_nproc} workers")
                nproc = new_nproc
                failures = 0
        backoff = args.restart_backoff * (2 ** attempt)
        logger.warning(f"launch: group failed rc={rc}; restarting in "
                       f"{backoff:.1f}s "
                       f"({args.max_restarts - attempt} restarts left)")
        time.sleep(backoff)
    return rc


if __name__ == "__main__":
    sys.exit(main())
