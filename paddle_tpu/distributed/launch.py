"""Distributed launcher CLI: ``python -m paddle_tpu.distributed.launch``.

TPU-native analogue of the reference launcher (reference:
python/paddle/distributed/fleet/launch.py:334 launch(),
launch_utils.py:435-464 start_local_trainers — subprocess per rank with
the PADDLE_TRAINER_ID / PADDLE_CURRENT_ENDPOINT / PADDLE_TRAINERS_NUM /
PADDLE_TRAINER_ENDPOINTS env protocol; watch_local_trainers +
terminate_local_procs:295 tear the job down on any failure).

Differences by design:
  - one process per HOST (jax owns all local chips; the reference's
    one-process-per-GPU with FLAGS_selected_gpus has no TPU meaning).
    A chip belongs to one process at a time, so --nproc_per_node > 1
    is for CPU-simulation tests only and is refused without
    --backend cpu: children that all inherit the accelerator would
    fail or hang on it.
  - rendezvous is the jax coordination service (env.py
    init_parallel_env), not a raw-TCP ncclUniqueId exchange.

Usage:
    python -m paddle_tpu.distributed.launch --backend cpu \
        --nproc_per_node 2 train.py
    python -m paddle_tpu.distributed.launch --ips host1,host2 train.py
"""
from __future__ import annotations

import argparse
import os
import signal
import socket
import subprocess
import sys
import time
from typing import List


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("", 0))
        return s.getsockname()[1]


def parse_args(argv=None):
    p = argparse.ArgumentParser(
        prog="paddle_tpu.distributed.launch",
        description="spawn one training process per rank with the "
                    "PADDLE_* env protocol")
    p.add_argument("--nproc_per_node", type=int, default=1,
                   help="ranks to spawn on this node")
    p.add_argument("--ips", type=str, default="127.0.0.1",
                   help="comma-separated node ips (multi-host)")
    p.add_argument("--node_rank", type=int,
                   default=int(os.environ.get("PADDLE_NODE_RANK", "0")))
    p.add_argument("--started_port", type=int, default=0,
                   help="base port for rank endpoints (0 = pick free)")
    p.add_argument("--log_dir", type=str, default=None,
                   help="write per-rank logs to <log_dir>/workerlog.<rank>")
    p.add_argument("--backend", type=str, default=None,
                   help="override JAX_PLATFORMS in children (e.g. cpu)")
    p.add_argument("--host_devices", type=int, default=0,
                   help="virtual CPU devices per rank (testing; sets "
                        "xla_force_host_platform_device_count)")
    p.add_argument("training_script", type=str)
    p.add_argument("training_script_args", nargs=argparse.REMAINDER)
    return p.parse_args(argv)


def get_cluster_endpoints(ips: List[str], nproc: int, base_port: int
                          ) -> List[str]:
    """reference: launch.py get_cluster_from_args:172."""
    eps = []
    for ip in ips:
        for i in range(nproc):
            eps.append(f"{ip}:{base_port + i}")
    return eps


def start_local_trainers(args, endpoints: List[str]) -> List[subprocess.Popen]:
    """reference: launch_utils.py start_local_trainers:435."""
    procs = []
    nproc = args.nproc_per_node
    n_total = len(endpoints)
    if args.log_dir:
        os.makedirs(args.log_dir, exist_ok=True)
    for local_rank in range(nproc):
        rank = args.node_rank * nproc + local_rank
        env = dict(os.environ)
        env.update({
            "PADDLE_TRAINER_ID": str(rank),
            "PADDLE_CURRENT_ENDPOINT": endpoints[rank],
            "PADDLE_TRAINERS_NUM": str(n_total),
            "PADDLE_TRAINER_ENDPOINTS": ",".join(endpoints),
            "PADDLE_RANK_IN_NODE": str(local_rank),
            "PADDLE_COORDINATOR": endpoints[0],
        })
        if args.backend:
            env["JAX_PLATFORMS"] = args.backend
        if args.host_devices:
            flags = env.get("XLA_FLAGS", "")
            env["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{args.host_devices}").strip()
        cmd = [sys.executable, args.training_script] + \
            args.training_script_args
        out = None
        if args.log_dir:
            out = open(os.path.join(args.log_dir,
                                    f"workerlog.{rank}"), "w")
        procs.append(subprocess.Popen(
            cmd, env=env, stdout=out, stderr=subprocess.STDOUT
            if out else None))
    return procs


def watch_local_trainers(procs: List[subprocess.Popen]) -> int:
    """Poll children; on any failure terminate the rest (reference:
    launch_utils.py watch_local_trainers + terminate_local_procs:295)."""
    try:
        while True:
            alive = False
            for p in procs:
                rc = p.poll()
                if rc is None:
                    alive = True
                elif rc != 0:
                    terminate_local_procs(procs)
                    return rc
            if not alive:
                return 0
            time.sleep(0.5)
    except KeyboardInterrupt:
        terminate_local_procs(procs)
        return 130


def terminate_local_procs(procs: List[subprocess.Popen]) -> None:
    for p in procs:
        if p.poll() is None:
            p.send_signal(signal.SIGTERM)
    deadline = time.time() + 10
    for p in procs:
        while p.poll() is None and time.time() < deadline:
            time.sleep(0.2)
        if p.poll() is None:
            p.kill()


def launch(argv=None) -> int:
    args = parse_args(argv)
    if args.nproc_per_node > 1 and args.backend != "cpu":
        raise SystemExit(
            f"--nproc_per_node {args.nproc_per_node} needs --backend cpu: "
            "an accelerator belongs to one process per host, and "
            f"{args.nproc_per_node} children would all inherit it")
    if args.training_script_args[:1] == ["--"]:
        args.training_script_args = args.training_script_args[1:]
    ips = [ip.strip() for ip in args.ips.split(",") if ip.strip()]
    base = args.started_port or _free_port()
    endpoints = get_cluster_endpoints(ips, args.nproc_per_node, base)
    procs = start_local_trainers(args, endpoints)
    return watch_local_trainers(procs)


if __name__ == "__main__":
    sys.exit(launch())
