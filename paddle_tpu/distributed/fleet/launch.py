"""fleetrun launcher.

Reference parity: python/paddle/distributed/fleet/launch.py:334 launch() /
:208 launch_collective, and launch_utils.py:457-464 — spawns one process per
device/host rank with the PADDLE_TRAINER_* env protocol.

TPU-native design: on TPU one process drives all local chips (single-controller JAX),
so `--nproc_per_node` defaults to 1; multi-HOST launches export the coordination
address consumed by jax.distributed.initialize (env.init_distributed). The same env
names are kept so reference scripts port unchanged:
  PADDLE_TRAINER_ID / PADDLE_TRAINERS_NUM / PADDLE_TRAINER_ENDPOINTS /
  PADDLE_CURRENT_ENDPOINT.

One process per chip: a chip belongs to the first process that touches it, so
several local children that each start a TPU backend would all claim every chip and
fail or hang. `--nproc_per_node > 1` is therefore for the CPU backend only
(JAX_PLATFORMS=cpu — the multi-process test harness) and is refused otherwise. This
launcher itself never initialises a jax backend (pinned by tests/test_chip_smoke.py):
the children, not the parent, own the devices.

Usage: python -m paddle_tpu.distributed.fleet.launch --ips host1,host2 train.py args…
"""
import argparse
import os
import signal
import subprocess
import sys


def _parse_args(argv=None):
    p = argparse.ArgumentParser("fleetrun")
    p.add_argument("--ips", default="127.0.0.1", help="comma-separated host list")
    p.add_argument("--nproc_per_node", type=int, default=1,
                   help="processes per host (1: single-controller JAX drives all chips)")
    p.add_argument("--start_port", type=int, default=6070)
    p.add_argument("--log_dir", default=None)
    p.add_argument("--backend", default="xla", help="accepted for compat (nccl->xla)")
    p.add_argument("--server_num", type=int, default=0, help="PS servers (ps mode)")
    p.add_argument("--worker_num", type=int, default=None)
    p.add_argument("training_script")
    p.add_argument("training_script_args", nargs=argparse.REMAINDER)
    return p.parse_args(argv)


def get_cluster_env(ips, start_port, nproc_per_node, rank):
    hosts = ips.split(",")
    endpoints = []
    for h in hosts:
        for i in range(nproc_per_node):
            endpoints.append(f"{h}:{start_port + i}")
    return {
        "PADDLE_TRAINER_ID": str(rank),
        "PADDLE_TRAINERS_NUM": str(len(endpoints)),
        "PADDLE_TRAINER_ENDPOINTS": ",".join(endpoints),
        "PADDLE_CURRENT_ENDPOINT": endpoints[rank],
        "PADDLE_LOCAL_RANK": str(rank % nproc_per_node),
    }


def check_one_process_per_chip(nproc_per_node, environ):
    """Refuse several local processes unless they are pinned off the TPU.
    Decided from JAX_PLATFORMS alone — asking jax would start a backend in
    the parent and take the chips from the children."""
    platforms = environ.get("JAX_PLATFORMS", "")
    if nproc_per_node > 1 and (not platforms or "tpu" in platforms.split(",")):
        raise SystemExit(
            f"fleetrun: --nproc_per_node {nproc_per_node} would start "
            f"{nproc_per_node} processes that each claim every local TPU "
            "chip. One process drives all local chips: use --nproc_per_node "
            "1 (and shard over jax.devices() in the script), or set "
            "JAX_PLATFORMS=cpu for a multi-process CPU run.")


def launch_collective(args):
    """launch.py:208 parity: spawn local worker processes, wire env, wait, propagate
    failures (kill the gang on first death, as the reference does)."""
    check_one_process_per_chip(args.nproc_per_node, os.environ)
    local_host_rank = 0  # index of this host in --ips (single-host default)
    n_local = args.nproc_per_node
    procs = []
    log_dir = args.log_dir
    if log_dir:
        os.makedirs(log_dir, exist_ok=True)
    for local_rank in range(n_local):
        rank = local_host_rank * n_local + local_rank
        env = dict(os.environ)
        env.update(get_cluster_env(args.ips, args.start_port, n_local, rank))
        cmd = [sys.executable, args.training_script] + args.training_script_args
        out = open(os.path.join(log_dir, f"workerlog.{local_rank}"), "w") if log_dir else None
        procs.append((subprocess.Popen(cmd, env=env, stdout=out, stderr=subprocess.STDOUT if out else None), out))

    exit_code = 0
    try:
        alive = True
        while alive:
            alive = False
            for p, _ in procs:
                ret = p.poll()
                if ret is None:
                    alive = True
                elif ret != 0:
                    exit_code = ret
                    for q, _ in procs:
                        if q.poll() is None:
                            q.send_signal(signal.SIGTERM)
                    alive = False
                    break
            if alive:
                import time

                time.sleep(0.5)
    finally:
        for p, out in procs:
            if p.poll() is None:
                p.wait()
            if out:
                out.close()
    return exit_code


def launch_ps(args):
    """launch.py:260 parity (launch_ps): spawn --server_num PS servers and
    --worker_num trainers on this host with the PADDLE_PSERVERS_IP_PORT_LIST /
    TRAINING_ROLE env protocol (fleet/launch_utils.py)."""
    import socket

    def free_port():
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        return port

    n_servers = args.server_num
    n_workers = args.worker_num if (args.worker_num or 0) > 0 else args.nproc_per_node
    check_one_process_per_chip(n_servers + n_workers, os.environ)
    server_eps = ",".join(f"127.0.0.1:{free_port()}" for _ in range(n_servers))
    log_dir = args.log_dir
    if log_dir:
        os.makedirs(log_dir, exist_ok=True)
    procs = []

    def spawn(role, idx, extra_env, tag):
        env = dict(os.environ)
        env.update({
            "PADDLE_PSERVERS_IP_PORT_LIST": server_eps,
            "PADDLE_TRAINERS_NUM": str(n_workers),
            "TRAINING_ROLE": role,
        })
        env.update(extra_env)
        cmd = [sys.executable, args.training_script] + args.training_script_args
        out = open(os.path.join(log_dir, f"{tag}.{idx}"), "w") if log_dir else None
        procs.append((subprocess.Popen(
            cmd, env=env, stdout=out, stderr=subprocess.STDOUT if out else None), out))

    for i in range(n_servers):
        ip, port = server_eps.split(",")[i].rsplit(":", 1)
        spawn("PSERVER", i, {"PADDLE_PSERVER_ID": str(i), "POD_IP": ip,
                             "PADDLE_PORT": port}, "serverlog")
    for i in range(n_workers):
        spawn("TRAINER", i, {"PADDLE_TRAINER_ID": str(i)}, "workerlog")

    exit_code = 0
    try:
        # workers are the tail of `procs`; servers exit when a worker stops them
        for p, _ in procs[n_servers:]:
            ret = p.wait()
            if ret != 0:
                exit_code = ret
    finally:
        for p, out in procs:
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
                p.wait()
            if out:
                out.close()
    return exit_code


def launch():
    args = _parse_args()
    if args.server_num > 0:
        sys.exit(launch_ps(args))
    sys.exit(launch_collective(args))


if __name__ == "__main__":
    launch()
