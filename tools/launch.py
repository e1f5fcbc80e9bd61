#!/usr/bin/env python
"""Launch a distributed training job as N local worker processes.

Reference: tools/launch.py (dmlc tracker: spawns scheduler/servers/workers
with DMLC_ROLE env). The TPU build is allreduce-based — no separate server
role — so the launcher spawns ``-n`` identical workers wired together via
jax.distributed (MXTPU_COORDINATOR / MXTPU_NUM_WORKERS / MXTPU_WORKER_ID,
consumed by mxnet_tpu.kvstore._ensure_distributed). ``--launcher local``
is the reference's fake-cluster test mode (tests/nightly/dist_sync_kvstore
pattern: N processes on localhost).
"""
import argparse
import os
import socket
import subprocess
import sys


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def clean_env(base=None):
    """Worker environment without the variables that bind a process to
    the local TPU (``TPU_*``, ``PALLAS_*``): TPU cluster auto-detection
    would override the explicit jax.distributed arguments the launcher
    passes."""
    env = dict(base if base is not None else os.environ)
    for k in list(env):
        if k.startswith(("TPU_", "PALLAS_")):
            env.pop(k)
    return env


def _drain(stream):
    for _ in iter(stream.readline, b""):
        pass


def launch_servers(num_servers, platform="cpu"):
    """Spawn parameter-server processes for dist_async (reference: the
    tracker's server role, DMLC_ROLE=server). Returns (procs, addr_csv) —
    pass the address string to workers as MXTPU_PS_ADDR."""
    procs, addrs = [], []
    try:
        for _ in range(num_servers):
            env = clean_env()
            env["JAX_PLATFORMS"] = platform
            env["MXTPU_PS_BIND"] = "127.0.0.1:0"
            p = subprocess.Popen(
                [sys.executable, "-m", "mxnet_tpu.kvstore_server"], env=env,
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
            procs.append(p)
            # the server prints its bound address (port 0 = ephemeral);
            # tolerate a few interpreter warning lines before it
            consumed = []
            for _ in range(20):
                raw = p.stdout.readline()
                if not raw:  # EOF: the server process died
                    raise RuntimeError(
                        "server exited before printing its address; "
                        "output:\n%s" % "".join(consumed))
                line = raw.decode()
                if line.strip().startswith("MXTPU_PS_ADDR="):
                    line = line.strip()
                    break
                consumed.append(line)
            else:
                raise RuntimeError(
                    "server failed to start: no address line printed; "
                    "output:\n%s" % "".join(consumed))
            addrs.append(line.split("=", 1)[1])
            # keep draining the pipe: a chatty server would otherwise
            # block on a full pipe buffer and stop serving
            import threading

            threading.Thread(target=_drain, args=(p.stdout,),
                             daemon=True).start()
    except Exception:
        for p in procs:
            p.kill()
        raise
    return procs, ",".join(addrs)


class WorkerProcs(list):
    """Worker Popen list; ``.ps_procs`` holds any parameter-server
    processes launched alongside (empty for allreduce jobs)."""

    def __init__(self, procs, ps_procs=()):
        super().__init__(procs)
        self.ps_procs = list(ps_procs)


def launch_local(n, command, env_extra=None, platform="cpu",
                 num_servers=0):
    """Spawn n local worker processes (plus optional PS servers for
    dist_async); returns a WorkerProcs list.

    Workers are CPU processes — a fake cluster. A chip belongs to one
    process at a time and the launcher has no per-worker chip binding, so
    several TPU workers on one host would fight over (or hang on) the
    same chips: ``platform="tpu"`` with ``n > 1`` is refused. One process
    drives all local chips instead (``make_mesh`` over
    ``jax.devices()``)."""
    if n > 1 and platform != "cpu":
        raise ValueError(
            "launch_local starts %d workers on platform %r, but a chip "
            "belongs to one process at a time and the launcher binds no "
            "chip to a worker: use --platform cpu (the fake cluster), or "
            "one process over all local chips" % (n, platform))
    port = _free_port()
    extra = dict(env_extra or {})
    ps_procs = []
    if num_servers:
        ps_procs, addr_csv = launch_servers(num_servers, platform)
        extra["MXTPU_PS_ADDR"] = addr_csv
    procs = []
    try:
        for i in range(n):
            env = clean_env()
            env.update(extra)
            env["JAX_PLATFORMS"] = platform
            env["MXTPU_COORDINATOR"] = "127.0.0.1:%d" % port
            env["MXTPU_NUM_WORKERS"] = str(n)
            env["MXTPU_WORKER_ID"] = str(i)
            procs.append(subprocess.Popen(
                command, env=env,
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT))
    except Exception:
        for p in procs + ps_procs:
            p.kill()
        raise
    return WorkerProcs(procs, ps_procs)


def main():
    parser = argparse.ArgumentParser(description="Launch a distributed job")
    parser.add_argument("-n", "--num-workers", type=int, required=True)
    parser.add_argument("-s", "--num-servers", type=int, default=0,
                        help="parameter servers for dist_async (the "
                             "reference tracker's server role); 0 for "
                             "allreduce-based dist_sync")
    parser.add_argument("--launcher", choices=["local"], default="local",
                        help="only 'local' (fake cluster); multi-host "
                             "launches use the cluster scheduler's own "
                             "process manager + jax.distributed auto-init")
    parser.add_argument("--platform", default="cpu")
    parser.add_argument("command", nargs=argparse.REMAINDER)
    args = parser.parse_args()
    try:
        procs = launch_local(args.num_workers, args.command,
                             platform=args.platform,
                             num_servers=args.num_servers)
    except ValueError as err:
        parser.error(str(err))
    rc = 0
    for i, p in enumerate(procs):
        out, _ = p.communicate()
        sys.stdout.write("---- worker %d (rc=%d) ----\n%s\n"
                         % (i, p.returncode, out.decode()))
        rc = rc or p.returncode
    for p in procs.ps_procs:
        p.kill()
    sys.exit(rc)


if __name__ == "__main__":
    main()
