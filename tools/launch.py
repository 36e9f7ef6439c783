#!/usr/bin/env python
"""Cluster launcher (reference tools/launch.py over dmlc-core trackers).

TPU-native: there are no server/scheduler processes to launch — only N
worker processes that join a jax.distributed coordination service. The
'local' launcher (the one the reference's CI uses for distributed tests,
tools/launch.py:49-52) spawns N local processes with
MXNET_TPU_COORDINATOR / MXNET_TPU_NUM_WORKERS / MXNET_TPU_WORKER_ID env
vars; KVStore('dist_sync') picks them up (parallel/kvstore_tpu.py
maybe_init_distributed). For real multi-host TPU pods, the platform's
own process-per-host launcher plays this role and jax.distributed
auto-detects — pass --launcher none to just exec the command.

The 'local' launcher is a CPU-only test tier: a chip belongs to one
process at a time, so N workers on one host run on the CPU backend
(JAX_PLATFORMS=cpu) and exercise the collectives, not the chips. One
process drives all the chips of a host (Module(context=[mx.tpu(i) ...]))
— that, not N local workers, is how one host trains.

Usage:
  python tools/launch.py -n 2 python tests/nightly/dist_sync_kvstore.py
"""
from __future__ import annotations

import argparse
import os
import shlex
import socket
import subprocess
import sys


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _worker_env_args(coordinator, n, wid, extra):
    pairs = {
        "MXNET_TPU_COORDINATOR": coordinator,
        "MXNET_TPU_NUM_WORKERS": str(n),
        "MXNET_TPU_WORKER_ID": str(wid),
    }
    for kv in extra:
        k, _, v = kv.partition("=")
        pairs[k] = v
    return pairs


def _launch_local(args):
    port = _free_port()
    procs = []
    for wid in range(args.num_workers):
        env = dict(os.environ)
        env.update(_worker_env_args(
            f"127.0.0.1:{port}", args.num_workers, wid, args.env))
        # CPU-only tier (module docstring): N processes cannot share
        # this host's chips
        env.setdefault("JAX_PLATFORMS", "cpu")
        procs.append(subprocess.Popen(args.command, env=env))

    rc = 0
    for p in procs:
        p.wait()
        rc = rc or p.returncode
    return rc


def _read_hostfile(path):
    hosts = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line and not line.startswith("#"):
                hosts.append(line.split()[0])
    if not hosts:
        raise SystemExit(f"hostfile {path} lists no hosts")
    return hosts


def _launch_ssh(args):
    """One worker per hostfile line (reference tools/launch.py ssh
    tracker): the coordinator runs on the first host's port; env is
    threaded through the remote shell."""
    import random as _random

    hosts = _read_hostfile(args.hostfile)
    if len(hosts) < args.num_workers:
        raise SystemExit(
            f"hostfile has {len(hosts)} hosts < -n {args.num_workers}")
    # the coordinator binds on hosts[0], NOT this machine — probing a
    # local free port would be meaningless there; pick from the
    # ephemeral range (override with --port when it collides)
    port = args.port or _random.randint(20000, 59999)
    coord = f"{hosts[0]}:{port}"
    procs = []
    for wid in range(args.num_workers):
        pairs = _worker_env_args(coord, args.num_workers, wid, args.env)
        exports = " ".join(
            f"{k}={shlex.quote(v)}" for k, v in pairs.items())
        remote = f"cd {os.getcwd()} && env {exports} " + \
            shlex.join(args.command)
        procs.append(subprocess.Popen(
            ["ssh", "-o", "StrictHostKeyChecking=no", hosts[wid],
             remote]))
    rc = 0
    for p in procs:
        p.wait()
        rc = rc or p.returncode
    return rc


def _launch_mpi(args):
    """Delegate process placement to mpirun; each rank derives its
    worker id from OMPI_COMM_WORLD_RANK / PMI_RANK (reference mpirun
    tracker role). The coordinator must be reachable from all ranks:
    this host's address."""
    port = _free_port()
    coord = f"{socket.getfqdn()}:{port}"
    env = dict(os.environ)
    env.update(_worker_env_args(coord, args.num_workers, 0, args.env))
    del env["MXNET_TPU_WORKER_ID"]  # per-rank, from MPI env at runtime
    env["MXNET_TPU_WORKER_ID_FROM_MPI"] = "1"
    cmd = ["mpirun", "-n", str(args.num_workers)]
    export = ["MXNET_TPU_COORDINATOR", "MXNET_TPU_NUM_WORKERS",
              "MXNET_TPU_WORKER_ID_FROM_MPI"]
    export += [kv.partition("=")[0] for kv in args.env]
    for k in export:
        cmd += ["-x", k]
    return subprocess.call(cmd + args.command, env=env)


def _rendezvous_preamble(rdv_path, port, num_workers, wid_expr, extra):
    """Shell fragment implementing shared-filesystem rendezvous: worker 0
    publishes its host; the rest poll for it. Batch schedulers (SGE,
    YARN) place tasks on hosts unknown at submit time, so the
    coordinator address cannot be baked in the way the ssh launcher
    does — the cluster's shared filesystem is the discovery channel
    (the role the reference's dmlc tracker played over TCP)."""
    exports = "".join(
        f"export {kv.partition('=')[0]}="
        f"{shlex.quote(kv.partition('=')[2])}\n"
        for kv in extra)
    return f"""WID={wid_expr}
if [ "$WID" -eq 0 ]; then hostname -f > {rdv_path}.tmp && \
mv {rdv_path}.tmp {rdv_path}; fi
tries=0
while [ ! -s {rdv_path} ]; do
  sleep 1
  tries=$((tries+1))
  if [ "$tries" -gt 300 ]; then echo "rendezvous timeout" >&2; exit 1; fi
done
export MXNET_TPU_COORDINATOR="$(cat {rdv_path}):{port}"
export MXNET_TPU_NUM_WORKERS={num_workers}
export MXNET_TPU_WORKER_ID=$WID
{exports}"""


def _sge_script(args, port, rdv_path):
    """qsub array-job script: task i is worker i-1 (reference sge
    tracker role, tools/launch.py:49-52). Requires -cwd on a shared
    filesystem (the SGE norm)."""
    body = _rendezvous_preamble(
        rdv_path, port, args.num_workers, "$((SGE_TASK_ID-1))",
        args.env)
    return f"""#!/bin/bash
#$ -S /bin/bash
#$ -cwd
#$ -V
#$ -t 1-{args.num_workers}
#$ -N mxtpu-launch
{body}exec {shlex.join(args.command)}
"""


def _launch_sge(args):
    import random as _random
    import tempfile

    port = args.port or _random.randint(20000, 59999)
    rdv = os.path.abspath(f".mxtpu_rdv_{os.getpid()}")
    if os.path.exists(rdv):
        os.remove(rdv)
    script = _sge_script(args, port, rdv)
    with tempfile.NamedTemporaryFile(
            "w", suffix=".sh", dir=".", delete=False) as tf:
        tf.write(script)
        path = tf.name
    try:
        # -sync y blocks until the array job finishes, so launch.py
        # keeps the reference's wait-for-completion contract
        return subprocess.call(["qsub", "-sync", "y", path])
    finally:
        import glob

        for f in [path] + glob.glob(rdv + "*"):
            if os.path.exists(f):
                os.remove(f)


def _yarn_command(args, port, rdv_path):
    """YARN distributed-shell invocation (reference yarn tracker role).
    Containers rendezvous through the same shared-filesystem protocol;
    worker ids are claimed atomically with mkdir (container ordinals
    are not dense across YARN attempts)."""
    claim = f"""i=0
while ! mkdir {rdv_path}.claim.$i 2>/dev/null; do
  i=$((i+1))
  if [ "$i" -ge {args.num_workers} ]; then echo claim-fail >&2; exit 1; fi
done
"""
    body = claim + _rendezvous_preamble(
        rdv_path, port, args.num_workers, "$i", args.env)
    shell = body + "exec " + shlex.join(args.command)
    jar = os.environ.get("YARN_DSHELL_JAR") or os.path.join(
        os.environ.get("HADOOP_HOME", "/usr/lib/hadoop"),
        "share/hadoop/yarn",
        "hadoop-yarn-applications-distributedshell.jar")
    # POSIX quoting: the container shell must NOT expand $i/$((..))/
    # $(cat ..) before the inner bash runs (list2cmdline would
    # double-quote, losing exactly that)
    return ["yarn", "jar", jar,
            "-jar", jar,
            "-num_containers", str(args.num_workers),
            "-shell_command", "bash -c " + shlex.quote(shell)]


def _launch_yarn(args):
    import glob
    import random as _random
    import shutil

    port = args.port or _random.randint(20000, 59999)
    rdv = os.path.abspath(f".mxtpu_rdv_{os.getpid()}")
    if os.path.exists(rdv):
        os.remove(rdv)
    try:
        return subprocess.call(_yarn_command(args, port, rdv))
    finally:
        for f in glob.glob(rdv + "*"):
            (shutil.rmtree if os.path.isdir(f) else os.remove)(f)


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("-n", "--num-workers", type=int, required=True)
    ap.add_argument("--launcher", default="local",
                    choices=["local", "ssh", "mpi", "sge", "yarn",
                             "none"])
    ap.add_argument("-H", "--hostfile", default=None,
                    help="hostfile for --launcher ssh")
    ap.add_argument("--port", type=int, default=None,
                    help="coordinator port (ssh/sge/yarn launchers; "
                         "default: random ephemeral)")
    ap.add_argument("--env", action="append", default=[],
                    help="extra KEY=VALUE for workers")
    ap.add_argument("command", nargs=argparse.REMAINDER)
    args = ap.parse_args()
    if not args.command:
        ap.error("no command given")

    if args.launcher == "none":
        os.execvp(args.command[0], args.command)
    if args.launcher == "ssh":
        if not args.hostfile:
            ap.error("--launcher ssh needs --hostfile")
        sys.exit(_launch_ssh(args))
    if args.launcher == "mpi":
        sys.exit(_launch_mpi(args))
    if args.launcher == "sge":
        sys.exit(_launch_sge(args))
    if args.launcher == "yarn":
        sys.exit(_launch_yarn(args))
    sys.exit(_launch_local(args))


if __name__ == "__main__":
    main()
