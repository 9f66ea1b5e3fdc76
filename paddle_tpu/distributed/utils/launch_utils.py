"""Launch topology dataclasses + process helpers (reference:
python/paddle/distributed/utils/launch_utils.py — Hdfs :102,
Cluster :131, JobServer :197, Trainer :211, Pod :242, get_cluster :305,
terminate_local_procs :332, add_arguments :368, find_free_ports :386,
TrainerProc :457).

These model the multi-host job layout that paddle_tpu.distributed.launch
drives; "gpus" become TPU-chip ordinals, everything else carries over.
"""
from __future__ import annotations

import os
import signal
import socket
import time

__all__ = ["Hdfs", "Cluster", "JobServer", "Trainer", "Pod", "TrainerProc",
           "get_cluster", "get_cluster_from_args", "terminate_local_procs",
           "get_host_name_ip", "add_arguments", "find_free_ports",
           "get_logger"]


class Hdfs:
    def __init__(self):
        self.hdfs_ugi = None
        self.hdfs_name = None
        self.hdfs_path = None

    def is_valid(self):
        return bool(self.hdfs_ugi and self.hdfs_name and self.hdfs_path)

    def __eq__(self, other):
        return (self.hdfs_ugi == other.hdfs_ugi
                and self.hdfs_name == other.hdfs_name
                and self.hdfs_path == other.hdfs_path)

    def __ne__(self, other):
        return not self == other

    def __str__(self):
        return f"hdfs_ugi:{self.hdfs_ugi} hdfs_name:{self.hdfs_name} " \
               f"hdfs_path:{self.hdfs_path}"


class JobServer:
    def __init__(self):
        self.endpoint = None

    def __str__(self):
        return f"{self.endpoint}"

    def __eq__(self, other):
        return self.endpoint == other.endpoint

    def __ne__(self, other):
        return not self == other


class Trainer:
    def __init__(self):
        self.gpus = []      # chip ordinals on this pod
        self.endpoint = None
        self.rank = None

    def __str__(self):
        return f"gpu:{self.gpus} endpoint:{self.endpoint} rank:{self.rank}"

    def __eq__(self, other):
        return (self.gpus == other.gpus and self.endpoint == other.endpoint
                and self.rank == other.rank)

    def __ne__(self, other):
        return not self == other

    def rank_str(self):
        return str(self.rank)


class Pod:
    def __init__(self):
        self.rank = None
        self.id = None
        self.addr = None
        self.port = None
        self.trainers = []
        self.gpus = []

    def __str__(self):
        return (f"rank:{self.rank} id:{self.id} addr:{self.addr} "
                f"port:{self.port} visible_gpu:{self.gpus} "
                f"trainers:{[str(t) for t in self.trainers]}")

    def __eq__(self, other):
        if (self.rank != other.rank or self.id != other.id
                or self.addr != other.addr or self.port != other.port
                or len(self.trainers) != len(other.trainers)):
            return False
        return all(a == b for a, b in zip(self.trainers, other.trainers))

    def __ne__(self, other):
        return not self == other

    def rank_str(self):
        return str(self.rank)

    def get_visible_gpus(self):
        return ",".join(str(g) for g in self.gpus)


class Cluster:
    def __init__(self, hdfs=None):
        self.job_server = None
        self.pods = []
        self.hdfs = hdfs
        self.job_stage_flag = None

    def __str__(self):
        return (f"job_server:{self.job_server} "
                f"pods:{[str(p) for p in self.pods]} "
                f"job_stage_flag:{self.job_stage_flag} hdfs:{self.hdfs}")

    def __eq__(self, other):
        if len(self.pods) != len(other.pods):
            return False
        return all(a == b for a, b in zip(self.pods, other.pods))

    def __ne__(self, other):
        return not self == other

    def update_pods(self, cluster):
        self.pods = list(cluster.pods)

    def trainers_nranks(self):
        return len(self.trainers_endpoints())

    def pods_nranks(self):
        return len(self.pods)

    def trainers_endpoints(self):
        return [t.endpoint for p in self.pods for t in p.trainers]

    def pods_endpoints(self):
        return [f"{p.addr}:{p.port}" for p in self.pods]

    def pod(self, rank):
        for p in self.pods:
            if p.rank == rank:
                return p
        return None


class TrainerProc:
    def __init__(self):
        self.proc = None
        self.log_fn = None
        self.log_offset = None
        self.rank = None
        self.local_rank = None
        self.cmd = None


def get_cluster(node_ips, node_ip, trainer_endpoints, selected_gpus):
    """Build the Cluster/Pod/Trainer topology (reference :305)."""
    assert isinstance(trainer_endpoints, list)
    cluster = Cluster(hdfs=None)
    trainer_rank = 0
    for node_rank, ip in enumerate(node_ips):
        pod = Pod()
        pod.rank = node_rank
        pod.addr = ip
        pod.id = node_rank
        cur_eps = trainer_endpoints[node_rank]
        for i in range(len(selected_gpus)):
            trainer = Trainer()
            trainer.gpus.append(selected_gpus[i])
            trainer.endpoint = cur_eps[i]
            trainer.rank = trainer_rank
            trainer_rank += 1
            pod.trainers.append(trainer)
        cluster.pods.append(pod)
    pod_rank = node_ips.index(node_ip)
    return cluster, cluster.pods[pod_rank]


def get_cluster_from_args(args, selected_gpus):
    node_ips = [ip.strip() for ip in args.cluster_node_ips.split(",")]
    node_ip = args.node_ip
    started_port = getattr(args, "started_port", None)
    # random free ports are only safe when every node can SEE the choice
    # — i.e. single-node with no explicit port (reference semantics);
    # multi-node must agree on started_port arithmetic
    if len(node_ips) == 1 and started_port is None:
        ports = sorted(find_free_ports(len(selected_gpus)))
    else:
        base = started_port if started_port is not None else 6170
        ports = list(range(base, base + len(selected_gpus)))
    eps = [[f"{ip}:{p}" for p in ports] for ip in node_ips]
    return get_cluster(node_ips, node_ip, eps, selected_gpus)


def terminate_local_procs(procs):
    """SIGTERM then SIGKILL stragglers (reference :332)."""
    for p in procs:
        if p.proc is not None and p.proc.poll() is None:
            p.proc.terminate()
            if p.log_fn:
                try:
                    p.log_fn.close()
                except OSError:
                    pass
    deadline = time.time() + 10
    while time.time() < deadline:
        if all(p.proc is None or p.proc.poll() is not None for p in procs):
            return
        time.sleep(0.2)
    for p in procs:
        if p.proc is not None and p.proc.poll() is None:
            try:
                os.kill(p.proc.pid, signal.SIGKILL)
            except OSError:
                pass


def get_host_name_ip():
    try:
        host = socket.gethostname()
        return host, socket.gethostbyname(host)
    except OSError:
        return None


def add_arguments(argname, type, default, help, argparser, **kwargs):
    """argparse helper preserving the reference's call shape."""
    argparser.add_argument(
        "--" + argname, default=default, type=type,
        help=help + f" Default: %(default)s.", **kwargs)


def find_free_ports(num):
    ports, socks = set(), []
    while len(ports) < num:
        s = socket.socket()
        s.bind(("", 0))
        socks.append(s)
        ports.add(s.getsockname()[1])
    for s in socks:
        s.close()
    return ports


def get_logger(log_level=None, name="FLEET"):
    import logging
    logger = logging.getLogger(name)
    # never touch the ROOT logger's level implicitly — setLevel only on
    # an explicit request, and never for the root logger by default
    if log_level is not None:
        logger.setLevel(log_level)
    return logger


def get_gpus(selected_gpus):
    """Reference launch_utils.py:66 parses selected_gpus against
    CUDA_VISIBLE_DEVICES; the TPU analogue resolves device indices
    against TPU_VISIBLE_CHIPS (or the node's device inventory — counted
    without initialising a JAX backend in this launcher process)."""
    visible = os.environ.get("TPU_VISIBLE_CHIPS")
    if visible is None:
        visible = os.environ.get("CUDA_VISIBLE_DEVICES")
    # "" is an explicit ZERO-device set, distinct from unset (None)
    vis = None if visible is None else \
        [int(x) for x in visible.split(",") if x.strip() != ""]
    if selected_gpus is None:
        # relative (local) indices in BOTH branches — same index space
        # as the selected_gpus path below (reference returns
        # range(device_count) here)
        if vis is not None:
            return list(range(len(vis)))
        from paddle_tpu.distributed.launch.context import Device
        return list(range(Device.detect_device().count))
    want = [int(x) for x in str(selected_gpus).split(",")]
    if vis is None:
        return want
    for w in want:
        if w not in vis:
            raise ValueError(
                f"selected device {w} not in visible set {vis}")
    # reference remaps to position within the visible list
    return [vis.index(w) for w in want]


def start_local_trainers(cluster, pod, training_script,
                         training_script_args, log_dir=None):
    """Spawn one worker process per local trainer with the jax.distributed
    bootstrap env (reference :467 sets the NCCL/gloo endpoints; here the
    coordinator/rank/world-size variables distributed.init_parallel_env
    reads)."""
    import subprocess
    import sys
    base_env = dict(os.environ)
    base_env.pop("http_proxy", None)
    base_env.pop("https_proxy", None)
    coordinator = cluster.pods_endpoints()[0]
    world = len(cluster.trainers_endpoints())
    procs = []
    for idx, t in enumerate(pod.trainers):
        env = dict(base_env)
        env.update({
            # read by distributed.init_parallel_env()'s no-arg fallback
            # and launch.py's _from_env — this is the live bootstrap path
            "PADDLE_MASTER": coordinator,
            "PADDLE_NNODES": str(world),
            "PADDLE_TRAINER_ID": str(t.rank),
            "PADDLE_TRAINERS_NUM": str(world),
            "PADDLE_CURRENT_ENDPOINT": t.endpoint,
            "PADDLE_TRAINER_ENDPOINTS": ",".join(
                cluster.trainers_endpoints()),
            # honored by jax.distributed.initialize() autodetect
            "JAX_COORDINATOR_ADDRESS": coordinator,
        })
        cmd = [sys.executable, "-u", training_script] + list(
            training_script_args or [])
        fn = None
        if log_dir is not None:
            os.makedirs(log_dir, exist_ok=True)
            fn = open(os.path.join(log_dir, f"workerlog.{idx}"), "a")
            proc = subprocess.Popen(cmd, env=env, stdout=fn, stderr=fn)
        else:
            proc = subprocess.Popen(cmd, env=env)
        tp = TrainerProc()
        tp.proc = proc
        tp.rank = t.rank
        tp.local_rank = idx
        tp.log_fn = fn
        tp.log_offset = fn.tell() if fn else None
        tp.cmd = cmd
        procs.append(tp)
    return procs


def pull_worker_log(tp):
    """Stream new lines from a trainer's log file (reference :510)."""
    import sys
    if not tp.log_fn:
        return
    # errors="replace": a worker emitting non-UTF-8 bytes (progress bars,
    # locale output) must not crash the watch loop with UnicodeDecodeError
    with open(tp.log_fn.name, "r", errors="replace") as fin:
        fin.seek(tp.log_offset or 0, 0)
        for line in fin:
            try:
                sys.stdout.write(line)
            except UnicodeEncodeError:
                sys.stdout.write(f"<unwritable line; see {tp.log_fn.name}>\n")
        tp.log_offset = fin.tell()


def watch_local_trainers(procs, nranks):
    """Poll trainers: stream rank-0's log, kill the job on any nonzero
    exit, return whether any are still alive (reference :526)."""
    error, error_rank, alive = False, [], False
    for p in procs:
        if p.log_fn and p.local_rank == 0:
            pull_worker_log(p)
        ret = p.proc.poll()
        if ret is None:
            alive = True
        elif ret != 0:
            error = True
            error_rank.append(p.rank)
    if error:
        terminate_local_procs(procs)
        raise RuntimeError(
            f"local trainer ranks {error_rank} exited nonzero; job "
            "terminated")
    return alive
