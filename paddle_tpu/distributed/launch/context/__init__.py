"""Launch context (reference: distributed/launch/context/__init__.py
Context + node.py Node + device.py Device/DeviceType + resource.py,
status.py, event.py).

The context gathers CLI args, PADDLE_* env, and the node's device
inventory; controllers consume it to build the pod.
"""
from __future__ import annotations

import argparse
import glob
import logging
import os
import socket

__all__ = ["Context", "Node", "Device", "DeviceType", "Event", "Resource",
           "Status", "fetch_envs"]


class DeviceType:
    CPU = "cpu"
    GPU = "gpu"
    XPU = "xpu"
    NPU = "npu"
    IPU = "ipu"
    TPU = "tpu"


# where the TPU driver exposes one node per chip (accel: v2-v4;
# vfio: v5e and later)
_TPU_DEVICE_NODES = ("/dev/accel[0-9]*", "/dev/vfio/[0-9]*")


class Device:
    """Node-local accelerator inventory (reference context/device.py).

    Counted WITHOUT initialising a JAX backend: the launcher is the
    parent of the workers that need the chips, and a chip belongs to one
    process at a time — a parent that had asked jax would hold them.
    Detection prefers TPU_VISIBLE_CHIPS, then the chips' device nodes; a
    host with neither is a CPU host.  ``--devices`` selects among them."""

    def __init__(self, dtype=None, count=1, memory="", labels=None):
        self.dtype = dtype
        self.count = count
        self.memory = memory
        self.labels = labels or []

    @classmethod
    def detect_device(cls):
        visible = os.environ.get("TPU_VISIBLE_CHIPS")
        if visible is not None:
            labels = [x for x in visible.split(",") if x.strip() != ""]
            return cls(DeviceType.TPU, len(labels), labels=labels)
        for pattern in _TPU_DEVICE_NODES:
            n = len(glob.glob(pattern))
            if n:
                return cls(DeviceType.TPU, n,
                           labels=[str(i) for i in range(n)])
        return cls(DeviceType.CPU, 1, labels=["0"])

    def get_selected_device_key(self):
        return {DeviceType.TPU: "TPU_VISIBLE_CHIPS",
                DeviceType.GPU: "CUDA_VISIBLE_DEVICES"}.get(
                    self.dtype, "CPU_NUM")

    def get_selected_devices(self, devices=""):
        if devices:
            return [str(x) for x in devices.split(",")]
        return [str(x) for x in self.labels]


class Node:
    """This host (reference context/node.py): ip + device inventory +
    free-port allocation."""

    def __init__(self):
        self.ip = self._get_host_ip()
        self.device = Device.detect_device()
        self.free_ports = []

    @staticmethod
    def _get_host_ip():
        try:
            return socket.gethostbyname(socket.gethostname())
        except OSError:
            return "127.0.0.1"

    def get_free_port(self):
        from paddle_tpu.distributed.utils import find_free_ports
        port = sorted(find_free_ports(1))[0]
        self.free_ports.append(port)
        return port


class Status:
    UNINIT = "uninit"
    READY = "ready"
    RUNNING = "running"
    FAILED = "failed"
    TERMINATING = "terminating"
    RESTARTING = "restarting"
    UNKNOWN = "unknown"
    COMPLETED = "completed"

    def __init__(self):
        self._current_status = self.UNINIT

    def current(self):
        return self._current_status

    def is_running(self):
        return self._current_status == self.RUNNING

    def is_restarting(self):
        return self._current_status == self.RESTARTING

    def is_done(self):
        return self._current_status in (self.COMPLETED, self.FAILED)

    def run(self):
        self._current_status = self.RUNNING

    def fail(self):
        self._current_status = self.FAILED

    def complete(self):
        self._current_status = self.COMPLETED

    def restart(self):
        self._current_status = self.RESTARTING

    def done(self):
        self._current_status = self.COMPLETED


class Event:
    def __init__(self, kind="status", message="", fatal=False):
        self.kind = kind
        self.message = message
        self.fatal = fatal


class Resource:
    def __init__(self, devices=None):
        self.devices = devices or []


def fetch_envs():
    """Full environment snapshot minus proxies (reference context copies
    os.environ; workers NEED PATH/HOME/PYTHONPATH/LD_LIBRARY_PATH — a
    prefix-filtered env would strand every spawned trainer)."""
    env = dict(os.environ)
    env.pop("http_proxy", None)
    env.pop("https_proxy", None)
    return env


def parse_args(argv=None):
    """THE launch CLI — one parser shared by `python -m ...launch`
    (launch/__init__.py main) and Context, so the flag surface cannot
    drift between the two."""
    p = argparse.ArgumentParser(prog="paddle_tpu.distributed.launch",
                                allow_abbrev=False)
    p.add_argument("--master", default=None,
                   help="coordinator host:port (rank 0)")
    p.add_argument("--nnodes", type=str, default=None,
                   help="node count N, or elastic range N:M")
    p.add_argument("--rank", type=int, default=None,
                   help="this node's rank")
    p.add_argument("--nproc_per_node", type=int, default=None)
    p.add_argument("--log_dir", default=None)
    p.add_argument("--log_level", default="INFO")
    p.add_argument("--run_mode", default="collective")
    p.add_argument("--job_id", default="default")
    p.add_argument("--devices", "--gpus", default=None)
    p.add_argument("--ips", default=None)
    p.add_argument("--legacy", action="store_true")
    p.add_argument("--watchdog-timeout", type=float, default=None)
    p.add_argument("training_script", nargs="?", default=None)
    p.add_argument("training_script_args", nargs=argparse.REMAINDER)
    return p.parse_known_args(argv)


class Context:
    """Everything a controller needs (reference context/__init__.py:24):
    args + env snapshot + node inventory + status + logger."""

    def __init__(self, enable_plugin=True, argv=None):
        self.args, self.unknown_args = parse_args(argv)
        self.envs = fetch_envs()
        self.node = Node()
        self.status = Status()
        self.logger = self.get_logger()
        self.events = []
        if enable_plugin:
            self._enable_plugin()

    def get_envs(self):
        return self.envs.copy()

    def set_envs(self, env=None):
        self.envs.update({k: v for k, v in (env or {}).items()
                          if isinstance(v, str)})

    def is_legacy_mode(self):
        return bool(self.args.legacy)

    def get_logger(self, level=logging.INFO):
        logger = logging.getLogger("LAUNCH")
        logger.setLevel(getattr(logging,
                                str(self.args.log_level).upper(), level))
        if not logger.handlers:
            ch = logging.StreamHandler()
            ch.setFormatter(logging.Formatter(
                fmt="%(name)s %(levelname)s %(asctime)s %(message)s"))
            logger.addHandler(ch)
        return logger

    def print(self):
        self.logger.info("-----------  Configuration  ------------------")
        for arg, value in sorted(vars(self.args).items()):
            self.logger.info("%s: %s", arg, value)
        self.logger.info("----------------------------------------------")

    def _enable_plugin(self):
        from paddle_tpu.distributed.launch import plugins
        for pl in plugins.enabled_plugins:
            pl(self)

    def continous_log(self):
        return str(self.args.log_level).upper() in ("DEBUG", "ERROR")
