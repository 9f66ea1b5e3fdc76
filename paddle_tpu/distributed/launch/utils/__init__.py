"""Launcher utilities (reference: distributed/launch/utils/ —
kv_server.py KVHandler/KVServer/PKVServer, kv_client.py KVClient,
process_context.py ProcessContext, nvsmi.py Info/get_gpu_info/
get_gpu_process).

The KV server/client are the master's node-discovery store (real
threaded HTTP, stdlib only). nvsmi's GPU probes map to the TPU device
inventory.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
from http.server import HTTPServer, SimpleHTTPRequestHandler

__all__ = ["KVHandler", "KVServer", "PKVServer", "KVClient", "Info",
           "ProcessContext", "get_gpu_info", "get_gpu_process"]


class KVHandler(SimpleHTTPRequestHandler):
    """GET returns the whole scope as JSON; PUT/POST writes a key;
    DELETE removes it (reference kv_server.py:24)."""

    def do_GET(self):
        with self.server.kv_lock:
            scope = {k: v for k, v in self.server.kv.items()
                     if k.startswith(self.path)}
        body = json.dumps({k: v.decode() if isinstance(v, bytes) else v
                           for k, v in scope.items()}).encode()
        self.send_response(200)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def do_PUT(self):
        n = int(self.headers.get("Content-Length", 0))
        value = self.rfile.read(n).decode() if n else ""
        with self.server.kv_lock:
            self.server.kv[self.path] = value
        self.send_response(200)
        self.send_header("Content-Length", "0")
        self.end_headers()

    do_POST = do_PUT

    def do_DELETE(self):
        with self.server.kv_lock:
            self.server.kv.pop(self.path, None)
        self.send_response(200)
        self.send_header("Content-Length", "0")
        self.end_headers()

    def log_message(self, *args):
        pass  # silent


class KVServer(HTTPServer):
    def __init__(self, port):
        super().__init__(("", port), KVHandler)
        self.kv = {}
        self.kv_lock = threading.Lock()
        self.stopped = False

    def start(self):
        self.listen_thread = threading.Thread(target=self.serve_forever,
                                              daemon=True)
        self.listen_thread.start()

    def stop(self):
        self.shutdown()
        self.listen_thread.join()
        self.server_close()
        self.stopped = True


class PKVServer:
    """KVServer in a separate PROCESS (reference kv_server.py:91) so it
    survives the controller's GIL-heavy phases."""

    def __init__(self, port):
        self._port = port
        self._proc = None

    def start(self):
        code = ("from paddle_tpu.distributed.launch.utils import KVServer;"
                f"s = KVServer({self._port}); s.start(); "
                "import time\n"
                "while True: time.sleep(3600)")
        self._proc = subprocess.Popen([sys.executable, "-c", code])

    def stop(self):
        if self._proc:
            self._proc.terminate()
            self._proc.wait(10)

    @property
    def started(self):
        return self._proc is not None and self._proc.poll() is None


class KVClient:
    """stdlib http client for KVServer (reference kv_client.py)."""

    def __init__(self, endpoint="localhost:2379"):
        self.endpoint = (endpoint if endpoint.startswith("http")
                         else f"http://{endpoint}")

    def _request(self, method, key, value=None):
        import urllib.request
        key = key if key.startswith("/") else "/" + key
        req = urllib.request.Request(
            self.endpoint + key, method=method,
            data=value.encode() if value is not None else None)
        try:
            with urllib.request.urlopen(req, timeout=3) as r:
                return r.read().decode()
        except OSError:
            return None

    def put(self, key, value):
        return self._request("PUT", key, value) is not None

    def get(self, key):
        out = self._request("GET", key)
        if out is None:
            return ""
        data = json.loads(out)
        key = key if key.startswith("/") else "/" + key
        return data.get(key, "")

    def get_prefix(self, key):
        out = self._request("GET", key)
        return json.loads(out) if out else {}

    def delete(self, key):
        return self._request("DELETE", key) is not None

    def wait_server_ready(self, timeout=30):
        import time
        deadline = time.time() + timeout
        while time.time() < deadline:
            if self._request("GET", "/") is not None:
                return True
            time.sleep(0.3)
        return False


class Info:
    """Device info record (reference nvsmi.py Info)."""

    def __init__(self, **kwargs):
        self.__dict__.update(kwargs)

    def __repr__(self):
        return json.dumps(self.__dict__)

    def json(self):
        return json.dumps(self.__dict__)

    def dict(self):
        return dict(self.__dict__)


def get_gpu_info(query=None):
    """Accelerator inventory (reference nvsmi.get_gpu_info shells to
    nvidia-smi): reports the node's TPU/CPU devices."""
    from paddle_tpu.distributed.launch.context import Device
    dev = Device.detect_device()
    return [Info(index=str(i), uuid=f"{dev.dtype}-{i}",
                 utilization_gpu="", memory_total="", memory_used="")
            for i in range(dev.count)]


def get_gpu_process(query=None):
    """Processes bound to local accelerators: a chip belongs to one
    process at a time, so at most this process."""
    from paddle_tpu.distributed.launch.context import Device
    dev = Device.detect_device()
    if dev.dtype == "tpu":
        return [Info(pid=os.getpid(), process_name=sys.argv[0],
                     gpu_uuid="tpu-0")]
    return []


class ProcessContext:
    """One worker subprocess with env + log redirection (reference
    process_context.py)."""

    def __init__(self, cmd, env=None, out=None, err=None,
                 preexec_fn=None, shell=False):
        self._cmd = cmd if isinstance(cmd, list) else cmd.split()
        self._env = dict(env or os.environ)
        self._out = out
        self._err = err
        self._preexec_fn = preexec_fn
        self._shell = shell
        self._proc = None
        self._out_fh = self._err_fh = None

    def start(self):
        if self._out:
            os.makedirs(os.path.dirname(self._out) or ".", exist_ok=True)
            self._out_fh = open(self._out, "ab")
        if self._err and self._err != self._out:
            self._err_fh = open(self._err, "ab")
        self._proc = subprocess.Popen(
            self._cmd, env=self._env, shell=self._shell,
            stdout=self._out_fh, stderr=self._err_fh or self._out_fh,
            preexec_fn=self._preexec_fn)
        return self._proc

    def alive(self):
        return self._proc is not None and self._proc.poll() is None

    def exit_code(self):
        return self._proc.poll() if self._proc else None

    def wait(self, timeout=None):
        if self._proc:
            try:
                return self._proc.wait(timeout)
            except subprocess.TimeoutExpired:
                return None

    def terminate(self, force=False):
        if self._proc is None:
            return True
        if self._proc.poll() is None:
            self._proc.kill() if force else self._proc.terminate()
        for fh in (self._out_fh, self._err_fh):
            if fh:
                fh.close()
        return self._proc.poll() is not None


# ---- reference launch/utils/nvsmi.py surface (no nvidia in a TPU
# deployment: honest empty results, never a crash) ----
def has_nvidia_smi():
    import shutil
    return shutil.which("nvidia-smi") is not None


def _smi_rows(fields):
    """Shell out to nvidia-smi when present; [] otherwise (every TPU
    host) — consistent with has_nvidia_smi."""
    if not has_nvidia_smi():
        return []
    import subprocess
    try:
        out = subprocess.run(
            ["nvidia-smi", f"--query-gpu={','.join(fields)}",
             "--format=csv,noheader,nounits"],
            capture_output=True, text=True, timeout=10).stdout
    except Exception:
        return []
    rows = []
    for line in out.strip().splitlines():
        vals = [v.strip() for v in line.split(",")]
        rows.append(dict(zip(fields, vals)))
    return rows


def query_smi(query=None, query_type="gpu", index=None, dtype=None):
    """Reference nvsmi.query_smi: list of per-GPU info dicts."""
    return _smi_rows(query or ["index", "uuid", "name",
                               "memory.total", "memory.used"])


def get_gpu_util(index=None):
    return _smi_rows(["index", "utilization.gpu", "memory.total",
                      "memory.used"])


def get_gpu_info(index=None):
    return _smi_rows(["index", "uuid", "driver_version", "name"])
