"""PostSupervisor: spawn + babysit the out-of-process POST worker.

Mirrors the reference's subprocess management (reference
activation/post_supervisor.go:66-299: runCmd spawns the Rust post-service
with its flags, captures logs, restarts it on exit until stopped). The
worker here is this package's own CLI (`python -m spacemesh_tpu.post
serve`), so one binary covers init/prove/verify/serve.

Chip ownership: the worker proves on JAX, so on an accelerator host the
worker is the ONE process that owns the chip — the process that starts a
supervisor must not have opened it (node/app.py start_smeshing refuses
the combination; tools/cluster.py pins its children to the CPU).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time
from pathlib import Path


class PostSupervisor:
    def __init__(self, base_dir: str | Path, listen: str = "127.0.0.1:0",
                 restart_backoff: float = 1.0, env: dict | None = None,
                 params=None, node_address: str | None = None):
        self.base_dir = str(base_dir)
        self.listen = listen
        # gRPC mode (reference topology): worker dials the node's
        # PostService instead of listening (activation/post_supervisor.go
        # passes --address the same way)
        self.node_address = node_address
        self.restart_backoff = restart_backoff
        self.env = env
        self.params = params  # ProofParams for the worker's provers
        self.address: tuple[str, int] | None = None
        self._proc: subprocess.Popen | None = None
        self._stopped = threading.Event()
        self._ready = threading.Event()
        self._thread: threading.Thread | None = None
        self.restarts = -1  # first start is not a restart

    def start(self, timeout: float = 60.0) -> tuple[str, int]:
        """Spawn the worker and wait until it reports its listen port."""
        self._thread = threading.Thread(target=self._babysit, daemon=True)
        self._thread.start()
        if not self._ready.wait(timeout):
            self.stop()
            raise TimeoutError("post worker did not come up")
        if self.node_address is None:
            assert self.address is not None
        return self.address  # None in gRPC dial mode (worker has no port)

    def _spawn(self) -> subprocess.Popen:
        env = dict(os.environ if self.env is None else self.env)
        repo_root = str(Path(__file__).resolve().parent.parent.parent)
        env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
        # every (re)spawned worker shares the checkout's persistent XLA
        # compile cache (utils/accel.py: a fixed in-checkout path, or
        # JAX_COMPILATION_CACHE_DIR, which the env copy above carries) —
        # a crash-restart does not pay the per-shape compile again
        # keep the worker's port stable across restarts so clients reconnect
        listen = self.listen
        if self.address is not None:
            listen = f"{self.address[0]}:{self.address[1]}"
        cmd = [sys.executable, "-u", "-m", "spacemesh_tpu.post", "serve",
               "--data-dir", self.base_dir, "--listen", listen]
        if self.node_address is not None:
            cmd += ["--node-address", self.node_address]
        if self.params is not None:
            cmd += ["--k1", str(self.params.k1), "--k2", str(self.params.k2),
                    "--k3", str(self.params.k3),
                    "--pow-difficulty", self.params.pow_difficulty.hex()]
        return subprocess.Popen(
            cmd, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
            env=env, text=True)

    def _babysit(self) -> None:
        while not self._stopped.is_set():
            self._proc = self._spawn()
            self.restarts += 1
            if self._stopped.is_set():
                # stop() raced our spawn; it may have terminated only the
                # previous proc — reap this one ourselves
                self._proc.terminate()
                self._proc.wait(timeout=10)
                return
            for line in self._proc.stdout:  # type: ignore[union-attr]
                try:
                    ev = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if ev.get("event") == "Serving":
                    self.address = (ev["host"], ev["port"])
                    self._ready.set()
                elif ev.get("event") == "Registering":
                    self._ready.set()
            self._proc.wait()
            if self._stopped.is_set():
                return
            time.sleep(self.restart_backoff)  # crash: restart

    def stop(self) -> None:
        self._stopped.set()
        # _babysit may be mid-restart: keep terminating whatever proc is
        # current until the babysitter thread exits
        for _ in range(5):
            proc = self._proc
            if proc is not None and proc.poll() is None:
                proc.terminate()
                try:
                    proc.wait(timeout=5)
                except subprocess.TimeoutExpired:
                    proc.kill()
            if self._thread is None or not self._thread.is_alive():
                return
            self._thread.join(timeout=3)
            if not self._thread.is_alive():
                return

    def alive(self) -> bool:
        return self._proc is not None and self._proc.poll() is None
