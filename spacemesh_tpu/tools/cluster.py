"""Systest cluster harness: N subprocess nodes + chaos, one command.

The reference's systest framework spins a cluster in k8s and injects
faults with chaos-mesh (reference systest/cluster/, systest/chaos/
fail.go:31 kill, partition.go:14 iptables split, timeskew.go:12 clock
shift); scenario watchers assert liveness from the public API
(systest/tests/common.go).  Here the cluster is subprocess-per-node over
real TCP + noise, faults ride the admin API (transport chaos_block,
time_offset), and the watchers poll each node's JSON API.

One command:

  python -m spacemesh_tpu.tools.cluster --nodes 6 --smeshers 2 \
      --scenario partition --layers 14

prints a JSON verdict line per scenario phase and exits non-zero on
failure.  The same ``Cluster`` class is the fixture behind
tests/test_cluster_chaos.py.
"""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent.parent


def _reserve_port() -> tuple[socket.socket, int]:
    """Bind-and-HOLD: the socket stays open until just before the node
    spawns, shrinking the reuse window from the whole spinup to the
    node's own startup (ports handed out then instantly released can be
    re-assigned by the OS to another node or process)."""
    s = socket.socket()
    s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    s.bind(("127.0.0.1", 0))
    return s, s.getsockname()[1]


class NodeProc:
    def __init__(self, idx: int, base: Path, smesh: bool):
        self.idx = idx
        self.name = f"node{idx}"
        self.dir = base / self.name
        self.smesh = smesh
        self._port_holds: list[socket.socket] = []
        hold, self.listen_port = _reserve_port()
        self._port_holds.append(hold)
        hold, self.api_port = _reserve_port()
        self._port_holds.append(hold)
        self.proc: subprocess.Popen | None = None
        self.log_path = base / f"{self.name}.log"
        self._log = None

    def release_ports(self) -> None:
        for s in self._port_holds:
            s.close()
        self._port_holds = []

    @property
    def listen(self) -> str:
        return f"127.0.0.1:{self.listen_port}"

    def api(self, path: str, body: dict | None = None, timeout=5.0,
            attempts: int = 4):
        """One API call with transient-failure retries: on a machine
        loaded with N JAX subprocesses a node's accept queue can stall
        for a beat — a single refused connection must not fail a chaos
        scenario."""
        url = f"http://127.0.0.1:{self.api_port}{path}"
        data = json.dumps(body).encode() if body is not None else None
        last: Exception | None = None
        for attempt in range(attempts):
            req = urllib.request.Request(
                url, data=data,
                headers={"Content-Type": "application/json"} if data else {})
            try:
                with urllib.request.urlopen(req, timeout=timeout) as r:
                    return json.loads(r.read())
            except (urllib.error.URLError, OSError, TimeoutError) as e:
                last = e
                if attempt + 1 < attempts:
                    time.sleep(1.0)
        raise last

    def alive(self) -> bool:
        return self.proc is not None and self.proc.poll() is None


class Cluster:
    """Spin N nodes (the first ``smeshers`` of them smeshing), watch and
    shake them."""

    def __init__(self, base_dir: str | Path, n: int, smeshers: int = 1,
                 layer_sec: float = 1.5, lpe: int = 3,
                 spinup: float = 75.0, until_layer: int | None = None,
                 hare_round: float = 0.1):
        self.base = Path(base_dir)
        self.base.mkdir(parents=True, exist_ok=True)
        self.layer_sec = layer_sec
        self.lpe = lpe
        self.spinup = spinup
        self.until_layer = until_layer
        self.hare_round = hare_round
        self.genesis_time: float | None = None
        self.nodes = [NodeProc(i, self.base, i < smeshers)
                      for i in range(n)]

    # -- lifecycle ----------------------------------------------------

    def _config(self, node: NodeProc) -> Path:
        cfg = {
            "data_dir": str(node.dir),
            "layer_duration": self.layer_sec,
            "layers_per_epoch": self.lpe,
            "slots_per_layer": 2,
            "genesis": {"time": self.genesis_time},
            "post": {"labels_per_unit": 256, "scrypt_n": 2, "k1": 64,
                     "k2": 8, "k3": 4, "min_num_units": 1,
                     "pow_difficulty": "20" + "ff" * 31},
            "smeshing": {"start": node.smesh, "num_units": 1,
                         "init_batch": 128},
            "hare": {"committee_size": 20,
                     "round_duration": self.hare_round,
                     "preround_delay": 0.35, "iteration_limit": 2},
            "beacon": {"proposal_duration": 0.1},
            "tortoise": {"hdist": 4, "window_size": 50},
            "api": {"private_listener": f"127.0.0.1:{node.api_port}"},
        }
        path = self.base / f"{node.name}.json"
        path.write_text(json.dumps(cfg))
        return path

    def start(self) -> None:
        # one shared genesis AFTER every node's prepare budget — per-node
        # "now" genesis would put them on different networks
        self.genesis_time = time.time() + self.spinup
        boot = self.nodes[0].listen
        for node in self.nodes:
            cfg_path = self._config(node)
            env = dict(os.environ)
            # N node processes on one host: none may claim an accelerator
            # (a chip belongs to one process)
            env["JAX_PLATFORMS"] = "cpu"
            env["PYTHONPATH"] = str(REPO) + os.pathsep + \
                env.get("PYTHONPATH", "")
            # every node compiles the same tiny POST shapes; they share
            # the checkout's persistent XLA cache (utils/accel.py — the
            # node enables it itself inside initialize())
            cmd = [sys.executable, "-u", "-m", "spacemesh_tpu.node",
                   "--preset", "standalone", "--config", str(cfg_path),
                   "--listen", node.listen, "--api"]
            if node.idx > 0:
                cmd += ["--bootnode", boot]
            if self.until_layer is not None:
                cmd += ["--until-layer", str(self.until_layer)]
            node._log = open(node.log_path, "w")
            node.release_ports()  # the node binds them itself now
            node.proc = subprocess.Popen(
                cmd, stdout=node._log, stderr=subprocess.STDOUT, env=env,
                cwd=str(REPO))

    def stop(self) -> None:
        for node in self.nodes:
            if node.alive():
                node.proc.terminate()
        deadline = time.time() + 15
        for node in self.nodes:
            if node.proc is not None:
                try:
                    node.proc.wait(max(0.1, deadline - time.time()))
                except subprocess.TimeoutExpired:
                    node.proc.kill()
            if node._log:
                node._log.close()

    # -- watchers (public API only, like systest/tests/common.go) -----

    def wait_api(self, timeout: float = 120.0) -> None:
        deadline = time.time() + timeout
        pending = list(self.nodes)
        while pending and time.time() < deadline:
            pending = [n for n in pending if not self._api_up(n)]
            time.sleep(0.5)
        if pending:
            raise TimeoutError(
                f"API never came up on {[n.name for n in pending]}")

    @staticmethod
    def _api_up(node: NodeProc) -> bool:
        try:
            node.api("/v1/node/status", attempts=1)  # polled: no retry
            return True
        except (urllib.error.URLError, OSError, TimeoutError):
            return False

    def wait_layer(self, layer: int, timeout: float = 120.0,
                   nodes: list[NodeProc] | None = None) -> None:
        deadline = time.time() + timeout
        for node in nodes or self.nodes:
            while True:
                if not node.alive():
                    # a node that EXITED CLEANLY ran through its
                    # configured until_layer — if that covers the
                    # requested layer, it reached it (its API is just
                    # gone); anything else is a real death
                    if node.proc is not None and node.proc.poll() == 0 \
                            and self.until_layer is not None \
                            and self.until_layer >= layer:
                        break
                    raise RuntimeError(f"{node.name} died "
                                       f"(log: {node.log_path})")
                try:
                    st = node.api("/v1/node/status")["status"]
                    if st["top_layer"] >= layer:
                        break
                except (urllib.error.URLError, OSError, TimeoutError):
                    pass
                if time.time() > deadline:
                    raise TimeoutError(
                        f"{node.name} never reached layer {layer}")
                time.sleep(self.layer_sec / 3)

    def state_hashes(self, layer: int,
                     nodes: list[NodeProc] | None = None) -> dict[str, str]:
        out = {}
        for node in nodes or self.nodes:
            info = node.api(f"/v1/mesh/layer/{layer}")
            out[node.name] = info.get("state_hash")
        return out

    def db_state_hashes(self, layer: int,
                        nodes: list[NodeProc] | None = None
                        ) -> dict[str, str | None]:
        """State hashes straight from each node's state.db — the
        post-mortem convergence check once nodes have exited cleanly
        and their APIs are gone (WAL files persist the applied state)."""
        from ..storage import db as dbmod
        from ..storage import layers as layerstore

        out: dict[str, str | None] = {}
        for node in nodes or self.nodes:
            d = dbmod.open_state(node.dir / "state.db")
            try:
                h = layerstore.state_hash(d, layer)
                out[node.name] = h.hex() if h else None
            finally:
                d.close()
        return out

    def converged(self, layer: int,
                  nodes: list[NodeProc] | None = None) -> bool:
        hashes = self.state_hashes(layer, nodes)
        vals = set(hashes.values())
        return len(vals) == 1 and None not in vals

    def wait_converged(self, layer: int, timeout: float = 90.0,
                       nodes: list[NodeProc] | None = None) -> None:
        deadline = time.time() + timeout
        while time.time() < deadline:
            try:
                if self.converged(layer, nodes):
                    return
            except (urllib.error.URLError, OSError, TimeoutError):
                pass
            time.sleep(self.layer_sec / 2)
        raise TimeoutError(
            f"no convergence at layer {layer}: {self.state_hashes(layer, nodes)}")

    # -- chaos (reference systest/chaos/) -----------------------------

    def partition(self, *groups: list[NodeProc]) -> None:
        """Split the cluster: every node blocks every node outside its
        group (chaos/partition.go:14)."""
        for group in groups:
            others = [n.listen for n in self.nodes if n not in group]
            for node in group:
                if node.alive():
                    node.api("/v1/admin/chaos/block", {"addrs": others})

    def heal(self) -> None:
        for node in self.nodes:
            if node.alive():
                node.api("/v1/admin/chaos/clear", {})

    def timeskew(self, node: NodeProc, offset: float) -> None:
        """Shift one node's clock (chaos/timeskew.go:12)."""
        node.api("/v1/admin/chaos/timeskew", {"offset": offset})

    def kill(self, node: NodeProc) -> None:
        """SIGKILL, no shutdown (chaos/fail.go:31)."""
        if node.alive():
            node.proc.send_signal(signal.SIGKILL)
            node.proc.wait(10)


# -- scenarios -------------------------------------------------------------


def scenario_partition(c: Cluster, report) -> None:
    c.wait_layer(2 * c.lpe, timeout=c.spinup + 2 * c.lpe * c.layer_sec + 120)
    half = len(c.nodes) // 2
    a, b = c.nodes[:half], c.nodes[half:]
    c.partition(a, b)
    report("partitioned", groups=[len(a), len(b)])
    split_until = 3 * c.lpe
    c.wait_layer(split_until, timeout=120)
    c.heal()
    report("healed", at_layer=split_until)
    target = split_until + c.lpe
    c.wait_layer(target + 2, timeout=180)
    c.wait_converged(target, timeout=180)
    report("converged", layer=target)


def scenario_timeskew(c: Cluster, report) -> None:
    c.wait_layer(c.lpe, timeout=c.spinup + c.lpe * c.layer_sec + 120)
    victim = c.nodes[-1]
    c.timeskew(victim, 3 * c.layer_sec)
    report("skewed", node=victim.name, offset=3 * c.layer_sec)
    c.wait_layer(2 * c.lpe + 1, timeout=120)
    c.timeskew(victim, 0.0)
    report("unskewed", node=victim.name)
    target = 3 * c.lpe
    c.wait_layer(target + 1, timeout=120)
    c.wait_converged(target, timeout=120)
    report("converged", layer=target)


def scenario_kill(c: Cluster, report) -> None:
    c.wait_layer(c.lpe, timeout=c.spinup + c.lpe * c.layer_sec + 120)
    victim = c.nodes[-1]
    c.kill(victim)
    report("killed", node=victim.name)
    survivors = [n for n in c.nodes if n is not victim]
    target = 2 * c.lpe + 2
    c.wait_layer(target + 1, timeout=120, nodes=survivors)
    c.wait_converged(target, timeout=120, nodes=survivors)
    report("converged_without_victim", layer=target)


SCENARIOS = {"partition": scenario_partition,
             "timeskew": scenario_timeskew,
             "kill": scenario_kill}


def main(argv=None) -> int:
    import argparse

    p = argparse.ArgumentParser(prog="spacemesh_tpu.tools.cluster")
    p.add_argument("--nodes", type=int, default=6)
    p.add_argument("--smeshers", type=int, default=2)
    p.add_argument("--scenario", choices=[*SCENARIOS, "all"],
                   default="partition")
    p.add_argument("--base-dir", default=None)
    p.add_argument("--layer-sec", type=float, default=1.5)
    p.add_argument("--spinup", type=float, default=75.0)
    a = p.parse_args(argv)

    import tempfile

    base = a.base_dir or tempfile.mkdtemp(prefix="smcluster-")
    names = list(SCENARIOS) if a.scenario == "all" else [a.scenario]
    rc = 0
    for name in names:
        c = Cluster(Path(base) / name, a.nodes, smeshers=a.smeshers,
                    layer_sec=a.layer_sec, spinup=a.spinup)

        def report(phase, **kw):
            print(json.dumps({"scenario": name, "phase": phase, **kw}),
                  flush=True)

        c.start()
        try:
            c.wait_api(timeout=a.spinup + 120)
            report("api_up")
            SCENARIOS[name](c, report)
            report("PASS")
        except Exception as e:  # noqa: BLE001 — verdict, not traceback
            report("FAIL", error=f"{type(e).__name__}: {e}")
            rc = 1
        finally:
            c.stop()
    return rc


if __name__ == "__main__":
    sys.exit(main())
