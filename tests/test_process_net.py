"""Three separate OS processes form a real TCP network (+ chaos kill).

The round-2 "done" criterion for the transport: the multinode scenario —
smesher A, observers B and C — over real sockets between real processes,
not in-proc loopback. B is SIGKILLed mid-run (chaos, reference
systest/chaos/fail.go); A and C must still converge on ATXs, blocks, and
state roots, read from their state databases after clean exit.
"""

import json
import os
import signal
import socket
import subprocess
import sys
import time
from pathlib import Path

import pytest

from spacemesh_tpu.storage import atxs as atxstore
from spacemesh_tpu.storage import blocks as blockstore
from spacemesh_tpu.storage import db as dbmod
from spacemesh_tpu.storage import layers as layerstore

LPE = 3
LAYER_SEC = 1.0
UNTIL = 8
PREPARE_BUDGET = 50  # seconds for the smesher's POST init + jit warmup

# tier-2: three real OS-process nodes ride wall-clock layer timing —
# minutes per run and flaky on loaded machines; tier-1 skips it
pytestmark = pytest.mark.slow

REPO = Path(__file__).resolve().parent.parent


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _write_config(tmp, name, genesis_time, smesh) -> Path:
    cfg = {
        "data_dir": str(tmp / name),
        "layer_duration": LAYER_SEC,
        "layers_per_epoch": LPE,
        "slots_per_layer": 2,
        "genesis": {"time": genesis_time},
        "post": {"labels_per_unit": 256, "scrypt_n": 2, "k1": 64, "k2": 8,
                 "k3": 4, "min_num_units": 1,
                 "pow_difficulty": "20" + "ff" * 31},
        "smeshing": {"start": smesh, "num_units": 1, "init_batch": 128},
        "hare": {"committee_size": 20, "round_duration": 0.1,
                 "preround_delay": 0.35, "iteration_limit": 2},
        "beacon": {"proposal_duration": 0.1},
        "tortoise": {"hdist": 4, "window_size": 50},
    }
    path = tmp / f"{name}.json"
    path.write_text(json.dumps(cfg))
    return path


def _spawn(cfg_path, listen_port, bootnodes, log_path):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(REPO) + os.pathsep + env.get("PYTHONPATH", "")
    cmd = [sys.executable, "-u", "-m", "spacemesh_tpu.node",
           "--preset", "standalone", "--config", str(cfg_path),
           "--listen", f"127.0.0.1:{listen_port}",
           "--until-layer", str(UNTIL)]
    for bn in bootnodes:
        cmd += ["--bootnode", bn]
    log = open(log_path, "w")
    return subprocess.Popen(cmd, stdout=log, stderr=subprocess.STDOUT,
                            env=env, cwd=str(REPO)), log


@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("procnet")
    genesis = float(int(time.time()) + PREPARE_BUDGET)
    pa, pb, pc = _free_port(), _free_port(), _free_port()
    boot = [f"127.0.0.1:{pa}"]

    procs, logs = {}, {}
    for name, port, bootnodes, smesh in (
            ("a", pa, [], True),
            ("b", pb, boot, False),
            ("c", pc, boot, False)):
        cfg = _write_config(tmp, name, genesis, smesh)
        procs[name], logs[name] = _spawn(cfg, port, bootnodes,
                                         tmp / f"{name}.log")

    # chaos: SIGKILL B in the middle of epoch 1
    kill_at = genesis + LAYER_SEC * (LPE + 1.5)
    time.sleep(max(kill_at - time.time(), 0))
    procs["b"].send_signal(signal.SIGKILL)

    deadline = genesis + LAYER_SEC * UNTIL + 90
    rcs = {}
    try:
        for name in ("a", "c"):
            rcs[name] = procs[name].wait(timeout=max(
                deadline - time.time(), 5))
    finally:
        for name, proc in procs.items():
            if proc.poll() is None:
                proc.kill()
                proc.wait(timeout=10)
        for log in logs.values():
            log.close()

    tail = {n: (tmp / f"{n}.log").read_text()[-2000:] for n in ("a", "c")}
    assert rcs.get("a") == 0, f"node A failed:\n{tail['a']}"
    assert rcs.get("c") == 0, f"node C failed:\n{tail['c']}"
    return tmp


def test_processes_exit_clean_and_converge(cluster):
    tmp = cluster
    sa = dbmod.open_state(tmp / "a" / "state.db")
    sc = dbmod.open_state(tmp / "c" / "state.db")
    try:
        # A's ATXs propagated over real sockets
        atx_rows = atxstore.all_rows(sa)
        assert len(atx_rows) >= 2, "A should publish ATXs for epochs 0+1"
        for row in atx_rows:
            assert atxstore.get(sc, row["id"]) is not None, (
                f"C missing ATX {row['id'].hex()[:12]}")

        # block convergence on every layer that has blocks, excluding the
        # last two: the syncer intentionally defers recent layers whose
        # certificates may still be propagating, and both nodes exit at
        # until_layer — those tip layers can legitimately lag
        layers_with_blocks = [
            lyr for lyr in range(LPE, UNTIL - 1)
            if blockstore.ids_in_layer(sa, lyr)]
        assert layers_with_blocks, "A generated no blocks"
        for lyr in layers_with_blocks:
            ids_a = blockstore.ids_in_layer(sa, lyr)
            ids_c = blockstore.ids_in_layer(sc, lyr)
            assert ids_a == ids_c, f"layer {lyr}: A and C disagree"

        # state root convergence at the last layer both applied
        lyr = min(layerstore.last_applied(sa), layerstore.last_applied(sc))
        assert lyr >= LPE
        assert layerstore.state_hash(sa, lyr) == \
            layerstore.state_hash(sc, lyr), f"state divergence at {lyr}"
    finally:
        sa.close()
        sc.close()


def test_killed_node_left_artifacts_but_not_needed(cluster):
    """B died mid-epoch-1; its DB exists (was syncing) and the survivors
    finished anyway — the chaos didn't stall the network."""
    tmp = cluster
    assert (tmp / "b" / "state.db").exists()
