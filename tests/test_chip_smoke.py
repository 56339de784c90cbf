"""chip_smoke.py off the chip: the gate, and the phases' own logic.

The chip check itself can only pass on a TPU. What CAN rot between chip
runs is the script: its refusal to run anywhere else (acceptance: with
JAX_PLATFORMS=cpu it exits non-zero before any phase and prints no
result), and the phase code, which runs here on the CPU at a toy scale
by calling ``run_phases`` behind the gate.
"""

import dataclasses
import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent


def test_refuses_to_start_off_the_chip():
    r = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=REPO,
        capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert r.stdout.strip() == "", "a refused run must print no result"
    assert "refusing to start" in r.stderr
    assert "phase" not in r.stderr, "a phase ran before the refusal"


def test_phases_hold_at_toy_scale():
    sys.path.insert(0, str(REPO))
    try:
        import chip_smoke
    finally:
        sys.path.remove(str(REPO))
    # widths stay mainnet's except scrypt N (CPU time); scale is a toy
    dep = dataclasses.replace(
        chip_smoke.Deployment.mainnet(labels_per_unit=512),
        scrypt_n=16, init_batch=256)
    assert (dep.num_units, dep.k1, dep.k2, dep.k3_synced) == (4, 26, 37, 1)
    assert dep.pow_difficulty.hex().startswith("000dfb23b0979b4b")
    report = chip_smoke.run_phases(dep, chip_smoke.CompileClock())
    assert list(report)[:4] == ["init", "prove", "verify", "verifyd"]
    assert report["init"]["labels"] == 2048
    assert report["init"]["checked"]["sampled_labels"] == 64
    assert report["prove"]["checked"]["equals_prove_serial"]
    assert report["verify"]["full"] == [True, False, False]
    assert report["verifyd"]["items"] == 10
    assert report["fallbacks_moved"] == {}
    for name in ("init", "prove", "verify", "verifyd"):
        assert {"wall_s", "compile_s"} <= set(report[name]), name
    for name in ("init", "verify"):
        assert report[name]["decision"]["devices"] == 1, name
        assert report[name]["decision"]["batch"] > 0, name
    assert {"impl", "devices"} <= set(report["prove"]["decision"])
