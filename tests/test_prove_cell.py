"""The prove cell's contract, on the CPU: ``benchmark/run.py --workload
prove-mainnet.scan --rehearse`` (the tiny copy of the configuration)
ends with one JSON object that a driver can take, traced or not, and a
window in which no request finished is an error that says so, never
``attempted`` 0 (the fault PR 26's cell was refused for)."""

import json
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent
PER_LAYER = {"prove_dispatch_ms", "prove_upload_ms", "prove_retire_ms",
             "prove_read_wait_share", "prove_h2d_bytes_per_label",
             "prove_fixed_ms", "scan_labels_per_s",
             # a proof's edges (ISSUE 36)
             "prove_edge_ms", "prove_host_cpu_ms", "prove_abandoned_share"}


def _run(seconds, trace):
    return subprocess.run(
        [sys.executable, str(ROOT / "benchmark" / "run.py"), "--workload",
         "prove-mainnet.scan", "--seed", "3000000027", "--seconds",
         str(seconds), "--trace", str(trace), "--rehearse"],
        capture_output=True, text=True, timeout=600)


@pytest.mark.parametrize("trace", [0, 1])
def test_last_line_is_the_contracts_object(trace):
    out = _run(6, trace)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert isinstance(line["attempted"], int) and line["attempted"] >= 1
    assert isinstance(line["failed"], int)
    assert 0 <= line["failed"] <= line["attempted"]
    assert line["correct"] is True, out.stderr[-3000:]
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == 1
    metrics = line["metrics"]
    if trace:
        # the span readers report; the device-trace readers find no
        # device plane on the CPU and stay silent
        assert PER_LAYER <= set(metrics), sorted(metrics)
        # 16 of label words and the flight's three start/count words;
        # the rehearsal's store of two batches is one flight of two
        assert metrics["prove_h2d_bytes_per_label"]["value"] \
            == 16.0 + 12 / (2 * 16384)
        assert 0 <= line["device"]["busy_s"] <= line["device"]["window_s"]
        # a thread's CPU time cannot pass its wall time
        assert metrics["prove_host_cpu_ms"]["value"] \
            <= metrics["prove_dispatch_ms"]["value"]
    else:
        assert sorted(metrics) == ["p50_ms", "setup_s"]
        assert metrics["p50_ms"]["value"] > 0
        assert metrics["setup_s"]["unit"] == "s"


def test_a_window_without_a_finished_request_is_an_error():
    out = _run(0.01, 0)
    assert out.returncode != 0
    assert "ended with no finished request" in out.stderr
    assert '"attempted"' not in out.stdout


@pytest.mark.parametrize("lat_ms, p50", [
    ([2800.0], 2800.0),
    ([2800.0, 5600.0], 2800.0),
    ([5600.0, 2810.0, 2790.0], 2810.0),
    # four of eight proofs took a second pass: the midpoint, 4,200 ms,
    # is a latency no request had; rank 4 of 8 is a one-pass proof
    ([2800.0, 5600.0, 2790.0, 5610.0, 2810.0, 5590.0, 2805.0, 5605.0],
     2810.0),
    # five of nine: the median request IS a two-pass proof
    ([5600.0] * 5 + [2800.0] * 4, 5600.0),
])
def test_p50_is_the_latency_of_a_request_that_was_made(lat_ms, p50):
    sys.path.insert(0, str(ROOT / "benchmark"))
    try:
        from drivers import prove_stream
    finally:
        sys.path.remove(str(ROOT / "benchmark"))
    assert prove_stream.median_request(lat_ms) == p50
