"""The eleven per-layer readers of the prove cell
(benchmark/layer_metrics/): those that read the prover's span tree over
the small hand-written span list ``benchmark/testdata/prove_spans.json``,
those that read the device trace over a reduction written out by hand;
each value worked out by hand, and nothing to report where there is
nothing to read (no trace, no span, a parent commit's spans)."""

import importlib
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent.parent / "benchmark"

# worked out by hand from the file (its "about" says what the spans are)
FROM_SPANS = {
    # (5,000 - 1,000) and (7,000 - 3,000) us: dispatch less its read wait
    "prove_dispatch_ms": 4.0,
    "prove_upload_ms": 1.0,               # 800 and 1,200 us
    # 1,500 and 2,500 us; the one cut by the window's start does not count
    "prove_retire_ms": 2.0,
    # 1,000 + 3,000 us inside, 1,000 us of the one cut: 5 ms of 2 s
    "prove_read_wait_share": 0.25,
    # 393,216 B a batch over 16,384 labels dispatched (the ragged batch
    # is padded to the full shape: the bytes are sent all the same)
    "prove_h2d_bytes_per_label": 24.0,
    # 900 - 850 = 50 and 1,000 - (400 + 500) = 100; proof 40 is cut
    "prove_fixed_ms": 75.0,
    # retired inside the window: 16,384 + 8,192 labels in 2 s
    "scan_labels_per_s": 12288.0,
}
# the reduction below: busy 0.3 of 2 s; four step programs; the kernel's
# op events 0.12 ms of the programs' 1.2 ms; 2 enqueues x 4 groups = 8
# kernel calls of 16,384 labels x 16 nonces: 640 x 16,384 x 16 ops over
# 393e12 op/s = 0.4269 us a call (the HBM bound, 262,144 B over 819e9 =
# 0.32 us, is the smaller), 8 calls = 3.415 us of 120 us
FROM_TRACE = {
    "pv_device_idle_share": 85.0,
    "scan_step_ms": 0.25,
    "scan_kernel_share": 10.0,
    "scan_roofline": 100.0 * 8 * (640 * 16384 * 16 / 393e12) / 0.00012,
}


def _bench(modname):
    sys.path.insert(0, str(BENCH))
    try:
        return importlib.import_module(modname)
    finally:
        sys.path.remove(str(BENCH))


def _reduction():
    xplane = _bench("lib.xplane")
    return xplane.Reduction(
        window_s=2.0,
        chips=[{"chip": 0, "busy_s": 0.3,
                "programs": {"jit_prove_scan_step_pallas":
                             [0.0002, 0.0003, 0.0002, 0.0005],
                             "jit_pow_hash_batch_jit": [0.004]},
                "ops": {"%_scan_pallas.1 custom-call": 0.00012,
                        "%while.4 while": 0.0005,
                        "%fusion.31 fusion kLoop": 0.0003}}],
        host_spans=[("prove.enqueue", 100.0, 2100.0),
                    ("prove.enqueue", 9000.0, 11000.0),
                    ("prove.retire", 12000.0, 13000.0),
                    ("prove.enqueue", 2e9 + 5, 2e9 + 900)],   # after it
        gaps_by_span={}, window_ns=(0.0, 2e9))


def _facts(doc, reduction=None):
    layers = _bench("lib.layers")
    device = _bench("lib.device")
    return layers.Facts(run=None, reduction=reduction, spans=doc["spans"],
                        counters={}, generator=doc["generator"],
                        peaks=device.peaks("TPU v5 lite"), end_to_end={},
                        run_window_s=doc["run_window_s"])


def _reader(name):
    return _bench(f"layer_metrics.{name}")


@pytest.fixture(scope="module")
def doc():
    with open(BENCH / "testdata" / "prove_spans.json") as f:
        return json.load(f)


@pytest.mark.parametrize("name", sorted(FROM_SPANS))
def test_span_reader_gives_the_value_worked_out_by_hand(doc, name):
    assert _reader(name).read(_facts(doc)) == pytest.approx(
        FROM_SPANS[name], rel=1e-9)


@pytest.mark.parametrize("name", sorted(FROM_TRACE))
def test_trace_reader_gives_the_value_worked_out_by_hand(doc, name):
    got = _reader(name).read(_facts(doc, _reduction()))
    assert got == pytest.approx(FROM_TRACE[name], rel=1e-9)
    if name == "scan_roofline":
        assert 2.8 < got < 2.9      # the op bound holds, far under 100%


@pytest.mark.parametrize("name", sorted(FROM_SPANS) + sorted(FROM_TRACE))
def test_reader_has_nothing_to_report_without_its_source(name):
    empty = {"spans": [], "generator": {}, "run_window_s": 2.0}
    assert _reader(name).read(_facts(empty)) is None


# what the parent commit's prover records: the engine's and the pass's
# spans, none of the stage spans, no count on a retire, no prove.proof
OLD = {"spans": [
    {"name": n, "ts_us": 1000 + 20 * i, "dur_us": 10, "tid": 1,
     "inside": True, "clipped_us": 10,
     "args": {"id": i + 1, "parent": None, "kind": "prove", "window": 0}}
    for i, n in enumerate(("prove.window", "prove.dispatch",
                           "prove.read_wait", "prove.retire",
                           "prove.k2pow"))],
    "generator": {}, "run_window_s": 2.0}
NEW_IN_THIS_TREE = ("prove_upload_ms", "prove_h2d_bytes_per_label",
                    "prove_fixed_ms", "scan_labels_per_s", "scan_roofline")


@pytest.mark.parametrize("name", NEW_IN_THIS_TREE)
def test_reader_is_silent_on_a_parent_commits_spans(name):
    assert _reader(name).read(_facts(OLD, _reduction())) is None


def test_shapes_prove_says_which_bound_holds():
    shapes = _bench("lib.shapes_prove")
    peaks = _bench("lib.device").peaks("TPU v5 lite")
    least = shapes.scan_least_s(16384, 16, peaks)
    assert least["bound"] == "ops" and least["ops"] > least["hbm"]
    assert least["seconds"] == pytest.approx(640 * 16384 * 16 / 393e12)
    assert shapes.scan_bytes(16384) == 256 * 1024
    # one nonce a label: the bytes bind
    assert shapes.scan_least_s(16384, 1, peaks)["bound"] == "hbm"
